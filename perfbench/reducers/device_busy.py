"""Busy union of the device over the captured window, per round."""

from perfbench import xplane


def reduce(ev):
    busy = xplane.busy_s(ev.trace)
    rounds = ev.facts.get("trace_rounds")
    if busy <= 0 or not rounds:
        return {}
    return {"busy_s": busy, "window_s": ev.trace.window_s,
            "device_round_ms": 1000.0 * busy / rounds,
            "device_idle_pct": 100.0 * (1.0 - busy / ev.trace.window_s)}
