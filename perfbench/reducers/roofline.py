"""Share of the roofline the round program reaches: the least time at the
published peaks for the round's operations and compulsory bytes (flops.py),
over the device time of a round. Which peak bounds is computed, and noted."""

from perfbench import flops


def reduce(ev):
    round_ms = ev.reduced("device_busy").get("device_round_ms")
    if not round_ms:
        return {}
    pct, bound = flops.roofline(ev.facts["cost"], ev.facts["peaks"],
                                ev.facts["chips"], round_ms / 1000.0)
    ev.notes["roofline_bound"] = bound
    return {"round_roofline": pct}
