"""Events of the program's own sink (``fedtpu.telemetry.trace``), one JSON
line each: ``{"kind": "span", "event": "span" | "round" | ..., "phase":
<name or absent>, "stat": "sum" | "median" | "count", "per_round": bool,
"skip_first": n, "sink": "job"}``. ``per_round`` divides each duration by
the rounds its payload says it covered; ``skip_first`` leaves out the first
n events (the chunk that compiled)."""

import statistics


def read(spec, ev):
    events = ev.sinks.get(spec.get("sink", "job"))
    if not events:
        return None
    picked = [e for e in events
              if e.get("kind") == spec.get("event", "span")
              and ("phase" not in spec or e.get("phase") == spec["phase"])]
    picked = picked[int(spec.get("skip_first", 0)):]
    if not picked:
        return None
    durs = [e["dur_s"] / (e["payload"].get("rounds", 1)
                          if spec.get("per_round") else 1) for e in picked]
    return {"sum": sum, "median": statistics.median,
            "count": len}[spec["stat"]](durs)
