"""Backend compiles heard on ``jax.monitoring`` in one phase of the run:
``{"kind": "monitoring", "phases": ["setup", "warmup"] | ["job"], "stat":
"count" | "seconds" | "hits"}``."""


def read(spec, ev):
    log, phases = ev.compiles, spec["phases"]
    if log is None or not set(phases) <= set(ev.facts.get("phases", ())):
        return None
    return {"count": log.count, "seconds": log.seconds,
            "hits": log.hit_count}[spec["stat"]](*phases)
