"""A counter of the program's registry, from the last ``counters`` event of
the sink: ``{"kind": "counter", "counter": <name>, "sink": "job"}``."""


def read(spec, ev):
    events = ev.sinks.get(spec.get("sink", "job")) or []
    snaps = [e for e in events if e.get("kind") == "counters"]
    if not snaps:
        return None
    payload = snaps[-1]["payload"]
    value = payload.get("counters", payload).get(spec["counter"])
    return value if isinstance(value, (int, float)) else None
