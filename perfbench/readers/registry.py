"""A value of the program's registry, from the last ``counters`` event of the
sink, counter or gauge: ``{"kind": "registry", "section": "counters" |
"gauges", "name": <name>, "over_fact": <a key of ev.facts>, "sink": "job"}``.
``over_fact`` divides by a number the driver put among the run's facts (the
positions the traced job computed, for a share of them)."""


def read(spec, ev):
    events = ev.sinks.get(spec.get("sink", "job")) or []
    snaps = [e for e in events if e.get("kind") == "counters"]
    if not snaps:
        return None
    value = snaps[-1]["payload"].get(spec["section"], {}).get(spec["name"])
    if not isinstance(value, (int, float)):
        return None
    if "over_fact" in spec:
        over = ev.facts.get(spec["over_fact"])
        return value / over if over else None
    return value
