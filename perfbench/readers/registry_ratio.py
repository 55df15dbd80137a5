"""One counter of the program's registry over another, from the last
``counters`` event of the sink (reader ``registry`` reads each):
``{"kind": "registry_ratio", "name": <a counter>, "over": <a counter>,
"sink": "job"}``. Nothing where either is missing or the second is zero."""

from perfbench.readers import registry


def read(spec, ev):
    one = lambda name: registry.read(
        {"section": "counters", "name": name, "sink": spec.get("sink", "job")},
        ev)
    value, over = one(spec["name"]), one(spec["over"])
    return value / over if value is not None and over else None
