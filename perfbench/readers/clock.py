"""A clock the harness itself held: ``{"kind": "clock", "clock": <name>}``."""


def read(spec, ev):
    return ev.clocks.get(spec["clock"])
