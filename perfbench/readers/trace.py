"""A field of a trace reducer: ``{"kind": "trace", "reducer": <module under
reducers/>, "field": <key of what it returns>}``."""


def read(spec, ev):
    if ev.trace is None:
        return None
    return ev.reduced(spec["reducer"]).get(spec["field"])
