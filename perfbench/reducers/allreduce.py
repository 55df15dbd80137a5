"""All-reduce time of a round and the part of it no compute hid."""

from perfbench import xplane


def reduce(ev):
    rounds = ev.facts.get("trace_rounds")
    tot, exposed = xplane.collectives(ev.trace, "all-reduce")
    if tot <= 0 or not rounds:
        return {}
    return {"allreduce_ms": 1000.0 * tot / rounds,
            "allreduce_exposed_ms": 1000.0 * exposed / rounds}
