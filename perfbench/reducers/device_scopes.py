"""The device time of a round, put down to the stage of the round program
that each operation belongs to.

A trace names an operation by its HLO text and carries no ``op_name``, so the
program says which operation is whose: with its sink on and a profile
configured it emits one ``program_scopes`` event per compiled program (fedtpu
``analysis/program.py``), whose ``scopes`` map ``"<instruction> <first result
shape>"`` — what ``xplane.short`` makes of a trace event's name — to the
``jax.named_scope`` stage, and whose ``unscoped`` list the operations that
carry none. Self time of every operation (a ``while`` counts for what its
body does not cover), averaged over the devices, summed by stage, per traced
round, in milliseconds; an operation no event lists goes to ``unscoped_ms``
too. A sink without such an event gives nothing.

Two programs of one window can list the same key (``fusion.3 pred[]`` of the
round program and of the state check). The loop's annotations are on the
operations' clock: the check's program runs inside ``fedtpu.state_check``,
which follows ``fedtpu.stop_check``, during which the device has nothing of
the loop's to do. An operation whose midpoint lies in either is looked up in
the state check's event first, and every other in the round program's first.
(Both annotations, because the profiler puts the device's clock early against
the host's, by 0.2-2.5 ms and differently each run: the check's operations
read as starting 0.15-1.3 ms before ``fedtpu.state_check`` opens, PR 23.)

An executable served from the persistent cache carries the metadata of the
checkout that compiled it first, and the program then marks its event
``stale_metadata`` (no stage named in the text). The stages still read what
the executable that ran says, which is nothing, so all its time is
``unscoped_ms``; the run's ``notes`` say why, so that the reading is not
taken for the program's. The metrics are not left out: a traced run has to
report every per-layer metric that has no ``workloads`` list.
"""

# The metrics' names (layer_metrics/*.json), which are the program's stage
# names; not imported from it, since a parent without them is read too.
STAGES = ("client_train", "client_eval", "aggregate", "metrics", "state_check")
CHECKS = ("fedtpu.stop_check", "fedtpu.state_check")


def reduce(ev):
    view, rounds = ev.trace, ev.facts.get("trace_rounds")
    events = [e["payload"] for e in ev.sinks.get("job") or []
              if e.get("kind") == "program_scopes" and "scopes" in e["payload"]]
    if not view.devices or not rounds or not events:
        return {}
    stale = sorted({p.get("program") for p in events if p.get("stale_metadata")})
    if stale:
        ev.notes["device_scopes"] = (
            f"program_scopes of {', '.join(map(str, stale))}: the executable "
            "came from the persistent cache with another checkout's metadata "
            "and names no stage; its device time reads unscoped_ms")
    of_check, of_round = {}, {}
    for payload in events:
        (of_check if payload.get("program") == "state_check"
         else of_round).update(payload["scopes"])
    checks = [(h.start, h.end) for h in view.host if h.name in CHECKS]

    def stage(op):
        middle = (op.start + op.end) / 2
        inside = any(s <= middle <= e for s, e in checks)
        first, then = (of_check, of_round) if inside else (of_round, of_check)
        return first.get(op.name) or then.get(op.name)

    acc = dict.fromkeys(STAGES + ("unscoped",), 0.0)
    for ops in view.devices.values():
        for o in ops:
            name = stage(o)
            acc[name if name in acc else "unscoped"] += o.self_ns
    per_ms = 1e-6 / rounds / len(view.devices)
    out = {f"{name}_ms": per_ms * ns for name, ns in acc.items()}
    out["state_check_device_ms"] = out.pop("state_check_ms")
    return out
