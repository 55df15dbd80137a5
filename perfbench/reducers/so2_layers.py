"""The device time of the one piece only the Solar-Open2 stack names
(``fedtpu/ops/scopes.py``): the sigmoid gate on the grouped-query layer's
context (``attn_gate`` inside ``attention``: its projection ``W_g x``, the
sigmoid and the product; forward, recomputed and backward), from the
``program_scopes`` event's ``pieces``. The operations are ``attention``'s
and outside its core, so ``so2_attn_gate_ms`` is a part of ``attn_proj_ms``,
an OVERLAPPING sum as ``x4_attn_latent_ms`` is. ``lm_layers``' rule: an
operation's self time (a ``while`` less what its body covers), averaged over
the devices, per traced round, in milliseconds; operations whose middle lies
inside the loop's check annotations are the state check's and are left out.
A program that names no such piece (any other model's, a parent's) gives
nothing.
"""

from perfbench.reducers.lm_layers import CHECKS

PIECES = {"attn_gate": "so2_attn_gate_ms"}
# every field ``reduce`` can give: what a metric's file may name
EMITS = tuple(PIECES.values())


def reduce(ev):
    view, rounds = ev.trace, ev.facts.get("trace_rounds")
    pieces = {}
    for e in ev.sinks.get("job") or []:
        if (e.get("kind") == "program_scopes"
                and e["payload"].get("program") != "state_check"):
            pieces.update(e["payload"].get("pieces") or {})
    if (not view or not view.devices or not rounds
            or not set(pieces.values()) & set(PIECES)):
        return {}
    checks = [(h.start, h.end) for h in view.host if h.name in CHECKS]
    acc = dict.fromkeys(PIECES.values(), 0.0)
    for ops in view.devices.values():
        for o in ops:
            field = PIECES.get(pieces.get(o.name))
            middle = (o.start + o.end) / 2
            if field and not any(s <= middle <= e for s, e in checks):
                acc[field] += o.self_ns
    per_ms = 1e-6 / rounds / len(view.devices)
    return {name: per_ms * ns for name, ns in acc.items()}
