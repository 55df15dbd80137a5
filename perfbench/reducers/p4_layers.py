"""The device time of the decoder-hybrid-decoder round by the pieces and the
modules only that stack names (``fedtpu/ops/scopes.py``): the four parts of
a Mamba-1 mixer (``s6_proj``, ``s6_conv``, ``s6_scan``, ``s6_gate``) and the
Gated Memory Unit (``gmu``) inside ``ssm``, the combination of differential
attention's two softmaxes (``diff_combine``) inside ``attention``, the meeting
of the tied embedding's two gradients (``tied_embed_grad``) inside ``embed``,
from the ``program_scopes`` event's ``pieces``; and everything under the
outer scopes ``attn_window`` and ``attn_cross`` (projections, core,
combination: an OVERLAPPING sum with ``attention_ms``, as ``x4_mtp_ms`` is)
from its ``modules``. ``lm_layers``' rule: an operation's self time (a
``while`` less what its body covers), averaged over the devices, per traced
round, in milliseconds; operations whose middle lies inside the loop's check
annotations are the state check's and are left out.

``p4_s6_scan_roofline``: the scans' least time at the chip's peaks (the
larger of ``cost.scan``'s operations over the bf16 peak and its bytes over
the memory's: ``flops_phi4_flash.scan_cost``) over ``p4_s6_scan_ms``; which
of the two bounds goes to the notes. A program that names none of these (any
other model's, a parent's) gives nothing.
"""

from perfbench.reducers.lm_layers import CHECKS

PIECES = {"s6_proj": "p4_s6_proj_ms", "s6_conv": "p4_s6_conv_ms",
          "s6_scan": "p4_s6_scan_ms", "s6_gate": "p4_s6_gate_ms",
          "gmu": "p4_gmu_ms", "diff_combine": "p4_diff_combine_ms",
          "tied_embed_grad": "p4_tied_embed_grad_ms"}
MODULES = {"attn_window": "p4_attn_window_ms",
           "attn_cross": "p4_attn_cross_ms"}
# every field ``reduce`` can give: what a metric's file may name
EMITS = (*PIECES.values(), *MODULES.values(), "p4_s6_scan_roofline")


def reduce(ev):
    view, rounds = ev.trace, ev.facts.get("trace_rounds")
    events = [e["payload"] for e in ev.sinks.get("job") or []
              if e.get("kind") == "program_scopes"
              and e["payload"].get("program") != "state_check"]
    pieces, modules = {}, {}
    for payload in events:
        pieces.update(payload.get("pieces") or {})
        modules.update(payload.get("modules") or {})
    named = (set(pieces.values()) & set(PIECES)
             or set(modules.values()) & set(MODULES))
    if not view or not view.devices or not rounds or not named:
        return {}
    checks = [(h.start, h.end) for h in view.host if h.name in CHECKS]
    acc = dict.fromkeys([*PIECES.values(), *MODULES.values()], 0.0)
    for ops in view.devices.values():
        for o in ops:
            middle = (o.start + o.end) / 2
            if any(s <= middle <= e for s, e in checks):
                continue
            for found, fields in ((pieces, PIECES), (modules, MODULES)):
                field = fields.get(found.get(o.name))
                if field:
                    acc[field] += o.self_ns
    per_ms = 1e-6 / rounds / len(view.devices)
    out = {name: per_ms * ns for name, ns in acc.items()}
    cost, peaks = ev.facts.get("cost") or {}, ev.facts.get("peaks") or {}
    chips = ev.facts.get("chips", 1)
    scan = cost.get("scan")
    if (out["p4_s6_scan_ms"] > 0 and scan
            and peaks.get("bf16_flops_per_s") and peaks.get("hbm_bytes_per_s")):
        by = {"flops": scan["flops"] / (chips * peaks["bf16_flops_per_s"]),
              "bytes": scan["bytes"] / (chips * peaks["hbm_bytes_per_s"])}
        bound = max(by, key=by.get)
        ev.notes["p4_s6_scan_roofline_bound"] = bound
        out["p4_s6_scan_roofline"] = (100.0 * by[bound]
                                      / (out["p4_s6_scan_ms"] / 1000.0))
    return out
