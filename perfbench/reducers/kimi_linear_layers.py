"""The device time of a round of the Kimi-Linear stack by the parts of the
model and of the server.

``hybrid_layers``' rule on this model's scopes: the program's
``program_scopes`` event maps each operation to the INNERMOST second-level
scope under ``layers`` (``kda_scan`` lies inside ``kda``, so the recurrence's
operations are the recurrence's and the rest of the mixer, its projections,
convolutions, gates and norms, is ``kl_kda_proj_ms``); an operation's self
time (a ``while`` less what its body covers), averaged over the devices, per
traced round, in milliseconds. What runs in ``client_train`` or ``aggregate``
under none of the ten (the embedding, the one fused pass a step over the
gradient, FedAvgM's scaling) is ``kl_layers_unscoped_ms``, so the eleven add
up to ``client_train_ms + aggregate_ms``. Operations whose middle lies inside
the loop's check annotations belong to the state check's program and are
left out. A program that emits no ``layers`` gives nothing.

The mixer's four pieces, from the event's ``pieces`` by the same test:
``kl_kda_in_proj_ms``, ``kl_kda_conv_ms``, ``kl_kda_gates_ms`` and
``kl_kda_out_proj_ms``; what of ``kda`` lies under none of them (an
instruction of the compiler's whose neighbours disagree) is in
``kl_kda_proj_ms`` alone.

Shares of a peak, each from operations or bytes ``flops_kimi_linear`` counts
from the real tokens (no recomputation, no padding of the head or of the
buffer): ``kl_kda_scan_roofline`` (the recurrences' least time, the larger of
their operations over the bf16 peak and their inputs' and outputs' float32
bytes over the memory's, over ``kl_kda_scan_ms``; which of the two bounds
goes to the notes), ``kl_attn_core_mfu`` (the cores' needed operations over
the time of the piece ``attn_core`` at the bf16 peak) and ``kl_experts_mfu``
(the held experts' matmul operations from the assignments the run counted,
registry counter ``moe_assignments_held`` over the traced job's rounds, over
``kl_experts_ms``).
"""

from perfbench import flops_kimi_linear
from perfbench.readers import registry

FIELDS = {"kda": "kl_kda_proj_ms", "kda_scan": "kl_kda_scan_ms",
          "attention": "kl_attention_ms", "dense_mlp": "kl_dense_mlp_ms",
          "shared_expert": "kl_shared_expert_ms", "router": "kl_router_ms",
          "expert_dispatch": "kl_expert_dispatch_ms",
          "experts": "kl_experts_ms", "lm_head_loss": "kl_lm_head_ms",
          "server_update": "kl_server_update_ms"}
PIECES = {"kda_in_proj": "kl_kda_in_proj_ms", "kda_conv": "kl_kda_conv_ms",
          "kda_gates": "kl_kda_gates_ms", "kda_out_proj": "kl_kda_out_proj_ms"}
STAGES = ("client_train", "aggregate")
CHECKS = ("fedtpu.stop_check", "fedtpu.state_check")


def reduce(ev):
    view, rounds = ev.trace, ev.facts.get("trace_rounds")
    events = [e["payload"] for e in ev.sinks.get("job") or []
              if e.get("kind") == "program_scopes"
              and e["payload"].get("program") != "state_check"]
    layers, stages, pieces = {}, {}, {}
    for payload in events:
        for merged, name in ((layers, "layers"), (stages, "scopes"),
                             (pieces, "pieces")):
            merged.update(payload.get(name) or {})
    if (not view or not view.devices or not rounds
            or "kda" not in layers.values()):
        return {}
    checks = [(h.start, h.end) for h in view.host if h.name in CHECKS]
    acc = dict.fromkeys([*FIELDS.values(), *PIECES.values(),
                         "kl_layers_unscoped_ms", "core"], 0.0)
    for ops in view.devices.values():
        for o in ops:
            middle = (o.start + o.end) / 2
            if any(s <= middle <= e for s, e in checks):
                continue
            field = FIELDS.get(layers.get(o.name))
            if field is None and stages.get(o.name) in STAGES:
                field = "kl_layers_unscoped_ms"
            if field:
                acc[field] += o.self_ns
            piece = pieces.get(o.name)
            if piece in PIECES:
                acc[PIECES[piece]] += o.self_ns
            elif piece == "attn_core":
                acc["core"] += o.self_ns
    per_ms = 1e-6 / rounds / len(view.devices)
    out = {name: per_ms * ns for name, ns in acc.items()}
    core_ms = out.pop("core")
    cost, peaks = ev.facts.get("cost") or {}, ev.facts.get("peaks") or {}
    chips = ev.facts.get("chips", 1)
    flops_peak = chips * peaks.get("bf16_flops_per_s", 0)
    scan = cost.get("scan")
    if out["kl_kda_scan_ms"] > 0 and scan and flops_peak:
        by = {"flops": scan["flops"] / flops_peak,
              "bytes": scan["bytes"] / (chips * peaks["hbm_bytes_per_s"])}
        bound = max(by, key=by.get)
        ev.notes["kl_kda_scan_roofline_bound"] = bound
        out["kl_kda_scan_roofline"] = (100.0 * by[bound]
                                       / (out["kl_kda_scan_ms"] / 1000.0))
    if core_ms > 0 and cost.get("core_flops") and flops_peak:
        out["kl_attn_core_mfu"] = (100.0 * cost["core_flops"]
                                   / (core_ms / 1000.0) / flops_peak)
    held = registry.read({"section": "counters",
                          "name": "moe_assignments_held"}, ev)
    job_rounds = ev.facts.get("job_rounds")
    model = ev.facts.get("model")
    if out["kl_experts_ms"] > 0 and held and job_rounds and model and flops_peak:
        flops = flops_kimi_linear.held_experts_flops(model, held / job_rounds)
        out["kl_experts_mfu"] = (100.0 * flops
                                 / (out["kl_experts_ms"] / 1000.0) / flops_peak)
    return out
