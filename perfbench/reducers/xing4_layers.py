"""The device time of a round of the Xing4.0 stack by the parts of the model
and of the server.

``hybrid_layers``' rule on this model's scopes: the program's
``program_scopes`` event maps each operation to the INNERMOST second-level
scope under ``layers``; an operation's self time (a ``while`` less what its
body covers), averaged over the devices, per traced round, in milliseconds.
What runs in ``client_train`` or ``aggregate`` under none of the ten (the
embedding, the one fused pass a step over the gradient, FedAvgM's scaling)
is ``x4_layers_unscoped_ms``, so the eleven add up to ``client_train_ms +
aggregate_ms``. Operations whose middle lies inside the loop's check
annotations belong to the state check's program and are left out. A program
that emits no ``layers`` gives nothing.

Two pieces ``lm_pieces`` does not know, from the event's ``pieces`` by the
same test: ``x4_attn_latent_ms`` (the low-rank projections, their norms,
RoPE and the output projection: ``attention`` outside its core) and
``x4_hc_sinkhorn_ms`` (the Sinkhorn iterations alone inside ``hyper_conn``).
And one OVERLAPPING sum from the event's ``modules``: ``x4_mtp_ms``,
everything under the outer scope ``mtp``, whose attention, experts and head
are counted in their own layers' metrics as well.

Shares of a peak, each from operations or bytes ``flops_xing4`` counts from
the real tokens (no recomputation, no padding of the head or of the
buffer): ``x4_attn_core_mfu`` (the cores' needed operations over the time of
the piece ``attn_core`` at the bf16 peak), ``x4_hyper_conn_roofline`` (the
residual modules' least time, the larger of their operations over the bf16
peak and their compulsory float32 bytes over the memory's, over
``x4_hyper_conn_ms``; which of the two bounds goes to the notes) and
``x4_experts_mfu`` (the held experts' matmul operations from the assignments
the run counted, registry counter ``moe_assignments_held`` over the traced
job's rounds, over ``x4_experts_ms``).
"""

from perfbench import flops_xing4
from perfbench.readers import registry

FIELDS = {"attention": "x4_attention_ms", "hyper_conn": "x4_hyper_conn_ms",
          "dense_mlp": "x4_dense_mlp_ms",
          "shared_expert": "x4_shared_expert_ms", "router": "x4_router_ms",
          "expert_dispatch": "x4_expert_dispatch_ms",
          "experts": "x4_experts_ms", "mtp_proj": "x4_mtp_proj_ms",
          "lm_head_loss": "x4_lm_head_ms",
          "server_update": "x4_server_update_ms"}
PIECES = {"attn_latent": "x4_attn_latent_ms",
          "hc_sinkhorn": "x4_hc_sinkhorn_ms"}
STAGES = ("client_train", "aggregate")
CHECKS = ("fedtpu.stop_check", "fedtpu.state_check")


def reduce(ev):
    view, rounds = ev.trace, ev.facts.get("trace_rounds")
    events = [e["payload"] for e in ev.sinks.get("job") or []
              if e.get("kind") == "program_scopes"
              and e["payload"].get("program") != "state_check"]
    layers, stages, pieces, modules = {}, {}, {}, {}
    for payload in events:
        for merged, name in ((layers, "layers"), (stages, "scopes"),
                             (pieces, "pieces"), (modules, "modules")):
            merged.update(payload.get(name) or {})
    if not view or not view.devices or not rounds or not layers:
        return {}
    checks = [(h.start, h.end) for h in view.host if h.name in CHECKS]
    acc = dict.fromkeys([*FIELDS.values(), *PIECES.values(),
                         "x4_layers_unscoped_ms", "x4_mtp_ms", "core"], 0.0)
    for ops in view.devices.values():
        for o in ops:
            middle = (o.start + o.end) / 2
            if any(s <= middle <= e for s, e in checks):
                continue
            field = FIELDS.get(layers.get(o.name))
            if field is None and stages.get(o.name) in STAGES:
                field = "x4_layers_unscoped_ms"
            if field:
                acc[field] += o.self_ns
            piece = pieces.get(o.name)
            if piece in PIECES:
                acc[PIECES[piece]] += o.self_ns
            elif piece == "attn_core":
                acc["core"] += o.self_ns
            if modules.get(o.name) == "mtp":
                acc["x4_mtp_ms"] += o.self_ns
    per_ms = 1e-6 / rounds / len(view.devices)
    out = {name: per_ms * ns for name, ns in acc.items()}
    core_ms = out.pop("core")
    cost, peaks = ev.facts.get("cost") or {}, ev.facts.get("peaks") or {}
    chips = ev.facts.get("chips", 1)
    flops_peak = chips * peaks.get("bf16_flops_per_s", 0)
    if core_ms > 0 and cost.get("core_flops") and flops_peak:
        out["x4_attn_core_mfu"] = (100.0 * cost["core_flops"]
                                   / (core_ms / 1000.0) / flops_peak)
    hyper = cost.get("hyper")
    if out["x4_hyper_conn_ms"] > 0 and hyper and flops_peak:
        by = {"flops": hyper["flops"] / flops_peak,
              "bytes": hyper["bytes"] / (chips * peaks["hbm_bytes_per_s"])}
        bound = max(by, key=by.get)
        ev.notes["x4_hyper_conn_roofline_bound"] = bound
        out["x4_hyper_conn_roofline"] = (100.0 * by[bound]
                                         / (out["x4_hyper_conn_ms"] / 1000.0))
    held = registry.read({"section": "counters",
                          "name": "moe_assignments_held"}, ev)
    job_rounds = ev.facts.get("job_rounds")
    model = ev.facts.get("model")
    if out["x4_experts_ms"] > 0 and held and job_rounds and model and flops_peak:
        flops = flops_xing4.held_experts_flops(model, held / job_rounds)
        out["x4_experts_mfu"] = (100.0 * flops
                                 / (out["x4_experts_ms"] / 1000.0) / flops_peak)
    return out
