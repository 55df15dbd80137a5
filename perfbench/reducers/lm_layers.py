"""The device time of a language-model round by the parts of the model and
of the server: ONE loop for every kind of program, driven by tables.

Under the round's stages the program names a second level of scopes
(``fedtpu/ops/scopes.py``), and its ``program_scopes`` event maps each
operation to the INNERMOST of them under ``layers`` (``ssm_scan`` lies inside
``ssm`` and ``kda_scan`` inside ``kda``: the scan's operations are the
scan's, the rest of the mixer is ``ssm_proj_ms`` / ``kl_kda_proj_ms``),
beside ``scopes`` (operation to stage). Same self-time rule as
``device_scopes``: an operation's own time (a ``while`` less what its body
covers), averaged over the devices, per traced round, in milliseconds.
``FIELDS`` is every layer any model names and the metric that reads it: one
name a layer, whichever model opens the scope; a layer only one model has
keeps that model's prefix. What runs in ``client_train`` or ``aggregate``
under none of them (the embedding, the one fused pass a step over the
gradient, the copy of the global, FedAvgM's scaling) is
``layers_unscoped_ms``, so the fields of ``FIELDS`` and it add up to
``client_train_ms + aggregate_ms`` in every cell; a layer the program does
not have reads 0 and no cell lists it. Operations whose middle lies inside
the loop's check annotations belong to the state check's program and are
left out. A program that emits no ``layers`` (the MLP and ConvNet programs
name none) gives nothing.

``PIECES``: third-level scopes of one model's own, from the event's
``pieces`` by the same test (the pieces every model shares are
``lm_pieces``'); what of ``kda`` lies under none of its four is in
``kl_kda_proj_ms`` alone. ``MODULES``: an OVERLAPPING sum from the event's
``modules``: ``x4_mtp_ms`` is everything under the outer scope ``mtp``, whose
attention, experts and head are counted in their own layers' metrics too.

Shares of a peak, each from operations or bytes the cell's own ``flops_*``
module counts from the real tokens (no recomputation, no padding of a head
or of a buffer) and the driver puts among the facts as ``cost``; none is
given where its time is nought or its cost is not stated:

* ``ROOFLINES``: the least time the chip could take (the larger of the
  operations over the bf16 peak and the compulsory bytes over the memory's)
  over the layer's time; which of the two bounds goes to the notes under
  ``<name>_bound``;
* ``attn_core_mfu``: the cores' needed operations (``cost.core_flops``) over
  the time of the piece ``attn_core`` at the bf16 peak;
* ``experts_mfu``: the expert matmuls' operations over ``experts_ms`` at the
  bf16 peak. A program that computes every expert states them as
  ``cost.experts_flops`` (``flops_lm``); one that holds a share counts its
  assignments (registry counter ``moe_assignments_held`` over the traced
  job's rounds) and ``flops_<kind>.held_experts_flops`` turns them into
  operations, ``<kind>`` being the model the traced job's own ``manifest``
  event names: a new model brings its module and edits nothing here.
"""

import importlib

from perfbench.readers import registry

FIELDS = {"attention": "attention_ms", "router": "router_ms",
          "expert_dispatch": "expert_dispatch_ms", "experts": "experts_ms",
          "lm_head_loss": "lm_head_ms", "server_update": "server_update_ms",
          "shared_expert": "shared_expert_ms", "dense_mlp": "dense_mlp_ms",
          "ssm": "ssm_proj_ms", "ssm_scan": "ssm_scan_ms",
          "hyper_conn": "x4_hyper_conn_ms", "mtp_proj": "x4_mtp_proj_ms",
          "kda": "kl_kda_proj_ms", "kda_scan": "kl_kda_scan_ms"}
PIECES = {"attn_latent": "x4_attn_latent_ms",
          "hc_sinkhorn": "x4_hc_sinkhorn_ms",
          "kda_in_proj": "kl_kda_in_proj_ms", "kda_conv": "kl_kda_conv_ms",
          "kda_gates": "kl_kda_gates_ms", "kda_out_proj": "kl_kda_out_proj_ms"}
MODULES = {"mtp": "x4_mtp_ms"}
# the share's name: (the time it is read against, the cost's key)
ROOFLINES = {"ssm_scan_roofline": ("ssm_scan_ms", "scan"),
             "kl_kda_scan_roofline": ("kl_kda_scan_ms", "scan"),
             "x4_hyper_conn_roofline": ("x4_hyper_conn_ms", "hyper")}
STAGES = ("client_train", "aggregate")
CHECKS = ("fedtpu.stop_check", "fedtpu.state_check")
# every field ``reduce`` can give: what a metric's file may name
EMITS = (*FIELDS.values(), "layers_unscoped_ms", *PIECES.values(),
         *MODULES.values(), *ROOFLINES, "attn_core_mfu", "experts_mfu")


def _held_experts_flops(ev):
    """Operations of a round of the experts held here, from the assignments
    the traced job counted; ``None`` where the program counts none or the
    model it names has no ``flops_<kind>`` module."""
    held = registry.read({"section": "counters",
                          "name": "moe_assignments_held"}, ev)
    job_rounds, model = ev.facts.get("job_rounds"), ev.facts.get("model")
    kind = next((e["payload"].get("config", {}).get("model", {}).get("kind")
                 for e in ev.sinks.get("job") or []
                 if e.get("kind") == "manifest"), None)
    if not (held and job_rounds and model and kind):
        return None
    try:
        module = importlib.import_module(f"perfbench.flops_{kind}")
    except ImportError:
        return None
    return module.held_experts_flops(model, held / job_rounds)


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
    acc = dict.fromkeys([*FIELDS.values(), "layers_unscoped_ms",
                         *PIECES.values(), *MODULES.values(), "core"], 0.0)
    for ops in view.devices.values():
        for o in ops:
            middle = (o.start + o.end) / 2
            if any(s <= middle <= e for s, e in checks):
                continue
            field = FIELDS.get(layers.get(o.name))
            if field is None and stages.get(o.name) in STAGES:
                field = "layers_unscoped_ms"
            if field:
                acc[field] += o.self_ns
            piece = pieces.get(o.name)
            if piece in PIECES:
                acc[PIECES[piece]] += o.self_ns
            elif piece == "attn_core":
                acc["core"] += o.self_ns
            if modules.get(o.name) in MODULES:
                acc[MODULES[modules[o.name]]] += o.self_ns
    per_ms = 1e-6 / rounds / len(view.devices)
    out = {name: per_ms * ns for name, ns in acc.items()}
    core_ms = out.pop("core")
    cost, peaks = ev.facts.get("cost") or {}, ev.facts.get("peaks") or {}
    chips = ev.facts.get("chips", 1)
    flops_peak = chips * peaks.get("bf16_flops_per_s", 0)
    if not flops_peak:
        return out
    for name, (time, key) in ROOFLINES.items():
        if out[time] > 0 and cost.get(key):
            by = {"flops": cost[key]["flops"] / flops_peak,
                  "bytes": cost[key]["bytes"] / (chips * peaks["hbm_bytes_per_s"])}
            bound = max(by, key=by.get)
            ev.notes[f"{name}_bound"] = bound
            out[name] = 100.0 * by[bound] / (out[time] / 1000.0)
    if core_ms > 0 and cost.get("core_flops"):
        out["attn_core_mfu"] = (100.0 * cost["core_flops"]
                                / (core_ms / 1000.0) / flops_peak)
    if out["experts_ms"] > 0:
        flops = cost.get("experts_flops") or _held_experts_flops(ev)
        if flops:
            out["experts_mfu"] = (100.0 * flops
                                  / (out["experts_ms"] / 1000.0) / flops_peak)
    return out
