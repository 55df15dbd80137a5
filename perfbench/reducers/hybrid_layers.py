"""The device time of a round of the hybrid (``nemotron_h``) tower by the
parts of the model and of the server.

``lm_layers``' rule on this model's scopes: the program's ``program_scopes``
event maps each operation to the INNERMOST second-level scope under
``layers`` (``ssm_scan`` lies inside ``ssm``, so the scan's operations are
the scan's and the rest of the mixer, ``W_in``, the convolution, the gate
and norm, ``W_out``, is ``ssm_proj_ms``); an operation's self time (a
``while`` less what its body covers), averaged over the devices, per traced
round, in milliseconds. What runs in ``client_train`` or ``aggregate`` under
none of the nine (the embedding, the one fused pass a step over the
gradient, FedAvgM's scaling) is ``nh_layers_unscoped_ms``, so the ten add up
to ``client_train_ms + aggregate_ms``. Operations whose middle lies inside
the loop's check annotations belong to the state check's program and are
left out. A program that emits no ``layers`` gives nothing.

``ssm_scan_roofline``: the least time the chip could take for the scans of
a round (``flops_nemotron_h.scan_cost``: the larger of its operations over
the bf16 peak and its compulsory bytes over the memory's peak) over
``ssm_scan_ms``; which of the two bounds goes to the notes.
``nh_experts_mfu``: the held experts' matmul operations of a round, from
the assignments the run counted (registry counter ``moe_assignments_held``
over the traced job's rounds), over ``nh_experts_ms`` at the bf16 peak.
"""

from perfbench import flops_nemotron_h
from perfbench.readers import registry

FIELDS = {"ssm": "ssm_proj_ms", "ssm_scan": "ssm_scan_ms",
          "shared_expert": "shared_expert_ms", "attention": "nh_attention_ms",
          "router": "nh_router_ms",
          "expert_dispatch": "nh_expert_dispatch_ms",
          "experts": "nh_experts_ms", "lm_head_loss": "nh_lm_head_ms",
          "server_update": "nh_server_update_ms"}
STAGES = ("client_train", "aggregate")
CHECKS = ("fedtpu.stop_check", "fedtpu.state_check")


def reduce(ev):
    view, rounds = ev.trace, ev.facts.get("trace_rounds")
    events = [e["payload"] for e in ev.sinks.get("job") or []
              if e.get("kind") == "program_scopes"
              and e["payload"].get("program") != "state_check"]
    layers, stages = {}, {}
    for payload in events:
        layers.update(payload.get("layers") or {})
        stages.update(payload.get("scopes") or {})
    if not view or not view.devices or not rounds or not layers:
        return {}
    checks = [(h.start, h.end) for h in view.host if h.name in CHECKS]
    acc = dict.fromkeys(list(FIELDS.values()) + ["nh_layers_unscoped_ms"], 0.0)
    for ops in view.devices.values():
        for o in ops:
            middle = (o.start + o.end) / 2
            if any(s <= middle <= e for s, e in checks):
                continue
            field = FIELDS.get(layers.get(o.name))
            if field is None and stages.get(o.name) in STAGES:
                field = "nh_layers_unscoped_ms"
            if field:
                acc[field] += o.self_ns
    per_ms = 1e-6 / rounds / len(view.devices)
    out = {name: per_ms * ns for name, ns in acc.items()}
    cost, peaks = ev.facts.get("cost") or {}, ev.facts.get("peaks") or {}
    chips = ev.facts.get("chips", 1)
    scan = cost.get("scan")
    if out["ssm_scan_ms"] > 0 and scan and peaks:
        by = {"flops": scan["flops"] / (chips * peaks["bf16_flops_per_s"]),
              "bytes": scan["bytes"] / (chips * peaks["hbm_bytes_per_s"])}
        bound = max(by, key=by.get)
        ev.notes["ssm_scan_roofline_bound"] = bound
        out["ssm_scan_roofline"] = 100.0 * by[bound] / (out["ssm_scan_ms"] / 1000.0)
    held = registry.read({"section": "counters",
                          "name": "moe_assignments_held"}, ev)
    job_rounds = ev.facts.get("job_rounds")
    model = ev.facts.get("model")
    if out["nh_experts_ms"] > 0 and held and job_rounds and model and peaks:
        flops = flops_nemotron_h.held_experts_flops(model, held / job_rounds)
        out["nh_experts_mfu"] = 100.0 * flops / (out["nh_experts_ms"] / 1000.0) / (
            chips * peaks["bf16_flops_per_s"])
    return out
