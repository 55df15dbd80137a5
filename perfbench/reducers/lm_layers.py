"""The device time of a round by the parts of the model and of the server.

Under the round's stages the program names a second level of scopes
(``attention``, ``router``, ``expert_dispatch``, ``experts``,
``lm_head_loss`` in the model, ``server_update`` in the aggregation), and its
``program_scopes`` event maps each operation to the innermost of them under
``layers``, beside ``scopes`` (operation to stage). Same self-time rule as
``device_scopes``: an operation's own time (a ``while`` less what its body
covers), averaged over the devices, per traced round, in milliseconds. What
runs in ``client_train`` or ``aggregate`` under none of the six (the
embedding's gather and its scatter, the SGD update, the copy of the global,
the accumulation of the delta) is ``layers_unscoped_ms``, so the seven add up
to ``client_train_ms + aggregate_ms``. Operations whose middle lies inside
the loop's check annotations belong to the state check's program and are
left out. A program that emits no ``layers`` (the parent of the PR that
brought them; the MLP and ConvNet programs name none) gives nothing.

``experts_mfu``: the expert matmuls' forward and backward operations of a
round (``flops_lm.round_cost``, from the measured tokens) over
``experts_ms`` at the chip's bf16 peak: the grouped matmuls' share of it.
"""

FIELDS = {"attention": "attention_ms", "router": "router_ms",
          "expert_dispatch": "expert_dispatch_ms", "experts": "experts_ms",
          "lm_head_loss": "lm_head_ms", "server_update": "server_update_ms"}
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
    acc = dict.fromkeys(list(FIELDS.values()) + ["layers_unscoped_ms"], 0.0)
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
    per_ms = 1e-6 / rounds / len(view.devices)
    out = {name: per_ms * ns for name, ns in acc.items()}
    cost, peaks = ev.facts.get("cost") or {}, ev.facts.get("peaks") or {}
    if out["experts_ms"] > 0 and cost.get("experts_flops") and peaks:
        out["experts_mfu"] = 100.0 * cost["experts_flops"] / (
            out["experts_ms"] / 1000.0) / (
            ev.facts.get("chips", 1) * peaks["bf16_flops_per_s"])
    return out
