"""The device time of a language-model round by piece and by pass.

Under its layers the program names a third level of scopes (the four parts
of a state-space mixer around its scan, ``ssm_in_proj`` / ``ssm_conv`` /
``ssm_gate_norm`` / ``ssm_out_proj``; the attention core alone,
``attn_core``; the one fused pass a step over the gradient, ``sgd_pass``),
and its ``program_scopes`` event maps each operation to the innermost of
them under ``pieces`` and to the direction it runs in (``forward``,
``recompute``, ``backward``, ``update``) under ``passes``, beside ``layers``
and ``scopes``. Same rule as ``lm_layers``: an
operation's self time (a ``while`` less what its body covers), averaged over
the devices, per traced round, in milliseconds; operations whose middle lies
inside the loop's check annotations are the state check's and are left out.

A layer's pieces split what ``lm_layers`` gives the layer, the same
operations by the same test, so each group adds up to the layer's metric
in every cell: the mixer's four and ``ssm_rest_ms`` (scope ``ssm`` under
none of the four: an instruction of the compiler's whose neighbours
disagree) to ``ssm_proj_ms``; ``attn_core_ms`` and ``attn_proj_ms``
(``attention`` outside the core: norms, projections, RoPE, the key-value
repeat, the latent bottlenecks) to ``attention_ms``; ``embed_ms``,
``sgd_pass_ms`` and ``outside_rest_ms`` (``client_train`` or ``aggregate``
under no layer of ``lm_layers.FIELDS``, and neither) to
``layers_unscoped_ms``; the four passes to ``client_train_ms +
aggregate_ms``. The whole table, a row a layer (``layer/piece`` where a
piece applies, ``outside`` for none) and a column a pass, goes to the run's
notes under ``layer_pass_ms``. A program that emits no ``pieces`` (a parent
of the PR that brought them) gives nothing.
"""

# the layers with a metric of their own, whichever model names them: what
# lies outside them is ``layers_unscoped_ms``
from perfbench.reducers.lm_layers import CHECKS, FIELDS as LAYERED, STAGES

INSIDE = {"ssm": ("ssm_in_proj", "ssm_conv", "ssm_gate_norm", "ssm_out_proj"),
          "attention": ("attn_core",)}
REST = {"ssm": "ssm_rest_ms", "attention": "attn_proj_ms"}
PASSES = ("forward", "recompute", "backward", "update")
# every field ``reduce`` gives: what a metric's file may name
EMITS = (*(f"{piece}_ms" for inside in INSIDE.values() for piece in inside),
         *REST.values(), "embed_ms", "sgd_pass_ms", "outside_rest_ms",
         *(f"{p}_ms" for p in PASSES))


def _piece_of(layer, piece, staged):
    """``(field or None, the piece that counts)`` of one operation."""
    if layer in INSIDE:
        if piece in INSIDE[layer]:
            return f"{piece}_ms", piece
        return REST[layer], None
    if layer in LAYERED or not staged:
        return None, None
    if layer == "embed":
        return "embed_ms", None
    if piece == "sgd_pass":
        return "sgd_pass_ms", piece
    return "outside_rest_ms", None


def reduce(ev):
    view, rounds = ev.trace, ev.facts.get("trace_rounds")
    events = [e["payload"] for e in ev.sinks.get("job") or []
              if e.get("kind") == "program_scopes"
              and e["payload"].get("program") != "state_check"]
    if (not view or not view.devices or not rounds
            or not any("pieces" in payload for payload in events)):
        return {}
    layers, stages, pieces, passes = {}, {}, {}, {}
    for payload in events:
        for merged, name in ((layers, "layers"), (stages, "scopes"),
                             (pieces, "pieces"), (passes, "passes")):
            merged.update(payload.get(name) or {})
    checks = [(h.start, h.end) for h in view.host if h.name in CHECKS]
    acc = dict.fromkeys(EMITS, 0.0)
    table = {}
    for ops in view.devices.values():
        for o in ops:
            middle = (o.start + o.end) / 2
            if any(s <= middle <= e for s, e in checks):
                continue
            layer = layers.get(o.name)
            staged = stages.get(o.name) in STAGES
            field, piece = _piece_of(layer, pieces.get(o.name), staged)
            if field:
                acc[field] += o.self_ns
            if not staged:
                continue
            direction = passes.get(o.name, "forward")
            acc[f"{direction}_ms"] += o.self_ns
            row = (layer or "outside") + (f"/{piece}" if piece else "")
            cells = table.setdefault(row, dict.fromkeys(PASSES, 0.0))
            cells[direction] += o.self_ns
    per_ms = 1e-6 / rounds / len(view.devices)
    ev.notes["layer_pass_ms"] = {
        row: {p: round(per_ms * ns, 3) for p, ns in cells.items()}
        for row, cells in sorted(table.items())}
    return {name: per_ms * ns for name, ns in acc.items()}
