"""PR 43's fold: ONE reducer for the layer sums of every language-model
program, under one name a layer, piece and pass.

``frozen_fold.json`` holds what the four reducers this one replaced
(``lm_layers``, ``hybrid_layers``, ``xing4_layers``, ``kimi_linear_layers``)
and ``lm_pieces`` gave on ``evidence(kind)`` before they went, each reading
under the name it has now (the file's ``renamed`` block is the whole table
old name -> new name): written by the PARENT's code from this file's input
(``evidence(kind, root)`` with the parent's checkout as ``root``), never by
hand. The one reducer has to give the same numbers to the last bit."""

import json
import os

import pytest

from perfbench import manifest, xplane
from perfbench.evidence import Evidence
from perfbench.xplane import Op, TraceView

ROOT = os.path.dirname(manifest.HERE)
FROZEN = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "frozen_fold.json")
CELLS = {"olmoe": "olmoe-l1-fed8-4k", "nemotron_h": "nemotron-l9-fed8-packed",
         "xing4": "xing4-l5-mtp1-fed8-4k",
         "kimi_linear": "kimi-linear-l5-fed8-packed"}
PASSES = ("forward", "recompute", "backward", "update")

# What a program of each kind names: (layer, piece or None, module or None),
# a few operations each. ``None`` as the layer: under the stage alone.
SHARED = [("embed", None), ("attention", None), ("attention", "attn_core"),
          ("router", None), ("expert_dispatch", None), ("experts", None),
          ("lm_head_loss", None), (None, None), (None, "sgd_pass")]
HELD = [("shared_expert", None)]
LATENT = [("attention", "attn_latent"), ("dense_mlp", None)]
SCOPES = {
    "olmoe": SHARED,
    "nemotron_h": SHARED + HELD + [
        ("ssm", "ssm_in_proj"), ("ssm", "ssm_conv"), ("ssm_scan", None),
        ("ssm", "ssm_gate_norm"), ("ssm", "ssm_out_proj"), ("ssm", None),
        # one of the mixer's that inherited the update's piece stays its own
        ("ssm", "sgd_pass")],
    "xing4": SHARED + HELD + LATENT + [
        ("hyper_conn", None), ("hyper_conn", "hc_sinkhorn"),
        ("mtp_proj", None)],
    "kimi_linear": SHARED + HELD + LATENT + [
        ("kda", "kda_in_proj"), ("kda", "kda_conv"), ("kda_scan", None),
        ("kda", "kda_gates"), ("kda", "kda_out_proj"), ("kda", None)],
}
COST = {"olmoe": {"experts_flops": 7.0e5},
        "nemotron_h": {"scan": {"flops": 3.0e4, "bytes": 9.0e2}},
        "xing4": {"core_flops": 6.0e4, "hyper": {"flops": 9.0e6, "bytes": 40.0}},
        "kimi_linear": {"core_flops": 5.0e4,
                        "scan": {"flops": 2.0e4, "bytes": 7.0e2}}}
MODEL = {"hidden_size": 8, "moe_intermediate_size": 6}
US = 1000.0


def evidence(kind, root=ROOT):
    """One device, three traced rounds. Every scope of the kind's program
    runs three operations of differing lengths, a pass each in turn; the
    third lies inside a ``while`` of the stage's (so self times count); one
    expert operation is the compiler's ``ragged-dot-none*``; the server's
    update runs in ``aggregate``; one key of the round program's runs once
    more inside the state check's annotation and is left out."""
    ops, scopes, layers, pieces, modules, passes = [], {}, {}, {}, {}, {}
    at, n = 10.0, 0

    def run(name, us, layer, piece, stage):
        nonlocal at
        ops.append(Op(name, at * US, (at + us) * US))
        at += us
        scopes[name] = stage
        if layer:
            layers[name] = layer
        if piece:
            pieces[name] = piece

    rows = [*SCOPES[kind], ("server_update", None)]
    inner = []
    for i, (layer, piece) in enumerate(rows):
        stage = "aggregate" if layer == "server_update" else "client_train"
        for j in range(3):
            n += 1
            head = ("ragged-dot-none" if (layer, j) == ("experts", 1)
                    else "fusion")
            name = f"{head}.{n} f32[{8 + i}]"
            passes[name] = ("update" if layer == "server_update"
                            or piece == "sgd_pass" else PASSES[(i + j) % 3])
            if kind == "xing4" and layer in ("experts", "lm_head_loss") and j:
                modules[name] = "mtp"
            if j == 2 and stage == "client_train":
                inner.append((name, 3.0 + 0.5 * i + 0.25 * n, layer, piece))
            else:
                run(name, 1.0 + 1.5 * i + 0.75 * j + 0.125 * n, layer, piece,
                    stage)
    # a loop of the stage's around the third operations: its self time is
    # what its body does not cover, and is no layer's
    start = at
    at += 2.0
    for name, us, layer, piece in inner:
        run(name, us, layer, piece, "client_train")
    at += 1.5
    ops.append(Op("while.1 (f32[8])", start * US, at * US))
    scopes["while.1 (f32[8])"] = "client_train"
    passes["while.1 (f32[8])"] = "forward"
    # the state check's program lists a key of the round program's too
    first = ops[1].name
    ops.append(Op(first, (at + 20) * US, (at + 23) * US))
    dev = xplane._self_times(sorted(ops, key=lambda o: (o.start, -o.end)))
    host = [Op("fedtpu.chunk_fetch", 0.0, (at + 5) * US),
            Op("fedtpu.stop_check", (at + 10) * US, (at + 14) * US),
            Op("fedtpu.state_check", (at + 15) * US, (at + 30) * US)]
    ev = Evidence(manifest=manifest.load(root))
    ev.trace = TraceView(devices={"/device:TPU:0": dev}, host=host, start=0.0,
                         end=(at + 40) * US)
    ev.facts.update(
        trace_rounds=3, job_rounds=5, chips=1, lm_positions=4096,
        kda_rows=12, model=MODEL, cost=COST[kind],
        peaks={"bf16_flops_per_s": 1.97e11, "hbm_bytes_per_s": 8.19e8})
    ev.sinks["job"] = [
        {"kind": "manifest", "dur_s": 0.0,
         "payload": {"config": {"model": {"kind": kind}}}},
        {"kind": "program_scopes", "dur_s": 0.25, "payload": {
            "program": "state_check", "width": None, "unscoped": [],
            "scopes": {first: "state_check"}}},
        {"kind": "program_scopes", "dur_s": 1.5, "payload": {
            "program": "round_step", "width": 1, "unscoped": [],
            "scopes": scopes, "layers": layers, "pieces": pieces,
            "modules": modules, "passes": passes}},
        {"kind": "counters", "dur_s": 0.0, "payload": {"counters": {
            "moe_assignments_held": 350.0, "moe_assignments_total": 2800.0,
            "moe_rows_computed": 512.0}, "gauges": {}}}]
    return ev


def declared(kind):
    """The trace-read metrics ``BENCHMARK.json`` lists for the kind's cell."""
    m = manifest.load(ROOT)
    return [e["name"] for e in m.metrics_of("per_layer", CELLS[kind])
            if m.layer_metric(e["name"])["read"].get("reducer") in (
                "lm_layers", "lm_pieces")]


@pytest.mark.parametrize("kind", sorted(CELLS))
def test_the_one_reducer_reads_what_the_four_read(kind):
    with open(FROZEN) as fh:
        frozen = json.load(fh)
    ev = evidence(kind)
    found = {name: ev.metric(name) for name in declared(kind)}
    was = frozen["readings"][kind]
    # every reading the old reducers declared for this cell, to the last bit
    assert {name: found.get(name) for name in was} == was
    assert {k: v for k, v in ev.notes.items() if k.endswith("_bound")} == (
        frozen["notes"][kind])
    # what the cell lists beyond them is new in it: a piece or the rest that
    # only the one table of layers can tell
    assert set(found) - set(was) <= set(frozen["new_in"][kind])
    assert all(value is not None for value in found.values()), found


@pytest.mark.parametrize("kind", sorted(CELLS))
def test_the_layers_add_up_to_the_two_stages(kind):
    """Layers, not pieces, passes or shares: the fields of the reducer's own
    table of layers and the rest sum to what ``device_scopes`` puts down to
    ``client_train`` and ``aggregate``; so do the four passes; each group of
    pieces sums to its layer."""
    from perfbench.reducers import lm_layers

    ev = evidence(kind)
    out = ev.reduced("lm_layers")
    stages = ev.metric("client_train_ms") + ev.metric("aggregate_ms")
    summed = sum(out[f] for f in [*lm_layers.FIELDS.values(),
                                  "layers_unscoped_ms"])
    assert summed == pytest.approx(stages, abs=1e-12)
    listed = [n for n in declared(kind) if n in lm_layers.FIELDS.values()
              or n == "layers_unscoped_ms"]
    # and the ones this cell lists are all of them that are not nought
    assert sum(ev.metric(n) for n in listed) == pytest.approx(stages, abs=1e-12)
    assert sum(ev.metric(f"{p}_ms") for p in PASSES) == pytest.approx(
        stages, abs=1e-12)
    assert (ev.metric("attn_core_ms") + ev.metric("attn_proj_ms")
            == pytest.approx(ev.metric("attention_ms"), abs=1e-12))
    assert (ev.metric("embed_ms") + ev.metric("sgd_pass_ms")
            + ev.metric("outside_rest_ms")
            == pytest.approx(ev.metric("layers_unscoped_ms"), abs=1e-12))


def test_a_layers_operation_that_inherited_the_updates_piece_stays_the_layers():
    """``hyper_conn`` and ``kda`` are layers of the one table, as ``ssm`` was
    of the hybrid's: an operation of theirs under the piece ``sgd_pass`` is
    theirs and not the pass's (the four reducers' ``lm_pieces`` knew the
    hybrid's nine layers alone and would have counted it twice)."""
    ev = evidence("xing4")
    scopes = next(e["payload"] for e in ev.sinks["job"]
                  if e["payload"].get("program") == "round_step")
    before = ev.metric("sgd_pass_ms")
    name = next(k for k, v in scopes["layers"].items() if v == "hyper_conn")
    again = evidence("xing4")
    next(e["payload"] for e in again.sinks["job"]
         if e["payload"].get("program") == "round_step")["pieces"][name] = (
        "sgd_pass")
    assert again.metric("sgd_pass_ms") == before
    assert again.metric("x4_hyper_conn_ms") == ev.metric("x4_hyper_conn_ms")


def test_a_program_without_layers_or_a_kind_gives_nothing_for_them():
    ev = evidence("kimi_linear")
    ev.sinks["job"] = [e for e in ev.sinks["job"] if e["kind"] != "manifest"]
    assert ev.metric("experts_mfu") is None             # whose operations?
    assert ev.metric("attn_core_mfu") is not None       # the cost states them
    ev = evidence("olmoe")
    for e in ev.sinks["job"]:
        e["payload"].pop("layers", None)
    assert ev.reduced("lm_layers") == {}
    assert ev.metric("attention_ms") is None and ev.metric("experts_mfu") is None


def test_every_old_name_went_and_its_new_name_is_declared():
    """``renamed`` is the whole table old name -> new name (PERF.md section 3
    has it by rule): the ledger's and PERF.md's numbers under an old name are
    found under the new one."""
    with open(FROZEN) as fh:
        renamed = json.load(fh)["renamed"]
    m = manifest.load(ROOT)
    declared_now = {e["name"]: e for e in m.doc["per_layer"]}
    cell_of = {"nh_": CELLS["nemotron_h"], "x4_": CELLS["xing4"],
               "kl_": CELLS["kimi_linear"]}
    assert len(renamed) == 73
    for old, new in renamed.items():
        assert old not in declared_now
        assert not os.path.exists(m.file("layer_metrics", old)), old
        assert cell_of[old[:3]] in declared_now[new]["workloads"], (old, new)
