"""PR 35's per-layer metrics: the ``lm_pieces`` reducer on a hand-built trace
beside the layer reducer it splits, a stack with a state-space mixer and one
without, and the event's cost through the span reader."""

import os

import pytest

from perfbench import manifest, xplane
from perfbench.evidence import Evidence
from perfbench.tests.entries import check_cell
from perfbench.xplane import Op, TraceView

ROOT = os.path.dirname(manifest.HERE)
US = 1000.0
# name, layer, piece, pass, microseconds: one operation each, back to back
# on one device inside ``client_train`` (the server's update in
# ``aggregate``); the last two are no layer's and the check's
OPS = [
    ("fusion.1 f32[8]", "embed", None, "forward", 2),
    ("fusion.2 f32[8]", "ssm", "ssm_in_proj", "forward", 10),
    ("fusion.3 f32[8]", "ssm", "ssm_conv", "forward", 6),
    ("fusion.4 f32[8]", "ssm_scan", None, "forward", 7),
    ("fusion.5 f32[8]", "ssm", "ssm_gate_norm", "forward", 4),
    ("fusion.6 f32[8]", "ssm", "ssm_out_proj", "forward", 8),
    ("fusion.7 f32[8]", "attention", None, "forward", 5),
    ("custom-call.8 f32[8]", "attention", "attn_core", "forward", 9),
    ("fusion.9 f32[8]", "lm_head_loss", None, "forward", 12),
    ("fusion.10 f32[8]", "attention", "attn_core", "recompute", 9),
    ("fusion.11 f32[8]", "ssm", "ssm_conv", "recompute", 6),
    ("fusion.12 f32[8]", "ssm", "ssm_conv", "backward", 12),
    # a copy of the compiler's between two pieces: the mixer's rest; one of
    # the mixer's that inherited the update's piece stays the mixer's
    ("copy.13 f32[8]", "ssm", None, "backward", 2),
    ("copy.14 f32[8]", "ssm", "sgd_pass", "update", 2),
    ("fusion.15 f32[8]", "experts", None, "recompute", 3),
    ("fusion.16 f32[8]", None, None, "backward", 4),
    ("fusion.17 f32[8]", None, "sgd_pass", "update", 20),
    ("fusion.18 f32[8]", "server_update", None, "update", 5),
]


def _mixers(row) -> bool:
    return (row[1] or "").startswith("ssm")


def _evidence(with_pieces=True, mixer=True):
    """``mixer`` off: the rows of ``OPS`` a program without a state-space
    mixer has (OLMoE's names no ``ssm``)."""
    rows = [row for row in OPS if mixer or not _mixers(row)]
    ops, at = [], 10.0
    for name, _, _, _, us in rows:
        ops.append(Op(name, at * US, (at + us) * US))
        at += us
    # the state check's program lists a key of the round program's too
    ops.append(Op("fusion.2 f32[8]", 200 * US, 203 * US))
    dev = xplane._self_times(sorted(ops, key=lambda o: (o.start, -o.end)))
    host = [Op("fedtpu.chunk_fetch", 0.0, 190 * US),
            Op("fedtpu.state_check", 195 * US, 210 * US)]
    ev = Evidence(manifest=manifest.load(ROOT))
    ev.trace = TraceView(devices={"/device:TPU:0": dev}, host=host,
                         start=0.0, end=220 * US)
    ev.facts["trace_rounds"] = 2
    payload = {
        "program": "round_step", "width": 1, "unscoped": [],
        "scopes": {name: "aggregate" if layer == "server_update"
                   else "client_train" for name, layer, *_ in rows},
        "layers": {name: layer for name, layer, *_ in rows if layer}}
    if with_pieces:
        payload["pieces"] = {name: piece for name, _, piece, *_ in rows if piece}
        payload["passes"] = {name: way for name, _, _, way, _ in rows}
    ev.sinks["job"] = [
        {"kind": "program_scopes", "dur_s": 0.25, "payload": {
            "program": "state_check", "width": None, "unscoped": [],
            "scopes": {"fusion.2 f32[8]": "state_check"}}},
        {"kind": "program_scopes", "dur_s": 1.5, "payload": payload}]
    return ev


def _total(mixer, *tests):
    """Microseconds of ``OPS`` a round of two, in milliseconds."""
    return sum(us for *row, us in OPS if all(t(*row) for t in tests)
               and (mixer or not _mixers(row))) / 2 / 1000


@pytest.mark.parametrize("mixer", [False, True], ids=["olmoe", "hybrid"])
def test_each_group_of_pieces_adds_up_to_its_layer(mixer):
    ev = _evidence(mixer=mixer)
    parts = [ev.metric(name) for name in (
        "ssm_in_proj_ms", "ssm_conv_ms", "ssm_gate_norm_ms",
        "ssm_out_proj_ms", "ssm_rest_ms")]
    assert parts == pytest.approx(
        [0.005, 0.012, 0.002, 0.004, 0.002] if mixer else [0.0] * 5)
    assert sum(parts) == pytest.approx(ev.metric("ssm_proj_ms"))
    core, proj = ev.metric("attn_core_ms"), ev.metric("attn_proj_ms")
    assert (core, proj) == pytest.approx((0.009, 0.0025))
    assert core + proj == pytest.approx(ev.metric("attention_ms"))
    outside = [ev.metric(name) for name in (
        "embed_ms", "sgd_pass_ms", "outside_rest_ms")]
    assert outside == pytest.approx([0.001, 0.010, 0.002])
    assert sum(outside) == pytest.approx(ev.metric("layers_unscoped_ms"))
    passes = {way: ev.metric(f"{way}_ms") for way in (
        "forward", "recompute", "backward", "update")}
    assert passes == pytest.approx({
        way: _total(mixer, lambda *row, way=way: row[3] == way)
        for way in passes})
    assert sum(passes.values()) == pytest.approx(
        ev.metric("client_train_ms") + ev.metric("aggregate_ms"))
    # the table in the notes: a row a layer and piece, a column a pass
    table = ev.notes["layer_pass_ms"]
    if mixer:
        assert table["ssm/ssm_conv"] == {"forward": 0.003, "recompute": 0.003,
                                         "backward": 0.006, "update": 0.0}
        assert table["ssm"]["update"] == 0.001
    assert table["outside/sgd_pass"]["update"] == 0.010
    # (each of its numbers rounded to a microsecond)
    assert sum(map(sum, (row.values() for row in table.values()))) == (
        pytest.approx(sum(passes.values()), abs=1e-3 * len(table)))


def test_a_program_without_pieces_gives_nothing():
    ev = _evidence(with_pieces=False)
    for name in ("ssm_conv_ms", "attn_core_ms", "outside_rest_ms",
                 "recompute_ms", "update_ms"):
        assert ev.metric(name) is None
    assert ev.metric("ssm_proj_ms") == pytest.approx(0.025)   # the parent's
    assert "layer_pass_ms" not in ev.notes


def test_the_events_cost_is_read_by_the_span_reader():
    m = manifest.load(ROOT)
    assert m.layer_metric("scopes_emit_s")["read"] == {
        "kind": "span", "event": "program_scopes", "stat": "sum"}
    assert _evidence().metric("scopes_emit_s") == pytest.approx(1.75)
    # a program that does not stamp its events reads 0, one without a sink
    # nothing
    ev = _evidence()
    for e in ev.sinks["job"]:
        e["dur_s"] = 0.0
    assert ev.metric("scopes_emit_s") == 0.0
    ev = _evidence()
    ev.sinks["job"] = []
    assert ev.metric("scopes_emit_s") is None


def test_every_piece_and_pass_resolves_and_lists_the_cells_that_have_it():
    m = manifest.load(ROOT)
    lm = ["olmoe-l1-fed8-4k", "nemotron-l9-fed8-packed",
          "xing4-l5-mtp1-fed8-4k", "kimi-linear-l5-fed8-packed"]
    for cell in lm:
        check_cell(m, cell)
    from perfbench.reducers import lm_pieces
    pieces = [e for e in m.doc["per_layer"] if m.layer_metric(e["name"])[
        "read"].get("reducer") == "lm_pieces"]
    # every field the reducer gives is declared, once
    assert sorted(m.layer_metric(e["name"])["read"]["field"]
                  for e in pieces) == sorted(lm_pieces.EMITS)
    for entry in pieces:
        # the mixer's pieces are the hybrid stack's; the rest every
        # language model's
        assert entry["workloads"] == (
            lm[1:2] if entry["name"].startswith("ssm_") else lm)
        assert entry["moves"] == "round_ms" and entry["layer"] == "round program"
    assert "workloads" not in next(
        e for e in m.doc["per_layer"] if e["name"] == "scopes_emit_s")
