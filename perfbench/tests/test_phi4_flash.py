"""The Phi-4-mini-flash cell's own pieces: its manifest entries resolve and
touch no other cell's lists but by naming this cell, the configuration's cut
against the catalog and its parameter count against ``init``'s, the cost
from shapes and measured tokens against a count by hand, the ``p4_layers``
reducer and the cell's counters on a made-up trace, reference self-checks (a
document alone against the same document packed; the step a layer at a time
against the gradient of the whole loss, the tied embedding's two gradients
in one update), and the cell's walk-through on the CPU."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from perfbench import datasets_lm, flops_phi4_flash, manifest, xplane
from perfbench.evidence import Evidence
from perfbench.tests.entries import check_cell

ROOT = os.path.dirname(manifest.HERE)
CELL, CONFIG = "phi4-flash-l8-fed8-4k", "phi4-mini-flash-l8-fed8"
OWN = ["p4_s6_scan_ms", "p4_s6_scan_roofline", "p4_s6_proj_ms",
       "p4_s6_conv_ms", "p4_s6_gate_ms", "p4_gmu_ms", "p4_attn_window_ms",
       "p4_attn_cross_ms", "p4_diff_combine_ms", "p4_tied_embed_grad_ms",
       "p4_s6_scan_chunked_pct", "p4_s6_restarts_per_row",
       "p4_window_pairs_over_causal", "p4_conv_fused_pct"]
SHARED = ["attention_ms", "attn_core_ms", "attn_proj_ms", "attn_core_mfu",
          "attention_fused_pct", "attn_blocks_computed_over_causal",
          "dense_mlp_ms", "lm_head_ms", "embed_ms", "server_update_ms",
          "sgd_pass_ms", "layers_unscoped_ms", "outside_rest_ms",
          "forward_ms", "recompute_ms", "backward_ms", "update_ms",
          "lm_padding_pct"]
# the published pattern at a depth of 8, all held
TINY = {"hidden_size": 8, "num_hidden_layers": 8, "mb_per_layer": 2,
        "layers_held": (), "num_attention_heads": 4, "num_key_value_heads": 2,
        "intermediate_size": 12, "sliding_window": 3, "mamba_d_state": 2,
        "mamba_d_conv": 4, "mamba_expand": 2, "mamba_dt_rank": 0,
        "vocab_size": 32}
REF_CFG = {"num_attention_heads": 4, "num_key_value_heads": 2,
           "num_hidden_layers": 8, "mb_per_layer": 2, "layers_held": (),
           "sliding_window": 3, "layer_norm_eps": 1e-5}


def test_the_entries_that_list_the_cell_resolve_and_no_other_models_do():
    m = manifest.load(ROOT)
    cell = m.cell(CELL)
    assert cell["config"] == CONFIG and cell["chips"] == 1
    assert cell["window"] == {**cell["window"], "jobs": 2}
    traffic = m.traffic(cell["traffic"])
    assert traffic["driver"] == "train_phi4_flash"
    assert os.path.exists(os.path.join(manifest.HERE, "drivers",
                                       traffic["driver"] + ".py"))
    # the Xing4.0 cell's traffic with another driver
    xing4 = m.traffic("lm-xing4-epoch1-width1")
    own = ("name", "driver", "what", "check_rounds_why", "trace_chunks_why")
    assert {k: v for k, v in traffic.items() if k not in own} == {
        k: v for k, v in xing4.items() if k not in own}
    assert traffic["check_rounds"] == traffic["warmup_rounds"] == 1
    listed = check_cell(m, CELL)
    names = {p["name"] for p in listed}
    assert set(OWN) | set(SHARED) <= names
    assert {p["name"] for p in m.doc["per_layer"]
            if p["name"].startswith("p4_")} == set(OWN)
    assert len(OWN) == 14 and len(m.doc["per_layer"]) == 101 <= 128
    for p in m.doc["per_layer"]:
        if p["name"].startswith("p4_"):
            assert p["workloads"] == [CELL]
            assert p["layer"] == "round program" and p["moves"] == "round_ms"
    # no name of the experts', of another model's state-space mixer, of the
    # residual streams or of the delta rule lists the cell
    for p in listed:
        assert not p["name"].startswith(("expert", "moe_", "router", "ssm_",
                                         "x4_", "kl_", "shared_expert")), p
    assert {e["name"] for e in m.metrics_of("end_to_end", CELL)} >= {
        "setup_s", "round_ms", "peak_hbm_mb"}
    assert len(m.doc["workloads"]) == 9
    assert sum(w["chips"] == 4 for w in m.doc["workloads"]) == 1
    assert len(open(os.path.join(ROOT, "BENCHMARK.json")).read()) < 64 * 1024


def test_the_configuration_is_the_published_one_cut_to_a_share():
    conf = manifest.load(ROOT).config(CONFIG)
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as fh:
        row = next(json.loads(line) for line in fh
                   if '"Phi-4-mini-flash-reasoning"' in line)
    changed = {k for k, v in row["config"].items() if conf[k] != v}
    assert changed == {"vocab_size"}
    assert set(conf["reduced"]) == {"layers_held", "vocab_size"}
    assert "layers_held" not in row["config"]       # the new key, for the order
    assert conf["published"] == {"layers_held": None,
                                 "vocab_size": row["config"]["vocab_size"]}
    assert conf["source"] == row["source_url"]
    assert conf["vocab_size"] * 4 == row["config"]["vocab_size"]
    assert conf["layout"]["vocabulary_cut_in"] == 4
    assert conf["layout"]["chips_sharing_a_layer"] == 4
    assert conf["layers_held"] == [0, 1, 2, 3, 16, 17, 18, 19]
    from perfbench.drivers.train_phi4_flash import model_fields
    fields = model_fields(conf)
    assert [flops_phi4_flash.kind_at(i, fields) for i in conf["layers_held"]] \
        == ["s6", "window", "s6", "window", "s6_memory", "full", "gmu",
            "cross"]
    # every kind of the published 32, at the published ratio 9 : 8 : 1 : 7 : 7
    kinds = [flops_phi4_flash.kind_at(i, fields) for i in range(32)]
    assert [kinds.count(k) for k in ("s6", "window", "s6_memory", "full",
                                     "gmu", "cross")] == [8, 8, 1, 1, 7, 7]
    counted = flops_phi4_flash.params(fields)
    assert counted["total"] == conf["parameters"] == 979_332_096
    assert (counted["s6_mixer"], counted["attention_mixer"],
            counted["cross_mixer"], counted["gmu_mixer"],
            counted["feed_forward"]) == (
        41_241_600, 19_668_864, 13_112_704, 26_214_400, 78_643_200)
    assert (counted["s6_layer"], counted["attention_layer"],
            counted["gmu_layer"], counted["cross_layer"]) == (
        119_895_040, 98_322_304, 104_867_840, 91_766_144)
    # the program's own count, from ``init``'s shapes
    import jax
    from fedtpu.config import ModelConfig
    from fedtpu.models.registry import build_model
    shapes = jax.eval_shape(
        build_model(ModelConfig(kind="phi4_flash", **fields))[0],
        jax.random.key(0))
    assert sum(int(np.prod(l.shape)) for l in jax.tree.leaves(shapes)) == (
        conf["parameters"])
    memory = conf["memory"]
    assert memory["engine_bytes"] == 12 * conf["parameters"]
    assert (4.0e9 < memory["engine_bytes"] < memory["round_account_bytes"]
            <= memory["round_account_bound_bytes"] == 15.7e9)
    # ISSUE 44's traffic: 16 sequences of 4,096 tokens, one kind of step
    assert (conf["dataset"]["rows"], conf["dataset"]["sequence_length"]) == (
        16, 4096)
    assert conf["experiment"]["fed"]["one_step_kind"] is True
    xing4 = manifest.load(ROOT).config("xing4-29b-a4b-l5-mtp1-fed8")
    assert conf["dataset"] == xing4["dataset"]
    assert {**conf["experiment"], "model": None} == {
        **xing4["experiment"], "model": None}


def test_the_cost_of_a_round_is_the_count_by_hand():
    x = np.zeros((2, 2, 10), np.int32)
    x[0, 1, :7] = [1, 1, 1, 2, 2, 2, 2]
    x[1, 1, :] = 1
    counts = datasets_lm.counts(x)
    tokens, pairs = 17, 3 * 4 // 2 + 4 * 5 // 2 + 10 * 11 // 2
    # under a window of 3: a token sees min(place + 1, 3)
    windowed = (1 + 2 + 3) + (1 + 2 + 3 + 3) + (1 + 2 + 3 * 8)
    assert (counts["tokens"], counts["attention_pairs"]) == (tokens, pairs)
    assert flops_phi4_flash.window_pairs(x, 3) == windowed
    assert flops_phi4_flash.window_pairs(x, 100) == pairs
    counts["window_pairs"] = windowed
    cost = flops_phi4_flash.round_cost(TINY, counts, clients=1)
    h, d, inner, rank, n, kv, i, v = 8, 2, 16, 1, 2, 4, 12, 32
    proj = 2 * (h * 2 * inner + inner * (rank + 2 * n) + rank * inner
                + inner * h)
    scan = 6 * inner * n
    per_pair = (4 // 2) * (2 * 2 * d + 2 * 2 * 2 * d)
    core = per_pair * (2 * windowed + 2 * pairs)    # 2 window; full + cross
    assert cost["by_part"] == {
        "s6_proj": 3.0 * 3 * tokens * proj,
        "s6_conv": 3.0 * 3 * tokens * 2 * 4 * inner,
        "s6_scan": 3.0 * 3 * tokens * scan,
        "gmu": 3.0 * tokens * 2 * 2 * h * inner,
        "attn_proj": 3.0 * tokens * 2 * (3 * (h * (h + 2 * kv) + h * h)
                                         + 2 * h * h),
        "attn_core": 3.0 * core,
        "dense_mlp": 3.0 * 8 * tokens * 3 * 2 * h * i,
        "head": 3.0 * tokens * 2 * h * v}
    assert cost["flops"] == sum(cost["by_part"].values())
    assert cost["core_flops"] == cost["by_part"]["attn_core"]
    # x, dl and y the inner width, B and C the states: 3 w + 2 n forward;
    # those and dy in, four gradients out backward
    assert cost["scan"] == {
        "flops": cost["by_part"]["s6_scan"],
        "bytes": 3.0 * tokens * ((3 * inner + 2 * n) + (5 * inner + 4 * n)) * 4}
    s6 = (h * 2 * inner + 4 * inner + inner + inner * (rank + 2 * n)
          + rank * inner + inner + inner * n + inner + inner * h)
    attention = h * (h + 2 * kv) + (h + 2 * kv) + h * h + h + 6 * d
    cross = 2 * (h * h + h) + 6 * d
    params = (v * h + 2 * h + 3 * s6 + 3 * attention + 2 * h * inner + cross
              + 8 * (3 * h * i + 4 * h))
    assert cost["params"] == params == flops_phi4_flash.params(TINY)["total"]
    # two steps of one client: one writes the working copy
    assert cost["bytes"] == 4.0 * params * (5 * 2 + 2 * 1 + 6)


def _view(ops, host=()):
    ops = xplane._self_times(sorted(ops, key=lambda o: (o.start, -o.end)))
    return xplane.TraceView(devices={"/device:TPU:0": ops}, host=list(host),
                            start=0.0, end=max(o.end for o in ops))


def test_p4_layers_sums_the_stacks_pieces_and_modules():
    op = xplane.Op
    ev = Evidence(manifest=manifest.load(ROOT))
    ev.trace = _view(
        [op("while.1", 0, 1000),                       # self: 1000 - 900
         op("fusion.1 bf16[8]", 0, 300), op("fusion.2 f32[8]", 300, 500),
         op("fusion.3 f32[8]", 500, 700), op("fusion.4 f32[8]", 700, 800),
         op("fusion.5 f32[8]", 800, 900), op("fusion.9 f32[8]", 1000, 1200),
         op("fusion.2 f32[8]", 1500, 1600)],           # inside the state check
        host=[op("fedtpu.state_check", 1450, 1700)])
    ev.facts.update(
        trace_rounds=2, job_rounds=4, chips=1, model=TINY, lm_positions=200,
        p4_s6_rows=24, p4_causal_pairs=400,
        peaks={"bf16_flops_per_s": 1e9, "hbm_bytes_per_s": 1e6},
        cost={"core_flops": 60.0, "scan": {"flops": 10.0, "bytes": 0.05}})
    staged = dict.fromkeys(["while.1", "fusion.1 bf16[8]", "fusion.2 f32[8]",
                            "fusion.3 f32[8]", "fusion.4 f32[8]",
                            "fusion.5 f32[8]"], "client_train")
    ev.sinks["job"] = [
        {"kind": "manifest", "payload": {"config": {"model": {
            "kind": "phi4_flash"}}}},
        {"kind": "program_scopes", "payload": {
            "program": "round_step",
            "scopes": {**staged, "fusion.9 f32[8]": "aggregate"},
            "layers": {"fusion.1 bf16[8]": "attention",
                       "fusion.2 f32[8]": "ssm", "fusion.3 f32[8]": "ssm",
                       "fusion.4 f32[8]": "attention",
                       "fusion.5 f32[8]": "embed",
                       "fusion.9 f32[8]": "server_update"},
            "pieces": {"fusion.1 bf16[8]": "attn_core",
                       "fusion.2 f32[8]": "s6_scan",
                       "fusion.3 f32[8]": "gmu",
                       "fusion.4 f32[8]": "diff_combine",
                       "fusion.5 f32[8]": "tied_embed_grad"},
            "modules": {"fusion.1 bf16[8]": "attn_window",
                        "fusion.4 f32[8]": "attn_cross"},
            "passes": {"fusion.2 f32[8]": "backward",
                       "fusion.9 f32[8]": "update"}}},
        {"kind": "counters", "payload": {"counters": {
            "s6_chunked_scan_positions": 200.0, "s6_fused_conv_positions": 0.0,
            "s6_document_restarts": 84.0, "lm_window_pairs": 100.0,
            "lm_attention_pairs": 400.0, "lm_padding_tokens": 20.0,
            "lm_fused_attention_positions": 200.0,
            "lm_attention_blocks_computed": 30.0,
            "lm_attention_blocks_causal": 40.0}, "gauges": {}}}]
    half = lambda ns: pytest.approx(ns * 1e-6 / 2)
    assert ev.metric("p4_s6_scan_ms") == half(200)
    assert ev.metric("p4_gmu_ms") == half(200)
    assert ev.metric("p4_diff_combine_ms") == half(100)
    assert ev.metric("p4_tied_embed_grad_ms") == half(100)
    assert ev.metric("p4_attn_window_ms") == half(300)
    assert ev.metric("p4_attn_cross_ms") == half(100)
    assert ev.metric("p4_s6_proj_ms") == 0.0 == ev.metric("p4_s6_conv_ms")
    # the shared names, by lm_layers and lm_pieces
    assert ev.metric("attention_ms") == half(400)
    assert ev.metric("attn_core_ms") == half(300)
    assert ev.metric("attn_proj_ms") == half(100)       # the combination's
    assert ev.metric("embed_ms") == half(100)
    assert ev.metric("server_update_ms") == half(200)
    assert ev.metric("layers_unscoped_ms") == half(200)     # the while, embed
    assert ev.metric("backward_ms") == half(200)
    # 0.05 bytes at 1e6 a second: 5e-8 s; 10 operations: 1e-8 s; bytes bound
    assert ev.notes["p4_s6_scan_roofline_bound"] == "bytes"
    assert ev.metric("p4_s6_scan_roofline") == pytest.approx(
        100 * 5e-8 / 0.1e-6)
    assert ev.metric("attn_core_mfu") == pytest.approx(
        100 * 60 / 0.15e-6 / 1e9)
    assert ev.metric("p4_s6_scan_chunked_pct") == pytest.approx(100.0)
    assert ev.metric("p4_conv_fused_pct") == 0.0
    assert ev.metric("p4_s6_restarts_per_row") == pytest.approx(3.5)
    assert ev.metric("p4_window_pairs_over_causal") == pytest.approx(25.0)
    assert ev.metric("attention_fused_pct") == pytest.approx(100.0)
    assert ev.metric("lm_padding_pct") == pytest.approx(10.0)
    assert ev.metric("attn_blocks_computed_over_causal") == pytest.approx(0.75)
    listed = {p["name"] for p in ev.manifest.metrics_of("per_layer", CELL)}
    for name in (*OWN, *SHARED):
        assert name in listed and ev.metric(name) is not None, name


def test_a_program_without_the_scopes_or_counters_gives_nothing():
    ev = Evidence(manifest=manifest.load(ROOT))
    ev.trace = _view([xplane.Op("fusion.1 f32[8]", 0, 100)])
    ev.facts.update(trace_rounds=1)
    ev.sinks["job"] = [{"kind": "program_scopes", "payload": {
        "program": "round_step", "scopes": {"fusion.1 f32[8]": "client_train"},
        "pieces": {"fusion.1 f32[8]": "kda_gates"}, "unscoped": []}},
        {"kind": "counters", "payload": {"counters": {"rounds": 3}, "gauges": {}}}]
    for name in OWN:
        assert ev.metric(name) is None, name


def _tiny_params(rng):
    import jax.numpy as jnp
    w = lambda *s: jnp.asarray(0.3 * rng.normal(size=s), jnp.float32)
    normed = lambda part: {**part, "norm": 1 + w(8), "norm_bias": w(8)}
    s6 = lambda: normed({
        "in_proj": w(8, 32), "conv_w": w(4, 16), "conv_b": w(16),
        "x_proj": w(16, 5), "dt_proj": w(1, 16), "dt_bias": w(16),
        "A_log": w(16, 2), "D": 1 + w(16), "out_proj": w(16, 8)})
    own = lambda: {"o": w(8, 8), "o_bias": w(8), "sub_norm": 1 + w(4),
                   **{f"lambda_{n}": w(2) for n in ("q1", "k1", "q2", "k2")}}
    attn = lambda: normed({"qkv": w(8, 16), "qkv_bias": w(16), **own()})
    cross = lambda: normed({"q": w(8, 8), "q_bias": w(8), **own()})
    gmu = lambda: normed({"in_proj": w(8, 16), "out_proj": w(16, 8)})
    ffn = lambda: normed({"gate_up": w(8, 24), "down": w(12, 8)})
    mixers = [s6(), attn(), s6(), attn(), s6(), attn(), gmu(), cross()]
    return {"embed": w(16, 8), "final_norm": 1 + w(8), "final_norm_bias": w(8),
            "layers": tuple({"mixer": m, "ffn": ffn()} for m in mixers)}


def test_the_reference_gives_a_packed_document_what_it_gives_it_alone():
    """Two documents in one row count and cost what each does alone: the
    scans' state, the convolutions and the three attentions' masks all
    restart (documents of 5 and 7 under a window of 3)."""
    import jax
    import jax.numpy as jnp
    from perfbench import reference_phi4_flash as ref

    rng = np.random.default_rng(1)
    params = _tiny_params(rng)
    tokens = rng.integers(1, 16, 12).astype(np.int32)
    packed = np.stack([tokens, np.array([1] * 5 + [2] * 7, np.int32)])
    alone = lambda lo, hi: np.stack([
        np.pad(tokens[lo:hi], (0, 12 - hi + lo)),
        np.pad(np.ones(hi - lo, np.int32), (0, 12 - hi + lo))])
    with jax.default_matmul_precision("highest"):
        both = ref.sequence_loss(params, jnp.asarray(packed), REF_CFG)
        first = ref.sequence_loss(params, jnp.asarray(alone(0, 5)), REF_CFG)
        second = ref.sequence_loss(params, jnp.asarray(alone(5, 12)), REF_CFG)
    assert float(both[1]) == float(first[1] + second[1]) == 10
    assert float(both[0]) == pytest.approx(float(first[0] + second[0]), rel=1e-5)
    assert [ref.kind_at(i, REF_CFG) for i in range(8)] == [
        "s6", "window", "s6", "window", "s6_memory", "full", "gmu", "cross"]


def test_the_step_a_layer_at_a_time_is_the_gradient_of_the_whole_loss():
    """``compiled_step`` (each layer's ``jax.vjp`` in turn, its update
    applied there, the cotangents of the memory and of the kept keys and
    values handed back layer by layer, the embedding's two gradients in one
    update) gives the parameters, the loss and the two sums that one SGD step
    on ``jax.grad(mean_loss)`` gives."""
    import jax
    import jax.numpy as jnp
    from perfbench import reference_phi4_flash as ref

    rng = np.random.default_rng(2)
    params = _tiny_params(rng)
    row = jnp.asarray(np.stack([rng.integers(1, 16, 12),
                                [1] * 5 + [2] * 6 + [0]]), jnp.int32)
    with jax.default_matmul_precision("highest"):
        (loss, sums), grads = jax.value_and_grad(
            lambda q: ref.mean_loss(q, row, REF_CFG), has_aux=True)(params)
    want = jax.tree.map(lambda a, b: a - 0.1 * b, params, grads)
    step = ref.compiled_step(params, row, REF_CFG, 0.1)
    new, got_loss, got_sums = step(jax.tree.map(jnp.copy, params), row)
    assert jax.tree.structure(new) == jax.tree.structure(params)
    assert float(got_loss) == pytest.approx(float(loss), rel=1e-6)
    np.testing.assert_allclose(np.asarray(got_sums), np.asarray(sums),
                               rtol=1e-6)
    assert float(sums[1]) == 9.0
    for a, b in zip(jax.tree.leaves(new), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=0,
                                   atol=5e-6)
    # the cross layer's cotangents reach the full layer's key-value columns
    assert float(jnp.abs(grads["layers"][5]["mixer"]["qkv"][:, 8:]).max()) > 0


def test_the_limits_tell_an_unchanged_state():
    from perfbench.drivers.train_kimi_linear import compare
    from perfbench.drivers.train_phi4_flash import (LOSS_TOLERANCE,
                                                    PARAMS_SHARE_TOLERANCE,
                                                    limits_of)
    limits = limits_of({})
    assert limits == {"loss": LOSS_TOLERANCE,
                      "params_share": PARAMS_SHARE_TOLERANCE}
    assert 0 < PARAMS_SHARE_TOLERANCE < 1       # an unchanged state reads 1
    rng = np.random.default_rng(3)
    start = {"a": rng.normal(size=(5, 7)).astype(np.float32)}
    moved = {"a": start["a"] + 0.01}
    losses = rng.uniform(9, 10, (2, 8))
    assert compare(losses, moved, losses, moved, start, limits)["within"]
    unchanged = compare(losses, start, losses, moved, start, limits)
    assert unchanged["params_share"] == 1.0 and not unchanged["within"]
    off = losses + np.eye(2, 8, 3) * 1.01 * limits["loss"]
    assert not compare(off, moved, losses, moved, start, limits)["within"]


def _walk(seed: int, trace: int):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    done = subprocess.run(
        [sys.executable, "-m", "perfbench.run", "--workload", CELL,
         "--seed", str(seed), "--trace", str(trace), "--rehearse-cpu"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert done.returncode == 10, done.stderr[-2000:]
    return [json.loads(l) for l in done.stdout.strip().splitlines()]


def test_the_cells_walk_through_on_the_cpu_exits_10():
    lines = _walk(2147483999, 0)
    last = lines[-1]
    assert last["rehearsal_passed"] is True and last["correct"] is False
    assert last["would_report"] == ["peak_hbm_mb", "round_ms", "setup_s"]
    check = next(l["check"] for l in lines if "check" in l)
    assert check["rounds"] == 1 and check["within"]
    # float32 on both sides: far inside the rehearsal's limits
    assert check["loss_gap"] <= 1e-5 and check["params_share"] <= 1e-4


def test_the_traced_walk_through_reads_the_cells_counters():
    lines = _walk(5, 1)
    last = lines[-1]
    assert last["rehearsal_passed"] is True
    # no device trace on the CPU: the counters are what a walk can read
    assert {"p4_s6_scan_chunked_pct", "p4_s6_restarts_per_row",
            "p4_window_pairs_over_causal", "p4_conv_fused_pct",
            "attention_fused_pct", "lm_padding_pct"} <= set(
                last["would_report"])
