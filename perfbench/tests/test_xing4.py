"""The Xing4.0 cell's own pieces: its manifest entries resolve and touch no
other cell's lists, the configuration's cut against the catalog and its
parameter count, the cost from shapes and measured tokens against a count by
hand, the layer reducer on a made-up trace, a reference self-check (a
document alone against the same document packed, for both losses), and the
cell's walk-through on the CPU."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from perfbench import datasets_lm, flops_xing4, manifest, xplane
from perfbench.evidence import Evidence
from perfbench.tests.entries import check_cell

ROOT = os.path.dirname(manifest.HERE)
CELL, CONFIG = "xing4-l5-mtp1-fed8-4k", "xing4-29b-a4b-l5-mtp1-fed8"
ADDING_UP = ("attention_ms", "x4_hyper_conn_ms", "dense_mlp_ms",
             "shared_expert_ms", "router_ms", "expert_dispatch_ms",
             "experts_ms", "x4_mtp_proj_ms", "lm_head_ms",
             "server_update_ms", "layers_unscoped_ms")
TINY = {"hidden_size": 8, "num_hidden_layers": 3, "first_k_dense_replace": 1,
        "num_nextn_predict_layers": 1, "num_attention_heads": 2,
        "q_lora_rank": 6, "kv_lora_rank": 4, "qk_nope_head_dim": 4,
        "qk_rope_head_dim": 2, "v_head_dim": 4, "intermediate_size": 12,
        "hc_mult": 4, "n_routed_experts": 8, "experts_held": 2,
        "n_shared_experts": 1, "num_experts_per_tok": 2,
        "moe_intermediate_size": 6, "vocab_size": 32}


def test_the_entries_that_list_the_cell_resolve_and_no_other_models_do():
    m = manifest.load(ROOT)
    cell = m.cell(CELL)
    assert cell["config"] == CONFIG and cell["chips"] == 1
    assert cell["window"]["job_rounds"] >= 4 and cell["window"]["jobs"] == 2
    traffic = m.traffic(cell["traffic"])
    assert traffic["driver"] == "train_xing4"
    assert os.path.exists(os.path.join(manifest.HERE, "drivers",
                                       traffic["driver"] + ".py"))
    # the hybrid cell's traffic with another driver
    hybrid = m.traffic("lm-hybrid-epoch1-width1")
    own = ("name", "driver", "what", "trace_chunks", "trace_chunks_why",
           "rehearsal", "check_rounds", "check_rounds_why", "warmup_rounds")
    assert {k: v for k, v in traffic.items() if k not in own} == {
        k: v for k, v in hybrid.items() if k not in own}
    # ONE round is compared, and the warm-up job ends there
    assert traffic["trace_chunks"] == 1
    assert traffic["check_rounds"] == traffic["warmup_rounds"] == 1
    listed = check_cell(m, CELL)
    names = {p["name"] for p in listed}
    assert set(ADDING_UP) <= names
    own = [p for p in listed if p["name"].startswith("x4_")]
    assert own and all(p["moves"] == "round_ms" for p in own)
    for p in own:
        assert m.layer_metric(p["name"])["read"]["kind"] in (
            "trace", "registry", "registry_ratio")
    assert {e["name"] for e in m.metrics_of("end_to_end", CELL)} >= {
        "setup_s", "round_ms", "peak_hbm_mb"}
    assert len(json.dumps(m.doc)) < 64 * 1024


def test_the_configuration_is_the_published_one_cut_to_a_share():
    conf = manifest.load(ROOT).config(CONFIG)
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as fh:
        row = next(json.loads(line) for line in fh if '"Xing4.0-29B-A4B"' in line)
    changed = {k for k, v in row["config"].items() if conf[k] != v}
    assert changed == set(conf["reduced"]) == {
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "vocab_size"}
    assert conf["published"] == {k: row["config"][k] for k in conf["reduced"]}
    assert conf["source"] == row["source_url"]
    assert conf["num_nextn_predict_layers"] == 1        # the module is kept
    assert conf["vocab_size"] * 8 == row["config"]["vocab_size"]
    assert conf["layout"]["chips_sharing_a_layer"] * conf["n_routed_experts"] == 64
    assert conf["num_hidden_layers"] - conf["first_k_dense_replace"] == 4
    from perfbench.drivers.train_xing4 import model_fields
    fields = model_fields(conf)
    assert (fields["n_routed_experts"], fields["experts_held"],
            fields["first_expert"]) == (64, 8, 0)
    assert fields["rope_scaling_factor"] == 64
    # the module's loss weight: the reference reads the file's, the program's
    # is a constant of its task
    from fedtpu.training.task import MTP_LOSS_WEIGHT
    assert conf["mtp_loss_weight"] == MTP_LOSS_WEIGHT == 0.3
    counted = flops_xing4.params(fields)
    assert counted["total"] == conf["parameters"] == 913_473_668
    assert (counted["attention"], counted["hyper_module"],
            counted["routed_expert"]) == (28_411_136, 344_091, 11_010_048)
    memory = conf["memory"]
    assert memory["engine_bytes"] == 12 * conf["parameters"]
    assert (memory["engine_bytes"] < memory["round_account_bytes"]
            <= memory["round_account_bound_bytes"])
    # ISSUE 37's traffic: 16 sequences of 4,096 tokens, one kind of step
    assert (conf["dataset"]["rows"], conf["dataset"]["sequence_length"]) == (
        16, 4096)
    assert conf["experiment"]["fed"]["one_step_kind"] is True


def test_the_cost_of_a_round_is_the_count_by_hand():
    x = np.zeros((2, 2, 10), np.int32)
    x[0, 1, :7] = [1, 1, 1, 2, 2, 2, 2]
    x[1, 1, :] = 1
    counts = datasets_lm.counts(x)
    tokens, pairs = 17, 3 * 4 // 2 + 4 * 5 // 2 + 10 * 11 // 2
    assert (counts["tokens"], counts["attention_pairs"]) == (tokens, pairs)
    cost = flops_xing4.round_cost(TINY, counts, clients=1)
    h, heads, n = 8, 2, 4
    blocks = 3 + 1                      # the main stack's and the module's
    latent = 2 * (h * 6 + 6 * heads * 6 + h * 6 + 4 * heads * 8 + heads * 4 * h)
    core = 2 * heads * (6 + 4) * pairs
    hyper = 2 * n * h * n * (n + 2) + 2 * n * h + 2 * (n * n + n) * h
    expert = 3 * 2 * h * 6
    assert cost["by_part"] == {
        "attn_latent": 3.0 * blocks * tokens * latent,
        "attn_core": 3.0 * blocks * core,
        "hyper_conn": 3.0 * 2 * blocks * tokens * hyper,
        "dense_mlp": 3.0 * tokens * 3 * 2 * h * 12,
        "router": 3.0 * 3 * tokens * 2 * h * 8,
        "experts": 3.0 * 3 * tokens * (2 * 2 / 8) * expert,
        "shared_expert": 3.0 * 3 * tokens * expert,
        "mtp_proj": 3.0 * tokens * 2 * 2 * h * h,
        "head": 3.0 * 2 * tokens * 2 * h * 32}
    assert cost["flops"] == sum(cost["by_part"].values())
    assert cost["core_flops"] == cost["by_part"]["attn_core"]
    assert cost["hyper"] == {"flops": cost["by_part"]["hyper_conn"],
                             "bytes": 2.0 * blocks * tokens * 25 * h * 4}
    attention = h * 6 + 6 + 6 * heads * 6 + h * 6 + 4 + 4 * heads * 8 + heads * 4 * h
    module = n * (n + 2) * n * h + n * (n + 2) + 3
    dense = attention + 2 * h + 2 * module + 3 * h * 12
    sparse = attention + 2 * h + 2 * module + h * 8 + 8 + 3 * 3 * h * 6
    params = 2 * 32 * h + h + dense + 2 * sparse + (2 * h + 2 * h * h + sparse + h)
    assert cost["params"] == params == flops_xing4.params(TINY)["total"]
    # two steps of one client: one writes the working copy
    assert cost["bytes"] == 4.0 * params * (5 * 2 + 2 * 1 + 6)
    assert flops_xing4.held_experts_flops(TINY, 5) == 3 * 5 * 3 * 2 * h * 6


def _view(ops, host=()):
    ops = xplane._self_times(sorted(ops, key=lambda o: (o.start, -o.end)))
    return xplane.TraceView(devices={"/device:TPU:0": ops}, host=list(host),
                            start=0.0, end=max(o.end for o in ops))


def test_lm_layers_sums_the_four_stream_stack_by_innermost_scope():
    op = xplane.Op
    ev = Evidence(manifest=manifest.load(ROOT))
    ev.trace = _view(
        [op("while.1", 0, 1000),                       # self: 1000 - 900
         op("fusion.1 bf16[8]", 0, 300), op("fusion.2 f32[8]", 300, 500),
         op("fusion.3 f32[8]", 500, 700),
         op("ragged-dot-none.3 f32[8]", 700, 900), op("fusion.9 f32[8]", 1000, 1200),
         op("fusion.1 bf16[8]", 1500, 1600)],          # inside the state check
        host=[op("fedtpu.state_check", 1450, 1700)])
    ev.facts.update(
        trace_rounds=2, job_rounds=4, chips=1, model=TINY, lm_positions=200,
        peaks={"bf16_flops_per_s": 1e9, "hbm_bytes_per_s": 1e6},
        cost={"core_flops": 60.0, "hyper": {"flops": 10.0, "bytes": 0.05}})
    ev.sinks["job"] = [
        {"kind": "manifest", "payload": {"config": {"model": {"kind": "xing4"}}}},
        {"kind": "program_scopes", "payload": {
            "program": "round_step",
            "scopes": {"while.1": "client_train", "fusion.1 bf16[8]": "client_train",
                       "fusion.2 f32[8]": "client_train",
                       "fusion.3 f32[8]": "client_train",
                       "ragged-dot-none.3 f32[8]": "client_train",
                       "fusion.9 f32[8]": "aggregate"},
            "layers": {"fusion.1 bf16[8]": "attention",
                       "fusion.2 f32[8]": "hyper_conn",
                       "fusion.3 f32[8]": "attention",
                       "ragged-dot-none.3 f32[8]": "experts",
                       "fusion.9 f32[8]": "server_update"},
            "pieces": {"fusion.1 bf16[8]": "attn_core",
                       "fusion.2 f32[8]": "hc_sinkhorn",
                       "fusion.3 f32[8]": "attn_latent"},
            "modules": {"fusion.3 f32[8]": "mtp",
                        "ragged-dot-none.3 f32[8]": "mtp"},
            "passes": {}}},
        {"kind": "counters", "payload": {"counters": {
            "moe_assignments_held": 40.0, "moe_assignments_total": 320.0,
            "moe_rows_computed": 64.0, "mtp_positions": 150.0,
            "lm_fused_attention_positions": 200.0},
            "gauges": {"attention_padded_width": 256.0}}}]
    assert ev.metric("attention_ms") == pytest.approx(500e-6 / 2)
    assert ev.metric("x4_hyper_conn_ms") == pytest.approx(200e-6 / 2)
    assert ev.metric("experts_ms") == pytest.approx(200e-6 / 2)
    assert ev.metric("server_update_ms") == pytest.approx(200e-6 / 2)
    assert ev.metric("layers_unscoped_ms") == pytest.approx(100e-6 / 2)
    assert ev.metric("dense_mlp_ms") == 0.0
    # the eleven add up to what the two stages took
    assert sum(ev.metric(n) for n in ADDING_UP) == pytest.approx(
        (1000 + 200) * 1e-6 / 2)
    # the pieces, and the module's overlapping sum
    assert ev.metric("x4_attn_latent_ms") == pytest.approx(200e-6 / 2)
    assert ev.metric("x4_hc_sinkhorn_ms") == pytest.approx(200e-6 / 2)
    assert ev.metric("attn_core_ms") == pytest.approx(300e-6 / 2)   # lm_pieces
    assert ev.metric("x4_mtp_ms") == pytest.approx(400e-6 / 2)
    # 60 operations a round in 0.15 us at 1e9 a second
    assert ev.metric("attn_core_mfu") == pytest.approx(100 * 60 / 0.15e-6 / 1e9)
    # 0.05 bytes at 1e6 a second: 5e-8 s; 10 operations: 1e-8 s; bytes bound
    assert ev.notes["x4_hyper_conn_roofline_bound"] == "bytes"
    assert ev.metric("x4_hyper_conn_roofline") == pytest.approx(100 * 5e-8 / 0.1e-6)
    flops = flops_xing4.held_experts_flops(TINY, 10)
    assert ev.metric("experts_mfu") == pytest.approx(100 * flops / 0.1e-6 / 1e9)
    assert ev.metric("experts_held_share_pct") == pytest.approx(12.5)
    assert ev.metric("expert_rows_computed_over_routed") == pytest.approx(1.6)
    assert ev.metric("x4_mtp_positions_pct") == pytest.approx(75.0)
    assert ev.metric("attention_fused_pct") == pytest.approx(100.0)
    assert ev.metric("x4_attention_padded_width") == 256.0


def test_a_program_without_the_scopes_or_counters_gives_nothing():
    ev = Evidence(manifest=manifest.load(ROOT))
    ev.trace = _view([xplane.Op("fusion.1 f32[8]", 0, 100)])
    ev.facts.update(trace_rounds=1)
    ev.sinks["job"] = [{"kind": "program_scopes", "payload": {
        "program": "round_step", "scopes": {"fusion.1 f32[8]": "client_train"},
        "unscoped": []}},
        {"kind": "counters", "payload": {"counters": {"rounds": 3}, "gauges": {}}}]
    m = manifest.load(ROOT)
    for p in m.doc["per_layer"]:
        if CELL in p.get("workloads", ()):
            assert ev.metric(p["name"]) is None, p["name"]


def test_the_reference_gives_a_packed_document_what_it_gives_it_alone():
    """Both losses: two documents in one row count and cost what each does
    alone (attention's mask, RoPE's restart, the module's next token read
    from the same document)."""
    import jax
    import jax.numpy as jnp
    from perfbench import reference_xing4 as ref

    cfg = {"num_attention_heads": 2, "kv_lora_rank": 4, "qk_nope_head_dim": 4,
           "qk_rope_head_dim": 2, "v_head_dim": 4, "num_experts_per_tok": 2,
           "norm_topk_prob": True, "routed_scaling_factor": 2.0, "hc_mult": 2,
           "hc_sinkhorn_iters": 5, "hc_eps": 1e-6, "mhc_h_res_clamp_min": -30,
           "mhc_h_res_clamp_max": 30, "rms_norm_eps": 1e-6, "rope_theta": 10000,
           "rope_scaling": {"factor": 64, "original_max_position_embeddings": 4096,
                            "beta_fast": 32, "beta_slow": 1, "mscale": 1,
                            "mscale_all_dim": 1}, "mtp_loss_weight": 0.3}
    rng = np.random.default_rng(1)
    w = lambda *s: jnp.asarray(0.3 * rng.normal(size=s), jnp.float32)
    hc = lambda: {"phi": w(8, 16), "alpha": 0.1 + w(3), "bias": w(8)}
    attn = lambda: {"norm": 1 + w(8), "q_a": w(8, 6), "q_a_norm": 1 + w(6),
                    "q_b": w(6, 12), "kv_a": w(8, 6), "kv_a_norm": 1 + w(4),
                    "kv_b": w(4, 16), "o": w(8, 8)}
    sparse = lambda: {"attn": attn(), "attn_hc": hc(), "ffn_hc": hc(), "ffn": {
        "norm": 1 + w(8), "router": w(8, 4), "router_bias": w(4),
        "gate": w(2, 8, 6), "up": w(2, 8, 6), "down": w(2, 6, 8),
        "shared_gate": w(8, 6), "shared_up": w(8, 6), "shared_down": w(6, 8)}}
    params = {
        "embed": w(16, 8), "final_norm": 1 + w(8), "head": w(8, 16),
        "dense": ({"attn": attn(), "attn_hc": hc(), "ffn_hc": hc(), "ffn": {
            "norm": 1 + w(8), "gate": w(8, 12), "up": w(8, 12),
            "down": w(12, 8)}},),
        "experts": (sparse(),),
        "mtp": ({"h_norm": 1 + w(8), "e_norm": 1 + w(8), "proj": w(16, 8),
                 "block": sparse(), "final_norm": 1 + w(8)},)}
    tokens = rng.integers(1, 16, 12).astype(np.int32)
    packed = np.stack([tokens, np.array([1] * 5 + [2] * 7, np.int32)])
    alone = lambda lo, hi: np.stack([
        np.pad(tokens[lo:hi], (0, 12 - hi + lo)),
        np.pad(np.ones(hi - lo, np.int32), (0, 12 - hi + lo))])
    with jax.default_matmul_precision("highest"):
        both = ref.sequence_losses(params, jnp.asarray(packed), cfg)
        first = ref.sequence_losses(params, jnp.asarray(alone(0, 5)), cfg)
        second = ref.sequence_losses(params, jnp.asarray(alone(5, 12)), cfg)
    assert float(both[1]) == float(first[1] + second[1]) == 10
    assert float(both[3]) == float(first[3] + second[3]) == 8
    assert float(both[0]) == pytest.approx(float(first[0] + second[0]), rel=1e-5)
    assert float(both[2]) == pytest.approx(float(first[2] + second[2]), rel=1e-5)


def test_the_step_a_block_at_a_time_is_the_gradient_of_the_whole_loss():
    """``compiled_step`` (each block's ``jax.vjp`` in turn, its update
    applied there) gives the parameters, the loss and the four sums that one
    SGD step on ``jax.grad(mean_loss)`` gives, with and without a module."""
    import jax
    import jax.numpy as jnp
    from fedtpu.config import ModelConfig
    from fedtpu.models.registry import build_model
    from perfbench import reference_xing4 as ref

    cfg = {"num_attention_heads": 2, "kv_lora_rank": 4, "qk_nope_head_dim": 4,
           "qk_rope_head_dim": 2, "v_head_dim": 4, "num_experts_per_tok": 2,
           "norm_topk_prob": True, "routed_scaling_factor": 2.0, "hc_mult": 2,
           "hc_sinkhorn_iters": 5, "hc_eps": 1e-6, "mhc_h_res_clamp_min": -30,
           "mhc_h_res_clamp_max": 30, "rms_norm_eps": 1e-6, "rope_theta": 10000,
           "rope_scaling": {"factor": 64, "original_max_position_embeddings": 4096,
                            "beta_fast": 32, "beta_slow": 1, "mscale": 1,
                            "mscale_all_dim": 1}, "mtp_loss_weight": 0.3}
    for modules in (1, 0):
        model = ModelConfig(
            kind="xing4", hidden_size=8, num_attention_heads=2,
            num_hidden_layers=3, first_k_dense_replace=1, intermediate_size=12,
            q_lora_rank=6, kv_lora_rank=4, qk_nope_head_dim=4,
            qk_rope_head_dim=2, v_head_dim=4, n_routed_experts=4,
            experts_held=2, moe_intermediate_size=6, num_experts_per_tok=2,
            norm_topk_prob=True, routed_scaling_factor=2.0, rms_norm_eps=1e-6,
            vocab_size=16, hc_mult=2, hc_sinkhorn_iters=5,
            num_nextn_predict_layers=modules)
        params = build_model(model)[0](jax.random.key(modules))
        rng = np.random.default_rng(2)
        row = jnp.asarray(np.stack([rng.integers(1, 16, 12),
                                    [1] * 5 + [2] * 6 + [0]]), jnp.int32)
        with jax.default_matmul_precision("highest"):
            (loss, sums), grads = jax.value_and_grad(
                lambda q: ref.mean_loss(q, row, cfg), has_aux=True)(params)
        want = jax.tree.map(lambda a, b: a - 0.1 * b, params, grads)
        step = ref.compiled_step(params, row, cfg, 0.1)
        new, got_loss, got_sums = step(jax.tree.map(jnp.copy, params), row)
        assert jax.tree.structure(new) == jax.tree.structure(params)
        assert float(got_loss) == pytest.approx(float(loss), rel=1e-6)
        np.testing.assert_allclose(np.asarray(got_sums), np.asarray(sums),
                                   rtol=1e-6)
        for a, b in zip(jax.tree.leaves(new), jax.tree.leaves(want)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=0,
                                       atol=1e-6)
        assert float(sums[3]) == (7.0 if modules else 0.0)


def test_the_cells_walk_through_on_the_cpu_exits_10():
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    done = subprocess.run(
        [sys.executable, "-m", "perfbench.run", "--workload", CELL,
         "--seed", "2147483999", "--trace", "0", "--rehearse-cpu"], cwd=ROOT,
        env=env, capture_output=True, text=True, timeout=900)
    assert done.returncode == 10, done.stderr[-2000:]
    lines = [json.loads(l) for l in done.stdout.strip().splitlines()]
    last = lines[-1]
    assert last["rehearsal_passed"] is True and last["correct"] is False
    assert last["would_report"] == ["peak_hbm_mb", "round_ms", "setup_s"]
    check = next(l["check"] for l in lines if "check" in l)
    assert check["rounds"] == 1 and check["within"] is True
    assert check["params_share"] <= 1e-3 and check["params_moved"] > 1e-3
    assert check["main_gap"] <= 1e-5 and check["mtp_gap"] <= 1e-5


def test_the_comparison_tells_an_unchanged_state_and_a_loss_off_its_limit():
    """``train_xing4.compare`` on made-up host values: the reference against
    itself is within; a job that left the global where it started reads a
    ``params_share`` of exactly 1 and is NOT within, whatever its losses; a
    loss a limit and a bit away from the reference's is not within, the main
    part and the module's part each; without the job's parameters (a job
    that ran on past the checked rounds) the losses decide alone."""
    from perfbench.drivers.train_xing4 import (MAIN_LOSS_TOLERANCE,
                                               MTP_LOSS_TOLERANCE,
                                               PARAMS_SHARE_TOLERANCE, compare,
                                               limits_of)

    limits = limits_of({})
    assert limits == {"main": MAIN_LOSS_TOLERANCE, "mtp": MTP_LOSS_TOLERANCE,
                      "params_share": PARAMS_SHARE_TOLERANCE}
    assert 0 < PARAMS_SHARE_TOLERANCE < 1       # an unchanged state reads 1
    rng = np.random.default_rng(3)
    start = {"a": rng.normal(size=(5, 7)).astype(np.float32),
             "b": [rng.normal(size=11).astype(np.float32)]}
    moved = {"a": start["a"] + 0.01, "b": [start["b"][0] - 0.02]}
    losses = {k: rng.uniform(9, 10, (1, 8)) for k in ("loss", "main", "mtp")}
    same = compare(losses, moved, losses, moved, start, limits)
    assert same["within"] and same["params_share"] == 0.0
    assert same["params_moved"] == pytest.approx(
        np.sqrt(35 * 0.01 ** 2 + 11 * 0.02 ** 2), rel=1e-5)
    unchanged = compare(losses, start, losses, moved, start, limits)
    assert unchanged["params_share"] == 1.0 and not unchanged["within"]
    near = {"a": moved["a"] + 0.001, "b": moved["b"]}
    assert compare(losses, near, losses, moved, start, limits)["within"]
    for part, limit in (("main", limits["main"]), ("mtp", limits["mtp"])):
        off = {**losses, part: losses[part] + np.eye(1, 8, 3) * 1.01 * limit}
        found = compare(off, moved, losses, moved, start, limits)
        assert not found["within"] and found[f"{part}_gap"] > limit
        assert not compare(off, None, losses, moved, start, limits)["within"]
        inside = {**losses, part: losses[part] + np.eye(1, 8, 3) * 0.9 * limit}
        assert compare(inside, moved, losses, moved, start, limits)["within"]
    assert "params_share" not in compare(losses, None, losses, moved, start,
                                         limits)
    bad = {**losses, "main": losses["main"] * np.nan}
    assert not compare(bad, moved, losses, moved, start, limits)["within"]


def test_the_traced_walk_through_would_report_the_new_metrics():
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    done = subprocess.run(
        [sys.executable, "-m", "perfbench.run", "--workload", CELL,
         "--seed", "7", "--trace", "1", "--rehearse-cpu"], cwd=ROOT,
        env=env, capture_output=True, text=True, timeout=900)
    assert done.returncode == 10, done.stderr[-2000:]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert last["rehearsal_passed"] is True and last["correct"] is False
    would = set(last["would_report"])
    # the registry's and the scopes' (a CPU trace has no device plane: the
    # device-trace metrics need the chip)
    assert {"x4_mtp_positions_pct", "experts_held_share_pct",
            "expert_rows_computed_over_routed", "moe_tokens_dropped",
            "lm_padding_pct", "attention_fused_pct",
            "experts_grouped_pct", "x4_attention_padded_width",
            "expert_load_max_over_mean"} <= would
