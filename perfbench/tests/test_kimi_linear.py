"""The Kimi-Linear cell's own pieces: its manifest entries resolve and touch
no other cell's lists, the configuration's cut against the catalog and its
parameter count, the cost from shapes and measured tokens against a count by
hand, the layer reducer on a made-up trace, reference self-checks (a document
alone against the same document packed; the step a sublayer at a time against
the gradient of the whole loss), the comparison on made-up values, and the
cell's walk-through on the CPU."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from perfbench import datasets_lm, flops_kimi_linear, manifest, xplane
from perfbench.evidence import Evidence
from perfbench.tests.entries import check_cell

ROOT = os.path.dirname(manifest.HERE)
CELL, CONFIG = "kimi-linear-l5-fed8-packed", "kimi-linear-48b-a3b-l5-fed8"
# the delta-rule mixer's own readings; every other is a shared name that
# lists the cell (PR 43: all of them are declared and reach the result line)
OWN = ["kl_kda_proj_ms", "kl_kda_scan_ms", "kl_kda_conv_ms",
       "kl_kda_gates_ms", "kl_kda_scan_roofline", "kl_kda_restarts_per_row",
       "kl_kda_scan_fused_pct", "kl_kda_in_proj_ms", "kl_kda_out_proj_ms"]
ADDING_UP = ("kl_kda_proj_ms", "kl_kda_scan_ms", "attention_ms",
             "dense_mlp_ms", "shared_expert_ms", "router_ms",
             "expert_dispatch_ms", "experts_ms", "lm_head_ms",
             "server_update_ms", "layers_unscoped_ms")
TINY = {"hidden_size": 8, "num_hidden_layers": 5, "first_k_dense_replace": 1,
        "kda_layers": (1, 2, 3, 5), "full_attn_layers": (4,),
        "kda_num_heads": 2, "kda_head_dim": 4, "short_conv_kernel_size": 4,
        "num_attention_heads": 2, "kv_lora_rank": 4, "qk_nope_head_dim": 4,
        "qk_rope_head_dim": 2, "v_head_dim": 4, "intermediate_size": 12,
        "n_routed_experts": 8, "experts_held": 2, "n_shared_experts": 1,
        "num_experts_per_tok": 2, "moe_intermediate_size": 6,
        "vocab_size": 32}
REF_CFG = {"num_attention_heads": 2, "kv_lora_rank": 4, "qk_nope_head_dim": 4,
           "qk_rope_head_dim": 2, "v_head_dim": 4, "num_experts_per_token": 2,
           "moe_renormalize": True, "routed_scaling_factor": 2.446,
           "rms_norm_eps": 1e-5,
           "linear_attn_config": {"num_heads": 2, "head_dim": 4,
                                  "short_conv_kernel_size": 4}}


def test_the_entries_that_list_the_cell_resolve_and_no_other_models_do():
    m = manifest.load(ROOT)
    cell = m.cell(CELL)
    assert cell["config"] == CONFIG and cell["chips"] == 1
    assert cell["window"] == {**cell["window"], "jobs": 2, "job_rounds": 4}
    traffic = m.traffic(cell["traffic"])
    assert traffic["driver"] == "train_kimi_linear"
    assert os.path.exists(os.path.join(manifest.HERE, "drivers",
                                       traffic["driver"] + ".py"))
    # the Xing4.0 cell's traffic with another driver
    xing4 = m.traffic("lm-xing4-epoch1-width1")
    own = ("name", "driver", "what", "check_rounds_why", "trace_chunks_why")
    assert {k: v for k, v in traffic.items() if k not in own} == {
        k: v for k, v in xing4.items() if k not in own}
    assert traffic["trace_chunks"] == 1 and traffic["fixed_job_chunks"] == 2
    assert traffic["check_rounds"] == traffic["warmup_rounds"] == 1
    listed = check_cell(m, CELL)
    names = {p["name"] for p in listed}
    assert set(OWN) | set(ADDING_UP) <= names
    assert {p["name"] for p in listed if p["name"].startswith("kl_")} == set(OWN)
    assert len(m.doc["per_layer"]) <= 128
    for p in listed:
        if "workloads" in p:
            spec = m.layer_metric(p["name"])
            assert spec["moves"] == "round_ms"
            assert spec["read"]["kind"] in ("trace", "registry",
                                            "registry_ratio")
    assert {e["name"] for e in m.metrics_of("end_to_end", CELL)} >= {
        "setup_s", "round_ms", "peak_hbm_mb"}
    assert len(open(os.path.join(ROOT, "BENCHMARK.json")).read()) < 64 * 1024


def test_the_configuration_is_the_published_one_cut_to_a_share():
    conf = manifest.load(ROOT).config(CONFIG)
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as fh:
        row = next(json.loads(line) for line in fh
                   if '"Kimi-Linear-48B-A3B-Instruct"' in line)
    changed = {k for k, v in row["config"].items() if conf[k] != v}
    assert changed == set(conf["reduced"]) == {
        "num_hidden_layers", "linear_attn_config", "num_experts", "vocab_size"}
    assert conf["published"] == {k: row["config"][k] for k in conf["reduced"]}
    assert conf["source"] == row["source_url"]
    # the group's widths stay; its two lists are the first five layers'
    lin, published = conf["linear_attn_config"], row["config"]["linear_attn_config"]
    assert {k: lin[k] for k in ("head_dim", "num_heads",
                                "short_conv_kernel_size")} == {
        k: published[k] for k in ("head_dim", "num_heads",
                                  "short_conv_kernel_size")}
    assert lin["kda_layers"] == [i for i in published["kda_layers"] if i <= 5]
    assert lin["full_attn_layers"] == [
        i for i in published["full_attn_layers"] if i <= 5] == [4]
    assert conf["vocab_size"] * 8 == row["config"]["vocab_size"]
    assert conf["layout"]["chips_sharing_a_layer"] * conf["num_experts"] == 256
    assert conf["layout"]["vocabulary_cut_in"] == 8
    assert conf["num_hidden_layers"] - conf["first_k_dense_replace"] == 4
    from perfbench.drivers.train_kimi_linear import model_fields
    fields = model_fields(conf)
    assert (fields["n_routed_experts"], fields["experts_held"],
            fields["first_expert"]) == (256, 8, 0)
    assert fields["q_lora_rank"] is None and fields["mla_use_nope"] is True
    assert fields["num_nextn_predict_layers"] == 0
    counted = flops_kimi_linear.params(fields)
    assert counted["total"] == conf["parameters"] == 602_450_816
    assert (counted["kda_mixer"], counted["full_mixer"],
            counted["routed_expert"]) == (39_518_368, 29_114_880, 7_077_888)
    memory = conf["memory"]
    assert memory["engine_bytes"] == 12 * conf["parameters"]
    assert (4.3e9 < memory["engine_bytes"] < memory["round_account_bytes"]
            <= memory["round_account_bound_bytes"] == 15.0e9)
    # ISSUE 39's traffic: 16 sequences of 4,096 tokens, one kind of step
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
    assert (counts["tokens"], counts["attention_pairs"]) == (tokens, pairs)
    cost = flops_kimi_linear.round_cost(TINY, counts, clients=1)
    h, heads, width, rank = 8, 2, 8, 4
    proj = 2 * (3 * h * width + 2 * (h * rank + rank * width) + h * 2
                + width * h) + 2 * 4 * 3 * width
    scan = 3 * 2 * 2 * 4 * 4
    latent = 2 * (h * heads * 6 + h * 6 + 4 * heads * 8 + heads * 4 * h)
    core = 2 * heads * (6 + 4) * pairs
    expert = 3 * 2 * h * 6
    assert cost["by_part"] == {
        "kda_proj": 3.0 * 4 * tokens * proj,
        "kda_scan": 3.0 * 4 * tokens * scan,
        "attn_latent": 3.0 * tokens * latent, "attn_core": 3.0 * core,
        "dense_mlp": 3.0 * tokens * 3 * 2 * h * 12,
        "router": 3.0 * 4 * tokens * 2 * h * 8,
        "experts": 3.0 * 4 * tokens * (2 * 2 / 8) * expert,
        "shared_expert": 3.0 * 4 * tokens * expert,
        "head": 3.0 * tokens * 2 * h * 32}
    assert cost["flops"] == sum(cost["by_part"].values())
    assert cost["core_flops"] == cost["by_part"]["attn_core"]
    # q, k, v, g and o a head's width, beta a number a head: 5 w + h forward,
    # the same and do in and five gradients out backward
    assert cost["scan"] == {
        "flops": cost["by_part"]["kda_scan"],
        "bytes": 4.0 * tokens * ((5 * width + 2) + (9 * width + 4)) * 4}
    kda = (3 * h * width + 3 * 4 * width + 2 * (h * rank + rank * width) + 2
           + 2 * width + h * 2 + 4 + width * h)
    full = h * heads * 6 + h * 6 + 4 + 4 * heads * 8 + heads * 4 * h
    sparse = h * 8 + 8 + 3 * 3 * h * 6
    params = (2 * 32 * h + h + 4 * kda + full + 5 * 2 * h + 3 * h * 12
              + 4 * sparse)
    assert cost["params"] == params == flops_kimi_linear.params(TINY)["total"]
    # two steps of one client: one writes the working copy
    assert cost["bytes"] == 4.0 * params * (5 * 2 + 2 * 1 + 6)
    assert flops_kimi_linear.held_experts_flops(TINY, 5) == 3 * 5 * 3 * 2 * h * 6


def _view(ops, host=()):
    ops = xplane._self_times(sorted(ops, key=lambda o: (o.start, -o.end)))
    return xplane.TraceView(devices={"/device:TPU:0": ops}, host=list(host),
                            start=0.0, end=max(o.end for o in ops))


def test_lm_layers_sums_the_delta_rule_stack_by_innermost_scope():
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
        kda_rows=16, peaks={"bf16_flops_per_s": 1e9, "hbm_bytes_per_s": 1e6},
        cost={"core_flops": 60.0, "scan": {"flops": 10.0, "bytes": 0.05}})
    ev.sinks["job"] = [
        {"kind": "manifest", "payload": {"config": {"model": {
            "kind": "kimi_linear"}}}},
        {"kind": "program_scopes", "payload": {
            "program": "round_step",
            "scopes": {"while.1": "client_train", "fusion.1 bf16[8]": "client_train",
                       "fusion.2 f32[8]": "client_train",
                       "fusion.3 f32[8]": "client_train",
                       "ragged-dot-none.3 f32[8]": "client_train",
                       "fusion.9 f32[8]": "aggregate"},
            "layers": {"fusion.1 bf16[8]": "attention",
                       "fusion.2 f32[8]": "kda_scan",
                       "fusion.3 f32[8]": "kda",
                       "ragged-dot-none.3 f32[8]": "experts",
                       "fusion.9 f32[8]": "server_update"},
            "pieces": {"fusion.1 bf16[8]": "attn_core",
                       "fusion.3 f32[8]": "kda_gates"},
            "passes": {"fusion.2 f32[8]": "backward",
                       "fusion.9 f32[8]": "update"}}},
        {"kind": "counters", "payload": {"counters": {
            "moe_assignments_held": 40.0, "moe_assignments_total": 1280.0,
            "moe_rows_computed": 64.0, "kda_document_restarts": 56.0,
            "lm_fused_attention_positions": 200.0,
            "lm_attention_blocks_computed": 30.0,
            "lm_attention_blocks_causal": 40.0},
            "gauges": {"moe_expert_load_max_over_mean": 1.5}}}]
    assert ev.metric("attention_ms") == pytest.approx(300e-6 / 2)
    assert ev.metric("kl_kda_scan_ms") == pytest.approx(200e-6 / 2)
    assert ev.metric("kl_kda_proj_ms") == pytest.approx(200e-6 / 2)
    assert ev.metric("experts_ms") == pytest.approx(200e-6 / 2)
    assert ev.metric("server_update_ms") == pytest.approx(200e-6 / 2)
    assert ev.metric("layers_unscoped_ms") == pytest.approx(100e-6 / 2)
    assert ev.metric("dense_mlp_ms") == 0.0
    # the eleven add up to what the two stages took, and the four passes too
    assert sum(ev.metric(n) for n in ADDING_UP) == pytest.approx(
        (1000 + 200) * 1e-6 / 2)
    assert sum(ev.metric(f"{p}_ms") for p in (
        "forward", "recompute", "backward", "update")) == pytest.approx(
            (1000 + 200) * 1e-6 / 2)
    assert ev.metric("backward_ms") == pytest.approx(200e-6 / 2)
    # the pieces
    assert ev.metric("kl_kda_gates_ms") == pytest.approx(200e-6 / 2)
    assert ev.metric("kl_kda_in_proj_ms") == 0.0
    assert ev.metric("attn_core_ms") == pytest.approx(300e-6 / 2)   # lm_pieces
    # 0.05 bytes at 1e6 a second: 5e-8 s; 10 operations: 1e-8 s; bytes bound
    assert ev.notes["kl_kda_scan_roofline_bound"] == "bytes"
    assert ev.metric("kl_kda_scan_roofline") == pytest.approx(100 * 5e-8 / 0.1e-6)
    # 60 operations a round in 0.15 us at 1e9 a second
    assert ev.metric("attn_core_mfu") == pytest.approx(100 * 60 / 0.15e-6 / 1e9)
    flops = flops_kimi_linear.held_experts_flops(TINY, 10)
    assert ev.metric("experts_mfu") == pytest.approx(100 * flops / 0.1e-6 / 1e9)
    assert ev.metric("experts_held_share_pct") == pytest.approx(3.125)
    assert ev.metric("expert_rows_computed_over_routed") == pytest.approx(1.6)
    assert ev.metric("kl_kda_restarts_per_row") == pytest.approx(3.5)
    assert ev.metric("attention_fused_pct") == pytest.approx(100.0)
    assert ev.metric("attn_blocks_computed_over_causal") == pytest.approx(0.75)
    assert ev.metric("expert_load_max_over_mean") == 1.5
    # every reading the cell lists that this made-up run can give is one
    # the result line would hold: nothing waits on a line of its own
    listed = {p["name"] for p in ev.manifest.metrics_of("per_layer", CELL)}
    for name in (*OWN, *ADDING_UP):
        assert name in listed, name
        # (this made-up run counts no position in the kernels)
        assert (ev.metric(name) is None) == (name == "kl_kda_scan_fused_pct")


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


def _tiny_params(rng):
    import jax.numpy as jnp
    w = lambda *s: jnp.asarray(0.3 * rng.normal(size=s), jnp.float32)
    kda = lambda: {
        "norm": 1 + w(8), "q_proj": w(8, 8), "k_proj": w(8, 8),
        "v_proj": w(8, 8), "q_conv": w(4, 8), "k_conv": w(4, 8),
        "v_conv": w(4, 8), "f_a": w(8, 4), "f_b": w(4, 8), "A_log": w(2),
        "dt_bias": w(8), "b_proj": w(8, 2), "g_a": w(8, 4), "g_b": w(4, 8),
        "g_bias": w(8), "o_norm": 1 + w(4), "o_proj": w(8, 8)}
    full = lambda: {"norm": 1 + w(8), "q": w(8, 12), "kv_a": w(8, 6),
                    "kv_a_norm": 1 + w(4), "kv_b": w(4, 16), "o": w(8, 8)}
    dense = lambda: {"norm": 1 + w(8), "gate": w(8, 12), "up": w(8, 12),
                     "down": w(12, 8)}
    sparse = lambda: {
        "norm": 1 + w(8), "router": w(8, 4), "router_bias": w(4),
        "gate": w(2, 8, 6), "up": w(2, 8, 6), "down": w(2, 6, 8),
        "shared_gate": w(8, 6), "shared_up": w(8, 6), "shared_down": w(6, 8)}
    return {"embed": w(16, 8), "final_norm": 1 + w(8), "head": w(8, 16),
            "layers": ({"mixer": kda(), "ffn": dense()},
                       {"mixer": kda(), "ffn": sparse()},
                       {"mixer": full(), "ffn": sparse()})}


def test_the_reference_gives_a_packed_document_what_it_gives_it_alone():
    """Two documents in one row count and cost what each does alone: the
    recurrence's state, the convolutions and attention's mask all restart."""
    import jax
    import jax.numpy as jnp
    from perfbench import reference_kimi_linear as ref

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
    assert [ref.kind_of(layer[part]) for layer in params["layers"]
            for part in ("mixer", "ffn")] == [
        "kda", "dense", "kda", "experts", "full", "experts"]


def test_the_step_a_sublayer_at_a_time_is_the_gradient_of_the_whole_loss():
    """``compiled_step`` (each sublayer's ``jax.vjp`` in turn, its update
    applied there) gives the parameters, the loss and the two sums that one
    SGD step on ``jax.grad(mean_loss)`` gives."""
    import jax
    import jax.numpy as jnp
    from perfbench import reference_kimi_linear as ref

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


def test_the_comparison_tells_an_unchanged_state_and_a_loss_off_its_limit():
    """``train_kimi_linear.compare`` on made-up host values: the reference
    against itself is within; a job that left the global where it started
    reads a ``params_share`` of exactly 1 and is NOT within, whatever its
    losses; a loss a limit and a bit away from the reference's is not within;
    without the job's parameters (a job that ran on past the checked rounds)
    the losses decide alone."""
    from perfbench.drivers.train_kimi_linear import (LOSS_TOLERANCE,
                                                     PARAMS_SHARE_TOLERANCE,
                                                     compare, limits_of)

    limits = limits_of({})
    assert limits == {"loss": LOSS_TOLERANCE,
                      "params_share": PARAMS_SHARE_TOLERANCE}
    assert 0 < PARAMS_SHARE_TOLERANCE < 1       # an unchanged state reads 1
    rng = np.random.default_rng(3)
    start = {"a": rng.normal(size=(5, 7)).astype(np.float32),
             "b": [rng.normal(size=11).astype(np.float32)]}
    moved = {"a": start["a"] + 0.01, "b": [start["b"][0] - 0.02]}
    losses = rng.uniform(9, 10, (1, 8))
    same = compare(losses, moved, losses, moved, start, limits)
    assert same["within"] and same["params_share"] == 0.0
    assert same["params_moved"] == pytest.approx(
        np.sqrt(35 * 0.01 ** 2 + 11 * 0.02 ** 2), rel=1e-5)
    unchanged = compare(losses, start, losses, moved, start, limits)
    assert unchanged["params_share"] == 1.0 and not unchanged["within"]
    near = {"a": moved["a"] + 0.1 * PARAMS_SHARE_TOLERANCE * 0.01,
            "b": moved["b"]}
    assert compare(losses, near, losses, moved, start, limits)["within"]
    off = losses + np.eye(1, 8, 3) * 1.01 * limits["loss"]
    found = compare(off, moved, losses, moved, start, limits)
    assert not found["within"] and found["loss_gap"] > limits["loss"]
    assert not compare(off, None, losses, moved, start, limits)["within"]
    inside = losses + np.eye(1, 8, 3) * 0.9 * limits["loss"]
    assert compare(inside, moved, losses, moved, start, limits)["within"]
    assert "params_share" not in compare(losses, None, losses, moved, start,
                                         limits)
    assert not compare(losses * np.nan, moved, losses, moved, start,
                       limits)["within"]


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
    assert check["rounds"] == 1 and check["within"] is True
    assert check["params_share"] <= 1e-3 and check["params_moved"] > 1e-3
    assert check["loss_gap"] <= 1e-5


def test_the_traced_walk_through_would_report_the_new_metrics():
    lines = _walk(7, 1)
    last = lines[-1]
    assert last["rehearsal_passed"] is True and last["correct"] is False
    # the registry's (a CPU trace has no device plane: the device-trace
    # metrics need the chip), every one in the result line
    assert not any("kl_table" in l for l in lines)
    assert {"kl_kda_restarts_per_row", "kl_kda_scan_fused_pct",
            "experts_held_share_pct", "expert_rows_computed_over_routed",
            "moe_tokens_dropped", "lm_padding_pct", "attention_fused_pct",
            "experts_grouped_pct", "expert_load_max_over_mean"} <= set(
                last["would_report"])
