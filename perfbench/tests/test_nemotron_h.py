"""The hybrid cell's own pieces: its manifest entries resolve, the
configuration's cut and parameter count, the cost from shapes and measured
tokens against a count by hand, the layer reducer on a made-up trace, the
ratio reader, a reference self-check (the recurrence against a closed form;
a document alone against the same document packed), and the cell's
walk-through on the CPU."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from perfbench import datasets_lm, flops_nemotron_h, manifest, xplane
from perfbench.evidence import Evidence
from perfbench.tests.entries import check_cell

ROOT = os.path.dirname(manifest.HERE)
CELL, CONFIG = "nemotron-l9-fed8-packed", "nemotron-twotower-30b-a3b-l9-fed8"
TINY = {"hidden_size": 8, "hybrid_override_pattern": "ME*E",
        "mamba_num_heads": 2, "mamba_head_dim": 4, "n_groups": 1,
        "ssm_state_size": 4, "conv_kernel": 4, "num_attention_heads": 2,
        "num_key_value_heads": 1, "head_dim": 4, "n_routed_experts": 8,
        "experts_held": 2, "num_experts_per_tok": 2,
        "moe_intermediate_size": 6, "moe_shared_expert_intermediate_size": 10,
        "vocab_size": 32}


def test_the_entries_that_list_the_cell_resolve_and_no_other_models_do():
    m = manifest.load(ROOT)
    cell = m.cell(CELL)
    assert cell["config"] == CONFIG and cell["chips"] == 1
    assert m.traffic(cell["traffic"])["driver"] == "train_nemotron_h"
    assert os.path.exists(os.path.join(
        manifest.HERE, "drivers", m.traffic(cell["traffic"])["driver"] + ".py"))
    listed = check_cell(m, CELL)
    names = {p["name"] for p in listed}
    # the mixer's own, and the layers it shares with the other stacks
    assert {"ssm_proj_ms", "ssm_scan_ms", "ssm_scan_roofline",
            "shared_expert_ms", "attention_ms", "experts_ms", "experts_mfu",
            "lm_head_ms", "layers_unscoped_ms"} <= names
    assert all(p["moves"] == "round_ms" for p in listed
               if "workloads" in p)
    # the cell reports setup_s, one other end-to-end metric, per-layer ones
    assert {e["name"] for e in m.metrics_of("end_to_end", CELL)} >= {
        "setup_s", "round_ms", "peak_hbm_mb"}
    assert len(json.dumps(m.doc)) < 64 * 1024


def test_the_configuration_is_the_published_one_cut_to_a_share():
    conf = manifest.load(ROOT).config(CONFIG)
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as fh:
        row = next(json.loads(line) for line in fh if "TwoTower" in line)
    changed = {k for k, v in row["config"].items() if conf[k] != v}
    assert changed == set(conf["reduced"]) == {
        "num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
        "vocab_size"}
    assert conf["published"] == {k: row["config"][k] for k in conf["reduced"]}
    assert conf["source"] == row["source_url"]
    # the model's first nine layers, an eighth of the vocabulary, 8 held
    assert row["config"]["hybrid_override_pattern"].startswith(
        conf["hybrid_override_pattern"])
    assert len(conf["hybrid_override_pattern"]) == conf["num_hidden_layers"] == 9
    assert conf["vocab_size"] * 8 == row["config"]["vocab_size"]
    assert conf["layout"]["chips_sharing_a_layer"] * conf["n_routed_experts"] == 128
    assert any("SECOND TOWER IS NOT BUILT" in d for d in conf["departures"])
    from perfbench.drivers.train_nemotron_h import model_fields
    fields = model_fields(conf)
    assert (fields["n_routed_experts"], fields["experts_held"],
            fields["first_expert"]) == (128, 8, 0)
    assert flops_nemotron_h.params(fields)["total"] == conf["parameters"] == 666_963_456


def test_the_cost_of_a_round_is_the_count_by_hand():
    x = np.zeros((2, 2, 10), np.int32)
    x[0, 1, :7] = [1, 1, 1, 2, 2, 2, 2]
    x[1, 1, :] = 1
    counts = datasets_lm.counts(x)
    tokens, pairs = 17, 3 * 4 // 2 + 4 * 5 // 2 + 10 * 11 // 2
    assert (counts["tokens"], counts["attention_pairs"]) == (tokens, pairs)
    cost = flops_nemotron_h.round_cost(TINY, counts, clients=1)
    h, width, state, heads = 8, 8, 4, 2
    ssm_proj = tokens * (2 * h * (2 * width + 2 * state + heads) + 2 * width * h
                         + 2 * 4 * (width + 2 * state))
    ssm_scan = tokens * 2 * 2 * heads * 4 * 4
    attention = tokens * (2 * h * (8 + 2 * 4) + 2 * 8 * h) + 2 * 2 * 8 * pairs
    router = 2 * tokens * 2 * h * 8
    experts = 2 * tokens * (2 * 2 / 8) * 2 * 2 * h * 6
    shared = 2 * tokens * 2 * 2 * h * 10
    head = tokens * 2 * h * 32
    assert cost["by_part"] == {
        "ssm_proj": 3.0 * ssm_proj, "ssm_scan": 3.0 * ssm_scan,
        "attention": 3.0 * attention, "router": 3.0 * router,
        "experts": 3.0 * experts, "shared_expert": 3.0 * shared,
        "head": 3.0 * head}
    assert cost["flops"] == sum(cost["by_part"].values())
    mamba = h + h * (2 * width + 2 * state + heads) + 4 * 16 + 16 + 3 * heads + width + width * h
    attn = h + h * (8 + 2 * 4) + 8 * h
    expert_layer = h + h * 8 + 8 + 2 * 2 * h * 6 + 2 * h * 10
    params = 2 * 32 * h + h + mamba + attn + 2 * expert_layer
    assert cost["params"] == params
    # two steps of one client: one writes the working copy
    assert cost["bytes"] == 4.0 * params * (5 * 2 + 2 * 1 + 6)
    assert cost["scan"] == {"flops": 3.0 * ssm_scan,
                            "bytes": 3.0 * tokens * (2 * width + 2 * state + heads) * 4}
    assert flops_nemotron_h.held_experts_flops(TINY, 5) == 3 * 5 * 2 * 2 * h * 6


def _view(ops, host=()):
    ops = xplane._self_times(sorted(ops, key=lambda o: (o.start, -o.end)))
    return xplane.TraceView(devices={"/device:TPU:0": ops}, host=list(host),
                            start=0.0, end=max(o.end for o in ops))


def test_lm_layers_sums_the_hybrid_stack_by_innermost_scope():
    op = xplane.Op
    ev = Evidence(manifest=manifest.load(ROOT))
    ev.trace = _view(
        [op("while.1", 0, 1000),                       # self: 1000 - 900
         op("fusion.1 bf16[8]", 0, 400), op("fusion.2 f32[8]", 400, 700),
         op("ragged-dot-none.3 f32[8]", 700, 900), op("fusion.9 f32[8]", 1000, 1200),
         op("fusion.1 bf16[8]", 1500, 1600)],          # inside the state check
        host=[op("fedtpu.state_check", 1450, 1700)])
    ev.facts.update(
        trace_rounds=2, job_rounds=4, chips=1, model=TINY,
        peaks={"bf16_flops_per_s": 1e9, "hbm_bytes_per_s": 1e6},
        cost={"scan": {"flops": 100.0, "bytes": 0.05}})
    ev.sinks["job"] = [
        {"kind": "manifest", "payload": {"config": {"model": {
            "kind": "nemotron_h"}}}},
        {"kind": "program_scopes", "payload": {
            "program": "round_step",
            "scopes": {"while.1": "client_train", "fusion.1 bf16[8]": "client_train",
                       "fusion.2 f32[8]": "client_train",
                       "ragged-dot-none.3 f32[8]": "client_train",
                       "fusion.9 f32[8]": "aggregate"},
            "layers": {"fusion.1 bf16[8]": "ssm_scan", "fusion.2 f32[8]": "ssm",
                       "ragged-dot-none.3 f32[8]": "experts",
                       "fusion.9 f32[8]": "server_update"}}},
        {"kind": "counters", "payload": {"counters": {
            "moe_assignments_held": 40.0, "moe_assignments_total": 400.0,
            "moe_rows_computed": 64.0}, "gauges": {}}}]
    assert ev.metric("ssm_scan_ms") == pytest.approx(400e-6 / 2)
    assert ev.metric("ssm_proj_ms") == pytest.approx(300e-6 / 2)
    assert ev.metric("experts_ms") == pytest.approx(200e-6 / 2)
    assert ev.metric("server_update_ms") == pytest.approx(200e-6 / 2)
    assert ev.metric("layers_unscoped_ms") == pytest.approx(100e-6 / 2)
    assert ev.metric("shared_expert_ms") == 0.0
    # the ten add up to what the two stages took
    total = sum(ev.metric(n) for n in (
        "ssm_proj_ms", "ssm_scan_ms", "shared_expert_ms", "attention_ms",
        "router_ms", "expert_dispatch_ms", "experts_ms",
        "lm_head_ms", "server_update_ms", "layers_unscoped_ms"))
    assert total == pytest.approx((1000 + 200) * 1e-6 / 2)
    # 0.05 bytes at 1e6 a second bound (5e-8 s; 100 operations take 1e-7...
    # no: 1e-7 s is longer): the operations bound, over 0.2 us of scan
    assert ev.notes["ssm_scan_roofline_bound"] == "flops"
    assert ev.metric("ssm_scan_roofline") == pytest.approx(100 * 1e-7 / 0.2e-6)
    # 10 held assignments a round: 3 * 10 * 4 * 8 * 6 operations in 0.1 us
    flops = flops_nemotron_h.held_experts_flops(TINY, 10)
    assert ev.metric("experts_mfu") == pytest.approx(100 * flops / 0.1e-6 / 1e9)
    assert ev.metric("experts_held_share_pct") == pytest.approx(10.0)
    assert ev.metric("expert_rows_computed_over_routed") == pytest.approx(1.6)


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


def test_the_references_recurrence_is_the_closed_form():
    """One head, one state column, constant decay: ``y_t`` is the sum of
    ``a^(t-s) dt x_s b_s c_t`` over the document's ``s <= t``."""
    import jax
    import jax.numpy as jnp
    from perfbench import reference_nemotron_h as ref

    t, decay_rate = 12, -0.3
    rng = np.random.default_rng(0)
    x, b, c = (rng.normal(size=(t, 1, 1)).astype(np.float32) for _ in range(3))
    dt = np.full((t, 1), 0.5, np.float32)
    starts = np.zeros(t, bool)
    starts[[0, 5]] = True
    with jax.default_matmul_precision("highest"):
        got = np.asarray(ref.recurrence(
            jnp.asarray(x), jnp.asarray(dt), jnp.asarray([decay_rate]),
            jnp.asarray(b), jnp.asarray(c), jnp.asarray(starts)))[:, 0, 0]
    a = np.exp(0.5 * decay_rate)
    want = [sum(a ** (i - s) * 0.5 * x[s, 0, 0] * b[s, 0, 0] * c[i, 0, 0]
                for s in range(0 if i < 5 else 5, i + 1)) for i in range(t)]
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-6)


def test_the_reference_gives_a_packed_document_what_it_gives_it_alone():
    import jax
    import jax.numpy as jnp
    from perfbench import reference_nemotron_h as ref

    cfg = {"hybrid_override_pattern": "ME*", "layer_norm_epsilon": 1e-5,
           "mamba_num_heads": 2, "mamba_head_dim": 4, "n_groups": 1,
           "ssm_state_size": 4, "num_attention_heads": 2,
           "num_key_value_heads": 1, "head_dim": 4, "num_experts_per_tok": 2,
           "norm_topk_prob": True, "routed_scaling_factor": 2.5}
    rng = np.random.default_rng(1)
    w = lambda *s: jnp.asarray(0.3 * rng.normal(size=s), jnp.float32)
    params = {
        "embed": w(16, 8), "final_norm": 1 + w(8), "head": w(8, 16),
        "mamba": ({"norm": 1 + w(8), "in_proj": w(8, 26), "conv_w": w(4, 16),
                   "conv_b": w(16), "dt_bias": w(2), "A_log": w(2), "D": 1 + w(2),
                   "gate_norm": 1 + w(8), "out_proj": w(8, 8)},),
        "experts": ({"norm": 1 + w(8), "router": w(8, 4), "router_bias": w(4),
                     "up": w(2, 8, 6), "down": w(2, 6, 8),
                     "shared_up": w(8, 10), "shared_down": w(10, 8)},),
        "attention": ({"norm": 1 + w(8), "q": w(8, 8), "k": w(8, 4), "v": w(8, 4),
                       "o": w(8, 8)},)}
    tokens = rng.integers(1, 16, 12).astype(np.int32)
    packed = np.stack([tokens, np.array([1] * 5 + [2] * 7, np.int32)])
    alone = lambda lo, hi: np.stack([
        np.pad(tokens[lo:hi], (0, 12 - hi + lo)),
        np.pad(np.ones(hi - lo, np.int32), (0, 12 - hi + lo))])
    with jax.default_matmul_precision("highest"):
        both, n = ref.sequence_loss(params, jnp.asarray(packed), cfg)
        first, n1 = ref.sequence_loss(params, jnp.asarray(alone(0, 5)), cfg)
        second, n2 = ref.sequence_loss(params, jnp.asarray(alone(5, 12)), cfg)
    assert float(n) == float(n1 + n2) == 10
    assert float(both) == pytest.approx(float(first + second), rel=1e-5)


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
    assert check["params_gap"] <= 1e-5 and check["params_moved"] > 1e-3
