"""The Solar-Open2 cell's own pieces: its manifest entries resolve, the three
new metrics list the cell alone and the cell's name stands on the 36 lists of
the Kimi-Linear cell's; the configuration's cut against the catalog, its own
parameter sum and the share the reference is given; the cost from shapes and
measured tokens against a count by hand; the gate's reducer and the two
counters' readers on a made-up run (and nothing from a program without
them); reference self-checks (a document alone against the same document
packed; the step a sublayer at a time against the gradient of the whole
loss); and the cell's walk-through on the CPU."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from perfbench import datasets_lm, flops_solar_open2, manifest, xplane
from perfbench.evidence import Evidence

ROOT = os.path.dirname(manifest.HERE)
CELL, CONFIG = "solar-open2-l4-fed8-packed", "solar-open2-250b-l4-fed8"
KIMI = "kimi-linear-l5-fed8-packed"
OWN = ["so2_attn_gate_ms", "so2_kda_steps_over_one_pct",
       "so2_heads_held_share_pct"]
ADDING_UP = ("kl_kda_proj_ms", "kl_kda_scan_ms", "attention_ms",
             "shared_expert_ms", "router_ms", "expert_dispatch_ms",
             "experts_ms", "lm_head_ms", "server_update_ms",
             "layers_unscoped_ms")
TINY = {"hidden_size": 8, "num_hidden_layers": 4, "gqa_layers": (0,),
        "kda_num_heads": 2, "kda_head_dim": 4, "short_conv_kernel_size": 4,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 4,
        "use_gqa_gate": True, "kda_allow_neg_eigval": True,
        "n_routed_experts": 8, "experts_held": 2, "n_shared_experts": 1,
        "num_experts_per_tok": 2, "moe_intermediate_size": 6,
        "vocab_size": 32}
REF_CFG = {"num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 4,
           "num_experts_per_tok": 2, "norm_topk_prob": True,
           "routed_scaling_factor": 1, "rms_norm_eps": 1e-5,
           "use_gqa_gate": True, "kda_allow_neg_eigval": True,
           "linear_attn_config": {"num_heads": 2, "head_dim": 4,
                                  "short_conv_kernel_size": 4}}


def test_the_manifest_resolves_and_every_new_metric_lists_the_cell():
    m = manifest.load(ROOT)
    cell = m.cell(CELL)
    assert (cell["config"], cell["chips"]) == (CONFIG, 1)
    assert cell["window"]["jobs"] == 2 and cell["window"]["job_rounds"] >= 2
    assert "102 rows" in cell["why"] and "quarter of their heads" in cell["why"]
    traffic = m.traffic(cell["traffic"])
    assert traffic["driver"] == "train_solar_open2"
    assert os.path.exists(os.path.join(manifest.HERE, "drivers",
                                       traffic["driver"] + ".py"))
    # the Kimi-Linear cell's traffic with another driver
    kimi = m.traffic("lm-kimi-linear-epoch1-width1")
    own = ("name", "driver", "what", "trace_chunks_why")
    assert {k: v for k, v in traffic.items() if k not in own} == {
        k: v for k, v in kimi.items() if k not in own}
    listed = {p["name"]: p for p in m.metrics_of("per_layer", CELL)}
    # the three this PR brings list this cell alone, each a file that agrees
    for name in OWN:
        assert listed[name]["workloads"] == [CELL], name
        spec = m.layer_metric(name)
        assert all(spec[k] == listed[name][k] for k in (
            "unit", "layer", "moves", "better", "source")), name
    from perfbench.reducers import so2_layers
    assert m.layer_metric("so2_attn_gate_ms")["read"] == {
        "kind": "trace", "reducer": "so2_layers", "field": "so2_attn_gate_ms"}
    assert "so2_attn_gate_ms" in so2_layers.EMITS
    assert [p["name"] for p in m.doc["per_layer"][-3:]] == OWN
    # and the cell's name stands last on the Kimi-Linear cell's lists but
    # the leading dense layer's, which this stack has not
    theirs = {p["name"] for p in m.doc["per_layer"]
              if KIMI in p.get("workloads", ())}
    ours = {p["name"] for p in m.doc["per_layer"]
            if CELL in p.get("workloads", ())}
    assert theirs - ours == {"dense_mlp_ms"} and ours - theirs == set(OWN)
    assert len(ours) == 36 + 3 and set(ADDING_UP) <= ours
    for p in m.doc["per_layer"]:
        if CELL in p.get("workloads", ()):
            assert p["workloads"][-1] == CELL, p["name"]
    assert len(m.doc["per_layer"]) == 105 <= 128
    assert {e["name"] for e in m.metrics_of("end_to_end", CELL)} >= {
        "setup_s", "round_ms", "peak_hbm_mb"}
    assert len(open(os.path.join(ROOT, "BENCHMARK.json")).read()) < 64 * 1024
    assert [c["name"] for c in m.doc["configs"]][-1] == CONFIG
    assert [w["name"] for w in m.doc["workloads"]][-1] == CELL


def test_the_configuration_is_the_published_one_cut_to_a_share():
    conf = manifest.load(ROOT).config(CONFIG)
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as fh:
        row = next(json.loads(line) for line in fh
                   if '"Solar-Open2-250B"' in line)
    changed = {k for k, v in row["config"].items() if conf[k] != v}
    assert changed == set(conf["reduced"]) == {
        "num_hidden_layers", "gqa_layers", "n_routed_experts",
        "num_attention_heads", "num_key_value_heads", "linear_attn_config",
        "vocab_size"}
    assert conf["published"] == {k: row["config"][k] for k in conf["reduced"]}
    assert conf["source"] == row["source_url"]
    # no width is cut: every head keeps its 128, the group's taps stay
    lin, published = conf["linear_attn_config"], row["config"]["linear_attn_config"]
    assert {**lin, "num_heads": 64} == published
    assert conf["head_dim"] == lin["head_dim"] == 128
    assert conf["gqa_layers"] == [
        i for i in row["config"]["gqa_layers"] if i < 4] == [0]
    layout = conf["layout"]
    assert (layout["chips"], layout["chips_sharing_a_layer"],
            layout["chips_sharing_a_mixer"], layout["vocabulary_cut_in"],
            layout["first_expert"]) == (1, 40, 4, 8, 0)
    assert conf["n_routed_experts"] * 40 == 320
    assert conf["num_attention_heads"] * 4 == lin["num_heads"] * 4 == 64
    assert conf["num_key_value_heads"] * 4 == 8
    assert conf["vocab_size"] * 8 == row["config"]["vocab_size"]
    for key in ("assumed", "departures", "reduced_why", "model_keys_why"):
        assert conf[key], key
    # the file's own sum
    s = conf["parameter_sum"]
    assert (s["kda_mixer"], s["gqa_mixer"], s["feed_forward"]) == (
        35_221_648, 27_262_976, 142_868_800)
    assert (3 * s["kda_mixer"] + s["gqa_mixer"]
            + 4 * (s["feed_forward"] + s["pre_norms"]) + s["embedding"]
            + s["head"] + s["final_norm"]) == conf["parameters"] == 905_766_576
    from perfbench.drivers.train_solar_open2 import (model_fields,
                                                     reference_config)
    fields = model_fields(conf)
    assert (fields["n_routed_experts"], fields["experts_held"],
            fields["first_expert"]) == (320, 8, 0)
    assert fields["gqa_layers"] == (0,) and fields["kda_num_heads"] == 16
    assert fields["use_gqa_gate"] and fields["kda_allow_neg_eigval"]
    counted = flops_solar_open2.params(fields)
    assert counted["total"] == conf["parameters"]
    assert (counted["kda_mixer"], counted["gqa_mixer"],
            counted["feed_forward"], counted["routed_expert"]) == (
        s["kda_mixer"], s["gqa_mixer"], s["feed_forward"], 15_728_640)
    # the reference is given the same share: the heads, and through the
    # parameters' shapes the experts and the vocabulary's rows
    given = reference_config(conf)
    assert (given["num_attention_heads"], given["num_key_value_heads"],
            given["linear_attn_config"]["num_heads"], given["first_expert"]) == (
        fields["num_attention_heads"], fields["num_key_value_heads"],
        fields["kda_num_heads"], fields["first_expert"])
    with pytest.raises(ValueError, match="kda_use_full_proj"):
        model_fields({**conf, "kda_use_full_proj": True})
    memory = conf["memory"]
    assert memory["engine_bytes"] == 12 * conf["parameters"]
    assert (memory["round_account_floor_bytes"] == 4.0e9
            < memory["engine_bytes"] < memory["round_account_bytes"]
            < memory["round_account_bound_bytes"] == 15.0e9)
    # ISSUE 47's traffic: the Kimi-Linear cell's
    kimi = manifest.load(ROOT).config("kimi-linear-48b-a3b-l5-fed8")
    assert conf["dataset"] == kimi["dataset"]
    assert {**conf["experiment"], "model": None} == {
        **kimi["experiment"], "model": None}


def test_the_cost_of_a_round_is_the_count_by_hand():
    x = np.zeros((2, 2, 10), np.int32)
    x[0, 1, :7] = [1, 1, 1, 2, 2, 2, 2]
    x[1, 1, :] = 1
    counts = datasets_lm.counts(x)
    tokens, pairs = 17, 3 * 4 // 2 + 4 * 5 // 2 + 10 * 11 // 2
    cost = flops_solar_open2.round_cost(TINY, counts, clients=1)
    h, width, rank, q, kv = 8, 8, 4, 16, 8
    proj = 2 * (3 * h * width + 2 * (h * rank + rank * width) + h * 2
                + width * h) + 2 * 4 * 3 * width
    expert = 3 * 2 * h * 6
    assert cost["by_part"] == {
        "kda_proj": 3.0 * 3 * tokens * proj,
        "kda_scan": 3.0 * 3 * tokens * 3 * 2 * 2 * 4 * 4,
        "attn_proj": 3.0 * tokens * 2 * (2 * h * q + 2 * h * kv),
        "attn_gate": 3.0 * tokens * (2 * h * q + q),
        "attn_core": 3.0 * 2 * 4 * (4 + 4) * pairs,
        "router": 3.0 * 4 * tokens * 2 * h * 8,
        "experts": 3.0 * 4 * tokens * (2 * 2 / 8) * expert,
        "shared_expert": 3.0 * 4 * tokens * expert,
        "head": 3.0 * tokens * 2 * h * 32}
    assert cost["flops"] == sum(cost["by_part"].values())
    assert cost["core_flops"] == cost["by_part"]["attn_core"]
    assert cost["scan"] == {
        "flops": cost["by_part"]["kda_scan"],
        "bytes": 3.0 * tokens * ((5 * width + 2) + (9 * width + 4)) * 4}
    kda = (3 * h * width + 3 * 4 * width + 2 * (h * rank + rank * width) + 2
           + 2 * width + h * 2 + 4 + width * h)
    gqa = 3 * h * q + 2 * h * kv
    sparse = h * 8 + 8 + 3 * 3 * h * 6
    params = 2 * 32 * h + h + 3 * kda + gqa + 4 * (2 * h + sparse)
    assert cost["params"] == params == flops_solar_open2.params(TINY)["total"]
    assert cost["bytes"] == 4.0 * params * (5 * 2 + 2 * 1 + 6)
    assert flops_solar_open2.held_experts_flops(TINY, 5) == 3 * 5 * 3 * 2 * h * 6


def _view(ops, host=()):
    ops = xplane._self_times(sorted(ops, key=lambda o: (o.start, -o.end)))
    return xplane.TraceView(devices={"/device:TPU:0": ops}, host=list(host),
                            start=0.0, end=max(o.end for o in ops))


def _made_up_run(pieces, counters):
    op = xplane.Op
    ev = Evidence(manifest=manifest.load(ROOT))
    ev.trace = _view(
        [op("fusion.1 bf16[8]", 0, 300), op("fusion.2 f32[8]", 300, 500),
         op("fusion.3 f32[8]", 500, 700), op("fusion.9 f32[8]", 1000, 1200),
         op("fusion.2 f32[8]", 1500, 1600)],           # inside the state check
        host=[op("fedtpu.state_check", 1450, 1700)])
    ev.facts.update(
        trace_rounds=2, job_rounds=4, chips=1, model=TINY, lm_positions=200,
        kda_rows=24, so2_published_head_steps=4 * 17 * 3 * 8,
        peaks={"bf16_flops_per_s": 1e9, "hbm_bytes_per_s": 1e6},
        cost={"core_flops": 60.0, "scan": {"flops": 10.0, "bytes": 0.05}})
    ev.sinks["job"] = [
        {"kind": "manifest", "payload": {"config": {"model": {
            "kind": "solar_open2"}}}},
        {"kind": "program_scopes", "payload": {
            "program": "round_step",
            "scopes": {"fusion.1 bf16[8]": "client_train",
                       "fusion.2 f32[8]": "client_train",
                       "fusion.3 f32[8]": "client_train",
                       "fusion.9 f32[8]": "aggregate"},
            "layers": {"fusion.1 bf16[8]": "attention",
                       "fusion.2 f32[8]": "attention",
                       "fusion.3 f32[8]": "kda_scan",
                       "fusion.9 f32[8]": "server_update"},
            "pieces": pieces}},
        {"kind": "counters", "payload": {"counters": counters, "gauges": {}}}]
    return ev


def test_the_gates_reducer_and_the_counters_readers_on_a_made_up_run():
    ev = _made_up_run(
        {"fusion.1 bf16[8]": "attn_core", "fusion.2 f32[8]": "attn_gate"},
        {"kda_head_steps": 4 * 17 * 3 * 2.0, "kda_steps_over_one": 51.0,
         "moe_assignments_held": 40.0, "moe_assignments_total": 1280.0})
    # the gate's operations are attention's, outside its core: a part of
    # attn_proj_ms; the one inside the state check is left out
    assert ev.metric("so2_attn_gate_ms") == pytest.approx(200e-6 / 2)
    assert ev.metric("attn_proj_ms") == pytest.approx(200e-6 / 2)
    assert ev.metric("attn_core_ms") == pytest.approx(300e-6 / 2)
    assert ev.metric("attention_ms") == pytest.approx(500e-6 / 2)
    assert ev.metric("kl_kda_scan_ms") == pytest.approx(200e-6 / 2)
    assert ev.metric("so2_kda_steps_over_one_pct") == pytest.approx(12.5)
    assert ev.metric("so2_heads_held_share_pct") == pytest.approx(25.0)
    # lm_layers finds this model's module by its kind
    flops = flops_solar_open2.held_experts_flops(TINY, 10)
    assert flops > 0
    assert ev.metric("experts_mfu") is None       # no time under ``experts``
    assert ev.metric("attn_core_mfu") == pytest.approx(100 * 60 / 0.15e-6 / 1e9)


def test_a_program_without_the_scope_or_the_counters_gives_nothing():
    """What a parent of this PR emits: no piece ``attn_gate``, neither
    counter. Each new reader returns nothing and does not raise."""
    ev = _made_up_run({"fusion.1 bf16[8]": "attn_core"},
                      {"kda_positions": 12.0})
    for name in OWN:
        assert ev.metric(name) is None, name
    bare = Evidence(manifest=manifest.load(ROOT))
    for name in OWN:
        assert bare.metric(name) is None, name


def _tiny_params(rng):
    import jax.numpy as jnp
    w = lambda *s: jnp.asarray(0.3 * rng.normal(size=s), jnp.float32)
    kda = lambda: {
        "norm": 1 + w(8), "q_proj": w(8, 8), "k_proj": w(8, 8),
        "v_proj": w(8, 8), "q_conv": w(4, 8), "k_conv": w(4, 8),
        "v_conv": w(4, 8), "f_a": w(8, 4), "f_b": w(4, 8), "A_log": w(2),
        "dt_bias": w(8), "b_proj": w(8, 2), "g_a": w(8, 4), "g_b": w(4, 8),
        "g_bias": w(8), "o_norm": 1 + w(4), "o_proj": w(8, 8)}
    gqa = lambda: {"norm": 1 + w(8), "q": w(8, 16), "k": w(8, 8),
                   "v": w(8, 8), "gate": w(8, 16), "o": w(16, 8)}
    sparse = lambda: {
        "norm": 1 + w(8), "router": w(8, 4), "router_bias": w(4),
        "gate": w(2, 8, 6), "up": w(2, 8, 6), "down": w(2, 6, 8),
        "shared_gate": w(8, 6), "shared_up": w(8, 6), "shared_down": w(6, 8)}
    return {"embed": w(16, 8), "final_norm": 1 + w(8), "head": w(8, 16),
            "layers": ({"mixer": gqa(), "ffn": sparse()},
                       {"mixer": kda(), "ffn": sparse()},
                       {"mixer": kda(), "ffn": sparse()})}


def test_the_reference_gives_a_packed_document_what_it_gives_it_alone():
    """Two documents in one row count and cost what each does alone: the
    recurrence's state, the convolutions and attention's mask all restart;
    and the reference imports nothing of the program."""
    import jax
    import jax.numpy as jnp
    from perfbench import reference_solar_open2 as ref

    with open(ref.__file__) as fh:
        assert "fedtpu" not in "".join(
            line for line in fh if line.startswith(("import", "from")))
    rng = np.random.default_rng(1)
    params = _tiny_params(rng)
    tokens = rng.integers(1, 16, 12).astype(np.int32)
    packed = np.stack([tokens, np.array([1] * 5 + [2] * 7, np.int32)])
    alone = lambda lo, hi: np.stack([
        np.pad(tokens[lo:hi], (0, 12 - hi + lo)),
        np.pad(np.ones(hi - lo, np.int32), (0, 12 - hi + lo))])
    with jax.default_matmul_precision("highest"):
        both = ref.mean_loss(params, jnp.asarray(packed), REF_CFG)[1]
        first = ref.mean_loss(params, jnp.asarray(alone(0, 5)), REF_CFG)[1]
        second = ref.mean_loss(params, jnp.asarray(alone(5, 12)), REF_CFG)[1]
    assert float(both[1]) == float(first[1] + second[1]) == 10
    assert float(both[0]) == pytest.approx(float(first[0] + second[0]), rel=1e-5)
    assert [ref.kind_of(layer[part]) for layer in params["layers"]
            for part in ("mixer", "ffn")] == [
        "gqa", "experts", "kda", "experts", "kda", "experts"]
    # the step's factor and the gate are the config's to switch
    x = jnp.asarray(rng.normal(size=(12, 8)), jnp.float32)
    kda_layer, gqa_layer = params["layers"][1]["mixer"], params["layers"][0]["mixer"]
    assert float((ref.step_size(kda_layer, x, REF_CFG)
                  / ref.step_size(kda_layer, x, {**REF_CFG, "kda_allow_neg_eigval": False})
                  ).mean()) == pytest.approx(2.0)
    assert ref.context_gate(gqa_layer, x, {**REF_CFG, "use_gqa_gate": False}) == 1.0
    assert ref.context_gate(gqa_layer, x, REF_CFG).shape == (12, 16)


def test_the_step_a_sublayer_at_a_time_is_the_gradient_of_the_whole_loss():
    """``compiled_step`` (each sublayer's ``jax.vjp`` in turn, its update
    applied there) gives the parameters, the loss and the two sums that one
    SGD step on ``jax.grad(mean_loss)`` gives."""
    import jax
    import jax.numpy as jnp
    from perfbench import reference_solar_open2 as ref

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


def test_the_sums_on_the_host_are_the_kimi_references_fedavgm():
    """``fedavgm_rounds`` keeps the global, the momentum and the round's sum
    on the host and updates them in place; with a smooth made-up step four
    rounds are ``reference_kimi_linear.fedavgm_rounds``' to the rounding of
    a sum, and the caller's arrays are not written."""
    import jax
    import jax.numpy as jnp
    from perfbench import reference_kimi_linear as base
    from perfbench import reference_solar_open2 as ref

    rng = np.random.default_rng(0)
    params = {"a": rng.normal(size=(5, 7)).astype(np.float32),
              "b": [rng.normal(size=11).astype(np.float32)]}
    kept = jax.tree.map(np.copy, params)
    rows = [rng.integers(1, 9, (n, 2, 6)).astype(np.int32) for n in (1, 2, 3)]

    def step(p, row):
        to = 0.1 * row[0].astype(jnp.float32).mean()
        loss = sum(((x - to) ** 2).sum() for x in jax.tree.leaves(p))
        return (jax.tree.map(lambda x: x - 0.1 * (x - to), p), loss,
                (loss, row[1].sum().astype(jnp.float32)))

    ours = ref.fedavgm_rounds(params, rows, 4, {}, 0.1, step=step)
    theirs = base.fedavgm_rounds(params, rows, 4, {}, 0.1, step=step)
    np.testing.assert_allclose(ours[0], theirs[0], rtol=1e-6)
    for a, b, c, d in zip(*map(jax.tree.leaves, (ours[1], theirs[1], params,
                                                 kept))):
        np.testing.assert_allclose(a, b, rtol=0, atol=2e-6)
        assert (c == d).all() and float(np.abs(a - c).max()) > 0.1


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
    assert {"so2_kda_steps_over_one_pct", "so2_heads_held_share_pct",
            "kl_kda_restarts_per_row", "kl_kda_scan_fused_pct",
            "experts_held_share_pct", "expert_rows_computed_over_routed",
            "moe_tokens_dropped", "lm_padding_pct", "attention_fused_pct",
            "experts_grouped_pct", "expert_load_max_over_mean"} <= set(
                last["would_report"])
