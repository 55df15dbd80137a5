"""The shared-global engine (fedtpu.parallel.stateless) and the task
interface (fedtpu.training.task): against the plain reference's FedAvgM on a
tiny OLMoE, against the resident engine's server-optimizer path on the MLP,
on uneven shards cut into minibatches, across a mesh, and what it refuses."""

import contextlib
import dataclasses
import functools
import io
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedtpu.config import (DataConfig, ExperimentConfig, FedConfig,
                           ModelConfig, OptimConfig, RunConfig, ShardConfig,
                           TelemetryConfig, get_preset)
from fedtpu.data import load_dataset
from fedtpu.models.registry import build_model
from fedtpu.orchestration import loop
from fedtpu.orchestration.loop import build_experiment, run_experiment
from fedtpu.parallel import round as round_mod
from fedtpu.parallel.round import LAYERS
from perfbench import reference_lm, reference_nemotron_h

SGD = OptimConfig(name="sgd", learning_rate=0.05, momentum=0.0,
                  steplr_step_size=2, steplr_gamma=0.5)


def tiny_olmoe(rounds=2, **run):
    cfg = get_preset("olmoe-1b-7b-l1")
    return cfg.replace(
        model=dataclasses.replace(
            cfg.model, hidden_size=32, num_attention_heads=4, num_experts=8,
            num_experts_per_tok=2, intermediate_size=16, vocab_size=128,
            compute_dtype="float32"),
        data=dataclasses.replace(cfg.data, synthetic_rows=10,
                                 synthetic_features=48),
        shard=dataclasses.replace(cfg.shard, num_clients=4),
        optim=dataclasses.replace(cfg.optim, learning_rate=0.5),
        fed=dataclasses.replace(cfg.fed, rounds=rounds, init_seed=3),
        run=dataclasses.replace(cfg.run, mesh_devices=1, **run))


def tiny_nemotron_h(rounds=2, **run):
    cfg = get_preset("nemotron-h-30b-a3b-l9")
    return cfg.replace(
        model=dataclasses.replace(
            cfg.model, hidden_size=48, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, num_hidden_layers=5,
            hybrid_override_pattern="ME*ME", mamba_num_heads=8,
            mamba_head_dim=8, n_groups=2, ssm_state_size=16, chunk_size=16,
            n_routed_experts=16, experts_held=4, first_expert=8,
            num_experts_per_tok=3, moe_intermediate_size=24,
            moe_shared_expert_intermediate_size=40, vocab_size=128,
            compute_dtype="float32"),
        data=dataclasses.replace(cfg.data, synthetic_rows=10,
                                 synthetic_features=48),
        shard=dataclasses.replace(cfg.shard, num_clients=4),
        optim=dataclasses.replace(cfg.optim, learning_rate=0.1),
        fed=dataclasses.replace(cfg.fed, rounds=rounds, init_seed=3),
        run=dataclasses.replace(cfg.run, mesh_devices=1, **run))


def mlp_cfg(client_state, rows=0, clients=4, devices=1, rounds=3, **fed):
    return ExperimentConfig(
        shard=ShardConfig(num_clients=clients, shuffle=False),
        model=ModelConfig(hidden_sizes=(8,)), optim=SGD,
        fed=FedConfig(rounds=rounds, server_opt="fedavgm", same_init=True,
                      client_state=client_state, local_batch_rows=rows,
                      termination_patience=1000, **fed),
        run=RunConfig(mesh_devices=devices))


def income(rows=50):
    return load_dataset(DataConfig(synthetic_rows=rows, synthetic_features=6))


def _gap(a, b):
    return max(float(np.max(np.abs(np.asarray(x) - np.asarray(y))))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def test_two_rounds_of_tiny_olmoe_match_the_references_fedavgm(tmp_path):
    sink = str(tmp_path / "ev.jsonl")
    cfg = tiny_olmoe(eval_test_every=1,
                     telemetry=TelemetryConfig(events_path=sink))
    result = run_experiment(cfg, verbose=False)
    ds = build_experiment(cfg).dataset
    rows = [ds.x_train[ds.client_of_row == c] for c in range(4)]
    assert sorted(len(r) for r in rows) == [1, 2, 3, 4]         # size skew
    # on the host: the reference donates (consumes) what it is handed
    init = jax.tree.map(np.asarray, build_model(cfg.model)[0](
        jax.random.key(cfg.fed.init_seed)))
    model = {k: getattr(cfg.model, k) for k in
             ("num_attention_heads", "num_experts_per_tok", "rope_theta",
              "rms_norm_eps", "norm_topk_prob")}
    ref_loss, ref_params = reference_lm.fedavgm_rounds(
        init, rows, 2, model, learning_rate=cfg.optim.learning_rate,
        momentum=cfg.fed.server_momentum, server_lr=cfg.fed.server_lr)
    # float32 against float32: rounding, and two orders of summation
    assert np.max(np.abs(np.stack(result.loss) - ref_loss)) <= 1e-5
    assert _gap(result.final_params, ref_params) <= 1e-5
    assert _gap(result.final_params, init) > 1e-3               # it moved
    assert set(result.global_metrics) == {"accuracy", "perplexity"}
    assert len(result.test_metrics["perplexity"]) == 2          # held-out eval
    events = [json.loads(line) for line in open(sink)]
    counters = [e for e in events if e["kind"] == "counters"][-1]["payload"]
    tokens = int((ds.x_train[:, 1] > 0).sum())
    assert counters["counters"]["moe_tokens_dropped"] == 0
    assert counters["counters"]["moe_tokens_routed"] == 2 * 2 * tokens
    assert counters["counters"]["lm_padding_tokens"] == 2 * (10 * 48 - tokens)
    # the CPU and these widths: the XLA bodies of attention and experts ran
    assert counters["counters"]["lm_fused_attention_positions"] == 0
    assert counters["counters"]["moe_grouped_kernel_positions"] == 0
    assert counters["gauges"]["moe_expert_load_max_over_mean"] > 1.0
    load = [e["payload"]["moe_expert_load"] for e in events if e["kind"] == "round"]
    assert len(load) == 2 and sum(load[0]) == 2 * tokens


def test_two_rounds_of_a_tiny_hybrid_stack_match_the_references_fedavgm(
        tmp_path):
    """The same entry point, engine and sinks as OLMoE: ``run_experiment``
    on a preset of ``kind='nemotron_h'`` cut to a tiny size, against the
    plain reference's rounds, with the share's and the scan's counters in
    the registry's last snapshot."""
    sink = str(tmp_path / "ev.jsonl")
    cfg = tiny_nemotron_h(telemetry=TelemetryConfig(events_path=sink))
    result = run_experiment(cfg, verbose=False)
    ds = build_experiment(cfg).dataset
    rows = [ds.x_train[ds.client_of_row == c] for c in range(4)]
    assert sorted(len(r) for r in rows) == [1, 2, 3, 4]         # size skew
    init = jax.tree.map(np.asarray, build_model(cfg.model)[0](
        jax.random.key(cfg.fed.init_seed)))
    model = {k: getattr(cfg.model, k) for k in (
        "hybrid_override_pattern", "layer_norm_epsilon", "mamba_num_heads",
        "mamba_head_dim", "n_groups", "ssm_state_size", "num_attention_heads",
        "num_key_value_heads", "head_dim", "num_experts_per_tok",
        "norm_topk_prob", "routed_scaling_factor", "first_expert")}
    ref_loss, ref_params = reference_nemotron_h.fedavgm_rounds(
        init, rows, 2, model, learning_rate=cfg.optim.learning_rate,
        momentum=cfg.fed.server_momentum, server_lr=cfg.fed.server_lr)
    assert np.max(np.abs(np.stack(result.loss) - ref_loss)) <= 2e-5
    assert _gap(result.final_params, ref_params) <= 2e-5
    assert _gap(result.final_params, init) > 1e-3               # it moved
    # the selection bias only picks: no gradient, no update (the program
    # draws it inside a jitted init: a last-bit difference from this one)
    for layer, first in zip(result.final_params["experts"], init["experts"]):
        assert np.abs(layer["router_bias"] - first["router_bias"]).max() <= 1e-8
    events = [json.loads(line) for line in open(sink)]
    counters = [e for e in events if e["kind"] == "counters"][-1]["payload"]
    tokens = int((ds.x_train[:, 1] > 0).sum())
    counted = counters["counters"]
    assert counted["moe_assignments_total"] == 2 * 2 * 3 * tokens
    assert 0 < counted["moe_assignments_held"] < counted["moe_assignments_total"]
    assert counted["moe_rows_computed"] >= counted["moe_assignments_held"]
    assert counted["moe_tokens_dropped"] == 0
    assert counted["ssm_positions"] == 2 * 2 * 10 * 48
    documents = int(sum((row[1][1:] != row[1][:-1]).sum() + 1
                        - (row[1][-1] == 0) for row in ds.x_train))
    assert counted["ssm_document_restarts"] == 2 * 2 * documents
    assert counted["ssm_fused_pass_positions"] == 0     # a CPU: the definitions
    assert counted["lm_padding_tokens"] == 2 * (10 * 48 - tokens)
    assert counted["stateless_client_steps"] == 2 * 10
    load = [e["payload"]["moe_expert_load"] for e in events if e["kind"] == "round"]
    assert len(load) == 2 and len(load[0]) == 16 and sum(load[0]) == 2 * 3 * tokens


def test_mlp_equals_the_resident_engines_server_opt_path():
    ds = income()
    resident = run_experiment(mlp_cfg("resident"), dataset=ds, verbose=False)
    shared = run_experiment(mlp_cfg("stateless"), dataset=ds, verbose=False)
    # one full-batch SGD step a client from one global, FedAvgM on the
    # data-size-weighted mean delta: the same algorithm, two programs
    np.testing.assert_allclose(np.stack(shared.loss), np.stack(resident.loss),
                               rtol=0, atol=1e-6)
    assert _gap(shared.final_params, resident.final_params) <= 1e-6
    assert set(shared.global_metrics) == set(resident.global_metrics)


def _by_hand(cfg, ds, batch_rows, mask=None):
    """Clients in turn, minibatches in order, plain SGD; FedAvgM. The
    parameters keep their own dtype from step to step, a client's delta is
    what it holds at the end less the global, and the accumulator and the
    momentum are float32. ``mask`` replaces the experiment's own."""
    from fedtpu.models.mlp import mlp_apply
    from fedtpu.ops.losses import masked_cross_entropy
    f32 = lambda a: np.asarray(a, np.float32)
    exp = build_experiment(cfg, ds)
    x, y = (np.asarray(exp.batch[k]) for k in ("x", "y"))
    mask = np.asarray(exp.batch["mask"] if mask is None else mask)
    g = jax.tree.map(np.asarray, exp.state["params"])
    m = jax.tree.map(lambda a: np.zeros(a.shape, np.float32), g)
    grad = jax.jit(jax.value_and_grad(
        lambda p, xb, yb, mb: masked_cross_entropy(mlp_apply(p, xb), yb, mb)))
    losses = []
    for r in range(cfg.fed.rounds):
        lr = np.float32(cfg.optim.learning_rate * cfg.optim.steplr_gamma ** (
            r // cfg.optim.steplr_step_size))
        acc, row = jax.tree.map(np.zeros_like, m), []
        for c in range(x.shape[0]):
            p, n, total = g, int(mask[c].sum()), 0.0
            for i in range(0, n, batch_rows):
                sl = slice(i, i + batch_rows)
                loss, d = grad(p, x[c, sl], y[c, sl], mask[c, sl])
                total += float(loss) * float(mask[c, sl].sum())
                p = jax.tree.map(
                    lambda a, b: a - (lr * f32(b)).astype(a.dtype), p, d)
            acc = jax.tree.map(lambda a, pc, gl: a + n * (f32(pc) - f32(gl)),
                               acc, p, g)
            row.append(total / max(n, 1))
        m = jax.tree.map(lambda a, b: 0.9 * a + b / mask.sum(), m, acc)
        g = jax.tree.map(lambda a, b: a + b.astype(a.dtype), g, m)
        losses.append(row)
    return np.asarray(losses), g, m


def test_uneven_shards_in_minibatches_match_a_loop_by_hand():
    ds = income(rows=63)                # 40 training rows: 13, 13, 14 a client
    cfg = mlp_cfg("stateless", rows=4, clients=3)
    exp = build_experiment(cfg, ds)
    counts = np.asarray(exp.batch["mask"]).sum(axis=1)
    assert len(set(counts)) > 1 and counts.max() % 4      # uneven, ragged tail
    got = run_experiment(cfg, dataset=ds, verbose=False)
    want_loss, want_params, _ = _by_hand(cfg, ds, 4)
    np.testing.assert_allclose(np.stack(got.loss), want_loss, atol=2e-6)
    assert _gap(got.final_params, want_params) <= 2e-6


@pytest.mark.parametrize("steps,dtype,one_kind", [
    ((1, 1, 1), "float32", False),       # no client ever has a working copy
    ((3, 3, 3), "float32", False),       # first, between, last
    ((2, 0, 3), "float32", False),       # an empty shard beside full ones
    # rounds at every step: the delta is what the client holds (its
    # gradients' sum, which a cast and back compiles to, is 1e-3 off)
    ((1, 2, 3), "bfloat16", False),
    # one kind of step: every step from the copy a client's start fills
    ((1, 2, 3), "float32", True),
    ((2, 0, 3), "float32", True),
    ((1, 2, 3), "bfloat16", True),
], ids=["one-step", "three-steps", "an-empty-client", "bf16-parameters",
        "one-kind", "one-kind-an-empty-client", "one-kind-bf16-parameters"])
def test_each_step_goes_straight_into_the_accumulator(steps, dtype, one_kind):
    """The accumulation as the steps are taken, against the loop by hand
    that forms ``w_c (p_c - global)`` at each client's end, and the engine's
    two counters against the steps the shards have: a working copy a step
    that another follows, none for a client of one; with ``one_step_kind``
    every step writes the copy, and the program holds one trace of the
    model where the counts call for up to four."""
    from fedtpu.ops.server_opt import make_server_optimizer
    from fedtpu.parallel.stateless import build_stateless_round_fn
    ds = income(rows=63)                # 13, 13, 14 rows, padded to 16
    cfg = mlp_cfg("stateless", rows=4, clients=3, rounds=2)
    cfg = cfg.replace(model=dataclasses.replace(cfg.model, param_dtype=dtype))
    exp = build_experiment(cfg, ds)
    rows = np.asarray([max(4 * n - 1, 0) for n in steps])     # a ragged tail
    mask = (np.arange(16) < rows[:, None]).astype(np.float32)
    step = build_stateless_round_fn(
        exp.mesh, exp.task, rows, learning_rate=cfg.optim.learning_rate,
        steplr_step_size=cfg.optim.steplr_step_size,
        steplr_gamma=cfg.optim.steplr_gamma,
        server_opt=make_server_optimizer("fedavgm", 1.0, 0.9),
        local_batch_rows=4, one_step_kind=one_kind)
    batch = dict(exp.batch, mask=jax.device_put(mask, exp.batch["mask"].sharding))
    state, losses, atol = exp.state, [], 2e-6
    # a loop a kind of step (only; first and last; between) beside the scans
    # over the rounds and over the clients
    kinds = (1 in steps) + 2 * (max(steps) > 1) + (max(steps) > 2)
    loops = step.lower(state, batch).as_text().count("stablehlo.while")
    assert loops == 2 + (1 if one_kind else kinds)
    assert jax.tree.leaves(state["params"])[0].dtype == dtype
    for _ in range(2):
        state, metrics = step(state, batch)
        losses.append(np.asarray(metrics["loss"]))
        assert int(metrics["counters"]["stateless_client_steps"]) == sum(steps)
        assert int(metrics["counters"]["stateless_working_copy_writes"]) == sum(
            n if one_kind else max(n - 1, 0) for n in steps)
    want_loss, want_params, want_m = _by_hand(cfg, ds, 4, mask)
    np.testing.assert_allclose(np.stack(losses), want_loss, atol=atol)
    # the accumulator, float32 whatever the parameters are
    got_m = state["server_opt_state"]["m"]
    assert {a.dtype for a in jax.tree.leaves(got_m)} == {np.dtype("float32")}
    assert _gap(got_m, want_m) <= atol
    as_f32 = lambda tree: jax.tree.map(lambda a: np.asarray(a, np.float32), tree)
    assert _gap(as_f32(state["params"]), as_f32(want_params)) <= atol
    assert _gap(got_m, jax.tree.map(np.zeros_like, want_m)) > 1e-3    # it moved


def test_a_mesh_of_two_devices_gives_what_one_device_gives():
    ds = income(rows=100)
    one = run_experiment(mlp_cfg("stateless", rows=8), dataset=ds, verbose=False)
    two = run_experiment(mlp_cfg("stateless", rows=8, devices=2), dataset=ds,
                         verbose=False)
    np.testing.assert_allclose(np.stack(two.loss), np.stack(one.loss), atol=1e-6)
    assert _gap(two.final_params, one.final_params) <= 1e-6
    for k in one.global_metrics:
        np.testing.assert_allclose(two.pooled_metrics[k], one.pooled_metrics[k],
                                   atol=1e-6)


def test_a_later_job_of_a_process_runs_the_round_program_of_the_one_before():
    """Same configuration, data shapes and mesh: the jitted function itself,
    and with it jit's executable (no second trace, lowering or load), whatever
    the job's length and seed; anything the builder is handed differs: a
    program of its own, and one configuration's programs at a time."""
    ds = income()
    cfg = mlp_cfg("stateless", rows=8)
    first = build_experiment(cfg, ds).make_step(1)
    later = cfg.replace(fed=dataclasses.replace(cfg.fed, rounds=7, init_seed=5))
    exp = build_experiment(later, ds)
    assert exp.make_step(1) is first and exp.make_step(2) is not first
    assert exp.make_step(2) is build_experiment(cfg, ds).make_step(2)
    for other in (mlp_cfg("stateless", rows=4),
                  cfg.replace(optim=dataclasses.replace(SGD, learning_rate=0.3)),
                  mlp_cfg("stateless", rows=8, server_momentum=0.5)):
        assert build_experiment(other, ds).make_step(1) is not first
    assert build_experiment(cfg, income(rows=40)).make_step(1) is not first
    assert build_experiment(cfg, ds).make_step(1) is not first
    one = run_experiment(cfg, dataset=ds, verbose=False)
    two = run_experiment(cfg, dataset=ds, verbose=False)
    assert all(np.array_equal(a, b) for a, b in zip(one.loss, two.loss))
    assert _gap(one.final_params, two.final_params) == 0.0


def test_a_round_program_compiled_ahead_is_what_the_jobs_dispatch(tmp_path):
    """``compile_round_program`` from shapes alone: the jobs that follow run
    the executable (the jitted function is never called, so it never traces
    or compiles), give what jobs without it give, and a sink's
    ``program_scopes`` event reads the executable's text; another
    configuration gets nothing of it."""
    ds = income()
    cfg = mlp_cfg("stateless", rows=8, server_momentum=0.7)
    plain = run_experiment(cfg, dataset=ds, verbose=False)
    exp = build_experiment(cfg, ds)
    step = exp.make_step(1)
    assert loop._compiled_ahead(step) is step
    shapes = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding),
        (exp.state, exp.batch))
    del exp
    compiled = loop.compile_round_program(step, *shapes)
    assert loop._compiled_ahead(step) is compiled
    calls = step._cache_size()
    sink = str(tmp_path / "ev.jsonl")
    traced = cfg.replace(run=dataclasses.replace(
        cfg.run, telemetry=TelemetryConfig(events_path=sink),
        profile_dir=str(tmp_path / "profile"), profile_rounds=1))
    for job in (cfg, traced):
        ahead = run_experiment(job, dataset=ds, verbose=False)
        assert all(np.array_equal(a, b) for a, b in zip(plain.loss, ahead.loss))
        assert _gap(plain.final_params, ahead.final_params) == 0.0
    assert step._cache_size() == calls
    events = [json.loads(line) for line in open(sink)]
    scopes = [e["payload"] for e in events if e["kind"] == "program_scopes"
              and e["payload"]["program"] == "round_step"]
    assert scopes and "error" not in scopes[0] and scopes[0]["scopes"]
    # the engine's own piece, and its direction
    fused = [k for k, piece in scopes[0]["pieces"].items()
             if piece == "sgd_pass"]
    assert fused and {scopes[0]["passes"][k] for k in fused} == {"update"}
    other = build_experiment(mlp_cfg("stateless", rows=4), ds).make_step(1)
    assert loop._compiled_ahead(other) is other
    assert loop._compiled_ahead(build_experiment(cfg, ds).make_step(1)) is not compiled


def test_a_scanned_chunk_of_rounds_is_the_rounds_one_by_one():
    ds = income()
    one = run_experiment(mlp_cfg("stateless", rounds=4), dataset=ds, verbose=False)
    cfg = mlp_cfg("stateless", rounds=4)
    wide = run_experiment(cfg.replace(run=dataclasses.replace(
        cfg.run, rounds_per_step=2)), dataset=ds, verbose=False)
    np.testing.assert_allclose(np.stack(wide.loss), np.stack(one.loss), atol=1e-6)
    assert _gap(wide.final_params, one.final_params) <= 1e-6


@pytest.mark.parametrize("change,says", [
    ({"optim": OptimConfig(name="adam")}, "needs optim.name='sgd'"),
    ({"optim": dataclasses.replace(SGD, momentum=0.9)}, "momentum 0"),
    ({"fed": {"participation_rate": 0.5}}, "no partial participation"),
    ({"fed": {"local_steps": 2}}, "one local epoch a round"),
    ({"fed": {"prox_mu": 0.1}}, "no FedProx term"),
    ({"fed": {"scaffold": True, "weighting": "uniform"}}, "SCAFFOLD"),
    ({"fed": {"dp_clip_norm": 1.0}}, "DP aggregation"),
    ({"fed": {"compress": "int8"}}, "compressed exchange"),
    ({"fed": {"robust_aggregation": "median", "weighting": "uniform"}},
     "robust aggregation"),
    ({"fed": {"aggregation": "ring"}}, "psum"),
    ({"fed": {"async_mode": True, "weighting": "uniform"}}, "engine of its own"),
    ({"fed": {"cohort_size": 2}}, "engine of its own"),
    ({"fed": {"personalize_steps": 1}}, "personalize_steps"),
    ({"fed": {"init_weights_npz": "w.npz"}}, "init_weights_npz"),
    ({"run": {"checkpoint_dir": "ckpt", "checkpoint_every": 1}},
     "does not write checkpoints"),
])
def test_what_the_engine_does_not_support_is_refused(change, says):
    cfg = mlp_cfg("stateless")
    for section, value in change.items():
        if isinstance(value, dict):
            value = dataclasses.replace(getattr(cfg, section), **value)
        cfg = cfg.replace(**{section: value})
    with pytest.raises(ValueError, match=says):
        run_experiment(cfg, dataset=income(), verbose=False)


def test_minibatches_need_the_stateless_engine_and_a_known_client_state():
    with pytest.raises(ValueError, match="local_batch_rows needs"):
        build_experiment(mlp_cfg("resident", rows=4), income())
    with pytest.raises(ValueError, match="'resident' or 'stateless'"):
        build_experiment(mlp_cfg("shared"), income())


def test_a_resident_state_that_cannot_fit_says_so():
    from fedtpu.ops import build_optimizer
    init_fn, _ = build_model(ModelConfig(hidden_sizes=(8,), input_dim=6))
    tx = build_optimizer(OptimConfig())
    mesh = build_experiment(mlp_cfg("resident"), income()).mesh
    need = round_mod.resident_state_bytes(init_fn, tx, 100, 1)
    assert need >= 100 * 3 * 4 * (6 * 8 + 8 + 8 * 2 + 2)   # params, m, v
    round_mod.check_resident_fits(init_fn, tx, 100, mesh, limit_bytes=need)
    with pytest.raises(ValueError, match="client_state='stateless'"):
        round_mod.check_resident_fits(init_fn, tx, 100, mesh,
                                      limit_bytes=need - 1)


def test_classification_through_the_task_is_bit_for_bit_what_it_was(monkeypatch):
    """income-8's numbers with the task interface against the same run with
    the task replaced by the functions the engines called before it existed."""
    from fedtpu.ops.losses import masked_cross_entropy
    from fedtpu.ops.metrics import (METRIC_NAMES, confusion_matrix,
                                    metrics_from_confusion)
    from fedtpu.training.task import Task

    def before(model_cfg, apply_fn, num_classes):
        def stats(p, x, y, mask):
            return confusion_matrix(y, jnp.argmax(apply_fn(p, x), axis=-1), mask,
                                    num_classes)
        return Task("classification", METRIC_NAMES,
                    loss=lambda p, x, y, m: (
                        masked_cross_entropy(apply_fn(p, x), y, m), None),
                    stats=stats, weight=lambda x, y, m: m.sum(),
                    metrics=metrics_from_confusion)

    cfg = get_preset("income-8")
    cfg = cfg.replace(data=dataclasses.replace(cfg.data, csv_path=None),
                      fed=dataclasses.replace(cfg.fed, rounds=3),
                      run=dataclasses.replace(cfg.run, eval_test_every=1))
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        now = run_experiment(cfg)
    monkeypatch.setattr(loop, "build_task", before)
    was = run_experiment(cfg, verbose=False)
    for hist in ("global_metrics", "pooled_metrics", "test_metrics"):
        assert getattr(now, hist) == getattr(was, hist)
        assert tuple(getattr(now, hist)) == METRIC_NAMES
    assert all(np.array_equal(a, b) for a, b in zip(now.loss, was.loss))
    assert _gap(now.final_params, was.final_params) == 0.0
    line = re.findall(r"Global Metrics \(Round (\d+)\): \[accuracy: [\d.]+, "
                      r"precision: [\d.]+, recall: [\d.]+, f1: [\d.]+\]  \(",
                      out.getvalue())
    assert line == ["1", "2", "3"]


def test_the_layers_of_the_compiled_round_are_named():
    from fedtpu.analysis.program import program_scopes
    from fedtpu.ops import scopes
    from fedtpu.parallel.round import STAGES
    assert LAYERS == scopes.LAYERS + ("server_update",)
    exp = build_experiment(tiny_olmoe())
    text = exp.make_step(1).lower(exp.state, exp.batch).compile().as_text()
    layers = set(program_scopes(text, LAYERS, strict=True)["scopes"].values())
    assert {"attention", "router", "expert_dispatch", "experts",
            "lm_head_loss", "server_update"} <= layers
    stages = set(program_scopes(text, STAGES)["scopes"].values())
    assert {"client_train", "aggregate"} <= stages <= set(STAGES)
    assert "client_eval" not in stages              # no second forward


@functools.lru_cache(maxsize=None)
def _compiled_round(which):
    """``(text, the one-walk program_scopes of it)`` of a tiny round
    program: the resident engine's (``sync``), the shared-global engine's on
    OLMoE and on the hybrid stack."""
    from fedtpu.analysis.program import program_scopes
    from fedtpu.parallel.round import (PIECES, RECOMPUTE, SERVER_UPDATE,
                                       SGD_PASS, STAGES)
    cfg, ds = {"sync": lambda: (mlp_cfg("resident"), income()),
               "olmoe": lambda: (tiny_olmoe(), None),
               "hybrid": lambda: (tiny_nemotron_h(), None)}[which]()
    exp = build_experiment(cfg, ds)
    text = exp.make_step(1).lower(exp.state, exp.batch).compile().as_text()
    return text, program_scopes(
        text, STAGES + (loop.STATE_CHECK,), layers=LAYERS, pieces=PIECES,
        update=(SGD_PASS, SERVER_UPDATE), recompute=(RECOMPUTE,))


@pytest.mark.parametrize("which", ["sync", "olmoe", "hybrid"])
def test_one_walk_of_the_text_gives_what_a_call_a_level_gave(which):
    """``scopes`` / ``unscoped`` and ``layers`` of the one walk are what the
    two calls over the text returned before there were four maps."""
    from fedtpu.analysis.program import PASSES, program_scopes
    from fedtpu.parallel.round import STAGES
    text, walk = _compiled_round(which)
    stages = program_scopes(text, STAGES + (loop.STATE_CHECK,))
    assert walk["scopes"] == stages["scopes"] and stages["scopes"]
    assert walk["unscoped"] == stages["unscoped"]
    assert walk["layers"] == program_scopes(text, LAYERS, strict=True)["scopes"]
    assert bool(walk["layers"]) is (which != "sync")
    # every operation has a direction, a piece only where a scope says so
    keys = {*walk["scopes"], *walk["unscoped"]}
    assert set(walk["passes"]) == keys and set(walk["pieces"]) <= keys
    assert set(walk["passes"].values()) <= set(PASSES)
    assert set(walk["pieces"].values()) <= (
        {"sync": set(), "olmoe": {"attn_core", "sgd_pass"},
         "hybrid": set(round_mod.PIECES)}[which])


def _named(text, marker, listed):
    """Those of the keys ``listed`` whose own ``op_name`` holds ``marker``
    (an instruction inside a fusion's body is no operation of its own)."""
    found = re.findall(r"^\s+(?:ROOT )?%?([\w.\-]+) = \S*?([a-z]\w*\[[\d,]*\])"
                       r".*op_name=\"[^\"]*" + re.escape(marker), text, re.M)
    return [key for key in (f"{inst} {shape}"[:120] for inst, shape in found)
            if key in listed]


@pytest.mark.parametrize("what", ["ssm_pieces", "attn_core", "sgd_pass",
                                  "recompute"])
def test_the_pieces_and_passes_of_a_tiny_hybrid_round(what):
    from fedtpu.analysis.program import PASSES
    from fedtpu.ops import scopes
    assert round_mod.PIECES == scopes.PIECES + ("sgd_pass",)
    assert round_mod.RECOMPUTE == scopes.RECOMPUTE
    text, walk = _compiled_round("hybrid")
    layers, pieces, passes = walk["layers"], walk["pieces"], walk["passes"]
    heavy = lambda key: key.startswith(("fusion", "dot", "convolution"))
    if what == "ssm_pieces":
        # the mixer outside its scan is its four pieces; what is left (an
        # instruction of the compiler's whose neighbours disagree) is the
        # reducers' ``ssm_rest_ms``, and no fusion or product is in it
        mixer = [k for k, layer in layers.items() if layer == "ssm"]
        assert {pieces[k] for k in mixer if k in pieces} >= set(
            round_mod.PIECES[:4])
        rest = [k for k in mixer if pieces.get(k) not in round_mod.PIECES[:4]]
        assert len(rest) <= 0.1 * len(mixer) and not any(map(heavy, rest))
    elif what == "attn_core":
        core = [k for k, piece in pieces.items() if piece == "attn_core"]
        assert core and all(layers[k] == "attention" for k in core)
        assert any(layers[k] == "attention" and k not in pieces
                   and heavy(k) for k in layers)        # the projections
    elif what == "sgd_pass":
        # an operation that names itself under either scope of the update
        for marker in ("/sgd_pass/", "/server_update/"):
            update = _named(text, marker, passes)
            assert update and all(passes[k] == "update" for k in update)
        assert all(pieces[k] == "sgd_pass"
                   for k in _named(text, "/sgd_pass/", passes))
    else:
        assert set(passes.values()) == set(PASSES)
        # remat's recomputed body, and the held experts' by hand
        for marker in ("/rematted_computation/", "/recompute/"):
            again = _named(text, marker, passes)
            assert again and all(passes[k] == "recompute" for k in again)
        by_hand = _named(text, "/recompute/", passes)
        # the gradients of the block it ran again are the backward pass's
        # (the TPU's kernels name themselves so; here they are fused away)
        from fedtpu.analysis.program import _pass_of
        assert "/transpose(recompute)/" in text
        assert all(passes[k] == "backward"
                   for k in _named(text, "/transpose(recompute)/", passes))
        stack = "jit(s)/client_train/transpose(jvp())/checkpoint/while/body/"
        assert [_pass_of(stack + rest, ("sgd_pass",), ("recompute",))
                for rest in ("recompute/jvp(experts)/jit(gmm)/pallas_call",
                             "transpose(recompute)/jvp(experts)/jit(tgmm)/"
                             "pallas_call", "rematted_computation/ssm/mul",
                             "ssm/mul")] == ["recompute", "backward",
                                             "recompute", "backward"]
        assert {layers.get(k) for k in by_hand} & {"experts",
                                                   "expert_dispatch"}
        # a transposed operation outside both is the backward pass's
        assert any(passes[k] == "backward"
                   for k in _named(text, "/transpose(jvp(", passes))


def test_a_compiler_kernel_that_drops_its_scope_is_put_down_by_its_name():
    """The TPU's compiler names its ragged-dot kernel itself; the event says
    whose it is all the same."""
    text = '''HloModule m

ENTRY %main (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0)
  %fusion.1 = f32[8]{0} fusion(%x), kind=kLoop, calls=%f, metadata={op_name="jit(s)/client_train/expert_dispatch/gather"}
  %ragged-dot-none.2 = f32[8]{0} custom-call(%fusion.1), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %copy.3 = f32[8]{0} copy(%fusion.1)
  ROOT %fusion.4 = f32[8]{0} fusion(%ragged-dot-none.2, %copy.3), kind=kLoop, calls=%g, metadata={op_name="jit(s)/client_train/sub"}
}
'''

    class Program:
        def as_text(self):
            return text

    class Sink:
        def event(self, kind, **payload):
            self.kind, self.payload = kind, payload

    sink = Sink()
    loop._emit_program_scopes(sink, "round_step", 1, Program())
    assert sink.kind == "program_scopes" and "error" not in sink.payload
    # the copy has no op_name and inherits; the update names itself outside
    # every layer and stays outside, whoever made its operands
    assert sink.payload["layers"] == {"fusion.1 f32[8]": "expert_dispatch",
                                      "copy.3 f32[8]": "expert_dispatch",
                                      "ragged-dot-none.2 f32[8]": "experts"}
    assert sink.payload["scopes"]["ragged-dot-none.2 f32[8]"] == "client_train"
