"""2-D ('clients','model') GSPMD engine (fedtpu.parallel.tp): the round
semantics must match the 1-D shard_map engine exactly, with hidden weights
genuinely sharded over the tensor-parallel axis."""

import dataclasses

import jax
import numpy as np
import pytest

from fedtpu.config import (DataConfig, ExperimentConfig, FedConfig,
                           ModelConfig, OptimConfig, RunConfig, ShardConfig)
from fedtpu.data.sharding import pack_clients
from fedtpu.data.tabular import synthetic_income_like
from fedtpu.models import build_model
from fedtpu.ops import build_optimizer
from fedtpu.orchestration.loop import run_experiment
from fedtpu.parallel import make_mesh, client_sharding, tp
from fedtpu.parallel.round import build_round_fn, init_federated_state

HIDDEN = (16, 8)  # both divisible by the tp extent 2


def _engines(rounds_per_step=1, num_clients=8, hidden=HIDDEN,
             weighting="data_size", seed=3, rows=256):
    """Build the SAME federated setup on both engines (one construction path
    — signature changes to build_round_fn/init_federated_state show up here
    once, for every test)."""
    x, y = synthetic_income_like(rows, 6, 2, seed=seed)
    packed = pack_clients(x, y, ShardConfig(num_clients=num_clients,
                                            shuffle=False))
    init_fn, apply_fn = build_model(ModelConfig(input_dim=6,
                                                hidden_sizes=hidden))
    tx = build_optimizer(OptimConfig())
    key = jax.random.key(3)

    mesh1 = make_mesh(num_clients=num_clients)
    s1 = init_federated_state(key, mesh1, num_clients, init_fn, tx)
    b1 = {k: jax.device_put(v, client_sharding(mesh1)) for k, v in
          {"x": packed.x, "y": packed.y, "mask": packed.mask}.items()}
    step1 = build_round_fn(mesh1, apply_fn, tx, 2, weighting=weighting,
                           rounds_per_step=rounds_per_step)

    mesh2 = tp.make_mesh_2d(2, num_clients)
    s2 = tp.init_federated_state_2d(key, mesh2, num_clients, init_fn, tx)
    b2 = {k: jax.device_put(v, tp.batch_sharding_2d(mesh2)) for k, v in
          {"x": packed.x, "y": packed.y, "mask": packed.mask}.items()}
    step2 = tp.build_round_fn_2d(mesh2, apply_fn, tx, 2, weighting=weighting,
                                 rounds_per_step=rounds_per_step)
    return (s1, b1, step1), (s2, b2, step2)


def test_mesh_2d_shape():
    mesh = tp.make_mesh_2d(2, 8)
    assert mesh.axis_names == ("clients", "model")
    assert mesh.devices.shape == (4, 2)


def test_hidden_weights_actually_sharded_over_model():
    mesh = tp.make_mesh_2d(2, 8)
    init_fn, _ = build_model(ModelConfig(input_dim=6, hidden_sizes=HIDDEN))
    tx = build_optimizer(OptimConfig())
    state = tp.init_federated_state_2d(jax.random.key(0), mesh, 8, init_fn, tx)
    w0 = state["params"]["layers"][0]["w"]        # (C, in, h) col-sharded
    shard_shapes = {s.data.shape for s in w0.addressable_shards}
    assert shard_shapes == {(2, 6, HIDDEN[0] // 2)}
    w1 = state["params"]["layers"][1]["w"]        # (C, h, h2) row-sharded
    assert {s.data.shape for s in w1.addressable_shards} == \
        {(2, HIDDEN[0] // 2, HIDDEN[1])}


def test_2d_engine_matches_1d_engine():
    (s1, b1, step1), (s2, b2, step2) = _engines()
    for _ in range(3):
        s1, m1 = step1(s1, b1)
        s2, m2 = step2(s2, b2)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                                rtol=2e-5, atol=1e-5),
        s1["params"], s2["params"])
    np.testing.assert_allclose(float(m1["client_mean"]["accuracy"]),
                               float(m2["client_mean"]["accuracy"]),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(m1["per_client"]["f1"]),
                               np.asarray(m2["per_client"]["f1"]), atol=1e-6)
    np.testing.assert_allclose(np.asarray(m1["pooled"]["f1"]),
                               np.asarray(m2["pooled"]["f1"]), atol=1e-6)


def test_2d_engine_multi_round_scan():
    (_, _, _), (s2, b2, step2) = _engines(rounds_per_step=4)
    s2, m2 = step2(s2, b2)
    assert np.asarray(m2["client_mean"]["accuracy"]).shape == (4,)
    assert int(s2["round"]) == 4


@pytest.mark.parametrize("hidden,clients,weighting", [
    ((16,), 4, "data_size"),          # single hidden layer (col then logits)
    ((16, 8), 8, "uniform"),          # even depth, uniform averaging
    ((16, 8, 4), 8, "data_size"),     # odd depth: ends col-sharded pre-logits
])
def test_engines_agree_across_configs(hidden, clients, weighting):
    """Config-sweep contract: for any depth/clients/weighting combo the 1-D
    shard_map engine and the 2-D GSPMD engine produce the same params."""
    (s1, b1, step1), (s2, b2, step2) = _engines(
        num_clients=clients, hidden=hidden, weighting=weighting,
        seed=clients, rows=32 * clients)
    for _ in range(2):
        s1, m1 = step1(s1, b1)
        s2, m2 = step2(s2, b2)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                                rtol=2e-5, atol=1e-5),
        s1["params"], s2["params"])
    np.testing.assert_allclose(np.asarray(m1["per_client"]["accuracy"]),
                               np.asarray(m2["per_client"]["accuracy"]),
                               atol=1e-6)


def test_convnet_engines_agree():
    """ConvNet on the 2-D mesh: conv kernels channel-shard over 'model' and
    the round must match the 1-D engine."""
    from fedtpu.data.cifar10 import synthetic_cifar_like
    x, y = synthetic_cifar_like(64, seed=4, image_shape=(8, 8, 3), classes=4)
    x = x.reshape(64, -1)
    packed = pack_clients(x, y, ShardConfig(num_clients=8, shuffle=False))
    model_cfg = ModelConfig(kind="convnet", image_shape=(8, 8, 3),
                            conv_channels=(4, 8), hidden_sizes=(16,),
                            num_classes=4)
    init_fn, apply_fn = build_model(model_cfg)
    tx = build_optimizer(OptimConfig())
    key = jax.random.key(9)
    feed = {"x": packed.x, "y": packed.y, "mask": packed.mask}

    mesh1 = make_mesh(num_clients=8)
    s1 = init_federated_state(key, mesh1, 8, init_fn, tx)
    b1 = {k: jax.device_put(v, client_sharding(mesh1)) for k, v in feed.items()}
    step1 = build_round_fn(mesh1, apply_fn, tx, 4)

    mesh2 = tp.make_mesh_2d(2, 8)
    s2 = tp.init_federated_state_2d(key, mesh2, 8, init_fn, tx)
    b2 = {k: jax.device_put(v, tp.batch_sharding_2d(mesh2))
          for k, v in feed.items()}
    step2 = tp.build_round_fn_2d(mesh2, apply_fn, tx, 4)

    # Conv kernels really are channel-sharded over 'model'.
    w0 = s2["params"]["convs"][0]["w"]          # (C, 3, 3, 3, 4) col-sharded
    assert {s.data.shape for s in w0.addressable_shards} == {(2, 3, 3, 3, 2)}
    w1 = s2["params"]["convs"][1]["w"]          # (C, 3, 3, 4, 8) row-sharded
    assert {s.data.shape for s in w1.addressable_shards} == {(2, 3, 3, 2, 8)}

    for _ in range(2):
        s1, m1 = step1(s1, b1)
        s2, m2 = step2(s2, b2)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                                rtol=2e-5, atol=1e-5),
        s1["params"], s2["params"])
    np.testing.assert_allclose(np.asarray(m1["per_client"]["accuracy"]),
                               np.asarray(m2["per_client"]["accuracy"]),
                               atol=1e-6)


def test_checkpoint_resume_preserves_tp_layout(tmp_path):
    cfg = ExperimentConfig(
        data=DataConfig(csv_path=None, synthetic_rows=256),
        shard=ShardConfig(num_clients=8),
        model=ModelConfig(hidden_sizes=HIDDEN),
        fed=FedConfig(rounds=2),
        run=RunConfig(model_parallel=2, checkpoint_dir=str(tmp_path),
                      checkpoint_every=1),
    )
    run_experiment(cfg, verbose=False)
    from fedtpu.orchestration.loop import build_experiment
    from fedtpu.orchestration.checkpoint import load_checkpoint
    exp = build_experiment(cfg)
    state, _, step = load_checkpoint(str(tmp_path), state_like=exp.state)
    assert step == 2
    w0 = state["params"]["layers"][0]["w"]
    # The column-sharded hidden weight must come back model-sharded, not
    # replicated over the model axis.
    assert {s.data.shape for s in w0.addressable_shards} == \
        {(2, w0.shape[1], HIDDEN[0] // 2)}
    # And resume must run on from it.
    res = run_experiment(cfg, verbose=False, resume=True)
    assert res.rounds_run == 2


def test_unsupported_combos_raise():
    base = ExperimentConfig(
        data=DataConfig(csv_path=None, synthetic_rows=128),
        shard=ShardConfig(num_clients=8),
        model=ModelConfig(hidden_sizes=HIDDEN),
        fed=FedConfig(rounds=1),
        run=RunConfig(model_parallel=2),
    )
    from fedtpu.orchestration.loop import build_experiment
    with pytest.raises(ValueError, match="ring"):
        build_experiment(dataclasses.replace(
            base, fed=dataclasses.replace(base.fed, aggregation="ring")))
    with pytest.raises(ValueError, match="divisible"):
        build_experiment(dataclasses.replace(
            base, model=dataclasses.replace(base.model,
                                            hidden_sizes=(25, 16))))
    # Odd-index dims are never placed on the model axis (row layers shard
    # the previous out-dim), so (50, 25) is a VALID layout at tp=2.
    build_experiment(dataclasses.replace(
        base, model=dataclasses.replace(base.model, hidden_sizes=(50, 25))))


def test_run_experiment_model_parallel():
    cfg = ExperimentConfig(
        data=DataConfig(csv_path=None, synthetic_rows=256),
        shard=ShardConfig(num_clients=8),
        model=ModelConfig(hidden_sizes=HIDDEN),
        fed=FedConfig(rounds=3),
        run=RunConfig(model_parallel=2),
    )
    res = run_experiment(cfg, verbose=False)
    base = run_experiment(
        dataclasses.replace(cfg, run=RunConfig(model_parallel=1)),
        verbose=False)
    np.testing.assert_allclose(res.global_metrics["accuracy"],
                               base.global_metrics["accuracy"], atol=1e-6)


def test_per_device_state_bytes_scale_down_with_tp():
    """The 2-D engine's reason to exist: measured per-device params+opt bytes drop ~1/tp
    for a fixed federation as chips-per-client grow. Slack below the
    ideal 2x/4x is the model-replicated logits head and row biases."""
    from fedtpu.utils.trees import max_device_bytes

    init_fn, _ = build_model(ModelConfig(input_dim=64,
                                         hidden_sizes=(256, 256)))
    tx = build_optimizer(OptimConfig())

    def state_bytes(state):
        return max_device_bytes({"p": state["params"],
                                 "o": state["opt_state"]})

    mesh1 = make_mesh(num_devices=2, num_clients=2)
    base = state_bytes(
        init_federated_state(jax.random.key(0), mesh1, 2, init_fn, tx))
    for mp, floor in ((2, 1.8), (4, 3.6)):
        mesh2 = tp.make_mesh_2d(mp, 2)
        b = state_bytes(tp.init_federated_state_2d(
            jax.random.key(0), mesh2, 2, init_fn, tx))
        assert base / b > floor, (mp, base, b)


def test_bare_leaf_params_rejected():
    """Advisor r4: a single-leaf params pytree ('*' treedef) would match
    EVERY optimizer-state subtree in place_opt and assign 2-D param
    shardings to scalar step counts. The init must refuse it up front."""
    mesh = tp.make_mesh_2d(2, 8)
    tx = build_optimizer(OptimConfig())
    with pytest.raises(ValueError, match="dict params pytree"):
        tp.init_federated_state_2d(
            jax.random.key(0), mesh, 8,
            lambda k: jax.random.normal(k, (6, 4)), tx)
