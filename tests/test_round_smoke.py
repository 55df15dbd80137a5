"""Smoke test: one compiled federated round on 8 virtual devices."""

import jax
import numpy as np

from fedtpu.config import ModelConfig, OptimConfig, ShardConfig
from fedtpu.data.sharding import pack_clients
from fedtpu.data.tabular import synthetic_income_like
from fedtpu.models import build_model
from fedtpu.ops import build_optimizer
from fedtpu.parallel import make_mesh, client_sharding
from fedtpu.parallel.round import (build_round_fn, init_federated_state,
                                   global_params, build_eval_fn)
from fedtpu.training.task import classification_task


def test_round_runs_on_8_device_mesh():
    assert len(jax.devices()) == 8
    x, y = synthetic_income_like(512, 14, 2)
    batch_np = pack_clients(x, y, ShardConfig(num_clients=8))

    mesh = make_mesh(num_clients=8)
    init_fn, apply_fn = build_model(ModelConfig(input_dim=14))
    tx = build_optimizer(OptimConfig())
    state = init_federated_state(jax.random.key(0), mesh, 8, init_fn, tx)

    shard = client_sharding(mesh)
    batch = {
        "x": jax.device_put(batch_np.x, shard),
        "y": jax.device_put(batch_np.y, shard),
        "mask": jax.device_put(batch_np.mask, shard),
    }
    round_step = build_round_fn(mesh, apply_fn, tx, num_classes=2)

    state, metrics = round_step(state, batch)
    assert metrics["loss"].shape == (8,)
    assert float(metrics["client_mean"]["accuracy"]) >= 0.0

    # After averaging, every client slot must hold the identical global model.
    p = np.asarray(state["params"]["layers"][0]["w"])
    for c in range(1, 8):
        np.testing.assert_allclose(p[c], p[0], rtol=0, atol=0)

    # A few more rounds should drive accuracy up on separable synthetic data.
    for _ in range(20):
        state, metrics = round_step(state, batch)
    assert float(metrics["client_mean"]["accuracy"]) > 0.8

    ev = build_eval_fn(classification_task(apply_fn, 2))
    m = ev(global_params(state), batch["x"][0], batch["y"][0])
    assert 0.0 <= float(m["accuracy"]) <= 1.0


def test_empty_hidden_sizes_is_logistic_regression():
    """hidden_sizes=() degenerates the MLP family to a single Linear —
    multinomial logistic regression — and the whole stack (init, round,
    averaging, metrics) handles it: the smallest model family a reference
    user might bring."""
    from fedtpu.config import (DataConfig, ExperimentConfig, FedConfig,
                               RunConfig)
    from fedtpu.orchestration.loop import run_experiment

    init_fn, apply_fn = build_model(ModelConfig(input_dim=6,
                                                hidden_sizes=()))
    params = init_fn(jax.random.key(0))
    assert len(params["layers"]) == 1           # one Linear: logits head
    assert params["layers"][0]["w"].shape == (6, 2)

    cfg = ExperimentConfig(
        data=DataConfig(csv_path=None, synthetic_rows=256,
                        synthetic_features=6),
        shard=ShardConfig(num_clients=8, shuffle=False),
        model=ModelConfig(input_dim=6, hidden_sizes=()),
        # Early stop disabled: a linear model saturating the separable
        # synthetic data within atol=1e-4 would otherwise stop the run and
        # fail the rounds_run assertion spuriously.
        fed=FedConfig(rounds=20, termination_patience=10**9),
        run=RunConfig(rounds_per_step=5),
    )
    result = run_experiment(cfg, verbose=False)
    assert result.rounds_run == 20
    assert np.isfinite(result.global_metrics["accuracy"][-1])
    assert result.global_metrics["accuracy"][-1] > 0.6   # separable synth
