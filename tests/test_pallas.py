"""Pallas kernel parity against the pure-XLA implementations (interpret mode
on the CPU mesh; the same kernels compile natively on TPU)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from fedtpu.models.mlp import mlp_init, mlp_apply
from fedtpu.ops.pallas_kernels import fused_mlp_forward, weighted_average_clients


def test_fused_mlp_matches_xla_apply():
    params = mlp_init(jax.random.key(0), 14, (50, 200), 2)
    x = jax.random.normal(jax.random.key(1), (64, 14), jnp.float32)
    ref = mlp_apply(params, x)
    out = fused_mlp_forward(params, x, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


def test_fused_mlp_gridded_rows():
    # 1024 rows forces multiple row tiles through the grid path.
    params = mlp_init(jax.random.key(2), 6, (8,), 3)
    x = jax.random.normal(jax.random.key(3), (1024, 6), jnp.float32)
    out = fused_mlp_forward(params, x, interpret=True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(mlp_apply(params, x)), atol=1e-4)


def test_fused_mlp_unpadded_rows():
    # 100 % 8 != 0: remainder rows must be computed, not dropped.
    params = mlp_init(jax.random.key(4), 6, (8,), 3)
    x = jax.random.normal(jax.random.key(5), (100, 6), jnp.float32)
    out = fused_mlp_forward(params, x, interpret=True)
    assert out.shape == (100, 3)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(mlp_apply(params, x)), atol=1e-4)


def test_experiment_with_pallas_heldout_eval_matches_xla():
    from fedtpu.config import (DataConfig, ExperimentConfig, FedConfig,
                               ModelConfig, RunConfig, ShardConfig)
    from fedtpu.orchestration.loop import run_experiment

    base = ExperimentConfig(
        data=DataConfig(csv_path=None, synthetic_rows=256),
        shard=ShardConfig(num_clients=8),
        fed=FedConfig(rounds=3),
        run=RunConfig(eval_test_every=1),
    )
    r_xla = run_experiment(base, verbose=False)
    r_pl = run_experiment(
        base.replace(model=ModelConfig(use_pallas=True)), verbose=False)
    np.testing.assert_allclose(r_pl.global_metrics["accuracy"],
                               r_xla.global_metrics["accuracy"], atol=1e-6)
    # The held-out eval ran through the Pallas kernel: same test metrics.
    np.testing.assert_allclose(r_pl.test_metrics["accuracy"],
                               r_xla.test_metrics["accuracy"], atol=1e-6)


@pytest.mark.parametrize("model_kw", [
    dict(compute_dtype="bfloat16"),
    dict(param_dtype="bfloat16", compute_dtype="bfloat16"),
    dict(kind="convnet"),
])
def test_use_pallas_the_kernel_cannot_serve_is_an_error(model_kw):
    """--use-pallas used to give way to the XLA eval without a word when
    the model was not the float32 MLP; a requested kernel that does not
    run is an error."""
    from fedtpu.config import (DataConfig, ExperimentConfig, ModelConfig,
                               ShardConfig)
    from fedtpu.orchestration.loop import build_experiment

    cfg = ExperimentConfig(
        data=DataConfig(csv_path=None, synthetic_rows=64,
                        synthetic_features=(3072 if "kind" in model_kw
                                            else 14)),
        shard=ShardConfig(num_clients=2),
        model=ModelConfig(use_pallas=True, **model_kw))
    with pytest.raises(ValueError, match="use_pallas needs the float32 MLP"):
        build_experiment(cfg)


def test_weighted_average_kernel_matches_numpy():
    rng = np.random.default_rng(0)
    stacked = rng.normal(size=(8, 96)).astype(np.float32)
    w = np.array([12, 12, 12, 12, 12, 12, 12, 19], np.float32)
    expected = (stacked * (w / w.sum())[:, None]).sum(axis=0)
    out = weighted_average_clients(jnp.asarray(stacked), jnp.asarray(w),
                                   interpret=True)
    np.testing.assert_allclose(np.asarray(out), expected, atol=1e-5)


def test_fused_eval_confusion_matches_xla_chain():
    # The batched fused eval->confusion kernel (measured SLOWER than the
    # XLA chain on the v5e — see PERF.md 'Earlier records'; kept as a
    # library op) must match vmap(argmax -> confusion_matrix) exactly in
    # interpret mode.
    import jax
    import jax.numpy as jnp
    import numpy as np

    from fedtpu.config import ModelConfig, OptimConfig, ShardConfig
    from fedtpu.data.sharding import pack_clients
    from fedtpu.data.tabular import synthetic_income_like
    from fedtpu.models import build_model
    from fedtpu.ops import build_optimizer
    from fedtpu.ops.metrics import confusion_matrix
    from fedtpu.ops.pallas_kernels import fused_eval_confusion
    from fedtpu.parallel import make_mesh
    from fedtpu.parallel.round import init_federated_state

    x, y = synthetic_income_like(64 * 4, 6, 2)
    packed = pack_clients(x, y, ShardConfig(num_clients=4, shuffle=False))
    init_fn, apply_fn = build_model(ModelConfig(input_dim=6,
                                                hidden_sizes=(16,)))
    tx = build_optimizer(OptimConfig())
    mesh = make_mesh(num_clients=4)
    state = init_federated_state(jax.random.key(3), mesh, 4, init_fn, tx,
                                 same_init=False)
    xd, yd, md = (jnp.asarray(packed.x), jnp.asarray(packed.y),
                  jnp.asarray(packed.mask))
    conf_pal = fused_eval_confusion(state["params"], xd, yd, md, 2)
    conf_xla = jax.vmap(lambda p, xx, yy, mm: confusion_matrix(
        yy, jnp.argmax(apply_fn(p, xx), -1), mm, 2))(
            state["params"], xd, yd, md)
    np.testing.assert_array_equal(np.asarray(conf_pal),
                                  np.asarray(conf_xla))


def test_fused_eval_confusion_rejects_wide_class_counts():
    import jax.numpy as jnp
    import pytest

    from fedtpu.ops.pallas_kernels import fused_eval_confusion

    params = {"layers": [{"w": jnp.zeros((2, 4, 9)),
                          "b": jnp.zeros((2, 9))}]}
    with pytest.raises(ValueError, match="> 8"):
        fused_eval_confusion(params, jnp.zeros((2, 8, 4)),
                             jnp.zeros((2, 8), jnp.int32),
                             jnp.ones((2, 8)), 9)
