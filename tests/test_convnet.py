"""ConvNet model family (BASELINE.json config 5 analogue, scaled down for the
single-core CPU mesh) + bf16 compute path."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax import lax

from fedtpu.config import (DataConfig, ExperimentConfig, FedConfig,
                           ModelConfig, ShardConfig)
from fedtpu.models import build_model
from fedtpu.models.convnet import _maxpool2, convnet_apply, convnet_init
from fedtpu.ops.losses import masked_cross_entropy
from fedtpu.orchestration.loop import run_experiment


def _model_cfg(**kw):
    return ModelConfig(kind="convnet", image_shape=(8, 8, 3),
                       conv_channels=(8, 16), hidden_sizes=(32,),
                       num_classes=10, **kw)


def test_convnet_fedavg_end_to_end():
    cfg = ExperimentConfig(
        data=DataConfig(csv_path=None, synthetic_rows=128,
                        synthetic_features=8 * 8 * 3, synthetic_classes=10),
        shard=ShardConfig(num_clients=8),
        model=_model_cfg(),
        fed=FedConfig(rounds=2),
    )
    res = run_experiment(cfg, verbose=False)
    assert res.rounds_run == 2
    assert 0.0 <= res.global_metrics["accuracy"][-1] <= 1.0
    # Global convnet params came back with conv kernels intact.
    assert res.final_params["convs"][0]["w"].shape == (3, 3, 3, 8)


def test_convnet_accepts_nhwc_and_flat_inputs():
    init_fn, apply_fn = build_model(_model_cfg())
    params = init_fn(jax.random.key(0))
    imgs = jnp.ones((4, 8, 8, 3), jnp.float32)
    flat = imgs.reshape(4, -1)
    np.testing.assert_allclose(np.asarray(apply_fn(params, imgs)),
                               np.asarray(apply_fn(params, flat)),
                               atol=1e-6)


def test_bf16_compute_path():
    init_fn, apply_fn = build_model(_model_cfg(compute_dtype="bfloat16"))
    params = init_fn(jax.random.key(0))
    out = apply_fn(params, jnp.ones((4, 8, 8, 3), jnp.float32))
    # Params and logits stay f32 (mixed-precision recipe: bf16 matmuls only).
    assert out.dtype == jnp.float32
    assert params["head"]["w"].dtype == jnp.float32
    assert bool(jnp.isfinite(out).all())


# ------------------------------------------------- the order inside a block
# convnet_apply computes relu(maxpool(conv) + b). The cases below hold it to
# the order the textbook writes, maxpool(relu(conv + b)): same values bit
# for bit, same subgradient, and a tie convention that is written down.

def old_order_apply(params, x, compute_dtype=None):
    """convnet_apply with each block as maxpool(relu(conv + b)); the rest is
    a transcription. tests/test_aot_tpu_compile.py compiles it (and
    ``_masked_loss``) too."""
    cast = (lambda a: a.astype(compute_dtype)) if compute_dtype else (lambda a: a)
    h = cast(x)
    for conv in params["convs"]:
        h = lax.conv_general_dilated(
            h, cast(conv["w"]), window_strides=(1, 1), padding="SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        h = _maxpool2(jax.nn.relu(h + cast(conv["b"])))
    h = h.reshape(h.shape[0], -1)
    h = jax.nn.relu(h @ cast(params["dense"]["w"]) + cast(params["dense"]["b"]))
    h = h @ cast(params["head"]["w"]) + cast(params["head"]["b"])
    return h.astype(params["head"]["w"].dtype)


def _masked_loss(apply, compute_dtype):
    def loss(params, x, y, mask):
        return masked_cross_entropy(apply(params, x, compute_dtype), y, mask)
    return loss


def _problem(side, images):
    """Parameters at the repo's channel widths with conv biases of both
    signs, some large enough that whole windows go <= 0."""
    kp, kb, kx, ky = jax.random.split(jax.random.key(0), 4)
    params = convnet_init(kp, (side, side, 3), (32, 64), 64, 10)
    for conv, k in zip(params["convs"], jax.random.split(kb, 2)):
        conv["b"] = 0.5 * jax.random.normal(k, conv["b"].shape, jnp.float32)
    x = jax.random.normal(kx, (images, side, side, 3), jnp.float32)
    y = jax.random.randint(ky, (images,), 0, 10)
    mask = (jnp.arange(images) < images - 3).astype(jnp.float32)  # padded rows
    return params, x, y, mask


def _rel_l2(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


@pytest.mark.parametrize("side,images", [(8, 256), (32, 64)],
                         ids=["8x8", "32x32"])
@pytest.mark.parametrize("compute_dtype", [None, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_pool_before_bias_and_relu_is_the_same_block(compute_dtype, side,
                                                     images):
    params, x, y, mask = _problem(side, images)
    new = convnet_apply(params, x, compute_dtype)
    old = old_order_apply(params, x, compute_dtype)
    # Whole windows do go <= 0, or the case would not test the ReLU.
    z = lax.conv_general_dilated(
        x, params["convs"][0]["w"], (1, 1), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    assert float((_maxpool2(z) + params["convs"][0]["b"] <= 0).mean()) > 0.1
    np.testing.assert_array_equal(np.asarray(new), np.asarray(old))

    def grad(apply, dtype):
        fn = jax.jit(jax.grad(_masked_loss(apply, dtype)))
        return fn(params, x, y, mask)

    g_new = grad(convnet_apply, compute_dtype)
    g_old = grad(old_order_apply, compute_dtype)
    leaves = jax.tree_util.tree_leaves_with_path
    if compute_dtype is None:
        for (path, a), (_, b) in zip(leaves(g_new), leaves(g_old)):
            name = jax.tree_util.keystr(path)
            if name.endswith("['b']"):   # summed over other positions
                np.testing.assert_allclose(a, b, rtol=0, atol=1e-5,
                                           err_msg=name)
            else:
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                              err_msg=name)
        return
    # In bf16 the two orders part wherever rounding the bias in creates a
    # tie (a few percent of the gradient), so they are not held to each
    # other but to the float32 gradient, leaf by leaf. Over 20 seeds the new
    # order's weight gradients were closer every time (0.83-0.97 of the old
    # order's distance); a conv bias gradient is 32 or 64 sums that mostly
    # cancel and scattered evenly about 1 (0.76-1.28), so the images here
    # are many enough to hold it under 1.1.
    g_f32 = grad(convnet_apply, None)
    for (path, a), (_, b), (_, ref) in zip(leaves(g_new), leaves(g_old),
                                           leaves(g_f32)):
        assert _rel_l2(a, ref) <= 1.1 * _rel_l2(b, ref), \
            jax.tree_util.keystr(path)


def _one_window(bias):
    """One 2x2 window, one channel, an identity convolution: the gradient
    with respect to the image says which element of the window got it."""
    w = jnp.zeros((3, 3, 1, 1), jnp.float32).at[1, 1, 0, 0].set(1.0)
    return {"convs": [{"w": w, "b": jnp.full((1,), bias, jnp.float32)}],
            "dense": {"w": jnp.ones((1, 1), jnp.float32),
                      "b": jnp.ones((1,), jnp.float32)},
            "head": {"w": jnp.asarray([[1.0, -1.0]], jnp.float32),
                     "b": jnp.zeros((2,), jnp.float32)}}


def _window_grads(apply, params, window, compute_dtype):
    """(d loss / d window as 4 numbers in window order, d loss / d bias);
    op by op, so every operation rounds as written."""
    x = jnp.asarray(window, jnp.float32).reshape(1, 2, 2, 1)
    y, mask = jnp.asarray([1]), jnp.ones((1,), jnp.float32)
    gp, gx = jax.grad(_masked_loss(apply, compute_dtype), argnums=(0, 1))(
        params, x, y, mask)
    return np.asarray(gx).reshape(4), float(gp["convs"][0]["b"][0])


@pytest.mark.parametrize("apply", [convnet_apply, old_order_apply],
                         ids=["pool_first", "relu_first"])
def test_window_at_or_below_minus_bias_gets_no_gradient(apply):
    # max + b == 0 exactly: relu's derivative at 0 is 0, both ways.
    gx, gb = _window_grads(apply, _one_window(-1.0), [1.0, -2.0, 0.5, -3.0],
                           None)
    np.testing.assert_array_equal(gx, np.zeros(4, np.float32))
    assert gb == 0.0


def test_rounding_tie_sends_the_gradient_to_the_larger_conv_output():
    """1.0 and 1.0078125 are distinct bfloat16 numbers; with 512 added both
    round to 512 (bfloat16 steps by 4 there). ReLU first sees a tie and the
    pool's backward takes the first in window order; pooling first compares
    the convolution's own outputs and takes the larger, the true argmax."""
    window = [1.0, 1.0078125, -100.0, -100.0]
    params = _one_window(512.0)
    new_gx, new_gb = _window_grads(convnet_apply, params, window, jnp.bfloat16)
    old_gx, old_gb = _window_grads(old_order_apply, params, window,
                                   jnp.bfloat16)
    assert new_gb == old_gb != 0.0
    np.testing.assert_array_equal(new_gx != 0, [False, True, False, False])
    np.testing.assert_array_equal(old_gx != 0, [True, False, False, False])
    assert new_gx[1] == old_gx[0]
    # Equal before the bias: the first in window order.
    tied_gx, _ = _window_grads(convnet_apply, params, [2.0, 2.0, 2.0, -1.0],
                               jnp.bfloat16)
    np.testing.assert_array_equal(tied_gx != 0, [True, False, False, False])
