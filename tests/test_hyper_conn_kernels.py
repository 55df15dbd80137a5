"""The residual modules' tiled passes over the streams (fedtpu.ops.hyper_conn),
interpreted on the CPU, against what says what they compute: ``xing4.
hyper_mix``, ``hyper_read`` and ``hyper_write`` in XLA (the definitions, and
the body wherever the kernels do not exist). Values and the gradient of the
streams, of ``y`` and of every leaf of the module (through the Sinkhorn loop)
in float32 over the hard cases; the rule between the two bodies; one whole
block of the stack on the kernels against the plain one."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from fedtpu.models import layers, xing4
from fedtpu.ops import hyper_conn as kernels
from tests.test_xing4 import TINY, packed_row, seeded

N, T, C = 4, 256, 128
WIDE = dataclasses.replace(TINY, hidden_size=C)
CLAMP = (WIDE.mhc_h_res_clamp_min, WIDE.mhc_h_res_clamp_max)


def _case(scales, res_bias=0.0, padding=False, seed=0):
    """``(streams, y0, module)``: streams of the given scales (a tile of
    zeros where ``padding``), a sublayer's additive output, a module whose
    stream-to-stream logits are moved by ``res_bias``."""
    module = xing4._hyper_init(WIDE, jax.random.key(seed), jnp.float32)
    module["bias"] = module["bias"].at[2 * N:].add(res_bias)
    x = (jax.random.normal(jax.random.key(seed + 1), (N, T, C))
         * jnp.asarray(scales, jnp.float32)[:, None, None])
    y0 = jax.random.normal(jax.random.key(seed + 2), (T, C))
    if padding:         # the second of the four tiles holds no token's state
        x = x.at[:, kernels.ROWS:2 * kernels.ROWS].set(0.0)
        y0 = y0.at[kernels.ROWS:2 * kernels.ROWS].set(0.0)
    return x, y0, module


def _sublayer_with_gradients(x, y0, module):
    """The streams after one sublayer ``y = tanh(u) + y0`` and the gradient
    of a weighted sum of them by the streams, ``y0`` (that is ``dy``) and the
    module's leaves, from ONE jitted program."""
    weigh = jnp.cos(jnp.arange(N * T * C, dtype=jnp.float32)).reshape(N, T, C)

    def total(x, y0, module):
        out, _, off = xing4.sublayer(
            WIDE, x, module, lambda u: (jnp.tanh(u) + y0, {}))
        return (out * weigh).sum(), (out, off)

    both = jax.jit(jax.value_and_grad(total, argnums=(0, 1, 2), has_aux=True))
    (_, (out, off)), gradients = both(x, y0, module)
    return out, off, gradients


@pytest.mark.parametrize("scales,res_bias,padding", [
    ((1, 2, 3, 4), 0.0, False), ((1, 2, 3, 4), 2 * CLAMP[1], False),
    ((1, 2, 3, 4), 2 * CLAMP[0], False), ((1, 2, 3, 4), 0.0, True),
    ((1, 30, 0.03, 1), 0.0, False)],
    ids=["streams-that-differ", "logits-over-the-upper-clamp",
         "logits-under-the-lower-clamp", "a-tile-of-padding",
         "streams-of-unequal-scale"])
def test_the_kernels_are_the_definitions(scales, res_bias, padding,
                                         monkeypatch):
    """Values and the gradients of the streams, ``y``, ``phi``, ``alpha`` and
    ``bias``, four row tiles, against the XLA definitions under autodiff:
    2e-6 of the largest entry (the sums over ``C`` and over the positions
    are the only ones in another order). Logits clipped at either clamp give
    ``phi`` and the stream-to-stream ``alpha`` and ``bias`` no gradient
    through ``H_res`` in both bodies; a tile of zeros (what the norm sees of
    padding: ``1 / rms`` is ``eps^-1/2`` there) stays finite."""
    x, y0, module = _case(scales, res_bias, padding)
    want, want_off, want_d = _sublayer_with_gradients(x, y0, module)
    monkeypatch.setattr(kernels, "hyper_passes_apply", lambda x: True)
    with pltpu.force_tpu_interpret_mode():
        ours, off, ours_d = _sublayer_with_gradients(x, y0, module)
    np.testing.assert_allclose(np.asarray(ours), np.asarray(want), rtol=0,
                               atol=2e-6 * float(jnp.abs(want).max()))
    assert abs(float(off) - float(want_off)) <= 1e-6
    leaves = jax.tree_util.tree_leaves_with_path(ours_d)
    assert len(leaves) == 5
    for (path, a), b in zip(leaves, jax.tree.leaves(want_d)):
        assert bool(jnp.all(jnp.isfinite(a))), path
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=0,
            atol=2e-6 * float(jnp.abs(b).max()) + 1e-30,
            err_msg=jax.tree_util.keystr(path))
    if res_bias:        # every stream-to-stream logit sits on a clamp
        assert float(jnp.abs(ours_d[2]["bias"][2 * N:]).max()) == 0.0


def test_the_rule_between_the_bodies(monkeypatch):
    """``hyper_passes_apply``: on a TPU, float32 streams, whole row tiles,
    ``C`` whole lane tiles, and a tile within the chip's own memory at this
    ``n``; the definitions everywhere else, this CPU among them."""
    streams = lambda n, t, c, dtype=jnp.float32: jax.ShapeDtypeStruct(
        (n, t, c), dtype)
    cell = streams(4, 4096, 3584)
    assert not kernels.hyper_passes_apply(cell)           # this is a CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert kernels.hyper_passes_apply(cell)
    assert kernels.hyper_passes_apply(streams(4, 2048, 3584))
    assert kernels.hyper_passes_apply(streams(2, 64, 128))
    assert not kernels.hyper_passes_apply(streams(4, 4096, 3584, jnp.bfloat16))
    assert not kernels.hyper_passes_apply(streams(4, 4096 + 32, 3584))
    assert not kernels.hyper_passes_apply(streams(4, 4096, 3584 + 64))
    assert not kernels.hyper_passes_apply(streams(4, 4096, 48))
    assert not kernels.hyper_passes_apply(streams(16, 4096, 3584))    # memory
    assert kernels.columns(4) == 32 and kernels.columns(2) == 16


def test_a_block_on_the_kernels_is_the_plain_block(hyper_passes_on_the_cpu,
                                                   monkeypatch):
    """One expert block of the stack, both its residual modules on the
    kernels (four row tiles of 16), against the same block on the
    definitions: the streams, the statistics and every weight's gradient."""
    monkeypatch.setattr(kernels, "ROWS", 16)
    params = seeded(TINY)["experts"][0]
    row = jnp.asarray(packed_row(np.random.default_rng(0), (30, 20, 9)))
    segs = row[1]
    pos = layers.segment_positions(segs)
    x = jax.random.normal(jax.random.key(5), (TINY.hc_mult, row.shape[1],
                                              TINY.hidden_size))

    def total(x, layer):
        out, stats = xing4.block("experts", TINY, jnp.float32, x, layer, segs,
                                 pos)
        return jnp.sin(out).sum(), (out, stats)

    both = lambda: jax.jit(jax.value_and_grad(total, argnums=(0, 1),
                                              has_aux=True))
    (_, (ours, stats)), ours_d = both()(x, params)
    monkeypatch.setattr(kernels, "hyper_passes_apply", lambda x: False)
    (_, (want, want_stats)), want_d = both()(x, params)
    np.testing.assert_allclose(np.asarray(ours), np.asarray(want), rtol=0,
                               atol=1e-5)
    assert stats.keys() == want_stats.keys()
    for name in stats:
        np.testing.assert_allclose(np.asarray(stats[name]),
                                   np.asarray(want_stats[name]), rtol=0,
                                   atol=1e-6, err_msg=name)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(ours_d),
                            jax.tree.leaves(want_d)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=0,
            atol=1e-5 * float(jnp.abs(b).max()) + 1e-30,
            err_msg=jax.tree_util.keystr(path))
