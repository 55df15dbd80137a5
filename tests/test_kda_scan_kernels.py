"""The delta-rule recurrence's tiled kernels (fedtpu.ops.kda_scan), interpreted
on the CPU, against the two things that say what they compute: the chunked XLA
form ``kda_scan.kda_scan`` (the definition, and the body wherever the
kernels do not exist) and the reference's token-by-token recurrence. Values
and the gradient of every input in float32 at the tolerances the definition's
own test holds, over its hard cases; what bfloat16 products move and what a
bfloat16 state would; the rule between the two bodies."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from fedtpu.models import kimi_linear as kl
from fedtpu.ops import kda_scan as kernels
from fedtpu.ops import ssm_passes
from perfbench import reference_kimi_linear as ref
from tests.test_kimi_linear import (ONE, SEVERAL, T, TINY, _scan_inputs,
                                    seeded)

# a document's first token on a chunk's edge (64) and inside one (104)
ON_AN_EDGE = [1] * 64 + [2] * 40 + [3] * 24
PADDING = [0] * T


def _kernel(run, chunk, sub, dtype):
    """The kernels' ``kda_scan`` of one row, jitted (the interpreter is
    driven under ``jax.jit`` alone: SKILL.md)."""
    return jax.jit(lambda *a: kernels.fused_kda_scan(*a, run, chunk, sub, dtype))


def _with_gradients(fn, inputs, weigh):
    """``(fn(*inputs), the gradient of its weighted sum by each input)`` from
    ONE jitted program: under the rule the kernels' forward pass also writes
    what the backward pass reads."""
    def total(*a):
        out = fn(*a)
        return (out * weigh).sum(), out

    both = jax.jit(jax.value_and_grad(total, argnums=range(5), has_aux=True))
    (_, out), gradients = both(*inputs)
    return out, gradients


@pytest.mark.parametrize("segs,chunk,sub,strength,bias", [
    (SEVERAL, 64, 16, 0.1, 0), (ON_AN_EDGE, 64, 16, 0.1, 0),
    (ONE, 64, 16, 0.1, 0), (SEVERAL, 64, 16, 4.0, 0), (ONE, 64, 16, 4.0, 0),
    (ONE, 64, 16, 0.01, 3), (PADDING, 64, 16, 0.1, 0),
    (SEVERAL, 32, 8, 0.1, 0), (SEVERAL, 16, 16, 0.1, 0),
    (SEVERAL, 128, 16, 0.1, 0)],
    ids=["restarts-inside-chunks", "a-restart-on-a-chunks-edge",
         "one-document", "overflowing-decay", "overflowing-one-document",
         "keys-alike-and-slow-decay", "a-row-of-padding", "chunk32-sub8",
         "chunk16-one-sub-chunk", "one-chunk-three-levels"])
def test_the_kernels_are_the_definition_and_the_token_by_token_recurrence(
        segs, chunk, sub, strength, bias):
    """Values and the gradient of each of q, k, v, g and beta, float32,
    against the XLA form AND the reference's recurrence, at the tolerances
    ``test_the_chunked_recurrence_is_the_token_by_token_one`` holds (the
    kernels' gaps to the recurrence are the definition's own: 2e-7 to 2e-6
    of a gradient's scale over these cases): documents that start inside a
    chunk, on its edge and nowhere, decays at which ``exp(-G)`` overflows
    float32, keys alike under hardly any decay (the case a product of powers
    for the inverse read 1e28 in), a row that is padding alone, a chunk of
    one sub-chunk (no level) and of eight (three levels)."""
    segs = jnp.asarray(segs, jnp.int32)
    *inputs, weigh = _scan_inputs(segs, strength, bias=bias)
    run, starts = ssm_passes.document_runs(segs)
    with pltpu.force_tpu_interpret_mode():
        ours, ours_d = _with_gradients(
            _kernel(run, chunk, sub, jnp.float32), inputs, weigh)
    for name, other in (
            ("definition", lambda *a: kernels.kda_scan(*a, run, chunk, jnp.float32,
                                                  sub)),
            ("recurrence", lambda *a: ref.kda_recurrence(*a, starts))):
        theirs, theirs_d = _with_gradients(other, inputs, weigh)
        np.testing.assert_allclose(np.asarray(ours), np.asarray(theirs),
                                   rtol=0, atol=2e-6, err_msg=name)
        for leaf, a, b in zip("qkvgb", ours_d, theirs_d):
            assert bool(jnp.isfinite(a).all()), leaf
            scale = float(jnp.abs(b).max())
            assert scale > 0.1, leaf                        # it is reached
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=0,
                atol=2e-5 * max(scale, 1.0), err_msg=f"{name} d{leaf}")


def test_bfloat16_products_stay_near_the_float32_ones_in_the_kernels():
    """As the definition's: the chunk's large products with bfloat16 inputs
    move the result, by under 3% of its size, and the gradients likewise."""
    segs = jnp.asarray(SEVERAL, jnp.int32)
    *inputs, weigh = _scan_inputs(segs, 0.3, seed=2)
    run = ssm_passes.document_runs(segs)[0]
    with pltpu.force_tpu_interpret_mode():
        exact, exact_d = _with_gradients(
            _kernel(run, 64, 16, jnp.float32), inputs, weigh)
        rounded, rounded_d = _with_gradients(
            _kernel(run, 64, 16, jnp.bfloat16), inputs, weigh)
    assert rounded.dtype == jnp.float32
    for a, b in zip((exact, *exact_d), (rounded, *rounded_d)):
        assert 1e-6 < float(jnp.abs(a - b).max()) < 0.03 * float(
            jnp.abs(a).max())


def test_a_bfloat16_state_is_told_apart_at_ten_tolerances(monkeypatch):
    """The control the chip's comparison cannot show (PERF.md section 6, PR
    39: it reads 0.0054 inside 0.082): with the state a chunk hands the next
    rounded to bfloat16 the float32 comparison fails ten times over."""
    segs = jnp.asarray(ONE, jnp.int32)
    *inputs, _ = _scan_inputs(segs, 0.1)
    run, starts = ssm_passes.document_runs(segs)
    chunk = kernels._forward_chunk

    def rounded_state(*args):
        o, state = chunk(*args)
        return o, state.astype(jnp.bfloat16).astype(jnp.float32)

    monkeypatch.setattr(kernels, "_forward_chunk", rounded_state)
    with pltpu.force_tpu_interpret_mode():
        ours = _kernel(run, 64, 16, jnp.float32)(*inputs)
    gap = float(jnp.abs(ours - ref.kda_recurrence(*inputs, starts)).max())
    assert gap > 10 * 2e-6, gap


def test_the_rule_between_the_bodies(monkeypatch):
    """``fused_scan_applies``: no on a CPU backend whatever the shapes; on a
    TPU yes at the cell's shapes and no at a head 64 wide, at a row that is
    not whole chunks and at a chunk that is not whole sub-chunks doubling up
    to it; and where it says no the XLA form runs, where yes the kernels."""
    applies = lambda t, d, chunk=64, sub=16: kernels.fused_scan_applies(
        t, d, d, chunk, sub)
    assert jax.default_backend() == "cpu" and not applies(4096, 128)

    def never(*args):
        raise AssertionError("the kernels were called")

    segs = jnp.asarray(SEVERAL, jnp.int32)
    *inputs, _ = _scan_inputs(segs, 0.1)
    run = ssm_passes.document_runs(segs)[0]
    with monkeypatch.context() as patch:
        patch.setattr(kernels, "fused_kda_scan", never)
        theirs = kernels.kda_scan(*inputs, run, 64, jnp.float32)     # the XLA form
        patch.setattr(jax, "default_backend", lambda: "tpu")
        assert applies(4096, 128) and applies(8192, 256)
        assert not applies(4096, 64)
        assert not applies(4096 + 32, 128)
        assert not applies(4096, 128, chunk=48) and not applies(4096, 128, sub=12)
        assert bool(jnp.array_equal(
            kernels.kda_scan(*inputs, run, 64, jnp.float32), theirs))   # d = 8
    # told the shapes have tiles, the same call goes through the kernels
    monkeypatch.setattr(kernels, "fused_scan_applies", lambda *a: True)
    through = jax.jit(lambda *a: kernels.kda_scan(*a, run, 64, jnp.float32))
    with pltpu.force_tpu_interpret_mode():
        ours = through(*inputs)
    np.testing.assert_allclose(np.asarray(ours), np.asarray(theirs), rtol=0,
                               atol=2e-6)


def test_a_mixer_on_the_kernels_and_its_gates_on_their_tiles_is_the_plain_one(
        monkeypatch):
    """A whole KDA mixer with the rule told yes (the gates computed on
    ``_head_tiles`` of eight rows, what the kernels read in place; the
    recurrence interpreted) against the same mixer as every CPU run takes
    it: the output, the deepest decay, and the gradient of the input and of
    every weight; ``kda_fused_scan`` says which ran."""
    layer = seeded(TINY)["layers"][0]["mixer"]
    h = jax.random.normal(jax.random.key(3), (T, TINY.hidden_size))
    segs = jnp.asarray(SEVERAL, jnp.int32)
    weigh = jax.random.normal(jax.random.key(4), h.shape)

    def both():
        def total(h, layer):
            out, stats = kl.kda_mixer(TINY, jnp.float32, h, layer, segs)
            return (out * weigh).sum(), (out, stats)

        # a program a body: the rule is read while it is traced
        program = jax.jit(jax.value_and_grad(total, argnums=(0, 1),
                                             has_aux=True))
        return program(h, layer)

    (_, (plain, plain_stats)), plain_d = both()
    monkeypatch.setattr(kernels, "fused_scan_applies", lambda *sizes: True)
    with pltpu.force_tpu_interpret_mode():
        (_, (tiled, tiled_stats)), tiled_d = both()
    assert float(plain_stats["kda_fused_scan"]) == 0.0
    assert float(tiled_stats["kda_fused_scan"]) == float(
        tiled_stats["kda_positions"]) == T
    np.testing.assert_allclose(float(tiled_stats["kda_log_decay_min"]),
                               float(plain_stats["kda_log_decay_min"]),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(tiled), np.asarray(plain), rtol=0,
                               atol=2e-6)
    for a, b in zip(jax.tree.leaves(tiled_d), jax.tree.leaves(plain_d)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=0,
            atol=2e-5 * max(float(jnp.abs(b).max()), 1.0))


def test_how_far_back_a_positions_run_reaches():
    run = ssm_passes.document_runs(jnp.asarray([3, 3, 3, 5, 5, 0, 0, 2], jnp.int32))[0]
    assert kernels.positions_back(run, 4).T.tolist() == [
        [0, 1, 2, 0, 1, 0, 1, 0], [0, 0, 0, 0, 0, 0, 0, 0]]
    assert kernels.positions_back(run, 8)[:, 1].tolist() == [0] * 8
    assert kernels.positions_back(run[:7], 7)[:, 1].tolist() == [1] * 7
