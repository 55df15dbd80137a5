"""The tiled attention core that leaves out the blocks across two documents
(fedtpu.ops.packed_attention, through ``packed_attention._fused_attention``): its
kernels interpreted on the CPU against the XLA body, which defines what is
computed; the table it decides from against a numpy count of the allowed
pairs; the two block counters of all three language models."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from fedtpu.models.registry import build_model
from fedtpu.ops import packed_attention
from fedtpu.training.task import build_task
from tests.test_olmoe import _core_and_gradients, _gap, _row

# Eight blocks of one lane tile a row (the chip's rows are 8 or 16 blocks of
# 512): a block far from the diagonal, a document over several blocks and a
# block of padding alone all exist, and the interpreter takes seconds.
BLOCK, T, HEADS = 128, 1024, 2
LAYOUTS = {
    # (a) one document: nothing is left out, the library's grid
    "one_document": (T,),
    # (b) many documents, then padding: a whole block of it and one token
    "documents_and_padding": (70, 200, 45, 130, 260, 100, 90),
    # (c) a document over blocks 0 to 3, its neighbours inside one or two
    "a_document_over_three_blocks": (100, 400, 300, 224),
}


def _segs(docs, t=T):
    return _row(0, docs, t=t)[1]


def allowed_blocks(segs, block):
    """``[query block, key block]``: whether the XLA body's mask (causal,
    equal segment ids) allows any pair of the two blocks. Plain numpy over
    the whole ``(T, T)`` mask."""
    at, blocks = np.arange(len(segs)), len(segs) // block
    allowed = (at[:, None] >= at[None, :]) & (segs[:, None] == segs[None, :])
    return allowed.reshape(blocks, block, blocks, block).any(axis=(1, 3))


def _kept(segs, block):
    return np.asarray(packed_attention.pairs_kept(jnp.asarray(segs), block))


# The tolerances tests/test_olmoe.py::test_the_fused_attention_body_is_the_
# xla_body holds (its comment says what they were measured against): float32
# differs by rounding alone, bfloat16 by the kernel's rounded context and
# probabilities; a block left out that held an allowed pair, or one run
# twice, moves entries by their own size in either.
@pytest.mark.parametrize("dtype,ctx_tol,grad_tol", [
    (jnp.float32, 1e-5, 1e-5), (jnp.bfloat16, 3e-2, 6e-2)],
    ids=["float32", "bfloat16"])
@pytest.mark.parametrize("width", [128, 256])
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_the_tiled_core_is_the_xla_body_on_packed_rows(
        monkeypatch, layout, width, dtype, ctx_tol, grad_tol):
    monkeypatch.setattr(packed_attention, "ATTENTION_BLOCK", BLOCK)
    segs = _segs(LAYOUTS[layout])
    kept, causal = _kept(segs, BLOCK).sum(), 8 * 9 // 2
    # the rows exercise what they are named for: 36, 15 and 21 of 36
    assert (kept == causal) == (layout == "one_document")
    assert layout == "one_document" or 8 < kept < 2 * causal // 3
    args = [jax.random.normal(k, (T, HEADS, width))
            for k in jax.random.split(jax.random.key(7), 4)]
    segs = jnp.asarray(segs)
    with pltpu.force_tpu_interpret_mode():
        ctx, grads = _core_and_gradients(packed_attention._fused_attention, dtype,
                                         segs)(*args)
    want, want_grads = _core_and_gradients(packed_attention._xla_attention, dtype,
                                           segs)(*args)
    assert ctx.dtype == want.dtype == jnp.float32
    assert float(jnp.max(jnp.abs(ctx - want))) <= ctx_tol
    assert _gap(grads, want_grads) <= grad_tol
    assert float(jnp.max(jnp.abs(want_grads[0]))) > 1.0


@pytest.mark.parametrize("seed", range(6))
def test_the_table_leaves_out_no_block_that_holds_an_allowed_pair(seed):
    """Over random packings: a row as the corpus packs it (documents whole,
    padding at its end) keeps exactly the blocks with an allowed pair on or
    under the diagonal; a row of ANY ids (padding inside, an id come back
    after another) keeps all of those, and nothing above the diagonal."""
    rng = np.random.default_rng(seed)
    block, t = 16, 512
    docs = []
    while sum(docs) < t:
        docs.append(int(np.clip(rng.lognormal(np.log(40), 1.0), 2, t)))
    packed = _segs(docs[:-1], t)            # the last did not fit: padding
    assert 0 < (packed == 0).sum() < t
    np.testing.assert_array_equal(_kept(packed, block),
                                  allowed_blocks(packed, block))
    runs = rng.integers(1, 24, 96)
    anyhow = np.repeat(rng.integers(0, 4, 96), runs)[:t]
    assert len(anyhow) == t and (anyhow == 0).any()
    kept, allowed = _kept(anyhow, block), allowed_blocks(anyhow, block)
    assert not (allowed & ~kept).any()
    assert not np.triu(kept, 1).any() and kept.diagonal().all()
    assert kept.sum() < (t // block) * (t // block + 1) // 2   # and it engages


def test_a_step_left_out_holds_the_block_of_the_next_step_that_runs():
    kept = jnp.asarray([[1, 0, 0, 0], [0, 1, 0, 0], [1, 0, 1, 0],
                        [0, 0, 1, 1]], bool)
    held = np.asarray(packed_attention._held(kept)).reshape(4, 4)
    np.testing.assert_array_equal(held, [[0, 1, 1, 1], [1, 1, 0, 0],
                                         [0, 2, 2, 2], [2, 2, 2, 3]])
    # every running step holds its own block
    assert (held[np.asarray(kept)] == np.nonzero(np.asarray(kept))[1]).all()


def _tiny(kind):
    from tests.test_stateless_round import tiny_nemotron_h, tiny_olmoe
    from tests.test_xing4 import tiny_xing4
    return {"olmoe": tiny_olmoe, "nemotron_h": tiny_nemotron_h,
            "xing4": tiny_xing4}[kind]().model


# Layers with an attention core: OLMoE's one, the ``*`` of ``ME*ME``, and
# the four-stream stack's three blocks and its prediction module's.
@pytest.mark.parametrize("kind,layers", [
    ("olmoe", 1), ("nemotron_h", 1), ("xing4", 4)])
def test_the_block_counters_are_the_numpy_count(monkeypatch, kind, layers):
    """Every model's statistics count, a sequence and attention layer, the
    block pairs the fused body runs and those on or under the diagonal, from
    the kernels' own table: told the fused body applies (its place taken by
    the XLA body, which these widths can run), 48-token rows in blocks of 8.
    A one-document row reads computed == causal; a masked row counts for
    nothing; where the XLA body is what runs both are 0."""
    block, t = 8, 48
    cfg = _tiny(kind)
    init_fn, stats_fn = build_model(cfg)
    params = init_fn(jax.random.key(0))
    x = np.stack([_row(3, docs, t=t, vocab=cfg.vocab_size)
                  for docs in ((5, 9, 14, 6), (t,), (20, 20))])
    rows, x = x[:, 1], jnp.asarray(x)
    counters = build_task(cfg, stats_fn, cfg.vocab_size).counters
    on_the_cpu = counters(stats_fn(params, x[:1], jnp.ones((1,))))
    assert on_the_cpu["lm_attention_blocks_computed"] == 0
    assert on_the_cpu["lm_attention_blocks_causal"] == 0

    monkeypatch.setattr(packed_attention, "ATTENTION_BLOCK", block)
    monkeypatch.setattr(packed_attention, "fused_attention_applies",
                        lambda q, k, v: True)
    monkeypatch.setattr(packed_attention, "_fused_attention",
                        packed_attention._xla_attention)
    causal = (t // block) * (t // block + 1) // 2
    want = [int(np.tril(allowed_blocks(s, block)).sum()) for s in rows]
    assert want[1] == causal and want[0] < causal and want[2] < causal
    for mask in ([1.0, 0.0, 0.0], [0.0, 1.0, 0.0], [1.0, 1.0, 0.0]):
        got = counters(stats_fn(params, x, jnp.asarray(mask)))
        assert got["lm_fused_attention_positions"] == t * sum(mask)
        assert got["lm_attention_blocks_computed"] == layers * sum(
            n * m for n, m in zip(want, mask))
        assert got["lm_attention_blocks_causal"] == layers * causal * sum(mask)
