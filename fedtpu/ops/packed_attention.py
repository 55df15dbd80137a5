"""The attention core of one packed row: one function, two bodies.

``attention_core(q, k, v, segs)`` is ``softmax(mask(q k^T scale)) v``, causal
within a document, and under a window where one is asked for (a query sees
the ``window`` positions that end at its own: the table of kept block pairs,
the mask inside a block and the XLA body each take it; with none, each is
what it was). Its XLA body (``_xla_attention``) is the definition: it
writes the ``[heads, T, T]`` float32 scores, the masked scores and the
probabilities to memory and keeps them for the backward pass. Its fused body
(``_fused_attention``) is the three tiled kernels below with an online
softmax, which never hold a ``[heads, T, T]`` array: the same mask, bf16
matmul inputs, float32 accumulation, maximum, sum and exponentials. Which
body runs is read off what the code can see and is nobody's to set
(``fused_attention_applies``): the fused body when the program is built for a
TPU, the head width is a multiple of 128 lanes, ``T`` a multiple of the
kernel's block and q, k and v share one width (a head whose query-key and
value widths differ is padded with zeros to one, ``padded_head_width``, and
its context cut back); the XLA body everywhere else. The fused backward takes
its row term ``sum(o * do)`` from the bf16 ``ctx`` and feeds bf16 ``dS`` to
its matmuls, where the XLA body's softmax backward is float32 throughout:
within "bf16 matmul inputs", and measured inside the benchmark's limits
(PERF.md section 6, PR 26). A model's statistics say how many positions ran
fused and how many block pairs of those on or under the diagonal
(``attention_blocks``).

**The kernels: tiled causal attention within the documents of one packed
row, computing only the block pairs that can hold an allowed pair.**

The three kernels (forward with an online softmax, ``dk``/``dv``, ``dq``) are
the bodies of the library's ``jax.experimental.pallas.ops.tpu.flash_attention``
at one block size, causal, with segment ids and without a bias: bf16 (or
whatever the operands are) matmul inputs, float32 accumulation, maximum, sum
and exponentials, the same mask inside a block. The library's kernels take
the segment ids as a mask inside a block and leave out only the blocks above
the diagonal; a row of several documents is mostly blocks that lie wholly
across two of them, whose every probability is zero.

What decides is a table of the row, made once a call on the device:
``block_ranges`` holds the least and the largest segment id of each block
(padding's 0 relabelled to the largest int32: equal ids stay equal, and a row
whose padding stands at its end has ids that never fall, so the ranges are
tight), and ``pairs_kept`` keeps a (query block, key block) pair on or under
the diagonal whose two ranges overlap. The test is conservative for any
layout of ids: a pair with one allowed (query, key) has that id in both
ranges. A kept block runs under the full mask, as in the library. A row that
is one document keeps every pair on or under the diagonal and runs the
library's grid.

The kernels are handed two int32 tables ahead of the grid (scalar prefetch),
one entry a grid step in the order the grid walks: whether the step runs, and
which block of the inner operand to hold at it. A step left out holds the
block of the next step that runs, so nothing is fetched for it and the block
a running step needs is there before it (``_held``; the library points a
skipped step above the diagonal at block 0 to the same end).

Once a row has met an allowed key a skipped block's probabilities are exactly
zero, and what a fully masked block adds before that (the library's online
softmax gives its keys equal weight) is wiped by the rescaling at the first
allowed key, which every row has in its diagonal block. So the context and
the three gradients are the library kernel's but for the float32 rounding of
the rescaling steps a skipped block no longer makes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fedtpu.ops.scopes import ATTN_CORE

LANES, SUBLANES = 128, 8
# The library's: far below any score, and finite so that a row's maximum is.
MASK_VALUE = -0.7 * float(np.finfo(np.dtype("float32")).max)
_TRANS_B = (((1,), (1,)), ((), ()))
_PADDING_LAST = np.iinfo(np.int32).max


def block_ranges(segs, block: int):
    """``(T / block, 2)`` int32: the least and the largest segment id of each
    block of ``segs (T,)``, padding's 0 counted as the largest int32."""
    ids = jnp.where(segs == 0, _PADDING_LAST, segs).reshape(-1, block)
    return jnp.stack([ids.min(axis=1), ids.max(axis=1)], axis=1)


def pairs_kept(segs, block: int, window: int | None = None):
    """``(blocks, blocks)`` bool, ``[query block, key block]``, of the row
    ``segs (T,)``: on or under the diagonal, and the two blocks' ranges of
    ids (``block_ranges``) overlap. Under a ``window`` (a query sees the
    ``window`` positions that end at its own) also: the key block's last
    position is within the window of the query block's first."""
    ranges = block_ranges(segs, block)
    lo, hi = ranges[:, 0], ranges[:, 1]
    at = jnp.arange(ranges.shape[0])
    kept = ((at[:, None] >= at[None, :]) & (lo[:, None] <= hi[None, :])
            & (lo[None, :] <= hi[:, None]))
    if window is None:
        return kept
    # q0 - (k0 + block - 1) < window, q0 and k0 the blocks' first positions
    return kept & ((at[:, None] - at[None, :]) * block - (block - 1) < window)


def _held(kept):
    """The inner block to hold at each step of a grid that walks ``kept
    (outer, inner)`` row by row, ``(outer * inner,)`` int32: a running
    step's own, else that of the next step that runs (the last step, on the
    diagonal, always does). One comparison of every step with every other
    (4,096 to 65,536 of them): a single small fusion, where a cumulative
    minimum is a ladder of them."""
    steps = kept.size
    at = jnp.arange(steps, dtype=jnp.int32)
    at_or_after = kept.reshape(1, -1) & (at[None, :] >= at[:, None])
    following = jnp.min(jnp.where(at_or_after, at[None, :], steps - 1),
                        axis=1)
    return following % kept.shape[1]


def _mask(q_ids_ref, k_ids_ref, qi, ki, block, window=None):
    """Causal, and equal segment ids: ``(block, block)`` bool of the pair of
    blocks ``(qi, ki)``; under a ``window``, the key within it too."""
    q_ids = jnp.tile(q_ids_ref[...], (1, block // LANES))
    rows = lax.broadcasted_iota(jnp.int32, (block, block), 0) + qi * block
    cols = lax.broadcasted_iota(jnp.int32, (block, block), 1) + ki * block
    mask = jnp.logical_and(q_ids == k_ids_ref[:1, :], cols <= rows)
    if window is None:
        return mask
    return jnp.logical_and(mask, rows - cols < window)


def _wide(a, width):
    return jnp.tile(a, (1, width // LANES))


def _forward_kernel(runs_ref, held_ref, q_ref, k_ref, v_ref, q_ids_ref,
                    k_ids_ref, o_ref, *rest, sm_scale, window=None):
    # the rows' sums and maxima are written where a backward pass will read
    # them, and not by a forward pass alone
    *statistics, m_scratch, l_scratch, acc_scratch = rest
    del held_ref
    qi, ki, blocks = pl.program_id(1), pl.program_id(2), pl.num_programs(2)
    block, width = q_ref.shape[1:]

    @pl.when(ki == 0)
    def _():
        m_scratch[...] = jnp.full(m_scratch.shape, -jnp.inf, jnp.float32)
        l_scratch[...] = jnp.zeros(l_scratch.shape, jnp.float32)
        acc_scratch[...] = jnp.zeros(acc_scratch.shape, jnp.float32)

    @pl.when(runs_ref[qi * blocks + ki] != 0)
    def _():
        m_prev, l_prev = m_scratch[...], l_scratch[...]
        s = lax.dot_general(q_ref[0], k_ref[0], _TRANS_B,
                            preferred_element_type=jnp.float32)
        if sm_scale != 1.0:
            s *= sm_scale
        s += jnp.where(_mask(q_ids_ref, k_ids_ref, qi, ki, block, window),
                       0.0, MASK_VALUE)
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=1)[:, None])
        p = jnp.exp(s - _wide(m_next, block))
        l_corr = jnp.exp(m_prev - m_next) * l_prev
        l_next = jnp.sum(p, axis=1)[:, None] + l_corr
        l_scratch[...], m_scratch[...] = l_next, m_next
        l_next_inv = jnp.where(l_next == 0.0, 1.0, 1.0 / l_next)
        acc_scratch[...] *= _wide(l_corr * l_next_inv, width)
        v = v_ref[0]
        acc_scratch[...] += lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32
        ) * _wide(l_next_inv, width)

    @pl.when(ki == blocks - 1)
    def _():
        o_ref[0] = acc_scratch[...].astype(o_ref.dtype)
        for ref, scratch in zip(statistics, (l_scratch, m_scratch)):
            ref[0] = scratch[...]


def _probabilities_and_ds(q, k, v, l, m, do, di, mask, sm_scale):
    """A block pair's probabilities and the scores' gradient, float32, from
    the forward pass's row sums ``l`` and maxima ``m``."""
    block = k.shape[0]
    s = lax.dot_general(q, k, _TRANS_B, preferred_element_type=jnp.float32)
    if sm_scale != 1.0:
        s *= sm_scale
    s += jnp.where(mask, 0.0, MASK_VALUE)
    p = jnp.exp(s - _wide(m, block)) * _wide(1 / l, block)
    dp = lax.dot_general(do, v, _TRANS_B, preferred_element_type=jnp.float32)
    ds = (dp - _wide(di, block)) * p
    if sm_scale != 1.0:
        ds = ds * sm_scale
    return p, ds


def _dkv_kernel(runs_ref, held_ref, q_ref, k_ref, v_ref, q_ids_ref, k_ids_ref,
                l_ref, m_ref, do_ref, di_ref, dk_ref, dv_ref, dk_scratch,
                dv_scratch, *, sm_scale, window=None):
    del held_ref
    ki, qi, blocks = pl.program_id(1), pl.program_id(2), pl.num_programs(2)
    block = q_ref.shape[1]

    @pl.when(qi == 0)
    def _():
        dk_scratch[...] = jnp.zeros(dk_scratch.shape, jnp.float32)
        dv_scratch[...] = jnp.zeros(dv_scratch.shape, jnp.float32)

    @pl.when(runs_ref[qi * blocks + ki] != 0)
    def _():
        q, do = q_ref[0], do_ref[0]
        p, ds = _probabilities_and_ds(
            q, k_ref[0], v_ref[0], l_ref[0], m_ref[0], do, di_ref[0],
            _mask(q_ids_ref, k_ids_ref, qi, ki, block, window), sm_scale)
        dv_scratch[...] += lax.dot(p.T.astype(do.dtype), do,
                                   preferred_element_type=jnp.float32)
        dk_scratch[...] += lax.dot(ds.T.astype(do.dtype), q,
                                   preferred_element_type=jnp.float32)

    @pl.when(qi == blocks - 1)
    def _():
        dv_ref[0] = dv_scratch[...].astype(dv_ref.dtype)
        dk_ref[0] = dk_scratch[...].astype(dk_ref.dtype)


def _dq_kernel(runs_ref, held_ref, q_ref, k_ref, v_ref, q_ids_ref, k_ids_ref,
               l_ref, m_ref, do_ref, di_ref, dq_ref, dq_scratch, *, sm_scale,
               window=None):
    del held_ref
    qi, ki, blocks = pl.program_id(1), pl.program_id(2), pl.num_programs(2)
    block = q_ref.shape[1]

    @pl.when(ki == 0)
    def _():
        dq_scratch[...] = jnp.zeros(dq_scratch.shape, jnp.float32)

    @pl.when(runs_ref[qi * blocks + ki] != 0)
    def _():
        k = k_ref[0]
        _, ds = _probabilities_and_ds(
            q_ref[0], k, v_ref[0], l_ref[0], m_ref[0], do_ref[0], di_ref[0],
            _mask(q_ids_ref, k_ids_ref, qi, ki, block, window), sm_scale)
        dq_scratch[...] += lax.dot(ds.astype(k.dtype), k,
                                   preferred_element_type=jnp.float32)

    @pl.when(ki == blocks - 1)
    def _():
        dq_ref[0] = dq_scratch[...].astype(dq_ref.dtype)


def _specs(block, width, blocks, queries_inner: bool):
    """``(rows, keys, row_ids, key_ids, row_sums)``: the block specs of an
    operand by the query's rows, by the key's, of the two forms of the
    segment ids and of a ``(heads, T, LANES)`` row statistic, for a grid
    ``(heads, outer, inner)`` whose outer operand is at its own block and
    whose inner one is held as the second table says."""
    def q_block(h, i, j, runs, held):
        return held[i * blocks + j] if queries_inner else i

    def k_block(h, i, j, runs, held):
        return i if queries_inner else held[i * blocks + j]

    return (pl.BlockSpec((1, block, width),
                         lambda *g: (g[0], q_block(*g), 0)),
            pl.BlockSpec((1, block, width),
                         lambda *g: (g[0], k_block(*g), 0)),
            pl.BlockSpec((block, LANES), lambda *g: (q_block(*g), 0)),
            pl.BlockSpec((SUBLANES, block), lambda *g: (0, k_block(*g))),
            pl.BlockSpec((1, block, LANES),
                         lambda *g: (g[0], q_block(*g), 0)))


def _call(kernel, name, kept, queries_inner, operands, in_specs, out_specs,
          out_shape, scratch, **kwargs):
    """One kernel over the grid ``(heads, outer block, inner block)``, handed
    its two tables ahead of it: whether a step runs, read at ``[query block,
    key block]`` whichever is outermost, and the inner block to hold."""
    blocks = kept.shape[0]
    walked = kept.T if queries_inner else kept  # fedtpu: noqa[FTP004] the caller's constant: which kernel this is
    tables = (kept.reshape(-1).astype(jnp.int32), _held(walked))
    return pl.pallas_call(
        kernel, name=name, out_shape=out_shape,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(operands[0].shape[0], blocks, blocks),
            in_specs=in_specs, out_specs=out_specs, scratch_shapes=scratch),
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "arbitrary")), **kwargs)(
        *tables, *operands)


def _ids(segs):
    """The segment ids as the kernels read them: a query's along the lanes,
    a key's along the sublanes (the library's two forms)."""
    t = segs.shape[0]
    return (lax.broadcast_in_dim(segs, (t, LANES), (0,)),
            lax.broadcast_in_dim(segs, (SUBLANES, t), (1,)))


def _bound(kernel, sm_scale, window):
    """The kernel with its constants; the window only where there is one, so
    that a call without is the call it always was."""
    if window is None:
        return functools.partial(kernel, sm_scale=sm_scale)
    return functools.partial(kernel, sm_scale=sm_scale, window=window)


def _forward(q, k, v, segs, kept, sm_scale, block, statistics: bool,
             window=None):
    """``(ctx, l, m)``; ``l`` and ``m (heads, T)``, the rows' sums and
    maxima, only where ``statistics`` (a backward pass follows)."""
    heads, t, width = q.shape
    blocks = t // block
    rows, keys, row_ids, key_ids, row_sums = _specs(block, width, blocks,
                                                    False)
    stat = jax.ShapeDtypeStruct((heads, t, LANES), jnp.float32)
    o, *lm = _call(
        _bound(_forward_kernel, sm_scale, window),
        "packed_attention_forward", kept, False, (q, k, v, *_ids(segs)),
        [rows, keys, keys, row_ids, key_ids],
        [rows] + [row_sums] * 2 * statistics,
        [jax.ShapeDtypeStruct(q.shape, q.dtype)] + [stat] * 2 * statistics,
        [pltpu.VMEM((block, LANES), jnp.float32)] * 2
        + [pltpu.VMEM((block, width), jnp.float32)],
        cost_estimate=pl.CostEstimate(
            flops=4 * heads * t * t * width, transcendentals=heads * t * t,
            bytes_accessed=4 * q.size * q.dtype.itemsize))
    return (o, *(a[..., 0] for a in lm))


def _backward(q, k, v, segs, kept, l, m, do, di, sm_scale, block,
              window=None):
    _, t, width = q.shape
    lanes = lambda a: jnp.broadcast_to(a[..., None], (*a.shape, LANES))
    operands = (q, k, v, *_ids(segs), lanes(l), lanes(m), do, lanes(di))

    def gradients(kernel, name, queries_inner, of):
        # one float32 sum in the chip's own memory for each of ``of``, which
        # are the outer operand's: its block changes once the inner walk ends
        rows, keys, row_ids, key_ids, row_sums = _specs(
            block, width, t // block, queries_inner)
        return _call(
            _bound(kernel, sm_scale, window), name, kept,
            queries_inner, operands,
            [rows, keys, keys, row_ids, key_ids, row_sums, row_sums, rows,
             row_sums], [keys if queries_inner else rows] * len(of),
            [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in of],
            [pltpu.VMEM((block, width), jnp.float32)] * len(of))

    dk, dv = gradients(_dkv_kernel, "packed_attention_backward_dkv", True,
                       (k, v))
    dq, = gradients(_dq_kernel, "packed_attention_backward_dq", False, (q,))
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _attention(q, k, v, segs, sm_scale, block, window=None):
    kept = pairs_kept(segs, block, window)
    return _forward(q, k, v, segs, kept, sm_scale, block, False, window)[0]


def _attention_fwd(q, k, v, segs, sm_scale, block, window=None):
    kept = pairs_kept(segs, block, window)
    o, l, m = _forward(q, k, v, segs, kept, sm_scale, block, True, window)
    return o, (q, k, v, segs, kept, o, l, m)


def _attention_bwd(sm_scale, block, window, residuals, do):
    q, k, v, segs, kept, o, l, m = residuals
    di = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)
    dq, dk, dv = _backward(q, k, v, segs, kept, l, m, do, di, sm_scale, block,
                           window)
    return dq, dk, dv, None


_attention.defvjp(_attention_fwd, _attention_bwd)


# Under ``jax.jit`` as the library's wrapper is: a model's layers of one
# shape share one trace and one lowering of each kernel, where every call
# site would bring its own (24 Mosaic payloads in the four-stream round for
# 4, and 7 to 9 s of its compile: PERF.md section 6, PR 38).
@functools.partial(jax.jit, static_argnames=("sm_scale", "block", "window"))
def attention(q, k, v, segs, sm_scale: float, block: int,
              window: int | None = None):
    """``ctx (heads, T, d)`` in the operands' dtype: causal attention within
    the segments of ``segs (T,)`` int32 (equal ids, padding's 0 among them),
    ``q``, ``k``, ``v`` ``(heads, T, d)`` with ``d`` whole lane tiles and
    ``T`` whole ``block``s of whole lanes; under a ``window`` a query sees
    only the ``window`` positions that end at its own; reverse mode only."""
    return _attention(q, k, v, segs, sm_scale, block, window)


# ----------------------------------------- the core and its two bodies
# Rows and columns of a tile of the fused attention kernel, forward and both
# backward kernels. Chosen on the chip (PERF.md section 6, PR 26), forward +
# backward of one (4096, 16, 128) sequence: the library's default 128s take
# 17.0 ms (the XLA body 16.3), 256s 7.1, 512s 3.8, 1024s 3.7 with 134 MB
# more temporaries; no mixed shape beat 512s.
ATTENTION_BLOCK = 512


def padded_head_width(q, v) -> int:
    """The head width the tiled kernel would run ``q (T, heads, dq)`` and
    ``v (T, heads, dv)`` at: the wider of the two, up to whole lane tiles.
    The kernel takes one width for q, k and v; zero columns of q and k add
    nothing to a score and zero columns of v give zero columns of the
    context, which are cut, so the padded form is exact."""
    return -(-max(q.shape[-1], v.shape[-1]) // 128) * 128


def fused_attention_applies(q, k, v) -> bool:
    """Whether the tiled kernel exists for these ``(T, heads, d)`` operands
    where the program is being built: a TPU, lane-wide heads, whole blocks
    and one head width for q, k and v (``attention_core`` pads a head whose
    query-key and value widths differ to one before it asks).

    The platform read is the PROCESS's default backend, not the one a
    program is lowered for: a compile for a described TPU from a CPU host
    gets the XLA body (``tests/test_aot_tpu_compile.py`` steers this rule
    for that reason), and a CPU mesh on a TPU host at these widths would
    get a kernel it cannot lower."""
    t, _, d = q.shape
    return (jax.default_backend() == "tpu" and q.shape == k.shape == v.shape
            and d % 128 == 0 and t % ATTENTION_BLOCK == 0)


def _xla_attention(q, k, v, segs, scale=None, window=None):
    t, _, d = q.shape
    scores = jnp.einsum("qhd,khd->hqk", q, k,
                        preferred_element_type=jnp.float32)
    scores = scores / (d ** 0.5) if scale is None else scores * scale
    idx = jnp.arange(t)
    # causal, and within one segment; padding (segment 0) sees padding,
    # which keeps its rows finite and is masked out of the loss
    allowed = (idx[:, None] >= idx[None, :]) & (segs[:, None] == segs[None, :])
    if window is not None:
        allowed = allowed & (idx[:, None] - idx[None, :] < window)
    probs = jax.nn.softmax(jnp.where(allowed[None], scores, -1e30), axis=-1)
    return jnp.einsum("hqk,khd->qhd", probs.astype(v.dtype), v,
                      preferred_element_type=jnp.float32)


def _fused_attention(q, k, v, segs, scale=None, window=None):
    # the kernels' layout is (heads, T, d); their mask is the XLA body's:
    # causal, and equal segment ids (padding's 0 among them)
    heads_first = lambda a: a.transpose(1, 0, 2)
    # the window only where there is one: a call without is the call it was
    under = {} if window is None else {"window": window}
    ctx = attention(
        heads_first(q), heads_first(k), heads_first(v), segs,
        q.shape[-1] ** -0.5 if scale is None else scale, ATTENTION_BLOCK,
        **under)
    return ctx.transpose(1, 0, 2).astype(jnp.float32)


def attention_blocks(segs, fused: bool, layers: int,
                     window: int | None = None) -> dict:
    """A sequence's two block counters: the (query block, key block) pairs
    the fused body's forward kernel runs on the row ``segs (T,)``, from the
    table it reads (a ``window``'s, where the layers have one), and the
    pairs on or under the diagonal, a head's worth
    for each of ``layers`` attention layers; both 0 where the XLA body ran
    (it has no blocks)."""
    if not fused:
        return {"attention_blocks_computed": jnp.float32(0.0),
                "attention_blocks_causal": jnp.float32(0.0)}
    kept = pairs_kept(segs, ATTENTION_BLOCK, window)
    blocks = kept.shape[0]
    return {"attention_blocks_computed":
            layers * kept.sum().astype(jnp.float32),
            "attention_blocks_causal":
            jnp.float32(layers * blocks * (blocks + 1) // 2)}


def attention_core(q, k, v, segs, compute_dtype, scale=None, window=None):
    """``ctx (T, heads, dv)`` float32: the attention of one packed sequence
    after RoPE and before the output projection, ``q``, ``k`` ``(T, heads,
    dq)`` and ``v (T, heads, dv)`` float32 and cast to ``compute_dtype`` for
    both matmuls; the scores are scaled by ``scale`` (``dq ** -0.5`` where
    none is given). A head whose two widths differ (latent attention: 192
    beside 128) reaches the tiled kernel padded with zeros to one width
    (``padded_head_width``) and its context is cut back: exact, at the
    padded width's cost. The XLA body takes the widths as they are. Under a
    ``window`` a query sees the ``window`` positions of its document that end
    at its own (``0 <= t - s < window``); without one, all before it."""
    q, k, v = (a.astype(compute_dtype) for a in (q, k, v))
    if scale is None and q.shape == v.shape:
        padded = q, k, v
    else:
        if scale is None:
            scale = q.shape[-1] ** -0.5
        wide = padded_head_width(q, v)
        padded = tuple(jnp.pad(a, ((0, 0), (0, 0), (0, wide - a.shape[-1])))
                       for a in (q, k, v))
    with jax.named_scope(ATTN_CORE):
        if not fused_attention_applies(*padded):
            return _xla_attention(q, k, v, segs, scale, window)
        ctx = _fused_attention(*padded, segs, scale, window)
        return ctx if padded[2] is v else ctx[..., :v.shape[-1]]
