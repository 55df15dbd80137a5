"""The Mamba-2 mixer's two float32 passes, each ONE row-tiled kernel a direction.

``causal_conv`` under a SiLU and ``gated_group_norm`` after the ``D`` skip,
at the end of this file, are the definitions: the CPU's path and tier-1's,
and autodiff's to differentiate. ``fused_passes_apply`` beside them says
where the tiled bodies below exist. Both passes are elementwise along the
width but for a window of ``taps`` rows (the convolution) and a mean over a
group of lanes (the norm), so a tile of rows of some columns is all a step
needs: every operand is read once and every result written once, forward
and backward, where XLA's
fusions of the definitions and of their transposes pass over the arrays three
to seven times (PERF.md section 6, PR 36). Float32 throughout; the sums over
rows that make the weights' gradients are the only sums whose order differs
from the definitions'.

Each pass has a differentiation rule of its own (``jax.custom_vjp``, reverse
mode only): the backward kernel recomputes what it needs from the pass's
inputs in the tile, so nothing but the inputs is kept, and adds up the
weights' gradients across the row tiles in its own memory.

A pass reads its wide operands IN PLACE: the convolution takes the whole
product of ``W_in`` and the first of the columns that are ``xBC``, the gate
takes that product (``z`` are its first columns) and the convolution's output
(``x`` are its first columns), and the index maps step over the rest, so no
slice of them is copied for a kernel to read.

And a pass meets the scan in the scan's own form. On a TPU ``ssd_scan``'s
arrays have a chunk's positions on the lanes, so a row-major ``x`` or ``y``
between a kernel and the scan is a transposing copy of 134 MB, three of them
a direction for ``x`` (PERF.md section 6, PR 36). The convolution therefore
writes ``x`` once more with the positions last (``(width, T)``: one copy of
XLA's from the scan's own form), and takes that copy's cotangent in the same
form; the gate takes the scan's ``y`` ``chunk_transposed``, a view of what
XLA holds, and hands ``dy`` back the same way. The tile is turned on the
chip, whose transpose unit is idle in these passes.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Rows of a float32 tile of the chip's registers: the convolution's window
# reaches ``taps - 1 <= HALO`` rows past a tile, and reads them as one block.
HALO = 8
LANES = 128
# (rows, columns) of a tile, for the cell's shapes (``T`` 8,192; ``xBC`` 6,144
# wide at column 4,096 of 10,304; the inner width 4,096 in 8 groups of 512).
# Swept on the chip (PERF.md section 6, PR 36): every kernel is bound by the
# chip's memory (637-713 GB/s in the cell's trace) and twice these tiles gain
# 0.01-0.02 ms a call, while Mosaic's compile time doubles with the tile (the
# body is unrolled over its registers) and the cell's round program holds 96
# of these calls; half these tiles cost the convolution 0.04-0.13 ms a call.
CONV_TILE = (512, 512)
GATE_TILE = (256, 512)
# What a kernel may take of the chip's own memory: the tiles above pass the
# default 16 MB with their double buffers and the values a step holds.
VMEM_LIMIT = 48 * 1024 * 1024


def tiles_apply(t: int, chunk: int, taps: int, first: int, conv_width: int,
                width: int, groups: int) -> bool:
    """Whether the tiled bodies exist for a sequence of ``t`` rows in chunks
    of ``chunk`` whose ``xBC`` are ``conv_width`` columns from column
    ``first`` on, under a window of ``taps`` rows, and whose inner ``width``
    is normed in ``groups``: whole row tiles, the convolution's of whole
    lanes (its rows are the lanes of ``x`` transposed) and the norm's of
    whole chunks, a window within one halo block, a chunk, every width and a
    group whole lane tiles (shapes only; the platform is the caller's to
    read)."""
    return (t % CONV_TILE[0] == 0 and t % GATE_TILE[0] == 0
            and CONV_TILE[0] % LANES == 0 and GATE_TILE[0] % chunk == 0
            and taps - 1 <= HALO and width % groups == 0
            and all(n % LANES == 0 for n in (
                chunk, first, conv_width, width, width // groups)))


def _row_tile(cap: int, t: int, unit: int = HALO) -> int:
    rows = min(cap, t)
    if t % rows or rows % HALO or rows % unit:
        raise ValueError(f"{t} rows are not whole tiles of {rows} rows of "
                         f"whole {unit}s")
    return rows


def _column_tile(cap: int, unit: int, *widths: int) -> int:
    """The widest tile of whole ``unit``s, ``cap`` at most (one ``unit`` at
    least), that divides every one of ``widths``."""
    whole = math.gcd(*widths)
    return max(c for c in range(unit, max(min(cap, whole), unit) + 1, unit)
               if whole % c == 0)


def _params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=VMEM_LIMIT)


def _dsilu(x, sig):
    """``d silu(x) / dx`` from ``sig = sigmoid(x)``."""
    return sig * (1.0 + x * (1.0 - sig))


def _eight(a):
    """``a (rows, C)`` summed over its rows down to eight partial sums a
    column: whole registers added, no shuffle; the caller adds the eight."""
    return a.reshape(a.shape[0] // HALO, HALO, -1).sum(axis=0)


def _add_to_sums(sums_ref, sums):
    """A row tile's ``sums`` into its column tile's place in ``sums_ref``,
    which stays in the chip's memory for the whole grid (rows outermost) and
    is written once, at the end."""
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(i == 0)
    def _():
        sums_ref[j] = sums

    @pl.when(i > 0)
    def _():
        sums_ref[j] = sums_ref[j] + sums


def _by_width(sums):
    """The kernels' ``(column tiles, k, 8, columns)`` sums as ``(k, width)``."""
    n_cols, k, _, cols = sums.shape
    return sums.sum(axis=2).transpose(1, 0, 2).reshape(k, n_cols * cols)


def chunk_transposed(a, chunk: int):
    """``a (T, C)`` as ``(T / chunk, C, chunk)``, positions of a chunk last:
    what ``ssd_scan`` keeps its operands as on a TPU (positions on the
    lanes), so XLA's to make, and a view where its layouts already agree."""
    t, c = a.shape
    return a.reshape(t // chunk, chunk, c).transpose(0, 2, 1)


def _from_chunks(at_ref):
    """A block ``(chunks, C, chunk)`` of a chunk-transposed array as the
    tile's ``(rows, C)``: one transpose a chunk, on the chip."""
    return jnp.concatenate([at_ref[c].T for c in range(at_ref.shape[0])],
                           axis=0)


def _to_chunks(at_ref, a):
    """The tile ``a (rows, C)`` into the block ``(chunks, C, chunk)``."""
    chunks, _, chunk = at_ref.shape
    for c in range(chunks):
        at_ref[c] = a[c * chunk:(c + 1) * chunk].T


# ------------------------------------------------------ convolution + SiLU
def _edge_bits(run, taps: int):
    """``(T, 1)`` int32 of bits: bit ``back - 1`` says the position ``back``
    rows EARLIER is of this row's run (the definition's ``same``), bit
    ``taps - 2 + back`` that the position ``back`` rows LATER is. Run ids
    start at 1, so the zeros padded in match nothing."""
    t = run.shape[0]
    bits = jnp.zeros((t,), jnp.int32)
    for back in range(1, taps):
        earlier = jnp.pad(run, (back, 0))[:t] == run
        later = jnp.pad(run, (0, back))[back:] == run
        bits = (bits | (earlier.astype(jnp.int32) << (back - 1))
                | (later.astype(jnp.int32) << (taps - 2 + back)))
    return bits[:, None]


def _taps_of(x, bits, w, taps: int):
    """``(conv(x), [x shifted ``back`` rows and cut to its run, back = 0..])``
    over the rows of ``x (rows, C)``: the rolled rows that wrap around are
    wrong in the first ``taps - 1`` rows, which are a halo's."""
    shifted = [x]
    out = x * w[taps - 1:taps]
    for back in range(1, taps):
        same = (bits & (1 << (back - 1))) != 0
        shifted.append(jnp.where(same, pltpu.roll(x, back, 0), 0.0))
        out = out + shifted[back] * w[taps - 1 - back:taps - back]
    return out, shifted


def _conv_forward_kernel(bits_ref, x_ref, before_ref, w_ref, b_ref, out_ref,
                         out_t_ref, *, taps, tiles_t):
    x = jnp.concatenate([before_ref[...], x_ref[...]], axis=0)
    bits = jnp.concatenate(
        [jnp.zeros((HALO, 1), jnp.int32), bits_ref[...]], axis=0)
    pre, _ = _taps_of(x, bits, w_ref[...].astype(jnp.float32), taps)
    pre = pre[HALO:] + b_ref[...].astype(jnp.float32)
    out = pre * jax.nn.sigmoid(pre)
    out_ref[...] = out

    @pl.when(pl.program_id(1) < tiles_t)
    def _():
        out_t_ref[...] = out.T


def _conv_backward_kernel(bits_ref, bits_after_ref, x_ref, before_ref,
                          after_ref, g_ref, g_after_ref, g_t_ref,
                          g_t_after_ref, w_ref, b_ref, dx_ref, sums_ref,
                          *, taps, tiles_t):
    rows = x_ref.shape[0]
    whole = rows + 2 * HALO
    tile = lambda a: a[HALO:HALO + rows]
    x = jnp.concatenate([before_ref[...], x_ref[...], after_ref[...]], axis=0)
    bits = jnp.concatenate([jnp.zeros((HALO, 1), jnp.int32), bits_ref[...],
                            bits_after_ref[...]], axis=0)
    # the first column tiles went out twice: their cotangent is the sum
    twice = pl.program_id(1) < tiles_t
    g = jnp.concatenate([
        jnp.zeros(g_after_ref.shape, jnp.float32),
        g_ref[...] + jnp.where(twice, g_t_ref[...].T, 0.0),
        g_after_ref[...] + jnp.where(twice, g_t_after_ref[...].T[:HALO], 0.0)],
        axis=0)
    w = w_ref[...].astype(jnp.float32)
    pre, shifted = _taps_of(x, bits, w, taps)
    pre = pre + b_ref[...].astype(jnp.float32)
    # the rows after the tile are the next tile's (past the last tile, rows
    # that no bit of this tile's lets in): their part of ``dx`` is taken here
    dpre = g * _dsilu(pre, jax.nn.sigmoid(pre))
    dx = dpre * w[taps - 1:taps]
    for back in range(1, taps):
        same = (bits & (1 << (taps - 2 + back))) != 0
        dx = dx + (jnp.where(same, pltpu.roll(dpre, whole - back, 0), 0.0)
                   * w[taps - 1 - back:taps - back])
    dx_ref[...] = tile(dx)
    _add_to_sums(sums_ref, jnp.stack(
        [_eight(tile(dpre * s)) for s in shifted] + [_eight(tile(dpre))]))


def _conv_blocks(t, first, width, width_t):
    cap_rows, cap_cols = CONV_TILE
    rows = _row_tile(cap_rows, t)
    # a tile starts at a whole number of tiles from column 0 (gcd(0, n) = n)
    # and the columns that go out transposed end at one; widths of no whole
    # lanes are the interpreter's alone
    whole = math.gcd(first, width, width_t)
    cols = _column_tile(cap_cols, LANES if whole % LANES == 0 else 1, whole)
    halos, last = rows // HALO, t // HALO - 1
    before = lambda i: jnp.maximum(i * halos - 1, 0)
    after = lambda i: jnp.minimum((i + 1) * halos, last)
    # the transposed tiles: past the last of them a step stays on it, and
    # neither reads nor writes it; the rows after a tile are the first of a
    # block of whole lanes there (of a halo block where the interpreter
    # runs tiles of fewer rows)
    tiles_t = width_t // cols
    lanes = LANES if rows % LANES == 0 else HALO
    of_t = lambda i, j: (jnp.minimum(j, tiles_t - 1), i)
    after_t = lambda i, j: (
        jnp.minimum(j, tiles_t - 1),
        jnp.minimum((i + 1) * (rows // lanes), t // lanes - 1))
    return (rows, cols, first // cols, before, after, tiles_t,
            pl.BlockSpec((cols, rows), of_t),
            pl.BlockSpec((cols, lanes), after_t))


def _conv_forward(src, w, b, run, first, width, width_t):
    t, taps = src.shape[0], w.shape[0]
    rows, cols, c0, before, _, tiles_t, tile_t, _ = _conv_blocks(
        t, first, width, width_t)
    return pl.pallas_call(
        functools.partial(_conv_forward_kernel, taps=taps, tiles_t=tiles_t),
        grid=(t // rows, width // cols),
        in_specs=[
            pl.BlockSpec((rows, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((rows, cols), lambda i, j: (i, c0 + j)),
            pl.BlockSpec((HALO, cols), lambda i, j: (before(i), c0 + j)),
            pl.BlockSpec((taps, cols), lambda i, j: (0, j)),
            pl.BlockSpec((1, cols), lambda i, j: (0, j))],
        out_specs=[pl.BlockSpec((rows, cols), lambda i, j: (i, j)), tile_t],
        out_shape=[jax.ShapeDtypeStruct((t, width), jnp.float32),
                   jax.ShapeDtypeStruct((width_t, t), jnp.float32)],
        compiler_params=_params("parallel", "arbitrary"),
        name="ssm_conv_forward",
    )(_edge_bits(run, taps), src, src, w, b[None])


def _conv_backward(src, w, b, run, g, g_t, first, width):
    t, taps = src.shape[0], w.shape[0]
    rows, cols, c0, before, after, tiles_t, tile_t, after_t = _conv_blocks(
        t, first, width, g_t.shape[0])
    bits = _edge_bits(run, taps)
    n_cols = width // cols
    dx, sums = pl.pallas_call(
        functools.partial(_conv_backward_kernel, taps=taps, tiles_t=tiles_t),
        grid=(t // rows, n_cols),
        in_specs=[
            pl.BlockSpec((rows, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((HALO, 1), lambda i, j: (after(i), 0)),
            pl.BlockSpec((rows, cols), lambda i, j: (i, c0 + j)),
            pl.BlockSpec((HALO, cols), lambda i, j: (before(i), c0 + j)),
            pl.BlockSpec((HALO, cols), lambda i, j: (after(i), c0 + j)),
            pl.BlockSpec((rows, cols), lambda i, j: (i, j)),
            pl.BlockSpec((HALO, cols), lambda i, j: (after(i), j)),
            tile_t, after_t,
            pl.BlockSpec((taps, cols), lambda i, j: (0, j)),
            pl.BlockSpec((1, cols), lambda i, j: (0, j))],
        out_specs=[
            pl.BlockSpec((rows, cols), lambda i, j: (i, j)),
            pl.BlockSpec((n_cols, taps + 1, HALO, cols),
                         lambda i, j: (0, 0, 0, 0))],
        out_shape=[
            jax.ShapeDtypeStruct((t, width), jnp.float32),
            jax.ShapeDtypeStruct((n_cols, taps + 1, HALO, cols), jnp.float32)],
        compiler_params=_params("arbitrary", "arbitrary"),
        name="ssm_conv_backward",
    )(bits, bits, src, src, src, g, g, g_t, g_t, w, b[None])
    sums = _by_width(sums)
    # ``shifted[back]`` weighs by ``w[taps - 1 - back]``
    return dx, sums[:taps][::-1], sums[taps]


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def conv_silu(src, w, b, run, first: int, width: int, width_t: int):
    """``(out, out[:, :width_t].T)``, ``out = silu(causal_conv(src[:,
    first:first + width], w, b, run))`` ``(T, width)`` float32, ``causal_conv``
    the definition; its first ``width_t`` columns (``x``) go out once more
    with the positions last, the form the scan takes them in. ``src (T, >=
    first + width)`` float32 is read in place,
    ``w (taps, width)``, ``b (width,)``, ``run (T,)`` the run ids from 1."""
    return tuple(_conv_forward(src, w, b, run, first, width, width_t))


def _conv_silu_fwd(src, w, b, run, first, width, width_t):
    return (conv_silu(src, w, b, run, first, width, width_t),
            (src, w, b, run))


def _conv_silu_bwd(first, width, width_t, residuals, cotangents):
    src, w, b, run = residuals
    dx, dw, db = _conv_backward(src, w, b, run, *cotangents, first, width)
    dsrc = jnp.pad(dx, ((0, 0), (first, src.shape[1] - first - width)))
    return dsrc, dw.astype(w.dtype), db.astype(b.dtype), None


conv_silu.defvjp(_conv_silu_fwd, _conv_silu_bwd)


# ------------------------------------------------ skip, gate, grouped norm
def _gate_forward_kernel(yt_ref, x_ref, z_ref, skip_ref, gain_ref, out_ref,
                         *, group, eps):
    y = _from_chunks(yt_ref)
    for at in range(0, out_ref.shape[1], group):
        part = slice(at, at + group)
        z = z_ref[:, part]
        u = (y[:, part] + skip_ref[:, part] * x_ref[:, part]) * (
            z * jax.nn.sigmoid(z))
        scale = jax.lax.rsqrt(jnp.mean(u * u, axis=-1, keepdims=True) + eps)
        out_ref[:, part] = (u * scale * gain_ref[:, part]).astype(out_ref.dtype)


def _gate_backward_kernel(g_ref, yt_ref, x_ref, z_ref, skip_ref, gain_ref,
                          dyt_ref, dx_ref, dz_ref, sums_ref, *, group, eps):
    y = _from_chunks(yt_ref)
    dy, dgain, dskip = [], [], []
    for at in range(0, dx_ref.shape[1], group):
        part = slice(at, at + group)
        x, z, g = x_ref[:, part], z_ref[:, part], g_ref[:, part]
        sig = jax.nn.sigmoid(z)
        gate = z * sig
        skip = skip_ref[:, part]
        v = y[:, part] + skip * x
        u = v * gate
        scale = jax.lax.rsqrt(jnp.mean(u * u, axis=-1, keepdims=True) + eps)
        normed = u * scale
        g = g.astype(jnp.float32)
        dn = g * gain_ref[:, part]
        du = scale * (dn - normed * jnp.mean(dn * normed, axis=-1,
                                             keepdims=True))
        dv = du * gate
        dy.append(dv)
        dx_ref[:, part] = dv * skip
        dz_ref[:, part] = du * v * _dsilu(z, sig)
        dgain.append(_eight(g * normed))
        dskip.append(_eight(dv * x))
    _to_chunks(dyt_ref, jnp.concatenate(dy, axis=1))
    _add_to_sums(sums_ref, jnp.stack([jnp.concatenate(dgain, axis=1),
                                      jnp.concatenate(dskip, axis=1)]))


def _gate_blocks(yt, groups):
    k, width, chunk = yt.shape
    group = width // groups
    rows = _row_tile(GATE_TILE[0], k * chunk, chunk)
    cols = _column_tile(GATE_TILE[1], group, width)
    return (k * chunk, width, rows, cols, group,
            pl.BlockSpec((rows, cols), lambda i, j: (i, j)),
            pl.BlockSpec((rows // chunk, cols, chunk), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, cols), lambda i, j: (0, j)))


def _gate_forward(yt, xs, zs, skip, gain, groups, eps, dtype):
    t, width, rows, cols, group, wide, wide_t, row = _gate_blocks(yt, groups)
    return pl.pallas_call(
        functools.partial(_gate_forward_kernel, group=group, eps=eps),
        grid=(t // rows, width // cols),
        in_specs=[wide_t, wide, wide, row, row],
        out_specs=wide,
        out_shape=jax.ShapeDtypeStruct((t, width), dtype),
        compiler_params=_params("parallel", "parallel"),
        name="ssm_gate_norm_forward",
    )(yt, xs, zs, skip[None], gain[None])


def _gate_backward(g, yt, xs, zs, skip, gain, groups, eps):
    t, width, rows, cols, group, wide, wide_t, row = _gate_blocks(yt, groups)
    n_cols = width // cols
    result = jax.ShapeDtypeStruct((t, width), jnp.float32)
    dyt, dx, dz, sums = pl.pallas_call(
        functools.partial(_gate_backward_kernel, group=group, eps=eps),
        grid=(t // rows, n_cols),
        in_specs=[wide, wide_t, wide, wide, row, row],
        out_specs=[wide_t, wide, wide,
                   pl.BlockSpec((n_cols, 2, HALO, cols),
                                lambda i, j: (0, 0, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(yt.shape, jnp.float32), result, result,
                   jax.ShapeDtypeStruct((n_cols, 2, HALO, cols), jnp.float32)],
        compiler_params=_params("arbitrary", "arbitrary"),
        name="ssm_gate_norm_backward",
    )(g, yt, xs, zs, skip[None], gain[None])
    dgain, dskip = _by_width(sums)
    return dyt, dx, dz, dgain, dskip


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def skip_gate_norm(yt, xs, zs, skip, gain, groups: int, eps, dtype):
    """``gated_group_norm(y + skip * x, z, gain, groups, eps)`` in ``dtype``,
    ``(T, width)``, ``gated_group_norm`` the definition: ``yt (T / chunk,
    width, chunk)`` float32 is ``chunk_transposed(y)``, the form
    the scan leaves its result in; ``x`` and ``z`` the first ``width``
    columns of ``xs`` and of ``zs`` (float32, read in place); ``skip
    (heads,)`` a head's ``D``, a head ``width / heads`` columns; ``gain
    (width,)``. Float32 until the result is rounded to ``dtype``, once."""
    return _gate_forward(yt, xs, zs, _by_column(skip, yt.shape[1]), gain,
                         groups, eps, dtype)


def _by_column(skip, width):
    return jnp.repeat(skip.astype(jnp.float32), width // skip.shape[0])


def _skip_gate_norm_fwd(yt, xs, zs, skip, gain, groups, eps, dtype):
    out = skip_gate_norm(yt, xs, zs, skip, gain, groups, eps, dtype)
    return out, (yt, xs, zs, skip, gain)


def _skip_gate_norm_bwd(groups, eps, dtype, residuals, g):
    yt, xs, zs, skip, gain = residuals
    width = yt.shape[1]
    dyt, dx, dz, dgain, dskip = _gate_backward(
        g, yt, xs, zs, _by_column(skip, width), gain, groups, eps)
    past = lambda a: ((0, 0), (0, a.shape[1] - width))
    return (dyt, jnp.pad(dx, past(xs)), jnp.pad(dz, past(zs)),
            dskip.reshape(skip.shape[0], -1).sum(axis=1).astype(skip.dtype),
            dgain.astype(gain.dtype))


skip_gate_norm.defvjp(_skip_gate_norm_fwd, _skip_gate_norm_bwd)


# --------------------------------------- the definitions, and the rule
def document_runs(segs):
    """``(run (T,) int32, starts (T,) bool)``: the index of the run of equal
    segment ids each position lies in (1, 2, ...), and where a run starts.
    The state-space layer restarts at every start, padding's run included."""
    starts = jnp.concatenate([jnp.ones((1,), bool), segs[1:] != segs[:-1]])
    return jnp.cumsum(starts.astype(jnp.int32)), starts


def causal_conv(x, w, b, run):
    """Depthwise causal convolution of ``x (T, C)`` with ``w (K, C)``, ``w[j]``
    weighing the position ``K - 1 - j`` back, over the positions of the same
    run only: a document's first tokens see zeros before them."""
    taps, t = w.shape[0], x.shape[0]
    out = x * w[taps - 1] + b
    for back in range(1, taps):
        earlier = jnp.pad(x, ((back, 0), (0, 0)))[:t]
        same = jnp.pad(run, (back, 0))[:t] == run       # run ids start at 1
        out = out + jnp.where(same[:, None], earlier, 0.0) * w[taps - 1 - back]
    return out


def gated_group_norm(y, z, gain, groups: int, eps):
    """``gain * RMSNorm(y * silu(z))``, the norm over each of ``groups``
    equal parts of the last axis; float32."""
    y = y * jax.nn.silu(z)
    parts = y.reshape(y.shape[0], groups, -1)
    parts = parts * lax.rsqrt(jnp.mean(parts * parts, axis=-1, keepdims=True)
                              + eps)
    return parts.reshape(y.shape) * gain


def fused_passes_apply(cfg, t: int) -> bool:
    """Whether the tiled bodies of the mixer's two float32 passes
    (``conv_silu``: the convolution under its SiLU; ``skip_gate_norm``: the
    skip, the gate and the grouped norm) exist for a sequence of ``t``
    positions where the program is being built: a TPU (the PROCESS's backend,
    as ``packed_attention.fused_attention_applies`` reads it), ``t`` whole row
    tiles, and the inner width, ``xBC``'s width and a group of the norm whole
    lane tiles. ``causal_conv`` and ``gated_group_norm`` are the definitions and
    the body everywhere else."""
    width = cfg.mamba_num_heads * cfg.mamba_head_dim
    return jax.default_backend() == "tpu" and tiles_apply(
        t, min(cfg.chunk_size, t), cfg.conv_kernel, width,
        width + 2 * cfg.n_groups * cfg.ssm_state_size, width, cfg.n_groups)


def fused_conv_applies(t: int, taps: int, width: int) -> bool:
    """Whether ``conv_silu`` alone exists for a sequence of ``t`` positions
    whose convolution is over the first ``width`` columns of its operand,
    where the program is being built (a mixer with no grouped norm, the
    Mamba-1 one: the same rule as ``fused_passes_apply`` without the gate's
    part): a TPU, whole row tiles, a window within a halo, whole lane tiles.
    ``causal_conv`` under a SiLU is the body everywhere else."""
    return (jax.default_backend() == "tpu" and t % CONV_TILE[0] == 0
            and CONV_TILE[0] % LANES == 0 and taps - 1 <= HALO
            and width % LANES == 0)
