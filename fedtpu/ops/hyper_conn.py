"""The four-stream residual module (mHC, arXiv:2512.24880): its passes over
the streams as XLA's, and as tiled kernels, two a direction.

A token's state is ``n`` streams of ``C``, ``X (n, C)``. Around a sublayer
``F`` three maps are made from the token's own state: with ``x' =
flatten(X) / sqrt(mean(flatten(X)^2) + eps)`` (no gain), ``H_pre =
sigmoid(a_pre x' phi_pre + b_pre)`` (n), ``H_post = 2 sigmoid(a_post x'
phi_post + b_post)`` (n) and ``H_res = SK(a_res mat(x' phi_res) + b_res)``
(n x n), where ``SK`` exponentiates the clipped logits and normalises
columns, then rows, ``hc_sinkhorn_iters`` times (each sum plus ``hc_eps``),
which makes the matrix doubly stochastic. The sublayer reads ``u = H_pre X``,
computes ``y = F(RMSNorm(u))`` and the state becomes ``H_res X + H_post^T
y``. All of it float32. The streams lie ``(n, T, C)``, a stream a plane, and
the maps ``(n, T)`` / ``(n, n, T)``, positions on the lanes: a ``(T, 4, 4)``
array would fill a thirty-second of its tiles.

**Which body runs where.** ``hyper_mix``, ``hyper_read`` and ``hyper_write``,
at the end of this file, are the definition: XLA's passes (JAX differentiates
through the Sinkhorn loop), the body on a CPU and at shapes without tiles,
and the oracle of the kernels' tests. Where ``hyper_passes_apply`` (a TPU,
float32 streams, ``C`` whole lane tiles, ``T`` whole row tiles, a tile within
the chip's own memory at this ``n``) the same algebra at the same precision
runs as the Mosaic kernels below under differentiation rules of their own,
``mix_read`` and ``write``; the scale and bias, ``H_post``, the clip and the
Sinkhorn turns stay in XLA (``_hyper_maps``, both bodies' own).

**The kernels.** A module touches the ``(n, T, C)`` float32
streams for four things (the norm's mean square, the logits' product with
``phi``, the read ``u = H_pre X`` and the write ``H_res X + H_post^T y``) and
XLA runs each as fusions of its own, forward, recomputed and transposed, the
streams' three cotangents added as arrays. Here a grid step holds a tile of
positions with ALL ``n * C`` of a position's state, so a direction is two
passes:

* ``mix_read`` (A): the streams in once; out ``u (T, C)``, the normed raw
  logits ``z = flatten(X) phi / rms(X)`` (``(n (n + 2), T)``, positions on the
  lanes, as the definition lays them) and the streams themselves, untouched,
  for ``write`` to consume. The scale, the bias, ``H_post``, the clip, the
  exponential and the Sinkhorn turns stay in XLA (``_hyper_maps``); only
  ``H_pre``'s own ``sigmoid(scale z + bias)`` is made in the tile, because
  ``u`` needs it.
* ``write`` (B): streams, ``y`` and the maps in, the new streams out.
* ``write``'s transpose: the new streams' cotangent, the streams and ``y`` in;
  ``dy``, the part ``H_res^T dX'`` of the streams' cotangent and the maps'
  small gradients out.
* ``mix_read``'s transpose: the streams, ``du``, ``dz`` and that part in; the
  streams' WHOLE cotangent out, once, written over the part
  (``input_output_aliases``): the part reaches the rule as the cotangent of
  ``mix_read``'s third output, so the three uses' cotangents never meet in an
  XLA add. ``dphi`` is summed over the row tiles in the chip's own memory.

Float32 throughout, the products with ``phi`` (forward and both transposes)
at ``HIGHEST``, as the definition states (the forward one as that
precision's six bfloat16 products written out, ``_mix_read_kernel``); sums
over ``C`` and over positions are the only ones whose order differs from the
definition's. The streams,
``u`` and ``y`` are read in place as ``(n, rows, C)`` / ``(rows, C)`` blocks;
the maps, a few hundred KB a module, are laid ``(T, columns)`` for the
kernels (a position a row, its maps along the lanes, so that a map's value
scales a row of ``C``) by XLA on either side. Reverse mode only.

Inside a grid step the elementwise work walks the tile ``STRIP`` rows at a
time in a loop that is NOT unrolled: the body stays a few hundred
instructions whatever the tile (the round program holds 66 of these calls and
Mosaic's compile time grows with the unrolled body: 0.1-1.4 s a call here),
and a strip's values fit the registers' spill space. Only the products with
``phi`` see the whole tile.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fedtpu.ops.scopes import HC_SINKHORN, HYPER_CONN

LANES, STRIP = 128, 8
# Positions a grid step holds, for the cell's shapes (``n`` 4, ``T`` 4,096,
# ``C`` 3,584: a (4, ROWS, 3584) float32 block is ROWS * 57 KB). Swept on the
# chip, the kernels alone under ``jax.jit``, ms a call on the host's clock
# (PERF.md section 6, PR 41; ``mix_read`` forward with the compiler's own
# ``HIGHEST`` product still):
#
#   ROWS   mix_read  its transpose   write   its transpose   first call, s
#     32     0.741       1.337       0.863       1.303       0.6 1.2 0.2 0.3
#     64     0.726       1.352       0.867       1.305       0.7  .   .   .
#    128     0.721       1.334       0.856       1.298       2.0  .   .   .
#
# Alike to 2%: every kernel but ``mix_read`` forward is bound by the chip's
# memory at any of them (665-735 GB/s in the round's trace), that one was
# bound by its product (``_mix_read_kernel``), and 128 rows take three times
# the compile and 51 MB of the chip's own memory.
ROWS = 64
# What a kernel may take of the chip's own memory (of 128 MiB): the transposes
# hold 3 n + 2 rows of C a position, twice for the pipeline (26 MB at ROWS).
VMEM_LIMIT = 64 * 1024 * 1024
_HIGHEST = lax.Precision.HIGHEST


def columns(n: int) -> int:
    """Columns of the kernels' small ``(T, columns)`` arrays: the ``n (n +
    2)`` logits (or ``n`` of ``H_post`` and ``n * n`` of ``H_res``) and one
    more (``mix_read`` keeps the norm's ``1 / rms`` there), whole sublanes."""
    return -(-(n * (n + 2) + 1) // STRIP) * STRIP


def tiles_apply(n: int, t: int, c: int) -> bool:
    """Whether the tiled bodies exist for streams ``(n, t, c)``: whole row
    tiles, ``c`` whole lane tiles, and the widest kernel's blocks (``3 n + 2``
    rows of ``c`` a position, double-buffered) and ``phi`` with its gradient
    within the chip's own memory at this ``n`` (shapes only; the platform and
    the dtype are the caller's to read)."""
    rows = min(ROWS, t)
    blocks = 2 * (3 * n + 2) * rows * c * 4
    phi = 4 * n * columns(n) * c * 4
    return (t % rows == 0 and rows % STRIP == 0 and c % LANES == 0
            and blocks + phi <= VMEM_LIMIT * 3 // 4)


def _params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=VMEM_LIMIT)


def _strips(rows: int, body) -> None:
    """``body(rows r .. r + STRIP)`` over a tile's strips, in a real loop."""
    def step(s, carry):
        body(pl.ds(pl.multiple_of(s * STRIP, STRIP), STRIP))
        return carry
    lax.fori_loop(0, rows // STRIP, step, 0)


def _lane(width: int):
    return lax.broadcasted_iota(jnp.int32, (STRIP, width), 1)


def _row_sum(a):
    return jnp.sum(a, axis=-1, keepdims=True)


# ------------------------------------------------- A: norm, logits, read
def _three_parts(a):
    """A float32 array as three bfloat16 ones whose sum it is (to its last
    bit: 8 + 8 + 8 bits of mantissa): what a product at ``HIGHEST`` is made
    of on this chip."""
    hi = a.astype(jnp.bfloat16)
    rest = a - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    return hi, mid, (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)


def _mix_read_kernel(x_ref, parts_ref, sb_ref, u_ref, z_ref, *, n, logits,
                     eps):
    rows, c = u_ref.shape
    w = z_ref.shape[1]
    # flatten(X) phi over the whole tile, a stream at a time, at HIGHEST: the
    # six bfloat16 products that precision is made of (hi hi, hi mid, mid hi,
    # hi lo, lo hi, mid mid; float32 sums), in THREE passes of the matrix
    # unit, because ``parts_ref`` holds phi's three parts side by side (a
    # pass has 128 columns and the logits fill 32): the compiler's own
    # ``HIGHEST`` takes six, and bound this kernel (0.69 ms a call against
    # 0.47 without the product; 0.50 so, and 2.0e-5 from a float64 product
    # where its own reads 6.1e-5, on logits of 168: PERF.md section 6, PR 41)
    dot = functools.partial(
        lax.dot_general, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    hi = mid = lo = 0.0
    for i in range(n):
        x_hi, x_mid, x_lo = _three_parts(x_ref[i])
        hi += dot(x_hi, parts_ref[i])                   # phi's hi, mid, lo
        mid += dot(x_mid, parts_ref[i, 0:2 * w, :])     # phi's hi, mid
        lo += dot(x_lo, parts_ref[i, 0:w, :])           # phi's hi
    z_ref[...] = ((lo + mid[:, w:2 * w] + hi[:, 2 * w:3 * w])
                  + (mid[:, 0:w] + hi[:, w:2 * w])) + hi[:, 0:w]

    def strip(at):
        xs = [x_ref[i, at, :] for i in range(n)]
        inv = lax.rsqrt(sum(_row_sum(x * x) for x in xs) / (n * c) + eps)
        z = z_ref[at, :] * inv
        pre = jax.nn.sigmoid(z * sb_ref[0:1, :] + sb_ref[1:2, :])
        u_ref[at, :] = sum(pre[:, i:i + 1] * xs[i] for i in range(n))
        z_ref[at, :] = jnp.where(_lane(z.shape[1]) == logits, inv, z)

    _strips(rows, strip)


def _mix_read_backward_kernel(x_ref, du_ref, part_ref, z_ref, dz_ref, phi_ref,
                              sb_ref, dx_ref, dphi_ref, dpre_ref, draw_ref,
                              *, n, logits):
    rows, c = du_ref.shape

    def strip(at):
        lane = _lane(z_ref.shape[1])
        kept = z_ref[at, :]
        inv = kept[:, logits:logits + 1]
        z = jnp.where(lane < logits, kept, 0.0)
        scale = sb_ref[0:1, :]
        pre = jax.nn.sigmoid(z * scale + sb_ref[1:2, :])
        du = du_ref[at, :]
        xs = [x_ref[i, at, :] for i in range(n)]
        dpre = jnp.zeros_like(z)
        for i in range(n):
            dpre = jnp.where(lane == i, _row_sum(du * xs[i]), dpre)
        dpre = dpre * pre * (1.0 - pre)         # the logits' of H_pre
        dpre_ref[at, :] = dpre
        dz = jnp.where(lane < logits, dz_ref[at, :], 0.0) + dpre * scale
        draw_ref[at, :] = dz * inv
        # the norm's term: d(1 / rms) through the mean square
        coef = _row_sum(dz * z) * inv * inv * (-1.0 / (n * c))
        for i in range(n):
            dx_ref[i, at, :] = (part_ref[i, at, :] + pre[:, i:i + 1] * du
                                + coef * xs[i])

    _strips(rows, strip)

    # dphi stays in the chip's memory for the whole grid, written once
    @pl.when(pl.program_id(0) == 0)
    def _():
        dphi_ref[...] = jnp.zeros(dphi_ref.shape, jnp.float32)

    draw = draw_ref[...]
    for i in range(n):
        dx_ref[i] += lax.dot_general(
            draw, phi_ref[i], (((1,), (0,)), ((), ())), precision=_HIGHEST,
            preferred_element_type=jnp.float32)
        dphi_ref[i] += lax.dot_general(
            draw, x_ref[i], (((0,), (0,)), ((), ())), precision=_HIGHEST,
            preferred_element_type=jnp.float32)


def _streams_spec(n, rows, c):
    return pl.BlockSpec((n, rows, c), lambda r: (0, r, 0))


def _row_spec(rows, width):
    return pl.BlockSpec((rows, width), lambda r: (r, 0))


def _whole(*shape):
    return pl.BlockSpec(shape, lambda r: (0,) * len(shape))


def _mix_read_forward(x, phi, sb, eps):
    n, t, c = x.shape
    rows, width = min(ROWS, t), sb.shape[1]
    parts = jnp.concatenate(_three_parts(phi), axis=1)
    return pl.pallas_call(
        functools.partial(_mix_read_kernel, n=n, logits=n * (n + 2), eps=eps),
        grid=(t // rows,),
        in_specs=[_streams_spec(n, rows, c), _whole(n, 3 * width, c),
                  _whole(2, width)],
        out_specs=[_row_spec(rows, c), _row_spec(rows, width)],
        out_shape=[jax.ShapeDtypeStruct((t, c), jnp.float32),
                   jax.ShapeDtypeStruct((t, width), jnp.float32)],
        compiler_params=_params("parallel"),
        name="hyper_conn_mix_read_forward",
    )(x, parts, sb)


def _mix_read_backward(x, du, part, z, dz, phi, sb):
    n, t, c = x.shape
    rows, width = min(ROWS, t), sb.shape[1]
    streams, small = _streams_spec(n, rows, c), _row_spec(rows, width)
    return pl.pallas_call(
        functools.partial(_mix_read_backward_kernel, n=n,
                          logits=n * (n + 2)),
        grid=(t // rows,),
        in_specs=[streams, _row_spec(rows, c), streams, small, small,
                  _whole(n, width, c), _whole(2, width)],
        out_specs=[streams, _whole(n, width, c), small],
        out_shape=[jax.ShapeDtypeStruct((n, t, c), jnp.float32),
                   jax.ShapeDtypeStruct((n, width, c), jnp.float32),
                   jax.ShapeDtypeStruct((t, width), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((rows, width), jnp.float32)],
        input_output_aliases={2: 0},
        compiler_params=_params("arbitrary"),
        name="hyper_conn_mix_read_backward",
    )(x, du, part, z, dz, phi, sb)


def _by_stream(phi, n: int, width: int):
    """``phi (logits, n * C)`` as the kernels' ``(n, columns, C)``: a stream's
    rows together, zero rows past the logits."""
    logits = phi.shape[0]
    return jnp.pad(phi.reshape(logits, n, -1).transpose(1, 0, 2),
                   ((0, 0), (0, width - logits), (0, 0)))


def _columns_of(a, width: int):
    """``a (k, T)``, positions on the lanes, as ``(T, width)``."""
    return jnp.pad(a.T, ((0, 0), (0, width - a.shape[0])))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def mix_read(x, phi, scale, bias, eps: float):
    """``(u, z, x)`` of the streams ``x (n, T, C)`` float32: ``z (n (n + 2),
    T) = flatten(X) phi / sqrt(mean(flatten(X)^2) + eps)`` (``phi (n (n + 2),
    n * C)``, a row a logit, the product at ``HIGHEST``),
    ``u (T, C) = sum_i sigmoid(scale_i z_i + bias_i) x_i`` over the first ``n``
    rows of ``z`` (``scale``, ``bias (n,)``), and ``x`` itself: the copy
    ``write`` takes, so that its cotangent comes back to this rule alone."""
    return _mix_read_fwd(x, phi, scale, bias, eps)[0]


def _mix_read_fwd(x, phi, scale, bias, eps):
    n, logits = x.shape[0], phi.shape[0]
    width = columns(n)
    by_stream = _by_stream(phi, n, width)
    sb = jnp.pad(jnp.stack([scale, bias]), ((0, 0), (0, width - n)))
    u, z = _mix_read_forward(x, by_stream, sb, eps)
    return (u, z[:, :logits].T, x), (x, phi, sb, z)


def _mix_read_bwd(eps, residuals, cotangents):
    x, phi, sb, z = residuals
    du, dz, part = cotangents
    n, logits = x.shape[0], phi.shape[0]
    dx, dphi, dpre = _mix_read_backward(
        x, du, part, z, _columns_of(dz, z.shape[1]),
        _by_stream(phi, n, z.shape[1]), sb)
    dphi = dphi[:, :logits].transpose(1, 0, 2).reshape(phi.shape)
    dpre = dpre[:, :n]
    return (dx, dphi.astype(phi.dtype), (dpre * z[:, :n]).sum(axis=0),
            dpre.sum(axis=0))


mix_read.defvjp(_mix_read_fwd, _mix_read_bwd)


# ------------------------------------------------------------- B: write
def _write_kernel(x_ref, y_ref, m_ref, out_ref, *, n):
    def strip(at):
        m, y = m_ref[at, :], y_ref[at, :]
        xs = [x_ref[j, at, :] for j in range(n)]
        for i in range(n):
            at_i = n + i * n
            out_ref[i, at, :] = m[:, i:i + 1] * y + sum(
                m[:, at_i + j:at_i + j + 1] * xs[j] for j in range(n))

    _strips(y_ref.shape[0], strip)


def _write_backward_kernel(g_ref, x_ref, y_ref, m_ref, dy_ref, dx_ref, dm_ref,
                           *, n):
    def strip(at):
        m, y = m_ref[at, :], y_ref[at, :]
        lane = _lane(m.shape[1])
        gs = [g_ref[i, at, :] for i in range(n)]
        xs = [x_ref[j, at, :] for j in range(n)]
        dy_ref[at, :] = sum(m[:, i:i + 1] * gs[i] for i in range(n))
        dm = jnp.zeros_like(m)
        for i in range(n):
            dm = jnp.where(lane == i, _row_sum(gs[i] * y), dm)
            for j in range(n):
                dm = jnp.where(lane == n + i * n + j, _row_sum(gs[i] * xs[j]),
                               dm)
        dm_ref[at, :] = dm
        for j in range(n):
            dx_ref[j, at, :] = sum(
                m[:, n + i * n + j:n + i * n + j + 1] * gs[i]
                for i in range(n))

    _strips(y_ref.shape[0], strip)


def _maps_columns(post, res, width: int):
    """``H_post (n, T)`` and ``H_res (n, n, T)`` as ``(T, width)``: ``H_post``
    first, then ``H_res`` row-major."""
    n = post.shape[0]
    return _columns_of(jnp.concatenate([post, res.reshape(n * n, -1)]), width)


def _write_forward(x, y, maps):
    n, t, c = x.shape
    rows, width = min(ROWS, t), maps.shape[1]
    streams = _streams_spec(n, rows, c)
    return pl.pallas_call(
        functools.partial(_write_kernel, n=n),
        grid=(t // rows,),
        in_specs=[streams, _row_spec(rows, c), _row_spec(rows, width)],
        out_specs=streams,
        out_shape=jax.ShapeDtypeStruct((n, t, c), jnp.float32),
        compiler_params=_params("parallel"),
        name="hyper_conn_write_forward",
    )(x, y, maps)


def _write_backward(g, x, y, maps):
    n, t, c = x.shape
    rows, width = min(ROWS, t), maps.shape[1]
    streams, wide = _streams_spec(n, rows, c), _row_spec(rows, c)
    small = _row_spec(rows, width)
    return pl.pallas_call(
        functools.partial(_write_backward_kernel, n=n),
        grid=(t // rows,),
        in_specs=[streams, streams, wide, small],
        out_specs=[wide, streams, small],
        out_shape=[jax.ShapeDtypeStruct((t, c), jnp.float32),
                   jax.ShapeDtypeStruct((n, t, c), jnp.float32),
                   jax.ShapeDtypeStruct((t, width), jnp.float32)],
        compiler_params=_params("parallel"),
        name="hyper_conn_write_backward",
    )(g, x, y, maps)


@jax.custom_vjp
def write(x, y, post, res):
    """``H_res X + H_post^T y``, ``hyper_write`` the definition: ``x (n,
    T, C)`` and ``y (T, C)`` float32, ``post (n, T)``, ``res (n, n, T)``."""
    return _write_forward(x, y, _maps_columns(post, res, columns(x.shape[0])))


def _write_fwd(x, y, post, res):
    return write(x, y, post, res), (x, y, post, res)


def _write_bwd(residuals, g):
    x, y, post, res = residuals
    n = x.shape[0]
    dy, dx, dm = _write_backward(
        g, x, y, _maps_columns(post, res, columns(n)))
    dm = dm.T
    return dx, dy, dm[:n], dm[n:n + n * n].reshape(res.shape)


write.defvjp(_write_fwd, _write_bwd)


# --------------------------------------- the definitions, and the rule
def sinkhorn(logits, cfg):
    """``(n, n, T)`` logits to doubly stochastic matrices, a position a
    matrix: ``exp`` of the clipped logits, then columns and rows in turn,
    ``hc_sinkhorn_iters`` times: a loop of that many trips (its backward pass
    keeps the iterates, 256 KB each at 4,096 positions), because unrolled the
    twelve modules' forty passes each, forward, recomputed and backward, were
    a fifth of the round program's compile."""
    def turn(_, m):
        m = m / (m.sum(axis=0, keepdims=True) + cfg.hc_eps)
        return m / (m.sum(axis=1, keepdims=True) + cfg.hc_eps)

    with jax.named_scope(HC_SINKHORN):
        return lax.fori_loop(
            0, cfg.hc_sinkhorn_iters, turn,
            jnp.exp(jnp.clip(logits, cfg.mhc_h_res_clamp_min,
                             cfg.mhc_h_res_clamp_max)))


def _hyper_maps(z, module, cfg):
    """``(H_pre, H_post, H_res)`` from the normed raw logits ``z (n (n + 2),
    T)``: the scale and the bias, then the two sigmoids and the Sinkhorn
    turns (under ``hyper_conn`` and ``hc_sinkhorn``: the caller's scope)."""
    n = cfg.hc_mult
    scale = jnp.repeat(module["alpha"], np.array([n, n, n * n]),
                       total_repeat_length=n * (n + 2))
    logits = z * scale[:, None] + module["bias"][:, None]
    pre = jax.nn.sigmoid(logits[:n])
    post = 2.0 * jax.nn.sigmoid(logits[n:2 * n])
    res = sinkhorn(logits[2 * n:].reshape(n, n, -1), cfg)
    return pre, post, res


def hyper_mix(x, module, cfg):
    """The three maps of one residual module from the streams ``x (n, T,
    C)`` float32: ``(H_pre (n, T), H_post (n, T), H_res (n, n, T))``, where
    ``H_res[i, j]`` weighs stream ``j`` into stream ``i``."""
    n = x.shape[0]
    with jax.named_scope(HYPER_CONN):
        inv = lax.rsqrt(jnp.mean(x * x, axis=(0, 2)) + cfg.rms_norm_eps)
        phi = module["phi"].reshape(-1, n, x.shape[2])
        # flatten(X) phi, a stream at a time: positions come out on the lanes
        logits = sum(lax.dot_general(
            phi[:, i], x[i], (((1,), (1,)), ((), ())),
            precision=lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32) for i in range(n))
        return _hyper_maps(logits * inv, module, cfg)


def hyper_read(x, pre):
    """``u (T, C) = H_pre X``: what a sublayer reads of the streams."""
    with jax.named_scope(HYPER_CONN):
        return (pre[:, :, None] * x).sum(axis=0)


def hyper_write(x, y, post, res):
    """``H_res X + H_post^T y``: the streams after a sublayer gave ``y``.
    A broadcast product under a sum over the source streams, here and (by
    autodiff) in every gradient: the form the compiler keeps as ONE
    multiply-and-reduce pass a result. (Sixteen products written as a Python
    sum came out of the backward pass as sixteen ``(T, C)`` arrays a module,
    0.9 GB at the published widths; an ``einsum`` as bfloat16 convolutions.)"""
    with jax.named_scope(HYPER_CONN):
        return ((res[:, :, :, None] * x[None]).sum(axis=1)
                + post[:, :, None] * y[None])


def hyper_passes_apply(x) -> bool:
    """Whether the tiled bodies of a residual module's passes over the
    streams (``mix_read`` and ``write``, a differentiation rule each) exist
    for the streams ``x (n, T, C)`` where the program is being built: a TPU
    (the PROCESS's backend, as ``packed_attention.fused_attention_applies``
    reads it), float32 streams, ``C`` whole lane tiles, ``T`` whole row
    tiles, the tile within the chip's own memory at this ``n``.
    ``hyper_mix``, ``hyper_read`` and ``hyper_write`` are the definitions
    and the body everywhere else."""
    return (jax.default_backend() == "tpu" and x.dtype == jnp.float32
            and tiles_apply(*x.shape))
