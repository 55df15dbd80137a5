"""The expert matmuls over uneven groups: one function, two bodies.

``grouped_matmul(xs, w, sizes)`` multiplies the rows of each group by the
group's own matrix. Its XLA body, ``lax.ragged_dot``, is the definition,
and what the TPU's compiler makes of it runs at 27-35% of the MXU's peak
on the uneven groups a router leaves. Its Pallas body is the library's tiled
grouped matmul (``jax.experimental.pallas.ops.tpu.megablox``: ``gmm``
forward, ``gmm`` on the weight in place for the input's gradient, ``tgmm``
for the weight's) at tiles chosen on the chip, 52-61% of the peak: the same
bf16 operands and float32 sums, gradients rounded to bf16 once, where
autodiff rounded the XLA body's float32 ones. ``grouped_matmul_applies``
picks, and it is nobody's to set: the kernels on a TPU at bf16 operands,
whole row tiles and the widths that have tiles from a sweep on the chip (1024
and 2048; the hybrid stack's 2688 x 1856, the Xing4.0 stack's 3584 x 1024,
the Kimi-Linear stack's 2304 x 1024); ``lax.ragged_dot`` everywhere else. A
model's ``grouped_experts`` statistic says which ran. The dispatch of every
expert layer starts here too (``sorted_assignments``, ``gather_rows``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm, tgmm


# Tiles of the three grouped expert kernels, ``(tm, tk, tn)`` = rows,
# contracted width, output width of a tile. Chosen on the chip (PERF.md
# section 6, PR 29) on the benchmark's shapes, 32,768 assignment rows in 64
# groups as its own corpus and router give them (the fullest group 2,065 to
# 2,976 rows, 23 to 32 groups under 128), 20 timed calls each, ms a call
# beside ``lax.ragged_dot`` on the same operands (its kernel alone takes
# 1.98-2.64 in the round; the calls timed here also hold its cast and its
# transposed copy of the weight):
#   gmm, [32768,2048].[64,2048,1024]: XLA 2.15; (256, 2048, 1024) 1.22,
#     (128, 2048, 1024) 1.23, (256, 2048, 512) 1.33, (512, 2048, 512) 1.50,
#     (256, 1024, 1024) 1.63, (256, 512, 512) 2.28, (128, 512, 512) 3.00.
#   gmm, [32768,1024].[64,1024,2048]: XLA 2.25; (128, 1024, 2048) 1.27,
#     (256, 1024, 2048) 1.30, (256, 1024, 1024) 1.36, (512, 1024, 1024)
#     1.57, (256, 512, 2048) 1.79, (128, 512, 512) 3.00.
#   gmm on the weight in place (transpose_rhs), to [32768,2048]: XLA 4.09;
#     (128 or 256, 1024, 2048) 1.23, (256, 1024, 1024) 1.28, (512, 1024,
#     2048) 1.48, (256, 512, 2048) 1.56; to [32768,1024]: XLA 3.61;
#     (256, 2048, 1024) 1.19, (128, 2048, 1024) 1.21, (512, 2048, 512) 1.51.
#   tgmm, to [64,2048,1024] and [64,1024,2048]: XLA 3.86; (256, 1024, 1024)
#     1.46 / 1.47, (256, 2048, 512) 1.51, (256, 512, 1024) 1.70, (512, 1024,
#     1024) 1.70, (1024, 1024, 512) 2.44; a float32 result +0.2 to +0.4.
# So: 256 rows (a tile that straddles a group's edge runs once a group: 512
# rows cost 1.2-1.3x, and 128 are no better with the weight held); the two
# gmm's take the contracted width whole and as much of the output width as
# one weight tile of 2048 x 1024 holds, so a group's weight is fetched once;
# tgmm takes 1024 x 1024 of the weight's gradient at a time. Wider tiles do
# not fit the kernel's 16 MB of the chip's own memory.
GROUPED_ROW_TILE = 256
GROUPED_WIDTH_TILE = 1024
GROUPED_WEIGHT_TILE = 2048 * 1024
# The same three kernels at the hybrid stack's widths (8 held experts of
# 2,688 x 1,856, neither a whole number of the tiles above). Chosen on the
# chip (PERF.md section 6, PR 33): one 8,192-row buffer in 8 groups as the
# cell's own corpus and router fill it (twelve
# layer-steps: 1,644 to 3,944 rows filled, groups of 43 to 1,272, the rest
# of the buffer past the last group), 20 timed calls a filling, mean ms a
# call beside ``lax.ragged_dot`` on the same operands. A tile that is no
# whole divisor of its width is cut by the kernel (1,856 = 640 + 640 + 576;
# 2,688 = 3 x 896); 1,344 and 928 are no whole lanes and no tile:
#   gmm, [8192,2688].[8,2688,1856], float32 out: XLA 2.17; (128, 896, 1856)
#     0.555, (128, 2688, 640) 0.558, (128, 2688, 512) 0.577, (256, 896,
#     1856) 0.596, (128, 2688, 768) 0.597, (256, 2688, 640) 0.598, (256,
#     2688, 768) 0.646, (256, 896, 1024) 0.661, (256, 2688, 896) 0.696,
#     (128, 1280, 640) 0.751; 512 rows do not fit with the width whole.
#   gmm, [8192,1856].[8,1856,2688], float32 out: XLA 1.68; (128 or 256,
#     1856, 896) 0.356, (128, 1856, 1408) 0.356, (256, 1856, 1024) 0.383,
#     (256, 640, 2688) 0.384, (256, 1856, 640) 0.409, (512, 1856, 896)
#     0.420, (128, 640, 2688) 0.503.
#   gmm on the weight in place (transpose_rhs), bf16 out, to [8192,1856]: XLA
#     2.07; (256, 2688, 640) 0.331, (128, 2688, 640) 0.342, (256, 896, 1856)
#     0.350, (256, 2688, 768) 0.382, (256, 2688, 896) 0.432, (128, 896, 1856)
#     0.479; to [8192,2688]: XLA 2.65; (128, 1856, 896) 0.401, (128, 1856,
#     1408) 0.406, (256, 1856, 896) 0.437, (128, 640, 2688) 0.471, (256,
#     1856, 1024) 0.473, (512, 1856, 896) 0.536.
#   tgmm, to [8,2688,1856]: XLA 2.25; (128, 896, 1856) 0.442, (128, 384,
#     1856) 0.478, (256, 896, 1856) 0.479, (128, 2688, 384) 0.496, (128, 896,
#     1024) 0.517, (128, 896, 640) 0.542, (256, 1024, 1024) 0.604, (512, 896,
#     1856) 0.574; to [8,1856,2688]: XLA 2.82; (128, 640, 2688) 0.407, (128
#     or 256, 1856, 896) 0.408, (128, 1024, 1408) 0.442, (128, 640, 896)
#     0.468, (256, 1024, 1024) 0.503, (512, 640, 896) 0.550.
#   With the 1,856 padded to 1,920 = 15 x 128 (zero columns, exact): 0.323 /
#     0.318 / 0.301 / 0.303 / 0.356 / 0.358 at the best tile of each, 1.96
#     for the six against 2.51: not taken, a padded copy of both weights,
#     of the activations and a cut of both gradients for 0.55 ms.
# So: 128 rows (a group here is one to three tiles of 256, where OLMoE's are
# eight to twelve, and a tile that straddles a group's edge runs once a
# group); the two gmm's take the contracted width whole and a third of the
# output width, so a group's weight is fetched once; tgmm takes a third of
# 2,688 by the whole of 1,856. The formula above gives (256, 2688, 768),
# (256, 1856, 1024) and (256, 1024, 1024) here: 2.99 for the six against
# 2.51. Neither kernel visits a tile past the last group: the XLA body's
# 1.7-2.8 ms are mostly the buffer's empty rows.
# The same three kernels at the Xing4.0 stack's widths (8 held experts of
# 3,584 x 1,024, a block of 5,632 rows) and at the Kimi-Linear stack's
# (2,304 x 1,024, a block of 2,816 rows). Chosen on the chip (PERF.md section
# 6, PR 45) by PR 33's recipe but for the clock: the candidates that fit the
# kernel's 16 MB (264 of 306, compiled for a described v5e first), one block
# in 8 groups as each cell's own corpus and initial router fill it (twelve
# layer-steps: 634 to 3,077 rows filled in groups of 0 to 1,314; 320 to 999
# in groups of 3 to 402), beside ``lax.ragged_dot`` on the same operands. A
# call here is shorter than the host's dispatch of it: timed a call a dispatch
# (20 a filling), every candidate read 0.20-0.26 ms and the ranking was
# noise. So a ``lax.scan`` runs 120 calls a dispatch (ten times the twelve
# fillings, the sizes scanned, one element of each result kept), three
# dispatches timed, mean ms a call with the kernels' own group metadata:
#   gmm, [5632,3584].[8,3584,1024], float32 out: XLA 0.252; (256, 3584, 512)
#     0.157, (128, 3584, 512) 0.159, (256, 3584, 256) 0.173, (256, 896, 1024)
#     0.175, (256, 1792, 1024) 0.176, (128, 3584, 256) 0.178, (256, 1280,
#     1024) 0.181; best of 512 rows (512, 896, 1024) 0.224; worst (512, 1024,
#     512) 0.269 of 28.
#   gmm, [5632,1024].[8,1024,3584], float32 out: XLA 0.263; (256, 1024, 1792)
#     0.155, (128, 1024, 1792) 0.158, (256, 1024, 896) 0.161, (128, 1024,
#     896) 0.165, (256, 1024, 1280) 0.166, (128, 1024, 1280) 0.167, (256,
#     256, 3584) 0.167; best of 512 rows (512, 1024, 896) 0.221; worst (512,
#     512, 1024) 0.276 of 26.
#   gmm on the weight in place (transpose_rhs), bf16 out, [5632,1024] to
#     [5632,3584]: XLA 0.271; (256, 1024, 1792) 0.153, (128, 1024, 1792)
#     0.156, (256, 1024, 896) 0.159, (128, 1024, 896) 0.163, (256, 1024,
#     1280) 0.164, (256, 512, 3584) 0.165, (128, 1024, 1280) 0.165; best of
#     512 rows (512, 1024, 1792) 0.220; worst (512, 512, 1024) 0.274 of 28.
#   gmm on the weight in place (transpose_rhs), bf16 out, [5632,3584] to
#     [5632,1024]: XLA 0.308; (256, 3584, 512) 0.156, (128, 3584, 512) 0.158,
#     (256, 3584, 256) 0.168, (128, 3584, 256) 0.170, (256, 1792, 1024)
#     0.176, (256, 896, 1024) 0.176, (256, 1280, 1024) 0.182; best of 512
#     rows (512, 896, 1024) 0.223; worst (512, 1024, 512) 0.270 of 28.
#   tgmm, to [8,3584,1024]: XLA 0.383; (128, 1792, 1024) 0.147, (128, 896,
#     1024) 0.158, (128, 3584, 512) 0.163, (128, 1792, 512) 0.170, (128,
#     1280, 1024) 0.173, (128, 3584, 256) 0.176, (128, 512, 1024) 0.176; best
#     of 512 rows (512, 896, 1024) 0.253; worst (512, 1024, 512) 0.311 of 25.
#   tgmm, to [8,1024,3584]: XLA 0.365; (128, 1024, 1792) 0.158, (128, 256,
#     3584) 0.162, (128, 512, 1792) 0.169, (128, 1024, 896) 0.172, (128,
#     1024, 1280) 0.172, (256, 256, 3584) 0.187, (128, 1024, 512) 0.188; best
#     of 512 rows (512, 256, 3584) 0.256; worst (512, 512, 1024) 0.315 of 25.
#   gmm, [2816,2304].[8,2304,1024], float32 out: XLA 0.141; (128, 2304, 1024)
#     0.085, (256, 2304, 1024) 0.087, (128, 2304, 512) 0.088, (256, 2304,
#     512) 0.088, (256, 1152, 1024) 0.095, (256, 768, 1024) 0.097, (128,
#     2304, 256) 0.099; worst (256, 1024, 512) 0.141 of 18.
#   gmm, [2816,1024].[8,1024,2304], float32 out: XLA 0.160; (128, 1024, 2304)
#     0.084, (256, 1024, 2304) 0.085, (128, 1024, 1152) 0.087, (256, 1024,
#     1152) 0.087, (128, 1024, 768) 0.089, (256, 1024, 768) 0.090, (256, 512,
#     2304) 0.093; worst (256, 512, 1024) 0.129 of 18.
#   gmm on the weight in place (transpose_rhs), bf16 out, [2816,1024] to
#     [2816,2304]: XLA 0.128; (128, 1024, 2304) 0.085, (256, 1024, 2304)
#     0.086, (128, 1024, 1152) 0.087, (256, 1024, 1152) 0.087, (128, 1024,
#     768) 0.089, (256, 1024, 768) 0.090, (256, 512, 2304) 0.094; worst (256,
#     512, 1024) 0.129 of 18.
#   gmm on the weight in place (transpose_rhs), bf16 out, [2816,2304] to
#     [2816,1024]: XLA 0.122; (128, 2304, 1024) 0.085, (256, 2304, 1024)
#     0.087, (128, 2304, 512) 0.087, (256, 2304, 512) 0.088, (128, 2304, 256)
#     0.094, (256, 1152, 1024) 0.096, (256, 2304, 256) 0.098; worst (256,
#     1024, 512) 0.141 of 18.
#   tgmm, to [8,2304,1024]: XLA 0.183; (128, 1152, 1024) 0.080, (128, 768,
#     1024) 0.084, (128, 2304, 512) 0.090, (128, 1152, 512) 0.096, (128,
#     2304, 256) 0.098, (256, 1152, 1024) 0.102, (256, 768, 1024) 0.105;
#     worst (256, 1024, 512) 0.150 of 16.
#   tgmm, to [8,1024,2304]: XLA 0.184; (128, 512, 2304) 0.081, (128, 1024,
#     1152) 0.088, (128, 256, 2304) 0.089, (128, 1024, 768) 0.091, (128, 512,
#     1152) 0.097, (256, 512, 2304) 0.102, (128, 1024, 512) 0.107; worst
#     (256, 512, 1024) 0.151 of 16.
# So: both ``gmm`` take the contracted width whole, with half the output
# width at 3,584 x 1,024 (the whole of either weight does not fit the 16 MB)
# and the whole of it at 2,304 x 1,024, so a group's weight is fetched once: a
# call is bound by the eight weights' 59 or 38 MB (0.072 or 0.046 ms at the
# memory's peak), not by its 0.3 to 3 thousand rows; ``tgmm`` takes half a
# weight's gradient at a time; 256 rows in Xing4.0's ``gmm`` (by 1-2%: its
# groups reach 1,314 rows), 128 everywhere else (by 1-28%). In the round
# (the cells' traced runs, same section) the calls read 0.130-0.163 ms
# (``tgmm`` to [8,1024,3584] 0.213) and 0.048-0.088.
_MEASURED_TILES = {
    ("forward", 2688, 1856): (128, 2688, 640),
    ("forward", 1856, 2688): (128, 1856, 896),
    ("input_gradient", 2688, 1856): (128, 2688, 640),
    ("input_gradient", 1856, 2688): (128, 1856, 896),
    ("weight_gradient", 2688, 1856): (128, 896, 1856),
    ("weight_gradient", 1856, 2688): (128, 1856, 896),
    ("forward", 3584, 1024): (256, 3584, 512),
    ("forward", 1024, 3584): (256, 1024, 1792),
    ("input_gradient", 1024, 3584): (256, 1024, 1792),
    ("input_gradient", 3584, 1024): (256, 3584, 512),
    ("weight_gradient", 3584, 1024): (128, 1792, 1024),
    ("weight_gradient", 1024, 3584): (128, 1024, 1792),
    ("forward", 2304, 1024): (128, 2304, 1024),
    ("forward", 1024, 2304): (128, 1024, 2304),
    ("input_gradient", 1024, 2304): (128, 1024, 2304),
    ("input_gradient", 2304, 1024): (128, 2304, 1024),
    ("weight_gradient", 2304, 1024): (128, 1152, 1024),
    ("weight_gradient", 1024, 2304): (128, 512, 2304),
}


def _grouped_tiles(kernel, k, n):
    """``(tm, tk, tn)`` of one of the three grouped kernels for a contracted
    width ``k`` and an output width ``n`` (of ``tgmm``: the weight's two)."""
    measured = _MEASURED_TILES.get((kernel, k, n))
    if measured:
        return measured
    if kernel == "weight_gradient":
        return (GROUPED_ROW_TILE, min(k, GROUPED_WIDTH_TILE),
                min(n, GROUPED_WIDTH_TILE))
    return GROUPED_ROW_TILE, k, min(n, GROUPED_WEIGHT_TILE // k)


def grouped_matmul_applies(xs, w) -> bool:
    """Whether the tiled kernels exist for ``xs (rows, K)`` and ``w (groups,
    K, N)`` where the program is being built: a TPU (the PROCESS's backend,
    as ``packed_attention.fused_attention_applies`` reads it), the bf16
    operands the tiles were measured on (the kernel multiplies float32 operands in float32,
    several MXU passes where the XLA body takes one), whole row tiles, and
    a pair of widths that has tiles from a sweep on the chip: each width
    whole width tiles and, as the contracted width of a kernel, leaving a
    width tile's room in one weight tile (1024 or 2048), or the pair in
    ``_MEASURED_TILES`` (2688 and 1856, 3584 and 1024, 2304 and 1024). Any
    other width (1408, 4096), or a measured width beside one it was not
    measured with, runs ``lax.ragged_dot`` until it has a sweep of its own."""
    (rows, k), n = xs.shape, w.shape[2]
    return (jax.default_backend() == "tpu"
            and xs.dtype == w.dtype == jnp.bfloat16
            and rows % GROUPED_ROW_TILE == 0
            and (("forward", k, n) in _MEASURED_TILES
                 or all(width % GROUPED_WIDTH_TILE == 0
                        and width * GROUPED_WIDTH_TILE <= GROUPED_WEIGHT_TILE
                        for width in (k, n))))


def _xla_grouped_matmul(xs, w, sizes):
    return lax.ragged_dot(xs, w, group_sizes=sizes,
                          preferred_element_type=jnp.float32)


def _rows_of_groups(out, sizes):
    # the kernel visits no tile past the last group: what it left there is
    # not zero, as the definition's is, until it is made so
    rows = lax.broadcasted_iota(jnp.int32, (out.shape[0], 1), 0)
    return jnp.where(rows < sizes.sum(), out, 0)


@jax.custom_vjp
def _pallas_grouped_matmul(xs, w, sizes):
    """The library's grouped matmul (``megablox.gmm``) under a rule of its
    own, reverse mode only: the input's gradient is the same kernel reading
    the weight in place (``transpose_rhs``: no transposed copy of it
    exists), the weight's is ``tgmm``. The cotangent enters both in the
    operands' dtype (what the MXU made of the float32 one autodiff handed
    the XLA body), sums are float32 over the whole contracted width, and
    each gradient is rounded once to its primal's dtype."""
    k, n = w.shape[1:]
    return _rows_of_groups(gmm(
        xs, w, sizes, jnp.float32, _grouped_tiles("forward", k, n)), sizes)


def _pallas_grouped_matmul_fwd(xs, w, sizes):
    return _pallas_grouped_matmul(xs, w, sizes), (xs, w, sizes)


def _pallas_grouped_matmul_bwd(residuals, g):
    xs, w, sizes = residuals
    k, n = w.shape[1:]
    g = g.astype(xs.dtype)
    dxs = _rows_of_groups(gmm(
        g, w, sizes, xs.dtype, _grouped_tiles("input_gradient", n, k),
        transpose_rhs=True), sizes)
    # tgmm takes the activations contracted-axis last and swaps them back
    # itself: the two transposes meet under jit and no copy is made
    dw = tgmm(xs.swapaxes(0, 1), g, sizes, w.dtype,
              _grouped_tiles("weight_gradient", k, n))
    return dxs, dw, None


_pallas_grouped_matmul.defvjp(_pallas_grouped_matmul_fwd,
                              _pallas_grouped_matmul_bwd)


def grouped_matmul(xs, w, sizes):
    """``out (rows, N)`` float32: rows ``sizes[:g].sum()`` to
    ``sizes[:g + 1].sum()`` of ``xs (rows, K)`` times ``w[g] (K, N)``, for
    every group; rows past the last group are zero. ``lax.ragged_dot`` is
    the definition and the XLA body."""
    body = (_pallas_grouped_matmul if grouped_matmul_applies(xs, w)
            else _xla_grouped_matmul)
    return body(xs, w, sizes)


def sorted_assignments(groups, n_groups: int):
    """``(order, sizes)`` of the assignments ``groups (A,)`` int32, each the
    group (expert) one row goes to: ``order`` lists the assignments group by
    group, earlier ones first within a group, and ``sizes (n_groups,)`` are
    the groups' loads. The dispatch of every expert layer starts here."""
    order = jnp.argsort(groups, stable=True)
    sizes = jnp.bincount(groups, length=n_groups).astype(jnp.int32)
    return order, sizes


def gather_rows(x, order, per_token: int):
    """The tokens' rows in the order of their assignments: assignment ``a``
    of the token-major list belongs to token ``a // per_token``."""
    return jnp.take(x, order // per_token, axis=0)
