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
and 2048; the hybrid stack's 2688 x 1856); ``lax.ragged_dot`` everywhere
else. A model's ``grouped_experts`` statistic says which ran. The dispatch of
every expert layer starts here too (``sorted_assignments``, ``gather_rows``).
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
_MEASURED_TILES = {
    ("forward", 2688, 1856): (128, 2688, 640),
    ("forward", 1856, 2688): (128, 1856, 896),
    ("input_gradient", 2688, 1856): (128, 2688, 640),
    ("input_gradient", 1856, 2688): (128, 1856, 896),
    ("weight_gradient", 2688, 1856): (128, 896, 1856),
    ("weight_gradient", 1856, 2688): (128, 1856, 896),
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
    ``_MEASURED_TILES`` (2688 and 1856). Any other width (1408, 4096) runs
    ``lax.ragged_dot`` until it has a sweep of its own."""
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
