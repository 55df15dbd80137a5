"""The Mamba-1 (S6) selective scan of one packed row: its chunked form, and
two tiled kernels.

A channel ``d`` of the inner width carries ``N`` numbers of state, and every
one of the ``D x N`` decays its own way: with ``dl (T, D)`` the step after
its softplus and ``a (D, N)`` negative,

    h_t = exp(dl_t (x) a) * h_{t-1} + (dl_t * x_t) (x) b_t,    y_t = h_t c_t,

``h = 0`` at a document's first token (``run`` from ``ssm_passes.
document_runs``). Mamba-2's decay is one number a head, so a chunk of it is
matrix products (``models.nemotron_h.ssd_scan``); this one has no product
form, and the states of a whole row, ``(T, D, N)``, are 1.34 GB in float32 at
the published widths.

**The chunked form** (``chunked_selective_scan``), XLA's fusions all
through: the definition, the body on a CPU and at shapes without tiles, and
the oracle of the kernels' tests (``plain_scan``, token by token, is the
tests' definition of both). The row runs in chunks of ``CHUNK`` positions and
a state ``(N, D)`` is carried from chunk to chunk; only one chunk's states
ever exist, forward or backward. Inside a chunk (``_blocked``) the positions
are cut into sub-blocks of ``SERIAL``, which are walked side by side:
``SERIAL`` steps each over all sub-blocks at once give every position's state
from a zero start of its sub-block and the product of the decays since that
start; a short walk over the sub-blocks' ends gives the state each sub-block
enters with; a state is ``local + product * entering``. Passes over the
chunk's ``(positions, N, D)`` arrays, all elementwise: float32 throughout, no
exponential of a positive number, nothing divided. ``D`` lies on the lanes
and ``N`` on the sublanes. The backward pass has a rule of its own
(``jax.custom_vjp``, reverse mode only). The forward pass keeps the state
each chunk entered with (``(T / CHUNK, N, D)``) and its inputs; the backward
pass walks the chunks in reverse, recomputes a chunk's states from its
entering state, runs the cotangent's recurrence (the same walk on the chunk
reversed, the decays one position late) and takes the five gradients from the
two sets of states while they exist. ``chunked_scan_positions`` says whether
a row of ``t`` positions is cut at all.

**Which body runs where.** Where ``fused_scan_applies`` (a TPU, ``D`` whole
lane tiles, ``N`` whole sublane tiles, ``T`` whole blocks of the kernels'
positions) the same recurrence at the same precision runs as the two Mosaic
kernels below under a rule of their own (``fused_selective_scan``);
``selective_scan`` asks and calls them.

**The kernels.** The chunked form passes a chunk's state-sized arrays through
the memory four to five times forward and nine times backward; here a state
never leaves the chip's own memory. A grid step holds a block of
``SCAN_BLOCK`` positions of one tile of ``SCAN_TILE`` channels, read in place
out of the ``(T, D)`` arrays the convolution and the softplus leave; the grid
is (blocks of positions, channel tiles), the tiles' float32 states ``(tiles,
N, tile)`` in a scratch buffer across a row's blocks. Forward
(``_forward_kernel``): the step's dense passes first, whole registers at a
time (``dl * x``; the step with ``+inf`` at a document's first position, so
that its decay is ``2^-inf = 0``: a restart costs a select a register of
EIGHT positions, not a multiply a register of ONE; ``a log2 e``, the chip's
exponential being ``2^x``), then the positions in a ``lax.fori_loop`` of
eight a trip with the tile's state in registers (two sublane tiles of
``SCAN_TILE / 128`` lane registers, independent, which fill the four slots
of the vector unit), and a trip's eight outputs a lane register are summed
over ``N`` by seven folds of two registers into one (``_sum_sublanes``) and
written as one dense ``(8, 128)`` register. What a position needs comes in
forms the loop reads without a shuffle a register: a row of ``dl`` or ``dl *
x`` over the eight sublanes by a load that repeats one row (``_row``: the
rows lie in ``(lane tiles, positions, 128)`` buffers for it), ``b_t`` and
``c_t`` as ``(N, 128)`` registers with each number over the lanes, made once
a BLOCK of positions from ``B`` and ``C`` handed over positions-last ``(N,
T)`` (``_spread``: one lane broadcast a position and sublane tile, on the
otherwise idle permute units) and read by every channel tile and lane
register. Under the rule the kernel also writes the state each block entered
with, ``(T / SCAN_BLOCK, N, D)``, the only residual beside the inputs.

Backward (``_backward_kernel``), the blocks in reverse with the state's
cotangent ``g`` in the chip's memory across blocks: a block's states of one
channel tile are made again from its entering state into a scratch buffer,
each beside its decay (``(block, N, tile)`` twice), then the positions are
walked backwards, ``g_t = decay_{t+1} g_{t+1} + dy_t (x) c_t``, and the five
gradients taken while both exist: ``d_x`` and ``d_dl`` through the same folds
as dense registers; ``dA (N, tile)`` in registers over the block and in the
output over the blocks; ``d_b`` and ``d_c`` (sums over the channels) as
``(N, 128)`` partial sums a position that add up over the channel tiles in
the chip's memory and are summed over the lanes once a block by a product
with ones on the matrix unit (which nothing else here uses), the result
row-major. No state-sized array reaches HBM in either direction.

Precision is the definition's: float32 state, decays, products and sums
throughout; the exponential of a non-positive number; nothing divided; no
bfloat16. The order of the sums over ``N`` and over the channels differs
from the chunked form's, and ``exp(dl a)`` is taken as ``2^(dl (a log2 e))``
(the chunked form on the chip as ``2^((dl a) log2 e)``): one rounding
elsewhere in the exponent. Reverse mode only.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES, SUBLANES = 128, 8

# Positions whose states exist at one time, and the positions of a sub-block
# (walked one after another; ``CHUNK / SERIAL`` sub-blocks side by side). At
# the published widths a chunk's states are 21 MB an array. Swept on the chip
# at those widths, one row forward / forward + backward, ms (PERF.md section
# 6, PR 44): (256, 16) 5.1 / 35.6, (128, 16) 4.3 / 23.8, (64, 16) 4.6 / 13.7,
# (64, 8) 4.5 / 14.2, (32, 16) 6.1 / 13.8, (32, 8) 4.7 / 15.2, (16, 4) 5.2 /
# 18.5: the backward pass wants a chunk whose arrays stay near the chip's own
# memory, and under 32 positions the loop's trips cost more than they save.
CHUNK = 64
SERIAL = 16


def chunk_of(t: int, chunk: int = CHUNK) -> int:
    """The chunk a row of ``t`` positions runs in: ``chunk``, or the whole
    row where it is no whole number of them."""
    return chunk if t % chunk == 0 else t


def chunked_scan_positions(t: int, chunk: int = CHUNK) -> int:
    """The positions of a row of ``t`` whose states are never held beyond a
    chunk of ``chunk``: all of them, or none where the row is one longer
    chunk."""
    return t if chunk_of(t, chunk) <= chunk else 0


def _serial(chunk: int) -> int:
    return max(s for s in range(1, SERIAL + 1) if chunk % s == 0)


def _walk(x, serial: int):
    """``x (Q, ...)`` as ``(serial, Q / serial, ...)``: position ``i * serial
    + j`` at ``[j, i]``, a step of every sub-block side by side."""
    return x.reshape(-1, serial, *x.shape[1:]).swapaxes(0, 1)


def _unwalk(x):
    """``_walk``'s inverse: ``(Q, ...)``."""
    return x.swapaxes(0, 1).reshape(-1, *x.shape[2:])


def _blocked(dl, keep, u, w, a, h0):
    """The states of one chunk of ``h_t = exp(dl_t (x) a) keep_t h_{t-1} +
    u_t (x) w_t`` from ``h0 (N, D)``, every array as ``_walk`` lays it:
    ``dl``, ``u`` ``(serial, S, D)``, ``keep (serial, S)`` (0 where a
    document starts), ``w (serial, S, N)``, ``a (N, D)``. Returns ``(local,
    product, entering, last)``: the states from a zero start of each
    sub-block and the products of the decays since it, ``(serial, S, N,
    D)``, the state each sub-block enters with ``(S, N, D)`` and the chunk's
    last state; a position's state is ``local + product * entering``."""
    subs = dl.shape[1]

    def step(carry, xs):
        local, product = carry
        dl_j, keep_j, u_j, w_j = xs
        decay = jnp.exp(dl_j[:, None, :] * a) * keep_j[:, None, None]
        local = decay * local + u_j[:, None, :] * w_j[:, :, None]
        product = decay * product
        return (local, product), (local, product)

    start = jnp.zeros((subs, *a.shape), jnp.float32)
    (local_end, product_end), (local, product) = lax.scan(
        step, (start, jnp.ones_like(start)), (dl, keep, u, w))

    def across(h, ends):
        product_i, local_i = ends
        return product_i * h + local_i, h

    last, entering = lax.scan(across, h0, (product_end, local_end))
    return local, product, entering, last


def _keep(run):
    """1 where a position goes on with the state before it, 0 where its
    document starts; ``(T,)`` float32."""
    return jnp.concatenate([jnp.zeros((1,), jnp.float32),
                            (run[1:] == run[:-1]).astype(jnp.float32)])


def _forward(x, dl, a, b, c, run, chunk):
    """``(y (T, D), entered (T / chunk, N, D))``: the scan's output and the
    state each chunk entered with; ``a (N, D)``."""
    t = x.shape[0]
    chunk = chunk_of(t, chunk)
    walk = functools.partial(_walk, serial=_serial(chunk))
    chunks = lambda arr: arr.reshape(t // chunk, chunk, *arr.shape[1:])

    def one(h, xs):
        x_w, dl_w, keep_w, b_w, c_w = map(walk, xs)
        local, product, entering, last = _blocked(dl_w, keep_w, dl_w * x_w,
                                                  b_w, a, h)
        y = ((local + product * entering[None]) * c_w[..., None]).sum(axis=2)
        return last, (_unwalk(y), h)

    _, (y, entered) = lax.scan(
        one, jnp.zeros(a.shape, jnp.float32),
        tuple(map(chunks, (x, dl, _keep(run), b, c))))
    return y.reshape(x.shape), entered


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def chunked_selective_scan(x, dl, a, b, c, run, chunk: int = CHUNK):
    """``selective_scan`` in XLA's fusions, the definition: the row in
    chunks of ``chunk`` positions (as one where ``T`` is no whole number of
    them)."""
    return _forward(x, dl, a.T, b, c, run, chunk)[0]


def _selective_scan_fwd(x, dl, a, b, c, run, chunk):
    y, entered = _forward(x, dl, a.T, b, c, run, chunk)
    return y, (x, dl, a, b, c, run, entered)


def _selective_scan_bwd(chunk, residuals, dy):
    x, dl, a, b, c, run, entered = residuals
    a = a.T                                                 # (N, D)
    t = x.shape[0]
    chunk = chunk_of(t, chunk)
    serial = _serial(chunk)
    keep = _keep(run)
    late = lambda arr: jnp.concatenate([arr[1:], jnp.zeros_like(arr[:1])])
    chunks = lambda arr: arr.reshape(t // chunk, chunk, *arr.shape[1:])
    walk = functools.partial(_walk, serial=serial)

    def one(carry, xs):
        g_next, da = carry
        x_c, dl_c, keep_c, b_c, c_c, dy_c, dl_late, keep_late, h0 = xs
        x_w, dl_w, keep_w, b_w, c_w, dy_w = map(
            walk, (x_c, dl_c, keep_c, b_c, c_c, dy_c))
        # the chunk's states again, and the state before each position
        local, product, entering, _ = _blocked(dl_w, keep_w, dl_w * x_w, b_w,
                                               a, h0)
        h = local + product * entering[None]
        before = jnp.concatenate([entering[None], h[:-1]], axis=0)
        # the cotangent's recurrence: g_t = decay_{t+1} g_{t+1} + dy_t (x)
        # c_t, the same walk over the chunk reversed
        back = lambda arr: walk(arr[::-1])
        g_local, g_product, g_entering, g_first = _blocked(
            back(dl_late), back(keep_late), back(dy_c), back(c_c), a, g_next)
        g = (g_local + g_product * g_entering[None])[::-1, ::-1]
        decay = (jnp.exp(dl_w[:, :, None, :] * a)
                 * keep_w[:, :, None, None])
        through = g * before * decay        # d decay * decay
        over_n = (g * b_w[..., None]).sum(axis=2)           # (serial, S, D)
        d_dl = (through * a).sum(axis=2) + x_w * over_n
        d_x = dl_w * over_n
        d_b = (g * (dl_w * x_w)[:, :, None, :]).sum(axis=3)
        d_c = (h * dy_w[:, :, None, :]).sum(axis=3)
        da = da + (through * dl_w[:, :, None, :]).sum(axis=(0, 1))
        return (g_first, da), tuple(map(_unwalk, (d_x, d_dl, d_b, d_c)))

    zero = jnp.zeros(a.shape, jnp.float32)
    (_, da), grads = lax.scan(
        one, (zero, zero),
        tuple(map(chunks, (x, dl, keep, b, c, dy, late(dl), late(keep))))
        + (entered,), reverse=True)
    d_x, d_dl, d_b, d_c = (g.reshape(like.shape)
                           for g, like in zip(grads, (x, dl, b, c)))
    return d_x, d_dl, da.T, d_b, d_c, None


chunked_selective_scan.defvjp(_selective_scan_fwd, _selective_scan_bwd)


# ------------------------------------------------------------- the kernels
# Positions of a grid step and channels of its tile (``D`` on the lanes, so
# ``SCAN_TILE / 128`` lane registers a state's sublane tile). Swept on the
# chip at the published widths ``(4096, 5120, 16)``, twenty calls a dispatch,
# one row forward / forward under the rule + backward, ms, each with 0.3 ms
# of the sweep's own pass between two calls (PERF.md section 6, PR 46):
# (256, 512) 1.07 / 2.86, (256, 1024) 1.06 / 3.26 (its backward walk holds 32
# state registers of the chip's 64 and spills 543 a trip), (512, 1024) over
# the chip's own memory; in the round a call is 0.65 ms forward and 1.78
# backward. (A first form of these kernels, a lane register an operation,
# read (128, 256) 1.25 / 3.76, (256, 256) 1.17 / 3.64, (128, 512) 1.09 /
# 2.91, (256, 512) 1.08 / 2.91, (512, 512) 1.07 / 2.84, (256, 1024) 1.04 /
# 2.76: blocks under 256 and tiles under 512 pay for a step's prologue.)
SCAN_BLOCK, SCAN_TILE = 256, 512
# Positions walked between two trips of the kernels' loops: one dense (8,
# 128) register of outputs a lane register.
GROUP = SUBLANES
# The backward kernel keeps a block's states and decays of one channel tile
# (2 x 8.4 MB at 256 x 512) beside its blocks: over the 16 MiB a kernel is
# given unasked; the chip has 128.
VMEM_LIMIT = 96 * 1024 * 1024
_LOG2E = 1.4426950408889634
_HI = lax.Precision.HIGHEST


def scan_tiles(d: int) -> tuple:
    """``(block, tile)`` the kernels run rows of ``d`` channels in:
    ``SCAN_BLOCK`` and ``SCAN_TILE``, or the largest number of whole lane
    tiles under it that divides ``d``."""
    fits = [w for w in range(LANES, min(SCAN_TILE, d) + 1, LANES)
            if d % w == 0]
    return SCAN_BLOCK, (max(fits) if fits else SCAN_TILE)


def tiles_apply(t: int, d: int, n: int, block: int, tile: int) -> bool:
    """Whether the kernels exist for a row of ``t`` positions, ``d`` channels
    and ``n`` states in blocks of ``block`` positions and tiles of ``tile``
    channels: whole blocks of whole lane tiles of positions (``B`` and ``C``
    are read with the positions on the lanes), whole channel tiles of whole
    lane tiles, ``n`` whole sublane tiles that divide a lane tile (shapes
    only; the platform is the caller's to read)."""
    return (block > 0 and tile > 0 and t % block == 0 and block % LANES == 0
            and d % tile == 0 and tile % LANES == 0 and n % SUBLANES == 0
            and LANES % n == 0)


def _lane_tiles(tile: int):
    return [slice(at, at + LANES) for at in range(0, tile, LANES)]


def _sublane_tiles(n: int):
    return [slice(at, at + SUBLANES) for at in range(0, n, SUBLANES)]


def _row(ref, t):
    """Row ``t`` of a ``(lane tiles, positions, 128)`` buffer over the eight
    sublanes, ``(8, tile)``: a load a lane register that repeats one row, no
    shuffle."""
    return jnp.concatenate([
        jnp.broadcast_to(ref[l, pl.ds(t, 1), :], (SUBLANES, LANES))
        for l in range(ref.shape[0])], axis=1)


def _over_tiles(reg, width: int):
    """An ``(8, 128)`` register beside itself over ``width`` lanes."""
    return jnp.concatenate([reg] * (width // LANES), axis=1)


def _plus(total, term):
    """``total + term``, a sum that starts at nothing."""
    return term if total is None else total + term


def _fold(first, second, by: int, low):
    """Two arrays of sublane partial sums as one: where ``low`` the sums of
    ``first``'s sublanes ``by`` apart, elsewhere ``second``'s."""
    return jnp.where(low, first + pltpu.roll(first, SUBLANES - by, 0),
                     second + pltpu.roll(second, by, 0))


def _sum_sublanes(parts):
    """``(8, width)``: row ``j`` the sum over the sublanes of ``parts[j]``,
    eight ``(8, width)`` arrays; seven folds where eight sums would be
    twenty-four rotations and additions a lane register."""
    at = lax.broadcasted_iota(jnp.int32, parts[0].shape, 0)
    by = SUBLANES // 2
    while by:
        low = (at & by) == 0
        parts = [_fold(parts[j], parts[j + by], by, low) for j in range(by)]
        by //= 2
    return parts[0]


def _spread(src_ref, dst_ref):
    """``dst (positions, n, 128)``: ``src (n, positions)`` with each number
    over the lanes, so that a position's ``b_t`` or ``c_t`` is a register a
    sublane tile that meets every lane register of the state as it is: made
    once a block of positions, read by every channel tile."""
    n = src_ref.shape[0]

    def lanes(g, _):
        at = pl.multiple_of(g * LANES, LANES)
        cols = src_ref[:, pl.ds(at, LANES)]
        for j in range(LANES):
            dst_ref[at + j] = jnp.broadcast_to(cols[:, j:j + 1], (n, LANES))

    lax.fori_loop(0, src_ref.shape[1] // LANES, lanes, None)


def _rows_of(block, ref):
    """A row-major ``(positions, tile)`` block into a ``(lane tiles,
    positions, 128)`` buffer, where ``_row`` reads it."""
    for i in range(ref.shape[0]):
        ref[i] = block[:, i * LANES:(i + 1) * LANES]


def _block_inputs(x_ref, dl_ref, a_ref, keep_ref, a2_ref, u_ref, dlk_ref):
    """What a grid step makes of its blocks before it walks them, whole
    registers at a time: the decay's exponent by base 2 (``exp(dl a) =
    2^(dl a log2 e)``: the chip's exponential is that one), ``dl * x``, and
    the step with a restart in it: ``+inf`` where a document starts, so that
    the decay there is ``2^-inf = 0`` and nothing of the state before it is
    kept (``a`` is held under zero: ``-0`` would make a NaN of it). The two
    row-major blocks go into ``(lane tiles, positions, 128)`` buffers, whose
    rows load over the sublanes."""
    a2_ref[...] = jnp.minimum(a_ref[...] * _LOG2E, -1e-30)
    dl = dl_ref[...]
    _rows_of(dl * x_ref[...], u_ref)
    _rows_of(jnp.where(keep_ref[...] > 0, dl, jnp.inf), dlk_ref)


def _load_state(ref, i):
    """``ref[i] (n, tile)`` as a list of ``(8, tile)`` arrays, one a sublane
    tile of ``n``: ``tile / 128`` registers each, which a loop carries."""
    return tuple(ref[i, rows, :] for rows in _sublane_tiles(ref.shape[1]))


def _store_state(ref, i, state):
    for rows, value in zip(_sublane_tiles(ref.shape[1]), state):
        ref[i, rows, :] = value


def _forward_kernel(x_ref, dl_ref, a_ref, b_ref, c_ref, keep_ref, y_ref,
                    *rest):
    # under the rule the state each block entered with goes out too
    *entered_ref, h_ref, a2_ref, u_ref, dlk_ref, bs_ref, cs_ref = rest
    first, i = pl.program_id(0) == 0, pl.program_id(1)
    block, tile = x_ref.shape
    n = a_ref.shape[0]
    halves = _sublane_tiles(n)

    @pl.when(first)
    def _():
        h_ref[i] = jnp.zeros((n, tile), jnp.float32)

    @pl.when(i == 0)
    def _():
        _spread(b_ref, bs_ref)
        _spread(c_ref, cs_ref)

    for ref in entered_ref:
        ref[0] = h_ref[i]
    _block_inputs(x_ref, dl_ref, a_ref, keep_ref, a2_ref, u_ref, dlk_ref)

    def walk(g, h):
        h = list(h)
        t0 = pl.multiple_of(g * GROUP, GROUP)
        sums = [None] * GROUP
        for j in range(GROUP):
            t = t0 + j
            b_t, c_t = bs_ref[t], cs_ref[t]
            step, u = _row(dlk_ref, t), _row(u_ref, t)
            for k, rows in enumerate(halves):
                h[k] = (jnp.exp2(step * a2_ref[rows, :]) * h[k]
                        + u * _over_tiles(b_t[rows], tile))
                sums[j] = _plus(sums[j], h[k] * _over_tiles(c_t[rows], tile))
        y_ref[pl.ds(t0, GROUP), :] = _sum_sublanes(sums)
        return tuple(h)

    _store_state(h_ref, i, lax.fori_loop(
        0, block // GROUP, walk, _load_state(h_ref, i)))


def _lane_sums(part_ref, out_ref):
    """``out (rows / 128, 128)``: the sum over the lanes of each row of
    ``part (rows, 128)``, the rows' sums side by side on the lanes (the
    row-major ``(positions, n)`` the caller wants): a product with ones on
    the matrix unit, which nothing else here uses."""
    ones = jnp.ones((SUBLANES, LANES), jnp.float32)
    for k in range(part_ref.shape[0] // LANES):
        sums = lax.dot_general(
            ones, part_ref[k * LANES:(k + 1) * LANES, :],
            (((1,), (1,)), ((), ())), precision=_HI,
            preferred_element_type=jnp.float32)
        out_ref[k:k + 1, :] = sums[:1]


def _owed(part_ref, t, owed, n: int):
    """Adds position ``t``'s sums over a tile's lane registers, an ``(8,
    tile)`` array a sublane tile of ``N``, to the block's ``(positions * N,
    128)``."""
    for k, wide in enumerate(owed):
        at = pl.ds(pl.multiple_of(t * n + k * SUBLANES, SUBLANES), SUBLANES)
        parts = [wide[:, lanes] for lanes in _lane_tiles(wide.shape[1])]
        while len(parts) > 1:       # pairwise: no chain as long as the tile
            parts = [a + b for a, b in zip(parts[::2], parts[1::2])] + (
                parts[-1:] if len(parts) % 2 else [])
        part_ref[at, :] = part_ref[at, :] + parts[0]


def _backward_kernel(x_ref, dl_ref, a_ref, b_ref, c_ref, keep_ref,
                     entered_ref, dy_ref, dx_ref, ddl_ref, da_ref, db_ref,
                     dc_ref, g_ref, a2_ref, u_ref, dlk_ref, dlr_ref, dyr_ref,
                     bs_ref, cs_ref, hs_ref, es_ref, pb_ref, pc_ref):
    first, i = pl.program_id(0) == 0, pl.program_id(1)
    last = i == pl.num_programs(1) - 1
    block, tile = x_ref.shape
    n = a_ref.shape[0]
    halves = _sublane_tiles(n)

    @pl.when(first)
    def _():
        g_ref[i] = jnp.zeros((n, tile), jnp.float32)
        da_ref[i] = jnp.zeros((n, tile), jnp.float32)

    @pl.when(i == 0)
    def _():
        _spread(b_ref, bs_ref)
        _spread(c_ref, cs_ref)
        pb_ref[...] = jnp.zeros_like(pb_ref)
        pc_ref[...] = jnp.zeros_like(pc_ref)

    _block_inputs(x_ref, dl_ref, a_ref, keep_ref, a2_ref, u_ref, dlk_ref)
    _rows_of(dl_ref[...], dlr_ref)
    _rows_of(dy_ref[...], dyr_ref)

    # the block's states again, each beside its decay; ``hs[t + 1]`` is the
    # state after position ``t``, ``hs[0]`` the one the block entered with
    hs_ref[0] = entered_ref[0]

    def again(g, h):
        h = list(h)
        t0 = pl.multiple_of(g * GROUP, GROUP)
        for j in range(GROUP):
            t = t0 + j
            b_t = bs_ref[t]
            step, u, dy_t = _row(dlk_ref, t), _row(u_ref, t), _row(dyr_ref, t)
            owed_c = []
            for k, rows in enumerate(halves):
                decay = jnp.exp2(step * a2_ref[rows, :])
                h[k] = decay * h[k] + u * _over_tiles(b_t[rows], tile)
                es_ref[t, rows, :] = decay
                hs_ref[t + 1, rows, :] = h[k]
                # ``C``'s gradient here, where the stores bind and the
                # vector unit has slots to spare
                owed_c.append(h[k] * dy_t)
            _owed(pc_ref, t, owed_c, n)
        return tuple(h)

    lax.fori_loop(0, block // GROUP, again, _load_state(entered_ref, 0))

    # the positions backwards: ``g`` enters a position as ``decay_{t+1}
    # g_{t+1}``, the part of the state's cotangent that later positions owe
    def back(trip, carry):
        g, da = (list(part) for part in carry)
        t0 = pl.multiple_of((block // GROUP - 1 - trip) * GROUP, GROUP)
        to_u, to_dl = [None] * GROUP, [None] * GROUP
        for j in reversed(range(GROUP)):
            t = t0 + j
            b_t, c_t = bs_ref[t], cs_ref[t]
            dy_t, u, step = _row(dyr_ref, t), _row(u_ref, t), _row(dlr_ref, t)
            owed_b = []
            for k, rows in enumerate(halves):
                g_t = g[k] + dy_t * _over_tiles(c_t[rows], tile)
                owed_b.append(g_t * u)
                g[k] = g_t * es_ref[t, rows, :]
                through = g[k] * hs_ref[t, rows, :]
                da[k] = da[k] + through * step
                to_u[j] = _plus(to_u[j], g_t * _over_tiles(b_t[rows], tile))
                to_dl[j] = _plus(to_dl[j], through * a_ref[rows, :])
            _owed(pb_ref, t, owed_b, n)
        at = pl.ds(t0, GROUP)
        d_u = _sum_sublanes(to_u)
        dx_ref[at, :] = dl_ref[at, :] * d_u
        ddl_ref[at, :] = _sum_sublanes(to_dl) + x_ref[at, :] * d_u
        return tuple(g), tuple(da)

    g, da = lax.fori_loop(
        0, block // GROUP, back,
        (_load_state(g_ref, i), _load_state(da_ref, i)))
    _store_state(g_ref, i, g)
    _store_state(da_ref, i, da)

    @pl.when(last)
    def _():
        _lane_sums(pb_ref, db_ref)
        _lane_sums(pc_ref, dc_ref)


def _specs(t, d, n, block, tile, flip):
    """The grid (blocks of positions, channel tiles: a block's ``B``, ``C``
    and its sums over the channels stay while the tiles go by) and the block
    of each kind of operand; ``flip`` walks the blocks from the last to the
    first."""
    steps = t // block
    at = (lambda j: steps - 1 - j) if flip else (lambda j: j)
    return ((steps, d // tile),
            pl.BlockSpec((block, tile), lambda j, i: (at(j), i)),
            pl.BlockSpec((n, tile), lambda j, i: (0, i)),
            pl.BlockSpec((n, block), lambda j, i: (0, at(j))),
            pl.BlockSpec((block, 1), lambda j, i: (at(j), 0)),
            pl.BlockSpec((1, n, tile), lambda j, i: (at(j), 0, i)),
            pl.BlockSpec((block * n // LANES, LANES),
                         lambda j, i: (at(j), 0)))


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("arbitrary", "arbitrary"),
        vmem_limit_bytes=VMEM_LIMIT)


def _rows_buffer(block, tile):
    return pltpu.VMEM((tile // LANES, block, LANES), jnp.float32)


def _scan_forward(x, dl, a, b, c, keep, block, tile, entered):
    (t, d), n = x.shape, a.shape[0]
    grid, wide, states, marks, restart, entering, _ = _specs(
        t, d, n, block, tile, False)
    out, out_specs = [jax.ShapeDtypeStruct((t, d), jnp.float32)], [wide]
    if entered:
        out.append(jax.ShapeDtypeStruct((t // block, n, d), jnp.float32))
        out_specs.append(entering)
    return pl.pallas_call(
        _forward_kernel, grid=grid,
        in_specs=[wide, wide, states, marks, marks, restart],
        out_specs=out_specs, out_shape=out,
        scratch_shapes=[pltpu.VMEM((d // tile, n, tile), jnp.float32),
                        pltpu.VMEM((n, tile), jnp.float32),
                        _rows_buffer(block, tile), _rows_buffer(block, tile),
                        pltpu.VMEM((block, n, LANES), jnp.float32),
                        pltpu.VMEM((block, n, LANES), jnp.float32)],
        compiler_params=_params(), name="s6_scan_forward",
    )(x, dl, a, b, c, keep)


def _scan_backward(x, dl, a, b, c, keep, entered, dy, block, tile):
    (t, d), n = x.shape, a.shape[0]
    grid, wide, states, marks, restart, entering, sums = _specs(
        t, d, n, block, tile, True)
    like = lambda arr: jax.ShapeDtypeStruct(arr.shape, jnp.float32)
    whole = pl.BlockSpec((d // tile, n, tile), lambda j, i: (0, 0, 0))
    flat = jax.ShapeDtypeStruct((t * n // LANES, LANES), jnp.float32)
    rows = lambda: _rows_buffer(block, tile)
    return pl.pallas_call(
        _backward_kernel, grid=grid,
        in_specs=[wide, wide, states, marks, marks, restart, entering, wide],
        out_specs=[wide, wide, whole, sums, sums],
        out_shape=[like(x), like(dl),
                   jax.ShapeDtypeStruct((d // tile, n, tile), jnp.float32),
                   flat, flat],
        scratch_shapes=[pltpu.VMEM((d // tile, n, tile), jnp.float32),
                        pltpu.VMEM((n, tile), jnp.float32),
                        rows(), rows(), rows(), rows(),
                        pltpu.VMEM((block, n, LANES), jnp.float32),
                        pltpu.VMEM((block, n, LANES), jnp.float32),
                        pltpu.VMEM((block + 1, n, tile), jnp.float32),
                        pltpu.VMEM((block, n, tile), jnp.float32),
                        pltpu.VMEM((block * n, LANES), jnp.float32),
                        pltpu.VMEM((block * n, LANES), jnp.float32)],
        compiler_params=_params(), name="s6_scan_backward",
    )(x, dl, a, b, c, keep, entered, dy)


def _operands(x, dl, a, b, c, run):
    """The arrays as the kernels read them: ``A`` with the channels on the
    lanes, ``B`` and ``C`` with the positions on the lanes, the restarts a
    column."""
    return x, dl, a.T, b.T, c.T, _keep(run)[:, None]


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def fused_selective_scan(x, dl, a, b, c, run, block: int, tile: int):
    """``selective_scan`` in the kernels, a grid step ``block`` positions of
    ``tile`` channels. ``tiles_apply`` says at which shapes."""
    return _scan_forward(*_operands(x, dl, a, b, c, run), block, tile,
                         False)[0]


def _fused_scan_fwd(x, dl, a, b, c, run, block, tile):
    y, entered = _scan_forward(*_operands(x, dl, a, b, c, run), block, tile,
                               True)
    return y, (x, dl, a, b, c, run, entered)


def _fused_scan_bwd(block, tile, residuals, dy):
    x, dl, a, b, c, run, entered = residuals
    d_x, d_dl, d_a, d_b, d_c = _scan_backward(
        *_operands(x, dl, a, b, c, run), entered, dy, block, tile)
    # ``(tiles, N, tile)`` is ``A``'s transpose a tile at a time; the sums
    # over the channels come out row-major, eight positions a row of lanes
    return (d_x, d_dl, d_a.transpose(0, 2, 1).reshape(a.shape),
            d_b.reshape(b.shape), d_c.reshape(c.shape), None)


fused_selective_scan.defvjp(_fused_scan_fwd, _fused_scan_bwd)


# --------------------------------------------------------------- the rule
def fused_scan_applies(t: int, d: int, n: int) -> bool:
    """Whether the scan's kernels (``fused_selective_scan``: one forward, one
    backward, a channel tile's state in the chip's own memory across a row's
    blocks) exist for a row of ``t`` positions, ``d`` channels and ``n``
    states where the program is being built: a TPU (the PROCESS's backend,
    as ``packed_attention.fused_attention_applies`` reads it), ``d`` whole
    lane tiles, ``n`` whole sublane tiles, ``t`` whole blocks of the kernels'
    positions. The chunked form above is the definition and the body
    everywhere else."""
    return jax.default_backend() == "tpu" and tiles_apply(
        t, d, n, *scan_tiles(d))


def fused_scan_positions(t: int, d: int, n: int) -> int:
    """The positions of a row that run in the kernels: all of them, or none."""
    return t if fused_scan_applies(t, d, n) else 0


def selective_scan(x, dl, a, b, c, run, chunk: int = CHUNK):
    """``y (T, D)`` float32, ``y_t = h_t c_t`` of the recurrence at the head
    of this file. ``x``, ``dl (T, D)`` (the convolution's output and the step
    after its softplus), ``a (D, N)`` negative, ``b``, ``c (T, N)``, ``run
    (T,)`` the run ids of ``ssm_passes.document_runs``; all float32. Where
    ``fused_scan_applies`` the kernels run, named for their direction so that
    their ``op_name`` keeps it; elsewhere the row runs in chunks of ``chunk``
    positions (as one where ``T`` is no whole number of them)."""
    (t, d), n = x.shape, a.shape[1]
    if fused_scan_applies(t, d, n):
        return fused_selective_scan(x, dl, a, b, c, run, *scan_tiles(d))
    return chunked_selective_scan(x, dl, a, b, c, run, chunk)


def plain_scan(x, dl, a, b, c, run):
    """The definition, token by token over the whole row, for the tests:
    ``lax.scan`` over time, autodiff's to differentiate. Holds ``(T, D, N)``
    in its backward pass: not the program's."""
    def step(h, xs):
        x_t, dl_t, b_t, c_t, keep_t = xs
        h = (jnp.exp(dl_t[:, None] * a) * keep_t * h
             + (dl_t * x_t)[:, None] * b_t[None, :])
        return h, h @ c_t

    _, y = lax.scan(step, jnp.zeros(a.shape, jnp.float32),
                    (x, dl, b, c, _keep(run)))
    return y
