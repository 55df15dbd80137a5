"""The Mamba-1 (S6) selective scan of one packed row, a chunk at a time.

A channel ``d`` of the inner width carries ``N`` numbers of state, and every
one of the ``D x N`` decays its own way: with ``dl (T, D)`` the step after
its softplus and ``a (D, N)`` negative,

    h_t = exp(dl_t (x) a) * h_{t-1} + (dl_t * x_t) (x) b_t,    y_t = h_t c_t,

``h = 0`` at a document's first token (``run`` from ``ssm_passes.
document_runs``). Mamba-2's decay is one number a head, so a chunk of it is
matrix products (``models.nemotron_h.ssd_scan``); this one has no product
form, and the states of a whole row, ``(T, D, N)``, are 1.34 GB in float32 at
the published widths. So the row runs in chunks of ``CHUNK`` positions and a
state ``(N, D)`` is carried from chunk to chunk; only one chunk's states ever
exist, forward or backward.

Inside a chunk (``_blocked``) the positions are cut into sub-blocks of
``SERIAL``, which are walked side by side: ``SERIAL`` steps each over all
sub-blocks at once give every position's state from a zero start of its
sub-block and the product of the decays since that start; a short walk over
the sub-blocks' ends gives the state each sub-block enters with; a state is
``local + product * entering``. Passes over the chunk's ``(positions, N, D)``
arrays, all elementwise: float32 throughout, no exponential of a positive
number, nothing divided. ``D`` lies on the lanes and ``N`` on the sublanes.

The backward pass has a rule of its own (``jax.custom_vjp``, reverse mode
only). The forward pass keeps the state each chunk entered with (``(T /
CHUNK, N, D)``) and its inputs; the backward pass walks the chunks in
reverse, recomputes a chunk's states from its entering state, runs the
cotangent's recurrence (the same walk on the chunk reversed, the decays one
position late) and takes the five gradients from the two sets of states
while they exist. ``chunked_scan_positions`` says whether a row of ``t``
positions is cut at all. XLA's fusions all through: a Mosaic kernel for the
chunk would stand in ``_blocked``'s place.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

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
def selective_scan(x, dl, a, b, c, run, chunk: int = CHUNK):
    """``y (T, D)`` float32, ``y_t = h_t c_t`` of the recurrence at the head
    of this file. ``x``, ``dl (T, D)`` (the convolution's output and the step
    after its softplus), ``a (D, N)`` negative, ``b``, ``c (T, N)``, ``run
    (T,)`` the run ids of ``ssm_passes.document_runs``; all float32. The row
    runs in chunks of ``chunk`` positions (as one where ``T`` is no whole
    number of them)."""
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


selective_scan.defvjp(_selective_scan_fwd, _selective_scan_bwd)


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
