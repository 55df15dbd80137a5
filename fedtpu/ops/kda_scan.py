"""The KDA delta-rule recurrence: its chunked form, and two tiled kernels.

**The chunked form** (``kda_scan``, at the end of this file), this repo's
own: the definition, XLA's passes over ``(chunks, heads, C, d)`` arrays, the
body on a CPU and at shapes without tiles, and the oracle of the kernels'
tests. With ``G_r`` the cumulative log-decay inside a chunk and ``S_0`` the
state entering it, the corrections ``u_r = beta_r (v_r - (Diag(exp g_r)
S_{r-1})^T k_r)`` solve ``(I + Diag(beta) A) U = Diag(beta) (V - K~ S_0)``,
``A_ri = sum_c k_rc k_ic exp(G_rc - G_ic)`` for ``i < r``, ``K~_r = exp(G_r)
k_r``: one inverse of a unit lower triangular matrix a chunk and head
(``unit_lower_inverse``, by forward substitution). Then ``O = Q~ S_0 + B U``
with ``B_ri`` the same sum with ``q_r`` for ``i <= r``, and ``S_C = Diag(exp
G_C) S_0 + K^^T U``, ``K^_i = exp(G_C - G_i) k_i``, carried from chunk to
chunk by a ``lax.scan``. **No exponential of a positive number is ever
taken**: ``exp(-G)`` overflows float32 inside a chunk of 64 at the decays the
model starts with (a token's ``g`` reaches -1.6), so ``A`` and ``B`` are made
of sub-chunks of ``KDA_SUB`` positions: a diagonal block pairwise (``exp(G_r
- G_i)`` per pair and channel, masked before the exponential), a block below
the diagonal as a product of two factors taken from the row block's first
position, ``exp(G_r - G_ref)`` and ``exp(G_ref - G_i)``, both at most 1
because ``g <= 0``. A document's first token may fall anywhere: pairs across
two documents are masked out of ``A`` and ``B``, only the positions of the
entering document read ``S_0``, only the chunk's last document reaches
``S_C``. The state, the decays, the norms and the triangular inverse
(``HIGHEST`` precision) are float32; the chunk's large products take
``compute_dtype`` inputs and sum in float32. Autodiff differentiates all of
it but the inverse, which has the rule ``-T^T dT T^T``.

**Which body runs where.** Where ``fused_scan_applies`` (a TPU, keys and
values of whole lane tiles, whole chunks of whole sub-chunks) the same
algebra at the same precision runs as the two Mosaic kernels below under a
rule of their own (``fused_kda_scan``); ``kda_scan`` asks and calls them.

**The kernels.** The definition's chunked form is a dozen XLA passes over
``(chunks, heads, 64, 128)`` float32 arrays with heads-first transposes between them; here a
grid step holds a block of whole chunks of ONE head, read in place out of the
``(T, heads * d)`` arrays the convolutions leave (column block ``h``), walks
its chunks in order with the head's state in the chip's own memory, and
writes ``o`` row-major. Nothing of a chunk but ``o`` (and, for the backward
pass, the state that entered it) goes back to HBM.

A chunk, forward (``_forward_chunk``): the cumulative log-decay by a product
with a triangle of ones; the decay-weighted scores ``A`` (keys with earlier
keys) and ``B`` (queries with keys): blocks of ``PAIRWISE`` positions on the
diagonal pair by pair, one distance ``r - i`` a step over the whole chunk (a
sublane roll, ``exp(G_r - G_i)`` masked BEFORE the exponential, a sum over
the lanes), everything below them as products of two factors taken either
side of the position between two blocks, level by level (blocks of 8 against
the 8 before them, of 16 against the 16 before them, ...): every exponent is
at most 0, as in the definition, **no exponential of a positive number**, and
inside a sub-chunk of ``sub`` positions every factor and product is float32
at ``HIGHEST`` (a level below ``sub`` is the same numbers as the definition's
pair by pair, at a fifth of a step's cost), beyond it ``compute_dtype``, as
the definition's. ``T = (I + Diag(beta) A)^-1`` by forward substitution: the
diagonal blocks a column a step, all at once, then ``T - T M T`` a level.
Then ``u``, ``o`` and the next state. Documents restart anywhere: what a
position may read comes from ONE small operand, ``positions_back`` (how many
positions before it lie in its own run), broadcast over the lanes once a
chunk: a mask or a scale made from one lane costs a shuffle a register
every time it meets a full one, and the first form of these kernels spent a
fifth of its schedule on them.

Backward (``_backward_chunk``), the chunks in reverse with the state's
cotangent in the chip's memory: a chunk's scores, ``T`` and ``u`` are
recomputed from its inputs and the state that entered it (the forward pass
under the rule writes those: ``(chunks, heads, d_v, d_k)`` float32, the only
residual beside the inputs), then every product's transpose; the inverse's
cotangent is ``-T^T dT T^T``; the two factors' common reference carries no
gradient (its two parts cancel).

Precision is the definition's: float32 state (kept transposed, ``(d_v,
d_k)``, so that its decay is a row), decays, cumulative sums and inverse
(``HIGHEST``); ``compute_dtype`` inputs to the large products only, float32
sums. Reverse mode only.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES, SUBLANES = 128, 8
# Positions of a chunk of the recurrence, and of a sub-chunk of the decay-
# weighted scores inside it (pairwise on the diagonal, two factors below).
KDA_CHUNK, KDA_SUB = 64, 16
# Chunks of one head a grid step walks (512 rows at chunks of 64): a grid
# step costs a third of a microsecond and a chunk about one.
BLOCK_CHUNKS = 8
# Positions of a block of the scores' diagonal that is made pair by pair.
PAIRWISE = 8
# A head 256 wide needs more than the 16 MiB a kernel is given unasked (the
# backward's ten blocks, twice each); the chip has 128.
VMEM_LIMIT = 64 * 1024 * 1024
_HI = lax.Precision.HIGHEST
_NN, _NT, _TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))


def tiles_apply(t: int, d_k: int, d_v: int, chunk: int, sub: int) -> bool:
    """Whether the kernels exist for a row of ``t`` positions in chunks of
    ``chunk`` and sub-chunks of ``sub``: whole chunks, a chunk whole
    sub-chunks doubling up to it, both of whole sublane tiles, keys and
    values of whole lane tiles (shapes only; the platform is the caller's to
    read)."""
    blocks = chunk // sub if sub and chunk % sub == 0 else 0
    return (t % chunk == 0 and blocks > 0 and blocks & (blocks - 1) == 0
            and sub % SUBLANES == 0 and d_k % LANES == 0 and d_v % LANES == 0)


def _hi(a, b, dims=_NN):
    return lax.dot_general(a, b, (dims, ((), ())), precision=_HI,
                           preferred_element_type=jnp.float32)


def _product(dtype):
    """The chunk's large products: ``dtype`` inputs, float32 sums."""
    precision = {jnp.dtype(jnp.float32): _HI}.get(jnp.dtype(dtype))

    def dot(a, b, dims=_NN):
        return lax.dot_general(a.astype(dtype), b.astype(dtype),
                               (dims, ((), ())), precision=precision,
                               preferred_element_type=jnp.float32)
    return dot


def _levels(c: int, sub: int):
    size = sub
    while size < c:
        yield size
        size *= 2


def _masks(back, c: int, width: int, sub: int):
    """From ``back (C, 2)`` int32 (``positions_back``: the positions before
    each that lie in its run, and the same of the chunk's last position),
    everything a chunk's masks come from, each OVER THE LANES already (a mask
    made from one lane costs a shuffle a register every time it is used):
    ``at (C, width)`` the position; ``reach`` (how far back inside its run
    AND sub-chunk); ``from_start`` (its run began before the chunk) and
    ``to_end`` (its run holds the chunk's last position), float32; ``keep (1,
    1)`` (one run spans the chunk and entered it); ``row - col`` of a ``(C,
    C)`` plane; a level's mask of pairs ``(r, i)``."""
    at = lax.broadcasted_iota(jnp.int32, (c, width), 0)
    own, last = (jnp.broadcast_to(back[:, i:i + 1], (c, width))
                 for i in range(2))
    reach = jnp.minimum(at % sub, own)
    from_start = (own > at).astype(jnp.float32)
    to_end = (last >= c - 1 - at).astype(jnp.float32)
    keep = (back[:1, 1:] >= c).astype(jnp.float32)
    row = lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = lax.broadcasted_iota(jnp.int32, (c, c), 1)
    apart = row - col
    same_run = apart <= jnp.broadcast_to(back[:, :1], (c, c))

    def level(size):
        return ((row // (2 * size) == col // (2 * size))
                & ((row // size) % 2 == 1) & ((col // size) % 2 == 0)
                & same_run)
    return at, apart, reach, from_start, to_end, keep, level


def _middle(cum, size: int):
    """Each position's reference at a level: ``cum`` at the middle of its
    block of ``2 size`` positions, the first of the later half."""
    c = cum.shape[0]
    return jnp.concatenate([
        jnp.broadcast_to(cum[at + size:at + size + 1],
                         (2 * size, cum.shape[1]))
        for at in range(0, c, 2 * size)], axis=0)


def _factors(cum, at, size: int):
    """``(rows', columns')`` factors of a level, each at most 1 and zero where
    the position is not on that side of its block's middle."""
    ref = _middle(cum, size)
    later = (at // size) % 2 == 1
    return (jnp.exp(jnp.where(later, cum - ref, -jnp.inf)),
            jnp.exp(jnp.where(later, -jnp.inf, ref - cum)))


def _pair(cum, k, reach, back_by: int):
    """``(decay, k_i decay)``, ``decay = exp(G_r - G_i)`` at ``i = r -
    back_by`` and zero where that position is not of ``r``'s run and
    sub-chunk (masked before the exponential): each ``(C, d)``."""
    decay = jnp.exp(jnp.where(
        reach >= back_by, cum - pltpu.roll(cum, back_by, 0), -jnp.inf))
    return decay, pltpu.roll(k, back_by, 0) * decay


def _rows_of_blocks(x, i: int, sub: int):
    """Row ``i`` of every diagonal block's rows of ``x (C, n)``, each over
    its block's ``sub`` rows."""
    return jnp.concatenate([
        jnp.broadcast_to(x[at + i:at + i + 1], (sub, x.shape[1]))
        for at in range(0, x.shape[0], sub)], axis=0)


def _recomputed(q, k, v, g, beta, back, state, sub, dtype):
    """Everything a chunk's forward pass makes, which its backward pass makes
    again: a dictionary of values."""
    c, d_k = k.shape
    dot = _product(dtype)
    # pair by pair inside blocks of ``pair``; two float32 factors up to the
    # sub-chunk, which stays float32 throughout; ``dtype`` factors beyond
    pair = PAIRWISE if sub % PAIRWISE == 0 else sub
    at, apart, reach, from_start, to_end, keep, level = _masks(
        back, c, d_k, pair)
    tri = (apart >= 0).astype(jnp.float32)
    cum = _hi(tri, g)
    total = cum[c - 1:c]
    # the step over the lanes of each shape it scales, once
    beta_k, beta_c, beta_s = (jnp.broadcast_to(beta, (c, n))
                              for n in (d_k, c, pair))
    beta_v = jnp.broadcast_to(beta, v.shape)

    # the diagonal blocks, a distance a step: ``a`` strictly lower
    place = (lax.broadcasted_iota(jnp.int32, (c, pair), 1)
             - lax.broadcasted_iota(jnp.int32, (c, pair), 0) % pair)
    diagonal = (q * k).sum(axis=1, keepdims=True)
    a = jnp.zeros((c, c), jnp.float32)
    b = jnp.where(apart == 0, diagonal, 0.0)
    lower = jnp.zeros((c, pair), jnp.float32)    # ``beta a``'s blocks, compact
    for back_by in range(1, pair):
        _, decayed = _pair(cum, k, reach, back_by)
        kk = (k * decayed).sum(axis=1, keepdims=True)
        qk = (q * decayed).sum(axis=1, keepdims=True)
        a = jnp.where(apart == back_by, kk, a)
        b = jnp.where(apart == back_by, qk, b)
        lower = jnp.where(place == -back_by, beta_s * kk, lower)

    # the blocks below them, a level a product
    below = []
    for size in _levels(c, pair):
        rows, cols = _factors(cum, at, size)
        right = k * cols
        # inside a sub-chunk every product is float32
        mm = dot if size >= sub else _hi  # fedtpu: noqa[FTP004] static sizes
        mask = level(size)
        below.append((mm, mask, rows, cols,
                      jnp.where(mask, mm(k * rows, right, _NT), 0.0)))
        b = b + jnp.where(mask, mm(q * rows, right, _NT), 0.0)

    # T = (I + beta a)^-1: the diagonal blocks a column a step, then a level
    solve = (apart == 0).astype(jnp.float32)
    for i in range(pair - 1):
        solve = solve - lower[:, i:i + 1] * _rows_of_blocks(solve, i, pair)
    for _, _, _, _, part in below:
        solve = solve - _hi(_hi(solve, beta_c * part), solve)

    f_in = from_start * jnp.exp(cum)
    f_out = to_end * jnp.exp(total - cum)
    k_in, q_in, k_out = k * f_in, q * f_in, k * f_out           # K~, Q~, K^
    kept = keep * jnp.exp(total)                                # (1, d_k)
    bv, bk = beta_v * v, beta_k * k_in
    w_v, w_k = dot(solve, bv), dot(solve, bk)
    u = w_v - dot(w_k, state, _NT)
    return dict(pair=pair, at=at, apart=apart, reach=reach, beta_k=beta_k,
                beta_c=beta_c, beta_v=beta_v, tri=tri, cum=cum, a=a,
                b=b, below=below, solve=solve, k_in=k_in, q_in=q_in,
                k_out=k_out, kept=kept, f_in=f_in, f_out=f_out, bv=bv, bk=bk,
                w_k=w_k, u=u, dot=dot)


def _forward_chunk(q, k, v, g, beta, back, state, sub, dtype):
    """``(o (C, d_v), next state (d_v, d_k))`` of one chunk of one head."""
    m = _recomputed(q, k, v, g, beta, back, state, sub, dtype)
    dot, u = m["dot"], m["u"]
    o = dot(m["q_in"], state, _NT) + dot(m["b"], u)
    return o, m["kept"] * state + dot(u, m["k_out"], _TN)


def _backward_chunk(q, k, v, g, beta, back, state, do, dnext, sub, dtype):
    """The cotangents ``(dq, dk, dv, dg, dbeta, dstate)`` of one chunk from
    ``do (C, d_v)`` and the next state's ``dnext (d_v, d_k)``."""
    c = k.shape[0]
    m = _recomputed(q, k, v, g, beta, back, state, sub, dtype)
    dot, apart, cum, u = m["dot"], m["apart"], m["cum"], m["u"]
    solve, k_in, q_in, k_out, kept = (m["solve"], m["k_in"], m["q_in"],
                                      m["k_out"], m["kept"])
    # o = Q~ S + B u; S' = kept S + K^T u (the state transposed)
    dq_in = dot(do, state)
    db = dot(do, u, _NT)
    du = dot(m["b"], do, _TN) + dot(k_out, dnext, _NT)
    dk_out = dot(u, dnext)
    dkept = (dnext * state).sum(axis=0, keepdims=True)
    # u = w_v - w_k S; w = T (beta [v, K~])
    dw_k = -dot(du, state)
    dstate = (dot(do, q_in, _TN) + kept * dnext - dot(du, m["w_k"], _TN))
    dsolve = dot(du, m["bv"], _NT) + dot(dw_k, m["bk"], _NT)
    dbv, dbk = dot(solve, du, _TN), dot(solve, dw_k, _TN)
    dlower = -_hi(_hi(solve, dsolve, _TN), solve, _NT)
    da = m["beta_c"] * dlower
    a_whole = m["a"] + sum(part for *_, part in m["below"])
    dbeta = ((dlower * a_whole).sum(axis=1, keepdims=True)
             + (dbv * v).sum(axis=1, keepdims=True)
             + (dbk * k_in).sum(axis=1, keepdims=True))
    dv = m["beta_v"] * dbv
    dk_in = m["beta_k"] * dbk
    # K~, Q~, K^ and kept, through the decays
    fading = k_out * dk_out
    dtotal = fading.sum(axis=0, keepdims=True) + dkept * kept
    dcum = (dk_in * k_in + dq_in * q_in - fading
            + jnp.where(m["at"] == c - 1, dtotal, 0.0))
    dk = dk_in * m["f_in"] + dk_out * m["f_out"]
    dq = dq_in * m["f_in"]
    # the levels of the scores
    for mm, mask, rows, cols, _ in m["below"]:
        left_k, left_q, right = k * rows, q * rows, k * cols
        dp_k, dp_q = jnp.where(mask, da, 0.0), jnp.where(mask, db, 0.0)
        dleft_k, dleft_q = mm(dp_k, right), mm(dp_q, right)
        dright = mm(dp_k, left_k, _TN) + mm(dp_q, left_q, _TN)
        dk = dk + dleft_k * rows + dright * cols
        dq = dq + dleft_q * rows
        dcum = dcum + dleft_k * left_k + dleft_q * left_q - dright * right
    # the diagonal blocks, a distance a step
    pick = lambda mat, back_by: jnp.where(
        apart == back_by, mat, 0.0).sum(axis=1, keepdims=True)
    on = pick(db, 0)
    dq, dk = dq + on * k, dk + on * q
    for back_by in range(1, m["pair"]):
        decay, decayed = _pair(cum, k, m["reach"], back_by)
        ga, gb = pick(da, back_by), pick(db, back_by)
        dk = dk + ga * decayed
        dq = dq + gb * decayed
        weigh = ga * k + gb * q                     # of ``decayed``
        through = weigh * decayed                   # of the exponent
        # what position ``i`` is owed goes ``back_by`` rows up: the rows that
        # wrap around hold nothing (``decay`` is zero there)
        dk = dk + pltpu.roll(weigh * decay, c - back_by, 0)
        dcum = dcum + through - pltpu.roll(through, c - back_by, 0)
    dg = _hi(m["tri"], dcum, _TN)
    return dq, dk, dv, dg, dbeta, dstate


# ------------------------------------------------------------- the kernels
def _chunk_rows(i, chunk: int):
    return pl.ds(pl.multiple_of(i * chunk, chunk), chunk)


def _forward_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, back_ref, o_ref,
                    *rest, chunk, sub, dtype):
    # under the rule a chunk's entering state goes out too
    entering_ref, state_ref = rest if len(rest) == 2 else (None, rest[0])

    @pl.when(pl.program_id(1) == 0)
    def _():
        state_ref[...] = jnp.zeros_like(state_ref)

    def one(i, _):
        rows = _chunk_rows(i, chunk)
        state = state_ref[...]
        if entering_ref is not None:
            entering_ref[i, 0] = state
        o, state_ref[...] = _forward_chunk(
            q_ref[rows, :], k_ref[rows, :], v_ref[rows, :], g_ref[rows, :],
            beta_ref[0, rows, :], back_ref[rows, :], state, sub, dtype)
        o_ref[rows, :] = o

    lax.fori_loop(0, q_ref.shape[0] // chunk, one, None)


def _backward_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, back_ref,
                     entering_ref, do_ref, dq_ref, dk_ref, dv_ref, dg_ref,
                     dbeta_ref, dstate_ref, *, chunk, sub, dtype):
    @pl.when(pl.program_id(1) == 0)
    def _():
        dstate_ref[...] = jnp.zeros_like(dstate_ref)

    chunks = q_ref.shape[0] // chunk

    def one(step, _):
        i = chunks - 1 - step
        rows = _chunk_rows(i, chunk)
        (dq_ref[rows, :], dk_ref[rows, :], dv_ref[rows, :], dg_ref[rows, :],
         dbeta_ref[0, rows, :], dstate_ref[...]) = _backward_chunk(
            q_ref[rows, :], k_ref[rows, :], v_ref[rows, :], g_ref[rows, :],
            beta_ref[0, rows, :], back_ref[rows, :], entering_ref[i, 0],
            do_ref[rows, :], dstate_ref[...], sub, dtype)

    lax.fori_loop(0, chunks, one, None)


def _blocks(t, heads, d_k, d_v, chunk, flip):
    """The grid (heads, blocks of whole chunks) and the block of each kind of
    operand; ``flip`` walks the blocks from the last to the first."""
    n = t // chunk
    per = max(p for p in range(1, min(BLOCK_CHUNKS, n) + 1) if n % p == 0)
    rows, steps = per * chunk, n // per
    at = (lambda j: steps - 1 - j) if flip else (lambda j: j)
    wide = lambda d: pl.BlockSpec((rows, d), lambda h, j: (at(j), h))
    return ((heads, steps), wide(d_k), wide(d_v),
            pl.BlockSpec((1, rows, 1), lambda h, j: (h, at(j), 0)),
            pl.BlockSpec((rows, 2), lambda h, j: (at(j), 0)),
            pl.BlockSpec((per, 1, d_v, d_k), lambda h, j: (at(j), h, 0, 0)))


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=VMEM_LIMIT)


def _forward(q, k, v, g, beta, back, heads, chunk, sub, dtype, entering):
    t, d_k, d_v = q.shape[0], q.shape[1] // heads, v.shape[1] // heads
    grid, keys, values, step, marks, states = _blocks(
        t, heads, d_k, d_v, chunk, False)
    out = [jax.ShapeDtypeStruct((t, heads * d_v), jnp.float32)]
    out_specs = [values]
    if entering:
        out.append(jax.ShapeDtypeStruct((t // chunk, heads, d_v, d_k),
                                        jnp.float32))
        out_specs.append(states)
    return pl.pallas_call(
        functools.partial(_forward_kernel, chunk=chunk, sub=sub, dtype=dtype),
        grid=grid, in_specs=[keys, keys, values, keys, step, marks],
        out_specs=out_specs, out_shape=out,
        scratch_shapes=[pltpu.VMEM((d_v, d_k), jnp.float32)],
        compiler_params=_params(), name="kda_scan_forward",
    )(q, k, v, g, beta, back)


def _backward(q, k, v, g, beta, back, entering, do, heads, chunk, sub, dtype):
    t, d_k, d_v = q.shape[0], q.shape[1] // heads, v.shape[1] // heads
    grid, keys, values, step, marks, states = _blocks(
        t, heads, d_k, d_v, chunk, True)
    like = lambda a: jax.ShapeDtypeStruct(a.shape, jnp.float32)
    return pl.pallas_call(
        functools.partial(_backward_kernel, chunk=chunk, sub=sub, dtype=dtype),
        grid=grid,
        in_specs=[keys, keys, values, keys, step, marks, states, values],
        out_specs=[keys, keys, values, keys, step],
        out_shape=[like(q), like(k), like(v), like(g), like(beta)],
        scratch_shapes=[pltpu.VMEM((d_v, d_k), jnp.float32)],
        compiler_params=_params(), name="kda_scan_backward",
    )(q, k, v, g, beta, back, entering, do)


def positions_back(run, chunk: int):
    """``(T, 2)`` int32: how many positions before each lie in its own run
    (``run`` from ``document_runs``: a position's run id, never falling), and
    the same of its chunk's last position."""
    at = jnp.arange(run.shape[0], dtype=jnp.int32)
    starts = jnp.concatenate([jnp.ones((1,), bool), run[1:] != run[:-1]])
    own = at - lax.cummax(jnp.where(starts, at, 0))
    last = jnp.repeat(own.reshape(-1, chunk)[:, -1], chunk)
    return jnp.stack([own, last], axis=1)


def _operands(q, k, v, g, beta, run, chunk):
    """The arrays as the kernels read them: a head a block of columns, the
    step a head's column of its own."""
    t = q.shape[0]
    flat = lambda a: a.reshape(t, -1)
    return (flat(q), flat(k), flat(v), flat(g), beta.T[:, :, None],
            positions_back(run, chunk))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def fused_kda_scan(q, k, v, g, beta, run, chunk: int, sub: int,
                   compute_dtype):
    """``kda_scan`` (the definition) of ``q``, ``k``, ``g (T, heads, d_k)``,
    ``v (T, heads, d_v)``, ``beta (T, heads)``, all float32, and ``run
    (T,)``, in the kernels: ``o (T, heads, d_v)`` float32. ``tiles_apply``
    says at which shapes."""
    t, heads, _ = q.shape
    o, = _forward(*_operands(q, k, v, g, beta, run, chunk), heads, chunk,
                  sub, compute_dtype, False)
    return o.reshape(t, heads, -1)


def _fused_kda_scan_fwd(q, k, v, g, beta, run, chunk, sub, compute_dtype):
    t, heads, _ = q.shape
    o, entering = _forward(*_operands(q, k, v, g, beta, run, chunk), heads,
                           chunk, sub, compute_dtype, True)
    return o.reshape(t, heads, -1), (q, k, v, g, beta, run, entering)


def _fused_kda_scan_bwd(chunk, sub, compute_dtype, residuals, do):
    q, k, v, g, beta, run, entering = residuals
    t, heads, _ = q.shape
    dq, dk, dv, dg, dbeta = _backward(
        *_operands(q, k, v, g, beta, run, chunk), entering,
        do.reshape(t, -1), heads, chunk, sub, compute_dtype)
    return (dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape),
            dg.reshape(g.shape), dbeta[:, :, 0].T, None)


fused_kda_scan.defvjp(_fused_kda_scan_fwd, _fused_kda_scan_bwd)


# ---------------------------- the definition (the chunked form), and the rule
@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def unit_lower_inverse(low, block: int = KDA_SUB):
    """``T = (I + L)^-1`` of strictly lower triangular ``L (..., C, C)``
    float32 by forward substitution, products at ``HIGHEST`` precision: the
    diagonal blocks of ``block`` rows a row a trip, all of them at once
    (``T_r = e_r - sum_{i<r} L_ri T_i``), then block row by block row,
    ``T_i: = -T_ii (sum_{j<i} L_ij T_j:)``. (A row a trip over the whole of
    ``C`` reads the whole of ``T`` every trip: 64 x 33 MB a call at 4,096
    positions, a tenth of the cell's round. And ``L`` is nilpotent, so ``(I -
    L)(I + L^2)(I + L^4)...`` is the same matrix in ``log2 C`` products, but
    its terms grow as ``|L|^n C(C, n)`` before they cancel: with the keys a
    SiLU leaves, most of them on one side of the origin, ``L`` has entries
    near a half and that product read 1e28 where the inverse's entries are
    under 1.) Reverse mode only, under the inverse's own rule."""
    c = low.shape[-1]
    s = block if c % block == 0 else c
    a = c // s
    mm = functools.partial(jnp.matmul, precision=lax.Precision.HIGHEST)
    corner = jnp.moveaxis(jnp.diagonal(
        low.reshape(*low.shape[:-2], a, s, a, s), axis1=-4, axis2=-2),
        -1, -3)                                             # (..., a, s, s)

    def row(r, inv):            # rows under ``r`` are done, the rest zero
        new = (jnp.arange(s) == r).astype(low.dtype) - mm(
            lax.dynamic_slice_in_dim(corner, r, 1, axis=-2), inv)
        return lax.dynamic_update_slice_in_dim(inv, new, r, axis=-2)

    own = lax.fori_loop(0, s, row, jnp.zeros_like(corner))
    inv = own[..., 0, :, :]                                 # (..., s, s)
    for i in range(1, a):       # the ``i`` block rows above are done
        under = low[..., i * s:(i + 1) * s, :i * s]
        new = jnp.concatenate([-mm(own[..., i, :, :], mm(under, inv)),
                               own[..., i, :, :]], axis=-1)
        inv = jnp.concatenate(
            [jnp.pad(inv, [(0, 0)] * (low.ndim - 1) + [(0, s)]), new], axis=-2)
    return inv


def _unit_lower_inverse_fwd(low, block):
    inv = unit_lower_inverse(low, block)
    return inv, inv


def _unit_lower_inverse_bwd(block, inv, g):
    mm = functools.partial(jnp.matmul, precision=lax.Precision.HIGHEST)
    turned = jnp.swapaxes(inv, -1, -2)
    return (-mm(mm(turned, g), turned),)


unit_lower_inverse.defvjp(_unit_lower_inverse_fwd, _unit_lower_inverse_bwd)


def _decayed_scores(lefts, k, cum, sub: int, compute_dtype):
    """``[P (n, h, C, C)]``, one for each ``left (n, h, C, d)`` of ``lefts``:
    ``P_ri = sum_c left_rc k_ic exp(cum_rc - cum_ic)`` for ``i <= r``, zero
    above the diagonal; ``cum (n, h, C, d)`` the cumulative log-decay inside
    the chunk, never rising. In sub-chunks of ``sub``: a diagonal block pair
    by pair, a block below it from the two factors either side of the row
    block's first position."""
    n, h, c, d = k.shape
    a = c // sub
    f32 = dict(preferred_element_type=jnp.float32)
    cast = lambda arr: arr.astype(compute_dtype)
    blocks = lambda arr: arr.reshape(n, h, a, sub, d)
    ks, cs = blocks(k), blocks(cum)
    idx = jnp.arange(sub)
    inside = (idx[:, None] >= idx[None, :])[:, :, None]           # (r, i, 1)
    pair = jnp.exp(jnp.where(inside, cs[:, :, :, :, None] - cs[:, :, :, None],
                             -jnp.inf)) * ks[:, :, :, None]       # (.., r, i, d)
    # below the diagonal blocks: row blocks 1.., columns before the last
    # block (none lies under block 0, and the last block's columns lie under
    # no other): (a - 1) x (C - sub) of the a x C pairs of blocks and columns
    cols = c - sub
    ref = cs[:, :, 1:, :1]                                     # (.., a - 1, 1, d)
    earlier = (jnp.arange(cols)[None, :]
               < (jnp.arange(1, a) * sub)[:, None])[:, :, None]
    right = cast(k[:, :, None, :cols] * jnp.exp(jnp.where(
        earlier, ref - cum[:, :, None, :cols], -jnp.inf)))     # (.., a - 1, cols, d)
    eye = jnp.eye(a, dtype=jnp.float32)[:, None, :, None]       # block place
    out = []
    for left in lefts:
        ls = blocks(left)
        diag = (ls[:, :, :, :, None] * pair).sum(axis=-1)       # (.., a, r, i)
        below = jnp.einsum(
            "nhard,nhaid->nhari",
            cast(ls[:, :, 1:] * jnp.exp(cs[:, :, 1:] - ref)), right, **f32)
        below = jnp.pad(below, ((0, 0), (0, 0), (1, 0), (0, 0), (0, sub)))
        out.append((below + (diag[:, :, :, :, None] * eye).reshape(
            n, h, a, sub, c)).reshape(n, h, c, c))
    return out


def fused_scan_applies(t: int, d_k: int, d_v: int, chunk: int,
                       sub: int) -> bool:
    """Whether the recurrence's tiled kernels (``fused_kda_scan``: one
    forward, one backward, a head's state in the chip's own memory) exist for
    a row of ``t`` positions, keys ``d_k`` and values ``d_v`` wide, where the
    program is being built: a TPU (the PROCESS's backend, as
    ``packed_attention.fused_attention_applies`` reads it), keys and values
    of whole lane tiles, ``t`` whole chunks and a chunk whole sub-chunks. The
    chunked form below is the definition and the body everywhere else."""
    return jax.default_backend() == "tpu" and tiles_apply(
        t, d_k, d_v, chunk, sub)


def kda_scan(q, k, v, g, beta, run, chunk: int, compute_dtype,
             sub: int = KDA_SUB):
    """``o (T, heads, d_v)`` float32 of the recurrence ``S_t = (I - beta_t k_t
    k_t^T) Diag(exp g_t) S_{t-1} [t-1 in t's run] + beta_t k_t v_t^T``, ``o_t
    = S_t^T q_t``, in chunks (the module's docstring has the algebra). ``q``,
    ``k (T, heads, d_k)``, ``v (T, heads, d_v)``, ``g (T, heads, d_k)`` the
    log-decay, NEVER positive, ``beta (T, heads)``, all float32; ``run (T,)``
    from ``document_runs``; ``T`` is whole chunks (or one shorter chunk) and
    a chunk whole sub-chunks. Where ``fused_scan_applies`` the kernels run,
    named for their direction so that their ``op_name`` keeps it."""
    t, heads, _ = k.shape
    if fused_scan_applies(t, k.shape[-1], v.shape[-1], chunk, sub):
        return fused_kda_scan(q, k, v, g, beta, run, chunk, sub,
                              compute_dtype)
    c = min(chunk, t)
    sub = min(sub, c)
    if t % c or c % sub:
        raise ValueError(f"a sequence of {t} positions is not whole chunks of "
                         f"{c}, or a chunk not whole sub-chunks of {sub}")
    n = t // c
    cast = lambda arr: arr.astype(compute_dtype)
    f32 = dict(preferred_element_type=jnp.float32)
    # chunks, heads, positions, width: a head's (C, C) planes have whole lanes
    fold = lambda arr: arr.reshape(n, c, heads, -1).transpose(0, 2, 1, 3)
    qc, kc, vc, gc = map(fold, (q, k, v, g))
    bc = fold(beta)                                                 # (n, h, C, 1)
    cum = jnp.cumsum(gc, axis=2)
    runs = run.reshape(n, c)
    last = runs[:, -1]
    before = jnp.concatenate([jnp.zeros((1,), run.dtype), last[:-1]])

    # inside a chunk: position r reads i <= r of its own run
    idx = jnp.arange(c)
    same = (runs[:, :, None] == runs[:, None, :])[:, None]          # (n, 1, r, i)
    # recomputed in the backward pass: the pairwise exponentials are
    # C * sub * d numbers a chunk and head, a gigabyte at 4,096 positions
    kk, qk = jax.checkpoint(functools.partial(
        _decayed_scores, sub=sub, compute_dtype=compute_dtype))(
            (kc, qc), kc, cum)
    a_mat = jnp.where(same & (idx[:, None] > idx[None, :]), kk, 0.0)
    b_mat = jnp.where(same & (idx[:, None] >= idx[None, :]), qk, 0.0)
    solve = unit_lower_inverse(bc * a_mat, sub)                     # (n, h, C, C)

    # the entering state is read by the positions of the run it belongs to,
    # and the chunk's last run is what reaches its end
    from_start = (runs == before[:, None])[:, None, :, None]         # (n, 1, C, 1)
    to_end = (runs == last[:, None])[:, None, :, None]
    grown = jnp.exp(cum)
    total = cum[:, :, -1:]                                          # (n, h, 1, d)
    k_in = jnp.where(from_start, kc * grown, 0.0)                   # K~
    q_in = jnp.where(from_start, qc * grown, 0.0)                   # Q~
    k_out = jnp.where(to_end, kc * jnp.exp(total - cum), 0.0)       # K^
    keep = jnp.where((last == before)[:, None, None],
                     jnp.exp(total[:, :, 0]), 0.0)                  # (n, h, d)
    w_v = jnp.einsum("nhri,nhiv->nhrv", cast(solve), cast(bc * vc), **f32)
    w_k = jnp.einsum("nhri,nhid->nhrd", cast(solve), cast(bc * k_in), **f32)

    def carry(state, step):
        w_v, w_k, k_out, keep = step
        u = w_v - jnp.einsum("hrd,hdv->hrv", cast(w_k), cast(state), **f32)
        new = keep[:, :, None] * state + jnp.einsum(
            "hrd,hrv->hdv", cast(k_out), cast(u), **f32)
        return new, (state, u)

    zero = jnp.zeros((heads, kc.shape[-1], vc.shape[-1]), jnp.float32)
    _, (entering, u) = lax.scan(carry, zero, (w_v, w_k, k_out, keep))
    o = (jnp.einsum("nhrd,nhdv->nhrv", cast(q_in), cast(entering), **f32)
         + jnp.einsum("nhri,nhiv->nhrv", cast(b_mat), cast(u), **f32))
    return o.transpose(0, 2, 1, 3).reshape(t, heads, -1)
