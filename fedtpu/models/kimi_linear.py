"""Kimi-Linear: a gated delta-rule recurrence (KDA) three layers in four,
latent attention without positions in the fourth, a leading dense layer and
a held share of sparse experts, on packed sequences.

The stack is the one ``config.json`` of moonshotai/Kimi-Linear-48B-A3B-
Instruct (``model_type: kimi_linear``) defines; the KDA layer is
``fla.layers.kda.KimiDeltaAttention`` and its recurrence
``fla.ops.kda.naive.naive_recurrent_kda``, which the model's own
``modeling_kimi.py`` follows. What the config leaves to the code is listed in
the benchmark's configuration file under ``assumed``. Every layer is
``h + Mixer(RMSNorm(h))``, then ``h + FFN(RMSNorm(h))``.

* **The KDA mixer** (layers ``kda_layers``; ``kda_num_heads`` heads, keys and
  values ``kda_head_dim`` wide). ``q, k, v = SiLU(conv(W x))``, the
  convolution depthwise, causal, ``short_conv_kernel_size`` taps, no bias,
  zeros before a document's first token (``nemotron_h.causal_conv``); a head's
  ``q <- q / |q| d^-1/2`` and ``k <- k / |k|``. The log-decay of head ``h``
  and key channel ``i`` is ``g = -exp(A_log[h]) softplus(W_f2 W_f1 x +
  dt_bias)``, the step ``beta = sigmoid(W_b x)``. A head's state ``S (d_k,
  d_v)`` is zero at a document's first token and
  ``S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T``,
  ``o_t = S_t^T q_t``; then ``W_o [RMSNorm_head(o) * sigmoid(W_g2 W_g1 x +
  b_g)]``.
* **The chunked form** (``kda_scan``), this repo's own. With ``G_r`` the
  cumulative log-decay inside a chunk and ``S_0`` the state entering it, the
  corrections ``u_r = beta_r (v_r - (Diag(exp g_r) S_{r-1})^T k_r)`` solve
  ``(I + Diag(beta) A) U = Diag(beta) (V - K~ S_0)``, ``A_ri = sum_c k_rc
  k_ic exp(G_rc - G_ic)`` for ``i < r``, ``K~_r = exp(G_r) k_r``: one inverse
  of a unit lower triangular matrix a chunk and head (``unit_lower_inverse``,
  by forward substitution).
  Then ``O = Q~ S_0 + B U`` with ``B_ri`` the same sum with ``q_r`` for ``i <=
  r``, and ``S_C = Diag(exp G_C) S_0 + K^^T U``, ``K^_i = exp(G_C - G_i)
  k_i``, carried from chunk to chunk by a ``lax.scan``. **No exponential of
  a positive number is ever taken**: ``exp(-G)`` overflows float32 inside a
  chunk of 64 at the decays the model starts with (a token's ``g`` reaches
  -1.6), so ``A`` and ``B`` are made of sub-chunks of ``KDA_SUB`` positions:
  a diagonal block pairwise (``exp(G_r - G_i)`` per pair and channel, masked
  before the exponential), a block below the diagonal as a product of two
  factors taken from the row block's first position, ``exp(G_r - G_ref)``
  and ``exp(G_ref - G_i)``, both at most 1 because ``g <= 0``. A document's
  first token may fall anywhere: pairs across two documents are masked out
  of ``A`` and ``B``, only the positions of the entering document read
  ``S_0``, only the chunk's last document reaches ``S_C``. The state, the
  decays, the norms and the triangular inverse (``HIGHEST`` precision) are
  float32; the chunk's large products take ``compute_dtype`` inputs and sum
  in float32. Autodiff differentiates all of it but the inverse, which has
  the rule ``-T^T dT T^T``.
* **Which body runs where.** ``kda_scan`` below is the definition: XLA's
  passes over ``(chunks, heads, C, d)`` arrays, the body on a CPU and at
  shapes without tiles, and the oracle of the kernels' tests. Where
  ``fused_scan_applies`` (a TPU, keys and values of whole lane tiles, whole
  chunks of whole sub-chunks: the published widths at any row of whole
  chunks) the same algebra at the same precision runs as two Mosaic kernels
  under a differentiation rule of their own, ``fedtpu.ops.kda_scan``: a
  head's state stays in the chip's memory across its chunks, the operands
  are read in place as the convolutions leave them, and nothing of a chunk
  but ``o`` (and, for the backward pass, the state that entered it) is
  written. ``kda_fused_scan`` among the statistics says which ran.
* **Latent attention** (layers ``full_attn_layers``): ``xing4.
  latent_attention`` with no query bottleneck and nothing rotated
  (``q_lora_rank`` None, ``mla_use_nope``).
* **Feed-forward**: the first ``first_k_dense_replace`` layers
  ``xing4.dense_mlp``, every other ``nemotron_h.experts_mixer`` with the gated
  activation: sigmoid scores over all routed experts, the top
  ``num_experts_per_tok`` of ``score + bias`` renormalised and scaled, this
  chip's share of them computed, beside the shared expert.

Every layer is recomputed from its input in the backward pass
(``jax.checkpoint`` a layer): one ``(T, hidden)`` float32 array a layer is
kept. Parameters are float32, a leaf a layer (``params["layers"][i]`` is
``{"mixer", "ffn"}``; a mixer with ``q_conv`` is KDA's, a feed-forward with
``router`` the experts').
"""

from __future__ import annotations

import functools
import itertools

import jax
import jax.numpy as jnp
from jax import lax

from fedtpu.models import nemotron_h, olmoe, xing4
from fedtpu.models.nemotron_h import (causal_conv, document_runs,
                                      experts_share, held_block_rows)
from fedtpu.models.olmoe import (EMBED, KDA, KDA_CONV, KDA_GATES, KDA_IN_PROJ,
                                 KDA_OUT_PROJ, KDA_SCAN, LM_HEAD_LOSS,
                                 _head_loss, next_token_targets, rms_norm)
from fedtpu.ops import kda_scan as scan_kernels

# Positions of a chunk of the recurrence, and of a sub-chunk of the decay-
# weighted scores inside it (pairwise on the diagonal, two factors below).
KDA_CHUNK, KDA_SUB = 64, 16
# The start of the decay (``fla``'s, Mamba's): ``A_log = log U(1, 16)`` a
# head, ``dt_bias`` the inverse softplus of a log-uniform step in this range.
A_RANGE, DT_RANGE = (1.0, 16.0), (0.001, 0.1)
L2_EPS = 1e-6

_mm = functools.partial(jnp.dot, preferred_element_type=jnp.float32)


def layer_kinds(cfg) -> tuple:
    """``(mixer, feed-forward)`` of every layer, in order: ``"kda"`` or
    ``"full"``, ``"dense"`` or ``"experts"``. The two published lists are
    1-based and must name every layer once; the stack builds no multi-token-
    prediction module."""
    layers = cfg.num_hidden_layers
    kda, full = tuple(cfg.kda_layers), tuple(cfg.full_attn_layers)
    if sorted(kda + full) != list(range(1, layers + 1)):
        raise ValueError(
            f"kda_layers {kda} and full_attn_layers {full} do not name each "
            f"of the {layers} layers once: a layer is a KDA mixer or latent "
            "attention, and no other kind is built")
    if not 0 <= cfg.first_k_dense_replace <= layers:
        raise ValueError(f"first_k_dense_replace {cfg.first_k_dense_replace} "
                         f"is not within the {layers} layers")
    if cfg.num_nextn_predict_layers:
        raise ValueError(
            f"num_nextn_predict_layers {cfg.num_nextn_predict_layers}: this "
            "stack builds no multi-token-prediction module")
    return tuple(("kda" if i in kda else "full",
                  "dense" if i <= cfg.first_k_dense_replace else "experts")
                 for i in range(1, layers + 1))


# ------------------------------------------------------------------ init
def _kda_init(cfg, normal, ones):
    # the rank of the decay's and the output gate's two-matrix projections
    # is no key of the config: ``fla``'s layer takes the head's width
    h, rank = cfg.hidden_size, cfg.kda_head_dim
    width = cfg.kda_num_heads * cfg.kda_head_dim
    return {"norm": ones(h), "q_proj": normal(h, width),
            "k_proj": normal(h, width), "v_proj": normal(h, width),
            "f_a": normal(h, rank), "f_b": normal(rank, width),
            "b_proj": normal(h, cfg.kda_num_heads),
            "g_a": normal(h, rank), "g_b": normal(rank, width),
            "o_norm": ones(cfg.kda_head_dim), "o_proj": normal(width, h)}


def _kda_own_init(cfg, key, dtype):
    """The mixer's leaves that are not N(0, 0.02), cut from one uniform
    draw: the three convolutions' weights PyTorch's default (bound
    ``kernel^-1/2``), ``A_log`` and ``dt_bias`` as ``fla`` starts them, the
    output gate's bias zero."""
    heads, taps = cfg.kda_num_heads, cfg.short_conv_kernel_size
    width = heads * cfg.kda_head_dim
    u = jax.random.uniform(key, (heads + (1 + 3 * taps) * width,), jnp.float32)
    log_uniform = lambda part, lo, hi: jnp.exp(
        part * (jnp.log(hi) - jnp.log(lo)) + jnp.log(lo))
    dt = log_uniform(u[heads:heads + width], *DT_RANGE)
    convs = ((2.0 * u[heads + width:] - 1.0) * taps ** -0.5).reshape(
        3, taps, width).astype(dtype)
    return {"A_log": jnp.log(u[:heads] * (A_RANGE[1] - A_RANGE[0])
                             + A_RANGE[0]).astype(dtype),
            "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
            "q_conv": convs[0], "k_conv": convs[1], "v_conv": convs[2],
            "g_bias": jnp.zeros((width,), dtype)}


def _full_init(cfg, normal, ones):
    h, heads = cfg.hidden_size, cfg.num_attention_heads
    nope, rope, v = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    return {"norm": ones(h), "q": normal(h, heads * (nope + rope)),
            "kv_a": normal(h, cfg.kv_lora_rank + rope),
            "kv_a_norm": ones(cfg.kv_lora_rank),
            "kv_b": normal(cfg.kv_lora_rank, heads * (nope + v)),
            "o": normal(heads * v, h)}


def kimi_linear_init(key: jax.Array, cfg, param_dtype=jnp.float32):
    """N(0, 0.02) weights and selection biases, unit norm gains, KDA's own
    leaves as ``_kda_own_init`` draws them; a mixer's and a feed-forward's
    weights each cut out of one draw (``xing4.cut_from_one_draw``)."""
    count = itertools.count()
    fresh = lambda: jax.random.fold_in(key, next(count))
    ones = lambda *shape: jnp.ones(shape, param_dtype)
    weights = lambda build: xing4.cut_from_one_draw(fresh(), build, ones,
                                                    param_dtype)

    def layer(mixer, ffn):
        if mixer == "kda":
            own = {**weights(functools.partial(_kda_init, cfg)),
                   **_kda_own_init(cfg, fresh(), param_dtype)}
        else:
            own = weights(functools.partial(_full_init, cfg))
        return {"mixer": own,
                "ffn": weights(functools.partial(xing4._ffn_init, ffn, cfg))}

    h = cfg.hidden_size
    normal = lambda *shape: weights(lambda draw, _: draw(*shape))
    return {"embed": normal(cfg.vocab_size, h),
            "layers": tuple(layer(*kinds) for kinds in layer_kinds(cfg)),
            "final_norm": ones(h), "head": normal(h, cfg.vocab_size)}


# ------------------------------------------------------------------- KDA
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
    """Whether the recurrence's tiled kernels (``fedtpu.ops.kda_scan``: one
    forward, one backward, a head's state in the chip's own memory) exist for
    a row of ``t`` positions, keys ``d_k`` and values ``d_v`` wide, where the
    program is being built: a TPU (the PROCESS's backend, as ``olmoe.
    fused_attention_applies`` reads it), keys and values of whole lane tiles,
    ``t`` whole chunks and a chunk whole sub-chunks. The chunked form below
    is the definition and the body everywhere else."""
    return jax.default_backend() == "tpu" and scan_kernels.tiles_apply(
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
        return scan_kernels.kda_scan(q, k, v, g, beta, run, chunk, sub,
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


def _l2_normed(x):
    return x * lax.rsqrt((x * x).sum(axis=-1, keepdims=True) + L2_EPS)


def _head_tiles(x, heads: int, rows: int):
    """``x (T, heads * d)`` as ``(T / rows, heads, rows, d)``, a head's ``d``
    last. At ``rows`` 8 these are the tiles a TPU holds the array in (eight
    rows of one head's lanes), so a view of what the convolutions leave and
    of what the recurrence's kernels read and write, where ``(T, heads, d)``
    is a transposing copy of the whole array each way (96 of them a step at
    the published widths, 67 MB each: PERF.md section 6, PR 40); at ``rows``
    1 it is ``(T, heads, 1, d)``, the plain form."""
    t = x.shape[0]
    return x.reshape(t // rows, rows, heads, -1).transpose(0, 2, 1, 3)


def _rows(x):
    """``_head_tiles``' inverse: ``(T, heads * d)``."""
    n, heads, rows, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(n * rows, heads * d)


def kda_mixer(cfg, compute_dtype, h, layer, segs):
    """``(mixer(RMSNorm(h)), statistics)`` of one KDA layer."""
    t = h.shape[0]
    heads, d = cfg.kda_num_heads, cfg.kda_head_dim
    cast = lambda arr: arr.astype(compute_dtype)
    two = lambda x, a, b: _mm(cast(_mm(x, cast(layer[a]))), cast(layer[b]))
    by_head = lambda arr: arr.reshape(t, heads, d)
    fused = fused_scan_applies(t, d, d, KDA_CHUNK, KDA_SUB)
    # where the kernels read the arrays in place, the gates on their tiles
    tiles = functools.partial(_head_tiles, heads=heads,
                              rows=scan_kernels.SUBLANES if fused else 1)
    run, starts = document_runs(segs)
    with jax.named_scope(KDA):
        with jax.named_scope(KDA_IN_PROJ):
            x = cast(rms_norm(h, layer["norm"], cfg.rms_norm_eps))
            q, k, v = (_mm(x, cast(layer[name]))
                       for name in ("q_proj", "k_proj", "v_proj"))
            decay, gate = two(x, "f_a", "f_b"), two(x, "g_a", "g_b")
            step = _mm(x, cast(layer["b_proj"]))
        with jax.named_scope(KDA_CONV):
            q, k, v = (jax.nn.silu(causal_conv(a, layer[name], 0.0, run))
                       for a, name in ((q, "q_conv"), (k, "k_conv"),
                                       (v, "v_conv")))
        with jax.named_scope(KDA_GATES):
            q = _rows(_l2_normed(tiles(q)) * d ** -0.5)
            k = _rows(_l2_normed(tiles(k)))
            fall = (-jnp.exp(layer["A_log"].astype(jnp.float32))[:, None, None]
                    * jax.nn.softplus(tiles(decay + layer["dt_bias"])))
            g, beta = _rows(fall), jax.nn.sigmoid(step)
        with jax.named_scope(KDA_SCAN):
            o = kda_scan(by_head(q), by_head(k), by_head(v), by_head(g), beta,
                         run, KDA_CHUNK, compute_dtype)
        with jax.named_scope(KDA_GATES):
            # a chunk is whole tiles of rows (or the row is one chunk)
            rows = fall.shape[2]
            deepest = lax.stop_gradient(fall.reshape(
                -1, min(KDA_CHUNK, t) // rows, heads, rows, d).sum(
                    axis=(1, 3)).min())
            y = _rows(rms_norm(tiles(o.reshape(t, heads * d)),
                               layer["o_norm"], cfg.rms_norm_eps)
                      * jax.nn.sigmoid(tiles(gate + layer["g_bias"])))
        with jax.named_scope(KDA_OUT_PROJ):
            out = _mm(cast(y), cast(layer["o_proj"]))
    return out, {"kda_positions": jnp.float32(t),
                 "kda_fused_scan": jnp.float32(t if fused else 0),
                 "kda_restarts": (starts & (segs > 0)).sum().astype(
                     jnp.float32),
                 "kda_log_decay_min": deepest}


# ------------------------------------------------------------- the model
def block(kinds, cfg, compute_dtype, h, layer, segs):
    """One layer on ``h (T, C)`` float32: the mixer of its kind, then the
    feed-forward of its kind, each behind its own pre-norm and added to the
    residual. ``(h, statistics)``."""
    mixer, ffn = kinds
    if mixer == "kda":
        out, stats = kda_mixer(cfg, compute_dtype, h, layer["mixer"], segs)
    else:       # without positions: ``pos`` is not read
        out, stats = xing4.latent_attention(cfg, compute_dtype, h,
                                            layer["mixer"], segs, None), {}
    h = h + out
    if ffn == "dense":
        return h + xing4.dense_mlp(cfg, compute_dtype, h, layer["ffn"]), stats
    out, routed = nemotron_h.experts_mixer(cfg, compute_dtype, h, layer["ffn"],
                                           segs, eps=cfg.rms_norm_eps)
    return h + out, {**stats, **routed}


def _zero_stats(cfg):
    zero = jnp.float32(0.0)
    return {"expert_load": jnp.zeros((cfg.n_routed_experts,), jnp.int32),
            "assignments_held": zero, "rows_computed": zero,
            "rows_held_computed": zero, "kda_positions": zero,
            "kda_fused_scan": zero, "kda_restarts": zero}


def kimi_linear_sequence_stats(params, row, cfg, compute_dtype=jnp.float32):
    """One packed row ``(2, T)`` through the model: ``nemotron_h_sequence_
    stats``'s sums without the state-space layer's, and this stack's own,
    summed over its KDA layers: ``kda_positions`` (positions the recurrence
    ran over), ``kda_fused_scan`` (the positions whose recurrences ran in the
    tiled kernels, ``fused_scan_applies``: the mean over the KDA layers, so
    the sequence's positions or 0), ``kda_restarts`` (documents whose state
    started at zero),
    ``kda_log_decay_min`` (the most negative cumulative log-decay of any
    chunk, head and channel of the sequence: at most 0, and under -88 where
    ``exp(-G)`` would have overflowed float32) and ``sequences`` (1)."""
    tokens, segs = row[0], row[1]
    kinds = layer_kinds(cfg)
    t, heads = tokens.shape[0], cfg.num_attention_heads
    wide = olmoe.padded_head_width(
        jax.ShapeDtypeStruct((t, heads, cfg.qk_nope_head_dim
                              + cfg.qk_rope_head_dim), compute_dtype),
        jax.ShapeDtypeStruct((t, heads, cfg.v_head_dim), compute_dtype))
    core = jax.ShapeDtypeStruct((t, heads, wide), compute_dtype)
    full = sum(mixer == "full" for mixer, _ in kinds)
    # the rules between the bodies, read as their own callers read them
    fused = full > 0 and olmoe.fused_attention_applies(core, core, core)
    held, _ = experts_share(cfg)
    rows = held_block_rows(t * cfg.num_experts_per_tok,
                           held / cfg.n_routed_experts)
    grouped = (any(ffn == "experts" for _, ffn in kinds)
               and all(olmoe.grouped_matmul_applies(
                   jax.ShapeDtypeStruct((rows, k), compute_dtype),
                   jax.ShapeDtypeStruct((held, k, m), compute_dtype))
                   for k, m in ((cfg.hidden_size, cfg.moe_intermediate_size),
                                (cfg.moe_intermediate_size, cfg.hidden_size))))
    with jax.named_scope(EMBED):
        h = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)
    stats, deepest = _zero_stats(cfg), jnp.float32(0.0)
    for kind, layer in zip(kinds, params["layers"]):
        # recomputed from its input in the backward pass: one (T, C) array a
        # layer is kept
        h, own = jax.checkpoint(functools.partial(
            block, kind, cfg, compute_dtype, segs=segs))(h, layer)
        deepest = jnp.minimum(deepest, own.pop("kda_log_decay_min", 0.0))
        stats = {**stats, **{k: stats[k] + v for k, v in own.items()}}
    stats["kda_fused_scan"] = stats["kda_fused_scan"] / max(
        sum(mixer == "kda" for mixer, _ in kinds), 1)
    with jax.named_scope(LM_HEAD_LOSS):
        labels, valid = next_token_targets(tokens, segs)
        loss, correct = _head_loss(
            rms_norm(h, params["final_norm"], cfg.rms_norm_eps),
            params["head"], labels, valid, compute_dtype)
    return {"loss_sum": loss, "correct": correct, "count": valid.sum(),
            "tokens": (segs > 0).sum().astype(jnp.float32),
            "padding": (segs == 0).sum().astype(jnp.float32),
            "fused_attention": jnp.float32(t if fused else 0),
            "grouped_experts": jnp.float32(t if grouped else 0),
            "sequences": jnp.float32(1.0), "kda_log_decay_min": deepest,
            **olmoe.attention_blocks(segs, fused, full), **stats}


def kimi_linear_stats(params, x, mask, cfg, compute_dtype=jnp.float32):
    """``kimi_linear_sequence_stats`` summed over the rows ``x (N, 2, T)``
    whose ``mask`` is 1, one row at a time."""
    def one(row_and_mask):
        row, m = row_and_mask
        stats = kimi_linear_sequence_stats(
            params, row * m.astype(row.dtype), cfg, compute_dtype)
        return {**stats, **{k: stats[k] * m for k in (
            "padding", "fused_attention", "grouped_experts",
            "attention_blocks_computed", "attention_blocks_causal",
            "rows_computed", "kda_positions", "kda_fused_scan",
            "kda_log_decay_min", "sequences")}}

    if x.shape[0] == 1:
        return one((x[0], mask[0]))
    stats = lax.map(one, (x, mask))
    return jax.tree.map(lambda a: a.sum(axis=0), stats)
