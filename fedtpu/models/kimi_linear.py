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
  zeros before a document's first token (``ssm_passes.causal_conv``); a head's
  ``q <- q / |q| d^-1/2`` and ``k <- k / |k|``. The log-decay of head ``h``
  and key channel ``i`` is ``g = -exp(A_log[h]) softplus(W_f2 W_f1 x +
  dt_bias)``, the step ``beta = sigmoid(W_b x)``. A head's state ``S (d_k,
  d_v)`` is zero at a document's first token and
  ``S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T``,
  ``o_t = S_t^T q_t``; then ``W_o [RMSNorm_head(o) * sigmoid(W_g2 W_g1 x +
  b_g)]``.
* **The recurrence** runs in chunks: ``fedtpu.ops.kda_scan`` has the chunked
  form (``kda_scan``, this repo's own: one inverse of a unit lower triangular
  matrix a chunk and head, no exponential of a positive number ever taken),
  the two Mosaic kernels that run it on a TPU with a head's state in the
  chip's memory, and the rule between them (``fused_scan_applies``).
  ``kda_fused_scan`` among the statistics says which ran.
* **Latent attention** (layers ``full_attn_layers``):
  ``fedtpu.models.layers.latent_attention`` with no query bottleneck and
  nothing rotated (``q_lora_rank`` None, ``mla_use_nope``).
* **Feed-forward**: the first ``first_k_dense_replace`` layers
  ``layers.dense_mlp``, every other ``layers.experts_mixer`` with the gated
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

from fedtpu.models.layers import (_ffn_init, bodies_at, cut_from_one_draw,
                                  dense_mlp, experts_mixer, experts_share,
                                  held_matmuls, latent_attention, rms_norm)
from fedtpu.ops import kda_scan as scan
from fedtpu.ops.kda_scan import KDA_CHUNK, KDA_SUB
from fedtpu.ops.lm_head import _head_loss, next_token_targets
from fedtpu.ops.packed_attention import attention_blocks
from fedtpu.ops.scopes import (EMBED, KDA, KDA_CONV, KDA_GATES, KDA_IN_PROJ,
                               KDA_OUT_PROJ, KDA_SCAN, LM_HEAD_LOSS)
from fedtpu.ops.ssm_passes import causal_conv, document_runs

# The start of the decay (``fla``'s, Mamba's): ``A_log = log U(1, 16)`` a
# head, ``dt_bias`` the inverse softplus of a log-uniform step in this range.
A_RANGE, DT_RANGE = (1.0, 16.0), (0.001, 0.1)
L2_EPS = 1e-6
# what counts a row, not its tokens: a padded row's is left out (rows_stats)
PER_ROW = ("padding", "fused_attention", "grouped_experts",
           "attention_blocks_computed", "attention_blocks_causal",
           "rows_computed", "kda_positions", "kda_fused_scan",
           "kda_log_decay_min", "sequences")

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


def check(cfg) -> None:
    """What the two lists and the share must satisfy."""
    layer_kinds(cfg)            # the two lists, no prediction module
    experts_share(cfg)


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


def init(key: jax.Array, cfg, param_dtype=jnp.float32):
    """N(0, 0.02) weights and selection biases, unit norm gains, KDA's own
    leaves as ``_kda_own_init`` draws them; a mixer's and a feed-forward's
    weights each cut out of one draw (``cut_from_one_draw``)."""
    count = itertools.count()
    fresh = lambda: jax.random.fold_in(key, next(count))
    ones = lambda *shape: jnp.ones(shape, param_dtype)
    weights = lambda build: cut_from_one_draw(fresh(), build, ones,
                                              param_dtype)

    def layer(mixer, ffn):
        if mixer == "kda":
            own = {**weights(functools.partial(_kda_init, cfg)),
                   **_kda_own_init(cfg, fresh(), param_dtype)}
        else:
            own = weights(functools.partial(_full_init, cfg))
        return {"mixer": own,
                "ffn": weights(functools.partial(_ffn_init, ffn, cfg))}

    h = cfg.hidden_size
    normal = lambda *shape: weights(lambda draw, _: draw(*shape))
    return {"embed": normal(cfg.vocab_size, h),
            "layers": tuple(layer(*kinds) for kinds in layer_kinds(cfg)),
            "final_norm": ones(h), "head": normal(h, cfg.vocab_size)}


# ------------------------------------------------------------------- KDA
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
    fused = scan.fused_scan_applies(t, d, d, KDA_CHUNK, KDA_SUB)
    # where the kernels read the arrays in place, the gates on their tiles
    tiles = functools.partial(_head_tiles, heads=heads,
                              rows=scan.SUBLANES if fused else 1)
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
            o = scan.kda_scan(by_head(q), by_head(k), by_head(v), by_head(g),
                              beta, run, KDA_CHUNK, compute_dtype)
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
        out, stats = latent_attention(cfg, compute_dtype, h, layer["mixer"],
                                      segs, None), {}
    h = h + out
    if ffn == "dense":
        return h + dense_mlp(cfg, compute_dtype, h, layer["ffn"]), stats
    out, routed = experts_mixer(cfg, compute_dtype, h, layer["ffn"], segs,
                                eps=cfg.rms_norm_eps)
    return h + out, {**stats, **routed}


def _zero_stats(cfg):
    zero = jnp.float32(0.0)
    return {"expert_load": jnp.zeros((cfg.n_routed_experts,), jnp.int32),
            "assignments_held": zero, "rows_computed": zero,
            "rows_held_computed": zero, "kda_positions": zero,
            "kda_fused_scan": zero, "kda_restarts": zero}


def sequence_stats(params, row, cfg, compute_dtype=jnp.float32):
    """One packed row ``(2, T)`` through the model: every language model's
    sums and the held experts', and this stack's own,
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
    full = sum(mixer == "full" for mixer, _ in kinds)
    _, fused, grouped = bodies_at(
        t, heads, cfg.qk_nope_head_dim + cfg.qk_rope_head_dim, cfg.v_head_dim,
        compute_dtype, scaled=True,
        experts=(held_matmuls(cfg, t)
                 if any(ffn == "experts" for _, ffn in kinds) else None))
    fused = full > 0 and fused
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
            **attention_blocks(segs, fused, full), **stats}
