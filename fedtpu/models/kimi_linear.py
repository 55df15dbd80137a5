"""The delta-rule stack of two models: a gated delta-rule recurrence (KDA)
three layers in four and softmax attention without positions in the fourth,
a held share of sparse experts, on packed sequences.

* **Kimi-Linear** (``kind="kimi_linear"``; ``config.json`` of moonshotai/
  Kimi-Linear-48B-A3B-Instruct, ``model_type: kimi_linear``): the fourth
  layer is latent attention, the lists ``kda_layers`` / ``full_attn_layers``
  are 1-based as published, a leading dense layer, ``beta = sigmoid``.
* **Solar-Open2** (``kind="solar_open2"``; ``config.json`` of upstage/
  Solar-Open2-250B, ``model_type: solar_open2``): the fourth layer is gated
  grouped-query attention and LEADS the period, the list ``gqa_layers`` is
  0-based as published and every other layer is KDA, no dense layer, ``beta =
  2 sigmoid`` (``kda_allow_neg_eigval``).

The KDA layer is ``fla.layers.kda.KimiDeltaAttention`` and its recurrence
``fla.ops.kda.naive.naive_recurrent_kda``, which Kimi-Linear's own
``modeling_kimi.py`` follows. What a config leaves to the code is listed in
the benchmark's configuration files under ``assumed``. Every layer is
``h + Mixer(RMSNorm(h))``, then ``h + FFN(RMSNorm(h))``.

* **The KDA mixer** (``kda_num_heads`` heads, keys and
  values ``kda_head_dim`` wide). ``q, k, v = SiLU(conv(W x))``, the
  convolution depthwise, causal, ``short_conv_kernel_size`` taps, no bias,
  zeros before a document's first token (``ssm_passes.causal_conv``); a head's
  ``q <- q / |q| d^-1/2`` and ``k <- k / |k|``. The log-decay of head ``h``
  and key channel ``i`` is ``g = -exp(A_log[h]) softplus(W_f2 W_f1 x +
  dt_bias)``, the step ``beta = sigmoid(W_b x)``, or twice that where
  ``kda_allow_neg_eigval``: ``I - beta k k^T`` then has eigenvalues in (-1,
  1] and the state can flip sign along ``k``. A head's state ``S (d_k,
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
* **The "full" layer**, by the list that names it (``_gqa``; nothing in this
  module reads ``kind``): by the two 1-based lists latent attention
  (``fedtpu.models.layers.latent_attention`` with no query bottleneck and
  nothing rotated: ``q_lora_rank`` None, ``mla_use_nope``), by ``gqa_layers``
  grouped-query attention without positions (``layers.attention_mixer``, the
  hybrid stack's) whose context is multiplied by ``sigmoid(W_g x)`` before
  ``W_o`` (``use_gqa_gate``).
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

from fedtpu.models.layers import (_ffn_init, attention_mixer, bodies_at,
                                  cut_from_one_draw, dense_mlp, experts_mixer,
                                  experts_share, held_matmuls,
                                  latent_attention, rms_norm)
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
           "kda_log_decay_min", "kda_step_max", "sequences")

_mm = functools.partial(jnp.dot, preferred_element_type=jnp.float32)


def _gqa(cfg) -> bool:
    """Whether the stack's "full" layer is the grouped-query one: where the
    configuration names its layers by ``gqa_layers`` (0-based, every other
    layer KDA); the two 1-based lists name them where it is empty. The
    configuration decides, whatever the model is called."""
    return bool(cfg.gqa_layers)


def layer_kinds(cfg) -> tuple:
    """``(mixer, feed-forward)`` of every layer, in order: ``"kda"`` or
    ``"full"``, ``"dense"`` or ``"experts"``. Kimi-Linear publishes two
    1-based lists, ``kda_layers`` and ``full_attn_layers``, which must name
    every layer once; Solar-Open2 publishes ``gqa_layers``, 0-based, and
    every layer it does not name is KDA. The stack builds no multi-token-
    prediction module."""
    layers = cfg.num_hidden_layers
    kda, full = tuple(cfg.kda_layers), tuple(cfg.full_attn_layers)
    if _gqa(cfg):       # the two 1-based lists are not read
        full = tuple(i + 1 for i in cfg.gqa_layers)
        if len(set(full)) != len(full) or not all(
                1 <= i <= layers for i in full):
            raise ValueError(
                f"gqa_layers {tuple(cfg.gqa_layers)} does not name layers "
                f"among the {layers} (0-based, as Solar-Open2 publishes "
                "them), each once")
        kda = tuple(i for i in range(1, layers + 1) if i not in full)
    elif sorted(kda + full) != list(range(1, layers + 1)):
        raise ValueError(
            f"kda_layers {kda} and full_attn_layers {full} do not name "
            f"each of the {layers} layers once (1-based, as Kimi-Linear "
            "publishes them), and gqa_layers (0-based, as Solar-Open2 "
            "publishes it) is empty: a layer is a KDA mixer or the full "
            "layer, latent attention by the two lists and grouped-query "
            "attention by gqa_layers, and no other kind is built")
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
    """What the lists, the share and the heads must satisfy."""
    layer_kinds(cfg)            # the lists, no prediction module
    experts_share(cfg)
    if _gqa(cfg) and cfg.num_attention_heads % cfg.num_key_value_heads:
        raise ValueError(
            f"{cfg.num_attention_heads} query heads do not divide over "
            f"{cfg.num_key_value_heads} key-value heads")


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


def _gqa_init(cfg, normal, ones):
    h = cfg.hidden_size
    q, kv = (cfg.num_attention_heads * cfg.head_dim,
             cfg.num_key_value_heads * cfg.head_dim)
    gate = {"gate": normal(h, q)} if cfg.use_gqa_gate else {}
    return {"norm": ones(h), "q": normal(h, q), "k": normal(h, kv),
            "v": normal(h, kv), **gate, "o": normal(q, h)}


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
            own = weights(functools.partial(
                _gqa_init if _gqa(cfg) else _full_init, cfg))
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
            if cfg.kda_allow_neg_eigval:
                beta = 2.0 * beta
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
    real = segs > 0
    steps = jnp.where(real[:, None], lax.stop_gradient(beta), 0.0)
    count = lambda mask: mask.sum().astype(jnp.float32)
    return out, {"kda_positions": jnp.float32(t),
                 "kda_fused_scan": jnp.float32(t if fused else 0),
                 "kda_restarts": count(starts & real),
                 "kda_head_steps": heads * count(real),
                 "kda_steps_over_one": count(steps > 1.0),
                 "kda_step_max": steps.max(),
                 "kda_log_decay_min": deepest}


# ------------------------------------------------------------- the model
def block(kinds, cfg, compute_dtype, h, layer, segs):
    """One layer on ``h (T, C)`` float32: the mixer of its kind, then the
    feed-forward of its kind, each behind its own pre-norm and added to the
    residual. ``(h, statistics)``."""
    mixer, ffn = kinds
    if mixer == "kda":
        out, stats = kda_mixer(cfg, compute_dtype, h, layer["mixer"], segs)
    elif _gqa(cfg):
        out, stats = attention_mixer(cfg, compute_dtype, h, layer["mixer"],
                                     segs, eps=cfg.rms_norm_eps)
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
            "kda_fused_scan": zero, "kda_restarts": zero,
            "kda_head_steps": zero, "kda_steps_over_one": zero}


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
    ``exp(-G)`` would have overflowed float32), ``kda_head_steps`` (real
    positions times heads: the delta rule's steps), ``kda_steps_over_one``
    (those of them whose ``beta`` is over 1, where ``I - beta k k^T`` has a
    negative eigenvalue: none unless ``kda_allow_neg_eigval``),
    ``kda_step_max`` (the largest ``beta`` at a real position) and
    ``sequences`` (1)."""
    tokens, segs = row[0], row[1]
    kinds = layer_kinds(cfg)
    t, heads = tokens.shape[0], cfg.num_attention_heads
    full = sum(mixer == "full" for mixer, _ in kinds)
    # the core's head as the full layer of this kind hands it over
    core = (dict(qk_width=cfg.head_dim, v_width=cfg.head_dim) if _gqa(cfg)
            else dict(qk_width=cfg.qk_nope_head_dim + cfg.qk_rope_head_dim,
                      v_width=cfg.v_head_dim, scaled=True))
    _, fused, grouped = bodies_at(
        t, heads, compute_dtype=compute_dtype, **core,
        experts=(held_matmuls(cfg, t)
                 if any(ffn == "experts" for _, ffn in kinds) else None))
    fused = full > 0 and fused
    with jax.named_scope(EMBED):
        h = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)
    stats, deepest, largest = (_zero_stats(cfg), jnp.float32(0.0),
                               jnp.float32(0.0))
    for kind, layer in zip(kinds, params["layers"]):
        # recomputed from its input in the backward pass: one (T, C) array a
        # layer is kept
        h, own = jax.checkpoint(functools.partial(
            block, kind, cfg, compute_dtype, segs=segs))(h, layer)
        deepest = jnp.minimum(deepest, own.pop("kda_log_decay_min", 0.0))
        largest = jnp.maximum(largest, own.pop("kda_step_max", 0.0))
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
            "kda_step_max": largest,
            **attention_blocks(segs, fused, full), **stats}
