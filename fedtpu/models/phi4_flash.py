"""Phi-4-mini-flash (SambaY): a decoder-hybrid-decoder stack on packed
sequences. Mamba-1 mixers and window attention in a self-decoder, one
Mamba-1 layer whose scan output is kept as the *memory* and one full
attention layer whose keys and values are kept, then a cross-decoder of Gated
Memory Units, which read the memory and compute no recurrence, and
cross-attention, which reads the kept keys and values.

The stack is the one ``config.json`` of microsoft/Phi-4-mini-flash-reasoning
(``model_type: phi4flash``) defines, described in arXiv:2507.06607 with Samba
(arXiv:2406.07522), Mamba (arXiv:2312.00752), YOCO (arXiv:2405.05254) and the
Differential Transformer (arXiv:2410.05258) behind it; what only the model's
own code fixes is listed in the benchmark's configuration file under
``assumed``. ``u`` is a layer's normed input, ``T`` positions, documents
independent within a packed row. Every layer is

    x <- x + Mixer(LN_1(x)),    x <- x + FF(LN_2(x)),

LayerNorm with gain and bias (``layer_norm_eps``); after the last layer a
final LayerNorm and ``logits = h E^T`` with ``E`` the embedding (tied, no
bias). No positions anywhere.

* **Feed-forward**: ``[g | v] = W_1 u`` (``2 x intermediate_size``), ``FF =
  W_2 (v * SiLU(g))``, no bias (``layers.dense_mlp``).
* **Mamba-1 mixer** (``d_inner = mamba_expand x hidden``, state ``N =
  mamba_d_state``, ``mamba_d_conv`` taps, rank ``R = mamba_dt_rank`` or
  ``ceil(hidden / 16)``): ``[x | z] = W_in u``; ``x <- SiLU(conv(x) + b_c)``,
  depthwise and causal, reading zeros before a document's first token; ``[dr
  | B_t | C_t] = W_x x`` (``R + 2N``); ``dl = softplus(W_d dr + b_d)``; ``A =
  -exp(A_log)`` ``(d_inner, N)``; ``h_t = exp(dl_t (x) A) * h_{t-1} + (dl_t *
  x_t) (x) B_t``, ``h = 0`` at a document's first token; ``y_t = h_t C_t + D
  * x_t``; ``Mixer = W_out (y * SiLU(z))``. The layer that produces the
  memory hands on ``m = y`` (with the skip, before the gate). The recurrence
  is ``fedtpu.ops.selective_scan``: two tiled kernels on a TPU, a chunk of
  the row at a time in XLA everywhere else.
* **Gated Memory Unit**: ``Mixer = W_2 (m * SiLU(W_1 u))``.
* **Attention**, self: ``[q | k | v] = W_qkv u + b``, heads of ``d = hidden /
  heads``, scale ``d^-1/2``; key ``s`` is allowed for query ``t`` iff same
  document and ``0 <= t - s`` (full) or ``0 <= t - s < sliding_window``.
  **Differential form**: heads are taken in pairs (heads ``2i`` and ``2i +
  1``; a key-value pair serves ``heads / kv heads`` query pairs); a pair has
  ``q1, q2, k1, k2`` of ``d`` and a value of ``2d`` (the pair's two values
  side by side); ``o = (1 - l0) RMSNorm_2d(softmax(q1 k1^T) V - l
  softmax(q2 k2^T) V)``, ``l = exp(lq1 . lk1) - exp(lq2 . lk2) + l0``, ``l0 =
  0.8 - 0.6 exp(-0.3 i)`` with ``i`` the layer's PUBLISHED index, the four
  vectors of ``d`` and the sub-norm's gain learned a layer; ``Mixer = W_o o +
  b_o``. Two calls of ``ops.packed_attention.attention_core`` with one value
  of width ``2d``, then the combination in float32.
* **Cross-attention**: ``q = W_q u + b`` of its own; ``k``, ``v`` the full
  layer's (after their projection); the same differential form under the
  full mask.

What is this repo's own:

* **A layer's kind follows from its published index** (``layer_kinds``):
  ``layers_held`` names the layers this chip holds by that index, so a cut
  in depth keeps each layer's kind and its ``l0``.
* **The layer loop carries a second kind of value.** A layer takes and hands
  on ``shared``, a dict that is empty until the memory layer puts ``memory``
  in and the full layer ``keys`` and ``values``; every layer is recomputed
  from its inputs in the backward pass (``jax.checkpoint`` a layer) and the
  cotangents of the three come summed from every layer that read them.
* **The tied head** (``ops.lm_head.tied_lookup``, ``_tied_head_loss``): one
  leaf, one gradient.

Parameters are float32, a leaf a layer (``params["layers"][i]`` is
``{"mixer", "ffn"}``). ``compute_dtype`` is the dtype of every large
product's inputs; the scan, the step, the convolution, every norm, the
softmaxes' statistics and the differential combination are float32.
"""

from __future__ import annotations

import functools
import itertools
import math

import jax
import jax.numpy as jnp

from fedtpu.models.layers import (bodies_at, cut_from_one_draw, dense_mlp,
                                  layer_norm, rms_norm, segment_positions)
from fedtpu.ops import selective_scan as scan
from fedtpu.ops import ssm_passes
from fedtpu.ops.lm_head import (_tied_head_loss, next_token_targets,
                                tied_lookup)
from fedtpu.ops.packed_attention import attention_blocks, attention_core
from fedtpu.ops.scopes import (ATTENTION, ATTN_CROSS, ATTN_FULL, ATTN_WINDOW,
                               DIFF_COMBINE, EMBED, GMU, LM_HEAD_LOSS,
                               S6_CONV, S6_GATE, S6_PROJ, S6_SCAN, SSM)

# The start of a Mamba-1 mixer (arXiv:2312.00752's code): ``A_log = log(1 ..
# N)`` a channel, ``D = 1``, the step's bias the inverse softplus of a
# log-uniform step in this range (floored).
DT_RANGE, DT_FLOOR = (0.001, 0.1), 1e-4
# the four lambda vectors of differential attention start N(0, 0.1)
LAMBDA_STD = 0.1
# what counts a row, not its tokens: a padded row's is left out (rows_stats)
PER_ROW = ("padding", "fused_attention", "attention_blocks_computed",
           "attention_blocks_causal", "s6_positions", "s6_chunked_scan",
           "s6_fused_scan", "s6_fused_conv")

_mm = functools.partial(jnp.dot, preferred_element_type=jnp.float32)
ATTENTIONS = ("window", "full", "cross")


def layer_kinds(cfg) -> tuple:
    """``(published index, kind)`` of every layer held, in order: ``"s6"``,
    ``"window"``, ``"s6_memory"``, ``"full"``, ``"gmu"`` or ``"cross"``, by
    where the index lies in the PUBLISHED depth ``num_hidden_layers``."""
    depth, period = cfg.num_hidden_layers, cfg.mb_per_layer
    held = tuple(cfg.layers_held) or tuple(range(depth))
    if list(held) != sorted(set(held)) or not 0 <= held[0] <= held[-1] < depth:
        raise ValueError(
            f"layers_held {held} are not rising indices among the {depth} "
            "published layers")
    half = depth // 2

    def kind(i):
        state_space = i % period == 0
        if i < half:
            return "s6" if state_space else "window"
        if i == half:
            # the layer at the hinge keeps its scan's output
            return "s6_memory" if state_space else "window"
        if i == half + 1:
            return "full"
        return "gmu" if state_space else "cross"

    kinds = tuple((i, kind(i)) for i in held)
    names = [k for _, k in kinds]
    for reader, source in (("gmu", "s6_memory"), ("cross", "full")):
        if reader in names and source not in names[:names.index(reader)]:
            raise ValueError(
                f"layers_held {held} hold a {reader!r} layer and no "
                f"{source!r} layer before it: what it reads is made there")
    return kinds


def widths(cfg) -> dict:
    """The widths the config implies: a head's, the mixer's inner width, the
    step's rank."""
    return {"head": cfg.hidden_size // cfg.num_attention_heads,
            "inner": cfg.mamba_expand * cfg.hidden_size,
            "rank": cfg.mamba_dt_rank or math.ceil(cfg.hidden_size / 16)}


def check(cfg) -> None:
    """What the held layers and the heads must satisfy."""
    layer_kinds(cfg)
    heads, kv = cfg.num_attention_heads, cfg.num_key_value_heads
    if heads % 2 or kv % 2 or heads % kv or cfg.hidden_size % heads:
        raise ValueError(
            f"{heads} query heads over {kv} key-value heads of hidden "
            f"{cfg.hidden_size}: differential attention takes both in pairs, "
            "the query pairs divide over the key-value pairs, and a head is "
            "hidden / heads wide")
    if not cfg.tie_word_embeddings:
        raise ValueError(
            "tie_word_embeddings is false: this stack builds the tied head "
            "only (the embedding is the head's one matrix)")
    if cfg.sliding_window < 1:
        raise ValueError(f"sliding_window {cfg.sliding_window} is no window")


# ------------------------------------------------------------------ init
def _s6_init(cfg, normal, ones):
    h, w = cfg.hidden_size, widths(cfg)
    inner, n, rank = w["inner"], cfg.mamba_d_state, w["rank"]
    return {"norm": ones(cfg.hidden_size), "in_proj": normal(h, 2 * inner),
            "x_proj": normal(inner, rank + 2 * n),
            "dt_proj": normal(rank, inner), "D": ones(inner),
            "out_proj": normal(inner, h)}


def _s6_own_init(cfg, key, dtype):
    """The mixer's leaves that are not N(0, 0.02), as arXiv:2312.00752's code
    starts them: ``A_log = log(1 .. N)`` every channel, the step's bias the
    inverse softplus of a log-uniform step, the convolution PyTorch's
    default (uniform, bound ``taps^-1/2``) and its bias zero."""
    inner, n, taps = widths(cfg)["inner"], cfg.mamba_d_state, cfg.mamba_d_conv
    u = jax.random.uniform(key, ((1 + taps) * inner,), jnp.float32)
    lo, hi = (math.log(v) for v in DT_RANGE)
    dt = jnp.maximum(jnp.exp(u[:inner] * (hi - lo) + lo), DT_FLOOR)
    return {"A_log": jnp.broadcast_to(
                jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32)),
                (inner, n)).astype(dtype),
            "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
            "conv_w": ((2.0 * u[inner:] - 1.0) * taps ** -0.5).reshape(
                taps, inner).astype(dtype),
            "conv_b": jnp.zeros((inner,), dtype)}


def _gmu_init(cfg, normal, ones):
    h, inner = cfg.hidden_size, widths(cfg)["inner"]
    return {"norm": ones(cfg.hidden_size), "in_proj": normal(h, inner),
            "out_proj": normal(inner, h)}


def _attention_init(cross, cfg, normal, ones):
    h, d = cfg.hidden_size, widths(cfg)["head"]
    kv = 0 if cross else 2 * cfg.num_key_value_heads * d
    return {"norm": ones(cfg.hidden_size),
            "q" if cross else "qkv": normal(h, h + kv),
            "o": normal(h, h), "sub_norm": ones(2 * d)}


def _attention_own_init(cross, cfg, key, dtype):
    """The biases of the projections (zero) and the four lambda vectors,
    N(0, 0.1), cut from one draw."""
    h, d = cfg.hidden_size, widths(cfg)["head"]
    kv = 0 if cross else 2 * cfg.num_key_value_heads * d
    lambdas = (LAMBDA_STD * jax.random.normal(key, (4, d))).astype(dtype)
    return {("q_bias" if cross else "qkv_bias"): jnp.zeros((h + kv,), dtype),
            "o_bias": jnp.zeros((h,), dtype),
            **dict(zip(("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"),
                       lambdas))}


def _ffn_init(cfg, normal, ones):
    h, i = cfg.hidden_size, cfg.intermediate_size
    return {"norm": ones(cfg.hidden_size), "gate_up": normal(h, 2 * i),
            "down": normal(i, h)}


def init(key: jax.Array, cfg, param_dtype=jnp.float32):
    """N(0, 0.02) weights, unit gains, zero biases, the Mamba mixers' and the
    lambdas' own starts; a mixer's and a feed-forward's weights each cut out
    of one draw (``cut_from_one_draw``); every ``norm`` is a LayerNorm's gain
    and gets its ``norm_bias`` beside it."""
    count = itertools.count()
    fresh = lambda: jax.random.fold_in(key, next(count))
    ones = lambda *shape: jnp.ones(shape, param_dtype)
    weights = lambda build: cut_from_one_draw(fresh(), build, ones,
                                              param_dtype)
    biased = lambda part: {**part, "norm_bias": jnp.zeros_like(part["norm"])}

    def mixer(kind):
        if kind in ("s6", "s6_memory"):
            return {**weights(functools.partial(_s6_init, cfg)),
                    **_s6_own_init(cfg, fresh(), param_dtype)}
        if kind == "gmu":
            return weights(functools.partial(_gmu_init, cfg))
        cross = kind == "cross"
        return {**weights(functools.partial(_attention_init, cross, cfg)),
                **_attention_own_init(cross, cfg, fresh(), param_dtype)}

    normal = lambda *shape: weights(lambda draw, _: draw(*shape))
    h = cfg.hidden_size
    return {"embed": normal(cfg.vocab_size, h),
            "layers": tuple(
                {"mixer": biased(mixer(kind)),
                 "ffn": biased(weights(functools.partial(_ffn_init, cfg)))}
                for _, kind in layer_kinds(cfg)),
            "final_norm": ones(h), "final_norm_bias": jnp.zeros((h,),
                                                                param_dtype)}


# ------------------------------------------------------------ the mixers
def _normed(cfg, u, layer):
    return layer_norm(u, layer["norm"], layer["norm_bias"], cfg.layer_norm_eps)


def s6_mixer(cfg, compute_dtype, u, layer, segs):
    """``(mixer(LN(u)), y, statistics)`` of one Mamba-1 layer; ``y (T,
    d_inner)`` is the scan's output with the ``D`` skip, before the gate:
    what the memory layer hands on."""
    t = u.shape[0]
    inner, n, rank = (widths(cfg)["inner"], cfg.mamba_d_state,
                      widths(cfg)["rank"])
    cast = lambda arr: arr.astype(compute_dtype)
    run, starts = ssm_passes.document_runs(segs)
    fused = ssm_passes.fused_conv_applies(t, cfg.mamba_d_conv, inner)
    with jax.named_scope(SSM):
        with jax.named_scope(S6_PROJ):
            proj = _mm(cast(_normed(cfg, u, layer)), cast(layer["in_proj"]))
        with jax.named_scope(S6_CONV):
            if fused:   # ``x`` read out of the product in place; the form
                # with the positions last goes out for one tile and is not
                # read (a kernel of the hybrid stack's, which wants it)
                x, _ = ssm_passes.conv_silu(
                    proj, layer["conv_w"], layer["conv_b"], run, 0, inner,
                    ssm_passes.CONV_TILE[1])
            else:
                x = jax.nn.silu(ssm_passes.causal_conv(
                    proj[:, :inner], layer["conv_w"], layer["conv_b"], run))
        with jax.named_scope(S6_PROJ):
            dr, b, c = jnp.split(_mm(cast(x), cast(layer["x_proj"])),
                                 [rank, rank + n], axis=-1)
            dl = jax.nn.softplus(_mm(cast(dr), cast(layer["dt_proj"]))
                                 + layer["dt_bias"])
        with jax.named_scope(S6_SCAN):
            y = scan.selective_scan(
                x, dl, -jnp.exp(layer["A_log"].astype(jnp.float32)), b, c, run)
        with jax.named_scope(S6_GATE):
            y = y + layer["D"] * x
            gated = y * jax.nn.silu(proj[:, inner:])
        with jax.named_scope(S6_PROJ):
            out = _mm(cast(gated), cast(layer["out_proj"]))
    return out, y, {
        "s6_positions": jnp.float32(t),
        "s6_chunked_scan": jnp.float32(scan.chunked_scan_positions(t)),
        "s6_fused_scan": jnp.float32(scan.fused_scan_positions(t, inner, n)),
        "s6_fused_conv": jnp.float32(t if fused else 0),
        "s6_restarts": (starts & (segs > 0)).sum().astype(jnp.float32)}


def gmu_mixer(cfg, compute_dtype, u, layer, memory):
    """``W_2 (memory * SiLU(W_1 LN(u)))`` of one Gated Memory Unit."""
    cast = lambda arr: arr.astype(compute_dtype)
    with jax.named_scope(SSM), jax.named_scope(GMU):
        gate = _mm(cast(_normed(cfg, u, layer)), cast(layer["in_proj"]))
        return _mm(cast(memory * jax.nn.silu(gate)), cast(layer["out_proj"]))


def _module(kind):
    """The outer scope of an attention layer of ``kind``: which of three."""
    if kind == "window":
        return jax.named_scope(ATTN_WINDOW)
    if kind == "full":
        return jax.named_scope(ATTN_FULL)
    return jax.named_scope(ATTN_CROSS)


def lambda_init(index: int) -> float:
    """``l0`` of the layer at the published ``index``."""
    return 0.8 - 0.6 * math.exp(-0.3 * index)


def attention_mixer(kind, index, cfg, compute_dtype, u, layer, segs, shared):
    """``(mixer(LN(u)), keys, values)`` of one attention layer of ``kind``
    (``"window"``, ``"full"``, ``"cross"``) at the published ``index``;
    ``keys``, ``values`` ``(T, kv heads * d)`` float32 are the layer's own
    projections, or for ``"cross"`` the full layer's out of ``shared``."""
    t, d = u.shape[0], widths(cfg)["head"]
    heads, kv = cfg.num_attention_heads, cfg.num_key_value_heads
    cast = lambda arr: arr.astype(compute_dtype)
    with _module(kind), jax.named_scope(ATTENTION):
        into = "q" if kind == "cross" else "qkv"
        proj = (_mm(cast(_normed(cfg, u, layer)), cast(layer[into]))
                + layer[into + "_bias"])
        if kind == "cross":
            q, keys, values = proj, shared["keys"], shared["values"]
        else:
            q, keys, values = jnp.split(
                proj, [heads * d, (heads + kv) * d], axis=-1)
        # heads 2i and 2i + 1 are a pair; a key-value pair serves heads / kv
        # query pairs, its two values side by side one value of 2d
        q = q.reshape(t, heads // 2, 2, d)
        k = jnp.repeat(keys.reshape(t, kv // 2, 2, d), heads // kv, axis=1)
        v = jnp.repeat(values.reshape(t, kv // 2, 2 * d), heads // kv, axis=1)
        core = functools.partial(
            attention_core, v=v, segs=segs, compute_dtype=compute_dtype,
            scale=d ** -0.5,
            window=cfg.sliding_window if kind == "window" else None)
        first, second = core(q[:, :, 0], k[:, :, 0]), core(q[:, :, 1],
                                                           k[:, :, 1])
        with jax.named_scope(DIFF_COMBINE):
            f32 = lambda name: layer[name].astype(jnp.float32)
            base = lambda_init(index)
            lam = (jnp.exp(f32("lambda_q1") @ f32("lambda_k1"))
                   - jnp.exp(f32("lambda_q2") @ f32("lambda_k2")) + base)
            ctx = (1.0 - base) * rms_norm(first - lam * second,
                                          layer["sub_norm"],
                                          cfg.layer_norm_eps)
        out = _mm(cast(ctx.reshape(t, heads * d)), cast(layer["o"]))
        return out + layer["o_bias"], keys, values


# ------------------------------------------------------------- the model
def block(kind, index, cfg, compute_dtype, h, layer, shared, segs):
    """One layer on ``h (T, C)`` float32: the mixer of its kind, then the
    feed-forward, each behind its own LayerNorm and added to the residual.
    ``shared`` holds what earlier layers keep for later ones (``memory``,
    ``keys``, ``values``, each once it exists). ``(h, shared, statistics)``."""
    stats = {}
    if kind in ("s6", "s6_memory"):
        out, y, stats = s6_mixer(cfg, compute_dtype, h, layer["mixer"], segs)
        if kind == "s6_memory":
            shared = {**shared, "memory": y}
    elif kind == "gmu":
        out = gmu_mixer(cfg, compute_dtype, h, layer["mixer"],
                        shared["memory"])
    else:
        out, keys, values = attention_mixer(
            kind, index, cfg, compute_dtype, h, layer["mixer"], segs, shared)
        if kind == "full":
            shared = {**shared, "keys": keys, "values": values}
    h = h + out
    return h + dense_mlp(cfg, compute_dtype, h, layer["ffn"]), shared, stats


def window_pairs(segs, window: int) -> dict:
    """The (query, key) pairs of a row's real tokens that causal attention
    within a document allows, and those of them a ``window`` leaves."""
    seen = (segment_positions(segs) + 1) * (segs > 0)
    return {"attention_pairs": seen.sum().astype(jnp.float32),
            "window_pairs": jnp.minimum(seen, window).sum().astype(
                jnp.float32)}


def decoder(layers, h, segs, cfg, compute_dtype):
    """The held layers on the embedded rows ``h (T, C)`` float32: ``(h
    after the last, the Mamba-1 layers' statistics)``."""
    kinds = layer_kinds(cfg)
    stats = dict.fromkeys(("s6_positions", "s6_chunked_scan", "s6_fused_scan",
                           "s6_fused_conv", "s6_restarts"), jnp.float32(0.0))
    shared = {}
    for (index, kind), layer in zip(kinds, layers):
        # recomputed from its inputs in the backward pass: one (T, C) array a
        # layer is kept, and the three shared arrays once
        h, shared, own = jax.checkpoint(functools.partial(
            block, kind, index, cfg, compute_dtype, segs=segs))(
                h, layer, shared)
        stats = {**stats, **{k: stats[k] + v for k, v in own.items()}}
    # the mean over the Mamba-1 layers: the row's positions, or 0
    mixers = max(sum(kind.startswith("s6") for _, kind in kinds), 1)
    stats.update({k: stats[k] / mixers
                  for k in ("s6_chunked_scan", "s6_fused_scan",
                            "s6_fused_conv")})
    return h, stats


def sequence_stats(params, row, cfg, compute_dtype=jnp.float32):
    """One packed row ``(2, T)`` through the model: every language model's
    sums over tokens, and this stack's own, summed over its Mamba-1 layers:
    ``s6_positions`` (positions the scan ran over), ``s6_chunked_scan``
    (those whose states were never held beyond a chunk or a kernel's block),
    ``s6_fused_scan`` (those whose scan ran in the two kernels) and
    ``s6_fused_conv`` (those whose convolution ran in the tiled kernel; of
    the three the mean over the layers, so the row's positions or 0),
    ``s6_restarts``
    (documents whose state started at zero); and of the row, ``window_pairs``
    over ``attention_pairs`` (allowed pairs under the window and without
    it). The model has no experts and hands out none of their statistics."""
    tokens, segs = row[0], row[1]
    kinds = layer_kinds(cfg)
    names = [kind for _, kind in kinds]
    t, d = tokens.shape[0], widths(cfg)["head"]
    _, fused, _ = bodies_at(t, cfg.num_attention_heads // 2, d, 2 * d,
                            compute_dtype, scaled=True)
    attends = sum(names.count(k) for k in ATTENTIONS)
    fused = attends > 0 and fused
    with jax.named_scope(EMBED):
        rows, head = tied_lookup(params["embed"], tokens)
        h = rows.astype(jnp.float32)
    h, stats = decoder(params["layers"], h, segs, cfg, compute_dtype)
    with jax.named_scope(LM_HEAD_LOSS):
        labels, valid = next_token_targets(tokens, segs)
        loss, correct = _tied_head_loss(
            layer_norm(h, params["final_norm"], params["final_norm_bias"],
                       cfg.layer_norm_eps), head, labels, valid,
            compute_dtype)
    # two calls of the core a layer, under the window's table or the full one
    blocks = jax.tree.map(
        jnp.add,
        attention_blocks(segs, fused, 2 * names.count("window"),
                         cfg.sliding_window),
        attention_blocks(segs, fused, 2 * (attends - names.count("window"))))
    return {"loss_sum": loss, "correct": correct, "count": valid.sum(),
            "tokens": (segs > 0).sum().astype(jnp.float32),
            "padding": (segs == 0).sum().astype(jnp.float32),
            "fused_attention": jnp.float32(t if fused else 0),
            **blocks, **window_pairs(segs, cfg.sliding_window), **stats}
