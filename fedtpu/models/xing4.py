"""Xing4.0: latent attention and sparse experts on a four-stream residual,
with a multi-token-prediction module, on packed sequences.

The stack is the one ``config.json`` of XingChen-AGI/Xing4.0-29B-A4B
(``model_type: xing4_0``) defines. Its modelling code is not public on this
machine; every layer is written from the code and the papers its keys name,
and what that leaves to inference is listed in the benchmark's configuration
file under ``assumed``.

* **The residual path** (mHC, arXiv:2512.24880; keys ``hc_mult``,
  ``hc_sinkhorn_iters``, ``hc_eps``, ``mhc_h_res_clamp_min/max``). A token's
  state is ``hc_mult`` streams of ``hidden_size``, ``X (n, C)``, the
  embedding repeated on the way in and the streams summed on the way out.
  Around EVERY sublayer ``F`` (attention and the feed-forward each have a
  module of their own) three maps are made from the token's own state: with
  ``x' = flatten(X) / sqrt(mean(flatten(X)^2) + eps)`` (no gain),
  ``H_pre = sigmoid(a_pre x' phi_pre + b_pre)`` (n), ``H_post = 2
  sigmoid(a_post x' phi_post + b_post)`` (n) and ``H_res = SK(a_res mat(x'
  phi_res) + b_res)`` (n x n), where ``SK`` exponentiates the clipped
  logits and normalises columns, then rows, ``hc_sinkhorn_iters`` times
  (each sum plus ``hc_eps``), which makes the matrix doubly stochastic. The
  sublayer reads ``u = H_pre X``, computes ``y = F(RMSNorm(u))`` and the
  state becomes ``H_res X + H_post^T y``. All of it float32; ``hyper_mix``,
  ``hyper_read`` and ``hyper_write`` are the three functions and JAX
  differentiates through the loop. The streams lie ``(n, T, C)``, a stream a
  plane, and the maps ``(n, T)`` / ``(n, n, T)``, positions on the lanes: a
  ``(T, 4, 4)`` array would fill a thirty-second of its tiles.
  **Which body runs where.** The three functions are the definition: XLA's
  passes, the body on a CPU and at shapes without tiles, and the oracle of
  the kernels' tests. Where ``hyper_passes_apply`` (a TPU, float32 streams,
  ``C`` whole lane tiles, ``T`` whole row tiles, a tile within the chip's own
  memory at this ``n``: the published widths at any row of whole tiles) the
  same algebra at the same precision runs as Mosaic kernels under
  differentiation rules of their own, ``fedtpu.ops.hyper_conn``: ``mix_read``
  (the norm, the logits' product and the read from ONE visit of the streams)
  and ``write``, and their two transposes, the second of which writes the
  streams' whole cotangent once; the scale and bias, ``H_post``, the clip
  and the Sinkhorn turns stay in XLA (``_hyper_maps``, both bodies' own).
  ``hc_fused`` among the statistics says which ran.
* **Latent attention** (``transformers``' ``DeepseekV3Attention``): the
  query through a bottleneck of ``q_lora_rank`` behind an RMSNorm, keys and
  values through one of ``kv_lora_rank`` behind another; a head's query and
  key are ``qk_nope_head_dim`` columns without positions beside
  ``qk_rope_head_dim`` with RoPE (interleaved pairs, YaRN's frequencies), the
  rotary part of the key one vector shared by all heads; the value is
  ``v_head_dim`` wide. Scores are scaled by ``(nope + rope)^-1/2 mscale^2``.
  The core is ``olmoe.attention_core``, which takes the two widths and the
  scale: its tiled body runs the head padded with zeros to one width
  (``attention_padded_width`` says which), its XLA body as it is.
* **Feed-forward.** The first ``first_k_dense_replace`` layers are a plain
  gated MLP of ``intermediate_size``; every other layer routes over
  ``n_routed_experts`` gated experts (sigmoid scores, a selection bias no
  gradient reaches, the top ``num_experts_per_tok`` renormalised and scaled:
  ``nemotron_h.route``) beside ``n_shared_experts`` shared ones, and holds
  this chip's share of them (``nemotron_h.experts_mixer`` with the gated
  activation: the same held-first sort, blocks and differentiation rule).
* **Multi-token prediction** (DeepSeek-V3, arXiv:2412.19437 section 2.2;
  ``num_nextn_predict_layers`` 0 or 1). The module takes the main stack's
  summed streams before its final norm and the NEXT token's embedding,
  ``h' = [RMSNorm(h) ; RMSNorm(Emb(t_{i+1}))] M``, runs one more block of
  the expert kind on streams started from ``h'``, and predicts ``t_{i+2}``
  through its own final norm and the SHARED embedding and head. Its loss is
  a mean over its own valid positions (a document's last TWO tokens fall
  out) and enters the sum that is differentiated times ``task.MTP_LOSS_WEIGHT``.
  The statistics keep the two apart: ``loss_sum`` / ``count`` are the main
  loss's, ``mtp_loss_sum`` / ``mtp_count`` the module's.

Every layer is recomputed from its input in the backward pass
(``jax.checkpoint`` a layer, as ``nemotron_h``): the ``(n, T, C)`` float32
streams are what a layer keeps. Parameters are float32, a leaf a layer;
``compute_dtype`` is the dtype of every large matmul's inputs. The router,
every norm, RoPE and the whole residual path (its projection at ``HIGHEST``
precision) stay float32.
"""

from __future__ import annotations

import functools
import itertools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from fedtpu.models import nemotron_h, olmoe
from fedtpu.models.nemotron_h import experts_share, held_block_rows
from fedtpu.models.olmoe import (ATTENTION, ATTN_LATENT, DENSE_MLP, EMBED,
                                 HC_SINKHORN, HYPER_CONN, INIT_STD,
                                 LM_HEAD_LOSS, MTP, MTP_PROJ, _head_loss,
                                 _rope, attention_core, next_token_targets,
                                 rms_norm, segment_positions)
from fedtpu.ops import hyper_conn as hyper_passes

KINDS = ("dense", "experts")
# The start of a residual module (assumed: the published config has no key
# for any of it). The scalars start at the paper's 0.01; the projections are
# drawn so that the dynamic logits ``alpha x' phi`` have this standard
# deviation at any size, and the static ones from N(0, 1), the stream-to-
# stream matrix's leaning on its diagonal: four streams that differ, and a
# mix that both its static and its dynamic part move.
HC_ALPHA, HC_DYNAMIC_STD, HC_RES_DIAGONAL = 0.01, 0.2, 2.0

_mm = functools.partial(jnp.dot, preferred_element_type=jnp.float32)


def layer_kinds(cfg) -> tuple:
    """The kind of every layer of the main stack, in order."""
    dense, layers = cfg.first_k_dense_replace, cfg.num_hidden_layers
    if not 0 <= dense <= layers:
        raise ValueError(f"first_k_dense_replace {dense} is not within the "
                         f"{layers} layers")
    if cfg.num_nextn_predict_layers not in (0, 1):
        raise ValueError(
            f"num_nextn_predict_layers {cfg.num_nextn_predict_layers}: the "
            "stack builds no or one multi-token-prediction module")
    return ("dense",) * dense + ("experts",) * (layers - dense)


def yarn_inv_freq(cfg) -> np.ndarray:
    """RoPE's frequencies ``(qk_rope_head_dim / 2,)`` under YaRN, as
    ``transformers.modeling_rope_utils._compute_yarn_parameters`` blends the
    extrapolated and the interpolated ones (its attention factor is 1 here:
    ``mscale == mscale_all_dim``)."""
    dim, base = cfg.qk_rope_head_dim, cfg.rope_theta
    factor = cfg.rope_scaling_factor
    original = cfg.rope_scaling_original_max_position_embeddings

    def correction_dim(rotations):
        return (dim * math.log(original / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(cfg.rope_scaling_beta_fast)), 0)
    high = min(math.ceil(correction_dim(cfg.rope_scaling_beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    pos_freqs = base ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    extrapolated, interpolated = 1.0 / pos_freqs, 1.0 / (factor * pos_freqs)
    return (interpolated * ramp + extrapolated * (1 - ramp)).astype(np.float32)


def attention_scale(cfg) -> float:
    """``(nope + rope)^-1/2 mscale^2``, ``mscale = 0.1 mscale_all_dim
    ln(factor) + 1`` (``DeepseekV3Attention.__init__``)."""
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    if cfg.rope_scaling_mscale_all_dim and cfg.rope_scaling_factor > 1:
        mscale = (0.1 * cfg.rope_scaling_mscale_all_dim
                  * math.log(cfg.rope_scaling_factor) + 1.0)
        scale *= mscale * mscale
    return scale


# ------------------------------------------------------------------ init
def _hyper_init(cfg, key, dtype):
    """A residual module from one draw of standard normals."""
    n, width = cfg.hc_mult, cfg.hc_mult * cfg.hidden_size
    logits = n * (n + 2)
    flat = jax.random.normal(key, (logits * width + logits,), dtype)
    static = flat[logits * width:]
    return {
        # a row a logit: n of H_pre, n of H_post, n * n of H_res, row-major
        "phi": (HC_DYNAMIC_STD / (HC_ALPHA * width ** 0.5)
                * flat[:logits * width].reshape(logits, width)),
        "alpha": jnp.full((3,), HC_ALPHA, dtype),
        "bias": static.at[2 * n:].add(
            HC_RES_DIAGONAL * jnp.eye(n, dtype=dtype).reshape(-1)),
    }


def _attention_init(cfg, normal, ones):
    h, heads = cfg.hidden_size, cfg.num_attention_heads
    nope, rope, v = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    return {"norm": ones(h), "q_a": normal(h, cfg.q_lora_rank),
            "q_a_norm": ones(cfg.q_lora_rank),
            "q_b": normal(cfg.q_lora_rank, heads * (nope + rope)),
            "kv_a": normal(h, cfg.kv_lora_rank + rope),
            "kv_a_norm": ones(cfg.kv_lora_rank),
            "kv_b": normal(cfg.kv_lora_rank, heads * (nope + v)),
            "o": normal(heads * v, h)}


def _ffn_init(kind, cfg, normal, ones):
    h = cfg.hidden_size
    if kind == "dense":
        i = cfg.intermediate_size
        return {"norm": ones(h), "gate": normal(h, i), "up": normal(h, i),
                "down": normal(i, h)}
    i, s = cfg.moe_intermediate_size, (cfg.moe_intermediate_size
                                       * cfg.n_shared_experts)
    held, _ = experts_share(cfg)
    return {"norm": ones(h), "router": normal(h, cfg.n_routed_experts),
            "router_bias": normal(cfg.n_routed_experts),
            "gate": normal(held, h, i), "up": normal(held, h, i),
            "down": normal(held, i, h), "shared_gate": normal(h, s),
            "shared_up": normal(h, s), "shared_down": normal(s, h)}


def cut_from_one_draw(key, build, ones, param_dtype):
    """``build(normal, ones)`` with its ``normal(*shape)`` leaves cut, in the
    order they are asked for, out of ONE N(0, 0.02) vector drawn from
    ``key``: a draw a leaf was a hundred random-bit programs and 26 s of the
    init's compile for the TPU, which every job pays before its first round
    (15 s so)."""
    shapes = []
    jax.eval_shape(lambda: build(
        lambda *shape: shapes.append(shape) or jnp.zeros(shape), ones))
    flat = INIT_STD * jax.random.normal(
        key, (sum(map(math.prod, shapes)),), param_dtype)
    ends = list(itertools.accumulate(map(math.prod, shapes)))
    cut = iter(zip([0, *ends], ends))
    return build(lambda *shape: flat[slice(*next(cut))].reshape(shape), ones)


def xing4_init(key: jax.Array, cfg, param_dtype=jnp.float32):
    """N(0, 0.02) weights and selection biases, unit norm gains, the
    residual modules as ``_hyper_init`` draws them. Each kind's layers are a
    tuple under the kind's name; ``mtp`` holds the prediction modules. The
    weights of a block's attention are cut out of ONE draw and those of its
    feed-forward out of another (``cut_from_one_draw``)."""
    count = itertools.count()
    fresh = lambda: jax.random.fold_in(key, next(count))
    ones = lambda *shape: jnp.ones(shape, param_dtype)
    weights = lambda build: cut_from_one_draw(fresh(), build, ones,
                                              param_dtype)

    def layer(kind):
        return {"attn": weights(functools.partial(_attention_init, cfg)),
                "attn_hc": _hyper_init(cfg, fresh(), param_dtype),
                "ffn": weights(functools.partial(_ffn_init, kind, cfg)),
                "ffn_hc": _hyper_init(cfg, fresh(), param_dtype)}

    h = cfg.hidden_size
    normal = lambda *shape: weights(lambda draw, _: draw(*shape))
    params = {"embed": normal(cfg.vocab_size, h),
              **{kind: [] for kind in KINDS}}
    for kind in layer_kinds(cfg):
        params[kind].append(layer(kind))
    params.update({kind: tuple(params[kind]) for kind in KINDS},
                  final_norm=ones(h), head=normal(h, cfg.vocab_size))
    params["mtp"] = tuple(
        {"h_norm": ones(h), "e_norm": ones(h), "proj": normal(2 * h, h),
         "block": layer("experts"), "final_norm": ones(h)}
        for _ in range(cfg.num_nextn_predict_layers))
    return params


# ------------------------------------------------------ the residual path
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


def sinkhorn_residual(res):
    """The largest ``|rowsum - 1|`` or ``|colsum - 1|`` of ``(n, n, T)``."""
    return jnp.maximum(jnp.abs(res.sum(axis=1) - 1.0).max(),
                       jnp.abs(res.sum(axis=0) - 1.0).max())


def hyper_passes_apply(x) -> bool:
    """Whether the tiled bodies of a residual module's passes over the
    streams (``fedtpu.ops.hyper_conn``: ``mix_read`` and ``write``, a
    differentiation rule each) exist for the streams ``x (n, T, C)`` where
    the program is being built: a TPU (the PROCESS's backend, as
    ``olmoe.fused_attention_applies`` reads it), float32 streams, ``C`` whole
    lane tiles, ``T`` whole row tiles, the tile within the chip's own memory
    at this ``n``. ``hyper_mix``, ``hyper_read`` and ``hyper_write`` are the
    definitions and the body everywhere else."""
    return (jax.default_backend() == "tpu" and x.dtype == jnp.float32
            and hyper_passes.tiles_apply(*x.shape))


def sublayer(cfg, x, module, fn):
    """One sublayer ``fn(u) -> (y, statistics)`` on the streams: ``(streams,
    statistics, the module's Sinkhorn residual)``. Where
    ``hyper_passes_apply`` the streams are passed over by the kernels, named
    for their direction so that their ``op_name`` keeps it."""
    fused = hyper_passes_apply(x)
    if fused:
        n = x.shape[0]
        with jax.named_scope(HYPER_CONN):
            u, z, x = hyper_passes.mix_read(
                x, module["phi"], jnp.broadcast_to(module["alpha"][0], (n,)),
                module["bias"][:n], cfg.rms_norm_eps)
            _, post, res = _hyper_maps(z, module, cfg)
    else:
        pre, post, res = hyper_mix(x, module, cfg)
        u = hyper_read(x, pre)
    y, stats = fn(u)
    with jax.named_scope(HYPER_CONN):
        off = lax.stop_gradient(sinkhorn_residual(res))
        if fused:
            x = hyper_passes.write(x, y, post, res)
    if not fused:
        x = hyper_write(x, y, post, res)
    return x, stats, off


# ------------------------------------------------------- latent attention
def _pairs_apart(x):
    """``[x0, x2, ..., x1, x3, ...]`` of the last axis: the family's
    ``rope_interleave`` reads a pair as neighbours and rotates them as
    rotate-half does once they are apart (the same order for q and k, so no
    score changes)."""
    d = x.shape[-1]
    return jnp.swapaxes(x.reshape(*x.shape[:-1], d // 2, 2), -1, -2).reshape(
        x.shape)


def latent_attention(cfg, compute_dtype, u, layer, segs, pos):
    """``attention(RMSNorm(u))`` of one packed sequence, ``(T, C)`` float32.
    Two things a config may ask for besides (Kimi-Linear's does both): no
    query bottleneck (``q_lora_rank`` None: the query is ONE projection,
    ``layer["q"]``) and no positions (``mla_use_nope``: the ``rope`` columns
    are kept, a key's one vector for all heads still, and nothing is
    rotated; ``pos`` is not read)."""
    t, heads, eps = u.shape[0], cfg.num_attention_heads, cfg.rms_norm_eps
    nope, rope, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    cast = lambda arr: arr.astype(compute_dtype)
    with jax.named_scope(ATTENTION):
        with jax.named_scope(ATTN_LATENT):
            x = cast(rms_norm(u, layer["norm"], eps))
            if cfg.q_lora_rank is None:
                q = _mm(x, cast(layer["q"]))
            else:
                cq = rms_norm(_mm(x, cast(layer["q_a"])), layer["q_a_norm"],
                              eps)
                q = _mm(cast(cq), cast(layer["q_b"]))
            q = q.reshape(t, heads, nope + rope)
            ckv, k_r = jnp.split(_mm(x, cast(layer["kv_a"])),
                                 [cfg.kv_lora_rank], axis=-1)
            ckv = rms_norm(ckv, layer["kv_a_norm"], eps)
            k_n, v = jnp.split(
                _mm(cast(ckv), cast(layer["kv_b"])).reshape(
                    t, heads, nope + vd), [nope], axis=-1)
            if cfg.mla_use_nope:
                turn = lambda a: a
            else:
                inv = jnp.asarray(yarn_inv_freq(cfg))
                turn = lambda a: _rope(_pairs_apart(a), pos, cfg.rope_theta,
                                       inv)
                q = jnp.concatenate([q[..., :nope], turn(q[..., nope:])],
                                    axis=-1)
            # the rotary part of the key is one vector for all heads
            k = jnp.concatenate(
                [k_n, jnp.broadcast_to(turn(k_r[:, None]), (t, heads, rope))],
                axis=-1)
        ctx = attention_core(q, k, v, segs, compute_dtype,
                             scale=attention_scale(cfg))
        with jax.named_scope(ATTN_LATENT):
            return _mm(cast(ctx.reshape(t, heads * vd)), cast(layer["o"]))


def dense_mlp(cfg, compute_dtype, u, layer):
    """``W_down(silu(W_gate x) * W_up x)`` of ``x = RMSNorm(u)``."""
    cast = lambda arr: arr.astype(compute_dtype)
    with jax.named_scope(DENSE_MLP):
        x = cast(rms_norm(u, layer["norm"], cfg.rms_norm_eps))
        act = jax.nn.silu(_mm(x, cast(layer["gate"]))) * _mm(x, cast(layer["up"]))
        return _mm(cast(act), cast(layer["down"]))


def block(kind, cfg, compute_dtype, x, layer, segs, pos):
    """One layer on the streams ``x (n, T, C)``: latent attention, then the
    feed-forward of its kind, each behind a residual module of its own.
    ``(streams, statistics)``."""
    x, _, off_a = sublayer(
        cfg, x, layer["attn_hc"],
        lambda u: (latent_attention(cfg, compute_dtype, u, layer["attn"],
                                    segs, pos), {}))
    if kind == "dense":
        ffn = lambda u: (dense_mlp(cfg, compute_dtype, u, layer["ffn"]), {})
    else:
        ffn = lambda u: nemotron_h.experts_mixer(
            cfg, compute_dtype, u, layer["ffn"], segs, eps=cfg.rms_norm_eps)
    x, stats, off_f = sublayer(cfg, x, layer["ffn_hc"], ffn)
    return x, {**stats, "hc_sinkhorn_residual": jnp.maximum(off_a, off_f)}


# ------------------------------------------------------------- the model
def mtp_targets(tokens, segs):
    """``(labels (T,), valid (T,) float32)`` of the prediction module: the
    token two ahead where it and the one between belong to the same
    document; padding and each document's last two tokens are out."""
    ahead = lambda a, by: jnp.concatenate([a[by:], jnp.zeros((by,), a.dtype)])
    same = (segs > 0) & (ahead(segs, 1) == segs) & (ahead(segs, 2) == segs)
    return ahead(tokens, 2), same.astype(jnp.float32)


def _zero_stats(cfg):
    zero = jnp.float32(0.0)
    return {"expert_load": jnp.zeros((cfg.n_routed_experts,), jnp.int32),
            "assignments_held": zero, "rows_computed": zero,
            "rows_held_computed": zero, "hc_sinkhorn_residual": zero}


def xing4_sequence_stats(params, row, cfg, compute_dtype=jnp.float32):
    """One packed row ``(2, T)`` through the model: ``nemotron_h_sequence_
    stats``'s sums without the state-space layer's (``loss_sum``, ``correct``
    and ``count`` are the MAIN loss's), and this stack's own: ``mtp_loss_sum``
    and ``mtp_count`` (the prediction module's summed loss and valid targets;
    absent where the model has no module), ``hc_mix_positions`` (positions
    times residual modules mixed), ``hc_fused`` (the positions where the
    modules' passes ran in the tiled kernels, ``hyper_passes_apply``; 0
    where the definitions ran), ``hc_sinkhorn_residual`` (the largest
    ``|rowsum - 1|``, ``|colsum - 1|`` of any ``H_res`` of the sequence),
    ``attention_padded_width`` (positions times the head width the tiled
    core ran at; 0 where the XLA body ran) and ``sequences`` (1)."""
    tokens, segs = row[0], row[1]
    kinds = layer_kinds(cfg)
    t, heads, n = tokens.shape[0], cfg.num_attention_heads, cfg.hc_mult
    wide = olmoe.padded_head_width(
        jax.ShapeDtypeStruct((t, heads, cfg.qk_nope_head_dim
                              + cfg.qk_rope_head_dim), compute_dtype),
        jax.ShapeDtypeStruct((t, heads, cfg.v_head_dim), compute_dtype))
    core = jax.ShapeDtypeStruct((t, heads, wide), compute_dtype)
    # the rules between the bodies, read as their own callers read them
    fused = olmoe.fused_attention_applies(core, core, core)
    held, _ = experts_share(cfg)
    rows = held_block_rows(t * cfg.num_experts_per_tok,
                           held / cfg.n_routed_experts)
    has_experts = "experts" in kinds or cfg.num_nextn_predict_layers > 0
    grouped = has_experts and all(olmoe.grouped_matmul_applies(
        jax.ShapeDtypeStruct((rows, k), compute_dtype),
        jax.ShapeDtypeStruct((held, k, m), compute_dtype))
        for k, m in ((cfg.hidden_size, cfg.moe_intermediate_size),
                     (cfg.moe_intermediate_size, cfg.hidden_size)))
    pos = segment_positions(segs)
    cast = lambda arr: arr.astype(compute_dtype)
    streams = lambda h: jnp.broadcast_to(h, (n, *h.shape))

    def run(kind, x, layer):
        # recomputed from its input in the backward pass: the (n, T, C)
        # streams are what a layer keeps
        return jax.checkpoint(functools.partial(
            block, kind, cfg, compute_dtype, segs=segs, pos=pos))(x, layer)

    def add(stats, own):
        return {**stats, **{k: stats[k] + v for k, v in own.items()
                            if k != "hc_sinkhorn_residual"},
                "hc_sinkhorn_residual": jnp.maximum(
                    stats["hc_sinkhorn_residual"],
                    own["hc_sinkhorn_residual"])}

    with jax.named_scope(EMBED):
        x = streams(jnp.take(params["embed"], tokens, axis=0).astype(
            jnp.float32))
    stats, seen = _zero_stats(cfg), dict.fromkeys(KINDS, 0)
    for kind in kinds:
        x, own = run(kind, x, params[kind][seen[kind]])
        seen[kind] += 1
        stats = add(stats, own)
    with jax.named_scope(HYPER_CONN):
        h = x.sum(axis=0)
    with jax.named_scope(LM_HEAD_LOSS):
        labels, valid = next_token_targets(tokens, segs)
        loss, correct = _head_loss(
            rms_norm(h, params["final_norm"], cfg.rms_norm_eps),
            params["head"], labels, valid, compute_dtype)
    module_stats = {}
    for module in params["mtp"]:
        with jax.named_scope(MTP):
            with jax.named_scope(EMBED):
                ahead = jnp.take(params["embed"], labels, axis=0).astype(
                    jnp.float32)
            with jax.named_scope(MTP_PROJ):
                both = jnp.concatenate(
                    [rms_norm(h, module["h_norm"], cfg.rms_norm_eps),
                     rms_norm(ahead, module["e_norm"], cfg.rms_norm_eps)],
                    axis=-1)
                x = streams(_mm(cast(both), cast(module["proj"])))
            x, own = run("experts", x, module["block"])
            stats = add(stats, own)
            with jax.named_scope(HYPER_CONN):
                hm = x.sum(axis=0)
            with jax.named_scope(LM_HEAD_LOSS):
                labels2, valid2 = mtp_targets(tokens, segs)
                mtp_loss, _ = _head_loss(
                    rms_norm(hm, module["final_norm"], cfg.rms_norm_eps),
                    params["head"], labels2, valid2, compute_dtype)
        module_stats = {"mtp_loss_sum": mtp_loss, "mtp_count": valid2.sum()}
    modules = 2 * (len(kinds) + len(params["mtp"]))
    return {"loss_sum": loss, "correct": correct, "count": valid.sum(),
            "tokens": (segs > 0).sum().astype(jnp.float32),
            "padding": (segs == 0).sum().astype(jnp.float32),
            "fused_attention": jnp.float32(t if fused else 0),
            "grouped_experts": jnp.float32(t if grouped else 0),
            "attention_padded_width": jnp.float32(t * wide if fused else 0),
            "hc_mix_positions": jnp.float32(t * modules),
            "hc_fused": jnp.float32(t if hyper_passes_apply(
                jax.ShapeDtypeStruct((n, t, cfg.hidden_size), jnp.float32))
                else 0),
            "sequences": jnp.float32(1.0),
            **olmoe.attention_blocks(segs, fused,
                                     len(kinds) + len(params["mtp"])),
            **module_stats, **stats}


def xing4_stats(params, x, mask, cfg, compute_dtype=jnp.float32):
    """``xing4_sequence_stats`` summed over the rows ``x (N, 2, T)`` whose
    ``mask`` is 1, one row at a time."""
    def one(row_and_mask):
        row, m = row_and_mask
        stats = xing4_sequence_stats(
            params, row * m.astype(row.dtype), cfg, compute_dtype)
        return {**stats, **{k: stats[k] * m for k in (
            "padding", "fused_attention", "grouped_experts",
            "attention_blocks_computed", "attention_blocks_causal",
            "attention_padded_width", "hc_mix_positions", "hc_fused",
            "rows_computed", "hc_sinkhorn_residual", "sequences")}}

    if x.shape[0] == 1:
        return one((x[0], mask[0]))
    stats = lax.map(one, (x, mask))
    return jax.tree.map(lambda a: a.sum(axis=0), stats)
