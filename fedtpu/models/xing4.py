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
  Around EVERY sublayer (attention and the feed-forward each have a module
  of their own, ``sublayer``) three maps are made from the token's own
  state, the sublayer reads ``H_pre X`` and the state becomes ``H_res X +
  H_post^T y``, ``H_res`` made doubly stochastic by Sinkhorn turns:
  ``fedtpu.ops.hyper_conn`` has the algebra, the three functions that are its
  definition (``hyper_mix``, ``hyper_read``, ``hyper_write``), the Mosaic
  kernels that run it on a TPU (``mix_read``, ``write``) and the rule between
  them (``hyper_passes_apply``). ``hc_fused`` among the statistics says which
  ran.
* **Latent attention**: ``fedtpu.models.layers.latent_attention``, a query
  bottleneck, interleaved RoPE at YaRN's frequencies (``yarn_inv_freq``) and
  ``mscale`` in the scale (``attention_scale``); ``attention_padded_width``
  says at which head width the tiled core ran.
* **Feed-forward.** The first ``first_k_dense_replace`` layers are a plain
  gated MLP of ``intermediate_size``; every other layer routes over
  ``n_routed_experts`` gated experts (sigmoid scores, a selection bias no
  gradient reaches, the top ``num_experts_per_tok`` renormalised and scaled)
  beside ``n_shared_experts`` shared ones, and holds this chip's share of
  them (``fedtpu.models.layers.experts_mixer`` with the gated activation).
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
(``jax.checkpoint`` a layer): the ``(n, T, C)`` float32
streams are what a layer keeps. Parameters are float32, a leaf a layer;
``compute_dtype`` is the dtype of every large matmul's inputs. The router,
every norm, RoPE and the whole residual path (its projection at ``HIGHEST``
precision) stay float32.
"""

from __future__ import annotations

import functools
import itertools

import jax
import jax.numpy as jnp
from jax import lax

from fedtpu.models.layers import (_ffn_init, bodies_at, cut_from_one_draw,
                                  dense_mlp, experts_mixer, experts_share,
                                  held_matmuls, latent_attention, rms_norm,
                                  segment_positions)
from fedtpu.ops import hyper_conn as hyper_passes
from fedtpu.ops.lm_head import _head_loss, next_token_targets
from fedtpu.ops.packed_attention import attention_blocks
from fedtpu.ops.scopes import EMBED, HYPER_CONN, LM_HEAD_LOSS, MTP, MTP_PROJ

KINDS = ("dense", "experts")
# The start of a residual module (assumed: the published config has no key
# for any of it). The scalars start at the paper's 0.01; the projections are
# drawn so that the dynamic logits ``alpha x' phi`` have this standard
# deviation at any size, and the static ones from N(0, 1), the stream-to-
# stream matrix's leaning on its diagonal: four streams that differ, and a
# mix that both its static and its dynamic part move.
HC_ALPHA, HC_DYNAMIC_STD, HC_RES_DIAGONAL = 0.01, 0.2, 2.0

# what counts a row, not its tokens: a padded row's is left out (rows_stats)
PER_ROW = ("padding", "fused_attention", "grouped_experts",
           "attention_blocks_computed", "attention_blocks_causal",
           "attention_padded_width", "hc_mix_positions", "hc_fused",
           "rows_computed", "hc_sinkhorn_residual", "sequences")

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


def check(cfg) -> None:
    """What the depth and the share must satisfy before anything is built."""
    layer_kinds(cfg)            # the depth, the leading dense layers
    experts_share(cfg)


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


def init(key: jax.Array, cfg, param_dtype=jnp.float32):
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
def sinkhorn_residual(res):
    """The largest ``|rowsum - 1|`` or ``|colsum - 1|`` of ``(n, n, T)``."""
    return jnp.maximum(jnp.abs(res.sum(axis=1) - 1.0).max(),
                       jnp.abs(res.sum(axis=0) - 1.0).max())


def sublayer(cfg, x, module, fn):
    """One sublayer ``fn(u) -> (y, statistics)`` on the streams: ``(streams,
    statistics, the module's Sinkhorn residual)``. Where
    ``hyper_passes_apply`` the streams are passed over by the kernels, named
    for their direction so that their ``op_name`` keeps it."""
    fused = hyper_passes.hyper_passes_apply(x)
    if fused:
        n = x.shape[0]
        with jax.named_scope(HYPER_CONN):
            u, z, x = hyper_passes.mix_read(
                x, module["phi"], jnp.broadcast_to(module["alpha"][0], (n,)),
                module["bias"][:n], cfg.rms_norm_eps)
            _, post, res = hyper_passes._hyper_maps(z, module, cfg)
    else:
        pre, post, res = hyper_passes.hyper_mix(x, module, cfg)
        u = hyper_passes.hyper_read(x, pre)
    y, stats = fn(u)
    with jax.named_scope(HYPER_CONN):
        off = lax.stop_gradient(sinkhorn_residual(res))
        if fused:
            x = hyper_passes.write(x, y, post, res)
    if not fused:
        x = hyper_passes.hyper_write(x, y, post, res)
    return x, stats, off


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
        ffn = lambda u: experts_mixer(
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


def sequence_stats(params, row, cfg, compute_dtype=jnp.float32):
    """One packed row ``(2, T)`` through the model: every language model's
    sums and the held experts' (``loss_sum``, ``correct`` and ``count`` are
    the MAIN loss's), and this stack's own: ``mtp_loss_sum``
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
    has_experts = "experts" in kinds or cfg.num_nextn_predict_layers > 0
    wide, fused, grouped = bodies_at(
        t, heads, cfg.qk_nope_head_dim + cfg.qk_rope_head_dim, cfg.v_head_dim,
        compute_dtype, scaled=True,
        experts=held_matmuls(cfg, t) if has_experts else None)
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
            "hc_fused": jnp.float32(t if hyper_passes.hyper_passes_apply(
                jax.ShapeDtypeStruct((n, t, cfg.hidden_size), jnp.float32))
                else 0),
            "sequences": jnp.float32(1.0),
            **attention_blocks(segs, fused, len(kinds) + len(params["mtp"])),
            **module_stats, **stats}
