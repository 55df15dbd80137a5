"""What two or more language models compose out of ``fedtpu.ops``.

A model file imports ``fedtpu.ops`` and this module and never another model;
what only one model uses stays in that model. Everything here works on one
packed sequence (``(2, T)`` int32 token and segment ids, 0 marking padding).

* **Norm, positions, the initializer.** ``rms_norm``, ``layer_norm``,
  ``_rope``, ``segment_positions``, ``INIT_STD``, ``cut_from_one_draw``.
* **An expert layer that holds a share** (``experts_mixer``: the hybrid
  stack's ``relu^2`` experts, and the gated ones of the four-stream and the
  delta-rule stack). The layer is told ``experts_held`` and
  ``first_expert``: it scores and selects over ALL routed experts (sigmoid
  scores in float32, a selection bias no gradient reaches, the top
  ``num_experts_per_tok`` renormalised and scaled: ``route``) and computes,
  droplessly, exactly the assignments of real tokens that fall on the
  experts it holds, and the shared expert for every token; what the absent
  experts would have added is left out. On one chip there is no exchange.
  The assignments are sorted held-first
  (``ops.grouped_matmul.sorted_assignments``) and the first ``rows`` of them
  go through ``grouped_matmul``. A buffer has a static size, the worst case
  is every assignment and the mean is ``held / routed`` of them: so the
  buffer is one BLOCK of rows at 8/3 of the mean (``held_block_rows``) and a
  loop runs as many blocks as this step's held assignments fill, its trips
  read from the groups' sizes: one on nearly every step, all of them if
  every token chose only experts held here. Exact whatever the skew, and
  the worst case costs only when it happens. The tiled grouped kernels
  visit no tile past the last group, so the empty rows cost the dispatch's
  gathers and scatter-adds alone; the TPU's ``lax.ragged_dot`` takes time by
  the buffer's rows, filled or not (PERF.md section 6, PR 32). The
  statistics count ``rows_computed`` against ``assignments_held``. A loop of
  that kind has no transpose, so the function has its own differentiation
  rule (``held_experts``): the backward pass runs the same trips and
  differentiates each block inside its trip, adding up the weights'
  gradients (one pass over them a block: the price of the static buffer).
* **Grouped-query attention without positions** (``attention_mixer``: the
  hybrid stack's ``*`` layer, and with the sigmoid gate on its context the
  delta-rule stack's softmax layer where the config asks for one).
* **Latent attention** (``latent_attention``; ``transformers``'
  ``DeepseekV3Attention``): the query through a bottleneck of
  ``q_lora_rank`` behind an RMSNorm, keys and values through one of
  ``kv_lora_rank`` behind another; a head's query and key are
  ``qk_nope_head_dim`` columns without positions beside
  ``qk_rope_head_dim`` with RoPE (interleaved pairs, YaRN's frequencies),
  the rotary part of the key one vector shared by all heads; the value is
  ``v_head_dim`` wide. Scores are scaled by ``(nope + rope)^-1/2 mscale^2``
  and the core (``ops.packed_attention.attention_core``) takes the two
  widths. And the plain gated MLP layer beside it (``dense_mlp``).
* **A model's one wrapper and one question**: ``rows_stats``, ``bodies_at``.

Parameters are float32; ``compute_dtype`` is the dtype of every large
matmul's inputs; norms, the router and RoPE stay float32.
"""

from __future__ import annotations

import functools
import itertools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from fedtpu.ops import grouped_matmul as grouped_ops
from fedtpu.ops import packed_attention
from fedtpu.ops.grouped_matmul import (gather_rows, grouped_matmul,
                                       sorted_assignments)
from fedtpu.ops.packed_attention import attention_core
from fedtpu.ops.scopes import (ATTENTION, ATTN_GATE, ATTN_LATENT, DENSE_MLP,
                               EXPERT_DISPATCH, EXPERTS, RECOMPUTE, ROUTER,
                               SHARED_EXPERT)

_mm = functools.partial(jnp.dot, preferred_element_type=jnp.float32)


INIT_STD = 0.02


def rms_norm(x, gain, eps):
    x = x.astype(jnp.float32)
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def layer_norm(x, gain, bias, eps):
    """LayerNorm over the last axis, float32: mean and variance taken, a
    gain and a bias."""
    x = x.astype(jnp.float32)
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return (x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
            * gain + bias)


def segment_positions(segs):
    """Position of each token within its segment: 0 at every token whose
    segment id differs from the one before it."""
    idx = jnp.arange(segs.shape[0], dtype=jnp.int32)
    starts = jnp.concatenate([jnp.ones((1,), bool), segs[1:] != segs[:-1]])
    return idx - lax.cummax(jnp.where(starts, idx, 0))


def _rope(x, pos, theta, inv=None):
    """Rotate-half RoPE over all of the last axis; x ``(T, heads, d)``.
    ``inv (d / 2,)``: the frequencies, where a model scales its own."""
    d = x.shape[-1]
    if inv is None:
        inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


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


# --------------------------------------- experts of which a share is held
# The held assignments are computed in blocks of whole tiles of this many
# rows (grouped_matmul.GROUPED_ROW_TILE, what the tiled grouped kernels need).
HELD_ROW_TILE = 256


def experts_share(cfg) -> tuple:
    """``(experts held, first expert)`` of this chip; 0 held = all."""
    held = cfg.experts_held or cfg.n_routed_experts
    if not 0 <= cfg.first_expert <= cfg.n_routed_experts - held:
        raise ValueError(
            f"experts [{cfg.first_expert}, {cfg.first_expert + held}) are "
            f"not among the {cfg.n_routed_experts} the router scores")
    return held, cfg.first_expert


def held_block_rows(assignments: int, share: float) -> int:
    """The rows of one block of the held-assignments buffer, for
    ``assignments`` in all of which ``share`` are held on average: whole
    tiles, 8/3 of the mean. A layer's share moves with the draw of the
    router and the step's tokens (at the published widths a layer held 0.5
    to 1.8 of the mean over a seed's steps, which of the four by the seed):
    at a third over the mean most steps of some seeds took a second block
    and none of others', and a round's time moved by 4% with the seed."""
    tile = HELD_ROW_TILE
    return min(-(-assignments // tile) * tile,
               max(tile, -(-int(assignments * share * 8 / 3) // tile) * tile))


def _experts_init(cfg, normal, ones, key, dtype):
    h, i, s = (cfg.hidden_size, cfg.moe_intermediate_size,
               cfg.moe_shared_expert_intermediate_size)
    held, _ = experts_share(cfg)
    return {"norm": ones(h), "router": normal(h, cfg.n_routed_experts),
            "router_bias": normal(cfg.n_routed_experts),
            "up": normal(held, h, i), "down": normal(held, i, h),
            "shared_up": normal(h, s), "shared_down": normal(s, h)}


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


def route(x, router_w, bias, top_k: int, norm_topk_prob: bool, scale: float):
    """``(gates (T, k) float32, experts (T, k) int32)``: sigmoid scores over
    every expert in float32; the top k of ``score + bias`` are chosen and
    weigh by their SCORE, renormalised and scaled. ``bias`` only picks."""
    logits = jnp.dot(x.astype(jnp.float32), router_w.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    _, experts = lax.top_k(scores + lax.stop_gradient(
        bias.astype(jnp.float32)), top_k)
    gates = jnp.take_along_axis(scores, experts, axis=-1)
    if norm_topk_prob:
        gates = gates / (gates.sum(axis=-1, keepdims=True) + 1e-20)
    return gates * scale, experts.astype(jnp.int32)


def _activation(products):
    """An expert's activation from its first matmuls' products: ``relu(up)^2``
    of one (this tower's), ``silu(gate) * up`` of two (the gated form)."""
    if len(products) == 1:
        return jnp.square(jax.nn.relu(products[0]))
    gate, up = products
    return jax.nn.silu(gate) * up


def _held_block(x, weights, gates, order, sizes, block, rows: int,
                per_token: int, compute_dtype):
    """What rows ``[block * rows, (block + 1) * rows)`` of the sorted
    assignments add to the layer's output, ``(T, H)`` float32: the held
    assignments among them, each its expert's output times its gate."""
    cast = lambda arr: arr.astype(compute_dtype)
    start = block * rows
    with jax.named_scope(EXPERT_DISPATCH):
        taken = lax.dynamic_slice_in_dim(order, start, rows)
        # the groups' rows that fall inside this block
        ends = jnp.cumsum(sizes)
        inside = (jnp.clip(ends, start, start + rows)
                  - jnp.clip(ends - sizes, start, start + rows))
        # rows past the block's last group: ``lax.ragged_dot`` defines their
        # output as zero, but the TPU's kernel (and the tiled one) visits no
        # row past the last group and leaves there what memory held, forward
        # and in both gradients. Each product's rows are cut to the filled
        # ones by a select (which a stray infinity cannot pass, as a product
        # with zero would), going in and coming out, so that the transposes
        # cut them too.
        filled = (jnp.arange(rows) < inside.sum())[:, None]
        only_filled = lambda rows_: jnp.where(filled, rows_, 0)
        xs = only_filled(gather_rows(cast(x), taken, per_token))
        weigh = jnp.take(gates, taken)
    with jax.named_scope(EXPERTS):
        *into, down = weights
        act = _activation([only_filled(grouped_matmul(xs, cast(w), inside))
                           for w in into])
        ys = only_filled(grouped_matmul(cast(act), cast(down), inside))
    with jax.named_scope(EXPERT_DISPATCH):
        return jnp.zeros(x.shape, jnp.float32).at[taken // per_token].add(
            ys * weigh[:, None])


def held_blocks(sizes, rows: int):
    """How many blocks of ``rows`` the held assignments fill."""
    return (sizes.sum() + rows - 1) // rows


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def held_experts(x, weights, gates, order, sizes, rows: int, per_token: int,
                 compute_dtype):
    """``sum over held assignments of gate * expert_e(x)``, ``(T, H)``
    float32. ``x (T, H)`` float32; ``weights`` the held experts' matrices,
    ``(up (held, H, I), down (held, I, H))`` of ``down relu(up x)^2`` or
    ``(gate, up, down)`` of the gated ``down (silu(gate x) * up x)``;
    ``gates (T * per_token,)`` every assignment's gate,
    token-major; ``order`` (padded to whole blocks), ``sizes (held,)`` from
    ``sorted_assignments`` with the held assignments first. A loop over as
    many blocks of ``rows`` as hold them, its trips read from ``sizes``:
    reverse mode only, under a rule of its own, because a loop of that kind
    has no transpose."""
    block = functools.partial(_held_block, x, weights, gates, order, sizes,
                              rows=rows, per_token=per_token,
                              compute_dtype=compute_dtype)
    return lax.fori_loop(0, held_blocks(sizes, rows),
                         lambda i, out: out + block(i),
                         jnp.zeros(x.shape, jnp.float32))


def _held_experts_fwd(x, weights, gates, order, sizes, rows, per_token,
                      compute_dtype):
    out = held_experts(x, weights, gates, order, sizes, rows, per_token,
                       compute_dtype)
    return out, (x, weights, gates, order, sizes)


def _held_experts_bwd(rows, per_token, compute_dtype, residuals, g):
    x, weights, gates, order, sizes = residuals

    def step(i, grads):
        # a block is differentiated inside its own trip: what it keeps for
        # its backward pass lives and dies there
        with jax.named_scope(RECOMPUTE):
            _, pull = jax.vjp(
                lambda *primals: _held_block(
                    *primals, order, sizes, i, rows=rows, per_token=per_token,
                    compute_dtype=compute_dtype), x, weights, gates)
        return jax.tree.map(jnp.add, grads, pull(g))

    grads = lax.fori_loop(0, held_blocks(sizes, rows), step,
                          jax.tree.map(jnp.zeros_like, (x, weights, gates)))
    return (*grads, None, None)


held_experts.defvjp(_held_experts_fwd, _held_experts_bwd)


def experts_mixer(cfg, compute_dtype, h, layer, segs, eps=None):
    """``(mixer(RMSNorm(h)), statistics)`` of one expert layer: this chip's
    share of the routed sum, and the shared expert. A layer that has
    ``gate`` and ``shared_gate`` beside ``up`` and ``down`` holds gated
    experts (``_ffn_init``'s), one without them ``relu^2`` ones
    (``_experts_init``'s); ``eps`` is the pre-norm's where it is not the
    config's ``layer_norm_epsilon``."""
    t = h.shape[0]
    top_k, routed = cfg.num_experts_per_tok, cfg.n_routed_experts
    held, first_expert = experts_share(cfg)
    cast = lambda arr: arr.astype(compute_dtype)
    gated = "gate" in layer
    with jax.named_scope(ROUTER):
        x = rms_norm(h, layer["norm"],
                     cfg.layer_norm_epsilon if eps is None else eps)
        gates, experts = route(x, layer["router"], layer["router_bias"], top_k,
                               cfg.norm_topk_prob, cfg.routed_scaling_factor)
    with jax.named_scope(EXPERT_DISPATCH):
        # an assignment's group: the held expert's own index, or one past
        # them for an expert that lives elsewhere and for padding, which is
        # routed nowhere; sorted, the held ones come first
        flat = experts.reshape(-1)
        real = jnp.repeat(segs > 0, top_k)
        local = flat - first_expert
        here = real & (local >= 0) & (local < held)
        order, sizes = sorted_assignments(jnp.where(here, local, held),
                                          held + 1)
        sizes = sizes[:held]
        load = jnp.zeros((routed,), jnp.int32).at[flat].add(
            real.astype(jnp.int32))
        rows = held_block_rows(t * top_k, held / routed)
        total, computed = sizes.sum(), held_blocks(sizes, rows) * rows
        # counted, not derived: the held assignments the sort put inside
        # the blocks that are computed (all of them, or something is broken)
        covered = (jnp.take(here, order)
                   & (jnp.arange(order.shape[0]) < computed)).sum()
        order = jnp.pad(order, (0, -order.shape[0] % rows))
    weights = ((layer["gate"], layer["up"], layer["down"]) if gated
               else (layer["up"], layer["down"]))
    out = held_experts(x, weights, gates.reshape(-1), order, sizes, rows,
                       top_k, compute_dtype)
    with jax.named_scope(SHARED_EXPERT):
        xc = cast(x)
        into = ("shared_gate", "shared_up") if gated else ("shared_up",)
        act = _activation([_mm(xc, cast(layer[name])) for name in into])
        out = out + _mm(cast(act), cast(layer["shared_down"]))
    return out, {"expert_load": load,
                 "assignments_held": total.astype(jnp.float32),
                 "rows_computed": computed.astype(jnp.float32),
                 "rows_held_computed": covered.astype(jnp.float32)}


# ------------------------------------------------ grouped-query attention
def attention_mixer(cfg, compute_dtype, h, layer, segs, eps=None):
    """``(mixer(RMSNorm(h)), {})`` of one grouped-query softmax layer
    without positions: query head ``i`` attends key-value head ``i // (heads
    / kv heads)``. A layer that has ``gate`` (``use_gqa_gate``) multiplies
    the context by ``sigmoid(W_g x)``, a number a head and channel, float32,
    before ``W_o``; ``eps`` is the pre-norm's where it is not the config's
    ``layer_norm_epsilon``."""
    t = h.shape[0]
    heads, kv, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                     cfg.head_dim)
    cast = lambda arr: arr.astype(compute_dtype)
    with jax.named_scope(ATTENTION):
        x = cast(rms_norm(h, layer["norm"],
                          cfg.layer_norm_epsilon if eps is None else eps))
        q = _mm(x, cast(layer["q"])).reshape(t, heads, hd)
        # the core's bodies take one head count: each key-value head is
        # repeated for the query heads that share it
        k, v = (jnp.repeat(_mm(x, cast(layer[name])).reshape(t, kv, hd),
                           heads // kv, axis=1) for name in ("k", "v"))
        ctx = attention_core(q, k, v, segs, compute_dtype).reshape(
            t, heads * hd)
        if "gate" in layer:
            with jax.named_scope(ATTN_GATE):
                ctx = ctx * jax.nn.sigmoid(_mm(x, cast(layer["gate"])))
        out = _mm(cast(ctx), cast(layer["o"]))
    return out, {}


# ------------------------------------------------------- latent attention
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
    """``W_down(silu(W_gate x) * W_up x)`` of ``x = RMSNorm(u)``. A layer
    that has ``gate_up`` holds the two first matrices as one, ``[gate | up]``
    (one product, then cut in two), and one that has ``norm_bias`` stands
    behind a LayerNorm (``cfg.layer_norm_eps``)."""
    cast = lambda arr: arr.astype(compute_dtype)
    with jax.named_scope(DENSE_MLP):
        if "norm_bias" in layer:
            x = cast(layer_norm(u, layer["norm"], layer["norm_bias"],
                                cfg.layer_norm_eps))
        else:
            x = cast(rms_norm(u, layer["norm"], cfg.rms_norm_eps))
        if "gate_up" in layer:
            gate, up = jnp.split(_mm(x, cast(layer["gate_up"])), 2, axis=-1)
            act = jax.nn.silu(gate) * up
        else:
            act = (jax.nn.silu(_mm(x, cast(layer["gate"])))
                   * _mm(x, cast(layer["up"])))
        return _mm(cast(act), cast(layer["down"]))


# ------------------------------------- the wrapper, and the rules asked once
def rows_stats(sequence_stats, per_row: tuple):
    """``stats_fn(params, x, mask, cfg, compute_dtype)``: a model's
    ``sequence_stats`` summed over the rows ``x (N, 2, T)`` whose ``mask`` is
    1, one row at a time; ``per_row`` are those a padded row must not count."""
    def stats_fn(params, x, mask, cfg, compute_dtype=jnp.float32):
        def one(row_and_mask):
            row, m = row_and_mask
            # a padded row is all segment 0: nothing of it is counted
            stats = sequence_stats(params, row * m.astype(row.dtype), cfg,
                                   compute_dtype)
            return {**stats, **{k: stats[k] * m for k in per_row}}

        if x.shape[0] == 1:
            return one((x[0], mask[0]))
        stats = lax.map(one, (x, mask))
        return jax.tree.map(lambda a: a.sum(axis=0), stats)

    return stats_fn


def held_matmuls(cfg, t: int) -> tuple:
    """``bodies_at``'s ``experts`` of a layer that holds a share, at ``t``
    positions: its block's rows, the experts held, the two widths."""
    held, _ = experts_share(cfg)
    return (held_block_rows(t * cfg.num_experts_per_tok,
                            held / cfg.n_routed_experts),
            held, cfg.hidden_size, cfg.moe_intermediate_size)


def bodies_at(t: int, heads: int, qk_width: int, v_width: int, compute_dtype,
              scaled: bool = False, experts=None) -> tuple:
    """``(wide, fused, grouped)`` of a sequence of ``t`` positions, for the
    counters: the head width the attention core's rule is asked at (as
    ``attention_core`` pads: a head of two widths, or one with a scale of
    its own, ``scaled``), whether its fused body will run, and whether the
    expert matmuls' kernels will, ``experts`` the ``(rows, groups, k, m)`` of
    their buffer and weights, or None without an expert layer. Shapes are
    static: the rules are read once a trace, as their own callers read them."""
    shaped = lambda *shape: jax.ShapeDtypeStruct(shape, compute_dtype)
    wide = qk_width
    if scaled or qk_width != v_width:
        wide = packed_attention.padded_head_width(
            shaped(t, heads, qk_width), shaped(t, heads, v_width))
    core = shaped(t, heads, wide)
    fused = packed_attention.fused_attention_applies(core, core, core)
    grouped = False
    if experts is not None:
        rows, groups, k, m = experts
        grouped = all(grouped_ops.grouped_matmul_applies(
            shaped(rows, a), shaped(groups, a, b))
            for a, b in ((k, m), (m, k)))
    return wide, fused, grouped
