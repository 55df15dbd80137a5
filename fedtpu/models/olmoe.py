"""OLMoE: a sparse-expert decoder language model on packed sequences.

The block is allenai's OLMoE-1B-7B as its ``config.json`` and modelling code
state it: pre-norm residual layers; multi-head attention whose projected
queries and keys pass an RMSNorm over the WHOLE projection (before the split
into heads), then RoPE over every dimension of a head in the rotate-half
convention; a router that takes the softmax of its logits over all experts in
float32, keeps the top ``num_experts_per_tok`` and (``norm_topk_prob`` false)
does NOT renormalise them; SiLU-gated experts ``down(silu(gate(x)) * up(x))``;
a final RMSNorm and an untied linear head. No biases anywhere.

What is this repo's own:

* **Packed rows.** An input row is ``(2, T)`` int32: token ids and segment
  ids, 0 marking padding. Attention is causal within a segment, positions
  restart at each segment, and the next-token loss is masked at padding and
  at the last token of each document, so two documents packed into one row
  give the losses and gradients of the two alone.
* **Dropless experts.** Every (token, expert) assignment is computed: the
  assignments are sorted by expert and the three expert matmuls run as
  grouped matmuls over the uneven groups (``jax.lax.ragged_dot``). There is
  no capacity factor and no auxiliary loss (HF's default
  ``output_router_logits=False`` computes none).
* **A loss that never holds the logits whole.** The head and the
  cross-entropy run over chunks of the sequence under ``jax.checkpoint``:
  one chunk's ``[chunk, vocab]`` float32 logits exist at a time, forward and
  backward.
* **Scanned layers.** Layer parameters carry a leading layers axis and the
  stack is a ``lax.scan``, so depth 16 compiles as depth 1 does.

Parameters are float32. ``compute_dtype`` (bfloat16 in the shipped presets)
is the dtype of every large matmul's inputs; accumulation, norms, softmax,
the router (logits at ``HIGHEST`` precision, softmax, top-k) and the loss
stay float32.

The second-level ``jax.named_scope``s (``LAYER_SCOPES``) are what
``analysis.program.program_scopes`` puts a compiled program's operations down
to under the round's stages.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

EMBED, ATTENTION, ROUTER, EXPERT_DISPATCH, EXPERTS, LM_HEAD_LOSS = (
    "embed", "attention", "router", "expert_dispatch", "experts",
    "lm_head_loss")
LAYER_SCOPES = (EMBED, ATTENTION, ROUTER, EXPERT_DISPATCH, EXPERTS,
                LM_HEAD_LOSS)
# Rows of the sequence whose logits exist at one time in the loss.
LOSS_CHUNK = 512
INIT_STD = 0.02


def olmoe_init(key: jax.Array, cfg, param_dtype=jnp.float32):
    """N(0, 0.02) weights (the family's initializer range), unit norm
    gains; layer leaves carry a leading ``num_hidden_layers`` axis."""
    h, e, i, v, n = (cfg.hidden_size, cfg.num_experts, cfg.intermediate_size,
                     cfg.vocab_size, cfg.num_hidden_layers)
    keys = iter(jax.random.split(key, 10))

    def normal(shape):
        return INIT_STD * jax.random.normal(next(keys), shape, param_dtype)

    ones = lambda *shape: jnp.ones(shape, param_dtype)
    return {
        "embed": normal((v, h)),
        "layers": {
            "attn_norm": ones(n, h), "q": normal((n, h, h)),
            "k": normal((n, h, h)), "v": normal((n, h, h)),
            "o": normal((n, h, h)), "q_norm": ones(n, h),
            "k_norm": ones(n, h), "mlp_norm": ones(n, h),
            "router": normal((n, h, e)), "gate": normal((n, e, h, i)),
            "up": normal((n, e, h, i)), "down": normal((n, e, i, h)),
        },
        "final_norm": ones(h),
        "head": normal((h, v)),
    }


def rms_norm(x, gain, eps):
    x = x.astype(jnp.float32)
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def segment_positions(segs):
    """Position of each token within its segment: 0 at every token whose
    segment id differs from the one before it."""
    idx = jnp.arange(segs.shape[0], dtype=jnp.int32)
    starts = jnp.concatenate([jnp.ones((1,), bool), segs[1:] != segs[:-1]])
    return idx - lax.cummax(jnp.where(starts, idx, 0))


def _rope(x, pos, theta):
    """Rotate-half RoPE over all of the last axis; x ``(T, heads, d)``."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def route(x, router_w, top_k: int, norm_topk_prob: bool):
    """``(gates (T, k) float32, experts (T, k) int32)``: softmax over every
    expert's logit in float32, the top k of it, unrenormalised unless the
    config says otherwise."""
    logits = jnp.dot(x.astype(jnp.float32), router_w.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    gates, experts = lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
    if norm_topk_prob:
        gates = gates / gates.sum(axis=-1, keepdims=True)
    return gates, experts.astype(jnp.int32)


def _block(cfg, compute_dtype, h, layer, segs, pos):
    """One decoder layer on one packed sequence ``h (T, H)``; returns the
    new ``h`` and the tokens each expert was given (padding left out)."""
    t, hid = h.shape
    heads = cfg.num_attention_heads
    hd = hid // heads
    eps = cfg.rms_norm_eps
    cast = lambda a: a.astype(compute_dtype)
    mm = functools.partial(jnp.dot, preferred_element_type=jnp.float32)

    with jax.named_scope(ATTENTION):
        x = cast(rms_norm(h, layer["attn_norm"], eps))
        q = rms_norm(mm(x, cast(layer["q"])), layer["q_norm"], eps)
        k = rms_norm(mm(x, cast(layer["k"])), layer["k_norm"], eps)
        v = mm(x, cast(layer["v"])).reshape(t, heads, hd)
        q = _rope(q.reshape(t, heads, hd), pos, cfg.rope_theta)
        k = _rope(k.reshape(t, heads, hd), pos, cfg.rope_theta)
        scores = jnp.einsum("qhd,khd->hqk", cast(q), cast(k),
                            preferred_element_type=jnp.float32) / (hd ** 0.5)
        idx = jnp.arange(t)
        # causal, and within one segment; padding (segment 0) sees padding,
        # which keeps its rows finite and is masked out of the loss
        allowed = (idx[:, None] >= idx[None, :]) & (segs[:, None] == segs[None, :])
        probs = jax.nn.softmax(jnp.where(allowed[None], scores, -1e30), axis=-1)
        ctx = jnp.einsum("hqk,khd->qhd", cast(probs), cast(v),
                         preferred_element_type=jnp.float32)
        h = h + mm(cast(ctx.reshape(t, hid)), cast(layer["o"]))

    top_k, n_exp = cfg.num_experts_per_tok, cfg.num_experts
    with jax.named_scope(ROUTER):
        x = rms_norm(h, layer["mlp_norm"], eps)
        gates, experts = route(x, layer["router"], top_k, cfg.norm_topk_prob)
    with jax.named_scope(EXPERT_DISPATCH):
        # assignments sorted by expert: row a of the sorted list is token
        # order[a] // k, and the groups' sizes are the experts' loads
        flat = experts.reshape(-1)
        order = jnp.argsort(flat, stable=True)
        sizes = jnp.bincount(flat, length=n_exp).astype(jnp.int32)
        xs = jnp.take(cast(x), order // top_k, axis=0)
        real = (segs > 0).astype(jnp.int32)
        load = jnp.zeros((n_exp,), jnp.int32).at[flat].add(
            jnp.repeat(real, top_k))
    with jax.named_scope(EXPERTS):
        rd = functools.partial(lax.ragged_dot, group_sizes=sizes,
                               preferred_element_type=jnp.float32)
        act = jax.nn.silu(rd(xs, cast(layer["gate"]))) * rd(xs, cast(layer["up"]))
        ys = rd(cast(act), cast(layer["down"]))
    with jax.named_scope(EXPERT_DISPATCH):
        back = jnp.take(ys, jnp.argsort(order), axis=0, unique_indices=True)
        h = h + (back.reshape(t, top_k, hid) * gates[..., None]).sum(axis=1)
    return h, load


def next_token_targets(tokens, segs):
    """``(labels (T,), valid (T,) float32)``: the next token where it belongs
    to the same document; padding and each document's last token are out."""
    labels = jnp.concatenate([tokens[1:], jnp.zeros((1,), tokens.dtype)])
    nxt = jnp.concatenate([segs[1:], jnp.zeros((1,), segs.dtype)])
    return labels, ((segs > 0) & (nxt == segs)).astype(jnp.float32)


def _head_loss(h, head, labels, valid, compute_dtype):
    """``(summed loss, correct)`` over a sequence, a chunk of rows at a
    time; each chunk's logits are recomputed in the backward pass."""
    t = h.shape[0]
    chunk = LOSS_CHUNK if t % LOSS_CHUNK == 0 else t
    w = head.astype(compute_dtype)

    @jax.checkpoint
    def one(carry, xs):
        hc, yc, vc = xs
        logits = jnp.dot(hc.astype(compute_dtype), w,
                         preferred_element_type=jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, yc[:, None], axis=-1)[:, 0]
        hit = (jnp.argmax(logits, axis=-1) == yc).astype(jnp.float32)
        loss, correct = carry
        return (loss + ((lse - picked) * vc).sum(), correct + (hit * vc).sum()), None

    parts = (h.reshape(-1, chunk, h.shape[1]), labels.reshape(-1, chunk),
             valid.reshape(-1, chunk))
    (loss, correct), _ = lax.scan(one, (jnp.float32(0.0), jnp.float32(0.0)),
                                  parts)
    return loss, correct


def olmoe_sequence_stats(params, row, cfg, compute_dtype=jnp.float32):
    """One packed row ``(2, T)`` through the model: the sufficient statistics
    of the next-token task and the expert counters, all sums over tokens:
    ``loss_sum``, ``correct``, ``count`` (tokens in the loss), ``tokens``
    (of any document), ``padding`` (tokens of segment 0), ``expert_load (E,)`` (real tokens given to each
    expert, summed over layers)."""
    tokens, segs = row[0], row[1]
    pos = segment_positions(segs)
    with jax.named_scope(EMBED):
        h = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)

    def layer_step(h, layer):
        return _block(cfg, compute_dtype, h, layer, segs, pos)

    h, loads = lax.scan(layer_step, h, params["layers"])
    with jax.named_scope(LM_HEAD_LOSS):
        labels, valid = next_token_targets(tokens, segs)
        h = rms_norm(h, params["final_norm"], cfg.rms_norm_eps)
        loss, correct = _head_loss(h, params["head"], labels, valid,
                                   compute_dtype)
    return {"loss_sum": loss, "correct": correct, "count": valid.sum(),
            "tokens": (segs > 0).sum().astype(jnp.float32),
            "padding": (segs == 0).sum().astype(jnp.float32),
            "expert_load": loads.sum(axis=0)}


def olmoe_stats(params, x, mask, cfg, compute_dtype=jnp.float32):
    """``olmoe_sequence_stats`` summed over the rows ``x (N, 2, T)`` whose
    ``mask`` is 1, one row at a time (a padded row counts for nothing)."""
    def one(row_and_mask):
        row, m = row_and_mask
        # a padded row is all segment 0: nothing of it is counted
        stats = olmoe_sequence_stats(params, row * m.astype(row.dtype), cfg,
                                     compute_dtype)
        return {**stats, "padding": stats["padding"] * m}

    if x.shape[0] == 1:
        return one((x[0], mask[0]))
    stats = lax.map(one, (x, mask))
    return jax.tree.map(lambda a: a.sum(axis=0), stats)
