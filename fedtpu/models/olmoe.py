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
  restart at each segment, the loss is masked at padding and at each
  document's last token: two documents in one row give what the two alone do.
* **Dropless experts, every one of them.** Every (token, expert) assignment
  is computed: the assignments are sorted by expert and the three expert
  matmuls run as grouped matmuls over the uneven groups
  (``fedtpu.ops.grouped_matmul``, which has the two bodies and the rule
  between them). There is no capacity factor and no auxiliary loss (HF's
  default ``output_router_logits=False`` computes none).
* **Scanned layers.** Layer parameters carry a leading layers axis and the
  stack is a ``lax.scan``, so depth 16 compiles as depth 1 does.

The attention core with its two bodies is ``fedtpu.ops.packed_attention``'s,
the head and its loss ``fedtpu.ops.lm_head``'s, the norm, RoPE and positions
``fedtpu.models.layers``'s. Parameters are float32. ``compute_dtype``
(bfloat16 in the shipped presets) is the dtype of every large matmul's
inputs; accumulation, norms, softmax, the router (logits at ``HIGHEST``
precision, softmax, top-k) and the loss stay float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from fedtpu.models.layers import (INIT_STD, _rope, bodies_at, rms_norm,
                                  segment_positions)
from fedtpu.ops.grouped_matmul import (gather_rows, grouped_matmul,
                                       sorted_assignments)
from fedtpu.ops.lm_head import _head_loss, next_token_targets
from fedtpu.ops.packed_attention import attention_blocks, attention_core
from fedtpu.ops.scopes import (ATTENTION, EMBED, EXPERT_DISPATCH, EXPERTS,
                               LM_HEAD_LOSS, ROUTER)

# what counts a row, not its tokens: a padded row's is left out (rows_stats)
PER_ROW = ("padding", "fused_attention", "grouped_experts",
           "attention_blocks_computed", "attention_blocks_causal")


def check(cfg) -> None:
    """What the widths must satisfy before anything is built."""
    if cfg.hidden_size % cfg.num_attention_heads:
        raise ValueError(f"hidden_size {cfg.hidden_size} does not divide "
                         f"into {cfg.num_attention_heads} heads")


def init(key: jax.Array, cfg, param_dtype=jnp.float32):
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
        ctx = attention_core(q, k, v, segs, compute_dtype)
        h = h + mm(cast(ctx.reshape(t, hid)), cast(layer["o"]))

    top_k, n_exp = cfg.num_experts_per_tok, cfg.num_experts
    with jax.named_scope(ROUTER):
        x = rms_norm(h, layer["mlp_norm"], eps)
        gates, experts = route(x, layer["router"], top_k, cfg.norm_topk_prob)
    with jax.named_scope(EXPERT_DISPATCH):
        # assignments sorted by expert: row a of the sorted list is token
        # order[a] // k, and the groups' sizes are the experts' loads
        flat = experts.reshape(-1)
        order, sizes = sorted_assignments(flat, n_exp)
        xs = gather_rows(cast(x), order, top_k)
        real = (segs > 0).astype(jnp.int32)
        load = jnp.zeros((n_exp,), jnp.int32).at[flat].add(
            jnp.repeat(real, top_k))
    with jax.named_scope(EXPERTS):
        act = (jax.nn.silu(grouped_matmul(xs, cast(layer["gate"]), sizes))
               * grouped_matmul(xs, cast(layer["up"]), sizes))
        ys = grouped_matmul(cast(act), cast(layer["down"]), sizes)
    with jax.named_scope(EXPERT_DISPATCH):
        back = jnp.take(ys, jnp.argsort(order), axis=0, unique_indices=True)
        h = h + (back.reshape(t, top_k, hid) * gates[..., None]).sum(axis=1)
    return h, load


def sequence_stats(params, row, cfg, compute_dtype=jnp.float32):
    """One packed row ``(2, T)`` through the model: the sufficient statistics
    of the next-token task and the expert counters, all sums over tokens:
    ``loss_sum``, ``correct``, ``count`` (tokens in the loss), ``tokens``
    (of any document), ``padding`` (tokens of segment 0), ``expert_load (E,)``
    (real tokens given to each expert, summed over layers), ``fused_attention`` (positions whose
    attention ran in the fused body: T or 0), ``grouped_experts`` (positions
    whose expert matmuls ran in the tiled kernels: T or 0),
    ``attention_blocks_computed`` and ``attention_blocks_causal``
    (``attention_blocks``)."""
    tokens, segs = row[0], row[1]
    pos = segment_positions(segs)
    t, heads = tokens.shape[0], cfg.num_attention_heads
    _, fused, grouped = bodies_at(
        t, heads, cfg.hidden_size // heads, cfg.hidden_size // heads,
        compute_dtype, experts=(t * cfg.num_experts_per_tok, cfg.num_experts,
                                cfg.hidden_size, cfg.intermediate_size))
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
            "expert_load": loads.sum(axis=0),
            "fused_attention": jnp.float32(t if fused else 0),
            "grouped_experts": jnp.float32(t if grouped else 0),
            **attention_blocks(segs, fused, cfg.num_hidden_layers)}
