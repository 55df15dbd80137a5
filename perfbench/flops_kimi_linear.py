"""Operations and compulsory bytes of one federated round of the Kimi-Linear
stack, from the configuration's shapes and the token counts measured on the
round's data (``datasets_lm.counts``).

Operations are what the algorithm needs, whatever program computes them: a
multiply-add is two; the backward pass is twice the forward; what a program
recomputes (every layer's forward, here) is NOT counted, and neither is what
it computes on zeros (the attention core's padded head), so a share of a
peak computed from these can only be low, never above 100%. Real tokens
only. Per real token:

* a KDA mixer: the projections (``W_q``, ``W_k``, ``W_v``, the decay's and
  the output gate's two matrices each, ``W_b``, ``W_o``) and the three short
  convolutions' taps; and the recurrence, as the token-by-token definition
  needs it: the three products ``S^T k``, ``k (.)^T`` and ``S^T q`` over the
  state, ``3 * 2 * heads * d_k * d_v`` (the chunked form's matmuls and its
  triangular inverse do more: they are a way to compute this, not a need);
* latent attention: the four projections (``W_q``, ``W_kva``, ``W_kvb``,
  ``W_o``), and per (query, key) pair causal attention within a document
  allows, over every head, the score over the ``nope + rope`` columns and
  the weighted sum over the ``v`` columns (``core_flops``: what
  ``attn_core_mfu`` is read against);
* feed-forward: a leading layer's three matmuls; an expert layer's router
  over ALL routed experts, its shared expert's three matmuls and the routed
  experts' three for the assignments this chip holds: ``experts per token *
  held / routed`` a token under even routing (``round_cost``), or the
  assignments counted in the run (``held_experts_flops``);
* the head over the vocabulary slice.

Bytes are the compulsory traffic on parameter-sized arrays, in float32, of
the shared-global engine as it stands (``flops_nemotron_h`` has the
account). The recurrence's own cost (``scan_cost``) is what
``kl_kda_scan_roofline`` is read against: its operations as above and, as
compulsory bytes, its inputs and outputs as the program holds them, float32:
forward it reads ``q, k, v, g`` (a head's width each) and ``beta`` (a number
a head) and writes ``o``; backward it reads the same and ``do`` and writes
the five gradients.
"""

from __future__ import annotations

F32 = 4


def _layers(m: dict) -> dict:
    """How many layers of each kind a step runs."""
    dense = m["first_k_dense_replace"]
    return {"kda": len(m["kda_layers"]), "full": len(m["full_attn_layers"]),
            "dense": dense, "experts": m["num_hidden_layers"] - dense}


def _kda_width(m: dict) -> int:
    return m["kda_num_heads"] * m["kda_head_dim"]


def params(m: dict) -> dict:
    """Parameters held on this chip: ``experts_held`` of the routed experts
    and the vocabulary slice the configuration states. A mixer's count leaves
    out its pre-norm, which the layer's count brings."""
    h, v, heads = m["hidden_size"], m["vocab_size"], m["num_attention_heads"]
    nope, rope, vd = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    kvl, width, rank = m["kv_lora_rank"], _kda_width(m), m["kda_head_dim"]
    kda = (3 * h * width + 3 * m["short_conv_kernel_size"] * width
           + 2 * (h * rank + rank * width) + m["kda_num_heads"] + 2 * width
           + h * m["kda_num_heads"] + m["kda_head_dim"] + width * h)
    full = (h * heads * (nope + rope) + h * (kvl + rope) + kvl
            + kvl * heads * (nope + vd) + heads * vd * h)
    expert = 3 * h * m["moe_intermediate_size"]
    dense = 3 * h * m["intermediate_size"]
    routed = (h * m["n_routed_experts"] + m["n_routed_experts"]
              + (m["experts_held"] + m["n_shared_experts"]) * expert)
    mixers = {"kda": kda, "full": full}
    total = 2 * v * h + h
    for i in range(1, m["num_hidden_layers"] + 1):
        total += (mixers["kda" if i in m["kda_layers"] else "full"] + 2 * h
                  + (dense if i <= m["first_k_dense_replace"] else routed))
    return {"embed": v * h, "head": h * v, "kda_mixer": kda,
            "full_mixer": full, "routed_expert": expert,
            "kda_dense_layer": kda + 2 * h + dense,
            "kda_experts_layer": kda + 2 * h + routed,
            "full_experts_layer": full + 2 * h + routed, "total": total}


def scan_flops_per_token(m: dict) -> int:
    return 3 * 2 * m["kda_num_heads"] * m["kda_head_dim"] * m["kda_head_dim"]


def scan_cost(m: dict, tokens: int) -> dict:
    """``{'flops', 'bytes'}`` of the recurrences of a round over ``tokens``
    real tokens, forward and backward, every KDA layer."""
    width, heads, layers = _kda_width(m), m["kda_num_heads"], _layers(m)["kda"]
    forward = 5 * width + heads             # q, k, v, g, beta in; o out
    backward = 9 * width + 2 * heads        # the same and do in; five out
    return {"flops": float(3 * layers * tokens * scan_flops_per_token(m)),
            "bytes": float(layers * tokens * (forward + backward) * F32)}


def held_experts_flops(m: dict, assignments: float) -> float:
    """Forward and backward operations of the routed experts' three matmuls
    over ``assignments`` (token, held expert) pairs."""
    return 3.0 * assignments * 3 * 2 * m["hidden_size"] * m["moe_intermediate_size"]


def core_flops(m: dict, pairs: int) -> float:
    """Forward and backward operations of the attention cores of a round
    over ``pairs`` allowed (query, key) pairs a layer: the score over the
    query-key width and the weighted sum over the value width, every head."""
    per_pair = 2 * m["num_attention_heads"] * (
        m["qk_nope_head_dim"] + m["qk_rope_head_dim"] + m["v_head_dim"])
    return 3.0 * _layers(m)["full"] * pairs * per_pair


def forward_flops(m: dict, counts: dict) -> dict:
    """Forward operations of a round by part, from the measured counts."""
    h, tokens, n = m["hidden_size"], counts["tokens"], _layers(m)
    heads = m["num_attention_heads"]
    nope, rope, vd = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    kvl, width, rank = m["kv_lora_rank"], _kda_width(m), m["kda_head_dim"]
    held = m["num_experts_per_tok"] * m["experts_held"] / m["n_routed_experts"]
    expert = 3 * 2 * h * m["moe_intermediate_size"]
    return {
        "kda_proj": n["kda"] * tokens * (
            2 * (3 * h * width + 2 * (h * rank + rank * width)
                 + h * m["kda_num_heads"] + width * h)
            + 2 * m["short_conv_kernel_size"] * 3 * width),
        "kda_scan": n["kda"] * tokens * scan_flops_per_token(m),
        "attn_latent": n["full"] * tokens * 2 * (
            h * heads * (nope + rope) + h * (kvl + rope)
            + kvl * heads * (nope + vd) + heads * vd * h),
        "attn_core": core_flops(m, counts["attention_pairs"]) / 3.0,
        "dense_mlp": n["dense"] * tokens * 3 * 2 * h * m["intermediate_size"],
        "router": n["experts"] * tokens * 2 * h * m["n_routed_experts"],
        "experts": n["experts"] * tokens * held * expert,
        "shared_expert": n["experts"] * tokens * m["n_shared_experts"] * expert,
        "head": tokens * 2 * h * m["vocab_size"],
    }


def round_cost(m: dict, counts: dict, clients: int) -> dict:
    """``{'flops', 'bytes', 'params', 'by_part', 'scan', 'core_flops'}`` of
    one round: every client's epoch of one-sequence steps, forward and
    backward, and the server's update. ``m`` holds the program's model
    fields (``train_kimi_linear.model_fields``)."""
    fwd = forward_flops(m, counts)
    p = params(m)["total"]
    steps = counts["sequences"]
    copies = steps - clients        # steps another step of the client follows
    return {"flops": float(3 * sum(fwd.values())),
            "bytes": float(F32 * p * (5 * steps + 2 * copies + 6)),
            "params": p, "by_part": {k: float(3 * v) for k, v in fwd.items()},
            "scan": scan_cost(m, counts["tokens"]),
            "core_flops": core_flops(m, counts["attention_pairs"])}
