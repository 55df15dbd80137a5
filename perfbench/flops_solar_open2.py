"""Operations and compulsory bytes of one federated round of the Solar-Open2
stack, from the configuration's shapes and the token counts measured on the
round's data (``datasets_lm.counts``).

``flops_kimi_linear``'s rules: a multiply-add is two; the backward pass is
twice the forward; what a program recomputes (every layer's forward) is NOT
counted, so a share of a peak computed from these can only be low. Real
tokens only, and only the heads, experts and rows of the vocabulary this
chip holds. Per real token:

* a KDA mixer: the projections (``W_q``, ``W_k``, ``W_v``, the decay's and
  the output gate's two matrices each, ``W_b``, ``W_o``), the three short
  convolutions' taps, and the recurrence as the token-by-token definition
  needs it (``flops_kimi_linear.scan_cost``: what ``kl_kda_scan_roofline``
  is read against);
* the gated grouped-query layer: ``W_q``, ``W_k``, ``W_v``, the gate's
  ``W_g`` and ``W_o``; and per (query, key) pair causal attention within a
  document allows, over every held head, the score and the weighted sum
  over ``head_dim`` columns each (``core_flops``: what ``attn_core_mfu`` is
  read against);
* an expert layer: the router over ALL routed experts, the shared expert's
  three matmuls and the routed experts' three for the assignments this chip
  holds: ``experts per token * held / routed`` a token under even routing
  (``round_cost``), or the assignments counted in the run
  (``held_experts_flops``: what ``experts_mfu`` is read against);
* the head over the vocabulary slice.

Bytes are the compulsory traffic on parameter-sized arrays, in float32, of
the shared-global engine as it stands (``flops_nemotron_h`` has the account).
"""

from __future__ import annotations

from perfbench import flops_kimi_linear

F32 = 4


def _layers(m: dict) -> dict:
    """How many layers of each kind a step runs."""
    full = len(m["gqa_layers"])
    return {"kda": m["num_hidden_layers"] - full, "full": full,
            "experts": m["num_hidden_layers"]}


def _kda_width(m: dict) -> int:
    return m["kda_num_heads"] * m["kda_head_dim"]


def _kimi_fields(m: dict) -> dict:
    """The keys ``flops_kimi_linear.scan_cost`` reads: the KDA layers as a
    1-based list, every layer this model's ``gqa_layers`` does not name."""
    depth = m["num_hidden_layers"]
    full = [i + 1 for i in m["gqa_layers"]]
    return {**m, "full_attn_layers": full, "first_k_dense_replace": 0,
            "kda_layers": [i for i in range(1, depth + 1) if i not in full]}


def params(m: dict) -> dict:
    """Parameters held on this chip: the heads, ``experts_held`` of the
    routed experts and the vocabulary slice the configuration states. A
    mixer's count leaves out its pre-norm, which the layer's count brings."""
    h, v = m["hidden_size"], m["vocab_size"]
    width, rank, heads = _kda_width(m), m["kda_head_dim"], m["kda_num_heads"]
    q = m["num_attention_heads"] * m["head_dim"]
    kv = m["num_key_value_heads"] * m["head_dim"]
    kda = (3 * h * width + 3 * m["short_conv_kernel_size"] * width
           + 2 * (h * rank + rank * width) + heads + 2 * width + h * heads
           + m["kda_head_dim"] + width * h)
    gqa = h * q + 2 * h * kv + (h * q if m["use_gqa_gate"] else 0) + q * h
    expert = 3 * h * m["moe_intermediate_size"]
    ffn = (h * m["n_routed_experts"] + m["n_routed_experts"]
           + (m["experts_held"] + m["n_shared_experts"]) * expert)
    n = _layers(m)
    total = (2 * v * h + h + n["kda"] * kda + n["full"] * gqa
             + m["num_hidden_layers"] * (2 * h + ffn))
    return {"embed": v * h, "head": h * v, "kda_mixer": kda, "gqa_mixer": gqa,
            "routed_expert": expert, "feed_forward": ffn,
            "kda_layer": kda + 2 * h + ffn, "gqa_layer": gqa + 2 * h + ffn,
            "total": total}


def scan_cost(m: dict, tokens: int) -> dict:
    """``{'flops', 'bytes'}`` of the recurrences of a round over ``tokens``
    real tokens, forward and backward, every KDA layer at the heads held."""
    return flops_kimi_linear.scan_cost(_kimi_fields(m), tokens)


def held_experts_flops(m: dict, assignments: float) -> float:
    """Forward and backward operations of the routed experts' three matmuls
    over ``assignments`` (token, held expert) pairs."""
    return 3.0 * assignments * 3 * 2 * m["hidden_size"] * m["moe_intermediate_size"]


def core_flops(m: dict, pairs: int) -> float:
    """Forward and backward operations of the attention cores of a round
    over ``pairs`` allowed (query, key) pairs a layer: the score and the
    weighted sum over ``head_dim`` columns each, every held head."""
    per_pair = 2 * m["num_attention_heads"] * 2 * m["head_dim"]
    return 3.0 * _layers(m)["full"] * pairs * per_pair


def forward_flops(m: dict, counts: dict) -> dict:
    """Forward operations of a round by part, from the measured counts."""
    h, tokens, n = m["hidden_size"], counts["tokens"], _layers(m)
    width, rank = _kda_width(m), m["kda_head_dim"]
    q = m["num_attention_heads"] * m["head_dim"]
    kv = m["num_key_value_heads"] * m["head_dim"]
    held = m["num_experts_per_tok"] * m["experts_held"] / m["n_routed_experts"]
    expert = 3 * 2 * h * m["moe_intermediate_size"]
    return {
        "kda_proj": n["kda"] * tokens * (
            2 * (3 * h * width + 2 * (h * rank + rank * width)
                 + h * m["kda_num_heads"] + width * h)
            + 2 * m["short_conv_kernel_size"] * 3 * width),
        "kda_scan": n["kda"] * tokens * flops_kimi_linear.scan_flops_per_token(m),
        "attn_proj": n["full"] * tokens * 2 * (2 * h * q + 2 * h * kv),
        "attn_gate": (n["full"] * tokens * (2 * h * q + q)
                      if m["use_gqa_gate"] else 0),
        "attn_core": core_flops(m, counts["attention_pairs"]) / 3.0,
        "router": n["experts"] * tokens * 2 * h * m["n_routed_experts"],
        "experts": n["experts"] * tokens * held * expert,
        "shared_expert": n["experts"] * tokens * m["n_shared_experts"] * expert,
        "head": tokens * 2 * h * m["vocab_size"],
    }


def round_cost(m: dict, counts: dict, clients: int) -> dict:
    """``{'flops', 'bytes', 'params', 'by_part', 'scan', 'core_flops'}`` of
    one round: every client's epoch of one-sequence steps, forward and
    backward, and the server's update. ``m`` holds the program's model
    fields (``train_solar_open2.model_fields``)."""
    fwd = forward_flops(m, counts)
    p = params(m)["total"]
    steps = counts["sequences"]
    copies = steps - clients        # steps another step of the client follows
    return {"flops": float(3 * sum(fwd.values())),
            "bytes": float(F32 * p * (5 * steps + 2 * copies + 6)),
            "params": p, "by_part": {k: float(3 * v) for k, v in fwd.items()},
            "scan": scan_cost(m, counts["tokens"]),
            "core_flops": core_flops(m, counts["attention_pairs"])}
