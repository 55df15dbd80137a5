"""Operations and compulsory bytes of one federated round of the Xing4.0
stack, from the configuration's shapes and the token counts measured on the
round's data (``datasets_lm.counts``).

Operations are what the algorithm needs, whatever program computes them: a
multiply-add is two; the backward pass is twice the forward; what a program
recomputes (every layer's forward, here) is NOT counted, and neither is what
it computes on zeros (the attention core's padded head), so a share of a
peak computed from these can only be low, never above 100%. Real tokens
only. Per real token:

* latent attention, a block (the main stack's layers and the prediction
  module's one): the five projections (``W_qa``, ``W_qb``, ``W_kva``,
  ``W_kvb``, ``W_o``), and per (query, key) pair causal attention within a
  document allows, over every head, the score over the ``nope + rope``
  columns and the weighted sum over the ``v`` columns (``core``: what
  ``attn_core_mfu`` is read against; the program's tiled body computes
  both at the padded width, ``2 * 256`` columns a pair and head where
  ``192 + 128`` are needed, and its backward pass the scores once more);
* a residual module, two a block: the projection of the flattened streams
  onto the ``n (n + 2)`` logits, the read (``n`` multiply-adds a column) and
  the write (``n * n + n``);
* feed-forward: a leading layer's three matmuls; an expert layer's router
  over ALL routed experts, its shared expert's three matmuls and the routed
  experts' three for the assignments this chip holds: ``experts per token *
  held / routed`` a token under even routing (``round_cost``), or the
  assignments counted in the run (``held_experts_flops``);
* the prediction module's opening projection (``2C x C``);
* the head over the vocabulary slice, once a loss.

Bytes are the compulsory traffic on parameter-sized arrays, in float32, of
the shared-global engine as it stands (``flops_nemotron_h`` has the
account). The residual path's own cost (``hyper_cost``) is what
``x4_hyper_conn_roofline`` is read against: a sublayer's pass over the
streams, float32: forward it reads the streams and the sublayer's output
and writes the streams and the sublayer's input (``2n + 2`` rows of ``C``);
backward it reads the streams, their cotangent, the output and the input's
cotangent and writes the streams' and the output's cotangents (``3n + 3``).
"""

from __future__ import annotations

F32 = 4


def _blocks(m: dict) -> dict:
    """How many blocks of each kind a step runs: the main stack's and the
    prediction module's one expert block."""
    dense = m["first_k_dense_replace"]
    modules = m["num_nextn_predict_layers"]
    return {"dense": dense, "modules": modules,
            "experts": m["num_hidden_layers"] - dense + modules,
            "attention": m["num_hidden_layers"] + modules}


def params(m: dict) -> dict:
    """Parameters held on this chip: ``experts_held`` of the routed experts
    and the vocabulary slice the configuration states."""
    h, v, heads = m["hidden_size"], m["vocab_size"], m["num_attention_heads"]
    nope, rope, vd = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    ql, kvl, n = m["q_lora_rank"], m["kv_lora_rank"], m["hc_mult"]
    attention = (h * ql + ql + ql * heads * (nope + rope) + h * (kvl + rope)
                 + kvl + kvl * heads * (nope + vd) + heads * vd * h)
    hyper = n * (n + 2) * n * h + n * (n + 2) + 3
    expert = 3 * h * m["moe_intermediate_size"]
    dense_layer = attention + 2 * h + 2 * hyper + 3 * h * m["intermediate_size"]
    experts_layer = (attention + 2 * h + 2 * hyper
                     + h * m["n_routed_experts"] + m["n_routed_experts"]
                     + (m["experts_held"] + m["n_shared_experts"]) * expert)
    b = _blocks(m)
    module = 2 * h + 2 * h * h + experts_layer + h
    main = (2 * v * h + h + b["dense"] * dense_layer
            + (b["experts"] - b["modules"]) * experts_layer)
    return {"embed": v * h, "head": h * v, "attention": attention,
            "hyper_module": hyper, "routed_expert": expert,
            "dense_layer": dense_layer, "experts_layer": experts_layer,
            "module": module, "main": main,
            "total": main + b["modules"] * module}


def held_experts_flops(m: dict, assignments: float) -> float:
    """Forward and backward operations of the routed experts' three matmuls
    over ``assignments`` (token, held expert) pairs."""
    return 3.0 * assignments * 3 * 2 * m["hidden_size"] * m["moe_intermediate_size"]


def core_flops(m: dict, pairs: int) -> float:
    """Forward and backward operations of the attention cores of a round
    over ``pairs`` allowed (query, key) pairs a block: the score over the
    query-key width and the weighted sum over the value width, every head."""
    per_pair = 2 * m["num_attention_heads"] * (
        m["qk_nope_head_dim"] + m["qk_rope_head_dim"] + m["v_head_dim"])
    return 3.0 * _blocks(m)["attention"] * pairs * per_pair


def hyper_cost(m: dict, tokens: int) -> dict:
    """``{'flops', 'bytes'}`` of the residual modules of a round over
    ``tokens`` real tokens, forward and backward, two modules a block."""
    n, h = m["hc_mult"], m["hidden_size"]
    modules = 2 * _blocks(m)["attention"]
    flops = 2 * n * h * n * (n + 2) + 2 * n * h + 2 * (n * n + n) * h
    return {"flops": float(3 * modules * tokens * flops),
            "bytes": float(modules * tokens * (5 * n + 5) * h * F32)}


def forward_flops(m: dict, counts: dict) -> dict:
    """Forward operations of a round by part, from the measured counts."""
    h, tokens, b = m["hidden_size"], counts["tokens"], _blocks(m)
    heads = m["num_attention_heads"]
    nope, rope, vd = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    ql, kvl = m["q_lora_rank"], m["kv_lora_rank"]
    held = m["num_experts_per_tok"] * m["experts_held"] / m["n_routed_experts"]
    expert = 3 * 2 * h * m["moe_intermediate_size"]
    return {
        "attn_latent": b["attention"] * tokens * 2 * (
            h * ql + ql * heads * (nope + rope) + h * (kvl + rope)
            + kvl * heads * (nope + vd) + heads * vd * h),
        "attn_core": core_flops(m, counts["attention_pairs"]) / 3.0,
        "hyper_conn": hyper_cost(m, tokens)["flops"] / 3.0,
        "dense_mlp": b["dense"] * tokens * 3 * 2 * h * m["intermediate_size"],
        "router": b["experts"] * tokens * 2 * h * m["n_routed_experts"],
        "experts": b["experts"] * tokens * held * expert,
        "shared_expert": b["experts"] * tokens * m["n_shared_experts"] * expert,
        "mtp_proj": b["modules"] * tokens * 2 * 2 * h * h,
        "head": (1 + b["modules"]) * tokens * 2 * h * m["vocab_size"],
    }


def round_cost(m: dict, counts: dict, clients: int) -> dict:
    """``{'flops', 'bytes', 'params', 'by_part', 'hyper', 'core_flops'}`` of
    one round: every client's epoch of one-sequence steps, forward and
    backward, and the server's update. ``m`` holds the configuration's keys
    and ``experts_held``."""
    fwd = forward_flops(m, counts)
    p = params(m)["total"]
    steps = counts["sequences"]
    copies = steps - clients        # steps another step of the client follows
    return {"flops": float(3 * sum(fwd.values())),
            "bytes": float(F32 * p * (5 * steps + 2 * copies + 6)),
            "params": p, "by_part": {k: float(3 * v) for k, v in fwd.items()},
            "hyper": hyper_cost(m, counts["tokens"]),
            "core_flops": core_flops(m, counts["attention_pairs"])}
