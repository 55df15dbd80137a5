"""Operations and compulsory bytes of one federated round of a sparse-expert
language model, from the configuration's shapes and the token counts
measured on the round's data (``datasets_lm.counts``).

Operations are what the algorithm needs, whatever program computes them: a
multiply-add is two; the backward pass is twice the forward; what a program
recomputes (the loss chunks' logits here) is NOT counted, so a share of the
peak computed from these can only be low, never above 100%. Per real token
and layer: the four attention projections, the router, ``experts per token``
experts of three matmuls each; per (query, key) pair causal attention within
a document allows: the score and the weighted sum; per real token once: the
head. Padding positions are computed by the program and needed by nobody:
they are not counted.

Bytes are the compulsory traffic on parameter-sized arrays, in float32: a
step reads the parameters to compute and again to update, writes them, and
writes and reads the gradient (5); a client copies the global in and adds its
delta to the accumulator (2 + 4); the server's update reads the accumulator
and the global and writes the momentum and the global (4). Activations and
the bfloat16 copies of the weights are not counted: the share says how far
the program is from the least traffic, not from XLA's own.
"""

from __future__ import annotations

F32 = 4


def params(m: dict) -> dict:
    h, e, i, v, n = (m["hidden_size"], m["num_experts"], m["intermediate_size"],
                     m["vocab_size"], m["num_hidden_layers"])
    layer = 4 * h * h + 4 * h + h * e + 3 * e * h * i
    return {"embed": v * h, "head": h * v, "layer": layer,
            "experts_per_layer": 3 * e * h * i,
            "total": 2 * v * h + n * layer + h}


def forward_flops(m: dict, counts: dict) -> dict:
    """Forward operations of a round by part, from the measured counts."""
    h, i, n = m["hidden_size"], m["intermediate_size"], m["num_hidden_layers"]
    tokens = counts["tokens"]
    return {
        "attention": n * (4 * 2 * h * h * tokens
                          + 2 * 2 * h * counts["attention_pairs"]),
        "router": n * 2 * h * m["num_experts"] * tokens,
        "experts": n * m["num_experts_per_tok"] * 3 * 2 * h * i * tokens,
        "head": 2 * h * m["vocab_size"] * tokens,
    }


def round_cost(m: dict, counts: dict, clients: int) -> dict:
    """``{'flops', 'bytes', 'params', 'experts_flops', 'by_part'}`` of one
    round: every client's epoch of one-sequence steps, forward and backward,
    and the server's update."""
    fwd = forward_flops(m, counts)
    p = params(m)["total"]
    steps = counts["sequences"]
    bytes_ = F32 * p * (5 * steps + 6 * clients + 4)
    return {"flops": float(3 * sum(fwd.values())), "bytes": float(bytes_),
            "params": p, "experts_flops": float(3 * fwd["experts"]),
            "by_part": {k: float(3 * v) for k, v in fwd.items()}}
