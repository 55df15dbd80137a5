"""Operations and compulsory bytes of one federated round of the hybrid
(``nemotron_h``) tower, from the configuration's shapes and the token counts
measured on the round's data (``datasets_lm.counts``).

Operations are what the algorithm needs, whatever program computes them: a
multiply-add is two; the backward pass is twice the forward; what a program
recomputes (every layer's forward, here) is NOT counted, so a share of a
peak computed from these can only be low, never above 100%. Real tokens
only: padding is computed by the program and needed by nobody. Per real
token, a layer of each kind:

* Mamba-2: ``W_in`` and ``W_out``; the convolution's taps; and the scan, as
  the recurrence needs it: the outer product into the state and the readout
  with ``C``, ``2 * 2 * heads * head_dim * state`` (the chunked form's
  matmuls do more: they are a way to compute this, not a need).
* attention: the four projections (two of them over the key-value heads'
  width), and per (query, key) pair causal attention within a document
  allows, the score and the weighted sum over every query head.
* experts: the router over ALL routed experts; the shared expert's two
  matmuls; the routed experts' two matmuls for the assignments this chip
  holds: ``experts per token * held / routed`` a token under even routing
  (``round_cost``), or the assignments counted in the run
  (``held_experts_flops``).
* once: the head over the vocabulary slice.

Bytes are the compulsory traffic on parameter-sized arrays, in float32, of
the shared-global engine as it stands (fedtpu.parallel.stateless, PR 30):
a step reads the parameters to compute and writes their gradient (2), and
its one pass reads the gradient and the accumulator and writes the
accumulator (3); a step that another follows also reads the source and
writes the client's working copy (2 more); the server scales its momentum
into the accumulator (2) and applies it (reads accumulator and global,
writes momentum and global: 4). Activations and the bfloat16 copies of the
weights are not counted.

The scan's own cost (``scan_cost``) is what ``ssm_scan_roofline`` is read
against: its operations as above, and as compulsory bytes its inputs and
outputs in float32, forward (read x, B, C, dt; write y) and backward (read
them and dy; write dx, dB, dC, ddt).
"""

from __future__ import annotations

F32 = 4


def _kinds(m: dict) -> dict:
    pattern = m["hybrid_override_pattern"]
    return {"mamba": pattern.count("M"), "experts": pattern.count("E"),
            "attention": pattern.count("*")}


def _mamba_widths(m: dict):
    width = m["mamba_num_heads"] * m["mamba_head_dim"]
    state = m["n_groups"] * m["ssm_state_size"]
    return width, state


def params(m: dict) -> dict:
    """Parameters held on this chip: ``experts_held`` of the routed experts
    and the vocabulary slice the configuration states."""
    h, v = m["hidden_size"], m["vocab_size"]
    width, state = _mamba_widths(m)
    heads = m["mamba_num_heads"]
    conv = width + 2 * state
    mamba = (h + h * (2 * width + 2 * state + heads) + m["conv_kernel"] * conv
             + conv + 3 * heads + width + width * h)
    q, kv = (m["num_attention_heads"] * m["head_dim"],
             m["num_key_value_heads"] * m["head_dim"])
    attention = h + h * (q + 2 * kv) + q * h
    routed = 2 * h * m["moe_intermediate_size"]
    experts = (h + h * m["n_routed_experts"] + m["n_routed_experts"]
               + m["experts_held"] * routed
               + 2 * h * m["moe_shared_expert_intermediate_size"])
    n = _kinds(m)
    return {"embed": v * h, "head": h * v, "mamba_layer": mamba,
            "attention_layer": attention, "experts_layer": experts,
            "routed_expert": routed,
            "total": (2 * v * h + h + n["mamba"] * mamba
                      + n["attention"] * attention + n["experts"] * experts)}


def scan_flops_per_token(m: dict) -> int:
    return 2 * 2 * m["mamba_num_heads"] * m["mamba_head_dim"] * m["ssm_state_size"]


def held_experts_flops(m: dict, assignments: float) -> float:
    """Forward and backward operations of the routed experts' two matmuls
    over ``assignments`` (token, held expert) pairs."""
    return 3.0 * assignments * 2 * 2 * m["hidden_size"] * m["moe_intermediate_size"]


def scan_cost(m: dict, tokens: int) -> dict:
    """``{'flops', 'bytes'}`` of the state-space scans of a round over
    ``tokens`` real tokens, forward and backward, all ``M`` layers."""
    width, state = _mamba_widths(m)
    per_token = 2 * width + 2 * state + m["mamba_num_heads"]   # x, y, B, C, dt
    layers = _kinds(m)["mamba"]
    return {"flops": float(3 * layers * tokens * scan_flops_per_token(m)),
            # forward once; backward reads the same and dy, writes as much
            "bytes": float(3 * layers * tokens * per_token * F32)}


def forward_flops(m: dict, counts: dict) -> dict:
    """Forward operations of a round by part, from the measured counts."""
    h, tokens, n = m["hidden_size"], counts["tokens"], _kinds(m)
    width, state = _mamba_widths(m)
    q, kv = (m["num_attention_heads"] * m["head_dim"],
             m["num_key_value_heads"] * m["head_dim"])
    held = (m["num_experts_per_tok"] * m["experts_held"]
            / m["n_routed_experts"])
    return {
        "ssm_proj": n["mamba"] * tokens * (
            2 * h * (2 * width + 2 * state + m["mamba_num_heads"])
            + 2 * width * h + 2 * m["conv_kernel"] * (width + 2 * state)),
        "ssm_scan": n["mamba"] * tokens * scan_flops_per_token(m),
        "attention": n["attention"] * (
            tokens * (2 * h * (q + 2 * kv) + 2 * q * h)
            + 2 * 2 * q * counts["attention_pairs"]),
        "router": n["experts"] * tokens * 2 * h * m["n_routed_experts"],
        "experts": n["experts"] * tokens * held * 2 * 2 * h
        * m["moe_intermediate_size"],
        "shared_expert": n["experts"] * tokens * 2 * 2 * h
        * m["moe_shared_expert_intermediate_size"],
        "head": tokens * 2 * h * m["vocab_size"],
    }


def round_cost(m: dict, counts: dict, clients: int) -> dict:
    """``{'flops', 'bytes', 'params', 'by_part', 'scan'}`` of one round:
    every client's epoch of one-sequence steps, forward and backward, and
    the server's update. ``m`` holds the configuration's keys and
    ``experts_held``."""
    fwd = forward_flops(m, counts)
    p = params(m)["total"]
    steps = counts["sequences"]
    copies = steps - clients        # steps another step of the client follows
    return {"flops": float(3 * sum(fwd.values())),
            "bytes": float(F32 * p * (5 * steps + 2 * copies + 6)),
            "params": p, "by_part": {k: float(3 * v) for k, v in fwd.items()},
            "scan": scan_cost(m, counts["tokens"])}
