"""Operations and compulsory bytes of one federated round of the
Phi-4-mini-flash stack (SambaY), from the configuration's shapes and the
token counts measured on the round's data (``datasets_lm.counts``, and
``window_pairs`` here for the pairs a window leaves).

Operations are what the algorithm needs, whatever program computes them: a
multiply-add is two; the backward pass is twice the forward; what a program
recomputes (every layer's forward, here) is NOT counted, and neither is what
it computes on zeros (the attention core's padded head), so a share of a
peak computed from these can only be low, never above 100%. Real tokens
only. Per real token:

* a Mamba-1 mixer: its four projections (``W_in`` to twice the inner width,
  ``W_x`` to ``rank + 2 N``, ``W_d`` from the rank, ``W_out``), the short
  convolution's taps, and the selective scan as the token-by-token
  definition needs it: six operations a channel and state (``dl A``, the
  decay times the state, ``(dl x) B``, their sum, the state times ``C`` and
  its sum over the states; the exponential is not counted);
* a Gated Memory Unit: its two projections;
* attention: the projections (``W_qkv`` and ``W_o``; a cross layer's ``W_q``
  and ``W_o``), and per (query, key) pair its mask allows (causal within a
  document; within the window too in a window layer), over every pair of
  heads, the two scores over a head's width and the two weighted sums over
  the pair's value of twice that (``core_flops``: what ``attn_core_mfu`` is
  read against);
* the feed-forward's three matmuls (two of them one product), every layer;
* the head over the vocabulary slice (the embedding transposed: tied).

Bytes are the compulsory traffic on parameter-sized arrays, in float32, of
the shared-global engine as it stands (``flops_nemotron_h`` has the
account). The scan's own cost (``scan_cost``) is what ``p4_s6_scan_roofline``
is read against, whatever implements the scan: its operations as above and,
as compulsory bytes, what a real token and layer must move in float32:
forward ``x``, ``dl`` (the inner width each), ``B``, ``C`` (``N`` each) in
and ``y`` out; backward those and ``dy`` in and the five gradients out (``dA``
once a row: left out). No recomputation, no state written.
"""

from __future__ import annotations

import math

import numpy as np

from perfbench.reference_phi4_flash import kind_at     # the model's rule

F32 = 4
def _layers(m: dict) -> dict:
    """How many layers of each kind a step runs."""
    held = tuple(m["layers_held"]) or tuple(range(m["num_hidden_layers"]))
    kinds = [kind_at(i, m) for i in held]
    return {"s6": kinds.count("s6") + kinds.count("s6_memory"),
            **{k: kinds.count(k) for k in ("window", "full", "gmu", "cross")},
            "all": len(kinds)}


def _widths(m: dict) -> tuple:
    """``(head, inner, rank, N, key-value width)``."""
    h = m["hidden_size"]
    d = h // m["num_attention_heads"]
    return (d, m["mamba_expand"] * h,
            m.get("mamba_dt_rank") or math.ceil(h / 16), m["mamba_d_state"],
            d * m["num_key_value_heads"])


def params(m: dict) -> dict:
    """Parameters held on this chip: the layers held and the vocabulary
    slice the configuration states; the embedding is the head (counted
    once). A mixer's count leaves out its LayerNorm, which the layer's count
    brings (gain and bias, two a layer)."""
    h, v = m["hidden_size"], m["vocab_size"]
    d, inner, rank, n, kv = _widths(m)
    s6 = (h * 2 * inner + m["mamba_d_conv"] * inner + inner
          + inner * (rank + 2 * n) + rank * inner + inner + inner * n + inner
          + inner * h)
    own = 4 * d + 2 * d                    # four lambda vectors, the sub-norm
    attention = h * (h + 2 * kv) + (h + 2 * kv) + h * h + h + own
    cross = 2 * (h * h + h) + own
    gmu = 2 * h * inner
    ffn = 3 * h * m["intermediate_size"]
    norms = 2 * 2 * h
    n_of = _layers(m)
    mixers = {"s6": s6, "window": attention, "full": attention, "gmu": gmu,
              "cross": cross}
    total = v * h + 2 * h + sum(n_of[k] * mixers[k] for k in mixers) \
        + n_of["all"] * (ffn + norms)
    return {"embed": v * h, "s6_mixer": s6, "attention_mixer": attention,
            "cross_mixer": cross, "gmu_mixer": gmu, "feed_forward": ffn,
            "s6_layer": s6 + ffn + norms,
            "attention_layer": attention + ffn + norms,
            "gmu_layer": gmu + ffn + norms, "cross_layer": cross + ffn + norms,
            "total": total}


def window_pairs(x: np.ndarray, window: int) -> int:
    """The (query, key) pairs of the rows ``x (N, 2, T)`` that causal
    attention within a document AND a window of ``window`` positions allow:
    a real token at place ``i`` of its document sees ``min(i + 1, window)``."""
    pairs = 0
    for row in x[:, 1]:
        lengths = np.bincount(row[row > 0])
        short = np.minimum(lengths, window)
        pairs += int((short * (short + 1) // 2
                      + (lengths - short) * window).sum())
    return pairs


def scan_flops_per_token(m: dict) -> int:
    _, inner, _, n, _ = _widths(m)
    return 6 * inner * n


def scan_cost(m: dict, tokens: int) -> dict:
    """``{'flops', 'bytes'}`` of the selective scans of a round over
    ``tokens`` real tokens, forward and backward, every Mamba-1 layer."""
    _, inner, _, n, _ = _widths(m)
    layers = _layers(m)["s6"]
    forward = 3 * inner + 2 * n             # x, dl, B, C in; y out
    backward = 5 * inner + 4 * n            # those and dy in; four out
    return {"flops": float(3 * layers * tokens * scan_flops_per_token(m)),
            "bytes": float(layers * tokens * (forward + backward) * F32)}


def core_flops(m: dict, pairs: int, windowed: int) -> float:
    """Forward and backward operations of the attention cores of a round
    over ``pairs`` allowed (query, key) pairs a full or cross layer and
    ``windowed`` a window layer: a pair of heads' two scores over a head's
    width and two weighted sums over the pair's value, every pair of heads."""
    d = _widths(m)[0]
    per_pair = (m["num_attention_heads"] // 2) * (2 * 2 * d + 2 * 2 * 2 * d)
    n = _layers(m)
    return 3.0 * per_pair * (n["window"] * windowed
                             + (n["full"] + n["cross"]) * pairs)


def forward_flops(m: dict, counts: dict) -> dict:
    """Forward operations of a round by part, from the measured counts
    (``counts["window_pairs"]`` beside ``datasets_lm.counts``' keys)."""
    h, tokens, n = m["hidden_size"], counts["tokens"], _layers(m)
    _, inner, rank, states, kv = _widths(m)
    return {
        "s6_proj": n["s6"] * tokens * 2 * (
            h * 2 * inner + inner * (rank + 2 * states) + rank * inner
            + inner * h),
        "s6_conv": n["s6"] * tokens * 2 * m["mamba_d_conv"] * inner,
        "s6_scan": n["s6"] * tokens * scan_flops_per_token(m),
        "gmu": n["gmu"] * tokens * 2 * 2 * h * inner,
        "attn_proj": tokens * 2 * (
            (n["window"] + n["full"]) * (h * (h + 2 * kv) + h * h)
            + n["cross"] * 2 * h * h),
        "attn_core": core_flops(m, counts["attention_pairs"],
                                counts["window_pairs"]) / 3.0,
        "dense_mlp": n["all"] * tokens * 3 * 2 * h * m["intermediate_size"],
        "head": tokens * 2 * h * m["vocab_size"],
    }


def round_cost(m: dict, counts: dict, clients: int) -> dict:
    """``{'flops', 'bytes', 'params', 'by_part', 'scan', 'core_flops'}`` of
    one round: every client's epoch of one-sequence steps, forward and
    backward, and the server's update. ``m`` holds the program's model
    fields (``train_phi4_flash.model_fields``)."""
    fwd = forward_flops(m, counts)
    p = params(m)["total"]
    steps = counts["sequences"]
    copies = steps - clients        # steps another step of the client follows
    return {"flops": float(3 * sum(fwd.values())),
            "bytes": float(F32 * p * (5 * steps + 2 * copies + 6)),
            "params": p, "by_part": {k: float(3 * v) for k, v in fwd.items()},
            "scan": scan_cost(m, counts["tokens"]),
            "core_flops": core_flops(m, counts["attention_pairs"],
                                     counts["window_pairs"])}
