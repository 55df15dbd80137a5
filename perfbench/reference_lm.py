"""The plain reference of the language-model cells: OLMoE's block and FedAvg
with server momentum, as published, in straight ``jax.numpy`` and float32 at
``highest`` matmul precision, independent of ``fedtpu/``.

The block (allenai/OLMoE-1B-7B-0125-Instruct, ``config.json`` and the
modelling code): pre-norm residual layers; queries and keys pass an RMSNorm
over the whole projection before the split into heads; RoPE over all of a
head's dimensions, rotate-half; the router's softmax over every expert in
float32, the top ``num_experts_per_tok`` of it, not renormalised
(``norm_topk_prob`` false); experts ``down(silu(gate(x)) * up(x))`` computed
DENSELY here, every expert on every token, weighted by the top-k gate and
zero elsewhere; a final RMSNorm; an untied linear head; the whole
``[T, vocab]`` logits at once.

Departures from the published model, each an input or a statement of the
configuration and none of the mathematics: the depth is the parameters'
leading layers axis (the cell runs one layer of sixteen); the weights are
random, made from ``--seed`` and handed to both sides; no auxiliary router
loss (HF's default ``output_router_logits=False`` computes none); a row is a
packed sequence whose segments are documents: attention stays within a
segment, positions restart there, and padding (segment 0) and each
document's last token are out of the loss.

FedAvg as McMahan et al. write it: every client in turn starts from the
global model and runs one epoch of one-sequence SGD steps; the server takes
the mean of the clients' deltas weighted by the tokens each counted in its
loss, and applies it with momentum (FedAvgM, Hsu et al. 2019:
``m = beta m + delta``, ``g += lr m``). A client's loss of a round is the
token-weighted mean of its steps' losses, each at the parameters the step
started from.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _mm(a, b):
    """Every large matrix product of the block goes through here."""
    return a @ b


def _rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def _positions(segs):
    idx = jnp.arange(segs.shape[0])
    starts = jnp.concatenate([jnp.ones((1,), bool), segs[1:] != segs[:-1]])
    return idx - jax.lax.cummax(jnp.where(starts, idx, 0))


def _rope(x, pos, theta):
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos.astype(jnp.float32)[:, None] * inv[None]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None]
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + rot * sin


def gate_weights(x, router, top_k: int, norm_topk_prob: bool = False):
    """``(T, E)``: the router's softmax where an expert is among the token's
    top k, zero elsewhere."""
    probs = jax.nn.softmax(x @ router, axis=-1)     # float32 by statement
    kth = jnp.sort(probs, axis=-1)[:, -top_k][:, None]
    w = jnp.where(probs >= kth, probs, 0.0)
    return w / w.sum(-1, keepdims=True) if norm_topk_prob else w


def block(layer, h, segs, pos, cfg):
    t, hid = h.shape
    heads = cfg["num_attention_heads"]
    hd = hid // heads
    eps = cfg["rms_norm_eps"]
    x = _rms(h, layer["attn_norm"], eps)
    q = _rms(_mm(x, layer["q"]), layer["q_norm"], eps).reshape(t, heads, hd)
    k = _rms(_mm(x, layer["k"]), layer["k_norm"], eps).reshape(t, heads, hd)
    v = _mm(x, layer["v"]).reshape(t, heads, hd)
    q, k = _rope(q, pos, cfg["rope_theta"]), _rope(k, pos, cfg["rope_theta"])
    scores = _mm(q.transpose(1, 0, 2), k.transpose(1, 2, 0)) / np.sqrt(hd)
    idx = jnp.arange(t)
    allowed = (idx[:, None] >= idx[None]) & (segs[:, None] == segs[None])
    probs = jax.nn.softmax(jnp.where(allowed[None], scores, -1e30), axis=-1)
    ctx = _mm(probs, v.transpose(1, 0, 2)).transpose(1, 0, 2)
    h = h + _mm(ctx.reshape(t, hid), layer["o"])

    x = _rms(h, layer["mlp_norm"], eps)
    w = gate_weights(x, layer["router"], cfg["num_experts_per_tok"],
                     cfg.get("norm_topk_prob", False))

    @jax.checkpoint     # an expert's activations are recomputed in the
    def expert(acc, e):  # backward pass, not kept for all 64: memory only
        gate, up, down, we = e
        act = jax.nn.silu(_mm(x, gate)) * _mm(x, up)
        return acc + we[:, None] * _mm(act, down), None

    # a loop over the experts, each on every token: dense, no dispatch
    moe, _ = jax.lax.scan(expert, jnp.zeros_like(h),
                          (layer["gate"], layer["up"], layer["down"], w.T))
    return h + moe


def sequence_loss(params, row, cfg):
    """``(summed next-token loss, tokens counted)`` of one packed row
    ``(2, T)``: tokens and segment ids."""
    tokens, segs = row[0], row[1]
    pos = _positions(segs)
    h = params["embed"][tokens]
    depth = params["layers"]["q"].shape[0]
    for i in range(depth):
        h = block(jax.tree.map(lambda a: a[i], params["layers"]), h, segs,
                  pos, cfg)
    logits = _mm(_rms(h, params["final_norm"], cfg["rms_norm_eps"]),
                 params["head"])
    logp = jax.nn.log_softmax(logits, axis=-1)
    labels = jnp.concatenate([tokens[1:], jnp.zeros((1,), tokens.dtype)])
    nxt = jnp.concatenate([segs[1:], jnp.zeros((1,), segs.dtype)])
    valid = ((segs > 0) & (nxt == segs)).astype(jnp.float32)
    ll = jnp.take_along_axis(logp, labels[:, None], axis=1)[:, 0]
    return -(ll * valid).sum(), valid.sum()


def mean_loss(params, row, cfg):
    loss, count = sequence_loss(params, row, cfg)
    return loss / jnp.maximum(count, 1.0), (loss, count)


def fedavgm_rounds(init_params, client_rows, rounds: int, cfg: dict,
                   learning_rate: float, momentum: float = 0.9,
                   server_lr: float = 1.0):
    """``rounds`` rounds from the global ``init_params`` (host arrays) over
    ``client_rows`` (a list, one ``(n_c, 2, T)`` int32 array a client).
    Returns ``(losses (rounds, C), global parameters after the last round,
    on the host)``.

    The device holds the global model, the round's sum of weighted deltas,
    one client's copy and (inside a step) its gradient; the server's
    momentum waits on the host while the clients train, so the reference's
    peak stays under the round program's."""
    frozen = {k: (tuple(v) if isinstance(v, list) else v) for k, v in cfg.items()}

    @functools.partial(jax.jit, donate_argnums=(0,))
    def sgd_step(p, row):
        (_, (loss, count)), g = jax.value_and_grad(
            lambda q: mean_loss(q, row, frozen), has_aux=True)(p)
        return jax.tree.map(lambda a, b: a - learning_rate * b, p, g), loss, count

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def add_delta(acc, p, g, w):
        return jax.tree.map(lambda a, b, c: a + w * (b - c), acc, p, g)

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def server(g, m, acc, total):
        m = jax.tree.map(lambda a, b: momentum * a + b / total, m, acc)
        return jax.tree.map(lambda a, b: a + server_lr * b, g, m), m

    copy = jax.jit(lambda t: jax.tree.map(jnp.copy, t))
    g = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), init_params)
    m = jax.tree.map(lambda a: np.zeros(a.shape, np.float32), g)
    losses = []
    with jax.default_matmul_precision("highest"):
        for _ in range(rounds):
            acc = jax.tree.map(jnp.zeros_like, g)
            row_losses, total = [], 0.0
            for rows in client_rows:
                p, steps = copy(g), []
                for row in rows:
                    p, loss, count = sgd_step(p, jnp.asarray(row))
                    steps.append((loss, count))
                loss_sum = sum(float(a) for a, _ in steps)
                counted = sum(float(b) for _, b in steps)
                acc = add_delta(acc, p, g, counted)
                total += counted
                row_losses.append(loss_sum / max(counted, 1.0))
            g, m = server(g, jax.tree.map(jnp.asarray, m), acc, total)
            m = jax.tree.map(np.asarray, m)
            losses.append(row_losses)
    return np.asarray(losses, np.float64), jax.tree.map(np.asarray, g)
