"""The plain reference: FedAvg as the papers write it, in straight
``jax.numpy`` and float32 at ``highest`` matmul precision, independent of
``fedtpu/``. One full-batch local step per client and round (Adam with the
reference's StepLR), clients one after another (``lax.map``: a loop, no
batching of one client's rows with another's beyond the block ``lax.map``
runs at a time), then the data-size-weighted mean of their parameters, which every client starts the next round from. It is the "plain
single-worker run of the same task" the system's rounds are held to.

The initial parameters are an input, like the data: the benchmark makes them
from ``--seed`` and hands the same stack to both sides.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax


def mlp_forward(params, x):
    """``Linear -> ReLU`` per hidden size, then a ``Linear`` logits head
    (FL_CustomMLP...:12-25). params: ``{'layers': [{'w', 'b'}, ...]}``."""
    h = x
    layers = params["layers"]
    for i, lyr in enumerate(layers):
        h = h @ lyr["w"] + lyr["b"]
        if i < len(layers) - 1:
            h = jnp.maximum(h, 0.0)
    return h


def convnet_forward(params, x):
    """``[Conv3x3(SAME) -> ReLU -> MaxPool2x2]`` per entry of ``convs``,
    flatten, ``Dense -> ReLU -> Dense``; x is ``(N, H*W*C)`` rows of NHWC
    images."""
    cin = params["convs"][0]["w"].shape[2]
    side = int(round((x.shape[1] // cin) ** 0.5))
    h = x.reshape(x.shape[0], side, side, cin)
    for conv in params["convs"]:
        h = lax.conv_general_dilated(
            h, conv["w"], (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        h = jnp.maximum(h + conv["b"], 0.0)
        n, hh, ww, c = h.shape
        h = h.reshape(n, hh // 2, 2, ww // 2, 2, c).max(axis=(2, 4))
    h = h.reshape(h.shape[0], -1)
    h = jnp.maximum(h @ params["dense"]["w"] + params["dense"]["b"], 0.0)
    return h @ params["head"]["w"] + params["head"]["b"]


FORWARD = {"mlp": mlp_forward, "convnet": convnet_forward}


def cross_entropy(logits, y, mask):
    """Mean softmax cross-entropy over the rows where ``mask`` is 1."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, y[:, None], axis=1)[:, 0]
    return -(ll * mask).sum() / jnp.maximum(mask.sum(), 1.0)


def fedavg_rounds(kind: str, init_params, x, y, mask, rounds: int, optim: dict):
    """Run ``rounds`` FedAvg rounds from the per-client ``init_params``
    (leaves ``(C, ...)``) on the padded client shards ``x (C, N, F)``,
    ``y (C, N)``, ``mask (C, N)``. Returns ``(losses (rounds, C), global
    parameters after the last round)``; the loss of a round is each
    client's training loss at the parameters it started the round from.
    ``init_params`` are consumed (donated to the first round)."""
    forward = FORWARD[kind]
    lr0 = float(optim["learning_rate"])
    b1, b2, eps = float(optim["b1"]), float(optim["b2"]), float(optim["eps"])
    step_size, gamma = int(optim["steplr_step_size"]), float(optim["steplr_gamma"])

    def client_step(args):
        p, m, v, xc, yc, mc, t = args
        loss, g = jax.value_and_grad(
            lambda q: cross_entropy(forward(q, xc), yc, mc))(p)
        lr = lr0 * gamma ** jnp.floor(t / step_size)      # StepLR, stepped per round
        m = jax.tree.map(lambda a, b: b1 * a + (1 - b1) * b, m, g)
        v = jax.tree.map(lambda a, b: b2 * a + (1 - b2) * b * b, v, g)
        c1, c2 = 1 - b1 ** (t + 1), 1 - b2 ** (t + 1)
        p = jax.tree.map(
            lambda w, a, b: w - lr * (a / c1) / (jnp.sqrt(b / c2) + eps),
            p, m, v)
        return p, m, v, loss

    # clients go through in blocks of about 4M input values: the whole of
    # a small model's clients would be thousands of turns of a loop
    block = max(1, min(x.shape[0], 4_000_000 // max(1, x[0].size)))

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def one_round(p, m, v, t, x, y, mask):       # data as arguments: a
        c = x.shape[0]                           # closure would bake 600 MB
        tt = jnp.full((c,), t, jnp.float32)      # into the program
        p, m, v, loss = lax.map(client_step, (p, m, v, x, y, mask, tt),
                                batch_size=block)
        n = mask.sum(axis=1)
        w = n / n.sum()
        g = jax.tree.map(
            lambda a: jnp.tensordot(w, a, axes=1), p)     # data-size weights
        p = jax.tree.map(lambda a, b: jnp.broadcast_to(a[None], b.shape), g, p)
        return p, m, v, loss, g

    with jax.default_matmul_precision("highest"):
        # the round donates its state, init_params included (they are
        # consumed): the reference then holds one set of client states on
        # the chip, not two, and stays under the round program's footprint
        p = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), init_params)
        m = jax.tree.map(jnp.zeros_like, p)
        v = jax.tree.map(jnp.zeros_like, p)
        losses, g = [], None
        for t in range(rounds):
            p, m, v, loss, g = one_round(p, m, v, jnp.float32(t), x, y, mask)
            losses.append(loss)
        return jnp.stack(losses), g
