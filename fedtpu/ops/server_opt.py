"""Server-side optimizers for delta-based federated aggregation (FedOpt).

The reference's only aggregation rule is parameter averaging — the rank-0
weighted mean of client weights (FL_CustomMLPCLassifierImplementation_
Multiple_Rounds.py:108-119) or the uniform mean (hyperparameters_tuning.py:37).
Averaging is the ``server_lr=1, no-momentum`` point of a broader family
("Adaptive Federated Optimization", Reddi et al. 2021): treat the weighted
mean of client *updates*

    delta = sum_i w_i (trained_i - g) / sum_i w_i

as a pseudo-gradient and apply a first-order server optimizer to the global
model ``g``. fedtpu implements the family in-graph: the delta reduction rides
the same ICI collectives as FedAvg (fedtpu.parallel.round), and the server
state (momentum / second-moment pytrees) lives replicated in device memory —
the host never sees a weight byte, exactly as in the FedAvg path.

    fedavgm    g += lr * m,           m = beta * m + delta
    fedadagrad g += lr * m/(sqrt(v)+tau),  v = v + delta^2
    fedyogi    ...                    v = v - (1-b2) delta^2 sign(v - delta^2)
    fedadam    ...                    v = b2 v + (1-b2) delta^2
    (all three adaptives share m = b1 * m + (1-b1) * delta)

``fedavgm`` with ``momentum=0, lr=1`` reproduces FedAvg exactly:
``g + sum w_i (t_i - g) / sum w_i == sum w_i t_i / sum w_i`` — pinned by
``tests/test_server_opt.py``.

No bias correction (matching the published algorithms, which initialize
``m=v=0`` and rely on ``tau`` for early-round stability).
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp

SERVER_OPTIMIZERS = ("fedavgm", "fedadagrad", "fedyogi", "fedadam")


@dataclasses.dataclass(frozen=True)
class ServerOptimizer:
    """``init(g) -> state``; ``update(delta, state) -> (step, state)`` with
    the server applying ``g_new = g + step``. Pure pytree-to-pytree functions:
    they trace cleanly inside the shard_map'd round scan.

    ``begin`` / ``finish``, where an optimizer has them, are the same update
    in accumulating form, for an engine that sums the clients' normalised
    deltas one at a time (fedtpu.parallel.stateless): ``begin(state)`` is
    what the sum starts from and ``finish(total, state) -> (step, state)``
    closes it, with ``finish(begin(state) + delta, state) ==
    update(delta, state)``. FedAvgM's sum starts at ``momentum * m`` and IS
    the new momentum, so its accumulator needs no buffer of its own."""

    name: str
    init: Callable
    update: Callable
    begin: Optional[Callable] = None
    finish: Optional[Callable] = None


def _zeros_like_tree(tree):
    return jax.tree.map(jnp.zeros_like, tree)


def identity_server_optimizer() -> "ServerOptimizer":
    """The FedAvg point of the family: ``fedavgm(momentum=0, lr=1)`` —
    ``g + mean_delta`` is exactly parameter averaging. The single shared
    definition for every caller that needs the delta path without a real
    server optimizer (e.g. DP-only aggregation)."""
    return make_server_optimizer("fedavgm", learning_rate=1.0, momentum=0.0)


def make_server_optimizer(name: str, learning_rate: float = 1.0,
                          momentum: float = 0.9, b1: float = 0.9,
                          b2: float = 0.99, tau: float = 1e-3
                          ) -> ServerOptimizer:
    """Build one of ``SERVER_OPTIMIZERS``. Defaults follow Reddi et al.
    (b2=0.99, tau=1e-3); ``learning_rate`` defaults to 1.0 so fedavgm
    degenerates to FedAvg when momentum is 0."""
    if name not in SERVER_OPTIMIZERS:
        raise ValueError(f"unknown server optimizer {name!r}; "
                         f"available: {SERVER_OPTIMIZERS}")

    if name == "fedavgm":

        def init(g):
            return {"m": _zeros_like_tree(g)}

        def update(delta, state):
            m = jax.tree.map(lambda mm, d: momentum * mm + d,
                             state["m"], delta)
            return finish(m, state)

        def begin(state):
            return jax.tree.map(lambda mm: momentum * mm, state["m"])

        def finish(m, state):
            return jax.tree.map(lambda mm: learning_rate * mm, m), {"m": m}

        return ServerOptimizer(name, init, update, begin, finish)

    def init(g):
        return {"m": _zeros_like_tree(g), "v": _zeros_like_tree(g)}

    def second_moment(v, d):
        if name == "fedadagrad":
            return v + jnp.square(d)
        if name == "fedyogi":
            sq = jnp.square(d)
            return v - (1.0 - b2) * sq * jnp.sign(v - sq)
        return b2 * v + (1.0 - b2) * jnp.square(d)  # fedadam

    def update(delta, state):
        m = jax.tree.map(lambda mm, d: b1 * mm + (1.0 - b1) * d,
                         state["m"], delta)
        v = jax.tree.map(second_moment, state["v"], delta)
        step = jax.tree.map(
            lambda mm, vv: learning_rate * mm / (jnp.sqrt(vv) + tau), m, v)
        return step, {"m": m, "v": v}

    return ServerOptimizer(name, init, update)


def clip_by_global_norm(delta, clip_norm: float):
    """Per-client L2 clipping of an update pytree whose leaves carry a
    leading clients axis: each client's update is scaled by
    ``min(1, clip_norm / ||delta_c||_2)`` with the norm taken over ALL leaves
    jointly (the DP-FedAvg sensitivity bound — one clip per client, not per
    tensor). Returns ``(clipped_delta, norms)`` with ``norms`` shaped
    ``(clients,)`` for observability."""
    leaves = jax.tree.leaves(delta)
    sq = sum(jnp.sum(jnp.square(l.astype(jnp.float32)),
                     axis=tuple(range(1, l.ndim))) for l in leaves)
    norms = jnp.sqrt(sq)
    factor = jnp.minimum(1.0, clip_norm / jnp.maximum(norms, 1e-12))

    def scale(l):
        shape = (l.shape[0],) + (1,) * (l.ndim - 1)
        return (l * factor.reshape(shape).astype(l.dtype))

    return jax.tree.map(scale, delta), norms


def gaussian_noise_tree(key: jax.Array, tree, std):
    """i.i.d. N(0, std^2) noise shaped like ``tree``. The per-leaf key is
    folded from the leaf's position so the draw is deterministic in
    ``(key, tree structure)`` — every device generates IDENTICAL noise, which
    is what keeps the server model replicated without a broadcast."""
    leaves, treedef = jax.tree.flatten(tree)
    noises = [
        (jax.random.normal(jax.random.fold_in(key, i), l.shape)
         * std).astype(l.dtype)
        for i, l in enumerate(leaves)
    ]
    return jax.tree.unflatten(treedef, noises)
