"""Small pytree utilities."""

from __future__ import annotations

import jax
import numpy as np


def param_count(tree) -> int:
    return sum(int(np.prod(l.shape)) for l in jax.tree.leaves(tree))


def tree_bytes(tree) -> int:
    return sum(int(np.prod(l.shape)) * l.dtype.itemsize
               for l in jax.tree.leaves(tree))


def identity(tree):
    """Module-level identity for reshard/replicate jits
    (``jax.jit(identity, out_shardings=...)``): jit's cache is keyed on
    function identity, so a fresh lambda per call site would retrace and
    recompile every time. Shared by the orchestration loop's metric
    replication and the checkpoint restore's reshard."""
    return tree


def to_numpy(tree):
    """Device -> host copy of a whole pytree."""
    return jax.tree.map(lambda l: np.asarray(jax.device_get(l)), tree)


def clone(tree):
    """Fresh device buffers with the same values and shardings.

    The compiled round step DONATES its input state
    (fedtpu.parallel.round.build_round_fn): after ``new = round_step(state,
    batch)`` the old ``state``'s buffers are gone. Callers that need the
    pre-step state afterwards (A/B comparisons, snapshots) should step a
    ``clone(state)`` instead.
    """
    return jax.tree.map(
        lambda l: l.copy() if isinstance(l, jax.Array) else l, tree)


def per_device_bytes(tree) -> dict:
    """Measured live bytes per device id: sums each leaf's ACTUAL shard
    buffers (``addressable_shards``), so replicated leaves count fully on
    every device they occupy. The measurement behind the 2-D engine's
    memory proof (tests/test_tp.py)."""
    per: dict = {}
    for leaf in jax.tree.leaves(tree):
        for s in leaf.addressable_shards:
            per[s.device.id] = per.get(s.device.id, 0) + s.data.nbytes
    return per


def max_device_bytes(tree) -> int:
    """Max over devices of measured live bytes for ``tree``."""
    return max(per_device_bytes(tree).values())
