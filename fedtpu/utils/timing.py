"""Wall-clock timing — the observability the reference lacks entirely
(SURVEY.md §5: no timers, no profiler; ``print(flush=True)`` only).

Fetch-forced timing (``force_fetch`` / ``assert_above_flops_floor``): JAX
dispatch is asynchronous, so a timed window has to end on something that
waits for the device. A window in this repo closes with ``force_fetch`` —
a host fetch of one scalar that depends on the whole program, a completion
proof on any backend — and ``assert_above_flops_floor`` refuses a time the
device could not physically have achieved (a window that measured the
enqueue). On a local TPU v5e ``jax.block_until_ready`` waits for the
device too: chip_smoke.py (PR 21, 'TPU v5 lite' x1) observed a 64-long
4096^2 bf16 matmul chain at 46.7 ms closed by it against 47.1 ms closed by
a fetch, and a trivial jitted call at 0.60 ms against 0.93 ms. The fetch
stays as the one rule because it costs a third of a millisecond and holds
everywhere. The product path's timing is the benchmark's (``perfbench/``,
PERF.md); the device's peaks are ``perfbench/peaks.json``."""

from __future__ import annotations

import time


class Timer:
    """Accumulates per-lap wall-clock times (seconds)."""

    def __init__(self):
        self.laps = []
        self._t0 = None

    def start(self):
        self._t0 = time.perf_counter()
        return self

    def lap(self) -> float:
        now = time.perf_counter()
        dt = now - self._t0
        self._t0 = now
        self.laps.append(dt)
        return dt

    @property
    def total(self) -> float:
        return sum(self.laps)

    def mean(self, skip_first: int = 0) -> float:
        laps = self.laps[skip_first:] or self.laps
        return sum(laps) / max(len(laps), 1)


def force_fetch(tree) -> float:
    """Fetch one host value that depends on ``tree`` — a completion proof
    on any backend (see module docstring). The reduction to a scalar
    happens ON DEVICE so only ~4 bytes cross the host link — fetching a
    whole array would add its transfer to the timed window. Returns the
    fetched scalar so callers can sanity-check it."""
    import jax
    import numpy as np

    leaves = [l for l in jax.tree.leaves(tree) if isinstance(l, jax.Array)]
    if not leaves:
        # A host-only tree proves nothing about device completion — a timed
        # window "closed" here would silently measure dispatch rate again.
        # Refuse rather than look like success.
        raise TypeError(
            "force_fetch: no device-backed (jax.Array) leaf in the tree — "
            "fetching host values proves nothing about device completion")
    leaf = leaves[-1]
    if getattr(leaf, "ndim", 0):
        leaf = leaf.reshape(-1)[-1]        # device-side slice, scalar out
    return float(np.asarray(leaf))


def program_flops(compiled) -> float:
    """Flops from an executable's XLA cost analysis (0.0 when absent)."""
    cost = compiled.cost_analysis() or {}
    return float(cost.get("flops", 0.0))


def program_bytes_accessed(compiled) -> float:
    """Bytes accessed from an executable's XLA cost analysis (0.0 when
    absent) — the roofline denominator's memory side: flops / bytes is
    the program's arithmetic intensity (docs/observability.md)."""
    cost = compiled.cost_analysis() or {}
    return float(cost.get("bytes accessed", 0.0))


def assert_above_flops_floor(sec_per_round: float, flops_per_round: float,
                             peak_flops: float, label: str = "") -> float:
    """Physics guard for benchmark numbers: no program can run its FLOPs
    faster than 2x the measured peak (the 2x absorbs peak-measurement noise
    and mixed-precision ambiguity). A violation means the timing methodology
    is broken (round 1: async dispatch measured instead of compute) and MUST
    fail loudly rather than record a fantasy number. Returns the floor."""
    floor = flops_per_round / (2.0 * peak_flops)
    if sec_per_round < floor:
        raise RuntimeError(
            f"timing methodology broken{' (' + label + ')' if label else ''}:"
            f" measured {sec_per_round:.3e} s/round but the program costs "
            f"{flops_per_round:.3e} FLOPs and the device measures "
            f"{peak_flops:.3e} FLOP/s peak — physical floor "
            f"{floor:.3e} s/round. The timed window is not capturing "
            "execution (dispatch-rate artifact); close it with force_fetch.")
    return floor
