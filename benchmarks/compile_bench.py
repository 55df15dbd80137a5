"""Cold vs warm time-to-first-round through the serialized-executable cache.

ROUND5 measured the sweep's cold compile at 90-207 s on a contended box
against a 29 s warm-run win — compilation, not compute, dominates short
runs. This benchmark captures the remedy's two numbers for the round
program family:

    cold: trace + XLA compile (stored to a fresh ProgramCache) + the
          first chunk of rounds executed to completion;
    warm: a FRESH ProgramCache instance on the same directory
          deserializes the executable (no trace, no XLA) + the same
          first chunk from the same initial state.

The warm path must be at least --min-speedup (default 5) times faster
to first-round completion, and its outputs must be BITWISE equal to the
fresh-compiled program's — a deserialized executable is the same
program, not an approximation of it. A violation crashes the benchmark
rather than recording the number.

Run: ``python benchmarks/compile_bench.py`` (~10 s on the CPU box).
Emits bench.py-style output: detail lines on stderr, one full JSON blob
last on stdout (and to --out).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, __file__.rsplit("/", 2)[0])

import numpy as np


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--preset", default="income-8")
    ap.add_argument("--synthetic-rows", type=int, default=2048,
                    help="synthetic dataset rows (0 = the preset's real "
                         "data; default keeps the benchmark hermetic)")
    ap.add_argument("--rounds-per-step", type=int, default=4,
                    help="chunk width of the benchmarked round program")
    ap.add_argument("--cache", default=None, metavar="DIR",
                    help="cache dir (default: <checkout>/.jax_cache; "
                         "JAX_COMPILATION_CACHE_DIR, when set, wins). This "
                         "program's own entry is dropped first, so the cold "
                         "leg is genuinely cold")
    ap.add_argument("--min-speedup", type=float, default=5.0,
                    help="required cold/warm time-to-first-round ratio")
    ap.add_argument("--out", default="BENCH_COMPILE.json",
                    help="file the JSON result is written to")
    args = ap.parse_args(argv)

    import jax

    from fedtpu.compilation import (ProgramCache, program_cache_dir,
                                    program_config_slice,
                                    program_fingerprint)
    from fedtpu.config import get_preset
    from fedtpu.orchestration.loop import build_experiment
    from fedtpu.utils.trees import clone

    cfg = get_preset(args.preset)
    if args.synthetic_rows:
        cfg = dataclasses.replace(cfg, data=dataclasses.replace(
            cfg.data, csv_path=None, dataset_name=None,
            synthetic_rows=args.synthetic_rows))
    exp = build_experiment(cfg)
    step = exp.make_step(args.rounds_per_step)
    key = program_fingerprint(
        "round", config=program_config_slice(cfg), mesh=exp.mesh,
        args=(exp.state, exp.batch),
        extra={"rounds_per_step": int(args.rounds_per_step)})

    # The ProgramCache of the one cache directory every program of the
    # repo uses (never a temp dir: a cache that moves never hits). The
    # cold leg is made cold by dropping this program's own entry first.
    cache_dir = program_cache_dir(args.cache)
    cache = ProgramCache(cache_dir)
    for stale in cache._paths(key):
        if os.path.exists(stale):
            os.remove(stale)

    # COLD leg: trace + XLA compile (+ store) + first chunk of rounds.
    # The state is cloned per call: the round step donates its state
    # buffer, and both legs must start from identical bits.
    t0 = time.perf_counter()
    entry = cache.get_or_compile(key, step, exp.state, exp.batch,
                                 label="bench-round")
    cold_compile_s = time.perf_counter() - t0
    out_cold = entry.compiled(clone(exp.state), exp.batch)
    jax.block_until_ready(out_cold)
    cold_total_s = time.perf_counter() - t0

    # WARM leg: a fresh ProgramCache instance deserializes — no trace,
    # no XLA compile — then runs the same chunk from the same state.
    t0 = time.perf_counter()
    warm = ProgramCache(cache_dir).load(key)
    if warm is None:
        raise SystemExit("compile_bench: warm load failed (serialization "
                         "unsupported on this backend?)")
    warm_lookup_s = time.perf_counter() - t0
    out_warm = warm.compiled(clone(exp.state), exp.batch)
    jax.block_until_ready(out_warm)
    warm_total_s = time.perf_counter() - t0

    # The deserialized executable is the SAME program: bitwise equality
    # over every output leaf (new state + metrics), not approximate.
    pairs = list(zip(jax.tree.leaves(out_cold), jax.tree.leaves(out_warm)))
    bitwise_equal = bool(pairs) and all(
        np.array_equal(np.asarray(a), np.asarray(b)) for a, b in pairs)
    if not bitwise_equal:
        raise SystemExit("compile_bench: deserialized program diverged "
                         "bitwise from the fresh-compiled one")

    speedup = cold_total_s / warm_total_s
    if speedup < args.min_speedup:
        raise SystemExit(
            f"compile_bench: warm time-to-first-round only {speedup:.2f}x "
            f"faster than cold (need >= {args.min_speedup}x): "
            f"cold {cold_total_s:.3f} s vs warm {warm_total_s:.3f} s")

    result = {
        "metric": "compile_cache_time_to_first_round",
        "preset": args.preset,
        "rounds_per_step": int(args.rounds_per_step),
        "key": key,
        "cache_dir": cache_dir,
        "payload_bytes": int(entry_meta_bytes(cache, key)),
        "cold_compile_s": round(cold_compile_s, 4),
        "cold_time_to_first_round_s": round(cold_total_s, 4),
        "warm_lookup_ms": round(warm_lookup_s * 1e3, 2),
        "warm_time_to_first_round_s": round(warm_total_s, 4),
        "speedup_time_to_first_round": round(speedup, 2),
        "speedup_compile_vs_lookup": round(
            cold_compile_s / max(warm_lookup_s, 1e-9), 2),
        "bitwise_equal": bitwise_equal,
        "backend": jax.default_backend(),
        "device_count": jax.device_count(),
    }
    detail = [
        f"[compile_bench] cold: compile {cold_compile_s:.3f} s, "
        f"first round done at {cold_total_s:.3f} s",
        f"[compile_bench] warm: deserialize {warm_lookup_s * 1e3:.1f} ms, "
        f"first round done at {warm_total_s:.3f} s",
        f"[compile_bench] time-to-first-round speedup {speedup:.1f}x "
        f"(compile-vs-lookup {result['speedup_compile_vs_lookup']:.0f}x), "
        f"outputs bitwise equal: {bitwise_equal}",
    ]
    for line in detail:
        print(line, file=sys.stderr)
    blob = json.dumps(result)
    if args.out:
        with open(args.out, "w") as f:
            f.write(blob + "\n")
    sys.stderr.flush()
    print(blob, flush=True)
    return 0


def entry_meta_bytes(cache, key) -> int:
    meta = cache._read_meta(cache._paths(key)[1])
    return int((meta or {}).get("payload_bytes") or 0)


if __name__ == "__main__":
    raise SystemExit(main())
