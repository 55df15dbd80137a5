"""Pre-compile a preset's program family into a cache dir (``fedtpu
warmup``).

Pod-launch / CI use: pay every cold compile once on a toolbox machine
(or in a CI warm stage), ship the cache directory, and the real job
deserializes its executables in milliseconds instead of stalling its
first rounds on XLA. The "program family" is what a job actually
launches: the round program at each requested chunk width plus the eval
program. The same directory also hosts jax's persistent backend cache,
so even a program missing from the AOT store skips the XLA backend
compile.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, Optional, Sequence

from fedtpu.compilation.cache import (ProgramCache,
                                      configure_persistent_cache,
                                      program_cache_dir, program_fingerprint)

__all__ = ["program_config_slice", "warmup_preset"]


def program_config_slice(cfg) -> Dict[str, Any]:
    """The part of an ``ExperimentConfig`` that shapes the compiled round
    program. Telemetry paths, logging cadence and checkpoint locations
    are deliberately excluded — they vary per run without changing the
    program, and including them would turn every run into a cold miss."""
    return {
        "data": dataclasses.asdict(cfg.data),
        "shard": dataclasses.asdict(cfg.shard),
        "model": dataclasses.asdict(cfg.model),
        "optim": dataclasses.asdict(cfg.optim),
        "fed": dataclasses.asdict(cfg.fed),
        "run": {
            "model_parallel": cfg.run.model_parallel,
            "halt_on_nonfinite": cfg.run.halt_on_nonfinite,
            "pipelined_stop": cfg.run.pipelined_stop,
            "mesh_devices": cfg.run.mesh_devices,
        },
    }


def warmup_preset(
    preset: str = "income-8",
    cache_dir: Optional[str] = None,
    widths: Optional[Sequence[int]] = None,
    synthetic_rows: Optional[int] = None,
    include_eval: bool = True,
    tracer=None,
    registry=None,
) -> dict:
    """Compile (or verify cached) the preset's program family.

    Returns a JSON-serializable report: one row per program with its
    cache key, cold/warm state and build/deserialize seconds, plus the
    cache's aggregate hit/miss stats. Re-running against a populated
    cache is the verification mode: every row comes back ``warm``.
    """
    from fedtpu.config import get_preset
    from fedtpu.orchestration.loop import build_experiment
    from fedtpu.telemetry import build_manifest

    t_begin = time.perf_counter()
    cache_dir = configure_persistent_cache(cache_dir)
    cache = ProgramCache(program_cache_dir(cache_dir),
                         tracer=tracer, registry=registry)

    cfg = get_preset(preset)
    if synthetic_rows is not None:
        # CI mode: probe compilation, not accuracy — same forcing as
        # ``fedtpu check``.
        cfg = dataclasses.replace(
            cfg, data=dataclasses.replace(cfg.data, csv_path=None,
                                          dataset_name=None,
                                          synthetic_rows=synthetic_rows))
    if widths is None:
        widths = sorted({1, max(1, cfg.run.rounds_per_step)})

    exp = build_experiment(cfg)
    slice_ = program_config_slice(cfg)
    programs = []
    for width in widths:
        step = exp.make_step(int(width))
        key = program_fingerprint(
            "round", config=slice_, mesh=exp.mesh,
            args=(exp.state, exp.batch),
            extra={"rounds_per_step": int(width)})
        entry = cache.get_or_compile(key, step, exp.state, exp.batch,
                                     label=f"round[w={width}]")
        programs.append({"label": f"round[w={width}]", "key": entry.key,
                         "warm": entry.warm,
                         "seconds": round(entry.seconds, 4)})
    if include_eval:
        params = exp.global_fn(exp.state)
        ds = exp.dataset
        key = program_fingerprint(
            "eval", config=slice_, mesh=exp.mesh,
            args=(params, ds.x_test, ds.y_test))
        entry = cache.get_or_compile(key, exp.eval_step, params,
                                     ds.x_test, ds.y_test, label="eval")
        programs.append({"label": "eval", "key": entry.key,
                         "warm": entry.warm,
                         "seconds": round(entry.seconds, 4)})

    report = {
        "preset": preset,
        "cache_dir": cache_dir,
        "widths": [int(w) for w in widths],
        "programs": programs,
        "total_s": round(time.perf_counter() - t_begin, 4),
        **cache.stats(),
    }
    if tracer is not None:
        tracer.event("manifest", **build_manifest(
            cfg=cfg, mesh=exp.mesh,
            extra={"program": "warmup", **cache.manifest_info()}))
    return report
