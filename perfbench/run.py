"""One cell, one run: ``python3 -m perfbench.run --workload <cell> --seed <n>
--seconds <s> --trace <0|1>`` from the root of a checkout.

The last line of standard output is the contract's JSON object and holds
only its keys; what else is worth keeping (the sizing, each job's time, the
comparison with the reference) goes on earlier lines and into
``perfbench/out/``. ``--rehearse-cpu`` walks the same control flow on the CPU
at the tiny sizes a configuration's ``rehearsal`` block states; it prints no
metric, never says ``correct: true`` and exits 10.
"""

import time

T0 = time.perf_counter()        # set-up is counted from here

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

# The interpreter's bytecode is kept inside the checkout like the compiled
# programs: where the installation ships no .pyc and forbids writing them
# (PYTHONDONTWRITEBYTECODE), every run would compile jax's and the program's
# seven hundred modules from source again, two seconds of every set-up.
sys.dont_write_bytecode = False
sys.pycache_prefix = os.path.join(HERE, "out", "pycache")

import argparse
import json

REHEARSAL_EXIT = 10


class Context:
    """What a driver is handed: the cell and its files, the run's
    arguments, the harness's clocks, compile log and evidence."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def say(obj) -> None:
    print(json.dumps(obj, default=float), flush=True)


def gate(chips: int, rehearsal: bool):
    """The first touch of JAX. Returns ``(device dict, peaks, seconds)`` or
    exits non-zero, printing no result, where JAX found no TPU, a kind the
    table of peaks does not know, or fewer chips than the cell asks for."""
    t = time.perf_counter()
    import jax
    devices = jax.devices()
    init_s = time.perf_counter() - t
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    with open(os.path.join(HERE, "peaks.json")) as fh:
        peaks = json.load(fh)
    if rehearsal:
        ok = len(devices) >= chips
        peak = next(v for k, v in peaks.items() if not k.startswith("_"))
    else:
        ok = (device["platform"] == "tpu" and device["kind"] in peaks
              and len(devices) >= chips)
        peak = peaks.get(device["kind"])
    if not ok:
        print(f"perfbench: refusing to measure on {device}; the cell needs "
              f"{chips} TPU chip(s) of a kind in peaks.json", file=sys.stderr)
        raise SystemExit(3)
    return device, peak, init_s


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="perfbench.run")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)

    import importlib.util
    if importlib.util.find_spec("fedtpu") is None:
        print("perfbench: no fedtpu package beside perfbench/: the benchmark "
              "measures the program and cannot run without it", file=sys.stderr)
        return 4

    from perfbench import manifest as manifest_mod
    manifest = manifest_mod.load(ROOT)
    cell = manifest.cell(args.workload)
    config = manifest.config(cell["config"])
    traffic = manifest.traffic(cell["traffic"])
    seconds = float(args.seconds or manifest.doc["run_seconds"])

    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={cell['chips']}")
        os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")
        seconds = min(seconds, 4.0)

    # every program of the run goes into the persistent cache, the ones
    # that compile in under the program's half second too (some fifty of
    # them a run): a run after the first compiles nothing in set-up. Set
    # before jax is imported; the program's own rule yields to it.
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    device, peaks, init_s = gate(cell["chips"], args.rehearse_cpu)
    import jax
    from fedtpu.compilation import configure_persistent_cache
    from perfbench.evidence import Evidence
    from perfbench.monitor import CompileLog

    cache_dir = configure_persistent_cache()        # before the first compile
    compiles = CompileLog().listen()
    evidence = Evidence(compiles=compiles, manifest=manifest)
    evidence.facts["peaks"] = peaks
    out_dir = os.path.join(HERE, "out")
    os.makedirs(out_dir, exist_ok=True)
    def peak_bytes() -> int:
        return int(max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                       for d in jax.local_devices()[:cell["chips"]]))

    ctx = Context(cell=cell, config=config, traffic=traffic, seed=args.seed,
                  seconds=seconds, run_seconds=float(manifest.doc["run_seconds"]),
                  trace=bool(args.trace),
                  rehearsal=args.rehearse_cpu, t0=T0, clocks=evidence.clocks,
                  compiles=compiles, evidence=evidence, out_dir=out_dir,
                  memory={}, peak_bytes=peak_bytes)
    ctx.clocks["import_s"] = time.perf_counter() - T0 - init_s
    ctx.clocks["backend_init_s"] = init_s

    driver = importlib.import_module(f"perfbench.drivers.{traffic['driver']}")
    out = driver.run(ctx)

    # the runtime's counter misses program scratch; the driver says what
    # the compiler counts for the program the jobs run (train.py)
    ctx.memory["runtime_peak"] = peak_bytes()
    peak = max(ctx.memory["runtime_peak"],
               ctx.memory.get("round_program", {}).get("total", 0))
    device = {**device, "memory_peak_bytes": peak,
              **out.get("device_extra", {})}
    measured = {"setup_s": ctx.clocks["setup_s"],
                "peak_hbm_mb": peak / 1e6, **out["metrics"]}
    group = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in manifest.metrics_of(group, cell["name"]):
        value = (evidence.metric(m["name"]) if args.trace
                 else measured.get(m["name"]))
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    head = {"workload": cell["name"], "seed": args.seed, "seconds": seconds,
            "trace": args.trace, "cache_dir": cache_dir,
            "clocks": ctx.clocks, "notes": evidence.notes,
            "peak_bytes": ctx.memory,
            "setup_compiles": compiles.count("setup", "warmup"),
            "setup_compile_s": compiles.seconds("setup", "warmup"),
            "setup_cache_hits": compiles.hit_count("setup", "warmup")}
    for line in [head, *out["lines"]]:
        say(line)
    last = {"correct": out["correct"], "attempted": out["attempted"],
            "failed": out["failed"], "metrics": metrics, "device": device}
    if args.trace and out.get("breakdown"):
        last["breakdown"] = out["breakdown"]
    if args.rehearse_cpu:
        # a walk through the control flow, not a measurement: no metric,
        # no device, never correct
        last = {"rehearsal_passed": bool(out["correct"]),
                "correct": False, "attempted": out["attempted"],
                "failed": out["failed"],
                "would_report": sorted(metrics)}
    name = f"{cell['name']}-seed{args.seed}-trace{args.trace}.json"
    with open(os.path.join(out_dir, name), "w") as fh:
        json.dump({"head": head, "lines": out["lines"], "last": last}, fh,
                  indent=1, default=float)
    say(last)
    return REHEARSAL_EXIT if args.rehearse_cpu else 0


if __name__ == "__main__":
    sys.exit(main())
