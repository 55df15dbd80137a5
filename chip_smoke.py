#!/usr/bin/env python3
"""chip_smoke.py — does fedtpu's main path still start, and come out right,
on the chip?

    python chip_smoke.py              # one chip: gate, data, income-8,
                                      # cifar10-32, serve + loadgen
    python chip_smoke.py --chips 4    # ONLY the path across chips and what
                                      # it is compared with

Everything goes through the entry points a user calls (``fedtpu.cli.main``
for run / loadgen, a ``fedtpu serve`` process, ``run_experiment`` where the
CLI has no flag for the mesh extent). One JSON object per phase goes to
stdout as it finishes; the LAST stdout line is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

and the exit code is 0 only then. Where JAX finds no TPU the first phase
(the device gate) fails, nothing trains, the last line says ``"ok": false``
and the exit code is not 0. No phase's exception is caught and turned into
a warning: a worker that raises dies with its traceback and fails the run.

Processes. A chip belongs to one process at a time, so this process never
touches JAX. It runs the trainer phases in one child, waits for it to exit,
then starts ``fedtpu serve`` as the next child and drives it with the
loadgen (which imports no JAX). With ``--chips 4`` the mesh phases are one
child and the Pallas RDMA ring a second, under its own time limit, since a
semaphore fault there would be a hang and not an error.

``--rehearse-cpu`` runs the same phases on whatever backend JAX has (here:
the CPU, kernels in interpret mode) to find wrong paths and arguments
before a chip call. A rehearsal is not a result: its last line is
``"ok": false`` and it exits 10 when every phase passed.

The numbers in the phase lines (compile seconds, seconds per round, the
dispatch round trip) are smoke observations with the device beside them,
not benchmark results.
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import math
import os
import selectors
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))

EXIT_GATE = 3         # a worker found no TPU (or the wrong number of chips)
EXIT_REHEARSAL = 10   # --rehearse-cpu and every phase passed

# The reference's dataset shape (SURVEY.md §0): 10,000 rows, the Adult
# census schema — 14 features (8 of them strings) + the string label
# 'income', balanced 5,000 / 5,000.
ROWS = 10_000
CATEGORICAL = {
    "workclass": ("Federal-gov", "Local-gov", "Private", "Self-emp-inc",
                  "Self-emp-not-inc", "State-gov"),
    "education": ("10th", "11th", "Assoc-voc", "Bachelors", "Doctorate",
                  "HS-grad", "Masters", "Some-college"),
    "marital.status": ("Divorced", "Married-civ-spouse", "Never-married",
                       "Separated", "Widowed"),
    "occupation": ("Adm-clerical", "Craft-repair", "Exec-managerial",
                   "Machine-op-inspct", "Other-service", "Prof-specialty",
                   "Sales", "Tech-support"),
    "relationship": ("Husband", "Not-in-family", "Own-child", "Unmarried",
                     "Wife"),
    "race": ("Amer-Indian-Eskimo", "Asian-Pac-Islander", "Black", "Other",
             "White"),
    "sex": ("Female", "Male"),
    "native.country": ("Canada", "Germany", "India", "Mexico",
                       "Philippines", "United-States"),
}
COLUMNS = ("age", "workclass", "fnlwgt", "education", "education.num",
           "marital.status", "occupation", "relationship", "race", "sex",
           "capital.gain", "capital.loss", "hours.per.week",
           "native.country", "income")

# The tolerance the repo's own tests put on the metrics of "the same run on
# another mesh / chunk width / engine" (tests/test_multiround.py,
# test_round_smoke.py, test_fedavg.py, test_ring.py, test_tp.py).
METRIC_ATOL = 1e-6
HEAD_ROUNDS = 6         # rounds over which two programs are held to it
DRIFT_ATOL = 5e-3       # and over a whole history: half a point of accuracy
FIRST_WEIGHT_ATOL = 1e-5    # weights after one round (tests/test_fedavg.py)
WEIGHT_ATOL = 1e-3      # after several: a quarter of one Adam step at lr=4e-3


def emit(phase: str, **fields) -> dict:
    line = {"phase": phase, **fields}
    print(json.dumps(line, default=float), flush=True)
    return line


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


# ------------------------------------------------------------------- data
def write_income_csv(path: str, seed: int, rows: int = ROWS) -> dict:
    """A seeded CSV of the reference's shape. The label is the sign of a
    noisy linear score over the label-encoded columns, cut at the median so
    the classes balance exactly; the noise keeps a 14->50->200->2 MLP a few
    points short of perfect, so its metrics keep moving round to round (a
    frozen metric vector trips the reference's early stop)."""
    import numpy as np

    rng = np.random.default_rng(seed)
    num = {
        "age": rng.integers(17, 91, rows),
        "fnlwgt": rng.integers(12_000, 1_500_000, rows),
        "education.num": rng.integers(1, 17, rows),
        "capital.gain": np.where(rng.random(rows) < 0.1,
                                 rng.integers(100, 100_000, rows), 0),
        "capital.loss": np.where(rng.random(rows) < 0.05,
                                 rng.integers(100, 4_500, rows), 0),
        "hours.per.week": rng.integers(1, 100, rows),
    }
    cat = {name: rng.integers(0, len(levels), rows)
           for name, levels in CATEGORICAL.items()}
    z = lambda v: (v - v.mean()) / v.std()
    score = (1.2 * z(num["age"]) + 1.5 * z(num["education.num"])
             + 0.8 * z(num["hours.per.week"])
             + 1.0 * z(np.log1p(num["capital.gain"]))
             + 0.6 * z(cat["education"]) + 0.5 * z(cat["occupation"])
             + 0.5 * z(cat["sex"]) - 0.4 * z(cat["marital.status"])
             + rng.normal(0.0, 1.4, rows))
    rich = np.zeros(rows, bool)
    rich[np.argsort(score)[rows // 2:]] = True
    label = np.where(rich, ">50K", "<=50K")
    cols = {**{k: v.astype(str) for k, v in num.items()},
            **{k: np.asarray(CATEGORICAL[k])[v] for k, v in cat.items()},
            "income": label}
    with open(path, "w", newline="") as fh:
        fh.write(",".join(COLUMNS) + "\n")
        for row in zip(*(cols[c] for c in COLUMNS)):
            fh.write(",".join(row) + "\n")
    return {"path": path, "rows": rows, "columns": len(COLUMNS),
            "string_feature_columns": len(CATEGORICAL),
            "labels": {">50K": int(rich.sum()), "<=50K": int((~rich).sum())}}


# ----------------------------------------------------------------- worker
def gate(args, want_count: int) -> dict:
    """The first JAX touch. Stops the worker where JAX found no TPU."""
    t0 = time.perf_counter()
    import jax
    devs = jax.devices()
    init_s = time.perf_counter() - t0
    import jaxlib
    try:
        import libtpu
        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = None
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    from fedtpu.compilation import configure_persistent_cache
    cache_dir = configure_persistent_cache()     # before the first compile
    ok = (len(devs) >= want_count if args.rehearse_cpu
          else device["platform"] == "tpu" and len(devs) == want_count)
    emit("gate", ok=ok, device=device, want_count=want_count,
         backend_init_s=init_s, jax=jax.__version__,
         jaxlib=jaxlib.__version__, libtpu=libtpu_version,
         cache_dir=cache_dir, rehearsal=args.rehearse_cpu)
    if not ok:
        sys.exit(EXIT_GATE)
    return device


class JaxProbe:
    """What happened while a phase ran, from jax's own monitoring stream
    (the channel fedtpu's compile probe listens on) and the cache dir:
    backend compiles by function name (and the seconds of those that held
    up the main thread), persistent-cache hits, and entries new on disk
    (jax's ``*-cache`` files, ProgramCache's ``programs/*.bin``)."""

    compiled: list = []     # (fun_name, seconds, on main thread), in order
    hits = 0
    _listening = False

    def __init__(self, cache_dir: str):
        self.cache_dir = cache_dir
        if not JaxProbe._listening:
            import threading

            from jax import monitoring

            def on_duration(event, duration, fun_name=None, **kw):
                if event.endswith("/backend_compile_duration"):
                    JaxProbe.compiled.append(
                        (fun_name, duration, threading.current_thread()
                         is threading.main_thread()))

            def on_event(event, **kw):
                if event == "/jax/compilation_cache/cache_hits":
                    JaxProbe.hits += 1
            monitoring.register_event_duration_secs_listener(on_duration)
            monitoring.register_event_listener(on_event)
            JaxProbe._listening = True

    def _files(self) -> set:
        out = set()
        for root, _, files in os.walk(self.cache_dir):
            out.update(os.path.join(root, f) for f in files
                       if f.endswith(("-cache", ".bin")))
        return out

    def __enter__(self):
        self._files0, self._hits0 = self._files(), JaxProbe.hits
        self._compiled0 = len(JaxProbe.compiled)
        return self

    def __exit__(self, *exc):
        self.written = len(self._files() - self._files0)
        self.hit = JaxProbe.hits - self._hits0
        new = JaxProbe.compiled[self._compiled0:]
        self.compiles = [name for name, _, _ in new]
        self.compile_s = sum(s for _, s, main in new if main)
        self.background_compile_s = sum(s for _, s, main in new if not main)


def observe_round_trip(device: dict) -> None:
    """What a dispatch and a fetch cost on this host, and whether
    ``block_until_ready`` waits for the device (ROADMAP S1's first
    question). Observations, labelled as such."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    bump = jax.jit(lambda v: v + 1.0)
    x = jnp.zeros((), jnp.float32)
    float(np.asarray(bump(x)))                      # compile + warm
    fetch, ready = [], []
    for _ in range(50):
        t0 = time.perf_counter()
        float(np.asarray(bump(x)))
        fetch.append(time.perf_counter() - t0)
    for _ in range(50):
        t0 = time.perf_counter()
        jax.block_until_ready(bump(x))
        ready.append(time.perf_counter() - t0)

    # A program the device needs tens of milliseconds for: if
    # block_until_ready returned at enqueue it would read as far shorter
    # than the same program closed by a fetch of its scalar result.
    n, k = (4096, 64) if device["platform"] == "tpu" else (256, 8)
    a = jnp.asarray(np.random.default_rng(0).standard_normal((n, n)),
                    jnp.bfloat16)

    @jax.jit
    def chain(m):
        def body(y, _):
            return (y @ m) / jnp.asarray(math.sqrt(n), m.dtype), None
        return jax.lax.scan(body, m, length=k)[0].astype(jnp.float32).sum()

    float(np.asarray(chain(a)))
    long_fetch, long_ready = [], []
    for _ in range(5):
        t0 = time.perf_counter()
        float(np.asarray(chain(a)))
        long_fetch.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        jax.block_until_ready(chain(a))
        long_ready.append(time.perf_counter() - t0)
    med = statistics.median
    emit("observations", ok=True, label="smoke observations, not results",
         device=device,
         trivial_call={"n": 50, "fetch_median_s": med(fetch),
                       "block_until_ready_median_s": med(ready)},
         matmul_chain={"n": n, "length": k, "dtype": "bfloat16",
                       "fetch_median_s": med(long_fetch),
                       "block_until_ready_median_s": med(long_ready)},
         block_until_ready_synchronises=med(long_ready) > 0.5 * med(long_fetch))


def phase_data(args) -> str:
    t0 = time.perf_counter()
    csv = os.path.join(args.out, f"income_seed{args.seed}.csv")
    info = write_income_csv(csv, args.seed)
    write_s = time.perf_counter() - t0
    # The host pipeline once, outside any run, to say who parsed it (this
    # is also where the native loader is built from csv_loader.cpp).
    from fedtpu.config import DataConfig
    from fedtpu.data.tabular import load_tabular_dataset
    t0 = time.perf_counter()
    ds = load_tabular_dataset(DataConfig(csv_path=csv))
    check(ds.source["rows"] == ROWS and ds.input_dim == 14
          and ds.num_classes == 2, f"unexpected dataset: {ds.source}")
    check(info["labels"][">50K"] == info["labels"]["<=50K"] == ROWS // 2,
          f"labels not balanced: {info['labels']}")
    emit("data", ok=True, **info, parser=ds.source["parser"],
         train_rows=len(ds.x_train), test_rows=len(ds.x_test),
         write_s=write_s, load_s=time.perf_counter() - t0)
    return csv


def read_jsonl(path: str) -> list:
    with open(path) as fh:
        return [json.loads(line) for line in fh if line.strip()]


def cli_run(args, tag: str, argv: list, device: dict, cache_dir: str) -> dict:
    """One ``fedtpu run`` through ``fedtpu.cli.main``, with the events sink
    and per-round metrics on, checked for what every run must show."""
    from fedtpu import cli

    events = os.path.join(args.out, f"{tag}.events.jsonl")
    rounds_log = os.path.join(args.out, f"{tag}.rounds.jsonl")
    for p in (events, rounds_log):
        if os.path.exists(p):
            os.remove(p)
    argv = ["run", *argv, "--events", events, "--metrics-jsonl", rounds_log,
            "--quiet", "--json"]
    captured = io.StringIO()
    t0 = time.perf_counter()
    with JaxProbe(cache_dir) as probe, contextlib.redirect_stdout(captured):
        rc = cli.main(argv)
    wall_s = time.perf_counter() - t0
    check(rc == 0, f"{tag}: fedtpu {' '.join(argv)} exited {rc}")
    summary = json.loads(captured.getvalue().strip().splitlines()[-1])
    ev = read_jsonl(events)
    rounds = read_jsonl(rounds_log)
    manifest = next(e["payload"] for e in ev if e["kind"] == "manifest")
    counters = [e["payload"] for e in ev
                if e["kind"] == "counters"][-1]["counters"]
    want = int(argv[argv.index("--rounds") + 1])
    ran = summary["rounds_run"]

    check(not summary["diverged"] and len(rounds) == ran,
          f"{tag}: {summary}, {len(rounds)} rounds logged")
    if summary["stopped_early"]:
        # The reference's own rule ended the run (its learning rate halves
        # every 30 rounds, so the metric vector freezes long before round
        # 300). Hold the loop to that rule instead of to the round count:
        # ten consecutive rounds within 1e-4 of their predecessor.
        vec = [list(r["client_mean"].values()) for r in rounds]
        still = [max(abs(a - b) for a, b in zip(vec[i], vec[i - 1])) <= 1e-4
                 for i in range(ran - 10, ran)]
        check(ran > 10 and all(still) and any(
            e["kind"] == "early_stop" and e["round"] == ran for e in ev),
            f"{tag}: stopped at round {ran} of {want} but the early-stop "
            "rule does not hold there")
    else:
        check(ran == want, f"{tag}: asked for {want} rounds, ran {ran}")
    flat = [v for r in rounds
            for v in (r["loss_mean"], *r["client_mean"].values(),
                      *r["pooled"].values())]
    check(all(math.isfinite(v) for v in flat), f"{tag}: non-finite metrics")
    check(manifest["backend"] == device["platform"]
          and manifest["device_count"] == device["count"],
          f"{tag}: manifest says backend {manifest['backend']} x "
          f"{manifest['device_count']}, the gate saw {device}")
    check(manifest["compilation_cache"] == cache_dir,
          f"{tag}: cache at {manifest['compilation_cache']}, "
          f"expected {cache_dir}")
    # The silent fallbacks of the AOT paths, made loud.
    swallowed = {k: counters.get(k, 0)
                 for k in ("background_compile_failures",
                           "aot_dispatch_fallbacks",
                           "program_cache_load_errors",
                           "program_cache_store_errors")}
    check(not any(swallowed.values()),
          f"{tag}: swallowed AOT failures {swallowed}")
    # Steady state: the round program is compiled once for each chunk width
    # the loop dispatched and never again — a second backend compile at a
    # width is the retrace that multiplies round latency.
    widths = sorted({e["payload"]["rounds"] for e in ev
                     if e["kind"] == "span" and e["phase"] == "chunk"})
    step_compiles = sum(1 for n in probe.compiles if n == "jit(round_step)")
    served = any(e["kind"] == "program_cache" and e["phase"] == "hit"
                 for e in ev)            # a width the ProgramCache served
    check(step_compiles + served == len(widths),
          f"{tag}: {step_compiles} backend compiles of the round program "
          f"for chunk widths {widths}")
    return {"tag": tag, "argv": argv, "summary": summary, "rounds": rounds,
            "manifest": manifest, "events": ev,
            "line": {"rounds_asked": want, "rounds_run": ran,
                     "stopped_early": summary["stopped_early"],
                     # compile_s: backend compiles (or cache loads) that
                     # held up the main thread; run_s: the rest of the wall.
                     "wall_s": wall_s, "compile_s": probe.compile_s,
                     "run_s": wall_s - probe.compile_s,
                     "background_compile_s": probe.background_compile_s,
                     "median_sec_per_round": statistics.median(
                         r["sec_per_round"] for r in rounds),
                     "final_accuracy":
                         summary["final_global_metrics"]["accuracy"],
                     "chunk_widths": widths,
                     "round_program_compiles": step_compiles,
                     "backend_compiles": len(probe.compiles),
                     "cache_entries_written": probe.written,
                     "cache_entries_hit": probe.hit}}


def history_gap(a: list, b: list) -> float:
    """Largest difference between two runs' per-round metrics, over the
    rounds both have."""
    gap = 0.0
    for ra, rb in zip(a, b):
        for group in ("client_mean", "pooled"):
            gap = max(gap, *(abs(ra[group][k] - rb[group][k])
                             for k in ra[group]))
    return gap


def phase_income(args, csv: str, device: dict, cache_dir: str) -> None:
    """The synchronous trainer at the reference's widths (14->50->200->2,
    8 clients): the product default rounds_per_step=1, the
    rounds_per_step=100 every record quotes, and the AOT paths
    (--overlap-compile + ProgramCache), twice, so the second run is served."""
    import jax

    base = ["--preset", "income-8", "--csv", csv]
    few, many = str(HEAD_ROUNDS), "300"
    r1 = cli_run(args, "income8_rps1",
                 [*base, "--rounds", few, "--eval-test-every", "1"],
                 device, cache_dir)
    r100 = cli_run(args, "income8_rps100",
                   [*base, "--rounds", many, "--rounds-per-step", "100"],
                   device, cache_dir)
    aot = [*base, "--rounds", many, "--rounds-per-step", "100",
           "--overlap-compile", "--compilation-cache", cache_dir]
    first = cli_run(args, "income8_aot_first", aot, device, cache_dir)
    second = cli_run(args, "income8_aot_second", aot, device, cache_dir)

    for r in (r1, r100, first, second):
        data = r["manifest"]["data"]
        check(data["kind"] == "csv" and data["rows"] == ROWS
              and data["path"] == csv, f"{r['tag']}: trained on {data}")
        check(r["manifest"]["config"]["model"]["hidden_sizes"] == [50, 200]
              and r["manifest"]["config"]["shard"]["num_clients"] == 8,
              f"{r['tag']}: not the published widths")
    for r in (r100, first, second):
        check(r["line"]["final_accuracy"] > 0.75,
              f"{r['tag']}: accuracy {r['line']['final_accuracy']} after "
              f"{many} rounds is not well above chance")

    # The same seed through different programs must tell the same story.
    # Different programs (width-1 rounds, the scanned chunk) differ in the
    # last bit on the chip, and after the first borderline row flips the
    # trajectories part for good, so they are held to the tests' tolerance
    # over the first rounds and to DRIFT_ATOL over the whole history. The
    # same program — compiled here, or deserialised from the ProgramCache —
    # must repeat itself exactly.
    def gap_to_eager(r) -> dict:
        same = r["line"]["chunk_widths"] == r100["line"]["chunk_widths"]
        head = history_gap(r["rounds"][:HEAD_ROUNDS],
                           r100["rounds"][:HEAD_ROUNDS])
        whole = history_gap(r["rounds"], r100["rounds"])
        return {"same_program": same, "head": head, "whole": whole,
                "ok": whole == 0.0 if same
                else head <= METRIC_ATOL and whole <= DRIFT_ATOL}

    gaps = {"rps1": gap_to_eager(r1), "aot_first": gap_to_eager(first),
            "aot_second": gap_to_eager(second)}
    check(all(g["ok"] for g in gaps.values()),
          f"income-8: histories disagree with the rps=100 run: {gaps}")

    # The serialised executable met this runtime: stored by the first run
    # (or already there, where the machine came with its cache) and served
    # to the second, with no load that fell back to a recompile.
    phases = lambda r: [e["phase"] for e in r["events"]
                        if e["kind"] == "program_cache"]
    check(("store" in phases(first) or "hit" in phases(first))
          and "hit" in phases(second)
          and "load_error" not in phases(first) + phases(second),
          f"ProgramCache: first {phases(first)}, second {phases(second)}")

    emit("income8", ok=True, device=device,
         model="mlp 14->50->200->2, 8 clients, float32",
         data_rows=ROWS, backend=r1["manifest"]["backend"],
         rps1=r1["line"], rps100=r100["line"], aot_first=first["line"],
         aot_second=second["line"], gaps_to_rps100=gaps,
         peak_bytes_in_use=(jax.devices()[0].memory_stats() or {})
         .get("peak_bytes_in_use"))


def phase_convnet(args, device: dict, cache_dir: str) -> None:
    """cifar10-32: the only MXU-sized model the repo has — 32 clients, bf16
    compute, synthetic CIFAR-shaped images by the preset's own design."""
    import jax

    rounds = "5" if not args.rehearse_cpu else "1"
    r = cli_run(args, "cifar10_32",
                ["--preset", "cifar10-32", "--rounds", rounds],
                device, cache_dir)
    data = r["manifest"]["data"]
    check(data == {**data, "kind": "synthetic", "rows": 4096},
          f"cifar10-32 trained on {data}")
    model = r["manifest"]["config"]["model"]
    check(model["kind"] == "convnet" and model["compute_dtype"] == "bfloat16"
          and r["manifest"]["config"]["shard"]["num_clients"] == 32,
          f"cifar10-32: unexpected model {model}")
    emit("cifar10_32", ok=True, device=device,
         model="convnet 32x32x3 -> conv32 -> conv64 -> 256 -> 10, "
               "32 clients, bf16 compute",
         data=data, run=r["line"],
         loss_by_round=[x["loss_mean"] for x in r["rounds"]],
         peak_bytes_in_use=(jax.devices()[0].memory_stats() or {})
         .get("peak_bytes_in_use"))


def worker_train(args) -> None:
    device = gate(args, want_count=1)
    from fedtpu.compilation import resolve_cache_dir
    cache_dir = resolve_cache_dir()
    observe_round_trip(device)
    csv = phase_data(args)
    phase_income(args, csv, device, cache_dir)
    phase_convnet(args, device, cache_dir)


# ------------------------------------------------------- four-chip workers
def _final_weights(result) -> list:
    import jax
    import numpy as np
    return [np.asarray(l) for l in jax.tree.leaves(result.final_params)]


def _gaps(a, b) -> tuple:
    """Largest difference between two runs' metric histories and between
    their final weights."""
    import numpy as np
    metric_gap = max(
        float(np.max(np.abs(np.asarray(hist[k]) - np.asarray(other[k]))))
        for hist, other in ((a.global_metrics, b.global_metrics),
                            (a.pooled_metrics, b.pooled_metrics))
        for k in hist)
    weight_gap = max(float(np.max(np.abs(x - y)))
                     for x, y in zip(_final_weights(a), _final_weights(b)))
    return metric_gap, weight_gap


def compare_layouts(run, variant: dict, reference: dict, rounds: int) -> dict:
    """One seed under two layouts (meshes, reduction schedules, engines).

    After ONE round the two have computed the same average in another
    order, and are held to the tolerance the repo's tests put on N devices
    against one. After ``rounds`` they are held to DRIFT_ATOL / WEIGHT_ATOL
    only: the chip rounds matmul inputs to bf16, so a last-bit difference
    in a weight now and then becomes a 2^-8 one, Adam divides by sqrt(v)
    where gradients are near zero, and once a borderline row flips the
    trajectories part for good (ring vs psum: 2e-4 in the metrics after 5
    rounds on 4 chips, PR 21)."""
    m1, w1 = _gaps(run(1, **variant), run(1, **reference))
    long_a, long_b = run(rounds, **variant), run(rounds, **reference)
    check(long_a.rounds_run == long_b.rounds_run == rounds,
          "rounds cut short")
    mn, wn = _gaps(long_a, long_b)
    return {"ok": (m1 <= METRIC_ATOL and w1 <= FIRST_WEIGHT_ATOL
                   and mn <= DRIFT_ATOL and wn <= WEIGHT_ATOL),
            "round1": {"metric_gap": m1, "metric_atol": METRIC_ATOL,
                       "weight_gap": w1, "weight_atol": FIRST_WEIGHT_ATOL},
            f"round{rounds}": {"metric_gap": mn, "metric_atol": DRIFT_ATOL,
                               "weight_gap": wn, "weight_atol": WEIGHT_ATOL},
            "accuracy": long_a.global_metrics["accuracy"]}


def worker_mesh4(args) -> None:
    """income-8 across four chips (2 clients a chip), against one device in
    this same process; the ring schedules against psum; the 2-D engine on
    2x2."""
    device = gate(args, want_count=4)
    import dataclasses

    import jax
    from fedtpu.config import get_preset
    from fedtpu.orchestration.loop import build_experiment, run_experiment

    csv = os.path.join(args.out, f"income_seed{args.seed}.csv")
    write_income_csv(csv, args.seed)
    preset = get_preset("income-8")

    def cfg(rounds, mesh_devices=4, aggregation="psum", model_parallel=1):
        return dataclasses.replace(
            preset,
            data=dataclasses.replace(preset.data, csv_path=csv),
            fed=dataclasses.replace(preset.fed, rounds=rounds,
                                    aggregation=aggregation),
            run=dataclasses.replace(preset.run, mesh_devices=mesh_devices,
                                    model_parallel=model_parallel))

    # State and batch really are spread over four devices: make_mesh trims
    # to a divisor of the client count, and code that has only ever seen
    # one chip could leave everything on device 0.
    exp = build_experiment(cfg(1))
    check(exp.mesh.devices.size == 4, f"mesh is {exp.mesh}")
    placed = jax.tree.leaves((exp.state["params"], exp.state["opt_state"],
                              exp.batch))
    spread = [(len(l.sharding.device_set),
               {s.data.shape[0] for s in l.addressable_shards})
              for l in placed if l.ndim]
    check(all(n == 4 and rows == {2} for n, rows in spread),
          f"not 2 clients on each of 4 devices: {spread}")
    text = exp.make_step(1).lower(exp.state, exp.batch).compile().as_text()
    check("all-reduce" in text, "the round program holds no all-reduce")
    emit("mesh4_layout", ok=True, device=device,
         mesh={k: int(v) for k, v in exp.mesh.shape.items()},
         sharded_leaves=len(spread), clients_per_device=2,
         all_reduce_ops=text.count("all-reduce("))

    def run(rounds, **layout):
        return run_experiment(cfg(rounds, **layout), verbose=False)

    # Every comparison is made and printed before any of them fails the
    # worker: a four-chip call is too dear to learn one gap at a time.
    four = dict(mesh_devices=4)
    lines = []
    for phase, variant, reference, rounds in (
            ("mesh4_vs_one_device", four, dict(mesh_devices=1), 5),
            ("mesh4_ring_vs_psum", dict(aggregation="ring"), four, 5),
            ("mesh4_ring_rsag_vs_psum", dict(aggregation="ring-rsag"),
             four, 5),
            ("mesh4_tp2x2_vs_psum", dict(model_parallel=2), four, 2)):
        t0 = time.perf_counter()
        lines.append(emit(phase, **compare_layouts(run, variant, reference,
                                                   rounds),
                          device=device, rounds=rounds,
                          wall_s=time.perf_counter() - t0))
    check(all(l["ok"] for l in lines),
          f"disagreement: {[l['phase'] for l in lines if not l['ok']]}")


def worker_pallas_ring(args) -> None:
    """One execution of the Pallas RDMA ring all-reduce (barrier and
    capacity semaphores live) against psum, on the model's flat delta."""
    device = gate(args, want_count=4)
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from fedtpu.parallel.ring_pallas import pallas_ring_all_reduce_sum

    compiled_path = device["platform"] == "tpu"
    mesh = Mesh(np.asarray(jax.devices()[:4]), ("clients",))
    size = 14 * 50 + 50 + 50 * 200 + 200 + 200 * 2 + 2        # 11,352
    x = jax.device_put(
        np.random.default_rng(args.seed).standard_normal((4, size))
        .astype(np.float32), NamedSharding(mesh, P("clients")))

    def both(t):
        ring = pallas_ring_all_reduce_sum(t[0], "clients", 4)[None]
        return ring, jax.lax.psum(t[0], "clients")[None]

    fn = jax.jit(jax.shard_map(both, mesh=mesh, in_specs=P("clients"),
                               out_specs=(P("clients"), P("clients")),
                               # the interpreter is not vma-aware
                               check_vma=compiled_path))
    text = fn.lower(x).compile().as_text()
    check("tpu_custom_call" in text or not compiled_path,
          "the ring did not compile to a Mosaic kernel")
    t0 = time.perf_counter()
    ring, psum = (np.asarray(o) for o in fn(x))
    np.testing.assert_allclose(ring, psum, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(ring[0], np.asarray(x).sum(axis=0),
                               rtol=1e-5, atol=1e-5)
    emit("mesh4_pallas_ring_vs_psum", ok=True, device=device,
         payload_floats=size, compiled_kernel="tpu_custom_call" in text,
         max_abs_diff=float(np.max(np.abs(ring - psum))),
         first_call_s=time.perf_counter() - t0)


WORKERS = {"train": worker_train, "mesh4": worker_mesh4,
           "pallas_ring": worker_pallas_ring}


# ----------------------------------------------------------------- parent
class SmokeFailed(Exception):
    """A worker child failed; ``lines`` are the phase lines it got out."""

    def __init__(self, msg: str, lines=()):
        super().__init__(msg)
        self.lines = list(lines)


def run_worker(args, name: str, timeout_s: float) -> list:
    """Run one worker child to its end (or kill it at its time limit);
    pass its lines through and return the phase lines among them."""
    cmd = [sys.executable, os.path.abspath(__file__), "--worker", name,
           "--seed", str(args.seed), "--out", args.out]
    if args.rehearse_cpu:
        cmd.append("--rehearse-cpu")
    proc = subprocess.Popen(cmd, cwd=HERE, stdout=subprocess.PIPE, text=True)
    lines = []
    deadline = time.monotonic() + timeout_s
    try:
        sel = selectors.DefaultSelector()
        sel.register(proc.stdout, selectors.EVENT_READ)
        while True:
            left = deadline - time.monotonic()
            if left <= 0:
                raise TimeoutError(f"worker {name} passed its {timeout_s:.0f}"
                                   " s limit and was killed")
            if not sel.select(timeout=min(left, 5.0)):
                continue
            raw = proc.stdout.readline()
            if not raw:
                break
            print(raw, end="", flush=True)
            if raw.startswith("{"):
                with contextlib.suppress(ValueError):
                    line = json.loads(raw)
                    if isinstance(line, dict) and "phase" in line:
                        lines.append(line)
        rc = proc.wait(timeout=max(1.0, deadline - time.monotonic()))
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    if rc != 0:
        raise SmokeFailed(f"worker {name} exited {rc}", lines)
    return lines


def phase_serve(args, device: dict) -> None:
    """``fedtpu serve --once`` on the chip, a few hundred arrivals from
    ``fedtpu loadgen --synthesize``: every update acked and incorporated,
    the server drains and exits 0. The server child is the only process
    holding the chip; the loadgen runs here and must stay off JAX."""
    arrivals = 400
    work = os.path.join(args.out, "serve")
    os.makedirs(work, exist_ok=True)
    port_file, events = (os.path.join(work, n) for n in ("port", "ev.jsonl"))
    for p in (port_file, events):
        if os.path.exists(p):
            os.remove(p)
    t0 = time.perf_counter()
    server = subprocess.Popen(
        [sys.executable, "-m", "fedtpu.cli", "serve", "--port-file",
         port_file, "--cohort", "8", "--buffer-size", "2",
         "--tick-interval", "0.5", "--events", events, "--once", "--json",
         "--quiet"], cwd=HERE, stdout=subprocess.PIPE, text=True)
    try:
        while not os.path.exists(port_file):
            check(server.poll() is None,
                  f"fedtpu serve exited {server.returncode} before listening")
            check(time.perf_counter() - t0 < 300,
                  "fedtpu serve not listening after 300 s")
            time.sleep(0.2)
        listen_s = time.perf_counter() - t0

        from fedtpu import cli
        captured = io.StringIO()
        with contextlib.redirect_stdout(captured):
            rc = cli.main(["loadgen", os.path.join(work, "trace.jsonl"),
                           "--synthesize", "--users", "40", "--arrivals",
                           str(arrivals), "--horizon", "20", "--trace-seed",
                           str(args.seed), "--port-file", port_file,
                           "--batch", "128", "--json", "--quiet"])
        check(rc == 0, f"fedtpu loadgen exited {rc}")
        check("jax" not in sys.modules,
              "the loadgen path imported jax into the process that must "
              "stay off the chip")
        load = json.loads(captured.getvalue().strip().splitlines()[-1])
        out, _ = server.communicate(timeout=300)
    finally:
        if server.poll() is None:
            server.kill()
            server.wait()
    check(server.returncode == 0, f"fedtpu serve exited {server.returncode}")
    served = json.loads(out.strip().splitlines()[-1])
    manifest = next(e["payload"] for e in read_jsonl(events)
                    if e["kind"] == "manifest")
    check(manifest["backend"] == device["platform"],
          f"the server ran on {manifest['backend']}, the gate saw {device}")
    admitted = load["admission"]
    check(load["events_sent"] == arrivals
          and sum(admitted.values()) == arrivals
          and not any(k.startswith("reject") for k in admitted),
          f"not every update was acked: {load}")
    check(served["incorporated"] == arrivals and served["pending"] == 0
          and load["server_stats"]["incorporated"] == arrivals,
          f"sent {arrivals}, incorporated {served['incorporated']}, "
          f"pending {served['pending']}")
    check(math.isfinite(served["eval_accuracy"]), "non-finite eval accuracy")
    emit("serve", ok=True, device=device, backend=manifest["backend"],
         arrivals=arrivals, admission=admitted,
         incorporated=served["incorporated"], ticks=served["ticks"],
         eval_accuracy=served["eval_accuracy"],
         update_to_incorporation=served["update_to_incorporation"],
         listening_after_s=listen_s, server_wall_s=served["wall_s"],
         loadgen_wall_s=load["wall_s"], total_s=time.perf_counter() - t0)


def parent(args) -> int:
    t0 = time.perf_counter()
    os.makedirs(args.out, exist_ok=True)
    lines = []
    try:
        if args.chips == 4:
            lines += run_worker(args, "mesh4", timeout_s=700)
            lines += run_worker(args, "pallas_ring", timeout_s=240)
        else:
            lines += run_worker(args, "train", timeout_s=800)
            phase_serve(args, lines[0]["device"])
        device = lines[0]["device"]
        passed = all(l.get("ok") for l in lines)
    except Exception as exc:   # the boundary that reports: non-zero + why
        if not isinstance(exc, SmokeFailed):   # a worker printed its own
            import traceback
            traceback.print_exc()
        gates = [l for l in getattr(exc, "lines", lines)
                 if l["phase"] == "gate"]
        print(json.dumps({"ok": False, "error": f"{type(exc).__name__}: "
                          f"{exc}"[:500],
                          "device": gates[0]["device"] if gates else None}),
              flush=True)
        return 1
    emit("total", ok=passed, seconds=time.perf_counter() - t0,
         chips=args.chips)
    real = passed and device["platform"] == "tpu" and not args.rehearse_cpu
    print(json.dumps({"ok": real, "device": device} if real else
                     {"ok": False, "rehearsal_passed": passed,
                      "device": device}), flush=True)
    return 0 if real else EXIT_REHEARSAL if passed else 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the path across four chips and what "
                         "it is compared with")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the generated data and traffic")
    ap.add_argument("--out", default=os.path.join(HERE, "chiprun_out",
                                                  "chip_smoke"),
                    help="directory for the generated CSV, event sinks and "
                         "traces")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="run the phases on whatever backend JAX has; never "
                         "reports ok (exit 10 when every phase passed)")
    ap.add_argument("--worker", choices=sorted(WORKERS),
                    help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    args.out = os.path.abspath(args.out)
    if args.worker:
        WORKERS[args.worker](args)
        return 0
    return parent(args)


if __name__ == "__main__":
    sys.exit(main())
