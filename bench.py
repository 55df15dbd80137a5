"""Headline benchmark: sec/round of 8-client weighted FedAvg on the income MLP.

Prints ONE JSON line:
    {"metric": "sec_per_round_fedavg8_income_mlp", "value": <ours>,
     "unit": "s", "vs_baseline": <baseline/ours speedup>}

Ours: the fedtpu compiled round (local full-batch Adam step + in-graph
weighted FedAvg + in-graph metrics) on the TPU, one ('clients',) mesh over
the visible devices, 8 clients. ``main`` refuses any other backend: a time
or an MFU from the CPU is not a device metric and is never written under
one's name. The result carries the platform, device kind and device count
it ran on, and which rows it trained on — the income CSV when
``default_income_csv()`` finds one, else the synthetic income-like
stand-in, said so in the ``data`` field.
The headline value is measured at rounds_per_step=100 (100 rounds scanned
per compiled program, early-stop checks at chunk boundaries); the full rps
sweep is reported on stderr.

TIMING METHODOLOGY: dispatch is asynchronous, so a timed window that ends
before the device does measures the enqueue (round 1 recorded 22,260x that
way; ~44x was real). Every timed window here is closed by ``force_fetch``
(a host value fetch that provably depends on the full program), and every
result must pass ``assert_above_flops_floor``: sec/round >= program FLOPs /
(2 x measured device peak), with peak measured on-device by a
dispatch-cancelling matmul-chain slope. A floor violation crashes the
benchmark rather than recording a fantasy number.

The ``mpmd_sync`` row reruns the synchronous early-stopping loop shape
through the ``--mpmd`` DAG (PR 18, ``fedtpu/orchestration/mpmd.py``)
with bitwise metric-history parity re-proven in-run; see
``bench_mpmd_sync``.

Baseline: the reference publishes no numbers (BASELINE.md), so the baseline
is MEASURED here as a faithful single-host simulation of the reference's
per-round work under ``mpirun -np 8`` (FL_CustomMLP...:63-120): per rank a
full-batch torch forward/backward/Adam step + argmax eval on its shard, then
the rank-0 aggregation path — pickle every rank's weight dict (comm.gather),
numpy weighted average, pickle the global dict back out (comm.bcast), and
load into each model. Ranks run concurrently under mpirun, so the compute
part is divided by min(8, cpu_count) (ideal oversubscription); the
serialization + averaging path is inherently serialized through rank 0 and
is not divided.
"""

from __future__ import annotations

import argparse
import json
import os
import pickle
import sys
import time

import numpy as np

NUM_CLIENTS = 8
# rounds_per_step values swept; the headline is HEADLINE_RPS. The per-call
# dispatch and fetch amortize with scan depth, so sec/round falls with rps
# and flattens toward the marginal on-chip cost. The headline stays at
# rps=100, where early-stop checks remain round-granular enough for the
# reference's patience-10 driver.
RPS_SWEEP = (1, 10, 100, 1000, 4000)
HEADLINE_RPS = 100


def _dataset():
    """The income CSV where one is found; else the synthetic stand-in
    (``Dataset.source`` says which, and the result repeats it)."""
    from fedtpu.config import DataConfig, default_income_csv

    from fedtpu.data.tabular import load_tabular_dataset

    csv = default_income_csv()
    return load_tabular_dataset(DataConfig(csv_path=csv))


def require_tpu() -> dict:
    """The measurement path's device gate: the device this run measures,
    as jax reports it — or SystemExit where that is not a TPU."""
    import jax

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    if dev.platform != "tpu":
        raise SystemExit(
            f"bench.py measures the TPU and found {device}: a number from "
            "another backend is not a device metric. Run it on the chip "
            "(the chip tool), or rehearse bench_fedtpu() by hand at a small "
            "size without recording the result.")
    return device


def bench_fedtpu(ds) -> dict:
    import jax

    from fedtpu.config import ModelConfig, OptimConfig, ShardConfig
    from fedtpu.data.sharding import pack_clients
    from fedtpu.models import build_model
    from fedtpu.ops import build_optimizer
    from fedtpu.parallel import make_mesh, client_sharding
    from fedtpu.parallel.round import build_round_fn, init_federated_state
    from fedtpu.utils.timing import (assert_above_flops_floor,
                                     compile_with_flops, force_fetch,
                                     measured_peak_flops, timed_rounds)

    mesh = make_mesh(num_clients=NUM_CLIENTS)
    shard = client_sharding(mesh)
    packed = pack_clients(ds.x_train, ds.y_train,
                          ShardConfig(num_clients=NUM_CLIENTS))
    batch = {
        "x": jax.device_put(packed.x, shard),
        "y": jax.device_put(packed.y, shard),
        "mask": jax.device_put(packed.mask, shard),
    }
    init_fn, apply_fn = build_model(ModelConfig(input_dim=ds.input_dim,
                                                num_classes=ds.num_classes))
    tx = build_optimizer(OptimConfig())

    # Device peak for the flops floor, measured at the matmul rate the model
    # actually gets (XLA default precision; on TPU f32 matmuls ride the MXU
    # in bf16 passes, so this sits near the bf16 spec peak — a HIGH peak
    # only loosens the floor, which is the safe direction).
    dev = mesh.devices.ravel()[0]
    peak = measured_peak_flops(dtype="float32", device=dev)

    # Any backend compile inside a timed window is an unexpected retrace:
    # each rps's program compiles in compile_with_flops BEFORE arming, so
    # the armed count must stay 0 (BENCH_* files regress on it).
    from fedtpu.analysis.guards import RecompileSentinel
    sentinel = RecompileSentinel(label="bench_timed_windows")

    sweep = {}
    flops_per_round = None
    cold_compile_s = None
    warm_lookup_ms = None
    for rps in RPS_SWEEP:
        state = init_federated_state(jax.random.key(0), mesh, NUM_CLIENTS,
                                     init_fn, tx)
        step = build_round_fn(mesh, apply_fn, tx, ds.num_classes,
                              rounds_per_step=rps)
        # compile_with_flops raises if XLA cost analysis is unavailable —
        # no floor, no number. A lax.scan body is counted ONCE regardless
        # of length, so the scanned program's "flops" IS the per-round cost
        # (verified: cost(rps=100) == cost(rps=1) on this backend).
        t_compile = time.perf_counter()
        step, flops_per_round = compile_with_flops(step, state, batch)
        if rps == HEADLINE_RPS:
            # Compile-cost companion numbers for the headline program: what
            # a cold start pays (trace+XLA compile) vs what a warm
            # --compilation-cache start pays instead (serialized-executable
            # round-trip through fedtpu.compilation.ProgramCache).
            cold_compile_s = time.perf_counter() - t_compile
            warm_lookup_ms = _warm_lookup_ms(step)

        # PIPELINED throughput: back-to-back calls, one completion-proving
        # fetch at the end (the fixed-rounds production shape — run N
        # chunks, read results at the end). Dispatch overlaps compute.
        # timed_rounds is the mandatory harness: fetch-forced window +
        # flops-floor check. Multiple independent windows per rps: dispatch
        # time jitters with host load, and recording a single window lets
        # the artifact quote the top of its own jitter band (review r2) —
        # report the median and keep the band. The headline
        # gets 5 windows; every other row gets 2, so no row ever records a
        # degenerate zero-width band (advisor r3).
        n_calls = max(3, min(20, 2000 // rps))
        reps = 5 if rps == HEADLINE_RPS else 2
        samples = []
        with sentinel.armed():
            for _ in range(reps):
                sec_rep, state, metrics = timed_rounds(
                    step, state, batch, n_calls, rps, peak, flops_per_round,
                    label=f"rps={rps}")
                samples.append(sec_rep)
        sec_per_round = float(np.median(samples))
        acc = float(np.asarray(metrics["client_mean"]["accuracy"]).ravel()[-1])
        # The rounds the accuracy is attributed to must count EVERYTHING
        # the state trained through — warmup calls and all timed windows
        # across all reps — not just one window's n_calls * rps. The
        # state's own round counter is the exact ledger.
        rounds_trained = int(np.asarray(state["round"]))

        # SYNCHRONOUS latency: fetch the metrics after every call — the
        # early-stopping production loop's shape (host inspects metrics at
        # each chunk boundary), paying one dispatch+fetch RTT per chunk.
        t0 = time.perf_counter()
        sync_calls = 3
        with sentinel.armed():
            for _ in range(sync_calls):
                state, metrics = step(state, batch)
                force_fetch(metrics["client_mean"]["accuracy"])
        sec_sync = (time.perf_counter() - t0) / (sync_calls * rps)

        floor = assert_above_flops_floor(sec_per_round, flops_per_round,
                                         peak, label=f"rps={rps}")
        assert_above_flops_floor(sec_sync, flops_per_round, peak,
                                 label=f"rps={rps} sync")
        sweep[rps] = {"sec_per_round": sec_per_round,
                      "sec_per_round_range": [float(min(samples)),
                                              float(max(samples))],
                      "sec_per_round_sync": sec_sync,
                      "rounds_timed": n_calls * rps,
                      "rounds_trained": rounds_trained,
                      "floor_sec": floor,
                      # Model FLOPs utilization at this rps: fraction of the
                      # measured device peak the timed program sustains.
                      "mfu": flops_per_round / (sec_per_round * peak),
                      "final_accuracy": acc}

    head = sweep[HEADLINE_RPS]
    # Training must be real: ~2000+ rounds on the income MLP reaches ~0.83
    # accuracy (round-1 verified trajectory). A dead program would fail here.
    if head["final_accuracy"] < 0.75:
        raise RuntimeError(
            f"benchmark program is not actually training: accuracy "
            f"{head['final_accuracy']:.3f} after {head['rounds_trained']} "
            "rounds (expected ~0.83)")
    return {"sec_per_round": head["sec_per_round"],
            "sec_per_round_range": head["sec_per_round_range"],
            "sec_per_round_sync": head["sec_per_round_sync"],
            "rounds_per_step": HEADLINE_RPS,
            "accuracy": head["final_accuracy"],
            "devices": len(mesh.devices.ravel()),
            "backend": dev.platform,
            "peak_flops_measured": peak,
            "flops_per_round": flops_per_round,
            "mfu": head["mfu"],
            "recompiles": sentinel.count,
            "cold_compile_s": cold_compile_s,
            "warm_lookup_ms": warm_lookup_ms,
            "sweep": sweep}


def _warm_lookup_ms(compiled):
    """Serialized-executable round-trip for the headline program: store to
    the ProgramCache, then time a FRESH cache instance's load — the
    startup cost a warm ``--compilation-cache`` run pays in place of
    cold_compile_s (benchmarks/compile_bench.py asserts the ratio)."""
    from fedtpu.compilation import ProgramCache, program_cache_dir
    d = program_cache_dir()
    if not ProgramCache(d).store("bench-headline", compiled):
        return None                     # serialization unsupported here
    entry = ProgramCache(d).load("bench-headline")
    return entry.seconds * 1e3 if entry is not None else None


def bench_mfu_capability(peak: float) -> dict:
    """The >=50% MFU capability point, machine-captured (VERDICT r4 #4).

    The income headline above is BYTE-bound at its bandwidth roofline
    (benchmarks/roofline.py; PERF.md 'Earlier records').
    This row runs the IDENTICAL round program at an MXU-sized shape
    (hidden [512, 512], 800 rows/client, synthetic income-like data) so the
    artifact itself carries the engine's compute capability, not just the
    workload's bandwidth ceiling. Measured as a scan-length SLOPE
    (per-round marginal between rps=200 and rps=800 windows, fetch-forced)
    so the per-call dispatch and fetch cancel exactly — the same
    methodology as measured_peak_flops and benchmarks/roofline.py; the
    flops floor still applies."""
    import time as _time

    import jax

    from fedtpu.config import (DataConfig, ModelConfig, OptimConfig,
                               ShardConfig)
    from fedtpu.data import load_dataset
    from fedtpu.data.sharding import pack_clients
    from fedtpu.models import build_model
    from fedtpu.ops import build_optimizer
    from fedtpu.parallel import make_mesh, client_sharding
    from fedtpu.parallel.round import build_round_fn, init_federated_state
    from fedtpu.utils.timing import (assert_above_flops_floor,
                                     compile_with_flops, force_fetch)
    from fedtpu.utils.trees import clone

    HIDDEN, ROWS = (512, 512), 800
    ds = load_dataset(DataConfig(csv_path=None,
                                 synthetic_rows=ROWS * NUM_CLIENTS,
                                 synthetic_features=14))
    mesh = make_mesh(num_clients=NUM_CLIENTS)
    shard = client_sharding(mesh)
    packed = pack_clients(ds.x_train, ds.y_train,
                          ShardConfig(num_clients=NUM_CLIENTS))
    batch = {k: jax.device_put(v, shard) for k, v in
             {"x": packed.x, "y": packed.y, "mask": packed.mask}.items()}
    init_fn, apply_fn = build_model(
        ModelConfig(input_dim=ds.input_dim, hidden_sizes=HIDDEN,
                    num_classes=ds.num_classes))
    tx = build_optimizer(OptimConfig())
    state = init_federated_state(jax.random.key(0), mesh, NUM_CLIENTS,
                                 init_fn, tx)

    n_calls = 5
    times = {}
    flops = None
    for rps in (200, 800):
        step = build_round_fn(mesh, apply_fn, tx, ds.num_classes,
                              rounds_per_step=rps)
        step, flops = compile_with_flops(step, clone(state), batch)
        s = clone(state)
        s, m = step(s, batch)                     # warmup this executable
        force_fetch(m)
        best = float("inf")
        for _ in range(3):
            s = clone(state)
            t0 = _time.perf_counter()
            for _ in range(n_calls):
                s, m = step(s, batch)
            force_fetch(m)
            best = min(best, _time.perf_counter() - t0)
        times[rps] = best
    marginal = (times[800] - times[200]) / (n_calls * (800 - 200))
    assert_above_flops_floor(marginal, flops, peak, label="mfu capability")
    return {"hidden": list(HIDDEN), "rows_per_client": ROWS,
            "marginal_s_per_round": marginal, "flops_per_round": flops,
            "peak_flops_measured": peak,
            "mfu": flops / (marginal * peak)}


def bench_mpmd_sync(ds, peak: float) -> dict:
    """Sync-mode MPMD row: the early-stopping loop shape rerun through
    the ``--mpmd`` DAG (fedtpu/orchestration/mpmd.py).

    The monolithic sync loop blocks on a metric fetch after every chunk
    — dispatch + compute + fetch serialized per chunk, the gap between
    the sweep's sync and pipelined columns. The MPMD loop
    is the production ``RunConfig.mpmd`` schedule: the whole DAG is
    enqueued async (client chain on the round mesh, the metrics
    program's tiny output pushed eagerly to the server submesh) and the
    early-stop decision lags one in-flight chunk, so chunk k's fetch
    drains under chunk k+1's compute and the RTT leaves the critical
    path.

    Parity is load-bearing and CRASHES on failure: the two loops'
    fetched metric histories and final states must be bitwise equal —
    the tests/test_mpmd.py oracle contract, re-proven inside the
    artifact every run.

    ``improvement_measured`` is the one improvement number in the row,
    and it is this run's: the ratio of the two loops' measured sec/round.
    What it can hide is the per-chunk dispatch+fetch round trip, so where
    that is small against a chunk's compute the ratio is honestly ~1.
    """
    import jax

    from fedtpu.analysis.guards import RecompileSentinel
    from fedtpu.config import (ExperimentConfig, ModelConfig, OptimConfig,
                               RunConfig, ShardConfig)
    from fedtpu.data.sharding import pack_clients
    from fedtpu.models import build_model
    from fedtpu.ops import build_optimizer
    from fedtpu.orchestration.mpmd import build_mpmd_step
    from fedtpu.parallel import make_mesh, client_sharding
    from fedtpu.parallel.round import build_round_fn, init_federated_state
    from fedtpu.utils.timing import (assert_above_flops_floor,
                                     compile_with_flops, force_fetch)
    from fedtpu.utils.trees import clone

    rps = HEADLINE_RPS
    mesh = make_mesh(num_clients=NUM_CLIENTS)
    shard = client_sharding(mesh)
    packed = pack_clients(ds.x_train, ds.y_train,
                          ShardConfig(num_clients=NUM_CLIENTS))
    batch = {k: jax.device_put(v, shard) for k, v in
             {"x": packed.x, "y": packed.y, "mask": packed.mask}.items()}
    init_fn, apply_fn = build_model(ModelConfig(input_dim=ds.input_dim,
                                                num_classes=ds.num_classes))
    tx = build_optimizer(OptimConfig())
    state0 = init_federated_state(jax.random.key(0), mesh, NUM_CLIENTS,
                                  init_fn, tx)

    mono = build_round_fn(mesh, apply_fn, tx, ds.num_classes,
                          rounds_per_step=rps)
    mono, flops = compile_with_flops(mono, clone(state0), batch)
    cfg = ExperimentConfig(
        model=ModelConfig(input_dim=ds.input_dim,
                          num_classes=ds.num_classes),
        shard=ShardConfig(num_clients=NUM_CLIENTS),
        run=RunConfig(mpmd=True, rounds_per_step=rps))
    mpmd = build_mpmd_step(cfg, mesh=mesh, apply_fn=apply_fn, tx=tx,
                           num_classes=ds.num_classes, state=state0,
                           batch=batch, width=rps)

    chunks = 6
    sentinel = RecompileSentinel(label="bench_mpmd_sync")

    def fetched(m):
        force_fetch(m)
        return jax.tree.map(np.asarray, m)

    # Warm one chunk through each engine (absorbs one-time transfer
    # programs) before the armed, timed windows.
    _, m = mono(clone(state0), batch)
    force_fetch(m)
    _, m = mpmd(clone(state0), batch)
    force_fetch(m)

    # Monolithic sync loop: block on the metrics after every chunk.
    s = clone(state0)
    hist_mono = []
    with sentinel.armed():
        t0 = time.perf_counter()
        for _ in range(chunks):
            s, m = mono(s, batch)
            hist_mono.append(fetched(m))
        mono_sync_s = (time.perf_counter() - t0) / (chunks * rps)
    state_mono = jax.tree.map(np.asarray, s)

    # MPMD sync loop: the production one-chunk pending lag — dispatch
    # chunk k+1's DAG, THEN drain chunk k's already-pushed metrics.
    s = clone(state0)
    hist_mpmd = []
    pend = None
    dispatch = []
    with sentinel.armed():
        t0 = time.perf_counter()
        for _ in range(chunks):
            td = time.perf_counter()
            s, m = mpmd(s, batch)
            dispatch.append(time.perf_counter() - td)
            if pend is not None:
                hist_mpmd.append(fetched(pend))
            pend = m
        hist_mpmd.append(fetched(pend))
        mpmd_sync_s = (time.perf_counter() - t0) / (chunks * rps)
    state_mpmd = jax.tree.map(np.asarray, s)

    bad = 0
    for a, b in zip(hist_mono, hist_mpmd):
        if jax.tree.structure(a) != jax.tree.structure(b):
            raise RuntimeError("--mpmd sync row: metric tree structure "
                               "diverged from the monolithic oracle")
        bad += sum(not np.array_equal(x, y) for x, y in
                   zip(jax.tree.leaves(a), jax.tree.leaves(b)))
    bad += sum(not np.array_equal(x, y) for x, y in
               zip(jax.tree.leaves(state_mono),
                   jax.tree.leaves(state_mpmd)))
    if bad:
        raise RuntimeError(
            f"--mpmd sync row lost bitwise parity with the monolithic "
            f"oracle: {bad} leaves differ across {chunks} chunks")

    assert_above_flops_floor(mono_sync_s, flops, peak,
                             label="mpmd-row mono sync")
    assert_above_flops_floor(mpmd_sync_s, flops, peak,
                             label="mpmd-row mpmd sync")

    # Host dispatch cost per chunk — a DIAGNOSTIC: the DAG enqueue cost
    # where dispatch is asynchronous (the TPU); where the call blocks
    # through the compute (the CPU backend) it degenerates to ~chunk
    # compute.
    host_dispatch_s = float(np.median(dispatch))
    return {"rounds_per_step": rps,
            "sync_s": mono_sync_s,
            "mpmd_sync_s": mpmd_sync_s,
            "improvement_measured": mono_sync_s / mpmd_sync_s,
            "parity_bitwise": True,
            "chunks_compared": chunks,
            "recompiles": sentinel.count,
            "host_dispatch_s": host_dispatch_s}


def bench_reference_equivalent(ds) -> dict:
    """Measured reference-equivalent baseline; see module docstring."""
    import torch
    import torch.nn as nn

    def make_model():
        # Same architecture as FL_CustomMLP...:12-25, hidden [50, 200] (:40).
        return nn.Sequential(
            nn.Linear(ds.input_dim, 50), nn.ReLU(),
            nn.Linear(50, 200), nn.ReLU(),
            nn.Linear(200, ds.num_classes))

    torch.set_num_threads(max(1, os.cpu_count() or 1))
    n = len(ds.x_train)
    chunk = max(1, n // NUM_CLIENTS)
    shards = []
    for r in range(NUM_CLIENTS):
        s, e = r * chunk, (r + 1) * chunk if r != NUM_CLIENTS - 1 else n
        shards.append((torch.tensor(ds.x_train[s:e]),
                       torch.tensor(ds.y_train[s:e], dtype=torch.long)))

    models = [make_model() for _ in range(NUM_CLIENTS)]
    opts = [torch.optim.Adam(m.parameters(), lr=0.004) for m in models]
    scheds = [torch.optim.lr_scheduler.StepLR(o, step_size=30, gamma=0.5)
              for o in opts]
    crit = nn.CrossEntropyLoss()

    def one_round():
        t_compute = 0.0
        t_serial = 0.0
        gathered = []
        sizes = []
        for m, o, sch, (x, y) in zip(models, opts, scheds, shards):
            t0 = time.perf_counter()
            # train_one_epoch (:63-73): one full-batch fwd/bwd/Adam step.
            o.zero_grad()
            loss = crit(m(x), y)
            loss.backward()
            o.step()
            sch.step()
            # evaluate_local (:75-91): argmax on the local shard.
            with torch.no_grad():
                m(x).argmax(dim=1).numpy()
            t_compute += time.perf_counter() - t0

            t0 = time.perf_counter()
            # get_weights + comm.gather pickling (:93-94,105).
            w = {k: v.detach().numpy().copy()
                 for k, v in m.named_parameters()}
            gathered.append(pickle.loads(pickle.dumps(w)))
            sizes.append(len(x))
            t_serial += time.perf_counter() - t0

        t0 = time.perf_counter()
        # rank-0 weighted average (:108-116).
        total = sum(sizes)
        avg = {k: sum(g[k] * (s / total) for g, s in zip(gathered, sizes))
               for k in gathered[0]}
        # comm.bcast back out + set_weights (:119-120).
        for m in models:
            blob = pickle.loads(pickle.dumps(avg))
            with torch.no_grad():
                for k, p in m.named_parameters():
                    p.copy_(torch.tensor(blob[k]))
        t_serial += time.perf_counter() - t0
        return t_compute, t_serial

    one_round()  # warmup
    reps = 5
    rounds = [one_round() for _ in range(reps)]
    # mpirun runs ranks concurrently: ideal-parallel compute, serial comm.
    parallel = min(NUM_CLIENTS, os.cpu_count() or 1)
    # Min over reps, not mean: transient load on this shared box inflates
    # the baseline and would overstate OUR speedup — take the reference's
    # least-contended (fastest) showing of the REPORTED metric (the
    # parallel-credited sum, not raw tc+ts, which could pick a rep whose
    # reported value is actually slower on a multi-core box).
    tc, ts = min(rounds, key=lambda r: r[0] / parallel + r[1])
    return {"sec_per_round": tc / parallel + ts,
            "compute_s": tc, "serial_s": ts, "assumed_parallelism": parallel}


def emit_result(result: dict, detail_lines, out_path=None) -> str:
    """Emit the benchmark artifact in consumer-safe order.

    Detail lines go to stderr FIRST, then the full JSON blob is written to
    ``out_path`` (when given) and printed LAST on stdout. Harnesses that
    read "the last stdout line" or "everything after the last brace" get a
    complete, parseable document — the earlier ordering (JSON first) let
    interleaved stream flushing truncate the blob and parse to null.
    """
    for line in detail_lines:
        print(line, file=sys.stderr)
    blob = json.dumps(result)
    if out_path:
        with open(out_path, "w") as f:
            f.write(blob + "\n")
    sys.stderr.flush()
    print(blob, flush=True)
    return blob


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default="BENCH_RESULT.json",
                    help="file the full JSON result is written to "
                         "(default: %(default)s)")
    ap.add_argument("--events", default=None, metavar="PATH",
                    help="telemetry JSONL sink for per-stage bench spans "
                         "(inspect with 'fedtpu report PATH')")
    ap.add_argument("--compilation-cache", default=None, metavar="DIR",
                    help="keep the compile cache in DIR instead of "
                         "<checkout>/.jax_cache (JAX_COMPILATION_CACHE_DIR, "
                         "when set, wins); a warm cache collapses "
                         "cold_compile_s to the deserialize cost "
                         "(docs/performance.md)")
    args = ap.parse_args(argv)

    device = require_tpu()
    from fedtpu.compilation import configure_persistent_cache
    configure_persistent_cache(args.compilation_cache)

    from fedtpu.telemetry import build_manifest, make_tracer
    tracer = make_tracer(args.events)
    if tracer.enabled:
        tracer.event("manifest", **build_manifest(
            extra={"program": "bench", "headline_rps": HEADLINE_RPS}))

    with tracer.span("dataset"):
        ds = _dataset()
    with tracer.span("bench_fedtpu"):
        ours = bench_fedtpu(ds)
    with tracer.span("mfu_capability"):
        capability = bench_mfu_capability(ours["peak_flops_measured"])
    with tracer.span("mpmd_sync"):
        mpmd_row = bench_mpmd_sync(ds, ours["peak_flops_measured"])
    with tracer.span("baseline"):
        base = bench_reference_equivalent(ds)
    lo, hi = ours["sec_per_round_range"]
    g3 = lambda v: float(f"{v:.3g}")
    result = {
        "metric": "sec_per_round_fedavg8_income_mlp",
        "device": device,
        "data": ds.source,
        # 3 significant figures — the value sits at sub-millisecond scale
        # where fixed decimals would destroy it. The headline is the MEDIAN
        # of 5 independent timed windows; vs_baseline_range is the full
        # window band, so the single number can never travel without its
        # jitter (review r2).
        "value": g3(ours["sec_per_round"]),
        "unit": "s",
        "vs_baseline": float(
            f"{base['sec_per_round'] / ours['sec_per_round']:.4g}"),
        "vs_baseline_range": [g3(base["sec_per_round"] / hi),
                              g3(base["sec_per_round"] / lo)],
        "mfu": g3(ours["mfu"]),
        # Backend compiles observed INSIDE timed windows (recompile
        # sentinel, fedtpu.analysis.guards): must be 0 — a nonzero count
        # means the quoted numbers include silent retrace cost.
        "recompiles": ours["recompiles"],
        # Startup-cost pair for the headline program: trace+compile from
        # nothing vs a warm ProgramCache deserialize (what a
        # --compilation-cache / 'fedtpu warmup' start pays instead).
        "cold_compile_s": g3(ours["cold_compile_s"])
        if ours["cold_compile_s"] is not None else None,
        "warm_lookup_ms": g3(ours["warm_lookup_ms"])
        if ours["warm_lookup_ms"] is not None else None,
        # The headline mfu above is the income workload's BANDWIDTH roofline
        # (byte-bound); this row is the same engine at an MXU-sized shape,
        # dispatch-cancelled slope timing.
        "mfu_capability": {
            "hidden": capability["hidden"],
            "rows_per_client": capability["rows_per_client"],
            "marginal_s_per_round": g3(capability["marginal_s_per_round"]),
            "flops_per_round": g3(capability["flops_per_round"]),
            "mfu": g3(capability["mfu"]),
        },
        "sweep": {str(rps): {"pipelined_s": g3(row["sec_per_round"]),
                             "sync_s": g3(row["sec_per_round_sync"]),
                             "mfu": g3(row["mfu"])}
                  for rps, row in ours["sweep"].items()},
        # PR 18 --mpmd sync-mode row (bench_mpmd_sync): the early-stop
        # loop shape through the MPMD DAG, bitwise metric-history parity
        # re-proven in-run (the bench crashes otherwise).
        "mpmd_sync": {
            "rounds_per_step": mpmd_row["rounds_per_step"],
            "sync_s": g3(mpmd_row["sync_s"]),
            "mpmd_sync_s": g3(mpmd_row["mpmd_sync_s"]),
            "improvement_measured": g3(mpmd_row["improvement_measured"]),
            "parity_bitwise": mpmd_row["parity_bitwise"],
            "chunks_compared": mpmd_row["chunks_compared"],
            "recompiles": mpmd_row["recompiles"],
            "host_dispatch_s": g3(mpmd_row["host_dispatch_s"]),
        },
        "baseline": {
            "sec_per_round": g3(base["sec_per_round"]),
            "assumed_parallelism": base["assumed_parallelism"],
            # The parallel-credit caveat must ride IN the artifact: the
            # baseline's compute term is divided by min(8, cpu_count).
            # On this 1-core box that credit is 1; on an 8-core host the
            # reference's compute shrinks up to 8x and the quoted speedup
            # drops accordingly (see vs_baseline_if_8cores).
            "vs_baseline_if_8cores": g3(
                (base["compute_s"] / 8 + base["serial_s"])
                / ours["sec_per_round"]),
        },
    }
    # Detail lines accumulate here and hit stderr BEFORE the JSON blob —
    # the complete JSON must be the LAST thing on stdout (emit_result).
    detail = [
        f"[bench] headline (rps={HEADLINE_RPS}, pipelined): "
        f"{ours['sec_per_round']:.3e} s/round "
        f"(window band [{lo:.3e}, {hi:.3e}]; "
        f"synchronous {ours['sec_per_round_sync']:.3e}), "
        f"accuracy {ours['accuracy']:.4f}, device {device}, data "
        f"{ds.source}, measured peak "
        f"{ours['peak_flops_measured'] / 1e12:.1f} TFLOP/s, "
        f"{ours['flops_per_round']:.2e} FLOPs/round, "
        f"MFU {100 * ours['mfu']:.1f}%, "
        f"{ours['recompiles']} in-window recompiles",
        f"[bench] headline compile cost: cold {ours['cold_compile_s']:.3f} s"
        f", warm deserialize {ours['warm_lookup_ms']:.1f} ms"
        if ours["cold_compile_s"] is not None
        and ours["warm_lookup_ms"] is not None else
        "[bench] headline compile cost: unavailable",
        f"[bench] MFU capability (hidden {capability['hidden']}, "
        f"{capability['rows_per_client']} rows/client, slope-timed): "
        f"{capability['marginal_s_per_round']:.3e} s/round, "
        f"{capability['flops_per_round']:.2e} FLOPs/round, "
        f"MFU {100 * capability['mfu']:.1f}% — the income headline above "
        "is byte-bound at its own roofline",
    ]
    for rps, row in ours["sweep"].items():
        detail.append(
            f"[bench] rps={rps:>4}: pipelined "
            f"{row['sec_per_round']:.3e} s/round, sync "
            f"{row['sec_per_round_sync']:.3e} s/round "
            f"(floor {row['floor_sec']:.3e}, "
            f"MFU {100 * row['mfu']:.1f}%, "
            f"{row['rounds_timed']} rounds/window, "
            f"{row['rounds_trained']} trained)")
    detail.append(
        f"[bench] mpmd sync-mode (rps={mpmd_row['rounds_per_step']}, --mpmd "
        f"DAG, one-chunk lag): {mpmd_row['mpmd_sync_s']:.3e} s/round vs "
        f"monolithic sync {mpmd_row['sync_s']:.3e} — measured "
        f"{mpmd_row['improvement_measured']:.2f}x; metric history + final "
        f"state bitwise over {mpmd_row['chunks_compared']} chunks, "
        f"{mpmd_row['recompiles']} in-window recompiles")
    detail.append(
        f"[bench] baseline(measured reference-equivalent): {base} — "
        "compute credited /min(8, cpu_count); an 8-core host shrinks "
        "the baseline and the speedup accordingly")
    if args.out:
        detail.append(f"[bench] full JSON result written to {args.out}")
    emit_result(result, detail, out_path=args.out)
    tracer.event("bench_end", headline_s=result["value"],
                 vs_baseline=result["vs_baseline"])
    tracer.close()


if __name__ == "__main__":
    main()
