"""Driver ``train``: federated training jobs through the program's normal
path, ``fedtpu.orchestration.loop.run_experiment``.

Set-up (all of it is ``setup_s``): the cell's dataset from ``--seed``, the
plain reference's first rounds, one warm-up job at the cell's chunk width
(which compiles the cell's own shapes and gives the rate that sizes the
window). Every job runs as ``fedtpu run`` ships it, reporting each round on
standard output, and the harness holds that output and stamps each round's
line with its own clock (arith.RoundStamps). Window, tracing off: the jobs
the cell's file states, all of one length; a job's round is the median time
between two chunks' lines (arith.round_intervals), so what a job pays once,
and any stall, is in no value, and ``round_ms`` is the median of the jobs'. ``--trace 1`` runs the
same set-up, then one plain short job whose time beyond its rounds is the
per-layer ``job_fixed_s``, then one job with the program's events sink on and
its profiler window open over a few steady chunks, and reads the per-layer
metrics from sink, listener and trace.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import json
import os
import shutil
import time

import jax
import jax.numpy as jnp
import numpy as np

from perfbench import arith, datasets, flops, reference, xplane

# The system's first rounds against the plain reference (float32 at
# 'highest' matmul precision): the largest absolute difference over all
# clients' training losses of rounds 1..K. Measured on the v5e (my chip
# runs, PR 22, some twenty seeds): 7.4e-5 to 1.53e-4 for the float32 MLP, whose
# default-precision matmuls take bf16 inputs on the MXU, and 2.2e-5 to
# 3.7e-5 for the bf16 ConvNet, whose losses are means over 500 rows near
# log 10. The tolerances are about four times the largest gap seen. They
# hold the algorithm (optimiser, schedule, weighting, which rows a client
# holds); they cannot tell float32 from bfloat16 compute on this chip: the
# MLP run in bf16 lands at 7.8e-5 to 1.02e-4, inside the float32 spread
# (PERF.md, Findings). A configuration's rehearsal block widens them for the
# eight-row shards of the CPU walk-through.
LOSS_TOLERANCE = {"float32": 6e-4, "bfloat16": 1.5e-4}


def _coerce(value):
    return tuple(_coerce(v) for v in value) if isinstance(value, list) else value


def experiment_config(sections: list, seed: int):
    """``ExperimentConfig()`` with each dict of ``{section: {field: value}}``
    laid over it in turn (configuration, traffic, cell); seeds from --seed."""
    from fedtpu.config import ExperimentConfig

    cfg = ExperimentConfig()
    for over in sections:
        for section, fields in (over or {}).items():
            cfg = cfg.replace(**{section: dataclasses.replace(
                getattr(cfg, section),
                **{k: _coerce(v) for k, v in fields.items()})})
    fed = dataclasses.replace(cfg.fed, init_seed=seed, participation_seed=seed)
    return cfg.replace(fed=fed)


def with_run(cfg, rounds: int, **run_fields):
    from fedtpu.config import TelemetryConfig

    events = run_fields.pop("events_path", None)
    run = dataclasses.replace(cfg.run, **run_fields)
    if events:
        run = dataclasses.replace(
            run, telemetry=TelemetryConfig(events_path=events))
    return cfg.replace(fed=dataclasses.replace(cfg.fed, rounds=rounds), run=run)


def run_job(ctx, cfg, dataset, phase: str):
    """One job through the program, timed by the harness's clock; returns
    ``(result, seconds, stamps)``. The job reports its rounds as the shipped
    default does (``verbose``), into the harness's own stream, which stamps
    them. The window closes on host values: ``run_experiment`` returns its
    histories and the final model as numpy arrays, which is asserted."""
    t_import = time.perf_counter()
    from fedtpu.orchestration.loop import run_experiment
    ctx.clocks.setdefault("program_import_s", time.perf_counter() - t_import)

    gc.collect()
    out = arith.RoundStamps(ctx.traffic["round_marker"])
    ctx.compiles.phase = phase
    t0 = time.perf_counter()
    with contextlib.redirect_stdout(out):
        result = run_experiment(cfg, dataset=dataset, verbose=True)
    seconds = time.perf_counter() - t0
    ctx.compiles.phase = "between"
    leaves = jax.tree.leaves(result.final_params) + list(result.loss[-1:])
    if not all(isinstance(a, np.ndarray) for a in leaves):
        raise AssertionError("run_experiment returned a device value: the "
                             "window was not closed on a fetch")
    return result, seconds, out.stamps


def job_faults(result, rounds: int) -> int:
    """Rounds of a job that were not run or whose loss is not finite."""
    bad = max(0, rounds - result.rounds_run)
    bad += sum(1 for row in result.loss if not np.all(np.isfinite(row)))
    return rounds if result.diverged else bad


def initial_params(cfg, num_classes: int, input_dim: int):
    """The per-client initial parameters the program draws from
    ``fed.init_seed``, made the same way: an input of both sides."""
    from fedtpu.models.registry import build_model
    from fedtpu.parallel.round import client_init_keys

    model = cfg.model
    if model.kind == "mlp":
        model = dataclasses.replace(model, input_dim=input_dim)
    model = dataclasses.replace(model, num_classes=num_classes)
    init_fn, _ = build_model(model)
    keys = client_init_keys(jax.random.key(cfg.fed.init_seed),
                            cfg.shard.num_clients, cfg.fed.same_init)
    return jax.jit(jax.vmap(init_fn))(keys)


def reference_rounds(cfg, dataset, rounds: int):
    """``(losses (rounds, C), global params)`` of the plain reference on the
    contiguous client shards the configuration states (``shard.shuffle``
    off: client c holds rows ``[c*n, (c+1)*n)`` of the training split)."""
    c = cfg.shard.num_clients
    n = len(dataset.x_train) // c
    # shaped on the host and placed once: the reference's buffers must stay
    # under the round program's footprint, or the peak reported is its own
    x = jnp.asarray(dataset.x_train[:c * n].reshape(c, n, -1))
    y = jnp.asarray(dataset.y_train[:c * n].reshape(c, n))
    losses, glob = reference.fedavg_rounds(
        cfg.model.kind,
        initial_params(cfg, dataset.num_classes, dataset.input_dim), x, y,
        jnp.ones((c, n), jnp.float32), rounds, dataclasses.asdict(cfg.optim))
    return np.asarray(losses), jax.tree.map(np.asarray, glob)


def program_footprint(cfg, dataset, width: int) -> dict:
    """The compiler's account of the round program as compiled on this
    device, per chip: arguments + outputs - aliased + temporaries. The
    runtime's ``peak_bytes_in_use`` counts the buffers JAX holds and not the
    scratch a running program takes (PERF.md, PR 22: a program whose one
    temporary is 2.05 GB ran under a reported peak of 1.75 GB), so the peak
    on the chip is the larger of the two. Built by the program's own
    builder from the same configuration and data, so the executable is the
    one the jobs run and comes from the compile cache."""
    from fedtpu.orchestration.loop import build_experiment

    exp = build_experiment(cfg, dataset)
    compiled = exp.make_step(width).lower(exp.state, exp.batch).compile()
    ma = compiled.memory_analysis()
    parts = {"arguments": int(ma.argument_size_in_bytes),
             "outputs": int(ma.output_size_in_bytes),
             "aliased": int(ma.alias_size_in_bytes),
             "temporaries": int(ma.temp_size_in_bytes)}
    parts["total"] = (parts["arguments"] + parts["outputs"] - parts["aliased"]
                      + parts["temporaries"])
    return parts


def params_gap(a, b) -> float:
    return float(max(np.max(np.abs(np.asarray(x, np.float64) - np.asarray(y)))
                     for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))))


def one_device_share(dataset, cfg, devices: int):
    """The share of the job one device holds: the first ``C / devices``
    clients' rows as a job of its own on one device (weak scaling)."""
    c = cfg.shard.num_clients // devices
    n = len(dataset.x_train) // cfg.shard.num_clients
    part = dataclasses.replace(dataset, x_train=dataset.x_train[:c * n],
                               y_train=dataset.y_train[:c * n])
    small = cfg.replace(shard=dataclasses.replace(cfg.shard, num_clients=c))
    return part, small


def read_sink(path: str) -> list:
    if not os.path.exists(path):
        return []
    with open(path) as fh:
        return [json.loads(line) for line in fh if line.strip()]


def run(ctx) -> dict:
    """Run the cell; returns ``{"correct", "attempted", "failed", "metrics",
    "device_extra", "breakdown", "lines"}`` for run.py to print."""
    cell, conf, traffic = ctx.cell, ctx.config, ctx.traffic
    if ctx.rehearsal:
        conf = _overlay(conf, conf.get("rehearsal", {}))
        traffic = _overlay(traffic, traffic.get("rehearsal", {}))
    cfg = experiment_config(
        [conf["experiment"], {k: traffic[k] for k in ("run", "fed") if k in traffic},
         {"run": {"mesh_devices": cell["chips"]}}], ctx.seed)
    width = cfg.run.rounds_per_step
    clients = cfg.shard.num_clients
    lines = []

    # ------------------------------------------------------------ set-up
    t = time.perf_counter()
    dataset = datasets.make(conf["dataset"], clients, ctx.seed)
    ctx.clocks["data_build_s"] = time.perf_counter() - t

    k = int(traffic["check_rounds"])
    t = time.perf_counter()
    ref_losses, ref_params = reference_rounds(cfg, dataset, k)
    ctx.clocks["reference_s"] = time.perf_counter() - t
    gc.collect()
    ctx.memory["after_reference"] = ctx.peak_bytes()

    warm_rounds = int(traffic["warmup_rounds"])
    with jax.profiler.TraceAnnotation("warmup"):
        warm, warm_s, _ = run_job(ctx, with_run(cfg, warm_rounds), dataset,
                                  "warmup")
    ctx.clocks["warmup_job_s"] = warm_s
    sys_losses = np.stack(warm.loss[:k])
    loss_gap = float(np.max(np.abs(sys_losses - ref_losses)))
    check = {"rounds": k, "loss_gap": loss_gap,
             "tolerance": conf.get("loss_tolerance",
                                   LOSS_TOLERANCE[cfg.model.compute_dtype])}
    if warm_rounds == k:        # the job ended where the reference did
        check["params_gap"] = params_gap(warm.final_params, ref_params)
    source_ok = (warm.data.get("generator")
                 == f"perfbench.{conf['dataset']['generator']}")
    faults = job_faults(warm, warm_rounds)
    correct = (loss_gap <= check["tolerance"] and source_ok and faults == 0
               and bool(np.all(np.isfinite(ref_losses))))
    steady = warm.sec_per_round[width:] or warm.sec_per_round
    rate = float(np.median(steady))
    lines.append({"check": check, "data": warm.data, "source_ok": source_ok,
                  "warmup": {"rounds": warm_rounds, "seconds": warm_s,
                             "sec_per_round": rate, "faults": faults}})
    del warm
    ctx.memory["after_warmup"] = ctx.peak_bytes()
    t = time.perf_counter()
    ctx.compiles.phase = "setup"
    ctx.memory["round_program"] = program_footprint(cfg, dataset, width)
    ctx.clocks["footprint_s"] = time.perf_counter() - t
    gc.collect()
    ctx.clocks["setup_s"] = time.perf_counter() - ctx.t0
    ctx.compiles.phase = "between"

    cost = flops.round_cost(conf["experiment"]["model"] | {
        "input_dim": dataset.input_dim, "num_classes": dataset.num_classes},
        clients, len(dataset.x_train) // clients, dataset.input_dim)
    ctx.evidence.facts.update(cost=cost, chips=cell["chips"], width=width)

    if not ctx.trace:
        out = _window(ctx, cfg, dataset, width, lines)
    else:
        out = _traced(ctx, cfg, dataset, width, traffic, lines)
    out["correct"] = bool(correct and out["correct"])
    out["lines"] = lines
    return out


def _overlay(base: dict, over: dict) -> dict:
    out = dict(base)
    for key, value in over.items():
        out[key] = (_overlay(base[key], value)
                    if isinstance(value, dict) and isinstance(base.get(key), dict)
                    else value)
    return out


def stamped_job(ctx, cfg, dataset, rounds: int, width: int, phase: str):
    """One plain job of ``rounds`` rounds: its record for the earlier lines
    and its rounds' intervals; ``intervals`` is ``None`` where a round was
    not reported."""
    result, seconds, stamps = run_job(ctx, with_run(cfg, rounds), dataset, phase)
    try:
        intervals = arith.round_intervals(stamps, rounds, width)
    except ValueError as err:
        intervals, ctx.evidence.notes[phase] = None, str(err)
    record = {"rounds": rounds, "seconds": seconds,
              "faults": job_faults(result, rounds),
              "compiles": ctx.compiles.count(phase),
              "cache_hits": ctx.compiles.hit_count(phase),
              "loop_round_ms": 1000 * float(np.median(result.sec_per_round))}
    if intervals:
        record["round_ms"] = arith.round_ms(intervals)
        record["fixed_s"] = seconds - rounds * record["round_ms"] / 1000
    return record, intervals


def _window(ctx, cfg, dataset, width, lines):
    window = ctx.cell.get("window")
    if not window:
        raise SystemExit(f"perfbench: workloads/{ctx.cell['name']}.json states "
                         "no window (jobs, job_rounds) for driver train")
    rounds = arith.job_rounds(int(window["job_rounds"]), ctx.seconds,
                              ctx.run_seconds, width)
    jobs, values = [], 0
    t_window = time.perf_counter()
    for i in range(int(window["jobs"])):
        record, intervals = stamped_job(ctx, cfg, dataset, rounds, width,
                                        f"job{i}")
        jobs.append(record)
        values += len(intervals or ())
    window_s = time.perf_counter() - t_window
    reported = all("round_ms" in job for job in jobs)
    round_ms = (arith.window_round_ms([job["round_ms"] for job in jobs])
                if reported else None)
    lines.append({"window": {"seconds": window_s, "asked": ctx.seconds,
                             "job_rounds": rounds, "round_ms": round_ms,
                             "intervals": values, "jobs": jobs}})
    failed = sum(job["faults"] for job in jobs)
    same_compiles = len({job["compiles"] for job in jobs}) == 1
    return {"correct": failed == 0 and same_compiles and reported,
            "attempted": rounds * len(jobs), "failed": failed,
            "metrics": {"round_ms": round_ms} if reported else {}}


def _traced(ctx, cfg, dataset, width, traffic, lines):
    out_dir = os.path.join(ctx.out_dir, f"trace-{ctx.cell['name']}")
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    chunks = int(traffic["trace_chunks"])
    rounds = width * (chunks + 2)     # the chunk that compiles, the window, one more
    sink = os.path.join(out_dir, "events.jsonl")
    traced_cfg = with_run(cfg, rounds, events_path=sink,
                          profile_dir=os.path.join(out_dir, "profile"),
                          profile_rounds=width * chunks)
    # what a job costs beyond its rounds, from a plain job (no sink, no
    # profiler: the traced job below pays for both)
    plain, _ = stamped_job(ctx, cfg, dataset, int(traffic["fixed_job_chunks"]) * width,
                           width, "plain_job")
    if "fixed_s" in plain:
        ctx.clocks["job_fixed_s"] = plain["fixed_s"]
    lines.append({"plain_job": plain})
    with jax.profiler.TraceAnnotation("job"):
        result, seconds, _ = run_job(ctx, traced_cfg, dataset, "job")
    failed = job_faults(result, rounds) + plain["faults"]
    del result
    ev = ctx.evidence
    ev.sinks["job"] = read_sink(sink)
    stops = [e for e in ev.sinks["job"] if e.get("kind") == "profile_window"
             and e.get("phase") == "stop"]
    ev.facts["trace_rounds"] = stops[-1]["payload"]["rounds"] if stops else None
    ev.facts["phases"] = ("setup", "warmup", "job")
    path = xplane.newest_xplane(os.path.join(out_dir, "profile"))
    ev.trace = xplane.load(path) if path else None
    attempted = rounds + plain["rounds"]

    if ctx.cell.get("one_device_share") and ctx.cell["chips"] > 1:
        part, small = one_device_share(dataset, cfg, ctx.cell["chips"])
        sink1 = os.path.join(out_dir, "events_1dev.jsonl")
        gc.collect()
        r1, _, _ = run_job(ctx, with_run(small, rounds, events_path=sink1,
                                         mesh_devices=1), part, "one_device")
        failed += job_faults(r1, rounds)
        attempted += rounds
        del r1
        ev.sinks["one_device"] = read_sink(sink1)

    busy = ev.reduced("device_busy") if ev.trace else {}
    breakdown = ({"device_ops": xplane.top_ops(ev.trace),
                  "idle_gaps": xplane.idle_gaps(ev.trace)} if ev.trace else {})
    lines.append({"traced_job": {"rounds": rounds, "seconds": seconds,
                                 "trace_rounds": ev.facts["trace_rounds"],
                                 "xplane": path, "devices_traced":
                                 sorted(ev.trace.devices) if ev.trace else []}})
    return {"correct": failed == 0 and (bool(busy) or ctx.rehearsal),
            "attempted": attempted, "failed": failed, "metrics": {},
            "device_extra": {k: busy[k] for k in ("busy_s", "window_s")
                             if k in busy},
            "breakdown": breakdown}
