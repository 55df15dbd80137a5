"""Command-line entry point — the config/flag layer the reference never had.

The reference's launch story is ``mpirun -np N python <script>.py`` with every
hyperparameter hardcoded (SURVEY.md §1 L6); changing the client count means
changing the mpirun invocation, changing anything else means editing source.
fedtpu: ``python -m fedtpu.cli run --preset income-8 [overrides]`` on the TPU
host — no launcher, the mesh IS the topology.

Subcommands:
    run    — run a federated experiment from a preset + CLI overrides
    sweep  — the 90-config hyperparameter grid (hyperparameters_tuning.py)
    parity — the sklearn MLPClassifier warm-start limitation demo (FL_SkLearn...)
    presets — list shipped presets
    report — aggregate a telemetry events JSONL offline (docs/observability.md)
    lint   — JAX-aware static analysis (FTP rules, docs/analysis.md); pure
             AST, never touches a backend
    check  — runtime guard: prove the round step is retrace-free under
             jax.transfer_guard / the recompile sentinel
    autoscale — SLO-driven autoscaling control plane: poll live signals
             (or replay a trace in --simulate) and act through the
             reshard/serving knobs (docs/autoscale.md)
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

from fedtpu.config import PRESETS, get_preset, ExperimentConfig


def _hidden_sizes(text: str):
    try:
        return tuple(int(s) for s in text.split(","))
    except ValueError:
        raise argparse.ArgumentTypeError(
            f"expected comma-separated integers, got {text!r}")


def _participation_rate(text: str) -> float:
    rate = float(text)
    if not 0.0 < rate <= 1.0:
        raise argparse.ArgumentTypeError(
            f"participation rate must be in (0, 1], got {rate}")
    return rate


def _positive_int(text: str) -> int:
    value = int(text)
    if value < 1:
        raise argparse.ArgumentTypeError(f"must be >= 1, got {value}")
    return value


def _nonnegative_int(text: str) -> int:
    value = int(text)
    if value < 0:
        raise argparse.ArgumentTypeError(f"must be >= 0, got {value}")
    return value


def _nonnegative_float(text: str) -> float:
    value = float(text)
    if value < 0:
        raise argparse.ArgumentTypeError(f"must be >= 0, got {value}")
    return value


def _positive_float(text: str) -> float:
    value = float(text)
    if value <= 0:
        raise argparse.ArgumentTypeError(f"must be > 0, got {value}")
    return value


def _open_unit_float(text: str) -> float:
    value = float(text)
    if not 0.0 < value < 1.0:
        raise argparse.ArgumentTypeError(
            f"must be in the open interval (0, 1), got {value}")
    return value


def _add_common_overrides(p: argparse.ArgumentParser):
    p.add_argument("--preset", default="income-8", choices=sorted(PRESETS))
    p.add_argument("--csv", default=None, help="dataset CSV path")
    p.add_argument("--label-column", default=None)
    p.add_argument("--num-clients", type=int, default=None)
    p.add_argument("--rounds", type=int, default=None)
    p.add_argument("--hidden-sizes", type=_hidden_sizes, default=None,
                   help="comma-separated, e.g. 50,200")
    p.add_argument("--learning-rate", type=float, default=None)
    p.add_argument("--weighting", choices=["data_size", "uniform"], default=None)
    p.add_argument("--local-steps", type=_positive_int, default=None,
                   help="full-batch steps per client per round (classic "
                        "FedAvg E >= 1; reference does 1)")
    p.add_argument("--prox-mu", type=_nonnegative_float, default=None,
                   help="FedProx proximal coefficient >= 0 (0 = plain "
                        "FedAvg; meaningful with --local-steps > 1)")
    p.add_argument("--scaffold", action="store_true", default=None,
                   help="SCAFFOLD control-variate drift correction "
                        "(Karimireddy et al. 2020; needs --weighting "
                        "uniform)")
    p.add_argument("--participation-rate", type=_participation_rate,
                   default=None,
                   help="per-round client sampling probability in (0, 1] "
                        "(default 1.0)")
    p.add_argument("--server-opt",
                   choices=["none", "fedavgm", "fedadagrad", "fedyogi",
                            "fedadam"],
                   default=None,
                   help="server optimizer over client deltas (FedOpt; "
                        "'none' = the reference's parameter averaging)")
    p.add_argument("--server-lr", type=float, default=None,
                   help="server optimizer learning rate (default 1.0)")
    p.add_argument("--server-momentum", type=_nonnegative_float, default=None,
                   help="fedavgm momentum (default 0.9)")
    p.add_argument("--dp-clip-norm", type=_nonnegative_float, default=None,
                   help="per-client L2 clip of updates (DP-FedAvg; 0 = off)")
    p.add_argument("--dp-noise-multiplier", type=_nonnegative_float,
                   default=None,
                   help="Gaussian noise multiplier on the averaged clipped "
                        "delta (needs --dp-clip-norm > 0)")
    p.add_argument("--dp-delta", type=_open_unit_float, default=None,
                   help="target delta for the (epsilon, delta) report the "
                        "RDP accountant adds to the summary when DP noise "
                        "is on (default 1e-5; pick << 1/num_clients; "
                        "rejected at parse time outside (0, 1) — the "
                        "accountant would refuse it after the whole run)")
    p.add_argument("--dp-adaptive-clip", action="store_true", default=None,
                   help="adaptive clipping (Andrew et al. 2021): the clip "
                        "norm tracks --dp-target-quantile of client update "
                        "norms, starting at --dp-clip-norm")
    p.add_argument("--dp-target-quantile", type=_open_unit_float,
                   default=None,
                   help="quantile of update norms the adaptive clip tracks "
                        "(default 0.5)")
    p.add_argument("--dp-clip-lr", type=_nonnegative_float, default=None,
                   help="geometric step size of the adaptive clip update "
                        "(default 0.2)")
    p.add_argument("--dp-count-noise-multiplier", type=_nonnegative_float,
                   default=None,
                   help="noise on the clipped-count release under adaptive "
                        "clipping with DP noise on; must exceed "
                        "dp_noise_multiplier/2 (the delta noise is then "
                        "raised so the composed round charges exactly "
                        "--dp-noise-multiplier)")
    p.add_argument("--compress", choices=["none", "int8"], default=None,
                   help="int8-quantize the update exchange (D/8 of the f32 "
                        "psum traffic at D devices; for few-host DCN-bound "
                        "aggregation)")
    p.add_argument("--robust-aggregation",
                   choices=["none", "median", "trimmed_mean", "krum",
                            "geometric_median"],
                   default=None,
                   help="Byzantine-robust aggregation rule (requires "
                        "--weighting uniform and full participation)")
    p.add_argument("--trim-ratio", type=_nonnegative_float, default=None,
                   help="fraction trimmed from each end per coordinate "
                        "(trimmed_mean)")
    p.add_argument("--krum-f", type=int, default=None,
                   help="krum's assumed number of malicious clients")
    p.add_argument("--byzantine-clients", type=int, default=None,
                   help="fault injection: first k clients submit 10x "
                        "sign-flipped updates")
    p.add_argument("--shard-strategy",
                   choices=["contiguous", "label_sort", "dirichlet"],
                   default=None)
    p.add_argument("--compute-dtype", choices=["float32", "bfloat16"],
                   default=None)
    p.add_argument("--checkpoint-dir", default=None)
    p.add_argument("--checkpoint-every", type=int, default=None)
    p.add_argument("--keep-checkpoints", type=int, default=None,
                   help="retain only the k newest complete checkpoints "
                        "plus the best-accuracy round (0 = keep all)")
    p.add_argument("--eval-test-every", type=int, default=None)
    p.add_argument("--rounds-per-step", type=int, default=None,
                   help="rounds scanned per compiled step (throughput knob)")
    p.add_argument("--compilation-cache", default=None, metavar="DIR",
                   help="keep the compile cache in DIR instead of "
                        "<checkout>/.jax_cache, and store serialized "
                        "executables (ProgramCache) there too. "
                        "JAX_COMPILATION_CACHE_DIR, when set, wins over "
                        "DIR.")
    p.add_argument("--profile-dir", default=None,
                   help="write a jax.profiler trace of the round loop here")
    p.add_argument("--profile-rounds", type=int, default=None, metavar="K",
                   help="with --profile-dir: capture only a K-round "
                        "steady-state window (starts after the first "
                        "chunk, so compile time is excluded); 0 traces "
                        "the whole run")
    p.add_argument("--metrics-jsonl", default=None,
                   help="append one JSON line of metrics per round")
    p.add_argument("--events", default=None, metavar="JSONL",
                   help="append structured telemetry events here (run "
                        "manifest, per-phase spans, per-round cadence, "
                        "counter snapshots); analyze with "
                        "'fedtpu report <file>'")
    p.add_argument("--platform", choices=["default", "cpu"],
                   default="default",
                   help="force the JAX platform before backend init "
                        "('cpu' for hermetic debugging / chaos-test "
                        "subprocesses, and for every process but one "
                        "where several share a host's chip; 'default' "
                        "keeps the accelerator). Same effect as "
                        "JAX_PLATFORMS=cpu, as a flag a parent can put "
                        "on a child's command line")
    p.add_argument("--log-per-client", action="store_true")
    p.add_argument("--quiet", action="store_true")
    p.add_argument("--json", action="store_true",
                   help="print the result summary as one JSON line")


def _apply_overrides(cfg: ExperimentConfig, args) -> ExperimentConfig:
    data, shard, model = cfg.data, cfg.shard, cfg.model
    optim, fed, run = cfg.optim, cfg.fed, cfg.run
    if args.csv is not None:
        # --csv "" explicitly selects the synthetic dataset. Clearing
        # dataset_name makes --csv win over presets that select a named
        # loader (e.g. cifar10-32), which would otherwise ignore it.
        data = dataclasses.replace(data, csv_path=args.csv or None,
                                   dataset_name=None)
    if args.label_column is not None:
        data = dataclasses.replace(data, label_column=args.label_column)
    if args.num_clients is not None:
        shard = dataclasses.replace(shard, num_clients=args.num_clients)
    if args.shard_strategy is not None:
        shard = dataclasses.replace(shard, strategy=args.shard_strategy)
    if getattr(args, "partition_clients", None) is not None:
        shard = dataclasses.replace(shard,
                                    partition_clients=args.partition_clients)
    if getattr(args, "partition_offset", None) is not None:
        shard = dataclasses.replace(shard,
                                    partition_offset=args.partition_offset)
    if args.hidden_sizes is not None:
        model = dataclasses.replace(model, hidden_sizes=args.hidden_sizes)
    if args.compute_dtype is not None:
        model = dataclasses.replace(model, compute_dtype=args.compute_dtype)
    if args.learning_rate is not None:
        optim = dataclasses.replace(optim, learning_rate=args.learning_rate)
    if args.rounds is not None:
        fed = dataclasses.replace(fed, rounds=args.rounds)
    if args.weighting is not None:
        fed = dataclasses.replace(fed, weighting=args.weighting)
    if args.local_steps is not None:
        fed = dataclasses.replace(fed, local_steps=args.local_steps)
    if args.prox_mu is not None:
        fed = dataclasses.replace(fed, prox_mu=args.prox_mu)
    if args.scaffold:
        fed = dataclasses.replace(fed, scaffold=True)
    if args.participation_rate is not None:
        fed = dataclasses.replace(fed,
                                  participation_rate=args.participation_rate)
    if getattr(args, "aggregation", None) is not None:
        fed = dataclasses.replace(fed, aggregation=args.aggregation)
    if args.server_opt is not None:
        fed = dataclasses.replace(fed, server_opt=args.server_opt)
    if args.server_lr is not None:
        fed = dataclasses.replace(fed, server_lr=args.server_lr)
    if args.server_momentum is not None:
        fed = dataclasses.replace(fed, server_momentum=args.server_momentum)
    if args.dp_clip_norm is not None:
        fed = dataclasses.replace(fed, dp_clip_norm=args.dp_clip_norm)
    if args.dp_delta is not None:
        fed = dataclasses.replace(fed, dp_delta=args.dp_delta)
    if args.dp_noise_multiplier is not None:
        fed = dataclasses.replace(fed,
                                  dp_noise_multiplier=args.dp_noise_multiplier)
    if args.dp_adaptive_clip:
        fed = dataclasses.replace(fed, dp_adaptive_clip=True)
    if args.dp_target_quantile is not None:
        fed = dataclasses.replace(fed,
                                  dp_target_quantile=args.dp_target_quantile)
    if args.dp_clip_lr is not None:
        fed = dataclasses.replace(fed, dp_clip_lr=args.dp_clip_lr)
    if args.dp_count_noise_multiplier is not None:
        fed = dataclasses.replace(
            fed, dp_count_noise_multiplier=args.dp_count_noise_multiplier)
    if args.compress is not None:
        fed = dataclasses.replace(fed, compress=args.compress)
    if args.robust_aggregation is not None:
        fed = dataclasses.replace(fed,
                                  robust_aggregation=args.robust_aggregation)
    if args.trim_ratio is not None:
        fed = dataclasses.replace(fed, trim_ratio=args.trim_ratio)
    if args.krum_f is not None:
        fed = dataclasses.replace(fed, krum_f=args.krum_f)
    if getattr(args, "personalize_steps", None) is not None:
        fed = dataclasses.replace(fed,
                                  personalize_steps=args.personalize_steps)
    if args.byzantine_clients is not None:
        fed = dataclasses.replace(fed,
                                  byzantine_clients=args.byzantine_clients)
    if getattr(args, "init_weights", None) is not None:
        fed = dataclasses.replace(fed, init_weights_npz=args.init_weights)
    if getattr(args, "async_mode", False):
        fed = dataclasses.replace(fed, async_mode=True)
    elif any(getattr(args, a, None) is not None
             for a in ("arrival_rate", "arrival_seed", "staleness_power",
                       "buffer_size")):
        # Never silently ignore a semantic knob: these only exist under
        # the async tick process.
        raise SystemExit("--arrival-rate/--arrival-seed/--staleness-power/"
                         "--buffer-size require --async")
    if getattr(args, "arrival_rate", None) is not None:
        fed = dataclasses.replace(fed,
                                  async_arrival_rate=args.arrival_rate)
    if getattr(args, "arrival_seed", None) is not None:
        fed = dataclasses.replace(fed,
                                  async_arrival_seed=args.arrival_seed)
    if getattr(args, "staleness_power", None) is not None:
        fed = dataclasses.replace(
            fed, async_staleness_power=args.staleness_power)
    if getattr(args, "buffer_size", None) is not None:
        fed = dataclasses.replace(fed,
                                  async_buffer_size=args.buffer_size)
    if getattr(args, "cohort_size", None) is not None:
        fed = dataclasses.replace(fed, cohort_size=args.cohort_size)
    elif any(getattr(args, a, None) is not None
             for a in ("client_store", "client_store_path",
                       "cohort_sampling", "cohort_seed", "cohort_trace")):
        # Same rule as the async knobs: never silently ignore a semantic
        # flag whose engine mode is off.
        raise SystemExit("--client-store/--client-store-path/"
                         "--cohort-sampling/--cohort-seed/--cohort-trace "
                         "require --cohort-size")
    if getattr(args, "client_store", None) is not None:
        fed = dataclasses.replace(fed, client_store=args.client_store)
    if getattr(args, "client_store_path", None) is not None:
        fed = dataclasses.replace(fed,
                                  client_store_path=args.client_store_path)
    if getattr(args, "cohort_sampling", None) is not None:
        fed = dataclasses.replace(fed,
                                  cohort_sampling=args.cohort_sampling)
    if getattr(args, "cohort_seed", None) is not None:
        fed = dataclasses.replace(fed, cohort_seed=args.cohort_seed)
    if getattr(args, "cohort_trace", None) is not None:
        fed = dataclasses.replace(fed, cohort_trace=args.cohort_trace)
    run_kw = {}
    if args.checkpoint_dir is not None:
        run_kw["checkpoint_dir"] = args.checkpoint_dir
    if args.checkpoint_every is not None:
        run_kw["checkpoint_every"] = args.checkpoint_every
    if args.keep_checkpoints is not None:
        run_kw["keep_checkpoints"] = args.keep_checkpoints
    if args.eval_test_every is not None:
        run_kw["eval_test_every"] = args.eval_test_every
    if args.rounds_per_step is not None:
        run_kw["rounds_per_step"] = args.rounds_per_step
    if getattr(args, "compilation_cache", None):
        # Mirrored into RunConfig: it is what turns the ProgramCache on in
        # run_experiment / the sweep.
        run_kw["compilation_cache"] = os.path.abspath(args.compilation_cache)
    if getattr(args, "overlap_compile", False):
        run_kw["overlap_compile"] = True
    if args.profile_dir is not None:
        run_kw["profile_dir"] = args.profile_dir
    if getattr(args, "profile_rounds", None) is not None:
        run_kw["profile_rounds"] = args.profile_rounds
    if args.metrics_jsonl is not None:
        run_kw["metrics_jsonl"] = args.metrics_jsonl
    if args.log_per_client:
        run_kw["log_per_client"] = True
    if getattr(args, "pipelined_stop", False):
        run_kw["pipelined_stop"] = True
    if getattr(args, "mpmd", False):
        run_kw["mpmd"] = True
    if getattr(args, "model_parallel", None) is not None:
        run_kw["model_parallel"] = args.model_parallel
    if getattr(args, "fault_plan", None) is not None:
        run_kw["fault_plan"] = args.fault_plan
    if getattr(args, "on_divergence", None) is not None:
        run_kw["on_divergence"] = args.on_divergence
    if getattr(args, "rollback_retries", None) is not None:
        run_kw["rollback_retries"] = args.rollback_retries
    if getattr(args, "rollback_exclude", False):
        run_kw["rollback_exclude"] = True
    if getattr(args, "rollback_perturb", None) is not None:
        run_kw["rollback_perturb"] = args.rollback_perturb
    if getattr(args, "heartbeat", None) is not None:
        run_kw["heartbeat_file"] = args.heartbeat
    if getattr(args, "collective_timeout", None):
        run_kw["collective_timeout"] = args.collective_timeout
    if args.events is not None:
        run_kw["telemetry"] = dataclasses.replace(run.telemetry,
                                                  events_path=args.events)
    if run_kw:
        run = dataclasses.replace(run, **run_kw)
    return ExperimentConfig(data=data, shard=shard, model=model, optim=optim,
                            fed=fed, run=run)


def _add_serving_flags(p: argparse.ArgumentParser) -> None:
    """The shared serve/gateway flag surface: a gateway is a serve process
    plus fleet routing, so every ServingConfig knob means the same thing
    on both subcommands."""
    p.add_argument("--host", default="127.0.0.1",
                   help="bind address (default 127.0.0.1; the "
                        "protocol is a same-host ingestion socket)")
    p.add_argument("--port", type=_nonnegative_int, default=0,
                   help="TCP port (default 0 = ephemeral; pair "
                        "with --port-file)")
    p.add_argument("--port-file", default=None, metavar="FILE",
                   help="write the bound port here once listening "
                        "(ephemeral-port discovery for loadgen)")
    p.add_argument("--net-fault-plan", default=None, metavar="JSON",
                   help="seeded wire-fault schedule (path or inline "
                        "JSON, fedtpu.resilience.netfaults): fronts "
                        "this server with a deterministic fault proxy "
                        "discovered via <port-file>.net — partitions, "
                        "torn/replayed frames, resets, slow links. "
                        "Requires --port-file")
    p.add_argument("--cohort", type=_positive_int, default=8,
                   help="concurrent engine slots C; users get "
                        "stable slot bindings with LRU eviction "
                        "(default 8)")
    p.add_argument("--buffer-size", type=_nonnegative_int, default=0,
                   help="FedBuff K-buffer M: the global only moves "
                        "once M updates buffered (<=1 applies every "
                        "tick; default 0)")
    p.add_argument("--staleness-power", type=_nonnegative_float,
                   default=0.5,
                   help="delta discount (1+s)^-p (default 0.5)")
    p.add_argument("--tick-interval", type=_nonnegative_float,
                   default=0.5, metavar="S",
                   help="virtual seconds between engine ticks "
                        "(0 disables the timer; default 0.5)")
    p.add_argument("--flush-every", type=_nonnegative_int, default=0,
                   help="also fire a tick once this many eligible "
                        "updates pend (0 = timer only)")
    p.add_argument("--history-window", type=_nonnegative_int,
                   default=0, metavar="N",
                   help="keep only the newest N per-tick history "
                        "rows (0 = unbounded, the determinism "
                        "artifact; set for long-running servers)")
    p.add_argument("--rate-limit", type=_nonnegative_float,
                   default=0.0,
                   help="token-bucket admission rate in updates per "
                        "virtual second (0 = off)")
    p.add_argument("--rate-burst", type=_positive_float, default=64.0,
                   help="token-bucket burst capacity (default 64)")
    p.add_argument("--max-pending", type=_nonnegative_int, default=0,
                   help="reject_backpressure once this many admitted "
                        "updates await incorporation (0 = off)")
    p.add_argument("--stale-deprioritize", type=_nonnegative_int,
                   default=4,
                   help="versions behind at which an update is "
                        "deprioritized (default 4)")
    p.add_argument("--stale-reject", type=_nonnegative_int,
                   default=16,
                   help="versions behind at which an update is "
                        "rejected (default 16)")
    p.add_argument("--screen", action="store_true",
                   help="enable streaming update screening: non-finite "
                        "guard, norm-vs-rolling-median, and cosine "
                        "tests reject poisoned arrivals in-jit before "
                        "the K-buffer (docs/robustness.md)")
    p.add_argument("--screen-norm-mult", type=_positive_float,
                   default=4.0,
                   help="screen when an update's norm exceeds this "
                        "multiple of the rolling median of accepted "
                        "norms (default 4)")
    p.add_argument("--screen-cos-min", type=float, default=-0.2,
                   help="screen when cosine against the server "
                        "direction falls below this (in [-1, 1); "
                        "default -0.2)")
    p.add_argument("--screen-warmup", type=_positive_int, default=8,
                   help="accepted-norm samples before the norm test "
                        "arms (default 8)")
    p.add_argument("--screen-clip-norm", type=_nonnegative_float,
                   default=0.0,
                   help="also clip accepted update norms to this bound "
                        "(0 = off)")
    p.add_argument("--quarantine-strikes", type=_positive_int,
                   default=3,
                   help="screened strikes before a user id is "
                        "quarantined (default 3)")
    p.add_argument("--checkpoint-dir", default=None, metavar="DIR",
                   help="drain-time (and periodic) serving "
                        "checkpoints land here; required for "
                        "--resume")
    p.add_argument("--checkpoint-every-ticks", type=_nonnegative_int,
                   default=0,
                   help="also checkpoint every N engine ticks "
                        "(0 = drain-time only)")
    p.add_argument("--resume", action="store_true",
                   help="restore serving state (engine + pending "
                        "queue + history) from --checkpoint-dir")
    p.add_argument("--history", default=None, metavar="JSONL",
                   help="write the per-tick metric history here at "
                        "drain — the bitwise-determinism artifact")
    p.add_argument("--events", default=None, metavar="JSONL",
                   help="telemetry events sink (read back by "
                        "'fedtpu report')")
    p.add_argument("--heartbeat", default=None, metavar="FILE",
                   help="liveness heartbeat file for 'fedtpu "
                        "supervise' hang detection")
    p.add_argument("--once", action="store_true",
                   help="exit cleanly (drain + checkpoint) after "
                        "the first client connection closes — "
                        "bounded smoke runs")
    p.add_argument("--seed", type=_nonnegative_int, default=0,
                   help="engine init / synthetic-shard seed")
    p.add_argument("--platform", choices=["default", "cpu"],
                   default="default",
                   help="force the JAX platform before backend init")
    p.add_argument("--json", action="store_true",
                   help="print the drain summary as one JSON line")
    p.add_argument("--quiet", action="store_true",
                   help="suppress server status lines")


def build_parser() -> argparse.ArgumentParser:
    """The complete argument parser, exposed separately from ``main`` so
    tests can introspect the real flag surface (e.g. the docs-accuracy
    guard that every ``--flag`` the documentation mentions exists)."""
    parser = argparse.ArgumentParser(prog="fedtpu", description=__doc__)
    sub = parser.add_subparsers(dest="cmd", required=True)

    run_p = sub.add_parser("run", help="run a federated experiment")
    _add_common_overrides(run_p)
    # run-only: the FedAvg parameter-averaging reduction backend. The sweep
    # and parity programs use their own fixed psum reductions, so accepting
    # the flag there would silently ignore it.
    run_p.add_argument("--aggregation", choices=["psum", "ring", "ring-rsag"],
                       default=None,
                       help="FedAvg reduction backend (default psum; ring = "
                            "explicit ppermute ICI ring)")
    run_p.add_argument("--model-parallel", type=int, default=None,
                       help=">1 selects the 2-D ('clients','model') GSPMD "
                            "engine: hidden weights shard over a tensor-"
                            "parallel axis of this extent (MLP only)")
    # run-only: the elastic-reshard partition window (docs/resilience.md).
    # A shrunk gang trains --num-clients C as the contiguous window
    # [offset, offset+C) of a P-client partition, so its shards stay
    # bitwise identical to the pre-shrink full-width run's.
    run_p.add_argument("--partition-clients", type=int, default=None,
                       help="shard the dataset as if for this many clients "
                            "and keep only the --num-clients window "
                            "starting at --partition-offset (elastic-"
                            "reshard data layout; default: no window)")
    run_p.add_argument("--partition-offset", type=_nonnegative_int,
                       default=None,
                       help="first global client row of the partition "
                            "window (requires --partition-clients)")
    # run-only, like --aggregation: the sweep/parity programs have their
    # own init and stop semantics; accepting these there would silently
    # ignore them.
    run_p.add_argument("--init-weights", default=None, metavar="NPZ",
                       help="warm-start every client from a saved weights "
                            "artifact (the sweep's --save-weights output); "
                            "architecture must match")
    run_p.add_argument("--pipelined-stop", action="store_true",
                       help="overlap metric processing with the next "
                            "chunk's device execution; stop decisions lag "
                            "one chunk (recorded history stays identical "
                            "to the synchronous loop)")
    run_p.add_argument("--mpmd", action="store_true",
                       help="MPMD round pipelining: the round chunk as a "
                            "DAG of AOT sub-programs (client-step / "
                            "aggregate / metrics) with async dispatch and "
                            "a server-submesh metrics placement — hides "
                            "the per-round metric-fetch RTT under the "
                            "next chunk's client compute; bitwise metric "
                            "history vs the default monolithic path "
                            "(subsumes --pipelined-stop)")
    run_p.add_argument("--overlap-compile", action="store_true",
                       help="with --rounds-per-step R>1, train R=1 warmup "
                            "rounds while the R-wide chunk program compiles "
                            "on a background thread (bitwise-identical "
                            "results; composes with --compilation-cache)")
    run_p.add_argument("--resume", action="store_true",
                       help="resume from the latest checkpoint in "
                            "--checkpoint-dir")
    # run-only: asynchronous (FedBuff-style) federation. --rounds counts
    # server TICKS; composes with --local-steps/--prox-mu/--server-lr;
    # needs --weighting uniform (the arrival mean is unweighted).
    run_p.add_argument("--async", dest="async_mode", action="store_true",
                       help="asynchronous FedBuff-style federation: each "
                            "tick a Bernoulli(--arrival-rate) subset of "
                            "clients completes and ships staleness-"
                            "discounted deltas; --rounds counts ticks "
                            "(needs --weighting uniform)")
    run_p.add_argument("--arrival-rate", type=_participation_rate,
                       default=None,
                       help="async: per-tick completion probability in "
                            "(0, 1] (default 0.5)")
    run_p.add_argument("--arrival-seed", type=int, default=None,
                       help="async: seed of the deterministic arrival "
                            "process (default 0)")
    run_p.add_argument("--staleness-power", type=_nonnegative_float,
                       default=None,
                       help="async: arrival deltas are discounted "
                            "(1+staleness)^-p (default 0.5 = FedBuff's "
                            "1/sqrt; 0 disables discounting)")
    run_p.add_argument("--buffer-size", type=_nonnegative_int,
                       default=None,
                       help="async: >= 2 selects true FedBuff K-buffer "
                            "apply semantics — the global only moves once "
                            "this many updates sit in the server buffer "
                            "(default 0 = apply every arrival tick)")
    # run-only: the cohort-store engine (fedtpu.cohort; docs/scaling.md).
    # --num-clients is the POPULATION; --cohort-size is how many of them
    # exist on device per round.
    run_p.add_argument("--cohort-size", type=_positive_int, default=None,
                       help="stream rounds through a sampled cohort of "
                            "this many clients instead of materializing "
                            "all --num-clients on device; per-client "
                            "state lives in a host-side store (plain "
                            "FedAvg path only; bitwise-equal to the "
                            "default engine when equal to --num-clients)")
    run_p.add_argument("--client-store", choices=["memory", "mmap"],
                       default=None,
                       help="cohort store backend: 'memory' (sparse "
                            "calloc pages) or 'mmap' (file-backed, "
                            "survives as a plain binary; default memory)")
    run_p.add_argument("--client-store-path", default=None, metavar="BIN",
                       help="mmap store backing file (default "
                            "<checkpoint-dir>/client_store.bin)")
    run_p.add_argument("--cohort-sampling",
                       choices=["uniform", "weighted", "trace"],
                       default=None,
                       help="cohort sampling policy: uniform, weighted "
                            "(data-size-proportional), or trace (arrival "
                            "order of --cohort-trace)")
    run_p.add_argument("--cohort-seed", type=int, default=None,
                       help="cohort sampling seed (default 0; resume "
                            "replays the same cohorts)")
    run_p.add_argument("--cohort-trace", default=None, metavar="JSONL",
                       help="serving trace whose arrival order drives "
                            "--cohort-sampling trace")
    # run-only, like --aggregation: the sweep/parity programs would accept
    # but silently ignore it.
    run_p.add_argument("--personalize-steps", type=_positive_int,
                       default=None,
                       help="post-training per-client fine-tuning steps "
                            "from the final global model (personalized "
                            "metrics in the summary)")
    # run-only resilience knobs (fedtpu.resilience; docs/resilience.md).
    run_p.add_argument("--fault-plan", default=None, metavar="JSON",
                       help="deterministic fault schedule: a JSON file "
                            "path or inline JSON object (seeded; see "
                            "docs/resilience.md for the schema)")
    run_p.add_argument("--on-divergence", choices=["halt", "rollback"],
                       default=None,
                       help="non-finite guard policy: 'halt' (quarantine + "
                            "stop, the default) or 'rollback' (restore the "
                            "latest good checkpoint and retry; needs "
                            "--checkpoint-dir and --checkpoint-every)")
    run_p.add_argument("--rollback-retries", type=_nonnegative_int,
                       default=None,
                       help="rollback retry budget for the whole run "
                            "(default 2); exhausted -> halt as usual")
    run_p.add_argument("--rollback-exclude", action="store_true",
                       help="on rollback, permanently exclude the "
                            "offending client(s) from aggregation (mask "
                            "weight 0; needs --weighting data_size)")
    run_p.add_argument("--rollback-perturb", type=_nonnegative_float,
                       default=None,
                       help="relative parameter perturbation applied from "
                            "the SECOND rollback retry on (default 1e-6; "
                            "the first retry is always a pure replay)")
    run_p.add_argument("--heartbeat", default=None, metavar="FILE",
                       help="liveness heartbeat file the loop rewrites "
                            "atomically every chunk ('fedtpu supervise "
                            "--hang-timeout' watches its mtime)")
    run_p.add_argument("--collective-timeout", type=_nonnegative_float,
                       default=None, metavar="SECONDS",
                       help="multi-process watchdog: abort with exit 75 "
                            "(restartable) when a blocking collective/"
                            "fetch stalls past this many seconds — a hung "
                            "peer becomes a gang restart, never a "
                            "deadlock. Set it above EVERY guarded phase's "
                            "worst-case healthy duration: the chunk "
                            "walltime AND the collective checkpoint save, "
                            "which scales with model size (0 disables)")
    run_p.add_argument("--max-restarts", type=_positive_int, default=None,
                       help="self-supervise: run as a child process "
                            "auto-restarted with --resume up to N times on "
                            "crash/preemption (shorthand for 'fedtpu "
                            "supervise -- run ...')")

    sweep_p = sub.add_parser("sweep", help="federated hyperparameter grid")
    _add_common_overrides(sweep_p)
    sweep_p.add_argument("--no-vmap-lr", action="store_true",
                         help="run learning rates sequentially instead of "
                              "vmapped (parity-check path; ~9x slower)")
    sweep_p.add_argument("--table-jsonl", default=None,
                         help="write the full per-config result table here, "
                              "one JSON line per config (the reference only "
                              "prints the best, hyperparameters_tuning.py:126)")
    sweep_p.add_argument("--save-weights", default=None, metavar="NPZ",
                         help="persist the winning config's post-averaging "
                              "weights + hyperparameters + metrics as an "
                              ".npz (the reference only prints them, "
                              "hyperparameters_tuning.py:130-132)")
    sweep_p.add_argument("--no-vmap-arch", action="store_true",
                         help="launch one program per architecture instead "
                              "of stacking each depth class's architectures "
                              "into the vmapped axis (the default runs the "
                              "90-config grid as 2 launches; parity-check "
                              "path)")
    sweep_p.add_argument("--no-bucket-pad", action="store_true",
                         help="compile one program per architecture "
                              "instead of zero-padding each to its depth "
                              "class's max dims (the pad is exact math; "
                              "bucketing cuts the 90-config grid from 10 "
                              "compiles to 2)")
    sweep_p.add_argument("--no-overlap-compile", action="store_true",
                         help="compile each depth bucket's program eagerly "
                              "at dispatch instead of on a background "
                              "thread while the previous bucket executes "
                              "(the overlap is bitwise-identical; this is "
                              "the parity-check path)")
    sweep_p.add_argument("--plateau-stop", action="store_true",
                         help="sklearn-faithful local fits: treat the step "
                              "budget as a cap and stop each (client, lr) "
                              "fit once its loss plateaus (tol 1e-4, 10 "
                              "epochs — MLPClassifier's early stop, which "
                              "the reference's max_iter=400 grid runs "
                              "under, hyperparameters_tuning.py:90)")

    parity_p = sub.add_parser("parity",
                              help="sklearn warm-start limitation demo")
    _add_common_overrides(parity_p)

    # Offline analysis of a --events sink: no preset, no backend — the
    # report layer is numpy+stdlib only, so this works on any machine the
    # log was copied to.
    report_p = sub.add_parser("report",
                              help="aggregate a telemetry events JSONL "
                                   "(phase breakdown, round cadence, "
                                   "staleness, counters)")
    report_p.add_argument("events", nargs="+",
                          help="events JSONL path(s) written via --events; "
                               "several sinks (serve + gang + controller) "
                               "merge into one combined view plus a "
                               "per-source admission/SLO breakdown")
    report_p.add_argument("--format", choices=["text", "json"],
                          default="text",
                          help="report rendering (default text)")
    report_p.add_argument("--prometheus", default=None, metavar="PATH",
                          help="also write a Prometheus text-exposition "
                               "snapshot of the aggregated log here")
    report_p.add_argument("--heartbeat", default=None, metavar="FILE",
                          help="supervisor heartbeat base path: adds live "
                               "per-process status rows (serving/parked/"
                               "stale/missing) to the resilience section")
    report_p.add_argument("--num-processes", type=_positive_int, default=1,
                          help="gang size for --heartbeat (per-process "
                               "files <base>.p<i>; default 1)")

    # Causal fleet timeline: merges events sinks, netproxy logs and
    # autoscale decision logs into one ordered view. Like report, pure
    # reader — stdlib only, no backend, no preset.
    timeline_p = sub.add_parser(
        "timeline",
        help="merge events JSONL sinks + netproxy *.netlog + autoscale "
             "decision logs into one causal fleet timeline "
             "(deterministic JSONL or Chrome/Perfetto trace JSON)")
    timeline_p.add_argument(
        "artifacts", nargs="+",
        help="events JSONL path(s), *.netlog proxy logs, and/or "
             "autoscale decision JSONL — classified automatically")
    timeline_p.add_argument(
        "--format", choices=["jsonl", "chrome"], default="jsonl",
        help="'jsonl' = deterministic canonical lines (wall-clock-free, "
             "goldenable); 'chrome' = trace-event JSON for Perfetto / "
             "chrome://tracing (default jsonl)")
    timeline_p.add_argument(
        "--output", default=None, metavar="PATH",
        help="write the rendering here instead of stdout")
    timeline_p.add_argument(
        "--expand", action="store_true",
        help="also pick up sibling fleet artifacts derived from each "
             "events path (*.g<i>, *.p<i>, *.netlog)")

    # Static analysis: pure AST, no backend, no preset — safe in any
    # environment (CI lint gates, pre-commit).
    lint_p = sub.add_parser("lint",
                            help="JAX-aware static analysis (FTP rules; "
                                 "see docs/analysis.md)")
    lint_p.add_argument("paths", nargs="*", default=["fedtpu"],
                        help="files or directories to lint "
                             "(default: fedtpu)")
    lint_p.add_argument("--format", choices=["text", "json", "sarif"],
                        default="text",
                        help="finding rendering (default text; sarif "
                             "emits SARIF 2.1.0 for CI annotations)")
    lint_p.add_argument("--select", default=None, metavar="CODES",
                        help="comma-separated rule codes to run exclusively "
                             "(e.g. FTP005 or FTP001,FTP002)")
    lint_p.add_argument("--ignore", default=None, metavar="CODES",
                        help="comma-separated rule codes to skip")
    lint_p.add_argument("--show-suppressed", action="store_true",
                        help="also list findings silenced by "
                             "'# fedtpu: noqa[CODE]' comments")

    # Runtime guard: drives the real round step under the recompile
    # sentinel + transfer guard (the dynamic half of the lint rules).
    check_p = sub.add_parser("check",
                             help="prove the round step is retrace-free "
                                  "(recompile sentinel + transfer guard)")
    check_p.add_argument("--preset", default="income-8",
                         choices=sorted(PRESETS))
    check_p.add_argument("--rounds", type=_positive_int, default=4,
                         help="steady-state steps to drive while armed "
                              "(default 4)")
    check_p.add_argument("--transfer-guard",
                         choices=["allow", "log", "disallow"], default="log",
                         help="jax.transfer_guard level during the armed "
                              "window (default log)")
    check_p.add_argument("--debug-nans", action="store_true",
                         help="also enable jax_debug_nans for the window")
    check_p.add_argument("--synthetic-rows", type=_positive_int, default=512,
                         help="synthetic dataset size (the check probes "
                              "compilation, not accuracy)")
    check_p.add_argument("--platform", choices=["default", "cpu"],
                         default="default",
                         help="force the JAX platform before backend init")
    check_p.add_argument("--json", action="store_true",
                         help="print the check report as one JSON line")
    check_p.add_argument("--warmup-cache", default=None, metavar="DIR",
                         help="apply this persistent compilation cache "
                              "before building, so the retrace gate also "
                              "validates warm-cache startup (pair with "
                              "'fedtpu warmup --cache DIR')")
    check_p.add_argument("--audit", action="store_true",
                         help="also run the static side — the AST lint "
                              "over the package plus the jaxpr-level "
                              "program audit ('fedtpu audit') of the same "
                              "preset — folded into the exit code")
    check_p.add_argument("--mpmd", action="store_true",
                         help="also run the MPMD parity probe: the same "
                              "preset twice on small synthetic data — "
                              "monolithic oracle vs the MPMD DAG — with "
                              "the metric history and final parameters "
                              "compared bitwise, folded into the exit "
                              "code")
    check_p.add_argument("--autoscale-sim", default=None, metavar="GOLDEN",
                         help="also replay the pinned autoscale "
                              "simulation and compare its decision "
                              "sequence bitwise against this golden "
                              "JSONL, folded into the exit code")
    check_p.add_argument("--defense-sim", default=None, metavar="GOLDEN",
                         help="also replay the pinned poisoning-defense "
                              "simulation (screening engine over a seeded "
                              "adversarial trace) and compare its decision "
                              "log bitwise against this golden JSONL, "
                              "folded into the exit code")
    check_p.add_argument("--net-sim", default=None, metavar="GOLDEN",
                         help="also replay the pinned wire-fault "
                              "campaign (NetFaultPlan through the real "
                              "engine/session machinery) and compare "
                              "its decision log bitwise against this "
                              "golden JSONL, folded into the exit code")
    check_p.add_argument("--timeline-sim", default=None, metavar="GOLDEN",
                         help="also replay the pinned two-gateway causal "
                              "trace campaign (stamped frames + a "
                              "deliberate retry through the real "
                              "engine/session machinery) and compare the "
                              "merged deterministic timeline bitwise "
                              "against this golden JSONL, folded into "
                              "the exit code")
    check_p.add_argument("--gateway-probe", default=None,
                         metavar="PORT_FILE_BASE",
                         help="also probe a live gateway fleet's health "
                              "over its port-file base (each member "
                              "answers a stats round-trip), folded into "
                              "the exit code")
    check_p.add_argument("--gateway-count", type=_positive_int, default=1,
                         help="fleet size for --gateway-probe (default 1)")
    check_p.add_argument("--lockdep", action="store_true",
                         help="also run the lock-order sanitizer drills "
                              "(netproxy relay, overlap-compile, "
                              "prefetch/writeback, watchdog arm/disarm) "
                              "and compare the acquisition-order graph "
                              "bitwise against the committed golden, "
                              "folded into the exit code")
    check_p.add_argument("--lockdep-golden", default=None, metavar="GOLDEN",
                         help="golden lock graph for --lockdep (default: "
                              "tests/goldens/lockdep.json)")
    check_p.add_argument("--fuzz-corpus", default=None, metavar="DIR",
                         nargs="?", const="tests/corpus",
                         help="also replay every committed fuzz campaign "
                              "under DIR (default tests/corpus): digest "
                              "must match the entries, every oracle must "
                              "pass, two same-seed runs must be bitwise, "
                              "and the verdict artifact must match its "
                              "committed golden — folded into the exit "
                              "code")

    # IR-level program audit: trace the real engines, extract and verify
    # the collective schedule, prove donation, account comm bytes
    # (docs/analysis.md "Program audit").
    audit_p = sub.add_parser("audit",
                             help="jaxpr-level SPMD program audit: "
                                  "collective schedule, donation proof, "
                                  "comm-byte contract")
    audit_p.add_argument("preset", nargs="?", default="income-8",
                         choices=sorted(PRESETS))
    audit_p.add_argument("--format", choices=["text", "json"],
                         default="text",
                         help="contract rendering (default text)")
    audit_p.add_argument("--engines", default=None, metavar="E[,E...]",
                         help="comma-separated engines to audit "
                              "(sync,async,tp,cohort; default all)")
    audit_p.add_argument("--synthetic-rows", type=_positive_int, default=512,
                         help="synthetic dataset size (the audit traces "
                              "programs, it never steps them)")
    audit_p.add_argument("--platform", choices=["default", "cpu"],
                         default="default",
                         help="force the JAX platform before backend init")
    audit_p.add_argument("--host-devices", type=_positive_int, default=None,
                         metavar="N",
                         help="force N virtual host CPU devices (XLA flag; "
                              "applied before backend init — required for "
                              "the tp engine on single-device hosts)")
    audit_p.add_argument("--golden", default=None, metavar="PATH",
                         help="diff the live contract against this golden "
                              "JSON; any mismatch fails the audit")
    audit_p.add_argument("--write-golden", default=None, metavar="PATH",
                         help="write the JSON contract to PATH "
                              "(golden (re)generation)")

    # AOT pre-compilation: populate a persistent cache with a preset's
    # program family so later runs/sweeps start warm (docs/ARCHITECTURE.md).
    warmup_p = sub.add_parser("warmup",
                              help="pre-compile a preset's program family "
                                   "into a persistent cache dir")
    warmup_p.add_argument("--preset", default="income-8",
                          choices=sorted(PRESETS))
    warmup_p.add_argument("--cache", required=True, metavar="DIR",
                          help="cache directory (created if missing); "
                              "holds the XLA backend cache plus serialized "
                              "executables under programs/")
    warmup_p.add_argument("--widths", default=None, metavar="R[,R...]",
                          help="comma-separated chunk widths "
                               "(rounds-per-step values) to pre-compile; "
                               "default: 1 plus the preset's "
                               "rounds_per_step")
    warmup_p.add_argument("--synthetic-rows", type=_positive_int,
                          default=None,
                          help="force a synthetic dataset of this many rows "
                               "(warmup probes compilation, not accuracy; "
                               "default: the preset's own data)")
    warmup_p.add_argument("--no-eval", action="store_true",
                          help="skip pre-compiling the eval program")
    warmup_p.add_argument("--events", default=None, metavar="JSONL",
                          help="write compile spans to this telemetry "
                               "events sink")
    warmup_p.add_argument("--platform", choices=["default", "cpu"],
                          default="default",
                          help="force the JAX platform before backend init")
    warmup_p.add_argument("--json", action="store_true",
                          help="print the warmup report as one JSON line")
    warmup_p.add_argument("--quiet", action="store_true",
                          help="suppress per-program progress lines")

    # Process supervision: restart-on-crash with --resume. The parent
    # never imports jax — it only forks children — so it stays alive
    # through backend crashes that would take a same-process retry down.
    sup_p = sub.add_parser("supervise",
                           help="run a fedtpu command as a supervised "
                                "child: auto-restart with --resume on "
                                "crash/preemption (docs/resilience.md)")
    sup_p.add_argument("--num-processes", type=_positive_int, default=1,
                       help="launch the child as an SPMD gang of N "
                            "processes wired together via jax.distributed "
                            "(all-or-nothing restarts: any member's "
                            "crash/hang/preemption restarts the whole "
                            "gang; default 1 = single child)")
    sup_p.add_argument("--max-restarts", type=_nonnegative_int, default=2,
                       help="restart budget (default 2); divergence "
                            "(exit 3) is never restarted")
    sup_p.add_argument("--backoff", type=_nonnegative_float, default=1.0,
                       help="crash-restart backoff base in seconds, "
                            "doubled per restart (default 1.0; preemption "
                            "restarts — exit 75 — skip backoff)")
    sup_p.add_argument("--backoff-max", type=_nonnegative_float,
                       default=30.0,
                       help="backoff ceiling in seconds (default 30)")
    sup_p.add_argument("--grace", type=_nonnegative_float, default=15.0,
                       help="seconds a SIGTERM'd child gets to drain its "
                            "checkpoint before SIGKILL (default 15)")
    sup_p.add_argument("--healthy-window", type=_nonnegative_float,
                       default=300.0,
                       help="a child/gang that stays up this many seconds "
                            "is considered healthy again: the crash "
                            "streak driving exponential backoff resets "
                            "(default 300; 0 never resets)")
    sup_p.add_argument("--hang-timeout", type=_nonnegative_float,
                       default=None,
                       help="SIGKILL + restart the child when its "
                            "--heartbeat file goes stale for this many "
                            "seconds (default: no hang detection)")
    sup_p.add_argument("--heartbeat", default=None, metavar="FILE",
                       help="heartbeat file (auto-appended to 'run' "
                            "children; required for --hang-timeout)")
    sup_p.add_argument("--events", default=None, metavar="JSONL",
                       help="append supervisor events (child_start/"
                            "child_exit/restart) to this sink — point it "
                            "at the child's --events file for one merged "
                            "timeline")
    sup_p.add_argument("--quiet", action="store_true",
                       help="suppress supervisor status lines")
    sup_p.add_argument("child", nargs=argparse.REMAINDER,
                       help="the supervised fedtpu command, after '--': "
                            "e.g. fedtpu supervise -- run --rounds 100 "
                            "--checkpoint-dir d --checkpoint-every 10")

    # Chaos drill: execute the fault scenario matrix end-to-end and
    # report per-scenario survival/recovery. Children are subprocesses;
    # the parent stays jax-free like `supervise`.
    chaos_p = sub.add_parser("chaos",
                             help="execute the resilience scenario matrix "
                                  "(kill/preempt/NaN/dropout/straggler) "
                                  "and report per-scenario recovery")
    from fedtpu.resilience.chaos import scenarios_help
    chaos_p.add_argument("--scenarios", default=None, metavar="A,B",
                         help=scenarios_help())
    chaos_p.add_argument("--rounds", type=_positive_int, default=10,
                         help="rounds per scenario run (default 10)")
    chaos_p.add_argument("--num-clients", type=_positive_int, default=4,
                         help="synthetic clients per run (default 4)")
    chaos_p.add_argument("--workdir", default=None, metavar="DIR",
                         help="scenario artifact directory (default: a "
                              "temp dir, removed unless --keep-artifacts)")
    chaos_p.add_argument("--keep-artifacts", action="store_true",
                         help="keep per-scenario checkpoints/metrics/"
                              "events for inspection")
    chaos_p.add_argument("--timeout", type=_positive_int, default=600,
                         help="per-child-run timeout in seconds "
                              "(default 600)")
    chaos_p.add_argument("--platform", choices=["default", "cpu"],
                         default="cpu",
                         help="platform for the child runs (default cpu: "
                              "the matrix is a correctness drill, not a "
                              "perf run)")
    chaos_p.add_argument("--json", action="store_true",
                         help="print the matrix report as one JSON line")
    chaos_p.add_argument("--quiet", action="store_true",
                         help="suppress per-scenario progress lines")

    # Compositional chaos fuzzing: seeded multi-fault campaigns against
    # the deterministic in-process gang, judged by the oracle library,
    # failures ddmin-shrunk to committed reproducers
    # (fedtpu.resilience.fuzz; docs/resilience.md).
    fuzz_p = sub.add_parser("fuzz",
                            help="sample seeded COMPOSED fault campaigns "
                                 "(process + wire + lifecycle + poison) "
                                 "and replay each against a deterministic "
                                 "two-gateway gang, judged by the "
                                 "invariant-oracle library; failing "
                                 "campaigns are delta-debugged to minimal "
                                 "reproducers (docs/resilience.md)")
    fuzz_p.add_argument("--budget", type=_positive_int, default=25,
                        help="campaigns to sample and replay (default 25)")
    fuzz_p.add_argument("--seed", type=int, default=0,
                        help="campaign-generator seed (default 0): the "
                             "run is a pure function of (seed, budget)")
    fuzz_p.add_argument("--rounds", type=_positive_int, default=8,
                        help="virtual rounds per campaign (default 8)")
    fuzz_p.add_argument("--campaign", default=None, metavar="SPEC",
                        help="replay ONE campaign instead of sampling: a "
                             "manifest path or inline JSON (digest "
                             "verified when present)")
    fuzz_p.add_argument("--shrink-to", default=None, metavar="DIR",
                        help="write each failing campaign's ddmin-minimal "
                             "reproducer + bitwise verdict golden under "
                             "DIR (the tests/corpus layout)")
    fuzz_p.add_argument("--no-shrink", action="store_true",
                        help="report failures without delta-debugging "
                             "them")
    fuzz_p.add_argument("--events", default=None, metavar="PATH",
                        help="append one fuzz_campaign event per campaign "
                             "(plus the fuzz_run summary) to this JSONL "
                             "for 'fedtpu report'")
    fuzz_p.add_argument("--json", action="store_true",
                        help="print the fuzz report as one JSON line")

    # Serving front-end: a long-running ingestion process feeding the
    # async FedBuff engine from real (traced) arrivals instead of the
    # in-graph synthetic draw (fedtpu.serving; docs/serving.md).
    serve_p = sub.add_parser("serve",
                             help="trace-driven FL serving front-end: "
                                  "accept streamed client updates over a "
                                  "localhost socket, admission-control "
                                  "them, and drive async FedBuff ticks "
                                  "(docs/serving.md)")
    _add_serving_flags(serve_p)

    # Gateway fleet: N serve-shaped processes, each owning the id-shard
    # of clients matching its store shard, with redirect routing and the
    # flush/adopt shard-failover ops (fedtpu.serving.gateway;
    # docs/serving.md). Launch N under `fedtpu supervise --num-processes
    # N -- gateway ...` — every shared path below is a BASE each member
    # derives its own file/subdir from.
    gateway_p = sub.add_parser("gateway",
                               help="one member of a fault-tolerant "
                                    "multi-gateway ingestion fleet: serve "
                                    "plus id-shard routing, redirects, "
                                    "and store-shard failover "
                                    "(docs/serving.md)")
    _add_serving_flags(gateway_p)
    gateway_p.add_argument("--num-gateways", type=_positive_int, default=1,
                           help="fleet size N; this process owns users "
                                "with id %% N == its index (default 1)")
    gateway_p.add_argument("--gateway-index", type=_nonnegative_int,
                           default=None,
                           help="this member's index (default: the gang's "
                                "FEDTPU_PROCESS_ID, so a supervised fleet "
                                "needs no per-member flags)")
    gateway_p.add_argument("--total-users", type=_nonnegative_int,
                           default=0,
                           help="attach a per-user state store over this "
                                "population, sharded to the fleet "
                                "(0 = no store; required for adopt)")
    gateway_p.add_argument("--store", choices=["memory", "mmap"],
                           default="memory",
                           help="store backend (default memory)")
    gateway_p.add_argument("--store-path", default=None, metavar="FILE",
                           help="mmap backing file base path (each member "
                                "appends .g<i>)")

    # Load generation: replay (or synthesize) an arrival trace against a
    # running server. jax-free — it can run from any machine beside the
    # server process.
    load_p = sub.add_parser("loadgen",
                            help="replay a heavy-tailed arrival trace "
                                 "against a running 'fedtpu serve' "
                                 "(docs/serving.md)")
    load_p.add_argument("trace", help="arrival-trace JSONL path "
                                      "(fedtpu.serving.traces schema "
                                      "v1/v2)")
    load_p.add_argument("--synthesize", action="store_true",
                        help="first write a fresh synthetic trace to the "
                             "given path (--users/--arrivals/--horizon/"
                             "--trace-seed), then replay it")
    load_p.add_argument("--users", type=_positive_int, default=1000000,
                        help="simulated user population for --synthesize "
                             "(default 1e6)")
    load_p.add_argument("--arrivals", type=_positive_int, default=100000,
                        help="arrival events for --synthesize "
                             "(default 1e5)")
    load_p.add_argument("--horizon", type=_positive_float, default=60.0,
                        help="virtual-time horizon in seconds for "
                             "--synthesize (default 60)")
    load_p.add_argument("--trace-seed", type=_nonnegative_int, default=0,
                        help="synthesizer seed (default 0)")
    load_p.add_argument("--poison-frac", type=_nonnegative_float,
                        default=0.0,
                        help="for --synthesize: fraction of users that "
                             "are seeded attackers (schema v2 adversarial "
                             "trace; 0 = honest v1 trace, the default)")
    load_p.add_argument("--poison-scale", type=_positive_float,
                        default=10.0,
                        help="sign-flip amplification the attackers "
                             "submit (default 10)")
    load_p.add_argument("--host", default="127.0.0.1")
    load_p.add_argument("--port", type=_nonnegative_int, default=None,
                        help="server port (or use --port-file)")
    load_p.add_argument("--port-file", default=None, metavar="FILE",
                        help="poll this file (written by serve "
                             "--port-file) for the port")
    load_p.add_argument("--batch", type=_positive_int, default=1024,
                        help="arrivals per protocol frame (default 1024)")
    load_p.add_argument("--num-gateways", type=_positive_int, default=1,
                        help="route through a gateway fleet of this size: "
                             "events partition by user id %% N, wrong-"
                             "gateway redirects are followed (default 1)")
    load_p.add_argument("--retries", type=_nonnegative_int, default=8,
                        help="per-frame retry attempts against a dying/"
                             "restarting gateway before giving up "
                             "(default 8)")
    load_p.add_argument("--retry-backoff", type=_positive_float,
                        default=0.05,
                        help="base of the capped exponential retry "
                             "backoff in seconds (default 0.05)")
    load_p.add_argument("--max-events", type=_nonnegative_int, default=0,
                        help="truncate the replay after this many events "
                             "(0 = whole trace)")
    load_p.add_argument("--no-drain", action="store_true",
                        help="skip the final drain+stats round-trip")
    load_p.add_argument("--timeout", type=_positive_float, default=120.0,
                        help="socket/port-file timeout in seconds")
    load_p.add_argument("--json", action="store_true",
                        help="print the replay summary as one JSON line")
    load_p.add_argument("--quiet", action="store_true",
                        help="suppress the human-readable summary")

    # SLO-driven autoscaling control plane (fedtpu.autoscale;
    # docs/autoscale.md). jax-free: signals come over the serve socket +
    # heartbeat files, actions go out as protocol ops and signals.
    auto_p = sub.add_parser("autoscale",
                            help="SLO-driven autoscaling control plane: "
                                 "fold live signals into decisions and "
                                 "act through the reshard/serving knobs "
                                 "(docs/autoscale.md)")
    auto_p.add_argument("--simulate", action="store_true",
                        help="replay a seeded bursty trace against the "
                             "policy in pure virtual time instead of "
                             "attaching to a live deployment; the decision "
                             "sequence is a bitwise-comparable artifact")
    auto_p.add_argument("--trace", default=None, metavar="JSONL",
                        help="simulate against this arrival trace instead "
                             "of the pinned synthetic one (the pinned one "
                             "is the golden contract)")
    auto_p.add_argument("--golden", default=None, metavar="PATH",
                        help="compare the simulated decision sequence "
                             "bitwise against this golden JSONL; any "
                             "divergence fails the command")
    auto_p.add_argument("--out", default=None, metavar="PATH",
                        help="write the decision sequence JSONL here "
                             "(golden (re)generation)")
    auto_p.add_argument("--policy", default="threshold",
                        help="policy name from the registry "
                             "(default threshold)")
    auto_p.add_argument("--objective", type=_positive_float, default=None,
                        metavar="S",
                        help="SLO objective on update-to-incorporation "
                             "latency in virtual seconds (default 1.0)")
    auto_p.add_argument("--error-budget", type=_positive_float,
                        default=None,
                        help="share of updates allowed past the objective "
                             "(burn 1.0 = budget exactly consumed; "
                             "default 0.1)")
    auto_p.add_argument("--interval", type=_positive_float, default=None,
                        metavar="S",
                        help="control-loop interval (default 0.5; live "
                             "mode polls at this wall-clock cadence, "
                             "simulation ticks this much virtual time)")
    auto_p.add_argument("--host", default="127.0.0.1",
                        help="live: serve host (default 127.0.0.1)")
    auto_p.add_argument("--port", type=_nonnegative_int, default=0,
                        help="live: serve port (or use --port-file; "
                             "0 = no serving signals/actions)")
    auto_p.add_argument("--port-file", default=None, metavar="FILE",
                        help="live: poll this file (written by serve "
                             "--port-file) for the port")
    auto_p.add_argument("--heartbeat", default=None, metavar="FILE",
                        help="live: gang heartbeat base path (per-process "
                             "files <base>.p<i>) for membership signals")
    auto_p.add_argument("--num-processes", type=_positive_int, default=1,
                        help="live: gang size behind --heartbeat")
    auto_p.add_argument("--supervisor-pid", type=_nonnegative_int,
                        default=0, metavar="PID",
                        help="live: 'fedtpu supervise' parent to signal "
                             "for grow/shrink (SIGUSR2/SIGUSR1; 0 = no "
                             "gang actions)")
    auto_p.add_argument("--notice-file", default=None, metavar="FILE",
                        help="live: poll this JSON file ({\"victim\": p}) "
                             "for preemption notices; each payload is "
                             "acted on once (pre-drain + shrink)")
    auto_p.add_argument("--spool-path", default=None, metavar="FILE",
                        help="live: where the server spools pending "
                             "updates on pre-drain (default: its "
                             "checkpoint dir)")
    auto_p.add_argument("--duration", type=_nonnegative_float, default=0.0,
                        metavar="S",
                        help="live: stop after this many wall seconds "
                             "(0 = until interrupted)")
    auto_p.add_argument("--stop-after-notice", action="store_true",
                        help="live: exit once a preemption notice has "
                             "been acted on (chaos drill mode)")
    auto_p.add_argument("--events", default=None, metavar="JSONL",
                        help="telemetry events sink (decision/act events; "
                             "read back by 'fedtpu report')")
    auto_p.add_argument("--json", action="store_true",
                        help="print the summary as one JSON line")
    auto_p.add_argument("--quiet", action="store_true",
                        help="suppress status lines")

    sub.add_parser("presets", help="list shipped presets")
    return parser


def _strip_flag(argv, flag):
    """argv minus ``flag`` (both ``--f V`` and ``--f=V`` spellings)."""
    out, skip = [], False
    for tok in argv:
        if skip:
            skip = False
            continue
        if tok == flag:
            skip = True
            continue
        if tok.startswith(flag + "="):
            continue
        out.append(tok)
    return out


def main(argv=None) -> int:
    # The raw argv is kept so `run --max-restarts N` can re-issue THIS
    # exact invocation as a supervised child (with the flag stripped).
    raw_argv = list(argv) if argv is not None else sys.argv[1:]
    args = build_parser().parse_args(raw_argv)

    if args.cmd == "presets":
        for name, preset in sorted(PRESETS.items()):
            print(f"{name}: clients={preset.shard.num_clients} "
                  f"model={preset.model.kind}{list(preset.model.hidden_sizes)} "
                  f"rounds={preset.fed.rounds} weighting={preset.fed.weighting}")
        return 0

    if args.cmd == "lint":
        # Before any backend/preset touch: the linter is pure AST and must
        # work in environments with no jax installed at all.
        from fedtpu.analysis.engine import lint_paths
        from fedtpu.analysis.reporters import (render_json, render_sarif,
                                               render_text)
        select = ([c.strip() for c in args.select.split(",") if c.strip()]
                  if args.select else None)
        ignore = ([c.strip() for c in args.ignore.split(",") if c.strip()]
                  if args.ignore else None)
        try:
            result = lint_paths(args.paths, select=select, ignore=ignore)
        except ValueError as exc:      # unknown rule code
            raise SystemExit(f"fedtpu lint: {exc}")
        if args.format == "json":
            print(render_json(result))
        elif args.format == "sarif":
            print(render_sarif(result))
        else:
            print(render_text(result,
                              show_suppressed=args.show_suppressed))
        return 0 if result.clean else 1

    if args.cmd == "report":
        # Before _apply_overrides: the report parser carries no --preset
        # (and must not — it reads a log, not a config).
        from fedtpu.telemetry.report import render_report
        rendered, prom = render_report(args.events, fmt=args.format,
                                       heartbeat=args.heartbeat,
                                       process_count=args.num_processes)
        print(rendered)
        if args.prometheus:
            with open(args.prometheus, "w") as f:
                f.write(prom)
        return 0

    if args.cmd == "timeline":
        # Pure reader like report: no preset, no backend.
        from fedtpu.telemetry.timeline import (default_artifacts,
                                               render_timeline)
        paths = []
        for p in args.artifacts:
            expanded = (default_artifacts(p) if args.expand
                        and not p.endswith(".netlog") else [p])
            for q in expanded:
                if q not in paths:
                    paths.append(q)
        rendered = render_timeline(paths, fmt=args.format)
        if args.output:
            with open(args.output, "w") as f:
                f.write(rendered + "\n")
        else:
            print(rendered)
        return 0

    if args.cmd == "supervise":
        # Before the platform pin: the supervisor parent never imports
        # jax — it only forks children, so it survives backend crashes.
        from fedtpu.resilience.supervisor import supervise, supervise_gang
        child = list(args.child)
        if child and child[0] == "--":
            child = child[1:]
        if not child:
            raise SystemExit(
                "fedtpu supervise: give the child command after '--', "
                "e.g. fedtpu supervise -- run --rounds 100 "
                "--checkpoint-dir d --checkpoint-every 10")
        if args.num_processes > 1:
            return supervise_gang(child, num_processes=args.num_processes,
                                  max_restarts=args.max_restarts,
                                  backoff_base=args.backoff,
                                  backoff_max=args.backoff_max,
                                  grace=args.grace,
                                  hang_timeout=args.hang_timeout,
                                  heartbeat=args.heartbeat,
                                  events=args.events,
                                  healthy_window=args.healthy_window,
                                  verbose=not args.quiet)
        return supervise(child, max_restarts=args.max_restarts,
                         backoff_base=args.backoff,
                         backoff_max=args.backoff_max,
                         grace=args.grace, hang_timeout=args.hang_timeout,
                         heartbeat=args.heartbeat, events=args.events,
                         healthy_window=args.healthy_window,
                         verbose=not args.quiet)

    if args.cmd == "chaos":
        # Also jax-free in the parent: every scenario run is a child
        # process (its --platform applies to the children, not us).
        from fedtpu.resilience.chaos import run_chaos
        scenarios = ([s.strip() for s in args.scenarios.split(",")
                      if s.strip()] if args.scenarios else None)
        report = run_chaos(scenarios=scenarios, rounds=args.rounds,
                           num_clients=args.num_clients,
                           workdir=args.workdir,
                           keep_artifacts=args.keep_artifacts,
                           timeout=args.timeout, platform=args.platform,
                           verbose=not args.quiet)
        if args.json:
            print(json.dumps(report, default=float))
        return 0 if report["ok"] else 1

    if args.cmd == "fuzz":
        from fedtpu.config import FuzzConfig
        from fedtpu.resilience.fuzz import (Campaign, emit_event,
                                            run_campaign, run_fuzz)
        fcfg = FuzzConfig(budget=args.budget, seed=args.seed,
                          rounds=args.rounds, shrink=not args.no_shrink)
        if args.campaign:
            c = Campaign.load(args.campaign)
            res = run_campaign(c, cfg=fcfg)
            if args.events:
                emit_event(args.events, "fuzz_campaign",
                           {"name": c.name, "digest": c.digest,
                            "ok": res["ok"], "failed": res["failed"],
                            "fired": res["summary"]["fired"]})
            if args.json:
                print(json.dumps({"ok": res["ok"], "failed": res["failed"],
                                  "verdicts": res["verdicts"],
                                  "summary": res["summary"]},
                                 default=float))
            else:
                s = res["summary"]
                print(f"campaign {s['digest']}: "
                      f"{'OK' if res['ok'] else 'VIOLATION'} "
                      f"({len(res['verdicts'])} oracles"
                      + (f"; failed {res['failed']}" if res["failed"]
                         else "") + ")")
                print(f"  admitted {s['client_admitted']}, incorporated "
                      f"{s['incorporated']}, screened {s['screened']}, "
                      f"lost_acked {s['lost_acked']}, retried "
                      f"{s['retried']}, restarts {s['restarts']}")
            return 0 if res["ok"] else 1
        report = run_fuzz(budget=args.budget, seed=args.seed, cfg=fcfg,
                          out_dir=args.shrink_to, events=args.events,
                          shrink=not args.no_shrink)
        if args.json:
            print(json.dumps(report, default=float))
        else:
            print(f"fuzz seed {report['seed']}: {report['passed']}/"
                  f"{report['campaigns']} campaigns passed all oracles")
            for r in report["rows"]:
                if not r["ok"]:
                    tail = (f" -> minimized to {r['shrunk_entries']} "
                            f"entries in {r['shrink_runs']} runs"
                            if "minimized" in r else "")
                    print(f"  VIOLATION {r['name']} ({r['digest']}): "
                          f"{r.get('failed')}{tail}")
                    if "reproducer" in r:
                        print(f"    reproducer: {r['reproducer']}")
        return 0 if report["ok"] else 1

    if args.cmd == "loadgen":
        # Before the platform pin: the loadgen never imports jax — it can
        # hammer a server from a machine with no backend at all.
        from fedtpu.serving.loadgen import run_loadgen
        from fedtpu.serving.traces import synthesize_trace, write_trace
        if args.synthesize:
            header, t, user, lat = synthesize_trace(
                users=args.users, arrivals=args.arrivals,
                horizon_s=args.horizon, seed=args.trace_seed,
                poison_frac=args.poison_frac,
                poison_scale=args.poison_scale)
            write_trace(args.trace, header, t, user, lat)
            if not args.quiet:
                tag = (f" ({args.poison_frac:.0%} poisoned, scale "
                       f"{args.poison_scale:g})" if args.poison_frac > 0
                       else "")
                print(f"synthesized {args.arrivals} arrivals / "
                      f"{args.users} users over {args.horizon}s"
                      f"{tag} -> {args.trace}")
        summary = run_loadgen(args.trace, host=args.host, port=args.port,
                              port_file=args.port_file, batch=args.batch,
                              max_events=args.max_events,
                              drain=not args.no_drain,
                              timeout=args.timeout,
                              num_gateways=args.num_gateways,
                              retries=args.retries,
                              backoff_s=args.retry_backoff)
        if args.json:
            print(json.dumps(summary, default=float))
        elif not args.quiet:
            print(f"replayed {summary['events_sent']} events in "
                  f"{summary['frames']} frames "
                  f"({summary['events_per_sec']:.0f} ev/s); "
                  f"admission: {summary['admission']}")
            if summary.get("retried") or summary.get("redirected"):
                print(f"delivery: attempted {summary['attempted']}, "
                      f"retried {summary['retried']}, redirected "
                      f"{summary['redirected']}, reconnects "
                      f"{summary['reconnects']}")
        return 0

    if args.cmd == "autoscale":
        # Before the platform pin: the control plane is jax-free — it
        # reads signals over the serve socket / heartbeat files and acts
        # through protocol ops and process signals, never a backend.
        import dataclasses as _dc

        from fedtpu.autoscale.controller import (LiveController,
                                                 compare_decisions, simulate,
                                                 write_decisions)
        from fedtpu.config import AutoscaleConfig
        from fedtpu.telemetry import make_tracer
        acfg = AutoscaleConfig(policy=args.policy)
        over = {}
        if args.objective is not None:
            over["objective_s"] = args.objective
        if args.error_budget is not None:
            over["error_budget"] = args.error_budget
        if args.interval is not None:
            over["control_interval_s"] = args.interval
        if over:
            acfg = _dc.replace(acfg, **over)
        tracer = make_tracer(args.events)
        try:
            if args.simulate:
                result = simulate(acfg, trace_path=args.trace,
                                  tracer=tracer)
                if args.out:
                    write_decisions(args.out, result["lines"])
                ok = True
                if args.golden:
                    cmp = compare_decisions(result["lines"], args.golden)
                    ok = cmp["ok"]
                if args.json:
                    print(json.dumps({**result["summary"],
                                      "ok": ok}, default=float))
                elif not args.quiet:
                    s = result["summary"]
                    print(f"simulated {s['control_ticks']} control "
                          f"tick(s) over {s['arrivals']} arrival(s): "
                          f"admitted {s['admitted']}, incorporated "
                          f"{s['incorporated']}, spooled {s['spooled']}, "
                          f"capacity {s['capacity_end']}, decisions "
                          f"{s['decisions']}")
                    if args.out:
                        print(f"decisions -> {args.out}")
                    if args.golden:
                        if ok:
                            print(f"golden: matches {args.golden}")
                        else:
                            print(f"golden: {cmp['reason']} "
                                  f"vs {args.golden}")
                return 0 if ok else 1
            port = args.port
            if args.port_file:
                from fedtpu.serving.loadgen import read_port_file
                port = read_port_file(args.port_file)
            ctl = LiveController(
                acfg, host=args.host, port=port,
                supervisor_pid=args.supervisor_pid,
                heartbeat=args.heartbeat,
                process_count=args.num_processes,
                notice_file=args.notice_file,
                spool_path=args.spool_path, tracer=tracer)
            summary = ctl.run(duration_s=args.duration,
                              interval_s=args.interval,
                              stop_after_notice=args.stop_after_notice)
            if args.json:
                print(json.dumps(summary, default=float))
            elif not args.quiet:
                print(f"autoscale: {summary['control_ticks']} control "
                      f"tick(s) in {summary['wall_s']:.1f} s wall; "
                      f"acted {summary['acted']}")
            return 0
        finally:
            tracer.close()

    if args.cmd == "run" and getattr(args, "max_restarts", None):
        # Self-supervision shorthand: re-issue this exact run as a
        # supervised child. Stripping the flag is what stops the child
        # from recursing into another supervisor.
        from fedtpu.resilience.supervisor import supervise
        child = _strip_flag(raw_argv, "--max-restarts")
        return supervise(child, max_restarts=args.max_restarts,
                         heartbeat=args.heartbeat, events=args.events,
                         verbose=not args.quiet)

    if getattr(args, "host_devices", None):
        # Before ANY backend touch: XLA only reads this flag at backend
        # init, so it must land in the environment first.
        flags = os.environ.get("XLA_FLAGS", "")
        os.environ["XLA_FLAGS"] = (
            (flags + " " if flags else "")
            + f"--xla_force_host_platform_device_count={args.host_devices}")

    if getattr(args, "platform", "default") == "cpu":
        # Before ANY backend touch (including the compilation-cache config
        # below, which imports jax): pin the CPU platform for the whole
        # process. Mirrors tests/conftest.py's hermetic pin.
        import jax
        jax.config.update("jax_platforms", "cpu")

    # Gang child? supervise_gang sets FEDTPU_COORDINATOR & friends per
    # child; wire into the shared jax.distributed runtime BEFORE any
    # other backend touch (the compilation-cache config below counts).
    # Gateways are the exception: each fleet member runs its OWN
    # single-process engine — the gang contract is supervision/restart
    # only, never one SPMD runtime spanning the fleet.
    if args.cmd != "gateway":
        from fedtpu.parallel.multihost import initialize_from_env
        initialize_from_env()

    # Before any compile: every subcommand's first jit lands in (or is
    # served from) the one on-disk cache across CLI invocations
    # (JAX_COMPILATION_CACHE_DIR, else --compilation-cache, else the fixed
    # in-checkout directory; warmup/check place their own --cache /
    # --warmup-cache through the same function before they compile).
    from fedtpu.compilation import configure_persistent_cache
    configure_persistent_cache(getattr(args, "compilation_cache", None))

    if args.cmd == "warmup":
        # Before _apply_overrides: warmup carries only its own flag set
        # (the preset's config IS the program being pre-compiled).
        from fedtpu.compilation import warmup_preset
        from fedtpu.telemetry import make_tracer
        widths = ([int(w) for w in args.widths.split(",") if w.strip()]
                  if args.widths else None)
        tracer = make_tracer(args.events)
        try:
            report = warmup_preset(preset=args.preset, cache_dir=args.cache,
                                   widths=widths,
                                   synthetic_rows=args.synthetic_rows,
                                   include_eval=not args.no_eval,
                                   tracer=tracer)
        finally:
            tracer.close()
        if args.json:
            print(json.dumps(report))
        elif not args.quiet:
            for prog in report["programs"]:
                state = "warm" if prog["warm"] else "cold"
                print(f"{prog['label']}: {state} {prog['seconds']:.3f}s "
                      f"key={prog['key']}")
            print(f"cache: {report['dir']} entries={report['entries']} "
                  f"hits={report['hits']} misses={report['misses']} "
                  f"total={report['total_s']:.3f}s")
        return 0

    if args.cmd == "check":
        # Before _apply_overrides: check carries only its own small flag
        # set (it probes compilation behavior, not experiment config).
        from fedtpu.analysis.check import run_check
        report = run_check(preset=args.preset, rounds=args.rounds,
                           transfer=args.transfer_guard,
                           nans=args.debug_nans,
                           synthetic_rows=args.synthetic_rows,
                           warmup_cache=args.warmup_cache)
        if args.audit:
            # --audit = the full static side alongside the runtime probe:
            # the AST lint over the package plus the IR-level program
            # audit of the same preset, all folded into one exit code.
            from fedtpu.analysis.engine import lint_paths
            from fedtpu.analysis.program import audit_preset
            pkg_dir = os.path.dirname(os.path.abspath(__file__))
            lint_res = lint_paths([pkg_dir])
            report["lint"] = {"clean": not lint_res.findings,
                             "findings": len(lint_res.findings)}
            audit = audit_preset(args.preset,
                                 synthetic_rows=args.synthetic_rows)
            report["audit"] = {
                "ok": audit["ok"],
                "findings": audit["findings"],
                "digests": {
                    name: c.get("schedule_digest")
                    for name, c in audit["engines"].items()},
            }
            report["ok"] = (report["ok"] and audit["ok"]
                            and report["lint"]["clean"])
        if args.mpmd:
            # Fold the MPMD parity probe into the check: the DAG of AOT
            # sub-programs must reproduce the monolithic oracle's metric
            # history and final parameters BITWISE — any reassociated
            # cross-client sum, sharding drift inside a sub-program, or
            # round dropped at a chunk boundary fails the gate.
            from fedtpu.orchestration.mpmd import parity_check
            par = parity_check(args.preset, rounds=args.rounds,
                               synthetic_rows=args.synthetic_rows)
            report["mpmd_parity"] = par
            report["ok"] = report["ok"] and par["ok"]
        if args.autoscale_sim:
            # Fold the pinned control-plane simulation into the check:
            # the decision sequence must match the committed golden
            # bitwise — policy drift fails the gate like a retrace.
            from fedtpu.autoscale.controller import (compare_decisions,
                                                     simulate)
            sim = simulate()
            cmp = compare_decisions(sim["lines"], args.autoscale_sim)
            report["autoscale_sim"] = {
                "ok": cmp["ok"], "reason": cmp["reason"],
                "golden": args.autoscale_sim,
                "control_ticks": sim["summary"]["control_ticks"]}
            report["ok"] = report["ok"] and cmp["ok"]
        if args.defense_sim:
            # Fold the pinned poisoning-defense simulation into the
            # check: the screen/quarantine decision log must match the
            # committed golden bitwise — defense drift (screen math,
            # thresholds, trace synthesis) fails the gate like a retrace.
            from fedtpu.robust.defense_sim import (compare_decisions as
                                                   _cmp_defense)
            from fedtpu.robust.defense_sim import simulate as _sim_defense
            sim = _sim_defense()
            cmp = _cmp_defense(sim["lines"], args.defense_sim)
            report["defense_sim"] = {
                "ok": cmp["ok"], "reason": cmp["reason"],
                "golden": args.defense_sim,
                "screened": sim["summary"]["screened"],
                "quarantined": sim["summary"]["quarantined"],
                "quarantined_honest": sim["summary"]["quarantined_honest"],
                "eval_accuracy": sim["summary"]["eval_accuracy"]}
            report["ok"] = report["ok"] and cmp["ok"]
        if args.net_sim:
            # Fold the pinned wire-fault campaign into the check: the
            # frame-by-frame decision log (fault verdicts, retries,
            # duplicate acks) must match the committed golden bitwise —
            # drift anywhere in the exactly-once chain (schedule
            # materialization, session dedup, ack shape) fails the gate.
            from fedtpu.resilience.net_sim import (compare_decisions as
                                                   _cmp_net)
            from fedtpu.resilience.net_sim import simulate as _sim_net
            sim = _sim_net()
            cmp = _cmp_net(sim["lines"], args.net_sim)
            report["net_sim"] = {
                "ok": cmp["ok"], "reason": cmp["reason"],
                "golden": args.net_sim,
                "wire_frames": sim["summary"]["wire_frames"],
                "incorporated": sim["summary"]["incorporated"],
                "duplicate_drops": sim["summary"]["duplicate_drops"],
                "lost_acked": sim["summary"]["lost_acked"]}
            report["ok"] = report["ok"] and cmp["ok"]
        if args.timeline_sim:
            # Fold the pinned causal-trace campaign into the check: the
            # merged two-gateway timeline (trace chains, dedup legs,
            # stage ordering) must match the committed golden bitwise —
            # drift anywhere in the trace-id derivation, the stage
            # emission points, or the canonicalization fails the gate.
            from fedtpu.telemetry.timeline_sim import (compare_decisions as
                                                       _cmp_tl)
            from fedtpu.telemetry.timeline_sim import simulate as _sim_tl
            sim = _sim_tl()
            cmp = _cmp_tl(sim["lines"], args.timeline_sim)
            report["timeline_sim"] = {
                "ok": cmp["ok"], "reason": cmp["reason"],
                "golden": args.timeline_sim,
                "chains": sim["summary"]["chains"],
                "retry_duplicate": sim["summary"]["retry_duplicate"],
                "retry_stages": sim["summary"]["retry_stages"],
                "incorporated": sim["summary"]["incorporated"]}
            report["ok"] = report["ok"] and cmp["ok"]
        if args.gateway_probe:
            # Fold a live fleet health probe into the check: every member
            # must answer a stats round-trip on its derived port file.
            from fedtpu.serving.gateway import probe_fleet
            rows = probe_fleet(args.gateway_probe, args.gateway_count)
            report["gateway_probe"] = rows
            report["ok"] = report["ok"] and all(r["ok"] for r in rows)
        if args.lockdep:
            # Fold the lock-order sanitizer into the check: the pinned
            # drills run with the real locks swapped for TrackedLocks
            # and the resulting acquisition-order graph must match the
            # committed golden bitwise — a new lock, a new nesting edge,
            # or a dropped drill fails the gate like a retrace.
            from fedtpu.analysis.lockdep import (compare_graph,
                                                 default_golden_path,
                                                 render_graph, run_drills)
            golden = args.lockdep_golden or default_golden_path()
            graph, ran = run_drills()
            rendered = render_graph(graph, ran)
            cmp = compare_graph(rendered, golden)
            cycles = graph.cycles()
            ok = cmp["ok"] and not cycles
            report["lockdep"] = {
                "ok": ok, "reason": cmp["reason"], "golden": golden,
                "drills": ran, "locks": sorted(graph.nodes),
                "edges": len(graph.edges), "cycles": cycles}
            report["ok"] = report["ok"] and ok
        if args.fuzz_corpus:
            # Fold the committed fuzz corpus into the check: every
            # minimized reproducer must still pass every oracle, replay
            # bitwise across two same-seed runs, and match its committed
            # verdict golden — a campaign-digest mismatch (hand-edited
            # manifest) fails the gate loudly.
            from fedtpu.resilience.fuzz import run_corpus
            fc = run_corpus(args.fuzz_corpus)
            report["fuzz_corpus"] = fc
            report["ok"] = report["ok"] and fc["ok"]
        if args.json:
            print(json.dumps(report))
        else:
            for key in ("preset", "backend", "device_count", "rounds",
                        "transfer_guard", "debug_nans", "warmup_cache",
                        "sentinel_available", "recompiles"):
                print(f"{key}: {report[key]}")
            if "lint" in report:
                print(f"lint: clean={report['lint']['clean']} "
                      f"findings={report['lint']['findings']}")
            if "audit" in report:
                print(f"audit: ok={report['audit']['ok']} "
                      f"digests={report['audit']['digests']}")
            if "mpmd_parity" in report:
                m = report["mpmd_parity"]
                print(f"mpmd-parity: ok={m['ok']} "
                      f"rounds_run={m['rounds_run']} width={m['width']} "
                      f"metric_mismatches={m['metric_mismatches']} "
                      f"param_leaf_mismatches={m['param_leaf_mismatches']}")
            if "autoscale_sim" in report:
                a = report["autoscale_sim"]
                print(f"autoscale-sim: ok={a['ok']} ({a['reason']})")
            if "defense_sim" in report:
                d = report["defense_sim"]
                print(f"defense-sim: ok={d['ok']} ({d['reason']}) "
                      f"quarantined={d['quarantined']} "
                      f"honest={d['quarantined_honest']} "
                      f"accuracy={d['eval_accuracy']:.4f}")
            if "net_sim" in report:
                n = report["net_sim"]
                print(f"net-sim: ok={n['ok']} ({n['reason']}) "
                      f"frames={n['wire_frames']} "
                      f"incorporated={n['incorporated']} "
                      f"dups={n['duplicate_drops']} "
                      f"lost_acked={n['lost_acked']}")
            if "timeline_sim" in report:
                t = report["timeline_sim"]
                print(f"timeline-sim: ok={t['ok']} ({t['reason']}) "
                      f"chains={t['chains']} "
                      f"retry_duplicate={t['retry_duplicate']}")
            if "gateway_probe" in report:
                for r in report["gateway_probe"]:
                    state = ("up" if r["ok"]
                             else r.get("error", "unreachable"))
                    print(f"gateway {r['gateway']}: {state}")
            if "fuzz_corpus" in report:
                fc = report["fuzz_corpus"]
                print(f"fuzz-corpus: ok={fc['ok']} "
                      f"campaigns={fc['campaigns']} ({fc['corpus']})")
                for r in fc["rows"]:
                    if not r["ok"]:
                        print(f"  {r['name']}: {r['reason']}")
            if "lockdep" in report:
                ld = report["lockdep"]
                print(f"lockdep: ok={ld['ok']} ({ld['reason']}) "
                      f"drills={len(ld['drills'])} "
                      f"locks={len(ld['locks'])} edges={ld['edges']} "
                      f"cycles={len(ld['cycles'])}")
            print(f"ok: {report['ok']}")
        return 0 if report["ok"] else 1

    if args.cmd == "audit":
        # Before _apply_overrides: the audit traces the preset's program
        # family as configured — it carries only its own flag set.
        from fedtpu.analysis.program import (audit_preset, diff_audit,
                                             render_audit_text)
        engines = ([e.strip() for e in args.engines.split(",") if e.strip()]
                   if args.engines else None)
        report = audit_preset(args.preset, engines=engines,
                              synthetic_rows=args.synthetic_rows)
        ok = report["ok"]
        if args.write_golden:
            with open(args.write_golden, "w", encoding="utf-8") as fh:
                json.dump(report, fh, indent=2, sort_keys=True)
                fh.write("\n")
        if args.golden:
            with open(args.golden, encoding="utf-8") as fh:
                golden = json.load(fh)
            mismatches = diff_audit(report, golden)
            ok = ok and not mismatches
        if args.format == "json":
            print(json.dumps(report, sort_keys=True))
            if args.golden and mismatches:
                print(json.dumps({"golden_mismatches": mismatches}))
        else:
            print(render_audit_text(report))
            if args.golden:
                if mismatches:
                    print(f"golden: {len(mismatches)} mismatch(es) "
                          f"vs {args.golden}")
                    for m in mismatches:
                        print(f"  {m}")
                else:
                    print(f"golden: matches {args.golden}")
        return 0 if ok else 1

    if args.cmd == "serve":
        # Before _apply_overrides: serve carries its own ServingConfig
        # flag set, not an experiment preset.
        from fedtpu.config import ServingConfig
        from fedtpu.resilience.supervisor import EXIT_PREEMPTED, Preempted
        from fedtpu.serving.server import run_server
        scfg = ServingConfig(
            host=args.host, port=args.port, cohort=args.cohort,
            buffer_size=args.buffer_size,
            staleness_power=args.staleness_power,
            tick_interval_s=args.tick_interval,
            flush_every=args.flush_every,
            history_window=args.history_window,
            rate_limit=args.rate_limit,
            rate_burst=args.rate_burst, max_pending=args.max_pending,
            stale_deprioritize=args.stale_deprioritize,
            stale_reject=args.stale_reject, seed=args.seed,
            screen=args.screen,
            screen_norm_mult=args.screen_norm_mult,
            screen_cos_min=args.screen_cos_min,
            screen_warmup=args.screen_warmup,
            screen_clip_norm=args.screen_clip_norm,
            quarantine_strikes=args.quarantine_strikes)
        try:
            summary = run_server(
                scfg, events=args.events,
                checkpoint_dir=args.checkpoint_dir,
                checkpoint_every_ticks=args.checkpoint_every_ticks,
                port_file=args.port_file, history_path=args.history,
                heartbeat=args.heartbeat, once=args.once,
                resume=args.resume, verbose=not args.quiet,
                net_fault_plan=args.net_fault_plan)
        except Preempted as p:
            # SIGTERM drain completed: serving state (engine + pending
            # queue + history) is checkpointed; the supervisor contract's
            # "restart me" code, same as run.
            if args.json:
                print(json.dumps({"preempted": True, "tick": p.round}))
            return EXIT_PREEMPTED
        if args.json:
            print(json.dumps(summary, default=float))
        return 0

    if args.cmd == "gateway":
        # Before _apply_overrides: a gateway is a serve process plus fleet
        # routing — same ServingConfig flag set, never an experiment
        # preset.
        from fedtpu.config import ServingConfig
        from fedtpu.resilience.supervisor import EXIT_PREEMPTED, Preempted
        from fedtpu.serving.gateway import run_gateway
        scfg = ServingConfig(
            host=args.host, port=args.port, cohort=args.cohort,
            buffer_size=args.buffer_size,
            staleness_power=args.staleness_power,
            tick_interval_s=args.tick_interval,
            flush_every=args.flush_every,
            history_window=args.history_window,
            rate_limit=args.rate_limit,
            rate_burst=args.rate_burst, max_pending=args.max_pending,
            stale_deprioritize=args.stale_deprioritize,
            stale_reject=args.stale_reject, seed=args.seed,
            screen=args.screen,
            screen_norm_mult=args.screen_norm_mult,
            screen_cos_min=args.screen_cos_min,
            screen_warmup=args.screen_warmup,
            screen_clip_norm=args.screen_clip_norm,
            quarantine_strikes=args.quarantine_strikes)
        try:
            summary = run_gateway(
                scfg, gateway_index=args.gateway_index,
                num_gateways=args.num_gateways,
                port_file=args.port_file, events=args.events,
                checkpoint_dir=args.checkpoint_dir,
                checkpoint_every_ticks=args.checkpoint_every_ticks,
                history_path=args.history, heartbeat=args.heartbeat,
                total_users=args.total_users,
                store_backend=args.store, store_path=args.store_path,
                once=args.once, resume=args.resume,
                verbose=not args.quiet,
                net_fault_plan=args.net_fault_plan)
        except Preempted as p:
            if args.json:
                print(json.dumps({"preempted": True, "tick": p.round}))
            return EXIT_PREEMPTED
        if args.json:
            print(json.dumps(summary, default=float))
        return 0

    cfg = _apply_overrides(get_preset(args.preset), args)

    if args.cmd == "run":
        from fedtpu.orchestration.loop import run_experiment
        from fedtpu.resilience.supervisor import (EXIT_DIVERGED,
                                                  EXIT_PREEMPTED, Preempted)
        try:
            result = run_experiment(cfg, verbose=not args.quiet,
                                    resume=args.resume)
        except Preempted as p:
            # SIGTERM drain completed: state is checkpointed and the run
            # is resumable — the supervisor contract's "restart me" code.
            if args.json:
                print(json.dumps({"preempted": True, "round": p.round}))
            return EXIT_PREEMPTED
        summary = result.summary()
        if summary.get("diverged"):
            # Divergence halt is deterministic — replaying it cannot
            # help, so the exit code tells supervisors NOT to restart.
            if args.json:
                print(json.dumps(summary, default=float))
            return EXIT_DIVERGED
    elif args.cmd == "sweep":
        from fedtpu.sweep.grid import run_grid_search, save_best_weights
        # Fail fast on BOTH output paths before the (minutes-long) sweep —
        # and probe the weights path before truncating the table file, so a
        # typo'd weights path can't destroy a previous run's table.
        if args.save_weights:
            open(args.save_weights, "ab").close()
        table_f = open(args.table_jsonl, "w") if args.table_jsonl else None
        # --hidden-sizes / --learning-rate narrow the sweep to that single
        # architecture / learning rate (the default is the reference's full
        # 10x9 grid) — the flags must never be silently ignored.
        grid_kw = {}
        if args.hidden_sizes is not None:
            grid_kw["hidden_grid"] = (tuple(args.hidden_sizes),)
        if args.learning_rate is not None:
            grid_kw["lr_grid"] = (args.learning_rate,)
        try:
            summary = run_grid_search(
                cfg, vmap_lr=not args.no_vmap_lr,
                # --local-steps overrides the grid's reference default of
                # 400 (MLPClassifier max_iter, hyperparameters_tuning.py:90).
                **({"local_steps": args.local_steps}
                   if args.local_steps is not None else {}),
                **grid_kw,
                keep_weights=bool(args.save_weights),
                plateau_stop=args.plateau_stop,
                bucket_pad=not args.no_bucket_pad,
                vmap_arch=not args.no_vmap_arch,
                overlap_compile=not args.no_overlap_compile,
                verbose=not args.quiet)
            if table_f is not None:
                for row in summary["table"]:
                    table_f.write(json.dumps(row, default=float) + "\n")
            if args.save_weights:
                save_best_weights(args.save_weights, summary)
                # Keep the JSON summary line serializable.
                summary.pop("weights", None)
        finally:
            if table_f is not None:
                table_f.close()
    elif args.cmd == "parity":
        from fedtpu.parity.sklearn_warmstart import run_parity_demo
        summary = run_parity_demo(cfg, verbose=not args.quiet)
    else:  # pragma: no cover — subparsers(required=True) rejects earlier
        raise SystemExit(f"unknown command {args.cmd}")

    if args.json:
        print(json.dumps(summary, default=float))
    return 0


if __name__ == "__main__":
    sys.exit(main())
