"""Host round loop: the fedtpu analogue of ``train_and_evaluate``
(FL_CustomMLPCLassifierImplementation_Multiple_Rounds.py:122-207).

What the reference round loop does with ~5 collectives, 2N+3 barriers, and
pickled weight dicts per round, this loop does with ONE call into the compiled
round program (fedtpu.parallel.round) per chunk of rounds and a scalar
metrics read-back. The host's only jobs are: decide early stopping, accumulate
history, log, checkpoint, and time.

Early-stopping parity (:181-192): rank 0 compares the 4-metric vector
(accuracy, precision, recall, f1 — mean over clients) against the previous
round with ``np.allclose(atol=tolerance)``; `patience` consecutive unchanged
rounds stop training. The reference's stop SIGNAL is read one loop-top late
(:132 reads the signal set at :195), but that lag changes NOTHING trained:
detection at round r happens after round r's train/eval/averaging, and the
re-entered iteration r+1 breaks before its Barrier/train — so the reference
trains and averages exactly r rounds, the same count fedtpu stops at. Pinned
by executing the reference's own ``train_and_evaluate`` under a fake
single-rank comm (tests/test_stop_lag.py); the only observable residue is
the second message ("Training stopped early at round N.") printed from the
doomed iteration, which this loop reproduces for log-faithful A/B.

Throughput knob: ``RunConfig.rounds_per_step = R`` scans R rounds inside one
compiled program, syncing metrics to host once per R rounds. Early stopping is
still evaluated for every round (the compiled program returns per-round
metrics), but a stop that triggers mid-chunk is detected after the chunk
already ran — training may overshoot by up to R-1 rounds (history is
truncated at the stop round; final params include the overshoot). R=1
(default) reproduces the reference cadence exactly.

The metric accumulated for stopping is the reference's semantics #1 — the
MEAN of per-client train-shard metrics (:169). The pooled semantics
(FL_SkLearn...:132-134) and the held-out test metrics (NEW — the reference
broadcasts a test split it never touches, :243-246) are recorded alongside.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import math
import os
import signal
import threading
import time
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from fedtpu.config import ExperimentConfig
from fedtpu.data import data_notice, load_dataset, load_token_corpus
from fedtpu.data.sharding import pack_clients
from fedtpu.data.tabular import Dataset
from fedtpu.models import build_model
from fedtpu.ops import build_optimizer
from fedtpu.orchestration.checkpoint import (complete_steps,
                                             retain_checkpoints,
                                             save_checkpoint)
from fedtpu.orchestration.privacy import PrivacyLedger
from fedtpu.resilience.distributed import (CollectiveWatchdog,
                                           heartbeat_path_for)
from fedtpu.resilience.supervisor import Preempted, write_heartbeat
from fedtpu.parallel.mesh import make_mesh, client_sharding
from fedtpu.telemetry import (TelemetryLogger, build_manifest,
                              default_registry, install_compile_probe,
                              make_tracer)
from fedtpu.telemetry.metrics import device_memory_gauges
from fedtpu.telemetry.trace import Phase
from fedtpu.parallel.round import (LAYER_KERNELS, LAYERS, MODULES, PIECES,
                                   RECOMPUTE,
                                   SERVER_UPDATE, SGD_PASS, STAGES,
                                   build_round_fn,
                                   build_eval_fn, check_resident_fits,
                                   init_federated_state, global_params)
from fedtpu.models.registry import LANGUAGE_MODELS
from fedtpu.training.task import Task, build_task
from fedtpu.utils.timing import Timer, force_fetch
from fedtpu.utils.trees import to_numpy


@dataclasses.dataclass
class ExperimentResult:
    """History + final model, the superset of the reference's
    ``global_metrics`` return dict (FL_CustomMLP...:124,207)."""

    # semantics #1: mean of per-client train-shard metrics, one list per
    # metric name — shape-compatible with the reference's global_metrics.
    global_metrics: Dict[str, List[float]]
    # semantics #2: pooled-over-all-clients metrics per round.
    pooled_metrics: Dict[str, List[float]]
    # per-client metric trajectories: (rounds, clients) per name.
    per_client_metrics: Dict[str, List[np.ndarray]]
    # held-out test metrics of the averaged global model (NEW).
    test_metrics: Dict[str, List[float]]
    loss: List[np.ndarray]
    sec_per_round: List[float]
    rounds_run: int
    stopped_early: bool
    final_params: dict
    config: ExperimentConfig
    # True when the non-finite guard (RunConfig.halt_on_nonfinite) fired.
    diverged: bool = False
    # per-client metrics after post-training local fine-tuning
    # (FedConfig.personalize_steps > 0): {"per_client": {name: (C,)},
    # "client_mean": {name: float}}. Empty dict when personalization is off.
    personalized_metrics: Dict[str, dict] = dataclasses.field(
        default_factory=dict)
    # Rounds the RELEASED final_params actually trained through — after a
    # pipelined early stop this exceeds rounds_run by the dropped
    # in-flight overshoot chunk. 0 means "same as rounds_run".
    rounds_trained: int = 0
    # Cumulative per-order RDP curve of the released state (None when DP
    # noise is off). Composes across resumes: rounds noised under an
    # earlier config are charged at THAT config's rate (restored from the
    # checkpoint meta), not the current one.
    dp_rdp_total: Optional[np.ndarray] = None
    # True when a resumed pre-r3 checkpoint carried no RDP curve and the
    # pre-resume rounds had to be charged at the current config's rate.
    dp_base_assumed: bool = False
    # True when rounds AFTER the noised ones re-trained on the private
    # data with noise off — the released model then has NO (eps, delta)
    # guarantee, whatever the curve says (reported as epsilon=inf).
    dp_guarantee_void: bool = False
    # True when the epsilon composes noised rounds from EARLIER resumed
    # segments: the reported (noise_multiplier, sampling_rate) describe
    # only the current segment and cannot re-derive the epsilon alone.
    dp_composed: bool = False
    # Final adaptive clip norm (FedConfig.dp_adaptive_clip); None when
    # adaptive clipping is off.
    final_dp_clip: Optional[float] = None
    # Async engine only (FedConfig.async_mode): per-tick (C,) staleness
    # vectors — arrivals report the staleness their shipped update had,
    # absentees their current age. Empty for the synchronous engines.
    staleness: List[np.ndarray] = dataclasses.field(default_factory=list)
    # Which rows the run trained on (Dataset.source + split sizes) — in the
    # summary so that even a --quiet --json run says when it was synthetic.
    data: Dict[str, object] = dataclasses.field(default_factory=dict)

    def summary(self) -> dict:
        last = {k: v[-1] for k, v in self.global_metrics.items() if v}
        extra = ({"personalized_client_mean":
                  self.personalized_metrics.get("client_mean")}
                 if self.personalized_metrics else {})
        # Exclude the first chunk's entries from the mean: its compile time is
        # smeared over rounds_per_step per-round entries, not just the first.
        warm = max(1, self.config.run.rounds_per_step)
        steady = (self.sec_per_round[warm:] if len(self.sec_per_round) > warm
                  else self.sec_per_round or [0.0])
        dp = self.privacy_spent()
        return {
            "rounds_run": self.rounds_run,
            "stopped_early": self.stopped_early,
            "diverged": self.diverged,
            "final_global_metrics": last,
            "mean_sec_per_round": float(np.mean(steady)),
            **({"data": self.data} if self.data else {}),
            **extra,
            **({"dp": dp} if dp else {}),
            **({"final_dp_clip": self.final_dp_clip}
               if self.final_dp_clip is not None else {}),
            **({"mean_staleness":
                float(np.mean([s.mean() for s in self.staleness])),
                "max_staleness":
                float(max(s.max() for s in self.staleness))}
               if self.staleness else {}),
        }

    def privacy_spent(self) -> dict:
        """(epsilon, delta) actually spent by this run's DP aggregation —
        the number a DP feature exists to produce (VERDICT r2 weak #6).
        Empty dict when DP noise was off (clipping alone bounds influence
        but provides no epsilon). The mechanism is the client-level
        subsampled Gaussian: q = participation_rate, sigma =
        dp_noise_multiplier, one invocation per round the released state
        trained through — ``rounds_trained``, NOT ``rounds_run``: after a
        pipelined early stop the final params carry the overshoot chunk's
        extra noised rounds, and a privacy accountant must never
        under-count. See fedtpu.ops.dp_accountant for the RDP analysis."""
        fed = self.config.fed
        curve_spent = (self.dp_rdp_total is not None
                       and bool(np.any(np.asarray(self.dp_rdp_total) > 0)))
        if fed.dp_noise_multiplier <= 0 and not curve_spent:
            return {}
        from fedtpu.ops.dp_accountant import (epsilon_from_rdp,
                                              privacy_spent)
        steps = max(self.rounds_run, self.rounds_trained)
        if self.dp_rdp_total is not None:
            # The composed curve — exact across resumes with changed
            # (noise multiplier, sampling rate), and still reported when
            # the CURRENT segment ran with noise off but earlier noised
            # segments built the released model.
            spent = epsilon_from_rdp(list(self.dp_rdp_total), fed.dp_delta)
        else:
            spent = privacy_spent(q=fed.participation_rate,
                                  noise_multiplier=fed.dp_noise_multiplier,
                                  steps=steps, delta=fed.dp_delta)
        out = {"epsilon": spent["epsilon"], "delta": spent["delta"],
               "rdp_order": spent["order"],
               "noise_multiplier": fed.dp_noise_multiplier,
               "sampling_rate": fed.participation_rate,
               "rounds": steps}
        if self.dp_composed:
            # (sigma, q) above are the CURRENT segment's only; the
            # epsilon composes earlier resumed segments' spend from the
            # persisted RDP curve and cannot be re-derived from this
            # dict's triple alone.
            out["composed_over_resumed_segments"] = True
        if self.dp_guarantee_void:
            # Unnoised rounds re-trained on the private data after the
            # noised ones — NOT post-processing: no finite (eps, delta)
            # holds for the released model, whatever was spent before.
            out["epsilon"] = math.inf
            out["rdp_order"] = None
            out["guarantee_void"] = ("rounds trained with noise off "
                                     "after noised rounds")
        if self.dp_base_assumed:
            # Pre-r3 checkpoint: the pre-resume rounds' true (sigma, q)
            # are unrecorded — they were charged at the CURRENT config's
            # rate, so epsilon may be off for those rounds.
            out["resume_rdp"] = "assumed_current_config"
        return out


@dataclasses.dataclass
class Experiment:
    """Wired-up experiment: data on the mesh + compiled-step factory."""

    make_step: Callable[[int], Callable]   # rounds_per_step -> round_step fn
    state: dict
    batch: dict
    eval_step: Callable
    dataset: Dataset
    mesh: object
    # Post-training per-client fine-tune (FedConfig.personalize_steps > 0).
    personalize_fn: Optional[Callable] = None
    # Extract the global model from the engine's state: slot 0 for the
    # synchronous engines (every slot holds the post-average global), the
    # freshest anchor for the async engine (slots hold per-client models).
    global_fn: Callable = global_params
    # Model/optimizer handles for engines that compile their own programs
    # from the experiment's wiring (the MPMD DAG builds its sub-programs
    # from these).
    apply_fn: Optional[Callable] = None
    tx: Optional[object] = None
    num_classes: int = 0
    # What the job learns (fedtpu.training.task): the loop's histories, its
    # printed line, the early-stop rule and checkpoint retention go by its
    # metric names.
    task: Optional[Task] = None


# The shared-global round program of the newest configuration, by width. A
# later job of the same process (a sweep's next point, a benchmark's next
# job) is handed the jitted function the job before it ran, and with it jit's
# own executable: a language model's round is seconds to trace and, at
# hundreds of megabytes, tens of seconds to load even from the persistent
# cache. Beside a width's function, under ``("compiled", width)``, the
# executable ``compile_round_program`` made of it ahead of any job. One
# configuration at a time: a process that moves on lets the old executables
# go.
_ROUND_PROGRAMS: dict = {}


def _round_program(program, width: int, build: Callable[[int], Callable]):
    """``build(width)``, or what it returned the last time ``program`` (all
    that ``build`` closes over, by value) was asked for at this width."""
    if _ROUND_PROGRAMS.get("program") != program:
        _ROUND_PROGRAMS.clear()
        _ROUND_PROGRAMS["program"] = program
    if width not in _ROUND_PROGRAMS:
        _ROUND_PROGRAMS[width] = build(width)
    return _ROUND_PROGRAMS[width]


def compile_round_program(step: Callable, *args):
    """``step`` (what ``Experiment.make_step`` returned) compiled for
    arguments shaped as ``args``, ahead of the job that will dispatch it (a
    caller with other work for the minutes a language model's round takes
    to compile). Where ``step`` is the process's kept round program, the
    executable is kept beside it and ``run_experiment`` dispatches it in the
    function's place (an AOT ``Compiled`` is called exactly like the jit
    wrapper): no second trace, no lowering, no load from the persistent
    cache, and the program's text is read from it."""
    compiled = step.lower(*args).compile()
    for width, kept in list(_ROUND_PROGRAMS.items()):
        if kept is step:
            _ROUND_PROGRAMS["compiled", width] = compiled
    return compiled


def _compiled_ahead(step: Callable) -> Callable:
    """The executable ``compile_round_program`` kept for ``step``, else
    ``step`` itself."""
    for width, kept in _ROUND_PROGRAMS.items():
        if kept is step:
            return _ROUND_PROGRAMS.get(("compiled", width), step)
    return step


def build_experiment(cfg: ExperimentConfig,
                     dataset: Optional[Dataset] = None,
                     mesh: Optional[object] = None) -> Experiment:
    """Wire data -> mesh -> model -> optimizer -> compiled round factory.

    ``mesh``: explicit ('clients',) mesh to build on instead of the
    process-local default from ``make_mesh``. A live reshard
    (fedtpu.resilience.reshard) passes the agreed post-shrink submesh here —
    under jax.distributed the default would re-enroll every process,
    including the departing one."""
    if cfg.fed.client_state not in ("resident", "stateless"):
        raise ValueError(f"client_state must be 'resident' or 'stateless', "
                         f"got {cfg.fed.client_state!r}")
    stateless = cfg.fed.client_state == "stateless"
    if stateless:
        from fedtpu.parallel import stateless as sl
        sl.validate_stateless_config(cfg)
    elif cfg.fed.local_batch_rows or cfg.fed.one_step_kind:
        raise ValueError("local_batch_rows needs client_state='stateless' "
                         "(and one_step_kind with it): "
                         "the resident engines take one full-batch step")
    if dataset is not None:
        ds = dataset
    elif cfg.data.dataset_name == "tokens":
        ds = load_token_corpus(cfg)
    else:
        ds = load_dataset(cfg.data)
    model_cfg = cfg.model
    # The data say how wide the input and the output are, for the kinds
    # that have either; a language model brings its own vocabulary.
    if model_cfg.kind == "mlp" and model_cfg.input_dim != ds.input_dim:
        model_cfg = dataclasses.replace(model_cfg, input_dim=ds.input_dim)
    if (model_cfg.kind in ("mlp", "convnet")
            and model_cfg.num_classes != ds.num_classes):
        model_cfg = dataclasses.replace(model_cfg, num_classes=ds.num_classes)
    if (model_cfg.kind in LANGUAGE_MODELS
            and ds.num_classes != model_cfg.vocab_size):
        raise ValueError(f"the corpus has a vocabulary of {ds.num_classes} "
                         f"and the model one of {model_cfg.vocab_size}")

    init_fn, apply_fn = build_model(model_cfg)
    task = build_task(model_cfg, apply_fn, ds.num_classes)
    tx = build_optimizer(cfg.optim)
    packed = pack_clients(ds.x_train, ds.y_train, cfg.shard,
                          client_of_row=ds.client_of_row)

    # Fail fast on a DP config the round builders would reject later —
    # after data loading and state init (both engines share this check).
    if cfg.fed.dp_noise_multiplier > 0 and cfg.fed.dp_clip_norm <= 0:
        raise ValueError("dp_noise_multiplier requires dp_clip_norm > 0 "
                         "(noise std is noise_multiplier * clip / weight)")
    if cfg.fed.dp_adaptive_clip and cfg.fed.dp_clip_norm <= 0:
        # Fail before state init (its adaptive_clip_init guard fires first
        # otherwise, with a less actionable message).
        raise ValueError("dp_adaptive_clip needs dp_clip_norm > 0 as the "
                         "initial clip")

    # Server optimizer / DP delta path: shared by both engines.
    server = None
    if cfg.fed.server_opt != "none":
        from fedtpu.ops.server_opt import make_server_optimizer
        server = make_server_optimizer(
            cfg.fed.server_opt, learning_rate=cfg.fed.server_lr,
            momentum=cfg.fed.server_momentum, b1=cfg.fed.server_b1,
            b2=cfg.fed.server_b2, tau=cfg.fed.server_tau)
    elif cfg.fed.dp_clip_norm > 0 or cfg.fed.scaffold:
        # DP with plain averaging — and SCAFFOLD, whose server update is
        # the paper's eta_g=1 — still run the delta path and need the
        # (empty-momentum) server state initialized.
        from fedtpu.ops.server_opt import identity_server_optimizer
        server = identity_server_optimizer()

    global_fn = global_params
    if stateless:
        if mesh is None:
            mesh = make_mesh(cfg.run.mesh_devices, cfg.shard.num_clients)
        shard = client_sharding(mesh)
        if server is None:
            from fedtpu.ops.server_opt import identity_server_optimizer
            server = identity_server_optimizer()
        state_fn = lambda: sl.init_stateless_state(
            jax.random.key(cfg.fed.init_seed), mesh, init_fn, server)
        build_step = lambda r: sl.build_stateless_round_fn(
            mesh, task, packed.counts,
            learning_rate=cfg.optim.learning_rate,
            steplr_step_size=cfg.optim.steplr_step_size,
            steplr_gamma=cfg.optim.steplr_gamma,
            weighting=cfg.fed.weighting, server_opt=server,
            local_batch_rows=cfg.fed.local_batch_rows,
            one_step_kind=cfg.fed.one_step_kind, rounds_per_step=r)
        # everything the builder above is handed, by value
        program = (mesh, model_cfg, ds.num_classes,
                   tuple(int(n) for n in packed.counts), cfg.optim,
                   cfg.fed.weighting, cfg.fed.server_opt, cfg.fed.server_lr,
                   cfg.fed.server_momentum, cfg.fed.server_b1,
                   cfg.fed.server_b2, cfg.fed.server_tau,
                   cfg.fed.local_batch_rows, cfg.fed.one_step_kind)
        step_fn = lambda r: _round_program(program, r, build_step)
        global_fn = lambda state: state["params"]
    elif cfg.fed.async_mode:
        # The async engine replaces the whole synchronous aggregation
        # stack with the tick/arrival process — every knob of that stack
        # is meaningless (or privacy-unsound) under it, so each is
        # rejected loudly rather than silently ignored.
        if cfg.run.model_parallel > 1:
            raise ValueError("async_mode requires the 1-D engine "
                             "(model_parallel=1)")
        if cfg.fed.weighting != "uniform":
            raise ValueError("async_mode requires weighting='uniform': the "
                             "FedBuff arrival mean is unweighted "
                             "(--weighting uniform)")
        if cfg.fed.participation_rate < 1.0:
            raise ValueError("async_mode replaces client sampling with its "
                             "own arrival process; use --arrival-rate, not "
                             "--participation-rate")
        if server is not None and cfg.fed.server_opt != "none":
            raise ValueError("async_mode has its own server update "
                             "(server_lr-scaled discounted delta mean); "
                             "FedOpt server optimizers are unsupported")
        if cfg.fed.dp_clip_norm > 0 or cfg.fed.dp_noise_multiplier > 0:
            raise ValueError("async_mode does not support DP aggregation: "
                             "per-arrival releases need an async-specific "
                             "accountant fedtpu does not claim to have")
        if cfg.fed.robust_aggregation != "none" or cfg.fed.byzantine_clients:
            raise ValueError("async_mode does not support robust "
                             "aggregation rules (they need the full cohort "
                             "each round; arrivals are a sparse subset)")
        if cfg.fed.compress != "none":
            raise ValueError("async_mode does not support compressed "
                             "exchange")
        if cfg.fed.scaffold:
            raise ValueError("async_mode does not support SCAFFOLD (its "
                             "variate refresh assumes lockstep rounds)")
        if cfg.fed.personalize_steps > 0:
            raise ValueError("async_mode does not support personalize_steps: "
                             "post-training fine-tune starts every client "
                             "from the final averaged global, but async "
                             "client slots hold distinct (possibly stale) "
                             "local models, not that global")
        if cfg.fed.aggregation != "psum":
            raise ValueError("async_mode uses the psum aggregation path "
                             "only")
        from fedtpu.parallel import async_fed
        if mesh is None:
            mesh = make_mesh(cfg.run.mesh_devices, cfg.shard.num_clients)
        shard = client_sharding(mesh)
        state_fn = lambda: async_fed.init_async_state(
            jax.random.key(cfg.fed.init_seed), mesh, cfg.shard.num_clients,
            init_fn, tx, same_init=cfg.fed.same_init,
            buffer_size=cfg.fed.async_buffer_size)
        step_fn = lambda r: async_fed.build_async_round_fn(
            mesh, apply_fn, tx, ds.num_classes,
            arrival_rate=cfg.fed.async_arrival_rate,
            arrival_seed=cfg.fed.async_arrival_seed,
            staleness_power=cfg.fed.async_staleness_power,
            server_lr=cfg.fed.server_lr,
            local_steps=cfg.fed.local_steps,
            prox_mu=cfg.fed.prox_mu,
            buffer_size=cfg.fed.async_buffer_size,
            ticks_per_step=r)
        global_fn = async_fed.async_global_params
    elif cfg.run.model_parallel > 1:
        # 2-D ('clients','model') GSPMD engine (fedtpu.parallel.tp).
        from fedtpu.parallel import tp
        if model_cfg.kind not in ("mlp", "convnet"):
            raise ValueError("model_parallel > 1 supports the MLP and "
                             "ConvNet families only")
        if cfg.fed.participation_rate < 1.0:
            raise ValueError("partial participation requires the 1-D engine "
                             "(model_parallel=1)")
        if cfg.fed.aggregation != "psum":
            raise ValueError("explicit ring aggregation requires the 1-D "
                             "engine (model_parallel=1); the 2-D engine's "
                             "collectives are GSPMD-chosen")
        if cfg.fed.compress != "none":
            raise ValueError("compressed aggregation requires the 1-D "
                             "engine (model_parallel=1)")
        if (cfg.fed.robust_aggregation != "none"
                or cfg.fed.byzantine_clients > 0):
            raise ValueError("robust aggregation / byzantine injection "
                             "requires the 1-D engine (model_parallel=1)")
        if cfg.fed.scaffold:
            raise ValueError("scaffold requires the 1-D engine "
                             "(model_parallel=1)")
        if cfg.fed.dp_adaptive_clip:
            raise ValueError("dp_adaptive_clip requires the 1-D engine "
                             "(model_parallel=1)")
        # Only dims the tp specs actually place on the 'model' axis need to
        # divide: the col-sharded out-dims (even indices — row layers shard
        # the PREVIOUS layer's out-dim, already covered) plus, for convnets,
        # the dense hidden dim (col out / head row in).
        sharded_dims = (model_cfg.hidden_sizes[0::2]
                        if model_cfg.kind == "mlp"
                        else (*model_cfg.conv_channels[0::2],
                              model_cfg.hidden_sizes[0]))
        bad = [h for h in sharded_dims if h % cfg.run.model_parallel]
        if bad:
            raise ValueError(
                f"sharded dims {bad} not divisible by "
                f"model_parallel={cfg.run.model_parallel}; uneven shards "
                "would silently pad and imbalance memory/compute")
        if mesh is not None:
            raise ValueError("build_experiment(mesh=...) supports the 1-D "
                             "engines only (elastic reshard does not cover "
                             "model_parallel > 1)")
        mesh = tp.make_mesh_2d(cfg.run.model_parallel, cfg.shard.num_clients,
                               cfg.run.mesh_devices)
        shard = tp.batch_sharding_2d(mesh)
        state_fn = lambda: tp.init_federated_state_2d(
            jax.random.key(cfg.fed.init_seed), mesh, cfg.shard.num_clients,
            init_fn, tx, same_init=cfg.fed.same_init, server_opt=server)
        step_fn = lambda r: tp.build_round_fn_2d(
            mesh, apply_fn, tx, ds.num_classes, weighting=cfg.fed.weighting,
            rounds_per_step=r, local_steps=cfg.fed.local_steps,
            prox_mu=cfg.fed.prox_mu,
            server_opt=server,
            dp_clip_norm=cfg.fed.dp_clip_norm,
            dp_noise_multiplier=cfg.fed.dp_noise_multiplier,
            dp_seed=cfg.fed.dp_seed)
    else:
        if mesh is None:
            mesh = make_mesh(cfg.run.mesh_devices, cfg.shard.num_clients)
        shard = client_sharding(mesh)
        check_resident_fits(init_fn, tx, cfg.shard.num_clients, mesh)
        state_fn = lambda: init_federated_state(
            jax.random.key(cfg.fed.init_seed), mesh, cfg.shard.num_clients,
            init_fn, tx, same_init=cfg.fed.same_init, server_opt=server,
            shared_start=cfg.fed.compress != "none",
            scaffold=cfg.fed.scaffold,
            adaptive_clip_init=(cfg.fed.dp_clip_norm
                                if cfg.fed.dp_adaptive_clip else None))
        step_fn = lambda r: build_round_fn(
            mesh, apply_fn, tx, ds.num_classes, weighting=cfg.fed.weighting,
            rounds_per_step=r,
            participation_rate=cfg.fed.participation_rate,
            participation_seed=cfg.fed.participation_seed,
            aggregation=cfg.fed.aggregation,
            local_steps=cfg.fed.local_steps,
            prox_mu=cfg.fed.prox_mu,
            server_opt=server,
            dp_clip_norm=cfg.fed.dp_clip_norm,
            dp_noise_multiplier=cfg.fed.dp_noise_multiplier,
            dp_seed=cfg.fed.dp_seed,
            dp_adaptive_clip=cfg.fed.dp_adaptive_clip,
            dp_target_quantile=cfg.fed.dp_target_quantile,
            dp_clip_lr=cfg.fed.dp_clip_lr,
            dp_count_noise_multiplier=cfg.fed.dp_count_noise_multiplier,
            compress=cfg.fed.compress,
            robust_aggregation=cfg.fed.robust_aggregation,
            trim_ratio=cfg.fed.trim_ratio,
            krum_f=cfg.fed.krum_f,
            byzantine_clients=cfg.fed.byzantine_clients,
            scaffold=cfg.fed.scaffold, task=task)

    # safe_put: plain device_put of a host value onto a cross-process
    # sharding runs an implicit per-array equality broadcast under
    # jax.distributed (fedtpu.parallel.multihost.safe_put).
    from fedtpu.parallel.multihost import safe_put
    batch = {
        "x": safe_put(packed.x, shard),
        "y": safe_put(packed.y, shard),
        "mask": safe_put(packed.mask, shard),
    }
    state = state_fn()

    if cfg.fed.init_weights_npz:
        # Warm start from a persisted weights artifact (sweep winner):
        # broadcast the loaded global model into every client slot,
        # preserving each leaf's live sharding (works for both engines and
        # under jax.distributed — same host data on every process).
        from fedtpu.sweep.grid import load_best_weights
        loaded = load_best_weights(cfg.fed.init_weights_npz)["weights"]
        live = state["params"]
        l_leaves = jax.tree.leaves(loaded)
        p_leaves = jax.tree.leaves(live)
        shapes_ok = (jax.tree.structure(loaded) == jax.tree.structure(live)
                     and all(tuple(a.shape) == tuple(b.shape[1:])
                             for a, b in zip(l_leaves, p_leaves)))
        if not shapes_ok:
            raise ValueError(
                f"init_weights_npz architecture mismatch: artifact leaves "
                f"{[tuple(a.shape) for a in l_leaves]} vs model (per-client) "
                f"{[tuple(b.shape[1:]) for b in p_leaves]} — the artifact "
                "was saved for a different hidden_sizes/input_dim")
        state["params"] = _bcast_into_slots(loaded, live)
        if "anchors" in state:
            # Async engine: clients "pulled" the warm-start global, so the
            # anchors (the deltas' reference points) must carry it too.
            state["anchors"] = _bcast_into_slots(loaded, state["anchors"])

    eval_step = build_eval_fn(task)
    personalize_fn = None
    if cfg.fed.personalize_steps > 0:
        from fedtpu.training.personalize import build_personalize_fn
        personalize_fn = build_personalize_fn(apply_fn, tx, ds.num_classes,
                                              cfg.fed.personalize_steps)
    return Experiment(make_step=step_fn, state=state, batch=batch,
                      eval_step=eval_step, dataset=ds, mesh=mesh,
                      personalize_fn=personalize_fn, global_fn=global_fn,
                      apply_fn=apply_fn, tx=tx, num_classes=ds.num_classes,
                      task=task)


# The loop's finiteness check: its phase in the sink and the trace, and the
# named scope of its program.
STATE_CHECK = "state_check"


@jax.jit
def _tree_finite(tree) -> jax.Array:
    """Single-scalar device reduction: every floating leaf entirely finite
    (integer leaves — optimizer step counts — cannot be non-finite)."""
    with jax.named_scope(STATE_CHECK):
        checks = [jnp.all(jnp.isfinite(l)) for l in jax.tree.leaves(tree)
                  if jnp.issubdtype(l.dtype, jnp.inexact)]
        return jnp.all(jnp.stack(checks)) if checks else jnp.array(True)


def _emit_program_scopes(tracer, program: str, width: Optional[int], fn,
                         *args) -> None:
    """The join between a device trace and the scopes: a trace names an
    operation by its HLO text, without op_name, so the run says which
    operation of ``fn``'s program belongs to which stage (``scopes``),
    second-level scope (``layers``), third-level one (``pieces``) and
    direction (``passes``), all from one walk of the executable's text
    (analysis.program.program_scopes). After ``fn`` ran on arguments shaped
    as ``args``, jit's own lowering cache holds the executable (else the
    persistent cache does): reading its text compiles nothing. The event's
    ``dur_s`` is what reading and walking the text took.

    ``configure_persistent_cache`` keys the persistent cache by the
    metadata too, so the executable a run is served carries its own
    checkout's scopes. One from elsewhere (a ``ProgramCache`` entry, a cache
    directory filled before that) can still carry another's: every program
    named here is built under a stage scope; a text that names none is such
    an executable, and the event says ``stale_metadata`` rather than pass
    its operations off as unscoped."""
    t0 = time.perf_counter()
    try:
        from fedtpu.analysis.program import program_scopes
        compiled = (fn if hasattr(fn, "as_text")
                    else fn.lower(*args).compile())
        found = program_scopes(
            compiled.as_text(), STAGES + (STATE_CHECK,), layers=LAYERS,
            pieces=PIECES, modules=MODULES, update=(SGD_PASS, SERVER_UPDATE),
            recompute=(RECOMPUTE,))
        if not found["scopes"]:
            found["stale_metadata"] = True
        for key in [*found["scopes"], *found["unscoped"]]:
            for prefix, layer in LAYER_KERNELS.items():
                if key.startswith(prefix):
                    found["layers"][key] = layer
        tracer.event("program_scopes", dur_s=time.perf_counter() - t0,
                     program=program, width=width, **found)
    except Exception as exc:
        # Diagnostic metadata, like the manifest's audit: a failure here
        # must not take down the run it describes.
        tracer.event("program_scopes", dur_s=time.perf_counter() - t0,
                     program=program, width=width, error=str(exc))


def _bcast_into_slots(global_np, live_params):
    """Host-side form of bcast_global (fedtpu.parallel.round): one global
    (clients-free) numpy pytree into every client slot of the live sharded
    params, preserving each leaf's per-leaf sharding and dtype. Shared by
    elastic resume and the init_weights warm start — keep them from
    drifting apart."""
    from fedtpu.parallel.multihost import safe_put
    return jax.tree.map(
        lambda g, p: safe_put(
            np.broadcast_to(np.asarray(g)[None], p.shape).astype(p.dtype),
            p.sharding),
        global_np, live_params)


def _unstack_metrics(metrics: dict, take: int) -> List[dict]:
    """Per-round metric dicts out of a (possibly R-stacked) metrics pytree."""
    if take == 1:
        return [metrics]
    return [jax.tree.map(lambda v: v[j], metrics) for j in range(take)]


def _drop_tail(lst: list, n: int) -> None:
    """Drop the last ``n`` entries in place (no-op for n <= 0; clamped) —
    the rollback truncation primitive for the in-memory-only histories,
    which may hold FEWER entries than rounds when the run resumed."""
    if n > 0:
        del lst[max(0, len(lst) - n):]


def run_experiment(cfg: ExperimentConfig, dataset: Optional[Dataset] = None,
                   verbose: bool = True,
                   resume: bool = False,
                   on_chunk: Optional[Callable[[int, int], None]] = None
                   ) -> ExperimentResult:
    """``resume=True``: restore the latest checkpoint under
    ``cfg.run.checkpoint_dir`` (full per-client state + the client-mean metric
    history) and continue the round loop from the saved round. Pooled /
    per-client / test histories restart at the resume point; the early-stop
    comparator re-seeds from the restored history's last entry.

    ``on_chunk(last_round, take)`` is called where the ``chunk`` span
    closes: the chunk's metrics are on the host, so its device work has
    finished and the next chunk is not dispatched yet (under
    ``pipelined_stop`` / ``mpmd`` it is already in flight). The
    cohort-store engine (``fed.cohort_size > 0``) has its own loop and
    does not call it."""
    # Multi-process (multi-host) awareness — the reference runs its WHOLE
    # driver under `mpirun --hostfile`, so the whole loop must run under
    # jax.distributed too (tests/test_multihost_e2e.py runs it across two
    # OS processes). Three rules:
    #   * anything fetched to host must be FULLY REPLICATED first —
    #     per-client leaves are client-sharded across processes and not
    #     host-addressable; `_rep` re-lays a pytree out replicated (GSPMD
    #     inserts the cross-host all-gathers), which also keeps the
    #     early-stop/divergence control flow consensual on every process;
    #   * print/console side effects happen on process 0 only — every
    #     process gets a real role-scoped tracer (peers write to the
    #     derived ``<events>.p<i>`` sink) but a silent logger — but
    #     NOT checkpoint writes: orbax save is a collective (every process
    #     must call it or the job deadlocks in orbax's internal barrier),
    #     with each process persisting the client shards it owns to the
    #     shared checkpoint filesystem;
    #   * control flow (early stop, divergence, round counters) stays
    #     identical on every process because it is derived from the
    #     replicated metrics.
    # Cohort-store engine mode (fedtpu.cohort; docs/scaling.md): the
    # population lives in a host-side ClientStateStore and only
    # cohort_size slots exist on device — the round loop, prefetch, and
    # store writeback all live in run_cohort_experiment. Same config
    # surface, same ExperimentResult, bitwise-equal to this loop when
    # cohort_size == num_clients (tests/test_cohort.py).
    if cfg.fed.cohort_size > 0:
        if cfg.fed.client_state == "stateless":
            # this dispatch never reaches build_experiment, which refuses
            from fedtpu.parallel.stateless import validate_stateless_config
            validate_stateless_config(cfg)
        from fedtpu.cohort.scheduler import run_cohort_experiment
        return run_cohort_experiment(cfg, dataset=dataset, verbose=verbose,
                                     resume=resume)
    # Resilience knob validation FIRST — before any build/compile work,
    # so a bad combination fails in milliseconds, not after a compile.
    if cfg.run.on_divergence not in ("halt", "rollback"):
        raise ValueError("on_divergence must be 'halt' or 'rollback', got "
                         f"{cfg.run.on_divergence!r}")
    if cfg.run.on_divergence == "rollback":
        if not (cfg.run.checkpoint_dir and cfg.run.checkpoint_every > 0):
            raise ValueError("on_divergence='rollback' needs a restore "
                             "point: set checkpoint_dir and "
                             "checkpoint_every > 0")
        if cfg.run.pipelined_stop:
            raise ValueError(
                "on_divergence='rollback' is incompatible with "
                "pipelined_stop: the pipelined divergence guard fires one "
                "in-flight chunk late, after the restore point's successor "
                "chunk already dispatched")
    if cfg.run.rollback_exclude:
        if cfg.run.on_divergence != "rollback":
            raise ValueError("rollback_exclude requires "
                             "on_divergence='rollback'")
        if cfg.fed.async_mode:
            raise ValueError("rollback_exclude requires the synchronous "
                             "engines: exclusion zeroes the sample mask, "
                             "which the async arrival process ignores")
        if cfg.fed.weighting != "data_size":
            raise ValueError(
                "rollback_exclude requires weighting='data_size': a "
                "zero-mask client has aggregation weight mask.sum()=0 only "
                "under data-size weighting (under 'uniform' it would still "
                "average in at weight 1)")
    if cfg.run.mpmd:
        # MPMD DAG (fedtpu.orchestration.mpmd): same fail-fast contract —
        # every engine knob the decomposition cannot honor is rejected
        # before any build work.
        from fedtpu.orchestration.mpmd import validate_mpmd_config
        validate_mpmd_config(cfg)
        if cfg.run.pipelined_stop:
            raise ValueError(
                "run.mpmd subsumes pipelined_stop (the DAG already keeps "
                "one chunk in flight); set only one of the two")
        if cfg.run.on_divergence == "rollback":
            raise ValueError(
                "on_divergence='rollback' is incompatible with mpmd for "
                "the same reason as pipelined_stop: the divergence guard "
                "fires one in-flight chunk late, after the restore "
                "point's successor chunk already dispatched")
        if cfg.run.overlap_compile:
            raise ValueError(
                "run.mpmd compiles every sub-program ahead of time; "
                "overlap_compile has no monolithic chunk left to build "
                "in the background")

    multiproc = jax.process_count() > 1
    if cfg.run.mpmd and multiproc:
        raise ValueError(
            "run.mpmd is single-process: the DAG's cross-slice "
            "device_put edge has no multihost transfer path")
    io_proc = jax.process_index() == 0
    verbose = verbose and io_proc

    tel = cfg.run.telemetry
    # Schema-v2 identity: every process gets a REAL role-scoped tracer.
    # Process 0 keeps the configured sink; peers derive ``<events>.p<i>``
    # (the heartbeat derivation rule) so each file stays single-writer
    # and `fedtpu timeline` / merged `fedtpu report` can key per-process
    # sections explicitly instead of colliding on run_id.
    events_path = tel.events_path
    if events_path and not io_proc:
        events_path = f"{events_path}.p{jax.process_index()}"
    tracer = make_tracer(events_path, role="run",
                         process_index=jax.process_index())
    registry = default_registry()
    registry.reset()
    install_compile_probe()
    log = TelemetryLogger(verbose=verbose, tracer=tracer,
                          level=tel.log_level)

    # Before ANY compile (build_experiment may already trace programs):
    # the same entry point the CLI uses, so library callers cache in the
    # same directory (fedtpu.compilation.resolve_cache_dir).
    from fedtpu.compilation import configure_persistent_cache
    configure_persistent_cache(cfg.run.compilation_cache)

    with tracer.span("build"):
        exp = build_experiment(cfg, dataset)
    state, batch, eval_step, ds = exp.state, exp.batch, exp.eval_step, exp.dataset
    names = exp.task.metric_names
    # First log line: which rows this run trains on (never a synthetic
    # stand-in passing for the preset's dataset); the manifest repeats it.
    log.info(data_notice(ds))
    data_info = {**ds.source, "train_rows": int(len(ds.x_train)),
                 "test_rows": int(len(ds.x_test)),
                 "features": int(ds.input_dim)}

    # Supervisor restart generation (fedtpu.resilience.supervisor sets
    # FEDTPU_RESTARTS on every child): recorded in the manifest, and it
    # disarms the fault plan's once-per-run kill faults — a restarted run
    # resumes BELOW the fault round and would re-kill itself forever.
    restart_count = int(os.environ.get("FEDTPU_RESTARTS", "0") or 0)

    injector = None
    if cfg.run.fault_plan:
        from fedtpu.resilience.faults import FaultInjector, FaultPlan
        plan = FaultPlan.load(cfg.run.fault_plan,
                              num_clients=cfg.shard.num_clients,
                              rounds=cfg.fed.rounds)
        injector = FaultInjector(plan, restart_count=restart_count,
                                 tracer=tracer, registry=registry,
                                 process_index=jax.process_index())
        log.info(f"Fault plan {plan.digest}: {len(plan.faults)} fault(s), "
                 f"{injector.armed_count} armed"
                 + (f" (restart {restart_count})" if restart_count else "")
                 + ".")

    # Preemption drain: SIGTERM (the cloud's eviction notice, and the
    # supervisor's forwarded stop) sets a flag the loop-top check turns
    # into checkpoint + Preempted (exit code 75 via the CLI). Installed
    # only when there is somewhere to drain TO, and only on the main
    # thread (signal.signal's requirement). Multihost preemption assumes
    # the signal reaches every process (the TPU maintenance-event
    # convention) — the drain save is a collective.
    preempt = {"sig": None}
    _prev_term = None
    if (cfg.run.checkpoint_dir
            and threading.current_thread() is threading.main_thread()):
        def _on_term(signum, frame):
            preempt["sig"] = signum
        _prev_term = signal.signal(signal.SIGTERM, _on_term)

    # Liveness: EVERY process writes its own derived heartbeat path
    # (process 0 keeps the configured base, peers get .p<i>) so the gang
    # supervisor can tell a wedged worker from a healthy gang — a single
    # shared file would let any one live process mask a hung peer.
    heartbeat = (heartbeat_path_for(cfg.run.heartbeat_file,
                                    jax.process_index())
                 if cfg.run.heartbeat_file else None)

    def _beat(status: str, rnd: int) -> None:
        """Liveness heartbeat (atomic rewrite, one file per process): the
        supervisor's --hang-timeout reads its mtime."""
        if heartbeat:
            write_heartbeat(heartbeat, status=status, round=rnd,
                            restarts=restart_count)

    _beat("starting", 0)

    # Elastic live reshard (fedtpu.resilience.reshard; docs/resilience.md
    # "Elastic resharding"): a preemption NOTICE — SIGUSR1/SIGUSR2
    # forwarded by the gang supervisor, or a preempt_notice/preempt_cancel
    # fault-plan entry — resizes the gang at a round boundary instead of
    # tearing it down. 1-D engines only; the lockstep protocol needs
    # width-1 chunks and the synchronous stop path, so a SIGNAL under any
    # other config degrades to the ordinary SIGTERM drain in the loop (a
    # PLAN entry under such a config is a startup error instead — the plan
    # promised an exact-round reshard the config cannot deliver).
    reshard_ctl = None
    reshard_stack: List[dict] = []     # pre-shrink bindings, for grow-back
    ckpt_group = None                  # surviving processes after a shrink
    reshard_live = (max(1, cfg.run.rounds_per_step) == 1
                    and not cfg.run.pipelined_stop and not cfg.run.mpmd)
    if cfg.run.model_parallel == 1:
        from fedtpu.resilience.distributed import ENV_LAUNCH_ID
        from fedtpu.resilience.reshard import (ReshardController,
                                               ReshardFailed)
        reshard_ctl = ReshardController(
            plan=injector.plan if injector is not None else None,
            process_index=jax.process_index(),
            process_count=jax.process_count(),
            launch_id=os.environ.get(ENV_LAUNCH_ID) or None,
            restart_count=restart_count,
            checkpoint_dir=cfg.run.checkpoint_dir or None,
            ack_timeout=cfg.run.collective_timeout or 60.0,
            tracer=tracer, registry=registry,
            heartbeat=cfg.run.heartbeat_file or None)
        reshard_ctl.install_signal_handlers()
    if injector is not None:
        from fedtpu.resilience.faults import RESHARD_KINDS
        if any(f.kind in RESHARD_KINDS for f in injector.plan.faults):
            if reshard_ctl is None:
                raise ValueError("preempt_notice/preempt_cancel faults "
                                 "require the 1-D engines "
                                 "(model_parallel=1)")
            if not reshard_live:
                raise ValueError("preempt_notice/preempt_cancel faults "
                                 "require rounds_per_step=1 and "
                                 "pipelined_stop off: the reshard fires at "
                                 "an exact round boundary")
            if multiproc and not cfg.run.checkpoint_dir:
                raise ValueError("multi-process elastic reshard needs "
                                 "checkpoint_dir: the commit barrier and "
                                 "grow spool live under "
                                 "<checkpoint_dir>/.reshard")

    # Collective watchdog: armed only around the loop's BLOCKING windows
    # (warm round dispatch, chunk metric fetch, held-out-eval fetch,
    # collective checkpoint save) — the FIRST dispatch at each chunk
    # width is excluded, so compile time never counts against the
    # timeout. Fires from any process (non-io processes append the
    # collective_hang event to the sink directly) and turns the hang
    # into exit 75, which the gang supervisor answers with a gang
    # restart. See fedtpu.resilience.distributed.
    watchdog = None
    if cfg.run.collective_timeout:
        watchdog = CollectiveWatchdog(
            cfg.run.collective_timeout, events_path=tel.events_path,
            process_index=jax.process_index(), heartbeat=heartbeat,
            restart_count=restart_count).start()
        _guard = watchdog.guard
    else:
        from contextlib import nullcontext
        _guard = lambda phase, rnd=None: nullcontext()

    # Overlap compile (fedtpu.compilation): the rounds_per_step-wide chunk
    # program builds on a background thread — from abstract avals, through
    # the serialized-executable ProgramCache when a cache dir is set — while
    # R=1 warmup rounds already train. The same math (R width-1 chunks
    # compute what one R-wide chunk computes; bitwise so on the CPU backend,
    # to the last bit or two on the TPU); dispatch blocks only if the
    # executable isn't ready when it is finally needed.
    overlap_exec = None
    overlap_cache = None
    overlap_key = None
    overlap_chunk = max(1, cfg.run.rounds_per_step)
    if (cfg.run.overlap_compile and overlap_chunk > 1
            and cfg.fed.rounds > 1):
        from fedtpu.compilation import (CompileExecutor, ProgramCache,
                                        program_cache_dir,
                                        program_config_slice,
                                        program_fingerprint)
        _wide_step = exp.make_step(overlap_chunk)
        _abstract = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=a.sharding),
            (state, batch))
        overlap_key = program_fingerprint(
            "round", config=program_config_slice(cfg), mesh=exp.mesh,
            args=_abstract, extra={"rounds_per_step": overlap_chunk})
        if cfg.run.compilation_cache:
            overlap_cache = ProgramCache(
                program_cache_dir(cfg.run.compilation_cache),
                tracer=tracer, registry=registry)
        overlap_exec = CompileExecutor(tracer=tracer, registry=registry)

        def _build_wide(step=_wide_step, avals=_abstract):
            if overlap_cache is not None:
                return overlap_cache.get_or_compile(
                    overlap_key, step, *avals,
                    label=f"round[w={overlap_chunk}]").compiled
            return step.lower(*avals).compile()

        overlap_exec.submit(overlap_key, _build_wide,
                            label=f"round[w={overlap_chunk}]")

    # Only where a sink will hold it: the audit and the cost model below
    # trace the round program twice more, seconds of every job's start for
    # a program of the language model's size, and the NullTracer drops the
    # event.
    if tel.manifest and tracer.enabled:
        manifest_extra = {"program": "run",
                          "engine": ("stateless"
                                     if cfg.fed.client_state == "stateless"
                                     else "async" if cfg.fed.async_mode
                                     else "tp2d" if cfg.run.model_parallel > 1
                                     else "mpmd" if cfg.run.mpmd
                                     else "sync1d"),
                          # Resilience attribution: which restart of a
                          # supervised run wrote this sink, under which
                          # exact fault schedule (digest of the
                          # MATERIALIZED plan, probabilistic entries
                          # already expanded).
                          "restarts": restart_count,
                          "data": data_info}
        if injector is not None:
            manifest_extra["fault_plan"] = injector.plan.digest
        if overlap_key is not None:
            # Cache directory + hit/miss state for the run's main program
            # (peek: no deserialization at manifest time).
            manifest_extra["program_cache"] = {
                "key": overlap_key,
                "dir": overlap_cache.cache_dir if overlap_cache else None,
                "cached": bool(overlap_cache
                               and overlap_cache.peek(overlap_key)),
            }
        try:
            # Trace-only program audit of the canonical width-1 round:
            # schedule digest + per-round comm bytes pin WHAT this run
            # communicated (docs/analysis.md "Program audit"); no compile,
            # no donation proof here — 'fedtpu audit' carries the proofs.
            from fedtpu.analysis.program import (audit_step_summary,
                                                 engine_audit_spec)
            manifest_extra["audit"] = dict(
                audit_step_summary(exp.make_step(1), (state, batch)),
                engine=engine_audit_spec(cfg)["engine"])
            if cfg.run.mpmd:
                # Under mpmd the summary above still audits the
                # monolithic ORACLE program (the parity reference); the
                # per-sub-program contracts live in the committed
                # `fedtpu audit --engines mpmd_*` goldens.
                from fedtpu.orchestration.mpmd import AUDIT_SPECS
                manifest_extra["audit"]["audited_program"] = \
                    "monolithic_oracle"
                manifest_extra["mpmd"] = {
                    "sub_programs": sorted(AUDIT_SPECS),
                    "width": max(1, cfg.run.rounds_per_step),
                    "server_mesh_devices": 1,
                }
        except Exception as exc:
            # The audit is diagnostic metadata; a trace failure must not
            # take down the run it describes.
            manifest_extra["audit"] = {"error": str(exc)}
        try:
            # Device-time attribution (docs/observability.md): XLA's own
            # cost model for the canonical width-1 round. `fedtpu report`
            # joins these static counts with the measured chunk span
            # durations into per-round MFU / roofline rows. Trace+lower
            # only — no compile — so the manifest stays cheap and
            # deterministic.
            costs = exp.make_step(1).lower(state, batch).cost_analysis()
            profile: dict = {
                "flops_per_round": float(costs.get("flops", 0.0)),
                "bytes_per_round": float(costs.get("bytes accessed", 0.0)),
                "profile_rounds": int(cfg.run.profile_rounds),
            }
            peak_env = os.environ.get("FEDTPU_PEAK_FLOPS")
            if peak_env:
                # Hardware peak for MFU denominators, as the caller states
                # it (a peak table keyed by device_kind is the benchmark's).
                profile["peak_flops"] = float(peak_env)
            manifest_extra["profile"] = profile
        except Exception as exc:
            manifest_extra["profile"] = {"error": str(exc)}
        tracer.event("manifest", **build_manifest(
            cfg=cfg, mesh=exp.mesh, extra=manifest_extra))
    # Estimated exchange volume per round: every client ships one model's
    # worth of floats through the aggregation (and receives the average
    # back); int8 compression quarters the f32 payload. An estimate of the
    # logical exchange, not a wire measurement — psum's actual traffic is
    # XLA-scheduled.
    # the shared-global engine's params carry no clients axis
    lead = 0 if cfg.fed.client_state == "stateless" else 1
    model_bytes = sum(int(np.prod(l.shape[lead:]) or 1) * l.dtype.itemsize
                      for l in jax.tree.leaves(state["params"]))
    registry.gauge("exchange_bytes_per_round_est").set(
        model_bytes * cfg.shard.num_clients
        // (4 if cfg.fed.compress == "int8" else 1))

    if multiproc:
        from fedtpu.parallel.mesh import replicated_sharding
        from fedtpu.utils.trees import identity
        # Module-level `identity` (not a lambda) so repeated run_experiment
        # calls in one process hit the jit cache instead of retracing.
        _rep = jax.jit(identity,
                       out_shardings=replicated_sharding(exp.mesh))
    else:
        _rep = lambda t: t

    start_round = 0
    restored_history = None
    restored_meta = None
    if (not resume and cfg.run.checkpoint_dir and cfg.run.checkpoint_every
            and complete_steps(cfg.run.checkpoint_dir)):
        # A FRESH run into a directory already holding rounds is almost
        # always a mistake, and actively dangerous: a later --resume (or
        # crash-resume) would restore the STALE higher-numbered round
        # over this run's work, and retention would treat the stale
        # rounds as this run's newest and GC the fresh ones (review r4).
        # Deleting another run's checkpoints uninvited would be worse —
        # refuse with the two honest options instead.
        raise ValueError(
            f"checkpoint dir {cfg.run.checkpoint_dir!r} already holds "
            f"round checkpoints (latest: "
            f"{complete_steps(cfg.run.checkpoint_dir)[-1]}). Pass "
            "resume=True (--resume) to continue that run, or point "
            "checkpoint_dir at a clean directory.")
    if resume and cfg.run.checkpoint_dir:
        from fedtpu.orchestration.checkpoint import (
            latest_step, load_checkpoint_fallback, load_checkpoint_raw,
            load_meta, saved_num_clients)
        agreed_step = None
        local_latest = latest_step(cfg.run.checkpoint_dir)
        if multiproc:
            # Cross-host checkpoint agreement: a worker that died mid-save
            # (or a filesystem syncing unevenly) can leave processes seeing
            # DIFFERENT latest complete rounds — restoring each process's
            # own latest would silently desync the gang. Exchange the
            # locally-visible latest step and restore the minimum common
            # one; when any process sees none, ALL start fresh together.
            from fedtpu.resilience.distributed import (ENV_LAUNCH_ID,
                                                       NO_CHECKPOINT,
                                                       agree_resume_step)
            launch_id = os.environ.get(ENV_LAUNCH_ID) or None
            if launch_id is None:
                # Manual multi-host launch (no gang parent): the
                # generation tag must still be launch-unique, or a
                # leftover .agreement file from a previous launch —
                # which also had FEDTPU_RESTARTS == 0 — could hand one
                # process a stale step while a peer reads the fresh
                # one: the split-brain restore the agreement exists to
                # prevent. Process 0's nonce, broadcast once, is the
                # gang-wide launch identity.
                from jax.experimental import multihost_utils
                nonce = np.frombuffer(os.urandom(4), np.uint32)[0]
                with _guard("resume_agreement"):
                    shared = multihost_utils.broadcast_one_to_all(
                        np.asarray(nonce, np.uint32))
                launch_id = f"bcast:{int(shared):08x}"
            agreed_step = agree_resume_step(
                cfg.run.checkpoint_dir, jax.process_index(),
                jax.process_count(), local_latest,
                restart_count=restart_count, launch_id=launch_id)
            if agreed_step == NO_CHECKPOINT:
                log.info("Resume agreement: no complete checkpoint common "
                         "to the whole gang; starting fresh consensually.")
                agreed_step = None
                local_latest = None
            elif agreed_step != local_latest:
                log.info(f"Resume agreement: restoring round {agreed_step}"
                         f" (local latest: {local_latest}) — the newest "
                         "step every process can see.")
        if local_latest is not None:
            # ONE meta read serves elastic detection AND the DP RDP-curve
            # restore below; only a count MISMATCH (or a pre-num_clients
            # checkpoint) pays the raw state read.
            restored_meta = load_meta(cfg.run.checkpoint_dir,
                                      step=agreed_step)
            # Engine kind gate FIRST, from the meta item alone: a
            # cross-engine resume at the SAME client count used to sail
            # past the count comparison into the template restore, where
            # orbax killed it with an opaque tree-structure diff. The
            # saved flag (engine_async, written by save_checkpoint) names
            # the real problem before any state is read; checkpoints from
            # before the flag existed fall through to the structural
            # check in the elastic path below.
            saved_async = restored_meta.get("engine_async")
            if saved_async is not None:
                saved_async = bool(int(np.asarray(saved_async)))
                if saved_async != ("anchors" in state):
                    raise ValueError(
                        "resume engine mismatch: the checkpoint was "
                        f"written by the "
                        f"{'async' if saved_async else 'synchronous'} "
                        "engine but the current config selects the "
                        "other; resume with the matching engine, or "
                        "warm-start a fresh run from exported weights")
            nc = restored_meta.get("num_clients")
            saved_c = None if nc is None else int(np.asarray(nc))
            if saved_c is None:
                raw, raw_history, raw_round = load_checkpoint_raw(
                    cfg.run.checkpoint_dir, step=agreed_step)
                saved_c = saved_num_clients(raw)
            elif saved_c != cfg.shard.num_clients:
                raw, raw_history, raw_round = load_checkpoint_raw(
                    cfg.run.checkpoint_dir, step=agreed_step)
            if saved_c == cfg.shard.num_clients:
                # Per-leaf shardings come from the live state template, so
                # the 2-D engine's tensor-parallel layout survives resume.
                # Fallback restore: corrupt-on-disk rounds pass the commit
                # check but fail to load — walk back to the newest round
                # that actually restores instead of stranding the run.
                state, restored_history, start_round = \
                    load_checkpoint_fallback(cfg.run.checkpoint_dir,
                                             state_like=state,
                                             max_step=agreed_step)
                if start_round != int(np.asarray(restored_meta["step"])):
                    # The ledger (DP RDP curve) must come from the round
                    # actually restored, not the corrupt latest.
                    restored_meta = load_meta(cfg.run.checkpoint_dir,
                                              step=start_round)
                log.info(f"Resumed from checkpoint at round {start_round}.")
            else:
                from fedtpu.parallel.multihost import safe_put
                if ("anchors" in state) != ("anchors" in raw):
                    # Engine mismatch either way: async state is NOT
                    # post-averaging (slots hold distinct local models),
                    # so a sync resume of an async checkpoint would
                    # mean-collapse models nobody trained, and an async
                    # resume of a sync checkpoint has no pull/anchor
                    # history to restore.
                    raise ValueError(
                        "elastic resume engine mismatch: the checkpoint "
                        f"was written by the "
                        f"{'async' if 'anchors' in raw else 'synchronous'}"
                        " engine but the current config selects the other"
                        "; resume with the matching engine (and client "
                        f"count {saved_c}), or warm-start a fresh run "
                        "from exported weights")
                if "anchors" in state:
                    # ASYNC elastic resume: a restart IS every client
                    # re-pulling the current global — which lives in the
                    # FRESHEST anchor, not a mean over slots (slots hold
                    # distinct per-client local models). New cohort:
                    # params = anchors = that global, pull ticks at the
                    # resume tick (staleness restarts at 0), fresh Adam
                    # moments, and any PENDING K-buffer contributions are
                    # dropped (their deltas reference anchors of a cohort
                    # that no longer exists) — said out loud below.
                    from fedtpu.parallel.async_fed import \
                        async_global_params
                    # The engine's own freshest-anchor rule (ONE
                    # definition); works on the raw numpy tree at the
                    # saved client count.
                    g = jax.tree.map(np.asarray, async_global_params(raw))
                    state["params"] = _bcast_into_slots(g, state["params"])
                    state["anchors"] = _bcast_into_slots(g,
                                                         state["anchors"])
                    state["pull_tick"] = safe_put(
                        np.full(cfg.shard.num_clients, raw_round, np.int32),
                        state["pull_tick"].sharding)
                    state["round"] = jnp.asarray(raw_round, jnp.int32)
                    dropped = float(np.asarray(raw.get("buf_count", 0.0)))
                    restored_history, start_round = raw_history, raw_round
                    buf_note = (f", {int(dropped)} pending buffered "
                                "updates dropped" if dropped > 0 else "")
                    log.info(f"Async elastic resume at tick {raw_round}: "
                             f"{saved_c} -> {cfg.shard.num_clients} "
                             "clients (freshest-anchor global carried "
                             "over, every client re-pulled, fresh "
                             f"optimizer state{buf_note}).")
                else:
                    # SYNC ELASTIC resume — the cluster grew or shrank
                    # (the reference cannot do this at all: client count
                    # is baked into `mpirun -np N`). Periodic checkpoints
                    # hold a post-averaging state, so every client slot is
                    # the same global model: collapse to the global (mean
                    # over slots == slot 0), re-broadcast over the NEW
                    # client count, and restore the client-count-
                    # independent server-optimizer state as-is. Per-client
                    # Adam moments cannot be re-shaped meaningfully across
                    # counts — they restart fresh (the same state a client
                    # joining a federation starts with).
                    g = jax.tree.map(lambda a: np.asarray(a).mean(axis=0),
                                     raw["params"])
                    state["params"] = _bcast_into_slots(g, state["params"])
                    if ("server_opt_state" in raw
                            and "server_opt_state" in state):
                        state["server_opt_state"] = jax.tree.map(
                            lambda live, rawv: safe_put(
                                np.asarray(rawv), live.sharding),
                            state["server_opt_state"],
                            raw["server_opt_state"])
                    if "dp_clip" in raw and "dp_clip" in state:
                        # The adaptive clip is client-count-independent
                        # server state — carry it like the server
                        # optimizer state.
                        state["dp_clip"] = safe_put(
                            np.asarray(raw["dp_clip"]),
                            state["dp_clip"].sharding)
                    state["round"] = jnp.asarray(raw_round, jnp.int32)
                    restored_history, start_round = raw_history, raw_round
                    # Per-client SCAFFOLD variates are client-count-
                    # shaped like the Adam moments: an elastic resume
                    # restarts them at zero (invariant-consistent; the
                    # correction re-warms over the next rounds) — say
                    # so, or a drift study across a resume sees an
                    # unexplained regression.
                    cv_note = (", control variates reset to zero"
                               if "client_cv" in state else "")
                    log.info(f"Elastic resume at round {raw_round}: "
                             f"{saved_num_clients(raw)} -> "
                             f"{cfg.shard.num_clients} clients (global "
                             "model carried over, fresh client optimizer "
                             f"state{cv_note}).")
        if multiproc:
            # The agreement bounds the restore step, but the restore
            # itself is per-process: load_checkpoint_fallback walks back
            # past rounds that fail to LOAD locally, so an agreed step
            # that is unreadable (or not yet synced) on one host leaves
            # that host on an OLDER round than its peers — the desync
            # the agreement exists to rule out. Cross-check the round
            # each process ACTUALLY restored and fail loudly on
            # mismatch: the gang supervisor turns the crash into a
            # clean gang restart, whereas proceeding would silently
            # corrupt the federation.
            from jax.experimental import multihost_utils
            with _guard("resume_verify"):
                gang_rounds = np.asarray(multihost_utils.process_allgather(
                    np.asarray(start_round, np.int32)))
            if int(gang_rounds.min()) != int(gang_rounds.max()):
                raise RuntimeError(
                    "post-restore desync: the gang restored different "
                    f"rounds {gang_rounds.tolist()} (agreed step: "
                    f"{agreed_step}) — the agreed checkpoint loaded on "
                    "some hosts but not others; refusing to train "
                    "desynced")

    if restored_history is not None:
        tracer.event("resume", round=start_round)

    # DP RDP bookkeeping lives in its own module (fedtpu.orchestration.
    # privacy): the cumulative per-order RDP curve is the resumable
    # currency of the privacy spend, persisted in every checkpoint's meta
    # item UNCONDITIONALLY (a zero curve while DP is off) so a DP-off
    # resume segment carries the earlier segments' spend forward.
    ledger = PrivacyLedger(cfg.fed, start_round=start_round,
                           restored_meta=restored_meta)

    history = {k: [] for k in names}
    pooled_hist = {k: [] for k in names}
    per_client_hist = {k: [] for k in names}
    test_hist = {k: [] for k in names}
    staleness_hist: List[np.ndarray] = []
    losses: List[np.ndarray] = []
    sec_per_round: List[float] = []
    timer = Timer().start()

    prev_metric = None
    termination_count = cfg.fed.termination_patience
    stopped_early = False
    diverged = False
    rounds_run = 0

    def _checked_state() -> dict:
        return {k: state[k] for k in
                ("params", "opt_state", "server_opt_state",
                 "client_cv", "server_cv", "dp_clip", "anchors")
                if k in state}

    def state_poisoned(rnd: int, take: Optional[int] = None) -> bool:
        """The full poisoned-state predicate shared by the in-loop and
        loop-exit gates: any non-finite leaf in params, client optimizer
        moments, or server optimizer state. Reads the CURRENT ``state``
        binding (one definition — the two gates can't drift apart).
        ``rnd`` / ``take`` label its ``state_check`` phase (a second
        dispatch and fetch a chunk), which closes on the ``bool()``."""
        with phase(STATE_CHECK, rnd, rounds=take):
            return not bool(_tree_finite(_checked_state()))

    def halt_diverged(reason: str, label_round: int):
        """Shared divergence halt: quarantine the poisoned state under
        diverged/ (so latest_step() — and therefore resume — still finds the
        last GOOD periodic checkpoint) and stop the loop. ``label_round`` is
        the round the CURRENT ``state`` corresponds to — under chunking the
        chunk-end state; in pipelined mode possibly one chunk past the
        divergent metrics (callers pass ``state_round``), so the quarantine
        label always matches the saved state even when the history ends at
        the earlier divergent round."""
        nonlocal stopped_early, diverged
        log.warning(f"Non-finite {reason}; halting (diverged run).")
        tracer.event("diverged", round=label_round, reason=reason)
        if cfg.run.checkpoint_dir:
            # All processes reach here together (the decision derives from
            # replicated metrics/state) and all must call the save — orbax
            # barriers internally (see save_checkpoint).
            save_checkpoint(
                os.path.join(cfg.run.checkpoint_dir, "diverged"),
                state, history, label_round,
                extra_meta=ledger.checkpoint_meta(label_round),
                process_group=ckpt_group)
        stopped_early = True
        diverged = True

    # --- Divergence rollback (cfg.run.on_divergence == 'rollback') ----
    # The retry budget is per RUN (not per incident): a run that keeps
    # diverging must eventually halt, and a single monotone counter is
    # the property the supervisor/report can reason about.
    rollback = {"attempts": 0, "resume_at": None}
    excluded: set = set()

    def _offending_clients(m, loss_row) -> tuple:
        """Clients with a non-finite loss or per-client metric this
        round — the rollback_exclude candidates."""
        bad = ~np.isfinite(np.asarray(loss_row))
        for k in names:
            bad = bad | ~np.isfinite(np.asarray(m["per_client"][k]))
        return tuple(int(c) for c in np.nonzero(bad)[0])

    def try_rollback(reason: str, label_round: int, offenders=()) -> bool:
        """Restore the newest loadable checkpoint, truncate every history
        to it, optionally exclude the offending clients, and tell the
        loop to re-enter at the restored round. Returns False — caller
        halts as before — when the policy is off, the retry budget is
        spent, or nothing restores. The first retry is a PURE replay
        (transient faults recover bitwise — round-keyed randomness makes
        the replayed rounds identical); from the second on, params are
        perturbed by rollback_perturb to move off a deterministic
        re-divergence."""
        nonlocal state, prev_metric, termination_count, rounds_run
        if cfg.run.on_divergence != "rollback":
            return False
        if rollback["attempts"] >= cfg.run.rollback_retries:
            log.warning("Rollback budget exhausted "
                        f"({cfg.run.rollback_retries}); halting.")
            return False
        from fedtpu.orchestration.checkpoint import load_checkpoint_fallback
        try:
            state2, hist2, j = load_checkpoint_fallback(
                cfg.run.checkpoint_dir, state_like=state)
        except FileNotFoundError:
            return False
        rollback["attempts"] += 1
        state = state2
        # The divergent rounds' entries were appended BEFORE the guard
        # fired: the client-mean history comes back from the checkpoint
        # (authoritative through round j); the in-memory-only histories
        # drop exactly the rounds past j they hold.
        drop = max(0, rounds_run - j)
        for k in names:
            history[k] = list(hist2.get(k, []))
            _drop_tail(pooled_hist[k], drop)
            _drop_tail(per_client_hist[k], drop)
        _drop_tail(losses, drop)
        _drop_tail(sec_per_round, drop)
        _drop_tail(staleness_hist, drop)
        if cfg.run.eval_test_every:
            edrop = sum(1 for rr in range(j + 1, rounds_run + 1)
                        if rr % cfg.run.eval_test_every == 0)
            for k in names:
                _drop_tail(test_hist[k], edrop)
        rounds_run = j
        prev_metric = ([history[k][-1] for k in names]
                       if history[names[0]] else None)
        termination_count = cfg.fed.termination_patience
        if cfg.run.rollback_exclude and offenders:
            fresh = sorted(set(offenders) - excluded)
            if fresh:
                excluded.update(fresh)
                from fedtpu.resilience.faults import drop_clients
                batch["mask"] = drop_clients(batch["mask"], fresh)
                if injector is not None:
                    # A departed client cannot re-inject: drop its
                    # still-armed faults, or a sticky NaN source would
                    # defeat the retry (NaN*0 still poisons a psum).
                    injector.exclude(fresh)
                tracer.event("exclusion", round=j, clients=list(fresh))
                registry.counter("clients_excluded").inc(len(fresh))
                log.warning(f"Excluding diverging client(s) {fresh} from "
                            "aggregation (mask weight 0) for the retry.")
        if rollback["attempts"] >= 2 and cfg.run.rollback_perturb > 0:
            from fedtpu.resilience.faults import perturb_params
            state["params"] = perturb_params(state["params"],
                                             rollback["attempts"],
                                             cfg.run.rollback_perturb)
        tracer.event("rollback", round=label_round, restored_round=j,
                     attempt=rollback["attempts"], reason=reason,
                     excluded=sorted(excluded))
        registry.counter("rollbacks").inc()
        log.warning(f"Non-finite {reason}; rolled back to round {j} "
                    f"(attempt {rollback['attempts']}/"
                    f"{cfg.run.rollback_retries}).")
        timer.lap()        # restore time must not pollute sec/round
        rollback["resume_at"] = j
        return True

    if restored_history is not None:
        for k in names:
            history[k] = list(restored_history.get(k, []))
        if history[names[0]]:
            prev_metric = [history[k][-1] for k in names]
        rounds_run = start_round

    # Checkpoint retention (RunConfig.keep_checkpoints > 0): after every
    # periodic save, keep only the k newest complete rounds plus the
    # best-client-mean-accuracy round. ``best_saved`` tracks (accuracy,
    # step) over the checkpoints THIS run wrote; on resume it re-seeds
    # from the rounds still on disk and the restored history, so a
    # resumed run never GCs a better pre-resume round. Derived from
    # replicated metrics, so it is identical on every process; only
    # io_proc deletes (orbax save has barriered by then, so every round
    # being deleted is fully committed).
    best_saved = None
    if (cfg.run.keep_checkpoints > 0 and cfg.run.checkpoint_dir
            and restored_history is not None):
        acc_hist = history["accuracy"]
        for s in complete_steps(cfg.run.checkpoint_dir):
            if 0 < s <= len(acc_hist) and (best_saved is None
                                           or acc_hist[s - 1] > best_saved[0]):
                best_saved = (acc_hist[s - 1], s)

    def retain_after_save(step: int) -> None:
        nonlocal best_saved
        if cfg.run.keep_checkpoints <= 0:
            return
        acc = history["accuracy"][-1] if history["accuracy"] else -math.inf
        if best_saved is None or acc > best_saved[0]:
            best_saved = (acc, step)
        if io_proc:
            retain_checkpoints(cfg.run.checkpoint_dir,
                               cfg.run.keep_checkpoints,
                               protect=(best_saved[1],))

    if (cfg.run.on_divergence == "rollback"
            and not complete_steps(cfg.run.checkpoint_dir)):
        # Rollback's worst case — divergence before the first periodic
        # save — still needs a restore point: persist the initial state
        # as round `start_round` (0 for a fresh run). Collective: every
        # process calls (the condition is deterministic).
        save_checkpoint(cfg.run.checkpoint_dir, state, history, start_round,
                        extra_meta=ledger.checkpoint_meta(start_round))

    ckpt_every = cfg.run.checkpoint_every
    chunk = max(1, cfg.run.rounds_per_step)
    step_fns: Dict[int, Callable] = {}

    # MPMD sub-program cache: same directory layout as overlap_compile's
    # (the <cache>/programs store), so a warmed cache serves both paths.
    mpmd_cache = None
    if cfg.run.mpmd and cfg.run.compilation_cache:
        from fedtpu.compilation import ProgramCache, program_cache_dir
        mpmd_cache = ProgramCache(
            program_cache_dir(cfg.run.compilation_cache),
            tracer=tracer, registry=registry)

    def get_step(r: int) -> Callable:
        if r not in step_fns:
            if cfg.run.mpmd:
                # The DAG of AOT sub-programs; compiles (or loads from
                # the cache) every sub-program at this width up front.
                from fedtpu.orchestration.mpmd import build_mpmd_step
                step_fns[r] = build_mpmd_step(
                    cfg, mesh=exp.mesh, apply_fn=exp.apply_fn, tx=exp.tx,
                    num_classes=exp.num_classes, state=state, batch=batch,
                    width=r, cache=mpmd_cache, tracer=tracer)
            else:
                step_fns[r] = _compiled_ahead(exp.make_step(r))
        return step_fns[r]

    jsonl = (open(cfg.run.metrics_jsonl, "a")
             if cfg.run.metrics_jsonl and io_proc else None)
    # Windowed device profiling (--profile-rounds K, K > 0): the
    # jax.profiler capture is deferred until the FIRST chunk's metrics
    # land on host — compile and warmup never pollute the window — and
    # stops once K steady-state rounds are covered (chunk granularity:
    # the window closes at the first chunk boundary at or past K).
    # K == 0 keeps the historical whole-run trace.
    prof_win = {"on": False, "start_round": 0,
                "pending": bool(cfg.run.profile_dir
                                and cfg.run.profile_rounds > 0)}
    if cfg.run.profile_dir and cfg.run.profile_rounds <= 0:
        # Tracing subsystem the reference lacks entirely (SURVEY.md §5):
        # capture a device profile of the round loop for xprof/tensorboard.
        jax.profiler.start_trace(cfg.run.profile_dir)
        prof_win["on"] = True

    def phase(name: str, rnd: int, rounds: Optional[int] = None,
              guard: Optional[str] = None):
        """One phase of a round where its work happens, on every clock
        that is on (docs/observability.md "Phases of a round"): the sink
        span ``name``, the annotation ``fedtpu.<name>`` while the profiler
        window is open (sink on or not), and the watchdog window ``guard``.
        With all three off this is the NullTracer's span and nothing else:
        no profiler object is made."""
        span = (tracer.span(name, round=rnd) if rounds is None
                else tracer.span(name, round=rnd, rounds=rounds))
        if not prof_win["on"] and (watchdog is None or guard is None):
            return span
        return Phase(
            span,
            jax.profiler.TraceAnnotation(f"fedtpu.{name}", round=rnd)
            if prof_win["on"] else None,
            watchdog.guard(guard, rnd)
            if watchdog is not None and guard else None)

    epilogue = contextlib.ExitStack()
    # The ``fedtpu.chunk`` step annotations open now: one from a chunk's
    # dispatch to its fetch, two while a pipelined chunk is in flight.
    open_steps: list = []

    def close_step(ann) -> None:
        if ann in open_steps:
            open_steps.remove(ann)
            ann.__exit__(None, None, None)

    # try/finally so a mid-run failure (OOM, Ctrl-C, I/O error) still
    # finalizes the profiler trace and closes the jsonl handle — the trace
    # exists precisely to diagnose such runs.
    try:
        def process_chunk(rnd0, take, metrics, step_ann=None,
                          state_round=None):
            """Host-side consumption of one chunk's metrics: history, logs,
            JSONL, divergence guard, early stopping. Fetches the metrics —
            the completion proof AND (in pipelined mode) the point where
            the host finally waits on this chunk. ``step_ann``: the chunk's
            open ``fedtpu.chunk`` step annotation, closed on the fetch.
            ``state_round``: the round
            the loop's CURRENT ``state`` corresponds to (in pipelined mode
            one chunk past this chunk's metrics) — used to label a
            divergence quarantine honestly."""
            if state_round is None:
                state_round = rnd0 + take
            nonlocal prev_metric, termination_count, stopped_early
            nonlocal rounds_run
            # ONE batched device->host transfer for the whole chunk's
            # metrics: the per-round float()/np.asarray conversions below
            # would otherwise each pay a serialized transfer round-trip
            # (~13 per round). Issue every leaf's transfer async first,
            # then materialize — holding the values on the host is also
            # the completion proof that closes the lap time.
            # Multi-process: replicate first (collective, every process) so
            # the client-sharded leaves become host-addressable everywhere.
            with phase("chunk_fetch", rnd0 + take, rounds=take,
                       guard="chunk_fetch"):
                metrics = _rep(metrics)
                for leaf in jax.tree.leaves(metrics):
                    if hasattr(leaf, "copy_to_host_async"):
                        leaf.copy_to_host_async()
                metrics = jax.tree.map(np.asarray, metrics)
            close_step(step_ann)
            per_round = _unstack_metrics(metrics, take)
            dt = timer.lap() / take
            # The chunk span closes HERE, on the np.asarray materialization
            # above: the host holds the chunk's metrics, so its device work
            # has finished.
            tracer.event("span", phase="chunk", round=rnd0 + take,
                         dur_s=dt * take, rounds=take)
            if on_chunk is not None:
                on_chunk(rnd0 + take, take)
            # Windowed profiler control: arm after the first chunk's fetch
            # (the completion proof that compile is behind us), disarm at
            # the first chunk boundary covering >= profile_rounds rounds —
            # the fetch above already proved the window's device work
            # finished, so stop_trace here loses nothing.
            if prof_win["pending"]:
                prof_win["pending"] = False
                prof_win["on"] = True
                prof_win["start_round"] = rnd0 + take
                jax.profiler.start_trace(cfg.run.profile_dir)
                tracer.event("profile_window", phase="start",
                             round=rnd0 + take,
                             rounds=int(cfg.run.profile_rounds))
            elif (prof_win["on"] and cfg.run.profile_rounds > 0
                    and rnd0 + take - prof_win["start_round"]
                    >= cfg.run.profile_rounds):
                jax.profiler.stop_trace()
                prof_win["on"] = False
                tracer.event("profile_window", phase="stop",
                             round=rnd0 + take,
                             rounds=rnd0 + take - prof_win["start_round"])
            # Host-side decision window (history/log/early-stop); every
            # exit of the loop below leaves the with-block and closes it.
            with phase("stop_check", rnd0 + take, rounds=take):
                for j, m in enumerate(per_round):
                    r = rnd0 + j
                    client_mean = {k: float(v) for k, v in m["client_mean"].items()}
                    per_client = {k: np.asarray(v) for k, v in m["per_client"].items()}
                    losses.append(np.asarray(m["loss"]))
                    sec_per_round.append(dt)
                    rounds_run = r + 1
                    loss_mean = float(np.mean(losses[-1]))

                    for k in names:
                        history[k].append(client_mean[k])
                        pooled_hist[k].append(float(m["pooled"][k]))
                        per_client_hist[k].append(per_client[k])
                    if "staleness" in m:        # async engine's extra metric
                        staleness_hist.append(np.asarray(m["staleness"]))
                    extra = {}
                    for name, value in m.get("counters", {}).items():
                        # the task's own counters (next_token: expert load,
                        # tokens): scalars to the registry, vectors to the
                        # round's event
                        if np.ndim(value):
                            extra[name] = np.asarray(value).tolist()
                        elif name in exp.task.gauges:
                            registry.gauge(name).set(float(value))
                        else:
                            registry.counter(name).inc(int(value))

                    registry.counter("rounds").inc()
                    tracer.event("round", round=r + 1, dur_s=dt,
                                 accuracy=client_mean["accuracy"],
                                 loss_mean=loss_mean, **extra,
                                 **({"staleness_mean":
                                     float(staleness_hist[-1].mean()),
                                     "staleness_max":
                                     float(staleness_hist[-1].max())}
                                    if "staleness" in m else {}))
                    if "staleness" in m:
                        from fedtpu.parallel.async_fed import \
                            record_tick_telemetry
                        record_tick_telemetry(registry, tracer, r + 1,
                                              staleness_hist[-1])

                    if jsonl is not None:
                        jsonl.write(json.dumps({
                            "round": r + 1, "sec_per_round": dt,
                            "client_mean": client_mean,
                            "pooled": {k: pooled_hist[k][-1] for k in names},
                            "loss_mean": loss_mean,
                            **({"staleness_mean":
                                float(staleness_hist[-1].mean())}
                               if "staleness" in m else {}),
                        }) + "\n")
                        jsonl.flush()

                    if verbose and (r % cfg.run.log_every == 0):
                        log.parity(f"\nRound {r + 1}:\n")
                        if cfg.run.log_per_client:
                            # Parity with the barrier-serialized rank-ordered prints
                            # (FL_CustomMLP...:151-162) — here just a loop, no barriers.
                            for c in range(cfg.shard.num_clients):
                                vals = ", ".join(f"{k}: {per_client[k][c]:.4f}"
                                                 for k in names)
                                log.parity(f"  CLIENT {c} - Local Metrics "
                                           f"(Round {r + 1}): [{vals}]")
                        gvals = ", ".join(f"{k}: {client_mean[k]:.4f}"
                                          for k in names)
                        stale_note = (f"  (mean staleness "
                                      f"{staleness_hist[-1].mean():.2f})"
                                      if "staleness" in m else "")
                        # parity, not info: the line is reference-shaped and
                        # must never grow a prefix; its timing suffix is what
                        # keeps it out of the byte-identity tests.
                        log.parity(f"  Global Metrics (Round {r + 1}): [{gvals}]  "
                                   f"({dt * 1e3:.1f} ms/round){stale_note}")

                    # Failure detection: a diverged step (NaN/inf loss or
                    # metrics) halts cleanly instead of burning the remaining
                    # rounds — with an emergency checkpoint of the last state.
                    cur = [client_mean[k] for k in names]
                    if cfg.run.halt_on_nonfinite and not (
                            np.all(np.isfinite(cur))
                            and np.all(np.isfinite(losses[-1]))):
                        # Rollback policy first (restores + truncates + sets
                        # resume_at; the while loop re-enters at the restored
                        # round); only when it declines does the run halt.
                        if not try_rollback(
                                f"loss/metrics at round {r + 1}", r + 1,
                                offenders=_offending_clients(m, losses[-1])):
                            halt_diverged(f"loss/metrics at round {r + 1}",
                                          state_round)
                        return

                    # Early stopping — exact reference logic (FL_CustomMLP...:181-192).
                    if prev_metric is not None and np.allclose(
                            cur, prev_metric, atol=cfg.fed.tolerance):
                        termination_count -= 1
                        if termination_count == 0:
                            log.parity("Early stopping triggered: No significant "
                                       "change in metrics for "
                                       f"{cfg.fed.termination_patience} rounds.")
                            if r + 1 < cfg.fed.rounds:
                                # The reference's break-iteration message
                                # (FL_CustomMLP...:135): its loop re-enters
                                # round r+1 (0-indexed == this r+1) and
                                # breaks before training; printed only when
                                # there IS a next round to break out of.
                                log.parity(f"Training stopped early at round "
                                           f"{r + 1}.")
                            tracer.event("early_stop", round=r + 1)
                            stopped_early = True
                            return
                    else:
                        prev_metric = cur
                        termination_count = cfg.fed.termination_patience

        # ---- Elastic live reshard (docs/resilience.md) ----------------
        def _reshard_join_fn(join_map, tick_round):
            """join_rows callback for reshard_state: global-model rows for
            params/anchors, the current round for pull_tick, zeros (fresh
            optimizer moments / control variates) for everything else —
            the same joiner semantics as elastic resume."""
            def jr(path, jidx, row_shape, dtype):
                if path in join_map:
                    v = np.asarray(join_map[path])
                    return np.broadcast_to(
                        v, (len(jidx),) + tuple(row_shape)).astype(dtype)
                if path == "['pull_tick']":
                    return np.full((len(jidx),) + tuple(row_shape),
                                   tick_round, dtype=dtype)
                return np.zeros((len(jidx),) + tuple(row_shape), dtype=dtype)
            return jr

        def _global_join_map():
            """Join values from the CURRENT global model: state paths under
            ['params'] and (async) ['anchors'] both join at the live
            global — a joining client starts from the freshest model, like
            an elastic-resume joiner."""
            g = to_numpy(_rep(exp.global_fn(state)))
            jm = {}
            for keys, leaf in jax.tree_util.tree_flatten_with_path(g)[0]:
                sub = jax.tree_util.keystr(keys)
                jm[f"['params']{sub}"] = np.asarray(leaf)
                if "anchors" in state:
                    jm[f"['anchors']{sub}"] = np.asarray(leaf)
            return jm

        def _victim_grow(rec):
            """The parked member's rejoin: rebuild its full-topology state
            from the survivors' spool (replicated values + join rows over
            its stale structure), re-sync the host-side control state
            (history, early-stop comparator, DP ledger), and continue at
            the grow round — its compiled executables and batch still
            target the original mesh, so nothing recompiles."""
            nonlocal state, prev_metric, termination_count, rounds_run
            nonlocal ledger
            from fedtpu.parallel.reshard import grow_row_map, reshard_state
            ctl = reshard_ctl
            seq = ctl.seq        # advanced past the shrink by committed()
            r_grow = int(rec["round"])
            src_C = int(rec["src_clients"])
            orig_C = cfg.shard.num_clients
            join_map, repl, control = ctl.read_spool(seq)
            ctl.event("reshard_begin", r_grow, mode="grow_rejoin",
                      victim=ctl.process_index, target=orig_C)
            _beat("resharding", r_grow)
            ctl.publish_ack(seq, "a", r_grow)
            participants = tuple(sorted(set(ctl.active)
                                        | {ctl.process_index}))
            ctl.await_acks(seq, "a", participants)
            new_state, steps = reshard_state(
                state, dst_mesh=exp.mesh, dst_clients=orig_C,
                row_map=grow_row_map(src_C, orig_C,
                                     int(rec["block_start"])),
                join_rows=_reshard_join_fn(join_map, r_grow),
                replicated_values=repl)
            ctl.publish_ack(seq, "b", r_grow)
            ctl.await_acks(seq, "b", participants)
            state = new_state
            ctl.committed("grow", ctl.process_index)
            for k in names:
                if control.get("history", {}).get(k) is not None:
                    history[k] = list(control["history"][k])
            prev_metric = control.get("prev_metric")
            termination_count = int(control.get(
                "termination_count", cfg.fed.termination_patience))
            if control.get("ledger"):
                ledger = PrivacyLedger(
                    cfg.fed, start_round=r_grow,
                    restored_meta={k: np.asarray(v) for k, v in
                                   control["ledger"].items()})
            rounds_run = r_grow
            ctl.event("reshard_done", r_grow, mode="grow_rejoin",
                      steps=[s.to_json() for s in steps])
            _beat("running", r_grow)
            return r_grow

        def _do_reshard(req, rnd):
            """Execute one agreed reshard at loop-top ``rnd``: move the
            live state onto the new mesh with the wire-free planner,
            rebuild (shrink) or restore (grow) the round executables, and
            rebind every loop-level reference — then continue at the SAME
            round, no process restart, no checkpoint restore. Returns the
            round to continue from (the parked victim returns at the grow
            round, or exits EXIT_RESHARDED at run end). A participant
            dying mid-protocol times out the commit barrier and raises
            ReshardFailed, which crashes this process into the gang
            supervisor's ordinary restart + checkpoint-resume contract."""
            nonlocal state, batch, exp, _rep, cfg, eval_step, step_fns
            nonlocal prev_metric, termination_count, ckpt_group
            from fedtpu.parallel.mesh import submesh
            from fedtpu.parallel.reshard import (grow_row_map,
                                                 host_replicated,
                                                 is_client_leaf,
                                                 reshard_state,
                                                 shrink_row_map)
            from fedtpu.resilience.reshard import ReshardFailed
            ctl = reshard_ctl
            seq = ctl.seq
            me = ctl.process_index
            try:
                if req.mode == "shrink":
                    src_C = cfg.shard.num_clients
                    src_devs = list(exp.mesh.devices.flat)
                    pd = src_C // len(src_devs)
                    target = req.target_clients
                    survivors = (me,)
                    if multiproc:
                        survivors = tuple(p for p in ctl.active
                                          if p != req.victim)
                        n_dst = sum(1 for d in src_devs
                                    if d.process_index != req.victim)
                        target = target or pd * n_dst
                        if target != pd * n_dst:
                            raise ReshardFailed(
                                f"shrink target {target} does not match "
                                f"the surviving devices ({n_dst} devices x "
                                f"{pd} clients/device)")
                    elif not target:
                        log.warning("Ignoring shrink notice: a "
                                    "single-process signal shrink needs a "
                                    "fault-plan target_clients.")
                        return rnd
                    ctl.event("reshard_begin", rnd, mode="shrink",
                              victim=req.victim, target=target)
                    _beat("resharding", rnd)
                    ctl.maybe_crash()
                    # Phase A: every PRE-reshard member is at this round's
                    # loop-top with no collective in flight. A victim that
                    # died without handing off fails this barrier -> gang
                    # restart, never a half-resharded continue.
                    ctl.publish_ack(seq, "a", rnd)
                    ctl.await_acks(seq, "a", ctl.active)
                    if multiproc and me == req.victim:
                        ctl.committed("shrink", req.victim)
                        log.info(f"Preempted member parking at round {rnd} "
                                 "(state handed off; will rejoin on grow).")
                        return _victim_grow(ctl.park(seq, rnd))
                    dst_mesh = (submesh(exp.mesh, process_indices=survivors,
                                        num_clients=target)
                                if multiproc
                                else submesh(exp.mesh, num_clients=target))
                    pos = {d.id: i for i, d in enumerate(src_devs)}
                    rows = []
                    for d in dst_mesh.devices.flat:
                        rows.extend(range(pos[d.id] * pd,
                                          (pos[d.id] + 1) * pd))
                    if rows != list(range(rows[0], rows[0] + target)):
                        raise ReshardFailed(
                            f"surviving client rows {rows} are not one "
                            "contiguous block; the wire-free plan cannot "
                            "renumber them")
                    block_start = rows[0]
                    with tracer.span("reshard_move", round=rnd):
                        new_state, steps = reshard_state(
                            state, dst_mesh=dst_mesh, dst_clients=target,
                            row_map=shrink_row_map(block_start, target))
                    # Data repack through the partition view
                    # (ShardConfig.partition_clients): shard as the
                    # ORIGINAL full population, keep the survivors'
                    # window — every kept client's packed batch (padding
                    # included) is bitwise its pre-shrink one.
                    P = cfg.shard.partition_clients or src_C
                    cfg2 = dataclasses.replace(
                        cfg, shard=dataclasses.replace(
                            cfg.shard, num_clients=target,
                            partition_clients=P,
                            partition_offset=(cfg.shard.partition_offset
                                              + block_start)))
                    reshard_stack.append({
                        "cfg": cfg, "exp": exp, "rep": _rep,
                        "eval_step": eval_step, "step_fns": step_fns,
                        "ckpt_group": ckpt_group,
                        "block_start": block_start})
                    with tracer.span("reshard_build", round=rnd):
                        exp2 = build_experiment(cfg2, ds, mesh=dst_mesh)
                    cfg, exp = cfg2, exp2
                    state, batch = new_state, exp2.batch
                    eval_step = exp2.eval_step
                    step_fns = {}
                    if multiproc:
                        from fedtpu.parallel.mesh import replicated_sharding
                        from fedtpu.utils.trees import identity
                        _rep = jax.jit(
                            identity,
                            out_shardings=replicated_sharding(dst_mesh))
                    # Phase B: every POST-reshard member holds the rebuilt
                    # state — only then does anyone dispatch on the shrunk
                    # mesh.
                    ctl.publish_ack(seq, "b", rnd)
                    ctl.await_acks(seq, "b", survivors)
                    ctl.committed("shrink", req.victim)
                    if multiproc:
                        ckpt_group = sorted(ctl.active)
                    if history[names[0]]:
                        prev_metric = [history[k][-1] for k in names]
                    termination_count = cfg.fed.termination_patience
                    ctl.event("reshard_done", rnd, mode="shrink",
                              target=target, block_start=block_start,
                              steps=[s.to_json() for s in steps])
                    log.info(f"Elastic shrink at round {rnd}: {src_C} -> "
                             f"{target} clients (block offset "
                             f"{block_start}), no restart.")
                    _beat("running", rnd)
                    return rnd

                # ---- grow ---------------------------------------------
                if not reshard_stack:
                    log.warning("Ignoring grow notice: nothing shrunk.")
                    return rnd
                st = reshard_stack[-1]
                orig_C = st["cfg"].shard.num_clients
                src_C = cfg.shard.num_clients
                ctl.event("reshard_begin", rnd, mode="grow",
                          victim=req.victim, target=orig_C)
                _beat("resharding", rnd)
                ctl.maybe_crash()
                jm = _global_join_map()
                if multiproc and me == min(ctl.active):
                    # Leader spools everything the rejoiner needs BEFORE
                    # publishing the grow record its park loop polls —
                    # record visibility implies spool completeness.
                    repl = {}
                    def _collect(keys, leaf):
                        if not is_client_leaf(leaf) and hasattr(leaf, "sharding"):
                            repl[jax.tree_util.keystr(keys)] = \
                                host_replicated(leaf)
                        return leaf
                    jax.tree_util.tree_map_with_path(_collect, state)
                    ctl.write_spool(ctl.seq, jm, repl, {
                        "round": rnd,
                        "history": {k: [float(v) for v in history[k]]
                                    for k in names},
                        "prev_metric": prev_metric,
                        "termination_count": termination_count,
                        "ledger": {k: np.asarray(v).tolist() for k, v in
                                   ledger.checkpoint_meta(rnd).items()},
                    })
                    ctl.publish_grow(ctl.seq, rnd, {
                        "src_clients": src_C,
                        "block_start": st["block_start"]})
                participants = (tuple(sorted(set(ctl.active)
                                             | {req.victim}))
                                if multiproc and req.victim >= 0
                                else ctl.active)
                ctl.publish_ack(seq, "a", rnd)
                ctl.await_acks(seq, "a", participants)
                with tracer.span("reshard_move", round=rnd):
                    new_state, steps = reshard_state(
                        state, dst_mesh=st["exp"].mesh,
                        dst_clients=orig_C,
                        row_map=grow_row_map(src_C, orig_C,
                                             st["block_start"]),
                        join_rows=_reshard_join_fn(jm, rnd))
                ctl.publish_ack(seq, "b", rnd)
                ctl.await_acks(seq, "b", participants)
                reshard_stack.pop()
                cfg, exp, _rep = st["cfg"], st["exp"], st["rep"]
                eval_step, step_fns = st["eval_step"], st["step_fns"]
                ckpt_group = st["ckpt_group"]
                state, batch = new_state, exp.batch
                ctl.committed("grow", req.victim)
                if history[names[0]]:
                    prev_metric = [history[k][-1] for k in names]
                termination_count = cfg.fed.termination_patience
                ctl.event("reshard_done", rnd, mode="grow", target=orig_C,
                          steps=[s.to_json() for s in steps])
                log.info(f"Elastic grow at round {rnd}: {src_C} -> "
                         f"{orig_C} clients, no restart, no recompile.")
                _beat("running", rnd)
                return rnd
            except ReshardFailed as e:
                ctl.event("reshard_failed", rnd, error=str(e))
                _beat("reshard_failed", rnd)
                log.warning(f"Elastic reshard failed ({e}); degrading to "
                            "the gang-restart contract.")
                raise

        # Pipelined-stop mode (cfg.run.pipelined_stop): dispatch chunk k+1
        # BEFORE processing chunk k's metrics, so the per-chunk host work
        # (metric fetch + early-stop decision — one dispatch+fetch round
        # trip) overlaps the device executing the next chunk. The trade,
        # documented and deliberate:
        #   * stop decisions lag one chunk — when early stopping (or the
        #     metric divergence guard) fires, one already-in-flight chunk
        #     has trained past the stop; its metrics are DROPPED (history
        #     matches the synchronous run exactly) but the final state
        #     carries its training. (The reference's stop-signal bcast is
        #     also read one loop-top late — :132 vs :195 — but its doomed
        #     iteration breaks BEFORE training, so unlike this mode the
        #     reference never trains past the stop; see module docstring.)
        #   * the chunk-end STATE finiteness gate runs only at checkpoint /
        #     held-out-eval boundaries (which sync inherently) and at loop
        #     exit — fetching the in-flight state between ordinary chunks
        #     would serialize every chunk, the exact cost this mode removes;
        #     the per-round METRIC guard still runs every round, one chunk
        #     late.
        # Checkpoint / held-out-eval boundaries force their inherent sync
        # and are unchanged. Default OFF: the synchronous loop keeps exact
        # reference stop semantics.
        # run.mpmd rides the same pending machinery: the DAG dispatches
        # everything (chain, cross-slice transfer, metrics program)
        # asynchronously, and this one-chunk-in-flight schedule is what
        # overlaps chunk k's metric fetch under chunk k+1's client
        # compute — the RTT-hiding half of the MPMD win.
        pipelined = cfg.run.pipelined_stop or cfg.run.mpmd
        pending = None                      # (rnd0, take, metrics) in flight
        rnd = start_round
        while rnd < cfg.fed.rounds and not stopped_early:
            if preempt["sig"] is not None:
                # Graceful preemption drain: finish any in-flight chunk,
                # checkpoint (unless the state is poisoned — a NaN drain
                # checkpoint would resume straight back into divergence),
                # and exit through the Preempted contract (code 75, the
                # supervisor restarts with --resume).
                if pending is not None:
                    process_chunk(*pending, state_round=rnd)
                    pending = None
                if not stopped_early:
                    if not (cfg.run.halt_on_nonfinite
                            and state_poisoned(rnd)):
                        with phase("checkpoint", rnd, guard="checkpoint"):
                            save_checkpoint(
                                cfg.run.checkpoint_dir, state, history, rnd,
                                extra_meta=ledger.checkpoint_meta(rnd),
                                process_group=ckpt_group)
                            retain_after_save(rnd)
                    tracer.event("preempted", round=rnd)
                    registry.counter("preemptions").inc()
                    log.warning(f"SIGTERM: drained checkpoint at round "
                                f"{rnd}; exiting for resume (preempted).")
                    _beat("preempted", rnd)
                    raise Preempted(rnd)
                break
            if reshard_ctl is not None and reshard_ctl.pending:
                if not reshard_live or (multiproc
                                        and not cfg.run.checkpoint_dir):
                    # This config cannot live-reshard (validated at
                    # startup for PLAN entries, so only a SIGNAL notice
                    # reaches here): degrade it to the plain preemption
                    # drain — checkpoint + exit 75 + gang restart at the
                    # new size.
                    reshard_ctl.clear_signal()
                    if cfg.run.checkpoint_dir:
                        tracer.event("reshard_degraded", round=rnd)
                        registry.counter("reshard_degraded").inc()
                        log.warning("Preemption notice under a config that "
                                    "cannot live-reshard (rounds_per_step"
                                    ">1, pipelined_stop, or no checkpoint_"
                                    "dir); draining via the preempt path.")
                        preempt["sig"] = getattr(signal, "SIGUSR1", 10)
                        continue
                    log.warning("Ignoring preemption notice: no "
                                "checkpoint_dir to drain to and no "
                                "live-reshard support in this config.")
                else:
                    req = reshard_ctl.poll(rnd)
                    if req is not None:
                        rnd = _do_reshard(req, rnd)
                        continue
            take = min(chunk, cfg.fed.rounds - rnd)
            if injector is not None:
                # A fault round must run as its own width-1 dispatch so
                # pre/post_round bracket exactly that round.
                take = injector.chunk_limit(rnd, take)
            if (overlap_exec is not None and take == chunk
                    and chunk not in step_fns):
                if (overlap_exec.done(overlap_key)
                        or cfg.fed.rounds - rnd <= chunk):
                    # Adopt the background-built executable (an AOT
                    # ``Compiled`` is called exactly like the jit wrapper).
                    # When no warmup round can still fit, this get() is the
                    # one place dispatch blocks on compilation.
                    try:
                        step_fns[chunk] = overlap_exec.get(overlap_key)
                    except Exception:
                        # Background build failed; the eager compile path
                        # below takes over at this width.
                        registry.counter(
                            "background_compile_failures").inc()
                        overlap_exec = None
                else:
                    # Wide program still compiling: train a width-1 warmup
                    # round meanwhile (the same math — R width-1 chunks ==
                    # one R-wide chunk).
                    take = 1
            if injector is not None:
                injector.pre_round(rnd, state, batch,
                                   checkpoint_dir=cfg.run.checkpoint_dir)
            # xprof's step view groups the device's operations by chunk:
            # the step runs from here to the chunk's fetch (process_chunk).
            step_ann = None
            if prof_win["on"]:
                step_ann = jax.profiler.StepTraceAnnotation(
                    "fedtpu.chunk", step_num=rnd + take)
                step_ann.__enter__()
                open_steps.append(step_ann)
            if take not in step_fns:
                # First call at this chunk width: trace + lower + compile
                # happen synchronously inside the dispatch (only execution
                # is async), so the span brackets the compile cost. The
                # jax.monitoring probe (install_compile_probe) counts the
                # backend-reported compile seconds alongside.
                with phase("compile", rnd + take, rounds=take):
                    state, metrics = get_step(take)(state, batch)
            else:
                # Guarded: on the CPU/gloo backend a dispatch whose
                # collectives wait on a dead peer blocks HERE, not at the
                # metric fetch (TPU dispatch is async, so this guard
                # window is microseconds there). The first-call branch
                # above stays unguarded — compile time must never count
                # against --collective-timeout; a hang during a first
                # dispatch is the supervisor --hang-timeout's job.
                with phase("dispatch", rnd + take, rounds=take,
                           guard="dispatch"):
                    state, metrics = get_step(take)(state, batch)
            if injector is not None:
                # After dispatch (the launched chunk holds its own array
                # references): restore the pre-fault mask so every later
                # round is bitwise-identical to an unfaulted run.
                injector.post_round(rnd, batch)
            if pipelined:
                if pending is not None:
                    # The current `state` is the just-dispatched chunk's
                    # output, ending at rnd + take.
                    process_chunk(*pending, state_round=rnd + take)
                pending = (rnd, take, metrics, step_ann)
            else:
                process_chunk(rnd, take, metrics, step_ann)
            rnd += take

            if rollback["resume_at"] is not None:
                # A divergence rolled back mid-chunk-processing: re-enter
                # the loop at the restored round (state/history already
                # rewound by try_rollback).
                rnd = rollback["resume_at"]
                rollback["resume_at"] = None
                _beat("running", rnd)
                continue
            _beat("running", rnd)

            if stopped_early:
                # The chunk overshot the stop round; don't checkpoint or eval the
                # overshoot state (the unchunked loop's `break` skips these too).
                # In pipelined mode `pending` is the in-flight overshoot chunk:
                # dropped (see above), its step annotation closed.
                if pending is not None:
                    close_step(pending[3])
                pending = None
                break

            # Held-out eval / checkpoint at chunk boundaries when due within the
            # chunk (with rounds_per_step=1 this is the exact per-round cadence).
            # Every due round appends an entry so test_hist round-alignment
            # matches the unchunked run; due rounds inside one chunk share the
            # chunk-end global params (documented approximation). In pipelined
            # mode these fetch the in-flight state — an inherent sync, paid
            # only on due boundaries; process the pending chunk first so
            # history stays ordered.
            eval_due = cfg.run.eval_test_every and sum(
                1 for j in range(take)
                if (rnd - j) % cfg.run.eval_test_every == 0)
            ckpt_due = bool(ckpt_every and cfg.run.checkpoint_dir and any(
                (rnd - j) % ckpt_every == 0 for j in range(take)))
            if pipelined and pending is not None and (eval_due or ckpt_due):
                process_chunk(*pending, state_round=rnd)
                pending = None
                if stopped_early:
                    break

            # Chunk-end state check: metrics can stay finite for one round
            # AFTER params go NaN (argmax over NaN logits yields index 0, and
            # the reported loss is computed at pre-update params), and Adam
            # moments can overflow while params are still finite — so the
            # per-round metric guard above would let a periodic checkpoint
            # capture a poisoned state as "good". Gate the checkpoint on the
            # actual full state (params + optimizer moments). In pipelined
            # mode the per-chunk check would force a sync every chunk — the
            # exact cost the mode removes — so it runs only at checkpoint /
            # held-out-eval boundaries (which already sync inherently; the
            # gate adds no extra serialization) and once at loop exit. A
            # periodic save therefore NEVER persists a poisoned state as the
            # latest good checkpoint, and held-out eval never runs on NaN
            # params, in either mode.
            if cfg.run.halt_on_nonfinite \
                    and (not pipelined or ckpt_due or eval_due) \
                    and state_poisoned(rnd, take):
                # Offenders unknown here (the poison shows in the full
                # state, not a per-client metric) — rollback without
                # exclusion; halt when the policy declines.
                if try_rollback(
                        f"params/optimizer state after round {rnd}", rnd):
                    rnd = rollback["resume_at"]
                    rollback["resume_at"] = None
                    continue
                halt_diverged(f"params/optimizer state after round {rnd}",
                              rnd)
                break

            if eval_due:
                # _rep: the global slice of a client-sharded array is not
                # host-addressable from every process; replicated params
                # also make the eval jit's output fetchable everywhere.
                with phase("eval", rnd, guard="eval_fetch") as sp:
                    tm = eval_step(_rep(exp.global_fn(state)),
                                   ds.x_test, ds.y_test)
                    # Span closes on the host fetch of the eval metrics —
                    # the fetch-forced-completion rule again.
                    sp.end_after_fetch(tm)
                registry.counter("held_out_evals").inc()
                for _ in range(eval_due):
                    for k in names:
                        test_hist[k].append(float(tm[k]))

            # Checkpoint label semantics under chunking: a checkpoint due
            # mid-chunk is saved once at the chunk boundary, labeled with —
            # and containing — the CHUNK-END round `rnd` (states interior to
            # a scanned chunk never exist on the host). With rounds_per_step
            # R and checkpoint_every not a multiple of R, on-disk
            # `round_NNNN` labels therefore land on chunk ends rather than
            # on the exact due rounds; resume is consistent (label == state
            # == resume point), just coarser than the R=1 cadence.
            if ckpt_due:
                # EVERY process calls this: orbax save is itself a
                # collective (barriers internally — a process-0-only call
                # deadlocks), and it writes each client shard from the
                # process that owns it (true distributed checkpointing).
                with phase("checkpoint", rnd, guard="checkpoint"):
                    save_checkpoint(cfg.run.checkpoint_dir, state, history,
                                    rnd,
                                    extra_meta=ledger.checkpoint_meta(rnd),
                                    process_group=ckpt_group)
                    retain_after_save(rnd)

        if pending is not None and not stopped_early:
            process_chunk(*pending, state_round=rnd)
        if (pipelined or stopped_early) and not diverged \
                and cfg.run.halt_on_nonfinite and state_poisoned(rnd):
            # The deferred state gate (see above) — in pipelined mode the
            # only between-boundary state check; in sync mode only after an
            # early-stop break, the one path the in-loop gate misses (its
            # final chunk may poison the state while pre-update metrics
            # stay finite). A healthy sync completion skips it: the in-loop
            # gate already checked the final chunk, and the re-check would
            # cost a redundant fetch RTT. Label with `rnd` — the
            # round the CURRENT state corresponds to — not rounds_run: after
            # an early stop the state carries the overshoot chunk's training
            # (up to one chunk past rounds_run), and halt_diverged's
            # contract is label == saved state.
            halt_diverged(f"params/optimizer state after round {rnd}", rnd)
        if reshard_ctl is not None:
            # Release any still-parked member: the run is over, and it
            # must exit EXIT_RESHARDED (76, a non-failure departure to the
            # gang supervisor) rather than wait for a grow that will
            # never come. Reached only on clean completion — on a crash
            # the supervisor's gang teardown collects the parked member.
            reshard_ctl.finish()
        # What a job pays after its last round, to run_end: the finally
        # block's gauges, personalisation, the final fetch of the global
        # model and of the result's scalars. Closed on the last fetch.
        epilogue.enter_context(phase("epilogue", rounds_run))

    finally:
        if watchdog is not None:
            # Post-loop fetches (final params, personalization) run
            # unguarded — a healthy completion reached them, and the
            # watchdog must never fire on epilogue work it can't see.
            watchdog.stop()
        if _prev_term is not None:
            signal.signal(signal.SIGTERM, _prev_term)
        if overlap_exec is not None:
            # Don't wait on a background compile the run never needed
            # (early stop before the first wide chunk).
            overlap_exec.shutdown()
        for ann in list(open_steps):
            # a chunk that raised before its fetch, or was never processed
            close_step(ann)
        if prof_win["on"]:
            # Completion proof before finalizing the trace — a trace
            # stopped while work is still in flight would miss the device
            # activity it exists to capture. Best-effort: on the
            # mid-run-failure path this finally exists for, the donated
            # state buffers may already be deleted, and a raise here would
            # mask the original error and skip stop_trace/close below.
            try:
                force_fetch(state["params"])
            except Exception:  # fedtpu: noqa[FTP102] raising here would mask the original error and skip stop_trace/close
                pass
            jax.profiler.stop_trace()
        if jsonl is not None:
            jsonl.close()
        # Final counter snapshot even on the failure path — the sink exists
        # to diagnose exactly such runs. Memory gauges are best-effort
        # (buffers may already be deleted mid-failure).
        device_memory_gauges(registry)
        tracer.counters(registry.snapshot())

    personalized: Dict[str, dict] = {}
    if exp.personalize_fn is not None and not diverged:
        # Post-training per-client fine-tune from the final global model;
        # the personalized models are reported, not kept (the returned
        # final_params stay the GLOBAL model, which is what checkpoints and
        # downstream eval use).
        _, pm = exp.personalize_fn(state["params"], batch)
        pm = _rep(pm)
        personalized = {
            "per_client": {k: np.asarray(v)
                           for k, v in pm["per_client"].items()},
            "client_mean": {k: float(v)
                            for k, v in pm["client_mean"].items()},
        }
        vals = ", ".join(f"{k}: {v:.4f}"
                         for k, v in personalized["client_mean"].items())
        log.info(f"Personalized ({cfg.fed.personalize_steps} local steps) "
                 f"client-mean: [{vals}]")

    result = ExperimentResult(
        global_metrics=history,
        pooled_metrics=pooled_hist,
        per_client_metrics=per_client_hist,
        test_metrics=test_hist,
        loss=losses,
        sec_per_round=sec_per_round,
        rounds_run=rounds_run,
        stopped_early=stopped_early,
        final_params=to_numpy(_rep(exp.global_fn(state))),
        config=cfg,
        diverged=diverged,
        personalized_metrics=personalized,
        staleness=staleness_hist,
        # The state's own round counter — the exact ledger of what the
        # released params trained through (> rounds_run after a pipelined
        # early stop's overshoot chunk; the DP accountant must count it).
        rounds_trained=int(np.asarray(jax.device_get(_rep(state["round"])))),
        dp_base_assumed=ledger.base_assumed,
        final_dp_clip=(float(np.asarray(jax.device_get(
            _rep(state["dp_clip"])))) if "dp_clip" in state else None),
        data=data_info,
    )
    result = dataclasses.replace(
        result, dp_rdp_total=ledger.rdp_at(result.rounds_trained),
        dp_guarantee_void=ledger.void_at(result.rounds_trained),
        dp_composed=ledger.composed)
    if verbose or tracer.enabled:
        dp = result.privacy_spent()
        if dp:
            notes = ""
            if dp.get("composed_over_resumed_segments"):
                notes += ("; composed over resumed segments — sigma/q "
                          "shown are the current segment's")
            if dp.get("guarantee_void"):
                notes += f"; GUARANTEE VOID: {dp['guarantee_void']}"
            log.info(f"DP budget spent: epsilon={dp['epsilon']:.3f} at "
                     f"delta={dp['delta']:.1e} (noise multiplier "
                     f"{dp['noise_multiplier']}, sampling rate "
                     f"{dp['sampling_rate']}, {dp['rounds']} rounds; RDP "
                     f"order {dp['rdp_order']}{notes})")
    if (cfg.fed.async_mode and cfg.fed.async_buffer_size >= 2
            and not diverged and "buf_count" in state):
        # K-buffer starvation guard (VERDICT item 7): with --buffer-size
        # large relative to arrivals the buffer may never fill, so the
        # global silently never moves. The run is still sound — metrics
        # recorded, checkpoints/resume carry the pending buffer — but
        # the user must hear that their contributions were never applied.
        pending = int(np.asarray(jax.device_get(_rep(state["buf_count"]))))
        if pending > 0:
            log.warning(
                f"ASYNC K-BUFFER STARVATION: {pending} buffered update(s) "
                f"never reached --buffer-size {cfg.fed.async_buffer_size} "
                f"by the final tick, so the global model did not advance "
                "on them. Lower --buffer-size or raise --arrival-rate/"
                "--rounds; a resumed run carries the pending buffer "
                "forward.")
            tracer.event("async_starvation", round=rounds_run,
                         pending=pending,
                         buffer_size=cfg.fed.async_buffer_size)
    epilogue.close()
    if tracer.enabled and cfg.run.profile_dir and not cfg.run.mpmd:
        # Sink on and a profile taken: one program_scopes event for each
        # program of the trace. Here, after every span has closed, so that
        # reading the executables' text falls in no window and no lap.
        for width, fn in sorted(step_fns.items()):
            _emit_program_scopes(tracer, "round_step", width, fn, state,
                                 batch)
        if cfg.run.halt_on_nonfinite:
            _emit_program_scopes(tracer, STATE_CHECK, None, _tree_finite,
                                 _checked_state())
    _beat("diverged" if diverged else "done", rounds_run)
    tracer.event("run_end", round=rounds_run, stopped_early=stopped_early,
                 diverged=diverged, rounds_trained=result.rounds_trained,
                 restarts=restart_count, rollbacks=rollback["attempts"])
    tracer.close()
    return result
