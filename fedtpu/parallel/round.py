"""The federated round as ONE jit-compiled SPMD program.

Reference semantics being compiled away (SURVEY.md §3.2-3.3): per round, the
MPI driver does a full-batch local train step per rank (FL_CustomMLP...:63-73),
local eval (:75-91), a pickled gather of every rank's weights + shard sizes to
rank 0, a host-side weighted average, and a pickled broadcast back
(:101-120) — plus 2N+3 barriers. fedtpu fuses all of it into a single XLA
program over the ('clients',) mesh:

    train (vmap over local clients)           == train_one_epoch per rank
    confusion-matrix eval (vmap)              == evaluate_local per rank
    psum(w_i * n_i) / psum(n_i) over ICI      == gather+weighted average+bcast
                                                 (FL_CustomMLP...:108-119)
    psum of confusion matrices                == gather of per-rank preds

No weight byte ever touches the host; the host loop only reads back scalar
metrics. Barriers vanish — XLA collectives are the synchronization.

Order parity matters: the reference evaluates local models BEFORE averaging
(:145 train, :148 eval, :198 average), so round-r metrics describe the
pre-average local models. This program preserves that order.

FedAvg weighting: 'data_size' multiplies each client's params by its true
shard size n_i == len(X_local) (:104-106,112-115); 'uniform' is the plain mean
of hyperparameters_tuning.py:37. Optimizer state is deliberately NOT averaged
(:101-120 never touches it) — each client's Adam moments persist, sharded.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
import optax
from jax.sharding import PartitionSpec as P

from fedtpu.ops import scopes
from fedtpu.ops.losses import masked_cross_entropy
from fedtpu.ops.metrics import metrics_from_confusion
from fedtpu.ops.server_opt import (ServerOptimizer, clip_by_global_norm,
                                   gaussian_noise_tree,
                                   identity_server_optimizer)
from fedtpu.parallel.compress import make_quantized_weighted_mean
from fedtpu.parallel.mesh import CLIENTS_AXIS, client_sharding
from fedtpu.parallel.ring import make_all_reduce
from fedtpu.training.client import make_local_train_step, make_local_eval_step
from fedtpu.training.task import Task, classification_task

# Read-only audit hook (fedtpu.analysis.program): names this engine's
# traced entry point and the donation contract its builder applies, so
# the SPMD auditor / manifest wiring never hardcode engine internals.
AUDIT_SPEC = {
    "engine": "sync",
    "builder": "build_round_fn",
    "donate_argnums": (0,),
    "collective_axes": (CLIENTS_AXIS,),
}

# The stages of a round, as the ``jax.named_scope`` each is built under
# (here, in orchestration/mpmd.py's sub-programs and in assemble_metrics):
# what analysis.program.program_scopes puts a compiled program's operations
# down to. Metadata only: no instruction and no cache key changes with them.
CLIENT_TRAIN, CLIENT_EVAL, AGGREGATE, METRICS = STAGES = (
    "client_train", "client_eval", "aggregate", "metrics")
# The second level of scopes, under the stages: the parts of a model, each a
# name of ``fedtpu.ops.scopes`` (every model's and piece's scope is written
# there, once), and the server's own step inside ``aggregate``
# (fedtpu.parallel.stateless). The ``program_scopes`` event maps operations
# to them under ``layers``.
SERVER_UPDATE = "server_update"
LAYERS = (*scopes.LAYERS, SERVER_UPDATE)
# The third level, inside a layer or outside every one, set where the work
# happens: the models' pieces (``scopes.PIECES``), and the one fused pass a
# step that applies a gradient and adds the step's share to the accumulator
# (fedtpu.parallel.stateless). The event maps operations to them under
# ``pieces``.
SGD_PASS = "sgd_pass"
PIECES = (*scopes.PIECES, SGD_PASS)
# An outer scope AROUND layers (a multi-token-prediction module): the event
# maps operations to it under ``modules``, beside ``layers``, so a metric can
# read the module whole. A direction, not a piece: a forward pass run again
# by hand inside a backward rule, which ``program_scopes`` reads under
# ``passes`` as it does remat's own. And the kernels the TPU's compiler
# names itself, by whose they are. All three are ``scopes``' to say.
MODULES, RECOMPUTE, LAYER_KERNELS = (scopes.MODULES, scopes.RECOMPUTE,
                                     scopes.LAYER_KERNELS)


# PRNG domain-separation tag for the DP noise stream (vs the participation
# stream, which folds the round index directly into key(participation_seed)).
_DP_NOISE_STREAM = 0x6E6F6973  # "nois"
# Separate stream for the adaptive-clip count noise (the clipped-fraction
# release is its own mechanism; its draw must be independent of the delta
# noise at the same round index).
_DP_COUNT_STREAM = 0x636E7420  # "cnt "


def effective_delta_noise_multiplier(z: float, z_count: float) -> float:
    """Andrew et al. 2021 (adaptive clipping) split-noise calibration: to
    release BOTH the noised mean delta and the noised clipped-fraction with
    a total privacy cost equal to a single Gaussian mechanism of noise
    multiplier ``z``, the delta noise runs at
    ``z_delta = (z^-2 - (2*z_count)^-2)^-1/2`` while the count release —
    the RECENTERED sum ``sum_i(indicator_i - 1/2)``, add/remove
    sensitivity 1/2 — takes absolute noise std ``z_count``, i.e. an
    effective noise multiplier of ``2*z_count`` (the recentering is what
    earns the factor 2; the round body implements exactly that release).
    Requires ``z_count > z/2`` (else the count mechanism alone exceeds
    the budget). The RDP accountant keeps charging the configured ``z`` —
    the composition theorem is exactly this identity:
    z^-2 == z_delta^-2 + (2*z_count)^-2."""
    if z_count <= z / 2:
        raise ValueError(
            f"dp_count_noise_multiplier must exceed dp_noise_multiplier/2 "
            f"(got z_count={z_count} vs z={z}): the clipped-count release "
            "alone would exceed the per-round budget z")
    return (z ** -2 - (2.0 * z_count) ** -2) ** -0.5

# Smoothed-Weiszfeld iteration budget for geometric_median. Fixed (not a
# data-dependent stopping rule) so the scan stays compiler-friendly.
# Measured convergence is linear at ~1e-2 relative step per iteration;
# the slowest observed case (low-dimensional joint updates with a 25%
# outlier cluster) reaches a 1e-7 relative step by ~13 iterations, and
# high-dimensional (model-scale) cases converge faster — 16 leaves
# margin at a cost of a few extra (C, dim) passes per round.
# tests/test_robust.py::test_weiszfeld_iteration_budget_converges pins
# both the monotone objective decrease (the Weiszfeld guarantee) and
# stationarity within this budget at small AND model-scale dimensions.
WEISZFELD_ITERS = 16


def bcast_global(gl, p):
    """One global (clients-free) tensor into every client slot of ``p``'s
    shape and dtype — the in-graph form of the reference's weight broadcast
    (FL_CustomMLP...:119). Shared by every aggregation path here and in the
    2-D engine (fedtpu.parallel.tp)."""
    return jnp.broadcast_to(gl[None], p.shape).astype(p.dtype)


def client_init_keys(key: jax.Array, num_clients: int, same_init: bool):
    """Per-client PRNG keys: identical when ``same_init`` (all clients start
    from one model), else split — the reproducible stand-in for the
    reference's unseeded per-rank torch init (FL_CustomMLP...:42). Shared by
    both engines (this module and fedtpu.parallel.tp)."""
    if same_init:
        return jnp.broadcast_to(key, (num_clients, *key.shape))
    return jax.random.split(key, num_clients)


def init_federated_state(key: jax.Array, mesh, num_clients: int,
                         init_fn: Callable, tx: optax.GradientTransformation,
                         same_init: bool = False,
                         server_opt: ServerOptimizer | None = None,
                         shared_start: bool = False,
                         scaffold: bool = False,
                         adaptive_clip_init: float | None = None):
    """Per-client params + optimizer state, leading axis = clients, sharded.

    ``same_init=False`` matches the reference, where every rank constructs an
    independently-initialized torch model (FL_CustomMLP...:42 — unseeded, so
    ranks differ); here each client folds its index into the key instead, so
    the "different inits" are still reproducible.

    ``server_opt`` (delta-based aggregation, fedtpu.ops.server_opt): the
    server model is defined as the uniform mean of the client inits and every
    client starts FROM it (server-state semantics — under delta aggregation
    clients always begin a round at the global model), and the state gains a
    replicated ``server_opt_state`` entry (momentum / second-moment pytrees).

    ``shared_start`` (without a server optimizer) likewise starts every
    client from the uniform mean of the inits — required by aggregations
    that reconstruct the new global as ``start + mean(delta)`` (the int8
    compressed exchange, fedtpu.parallel.compress).

    ``scaffold`` adds zero-initialized SCAFFOLD control variates:
    ``client_cv`` (per-client, sharded like params) and ``server_cv``
    (their replicated mean). Requires ``server_opt`` (the delta path) —
    see ``build_round_fn(scaffold=True)``.

    ``adaptive_clip_init`` adds the replicated ``dp_clip`` scalar for
    adaptive DP clipping (``build_round_fn(dp_adaptive_clip=True)``),
    initialized at the given value (the config's ``dp_clip_norm``).
    """
    params = jax.vmap(init_fn)(client_init_keys(key, num_clients, same_init))
    opt_state = jax.vmap(tx.init)(params)
    shard = client_sharding(mesh)
    # safe_put, not jax.device_put: under jax.distributed a host value put
    # onto a cross-process sharding runs an implicit per-leaf equality
    # broadcast — O(leaves) DCN collectives before round 1 (see
    # fedtpu.parallel.multihost.safe_put).
    from fedtpu.parallel.multihost import safe_put
    put = lambda t: safe_put(t, shard)
    from jax.sharding import NamedSharding
    state = {
        "params": jax.tree.map(put, params),
        "opt_state": jax.tree.map(put, opt_state),
        # Replicated placement from birth: the round step returns this
        # scalar with a replicated NamedSharding, so a SingleDeviceSharding
        # init would make the second call at each chunk width retrace
        # (caught by `fedtpu check`'s recompile sentinel).
        "round": safe_put(jnp.zeros((), jnp.int32),
                          NamedSharding(mesh, P())),
    }
    if server_opt is not None or shared_start:
        g0 = jax.tree.map(lambda p: p.mean(axis=0), params)
        state["params"] = jax.tree.map(
            lambda g, p: put(jnp.broadcast_to(g[None], p.shape)), g0, params)
        # Leafless structural marker: build_round_fn's compressed path can
        # fail fast when handed a state whose slots never started shared
        # (dict membership is static under jit; no runtime cost).
        state["shared_start"] = ()
        if server_opt is not None:
            from jax.sharding import NamedSharding
            replicated = NamedSharding(mesh, P())
            # Server accumulators live in f32 regardless of param dtype:
            # the delta reduction is f32, so a bf16-born server state would
            # change dtype across the scan carry (and bf16 momentum loses
            # precision for no memory win at server scale).
            state["server_opt_state"] = jax.tree.map(
                lambda t: safe_put(t.astype(jnp.float32), replicated),
                server_opt.init(g0))
    if scaffold:
        if server_opt is None:
            raise ValueError(
                "scaffold runs on the delta path — pass a server_opt "
                "(identity_server_optimizer() for the paper's plain "
                "eta_g=1 server update)")
        from jax.sharding import NamedSharding
        # Zero-initialized control variates (the paper's init): per-client
        # c_i sharded like params, their replicated mean c. The invariant
        # server_cv == mean(client_cv) holds from here inductively. Param
        # dtype throughout — a f32 variate under bf16 params would promote
        # the corrected grads and break the scan carry's dtype contract.
        state["client_cv"] = jax.tree.map(
            lambda p: put(jnp.zeros(p.shape, p.dtype)), params)
        state["server_cv"] = jax.tree.map(
            lambda g: safe_put(jnp.zeros(g.shape, g.dtype),
                               NamedSharding(mesh, P())),
            jax.tree.map(lambda p: p[0], params))
    if adaptive_clip_init is not None:
        if adaptive_clip_init <= 0:
            raise ValueError(f"adaptive_clip_init must be > 0, got "
                             f"{adaptive_clip_init}")
        from jax.sharding import NamedSharding
        state["dp_clip"] = safe_put(
            jnp.asarray(adaptive_clip_init, jnp.float32),
            NamedSharding(mesh, P()))
    return state


def resident_state_bytes(init_fn, tx, num_clients: int, n_devices: int) -> int:
    """Bytes a device holds of the resident engines' client state: its
    clients' parameters and optimizer state (shapes only, nothing built)."""
    params = jax.eval_shape(init_fn, jax.random.key(0))
    opt = jax.eval_shape(tx.init, params)
    one = sum(math.prod(l.shape) * l.dtype.itemsize
              for l in jax.tree.leaves((params, opt)))
    return one * math.ceil(num_clients / n_devices)


def check_resident_fits(init_fn, tx, num_clients: int, mesh,
                        limit_bytes: int | None = None) -> None:
    """Raise, instead of running out of memory later, where the resident
    engines' per-client copies cannot fit the device. ``limit_bytes``
    defaults to what the first device reports (nothing on the CPU)."""
    if limit_bytes is None:
        # one of this process's own devices: another's cannot be asked
        mine = [d for d in mesh.devices.flat
                if d.process_index == jax.process_index()]
        stats = (mine[0].memory_stats() if mine else None) or {}
        limit_bytes = stats.get("bytes_limit")
    if not limit_bytes:
        return
    need = resident_state_bytes(init_fn, tx, num_clients, mesh.devices.size)
    if need > limit_bytes:
        raise ValueError(
            f"the resident engines keep a copy of the parameters and the "
            f"optimizer state for every client: {need / 1e9:.1f} GB a device "
            f"for {num_clients} clients, and the device has "
            f"{limit_bytes / 1e9:.1f} GB. Set fed.client_state='stateless' "
            "(one shared global model, clients in turn; "
            "fedtpu.parallel.stateless)")


def build_round_fn(mesh, apply_fn: Callable, tx: optax.GradientTransformation,
                   num_classes: int, weighting: str = "data_size",
                   rounds_per_step: int = 1,
                   participation_rate: float = 1.0,
                   participation_seed: int = 0,
                   aggregation: str = "psum",
                   local_steps: int = 1,
                   prox_mu: float = 0.0,
                   server_opt: ServerOptimizer | None = None,
                   dp_clip_norm: float = 0.0,
                   dp_noise_multiplier: float = 0.0,
                   dp_seed: int = 0,
                   dp_adaptive_clip: bool = False,
                   dp_target_quantile: float = 0.5,
                   dp_clip_lr: float = 0.2,
                   dp_count_noise_multiplier: float = 0.0,
                   compress: str = "none",
                   robust_aggregation: str = "none",
                   trim_ratio: float = 0.1,
                   krum_f: int = 0,
                   byzantine_clients: int = 0,
                   scaffold: bool = False,
                   task: Task | None = None):
    """Compile the full federated round. Returns
    ``round_step(state, batch) -> (state, metrics)`` where ``batch`` is a dict
    of client-sharded arrays ``x (C,N,...), y (C,N), mask (C,N)`` and
    ``metrics`` holds per-client, client-mean, and pooled views (the
    reference's two global-metric semantics, SURVEY.md §5).

    ``task`` (fedtpu.training.task): the loss the clients train on, the
    statistics their in-round evaluation sums and the metrics derived from
    them; ``None`` is classification over ``apply_fn``'s ``num_classes``
    logits, the reference's.

    ``round_step`` DONATES the input state (its buffers are consumed; params
    and optimizer state update in place on device). Always rebind:
    ``state, metrics = round_step(state, batch)``. To step one state down
    two paths, step a ``fedtpu.utils.trees.clone`` of it.

    ``rounds_per_step=R`` runs R consecutive federated rounds inside ONE
    compiled program (``lax.scan`` over the round body): metric leaves gain a
    leading R axis and the host syncs once per R rounds instead of every
    round. Where the per-round host dispatch+fetch outweighs the round
    itself (the income round is tens of microseconds of device work) this
    is the fedtpu answer to the reference's per-round pickled-collective
    overhead — not just cheaper synchronization, but R-fold fewer
    synchronizations.

    ``participation_rate < 1.0`` enables partial participation (classic
    FedAvg client sampling / straggler-dropout simulation — an extension:
    the reference always trains every rank). Each round, each client joins
    with iid probability ``participation_rate`` (deterministic in
    ``(participation_seed, round, client)``). Non-participants neither train
    nor update optimizer moments that round, and contribute zero weight to
    the average; everyone still receives the new global params (server-state
    semantics). If a round samples zero participants, averaging is skipped
    and params carry over unchanged.

    ``server_opt`` / ``dp_clip_norm`` / ``dp_noise_multiplier`` switch the
    aggregation from parameter averaging to the DELTA path: the weighted mean
    of client updates ``trained_i - g`` becomes a pseudo-gradient for a
    server optimizer (FedOpt family, fedtpu.ops.server_opt), optionally
    per-client L2-clipped to ``dp_clip_norm`` and perturbed with Gaussian
    noise of std ``dp_noise_multiplier * dp_clip_norm / denominator``
    (DP-FedAvg central DP). The denominator is the realized participant
    weight at full participation; under client sampling it is the FIXED
    public ``participation_rate * num_clients`` so sigma is not
    data-dependent — a zero-participant round then still releases noise,
    which is the mechanism, not a bug. DP noise requires
    ``weighting='uniform'`` (enforced): the sensitivity bound
    clip/denominator must be client-agnostic, and data-size weighting would
    silently deflate the effective noise multiplier to ~z/n_i for a client
    with n_i samples. DP with no explicit server optimizer
    applies the pure
    averaging rule (fedavgm, momentum 0, lr 1 — exactly FedAvg on clipped,
    noised deltas). State must come from ``init_federated_state`` with the
    same ``server_opt`` so clients start at the server model and
    ``server_opt_state`` exists.

    ``robust_aggregation``: 'median' (coordinate-wise median over clients),
    'trimmed_mean' (drop the ``trim_ratio`` fraction of extreme values
    per coordinate from each end, mean the rest), or 'krum' (Blanchard et
    al. 2017: pick the ONE client whose update has the smallest summed
    squared distance to its ``C - krum_f - 2`` nearest peers, ``krum_f`` =
    assumed malicious count) replace the weighted mean — the standard
    Byzantine-robust rules: a minority of arbitrarily corrupted client
    updates cannot move any coordinate beyond the honest majority's range
    (median/trimmed-mean) or be selected at all (krum). All are inherently
    UNWEIGHTED and ride the psum/plain-averaging path. The coordinate-wise
    rules ('median'/'trimmed_mean') compose with client sampling — order
    statistics run over the PARTICIPATING subset only (mask-aware, +inf
    padding); the whole-update rules (krum/geometric_median) still require
    full participation.
    ``byzantine_clients = k`` is the matching FAULT INJECTION: the first k
    clients' submitted updates are replaced in-graph with a 10x-amplified
    sign-flipped update (a strong model-poisoning attack) while their local
    metrics stay honest — the knob that lets tests and chaos runs prove the
    robust rules hold and the plain mean breaks.

    ``dp_adaptive_clip=True`` — adaptive clipping (Andrew et al. 2021):
    the clip norm becomes replicated server state (from
    ``init_federated_state(..., adaptive_clip_init=dp_clip_norm)``)
    tracking the ``dp_target_quantile`` of client update norms via
    ``clip *= exp(-dp_clip_lr * (b_noisy - quantile))``. With DP noise
    the per-round budget splits between the delta release and the
    unit-sensitivity clipped-count (``dp_count_noise_multiplier``) via
    ``effective_delta_noise_multiplier`` so the composition charges
    exactly the configured ``dp_noise_multiplier`` — the accountant needs
    no change. Without noise it is plain quantile tracking.

    ``scaffold=True`` — SCAFFOLD (Karimireddy et al. 2020): each client
    carries a control variate ``c_i`` (an estimate of its own shard's
    gradient at the global model) and the server carries their mean ``c``;
    every local gradient is corrected by ``c - c_i`` before the optimizer,
    cancelling the client-specific drift direction that many local steps
    on non-IID shards accumulate (the failure mode FedProx only damps).
    Variate refresh is the paper's option I — ``c_i+ = grad_i(x)``, the
    local gradient at the round-start server model — which stays exact
    under ANY local optimizer (option II's ``(x - y_i)/(K*lr)`` closed
    form assumes plain SGD steps). Runs on the delta path (plain identity
    server update == the paper's eta_g=1; composes with FedOpt server
    optimizers and with client sampling — absentees keep stale variates
    and contribute zero to the server-variate mean, the paper's
    (|S|/N)-scaled rule), uniform weighting, psum aggregation; state must
    come from ``init_federated_state(..., scaffold=True)``. The
    new-state invariant ``server_cv == mean_i(client_cv_i)`` holds
    inductively from the zero init, sampled or not, and is test-pinned.
    """

    if task is None:
        task = classification_task(apply_fn, num_classes)
    local_train = make_local_train_step(apply_fn, tx, local_steps=local_steps,
                                        prox_mu=prox_mu, scaffold=scaffold,
                                        task_loss=task.loss)
    local_eval = make_local_eval_step(task)

    sampling = participation_rate < 1.0
    # Reduction backend for the parameter-averaging path: psum
    # (XLA-scheduled, production) or an explicit ppermute ring
    # (fedtpu.parallel.ring) — the ICI-native analogue of the reference's
    # rank-0 gather/average/bcast (FL_CustomMLP...:101-120). Metric pooling
    # below stays on psum (replicated host output, not the averaging path).
    n_devices = mesh.devices.size
    all_reduce = make_all_reduce(aggregation, CLIENTS_AXIS, n_devices)

    delta_path = (server_opt is not None or dp_clip_norm > 0
                  or dp_noise_multiplier > 0 or scaffold)
    if dp_noise_multiplier > 0 and dp_clip_norm <= 0:
        raise ValueError("dp_noise_multiplier requires dp_clip_norm > 0 "
                         "(noise std is noise_multiplier * clip / weight)")
    if scaffold:
        if weighting != "uniform":
            raise ValueError("scaffold is defined over the uniform client "
                             "mean (Karimireddy et al. 2020) — set "
                             "weighting='uniform'")
        if dp_clip_norm > 0 or dp_noise_multiplier > 0:
            raise ValueError("scaffold + DP is not supported: the control "
                             "variates are derived from raw local gradients "
                             "and released unclipped/unnoised — an "
                             "unaccounted privacy leak")
        if compress != "none" or robust_aggregation != "none":
            raise ValueError("scaffold composes with the plain delta path "
                             "only (not compress/robust_aggregation)")
        if aggregation != "psum":
            raise ValueError("scaffold requires aggregation='psum' (the "
                             "replicated server variate rides psum's "
                             "provable replication, like server state)")
        if byzantine_clients > 0:
            raise ValueError("byzantine injection corrupts submitted "
                             "updates but not variates — the attack model "
                             "is incoherent under scaffold; use the robust "
                             "rules to study poisoning")
    if delta_path and server_opt is None:
        # DP without an explicit server optimizer: pure averaging of the
        # clipped, noised deltas == FedAvg (see fedtpu.ops.server_opt).
        server_opt = identity_server_optimizer()
    if delta_path and aggregation != "psum":
        # The replicated server state rides psum's provable replication; an
        # explicit ppermute ring can't be statically proven replicated for
        # the P() out-spec below.
        raise ValueError("server_opt / DP aggregation requires "
                         "aggregation='psum'")
    # DP + client sampling: the DP-FedAvg estimator divides by the FIXED
    # public denominator q*C (expected participant weight), not the realized
    # per-round total — otherwise sigma is data-dependent and no single
    # (epsilon, delta) holds across rounds. Requires uniform weighting (the
    # per-client sensitivity bound clip/denominator must be client-agnostic).
    # Under the fixed denominator, zero-participant rounds still release
    # noise — that IS the mechanism, not a bug.
    # Adaptive clipping (Andrew et al. 2021): the clip norm becomes server
    # state tracking the dp_target_quantile of client update norms via the
    # geometric rule clip *= exp(-dp_clip_lr * (b_noisy - quantile)), where
    # b is the clipped-fraction (unit-sensitivity count). With DP noise on,
    # the budget splits: deltas run at the effective z_delta and the count
    # at z_count so the composition charges exactly the configured z (the
    # accountant is unchanged). With noise off it is plain quantile
    # tracking (exact fraction, no count noise allowed).
    dp_z_delta = dp_noise_multiplier
    if dp_adaptive_clip:
        if dp_clip_norm <= 0:
            raise ValueError("dp_adaptive_clip needs dp_clip_norm > 0 as "
                             "the initial clip")
        if not 0.0 < dp_target_quantile < 1.0:
            raise ValueError(f"dp_target_quantile must be in (0, 1), got "
                             f"{dp_target_quantile}")
        if dp_clip_lr <= 0:
            raise ValueError(f"dp_clip_lr must be > 0, got {dp_clip_lr}")
        if dp_noise_multiplier > 0:
            dp_z_delta = effective_delta_noise_multiplier(
                dp_noise_multiplier, dp_count_noise_multiplier)
        elif dp_count_noise_multiplier != 0:
            raise ValueError("dp_count_noise_multiplier without "
                             "dp_noise_multiplier is meaningless: with no "
                             "delta noise there is no privacy budget to "
                             "split — set both or neither")
        if compress != "none" or robust_aggregation != "none":
            raise ValueError("dp_adaptive_clip composes with the plain "
                             "delta path only")
    elif dp_count_noise_multiplier != 0:
        raise ValueError("dp_count_noise_multiplier requires "
                         "dp_adaptive_clip=True")
    dp_fixed_denom = dp_clip_norm > 0 and sampling
    if dp_fixed_denom and weighting != "uniform":
        raise ValueError("DP with partial participation requires "
                         "weighting='uniform' (fixed public denominator "
                         "q*C for the sensitivity accounting)")
    if dp_noise_multiplier > 0 and weighting != "uniform":
        # The noise std z*clip/denominator assumes every client's
        # contribution to the weighted mean is bounded by clip/denominator.
        # Under data_size weighting a client with n_i samples contributes up
        # to n_i*clip/denominator — the effective noise multiplier silently
        # becomes ~z/n_i, far below the requested privacy level.
        raise ValueError("DP noise requires weighting='uniform': the "
                         "per-client sensitivity bound (clip/denominator) "
                         "must be client-agnostic for the noise calibration "
                         "to deliver the requested privacy level")
    if compress not in ("none", "int8"):
        raise ValueError(f"unknown compress mode {compress!r}; "
                         "available: 'none', 'int8'")
    if compress != "none" and delta_path:
        # The quantized exchange's all_gather result is clients-varying
        # typed, which the replicated server-state carry cannot accept; DP
        # noise calibration also assumes exact (unquantized) sensitivity.
        raise ValueError("compress composes with plain averaging only, not "
                         "server_opt / DP aggregation")
    if compress != "none" and aggregation != "psum":
        raise ValueError("compress replaces the reduction; use "
                         "aggregation='psum' with it")
    qmean = (make_quantized_weighted_mean(CLIENTS_AXIS)
             if compress == "int8" else None)
    if robust_aggregation not in ("none", "median", "trimmed_mean", "krum",
                                  "geometric_median"):
        raise ValueError(f"unknown robust_aggregation "
                         f"{robust_aggregation!r}; available: 'none', "
                         "'median', 'trimmed_mean', 'krum', "
                         "'geometric_median'")
    robust = robust_aggregation != "none"
    if robust and (delta_path or compress != "none"
                   or aggregation != "psum"):
        raise ValueError("robust_aggregation composes with the plain psum "
                         "averaging path only (not server_opt/DP/compress/"
                         "ring); for robust aggregation at scale use the "
                         "cohort robust path (cohort_size > 0 with "
                         "robust_aggregation='median'/'trimmed_mean', "
                         "fedtpu.cohort.scheduler)")
    if robust and sampling and robust_aggregation in ("krum",
                                                      "geometric_median"):
        # The coordinate-wise rules below are mask-aware (order statistics
        # over the participating subset); the whole-update rules are not —
        # krum's resilience precondition n > 2f + 2 is over the REALIZED
        # participant count, which a Bernoulli draw can push below any
        # static bound, and Weiszfeld over absentee zero-updates is
        # meaningless.
        raise ValueError(
            f"robust_aggregation={robust_aggregation!r} needs every "
            "client's update — full participation required "
            "(participation_rate=1.0); under client sampling use "
            "'median'/'trimmed_mean' here, or the cohort robust path "
            "(cohort_size > 0, fedtpu.cohort.scheduler) which samples "
            "cohorts and applies mask-aware order statistics")
    if robust and weighting != "uniform":
        raise ValueError("robust aggregation is unweighted (order "
                         "statistics have no data-size weighting) — set "
                         "weighting='uniform' to make that explicit")
    if not 0 <= trim_ratio < 0.5:
        raise ValueError(f"trim_ratio must be in [0, 0.5), got {trim_ratio}")
    if krum_f < 0:
        raise ValueError("krum_f must be >= 0")
    if byzantine_clients < 0:
        raise ValueError("byzantine_clients must be >= 0")

    # SCAFFOLD variate refresh (option I): the local gradient of the plain
    # CE at the round-START server model — exact under any local optimizer.
    ce_grad = jax.grad(
        lambda p, xx, yy, mm: masked_cross_entropy(apply_fn(p, xx), yy, mm))

    def round_body(params, opt_state, sstate, ccv, scv, dpc, x, y, mask,
                   rnd):
        # Shapes here are per-device blocks: leading axis Cb = C / n_devices.
        # The batch is scan-invariant (full-batch training): close over it so
        # XLA treats it as a loop constant instead of threading it as carry.
        n = mask.sum(axis=1)                                  # true shard sizes
        base_w = n if weighting == "data_size" else jnp.ones_like(n)
        cb = x.shape[0]
        gidx = jax.lax.axis_index(CLIENTS_AXIS) * cb + jnp.arange(cb)

        def one_round(carry, _):
            params, opt_state, sstate, ccv, scv, dpc, r = carry
            start = params           # delta path: every slot holds the server model

            def per_client_where(cond, a, b):
                # (Cb,) mask broadcast over each leaf's trailing dims.
                return jnp.where(cond.reshape((cb,) + (1,) * (a.ndim - 1)),
                                 a, b)

            # Stage scopes (client_train / client_eval / aggregate / metrics):
            # op_name metadata only — the optimised program is unchanged;
            # the program_scopes event (analysis.program) joins them to a
            # device trace's operations.
            with jax.named_scope(CLIENT_TRAIN):
                if sampling:
                    # Per-(round, client) Bernoulli draw, deterministic in the
                    # seed — the in-graph analogue of server-side client
                    # sampling. Drawn BEFORE local work so the SCAFFOLD variate
                    # refresh below can respect it.
                    round_key = jax.random.fold_in(
                        jax.random.key(participation_seed), r)
                    u = jax.vmap(
                        lambda i: jax.random.uniform(
                            jax.random.fold_in(round_key, i)))(gidx)
                    part = (u < participation_rate).astype(jnp.float32)
                if scaffold:
                    # Correction c - c_i enters every local gradient; variates
                    # then refresh from the gradient at the shared round start.
                    corr = jax.tree.map(lambda cv, ci: cv[None] - ci, scv, ccv)
                    trained, new_opt, loss = jax.vmap(local_train)(
                        params, opt_state, x, y, mask, corr)
                    ci_plus = jax.vmap(ce_grad)(start, x, y, mask)
                    num_clients = cb * n_devices

                    def cv_mean(d):
                        # Reduce in f32 regardless of variate dtype, cast back
                        # at the carry boundary (scan carries are dtype-exact).
                        return (jax.lax.psum(d.astype(jnp.float32).sum(axis=0),
                                             CLIENTS_AXIS) / num_clients)

                    # Participants refresh to c_i+ = grad_i(x); absentees keep
                    # their (stale) variate — the paper's sampled rule.
                    new_ccv = jax.tree.map(lambda n, o: n.astype(o.dtype),
                                           ci_plus, ccv)
                    if sampling:
                        new_ccv = jax.tree.map(
                            lambda n, o: per_client_where(part > 0, n, o),
                            new_ccv, ccv)
                    # c+ = c + mean over ALL clients of (c_i+ - c_i) (absentees
                    # contribute zero — this IS the paper's (|S|/N)-scaled
                    # participant mean); with the zero init this keeps
                    # c == mean_i(c_i) inductively, sampled or not.
                    scv = jax.tree.map(
                        lambda s, dm: (s + dm).astype(s.dtype), scv,
                        jax.tree.map(cv_mean,
                                     jax.tree.map(lambda a, b: a - b,
                                                  new_ccv, ccv)))
                    ccv = new_ccv
                else:
                    trained, new_opt, loss = jax.vmap(local_train)(
                        params, opt_state, x, y, mask)

                if sampling:
                    select = lambda a, b: per_client_where(part > 0, a, b)
                    params = jax.tree.map(select, trained, params)
                    opt_state = jax.tree.map(
                        lambda a, b: (select(a, b)
                                      if getattr(a, "ndim", 0) >= 1
                                      and a.shape[:1] == (cb,) else a),
                        new_opt, opt_state)
                    w = base_w * part
                else:
                    params, opt_state = trained, new_opt
                    w = base_w

            with jax.named_scope(CLIENT_EVAL):
                # the task's statistics: for classification (Cb, K, K)
                conf = jax.vmap(local_eval)(params, x, y, mask)

            with jax.named_scope(AGGREGATE):
                # Byzantine fault injection: the first k clients SUBMIT a
                # 10x-amplified sign-flipped update (model poisoning) while
                # their local training and metrics above stay honest — only
                # what enters aggregation is corrupted, like a real attacker.
                agg_params = params
                if byzantine_clients > 0:
                    bad = gidx < byzantine_clients
                    agg_params = jax.tree.map(
                        lambda t, s: per_client_where(bad, s - 10.0 * (t - s), t),
                        params, start)

                if delta_path:
                    # Weighted mean of per-client UPDATES as a pseudo-gradient
                    # for the server optimizer (fedtpu.ops.server_opt). Eval
                    # above ran on the trained local models, preserving the
                    # reference's metrics-before-aggregation order. Raw psum
                    # here — its result is axis-INVARIANT, unlike
                    # make_all_reduce's clients-varying typing — so the
                    # replicated server state provably stays replicated through
                    # the scan carry and the P() out-spec.
                    total_w = jax.lax.psum(w.sum(), CLIENTS_AXIS)
                    # Fixed public denominator q*C under DP+sampling (see the
                    # dp_fixed_denom note above); realized weight otherwise.
                    denom = (participation_rate * cb * n_devices
                             if dp_fixed_denom else jnp.maximum(total_w, 1.0))
                    delta = jax.tree.map(lambda t, s: t - s, agg_params, start)
                    clip_t = dpc if dp_adaptive_clip else dp_clip_norm
                    if dp_clip_norm > 0:
                        delta, dnorms = clip_by_global_norm(delta, clip_t)

                    def mean_delta_leaf(d):
                        local = jnp.tensordot(w.astype(jnp.float32),
                                              d.astype(jnp.float32), axes=1)
                        return jax.lax.psum(local, CLIENTS_AXIS) / denom

                    mean_delta = jax.tree.map(mean_delta_leaf, delta)
                    if dp_noise_multiplier > 0:
                        # Adaptive clipping splits the budget: deltas take the
                        # effective z_delta (> z) so that together with the
                        # count release below the round charges exactly z.
                        std = dp_z_delta * clip_t / denom
                        # Domain-separate the noise stream from the
                        # participation stream (same fold_in(key(seed), r)
                        # shape; both seeds default 0): fold a fixed tag in
                        # first so the Gaussian draw is independent of the
                        # participation coin flips.
                        noise_key = jax.random.fold_in(
                            jax.random.fold_in(jax.random.key(dp_seed),
                                               _DP_NOISE_STREAM), r)
                        mean_delta = jax.tree.map(
                            jnp.add, mean_delta,
                            gaussian_noise_tree(noise_key, mean_delta, std))
                    if dp_adaptive_clip:
                        # Noisy clipped-fraction b (unit-sensitivity count over
                        # participants), then the geometric quantile step
                        # clip *= exp(-lr * (b - quantile)) — Andrew et al.'s
                        # update toward the dp_target_quantile of update norms.
                        # b is a COUNT fraction: its denominator is the
                        # participant count (fixed q*C under DP+sampling),
                        # never the data-size weight — a weight denominator
                        # under weighting='data_size' would divide ~num_clients
                        # clipped clients by the total SAMPLE count, pinning
                        # b near 0 and growing the clip without bound
                        # (review r4).
                        present = (w > 0).astype(jnp.float32)
                        count = jax.lax.psum(present.sum(), CLIENTS_AXIS)
                        denom_b = (participation_rate * cb * n_devices
                                   if dp_fixed_denom
                                   else jnp.maximum(count, 1.0))
                        # The released quantity is the RECENTERED sum
                        # sum_i(indicator_i - 1/2) — add/remove sensitivity
                        # 1/2, which is what justifies crediting the count
                        # noise as a 2*z_count multiplier in the split
                        # identity (Andrew et al.; noising the raw sum would
                        # be sensitivity 1 and undercharge epsilon — review
                        # r4). At full participation the estimate below is
                        # numerically identical to the raw fraction.
                        b_sum = jax.lax.psum(
                            (present * ((dnorms <= clip_t)
                                        .astype(jnp.float32) - 0.5)).sum(),
                            CLIENTS_AXIS)
                        if dp_count_noise_multiplier > 0:
                            count_key = jax.random.fold_in(
                                jax.random.fold_in(jax.random.key(dp_seed),
                                                   _DP_COUNT_STREAM), r)
                            b_sum = b_sum + (dp_count_noise_multiplier
                                             * jax.random.normal(count_key))
                        b = b_sum / denom_b + 0.5
                        dpc_new = dpc * jnp.exp(
                            -dp_clip_lr * (b - dp_target_quantile))
                        if dp_count_noise_multiplier == 0:
                            # Noise-free quantile tracking: a zero-participant
                            # round observed nothing — b collapses to the 0.5
                            # prior and would still move the clip by
                            # exp(-lr*(0.5-q)). Hold the clip instead. (With
                            # count noise on, the release happens regardless
                            # and must be consumed as drawn.)
                            dpc_new = jnp.where(count > 0, dpc_new, dpc)
                        dpc = dpc_new
                    new_step, new_sstate = server_opt.update(mean_delta, sstate)
                    if sampling and not dp_fixed_denom:
                        # Plain FedOpt under sampling: a zero-participant round
                        # leaves the server model AND its momentum untouched
                        # (params carry over unchanged, like the averaging path).
                        keep = total_w > 0
                        new_step = jax.tree.map(
                            lambda s: jnp.where(keep, s, jnp.zeros_like(s)),
                            new_step)
                        new_sstate = jax.tree.map(
                            lambda nv, ov: jnp.where(keep, nv, ov),
                            new_sstate, sstate)
                    sstate = new_sstate
                    g = jax.tree.map(lambda s: s[0], start)   # slots identical
                    g_new = jax.tree.map(jnp.add, g, new_step)
                    params = jax.tree.map(bcast_global, g_new, params)
                elif compress == "int8":
                    # Bandwidth-lean exchange (fedtpu.parallel.compress): the
                    # new global is reconstructed as start + weighted-mean of
                    # int8-quantized deltas; requires every slot to start the
                    # round at the shared global (init_federated_state
                    # shared_start=True), like the delta path.
                    total_w = all_reduce(w.sum())             # clients-varying
                    delta = jax.tree.map(lambda t, s: t - s, agg_params, start)
                    mean_delta = qmean(delta, w.astype(jnp.float32), total_w)
                    g = jax.tree.map(lambda s: s[0], start)   # slots identical

                    def q_avg(gl, md, p):
                        # Zero participants (under sampling): skip averaging.
                        return jnp.where(total_w > 0, bcast_global(gl + md, p), p)

                    params = jax.tree.map(q_avg, g, mean_delta, params)
                elif robust:
                    # Robust rules need every client's submitted value: gather
                    # the (corrupted-as-submitted) params across the mesh.
                    num_clients = cb * n_devices
                    k_trim = int(round(trim_ratio * num_clients))
                    if robust_aggregation == "trimmed_mean" and (
                            2 * k_trim >= num_clients):
                        raise ValueError(
                            f"trim_ratio={trim_ratio} removes all "
                            f"{num_clients} clients")
                    if robust_aggregation == "krum" and (
                            num_clients < 2 * krum_f + 3):
                        # Blanchard et al.'s Byzantine-resilience precondition
                        # n > 2f + 2 — below it, f colluding clients can win
                        # the score and the guarantee is void.
                        raise ValueError(
                            f"krum needs >= 2 * krum_f + 3 clients "
                            f"(got C={num_clients}, krum_f={krum_f})")

                    def gather_clients(p):
                        pg = jax.lax.all_gather(p.astype(jnp.float32),
                                                CLIENTS_AXIS)   # (D, Cb, ...)
                        return pg.reshape((-1,) + pg.shape[2:])  # (C, ...)

                    whole_update_rule = robust_aggregation in ("krum",
                                                               "geometric_median")
                    if whole_update_rule:
                        # krum and geometric_median both work on the JOINT
                        # flattened update per client — one shared
                        # gather/flatten (and its inverse below).
                        gathered = jax.tree.map(gather_clients, agg_params)
                        leaves = jax.tree.leaves(gathered)
                        flat = jnp.concatenate(
                            [g.reshape(num_clients, -1) for g in leaves], axis=1)

                    if robust_aggregation == "geometric_median":
                        # Smoothed Weiszfeld (the RFA rule, Pillutla et al.):
                        # iterate u <- sum_i u_i/max(||u_i - u||, eps) /
                        # sum_i 1/max(||u_i - u||, eps) from the mean — the
                        # point minimizing the SUM of distances to client
                        # updates, robust to any <50% corrupted minority.
                        mu = flat.mean(axis=0)

                        def weiszfeld(u, _):
                            d = jnp.sqrt(jnp.sum(jnp.square(flat - u), axis=1))
                            wgt = 1.0 / jnp.maximum(d, 1e-8)
                            return ((wgt[:, None] * flat).sum(axis=0)
                                    / wgt.sum()), None

                        mu, _ = jax.lax.scan(weiszfeld, mu,
                                             length=WEISZFELD_ITERS)
                        offsets = [0]
                        for l in leaves:
                            offsets.append(offsets[-1]
                                           + math.prod(l.shape[1:]))
                        flat_leaves = [
                            mu[offsets[i]:offsets[i + 1]].reshape(
                                leaves[i].shape[1:])
                            for i in range(len(leaves))]
                        glob = jax.tree.unflatten(
                            jax.tree.structure(gathered), flat_leaves)
                        params = jax.tree.map(bcast_global, glob, agg_params)
                    elif robust_aggregation == "krum":
                        # Blanchard et al. 2017: score each client by the sum
                        # of squared distances to its C - f - 2 nearest peers;
                        # the winner's whole update becomes the global. MXU
                        # form: pairwise distances via the gram matrix of the
                        # flattened updates.
                        # Pairwise distances are invariant under any common
                        # shift: center on the client mean BEFORE the gram
                        # matrix, so the shared model magnitude (>> per-client
                        # differences late in training) cancels exactly instead
                        # of catastrophically in f32 — otherwise rounding noise
                        # ~eps*||params||^2 can outweigh the honest-vs-poisoned
                        # distance gap and noise-rank the scores.
                        flat = flat - flat.mean(axis=0, keepdims=True)
                        gram = flat @ flat.T                     # (C, C)
                        sq = jnp.diag(gram)
                        d2 = sq[:, None] + sq[None, :] - 2.0 * gram
                        d2 = jnp.where(jnp.eye(num_clients, dtype=bool),
                                       jnp.inf, d2)              # exclude self
                        k_near = num_clients - krum_f - 2
                        scores = jnp.sort(d2, axis=1)[:, :k_near].sum(axis=1)
                        winner = jnp.argmin(scores)

                        def select_winner(g, p):
                            return bcast_global(jax.lax.dynamic_index_in_dim(
                                g, winner, keepdims=False), p)

                        params = jax.tree.map(select_winner, gathered,
                                              agg_params)
                    else:
                        if sampling:
                            # Mask-aware order statistics: the median /
                            # trimmed mean of the PARTICIPATING subset only.
                            # Absentee rows are pushed to +inf so they sort
                            # past every live value; the traced participant
                            # count n then addresses the order statistics.
                            part_all = jax.lax.all_gather(
                                part, CLIENTS_AXIS).reshape(-1)   # (C,)
                            n_act = part_all.sum()
                            n_i = n_act.astype(jnp.int32)
                            k_t = jnp.round(trim_ratio * n_act).astype(jnp.int32)

                        def ragg(p):
                            allc = gather_clients(p)
                            if not sampling:
                                if robust_aggregation == "median":
                                    glob = jnp.median(allc, axis=0)
                                else:
                                    srt = jnp.sort(allc, axis=0)
                                    if k_trim:
                                        srt = srt[k_trim:num_clients - k_trim]
                                    glob = srt.mean(axis=0)
                                return bcast_global(glob, p)
                            live = part_all.reshape(
                                (num_clients,) + (1,) * (allc.ndim - 1))
                            srt = jnp.sort(jnp.where(live > 0, allc, jnp.inf),
                                           axis=0)
                            if robust_aggregation == "median":
                                lo = jax.lax.dynamic_index_in_dim(
                                    srt, jnp.maximum((n_i - 1) // 2, 0),
                                    keepdims=False)
                                hi = jax.lax.dynamic_index_in_dim(
                                    srt, jnp.maximum(n_i // 2, 0),
                                    keepdims=False)
                                glob = 0.5 * (lo + hi)
                            else:
                                j = jax.lax.broadcasted_iota(jnp.int32,
                                                             srt.shape, 0)
                                keep = (j >= k_t) & (j < n_i - k_t)
                                denom = jnp.maximum(
                                    (n_i - 2 * k_t).astype(jnp.float32), 1.0)
                                glob = jnp.where(keep, srt,
                                                 0.0).sum(axis=0) / denom
                            # Zero participants: params carry over unchanged,
                            # exactly like the averaging path.
                            return jnp.where(n_act > 0, bcast_global(glob, p),
                                             p)

                        params = jax.tree.map(ragg, agg_params)
                else:
                    total_w = all_reduce(w.sum())             # clients-varying

                    def avg(p):
                        # sum_i w_i * p_i locally, then all-reduce across
                        # devices == the rank-0 gather + weighted average +
                        # bcast of FL_CustomMLP...:105-119.
                        local = jnp.tensordot(w.astype(jnp.float32),
                                              p.astype(jnp.float32), axes=1)
                        glob = all_reduce(local) / jnp.maximum(total_w, 1.0)
                        # Zero participants (under sampling): skip averaging.
                        return jnp.where(total_w > 0, bcast_global(glob, p), p)

                    params = jax.tree.map(avg, agg_params)
            with jax.named_scope(METRICS):
                pooled_conf = jax.tree.map(
                    lambda c: jax.lax.psum(c.sum(axis=0), CLIENTS_AXIS), conf)
            return (params, opt_state, sstate, ccv, scv, dpc, r + 1), (
                loss, conf, pooled_conf)

        (params, opt_state, sstate, ccv, scv, dpc, _), stacked = jax.lax.scan(
            one_round, (params, opt_state, sstate, ccv, scv, dpc, rnd),
            length=rounds_per_step)
        loss, conf, pooled_conf = stacked        # leading axis = rounds R
        return (params, opt_state, sstate, ccv, scv, dpc, loss, conf,
                pooled_conf)

    spec_c = P(CLIENTS_AXIS)
    spec_rc = P(None, CLIENTS_AXIS)              # (rounds, clients, ...)
    sharded_body = jax.shard_map(
        round_body, mesh=mesh,
        # sstate (server optimizer state), scv (SCAFFOLD server variate),
        # and dpc (adaptive clip scalar) are replicated: all derive only
        # from all-reduced quantities, so every device computes them
        # identically. ccv (per-client variates) shards over clients like
        # params. Disabled features pass leafless () and their specs bind
        # nothing.
        in_specs=(spec_c, spec_c, P(), spec_c, P(), P(), spec_c, spec_c,
                  spec_c, P()),
        out_specs=(spec_c, spec_c, P(), spec_c, P(), P(), spec_rc, spec_rc,
                   P()),
    )

    # Donate the state: every caller rebinds `state = round_step(state, ...)`,
    # so XLA can update params/opt-state in place instead of allocating a
    # fresh copy of every buffer each chunk (the batch is NOT donated — it is
    # reused every call). CPU ignores donation with a warning; TPU honors it.
    @partial(jax.jit, donate_argnums=(0,))
    def round_step(state, batch):
        if delta_path and "server_opt_state" not in state:
            raise ValueError(
                "delta aggregation (server_opt / DP) needs state from "
                "init_federated_state(..., server_opt=...) — "
                "'server_opt_state' missing")
        if not delta_path and "server_opt_state" in state:
            # Symmetric to the check above: a state built WITH server_opt
            # stepped by a round_fn built WITHOUT it would silently fall
            # back to parameter averaging and drop the server momentum.
            raise ValueError(
                "state holds 'server_opt_state' (built with server_opt=...) "
                "but this round_fn was built without server_opt / DP — the "
                "server momentum would be silently dropped; build the "
                "round_fn with the same server_opt")
        if compress != "none" and "shared_start" not in state:
            raise ValueError(
                "compressed aggregation reconstructs the global as "
                "start + mean(delta), which needs every client slot to "
                "start the round at the shared global — build the state "
                "with init_federated_state(..., shared_start=True)")
        if scaffold and "client_cv" not in state:
            raise ValueError(
                "scaffold needs control-variate state — build it with "
                "init_federated_state(..., scaffold=True)")
        if not scaffold and "client_cv" in state:
            raise ValueError(
                "state holds control variates (built with scaffold=True) "
                "but this round_fn was built without scaffold — the "
                "variates would silently stop updating; build the "
                "round_fn with scaffold=True")
        if dp_adaptive_clip and "dp_clip" not in state:
            raise ValueError(
                "dp_adaptive_clip needs the clip state — build it with "
                "init_federated_state(..., adaptive_clip_init=...)")
        if not dp_adaptive_clip and "dp_clip" in state:
            raise ValueError(
                "state carries an adaptive clip (built with "
                "adaptive_clip_init=...) but this round_fn was built "
                "without dp_adaptive_clip — the clip would silently "
                "freeze; build the round_fn with dp_adaptive_clip=True")
        sstate = state.get("server_opt_state", ())
        ccv = state.get("client_cv", ())
        scv = state.get("server_cv", ())
        dpc = state.get("dp_clip", ())
        (params, opt_state, sstate, ccv, scv, dpc, loss, conf,
         pooled_conf) = sharded_body(
            state["params"], state["opt_state"], sstate, ccv, scv, dpc,
            batch["x"], batch["y"], batch["mask"], state["round"])
        metrics = assemble_metrics(loss, conf, pooled_conf, batch["mask"],
                                   rounds_per_step, task.metrics)
        new_state = {"params": params, "opt_state": opt_state,
                     "round": state["round"] + rounds_per_step}
        if delta_path:
            new_state["server_opt_state"] = sstate
        if scaffold:
            new_state["client_cv"] = ccv
            new_state["server_cv"] = scv
        if dp_adaptive_clip:
            new_state["dp_clip"] = dpc
        if "shared_start" in state:
            new_state["shared_start"] = ()
        return new_state, metrics

    return round_step


def masked_client_mean(per_client, mask):
    """Mean over clients excluding empty shards — THE client-mean
    convention (one dataless client must not deflate the global metric /
    early-stop signal). ``per_client`` leaves end in a clients axis
    (``(..., C)``); ``mask`` is the ``(C, N)`` sample mask. Shared by the
    round programs and post-training personalization."""
    nonempty = (mask.sum(axis=1) > 0).astype(jnp.float32)
    denom = jnp.maximum(nonempty.sum(), 1.0)
    return jax.tree.map(lambda v: (v * nonempty).sum(axis=-1) / denom,
                        per_client)


def assemble_metrics(loss, conf, pooled_conf, mask, rounds_per_step: int,
                     metrics_fn: Callable = metrics_from_confusion,
                     counters_fn: Callable | None = None):
    """Per-round metric dicts from stacked statistics of a task (by default
    classification's confusion matrices); shared by the shard_map engine
    above and the GSPMD 2-D engine (fedtpu.parallel.tp).

    ``conf``: (R, C, K, K); ``metrics_fn`` is the task's ``metrics`` and
    ``counters_fn`` its ``counters`` (of the pooled statistics), if any. Empty shards (possible under dirichlet skew or
    clients > samples) report all-zero metrics; they are excluded from the
    client mean so one dataless client doesn't deflate the global metric /
    early-stop signal. (The reference's sklearn scripts likewise skip
    dataless ranks, FL_SkLearn...:91-93.)"""
    with jax.named_scope(METRICS):
        per_client = jax.vmap(jax.vmap(metrics_fn))(conf)
        metrics = {
            "loss": loss,
            "per_client": per_client,
            "client_mean": masked_client_mean(per_client, mask),
            "pooled": jax.vmap(metrics_fn)(pooled_conf),
        }
        if counters_fn is not None:
            metrics["counters"] = jax.vmap(counters_fn)(pooled_conf)
        if rounds_per_step == 1:
            metrics = jax.tree.map(lambda v: v[0], metrics)
    return metrics


def global_params(state):
    """The post-average global model: every client slot holds an identical
    copy (the in-graph broadcast above), so take slot 0."""
    return jax.tree.map(lambda p: p[0], state["params"])


# Replicated SERVER state keys whose leading dim may coincidentally equal
# num_clients (the defense screen's (window,) norm ring) — excluded from
# the per-client selection BY NAME, never by shape, so a window == C
# configuration cannot silently leak server state into the client store.
_SERVER_ONLY_KEYS = frozenset({"screen_norms", "screen_count"})


def _is_server_only(path) -> bool:
    return any(getattr(k, "key", None) in _SERVER_ONLY_KEYS for k in path)


def per_client_view(state, num_clients: int):
    """The PER-CLIENT leaves of a federated state, in flatten order.

    A state dict mixes two kinds of leaves: per-client ones carrying a
    leading ``(num_clients, ...)`` axis (params, Adam moments, SCAFFOLD
    client variates, async anchors/pull ticks) and replicated server
    scalars/pytrees (round counter, server optimizer state, buffers).
    The cohort subsystem (fedtpu.cohort) persists exactly the per-client
    portion — one record per client id — so both engines and the store
    must agree on WHICH leaves those are. The single rule, applied here
    and only here: ``ndim >= 1 and shape[0] == num_clients``, minus the
    named replicated keys in ``_SERVER_ONLY_KEYS`` (whose leading dim can
    collide with ``num_clients`` by coincidence).

    Returns the per-client leaves only, ordered by ``jax.tree.flatten``
    of the full state; pair with :func:`with_per_client` to rebuild a
    state around replaced per-client leaves. Works on both the sync
    (fedtpu.parallel.round) and async (fedtpu.parallel.async_fed) state
    layouts, and on host-numpy as well as device trees."""
    flat, _ = jax.tree_util.tree_flatten_with_path(state)
    return [l for p, l in flat
            if not _is_server_only(p)
            and getattr(l, "ndim", 0) >= 1 and l.shape[0] == num_clients]


def with_per_client(state, num_clients: int, new_leaves):
    """Rebuild ``state`` with its per-client leaves (the
    :func:`per_client_view` selection, same order) replaced by
    ``new_leaves``; replicated leaves pass through untouched."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(state)
    it = iter(new_leaves)
    out = []
    for p, l in flat:
        if (not _is_server_only(p)
                and getattr(l, "ndim", 0) >= 1
                and l.shape[0] == num_clients):
            out.append(next(it))
        else:
            out.append(l)
    rest = list(it)
    if rest:
        raise ValueError(
            f"with_per_client: {len(rest)} replacement leaves left over — "
            "the replacement list must match per_client_view's selection")
    return jax.tree.unflatten(treedef, out)


def build_eval_fn(task: Task):
    """Held-out evaluation of the global model by the task's statistics and
    metrics — NEW relative to the reference, which broadcasts a test split
    it never uses (FL_CustomMLP...:243-246)."""

    @jax.jit
    def eval_step(params, x, y):
        mask = jnp.ones(y.shape, jnp.float32)
        return task.metrics(task.stats(params, x, y, mask))

    return eval_step
