"""MPMD round pipelining — the monolithic round chunk decomposed into a
small static DAG of AOT sub-programs (ISSUE 18; ROADMAP item 2).

The synchronous early-stopping mode pays one dispatch *and* one metric
fetch per chunk, serialized with the device (PERF.md §6 "Before PR 1":
1.04e-3 vs 7.1e-5 s/round pipelined at rps=100 through the earlier
remote transport; on a v5e's own host the width-1 host share is 4.7-6.2
ms a round, PERF.md §5). The income round's device side is bound by its
HBM traffic (PERF.md §5), so the remaining lever is host-side: split the
round into concurrently resident programs in the spirit of MPMD pipeline
parallelism (PAPERS.md, arXiv 2412.14374)
and let round k+1's client step run in flight while round k's
aggregation output transfers to the server slice, its metrics program
runs there, and its host fetch drains. The per-round RTT then amortizes
to pipeline fill cost.

The DAG (per chunk of ``R = rounds_per_step`` rounds)::

    client slice (the full round mesh)          server slice (submesh)
    ------------------------------------------  ----------------------
    R == 1:  client_step ──> aggregate ──┐
    R  > 1:  chain (scanned c+a rounds) ─┤
                                         ├─ device_put raw stats ──> metrics
    state' stays resident ───────────────┘      (loss/conf/pooled_conf)

Every sub-program is compiled ahead-of-time (``fn.lower().compile()``),
through the PR 3 :class:`~fedtpu.compilation.cache.ProgramCache` when a
cache directory is configured — the fingerprint includes the
sub-program's device-assignment slice, so client-slice and server-slice
builds of the same avals never collide. Donation crosses program
boundaries: the chain donates the whole federated state (params /
opt-state update in place, exactly like the monolithic step), and the
metrics program donates the transferred raw-stat buffers.

**Parity contract.** The monolithic :func:`fedtpu.parallel.round
.build_round_fn` chunk stays the default engine and the bitwise oracle:
the sub-programs are built from the SAME primitives
(``make_local_train_step`` / ``make_local_eval_step`` /
``make_all_reduce`` / ``bcast_global``) in the same op order, so metric
history and final params match the monolithic path bit for bit
(tests/test_mpmd.py). Only the plain synchronous averaging path
decomposes this way — :func:`validate_mpmd_config` rejects every knob
whose math threads state *through* the aggregation boundary
(server_opt / DP / scaffold / compression / robust rules / sampling)
loudly at startup.

On a single-host mesh the "server slice" is a 1-device
:func:`~fedtpu.parallel.mesh.submesh` of the same device pool (it
overlaps the client slice at device 0); the scheduling win is the host
RTT hiding, which needs no disjoint hardware. On a pod with a spare
slice, heterogeneous placement falls out of the same code path.
"""

from __future__ import annotations

import itertools
import time
from functools import partial
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from fedtpu.ops.metrics import metrics_from_confusion
from fedtpu.parallel.mesh import (CLIENTS_AXIS, replicated_sharding,
                                  submesh)
from fedtpu.parallel.ring import make_all_reduce
from fedtpu.parallel.round import (AGGREGATE, CLIENT_EVAL, CLIENT_TRAIN,
                                   METRICS, bcast_global)
from fedtpu.training.client import make_local_eval_step, make_local_train_step
from fedtpu.training.task import classification_task

__all__ = [
    "AUDIT_SPEC", "AUDIT_SPECS", "MpmdStep", "build_mpmd_step",
    "build_mpmd_programs", "parity_check", "server_submesh",
    "validate_mpmd_config",
]

# Per-sub-program audit contracts (PR 8 auditor; fedtpu.analysis.program).
# Each sub-program's collective schedule is gated INDEPENDENTLY: the
# client step and the metrics program must stay collective-free (their
# whole point is to dispatch without waiting on a cross-device phase),
# while aggregate/chain own the clients-axis reductions. ``state`` is
# donated everywhere it threads through; the metrics program donates the
# transferred raw-stat buffers (``loss`` aliases straight back out).
AUDIT_SPECS: Dict[str, dict] = {
    "mpmd_client": {
        "engine": "mpmd_client",
        "builder": "build_mpmd_programs",
        "donate_argnums": (0,),
        "collective_axes": (),
    },
    "mpmd_aggregate": {
        "engine": "mpmd_aggregate",
        "builder": "build_mpmd_programs",
        "donate_argnums": (0,),
        "collective_axes": (CLIENTS_AXIS,),
    },
    "mpmd_chain": {
        "engine": "mpmd_chain",
        "builder": "build_mpmd_programs",
        "donate_argnums": (0,),
        "collective_axes": (CLIENTS_AXIS,),
    },
    "mpmd_metrics": {
        "engine": "mpmd_metrics",
        "builder": "build_mpmd_programs",
        "donate_argnums": (0,),
        # Donate-to-free: the raw-stat buffers are consumed, but only
        # ``loss`` threads back out (metrics["loss"] aliases it) — the
        # confusion matrices have no same-shape output to alias.
        "alias_expected": (),
        "collective_axes": (),
    },
}

# The engine-level spec (engine_audit_spec dispatch): the chain is the
# program that holds the round math and the donated state, so it is the
# manifest's headline sub-program.
AUDIT_SPEC = AUDIT_SPECS["mpmd_chain"]


def validate_mpmd_config(cfg) -> None:
    """Reject configs whose round math cannot decompose at the
    client/aggregate boundary. Loud and exhaustive, at startup — the
    same contract style as ``build_experiment``'s engine branches."""
    fed = cfg.fed
    bad = []
    if fed.async_mode:
        bad.append("async_mode (FedBuff owns its own arrival loop)")
    if fed.cohort_size > 0:
        bad.append("cohort_size > 0 (the cohort scheduler owns the loop)")
    if cfg.run.model_parallel > 1:
        bad.append("model_parallel > 1 (the GSPMD engine is one program "
                   "by construction)")
    if fed.participation_rate < 1.0:
        bad.append("participation_rate < 1 (the sampling coin flips "
                   "thread round state through aggregation)")
    if fed.server_opt != "none":
        bad.append("server_opt (server momentum threads through the "
                   "aggregate boundary)")
    if fed.dp_clip_norm > 0 or fed.dp_noise_multiplier > 0 \
            or fed.dp_adaptive_clip:
        bad.append("differential privacy (clip state and the noise "
                   "stream live on the delta path)")
    if fed.robust_aggregation != "none":
        bad.append("robust_aggregation (gather-based rules)")
    if fed.compress != "none":
        bad.append("compress (delta reconstruction needs shared_start "
                   "state)")
    if fed.scaffold:
        bad.append("scaffold (control variates update inside "
                   "aggregation)")
    if fed.byzantine_clients > 0:
        bad.append("byzantine_clients (corruption is injected between "
                   "training and aggregation)")
    if bad:
        raise ValueError(
            "run.mpmd decomposes the plain synchronous averaging round "
            "only; incompatible with: " + "; ".join(bad))


def server_submesh(mesh):
    """The server slice: a 1-device submesh of the round mesh (order
    preserved, PR 9 machinery), hosting the metrics program. Degenerates
    to the same device on a 1-device mesh — the dispatch overlap, not
    device disjointness, is what hides the RTT."""
    return submesh(mesh, num_devices=1)


def _spec_c():
    return P(CLIENTS_AXIS)


def build_mpmd_programs(mesh, apply_fn: Callable, tx, num_classes: int, *,
                        weighting: str = "data_size",
                        aggregation: str = "psum",
                        local_steps: int = 1,
                        prox_mu: float = 0.0,
                        rounds_per_step: int = 1) -> Dict[str, Callable]:
    """The DAG's jit wrappers, pre-AOT: ``{"client", "aggregate",
    "chain", "metrics"}``. Built from the same primitives as the
    monolithic ``build_round_fn`` plain path, in the same op order, so
    every value is bitwise-identical to the oracle.

    Signatures (all state-dict shaped like the loop's ``state``):

    * ``client(state, batch) -> (state', loss, conf)`` — vmap'd local
      train + eval, zero collectives, donates ``state``.
    * ``aggregate(state, conf, mask) -> (state'', pooled_conf)`` —
      weighted average + pooled-confusion psum, donates ``state``
      (``conf`` is NOT donated: the metrics program still reads it).
    * ``chain(state, batch) -> (state', raw)`` — ``rounds_per_step``
      scanned client+aggregate rounds in one program (one dispatch per
      chunk); ``raw = {"loss", "conf", "pooled_conf"}`` stacked over
      rounds exactly like the monolithic scan outputs.
    * ``metrics(raw, mask) -> metrics`` — ``assemble_metrics`` math,
      donates ``raw``. Takes the LIVE batch mask and derives
      ``masked_client_mean``'s nonempty row in-graph exactly like the
      oracle — fault injection (client dropout) mutates the mask in
      place between rounds, so a build-time snapshot would go stale.
    """
    local_train = make_local_train_step(apply_fn, tx,
                                        local_steps=local_steps,
                                        prox_mu=prox_mu)
    local_eval = make_local_eval_step(
        classification_task(apply_fn, num_classes))
    n_devices = mesh.devices.size
    all_reduce = make_all_reduce(aggregation, CLIENTS_AXIS, n_devices)
    spec_c = _spec_c()
    spec_rc = P(None, CLIENTS_AXIS)

    # The monolithic round's stage scopes (parallel/round.py), on the
    # sub-programs that build the same stages.
    def train_eval(params, opt_state, x, y, mask):
        with jax.named_scope(CLIENT_TRAIN):
            trained, new_opt, loss = jax.vmap(local_train)(
                params, opt_state, x, y, mask)
        with jax.named_scope(CLIENT_EVAL):
            conf = jax.vmap(local_eval)(trained, x, y, mask)     # (Cb, K, K)
        return trained, new_opt, loss, conf

    def average(params, conf, mask):
        with jax.named_scope(AGGREGATE):
            n = mask.sum(axis=1)
            w = n if weighting == "data_size" else jnp.ones_like(n)
            total_w = all_reduce(w.sum())             # clients-varying

            def avg(p):
                local = jnp.tensordot(w.astype(jnp.float32),
                                      p.astype(jnp.float32), axes=1)
                glob = all_reduce(local) / jnp.maximum(total_w, 1.0)
                return jnp.where(total_w > 0, bcast_global(glob, p), p)

            new_params = jax.tree.map(avg, params)
        with jax.named_scope(METRICS):
            pooled_conf = jax.lax.psum(conf.sum(axis=0), CLIENTS_AXIS)
        return new_params, pooled_conf

    client_body = jax.shard_map(
        train_eval, mesh=mesh,
        in_specs=(spec_c, spec_c, spec_c, spec_c, spec_c),
        out_specs=(spec_c, spec_c, spec_c, spec_c))

    aggregate_body = jax.shard_map(
        average, mesh=mesh,
        in_specs=(spec_c, spec_c, spec_c),
        out_specs=(spec_c, P()))

    def chain_body(params, opt_state, x, y, mask):
        def one_round(carry, _):
            params, opt_state = carry
            trained, new_opt, loss, conf = train_eval(
                params, opt_state, x, y, mask)
            new_params, pooled_conf = average(trained, conf, mask)
            return (new_params, new_opt), (loss, conf, pooled_conf)

        (params, opt_state), stacked = jax.lax.scan(
            one_round, (params, opt_state), length=rounds_per_step)
        loss, conf, pooled_conf = stacked        # leading axis = rounds R
        return params, opt_state, loss, conf, pooled_conf

    chain_sharded = jax.shard_map(
        chain_body, mesh=mesh,
        in_specs=(spec_c, spec_c, spec_c, spec_c, spec_c),
        out_specs=(spec_c, spec_c, spec_rc, spec_rc, P()))

    def _check_state(state):
        for key in ("server_opt_state", "client_cv", "dp_clip"):
            if key in state:
                raise ValueError(
                    f"state holds {key!r} — built for an engine "
                    "validate_mpmd_config rejects; the MPMD DAG would "
                    "silently drop it")

    @partial(jax.jit, donate_argnums=(0,))
    def client(state, batch):
        _check_state(state)
        trained, new_opt, loss, conf = client_body(
            state["params"], state["opt_state"], batch["x"], batch["y"],
            batch["mask"])
        return ({"params": trained, "opt_state": new_opt,
                 "round": state["round"]}, loss, conf)

    @partial(jax.jit, donate_argnums=(0,))
    def aggregate(state, conf, mask):
        new_params, pooled_conf = aggregate_body(state["params"], conf,
                                                 mask)
        return ({"params": new_params, "opt_state": state["opt_state"],
                 "round": state["round"] + 1}, pooled_conf)

    @partial(jax.jit, donate_argnums=(0,))
    def chain(state, batch):
        _check_state(state)
        params, opt_state, loss, conf, pooled_conf = chain_sharded(
            state["params"], state["opt_state"], batch["x"], batch["y"],
            batch["mask"])
        return ({"params": params, "opt_state": opt_state,
                 "round": state["round"] + rounds_per_step},
                {"loss": loss, "conf": conf, "pooled_conf": pooled_conf})

    stacked = rounds_per_step > 1

    @partial(jax.jit, donate_argnums=(0,))
    @jax.named_scope(METRICS)
    def metrics(raw, mask):
        loss, conf, pooled_conf = (raw["loss"], raw["conf"],
                                   raw["pooled_conf"])
        # The oracle's masked_client_mean occupancy row, derived from
        # the live mask inside the program (never snapshotted: dropout
        # faults edit the mask between rounds).
        nonempty = (mask.sum(axis=1) > 0).astype(jnp.float32)
        # Same per-element math as assemble_metrics: the R=1 DAG feeds
        # UNSTACKED raws (no leading rounds axis), so the monolithic
        # path's stack-then-squeeze becomes a no-op here instead of a
        # device round-trip.
        if stacked:
            per_client = jax.vmap(jax.vmap(metrics_from_confusion))(conf)
            pooled = jax.vmap(metrics_from_confusion)(pooled_conf)
        else:
            per_client = jax.vmap(metrics_from_confusion)(conf)
            pooled = metrics_from_confusion(pooled_conf)
        denom = jnp.maximum(nonempty.sum(), 1.0)
        client_mean = jax.tree.map(
            lambda v: (v * nonempty).sum(axis=-1) / denom, per_client)
        return {"loss": loss, "per_client": per_client,
                "client_mean": client_mean, "pooled": pooled}

    return {"client": client, "aggregate": aggregate, "chain": chain,
            "metrics": metrics}


def _avals(tree) -> Any:
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(tuple(a.shape), a.dtype,
                                       sharding=a.sharding), tree)


def _aot(fn: Callable, args: Tuple[Any, ...], *, label: str,
         mesh=None, cache=None, config_slice=None, extra=None):
    """AOT-compile one sub-program, through the ProgramCache when one is
    wired (the fingerprint's mesh signature carries the device slice —
    see cache._mesh_signature)."""
    if cache is None:
        return fn.lower(*args).compile(), None
    from fedtpu.compilation.cache import program_fingerprint
    key = program_fingerprint(label, config=config_slice, mesh=mesh,
                              args=args, extra=extra)
    entry = cache.get_or_compile(key, fn, *args, label=label,
                                 extra_meta={"mpmd": label})
    return entry.compiled, entry


def audit_probes(cfg, chain_width: int = 4) -> Dict[str, tuple]:
    """Per-sub-program audit probe parts for the PR 8 auditor
    (fedtpu.analysis.program ``_PROBES``): ``{engine_name: (jit wrapper,
    example avals, AUDIT_SPEC, mesh)}``. The chain is probed at a
    representative multi-round width so its scanned collective schedule
    (one reduction set per round trip) is what the golden pins."""
    import dataclasses as dc

    from jax.sharding import NamedSharding

    from fedtpu.orchestration.loop import build_experiment

    cfg = dc.replace(cfg, run=dc.replace(
        cfg.run, mpmd=True, pipelined_stop=False, overlap_compile=False,
        model_parallel=1))
    validate_mpmd_config(cfg)
    exp = build_experiment(cfg)
    mesh = exp.mesh
    state_av, batch_av = _avals(exp.state), _avals(exp.batch)
    k = exp.num_classes
    c = exp.batch["mask"].shape[0]
    spec_c = P(CLIENTS_AXIS)

    def c_aval(shape, spec):
        return jax.ShapeDtypeStruct(shape, jnp.float32,
                                    sharding=NamedSharding(mesh, spec))

    kw = dict(weighting=cfg.fed.weighting, aggregation=cfg.fed.aggregation,
              local_steps=cfg.fed.local_steps, prox_mu=cfg.fed.prox_mu)
    p1 = build_mpmd_programs(mesh, exp.apply_fn, exp.tx, k,
                             rounds_per_step=1, **kw)
    pR = build_mpmd_programs(mesh, exp.apply_fn, exp.tx, k,
                             rounds_per_step=chain_width, **kw)
    raw1 = {"loss": c_aval((c,), spec_c),
            "conf": c_aval((c, k, k), spec_c),
            "pooled_conf": c_aval((k, k), P())}
    return {
        "mpmd_client": (p1["client"], (state_av, batch_av),
                        AUDIT_SPECS["mpmd_client"], mesh),
        "mpmd_aggregate": (p1["aggregate"],
                           (state_av, c_aval((c, k, k), spec_c),
                            batch_av["mask"]),
                           AUDIT_SPECS["mpmd_aggregate"], mesh),
        "mpmd_chain": (pR["chain"], (state_av, batch_av),
                       AUDIT_SPECS["mpmd_chain"], mesh),
        "mpmd_metrics": (p1["metrics"], (raw1, batch_av["mask"]),
                         AUDIT_SPECS["mpmd_metrics"], mesh),
    }


class MpmdStep:
    """One chunk of the DAG, presented as the loop's ``step(state,
    batch) -> (new_state, metrics)`` contract.

    Every call issues the whole DAG asynchronously — chain (or
    client->aggregate at width 1) on the client slice, the raw-stat
    transfer, and the metrics program on the server slice — and returns
    with everything still in flight. The loop's pipelined pending
    machinery then overlaps this chunk's fetch under the NEXT chunk's
    dispatch, which is where the RTT disappears.
    """

    def __init__(self, programs: Dict[str, Any], *, width: int,
                 server_mesh, tracer=None):
        self._p = programs
        self._width = width
        self._server_sharding = replicated_sharding(server_mesh)
        self._tracer = tracer
        self._chunk_ids = itertools.count()

    def _event(self, stage: str, rnd, trace_id: str, dur_s: float) -> None:
        tr = self._tracer
        if tr is not None and tr.enabled:
            tr.event("trace", phase=stage, round=rnd, dur_s=dur_s,
                     trace_id=trace_id, op="mpmd", rounds=self._width)

    def __call__(self, state, batch):
        tid = f"mpmd-{next(self._chunk_ids)}"
        rnd = None
        # Dispatch timing brackets ASYNC enqueues on purpose: the whole
        # point of the DAG is that these clocks close before the device
        # work does, so the spans measure host dispatch cost, not
        # compute. A sync here would re-serialize the pipeline.
        t0 = time.perf_counter()  # fedtpu: noqa[FTP010] dispatch-cost span: timing the async enqueue itself; a sync would defeat the MPMD overlap
        if self._width == 1:
            state, loss, conf = self._p["client"](state, batch)
            t1 = time.perf_counter()  # fedtpu: noqa[FTP010] dispatch-cost span (see above)
            self._event("client_step", rnd, tid, t1 - t0)
            state, pooled_conf = self._p["aggregate"](state, conf,
                                                      batch["mask"])
            raw = {"loss": loss, "conf": conf, "pooled_conf": pooled_conf}
        else:
            state, raw = self._p["chain"](state, batch)
            t1 = time.perf_counter()  # fedtpu: noqa[FTP010] dispatch-cost span (see above)
            self._event("client_step", rnd, tid, t1 - t0)
        t2 = time.perf_counter()  # fedtpu: noqa[FTP010] dispatch-cost span (see above)
        self._event("aggregate", rnd, tid, t2 - t1)
        # Metrics sub-program: compiled against the client mesh's
        # shardings (its cross-client reductions must partition exactly
        # like the monolithic oracle's for bitwise parity), then the
        # finished metric dict — a few KB — crosses to the server slice
        # asynchronously. The host fetch drains single-device buffers
        # there while the next chunk's client step is already in flight;
        # client-slice params/opt-state never move.
        metrics = self._p["metrics"](raw, batch["mask"])
        metrics = jax.device_put(metrics, self._server_sharding)
        t3 = time.perf_counter()  # fedtpu: noqa[FTP010] dispatch-cost span (see above)
        self._event("metrics", rnd, tid, t3 - t2)
        return state, metrics


def build_mpmd_step(cfg, *, mesh, apply_fn, tx, num_classes: int,
                    state, batch, width: int, cache=None,
                    tracer=None) -> MpmdStep:
    """Wire the whole DAG for one chunk width: build the jit wrappers,
    AOT-compile each on its slice (through ``cache`` when given), and
    return the loop-ready :class:`MpmdStep`."""
    validate_mpmd_config(cfg)
    programs = build_mpmd_programs(
        mesh, apply_fn, tx, num_classes,
        weighting=cfg.fed.weighting, aggregation=cfg.fed.aggregation,
        local_steps=cfg.fed.local_steps, prox_mu=cfg.fed.prox_mu,
        rounds_per_step=width)
    srv = server_submesh(mesh)
    srv_sharding = replicated_sharding(srv)

    config_slice = None
    if cache is not None:
        from fedtpu.compilation.warmup import program_config_slice
        config_slice = dict(program_config_slice(cfg), mpmd=True)

    state_av, batch_av = _avals(state), _avals(batch)
    k = num_classes
    c = batch["mask"].shape[0]
    compiled: Dict[str, Any] = {}

    def aot(name, fn, args, prog_mesh, extra=None):
        span = tracer.span("mpmd_compile", program=name) if tracer \
            else None
        out, _ = _aot(fn, args, label=f"mpmd_{name}", mesh=prog_mesh,
                      cache=cache, config_slice=config_slice, extra=extra)
        if span is not None:
            span.end()
        compiled[name] = out

    from jax.sharding import NamedSharding

    def c_aval(shape, spec):
        return jax.ShapeDtypeStruct(shape, jnp.float32,
                                    sharding=NamedSharding(mesh, spec))

    spec_c = P(CLIENTS_AXIS)
    spec_rc = P(None, CLIENTS_AXIS)
    if width == 1:
        aot("client", programs["client"], (state_av, batch_av), mesh)
        aot("aggregate", programs["aggregate"],
            (state_av, c_aval((c, k, k), spec_c), batch_av["mask"]), mesh)
        raw_av = {"loss": c_aval((c,), spec_c),
                  "conf": c_aval((c, k, k), spec_c),
                  "pooled_conf": c_aval((k, k), P())}
    else:
        aot("chain", programs["chain"], (state_av, batch_av), mesh,
            extra={"rounds_per_step": width})
        raw_av = {"loss": c_aval((width, c), spec_rc),
                  "conf": c_aval((width, c, k, k), spec_rc),
                  "pooled_conf": c_aval((width, k, k), P())}
    # The metrics program compiles on the CLIENT mesh against the raw
    # stats' live shardings: masked_client_mean's cross-client sum must
    # partition exactly like the monolithic oracle's for bitwise parity.
    # Its (tiny, replicated) outputs are what cross to the server slice.
    aot("metrics", programs["metrics"], (raw_av, batch_av["mask"]),
        mesh, extra={"rounds_per_step": width})

    return MpmdStep(compiled, width=width, server_mesh=srv,
                    tracer=tracer)


def parity_check(preset: str = "income-8", *, rounds: int = 4,
                 synthetic_rows: int = 256) -> dict:
    """Bitwise MPMD-vs-monolithic parity probe (``fedtpu check --mpmd``).

    Runs the preset twice on small synthetic data — once through the
    monolithic oracle, once through the MPMD DAG — and compares the
    recorded metric history and the final parameters bitwise.  Any
    drift (a reassociated cross-client sum, a sharding change in a
    sub-program, a round dropped at a chunk boundary) fails the gate;
    there is no tolerance knob on purpose.
    """
    import dataclasses

    import numpy as np

    from fedtpu.config import get_preset
    from fedtpu.orchestration.loop import run_experiment

    base = get_preset(preset)
    # Chunk width > 1 so the scanned chain program — the production
    # configuration — is what's being compared, not just the 2-program
    # special case.
    width = max(1, rounds // 2)
    base = dataclasses.replace(
        base,
        data=dataclasses.replace(base.data, csv_path=None,
                                 dataset_name=None,
                                 synthetic_rows=synthetic_rows),
        fed=dataclasses.replace(base.fed, rounds=rounds),
        run=dataclasses.replace(base.run, rounds_per_step=width))

    mono = run_experiment(
        dataclasses.replace(base, run=dataclasses.replace(
            base.run, rounds_per_step=width, mpmd=False)),
        verbose=False)
    mp = run_experiment(
        dataclasses.replace(base, run=dataclasses.replace(
            base.run, rounds_per_step=width, mpmd=True)),
        verbose=False)

    metric_mismatches = []
    for key in sorted(set(mono.global_metrics) | set(mp.global_metrics)):
        a = np.asarray(mono.global_metrics.get(key))
        b = np.asarray(mp.global_metrics.get(key))
        if a.shape != b.shape or not np.array_equal(a, b):
            metric_mismatches.append(key)
    param_leaf_mismatches = sum(
        not np.array_equal(np.asarray(pa), np.asarray(pb))
        for pa, pb in zip(jax.tree_util.tree_leaves(mono.final_params),
                          jax.tree_util.tree_leaves(mp.final_params)))
    ok = (not metric_mismatches and param_leaf_mismatches == 0
          and mono.rounds_run == mp.rounds_run)
    return {
        "ok": bool(ok),
        "preset": preset,
        "rounds": rounds,
        "width": width,
        "rounds_run": [mono.rounds_run, mp.rounds_run],
        "metric_mismatches": metric_mismatches,
        "param_leaf_mismatches": int(param_leaf_mismatches),
    }
