"""CohortScheduler: stream sampled cohorts through a fixed-size engine.

The vmap engines hold every client on device; this scheduler holds only
``cohort_size`` slots and, per round, (1) SAMPLES a cohort (uniform /
weighted / trace-driven availability), (2) lazily initializes any
never-seen member in the :class:`~fedtpu.cohort.store.ClientStateStore`
(bitwise the same init the vmap path would have given it: the same
``client_init_keys`` table feeds ``init_fn``/``tx.init``), (3) STREAMS
the cohort's records host→device while the previous chunk computes
(double-buffered prefetch on one worker thread; the wait, if any, is
the ``cohort_prefetch_stall_s`` gauge), (4) runs ``cohorts_per_step``
cohorts as ONE compiled scan-over-cohorts with donated buffers, and
(5) writes the updated records back.

Round semantics are EXACTLY the plain-FedAvg vmap path's, op for op
(fedtpu.parallel.round's ``avg``): cohort members train from the carried
global (their own stored init on the very first round — the scan carry
is seeded with cohort 0's gathered params), the weighted mean runs as a
per-device partial ``tensordot`` followed by the configured cross-device
``make_all_reduce`` backend — hierarchical by construction: the local
tensordot is the per-chip reduction, psum/ring the cross-chip one — and
every slot receives the new global. With ``cohort_size == population``
(identity order) the two engines are bitwise-equal per round
(tests/test_cohort.py pins it). Optimizer moments are per-client and
never averaged, exactly as in the vmap path; they ride the store between
the rounds their owner participates in.

Within one compiled chunk the sampled cohorts are DISJOINT (one store
read/write per client per chunk — a client appearing twice would train
its second round from a stale optimizer record), so
``cohorts_per_step <= population // cohort_size``.

``run_cohort_experiment`` is the ``cohort_store=`` engine mode
``orchestration/loop.py`` delegates to when ``FedConfig.cohort_size >
0``: same config surface, same :class:`ExperimentResult`, same
reference early-stop rule, checkpoint/resume through the same orbax
layout (the store's touched records ride the checkpoint's meta item, so
engine state and store commit atomically).
"""

from __future__ import annotations

import dataclasses
import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from fedtpu.cohort.store import ClientStateStore
from fedtpu.ops.metrics import METRIC_NAMES, metrics_from_confusion
from fedtpu.parallel.mesh import CLIENTS_AXIS, make_mesh
from fedtpu.parallel.ring import make_all_reduce
from fedtpu.parallel.round import bcast_global, client_init_keys
from fedtpu.training.client import make_local_eval_step, make_local_train_step
from fedtpu.training.task import classification_task

# Read-only audit hook (fedtpu.analysis.program): the scan-over-cohorts
# chunk donates BOTH the carry state and the streamed xs buffers.
AUDIT_SPEC = {
    "engine": "cohort",
    "builder": "build_cohort_round_fn",
    "donate_argnums": (0, 1),
    # xs (arg 1) is donated to FREE the streamed chunk, not to alias it:
    # the prefetcher allocates the next chunk fresh, so no output exists
    # for x/y/mask to alias into.  Only state (arg 0) must round-trip.
    "alias_expected": (0,),
    "collective_axes": (CLIENTS_AXIS,),
}

SAMPLING_POLICIES = ("uniform", "weighted", "trace")


class CohortSampler:
    """Deterministic cohort sampling: ``sample(round0, num_cohorts)`` is a
    pure function of ``(seed, round0)`` — resume replays the same cohorts.

    - ``uniform``: distinct ids uniformly at random; the full-population
      draw (``num_cohorts * cohort_size == total``) returns IDENTITY
      order — everyone participates, and id order is what makes the
      reduction bitwise-comparable to the vmap path.
    - ``weighted``: distinct ids, probability proportional to a
      caller-supplied nonnegative ``weights`` array (O(total) host work,
      the documented cost of weighted sampling).
    - ``trace``: availability-driven — cohorts are the next distinct
      user ids from a serving trace's arrival order (wrapping), so the
      participation process is the measured one, not a model.
    """

    def __init__(self, total_clients: int, cohort_size: int,
                 policy: str = "uniform", seed: int = 0,
                 weights: Optional[np.ndarray] = None,
                 trace_users: Optional[np.ndarray] = None):
        if policy not in SAMPLING_POLICIES:
            raise ValueError(f"cohort_sampling must be one of "
                             f"{SAMPLING_POLICIES}, got {policy!r}")
        if not 0 < cohort_size <= total_clients:
            raise ValueError(f"cohort_size must be in [1, total_clients="
                             f"{total_clients}], got {cohort_size}")
        self.total = int(total_clients)
        self.k = int(cohort_size)
        self.policy = policy
        self.seed = int(seed)
        # Quarantined ids (fedtpu.robust): refuse() removes them from
        # every future draw. Empty set = the exact pre-defense sampling
        # code path, bitwise (the parity tests pin it).
        self.quarantined: set = set()
        if policy == "weighted":
            if weights is None:
                raise ValueError("weighted sampling needs a weights array")
            w = np.asarray(weights, np.float64)
            if w.shape != (self.total,) or (w < 0).any() or w.sum() <= 0:
                raise ValueError("weights must be (total_clients,) "
                                 "nonnegative with a positive sum")
            self.p = w / w.sum()
        if policy == "trace":
            if trace_users is None:
                raise ValueError("trace sampling needs the trace's user "
                                 "id sequence (cohort_trace path)")
            tu = np.asarray(trace_users, np.int64)
            if tu.size == 0:
                raise ValueError("trace has no arrivals")
            if tu.min() < 0 or tu.max() >= self.total:
                raise ValueError(
                    f"trace user ids span [{tu.min()}, {tu.max()}] — "
                    f"outside the population [0, {self.total})")
            self.trace_users = tu

    def refuse(self, ids) -> None:
        """Quarantine ``ids`` (fedtpu.robust): no future sample() ever
        includes them. Raises if the surviving population cannot fill
        one cohort — a defense that quarantines the training population
        away must fail loudly, not sample ghosts."""
        self.quarantined |= {int(i) for i in np.atleast_1d(
            np.asarray(ids, np.int64))}
        if self.total - len(self.quarantined) < self.k:
            raise ValueError(
                f"{len(self.quarantined)} quarantined ids leave fewer "
                f"than cohort_size={self.k} of {self.total} clients — "
                "population exhausted (raise the population or review "
                "the quarantine thresholds, docs/robustness.md)")

    def sample(self, round0: int, num_cohorts: int = 1) -> np.ndarray:
        """``(num_cohorts, cohort_size)`` int64 ids, distinct across the
        WHOLE chunk (see the module docstring's disjointness contract).
        Quarantined ids never appear."""
        need = num_cohorts * self.k
        q = self.quarantined
        if need > self.total - len(q):
            raise ValueError(
                f"{num_cohorts} disjoint cohorts of {self.k} need "
                f"{need} distinct clients, population is {self.total}"
                + (f" minus {len(q)} quarantined" if q else ""))
        if self.policy == "trace":
            ids = self._from_trace(round0, need)
        elif self.policy == "weighted":
            rng = np.random.default_rng((self.seed, round0))
            p = self.p
            if q:
                p = p.copy()
                p[sorted(q)] = 0.0
                if p.sum() <= 0:
                    raise ValueError("quarantine removed every positively "
                                     "weighted client")
                p = p / p.sum()
            ids = rng.choice(self.total, size=need, replace=False, p=p)
        elif need == self.total and not q:
            # Full participation: identity order, no draw — the ordering
            # the bitwise vmap-parity contract pins.
            ids = np.arange(self.total, dtype=np.int64)
        else:
            rng = np.random.default_rng((self.seed, round0))
            if need * 8 >= self.total - len(q):
                perm = rng.permutation(self.total)
                ids = np.array([c for c in perm if c not in q][:need],
                               np.int64)
            else:
                # Rejection sampling: O(need) for need << total — a
                # permutation would allocate the whole population.
                seen: set = set()
                out = []
                while len(out) < need:
                    for c in rng.integers(0, self.total,
                                          size=2 * (need - len(out))):
                        if c not in seen and c not in q:
                            seen.add(int(c))
                            out.append(int(c))
                            if len(out) == need:
                                break
                ids = np.array(out, np.int64)
        return np.asarray(ids, np.int64).reshape(num_cohorts, self.k)

    def _from_trace(self, round0: int, need: int) -> np.ndarray:
        tu = self.trace_users
        start = (round0 * self.k) % tu.size
        seen: set = set()
        out = []
        for i in range(2 * tu.size):
            u = int(tu[(start + i) % tu.size])
            if u not in seen and u not in self.quarantined:
                seen.add(u)
                out.append(u)
                if len(out) == need:
                    return np.array(out, np.int64)
        raise ValueError(
            f"trace holds only {len(seen)} distinct users (quarantined "
            f"excluded), cohort chunk needs {need} — shrink cohort_size/"
            "rounds_per_step or widen the trace")


def build_cohort_round_fn(mesh, apply_fn: Callable, tx, num_classes: int,
                          weighting: str = "data_size",
                          cohorts_per_step: int = 1,
                          aggregation: str = "psum",
                          local_steps: int = 1,
                          prox_mu: float = 0.0,
                          robust: str = "none",
                          trim_ratio: float = 0.1) -> Callable:
    """Compile the scan-over-cohorts chunk. Returns ``step(state, xs) ->
    (state, out)`` where ``state = {params (K,...), round}`` carries the
    global between cohorts (every slot identical after a round — the
    vmap-path invariant) and ``xs`` stacks ``cohorts_per_step`` cohorts'
    streamed inputs: ``opt (S,K,...), x/y/mask (S,K,N,...)``. ``out``
    returns the per-cohort post-round slot params and optimizer state —
    (S,K,...), exactly what the store writes back — plus the stacked
    metric dicts. DONATES state AND xs (the streamed buffers are consumed
    in place; the prefetcher allocates the next chunk's).

    The per-cohort body is the plain-averaging vmap round, op for op —
    that identity is the parity contract, so this program supports
    exactly what that path supports (no DP / compress / scaffold;
    ``run_cohort_experiment`` rejects those loudly).

    ``robust`` in ``('median', 'trimmed_mean')`` replaces the weighted
    mean with MASK-AWARE coordinate order statistics over the cohort
    block (fedtpu.robust; docs/robustness.md): dataless slots pad to
    +inf, the order statistic runs over the participating count only,
    and a fully dataless cohort carries the global unchanged — the same
    semantics the vmap path's sampling-aware rules use. Requires
    uniform weighting and the psum backend (an all_gather replaces the
    tensordot reduction; the audit goldens pin the new schedule)."""
    if robust not in ("none", "median", "trimmed_mean"):
        raise ValueError(
            f"cohort robust must be 'none', 'median' or 'trimmed_mean', "
            f"got {robust!r} (krum/geometric_median score whole updates "
            "and stay vmap-engine-only)")
    if robust != "none":
        if weighting != "uniform":
            raise ValueError("cohort robust aggregation is unweighted — "
                             "median/trimmed_mean of weighted updates is "
                             "not the weighted robust location; use "
                             "weighting='uniform'")
        if aggregation != "psum":
            raise ValueError("cohort robust aggregation needs the plain "
                             "psum backend (order statistics gather the "
                             "cohort block; the ring backend reduces)")
        if not 0.0 <= trim_ratio < 0.5:
            raise ValueError(f"trim_ratio must be in [0, 0.5), got "
                             f"{trim_ratio}")
    local_train = make_local_train_step(apply_fn, tx,
                                        local_steps=local_steps,
                                        prox_mu=prox_mu)
    local_eval = make_local_eval_step(
        classification_task(apply_fn, num_classes))
    n_devices = mesh.devices.size
    all_reduce = make_all_reduce(aggregation, CLIENTS_AXIS, n_devices)

    def chunk_body(params, opt_xs, x_xs, y_xs, m_xs, rnd):
        def one_cohort(carry, xs):
            params, r = carry
            opt_state, x, y, mask = xs
            n = mask.sum(axis=1)
            base_w = n if weighting == "data_size" else jnp.ones_like(n)
            trained, new_opt, loss = jax.vmap(local_train)(
                params, opt_state, x, y, mask)
            w = base_w
            conf = jax.vmap(local_eval)(trained, x, y, mask)
            total_w = all_reduce(w.sum())

            def avg(p):
                # The vmap path's reduction verbatim: per-device partial
                # sums (the per-chip stage), then the configured
                # cross-device backend (psum or the explicit ring).
                local = jnp.tensordot(w.astype(jnp.float32),
                                      p.astype(jnp.float32), axes=1)
                glob = all_reduce(local) / jnp.maximum(total_w, 1.0)
                # A fully dataless cohort (total_w == 0) skips averaging,
                # like the vmap path's zero-participant round.
                return jnp.where(total_w > 0, bcast_global(glob, p), p)

            if robust != "none":
                # Mask-aware order statistics over the WHOLE cohort
                # block: gather the K slot params, pad dataless slots to
                # +inf so they sort past every live value, and take the
                # statistic over the participating count (traced).
                part = (n > 0).astype(jnp.float32)
                part_all = jax.lax.all_gather(
                    part, CLIENTS_AXIS).reshape(-1)       # (K,)
                n_act = part_all.sum()
                n_i = n_act.astype(jnp.int32)
                k_t = jnp.round(trim_ratio * n_act).astype(jnp.int32)

                def ragg(p):
                    allc = jax.lax.all_gather(p.astype(jnp.float32),
                                              CLIENTS_AXIS)
                    allc = allc.reshape((-1,) + p.shape[1:])   # (K, ...)
                    live = part_all.reshape(
                        (-1,) + (1,) * (allc.ndim - 1))
                    srt = jnp.sort(jnp.where(live > 0, allc, jnp.inf),
                                   axis=0)
                    if robust == "median":
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
                            n_act - 2.0 * k_t.astype(jnp.float32), 1.0)
                        glob = jnp.where(keep, srt, 0.0).sum(
                            axis=0) / denom
                    return jnp.where(n_act > 0,
                                     bcast_global(glob, p), p)

                new_params = jax.tree.map(ragg, trained)
            else:
                new_params = jax.tree.map(avg, trained)
            pooled = jax.lax.psum(conf.sum(axis=0), CLIENTS_AXIS)
            return (new_params, r + 1), (new_params, new_opt, loss, conf,
                                         pooled)

        (params, _), stacked = jax.lax.scan(
            one_cohort, (params, rnd), (opt_xs, x_xs, y_xs, m_xs))
        par_ys, opt_ys, loss, conf, pooled = stacked
        return params, par_ys, opt_ys, loss, conf, pooled

    spec_c = P(CLIENTS_AXIS)
    spec_sc = P(None, CLIENTS_AXIS)            # (cohorts, clients, ...)
    sharded = jax.shard_map(
        chunk_body, mesh=mesh,
        in_specs=(spec_c, spec_sc, spec_sc, spec_sc, spec_sc, P()),
        out_specs=(spec_c, spec_sc, spec_sc, spec_sc, spec_sc, P()))

    @partial(jax.jit, donate_argnums=(0, 1))
    def step(state, xs):
        params, par_ys, opt_ys, loss, conf, pooled = sharded(
            state["params"], xs["opt"], xs["x"], xs["y"], xs["mask"],
            state["round"])
        per_client = jax.vmap(jax.vmap(metrics_from_confusion))(conf)
        nonempty = (xs["mask"].sum(axis=2) > 0).astype(jnp.float32)
        denom = jnp.maximum(nonempty.sum(axis=1), 1.0)
        metrics = {
            "loss": loss,
            "per_client": per_client,
            "client_mean": jax.tree.map(
                lambda v: (v * nonempty).sum(axis=-1) / denom, per_client),
            "pooled": jax.vmap(metrics_from_confusion)(pooled),
        }
        new_state = {"params": params,
                     "round": state["round"] + cohorts_per_step}
        return new_state, {"params": par_ys, "opt": opt_ys,
                           "metrics": metrics}

    return step


class CohortScheduler:
    """Owns the store, the sampler, the compiled chunk program, and the
    prefetch pipeline. ``run_chunk()`` advances ``cohorts_per_step``
    rounds and returns the chunk's host metrics; the engine state between
    chunks is just the global model in K slots plus the round counter
    (everything per-client lives in the store)."""

    def __init__(self, mesh, store: ClientStateStore, sampler: CohortSampler,
                 init_fn: Callable, tx, apply_fn: Callable, num_classes: int,
                 data_fn: Callable, init_key, same_init: bool = False,
                 weighting: str = "data_size", aggregation: str = "psum",
                 local_steps: int = 1, prox_mu: float = 0.0,
                 cohorts_per_step: int = 1, prefetch: bool = True,
                 robust: str = "none", trim_ratio: float = 0.1,
                 registry=None, tracer=None):
        self.mesh = mesh
        self.store = store
        self.sampler = sampler
        self.data_fn = data_fn
        self.k = sampler.k
        self.s = int(cohorts_per_step)
        self.tx = tx
        self.init_fn = init_fn
        self.registry = registry
        self.tracer = tracer
        self.step_fn = build_cohort_round_fn(
            mesh, apply_fn, tx, num_classes, weighting=weighting,
            cohorts_per_step=self.s, aggregation=aggregation,
            local_steps=local_steps, prox_mu=prox_mu,
            robust=robust, trim_ratio=trim_ratio)
        # Durable quarantine: records flagged in the store (by a serving
        # engine sharing it, or a prior run) never enter a cohort.
        flagged = store.quarantined_ids()
        if flagged.size:
            sampler.refuse(flagged)
        # The SAME per-client key table the vmap path's
        # init_federated_state derives — lazy store init must hand client
        # i the identical init the vmap engine would have (the bitwise
        # contract). The only O(population) host structure in the
        # scheduler: 8 bytes per client.
        self._key_table = np.asarray(jax.random.key_data(
            client_init_keys(jax.random.key(0) if init_key is None
                             else init_key, store.total_clients,
                             same_init)))
        # One-slot template tree: the store record <-> state-leaf mapping
        # (jax.tree flatten order of {"opt_state", "params"}).
        p1 = jax.tree.map(np.asarray, init_fn(jax.random.key(0)))
        self._slot_struct = jax.tree.structure(
            {"opt_state": tx.init(p1), "params": p1})
        self._init_batch = jax.jit(lambda keys: (
            lambda pp: {"opt_state": jax.vmap(tx.init)(pp), "params": pp}
        )(jax.vmap(init_fn)(jax.random.wrap_key_data(keys))))
        self._xs_shard = NamedSharding(mesh, P(None, CLIENTS_AXIS))
        self._state = None
        self._round = 0
        self._pool = ThreadPoolExecutor(max_workers=1) if prefetch else None
        self._next = None
        self._wb_done = threading.Event()
        self._wb_done.set()

    # -- host <-> store ------------------------------------------------
    def _ensure_init(self, ids: np.ndarray) -> None:
        """Lazily initialize never-seen members of one cohort. Computes
        the full cohort's inits (fixed K — one compile) and writes only
        the version-0 rows; initialized rows are never overwritten."""
        fresh = self.store.versions(ids) == 0
        if not fresh.any():
            return
        init_tree = self._init_batch(jnp.asarray(self._key_table[ids]))
        leaves = [np.asarray(l)[fresh]  # fedtpu: noqa[FTP001] lazy store init is a host-side path, off the compiled round
                  for l in jax.tree.leaves(init_tree)]
        self.store.write(np.asarray(ids)[fresh], leaves,
                         keys=self._key_table[ids][fresh],
                         participated=False)

    def seed_from_state(self, state, num_slots: int,
                        ids: np.ndarray) -> None:
        """Eagerly persist engine slots into the store: slot j of
        ``state`` becomes client ``ids[j]``'s record (version 1). Works
        for sync AND async state layouts (per_client_view order must
        match this store's template — build the store with
        ``state_template(state, num_slots)``)."""
        from fedtpu.parallel.round import per_client_view
        leaves = [np.asarray(l)  # fedtpu: noqa[FTP001] explicit state export to the host store
                  for l in per_client_view(state, num_slots)]
        self.store.write(ids, leaves, keys=self._key_table[ids],
                         participated=False)

    def _prepare(self, round0: int, wb_done=None) -> dict:
        """Sample + init + gather + device_put one chunk. Runs on the
        prefetch worker while the previous chunk computes. Sampling,
        lazy init, and data slicing overlap freely (they touch rows the
        in-flight chunk cannot write: its members were initialized at
        its OWN prep, so their versions are nonzero and lazy init skips
        them). The STORE READ must not — chunks overlap in membership
        across rounds, and reading a shared member before the previous
        writeback lands would hand round r+1 a round r-1 optimizer
        record — so it gates on the previous chunk's writeback event."""
        ids = self.sampler.sample(round0, self.s)          # (S, K)
        for s in range(self.s):
            self._ensure_init(ids[s])
        data = [self.data_fn(ids[s]) for s in range(self.s)]
        if wb_done is not None:
            wb_done.wait()
        host_opt, host_par = [], []
        for s in range(self.s):
            tree = jax.tree.unflatten(self._slot_struct,
                                      self.store.read(ids[s]))
            host_opt.append(tree["opt_state"])
            host_par.append(tree["params"])
        stack = lambda trees: jax.tree.map(
            lambda *ls: np.stack(ls, axis=0), *trees)
        from fedtpu.parallel.multihost import safe_put
        put = lambda t: jax.tree.map(
            lambda l: safe_put(np.asarray(l), self._xs_shard), t)
        sdata = stack(data)
        xs = {"opt": put(stack(host_opt)), "x": put(sdata["x"]),
              "y": put(sdata["y"]), "mask": put(sdata["mask"])}
        # Cohort 0's gathered params seed the engine's very first carry
        # (round-1 members train from their own stored inits, like vmap
        # round 1); once any round has run the carry holds the global and
        # gathered params are not transferred again.
        return {"ids": ids, "xs": xs,
                "params0": host_par[0] if self._state is None else None}

    def _take_prepared(self, round0: int) -> dict:
        if self._pool is None:
            return self._prepare(round0)
        if self._next is None:
            self._next = self._pool.submit(self._prepare, round0)
        t0 = time.perf_counter()
        prep = self._next.result()
        stall = time.perf_counter() - t0
        self._next = None
        if self.registry is not None:
            self.registry.gauge("cohort_prefetch_stall_s").set(stall)
            if stall > 1e-3:
                self.registry.counter("cohort_prefetch_stalls").inc()
        return prep

    def _schedule_next(self, round0: int, wb_done) -> None:
        if self._pool is not None and self._next is None:
            self._next = self._pool.submit(self._prepare, round0, wb_done)

    # -- engine state --------------------------------------------------
    def _init_state(self, params0) -> dict:
        from fedtpu.parallel.multihost import safe_put
        shard_c = NamedSharding(self.mesh, P(CLIENTS_AXIS))
        return {
            "params": jax.tree.map(
                lambda l: safe_put(np.asarray(l), shard_c), params0),
            "round": safe_put(jnp.zeros((), jnp.int32),
                              NamedSharding(self.mesh, P())),
        }

    @property
    def round(self) -> int:
        return self._round

    def state_for_checkpoint(self) -> dict:
        return self._state

    def restore(self, state, round0: int, store_arrays: dict) -> None:
        from fedtpu.parallel.multihost import safe_put
        shard_c = NamedSharding(self.mesh, P(CLIENTS_AXIS))
        self._state = {  # fedtpu: noqa[FTP011] restore() runs before the first run_chunk(), so no _prepare is in flight yet; _prepare only reads _state via the wb_done Event handoff armed inside run_chunk
            "params": jax.tree.map(
                lambda l: safe_put(np.asarray(l), shard_c),
                state["params"]),
            "round": safe_put(
                jnp.asarray(np.asarray(state["round"]), jnp.int32),
                NamedSharding(self.mesh, P())),
        }
        self._round = int(round0)
        self.store.restore_arrays(store_arrays)

    # -- the chunk -----------------------------------------------------
    def run_chunk(self) -> dict:
        """Advance ``cohorts_per_step`` rounds; returns host metrics with
        a leading (S,) cohort axis per leaf."""
        sp = (self.tracer.span("cohort_gather", round=self._round + self.s)
              if self.tracer else None)
        prep = self._take_prepared(self._round)
        if sp:
            sp.end()
        if self._state is None:
            self._state = self._init_state(prep["params0"])
        self._wb_done = threading.Event()
        self._schedule_next(self._round + self.s, self._wb_done)
        self._state, out = self.step_fn(self._state, prep["xs"])
        sp = (self.tracer.span("cohort_writeback",
                               round=self._round + self.s)
              if self.tracer else None)
        # ONE batched device->host fetch for slots + metrics; it is also
        # the chunk's completion proof (the caller times around it).
        for leaf in jax.tree.leaves(out):
            if hasattr(leaf, "copy_to_host_async"):
                leaf.copy_to_host_async()
        out = jax.tree.map(np.asarray, out)  # fedtpu: noqa[FTP001] chunk-boundary writeback fetch, the one host sync per S rounds
        for s in range(self.s):
            slot_tree = {"opt_state": jax.tree.map(lambda l: l[s],
                                                   out["opt"]),
                         "params": jax.tree.map(lambda l: l[s],
                                                out["params"])}
            self.store.write(prep["ids"][s], jax.tree.leaves(slot_tree))
        self._wb_done.set()       # unblock the next chunk's store read
        if sp:
            sp.end()
        if self.registry is not None:
            self.registry.gauge("client_store_resident_bytes").set(
                self.store.resident_estimate_bytes())
            self.registry.gauge("client_store_apparent_bytes").set(
                self.store.apparent_nbytes)
        self._round += self.s
        return {"ids": prep["ids"], "metrics": out["metrics"]}

    def close(self) -> None:
        # A half-finished chunk (exception between dispatch and
        # writeback) leaves the prefetch worker parked on the writeback
        # event; release it so shutdown(wait=True) cannot deadlock.
        self._wb_done.set()
        if self._pool is not None:
            self._pool.shutdown(wait=True)
        self.store.flush()


def _validate_cohort_config(cfg) -> None:
    """The cohort engine runs the plain-FedAvg path only (the parity
    contract); every composition the scan body does not reproduce is
    rejected loudly, mirroring build_experiment's async-branch style."""
    fed = cfg.fed
    if fed.cohort_size > cfg.shard.num_clients:
        raise ValueError(
            f"cohort_size={fed.cohort_size} exceeds the population "
            f"(num_clients={cfg.shard.num_clients})")
    if fed.client_store not in ("memory", "mmap"):
        raise ValueError("client_store must be 'memory' or 'mmap', got "
                         f"{fed.client_store!r}")
    if fed.async_mode:
        raise ValueError("cohort_size composes with the synchronous "
                         "engine only; the serving front-end is the "
                         "store-backed async path (docs/scaling.md)")
    if cfg.run.model_parallel > 1:
        raise ValueError("cohort mode requires the 1-D engine "
                         "(model_parallel=1)")
    if fed.participation_rate < 1.0:
        raise ValueError("cohort mode replaces in-graph client sampling "
                         "with the cohort sampler — use --cohort-sampling, "
                         "not --participation-rate")
    if (fed.server_opt != "none" or fed.dp_clip_norm > 0
            or fed.dp_noise_multiplier > 0 or fed.dp_adaptive_clip):
        raise ValueError("cohort mode supports plain FedAvg averaging "
                         "only (no server_opt / DP): the delta path's "
                         "replicated server state is not yet streamed "
                         "through the client store")
    if fed.robust_aggregation not in ("none", "median", "trimmed_mean"):
        raise ValueError(
            f"cohort mode supports robust_aggregation 'median'/"
            f"'trimmed_mean' only (mask-aware order statistics over the "
            f"cohort block); {fed.robust_aggregation!r} scores whole "
            "updates and needs the vmap engine's full population")
    if fed.robust_aggregation != "none" and fed.weighting != "uniform":
        raise ValueError("cohort robust aggregation is unweighted — set "
                         "weighting='uniform' (the median of weighted "
                         "updates is not the weighted robust location)")
    if fed.robust_aggregation != "none" and fed.aggregation != "psum":
        raise ValueError("cohort robust aggregation needs the plain psum "
                         "backend (order statistics gather the cohort "
                         "block)")
    if fed.byzantine_clients:
        raise ValueError("cohort mode does not inject synthetic byzantine "
                         "clients (byzantine_clients) — adversarial load "
                         "comes from poisoned serving traces "
                         "(serving/traces.py --poison-frac)")
    if fed.compress != "none":
        raise ValueError("cohort mode does not support compressed "
                         "exchange")
    if fed.scaffold:
        raise ValueError("cohort mode does not support SCAFFOLD")
    if fed.personalize_steps > 0:
        raise ValueError("cohort mode does not support personalize_steps")
    if fed.init_weights_npz:
        raise ValueError("cohort mode does not support init_weights_npz "
                         "warm starts yet")
    if cfg.run.on_divergence != "halt" or cfg.run.fault_plan:
        raise ValueError("cohort mode supports on_divergence='halt' only "
                         "(no rollback/fault-plan)")
    if cfg.run.pipelined_stop:
        raise ValueError("cohort mode does not support pipelined_stop "
                         "(the store writeback is the chunk boundary)")
    if fed.cohort_sampling == "trace" and not fed.cohort_trace:
        raise ValueError("cohort_sampling='trace' needs --cohort-trace "
                         "<trace.jsonl>")


def _store_path_for(cfg) -> Optional[str]:
    if cfg.fed.client_store != "mmap":
        return None
    if cfg.fed.client_store_path:
        return cfg.fed.client_store_path
    if cfg.run.checkpoint_dir:
        return os.path.join(cfg.run.checkpoint_dir, "client_store.bin")
    raise ValueError("client_store='mmap' needs --client-store-path (or a "
                     "checkpoint_dir to place client_store.bin under)")


def run_cohort_experiment(cfg, dataset=None, verbose: bool = True,
                          resume: bool = False):
    """The cohort-store engine's round loop: the ``run_experiment``
    delegate for ``cfg.fed.cohort_size > 0``. Same ExperimentResult, same
    reference early-stop rule (client-mean 4-metric vector, allclose
    within ``tolerance`` for ``termination_patience`` rounds), same
    checkpoint layout (+ the store's touched records in the meta item)."""
    from fedtpu.data import data_notice, load_dataset
    from fedtpu.data.sharding import pack_clients
    from fedtpu.models import build_model
    from fedtpu.ops import build_optimizer
    from fedtpu.orchestration.checkpoint import (latest_step, load_meta,
                                                 load_checkpoint,
                                                 retain_checkpoints,
                                                 save_checkpoint)
    from fedtpu.orchestration.loop import ExperimentResult
    from fedtpu.parallel.round import build_eval_fn
    from fedtpu.telemetry import (TelemetryLogger, default_registry,
                                  make_tracer)
    from fedtpu.utils.timing import Timer

    _validate_cohort_config(cfg)
    if jax.process_count() > 1:
        raise ValueError("cohort mode is single-process for now; the "
                         "store shards by id (ClientStateStore num_shards) "
                         "but the multi-host gather path is future work "
                         "(ROADMAP)")

    tel = cfg.run.telemetry
    tracer = make_tracer(tel.events_path)
    registry = default_registry()
    registry.reset()
    log = TelemetryLogger(verbose=verbose, tracer=tracer,
                          level=tel.log_level)

    ds = dataset if dataset is not None else load_dataset(cfg.data)
    log.info(data_notice(ds))
    model_cfg = cfg.model
    if model_cfg.kind == "mlp" and model_cfg.input_dim != ds.input_dim:
        model_cfg = dataclasses.replace(model_cfg, input_dim=ds.input_dim)
    if model_cfg.num_classes != ds.num_classes:
        model_cfg = dataclasses.replace(model_cfg,
                                        num_classes=ds.num_classes)
    init_fn, apply_fn = build_model(model_cfg)
    tx = build_optimizer(cfg.optim)

    total = cfg.shard.num_clients
    k = cfg.fed.cohort_size
    mesh = make_mesh(cfg.run.mesh_devices, k)
    packed = pack_clients(ds.x_train, ds.y_train, cfg.shard)
    px, py, pm = (np.asarray(packed.x), np.asarray(packed.y),
                  np.asarray(packed.mask))
    data_fn = lambda ids: {"x": px[ids], "y": py[ids], "mask": pm[ids]}

    weights = None
    trace_users = None
    if cfg.fed.cohort_sampling == "weighted":
        # Data-size-proportional availability — the principled default
        # weighting for tabular shards (clients with data show up).
        weights = pm.sum(axis=1)
    if cfg.fed.cohort_sampling == "trace":
        from fedtpu.serving.traces import load_trace_arrays
        _, _, trace_users_arr, _ = load_trace_arrays(cfg.fed.cohort_trace)
        trace_users = np.asarray(trace_users_arr, np.int64) % total
    sampler = CohortSampler(total, k, policy=cfg.fed.cohort_sampling,
                            seed=cfg.fed.cohort_seed, weights=weights,
                            trace_users=trace_users)

    p1 = jax.tree.map(np.asarray, init_fn(jax.random.key(0)))
    slot_tree = {"opt_state": tx.init(p1), "params": p1}
    template = [(tuple(np.shape(l)), np.asarray(l).dtype)
                for l in jax.tree.leaves(slot_tree)]
    store = ClientStateStore(template, total,
                             backend=cfg.fed.client_store,
                             path=_store_path_for(cfg))

    # Chunk width: disjoint cohorts bound it at total // k.
    s = max(1, min(cfg.run.rounds_per_step, total // k))
    sched = CohortScheduler(
        mesh, store, sampler, init_fn, tx, apply_fn, ds.num_classes,
        data_fn, jax.random.key(cfg.fed.init_seed),
        same_init=cfg.fed.same_init, weighting=cfg.fed.weighting,
        aggregation=cfg.fed.aggregation, local_steps=cfg.fed.local_steps,
        prox_mu=cfg.fed.prox_mu, cohorts_per_step=s,
        robust=cfg.fed.robust_aggregation, trim_ratio=cfg.fed.trim_ratio,
        registry=registry, tracer=tracer)

    history = {k2: [] for k2 in METRIC_NAMES}
    pooled_hist = {k2: [] for k2 in METRIC_NAMES}
    per_client_hist = {k2: [] for k2 in METRIC_NAMES}
    test_hist = {k2: [] for k2 in METRIC_NAMES}
    eval_step = None
    losses, sec_per_round = [], []
    prev_metric = None
    termination_count = cfg.fed.termination_patience
    stopped_early = False
    diverged = False
    rounds_run = 0
    start_round = 0

    ckdir = cfg.run.checkpoint_dir
    if resume and ckdir:
        step0 = latest_step(ckdir)
        if step0 is not None:
            state, hist, start_round = load_checkpoint(ckdir, step0)
            meta = load_meta(ckdir, step0)
            sched.restore(state, start_round, meta)
            for k2 in METRIC_NAMES:
                history[k2] = list(np.asarray(hist.get(k2, [])))
            if history[METRIC_NAMES[0]]:
                prev_metric = [history[k2][-1] for k2 in METRIC_NAMES]
            rounds_run = start_round
            log.info(f"Resumed cohort run at round {start_round} "
                     f"({len(store._touched)} touched records).")

    tracer.event("cohort_config", cohort_size=k, total_clients=total,
                 store=cfg.fed.client_store,
                 sampling=cfg.fed.cohort_sampling,
                 cohorts_per_step=s,
                 store_apparent_bytes=store.apparent_nbytes)

    timer = Timer().start()
    try:
        rnd = start_round
        while rnd < cfg.fed.rounds and not stopped_early and not diverged:
            take = min(s, cfg.fed.rounds - rnd)
            if take < s:
                # Tail chunk narrower than the compiled width: run the
                # full chunk and truncate host-side (the extra cohorts
                # still persist — they are real trained rounds; history
                # is what the round budget bounds).
                take = s
            chunk = sched.run_chunk()
            m = chunk["metrics"]
            dt = timer.lap() / s
            take = min(take, cfg.fed.rounds - rnd)
            tracer.event("span", phase="chunk", round=rnd + take,
                         dur_s=dt * take, rounds=take)
            for j in range(take):
                r = rnd + j
                client_mean = {k2: float(m["client_mean"][k2][j])
                               for k2 in METRIC_NAMES}
                losses.append(np.asarray(m["loss"][j]))
                sec_per_round.append(dt)
                rounds_run = r + 1
                for k2 in METRIC_NAMES:
                    history[k2].append(client_mean[k2])
                    pooled_hist[k2].append(float(m["pooled"][k2][j]))
                    per_client_hist[k2].append(
                        np.asarray(m["per_client"][k2][j]))
                registry.counter("rounds").inc()
                tracer.event(
                    "cohort_round", round=r + 1, dur_s=dt,
                    cohort_size=sampler.k,
                    accuracy=client_mean["accuracy"],
                    loss_mean=float(np.mean(losses[-1])),
                    store_resident_bytes=store.resident_estimate_bytes(),
                    prefetch_stall_s=float(
                        registry.gauge("cohort_prefetch_stall_s").value))
                if verbose and (r % cfg.run.log_every == 0):
                    gvals = ", ".join(f"{k2}: {client_mean[k2]:.4f}"
                                      for k2 in METRIC_NAMES)
                    log.parity(f"  Global Metrics (Round {r + 1}): "
                               f"[{gvals}]  ({dt * 1e3:.1f} ms/round, "
                               f"cohort {sampler.k}/{total})")
                cur = [client_mean[k2] for k2 in METRIC_NAMES]
                if cfg.run.halt_on_nonfinite and not (
                        np.all(np.isfinite(cur))
                        and np.all(np.isfinite(losses[-1]))):
                    log.warning(f"Non-finite loss/metrics at round "
                                f"{r + 1}; halting (diverged run).")
                    tracer.event("diverged", round=r + 1,
                                 reason=f"loss/metrics at round {r + 1}")
                    diverged = True
                    break
                if prev_metric is not None and np.allclose(
                        cur, prev_metric, atol=cfg.fed.tolerance):
                    termination_count -= 1
                    if termination_count == 0:
                        log.parity("Early stopping triggered: No "
                                   "significant change in metrics for "
                                   f"{cfg.fed.termination_patience} "
                                   "rounds.")
                        tracer.event("early_stop", round=r + 1)
                        stopped_early = True
                        break
                else:
                    prev_metric = cur
                    termination_count = cfg.fed.termination_patience
            # Held-out eval on the vmap loop's cadence: one appended row
            # per due round; due rounds inside one chunk share the
            # chunk-end global (the same documented approximation as
            # rounds_per_step > 1 there; exact at cohorts_per_step=1).
            if (cfg.run.eval_test_every and not diverged
                    and len(ds.x_test)):
                due = sum(1 for j in range(take)
                          if rnd + 1 + j <= rounds_run
                          and (rnd + 1 + j) % cfg.run.eval_test_every == 0)
                if due:
                    if eval_step is None:
                        eval_step = build_eval_fn(
                            classification_task(apply_fn, ds.num_classes))
                    glob = jax.tree.map(
                        lambda p: p[0],
                        sched.state_for_checkpoint()["params"])
                    tm = eval_step(glob, jnp.asarray(ds.x_test),
                                   jnp.asarray(ds.y_test))
                    for _ in range(due):
                        for k2 in METRIC_NAMES:
                            test_hist[k2].append(float(tm[k2]))
            rnd += s
            if (ckdir and cfg.run.checkpoint_every > 0
                    and not stopped_early and not diverged
                    and (rnd % cfg.run.checkpoint_every == 0
                         or rnd >= cfg.fed.rounds)):
                save_checkpoint(ckdir, sched.state_for_checkpoint(),
                                history, min(rnd, rounds_run),
                                extra_meta=store.checkpoint_arrays())
                if cfg.run.keep_checkpoints > 0:
                    retain_checkpoints(ckdir, cfg.run.keep_checkpoints)
    finally:
        sched.close()

    # The final global model = any slot of the carry (all identical
    # after a round); slot 0 by convention.
    final_params = {}
    if sched.state_for_checkpoint() is not None:
        final_params = jax.tree.map(
            lambda p: np.asarray(p[0]),  # fedtpu: noqa[FTP001] final model export after the loop
            sched.state_for_checkpoint()["params"])

    tracer.event("cohort_summary", rounds=rounds_run,
                 cohort_size=sampler.k, total_clients=total,
                 touched_records=len(store._touched),
                 store_resident_bytes=store.resident_estimate_bytes(),
                 store_apparent_bytes=store.apparent_nbytes,
                 prefetch_stalls=int(
                     registry.counter("cohort_prefetch_stalls").value))
    tracer.event("run_end", round=rounds_run, stopped_early=stopped_early,
                 diverged=diverged)
    tracer.counters(registry.snapshot())
    tracer.close()

    return ExperimentResult(
        global_metrics=history, pooled_metrics=pooled_hist,
        per_client_metrics=per_client_hist, test_metrics=test_hist,
        loss=losses, sec_per_round=sec_per_round, rounds_run=rounds_run,
        stopped_early=stopped_early, final_params=final_params,
        config=cfg, diverged=diverged)
