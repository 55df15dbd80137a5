"""Asynchronous federated aggregation (FedBuff-style), simulated in-graph.

The reference — and fedtpu's synchronous engines — advance in lockstep
rounds: every client trains from the same global and the server waits for
all of them (the MPI barrier structure of FL_CustomMLP...:142,201 IS that
lockstep). Real federations are asynchronous: clients pull the global at
different times, train against STALE versions, and the server folds in
updates as they arrive (FedAsync, Xie et al. 2019; FedBuff, Nguyen et al.
2022). This module simulates that regime deterministically inside one
jit-compiled scan, so staleness effects are studyable on-TPU without a
wall-clock event loop:

- every client carries an ANCHOR — the global version it last pulled —
  and the server tick it pulled at;
- each server tick, a Bernoulli(arrival_rate) draw marks which clients
  COMPLETE this tick (the in-graph stand-in for heterogeneous client
  speed); completing clients train ``local_steps`` full-batch steps from
  their anchor and ship ``delta_i = trained_i - anchor_i`` with staleness
  ``s_i = tick - pull_tick_i``;
- the server applies the arrival-mean of deltas, each discounted by
  ``1 / sqrt(1 + s_i)`` (FedBuff's staleness weight; ``staleness_power=0``
  disables discounting), scaled by ``server_lr`` — every arrival tick by
  default, or, with ``buffer_size=M >= 2``, only once M updates have
  accumulated in the server buffer (TRUE FedBuff's K-buffer apply rule;
  the buffer persists in the state across calls and checkpoints);
- completing clients re-pull: anchor <- the new global, pull_tick <- tick.
  Clients that did not complete keep their anchor — their eventual update
  grows STALER, which is exactly the dynamic under study.

Degenerate-case contract (test-pinned): ``arrival_rate=1`` with
``staleness_power=0`` and ``server_lr=1`` is EXACTLY the synchronous
uniform delta path — every client pulls every tick, staleness is
identically zero, and the arrival mean is the plain client mean.

State layout mirrors the synchronous engines: per-client params/opt_state
/anchors sharded over the ``('clients',)`` mesh axis, the global derived
on the fly (anchors of just-pulled clients), pull ticks a small per-client
int vector. The whole tick — train, discounted aggregation, re-pull — is
one ``lax.scan`` body under ``shard_map``, ``ticks_per_step`` ticks per
compiled call, donated state.
"""

from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import PartitionSpec as P

from fedtpu.parallel.mesh import CLIENTS_AXIS, client_sharding
from fedtpu.parallel.round import (assemble_metrics, bcast_global,
                                   client_init_keys)
from fedtpu.training.client import (make_local_eval_step,
                                    make_local_train_step)
from fedtpu.training.task import classification_task

# Read-only audit hook (fedtpu.analysis.program): the FedBuff tick's
# traced entry point + donation contract, consumed by the SPMD auditor.
AUDIT_SPEC = {
    "engine": "async",
    "builder": "build_async_round_fn",
    "donate_argnums": (0,),
    "collective_axes": (CLIENTS_AXIS,),
}


def record_tick_telemetry(registry, tracer, tick: int, staleness) -> None:
    """Fold one tick's (C,) staleness vector into the metrics registry
    (tick counter, staleness histogram, last-mean gauge) and emit the
    per-tick ``async_tick`` event. Called by the host round loop on the
    ALREADY-FETCHED numpy staleness — no device sync here; pure host
    bookkeeping shared so the loop and any external driver agree on what
    an async tick records."""
    s = np.ravel(np.asarray(staleness, dtype=np.float64))
    registry.counter("async_ticks").inc()
    registry.histogram("staleness").observe_many(s)
    mean = float(s.mean()) if s.size else 0.0
    registry.gauge("staleness_last_mean").set(mean)
    tracer.event("async_tick", round=tick, staleness_mean=mean,
                 staleness_max=float(s.max()) if s.size else 0.0)


def init_async_state(key: jax.Array, mesh, num_clients: int,
                     init_fn: Callable, tx: optax.GradientTransformation,
                     same_init: bool = True,
                     buffer_size: int = 0,
                     screen_window: int = 0) -> dict:
    """Per-client state + anchors. Every client starts having just pulled
    the shared initial global (the uniform mean of the inits), tick 0.
    ``buffer_size >= 2`` adds the FedBuff server buffer
    (``buf_delta``/``buf_count``, replicated, empty) so it persists across
    compiled calls and checkpoints. ``screen_window >= 1`` adds the
    defense screen's rolling norm ring (``screen_norms``/``screen_count``,
    replicated, empty) — required by ``build_async_round_fn(...,
    screen=True)`` so the rolling median survives calls and checkpoints."""
    params = jax.vmap(init_fn)(client_init_keys(key, num_clients, same_init))
    g0 = jax.tree.map(lambda p: p.mean(axis=0), params)
    anchors = jax.tree.map(
        lambda g, p: jnp.broadcast_to(g[None], p.shape).astype(p.dtype),
        g0, params)
    shard = client_sharding(mesh)
    # safe_put: no implicit cross-process equality broadcast per leaf
    # under jax.distributed (fedtpu.parallel.multihost.safe_put).
    from fedtpu.parallel.multihost import safe_put
    put = lambda t: safe_put(t, shard)
    anchors = jax.tree.map(put, anchors)
    extra = {}
    from fedtpu.parallel.mesh import replicated_sharding
    rep = replicated_sharding(mesh)
    if buffer_size >= 2:
        extra = {
            "buf_delta": jax.tree.map(
                lambda gl: safe_put(
                    jnp.zeros(gl.shape, jnp.float32), rep), g0),
            "buf_count": safe_put(jnp.zeros((), jnp.float32), rep),
        }
    if screen_window >= 1:
        extra["screen_norms"] = safe_put(
            jnp.zeros((screen_window,), jnp.float32), rep)
        extra["screen_count"] = safe_put(jnp.zeros((), jnp.int32), rep)
    return {
        **extra,
        # params start equal to the anchors but must be INDEPENDENT
        # buffers: on a single-device mesh device_put of an already-placed
        # array is a no-op, and aliased params/anchors leaves make the
        # donating tick fail with "donate the same buffer twice" (found
        # the first time the engine ran on the real one-chip TPU — every
        # virtual-mesh test had one client per device).
        "params": jax.tree.map(jnp.copy, anchors),  # last trained local model
        "anchors": anchors,                         # pulled global per client
        "opt_state": jax.tree.map(put, jax.vmap(tx.init)(anchors)),
        "pull_tick": put(jnp.zeros((num_clients,), jnp.int32)),
        # Replicated from birth, matching the tick's output sharding — a
        # SingleDeviceSharding init retraces the second tick (fedtpu check).
        "round": safe_put(jnp.zeros((), jnp.int32), rep),
    }


def build_async_round_fn(mesh, apply_fn: Callable,
                         tx: optax.GradientTransformation, num_classes: int,
                         arrival_rate: float = 0.5,
                         arrival_seed: int = 0,
                         staleness_power: float = 0.5,
                         server_lr: float = 1.0,
                         local_steps: int = 1,
                         prox_mu: float = 0.0,
                         buffer_size: int = 0,
                         ticks_per_step: int = 1,
                         driven: bool = False,
                         screen: bool = False,
                         screen_norm_mult: float = 4.0,
                         screen_cos_min: float = -0.2,
                         screen_warmup: int = 8,
                         screen_window: int = 64,
                         clip_norm: float = 0.0) -> Callable:
    """Compile the async server tick. Returns ``step(state, batch) ->
    (state, metrics)`` over client-sharded batches, like the synchronous
    engines; ``metrics`` additionally carries ``staleness`` — the (R, C)
    per-client staleness at each tick (absentees report their CURRENT
    age, arrivals the staleness their shipped update had).

    ``staleness_power`` p: arrival i is discounted ``(1 + s_i)^-p``
    (p=0.5 is FedBuff's ``1/sqrt(1+s)``; p=0 disables discounting).

    ``buffer_size`` M >= 2 selects TRUE FedBuff server semantics (Nguyen
    et al. 2022): discounted deltas accumulate in a server-side buffer
    and the global only moves once M updates have arrived (then the
    buffer resets) — between applies, new arrivals pull the UNCHANGED
    global. M <= 1 applies every arrival tick (the FedAsync-with-cohorts
    cadence; M=1 is test-pinned bitwise identical to M=0, the default).
    Buffered state (``buf_delta``/``buf_count``) persists in the state
    dict across compiled calls and checkpoints; the buffer's pending
    contributions are, by design, NOT in the evaluated/checkpointed
    global until they apply. Requires ``init_async_state(...,
    buffer_size=M)`` so the state carries the buffer keys.

    ``driven=True`` replaces the in-graph Bernoulli arrival draw with an
    EXTERNALLY SUPPLIED arrival mask: the step becomes ``step(state,
    batch, arrivals)`` where ``arrivals`` is a ``(ticks_per_step, C)``
    0/1 float array — tick t trains exactly the clients ``arrivals[t]``
    marks. This is the serving front-end's ingestion hook
    (fedtpu.serving): real client arrivals, already through admission
    control, become the completion process instead of a synthetic rate.
    ``arrival_rate``/``arrival_seed`` are ignored when driven; every
    other knob (staleness discounting, server_lr, the K-buffer) applies
    identically, so trace-driven and synthetic numbers are directly
    comparable.

    In driven mode each arrival entry is a signed WEIGHT, not just a 0/1
    flag: entry ``w != 0`` means the client completed this tick and its
    delta enters aggregation scaled by ``w`` (honest arrivals are 1.0; a
    poisoned arrival carries ``-scale`` — the amplified sign-flip attack
    of the serving trace synthesizer's ``--poison-frac`` mode, injected
    through the existing ``tensordot(disc, delta)`` with zero new math).
    Every arrival/re-pull gate keys on ``w != 0``, so a poisoned client
    still pulls, trains, and ages like any other.

    ``screen=True`` (driven mode only; docs/robustness.md) inserts the
    STREAMING UPDATE SCREEN before the K-buffer: each arrival's submitted
    update ``w * delta`` is scored in-graph — non-finite guard, norm vs a
    rolling median of accepted norms (``screen_norm_mult`` x, after
    ``screen_warmup`` accepted ticks), and cosine vs the current server
    direction (the pending buffer plus this tick's norm-normalized
    arrival consensus; below ``screen_cos_min`` fails). A screened
    arrival is treated as if it never arrived: no param/opt update, no
    buffer fold, no re-pull (its staleness keeps growing), and its flag
    is surfaced in ``metrics['screened']`` for host-side strike
    accounting. The rolling-norm ring lives in the state
    (``init_async_state(..., screen_window=W)``) so screening decisions
    replay bitwise across checkpoint/restore. ``clip_norm > 0`` adds the
    FedBuff-side robust rule — per-arrival L2 clipping of the submitted
    update to ``clip_norm`` before the discounted sum (full-cohort order
    statistics don't apply to a K-buffer; a screened/clipped mean does).
    DONATES the input state — rebind, clone to keep."""
    if not 0.0 < arrival_rate <= 1.0:
        raise ValueError(f"arrival_rate must be in (0, 1], got "
                         f"{arrival_rate}")
    if staleness_power < 0:
        raise ValueError(f"staleness_power must be >= 0, got "
                         f"{staleness_power}")
    if server_lr <= 0:
        raise ValueError(f"server_lr must be > 0, got {server_lr}")
    if buffer_size < 0:
        raise ValueError(f"buffer_size must be >= 0, got {buffer_size}")
    if screen and not driven:
        raise ValueError("screen=True needs driven=True — the screen "
                         "scores externally submitted updates; the "
                         "synthetic Bernoulli completion process has "
                         "nothing to screen")
    if screen:
        if screen_window < 1:
            raise ValueError(f"screen_window must be >= 1, got "
                             f"{screen_window}")
        if not 1 <= screen_warmup <= screen_window:
            raise ValueError(f"need 1 <= screen_warmup <= screen_window, "
                             f"got warmup={screen_warmup} "
                             f"window={screen_window}")
        if screen_norm_mult <= 0:
            raise ValueError(f"screen_norm_mult must be > 0, got "
                             f"{screen_norm_mult}")
        if not -1.0 <= screen_cos_min < 1.0:
            raise ValueError(f"screen_cos_min must be in [-1, 1), got "
                             f"{screen_cos_min}")
    if clip_norm < 0:
        raise ValueError(f"clip_norm must be >= 0, got {clip_norm}")
    buffered = buffer_size >= 2
    need_norms = screen or clip_norm > 0
    # prox_mu's anchor is the params the step starts from — which here is
    # the client's pulled anchor, exactly the FedProx-against-stale-global
    # regularization FedBuff-style systems pair with many local steps.
    local_train = make_local_train_step(apply_fn, tx,
                                        local_steps=local_steps,
                                        prox_mu=prox_mu)
    local_eval = make_local_eval_step(
        classification_task(apply_fn, num_classes))
    n_devices = mesh.devices.size

    def tick_body(params, opt_state, anchors, pull, buf, nbuf, ring,
                  rcount, x, y, mask, rnd, arrivals):
        cb = x.shape[0]
        gidx = jax.lax.axis_index(CLIENTS_AXIS) * cb + jnp.arange(cb)

        def scan_tick(carry, arr):
            (params, opt_state, anchors, pull, buf, nbuf, ring, rcount,
             g, r) = carry

            def per_client(cond, a, b):
                return jnp.where(cond.reshape((cb,) + (1,) * (a.ndim - 1)),
                                 a, b)

            if driven:
                # The caller's admission layer decided who completes this
                # tick; `arr` is that (cb,) slice of the arrival mask —
                # SIGNED weights: nonzero means arrived, a negative entry
                # is the amplified sign-flip poison payload.
                arrive = arr.astype(jnp.float32)
            elif arrival_rate < 1.0:
                tick_key = jax.random.fold_in(
                    jax.random.key(arrival_seed), r)
                u = jax.vmap(lambda i: jax.random.uniform(
                    jax.random.fold_in(tick_key, i)))(gidx)
                arrive = (u < arrival_rate).astype(jnp.float32)
            else:
                arrive = jnp.ones((cb,), jnp.float32)
            arrived = arrive != 0.0

            trained, new_opt, loss = jax.vmap(local_train)(
                anchors, opt_state, x, y, mask)

            eps = 1e-12
            if need_norms:
                # The SUBMITTED update is w_i * delta_i — the arrival
                # weight is part of the submission, so an amplified
                # sign-flip inflates the norm and inverts the cosine.
                sq = sum(
                    jnp.square(tr.astype(jnp.float32)
                               - an.astype(jnp.float32)).reshape(
                                   cb, -1).sum(axis=1)
                    for tr, an in zip(jax.tree.leaves(trained),
                                      jax.tree.leaves(anchors)))
                norms = jnp.abs(arrive) * jnp.sqrt(sq)
            else:
                norms = jnp.zeros((cb,), jnp.float32)
            scr = jnp.zeros((cb,), jnp.float32)
            if screen:
                finite = jnp.ones((cb,), bool)
                for tr, an in zip(jax.tree.leaves(trained),
                                  jax.tree.leaves(anchors)):
                    d = tr.astype(jnp.float32) - an.astype(jnp.float32)
                    finite = finite & jnp.isfinite(d).reshape(
                        cb, -1).all(axis=1)
                # Server direction: the pending K-buffer plus this tick's
                # norm-normalized arrival consensus — each arrival votes
                # ONE unit vector, so magnitude cannot buy direction and
                # a sub-majority of attackers cannot flip the reference.
                w_unit = jnp.where(arrived & finite,
                                   arrive / jnp.maximum(norms, eps), 0.0)

                def dir_leaf(tr, an, b):
                    d = tr.astype(jnp.float32) - an.astype(jnp.float32)
                    return b + jax.lax.psum(
                        jnp.tensordot(w_unit, d, axes=1), CLIENTS_AXIS)

                u = jax.tree.map(dir_leaf, trained, anchors, buf)
                unorm = jnp.sqrt(sum(jnp.square(l).sum()
                                     for l in jax.tree.leaves(u)))
                dot = sum(
                    jnp.tensordot(
                        (tr.astype(jnp.float32)
                         - an.astype(jnp.float32)).reshape(cb, -1),
                        ul.reshape(-1), axes=1)
                    for tr, an, ul in zip(jax.tree.leaves(trained),
                                          jax.tree.leaves(anchors),
                                          jax.tree.leaves(u)))
                cosv = arrive * dot / (norms * unorm + eps)
                # Rolling median of the accepted-norm ring's valid slice.
                cnt = jnp.minimum(rcount, screen_window)
                vals = jnp.where(jnp.arange(screen_window) < cnt, ring,
                                 jnp.inf)
                srt = jnp.sort(vals)
                med = 0.5 * (
                    jax.lax.dynamic_index_in_dim(
                        srt, jnp.maximum((cnt - 1) // 2, 0),
                        keepdims=False)
                    + jax.lax.dynamic_index_in_dim(
                        srt, jnp.maximum(cnt // 2, 0), keepdims=False))
                warm = rcount >= screen_warmup
                n_tick = jax.lax.psum(
                    arrived.astype(jnp.float32).sum(), CLIENTS_AXIS)
                # The cosine screen needs a reference that is not the
                # update's own vote: at least two contributions (pending
                # buffer count + this tick's arrivals).
                dir_ok = (nbuf + n_tick) >= 2.0
                screened = arrived & (
                    ~finite
                    | (warm & (norms > screen_norm_mult * med))
                    | (dir_ok & (unorm > eps)
                       & (cosv < screen_cos_min)))
                scr = screened.astype(jnp.float32)
                arrived = arrived & ~screened
                arrive = jnp.where(arrived, arrive, 0.0)
                # Push one scalar per tick: the mean ACCEPTED norm (no
                # push on all-screened/empty ticks, so attackers cannot
                # drag the median by being rejected).
                acc = arrived.astype(jnp.float32)
                acc_n = jax.lax.psum((acc * norms).sum(), CLIENTS_AXIS)
                acc_c = jax.lax.psum(acc.sum(), CLIENTS_AXIS)
                mean_n = acc_n / jnp.maximum(acc_c, 1.0)
                pos = jnp.mod(rcount, screen_window)
                ring = jnp.where(acc_c > 0, ring.at[pos].set(mean_n),
                                 ring)
                rcount = rcount + (acc_c > 0).astype(jnp.int32)

            # A screened arrival is treated as if it never arrived from
            # here on: no param/opt adoption, no buffer fold, no re-pull
            # — its staleness keeps growing, so persistent offenders age
            # into the admission layer's staleness rejection too.
            params = jax.tree.map(partial(per_client, arrived),
                                  trained, params)
            opt_state = jax.tree.map(
                lambda a, b: (per_client(arrived, a, b)
                              if getattr(a, "ndim", 0) >= 1
                              and a.shape[:1] == (cb,) else a),
                new_opt, opt_state)

            stale = (r - pull).astype(jnp.float32)
            disc = arrive * (1.0 + stale) ** -staleness_power
            if clip_norm > 0:
                # Clipped-mean rule: the submitted update's contribution
                # is L2-clipped to clip_norm before the discounted sum.
                disc = disc * jnp.minimum(
                    1.0, clip_norm / jnp.maximum(norms, eps))
            n_arrived = jax.lax.psum(arrived.astype(jnp.float32).sum(),
                                     CLIENTS_AXIS)

            def summed(tr, an):
                delta = tr.astype(jnp.float32) - an.astype(jnp.float32)
                local = jnp.tensordot(disc, delta, axes=1)
                return jax.lax.psum(local, CLIENTS_AXIS)

            tick_sum = jax.tree.map(summed, trained, anchors)
            # Server buffer: this tick's discounted deltas join; the
            # global moves only once `apply_n` updates sit in the buffer,
            # divided by the realized arrival count (== the per-tick
            # arrival mean at M<=1, bitwise — the add of a zero buffer
            # and the same division land on identical floats).
            apply_n = buffer_size if buffered else 1
            buf = jax.tree.map(jnp.add, buf, tick_sum)
            nbuf = nbuf + n_arrived
            apply = nbuf >= apply_n
            g = jax.tree.map(
                lambda gl, b: jnp.where(
                    apply,
                    gl + server_lr
                    * (b / jnp.maximum(nbuf, 1.0)).astype(gl.dtype), gl),
                g, buf)
            buf = jax.tree.map(
                lambda b: jnp.where(apply, jnp.zeros_like(b), b), buf)
            nbuf = jnp.where(apply, 0.0, nbuf)
            # Arrivals re-pull the fresh global; absentees keep aging.
            anchors = jax.tree.map(
                lambda gl, an: per_client(arrived, bcast_global(gl, an),
                                          an),
                g, anchors)
            pull = jnp.where(arrived, r + 1, pull)

            conf = jax.vmap(local_eval)(params, x, y, mask)
            pooled = jax.lax.psum(conf.sum(axis=0), CLIENTS_AXIS)
            # Arrivals report the staleness their shipped update had;
            # absentees their current age — which is the same expression,
            # because `pull` only moved for arrivals and pre-update
            # `stale` already equals (r - pull) for everyone else.
            report_stale = stale
            return (params, opt_state, anchors, pull, buf, nbuf, ring,
                    rcount, g, r + 1), (loss, conf, pooled, report_stale,
                                        scr, norms, n_arrived)

        # The current global, reconstructed once per compiled call from
        # the FRESHEST anchor: arrivals re-pull the new global right after
        # every server update, so the max-pull slot always holds it (slot
        # 0 at init, where every client pulled the shared g0 at tick 0).
        pulls_all = jax.lax.all_gather(pull, CLIENTS_AXIS).reshape(-1)
        freshest = jnp.argmax(pulls_all)

        def pick_freshest(an):
            alla = jax.lax.all_gather(an, CLIENTS_AXIS)
            alla = alla.reshape((-1,) + alla.shape[2:])
            return jax.lax.dynamic_index_in_dim(alla, freshest,
                                                keepdims=False)

        g0 = jax.tree.map(pick_freshest, anchors)
        (params, opt_state, anchors, pull, buf, nbuf, ring, rcount, _,
         _), stacked = jax.lax.scan(
            scan_tick,
            (params, opt_state, anchors, pull, buf, nbuf, ring, rcount,
             g0, rnd),
            arrivals)
        loss, conf, pooled, stale, scr, norms, acc = stacked
        return (params, opt_state, anchors, pull, buf, nbuf, ring, rcount,
                loss, conf, pooled, stale, scr, norms, acc)

    spec_c = P(CLIENTS_AXIS)
    spec_rc = P(None, CLIENTS_AXIS)
    sharded = jax.shard_map(
        tick_body, mesh=mesh,
        in_specs=(spec_c, spec_c, spec_c, spec_c, P(), P(), P(), P(),
                  spec_c, spec_c, spec_c, P(), spec_rc),
        out_specs=(spec_c, spec_c, spec_c, spec_c, P(), P(), P(), P(),
                   spec_rc, spec_rc, P(), spec_rc, spec_rc, spec_rc, P()),
    )

    def _run(state, batch, arrivals):
        if buffered and "buf_delta" not in state:
            raise ValueError("buffer_size >= 2 needs a state initialized "
                             "with init_async_state(..., buffer_size=M)")
        if screen and "screen_norms" not in state:
            raise ValueError("screen=True needs a state initialized with "
                             "init_async_state(..., screen_window=W) — "
                             "'screen_norms' missing")
        if not screen and "screen_norms" in state:
            raise ValueError(
                "state carries the defense screen ring (built with "
                "screen_window=W) but this round_fn was built without "
                "screen=True — the rolling median would silently freeze; "
                "build the round_fn with screen=True")
        # M<=1 runs the same program with an all-zero buffer carry that
        # resets every arrival tick — no extra state keys, and bitwise
        # the per-tick apply (test-pinned).
        buf = (state["buf_delta"] if buffered else jax.tree.map(
            lambda a: jnp.zeros(a.shape[1:], jnp.float32),
            state["anchors"]))
        nbuf = (state["buf_count"] if buffered
                else jnp.zeros((), jnp.float32))
        if screen:
            ring = state["screen_norms"]
            if tuple(ring.shape) != (screen_window,):
                raise ValueError(
                    f"screen ring width {ring.shape} does not match "
                    f"screen_window={screen_window}")
            rcount = state["screen_count"]
        else:
            # Zero constants traced inside jit — no new arguments, so the
            # screen-off recompile surface / audit contract is unchanged.
            ring = jnp.zeros((1,), jnp.float32)
            rcount = jnp.zeros((), jnp.int32)
        (params, opt_state, anchors, pull, buf, nbuf, ring, rcount, loss,
         conf, pooled, stale, scr, norms, acc) = sharded(
            state["params"], state["opt_state"],
            state["anchors"], state["pull_tick"], buf, nbuf, ring, rcount,
            batch["x"], batch["y"], batch["mask"],
            state["round"], arrivals)
        metrics = assemble_metrics(loss, conf, pooled, batch["mask"],
                                   ticks_per_step)
        metrics["staleness"] = (stale if ticks_per_step > 1 else stale[0])
        if screen:
            first = ticks_per_step > 1
            metrics["screened"] = scr if first else scr[0]
            metrics["update_norms"] = norms if first else norms[0]
            metrics["accepted"] = acc if first else acc[0]
        new_state = {"params": params, "opt_state": opt_state,
                     "anchors": anchors, "pull_tick": pull,
                     "round": state["round"] + ticks_per_step}
        if buffered:
            new_state["buf_delta"] = buf
            new_state["buf_count"] = nbuf
        if screen:
            new_state["screen_norms"] = ring
            new_state["screen_count"] = rcount
        return new_state, metrics

    if driven:
        @partial(jax.jit, donate_argnums=(0,))
        def step(state, batch, arrivals):
            arrivals = jnp.asarray(arrivals, jnp.float32)
            return _run(state, batch, arrivals)
    else:
        @partial(jax.jit, donate_argnums=(0,))
        def step(state, batch):
            # The scan xs slot exists in both modes; here it is a traced
            # zero constant the Bernoulli branch never reads, so XLA
            # folds it away and the compiled program is the pre-driven
            # one.
            arrivals = jnp.zeros((ticks_per_step, batch["x"].shape[0]),
                                 jnp.float32)
            return _run(state, batch, arrivals)

    return step


@partial(jax.jit, static_argnums=(1,))
def read_client_slot(state, num_clients: int, slot):  # fedtpu: noqa[FTP003] read-only gather: the caller keeps training on `state` after persisting the slot; donating would invalidate the live engine state
    """The per-client leaves of engine slot ``slot``, as a flat list in
    :func:`fedtpu.parallel.round.per_client_view` order. ``slot`` is a
    traced index (one compile covers every slot). The serving engine's
    slot binder uses this to persist an evicted user's state into the
    client store before rebinding the slot to a newcomer."""
    from fedtpu.parallel.round import per_client_view
    return [l[slot] for l in per_client_view(state, num_clients)]


@partial(jax.jit, static_argnums=(1,), donate_argnums=(0,))
def write_client_slot(state, num_clients: int, slot, values):
    """Rebind engine slot ``slot``'s per-client leaves to ``values``
    (the :func:`read_client_slot` layout — store records round-trip
    bitwise). Donates the input state; the caller rebinds."""
    from fedtpu.parallel.round import per_client_view, with_per_client
    leaves = per_client_view(state, num_clients)
    new = [l.at[slot].set(jnp.asarray(v).astype(l.dtype))
           for l, v in zip(leaves, values)]
    return with_per_client(state, num_clients, new)


@jax.jit
def _freshest_anchor(pull_tick, anchors):
    idx = jnp.argmax(pull_tick)
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, idx, keepdims=False),
        anchors)


def async_global_params(state):
    """The freshest global: the anchor of the most recently pulled client.
    A jitted gather (not a host argmax+index) so it works when the
    client-sharded leaves are not host-addressable — multi-process meshes
    (fedtpu.parallel.multihost), where run_experiment evaluates and
    checkpoints through this exactly like the sync engines' slot 0."""
    return _freshest_anchor(state["pull_tick"], state["anchors"])
