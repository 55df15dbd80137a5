"""The shared-global round: ONE copy of the model, clients one after another.

The resident engines (fedtpu.parallel.round and its siblings) keep a copy of
the parameters and of the optimizer state for every client on the clients
axis, which is what lets all clients train at once and what stops a model of
more than a few million parameters from entering any of them. This engine
keeps the state a federated server really has, ``{params, server_opt_state,
round}`` with no clients axis, and runs the round as its stages:

    client step   for each client in turn: start from the global, run one
                  epoch of local SGD steps over its rows (``local_batch_rows``
                  a step; 0 = the whole shard in one), no step on a padded
                  row: ``while``s over the client's own number of steps. The
                  first step reads the global itself; a working copy is
                  written only by a step that another follows
    combine       as the steps are taken: the pass that applies a gradient
                  ``d`` also adds the step's share of the client's delta,
                  ``-(w_c / W) lr d``, to one accumulator (``w_c`` the task's
                  data-size weight, or 1), since ``p_c - global`` is the sum
                  of the steps (for a leaf narrower than float32, which
                  rounds, the step as taken); across a mesh the clients axis
                  is sharded and the accumulator is ``psum``med once
    server apply  the server optimizer (fedtpu.ops.server_opt) on the mean
                  delta; FedAvgM accumulates straight into its momentum
                  buffer, so no separate accumulator exists
    metrics       from the training pass's own losses and statistics: there
                  is no second forward pass, and ``client_eval`` is empty

A client is stateless: plain SGD (momentum 0) at the round's learning rate
(StepLR stepped once a round, as the reference steps it), nothing carried to
the next round. The task (fedtpu.training.task) is an argument: the MLP and
the ConvNet run through here as the language model does. Two counters of
the engine's own go out beside the task's (``metrics["counters"]``):
``stateless_client_steps`` and ``stateless_working_copy_writes``, counted in
the loops' carries where a step is taken and where the copy is written.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from fedtpu.ops.server_opt import ServerOptimizer, identity_server_optimizer
from fedtpu.parallel.mesh import CLIENTS_AXIS
from fedtpu.parallel.round import (AGGREGATE, CLIENT_TRAIN, SERVER_UPDATE,
                                   SGD_PASS, assemble_metrics)
from fedtpu.training.task import Task

AUDIT_SPEC = {
    "engine": "stateless",
    "builder": "build_stateless_round_fn",
    "donate_argnums": (0,),
    "collective_axes": (CLIENTS_AXIS,),
}


def validate_stateless_config(cfg) -> None:
    """Everything ``client_state='stateless'`` does not support, refused in
    one place with one message each, before anything is built."""
    fed, run, optim = cfg.fed, cfg.run, cfg.optim
    refusals = [
        (optim.name != "sgd" or optim.momentum != 0.0,
         "client_state='stateless' needs optim.name='sgd' with momentum 0: a "
         "client keeps nothing between rounds, so there is nowhere for Adam "
         "moments or a momentum buffer to live"),
        (fed.participation_rate < 1.0,
         "client_state='stateless' has no partial participation: every "
         "client trains every round (participation_rate=1.0)"),
        (fed.local_steps != 1,
         "client_state='stateless' runs one local epoch a round "
         "(local_steps=1); cut the epoch into steps with local_batch_rows"),
        (fed.prox_mu != 0.0,
         "client_state='stateless' has no FedProx term (prox_mu=0)"),
        (fed.scaffold,
         "client_state='stateless' does not support SCAFFOLD: its control "
         "variates are per-client state"),
        (fed.dp_clip_norm > 0 or fed.dp_noise_multiplier > 0
         or fed.dp_adaptive_clip,
         "client_state='stateless' does not support DP aggregation"),
        (fed.compress != "none",
         "client_state='stateless' does not support compressed exchange"),
        (fed.robust_aggregation != "none" or fed.byzantine_clients > 0,
         "client_state='stateless' does not support robust aggregation or "
         "byzantine injection: they need every client's update at once"),
        (fed.aggregation != "psum",
         "client_state='stateless' sums its accumulator with psum "
         "(aggregation='psum')"),
        (fed.async_mode or fed.cohort_size > 0 or run.model_parallel > 1
         or run.mpmd,
         "client_state='stateless' is an engine of its own: not async_mode, "
         "cohort_size, model_parallel > 1 or mpmd"),
        (fed.personalize_steps > 0,
         "client_state='stateless' does not support personalize_steps"),
        (fed.init_weights_npz is not None,
         "client_state='stateless' does not support init_weights_npz"),
        (bool(run.checkpoint_dir) and run.checkpoint_every > 0,
         "client_state='stateless' does not write checkpoints yet "
         "(checkpoint_every=0): the checkpoint format records a clients "
         "axis this state does not have"),
        (fed.local_batch_rows < 0,
         "local_batch_rows must be >= 0"),
    ]
    for refused, message in refusals:
        if refused:
            raise ValueError(message)


def init_stateless_state(key: jax.Array, mesh, init_fn,
                         server_opt: ServerOptimizer):
    """``{params, server_opt_state, round}``, replicated, from one jitted
    program (no eager per-leaf operation). The server optimizer's
    accumulators are float32 whatever the parameters are."""

    def init(key):
        params = init_fn(key)
        return {"params": params,
                "server_opt_state": jax.tree.map(
                    lambda t: t.astype(jnp.float32), server_opt.init(params)),
                "round": jnp.zeros((), jnp.int32)}

    program = jax.jit(init, out_shardings=NamedSharding(mesh, P()))
    return program(key)


def build_stateless_round_fn(mesh, task: Task, counts, *,
                             learning_rate: float,
                             steplr_step_size: int = 30,
                             steplr_gamma: float = 1.0,
                             weighting: str = "data_size",
                             server_opt: ServerOptimizer | None = None,
                             local_batch_rows: int = 0,
                             one_step_kind: bool = False,
                             rounds_per_step: int = 1):
    """Returns ``round_step(state, batch) -> (state, metrics)`` over the
    state of ``init_stateless_state`` and the batch every engine takes
    (``x (C, N, ...)``, ``y (C, N)``, ``mask (C, N)``, sharded over
    clients). ``counts (C,)`` are the clients' true numbers of rows (host
    integers: they fix how many steps each client's epoch has). The state is
    donated. ``one_step_kind``: every step runs from the working copy, which
    each client's start fills from the global, so the program holds ONE
    trace of the model where the clients' counts would call for up to four
    (a third to a quarter of a deep model's compile and executable), for one
    copy of the parameters a client and the write of a client's last step.
    ``metrics`` are the resident engines' (``loss (C,)``,
    ``per_client``, ``client_mean``, ``pooled``), and ``counters``: the
    task's where it has them, and the engine's two."""
    if server_opt is None:
        server_opt = identity_server_optimizer()
    if weighting not in ("data_size", "uniform"):
        raise ValueError(f"unknown weighting {weighting!r}")
    counts = np.asarray(counts, np.int64)
    n_devices = mesh.devices.size
    if len(counts) % n_devices:
        raise ValueError(f"{len(counts)} clients do not divide over "
                         f"{n_devices} devices")
    # each client's number of steps, on the host: the device's loops run
    # over them, and the program holds only the kinds of step some client has
    steps = (np.ceil(counts / local_batch_rows) if local_batch_rows
             else counts > 0).astype(np.int64)
    single, further, between = (bool(found.any()) for found in (
        steps == 1, steps > 1, steps > 2))

    def round_body(g, sstate, x, y, mask, nsteps, rnd):
        rows = x.shape[1]
        b = local_batch_rows or rows
        if rows % b:
            raise ValueError(
                f"local_batch_rows={b} does not divide the padded shard "
                f"length {rows}")
        units = jax.vmap(task.weight)(x, y, mask)            # (Cb,)
        w = units if weighting == "data_size" else (units > 0).astype(
            jnp.float32)
        total_w = jnp.maximum(jax.lax.psum(w.sum(), CLIENTS_AXIS), 1.0)
        stats0 = jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype),
            jax.eval_shape(lambda: task.loss(g, x[0, :b], y[0, :b],
                                             mask[0, :b])[1]))

        def one_round(carry, _):
            g, sstate, r = carry
            lr = learning_rate * steplr_gamma ** jnp.floor(
                r.astype(jnp.float32) / steplr_step_size)

            def sgd_pass(at, acc, grads, scale):
                """One pass over a step's gradient ``d`` at the parameters
                ``at``: the parameters after the step, and the accumulator
                with the step's share of the client's delta, both from one
                read of ``d``. A step that drops the first writes only the
                second."""

                def leaf(a, m, d):
                    step = lr * d                   # float32, whatever d is
                    if a.dtype == jnp.float32:
                        new, m = a - step, m - scale * step
                    else:
                        # a narrower leaf rounds at every step: the client's
                        # delta is what it holds, not what its gradients sum
                        # to, so the step is added as taken. Rounded by
                        # ``reduce_precision``: a cast and back is the
                        # compiler's to skip, and it does
                        fi = jnp.finfo(a.dtype)
                        rounded = lambda t: jax.lax.reduce_precision(
                            t, fi.nexp, fi.nmant)
                        old = a.astype(jnp.float32)
                        new = rounded(old - rounded(step))
                        m = m + scale * (new - old)
                        new = new.astype(a.dtype)
                    return new, m

                return jax.tree.transpose(
                    jax.tree.structure(at), None,
                    jax.tree.map(leaf, at, acc, grads))

            def client(carry, inputs):
                acc, p, taken, written = carry
                xc, yc, mc, n, wc, uc = inputs
                scale = wc / total_w
                # The global as THIS client reads it. The weights' bf16 casts
                # are hoisted out of every loop that does not write what they
                # read; tied to the client's own step count they stop here,
                # once a client, and no bf16 copy of the global (1.05 GB of
                # the language model's) lives across clients.
                gc, n = jax.lax.optimization_barrier((g, n))

                def step(i, carry, first: bool, keep: bool):
                    """One kind of SGD step, a loop's body: from the global
                    (a client's ``first``) or from its working copy, which
                    is written only where ``keep``."""
                    p, acc, loss_sum, stats, taken, written = carry
                    if not first and not keep:
                        # read and handed on untouched, the copy would be
                        # invariant in this loop, and its weights' bf16 casts
                        # hoisted to where they run whether the loop makes
                        # its trip or not (a one-step client's, for nothing):
                        # tied to the step
                        p, i = jax.lax.optimization_barrier((p, i))
                    at = gc if first else p
                    take = lambda a: jax.lax.dynamic_slice_in_dim(a, i * b, b)
                    xb, yb, mb = take(xc), take(yc), take(mc)
                    (loss, s), grads = jax.value_and_grad(
                        task.loss, has_aux=True)(at, xb, yb, mb)
                    with jax.named_scope(SGD_PASS):
                        new, acc = sgd_pass(at, acc, grads, scale)
                    if keep:
                        p, written = new, written + 1
                    return (p, acc, loss_sum + loss * task.weight(xb, yb, mb),
                            jax.tree.map(jnp.add, stats, s), taken + 1,
                            written)

                # The client's own number of steps: none on a padded row, and
                # a client with no rows changes nothing. Each kind of step has
                # a loop of its own, its trips read from ``n``, because what a
                # step writes is settled where its gradient is made: the pass
                # fuses with the backward pass's last operations (inside a
                # ``cond`` or a loop of its own it would read a gradient
                # written out in float32 first). A ``while`` updates its carry
                # in place and hands it on untouched when it makes no trip, so
                # no kind costs a copy. A kind is a whole trace of the model
                # (a quarter of the language model's executable), so the
                # program holds only the kinds some client's count calls for.
                only, last = ((n == 1).astype(jnp.int32),
                              (n > 1).astype(jnp.int32))
                if one_step_kind:
                    # every step from the working copy, which starts as the
                    # global (a copy: the global lives on): one trace
                    p = gc
                    kinds = [(0, n, False, True)]
                else:
                    kinds = (
                        [(0, only, True, False)] * single       # the only
                        + [(0, last, True, True)] * further     # the first
                        + [(1, n - 1, False, True)] * between
                        + [(n - last, n, False, False)] * further)  # the last
                carry = (p, acc, jnp.float32(0.0), stats0, taken, written)
                for lo, hi, first, keep in kinds:
                    carry = jax.lax.fori_loop(
                        lo, hi, partial(step, first=first, keep=keep), carry)
                p, acc, loss_sum, stats, taken, written = carry
                return ((acc, p, taken, written),
                        (loss_sum / jnp.maximum(uc, 1.0), stats))

            with jax.named_scope(CLIENT_TRAIN):
                if server_opt.begin is not None:
                    # the accumulator IS the next momentum: one set of
                    # parameters fewer on the device
                    acc0 = jax.tree.map(lambda a: a / n_devices,
                                        server_opt.begin(sstate))
                else:
                    acc0 = jax.tree.map(
                        lambda a: jnp.zeros(a.shape, jnp.float32), g)
                # one working copy for all clients, in the scan's carry: born
                # at a client's first step, never read before; none where no
                # client has a second step
                zero = jnp.zeros((), jnp.int32)
                p0 = (jax.tree.map(jnp.zeros_like, g)
                      if further or one_step_kind else None)
                (acc, _, taken, written), (loss, stats) = jax.lax.scan(
                    client, (acc0, p0, zero, zero),
                    (x, y, mask, nsteps, w, units))
            with jax.named_scope(AGGREGATE):
                acc = jax.tree.map(lambda a: jax.lax.psum(a, CLIENTS_AXIS),
                                   acc)
                with jax.named_scope(SERVER_UPDATE):
                    if server_opt.begin is not None:
                        step_, sstate = server_opt.finish(acc, sstate)
                    else:
                        step_, sstate = server_opt.update(acc, sstate)
                    g = jax.tree.map(lambda a, s: a + s.astype(a.dtype),
                                     g, step_)
                pooled = jax.tree.map(
                    lambda s: jax.lax.psum(s.sum(axis=0), CLIENTS_AXIS), stats)
                counters = jax.lax.psum(
                    {"stateless_client_steps": taken,
                     "stateless_working_copy_writes": written}, CLIENTS_AXIS)
            return (g, sstate, r + 1), (loss, stats, pooled, counters)

        (g, sstate, _), stacked = jax.lax.scan(
            one_round, (g, sstate, rnd), length=rounds_per_step)
        return (g, sstate) + stacked

    spec_c, spec_rc = P(CLIENTS_AXIS), P(None, CLIENTS_AXIS)
    sharded_body = jax.shard_map(
        round_body, mesh=mesh,
        in_specs=(P(), P(), spec_c, spec_c, spec_c, spec_c, P()),
        out_specs=(P(), P(), spec_rc, spec_rc, P(), P()),
        # the model's own scans start their carries from constants, which
        # the varying-axes check would have every model annotate; what
        # leaves replicated (global, server state, pooled statistics) comes
        # out of a psum
        check_vma=False)

    @partial(jax.jit, donate_argnums=(0,))
    def round_step(state, batch):
        g, sstate, loss, stats, pooled, counters = sharded_body(
            state["params"], state["server_opt_state"], batch["x"],
            batch["y"], batch["mask"], jnp.asarray(steps, jnp.int32),
            state["round"])
        metrics = assemble_metrics(loss, stats, pooled, batch["mask"],
                                   rounds_per_step, task.metrics,
                                   task.counters)
        # the engine's own counters go where the task's do
        metrics["counters"] = {
            **metrics.get("counters", {}),
            **{k: v[0] if rounds_per_step == 1 else v
               for k, v in counters.items()}}
        return ({"params": g, "server_opt_state": sstate,
                 "round": state["round"] + rounds_per_step}, metrics)

    return round_step
