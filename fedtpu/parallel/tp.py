"""2-D mesh engine: federated data parallelism × tensor (model) parallelism.

The reference replicates every model whole — one full copy per MPI rank
(FL_CustomMLPCLassifierImplementation_Multiple_Rounds.py:42); its only
scaling axis is more ranks. SURVEY.md §2b leaves a ``('clients', 'model')``
mesh axis open for models too large for one core; this module fills it.

Where fedtpu.parallel.round is an explicit-SPMD program (shard_map + hand
-placed collectives — the right shape for the 1-D clients axis), this engine
is the OTHER canonical JAX recipe, per the scaling-book workflow: write the
round as a GLOBAL-view program (vmap over all clients, plain tensordot for
the weighted average), annotate shardings on params/batch, and let
XLA/GSPMD insert the collectives. Hidden-layer weights shard alternately
column-/row-wise over ``'model'`` (the Megatron MLP pattern: a column-
sharded Linear feeds a row-sharded Linear, whose output all-reduces over the
model axis); clients block-distribute over ``'clients'``; the FedAvg
reduction becomes XLA collectives over the clients axis. On hardware: ICI
for both axes within a host, DCN across hosts.

Same round semantics as the shard_map engine (tested equal): full-batch
local step, data-size-weighted averaging, optimizer state per-client and
never averaged. Partial participation is not supported here (use the 1-D
engine); selected via ``RunConfig.model_parallel > 1``.
"""

from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from fedtpu.ops.server_opt import (ServerOptimizer, clip_by_global_norm,
                                   gaussian_noise_tree,
                                   identity_server_optimizer)
from fedtpu.parallel.mesh import CLIENTS_AXIS, trim_to_divisor
from fedtpu.parallel.round import (_DP_NOISE_STREAM, assemble_metrics,
                                   bcast_global, client_init_keys)
from fedtpu.training.client import (make_local_eval_step,
                                    make_local_train_step)
from fedtpu.training.task import classification_task

MODEL_AXIS = "model"

# Read-only audit hook (fedtpu.analysis.program). This engine's
# collectives are GSPMD-chosen after partitioning, so the auditor pairs
# the (collective-free) jaxpr walk with a compiled-HLO census here.
AUDIT_SPEC = {
    "engine": "tp",
    "builder": "build_round_fn_2d",
    "donate_argnums": (0,),
    "collective_axes": (CLIENTS_AXIS, MODEL_AXIS),
}


def drop_client_axis(spec: P) -> P:
    """The per-leaf layout of a GLOBAL (clients-free) tensor: the same spec
    with the leading clients entry removed — server-optimizer state shards
    over 'model' exactly like the params it mirrors."""
    return P(*tuple(spec)[1:])


def make_mesh_2d(model_parallel: int, num_clients: int = 0,
                 num_devices: int = 0) -> Mesh:
    """(dp, tp) device mesh with axes ``('clients', 'model')``. The device
    count is trimmed so tp divides it and the dp extent divides
    ``num_clients``."""
    if model_parallel < 1:
        raise ValueError(f"model_parallel must be >= 1, got {model_parallel}")
    devices = jax.devices()
    n = num_devices or len(devices)
    n = min(n, len(devices))
    if n % model_parallel:
        raise ValueError(f"{n} devices not divisible by "
                         f"model_parallel={model_parallel}")
    dp = trim_to_divisor(n // model_parallel, num_clients)
    arr = np.asarray(devices[:dp * model_parallel]).reshape(dp, model_parallel)
    return Mesh(arr, (CLIENTS_AXIS, MODEL_AXIS))


def mlp_tp_specs(params) -> dict:
    """PartitionSpecs for the MLP pytree on the 2-D mesh: leading axis is
    always clients; hidden weights alternate column-sharded
    (``P(clients, None, model)``, bias sharded) and row-sharded
    (``P(clients, model, None)``, bias replicated); the logits head is
    replicated over model (it is small, and its output must be replicated
    for the loss anyway)."""
    layers = params["layers"]
    specs = []
    col = True
    for i in range(len(layers)):
        if i == len(layers) - 1:
            specs.append({"w": P(CLIENTS_AXIS), "b": P(CLIENTS_AXIS)})
        elif col:
            specs.append({"w": P(CLIENTS_AXIS, None, MODEL_AXIS),
                          "b": P(CLIENTS_AXIS, MODEL_AXIS)})
            col = False
        else:
            specs.append({"w": P(CLIENTS_AXIS, MODEL_AXIS, None),
                          "b": P(CLIENTS_AXIS)})
            col = True
    return {"layers": specs}


def convnet_tp_specs(params) -> dict:
    """PartitionSpecs for the ConvNet pytree (fedtpu.models.convnet): conv
    kernels (kh, kw, cin, cout) alternate output-channel sharding
    (``P(clients, None, None, None, model)``, bias sharded) and
    input-channel sharding (``P(clients, None, None, model, None)``, bias
    replicated — the conv analogue of Megatron column/row Linear); the dense
    layer column-shards its hidden dim and the head row-shards it (the
    classic pair), leaving logits replicated for the loss."""
    specs_convs = []
    col = True
    for _ in params["convs"]:
        if col:
            specs_convs.append({"w": P(CLIENTS_AXIS, None, None, None,
                                       MODEL_AXIS),
                                "b": P(CLIENTS_AXIS, MODEL_AXIS)})
        else:
            specs_convs.append({"w": P(CLIENTS_AXIS, None, None, MODEL_AXIS,
                                       None),
                                "b": P(CLIENTS_AXIS)})
        col = not col
    return {
        "convs": specs_convs,
        "dense": {"w": P(CLIENTS_AXIS, None, MODEL_AXIS),
                  "b": P(CLIENTS_AXIS, MODEL_AXIS)},
        "head": {"w": P(CLIENTS_AXIS, MODEL_AXIS, None),
                 "b": P(CLIENTS_AXIS)},
    }


def tp_specs(params) -> dict:
    """Model-structure dispatch: the 2-D layout for any supported family."""
    if "convs" in params:
        return convnet_tp_specs(params)
    if "layers" in params:
        return mlp_tp_specs(params)
    raise ValueError("unrecognized params structure for tensor-parallel "
                     f"layout: keys {sorted(params)}")


def init_federated_state_2d(key: jax.Array, mesh: Mesh, num_clients: int,
                            init_fn: Callable,
                            tx: optax.GradientTransformation,
                            same_init: bool = False,
                            server_opt: ServerOptimizer | None = None
                            ) -> dict:
    """Global-view per-client state laid out on the 2-D mesh, with every
    buffer BORN on its declared sharding: init runs inside one jit whose
    ``out_shardings`` carry the 2-D layout, so no device ever holds a full
    replica — required at exactly the scale this engine exists for (a
    model whose whole params+moments exceed one chip's HBM could not
    survive an unsharded init, and GSPMD propagation alone is not a
    guarantee either: at small shapes it replicates the Adam moments over
    'model', tripling per-device state —
    tests/test_tp.py::test_per_device_state_bytes_scale_down_with_tp).

    ``server_opt`` mirrors the 1-D engine (fedtpu.parallel.round): the
    server model is the uniform mean of the client inits, every client
    starts FROM it, and ``server_opt_state`` (clients-free pytrees) lays
    out with the client axis dropped — model-sharded like the params."""
    keys = client_init_keys(key, num_clients, same_init)
    pshape = jax.eval_shape(jax.vmap(init_fn), keys)
    if not isinstance(pshape, dict):
        # A bare-leaf (or list) params pytree would make opt leaves
        # "mirror" the params treedef and receive 2-D param shardings —
        # including scalar step counts, which then fail at jit. Every
        # tp_specs family is a dict; refuse loudly rather than misplace
        # silently (advisor r4).
        raise ValueError(
            "init_federated_state_2d requires a dict params pytree "
            "(a tp_specs model family), got "
            f"{type(pshape).__name__}: optimizer-state placement "
            "identifies param-mirroring subtrees by treedef")
    specs = tp_specs(pshape)
    pshard = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                          is_leaf=lambda x: isinstance(x, P))
    # Optax state subtrees that mirror the params treedef (Adam mu/nu) AND
    # its leaf shapes get the param shardings; everything else (step
    # counts, bare-leaf lookalikes) replicates.
    ptree = jax.tree.structure(pshape)
    pleaves_shape = [l.shape for l in jax.tree.leaves(pshape)]
    oshape = jax.eval_shape(jax.vmap(tx.init), pshape)

    def place_opt(sub):
        if (jax.tree.structure(sub) == ptree
                and [l.shape for l in jax.tree.leaves(sub)]
                == pleaves_shape):
            return pshard
        return jax.tree.map(lambda _: NamedSharding(mesh, P()), sub)

    oshard = jax.tree.map(
        place_opt, oshape,
        is_leaf=lambda x: x is not oshape
        and jax.tree.structure(x) == ptree)

    @partial(jax.jit, out_shardings=(pshard, oshard))
    def _sharded_init(ks):
        params = jax.vmap(init_fn)(ks)
        if server_opt is not None:
            g0 = jax.tree.map(lambda p: p.mean(axis=0), params)
            params = jax.tree.map(
                lambda g, p: jnp.broadcast_to(g[None], p.shape), g0, params)
        return params, jax.vmap(tx.init)(params)

    params, opt_state = _sharded_init(keys)
    # Replicated from birth — the step returns the counter with a
    # replicated NamedSharding, and a SingleDeviceSharding init would
    # retrace the second call (caught by `fedtpu check`).
    # safe_put: no implicit cross-process equality broadcast per leaf
    # under jax.distributed (fedtpu.parallel.multihost.safe_put).
    from fedtpu.parallel.multihost import safe_put
    state = {"params": params, "opt_state": opt_state,
             "round": safe_put(jnp.zeros((), jnp.int32),
                               NamedSharding(mesh, P()))}
    if server_opt is not None:
        g0 = jax.tree.map(lambda p: p[0], params)
        # f32 server accumulators regardless of param dtype, matching the
        # 1-D engine: the delta reduction is f32, so a bf16-born server
        # state would change dtype across the scan carry.
        sstate0 = jax.tree.map(lambda t: t.astype(jnp.float32),
                               server_opt.init(g0))
        sspecs = jax.tree.map(drop_client_axis, specs)
        state["server_opt_state"] = jax.tree.map(
            lambda t, s: safe_put(t, NamedSharding(mesh, s)),
            sstate0, {k: sspecs for k in sstate0})
    return state


def batch_sharding_2d(mesh: Mesh) -> NamedSharding:
    """Client shards split over the clients axis, replicated over model."""
    return NamedSharding(mesh, P(CLIENTS_AXIS))


def build_round_fn_2d(mesh: Mesh, apply_fn: Callable,
                      tx: optax.GradientTransformation, num_classes: int,
                      weighting: str = "data_size",
                      rounds_per_step: int = 1,
                      local_steps: int = 1,
                      prox_mu: float = 0.0,
                      server_opt: ServerOptimizer | None = None,
                      dp_clip_norm: float = 0.0,
                      dp_noise_multiplier: float = 0.0,
                      dp_seed: int = 0) -> Callable:
    """The federated round as a global-view jit program on the 2-D mesh.
    Semantics mirror fedtpu.parallel.round.build_round_fn: ``local_steps``
    full-batch steps per client (default 1 == the reference cadence), an
    optional FedProx term (``prox_mu``), then the weighted average of
    FL_CustomMLP...:108-119 as a plain tensordot over the clients axis —
    GSPMD lowers it to the cross-device reduction.

    ``server_opt`` / ``dp_clip_norm`` / ``dp_noise_multiplier`` enable the
    same DELTA aggregation as the 1-D engine (FedOpt server optimizers,
    DP-FedAvg clip+noise). Global view makes it direct: the mean client
    delta and server state are ordinary clients-free tensors; GSPMD
    replicates/shards them (server state lays out model-sharded like the
    params it mirrors). No client sampling here, so the DP denominator is
    always the realized participant weight.

    The returned ``round_step`` DONATES the input state (matching the 1-D
    engine): always rebind ``state = round_step(state, batch)``; to step one
    state down two different round functions, clone it first (see
    fedtpu.utils.trees)."""
    local_train = make_local_train_step(apply_fn, tx, local_steps=local_steps,
                                        prox_mu=prox_mu)
    local_eval = make_local_eval_step(
        classification_task(apply_fn, num_classes))

    delta_path = (server_opt is not None or dp_clip_norm > 0
                  or dp_noise_multiplier > 0)
    if dp_noise_multiplier > 0 and dp_clip_norm <= 0:
        raise ValueError("dp_noise_multiplier requires dp_clip_norm > 0 "
                         "(noise std is noise_multiplier * clip / weight)")
    if dp_noise_multiplier > 0 and weighting != "uniform":
        # Mirrors the 1-D engine: the noise std z*clip/total_weight assumes
        # a client-agnostic sensitivity bound clip/total_weight; data_size
        # weighting breaks that (a client contributes up to
        # n_i*clip/total_weight), silently deflating the privacy level.
        raise ValueError("DP noise requires weighting='uniform': the "
                         "per-client sensitivity bound (clip/denominator) "
                         "must be client-agnostic for the noise calibration "
                         "to deliver the requested privacy level")
    if delta_path and server_opt is None:
        server_opt = identity_server_optimizer()

    def constrain(params, specs):
        return jax.tree.map(
            lambda p, s: jax.lax.with_sharding_constraint(
                p, NamedSharding(mesh, s)), params, specs)

    # Donate the state, matching the 1-D engine's round_step: callers rebind
    # `state = round_step(state, ...)`, and this engine explicitly targets
    # models too large for one core — without donation, peak device memory
    # doubles for the per-client params/opt-state. CPU ignores donation with
    # a warning; TPU honors it.
    @partial(jax.jit, donate_argnums=(0,))
    def round_step(state, batch):
        if delta_path and "server_opt_state" not in state:
            raise ValueError(
                "delta aggregation (server_opt / DP) needs state from "
                "init_federated_state_2d(..., server_opt=...) — "
                "'server_opt_state' missing")
        if not delta_path and "server_opt_state" in state:
            raise ValueError(
                "state holds 'server_opt_state' (built with server_opt=...) "
                "but this round_fn was built without server_opt / DP — the "
                "server momentum would be silently dropped; build the "
                "round_fn with the same server_opt")
        x, y, mask = batch["x"], batch["y"], batch["mask"]
        specs = tp_specs(state["params"])
        sspecs = jax.tree.map(drop_client_axis, specs)
        sstate0 = state.get("server_opt_state", ())

        def one_round(carry, _):
            params, opt_state, sstate, r = carry
            start = params
            params, opt_state, loss = jax.vmap(local_train)(
                params, opt_state, x, y, mask)
            # Evaluate BEFORE averaging — reference ordering: evaluate_local
            # precedes federated_averaging (FL_CustomMLP...:148 vs :198).
            conf = jax.vmap(local_eval)(params, x, y, mask)
            n = mask.sum(axis=1)
            w = n if weighting == "data_size" else jnp.ones_like(n)
            tw_raw = w.sum()
            tw = jnp.maximum(tw_raw, 1.0)

            def wmean(p):
                return jnp.tensordot(w.astype(jnp.float32),
                                     p.astype(jnp.float32), axes=1) / tw

            if delta_path:
                delta = jax.tree.map(lambda t, s: t - s, params, start)
                if dp_clip_norm > 0:
                    delta, _ = clip_by_global_norm(delta, dp_clip_norm)
                mean_delta = jax.tree.map(wmean, delta)
                if dp_noise_multiplier > 0:
                    std = dp_noise_multiplier * dp_clip_norm / tw
                    noise_key = jax.random.fold_in(
                        jax.random.fold_in(jax.random.key(dp_seed),
                                           _DP_NOISE_STREAM), r)
                    mean_delta = jax.tree.map(
                        jnp.add, mean_delta,
                        gaussian_noise_tree(noise_key, mean_delta, std))
                step, sstate = server_opt.update(mean_delta, sstate)
                sstate = jax.tree.map(
                    lambda t, s: jax.lax.with_sharding_constraint(
                        t, NamedSharding(mesh, s)),
                    sstate, {k: sspecs for k in sstate})
                g = jax.tree.map(lambda s: s[0], start)  # slots identical
                params = jax.tree.map(
                    lambda gl, st, p: bcast_global(gl + st, p),
                    g, step, params)
            else:
                avg = jax.tree.map(wmean, params)
                # Zero total weight (every shard empty): keep params
                # unchanged, matching the 1-D engine's guard.
                params = jax.tree.map(
                    lambda a, p: jnp.where(tw_raw > 0, bcast_global(a, p),
                                           p),
                    avg, params)
            # Keep the broadcast result on the declared 2-D layout rather
            # than letting GSPMD pick (e.g. full replication).
            params = constrain(params, specs)
            return (params, opt_state, sstate, r + 1), (loss, conf,
                                                        conf.sum(axis=0))

        (params, opt_state, sstate, _), (loss, conf, pooled) = jax.lax.scan(
            one_round,
            (state["params"], state["opt_state"], sstate0, state["round"]),
            length=rounds_per_step)
        metrics = assemble_metrics(loss, conf, pooled, mask, rounds_per_step)
        new_state = {"params": params, "opt_state": opt_state,
                     "round": state["round"] + rounds_per_step}
        if delta_path:
            new_state["server_opt_state"] = sstate
        return new_state, metrics

    return round_step
