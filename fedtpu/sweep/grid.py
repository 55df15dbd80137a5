"""Federated hyperparameter grid search — the fedtpu analogue of
``hyperparameters_tuning.py``.

Reference semantics (hyperparameters_tuning.py:68-132): 10 hidden-layer
combos x 9 learning rates = 90 configs, run SEQUENTIALLY; per config every
rank fits a fresh ``MLPClassifier(max_iter=400, random_state=42)`` on its
shard (:90-91), predictions and local metrics are computed BEFORE averaging
(:94-95 vs :102), weights are uniform-averaged (:24-46), pooled global metrics
are computed from concatenated per-rank predictions (:105-112), and rank 0
tracks the best pooled accuracy + params + weights (:115-119).

fedtpu mapping:
  * "fresh model per config, random_state=42" -> same init key per config, so
    every config (and every client) starts from the identical params, like
    sklearn's seeded init.
  * "fit(max_iter=400)" -> ``local_steps`` full-batch Adam steps under
    ``lax.scan`` (the reference's solver is adam with constant lr).
  * "metrics before averaging" -> eval confusion matrices computed on the
    trained-but-not-yet-averaged params, exactly the reference order.
  * TPU-first speedup: the 9-learning-rate axis is vmapped — one compiled
    program trains ALL learning rates for a given architecture simultaneously
    (the MXU sees a 9x-wider batch of tiny matmuls instead of 9 sequential
    runs). The sequential path (``vmap_lr=False``) exists for parity checking.
  * Compile-count cut (VERDICT r3 #2): architectures are BUCKET-PADDED —
    each hidden tuple is zero-padded to the elementwise max of its depth
    class (the reference grid's two depths bucket to (100,) and (400, 400)),
    so every same-depth architecture traces to the SAME shapes and the jit
    cache reuses one compiled program per depth: 2 compiles instead of 10
    for the 90-config grid. Zero padding is EXACT for a ReLU MLP end to
    end: padded activations are 0 (zero weights + zero bias), ReLU'(0)=0
    kills their gradients, Adam on zero grads leaves zero weights zero, and
    sklearn's L2 term adds 0 for zero entries — pinned against the
    unpadded path in tests/test_sweep.py. Winner weights are sliced back
    to their true dims before they leave this module.
  * Launch-count cut (VERDICT r4 #2): since bucket-padded same-depth
    architectures trace to identical shapes, each depth class's
    architectures are additionally STACKED into the vmapped lr axis
    (arch-major), so the whole class runs as ONE program launch — the
    90-config grid is 2 launches end to end. Parity with the
    per-architecture path is pinned in tests/test_sweep.py (observed
    bit-identical; asserted at float-drift tolerance, since the two
    launch plans are differently-shaped XLA programs).
  * Winner reporting (VERDICT r4 #3): the strict-`>` first-hit argmax in
    grid order is kept as the labeled reference-parity answer
    (hyperparameters_tuning.py:115-119), and the STABLE result — the
    ``tie_set`` of every config within ``tie_tolerance`` of the top
    accuracy — rides alongside it, because several configs genuinely tie
    at 1.0 and ulp drift between compiled programs re-orders the argmax.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import PartitionSpec as P

from fedtpu.config import ExperimentConfig
from fedtpu.data.sharding import pack_clients
from fedtpu.data import load_dataset
from fedtpu.data.tabular import Dataset
from fedtpu.models.mlp import mlp_init, mlp_apply
from fedtpu.ops.losses import masked_cross_entropy
from fedtpu.ops.metrics import confusion_matrix, metrics_from_confusion
from fedtpu.parallel.mesh import (CLIENTS_AXIS, make_mesh, client_sharding,
                                  replicated_sharding)
from fedtpu.telemetry import (MetricsRegistry, TelemetryLogger,
                              build_manifest, make_tracer)

# hyperparameters_tuning.py:73-74, verbatim grid.
HIDDEN_GRID = ((50,), (100,), (50, 50), (100, 50), (50, 100), (50, 200),
               (50, 400), (100, 400), (400, 200), (200, 400))
LR_GRID = (0.002, 0.005, 0.004, 0.008, 0.01, 0.02, 0.05, 0.1, 0.2)


def _build_sweep_fn(mesh, num_classes: int, local_steps: int, optim_cfg,
                    plateau_stop: bool = False, tol: float = 1e-4,
                    n_iter_no_change: int = 10, l2_alpha: float = 0.0):
    """One compiled program: train every (lr, client) pair for up to
    ``local_steps`` full-batch steps, then uniform-average over clients
    per lr.

    Array layout: params/opt_state leaves are (C, L, ...) — clients leading
    (sharded over the mesh), learning rates dense per device.

    ``plateau_stop`` reproduces the sklearn semantics the reference's grid
    actually runs under: ``MLPClassifier(max_iter=400)``'s 400 is a CAP,
    not a count — the adam solver stops early once the loss fails to
    improve by more than ``tol`` for ``n_iter_no_change`` consecutive
    epochs (sklearn defaults 1e-4 / 10; the bookkeeping below mirrors
    ``_update_no_improvement_count``: best_loss starts at +inf, the
    counter resets on improvement, training stops once it EXCEEDS
    ``n_iter_no_change``). Under jit this is a ``where``-gated freeze
    inside the same fixed-length scan — stopped (lr, client) pairs coast
    as no-ops, so the compiled shape stays static and the lr axis stays
    vmappable even though each pair stops at its own step. Off by
    default: the fixed-step trainer is the documented fedtpu semantics;
    the flag exists to measure the reference-faithful winner
    (hyperparameters_tuning.py:90).

    ``l2_alpha``: sklearn's L2 penalty ``0.5*alpha*||coefs||^2/n_samples``
    — the term MLPClassifier adds to both the loss its plateau detector
    watches (``loss_curve_``) AND the gradient its updates follow
    (intercepts are NOT penalized, matching sklearn). 0 = fedtpu's plain
    CE; ``run_grid_search(plateau_stop=True)`` passes sklearn's default
    1e-4 so the plateau semantics are faithful end to end (review r3:
    with tol=1e-4 the penalty term is the same scale as the improvement
    bar, so omitting it shifts stop points).
    """
    base = optax.scale_by_adam(b1=optim_cfg.b1, b2=optim_cfg.b2,
                               eps=optim_cfg.eps, eps_root=0.0)

    def train_one(params, opt_state, lr, x, y, mask):
        def loss_fn(q):
            loss = masked_cross_entropy(mlp_apply(q, x), y, mask)
            if l2_alpha > 0.0:
                # sklearn penalizes coefs_ only, averaged over the local
                # fit's sample count (_multilayer_perceptron._backprop).
                sq = sum(jnp.sum(jnp.square(lyr["w"]))
                         for lyr in q["layers"])
                loss = loss + 0.5 * l2_alpha * sq / jnp.maximum(
                    mask.sum().astype(jnp.float32), 1.0)
            return loss

        if plateau_stop:
            def step(carry, _):
                p, s, best, no_imp, active, steps = carry
                loss, grads = jax.value_and_grad(loss_fn)(p)
                updates, s_new = base.update(grads, s)
                p_new = jax.tree.map(lambda a, u: a - lr * u, p, updates)
                # Epoch runs only while active; a stopped pair's whole
                # carry freezes (params, moments, plateau bookkeeping).
                keep = lambda new, old: jax.tree.map(
                    lambda a, b: jnp.where(active, a, b), new, old)
                p, s = keep(p_new, p), keep(s_new, s)
                worse = loss > best - tol
                no_imp = jnp.where(active,
                                   jnp.where(worse, no_imp + 1, 0), no_imp)
                best = jnp.where(active, jnp.minimum(best, loss), best)
                steps = steps + active.astype(jnp.int32)
                active = active & (no_imp <= n_iter_no_change)
                return (p, s, best, no_imp, active, steps), None

            # The bookkeeping scalars must enter the scan carry already
            # marked clients-varying (the loss they get compared to is
            # computed from the client's shard), or shard_map rejects the
            # carry as unvarying-in / varying-out.
            vary = lambda v: jax.lax.pcast(v, CLIENTS_AXIS, to="varying")
            init = (params, opt_state, vary(jnp.float32(jnp.inf)),
                    vary(jnp.int32(0)), vary(jnp.bool_(True)),
                    vary(jnp.int32(0)))
            (params, opt_state, _, _, _, steps), _ = jax.lax.scan(
                step, init, length=local_steps)
        else:
            def fixed_step(carry, _):
                p, s = carry
                grads = jax.grad(loss_fn)(p)
                updates, s = base.update(grads, s)
                p = jax.tree.map(lambda a, u: a - lr * u, p, updates)
                return (p, s), None

            (params, opt_state), _ = jax.lax.scan(
                fixed_step, (params, opt_state), length=local_steps)
            steps = jnp.int32(local_steps)
        preds = jnp.argmax(mlp_apply(params, x), axis=-1)
        conf = confusion_matrix(y, preds, mask, num_classes)
        return params, conf, steps

    def body(params, opt_state, lrs, x, y, mask):
        # params: (Cb, L, ...), lrs: (L,) replicated, x/y/mask: (Cb, N, ...)
        over_lr = jax.vmap(train_one,
                           in_axes=(0, 0, 0, None, None, None))
        over_clients = jax.vmap(over_lr,
                                in_axes=(0, 0, None, 0, 0, 0))
        params, conf, steps = over_clients(params, opt_state, lrs,
                                           x, y, mask)
        # Uniform mean over ALL clients per lr (hyperparameters_tuning.py:37).
        num_clients = jax.lax.psum(jnp.float32(x.shape[0]), CLIENTS_AXIS)
        avg_params = jax.tree.map(
            lambda p: jax.lax.psum(p.sum(axis=0), CLIENTS_AXIS) / num_clients,
            params)                               # (L, ...)
        pooled_conf = jax.lax.psum(conf.sum(axis=0), CLIENTS_AXIS)  # (L, K, K)
        # Mean steps actually run per lr (every client fitted local_steps
        # in fixed mode; own plateau point each in plateau mode).
        mean_steps = (jax.lax.psum(steps.sum(axis=0).astype(jnp.float32),
                                   CLIENTS_AXIS) / num_clients)  # (L,)
        return avg_params, conf, pooled_conf, mean_steps

    spec_c = P(CLIENTS_AXIS)
    return jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(spec_c, spec_c, P(), spec_c, spec_c, spec_c),
        out_specs=(P(), spec_c, P(), P()),
    ))


def _bucket_shape(hidden, hidden_grid) -> tuple:
    """Elementwise max over the grid's same-depth entries — the padded
    shape every architecture of this depth traces to."""
    same_depth = [h for h in hidden_grid if len(h) == len(hidden)]
    return tuple(max(h[i] for h in same_depth) for i in range(len(hidden)))


def _pad_params(params: dict, input_dim: int, hidden, bucket,
                num_classes: int) -> dict:
    """Zero-pad an mlp params pytree from ``hidden`` dims to ``bucket``
    dims (input/output dims unchanged). Exact for a ReLU MLP: see module
    docstring."""
    dims = [input_dim, *hidden, num_classes]
    bdims = [input_dim, *bucket, num_classes]
    layers = []
    for i, lyr in enumerate(params["layers"]):
        w, b = np.asarray(lyr["w"]), np.asarray(lyr["b"])
        layers.append({
            "w": np.pad(w, ((0, bdims[i] - dims[i]),
                            (0, bdims[i + 1] - dims[i + 1]))),
            "b": np.pad(b, (0, bdims[i + 1] - dims[i + 1])),
        })
    return {"layers": layers}


def _unpad_params(params: dict, input_dim: int, hidden, num_classes: int
                  ) -> dict:
    """Slice a bucket-padded params pytree back to its true dims."""
    dims = [input_dim, *hidden, num_classes]
    return {"layers": [
        {"w": np.asarray(lyr["w"])[:dims[i], :dims[i + 1]],
         "b": np.asarray(lyr["b"])[:dims[i + 1]]}
        for i, lyr in enumerate(params["layers"])]}


def run_grid_search(cfg: ExperimentConfig, dataset: Optional[Dataset] = None,
                    hidden_grid=None, lr_grid=None,
                    local_steps: int = 400, vmap_lr: bool = True,
                    keep_weights: bool = False,
                    plateau_stop: bool = False,
                    bucket_pad: bool = True,
                    vmap_arch: bool = True,
                    tie_tolerance: float = 1e-6,
                    overlap_compile: bool = True,
                    verbose: bool = True) -> dict:
    """Run the 90-config federated grid; returns the best-config summary
    (the reference's :126-132 printout, as data). ``hidden_grid``/``lr_grid``
    default to the module-level reference grids, resolved at call time.

    ``keep_weights=True`` retains the winning config's post-averaging
    weight pytree under ``best["weights"]`` (numpy leaves) — the artifact
    the reference prints to stdout at hyperparameters_tuning.py:130-132
    (tracked at :115-119); pass it to ``save_best_weights`` to persist.

    ``plateau_stop=True`` selects sklearn's early-stopping semantics for
    the local fits (``max_iter`` as a cap with tol-1e-4 / 10-epoch plateau
    detection, AND sklearn's default L2 penalty alpha=1e-4 in the watched
    loss and the updates — what ``MLPClassifier(max_iter=400)`` at
    hyperparameters_tuning.py:90 actually does) instead of the fixed
    ``local_steps`` count; each table row then carries the mean steps the
    clients actually ran (``mean_local_steps``).

    ``bucket_pad=True`` (default) zero-pads every architecture to its
    depth class's max dims so same-depth configs share one compiled
    program (module docstring; exact math, pinned in tests).
    ``vmap_arch=True`` (default) goes one step further: since same-depth
    architectures already trace to identical padded shapes, each depth
    class's architectures are STACKED into the vmapped lr axis and the
    whole class runs as ONE launch — the reference's 90 sequential
    configs (hyperparameters_tuning.py:80-84) become 2 program launches.
    Requires vmap_lr and bucket_pad (falls back to per-architecture
    launches otherwise). The returned dict carries ``compile_count`` and
    ``launch_count`` either way.

    ``overlap_compile=True`` (default) AOT-compiles each launch's program
    on a background thread (``fedtpu.compilation.CompileExecutor``) from
    abstract avals, submitted up front — so bucket k+1 compiles while
    bucket k executes and dispatch blocks only when an executable isn't
    ready yet. The compiled program is the same jit object lowered at the
    same shapes, so results are bitwise-identical to the eager path; any
    background-build or dispatch failure falls back to that path. With
    ``cfg.run.compilation_cache`` set, launch executables additionally
    persist through the serialized-executable ``ProgramCache``, in the
    directory jax's persistent backend cache uses
    (``fedtpu.compilation.resolve_cache_dir``).

    Winner semantics: ``best`` keeps the reference's strict-``>``
    first-hit argmax in grid order (:115-119) — the labeled parity
    answer. Because ties are real (several configs hit exactly 1.0 train
    accuracy on separable data) and ulp-level drift between compiled
    programs can re-order that argmax, the STABLE result is
    ``tie_set``: every config within ``tie_tolerance`` of the top
    accuracy (well below the one-sample accuracy quantum, well above
    float drift). Each table row carries ``in_tie_set``."""
    hidden_grid = HIDDEN_GRID if hidden_grid is None else hidden_grid
    lr_grid = LR_GRID if lr_grid is None else lr_grid
    # Before any compile — library/sweep callers cache in the same
    # directory as the CLI (fedtpu.compilation.resolve_cache_dir).
    from fedtpu.compilation import configure_persistent_cache
    configure_persistent_cache(cfg.run.compilation_cache)
    tel = cfg.run.telemetry
    tracer = make_tracer(tel.events_path)
    # The sweep keeps its OWN registry (not default_registry): a sweep that
    # warm-starts run_experiment launches — or one driven alongside a
    # training run — must not have its counters wiped by the run loop's
    # per-run reset.
    registry = MetricsRegistry()
    log = TelemetryLogger(verbose=verbose, tracer=tracer,
                          level=tel.log_level)
    ds = dataset or load_dataset(cfg.data)
    mesh = make_mesh(cfg.run.mesh_devices, cfg.shard.num_clients)
    if tel.manifest:
        tracer.event("manifest", **build_manifest(
            cfg=cfg, mesh=mesh,
            extra={"program": "sweep",
                   "grid_size": len(hidden_grid) * len(lr_grid)}))
    shard = client_sharding(mesh)
    packed = pack_clients(ds.x_train, ds.y_train, cfg.shard)
    # safe_put: no implicit cross-process equality broadcast per array
    # under jax.distributed (fedtpu.parallel.multihost.safe_put).
    from fedtpu.parallel.multihost import safe_put
    x = safe_put(packed.x, shard)
    y = safe_put(packed.y, shard)
    mask = safe_put(packed.mask, shard)

    c = cfg.shard.num_clients
    adam = optax.scale_by_adam(b1=cfg.optim.b1, b2=cfg.optim.b2,
                               eps=cfg.optim.eps, eps_root=0.0)

    # ONE jit object for the whole grid (its closure is architecture-free):
    # the jit cache then shares a compiled program between every
    # architecture that traces to the same shapes — with bucket_pad, one
    # program per depth class.
    sweep_fn = _build_sweep_fn(mesh, ds.num_classes, local_steps,
                               cfg.optim, plateau_stop=plateau_stop,
                               l2_alpha=1e-4 if plateau_stop else 0.0)

    # ---- launch plan: each launch trains a list of same-bucket
    # architectures x a list of learning rates in one compiled call, the
    # (arch, lr) product flattened arch-major into the vmapped slot axis.
    use_arch_vmap = vmap_arch and vmap_lr and bucket_pad
    if use_arch_vmap:
        classes: dict = {}
        for h in hidden_grid:
            classes.setdefault(len(h), []).append(h)
        launches = [(archs, list(lr_grid)) for archs in classes.values()]
    else:
        lr_groups = [list(lr_grid)] if vmap_lr else [[lr] for lr in lr_grid]
        launches = [([h], g) for h in hidden_grid for g in lr_groups]

    # ---- background AOT compilation (fedtpu.compilation): every launch's
    # program is submitted to a compile worker up front, keyed by its
    # abstract argument signature — so while launch k executes (and its
    # host-side fetch blocks), launch k+1's program lowers and compiles on
    # the worker. The avals come from jax.eval_shape, so no launch's param
    # stack is materialized early; identical-shape launches (non-arch-vmap
    # mode) dedupe to one build exactly like the jit cache would.
    comp_exec = None
    launch_keys: list = []
    pcache = None
    if overlap_compile:
        from fedtpu.compilation import CompileExecutor, program_fingerprint
        if cfg.run.compilation_cache:
            from fedtpu.compilation import ProgramCache, program_cache_dir
            pcache = ProgramCache(
                program_cache_dir(cfg.run.compilation_cache),
                tracer=tracer, registry=registry)
        comp_exec = CompileExecutor(tracer=tracer, registry=registry)
        prog_cfg = {"local_steps": local_steps,
                    "plateau_stop": plateau_stop,
                    "l2_alpha": 1e-4 if plateau_stop else 0.0,
                    "optim": dataclasses.asdict(cfg.optim),
                    "num_classes": ds.num_classes}

        def _launch_avals(archs, lr_group):
            """Abstract (params, opt_state, lrs, x, y, mask) for one
            launch, with the dispatch-time shardings attached."""
            a_l = len(archs) * len(lr_group)
            bkt = (_bucket_shape(archs[0], hidden_grid) if bucket_pad
                   else tuple(archs[0]))
            dims = [ds.input_dim, *bkt, ds.num_classes]

            def make():
                p = {"layers": [
                    {"w": jnp.zeros((c, a_l, dims[i], dims[i + 1])),
                     "b": jnp.zeros((c, a_l, dims[i + 1]))}
                    for i in range(len(dims) - 1)]}
                return p, jax.vmap(jax.vmap(adam.init))(p), \
                    jnp.zeros((a_l,), jnp.float32)

            p_sds, s_sds, lr_sds = jax.eval_shape(make)

            def with_sharding(tree, sh):
                return jax.tree.map(
                    lambda u: jax.ShapeDtypeStruct(u.shape, u.dtype,
                                                   sharding=sh), tree)

            return (with_sharding(p_sds, shard), with_sharding(s_sds, shard),
                    with_sharding(lr_sds, replicated_sharding(mesh)),
                    x, y, mask)

        for idx, (archs_i, lrs_i) in enumerate(launches):
            avals = _launch_avals(archs_i, lrs_i)
            key = program_fingerprint("sweep", config=prog_cfg, mesh=mesh,
                                      args=avals)
            launch_keys.append(key)

            def _build(a=avals, k=key, lbl=f"sweep_launch_{idx + 1}"):
                if pcache is not None:
                    return pcache.get_or_compile(k, sweep_fn, *a,
                                                 label=lbl).compiled
                return sweep_fn.lower(*a).compile()

            comp_exec.submit(key, _build, label=f"sweep_launch_{idx + 1}")

    # (hidden, lr) -> row dict. Weights are materialized EAGERLY for each
    # launch's first slot at the launch's max accuracy — the only slot of
    # that launch the global strict-> winner can be (the winner sits at
    # the global max, which is its own launch's max, and nothing earlier
    # in its launch matches it) — so no launch's device output outlives
    # its iteration (review r5: lazy closures kept every launch's
    # avg_params resident until return).
    results: dict = {}
    for n_launch, (archs, lr_group) in enumerate(launches):
        l = len(lr_group)
        sp_launch = tracer.span("launch", round=n_launch + 1,
                                architectures=len(archs),
                                learning_rates=l)
        bucket = (_bucket_shape(archs[0], hidden_grid) if bucket_pad
                  else tuple(archs[0]))
        slabs = []
        for hidden in archs:
            # Same-seed init per config == fresh random_state=42 model per
            # config (hyperparameters_tuning.py:90): identical across
            # clients and learning rates. Padding to the bucket shape
            # happens AFTER the true-shape init, so padded and unpadded
            # runs train the exact same effective network.
            base_params = mlp_init(jax.random.key(42), ds.input_dim, hidden,
                                   ds.num_classes)
            if bucket != tuple(hidden):
                base_params = jax.tree.map(
                    jnp.asarray, _pad_params(base_params, ds.input_dim,
                                             hidden, bucket,
                                             ds.num_classes))
            slabs.append(base_params)
        # (A, ...) stack -> (A*L, ...) arch-major repeat -> (c, A*L, ...).
        stacked = jax.tree.map(lambda *ps: jnp.stack(ps), *slabs)
        params = jax.tree.map(
            lambda p: jnp.broadcast_to(
                jnp.repeat(p, l, axis=0)[None],
                (c, len(archs) * l) + p.shape[1:]), stacked)
        opt_state = jax.vmap(jax.vmap(adam.init))(params)
        params = jax.tree.map(lambda p: safe_put(p, shard), params)
        opt_state = jax.tree.map(lambda p: safe_put(p, shard),
                                 opt_state)
        lrs = jnp.tile(jnp.asarray(lr_group, jnp.float32), len(archs))
        exe = None
        if comp_exec is not None:
            # Acquire the background-built executable; blocks only if the
            # worker hasn't finished it (launch 1, or a compile slower than
            # the previous launch's execution).
            try:
                exe = comp_exec.get(launch_keys[n_launch])
            except Exception:
                # Build failed on the worker; the jit path below computes
                # the identical program.
                registry.counter("background_compile_failures").inc()
        if exe is not None:
            try:
                # The AOT executable pins its input shardings; the lr
                # vector must arrive replicated-committed (the jit path
                # replicates the uncommitted array at dispatch instead).
                avg_params, conf, pooled_conf, mean_steps = exe(
                    params, opt_state,
                    safe_put(lrs, replicated_sharding(mesh)),
                    x, y, mask)
            except Exception:
                registry.counter("aot_dispatch_fallbacks").inc()
                exe = None
        if exe is None:
            avg_params, conf, pooled_conf, mean_steps = sweep_fn(
                params, opt_state, lrs, x, y, mask)

        pooled = jax.vmap(metrics_from_confusion)(pooled_conf)
        pooled = {k: np.asarray(v) for k, v in pooled.items()}
        mean_steps = np.asarray(mean_steps)
        cand = int(np.argmax(pooled["accuracy"]))   # first slot at launch max
        for a, hidden in enumerate(archs):
            for j, lr in enumerate(lr_group):
                i = a * l + j
                w = None
                if i == cand:
                    w = jax.tree.map(lambda p: np.asarray(p[i]), avg_params)
                    if bucket != tuple(hidden):
                        w = _unpad_params(w, ds.input_dim, hidden,
                                          ds.num_classes)
                results[(tuple(hidden), float(lr))] = {
                    "metrics": {k: float(v[i]) for k, v in pooled.items()},
                    "mean_local_steps": float(mean_steps[i]),
                    "win": w,
                }
        del avg_params, conf, pooled_conf
        # np.asarray on pooled/weights above already materialized the
        # launch's outputs on host (the fetch-forced completion proof), so
        # the span closes on finished device work.
        sp_launch.end(launch_max_accuracy=float(pooled["accuracy"].max()))
        registry.counter("sweep_launches").inc()
        registry.counter("sweep_configs").inc(len(archs) * l)
        log.info(f"  launch {n_launch + 1}/{len(launches)} done "
                 f"({len(archs)} architectures x {l} learning rates)")

    # ---- reporting in REFERENCE grid order (hidden outer, lr inner), so
    # the first-hit strict-> argmax is launch-plan-independent.
    best = {"accuracy": -1.0, "params": None, "metrics": None,
            "weights": None}
    table = []
    for hidden in hidden_grid:
        for lr in lr_grid:
            row = results[(tuple(hidden), float(lr))]
            metrics = row["metrics"]
            table.append({"hidden_layer_sizes": tuple(hidden),
                          "learning_rate": float(lr),
                          "mean_local_steps": row["mean_local_steps"],
                          **metrics})
            log.info(f"  grid [{hidden} lr={lr}]: "
                     f"acc={metrics['accuracy']:.4f} "
                     f"f1={metrics['f1']:.4f}")
            if metrics["accuracy"] > best["accuracy"]:
                best = {
                    "accuracy": metrics["accuracy"],
                    "params": {"hidden_layer_sizes": tuple(hidden),
                               "learning_rate": float(lr)},
                    "metrics": metrics,
                    "weights": None,
                }
    # The strict-> scan's final winner is the first grid-order row at the
    # global max — which is its own launch's first-at-max slot, the one
    # slot per launch whose weights were materialized above.
    winner_key = (tuple(best["params"]["hidden_layer_sizes"]),
                  best["params"]["learning_rate"])
    best["weights"] = results[winner_key]["win"]
    assert best["weights"] is not None
    # Every launch materialized its first-at-max slot's weights above;
    # now that the grid-order winner is known, the non-winning copies are
    # dead — drop them so a 2-launch sweep holds ONE model's weights from
    # here on instead of one per launch for the rest of the call (and,
    # with keep_weights=False, of the caller's hold on the return value).
    _drop_nonwinning_weights(results, winner_key)

    # ---- tie set: the stable answer (VERDICT r4 next #3). Strict-> picks
    # ONE of these depending on ulp drift between compiled programs; the
    # set itself is invariant to that drift because tie_tolerance sits
    # well above float noise and well below one sample's accuracy quantum.
    top = best["accuracy"]
    tie_set = []
    for row in table:
        tied = row["accuracy"] >= top - tie_tolerance
        row["in_tie_set"] = tied
        if tied:
            tie_set.append({"hidden_layer_sizes": row["hidden_layer_sizes"],
                            "learning_rate": row["learning_rate"],
                            "accuracy": row["accuracy"]})

    # The two winner lines are the reference's own report
    # (hyperparameters_tuning.py:126-129) — parity output, byte-identical
    # to the former two-arg print form.
    log.parity(f"\nBest Global Hyperparameters: {best['params']}")
    log.parity(f"Best Global Metrics: {best['metrics']}")
    if len(tie_set) > 1:
        log.info(f"Tie set ({len(tie_set)} configs within "
                 f"{tie_tolerance:g} of accuracy {top:.4f} — the strict-> "
                 "winner above is one arbitrary member):")
        for t in tie_set:
            log.info(f"  {t['hidden_layer_sizes']} "
                     f"lr={t['learning_rate']}")
    weights = best["weights"] if keep_weights else best.pop("weights")
    best["weight_shapes"] = ([list(lyr["w"].shape) for lyr in weights["layers"]]
                             if weights else [])
    best["table"] = table
    best["tie_set"] = tie_set
    best["tie_tolerance"] = tie_tolerance
    best["launch_count"] = len(launches)
    # Compiled-program accounting (VERDICT r3 #2): with bucket_pad this is
    # the number of depth classes, not architectures. On the overlap path
    # the builds live in the CompileExecutor, not the jit cache — count
    # successful background builds plus any jit-path fallback compiles.
    try:
        jit_compiles = int(sweep_fn._cache_size())
    except Exception:
        jit_compiles = None
    if comp_exec is not None:
        best["compile_count"] = (len(comp_exec.succeeded())
                                 + (jit_compiles or 0))
        comp_exec.shutdown()
    else:
        best["compile_count"] = jit_compiles
    tracer.counters(registry.snapshot())
    tracer.event("sweep_end", best_accuracy=best["accuracy"],
                 launch_count=best["launch_count"],
                 tie_set_size=len(tie_set))
    tracer.close()
    return best


def _drop_nonwinning_weights(results: dict, winner_key) -> int:
    """Null out the materialized ``win`` weights of every non-winning row
    (each launch eagerly kept one candidate's weights; only the grid-order
    winner's survive). Returns how many copies were dropped."""
    dropped = 0
    for key, row in results.items():
        if key != winner_key and row.get("win") is not None:
            row["win"] = None
            dropped += 1
    return dropped


def save_best_weights(path: str, best: dict) -> None:
    """Persist the sweep winner — weights + hyperparameters + metrics — as
    one ``.npz``. The reference only PRINTS the winning weight matrices
    (hyperparameters_tuning.py:130-132); this makes the artifact real.
    Requires ``run_grid_search(..., keep_weights=True)``."""
    import json

    weights = best.get("weights")
    if not weights:
        raise ValueError("best has no weights — run run_grid_search with "
                         "keep_weights=True")
    arrays = {}
    for i, lyr in enumerate(weights["layers"]):
        arrays[f"layers.{i}.w"] = np.asarray(lyr["w"])
        arrays[f"layers.{i}.b"] = np.asarray(lyr["b"])
    arrays["meta"] = np.frombuffer(json.dumps(
        {"params": {"hidden_layer_sizes":
                    list(best["params"]["hidden_layer_sizes"]),
                    "learning_rate": best["params"]["learning_rate"]},
         "metrics": best["metrics"],
         "accuracy": best["accuracy"]}).encode(), dtype=np.uint8)
    # Write through a file handle: np.savez(str_path) silently appends
    # ".npz" when the suffix is missing, which would orphan the CLI's
    # fail-fast-created file at the exact requested path.
    with open(path, "wb") as f:
        np.savez(f, **arrays)


def load_best_weights(path: str) -> dict:
    """Inverse of ``save_best_weights``: returns ``{"weights": params_pytree,
    "params": hyperparams, "metrics": ..., "accuracy": ...}``. The weights
    pytree has the mlp layout (``{"layers": [{"w", "b"}, ...]}``) and plugs
    directly into ``fedtpu.models.mlp.mlp_apply``."""
    import json

    with np.load(path) as z:
        meta = json.loads(bytes(z["meta"]).decode())
        n_layers = sum(1 for k in z.files if k.endswith(".w"))
        layers = [{"w": z[f"layers.{i}.w"], "b": z[f"layers.{i}.b"]}
                  for i in range(n_layers)]
    return {"weights": {"layers": layers}, **meta}
