"""Round-indexed checkpoint / resume.

The reference has NO persistence at all (SURVEY.md §5): best weights are only
printed to stdout (hyperparameters_tuning.py:130-132, FL_SkLearn...:146-150)
and a 300-round run that dies restarts from scratch. fedtpu checkpoints the
full federated state — per-client params, per-client optimizer state (Adam
moments are NOT averaged, so they are real per-client state), round counter,
and metric history — via orbax, and can resume mid-run.

Layout: ``<dir>/round_<step>/{state,meta}`` — two orbax PyTree items. The
``state`` item is restored against a live state template (``state_like``) so
optax namedtuple nodes come back as namedtuples, not dicts.
"""

from __future__ import annotations

import os
import shutil
import warnings
from typing import Optional, Tuple

import jax
import numpy as np

from fedtpu.telemetry import default_registry
from fedtpu.utils.trees import identity, to_numpy


def _ocp():
    """``orbax.checkpoint``, imported where a checkpoint is written or read:
    its import is two thirds of the whole program's (seconds of every
    process's start: PERF.md, ``program_import_s``) and most jobs keep no
    checkpoint."""
    import orbax.checkpoint as ocp
    return ocp


def _ckpt_path(directory: str, step: int) -> str:
    return os.path.join(os.path.abspath(directory), f"round_{step:06d}")


def _strip_marker(state):
    """Drop the leafless 'shared_start' marker (fedtpu.parallel.round) from
    a state dict. The marker records how the LIVE state was constructed —
    config, not data — so it is never persisted; keeping it out of the
    on-disk tree also keeps checkpoints written before the marker existed
    restorable (orbax rejects template/on-disk structure mismatches)."""
    if isinstance(state, dict) and "shared_start" in state:
        state = {k: v for k, v in state.items() if k != "shared_start"}
    return state


def _checkpointer(step: int, process_group=None):
    """A PyTree checkpointer scoped to ``process_group`` (process indices)
    when given. After a live shrink (fedtpu.resilience.reshard) the
    departed member is parked outside every collective, so orbax's default
    all-process barrier would hang; the group-scoped checkpointer barriers
    only the survivors, with the lowest survivor as primary host. The
    barrier key prefix is derived from (group, step) so concurrent saves
    of different rounds never alias."""
    if process_group is None or jax.process_count() == 1:
        return _ocp().PyTreeCheckpointer()
    group = sorted(int(p) for p in process_group)
    mp_opts = _ocp().options.MultiprocessingOptions(
        primary_host=group[0],
        active_processes=set(group),
        barrier_sync_key_prefix=f"fedtpu_g{group[0]}x{len(group)}s{step}")
    # The handler holds its OWN barrier options (defaulting to every
    # process) — scoping only the Checkpointer leaves the handler's
    # internal save barrier waiting on the parked member forever.
    return _ocp().Checkpointer(
        _ocp().PyTreeCheckpointHandler(multiprocessing_options=mp_opts),
        multiprocessing_options=mp_opts)


def save_checkpoint(directory: str, state, history: dict, step: int,
                    extra_meta: Optional[dict] = None,
                    process_group=None) -> str:
    """Write state + {history, step, num_clients, **extra_meta} under
    ``directory/round_<step>``. ``num_clients`` lives in the tiny meta item
    so elastic-resume detection (fedtpu.orchestration.loop) never has to
    read the full state twice on the common same-count path.
    ``extra_meta``: additional small arrays/scalars for the meta item —
    the loop uses it to persist the cumulative DP RDP curve so a resumed
    run composes its privacy spend instead of re-deriving it from the
    possibly-changed current config.

    Multi-process (jax.distributed): EVERY process must call this — orbax
    save is a collective (it barriers internally; a process-0-only call
    deadlocks the job). The state is passed through as jax.Arrays so orbax
    writes each client shard from the process that owns it (distributed
    checkpointing over the shared checkpoint filesystem); single-process
    keeps the simple host-numpy path.

    ``process_group``: after a live shrink, the surviving process indices —
    every member of the group (and ONLY the group) must make this call;
    see ``_checkpointer``."""
    path = _ckpt_path(directory, step)
    ckptr = _checkpointer(step, process_group)
    state_item = _strip_marker(state)
    if jax.process_count() == 1:
        state_item = to_numpy(state_item)
    else:
        # After a live shrink the surviving group may hold the WHOLE state
        # (every leaf fully addressable) while jax.process_count() still
        # reports the original gang — jax's array serialization refuses
        # fully-addressable arrays under multiprocess ("Cannot serialize
        # host local arrays"). Route such leaves through the host-numpy
        # path; the scoped checkpointer's primary is the only writer, so
        # the on-disk checkpoint is equivalent. Full-gang saves never
        # match (client-sharded and gang-replicated leaves are not fully
        # addressable from any one process), so their path is unchanged.
        state_item = jax.tree.map(
            lambda l: np.asarray(l)
            if isinstance(l, jax.Array) and l.is_fully_addressable else l,
            state_item)
    ckptr.save(os.path.join(path, "state"), state_item, force=True)
    num_clients = jax.tree.leaves(state["params"])[0].shape[0]
    # Engine kind as an int flag (orbax meta passes through np.asarray, so
    # strings are off the table): the async engine's state carries its
    # anchors pytree, the sync engines' never does. Read back by resume
    # BEFORE the client-count comparison — a cross-engine resume must fail
    # on engine kind, not on whichever structural mismatch orbax hits first.
    engine_async = 1 if (isinstance(state, dict) and "anchors" in state) else 0
    # Zero-length metric arrays are dropped: tensorstore cannot commit an
    # empty chunk (orbax rejects the save as "missing params"), and the
    # loop's restore paths already treat an absent key as an empty
    # history. This is what makes the round-0 restore point — saved
    # BEFORE any metrics exist, for ``on_divergence=rollback`` — storable.
    meta = {"history": {k: np.asarray(v) for k, v in history.items()
                        if np.asarray(v).size},
            "step": np.asarray(step),
            "num_clients": np.asarray(num_clients),
            "engine_async": np.asarray(engine_async)}
    if extra_meta:
        meta.update({k: np.asarray(v) for k, v in extra_meta.items()})
    ckptr.save(os.path.join(path, "meta"), meta, force=True)
    reg = default_registry()
    reg.counter("checkpoint_saves").inc()
    reg.counter("checkpoint_bytes_written").inc(
        sum(getattr(l, "nbytes", 0) for l in jax.tree.leaves(state_item)))
    return path


def _is_complete(path: str) -> bool:
    """A round checkpoint is COMMITTED only when both orbax items exist at
    their final names. Each item is individually atomic (orbax writes to a
    ``*.orbax-checkpoint-tmp`` dir and renames), but the round is two
    sequential items — a SIGKILL mid-save leaves ``round_N`` holding only
    a tmp dir, or ``state`` without ``meta`` (found by the chaos test,
    tests/test_chaos_resume.py). Such half-rounds must be invisible to
    resume: ``meta`` is written last, so state-present + meta-present is
    the commit condition."""
    return (os.path.isdir(os.path.join(path, "state"))
            and os.path.isdir(os.path.join(path, "meta")))


def _step_of(name: str) -> Optional[int]:
    """Step of a ``round_<N>`` directory name; None for anything else.
    The ONE definition of what counts as a round dir — complete_steps
    and the retention remnant sweep must agree on it."""
    if not name.startswith("round_"):
        return None
    try:
        return int(name.split("_")[1])
    except (IndexError, ValueError):
        return None


def _scan_rounds(directory: str) -> list:
    """All round dirs under ``directory`` as sorted (step, complete)
    pairs — one listdir serving both the resume view and retention."""
    if not os.path.isdir(directory):
        return []
    out = []
    for name in os.listdir(directory):
        step = _step_of(name)
        if step is not None:
            out.append((step, _is_complete(os.path.join(directory, name))))
    return sorted(out)


def complete_steps(directory: str) -> list:
    """Sorted steps of every COMPLETE checkpoint under ``directory``
    (half-written rounds from a crash are skipped — see
    ``_is_complete``)."""
    return [s for s, ok in _scan_rounds(directory) if ok]


def latest_step(directory: str) -> Optional[int]:
    """Largest COMPLETE checkpoint step under ``directory``."""
    steps = complete_steps(directory)
    return steps[-1] if steps else None


def retain_checkpoints(directory: str, keep: int,
                       protect: Tuple[int, ...] = ()) -> list:
    """Delete all but the ``keep`` NEWEST complete round checkpoints
    (plus any ``protect``-ed steps — the loop protects the best-metric
    round), returning the deleted steps. ``keep <= 0`` keeps everything
    (the default; VERDICT r3 weak #4: unbounded accumulation is the
    wrong shape for a framework that advertises resume).

    Incomplete rounds OLDER than the newest complete one are reclaimed
    too: they are crash remnants (a SIGKILL between the state and meta
    items) that can hold a full-state-sized dir, are invisible to resume
    (``_is_complete``), and would otherwise accumulate across
    crash+resume cycles — the growth this flag exists to prevent. An
    incomplete round AT or ABOVE the newest complete step is left alone:
    called anywhere but right after a save, it could be a concurrent
    writer mid-commit. Multi-process: call from ONE process only (orbax
    save has already barriered, so every round being deleted is fully
    committed).

    GC is best-effort: a transient filesystem error deleting one round
    (NFS silly-rename, an external reader holding a handle) warns and
    skips that round rather than killing the training run — losing
    wall-clock progress over disk GC would invert the priorities."""
    if keep <= 0:
        return []
    rounds = _scan_rounds(directory)
    steps = [s for s, ok in rounds if ok]
    kept = set(steps[-keep:]) | {int(p) for p in protect}
    removed = []

    def _rm(step):
        try:
            shutil.rmtree(_ckpt_path(directory, step))
            removed.append(step)
        except OSError as e:
            warnings.warn(f"checkpoint retention: could not delete "
                          f"round {step} ({e}); will retry after the "
                          "next save", RuntimeWarning)

    for s in steps:
        if s not in kept:
            _rm(s)
    if steps:
        # Incomplete dirs below the newest complete round are dead crash
        # remnants (see docstring); at/above it they may be mid-commit.
        for s, ok in rounds:
            if not ok and s < steps[-1]:
                _rm(s)
    return sorted(removed)


def load_checkpoint_raw(directory: str, step: Optional[int] = None
                        ) -> Tuple[dict, dict, int]:
    """Read back ``(state, history, step)`` WITHOUT a restore template:
    plain nested dicts/lists of numpy arrays (optax namedtuples come back as
    dicts). Used by elastic resume (fedtpu.orchestration.loop), which needs
    the saved arrays under a DIFFERENT client count than the live state —
    a typed template restore would reject the shape mismatch."""
    if step is None:
        step = latest_step(directory)
        if step is None:
            raise FileNotFoundError(f"no checkpoints under {directory}")
    path = _ckpt_path(directory, step)
    ckptr = _ocp().PyTreeCheckpointer()
    state = ckptr.restore(os.path.join(path, "state"))
    meta = ckptr.restore(os.path.join(path, "meta"))
    history = {k: list(np.asarray(v))
               for k, v in (meta.get("history") or {}).items()}
    default_registry().counter("checkpoint_restores").inc()
    return state, history, int(np.asarray(meta["step"]))


def load_meta(directory: str, step: Optional[int] = None) -> dict:
    """The raw meta item of a checkpoint (history, step, num_clients, and
    any ``extra_meta`` the save attached — e.g. the cumulative DP RDP
    curve)."""
    if step is None:
        step = latest_step(directory)
        if step is None:
            raise FileNotFoundError(f"no checkpoints under {directory}")
    return _ocp().PyTreeCheckpointer().restore(
        os.path.join(_ckpt_path(directory, step), "meta"))


def saved_num_clients(raw_state: dict) -> int:
    """Client count of a raw checkpoint: the leading axis every params leaf
    carries."""
    return int(jax.tree.leaves(raw_state["params"])[0].shape[0])


def peek_num_clients(directory: str, step: Optional[int] = None
                     ) -> Optional[int]:
    """Client count of a checkpoint from the meta item alone (no state
    read). None for checkpoints written before num_clients was recorded —
    callers then fall back to :func:`load_checkpoint_raw`."""
    nc = load_meta(directory, step).get("num_clients")
    return None if nc is None else int(np.asarray(nc))


def load_checkpoint_fallback(directory: str, sharding=None, state_like=None,
                             max_step: Optional[int] = None
                             ) -> Tuple[dict, dict, int]:
    """``load_checkpoint`` of the NEWEST checkpoint that actually
    restores, walking complete steps newest-first past corrupt rounds.

    ``_is_complete`` only proves both items were committed — it cannot
    see in-place byte corruption (a dying disk, a partial overwrite; the
    ``ckpt_corrupt`` fault in fedtpu.resilience.faults manufactures
    exactly this). A restore failure on the latest round must not strand
    a resumable run when an older good round exists, so each failure is
    warned about, counted (``checkpoint_restore_corrupt``), and skipped.
    Raises FileNotFoundError when no checkpoint loads at all.

    ``max_step`` bounds the walk: on a multi-process resume the gang has
    AGREED on a common step (fedtpu.resilience.distributed), and a
    process restoring anything newer would desync the federation."""
    steps = complete_steps(directory)
    if max_step is not None:
        steps = [s for s in steps if s <= max_step]
    last_err: Optional[Exception] = None
    for step in reversed(steps):
        try:
            return load_checkpoint(directory, step=step, sharding=sharding,
                                   state_like=state_like)
        except Exception as e:
            last_err = e
            default_registry().counter("checkpoint_restore_corrupt").inc()
            warnings.warn(f"checkpoint round {step} failed to restore "
                          f"({type(e).__name__}: {e}); falling back to the "
                          "previous round", RuntimeWarning)
    raise FileNotFoundError(
        f"no restorable checkpoint under {directory} "
        f"({len(steps)} complete-looking round(s) all failed to load)"
    ) from last_err


def load_checkpoint(directory: str, step: Optional[int] = None,
                    sharding=None, state_like=None) -> Tuple[dict, dict, int]:
    """Read back ``(state, history, step)``.

    ``state_like``: a live state pytree (e.g. a freshly-initialized one from
    ``init_federated_state``) used as the restore template so container types
    (optax namedtuples) survive the roundtrip; when its leaves are committed
    jax Arrays, each restored leaf is placed on the SAME per-leaf sharding —
    this is what preserves the tensor-parallel layout of the 2-D engine
    (fedtpu.parallel.tp), where params mix clients-only and
    clients+model-sharded leaves. ``sharding``: fallback single layout for
    all non-scalar leaves when ``state_like`` carries no shardings.
    """
    if step is None:
        step = latest_step(directory)
        if step is None:
            raise FileNotFoundError(f"no checkpoints under {directory}")
    path = _ckpt_path(directory, step)
    ckptr = _ocp().PyTreeCheckpointer()
    # The 'shared_start' marker is config, not data: never on disk (see
    # _strip_marker), re-attached below from the live template.
    had_marker = isinstance(state_like, dict) and "shared_start" in state_like
    state_like = _strip_marker(state_like)
    # Template from the live state's STRUCTURE only (shapes/dtypes/container
    # types) — never fetch its values: under jax.distributed the
    # client-sharded leaves are not host-addressable (to_numpy would raise),
    # and orbax only reads the template's structure anyway.
    template = (jax.tree.map(lambda l: np.zeros(np.shape(l), l.dtype),
                             state_like)
                if state_like is not None else None)
    state = ckptr.restore(os.path.join(path, "state"), item=template)
    meta = ckptr.restore(os.path.join(path, "meta"))
    def _mesh_sharding(like):
        s = getattr(like, "sharding", None)
        return s if isinstance(s, jax.sharding.NamedSharding) else None

    def _place(l, sh):
        """Put a restored leaf on sharding ``sh``. Under jax.distributed a
        multi-process-saved checkpoint restores as GLOBAL jax.Arrays, which
        ``jax.device_put`` refuses to reshard (not fully addressable) — an
        identity jit with out_shardings does the reshard as an SPMD program
        instead. Host/numpy and single-process leaves take the plain path."""
        if isinstance(l, jax.Array) and not l.is_fully_addressable:
            if sh is None:
                return l                      # already a fine global array
            return jax.jit(identity, out_shardings=sh)(l)  # fedtpu: noqa[FTP006] one-shot resume-time reshard, not a hot path
        if sh is None:
            return jax.device_put(l)
        # safe_put: a host leaf onto a cross-process sharding would run an
        # implicit per-leaf equality broadcast under jax.distributed
        # (fedtpu.parallel.multihost.safe_put) — resume replays one per
        # restored leaf, exactly when a freshly restarted gang is most
        # sensitive to collective misalignment.
        from fedtpu.parallel.multihost import safe_put
        return safe_put(l, sh)

    if state_like is not None and any(
            _mesh_sharding(l) is not None for l in jax.tree.leaves(state_like)):
        # Mesh-laid-out leaves reuse their template sharding; scalars (the
        # round counter) stay uncommitted so jit can place them freely.
        state = jax.tree.map(
            lambda l, like: _place(l, _mesh_sharding(like)),
            state, state_like)
    elif sharding is not None:
        # Every non-scalar state leaf carries the leading clients axis
        # (params, Adam moments); scalars (the round counter, Adam counts of
        # shape (C,) stay client-sharded too since ndim >= 1).
        state = jax.tree.map(
            lambda l: _place(l, sharding if getattr(l, "ndim", 0) >= 1
                             else None),
            state)
    if had_marker:
        state["shared_start"] = ()
    history = {k: list(np.asarray(v))
               for k, v in (meta.get("history") or {}).items()}
    default_registry().counter("checkpoint_restores").inc()
    return state, history, int(np.asarray(meta["step"]))
