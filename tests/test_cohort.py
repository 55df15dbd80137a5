"""fedtpu.cohort — sharded client-state store + streaming cohort scheduler
(ISSUE 7 tier-1 suite).

Pins the contracts docs/scaling.md documents:
- cohort-store mode is BITWISE-equal to the vmap path at full
  participation (the acceptance criterion) — history, losses, test
  cadence, and final params;
- the store round-trips records bitwise on both backends, and mmap vs
  memory backends produce identical training trajectories;
- mid-run checkpoint/restore resumes to the identical history and final
  params as an uninterrupted run (store rows ride the same orbax commit);
- the serving engine's store-backed eviction preserves per-user identity
  across evictions and across a checkpoint/restore split;
- sampling policies are deterministic pure functions of (seed, round),
  with identity order at full participation (what makes parity possible);
- peak host RSS is FLAT in total client count under a fixed cohort size
  (the memory-model claim; measured per-row in subprocesses).

The 1M-population row is `slow`-marked (full tier only).
"""

import dataclasses
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from fedtpu.config import (DataConfig, ExperimentConfig, FedConfig,
                           ModelConfig, OptimConfig, RunConfig, ShardConfig)
from fedtpu.cohort import ClientStateStore, CohortSampler
from fedtpu.cohort.store import state_template

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cfg(num_clients=8, cohort_size=0, rounds=3, **kw):
    fed_kw = dict(rounds=rounds, cohort_size=cohort_size)
    run_kw = {}
    for k in ("client_store", "client_store_path", "cohort_sampling",
              "cohort_seed", "cohort_trace", "same_init", "weighting"):
        if k in kw:
            fed_kw[k] = kw.pop(k)
    for k in ("checkpoint_dir", "checkpoint_every", "eval_test_every",
              "rounds_per_step", "keep_checkpoints"):
        if k in kw:
            run_kw[k] = kw.pop(k)
    assert not kw, f"unknown keys {kw}"
    return ExperimentConfig(
        data=DataConfig(csv_path=None, synthetic_rows=512),
        shard=ShardConfig(num_clients=num_clients),
        model=ModelConfig(hidden_sizes=(8,)),
        fed=FedConfig(**fed_kw),
        run=RunConfig(**run_kw),
    )


def _assert_trees_equal(a, b):
    import jax
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ------------------------------------------------------------------ sampler

def test_sampler_uniform_full_population_is_identity():
    """Full participation draws IDENTITY order — the ordering that makes
    the cohort reduction bitwise-comparable to the vmap path."""
    s = CohortSampler(8, 8)
    np.testing.assert_array_equal(s.sample(0)[0], np.arange(8))
    np.testing.assert_array_equal(s.sample(5)[0], np.arange(8))
    # Two disjoint half-cohorts also cover everyone, in identity order.
    two = CohortSampler(8, 4).sample(0, num_cohorts=2)
    np.testing.assert_array_equal(two.ravel(), np.arange(8))


def test_sampler_policies_deterministic_and_distinct():
    for policy, extra in (("uniform", {}),
                          ("weighted", {"weights": np.arange(1.0, 101.0)}),
                          ("trace", {"trace_users":
                                     np.arange(100)[::-1] % 100})):
        s1 = CohortSampler(100, 8, policy=policy, seed=3, **extra)
        s2 = CohortSampler(100, 8, policy=policy, seed=3, **extra)
        for r in (0, 1, 7):
            a, b = s1.sample(r, 2), s2.sample(r, 2)
            np.testing.assert_array_equal(a, b)          # pure in (seed, r)
            assert len(set(a.ravel().tolist())) == a.size  # chunk-disjoint
    # Rejection-sampling regime (need << total) stays distinct too.
    big = CohortSampler(100_000, 16, seed=1).sample(2, 2)
    assert len(set(big.ravel().tolist())) == big.size


def test_sampler_weighted_excludes_zero_weight_clients():
    w = np.ones(64)
    w[10:] = 0.0                     # only clients 0..9 are available
    s = CohortSampler(64, 8, policy="weighted", weights=w)
    for r in range(4):
        assert s.sample(r).max() < 10


def test_sampler_trace_walk_and_exhaustion():
    # Trace order drives cohort membership, wrapping circularly.
    tu = np.array([5, 5, 3, 3, 9, 1], np.int64)
    s = CohortSampler(10, 3, policy="trace", trace_users=tu)
    np.testing.assert_array_equal(s.sample(0)[0], [5, 3, 9])
    # Only 4 distinct users exist: a cohort of 5 must fail loudly.
    s5 = CohortSampler(10, 5, policy="trace", trace_users=tu)
    with pytest.raises(ValueError, match="distinct users"):
        s5.sample(0)


def test_sampler_guards():
    with pytest.raises(ValueError, match="cohort_size"):
        CohortSampler(4, 5)
    with pytest.raises(ValueError, match="weights"):
        CohortSampler(4, 2, policy="weighted")
    with pytest.raises(ValueError, match="nonnegative"):
        CohortSampler(4, 2, policy="weighted", weights=-np.ones(4))
    with pytest.raises(ValueError, match="outside the population"):
        CohortSampler(4, 2, policy="trace",
                      trace_users=np.array([0, 7], np.int64))
    with pytest.raises(ValueError, match="disjoint cohorts"):
        CohortSampler(8, 3).sample(0, num_cohorts=3)


# -------------------------------------------------------------------- store

def test_store_roundtrip_memory_and_mmap(tmp_path):
    template = [((3, 2), np.dtype(np.float32)), ((4,), np.dtype(np.int32))]
    rng = np.random.default_rng(0)
    ids = np.array([0, 7, 3], np.int64)
    leaves = [rng.normal(size=(3, 3, 2)).astype(np.float32),
              rng.integers(0, 9, size=(3, 4)).astype(np.int32)]
    keys = rng.integers(0, 2**32, size=(3, 2), dtype=np.uint32)
    for backend, path in (("memory", None),
                          ("mmap", str(tmp_path / "s.bin"))):
        st = ClientStateStore(template, 16, backend=backend, path=path)
        assert (st.versions(ids) == 0).all()
        st.write(ids, leaves, keys=keys)
        got = st.read(ids)
        for want, have in zip(leaves, got):
            np.testing.assert_array_equal(want, have)
        np.testing.assert_array_equal(st.read_keys(ids), keys)
        assert (st.versions(ids) == 1).all()
        assert (st.participation(ids) == 1).all()
        untouched = np.array([1, 2], np.int64)
        assert (st.versions(untouched) == 0).all()
        st.write(ids[:1], [l[:1] for l in leaves])   # version bumps per write
        assert st.versions(ids).tolist() == [2, 1, 1]
        # checkpoint_arrays carries ONLY touched rows; a fresh store
        # restored from it reads back bitwise.
        arrs = st.checkpoint_arrays()
        assert arrs["store_ids"].shape[0] == 3
        st2 = ClientStateStore(template, 16)
        st2.restore_arrays(arrs)
        for want, have in zip(st.read(ids), st2.read(ids)):
            np.testing.assert_array_equal(want, have)
        np.testing.assert_array_equal(st2.versions(ids), st.versions(ids))


def test_store_sharding_partitions_ids():
    template = [((2,), np.dtype(np.float32))]
    shards = [ClientStateStore(template, 10, shard_index=i, num_shards=3)
              for i in range(3)]
    ids = np.arange(10, dtype=np.int64)
    owned = np.stack([s.owns(ids) for s in shards])
    assert (owned.sum(axis=0) == 1).all()      # every id owned exactly once
    assert sum(s.rows for s in shards) == 10


def test_store_guards(tmp_path):
    template = [((2,), np.dtype(np.float32))]
    with pytest.raises(ValueError, match="backend"):
        ClientStateStore(template, 4, backend="redis")
    with pytest.raises(ValueError, match="path"):
        ClientStateStore(template, 4, backend="mmap")
    with pytest.raises(ValueError, match="total_clients"):
        ClientStateStore(template, 0)
    with pytest.raises(ValueError, match="shard_index"):
        ClientStateStore(template, 4, shard_index=2, num_shards=2)


# ------------------------------------------------- shard failover (ISSUE 12)

def _two_shards(total=11):
    """The gateway-fleet partition: two shards over one population."""
    template = [((3,), np.dtype(np.float32)), ((2,), np.dtype(np.int32))]
    return [ClientStateStore(template, total, shard_index=i, num_shards=2)
            for i in range(2)]


def test_two_shard_partition_is_disjoint_and_exhaustive():
    """The 2-process ownership contract the gateway fleet routes by:
    owns() masks are disjoint AND exhaustive over the population, both
    before and after a failover absorb flips shard 1's ids to shard 0."""
    s0, s1 = _two_shards()
    ids = np.arange(11, dtype=np.int64)
    assert not (s0.owns(ids) & s1.owns(ids)).any()      # disjoint
    assert (s0.owns(ids) | s1.owns(ids)).all()          # exhaustive
    assert s0.rows + s1.rows == 11
    # After the survivor absorbs the dead shard, its mask alone covers
    # the whole population — the fleet keeps answering for every id.
    s1.generation = "g"
    s0.absorb_shard(s1.checkpoint_arrays(), expected_generation="g")
    assert s0.owns(ids).all()


def test_shard_handoff_roundtrip_is_bitwise():
    """Flush-export from the dying shard, absorb into the survivor: the
    absorbed rows read back bitwise (records, versions, keys), and
    writes to adopted ids keep working through the overlay."""
    s0, s1 = _two_shards()
    rng = np.random.default_rng(3)
    ids = np.array([1, 5, 9], np.int64)                 # shard-1 ids
    leaves = [rng.normal(size=(3, 3)).astype(np.float32),
              rng.integers(0, 9, size=(3, 2)).astype(np.int32)]
    keys = rng.integers(0, 2**32, size=(3, 2), dtype=np.uint32)
    s1.write(ids, leaves, keys=keys)
    s1.generation = "launchA"
    assert s0.absorb_shard(s1.checkpoint_arrays(),
                           expected_generation="launchA") == 3
    for want, have in zip(s1.read(ids), s0.read(ids)):
        np.testing.assert_array_equal(want, have)
    np.testing.assert_array_equal(s0.versions(ids), s1.versions(ids))
    np.testing.assert_array_equal(s0.read_keys(ids), keys)
    # The survivor's own checkpoint now carries the adopted ids, so a
    # post-failover resume keeps answering for them (store_absorbed).
    arrs = s0.checkpoint_arrays()
    assert arrs["store_absorbed"].tolist() == [1]
    s2 = ClientStateStore(s0.template, s0.total_clients, shard_index=0,
                          num_shards=2)
    s2.restore_arrays(arrs)
    for want, have in zip(s0.read(ids), s2.read(ids)):
        np.testing.assert_array_equal(want, have)
    # Adopted ids stay writable (version bumps ride the overlay).
    s0.write(ids[:1], [l[:1] for l in leaves])
    assert s0.versions(ids).tolist()[0] == 2


def test_shard_export_digest_and_generation_fences():
    """Corrupt or stale exports are refused loudly: a tampered record
    fails the sha256 digest, a wrong generation fails the fence, and a
    wrong-shard id set is rejected."""
    s0, s1 = _two_shards()
    s1.write(np.array([1, 3], np.int64),
             [np.ones((2, 3), np.float32),
              np.ones((2, 2), np.int32)])
    s1.generation = "live"
    good = s1.checkpoint_arrays()

    tampered = dict(good)
    recs = good["store_records"].copy()
    recs[0, 0] ^= 0xFF
    tampered["store_records"] = recs
    with pytest.raises(ValueError, match="digest mismatch"):
        s0.absorb_shard(tampered, expected_generation="live")

    with pytest.raises(ValueError, match="stale handoff"):
        s0.absorb_shard(good, expected_generation="previous-life")

    own = dict(good)
    own["store_shard_index"] = np.int64(0)   # "absorb yourself"
    with pytest.raises(ValueError, match="cannot absorb"):
        s0.absorb_shard(own, expected_generation="live")


def test_restore_arrays_verifies_digest_and_shard_identity():
    """restore_arrays (the checkpoint path) applies the same fences: a
    truncated/overwritten restore fails the digest check and a
    checkpoint from another shard is refused."""
    s0, s1 = _two_shards()
    s1.write(np.array([1], np.int64),
             [np.full((1, 3), 2.0, np.float32),
              np.full((1, 2), 4, np.int32)])
    arrs = s1.checkpoint_arrays()

    fresh = ClientStateStore(s1.template, s1.total_clients, shard_index=1,
                             num_shards=2)
    corrupt = dict(arrs)
    recs = arrs["store_records"].copy()
    recs[0, -1] ^= 0xFF
    corrupt["store_records"] = recs
    with pytest.raises(ValueError, match="digest mismatch"):
        fresh.restore_arrays(corrupt)

    with pytest.raises(ValueError, match="belongs to shard"):
        s0.restore_arrays(arrs)          # shard-1 checkpoint into shard 0


# ------------------------------------------------------------------ parity

def test_cohort_full_participation_bitwise_equals_vmap():
    """THE acceptance parity: cohort_size == num_clients routes through
    the store + scan-over-cohorts machinery yet reproduces the vmap
    path's history, losses, test cadence, and final params bitwise."""
    from fedtpu.orchestration.loop import run_experiment
    ref = run_experiment(_cfg(rounds=3, eval_test_every=1), verbose=False)
    coh = run_experiment(_cfg(rounds=3, eval_test_every=1, cohort_size=8),
                         verbose=False)
    assert coh.rounds_run == ref.rounds_run == 3
    for k in ("accuracy", "precision", "recall", "f1"):
        assert coh.global_metrics[k] == ref.global_metrics[k]
        assert coh.pooled_metrics[k] == ref.pooled_metrics[k]
        assert coh.test_metrics[k] == ref.test_metrics[k]
        for a, b in zip(coh.per_client_metrics[k],
                        ref.per_client_metrics[k]):
            np.testing.assert_array_equal(np.sort(np.asarray(a)),
                                          np.sort(np.asarray(b)))
    for a, b in zip(coh.loss, ref.loss):
        np.testing.assert_array_equal(np.sort(np.asarray(a).ravel()),
                                      np.sort(np.asarray(b).ravel()))
    _assert_trees_equal(coh.final_params, ref.final_params)


def test_mmap_backend_bitwise_equals_memory(tmp_path):
    from fedtpu.orchestration.loop import run_experiment
    mem = run_experiment(_cfg(rounds=2, cohort_size=4), verbose=False)
    mm = run_experiment(
        _cfg(rounds=2, cohort_size=4, client_store="mmap",
             client_store_path=str(tmp_path / "store.bin")),
        verbose=False)
    for k in ("accuracy", "precision", "recall", "f1"):
        assert mm.global_metrics[k] == mem.global_metrics[k]
    _assert_trees_equal(mm.final_params, mem.final_params)


def test_cohort_checkpoint_resume_is_bitwise(tmp_path):
    """Interrupt after round 4, resume to 6: history and final params
    match the uninterrupted 6-round run exactly — the restored store
    rows, sampler round index, and global params all line up."""
    from fedtpu.orchestration.loop import run_experiment
    ref = run_experiment(
        _cfg(rounds=6, cohort_size=4,
             checkpoint_dir=str(tmp_path / "ref"), checkpoint_every=2),
        verbose=False)
    half = _cfg(rounds=4, cohort_size=4,
                checkpoint_dir=str(tmp_path / "split"), checkpoint_every=2)
    run_experiment(half, verbose=False)
    resumed = run_experiment(half.replace(fed=dataclasses.replace(half.fed, rounds=6)),
                             verbose=False, resume=True)
    assert resumed.rounds_run == 6
    for k in ("accuracy", "precision", "recall", "f1"):
        assert resumed.global_metrics[k] == ref.global_metrics[k]
    _assert_trees_equal(resumed.final_params, ref.final_params)


def test_cohort_config_guards(tmp_path):
    from fedtpu.orchestration.loop import run_experiment
    with pytest.raises(ValueError, match="cohort_size"):
        run_experiment(_cfg(num_clients=4, cohort_size=8), verbose=False)
    with pytest.raises(ValueError, match="async"):
        cfg = _cfg(cohort_size=4)
        run_experiment(cfg.replace(fed=dataclasses.replace(cfg.fed, async_mode=True)),
                       verbose=False)
    with pytest.raises(ValueError, match="robust"):
        cfg = _cfg(cohort_size=4)
        run_experiment(
            cfg.replace(fed=dataclasses.replace(cfg.fed,
                        robust_aggregation="median")),
            verbose=False)
    with pytest.raises(ValueError, match="path"):
        run_experiment(_cfg(cohort_size=4, client_store="mmap"),
                       verbose=False)
    with pytest.raises(ValueError, match="cohort-trace"):
        run_experiment(_cfg(cohort_size=4, cohort_sampling="trace"),
                       verbose=False)


# ----------------------------------------------------- serving integration

def test_engine_store_preserves_identity_across_eviction():
    """Store-backed eviction: a user bounced out of the C slots and later
    readmitted gets ITS OWN state back, bitwise — not whatever the slot
    accumulated in between."""
    from fedtpu.parallel.async_fed import read_client_slot
    from fedtpu.serving.engine import ServingEngine
    from fedtpu.telemetry.metrics import MetricsRegistry
    from tests.test_serving import _small_cfg

    eng = ServingEngine(_small_cfg(cohort=2, tick_interval_s=0.0),
                        registry=MetricsRegistry())
    eng.attach_store(total_users=16)
    # Fill both slots, then snapshot user 0's trained slot state.
    for i, u in enumerate((0, 1)):
        eng.offer(0.1 * (i + 1), u, 0.0)
        eng.drain()
    slot0 = eng.binder.peek(0)
    assert slot0 is not None
    before = [np.asarray(v)
              for v in read_client_slot(eng.state, eng.C, slot0)]
    # End-to-end: users 2 and 3 evict users 0 and 1 at tick time; the
    # evictees' records hit the store.
    for i, u in enumerate((2, 3)):
        eng.offer(0.3 + 0.1 * i, u, 0.0)
        eng.drain()
    assert eng.binder.peek(0) is None
    assert eng.binder.evictions == 2
    assert len(eng.store._touched) >= 2
    # User 0's persisted record is its pre-eviction slot state, bitwise.
    rec = eng.store.read(np.asarray([0], np.int64))
    for a, b in zip(before, rec):
        np.testing.assert_array_equal(a, b[0])
    # Swap user 0 back in (the tick-time load path): the slot now holds
    # user 0's OWN record again, not what the interloper trained there.
    slot, evicted = eng.binder.bind(0)
    assert evicted in (2, 3)
    eng._swap_slot(slot, evicted_user=evicted, new_user=0)
    after = [np.asarray(v) for v in read_client_slot(eng.state, eng.C, slot)]
    for a, b in zip(before, after):
        np.testing.assert_array_equal(a, b)


def test_engine_store_checkpoint_restore_is_bitwise(tmp_path):
    """The store's touched rows ride the engine's orbax commit: restore
    mid-stream (with evictions already persisted) and the remaining
    replay matches the uninterrupted run's history and params."""
    import jax

    from fedtpu.serving.engine import ServingEngine
    from fedtpu.telemetry.metrics import MetricsRegistry
    from tests.test_serving import _small_cfg, _small_trace

    cfg = _small_cfg(cohort=4)           # 500 trace users over 4 slots:
    _, t, user, lat = _small_trace(arrivals=80)   # evictions guaranteed
    half = 40

    ref = ServingEngine(cfg, registry=MetricsRegistry())
    ref.attach_store(total_users=500)
    ref.offer_many(zip(user.tolist(), t.tolist(), lat.tolist()))
    ref.drain()
    assert ref.binder.evictions > 0

    eng1 = ServingEngine(cfg, registry=MetricsRegistry())
    eng1.attach_store(total_users=500)
    eng1.offer_many(zip(user[:half].tolist(), t[:half].tolist(),
                        lat[:half].tolist()))
    eng1.checkpoint(str(tmp_path))

    eng2 = ServingEngine(cfg, registry=MetricsRegistry())
    eng2.attach_store(total_users=500)
    eng2.restore(str(tmp_path))
    s1, s2 = eng1.binder.state(), eng2.binder.state()
    np.testing.assert_array_equal(s2["users"], s1["users"])
    np.testing.assert_array_equal(s2["slots"], s1["slots"])
    assert int(s2["evictions"]) == int(s1["evictions"])
    eng2.offer_many(zip(user[half:].tolist(), t[half:].tolist(),
                        lat[half:].tolist()))
    eng2.drain()

    assert eng2.history_lines() == ref.history_lines()
    for a, b in zip(jax.tree.leaves(eng2.state["params"]),
                    jax.tree.leaves(ref.state["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_async_slot_helpers_roundtrip():
    """read_client_slot/write_client_slot — the primitives the serving
    swap path is built on — round-trip one client's rows bitwise."""
    import jax

    from fedtpu.models import build_model
    from fedtpu.ops import build_optimizer
    from fedtpu.parallel import make_mesh
    from fedtpu.parallel.async_fed import (read_client_slot,
                                           write_client_slot)
    from fedtpu.parallel.round import init_federated_state

    init_fn, _ = build_model(ModelConfig(input_dim=4, num_classes=2,
                                         hidden_sizes=(4,)))
    tx = build_optimizer(OptimConfig())
    mesh = make_mesh(num_clients=4)
    state = init_federated_state(jax.random.key(0), mesh, 4, init_fn, tx)
    vals = [np.asarray(v) for v in read_client_slot(state, 4, 2)]
    bumped = [v + 1 if np.issubdtype(v.dtype, np.floating) else v
              for v in vals]
    state = write_client_slot(state, 4, 2, bumped)
    got = [np.asarray(v) for v in read_client_slot(state, 4, 2)]
    for a, b in zip(bumped, got):
        np.testing.assert_array_equal(a, b)
    # Other slots untouched.
    other = [np.asarray(v) for v in read_client_slot(state, 4, 1)]
    assert any(o.size for o in other)


def test_state_template_matches_slot_leaves():
    import jax

    from fedtpu.models import build_model
    from fedtpu.ops import build_optimizer
    from fedtpu.parallel import make_mesh
    from fedtpu.parallel.round import init_federated_state

    init_fn, _ = build_model(ModelConfig(input_dim=4, num_classes=2,
                                         hidden_sizes=(4,)))
    mesh = make_mesh(num_clients=4)
    state = init_federated_state(jax.random.key(0), mesh, 4, init_fn,
                                 build_optimizer(OptimConfig()))
    tpl = state_template(state, 4)
    assert len(tpl) >= 2           # params + optimizer moments at least
    for shape, dtype in tpl:
        assert isinstance(shape, tuple) and isinstance(dtype, np.dtype)
    # Template rows describe ONE client's record: no leading client axis.
    per_client = [tuple(np.asarray(l).shape[1:])
                  for l in jax.tree.leaves(state)
                  if hasattr(l, "shape") and l.ndim and l.shape[0] == 4]
    assert all(s in per_client for s, _ in tpl)


# ----------------------------------------------------------- memory model

def _scale_row(total, store, rounds=1, extra=()):
    cmd = [sys.executable,
           os.path.join(REPO, "tests", "cohort_scale_row_worker.py"),
           str(total), store, str(rounds), *extra]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)     # real host device count, real RSS
    out = subprocess.run(cmd, capture_output=True, text=True, env=env,
                         timeout=560)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_peak_rss_flat_in_population():
    """The memory-model claim: 10x the simulated population under a fixed
    cohort size moves peak host RSS by store-header noise, not by model
    state (each row measured in its own subprocess => independent
    ru_maxrss high-water marks)."""
    small = _scale_row(10_000, "memory")
    big = _scale_row(100_000, "memory")
    assert big["store_apparent_bytes"] >= 10 * small["store_apparent_bytes"]
    delta = big["peak_rss_bytes"] - small["peak_rss_bytes"]
    # Observed ~1 MB on this box; 64 MB bounds allocator/page-cache noise
    # while still failing loudly if state materializes O(total_clients).
    assert delta < 64 * 2**20, (
        f"peak RSS grew {delta / 2**20:.1f} MiB for 10x the population "
        f"({small['peak_rss_bytes']} -> {big['peak_rss_bytes']})")


@pytest.mark.slow
def test_million_client_round_completes_flat(tmp_path):
    """The acceptance artifact, as a test: one full cohort round over a
    1M-simulated-client population (mmap store) completes on CPU with
    resident store bytes ~cohort-sized while the apparent store is GBs."""
    row = _scale_row(1_000_000, "mmap",
                     extra=(str(tmp_path / "store.bin"),))
    assert row["rounds"] >= 1
    assert row["store_apparent_bytes"] > 10**9          # ~1.7 GB apparent
    assert row["store_resident_bytes"] < 64 * 2**20     # cohort-sized
    assert row["peak_rss_bytes"] < 1536 * 2**20         # ~510 MB observed


# ------------------------------------------ config-validator rejections

# Every composition the cohort scan body does not reproduce must be
# rejected at startup by _validate_cohort_config with a message that
# names the offending knob — a silent wrong-math run is the failure
# mode these guard against. One row per rejection branch.
_REJECTIONS = [
    # (fed overrides, run overrides, message fragment naming the knob)
    (dict(cohort_size=16), {}, r"cohort_size=16 exceeds the population"),
    (dict(client_store="redis"), {}, r"client_store must be"),
    (dict(async_mode=True), {}, r"synchronous engine only"),
    ({}, dict(model_parallel=2), r"model_parallel=1"),
    (dict(participation_rate=0.5), {}, r"--participation-rate"),
    (dict(server_opt="adam"), {}, r"no server_opt / DP"),
    (dict(dp_clip_norm=1.0), {}, r"no server_opt / DP"),
    (dict(dp_clip_norm=1.0, dp_noise_multiplier=0.5), {},
     r"no server_opt / DP"),
    (dict(dp_clip_norm=1.0, dp_adaptive_clip=True), {},
     r"no server_opt / DP"),
    # Coordinate-wise robust rules are supported (uniform + psum only);
    # whole-update rules and synthetic byzantine injection stay rejected.
    (dict(robust_aggregation="trimmed_mean"), {}, r"unweighted"),
    (dict(robust_aggregation="median", weighting="uniform",
          aggregation="ring"), {}, r"psum backend"),
    (dict(robust_aggregation="krum"), {}, r"vmap engine"),
    (dict(byzantine_clients=2), {}, r"poisoned serving traces"),
    (dict(compress="8bit"), {}, r"compressed\s+exchange"),
    (dict(scaffold=True), {}, r"SCAFFOLD"),
    (dict(personalize_steps=3), {}, r"personalize_steps"),
    (dict(init_weights_npz="w.npz"), {}, r"init_weights_npz"),
    ({}, dict(on_divergence="rollback"), r"on_divergence='halt' only"),
    ({}, dict(fault_plan='{"faults": []}'), r"on_divergence='halt' only"),
    ({}, dict(pipelined_stop=True), r"pipelined_stop"),
    (dict(cohort_sampling="trace"), {}, r"--cohort-trace"),
]


@pytest.mark.parametrize("fed_kw,run_kw,match", _REJECTIONS,
                         ids=[f"{i}:{m[:24]}" for i, (_, _, m)
                              in enumerate(_REJECTIONS)])
def test_cohort_config_rejections(fed_kw, run_kw, match):
    from fedtpu.cohort.scheduler import _validate_cohort_config
    cfg = _cfg(num_clients=8, cohort_size=4)
    cfg = dataclasses.replace(
        cfg,
        fed=dataclasses.replace(cfg.fed, **fed_kw),
        run=dataclasses.replace(cfg.run, **run_kw))
    with pytest.raises(ValueError, match=match):
        _validate_cohort_config(cfg)


def test_cohort_config_valid_baseline_passes():
    """The base config every rejection row perturbs must itself pass —
    otherwise the rows above could be failing for the wrong reason."""
    from fedtpu.cohort.scheduler import _validate_cohort_config
    _validate_cohort_config(_cfg(num_clients=8, cohort_size=4))
