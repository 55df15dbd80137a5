"""fedtpu.compilation: serialized-executable cache, fingerprints, overlap.

The contract under test (docs/ARCHITECTURE.md "Compilation and caches"): a
deserialized executable IS the fresh-compiled program (bitwise, not
approximately), cache keys move with anything that changes the program
(arch, client count, dtype, chunk width) and with nothing that doesn't,
and the background-compile overlap path produces the identical history
to the eager loop. Everything runs on the conftest-pinned 8-device CPU
mesh with tiny synthetic configs.
"""

from __future__ import annotations

import contextlib
import dataclasses

import numpy as np
import pytest

import jax

from fedtpu.compilation import (CompileExecutor, ProgramCache,
                                program_config_slice, program_fingerprint,
                                warmup_preset)
from fedtpu.config import get_preset


def tiny_cfg(hidden=(8,), rounds=4, rows=256, rps=1, **run_kw):
    cfg = get_preset("income-8")
    return dataclasses.replace(
        cfg,
        data=dataclasses.replace(cfg.data, csv_path=None, dataset_name=None,
                                 synthetic_rows=rows),
        model=dataclasses.replace(cfg.model, hidden_sizes=tuple(hidden)),
        fed=dataclasses.replace(cfg.fed, rounds=rounds),
        run=dataclasses.replace(cfg.run, rounds_per_step=rps,
                                log_every=0, **run_kw),
    )


@contextlib.contextmanager
def scoped_cache_config():
    """Scope the process-global persistent-cache config (main() and
    run_experiment set it) to one test."""
    prev_dir = jax.config.jax_compilation_cache_dir
    prev_min = jax.config.jax_persistent_cache_min_compile_time_secs
    try:
        yield
    finally:
        jax.config.update("jax_compilation_cache_dir", prev_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          prev_min)


@contextlib.contextmanager
def persistent_cache(tmpdir):
    from fedtpu.compilation import configure_persistent_cache
    with scoped_cache_config():
        configure_persistent_cache(str(tmpdir))
        yield


def bitwise_equal(a, b) -> bool:
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb))


# ------------------------------------------------------- serialize roundtrip
def test_serialize_deserialize_execute_bitwise_equal(tmp_path):
    """store -> (fresh ProgramCache) load -> execute must be bitwise equal
    to the fresh-compiled round program: the cache returns the program,
    not a reproduction of it."""
    from fedtpu.orchestration.loop import build_experiment
    from fedtpu.utils.trees import clone

    exp = build_experiment(tiny_cfg())
    step = exp.make_step(1)
    key = program_fingerprint("round", mesh=exp.mesh,
                              args=(exp.state, exp.batch))

    cache = ProgramCache(str(tmp_path))
    entry = cache.get_or_compile(key, step, exp.state, exp.batch)
    assert not entry.warm and cache.misses == 1

    warm = ProgramCache(str(tmp_path)).load(key)
    assert warm is not None and warm.warm

    out_cold = entry.compiled(clone(exp.state), exp.batch)
    out_warm = warm.compiled(clone(exp.state), exp.batch)
    jax.block_until_ready((out_cold, out_warm))
    assert bitwise_equal(out_cold, out_warm)

    # And the cache's own second lookup is a hit, not a recompile.
    again = cache.get_or_compile(key, step, exp.state, exp.batch)
    assert again.warm and cache.hits >= 1


def test_load_rejects_corrupted_payload(tmp_path):
    from fedtpu.orchestration.loop import build_experiment

    exp = build_experiment(tiny_cfg())
    key = program_fingerprint("round", mesh=exp.mesh,
                              args=(exp.state, exp.batch))
    cache = ProgramCache(str(tmp_path))
    cache.get_or_compile(key, exp.make_step(1), exp.state, exp.batch)
    bin_path, _ = cache._paths(key)
    with open(bin_path, "r+b") as fh:
        fh.seek(10)
        fh.write(b"\x00\x01\x02\x03")
    # Integrity guard: a flipped payload degrades to a miss, never a crash
    # — but a counted one: an entry that is there and does not load must
    # not pass for a plain miss (chip_smoke fails on the counter).
    reopened = ProgramCache(str(tmp_path))
    assert reopened.load(key) is None
    assert reopened.load_errors == 1 and reopened.stats()["load_errors"] == 1
    assert reopened.load("no-such-key") is None     # a plain miss is not one
    assert reopened.load_errors == 1


# ------------------------------------------------- where the cache is placed
@pytest.mark.parametrize("placed", [True, False], ids=["env", "no-env"])
@pytest.mark.parametrize("flag", [True, False], ids=["flag", "no-flag"])
def test_run_keeps_the_cache_where_it_was_placed(monkeypatch, tmp_path,
                                                 placed, flag):
    """JAX_COMPILATION_CACHE_DIR, when set, is where `fedtpu run` caches,
    with or without --compilation-cache; unset, the flag's directory, else
    the one fixed path in the checkout. The ProgramCache goes under the
    same directory, and never is any of it under the system temp dir
    (tmp_path stands in for "some directory placed from outside")."""
    import tempfile

    import fedtpu.cli as cli
    import fedtpu.orchestration.loop as loop
    from fedtpu.compilation import (CACHE_DIR_ENV, DEFAULT_CACHE_DIR,
                                    PROGRAMS_SUBDIR, program_cache_dir,
                                    resolve_cache_dir)

    class Result:
        def summary(self):
            return {}
    monkeypatch.setattr(loop, "run_experiment",
                        lambda cfg, verbose=True, resume=False: Result())
    env_dir, flag_dir = str(tmp_path / "placed"), str(tmp_path / "flag")
    if placed:
        monkeypatch.setenv(CACHE_DIR_ENV, env_dir)
    else:
        monkeypatch.delenv(CACHE_DIR_ENV, raising=False)
    expect = env_dir if placed else flag_dir if flag else DEFAULT_CACHE_DIR
    with scoped_cache_config():
        rc = cli.main(["run", "--csv", "", "--rounds", "1", "--quiet",
                       *(["--compilation-cache", flag_dir] if flag else [])])
        assert rc == 0
        assert jax.config.jax_compilation_cache_dir == expect
    explicit = flag_dir if flag else None
    assert resolve_cache_dir(explicit) == expect
    assert program_cache_dir(explicit) == f"{expect}/{PROGRAMS_SUBDIR}"
    repo = __file__.rsplit("/", 2)[0]
    assert DEFAULT_CACHE_DIR == f"{repo}/.jax_cache"
    assert not DEFAULT_CACHE_DIR.startswith(tempfile.gettempdir())


def test_no_cache_directory_comes_from_tempfile():
    """A cache that moves never hits: no program of the repo may take its
    cache directory from tempfile (mkstemp inside the cache dir, the
    ProgramCache's atomic publish, is not a directory)."""
    import pathlib
    import re

    repo = pathlib.Path(__file__).resolve().parents[1]
    sources = [*repo.glob("fedtpu/**/*.py"), repo / "chip_smoke.py"]
    moving = re.compile(r"mkdtemp|gettempdir|TemporaryDirectory")
    offenders = [f"{path.relative_to(repo)}:{n}: {line.strip()}"
                 for path in sources
                 for n, line in enumerate(path.read_text().splitlines(), 1)
                 if moving.search(line) and "cache" in line.lower()]
    assert not offenders, offenders


# ----------------------------------------------------------- key sensitivity
def test_fingerprint_moves_with_the_program():
    """Changed hidden sizes / client count / dtype must miss; the identical
    config must hit. The fingerprint needs no backend: abstract shapes via
    ShapeDtypeStruct."""
    base_cfg = program_config_slice(tiny_cfg(hidden=(8,)))
    args = (jax.ShapeDtypeStruct((4, 16), np.float32),)

    def fp(config=base_cfg, a=args, extra=None):
        return program_fingerprint("round", config=config, args=a,
                                   extra=extra)

    assert fp() == fp()                                     # deterministic
    assert fp(config=program_config_slice(tiny_cfg(hidden=(16,)))) != fp()
    wide_cfg = tiny_cfg()
    wide_cfg = dataclasses.replace(
        wide_cfg, shard=dataclasses.replace(wide_cfg.shard, num_clients=4))
    assert fp(config=program_config_slice(wide_cfg)) != fp()
    assert fp(a=(jax.ShapeDtypeStruct((4, 16), np.float16),)) != fp()
    assert fp(a=(jax.ShapeDtypeStruct((8, 16), np.float32),)) != fp()
    assert fp(extra={"rounds_per_step": 4}) != fp()
    # Telemetry knobs are excluded from the slice: pointing logs elsewhere
    # must NOT invalidate the cache.
    relogged = tiny_cfg()
    relogged = dataclasses.replace(
        relogged, run=dataclasses.replace(relogged.run, log_every=7))
    assert program_config_slice(relogged) == base_cfg


def test_fingerprint_is_stable_across_concrete_and_abstract_args():
    """warmup (concrete arrays) and the overlap loop (ShapeDtypeStructs)
    must derive the SAME key for the same program."""
    x = jax.numpy.zeros((4, 16), jax.numpy.float32)
    sds = jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)
    assert (program_fingerprint("round", args=(x,))
            == program_fingerprint("round", args=(sds,)))


def test_fingerprint_separates_same_extent_slices_of_one_mesh():
    """Two equal-sized slices of one parent mesh — the MPMD client slice
    vs the server slice — compile against DIFFERENT device sets and must
    never share a cache entry: the mesh signature carries the device
    assignment, not just the axis extents. Identical slices still hit."""
    from jax.sharding import Mesh
    devs = np.asarray(jax.devices())
    assert devs.size >= 8                 # conftest's 8-device CPU pin
    lo = Mesh(devs[:4], ("clients",))
    hi = Mesh(devs[4:8], ("clients",))
    again = Mesh(devs[:4], ("clients",))
    assert (program_fingerprint("round", mesh=lo)
            == program_fingerprint("round", mesh=again))
    assert (program_fingerprint("round", mesh=lo)
            != program_fingerprint("round", mesh=hi))


# ------------------------------------------------------------- the executor
def test_executor_dedupes_blocks_and_reraises():
    calls = {"n": 0}

    def build():
        calls["n"] += 1
        return "compiled"

    def boom():
        raise RuntimeError("lowering failed")

    with CompileExecutor() as ex:
        f1 = ex.submit("k1", build)
        f2 = ex.submit("k1", build)          # dedupe: same future
        assert f1 is f2
        assert ex.get("k1") == "compiled"
        assert calls["n"] == 1
        ex.submit("k2", boom)
        with pytest.raises(RuntimeError, match="lowering failed"):
            ex.get("k2", timeout=30)
        assert ex.succeeded() == ["k1"]


# ---------------------------------------------------------- overlap parity
@pytest.mark.slow
def test_overlap_loop_bitwise_identical_to_eager(tmp_path):
    """overlap_compile trains R=1 warmup rounds while the R-wide program
    compiles; final params and recorded history must be bitwise identical
    to the eager path, and the wide program must land in the cache."""
    from fedtpu.orchestration.loop import run_experiment

    eager = run_experiment(tiny_cfg(rounds=6, rps=3), verbose=False)
    overlapped = run_experiment(
        tiny_cfg(rounds=6, rps=3, overlap_compile=True,
                 compilation_cache=str(tmp_path)),
        verbose=False)
    assert eager.rounds_run == overlapped.rounds_run == 6
    assert bitwise_equal(eager.final_params, overlapped.final_params)
    assert eager.global_metrics["accuracy"] == \
        overlapped.global_metrics["accuracy"]
    cached = ProgramCache(str(tmp_path / "programs")).entries()
    assert cached, "overlap run did not persist the wide program"


# ----------------------------------------------- warm start / zero recompile
@pytest.mark.slow
def test_second_build_through_program_cache_zero_backend_compiles(tmp_path):
    """A SECOND in-process build of the same round program through the
    ProgramCache must report zero backend_compile events under the armed
    RecompileSentinel: the warm path deserializes the executable, it never
    re-enters XLA. (The raw jax persistent cache can't make this promise —
    0.4.x emits backend_compile_duration even on its disk hits.)"""
    from fedtpu.analysis.guards import RecompileSentinel
    from fedtpu.orchestration.loop import build_experiment
    from fedtpu.utils.trees import clone

    cfg = tiny_cfg(hidden=(9,))              # shape unique to this test
    exp = build_experiment(cfg)
    key = program_fingerprint("round", config=program_config_slice(cfg),
                              mesh=exp.mesh, args=(exp.state, exp.batch))
    cold = ProgramCache(str(tmp_path)).get_or_compile(
        key, exp.make_step(1), exp.state, exp.batch)   # pays the compile
    assert not cold.warm
    jax.block_until_ready(clone(exp.state))   # pre-pay clone's own compile

    sentinel = RecompileSentinel(label="warm_cache_smoke")
    with sentinel.armed():
        warm = ProgramCache(str(tmp_path)).get_or_compile(
            key, exp.make_step(1), exp.state, exp.batch)
        _, m = warm.compiled(clone(exp.state), exp.batch)
        jax.block_until_ready(m)
    assert warm.warm
    assert sentinel.available
    assert sentinel.count == 0, (
        f"{sentinel.count} backend compiles despite a warm program cache")


@pytest.mark.slow
def test_warmup_preset_then_check_start_warm(tmp_path):
    """fedtpu warmup twice over the same dir: the second pass must be all
    hits; run_check --warmup-cache over that dir stays retrace-free."""
    from fedtpu.analysis.check import run_check

    with persistent_cache(tmp_path):
        cold = warmup_preset(preset="income-8", cache_dir=str(tmp_path),
                             synthetic_rows=256)
        assert cold["misses"] == len(cold["programs"]) > 0
        warm = warmup_preset(preset="income-8", cache_dir=str(tmp_path),
                             synthetic_rows=256)
        assert warm["hits"] == len(warm["programs"])
        assert all(p["warm"] for p in warm["programs"])

        report = run_check(rounds=2, synthetic_rows=256,
                           warmup_cache=str(tmp_path))
        assert report["ok"] and report["warmup_cache"] == str(tmp_path)
