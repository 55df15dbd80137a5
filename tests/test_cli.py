"""CLI layer (the reference has none — SURVEY.md §1 L6)."""

import json

from fedtpu.cli import main


def test_presets_listing(capsys):
    assert main(["presets"]) == 0
    out = capsys.readouterr().out
    for name in ("income-2", "income-8", "sklearn-parity", "income-32-noniid",
                 "cifar10-32"):
        assert name in out


def test_run_with_overrides_json(capsys):
    rc = main(["run", "--preset", "income-8", "--csv", "", "--rounds", "3",
               "--num-clients", "4", "--quiet", "--json"])
    assert rc == 0
    line = capsys.readouterr().out.strip().splitlines()[-1]
    summary = json.loads(line)
    assert summary["rounds_run"] == 3
    assert "accuracy" in summary["final_global_metrics"]


def test_sweep_table_jsonl(tmp_path, monkeypatch):
    # Shrink the grid (2 archs x 9 lrs) — the full 10x9 takes minutes on CPU;
    # the full-size grid needs the chip.
    from fedtpu.sweep import grid
    monkeypatch.setattr(grid, "HIDDEN_GRID", ((8,), (8, 8)))
    path = str(tmp_path / "table.jsonl")
    rc = main(["sweep", "--csv", "", "--num-clients", "2",
               "--table-jsonl", path, "--quiet"])
    assert rc == 0
    rows = [json.loads(l) for l in open(path)]
    assert len(rows) == 2 * 9
    assert {"hidden_layer_sizes", "learning_rate", "accuracy",
            "f1"} <= set(rows[0])


def test_sweep_bad_table_path_fails_fast(monkeypatch):
    import pytest
    from fedtpu.sweep import grid

    def boom(*a, **k):                    # the sweep must never start
        raise AssertionError("sweep ran despite bad table path")

    monkeypatch.setattr(grid, "run_grid_search", boom)
    with pytest.raises(FileNotFoundError):
        main(["sweep", "--csv", "", "--num-clients", "2",
              "--table-jsonl", "/nonexistent-dir/t.jsonl", "--quiet"])


def test_sweep_honors_local_steps(tmp_path, monkeypatch):
    from fedtpu.sweep import grid
    seen = {}
    real = grid.run_grid_search

    def spy(cfg, **kw):
        seen.update(kw)
        kw.setdefault("hidden_grid", ((8,),))
        kw.setdefault("lr_grid", (0.004,))
        return real(cfg, **kw)

    monkeypatch.setattr(grid, "run_grid_search", spy)
    main(["sweep", "--csv", "", "--num-clients", "2", "--local-steps", "7",
          "--quiet"])
    assert seen.get("local_steps") == 7


def test_run_new_aggregation_flags_reach_config(monkeypatch):
    """--server-opt / --dp-* / --compress / --robust-* / --byzantine-clients
    must land in FedConfig (a dropped override silently runs the wrong
    experiment)."""
    import fedtpu.cli as cli
    captured = {}

    def spy(cfg, verbose=True, resume=False):
        captured["fed"] = cfg.fed

        class R:
            def summary(self):
                return {}
        return R()

    import fedtpu.orchestration.loop as loop
    monkeypatch.setattr(loop, "run_experiment", spy)
    rc = cli.main(["run", "--csv", "", "--rounds", "1",
                   "--server-opt", "fedyogi", "--server-lr", "0.05",
                   "--server-momentum", "0.8",
                   "--dp-clip-norm", "2.0", "--dp-noise-multiplier", "0.2",
                   "--weighting", "uniform", "--quiet"])
    assert rc == 0
    fed = captured["fed"]
    assert fed.server_opt == "fedyogi"
    assert fed.server_lr == 0.05
    assert fed.server_momentum == 0.8
    assert fed.dp_clip_norm == 2.0
    assert fed.dp_noise_multiplier == 0.2

    rc = cli.main(["run", "--csv", "", "--rounds", "1",
                   "--compress", "int8", "--quiet"])
    assert rc == 0
    assert captured["fed"].compress == "int8"

    rc = cli.main(["run", "--csv", "", "--rounds", "1",
                   "--weighting", "uniform",
                   "--robust-aggregation", "krum", "--krum-f", "1",
                   "--byzantine-clients", "1", "--quiet"])
    assert rc == 0
    fed = captured["fed"]
    assert fed.robust_aggregation == "krum"
    assert fed.krum_f == 1
    assert fed.byzantine_clients == 1

    rc = cli.main(["run", "--csv", "", "--rounds", "1",
                   "--weighting", "uniform",
                   "--robust-aggregation", "trimmed_mean",
                   "--trim-ratio", "0.2", "--quiet"])
    assert rc == 0
    assert captured["fed"].trim_ratio == 0.2


def test_run_compile_flags_reach_run_config(monkeypatch, tmp_path):
    """--compilation-cache / --overlap-compile must land in RunConfig —
    that is how run_experiment, the sweep, and library callers get the
    persistent-cache / background-compile behavior."""
    import fedtpu.cli as cli
    import fedtpu.orchestration.loop as loop
    captured = {}

    def spy(cfg, verbose=True, resume=False):
        captured["run"] = cfg.run

        class R:
            def summary(self):
                return {}
        return R()

    monkeypatch.setattr(loop, "run_experiment", spy)
    import os

    import jax
    prev_dir = jax.config.jax_compilation_cache_dir
    prev_min = jax.config.jax_persistent_cache_min_compile_time_secs
    cache_dir = str(tmp_path / "cc")
    try:
        rc = cli.main(["run", "--csv", "", "--rounds", "1",
                       "--compilation-cache", cache_dir,
                       "--overlap-compile", "--quiet"])
        flagged = captured["run"]
        # Defaults: no flag, no ProgramCache request, no overlap (the XLA
        # cache itself is always on — tests/test_compilation.py).
        rc_plain = cli.main(["run", "--csv", "", "--rounds", "1", "--quiet"])
    finally:
        # main() applies the cache config process-globally; scope it here.
        jax.config.update("jax_compilation_cache_dir", prev_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          prev_min)
    assert rc == 0 and rc_plain == 0
    assert flagged.compilation_cache == os.path.abspath(cache_dir)
    assert flagged.overlap_compile is True
    assert captured["run"].compilation_cache is None
    assert captured["run"].overlap_compile is False


def test_run_compress_end_to_end_via_cli(capsys):
    rc = main(["run", "--csv", "", "--rounds", "2", "--num-clients", "4",
               "--compress", "int8", "--quiet", "--json"])
    assert rc == 0
    line = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(line)["rounds_run"] == 2


def test_compilation_cache_flag_populates_cache(tmp_path):
    # --compilation-cache must be applied BEFORE any compile, so repeat CLI
    # invocations serve their XLA executables from disk. Subprocesses: the
    # cache config is process-global.
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cache = tmp_path / "xlacache"
    cmd = [sys.executable, "-m", "fedtpu.cli", "run", "--csv", "",
           "--num-clients", "2", "--hidden-sizes", "8", "--rounds", "1",
           "--compilation-cache", str(cache), "--quiet", "--json"]
    # Threshold 0: cache even the tiny CPU test program deterministically
    # (the CLI respects the env var and must not clobber it).
    # (conftest turns the cache off for every test process; this test of
    # the cache turns it back on in its own child.)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_ENABLE_COMPILATION_CACHE="true",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    r = subprocess.run(cmd, cwd=repo, env=env, capture_output=True,
                       text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    assert cache.is_dir() and len(list(cache.iterdir())) > 0


def test_every_documented_flag_exists_in_the_parser():
    """Docs-accuracy guard: every `--flag` README/docs/API.md/PARITY.md
    mention must exist in the real CLI parser (doc rot on the flag surface
    fails loudly here)."""
    import os
    import re

    from fedtpu.cli import build_parser

    parser = build_parser()
    known = set()
    # Top-level + every subparser's option strings.
    subactions = [a for a in parser._actions
                  if a.__class__.__name__ == "_SubParsersAction"]
    for sp in [parser] + [p for a in subactions
                          for p in a.choices.values()]:
        for act in sp._actions:
            known.update(act.option_strings)

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    documented = set()
    for rel in ("README.md", "docs/API.md", "docs/ARCHITECTURE.md",
                "docs/observability.md", "docs/analysis.md",
                "docs/resilience.md",
                "docs/serving.md", "docs/scaling.md", "docs/autoscale.md",
                "docs/robustness.md",
                "PARITY.md"):
        text = open(os.path.join(root, rel)).read()
        # Underscores ARE captured so `--dp_clip_norm`-style typos show up
        # as unknown flags instead of silently failing to match.
        documented.update(re.findall(
            r"(?<![\w/-])(--[a-z][a-z0-9_-]+)(?![a-z0-9_-])", text))
    # Flags documented for OTHER executables, not fedtpu.cli.
    other_tools = {"--write",     # python -m fedtpu.telemetry.timeline_sim
                   "--xla_force_host_platform_device_count",  # XLA flag
                   "--chips", "--rehearse-cpu",    # chip_smoke.py / chiprun
                   "--hostfile", "--np"}           # mpirun (reference docs)
    missing = documented - known - other_tools
    assert not missing, f"docs mention unknown CLI flags: {sorted(missing)}"
    # And the guard itself must be live: the docs do document real flags.
    assert len(documented & known) > 20
