"""Telemetry subsystem — fedtpu.telemetry (tracer, metrics, manifest,
report) plus the observability satellites: bench JSON-last emission, the
resume engine-mismatch guard, the async/personalize rejection, sweep
winner-weight retention, reference-parity byte identity with telemetry
on, and the bare-print lint over the package.
"""

import dataclasses
import json
import os

import numpy as np
import pytest

from fedtpu.config import (DataConfig, ExperimentConfig, FedConfig,
                           RunConfig, ShardConfig, TelemetryConfig)
from fedtpu.telemetry import (EVENT_SCHEMA_VERSION, MetricsRegistry,
                              NullTracer, Tracer, make_tracer)
from fedtpu.telemetry.report import aggregate, load_events, render_report


def _cfg(rounds=4, tmp=None, **run_kw):
    run_kw.setdefault("log_every", 1000)
    if tmp is not None:
        run_kw["telemetry"] = TelemetryConfig(events_path=str(tmp))
    return ExperimentConfig(
        data=DataConfig(csv_path=None, synthetic_rows=512),
        shard=ShardConfig(num_clients=8),
        fed=FedConfig(rounds=rounds, termination_patience=1000),
        run=RunConfig(**run_kw))


# ---------------------------------------------------------------- schema
def test_event_schema_roundtrip(tmp_path):
    """Emit -> read -> aggregate: every schema field survives the sink and
    the aggregation matches hand-computed numbers."""
    path = str(tmp_path / "ev.jsonl")
    tr = Tracer(path, run_id="deadbeef")
    durs = [0.25, 0.5, 1.0, 2.0]
    for i, d in enumerate(durs):
        tr.event("round", round=i + 1, dur_s=d, staleness_mean=float(i))
    tr.event("span", phase="eval", dur_s=0.125, note="x")
    reg = MetricsRegistry()
    reg.counter("rounds").inc(4)
    reg.gauge("g").set(7.5)
    reg.histogram("staleness", bins=(0, 1, 2)).observe_many([0, 1, 1, 5])
    tr.counters(reg.snapshot())
    tr.close()

    # Append garbage: a malformed line and a truncated (crash-cut) line.
    with open(path, "a") as f:
        f.write("not json\n")
        f.write('{"v": 1, "kind": "span", "pha')

    events, bad = load_events(path)
    assert bad == 2
    assert len(events) == 6
    for e in events:
        assert e["v"] == EVENT_SCHEMA_VERSION
        assert e["run_id"] == "deadbeef"
        # Schema v2 (fedtpu/telemetry/trace.py): the v1 fields plus the
        # fleet identity stamp.
        assert set(e) == {"v", "run_id", "kind", "phase", "round",
                          "t_start", "dur_s", "payload",
                          "process_index", "pid", "launch_id", "role"}
        # t_start defaults to emission time minus dur_s: the window END
        # (t_start + dur_s) always lands at/after the tracer epoch.
        assert e["t_start"] + e["dur_s"] >= 0.0

    agg = aggregate(events, malformed=bad)
    assert agg["malformed_lines"] == 2
    assert agg["run_ids"] == ["deadbeef"]
    assert agg["rounds"]["count"] == 4
    assert agg["rounds"]["last_round"] == 4
    assert np.isclose(agg["rounds"]["total_s"], sum(durs))
    cad = agg["rounds"]["cadence"]
    assert np.isclose(cad["p50_s"], np.percentile(durs, 50))
    assert np.isclose(cad["p90_s"], np.percentile(durs, 90))
    assert np.isclose(cad["max_s"], 2.0)
    assert agg["phases"]["eval"]["count"] == 1
    assert np.isclose(agg["phases"]["eval"]["total_s"], 0.125)
    assert agg["counters"]["rounds"] == 4
    assert agg["gauges"]["g"] == 7.5
    st = agg["staleness"]
    assert st["count"] == 4 and st["max"] == 5
    # le-style cumulative buckets over bins (0, 1, 2): 1, 3, 3.
    assert st["bucket_counts"] == [1, 3, 3]
    assert np.isclose(st["round_mean_of_means"], np.mean([0, 1, 2, 3]))


def test_null_tracer_is_total_noop(tmp_path):
    tr = make_tracer(None)
    assert isinstance(tr, NullTracer) and not tr.enabled
    with tr.span("anything", round=3) as sp:
        pass
    assert sp.end() == 0.0
    tr.event("round", dur_s=1.0)
    tr.counters({"counters": {}})
    tr.close()                                   # nothing written anywhere
    assert make_tracer(str(tmp_path / "e.jsonl")).enabled


def test_newer_schema_version_warns_not_crashes(tmp_path):
    path = str(tmp_path / "future.jsonl")
    with open(path, "w") as f:
        f.write(json.dumps({"v": EVENT_SCHEMA_VERSION + 1, "run_id": "r",
                            "kind": "span", "phase": "warp", "round": None,
                            "t_start": 0.0, "dur_s": 1.0,
                            "payload": {"field_from_the_future": 1}}) + "\n")
    rendered, prom = render_report(path)
    assert "schema newer than" in rendered
    assert "warp" in rendered
    assert prom.endswith("\n")


# ----------------------------------------------------------- integration
def test_run_emits_events_and_report_reconstructs(tmp_path):
    """Acceptance: a run with telemetry on emits manifest + per-round
    span/counter events, and the report reconstructs the per-phase
    breakdown and cadence percentiles from the log ALONE."""
    ev = tmp_path / "events.jsonl"
    from fedtpu.orchestration.loop import run_experiment
    res = run_experiment(_cfg(rounds=4, tmp=ev, eval_test_every=2),
                         verbose=False)
    assert res.rounds_run == 4

    events, bad = load_events(str(ev))
    assert bad == 0
    agg = aggregate(events)
    man = agg["manifest"]
    assert man["program"] == "run" and man["engine"] == "sync1d"
    assert man["config_hash"] and man["mesh_shape"] == {"clients": 8}
    assert man["device_count"] == 8
    for phase in ("build", "compile", "chunk", "eval", "stop_check"):
        assert agg["phases"][phase]["count"] >= 1, phase
    assert agg["rounds"]["count"] == 4
    assert agg["rounds"]["cadence"]["p50_s"] > 0
    assert agg["counters"]["rounds"] == 4
    assert agg["counters"]["held_out_evals"] == 2
    assert agg["gauges"]["exchange_bytes_per_round_est"] > 0
    kinds = {e["kind"] for e in events}
    assert {"manifest", "span", "round", "counters", "run_end"} <= kinds

    # The report CLI renders all three formats from the same log.
    from fedtpu.cli import main
    prom_file = tmp_path / "metrics.prom"
    assert main(["report", str(ev), "--format", "json",
                 "--prometheus", str(prom_file)]) == 0
    prom = prom_file.read_text()
    assert "fedtpu_rounds_total 4" in prom
    assert 'fedtpu_round_duration_seconds{quantile="0.5"}' in prom


def test_async_run_records_staleness_histogram(tmp_path):
    ev = tmp_path / "events.jsonl"
    from fedtpu.orchestration.loop import run_experiment
    cfg = _cfg(rounds=6, tmp=ev)
    cfg = dataclasses.replace(cfg, fed=FedConfig(
        rounds=6, weighting="uniform", async_mode=True,
        async_arrival_rate=0.4, termination_patience=1000))
    run_experiment(cfg, verbose=False)
    agg = aggregate(load_events(str(ev))[0])
    assert agg["manifest"]["engine"] == "async"
    assert agg["counters"]["async_ticks"] == 6
    st = agg["staleness"]
    assert st["count"] == 6 * 8                  # ticks x client slots
    assert st["bucket_counts"][-1] == st["count"]
    assert sum(1 for e in load_events(str(ev))[0]
               if e["kind"] == "async_tick") == 6


def test_checkpoint_counters_roundtrip(tmp_path):
    from fedtpu.orchestration.loop import run_experiment
    from fedtpu.telemetry import default_registry
    ev = tmp_path / "events.jsonl"
    cfg = _cfg(rounds=3, tmp=ev, checkpoint_dir=str(tmp_path / "ck"),
               checkpoint_every=3)
    run_experiment(cfg, verbose=False)
    run_experiment(dataclasses.replace(
        cfg, fed=dataclasses.replace(cfg.fed, rounds=6)),
        verbose=False, resume=True)
    reg = default_registry().snapshot()
    assert reg["counters"]["checkpoint_restores"] >= 1
    assert reg["counters"]["checkpoint_saves"] >= 1
    assert reg["counters"]["checkpoint_bytes_written"] > 0
    assert any(e["kind"] == "resume"
               for e in load_events(str(ev))[0])


# ------------------------------------------------------------- satellites
def test_resume_engine_mismatch_with_equal_client_counts(tmp_path):
    """Satellite regression: same client count on both sides used to slip
    past the count comparison and die inside orbax with an opaque
    structure error; the engine kind in the checkpoint meta must be
    checked FIRST and raise a clear ValueError."""
    from fedtpu.orchestration.loop import run_experiment
    sync_cfg = _cfg(rounds=3, checkpoint_dir=str(tmp_path / "sync"),
                    checkpoint_every=3)
    run_experiment(sync_cfg, verbose=False)
    async_same_count = dataclasses.replace(
        sync_cfg, fed=FedConfig(rounds=6, weighting="uniform",
                                async_mode=True, termination_patience=1000))
    with pytest.raises(ValueError, match="engine mismatch"):
        run_experiment(async_same_count, verbose=False, resume=True)

    # And the reverse direction: async-written, sync-resumed, equal counts.
    async_cfg = dataclasses.replace(
        _cfg(rounds=3, checkpoint_dir=str(tmp_path / "async"),
             checkpoint_every=3),
        fed=FedConfig(rounds=3, weighting="uniform", async_mode=True,
                      termination_patience=1000))
    run_experiment(async_cfg, verbose=False)
    sync_same_count = dataclasses.replace(
        async_cfg, fed=FedConfig(rounds=6, termination_patience=1000),
        run=dataclasses.replace(async_cfg.run,
                                checkpoint_dir=str(tmp_path / "async")))
    with pytest.raises(ValueError, match="engine mismatch"):
        run_experiment(sync_same_count, verbose=False, resume=True)


def test_async_mode_rejects_personalize_steps():
    """Satellite regression: async + personalize_steps used to run and
    silently fine-tune from stale per-slot locals instead of the final
    global; it must be rejected at build time."""
    from fedtpu.orchestration.loop import build_experiment
    cfg = dataclasses.replace(_cfg(rounds=2), fed=FedConfig(
        rounds=2, weighting="uniform", async_mode=True,
        personalize_steps=3, termination_patience=1000))
    with pytest.raises(ValueError, match="personalize_steps"):
        build_experiment(cfg)


def test_drop_nonwinning_weights_frees_losers():
    """Satellite regression: with keep_weights=False the sweep retained
    every launch's materialized winner candidate for the whole sweep;
    once the winner is known the rest must be dropped."""
    from fedtpu.sweep.grid import _drop_nonwinning_weights
    results = {
        ((8,), 0.01): {"win": {"w": np.ones(4)}},
        ((8,), 0.05): {"win": {"w": np.zeros(4)}},
        ((4, 4), 0.01): {"win": None},
    }
    dropped = _drop_nonwinning_weights(results, ((8,), 0.05))
    assert dropped == 1
    assert results[((8,), 0.01)]["win"] is None
    assert results[((4, 4), 0.01)]["win"] is None
    assert results[((8,), 0.05)]["win"] is not None


def test_sweep_emits_launch_spans(tmp_path):
    from fedtpu.data import load_dataset
    from fedtpu.sweep.grid import run_grid_search
    ev = tmp_path / "sweep.jsonl"
    cfg = dataclasses.replace(_cfg(rounds=2, tmp=ev), fed=FedConfig(
        rounds=2, weighting="uniform", termination_patience=1000))
    ds = load_dataset(cfg.data)
    res = run_grid_search(cfg, dataset=ds, hidden_grid=((8,), (4, 4)),
                          lr_grid=(0.01, 0.05), local_steps=10,
                          verbose=False)
    assert "params" in res
    events, bad = load_events(str(ev))
    assert bad == 0
    agg = aggregate(events)
    assert agg["manifest"]["program"] == "sweep"
    assert agg["phases"]["launch"]["count"] >= 1
    assert agg["counters"]["sweep_configs"] == 4
    assert any(e["kind"] == "sweep_end" for e in events)


# ------------------------------------------------------------------ parity
def test_reference_parity_lines_unchanged_with_telemetry_on(tmp_path,
                                                            capsys):
    """The reference-parity stdout (Round/CLIENT/early-stop lines) must be
    byte-identical whether telemetry is off or writing to a sink."""
    from fedtpu.orchestration.loop import run_experiment

    def parity_lines():
        out = capsys.readouterr().out
        return [ln for ln in out.splitlines()
                if ln.startswith(("Round ", "  CLIENT ", "Early stopping",
                                  "Training stopped"))]

    base = ExperimentConfig(
        data=DataConfig(csv_path=None, synthetic_rows=256),
        shard=ShardConfig(num_clients=8),
        model=dataclasses.replace(_cfg().model, hidden_sizes=(4,)),
        fed=FedConfig(rounds=6, tolerance=1.0, termination_patience=2),
        run=RunConfig(log_every=1, log_per_client=True))
    run_experiment(base, verbose=False)          # burn compiles off-capture
    capsys.readouterr()

    run_experiment(base, verbose=True)
    plain = parity_lines()
    with_tel = dataclasses.replace(base, run=dataclasses.replace(
        base.run, telemetry=TelemetryConfig(
            events_path=str(tmp_path / "ev.jsonl"))))
    run_experiment(with_tel, verbose=True)
    traced = parity_lines()

    assert plain, "parity filter matched nothing — stdout shape changed"
    assert any(ln.startswith("Early stopping") for ln in plain)
    assert plain == traced
    # And the sink really was written during the second run.
    assert os.path.getsize(tmp_path / "ev.jsonl") > 0


# -------------------------------------------------------------------- lint
def test_no_bare_prints_outside_allowlist():
    """Every user-facing line goes through the telemetry logger (leveled,
    mirrored to the sink) — a new bare print() in fedtpu/ fails here.

    The walk + allowlist that used to live inline here is now rule FTP005
    (fedtpu.analysis.rules_generic.PRINT_ALLOWLIST — one place), so this
    test is a thin ``fedtpu lint --select FTP005`` invocation."""
    from fedtpu.cli import main as cli_main

    root = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "fedtpu")
    assert cli_main(["lint", root, "--select", "FTP005"]) == 0, (
        "bare print() outside the FTP005 allowlist (use fedtpu.telemetry's "
        "TelemetryLogger instead); run `fedtpu lint --select FTP005` "
        "for locations")
