"""fedtpu.serving — admission control, traces, the serving engine, and
the socket path (ISSUE 6 tier-1 suite).

Pins the contracts the serving front-end documents:
- admission verdict ORDER (rate -> backpressure -> staleness -> accept);
- the versioned trace schema round-trips and synthesis is deterministic;
- replaying the same trace + seed yields a BITWISE-identical per-tick
  metric history (virtual-time determinism, the acceptance criterion);
- checkpoint/restore mid-stream continues to the identical history and
  global params as an uninterrupted run (the graceful-drain satellite);
- drain-time K-buffer starvation surfaces as the PR 5 async_starvation
  event;
- a real localhost serve + loadgen round trip works end to end;
- the report pipeline renders the serving section from serve events.

Subprocess SIGTERM coverage is `slow`-marked (full tier only).
"""

import json
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

from fedtpu.config import ServingConfig
from fedtpu.serving.admission import (ACCEPT, DEPRIORITIZE,
                                      REJECT_BACKPRESSURE, REJECT_RATE,
                                      REJECT_STALE, SCREENED, VERDICTS,
                                      AdmissionController, AdmissionPolicy,
                                      TokenBucket)
from fedtpu.serving.traces import (TRACE_SCHEMA_VERSION, load_trace_arrays,
                                   read_trace, synthesize_trace,
                                   write_trace)
from fedtpu.telemetry.metrics import MetricsRegistry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------- admission

def test_token_bucket_rate_and_refill():
    tb = TokenBucket(rate=2.0, burst=2.0)
    assert tb.take(0.0) and tb.take(0.0)
    assert not tb.take(0.0)            # burst exhausted
    assert tb.take(0.5)                # 0.5 virtual s => 1 token back
    assert not tb.take(0.5)
    # rate 0 disables limiting entirely.
    free = TokenBucket(rate=0.0, burst=1.0)
    assert all(free.take(0.0) for _ in range(100))


def test_admission_check_order_is_rate_backpressure_staleness():
    """The documented precedence: a single update violating EVERY
    constraint is billed to the rate limiter; with rate available, to
    backpressure; then staleness; then accepted."""
    pol = AdmissionPolicy(rate_limit=0.1, rate_burst=1.0, max_pending=4,
                          stale_deprioritize=2, stale_reject=8)
    ctl = AdmissionController(pol, registry=MetricsRegistry())
    # Burn the single burst token on a clean accept.
    assert ctl.decide(0.0, staleness=0, pending=0) == ACCEPT
    # Everything wrong at once, bucket empty -> rate wins.
    assert ctl.decide(0.0, staleness=99, pending=99) == REJECT_RATE
    # One token refilled (10 virtual s at 0.1/s), pending full ->
    # backpressure wins over staleness.
    assert ctl.decide(10.0, staleness=99, pending=99) == REJECT_BACKPRESSURE
    # Rate + pending fine, staleness strictly above the reject bar.
    assert ctl.decide(20.0, staleness=9, pending=0) == REJECT_STALE
    # Between the two staleness bars -> admitted but deprioritized.
    assert ctl.decide(30.0, staleness=3, pending=0) == DEPRIORITIZE
    assert ctl.decide(40.0, staleness=0, pending=0) == ACCEPT
    # The defense verdict never comes from decide() — it is recorded by
    # the engine's screen/quarantine path through record().
    assert ctl.record(SCREENED, 50.0) == SCREENED
    with pytest.raises(ValueError, match="unknown verdict"):
        ctl.record("bogus")
    # Every verdict was exercised and counted (both dict + registry).
    assert set(ctl.counts) == set(VERDICTS)
    assert all(n >= 1 for n in ctl.counts.values())


def test_admission_policy_validates_thresholds():
    with pytest.raises(ValueError):
        AdmissionPolicy(stale_deprioritize=8, stale_reject=4)


# ------------------------------------------------------------------- traces

def test_trace_roundtrip_and_header(tmp_path):
    header, t, user, lat = synthesize_trace(users=10_000, arrivals=500,
                                            horizon_s=30.0, seed=7)
    assert header.v == TRACE_SCHEMA_VERSION
    assert header.users == 10_000 and header.arrivals == 500
    assert np.all(np.diff(t) >= 0)          # sorted virtual time
    assert np.all(lat <= t)                 # pull happened after t=0
    path = tmp_path / "trace.jsonl"
    write_trace(str(path), header, t, user, lat)

    h2, events = read_trace(str(path))
    assert h2.to_json() == header.to_json()
    rows = list(events)
    assert len(rows) == 500
    assert [e.user for e in rows] == user.tolist()
    np.testing.assert_allclose([e.t for e in rows], t, rtol=0, atol=1e-9)

    h3, t3, u3, l3 = load_trace_arrays(str(path))
    np.testing.assert_array_equal(u3, user)
    np.testing.assert_allclose(t3, t, rtol=0, atol=1e-9)
    np.testing.assert_allclose(l3, lat, rtol=0, atol=1e-9)


def test_trace_rejects_unknown_schema_version(tmp_path):
    path = tmp_path / "bad.jsonl"
    path.write_text('{"kind": "header", "v": 99}\n')
    with pytest.raises(ValueError):
        read_trace(str(path))


def test_trace_synthesis_is_deterministic():
    a = synthesize_trace(users=1000, arrivals=200, seed=3)
    b = synthesize_trace(users=1000, arrivals=200, seed=3)
    c = synthesize_trace(users=1000, arrivals=200, seed=4)
    for x, y in zip(a[1:], b[1:]):
        np.testing.assert_array_equal(x, y)
    assert not np.array_equal(a[2], c[2])


# ------------------------------------------------------------------- engine

def _small_cfg(**kw):
    base = dict(cohort=8, buffer_size=2, tick_interval_s=0.5,
                data_rows=64, model_hidden=(8,), seed=0)
    base.update(kw)
    return ServingConfig(**base)


def _small_trace(arrivals=200, seed=11):
    return synthesize_trace(users=500, arrivals=arrivals, horizon_s=10.0,
                            seed=seed)


def _replay(engine, t, user, lat):
    engine.offer_many(zip(user.tolist(), t.tolist(), lat.tolist()))
    engine.drain()
    return engine


def test_engine_replay_is_bitwise_deterministic():
    from fedtpu.serving.engine import ServingEngine
    _, t, user, lat = _small_trace()
    lines = []
    for _ in range(2):
        eng = _replay(ServingEngine(_small_cfg(),
                                    registry=MetricsRegistry()),
                      t, user, lat)
        lines.append(eng.history_lines())
    assert lines[0] == lines[1]
    assert len(lines[0]) >= 10              # ticks actually fired


def test_engine_coalesces_same_slot_arrivals():
    """Multiple queued updates from one USER ride one tick as ONE
    arrival — tick_updates counts updates, tick_slots counts slots.
    (Slot coalescing is per user now: distinct users get distinct slots
    via the binder, so only repeat arrivals from the same user share.)"""
    from fedtpu.serving.engine import ServingEngine
    eng = ServingEngine(_small_cfg(cohort=4, tick_interval_s=0.0),
                        registry=MetricsRegistry())
    # user 0 twice + user 1 once: two slots, three updates.
    for u in (0, 0, 1):
        assert eng.offer(0.1, u, 0.0) == ACCEPT
    eng.drain()
    assert eng.history["tick_updates"][-1] == 3
    assert eng.history["tick_slots"][-1] == 2


def test_distinct_users_never_alias_onto_one_slot():
    """Regression for the residue-map bug the binder replaced: users 0
    and 4 with cohort=4 used to both train slot 0 (`user % C`), silently
    merging two client identities. Stable binding gives them distinct
    slots while capacity lasts."""
    from fedtpu.serving.engine import ServingEngine
    eng = ServingEngine(_small_cfg(cohort=4, tick_interval_s=0.0),
                        registry=MetricsRegistry())
    for u in (0, 4):
        assert eng.offer(0.1, u, 0.0) == ACCEPT
    eng.drain()
    assert eng.binder.peek(0) != eng.binder.peek(4)
    assert eng.history["tick_updates"][-1] == 2
    assert eng.history["tick_slots"][-1] == 2    # was 1 under `u % C`


def test_deprioritized_updates_wait_an_extra_tick():
    from fedtpu.serving.engine import ServingEngine
    eng = ServingEngine(_small_cfg(buffer_size=0, tick_interval_s=0.0,
                                   flush_every=1, stale_deprioritize=0,
                                   stale_reject=16),
                        registry=MetricsRegistry())
    # flush_every=1 with M=0: the accept fires a tick and bumps the
    # version, so the next arrival claiming version 0 is one stale.
    assert eng.offer(0.1, 1, 0.0) == ACCEPT
    assert eng.version == 1
    assert eng.offer(0.2, 2, 0.0, version=0) == DEPRIORITIZE
    assert eng.pending[0].elig_tick == eng.tick_count + 2


def test_stats_and_drain_on_idle_engine_do_not_crash():
    """REVIEW fix (high): a 'stats' request — or the SIGTERM/--once
    drain path — before any update is incorporated must answer with a
    None latency section, not IndexError out of _percentiles (which
    killed the whole single-threaded server and broke the
    drain->checkpoint->exit-75 contract for idle shutdowns)."""
    from fedtpu.serving.engine import ServingEngine
    from fedtpu.serving.server import _handle

    eng = ServingEngine(_small_cfg(), registry=MetricsRegistry())
    resp = _handle(eng, {"op": "stats"})
    assert resp["op"] == "stats"
    assert resp["update_to_incorporation"] is None
    # The idle-shutdown sequence: drain, then the summary emission that
    # precedes the history write + checkpoint in _shutdown.
    assert eng.drain() == 0
    s = eng.emit_summary()
    assert s["update_to_incorporation"] is None and s["incorporated"] == 0


def test_handler_exception_becomes_error_frame():
    """REVIEW fix (low): an unexpected exception inside request handling
    answers an ``error`` frame and counts serve_handler_errors instead
    of escaping and killing the server for every connection."""
    from fedtpu.serving.server import _safe_handle
    from fedtpu.telemetry.trace import NullTracer

    reg = MetricsRegistry()
    # engine=None: any real op dereferences it and raises AttributeError,
    # standing in for an arbitrary internal failure.
    resp = _safe_handle(None, {"op": "stats"}, NullTracer(), reg)
    assert resp["op"] == "error" and "AttributeError" in resp["reason"]
    assert reg.snapshot()["counters"]["serve_handler_errors"] == 1
    # Malformed frames still answer without touching the engine.
    assert _safe_handle(None, None, NullTracer(), reg)["op"] == "error"


def test_engine_checkpoint_restore_is_bitwise(tmp_path):
    """Drain-to-checkpoint at half-stream, restore into a FRESH engine,
    replay the rest: history and global params must match the
    uninterrupted run exactly (the supervise-restart contract)."""
    import jax

    from fedtpu.serving.engine import ServingEngine
    _, t, user, lat = _small_trace(arrivals=120)
    half = 60

    ref = _replay(ServingEngine(_small_cfg(), registry=MetricsRegistry()),
                  t, user, lat)

    eng1 = ServingEngine(_small_cfg(), registry=MetricsRegistry())
    eng1.offer_many(zip(user[:half].tolist(), t[:half].tolist(),
                        lat[:half].tolist()))
    eng1.checkpoint(str(tmp_path))

    eng2 = ServingEngine(_small_cfg(), registry=MetricsRegistry())
    eng2.restore(str(tmp_path))
    _replay(eng2, t[half:], user[half:], lat[half:])

    assert eng2.history_lines() == ref.history_lines()
    for a, b in zip(jax.tree.leaves(eng2.state["params"]),
                    jax.tree.leaves(ref.state["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_restores_admission_and_latency_state(tmp_path):
    """REVIEW fix (medium): the checkpoint carries token-bucket fill,
    per-verdict counts, and latency telemetry — so with rate limiting ON
    a resumed run produces the same verdict sequence, summary counts,
    and percentiles as an uninterrupted one (a fresh bucket would refill
    to full burst and diverge)."""
    from fedtpu.serving.engine import ServingEngine
    cfg = _small_cfg(rate_limit=4.0, rate_burst=2.0)
    _, t, user, lat = _small_trace(arrivals=120)
    half = 60

    ref = _replay(ServingEngine(cfg, registry=MetricsRegistry()),
                  t, user, lat)
    assert ref.admission.counts[REJECT_RATE] > 0   # the limiter did bite

    eng1 = ServingEngine(cfg, registry=MetricsRegistry())
    eng1.offer_many(zip(user[:half].tolist(), t[:half].tolist(),
                        lat[:half].tolist()))
    eng1.checkpoint(str(tmp_path))

    reg2 = MetricsRegistry()
    eng2 = ServingEngine(cfg, registry=reg2)
    eng2.restore(str(tmp_path))
    _replay(eng2, t[half:], user[half:], lat[half:])

    assert eng2.history_lines() == ref.history_lines()
    assert eng2.admission.counts == ref.admission.counts
    assert eng2.latencies == ref.latencies
    s_ref, s2 = ref.summary(), eng2.summary()
    assert s2["update_to_incorporation"] == s_ref["update_to_incorporation"]
    assert s2["admission"] == s_ref["admission"]
    # Histogram + registry instruments cover the WHOLE run post-resume.
    assert eng2._lat_hist.count == ref._lat_hist.count
    assert eng2._lat_hist.bucket_counts == ref._lat_hist.bucket_counts
    counters = reg2.snapshot()["counters"]
    assert counters["serve_updates_incorporated"] == ref.incorporated
    assert counters["admission_" + REJECT_RATE] == \
        ref.admission.counts[REJECT_RATE]


def test_latency_apply_log_and_history_stay_bounded(monkeypatch):
    """REVIEW fix (low): the exact-latency list and the apply log are
    windowed (full distribution lives in the cumulative histogram), and
    --history-window bounds the per-tick history — a long-running server
    must not grow host memory per incorporated update forever."""
    from fedtpu.serving import engine as engine_mod
    from fedtpu.serving.engine import ServingEngine

    monkeypatch.setattr(engine_mod, "LATENCY_WINDOW", 32)
    monkeypatch.setattr(engine_mod, "_APPLIES_MAX", 16)
    monkeypatch.setattr(engine_mod, "_APPLIES_KEEP", 8)
    eng = ServingEngine(
        _small_cfg(buffer_size=0, tick_interval_s=0.0, flush_every=1,
                   stale_deprioritize=2, stale_reject=4,
                   history_window=10),
        registry=MetricsRegistry())
    # Every arrival fires one tick and one apply (M=0): 100 applies.
    for i in range(100):
        assert eng.offer(0.1 * (i + 1), i, 0.0) == ACCEPT
    assert eng.incorporated == 100
    assert len(eng.latencies) <= 32
    assert eng._lat_hist.count == 100                 # full distribution
    assert len(eng._applies_t) <= 16
    # Recent lookups are untouched by compaction.
    assert eng.pulled_version(eng.clock.now) == eng.version == 100
    assert len(eng.history["tick_t"]) == 10
    assert eng.history["tick_version"][-1] == 100


def test_drain_flags_kbuffer_starvation():
    """Fewer buffered updates than the K-buffer needs to apply -> the
    PR 5 async_starvation event fires as an SLO signal at drain."""
    from fedtpu.serving.engine import ServingEngine
    reg = MetricsRegistry()
    eng = ServingEngine(_small_cfg(buffer_size=4, tick_interval_s=0.0),
                        registry=reg)
    eng.offer(0.1, 1, 0.0)
    eng.offer(0.2, 2, 0.0)
    eng.drain()
    assert eng.version == 0                 # never reached an apply
    assert reg.snapshot()["counters"]["async_starvation_events"] == 1


def test_summary_has_slo_sections():
    from fedtpu.serving.engine import ServingEngine
    _, t, user, lat = _small_trace(arrivals=80)
    eng = _replay(ServingEngine(_small_cfg(), registry=MetricsRegistry()),
                  t, user, lat)
    s = eng.summary()
    pct = s["update_to_incorporation"]
    assert set(pct) >= {"p50_s", "p90_s", "p99_s", "mean_s", "max_s"}
    assert 0.0 <= pct["p50_s"] <= pct["p99_s"] <= pct["max_s"]
    assert s["incorporated"] > 0 and s["ticks"] > 0
    assert s["rounds_per_sec"] > 0
    assert sum(s["admission"].values()) == 80


# -------------------------------------------------------------- socket path

def test_serve_loadgen_localhost_smoke(tmp_path):
    """Full wire path in-process: run_server (thread, once=True) fed by
    the loadgen replaying a written trace over localhost TCP."""
    from fedtpu.serving.loadgen import run_loadgen
    from fedtpu.serving.server import run_server

    header, t, user, lat = _small_trace(arrivals=150)
    trace = tmp_path / "trace.jsonl"
    write_trace(str(trace), header, t, user, lat)
    pf = tmp_path / "port"

    th = threading.Thread(
        target=run_server,
        kwargs=dict(cfg=_small_cfg(), port_file=str(pf), once=True,
                    history_path=str(tmp_path / "hist.jsonl"),
                    events=str(tmp_path / "ev.jsonl"), verbose=False))
    th.start()
    try:
        res = run_loadgen(str(trace), port_file=str(pf), batch=64)
    finally:
        th.join(timeout=60)
    assert not th.is_alive()
    # The server's sink says which backend served (chip_smoke reads it).
    manifest = next(json.loads(ln)["payload"]
                    for ln in (tmp_path / "ev.jsonl").read_text().splitlines()
                    if json.loads(ln)["kind"] == "manifest")
    assert manifest["program"] == "serve" and manifest["backend"] == "cpu"
    assert res["events_sent"] == 150
    assert sum(res["admission"].values()) == 150
    stats = res["server_stats"]
    assert stats["ticks"] > 0 and stats["incorporated"] > 0
    # The server wrote its deterministic per-tick history on shutdown.
    hist = (tmp_path / "hist.jsonl").read_text().strip().splitlines()
    assert len(hist) == stats["ticks"]
    assert json.loads(hist[-1])["tick_version"] == stats["version"]


def test_protocol_rejects_version_mismatch_and_keeps_connection():
    from fedtpu.serving.engine import ServingEngine
    from fedtpu.serving.server import _handle

    eng = ServingEngine(_small_cfg(), registry=MetricsRegistry())
    bad = _handle(eng, {"op": "hello", "v": 99})
    assert bad["op"] == "error"
    ok = _handle(eng, {"op": "hello", "v": 1})
    assert ok["op"] == "welcome" and ok["cohort"] == eng.C
    # Unknown op answers an error frame, never raises.
    assert _handle(eng, {"op": "nope"})["op"] == "error"


# ------------------------------------------------------------------- report

def test_report_renders_serving_section(tmp_path):
    from fedtpu.serving.engine import ServingEngine
    from fedtpu.telemetry.report import render_report
    from fedtpu.telemetry.trace import Tracer

    events = tmp_path / "events.jsonl"
    tracer = Tracer(str(events))
    _, t, user, lat = _small_trace(arrivals=100)
    eng = ServingEngine(_small_cfg(buffer_size=4),
                        registry=MetricsRegistry(), tracer=tracer)
    _replay(eng, t, user, lat)
    eng.emit_summary()
    # A second, starved engine on the same sink: two buffered updates
    # never reach the M=4 apply, so the drain emits async_starvation.
    starved = ServingEngine(_small_cfg(buffer_size=4,
                                       tick_interval_s=0.0),
                            registry=MetricsRegistry(), tracer=tracer)
    starved.offer(0.1, 1, 0.0)
    starved.offer(0.2, 2, 0.0)
    starved.drain()
    tracer.close()

    text, prom = render_report(str(events), fmt="text")
    assert "SERVING" in text.upper()
    assert "update_to_incorporation" in text
    assert "rounds/sec" in text
    assert "STARVATION" in text
    assert "fedtpu_update_to_incorporation_seconds" in prom
    assert 'quantile="0.99"' in prom
    assert "fedtpu_admission_accept_total" in prom
    assert "fedtpu_serve_ticks_total" in prom


# -------------------------------------------------- subprocess (full tier)

@pytest.mark.slow
def test_serve_sigterm_drains_checkpoints_and_exits_75(tmp_path):
    """SIGTERM mid-serve: drain, checkpoint, exit EXIT_PREEMPTED (75) —
    the supervise-compatible graceful preemption contract."""
    import signal

    from fedtpu.serving.loadgen import run_loadgen

    pf = tmp_path / "port"
    ckpt = tmp_path / "ckpt"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.Popen(
        [sys.executable, "-m", "fedtpu.cli", "serve", "--platform", "cpu",
         "--port-file", str(pf), "--buffer-size", "2",
         "--checkpoint-dir", str(ckpt),
         "--events", str(tmp_path / "events.jsonl"), "--quiet"],
        cwd=REPO, env=env)
    try:
        header, t, user, lat = _small_trace(arrivals=100)
        trace = tmp_path / "trace.jsonl"
        write_trace(str(trace), header, t, user, lat)
        run_loadgen(str(trace), port_file=str(pf), drain=False)
        proc.send_signal(signal.SIGTERM)
        rc = proc.wait(timeout=120)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30)
    assert rc == 75
    rounds = [p for p in os.listdir(ckpt) if p.startswith("round_")]
    assert rounds, "SIGTERM drain wrote no checkpoint"
