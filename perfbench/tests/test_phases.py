"""PR 23's per-layer metrics: the two trace reducers on a hand-built trace,
the six span metrics through the reader that was already there, and the CPU
walk-through's list."""

import json
import os
import subprocess
import sys

import pytest

from perfbench import manifest, xplane
from perfbench.evidence import Evidence
from perfbench.xplane import Op, TraceView

ROOT = os.path.dirname(manifest.HERE)
US = 1000.0
SPAN_METRICS = ("dispatch_ms", "fetch_ms", "stopcheck_ms", "state_check_ms",
                "first_chunk_s", "epilogue_s")


def _ops(*ops):
    return xplane._self_times(sorted(ops, key=lambda o: (o.start, -o.end)))


def _evidence():
    """Two rounds in a window of 100 us. Device 0 runs [10, 30] and [50, 70]
    (a while of 20 us over two bodies of 8); device 1 runs four short
    operations, one of which no event lists and one of which, inside the
    state check's annotation, has a key the round program lists too."""
    dev0 = _ops(Op("fusion.1 f32[8]", 10 * US, 30 * US),
                Op("while.2 s32[]", 50 * US, 70 * US),
                Op("fusion.3 f32[8]", 52 * US, 60 * US),
                Op("all-reduce.4 f32[4]", 60 * US, 68 * US))
    dev1 = _ops(Op("fusion.1 f32[8]", 10 * US, 20 * US),
                Op("mystery.9 f32[2]", 20 * US, 26 * US),
                Op("is-finite.5 pred[]", 30 * US, 32 * US),
                Op("fusion.3 f32[8]", 50 * US, 52 * US))
    host = [Op("job", 0.0, 100 * US),
            Op("fedtpu.chunk", 0.0, 45 * US),
            Op("fedtpu.dispatch", 0.0, 12 * US),
            Op("$array.py:1 _value", 13 * US, 44 * US),
            Op("fedtpu.chunk_fetch", 12 * US, 45 * US),
            Op("fedtpu.stop_check", 45 * US, 48 * US),
            Op("fedtpu.state_check", 48 * US, 55 * US),
            Op("fedtpu.dispatch", 55 * US, 58 * US),
            Op("fedtpu.chunk_fetch", 58 * US, 80 * US)]
    ev = Evidence(manifest=manifest.load(ROOT))
    ev.trace = TraceView(devices={"/device:TPU:0": dev0, "/device:TPU:1": dev1},
                         host=host, start=0.0, end=100 * US)
    ev.facts["trace_rounds"] = 2
    ev.sinks["job"] = [
        {"kind": "program_scopes", "payload": {
            "program": "state_check", "width": None,
            "scopes": {"is-finite.5 pred[]": "state_check",
                       # the same key as an operation of the round program
                       "fusion.3 f32[8]": "state_check"},
            "unscoped": []}},
        {"kind": "program_scopes", "payload": {
            "program": "round_step", "width": 1,
            "scopes": {"fusion.1 f32[8]": "client_train",
                       "fusion.3 f32[8]": "client_eval",
                       "all-reduce.4 f32[4]": "aggregate"},
            "unscoped": ["while.2 s32[]"]}}]
    return ev


def test_idle_time_goes_to_the_phase_open_over_it():
    ev = _evidence()
    # The window's first chunk is left out: read is [45,100], from the end
    # of the first chunk_fetch, for the one round of two that is left.
    # Device 0 idles there [45,50] and [70,100]: 35 us. Under stop_check
    # [45,48] and state_check [48,50]; under dispatch [55,58] nothing, the
    # device is busy; under chunk_fetch [70,80]; under nothing [80,100].
    assert ev.metric("idle_dispatch_ms") == 0.0
    assert ev.metric("idle_fetch_ms") == pytest.approx(10 / 1000)
    assert ev.metric("idle_check_ms") == pytest.approx(5 / 1000)
    assert ev.metric("idle_unspanned_ms") == pytest.approx(20 / 1000)
    assert ev.reduced("host_phases")["idle_ms"] == pytest.approx(35 / 1000)


def test_a_window_of_one_chunk_is_read_whole():
    ev = _evidence()
    ev.trace.host = [h for h in ev.trace.host if h.start < 50 * US]
    ev.trace.end = 50 * US
    ev.facts["trace_rounds"] = 1
    # device 0 idles [0,10] under dispatch, [30,45] under chunk_fetch,
    # [45,48] under stop_check and [48,50] under state_check
    assert ev.metric("idle_dispatch_ms") == pytest.approx(10 / 1000)
    assert ev.metric("idle_fetch_ms") == pytest.approx(15 / 1000)
    assert ev.metric("idle_check_ms") == pytest.approx(5 / 1000)
    assert ev.metric("idle_unspanned_ms") == pytest.approx(0.0)


def test_device_time_goes_to_the_stage_the_program_names():
    ev = _evidence()
    # self times, us: device 0 client_train 20, client_eval 8 (fusion.3
    # runs [52,60], not inside the state check's annotation [48,55], so the
    # round program's stage holds for the key both programs list),
    # aggregate 8, the while's own 4 unscoped; device 1 client_train 10, the
    # unlisted 6 unscoped, state_check 2 + 2 (fusion.3 at [50,52] is the
    # check's). Averaged over two devices, per round of two.
    assert ev.metric("client_train_ms") == pytest.approx(30 / 4 / 1000)
    assert ev.metric("client_eval_ms") == pytest.approx(8 / 4 / 1000)
    assert ev.metric("aggregate_ms") == pytest.approx(8 / 4 / 1000)
    assert ev.metric("metrics_ms") == 0.0
    assert ev.metric("unscoped_ms") == pytest.approx(10 / 4 / 1000)
    assert ev.metric("state_check_device_ms") == pytest.approx(4 / 4 / 1000)
    assert "device_scopes" not in ev.notes
    # no two operations of a device overlap here, so the stages add up to
    # the busy union: 40 and 20 us, over two devices and two rounds
    assert sum(ev.reduced("device_scopes").values()) == pytest.approx(
        1000 * xplane.busy_s(ev.trace) / 2)


def test_an_executable_with_stale_metadata_reads_unscoped_and_says_so():
    ev = _evidence()
    ev.sinks["job"][1]["payload"] = {
        "program": "round_step", "width": 1, "scopes": {},
        "unscoped": ["fusion.1 f32[8]", "while.2 s32[]", "fusion.3 f32[8]",
                     "all-reduce.4 f32[4]"], "stale_metadata": True}
    assert ev.metric("client_train_ms") == 0.0
    # all but the state check's 2 + 2 us (of 60 busy), and device 0's
    # fusion.3, which only the check's event lists now
    assert ev.metric("unscoped_ms") == pytest.approx(48 / 4 / 1000)
    assert "round_step" in ev.notes["device_scopes"]


def test_a_program_without_annotations_or_scopes_gives_nothing():
    ev = _evidence()
    ev.trace.host = [h for h in ev.trace.host if not h.name.startswith("fedtpu.")]
    ev.sinks["job"] = []
    for name in ("idle_dispatch_ms", "idle_unspanned_ms", "client_train_ms",
                 "unscoped_ms"):
        assert ev.metric(name) is None


def test_the_six_span_metrics_are_files_read_by_the_span_reader():
    m = manifest.load(ROOT)
    for name in SPAN_METRICS:
        assert m.layer_metric(name)["read"]["kind"] == "span"
    ev = Evidence(manifest=m)

    def span(phase, dur_s, rounds=None):
        return {"kind": "span", "phase": phase, "dur_s": dur_s,
                "payload": {} if rounds is None else {"rounds": rounds}}
    # a job of three chunks of four rounds; the first chunk compiles
    ev.sinks["job"] = [
        span("build", 0.5), span("compile", 2.0, 4),
        span("chunk_fetch", 0.9, 4), span("chunk", 3.0, 4),
        span("stop_check", 0.5, 4), span("state_check", 0.7, 4),
        span("dispatch", 0.004, 4), span("chunk_fetch", 0.100, 4),
        span("chunk", 0.120, 4), span("stop_check", 0.008, 4),
        span("state_check", 0.002, 4),
        span("dispatch", 0.008, 4), span("chunk_fetch", 0.108, 4),
        span("chunk", 0.124, 4), span("stop_check", 0.004, 4),
        span("state_check", 0.006, 4), span("epilogue", 0.25)]
    assert ev.metric("dispatch_ms") == pytest.approx(1.5)        # median of 1, 2
    assert ev.metric("fetch_ms") == pytest.approx(26.0)          # 25, 27: the first left out
    assert ev.metric("stopcheck_ms") == pytest.approx(1.5)
    assert ev.metric("state_check_ms") == pytest.approx(1.0)
    assert ev.metric("first_chunk_s") == pytest.approx(2.0)
    assert ev.metric("epilogue_s") == pytest.approx(0.25)


def test_the_walk_through_lists_the_span_metrics():
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    done = subprocess.run(
        [sys.executable, "-m", "perfbench.run", "--workload", "income2560-default",
         "--seed", "2147483659", "--trace", "1", "--rehearse-cpu"], cwd=ROOT,
        env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 10, done.stderr[-2000:]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert last["rehearsal_passed"] is True and last["correct"] is False
    assert set(SPAN_METRICS) <= set(last["would_report"])
    # a CPU has no device plane: no metric of the trace reducers
    assert not {"idle_fetch_ms", "client_train_ms", "unscoped_ms"} & set(last["would_report"])
