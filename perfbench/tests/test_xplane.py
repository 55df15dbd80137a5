"""The trace reduction, on made-up operations and on a small trace recorded
on the chip (perfbench/fixtures/, PR 22)."""

import os

import pytest

from perfbench import manifest, xplane
from perfbench.evidence import Evidence
from perfbench.xplane import Op, TraceView

FIXTURES = os.path.join(manifest.HERE, "fixtures")
US = 1000.0


def _view():
    # one device, two rounds of: fusion 10 us, a while of two 5 us bodies,
    # an all-reduce of 4 us of which 1 us runs under a fusion
    ops = []
    for base in (0.0, 100 * US):
        ops += [Op("fusion.1", base, base + 10 * US),
                Op("while.2", base + 20 * US, base + 32 * US),
                Op("fusion.3", base + 21 * US, base + 26 * US),
                Op("fusion.3", base + 27 * US, base + 32 * US),
                Op("all-reduce.4", base + 40 * US, base + 44 * US),
                Op("fusion.5", base + 43 * US, base + 50 * US)]
    ops = xplane._self_times(sorted(ops, key=lambda o: (o.start, -o.end)))
    host = [Op("job", 0.0, 200 * US), Op("$loop.py:1 process_chunk", 51 * US, 99 * US),
            Op("np.asarray", 60 * US, 70 * US)]
    return TraceView(devices={"/device:TPU:0": ops}, host=host, start=0.0, end=200 * US)


def test_busy_is_the_union_not_the_sum():
    v = _view()
    # per round: 10 + 12 (the while covers its bodies) + 10 (all-reduce and fusion.5 overlap by 1)
    assert xplane.busy_s(v) == pytest.approx(2 * 32e-6)


def test_top_ops_use_self_time():
    ops = dict(xplane.top_ops(_view()))
    assert ops["fusion.3"] == pytest.approx(2 * 10e-6)
    assert ops["while.2"] == pytest.approx(2 * 2e-6)      # 12 less its bodies' 10


def test_collectives_and_their_exposed_part():
    tot, exposed = xplane.collectives(_view(), "all-reduce")
    assert tot == pytest.approx(2 * 4e-6)
    assert exposed == pytest.approx(2 * 3e-6)


def test_async_collectives_pair_start_with_done():
    ops = [Op("all-reduce-start.1", 0, 1 * US), Op("fusion.2", 1 * US, 6 * US),
           Op("all-reduce-done.1", 6 * US, 8 * US)]
    v = TraceView({"/device:TPU:0": xplane._self_times(ops)}, [], 0.0, 8 * US)
    tot, exposed = xplane.collectives(v)
    assert tot == pytest.approx(8e-6) and exposed == pytest.approx(3e-6)


def test_idle_gaps_go_to_the_deepest_host_frame_that_covers_them():
    gaps = dict(xplane.idle_gaps(_view()))
    # 50..100 us is idle: process_chunk covers it, np.asarray only a fifth
    assert gaps["$loop.py:1 process_chunk"] == pytest.approx(50e-6)
    assert sum(gaps.values()) == pytest.approx(200e-6 - 2 * 32e-6)


def test_reducers_read_the_view():
    ev = Evidence(manifest=manifest.load(os.path.dirname(manifest.HERE)))
    ev.trace = _view()
    ev.facts.update(trace_rounds=2, chips=1,
                    cost={"flops": 197e12 * 8e-6, "bytes": 1.0},
                    peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    assert ev.metric("device_round_ms") == pytest.approx(0.032)
    assert ev.metric("device_idle_pct") == pytest.approx(68.0)
    assert ev.metric("round_roofline") == pytest.approx(25.0)
    assert ev.notes["roofline_bound"] == "flops"
    assert ev.metric("allreduce_exposed_ms") == pytest.approx(0.003)


def test_recorded_trace(tmp_path):
    """20 steady rounds of income2560-default at width 1 on a TPU v5e
    (my chip run, PR 22), kept compressed: 9,720 device operations and the
    host's main thread with the profiler's Python frames."""
    import lzma

    packed = os.path.join(FIXTURES, "income2560-default-20rounds.xplane.pb.xz")
    path = tmp_path / "trace.xplane.pb"
    with lzma.open(packed) as src:
        path.write_bytes(src.read())
    v = xplane.load(str(path))
    assert list(v.devices) == ["/device:TPU:0"] and len(v.host) > 10_000
    busy, window = xplane.busy_s(v), v.window_s
    # the window leaves out the profiler's own start and stop
    assert window == pytest.approx(0.7036, abs=1e-3)
    assert busy == pytest.approx(0.5872, abs=1e-3)
    top = xplane.top_ops(v)
    assert top[0][0].startswith("fusion.69 f32[2560,200]")
    assert sum(s for _, s in top) <= busy
    gaps = dict(xplane.idle_gaps(v, n=100))
    assert sum(gaps.values()) == pytest.approx(window - busy, rel=1e-6)
    # most of the idle time is the host waiting in the fetch of a round's metrics
    assert max(gaps, key=gaps.get).endswith("_value")
    assert xplane.collectives(v) == (0.0, 0.0)      # one chip: no all-reduce
