import pytest

from perfbench import arith, flops


MARKER = r"Global Metrics \(Round (\d+)\)"


def _job(monkeypatch, rounds, width, chunk_s, first_chunk_s, stall=None):
    """The lines a job of ``rounds`` prints, against a made-up clock: a
    burst of ``width`` rounds after each chunk; ``stall`` = (chunk, seconds)."""
    now = [100.0]
    monkeypatch.setattr(arith.time, "perf_counter", lambda: now[0])
    out = arith.RoundStamps(MARKER)
    for chunk in range(rounds // width):
        now[0] += first_chunk_s if chunk == 0 else chunk_s
        if stall and stall[0] == chunk:
            now[0] += stall[1]
        for r in range(chunk * width + 1, (chunk + 1) * width + 1):
            print(f"\nRound {r}:\n", file=out, flush=True)
            print(f"  Global Metrics (Round {r}): [accuracy: 0.5]  (1.0 ms/round)",
                  file=out, flush=True)
            now[0] += 1e-5              # printing takes a little
    return out.stamps


@pytest.mark.parametrize("width,rounds", [(1, 50), (100, 600)])
def test_round_intervals_leave_out_what_a_job_pays_once(monkeypatch, width, rounds):
    # 40 ms a round; the first chunk also loads the program (0.9 s more)
    stamps = _job(monkeypatch, rounds, width, 0.040 * width, 0.040 * width + 0.9)
    iv = arith.round_intervals(stamps, rounds, width)
    assert len(iv) == rounds // width - 1
    assert arith.round_ms(iv) == pytest.approx(40.0, rel=1e-3)


def test_a_stall_moves_no_median(monkeypatch):
    stamps = _job(monkeypatch, 40, 1, 0.125, 4.0, stall=(17, 1.4))
    iv = arith.round_intervals(stamps, 40, 1)
    assert max(iv) > 1.4 and arith.round_ms(iv) == pytest.approx(125.0, rel=1e-3)


def test_the_window_takes_the_median_of_its_jobs():
    # two jobs at two levels: their mean, not a point between two clusters
    assert arith.window_round_ms([33.46, 33.82]) == pytest.approx(33.64)
    assert arith.window_round_ms([125.0, 125.2, 140.0]) == pytest.approx(125.2)


def test_a_round_that_was_not_reported_is_an_error(monkeypatch):
    stamps = _job(monkeypatch, 10, 1, 0.1, 0.1)
    del stamps[7]
    with pytest.raises(ValueError, match="not reported"):
        arith.round_intervals(stamps, 10, 1)
    with pytest.raises(ValueError):                 # not whole chunks
        arith.round_intervals(stamps, 10, 4)


def test_only_the_marker_is_stamped_and_only_once(monkeypatch):
    now = [5.0]
    monkeypatch.setattr(arith.time, "perf_counter", lambda: now[0])
    out = arith.RoundStamps(MARKER)
    out.write("Training on 10 rows\n")
    out.write("  Global Metrics (Round 3): [..]")
    now[0] = 6.0
    out.write("  Global Metrics (Round 3): [..]")
    assert out.stamps == {3: 5.0}


@pytest.mark.parametrize("stated,seconds,width,want", [
    (120, 40, 1, 120),          # the window BENCHMARK.json states
    (120, 10, 1, 30),           # a shorter --seconds, in proportion
    (600, 40, 100, 600),
    (600, 4, 100, 200),         # never under two chunks: one interval
    (500, 4, 4, 48),            # whole chunks of the rehearsal's width
])
def test_job_rounds_are_fixed_by_the_files(stated, seconds, width, want):
    assert arith.job_rounds(stated, seconds, 40, width) == want


def test_quartile_spread():
    assert arith.quartile_spread([10, 10, 10, 10]) == 0
    assert arith.quartile_spread([9, 10, 10, 11]) == pytest.approx(0.05)


def test_round_cost_matches_the_compilers_count():
    # XLA counts 2.52e12 FLOPs for the 100 x 504 ConvNet round and 7.47e8
    # for the 8 x 1000 MLP round (AOT compile for a described v5e, PR 22)
    conv = {"kind": "convnet", "image_shape": [32, 32, 3], "conv_channels": [32, 64],
            "hidden_sizes": [256], "num_classes": 10}
    cost = flops.round_cost(conv, 100, 500, 3072)
    assert cost["params"] == 1_070_794
    assert cost["flops"] == pytest.approx(2.52e12, rel=0.03)
    mlp = {"kind": "mlp", "input_dim": 14, "hidden_sizes": [50, 200], "num_classes": 2}
    cost = flops.round_cost(mlp, 8, 1000, 14)
    assert cost["params"] == 11_352
    assert cost["flops"] == pytest.approx(7.47e8, rel=0.08)


def test_roofline_says_which_peak_bounds():
    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert flops.roofline({"flops": 100.0, "bytes": 1.0}, peaks, 1, 2.0) == (50.0, "flops")
    assert flops.roofline({"flops": 1.0, "bytes": 10.0}, peaks, 1, 4.0) == (25.0, "bytes")
