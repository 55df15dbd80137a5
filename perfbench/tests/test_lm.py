"""The language-model cell's own pieces: the cost from shapes and measured
tokens against a count by hand, the layer reducer on a made-up trace, the
registry reader, and the cell's walk-through on the CPU."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from perfbench import datasets_lm, flops_lm, manifest, xplane
from perfbench.evidence import Evidence

ROOT = os.path.dirname(manifest.HERE)
TINY = {"hidden_size": 8, "num_attention_heads": 2, "num_hidden_layers": 2,
        "num_experts": 4, "num_experts_per_tok": 2, "intermediate_size": 16,
        "vocab_size": 32}


def test_the_cost_of_a_round_is_the_count_by_hand():
    # one sequence of 10 positions: documents of 3 and 4 tokens, 3 of padding
    x = np.zeros((1, 2, 10), np.int32)
    x[0, 1, :7] = [1, 1, 1, 2, 2, 2, 2]
    counts = datasets_lm.counts(x)
    assert counts == {"sequences": 1, "positions": 10, "tokens": 7,
                      "counted": 5, "padding": 3,
                      "attention_pairs": 3 * 4 // 2 + 4 * 5 // 2}
    cost = flops_lm.round_cost(TINY, counts, clients=1)
    h, i, e, v = 8, 16, 4, 32
    attention = 2 * (4 * 2 * h * h * 7 + 2 * 2 * h * 16)   # 2 layers
    router = 2 * 2 * h * e * 7
    experts = 2 * 2 * 3 * 2 * h * i * 7                    # 2 layers, top-2
    head = 2 * h * v * 7
    assert cost["by_part"] == {"attention": 3.0 * attention, "router": 3.0 * router,
                               "experts": 3.0 * experts, "head": 3.0 * head}
    assert cost["flops"] == 3.0 * (attention + router + experts + head)
    assert cost["experts_flops"] == 3.0 * experts
    params = 2 * v * h + 2 * (4 * h * h + 4 * h + h * e + 3 * e * h * i) + h
    assert cost["params"] == params
    assert cost["bytes"] == 4.0 * params * (5 * 1 + 6 * 1 + 4)


def test_the_published_shapes_count_625_6_million_parameters():
    conf = manifest.load(ROOT).config("olmoe-1b7b-l1-fed8")
    p = flops_lm.params(conf)
    assert p["total"] == 625_616_896 and p["experts_per_layer"] == 402_653_184
    assert round(p["layer"] / 1e6, 1) == 419.6


def _view(ops, host=()):
    ops = xplane._self_times(sorted(ops, key=lambda o: (o.start, -o.end)))
    return xplane.TraceView(devices={"/device:TPU:0": ops}, host=list(host),
                            start=0.0, end=max(o.end for o in ops))


def test_lm_layers_sums_self_times_by_layer_and_leaves_the_check_out():
    op = xplane.Op
    ev = Evidence(manifest=manifest.load(ROOT))
    ev.trace = _view(
        [op("while.1", 0, 1000),                       # self: 1000 - 900
         op("fusion.1 bf16[8]", 0, 400), op("fusion.2 f32[8]", 400, 700),
         op("copy.3 f32[8]", 700, 900), op("fusion.9 f32[8]", 1000, 1200),
         op("fusion.1 bf16[8]", 1500, 1600)],          # inside the state check
        host=[op("fedtpu.state_check", 1450, 1700)])
    ev.facts.update(trace_rounds=2, chips=1, peaks={"bf16_flops_per_s": 1e6},
                    cost={"experts_flops": 0.1})
    ev.sinks["job"] = [{"kind": "program_scopes", "payload": {
        "program": "round_step",
        "scopes": {"while.1": "client_train", "fusion.1 bf16[8]": "client_train",
                   "fusion.2 f32[8]": "client_train", "copy.3 f32[8]": "client_train",
                   "fusion.9 f32[8]": "aggregate"},
        "layers": {"fusion.1 bf16[8]": "experts", "fusion.2 f32[8]": "attention",
                   "fusion.9 f32[8]": "server_update"}}}]
    assert ev.metric("experts_ms") == pytest.approx(400e-6 / 2)
    assert ev.metric("attention_ms") == pytest.approx(300e-6 / 2)
    assert ev.metric("server_update_ms") == pytest.approx(200e-6 / 2)
    assert ev.metric("layers_unscoped_ms") == pytest.approx((100 + 200) * 1e-6 / 2)
    assert ev.metric("router_ms") == 0.0
    # 0.1 operations in 0.2 us of experts at a peak of 1e6 a second
    assert ev.metric("experts_mfu") == pytest.approx(100 * 0.1 / 0.2e-6 / 1e6)


def test_a_program_without_layers_gives_nothing_and_does_not_raise():
    ev = Evidence(manifest=manifest.load(ROOT))
    ev.trace = _view([xplane.Op("fusion.1 f32[8]", 0, 100)])
    ev.facts.update(trace_rounds=1)
    ev.sinks["job"] = [{"kind": "program_scopes", "payload": {
        "program": "round_step", "scopes": {"fusion.1 f32[8]": "client_train"},
        "unscoped": []}},
        {"kind": "counters", "payload": {"counters": {"rounds": 3}, "gauges": {}}}]
    for name in ("attention_ms", "experts_mfu", "layers_unscoped_ms",
                 "expert_load_max_over_mean", "moe_tokens_dropped",
                 "lm_padding_pct"):
        assert ev.metric(name) is None


def test_the_registry_reader_reads_counters_gauges_and_shares():
    ev = Evidence(manifest=manifest.load(ROOT))
    ev.facts["lm_positions"] = 8000
    ev.sinks["job"] = [{"kind": "counters", "payload": {
        "counters": {"moe_tokens_dropped": 0.0, "lm_padding_tokens": 1000.0},
        "gauges": {"moe_expert_load_max_over_mean": 1.5}}}]
    assert ev.metric("moe_tokens_dropped") == 0.0
    assert ev.metric("expert_load_max_over_mean") == 1.5
    assert ev.metric("lm_padding_pct") == pytest.approx(12.5)


def test_the_cells_walk_through_on_the_cpu_exits_10():
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    done = subprocess.run(
        [sys.executable, "-m", "perfbench.run", "--workload", "olmoe-l1-fed8-4k",
         "--seed", "2147483999", "--trace", "0", "--rehearse-cpu"], cwd=ROOT,
        env=env, capture_output=True, text=True, timeout=900)
    assert done.returncode == 10, done.stderr[-2000:]
    lines = [json.loads(l) for l in done.stdout.strip().splitlines()]
    last = lines[-1]
    assert last["rehearsal_passed"] is True and last["correct"] is False
    assert last["would_report"] == ["peak_hbm_mb", "round_ms", "setup_s"]
    check = next(l["check"] for l in lines if "check" in l)
    assert check["params_gap"] <= 1e-5 and check["params_moved"] > 1e-3
