"""The command's refusals: no accelerator, no program."""

import os
import shutil
import subprocess
import sys

from perfbench import manifest

ROOT = os.path.dirname(manifest.HERE)
CELL = ["--workload", "income2560-default", "--seed", "1", "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run([sys.executable, "-m", "perfbench.run", *CELL], cwd=cwd,
                          env=env, capture_output=True, text=True, timeout=300)


def test_no_accelerator_means_no_result():
    done = _run(ROOT)
    assert done.returncode == 3 and done.stdout == ""
    assert "refusing to measure" in done.stderr


def test_the_benchmark_alone_does_not_run(tmp_path):
    shutil.copytree(manifest.HERE, tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    done = _run(tmp_path)
    assert done.returncode == 4 and done.stdout == ""


import json

import pytest


def _host_side(cell, trace):
    """What a walk-through on the CPU can read: every end-to-end metric, and
    of the per-layer ones those the host's clock, the program's spans and its
    counters give (a CPU trace has no device plane). From the manifest, so a
    PR that declares one more breaks nothing here."""
    m = manifest.load(ROOT)
    group = "per_layer" if trace == "1" else "end_to_end"
    return sorted(e["name"] for e in m.metrics_of(group, cell)
                  if e["source"] != "device_trace")


@pytest.mark.parametrize("cell,trace", [
    ("income2560-chunk100", "0"), ("income2560-default", "1")])
def test_rehearsal_walks_the_flow_and_reports_nothing(cell, trace):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    done = subprocess.run(
        [sys.executable, "-m", "perfbench.run", "--workload", cell, "--seed", "5",
         "--trace", trace, "--rehearse-cpu"], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=600)
    assert done.returncode == 10, done.stderr[-2000:]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert last["rehearsal_passed"] is True and last["correct"] is False
    assert "metrics" not in last and "device" not in last
    would = _host_side(cell, trace)
    # no device metric on the CPU, and nothing the manifest does not list
    assert set(last["would_report"]) <= set(would)
    if trace == "0":
        assert last["would_report"] == would == [
            "peak_hbm_mb", "round_ms", "setup_s"]
    else:
        assert {"backend_init_s", "build_span_s", "data_build_s",
                "job_compiles", "job_fixed_s", "loop_round_ms",
                "setup_compile_s", "setup_compiles"} <= set(
                    last["would_report"])
