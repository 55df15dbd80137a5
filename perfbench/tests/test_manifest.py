"""BENCHMARK.json against the contract, and the proof that a cell, a traffic
mix and a span-read per-layer metric are added as files and entries alone."""

import json
import os
import shutil

import pytest

from perfbench import manifest
from perfbench.evidence import Evidence

ROOT = os.path.dirname(manifest.HERE)


def test_the_shipped_manifest_is_valid_and_every_name_resolves():
    m = manifest.load(ROOT)
    for w in m.doc["workloads"]:
        cell = m.cell(w["name"])
        assert m.config(cell["config"])["experiment"]
        assert os.path.exists(os.path.join(
            manifest.HERE, "drivers", m.traffic(cell["traffic"])["driver"] + ".py"))
        assert m.metrics_of("per_layer", w["name"])
    assert m.doc["paths"] == ["perfbench"]
    assert sum(w["chips"] == 4 for w in m.doc["workloads"]) == 1
    assert len(json.dumps(m.doc)) < 64 * 1024


def _copy(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(manifest.HERE, root / "perfbench",
                    ignore=shutil.ignore_patterns("out", "__pycache__", "fixtures"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root / "BENCHMARK.json")
    return root


def _edit(root, fn):
    path = root / "BENCHMARK.json"
    doc = json.loads(path.read_text())
    fn(doc)
    path.write_text(json.dumps(doc))


@pytest.mark.parametrize("break_it,says", [
    (lambda d: d["workloads"][0].update(name="has space"), "workload name"),
    (lambda d: d["end_to_end"][0].update(unit="tokens per second"), "unit"),
    (lambda d: d["end_to_end"][0].update(unit="x" * 17), "unit"),
    (lambda d: d["workloads"][0].update(traffic="nowhere"), "no file traffic/nowhere.json"),
    # one may always take four chips and, at eight cells, a second: a third not
    (lambda d: [w.update(chips=4) for w in d["workloads"][:3]], "a quarter"),
    (lambda d: d["per_layer"][0].update(moves="nothing"), "moves no end-to-end"),
    (lambda d: d["end_to_end"][0].update(bound=0.2), "bound"),
    (lambda d: d["end_to_end"][0].update(source="program_span"), "taken by the benchmark"),
    (lambda d: d["end_to_end"][0].update(why="extra key"), "metric entry keys"),
    (lambda d: d.update(run_seconds=52), "run_seconds"),
    (lambda d: d["workloads"].append(dict(d["workloads"][0], name="twin")), "pair appears twice"),
])
def test_what_the_contract_refuses_is_refused(tmp_path, break_it, says):
    root = _copy(tmp_path)
    _edit(root, break_it)
    with pytest.raises(manifest.ManifestError, match=says):
        manifest.load(str(root), str(root / "perfbench"))


def test_a_cell_a_traffic_mix_and_a_span_metric_are_added_as_files_only(tmp_path):
    root = _copy(tmp_path)
    bench = root / "perfbench"
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}

    (bench / "traffic" / "width7.json").write_text(json.dumps({
        "name": "width7", "driver": "train", "run": {"rounds_per_step": 7},
        "fed": {"termination_patience": 1000000}, "check_rounds": 3,
        "warmup_rounds": 14, "trace_chunks": 2, "fixed_job_chunks": 2,
        "round_marker": r"Global Metrics \(Round (\d+)\)"}))
    (bench / "workloads" / "income2560-w7.json").write_text(json.dumps({
        "name": "income2560-w7", "config": "income-mlp-2560",
        "traffic": "width7", "chips": 1, "why": "a throw-away cell",
        "window": {"jobs": 2, "job_rounds": 700}}))
    (bench / "layer_metrics" / "stop_check_ms.json").write_text(json.dumps({
        "name": "stop_check_ms", "unit": "ms", "layer": "host round loop",
        "moves": "round_ms", "better": "lower", "source": "program_span",
        "read": {"kind": "span", "phase": "stop_check", "stat": "median",
                 "scale": 1000.0}}))

    def add(doc):
        doc["workloads"].append({"name": "income2560-w7", "config": "income-mlp-2560",
                                 "traffic": "width7", "chips": 1,
                                 "why": "a throw-away cell"})
        doc["per_layer"].append({"name": "stop_check_ms", "unit": "ms",
                                 "better": "lower", "source": "program_span",
                                 "layer": "host round loop", "moves": "round_ms"})
    _edit(root, add)

    m = manifest.load(str(root), str(bench))
    cell = m.cell("income2560-w7")
    assert m.traffic(cell["traffic"])["run"]["rounds_per_step"] == 7
    assert "stop_check_ms" in [x["name"] for x in m.metrics_of("per_layer", "income2560-w7")]
    # the new metric is read by the reader that was already there
    ev = Evidence(manifest=m)
    ev.sinks["job"] = [
        {"kind": "span", "phase": "stop_check", "dur_s": 0.002, "payload": {}},
        {"kind": "span", "phase": "stop_check", "dur_s": 0.004, "payload": {}},
        {"kind": "span", "phase": "chunk", "dur_s": 9.0, "payload": {"rounds": 7}}]
    assert ev.metric("stop_check_ms") == pytest.approx(3.0)
    # and nothing that was there changed
    assert all(p.read_bytes() == body for p, body in before.items())


def test_a_reader_with_nothing_to_read_returns_nothing():
    m = manifest.load(ROOT)
    ev = Evidence(manifest=m)
    assert ev.metric("build_span_s") is None        # no sink
    assert ev.metric("allreduce_ms") is None        # no trace
    assert ev.metric("chunk_host_ms") is None       # neither
