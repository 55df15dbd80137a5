"""What every cell's ``per_layer`` entries have to satisfy, whatever their
number: a later PR adds entries and cells, and pins no count here."""

import importlib
import os

from perfbench import manifest

# a layer, piece or counter only ONE model has keeps that model's prefix,
# and lists that model's cells alone
OWN = {"ssm_": "nemotron-l9-fed8-packed", "x4_": "xing4-l5-mtp1-fed8-4k",
       "kl_": "kimi-linear-l5-fed8-packed"}


def check_cell(m, cell: str) -> list:
    """Every entry listing ``cell`` resolves to a file that agrees with it,
    to a reader, and (read from a trace) to a reducer and a field that
    reducer can give; no entry of another model's own lists it. Returns the
    entries."""
    listed = m.metrics_of("per_layer", cell)
    assert listed, cell
    for entry in listed:
        name = entry["name"]
        spec = m.layer_metric(name)
        assert spec["name"] == name
        assert all(spec[k] == entry[k] for k in (
            "unit", "layer", "moves", "better", "source")), name
        read = spec["read"]
        reader = importlib.import_module(f"perfbench.readers.{read['kind']}")
        assert callable(reader.read), name
        if read["kind"] == "trace":
            reducer = importlib.import_module(
                f"perfbench.reducers.{read['reducer']}")
            assert callable(reducer.reduce), name
            if hasattr(reducer, "EMITS"):
                assert read["field"] in reducer.EMITS, name
        if "minus" in read:
            assert read["minus"] in {e["name"] for e in listed}, name
    for prefix, owner in OWN.items():
        for entry in m.doc["per_layer"]:
            if entry["name"].startswith(prefix):
                assert entry["workloads"] == [owner], entry["name"]
    files = {f[:-len(".json")] for f in os.listdir(
        os.path.join(manifest.HERE, "layer_metrics"))}
    assert files == {e["name"] for e in m.doc["per_layer"]}, (
        "a file no entry declares, or an entry without a file")
    return listed
