"""chip_smoke.py off the chip: the gate refuses, the data has the
reference's shape.

The smoke itself only means something on a TPU (``python chip_smoke.py``
through the chip tool). What can be held here is its contract where there
is no chip — non-zero exit, ``"ok": false`` on the last line, nothing
trained — and the seeded CSV it trains on.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402  (imports no jax, by design)


@pytest.mark.parametrize("option", [[], ["--chips", "4"]],
                         ids=["one-chip", "four-chips"])
def test_gate_refuses_the_cpu_and_trains_nothing(tmp_path, option):
    """Under the tests' CPU pin the first phase is the last: exit code not
    0, a last line that parses and says ok false, no data written, no
    trainer phase reached."""
    out = tmp_path / "out"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), *option,
         "--out", str(out)],
        cwd=str(tmp_path), capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    lines = [json.loads(ln) for ln in proc.stdout.splitlines()
             if ln.startswith("{")]
    assert lines[-1]["ok"] is False
    assert lines[-1]["device"]["platform"] == "cpu"
    assert [ln["phase"] for ln in lines[:-1]] == ["gate"]
    assert lines[0]["ok"] is False and lines[0]["rehearsal"] is False
    assert not any(name.endswith(".csv") or "events" in name
                   for name in os.listdir(out))


def test_income_csv_has_the_reference_shape_and_is_seeded(tmp_path):
    """SURVEY.md §0: 10,000 rows, 14 features + 'income', eight string
    feature columns, labels balanced 5,000/5,000 — and a function of the
    seed alone."""
    a, b, c = (str(tmp_path / n) for n in ("a.csv", "b.csv", "c.csv"))
    info = chip_smoke.write_income_csv(a, seed=0)
    chip_smoke.write_income_csv(b, seed=0)
    chip_smoke.write_income_csv(c, seed=1)
    assert open(a, "rb").read() == open(b, "rb").read()
    assert open(a, "rb").read() != open(c, "rb").read()
    assert info["rows"] == 10_000 and info["columns"] == 15
    assert info["labels"] == {">50K": 5_000, "<=50K": 5_000}

    import pandas as pd
    df = pd.read_csv(a)
    assert df.shape == (10_000, 15) and df.columns[-1] == "income"
    strings = [col for col in df.columns[:-1]
               if not pd.api.types.is_numeric_dtype(df[col])]
    assert len(strings) == 8 == info["string_feature_columns"]
    assert sorted(df["income"].unique()) == ["<=50K", ">50K"]


def test_income_csv_goes_through_the_host_pipeline(tmp_path):
    """The CSV the smoke writes loads through the real pipeline (parse,
    label encoding, scaling, split, pack_clients) to the shapes the
    income-8 preset trains on, and says where it came from."""
    from fedtpu.config import DataConfig, ShardConfig
    from fedtpu.data import data_notice
    from fedtpu.data.sharding import pack_clients
    from fedtpu.data.tabular import load_tabular_dataset

    path = str(tmp_path / "income.csv")
    chip_smoke.write_income_csv(path, seed=0)
    ds = load_tabular_dataset(DataConfig(csv_path=path))
    assert ds.x_train.shape == (8_000, 14) and ds.x_test.shape == (2_000, 14)
    assert ds.num_classes == 2 and set(np.unique(ds.y_train)) == {0, 1}
    assert list(ds.label_classes) == ["<=50K", ">50K"]
    assert ds.source["kind"] == "csv" and ds.source["rows"] == 10_000
    assert ds.source["parser"] in ("native", "pandas")
    assert path in data_notice(ds) and "SYNTHETIC" not in data_notice(ds)
    packed = pack_clients(ds.x_train, ds.y_train, ShardConfig(num_clients=8))
    assert packed.x.shape == (8, 1_000, 14)
    assert float(packed.mask.sum()) == 8_000
