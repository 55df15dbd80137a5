"""The plain reference against ``run_experiment`` at a tiny size on the CPU,
where float32 matmuls are exact: the same rounds to rounding."""

import dataclasses
import os

import numpy as np
import pytest

from perfbench import datasets, manifest
from perfbench.drivers import train

ROOT = os.path.dirname(manifest.HERE)


def _tiny(config_name, seed=3):
    m = manifest.load(ROOT)
    conf = m.config(config_name)
    conf = train._overlay(conf, conf["rehearsal"])
    traffic = m.traffic("width1")
    cfg = train.experiment_config(
        [conf["experiment"], {k: traffic[k] for k in ("run", "fed")}], seed)
    return cfg, datasets.make(conf["dataset"], cfg.shard.num_clients, seed)


@pytest.mark.parametrize("config_name,compute,loss_tol,param_tol", [
    ("income-mlp-2560", "float32", 1e-6, 1e-6),
    ("cifar10-cnn-100", "float32", 5e-6, 2e-5),
    # the configuration's own bf16 compute rounds every activation
    ("cifar10-cnn-100", "bfloat16", 3e-2, 5e-2),
])
def test_system_matches_reference(config_name, compute, loss_tol, param_tol):
    from fedtpu.orchestration.loop import run_experiment

    cfg, ds = _tiny(config_name)
    cfg = cfg.replace(model=dataclasses.replace(cfg.model, compute_dtype=compute))
    ref_losses, ref_params = train.reference_rounds(cfg, ds, 3)
    result = run_experiment(train.with_run(cfg, 3), dataset=ds, verbose=False)
    assert result.rounds_run == 3 and train.job_faults(result, 3) == 0
    assert np.max(np.abs(np.stack(result.loss) - ref_losses)) <= loss_tol
    assert train.params_gap(result.final_params, ref_params) <= param_tol
    assert result.data["generator"].startswith("perfbench.")


def test_a_lower_compute_dtype_parts_from_the_reference():
    from fedtpu.orchestration.loop import run_experiment

    cfg, ds = _tiny("income-mlp-2560")
    ref_losses, _ = train.reference_rounds(cfg, ds, 3)
    low = cfg.replace(model=dataclasses.replace(cfg.model, compute_dtype="bfloat16"))
    result = run_experiment(train.with_run(low, 3), dataset=ds, verbose=False)
    assert np.max(np.abs(np.stack(result.loss) - ref_losses)) > 1e-5


def test_the_same_seed_gives_the_same_rows():
    spec = {"generator": "income_like", "rows_per_client": 16, "test_size": 0.2}
    a, b, c = (datasets.make(spec, 4, s) for s in (5, 5, 6))
    assert np.array_equal(a.x_train, b.x_train) and np.array_equal(a.y_train, b.y_train)
    assert not np.array_equal(a.x_train, c.x_train)
    assert a.x_train.shape == (64, 14) and a.x_test.shape == (16, 14)
    assert abs(int(a.y_train.sum()) + int(a.y_test.sum()) - 40) <= 0
