"""End-to-end orchestration loop: history shapes, early stopping semantics
(FL_CustomMLP...:181-192), held-out eval."""

import numpy as np

from fedtpu.config import (DataConfig, ExperimentConfig, FedConfig, RunConfig,
                           ShardConfig)
from fedtpu.orchestration.loop import run_experiment


def _cfg(**fed_kw):
    return ExperimentConfig(
        data=DataConfig(csv_path=None, synthetic_rows=512),
        shard=ShardConfig(num_clients=8),
        fed=FedConfig(rounds=fed_kw.pop("rounds", 10), **fed_kw),
        run=RunConfig(eval_test_every=1),
    )


def test_run_experiment_history_shapes():
    res = run_experiment(_cfg(rounds=5), verbose=False)
    assert res.rounds_run == 5
    for k in ("accuracy", "precision", "recall", "f1"):
        assert len(res.global_metrics[k]) == 5
        assert len(res.pooled_metrics[k]) == 5
        assert len(res.test_metrics[k]) == 5
        assert res.per_client_metrics[k][0].shape == (8,)
    assert len(res.sec_per_round) == 5
    assert res.final_params["layers"][0]["w"].ndim == 2  # global, no client axis


def test_a_synthetic_stand_in_is_said_on_the_first_line_and_in_the_manifest(
        tmp_path, capsys):
    """A preset that finds no CSV trains on synthetic rows; that used to be
    silent. Now the first log line, the manifest and the summary (what a
    --quiet --json run prints) all name the stand-in and its row count."""
    import dataclasses
    import json

    from fedtpu.config import TelemetryConfig
    cfg = _cfg(rounds=1)
    events = tmp_path / "ev.jsonl"
    cfg = dataclasses.replace(cfg, run=dataclasses.replace(
        cfg.run, telemetry=TelemetryConfig(events_path=str(events))))
    res = run_experiment(cfg, verbose=True)
    first = capsys.readouterr().out.lstrip().splitlines()[0]
    rows = cfg.data.synthetic_rows
    assert first.startswith("Data: SYNTHETIC stand-in") and str(rows) in first
    data = res.summary()["data"]
    assert data["kind"] == "synthetic" and data["rows"] == rows
    assert data["train_rows"] + data["test_rows"] == rows
    manifest = next(json.loads(ln)["payload"]
                    for ln in events.read_text().splitlines()
                    if json.loads(ln)["kind"] == "manifest")
    assert manifest["data"] == data


def test_training_improves_metrics():
    res = run_experiment(_cfg(rounds=25), verbose=False)
    acc = res.global_metrics["accuracy"]
    assert acc[-1] > acc[0]
    assert acc[-1] > 0.8  # separable synthetic data


def test_early_stopping_with_huge_tolerance():
    # atol=1.0 makes every round "unchanged": patience must fire exactly.
    res = run_experiment(_cfg(rounds=50, termination_patience=3,
                              tolerance=1.0), verbose=False)
    assert res.stopped_early
    # Round 1 sets prev; rounds 2,3,4 count down 3->0 => stop at round 4.
    assert res.rounds_run == 4


def test_no_early_stop_when_metrics_move():
    res = run_experiment(_cfg(rounds=8, termination_patience=10,
                              tolerance=1e-12), verbose=False)
    assert not res.stopped_early
    assert res.rounds_run == 8


def test_run_experiment_is_deterministic():
    """Same config, two runs, identical metric histories (client-mean,
    pooled, per-client, test, personalized) and final params — the
    reproducibility guarantee the reference undermines with unseeded
    per-rank shuffles (SURVEY.md §2a _split_data)."""
    import jax
    from fedtpu.config import ModelConfig

    cfg = ExperimentConfig(
        data=DataConfig(csv_path=None, synthetic_rows=256,
                        synthetic_features=6),
        shard=ShardConfig(num_clients=8, shuffle=True, shard_seed=5),
        model=ModelConfig(input_dim=6, hidden_sizes=(8,)),
        fed=FedConfig(rounds=6, participation_rate=0.7,
                      personalize_steps=3),
        run=RunConfig(rounds_per_step=3, eval_test_every=3),
    )
    a = run_experiment(cfg, verbose=False)
    b = run_experiment(cfg, verbose=False)
    for k in a.global_metrics:
        np.testing.assert_array_equal(a.global_metrics[k],
                                      b.global_metrics[k])
        np.testing.assert_array_equal(a.pooled_metrics[k],
                                      b.pooled_metrics[k])
        np.testing.assert_array_equal(a.per_client_metrics[k],
                                      b.per_client_metrics[k])
        np.testing.assert_array_equal(a.test_metrics[k], b.test_metrics[k])
        np.testing.assert_array_equal(
            a.personalized_metrics["per_client"][k],
            b.personalized_metrics["per_client"][k])
    jax.tree.map(np.testing.assert_array_equal, a.final_params,
                 b.final_params)
    assert (a.personalized_metrics["client_mean"]
            == b.personalized_metrics["client_mean"])
