"""Regression tests for the round-2 advisor findings:

1. (medium) In pipelined_stop mode the chunk-end state-finiteness gate
   must ALSO run at periodic-checkpoint boundaries (the pipeline is
   already synced there), so a poisoned state can never persist as the
   latest good checkpoint that resume would restore.
2. (low) The deferred loop-exit state gate must label its quarantine
   checkpoint with the round the SAVED state corresponds to (`rnd`,
   which after a pipelined early stop includes the dropped in-flight
   overshoot chunk) — not `rounds_run`.
"""

import dataclasses

import numpy as np
import pytest

from fedtpu.config import (DataConfig, ExperimentConfig, FedConfig,
                           ModelConfig, RunConfig, ShardConfig)
from fedtpu.orchestration import loop as loop_mod
from fedtpu.orchestration.checkpoint import latest_step, load_checkpoint
from fedtpu.orchestration.loop import build_experiment, run_experiment


def _cfg(**run_kw):
    return ExperimentConfig(
        data=DataConfig(csv_path=None, synthetic_rows=256,
                        synthetic_features=6),
        shard=ShardConfig(num_clients=4, shuffle=False),
        model=ModelConfig(input_dim=6, hidden_sizes=(8,)),
        fed=FedConfig(rounds=12, tolerance=0.0),
        run=RunConfig(rounds_per_step=3, **run_kw),
    )


def test_pipelined_periodic_ckpt_gated_on_state_finiteness(
        tmp_path, monkeypatch):
    # Force the state gate to report "poisoned" while metrics stay finite —
    # the exact scenario (overflowed Adam moments, finite metrics) the gate
    # documents. Before the fix, pipelined mode skipped the gate at
    # checkpoint boundaries and the periodic save persisted the poisoned
    # state as the latest checkpoint resume would restore.
    monkeypatch.setattr(loop_mod, "_tree_finite", lambda t: False)
    ck = str(tmp_path / "ck")
    cfg = _cfg(pipelined_stop=True, checkpoint_dir=ck, checkpoint_every=3)
    res = run_experiment(cfg, verbose=False)
    assert res.diverged and res.stopped_early
    # No periodic save may have happened: the first checkpoint boundary
    # (round 3) must hit the gate BEFORE save_checkpoint.
    assert latest_step(ck) is None
    assert latest_step(str(tmp_path / "ck" / "diverged")) == 3


def test_deferred_gate_quarantine_label_matches_saved_state(
        tmp_path, monkeypatch):
    # Pipelined early stop: the final state carries the dropped in-flight
    # overshoot chunk (state round > rounds_run). The deferred gate's
    # quarantine label must equal the SAVED state's round.
    monkeypatch.setattr(loop_mod, "_tree_finite", lambda t: False)
    ck = str(tmp_path / "ck")
    base = _cfg(pipelined_stop=True, checkpoint_dir=ck)
    cfg = dataclasses.replace(
        base, fed=dataclasses.replace(base.fed, rounds=30, tolerance=1.0,
                                      termination_patience=2))
    res = run_experiment(cfg, verbose=False)
    assert res.stopped_early and res.diverged
    label = latest_step(str(tmp_path / "ck" / "diverged"))
    assert label is not None
    # The contract under test: label == the round stored IN the saved state.
    exp = build_experiment(cfg)
    state, _, step = load_checkpoint(str(tmp_path / "ck" / "diverged"),
                                     state_like=exp.state)
    assert step == label == int(np.asarray(state["round"]))
    # And the overshoot is real: the saved state trained past the recorded
    # history (one in-flight chunk), so rounds_run alone would mislabel it.
    assert label > res.rounds_run


def test_sync_early_stop_exit_gate_catches_poisoned_state(
        tmp_path, monkeypatch):
    # Synchronous mode's one unchecked path: an early-stop break whose
    # final chunk poisoned the state while its pre-update metrics stayed
    # finite. The deferred exit gate must now cover it (review r3) —
    # before, the run returned diverged=False with NaN final params.
    monkeypatch.setattr(loop_mod, "_tree_finite", lambda t: False)
    ck = str(tmp_path / "ck")
    base = _cfg(checkpoint_dir=ck)
    cfg = dataclasses.replace(
        base, fed=dataclasses.replace(base.fed, rounds=30, tolerance=1.0,
                                      termination_patience=1))
    res = run_experiment(cfg, verbose=False)
    assert res.stopped_early and res.diverged
    label = latest_step(str(tmp_path / "ck" / "diverged"))
    exp = build_experiment(cfg)
    state, _, step = load_checkpoint(str(tmp_path / "ck" / "diverged"),
                                     state_like=exp.state)
    assert step == label == int(np.asarray(state["round"]))
