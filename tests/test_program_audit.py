"""Jaxpr-level program auditor (fedtpu.analysis.program / .collectives).

Three layers, mirroring the auditor's own stack:

  * schedule extraction on hand-built shard_map programs — psum byte
    accounting, scan trip multiplication, and the AUD001 negative
    fixture (a lax.cond whose branches disagree on collectives);
  * donation proof on tiny jitted steps — the realized-alias positive,
    the AUD002 negative fixture (a donated buffer with no output to
    alias), and the ``alias_expected`` exemption for donate-to-free
    stream buffers;
  * the four real engines via the preset probes — trace-only (no
    compile), asserting the structural invariants the goldens pin:
    sync/cohort schedule parity, the async pull broadcast, and the
    GSPMD tp engine's empty explicit schedule.

The full compile-backed contract (digests, HLO census, donation tables)
lives in tests/goldens/audit_*.json, gated by test_audit_gate.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import fedtpu.parallel  # noqa: F401  (installs the jax.shard_map shim)
from fedtpu.analysis.collectives import (comm_bytes, extract_schedule,
                                         schedule_digest)
from fedtpu.analysis.program import (_PROBES, _synthetic_cfg, diff_audit,
                                     donation_proof, engine_audit_spec,
                                     hlo_collective_census)
from fedtpu.parallel.mesh import make_mesh

P = jax.sharding.PartitionSpec
CLIENTS = "clients"


def _mesh():
    return make_mesh(num_clients=len(jax.devices()))


def _shard_mapped(body, mesh, **kwargs):
    return jax.shard_map(body, mesh=mesh, in_specs=P(CLIENTS),
                         out_specs=P(CLIENTS), **kwargs)


# ------------------------------------------------------- schedule extraction


def test_extract_schedule_counts_psum_bytes():
    mesh = _mesh()

    def body(x):
        return jax.lax.psum(x, CLIENTS) * x

    x = jnp.ones((len(jax.devices()), 4), jnp.float32)
    sched = extract_schedule(jax.make_jaxpr(_shard_mapped(body, mesh))(x))
    assert [op.op for op in sched.ops] == ["psum"]
    assert sched.ops[0].axes == (CLIENTS,)
    # per-shard operand: (1, 4) f32 = 16 bytes, one trip
    assert comm_bytes(sched.ops) == 16
    assert not sched.findings and not sched.has_dynamic


@pytest.mark.parametrize("check_vma", [True, False])
def test_schedule_is_the_same_with_and_without_check_vma(check_vma):
    """jax names a collective's primitive by how shard_map types it
    (``psum_invariant`` under check_vma, ``psum`` without): the walker
    must read either. The one test a renamed primitive should fail."""
    mesh = _mesh()

    def body(x):
        total = jax.lax.psum(x, CLIENTS)
        return jax.lax.all_gather(x * total, CLIENTS, tiled=True)

    n = len(jax.devices())
    x = jnp.ones((n, 4), jnp.float32)
    sched = extract_schedule(jax.make_jaxpr(
        _shard_mapped(body, mesh, check_vma=check_vma))(x))
    assert [(op.op, op.axes, op.shapes, op.trips) for op in sched.ops] == [
        ("psum", (CLIENTS,), ((1, 4),), 1),
        ("all_gather", (CLIENTS,), ((1, 4),), 1),
    ]
    assert comm_bytes(sched.ops) == 32
    assert not sched.findings


def test_scan_multiplies_collective_trips():
    mesh = _mesh()
    steps = 5

    def body(x):
        def inner(c, _):
            return c + jax.lax.psum(c, CLIENTS), None
        out, _ = jax.lax.scan(inner, x, None, length=steps)
        return out

    x = jnp.ones((len(jax.devices()), 4), jnp.float32)
    sched = extract_schedule(jax.make_jaxpr(_shard_mapped(body, mesh))(x))
    assert [op.trips for op in sched.ops] == [steps]
    assert comm_bytes(sched.ops) == 16 * steps


def test_branch_divergent_schedule_flags_aud001():
    """The AUD001 negative fixture: one cond branch psums, the other
    doesn't — the round's collective schedule depends on a runtime
    predicate, so SPMD ranks can disagree and deadlock."""
    mesh = _mesh()

    def body(x):
        return jax.lax.cond(x.sum() > 0,
                            lambda v: jax.lax.psum(v, CLIENTS) * v,
                            lambda v: v * 2.0, x)

    x = jnp.ones((len(jax.devices()), 4), jnp.float32)
    sched = extract_schedule(jax.make_jaxpr(_shard_mapped(body, mesh))(x))
    codes = [f.code for f in sched.findings]
    assert codes == ["AUD001"]
    assert "branch" in sched.findings[0].message


def test_branch_identical_schedule_is_clean():
    mesh = _mesh()

    def body(x):
        return jax.lax.cond(x.sum() > 0,
                            lambda v: jax.lax.psum(v, CLIENTS) + 1.0,
                            lambda v: jax.lax.psum(v, CLIENTS) - 1.0, x)

    x = jnp.ones((len(jax.devices()), 4), jnp.float32)
    sched = extract_schedule(jax.make_jaxpr(_shard_mapped(body, mesh))(x))
    assert not sched.findings
    assert [op.op for op in sched.ops] == ["psum"]


# ------------------------------------------------- what a golden pins


def test_census_counts_combined_collectives():
    """XLA's combiner folds several all-reduces into one whose result
    type is a tuple (spaces inside): still one instruction."""
    text = "\n".join([
        "%all-reduce.5 = (f32[50]{0}, f32[], /*index=2*/f32[2,2]{1,0}) "
        "all-reduce(%a, %b, %c), channel_id=1, to_apply=%add",
        "%ag = f32[8,4]{1,0} all-gather-start(%x), dimensions={0}",
        "%gte = f32[] get-tuple-element(%all-reduce.5), index=1",
    ])
    assert hlo_collective_census(text) == {"all-reduce": 1, "all-gather": 1}


def _report(schedule, census):
    return {"engines": {"e": {"schedule": schedule, "schedule_digest": "d",
                              "hlo_collectives": census}}}


@pytest.mark.parametrize("schedule,golden,live,mismatches", [
    # traced schedule pins the engine: the compiled census is reported only
    ([{"op": "psum"}], {"all-reduce": 13}, {"all-reduce": 1}, 0),
    ([{"op": "psum"}], {"all-reduce": 13}, {}, 0),
    # GSPMD engine (empty traced schedule): which kinds, not how many
    ([], {"all-reduce": 15}, {"all-reduce": 2}, 0),
    ([], {"all-reduce": 15}, {"all-reduce": 2, "all-gather": 1}, 1),
    ([], {"all-reduce": 15}, {}, 1),
])
def test_diff_audit_pins_census_kinds_only_without_a_traced_schedule(
        schedule, golden, live, mismatches):
    out = diff_audit(_report(schedule, live), _report(schedule, golden))
    assert len(out) == mismatches, out
    # anything else in the contract is still compared exactly
    other = _report(schedule, live)
    other["engines"]["e"]["schedule_digest"] = "x"
    assert len(diff_audit(other, _report(schedule, golden))) == mismatches + 1


# ------------------------------------------------------------ donation proof


def _compiled_text(step, *args):
    return step.lower(*args).compile().as_text()


def test_donation_proof_proves_realized_alias():
    step = jax.jit(lambda s: s * 2.0, donate_argnums=(0,))
    s = jnp.ones((1024,), jnp.float32)
    proof = donation_proof(_compiled_text(step, s), (s,), (0,))
    assert proof["ok"], proof
    assert [row["aliased"] for row in proof["table"]] == [True]


def test_donation_proof_flags_unaliased_aud002():
    """The AUD002 negative fixture: `b` is donated but the program emits
    no output of its shape, so the donation can never be realized."""
    step = jax.jit(lambda s, b: s + b.sum(), donate_argnums=(0, 1))
    s = jnp.ones((1024,), jnp.float32)
    b = jnp.ones((2048,), jnp.float32)
    proof = donation_proof(_compiled_text(step, s, b), (s, b), (0, 1))
    assert not proof["ok"]
    codes = [f.code for f in proof["findings"]]
    assert codes == ["AUD002"]
    by_alias = {row["shape"][0]: row["aliased"] for row in proof["table"]}
    assert by_alias == {1024: True, 2048: False}


def test_alias_expected_exempts_consumed_stream_buffers():
    """Same program, but arg 1 declared donate-to-free (the cohort-xs
    idiom): the row stays unaliased in the table, with no finding."""
    step = jax.jit(lambda s, b: s + b.sum(), donate_argnums=(0, 1))
    s = jnp.ones((1024,), jnp.float32)
    b = jnp.ones((2048,), jnp.float32)
    proof = donation_proof(_compiled_text(step, s, b), (s, b), (0, 1),
                           alias_expected=(0,))
    assert proof["ok"], proof
    assert [row["aliased"] for row in proof["table"]] == [True, False]


def test_sub_floor_unaliased_leaf_is_table_only():
    proof_rows = donation_proof(
        "HloModule m, entry_computation_layout={()->()}",  # no alias header
        (jnp.ones((4,), jnp.float32),), (0,))
    # 16 bytes < the 1 KiB defect floor: recorded, not flagged.
    assert proof_rows["table"][0]["aliased"] is False
    assert proof_rows["ok"], proof_rows


# ----------------------------------------------------------- engine schedules


def _trace_engine(name, preset="income-2"):
    cfg = _synthetic_cfg(preset, 256)
    step, args, spec, mesh, _ = _PROBES[name](cfg)
    return extract_schedule(jax.make_jaxpr(step)(*args)), spec, mesh


def test_sync_engine_schedule_is_pure_psum():
    sched, spec, _ = _trace_engine("sync")
    assert sched.ops, "sync engine traced to an empty schedule"
    assert {op.op for op in sched.ops} == {"psum"}
    assert all(op.axes == (CLIENTS,) for op in sched.ops)
    assert not sched.findings
    assert comm_bytes(sched.ops) > 0
    assert spec["engine"] == "sync"


def test_cohort_schedule_matches_sync_parity():
    """The cohort scheduler's design claim: a cohort step runs the SAME
    per-round collective program as the sync engine — byte for byte."""
    sync_sched, _, _ = _trace_engine("sync")
    cohort_sched, spec, _ = _trace_engine("cohort")
    assert schedule_digest(cohort_sched.ops) == schedule_digest(sync_sched.ops)
    assert comm_bytes(cohort_sched.ops) == comm_bytes(sync_sched.ops)
    assert spec["alias_expected"] == (0,)


def test_async_engine_gathers_pulls():
    sched, spec, _ = _trace_engine("async")
    kinds = {op.op for op in sched.ops}
    assert "psum" in kinds and "all_gather" in kinds
    assert not sched.findings
    assert spec["engine"] == "async"


def test_tp_engine_has_no_explicit_collectives():
    """GSPMD engine: sharding constraints only — the collective schedule
    materializes post-partitioning, so the jaxpr walk must come back
    empty and the contract leans on the compiled-HLO census instead."""
    if len(jax.devices()) < 2 or len(jax.devices()) % 2:
        pytest.skip("tp probe needs an even device count >= 2")
    sched, spec, mesh = _trace_engine("tp")
    assert sched.ops == []
    assert not sched.findings
    assert set(spec["collective_axes"]) == {"clients", "model"}
    assert dict(mesh.shape)["model"] == 2


def test_engine_audit_spec_selects_like_build_experiment():
    import dataclasses as dc
    cfg = _synthetic_cfg("income-2", 256)
    assert engine_audit_spec(cfg)["engine"] == "sync"
    assert engine_audit_spec(dc.replace(
        cfg, fed=dc.replace(cfg.fed, async_mode=True)))["engine"] == "async"
    assert engine_audit_spec(dc.replace(
        cfg, run=dc.replace(cfg.run, model_parallel=2)))["engine"] == "tp"
    assert engine_audit_spec(dc.replace(
        cfg, fed=dc.replace(cfg.fed, cohort_size=2)))["engine"] == "cohort"


def test_manifest_audit_summary_shape():
    """The run-manifest stamp: trace-only (no donation proof), carrying
    exactly the keys orchestration/loop.py ships."""
    from fedtpu.analysis.program import audit_step_summary
    cfg = _synthetic_cfg("income-2", 256)
    step, args, _, _, _ = _PROBES["sync"](cfg)
    stamp = audit_step_summary(step, args)
    assert set(stamp) == {"schedule_digest", "collectives",
                          "comm_bytes_per_round", "donation_ok", "findings"}
    assert stamp["donation_ok"] is None  # no compile without donate_argnums
    assert stamp["collectives"] > 0 and stamp["findings"] == 0
    assert np.array(stamp["comm_bytes_per_round"]) > 0
