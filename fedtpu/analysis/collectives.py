"""Jaxpr-level collective-schedule extraction.

The MPI reference hangs forever when two ranks disagree on the next
collective (SURVEY.md §5); our port's runtime answer is PR 5's
collective watchdog, which can only turn the hang into an exit-75 crash
*after* the timeout burns.  This module rules the failure class out
statically: it walks the traced jaxpr of a round program and recovers
the ordered collective schedule — primitive, mesh axes, per-device
operand shapes/bytes, and the static trip count contributed by
enclosing ``lax.scan``s — then proves the schedule is identical across
every config-reachable ``lax.cond`` branch (finding ``AUD001`` when it
is not).  The same walk yields the per-round communication-byte account
that quantifies ROADMAP item 2's byte-bound gap.

Byte semantics: ``operand_bytes`` is the sum of the op's input-operand
sizes as seen *per device* (inside ``shard_map`` the walk sees per-shard
avals).  That is the tensor footprint handed to the collective, not the
wire traffic — algorithm-dependent wire bytes (ring vs tree all-reduce)
are a backend choice this static account deliberately stays above.

Primitive naming is empirical against the installed jax (0.9):
``jax.lax.psum`` traces as ``psum_invariant`` inside
``shard_map(check_vma=True)`` and as ``psum`` with the check off (``psum2``
on older releases), ``psum_scatter`` lowers to a ``reduce_scatter`` eqn,
and ``pvary`` / ``pcast`` / ``pbroadcast`` eqns are shard_map's
replication-typing markers (no wire transfer) — excluded by design.
``tests/test_program_audit.py::test_schedule_is_the_same_with_and_without_check_vma``
is the one test a renamed primitive should fail.

Import discipline: like the rest of the analysis package this module
never imports jax at module scope (``fedtpu lint`` must stay
backend-free); the walker only touches duck-typed jaxpr objects handed
in by callers who already traced something.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
from typing import Any, Iterable, Optional

__all__ = [
    "AuditFinding",
    "CollectiveOp",
    "ScheduleResult",
    "comm_bytes",
    "extract_schedule",
    "schedule_digest",
]

# eqn primitive name -> canonical collective name. Every spelling a jax
# release has used stays, so an older jax still reads: `psum` without
# check_vma, `psum2` under shard_map before 0.7, `*_invariant` under
# check_vma since.
COLLECTIVE_PRIMS = {
    "psum": "psum",
    "psum2": "psum",
    "psum_invariant": "psum",
    "pmax": "pmax",
    "pmin": "pmin",
    "all_gather": "all_gather",
    "all_gather_invariant": "all_gather",
    "ppermute": "ppermute",
    "pgather": "pgather",
    "reduce_scatter": "psum_scatter",
    "all_to_all": "all_to_all",
}

# Ops whose accumulation order XLA does not pin across backends/layouts
# (scatter with duplicate indices, segment-style adds lower to these).
# Reported informationally — bitwise replay contracts care.
NONDETERMINISTIC_PRIMS = {
    "scatter-add",
    "scatter-mul",
    "scatter-min",
    "scatter-max",
}

# Control-flow primitives the walker treats structurally rather than via
# the generic recurse-into-any-sub-jaxpr fallback.
_STRUCTURED = {"scan", "while", "cond"}


@dataclasses.dataclass(frozen=True)
class AuditFinding:
    """One audit defect. Codes: AUD001 branch-divergent collective
    schedule, AUD002 donated-but-unaliased buffer (see program.py)."""

    code: str
    message: str

    def to_json(self) -> dict:
        return {"code": self.code, "message": self.message}


@dataclasses.dataclass(frozen=True)
class CollectiveOp:
    """One collective eqn in program order.

    ``trips`` is the static execution count contributed by enclosing
    scans (scan lengths multiply); ``None`` means the op sits under a
    ``while_loop`` whose trip count is data-dependent, so its bytes
    cannot be statically accounted (callers surface that separately).
    """

    op: str
    axes: tuple[str, ...]
    shapes: tuple[tuple[int, ...], ...]
    dtypes: tuple[str, ...]
    operand_bytes: int
    trips: Optional[int] = 1

    @property
    def total_bytes(self) -> Optional[int]:
        if self.trips is None:
            return None
        return self.operand_bytes * self.trips

    def signature(self) -> tuple:
        """Identity used for cross-branch schedule comparison."""
        return (self.op, self.axes, self.shapes, self.dtypes, self.trips)

    def to_json(self) -> dict:
        return {
            "op": self.op,
            "axes": list(self.axes),
            "shapes": [list(s) for s in self.shapes],
            "dtypes": list(self.dtypes),
            "operand_bytes": self.operand_bytes,
            "trips": self.trips,
            "total_bytes": self.total_bytes,
        }


@dataclasses.dataclass
class ScheduleResult:
    """Walk output: ordered collectives + defects + the nondet census."""

    ops: list[CollectiveOp] = dataclasses.field(default_factory=list)
    findings: list[AuditFinding] = dataclasses.field(default_factory=list)
    # primitive name -> static occurrence count (trips folded in where
    # static, 1 otherwise).
    nondeterministic: dict[str, int] = dataclasses.field(default_factory=dict)

    @property
    def has_dynamic(self) -> bool:
        return any(o.trips is None for o in self.ops)


def _axes_of(params: dict) -> tuple[str, ...]:
    """Collective axis names from either param spelling (psum uses
    ``axes``, all_gather/ppermute use ``axis_name``); positional-axis
    ints are stringified so the schedule stays JSON-clean."""
    raw = params.get("axes", params.get("axis_name", ()))
    if not isinstance(raw, (tuple, list)):
        raw = (raw,)
    return tuple(a if isinstance(a, str) else str(a) for a in raw)


def _aval_bytes(aval: Any) -> int:
    size = 1
    for d in getattr(aval, "shape", ()):  # 0-d scalars -> itemsize
        size *= int(d)
    dtype = getattr(aval, "dtype", None)
    return size * int(getattr(dtype, "itemsize", 4))


def _mul(a: Optional[int], b: Optional[int]) -> Optional[int]:
    if a is None or b is None:
        return None
    return a * b


def _sub_jaxprs(value: Any) -> Iterable[Any]:
    """Duck-typed: yield every Jaxpr found in one eqn.params value
    (ClosedJaxpr wrappers unwrapped)."""
    items = value if isinstance(value, (tuple, list)) else [value]
    for item in items:
        inner = getattr(item, "jaxpr", item)
        if hasattr(inner, "eqns"):
            yield inner


def _record(eqn: Any, trips: Optional[int]) -> CollectiveOp:
    shapes, dtypes, nbytes = [], [], 0
    for v in eqn.invars:
        aval = getattr(v, "aval", None)
        if aval is None or not hasattr(aval, "shape"):
            continue
        shapes.append(tuple(int(d) for d in aval.shape))
        dtypes.append(str(aval.dtype))
        nbytes += _aval_bytes(aval)
    return CollectiveOp(
        op=COLLECTIVE_PRIMS[eqn.primitive.name],
        axes=_axes_of(eqn.params),
        shapes=tuple(shapes),
        dtypes=tuple(dtypes),
        operand_bytes=nbytes,
        trips=trips,
    )


def _walk(jaxpr: Any, trips: Optional[int], out: ScheduleResult) -> None:
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name in COLLECTIVE_PRIMS:
            out.ops.append(_record(eqn, trips))
            continue
        if name in NONDETERMINISTIC_PRIMS:
            out.nondeterministic[name] = (
                out.nondeterministic.get(name, 0) + (trips or 1)
            )
            # scatter carries no sub-jaxpr worth descending into for
            # collectives (its update computation is scalar).
            continue
        if name == "scan":
            inner_trips = _mul(trips, int(eqn.params.get("length", 1)))
            for sub in _sub_jaxprs(eqn.params.get("jaxpr")):
                _walk(sub, inner_trips, out)
        elif name == "while":
            # Data-dependent trip count: everything under it is
            # dynamically-counted communication.
            for key in ("cond_jaxpr", "body_jaxpr"):
                for sub in _sub_jaxprs(eqn.params.get(key)):
                    _walk(sub, None, out)
        elif name == "cond":
            _walk_cond(eqn, trips, out)
        else:
            # pjit / shard_map / remat / custom_* / closed_call ... —
            # anything carrying a sub-jaxpr executes it once per outer
            # trip.
            for value in eqn.params.values():
                for sub in _sub_jaxprs(value):
                    _walk(sub, trips, out)


def _walk_cond(eqn: Any, trips: Optional[int], out: ScheduleResult) -> None:
    """Extract each branch's schedule independently and require them to
    agree — the static gang-hang proof.  On agreement the schedule
    contributes one branch's ops (they are interchangeable); on
    divergence branch 0 is charged and AUD001 is raised with the
    per-branch signatures."""
    branch_results: list[ScheduleResult] = []
    for branch in eqn.params.get("branches", ()):
        sub = ScheduleResult()
        for j in _sub_jaxprs(branch):
            _walk(j, trips, sub)
        branch_results.append(sub)
    if not branch_results:
        return
    sigs = [tuple(o.signature() for o in r.ops) for r in branch_results]
    if any(s != sigs[0] for s in sigs[1:]):
        described = [
            [f"{o.op}@{','.join(o.axes) or '-'}x{o.trips}" for o in r.ops]
            for r in branch_results
        ]
        out.findings.append(AuditFinding(
            code="AUD001",
            message=(
                "collective schedule diverges across cond branches "
                f"(line of hang in SPMD execution): {described}"
            ),
        ))
    # Findings discovered inside branches (nested conds) propagate.
    for r in branch_results:
        out.findings.extend(r.findings)
        for k, v in r.nondeterministic.items():
            out.nondeterministic[k] = out.nondeterministic.get(k, 0) + v
    out.ops.extend(branch_results[0].ops)


def extract_schedule(closed_jaxpr: Any) -> ScheduleResult:
    """Walk a (Closed)Jaxpr; return the ordered collective schedule,
    branch-divergence findings, and the nondeterministic-op census."""
    jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
    result = ScheduleResult()
    _walk(jaxpr, 1, result)
    return result


def comm_bytes(ops: Iterable[CollectiveOp]) -> int:
    """Statically-accounted communication bytes (dynamic-trip ops are
    excluded; check ``ScheduleResult.has_dynamic``)."""
    return sum(o.total_bytes for o in ops if o.total_bytes is not None)


def schedule_digest(ops: Iterable[CollectiveOp]) -> str:
    """Stable contract fingerprint of the ordered schedule."""
    canon = json.dumps([o.to_json() for o in ops], sort_keys=True)
    return hashlib.sha256(canon.encode()).hexdigest()[:16]
