"""Span/event tracer with a versioned JSONL sink.

Event schema (``EVENT_SCHEMA_VERSION = 2``) — one JSON object per line:

    v              int    schema version
    run_id         str    one uuid4 hex per tracer (joins every event of a run)
    kind           str    'manifest' | 'span' | 'round' | 'counters' | 'log' |
                          'trace' | ...
    phase          str?   span phase label ('build', 'compile', 'chunk',
                          'eval', 'checkpoint', 'stop_check', 'personalize',
                          'launch', ...); for kind 'trace' the causal stage
                          ('client_stamp', 'wal', 'admit', 'buffer_insert',
                          'dedup_drop', 'incorporate')
    round          int?   1-based round (tick) the event belongs to, when any
    t_start        float  seconds since the tracer's epoch (time.monotonic-
                          based, so deltas are immune to wall-clock steps)
    dur_s          float  span duration; 0.0 for instantaneous events
    process_index  int    fleet process identity (v2): FEDTPU_PROCESS_ID or 0
    pid            int    OS pid of the emitting process (v2)
    launch_id      str?   gang launch id (FEDTPU_LAUNCH_ID) when one (v2)
    role           str    emitting role (v2): 'run', 'serve', 'gateway-<i>',
                          'proxy-<i>', 'supervisor', ...
    payload        dict   kind-specific data (metric values, counters...)

v1 files (no identity fields) stay readable: every consumer reads the
identity with defaults (``process_index=0``, ``role='run'``), so old
sinks parse unchanged and merged multi-process reports key sections on
``(run_id, role, process_index)`` instead of the colliding ``run_id``
alone.

Timing rule (fedtpu.utils.timing): dispatch is asynchronous, so a device
span must close on a HOST VALUE FETCH (``force_fetch`` / ``np.asarray``
materialization), never on dispatch. ``Span.end_after_fetch`` packages
that rule; the round loop closes its chunk spans on the batched metrics
materialization, which is the same proof.

Crash flight recorder: every Tracer keeps a bounded in-memory ring of
its most recent event lines (``FlightRecorder``). The supervisor's
0/3/75 exit paths and the serving crash barrier (``_safe_handle``)
flush it to ``events.crash.<role>.jsonl`` next to the events sink, so a
chaos-row failure always ships a post-mortem timeline even when the
main sink is on a dead disk or got truncated mid-crash.

Writes flush per event: a crashed run's sink still holds everything
emitted before the crash (the tracer exists precisely to diagnose such
runs), so ``close()`` is a nicety, not a durability requirement.

No jax import at module scope — the reader side (fedtpu.telemetry.report)
and the tests' synthetic emitters must work backend-free.
"""

from __future__ import annotations

import collections
import json
import os
import time
import uuid
from typing import Optional

EVENT_SCHEMA_VERSION = 2

# Ring capacity of the per-process crash flight recorder: enough for the
# serving fleet's last few ticks of context without holding a long run's
# whole history in memory.
FLIGHT_RECORDER_CAPACITY = 256


def process_identity(role: Optional[str] = None,
                     process_index: Optional[int] = None) -> dict:
    """The v2 identity stamp for this process. ``process_index`` falls
    back to the gang supervisor's FEDTPU_PROCESS_ID contract
    (fedtpu.resilience.distributed), ``launch_id`` to FEDTPU_LAUNCH_ID —
    both absent on a plain single-process run, which stamps as the
    canonical (0, 'run')."""
    if process_index is None:
        try:
            process_index = int(os.environ.get("FEDTPU_PROCESS_ID", "0") or 0)
        except ValueError:
            process_index = 0
    return {"process_index": int(process_index), "pid": os.getpid(),
            "launch_id": os.environ.get("FEDTPU_LAUNCH_ID"),
            "role": role or "run"}


def crash_artifact_path(events_path: Optional[str], role: str) -> str:
    """Path of the flight-recorder flush target for ``role``:
    ``events.crash.<role>.jsonl`` in the events sink's directory (the
    cwd when the tracer has no sink)."""
    base = os.path.dirname(events_path) if events_path else "."
    return os.path.join(base or ".", f"events.crash.{role}.jsonl")


class FlightRecorder:
    """Bounded ring of the most recent serialized event lines.

    Append-only and O(1) per event (collections.deque with maxlen); the
    whole point is that recording must be cheap enough to run on EVERY
    event of a healthy process that will probably never crash."""

    def __init__(self, capacity: int = FLIGHT_RECORDER_CAPACITY):
        self.capacity = int(capacity)
        self._ring: collections.deque = collections.deque(
            maxlen=self.capacity)

    def record(self, line: str) -> None:
        self._ring.append(line)

    def __len__(self) -> int:
        return len(self._ring)

    def lines(self) -> list:
        return list(self._ring)

    def flush(self, path: str) -> int:
        """Write the ring to ``path`` (overwrite: the LAST crash of a
        process is the one worth keeping) and return the line count.
        Never raises — the flight recorder runs inside crash paths where
        a secondary I/O error must not mask the primary failure."""
        lines = self.lines()
        if not lines:
            return 0
        try:
            tmp = f"{path}.tmp.{os.getpid()}"
            with open(tmp, "w") as fh:
                for line in lines:
                    fh.write(line + "\n")
            os.replace(tmp, path)
        except OSError:
            return 0
        return len(lines)


class Span:
    """One open phase window; created by ``Tracer.span``. Usable as a
    context manager (closes on ``__exit__``) or manually via ``end`` /
    ``end_after_fetch``."""

    def __init__(self, tracer: "Tracer", phase: str,
                 round: Optional[int] = None, **payload):
        self._tracer = tracer
        self.phase = phase
        self.round = round
        self.payload = dict(payload)
        self._t0 = time.monotonic()
        self._closed = False

    def end(self, **extra) -> float:
        """Close the span (idempotent) and emit it; returns the duration."""
        dur = time.monotonic() - self._t0
        if not self._closed:
            self._closed = True
            self._tracer.event("span", phase=self.phase, round=self.round,
                               dur_s=dur, **{**self.payload, **extra})
        return dur

    def end_after_fetch(self, tree, **extra) -> float:
        """Close the span on a host value fetch of ``tree`` — the
        fetch-forced-completion rule (module docstring). The fetch is the
        proof the device work inside the span actually finished."""
        from fedtpu.utils.timing import force_fetch
        force_fetch(tree)
        return self.end(**extra)

    def __enter__(self) -> "Span":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.end(**({"error": repr(exc)} if exc is not None else {}))


class Phase:
    """One phase of a host loop on every clock that is on, as one context
    manager: the sink's span, a profiler annotation that puts the same
    window on the device trace's timeline (a ``jax.profiler.
    TraceAnnotation``, made by the caller: this module loads without a
    backend), and a watchdog guard. Entered guard first and span last, so
    the span is the innermost window; yields the span
    (``end_after_fetch`` closes it early, on the fetch)."""

    __slots__ = ("_span", "_annotation", "_guard")

    def __init__(self, span, annotation=None, guard=None):
        self._span = span
        self._annotation = annotation
        self._guard = guard

    def __enter__(self):
        if self._guard is not None:
            self._guard.__enter__()
        if self._annotation is not None:
            self._annotation.__enter__()
        return self._span.__enter__()

    def __exit__(self, exc_type, exc, tb) -> None:
        self._span.__exit__(exc_type, exc, tb)
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc, tb)
        if self._guard is not None:
            self._guard.__exit__(exc_type, exc, tb)


class Tracer:
    """Appends schema-v2 events to a JSONL sink. One per run; all
    timestamps are seconds since this tracer's construction (monotonic).
    Every emitted line also lands in the in-memory flight recorder."""

    def __init__(self, path: str, run_id: Optional[str] = None,
                 role: Optional[str] = None,
                 process_index: Optional[int] = None):
        self.path = path
        self.run_id = run_id or uuid.uuid4().hex
        self.identity = process_identity(role, process_index)
        self.role = self.identity["role"]
        self.flight = FlightRecorder()
        self._epoch = time.monotonic()
        self._f = open(path, "a")

    @property
    def enabled(self) -> bool:
        return True

    def _now(self) -> float:
        return time.monotonic() - self._epoch

    def event(self, kind: str, phase: Optional[str] = None,
              round: Optional[int] = None, dur_s: float = 0.0,
              t_start: Optional[float] = None, **payload) -> None:
        """Emit one event. ``t_start`` defaults to now minus ``dur_s`` so a
        caller that timed a window itself (the round loop's chunk lap) gets
        an honest window start without threading timestamps around."""
        if self._f.closed:
            return
        rec = {"v": EVENT_SCHEMA_VERSION, "run_id": self.run_id,
               "kind": kind, "phase": phase, "round": round,
               "t_start": (self._now() - dur_s if t_start is None
                           else t_start),
               "dur_s": dur_s, **self.identity, "payload": payload}
        line = json.dumps(rec, default=_json_default)
        self.flight.record(line)
        self._f.write(line + "\n")
        self._f.flush()

    def span(self, phase: str, round: Optional[int] = None,
             **payload) -> Span:
        return Span(self, phase, round=round, **payload)

    def counters(self, snapshot: dict) -> None:
        """Emit a full registry snapshot (kind 'counters'). The report's
        counter totals come from the LAST such event in the log."""
        self.event("counters", **snapshot)

    def flush_crash(self, reason: str = "") -> Optional[str]:
        """Flush the flight recorder to ``events.crash.<role>.jsonl``
        next to the sink; returns the artifact path (None when the ring
        was empty). Called from crash barriers — never raises."""
        path = crash_artifact_path(self.path, self.role)
        if reason:
            self.flight.record(json.dumps(
                {"v": EVENT_SCHEMA_VERSION, "run_id": self.run_id,
                 "kind": "crash_flush", "phase": None, "round": None,
                 "t_start": self._now(), "dur_s": 0.0, **self.identity,
                 "payload": {"reason": reason}}, default=_json_default))
        return path if self.flight.flush(path) else None

    def close(self) -> None:
        if not self._f.closed:
            self._f.flush()
            self._f.close()


class _NullSpan:
    phase = None
    round = None
    payload: dict = {}

    def end(self, **extra) -> float:
        return 0.0

    def end_after_fetch(self, tree, **extra) -> float:
        return 0.0

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        pass


class NullTracer:
    """Telemetry-off tracer: same surface as ``Tracer``, every call a
    no-op. The round loop is written against this API unconditionally, so
    the disabled path costs a method call per event, not a branch per
    call site."""

    path = None
    run_id = None
    role = "run"

    def __init__(self):
        self.identity = process_identity()
        self.flight = FlightRecorder(capacity=1)

    @property
    def enabled(self) -> bool:
        return False

    def event(self, kind, phase=None, round=None, dur_s=0.0, t_start=None,
              **payload) -> None:
        pass

    def span(self, phase, round=None, **payload) -> _NullSpan:
        return _NullSpan()

    def counters(self, snapshot) -> None:
        pass

    def flush_crash(self, reason: str = "") -> Optional[str]:
        return None

    def close(self) -> None:
        pass


def _json_default(obj):
    """Sink-side coercion for numpy scalars/arrays and other non-JSON
    payload leaves — the tracer must never crash the run it observes."""
    for attr in ("item",):
        if hasattr(obj, attr) and getattr(obj, "ndim", None) == 0:
            return obj.item()
    if hasattr(obj, "tolist"):
        return obj.tolist()
    return repr(obj)


def make_tracer(path: Optional[str], run_id: Optional[str] = None,
                role: Optional[str] = None,
                process_index: Optional[int] = None):
    """The one constructor call sites use: a real ``Tracer`` when ``path``
    is set, a ``NullTracer`` otherwise. ``role`` scopes the v2 identity
    stamp ('run' default; the gateway fleet passes 'gateway-<i>', the
    supervisor 'supervisor') so merged fleet timelines can key sections
    on something better than a colliding run_id."""
    return (Tracer(path, run_id=run_id, role=role,
                   process_index=process_index)
            if path else NullTracer())
