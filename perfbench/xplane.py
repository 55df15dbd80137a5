"""Reduction of a ``jax.profiler`` trace (``.xplane.pb``) with nothing but
``jax.profiler.ProfileData``: the device's operations with their self times,
the union of the time an operation ran, the collectives and the part of
them no compute hid, and the idle gaps named by what the host was doing.

A trace holds one plane per device (``/device:TPU:<n>``) whose ``XLA Ops``
line carries every operation with start and duration in nanoseconds, control
operations (``while``, ``conditional``, ``call``) spanning their bodies; and
the host plane, whose main-thread line carries the profiler's Python frames
and the ``TraceAnnotation`` spans on the same clock.
"""

from __future__ import annotations

import dataclasses
import glob
import os

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")


@dataclasses.dataclass
class Op:
    name: str
    start: float        # ns
    end: float
    self_ns: float = 0.0
    leaf: bool = True


@dataclasses.dataclass
class TraceView:
    devices: dict       # plane name -> [Op], sorted by start
    host: list          # [Op] of the host's main thread
    start: float
    end: float

    @property
    def window_s(self) -> float:
        return (self.end - self.start) / 1e9


def newest_xplane(profile_dir: str):
    found = glob.glob(os.path.join(profile_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


def short(name: str) -> str:
    """``fusion.69 f32[2560,200]`` from the HLO text the trace names an
    operation by: its name and the first shape of its result."""
    head, _, rest = name.partition(" = ")
    shape = rest.lstrip("(").split("{")[0].split(" ")[0] if rest else ""
    return (head.lstrip("%") + (" " + shape if shape else ""))[:120]


def _self_times(ops):
    """Mark parents and subtract their children's time (ops sorted by
    start; a child lies inside its parent)."""
    stack = []
    for op in ops:
        while stack and stack[-1].end <= op.start:
            stack.pop()
        op.self_ns = op.end - op.start
        if stack:
            stack[-1].leaf = False
            stack[-1].self_ns -= op.self_ns
        stack.append(op)
    return ops


def load(path: str, device_prefix: str = DEVICE_PREFIX) -> TraceView:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        lines = list(plane.lines)
        if plane.name.startswith(device_prefix):
            line = next((ln for ln in lines if ln.name == OPS_LINE), None)
            if line is None:
                continue
            ops = sorted((Op(short(e.name), e.start_ns,
                             e.start_ns + e.duration_ns) for e in line.events),
                         key=lambda o: (o.start, -o.end))
            devices[plane.name] = _self_times(ops)
        elif plane.name == "/host:CPU" and lines:
            # the main thread: the line that carries the harness's own
            # 'job' annotation, else the busiest
            def mine(ln):
                evs = list(ln.events)
                return (any(e.name == "job" for e in evs), len(evs))
            main = max(lines, key=mine)
            host = sorted((Op(e.name, e.start_ns, e.start_ns + e.duration_ns)
                           for e in main.events),
                          key=lambda o: (o.start, -o.end))
    spans = [o for ops in devices.values() for o in ops] + host
    start = min((o.start for o in spans), default=0.0)
    end = max((o.end for o in spans), default=0.0)
    # the profiler's own start and stop are not the program's time: the
    # window runs from the return of start_trace to the call of stop_trace
    starts = [o.end for o in host if o.name.endswith(" start_trace")]
    stops = [o.start for o in host if o.name.endswith(" stop_trace")]
    if starts and min(starts) < end:
        start = max(start, min(starts))
    if stops and max(stops) > start:
        end = min(end, max(stops))
    return TraceView(devices=devices, host=host, start=start, end=end)


def union(intervals):
    """Merged, sorted ``[(start, end)]`` of possibly overlapping intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def subtract(span, cover):
    """Length of ``span`` not covered by the merged intervals ``cover``."""
    s, e = span
    left = e - s
    for cs, ce in cover:
        if ce <= s or cs >= e:
            continue
        left -= min(e, ce) - max(s, cs)
    return max(left, 0.0)


def is_collective(name: str) -> bool:
    return name.lstrip("%").startswith(COLLECTIVES)


def _busy(view: TraceView, ops):
    """Merged intervals in which an operation ran, cut to the window."""
    return union((max(o.start, view.start), min(o.end, view.end))
                 for o in ops if o.end > view.start and o.start < view.end)


def busy_s(view: TraceView) -> float:
    """Seconds in which an operation ran, averaged over the devices."""
    if not view.devices:
        return 0.0
    per = [total(_busy(view, ops)) for ops in view.devices.values()]
    return sum(per) / len(per) / 1e9


def top_ops(view: TraceView, n: int = 10):
    """``[[name, seconds]]`` by summed self time, averaged over devices."""
    acc = {}
    for ops in view.devices.values():
        for o in ops:
            acc[o.name] = acc.get(o.name, 0.0) + o.self_ns
    k = max(len(view.devices), 1)
    ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / k / 1e9] for name, ns in ranked]


def collective_spans(ops, prefix: str = "all-reduce"):
    """Intervals of one kind of collective on one device: a synchronous op
    is its own interval; an asynchronous ``-start`` is paired with the next
    ``-done`` and the interval runs from the one's start to the other's end."""
    out, pending = [], []
    for o in ops:
        name = o.name.lstrip("%")
        if not name.startswith(prefix):
            continue
        head = name.split(".")[0].split(" ")[0]
        if head.endswith("-start"):
            pending.append(o)
        elif head.endswith("-done") and pending:
            out.append((pending.pop(0).start, o.end))
        else:
            out.append((o.start, o.end))
    return out


def collectives(view: TraceView, prefix: str = "all-reduce"):
    """``(seconds, exposed seconds)`` of a collective, averaged over the
    devices: its intervals, and the part of them under which no compute
    operation ran on that device."""
    if not view.devices:
        return 0.0, 0.0
    tot = exposed = 0.0
    for ops in view.devices.values():
        compute = union((o.start, o.end) for o in ops
                        if o.leaf and not is_collective(o.name))
        for span in union(collective_spans(ops, prefix)):
            tot += span[1] - span[0]
            exposed += subtract(span, compute)
    k = len(view.devices)
    return tot / k / 1e9, exposed / k / 1e9


def idle_gaps(view: TraceView, n: int = 10, floor_ns: float = 2000.0):
    """``[[what the host was doing, seconds]]`` for the idle time of the
    first device, summed by host frame. Each gap between operations goes to
    the deepest frame of the host's main thread that is open at the gap's
    middle and covers at least half of it; gaps under ``floor_ns`` are the
    device's own turn-around and are summed as ``(between ops)``."""
    if not view.devices:
        return []
    busy = _busy(view, next(iter(view.devices.values())))
    edges = [view.start] + [t for s, e in busy for t in (s, e)] + [view.end]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    acc, stack, nxt = {}, [], 0
    for gs, ge in gaps:
        if ge - gs < floor_ns:
            acc["(between ops)"] = acc.get("(between ops)", 0.0) + ge - gs
            continue
        mid = 0.5 * (gs + ge)
        while nxt < len(view.host) and view.host[nxt].start <= mid:
            stack.append(view.host[nxt])
            nxt += 1
        stack = [h for h in stack if h.end > mid]
        name = "(no host span)"
        for h in reversed(stack):
            if min(ge, h.end) - max(gs, h.start) >= 0.5 * (ge - gs):
                name = h.name
                break
        acc[name] = acc.get(name, 0.0) + ge - gs
    ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in ranked]
