"""The device's idle time, put down to the phase of the program's round loop
that was open while it idled.

The loop opens a ``jax.profiler.TraceAnnotation`` ``fedtpu.<phase>`` around
each phase of a round while its profiler window is open (fedtpu
``orchestration/loop.py``, ``phase``), so the host's main-thread line carries
them on the clock of the device's operations. The first device's idle
intervals (the window less the union of its operations) are intersected with
the annotations of each phase; what lies under none of them is
``idle_unspanned_ms``. Per traced round, in milliseconds. A program that
opens no such annotation gives nothing.

The window's first chunk is left out, as ``skip_first`` leaves the first
span out of the span metrics: the profiler's Python tracer starts up inside
the first dispatch after ``start_trace`` (19-23 ms on the v5e's host, PR 23),
which is the profiler's time and not the loop's. What is read runs from the
end of the window's first ``fedtpu.chunk_fetch`` to the window's end, and
holds every phase once for each chunk that is left; the rounds are the
window's, less that chunk's share. A window of one chunk is read whole.
"""

from perfbench import xplane

FETCH = "fedtpu.chunk_fetch"
PHASES = {"idle_dispatch_ms": ("fedtpu.dispatch",),
          "idle_fetch_ms": (FETCH,),
          "idle_check_ms": ("fedtpu.stop_check", "fedtpu.state_check")}


def reduce(ev):
    view, rounds = ev.trace, ev.facts.get("trace_rounds")
    if not view.devices or not rounds:
        return {}
    named = {n for names in PHASES.values() for n in names}
    spans = {n: [] for n in named}
    for h in view.host:
        if h.name in named and h.end > view.start and h.start < view.end:
            spans[h.name].append((h.start, h.end))
    if not any(spans.values()):
        return {}
    start, end = view.start, view.end
    fetches = sorted(e for _, e in spans[FETCH] if e <= end)
    if len(fetches) > 1:
        start = fetches[0]
        rounds = rounds * (len(fetches) - 1) / len(fetches)

    def clipped(some):
        return xplane.union((max(s, start), min(e, end))
                            for s, e in some if e > start and s < end)

    ops = next(iter(view.devices.values()))
    busy = clipped((o.start, o.end) for o in ops)

    def idle_under(cover):
        return sum(xplane.subtract(span, busy) for span in cover)

    out, per_ms = {}, 1e-6 / rounds
    for field, names in PHASES.items():
        out[field] = per_ms * idle_under(
            clipped(s for n in names for s in spans[n]))
    idle = xplane.subtract((start, end), busy)
    spanned = idle_under(clipped(s for one in spans.values() for s in one))
    out["idle_unspanned_ms"] = per_ms * (idle - spanned)
    out["idle_ms"] = per_ms * idle
    return out
