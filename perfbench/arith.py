"""The arithmetic between the benchmark's clock and its numbers."""

from __future__ import annotations

import io
import re
import statistics
import time


class RoundStamps(io.TextIOBase):
    """Standard output of a job, as the benchmark holds it: every line that
    reports a round (the traffic file's ``round_marker``, whose one group is
    the round's number) is stamped with the harness's clock as it is
    written, and nothing is kept of the text. The program prints that line
    once the round's metrics are on the host, so the time between two of
    them is one turn of its loop: device work, fetch, history, stop check,
    dispatch of the next."""

    def __init__(self, marker: str):
        super().__init__()
        self.marker = re.compile(marker)
        self.stamps = {}                # round (from 1) -> perf_counter

    def writable(self) -> bool:
        return True

    def write(self, text: str) -> int:
        found = self.marker.search(text)
        if found:
            self.stamps.setdefault(int(found.group(1)), time.perf_counter())
        return len(text)


def round_intervals(stamps: dict, rounds: int, width: int) -> list:
    """Seconds a round, one value per chunk after the job's first: a chunk
    of ``width`` rounds is reported in one burst when its metrics arrive,
    so the time from the last line of one burst to the last line of the
    next, over ``width``, is that chunk's round. The first chunk has no
    burst before it (and holds the program's load and first execution):
    what a job pays once is in none of the values. Raises where a round of
    the job was not reported."""
    missing = [r for r in range(1, rounds + 1) if r not in stamps]
    if missing or rounds % width:
        raise ValueError(f"rounds not reported on standard output: "
                         f"{missing[:5]} of {rounds} at width {width}")
    ends = [stamps[r] for r in range(width, rounds + 1, width)]
    return [(b - a) / width for a, b in zip(ends, ends[1:])]


def round_ms(intervals: list) -> float:
    """One job's median round, in ms: a stall or the job's fixed cost moves
    no median."""
    return 1000.0 * statistics.median(intervals)


def window_round_ms(job_values: list) -> float:
    """The window's ``round_ms``: the median over its jobs of each job's
    median round (of two jobs, their mean). Not one median over all rounds:
    jobs of one run differ in level by up to 1% on a busy host (PERF.md,
    PR 22), and the median of two clusters of equal size falls anywhere
    between them."""
    return statistics.median(job_values)


def job_rounds(stated: int, seconds: float, run_seconds: float,
               width: int) -> int:
    """Rounds of one job of the window: what the cell's file states for a
    window of ``run_seconds``, in proportion for another ``--seconds``, in
    whole chunks and never under two (one interval). The work is fixed by
    the files, not sized from a rate measured in the run, so two runs time
    the same jobs."""
    chunks = round(stated * seconds / run_seconds / width)
    return max(2, chunks) * width


def quartile_spread(values):
    """Distance between the quartiles over the median (the driver's
    spread), for the sets of runs PERF.md reports."""
    vs = sorted(values)
    if len(vs) < 2:
        return 0.0
    q = statistics.quantiles(vs, n=4, method="inclusive")
    med = statistics.median(vs)
    return (q[2] - q[0]) / med if med else 0.0
