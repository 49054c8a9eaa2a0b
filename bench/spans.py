"""The program's own spans, for the metric readers.

The program records its spans in one bounded ring in this process
(``repro.core.instrument.SPANS``), in nanoseconds of the clock that
``time.perf_counter`` reads.  The reduced profiler trace keeps only the
benchmark's spans (``bench.trace``), so a reader takes the program's from
the ring, and maps them onto the device trace's clock by the engine steps
that both clocks saw: ``run.steps`` on the host clock, the traced
``engine_step.*`` spans, in the same order, on the trace's.  Where the
program keeps no such ring, every function here returns None.
"""

from __future__ import annotations

import statistics
from typing import List, Optional, Sequence, Tuple


def window_spans(run) -> Optional[List]:
    """The program spans that start in the run's window, in order of start;
    None where the program keeps none or its ring dropped spans that may
    reach into the window."""
    try:
        from repro.core.instrument import SPANS
    except ImportError:
        return None
    got = SPANS.read(round(run.t0 * 1e9), round(run.t_end * 1e9))
    return None if got.dropped else got.spans


def named(run, name: str) -> Optional[List]:
    """The window's spans called ``name``, or None where there are none."""
    spans = window_spans(run)
    if spans is None:
        return None
    return [s for s in spans if s.name == name] or None


def step_offsets(run) -> Optional[List[float]]:
    """Per engine step, its traced ``engine_step.*`` span's start minus its
    start on the host clock, in ns; None without a trace or where the two
    do not hold the same number of steps."""
    if run.trace is None or not run.steps:
        return None
    traced = [s for n, s, _ in run.trace.spans if n.startswith("engine_step")]
    if len(traced) != len(run.steps):
        return None
    return [t - s.start * 1e9 for t, s in zip(traced, run.steps)]


def clock_offset(run) -> Optional[float]:
    """The trace's clock minus the host's, in ns: the median of the steps'."""
    d = step_offsets(run)
    return statistics.median(d) if d else None


def overlap(a: Sequence[Tuple[float, float]], b: Sequence[Tuple[float, float]]) -> float:
    """Length of the intersection of two lists of disjoint intervals, each
    in order of start."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total
