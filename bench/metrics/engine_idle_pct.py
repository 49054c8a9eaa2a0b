"""Scheduler: the share of the traced window in which the first device ran
no operation while the host was inside ``Engine.step`` (the gaps of its
operations, intersected with the ``engine.step`` spans mapped onto the
trace's clock by the engine steps both clocks saw)."""

from bench import spans
from bench.stats import gaps


def read(run):
    if run.trace is None or not run.trace.devices or run.trace_window is None:
        return None
    offset, steps = spans.clock_offset(run), spans.named(run, "engine.step")
    if offset is None or steps is None:
        return None
    lo, hi = run.trace_window
    ops = next(iter(run.trace.devices.values()))
    idle = gaps(((s, e) for _, s, e in ops), lo, hi)
    inside = spans.overlap(idle, [(s.start_ns + offset, s.end_ns + offset) for s in steps])
    return 100.0 * inside / (hi - lo)
