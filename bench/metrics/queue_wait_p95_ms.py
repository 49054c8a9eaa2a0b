"""Scheduler: 95th percentile of the time from when a request was due to
the start of the engine step that admitted it (benchmark spans, host
clock)."""

import math

from bench.stats import percentile


def read(run):
    v = [(c.admit_step_start - c.due) * 1e3 for c in run.requests
         if not math.isnan(c.admit_step_start)]
    return percentile(v, 95) if v else None
