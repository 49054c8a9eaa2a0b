"""95th percentile of every gap between consecutive tokens of every request
due in the window (host clock)."""

from bench.stats import percentile


def read(run):
    v = [(b - a) * 1e3 for c in run.requests for a, b in zip(c.token_times, c.token_times[1:])]
    return percentile(v, 95) if v else None
