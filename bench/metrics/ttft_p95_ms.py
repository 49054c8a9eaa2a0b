"""95th percentile, over every request due in the window, of the time from
when it was due to when its first token was seen (host clock)."""

from bench.stats import percentile


def read(run):
    v = [(c.token_times[0] - c.due) * 1e3 for c in run.requests if c.token_times]
    return percentile(v, 95) if v else None
