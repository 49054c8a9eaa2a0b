"""Scheduler: 95th percentile, over the window's requests, of the program's
own queue wait, from ``Engine.submit`` to the start of the request's
prefill (the ``engine.queued`` span, matched by stream id)."""

from bench import spans
from bench.stats import percentile


def read(run):
    queued = spans.named(run, "engine.queued")
    if queued is None:
        return None
    ids = {c.req.stream_id for c in run.requests if c.req is not None}
    v = [s.seconds * 1e3 for s in queued if s.stream in ids]
    return percentile(v, 95) if v else None
