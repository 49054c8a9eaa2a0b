"""Model step, decode: the median ``engine.decode`` span of the window, the
host's time from the decode's dispatch to holding its tokens."""

import statistics

from bench import spans


def read(run):
    decode = spans.named(run, "engine.decode")
    return statistics.median(s.seconds for s in decode) * 1e3 if decode else None
