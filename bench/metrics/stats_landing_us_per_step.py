"""Per-stream stats: the host time the engine spends landing per-stream
records, lanes and exit reports (the ``engine.stats`` spans, finishes
inside them) in the window, over the window's ``engine.step`` spans."""

from bench import spans


def read(run):
    landing, steps = spans.named(run, "engine.stats"), spans.named(run, "engine.step")
    if landing is None or steps is None:
        return None
    return sum(s.end_ns - s.start_ns for s in landing) / 1e3 / len(steps)
