"""Tokens of every training step taken in the window, over the window, which
ends with the step that crosses the requested length."""


def read(run):
    if not run.steps:
        return None
    return sum(s.tokens for s in run.steps) / run.window_s
