"""Output tokens seen inside the window, over the window's length."""


def read(run):
    if not run.requests:
        return None
    n = sum(1 for c in run.requests for t in c.token_times if t <= run.t_end)
    return n / run.window_s
