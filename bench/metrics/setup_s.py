"""Set-up: from the process's start to the window's, weights, warm-up and
compilation included (host clock)."""


def read(run):
    return run.setup_s
