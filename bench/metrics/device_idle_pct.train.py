"""Device: the share of the traced window in which no operation ran on the
device (1 - the union of the device's operation intervals)."""

from bench import trace as tr


def read(run):
    return tr.idle_pct(run)
