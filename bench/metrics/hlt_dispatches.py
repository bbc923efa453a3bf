"""Program launches per product that start inside the HLT stage spans."""

from bench.spans import stage


def read(run):
    return stage(run, "hlt", "dispatches")
