"""Program launches per product that start inside ``hemm.tail``."""

from bench.spans import stage


def read(run):
    return stage(run, "tail", "dispatches")
