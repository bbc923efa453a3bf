"""Device seconds per product of the launches that start inside
``hemm.tail``, paired launch by launch with the programs the chip ran."""

from bench.spans import stage


def read(run):
    return stage(run, "tail", "device_s")
