"""Device seconds per product of the launches that start inside the HLT
stage spans, paired launch by launch with the programs the chip ran: the
eager glue between the HLT launches counts, whatever its program's name."""

from bench.spans import stage


def read(run):
    return stage(run, "hlt", "device_s")
