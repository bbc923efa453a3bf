"""Host seconds per product inside the HLT stage spans (``hemm.step1``,
``hemm.hoist``, ``hemm.step2``), summed (``bench/spans.py``)."""

from bench.spans import stage


def read(run):
    return stage(run, "hlt", "wall_s")
