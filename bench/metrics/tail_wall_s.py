"""Host seconds per product inside ``hemm.tail``: the l-fold Mult,
relinearise, Rescale and Add accumulation."""

from bench.spans import stage


def read(run):
    return stage(run, "tail", "wall_s")
