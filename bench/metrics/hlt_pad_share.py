"""Padding slots as a share (%) of all rotation slots the HLT launches of
one product run, from the program's ``hlt.launch`` counters
(``bench/launches.py``): the work a kernel spends on slots that hold no
diagonal."""

from bench.launches import totals


def read(run):
    c = totals(run)
    if not c or not c["slots"]:
        return None
    return 100.0 * c["padding"] / c["slots"]
