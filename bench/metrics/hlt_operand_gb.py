"""GB of rotation-key table and diagonal operands that the HLT launches of
one product read from, from the program's ``hlt.launch`` counters
(``bench/launches.py``)."""

from bench.launches import totals


def read(run):
    c = totals(run)
    if not c:
        return None
    return (c["key_bytes"] + c["diag_bytes"]) / 1e9
