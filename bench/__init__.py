"""Chip benchmark of the HE matrix multiplication (``python3 bench/run.py``).

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own, found by the name ``BENCHMARK.json`` gives it:
``configs/<config>.json``, ``traffic/<traffic>.json`` and
``metrics/<metric>.py``. The yardstick (trace reduction, work count, peak
table, reference and comparison) lives here too, apart from the program.
"""
