"""Share of the HLT steps' device time that the least time for their work
would take: the larger of the modular multiplications over the measured
peak and the bytes over the HBM bandwidth (``bench/work.py``)."""

from bench import work


def read(run):
    t = run.get("trace")
    if not t or t["hlt_s"] <= 0 or not run.get("work"):
        return None
    least, _ = work.least_time_s(run["work"], run["modmul_per_s"],
                                 run["hbm_bytes_per_s"])
    return 100.0 * least / (t["hlt_s"] / t["products"])
