"""Device seconds per product in every program not matched to the HLT steps:
the CKKS tail (Mult, relinearisation, Rescale, Add) and the eager glue."""


def read(run):
    t = run.get("trace")
    if not t or t["tail_s"] <= 0:
        return None
    return t["tail_s"] / t["products"]
