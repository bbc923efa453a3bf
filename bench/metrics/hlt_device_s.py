"""Device seconds per product in the programs of the HLT steps (Step 1 and
Step 2 launches and the batched hoist), matched by name from
``bench/programs.json``."""


def read(run):
    t = run.get("trace")
    if not t or t["hlt_s"] <= 0:
        return None
    return t["hlt_s"] / t["products"]
