"""Host seconds of ``compile_hemm``: cost model, operand arena, verifier."""


def read(run):
    return run.get("phases", {}).get("compile_s")
