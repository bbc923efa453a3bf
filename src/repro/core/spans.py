"""Stable names of the program's host spans.

Each span is a ``jax.profiler.TraceAnnotation`` around an eager stage
boundary: it records nothing unless a profiler session is running, and the
profiler is its only sink. Profile readers (the benchmark's trace reduction)
match these names, so a name is changed here or nowhere.
"""
from __future__ import annotations

import jax

SPANS = {
    "hemm.compile": "compile_hemm: cost model, operand arena, verifier",
    "hemm.call": "HEMMProgram.__call__, whole",
    "hemm.step1": "Step 1: the sigma/tau CompiledHLT call(s)",
    "hemm.hoist": "Step-2 input hoist (hoist_batched, or one hoist per input)",
    "hemm.step2": "Step 2: the epsilon/omega CompiledHLT call(s)",
    "hemm.tail": "the l-fold Mult, relinearise, Rescale and Add accumulation",
    "hlt.hoist": "CompiledHLT: hoist (or pack) the inputs of one launch",
    "hlt.launch": "CompiledHLT: the jitted HLT pipeline call; metadata "
                  "slots, rotations, padding, key_bytes, diag_bytes "
                  "(HLTPlan.launch_stats)",
    "hlt.finish": "CompiledHLT: slice the launch's outputs into ciphertexts",
    "hlt.keytable": "HEContext.key_table: the rotation-key table to the "
                    "Montgomery domain, in place, once per keygen",
    "ckks.keygen": "CkksEngine.keygen: secret, relinearisation, rotation keys",
    "ckks.mult": "CkksEngine.mult: tensor product and relinearisation",
    "ckks.key_switch": "CkksEngine.key_switch: hybrid key switch",
    "ckks.rescale": "CkksEngine.rescale: divide by the last prime",
    "ckks.add": "CkksEngine.add",
}


def span(name: str, **metadata) -> jax.profiler.TraceAnnotation:
    """The host span ``name``, which must be one of :data:`SPANS`;
    ``metadata`` (whole numbers) becomes the event's stats in the
    profile."""
    if name not in SPANS:
        raise KeyError(f"unknown span {name!r}")
    return jax.profiler.TraceAnnotation(name, **metadata)
