"""Published peaks of each chip, keyed by JAX's ``device_kind``."""
from __future__ import annotations

import json

from bench.cells import HERE


def lookup(kind: str, path=HERE / "peaks.json") -> dict:
    """The peak entry for ``kind``; a kind the table lacks is an error, never
    a default."""
    table = json.loads(path.read_text())
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in {path.name} "
                       f"(known: {', '.join(sorted(table))})")
    return table[kind]
