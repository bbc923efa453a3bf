"""bench/peaks.json: keyed by device kind, with a source; unknown kinds are
refused. The modmul peak kernel computes the chain it times."""
import json

import pytest

from bench import peak, peaks
from bench.cells import HERE


def test_known_kind_has_its_source_and_bandwidth():
    entry = peaks.lookup("TPU v5 lite")
    assert entry["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in entry["source"]


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v5e", ""])
def test_unknown_kind_is_refused(kind):
    with pytest.raises(KeyError, match="not in peaks.json"):
        peaks.lookup(kind)


def test_every_entry_names_a_source():
    table = json.loads((HERE / "peaks.json").read_text())
    for kind, entry in table.items():
        assert entry["source"], kind
        assert entry["hbm_bytes_per_s"] > 0, kind


def test_peak_kernel_checks_its_chain_in_interpret_mode():
    got = peak.measure(blocks=1, iters=2, unroll=3, min_batch_s=0.0,
                       batches=1, interpret=True)
    assert got["checked"] and got["modmul_per_s"] > 0


def test_montmul_copy_matches_python_integers():
    import jax
    import jax.numpy as jnp
    import numpy as np
    rng = np.random.default_rng(3)
    a = rng.integers(0, peak.Q, 256, dtype=np.uint64).astype(np.uint32)
    b = rng.integers(0, peak.Q, 256, dtype=np.uint64).astype(np.uint32)
    with jax.enable_x64(False):
        got = np.asarray(peak.montmul(jnp.asarray(a), jnp.asarray(b),
                                      jnp.uint32(peak.Q),
                                      jnp.uint32(peak.QNEG)))
    rinv = pow(1 << 32, -1, peak.Q)
    want = [int(x) * int(y) * rinv % peak.Q for x, y in zip(a, b)]
    assert [int(v) for v in got] == want
