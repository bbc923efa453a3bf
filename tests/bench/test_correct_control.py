"""The comparison that decides ``correct``, driven through a whole run at a
toy size on the CPU: the program passes; the bfloat16 control and each
fault the cell can have (a product that returns its input unchanged, half
of the sum over k left out and the rest doubled, an answer altered where it
is produced) come out not correct.

The toy limit is set like the cells' limits: sound runs read 5e-7..2.3e-6
over six seeds and the control 1.4e-3..2.2e-3, so 2e-5 sits about ten times
above the one and seventy times below the other.
"""
import numpy as np
import pytest

from bench import cells, control, run

CONFIG = {"name": "toy", "shape": [4, 4, 4], "limits": {"rms_err": 2e-5},
          "params": {"name": "toy", "logN": 6, "L": 4, "k": 3, "beta": 2,
                     "scale_bits": 26, "q0_bits": 29, "sp_bits": 30}}
DEVICE = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}
SEED = 2**33 + 17


@pytest.fixture(scope="module")
def harness(tmp_path_factory):
    import repro  # noqa: F401
    from bench import plancache
    mp = pytest.MonkeyPatch()
    mp.setattr(plancache, "CACHE", tmp_path_factory.mktemp("plans"))
    mp.setattr(run, "TRACE_DIR", tmp_path_factory.mktemp("trace"))
    bench = cells.load_benchmark()
    entry = cells.find_cell(bench, "set-a-type-iv.seq")
    traffic = cells.load_traffic("seq")

    def go(trace=False, seed=SEED):
        return run.run_cell(bench, entry, CONFIG, traffic, seed, 0.2, trace,
                            DEVICE)
    yield go, traffic
    mp.undo()


def test_program_is_correct(harness):
    go, _ = harness
    r = go()
    assert r["correct"] and r["failed"] == 0 and r["attempted"] >= 1
    assert r["checks"]["rms_err"]["value"] < CONFIG["limits"]["rms_err"]
    assert list(r)[-1] == "checks"
    assert set(r["metrics"]) == {"hemm_s", "hbm_peak_gb", "setup_s"}


def test_control_is_not_correct(harness):
    _, traffic = harness
    for seed in (SEED, 5, 2**31 + 1):
        got = control.reading(CONFIG, traffic, seed, products=2)
        assert got["failed"] == 2
        assert got["checks"]["rms_err"]["value"] > \
            10 * CONFIG["limits"]["rms_err"]


def _input_unchanged(original):
    def call(self, ctA, ctB):
        original(self, ctA, ctB)
        return ctA
    return call


def _half_sum_doubled(self, ctA, ctB):
    eng, keys, p = self.ctx.eng, self.ctx.keys, self.mm_plan
    outs = self._step2(self._step2_items(ctA, ctB))
    acc = None
    for k in range(p.l // 2):
        prod = eng.rescale(eng.mult(outs[k], outs[p.l + k], keys))
        acc = prod if acc is None else eng.add(acc, prod)
    return eng.add(acc, acc)


def _altered_answer(original):
    def call(self, ctA, ctB):
        out = original(self, ctA, ctB)
        q0 = self.ctx.eng.ctx.moduli_host[0]
        c0 = np.asarray(out.c0).copy()
        c0[0, 0] = (int(c0[0, 0]) + 1) % q0
        return type(out)(c0=c0, c1=out.c1, level=out.level, scale=out.scale)
    return call


@pytest.mark.parametrize("fault", ["input_unchanged", "half_sum_doubled",
                                   "answer_altered"])
def test_fault_is_not_correct(harness, fault):
    from repro.core.compile import HEMMProgram
    go, _ = harness
    broken = {"input_unchanged": _input_unchanged(HEMMProgram.__call__),
              "half_sum_doubled": _half_sum_doubled,
              "answer_altered": _altered_answer(HEMMProgram.__call__)}[fault]
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(HEMMProgram, "__call__", broken)
        r = go()
    assert not r["correct"] and r["failed"] == r["attempted"] >= 1
    assert r["checks"]["rms_err"]["value"] > CONFIG["limits"]["rms_err"]


def test_traced_run_reports_per_layer_metrics(harness, monkeypatch):
    from bench import peak
    original = peak.measure
    monkeypatch.setattr(peak, "measure", lambda: original(
        blocks=1, iters=2, unroll=2, min_batch_s=0.0, batches=1,
        interpret=True))
    go, _ = harness
    r = go(trace=True)
    assert r["correct"] and r["attempted"] == 1
    # a CPU trace holds no device plane: no HLT time, so no roofline share
    assert {"keygen_s", "compile_s", "idle_share"} <= set(r["metrics"])
    assert "hlt_roofline" not in r["metrics"]
    assert r["device"]["window_s"] > 0 and "breakdown" in r
