"""The program's host spans (``repro.core.spans``) around one toy HE MM,
read back from a real profiler trace: the stage tree, the span count of the
CKKS tail, outputs bit-identical with the profiler on and off, and one list
of names. All in one file, so that one xdist worker holds the profiler."""
import pathlib
import re

import numpy as np
import pytest
import jax

import repro  # noqa: F401
from repro.core import spans
from repro.core.ckks import CkksEngine
from repro.core.compile import HEContext, compile_hemm
from repro.core.hemm import decrypt_matrix, encrypt_matrix, plan_hemm
from repro.core.params import toy_params

TOY = toy_params(logN=6, L=4, k=3, beta=2, scale_bits=26)
SRC = pathlib.Path(__file__).resolve().parents[1] / "src" / "repro"
PREFIXES = ("hemm.", "hlt.", "ckks.")


def _program_spans(logdir) -> list:
    """(name, start_ns, end_ns) of every program span on the host plane,
    sorted by start, outer first."""
    from jax.profiler import ProfileData
    path = sorted(pathlib.Path(logdir).rglob("*.xplane.pb"))[-1]
    pd = ProfileData.from_file(str(path))
    out = [(ev.name, ev.start_ns, ev.end_ns)
           for plane in pd.planes if plane.name.startswith("/host")
           for line in plane.lines for ev in line.events
           if ev.name.startswith(PREFIXES)]
    return sorted(out, key=lambda sp: (sp[1], -sp[2]))


def _parents(sps) -> list:
    """Index of the span directly around each span, or None."""
    parent, stack = [], []
    for i, (_, s, _) in enumerate(sps):
        while stack and sps[stack[-1]][2] <= s:
            stack.pop()
        parent.append(stack[-1] if stack else None)
        stack.append(i)
    return parent


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Keygen, compile and one product under the profiler, then the same
    product with the profiler off."""
    rng = np.random.default_rng(11)
    ctx = HEContext(CkksEngine(TOY))
    m, l, n = 4, 3, 5
    plan = plan_hemm(ctx.eng, m, l, n)
    A = rng.uniform(-1, 1, (m, l))
    B = rng.uniform(-1, 1, (l, n))
    logdir = tmp_path_factory.mktemp("trace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(logdir), profiler_options=opts)
    try:
        ctx.keygen(rng, rot_steps=plan.rot_steps)
        prog = compile_hemm(ctx, plan)
        ctA = encrypt_matrix(ctx.eng, ctx.keys, A, rng)
        ctB = encrypt_matrix(ctx.eng, ctx.keys, B, rng)
        on = prog(ctA, ctB)
        jax.block_until_ready((on.c0, on.c1))
    finally:
        jax.profiler.stop_trace()
    off = prog(ctA, ctB)
    sps = _program_spans(logdir)
    return dict(ctx=ctx, prog=prog, l=l, shape=(m, n), on=on, off=off,
                spans=sps, parent=_parents(sps))


def _children(t, i) -> list:
    return [t["spans"][j][0] for j, p in enumerate(t["parent"]) if p == i]


def _within(t, i, name) -> list:
    """Indices of the spans named ``name`` anywhere inside span ``i``."""
    out = []
    for j, (nm, _, _) in enumerate(t["spans"]):
        p = t["parent"][j]
        while p is not None and p != i:
            p = t["parent"][p]
        if nm == name and p == i:
            out.append(j)
    return out


def _index(t, name) -> list:
    return [i for i, sp in enumerate(t["spans"]) if sp[0] == name]


def test_one_call_holds_the_four_stages_in_order(traced):
    (call,) = _index(traced, "hemm.call")
    assert traced["parent"][call] is None
    assert _children(traced, call) == ["hemm.step1", "hemm.hoist",
                                       "hemm.step2", "hemm.tail"]


def test_set_up_spans_are_top_level(traced):
    for name in ("ckks.keygen", "hemm.compile"):
        (i,) = _index(traced, name)
        assert traced["parent"][i] is None


@pytest.mark.parametrize("stage", ["hemm.step1", "hemm.step2"])
def test_each_hlt_step_hoists_launches_and_finishes(traced, stage):
    (i,) = _index(traced, stage)
    assert _children(traced, i) == ["hlt.hoist", "hlt.launch", "hlt.finish"]


def test_tail_holds_l_mults_rescales_and_l_minus_1_adds(traced):
    l = traced["l"]
    (tail,) = _index(traced, "hemm.tail")
    mults = _within(traced, tail, "ckks.mult")
    assert len(mults) == len(_index(traced, "ckks.mult")) == l
    for i in mults:
        assert _children(traced, i) == ["ckks.key_switch"]
    assert len(_index(traced, "ckks.key_switch")) == l
    assert len(_within(traced, tail, "ckks.rescale")) == l
    assert len(_within(traced, tail, "ckks.add")) == l - 1
    assert _children(traced, tail) == (["ckks.mult", "ckks.rescale"]
                                       + ["ckks.mult", "ckks.rescale",
                                          "ckks.add"] * (l - 1))


def test_output_is_bit_identical_with_the_profiler_on_and_off(traced):
    on, off = traced["on"], traced["off"]
    np.testing.assert_array_equal(np.asarray(on.c0), np.asarray(off.c0))
    np.testing.assert_array_equal(np.asarray(on.c1), np.asarray(off.c1))
    assert (on.level, on.scale) == (off.level, off.scale)
    eng, keys = traced["ctx"].eng, traced["ctx"].keys
    np.testing.assert_array_equal(decrypt_matrix(eng, keys, on, *traced["shape"]),
                                  decrypt_matrix(eng, keys, off,
                                                 *traced["shape"]))


def test_every_recorded_name_is_listed(traced):
    assert {nm for nm, _, _ in traced["spans"]} <= set(spans.SPANS)


def test_source_uses_exactly_the_listed_names():
    """Every ``span("...")`` in the source names a listed span, every
    listed span is used, and nothing else opens a TraceAnnotation."""
    used = set()
    for path in SRC.rglob("*.py"):
        text = path.read_text()
        used |= set(re.findall(r'\bspan\("([^"]+)"\)', text))
        if path.name != "spans.py":
            assert "TraceAnnotation" not in text, path
    assert used == set(spans.SPANS)
    assert all(name.startswith(PREFIXES) for name in spans.SPANS)


def test_an_unlisted_name_is_refused():
    with pytest.raises(KeyError, match="hemm.bogus"):
        spans.span("hemm.bogus")
