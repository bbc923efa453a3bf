"""Ahead-of-time compiles of the Set-A kernels for a TPU v5e, without a chip.

Every Pallas kernel the Set-A HE MM program launches is lowered with
``interpret=False`` at the real widths (N = 8192, β = 5, M = 6 extended
limbs at the top level) and compiled by the TPU compiler for one chip of a
described v5e topology. Nothing runs: these tests catch what interpret mode
cannot (block shapes Mosaic refuses, unsupported ops or casts, scalar-memory
overflow, an HBM working set that does not fit) at no chip time.

The topology is described only inside the module fixture below, so the file
imports and collects the same tests everywhere; where the TPU compiler
cannot be loaded the fixture skips.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

import repro  # noqa: F401
from repro.core.ckks import CkksEngine
from repro.core import ntt as core_ntt
from repro.core.costmodel import pick_rotation_chunk
from repro.core.params import SET_A
from repro.kernels import basechange, fused_hlt, ntt as kntt

P = SET_A.runtime_variant()
#: one v5e chip's HBM (Google Cloud documentation, "TPU v5e")
HBM_BYTES = 16 * 10**9
U32 = jnp.uint32


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # the TPU compiler otherwise writes its logs under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to a persistent cache but
    # cannot be read back without the chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to load libtpu
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def eng():
    return CkksEngine(P)


def _shape(sharding, x):
    return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)


def _sds(sharding, shape, dtype=U32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _tables(sharding, tabs):
    """Array entries of a fused-table dict as shapes; the rest stays."""
    arrays = {k: _shape(sharding, np.asarray(v)) for k, v in tabs.items()
              if hasattr(v, "shape") and k != "drop_idx"}
    static = {k: v for k, v in tabs.items() if k not in arrays}
    return arrays, static


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < HBM_BYTES, used
    return compiled


@pytest.mark.parametrize("kind", ["ntt", "intt"])
def test_ntt_kernels_compile(one_chip, eng, kind):
    ctx = eng.ctx
    x = _sds(one_chip, (1, P.num_total, P.N))
    col = lambda a: _shape(one_chip, np.asarray(a))
    if kind == "ntt":
        _compile(lambda *a: kntt.ntt(*a, interpret=False), x,
                 col(ctx.psi_brv_mont), col(ctx.moduli_u32),
                 col(ctx.qneg_inv))
    else:
        _compile(lambda *a: kntt.intt(*a, interpret=False), x,
                 col(ctx.psi_inv_brv_mont), col(ctx.n_inv_mont),
                 col(ctx.moduli_u32), col(ctx.qneg_inv))


@pytest.mark.parametrize("level", [P.L, P.L - 1])
def test_hoist_fused_db_compiles(one_chip, eng, level):
    """The batched hoist of Step 1 (level L) and of Step 2 (level L-1),
    with the chip's f32 BaseConv tables."""
    arrays, static = _tables(one_chip, basechange.build_hoist_tables(
        eng.ctx, eng.tools, level, fp_dtype=np.float32))
    _compile(lambda c1s, a: basechange.hoist_fused_db(
        c1s, {**a, **static}, interpret=False),
        _sds(one_chip, (2, level + 1, P.N)), arrays)


@pytest.mark.parametrize("level", [P.L, P.L - 1])
def test_moddown_fused_compiles(one_chip, eng, level):
    """Merged ModDown+Rescale, vmapped over a batch as the slot-indexed
    pipeline runs it."""
    tabs = basechange.build_moddown_tables(eng.ctx, eng.tools, level,
                                           fp_dtype=np.float32)
    arrays, static = _tables(one_chip, tabs)
    static["drop_idx"] = tabs["drop_idx"]
    m_ext = level + 1 + P.k
    _compile(lambda x, a: jax.vmap(lambda y: basechange.moddown_fused(
        y, {**a, **static}, interpret=False))(x),
        _sds(one_chip, (4, m_ext, P.N)), arrays)


def _compile_fused_hlt(sharding, level, B, S, H, d, K):
    """The slot-indexed fused HLT of one launch, reading a table of K
    rotation keys at the top basis, with the diagonals and the table stored
    as kernel tiles as the context keeps them; returns the compiled
    executable."""
    nbeta, M = level + 1, level + 1 + P.k
    tile = core_ntt.tile_shape(P.N)
    chunk = max(1, min(pick_rotation_chunk(P, nbeta=nbeta), d))
    d_pad = -(-d // chunk) * chunk
    s = lambda shape, dtype=U32: _sds(sharding, shape, dtype)
    i32 = jnp.int32
    return _compile(lambda *a: fused_hlt.fused_hlt_indexed(
        *a, chunk=chunk, interpret=False),
        s((H, nbeta, M, P.N)), s((H, M, P.N)), s((H, M, P.N)),
        s((S, d_pad, M) + tile), s((K, P.beta, P.num_total) + tile),
        s((K, P.beta, P.num_total) + tile), s((S, d_pad), i32), s((M,), i32),
        s((S, d_pad, P.N), i32), s((S, d_pad, 1), i32), s((B,), i32),
        s((B,), i32), s((M, 1)), s((M, 1)))


# (level, batch B, diagonal sets S, hoisting products H, diagonals d, keys K):
# Step 1 is {σ(A), τ(B)} with 2·64-1 diagonals each; Step 2 is the 2·l = 128
# ε/ω HLTs with 2 diagonals each off the 2 Step-1 outputs (type IV, l = 64),
# reading Step 2's digits and limb rows out of the top-level key table.
@pytest.mark.parametrize("level,B,S,H,d,K", [(P.L, 2, 2, 2, 127, 252),
                                             (P.L - 1, 128, 128, 2, 2, 252)],
                         ids=["step1", "step2"])
def test_fused_hlt_indexed_compiles(one_chip, level, B, S, H, d, K):
    _compile_fused_hlt(one_chip, level, B, S, H, d, K)


def test_fused_hlt_type_ii_step2_fits(one_chip):
    """Set-A type II (64×16×64) Step 2: 32 HLTs of up to 194 diagonals
    (d_pad 195 at the cost model's chunk of 13) against the table of the
    3,117 distinct rotation keys of its 3,132 rotation steps: arguments and
    working bytes of the launch fit one chip's HBM."""
    compiled = _compile_fused_hlt(one_chip, P.L - 1, 32, 32, 2, 194, 3117)
    mem = compiled.memory_analysis()
    table = 2 * 3117 * P.beta * P.num_total * P.N * 4
    assert mem.argument_size_in_bytes > table
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < HBM_BYTES


def test_key_table_is_built_in_place(one_chip):
    """The type II table of 3,117 rotation keys is converted to the
    Montgomery domain in place: the table is aliased, never copied."""
    from repro.core import ckks
    M = P.num_total
    tab = _sds(one_chip, (3117, P.beta, M) + core_ntt.tile_shape(P.N))
    col = _sds(one_chip, (M, 1))
    mem = ckks._table_to_mont.lower(tab, tab, col, col, col).compile(
    ).memory_analysis()
    assert mem.alias_size_in_bytes == 2 * 3117 * P.beta * M * P.N * 4
    assert mem.temp_size_in_bytes == 0


@pytest.mark.parametrize("kind", ["modmul", "modadd", "baseconv"])
def test_standalone_kernels_compile(one_chip, kind):
    """The kernels outside the HE MM program (ops.modmul/modadd/baseconv)
    obey the same block rules."""
    from repro.kernels import baseconv, modmul
    M, N, S, T = P.num_total, P.N, P.L + 1, P.k + 1
    s = lambda shape, dtype=U32: _sds(one_chip, shape, dtype)
    if kind == "modmul":
        _compile(lambda *a: modmul.modmul(*a, interpret=False),
                 s((M, N)), s((M, N)), s((M, 1)), s((M, 1)))
    elif kind == "modadd":
        _compile(lambda *a: modmul.modadd(*a, interpret=False),
                 s((M, N)), s((M, N)), s((M, 1)))
    else:
        _compile(lambda *a: baseconv.baseconv(*a, interpret=False),
                 s((S, N)), s((S, 1)), s((S, 1)), s((S, 1)), s((T, S)),
                 s((T, 1)), s((S, 1), jnp.float32), s((T, 1)), s((T, 1)))
