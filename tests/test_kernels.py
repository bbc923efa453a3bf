"""Pallas kernel validation: interpret=True vs pure-jnp oracles (ref.py),
sweeping shapes/dtypes (prime sizes) and asserting exact equality (integer
kernels are bit-exact, not approximate)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

import repro  # noqa: F401
from repro.core import modmath as mm
from repro.core.params import toy_params, get_context
from repro.kernels import ops, ref


def _ctx(logN=6, L=3, k=2, beta=2):
    return get_context(toy_params(logN=logN, L=L, k=k, beta=beta))


def _rand(rng, qs, shape):
    return rng.integers(0, qs, size=shape).astype(np.uint32)


@pytest.mark.parametrize("logN,M", [(5, 3), (6, 6), (8, 4)])
def test_modmul_modadd(logN, M):
    ctx = _ctx(logN=logN, L=M - 1, k=1)
    rng = np.random.default_rng(0)
    N = ctx.params.N
    qs = np.asarray(ctx.moduli_host[:M], dtype=np.uint64)[:, None]
    x = _rand(rng, qs, (M, N))
    y = _rand(rng, qs, (M, N))
    q32 = jnp.asarray(ctx.moduli_u32[:M])
    qneg = jnp.asarray(ctx.qneg_inv[:M])
    got = ops.modmul(jnp.asarray(x), jnp.asarray(y), q32, qneg, block=32)
    want = ref.modmul_ref(jnp.asarray(x), jnp.asarray(y), q32, qneg)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    got = ops.modadd(jnp.asarray(x), jnp.asarray(y), q32, block=32)
    want = ref.modadd_ref(jnp.asarray(x), jnp.asarray(y), q32)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("logN,B", [(5, 1), (6, 2), (7, 3)])
def test_ntt_kernel(logN, B):
    ctx = _ctx(logN=logN)
    rng = np.random.default_rng(1)
    p = ctx.params
    M = p.num_total
    qs = np.asarray(ctx.moduli_host, dtype=np.uint64)[:, None]
    x = _rand(rng, qs, (B, M, p.N))
    got = ops.ntt(jnp.asarray(x), ctx.psi_brv_mont, ctx.moduli_u32,
                  ctx.qneg_inv)
    want = ref.ntt_ref(jnp.asarray(x), ctx.psi_brv_mont, ctx.moduli_u32,
                       ctx.qneg_inv)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    ninv_m = mm.to_mont(ctx.n_inv, ctx.moduli_u32, ctx.qneg_inv, ctx.r2)
    back = ops.intt(got, ctx.psi_inv_brv_mont, ninv_m, ctx.moduli_u32,
                    ctx.qneg_inv)
    np.testing.assert_array_equal(np.asarray(back), x)


@pytest.mark.parametrize("logN,d,nbeta,chunk", [(5, 4, 1, 2), (6, 6, 2, 3),
                                                (6, 8, 3, 8), (7, 5, 2, 1)])
def test_fused_hlt_kernel(logN, d, nbeta, chunk):
    ctx = _ctx(logN=logN, L=5, k=2, beta=nbeta)
    rng = np.random.default_rng(2)
    p = ctx.params
    M, N = p.num_total, p.N
    qs = np.asarray(ctx.moduli_host, dtype=np.uint64)[:, None]
    digits = _rand(rng, qs[None], (nbeta, M, N))
    c0e = _rand(rng, qs, (M, N))
    c1e = _rand(rng, qs, (M, N))
    u = _rand(rng, qs[None], (d, M, N))
    rk0 = _rand(rng, qs[None, None], (d, nbeta, M, N))
    rk1 = _rand(rng, qs[None, None], (d, nbeta, M, N))
    perms = np.stack([np.random.default_rng(i).permutation(N)
                      for i in range(d)]).astype(np.int32)
    id_idx = d // 2
    is_id = np.zeros((d, 1), np.int32)
    is_id[id_idx] = 1
    if d % chunk:
        pytest.skip("chunk must divide d")
    got0, got1 = ops.fused_hlt(
        jnp.asarray(digits), jnp.asarray(c0e), jnp.asarray(c1e),
        jnp.asarray(u), jnp.asarray(rk0), jnp.asarray(rk1),
        jnp.asarray(perms), jnp.asarray(is_id), ctx.moduli_u32, ctx.qneg_inv,
        chunk=chunk)
    want0, want1 = ref.fused_hlt_ref(
        jnp.asarray(digits), jnp.asarray(c0e), jnp.asarray(c1e),
        jnp.asarray(u), jnp.asarray(rk0), jnp.asarray(rk1),
        jnp.asarray(perms), ctx.moduli_u32, ctx.qneg_inv, id_idx)
    np.testing.assert_array_equal(np.asarray(got0), np.asarray(want0))
    np.testing.assert_array_equal(np.asarray(got1), np.asarray(want1))


@pytest.mark.parametrize("logN,B,d,nbeta,chunk", [(5, 2, 4, 1, 2),
                                                  (6, 3, 6, 2, 3),
                                                  (6, 1, 4, 2, 4)])
def test_fused_hlt_batched_kernel(logN, B, d, nbeta, chunk):
    """Batched kernel (leading ciphertext axis, per-batch rotation operands)
    == loop of single-ciphertext oracles."""
    ctx = _ctx(logN=logN, L=5, k=2, beta=nbeta)
    rng = np.random.default_rng(5)
    p = ctx.params
    M, N = p.num_total, p.N
    qs = np.asarray(ctx.moduli_host, dtype=np.uint64)[:, None]
    digits = _rand(rng, qs[None], (B, nbeta, M, N))
    c0e = _rand(rng, qs, (B, M, N))
    c1e = _rand(rng, qs, (B, M, N))
    u = _rand(rng, qs[None], (B, d, M, N))
    rk0 = _rand(rng, qs[None, None], (B, d, nbeta, M, N))
    rk1 = _rand(rng, qs[None, None], (B, d, nbeta, M, N))
    perms = np.stack([[np.random.default_rng(10 * b + i).permutation(N)
                       for i in range(d)] for b in range(B)]).astype(np.int32)
    is_id = np.zeros((B, d, 1), np.int32)
    for b in range(B):           # different identity slot per batch element
        is_id[b, b % d] = 1
    args = (jnp.asarray(digits), jnp.asarray(c0e), jnp.asarray(c1e),
            jnp.asarray(u), jnp.asarray(rk0), jnp.asarray(rk1),
            jnp.asarray(perms), jnp.asarray(is_id), ctx.moduli_u32,
            ctx.qneg_inv)
    got0, got1 = ops.fused_hlt_batched(*args, chunk=chunk)
    want0, want1 = ref.fused_hlt_batched_ref(*args)
    np.testing.assert_array_equal(np.asarray(got0), np.asarray(want0))
    np.testing.assert_array_equal(np.asarray(got1), np.asarray(want1))


@pytest.mark.parametrize("logN,H,S,B,d,nbeta,chunk",
                         [(5, 2, 3, 5, 4, 1, 2), (6, 3, 2, 6, 6, 2, 3)])
def test_fused_hlt_indexed_kernel(logN, H, S, B, d, nbeta, chunk):
    """Slot-indexed kernel over deduped operands and one key table == batched
    kernel on the gathered (replicated) operands and per-diagonal key
    copies — the scalar-prefetch index maps (slots, key rows, the limb rows
    and leading digits a lower level reads) must be pure routing, bit for
    bit."""
    ctx = _ctx(logN=logN, L=5, k=2, beta=nbeta + 1)
    rng = np.random.default_rng(8)
    p = ctx.params
    Mt, N = p.num_total, p.N
    rows = np.sort(rng.choice(Mt, Mt - 1, replace=False)).astype(np.int32)
    M = len(rows)
    qs = np.asarray(ctx.moduli_host, dtype=np.uint64)[:, None]
    digits = _rand(rng, qs[None, rows], (H, nbeta, M, N))
    c0e = _rand(rng, qs[rows], (H, M, N))
    c1e = _rand(rng, qs[rows], (H, M, N))
    u = _rand(rng, qs[None, rows], (S, d, M, N))
    K = 3
    rk0 = _rand(rng, qs[None, None], (K, nbeta + 1, Mt, N))
    rk1 = _rand(rng, qs[None, None], (K, nbeta + 1, Mt, N))
    key_idx = rng.integers(0, K, (S, d)).astype(np.int32)
    perms = np.stack([[np.random.default_rng(10 * s + i).permutation(N)
                       for i in range(d)] for s in range(S)]).astype(np.int32)
    is_id = np.zeros((S, d, 1), np.int32)
    for s in range(S):
        is_id[s, s % d] = 1
    ct_slots = rng.integers(0, H, B).astype(np.int32)
    diag_slots = rng.integers(0, S, B).astype(np.int32)
    q32, qneg = ctx.moduli_u32[rows], ctx.qneg_inv[rows]
    got0, got1 = ops.fused_hlt_indexed(
        jnp.asarray(digits), jnp.asarray(c0e), jnp.asarray(c1e),
        jnp.asarray(u), jnp.asarray(rk0), jnp.asarray(rk1),
        jnp.asarray(key_idx), jnp.asarray(rows), jnp.asarray(perms),
        jnp.asarray(is_id), jnp.asarray(ct_slots), jnp.asarray(diag_slots),
        q32, qneg, chunk=chunk)
    copies = lambda t: t[key_idx][:, :, :nbeta][:, :, :, rows][diag_slots]
    want0, want1 = ops.fused_hlt_batched(
        jnp.asarray(digits[ct_slots]), jnp.asarray(c0e[ct_slots]),
        jnp.asarray(c1e[ct_slots]), jnp.asarray(u[diag_slots]),
        jnp.asarray(copies(rk0)), jnp.asarray(copies(rk1)),
        jnp.asarray(perms[diag_slots]), jnp.asarray(is_id[diag_slots]),
        q32, qneg, chunk=chunk)
    np.testing.assert_array_equal(np.asarray(got0), np.asarray(want0))
    np.testing.assert_array_equal(np.asarray(got1), np.asarray(want1))


@pytest.mark.parametrize("logN,block", [(5, 32), (6, 32), (7, 32),
                                        (5, 24), (6, 48)])
def test_baseconv_kernel(logN, block):
    """block=24/48 do NOT divide N — the clamped last tile recomputes
    overlap columns, which must stay bit-identical (columnwise-pure)."""
    ctx = _ctx(logN=logN, L=4, k=3, beta=2)
    from repro.core.rns import RnsTools
    tools = RnsTools(ctx)
    rng = np.random.default_rng(3)
    p = ctx.params
    S = (0, 1, 2)
    T = (3, 4, p.num_main, p.num_main + 1)
    hat_inv, W, D_mod_t, inv_d = tools._bc_tables(S, T)
    qs_own = np.array([ctx.moduli_host[i] for i in S], np.uint64)[:, None]
    qs_gen = np.array([ctx.moduli_host[i] for i in T], np.uint64)[:, None]
    x = _rand(rng, qs_own, (len(S), p.N))

    def mont(v, q):
        return jnp.asarray(((v.astype(np.uint64) << np.uint64(32))
                            % q).astype(np.uint32))
    hat_inv_m = mont(np.asarray(hat_inv), qs_own)
    W_m = mont(np.asarray(W), qs_gen)              # (|T|, |S|)
    D_mod_m = mont(np.asarray(D_mod_t), qs_gen)
    q_own = jnp.asarray(qs_own.astype(np.uint32))
    q_gen = jnp.asarray(qs_gen.astype(np.uint32))
    qneg_own = jnp.asarray(np.array(
        [[mm.mont_constants(int(q))[0]] for q in qs_own[:, 0]], np.uint32))
    qneg_gen = jnp.asarray(np.array(
        [[mm.mont_constants(int(q))[0]] for q in qs_gen[:, 0]], np.uint32))
    got = ops.baseconv(jnp.asarray(x), hat_inv_m, q_own, qneg_own, W_m,
                       D_mod_m, jnp.asarray(inv_d), q_gen, qneg_gen,
                       block=block)
    # oracle 1: the mont ref
    want = ref.baseconv_ref(jnp.asarray(x), hat_inv_m, W_m[:, :, None],
                            D_mod_m, jnp.asarray(inv_d), q_own, qneg_own,
                            q_gen, qneg_gen)
    # oracle 2: the u64 runtime path
    want2 = tools.base_conv(jnp.asarray(x), S, T)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want2))
