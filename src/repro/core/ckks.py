"""CKKS (RNS variant) over the repro substrate: encode/decode, keygen,
encrypt/decrypt, Add / CMult / Mult / Rot with hybrid (β-digit) keyswitching.

Conventions
-----------
* ciphertext ct = (c0, c1), dec(ct) = c0 + c1·s (mod Q_ℓ); polys stored as
  (ℓ+1, N) uint32 limbs in **bit-reversed evaluation domain** (paper §II-B3:
  polynomials stay in the evaluation domain; only BaseConv drops to coeff).
* prime order: [q_0 .. q_L, p_0 .. p_{k-1}]; a level-ℓ ct uses limbs 0..ℓ.
* scales are tracked on the host (float); Rescale divides by q_ℓ.

The KeySwitch here is the *unfused, coarse-grained* reference (paper Fig. 2(A)
baseline). The hoisted + fused MO-HLT datapath lives in core/hlt.py.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from collections.abc import Mapping
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import automorph, modmath as mm, ntt
from repro.core.params import HEParams, PrimeContext, get_context
from repro.core.rns import RnsTools
from repro.core.spans import span
from repro.kernels import basechange, ops

#: rotation keys generated per device program by ``CkksEngine.keygen``
KEYGEN_CHUNK = 16


# ---------------------------------------------------------------------------
# containers
# ---------------------------------------------------------------------------


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=("c0", "c1"),
    meta_fields=("level", "scale"),
)
@dataclasses.dataclass
class Ciphertext:
    c0: jnp.ndarray           # (level+1, N) u32, eval domain
    c1: jnp.ndarray
    level: int
    scale: float


@dataclasses.dataclass
class Plaintext:
    data: jnp.ndarray         # (level+1, N) u32, eval domain
    level: int
    scale: float


@dataclasses.dataclass
class EvalKey:
    """Hybrid keyswitching key: digit-stacked rows over the FULL basis."""
    k0: jnp.ndarray           # (beta, M, N) u32 eval
    k1: jnp.ndarray


@dataclasses.dataclass
class KeyTable:
    """Every rotation key once, stacked: row ``rows[g]`` is the key of
    galois element g, all β digits over the full basis, each polynomial as
    fused-kernel tiles (``core/ntt.tile_shape``), the form the fused HLT
    kernel reads by row index.

    ``mont`` marks the Montgomery form that kernel needs:
    ``HEContext.key_table`` converts the table to it in place (``to_mont``),
    once per keygen, so the device never holds a second copy. ``key(row)``
    returns the standard (β, M, N) form either way. An empty key set keeps
    one zero row, so the kernel always has a row to index."""
    k0: jnp.ndarray           # (max(1, S), beta, M, R, C) u32 eval
    k1: jnp.ndarray
    rows: dict[int, int]      # galois element -> row
    q32: jnp.ndarray          # (M, 1) full-basis Montgomery constants
    qneg: jnp.ndarray
    r2: jnp.ndarray
    mont: bool = False

    @property
    def nbytes(self) -> int:
        return int(self.k0.nbytes) + int(self.k1.nbytes)

    def key(self, row: int) -> EvalKey:
        shape = self.k0.shape[1:3] + (-1,)
        k0 = self.k0[row].reshape(shape)
        k1 = self.k1[row].reshape(shape)
        if self.mont:
            k0 = mm.from_mont(k0, self.q32, self.qneg)
            k1 = mm.from_mont(k1, self.q32, self.qneg)
        return EvalKey(k0=k0, k1=k1)

    def to_mont(self) -> None:
        """Convert the table to the Montgomery domain, in place on the
        device (the standard-form buffers are donated)."""
        if not self.mont:
            self.k0, self.k1 = _table_to_mont(self.k0, self.k1, self.q32,
                                              self.qneg, self.r2)
            self.mont = True


class _TableKeys(Mapping):
    """Read-only ``key -> EvalKey`` view of a KeyTable through ``index``
    (key -> row): keys are sliced out on access, never held."""

    def __init__(self, table: KeyTable, index: dict):
        self._table, self._index = table, index

    def __getitem__(self, key) -> EvalKey:
        return self._table.key(self._index[key])

    def __iter__(self):
        return iter(self._index)

    def __len__(self) -> int:
        return len(self._index)


@dataclasses.dataclass
class Keys:
    s_eval: jnp.ndarray                 # (M, N) secret over full basis
    evk_mult: EvalKey
    table: KeyTable                     # every rotation key, once
    steps: dict[int, int]               # rotation step -> galois element

    @property
    def galois(self) -> Mapping:
        """galois element -> rotation key (standard form)."""
        return _TableKeys(self.table, self.table.rows)

    @property
    def rot(self) -> Mapping:
        """rotation step -> rotation key (standard form)."""
        return _TableKeys(self.table, {r: self.table.rows[g]
                                       for r, g in self.steps.items()})


# ---------------------------------------------------------------------------
# engine
# ---------------------------------------------------------------------------


class CkksEngine:
    """`datapath` selects the (i)NTT lowering for every transform the engine
    performs: "xla" is the u64 reference lowering; "pallas" routes _ntt/_intt
    through the VMEM-resident Montgomery kernel (kernels/ntt.py) and the
    hoist / merged ModDown+Rescale through the fused base-change kernels
    (kernels/basechange.py). Both paths are bit-identical — the knob trades
    lowering, not semantics (tests/test_fused_datapath.py)."""

    def __init__(self, params: HEParams, datapath: str = "xla"):
        assert datapath in ("xla", "pallas"), datapath
        self.params = params
        self.datapath = datapath
        self.ctx: PrimeContext = get_context(params)
        self.tools = RnsTools(self.ctx)
        self._fused_tabs: dict = {}
        # monotonic boundary-crossing counters: chained programs prove their
        # zero-intermediate-decrypt claim by asserting "decrypts" deltas
        self.op_counts: dict = {"encrypts": 0, "decrypts": 0}

    # -- basis helpers ------------------------------------------------------

    def basis(self, idx):
        return self.ctx.slc(np.asarray(idx, dtype=np.int64))

    def main_basis(self, ell: int):
        return self.basis(np.arange(ell + 1))

    def _ntt(self, x, view):
        if self.datapath == "pallas":
            return ops.ntt(x[None], view.psi_brv_mont, view.moduli_u32,
                           view.qneg_inv)[0]
        return ntt.ntt(x, view.psi_brv, view.moduli)

    def _intt(self, x, view):
        if self.datapath == "pallas":
            return ops.intt(x[None], view.psi_inv_brv_mont, view.n_inv_mont,
                            view.moduli_u32, view.qneg_inv)[0]
        return ntt.intt(x, view.psi_inv_brv, view.n_inv, view.moduli)

    # -- fused base-change tables (cached per level) -------------------------

    def _fp_dtype(self):
        """Float dtype of the fused BaseConv correction: f64 keeps CPU runs
        bit-exact vs the u64 oracle; TPU uses the native f32 path (same
        convention as the sharded datapath)."""
        return np.float64 if jax.default_backend() == "cpu" else np.float32

    def fused_hoist_tables(self, level: int) -> dict:
        key = ("hoist", level)
        if key not in self._fused_tabs:
            # ensure_compile_time_eval: the first call may happen inside a
            # jit/make_jaxpr trace (the verifier's shape-only lint) — the
            # cached tables must be CONCRETE arrays, never leaked tracers.
            with jax.ensure_compile_time_eval():
                self._fused_tabs[key] = basechange.build_hoist_tables(
                    self.ctx, self.tools, level, fp_dtype=self._fp_dtype())
        return self._fused_tabs[key]

    def fused_moddown_tables(self, level: int) -> dict:
        key = ("moddown", level)
        if key not in self._fused_tabs:
            with jax.ensure_compile_time_eval():
                self._fused_tabs[key] = basechange.build_moddown_tables(
                    self.ctx, self.tools, level, fp_dtype=self._fp_dtype())
        return self._fused_tabs[key]

    # -- encode / decode (host, FFT-based canonical embedding) --------------

    def encode(self, m, level: Optional[int] = None, scale: Optional[float] = None) -> Plaintext:
        p = self.params
        level = p.L if level is None else level
        scale = p.scale if scale is None else scale
        m = np.asarray(m, dtype=np.complex128).ravel()
        assert m.size <= p.slots, f"message {m.size} > slots {p.slots}"
        mv = np.zeros(p.slots, dtype=np.complex128)
        mv[: m.size] = m
        spec = np.zeros(2 * p.N, dtype=np.complex128)
        spec[self.ctx.rot_group] = mv
        coeffs = np.fft.fft(spec)[: p.N].real * (2.0 / p.N) * scale
        coeffs = np.round(coeffs).astype(object)
        res = self._int_coeffs_to_limbs(coeffs, level)
        data = self._ntt(jnp.asarray(res), self.main_basis(level))
        return Plaintext(data=data, level=level, scale=scale)

    def _int_coeffs_to_limbs(self, coeffs, level: int) -> np.ndarray:
        return self._int_coeffs_to_basis(coeffs, list(range(level + 1)))

    def _int_coeffs_to_basis(self, coeffs, idx) -> np.ndarray:
        out = np.empty((len(idx), self.params.N), dtype=np.uint32)
        for row, i in enumerate(idx):
            q = self.ctx.moduli_host[i]
            out[row] = np.array([int(c) % q for c in coeffs], dtype=np.uint32)
        return out

    def encode_to_basis(self, m, idx, scale: float) -> jnp.ndarray:
        """Encode a message over an arbitrary prime basis (e.g. the extended
        basis Q∪P for DiagIP plaintexts). Returns (|idx|, N) eval residues."""
        p = self.params
        m = np.asarray(m, dtype=np.complex128).ravel()
        mv = np.zeros(p.slots, dtype=np.complex128)
        mv[: m.size] = m
        spec = np.zeros(2 * p.N, dtype=np.complex128)
        spec[self.ctx.rot_group] = mv
        coeffs = np.round(np.fft.fft(spec)[: p.N].real * (2.0 / p.N) * scale
                          ).astype(object)
        return self._ntt(jnp.asarray(self._int_coeffs_to_basis(coeffs, idx)),
                         self.basis(idx))

    def _crt_lift_centered(self, limbs: np.ndarray, level: int) -> np.ndarray:
        """uint32 (level+1, N) -> centered python-int coefficients."""
        qs = [self.ctx.moduli_host[i] for i in range(level + 1)]
        Q = 1
        for q in qs:
            Q *= q
        acc = np.zeros(limbs.shape[1], dtype=object)
        for i, q in enumerate(qs):
            hat = Q // q
            w = hat * mm.host_inv(hat % q, q)
            acc = (acc + limbs[i].astype(object) * (w % Q)) % Q
        return np.where(acc > Q // 2, acc - Q, acc)

    def decode(self, pt: Plaintext, num: Optional[int] = None) -> np.ndarray:
        p = self.params
        coeff = np.asarray(self._intt(pt.data, self.main_basis(pt.level)))
        c = self._crt_lift_centered(coeff, pt.level).astype(np.float64)
        vals = np.conj(np.fft.fft(c, 2 * p.N))[self.ctx.rot_group] / pt.scale
        return vals[: (num if num is not None else p.slots)]

    # -- sampling ------------------------------------------------------------

    def _residues_all(self, ints: np.ndarray, idx) -> np.ndarray:
        out = np.empty((len(idx), ints.size), dtype=np.uint32)
        for row, i in enumerate(idx):
            q = self.ctx.moduli_host[i]
            out[row] = np.mod(ints, q).astype(np.uint32)
        return out

    def _small_poly_eval(self, ints: np.ndarray, idx) -> jnp.ndarray:
        view = self.basis(idx)
        return self._ntt(jnp.asarray(self._residues_all(ints, idx)), view)

    # -- keygen ---------------------------------------------------------------

    def keygen(self, rng: np.random.Generator, rot_steps=()) -> Keys:
        with span("ckks.keygen"):
            p = self.params
            full = list(range(p.num_total))
            s_int = rng.integers(-1, 2, size=p.N).astype(np.int64)
            s_eval = self._small_poly_eval(s_int, full)

            # s^2 over full basis (eval-domain product)
            view = self.basis(full)
            s2_eval = mm.mulmod(s_eval, s_eval, view.moduli)

            evk_mult = self._make_evk(rng, s_eval, s2_eval)
            steps, rows = {}, {}
            for r in rot_steps:
                g = automorph.galois_elt_rot(r, p.N)
                steps[r] = g
                rows.setdefault(g, len(rows))
            table = self._rotation_key_table(rng, s_eval, list(rows))
            return Keys(s_eval=s_eval, evk_mult=evk_mult,
                        table=KeyTable(*table, rows=rows,
                                       q32=view.moduli_u32,
                                       qneg=view.qneg_inv, r2=view.r2),
                        steps=steps)

    def _gadget(self) -> np.ndarray:
        """(β, M, 1) u32: W_j = P · [ D̂_j · (D̂_j^{-1} mod D_j) ] of each
        key-switch digit j over the full basis (paper §II-B3)."""
        p = self.params
        full = list(range(p.num_total))
        Pprod = 1
        for i in range(p.num_main, p.num_total):
            Pprod *= self.ctx.moduli_host[i]
        QL = 1
        for i in range(p.num_main):
            QL *= self.ctx.moduli_host[i]
        ws = []
        for (st, en) in p.digits_at_level(p.L):
            Dj = 1
            for i in range(st, en):
                Dj *= self.ctx.moduli_host[i]
            hatDj = QL // Dj
            # NB: D_j is composite — use the general modular inverse, not Fermat.
            w_int = Pprod * hatDj * pow(hatDj % Dj, -1, Dj)
            ws.append([w_int % self.ctx.moduli_host[i] for i in full])
        return np.array(ws, dtype=np.uint64)[:, :, None].astype(np.uint32)

    def _make_evk(self, rng: np.random.Generator, s_eval, sprime_eval) -> EvalKey:
        """evk_j = (-a_j s + e_j + W_j s', a_j) over the full basis, where
        W_j is the gadget factor (``_gadget``)."""
        p = self.params
        full = list(range(p.num_total))
        view = self.basis(full)
        k0s, k1s = [], []
        for w_res in self._gadget():
            a = self._uniform_poly(rng, full)
            e_eval = self._small_poly_eval(
                np.round(rng.normal(0, 3.2, size=p.N)).astype(np.int64), full)
            w_sp = mm.mulmod(sprime_eval, jnp.asarray(w_res), view.moduli)
            k0 = mm.addmod(
                mm.submod(e_eval, mm.mulmod(a, s_eval, view.moduli), view.moduli),
                w_sp, view.moduli)
            k0s.append(k0)
            k1s.append(a)
        return EvalKey(k0=jnp.stack(k0s), k1=jnp.stack(k1s))

    def _rotation_key_table(self, rng: np.random.Generator, s_eval, gs):
        """(k0, k1) tables of the rotation keys of galois elements ``gs``,
        row by row in that order: the same draws, in the same order, and the
        same keys as one ``_make_evk(rng, s_eval, ψ_g(s))`` per element,
        computed ``KEYGEN_CHUNK`` keys per device program and written into
        the table in place."""
        p = self.params
        full = list(range(p.num_total))
        nbeta, M = len(p.digits_at_level(p.L)), len(full)
        qs = np.array([self.ctx.moduli_host[i] for i in full],
                      dtype=np.uint64)[:, None]
        w = jnp.asarray(self._gadget())
        shape = (max(1, len(gs)), nbeta, M) + ntt.tile_shape(p.N)
        k0 = jnp.zeros(shape, jnp.uint32)
        k1 = jnp.zeros(shape, jnp.uint32)
        for start in range(0, len(gs), KEYGEN_CHUNK):
            part = gs[start: start + KEYGEN_CHUNK]
            a = np.empty((len(part), nbeta, M, p.N), np.uint32)
            e = np.empty((len(part), nbeta, M, p.N), np.uint32)
            for i in range(len(part)):
                for j in range(nbeta):      # _make_evk's draws, in order
                    a[i, j] = rng.integers(0, qs, size=(M, p.N))
                    e[i, j] = self._residues_all(np.round(rng.normal(
                        0, 3.2, size=p.N)).astype(np.int64), full)
            perms = np.stack([automorph.eval_perm(p.N, g) for g in part])
            k0, k1 = _rotation_keys_program(
                p, self.datapath, k0, k1, np.int32(start), s_eval, w,
                jnp.asarray(a), jnp.asarray(e), jnp.asarray(perms))
        return k0, k1

    def _uniform_poly(self, rng: np.random.Generator, idx) -> jnp.ndarray:
        qs = np.array([self.ctx.moduli_host[i] for i in idx], dtype=np.uint64)[:, None]
        return jnp.asarray(rng.integers(0, qs, size=(len(idx), self.params.N))
                           .astype(np.uint32))

    # -- encrypt / decrypt ----------------------------------------------------

    def encrypt(self, pt: Plaintext, keys: Keys, rng: np.random.Generator) -> Ciphertext:
        self.op_counts["encrypts"] += 1
        idx = list(range(pt.level + 1))
        view = self.basis(idx)
        a = self._uniform_poly(rng, idx)
        e = self._small_poly_eval(
            np.round(rng.normal(0, 3.2, size=self.params.N)).astype(np.int64), idx)
        c0 = mm.addmod(
            mm.submod(e, mm.mulmod(a, keys.s_eval[: pt.level + 1], view.moduli),
                      view.moduli),
            pt.data, view.moduli)
        return Ciphertext(c0=c0, c1=a, level=pt.level, scale=pt.scale)

    def decrypt(self, ct: Ciphertext, keys: Keys) -> Plaintext:
        self.op_counts["decrypts"] += 1
        view = self.main_basis(ct.level)
        data = mm.addmod(
            ct.c0, mm.mulmod(ct.c1, keys.s_eval[: ct.level + 1], view.moduli),
            view.moduli)
        return Plaintext(data=data, level=ct.level, scale=ct.scale)

    def decrypt_decode(self, ct: Ciphertext, keys: Keys, num=None) -> np.ndarray:
        return self.decode(self.decrypt(ct, keys), num)

    # -- homomorphic ops ------------------------------------------------------

    def add(self, a: Ciphertext, b: Ciphertext) -> Ciphertext:
        with span("ckks.add"):
            assert a.level == b.level, (a.level, b.level)
            c0, c1 = _add_program(self.params, a.level, a.c0, a.c1, b.c0, b.c1)
            return Ciphertext(c0, c1, a.level, max(a.scale, b.scale))

    def sub(self, a: Ciphertext, b: Ciphertext) -> Ciphertext:
        view = self.main_basis(a.level)
        return Ciphertext(mm.submod(a.c0, b.c0, view.moduli),
                          mm.submod(a.c1, b.c1, view.moduli),
                          a.level, max(a.scale, b.scale))

    def cmult(self, ct: Ciphertext, pt: Plaintext) -> Ciphertext:
        assert pt.level >= ct.level
        view = self.main_basis(ct.level)
        d = pt.data[: ct.level + 1]
        return Ciphertext(mm.mulmod(ct.c0, d, view.moduli),
                          mm.mulmod(ct.c1, d, view.moduli),
                          ct.level, ct.scale * pt.scale)

    def mod_drop(self, ct: Ciphertext, level: int) -> Ciphertext:
        assert level <= ct.level
        return Ciphertext(ct.c0[: level + 1], ct.c1[: level + 1], level, ct.scale)

    def mult(self, a: Ciphertext, b: Ciphertext, keys: Keys) -> Ciphertext:
        """ct × ct with relinearization (no rescale — call rescale() after,
        mirroring paper Algorithm 1/2 structure). One compiled program."""
        with span("ckks.mult"):
            assert a.level == b.level
            evk = keys.evk_mult
            c0, c1 = _mult_program(self.params, self.datapath, a.level,
                                   a.c0, a.c1, b.c0, b.c1, evk.k0, evk.k1)
            return Ciphertext(c0, c1, a.level, a.scale * b.scale)

    def _mult_body(self, a0, a1, b0, b1, k0, k1, ell: int):
        """Tensor product, relinearisation of d2 and the final adds."""
        q = self.main_basis(ell).moduli
        d0 = mm.mulmod(a0, b0, q)
        d1 = mm.addmod(mm.mulmod(a0, b1, q), mm.mulmod(a1, b0, q), q)
        d2 = mm.mulmod(a1, b1, q)
        ks0, ks1 = self._key_switch_body(d2, k0, k1, ell)
        return mm.addmod(d0, ks0, q), mm.addmod(d1, ks1, q)

    def rotate(self, ct: Ciphertext, r: int, keys: Keys) -> Ciphertext:
        """Rot(ct, r): circular left rotation of slots by r."""
        p = self.params
        g = automorph.galois_elt_rot(r, p.N)
        key = keys.galois[g]
        c0p = automorph.apply_eval(ct.c0, p.N, g)
        c1p = automorph.apply_eval(ct.c1, p.N, g)
        k0, k1 = self.key_switch(c1p, key, ct.level)
        view = self.main_basis(ct.level)
        return Ciphertext(mm.addmod(c0p, k0, view.moduli), k1, ct.level, ct.scale)

    # -- keyswitch (coarse-grained baseline; Fig. 2(A)) ------------------------

    def key_switch(self, d, evk: EvalKey, ell: int):
        """d: (ell+1, N) eval-domain poly under s'; returns (k0, k1) under s.
        One compiled program."""
        with span("ckks.key_switch"):
            return _key_switch_program(self.params, self.datapath, ell,
                                       d, evk.k0, evk.k1)

    def _key_switch_body(self, d, k0, k1, ell: int):
        p = self.params
        bases = self.tools.digit_bases(ell)
        full = bases[0][2]
        fview = self.basis(full)
        pos = {g: i for i, g in enumerate(full)}
        rows = np.array(full)
        # the digits partition d's limbs and the transforms work limb by
        # limb: one iNTT gives every digit's coefficients, one NTT every
        # digit's extension
        coeff = self._intt(d, self.main_basis(ell))
        exts = [self.tools.mod_up(coeff[own[0]: own[-1] + 1], own, gen)
                for own, gen, _ in bases]
        gens = [i for _, gen, _ in bases for i in gen]
        ext_evals = jnp.split(self._ntt(jnp.concatenate(exts), self.basis(gens)),
                              np.cumsum([len(e) for e in exts])[:-1])
        acc0 = jnp.zeros((len(full), p.N), dtype=jnp.uint32)
        acc1 = jnp.zeros_like(acc0)
        for j, ((own, gen, _), ext_eval) in enumerate(zip(bases, ext_evals)):
            dig_eval = d[own[0]: own[-1] + 1]
            # assemble digit over full basis (reuse own eval limbs directly)
            xfull = jnp.zeros((len(full), p.N), dtype=jnp.uint32)
            xfull = xfull.at[np.array([pos[i] for i in own])].set(dig_eval)
            xfull = xfull.at[np.array([pos[i] for i in gen])].set(ext_eval)
            acc0 = mm.addmod(acc0, mm.mulmod(xfull, k0[j][rows], fview.moduli),
                             fview.moduli)
            acc1 = mm.addmod(acc1, mm.mulmod(xfull, k1[j][rows], fview.moduli),
                             fview.moduli)
        ks = self._mod_down_eval(jnp.stack([acc0, acc1]), ell)
        return ks[0], ks[1]

    def _mod_down_eval(self, x_full, ell: int, drop_last: bool = False,
                       datapath: Optional[str] = None):
        """ModDown from Q_ℓ ∪ P back to Q_ℓ (or Q_{ℓ-1} when drop_last — the
        paper's merged ModDown+Rescale), eval domain in/out. x_full is
        (..., M, N); the fused pallas path takes one (M, N) poly.

        datapath overrides the engine knob per call; "pallas" + drop_last
        runs the whole iNTT→BaseConv→NTT→sub→·P⁻¹ tail as two fused
        pallas_calls (kernels/basechange.py), bit-exact vs the XLA chain."""
        dp = self.datapath if datapath is None else datapath
        if dp == "pallas" and drop_last:
            tabs = self.fused_moddown_tables(ell)
            return basechange.moddown_fused(x_full, tabs,
                                            interpret=ops._interp())
        p = self.params
        spec = tuple(range(p.num_main, p.num_total))
        P = spec + ((ell,) if drop_last else ())
        Q = tuple(range(ell)) if drop_last else tuple(range(ell + 1))
        nq = ell + 1
        if drop_last:  # fold q_ell into the dropped basis (merged ModDown+Rescale)
            x_p_eval = jnp.concatenate([x_full[..., nq:, :],
                                        x_full[..., ell:ell + 1, :]], axis=-2)
        else:
            x_p_eval = x_full[..., nq:, :]
        return self._drop_basis(x_full[..., :len(Q), :], x_p_eval, P, Q)

    def _drop_basis(self, x_q, x_p, P: tuple, Q: tuple):
        """(x − [x]_P)·P⁻¹ over Q, eval domain in and out, from x's residues
        x_q (..., |Q|, N) over Q and x_p (..., |P|, N) over P. The transforms
        work limb by limb, so the rows of all leading entries share one iNTT
        and one NTT."""
        lead, N = x_p.shape[:-2], x_p.shape[-1]
        n = math.prod(lead)
        coeff = self._intt(x_p.reshape(-1, N), self.basis(P * n))
        conv = jnp.concatenate([self.tools.base_conv(c, P, Q)
                                for c in jnp.split(coeff, n)])
        conv_eval = self._ntt(conv, self.basis(Q * n)).reshape(x_q.shape)
        q = self.basis(Q).moduli
        p_inv = self.tools._moddown_tables(P, Q)
        return mm.mulmod(mm.submod(x_q, conv_eval, q), p_inv, q)

    # -- rescale ---------------------------------------------------------------

    def rescale(self, ct: Ciphertext) -> Ciphertext:
        """Divide by q_ℓ, dropping one level (eval-domain single-limb path).
        One compiled program."""
        with span("ckks.rescale"):
            ell = ct.level
            q_ell = self.ctx.moduli_host[ell]
            c0, c1 = _rescale_program(self.params, self.datapath, ell,
                                      ct.c0, ct.c1)
            return Ciphertext(c0, c1, ell - 1, ct.scale / q_ell)

    def _rescale_poly(self, x, ell: int):
        """x (..., ell+1, N) -> (..., ell, N): the single-limb ModDown."""
        return self._drop_basis(x[..., :ell, :], x[..., ell:ell + 1, :],
                                (ell,), tuple(range(ell)))


# ---------------------------------------------------------------------------
# compiled programs of the homomorphic ops
# ---------------------------------------------------------------------------
# One XLA program per (params, datapath, level), shared by every engine with
# equal params. Limbs and key rows are arguments (keygen and
# HEContext.invalidate() swap keys without a retrace); the level, and the
# bases it fixes, are static; scales stay on the host. The bodies carry no
# span: an annotation inside a traced body fires at trace time only.


@functools.lru_cache(maxsize=None)
def _engine(params: HEParams, datapath: str) -> CkksEngine:
    """The engine whose methods trace the programs for ``params``."""
    return CkksEngine(params, datapath)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _mult_program(params, datapath, ell, a0, a1, b0, b1, k0, k1):
    return _engine(params, datapath)._mult_body(a0, a1, b0, b1, k0, k1, ell)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _key_switch_program(params, datapath, ell, d, k0, k1):
    return _engine(params, datapath)._key_switch_body(d, k0, k1, ell)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _rescale_program(params, datapath, ell, c0, c1):
    out = _engine(params, datapath)._rescale_poly(jnp.stack([c0, c1]), ell)
    return out[0], out[1]


@functools.partial(jax.jit, static_argnums=(0, 1), donate_argnums=(2, 3))
def _rotation_keys_program(params, datapath, k0, k1, start, s_eval, w, a, e,
                           perms):
    """Rows ``start ..`` of the tiled rotation-key tables (donated, written
    in place): for each key i and digit j, k1 = a and k0 = e − a·s + W_j·ψ(s),
    with ψ the key's eval-domain permutation ``perms[i]``."""
    eng = _engine(params, datapath)
    view = eng.basis(list(range(params.num_total)))
    q = view.moduli
    K, nbeta, M, N = a.shape
    e_eval = jax.vmap(lambda x: eng._ntt(x, view))(
        e.reshape(K * nbeta, M, N)).reshape(K, nbeta, M, N)
    s_rot = jnp.moveaxis(s_eval[:, perms], 1, 0)[:, None]    # (K, 1, M, N)
    w_sp = mm.mulmod(s_rot, w, q)
    new0 = mm.addmod(mm.submod(e_eval, mm.mulmod(a, s_eval, q), q), w_sp, q)
    zero = jnp.zeros((), start.dtype)
    at = (start, zero, zero, zero, zero)
    tile = lambda x: x.reshape(x.shape[:3] + k0.shape[3:])
    return (jax.lax.dynamic_update_slice(k0, tile(new0), at),
            jax.lax.dynamic_update_slice(k1, tile(a), at))


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _table_to_mont(k0, k1, q32, qneg, r2):
    """Both (S, β, M, R, C) key tables to the Montgomery domain, in place."""
    c = lambda x: x[:, :, None]                  # (M, 1) -> (M, 1, 1)
    return (mm.to_mont(k0, c(q32), c(qneg), c(r2)),
            mm.to_mont(k1, c(q32), c(qneg), c(r2)))


@functools.partial(jax.jit, static_argnums=(0, 1))
def _add_program(params, ell, a0, a1, b0, b1):
    q = get_context(params).moduli[: ell + 1]
    return mm.addmod(a0, b0, q), mm.addmod(a1, b1, q)
