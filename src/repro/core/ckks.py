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
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import automorph, modmath as mm, ntt
from repro.core.params import HEParams, PrimeContext, get_context
from repro.core.rns import RnsTools
from repro.core.spans import span
from repro.kernels import basechange, ops


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
class Keys:
    s_eval: jnp.ndarray                 # (M, N) secret over full basis
    evk_mult: EvalKey
    rot: dict[int, EvalKey]             # step -> key
    galois: dict[int, EvalKey]          # galois element -> key (same objects)


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
            s2_int = None  # s^2 handled in eval domain below

            # s^2 over full basis (eval-domain product)
            view = self.basis(full)
            s2_eval = mm.mulmod(s_eval, s_eval, view.moduli)

            evk_mult = self._make_evk(rng, s_eval, s2_eval)
            rot, galois = {}, {}
            for r in rot_steps:
                g = automorph.galois_elt_rot(r, p.N)
                if g in galois:
                    rot[r] = galois[g]
                    continue
                s_rot = automorph.apply_eval(s_eval, p.N, g)
                k = self._make_evk(rng, s_eval, s_rot)
                rot[r] = k
                galois[g] = k
            return Keys(s_eval=s_eval, evk_mult=evk_mult, rot=rot, galois=galois)

    def _make_evk(self, rng: np.random.Generator, s_eval, sprime_eval) -> EvalKey:
        """evk_j = (-a_j s + e_j + W_j s', a_j) over the full basis, where
        W_j = P · [ D̂_j · (D̂_j^{-1} mod D_j) ]  (gadget factor, paper §II-B3)."""
        p = self.params
        full = list(range(p.num_total))
        view = self.basis(full)
        Pprod = 1
        for i in range(p.num_main, p.num_total):
            Pprod *= self.ctx.moduli_host[i]
        QL = 1
        for i in range(p.num_main):
            QL *= self.ctx.moduli_host[i]

        k0s, k1s = [], []
        for (st, en) in p.digits_at_level(p.L):
            Dj = 1
            for i in range(st, en):
                Dj *= self.ctx.moduli_host[i]
            hatDj = QL // Dj
            # NB: D_j is composite — use the general modular inverse, not Fermat.
            w_int = Pprod * hatDj * pow(hatDj % Dj, -1, Dj)
            w_res = np.array(
                [w_int % self.ctx.moduli_host[i] for i in full], dtype=np.uint64
            )[:, None]
            a = self._uniform_poly(rng, full)
            e_eval = self._small_poly_eval(
                np.round(rng.normal(0, 3.2, size=p.N)).astype(np.int64), full)
            w_sp = mm.mulmod(sprime_eval, jnp.asarray(w_res).astype(jnp.uint32),
                             view.moduli)
            k0 = mm.addmod(
                mm.submod(e_eval, mm.mulmod(a, s_eval, view.moduli), view.moduli),
                w_sp, view.moduli)
            k0s.append(k0)
            k1s.append(a)
        return EvalKey(k0=jnp.stack(k0s), k1=jnp.stack(k1s))

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
            view = self.main_basis(a.level)
            return Ciphertext(mm.addmod(a.c0, b.c0, view.moduli),
                              mm.addmod(a.c1, b.c1, view.moduli),
                              a.level, max(a.scale, b.scale))

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
        mirroring paper Algorithm 1/2 structure)."""
        with span("ckks.mult"):
            assert a.level == b.level
            ell = a.level
            view = self.main_basis(ell)
            d0 = mm.mulmod(a.c0, b.c0, view.moduli)
            d1 = mm.addmod(mm.mulmod(a.c0, b.c1, view.moduli),
                           mm.mulmod(a.c1, b.c0, view.moduli), view.moduli)
            d2 = mm.mulmod(a.c1, b.c1, view.moduli)
            k0, k1 = self.key_switch(d2, keys.evk_mult, ell)
            return Ciphertext(mm.addmod(d0, k0, view.moduli),
                              mm.addmod(d1, k1, view.moduli),
                              ell, a.scale * b.scale)

    def rotate(self, ct: Ciphertext, r: int, keys: Keys) -> Ciphertext:
        """Rot(ct, r): circular left rotation of slots by r."""
        p = self.params
        g = automorph.galois_elt_rot(r, p.N)
        key = keys.galois.get(g) or keys.rot[r]
        c0p = automorph.apply_eval(ct.c0, p.N, g)
        c1p = automorph.apply_eval(ct.c1, p.N, g)
        k0, k1 = self.key_switch(c1p, key, ct.level)
        view = self.main_basis(ct.level)
        return Ciphertext(mm.addmod(c0p, k0, view.moduli), k1, ct.level, ct.scale)

    # -- keyswitch (coarse-grained baseline; Fig. 2(A)) ------------------------

    def key_switch(self, d, evk: EvalKey, ell: int):
        """d: (ell+1, N) eval-domain poly under s'; returns (k0, k1) under s."""
        with span("ckks.key_switch"):
            p = self.params
            bases = self.tools.digit_bases(ell)
            full = bases[0][2]
            fview = self.basis(full)
            acc0 = jnp.zeros((len(full), p.N), dtype=jnp.uint32)
            acc1 = jnp.zeros_like(acc0)
            for j, (own, gen, _) in enumerate(bases):
                dig_eval = d[own[0]: own[-1] + 1]
                coeff = self._intt(dig_eval, self.basis(own))
                ext = self.tools.mod_up(coeff, own, gen)
                ext_eval = self._ntt(ext, self.basis(gen))
                # assemble digit over full basis (reuse own eval limbs directly)
                pos = {g: i for i, g in enumerate(full)}
                xfull = jnp.zeros((len(full), p.N), dtype=jnp.uint32)
                xfull = xfull.at[np.array([pos[i] for i in own])].set(dig_eval)
                xfull = xfull.at[np.array([pos[i] for i in gen])].set(ext_eval)
                rows = np.array(full)
                acc0 = mm.addmod(acc0, mm.mulmod(xfull, evk.k0[j][rows], fview.moduli),
                                 fview.moduli)
                acc1 = mm.addmod(acc1, mm.mulmod(xfull, evk.k1[j][rows], fview.moduli),
                                 fview.moduli)
            return self._mod_down_eval(acc0, ell), self._mod_down_eval(acc1, ell)

    def _mod_down_eval(self, x_full, ell: int, drop_last: bool = False,
                       datapath: Optional[str] = None):
        """ModDown from Q_ℓ ∪ P back to Q_ℓ (or Q_{ℓ-1} when drop_last — the
        paper's merged ModDown+Rescale), eval domain in/out.

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
            x_p_eval = jnp.concatenate([x_full[nq:], x_full[ell:ell + 1]], axis=0)
        else:
            x_p_eval = x_full[nq:]
        # P-part -> coeff -> baseconv -> eval over Q
        x_p_coeff = self._intt(x_p_eval, self.basis(P))
        conv = self.tools.base_conv(x_p_coeff, P, Q)
        qv = self.basis(Q)
        conv_eval = self._ntt(conv, qv)
        p_inv = self.tools._moddown_tables(P, Q)
        return mm.mulmod(mm.submod(x_full[: len(Q)], conv_eval, qv.moduli),
                         p_inv, qv.moduli)

    # -- rescale ---------------------------------------------------------------

    def rescale(self, ct: Ciphertext) -> Ciphertext:
        """Divide by q_ℓ, dropping one level (eval-domain single-limb path)."""
        with span("ckks.rescale"):
            ell = ct.level
            q_ell = self.ctx.moduli_host[ell]
            c0 = self._rescale_poly(ct.c0, ell)
            c1 = self._rescale_poly(ct.c1, ell)
            return Ciphertext(c0, c1, ell - 1, ct.scale / q_ell)

    def _rescale_poly(self, x, ell: int):
        last_coeff = self._intt(x[ell:ell + 1], self.basis((ell,)))
        conv = self.tools.base_conv(last_coeff, (ell,), tuple(range(ell)))
        qv = self.main_basis(ell - 1)
        conv_eval = self._ntt(conv, qv)
        p_inv = self.tools._moddown_tables((ell,), tuple(range(ell)))
        return mm.mulmod(mm.submod(x[:ell], conv_eval, qv.moduli), p_inv, qv.moduli)
