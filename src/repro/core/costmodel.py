"""Paper §III cost model: on-chip memory requirement for HE MM (Eqs. 16–24),
operation counts (Table I), and off-chip/HBM traffic estimates.

Two word models:
 * ``paper``  — B_coeff = logq_paper/8 bytes per coefficient (54-bit FPGA
   words); reproduces the §III-B3 numbers (0.43/3.6 MB Set-A, 6.7/61 MB Set-B,
   27/255 MB Set-C, Eq. 24 ≈ 29 MB).
 * ``tpu``    — 4-byte u32 words with ~2× the limb count for equal log Q
   (core/params.py word-size adaptation); drives VMEM BlockSpec sizing and
   the roofline memory term.
"""
from __future__ import annotations

import dataclasses

from repro.core.params import HEParams
from repro.core.hemm import diag_count_formulas

MB = float(1 << 20)

# Per-core TPU VMEM (the FPGA scratchpad analogue; pallas guide: ~16 MB/core).
VMEM_BYTES = 16.0 * MB

#: Fraction of per-core VMEM the fused-HLT working set may claim
#: (dimensionless, in (0, 1]; default 0.75 → 12 of 16 MB on v5e-class cores).
#: Derivation: the Pallas runtime double-buffers every streamed BlockSpec
#: operand (one tile in flight while the previous one computes), so the
#: per-grid-step working set of ``pick_rotation_chunk``'s formula can
#: transiently double for the streamed rows; 0.75 of VMEM for the steady-state
#: set leaves the remaining quarter for that second in-flight tile plus the
#: compiler's own spills.  It is a NAMED budget knob (was a hard-coded 0.75
#: guess buried in two signatures): the default of
#: ``HEContext(vmem_headroom=...)``, threaded into every HLTPlan, so
#: tests/benchmarks can pin chunk choices (e.g. ``rotation_chunk=2``)
#: explicitly and see which headroom produced a plan.  Replace with a
#: VMEM-measured value once the kernels run with ``interpret=False`` on
#: hardware (ROADMAP).
VMEM_HEADROOM = 0.75

#: Cost multiplier for cross-device (ICI) bytes relative to local HBM bytes
#: (dimensionless; used as HBM-equivalent-bytes per collective byte).
#: Derivation: a v5e-class core streams ~0.8 TB/s from HBM but ~0.1 TB/s
#: per ICI link direction, so moving one byte across the interconnect costs
#: roughly the time of eight local bytes — ``select_schedule`` charges the
#: sharded schedule's BaseConv psum at this rate when comparing per-device
#: traffic.  The ratio is stable across recent TPU generations (v4/v5p are
#: within ~2x); refine per-topology when a multi-host mesh is measured.
ICI_PENALTY = 8.0

# Representative per-HLT diagonal count when the caller doesn't know d yet
# (σ of a 16×16 single-ciphertext MM tile: 2·16−1).
_DEFAULT_D = 31


def pick_rotation_chunk(params: "HEParams", nbeta: int | None = None,
                        vmem_bytes: float = VMEM_BYTES,
                        headroom: float | None = None) -> int:
    """Largest rotation chunk whose fused-HLT per-grid-step working set
    (kernels/fused_hlt.py docstring) fits the per-core VMEM budget.

    The per-step row count is ``kernels/fused_hlt.working_set_rows`` (P·c0,
    P·c1 and two accumulator rows resident; per rotation β rotated digit
    rows, a rotated P·c0 row, one diagonal row and 2β rot-key rows; all
    double-buffered). Each row is N u32 coefficients (4 bytes).
    """
    nbeta = params.beta if nbeta is None else nbeta
    headroom = VMEM_HEADROOM if headroom is None else headroom
    from repro.kernels.fused_hlt import working_set_rows
    row = 4.0 * params.N
    budget_rows = headroom * vmem_bytes / row
    resident = working_set_rows(nbeta, 0)
    per_rotation = working_set_rows(nbeta, 1) - resident
    return max(1, int((budget_rows - resident) // per_rotation))


def fused_stage_working_sets(params: "HEParams", *, nbeta: int, chunk: int,
                             level: int | None = None) -> dict:
    """Per-grid-step working-set bytes of EACH fused pipeline stage.

    ``rot`` is the rotation-loop kernel (``kernels/fused_hlt.
    working_set_rows``, the chunk-dependent term ``pick_rotation_chunk``
    inverts); ``hoist`` / ``moddown`` are the fused base-change stages
    (``kernels/basechange.py`` footprint helpers) — chunk-independent, so
    they bound the budget but never the chunk pick.  ``level`` sizes the
    hoist's digit width α and the ModDown drop-basis |P∪{q_ℓ}| (defaults
    to the top level).
    """
    from repro.kernels.basechange import (hoist_working_set_rows,
                                          moddown_working_set_rows)
    from repro.kernels.fused_hlt import working_set_rows
    level = params.L if level is None else level
    alpha = min(params.alpha, level + 1)
    row = 4 * params.N
    return {
        "rot": int(working_set_rows(nbeta, chunk) * row),
        "hoist": int(hoist_working_set_rows(nbeta, alpha, params.logN) * row),
        "moddown": int(moddown_working_set_rows(params.k + 1, params.logN)
                       * row),
    }


def fused_working_set_bytes(params: "HEParams", *, nbeta: int,
                            chunk: int, level: int | None = None) -> int:
    """Peak per-grid-step working set of the fused datapath: the MAX over
    the rotation-loop / hoist / ModDown stage footprints
    (``fused_stage_working_sets``).  The verifier's VMEM pass
    (``repro.analysis.vmem``, VM001) fails a compile whose explicit
    ``rotation_chunk`` pushes this past ``vmem_headroom × VMEM_BYTES``;
    under ``schedule="sharded"`` the same bound applies per model rank
    (the kernel sees the limb-row shard, so the per-row set is unchanged).
    """
    return max(fused_stage_working_sets(
        params, nbeta=nbeta, chunk=chunk, level=level).values())


def sharded_collective_bytes(params: "HEParams", *, n_model: int = 1,
                             ctb: int = 1) -> int:
    """Predicted per-execution collective traffic of schedule="sharded".

    The merged ModDown+Rescale BaseConv is the program's ONLY collective
    (core/hlt_dist.py): a psum of the (k+1) dropped limb rows for both output
    polys of every ciphertext in the batch.  A ring all-reduce moves
    ~2·(n−1)/n of the payload per device.
    """
    if n_model <= 1:
        return 0
    payload = 2 * (params.k + 1) * params.N * 4 * max(1, ctb)
    return int(2 * (n_model - 1) / n_model * payload)


def hlt_operand_bytes(params: "HEParams", *, d: int,
                      nbeta: int | None = None,
                      n_limbs_ext: int | None = None) -> float:
    """Rotation-loop operand footprint of one HLT (keys + diagonals): the
    traffic limb-sharding divides across the ``model`` axis."""
    nbeta = params.beta if nbeta is None else nbeta
    m = (params.L + 1 + params.k) if n_limbs_ext is None else n_limbs_ext
    return d * (2 * nbeta + 1) * m * 4.0 * params.N


def hlt_hoist_bytes(params: "HEParams", nbeta: int | None = None,
                    n_limbs_ext: int | None = None) -> float:
    """Bytes of ONE hoisting product (β digit expansions + raised c0/c1).

    This is the unit the ct-slot dedup saves: the fused-sharded program
    hoists it once per UNIQUE input ciphertext, the pre-dedup program once
    per batch ELEMENT.
    """
    nbeta = params.beta if nbeta is None else nbeta
    m = (params.L + 1 + params.k) if n_limbs_ext is None else n_limbs_ext
    return (nbeta + 2) * m * 4.0 * params.N


def select_schedule(params: "HEParams", nbeta: int | None = None,
                    vmem_bytes: float = VMEM_BYTES,
                    headroom: float | None = None, *,
                    n_model: int = 1, n_ct: int = 1,
                    d: int | None = None, ctb: int | None = None,
                    n_uniq: int | None = None,
                    dedup_hoist: bool = True) -> str:
    """Cost-model schedule pick for compile_hlt/compile_hemm (schedule=None).

    Single device — the fused Pallas datapath needs its minimal per-grid-step
    working set (``working_set_rows(nbeta, 1)``, the chunk=1 residency of
    pick_rotation_chunk's formula) to fit the per-core VMEM budget.  When it
    does (every shipped parameter set), the fused kernel is the schedule;
    when a hypothetical parameter set overflows even chunk=1, fall back to
    the u64 limb-outer reference ("mo").

    Multi-device mesh (``n_model``-way limb sharding × ``n_ct``-way
    ciphertext-batch sharding, from HEContext's mesh) — compare PER-DEVICE
    traffic.  With ``rot = hlt_operand_bytes(d)`` (keys+diagonals of one HLT),
    ``hoist = hlt_hoist_bytes()`` (one hoisting product), ``B`` the batch,
    ``B_pad`` the batch padded to the ct axis, ``U`` the unique-input count
    (``n_uniq``; ``B`` when unknown) and ``coll = sharded_collective_bytes``,
    the decision rule is the readable inequality::

        rot·B_pad/(n_model·n_ct) + hoist·U/n_model + ICI_PENALTY·coll
            <  rot·B + hoist·U                       ->  "sharded"

    i.e. sharded wins when the rotation-loop bytes saved by spreading the
    batch over the mesh exceed the ICI-penalized BaseConv psum.  Both sides
    dedup the hoist to U products — the fused-sharded datapath by ct slot,
    the single-device batched kernel by object identity — and each model
    rank materializes only its ``1/n_model`` share of the hoisted rows
    (same per-device convention as ``hlt_stage_costs``).
    ``dedup_hoist=False`` models the pre-dedup program (``sharded_xla``),
    which re-hoists every batch element: its left side pays
    ``hoist·(B_pad/n_ct)/n_model`` instead of ``hoist·U/n_model``, so
    heavily aliased batches (hemm Step-2's 2 unique inputs across 2·l
    elements) can flip AWAY from sharded — the replicated-hoist penalty the
    fusion removed.

    Large N / many limbs / big d / batches that span the ct axis flip to
    "sharded"; one device — or work too small to amortize the collective —
    keeps the single-device pick.
    """
    nbeta = params.beta if nbeta is None else nbeta
    headroom = VMEM_HEADROOM if headroom is None else headroom
    from repro.kernels.fused_hlt import working_set_rows
    min_working_set = working_set_rows(nbeta, 1) * 4.0 * params.N
    single = "pallas" if min_working_set <= headroom * vmem_bytes else "mo"
    n_model, n_ct = max(1, n_model), max(1, n_ct)
    if n_model * n_ct <= 1 or single != "pallas":
        # "sharded" now drives the fused kernel per rank, and limb sharding
        # splits the ROWS, not the per-row working set — if even chunk=1
        # overflows VMEM on one device it overflows on every rank too
        return single
    single_dev, shard_dev = _hlt_device_costs(
        params, nbeta=nbeta, d=d, ctb=ctb, n_uniq=n_uniq,
        n_model=n_model, n_ct=n_ct, dedup_hoist=dedup_hoist)
    return "sharded" if shard_dev < single_dev else single


def _hlt_device_costs(params: "HEParams", *, nbeta: int, d: int | None,
                      ctb: int | None, n_uniq: int | None,
                      n_model: int, n_ct: int,
                      dedup_hoist: bool = True) -> tuple[float, float]:
    """(single-device bytes, per-device sharded bytes) of one HLT launch —
    the two sides of ``select_schedule``'s inequality, factored out so
    ``select_chain_schedules`` prices hops with the SAME terms."""
    d_eff = _DEFAULT_D if d is None else d
    ctb_eff = max(1, ctb or 1)
    uniq = ctb_eff if n_uniq is None else max(1, min(n_uniq, ctb_eff))
    b_pad = -(-ctb_eff // n_ct) * n_ct          # slot/zero-ct padded batch
    operand = hlt_operand_bytes(params, d=d_eff, nbeta=nbeta)
    hoist = hlt_hoist_bytes(params, nbeta=nbeta)
    single_dev = operand * ctb_eff + hoist * uniq
    shard_hoist = hoist * (uniq if dedup_hoist else b_pad / n_ct) / n_model
    shard_dev = (operand * b_pad / (n_model * n_ct) + shard_hoist
                 + ICI_PENALTY * sharded_collective_bytes(
                     params, n_model=n_model, ctb=b_pad // n_ct))
    return single_dev, shard_dev


def chain_boundary_bytes(params: "HEParams", *,
                         level: int | None = None) -> float:
    """ICI-penalized bytes to re-lay a chained ciphertext out when adjacent
    hops change residency class (single-device ↔ limb-sharded): both (c0,c1)
    limb tensors at the boundary level cross the interconnect once, weighted
    with the same ``ICI_PENALTY`` as the in-schedule collective."""
    n_limbs = (params.L if level is None else level) + 1
    return ICI_PENALTY * 2.0 * n_limbs * 4.0 * params.N


def select_chain_schedules(params: "HEParams", hops, *,
                           vmem_bytes: float = VMEM_BYTES,
                           headroom: float | None = None,
                           n_model: int = 1, n_ct: int = 1) -> tuple:
    """Joint per-hop schedule pick for ``compile_hemm_chain`` (DESIGN.md §8).

    ``hops`` is a sequence of per-hop dicts: ``d`` (rotation count of the
    hop's widest HLT), ``ctb`` (HLT batch — hemm Step-2's 2·l), ``n_uniq``
    (unique inputs — 2), ``nbeta`` (digit count at the hop's input level)
    and ``level`` (the hop's input level, pricing its boundary ciphertext).

    k independent ``select_schedule`` calls ignore that hop h's output
    layout IS hop h+1's input layout: flipping residency class between hops
    (single-device ↔ sharded) moves the chained ciphertext across the
    interconnect once per flip (``chain_boundary_bytes``).  This pass runs a
    two-state dynamic program over the hop sequence — per-hop device bytes
    from ``_hlt_device_costs`` (the exact ``select_schedule`` terms) plus
    the transition penalty on class changes — so a middle hop that would
    flip in isolation stays put when the two re-layouts cost more than the
    flip saves.  With one device, or a single hop, the result degenerates
    to per-hop ``select_schedule`` picks.
    """
    from repro.kernels.fused_hlt import working_set_rows
    headroom = VMEM_HEADROOM if headroom is None else headroom
    n_model, n_ct = max(1, n_model), max(1, n_ct)
    row = 4.0 * params.N
    k = len(hops)
    assert k >= 1
    INF = float("inf")
    singles, costs = [], []
    for hop in hops:
        nbeta = hop.get("nbeta") or params.beta
        min_ws = working_set_rows(nbeta, 1) * row
        sname = "pallas" if min_ws <= headroom * vmem_bytes else "mo"
        singles.append(sname)
        single_dev, shard_dev = _hlt_device_costs(
            params, nbeta=nbeta, d=hop.get("d"), ctb=hop.get("ctb"),
            n_uniq=hop.get("n_uniq"), n_model=n_model, n_ct=n_ct)
        if n_model * n_ct <= 1 or sname != "pallas":
            shard_dev = INF               # sharded not viable for this hop
        costs.append((single_dev, shard_dev))
    # DP over residency classes: 0 = single-device, 1 = sharded.
    best = [list(costs[0])] + [[INF, INF] for _ in range(k - 1)]
    back = [[0, 0] for _ in range(k)]
    for h in range(1, k):
        bnd = chain_boundary_bytes(params, level=hops[h].get("level"))
        for c in (0, 1):
            for p in (0, 1):
                t = best[h - 1][p] + costs[h][c] + (bnd if p != c else 0.0)
                if t < best[h][c]:
                    best[h][c], back[h][c] = t, p
    c = 0 if best[k - 1][0] <= best[k - 1][1] else 1
    path = [c]
    for h in range(k - 1, 0, -1):
        c = back[h][c]
        path.append(c)
    path.reverse()
    return tuple("sharded" if cls else singles[h] for h, cls in enumerate(path))


def hlt_stage_costs(params: "HEParams", *, d: int, d_pad: int, nbeta: int,
                    chunk: int, n_limbs_ext: int, n_model: int = 1,
                    ctb: int = 1, n_hoist: int | None = None) -> dict:
    """Per-stage byte / rotation / collective counts of ONE HLT at a given
    compile point (u32 word model) — attached to HLTPlan for inspection.

    bytes = operand traffic the stage streams through VMEM per ciphertext
    (per DEVICE when the limb axis is n_model-way sharded); rotations = real
    (non-padding) rotations; collective_bytes = predicted cross-device
    traffic (only the merged ModDown+Rescale BaseConv moves data between
    ranks — ModUp reads the limb-replicated inputs, everything else is
    limb-local).

    ``n_hoist`` is the number of hoisting products the execution actually
    computes (the ct-slot dedup: unique input ciphertexts, not batch
    elements; default = ``ctb``, the no-aliasing assumption).  The hoist
    stage's per-ciphertext bytes are amortized by ``n_hoist / ctb`` — the
    replicated-hoist term that the fused-sharded datapath drops.
    """
    row = 4 * params.N
    m = n_limbs_ext
    nm = max(1, n_model)
    m_loc = -(-m // nm)                  # per-device rows (padded shard)
    nh = ctb if n_hoist is None else max(1, min(n_hoist, ctb))
    coll = sharded_collective_bytes(params, n_model=nm, ctb=ctb)
    return {
        "hoist": {                       # Decomp/ModUp digits + raised c0/c1
            "bytes": int(hlt_hoist_bytes(params, nbeta=nbeta,
                                         n_limbs_ext=m_loc)) * nh
            // max(1, ctb),
            "rotations": 0, "collective_bytes": 0},
        "automorph": {                   # per-rotation perm-table gather
            "bytes": d_pad * (1 + nbeta) * m_loc * row, "rotations": d,
            "collective_bytes": 0},
        "keyip": {                       # 2β rot-key rows per rotation
            "bytes": 2 * nbeta * d_pad * m_loc * row, "rotations": d,
            "collective_bytes": 0},
        "diagip": {                      # one diagonal row per rotation
            "bytes": d_pad * m_loc * row, "rotations": d,
            "collective_bytes": 0},
        "moddown": {                     # merged ModDown+Rescale in/out
            "bytes": 2 * m_loc * row, "rotations": 0,
            "collective_bytes": coll},
        "chunk": chunk,
    }


def serve_amortization(params: "HEParams", *, nbeta: int | None = None,
                       n_calls: int, n_tiles: int, n_uniq_tiles: int,
                       launches: int, launches_naive: int) -> dict:
    """Per-decode-step amortization stats for the cross-request HE batcher.

    ``n_calls`` is how many in-flight requests' secure-layer calls the step
    folded together, ``n_tiles`` the activation tiles they submitted and
    ``n_uniq_tiles`` the unique ciphertexts after shared-prompt aliasing
    (``n_tiles - n_uniq_tiles`` hoisting products skipped — each worth
    ``hlt_hoist_bytes``).  ``launches`` / ``launches_naive`` come from
    BlockMMPlan: what the batched step issued vs what one program per
    request-tile-pair would have.  The serving layer attaches this dict to
    every step's stats and BENCH_serve.json aggregates it.
    """
    hoist = hlt_hoist_bytes(params, nbeta=nbeta)
    n_uniq_tiles = max(0, min(n_uniq_tiles, n_tiles))
    return {
        "n_calls": int(n_calls),
        "launches": int(launches),
        "launches_naive": int(launches_naive),
        "launch_amortization_x": launches_naive / max(1, launches),
        "hoist_bytes": int(hoist * n_uniq_tiles),
        "hoist_bytes_naive": int(hoist * n_tiles),
        "hoist_dedup_saved_bytes": int(hoist * (n_tiles - n_uniq_tiles)),
    }


@dataclasses.dataclass(frozen=True)
class CostModel:
    """Paper §III data sizes, on-chip memory requirements and traffic.

    ``word_model="paper"`` uses 54-bit FPGA words and reproduces the paper's
    §III-B3 megabyte numbers; ``"tpu"`` uses 4-byte u32 words (the word-size
    adaptation, DESIGN.md §3) for VMEM sizing and roofline math.
    """

    params: HEParams
    word_model: str = "paper"     # "paper" | "tpu"

    # -- data sizes (§III-B1) ------------------------------------------------

    @property
    def bytes_per_coeff(self) -> float:
        """Bytes per polynomial coefficient under the word model."""
        if self.word_model == "paper":
            return self.params.logq_paper / 8.0
        return 4.0

    @property
    def b_limb(self) -> float:
        """Bytes of one RNS limb row (Eq. 16): N coefficients."""
        return self.params.N * self.bytes_per_coeff

    def b_ct(self, nlimbs: int | None = None) -> float:
        """Eq. 17 (at full level by default): 2 polys × limbs × limb bytes."""
        n = self.params.num_main if nlimbs is None else nlimbs
        return 2.0 * n * self.b_limb

    def b_evk(self, nlimbs_ext: int | None = None) -> float:
        """Eq. 18."""
        p = self.params
        n = (p.L + p.k + 1) if nlimbs_ext is None else nlimbs_ext
        return 2.0 * p.beta * n * self.b_limb

    # -- on-chip memory requirement (§III-B2) ---------------------------------

    @property
    def m_keyswitch(self) -> float:
        """Eq. 19: output Ct + β-digit extended expansion of one poly."""
        p = self.params
        return self.b_ct() + 0.5 * p.beta * self.b_ct(p.L + p.k + 1)

    @property
    def m_rot(self) -> float:
        """Eq. 20: + original (a,b) and ψ(a)."""
        return self.m_keyswitch + 1.5 * self.b_ct()

    @property
    def m_hlt_s1(self) -> float:
        """Eq. 21: one input buffer + two output buffers (+ in-place MAC)."""
        return self.m_rot + 3.0 * self.b_ct()

    @property
    def m_hlt_s2(self) -> float:
        """Eq. 22: two input buffers (A^(0), B^(0) reused across iterations)."""
        return self.m_rot + 4.0 * self.b_ct()

    @property
    def m_hemm(self) -> float:
        """Eq. 23: + accumulator Ct_AB."""
        return self.m_hlt_s2 + self.b_ct()

    @property
    def m_mo_hlt(self) -> float:
        """Eq. 24: MO-HLT stores one Ct + (β+1) intermediate limbs."""
        return self.b_ct() + (self.params.beta + 1) * self.b_limb

    # -- traffic model ---------------------------------------------------------

    def baseline_hlt_traffic(self, d: int, sram_bytes: float) -> float:
        """Off-chip Ct traffic of the coarse-grained HLT (Fig. 2(A)) when the
        working set (m_hlt_s2) exceeds on-chip memory: every Rot spills the
        extended Ct between sub-operations (read+write per KeySwitch stage:
        Decomp/ModUp out, KeyIP in+out, ModDown in+out)."""
        if self.m_hemm <= sram_bytes:
            return 2.0 * self.b_ct()          # just input + output
        p = self.params
        ext = 0.5 * p.beta * self.b_ct(p.L + p.k + 1)
        per_rot = 2.0 * (ext + self.b_ct(p.L + p.k + 1))   # spill + refill
        return 2.0 * self.b_ct() + d * per_rot

    # d is unused by design — MO fuses all d rotations on-chip; the signature
    # mirrors baseline_hlt_traffic so the two are interchangeable.
    def mo_hlt_traffic(self, d: int, sram_bytes: float) -> float:  # noqa: ARG002
        """MO-HLT: input Ct read + output Ct write; only the unfused BaseConv
        stages (ModUp/ModDown) round-trip limbs when the Ct exceeds on-chip."""
        base = 2.0 * self.b_ct()
        if self.m_mo_hlt <= sram_bytes:
            return base
        p = self.params
        return base + 2.0 * (p.k + 1) * self.b_limb * 2.0

    # -- Table I ---------------------------------------------------------------

    def table1_counts(self, m: int, l: int, n: int) -> dict:
        """Paper Table I: HE op counts per Algorithm-2 step for (m, l, n)."""
        d = diag_count_formulas(m, l, n)
        phi = d["sigma"] + d["tau"]
        zeta = l * (d["eps"] + d["omega"])
        return {
            "step1": {"Add": phi, "Mult": 0, "CMult": phi, "Rot": phi, "Depth": 1},
            "step2": {"Add": zeta + l, "Mult": l, "CMult": zeta, "Rot": zeta,
                      "Depth": 2},
            "total": {"Add": phi + zeta + l, "Mult": l, "CMult": phi + zeta,
                      "Rot": phi + zeta, "Depth": 3},
        }


def report(params: HEParams, word_model: str = "paper") -> dict:
    """Summarize the §III-B3 memory numbers for one parameter set (MB)."""
    cm = CostModel(params, word_model)
    return {
        "set": params.name,
        "word_model": word_model,
        "B_ct_MB": cm.b_ct() / MB,
        "M_keyswitch_MB": cm.m_keyswitch / MB,
        "M_rot_MB": cm.m_rot / MB,
        "M_hlt_s2_MB": cm.m_hlt_s2 / MB,
        "M_hemm_MB": cm.m_hemm / MB,
        "M_mo_hlt_MB": cm.m_mo_hlt / MB,
        "reduction_x": cm.m_hemm / cm.m_mo_hlt,
    }
