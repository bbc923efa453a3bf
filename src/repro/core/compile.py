"""Plan → compile → execute: the public API for HE matmul.

FAME's central design move is a cost-model-driven *planning* step (on-chip
memory budget → datapath configuration) separated from *execution*; FAB shows
that explicit operand residency — not raw compute — decides HE accelerator
performance.  This module is that separation for the jax/Pallas reproduction:

    ctx = HEContext(CkksEngine(params))          # engine + keys + arena
    plan = plan_hemm(ctx.eng, m, l, n)
    ctx.keygen(rng, rot_steps=plan.rot_steps)
    prog = compile_hemm(ctx, plan)               # cost model runs ONCE here
    ctC = prog(ctA, ctB)                         # compiled, reusable
    prog.plan                                    # inspectable: schedule,
                                                 # chunk, padded d, per-stage
                                                 # byte/rotation counts

``HEContext`` owns ALL precompute: the Montgomery key/diagonal operand arena,
the jitted pipelines, and the compiled-program memo.  Nothing hides in module
globals keyed by ``id(engine)`` (an id can be recycled after GC and silently
serve a stale pipeline) or in ``DiagSet.__dict__`` side-channels; after a
re-keygen, ``ctx.invalidate()`` (called automatically by ``ctx.keygen``)
drops everything.

``compile_hlt(ctx, diags, level=..., batch=...)`` returns a ``CompiledHLT``.
Batched compiles store each UNIQUE operand tensor once in the arena and map
batch index → operand slot: the fused kernel gathers operands by slot index
through scalar-prefetch BlockSpec index maps (kernels/fused_hlt.py
``fused_hlt_indexed``) instead of ``jnp.stack``-ing B-fold copies.  hemm
Step-2's l-fold hoisted digits and block MM's per-tile σ/τ keys/diagonals are
therefore stored once — an ~l× / ~tiles× operand-memory reduction.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Union

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import hlt as hlt_mod, hlt_dist
from repro.core.ckks import Ciphertext, CkksEngine, Keys
from repro.core.costmodel import (VMEM_HEADROOM, hlt_hoist_bytes,
                                  hlt_stage_costs, pick_rotation_chunk,
                                  select_chain_schedules, select_schedule,
                                  sharded_collective_bytes)
from repro.core.hlt import DiagSet, Hoisted, hoist, hoist_batched
from repro.core.spans import span
from repro.distributed.sharding import logical_axis_size, make_rules


# ---------------------------------------------------------------------------
# identity keys + operand arena
# ---------------------------------------------------------------------------


class _StrongKey:
    """Dict key by object identity holding a STRONG reference.

    Unlike a bare ``id(obj)`` key, the reference keeps the object alive, so
    its id cannot be recycled by a new object while the entry exists — the
    failure mode of the old module-level ``id(engine)``-keyed jit caches.
    """

    __slots__ = ("obj",)

    def __init__(self, obj):
        self.obj = obj

    def __hash__(self):
        return id(self.obj)

    def __eq__(self, other):
        return isinstance(other, _StrongKey) and self.obj is other.obj


class OperandArena:
    """Device-resident operand store: ONE slot per unique operand group.

    Entries are keyed by (kind, owning object identity, compile point), e.g.
    the Montgomery kernel operands of one DiagSet at one (level, β, d_pad).
    Compiling the same DiagSet into many programs (hemm Step-1, every block-MM
    tile stage, …) reuses the same device buffers.
    """

    def __init__(self):
        self._entries: dict = {}

    def slot(self, kind: str, obj, extra: tuple, builder):
        """Return ``(slot_id, value)`` for the key, building it on miss."""
        key = (kind, _StrongKey(obj), extra)
        hit = self._entries.get(key)
        if hit is None:
            hit = (len(self._entries), builder())
            self._entries[key] = hit
        return hit                      # (slot_id, value)

    def __len__(self) -> int:
        return len(self._entries)

    @property
    def nbytes(self) -> int:
        """Total device bytes held across every arena slot."""
        total = 0
        for _, value in self._entries.values():
            for arr in jax.tree_util.tree_leaves(value):
                total += getattr(arr, "nbytes", 0)
        return total

    def clear(self) -> None:
        """Drop every slot (HEContext.invalidate calls this on re-keygen)."""
        self._entries.clear()


# ---------------------------------------------------------------------------
# HEContext
# ---------------------------------------------------------------------------


class HEContext:
    """Engine + keys + device-resident operand arena: owns ALL precompute.

    Create with an engine (and optionally existing keys), then ``keygen``::

        ctx = HEContext(CkksEngine(params))
        ctx.keygen(rng, rot_steps=plan.rot_steps)

    ``invalidate()`` drops the arena, the jitted pipelines and the compiled
    program memo; ``keygen()`` calls it so a re-keyed context can never serve
    Montgomery operands derived from the old keys.

    ``verify`` selects the static-verifier mode (repro.analysis, DESIGN.md
    §6) every compile runs through: ``"warn"`` (default) emits
    VerificationWarning findings, ``"error"`` raises VerificationError on
    error-severity findings, ``"off"`` skips verification entirely.

    ``datapath`` selects the stage coverage of compiled fused-schedule
    programs (DESIGN.md §7): ``"pallas"`` (default) runs the hoist and the
    merged ModDown+Rescale through the fused Pallas base-change kernels
    (kernels/basechange.py), so the whole HLT pipeline is Pallas;
    ``"xla"`` keeps those two stages on the pre-fusion XLA lowering (the
    comparison baseline benchmarks report against).  Reference schedules
    (baseline/hoisted/mo) always stay on the XLA oracle path.
    """

    VERIFY_MODES = ("error", "warn", "off")
    DATAPATHS = ("pallas", "xla")

    def __init__(self, eng: CkksEngine, keys: Optional[Keys] = None,
                 mesh=None, vmem_headroom: Optional[float] = None,
                 verify: str = "warn", datapath: str = "pallas"):
        assert verify in self.VERIFY_MODES, \
            f"verify={verify!r} not in {self.VERIFY_MODES}"
        assert datapath in self.DATAPATHS, \
            f"datapath={datapath!r} not in {self.DATAPATHS}"
        self.verify = verify
        self.datapath = datapath
        self.eng = eng
        self.keys = keys
        self.arena = OperandArena()
        self._jit: dict = {}            # pipeline cache (key -> jitted fn)
        self._compiled: dict = {}       # compile memo (key -> program)
        self._generation = 0            # bumped by invalidate()
        # monotonic execution counters (NOT reset by invalidate — they are
        # lifetime stats, not cached state): "hlt_launches" counts CompiledHLT
        # invocations (one slot-indexed pipeline launch each), and
        # "program_launches" counts program-level calls (HEMMProgram /
        # BlockMMProgram).  The serving layer asserts its one-launch-per-step
        # invariant against deltas of these.
        self.counters = {"hlt_launches": 0, "program_launches": 0}
        # distributed execution: a (pod, data, model) mesh makes the
        # schedule="sharded" SPMD program available — limbs shard over
        # `model`, the ciphertext/tile batch over `pod`×`data`
        # (distributed/sharding.py rules); the cost model sees the axis
        # sizes and may pick "sharded" on its own.
        self.mesh = mesh
        self.rules = make_rules(mesh)
        self.n_model = logical_axis_size(self.rules, "limbs")
        self.n_ct = logical_axis_size(self.rules, "ct_batch")
        self.n_devices = self.n_model * self.n_ct
        # VMEM budget fraction for the fused-kernel working set (the named
        # knob replacing the old hard-coded 0.75 guess; threaded into plans)
        self.vmem_headroom = (VMEM_HEADROOM if vmem_headroom is None
                              else float(vmem_headroom))

    @classmethod
    def create(cls, params, rng: np.random.Generator,
               rot_steps: Sequence[int] = (), mesh=None,
               vmem_headroom: Optional[float] = None,
               verify: str = "warn", datapath: str = "pallas") -> "HEContext":
        """Build an engine from ``params`` and keygen in one call."""
        ctx = cls(CkksEngine(params), mesh=mesh, vmem_headroom=vmem_headroom,
                  verify=verify, datapath=datapath)
        ctx.keygen(rng, rot_steps=rot_steps)
        return ctx

    def keygen(self, rng: np.random.Generator,
               rot_steps: Sequence[int] = ()) -> Keys:
        """Generate fresh keys and invalidate every cached operand."""
        self.keys = self.eng.keygen(rng, rot_steps=rot_steps)
        self.invalidate()
        return self.keys

    def invalidate(self) -> None:
        """Drop every arena operand, jitted pipeline and compiled program
        (call after replacing keys by hand; keygen() does it for you).
        Compiled objects from before the invalidation refuse to run — their
        operands were derived from the old keys."""
        self.arena.clear()
        self._jit.clear()
        self._compiled.clear()
        self._generation += 1

    def _check_generation(self, gen: int) -> None:
        if gen != self._generation:
            raise RuntimeError(
                "stale compiled object: its HEContext was invalidated "
                "(re-keygen?) after compilation — recompile via "
                "compile_hlt/compile_hemm")

    # -- jitted pipelines (merged ModDown+Rescale included) ------------------

    def _pallas_pipeline(self, level: int, chunk: int, kind: str):
        """Jitted fused-kernel pipeline; kind = "single" | "indexed".

        ``ctx.datapath`` picks the merged-ModDown lowering: "pallas" routes
        it through the fused base-change kernel, "xla" keeps the scan
        baseline (the hoist side of the knob lives at the hoist call
        sites)."""
        key = ("pallas", kind, level, chunk, self.datapath)
        fn = self._jit.get(key)
        if fn is not None:
            return fn
        from repro.kernels import ops
        eng = self.eng
        dp = self.datapath
        full = eng.tools.digit_bases(level)[0][2]
        view = eng.basis(full)
        q32, qneg = view.moduli_u32, view.qneg_inv

        def single(digits, c0e, c1e, u_m, rk0_m, rk1_m, perms, is_id):
            a0, a1 = ops.fused_hlt(digits, c0e, c1e, u_m, rk0_m, rk1_m,
                                   perms, is_id, q32, qneg, chunk=chunk)
            return (eng._mod_down_eval(a0, level, drop_last=True,
                                       datapath=dp),
                    eng._mod_down_eval(a1, level, drop_last=True,
                                       datapath=dp))

        def indexed(digits, c0e, c1e, u_m, rk0_m, rk1_m, perms, is_id,
                    ct_slots, diag_slots):
            a0, a1 = ops.fused_hlt_indexed(
                digits, c0e, c1e, u_m, rk0_m, rk1_m, perms, is_id,
                ct_slots, diag_slots, q32, qneg, chunk=chunk)
            down = jax.vmap(
                lambda a: eng._mod_down_eval(a, level, drop_last=True,
                                             datapath=dp))
            return down(a0), down(a1)

        fn = jax.jit(single if kind == "single" else indexed)
        self._jit[key] = fn
        return fn

    def _sharded_pipeline(self, tabs, d_pad: int, nbeta: int,
                          datapath: str = "pallas",
                          chunk: Optional[int] = None,
                          hoist_layout: str = "dedup",
                          stages: str = "pallas"):
        """Jitted shard_map SPMD MO-HLT (core/hlt_dist.py) for one compile
        point; batch/slot-count changes retrace automatically (arg shapes).

        ``datapath="pallas"`` drives each model rank's limb shard through the
        fused Pallas kernel, with the hoist inputs laid out per
        ``hoist_layout`` ("dedup" = unique cts replicated over the ct axis,
        "element" = per-element cts sharded over it — CompiledHLT picks per
        call); ``"xla"`` is the pre-fusion scan baseline
        (``schedule="sharded_xla"``).  The f64 BaseConv correction keeps CPU
        runs bit-exact vs the MO oracle; TPU runs use the native f32 path.
        """
        key = ("sharded", datapath, stages, hoist_layout, tabs.level,
               tabs.n_model, d_pad, nbeta, chunk)
        fn = self._jit.get(key)
        if fn is not None:
            return fn
        fp = jnp.float64 if jax.default_backend() == "cpu" else jnp.float32
        fn = jax.jit(hlt_dist.make_sharded_hlt_fn(
            tabs, self.rules, d_pad=d_pad, nbeta=nbeta, fp_dtype=fp,
            datapath=datapath, chunk=chunk, hoist_layout=hoist_layout,
            stages=stages))
        self._jit[key] = fn
        return fn


# Context pool for the DEPRECATED string-threaded shims (hlt(), hemm(), ...):
# one context per (engine, keys) pair, keyed by strong identity so a live
# entry's ids can never alias a new engine (the old _MO_JIT_CACHE bug).
# Bounded LRU: evicting an entry drops its strong refs (engine, keys, arena,
# jitted pipelines) so shim-heavy long-lived processes don't leak; a later
# id recycled from an EVICTED pair maps to a fresh context, never stale state.
_LEGACY_CONTEXTS: "dict" = {}
_LEGACY_POOL_MAX = 8


def legacy_context(eng: CkksEngine, keys: Keys) -> HEContext:
    """Pooled HEContext for the deprecated string-threaded shims (LRU)."""
    key = (_StrongKey(eng), _StrongKey(keys))
    ctx = _LEGACY_CONTEXTS.pop(key, None)
    if ctx is None:
        ctx = HEContext(eng, keys)
        while len(_LEGACY_CONTEXTS) >= _LEGACY_POOL_MAX:
            _LEGACY_CONTEXTS.pop(next(iter(_LEGACY_CONTEXTS)))
    _LEGACY_CONTEXTS[key] = ctx         # (re)insert as most-recently-used
    return ctx


# ---------------------------------------------------------------------------
# compile_hlt -> CompiledHLT
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class HLTPlan:
    """The cost model's output for one compiled HLT — fully inspectable.

    ``datapath`` records the hoist/ModDown stage coverage the program
    compiled with: ``"pallas"`` = the fused base-change kernels
    (kernels/basechange.py), ``"xla"`` = the pre-fusion lowering (always
    the case for the reference schedules and ``sharded_xla``).

    Sizing fields: ``level`` is the input ciphertext level (output is one
    lower); ``batch`` is the compile-time batch width (``None`` = a
    single-ciphertext compile); ``nbeta`` is the digit count β' at this
    level; ``d`` holds each batch element's REAL diagonal count and
    ``d_pad`` the common padded rotation count (a ``chunk`` multiple —
    padding rotations are identity+zero-diagonal and contribute nothing).

    Operand-dedup fields: ``diag_slots`` maps batch index -> unique
    diagonal-set arena slot (``n_diag_slots`` unique); ``ct_slots`` is the
    compile-time input-aliasing hint (batch index -> unique input
    ciphertext, ``None`` = unknown until call time) and ``n_ct_slots`` its
    unique count — the number of hoisting products the execution stores
    (sharded: hoists per rank).  ``operand_bytes`` / ``operand_bytes_naive``
    are the key+diagonal bytes after / before slot dedup, and
    ``hoist_bytes`` / ``hoist_bytes_naive`` the same for hoisting products
    (``sharded_xla`` re-hoists per element, so there they are equal).

    Execution-shape fields: ``chunk`` is the rotation chunk the fused kernel
    keeps resident per grid step (the cost model's VMEM-budget pick — under
    ``sharded`` this is the PER-RANK chunk applied to the limb-row shard);
    ``rotations`` counts real rotations per execution; ``stage_costs`` holds
    the per-stage byte/rotation/collective counts (costmodel.hlt_stage_costs);
    ``collective_bytes`` is the predicted cross-device traffic per execution
    (0 off-mesh); ``n_model``/``n_ct`` are the mesh factorization the compile
    saw, and ``vmem_headroom`` the VMEM fraction the chunk pick used.
    """

    schedule: str                       # chosen schedule
    datapath: str                       # hoist/ModDown coverage: pallas | xla
    level: int                          # input ciphertext level
    batch: Optional[int]                # None = single-ciphertext compile
    nbeta: int                          # digit count β' at this level
    chunk: int                          # rotation chunk (VMEM budget pick)
    d: tuple                            # per-item real diagonal counts
    d_pad: int                          # common padded rotation count
    diag_slots: tuple                   # batch index -> unique operand slot
    n_diag_slots: int                   # == number of UNIQUE diagonal sets
    rotations: int                      # total real rotations per execution
    operand_bytes: int                  # deduped key/diag operand bytes
    operand_bytes_naive: int            # what B-fold stacking would allocate
    stage_costs: dict                   # per-stage byte/rotation counts
    collective_bytes: int = 0           # predicted cross-device bytes / exec
    n_model: int = 1                    # limb-sharding ways (mesh `model`)
    n_ct: int = 1                       # ct-batch-sharding ways (pod×data)
    vmem_headroom: float = VMEM_HEADROOM  # VMEM fraction the chunk pick used
    ct_slots: Optional[tuple] = None    # batch index -> unique input ct
    n_ct_slots: Optional[int] = None    # unique hoisting products stored
    hoist_bytes: int = 0                # hoisting-product bytes after dedup
    hoist_bytes_naive: int = 0          # per-element (no-dedup) hoist bytes

    @property
    def dedup_factor(self) -> float:
        """Key/diagonal operand-memory reduction of the slot dedup (≥ 1)."""
        return self.operand_bytes_naive / max(1, self.operand_bytes)


def _operand_nbytes(ops_tuple) -> int:
    return sum(int(a.nbytes) for a in ops_tuple)


def _dedup_by_identity(items):
    """Batch elements -> (unique_items, slots): first-appearance order.

    The ONE numbering convention for operand/ct slots — compile-time DiagSet
    slots, the canonicalized ``ct_slots`` hint, and the call-time identity
    pattern are all produced by (or compared against) this order.
    """
    local, uniq, slots = {}, [], []
    for it in items:
        k = id(it)
        if k not in local:
            local[k] = len(uniq)
            uniq.append(it)
        slots.append(local[k])
    return uniq, slots


def _enforce_verify(ctx: HEContext, prog) -> None:
    """Run the static verifier on a freshly compiled program per
    ``ctx.verify`` (repro.analysis; no-op when "off").  Called BEFORE the
    memo store so a rejected compile is never cached; the memo keys carry
    ``ctx.verify`` so flipping the mode never returns a program that was
    compiled under different checking."""
    if ctx.verify == "off":
        return
    from repro.analysis import verify as _verify   # deferred: imports us
    _verify.enforce(ctx, prog)


def compile_hlt(ctx: HEContext, diags: Union[DiagSet, Sequence[DiagSet]], *,
                level: Optional[int] = None, batch: Optional[int] = None,
                schedule: Optional[str] = None,
                rotation_chunk: Optional[int] = None,
                ct_slots: Optional[Sequence[int]] = None) -> "CompiledHLT":
    """Run the cost model once and return a reusable CompiledHLT.

    ``diags``: one DiagSet (single-ciphertext compile, or — with ``batch=B``
    — a B-wide batch sharing that DiagSet) or a sequence of DiagSets (one per
    batch element; duplicates share one operand slot).

    ``ct_slots``: optional input-aliasing hint — one slot id per batch
    element, equal ids meaning "the SAME ciphertext will be passed here"
    (hemm Step-2 passes ``(0,)*l + (1,)*l``).  The hint sizes the plan's
    hoisting-dedup byte counts and pre-builds the sharded program's
    slot tables in the arena; execution always re-derives the actual
    aliasing from object identity, so a mismatched hint degrades plan
    accounting, never correctness.

    ``schedule=None`` lets the cost model choose (select_schedule);
    ``rotation_chunk=None`` takes the VMEM-budget pick.  Compiles are memoized
    on the context: compiling the same diagonal sets at the same point returns
    the SAME CompiledHLT object.
    """
    assert ctx.keys is not None, "HEContext has no keys; call ctx.keygen()"
    eng = ctx.eng
    level = eng.params.L if level is None else level
    if isinstance(diags, DiagSet):
        diag_list = [diags] if batch is None else [diags] * int(batch)
        batch = None if batch is None else int(batch)
    else:
        diag_list = list(diags)
        assert batch is None or batch == len(diag_list), (batch, len(diag_list))
        batch = len(diag_list)
        assert batch > 0, "batched compile needs at least one DiagSet"
    nbeta = len(eng.tools.digit_bases(level))
    d_list = tuple(ds.d for ds in diag_list)
    d_max = max(d_list)
    if ct_slots is not None:
        # canonicalize the aliasing hint to first-appearance numbering so it
        # can be compared against the identity-derived pattern at call time
        assert len(ct_slots) == len(diag_list), (len(ct_slots), len(diag_list))
        remap: dict = {}
        ct_slots = tuple(remap.setdefault(s, len(remap)) for s in ct_slots)
    if schedule is None:
        schedule = select_schedule(
            eng.params, nbeta=nbeta, headroom=ctx.vmem_headroom,
            n_model=ctx.n_model, n_ct=ctx.n_ct, d=d_max,
            ctb=batch if batch is not None else 1,
            n_uniq=None if ct_slots is None else len(set(ct_slots)))
    assert schedule in hlt_mod.SCHEDULES, schedule
    sharded = schedule.startswith("sharded")

    # stage coverage: the ctx knob only applies to the fused schedules —
    # reference schedules and the pre-fusion sharded_xla baseline always
    # run the hoist/ModDown stages on the XLA oracle lowering
    datapath = ctx.datapath if schedule in ("pallas", "sharded") else "xla"

    memo_key = ("hlt", schedule, level, batch, rotation_chunk, ct_slots,
                ctx.verify, datapath,
                tuple(_StrongKey(ds) for ds in diag_list))
    hit = ctx._compiled.get(memo_key)
    if hit is not None:
        return hit

    if rotation_chunk is None and schedule in ("pallas", "sharded"):
        # the fused kernel's per-grid-step working set must fit VMEM; under
        # "sharded" the SAME pick applies per rank (the kernel sees the
        # limb-row shard, so the budget formula is unchanged per row)
        chunk = max(1, min(pick_rotation_chunk(
            eng.params, nbeta=nbeta, headroom=ctx.vmem_headroom), d_max))
    elif rotation_chunk is None:
        chunk = d_max
    else:
        chunk = max(1, min(rotation_chunk, d_max))
    d_pad = -(-d_max // chunk) * chunk

    # unique-operand slots: one arena entry per distinct DiagSet
    uniq, slots = _dedup_by_identity(diag_list)

    ctb = batch if batch is not None else 1
    operands = None
    sharded_tabs = None
    slot_tables = None
    if schedule == "pallas" or sharded:
        per = [ctx.arena.slot(
                   "pallas_operands", ds, (level, nbeta, d_pad),
                   lambda ds=ds: hlt_mod._build_pallas_operands(
                       eng, ds, ctx.keys, level, nbeta, d_pad))[1]
               for ds in uniq]
        if sharded:
            # one stacked-and-limb-padded operand set per UNIQUE DiagSet;
            # the SPMD program gathers by slot (same dedup as the fused
            # kernel).  DistTables-style constants live in the arena, keyed
            # like every other operand and dropped by ctx.invalidate().
            def _build_tabs():
                t = hlt_dist.build_shard_tables(eng.params, level,
                                                ctx.n_model)
                arrays, _ = hlt_dist.place_operands(
                    t, ctx.rules, hlt_dist.shard_operand_arrays(t), ())
                return (t, arrays)
            _, sharded_tabs = ctx.arena.slot(
                "sharded_tables", eng, (level, ctx.n_model), _build_tabs)
            m_pad = sharded_tabs[0].M_pad
            stacked = [jnp.stack([p[i] for p in per]) for i in range(5)]
            pad = m_pad - stacked[0].shape[2]
            if pad:
                u, rk0, rk1 = stacked[:3]
                stacked[0] = jnp.pad(u, ((0, 0), (0, 0), (0, pad), (0, 0)))
                stacked[1] = jnp.pad(rk0, ((0, 0), (0, 0), (0, 0), (0, pad),
                                           (0, 0)))
                stacked[2] = jnp.pad(rk1, ((0, 0), (0, 0), (0, 0), (0, pad),
                                           (0, 0)))
            _, operands = hlt_dist.place_operands(
                sharded_tabs[0], ctx.rules, {}, tuple(stacked))
            # batch-index -> slot tables, padded to the ct-axis multiple,
            # arena-owned like every other operand (hlt_dist.build_slot_tables)
            b_pad = -(-ctb // max(1, ctx.n_ct)) * max(1, ctx.n_ct)
            _, slot_tables = ctx.arena.slot(
                "sharded_slot_tables", eng,
                (level, tuple(slots), ct_slots, b_pad),
                lambda: hlt_dist.build_slot_tables(slots, ct_slots, b_pad))
        elif batch is None:
            operands = per[0]
        else:
            operands = tuple(jnp.stack([p[i] for p in per]) for i in range(5))

    op_bytes = _operand_nbytes(operands) if operands is not None else 0
    naive = (op_bytes if batch is None else
             op_bytes // max(1, len(uniq)) * len(diag_list))
    # hoisting-product accounting: one product per UNIQUE input ciphertext
    # (the ct-slot dedup), except sharded_xla which re-hoists per element
    # and baseline which never hoists.  Without a hint, assume all-distinct.
    m_ext = len(eng.tools.digit_bases(level)[0][2])
    h_unit = int(hlt_hoist_bytes(eng.params, nbeta=nbeta, n_limbs_ext=m_ext))
    n_ct_slots = None if ct_slots is None else len(set(ct_slots))
    n_hoist = ctb if (n_ct_slots is None or schedule == "sharded_xla") \
        else n_ct_slots
    plan = HLTPlan(
        schedule=schedule, datapath=datapath,
        level=level, batch=batch, nbeta=nbeta, chunk=chunk,
        d=d_list, d_pad=d_pad, diag_slots=tuple(slots),
        n_diag_slots=len(uniq), rotations=sum(d_list),
        operand_bytes=op_bytes, operand_bytes_naive=naive,
        stage_costs=hlt_stage_costs(
            eng.params, d=d_max, d_pad=d_pad, nbeta=nbeta, chunk=chunk,
            n_limbs_ext=m_ext,
            n_model=ctx.n_model if sharded else 1, ctb=ctb, n_hoist=n_hoist),
        collective_bytes=(sharded_collective_bytes(
            # the psum moves the slot-PADDED batch, not the logical one
            eng.params, n_model=ctx.n_model,
            ctb=-(-ctb // max(1, ctx.n_ct)) * max(1, ctx.n_ct))
            if sharded else 0),
        n_model=ctx.n_model if sharded else 1,
        n_ct=ctx.n_ct if sharded else 1,
        vmem_headroom=ctx.vmem_headroom,
        ct_slots=ct_slots, n_ct_slots=n_ct_slots,
        hoist_bytes=0 if schedule == "baseline" else h_unit * n_hoist,
        hoist_bytes_naive=0 if schedule == "baseline" else h_unit * ctb)
    run = CompiledHLT(ctx, plan, tuple(diag_list), tuple(uniq), operands,
                      sharded_tabs=sharded_tabs, slot_tables=slot_tables)
    _enforce_verify(ctx, run)
    ctx._compiled[memo_key] = run
    return run


class CompiledHLT:
    """A compiled homomorphic linear transformation.

    Call with one ciphertext/hoisting-product (single compile) or a sequence
    of them (batched compile; repeated objects share one hoisting slot).
    Execution never re-runs the cost model or rebuilds operands.
    """

    def __init__(self, ctx: HEContext, plan: HLTPlan, diag_list, uniq_diags,
                 operands, sharded_tabs=None, slot_tables=None):
        self.ctx = ctx
        self.plan = plan
        self._diags = diag_list         # strong refs, one per batch element
        self._uniq = uniq_diags
        self._operands = operands       # single tuple | stacked tuple | None
        self._sharded = sharded_tabs    # (ShardTables, table arrays) | None
        self._slot_tables = slot_tables  # arena {"diag": (b_pad,), "ct": ...}
        self._diag_slots = (None if plan.batch is None else
                            jnp.asarray(np.array(plan.diag_slots, np.int32)))
        self._gen = ctx._generation

    def device_bytes(self) -> dict:
        """Bytes of this program's compile-time operands (rotation keys,
        diagonals, sharded tables) resident on each device, by device id."""
        leaves = jax.tree_util.tree_leaves(
            (self._operands, None if self._sharded is None
             else self._sharded[1]))
        held: dict = {}
        for arr in leaves:
            for shard in arr.addressable_shards:
                held[shard.device.id] = (held.get(shard.device.id, 0)
                                         + shard.data.nbytes)
        return held

    # -- helpers -------------------------------------------------------------

    def _hoist_items(self, items):
        """Dedupe by object identity, hoist unique ciphertexts in ONE batched
        pipeline (the plan's datapath picks fused-Pallas vs XLA), return
        (unique_hoisted, ct_slots)."""
        eng = self.ctx.eng
        uniq, slots = _dedup_by_identity(items)
        cts = [(i, it) for i, it in enumerate(uniq)
               if not isinstance(it, Hoisted)]
        hoisted = list(uniq)
        for (i, _), h in zip(cts, hoist_batched(eng, [it for _, it in cts],
                                                datapath=self.plan.datapath),
                             strict=True):
            hoisted[i] = h
        for h in hoisted:
            assert h.level == self.plan.level, (h.level, self.plan.level)
        return hoisted, slots

    def _finish(self, c0, c1, scale_in: float, ds: DiagSet) -> Ciphertext:
        level = self.plan.level
        q_ell = self.ctx.eng.ctx.moduli_host[level]
        return Ciphertext(c0, c1, level - 1, scale_in * ds.scale / q_ell)

    # -- execution -----------------------------------------------------------

    def __call__(self, items):
        self.ctx._check_generation(self._gen)
        self.ctx.counters["hlt_launches"] += 1
        if self.plan.schedule.startswith("sharded"):
            if self.plan.batch is None:
                return self._run_sharded([items])[0]
            items = list(items)
            assert len(items) == self.plan.batch, (len(items), self.plan.batch)
            return self._run_sharded(items)
        if self.plan.batch is None:
            return self._run_single(items, self._diags[0], self._operands)
        items = list(items)
        assert len(items) == self.plan.batch, (len(items), self.plan.batch)
        if self.plan.schedule == "pallas":
            return self._run_batched_pallas(items)
        # reference schedules: loop of single executions (oracle path)
        return [self._run_single(it, ds, None)
                for it, ds in zip(items, self._diags, strict=True)]

    def _run_single(self, item, ds: DiagSet, operands) -> Ciphertext:
        ctx, eng, plan = self.ctx, self.ctx.eng, self.plan
        if plan.schedule == "baseline":
            assert isinstance(item, Ciphertext), \
                "schedule='baseline' has no hoisting product; pass Ciphertexts"
            assert item.level == plan.level
            with span("hlt.launch"):
                return hlt_mod._hlt_baseline(eng, item, ds, ctx.keys)
        if isinstance(item, Hoisted):
            hst = item
        else:
            with span("hlt.hoist"):
                hst = hoist(eng, item, datapath=plan.datapath)
        assert hst.level == plan.level, (hst.level, plan.level)
        if plan.schedule == "hoisted":
            with span("hlt.launch"):
                return hlt_mod._hlt_hoisted(eng, hst, ds, ctx.keys)
        if plan.schedule == "mo":
            with span("hlt.launch"):
                return hlt_mod._hlt_mo(eng, hst, ds, ctx.keys, plan.chunk,
                                       ctx._jit)
        if operands is None:            # single-DiagSet operands from arena
            operands = ctx.arena.slot(
                "pallas_operands", ds, (plan.level, plan.nbeta, plan.d_pad),
                lambda: hlt_mod._build_pallas_operands(
                    eng, ds, ctx.keys, plan.level, plan.nbeta, plan.d_pad))[1]
        fn = ctx._pallas_pipeline(plan.level, plan.chunk, "single")
        with span("hlt.launch"):
            c0, c1 = fn(hst.digits, hst.c0_ext, hst.c1_ext, *operands)
        with span("hlt.finish"):
            return self._finish(c0, c1, hst.scale, ds)

    @property
    def _datapath(self) -> str:
        return "xla" if self.plan.schedule == "sharded_xla" else "pallas"

    def _sharded_args(self, items):
        """Pack the shard_map argument dict; returns ``(args, hoist_layout)``.

        Fused ("pallas"): dedupe the batch by object identity and pick the
        hoist layout that performs FEWER hoists per rank — "dedup" stacks
        only the H unique ciphertexts (replicated over the ct axis, each
        rank hoists H) when H fits a rank's batch share, else "element"
        keeps the per-element stacking sharded over the ct axis (each rank
        hoists its B_loc local elements).  Either way the limb axis is
        zero-extended to the padded shard and the ct-slot vector routes each
        batch element to its hoisting product; padding elements alias slot 0
        (dedup) or are zero ciphertexts (element) and their outputs are
        dropped.  Prefers the arena-owned slot tables when the call-time
        aliasing matches the compile-time ``ct_slots`` hint.

        XLA baseline ("sharded_xla"): per-element stacking, padded with zero
        ciphertexts (they flow zeros and are dropped again).
        """
        plan = self.plan
        tabs, tab_arrays = self._sharded
        for it in items:
            assert isinstance(it, Ciphertext), \
                "schedule='sharded' hoists inside the SPMD program; pass " \
                "Ciphertexts, not hoisting products"
            assert it.level == plan.level, (it.level, plan.level)
        B = len(items)
        diag_tab = self._slot_tables["diag"]
        b_pad = diag_tab.shape[0]
        b_loc = b_pad // max(1, self.ctx.n_ct)    # batch share of one ct rank
        rows_pad = tabs.M_pad - (plan.level + 1)
        ext = ((0, 0), (0, rows_pad), (0, 0))
        u, rk0, rk1, perms, is_id = self._operands
        common = dict(u=u, rk0=rk0, rk1=rk1, perms=perms, is_id=is_id,
                      tab=tab_arrays)

        def stack_padded(its):
            c0 = jnp.stack([it.c0 for it in its])
            c1 = jnp.stack([it.c1 for it in its])
            if b_pad > len(its):
                z = jnp.zeros((b_pad - len(its),) + c0.shape[1:], jnp.uint32)
                c0 = jnp.concatenate([c0, z])
                c1 = jnp.concatenate([c1, z])
            return c0, c1
        if self._datapath == "xla":
            c0, c1 = stack_padded(items)
            return dict(c0f=jnp.pad(c0, ext), c1f=jnp.pad(c1, ext), c1rep=c1,
                        slots=diag_tab, **common), "dedup"
        uniq, ct_slots = _dedup_by_identity(items)
        if len(uniq) > b_loc:
            # mostly-distinct batch: replicating the uniques would make every
            # ct rank hoist MORE than its local share — keep per-element
            # stacking sharded over the ct axis, rank-local hoist indices
            c0u, c1u = stack_padded(items)
            ct_tab = jnp.asarray(
                (np.arange(b_pad) % b_loc).astype(np.int32))
            return dict(c0u=jnp.pad(c0u, ext), c1u=jnp.pad(c1u, ext),
                        c1rep=c1u, ct_slots=ct_tab, slots=diag_tab,
                        **common), "element"
        if plan.ct_slots is not None and tuple(ct_slots) == plan.ct_slots:
            ct_tab = self._slot_tables["ct"]      # arena-owned hint table
        else:
            ct_tab = jnp.asarray(np.array(
                list(ct_slots) + [0] * (b_pad - B), np.int32))
        c0u = jnp.stack([it.c0 for it in uniq])
        c1u = jnp.stack([it.c1 for it in uniq])
        return dict(c0u=jnp.pad(c0u, ext), c1u=jnp.pad(c1u, ext), c1rep=c1u,
                    ct_slots=ct_tab, slots=diag_tab, **common), "dedup"

    def _run_sharded(self, items) -> list:
        ctx, plan = self.ctx, self.plan
        tabs, _ = self._sharded
        with span("hlt.hoist"):
            args, layout = self._sharded_args(items)
        fn = ctx._sharded_pipeline(tabs, plan.d_pad, plan.nbeta,
                                   self._datapath, plan.chunk, layout,
                                   plan.datapath)
        with span("hlt.launch"):
            out0, out1 = fn(args)
        lvl = plan.level
        with span("hlt.finish"):
            return [self._finish(out0[b, :lvl], out1[b, :lvl], it.scale, ds)
                    for b, (it, ds) in enumerate(zip(items, self._diags, strict=True))]

    def hlo(self, items) -> str:
        """Optimized HLO text of the batched program this call would run
        (``pallas``: the slot-indexed fused pipeline; ``sharded``: the SPMD
        program) — benchmarks feed the sharded text to
        distributed/hlo_analysis.collective_stats to MEASURE collective bytes
        against the plan's prediction, and the chip smoke test checks that
        the fused kernel (``tpu_custom_call``) is in it."""
        self.ctx._check_generation(self._gen)
        if self.plan.schedule.startswith("sharded"):
            tabs, _ = self._sharded
            args, layout = self._sharded_args(items)
            fn = self.ctx._sharded_pipeline(tabs, self.plan.d_pad,
                                            self.plan.nbeta, self._datapath,
                                            self.plan.chunk, layout,
                                            self.plan.datapath)
            return fn.lower(args).compile().as_text()
        assert self.plan.schedule == "pallas" and self.plan.batch is not None
        fn, args, _, _ = self._batched_pallas_args(items)
        return fn.lower(*args).compile().as_text()

    def _batched_pallas_args(self, items):
        """(pipeline, args, unique hoisting products, ct slots) of one
        batched fused-schedule call."""
        ctx, plan = self.ctx, self.plan
        hoisted, ct_slots = self._hoist_items(items)
        digits = jnp.stack([h.digits for h in hoisted])
        c0e = jnp.stack([h.c0_ext for h in hoisted])
        c1e = jnp.stack([h.c1_ext for h in hoisted])
        fn = ctx._pallas_pipeline(plan.level, plan.chunk, "indexed")
        args = (digits, c0e, c1e, *self._operands,
                jnp.asarray(np.array(ct_slots, np.int32)), self._diag_slots)
        return fn, args, hoisted, ct_slots

    def _run_batched_pallas(self, items) -> list:
        with span("hlt.hoist"):
            fn, args, hoisted, ct_slots = self._batched_pallas_args(items)
        with span("hlt.launch"):
            c0b, c1b = fn(*args)
        with span("hlt.finish"):
            return [self._finish(c0b[b], c1b[b], hoisted[ct_slots[b]].scale, ds)
                    for b, ds in enumerate(self._diags)]


# ---------------------------------------------------------------------------
# compile_hemm -> HEMMProgram
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class HEMMPlan:
    """Inspectable compile summary for one HE matrix multiplication.

    ``m``/``l``/``n`` are the plaintext matrix dimensions of Algorithm 2;
    ``schedule`` is the common HLT schedule both steps compiled to;
    ``level`` is the input ciphertext level (the program consumes ``depth``
    = 3 levels: two HLT stages plus one Mult·Rescale); ``batched`` records
    whether the steps compiled as slot-indexed batched launches.  ``step1``
    and ``step2`` are the embedded :class:`HLTPlan` objects — the aggregate
    properties below just sum them.
    """

    m: int
    l: int
    n: int
    schedule: str
    level: int                          # input level; output is level - 3
    batched: bool
    step1: HLTPlan
    step2: HLTPlan
    depth: int = 3

    @property
    def rotations(self) -> int:
        """Total real rotations per execution (both HLT stages)."""
        return self.step1.rotations + self.step2.rotations

    @property
    def operand_bytes(self) -> int:
        """Deduped key/diagonal operand bytes across both stages."""
        return self.step1.operand_bytes + self.step2.operand_bytes

    @property
    def operand_bytes_naive(self) -> int:
        """Key/diagonal bytes B-fold stacking would have allocated."""
        return self.step1.operand_bytes_naive + self.step2.operand_bytes_naive

    @property
    def hoist_bytes(self) -> int:
        """Hoisting-product bytes after ct-slot dedup (Step 2 stores 2
        unique products — one per input ciphertext — not 2·l)."""
        return self.step1.hoist_bytes + self.step2.hoist_bytes

    @property
    def hoist_bytes_naive(self) -> int:
        """Hoisting-product bytes of the per-element (no-dedup) layout."""
        return self.step1.hoist_bytes_naive + self.step2.hoist_bytes_naive

    @property
    def collective_bytes(self) -> int:
        """Predicted cross-device bytes per execution (0 off-mesh): the two
        HLT stages' merged-ModDown BaseConv psums — the program's only
        collectives."""
        return self.step1.collective_bytes + self.step2.collective_bytes


class HEMMProgram:
    """A compiled Algorithm-2 HE MM: ``prog(ctA, ctB) -> ctC``.

    Consumes 3 levels (2 HLTs + 1 Mult·Rescale).  Under the fused schedule
    Step 1 runs {σ(A), τ(B)} as one batched launch and Step 2 runs all 2·l
    HLTs as ONE slot-indexed launch storing only the 2 unique hoisting
    products and 2·l unique diagonal sets (no l-fold operand replication).
    """

    def __init__(self, ctx: HEContext, mm_plan, plan: HEMMPlan,
                 step1: "CompiledHLT", step2: "CompiledHLT"):
        self.ctx = ctx
        self.mm_plan = mm_plan
        self.plan = plan
        self._step1 = step1
        self._step2 = step2
        self._gen = ctx._generation

    def __call__(self, ctA: Ciphertext, ctB: Ciphertext) -> Ciphertext:
        with span("hemm.call"):
            self.ctx._check_generation(self._gen)
            self.ctx.counters["program_launches"] += 1
            eng, keys, p = self.ctx.eng, self.ctx.keys, self.mm_plan
            assert ctA.level == ctB.level == self.plan.level
            if self.plan.batched:
                items = self._step2_items(ctA, ctB)
                with span("hemm.step2"):
                    outs = self._step2(items)
            else:
                s1a, s1b = self._step1
                with span("hemm.step1"):
                    ctA0, ctB0 = s1a(ctA), s1b(ctB)
                if self.plan.schedule == "baseline" or \
                        self.plan.schedule.startswith("sharded"):
                    inA, inB = ctA0, ctB0
                else:   # hoist once, reuse across all l Step-2 HLTs per input
                    dp = self.plan.step2.datapath
                    with span("hemm.hoist"):
                        inA = hoist(eng, ctA0, datapath=dp)
                        inB = hoist(eng, ctB0, datapath=dp)
                with span("hemm.step2"):
                    outs = ([run(inA) for run in self._step2[:p.l]]
                            + [run(inB) for run in self._step2[p.l:]])
            acc: Optional[Ciphertext] = None
            with span("hemm.tail"):
                for k in range(p.l):
                    prod = eng.rescale(eng.mult(outs[k], outs[p.l + k], keys))
                    acc = prod if acc is None else eng.add(acc, prod)
            return acc

    def device_bytes(self) -> dict:
        """Compile-time operand bytes per device id, both HLT steps."""
        held: dict = {}
        steps = (self._step1, self._step2) if self.plan.batched else \
            tuple(self._step1) + tuple(self._step2)
        for step in steps:
            for dev, n in step.device_bytes().items():
                held[dev] = held.get(dev, 0) + n
        return held

    def _step2_items(self, ctA: Ciphertext, ctB: Ciphertext) -> list:
        """Batched Step 1, then the 2·l Step-2 batch items."""
        l = self.mm_plan.l
        with span("hemm.step1"):
            ctA0, ctB0 = self._step1([ctA, ctB])
        if self.plan.schedule.startswith("sharded"):
            # the SPMD program hoists internally (limb-local, off the
            # replicated inputs; the fused datapath hoists each unique
            # ciphertext ONCE per rank) — feed the Step-1 cts directly
            return [ctA0] * l + [ctB0] * l
        with span("hemm.hoist"):
            hstA, hstB = hoist_batched(self.ctx.eng, [ctA0, ctB0],
                                       datapath=self.plan.step2.datapath)
        return [hstA] * l + [hstB] * l

    def hlo(self, ctA: Ciphertext, ctB: Ciphertext) -> str:
        """Optimized HLO text of the batched Step-2 launch (the 2·l HLTs)."""
        assert self.plan.batched, "only batched programs have one Step-2 launch"
        self.ctx._check_generation(self._gen)
        return self._step2.hlo(self._step2_items(ctA, ctB))


def compile_hemm(ctx: HEContext, plan, *, level: Optional[int] = None,
                 schedule: Optional[str] = None,
                 rotation_chunk: Optional[int] = None,
                 batched: Optional[bool] = None) -> HEMMProgram:
    """Compile Algorithm 2 for a HeMMPlan (core/hemm.py plan_hemm) into a
    reusable HEMMProgram.  ``schedule=None`` / ``rotation_chunk=None`` defer
    to the cost model; ``batched=None`` batches whenever the fused schedule
    is chosen.  Memoized on the context (same plan → same program)."""
    with span("hemm.compile"):
        assert ctx.keys is not None, "HEContext has no keys; call ctx.keygen()"
        eng = ctx.eng
        level = eng.params.L if level is None else level
        nbeta = len(eng.tools.digit_bases(level))
        if schedule is None:
            # Step 2 dominates (2·l HLTs) and runs off 2 unique inputs — model
            # the hoist-dedup term with the aliasing the program will create
            schedule = select_schedule(
                eng.params, nbeta=nbeta, headroom=ctx.vmem_headroom,
                n_model=ctx.n_model, n_ct=ctx.n_ct,
                d=plan.ds_sigma.d, ctb=2 * plan.l, n_uniq=2)
        if batched is None:
            batched = schedule in ("pallas", "sharded", "sharded_xla")
        batched = batched and schedule != "baseline"
        memo_key = ("hemm", _StrongKey(plan), schedule, level, rotation_chunk,
                    batched, ctx.verify)
        hit = ctx._compiled.get(memo_key)
        if hit is not None:
            return hit

        step2_sets = list(plan.ds_eps) + list(plan.ds_omega)
        if batched:
            step1 = compile_hlt(ctx, [plan.ds_sigma, plan.ds_tau], level=level,
                                schedule=schedule, rotation_chunk=rotation_chunk,
                                ct_slots=(0, 1))
            # Step 2 runs 2·l HLTs over TWO unique inputs ([A0]·l + [B0]·l):
            # the ct_slots hint sizes the hoist-dedup plan numbers and (under
            # sharded) pre-builds the arena slot tables for the common case.
            step2 = compile_hlt(ctx, step2_sets, level=level - 1,
                                schedule=schedule, rotation_chunk=rotation_chunk,
                                ct_slots=(0,) * plan.l + (1,) * plan.l)
            s1_plan, s2_plan = step1.plan, step2.plan
        else:
            c = lambda ds, lv: compile_hlt(ctx, ds, level=lv, schedule=schedule,
                                           rotation_chunk=rotation_chunk)
            step1 = (c(plan.ds_sigma, level), c(plan.ds_tau, level))
            step2 = tuple(c(ds, level - 1) for ds in step2_sets)
            s1_plan, s2_plan = step1[0].plan, step2[0].plan
        prog = HEMMProgram(
            ctx, plan,
            HEMMPlan(m=plan.m, l=plan.l, n=plan.n, schedule=schedule, level=level,
                     batched=batched, step1=s1_plan, step2=s2_plan),
            step1, step2)
        _enforce_verify(ctx, prog)
        ctx._compiled[memo_key] = prog
        return prog


# ---------------------------------------------------------------------------
# compile_blockmm -> BlockMMProgram (the whole tile grid as TWO launches)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class BlockMMPlan:
    """Inspectable compile summary for one block HE MM over a tile grid.

    ``m``/``l``/``n`` are the per-tile matrix dimensions and ``grid`` the
    (gm, gl, gn) tile grid — C[i][j] = Σ_k A[i][k]·B[k][j] with every tile a
    single ciphertext.  The whole grid compiles to TWO slot-indexed HLT
    launches per execution (``hlt_launches``): Step 1 σ/τ-transforms every
    A/B tile in one launch, Step 2 runs ALL l·(gm·gl + gl·gn) ε/ω HLTs in
    one launch (the per-``k`` launch loop of the pre-subsystem batched path
    folded into the batch axis).  ``hlt_launches_naive`` is what a loop of
    per-tile-pair HEMMPrograms would issue — the launch amortization the
    serving batcher reports per decode step.  ``step1``/``step2`` embed the
    stage :class:`HLTPlan` objects; the aggregate properties sum them.
    """

    m: int
    l: int
    n: int
    grid: tuple                         # (gm, gl, gn) tile grid
    schedule: str
    level: int                          # input level; output is level - 3
    step1: HLTPlan
    step2: HLTPlan
    depth: int = 3

    @property
    def hlt_launches(self) -> int:
        """Slot-indexed pipeline launches per execution: always 2."""
        return 2

    @property
    def hlt_launches_naive(self) -> int:
        """Launches a loop of per-tile-pair HEMMPrograms would issue
        (each pair: one Step-1 and one Step-2 batched launch)."""
        gm, gl, gn = self.grid
        return 2 * gm * gl * gn

    @property
    def rotations(self) -> int:
        """Total real rotations per execution (both HLT stages)."""
        return self.step1.rotations + self.step2.rotations

    @property
    def operand_bytes(self) -> int:
        """Deduped key/diagonal operand bytes across both stages."""
        return self.step1.operand_bytes + self.step2.operand_bytes

    @property
    def operand_bytes_naive(self) -> int:
        """Key/diagonal bytes B-fold stacking would have allocated."""
        return self.step1.operand_bytes_naive + self.step2.operand_bytes_naive

    @property
    def hoist_bytes(self) -> int:
        """Hoisting-product bytes after ct-slot dedup (one product per
        UNIQUE tile per stage, per the compile-time aliasing hint)."""
        return self.step1.hoist_bytes + self.step2.hoist_bytes

    @property
    def hoist_bytes_naive(self) -> int:
        """Hoisting-product bytes of the per-element (no-dedup) layout."""
        return self.step1.hoist_bytes_naive + self.step2.hoist_bytes_naive

    @property
    def collective_bytes(self) -> int:
        """Predicted cross-device bytes per execution (0 off-mesh)."""
        return self.step1.collective_bytes + self.step2.collective_bytes


class BlockMMProgram:
    """A compiled block HE MM: ``prog(A_tiles, B_tiles) -> C_tiles``.

    ``A_tiles`` is a gm×gl and ``B_tiles`` a gl×gn list-of-lists of
    ciphertext tiles (``SecureMatmulEngine.encrypt_tiles`` layout); the
    result is the gm×gn grid of accumulated output ciphertexts.  Repeated
    tile OBJECTS (e.g. shared-prompt rows the serving batcher aliases to one
    ciphertext) are transformed once in Step 1 and hoisted once in Step 2:
    execution re-derives the aliasing from object identity, reuses one
    Step-1 output per unique input, and the slot-indexed kernel routes every
    batch element to its unique hoisting product.
    """

    def __init__(self, ctx: HEContext, mm_plan, plan: BlockMMPlan,
                 step1: "CompiledHLT", step2: "CompiledHLT"):
        self.ctx = ctx
        self.mm_plan = mm_plan          # the per-tile HeMMPlan (math)
        self.plan = plan
        self._step1 = step1
        self._step2 = step2
        self._gen = ctx._generation

    def __call__(self, A_tiles, B_tiles) -> list:
        self.ctx._check_generation(self._gen)
        self.ctx.counters["program_launches"] += 1
        eng, keys, p = self.ctx.eng, self.ctx.keys, self.mm_plan
        gm, gl, gn = self.plan.grid
        assert len(A_tiles) == gm and len(A_tiles[0]) == gl, "A grid mismatch"
        assert len(B_tiles) == gl and len(B_tiles[0]) == gn, "B grid mismatch"
        ik = [(i, k) for i in range(gm) for k in range(gl)]
        kj = [(k, j) for k in range(gl) for j in range(gn)]
        nA, nB = len(ik), len(kj)
        items1 = ([A_tiles[i][k] for i, k in ik]
                  + [B_tiles[k][j] for k, j in kj])
        for it in items1:
            assert it.level == self.plan.level, (it.level, self.plan.level)
        # Step 1 — every tile σ/τ-transformed in ONE launch; alias the
        # outputs of repeated input OBJECTS to one output object so Step 2's
        # identity dedup hoists each unique tile once (outputs of aliased
        # inputs are bit-identical, so reusing the first is exact).
        _, slots1 = _dedup_by_identity(items1)
        outs = self._step1(items1)
        rep: dict = {}
        outs = [outs[rep.setdefault(s, b)] for b, s in enumerate(slots1)]
        sharded = self.plan.schedule.startswith("sharded")
        if sharded or self.plan.schedule == "baseline":
            # sharded hoists inside the SPMD program (once per unique ct per
            # rank); baseline never hoists — both consume Ciphertexts
            hst = outs
        else:
            uniq, uslots = _dedup_by_identity(outs)
            hu = hoist_batched(eng, uniq,
                               datapath=self.plan.step2.datapath)
            hst = [hu[s] for s in uslots]
        # Step 2 — ALL l·(nA + nB) ε/ω HLTs as ONE slot-indexed launch
        items2 = ([hst[t] for _ in range(p.l) for t in range(nA)]
                  + [hst[nA + t] for _ in range(p.l) for t in range(nB)])
        res = self._step2(items2)
        acc: list = [[None] * gn for _ in range(gm)]
        for kk in range(p.l):
            Ak = {t: res[kk * nA + ti] for ti, t in enumerate(ik)}
            Bk = {t: res[p.l * nA + kk * nB + ti] for ti, t in enumerate(kj)}
            for i in range(gm):
                for j in range(gn):
                    for k in range(gl):
                        prod = eng.rescale(eng.mult(Ak[i, k], Bk[k, j], keys))
                        acc[i][j] = (prod if acc[i][j] is None
                                     else eng.add(acc[i][j], prod))
        return acc


def compile_blockmm(ctx: HEContext, plan, grid, *,
                    level: Optional[int] = None,
                    schedule: Optional[str] = None,
                    rotation_chunk: Optional[int] = None,
                    a_slots: Optional[Sequence[int]] = None,
                    b_slots: Optional[Sequence[int]] = None
                    ) -> BlockMMProgram:
    """Compile a (gm, gl, gn) block MM over single-ciphertext tiles into a
    reusable BlockMMProgram — the WHOLE grid as two slot-indexed launches.

    ``plan`` is the per-tile HeMMPlan (core/hemm.py plan_hemm for the tile
    shape); ``grid`` the tile grid.  ``a_slots`` / ``b_slots`` are optional
    compile-time aliasing hints over the row-major gm·gl A tiles / gl·gn B
    tiles (equal ids = the SAME ciphertext tile will be passed — the serving
    batcher's shared-prompt pattern); like compile_hlt's ``ct_slots`` they
    size the plan's hoist-dedup accounting and pre-build sharded slot
    tables, while execution always re-derives aliasing from object identity.

    ``schedule=None`` defers to the cost model with the full Step-2 batch
    (l·(gm·gl + gl·gn) elements over gm·gl + gl·gn unique inputs).  Memoized
    on the context (same plan + grid + knobs → same program).
    """
    assert ctx.keys is not None, "HEContext has no keys; call ctx.keygen()"
    eng = ctx.eng
    gm, gl, gn = grid = tuple(int(g) for g in grid)
    assert gm > 0 and gl > 0 and gn > 0, grid
    level = eng.params.L if level is None else level
    nA, nB = gm * gl, gl * gn
    if a_slots is None:
        a_slots = tuple(range(nA))
    else:
        assert len(a_slots) == nA, (len(a_slots), nA)
        remap: dict = {}
        a_slots = tuple(remap.setdefault(s, len(remap)) for s in a_slots)
    if b_slots is None:
        b_slots = tuple(range(nB))
    else:
        assert len(b_slots) == nB, (len(b_slots), nB)
        remap = {}
        b_slots = tuple(remap.setdefault(s, len(remap)) for s in b_slots)
    off = max(a_slots) + 1
    slots1 = a_slots + tuple(off + s for s in b_slots)
    nbeta = len(eng.tools.digit_bases(level))
    if schedule is None:
        schedule = select_schedule(
            eng.params, nbeta=nbeta, headroom=ctx.vmem_headroom,
            n_model=ctx.n_model, n_ct=ctx.n_ct, d=plan.ds_sigma.d,
            ctb=plan.l * (nA + nB), n_uniq=len(set(slots1)))

    memo_key = ("blockmm", _StrongKey(plan), grid, schedule, level,
                rotation_chunk, a_slots, b_slots, ctx.verify)
    hit = ctx._compiled.get(memo_key)
    if hit is not None:
        return hit

    step1 = compile_hlt(
        ctx, [plan.ds_sigma] * nA + [plan.ds_tau] * nB, level=level,
        schedule=schedule, rotation_chunk=rotation_chunk, ct_slots=slots1)
    # Step 2's batch order is k-major (all A elements of iteration k, then
    # the next k; B after all A) — BlockMMProgram.__call__ indexes by it
    step2_sets = ([plan.ds_eps[k] for k in range(plan.l)
                   for _ in range(nA)]
                  + [plan.ds_omega[k] for k in range(plan.l)
                     for _ in range(nB)])
    slots2 = (tuple(a_slots[t] for _ in range(plan.l) for t in range(nA))
              + tuple(off + b_slots[t] for _ in range(plan.l)
                      for t in range(nB)))
    step2 = compile_hlt(ctx, step2_sets, level=level - 1, schedule=schedule,
                        rotation_chunk=rotation_chunk, ct_slots=slots2)
    prog = BlockMMProgram(
        ctx, plan,
        BlockMMPlan(m=plan.m, l=plan.l, n=plan.n, grid=grid,
                    schedule=schedule, level=level,
                    step1=step1.plan, step2=step2.plan),
        step1, step2)
    _enforce_verify(ctx, prog)
    ctx._compiled[memo_key] = prog
    return prog


# ---------------------------------------------------------------------------
# compile_hemm_chain -> HEMMChainProgram (Y = X·W1·…·Wk, zero decrypts)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class HEMMChainPlan:
    """Inspectable compile summary for a consecutive HE MM chain.

    ``dims = (m, l, n1, …, nk)``; hop h multiplies the running m×dims[h+1]
    ciphertext by a dims[h+1]×dims[h+2] weight.  ``hop_levels`` are the
    per-hop INPUT levels (``level - 3h`` — each hemm consumes 3);
    ``hop_out`` the ``trace_chain``-predicted (level, scale) state at each
    hop's OUTPUT, which execution matches float-exactly; ``schedules`` the
    jointly selected per-hop HLT schedules (``select_chain_schedules``).
    """

    dims: tuple
    shapes: tuple                       # (m, l, n) per hop
    schedules: tuple
    level: int                          # chain input level
    hop_levels: tuple                   # input level per hop
    hop_out: tuple                      # CtState out of each hop (predicted)
    weight_scale: float                 # weight scale the trace assumed
    repack: str                         # "fold" | "explicit" (HeMMChainPlan)
    hops: tuple                         # per-hop HEMMPlan

    @property
    def k(self) -> int:
        """Number of hops (matrix multiplications) in the chain."""
        return len(self.hops)

    @property
    def depth(self) -> int:
        """Multiplicative depth: 3 levels per hop."""
        return 3 * self.k

    @property
    def out_level(self) -> int:
        """Level of the final output ciphertext (``level - 3k``)."""
        return self.hop_out[-1].level

    @property
    def out_scale(self) -> float:
        """Scale of the final output ciphertext (trace-predicted)."""
        return self.hop_out[-1].scale

    @property
    def rotations(self) -> int:
        """Total rotation count across all hops (Table-I accounting)."""
        return sum(h.rotations for h in self.hops)

    @property
    def hop_bytes(self) -> tuple:
        """Per-hop deduped operand bytes (keys + diagonals, both stages)."""
        return tuple(h.operand_bytes for h in self.hops)

    @property
    def operand_bytes(self) -> int:
        """Arena-resident operand bytes for the whole chain (deduped)."""
        return sum(self.hop_bytes)

    @property
    def hoist_bytes(self) -> int:
        """Hoisting-product bytes after ct-slot dedup: each hop's Step 2
        stores 2 unique products (one per input), never 2·l."""
        return sum(h.hoist_bytes for h in self.hops)

    @property
    def collective_bytes(self) -> int:
        """Predicted cross-device bytes per execution — under the sharded
        schedule exactly 2 merged-ModDown psums per hop, nothing between
        hops (the re-pack is an identity fold, Mult/Rescale/Add are
        limb-local)."""
        return sum(h.collective_bytes for h in self.hops)


class HEMMChainProgram:
    """A compiled chain: ``prog(ctX, [ctW1, …, ctWk]) -> ctY`` with Y =
    X·W1·…·Wk entirely under encryption — no decrypt round-trip between
    hops.

    Hop h's column-major m×n output occupies slots [0, m·n) and IS hop
    h+1's σ input encoding (the identity re-pack fold, core/hemm.py
    :class:`~repro.core.hemm.ChainRepack`), so hops connect by plain
    dataflow: each intermediate stays a ciphertext at the traced
    (level, scale).  Weights enter at their hop's input level
    (:meth:`encrypt_weights`).

    Counter semantics: one call bumps ``program_launches`` by k+1 (the
    chain itself + each hop's HEMMProgram) and ``hlt_launches`` by 2·k
    under batched schedules (Step-1 + Step-2 launch per hop); the engine's
    ``op_counts["decrypts"]`` stays untouched — the zero-intermediate-
    decrypt claim tests assert.
    """

    def __init__(self, ctx: HEContext, chain, plan: HEMMChainPlan, hops):
        self.ctx = ctx
        self.chain = chain                  # core/hemm.py HeMMChainPlan
        self.plan = plan
        self._hops = tuple(hops)            # per-hop HEMMProgram
        self._gen = ctx._generation

    def encrypt_weights(self, Ws, rng) -> list:
        """Encrypt W1..Wk at their hop input levels (``plan.hop_levels``)
        with ``plan.weight_scale`` — exactly the weight states the compile
        trace assumed, so execution matches ``plan.hop_out`` float-exactly."""
        from repro.core.hemm import encrypt_matrix
        plan = self.plan
        assert len(Ws) == plan.k, (len(Ws), plan.k)
        cts = []
        for W, (_, l, n), lvl in zip(Ws, plan.shapes, plan.hop_levels,
                                     strict=True):
            W = np.asarray(W, dtype=np.float64)
            assert W.shape == (l, n), (W.shape, (l, n))
            cts.append(encrypt_matrix(self.ctx.eng, self.ctx.keys, W, rng,
                                      level=lvl, scale=plan.weight_scale))
        return cts

    def run_hops(self, ctX: Ciphertext, weights) -> list:
        """Run the chain, returning every hop's output ciphertext (the last
        is the chain output) — the per-hop handle the trace-exactness tests
        compare against ``plan.hop_out``."""
        self.ctx._check_generation(self._gen)
        self.ctx.counters["program_launches"] += 1
        plan = self.plan
        assert ctX.level == plan.level, (ctX.level, plan.level)
        assert len(weights) == plan.k, (len(weights), plan.k)
        ct, outs = ctX, []
        for h, (prog, ctW) in enumerate(zip(self._hops, weights,
                                            strict=True)):
            assert ctW.level == plan.hop_levels[h], \
                f"hop {h} weight at level {ctW.level}, chain expects " \
                f"{plan.hop_levels[h]} (encrypt_weights encrypts correctly)"
            ct = prog(ct, ctW)
            outs.append(ct)
        return outs

    def __call__(self, ctX: Ciphertext, weights) -> Ciphertext:
        return self.run_hops(ctX, weights)[-1]


def compile_hemm_chain(ctx: HEContext, chain, *, level: Optional[int] = None,
                       schedule: Optional[str] = None,
                       schedules: Optional[Sequence[str]] = None,
                       rotation_chunk: Optional[int] = None,
                       weight_scale: Optional[float] = None
                       ) -> HEMMChainProgram:
    """Compile a consecutive HE MM chain (core/hemm.py ``plan_hemm_chain``)
    into a reusable :class:`HEMMChainProgram`.

    The compile is trace-first: ``repro.analysis.trace_chain`` runs over
    the hop plans BEFORE anything is built.  A chain deeper than the
    modulus chain allows (input ``level`` < 3·k — the trace's LS001/LS003
    findings) cannot compile: under ``ctx.verify="error"`` it raises
    :class:`~repro.analysis.VerificationError` carrying the trace
    diagnostics; under ``"warn"``/``"off"`` it raises ``ValueError`` (there
    is no silent wrong-answer region — an unfittable chain NEVER returns a
    program).  ``repro.analysis.max_chain_depth`` names the largest k that
    fits.

    ``schedule`` forces one schedule for every hop; ``schedules`` gives an
    explicit per-hop tuple; with neither, ``select_chain_schedules``
    chooses per-hop schedules JOINTLY — the exact ``select_schedule`` byte
    terms per hop plus an ICI-penalized boundary term when adjacent hops
    change residency class (a hop's output layout is the next hop's input).
    Memoized on the context like every other compile.
    """
    assert ctx.keys is not None, "HEContext has no keys; call ctx.keygen()"
    eng = ctx.eng
    params = eng.params
    level = params.L if level is None else level
    ws = params.scale if weight_scale is None else float(weight_scale)
    k = chain.k

    from repro.analysis.level_scale import trace_chain   # deferred: analysis
    trace = trace_chain(eng.ctx.moduli_host, chain.hops, level=level,
                        scale=params.scale, weight_scale=ws)
    if level < 3 * k:       # == the trace's LS001/LS003 findings fire
        if ctx.verify == "error":
            from repro.analysis.diagnostics import VerificationError
            raise VerificationError(trace.diagnostics)
        msgs = "; ".join(str(d) for d in trace.diagnostics
                         if d.severity == "error")
        raise ValueError(
            f"chain of {k} hops needs input level >= {3 * k} "
            f"(3 per hemm hop), got {level}: {msgs}")

    if schedule is not None:
        assert schedules is None, "pass schedule= or schedules=, not both"
        scheds = (schedule,) * k
    elif schedules is not None:
        scheds = tuple(schedules)
        assert len(scheds) == k, (len(scheds), k)
    else:
        scheds = select_chain_schedules(
            params,
            [dict(d=hp.ds_sigma.d, ctb=2 * hp.l, n_uniq=2,
                  nbeta=len(eng.tools.digit_bases(level - 3 * h)),
                  level=level - 3 * h)
             for h, hp in enumerate(chain.hops)],
            headroom=ctx.vmem_headroom,
            n_model=ctx.n_model, n_ct=ctx.n_ct)

    memo_key = ("hemm_chain", _StrongKey(chain), scheds, level,
                rotation_chunk, ws, ctx.verify)
    hit = ctx._compiled.get(memo_key)
    if hit is not None:
        return hit

    hop_progs = [
        compile_hemm(ctx, hp, level=level - 3 * h, schedule=scheds[h],
                     rotation_chunk=rotation_chunk)
        for h, hp in enumerate(chain.hops)]
    plan = HEMMChainPlan(
        dims=chain.dims,
        shapes=tuple((hp.m, hp.l, hp.n) for hp in chain.hops),
        schedules=scheds, level=level,
        hop_levels=tuple(level - 3 * h for h in range(k)),
        hop_out=trace.hop_states,
        weight_scale=ws, repack=chain.repack,
        hops=tuple(p.plan for p in hop_progs))
    prog = HEMMChainProgram(ctx, chain, plan, hop_progs)
    _enforce_verify(ctx, prog)
    ctx._compiled[memo_key] = prog
    return prog
