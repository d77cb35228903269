"""Fused straight-line lowering of field-ALU VM programs (ISSUE 13 + 15).

WHY A SECOND LOWERING. The scan interpreter (ops/vm.py) pays a fixed
per-step cost that has nothing to do with the math: every step gathers
full lane-width operand blocks out of a ~600-register file, runs the ALU
over EVERY lane (idle ones included — the hard part fills ~5% of the mul
lanes), and scatters the results back with a whole-register-file copy.
Measured at ~280 µs/step, the interpreter — not the field arithmetic —
is the device-side bottleneck. This module compiles the SAME assembled
program (the exact schedule the interpreter would run, via
``ops/vm_analysis.lowering_plan``) into straight-line jax code: one SSA
value per real op — no register file, no dynamic op indexing, no idle
lanes — with each scheduled level running ONE vectorized
``fq.mont_mul_u64`` / stacked carry-add over exactly its live operands.

STRUCTURAL DEDUP (ISSUE 15). The PR 13 lowering chunked the schedule
into fixed level groups and paid one XLA compile per chunk per batch
shape — ~0.4 s/level on CPU, minutes per program cold. But a
955–4864-level square-and-multiply ladder is a handful of distinct
level-chunk *shapes* stamped out dozens of times, so the lowering now:

  - detects the ladder period from per-level op signatures and aligns
    the chunk window to it (``vm_analysis.detect_period`` /
    ``select_window``) so every steady-state window lands on one phase;
  - canonicalizes each chunk up to constant values and live-set
    permutation (``vm_analysis.structural_plan``): constants become
    runtime operand rows, carry wiring becomes per-instance ``in_idx``/
    ``boundary_idx`` gather tables, and the canonical body hashes into a
    STRUCTURE key — XLA compiles once per distinct structure (shared
    across chunks, programs, and via the persistent cache, processes)
    and the executor replays the compiled structure with per-instance
    operand tables;
  - folds runs of consecutive same-structure chunks into ONE
    ``lax.scan`` super-op over the stacked operand tables
    (``CONSENSUS_SPECS_TPU_VM_SUPEROP``) where the vmlint cost model
    says per-level dispatch glue dominates the real ALU work — one
    compile and one dispatch for a whole ladder mid-section.

Measured on the 2-core container: g2_subgroup fold-1 (955 levels) goes
from 40 per-chunk compiles to 7 distinct structures (25 of 35 chunks
riding scan runs); ``CONSENSUS_SPECS_TPU_VM_DEDUP=0`` pins the per-chunk
baseline.

Outputs stay BIT-IDENTICAL to the interpreter: the per-op integer
functions (Montgomery reduce / carry add / borrowless sub) are the same
exact maps, and tests + the vmexec smoke hold both backends to the
exact-int IR oracle (``vm_analysis.eval_ir``) limb for limb.

Routing (``CONSENSUS_SPECS_TPU_VM_EXEC``): ``interp`` pins the scan VM,
``fused`` pins this lowering, ``auto`` (default) runs fused only when
the artifact is ALREADY COMPILED in-process for the requested batch
shape AND the measured warm-ms/row pair (in-process ledger, seeded from
the ``.vm_cache`` plan's persisted measurements) says fused wins. With
``CONSENSUS_SPECS_TPU_VM_WARM_BG=1`` a missing shape additionally
enqueues a BACKGROUND warm — a daemon thread compiles it off the
serving path (seconds at dedup'd cost) and auto flips to fused when
ready; the serving call itself still never pays a compile. Any
trace/compile/run failure falls back to the interpreter with a
``vm/fused_fallback`` flight event; the Pallas dispatch modes of the
interpreter keep the scan path, while ``CONSENSUS_SPECS_TPU_VM_FUSED_
PALLAS=1`` routes the chunk bodies' Montgomery multiplies through the
``pallas_fq`` kernel (cross-checked bit-identical). The batch axis
semantics match ``vm.execute`` exactly — under a ``mesh`` the carry is
sharded over the mesh's axes and every chunk stays batch-elementwise.

Fused plans are disk-cached next to the interpreter tensors under
``.vm_cache/``: per-program ``fusedplan_l<ver>_…`` entries hold the
instance tables + measured ms/row pair and REFERENCE shared
``fusedstruct_l<ver>_<hash>.pkl`` entries holding the canonical bodies
— one struct entry serves every plan whose canonical form matches.
``prune_vm_cache`` evicts the retired PR 13 per-program ``fused_l…``
keying outright, keeps struct entries while any plan references them,
and a corrupted entry of either kind falls back to re-derivation.
"""
import os
import sys
import threading
import time
from collections import deque
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import fq, vm, vm_analysis

# bump when the lowering's emitted code or plan format changes: re-keys
# every fused .vm_cache artifact independently of the interpreter tensors
# (2 = ISSUE 15 structural dedup — the PR 13 per-program fused_l1 plans
# can never load again and prune evicts them on sight)
LOWERING_VERSION = 2

# bump when the PLANNING heuristics (window selection, period detection,
# boundary resync) change without changing the emitted code: a cached
# plan from an older policy is still CORRECT but not what the current
# planner would produce, so it re-derives instead of silently pinning
# old decisions (3 = period-resynced boundaries + cost-compared
# candidates + width-normalized inter-chunk carries)
PLAN_POLICY = 3


def exec_mode() -> str:
    """CONSENSUS_SPECS_TPU_VM_EXEC, normalized (interp | fused | auto)."""
    v = os.environ.get("CONSENSUS_SPECS_TPU_VM_EXEC", "auto")
    return v if v in ("interp", "fused", "auto") else "auto"


# warn-once env parsing (ISSUE 15 satellite): a malformed or
# non-positive knob must never raise mid-call — one stderr line, then
# the documented default
_ENV_WARNED = set()


def _env_warn_once(name: str, raw, default) -> None:
    if name not in _ENV_WARNED:
        _ENV_WARNED.add(name)
        print(
            f"vm_compile: ignoring invalid {name}={raw!r} — "
            f"using the default ({default})",
            file=sys.stderr,
        )


def _env_pos_int(name: str, default: int) -> int:
    raw = os.environ.get(name)
    if raw is None or raw == "" or raw == "0":
        return default  # unset/0 = "use the default", not an error
    try:
        v = int(raw)
    except ValueError:
        v = None
    if v is None or v <= 0:
        _env_warn_once(name, raw, default)
        return default
    return v


def chunk_steps() -> int:
    """Target scheduled levels per traced chunk
    (CONSENSUS_SPECS_TPU_VM_FUSED_CHUNK, default
    vm_analysis.FUSED_CHUNK_STEPS; the dedup window aligns this to the
    detected ladder period, within 2x). Invalid or non-positive values
    warn once and fall back to the default."""
    return _env_pos_int("CONSENSUS_SPECS_TPU_VM_FUSED_CHUNK",
                        vm_analysis.FUSED_CHUNK_STEPS)


def dedup_enabled() -> bool:
    """CONSENSUS_SPECS_TPU_VM_DEDUP: structural chunk dedup (default on;
    `0` pins the PR 13 one-compile-per-chunk baseline). Anything else
    warns once and keeps the default."""
    raw = os.environ.get("CONSENSUS_SPECS_TPU_VM_DEDUP")
    if raw is None or raw in ("1", ""):
        return True
    if raw == "0":
        return False
    _env_warn_once("CONSENSUS_SPECS_TPU_VM_DEDUP", raw, "1")
    return True


def _superop_env() -> Optional[int]:
    """CONSENSUS_SPECS_TPU_VM_SUPEROP parsed: None = auto (the
    ``vm_analysis.auto_min_run`` cost-model rule), 0 = off, int >= 2 =
    forced minimum run length. Invalid values warn once -> auto."""
    raw = os.environ.get("CONSENSUS_SPECS_TPU_VM_SUPEROP", "auto")
    if raw in ("auto", ""):
        return None
    if raw in ("off", "0"):
        return 0
    try:
        v = int(raw)
        if v >= 2:
            return v
    except ValueError:
        pass
    _env_warn_once("CONSENSUS_SPECS_TPU_VM_SUPEROP", raw, "auto")
    return None


def superop_min_run(plan: Dict) -> int:
    """Minimum same-structure run length folded into one lax.scan
    super-op (0 = never fold). ``auto`` (default) folds runs of >= 3
    only when the vmlint cost model says per-level dispatch glue
    dominates the program's real ALU work (the fold-1 ladder regime the
    measured ~30 µs/level XLA launch overhead hurts most)."""
    v = _superop_env()
    if v is not None:
        return v
    return vm_analysis.auto_min_run(plan)


def _fused_pallas() -> bool:
    """CONSENSUS_SPECS_TPU_VM_FUSED_PALLAS=1 routes the chunk bodies'
    Montgomery multiplies through the pallas_fq kernel (the hand-tiled
    attack on per-level op-launch glue; cross-checked bit-identical)."""
    return os.environ.get("CONSENSUS_SPECS_TPU_VM_FUSED_PALLAS") == "1"


def _bg_warm_enabled() -> bool:
    """CONSENSUS_SPECS_TPU_VM_WARM_BG=1: auto-routed executions whose
    shape is not yet compiled enqueue a background warm instead of
    staying interpreter-only forever."""
    return os.environ.get("CONSENSUS_SPECS_TPU_VM_WARM_BG") == "1"


# lowering-plane observability: compiled plans, fused executions,
# interpreter fallbacks, and the structural compile-unit hit/miss split
# — exported as vm.fused_* gauges
_COUNTERS = {"programs": 0, "executions": 0, "fallbacks": 0,
             "struct_hits": 0, "struct_misses": 0}
_COMPILED_STRUCTS = set()  # distinct structure keys compiled in-process


def _export_gauges() -> None:
    from . import profiling

    profiling.set_gauge("vm.fused_programs", _COUNTERS["programs"])
    profiling.set_gauge("vm.fused_executions", _COUNTERS["executions"])
    profiling.set_gauge("vm.fused_fallbacks", _COUNTERS["fallbacks"])
    profiling.set_gauge("vm.fused_structs", len(_COMPILED_STRUCTS))
    profiling.set_gauge("vm.fused_struct_hits", _COUNTERS["struct_hits"])
    profiling.set_gauge("vm.fused_struct_misses",
                        _COUNTERS["struct_misses"])


def _flight_note(kind: str, **data) -> None:
    try:
        from ..obs import flight

        flight.note("vm", kind, **data)
    except Exception:
        pass


# ---------------------------------------------------------------------------
# structure emission: canonical bodies -> jax functions
# ---------------------------------------------------------------------------


def _make_struct_core(body: Dict, pallas: bool):
    """The straight-line step function of ONE canonical chunk structure:

        (S, in_idx, consts, boundary_idx) -> S'

    where ``S`` is the (batch, m_in, L) inter-chunk carry in the
    INSTANCE's live-register order, ``in_idx`` gathers the canonical
    input slots out of it, ``consts`` is the instance's (n_const, L)
    Montgomery constant rows, and ``boundary_idx`` assembles the next
    carry from [canonical body outputs ++ S]. All three tables are
    RUNTIME operands — the traced graph depends only on the canonical
    structure (and shapes), which is what lets one XLA executable serve
    every instance of the structure.

    Per-level math is the interpreter's exact map: one vectorized
    Montgomery mul over the level's mul lanes, and the add and sub lanes
    sharing ONE stacked carry propagation (adds first, then the
    borrowless-complement subs), ``a + (is_sub ? (MP+1)+(MASK-b) : b)``.
    """
    levels = body["levels"]
    out_ids = body["out"]
    mp1 = np.asarray(vm._MP_PLUS_1)
    L = fq.NUM_LIMBS
    if pallas:
        from . import pallas_fq

        mont_mul = pallas_fq.mont_mul
    else:
        mont_mul = fq.mont_mul_u64

    def core(S, in_idx, consts, boundary_idx):
        batch = S.shape[:-2]
        X = jnp.take(S, in_idx, axis=-2)
        env: List[Optional[jnp.ndarray]] = []
        zero = None

        def get(ref):
            nonlocal zero
            tag, i = ref
            if tag == "i":
                return X[..., i, :]
            if tag == "d":
                return env[i]
            if tag == "c":
                return jnp.broadcast_to(consts[i], batch + (L,))
            if zero is None:
                zero = jnp.zeros(batch + (L,), dtype=jnp.uint64)
            return zero

        for lv in levels:
            mul_ops, add_ops, sub_ops = lv
            new: List[jnp.ndarray] = []
            if mul_ops:
                a = jnp.stack([get(o[0]) for o in mul_ops], axis=-2)
                b = jnp.stack([get(o[1]) for o in mul_ops], axis=-2)
                m = mont_mul(a, b)
                for j in range(len(mul_ops)):
                    new.append(m[..., j, :])
            if add_ops or sub_ops:
                la = jnp.stack(
                    [get(o[0]) for o in add_ops + sub_ops], axis=-2)
                lb = jnp.stack(
                    [get(o[1]) for o in add_ops + sub_ops], axis=-2)
                if sub_ops:
                    comp = mp1 + (jnp.uint64(fq.MASK)
                                  - lb[..., len(add_ops):, :])
                    rhs = (jnp.concatenate(
                        [lb[..., :len(add_ops), :], comp], axis=-2)
                        if add_ops else comp)
                else:
                    rhs = lb
                ssum = fq._carry_limbs(la + rhs, out_limbs=L + 1)[..., :L]
                for j in range(len(add_ops) + len(sub_ops)):
                    new.append(ssum[..., j, :])
            # defs become visible at the NEXT level only (matching the
            # interpreter's pre-step register-file read)
            env.extend(new)
        if out_ids:
            outs = jnp.stack([env[i] for i in out_ids], axis=-2)
            C = jnp.concatenate([outs, S], axis=-2)
        else:
            C = S
        return jnp.take(C, boundary_idx, axis=-2)

    return core


def _make_scan_fn(core):
    """Scan super-op over a run of same-structure instances: the carry S
    keeps one shape while (in_idx, consts, boundary_idx) stacks ride the
    scan axis — one compile and one dispatch for the whole run."""

    def fn(S, in_idx_stack, const_stack, b_idx_stack):
        def step(carry, xs):
            ii, cc, bb = xs
            return core(carry, ii, cc, bb), None

        S, _ = jax.lax.scan(
            step, S, (in_idx_stack, const_stack, b_idx_stack))
        return S

    return fn


def _widen_u32(x):
    return x.astype(jnp.uint64)


def _take_rows(S, idx):
    return jnp.take(S, idx, axis=-2)


_WIDEN_JIT = jax.jit(_widen_u32)
_TAKE_JIT = jax.jit(_take_rows)


# shared compile-unit caches (module-level on purpose: a structure
# compiled for one program serves every other program whose canonical
# form matches; the persistent XLA cache extends the same sharing across
# processes because the traced graphs carry no program-specific data).
# _COMPILE_LOCK serializes the check-then-compile per unit so the
# background-warm thread and a foreground warm never pay the same
# minutes-scale XLA compile twice (XLA CPU serializes compiles behind a
# global lock anyway, so duplication would double time-to-ready)
_STRUCT_JIT: Dict[tuple, object] = {}  # (mode, struct, pallas) -> jitted fn
_STRUCT_AOT: Dict[tuple, object] = {}  # (+ shapes) -> compiled executable
_COMPILE_LOCK = threading.Lock()


def _struct_jit(mode: str, struct: str, body: Dict, pallas: bool):
    key = (mode, struct, pallas)
    fn = _STRUCT_JIT.get(key)
    if fn is None:
        core = _make_struct_core(body, pallas)
        fn = jax.jit(core if mode == "step" else _make_scan_fn(core))
        _STRUCT_JIT[key] = fn
    return fn


# ---------------------------------------------------------------------------
# the per-program executor
# ---------------------------------------------------------------------------


def _const_block(vals: List[int]) -> np.ndarray:
    block = np.zeros((len(vals), fq.NUM_LIMBS), dtype=np.uint64)
    for i, v in enumerate(vals):
        block[i] = fq.to_mont_int(v)
    return block


class FusedProgram:
    """Compiled artifact for one assembled Program: an execution plan of
    structural segments — ``step`` (one chunk instance through its
    structure's compiled function) and ``scan`` (a run of same-structure
    instances through one lax.scan super-op) — plus the per-instance
    operand tables each segment feeds at run time."""

    def __init__(self, program: "vm.Program", plan: Dict):
        self.program = program
        self.plan = plan
        self.seen_shapes = set()  # (batch_shape, sharded) already traced
        self.compile_s: Dict[tuple, float] = {}  # batch -> AOT wall secs
        self._pallas = _fused_pallas()
        structs = plan["structs"]
        instances = plan["chunks"]
        self._n_inputs = len(plan["inputs"])
        self._final_idx = None
        if not instances:
            # zero scheduled steps: outputs select straight off the inputs
            pos = {r: i for i, r in enumerate(plan["inputs"])}
            self._final_idx = np.asarray(
                [pos[r] for r in plan["outputs"]], dtype=np.int32)
        tables = [
            (np.asarray(c["in_idx"], dtype=np.int32),
             _const_block(c["consts"]),
             np.asarray(c["boundary_idx"], dtype=np.int32))
            for c in instances
        ]
        # segment plan: fold qualifying runs into FIXED-SIZE scan blocks
        # (one compiled scan executable per structure, any run length)
        min_run = superop_min_run(plan) if dedup_enabled() else 0
        segments = []  # ("step", ci, tables, 1) | ("scan", ci, stacks, n)
        for seg in vm_analysis.scan_blocks(instances, min_run):
            if seg[0] == "step":
                segments.append(("step", seg[1], tables[seg[1]], 1))
            else:
                ci, length = seg[1], seg[2]
                stacks = tuple(
                    np.stack([tables[ci + j][t] for j in range(length)])
                    for t in range(3))
                segments.append(("scan", ci, stacks, length))
        self._segments = segments
        self._instances = instances
        self._structs = structs
        self._aot: Dict[tuple, List] = {}  # batch shape -> compiled units
        self.struct_stats = {
            "chunks": len(instances),
            "distinct_structs": len(structs),
            "window": plan.get("window"),
            "period": plan.get("period"),
            "resync": plan.get("resync", False),
            "superop_segments": sum(
                1 for s in segments if s[0] == "scan"),
        }

    # -- compile-unit bookkeeping ------------------------------------------

    def _unit_specs(self, batch: tuple):
        """(global cache key, lowering argspecs, jitted fn) per compile
        unit for one unsharded batch shape: the entry widen, every
        segment, and the zero-chunk final gather."""
        L = fq.NUM_LIMBS
        i32 = jnp.int32
        u64 = jnp.uint64
        units = []
        in_spec = jax.ShapeDtypeStruct(
            batch + (self._n_inputs, L), jnp.uint32)
        units.append((("widen", batch, self._n_inputs),
                      (in_spec,), _WIDEN_JIT))
        if self._final_idx is not None:
            units.append((
                ("take", batch, self._n_inputs, len(self._final_idx)),
                (jax.ShapeDtypeStruct(batch + (self._n_inputs, L), u64),
                 jax.ShapeDtypeStruct((len(self._final_idx),), i32)),
                _TAKE_JIT))
        for seg in self._segments:
            kind, ci = seg[0], seg[1]
            inst = self._instances[ci]
            struct = inst["struct"]
            body = self._structs[struct]
            if kind == "step":
                specs = (
                    jax.ShapeDtypeStruct(batch + (inst["m_in"], L), u64),
                    jax.ShapeDtypeStruct((body["n_in"],), i32),
                    jax.ShapeDtypeStruct((body["n_const"], L), u64),
                    jax.ShapeDtypeStruct((inst["m_out"],), i32),
                )
                key = ("step", struct, self._pallas, batch,
                       inst["m_in"], inst["m_out"])
            else:
                n = seg[3]
                specs = (
                    jax.ShapeDtypeStruct(batch + (inst["m_in"], L), u64),
                    jax.ShapeDtypeStruct((n, body["n_in"]), i32),
                    jax.ShapeDtypeStruct((n, body["n_const"], L), u64),
                    jax.ShapeDtypeStruct((n, inst["m_out"]), i32),
                )
                key = ("scan", struct, self._pallas, batch,
                       inst["m_in"], n)
            units.append((key, specs,
                          _struct_jit(kind, struct, body, self._pallas)))
        return units

    def warm(self, batch: tuple) -> float:
        """Trace + XLA-compile every compile unit for one (unsharded)
        batch shape through the AOT API — each unit's shapes are
        statically known, so the whole pipeline compiles without running
        anything. Distinct structures compile ONCE: a unit already
        compiled (by this program, another program sharing the
        structure, or an earlier batch of the same canonical shape)
        journals ``vm/structural_hit``; a real compile journals
        ``vm/structural_miss``. Returns the wall seconds (0.0 when this
        batch is already warm in-process). Compiled executables also
        land in the persistent XLA cache — and because the traced graphs
        are canonical (no inlined program constants), a DIFFERENT
        program's matching structure hits that cache across processes
        too. Units compile sequentially on purpose: XLA CPU serializes
        compilation behind a global lock in this jax build."""
        key = tuple(batch)
        if key in self._aot:
            return 0.0
        t0 = time.perf_counter()
        compiled = []
        hits = misses = 0
        for gkey, specs, fn in self._unit_specs(key):
            with _COMPILE_LOCK:
                unit = _STRUCT_AOT.get(gkey)
                if unit is None:
                    tu = time.perf_counter()
                    unit = fn.lower(*specs).compile()
                    _STRUCT_AOT[gkey] = unit
                    misses += 1
                    _COUNTERS["struct_misses"] += 1
                    if gkey[0] in ("step", "scan"):
                        _COMPILED_STRUCTS.add(gkey[1])
                        _flight_note(
                            "structural_miss", unit=gkey[0],
                            struct=gkey[1][:12],
                            seconds=round(time.perf_counter() - tu, 3))
                else:
                    hits += 1
                    _COUNTERS["struct_hits"] += 1
                    if gkey[0] in ("step", "scan"):
                        _flight_note("structural_hit", unit=gkey[0],
                                     struct=gkey[1][:12])
            compiled.append(unit)
        self._aot[key] = compiled
        dt = time.perf_counter() - t0
        self.compile_s[key] = dt
        _export_gauges()
        _flight_note(
            "fused_warm", batch=list(key), units=len(compiled),
            struct_hits=hits, struct_misses=misses,
            seconds=round(dt, 3))
        return dt

    def _run_units(self, carry, units):
        carry = units[0](carry)  # widen u32 -> u64
        if self._final_idx is not None:
            return units[1](carry, self._final_idx)
        for seg, unit in zip(self._segments, units[1:]):
            carry = unit(carry, *seg[2])
        return carry

    def run(self, stacked_u32: np.ndarray, mesh=None) -> jnp.ndarray:
        if mesh is not None:
            # sharded path: plain jitted unit functions — GSPMD
            # propagates the batch-axis sharding through the (purely
            # batch-elementwise) graphs, zero collectives; the operand
            # tables replicate
            from jax.sharding import NamedSharding, PartitionSpec as P

            carry = jax.device_put(
                jnp.asarray(stacked_u32),
                NamedSharding(mesh, P(mesh.axis_names)))
            units = [_WIDEN_JIT]
            if self._final_idx is not None:
                units.append(_TAKE_JIT)
            else:
                units.extend(
                    _struct_jit(seg[0],
                                self._instances[seg[1]]["struct"],
                                self._structs[
                                    self._instances[seg[1]]["struct"]],
                                self._pallas)
                    for seg in self._segments)
            return self._run_units(carry, units)
        carry = jnp.asarray(stacked_u32)
        units = self._aot.get(carry.shape[:-2])
        if units is None:
            self.warm(carry.shape[:-2])
            units = self._aot[carry.shape[:-2]]
        return self._run_units(carry, units)


# id(program) -> FusedProgram; values hold the program strongly, so a
# live entry's id can never be recycled by a different Program
_FUSED: Dict[int, FusedProgram] = {}


# ---------------------------------------------------------------------------
# disk cache: per-program plans referencing shared structure entries
# ---------------------------------------------------------------------------


def device_kind_tag() -> str:
    """The device kind JAX runs on, as a file-name tag: the plan's
    measured ms/row pick fused or interpreter for THIS device, so a CPU
    measurement never picks the chip's route."""
    import re

    kind = jax.devices()[0].device_kind
    return re.sub(r"[^0-9a-z]+", "-", kind.lower()).strip("-")


def _plan_cache_path(program) -> Optional[str]:
    """Disk path for this program's lowering plan on this device kind, or
    None when the program carries no cache identity (directly-assembled
    test programs, pre-meta pickles). ``fusedplan_l<ver>`` re-keys fused
    artifacts independently of the interpreter tensors; the retired PR 13
    ``fused_l…`` per-program keying is evicted by ``prune_vm_cache``."""
    meta = program.meta or {}
    key = meta.get("fused_key")
    if not key:
        return None
    kind, k, fold, fp = key
    from . import bls_backend as bb

    return os.path.join(
        bb._vm_cache_dir(),
        f"fusedplan_l{LOWERING_VERSION}_v{bb._VM_CACHE_VERSION}_{fp}"
        f"_{kind}_k{k}_f{fold}_w{meta.get('w_mul', 0)}x"
        f"{meta.get('w_lin', 0)}_p{program.n_steps}_c{chunk_steps()}"
        f"_d{device_kind_tag()}.pkl",
    )


def _struct_cache_path(struct: str, cache_dir: str = None) -> str:
    if cache_dir is None:
        from . import bls_backend as bb

        cache_dir = bb._vm_cache_dir()
    return os.path.join(
        cache_dir, f"fusedstruct_l{LOWERING_VERSION}_{struct}.pkl")


def _load_plan(program) -> Optional[Dict]:
    """The disk-cached structural plan for ``program`` at the CURRENT
    chunk setting, with every referenced shared structure entry loaded
    into ``plan["structs"]`` — or None (absent, unreadable, stale
    chunking, or any referenced structure entry missing/corrupted: the
    caller re-derives and re-stores, never errors)."""
    import pickle

    path = _plan_cache_path(program)
    if path is None or not dedup_enabled():
        return None
    try:
        with open(path, "rb") as fh:
            plan = pickle.load(fh)
        if (plan.get("format") != 2
                or plan.get("policy") != PLAN_POLICY
                or plan.get("sched_steps") is None
                or plan.get("chunk_steps") != chunk_steps()):
            return None
        structs: Dict[str, Dict] = {}
        for ref in plan.get("struct_refs", ()):
            spath = _struct_cache_path(ref)
            with open(spath, "rb") as fh:
                body = pickle.load(fh)
            if not isinstance(body, dict) or "levels" not in body:
                return None  # corrupted structure entry: re-derive
            structs[ref] = body
            try:
                os.utime(spath)
            except OSError:
                pass
        need = {c["struct"] for c in plan.get("chunks", ())}
        if not need <= set(structs):
            return None
        plan["structs"] = structs
        try:
            os.utime(path)  # prune evicts by idle age
        except OSError:
            pass
        return plan
    except Exception:
        pass
    return None


def _store_plan(program, plan: Dict) -> None:
    """Persist the plan (instance tables + measured pair) and each
    referenced structure body as a SHARED ``fusedstruct_…`` entry —
    a structure entry another program already wrote is reused as-is."""
    import pickle

    path = _plan_cache_path(program)
    if path is None or not dedup_enabled():
        return
    try:
        slim = {k: v for k, v in plan.items() if k != "structs"}
        slim["struct_refs"] = sorted(plan.get("structs", {}))
        for ref, body in plan.get("structs", {}).items():
            # unconditional rewrite on purpose: the body is canonical
            # (same key == same bytes), so this is idempotent — and it
            # self-heals a corrupted shared entry the moment any
            # referencing program re-derives
            spath = _struct_cache_path(ref)
            tmp = f"{spath}.{os.getpid()}.tmp"
            with open(tmp, "wb") as fh:
                pickle.dump(body, fh)
            os.replace(tmp, spath)
        tmp = f"{path}.{os.getpid()}.tmp"
        with open(tmp, "wb") as fh:
            pickle.dump(slim, fh)
        os.replace(tmp, path)
    except Exception:
        pass  # the disk cache is an optimization only


def _derive_plan(program) -> Dict:
    """Structural lowering plan from scratch: per-level real-op columns,
    ladder-period boundary selection (uniform window vs period-resync,
    whichever predicts the lower cold-compile cost), and the canonical
    structure split — all via the ``vm_analysis.plan_structures``
    pipeline vmlint reports on."""
    base = chunk_steps()
    dedup = dedup_enabled()
    plan_src, sp, info = vm_analysis.plan_structures(
        program, base, dedup=dedup, min_run=_superop_env())
    return {
        "format": 2,
        "policy": PLAN_POLICY,
        "sched_steps": plan_src["sched_steps"],
        "chunk_steps": base,
        "window": info["window"],
        "period": info["period"],
        "resync": info["resync"],
        "inputs": plan_src["inputs"],
        "outputs": plan_src["outputs"],
        "n_mul": plan_src["n_mul"],
        "n_lin": plan_src["n_lin"],
        "chunks": sp["instances"],
        "structs": sp["structs"],
        "measured": {},
    }


def _seed_stats_from_plan(program, plan: Dict) -> None:
    """Adopt the plan's persisted warm-ms/row measurements into the
    in-process ledger (keeping any better number this process measured) —
    this is what lets a FRESH process's ``auto`` route serve the winner a
    past process measured (once a shape is warmed) instead of re-measuring
    the interpreter per process."""
    meas = plan.get("measured")
    if not isinstance(meas, dict):
        return
    st = getattr(program, "_exec_stats", None)
    if st is None:
        st = {}
        program._exec_stats = st
    for key in ("interp_ms_row", "fused_ms_row"):
        v = meas.get(key)
        if v is not None and (st.get(key) is None or v < st[key]):
            st[key] = float(v)


def fused_program(program, plan: Dict = None) -> FusedProgram:
    """The compiled fused artifact for ``program`` (derive-or-load the
    structural plan, build the segment plan; XLA compiles lazily on the
    first call per batch shape)."""
    fp = _FUSED.get(id(program))
    if fp is None:
        t0 = time.perf_counter()
        if plan is None:
            plan = _load_plan(program)
        if plan is None:
            plan = _derive_plan(program)
            _store_plan(program, plan)
        _seed_stats_from_plan(program, plan)
        fp = FusedProgram(program, plan)
        _FUSED[id(program)] = fp
        _COUNTERS["programs"] += 1
        _export_gauges()
        _flight_note(
            "fused_compile",
            steps=int(program.n_steps),
            chunks=len(plan["chunks"]),
            structs=len(plan.get("structs", ())),
            window=plan.get("window"),
            plan_seconds=round(time.perf_counter() - t0, 4),
        )
    return fp


# ---------------------------------------------------------------------------
# background warm (ISSUE 15): compile missing shapes off the serving path
# ---------------------------------------------------------------------------

_BG_LOCK = threading.Lock()
_BG_QUEUE: deque = deque()
_BG_PENDING = set()
_BG_FAILED = set()  # keys whose warm raised: never auto-retried
_BG_THREAD: Optional[threading.Thread] = None
_BG_IDLE = threading.Condition(_BG_LOCK)


def _bg_worker() -> None:
    while True:
        with _BG_LOCK:
            if not _BG_QUEUE:
                _BG_IDLE.notify_all()
                _BG_IDLE.wait(timeout=5.0)
                if not _BG_QUEUE:
                    continue
            program, batch = _BG_QUEUE.popleft()
        key = (id(program), batch)
        try:
            dt = warm_fused(program, batch)
            _flight_note("bg_warm_ready", batch=list(batch),
                         seconds=round(dt, 3),
                         steps=int(program.n_steps))
        except Exception as e:
            # memoize the failure: a deterministically-failing compile
            # must not be retried on every serving call (each retry is a
            # minutes-scale CPU burn on the serving box) — the shape
            # stays on the interpreter for the process lifetime
            with _BG_LOCK:
                _BG_FAILED.add(key)
            note_fallback(program, e)
        finally:
            with _BG_LOCK:
                _BG_PENDING.discard(key)
                if not _BG_QUEUE:
                    _BG_IDLE.notify_all()


def _bg_enqueue(program, batch: tuple) -> None:
    global _BG_THREAD
    key = (id(program), batch)
    with _BG_LOCK:
        if key in _BG_PENDING or key in _BG_FAILED:
            return
        _BG_PENDING.add(key)
        _BG_QUEUE.append((program, batch))
        if _BG_THREAD is None or not _BG_THREAD.is_alive():
            _BG_THREAD = threading.Thread(
                target=_bg_worker, name="vm-fused-bg-warm", daemon=True)
            _BG_THREAD.start()
        _BG_IDLE.notify_all()
    _flight_note("bg_warm_queued", batch=list(batch),
                 steps=int(program.n_steps))


def bg_warm_drain(timeout: float = 60.0) -> bool:
    """Wait until the background-warm queue is empty and idle (tests use
    this; serving code never blocks on it)."""
    deadline = time.monotonic() + timeout
    with _BG_LOCK:
        while _BG_QUEUE or _BG_PENDING:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                return False
            _BG_IDLE.wait(timeout=min(0.25, remaining))
    return True


# ---------------------------------------------------------------------------
# routing
# ---------------------------------------------------------------------------


def use_fused(program, mode: str = None, shape_sig: tuple = None) -> bool:
    """Route decision for one execution. ``fused`` always takes this
    lowering (compiling on demand); ``auto`` only when BOTH hold:

      - the measured warm ms/row pair (in-process ledger, seeded from the
        ``.vm_cache`` plan's persisted measurements on first consult)
        says fused beats the interpreter for this program, AND
      - with a ``shape_sig`` (``(batch_shape, sharded)`` — what
        ``vm.execute`` passes), the fused artifact is ALREADY COMPILED
        in-process for that signature.

    The shape condition is what keeps ``auto`` from ever paying the
    cold trace+compile bill in the middle of a serving call or a test:
    the bill is only ever paid by an explicit ``warm_fused``, a
    pinned-``fused`` call — or, under
    ``CONSENSUS_SPECS_TPU_VM_WARM_BG=1``, the background-warm thread a
    not-yet-compiled winner shape enqueues here: the call itself stays
    on the interpreter and auto flips to fused once the warm lands
    (``vm/bg_warm_queued``/``vm/bg_warm_ready`` flight events). With no
    fused measurement at all, auto stays on the interpreter."""
    if mode is None:
        mode = exec_mode()
    if mode == "interp":
        return False
    if vm._pallas_mode() != "0":
        return False  # Pallas dispatch keeps the scan path
    if mode == "fused":
        return True
    st = getattr(program, "_exec_stats", None) or {}
    f, i = st.get("fused_ms_row"), st.get("interp_ms_row")
    if f is None or i is None:
        # no in-process pair yet: consult the disk plan once per Program
        # instance — building the segment plan is cheap (no XLA compile)
        # and seeds the ledger from the persisted numbers
        if not getattr(program, "_fused_plan_checked", False):
            try:
                program._fused_plan_checked = True
            except Exception:
                pass
            try:
                plan = _load_plan(program)
                meas = (plan.get("measured") or {}) if plan else {}
                if (meas.get("fused_ms_row") is not None
                        and meas.get("interp_ms_row") is not None):
                    fused_program(program, plan=plan)
            except Exception as e:
                # a loadable-but-malformed disk plan must not break the
                # route decision — vm.execute's contract is that lowering
                # problems never fail a call
                note_fallback(program, e)
        st = getattr(program, "_exec_stats", None) or {}
        f, i = st.get("fused_ms_row"), st.get("interp_ms_row")
    if f is None or i is None or f >= i:
        return False
    if shape_sig is None:
        return True  # shape-independent query (tests, diagnostics)
    fp = _FUSED.get(id(program))
    ready = fp is not None and tuple(shape_sig) in fp.seen_shapes
    if not ready and _bg_warm_enabled() and not shape_sig[1]:
        _bg_enqueue(program, tuple(int(d) for d in shape_sig[0]))
    return ready


def run_fused(program, stacked_u32, mesh=None) -> Tuple[jnp.ndarray, bool]:
    """Execute through the fused lowering. Returns (outputs (batch, n_out,
    L) u64 array, compile_inclusive) — the flag marks a first execution at
    this (batch shape, sharded) signature, whose wall time includes
    trace+XLA-compile and must not enter the warm ms/row ledger. The
    outputs are materialized before returning (still inside the
    caller's wall-timer window AND its fallback try), so the ledger
    records compute, not async dispatch, and a deferred runtime failure
    falls back to the interpreter like any other fused failure."""
    fp = fused_program(program)
    sig = (tuple(np.shape(stacked_u32)[:-2]), mesh is not None)
    compile_inclusive = sig not in fp.seen_shapes
    out = fp.run(stacked_u32, mesh=mesh)
    # materialize HERE, inside the caller's try: async dispatch defers
    # runtime failures to the block, and an unmaterialized return would
    # (a) escape the interpreter-fallback net and (b) mark the shape
    # seen/measured before it ever succeeded
    out.block_until_ready()
    fp.seen_shapes.add(sig)
    _COUNTERS["executions"] += 1
    _export_gauges()
    return out, compile_inclusive


def warm_fused(program, batch_shape=()) -> float:
    """Pre-compile the fused lowering for one unsharded batch shape
    (sequential AOT across compile units — see ``FusedProgram.warm``)
    and return the trace+compile wall seconds (0.0 when already compiled
    in-process; structure entries already compiled — by any program —
    count as ``vm/structural_hit`` and cost nothing). ``auto`` serves
    fused for a shape only after a call like this has compiled it."""
    fp = fused_program(program)
    dt = fp.warm(tuple(int(d) for d in batch_shape))
    fp.seen_shapes.add((tuple(int(d) for d in batch_shape), False))
    return dt


def note_execution(program, path: str, seconds: float, rows: int,
                   compile_inclusive: bool = False) -> None:
    """Feed the per-program measured-ms/row ledger the ``auto`` route
    reads: the process-lifetime MIN per path (cold compiles converge to
    the warm number; fused first-shape calls are excluded outright). A
    meaningful improvement is also persisted into the program's disk plan
    — that is the artifact a FRESH process's ``auto`` route consults."""
    if compile_inclusive:
        return
    try:
        st = getattr(program, "_exec_stats", None)
        if st is None:
            st = {}
            program._exec_stats = st
        key = f"{path}_ms_row"
        ms = seconds * 1e3 / max(1, rows)
        prev = st.get(key)
        if prev is not None and ms >= prev:
            return
        st[key] = ms
        # persist only when the fused artifact is live (its plan is the
        # carrier) and the number moved enough to matter — disk writes at
        # device-call scale, never hot-loop scale
        fp = _FUSED.get(id(program))
        if fp is not None and (prev is None or ms < prev * 0.9):
            meas = dict(fp.plan.get("measured") or {})
            meas[key] = round(ms, 4)
            for other in ("interp_ms_row", "fused_ms_row"):
                if other != key and st.get(other) is not None:
                    cur = meas.get(other)
                    if cur is None or st[other] < cur:
                        meas[other] = round(st[other], 4)
            fp.plan["measured"] = meas
            _store_plan(program, fp.plan)
    except Exception:
        pass  # the ledger is routing advice, never a failure source


def note_fallback(program, err: BaseException) -> None:
    """A fused attempt failed: count it, journal it, let the interpreter
    serve the call (the caller falls through)."""
    _COUNTERS["fallbacks"] += 1
    _export_gauges()
    _flight_note(
        "fused_fallback",
        steps=int(program.n_steps),
        error=f"{type(err).__name__}: {err}"[:200],
    )


def reset_fused_state() -> None:
    """Test hook: drop compiled artifacts, structure caches, the
    background-warm queue, and counters (gauges re-zeroed)."""
    _FUSED.clear()
    _STRUCT_JIT.clear()
    _STRUCT_AOT.clear()
    _COMPILED_STRUCTS.clear()
    with _BG_LOCK:
        _BG_QUEUE.clear()
        _BG_PENDING.clear()
        _BG_FAILED.clear()
    for k in _COUNTERS:
        _COUNTERS[k] = 0
    _export_gauges()
