"""SIMD field-ALU virtual machine: the TPU execution engine for BLS12-381.

WHY A VM. XLA compile time is superlinear in graph size, so emitting a
pairing (tens of thousands of field multiplies) as one traced graph cannot
compile. Instead the device program is ONE `lax.scan` whose body is a fixed
two-unit ALU:

  - MUL unit: W_m lanes of batched Montgomery multiply (ops.fq.mont_mul)
  - LIN unit: W_l lanes of add / borrowless-subtract (+ carry normalize)

and the *schedule* — which registers each lane reads/writes at each step —
is data (int32 arrays scanned over), assembled on host from a straight-line
field program. Compile cost is therefore constant (~one mont_mul call site)
no matter how long the pairing is; throughput comes from lane width x the
leading batch dimension (N independent verifications), which is also the
axis `shard_map` distributes over a TPU mesh.

This mirrors how the reference splits semantics (Python) from the crypto
hot loop (native milagro C, reference utils/bls.py:17-22): here the "native
backend" is a field-ALU program compiled once by XLA.

Register values are loose Montgomery residues (ops.fq conventions). The
assembler tracks magnitude bounds per value and auto-inserts compress
multiplies, so lazy reduction is handled statically at assembly time.
"""
import os
import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from . import fq

# value-magnitude bounds for lazy reduction. Limb-level uint64 overflow is
# impossible by representation (limbs always < 2^28 after carry); these
# bounds track VALUE magnitudes so results always fit the 15-limb capacity.
_B_SUB_B = fq.MP  # subtrahend must not exceed the MP shift
_B_SUB_A = 1 << 419  # minuend headroom: a + MP < 2^420
_B_CAP = 1 << 420  # register capacity (15 x 28-bit limbs)

_MUL, _ADD, _SUB = 0, 1, 2


def _load_native_sched():
    """ctypes handle to the native scheduling+allocation kernel
    (csrc/vm_sched.c, built by `make native`), or None — the pure-Python
    bucketed scheduler below is the always-available fallback and the two
    are gated bit-identical (tests/test_vm_scheduler.py)."""
    import ctypes

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))),
        "csrc", "libvmsched.so",
    )
    try:
        lib = ctypes.CDLL(path)
        i64p = ctypes.POINTER(ctypes.c_int64)
        lib.vm_schedule_alloc.restype = ctypes.c_int
        lib.vm_schedule_alloc.argtypes = [
            ctypes.c_int64, i64p, i64p, i64p,  # n, kind, a, b
            ctypes.c_int64, ctypes.c_int64,    # w_mul, w_lin
            ctypes.c_int64, i64p,              # n_out, outs
            i64p, i64p, i64p, i64p,            # step, last_use, reg, meta
        ]
        return lib
    except (OSError, AttributeError):
        # absent .so, or a stale/foreign one without the expected symbol:
        # fall back to the pure-Python scheduler, never fail import
        return None


_NATIVE_SCHED = _load_native_sched()
_NATIVE_WARNED = False


def _warn_native_missing() -> None:
    """One line, once per process, when the native scheduling kernel is
    not built: fresh clones otherwise silently run the ~1.2M ops/sec
    pure-Python scheduler (and the >= 4x throughput smoke quietly drops
    to its 2.5x fallback bar) instead of the ~3M ops/sec `make native`
    path — a discoverability fix, never an error."""
    global _NATIVE_WARNED
    if _NATIVE_SCHED is None and not _NATIVE_WARNED:
        _NATIVE_WARNED = True
        import sys

        print(
            "vm: csrc/libvmsched.so not built — assembling with the "
            "pure-Python scheduler (~1.2M ops/sec vs ~3M native); run "
            "`make native` once per clone",
            file=sys.stderr,
        )


def _native_schedule_alloc(kind_arr, a_all, b_all, w_mul, w_lin, outputs):
    """Run the native kernel over sanitized int64 IR columns. Returns
    (step, last_use, reg, n_steps, alloc_regs) or None on any failure
    (the caller falls back to the Python loops)."""
    if _NATIVE_SCHED is None:
        return None
    import ctypes

    n = kind_arr.size
    step = np.empty(n, dtype=np.int64)
    last_use = np.empty(n, dtype=np.int64)
    reg = np.full(n, -1, dtype=np.int64)
    meta = np.zeros(2, dtype=np.int64)
    outs = np.asarray(outputs, dtype=np.int64)
    i64p = ctypes.POINTER(ctypes.c_int64)

    def p(arr):
        return arr.ctypes.data_as(i64p)

    # keep every buffer bound to a local for the duration of the call —
    # all inputs are freshly built C-contiguous int64 arrays
    try:
        rc = _NATIVE_SCHED.vm_schedule_alloc(
            n, p(kind_arr), p(a_all), p(b_all),
            w_mul, w_lin, outs.size, p(outs),
            p(step), p(last_use), p(reg), p(meta),
        )
    except Exception:
        return None
    if rc != 0:
        return None
    return step, last_use, reg, int(meta[0]), int(meta[1])


@dataclass
class _Op:
    kind: int  # _MUL/_ADD/_SUB
    a: int  # producing op index (or register source)
    b: int
    bound: int
    step: int = -1
    reg: int = -1
    last_use_step: int = -1


class Val:
    """Handle to a symbolic field value inside a Prog."""

    __slots__ = ("prog", "idx")

    def __init__(self, prog: "Prog", idx: int):
        self.prog = prog
        self.idx = idx

    @property
    def bound(self) -> int:
        return self.prog.ops[self.idx].bound

    # arithmetic sugar so formula code reads naturally
    def __mul__(self, other: "Val") -> "Val":
        return self.prog.mul(self, other)

    def __add__(self, other: "Val") -> "Val":
        return self.prog.add(self, other)

    def __sub__(self, other: "Val") -> "Val":
        return self.prog.sub(self, other)


class Prog:
    """Straight-line field-program builder with bound tracking."""

    def __init__(self):
        self.ops: List[_Op] = []
        self.inputs: List[int] = []  # op indices with kind 'input'
        self.input_names: List[str] = []
        self.consts: Dict[int, int] = {}  # int value -> op idx
        self.outputs: List[int] = []
        self.output_names: List[str] = []
        self._one: Optional[Val] = None
        self._compressed: Dict[int, int] = {}  # op idx -> compressed op idx
        self._cse: Dict[Tuple[int, int, int], int] = {}  # (kind,a,b) -> op idx

    # -- value creation ----------------------------------------------------

    def _push(self, kind, a, b, bound) -> Val:
        """Create an ALU op, CSE-deduplicated. The dedup matters beyond op
        count: formula code that re-derives the same subexpression against a
        LOOP-INVARIANT operand (e.g. the Karatsuba half-sums of a constant
        multiplicand inside an exponentiation ladder) would otherwise emit
        input-ready ops the greedy scheduler places at step ~0, whose values
        then sit live until their distant consumer — measured as a 10x
        register-file blowup (and per-step cost is dominated by register-file
        gather/scatter traffic). Bounds are a pure function of (kind, operand
        bounds), so the memoized op is exact."""
        if a >= 0 and b >= 0:  # inputs/consts use -1 sentinels: never CSE
            key = (kind, a, b) if (kind == _SUB or a <= b) else (kind, b, a)
            hit = self._cse.get(key)
            if hit is not None:
                return Val(self, hit)
        else:
            key = None
        if bound >= _B_CAP:
            raise AssertionError("assembler bound overflow — missing compress")
        self.ops.append(_Op(kind, a, b, bound))
        v = Val(self, len(self.ops) - 1)
        if key is not None:
            self._cse[key] = v.idx
        return v

    def inp(self, name: str, bound: int = fq.P) -> Val:
        """Runtime input slot. Default ``bound`` declares a canonical
        Montgomery residue (< p); pass a looser bound (e.g. 1 << 382) when
        the input is another program's compressed OUTPUT fed back in without
        host-side canonicalization — the bound tracker then inserts the
        compress multiplies the looser magnitude needs."""
        v = self._push(_MUL, -1, -1, bound)
        self.ops[v.idx].kind = -1  # input marker
        self.inputs.append(v.idx)
        self.input_names.append(name)
        return v

    def const(self, value: int) -> Val:
        """Compile-time field constant (plain integer mod p; encoded to
        Montgomery form at program build)."""
        value %= fq.P
        if value in self.consts:
            return Val(self, self.consts[value])
        v = self._push(_MUL, -1, -1, fq.P)
        self.ops[v.idx].kind = -2  # const marker
        self.ops[v.idx].a = value  # stash the payload
        self.consts[value] = v.idx
        return v

    # -- ALU ops -----------------------------------------------------------

    def _raw_mul(self, a: Val, b: Val) -> Val:
        out_bound = (a.bound * b.bound) // fq.R_MONT + fq.P + 1
        return self._push(_MUL, a.idx, b.idx, out_bound)

    def compress(self, v: Val) -> Val:
        """Magnitude reduction: multiply by repr(1) (bound -> < 2^383);
        memoized so repeated consumers share one compress."""
        if v.idx in self._compressed:
            return Val(self, self._compressed[v.idx])
        if self._one is None or self._one.prog is not self:
            self._one = self.const(1)
        out = self._raw_mul(v, self._one)
        self._compressed[v.idx] = out.idx
        return out

    def _fit(self, v: Val, bound: int) -> Val:
        return self.compress(v) if v.bound > bound else v

    def mul(self, a: Val, b: Val) -> Val:
        while (a.bound * b.bound) // fq.R_MONT + fq.P + 1 >= _B_CAP:
            if a.bound >= b.bound:
                a = self.compress(a)
            else:
                b = self.compress(b)
        return self._raw_mul(a, b)

    def add(self, a: Val, b: Val) -> Val:
        if a.bound + b.bound >= _B_CAP:
            a = self.compress(a)
            if a.bound + b.bound >= _B_CAP:
                b = self.compress(b)
        return self._push(_ADD, a.idx, b.idx, a.bound + b.bound)

    def sub(self, a: Val, b: Val) -> Val:
        a = self._fit(a, _B_SUB_A - fq.MP)
        b = self._fit(b, _B_SUB_B)
        return self._push(_SUB, a.idx, b.idx, a.bound + fq.MP)

    def out(self, v: Val, name: str) -> None:
        """Mark a value as a program output (compressed to < 2^382 so hosts
        and epilogues get bounded limbs)."""
        v = self.compress(v)
        self.outputs.append(v.idx)
        self.output_names.append(name)

    # -- static analysis ----------------------------------------------------

    def analyze(self, name: str = "<prog>", **assemble_kwargs):
        """vmlint entry point over the IR: assemble with the given shape
        (schedules + allocates, annotating every op with step/reg/last-use)
        and run the full vm_analysis pass — independent bound re-derivation,
        liveness/register-pressure, critical-path/cost reports. Returns the
        report dict (see ops/vm_analysis.py)."""
        from . import vm_analysis

        return vm_analysis.analyze_prog(self, name=name, **assemble_kwargs)

    # -- scheduling + register allocation ----------------------------------

    def assemble(
        self,
        w_mul: int = 128,
        w_lin: int = 128,
        pad_steps_to: int = 1,
        pad_regs_to: int = 1,
        annotate: bool = True,
    ) -> "Program":
        """Schedule + allocate with the BUCKETED incremental scheduler.

        Placement rule (identical to the legacy list scheduler, gated
        bit-exact by tests/test_vm_scheduler.py): each ALU op lands on the
        first step >= max(operand steps) + 1 whose unit has a free lane,
        lanes filled in op-creation order. The legacy implementation
        re-SCANNED the fill array from `earliest` for every op — O(n x
        schedule length) on deep programs, the measured ~250k ops/sec that
        made every .vm_cache miss a 6-8 s stall. Here each unit keeps a
        union-find "next step with free capacity" forest (full steps point
        past themselves; finds path-compress), so placement is amortized
        O(alpha) per op, and liveness + instruction-tensor emission are
        numpy-vectorized — ~1M+ ops/sec end to end.

        `pad_steps_to`/`pad_regs_to` round the step count and register-file
        size up so distinct programs share XLA executables (compile cost is
        per shape bucket). ``annotate`` writes step/last-use/reg back onto
        the IR ops (vm_analysis reads them); the production program cache
        skips it (`annotate=False`) — attribute writes on a million-op IR
        are a measurable slice of the assembly budget.
        """
        _warn_native_missing()
        ops = self.ops
        n = len(ops)
        kind_l = [op.kind for op in ops]
        a_l = [op.a for op in ops]
        b_l = [op.b for op in ops]
        # operand columns are numpy-castable once the const payloads
        # (arbitrary-size field ints stashed in ``a``) are masked out —
        # there are only a handful of const ops per program
        if self.consts:
            a_l_safe = a_l[:]  # local copy: never mutate the IR
            for ci in self.consts.values():
                a_l_safe[ci] = 0
        else:
            a_l_safe = a_l
        kind_arr = np.fromiter(kind_l, dtype=np.int64, count=n)
        a_all = np.fromiter(a_l_safe, dtype=np.int64, count=n)
        b_all = np.fromiter(b_l, dtype=np.int64, count=n)

        native = _native_schedule_alloc(
            kind_arr, a_all, b_all, w_mul, w_lin, self.outputs)
        if native is not None:
            step_arr, last_use, reg_arr, n_steps, next_reg = native
        else:
            step_arr, last_use, reg_arr, n_steps, next_reg = (
                self._schedule_alloc_py(
                    kind_l, a_l, b_l, kind_arr, a_all, b_all, w_mul, w_lin))
        alu_idx = np.flatnonzero(kind_arr >= 0)
        n_alu = int(alu_idx.size)
        a_arr = a_all[alu_idx]
        b_arr = b_all[alu_idx]
        alu_steps = step_arr[alu_idx]
        kind_alu = kind_arr[alu_idx]

        sched_steps = n_steps  # pre-padding schedule length
        n_steps = -(-n_steps // pad_steps_to) * pad_steps_to
        n_regs = next_reg
        # trash registers for idle lanes
        trash_mul = n_regs
        trash_lin = n_regs + w_mul
        n_regs += w_mul + w_lin
        if n_regs < pad_regs_to:
            n_regs = pad_regs_to

        # 4) instruction arrays (vectorized): lanes are the within-step
        #    rank in creation order; idle lanes pre-filled with their trash
        #    destination registers (zero sources)
        reg_a = reg_arr.astype(np.int32)
        msa = np.zeros((n_steps, w_mul), dtype=np.int32)
        msb = np.zeros((n_steps, w_mul), dtype=np.int32)
        msd = np.empty((n_steps, w_mul), dtype=np.int32)
        msd[:] = trash_mul + np.arange(w_mul, dtype=np.int32)
        lsa = np.zeros((n_steps, w_lin), dtype=np.int32)
        lsb = np.zeros((n_steps, w_lin), dtype=np.int32)
        lsub = np.zeros((n_steps, w_lin), dtype=bool)
        lsd = np.empty((n_steps, w_lin), dtype=np.int32)
        lsd[:] = trash_lin + np.arange(w_lin, dtype=np.int32)

        is_mul = kind_alu == _MUL
        for unit_sel, (ma, mb, md) in ((is_mul, (msa, msb, msd)),
                                       (~is_mul, (lsa, lsb, lsd))):
            sel = np.flatnonzero(unit_sel)
            if not sel.size:
                continue
            steps_u = alu_steps[sel]
            o = np.argsort(steps_u, kind="stable")
            ss = steps_u[o]
            so = sel[o]
            # lane = rank within the step group (creation order preserved)
            group_start = np.r_[0, np.flatnonzero(np.diff(ss)) + 1]
            lanes = np.arange(ss.size, dtype=np.int64)
            lanes -= np.repeat(group_start,
                               np.diff(np.r_[group_start, ss.size]))
            ma[ss, lanes] = reg_a[a_arr[so]]
            mb[ss, lanes] = reg_a[b_arr[so]]
            md[ss, lanes] = reg_a[alu_idx[so]]
            if md is lsd:
                lsub[ss, lanes] = kind_alu[so] == _SUB

        const_payload = {
            int(reg_arr[idx]): ops[idx].a for idx in self.consts.values()
        }
        input_regs = [int(reg_arr[i]) for i in self.inputs]
        output_regs = [int(reg_arr[i]) for i in self.outputs]

        n_mul = int(is_mul.sum())
        n_lin = n_alu - n_mul

        if annotate:
            # write the schedule back onto the IR (vm_analysis reads
            # step/last_use_step/reg off the ops); a fresh assemble always
            # rewrites all three, so stale shapes cannot bleed through
            step_l = step_arr.tolist()
            last_l = last_use.tolist()
            reg_l = reg_arr.tolist()
            for i, op in enumerate(ops):
                op.step = step_l[i]
                op.last_use_step = last_l[i]
                op.reg = reg_l[i]
        return Program(
            n_regs=n_regs,
            instr=(msa, msb, msd, lsa, lsb, lsub, lsd),
            input_regs=np.asarray(input_regs, dtype=np.int32),
            input_names=list(self.input_names),
            output_regs=np.asarray(output_regs, dtype=np.int32),
            output_names=list(self.output_names),
            const_regs=const_payload,
            n_steps=n_steps,
            # schedule metadata for vm_analysis.program_stats — lets the
            # analyzer report on cache-loaded assembled programs whose IR
            # is not in memory (old .vm_cache pickles lack it: meta=None)
            meta={
                "sched_steps": sched_steps,
                "n_mul": n_mul,
                "n_lin": n_lin,
                "alloc_regs": next_reg,
                "trash_mul": trash_mul,
                "trash_lin": trash_lin,
                "w_mul": w_mul,
                "w_lin": w_lin,
            },
        )


    def _schedule_alloc_py(self, kind_l, a_l, b_l, kind_arr, a_all, b_all,
                           w_mul, w_lin):
        """Pure-Python twin of the native scheduling+allocation kernel
        (csrc/vm_sched.c): the always-available fallback, ~1M ops/sec.
        Returns (step, last_use, reg, n_steps, alloc_regs) as int64 arrays
        + ints, bit-identical to the native kernel and to the legacy
        scheduler."""
        n = len(kind_l)

        # 1) bucketed list scheduling: per-unit lane-fill counters plus a
        #    union-find over steps ("first step >= t with a free lane").
        #    A full step's root points one past itself, so probing a long
        #    saturated prefix costs one path-compressed find instead of a
        #    linear rescan.
        step: List[int] = [-1] * n
        fill0: List[int] = []
        fill1: List[int] = []
        nxt0: List[int] = []
        nxt1: List[int] = []
        ln0 = ln1 = 0
        for i, (k, ai, bi) in enumerate(zip(kind_l, a_l, b_l)):
            if k < 0:
                continue  # input/const: defined before step 0
            sa = step[ai]
            sb = step[bi]
            t = (sa if sa >= sb else sb) + 1
            if k == 0:  # _MUL
                f, nx, ln, width = fill0, nxt0, ln0, w_mul
            else:
                f, nx, ln, width = fill1, nxt1, ln1, w_lin
            if t >= ln:
                while ln <= t:
                    nx.append(ln)
                    f.append(0)
                    ln += 1
                r = t
            else:
                # find the root (first candidate free step >= t),
                # path-compressing the chain walked
                r = t
                x = nx[r]
                if x != r:
                    chain = []
                    ap_c = chain.append
                    while True:
                        ap_c(r)
                        r = x
                        if r == ln:
                            nx.append(ln)
                            f.append(0)
                            ln += 1
                            break
                        x = nx[r]
                        if x == r:
                            break
                    for c in chain:
                        nx[c] = r
            if k == 0:
                ln0 = ln
            else:
                ln1 = ln
            cnt = f[r] + 1
            f[r] = cnt
            if cnt == width:
                nx[r] = r + 1
            step[i] = r

        n_steps = ln0 if ln0 >= ln1 else ln1

        # 2) liveness (vectorized): last step at which each value is read
        step_arr = np.fromiter(step, dtype=np.int64, count=n)
        alu_idx = np.flatnonzero(kind_arr >= 0)
        alu_steps = step_arr[alu_idx]
        last_use = np.full(n, -1, dtype=np.int64)
        np.maximum.at(last_use, a_all[alu_idx], alu_steps)
        np.maximum.at(last_use, b_all[alu_idx], alu_steps)
        if self.outputs:
            last_use[np.asarray(self.outputs)] = n_steps + 1  # live to end

        # 3) linear-scan register allocation (reg 0 = always-zero scratch
        #    source for idle lanes). Same policy as ever: defs claim the
        #    most recently freed register (LIFO), frees happen after each
        #    step's last use — kept as a tight index loop over the
        #    step-sorted ALU ops with per-step expiry lists.
        reg_l = [-1] * n
        next_reg = 1
        free: List[int] = []
        # regs to free after step t; entries past the walked range (outputs
        # at n_steps + 1) are simply never freed, as before
        expiry: List[List[int]] = [[] for _ in range(n_steps + 2)]

        last_l = last_use.tolist()
        # inputs and constants in creation order, defined "before step 0"
        for i in sorted(self.inputs + list(self.consts.values())):
            if free:
                r = free.pop()
            else:
                r = next_reg
                next_reg += 1
            reg_l[i] = r
            lu = last_l[i]
            if lu >= 0:
                expiry[lu].append(r)
            # dead input/const: legacy pended the free on step -1, which
            # the step walk never reaches — so: never freed
        # ALU defs in (step, creation) order; stable sort keeps creation
        # order within a step, matching the legacy by_step walk
        alloc_order = np.argsort(alu_steps, kind="stable")
        order = alu_idx[alloc_order].tolist()
        order_steps = alu_steps[alloc_order].tolist()
        order_last = last_use[alu_idx][alloc_order].tolist()
        cur = 0
        free_pop = free.pop
        free_ext = free.extend
        for i, t, lu in zip(order, order_steps, order_last):
            while cur < t:  # free everything expiring strictly before t
                e = expiry[cur]
                if e:
                    free_ext(e)
                cur += 1
            if free:
                r = free_pop()
            else:
                r = next_reg
                next_reg += 1
            reg_l[i] = r
            expiry[lu if lu >= 0 else t].append(r)

        reg_arr = np.fromiter(reg_l, dtype=np.int64, count=n)
        return step_arr, last_use, reg_arr, n_steps, next_reg

    def assemble_legacy(
        self,
        w_mul: int = 128,
        w_lin: int = 128,
        pad_steps_to: int = 1,
        pad_regs_to: int = 1,
    ) -> "Program":
        """The pre-bucketing reference scheduler, kept VERBATIM as the
        equivalence oracle: tests/test_vm_scheduler.py gates that
        ``assemble`` produces bit-identical instruction tensors (and
        therefore bit-identical outputs) for every registry program, and
        the assembly-throughput smoke races the two on the chunk-16
        rlc_combine. Not used by any production path."""
        ops = self.ops
        n = len(ops)
        is_alu = [op.kind in (_MUL, _ADD, _SUB) for op in ops]

        # re-assembly must start clean: step/last-use/reg are schedule
        # outputs, and a previous assemble at a different shape would
        # otherwise bleed through the max() accumulation below (stale live
        # ranges -> corrupted liveness and allocation)
        for op in ops:
            op.step = -1
            op.last_use_step = -1
            op.reg = -1

        # 1) list-schedule ALU ops into steps
        unit_of = [0 if op.kind == _MUL else 1 for op in ops]
        width = (w_mul, w_lin)
        fill: List[List[int]] = [[], []]  # per unit, per step lane count

        for i, op in enumerate(ops):
            if not is_alu[i]:
                continue
            earliest = 0
            for src in (op.a, op.b):
                s = ops[src].step
                if s >= 0:
                    earliest = max(earliest, s + 1)
            u = unit_of[i]
            t = earliest
            f = fill[u]
            while True:
                while len(f) <= t:
                    f.append(0)
                if f[t] < width[u]:
                    f[t] += 1
                    op.step = t
                    break
                t += 1

        n_steps = max(len(fill[0]), len(fill[1]))

        # 2) liveness: last step at which each value is read
        for i, op in enumerate(ops):
            if not is_alu[i]:
                continue
            for src in (op.a, op.b):
                ops[src].last_use_step = max(ops[src].last_use_step, op.step)
        for idx in self.outputs:
            ops[idx].last_use_step = n_steps + 1  # live to the end

        # 3) linear-scan register allocation
        #    reg 0 = always-zero scratch source for idle lanes
        next_reg = 1
        free: List[int] = []
        # inputs and constants are defined "before step 0"
        expiry: Dict[int, List[int]] = {}  # step -> regs to free after it

        def alloc(op: _Op, def_step: int):
            nonlocal next_reg
            if free:
                op.reg = free.pop()
            else:
                op.reg = next_reg
                next_reg += 1
            if op.last_use_step >= 0:
                expiry.setdefault(op.last_use_step, []).append(op.reg)
            else:
                # value never used (dead code): free right away
                expiry.setdefault(def_step, []).append(op.reg)

        for i, op in enumerate(ops):
            if op.kind in (-1, -2):
                alloc(op, -1)
        # walk steps in order, allocating defs and freeing after last use
        by_step: Dict[int, List[int]] = {}
        for i, op in enumerate(ops):
            if is_alu[i]:
                by_step.setdefault(op.step, []).append(i)
        for t in range(n_steps):
            for i in by_step.get(t, ()):
                alloc(ops[i], t)
            for r in expiry.get(t, ()):
                free.append(r)

        sched_steps = n_steps  # pre-padding schedule length
        n_steps = -(-n_steps // pad_steps_to) * pad_steps_to
        n_regs = next_reg
        # trash registers for idle lanes
        trash_mul = n_regs
        trash_lin = n_regs + w_mul
        n_regs += w_mul + w_lin
        if n_regs < pad_regs_to:
            n_regs = pad_regs_to

        # 4) instruction arrays
        msa = np.zeros((n_steps, w_mul), dtype=np.int32)
        msb = np.zeros((n_steps, w_mul), dtype=np.int32)
        msd = np.full((n_steps, w_mul), -1, dtype=np.int32)
        lsa = np.zeros((n_steps, w_lin), dtype=np.int32)
        lsb = np.zeros((n_steps, w_lin), dtype=np.int32)
        lsub = np.zeros((n_steps, w_lin), dtype=bool)
        lsd = np.full((n_steps, w_lin), -1, dtype=np.int32)
        lane_ptr = [[0] * n_steps, [0] * n_steps]
        for i, op in enumerate(ops):
            if not is_alu[i]:
                continue
            t, u = op.step, unit_of[i]
            lane = lane_ptr[u][t]
            lane_ptr[u][t] = lane + 1
            ra, rb = ops[op.a].reg, ops[op.b].reg
            if u == 0:
                msa[t, lane], msb[t, lane], msd[t, lane] = ra, rb, op.reg
            else:
                lsa[t, lane], lsb[t, lane], lsd[t, lane] = ra, rb, op.reg
                lsub[t, lane] = op.kind == _SUB
        # idle lanes -> trash registers (zero sources)
        for t in range(n_steps):
            for lane in range(lane_ptr[0][t], w_mul):
                msd[t, lane] = trash_mul + lane
            for lane in range(lane_ptr[1][t], w_lin):
                lsd[t, lane] = trash_lin + lane

        const_payload = {
            op.reg: op.a for op in ops if op.kind == -2
        }
        input_regs = [ops[i].reg for i in self.inputs]
        output_regs = [ops[i].reg for i in self.outputs]

        n_mul = sum(1 for i, op in enumerate(ops) if is_alu[i] and unit_of[i] == 0)
        n_lin = sum(1 for i, op in enumerate(ops) if is_alu[i] and unit_of[i] == 1)
        return Program(
            n_regs=n_regs,
            instr=(msa, msb, msd, lsa, lsb, lsub, lsd),
            input_regs=np.asarray(input_regs, dtype=np.int32),
            input_names=list(self.input_names),
            output_regs=np.asarray(output_regs, dtype=np.int32),
            output_names=list(self.output_names),
            const_regs=const_payload,
            n_steps=n_steps,
            meta={
                "sched_steps": sched_steps,
                "n_mul": n_mul,
                "n_lin": n_lin,
                "alloc_regs": next_reg,
                "trash_mul": trash_mul,
                "trash_lin": trash_lin,
                "w_mul": w_mul,
                "w_lin": w_lin,
            },
        )


@dataclass
class Program:
    """Assembled VM program: static instruction tensors + register map."""

    n_regs: int
    instr: Tuple[np.ndarray, ...]
    input_regs: np.ndarray
    input_names: List[str]
    output_regs: np.ndarray
    output_names: List[str]
    const_regs: Dict[int, int]  # reg -> plain int value
    n_steps: int
    meta: Optional[Dict] = None  # assemble-time schedule stats (vm_analysis)
    # program kind (a vmlib.BUILDERS key) and K, stamped by
    # bls_backend._program; they name the program's span and XLA module
    kind: Optional[str] = None
    k: int = 0

    def init_regs(self, batch_shape: Tuple[int, ...]) -> np.ndarray:
        """Fresh register file with constants loaded (host-side numpy)."""
        regs = np.zeros(batch_shape + (self.n_regs, fq.NUM_LIMBS), dtype=np.uint64)
        for reg, value in self.const_regs.items():
            regs[..., reg, :] = fq.to_mont_int(value)
        return regs

    def load_inputs(self, regs: np.ndarray, values: Dict[str, np.ndarray]) -> np.ndarray:
        """Write named input limb arrays (batch-shaped, Montgomery form)."""
        for name, reg in zip(self.input_names, self.input_regs):
            regs[..., int(reg), :] = values[name]
        return regs

    def const_template(self) -> np.ndarray:
        """(n_regs, L) uint64 register template with constants loaded —
        broadcast over the batch on DEVICE so the host never materializes
        (or transfers) the full register file."""
        t = np.zeros((self.n_regs, fq.NUM_LIMBS), dtype=np.uint64)
        for reg, value in self.const_regs.items():
            t[reg] = fq.to_mont_int(value)
        return t

    def stack_inputs(self, values: Dict[str, np.ndarray], batch_shape,
                     names: Optional[List[str]] = None) -> np.ndarray:
        """Stack named inputs into (batch..., n_inputs, L) uint32 in
        input_names order (or in the order of ``names``, a subset).
        Program inputs are canonical Montgomery residues (limbs < 2^28), so
        the u32 transfer encoding is exact — and half the bytes over the
        host->device link."""
        names = self.input_names if names is None else names
        n_in = len(names)
        out = np.zeros(tuple(batch_shape) + (n_in, fq.NUM_LIMBS), dtype=np.uint32)
        for idx, name in enumerate(names):
            v = np.asarray(values[name], dtype=np.uint64)
            if v.size and int(v.max()) >> fq.LIMB_BITS:
                raise ValueError(
                    f"input {name!r} has limbs >= 2^{fq.LIMB_BITS} — program "
                    "inputs must be canonical Montgomery residues (the "
                    "assembler's bound tracking assumes canonical magnitude)"
                )
            out[..., idx, :] = v
        return out

    def check_stack(self, stacked: np.ndarray, batch_shape) -> np.ndarray:
        """An input stack built by the caller, in input_names order: its
        shape and the canonical bound that ``stack_inputs`` enforces."""
        want = tuple(batch_shape) + (len(self.input_names), fq.NUM_LIMBS)
        if stacked.shape != want or stacked.dtype != np.uint32:
            raise ValueError(f"input stack is {stacked.dtype}{stacked.shape}, "
                             f"program wants uint32{want}")
        if stacked.size and int(stacked.max()) >> fq.LIMB_BITS:
            raise ValueError(
                f"input stack has limbs >= 2^{fq.LIMB_BITS}: program "
                "inputs must be canonical Montgomery residues")
        return stacked

    def place_inputs(self, values: Dict[str, np.ndarray], device_inputs,
                     batch_shape):
        """The (batch..., n_inputs, L) uint32 input stack on the device:
        ``device_inputs`` is (names, array), the array (batch..., len(names),
        L) uint32 already on the device; every other input comes from the
        host's ``values`` (canonical, as for ``stack_inputs``)."""
        names, dev = device_inputs
        on_device = set(names)
        host_names = [n for n in self.input_names if n not in on_device]
        if len(host_names) + len(names) != len(self.input_names):
            raise ValueError("device inputs must be distinct program inputs")
        host = self.stack_inputs(values, batch_shape, host_names)
        pos = {n: i for i, n in enumerate(self.input_names)}
        return vm_place_inputs(
            jnp.asarray(host), dev,
            jnp.asarray([pos[n] for n in host_names], dtype=jnp.int32),
            jnp.asarray([pos[n] for n in names], dtype=jnp.int32))


@jax.jit
def vm_place_inputs(host, dev, host_pos, dev_pos):
    """The input stack from its host-stacked and device parts (XLA module
    ``jit_vm_place_inputs``)."""
    n_in = host_pos.shape[0] + dev_pos.shape[0]
    out = jnp.zeros(host.shape[:-2] + (n_in, host.shape[-1]), jnp.uint32)
    out = out.at[..., host_pos, :].set(host)
    return out.at[..., dev_pos, :].set(dev)


# MP + 1 in limb form: the additive shift of the borrowless subtract
_MP_PLUS_1 = fq._int_to_limbs_np(fq.MP + 1)


def _vm_step_with(mont_mul_fn, regs, instr):
    msa, msb, msd, lsa, lsb, lsub, lsd = instr
    # MUL unit
    a = jnp.take(regs, msa, axis=-2)
    b = jnp.take(regs, msb, axis=-2)
    m = mont_mul_fn(a, b)
    # LIN unit: out = a + (is_sub ? (MP+1) + (MASK - b) : b), carried
    la = jnp.take(regs, lsa, axis=-2)
    lb = jnp.take(regs, lsb, axis=-2)
    comp = jnp.asarray(_MP_PLUS_1) + (jnp.uint64(fq.MASK) - lb)
    rhs = jnp.where(lsub[..., None], comp, lb)
    lin = fq._carry_limbs(la + rhs, out_limbs=fq.NUM_LIMBS + 1)[..., : fq.NUM_LIMBS]
    regs = regs.at[..., msd, :].set(m)
    regs = regs.at[..., lsd, :].set(lin)
    return regs, None


def _vm_step(regs, instr):
    """Default scan body: the jnp u64 mont_mul lowering. Deliberately
    NOT fq.mont_mul — that dispatcher reads the Pallas env var at trace
    time, which would alias jit-cache entries across dispatch modes
    (same shapes, different semantics). The mode is a static argument of
    _vm_body instead."""
    return _vm_step_with(fq.mont_mul_u64, regs, instr)


def _vm_step_mont_pallas(regs, instr):
    """Scan body with the Pallas mont_mul kernel on the u64 register
    file (dispatch mode '1'); the LIN unit stays XLA."""
    from . import pallas_fq

    return _vm_step_with(pallas_fq.mont_mul, regs, instr)


# lax.scan unroll factor: >1 fuses that many ALU steps per loop iteration,
# trading compile time for less per-step loop/dispatch overhead on TPU.
# Step counts are padded to multiples of 256 (bls_backend.PAD_STEPS), so
# any power-of-two <= 256 divides evenly. Env-tunable for on-chip A/B;
# default 1 keeps compiles cheap.
_SCAN_UNROLL = int(os.environ.get("CONSENSUS_SPECS_TPU_SCAN_UNROLL", "1"))


def _vm_step14(regs14, instr):
    """Scan body of the fused-Pallas mode: the register file lives in
    14-bit uint32 limb form (ops/pallas_step.py) — half the HBM bytes per
    gather/scatter and no u64 emulation; one kernel does both units."""
    from . import pallas_step

    msa, msb, msd, lsa, lsb, lsub, lsd = instr
    m, lin = pallas_step.fused_step(
        jnp.take(regs14, msa, axis=-2),
        jnp.take(regs14, msb, axis=-2),
        jnp.take(regs14, lsa, axis=-2),
        jnp.take(regs14, lsb, axis=-2),
        lsub,
    )
    regs14 = regs14.at[..., msd, :].set(m)
    regs14 = regs14.at[..., lsd, :].set(lin)
    return regs14, None


def _vm_body(inputs_u32, template, input_regs, output_regs, instr,
             pallas_mode="0"):
    """Device program: broadcast the (n_regs, L) const template over the
    batch, scatter the compact u32 inputs in, scan the ALU steps, and slice
    ONLY the output registers — so host<->device traffic is the compact
    input stack in and the named outputs out, never the full register file
    (which is tens of times larger at epoch scale).

    ``pallas_mode`` (STATIC jit argument — set by execute() from
    CONSENSUS_SPECS_TPU_PALLAS on both the single-device and mesh paths;
    a pallas_call is opaque to the GSPMD partitioner, so under a mesh the
    Pallas modes route through shard_map — see _vm_run_for_mesh — and only
    the GSPMD-sharding fast path is mode-'0'-specific). Making it static
    keys the jit cache per mode — an env flip can never alias a cached
    executable of a different dispatch:
      '0'    — jnp u64 lowering for both units (default);
      '1'    — Pallas mont_mul kernel, LIN unit stays XLA;
      'step' — the whole scan on a 14-bit uint32 register file through
               the fused mul+lin kernel (ops/pallas_step.py); outputs
               convert back to u64 28-bit limbs, bit-identical."""
    batch = inputs_u32.shape[:-2]
    if pallas_mode == "step":
        from . import pallas_step

        regs14 = jnp.broadcast_to(
            pallas_step.split14(template),
            batch + (template.shape[0], 2 * fq.NUM_LIMBS),
        )
        regs14 = regs14.at[..., input_regs, :].set(
            pallas_step.split14(inputs_u32)
        )
        regs14, _ = jax.lax.scan(
            _vm_step14, regs14, instr, unroll=_SCAN_UNROLL
        )
        return pallas_step.join14(regs14[..., output_regs, :])
    step = _vm_step_mont_pallas if pallas_mode == "1" else _vm_step
    regs = jnp.broadcast_to(
        template, batch + template.shape
    ).astype(jnp.uint64)
    regs = regs.at[..., input_regs, :].set(inputs_u32.astype(jnp.uint64))
    regs, _ = jax.lax.scan(step, regs, instr, unroll=_SCAN_UNROLL)
    return regs[..., output_regs, :]


import functools as _functools


def _named_body(name: str):
    """``_vm_body`` under the ``__name__`` ``name``: XLA names the module
    ``jit_<name>``, so a device trace says which program kind ran."""
    def body(inputs_u32, template, input_regs, output_regs, instr,
             pallas_mode="0"):
        return _vm_body(inputs_u32, template, input_regs, output_regs, instr,
                        pallas_mode)

    body.__name__ = body.__qualname__ = name
    return body


@_functools.lru_cache(maxsize=None)
def _vm_run(name: str = "vm_program"):
    """The single-device jitted runner of the module ``jit_<name>``."""
    return jax.jit(_named_body(name), static_argnums=(5,))


# jit shape signature -> the module name that first compiled it: a second
# kind with the very same shapes reuses that executable instead of paying
# a compile of its own under another name
_SHAPE_OWNER: Dict[tuple, str] = {}


def _module_name(kind: Optional[str], sig: tuple) -> str:
    return _SHAPE_OWNER.setdefault(sig, f"vm_{kind or 'program'}")


@_functools.lru_cache(maxsize=64)
def _vm_run_for_mesh(mesh, pallas_mode="0", name="vm_program"):
    """Jitted VM runner with the leading batch axis sharded over ALL of
    ``mesh``'s axes (the DP axis of SURVEY.md §2.7/P1 — a hierarchical
    host x chip / DCN x ICI mesh flattens onto the one batch dimension) and
    the instruction stream replicated. The scan body is purely
    batch-elementwise, so the partition needs zero collectives — each
    device runs its slice of the verification batch.

    Mode '0' partitions via GSPMD shardings. The Pallas modes ('1',
    'step') go through shard_map instead: a pallas_call is opaque to the
    GSPMD partitioner, but under shard_map each device traces its OWN
    per-shard program, so the fused kernel runs unchanged on every
    device's batch slice."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    if pallas_mode == "0":
        batch_sh = NamedSharding(mesh, P(mesh.axis_names))
        repl = NamedSharding(mesh, P())
        return jax.jit(
            _named_body(name),
            in_shardings=(
                batch_sh,
                repl,
                repl,
                repl,
                tuple(repl for _ in range(7)),
            ),
            out_shardings=batch_sh,
        )

    spec_b = P(mesh.axis_names)
    repl = P()
    # a pallas_call's outputs carry no varying-mesh-axes metadata for the
    # vma/replication checker; the body is batch-elementwise so the manual
    # partition is trivially consistent. jax < 0.5 ships shard_map under
    # jax.experimental, and the checker flag was renamed check_rep ->
    # check_vma later still — so detect the kwarg, not just the attribute.
    import inspect

    if hasattr(jax, "shard_map"):
        shard_map_fn = jax.shard_map
    else:
        from jax.experimental.shard_map import shard_map as shard_map_fn
    if "check_vma" in inspect.signature(shard_map_fn).parameters:
        check_kw = {"check_vma": False}
    else:
        check_kw = {"check_rep": False}
    def per_shard(i, t, ir, o, ins):
        return _vm_body(i, t, ir, o, ins, pallas_mode)

    per_shard.__name__ = per_shard.__qualname__ = name
    body = shard_map_fn(
        per_shard,
        mesh=mesh,
        in_specs=(spec_b, repl, repl, repl, tuple(repl for _ in range(7))),
        out_specs=spec_b,
        **check_kw,
    )
    return jax.jit(body)


def execute(program: Program,
            inputs: Union[Dict[str, np.ndarray], np.ndarray], batch_shape=(),
            mesh=None, device_inputs=None) -> Dict[str, np.ndarray]:
    """Run an assembled program. Input arrays must be canonical Montgomery
    limb arrays of shape batch_shape + (NUM_LIMBS,), or ``inputs`` is the
    input stack itself (a batch_shape + (n_inputs, NUM_LIMBS) uint32 array
    in input_names order, as ``stack_inputs`` builds it). Returns named
    outputs (loose, bounded < 2^382). With ``mesh``, the leading batch
    axis is sharded over ALL the mesh's axes (batch_shape[0] must divide
    by the total device count). ``device_inputs`` (names, array): inputs
    already on the device, as a batch_shape + (len(names), NUM_LIMBS)
    uint32 array (the pubkey table's gathered keys); ``inputs`` then holds
    the rest.
    The program and its executable are the same either way.

    Execution backend (CONSENSUS_SPECS_TPU_VM_EXEC): ``interp`` runs the
    lax.scan interpreter below; ``fused`` runs the straight-line lowering
    (ops/vm_compile.py — same schedule, no register file, bit-identical
    outputs); ``auto`` (default) takes fused only when its artifact is
    already compiled in-process for THIS batch shape and its measured
    ms/row beats the interpreter's — auto never pays the cold fused
    trace+compile bill mid-call (``warm_fused`` or a pinned-``fused``
    call is what compiles shapes). A
    fused trace/compile/run failure falls back to the interpreter with a
    ``vm/fused_fallback`` flight event — this entry point never fails for
    lowering reasons."""
    from . import profiling, vm_compile
    from ..obs import tracing

    if isinstance(inputs, np.ndarray):
        stacked = program.check_stack(inputs, batch_shape)
    elif device_inputs is None:
        stacked = program.stack_inputs(inputs, tuple(batch_shape))
    else:
        stacked = program.place_inputs(inputs, device_inputs,
                                       tuple(batch_shape))
    label = (
        f"vm[steps={program.n_steps},regs={program.n_regs},"
        f"batch={tuple(batch_shape)},sharded={mesh is not None}]"
    )
    rows = 1
    for d in batch_shape:
        rows *= int(d)
    token = object()
    first = _SEEN_LABELS.setdefault(label, token) is token
    kind = program.kind or "program"
    if first:
        tracing.note_first(label, kind)
    path = "interp"
    compile_inclusive = False
    with tracing.span(f"vm.{kind}", k=program.k, rows=rows, first=first):
        t0 = time.perf_counter()
        shape_sig = (tuple(int(d) for d in batch_shape), mesh is not None)
        with profiling.timed(label):
            out = None
            if vm_compile.use_fused(program, shape_sig=shape_sig):
                try:
                    out, compile_inclusive = vm_compile.run_fused(
                        program, stacked, mesh=mesh)
                    path = "fused"
                except Exception as e:
                    vm_compile.note_fallback(program, e)
                    out = None
            if out is None:
                template = program.const_template()
                instr = tuple(jnp.asarray(x) for x in program.instr)
                out = _execute_device(
                    stacked, template, program.input_regs,
                    program.output_regs, instr, mesh, kind=kind,
                )
            # block BEFORE the timer stops, on BOTH backends: jax dispatch
            # is async (CPU included), and the routing ledger below
            # compares the two paths' dt against each other — an unblocked
            # dt records dispatch, not compute, and would poison the
            # measured-winner ``auto`` route. The fused path already
            # materialized inside run_fused (inside the try above, so async
            # runtime failures fall back to the interpreter too); this
            # block is what times the interpreter path and is a no-op
            # re-block for fused.
            out.block_until_ready()
        dt = time.perf_counter() - t0
    # per-program measured ms/row, per backend: the ledger the ``auto``
    # route reads (fused first-shape calls are compile-inclusive and
    # excluded; the stored value is the process-lifetime warm minimum)
    vm_compile.note_execution(program, path, dt, rows, compile_inclusive)
    # span-trace plane (obs/tracing.py): VM executions ride the Chrome
    # trace export next to the serve pipeline's request spans. Opt-in —
    # the disabled cost is one env read per execute() (device-call scale,
    # not hot-loop scale).
    if tracing.trace_enabled():
        tracing.global_tracer().note_execution(
            steps=program.n_steps, regs=program.n_regs,
            batch=tuple(batch_shape), sharded=mesh is not None,
            t0=t0, seconds=dt,
        )
    out = np.asarray(out)
    return {
        name: out[..., i, :]
        for i, name in enumerate(program.output_names)
    }


# the vm[...] labels executed so far in this process (a span's ``first``)
_SEEN_LABELS: Dict[str, object] = {}


def _pallas_mode() -> str:
    """The CONSENSUS_SPECS_TPU_PALLAS dispatch mode, normalized to the
    static _vm_body argument ('0' | '1' | 'step')."""
    v = os.environ.get("CONSENSUS_SPECS_TPU_PALLAS", "0")
    return v if v in ("1", "step") else "0"


def _execute_device(stacked, template, input_regs, output_regs, instr, mesh,
                    kind=None):
    """Run the interpreter on the device (sharded over ``mesh`` when
    given), under the XLA module named for the program ``kind``."""
    mode = _pallas_mode()
    sig = (tuple((np.shape(x), str(x.dtype)) for x in
                 (stacked, template, input_regs, output_regs) + tuple(instr)),
           mode, mesh)
    name = _module_name(kind, sig)
    if mesh is None:
        return _vm_run(name)(
            jnp.asarray(stacked),
            jnp.asarray(template),
            jnp.asarray(input_regs),
            jnp.asarray(output_regs),
            instr,
            mode,
        )
    from jax.sharding import NamedSharding, PartitionSpec as P

    batch_sh = NamedSharding(mesh, P(mesh.axis_names))
    repl = NamedSharding(mesh, P())
    stacked_d = jax.device_put(jnp.asarray(stacked), batch_sh)
    args_d = tuple(
        jax.device_put(jnp.asarray(x), repl)
        for x in (template, input_regs, output_regs)
    )
    instr_d = tuple(jax.device_put(x, repl) for x in instr)
    return _vm_run_for_mesh(mesh, mode, name)(stacked_d, *args_d, instr_d)
