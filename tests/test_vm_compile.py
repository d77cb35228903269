"""Fused straight-line lowering (ops/vm_compile.py, ISSUE 13): identity
against the interpreter and the exact-int IR oracle, chunk-boundary
liveness, routing (interp|fused|auto + the measured-winner persistence),
the interpreter fallback with its flight event, and the fused
``.vm_cache`` key/prune rules.

Everything here runs at SYNTHETIC-program scale (tens of levels, tiny
chunk overrides) so the whole module stays in the tier-1 budget — the
fused XLA compile bill for REGISTRY programs (~0.4 s per scheduled level
on CPU) lives in `make vmexec-smoke` and the @slow tier instead."""
import os
import random

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from consensus_specs_tpu.ops import (  # noqa: E402
    bls_backend as bb, fq, vm, vm_analysis, vm_compile, vmlib,
)
from consensus_specs_tpu.utils import bls12_381 as O  # noqa: E402

rng = random.Random(31)

BUCKET = dict(w_mul=64, w_lin=64, pad_steps_to=256, pad_regs_to=64)


@pytest.fixture(autouse=True)
def _fresh_fused_state():
    vm_compile.reset_fused_state()
    yield
    vm_compile.reset_fused_state()


def _mixed_prog(depth=6):
    """A synthetic program exercising every op kind, constants, input
    reuse, and enough depth to span several tiny chunks."""
    prog = vm.Prog()
    a = prog.inp("a")
    b = prog.inp("b")
    c = prog.inp("c")
    k = prog.const(0x1234567890ABCDEF ^ O.P // 3)
    acc = a * b + k
    other = (b - c) * (a + k)
    for _ in range(depth):
        acc = acc * acc + other
        other = other * b - a
    prog.out(acc, "acc")
    prog.out(other, "other")
    return prog


def _rand_inputs(prog, rows=0):
    names = set()
    ints = [
        {n: rng.randrange(O.P) for n in prog.input_names}
        for _ in range(max(1, rows))
    ]
    if rows:
        arrs = {
            n: np.stack([fq.to_mont_int(row[n]) for row in ints])
            for n in ints[0]
        }
    else:
        arrs = {n: fq.to_mont_int(v) for n, v in ints[0].items()}
    return ints, arrs


def _run_both(assembled, arrs, batch_shape, monkeypatch):
    monkeypatch.setenv("CONSENSUS_SPECS_TPU_VM_EXEC", "interp")
    out_i = vm.execute(assembled, arrs, batch_shape=batch_shape)
    monkeypatch.setenv("CONSENSUS_SPECS_TPU_VM_EXEC", "fused")
    out_f = vm.execute(assembled, arrs, batch_shape=batch_shape)
    return out_i, out_f


def test_fused_identity_and_oracle_scalar(monkeypatch):
    prog = _mixed_prog()
    assembled = prog.assemble(**BUCKET)
    ints, arrs = _rand_inputs(prog)
    monkeypatch.setenv("CONSENSUS_SPECS_TPU_VM_FUSED_CHUNK", "5")
    out_i, out_f = _run_both(assembled, arrs, (), monkeypatch)
    want = vm_analysis.eval_ir(prog, ints[0])
    for name in out_i:
        assert np.array_equal(np.asarray(out_i[name]),
                              np.asarray(out_f[name])), name
        got = fq.limbs_to_int(np.asarray(out_f[name]))
        # full loose-representative identity, not just mod-p agreement
        assert got == want[name], name
    assert vm_compile._COUNTERS["executions"] == 1
    assert vm_compile._COUNTERS["fallbacks"] == 0


def test_fused_identity_batch_axis(monkeypatch):
    prog = _mixed_prog(depth=4)
    assembled = prog.assemble(**BUCKET)
    ints, arrs = _rand_inputs(prog, rows=3)
    monkeypatch.setenv("CONSENSUS_SPECS_TPU_VM_FUSED_CHUNK", "4")
    out_i, out_f = _run_both(assembled, arrs, (3,), monkeypatch)
    for name in out_i:
        assert np.array_equal(np.asarray(out_i[name]),
                              np.asarray(out_f[name])), name
    for r in range(3):
        want = vm_analysis.eval_ir(prog, ints[r])
        for name, w in want.items():
            assert fq.limbs_to_int(np.asarray(out_f[name])[r]) == w


@pytest.mark.parametrize("chunk", ["1", "3", "1000000"])
def test_chunk_boundary_liveness(monkeypatch, chunk):
    """Identity must hold at EVERY chunking — chunk=1 puts a carry
    boundary after every level (maximum live-set stress), the huge value
    collapses to a single chunk (no boundaries at all)."""
    prog = _mixed_prog(depth=3)
    assembled = prog.assemble(**BUCKET)
    ints, arrs = _rand_inputs(prog)
    monkeypatch.setenv("CONSENSUS_SPECS_TPU_VM_FUSED_CHUNK", chunk)
    out_i, out_f = _run_both(assembled, arrs, (), monkeypatch)
    for name in out_i:
        assert np.array_equal(np.asarray(out_i[name]),
                              np.asarray(out_f[name])), name


def test_fused_f12_formula_vs_oracle(monkeypatch):
    """A real vmlib formula block (Fq12 mul) through the fused backend,
    held to the pure-Python field oracle — the same contract
    tests/test_vm.py pins on the interpreter."""
    prog = vm.Prog()
    x = [prog.inp(f"x{i}") for i in range(12)]
    y = [prog.inp(f"y{i}") for i in range(12)]
    m = vmlib.f12_mul(prog, x, y)
    for i, c in enumerate(m):
        prog.out(c, f"m{i}")
    assembled = prog.assemble(**BUCKET)
    ints, arrs = _rand_inputs(prog)
    monkeypatch.setenv("CONSENSUS_SPECS_TPU_VM_FUSED_CHUNK", "8")
    out_i, out_f = _run_both(assembled, arrs, (), monkeypatch)
    for name in out_i:
        assert np.array_equal(np.asarray(out_i[name]),
                              np.asarray(out_f[name])), name
    want = vm_analysis.eval_ir(prog, ints[0])
    for name, w in want.items():
        assert fq.limbs_to_int(np.asarray(out_f[name])) == w


def test_fused_fallback_flight_event(monkeypatch):
    """A fused trace/compile/run failure must fall back to the
    interpreter (correct outputs, no exception) and journal a
    vm/fused_fallback flight event."""
    from consensus_specs_tpu.obs import flight

    prog = _mixed_prog(depth=2)
    assembled = prog.assemble(**BUCKET)
    ints, arrs = _rand_inputs(prog)
    monkeypatch.setenv("CONSENSUS_SPECS_TPU_VM_EXEC", "fused")
    monkeypatch.setenv("CONSENSUS_SPECS_TPU_FLIGHT", "1")
    flight.reset_global()

    def boom(*a, **kw):
        raise RuntimeError("injected lowering failure")

    monkeypatch.setattr(vm_compile, "run_fused", boom)
    out = vm.execute(assembled, arrs)
    want = vm_analysis.eval_ir(prog, ints[0])
    for name, w in want.items():
        assert fq.limbs_to_int(np.asarray(out[name])) == w
    assert vm_compile._COUNTERS["fallbacks"] == 1
    events = [e for e in flight.global_recorder().events()
              if e.get("plane") == "vm" and e.get("kind") == "fused_fallback"]
    assert events, "fused_fallback flight event missing"
    assert "injected lowering failure" in events[-1]["data"]["error"]
    flight.reset_global()


def test_auto_routing_uses_measured_winner(monkeypatch):
    """auto == interp until a fused measurement exists; once the ledger
    holds both warm numbers the measured winner takes the call."""
    prog = _mixed_prog(depth=2)
    assembled = prog.assemble(**BUCKET)
    monkeypatch.setenv("CONSENSUS_SPECS_TPU_VM_EXEC", "auto")
    assert not vm_compile.use_fused(assembled)  # no measurements: interp
    assembled._exec_stats = {"fused_ms_row": 1.0, "interp_ms_row": 5.0}
    assert vm_compile.use_fused(assembled)
    assembled._exec_stats = {"fused_ms_row": 5.0, "interp_ms_row": 1.0}
    assert not vm_compile.use_fused(assembled)
    monkeypatch.setenv("CONSENSUS_SPECS_TPU_VM_EXEC", "interp")
    assembled._exec_stats = {"fused_ms_row": 1.0, "interp_ms_row": 5.0}
    assert not vm_compile.use_fused(assembled)  # pinned interp always wins
    monkeypatch.setenv("CONSENSUS_SPECS_TPU_VM_EXEC", "fused")
    assembled._exec_stats = {}
    assert vm_compile.use_fused(assembled)  # pinned fused compiles on demand


def test_auto_routing_persists_across_processes(monkeypatch, tmp_path):
    """The measured-winner pair rides the .vm_cache lowering plan: a
    fresh Program instance (== fresh process) with the same fused cache
    key adopts the persisted verdict — but auto only SERVES fused once
    the shape is compiled (warm_fused/pinned-fused), never paying the
    cold compile bill mid-call."""
    monkeypatch.setattr(bb, "_vm_cache_dir", lambda: str(tmp_path))
    monkeypatch.setenv("CONSENSUS_SPECS_TPU_VM_FUSED_CHUNK", "6")
    prog = _mixed_prog(depth=2)
    assembled = prog.assemble(**BUCKET)
    assembled.meta["fused_key"] = ("synthetic", 0, 1, "cafe0123")
    ints, arrs = _rand_inputs(prog)

    # measure both paths in "process one" (interp first, then fused twice
    # so the second, warm call lands in the ledger and persists)
    monkeypatch.setenv("CONSENSUS_SPECS_TPU_VM_EXEC", "interp")
    vm.execute(assembled, arrs)
    vm.execute(assembled, arrs)
    monkeypatch.setenv("CONSENSUS_SPECS_TPU_VM_EXEC", "fused")
    vm.execute(assembled, arrs)
    vm.execute(assembled, arrs)
    st = assembled._exec_stats
    assert st.get("fused_ms_row") is not None
    assert st.get("interp_ms_row") is not None

    plan_path = vm_compile._plan_cache_path(assembled)
    assert plan_path is not None and os.path.exists(plan_path)
    import pickle

    with open(plan_path, "rb") as fh:
        meas = pickle.load(fh).get("measured") or {}
    assert "fused_ms_row" in meas and "interp_ms_row" in meas

    # force the persisted pair to a known winner, then simulate a fresh
    # process: a new Program object with the same cache identity
    with open(plan_path, "rb") as fh:
        plan = pickle.load(fh)
    plan["measured"] = {"fused_ms_row": 1.0, "interp_ms_row": 9.0}
    with open(plan_path, "wb") as fh:
        pickle.dump(plan, fh)
    vm_compile.reset_fused_state()
    fresh = prog.assemble(**BUCKET)
    fresh.meta["fused_key"] = ("synthetic", 0, 1, "cafe0123")
    monkeypatch.setenv("CONSENSUS_SPECS_TPU_VM_EXEC", "auto")
    assert vm_compile.use_fused(fresh)  # winner adopted off the disk plan
    # ...but a not-yet-compiled shape must stay on the interpreter: auto
    # never pays the cold trace+compile bill inside a call
    assert not vm_compile.use_fused(fresh, shape_sig=((), False))
    before = vm_compile._COUNTERS["executions"]
    out_cold = vm.execute(fresh, arrs)
    assert vm_compile._COUNTERS["executions"] == before  # interp served it
    vm_compile.warm_fused(fresh, ())
    assert vm_compile.use_fused(fresh, shape_sig=((), False))
    out_a = vm.execute(fresh, arrs)
    assert vm_compile._COUNTERS["executions"] == before + 1  # fused now
    monkeypatch.setenv("CONSENSUS_SPECS_TPU_VM_EXEC", "interp")
    out_i = vm.execute(fresh, arrs)
    for name in out_i:
        assert np.array_equal(np.asarray(out_a[name]),
                              np.asarray(out_i[name])), name
        assert np.array_equal(np.asarray(out_cold[name]),
                              np.asarray(out_i[name])), name

    # a persisted interp win keeps auto on the interpreter
    plan["measured"] = {"fused_ms_row": 9.0, "interp_ms_row": 1.0}
    with open(plan_path, "wb") as fh:
        pickle.dump(plan, fh)
    vm_compile.reset_fused_state()
    fresh2 = prog.assemble(**BUCKET)
    fresh2.meta["fused_key"] = ("synthetic", 0, 1, "cafe0123")
    monkeypatch.setenv("CONSENSUS_SPECS_TPU_VM_EXEC", "auto")
    assert not vm_compile.use_fused(fresh2)


def test_warm_fused_reports_compile_seconds(monkeypatch):
    prog = _mixed_prog(depth=2)
    assembled = prog.assemble(**BUCKET)
    monkeypatch.setenv("CONSENSUS_SPECS_TPU_VM_FUSED_CHUNK", "6")
    dt = vm_compile.warm_fused(assembled, ())
    assert dt > 0.0
    assert vm_compile.warm_fused(assembled, ()) == 0.0  # in-process warm


# -- fused .vm_cache key + prune rules (ISSUE 13 + 15 satellites) ----------


def _plan_name(lowering=None, version=None, kind="g2_subgroup", fp=None,
               chunk=24):
    lowering = vm_compile.LOWERING_VERSION if lowering is None else lowering
    version = bb._VM_CACHE_VERSION if version is None else version
    fp = bb._program_fingerprint(kind) if fp is None else fp
    return (f"fusedplan_l{lowering}_v{version}_{fp}_{kind}"
            f"_k0_f1_w96x192_p1024_c{chunk}.pkl")


def _struct_name(key="ab" * 12, lowering=None):
    lowering = vm_compile.LOWERING_VERSION if lowering is None else lowering
    return f"fusedstruct_l{lowering}_{key}.pkl"


def test_fused_cache_stale_rules():
    assert not bb._vm_cache_entry_stale(_plan_name())
    # a lowering bump evicts fused plans WITHOUT touching interp tensors
    assert bb._vm_cache_entry_stale(
        _plan_name(lowering=vm_compile.LOWERING_VERSION + 1))
    assert bb._vm_cache_entry_stale(
        _plan_name(version=bb._VM_CACHE_VERSION + 1))
    # a moved per-program fingerprint (edited builder) evicts too
    assert bb._vm_cache_entry_stale(_plan_name(fp="00000000"))
    # unknown kinds are kept (age/size still bound them)
    assert not bb._vm_cache_entry_stale(
        _plan_name(kind="not_a_builder", fp="00000000"))
    # shared structure bodies re-key on the lowering version alone
    assert not bb._vm_cache_entry_stale(_struct_name())
    assert bb._vm_cache_entry_stale(
        _struct_name(lowering=vm_compile.LOWERING_VERSION + 1))
    # the RETIRED PR 13 per-program keying is stale on sight — ANY
    # version, including one matching the current numbers
    assert bb._vm_cache_entry_stale(
        f"fused_l{vm_compile.LOWERING_VERSION}_v{bb._VM_CACHE_VERSION}_"
        f"{bb._program_fingerprint('g2_subgroup')}_g2_subgroup"
        "_k0_f1_w96x192_p1024_c24.pkl")
    assert bb._vm_cache_entry_stale("fused_l1_v2_cafe_g2_subgroup"
                                    "_k0_f1_w96x192_p1024_c24.pkl")
    assert bb._vm_cache_entry_stale("fused_weird.pkl")
    # malformed new-prefix names are kept, never crash
    assert not bb._vm_cache_entry_stale("fusedplan_weird.pkl")
    assert not bb._vm_cache_entry_stale("fusedstruct_weird.pkl")


def _write_plan_entry(tmp_path, refs, name=None):
    import pickle

    p = tmp_path / (name or _plan_name())
    with open(p, "wb") as fh:
        pickle.dump({"format": 2, "struct_refs": list(refs)}, fh)
    return p


def test_prune_evicts_stale_fused_entries(tmp_path):
    stale = tmp_path / _plan_name(lowering=vm_compile.LOWERING_VERSION + 1)
    old_keying = tmp_path / (
        "fused_l1_v2_cafe_g2_subgroup_k0_f1_w96x192_p1024_c24.pkl")
    interp = tmp_path / (
        f"v{bb._VM_CACHE_VERSION}_{bb._program_fingerprint('g2_subgroup')}"
        "_g2_subgroup_k0_f1_w96x192_p1024.pkl")
    for p in (stale, old_keying, interp):
        p.write_bytes(b"x" * 64)
    fresh = _write_plan_entry(tmp_path, [])
    res = bb.prune_vm_cache(max_age_days=0, max_bytes=0,
                            cache_dir=str(tmp_path))
    assert not stale.exists()      # old lowering version: gone immediately
    assert not old_keying.exists()  # retired PR 13 keying: gone on sight
    assert fresh.exists()          # current fused plan: kept
    assert interp.exists()         # interp tensors: untouched by the bump
    assert res["evicted"] == 2 and res["kept"] == 2


def test_prune_keeps_referenced_structs_evicts_orphans(tmp_path):
    key_live, key_orphan = "aa" * 12, "bb" * 12
    live = tmp_path / _struct_name(key_live)
    orphan = tmp_path / _struct_name(key_orphan)
    for p in (live, orphan):
        p.write_bytes(b"x" * 64)
    plan = _write_plan_entry(tmp_path, [key_live])
    # make everything "old": referenced structs must still survive the
    # age rule because their referencing plan survives
    import os as _os
    import time as _time

    old = _time.time() - 90 * 86400
    _os.utime(live, (old, old))
    _os.utime(orphan, (old, old))
    res = bb.prune_vm_cache(max_age_days=365, max_bytes=0,
                            cache_dir=str(tmp_path))
    assert plan.exists()
    assert live.exists()        # referenced: survives despite its age
    assert not orphan.exists()  # no referencing plan: evicted
    assert res["evicted"] == 1


def test_prune_drops_structs_when_referencing_plan_goes(tmp_path):
    """When the last referencing plan is age-evicted, its structures
    orphan and go in the same prune; a corrupt plan contributes no refs
    (and the loader side falls back to re-derivation, tested below)."""
    key = "cc" * 12
    struct = tmp_path / _struct_name(key)
    struct.write_bytes(b"x" * 64)
    plan = _write_plan_entry(tmp_path, [key])
    import os as _os
    import time as _time

    old = _time.time() - 90 * 86400
    _os.utime(plan, (old, old))
    res = bb.prune_vm_cache(max_age_days=30, max_bytes=0,
                            cache_dir=str(tmp_path))
    assert not plan.exists()
    assert not struct.exists()
    assert res["evicted"] == 2


def test_fused_key_rides_program_cache(tmp_path, monkeypatch):
    """bls_backend._program stamps the fused cache identity onto the
    assembled (and disk-cached) program's meta so the lowering can disk-
    key its plan; the stamp survives the pickle round-trip."""
    monkeypatch.setattr(bb, "_vm_cache_dir", lambda: str(tmp_path))
    bb._program.cache_clear()
    try:
        prog, fold = bb._program("g2_subgroup", 0, 1)
        key = prog.meta.get("fused_key")
        assert key is not None
        kind, k, f, fp = key
        assert (kind, k, f) == ("g2_subgroup", 0, 1)
        assert fp == bb._program_fingerprint("g2_subgroup")
        bb._program.cache_clear()
        again, _ = bb._program("g2_subgroup", 0, 1)  # disk hit this time
        assert again.meta.get("fused_key") == key
    finally:
        bb._program.cache_clear()


# -- structural dedup + super-op coarsening (ISSUE 15) ---------------------


def _periodic_prog(iters=10):
    """A ladder-shaped program: one fixed loop body stamped ``iters``
    times — the structure class the chunk canonicalizer collapses. The
    two chains consume each other so the scheduler keeps them in
    lockstep (a constant steady-state live width, like the production
    square-and-multiply ladders); the per-iteration constants prove
    constants dedup as runtime operands."""
    prog = vm.Prog()
    acc = prog.inp("acc")
    other = prog.inp("other")
    for i in range(iters):
        k = prog.const(1000003 * (i + 1))  # per-iteration constant
        acc = acc * acc + other * k
        other = other * other - acc
    prog.out(acc, "acc")
    prog.out(other, "other")
    return prog


def test_structural_dedup_collapses_chunks(monkeypatch):
    """The ladder's repeated chunks must hash to FEWER distinct
    structures than chunks, runs must fold into scan super-ops, and the
    outputs must stay bit-identical to the interpreter + oracle."""
    prog = _periodic_prog(iters=12)
    assembled = prog.assemble(**BUCKET)
    ints, arrs = _rand_inputs(prog)
    monkeypatch.setenv("CONSENSUS_SPECS_TPU_VM_FUSED_CHUNK", "4")
    monkeypatch.setenv("CONSENSUS_SPECS_TPU_VM_SUPEROP", "2")
    out_i, out_f = _run_both(assembled, arrs, (), monkeypatch)
    for name in out_i:
        assert np.array_equal(np.asarray(out_i[name]),
                              np.asarray(out_f[name])), name
    want = vm_analysis.eval_ir(prog, ints[0])
    for name, w in want.items():
        assert fq.limbs_to_int(np.asarray(out_f[name])) == w
    fp = vm_compile._FUSED[id(assembled)]
    st = fp.struct_stats
    assert st["distinct_structs"] < st["chunks"], st
    assert st["superop_segments"] >= 1, st
    # compile units actually dedup'd: fewer misses than chunks
    assert vm_compile._COUNTERS["struct_misses"] < st["chunks"] + 1


def test_superop_off_still_identical(monkeypatch):
    prog = _periodic_prog(iters=8)
    assembled = prog.assemble(**BUCKET)
    ints, arrs = _rand_inputs(prog, rows=2)
    monkeypatch.setenv("CONSENSUS_SPECS_TPU_VM_FUSED_CHUNK", "4")
    monkeypatch.setenv("CONSENSUS_SPECS_TPU_VM_SUPEROP", "off")
    out_i, out_f = _run_both(assembled, arrs, (2,), monkeypatch)
    for name in out_i:
        assert np.array_equal(np.asarray(out_i[name]),
                              np.asarray(out_f[name])), name
    fp = vm_compile._FUSED[id(assembled)]
    assert fp.struct_stats["superop_segments"] == 0


def test_dedup_off_pins_per_chunk_baseline(monkeypatch):
    """CONSENSUS_SPECS_TPU_VM_DEDUP=0 is the PR 13 one-compile-per-chunk
    baseline: every chunk its own structure, no super-ops, identity
    unchanged."""
    prog = _periodic_prog(iters=8)
    assembled = prog.assemble(**BUCKET)
    ints, arrs = _rand_inputs(prog)
    monkeypatch.setenv("CONSENSUS_SPECS_TPU_VM_FUSED_CHUNK", "4")
    monkeypatch.setenv("CONSENSUS_SPECS_TPU_VM_DEDUP", "0")
    out_i, out_f = _run_both(assembled, arrs, (), monkeypatch)
    for name in out_i:
        assert np.array_equal(np.asarray(out_i[name]),
                              np.asarray(out_f[name])), name
    fp = vm_compile._FUSED[id(assembled)]
    st = fp.struct_stats
    assert st["distinct_structs"] == st["chunks"]
    assert st["superop_segments"] == 0


def test_struct_cache_shared_across_programs(monkeypatch):
    """Two PROGRAMS with the same canonical chunk structure share the
    in-process compiled structures: the second program's warm is all
    structural hits, zero new compiles — and the batch shape SERVED
    through those hits (a different (program, shape) pair than the one
    that compiled them) stays bit-identical to the interpreter and the
    exact-int oracle (the ISSUE 15 acceptance case)."""
    monkeypatch.setenv("CONSENSUS_SPECS_TPU_VM_FUSED_CHUNK", "4")
    a = _periodic_prog(iters=6).assemble(**BUCKET)
    prog_b = _periodic_prog(iters=6)
    b = prog_b.assemble(**BUCKET)  # fresh program, same canonical form
    vm_compile.warm_fused(a, ())
    misses_after_a = vm_compile._COUNTERS["struct_misses"]
    assert misses_after_a > 0
    vm_compile.warm_fused(b, ())
    assert vm_compile._COUNTERS["struct_misses"] == misses_after_a
    assert vm_compile._COUNTERS["struct_hits"] > 0
    ints, arrs = _rand_inputs(prog_b)
    out_i, out_f = _run_both(b, arrs, (), monkeypatch)
    want = vm_analysis.eval_ir(prog_b, ints[0])
    for name in out_i:
        assert np.array_equal(np.asarray(out_i[name]),
                              np.asarray(out_f[name])), name
        assert fq.limbs_to_int(np.asarray(out_f[name])) == want[name]


def test_corrupted_struct_entry_falls_back_to_rederive(
        monkeypatch, tmp_path):
    """A corrupted shared structure entry must make _load_plan return
    None (the caller re-derives and re-stores) — never raise into the
    execute path."""
    monkeypatch.setattr(bb, "_vm_cache_dir", lambda: str(tmp_path))
    monkeypatch.setenv("CONSENSUS_SPECS_TPU_VM_FUSED_CHUNK", "4")
    prog = _periodic_prog(iters=6)
    assembled = prog.assemble(**BUCKET)
    assembled.meta["fused_key"] = ("synthetic", 0, 1, "cafe0123")
    fp = vm_compile.fused_program(assembled)  # derives + stores
    refs = sorted(fp.plan["structs"])
    assert refs
    for ref in refs:
        spath = vm_compile._struct_cache_path(ref)
        assert os.path.exists(spath), ref
    # corrupt one structure entry on disk
    with open(vm_compile._struct_cache_path(refs[0]), "wb") as fh:
        fh.write(b"not a pickle")
    assert vm_compile._load_plan(assembled) is None
    # a fresh "process" still lowers fine (re-derive + re-store)
    vm_compile.reset_fused_state()
    fresh = prog.assemble(**BUCKET)
    fresh.meta["fused_key"] = ("synthetic", 0, 1, "cafe0123")
    ints, arrs = _rand_inputs(prog)
    monkeypatch.setenv("CONSENSUS_SPECS_TPU_VM_EXEC", "fused")
    out = vm.execute(fresh, arrs)
    want = vm_analysis.eval_ir(prog, ints[0])
    for name, w in want.items():
        assert fq.limbs_to_int(np.asarray(out[name])) == w
    assert vm_compile._load_plan(fresh) is not None  # re-stored intact


def test_env_knob_hardening_warns_once(monkeypatch, capsys):
    """Invalid or non-positive structural-dedup knobs warn ONCE on
    stderr and fall back to the documented default — never raise."""
    vm_compile._ENV_WARNED.clear()
    monkeypatch.setenv("CONSENSUS_SPECS_TPU_VM_FUSED_CHUNK", "banana")
    assert vm_compile.chunk_steps() == vm_analysis.FUSED_CHUNK_STEPS
    assert vm_compile.chunk_steps() == vm_analysis.FUSED_CHUNK_STEPS
    err = capsys.readouterr().err
    assert err.count("CONSENSUS_SPECS_TPU_VM_FUSED_CHUNK") == 1
    monkeypatch.setenv("CONSENSUS_SPECS_TPU_VM_FUSED_CHUNK", "-8")
    vm_compile._ENV_WARNED.clear()
    assert vm_compile.chunk_steps() == vm_analysis.FUSED_CHUNK_STEPS
    assert "ignoring invalid" in capsys.readouterr().err
    monkeypatch.setenv("CONSENSUS_SPECS_TPU_VM_DEDUP", "maybe")
    vm_compile._ENV_WARNED.clear()
    assert vm_compile.dedup_enabled() is True
    assert "CONSENSUS_SPECS_TPU_VM_DEDUP" in capsys.readouterr().err
    monkeypatch.setenv("CONSENSUS_SPECS_TPU_VM_SUPEROP", "1")
    vm_compile._ENV_WARNED.clear()
    assert vm_compile.superop_min_run({"sched_steps": 8, "n_mul": 1,
                                       "n_lin": 1}) == 3  # auto fallback
    assert "CONSENSUS_SPECS_TPU_VM_SUPEROP" in capsys.readouterr().err
    # valid values parse silently
    monkeypatch.setenv("CONSENSUS_SPECS_TPU_VM_FUSED_CHUNK", "7")
    assert vm_compile.chunk_steps() == 7
    monkeypatch.setenv("CONSENSUS_SPECS_TPU_VM_SUPEROP", "4")
    assert vm_compile.superop_min_run({"sched_steps": 8}) == 4
    monkeypatch.setenv("CONSENSUS_SPECS_TPU_VM_SUPEROP", "off")
    assert vm_compile.superop_min_run({"sched_steps": 8}) == 0
    assert capsys.readouterr().err == ""


def test_background_warm_flips_auto_to_fused(monkeypatch):
    """CONSENSUS_SPECS_TPU_VM_WARM_BG=1: an auto-routed call whose
    measured winner is fused but whose shape is cold serves the
    INTERPRETER and enqueues a background warm; once the warm lands,
    auto flips to fused for that shape."""
    prog = _mixed_prog(depth=2)
    assembled = prog.assemble(**BUCKET)
    ints, arrs = _rand_inputs(prog)
    monkeypatch.setenv("CONSENSUS_SPECS_TPU_VM_FUSED_CHUNK", "6")
    monkeypatch.setenv("CONSENSUS_SPECS_TPU_VM_EXEC", "auto")
    monkeypatch.setenv("CONSENSUS_SPECS_TPU_VM_WARM_BG", "1")
    assembled._exec_stats = {"fused_ms_row": 1.0, "interp_ms_row": 5.0}
    # shape not compiled: the call must stay on the interpreter...
    assert not vm_compile.use_fused(assembled, shape_sig=((), False))
    before = vm_compile._COUNTERS["executions"]
    out_cold = vm.execute(assembled, arrs)
    assert vm_compile._COUNTERS["executions"] == before
    # ...but the background warm flips the route once it lands
    assert vm_compile.bg_warm_drain(timeout=120.0)
    assembled._exec_stats = {"fused_ms_row": 1.0, "interp_ms_row": 5.0}
    assert vm_compile.use_fused(assembled, shape_sig=((), False))
    out_warm = vm.execute(assembled, arrs)
    assert vm_compile._COUNTERS["executions"] == before + 1
    for name in out_cold:
        assert np.array_equal(np.asarray(out_cold[name]),
                              np.asarray(out_warm[name])), name


# -- `make native` discoverability warning (ISSUE 13 satellite) ------------


def test_assemble_warns_once_when_native_kernel_missing(monkeypatch, capsys):
    monkeypatch.setattr(vm, "_NATIVE_SCHED", None)
    monkeypatch.setattr(vm, "_NATIVE_WARNED", False)
    prog = _mixed_prog(depth=1)
    prog.assemble(**BUCKET)
    err = capsys.readouterr().err
    assert "make native" in err and "libvmsched" in err
    prog2 = _mixed_prog(depth=1)
    prog2.assemble(**BUCKET)
    assert "make native" not in capsys.readouterr().err  # once per process


def test_no_warning_when_native_kernel_present(monkeypatch, capsys):
    # _warn_native_missing only prints when the kernel is absent; with a
    # (real or stand-in) kernel loaded it stays silent
    monkeypatch.setattr(vm, "_NATIVE_SCHED", object())
    monkeypatch.setattr(vm, "_NATIVE_WARNED", False)
    vm._warn_native_missing()
    assert "make native" not in capsys.readouterr().err
    assert vm._NATIVE_WARNED is False


def test_fused_plan_path_carries_the_device_kind():
    """A fused plan's measurements are keyed by the device kind, and the
    cache prune still parses the keyed file name."""
    program, _ = bb._program("g1_subgroup")
    tag = vm_compile.device_kind_tag()
    assert tag == "cpu"
    path = vm_compile._plan_cache_path(program)
    assert os.path.basename(path).endswith(f"_d{tag}.pkl")
    assert not bb._vm_cache_entry_stale(os.path.basename(path))


# -- full-registry identity (out of tier-1) --------------------------------


@pytest.mark.slow
def test_vmexec_smoke_full_registry(monkeypatch):
    """The `make vmexec-smoke` module over the ENTIRE BUILDERS registry
    (production shapes): fused == interp == exact-int oracle. Pays one
    fused XLA compile per program — minutes-to-hours on a cold persistent
    cache, so @slow (the CI job runs the module's default cheap subset)."""
    from consensus_specs_tpu.ops import vmexec_smoke

    monkeypatch.setenv("VMEXEC_SMOKE_FULL", "1")
    assert vmexec_smoke.main() == 0
