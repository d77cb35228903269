"""vmlint (ops/vm_analysis.py + tools/vmlint.py): the VM static-analysis
gate. Tier-1 keeps to small program shapes (fold <= 2, minimal K) and pure
host analysis — no device execution, no XLA compiles; the full production
registry (chunk-16 rlc_combine, folded hard part) runs under --run-slow.

What must hold:
- the independent bound re-derivation confirms every registered program
  (zero soundness findings) and the tier-1 subset matches the committed
  VMLINT_BASELINE.json;
- a reintroduced PR 3 select-then-multiply ladder (input-ready ops consumed
  thousands of steps later) is statically hazard-flagged, while the shipped
  chained form is not;
- seeded assembler bugs — a tampered tracker bound, a capacity overflow, a
  violated borrowless-subtract precondition, an unsound input declaration —
  each produce an error finding, and the gate turns any error or baseline
  pressure/depth regression into a failure (what `make check` enforces).
"""
import pytest

from consensus_specs_tpu.ops import fq, vm, vm_analysis, vmlib

# the production assembly shape (mirrors ops/bls_backend W_MUL/W_LIN/pads)
SHAPE = dict(w_mul=96, w_lin=192, pad_steps_to=256, pad_regs_to=64)


def _tiny_prog():
    """A few ALU ops with every kind represented."""
    prog = vm.Prog()
    a, b, c = (prog.inp(n) for n in "abc")
    r = (a * b + c) - a
    prog.out(r * r, "r")
    return prog


# ---------------------------------------------------------------------------
# bound soundness
# ---------------------------------------------------------------------------


def test_tiny_program_rederives_clean():
    r = vm_analysis.analyze_prog(_tiny_prog(), name="tiny")
    assert r["errors"] == 0
    assert r["bounds"]["checked"] > 0
    assert r["bounds"]["max_bound_bits"] <= 420


def test_tampered_tracker_bound_is_detected():
    prog = _tiny_prog()
    # simulate assembler drift: one op's tracked bound disagrees with the
    # transfer function the ALU actually implements
    alu = next(i for i, op in enumerate(prog.ops) if op.kind == 0)
    prog.ops[alu].bound += 1
    r = vm_analysis.analyze_prog(prog, name="tampered")
    assert any(f["rule"] == "bound-mismatch" for f in r["findings"])
    assert r["errors"] >= 1


def test_seeded_capacity_overflow_is_detected_and_gated():
    prog = _tiny_prog()
    a = prog.inp("loose", bound=1 << 419)
    # bypass Prog.add's auto-compress the way an assembler bug would:
    # an ADD whose derived bound reaches the 15-limb capacity
    prog.ops.append(vm._Op(1, a.idx, a.idx, (1 << 419) * 2))
    r = vm_analysis.analyze_prog(prog, name="seeded")
    assert any(f["rule"] == "bound-overflow" for f in r["findings"])
    # the gate (what `make check` runs) must fail on it regardless of
    # baseline scalars
    failures = vm_analysis.gate(
        [r], {"seeded": vm_analysis.baseline_entry(r)})
    assert any("bound-overflow" in f for f in failures)


def test_sub_precondition_violation_is_detected():
    prog = vm.Prog()
    a = prog.inp("a")
    b = prog.inp("b", bound=1 << 410)  # > MP: illegal subtrahend
    prog.ops.append(vm._Op(2, a.idx, b.idx, fq.P + fq.MP))
    r = vm_analysis.analyze_prog(prog, name="subbug")
    assert any(
        f["rule"] == "sub-subtrahend-overflow" for f in r["findings"])


def test_unsound_input_declaration_is_detected():
    prog = vm.Prog()
    a = prog.inp("a", bound=1 << 100)  # tighter than p: no canonical
    prog.out(a * a, "r")               # residue fits the declaration
    r = vm_analysis.analyze_prog(prog, name="tightinput")
    assert any(f["rule"] == "input-bound-unsound" for f in r["findings"])


def test_redundant_compress_and_dead_values_flagged():
    prog = vm.Prog()
    a, b = prog.inp("a"), prog.inp("b")
    dead = a * b  # never reaches an out()
    assert dead.bound
    c = prog.compress(a)  # canonical input: compress reduces nothing
    prog.out(c + b, "r")
    r = vm_analysis.analyze_prog(prog, name="waste")
    assert r["bounds"]["dead_ops"] >= 1
    assert r["bounds"]["redundant_compress"] >= 1
    # waste is warn-class: it must NOT fail the gate
    assert r["errors"] == 0


# ---------------------------------------------------------------------------
# the PR 3 scheduler-hazard regression (select-then-multiply)
# ---------------------------------------------------------------------------


def _select_then_multiply(n_bits=96):
    """The register-blowup form PR 3 eliminated: every bit's multiply is
    PREcomputed against the loop-invariant f, so all n_bits x 12 products
    are input-ready, get scheduled at step ~0, and sit live until their
    distant ladder level consumes them."""
    prog = vm.Prog()
    f = [prog.inp(f"f.{j}") for j in range(12)]
    bits = [prog.inp(f"r.{t}") for t in range(n_bits)]
    pre = [[bits[t] * f[j] for j in range(12)] for t in range(n_bits)]
    acc = f
    for t in range(n_bits):
        acc = vmlib.f12_square(prog, acc)
        acc = [acc[j] + pre[t][j] for j in range(12)]
    for j in range(12):
        prog.out(acc[j], f"c.{j}")
    return prog


def _chained(n_bits=96):
    """The shipped form: every multiply chains on the accumulator, so live
    ranges stay one ladder level long."""
    prog = vm.Prog()
    f = [prog.inp(f"f.{j}") for j in range(12)]
    bits = [prog.inp(f"r.{t}") for t in range(n_bits)]
    acc = f
    for t in range(n_bits):
        acc = vmlib.f12_square(prog, acc)
        m = vmlib.f12_mul(prog, acc, f)
        acc = [acc[j] + (bits[t] * m[j]) for j in range(12)]
    for j in range(12):
        prog.out(acc[j], f"c.{j}")
    return prog


def test_select_then_multiply_hazard_is_flagged():
    bad = vm_analysis.analyze_prog(
        _select_then_multiply(), name="select", **SHAPE)
    good = vm_analysis.analyze_prog(_chained(), name="chained", **SHAPE)
    assert bad["pressure"]["hazard"] is True
    assert any(f["rule"] == "live-range-outliers" for f in bad["findings"])
    assert good["pressure"]["hazard"] is False
    assert good["errors"] == 0
    # the hazard IS a register blowup: several times the chained pressure
    assert bad["pressure"]["max_live"] > 3 * good["pressure"]["max_live"]
    # and the gate fails on it even with matching baseline scalars
    failures = vm_analysis.gate(
        [bad], {"select": vm_analysis.baseline_entry(bad)})
    assert any("live-range-outliers" in f for f in failures)


# ---------------------------------------------------------------------------
# schedule reports / cost model / assembled-program stats
# ---------------------------------------------------------------------------


def test_cost_report_classifies_and_predicts():
    prog = vmlib.build_hard_part(1)
    r = vm_analysis.analyze_prog(prog, name="hard", **SHAPE)
    c = r["cost"]
    # the hard part is the canonical depth-bound program: the critical
    # path IS the schedule, with mul utilization in the single digits
    assert c["classification"] == "depth-bound"
    assert c["critical_path"] == c["sched_steps"]
    assert c["mul_utilization"] < 0.10
    assert c["predicted_row_s"] > 0.5  # ~seconds per row on CPU
    assert len(c["mul_width_profile"]) == 8


# the legacy bit-serial hard part's padded step count (ISSUE 10's "4864-
# step chain"): the acceptance bar for the width-for-depth variants
_LEGACY_HARD_PART_STEPS = 4864


def test_hard_part_variants_recover_depth():
    """ISSUE 10 acceptance, satellite 3: the new hard-part variants cut
    the vmlint critical path below 0.5x the legacy 4864-step chain (the
    frobenius flagship >= 2.5x), and the pipelined multi-row fold-8 shape
    is no longer depth-bound — width hides the residual depth."""
    frob = vm_analysis.analyze_prog(
        vmlib.build_hard_part_frobenius(1), name="frob", **SHAPE)
    assert frob["errors"] == 0
    crit = frob["cost"]["critical_path"]
    assert crit < 0.5 * _LEGACY_HARD_PART_STEPS
    assert crit * 2.5 <= _LEGACY_HARD_PART_STEPS  # the >=2.5x flagship bar

    win = vm_analysis.analyze_prog(
        vmlib.build_hard_part_windowed(1), name="win", **SHAPE)
    assert win["errors"] == 0
    assert win["cost"]["critical_path"] < 0.5 * _LEGACY_HARD_PART_STEPS

    # the pipelined multi-row shape (fold 8, the _fold_for cap for the
    # new variants): classified balanced or width-bound, NOT depth-bound
    frob8 = vm_analysis.analyze_prog(
        vmlib.build_hard_part_frobenius(8), name="frob8", **SHAPE)
    assert frob8["errors"] == 0
    assert frob8["cost"]["classification"] in ("balanced", "width-bound")
    # and the depth recovery survives folding: same critical path
    assert frob8["cost"]["critical_path"] == crit

    # ISSUE 13: the fused straight-line lowering's predicted runtime
    # (real per-level widths + per-level/per-chunk glue, no register-file
    # traffic) must beat the 280 µs/step interpreter model on the
    # pipelined frobenius fold-8 shape — the static-model statement of
    # the measured fused win
    assert frob8["cost"]["predicted_fused_row_s"] > 0
    assert (frob8["cost"]["predicted_fused_row_s"]
            < frob8["cost"]["predicted_row_s"])
    assert frob8["cost"]["fused_chunks"] > 0


def test_program_stats_cross_checks_the_ir_analysis():
    prog = _chained(24)
    r = vm_analysis.analyze_prog(prog, name="x", **SHAPE)
    assembled = prog.assemble(**SHAPE)
    ps = vm_analysis.program_stats(assembled)
    # the instruction-tensor recount must agree with the IR analysis
    assert ps["sched_steps"] == r["pressure"]["sched_steps"]
    assert ps["mul_ops"] == r["cost"]["mul_ops"]
    assert ps["lin_ops"] == r["cost"]["add_ops"] + r["cost"]["sub_ops"]
    assert ps["max_reg_occupancy"] <= ps["alloc_regs"]


# ---------------------------------------------------------------------------
# structural canonicalization (ISSUE 15)
# ---------------------------------------------------------------------------


def test_detect_period_and_window_selection():
    a, b, c = (7, 0, 0), (2, 1, 0), (1, 0, 1)
    assert vm_analysis.detect_period([a, b] * 40) == 2
    assert vm_analysis.detect_period([a, b, c] * 30 + [a]) == 3
    # sparse interruptions (the set-bit rows of a real ladder) survive
    # the match-fraction threshold
    sigs = ([a, b] * 20 + [c] + [a, b] * 20)
    assert vm_analysis.detect_period(sigs) == 2
    # aperiodic: no period
    import random as _r

    rng = _r.Random(5)
    rand = [(rng.randrange(50), rng.randrange(9), rng.randrange(9))
            for _ in range(200)]
    assert vm_analysis.detect_period(rand) is None
    # window selection: largest period multiple <= target, 2x-clamped
    assert vm_analysis.select_window(None, 24) == 24
    assert vm_analysis.select_window(14, 24) == 14
    assert vm_analysis.select_window(6, 24) == 24
    assert vm_analysis.select_window(28, 24) == 28  # period > target: itself
    assert vm_analysis.select_window(96, 24) == 24  # > 2x target: clamped


def _ladder_prog(iters=12):
    prog = vm.Prog()
    acc = prog.inp("acc")
    other = prog.inp("other")
    for i in range(iters):
        k = prog.const(1000003 * (i + 1))
        acc = acc * acc + other * k
        other = other * other - acc
    prog.out(acc, "acc")
    prog.out(other, "other")
    return prog


def test_structural_plan_dedups_ladder_chunks():
    """A repeated loop body canonicalizes to FEWER distinct structures
    than chunks — constants become per-instance operand slots, carry
    wiring becomes per-instance gather tables — and every instance's
    tables are self-consistent (index ranges, struct refs)."""
    prog = _ladder_prog()
    assembled = prog.assemble(w_mul=64, w_lin=64, pad_steps_to=256,
                              pad_regs_to=64)
    plan = vm_analysis.lowering_plan(assembled, chunk_steps=3)
    sp = vm_analysis.structural_plan(plan)
    inst = sp["instances"]
    assert len(sp["structs"]) < len(inst)
    for c in inst:
        body = sp["structs"][c["struct"]]
        assert len(c["in_idx"]) == body["n_in"]
        assert len(c["consts"]) == body["n_const"]
        assert len(c["boundary_idx"]) == c["m_out"]
        assert all(0 <= i < c["m_in"] for i in c["in_idx"])
        n_out = len(body["out"])
        assert all(0 <= i < n_out + c["m_in"]
                   for i in c["boundary_idx"])
    # dedup=False salts every key: the per-chunk baseline
    sp0 = vm_analysis.structural_plan(plan, dedup=False)
    assert len(sp0["structs"]) == len(sp0["instances"])
    # the canonical bodies are instance-value-free: runs exist for the
    # super-op folding to exploit
    runs = vm_analysis.superop_runs(inst, min_run=2)
    assert runs and max(r for _, r in runs) >= 4


def test_superop_runs_require_shape_invariant_carry():
    inst = [
        {"struct": "A", "m_in": 4, "m_out": 4},
        {"struct": "A", "m_in": 4, "m_out": 4},
        {"struct": "A", "m_in": 4, "m_out": 4},
        {"struct": "B", "m_in": 4, "m_out": 4},
        {"struct": "A", "m_in": 4, "m_out": 6},  # width change: no run
        {"struct": "A", "m_in": 6, "m_out": 6},
        {"struct": "A", "m_in": 6, "m_out": 6},
    ]
    assert vm_analysis.superop_runs(inst, min_run=3) == [(0, 3)]
    assert vm_analysis.superop_runs(inst, min_run=2) == [(0, 3), (5, 2)]
    assert vm_analysis.superop_runs([], min_run=2) == []


def test_structural_stats_report_shape():
    st = vm_analysis.structural_stats(
        _ladder_prog().assemble(w_mul=64, w_lin=64, pad_steps_to=256,
                                pad_regs_to=64), chunk_target=4)
    assert st["chunks"] >= st["distinct_structs"] >= 1
    assert st["dedup_ratio"] >= 1.0
    assert st["predicted_cold_s"] <= st["predicted_cold_nodedup_s"]
    # the report + baseline entry carry the structural shape
    r = vm_analysis.analyze_prog(_ladder_prog(), name="ladder")
    assert r["structure"]["distinct_structs"] >= 1
    entry = vm_analysis.baseline_entry(r)
    assert entry["distinct_structs"] == r["structure"]["distinct_structs"]
    assert entry["dedup_ratio"] == r["structure"]["dedup_ratio"]


# ---------------------------------------------------------------------------
# baseline gate
# ---------------------------------------------------------------------------


def test_gate_detects_pressure_and_depth_regressions():
    r = vm_analysis.analyze_prog(_tiny_prog(), name="p")
    entry = vm_analysis.baseline_entry(r)
    assert vm_analysis.gate([r], {"p": entry}) == []
    # a regression: baseline pinned tighter than the current program
    tight = dict(entry, max_live=max(1, entry["max_live"] // 2))
    assert any("max_live regressed" in f
               for f in vm_analysis.gate([r], {"p": tight}))
    tight = dict(entry, critical_path=max(1, entry["critical_path"] - 2))
    assert any("critical_path regressed" in f
               for f in vm_analysis.gate([r], {"p": tight}))
    # unknown program: must demand a baseline entry
    assert any("not in VMLINT_BASELINE" in f
               for f in vm_analysis.gate([r], {}))


def test_tier1_registry_is_sound_and_matches_committed_baseline():
    """The acceptance gate, tier-1 slice: vmlint independently re-derives
    and confirms bounds for the small-shape registry programs, and their
    pressure/depth scalars match the committed VMLINT_BASELINE.json."""
    reports = vm_analysis.run_registry(tier1_only=True, export=False)
    assert len(reports) >= 9
    for r in reports:
        assert r["errors"] == 0, (r["name"], r["findings"])
        assert r["bounds"]["checked"] > 0
        assert r["pressure"]["hazard"] is False
    failures = vm_analysis.gate(reports, vm_analysis.load_baseline())
    assert failures == []


@pytest.mark.slow
def test_full_registry_is_sound_and_matches_committed_baseline():
    """Full production shapes (chunk-16 rlc_combine, fold-8 hard part,
    production codec folds): ~20 s of host assembly + analysis."""
    reports = vm_analysis.run_registry(tier1_only=False, export=False)
    assert len(reports) >= 18
    for r in reports:
        assert r["errors"] == 0, (r["name"], r["findings"])
    assert vm_analysis.gate(reports, vm_analysis.load_baseline()) == []


# ---------------------------------------------------------------------------
# observability export
# ---------------------------------------------------------------------------


def test_analysis_exports_to_obs_registry_and_gauges():
    from consensus_specs_tpu.obs import programs as obs_programs
    from consensus_specs_tpu.ops import profiling

    r = vm_analysis.analyze_prog(_tiny_prog(), name="tiny[k=0,fold=1]")
    vm_analysis.export_to_obs([r])
    snap = obs_programs.registry_snapshot()["programs"]
    analysis = snap["tiny[k=0,fold=1]"]["analysis"]
    assert analysis["max_live"] == r["pressure"]["max_live"]
    assert analysis["classification"] == r["cost"]["classification"]
    gauges = profiling.summary()
    assert gauges["vm.analysis_programs"]["gauge"] == 1
    assert gauges["vm.analysis_errors"]["gauge"] == 0
    # analyze-then-execute ordering: a later note_assembly for the same
    # key must MERGE, keeping the analysis sub-dict alongside the
    # measured assembly stats
    obs_programs.note_assembly(
        "tiny[k=0,fold=1]", n_steps=8, n_regs=16, seconds=0.01,
        disk_cache_hit=False)
    merged = obs_programs.registry_snapshot()["programs"]["tiny[k=0,fold=1]"]
    assert merged["steps"] == 8
    assert merged["analysis"]["max_live"] == r["pressure"]["max_live"]
