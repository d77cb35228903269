"""Field-ALU VM correctness: assembler, scheduler, and vmlib formulas vs the
pure-Python oracle. All programs here share one small shape bucket
(W=64, steps padded to 256, regs padded to 64) so the suite pays for at most
one XLA compile (persistent-cached on disk afterwards)."""
import random

import pytest

jax = pytest.importorskip("jax")

from consensus_specs_tpu.ops import fq, vm, vmlib  # noqa: E402
from consensus_specs_tpu.utils import bls12_381 as O  # noqa: E402

rng = random.Random(99)

BUCKET = dict(w_mul=64, w_lin=64, pad_steps_to=256, pad_regs_to=64)


def run(prog, ins_ints, batch_shape=()):
    pr = prog.assemble(**BUCKET)
    ins = {k: fq.to_mont_int(v) for k, v in ins_ints.items()}
    out = vm.execute(pr, ins, batch_shape=batch_shape)
    return {k: fq.from_mont_limbs(v) for k, v in out.items()}


def test_alu_chain():
    prog = vm.Prog()
    a, b, c, d = (prog.inp(n) for n in "abcd")
    r = (a * b + c - d) * (a + a) - b
    prog.out(r, "r")
    av, bv, cv, dv = (rng.randrange(O.P) for _ in range(4))
    got = run(prog, dict(a=av, b=bv, c=cv, d=dv))["r"]
    assert got == (((av * bv + cv - dv) * 2 * av) - bv) % O.P


def test_execute_takes_a_prebuilt_input_stack():
    """A caller-built input stack runs like the named inputs it stacks;
    a wrong shape or a non-canonical limb is refused."""
    import numpy as np

    prog = vm.Prog()
    a, b = prog.inp("a"), prog.inp("b")
    prog.out(a * b - a, "r")
    pr = prog.assemble(**BUCKET)
    vals = [(rng.randrange(O.P), rng.randrange(O.P)) for _ in range(2)]
    ins = {n: np.stack([fq.to_mont_int(v[i]) for v in vals])
           for i, n in enumerate("ab")}
    stacked = pr.stack_inputs(ins, (2,))
    by_name = vm.execute(pr, ins, batch_shape=(2,))["r"]
    by_stack = vm.execute(pr, stacked, batch_shape=(2,))["r"]
    assert np.array_equal(by_name, by_stack)
    assert [fq.from_mont_limbs(r) for r in by_stack] == [
        (x * y - x) % O.P for x, y in vals]
    with pytest.raises(ValueError, match="program wants"):
        vm.execute(pr, stacked, batch_shape=(1, 2))
    bad = stacked.copy()
    bad[1, 0, 3] = 1 << fq.LIMB_BITS
    with pytest.raises(ValueError, match="canonical"):
        vm.execute(pr, bad, batch_shape=(2,))


def test_auto_compress_long_chains():
    # force magnitudes past the lazy-reduction bounds: deep add/sub chains
    prog = vm.Prog()
    a = prog.inp("a")
    b = prog.inp("b")
    acc = a
    for _ in range(40):
        acc = acc + acc  # doubles the bound each time; must auto-compress
    acc = acc - b
    acc = acc * acc
    prog.out(acc, "r")
    av, bv = rng.randrange(O.P), rng.randrange(O.P)
    exp = pow((av * (1 << 40) - bv) % O.P, 2, O.P)
    assert run(prog, dict(a=av, b=bv))["r"] == exp


def test_f2_mul_square_vs_oracle():
    prog = vm.Prog()
    x = vmlib.f2_inputs(prog, "x")
    y = vmlib.f2_inputs(prog, "y")
    m = x * y
    s = x.square()
    xi = x.mul_xi()
    prog.out(m.c0, "m0")
    prog.out(m.c1, "m1")
    prog.out(s.c0, "s0")
    prog.out(s.c1, "s1")
    prog.out(xi.c0, "xi0")
    prog.out(xi.c1, "xi1")
    xv = O.Fq2(rng.randrange(O.P), rng.randrange(O.P))
    yv = O.Fq2(rng.randrange(O.P), rng.randrange(O.P))
    got = run(
        prog,
        {"x.0": xv.c0, "x.1": xv.c1, "y.0": yv.c0, "y.1": yv.c1},
    )
    mv = xv * yv
    sv = xv * xv
    xiv = xv * O.Fq2(1, 1)
    assert (got["m0"], got["m1"]) == (mv.c0, mv.c1)
    assert (got["s0"], got["s1"]) == (sv.c0, sv.c1)
    assert (got["xi0"], got["xi1"]) == (xiv.c0, xiv.c1)


def _g1_prog_add():
    prog = vm.Prog()
    p1 = tuple(prog.inp(f"p.{c}") for c in "xyz")
    p2 = tuple(prog.inp(f"q.{c}") for c in "xyz")
    x3, y3, z3 = vmlib.g1_complete_add(prog, p1, p2)
    prog.out(x3, "x")
    prog.out(y3, "y")
    prog.out(z3, "z")
    return prog


def _to_affine_ints(x, y, z):
    if z == 0:
        return None
    zi = pow(z, O.P - 2, O.P)
    return (x * zi) % O.P, (y * zi) % O.P


def _oracle_affine(pt):
    if pt is None:
        return None
    aff = O.ec_to_affine(pt)
    return aff[0].n, aff[1].n


@pytest.mark.parametrize(
    "k1,k2",
    [(5, 7), (3, 3), (11, 0), (0, 13), (9, -9), (0, 0)],
    ids=["generic", "double", "q-inf", "p-inf", "negatives", "both-inf"],
)
def test_g1_complete_add_vs_oracle(k1, k2):
    """RCB complete addition handles generic/double/infinity/inverse cases."""
    prog = _g1_prog_add()

    def proj(k):
        if k == 0:
            return {"x": 0, "y": 1, "z": 0}
        aff = O.ec_to_affine(O.ec_mul(O.G1_GEN, k % O.R))
        return {"x": aff[0].n, "y": aff[1].n, "z": 1}

    a, b = proj(k1), proj(k2)
    ins = {f"p.{c}": a[c] for c in "xyz"}
    ins.update({f"q.{c}": b[c] for c in "xyz"})
    got = run(prog, ins)
    got_aff = _to_affine_ints(got["x"], got["y"], got["z"])
    exp_pt = O.ec_mul(O.G1_GEN, (k1 + k2) % O.R) if (k1 + k2) % O.R else None
    assert got_aff == _oracle_affine(exp_pt)


def test_g1_tree_sum_vs_oracle():
    ks = [rng.randrange(1, O.R) for _ in range(5)]
    prog = vm.Prog()
    pts = []
    for j in range(5):
        pts.append(tuple(prog.inp(f"p{j}.{c}") for c in "xyz"))
    x3, y3, z3 = vmlib.g1_tree_sum(prog, pts)
    prog.out(x3, "x")
    prog.out(y3, "y")
    prog.out(z3, "z")
    ins = {}
    for j, k in enumerate(ks):
        aff = O.ec_to_affine(O.ec_mul(O.G1_GEN, k))
        ins[f"p{j}.x"] = aff[0].n
        ins[f"p{j}.y"] = aff[1].n
        ins[f"p{j}.z"] = 1
    got = run(prog, ins)
    got_aff = _to_affine_ints(got["x"], got["y"], got["z"])
    assert got_aff == _oracle_affine(O.ec_mul(O.G1_GEN, sum(ks) % O.R))


def _rand_fq12():
    def r2():
        return O.Fq2(rng.randrange(O.P), rng.randrange(O.P))

    def r6():
        return O.Fq6(r2(), r2(), r2())

    return O.Fq12(r6(), r6())


def _f12_prog(fn, n_out=12):
    prog = vm.Prog()
    a = [prog.inp(f"a.{i}") for i in range(12)]
    r = fn(prog, a)
    for i in range(12):
        prog.out(r[i], f"r.{i}")
    return prog


def _f12_run(prog, x: O.Fq12):
    from consensus_specs_tpu.ops.bls_backend import (
        _flat_ints_to_oracle,
        _oracle_to_flat_ints,
    )

    flat = _oracle_to_flat_ints(x)
    got = run(prog, {f"a.{i}": flat[i] for i in range(12)})
    return _flat_ints_to_oracle([got[f"r.{i}"] for i in range(12)])


def test_f12_square_and_frobenius_vs_oracle():
    x = _rand_fq12()
    assert _f12_run(_f12_prog(vmlib.f12_square), x) == x * x
    assert _f12_run(
        _f12_prog(lambda p, a: vmlib.f12_frobenius(p, a, 1)), x
    ) == x.frobenius()
    assert _f12_run(
        _f12_prog(lambda p, a: vmlib.f12_frobenius(p, a, 2)), x
    ) == x.frobenius().frobenius()
    assert _f12_run(_f12_prog(vmlib.f12_conj), x) == x.conjugate()


def test_f12_cyclotomic_square_vs_oracle():
    # land a random element in the cyclotomic subgroup via the easy part
    f = _rand_fq12()
    g = f.conjugate() * f.inverse()
    g = g.frobenius().frobenius() * g
    got = _f12_run(_f12_prog(vmlib.f12_cyclotomic_square), g)
    assert got == g * g


def _rand_unitary():
    f = _rand_fq12()
    g = f.conjugate() * f.inverse()
    return g.frobenius().frobenius() * g


def test_f12_cyclotomic_square_comps_vs_oracle():
    """The depth-lean component-form squaring (ISSUE 10): same map as the
    flat Granger-Scott squaring, ~5 ALU levels instead of ~11."""
    def fn(p, a):
        return vmlib.f12_from_comps(
            vmlib.f12_cyclotomic_square_comps(p, vmlib.f12_to_comps(a)))

    g = _rand_unitary()
    assert _f12_run(_f12_prog(fn), g) == g * g


def test_cyc_pow_spine_and_window_vs_oracle():
    """The two new static-exponent ladders on a unitary base: the
    deferred-product spine (frobenius variant) and the sliding-window
    ladder (windowed variant), each vs exact-int pow."""
    e = 0xD3A1  # several set bits incl. adjacent ones
    g = _rand_unitary()

    def spine(p, a):
        return vmlib._cyc_pow_spine(p, vmlib.f12_to_comps(a), e)

    def window(p, a):
        return vmlib._cyc_pow_window(p, a, e)

    exp = g
    for b in bin(e)[3:]:
        exp = exp * exp
        if b == "1":
            exp = exp * g
    assert _f12_run(_f12_prog(spine), g) == exp
    assert _f12_run(_f12_prog(window), g) == exp


def _oracle_hard_part(g):
    # the one shared exact-int HHT chain (bls_backend owns the formula)
    from consensus_specs_tpu.ops.bls_backend import hard_part_res_oracle

    return hard_part_res_oracle(g)


@pytest.mark.parametrize("builder", [
    vmlib.build_hard_part_windowed,
    vmlib.build_hard_part_frobenius,
], ids=["windowed", "frobenius"])
def test_hard_part_variants_vs_oracle(builder):
    """The ISSUE 10 width-for-depth hard parts are BIT-identical to the
    exact-int HHT on random unitary inputs (production assembly shape, so
    the executable is the one bls_backend routes to)."""
    from consensus_specs_tpu.ops import bls_backend as bb

    prog = builder(1)
    pr = prog.assemble(w_mul=bb.W_MUL, w_lin=bb.W_LIN,
                       pad_steps_to=bb.PAD_STEPS, pad_regs_to=bb._pow2(64))
    from consensus_specs_tpu.ops.bls_backend import (
        _flat_ints_to_oracle,
        _oracle_to_flat_ints,
    )

    g = _rand_unitary()
    flat = _oracle_to_flat_ints(g)
    out = vm.execute(pr, {f"g.{i}": fq.to_mont_int(flat[i]) for i in range(12)})
    got = _flat_ints_to_oracle(
        [fq.from_mont_limbs(out[f"res.{i}"]) for i in range(12)]
    )
    assert got == _oracle_hard_part(g)


# ---------------------------------------------------------------------------
# the assembler's own bound machinery
# ---------------------------------------------------------------------------


def test_inp_loose_bound_accepts_another_programs_output():
    """The RLC feed path: program 1's out() is compressed but LOOSE
    (< 2^382, not < p); program 2 declares that magnitude via inp(bound=)
    and must still compute correctly when the raw limbs are fed straight
    back in with no host canonicalization."""
    p1 = vm.Prog()
    a, b = p1.inp("a"), p1.inp("b")
    p1.out(a * b, "r")
    av, bv = rng.randrange(O.P), rng.randrange(O.P)
    pr1 = p1.assemble(**BUCKET)
    raw = vm.execute(
        pr1, {"a": fq.to_mont_int(av), "b": fq.to_mont_int(bv)}
    )["r"]  # loose Montgomery limbs, NOT reduced mod p

    p2 = vm.Prog()
    x = p2.inp("x", bound=vmlib.RLC_F_BOUND)
    y = p2.inp("y")
    assert p2.ops[x.idx].bound == vmlib.RLC_F_BOUND  # declaration recorded
    p2.out(x * y + x, "r")
    yv = rng.randrange(O.P)
    got = vm.execute(
        p2.assemble(**BUCKET), {"x": raw, "y": fq.to_mont_int(yv)}
    )["r"]
    expect = (av * bv * yv + av * bv) % O.P
    assert fq.from_mont_limbs(got) == expect


def test_b_cap_assertion_fires_on_overdeclared_input():
    """_B_CAP guards declared input bounds too: a declaration at the
    15-limb capacity can never be carry-safe."""
    prog = vm.Prog()
    with pytest.raises(AssertionError, match="missing compress"):
        prog.inp("a", bound=1 << 420)


def test_sub_auto_compresses_loose_operands():
    """Loose-declared operands past the borrowless-subtract preconditions
    (subtrahend <= MP, minuend headroom) must be auto-compressed, keeping
    the result exact."""
    prog = vm.Prog()
    a = prog.inp("a", bound=1 << 412)
    b = prog.inp("b", bound=1 << 412)  # far above the MP subtrahend cap
    prog.out(a - b, "r")
    assert all(op.bound < (1 << 420) for op in prog.ops)
    av, bv = rng.randrange(O.P), rng.randrange(O.P)
    got = run(prog, dict(a=av, b=bv))["r"]
    assert got == (av - bv) % O.P


def test_cse_key_symmetry_for_commutative_ops():
    prog = vm.Prog()
    a, b = prog.inp("a"), prog.inp("b")
    # commutative: both operand orders must hit one op
    assert (a * b).idx == (b * a).idx
    assert (a + b).idx == (b + a).idx
    # and repeats add no ops at all
    n = len(prog.ops)
    assert (a * b).idx == (b * a).idx
    assert len(prog.ops) == n
    # subtraction is NOT commutative: orders must stay distinct
    assert (a - b).idx != (b - a).idx
