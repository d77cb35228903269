"""Mesh-sharded verify plane (ISSUE 9).

Covers the whole rung: the CONSENSUS_SPECS_TPU_MESH provider
(utils/jax_env.get_mesh), the _FoldLayout mesh fold-capping / row-padding
rules (previously untested — the ceil(n/devices) clamp and the
pad-rows-to-device-count floor), the cross-replica Fq12 all-gather
reduction (ops/mesh_rlc.py) against the exact-int oracle, end-to-end
verdict identity of ``batch_verify_rlc(items, mesh=...)`` vs the
single-device path over valid/invalid/malformed/infinity inputs
(bisection through a failed SHARDED combine included), and the serve
plane's mesh degradation rung (mesh failure -> single-device RLC with a
``degraded_mesh_to_single`` flight event + the serve.mesh_fallbacks
gauge).

Tier-1 keeps to the 4-device mixed batch (the multi-chunk cross-replica
case) plus jax-free layout/serve tests; the wider device counts
(2 and 8, wide batches) ride --run-slow with the other device-deep
suites.
"""
import random
import types

import numpy as np
import pytest

from consensus_specs_tpu.utils.jax_env import force_cpu, get_mesh

force_cpu(8)

import jax  # noqa: E402
from jax.sharding import Mesh  # noqa: E402

from consensus_specs_tpu.ops import bls_backend as bb  # noqa: E402
from consensus_specs_tpu.ops import fq, mesh_rlc  # noqa: E402
from consensus_specs_tpu.utils import bls  # noqa: E402
from consensus_specs_tpu.utils import bls12_381 as O  # noqa: E402
from consensus_specs_tpu.utils.bls12_381 import P, R  # noqa: E402


def _mesh(n: int) -> Mesh:
    return Mesh(np.array(jax.devices("cpu")[:n]), ("batch",))


def _committee(tag: int, k: int = 2, good: bool = True):
    sks = [1000 * tag + j + 1 for j in range(k)]
    pks = [bls.SkToPk(sk) for sk in sks]
    msg = (b"msh%03d" % tag) + b"\x00" * 26
    sig = bls.Sign(sum(sks) % R, msg)
    if not good:
        msg = b"\xff" + msg[1:]
    return ("fast_aggregate", pks, msg, sig)


# -- mesh provider (utils/jax_env.get_mesh) ---------------------------------


def test_get_mesh_resolution(monkeypatch):
    env = "CONSENSUS_SPECS_TPU_MESH"
    monkeypatch.delenv(env, raising=False)
    assert get_mesh() is None  # unset == off
    for off in ("off", "0", "1", "", "none"):
        monkeypatch.setenv(env, off)
        assert get_mesh() is None, off
    monkeypatch.setenv(env, "4")
    m = get_mesh()
    assert m is not None and m.shape["batch"] == 4
    assert m.axis_names == ("batch",)
    monkeypatch.setenv(env, "auto")
    assert get_mesh().shape["batch"] == 8  # conftest's 8 virtual devices
    # non-power-of-two clamps to the floor (butterfly + row padding need
    # a power-of-two axis); over-asking clamps to what exists
    monkeypatch.setenv(env, "6")
    assert get_mesh().shape["batch"] == 4
    monkeypatch.setenv(env, "16")
    assert get_mesh().shape["batch"] == 8
    # malformed specs degrade to the single-device path, never raise
    monkeypatch.setenv(env, "garbage")
    assert get_mesh() is None
    monkeypatch.setenv(env, "-3")
    assert get_mesh() is None


def test_maybe_mesh_off_is_cheap_and_none(monkeypatch):
    from consensus_specs_tpu.utils import jax_env

    monkeypatch.delenv("CONSENSUS_SPECS_TPU_MESH", raising=False)
    assert jax_env.maybe_mesh() is None
    monkeypatch.setenv("CONSENSUS_SPECS_TPU_MESH", "2")
    assert jax_env.maybe_mesh().shape["batch"] == 2


# -- _FoldLayout mesh fold-capping / row-padding (satellite 1) --------------


def _fake_mesh(n_dev: int):
    return types.SimpleNamespace(shape={"batch": n_dev})


@pytest.fixture()
def stub_program(monkeypatch):
    """_FoldLayout resolves a real assembled program; the layout rules
    under test are pure integer math, so stub the (expensive) resolution."""
    def fake_program(kind, k=0, fold=None):
        if fold is None:
            fold = bb._fold_for(kind, k)
        return f"prog[{kind},k={k},f={fold}]", fold

    monkeypatch.setattr(bb, "_program", fake_program)


@pytest.mark.parametrize("n_dev", [1, 2, 4, 8])
@pytest.mark.parametrize("n_items", [1, 3, 8, 17])
@pytest.mark.parametrize("kind,k", [("hard_part", 0), ("rlc_combine", 2),
                                    ("miller_product", 16)])
def test_fold_layout_mesh_invariants(stub_program, n_dev, n_items, kind, k):
    mesh = _fake_mesh(n_dev) if n_dev > 1 else None
    lay = bb._FoldLayout(kind, k, n_items, mesh)
    # every item fits, and filler never exceeds one row's worth past the
    # device-count floor
    assert lay.nb == lay.rows * lay.fold
    assert lay.nb >= n_items
    # rows pad to the device count (each device gets >= 1 row) and stay a
    # power of two (shard divisibility)
    if mesh is not None:
        assert lay.rows % n_dev == 0
        assert lay.rows >= n_dev
    assert lay.rows & (lay.rows - 1) == 0
    # the mesh fold clamp: folding past ceil(n/devices) would only run a
    # bigger program on filler — fold never exceeds it
    if mesh is not None:
        assert lay.fold <= bb._pow2(max(1, -(-n_items // n_dev)))
    assert lay.fold <= bb._fold_for(kind, k, n_items)
    # item -> (row, prefix) stays within the padded layout
    for i in range(n_items):
        r, ns = lay.split(i)
        assert 0 <= r < lay.rows
        assert ns == ("" if lay.fold == 1 else f"i{i % lay.fold}.")


def test_fold_layout_pinned_cases(stub_program):
    # 17 hard-part items on 8 devices: fold clamps 16 -> 4, rows pad to 8
    lay = bb._FoldLayout("hard_part", 0, 17, _fake_mesh(8))
    assert (lay.fold, lay.rows, lay.nb) == (4, 8, 32)
    # 1 item on 8 devices: a single fold-1 row padded out to the mesh
    lay = bb._FoldLayout("hard_part", 0, 1, _fake_mesh(8))
    assert (lay.fold, lay.rows) == (1, 8)
    # unsharded 17 items keep the full fold-16 table
    lay = bb._FoldLayout("hard_part", 0, 17, None)
    assert (lay.fold, lay.rows) == (16, 2)


def test_rlc_chunk_shards_the_width():
    # unsharded: the lane-saturating chunk
    assert bb._rlc_chunk(16, None) == 16
    assert bb._rlc_chunk(3, None) == 4
    # mesh: chunk shrinks until every device holds >= 1 chunk row
    assert bb._rlc_chunk(16, _fake_mesh(4)) == 4
    assert bb._rlc_chunk(16, _fake_mesh(8)) == 2
    assert bb._rlc_chunk(3, _fake_mesh(8)) == 1
    assert bb._rlc_chunk(64, _fake_mesh(4)) == 16  # capped at chunk max
    assert bb._rlc_chunk(2, _fake_mesh(2)) == 1


# -- cross-replica Fq12 reduction (ops/mesh_rlc.py) ------------------------


def _rand_f(rng: random.Random) -> O.Fq12:
    return O.Fq12(
        O.Fq6(*[O.Fq2(rng.randrange(P), rng.randrange(P))
                for _ in range(3)]),
        O.Fq6(*[O.Fq2(rng.randrange(P), rng.randrange(P))
                for _ in range(3)]),
    )


@pytest.mark.slow  # tens of seconds of fold compiles (ISSUE 11 tier-1 audit)
def test_mesh_fq12_product_matches_oracle():
    """All-gather + Fq12 fold == exact-int oracle product, at
    sub-device-count (identity padding) and multi-row widths."""
    rng = random.Random(17)
    mesh = _mesh(4)
    for n in (1, 3, 8):
        fs_o = [_rand_f(rng) for _ in range(n)]
        fs = np.stack([
            np.stack([fq.to_mont_int(c)
                      for c in bb._oracle_to_flat_ints(f)])
            for f in fs_o
        ])
        got = mesh_rlc.mesh_fq12_product(fs, mesh)
        got_ints = [fq.from_mont_limbs(got[j]) for j in range(12)]
        want = fs_o[0]
        for f in fs_o[1:]:
            want = want * f
        assert got_ints == bb._oracle_to_flat_ints(want), n


def test_mesh_fq12_identity_padding():
    one = mesh_rlc.fq12_identity()
    assert fq.from_mont_limbs(one[0]) == 1
    assert all(fq.from_mont_limbs(one[j]) == 0 for j in range(1, 12))
    # an all-identity batch reduces to the identity
    got = mesh_rlc.mesh_fq12_product(mesh_rlc.fq12_identity((3,)), _mesh(4))
    assert [fq.from_mont_limbs(got[j]) for j in range(12)] == \
        [1] + [0] * 11


# -- end-to-end verdict identity under the mesh -----------------------------


def _mixed_items():
    """Every input class: valid, corrupted message, undecodable signature,
    infinity signature, infinity pubkey (the test_rlc mixed batch)."""
    return [
        _committee(1, k=2, good=True),
        _committee(2, k=1, good=False),
        ("fast_aggregate", [bls.SkToPk(7)], b"m" * 32,
         b"\xa0" + b"\x01" * 95),
        ("fast_aggregate", [bls.SkToPk(8)], b"n" * 32,
         b"\xc0" + b"\x00" * 95),
        ("fast_aggregate", [b"\xc0" + b"\x00" * 47],
         b"p" * 32, bls.Sign(9, b"p" * 32)),
    ]


def test_mesh_verdict_identity_mixed_batch(monkeypatch):
    """batch_verify_rlc over a 4-device mesh: bit-identical to the
    single-device path and the pinned host-oracle pattern, with the
    corrupted item bisecting through the failed SHARDED combine (chunk 1
    per device -> the cross-replica reduction actually reduces)."""
    monkeypatch.setenv("CONSENSUS_SPECS_TPU_RLC_CHUNK", "2")
    items = _mixed_items()
    single = bb.batch_verify_rlc(items, rng=random.Random(0xA5))
    before = dict(bb.RLC_STATS)
    got = bb.batch_verify_rlc(items, mesh=_mesh(4), rng=random.Random(0xA5))
    d = {k: bb.RLC_STATS[k] - before[k] for k in bb.RLC_STATS}
    assert np.array_equal(got, single)
    assert list(got) == [True, False, False, False, False]
    # same combine/bisection trajectory as the single-device run with the
    # same injected rng: malformed/infinity items never reach the combine
    assert d["items"] == 2
    assert d["bisections"] >= 1  # the failed sharded combine split
    assert d["final_exps"] == 3  # root combine + 2 singleton finalizations
    # the chunk products were folded across the mesh, not on the host
    assert d["mesh_reductions"] >= 1


def test_mesh_reduction_failure_is_not_hidden(monkeypatch):
    """A failing cross-replica reduction propagates out of the combine
    (the service's mesh rung counts it) instead of a silent host
    multiply that would leave the verdicts right and the mesh path dead."""
    monkeypatch.setenv("CONSENSUS_SPECS_TPU_RLC_CHUNK", "2")

    def broken(products, mesh):
        raise RuntimeError("all_gather refused")

    monkeypatch.setattr(mesh_rlc, "mesh_fq12_product", broken)
    before = bb.RLC_STATS["mesh_reductions"]
    with pytest.raises(RuntimeError, match="all_gather refused"):
        bb.batch_verify_rlc(_mixed_items(), mesh=_mesh(4),
                            rng=random.Random(0xA5))
    assert bb.RLC_STATS["mesh_reductions"] == before


@pytest.mark.slow
@pytest.mark.parametrize("n_dev", [2, 8])
def test_mesh_verdict_identity_wide(n_dev):
    """Wide batches at the remaining device counts {2, 8}: verdicts
    bit-identical to the single-device path with two corrupted items
    localized by bisection through sharded combines."""
    n, bad = 17, {5, 11}
    items = [_committee(100 + i, k=1, good=(i not in bad))
             for i in range(n)]
    single = bb.batch_verify_rlc(items, rng=random.Random(n_dev))
    got = bb.batch_verify_rlc(items, mesh=_mesh(n_dev),
                              rng=random.Random(n_dev))
    assert np.array_equal(got, single)
    want = np.array([i not in bad for i in range(n)])
    assert np.array_equal(got, want)


def test_mesh_filler_rows_never_flip_verdicts():
    """The per-item (non-RLC) path with rows padded to the device count:
    3 items on 4 devices run one filler row, whose generator-point lanes
    must never leak into real verdicts."""
    items = [_committee(200, k=1), _committee(201, k=1, good=False),
             _committee(202, k=1)]
    sharded = bb.batch_fast_aggregate_verify(
        [it[1] for it in items], [it[2] for it in items],
        [it[3] for it in items], mesh=_mesh(4),
    )
    unsharded = bb.batch_fast_aggregate_verify(
        [it[1] for it in items], [it[2] for it in items],
        [it[3] for it in items],
    )
    assert np.array_equal(sharded, unsharded)
    assert list(sharded) == [True, False, True]


# -- serve-plane mesh rung (degradation ladder rung 0) ----------------------


class _MeshBackend:
    """Crypto-free backend recording whether calls arrived sharded; raises
    on the mesh path when ``explode`` — the serve rung's fault injection."""

    def __init__(self, explode: bool):
        self.explode = explode
        self.mesh_calls = 0
        self.plain_calls = 0

    def batch_verify_rlc(self, items, mesh=None):
        if mesh is not None:
            self.mesh_calls += 1
            if self.explode:
                raise RuntimeError("injected mesh failure")
        else:
            self.plain_calls += 1
        return [bytes(sig) != b"\xba" * 96 for (_k, _p, _m, sig) in items]


def _serve_items(n=3):
    out = []
    for i in range(n):
        sig = b"\xba" * 96 if i == n - 1 else bytes([i + 1]) * 96
        out.append(("fast_aggregate", [b"\x01" * 48],
                    b"%02d" % i + b"m" * 30, sig))
    return out


def test_serve_mesh_fallback_rung():
    """A mesh failure costs one fallback (serve.mesh_fallbacks + the
    degraded_mesh_to_single flight event), never the flush: the
    single-device RLC answers every request correctly."""
    import os

    from consensus_specs_tpu.obs import flight
    from consensus_specs_tpu.serve.service import VerificationService

    os.environ["CONSENSUS_SPECS_TPU_FLIGHT"] = "1"
    flight.reset_global()
    try:
        be = _MeshBackend(explode=True)
        svc = VerificationService(backend=be, mesh=_fake_mesh(2),
                                  max_wait_ms=200.0)
        try:
            futures = [svc.submit(*it) for it in _serve_items()]
            got = [f.result(timeout=30) for f in futures]
        finally:
            svc.close(timeout=30)
        assert got == [True, True, False]
        assert be.mesh_calls >= 1 and be.plain_calls >= 1
        snap = svc.metrics.snapshot()
        assert snap["mesh_devices"] == 2
        assert snap["mesh_fallbacks"] == be.mesh_calls
        kinds = [e["kind"] for e in flight.global_recorder().events()]
        assert "degraded_mesh_to_single" in kinds
    finally:
        del os.environ["CONSENSUS_SPECS_TPU_FLIGHT"]
        flight.reset_global()


def test_serve_mesh_success_no_fallback():
    from consensus_specs_tpu.serve.service import VerificationService

    be = _MeshBackend(explode=False)
    svc = VerificationService(backend=be, mesh=_fake_mesh(2),
                              max_wait_ms=200.0)
    try:
        futures = [svc.submit(*it) for it in _serve_items()]
        got = [f.result(timeout=30) for f in futures]
    finally:
        svc.close(timeout=30)
    assert got == [True, True, False]
    assert be.mesh_calls >= 1 and be.plain_calls == 0
    assert svc.metrics.mesh_fallbacks == 0
    assert svc.mesh_devices == 2


def test_serve_narrow_flush_stays_single_device():
    """A flush narrower than the mesh runs the single-device path — the
    rows would pad to the device count and run mostly filler, and the
    single-device executables are already warm. Not a fallback."""
    from consensus_specs_tpu.serve.service import VerificationService

    be = _MeshBackend(explode=True)  # would raise IF the mesh were used
    svc = VerificationService(backend=be, mesh=_fake_mesh(4),
                              max_wait_ms=200.0)
    try:
        futures = [svc.submit(*it) for it in _serve_items(2)]
        got = [f.result(timeout=30) for f in futures]
    finally:
        svc.close(timeout=30)
    assert got == [True, False]
    assert be.mesh_calls == 0 and be.plain_calls >= 1
    assert svc.metrics.mesh_fallbacks == 0
    assert svc.mesh_devices == 4


def test_serve_single_device_mesh_collapses_to_unsharded():
    """A 1-device mesh is the unsharded path — the service must not pay
    sharded dispatch for it."""
    from consensus_specs_tpu.serve.service import VerificationService

    be = _MeshBackend(explode=False)
    svc = VerificationService(backend=be, mesh=_fake_mesh(1),
                              max_wait_ms=5.0)
    try:
        fut = svc.submit(*_serve_items(2)[0])
        assert fut.result(timeout=30) is True
    finally:
        svc.close(timeout=30)
    assert svc.mesh_devices == 0
    assert be.mesh_calls == 0 and be.plain_calls >= 1


# -- fused lowering under the mesh batch axis (ISSUE 13) --------------------


def test_fused_execution_identity_under_mesh(monkeypatch):
    """The fused straight-line backend must ride `vm.execute(mesh=)`
    bit-identically to the interpreter: the chunk graphs are purely
    batch-elementwise, so GSPMD shards the carry over the mesh axes with
    zero collectives — the contract that lets PR 9's sharded Miller
    loops and PR 10's batcher take either backend unchanged."""
    import random

    from consensus_specs_tpu.ops import vm, vm_compile

    rng = random.Random(17)
    prog = vm.Prog()
    a, b, c = (prog.inp(n) for n in "abc")
    k = prog.const(12345)
    acc = (a * b + k) - c
    for _ in range(4):
        acc = acc * acc + (b - a)
    prog.out(acc, "r")
    assembled = prog.assemble(w_mul=64, w_lin=64, pad_steps_to=256,
                              pad_regs_to=64)
    ints = [{n: rng.randrange(O.P) for n in "abc"} for _ in range(4)]
    ins = {
        n: np.stack([fq.to_mont_int(row[n]) for row in ints])
        for n in "abc"
    }
    mesh = _mesh(2)
    vm_compile.reset_fused_state()
    monkeypatch.setenv("CONSENSUS_SPECS_TPU_VM_FUSED_CHUNK", "3")
    monkeypatch.setenv("CONSENSUS_SPECS_TPU_VM_EXEC", "interp")
    out_i = vm.execute(assembled, ins, batch_shape=(4,), mesh=mesh)
    monkeypatch.setenv("CONSENSUS_SPECS_TPU_VM_EXEC", "fused")
    out_f = vm.execute(assembled, ins, batch_shape=(4,), mesh=mesh)
    out_u = vm.execute(assembled, ins, batch_shape=(4,))  # unsharded fused
    assert vm_compile._COUNTERS["fallbacks"] == 0
    for name in out_i:
        assert np.array_equal(np.asarray(out_i[name]),
                              np.asarray(out_f[name])), name
        assert np.array_equal(np.asarray(out_i[name]),
                              np.asarray(out_u[name])), name
    vm_compile.reset_fused_state()
