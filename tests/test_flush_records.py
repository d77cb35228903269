"""Program spans and flush records (obs/tracing.py): the ``rlc`` record
each ``batch_verify_rlc`` call appends, the ``serve`` record each settled
service flush appends, the bounded ring, the ``vm.<kind>`` span's
``first`` flag, the per-kind XLA module names, and the spans' place on
the profiler's clock (``jax.profiler`` on the CPU)."""
import glob
import os
import random
import types

import numpy as np
import pytest

from consensus_specs_tpu.obs import tracing
from consensus_specs_tpu.ops import bls_backend as bb
from consensus_specs_tpu.ops import fq, vm
from consensus_specs_tpu.serve import VerificationService
from consensus_specs_tpu.utils import bls
from consensus_specs_tpu.utils.bls12_381 import P, R

CHILDREN = ("rlc.prep", "rlc.miller", "rlc.combine", "rlc.final_exp")


@pytest.fixture(autouse=True)
def _fresh_ring():
    tracing.reset_flush_records()
    was = bls.bls_active
    bls.bls_active = True
    yield
    bls.bls_active = was
    tracing.reset_flush_records()


def _committee(tag: int, k: int = 2):
    sks = [1000 * tag + j + 1 for j in range(k)]
    pks = [bls.SkToPk(sk) for sk in sks]
    msg = (b"rec%03d" % tag) + b"\x00" * 26
    return ("fast_aggregate", pks, msg, bls.Sign(sum(sks) % R, msg))


def _rlc_records():
    return [r for r in tracing.flush_records() if r["kind"] == "rlc"]


def test_rlc_call_appends_one_record_with_nested_spans(monkeypatch):
    monkeypatch.setenv("CONSENSUS_SPECS_TPU_RLC_CHUNK", "2")
    items = [_committee(61), _committee(62)]
    before = dict(bb.RLC_STATS)
    assert list(bb.batch_verify_rlc(items, rng=random.Random(5))) == [True,
                                                                       True]
    recs = _rlc_records()
    assert len(recs) == 1
    rec = recs[0]
    assert rec["items"] == 2 and rec["seconds"] > 0
    spans = rec["spans"]
    for name in ("rlc.flush",) + CHILDREN:
        assert 0 < spans[name] <= rec["seconds"], name
    for name in CHILDREN:
        assert spans[name] <= spans["rlc.flush"], name
    assert (spans["rlc.easy_part"] + spans["rlc.hard_part"]
            <= spans["rlc.final_exp"])
    assert spans["vm.miller_product"] <= spans["rlc.miller"]
    assert spans["vm.rlc_combine"] <= spans["rlc.combine"]
    assert spans.get("codec.hash_to_g2", 0.0) <= spans["rlc.prep"]
    for k in ("combines", "bisections", "final_exps"):
        assert rec[k] == bb.RLC_STATS[k] - before[k], k
    assert rec["combines"] == 1 and rec["final_exps"] == 1


def test_spans_nest_on_the_profiler_clock(monkeypatch, tmp_path):
    """The shared-clock check: inside a profiler session the program's
    spans land on one host line of the xplane, nested inside rlc.flush,
    and each lasts what the record says (within 1 ms or 5%)."""
    import jax
    from jax.profiler import ProfileData

    monkeypatch.setenv("CONSENSUS_SPECS_TPU_RLC_CHUNK", "2")
    items = [_committee(63), _committee(64)]
    bb.batch_verify_rlc(items, rng=random.Random(6))  # compiled outside
    tracing.reset_flush_records()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1  # TraceMe spans, not every XLA CPU op
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        bb.batch_verify_rlc(items, rng=random.Random(7))
    finally:
        jax.profiler.stop_trace()
    rec = _rlc_records()[-1]
    path = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                     recursive=True)[0]
    lines = [line for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:") for line in plane.lines
             if any(e.name == "rlc.flush" for e in line.events)]
    assert len(lines) == 1
    events = list(lines[0].events)
    (root,) = [e for e in events if e.name == "rlc.flush"]
    assert dict(root.stats) == {"flush": rec["id"], "items": 2}
    r0, r1 = root.start_ns, root.start_ns + root.duration_ns
    for name in ("rlc.flush",) + CHILDREN:
        got = [e for e in events if e.name == name]
        assert got, name
        for e in got:
            assert r0 <= e.start_ns and e.start_ns + e.duration_ns <= r1, name
        traced = sum(e.duration_ns for e in got) / 1e9
        recorded = rec["spans"][name]
        assert abs(traced - recorded) <= max(1e-3, 0.05 * recorded), name


def _oracle_pow(x, e):
    acc = None
    base = x
    while e:
        if e & 1:
            acc = base if acc is None else acc * base
        base = base * base
        e >>= 1
    return acc


def _oracle_combine(fs, bits, mesh=None):
    total = None
    for i in range(fs.shape[0]):
        f = bb._flat_ints_to_oracle(
            [fq.from_mont_limbs(fs[i, j]) for j in range(12)])
        e = int("".join(str(int(x)) for x in bits[i]), 2)
        x = _oracle_pow(f, e)
        total = x if total is None else total * x
    return bb._oracle_to_flat_ints(total)


class _Lay:
    def split(self, i):
        return i, ""


def _fake_miller(bad):
    """Stands in for the Miller stage: f = 1 for a valid item (its final
    exponentiation is 1), a random Fq12 for each index in ``bad``."""
    rng = random.Random(11)

    def fake(pubkey_sets, messages, signatures, mesh=None):
        n = len(pubkey_sets)
        rows = [[1 if j == 0 else 0 for j in range(12)] if i not in bad
                else [rng.randrange(P) for _ in range(12)] for i in range(n)]
        out = {"aggz": np.stack([fq.to_mont_int(1)] * n)}
        for j in range(12):
            out[f"f.{j}"] = np.stack([fq.to_mont_int(rows[i][j])
                                      for i in range(n)])
        return out, _Lay(), np.ones(n, dtype=bool)

    return fake


def _fake_items(n, tag=b"f"):
    return [("fast_aggregate", [b"\x01" * 48], tag + b"%04d" % i, b"s")
            for i in range(n)]


def test_bisecting_batch_records_bisections_and_final_exps(monkeypatch):
    monkeypatch.setattr(bb, "_miller_fast_aggregate", _fake_miller({5}))
    monkeypatch.setattr(bb, "_rlc_combine_vm", _oracle_combine)
    monkeypatch.setenv("CONSENSUS_SPECS_TPU_RLC_FINAL", "host")
    before = dict(bb.RLC_STATS)
    got = bb.batch_verify_rlc(_fake_items(8), rng=random.Random(3))
    assert list(got) == [i != 5 for i in range(8)]
    (rec,) = _rlc_records()
    assert rec["items"] == 8
    assert rec["bisections"] == bb.RLC_STATS["bisections"] - before[
        "bisections"] > 0
    assert rec["final_exps"] == bb.RLC_STATS["final_exps"] - before[
        "final_exps"] > 1
    assert rec["combines"] == bb.RLC_STATS["combines"] - before["combines"]
    assert (rec["spans"]["rlc.combine"] + rec["spans"]["rlc.final_exp"]
            <= rec["seconds"])


def test_service_writes_one_serve_record_per_flush(monkeypatch):
    monkeypatch.setattr(bb, "_miller_fast_aggregate", _fake_miller({3}))
    monkeypatch.setattr(bb, "_rlc_combine_vm", _oracle_combine)
    monkeypatch.setenv("CONSENSUS_SPECS_TPU_RLC_FINAL", "host")
    backend = types.SimpleNamespace(batch_verify_rlc=bb.batch_verify_rlc)
    n = 24
    with VerificationService(backend=backend, max_batch=8, max_wait_ms=5,
                             bucket_fn=lambda k: 1) as svc:
        futs = [svc.submit(*it) for it in _fake_items(n, b"svc")]
        assert sum(not f.result(timeout=60) for f in futs) >= 1
        flushes = svc.metrics.snapshot()["device_flushes"]
    records = tracing.flush_records()
    serve = [r for r in records if r["kind"] == "serve"]
    rlc = {r["id"]: r for r in records if r["kind"] == "rlc"}
    assert len(serve) == flushes
    assert sum(r["items"] for r in serve) == n
    for r in serve:
        assert r["queue_wait_s"] >= 0 and r["handoff_wait_s"] >= 0
        assert r["prep_s"] >= 0 and r["device_s"] > 0
        assert r["rlc"] in rlc and rlc[r["rlc"]]["items"] == r["items"]
        assert rlc[r["rlc"]]["seconds"] <= r["device_s"]
    assert len({r["id"] for r in records}) == len(records)


def test_flush_ring_keeps_its_bound_and_counts_drops():
    ring = tracing.FlushRecords(capacity=4)
    for i in range(10):
        ring.append({"kind": "rlc", "id": i})
    assert [r["id"] for r in ring.records()] == [6, 7, 8, 9]
    assert ring.dropped == 6
    ring.clear()
    assert ring.records() == [] and ring.dropped == 0
    assert tracing.FLUSH_RING >= 2048


class _SpanLog:
    """Stands in for tracing.span, logging each (name, args)."""

    log = []

    def __init__(self, name, **args):
        self.log.append((name, args))

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def test_vm_span_carries_first_once_per_label(monkeypatch):
    monkeypatch.setattr(vm, "_SEEN_LABELS", {})
    monkeypatch.setattr(tracing, "span", _SpanLog)
    _SpanLog.log = []
    prog = vm.Prog()
    a, b = prog.inp("a"), prog.inp("b")
    prog.out(a * b, "r")
    pr = prog.assemble(w_mul=64, w_lin=64, pad_steps_to=256, pad_regs_to=64)
    pr.kind, pr.k = "probe", 7
    ins = {"a": fq.to_mont_int(3), "b": fq.to_mont_int(5)}
    with tracing.rlc_record(1) as rec:
        for _ in range(3):
            out = vm.execute(pr, ins)
    assert fq.from_mont_limbs(out["r"]) == 15
    vm_spans = [args for name, args in _SpanLog.log if name == "vm.probe"]
    assert [s["first"] for s in vm_spans] == [True, False, False]
    assert all(s["k"] == 7 and s["rows"] == 1 for s in vm_spans)
    assert list(rec["first"].values()) == ["probe"]
    assert next(iter(rec["first"])).startswith("vm[")


def test_each_program_kind_gets_its_own_xla_module():
    """``jit_vm_<kind>`` names the module; a second kind with the very same
    shapes reuses the first one's executable instead of compiling."""
    lowered = vm._vm_run("vm_miller_product").lower(
        np.zeros((1, 2, fq.NUM_LIMBS), np.uint32),
        np.zeros((64, fq.NUM_LIMBS), np.uint64),
        np.zeros(2, np.int32), np.zeros(1, np.int32),
        tuple(np.zeros((256, 64), np.int32) for _ in range(7)), "0")
    assert "jit_vm_miller_product" in lowered.as_text()
    sig = ("probe-shape", object())
    assert vm._module_name("hard_part", sig) == "vm_hard_part"
    assert vm._module_name("rlc_combine", sig) == "vm_hard_part"
    assert vm._module_name(None, ("other", object())) == "vm_program"
