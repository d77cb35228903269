"""Random-linear-combination batch verification
(ops/bls_backend.batch_verify_rlc): bit-identical verdicts vs the
per-item path over valid/invalid/mixed/malformed/infinity inputs, the
bisection fallback's localization, the batch-of-1 degeneration,
deterministic injected rngs, and the jax combine (ops/pairing.rlc_combine)
against the exact-int oracle.

Tier-1 runs the small-N end-to-end cases (they share PROG A shapes the
default run compiles anyway) plus logic-level bisection at 16/64 through
an exact host-oracle combine; the wide end-to-end batches (16/64/256,
both combine backends) ride --run-slow like the rest of the device-deep
suites.
"""
import random

import numpy as np
import pytest

from consensus_specs_tpu.obs import tracing
from consensus_specs_tpu.ops import bls_backend as bb
from consensus_specs_tpu.ops import fq
from consensus_specs_tpu.utils import bls
from consensus_specs_tpu.utils import bls12_381 as O
from consensus_specs_tpu.utils.bls12_381 import P, R


def _committee(tag: int, k: int = 2, good: bool = True):
    """One fast_aggregate item (pubkeys, message, signature); corrupt the
    message after signing when not ``good``."""
    sks = [1000 * tag + j + 1 for j in range(k)]
    pks = [bls.SkToPk(sk) for sk in sks]
    msg = (b"rlc%03d" % tag) + b"\x00" * 26
    sig = bls.Sign(sum(sks) % R, msg)
    if not good:
        msg = b"\xff" + msg[1:]
    return ("fast_aggregate", pks, msg, sig)


def _aggregate_item(tag: int, k: int = 2, good: bool = True):
    sks = [5000 * tag + j + 1 for j in range(k)]
    pks = [bls.SkToPk(sk) for sk in sks]
    msgs = [(b"ag%03d_%d" % (tag, j)) + b"\x00" * 24 for j in range(k)]
    sig = bls.Aggregate([bls.Sign(sk, m) for sk, m in zip(sks, msgs)])
    if not good:
        sig = bls.Sign(999, b"z" * 32)
    return ("aggregate", pks, msgs, sig)


def _per_item_verdicts(items) -> np.ndarray:
    out = np.zeros(len(items), dtype=bool)
    fast = [(i, it) for i, it in enumerate(items) if it[0] == "fast_aggregate"]
    agg = [(i, it) for i, it in enumerate(items) if it[0] == "aggregate"]
    if fast:
        res = bb.batch_fast_aggregate_verify(
            [it[1] for _, it in fast], [it[2] for _, it in fast],
            [it[3] for _, it in fast],
        )
        for (i, _), r in zip(fast, res):
            out[i] = bool(r)
    if agg:
        res = bb.batch_aggregate_verify(
            [it[1] for _, it in agg], [it[2] for _, it in agg],
            [it[3] for _, it in agg],
        )
        for (i, _), r in zip(agg, res):
            out[i] = bool(r)
    return out


# -- tier-1: small-N end-to-end gate ----------------------------------------


def test_rlc_mixed_small_batch_matches_per_item(monkeypatch):
    """Valid / corrupted / malformed-signature / infinity-signature in one
    batch: verdicts bit-identical to the per-item path, failures localized
    by bisection."""
    monkeypatch.setenv("CONSENSUS_SPECS_TPU_RLC_CHUNK", "2")
    good_sig = bls.Sign(9, b"p" * 32)
    items = [
        _committee(1, k=2, good=True),
        _committee(2, k=1, good=False),                 # wrong message
        ("fast_aggregate", [bls.SkToPk(7)], b"m" * 32,
         b"\xa0" + b"\x01" * 95),                       # undecodable sig
        ("fast_aggregate", [bls.SkToPk(8)], b"n" * 32,
         b"\xc0" + b"\x00" * 95),                       # infinity sig
        ("fast_aggregate", [b"\xc0" + b"\x00" * 47],
         b"p" * 32, good_sig),                          # infinity pubkey
    ]
    before = dict(bb.RLC_STATS)
    got = bb.batch_verify_rlc(items, rng=random.Random(0xA5))
    want = _per_item_verdicts(items)
    assert np.array_equal(got, want)
    assert list(got) == [True, False, False, False, False]
    # malformed/infinity items never reached the combine: 2 candidates
    assert bb.RLC_STATS["items"] - before["items"] == 2
    # full combine failed (one bad candidate) -> one bisection -> exact
    # singleton finalizations
    assert bb.RLC_STATS["bisections"] > before["bisections"]


def test_rlc_all_valid_single_combine(monkeypatch):
    monkeypatch.setenv("CONSENSUS_SPECS_TPU_RLC_CHUNK", "2")
    items = [_committee(11, k=2), _committee(12, k=2)]
    before = dict(bb.RLC_STATS)
    got = bb.batch_verify_rlc(items, rng=random.Random(1))
    assert list(got) == [True, True]
    assert bb.RLC_STATS["combines"] - before["combines"] == 1
    assert bb.RLC_STATS["bisections"] == before["bisections"]
    # the whole batch paid ONE final exponentiation
    assert bb.RLC_STATS["final_exps"] - before["final_exps"] == 1


def test_rlc_all_invalid_batch(monkeypatch):
    monkeypatch.setenv("CONSENSUS_SPECS_TPU_RLC_CHUNK", "2")
    items = [_committee(21, good=False), _committee(22, good=False)]
    before = dict(bb.RLC_STATS)
    got = bb.batch_verify_rlc(items, rng=random.Random(2))
    assert list(got) == [False, False]
    assert bb.RLC_STATS["bisections"] - before["bisections"] == 1


def test_rlc_batch_of_one_degenerates_to_plain_path():
    before = dict(bb.RLC_STATS)
    assert list(bb.batch_verify_rlc([_committee(31)])) == [True]
    assert list(bb.batch_verify_rlc([_committee(32, good=False)])) == [False]
    # no combine ran: the plain per-item finalization answered both
    assert bb.RLC_STATS["combines"] == before["combines"]


def test_rlc_mixed_kinds_one_combine(monkeypatch):
    monkeypatch.setenv("CONSENSUS_SPECS_TPU_RLC_CHUNK", "2")
    items = [_committee(41, k=2), _aggregate_item(42, k=2)]
    before = dict(bb.RLC_STATS)
    got = bb.batch_verify_rlc(items, rng=random.Random(3))
    assert list(got) == [True, True]
    # both kinds' Miller outputs merged into ONE combined check
    assert bb.RLC_STATS["combines"] - before["combines"] == 1
    assert bb.RLC_STATS["final_exps"] - before["final_exps"] == 1


# -- one Miller launch per kind ----------------------------------------------


def _mixed_k_committees():
    """fast_aggregate items of K 1, 2, 4 and 2, the K=4 one signed over
    another message."""
    return [_committee(51, k=1), _committee(52, k=2),
            _committee(53, k=4, good=False), _committee(54, k=2)]


def _alone(verify, items) -> list:
    """Each item verified by ``verify`` in a batch of its own."""
    return [bool(verify([it[1]], [it[2]], [it[3]])[0]) for it in items]


def test_rlc_fast_aggregate_mixed_k_one_miller_launch(monkeypatch):
    """Items of three K-buckets share one PROG A launch at the largest
    bucket; the bad one is found by bisection and every verdict equals
    the per-item path's."""
    monkeypatch.setenv("CONSENSUS_SPECS_TPU_RLC_CHUNK", "2")
    items = _mixed_k_committees()
    got = bb.batch_verify_rlc(items, rng=random.Random(5))
    record = tracing.flush_records()[-1]
    assert record["miller_launches"] == 1
    assert record["bisections"] >= 1
    assert got.tolist() == _alone(bb.batch_fast_aggregate_verify, items)
    assert got.tolist() == [True, True, False, True]


def test_rlc_indexed_mixed_committee_sizes_one_miller_launch(monkeypatch):
    """The index path's committees of sizes 1, 2, 4 and 2 (one signed over
    another message) in one PROG A launch, with the bytes path's per-item
    verdicts."""
    from consensus_specs_tpu.scale.pubkeys import PubkeyTable

    monkeypatch.setenv("CONSENSUS_SPECS_TPU_RLC_CHUNK", "2")
    items = _mixed_k_committees()
    keys = [pk for it in items for pk in it[1]]
    table = PubkeyTable.build(keys)
    indexed, start = [], 0
    for _, pks, msg, sig in items:
        cols = np.arange(start, start + len(pks), dtype=np.int64)
        indexed.append(("fast_aggregate_indexed", cols, msg, sig))
        start += len(pks)
    got = bb.batch_verify_rlc(indexed, rng=random.Random(6), table=table)
    record = tracing.flush_records()[-1]
    assert record["miller_launches"] == 1
    assert record["bisections"] >= 1
    assert record["keys_gathered"] == len(keys)
    assert got.tolist() == _alone(bb.batch_fast_aggregate_verify, items)


def test_rlc_aggregate_kind_keeps_one_launch_per_bucket(monkeypatch):
    """'aggregate' K counts pairings, so its buckets stay apart: K 1 and
    K 2 items run two PROG A launches, beside one for a fast_aggregate."""
    monkeypatch.setenv("CONSENSUS_SPECS_TPU_RLC_CHUNK", "2")
    aggs = [_aggregate_item(55, k=1), _aggregate_item(56, k=2),
            _aggregate_item(57, k=2, good=False)]
    items = aggs + [_committee(58, k=1)]
    got = bb.batch_verify_rlc(items, rng=random.Random(7))
    record = tracing.flush_records()[-1]
    assert record["miller_launches"] == 3
    assert got.tolist() == (_alone(bb.batch_aggregate_verify, aggs)
                            + [True])
    assert got.tolist() == [True, True, False, True]


def test_rlc_empty_and_bad_kind():
    assert list(bb.batch_verify_rlc([])) == []
    with pytest.raises(ValueError):
        bb.batch_verify_rlc([("proposer", [b"x"], b"m", b"s")])


# -- deterministic injected rng ---------------------------------------------


def test_rlc_scalars_deterministic_and_nonzero():
    a = bb._rlc_scalars(8, random.Random(7))
    b = bb._rlc_scalars(8, random.Random(7))
    assert np.array_equal(a, b)  # injected rng reproduces exactly
    c = bb._rlc_scalars(8, random.Random(8))
    assert not np.array_equal(a, c)
    assert a.shape == (8, 128)
    assert (a.sum(axis=1) > 0).all()  # nonzero scalars only
    # os.urandom default: right shape, nonzero
    d = bb._rlc_scalars(3)
    assert d.shape == (3, 128) and (d.sum(axis=1) > 0).all()


def test_rlc_verdicts_reproducible_with_injected_rng(monkeypatch):
    monkeypatch.setenv("CONSENSUS_SPECS_TPU_RLC_CHUNK", "2")
    items = [_committee(51), _committee(52, good=False)]
    before = dict(bb.RLC_STATS)
    got1 = bb.batch_verify_rlc(items, rng=random.Random(9))
    mid = dict(bb.RLC_STATS)
    got2 = bb.batch_verify_rlc(items, rng=random.Random(9))
    after = dict(bb.RLC_STATS)
    assert np.array_equal(got1, got2) and list(got1) == [True, False]
    # identical scalars -> identical combine/bisection trajectory
    assert ({k: mid[k] - before[k] for k in mid}
            == {k: after[k] - mid[k] for k in after})


def test_reset_rlc_stats_and_clamped_serve_deltas():
    """reset_rlc_stats() zeroes the ledger + gauges, and a ServeMetrics
    baseline captured BEFORE a reset must clamp its deltas at zero (a
    rewound counter reads as no activity, never negative combines)."""
    from consensus_specs_tpu.ops import profiling
    from consensus_specs_tpu.serve.metrics import ServeMetrics

    bb.RLC_STATS["combines"] += 3
    bb.RLC_STATS["final_exps"] += 5
    sm = ServeMetrics()  # baseline sees the inflated counters
    bb.reset_rlc_stats()
    assert all(v == 0 for v in bb.RLC_STATS.values())
    assert profiling.summary()["bls.rlc_combines"]["gauge"] == 0.0
    assert profiling.summary()["bls.rlc_bisections"]["gauge"] == 0.0
    snap = sm.snapshot()
    assert snap["rlc"]["combines"] == 0  # clamped, not negative
    assert snap["rlc"]["final_exps"] == 0
    assert snap["final_exps_per_item"] == 0.0


# -- bisection localization at width (exact-oracle combine) -----------------


class _FakeLay:
    fold = 1

    def split(self, i):
        return i, ""


def _oracle_pow(f, e: int):
    acc = None
    for ch in bin(e)[2:]:
        if acc is not None:
            acc = acc * acc
        if ch == "1":
            acc = f if acc is None else acc * f
    return acc


def _oracle_combine(fs, bits, mesh=None):
    """Exact host reference of the combine stage (same contract as
    _rlc_combine_vm) — lets the bisection orchestration run at width
    with real final-exp math but no VM programs."""
    total = None
    for i in range(fs.shape[0]):
        f = bb._flat_ints_to_oracle(
            [fq.from_mont_limbs(fs[i, j]) for j in range(12)]
        )
        e = int("".join(str(int(x)) for x in bits[i]), 2)
        x = _oracle_pow(f, e)
        total = x if total is None else total * x
    return bb._oracle_to_flat_ints(total)


def _fake_miller(fs_rows):
    """Monkeypatch target for _miller_fast_aggregate: hands batch_verify_rlc
    pre-chosen f rows (valid item -> f = 1, whose final exp is 1; invalid
    -> a random Fq12, which fails the final exp with certainty ~1/r)."""
    def fake(pubkey_sets, messages, signatures, mesh=None):
        n = len(pubkey_sets)
        out = {"aggz": np.stack([fq.to_mont_int(1)] * n)}
        for j in range(12):
            out[f"f.{j}"] = np.stack([fs_rows[i][j] for i in range(n)])
        return out, _FakeLay(), np.ones(n, dtype=bool)

    return fake


def _f_row(valid: bool, rng: random.Random) -> np.ndarray:
    if valid:
        return np.stack([fq.to_mont_int(1 if j == 0 else 0)
                         for j in range(12)])
    return np.stack([fq.to_mont_int(rng.randrange(P)) for j in range(12)])


@pytest.mark.parametrize("n,bad", [(2, 1), (16, 3), (64, 40)])
def test_rlc_bisection_localizes_bad_items(monkeypatch, n, bad):
    """A single corrupted item in batches of 2/16/64 is isolated by
    bisection (everything else True), with O(log N) extra combines."""
    rng = random.Random(n * 1000 + bad)
    fs_rows = [_f_row(i != bad, rng) for i in range(n)]
    monkeypatch.setattr(bb, "_miller_fast_aggregate", _fake_miller(fs_rows))
    monkeypatch.setattr(bb, "_rlc_combine_vm", _oracle_combine)
    items = [("fast_aggregate", [b"\x01" * 48], b"m%03d" % i, b"s")
             for i in range(n)]
    before = dict(bb.RLC_STATS)
    got = bb.batch_verify_rlc(items, rng=rng)
    want = np.ones(n, dtype=bool)
    want[bad] = False
    assert np.array_equal(got, want)
    d = {k: bb.RLC_STATS[k] - before[k] for k in bb.RLC_STATS}
    assert d["items"] == n
    # one failing path down the tree: <= 2 combines per level + the root
    import math

    levels = max(1, math.ceil(math.log2(n)))
    assert d["bisections"] <= levels
    assert d["combines"] <= 1 + 2 * levels


def test_rlc_bisection_all_invalid_wide(monkeypatch):
    n = 16
    rng = random.Random(77)
    fs_rows = [_f_row(False, rng) for _ in range(n)]
    monkeypatch.setattr(bb, "_miller_fast_aggregate", _fake_miller(fs_rows))
    monkeypatch.setattr(bb, "_rlc_combine_vm", _oracle_combine)
    items = [("fast_aggregate", [b"\x01" * 48], b"w%03d" % i, b"s")
             for i in range(n)]
    got = bb.batch_verify_rlc(items, rng=rng)
    assert not got.any()


# -- jax combine backend + oracle cross-check -------------------------------


def test_pairing_rlc_combine_matches_oracle():
    """ops/pairing.rlc_combine == exact-int oracle prod f_i^{r_i}."""
    from consensus_specs_tpu.ops import pairing

    rng = random.Random(13)
    fs_o = []
    for _ in range(2):
        fs_o.append(O.Fq12(
            O.Fq6(*[O.Fq2(rng.randrange(P), rng.randrange(P))
                    for _ in range(3)]),
            O.Fq6(*[O.Fq2(rng.randrange(P), rng.randrange(P))
                    for _ in range(3)]),
        ))
    fs = np.stack([
        np.stack([fq.to_mont_int(c) for c in bb._oracle_to_flat_ints(f)])
        for f in fs_o
    ])
    bits = bb._rlc_scalars(2, rng)
    got = np.asarray(pairing.rlc_combine(fs, bits.astype(bool)))
    got_o = bb._flat_ints_to_oracle(
        [fq.from_mont_limbs(got[j]) for j in range(12)]
    )
    want = None
    for f, brow in zip(fs_o, bits):
        e = int("".join(str(int(x)) for x in brow), 2)
        x = _oracle_pow(f, e)
        want = x if want is None else want * x
    assert bb._oracle_to_flat_ints(got_o) == bb._oracle_to_flat_ints(want)


def test_rlc_jax_backend_end_to_end(monkeypatch):
    monkeypatch.setenv("CONSENSUS_SPECS_TPU_RLC_BACKEND", "jax")
    items = [_committee(61, k=2), _committee(62, k=2)]
    got = bb.batch_verify_rlc(items, rng=random.Random(4))
    assert list(got) == [True, True]


# -- final-exp routing ------------------------------------------------------


def test_rlc_final_host_and_device_agree(monkeypatch):
    """The combined check's hard part is bit-identical whether it runs as
    an exact-int oracle HHT on host or a hard_part VM row on device."""
    rng = random.Random(21)
    good = [1] + [0] * 11  # f = 1 passes
    bad = [rng.randrange(P) for _ in range(12)]
    for mode in ("host", "device"):
        monkeypatch.setenv("CONSENSUS_SPECS_TPU_RLC_FINAL", mode)
        assert bb._final_exp_is_one(list(good)) is True
        assert bb._final_exp_is_one(list(bad)) is False
    # degenerate f = 0: False without any hard part
    monkeypatch.setenv("CONSENSUS_SPECS_TPU_RLC_FINAL", "host")
    assert bb._final_exp_is_one([0] * 12) is False


def test_hard_part_oracle_matches_vm_on_real_item():
    """Host-oracle HHT vs the device hard part on a REAL unitary g (the
    easy-part output of a genuine Miller value), both verdict polarities."""
    (_, pks, msg, sig) = _committee(71, k=1)
    out, lay, precheck = bb._miller_fast_aggregate([pks], [msg], [sig], None)
    assert out is not None and precheck[0]
    r, ns = lay.split(0)
    coeffs = [fq.from_mont_limbs(out[f"{ns}f.{j}"][r]) for j in range(12)]
    g = bb._easy_part_flat(coeffs)
    gm = np.stack([fq.to_mont_int(c) for c in g])
    assert bb._hard_part_is_one_oracle(g) is True
    assert bool(bb._run_hard_part(gm[None])[0]) is True
    # perturb g out of the kernel: both must say False
    g_bad = list(g)
    g_bad[0] = (g_bad[0] + 1) % P
    gm_bad = np.stack([fq.to_mont_int(c) for c in g_bad])
    assert bb._hard_part_is_one_oracle(g_bad) is False
    assert bool(bb._run_hard_part(gm_bad[None])[0]) is False


# -- collector integration --------------------------------------------------


def test_collector_flush_rlc(monkeypatch):
    from consensus_specs_tpu.batch_verify import SignatureCollector

    monkeypatch.setenv("CONSENSUS_SPECS_TPU_RLC_CHUNK", "2")
    kind, pks, msg, sig = _committee(81, k=2)
    col = SignatureCollector()
    assert col._fast_aggregate_verify(pks, msg, sig) is True
    assert col._fast_aggregate_verify(pks, msg, sig) is True  # duplicate
    assert col._fast_aggregate_verify(pks, b"\xff" + msg[1:], sig) is True
    got = col.flush(rlc=True)
    assert np.array_equal(got, col.flush_oracle())
    assert list(got) == [True, True, False]


# -- wide end-to-end batches (slow: fresh big-program compiles) -------------


@pytest.mark.slow
def test_rlc_wide_batches_match_per_item_vm():
    for n, bad in ((16, 5), (64, None)):
        items = [_committee(100 + i, k=1, good=(i != bad)) for i in range(n)]
        got = bb.batch_verify_rlc(items, rng=random.Random(n))
        want = _per_item_verdicts(items)
        assert np.array_equal(got, want)
        if bad is None:
            assert got.all()
        else:
            assert got.sum() == n - 1 and not got[bad]


@pytest.mark.slow
def test_rlc_256_valid_vm():
    items = [_committee(400 + i, k=1) for i in range(256)]
    before = dict(bb.RLC_STATS)
    got = bb.batch_verify_rlc(items, rng=random.Random(256))
    assert got.all()
    assert bb.RLC_STATS["final_exps"] - before["final_exps"] == 1


@pytest.mark.slow
def test_rlc_wide_jax_backend(monkeypatch):
    monkeypatch.setenv("CONSENSUS_SPECS_TPU_RLC_BACKEND", "jax")
    n, bad = 16, 11
    items = [_committee(300 + i, k=1, good=(i != bad)) for i in range(n)]
    got = bb.batch_verify_rlc(items, rng=random.Random(5))
    want = np.ones(n, dtype=bool)
    want[bad] = False
    assert np.array_equal(got, want)
