"""Tier-1 coverage for the validator pubkey table (scale/pubkeys.py):
the batched KeyValidate equals the per-key decode, bad keys are marked
invalid, the threaded build equals a one-thread build across chunk
boundaries, ``extend`` appends deposits, and the device gather puts each
key (and infinity on padding lanes) where the per-key path would. Keys
come from a small registry; the index path's verdicts are in
test_scale.py."""
import random

import numpy as np
import pytest

from consensus_specs_tpu.ops import bls_backend, fq
from consensus_specs_tpu.scale import pubkeys
from consensus_specs_tpu.scale.registry import Registry
from consensus_specs_tpu.utils import bls12_381 as O


def _real_pubkeys(n, base=1):
    return Registry(base + n, seed=5).pubkey_column(base, base + n)


def _off_subgroup_key(seed):
    """A compressed point on the curve that is not in G1 (w.h.p.)."""
    rng = random.Random(seed)
    while True:
        x = rng.randrange(O.P)
        y = O.fq_sqrt((x * x % O.P * x + 4) % O.P)
        if y is not None:
            return O.g1_to_bytes((O.Fq(x), O.Fq(y)))


def test_pubkey_plane_batched_equals_per_key_decode():
    pks = _real_pubkeys(4, base=50)
    bad = b"\xa0" + b"\xff" * 47  # x out of range
    inf = b"\xc0" + b"\x00" * 47  # infinity: invalid as a pubkey
    table = pubkeys.PubkeyTable.build(pks + [bad, inf])
    assert len(table) == 6
    assert table.valid[:6].tolist() == [True] * 4 + [False] * 2
    limbs = np.asarray(table.limbs)
    for i, pk in enumerate(pks):
        want_x, want_y = bls_backend._pubkey_limbs_compute(pk)
        np.testing.assert_array_equal(limbs[i, 0], want_x)
        np.testing.assert_array_equal(limbs[i, 1], want_y)
    assert not limbs[4:].any()  # invalid rows and the capacity's tail


def test_pubkey_table_marks_bad_keys_invalid():
    good = _real_pubkeys(2)
    off_curve = bytes([0x80]) + b"\x00" * 46 + b"\x05"
    off_subgroup = _off_subgroup_key(3)
    # the per-key path rejects it too
    assert isinstance(bls_backend._pubkey_limbs_compute(off_subgroup),
                      ValueError)
    keys = [good[0], off_curve, off_subgroup,
            b"\xc0" + b"\x00" * 47,            # infinity
            good[1][:47],                      # short
            bytes([good[1][0] & 0x7F]) + good[1][1:],  # no compression flag
            good[1]]
    table = pubkeys.PubkeyTable.build(keys)
    assert table.valid[:len(keys)].tolist() == [
        True, False, False, False, False, False, True]
    assert not table.valid[len(keys):].any()


def test_pubkey_table_threads_and_chunks_equal_one_pass(monkeypatch):
    from consensus_specs_tpu.ops import codec

    keys = _real_pubkeys(23)
    keys[7] = _off_subgroup_key(9)
    limbs, valid = codec.pubkey_table_limbs(pubkeys._encoded(keys))
    monkeypatch.setattr(pubkeys, "_CHUNK", 5)  # chunk edges at 5, 10, ...
    many = pubkeys.PubkeyTable.build(keys)
    np.testing.assert_array_equal(np.asarray(many.limbs)[:23], limbs)
    np.testing.assert_array_equal(many.valid[:23], valid)
    assert valid.sum() == 22


def test_pubkey_table_extend_appends_deposits(monkeypatch):
    keys = _real_pubkeys(12)
    whole = pubkeys.PubkeyTable.build(keys)
    monkeypatch.setattr(pubkeys, "_BLOCK", 8)  # the extension must grow
    table = pubkeys.PubkeyTable.build(keys[:6])
    assert table.limbs.shape[0] == 8
    table.extend(keys[6:9])
    table.extend(keys[9:] + [b"\x00" * 48])
    assert len(table) == 13 and table.limbs.shape[0] == 16
    np.testing.assert_array_equal(np.asarray(table.limbs)[:12],
                                  np.asarray(whole.limbs)[:12])
    assert table.valid[:13].tolist() == [True] * 12 + [False]


def test_gather_equals_per_key_limbs_with_infinity_padding():
    keys = _real_pubkeys(40)
    table = pubkeys.PubkeyTable.build(keys)
    rng = np.random.default_rng(2**31 + 3)
    rows, fold, k = 2, 2, 8
    idx = rng.integers(0, 40, size=(rows, fold, k)).astype(np.int32)
    idx[0, 1, 5:] = -1
    idx[1, 0, :] = -1
    got = np.asarray(bls_backend.pubkey_gather(table.limbs, idx))
    got = got.reshape(rows, fold, k, 3, fq.NUM_LIMBS)
    one = bls_backend._ONE_LIMBS
    for r, t, j in np.ndindex(rows, fold, k):
        x, y, z = got[r, t, j]
        if idx[r, t, j] < 0:
            want = (np.zeros_like(one), one, np.zeros_like(one))
        else:
            want = bls_backend._pubkey_limbs_compute(keys[idx[r, t, j]]) + (
                one,)
        for a, b in zip((x, y, z), want):
            np.testing.assert_array_equal(a, b)


def test_registry_pubkey_column_equals_per_key_derivation():
    reg = Registry(40, seed=11)
    assert reg.pubkey_column(3, 17) == reg.pubkeys(range(3, 17))
    assert reg.pubkey_column(39) == [reg.pubkey(39)]
    assert reg.pubkey_column(5, 5) == []
    with pytest.raises(IndexError):
        reg.pubkey_column(0, 41)
