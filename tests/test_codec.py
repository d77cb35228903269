"""Oracle-equivalence gate for the batched input codec (ops/codec.py).

Every codec output must be BIT-IDENTICAL to the pure-Python oracle
(utils/bls12_381.py) / the per-item compute functions in ops/bls_backend
— on valid points, invalid encodings, non-subgroup points (including
cofactor-torsion points, the adversarial corner of the fast membership
tests), and infinity, across batch sizes 1..256.

Fast tests cover the raw-int host path (the CPU-fallback serving path in
tier-1). The VM/jax device path runs the same suite under --run-slow
(CONSENSUS_SPECS_TPU_CODEC_DEVICE=1 forces it on CPU, where the programs
are slow but correct).
"""
import random

import numpy as np
import pytest

from consensus_specs_tpu.ops import bls_backend as B
from consensus_specs_tpu.ops import codec, fq
from consensus_specs_tpu.utils import bls12_381 as O

DST = B.DST
SIZES = [1, 2, 3, 4, 5, 8, 16, 33, 64, 256]
POOL = 256


def _norm(v):
    """Codec results and per-item results on one footing: ValueErrors
    (raised or returned) compare by message, limb payloads by bytes."""
    if isinstance(v, ValueError):
        return ("err", str(v))
    if v is None:
        return ("inf",)
    if isinstance(v, tuple):
        return ("ok", tuple(np.asarray(x).tobytes() for x in v))
    return ("ok", np.asarray(v).tobytes())


def _ref(fn, blob):
    try:
        return _norm(fn(blob))
    except ValueError as e:
        return ("err", str(e))


def _rand_g1_affine(rng):
    while True:
        x = rng.randrange(O.P)
        y = O.fq_sqrt((x * x % O.P * x + 4) % O.P)
        if y is not None:
            return (O.Fq(x), O.Fq(y))


def _rand_g2_affine(rng):
    while True:
        x = O.Fq2(rng.randrange(O.P), rng.randrange(O.P))
        y = (x * x * x + O.B_G2).sqrt()
        if y is not None:
            return (x, y)


def _pool_g1():
    """Valid members, invalid encodings, infinity, random non-members,
    and [r]T cofactor-torsion points — POOL blobs, deterministic."""
    rng = random.Random(11)
    blobs = []
    for i in range(POOL):
        r = i % 8
        if r < 3:  # subgroup member
            k = rng.randrange(1, O.R)
            blobs.append(O.g1_to_bytes(O.ec_mul(O.G1_GEN, k)))
        elif r == 3:  # random curve point: non-member w.h.p.
            blobs.append(O.g1_to_bytes(O.ec_from_affine(_rand_g1_affine(rng))))
        elif r == 4:  # cofactor torsion: [r]T kills the G1 part only
            s = O.ec_mul(O.ec_from_affine(_rand_g1_affine(rng)), O.R)
            blobs.append(O.g1_to_bytes(s))
        elif r == 5:  # infinity (valid and corrupted)
            good = bytes([O.FLAG_COMPRESSED | O.FLAG_INFINITY]) + b"\x00" * 47
            blobs.append(good if i % 2 else good[:1] + b"\x01" + good[2:])
        elif r == 6:  # x not on curve / x out of range
            if i % 2:
                blobs.append(bytes([0x80]) + b"\x00" * 46 + b"\x05")
            else:
                blobs.append(
                    bytes([0x9F]) + b"\xff" * 47
                )  # x >= p with sign bit games
        else:  # structural: wrong length, missing compress bit
            blobs.append([b"\x00" * 48, b"\x12" * 48, b"\xc0" + b"\x00" * 40,
                          O.g1_to_bytes(O.G1_GEN)[:47]][i % 4])
    return blobs


def _pool_g2():
    rng = random.Random(13)
    blobs = []
    for i in range(POOL):
        r = i % 8
        if r < 3:
            k = rng.randrange(1, O.R)
            blobs.append(O.g2_to_bytes(O.ec_mul(O.G2_GEN, k)))
        elif r == 3:  # random curve point: outside G2 w.h.p.
            blobs.append(O.g2_to_bytes(_rand_g2_affine(rng)))
        elif r == 4:  # cofactor torsion on the twist
            s = O.ec_mul(O.ec_from_affine(_rand_g2_affine(rng)), O.R)
            blobs.append(O.g2_to_bytes(s))
        elif r == 5:
            good = bytes([O.FLAG_COMPRESSED | O.FLAG_INFINITY]) + b"\x00" * 95
            blobs.append(good if i % 2 else good[:5] + b"\x01" + good[6:])
        elif r == 6:
            if i % 2:
                blobs.append(bytes([0x80]) + b"\x00" * 94 + b"\x07")
            else:
                blobs.append(bytes([0x9F]) + b"\xff" * 95)
        else:
            blobs.append([b"\x00" * 96, b"\x34" * 96, b"\xc0" + b"\x01" * 95,
                          O.g2_to_bytes(O.G2_GEN)[:95]][i % 4])
    return blobs


def _pool_msgs():
    rng = random.Random(17)
    msgs = [b"", b"\x00", b"q" * 130]  # length edges incl. > one SHA block
    while len(msgs) < POOL:
        msgs.append(rng.randbytes(rng.choice([8, 32, 64])))
    return msgs


_G1 = _pool_g1()
_G2 = _pool_g2()
_MSGS = _pool_msgs()
# oracle references computed once per distinct blob, reused by all sizes
_G1_REF = {b: _ref(B._pubkey_limbs_compute, b) for b in set(_G1)}
_G2_REF = {b: _ref(B._signature_limbs_compute, b) for b in set(_G2)}
_MSG_REF = {m: _norm(B._message_limbs_compute(m)) for m in set(_MSGS)}


@pytest.mark.parametrize("n", SIZES)
def test_pubkey_batch_matches_oracle(n):
    blobs = _G1[:n]
    got = codec.pubkey_limbs_batch(blobs)
    assert [_norm(v) for v in got] == [_G1_REF[b] for b in blobs]


@pytest.mark.parametrize("n", SIZES)
def test_signature_batch_matches_oracle(n):
    blobs = _G2[:n]
    got = codec.signature_limbs_batch(blobs)
    assert [_norm(v) for v in got] == [_G2_REF[b] for b in blobs]


@pytest.mark.parametrize("n", SIZES)
def test_message_batch_matches_oracle(n):
    msgs = _MSGS[:n]
    got = codec.message_limbs_batch(msgs, DST)
    assert [_norm(v) for v in got] == [_MSG_REF[m] for m in msgs]


def test_decompress_infinity_is_none():
    inf1 = bytes([O.FLAG_COMPRESSED | O.FLAG_INFINITY]) + b"\x00" * 47
    inf2 = bytes([O.FLAG_COMPRESSED | O.FLAG_INFINITY]) + b"\x00" * 95
    assert codec.decompress_g1_batch([inf1]) == [None]
    assert codec.decompress_g2_batch([inf2]) == [None]
    # the backend-facing wrappers turn infinity into the oracle's error
    assert _norm(codec.pubkey_limbs_batch([inf1])[0]) == _ref(
        B._pubkey_limbs_compute, inf1
    )
    assert _norm(codec.signature_limbs_batch([inf2])[0]) == _ref(
        B._signature_limbs_compute, inf2
    )


def test_expand_message_xmd_batch_matches_oracle():
    msgs = [b"", b"abc", b"q" * 200, b"\x00" * 31]
    for lib in (32, 64, 100, 256):
        got = codec.expand_message_xmd_batch(msgs, DST, lib)
        want = [O.expand_message_xmd(m, DST, lib) for m in msgs]
        assert got == want


def test_int_batch_inverse_matches_fermat():
    rng = random.Random(19)
    vals = [0, 1, O.P - 1] + [rng.randrange(O.P) for _ in range(61)]
    got = codec.int_batch_inverse(vals)
    for v, iv in zip(vals, got):
        assert iv == (pow(v, O.P - 2, O.P) if v else 0)


def test_glv_beta_eigenvalue_against_generator():
    """The G1 host membership test hinges on phi(P) == [-z^2]P with
    _BETA_G1 the matching cube root; pin that pairing to the oracle."""
    z = codec._X_ABS
    g = O.ec_to_affine(O.G1_GEN)
    phi = (codec._BETA_G1 * g[0].n % O.P, g[1].n)
    q = O.ec_to_affine(O.ec_neg(O.ec_mul(O.G1_GEN, z * z)))
    assert phi == (q[0].n, q[1].n)
    assert pow(codec._BETA_G1, 3, O.P) == 1 and codec._BETA_G1 != 1


def test_g1_subgroup_host_matches_definitional():
    """The GLV two-ladder test vs the oracle's [r]P == O, specifically on
    torsion points where a wrong eigenvalue/criterion would diverge."""
    rng = random.Random(23)
    pts = []
    for _ in range(6):
        aff = _rand_g1_affine(rng)
        pts.append(aff)
        s = O.ec_mul(O.ec_from_affine(aff), O.R)
        if s is not None:
            pts.append(O.ec_to_affine(s))
    for k in (1, 2, 12345):
        pts.append(O.ec_to_affine(O.ec_mul(O.G1_GEN, k)))
    got = codec._g1_subgroup_host([(x.n, y.n) for x, y in pts])
    want = [O.is_in_g1_subgroup(O.ec_from_affine(a)) for a in pts]
    assert got == want


def test_g2_subgroup_host_matches_oracle():
    rng = random.Random(29)
    pts = [_rand_g2_affine(rng) for _ in range(6)]
    for _ in range(3):
        s = O.ec_mul(O.ec_from_affine(_rand_g2_affine(rng)), O.R)
        if s is not None:
            pts.append(O.ec_to_affine(s))
    for k in (1, 7, 99999):
        pts.append(O.ec_to_affine(O.ec_mul(O.G2_GEN, k)))
    got = codec._g2_subgroup_host(
        [((x.c0, x.c1), (y.c0, y.c1)) for x, y in pts]
    )
    want = [O.is_in_g2_subgroup(O.ec_from_affine(a)) for a in pts]
    assert got == want


# -- jax field kernels (shared sqrt chains / batch-inversion ladder) --------


def test_fq_batch_inverse_kernel():
    rng = random.Random(31)
    vals = [0, 1, O.P - 1] + [rng.randrange(O.P) for _ in range(13)]
    arr = np.stack([fq.to_mont_int(v) for v in vals])
    out = codec.fq_batch_inverse(arr)
    for v, limbs in zip(vals, out):
        want = pow(v, O.P - 2, O.P) if v else 0
        assert fq.from_mont_limbs(limbs) == want


def test_fq2_sqrt_batch_matches_oracle_choice():
    """Bit-identical root CHOICE, not just +/- equivalence; ok False
    exactly where the oracle returns None; b == 0 branches included."""
    rng = random.Random(37)
    vals = []
    for _ in range(10):
        v = O.Fq2(rng.randrange(O.P), rng.randrange(O.P))
        vals.append(v)
        vals.append(v.square())  # guaranteed residue
    for a in (0, 1, 5, O.P - 1):
        vals.append(O.Fq2(a, 0))  # b == 0 lanes
    arr = np.stack(
        [np.stack([fq.to_mont_int(v.c0), fq.to_mont_int(v.c1)]) for v in vals]
    )
    roots, ok = codec.fq2_sqrt_batch(arr)
    for v, r, k in zip(vals, roots, ok):
        want = v.sqrt()
        assert bool(k) == (want is not None)
        if want is not None:
            assert fq.from_mont_limbs(r[0]) == want.c0
            assert fq.from_mont_limbs(r[1]) == want.c1


# -- device path (VM programs + jax decode kernels), --run-slow only --------


@pytest.fixture
def force_device(monkeypatch):
    monkeypatch.setenv("CONSENSUS_SPECS_TPU_CODEC_DEVICE", "1")


@pytest.mark.parametrize("backend,env,want", [
    ("cpu", None, False), ("tpu", None, False), ("tpu", "0", False),
    ("cpu", "1", True)])
def test_codec_placement(monkeypatch, backend, env, want):
    monkeypatch.setattr(codec.jax, "default_backend", lambda: backend)
    if env is None:
        monkeypatch.delenv("CONSENSUS_SPECS_TPU_CODEC_DEVICE", raising=False)
    else:
        monkeypatch.setenv("CONSENSUS_SPECS_TPU_CODEC_DEVICE", env)
    assert codec._use_device() is want


def test_codec_device_route_refused_on_tpu(force_device, monkeypatch):
    """The route that never finished compiling on a v5e is refused there
    with the reason, not left to hang."""
    monkeypatch.setattr(codec.jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="_sswu_map_kernel"):
        codec.pubkey_limbs_batch(_G1[:1])


@pytest.mark.slow
@pytest.mark.parametrize("n", [1, 3, 8])
def test_device_pubkey_batch(force_device, n):
    blobs = _G1[:n]
    got = codec.pubkey_limbs_batch(blobs)
    assert [_norm(v) for v in got] == [_G1_REF[b] for b in blobs]


@pytest.mark.slow
@pytest.mark.parametrize("n", [1, 3, 8])
def test_device_signature_batch(force_device, n):
    blobs = _G2[:n]
    got = codec.signature_limbs_batch(blobs)
    assert [_norm(v) for v in got] == [_G2_REF[b] for b in blobs]


@pytest.mark.slow
@pytest.mark.parametrize("n", [1, 3])
def test_device_message_batch(force_device, n):
    msgs = _MSGS[:n]
    got = codec.message_limbs_batch(msgs, DST)
    assert [_norm(v) for v in got] == [_MSG_REF[m] for m in msgs]
