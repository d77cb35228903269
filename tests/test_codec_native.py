"""Bit-identity gate for the native host codec (csrc/bls_host.c).

Native kernel == raw-int Python path (ops/codec.py) == oracle
(utils/bls12_381.py), bit for bit: hash-to-G2 over batch sizes 1..256,
edge-length messages and the RFC 9380 G2 known-answer vectors; the SSWU
exceptional branch through the field-draw entry point; the Fq2 square
root's root choice and the batch inversion's zero lanes; G1/G2 decoding
and subgroup checks on every rejection class, each with the identical
ValueError message; and the loader, whose absence leaves the Python path
running and counted.
"""
import random
import shutil

import numpy as np
import pytest

from consensus_specs_tpu.ops import bls_backend as B
from consensus_specs_tpu.ops import codec, fq
from consensus_specs_tpu.utils import bls12_381 as O
from consensus_specs_tpu.utils import native_bls, native_sha256

DST = B.DST

pytestmark = pytest.mark.skipif(not native_bls.available(),
                                reason="no C compiler: the native kernel "
                                       "did not build")


@pytest.fixture
def python_route(monkeypatch):
    """The library absent: the codec takes its raw-int path."""
    monkeypatch.setattr(native_bls, "_lib", False)
    assert codec.host_route() == "python"


def _limbs(x: int, y: int, *more: int) -> bytes:
    return np.stack([fq.to_mont_int(v) for v in (x, y) + more]).tobytes()


def _affine_ints(pt):
    x, y = O.ec_to_affine(pt)
    return (x.c0, x.c1, y.c0, y.c1)


# -- hash-to-G2 ---------------------------------------------------------------

# RFC 9380 Appendix J.10.1, BLS12381G2_XMD:SHA-256_SSWU_RO_: (x.c0, x.c1,
# y.c0, y.c1) of the affine hash of each message
RFC_DST = b"QUUX-V01-CS02-with-BLS12381G2_XMD:SHA-256_SSWU_RO_"
RFC_VECTORS = {
    b"": (
        0x0141ebfbdca40eb85b87142e130ab689c673cf60f1a3e98d69335266f30d9b8d4ac44c1038e9dcdd5393faf5c41fb78a,
        0x05cb8437535e20ecffaef7752baddf98034139c38452458baeefab379ba13dff5bf5dd71b72418717047f5b0f37da03d,
        0x0503921d7f6a12805e72940b963c0cf3471c7b2a524950ca195d11062ee75ec076daf2d4bc358c4b190c0c98064fdd92,
        0x12424ac32561493f3fe3c260708a12b7c620e7be00099a974e259ddc7d1f6395c3c811cdd19f1e8dbf3e9ecfdcbab8d6),
    b"abc": (
        0x02c2d18e033b960562aae3cab37a27ce00d80ccd5ba4b7fe0e7a210245129dbec7780ccc7954725f4168aff2787776e6,
        0x139cddbccdc5e91b9623efd38c49f81a6f83f175e80b06fc374de9eb4b41dfe4ca3a230ed250fbe3a2acf73a41177fd8,
        0x1787327b68159716a37440985269cf584bcb1e621d3a7202be6ea05c4cfe244aeb197642555a0645fb87bf7466b2ba48,
        0x00aa65dae3c8d732d10ecd2c50f8a1baf3001578f71c694e03866e9f3d49ac1e1ce70dd94a733534f106d4cec0eddd16),
    b"abcdef0123456789": (
        0x121982811d2491fde9ba7ed31ef9ca474f0e1501297f68c298e9f4c0028add35aea8bb83d53c08cfc007c1e005723cd0,
        0x190d119345b94fbd15497bcba94ecf7db2cbfd1e1fe7da034d26cbba169fb3968288b3fafb265f9ebd380512a71c3f2c,
        0x05571a0f8d3c08d094576981f4a3b8eda0a8e771fcdcc8ecceaf1356a6acf17574518acb506e435b639353c2e14827c8,
        0x0bb5e7572275c567462d91807de765611490205a941a5a6af3b1691bfe596c31225d3aabdf15faff860cb4ef17c7c3be),
    b"q128_" + b"q" * 128: (
        0x19a84dd7248a1066f737cc34502ee5555bd3c19f2ecdb3c7d9e24dc65d4e25e50d83f0f77105e955d78f4762d33c17da,
        0x0934aba516a52d8ae479939a91998299c76d39cc0c035cd18813bec433f587e2d7a4fef038260eef0cef4d02aae3eb91,
        0x14f81cd421617428bc3b9fe25afbb751d934a00493524bc4e065635b0555084dd54679df1536101b2c979c0152d09192,
        0x09bcccfa036b4847c9950780733633f13619994394c23ff0b32fa6b795844f4a0673e20282d07bc69641cee04f5e5662),
    b"a512_" + b"a" * 512: (
        0x01a6ba2f9a11fa5598b2d8ace0fbe0a0eacb65deceb476fbbcb64fd24557c2f4b18ecfc5663e54ae16a84f5ab7f62534,
        0x11fca2ff525572795a801eed17eb12785887c7b63fb77a42be46ce4a34131d71f7a73e95fee3f812aea3de78b4d01569,
        0x0b6798718c8aed24bc19cb27f866f1c9effcdbf92397ad6448b5c9db90d2b9da6cbabf48adc1adf59a1a28344e79d57e,
        0x03a47f8e6d1763ba0cad63d6114c0accbef65707825a511b251a660a9b3994249ae4e63fac38b23da0c398689ee2ab52),
}


def _messages():
    rng = random.Random(41)
    msgs = [b"", b"\x00" * 1024, rng.randbytes(1024), b"abc"]
    while len(msgs) < 256:
        msgs.append(rng.randbytes(rng.choice([1, 8, 32, 63, 64, 65, 200])))
    return msgs


_MSGS = _messages()
ORACLE_MSGS = 6  # the oracle's own hash is ~30 ms a message


@pytest.fixture(scope="module")
def h2g_python():
    """The raw-int path once over all 256 messages: the reference every
    batch size meets (built when a test asks, not at collection)."""
    return [_limbs(*x, *y) for x, y in codec._hash_to_g2_host(_MSGS, DST)]


@pytest.mark.parametrize("n", [1, 2, 3, 5, 8, 17, 64, 256])
def test_hash_to_g2_native_matches_python_path(n, h2g_python):
    got = codec.hash_to_g2_batch(_MSGS[:n], DST)
    assert [g.tobytes() for g in got] == h2g_python[:n]


def test_hash_to_g2_native_matches_oracle():
    """Empty and 1 KiB messages among them."""
    got = codec.hash_to_g2_batch(_MSGS[:ORACLE_MSGS], DST)
    for m, g in zip(_MSGS[:ORACLE_MSGS], got):
        assert g.tobytes() == _limbs(*_affine_ints(O.hash_to_g2(m, DST)))


def test_hash_to_g2_rfc9380_vectors():
    msgs = list(RFC_VECTORS)
    native = codec.hash_to_g2_batch(msgs, RFC_DST)
    python = codec._hash_to_g2_host(msgs, RFC_DST)
    for m, g, (x, y) in zip(msgs, native, python):
        want = RFC_VECTORS[m]
        assert g.tobytes() == _limbs(*want)
        assert (x[0], x[1], y[0], y[1]) == want
        assert _affine_ints(O.hash_to_g2(m, RFC_DST)) == want


def _draw64(v: int) -> bytes:
    return v.to_bytes(64, "big")


def _oracle_from_draws(u0, u1):
    q0 = O.iso_map_g2(*O.map_to_curve_sswu_g2(O.Fq2(*u0)))
    q1 = O.iso_map_g2(*O.map_to_curve_sswu_g2(O.Fq2(*u1)))
    r = O.ec_add(O.ec_from_affine(q0), O.ec_from_affine(q1))
    return _affine_ints(O.clear_cofactor_g2(r))


def test_sswu_exceptional_branch_through_the_draws():
    """u == 0 gives tv2 == 0, SSWU's exceptional x1 = B/(ZA); as a 64-byte
    draw 0 is also p and 2^130 p (the kernel's 512-bit reduction). Both
    draws 0 make the two SSWU points equal: the add doubles."""
    rng = random.Random(43)
    r = (rng.randrange(O.P), rng.randrange(O.P))
    cases = [  # (u0, u1) as 64-byte coefficient encodings
        ((0, 0), (0, 0)),
        ((O.P, 0), r),
        (r, (0, O.P << 130)),
        (((1 << 512) - 1, (1 << 512) - 1), (O.P - 1, 1)),
    ]
    uniform = b"".join(_draw64(c) for u0, u1 in cases for c in u0 + u1)
    got, status = native_bls.hash_to_g2(uniform, len(cases))
    assert status == 0
    draws = [(c0 % O.P, c1 % O.P) for u0, u1 in cases for c0, c1 in (u0, u1)]
    python = codec._hash_to_g2_draws_host(draws)
    for i, (x, y) in enumerate(python):
        want = _oracle_from_draws(draws[2 * i], draws[2 * i + 1])
        assert (x[0], x[1], y[0], y[1]) == want
        assert got[i].tobytes() == _limbs(*want)


# -- field entry points -------------------------------------------------------


def test_fq2_sqrt_root_choice():
    """The oracle's root CHOICE, not just +/- equivalence; None exactly
    where it returns None; the b == 0 branches included."""
    rng = random.Random(47)
    vals = []
    for _ in range(24):
        v = O.Fq2(rng.randrange(O.P), rng.randrange(O.P))
        vals += [v, v.square()]  # a non-residue half the time; a residue
    vals += [O.Fq2(a, 0) for a in (0, 1, 2, 5, O.P - 1)]
    vals += [O.Fq2(0, b) for b in (1, 3, O.P - 1)]
    got = native_bls.fp2_sqrt_batch([(v.c0, v.c1) for v in vals])
    for v, g in zip(vals, got):
        want = v.sqrt()
        assert g == (None if want is None else (want.c0, want.c1))
        assert g == codec._f2sqrt_int((v.c0, v.c1))
    assert any(g is None for g in got) and any(g is not None for g in got)


@pytest.mark.parametrize("zeros", ["none", "first", "middle", "last", "all"])
def test_batch_inverse_zero_lanes(zeros):
    rng = random.Random(53)
    vals = [1, O.P - 1] + [rng.randrange(1, O.P) for _ in range(29)]
    at = {"none": [], "first": [0], "middle": [7, 8, 20], "last": [30],
          "all": range(31)}[zeros]
    for i in at:
        vals[i] = 0
    got = native_bls.fp_batch_inverse(vals)
    assert got == codec.int_batch_inverse(vals)
    assert got == [pow(v, O.P - 2, O.P) if v else 0 for v in vals]


# -- decoding and subgroup checks --------------------------------------------


def _off_curve_x(on_curve, start: int) -> int:
    x = start
    while on_curve(x):
        x += 1
    return x


def _g1_on_curve(x: int) -> bool:
    return O.fq_sqrt((x * x % O.P * x + 4) % O.P) is not None


def _g2_on_curve(c0: int) -> bool:
    x = O.Fq2(c0, 1)
    return (x * x * x + O.B_G2).sqrt() is not None


def _rand_g1(rng):
    while True:
        x = rng.randrange(O.P)
        y = O.fq_sqrt((x * x % O.P * x + 4) % O.P)
        if y is not None:
            return O.ec_from_affine((O.Fq(x), O.Fq(y)))


def _rand_g2(rng):
    while True:
        x = O.Fq2(rng.randrange(O.P), rng.randrange(O.P))
        y = (x * x * x + O.B_G2).sqrt()
        if y is not None:
            return O.ec_from_affine((x, y))


def _pool_g1():
    rng = random.Random(59)
    blobs = [O.g1_to_bytes(O.ec_mul(O.G1_GEN, rng.randrange(1, O.R)))
             for _ in range(8)]  # members, both sign flags w.h.p.
    blobs += [O.g1_to_bytes(_rand_g1(rng)) for _ in range(3)]  # non-members
    for _ in range(2):  # cofactor torsion: [r]T kills the G1 part only
        blobs.append(O.g1_to_bytes(O.ec_mul(_rand_g1(rng), O.R)))
    inf = bytes([0xC0]) + b"\x00" * 47
    blobs += [inf, inf[:1] + b"\x01" + inf[2:], bytes([0xE0]) + b"\x00" * 47]
    x_bad = _off_curve_x(_g1_on_curve, 5).to_bytes(48, "big")
    for sign in (0x00, 0x20):
        blobs.append(bytes([0x80 | sign | 0x1F]) + b"\xff" * 47)  # x >= p
        blobs.append(bytes([0x80 | sign]) + (O.P).to_bytes(48, "big")[1:])
        blobs.append(bytes([0x80 | sign]) + x_bad[1:])  # not on curve
    blobs += [b"\x00" * 48, O.g1_to_bytes(O.G1_GEN)[:47]]  # structural
    return blobs


def _pool_g2():
    rng = random.Random(61)
    blobs = [O.g2_to_bytes(O.ec_mul(O.G2_GEN, rng.randrange(1, O.R)))
             for _ in range(6)]
    blobs += [O.g2_to_bytes(_rand_g2(rng)) for _ in range(3)]
    blobs.append(O.g2_to_bytes(O.ec_mul(_rand_g2(rng), O.R)))  # torsion
    inf = bytes([0xC0]) + b"\x00" * 95
    blobs += [inf, inf[:5] + b"\x01" + inf[6:], bytes([0xE0]) + b"\x00" * 95]
    x_bad = _off_curve_x(_g2_on_curve, 2)
    for sign in (0x00, 0x20):
        blobs.append(bytes([0x80 | sign | 0x1F]) + b"\xff" * 95)  # x.c1 >= p
        blobs.append(bytes([0x80 | sign]) + b"\x00" * 47
                     + O.P.to_bytes(48, "big"))  # x.c0 == p
        # x = x_bad + u: c1 = 1, c0 = x_bad
        blobs.append(bytes([0x80 | sign]) + (1).to_bytes(48, "big")[1:]
                     + x_bad.to_bytes(48, "big"))
    blobs += [b"\x34" * 96, O.g2_to_bytes(O.G2_GEN)[:95]]
    return blobs


@pytest.fixture(scope="module")
def pools():
    return {"g1": _pool_g1(), "g2": _pool_g2()}


def _norm(v):
    if isinstance(v, ValueError):
        return ("err", str(v))
    if v is None:
        return ("inf",)
    if isinstance(v, tuple):
        return ("ok", tuple(np.asarray(x).tobytes() for x in v))
    return ("ok", np.asarray(v).tobytes())


def _results(fn, blobs):
    return [_norm(v) for v in fn(blobs)]


def _oracle(fn, blob):
    try:
        return _norm(fn(blob))
    except ValueError as e:  # the oracle's decoders raise
        return ("err", str(e))


@pytest.mark.parametrize("kind", ["g1", "g2"])
def test_decode_and_subgroup_native_python_oracle(kind, pools, monkeypatch):
    blobs = pools[kind]
    batch, oracle = {
        "g1": (codec.pubkey_limbs_batch, B._pubkey_limbs_compute),
        "g2": (codec.signature_limbs_batch, B._signature_limbs_compute),
    }[kind]
    assert codec.host_route() == "native"
    native = _results(batch, blobs)
    assert [_oracle(oracle, b) for b in blobs] == native
    monkeypatch.setattr(native_bls, "_lib", False)
    assert _results(batch, blobs) == native
    # every rejection class is in the pool
    errors = {v[1] for v in native if v[0] == "err"}
    tag = kind.upper()
    subject = "pubkey" if kind == "g1" else "signature"
    assert {f"{tag} x out of range", f"{tag} x not on curve",
            "invalid infinity encoding", f"{subject} not in {tag} subgroup",
            f"{subject} is the point at infinity"} <= errors


@pytest.mark.parametrize("kind", ["g1", "g2"])
def test_decompress_alone_matches_python_path(kind, pools, monkeypatch):
    """The decode entry points on their own (infinity stays None, no
    subgroup verdict)."""
    blobs = pools[kind]
    fn = {"g1": codec.decompress_g1_batch,
          "g2": codec.decompress_g2_batch}[kind]
    native = _results(fn, blobs)
    monkeypatch.setattr(native_bls, "_lib", False)
    assert _results(fn, blobs) == native
    assert ("inf",) in native


# -- loader and route counter -------------------------------------------------


def test_without_the_library_the_python_path_runs_and_counts(
        python_route, h2g_python, pools, monkeypatch):
    for k in ("native_items", "host_python_items"):
        monkeypatch.setitem(B.PREP_STATS, k, 0)
    got = codec.message_limbs_batch(_MSGS[:3], DST)
    assert [g.tobytes() for g in got] == h2g_python[:3]
    codec.signature_limbs_batch(pools["g2"][:4])
    codec.pubkey_limbs_batch(pools["g1"][:5])
    assert B.PREP_STATS["host_python_items"] == 12
    assert B.PREP_STATS["native_items"] == 0


def test_native_route_counts(pools, monkeypatch):
    for k in ("native_items", "host_python_items"):
        monkeypatch.setitem(B.PREP_STATS, k, 0)
    codec.message_limbs_batch(_MSGS[:3], DST)
    codec.signature_limbs_batch(pools["g2"][:4])
    codec.pubkey_limbs_batch(pools["g1"][:5])
    assert B.PREP_STATS["native_items"] == 12
    assert B.PREP_STATS["host_python_items"] == 0
    from consensus_specs_tpu.ops import profiling

    _, gauges = profiling.stats_and_gauges()
    assert gauges["bls.prep_native_items"] == 12


def _loader(monkeypatch, so, src):
    monkeypatch.setattr(native_bls, "_SO", so)
    monkeypatch.setattr(native_bls, "_SRC", src)
    monkeypatch.setattr(native_bls, "_lib", None)
    return native_bls.available()


def test_loader_binds_a_built_library(tmp_path, monkeypatch):
    so = tmp_path / "libbls_host.so"
    shutil.copy(native_bls._SO, so)
    assert _loader(monkeypatch, so, tmp_path / "absent.c")
    assert codec.host_route() == "native"


def test_loader_without_library_or_source(tmp_path, monkeypatch):
    assert not _loader(monkeypatch, tmp_path / "absent.so",
                       tmp_path / "absent.c")
    assert codec.host_route() == "python"


def test_loader_refuses_a_library_missing_entry_points(tmp_path, monkeypatch):
    """A library without the kernel's symbols, and no source to rebuild
    from: unavailable, not half-bound."""
    assert native_sha256.available()
    so = tmp_path / "libbls_host.so"
    shutil.copy(native_sha256._SO, so)
    assert not _loader(monkeypatch, so, tmp_path / "absent.c")
