"""Plain BLS signature semantics over the frozen reference curve.

The proof-of-possession ciphersuite the consensus specs use
(``BLS_SIG_BLS12381G2_XMD:SHA-256_SSWU_RO_POP_``): keys, signing, and
FastAggregateVerify written the straightforward way, one check at a time:
aggregate the public keys, hash the message to G2, and test
``e(aggregate, H(m)) * e(-G1, signature) == 1``. Imports nothing of the
program under test.
"""
from . import bls12_381 as C

DST = b"BLS_SIG_BLS12381G2_XMD:SHA-256_SSWU_RO_POP_"
_NEG_G1 = C.ec_to_affine(C.ec_neg(C.G1_GEN))
_ONE = C.Fq12.one()


def pubkey(sk: int):
    """(x, y, compressed bytes) of ``sk * G1``."""
    x, y = C.ec_to_affine(C.ec_mul(C.G1_GEN, sk))
    return x.n, y.n, C.g1_to_bytes((x, y))


def sign(sk: int, message: bytes) -> bytes:
    h = C.hash_to_g2(message, DST)
    return C.g2_to_bytes(C.ec_to_affine(C.ec_mul(h, sk % C.R)))


def fast_aggregate_verify(points, pk_bytes, message: bytes,
                          signature: bytes) -> bool:
    """FastAggregateVerify over keys the generator made: ``points`` are
    their affine coordinates, ``pk_bytes`` the encodings the program was
    handed, which must encode exactly those points."""
    if not points or len(points) != len(pk_bytes):
        return False
    acc = None
    for (x, y), enc in zip(points, pk_bytes):
        aff = (C.Fq(x), C.Fq(y))
        if C.g1_to_bytes(aff) != enc:
            return False
        acc = C.ec_add(acc, C.ec_from_affine(aff))
    if acc is None:
        return False
    try:
        sig = C.g2_from_bytes(signature)
    except ValueError:
        return False
    if sig is None or not C.is_in_g2_subgroup(C.ec_from_affine(sig)):
        return False
    h = C.ec_to_affine(C.hash_to_g2(message, DST))
    return C.multi_pairing([(C.ec_to_affine(acc), h), (_NEG_G1, sig)]) == _ONE
