"""The keys of a registry held whole, as ``generate.Keys.derive`` makes
them: a scalar multiplication a key would take minutes at 1M, but
consecutive secret keys ``((i + 1) << 16) | salt`` differ by 2^16, so each
chunk of indices starts from one scalar multiplication and steps by
2^16 G (``_pubkey_run``).

Pure Python and no JAX, like ``generate``, so its spawned workers never
reach for the chip."""
from .reference import bls as ref
from .reference import bls12_381 as C
from .reference.bls12_381 import P


def derive_all(keys, n: int, pool, chunk: int = 1 << 14) -> None:
    """Fill ``keys`` (a ``generate.Keys``) with validators 0 .. n - 1."""
    runs = [(keys.salt, lo, min(lo + chunk, n)) for lo in range(0, n, chunk)]
    for (_, lo, hi), part in zip(runs, pool.imap(_pubkey_run, runs)):
        for i, (x, y, enc) in zip(range(lo, hi), part):
            keys.points[i] = (x, y)
            keys.encoded[i] = enc


def _pubkey_run(args):
    """``ref.pubkey`` of the keys ``((i + 1) << 16) | salt`` for ``lo <= i <
    hi``: one scalar multiplication, then pk_i = pk_{i-1} + 2^16 G by mixed
    Jacobian additions in plain ints, then one batch inversion to affine
    and the reference's own compression."""
    salt, lo, hi = args
    sx, sy, _ = ref.pubkey(1 << 16)
    x, y, _ = ref.pubkey(((lo + 1) << 16) | salt)
    pts = [(x, y, 1)]
    for i in range(lo + 1, hi):
        X1, Y1, Z1 = pts[-1]
        z2 = Z1 * Z1 % P
        h = (sx * z2 - X1) % P
        r = (sy * Z1 * z2 - Y1) % P
        if h == 0:  # the step meets the point itself: no addition formula
            x, y, _ = ref.pubkey(((i + 1) << 16) | salt)
            pts.append((x, y, 1))
            continue
        hh = h * h % P
        hhh = h * hh % P
        v = X1 * hh % P
        X3 = (r * r - hhh - 2 * v) % P
        pts.append((X3, (r * (v - X3) - Y1 * hhh) % P, Z1 * h % P))
    # Montgomery's batch inversion of the z's
    acc, pref = 1, []
    for _, _, z in pts:
        pref.append(acc)
        acc = acc * z % P
    inv = pow(acc, P - 2, P)
    out = [None] * len(pts)
    for j in range(len(pts) - 1, -1, -1):
        X, Y, Z = pts[j]
        zi = inv * pref[j] % P
        inv = inv * Z % P
        zi2 = zi * zi % P
        ax, ay = X * zi2 % P, Y * zi2 * zi % P
        out[j] = (ax, ay, C.g1_to_bytes((C.Fq(ax), C.Fq(ay))))
    return out
