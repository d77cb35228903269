"""Batched input codec plane: array-wide host prep for the BLS pipeline.

The device pairing plane used to be starved by its own front door: every
cache-missed input paid ~29 ms of per-item pure-Python hash-to-G2 plus
~8 ms of per-item decode+subgroup work (serialized, or pushed through a
fragile process pool) before a single byte reached the VM. This module
replaces that per-item prep with BATCHED passes, the preprocessing cost
arXiv:2302.00418 identifies as the dominant term of committee-scale BLS
verification:

- **G1/G2 decompression**: vectorized limb decode (numpy bit unpack, no
  per-item bigint parsing), then ONE shared square-root exponentiation
  chain per batch — `fq.pow_fixed` scans the 380 static exponent bits once
  over the whole (N, L) limb array instead of running N pure-Python
  `pow()` calls — and sign selection by vectorized limb compares.
- **Montgomery batch inversion**: `fq_batch_inverse` is the classic
  product ladder (two associative scans + ONE Fermat chain for the entire
  batch + two multiplies per element, `inv(0) == 0` preserved). It backs
  every division in the plane: the complex-method Fq2 square root, SSWU's
  `1/tv2`, and the final projective->affine conversion.
- **Subgroup checks**: VM programs (`ops/vmlib.py`), so they run on device
  alongside the pairings — G2 via the psi-endomorphism criterion
  (utils/bls12_381.py is_in_g2_subgroup), G1 via the definitional [r]P
  ladder — both with complete (branchless) projective additions over a
  static bit schedule.
- **hash-to-G2**: `expand_message_xmd` runs through the native batched
  SHA-256 (`csrc/sha256_batch.c` `sha256_hash_many`, one C call per XMD
  round for the whole batch); the SSWU map runs as batched field kernels
  on host (its square-root branch is data-dependent — the one part of the
  pipeline a select-free VM cannot express); the isogeny evaluation,
  point addition, and cofactor clearing — the bulk of the field work —
  are lowered to the `h2g_finish` VM program.

By default the same algorithms run as a class-free raw-int host path
instead, on every backend — see the "host (CPU-fallback) batched path"
section below for why and what stays batched there.
`CONSENSUS_SPECS_TPU_CODEC_DEVICE=1` opts into the device placement off
the TPU; on the TPU it raises (`_use_device`): the route is not brought
up there.

Every path is gated by oracle-equivalence tests (tests/test_codec.py)
against `utils/bls12_381.py`, bit-identical including invalid encodings,
non-subgroup points, and infinity — the pure-Python `hash_to_g2` stays
the cross-check oracle, never the serving path.
"""
import os
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..utils import bls12_381 as O
from ..utils import native_sha256
from ..utils.bls12_381 import P
from . import fq, vm
from . import towers as tw

# ---------------------------------------------------------------------------
# constants (host numpy; canonical Montgomery limbs unless noted)
# ---------------------------------------------------------------------------

_SQRT_BITS = [int(b) for b in bin((P + 1) // 4)[2:]]  # p = 3 mod 4 sqrt chain
_L = fq.NUM_LIMBS
# raw-limb constant c = R^2 mod p: mont_mul(x_raw, c) == x*R == repr(x)
_R2_J = jnp.asarray(fq._int_to_limbs_np((fq.R_MONT * fq.R_MONT) % P))
_P_LIMBS = fq._int_to_limbs_np(P)
_HALF_LIMBS = fq._int_to_limbs_np((P - 1) // 2)  # sign threshold
_FOUR_J = jnp.asarray(fq.to_mont_int(4))  # b on G1
_B_G2_J = jnp.asarray(np.stack([fq.to_mont_int(4), fq.to_mont_int(4)]))
_INV2_J = jnp.asarray(fq.to_mont_int(pow(2, P - 2, P)))
_ONE_J = jnp.asarray(fq.ONE_MONT)
_ONE_RAW_J = jnp.asarray(fq._int_to_limbs_np(1))


def _fq2_const_np(x: "O.Fq2") -> np.ndarray:
    return np.stack([fq.to_mont_int(x.c0), fq.to_mont_int(x.c1)])


_SSWU_A_J = jnp.asarray(_fq2_const_np(O.SSWU_A))
_SSWU_B_J = jnp.asarray(_fq2_const_np(O.SSWU_B))
_SSWU_Z_J = jnp.asarray(_fq2_const_np(O.SSWU_Z))
_NEG_B_OVER_A_J = jnp.asarray(
    _fq2_const_np((-O.SSWU_B) * O.SSWU_A.inverse())
)
_X1_EXC_J = jnp.asarray(
    _fq2_const_np(O.SSWU_B * (O.SSWU_Z * O.SSWU_A).inverse())
)
_ONE2_J = jnp.asarray(np.stack([fq.ONE_MONT, fq._int_to_limbs_np(0)]))

_G2_COMPS = ("x.0", "x.1", "y.0", "y.1")


# ---------------------------------------------------------------------------
# vectorized limb decode + limb compares (host numpy)
# ---------------------------------------------------------------------------


def bytes_be_to_limbs(arr: np.ndarray) -> np.ndarray:
    """(N, nbytes) big-endian byte matrix -> (N, NUM_LIMBS) raw 28-bit
    limbs, fully vectorized (bit unpack + weighted fold; no per-item
    bigint parse). nbytes*8 must fit the 420-bit limb capacity."""
    n, nb = arr.shape
    assert nb * 8 <= _L * fq.LIMB_BITS
    bits = np.unpackbits(arr, axis=1, bitorder="big")[:, ::-1]  # LSB-first
    total = _L * fq.LIMB_BITS
    bits = np.pad(bits, ((0, 0), (0, total - bits.shape[1])))
    bits = bits.reshape(n, _L, fq.LIMB_BITS).astype(np.uint64)
    weights = np.uint64(1) << np.arange(fq.LIMB_BITS, dtype=np.uint64)
    return (bits * weights).sum(axis=2, dtype=np.uint64)


def _limbs_cmp_const(a: np.ndarray, c_limbs: np.ndarray, gt: bool
                     ) -> np.ndarray:
    """Vectorized lexicographic a > c (gt=True) or a < c (gt=False) for
    canonical-limb arrays, msb limb first. a: (N, L); c_limbs: (L,)."""
    n = a.shape[0]
    res = np.zeros(n, dtype=bool)
    eq = np.ones(n, dtype=bool)
    for k in reversed(range(a.shape[1])):
        ck = c_limbs[k]
        res |= eq & ((a[:, k] > ck) if gt else (a[:, k] < ck))
        eq &= a[:, k] == ck
    return res


def _limbs_lt_const(a: np.ndarray, c_limbs: np.ndarray) -> np.ndarray:
    return _limbs_cmp_const(a, c_limbs, gt=False)


def _limbs_gt_const(a: np.ndarray, c_limbs: np.ndarray) -> np.ndarray:
    return _limbs_cmp_const(a, c_limbs, gt=True)


def _sign_is_large_fq(y: np.ndarray) -> np.ndarray:
    """Vectorized _fq_sign_is_large: y > (p-1)/2 on RAW (non-Montgomery)
    canonical limbs."""
    return _limbs_gt_const(y, _HALF_LIMBS)


def _sign_is_large_fq2(y: np.ndarray) -> np.ndarray:
    """Vectorized _fq2_sign_is_large: lexicographic (c1, c0) > (-c1, -c0).
    y: (N, 2, L) RAW canonical. c1 > (p-1)/2, or c1 == 0 and c0 > (p-1)/2."""
    c0, c1 = y[:, 0], y[:, 1]
    c1_zero = ~c1.any(axis=1)
    return _limbs_gt_const(c1, _HALF_LIMBS) | (
        c1_zero & _limbs_gt_const(c0, _HALF_LIMBS)
    )


def _pad_batch(arr: np.ndarray) -> np.ndarray:
    """Pad the leading axis to a power of two (jit shape bucketing); the
    filler rows are zeros — every kernel either masks them or their
    outputs are sliced away."""
    from . import bls_backend  # shared shape-bucketing helper

    n = arr.shape[0]
    nb = bls_backend._pow2(max(1, n))
    if nb == n:
        return arr
    out = np.zeros((nb,) + arr.shape[1:], dtype=arr.dtype)
    out[:n] = arr
    return out


# ---------------------------------------------------------------------------
# Montgomery batch inversion (the ladder) + shared field kernels
# ---------------------------------------------------------------------------


def _fq_batch_inverse(a):
    """Montgomery batch-inversion ladder over the leading axis: two
    associative prefix/suffix product scans, ONE Fermat chain for the whole
    batch, then two multiplies per element. inv(0) == 0 (matching fq.inv
    and the oracle), zero lanes masked out of the ladder."""
    zero = fq.is_zero(a)
    one = jnp.broadcast_to(_ONE_J, a.shape)
    safe = fq.select(zero, one, a)
    pref = jax.lax.associative_scan(fq.mont_mul, safe, axis=0)
    suff = jax.lax.associative_scan(fq.mont_mul, safe, axis=0, reverse=True)
    total_inv = fq.inv(pref[-1])  # the batch's single inversion chain
    left = jnp.concatenate([one[:1], pref[:-1]], axis=0)
    right = jnp.concatenate([suff[1:], one[:1]], axis=0)
    out = fq.mont_mul(fq.mont_mul(left, right), total_inv)
    return fq.select(zero, jnp.zeros_like(a), out)


def _fq2_batch_inverse(a):
    """(a0 + a1 u)^-1 = conj / norm with the norms inverted through ONE
    shared ladder. a: (N, 2, L); inv(0) == 0."""
    a0, a1 = a[..., 0, :], a[..., 1, :]
    norm = fq.add(fq.mont_mul(a0, a0), fq.mont_mul(a1, a1))
    ni = _fq_batch_inverse(norm)
    return jnp.stack(
        [fq.mont_mul(a0, ni), fq.neg(fq.mont_mul(a1, ni))], axis=-2
    )


def _fq2_sqrt(v):
    """Batched Fq2 square root, complex method, replicating the oracle's
    Fq2.sqrt root CHOICE exactly (so outputs are bit-identical, not merely
    +/- equivalent). v: (N, 2, L), loose ok. Returns (root canonical
    (N, 2, L), ok (N,)) — ok False exactly where the oracle returns None.
    All square-root attempts are shared pow_fixed chains over the whole
    batch; the one division (b / 2x0) rides the batch-inversion ladder."""
    a, b = v[..., 0, :], v[..., 1, :]
    norm = fq.add(fq.mont_mul(a, a), fq.mont_mul(b, b))
    alpha = fq.pow_fixed(norm, _SQRT_BITS)
    d1 = fq.mont_mul(fq.add(a, alpha), _INV2_J)
    x0a = fq.pow_fixed(d1, _SQRT_BITS)
    ok_a = fq.eq(fq.mont_mul(x0a, x0a), d1)
    d2 = fq.mont_mul(fq.sub(a, alpha), _INV2_J)
    x0b = fq.pow_fixed(d2, _SQRT_BITS)
    x0 = fq.select(ok_a, x0a, x0b)
    x1 = fq.mont_mul(b, _fq_batch_inverse(fq.add(x0, x0)))
    # b == 0 lanes: (sqrt(a), 0) if a is a residue else (0, sqrt(-a))
    sa = fq.pow_fixed(a, _SQRT_BITS)
    ok_sa = fq.eq(fq.mont_mul(sa, sa), a)
    sna = fq.pow_fixed(fq.neg(a), _SQRT_BITS)
    zeros = jnp.zeros_like(a)
    b_zero = fq.is_zero(b)
    r0 = fq.select(b_zero, fq.select(ok_sa, sa, zeros), x0)
    r1 = fq.select(b_zero, fq.select(ok_sa, zeros, sna), x1)
    r = jnp.stack([fq.canonical(r0), fq.canonical(r1)], axis=-2)
    ok = tw.fq2_eq(tw.fq2_square(r), jnp.stack([a, b], axis=-2))
    return r, ok


@jax.jit
def _fq2_sqrt_kernel(v):
    return _fq2_sqrt(v)


@jax.jit
def _fq_batch_inverse_kernel(a):
    return _fq_batch_inverse(a)


@jax.jit
def _g1_decode_kernel(x_raw):
    """(N, L) raw x limbs (< p) -> Montgomery x, candidate y, -y (all
    canonical), the RAW y value (for the host's sign compare) and the
    on-curve flag, via one shared sqrt chain."""
    x = fq.canonical(fq.mont_mul(x_raw, _R2_J))
    y2 = fq.add(fq.mont_mul(fq.mont_mul(x, x), x), _FOUR_J)
    cand = fq.pow_fixed(y2, _SQRT_BITS)
    ok = fq.eq(fq.mont_mul(cand, cand), y2)
    y = fq.canonical(cand)
    yneg = fq.canonical(fq.neg(y))
    return x, y, yneg, _demont(y), ok


@jax.jit
def _g2_decode_kernel(x_raw):
    """(N, 2, L) raw x limbs -> Montgomery x, candidate y, -y, RAW y, and
    the on-curve flag."""
    x = fq.canonical(fq.mont_mul(x_raw, _R2_J))
    x3 = tw.fq2_mul(tw.fq2_square(x), x)
    y2 = fq.add(x3, jnp.broadcast_to(_B_G2_J, x3.shape))
    y, ok = _fq2_sqrt(y2)
    yneg = jnp.stack(
        [fq.canonical(fq.neg(y[..., 0, :])), fq.canonical(fq.neg(y[..., 1, :]))],
        axis=-2,
    )
    y_raw = jnp.stack(
        [_demont(y[..., 0, :]), _demont(y[..., 1, :])], axis=-2
    )
    return x, y, yneg, y_raw, ok


def _demont(x):
    """Montgomery repr -> canonical RAW integer limbs. Sign and parity are
    properties of the VALUE — a Montgomery residue's limbs have unrelated
    parity — so every sgn0 / lexicographic-sign test goes through this."""
    r = fq.mont_mul(x, _ONE_RAW_J)  # v*R * 1 * R^-1 = v, < 2p
    return jnp.where(fq._geq_p(r)[..., None], fq._sub_p(r), r)


def _sgn0(v):
    """RFC 9380 sgn0 for Fq2 limb arrays (N, 2, L), Montgomery form in."""
    c0 = _demont(v[..., 0, :])
    c1 = _demont(v[..., 1, :])
    sign0 = (c0[..., 0] & jnp.uint64(1)).astype(bool)
    zero0 = jnp.all(c0 == 0, axis=-1)
    sign1 = (c1[..., 0] & jnp.uint64(1)).astype(bool)
    return sign0 | (zero0 & sign1)


def _gprime(x):
    """g'(x) = x^3 + A'x + B' on the SSWU isogenous curve."""
    x3 = tw.fq2_mul(tw.fq2_square(x), x)
    ax = tw.fq2_mul(jnp.broadcast_to(_SSWU_A_J, x.shape), x)
    return fq.add(fq.add(x3, ax), jnp.broadcast_to(_SSWU_B_J, x3.shape))


@jax.jit
def _sswu_map_kernel(u):
    """Batched simplified SWU onto the isogenous curve (oracle
    map_to_curve_sswu_g2), u: (N, 2, L) canonical -> (x, y, ok). The
    data-dependent sqrt branch becomes a lane select; both candidate
    square roots ride the shared chains."""
    u2 = tw.fq2_square(u)
    tv1 = tw.fq2_mul(jnp.broadcast_to(_SSWU_Z_J, u2.shape), u2)
    tv2 = fq.add(tw.fq2_square(tv1), tv1)
    tv2_zero = tw.fq2_is_zero(tv2)
    one2 = jnp.broadcast_to(_ONE2_J, tv2.shape)
    inv_tv2 = _fq2_batch_inverse(tw.fq2_select(tv2_zero, one2, tv2))
    x1_gen = tw.fq2_mul(
        jnp.broadcast_to(_NEG_B_OVER_A_J, u2.shape), fq.add(one2, inv_tv2)
    )
    x1 = tw.fq2_select(tv2_zero, jnp.broadcast_to(_X1_EXC_J, u2.shape), x1_gen)
    gx1 = _gprime(x1)
    y1, ok1 = _fq2_sqrt(gx1)
    x2 = tw.fq2_mul(tv1, x1)
    gx2 = _gprime(x2)
    y2c, ok2 = _fq2_sqrt(gx2)
    x = tw.fq2_select(ok1, x1, x2)
    y = tw.fq2_select(ok1, y1, y2c)
    flip = _sgn0(u) != _sgn0(y)
    yneg = jnp.stack(
        [fq.canonical(fq.neg(y[..., 0, :])), fq.canonical(fq.neg(y[..., 1, :]))],
        axis=-2,
    )
    y = tw.fq2_select(flip, yneg, y)
    x = jnp.stack(
        [fq.canonical(x[..., 0, :]), fq.canonical(x[..., 1, :])], axis=-2
    )
    return x, y, ok1 | ok2


@jax.jit
def _proj_to_affine_kernel(X, Y, Z):
    """Projective (x = X/Z) -> affine, whole batch through one ladder."""
    zi = _fq2_batch_inverse(Z)
    x = tw.fq2_mul(X, zi)
    y = tw.fq2_mul(Y, zi)
    return (
        jnp.stack([fq.canonical(x[..., 0, :]), fq.canonical(x[..., 1, :])], axis=-2),
        jnp.stack([fq.canonical(y[..., 0, :]), fq.canonical(y[..., 1, :])], axis=-2),
    )


@jax.jit
def _is_zero_kernel(a):
    return fq.is_zero(a)


# public, test-facing wrappers ------------------------------------------------


def fq_batch_inverse(a) -> np.ndarray:
    """Batch inversion ladder (Montgomery form in/out, inv(0) == 0)."""
    return np.asarray(_fq_batch_inverse_kernel(jnp.asarray(a)))


def fq2_sqrt_batch(v) -> Tuple[np.ndarray, np.ndarray]:
    """Batched Fq2 sqrt; returns (roots (N,2,L) canonical, ok (N,))."""
    r, ok = _fq2_sqrt_kernel(jnp.asarray(v))
    return np.asarray(r), np.asarray(ok)


# ---------------------------------------------------------------------------
# VM-program subgroup checks + hash finish
# ---------------------------------------------------------------------------


def _layout(kind: str, n_items: int, mesh):
    from . import bls_backend  # lazy: bls_backend lazily imports codec back

    return bls_backend._FoldLayout(kind, 0, n_items, mesh)


def g1_subgroup_check_batch(points: np.ndarray, mesh=None) -> np.ndarray:
    """points: (M, 2, L) canonical affine (ON the curve) -> bool (M,).
    Device: the [r]P complete-addition ladder as a VM program. CPU
    fallback: the same ladder on raw ints."""
    m = points.shape[0]
    if m == 0:
        return np.zeros(0, dtype=bool)
    if not _use_device():
        pts = [
            (fq.from_mont_limbs(points[i, 0]), fq.from_mont_limbs(points[i, 1]))
            for i in range(m)
        ]
        return np.asarray(_g1_subgroup_host(pts), dtype=bool)
    lay = _layout("g1_subgroup", m, mesh)
    arr = np.zeros((lay.nb, 2, _L), dtype=np.uint64)
    arr[:m] = points
    ins: Dict[str, np.ndarray] = {}
    lay.scatter(ins, arr, lambda c: f"pt.{'xy'[c]}")
    out = vm.execute(lay.program, ins, batch_shape=(lay.rows,), mesh=mesh)
    rz = np.zeros((m, _L), dtype=np.uint64)
    for i in range(m):
        r, ns = lay.split(i)
        rz[i] = out[f"{ns}rz"][r]
    return np.asarray(_is_zero_kernel(jnp.asarray(rz)))


def g2_subgroup_check_batch(points: np.ndarray, mesh=None) -> np.ndarray:
    """points: (M, 4, L) canonical affine [x.0, x.1, y.0, y.1] (ON the
    curve) -> bool (M,). Device: the psi-criterion VM program. CPU
    fallback: the same criterion on raw ints."""
    m = points.shape[0]
    if m == 0:
        return np.zeros(0, dtype=bool)
    if not _use_device():
        pts = [
            (
                (fq.from_mont_limbs(points[i, 0]),
                 fq.from_mont_limbs(points[i, 1])),
                (fq.from_mont_limbs(points[i, 2]),
                 fq.from_mont_limbs(points[i, 3])),
            )
            for i in range(m)
        ]
        return np.asarray(_g2_subgroup_host(pts), dtype=bool)
    lay = _layout("g2_subgroup", m, mesh)
    arr = np.zeros((lay.nb, 4, _L), dtype=np.uint64)
    arr[:m] = points
    ins: Dict[str, np.ndarray] = {}
    lay.scatter(ins, arr, lambda c: f"pt.{_G2_COMPS[c]}")
    out = vm.execute(lay.program, ins, batch_shape=(lay.rows,), mesh=mesh)
    d = np.zeros((m, 4, _L), dtype=np.uint64)
    for i in range(m):
        r, ns = lay.split(i)
        for j in range(4):
            d[i, j] = out[f"{ns}d.{j}"][r]
    return np.asarray(_is_zero_kernel(jnp.asarray(d))).all(axis=1)


def _h2g_finish_batch(q0: np.ndarray, q1: np.ndarray, mesh=None) -> np.ndarray:
    """(M, 4, L) SSWU outputs q0, q1 -> (M, 4, L) hashed affine G2 points
    (isogeny + add + clear-cofactor on device, one affine ladder on host)."""
    m = q0.shape[0]
    lay = _layout("h2g_finish", m, mesh)
    a0 = np.zeros((lay.nb, 4, _L), dtype=np.uint64)
    a1 = np.zeros((lay.nb, 4, _L), dtype=np.uint64)
    a0[:m] = q0
    a1[:m] = q1
    ins: Dict[str, np.ndarray] = {}
    lay.scatter(ins, a0, lambda c: f"q0.{_G2_COMPS[c]}")
    lay.scatter(ins, a1, lambda c: f"q1.{_G2_COMPS[c]}")
    out = vm.execute(lay.program, ins, batch_shape=(lay.rows,), mesh=mesh)
    proj = np.zeros((m, 3, 2, _L), dtype=np.uint64)
    for i in range(m):
        r, ns = lay.split(i)
        for ci, cname in enumerate(("x", "y", "z")):
            proj[i, ci, 0] = out[f"{ns}h.{cname}.0"][r]
            proj[i, ci, 1] = out[f"{ns}h.{cname}.1"][r]
    x, y = _proj_to_affine_kernel(
        jnp.asarray(proj[:, 0]), jnp.asarray(proj[:, 1]), jnp.asarray(proj[:, 2])
    )
    x, y = np.asarray(x), np.asarray(y)
    return np.concatenate([x, y], axis=1)  # (M, 4, L)


# ---------------------------------------------------------------------------
# batched expand_message_xmd / hash_to_field (native SHA-256)
# ---------------------------------------------------------------------------


def expand_message_xmd_batch(
    messages: Sequence[bytes], dst: bytes, len_in_bytes: int
) -> List[bytes]:
    """RFC 9380 expand_message_xmd over a whole batch: one native SHA call
    per XMD round (1 + ell calls total) instead of per-message hashlib."""
    if len(dst) > 255:
        raise ValueError("DST too long")
    ell = (len_in_bytes + 31) // 32
    if ell > 255:
        raise ValueError("len_in_bytes too large")
    n = len(messages)
    if n == 0:
        return []
    dst_prime = dst + bytes([len(dst)])
    z_pad = b"\x00" * 64
    l_i_b = len_in_bytes.to_bytes(2, "big")
    b0 = native_sha256.hash_many(
        [z_pad + bytes(m) + l_i_b + b"\x00" + dst_prime for m in messages]
    )
    b0_arr = np.frombuffer(b"".join(b0), dtype=np.uint8).reshape(n, 32)
    prev = native_sha256.hash_many([d + b"\x01" + dst_prime for d in b0])
    rounds = [prev]
    for i in range(2, ell + 1):
        prev_arr = np.frombuffer(b"".join(prev), dtype=np.uint8).reshape(n, 32)
        xored = (b0_arr ^ prev_arr).tobytes()
        suffix = bytes([i]) + dst_prime
        prev = native_sha256.hash_many(
            [xored[32 * j : 32 * (j + 1)] + suffix for j in range(n)]
        )
        rounds.append(prev)
    return [
        b"".join(r[j] for r in rounds)[:len_in_bytes] for j in range(n)
    ]


def hash_to_field_fq2_batch(
    messages: Sequence[bytes], count: int, dst: bytes
) -> np.ndarray:
    """(N, count, 2, L) canonical Montgomery field draws (oracle
    hash_to_field_fq2 per message, batched through the native expander)."""
    len_in_bytes = count * 2 * O.L_FIELD
    uniform = expand_message_xmd_batch(messages, dst, len_in_bytes)
    n = len(messages)
    out = np.zeros((n, count, 2, _L), dtype=np.uint64)
    for i, u in enumerate(uniform):
        for c in range(count):
            for j in range(2):
                off = O.L_FIELD * (j + c * 2)
                out[i, c, j] = fq.to_mont_int(
                    int.from_bytes(u[off : off + O.L_FIELD], "big") % P
                )
    return out


def hash_to_g2_batch(
    messages: Sequence[bytes], dst: bytes, mesh=None
) -> np.ndarray:
    """Batched RFC 9380 hash_to_curve: returns (N, 4, L) canonical affine
    G2 limb stacks, bit-identical to
    ec_to_affine(oracle.hash_to_g2(msg, dst)) per message."""
    n = len(messages)
    if n == 0:
        return np.zeros((0, 4, _L), dtype=np.uint64)
    if not _use_device():
        out = np.zeros((n, 4, _L), dtype=np.uint64)
        for i, (x, y) in enumerate(_hash_to_g2_host(messages, dst)):
            out[i, 0] = fq.to_mont_int(x[0])
            out[i, 1] = fq.to_mont_int(x[1])
            out[i, 2] = fq.to_mont_int(y[0])
            out[i, 3] = fq.to_mont_int(y[1])
        return out
    us = hash_to_field_fq2_batch(messages, 2, dst)  # (n, 2, 2, L)
    u_all = np.concatenate([us[:, 0], us[:, 1]], axis=0)  # (2n, 2, L)
    x, y, ok = _sswu_map_kernel(jnp.asarray(_pad_batch(u_all)))
    x, y, ok = np.asarray(x), np.asarray(y), np.asarray(ok)
    assert ok[: 2 * n].all(), "SSWU: no square root found"  # oracle parity
    q = np.concatenate([x[: 2 * n], y[: 2 * n]], axis=1)  # (2n, 4, L)
    return _h2g_finish_batch(q[:n], q[n : 2 * n], mesh=mesh)


# ---------------------------------------------------------------------------
# host (CPU-fallback) batched path: class-free Python ints
# ---------------------------------------------------------------------------
# The jax field kernels and VM programs above are the serving path on a
# real accelerator, where wide limb arithmetic is effectively free. On the
# CPU fallback the same limb math is compute-bound (hundreds of ms per
# item through XLA:CPU) while CPython's bignum pow/mulmod is microseconds
# — so the host path runs the SAME algorithms on raw ints, batched where
# batching actually pays on a CPU: one native SHA-256 call per
# expand_message_xmd round for the whole batch, one Fermat inversion
# ladder (int_batch_inverse) shared by every division in a pass, and
# class-free Jacobian ladders (~3x the oracle's Fq/Fq2-object path, which
# spends most of its time on operator-dispatch overhead). Outputs are
# bit-identical to the oracle on both paths.


def _use_device() -> bool:
    """Codec field math placement: raw-int host math unless
    CONSENSUS_SPECS_TPU_CODEC_DEVICE=1 opts into the VM/jax programs
    (tests use it to exercise the device path on CPU). The device route
    is not brought up on the TPU, so opting in there is refused."""
    if os.environ.get("CONSENSUS_SPECS_TPU_CODEC_DEVICE", "0") != "1":
        return False
    if jax.default_backend() == "tpu":
        raise RuntimeError(
            "CONSENSUS_SPECS_TPU_CODEC_DEVICE=1: the codec device route is "
            "not brought up on the TPU. XLA's TPU compile of "
            "codec._sswu_map_kernel never finished (stalled 25 and 7 "
            "minutes on a v5e, PR 21); unset the variable to run the "
            "bit-identical host path.")
    return True


_X_ABS = 0xD201000000010000  # |x|, the BLS parameter magnitude
_P14 = (P + 1) // 4  # sqrt exponent, p = 3 mod 4
_HALF_INT = (P - 1) // 2  # lexicographic sign threshold
_PSI_CX_T = (O._PSI_CX.c0, O._PSI_CX.c1)
_PSI_CY_T = (O._PSI_CY.c0, O._PSI_CY.c1)
_ONE_T = (1, 0)


def int_batch_inverse(vals: Sequence[int]) -> List[int]:
    """Montgomery batch-inversion ladder on Python ints mod p: ONE Fermat
    exponentiation for the whole batch + 3 multiplies per element;
    inv(0) == 0 (zero lanes skipped, matching fq_batch_inverse)."""
    n = len(vals)
    out = [0] * n
    pref = [1] * n
    acc = 1
    for i, v in enumerate(vals):
        pref[i] = acc
        if v:
            acc = acc * v % P
    inv = pow(acc, -1, P)  # extgcd: ~60x cheaper than a Fermat pow here
    for i in range(n - 1, -1, -1):
        v = vals[i]
        if v:
            out[i] = inv * pref[i] % P
            inv = inv * v % P
    return out


# Fq2 as (c0, c1) int tuples, always reduced mod p ------------------------


def _f2add(a, b):
    return ((a[0] + b[0]) % P, (a[1] + b[1]) % P)


def _f2sub(a, b):
    return ((a[0] - b[0]) % P, (a[1] - b[1]) % P)


def _f2neg(a):
    return (-a[0] % P, -a[1] % P)


def _f2mul(a, b):
    a0, a1 = a
    b0, b1 = b
    t0 = a0 * b0
    t1 = a1 * b1
    return ((t0 - t1) % P, ((a0 + a1) * (b0 + b1) - t0 - t1) % P)


def _f2sqr(a):
    a0, a1 = a
    return ((a0 + a1) * (a0 - a1) % P, 2 * a0 * a1 % P)


def _f2sqrt_int(v):
    """Fq2 square root on int pairs, the oracle Fq2.sqrt complex method
    verbatim (same root choice); None iff the oracle returns None."""
    a, b = v
    if b == 0:
        s = O.fq_sqrt(a)
        if s is not None:
            return (s, 0)
        s = O.fq_sqrt(-a % P)
        if s is None:
            return None
        return (0, s)
    alpha = O.fq_sqrt((a * a + b * b) % P)
    if alpha is None:
        return None
    inv2 = (P + 1) // 2
    delta = (a + alpha) * inv2 % P
    x0 = O.fq_sqrt(delta)
    if x0 is None:
        delta = (a - alpha) % P * inv2 % P
        x0 = O.fq_sqrt(delta)
        if x0 is None:
            return None
    x1 = b * pow(2 * x0 % P, -1, P) % P
    cand = (x0, x1)
    if _f2sqr(cand) == v:
        return cand
    return None


# Jacobian point arithmetic (None is infinity), mirroring the oracle's
# ec_double / ec_add exactly — any correct formula yields the same affine
# result, but keeping the branch structure identical makes the U1==U2
# edge behavior (doubling / cancellation) trivially oracle-equal.


def _j1_dbl(p):
    if p is None:
        return None
    X, Y, Z = p
    A = X * X % P
    B = Y * Y % P
    C = B * B % P
    D = 2 * ((X + B) * (X + B) - A - C) % P
    E = 3 * A % P
    X3 = (E * E - 2 * D) % P
    Y3 = (E * (D - X3) - 8 * C) % P
    Z3 = 2 * Y * Z % P
    return (X3, Y3, Z3)


def _j1_add(p1, p2):
    if p1 is None:
        return p2
    if p2 is None:
        return p1
    X1, Y1, Z1 = p1
    X2, Y2, Z2 = p2
    Z1Z1 = Z1 * Z1 % P
    Z2Z2 = Z2 * Z2 % P
    U1 = X1 * Z2Z2 % P
    U2 = X2 * Z1Z1 % P
    S1 = Y1 * Z2 * Z2Z2 % P
    S2 = Y2 * Z1 * Z1Z1 % P
    if U1 == U2:
        if S1 == S2:
            return _j1_dbl(p1)
        return None
    H = (U2 - U1) % P
    I = 4 * H * H % P
    J = H * I % P
    rr = 2 * (S2 - S1) % P
    V = U1 * I % P
    X3 = (rr * rr - J - 2 * V) % P
    Y3 = (rr * (V - X3) - 2 * S1 * J) % P
    Z3 = ((Z1 + Z2) * (Z1 + Z2) - Z1Z1 - Z2Z2) % P * H % P
    return (X3, Y3, Z3)


def _j2_dbl(p):
    if p is None:
        return None
    X, Y, Z = p
    A = _f2sqr(X)
    B = _f2sqr(Y)
    C = _f2sqr(B)
    t = _f2sqr(_f2add(X, B))
    D = _f2add(_f2sub(_f2sub(t, A), C), _f2sub(_f2sub(t, A), C))
    E = ((3 * A[0]) % P, (3 * A[1]) % P)
    X3 = _f2sub(_f2sqr(E), _f2add(D, D))
    C8 = ((8 * C[0]) % P, (8 * C[1]) % P)
    Y3 = _f2sub(_f2mul(E, _f2sub(D, X3)), C8)
    Z3 = _f2mul(_f2add(Y, Y), Z)
    return (X3, Y3, Z3)


def _j2_add(p1, p2):
    if p1 is None:
        return p2
    if p2 is None:
        return p1
    X1, Y1, Z1 = p1
    X2, Y2, Z2 = p2
    Z1Z1 = _f2sqr(Z1)
    Z2Z2 = _f2sqr(Z2)
    U1 = _f2mul(X1, Z2Z2)
    U2 = _f2mul(X2, Z1Z1)
    S1 = _f2mul(_f2mul(Y1, Z2), Z2Z2)
    S2 = _f2mul(_f2mul(Y2, Z1), Z1Z1)
    if U1 == U2:
        if S1 == S2:
            return _j2_dbl(p1)
        return None
    H = _f2sub(U2, U1)
    I = _f2sqr(_f2add(H, H))
    J = _f2mul(H, I)
    rr = _f2add(_f2sub(S2, S1), _f2sub(S2, S1))
    V = _f2mul(U1, I)
    X3 = _f2sub(_f2sub(_f2sqr(rr), J), _f2add(V, V))
    SJ = _f2mul(S1, J)
    Y3 = _f2sub(_f2mul(rr, _f2sub(V, X3)), _f2add(SJ, SJ))
    Z3 = _f2mul(_f2sub(_f2sqr(_f2add(Z1, Z2)), _f2add(Z1Z1, Z2Z2)), H)
    return (X3, Y3, Z3)


def _j2_neg(p):
    if p is None:
        return None
    X, Y, Z = p
    return (X, _f2neg(Y), Z)


def _j2_mul(p, k: int):
    """LSB-first double-and-add, the oracle ec_mul schedule (k >= 0)."""
    result = None
    addend = p
    while k:
        if k & 1:
            result = _j2_add(result, addend)
        addend = _j2_dbl(addend)
        k >>= 1
    return result


def _j2_psi(p):
    """psi on Jacobian coords: conj is a field automorphism, so
    (X:Y:Z) -> (cx conj(X) : cy conj(Y) : conj(Z)) descends from the
    affine map (x, y) -> (cx conj(x), cy conj(y))."""
    if p is None:
        return None
    X, Y, Z = p
    return (
        _f2mul(_PSI_CX_T, (X[0], -X[1] % P)),
        _f2mul(_PSI_CY_T, (Y[0], -Y[1] % P)),
        (Z[0], -Z[1] % P),
    )


def _j1_mul(p, k: int):
    result = None
    addend = p
    while k:
        if k & 1:
            result = _j1_add(result, addend)
        addend = _j1_dbl(addend)
        k >>= 1
    return result


# beta: the primitive cube root of unity in Fq whose GLV endomorphism
# phi(x, y) = (beta*x, y) acts as [-z^2] on G1 (z = |BLS parameter|;
# verified against the generator in tests/test_codec.py)
_BETA_G1 = 0x5F19672FDF76CE51BA69C6076A0F77EADDB3A93BE6F89688DE17D813620A00022E01FFFFFFFEFFFE


def _g1_subgroup_host(pts: Sequence[Tuple[int, int]]) -> List[bool]:
    """GLV-endomorphism membership test on raw-int Jacobian ladders:
    P (on curve) is in G1 iff phi(P) == [-z^2]P, [z^2]P computed as two
    64-bit ladders [z]([z]P) — ~4x fewer point ops than the oracle's
    definitional 255-bit [r]P ladder, same verdict on EVERY curve point:
    phi^2 + phi + 1 == 0 holds identically on a j=0 curve ((x,y), (bx,y),
    (b^2 x,y) are collinear), so phi(P) = [-z^2]P forces [r]P = O."""
    out = []
    for x, y in pts:
        q = _j1_mul(_j1_mul((x, y, 1), _X_ABS), _X_ABS)
        if q is None:
            # ord(P) | z^2 and gcd(r, z^2) == 1: only infinity satisfies
            # both, so a finite P is a non-member
            out.append(False)
            continue
        Xq, Yq, Zq = q
        z2 = Zq * Zq % P
        z3 = z2 * Zq % P
        out.append(
            _BETA_G1 * x % P * z2 % P == Xq and (P - y) * z3 % P == Yq
        )
    return out


def _g2_subgroup_host(pts) -> List[bool]:
    """psi criterion on raw-int Jacobian: P in G2 iff psi(P) == -[|x|]P
    (the oracle is_in_g2_subgroup identity; psi acts as [x] on G2 and the
    BLS parameter x is negative), compared cross-multiplied so no
    inversion is needed anywhere."""
    out = []
    for x, y in pts:
        q = _j2_mul((x, y, _ONE_T), _X_ABS)
        if q is None:
            out.append(False)  # psi of a finite point is finite
            continue
        px = _f2mul(_PSI_CX_T, (x[0], -x[1] % P))
        py = _f2mul(_PSI_CY_T, (y[0], -y[1] % P))
        Xq, Yq, Zq = q
        z2 = _f2sqr(Zq)
        z3 = _f2mul(z2, Zq)
        out.append(
            _f2mul(px, z2) == Xq and _f2mul(py, z3) == _f2neg(Yq)
        )
    return out


def _decompress_g1_int(raw: bytes, sign_large: bool):
    """48 flag-stripped bytes -> (x, y) ints or the oracle's ValueError."""
    x = int.from_bytes(raw, "big")
    if x >= P:
        return ValueError("G1 x out of range")
    y2 = (x * x % P * x + 4) % P
    y = O.fq_sqrt(y2)
    if y is None:
        return ValueError("G1 x not on curve")
    if sign_large != (y > _HALF_INT):
        y = P - y
    return (x, y)


def _decompress_g2_int(raw1: bytes, raw0: bytes, sign_large: bool):
    """x.c1 / x.c0 bytes -> ((x0,x1), (y0,y1)) ints or the ValueError."""
    x1 = int.from_bytes(raw1, "big")
    x0 = int.from_bytes(raw0, "big")
    if x0 >= P or x1 >= P:
        return ValueError("G2 x out of range")
    x = (x0, x1)
    y2 = _f2add(_f2mul(_f2sqr(x), x), (4, 4))
    y = _f2sqrt_int(y2)
    if y is None:
        return ValueError("G2 x not on curve")
    is_large = y[1] > _HALF_INT or (y[1] == 0 and y[0] > _HALF_INT)
    if sign_large != is_large:
        y = _f2neg(y)
    return (x, y)


# SSWU / iso-map constants as int pairs (from the oracle's Fq2 objects)
def _t2(v: "O.Fq2") -> Tuple[int, int]:
    return (v.c0, v.c1)


_NEG_B_OVER_A_T = _t2((-O.SSWU_B) * O.SSWU_A.inverse())
_X1_EXC_T = _t2(O.SSWU_B * (O.SSWU_Z * O.SSWU_A).inverse())
_SSWU_A_T = (O.SSWU_A.c0, O.SSWU_A.c1)
_SSWU_B_T = (O.SSWU_B.c0, O.SSWU_B.c1)
_SSWU_Z_T = (O.SSWU_Z.c0, O.SSWU_Z.c1)
_ISO_X_NUM_T = [(c.c0, c.c1) for c in O.ISO_X_NUM]
_ISO_X_DEN_T = [(c.c0, c.c1) for c in O.ISO_X_DEN]
_ISO_Y_NUM_T = [(c.c0, c.c1) for c in O.ISO_Y_NUM]
_ISO_Y_DEN_T = [(c.c0, c.c1) for c in O.ISO_Y_DEN]


def _sgn0_t(v) -> int:
    return (v[0] % 2) or ((v[0] == 0) and (v[1] % 2))


def _horner_t(coeffs, x):
    acc = coeffs[-1]
    for c in reversed(coeffs[:-1]):
        acc = _f2add(_f2mul(acc, x), c)
    return acc


def _gprime_t(x):
    x3 = _f2mul(_f2sqr(x), x)
    return _f2add(_f2add(x3, _f2mul(_SSWU_A_T, x)), _SSWU_B_T)


def _hash_to_g2_host(messages: Sequence[bytes], dst: bytes):
    """Batched hash_to_g2 on raw ints: native batched SHA for the XMD
    stage, inline sqrts for SSWU (data-dependent, not batchable on a CPU),
    and ONE int_batch_inverse ladder each for the SSWU 1/tv2 divisions,
    the iso-map denominators, and the final Jacobian->affine conversion.
    Returns affine ((x0,x1),(y0,y1)) int pairs, oracle-identical."""
    n = len(messages)
    us = []  # 2n field draws, msg-major: [m0.u0, m0.u1, m1.u0, ...]
    len_in_bytes = 2 * 2 * O.L_FIELD
    for u in expand_message_xmd_batch(messages, dst, len_in_bytes):
        for c in range(2):
            off = O.L_FIELD * 2 * c
            us.append((
                int.from_bytes(u[off : off + O.L_FIELD], "big") % P,
                int.from_bytes(u[off + O.L_FIELD : off + 2 * O.L_FIELD],
                               "big") % P,
            ))
    # SSWU phase 1: tv1/tv2 for every draw, 1/tv2 through one ladder.
    # Fq2 inverse = conj/norm, norms inverted batch-wide (inv(0) unused:
    # tv2 == 0 lanes take the exceptional x1 and skip the division).
    tv1s, tv2s = [], []
    for u in us:
        tv1 = _f2mul(_SSWU_Z_T, _f2sqr(u))
        tv1s.append(tv1)
        tv2s.append(_f2add(_f2sqr(tv1), tv1))
    ninv = int_batch_inverse(
        [(t[0] * t[0] + t[1] * t[1]) % P for t in tv2s]
    )
    qs = []
    for u, tv1, tv2, ni in zip(us, tv1s, tv2s, ninv):
        if tv2 == (0, 0):
            x1 = _X1_EXC_T
        else:
            inv_tv2 = (tv2[0] * ni % P, -tv2[1] * ni % P)
            x1 = _f2mul(_NEG_B_OVER_A_T, _f2add(_ONE_T, inv_tv2))
        gx1 = _gprime_t(x1)
        y = _f2sqrt_int(gx1)
        if y is not None:
            x = x1
        else:
            x = _f2mul(tv1, x1)
            y = _f2sqrt_int(_gprime_t(x))
            if y is None:  # cannot happen for valid parameters
                raise ValueError("SSWU: no square root found")
        if _sgn0_t(u) != _sgn0_t(y):
            y = _f2neg(y)
        qs.append((x, y))
    # iso map: numerators/denominators for all draws, denominators through
    # one ladder (x_den and y_den interleaved in a single pass)
    dens = []
    nums = []
    for x, y in qs:
        xd = _horner_t(_ISO_X_DEN_T, x)
        yd = _horner_t(_ISO_Y_DEN_T, x)
        nums.append((_horner_t(_ISO_X_NUM_T, x),
                     _f2mul(y, _horner_t(_ISO_Y_NUM_T, x))))
        dens.extend([xd, yd])
    dinv = int_batch_inverse([(d[0] * d[0] + d[1] * d[1]) % P for d in dens])
    iso = []
    for j, (xn, yn) in enumerate(nums):
        xd, yd = dens[2 * j], dens[2 * j + 1]
        xdi = (xd[0] * dinv[2 * j] % P, -xd[1] * dinv[2 * j] % P)
        ydi = (yd[0] * dinv[2 * j + 1] % P, -yd[1] * dinv[2 * j + 1] % P)
        iso.append((_f2mul(xn, xdi), _f2mul(yn, ydi)))
    # add + clear cofactor (Budroni-Pintore psi decomposition, the oracle's
    # clear_cofactor_g2 schedule) on Jacobian ints
    accs = []
    for i in range(n):
        (x0, y0), (x1, y1) = iso[2 * i], iso[2 * i + 1]
        r = _j2_add((x0, y0, _ONE_T), (x1, y1, _ONE_T))
        t1 = _j2_mul(r, _X_ABS)            # [-x]P
        txx = _j2_mul(t1, _X_ABS)          # [x^2]P
        psi_p = _j2_psi(r)
        t2 = _j2_mul(psi_p, _X_ABS)        # [-x]psi(P)
        psi2_2p = _j2_psi(_j2_psi(_j2_dbl(r)))
        acc = _j2_add(txx, t1)
        acc = _j2_add(acc, _j2_neg(r))
        acc = _j2_add(acc, _j2_neg(t2))
        acc = _j2_add(acc, _j2_neg(psi_p))
        acc = _j2_add(acc, psi2_2p)
        if acc is None:  # not reachable: hash outputs are never infinity
            raise ValueError("hash_to_g2: point at infinity")
        accs.append(acc)
    # batched Jacobian -> affine: one ladder inverts every Z norm
    zinv = int_batch_inverse(
        [(z[0] * z[0] + z[1] * z[1]) % P for (_, _, z) in accs]
    )
    out = []
    for (X, Y, Z), ni in zip(accs, zinv):
        zi = (Z[0] * ni % P, -Z[1] * ni % P)
        zi2 = _f2sqr(zi)
        out.append((_f2mul(X, zi2), _f2mul(Y, _f2mul(zi2, zi))))
    return out


# ---------------------------------------------------------------------------
# batched decompression (ZCash format), oracle-exact rejection rules
# ---------------------------------------------------------------------------


def _parse_g1(blobs: Sequence[bytes]):
    """Shared flag/length validation for 48-byte compressed G1 blobs.
    Returns (res, live, raw_bytes, flags_sign): res pre-filled with the
    oracle's exact ValueErrors / None-for-infinity; live holds the indices
    whose x field still needs field math (device or host path)."""
    n = len(blobs)
    res: List[object] = [None] * n
    live: List[int] = []
    raw_bytes: List[bytes] = []
    flags_sign: List[bool] = []
    for i, data in enumerate(blobs):
        data = bytes(data)
        if len(data) != 48:
            res[i] = ValueError("G1 point must be 48 bytes")
            continue
        flags = data[0]
        if not (flags & O.FLAG_COMPRESSED):
            res[i] = ValueError("uncompressed G1 encoding not supported")
            continue
        if flags & O.FLAG_INFINITY:
            if (flags & O.FLAG_SIGN) or any(
                b for b in bytes([data[0] & 0x1F]) + data[1:]
            ):
                res[i] = ValueError("invalid infinity encoding")
            # else: infinity -> None, already the default
            continue
        live.append(i)
        raw_bytes.append(bytes([data[0] & 0x1F]) + data[1:])
        flags_sign.append(bool(flags & O.FLAG_SIGN))
    return res, live, raw_bytes, flags_sign


def decompress_g1_batch(blobs: Sequence[bytes]) -> List[object]:
    """Per item: (x_limbs, y_limbs) canonical Montgomery, None (infinity),
    or the exact ValueError the oracle g1_from_bytes raises."""
    res, live, raw_bytes, flags_sign = _parse_g1(blobs)
    if not live:
        return res
    if not _use_device():
        for i, raw, sign in zip(live, raw_bytes, flags_sign):
            v = _decompress_g1_int(raw, sign)
            res[i] = v if isinstance(v, ValueError) else (
                fq.to_mont_int(v[0]), fq.to_mont_int(v[1])
            )
        return res
    arr = np.frombuffer(b"".join(raw_bytes), dtype=np.uint8).reshape(-1, 48)
    x_raw = bytes_be_to_limbs(arr)
    in_range = _limbs_lt_const(x_raw, _P_LIMBS)
    x, y, yneg, y_raw, on_curve = _g1_decode_kernel(
        jnp.asarray(_pad_batch(np.where(in_range[:, None], x_raw, 0)))
    )
    m = len(live)
    x, y, yneg, y_raw, on_curve = (
        np.asarray(x)[:m],
        np.asarray(y)[:m],
        np.asarray(yneg)[:m],
        np.asarray(y_raw)[:m],
        np.asarray(on_curve)[:m],
    )
    want_large = np.asarray(flags_sign)
    is_large = _sign_is_large_fq(y_raw)
    y_final = np.where((is_large != want_large)[:, None], yneg, y)
    for j, i in enumerate(live):
        if not in_range[j]:
            res[i] = ValueError("G1 x out of range")
        elif not on_curve[j]:
            res[i] = ValueError("G1 x not on curve")
        else:
            res[i] = (x[j], y_final[j])
    return res


def _parse_g2(blobs: Sequence[bytes]):
    """Shared flag/length validation for 96-byte compressed G2 blobs
    (see _parse_g1)."""
    n = len(blobs)
    res: List[object] = [None] * n
    live: List[int] = []
    raw1: List[bytes] = []  # x.c1 (first 48 bytes, flags stripped)
    raw0: List[bytes] = []  # x.c0
    flags_sign: List[bool] = []
    for i, data in enumerate(blobs):
        data = bytes(data)
        if len(data) != 96:
            res[i] = ValueError("G2 point must be 96 bytes")
            continue
        flags = data[0]
        if not (flags & O.FLAG_COMPRESSED):
            res[i] = ValueError("uncompressed G2 encoding not supported")
            continue
        if flags & O.FLAG_INFINITY:
            if (flags & O.FLAG_SIGN) or any(
                bytes([data[0] & 0x1F]) + data[1:]
            ):
                res[i] = ValueError("invalid infinity encoding")
            continue
        live.append(i)
        raw1.append(bytes([data[0] & 0x1F]) + data[1:48])
        raw0.append(data[48:])
        flags_sign.append(bool(flags & O.FLAG_SIGN))
    return res, live, raw1, raw0, flags_sign


def decompress_g2_batch(blobs: Sequence[bytes]) -> List[object]:
    """Per item: (4, L) canonical [x.0, x.1, y.0, y.1] limb stack, None
    (infinity), or the exact ValueError the oracle g2_from_bytes raises."""
    res, live, raw1, raw0, flags_sign = _parse_g2(blobs)
    if not live:
        return res
    if not _use_device():
        for i, r1, r0, sign in zip(live, raw1, raw0, flags_sign):
            v = _decompress_g2_int(r1, r0, sign)
            res[i] = v if isinstance(v, ValueError) else np.stack(
                [fq.to_mont_int(v[0][0]), fq.to_mont_int(v[0][1]),
                 fq.to_mont_int(v[1][0]), fq.to_mont_int(v[1][1])]
            )
        return res
    a1 = bytes_be_to_limbs(
        np.frombuffer(b"".join(raw1), dtype=np.uint8).reshape(-1, 48)
    )
    a0 = bytes_be_to_limbs(
        np.frombuffer(b"".join(raw0), dtype=np.uint8).reshape(-1, 48)
    )
    in_range = _limbs_lt_const(a0, _P_LIMBS) & _limbs_lt_const(a1, _P_LIMBS)
    x_raw = np.stack([a0, a1], axis=1)  # (M, 2, L)
    x_raw = np.where(in_range[:, None, None], x_raw, 0)
    x, y, yneg, y_raw, on_curve = _g2_decode_kernel(
        jnp.asarray(_pad_batch(x_raw))
    )
    m = len(live)
    x, y, yneg, y_raw, on_curve = (
        np.asarray(x)[:m],
        np.asarray(y)[:m],
        np.asarray(yneg)[:m],
        np.asarray(y_raw)[:m],
        np.asarray(on_curve)[:m],
    )
    want_large = np.asarray(flags_sign)
    is_large = _sign_is_large_fq2(y_raw)
    y_final = np.where((is_large != want_large)[:, None, None], yneg, y)
    for j, i in enumerate(live):
        if not in_range[j]:
            res[i] = ValueError("G2 x out of range")
        elif not on_curve[j]:
            res[i] = ValueError("G2 x not on curve")
        else:
            res[i] = np.concatenate([x[j], y_final[j]], axis=0)
    return res


# ---------------------------------------------------------------------------
# backend-facing batch codecs (mirror bls_backend's per-item compute fns)
# ---------------------------------------------------------------------------


def pubkey_limbs_batch(pubkeys: Sequence[bytes], mesh=None) -> List[object]:
    """Batched _pubkey_limbs_compute: per item (x_limbs, y_limbs) or a
    ValueError VALUE (same messages as the per-item oracle path)."""
    res = decompress_g1_batch(pubkeys)
    live = [i for i, v in enumerate(res) if isinstance(v, tuple)]
    for i, v in enumerate(res):
        if v is None:
            res[i] = ValueError("pubkey is the point at infinity")
    if live:
        pts = np.stack([np.stack(res[i]) for i in live])
        ok = g1_subgroup_check_batch(pts, mesh=mesh)
        for j, i in enumerate(live):
            if not ok[j]:
                res[i] = ValueError("pubkey not in G1 subgroup")
    return res


def signature_limbs_batch(signatures: Sequence[bytes], mesh=None) -> List[object]:
    """Batched _signature_limbs_compute: per item a (4, L) limb stack or a
    ValueError VALUE (decode errors included, uniformly as values)."""
    res = decompress_g2_batch(signatures)
    live = [i for i, v in enumerate(res) if isinstance(v, np.ndarray)]
    for i, v in enumerate(res):
        if v is None:
            res[i] = ValueError("signature is the point at infinity")
    if live:
        pts = np.stack([res[i] for i in live])
        ok = g2_subgroup_check_batch(pts, mesh=mesh)
        for j, i in enumerate(live):
            if not ok[j]:
                res[i] = ValueError("signature not in G2 subgroup")
    return res


def message_limbs_batch(
    messages: Sequence[bytes], dst: bytes, mesh=None
) -> List[np.ndarray]:
    """Batched _message_limbs_compute: per message the (4, L) canonical
    affine hash-to-G2 limb stack."""
    pts = hash_to_g2_batch(messages, dst, mesh=mesh)
    return [pts[i] for i in range(pts.shape[0])]
