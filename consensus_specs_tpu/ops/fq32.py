"""Base-field (Fq) limb arithmetic in PURE uint32 lanes — the int32-oriented
fallback representation for BLS12-381 on TPU (SURVEY §7.3 risk #1).

The production path (ops/fq.py) uses 15x28-bit limbs with uint64
accumulators; on v5e the vector unit is 32-bit, so u64 elementwise work is
XLA-emulated. If a chip measurement shows that
emulation is the bottleneck, THIS module is the drop-in representation:

  - 32 limbs x 12 bits (384 bits capacity, p is 381 bits)
  - limb products < 2^24; a schoolbook column accumulates <= 32 of them
    plus reduction terms, all < 2^31 — no uint64 anywhere
  - same loose-Montgomery conventions as fq.py (R = 2^384 here), same API
    subset (mont_mul / add / sub / canonical / conversions)

Cross-checked limb-exactly against the exact-integer oracle in
tests/test_ops_fq32.py. The VM (ops/vm.py) is representation-agnostic at
the schedule level — switching it to fq32 is a dtype + limb-count swap in
its ALU body, done only once hardware numbers justify the 2x limb blowup.
"""
import jax.numpy as jnp
import numpy as np

from ..utils.bls12_381 import P

LIMB_BITS = 12
NUM_LIMBS = 32
MASK = (1 << LIMB_BITS) - 1
R_BITS = LIMB_BITS * NUM_LIMBS  # 384
R_MONT = 1 << R_BITS

DTYPE = jnp.uint32


def _int_to_limbs_np(x: int) -> np.ndarray:
    out = np.zeros(NUM_LIMBS, dtype=np.uint32)
    for i in range(NUM_LIMBS):
        out[i] = x & MASK
        x >>= LIMB_BITS
    assert x == 0, "value exceeds 384-bit capacity"
    return out


def limbs_to_int(limbs) -> int:
    limbs = np.asarray(limbs)
    x = 0
    for i in reversed(range(limbs.shape[-1])):
        x = (x << LIMB_BITS) | int(limbs[..., i])
    return x


P_LIMBS = _int_to_limbs_np(P)
N0 = (-pow(P, -1, 1 << LIMB_BITS)) % (1 << LIMB_BITS)  # -p^-1 mod 2^12
ONE_MONT = _int_to_limbs_np(R_MONT % P)
_P_LIMBS_J = jnp.asarray(P_LIMBS, dtype=DTYPE)
_ONE_MONT_J = jnp.asarray(ONE_MONT, dtype=DTYPE)


def to_mont_int(x: int) -> np.ndarray:
    return _int_to_limbs_np((x * R_MONT) % P)


R_INV = pow(R_MONT, -1, P)


def from_mont_limbs(limbs) -> int:
    return (limbs_to_int(limbs) * R_INV) % P


def _carry_limbs(t, out_limbs=NUM_LIMBS):
    """Propagate carries to limbs < 2^12. Column values must be < 2^32."""
    n = t.shape[-1]
    outs = []
    c = jnp.zeros(t.shape[:-1], dtype=DTYPE)
    for k in range(n):
        cur = t[..., k] + c
        outs.append(cur & DTYPE(MASK))
        c = cur >> DTYPE(LIMB_BITS)
    while len(outs) < out_limbs:
        outs.append(c & DTYPE(MASK))
        c = c >> DTYPE(LIMB_BITS)
    return jnp.stack(outs[:out_limbs], axis=-1)


def _shifted(vec, offset, total):
    pads = [(0, 0)] * (vec.ndim - 1) + [(offset, total - vec.shape[-1] - offset)]
    return jnp.pad(vec, pads)


def mont_mul(a, b):
    """Montgomery product a*b*R^-1 mod p in pure uint32.

    Overflow audit: tight limbs are < 2^12 (we carry-normalize inputs), so
    schoolbook columns accumulate <= 32 products < 2^24 => < 2^29; the
    reduction adds one m*P_limb (< 2^24) per outer step per column plus a
    carry => every column stays < 2^31 < 2^32."""
    a = _carry_limbs(jnp.asarray(a, DTYPE))  # enforce tight limbs
    b = _carry_limbs(jnp.asarray(b, DTYPE))
    n0 = DTYPE(N0)
    mask = DTYPE(MASK)
    shift = DTYPE(LIMB_BITS)
    total = 2 * NUM_LIMBS + 1

    t = None
    for i in range(NUM_LIMBS):
        row = a[..., i : i + 1] * b  # products < 2^24
        t = _shifted(row, i, total) if t is None else t + _shifted(row, i, total)
        if (i + 1) % 8 == 0:
            # re-normalize every 8 rows so columns never approach 2^32:
            # 8 rows add < 8 * 2^24 = 2^27 on top of < 2^13 carried limbs
            t = _carry_limbs(t, out_limbs=total)

    t = _carry_limbs(t, out_limbs=total)
    p_j = _P_LIMBS_J
    for i in range(NUM_LIMBS):
        ti = t[..., i]
        m = ((ti & mask) * n0) & mask  # < 2^12
        add = m[..., None] * p_j  # products < 2^24
        carry = (ti + m * p_j[0]) >> shift
        vec = jnp.concatenate(
            [add[..., 1:2] + carry[..., None], add[..., 2:]], axis=-1
        )
        t = t + _shifted(vec, i + 1, total)
        if (i + 1) % 8 == 0:
            # renormalize the UNPROCESSED suffix only: processed columns
            # <= i hold stale residuals that the final slice drops — carrying
            # them upward would double-count each cleared limb
            suffix = _carry_limbs(t[..., i + 1:], out_limbs=total - (i + 1))
            t = jnp.concatenate(
                [jnp.zeros_like(t[..., : i + 1]), suffix], axis=-1
            )

    return _carry_limbs(t[..., NUM_LIMBS : 2 * NUM_LIMBS + 1])


def add(a, b):
    return _carry_limbs(jnp.asarray(a, DTYPE) + jnp.asarray(b, DTYPE))


# smallest multiple of p above 2^382 (subtrahends are tight, < 2^384... use
# a shift covering any compressed value < p plus slack)
MP = ((1 << 382) // P + 1) * P
MP_LIMBS = _int_to_limbs_np(MP)
_MP_LIMBS_J = jnp.asarray(MP_LIMBS, dtype=DTYPE)


def compress(a):
    """Contract any loose value to < 2^382 via one Montgomery multiply."""
    return mont_mul(a, _ONE_MONT_J)


def sub(a, b):
    """a - b (mod p), borrowless: a + MP + comp(b) + 1 - 2^384."""
    a = _carry_limbs(jnp.asarray(a, DTYPE))
    b = compress(b)
    nb = DTYPE(MASK) - b
    t = a + _MP_LIMBS_J + nb
    t = t.at[..., 0].add(DTYPE(1))
    limbs = _carry_limbs(t, out_limbs=NUM_LIMBS + 1)
    return limbs[..., :NUM_LIMBS]


def _geq_p(a):
    ge = jnp.ones(a.shape[:-1], dtype=bool)
    gt = jnp.zeros(a.shape[:-1], dtype=bool)
    for k in reversed(range(NUM_LIMBS)):
        pk = DTYPE(int(P_LIMBS[k]))
        gt = gt | (ge & (a[..., k] > pk))
        ge = ge & (a[..., k] == pk)
    return gt | ge


def _sub_p(a):
    outs = []
    borrow = jnp.zeros(a.shape[:-1], dtype=DTYPE)
    base = DTYPE(1 << LIMB_BITS)
    for k in range(NUM_LIMBS):
        pk = DTYPE(int(P_LIMBS[k]))
        cur = a[..., k] + base - pk - borrow
        outs.append(cur & DTYPE(MASK))
        borrow = DTYPE(1) - (cur >> DTYPE(LIMB_BITS))
    return jnp.stack(outs, axis=-1)


def canonical(a):
    r = mont_mul(a, _ONE_MONT_J)
    return jnp.where(_geq_p(r)[..., None], _sub_p(r), r)
