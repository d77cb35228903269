"""Base-field (Fq) limb arithmetic for BLS12-381 in JAX.

Representation: an Fq element is an array of shape (..., 15) of uint64 limbs,
28 bits per limb (15*28 = 420 bits), in Montgomery form with R = 2^420.
The ~39 bits of headroom above p (2^381) make lazy-reduction bounds easy:
a Montgomery multiply of any two values < 2^401 contracts to < 2^383, sums
of <= 16 such stay < 2^387, and the borrowless subtract shift (MP ~ 2^400)
keeps every intermediate far below the 2^420 capacity.
All operations are batched over leading dims — parallelism lives in the batch
dimensions, keeping the XLA graph size independent of batch size.

LAZY REDUCTION: values are kept loosely reduced (any representative of the
residue class below ~2^405, limbs always < 2^29). No per-op compare/subtract
chains — only carry propagation. Bounds:
- mont_mul inputs a, b < 2^401  =>  output < a*b/2^420 + p < 2^383
- `canonical()` (one extra Montgomery multiply by the representation of 1 +
  a single conditional subtract) produces the unique value in [0, p) — used
  only for equality/zero tests and host export.

Montgomery multiply is CIOS with delayed carries: limb products are < 2^56
and each accumulator column absorbs < 64 of them before being shifted out,
so uint64 never overflows.

Cross-checked bit-exactly (mod p) against the pure-Python oracle in
tests/test_ops_fq.py.
"""
import jax.numpy as jnp
import numpy as np

from ..utils.bls12_381 import P

LIMB_BITS = 28
NUM_LIMBS = 15
MASK = (1 << LIMB_BITS) - 1
R_BITS = LIMB_BITS * NUM_LIMBS  # 420
R_MONT = 1 << R_BITS


def _int_to_limbs_np(x: int) -> np.ndarray:
    out = np.zeros(NUM_LIMBS, dtype=np.uint64)
    for i in range(NUM_LIMBS):
        out[i] = x & MASK
        x >>= LIMB_BITS
    assert x == 0
    return out


def limbs_to_int(limbs) -> int:
    limbs = np.asarray(limbs)
    x = 0
    for i in reversed(range(limbs.shape[-1])):
        x = (x << LIMB_BITS) | int(limbs[..., i])
    return x


P_LIMBS = _int_to_limbs_np(P)
N0 = (-pow(P, -1, 1 << LIMB_BITS)) % (1 << LIMB_BITS)  # -p^-1 mod 2^29
R_MOD_P = R_MONT % P
R_INV = pow(R_MONT, -1, P)  # hoisted: a ~70us modular inverse per call adds
# seconds at epoch scale (tens of thousands of from_mont_limbs calls)
ONE_MONT = _int_to_limbs_np(R_MOD_P)  # 1 in Montgomery form
ZERO = np.zeros(NUM_LIMBS, dtype=np.uint64)
# MP: multiple of p used as the additive shift in borrowless subtraction;
# smallest multiple of p above 2^402 (sub compresses its b operand to < 2^382
# first, and loose a operands stay far below 2^410)
MP = ((1 << 402) // P + 1) * P
MP_LIMBS = _int_to_limbs_np(MP)

_P_LIMBS_J = jnp.asarray(P_LIMBS, dtype=jnp.uint64)
_MP_LIMBS_J = jnp.asarray(MP_LIMBS, dtype=jnp.uint64)
_ONE_MONT_J = jnp.asarray(ONE_MONT, dtype=jnp.uint64)


def to_mont_int(x: int) -> np.ndarray:
    """Host: encode an integer < p into Montgomery-form limbs."""
    return _int_to_limbs_np((x * R_MONT) % P)


def from_mont_limbs(limbs) -> int:
    """Host: decode (possibly loose) Montgomery-form limbs to an int < p."""
    x = limbs_to_int(limbs)
    return (x * R_INV) % P


def _carry_limbs(t, out_limbs=NUM_LIMBS):
    """Propagate carries to limbs < 2^29; the value must fit out_limbs limbs."""
    n = t.shape[-1]
    outs = []
    c = jnp.zeros(t.shape[:-1], dtype=jnp.uint64)
    for k in range(n):
        cur = t[..., k] + c
        outs.append(cur & jnp.uint64(MASK))
        c = cur >> jnp.uint64(LIMB_BITS)
    while len(outs) < out_limbs:
        outs.append(c & jnp.uint64(MASK))
        c = c >> jnp.uint64(LIMB_BITS)
    return jnp.stack(outs[:out_limbs], axis=-1)


def _shifted(vec, offset, total):
    """Pad a (..., K)-limb vector to (..., total) at column `offset`
    (static) — compiles to one concat, no scatter."""
    k = vec.shape[-1]
    pads = [(0, 0)] * (vec.ndim - 1) + [(offset, total - k - offset)]
    return jnp.pad(vec, pads)


def mont_mul(a, b):
    """Montgomery product a*b*R^-1 (mod p); loose in, loose out.

    With CONSENSUS_SPECS_TPU_PALLAS=1 the multiply dispatches to the
    hand-tiled pure-uint32 Pallas kernel (ops/pallas_fq.py) — same
    Montgomery domain (R = 2^420), bit-identical results, all work in
    VMEM; otherwise the jnp uint64 lowering (mont_mul_u64) runs."""
    from . import pallas_fq

    if pallas_fq.enabled():
        return pallas_fq.mont_mul(a, b)
    return mont_mul_u64(a, b)


def mont_mul_u64(a, b):
    """The jnp uint64 lowering of mont_mul, reachable directly so a
    Pallas A/B can baseline against it even when the Pallas dispatch is
    switched on.

    Vectorized SOS: the schoolbook product and each reduction step are
    whole-vector ops (broadcast multiply + statically-padded shift + add) so
    a call site is ~100 HLO ops — no scatters, XLA-compile-friendly.

    Overflow audit (uint64 columns): schoolbook columns accumulate <= 15
    products of loose limbs (< 2^28 each) => < 15*2^56 < 2^60; the reduction
    adds one m*P_limb (< 2^56) per outer step per column plus single-limb
    carries => total < 2^62."""
    a = jnp.asarray(a, jnp.uint64)
    b = jnp.asarray(b, jnp.uint64)
    n0 = jnp.uint64(N0)
    mask = jnp.uint64(MASK)
    shift = jnp.uint64(LIMB_BITS)
    total = 2 * NUM_LIMBS  # 30 columns (29 used; one spare)

    # schoolbook columns: t[k] = sum_{i+j=k} a_i * b_j
    t = None
    for i in range(NUM_LIMBS):
        row = a[..., i : i + 1] * b  # (..., 15)
        t = _shifted(row, i, total) if t is None else t + _shifted(row, i, total)

    # Montgomery reduction: clear limbs 0..14 low-to-high, propagating the
    # single carry of each cleared limb
    p_j = jnp.asarray(P_LIMBS, dtype=jnp.uint64)
    for i in range(NUM_LIMBS):
        ti = t[..., i]
        m = ((ti & mask) * n0) & mask
        add = m[..., None] * p_j  # (..., 15)
        carry = (ti + m * p_j[0]) >> shift  # t[i] after add, divided by 2^28
        # columns i+1..i+14 receive add[1:]; column i+1 also gets the carry
        vec = jnp.concatenate(
            [add[..., 1:2] + carry[..., None], add[..., 2:]], axis=-1
        )
        t = t + _shifted(vec, i + 1, total)

    return _carry_limbs(t[..., NUM_LIMBS : 2 * NUM_LIMBS])


def add(a, b):
    return _carry_limbs(a + b)


def add_many(terms):
    """Sum a list of loose elements (raw limb accumulation + one carry pass)."""
    acc = terms[0]
    for t in terms[1:]:
        acc = acc + t
    return _carry_limbs(acc)


def compress(a):
    """Value-preserving magnitude reduction: one Montgomery multiply by the
    representation of 1 contracts any loose value to < 2^382."""
    return mont_mul(a, _ONE_MONT_J)


def sub(a, b):
    """a - b (mod p), borrowless, via the base-2^28 complement identity:
    a + MP + comp(b) + 1 == a + MP - b + 2^420.

    b is compressed first so MP > b always holds regardless of how loose the
    incoming chain value is; a may be loose (< ~2^410). The overflow limb of
    the complement identity is then exactly 1 and is dropped."""
    b = compress(b)
    nb = jnp.uint64(MASK) - b  # limbs < 2^28, no wrap
    t = a + _MP_LIMBS_J + nb
    t = t.at[..., 0].add(jnp.uint64(1))
    limbs = _carry_limbs(t, out_limbs=NUM_LIMBS + 1)
    # drop the 2^420 overflow bit from the complement identity
    return limbs[..., :NUM_LIMBS]


def neg(a):
    return sub(jnp.zeros_like(a), a)


def _geq_p(a):
    ge = jnp.ones(a.shape[:-1], dtype=bool)
    gt = jnp.zeros(a.shape[:-1], dtype=bool)
    for k in reversed(range(NUM_LIMBS)):
        pk = jnp.uint64(int(P_LIMBS[k]))
        gt = gt | (ge & (a[..., k] > pk))
        ge = ge & (a[..., k] == pk)
    return gt | ge


def _sub_p(a):
    outs = []
    borrow = jnp.zeros(a.shape[:-1], dtype=jnp.uint64)
    two29 = jnp.uint64(1 << LIMB_BITS)
    for k in range(NUM_LIMBS):
        pk = jnp.uint64(int(P_LIMBS[k]))
        cur = a[..., k] + two29 - pk - borrow
        outs.append(cur & jnp.uint64(MASK))
        borrow = jnp.uint64(1) - (cur >> jnp.uint64(LIMB_BITS))
    return jnp.stack(outs, axis=-1)


def canonical(a):
    """The unique representative in [0, p): one Montgomery multiply by
    repr(1) (output < p + eps) + a single conditional subtract."""
    r = mont_mul(a, _ONE_MONT_J)
    return jnp.where(_geq_p(r)[..., None], _sub_p(r), r)


def is_zero(a):
    """Mod-p zero test (canonicalizes internally)."""
    return jnp.all(canonical(a) == 0, axis=-1)


def eq(a, b):
    return jnp.all(canonical(a) == canonical(b), axis=-1)


def select(cond, a, b):
    return jnp.where(cond[..., None], a, b)


def pow_fixed(a, exp_bits):
    """a^e for a STATIC msb-first bit list `exp_bits`, branchless
    square-and-multiply via lax.scan (loose in, loose out)."""
    import jax

    bits = jnp.asarray(exp_bits[1:], dtype=bool)  # MSB handled by init
    batch = a.shape[:-1]

    def body(acc, bit):
        acc = mont_mul(acc, acc)
        acc_mul = mont_mul(acc, a)
        acc = jnp.where(jnp.broadcast_to(bit, batch)[..., None], acc_mul, acc)
        return acc, None

    acc, _ = jax.lax.scan(body, a, bits)
    return acc


_P_MINUS_2_BITS = [int(b) for b in bin(P - 2)[2:]]


def inv(a):
    """Modular inverse via Fermat: a^(p-2). inv(0) == 0 (used as the
    infinity-absorbing property in Jacobian->affine conversion)."""
    return pow_fixed(a, _P_MINUS_2_BITS)


def zeros_like_batch(batch_shape):
    return jnp.zeros(tuple(batch_shape) + (NUM_LIMBS,), dtype=jnp.uint64)


def const(x_int, batch_shape=()):
    c = jnp.asarray(to_mont_int(x_int % P), dtype=jnp.uint64)
    return jnp.broadcast_to(c, tuple(batch_shape) + (NUM_LIMBS,))
