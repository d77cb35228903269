"""Hand-tiled Pallas TPU kernel for the Montgomery multiply — the one hot
op of the field-ALU VM (ops/vm.py `_vm_step` spends ~all FLOPs in
fq.mont_mul; a pairing is tens of thousands of them).

Why a kernel at all: the jnp lowering of fq.mont_mul is ~100 HLO ops whose
intermediates XLA materializes at fusion boundaries, and its uint64 limb
arithmetic is emulated on v5e's 32-bit VPU. This kernel keeps the whole
multiply in VMEM and does ONLY native uint32 arithmetic:

  representation bridge
    fq (ops/fq.py):  15 limbs x 28 bits, uint64 lanes, R = 2^420
    kernel:          30 limbs x 14 bits, uint32 lanes, R = 2^420  (!)
  Same Montgomery R, so the kernel is a drop-in for fq.mont_mul with a pure
  bit-repack at the boundary (each 28-bit limb splits into two 14-bit
  halves; no multiplies, no modular work).

  layout: limbs on sublanes, batch on lanes — arrays are (32, M) uint32
  tiles (30 limb rows + 2 zero pad rows), M = flattened batch, gridded in
  TILE_M-lane blocks. Every product row is a full (30, TILE_M) VPU op.

  overflow discipline (all uint32): 14-bit limb products < 2^28; a column
  absorbs <= 8 of them between carry renormalizations (8 * 2^28 + carry
  slack < 2^32). The Montgomery reduction renormalizes only the
  not-yet-cleared column suffix, exactly like ops/fq32.py's proven
  schedule (cleared columns hold stale residuals the final slice drops).

Value contract is identical to fq.mont_mul: loose Montgomery residues in,
loose out (result < a*b/R + p), limbs of the INPUT must be < 2^28 (which
every VM register and fq.add/sub/carry output satisfies). Cross-checked
limb-exactly against fq.mont_mul and the exact-integer oracle in
tests/test_ops_pallas.py (interpret mode on CPU); its Mosaic compile for
v5e is pinned in tests/test_chip_compile.py, and chip_smoke.py holds a
whole VM program under this mode bit-identical to the default on the chip.

Enable via CONSENSUS_SPECS_TPU_PALLAS=1 (see fq.mont_mul dispatch). Opt-in
until a chip measurement says it wins.
"""
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

from ..utils.bls12_381 import P

LIMB_BITS = 14
NUM_LIMBS = 30  # 30 x 14 = 420 = fq's R_BITS — same Montgomery domain
MASK = (1 << LIMB_BITS) - 1
_T_ROWS = 2 * NUM_LIMBS + 1  # 61 working columns (one transient carry row)
_RENORM_EVERY = 8  # 8 products of < 2^28 + slack stay under 2^32

L_PAD = 32  # limb rows padded to a sublane-friendly count
TILE_M = 256  # batch lanes per grid step

N0 = (-pow(P, -1, 1 << LIMB_BITS)) % (1 << LIMB_BITS)


def _int_to_limbs14(x: int) -> np.ndarray:
    out = np.zeros(NUM_LIMBS, dtype=np.uint32)
    for i in range(NUM_LIMBS):
        out[i] = x & MASK
        x >>= LIMB_BITS
    assert x == 0
    return out


P14 = _int_to_limbs14(P)


def _carry_rows(t, n_rows):
    """Serial carry pass over limb rows: rows become < 2^14. The dropped
    final carry is zero under the caller's magnitude bounds (same 15-limb /
    2^420 capacity contract as fq._carry_limbs)."""
    mask = jnp.uint32(MASK)
    shift = jnp.uint32(LIMB_BITS)
    outs = []
    c = jnp.zeros_like(t[0:1])
    for k in range(n_rows):
        cur = t[k : k + 1] + c
        outs.append(cur & mask)
        c = cur >> shift
    return jnp.concatenate(outs, axis=0)


def _pad_rows(v, top, total):
    """Zero rows above and below ``v`` up to ``total`` rows. Built from
    uint32 zeros, not ``jnp.pad``: under x64 the pad value is an int64
    constant, which Mosaic cannot convert (RecursionError at lowering)."""
    parts = [v]
    bottom = total - v.shape[0] - top
    if top:
        parts.insert(0, jnp.zeros((top, v.shape[1]), dtype=v.dtype))
    if bottom:
        parts.append(jnp.zeros((bottom, v.shape[1]), dtype=v.dtype))
    return jnp.concatenate(parts, axis=0) if len(parts) > 1 else v


def mont_rows(a, b, p14):
    """The Montgomery-multiply math on limb-row tiles, shared by this
    kernel and the fused VM-step kernel (ops/pallas_step.py).

    a: (L_PAD, M) uint32 with rows NUM_LIMBS.. zero; b: (NUM_LIMBS, M);
    p14: (NUM_LIMBS, 1) modulus limbs. Returns (NUM_LIMBS, M) rows < 2^14:
    t = a*b (schoolbook columns), Montgomery reduction clearing 30 low
    columns, carry-normalized high half."""
    n0 = jnp.uint32(N0)
    mask = jnp.uint32(MASK)
    shift = jnp.uint32(LIMB_BITS)

    # schoolbook: t[k] = sum_{i+j=k} a_i * b_j, renormalized every 8 rows
    t = jnp.zeros((_T_ROWS, a.shape[1]), dtype=jnp.uint32)
    for i in range(NUM_LIMBS):
        prod = a[i : i + 1] * b  # (30, M), entries < 2^28
        t = t + _pad_rows(prod, i, _T_ROWS)
        if (i + 1) % _RENORM_EVERY == 0:
            t = _carry_rows(t, _T_ROWS)
    t = _carry_rows(t, _T_ROWS)

    # Montgomery reduction: clear columns 0..29 low-to-high; renormalize
    # only the unprocessed suffix (cleared columns keep stale residuals
    # that the final high-half slice drops — fq32.py's schedule)
    for i in range(NUM_LIMBS):
        ti = t[i : i + 1]  # (1, M)
        m = ((ti & mask) * n0) & mask
        add = m * p14  # (30, M) products < 2^28
        carry0 = (ti + m * p14[0:1]) >> shift
        vec = jnp.concatenate([add[1:2] + carry0, add[2:]], axis=0)
        t = t + _pad_rows(vec, i + 1, _T_ROWS)
        if (i + 1) % _RENORM_EVERY == 0:
            suffix = _carry_rows(t[i + 1 :], _T_ROWS - (i + 1))
            t = jnp.concatenate([jnp.zeros_like(t[: i + 1]), suffix], axis=0)

    return _carry_rows(t[NUM_LIMBS:], NUM_LIMBS + 1)[:NUM_LIMBS]


def _mont_mul_kernel(a_ref, b_ref, p_ref, o_ref):
    """One TILE_M-lane block of the standalone mont_mul call."""
    res = mont_rows(a_ref[:], b_ref[0:NUM_LIMBS], p_ref[0:NUM_LIMBS])
    o_ref[:] = jnp.concatenate(
        [res, jnp.zeros((L_PAD - NUM_LIMBS, res.shape[1]), dtype=jnp.uint32)],
        axis=0,
    )


@functools.lru_cache(maxsize=None)
def _pallas_mm(m_padded: int, interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    grid = m_padded // TILE_M
    # block indices are int32: under x64 a bare 0 lowers to i64, which
    # Mosaic refuses in an index map
    spec = pl.BlockSpec(
        (L_PAD, TILE_M), lambda i: (jnp.int32(0), i), memory_space=pltpu.VMEM
    )
    p_spec = pl.BlockSpec(
        (L_PAD, 1), lambda i: (jnp.int32(0), jnp.int32(0)),
        memory_space=pltpu.VMEM,
    )
    call = pl.pallas_call(
        _mont_mul_kernel,
        out_shape=jax.ShapeDtypeStruct((L_PAD, m_padded), jnp.uint32),
        grid=(grid,),
        in_specs=[spec, spec, p_spec],
        out_specs=spec,
        interpret=interpret,
    )
    p14_col = np.zeros((L_PAD, 1), dtype=np.uint32)
    p14_col[:NUM_LIMBS, 0] = P14
    return jax.jit(lambda a, b: call(a, b, jnp.asarray(p14_col)))


def _to14(x64):
    """(..., 15) uint64 28-bit limbs -> (30, M) uint32 14-bit limb rows."""
    x32 = x64.astype(jnp.uint32)  # limbs < 2^28: truncation is exact
    lo = x32 & jnp.uint32(MASK)
    hi = x32 >> jnp.uint32(LIMB_BITS)
    inter = jnp.stack([lo, hi], axis=-1).reshape(x64.shape[:-1] + (NUM_LIMBS,))
    return inter.reshape(-1, NUM_LIMBS).T


def _from14(rows, batch_shape):
    """(30, M) uint32 14-bit rows -> (..., 15) uint64 28-bit limbs."""
    inter = rows.T.reshape(batch_shape + (15, 2))
    out = inter[..., 0].astype(jnp.uint64) | (
        inter[..., 1].astype(jnp.uint64) << jnp.uint64(LIMB_BITS)
    )
    return out


def mont_mul(a, b):
    """Drop-in for fq.mont_mul via the Pallas kernel: same loose-Montgomery
    contract, (..., 15)-uint64 interface, limbs < 2^28 required."""
    a = jnp.asarray(a, jnp.uint64)
    b = jnp.asarray(b, jnp.uint64)
    batch_shape = jnp.broadcast_shapes(a.shape[:-1], b.shape[:-1])
    a = jnp.broadcast_to(a, batch_shape + a.shape[-1:])
    b = jnp.broadcast_to(b, batch_shape + b.shape[-1:])
    m = int(np.prod(batch_shape)) if batch_shape else 1

    a14 = _to14(a.reshape(-1, 15))
    b14 = _to14(b.reshape(-1, 15))
    m_padded = -(-m // TILE_M) * TILE_M
    pads = ((0, L_PAD - NUM_LIMBS), (0, m_padded - m))
    a14 = jnp.pad(a14, pads)
    b14 = jnp.pad(b14, pads)

    interpret = jax.default_backend() == "cpu"
    out = _pallas_mm(m_padded, interpret)(a14, b14)
    res = _from14(out[:NUM_LIMBS, :m], tuple(batch_shape))
    return res


def enabled() -> bool:
    """Dispatch switch for fq.mont_mul: opt-in (CONSENSUS_SPECS_TPU_PALLAS=1)
    until a chip measurement says it wins; =0 forces off."""
    return os.environ.get("CONSENSUS_SPECS_TPU_PALLAS", "0") == "1"
