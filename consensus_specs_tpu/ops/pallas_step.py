"""Fused VM-step Pallas kernel: BOTH ALU units of the field-VM — the
W_mul-lane Montgomery-multiply unit and the W_lin-lane add/borrowless-sub
unit (ops/vm.py `_vm_step`) — in ONE kernel launch per scan step, all
arithmetic native uint32 in VMEM.

This is the SURVEY §7.3 #1-#2 extension beyond ops/pallas_fq.py: with it,
the VM's register file lives in 14-bit uint32 limb form for the whole
scan (`vm._vm_body` 'step' mode), so
  - no uint64 emulation anywhere on v5e's 32-bit VPU (the lin unit's
    add/carry was still emulated u64 under the mont_mul-only dispatch),
  - half the register-file HBM bytes per gather/scatter,
  - one kernel launch per step instead of a mont_mul kernel plus an XLA
    elementwise chain.

Layout (pallas_fq conventions): limbs on sublanes, flattened batch*lanes
on lanes — (32, M) uint32 tiles, gridded in TILE_M blocks. The two units
have different widths, so one grid of max(gm, gl) blocks serves both:
block i processes mul tile i while i < gm and lin tile i while i < gl
(pl.when); out-of-range index maps clamp to the last block, which Pallas
revisits without flushing, so the clamped steps neither reload nor
clobber it.

Lin-unit math (14-bit rows, mirrors fq/_vm_step exactly): for subtract
lanes rhs = (MP+1) + (MASK - b) per limb row — the borrowless complement
shift — else rhs = b; out = carry(a + rhs) over 31 rows keeping 30
(== value mod 2^420, the same top-limb drop as fq's 16-keep-15).
Bit-identical to the u64 path (tests/test_ops_pallas_step.py).

Enable via CONSENSUS_SPECS_TPU_PALLAS=step (vm.py dispatch). Runs under a
device mesh too: a pallas_call is opaque to the GSPMD partitioner, so the
mesh runner routes modes '1'/'step' through jax.shard_map (each device
traces its own per-shard program — vm._vm_run_for_mesh); only GSPMD
sharding is mode-'0'-specific.
"""
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

from . import fq
from .pallas_fq import (
    L_PAD, LIMB_BITS, MASK, NUM_LIMBS, TILE_M, _carry_rows, _int_to_limbs14,
    mont_rows,
)

# MP+1 in 14-bit limb rows: the additive shift of the borrowless subtract
# (fq.MP ~ 2^402, so it fits the 30-limb/2^420 capacity)
_MP1_14 = _int_to_limbs14(fq.MP + 1)


def _step_kernel(gm, gl, ma_ref, mb_ref, la_ref, lb_ref, sub_ref, p_ref,
                 mp1_ref, mo_ref, lo_ref):
    from jax.experimental import pallas as pl

    i = pl.program_id(0)
    zero_pad = lambda r: jnp.concatenate(
        [r, jnp.zeros((L_PAD - NUM_LIMBS, r.shape[1]), dtype=jnp.uint32)],
        axis=0,
    )

    @pl.when(i < gm)
    def _mul_unit():
        res = mont_rows(ma_ref[:], mb_ref[0:NUM_LIMBS], p_ref[0:NUM_LIMBS])
        mo_ref[:] = zero_pad(res)

    @pl.when(i < gl)
    def _lin_unit():
        la = la_ref[0:NUM_LIMBS]
        lb = lb_ref[0:NUM_LIMBS]
        sub = sub_ref[0:NUM_LIMBS]  # 0/1 mask, identical rows
        comp = mp1_ref[0:NUM_LIMBS] + (jnp.uint32(MASK) - lb)
        rhs = jnp.where(sub != 0, comp, lb)
        s = jnp.concatenate(
            [la + rhs, jnp.zeros((1, la.shape[1]), dtype=jnp.uint32)], axis=0
        )
        lo_ref[:] = zero_pad(_carry_rows(s, NUM_LIMBS + 1)[:NUM_LIMBS])


@functools.lru_cache(maxsize=None)
def _fused_call(mm_padded: int, ml_padded: int, interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    gm = mm_padded // TILE_M
    gl = ml_padded // TILE_M
    grid = max(gm, gl)

    # int32 block indices: Mosaic refuses the i64 a bare 0 lowers to
    # under x64
    def tile_spec(g):
        return pl.BlockSpec(
            (L_PAD, TILE_M),
            lambda i, g=g: (jnp.int32(0), jnp.minimum(i, g - 1)),
            memory_space=pltpu.VMEM,
        )

    col_spec = pl.BlockSpec(
        (L_PAD, 1), lambda i: (jnp.int32(0), jnp.int32(0)),
        memory_space=pltpu.VMEM,
    )
    call = pl.pallas_call(
        functools.partial(_step_kernel, gm, gl),
        out_shape=(
            jax.ShapeDtypeStruct((L_PAD, mm_padded), jnp.uint32),
            jax.ShapeDtypeStruct((L_PAD, ml_padded), jnp.uint32),
        ),
        grid=(grid,),
        in_specs=[
            tile_spec(gm), tile_spec(gm),  # mul a, b
            tile_spec(gl), tile_spec(gl), tile_spec(gl),  # lin a, b, sub
            col_spec, col_spec,  # p14, MP+1
        ],
        out_specs=(tile_spec(gm), tile_spec(gl)),
        interpret=interpret,
    )
    p14_col = np.zeros((L_PAD, 1), dtype=np.uint32)
    p14_col[:NUM_LIMBS, 0] = _int_to_limbs14(fq.P)
    mp1_col = np.zeros((L_PAD, 1), dtype=np.uint32)
    mp1_col[:NUM_LIMBS, 0] = _MP1_14
    return lambda ma, mb, la, lb, sub: call(
        ma, mb, la, lb, sub, jnp.asarray(p14_col), jnp.asarray(mp1_col)
    )


def _rows(x):
    """(..., NUM_LIMBS) -> (NUM_LIMBS, M) limb-row tiles, batch flattened
    row-major so every operand uses the same lane order."""
    return x.reshape(-1, NUM_LIMBS).T


def _pad_tile(r, m_padded):
    return jnp.pad(r, ((0, L_PAD - NUM_LIMBS), (0, m_padded - r.shape[1])))


def fused_step(ma, mb, la, lb, lsub):
    """One VM step on 14-bit-limb operands.

    ma/mb: (..., w_mul, NUM_LIMBS) uint32 — mul-unit operand rows;
    la/lb: (..., w_lin, NUM_LIMBS); lsub: (..., w_lin) bool/int mask.
    Returns (m, lin) with the operand shapes, rows < 2^14."""
    m_shape, l_shape = ma.shape[:-1], la.shape[:-1]
    mm = int(np.prod(m_shape))
    ml = int(np.prod(l_shape))
    mm_padded = -(-mm // TILE_M) * TILE_M
    ml_padded = -(-ml // TILE_M) * TILE_M

    sub_flat = jnp.broadcast_to(lsub, l_shape).astype(jnp.uint32).reshape(-1)
    sub_rows = jnp.broadcast_to(sub_flat.reshape(1, -1), (NUM_LIMBS, ml))
    interpret = jax.default_backend() == "cpu"
    mo, lo = _fused_call(mm_padded, ml_padded, interpret)(
        _pad_tile(_rows(ma), mm_padded),
        _pad_tile(_rows(mb), mm_padded),
        _pad_tile(_rows(la), ml_padded),
        _pad_tile(_rows(lb), ml_padded),
        _pad_tile(sub_rows, ml_padded),
    )
    return (
        mo[:NUM_LIMBS, :mm].T.reshape(m_shape + (NUM_LIMBS,)),
        lo[:NUM_LIMBS, :ml].T.reshape(l_shape + (NUM_LIMBS,)),
    )


def split14(x):
    """(..., 15) uint 28-bit limbs -> (..., 30) uint32 14-bit limbs
    (exact bit repack; input limbs must be < 2^28)."""
    x32 = jnp.asarray(x).astype(jnp.uint32)
    lo = x32 & jnp.uint32(MASK)
    hi = x32 >> jnp.uint32(LIMB_BITS)
    return jnp.stack([lo, hi], axis=-1).reshape(x32.shape[:-1] + (NUM_LIMBS,))


def join14(x):
    """(..., 30) uint32 14-bit limbs -> (..., 15) uint64 28-bit limbs."""
    v = x.reshape(x.shape[:-1] + (fq.NUM_LIMBS, 2))
    return v[..., 0].astype(jnp.uint64) | (
        v[..., 1].astype(jnp.uint64) << jnp.uint64(LIMB_BITS)
    )


def enabled() -> bool:
    """'step' turns the whole-VM-step fused kernel on (vm.py dispatch);
    '1' keeps the narrower mont_mul-only dispatch (pallas_fq.enabled)."""
    return os.environ.get("CONSENSUS_SPECS_TPU_PALLAS", "0") == "step"
