"""AOT compiles of the main-path programs for a described v5e:2x2.

The TPU compiler is installed here and compiles for a chip that is
described, not attached: what it refuses (VMEM, tiling, Mosaic lowering)
is found without chip time. Covers the folded Miller program at the
K=512 bucket, the RLC combine chunk, the hard-part row, the index
path's key gather and input placement, and both Pallas kernels at the
widths the VM step feeds them — with x64 on, as the VM
runs. Nothing executes, so nothing here is a chip result.

The topology is described inside a fixture (never at import): only one
process may load the TPU library, and every xdist worker imports this
file. The persistent compilation cache is off around these compiles (a
compile for a described chip is written to it but cannot be read back).
"""
import os

import numpy as np
import pytest

from consensus_specs_tpu.utils.jax_env import force_cpu

force_cpu()

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from consensus_specs_tpu.ops import bls_backend, fq, pallas_fq, pallas_step, vm  # noqa: E402

ROWS = 32  # one mainnet slot: 64 committees of 512 at fold 2


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=sharding)


def _compile_vm(program, rows, sharding):
    assert jax.config.jax_enable_x64
    args = (
        _sds((rows, len(program.input_names), fq.NUM_LIMBS), jnp.uint32,
             sharding),
        _sds((program.n_regs, fq.NUM_LIMBS), jnp.uint64, sharding),
        _sds(np.shape(program.input_regs),
             np.asarray(program.input_regs).dtype, sharding),
        _sds(np.shape(program.output_regs),
             np.asarray(program.output_regs).dtype, sharding),
        tuple(_sds(np.shape(x), np.asarray(x).dtype, sharding)
              for x in program.instr),
    )
    return jax.jit(vm._vm_body, static_argnums=(5,)).lower(*args, "0").compile()


@pytest.mark.parametrize("kind,k,rows", [
    ("miller_product", 512, ROWS),   # folded Miller at the K=512 bucket
    ("rlc_combine", 16, 4),          # the RLC combine chunk
    ("hard_part_frobenius", 0, 1),   # the slot's one hard-part row
])
def test_vm_program_compiles_for_v5e(one_chip, kind, k, rows):
    program, fold = bls_backend._program(kind, k)
    assert fold == bls_backend._fold_for(kind, k)
    compiled = _compile_vm(program, rows, one_chip)
    mem = compiled.memory_analysis()
    if mem is not None:  # one program must fit a 16 GB v5e chip
        assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9


def _mul_lin_widths(rows):
    pad = lambda m: -(-m // pallas_fq.TILE_M) * pallas_fq.TILE_M
    return pad(rows * bls_backend.W_MUL), pad(rows * bls_backend.W_LIN)


def test_pallas_mont_mul_kernel_compiles_for_v5e(one_chip):
    mm, _ = _mul_lin_widths(ROWS)
    tile = _sds((pallas_fq.L_PAD, mm), jnp.uint32, one_chip)
    assert jax.config.jax_enable_x64
    compiled = jax.jit(pallas_fq._pallas_mm(mm, False)).lower(
        tile, tile).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_pallas_fused_step_kernel_compiles_for_v5e(one_chip):
    mm, ml = _mul_lin_widths(ROWS)
    mul = _sds((pallas_fq.L_PAD, mm), jnp.uint32, one_chip)
    lin = _sds((pallas_fq.L_PAD, ml), jnp.uint32, one_chip)
    assert jax.config.jax_enable_x64
    compiled = jax.jit(pallas_step._fused_call(mm, ml, False)).lower(
        mul, mul, lin, lin, lin).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_pubkey_gather_and_input_placement_compile_for_v5e(one_chip):
    """The index path's two modules at a 1,048,576-key table and one
    slot's 32 rows: the gather reads the table at its compact size."""
    program, fold = bls_backend._program("miller_product", 512)
    k = 512
    table = _sds((1 << 20, 2, fq.NUM_LIMBS), jnp.uint32, one_chip)
    idx = _sds((ROWS, fold, k), jnp.int32, one_chip)
    gather = jax.jit(bls_backend.pubkey_gather).lower(table, idx).compile()
    mem = gather.memory_analysis()
    if mem is not None:  # (x, y) x 15 limbs x 4 bytes a key, no padding
        assert mem.argument_size_in_bytes < 1.1 * (1 << 20) * 120
    n_dev = fold * k * 3
    n_host = len(program.input_names) - n_dev
    jax.jit(vm.vm_place_inputs).lower(
        _sds((ROWS, n_host, fq.NUM_LIMBS), jnp.uint32, one_chip),
        _sds((ROWS, n_dev, fq.NUM_LIMBS), jnp.uint32, one_chip),
        _sds((n_host,), jnp.int32, one_chip),
        _sds((n_dev,), jnp.int32, one_chip)).compile()
