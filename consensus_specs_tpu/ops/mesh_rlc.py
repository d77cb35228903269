"""Cross-replica RLC reduction: one Fq12 product over the whole mesh.

The mesh-sharded verify plane (ROADMAP item 1) runs each micro-batch's
Miller loops and per-chunk RLC ladders data-parallel over the device mesh
(``ops/vm.execute(mesh=)``), which leaves one sequential tail: multiplying
the per-chunk Fq12 products into the single element the combined final
exponentiation consumes. Host-multiplying them (one oracle mul per chunk)
serializes exactly the axis the mesh just parallelized — and XLA's ``psum``
cannot help, because its monoid vocabulary is scalar add/mul/min/max, not
a 12-coefficient tower-field multiply.

So the reduction rides the interconnect: one ``jax.lax.all_gather`` of
the chunk products (a few KiB per device), then every device folds the
whole gathered batch with ``towers.fq12_mul`` in one scan — an all-reduce
whose monoid is the Fq12 multiply, spelled out because the collective
library only knows scalar monoids. It replaced a local fold plus a
log2(n)-round ``ppermute`` butterfly: each inlined ``fq12_mul`` is a large
u64-emulated graph on the v5e, and the butterfly's three of them took
122 s to compile for a described v5e:2x2, the single fold 31 s (PR 21).
Fq12 multiplication is exact mod p and associative, so any association
order yields the same field element: verdicts stay bit-identical to the
single-device path, which is what tests/test_mesh_rlc.py pins.

Identity filler: inactive lanes carry f = 1 (the product's identity), so
padding the chunk-product batch up to the device count can never perturb
the combined element.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import fq
from . import towers as tw


def fq12_identity(batch_shape=()) -> np.ndarray:
    """(batch..., 12, L) host-side flat Fq12 one — the padding filler."""
    out = np.zeros(tuple(batch_shape) + (12, fq.NUM_LIMBS), dtype=np.uint64)
    out[..., 0, :] = fq.ONE_MONT
    return out


def _local_fold(fs):
    """Sequential Fq12 product of a device-local (k, 12, L) shard."""
    # derive the identity from the shard so its sharding varyingness
    # matches the scanned operand under shard_map (same trick as
    # mesh_reduce._local_fold's infinity init)
    one = jnp.zeros_like(fs[0])
    one = one.at[0, :].set(jnp.asarray(fq.ONE_MONT))

    def body(acc, f):
        return tw.fq12_mul(acc, f), None

    acc, _ = jax.lax.scan(body, one, fs)
    return acc


@functools.lru_cache(maxsize=8)
def _mesh_prod_fn(mesh, n_dev: int):
    try:
        from jax import shard_map  # jax >= 0.8
    except ImportError:
        from jax.experimental.shard_map import shard_map
    from jax.sharding import PartitionSpec as P

    axis = mesh.axis_names[0]

    def per_device(fs):  # (rows/n, 12, L) local shard of chunk products
        everyone = jax.lax.all_gather(fs, axis, tiled=True)
        return _local_fold(everyone)[None]  # every device: the product

    return jax.jit(
        shard_map(
            per_device,
            mesh=mesh,
            in_specs=P(axis),
            out_specs=P(axis),
        )
    )


def mesh_fq12_product(products: np.ndarray, mesh) -> np.ndarray:
    """Multiply a (n, 12, L) batch of flat Fq12 elements (loose Montgomery
    limbs) into ONE element over the mesh's first axis: local fold per
    device after one all-gather. Returns (12, L) (device 0's replica)."""
    n_dev = int(mesh.shape[mesh.axis_names[0]])  # reduction rides axis 0 only
    assert n_dev & (n_dev - 1) == 0, "mesh axis size must be a power of two"
    n = products.shape[0]
    pad = (-n) % n_dev
    if pad:
        products = np.concatenate(
            [products, fq12_identity((pad,))], axis=0
        )
    out = _mesh_prod_fn(mesh, n_dev)(jnp.asarray(products))
    return np.asarray(out)[0]
