"""TPU compute plane: JAX/XLA kernels for BLS12-381.

This package is the TPU-native replacement for the reference's native BLS
backend (`milagro_bls_binding`, C — reference utils/bls.py:17-22): batched
pairing-based signature verification lowered to XLA, designed so the batch
dimension maps onto TPU vector units and `shard_map` device meshes.

x64 mode is required: limb arithmetic uses uint64 accumulators.
"""
import os

import jax

jax.config.update("jax_enable_x64", True)

# The one place the persistent XLA compilation cache is placed: JAX reads
# JAX_COMPILATION_CACHE_DIR itself, so a cache set from outside is left
# alone; otherwise VM step programs compile once per shape bucket into
# <repo>/.jax_cache and later processes load them from disk.
if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    jax.config.update(
        "jax_compilation_cache_dir",
        os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))), ".jax_cache"),
    )
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
