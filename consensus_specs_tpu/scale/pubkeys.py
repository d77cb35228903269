"""The validator pubkey table: every registered key, by validator index,
held on the device.

A beacon node's pubkey cache holds the whole registry (1,048,576 keys on
today's mainnet), and the swap-or-not shuffle gives each slot attesters
that no other slot of the epoch has, so every key is touched once an
epoch: a cache smaller than the registry misses on most of a slot. The
table has no budget and no eviction. Its size is the registry's: each
key is KeyValidated once, through the codec's batched decompression and
subgroup check (``codec.pubkey_table_limbs``, spread over the host's
cores: the native calls release the interpreter lock), and stored as
(x, y) canonical Montgomery limbs, 120 bytes a key, in one (capacity, 2,
L) uint32 device array beside a host validity mask. The RLC engine's
index path (``bls_backend.batch_verify_rlc`` items
``("fast_aggregate_indexed", indices, message, signature)``) gathers a
committee's keys from it on the device.

Gauges: ``scale.pubkey_table_keys``, ``scale.pubkey_table_bytes``.
"""
import os
from concurrent.futures import ThreadPoolExecutor
from typing import Sequence, Union

import numpy as np

# the device array grows in whole blocks, so that a few deposits do not
# change its shape (each shape compiles the gather once)
_BLOCK = 1 << 16
_CHUNK = 8192  # keys a decode task


def rss_bytes() -> int:
    """Current resident set (linux: /proc/self/statm; 0 elsewhere)."""
    try:
        with open("/proc/self/statm") as fh:
            return int(fh.read().split()[1]) * os.sysconf("SC_PAGESIZE")
    except (OSError, IndexError, ValueError):
        return 0


def peak_rss_bytes() -> int:
    """Process high-water-mark resident set (linux VmHWM; falls back to
    the current RSS where /proc/self/status is unavailable)."""
    try:
        with open("/proc/self/status") as fh:
            for line in fh:
                if line.startswith("VmHWM:"):
                    return int(line.split()[1]) * 1024
    except (OSError, IndexError, ValueError):
        pass
    return rss_bytes()


Keys = Union[np.ndarray, Sequence[bytes]]


def _encoded(keys: Keys) -> np.ndarray:
    """(n, 48) uint8 from compressed keys; a key that is not 48 bytes
    becomes a row without the compression flag, which KeyValidate
    rejects."""
    if isinstance(keys, np.ndarray):
        if keys.dtype != np.uint8 or keys.ndim != 2 or keys.shape[1] != 48:
            raise ValueError("encoded keys must be an (n, 48) uint8 array")
        return keys
    keys = [bytes(k) for k in keys]
    if all(len(k) == 48 for k in keys):
        return np.frombuffer(b"".join(keys), dtype=np.uint8).reshape(-1, 48)
    out = np.zeros((len(keys), 48), dtype=np.uint8)
    for i, k in enumerate(keys):
        if len(k) == 48:
            out[i] = np.frombuffer(k, dtype=np.uint8)
    return out


def _capacity(n: int) -> int:
    return max(_BLOCK, -(-n // _BLOCK) * _BLOCK)


class PubkeyTable:
    """The registry's keys by validator index. ``limbs`` is the (capacity,
    2, L) uint32 device array (rows past ``n`` are zero), ``valid`` the
    host's (capacity,) KeyValidate mask (False past ``n``)."""

    def __init__(self, limbs, valid: np.ndarray, n: int):
        self.limbs = limbs
        self.valid = valid
        self.n = int(n)
        self._export_gauges()

    def __len__(self) -> int:
        return self.n

    @property
    def nbytes(self) -> int:
        """Bytes of the device array."""
        return int(self.limbs.nbytes)

    @classmethod
    def build(cls, compressed_keys: Keys) -> "PubkeyTable":
        """Decompress and KeyValidate every key (validator ``i`` is
        ``compressed_keys[i]``) and place the table on the device."""
        import jax

        encoded = _encoded(compressed_keys)
        n = encoded.shape[0]
        host, valid = _decode(encoded, _capacity(n))
        return cls(jax.device_put(host), valid, n)

    def extend(self, new_keys: Keys) -> None:
        """Append keys (deposits) as validators ``n, n + 1, ...``."""
        import jax
        import jax.numpy as jnp

        encoded = _encoded(new_keys)
        m = encoded.shape[0]
        if not m:
            return
        host, valid = _decode(encoded, m)
        lo, hi = self.n, self.n + m
        cap = self.limbs.shape[0]
        if hi > cap:
            grown = _capacity(hi)
            self.limbs = jnp.concatenate([self.limbs, jnp.zeros(
                (grown - cap,) + self.limbs.shape[1:], dtype=jnp.uint32)])
            self.valid = np.concatenate([self.valid,
                                         np.zeros(grown - cap, dtype=bool)])
        self.limbs = jax.jit(_put_rows)(self.limbs, jnp.asarray(host), lo)
        self.valid[lo:hi] = valid
        self.n = hi
        self._export_gauges()

    def _export_gauges(self) -> None:
        from ..ops import profiling

        profiling.set_gauge("scale.pubkey_table_keys", float(self.n))
        profiling.set_gauge("scale.pubkey_table_bytes", float(self.nbytes))


def _put_rows(limbs, rows, lo):
    from jax import lax

    return lax.dynamic_update_slice(limbs, rows, (lo, 0, 0))


def _decode(encoded: np.ndarray, capacity: int):
    """(capacity, 2, L) uint32 host limbs and (capacity,) validity of the
    keys in ``encoded``, decoded in chunks over the host's cores."""
    from ..obs import tracing
    from ..ops import codec, fq

    n = encoded.shape[0]
    host = np.zeros((capacity, 2, fq.NUM_LIMBS), dtype=np.uint32)
    valid = np.zeros(capacity, dtype=bool)
    bounds = [(lo, min(lo + _CHUNK, n)) for lo in range(0, n, _CHUNK)]
    route = codec.host_route()
    # only the native kernel runs outside the interpreter lock
    threads = (os.cpu_count() or 1) if route == "native" else 1
    threads = max(1, min(threads, len(bounds)))

    def one(span):
        lo, hi = span
        host[lo:hi], valid[lo:hi] = codec.pubkey_table_limbs(encoded[lo:hi])

    with tracing.span("pubkeys.table_build", n=n, threads=threads):
        with ThreadPoolExecutor(threads) as pool:
            for f in [pool.submit(one, b) for b in bounds]:
                f.result()
    codec._note_route(route, n)
    return host, valid
