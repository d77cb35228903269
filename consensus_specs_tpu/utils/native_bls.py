"""ctypes binding for the native BLS12-381 host codec (csrc/bls_host.c).

The codec's host path (ops/codec.py) runs its field arithmetic here when
the library loads: hash-to-G2 from the expand_message_xmd output, G1/G2
decompression, and the G1/G2 subgroup checks, each one native call over a
contiguous batch. Outputs are bit-identical to the raw-int Python path
(tests/test_codec_native.py), and points come back in the repo's limb
layout (ops/fq.py), so the caller converts nothing per item.

The shared object is built on first import (`make native` builds it too):
gcc to a temp name, then an atomic rename, and again whenever the source
is newer than the library or the library lacks a symbol. Without a
compiler, ``available()`` is False and the codec keeps its Python path —
the native path is a throughput component, never a correctness
dependency. A ctypes call releases the GIL, and the kernel keeps no
global mutable state, so other threads run during a call.
"""
import ctypes
import os
import subprocess
from pathlib import Path
from typing import List, Optional, Sequence, Tuple

import numpy as np

from .bls12_381 import P

_REPO = Path(__file__).resolve().parents[2]
_SRC = _REPO / "csrc" / "bls_host.c"
_SO = _REPO / "csrc" / "libbls_host.so"

_LIMBS = 15  # ops/fq.py NUM_LIMBS: the layout the kernel reads and writes

_B = ctypes.c_char_p  # a bytes argument
_P = ctypes.c_void_p  # an array's address
_N = ctypes.c_size_t
_SIGNATURES = {
    "bls_hash_to_g2": [_B, _N, _P],
    "bls_g1_decompress": [_B, _P, _N, _P, _P],
    "bls_g2_decompress": [_B, _P, _N, _P, _P],
    "bls_g1_subgroup_check": [_P, _N, _P],
    "bls_g2_subgroup_check": [_P, _N, _P],
    "bls_fp_batch_inverse": [_P, _P, _N],
    "bls_fp2_sqrt_batch": [_P, _P, _P, _N],
}

_lib = None


def _build() -> bool:
    """Compile to a temp path, then os.replace onto the final name (a
    process still mapping the old library keeps its pages; see
    utils/native_sha256._build)."""
    tmp = _SO.with_suffix(".so.%d.tmp" % os.getpid())
    try:
        subprocess.run(
            ["gcc", "-O3", "-fPIC", "-shared", "-o", str(tmp), str(_SRC)],
            check=True, capture_output=True, timeout=120,
        )
        os.replace(tmp, _SO)
        return True
    except Exception:
        tmp.unlink(missing_ok=True)
        return False


def _bind(lib) -> bool:
    """Declare every entry point; False when the library lacks one."""
    try:
        for name, argtypes in _SIGNATURES.items():
            fn = getattr(lib, name)
            fn.argtypes = argtypes
            fn.restype = ctypes.c_int
    except AttributeError:
        return False
    return True


def _stale() -> bool:
    return _SRC.exists() and (
        not _SO.exists() or _SO.stat().st_mtime < _SRC.stat().st_mtime)


def _load():
    global _lib
    if _lib is not None:
        return _lib
    if _stale():
        _build()
    if not _SO.exists():
        _lib = False
        return _lib
    try:
        lib = ctypes.CDLL(str(_SO))
        if not _bind(lib):
            # a library from before an entry point existed: rebuild, reload
            if not (_SRC.exists() and _build()):
                _lib = False
                return _lib
            lib = ctypes.CDLL(str(_SO))
            if not _bind(lib):
                lib = False
        _lib = lib
    except OSError:
        _lib = False
    return _lib


def available() -> bool:
    return bool(_load())


def _addr(arr: np.ndarray) -> int:
    return arr.ctypes.data


def _check(rc: int) -> None:
    if rc == -1:
        raise MemoryError("native BLS kernel: allocation failed")


def _need(ok: bool, what: str) -> None:
    """The buffers' sizes, checked before a pointer crosses into C."""
    if not ok:
        raise ValueError(f"native BLS kernel: {what}")


def hash_to_g2(uniform: bytes, n: int) -> Tuple[np.ndarray, int]:
    """``uniform``: n x 256 bytes, each message's expand_message_xmd output
    (two Fq2 draws of 64-byte big-endian coefficients). Returns the (n, 4,
    L) affine limb stacks and a status: 0, or 1 (SSWU found no square
    root) / 2 (a point at infinity), neither reachable for valid curve
    constants."""
    _need(len(uniform) == 256 * n, "hash_to_g2 wants 256 bytes a message")
    out = np.zeros((n, 4, _LIMBS), dtype=np.uint64)
    rc = _load().bls_hash_to_g2(uniform, n, _addr(out))
    _check(rc)
    return out, rc


def _decompress(fn, raw: bytes, signs: Sequence[bool], width: int, coords: int):
    n = len(signs)
    _need(len(raw) == width * n, f"decompress wants {width} bytes a point")
    sign = np.asarray(signs, dtype=np.uint8)
    out = np.zeros((n, coords, _LIMBS), dtype=np.uint64)
    status = np.zeros(n, dtype=np.int32)
    if n:
        fn(raw, _addr(sign), n, _addr(out), _addr(status))
    return out, status


def g1_decompress(raw: bytes, signs: Sequence[bool]):
    """``raw``: n x 48 flag-stripped x bytes; ``signs``: the sign flags.
    Returns ((n, 2, L) [x, y] limbs, (n,) status: 0 ok, 1 x out of range,
    2 x not on curve)."""
    return _decompress(_load().bls_g1_decompress, raw, signs, 48, 2)


def g2_decompress(raw: bytes, signs: Sequence[bool]):
    """``raw``: n x 96 flag-stripped bytes (x.c1, then x.c0). Returns
    ((n, 4, L) [x.0, x.1, y.0, y.1] limbs, (n,) status as for G1)."""
    return _decompress(_load().bls_g2_decompress, raw, signs, 96, 4)


def _subgroup(fn, points: np.ndarray, coords: int) -> np.ndarray:
    pts = np.ascontiguousarray(points, dtype=np.uint64)
    _need(pts.ndim == 3 and pts.shape[1:] == (coords, _LIMBS),
          f"subgroup check wants (n, {coords}, {_LIMBS}) limbs")
    n = pts.shape[0]
    ok = np.zeros(n, dtype=np.uint8)
    if n:
        fn(_addr(pts), n, _addr(ok))
    return ok.astype(bool)


def g1_subgroup_check(points: np.ndarray) -> np.ndarray:
    """(n, 2, L) on-curve affine limbs -> bool (n,) membership in G1."""
    return _subgroup(_load().bls_g1_subgroup_check, points, 2)


def g2_subgroup_check(points: np.ndarray) -> np.ndarray:
    """(n, 4, L) on-curve affine limbs -> bool (n,) membership in G2."""
    return _subgroup(_load().bls_g2_subgroup_check, points, 4)


def _words(vals: Sequence[int]) -> np.ndarray:
    _need(all(0 <= v < P for v in vals), "field values must lie in [0, p)")
    return np.frombuffer(
        b"".join(v.to_bytes(48, "little") for v in vals), dtype=np.uint64
    ).copy()


def _ints(words: np.ndarray) -> List[int]:
    raw = words.tobytes()
    return [int.from_bytes(raw[48 * i: 48 * (i + 1)], "little")
            for i in range(len(raw) // 48)]


def fp_batch_inverse(vals: Sequence[int]) -> List[int]:
    """1/v mod p for each v < p through the kernel's batch-inversion
    ladder; inv(0) == 0."""
    if not vals:
        return []
    buf = _words(vals)
    _check(_load().bls_fp_batch_inverse(_addr(buf), _addr(buf), len(vals)))
    return _ints(buf)


def fp2_sqrt_batch(
    vals: Sequence[Tuple[int, int]]
) -> List[Optional[Tuple[int, int]]]:
    """The oracle Fq2.sqrt of each (c0, c1), with its root choice; None
    where the oracle returns None."""
    n = len(vals)
    if n == 0:
        return []
    buf = _words([c for v in vals for c in v])
    out = np.zeros_like(buf)
    ok = np.zeros(n, dtype=np.uint8)
    _load().bls_fp2_sqrt_batch(_addr(buf), _addr(out), _addr(ok), n)
    roots = _ints(out)
    return [(roots[2 * i], roots[2 * i + 1]) if ok[i] else None
            for i in range(n)]


# the first import builds the library: ops/codec.py imports this module, so
# the one gcc run falls in a process's set-up, never in a timed call
_load()
