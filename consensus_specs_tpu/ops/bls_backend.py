"""TPU BLS backend: batched aggregate-signature verification on device.

This is the TPU-native replacement for the reference's C BLS backend
(`milagro_bls_binding`, selected at reference utils/bls.py:17-22) behind the
same switchboard API, plus the batched entry points the reference never had —
the north-star workload (BASELINE.json) of verifying every attestation of an
epoch in one device pipeline.

Pipeline (see ops/vm.py and ops/vmlib.py for the execution model):

  HOST  decode/KeyValidate pubkeys (LRU-cached with their Montgomery limb
        encodings), decode+subgroup-check signatures, hash messages to G2 —
        prewarmed array-wide by the BATCHED input codec (ops/codec.py:
        vectorized decompression, VM-program subgroup checks, native-SHA
        hash-to-G2), bit-identical to the oracle's rejection rules; the
        per-item exact-int Python path remains the cache-miss fallback.
  PROG A (device) aggregate K projective pubkeys (complete additions; masked
        lanes are infinity) + both Miller loops -> f, agg_Z.
  HOST  easy part of the final exponentiation (one exact Fq12 inversion +
        frobenius) — microseconds each, and the only data-dependent-depth
        op in the pipeline.
  PROG B (device) HHT hard part with cyclotomic squarings -> res.
  HOST  res == 1, AND precheck AND agg != infinity.

Verification results are bools; a verification whose host-side prep fails
(bad encoding, subgroup failure, infinity pubkey) is False without touching
the device, matching the oracle's exception-swallowing wrappers
(reference utils/bls.py:47-74).
"""
import functools
import os
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..obs import tracing
from ..utils import bls12_381 as O
from ..utils.bls12_381 import P
from . import fq, vm, vmlib

DST = b"BLS_SIG_BLS12381G2_XMD:SHA-256_SSWU_RO_POP_"


# VM shape buckets (compile cost is per bucket; the assembled-program build is
# disk-cached under .vm_cache/ and in-process lru_cached; the XLA executables
# persist via the compilation cache that ops/__init__.py places).
#
# LANE FOLDING: a single verification item's instruction-level parallelism
# fills only ~1/3 of the mul lanes (Miller) and ~7% (hard part) — the
# schedules are depth-bound, and idle lanes burn the same SIMD work as live
# ones. Folding F independent items into one program multiplies per-step ILP
# by F: measured per-item mul-slot cost drops ~2x (Miller) and ~10x (hard
# part), the single largest device-side win toward the BASELINE north star.
W_MUL = 96
W_LIN = 192
PAD_STEPS = 256
# 160 covers the mainnet target committee (~146 = 300k/2048) without padding
# to 256 — less aggregation waste and 1.6x less input transfer per item
_K_BUCKETS = [1, 2, 4, 8, 16, 32, 64, 128, 160, 256, 512, 1024, 2048]

_VM_CACHE_VERSION = 2  # v2: per-program fingerprints (ISSUE 10)


def _k_bucket(k: int) -> int:
    for b in _K_BUCKETS:
        if k <= b:
            return b
    raise ValueError(f"committee size {k} exceeds max bucket {_K_BUCKETS[-1]}")


def _pow2(n: int) -> int:
    b = 1
    while b < n:
        b <<= 1
    return b


def _pow2_floor(n: int) -> int:
    b = 1
    while b * 2 <= n:
        b <<= 1
    return b


# codec-plane programs (ops/codec.py): serial complete-addition ladders
# with little per-item ILP, so folding is the main lane-utilization lever;
# tables sized so assembly stays a few seconds per variant
_CODEC_FOLDS = {"g1_subgroup": 4, "g2_subgroup": 8, "h2g_finish": 4}


def _fold_for(kind: str, k: int, n_items: int = 1 << 30) -> int:
    """Items folded per program row — enough to saturate the lanes, capped
    so the register file stays modest for wide-committee buckets, and
    never exceeding the batch itself (a single verify must not pay for a
    mostly-filler folded program)."""
    from . import vm_compile

    if vm_compile.exec_mode() == "fused":
        # the straight-line lowering has no idle lanes to saturate:
        # folding only duplicates the op stream (F times the trace/compile
        # and F times the per-level work on every row), while independent
        # items vectorize for free on the batch axis — so a pinned fused
        # mode always runs the fold-1 program at batch = n_items
        return 1
    if kind == "hard_part":
        table = 32
    elif kind in ("hard_part_windowed", "hard_part_frobenius"):
        # the width-for-depth variants go work-bound past fold 8 (their
        # schoolbook const-folded squarings carry ~25% more muls than the
        # legacy chain), so folding further only grows the register file
        # — rows past 8 ride the batch axis instead
        table = 8
    elif kind == "rlc_combine":
        # k is the combine's chunk size (f's per instance); a 16-f chunk
        # already saturates the mul lanes, smaller chunks fold up to it
        table = max(1, 16 // max(1, k))
    elif kind in _CODEC_FOLDS:
        table = _CODEC_FOLDS[kind]
    elif k <= 160:
        table = 8
    elif k <= 256:
        table = 4
    elif k <= 512:
        table = 2
    else:
        table = 1
    return min(table, _pow2_floor(max(1, n_items)))


def _vm_cache_dir() -> str:
    # CONSENSUS_SPECS_TPU_VM_CACHE overrides the repo-local default —
    # the cold-start bench children point it (and the XLA cache) at
    # fresh temp dirs so BOTH arms measure a genuinely fresh runner
    d = os.environ.get("CONSENSUS_SPECS_TPU_VM_CACHE") or os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
        ".vm_cache",
    )
    os.makedirs(d, exist_ok=True)
    return d


@functools.lru_cache(maxsize=1)
def _core_fingerprint_parts() -> Tuple[bytes, bytes]:
    """(vm+fq source bytes, shared vmlib source bytes): the cache-key
    components EVERY program depends on — vm.py's scheduling semantics,
    fq.py's limb layout / bound tracking, and the vmlib helpers no single
    builder claims (F2/Fq12 algebra, Miller steps, cyclotomic ladders)."""
    core = b""
    for mod in (vm, fq):
        try:
            with open(mod.__file__, "rb") as fh:
                core += fh.read()
        except OSError:
            core += repr(mod).encode()
    shared, _ = vmlib.builder_source_parts("")
    return core, shared.encode()


@functools.lru_cache(maxsize=None)
def _program_fingerprint(kind: str) -> str:
    """PER-PROGRAM disk-cache fingerprint: hash of (builder-local source,
    shared vmlib source, vm+fq sources). Editing one builder's emit
    function re-keys only that kind's cached programs — tier-1 after a
    small vmlib edit re-pays assembly for the touched kind, not the whole
    registry (the ISSUE 10 satellite; the old single source-hash key made
    every edit a full-cache invalidation). Editing a shared helper still
    re-keys everything, which is exactly right."""
    import hashlib

    core, shared = _core_fingerprint_parts()
    _, local = vmlib.builder_source_parts(kind)
    h = hashlib.sha256()
    h.update(core)
    h.update(shared)
    h.update(local.encode())
    return h.hexdigest()[:10]


@functools.lru_cache(maxsize=None)
def _program(kind: str, k: int = 0, fold: int = None) -> Tuple[vm.Program, int]:
    """Assembled program + its fold factor. Assembly of a folded program
    used to be seconds-to-minutes of host Python; the bucketed scheduler
    (+ native kernel) cut it to ~1s/Mop, and the result is still
    disk-cached per-program (a fresh checkout, as on the chip, assembles
    once per program and kind)."""
    import pickle

    if fold is None:
        fold = _fold_for(kind, k)
    path = os.path.join(
        _vm_cache_dir(),
        f"v{_VM_CACHE_VERSION}_{_program_fingerprint(kind)}_{kind}_k{k}_f{fold}"
        f"_w{W_MUL}x{W_LIN}_p{PAD_STEPS}.pkl",
    )
    t0 = time.perf_counter()
    try:
        with open(path, "rb") as fh:
            loaded = pickle.load(fh)
        try:
            os.utime(path)  # mark touched: vm-cache-prune evicts by idle age
        except OSError:
            pass
        _attach_fused_key(loaded, kind, k, fold)
        _note_program(kind, k, fold, loaded, time.perf_counter() - t0, True)
        return loaded, fold
    except Exception:
        pass  # absent/stale cache: assemble below
    builder = vmlib.BUILDERS.get(kind)
    if builder is None:
        raise ValueError(kind)
    prog = builder(k, fold)
    assembled = prog.assemble(
        w_mul=W_MUL,
        w_lin=W_LIN,
        pad_steps_to=PAD_STEPS,
        pad_regs_to=_pow2(64),
        annotate=False,  # IR annotations are a vm_analysis concern
    )
    _attach_fused_key(assembled, kind, k, fold)
    _note_program(kind, k, fold, assembled, time.perf_counter() - t0, False)
    try:
        tmp = f"{path}.{os.getpid()}.tmp"
        with open(tmp, "wb") as fh:
            pickle.dump(assembled, fh)
        os.replace(tmp, path)
    except Exception:
        pass  # cache write is an optimization only
    return assembled, fold


def _attach_fused_key(assembled, kind: str, k: int, fold: int) -> None:
    """Stamp the program's kind and K (its span and XLA module name, on
    every program), and its cache identity onto its schedule metadata so
    the fused lowering (ops/vm_compile.py) can disk-cache its plan under
    a matching ``.vm_cache`` key. Pre-meta pickles (meta=None) keep no
    identity — they cannot lower fused anyway (no schedule metadata)."""
    assembled.kind, assembled.k = kind, k
    try:
        if isinstance(assembled.meta, dict):
            assembled.meta.setdefault(
                "fused_key", (kind, k, fold, _program_fingerprint(kind)))
    except Exception:
        pass  # identity stamping is an optimization, never a failure


_VM_CACHE_NAME_RE = None  # compiled lazily (module import stays light)
_FUSED_PLAN_NAME_RE = None
_FUSED_STRUCT_NAME_RE = None


def _vm_cache_entry_stale(name: str) -> bool:
    """True when a ``.vm_cache`` entry can NEVER hit again in this source
    tree: its version prefix is not the current ``_VM_CACHE_VERSION``, or
    it names a known program kind whose per-program fingerprint has moved
    (the builder was edited). Fused structural plans
    (``fusedplan_l<lowering>_v<cache>_<fp>_<kind>_…``) additionally
    re-key on ``vm_compile.LOWERING_VERSION`` — a lowering change evicts
    every fused artifact without touching the interpreter tensors, and
    vice versa — and shared structure bodies
    (``fusedstruct_l<lowering>_<hash>``) re-key on the lowering version
    alone (their referenced-ness is ``prune_vm_cache``'s concern). The
    RETIRED PR 13 per-program ``fused_l…`` keying is stale on sight:
    nothing in this tree can ever read those entries again. Unknown
    kinds are kept — age/size still bound them — so a checkout running
    older code is never sabotaged."""
    global _VM_CACHE_NAME_RE, _FUSED_PLAN_NAME_RE, _FUSED_STRUCT_NAME_RE
    if _VM_CACHE_NAME_RE is None:
        import re

        _VM_CACHE_NAME_RE = re.compile(
            r"^v(\d+)_([0-9a-f]+)_(.+)_k\d+_f\d+_w\d+x\d+_p\d+\.pkl$")
        _FUSED_PLAN_NAME_RE = re.compile(
            r"^fusedplan_l(\d+)_v(\d+)_([0-9a-f]+)_(.+)_k\d+_f\d+"
            r"_w\d+x\d+_p\d+_c\d+\.pkl$")
        _FUSED_STRUCT_NAME_RE = re.compile(
            r"^fusedstruct_l(\d+)_([0-9a-f]+)\.pkl$")
    if name.startswith("fusedplan_"):
        m = _FUSED_PLAN_NAME_RE.match(name)
        if not m:
            return False
        from . import vm_compile

        lowering, version, fp, kind = (m.group(1), m.group(2), m.group(3),
                                       m.group(4))
        if int(lowering) != vm_compile.LOWERING_VERSION:
            return True
        if int(version) != _VM_CACHE_VERSION:
            return True
        if kind in vmlib.BUILDERS and fp != _program_fingerprint(kind):
            return True
        return False
    if name.startswith("fusedstruct_"):
        m = _FUSED_STRUCT_NAME_RE.match(name)
        if not m:
            return False
        from . import vm_compile

        return int(m.group(1)) != vm_compile.LOWERING_VERSION
    if name.startswith("fused_"):
        # the PR 13 per-program fused plan keying, superseded by the
        # structural split above: evict on sight regardless of version
        return True
    m = _VM_CACHE_NAME_RE.match(name)
    if not m:
        return False
    version, fp, kind = m.group(1), m.group(2), m.group(3)
    if int(version) != _VM_CACHE_VERSION:
        return True
    if kind in vmlib.BUILDERS and fp != _program_fingerprint(kind):
        return True
    return False


def prune_vm_cache(max_age_days: float = None, max_bytes: int = None,
                   cache_dir: str = None, evict_stale: bool = True) -> dict:
    """Bound ``.vm_cache/`` growth (`make vm-cache-prune`): editing a
    builder re-keys its cached programs (per-program source fingerprints,
    ``_program_fingerprint``), so superseded pickles accumulate without
    eviction. Three rules:

    - entries whose cache version or per-program fingerprint no longer
      matches the current sources are evicted immediately (they can never
      hit again; ``evict_stale=False`` disables) — including every entry
      of the RETIRED PR 13 per-program ``fused_l…`` keying, superseded by
      the structural ``fusedplan_``/``fusedstruct_`` split;
    - entries idle longer than ``max_age_days`` are evicted
      (env VM_CACHE_MAX_AGE_DAYS, default 30; <= 0 disables the age rule;
      ``_program`` touches entries on every disk hit, so mtime == last
      use);
    - if the cache still exceeds ``max_bytes`` the oldest entries go until
      it fits (env VM_CACHE_MAX_BYTES, default 2 GiB; <= 0 disables);
    - SHARED structure bodies (``fusedstruct_…``, referenced by any
      number of plans) follow their referencing plans, not the age/size
      rules: a structure referenced by at least one surviving
      ``fusedplan_`` entry is kept, an orphaned one is evicted (it is
      re-derived in milliseconds if ever needed again). A plan whose
      refs cannot be read contributes no refs — its structures fall out
      and the next load falls back to re-derivation rather than erroring.

    Returns {"kept", "evicted", "kept_bytes", "evicted_bytes"}."""
    if max_age_days is None:
        max_age_days = float(os.environ.get("VM_CACHE_MAX_AGE_DAYS", "30"))
    if max_bytes is None:
        max_bytes = int(os.environ.get("VM_CACHE_MAX_BYTES",
                                       str(2 * 1024 ** 3)))
    if cache_dir is None:
        cache_dir = _vm_cache_dir()
    now = time.time()
    entries = []  # (mtime, size, path)
    structs = []  # (mtime, size, path, name) — referenced-ness governed
    evict = []
    for name in os.listdir(cache_dir):
        # cache entries plus crash-orphaned "<name>.pkl.<pid>.tmp" files
        # from an interrupted _program write; foreign files stay untouched
        if not (name.endswith(".pkl")
                or (".pkl." in name and name.endswith(".tmp"))):
            continue
        path = os.path.join(cache_dir, name)
        try:
            st = os.stat(path)
        except OSError:
            continue
        if evict_stale and name.endswith(".pkl") and _vm_cache_entry_stale(name):
            evict.append((st.st_mtime, st.st_size, path))
            continue
        if name.startswith("fusedstruct_") and name.endswith(".pkl"):
            structs.append((st.st_mtime, st.st_size, path, name))
            continue
        entries.append((st.st_mtime, st.st_size, path))
    entries.sort()  # oldest (least recently used) first
    if max_age_days > 0:
        cutoff = now - max_age_days * 86400.0
        while entries and entries[0][0] < cutoff:
            evict.append(entries.pop(0))
    if max_bytes > 0:
        total = sum(size for _, size, _ in entries)
        while entries and total > max_bytes:
            oldest = entries.pop(0)
            total -= oldest[1]
            evict.append(oldest)
    # structure entries: keep while any SURVIVING plan references them
    if structs:
        import pickle

        referenced = set()
        for _, _, path in entries:
            if not os.path.basename(path).startswith("fusedplan_"):
                continue
            try:
                with open(path, "rb") as fh:
                    refs = pickle.load(fh).get("struct_refs") or ()
                referenced.update(refs)
            except Exception:
                pass  # unreadable plan: contributes no refs
        for mt, size, path, name in structs:
            key = name[:-len(".pkl")].rsplit("_", 1)[-1]
            if key in referenced:
                entries.append((mt, size, path))
            else:
                evict.append((mt, size, path))
    evicted_bytes = 0
    evicted_entries = 0
    for _, size, path in evict:
        try:
            os.remove(path)
            evicted_bytes += size
            evicted_entries += 1
        except OSError:
            pass
    # publish what the prune reclaimed (previously invisible: the only
    # record was the returned dict the Make target printed and dropped)
    from . import profiling

    profiling.set_gauge("bls.vm_cache_pruned_entries", evicted_entries)
    profiling.set_gauge("bls.vm_cache_pruned_bytes", evicted_bytes)
    return {
        "kept": len(entries),
        "evicted": evicted_entries,
        "kept_bytes": sum(size for _, size, _ in entries),
        "evicted_bytes": evicted_bytes,
    }


def _note_program(kind: str, k: int, fold: int, assembled, seconds: float,
                  disk_hit: bool) -> None:
    """Feed the per-program observability registry (obs/programs.py):
    steps, register-file size, assembly-or-load time, .vm_cache/ hit/miss.
    Called once per (kind, k, fold) per process (the lru_cache on
    _program absorbs repeats); never allowed to break program resolution."""
    try:
        from ..obs import flight, programs as obs_programs

        key = f"{kind}[k={k},fold={fold}]"
        obs_programs.note_assembly(
            key,
            n_steps=assembled.n_steps, n_regs=assembled.n_regs,
            seconds=seconds, disk_cache_hit=disk_hit,
        )
        # flight journal: program resolutions are the "why was this run
        # slow" forensic — a .vm_cache miss means seconds-scale list
        # scheduling was paid inline (an assembly STALL when it crossed
        # one second, the threshold the measured ~250k ops/sec scheduler
        # makes meaningful)
        flight.note("vm", "program_resolved", key=key,
                    cache="hit" if disk_hit else "miss",
                    seconds=round(seconds, 4))
        if not disk_hit and seconds >= 1.0:
            flight.note("vm", "assembly_stall", key=key,
                        seconds=round(seconds, 4),
                        steps=int(assembled.n_steps))
    except Exception:
        pass


# ---------------------------------------------------------------------------
# host-side codecs (cached limb encodings)
# ---------------------------------------------------------------------------

_INF_G1 = (
    fq.to_mont_int(0),
    fq.to_mont_int(1),
    fq.to_mont_int(0),
)  # projective infinity (0:1:0)
_ONE_LIMBS = fq.to_mont_int(1)

# G2 generator limbs, stacked (x.0, x.1, y.0, y.1) x L — filler for
# inactive batch lanes
_G2GEN = O.ec_to_affine(O.G2_GEN)
_G2GEN_LIMBS = np.stack(
    [
        fq.to_mont_int(_G2GEN[0].c0),
        fq.to_mont_int(_G2GEN[0].c1),
        fq.to_mont_int(_G2GEN[1].c0),
        fq.to_mont_int(_G2GEN[1].c1),
    ]
)

_G2_COMPS = ("x.0", "x.1", "y.0", "y.1")


def _pubkey_limbs_compute(pk: bytes):
    """KeyValidate + Montgomery-encode; failures are returned as ValueError
    VALUES (so prewarm workers can ship them back across the pool)."""
    aff = O.g1_from_bytes(pk)
    if aff is None:
        return ValueError("pubkey is the point at infinity")
    if not O.is_in_g1_subgroup(O.ec_from_affine(aff)):
        return ValueError("pubkey not in G1 subgroup")
    return fq.to_mont_int(aff[0].n), fq.to_mont_int(aff[1].n)


def _pubkey_limbs(pk: bytes) -> Tuple[np.ndarray, np.ndarray]:
    """Cached: validator pubkeys repeat across every slot of an epoch."""
    return _cached(_PK_CACHE, pk, _pubkey_limbs_compute)


_SIG_CACHE: Dict[bytes, object] = {}
_MSG_CACHE: Dict[bytes, np.ndarray] = {}
_PK_CACHE: Dict[bytes, object] = {}
# pubkeys get the big cache: a mainnet validator set is ~1M keys and they
# repeat every slot; messages/signatures churn per epoch
_CACHE_CAPS = {id(_SIG_CACHE): 1 << 16, id(_MSG_CACHE): 1 << 16,
               id(_PK_CACHE): 1 << 20}


def _cache_put(cache: Dict, key: bytes, value) -> None:
    """Insert with the shared eviction policy: at capacity, drop the
    least-recently-USED half (hits refresh insertion order below, so dict
    order IS recency order) — wiping a whole cache would drop every hot
    validator key at once and cause a multi-second recompute cliff.
    Removals are tolerant pops: the serve pipeline's prep stage writes
    these dicts while the device stage reads them."""
    if len(cache) >= _CACHE_CAPS[id(cache)]:
        for k in list(cache.keys())[: len(cache) // 2]:
            cache.pop(k, None)
    cache[key] = value


def _cached(cache: Dict, key: bytes, compute):
    """Shared accessor: compute fns RETURN a ValueError value on validation
    failure (so pool workers can ship it); only successes are cached —
    attacker-supplied invalid inputs can neither occupy slots nor force the
    eviction wipe — and the result/raise semantics stay uniform.

    Concurrency: the serve pipeline's prep stage warms these dicts while
    the device stage reads them, so every remove is a tolerant pop — a
    key another thread just refreshed/evicted must not raise here (the
    worst case is a recompute or a slightly stale recency order, both
    harmless)."""
    v = cache.get(key)
    if v is None:
        v = compute(key)
        if not isinstance(v, ValueError):
            _cache_put(cache, key, v)
    else:
        # refresh recency so prewarmed hot keys outlive per-epoch churn
        cache.pop(key, None)
        cache[key] = v
    if isinstance(v, ValueError):
        raise v
    return v


def _signature_limbs_compute(sig: bytes):
    """(4, L) stacked Montgomery limbs, or the ValueError to re-raise —
    exceptions are VALUES here so prewarm workers can ship them back."""
    aff = O.g2_from_bytes(sig)
    if aff is None:
        return ValueError("signature is the point at infinity")
    if not O.is_in_g2_subgroup(O.ec_from_affine(aff)):
        return ValueError("signature not in G2 subgroup")
    x, y = aff
    return np.stack(
        [
            fq.to_mont_int(x.c0),
            fq.to_mont_int(x.c1),
            fq.to_mont_int(y.c0),
            fq.to_mont_int(y.c1),
        ]
    )


def _signature_limbs(sig: bytes) -> np.ndarray:
    return _cached(_SIG_CACHE, sig, _signature_limbs_compute)


def _message_limbs_compute(message: bytes) -> np.ndarray:
    x, y = O.ec_to_affine(O.hash_to_g2(message, DST))
    return np.stack(
        [
            fq.to_mont_int(x.c0),
            fq.to_mont_int(x.c1),
            fq.to_mont_int(y.c0),
            fq.to_mont_int(y.c1),
        ]
    )


def _message_limbs(message: bytes) -> np.ndarray:
    """(4, L) stacked hash-to-G2 point limbs (dict-cached; prewarmable)."""
    return _cached(_MSG_CACHE, message, _message_limbs_compute)


_PREWARM_FNS = {
    "msg": _message_limbs_compute,
    "sig": _signature_limbs_compute,
    "pk": _pubkey_limbs_compute,
}


def _pool_map(fn, work, procs: int, chunksize: int, timeout: float):
    """``fn`` over ``work`` in a process pool (fork by default,
    CONSENSUS_SPECS_TPU_HASH_MP_CTX) whose workers exit on their own:
    terminating them (the pool's with-exit) makes each worker that
    inherited libtpu's signal handler log a crash. A timeout or a worker
    error raises, and the with-exit then terminates the pool."""
    import multiprocessing as mp

    ctx = mp.get_context(os.environ.get("CONSENSUS_SPECS_TPU_HASH_MP_CTX",
                                        "fork"))
    with ctx.Pool(procs) as pool:
        done = pool.map_async(fn, work, chunksize=chunksize).get(
            timeout=timeout)
        pool.close()
        pool.join()
    return done


def _prewarm_worker(args):
    kind, payload = args
    try:
        return kind, payload, _PREWARM_FNS[kind](payload)
    except Exception:
        # TRANSIENT worker failure (validation failures come back as
        # ValueError VALUES from the compute fn): don't poison the cache,
        # let the serial item loop recompute
        return kind, payload, None


_POOL_BROKEN = False

# prep-plane observability (ISSUE 2 satellite): which path warmed the
# caches, how many items silently degraded to serial per-item prep, and
# whether the pool latch is set — exported as ops/profiling gauges and
# read by the serve plane's metrics snapshot
PREP_STATS = {
    "codec_batches": 0,
    "codec_items": 0,
    "pool_batches": 0,
    "pool_items": 0,
    "serial_fallback_items": 0,
    "pool_broken_latches": 0,
}


def _set_pool_broken(flag: bool) -> None:
    global _POOL_BROKEN
    _POOL_BROKEN = flag
    if flag:
        PREP_STATS["pool_broken_latches"] += 1
    from . import profiling

    profiling.set_gauge("bls.prep_pool_broken", 1.0 if flag else 0.0)


def _note_serial_fallback(n: int) -> None:
    PREP_STATS["serial_fallback_items"] += n
    from . import profiling

    profiling.set_gauge(
        "bls.prep_serial_fallback_items", PREP_STATS["serial_fallback_items"]
    )


def reset_prep_state() -> None:
    """reset_call_counts()-style recovery hook: clear the pool-broken latch
    and the prep counters, so a long-lived service can retry the pool after
    a transient failure instead of latching into serial prep forever."""
    global _POOL_BROKEN
    _POOL_BROKEN = False
    for k in PREP_STATS:
        PREP_STATS[k] = 0
    from . import profiling

    profiling.set_gauge("bls.prep_pool_broken", 0.0)
    profiling.set_gauge("bls.prep_serial_fallback_items", 0.0)


def _codec_enabled() -> bool:
    return os.environ.get("CONSENSUS_SPECS_TPU_BATCH_CODEC", "1") != "0"


def _prewarm_batched(msgs, sigs, pks) -> None:
    """Fill the caches through the batched input codec (ops/codec.py):
    array-wide decompression + subgroup checks + hash-to-G2. Validation
    failures come back as ValueError VALUES and are NOT cached, exactly
    like the per-item `_cached` policy (the serial item loop re-derives
    and raises them); at-capacity inserts evict like `_cached` too, so a
    full cache never silently discards a whole prepped batch."""
    from . import codec

    if msgs:
        with tracing.span("codec.hash_to_g2", n=len(msgs)):
            for m, v in zip(msgs, codec.message_limbs_batch(msgs, DST)):
                _cache_put(_MSG_CACHE, m, v)
    if sigs:
        with tracing.span("codec.signatures", n=len(sigs)):
            for s, v in zip(sigs, codec.signature_limbs_batch(sigs)):
                if not isinstance(v, ValueError):
                    _cache_put(_SIG_CACHE, s, v)
    if pks:
        with tracing.span("codec.pubkeys", n=len(pks)):
            for p, v in zip(pks, codec.pubkey_limbs_batch(pks)):
                if not isinstance(v, ValueError):
                    _cache_put(_PK_CACHE, p, v)


def prewarm_host_caches(messages: Sequence[bytes], signatures: Sequence[bytes],
                        pubkeys: Sequence[bytes] = ()):
    """Fill the hash-to-G2, signature-decode, and pubkey caches.

    Default path: the BATCHED input codec (ops/codec.py) — vectorized
    decompression with shared square-root chains and a Montgomery
    batch-inversion ladder, VM-program subgroup checks, and native-SHA
    batched hash-to-G2 — one array-wide pass instead of per-item
    pure-Python prep (which costs ~29 ms/hash + ~8 ms/decode and would
    serialize an epoch's ~2k distinct messages into minutes).

    CONSENSUS_SPECS_TPU_BATCH_CODEC=0 (or a codec failure) falls back to
    the legacy process pool (CONSENSUS_SPECS_TPU_HASH_PROCS workers,
    default min(8, cpus)); a pool failure latches `_POOL_BROKEN` and
    degrades to the serial per-item path — both visible via PREP_STATS /
    profiling gauges and recoverable via `reset_prep_state()`."""
    msgs = [m for m in dict.fromkeys(messages) if m not in _MSG_CACHE]
    sigs = [s for s in dict.fromkeys(signatures) if s not in _SIG_CACHE]
    pks = [p for p in dict.fromkeys(pubkeys) if p not in _PK_CACHE]
    total = len(msgs) + len(sigs) + len(pks)
    if total == 0:
        return
    if _codec_enabled():
        # no size floor here: the in-process codec has none of the pool's
        # spawn overhead, and small duplicate-heavy serve flushes are
        # exactly where per-item misses would stall the device stage
        try:
            _prewarm_batched(msgs, sigs, pks)
            PREP_STATS["codec_batches"] += 1
            PREP_STATS["codec_items"] += total
            return
        except Exception:
            from . import profiling

            profiling.record("bls.codec_prewarm_error", 0.0)
            # fall through to the pool path
    _prewarm_pool(msgs, sigs, pks)


def _prewarm_pool(msgs, sigs, pks) -> None:
    # re-filter: a codec prewarm that failed partway may already have
    # cached some kinds — the pool must not re-pay ~29 ms/hash for them
    work = [("msg", m) for m in msgs if m not in _MSG_CACHE]
    work += [("sig", s) for s in sigs if s not in _SIG_CACHE]
    work += [("pk", p) for p in pks if p not in _PK_CACHE]
    if len(work) < 16:
        # pool spawn overhead would exceed the serial recompute; these
        # items degrade to per-item prep in the verify loop — count them
        if work:
            _note_serial_fallback(len(work))
        return
    procs = int(
        os.environ.get(
            "CONSENSUS_SPECS_TPU_HASH_PROCS", str(min(8, os.cpu_count() or 1))
        )
    )
    if procs <= 1:
        _note_serial_fallback(len(work))
        return
    if _POOL_BROKEN:
        # a pool already hung/died this process: go straight serial (the
        # latch is visible as the bls.prep_pool_broken gauge and clears
        # via reset_prep_state())
        _note_serial_fallback(len(work))
        return
    try:
        # 'fork' after jax (and libtpu) initialization: the workers are
        # pure Python and never touch JAX, so they never reach for the
        # chip the parent holds (chip_smoke.py runs a pool batch after
        # the chip is up). Children inherit runtime locks, so a deadline
        # still guards: a hung pool degrades to the serial path instead
        # of blocking verification. ('spawn' children would re-import
        # the package, and with it JAX, in processes that must stay off
        # the chip.)
        with tracing.span("codec.pool", n=len(work)):
            done = _pool_map(_prewarm_worker, work, procs, chunksize=8,
                             timeout=max(120.0, 0.2 * len(work)))
        for kind, payload, value in done:
            if value is None:
                _note_serial_fallback(1)
                continue  # transient worker failure: recompute serially
            cache = {"msg": _MSG_CACHE, "sig": _SIG_CACHE,
                     "pk": _PK_CACHE}[kind]
            if not isinstance(value, ValueError):
                _cache_put(cache, payload, value)
        PREP_STATS["pool_batches"] += 1
        PREP_STATS["pool_items"] += len(work)
    except Exception:
        # serial fallback: the item loop computes on demand. Latch the
        # failure — without this, every subsequent batch would re-pay the
        # full pool deadline (>=120 s) before degrading, each time.
        _set_pool_broken(True)
        _note_serial_fallback(len(work))


def _flat_ints_to_oracle(coeffs: Sequence[int]) -> O.Fq12:
    sixes = []
    for half in range(2):
        fq2s = []
        for vi in range(3):
            k = 2 * vi + half
            b = coeffs[k + 6]
            a = (coeffs[k] + b) % P
            fq2s.append(O.Fq2(a, b))
        sixes.append(O.Fq6(*fq2s))
    return O.Fq12(sixes[0], sixes[1])


def _oracle_to_flat_ints(x: O.Fq12) -> List[int]:
    coeffs = [0] * 12
    for half, f6 in enumerate((x.c0, x.c1)):
        for vi, f2 in enumerate((f6.c0, f6.c1, f6.c2)):
            k = 2 * vi + half
            coeffs[k] = (coeffs[k] + f2.c0 - f2.c1) % P
            coeffs[k + 6] = (coeffs[k + 6] + f2.c1) % P
    return coeffs


def _easy_part_flat(f_coeffs: List[int]) -> Optional[List[int]]:
    """Host easy part: f -> f^((p^6-1)(p^2+1)); None if f is degenerate."""
    f = _flat_ints_to_oracle(f_coeffs)
    if f.is_zero():
        return None
    g = f.conjugate() * f.inverse()
    g = g.frobenius().frobenius() * g
    return _oracle_to_flat_ints(g)


def _ns(fold: int, t: int) -> str:
    return f"i{t}." if fold > 1 else ""


def _rows_for(n_items: int, fold: int, mesh) -> int:
    rows = _pow2(max(1, -(-n_items // fold)))
    if mesh is not None:
        rows = max(rows, int(np.prod(list(mesh.shape.values()))))
    return rows


class _FoldLayout:
    """Row/lane layout of a folded batch — the ONE place that knows item i
    lives at row i // fold under name prefix _ns(fold, i % fold). Used by
    every folded entry point (both BLS batch verifies, the hard part, and
    the KZG backend) so the scatter and the readback can never diverge."""

    __slots__ = ("program", "fold", "rows", "nb")

    def __init__(self, kind: str, k: int, n_items: int, mesh, fold=None):
        if fold is None:
            fold = _fold_for(kind, k, n_items)
        if mesh is not None:
            # the mesh pads rows up to the device count anyway, so folding
            # past ceil(n/devices) just runs a bigger program on filler
            n_dev = int(np.prod(list(mesh.shape.values())))
            fold = min(fold, _pow2(max(1, -(-n_items // n_dev))))
        self.program, self.fold = _program(kind, k, fold=fold)
        self.rows = _rows_for(n_items, self.fold, mesh)
        self.nb = self.rows * self.fold

    def views(self, arr: np.ndarray) -> np.ndarray:
        """(nb, ...) staging array -> (rows, fold, ...) view."""
        return arr.reshape((self.rows, self.fold) + arr.shape[1:])

    def split(self, i: int) -> Tuple[int, str]:
        """Item index -> (row, name prefix)."""
        r, t = divmod(i, self.fold)
        return r, _ns(self.fold, t)

    def scatter(self, ins: Dict[str, np.ndarray], arr: np.ndarray, name_fn):
        """Register a (nb, *inner, L) staging array's slices under their
        folded input names: ins[prefix + name_fn(*inner_idx)]."""
        v = self.views(arr)
        inner = v.shape[2:-1]
        for t in range(self.fold):
            ns = _ns(self.fold, t)
            for idx in np.ndindex(*inner):
                ins[ns + name_fn(*idx)] = v[(slice(None), t) + idx]


def _easy_worker(f_coeffs):
    """Pool-safe: easy part + Montgomery-encode; None for degenerate f."""
    g = _easy_part_flat(f_coeffs)
    if g is None:
        return None
    return np.stack([fq.to_mont_int(c) for c in g])


def _easy_parts_pooled(coeffs: Dict[int, List[int]]) -> Dict[int, object]:
    """Easy part for many items (keyed exact coefficient lists), pooled
    across processes at epoch scale — the per-item Fq12 inversion/frobenius
    work is ~1 ms of pure Python each. Values are Montgomery g limbs, or
    None for degenerate f."""
    results: Dict[int, object] = {}
    items = list(coeffs.items())
    procs = int(
        os.environ.get(
            "CONSENSUS_SPECS_TPU_HASH_PROCS", str(min(8, os.cpu_count() or 1))
        )
    )
    if len(items) >= 64 and procs > 1:
        try:
            done = _pool_map(_easy_worker, [c for _, c in items], procs,
                             chunksize=16, timeout=120.0)
            for (i, _), g in zip(items, done):
                results[i] = g
        except Exception:
            results = {}  # pool failed: recompute serially below
            _note_serial_fallback(len(items))
    if not results:
        for i, c in items:
            results[i] = _easy_worker(c)
    return results


def _easy_part_batch(out, lay, precheck, aggz: bool):
    """Readback of PROG A outputs + the final-exponentiation easy part for
    every active item (pooled, _easy_parts_pooled). Returns
    (g_batch, agg_nonzero | None); degenerate items clear their precheck
    bit in place."""
    nb = len(precheck)
    L = fq.NUM_LIMBS
    agg_nonzero = np.zeros(nb, dtype=bool) if aggz else None
    coeffs = {}
    for i in range(nb):
        if not precheck[i]:
            continue
        r, ns = lay.split(i)
        if aggz:
            agg_nonzero[i] = fq.from_mont_limbs(out[f"{ns}aggz"][r]) != 0
        coeffs[i] = [fq.from_mont_limbs(out[f"{ns}f.{j}"][r]) for j in range(12)]

    results = _easy_parts_pooled(coeffs)

    g_batch = np.zeros((nb, 12, L), dtype=np.uint64)
    for i, g in results.items():
        if g is None:
            precheck[i] = False
        else:
            g_batch[i] = g
    return g_batch, agg_nonzero


def _finalize_per_item(fs: np.ndarray, mesh=None) -> np.ndarray:
    """(N, 12, L) loose Miller-output rows -> (N,) bool via the PER-ITEM
    finalization (N pooled easy parts + N hard-part rows) — the exact
    final-exp pipeline the two batch entry points use, callable on raw f
    rows so the rlc microbench and the bisection cross-checks race it
    against the combine path on identical inputs."""
    n = fs.shape[0]
    coeffs = {
        i: [fq.from_mont_limbs(fs[i, j]) for j in range(12)] for i in range(n)
    }
    results = _easy_parts_pooled(coeffs)
    g_batch = np.zeros((n, 12, fq.NUM_LIMBS), dtype=np.uint64)
    active = np.zeros(n, dtype=bool)
    for i, g in results.items():
        if g is not None:
            g_batch[i] = g
            active[i] = True
    ok = _run_hard_part(g_batch, mesh=mesh)
    return ok & active


# hard-part program variants (ISSUE 10): all three share the g.*/res.*
# I/O contract, so routing is purely a program-kind choice
_HARD_PART_KINDS = {
    "bit_serial": "hard_part",
    "windowed": "hard_part_windowed",
    "frobenius": "hard_part_frobenius",
}


def _hard_part_kind(n_items: int) -> str:
    """Which hard-part program serves an n_items batch.

    CONSENSUS_SPECS_TPU_HARD_PART pins a variant (bit_serial | windowed |
    frobenius); 'auto' (default) routes by regime: small row counts — the
    latency-critical one-per-flush finalization and every pipelined-rows
    shape up to 16 — take the Frobenius width-for-depth variant (critical
    path 1840 vs the legacy 4740, measured 2.2-4.7x better ms/row at rows
    1-8), while lane-saturated batches past 16 keep the legacy bit-serial
    chain, whose ~25% lower mul count is work-optimal once the schedule is
    width-bound (fold 32: 217 steps/item vs frobenius 273)."""
    v = os.environ.get("CONSENSUS_SPECS_TPU_HARD_PART", "auto")
    if v in _HARD_PART_KINDS:
        return _HARD_PART_KINDS[v]
    return "hard_part_frobenius" if n_items <= 16 else "hard_part"


def _run_hard_part(g_flat_batch: np.ndarray, mesh=None,
                   kind: str = None, fold: int = None) -> np.ndarray:
    """(N, 12, L) unitary g limb batch -> (N,) bool (res == 1). Counts N
    rows (padding included) against RLC_STATS['final_exps'] — the
    amortization ledger behind the serve plane's final-exps-per-item.
    ``kind`` overrides the variant route (_hard_part_kind) — the finalexp
    bench races all three on identical rows; ``fold`` pins the fold
    factor (the bench's same-program backend race needs the interpreter
    on the fold-1 shape the fused lowering runs)."""
    n = g_flat_batch.shape[0]
    RLC_STATS["final_exps"] += n
    if kind is None:
        kind = _hard_part_kind(n)
    lay = _FoldLayout(kind, 0, n, mesh, fold=fold)
    L = fq.NUM_LIMBS
    gb = np.zeros((lay.nb, 12, L), dtype=np.uint64)
    gb[:n] = g_flat_batch
    ins = {}
    lay.scatter(ins, gb, lambda i: f"g.{i}")
    out = vm.execute(lay.program, ins, batch_shape=(lay.rows,), mesh=mesh)
    ok = np.zeros(n, dtype=bool)
    for i in range(n):
        r, ns = lay.split(i)
        res = [fq.from_mont_limbs(out[f"{ns}res.{j}"][r]) for j in range(12)]
        ok[i] = res[0] == 1 and all(rc == 0 for rc in res[1:])
    return ok


class _FinalExpBatcher:
    """Coalesces CONCURRENT device-routed hard-part rows into one VM
    execution (tentpole layer 2, ISSUE 10): each RLC flush pays ONE
    combined final exponentiation, and when several flushes are in flight
    at once (serve plane + mesh sweep + epoch replay in one process, or a
    multi-threaded serve front), their single rows batch onto the VM
    batch/fold axes so width hides the hard part's residual depth — the
    folded program runs 2-8 rows in barely more wall time than one.

    Protocol: the first arriving thread becomes the window leader, sleeps
    CONSENSUS_SPECS_TPU_FINAL_EXP_WINDOW_MS (default 2 ms — noise against
    the ~600 ms CPU row or the ~ms accelerator row), then executes every
    row that joined and resolves the followers. The
    ``bls.final_exp_rows_inflight`` gauge records the rows each window
    coalesced, and every window journals a ``vm/final_exp_route`` flight
    event — the forensic for route decisions the ISSUE asks for."""

    def __init__(self):
        import threading

        self._lock = threading.Lock()
        # windows are keyed by mesh (jax Mesh hashes structurally; None =
        # the unsharded path), so only rows bound for the SAME placement
        # coalesce — a sharded caller's row must never be diverted to the
        # default device by an unsharded leader, or vice versa
        self._pending = {}  # mesh -> [[g_row, result | Exception, Event]]
        self._leaders = set()  # meshes with an active window leader

    def run(self, g_row: np.ndarray, mesh=None) -> bool:
        import threading

        window = float(os.environ.get(
            "CONSENSUS_SPECS_TPU_FINAL_EXP_WINDOW_MS", "2")) / 1e3
        entry = [g_row, None, threading.Event()]
        with self._lock:
            self._pending.setdefault(mesh, []).append(entry)
            lead = mesh not in self._leaders
            if lead:
                self._leaders.add(mesh)
        if not lead:
            entry[2].wait()
            if isinstance(entry[1], BaseException):
                raise entry[1]
            return entry[1]
        # the leader owes every follower a resolution NO MATTER WHAT —
        # a KeyboardInterrupt mid-sleep or mid-execute must fail the
        # joined entries (and release the leader slot), never leave them
        # blocked on an Event that will not fire
        batch = None
        try:
            if window > 0:
                time.sleep(window)
            n = None
            with self._lock:
                batch = self._pending.pop(mesh, [])
                self._leaders.discard(mesh)  # later arrivals re-elect
                n = len(batch)
                # the ledger shares this lock: concurrent windows (one per
                # mesh key) must not lose read-modify-write increments
                RLC_STATS["final_exp_windows"] += 1
                RLC_STATS["final_exp_window_rows"] += n
            rows = np.stack([e[0] for e in batch])
            kind = _hard_part_kind(n)
            from . import profiling

            profiling.set_gauge("bls.final_exp_rows_inflight", n)
            try:
                from ..obs import flight

                flight.note("vm", "final_exp_route", route="device", rows=n,
                            variant=kind)
            except Exception:
                pass
            ok = _run_hard_part(rows, mesh=mesh, kind=kind)
        except BaseException as e:
            if batch is None:  # died before collecting: take over now
                with self._lock:
                    batch = self._pending.pop(mesh, [])
                    self._leaders.discard(mesh)
            # followers re-raise the original Exception; a BaseException
            # (KeyboardInterrupt/SystemExit) stays with the leader and
            # followers get a plain RuntimeError instead
            err = e if isinstance(e, Exception) else RuntimeError(
                f"final-exp window leader died: {e!r}")
            for other in batch:
                if other is not entry:
                    other[1] = err
                    other[2].set()
            raise
        mine = None
        for other, r in zip(batch, ok):
            if other is entry:
                mine = bool(r)
            else:
                other[1] = bool(r)
                other[2].set()
        return mine


_FINAL_EXP_BATCHER = _FinalExpBatcher()


# ---------------------------------------------------------------------------
# batched public API
# ---------------------------------------------------------------------------

# entry-point instrumentation: batch calls + per-item verifications, used
# by the serve plane's dedup assertions ("every duplicate verified exactly
# once") and attached to serve-bench JSON lines
CALL_COUNTS = {
    "batch_fast_aggregate_verify": 0,
    "batch_aggregate_verify": 0,
    "batch_verify_rlc": 0,
    "items": 0,
}


def _count_call(name: str, n_items: int) -> None:
    CALL_COUNTS[name] += 1
    CALL_COUNTS["items"] += n_items


def reset_call_counts() -> None:
    for k in CALL_COUNTS:
        CALL_COUNTS[k] = 0


# RLC-plane observability: how many combine programs ran, how many failed
# combined checks forced a bisection split, and how many hard-part
# evaluations (device rows, padding included, + host-oracle hard parts)
# the process has paid — final_exps / items is the amortization headline
# the serve bench reports as final-exps-per-item
RLC_STATS = {
    "combines": 0,
    "bisections": 0,
    "final_exps": 0,
    "items": 0,
    # device finalization windows the _FinalExpBatcher ran, and the rows
    # they coalesced: rows/windows > 1 means concurrent flushes actually
    # shared pipelined hard-part executions (serve snapshots carry the
    # deltas; the point-in-time gauge is bls.final_exp_rows_inflight)
    "final_exp_windows": 0,
    "final_exp_window_rows": 0,
    # chunk products folded over the mesh by mesh_rlc (cross-replica)
    "mesh_reductions": 0,
}


def _export_rlc_gauges() -> None:
    from . import profiling

    profiling.set_gauge("bls.rlc_combines", RLC_STATS["combines"])
    profiling.set_gauge("bls.rlc_bisections", RLC_STATS["bisections"])
    profiling.set_gauge("bls.final_exps", RLC_STATS["final_exps"])


def reset_rlc_stats() -> None:
    for k in RLC_STATS:
        RLC_STATS[k] = 0
    _export_rlc_gauges()


def _miller_fast_aggregate(
    pubkey_sets, messages, signatures, mesh=None
) -> Tuple[Optional[dict], "_FoldLayout", np.ndarray]:
    """PROG A stage of batch_fast_aggregate_verify: host prep + the
    aggregate-and-Miller program. Returns (out, lay, precheck); ``out`` is
    None when no item survived host prep (then only precheck matters).
    Split out so the RLC combine path (batch_verify_rlc) can share the
    Miller stage and swap just the finalization."""
    n = len(pubkey_sets)
    max_k = max((len(pks) for pks in pubkey_sets), default=1)
    k = _k_bucket(max(1, max_k))
    group = f"fast_aggregate/{k}"
    with tracing.span("rlc.prep", group=group, n=n):
        L = fq.NUM_LIMBS
        lay = _FoldLayout("miller_product", k, n, mesh)
        nb = lay.nb
        prewarm_host_caches(
            [bytes(m) for m in messages],
            [bytes(s) for s in signatures],
            [bytes(pk) for pks in pubkey_sets for pk in pks],
        )

        # stacked staging arrays (vectorized — the per-name dict assignment
        # loop was ~1.5 s of host time at epoch scale); inactive-lane
        # fillers: infinity pubkeys (0:1:0), generator G2 points
        precheck = np.zeros(nb, dtype=bool)
        pk_x = np.zeros((nb, k, L), dtype=np.uint64)
        pk_y = np.zeros((nb, k, L), dtype=np.uint64)
        pk_y[:] = _INF_G1[1]
        pk_z = np.zeros((nb, k, L), dtype=np.uint64)
        hm = np.zeros((nb, 4, L), dtype=np.uint64)
        hm[:] = _G2GEN_LIMBS
        sg = np.zeros((nb, 4, L), dtype=np.uint64)
        sg[:] = _G2GEN_LIMBS

        for i, (pks, msg, sig) in enumerate(
            zip(pubkey_sets, messages, signatures)
        ):
            try:
                if len(pks) == 0:
                    raise ValueError("empty pubkey set")
                enc = [_pubkey_limbs(bytes(pk)) for pk in pks]
                s = _signature_limbs(bytes(sig))
                h = _message_limbs(bytes(msg))
            except Exception:
                continue
            m = len(enc)
            pk_x[i, :m] = [e[0] for e in enc]
            pk_y[i, :m] = [e[1] for e in enc]
            pk_z[i, :m] = _ONE_LIMBS
            hm[i] = h
            sg[i] = s
            precheck[i] = True

        if not precheck.any():
            return None, lay, precheck

        ins = {}
        lay.scatter(ins, pk_x, lambda j: f"pk{j}.x")
        lay.scatter(ins, pk_y, lambda j: f"pk{j}.y")
        lay.scatter(ins, pk_z, lambda j: f"pk{j}.z")
        lay.scatter(ins, hm, lambda ci: f"h.{_G2_COMPS[ci]}")
        lay.scatter(ins, sg, lambda ci: f"sig.{_G2_COMPS[ci]}")
    with tracing.span("rlc.miller", group=group):
        out = vm.execute(lay.program, ins, batch_shape=(lay.rows,), mesh=mesh)
    return out, lay, precheck


def batch_fast_aggregate_verify(
    pubkey_sets: Sequence[Sequence[bytes]],
    messages: Sequence[bytes],
    signatures: Sequence[bytes],
    mesh=None,
) -> np.ndarray:
    """N independent FastAggregateVerify calls in one device pipeline.
    This is the TPU mapping of the reference's per-attestation verify loop
    (reference specs/phase0/beacon-chain.md:1742-1756, :719-735).
    With ``mesh``, the batch axis is sharded over its first mesh axis."""
    n = len(pubkey_sets)
    assert len(messages) == n and len(signatures) == n
    _count_call("batch_fast_aggregate_verify", n)
    if n == 0:
        return np.zeros(0, dtype=bool)
    out, lay, precheck = _miller_fast_aggregate(
        pubkey_sets, messages, signatures, mesh
    )
    if out is None:
        return precheck[:n]
    g_batch, agg_nonzero = _easy_part_batch(out, lay, precheck, aggz=True)
    ok = _run_hard_part(g_batch, mesh=mesh)
    return (ok & precheck & agg_nonzero)[:n]


def _miller_aggregate(
    pubkey_lists, message_lists, signatures, mesh=None
) -> Tuple[Optional[dict], "_FoldLayout", np.ndarray]:
    """PROG A stage of batch_aggregate_verify (distinct message per pubkey);
    same contract as _miller_fast_aggregate."""
    n = len(pubkey_lists)
    max_k = max(
        (len(pks) for pks in pubkey_lists), default=1
    )
    k = _k_bucket(max(1, max_k))
    group = f"aggregate/{k}"
    with tracing.span("rlc.prep", group=group, n=n):
        L = fq.NUM_LIMBS
        lay = _FoldLayout("aggregate_verify", k, n, mesh)
        nb = lay.nb
        prewarm_host_caches(
            [bytes(m) for ms in message_lists for m in ms],
            [bytes(s) for s in signatures],
            [bytes(pk) for pks in pubkey_lists for pk in pks],
        )

        precheck = np.zeros(nb, dtype=bool)
        pk_x = np.zeros((nb, k, L), dtype=np.uint64)
        pk_y = np.zeros((nb, k, L), dtype=np.uint64)
        pk_y[:] = _INF_G1[1]
        pk_z = np.zeros((nb, k, L), dtype=np.uint64)
        hm = np.zeros((nb, k, 4, L), dtype=np.uint64)
        hm[:] = _G2GEN_LIMBS
        sg = np.zeros((nb, 4, L), dtype=np.uint64)
        sg[:] = _G2GEN_LIMBS

        for i, (pks, msgs, sig) in enumerate(
            zip(pubkey_lists, message_lists, signatures)
        ):
            try:
                if len(pks) == 0 or len(pks) != len(msgs):
                    raise ValueError("bad pubkey/message lists")
                enc = [_pubkey_limbs(bytes(pk)) for pk in pks]
                hs = [_message_limbs(bytes(m)) for m in msgs]
                s = _signature_limbs(bytes(sig))
            except Exception:
                continue
            m = len(enc)
            pk_x[i, :m] = [e[0] for e in enc]
            pk_y[i, :m] = [e[1] for e in enc]
            pk_z[i, :m] = _ONE_LIMBS
            hm[i, :m] = hs
            sg[i] = s
            precheck[i] = True

        if not precheck.any():
            return None, lay, precheck

        ins = {}
        lay.scatter(ins, pk_x, lambda j: f"pk{j}.x")
        lay.scatter(ins, pk_y, lambda j: f"pk{j}.y")
        lay.scatter(ins, pk_z, lambda j: f"pk{j}.z")
        lay.scatter(ins, hm, lambda j, ci: f"h{j}.{_G2_COMPS[ci]}")
        lay.scatter(ins, sg, lambda ci: f"sig.{_G2_COMPS[ci]}")
    with tracing.span("rlc.miller", group=group):
        out = vm.execute(lay.program, ins, batch_shape=(lay.rows,), mesh=mesh)
    return out, lay, precheck


def batch_aggregate_verify(
    pubkey_lists: Sequence[Sequence[bytes]],
    message_lists: Sequence[Sequence[bytes]],
    signatures: Sequence[bytes],
    mesh=None,
) -> np.ndarray:
    """N independent AggregateVerify calls (distinct messages per pubkey).
    Inactive pair lanes use infinity G1 (their Miller factor lands in a
    proper subfield, killed by the final exponentiation).
    With ``mesh``, the batch axis is sharded over its first mesh axis."""
    n = len(pubkey_lists)
    _count_call("batch_aggregate_verify", n)
    if n == 0:
        return np.zeros(0, dtype=bool)
    out, lay, precheck = _miller_aggregate(
        pubkey_lists, message_lists, signatures, mesh
    )
    if out is None:
        return precheck[:n]
    g_batch, _ = _easy_part_batch(out, lay, precheck, aggz=False)
    ok = _run_hard_part(g_batch, mesh=mesh)
    return (ok & precheck)[:n]


# ---------------------------------------------------------------------------
# RLC batch verification: one final exponentiation per micro-batch
# ---------------------------------------------------------------------------


def rlc_enabled() -> bool:
    """Serve-plane default: micro-batches ride the RLC path unless
    CONSENSUS_SPECS_TPU_RLC=0 reverts to per-item final exponentiation."""
    return os.environ.get("CONSENSUS_SPECS_TPU_RLC", "1") != "0"


def _rlc_backend() -> str:
    """Combine-stage backend: 'vm' (the lane-scheduled device program,
    default) or 'jax' (ops/pairing.rlc_combine — the non-VM path, also the
    oracle cross-check's subject)."""
    v = os.environ.get("CONSENSUS_SPECS_TPU_RLC_BACKEND", "vm")
    return v if v == "jax" else "vm"


def _rlc_chunk_max() -> int:
    """f's combined per VM program instance. 16 saturates the mul lanes;
    bigger batches run more chunk rows and host-multiply the chunk
    products (each a single oracle Fq12 mul). Env-tunable so tests can
    exercise multi-chunk batching with small, fast-to-assemble programs."""
    return max(1, int(os.environ.get("CONSENSUS_SPECS_TPU_RLC_CHUNK", "16")))


def _rlc_final_mode() -> str:
    """Where the ONE combined hard part runs: 'device' (a hard-part VM
    row — variant per _hard_part_kind, concurrent rows coalesced by
    _FinalExpBatcher) or 'host' (exact-int oracle HHT). 'auto' (default)
    picks host on plain CPU — even the width-for-depth Frobenius row
    (~1.9k serial steps, ~0.6 s XLA-CPU) loses to the ~20 ms oracle there
    — and device under an accelerator, where the depth recovery plus
    multi-row pipelining make the device row the winning route whenever
    >= 2 flushes are in flight (the batcher folds their rows into one
    execution; `bls.final_exp_rows_inflight` records it). Both are exact;
    tests pin them bit-identical."""
    v = os.environ.get("CONSENSUS_SPECS_TPU_RLC_FINAL", "auto")
    if v in ("host", "device"):
        return v
    try:
        import jax

        return "host" if jax.default_backend() == "cpu" else "device"
    except Exception:
        return "host"


def _rlc_scalars(m: int, rng=None) -> np.ndarray:
    """(m, RLC_BITS) uint8 msb-first bit matrix of m fresh NONZERO random
    scalars — from ``rng.getrandbits`` when injected (deterministic
    tests), else os.urandom."""
    nbits = vmlib.RLC_BITS
    bits = np.zeros((m, nbits), dtype=np.uint8)
    for i in range(m):
        r = 0
        while r == 0:
            if rng is not None:
                r = rng.getrandbits(nbits)
            else:
                r = int.from_bytes(os.urandom(nbits // 8), "big")
        for t in range(nbits):
            bits[i, t] = (r >> (nbits - 1 - t)) & 1
    return bits


def _oracle_unitary_pow_abs(g, bits):
    acc = g
    for b in bits[1:]:
        acc = acc * acc
        if b:
            acc = acc * g
    return acc


def hard_part_res_oracle(g) -> "O.Fq12":
    """Exact-int HHT hard part RESULT on a unitary oracle Fq12 (the host
    twin of PROG B, same decomposition as vmlib.build_hard_part; inverse
    == conjugate in the cyclotomic subgroup). The ONE implementation of
    the security-critical chain — the finalexp smoke and the vmlib
    variant tests compare the VM programs against this exact function, so
    a formula fix here propagates to every gate."""
    px = lambda t: _oracle_unitary_pow_abs(t, vmlib.ABS_X_BITS).conjugate()
    px1 = lambda t: _oracle_unitary_pow_abs(
        t, vmlib.ABS_X_PLUS_1_BITS
    ).conjugate()
    t0 = px1(px1(g))
    t1 = px(t0) * t0.frobenius()
    t2 = px(px(t1))
    t2 = t2 * t1.frobenius().frobenius()
    t2 = t2 * t1.conjugate()
    return t2 * (g * g * g)


def _hard_part_is_one_oracle(g_coeffs: List[int]) -> bool:
    """res == 1 verdict over hard_part_res_oracle. ~20 ms per element —
    the right tool for the ONE combined element on CPU."""
    RLC_STATS["final_exps"] += 1
    g = _flat_ints_to_oracle(g_coeffs)
    return _oracle_to_flat_ints(hard_part_res_oracle(g)) == [1] + [0] * 11


def _final_exp_is_one(f_coeffs: List[int], mesh=None) -> bool:
    """ONE full final exponentiation on exact coefficients: the shared
    host easy part, then the hard part per _rlc_final_mode(). Device
    routes go through the final-exp batcher, so hard parts from flushes
    in flight at the same moment share one pipelined VM execution."""
    with tracing.span("rlc.final_exp", rows=1):
        with tracing.span("rlc.easy_part"):
            g = _easy_part_flat(f_coeffs)
        if g is None:
            return False  # degenerate f: no valid item produces it
        tracing.count("final_exps")
        with tracing.span("rlc.hard_part"):
            if _rlc_final_mode() == "host":
                try:
                    from ..obs import flight

                    flight.note("vm", "final_exp_route", route="host",
                                rows=1)
                except Exception:
                    pass
                return _hard_part_is_one_oracle(g)
            gm = np.stack([fq.to_mont_int(c) for c in g])
            return bool(_FINAL_EXP_BATCHER.run(gm, mesh=mesh))


def _rlc_chunk(m: int, mesh=None) -> int:
    """f's per rlc_combine program instance for an m-candidate combine.
    Unsharded: the lane-saturating chunk (_rlc_chunk_max, default 16).
    Under a mesh the WIDTH is the parallel axis, so the chunk shrinks
    until there is at least one chunk row per device — 16 candidates on
    8 devices run as 8 chunk-2 rows (one per device), not one idle-mesh
    chunk-16 row."""
    chunk = min(_pow2(m), _rlc_chunk_max())
    if mesh is not None:
        n_dev = int(np.prod(list(mesh.shape.values())))
        chunk = max(1, min(chunk, _pow2(-(-m // n_dev))))
    return chunk


def _rlc_combine_vm(fs: np.ndarray, bits: np.ndarray, mesh=None) -> List[int]:
    """Combine via the VM program: chunk the (m, 12, L) f batch into
    rlc_combine instances, execute one batched program (sharded over the
    mesh batch axis when ``mesh`` is given), then multiply the per-chunk
    products into one element — a CROSS-REPLICA Fq12 reduction on the
    mesh (ops/mesh_rlc.py: one all-gather, then an Fq12 fold on every
    device), or one host oracle Fq12 mul per chunk
    on the single-device path. Returns the exact flat coefficients of
    prod f_i^{r_i} — bit-identical either way (Fq12 multiplication is
    exact and associative)."""
    m = fs.shape[0]
    chunk = _rlc_chunk(m, mesh)
    n_chunks = -(-m // chunk)
    lay = _FoldLayout("rlc_combine", chunk, n_chunks, mesh)
    L = fq.NUM_LIMBS
    fb = np.zeros((lay.nb, chunk, 12, L), dtype=np.uint64)
    fb[:, :, 0] = _ONE_LIMBS  # inactive lanes: f = 1, bits = 0 -> 1^0
    rb = np.zeros((lay.nb, chunk, vmlib.RLC_BITS, L), dtype=np.uint64)
    fb.reshape(lay.nb * chunk, 12, L)[:m] = fs
    rb.reshape(lay.nb * chunk, vmlib.RLC_BITS, L)[:m] = np.where(
        bits[..., None].astype(bool), _ONE_LIMBS, np.uint64(0)
    )
    ins = {}
    lay.scatter(ins, fb, lambda i, j: f"f{i}.{j}")
    lay.scatter(ins, rb, lambda i, t: f"r{i}.{t}")
    out = vm.execute(lay.program, ins, batch_shape=(lay.rows,), mesh=mesh)
    if mesh is not None and n_chunks > 1:
        # cross-replica reduction: per-shard partial products folded over
        # the interconnect, so the combine's sequential tail never
        # re-serializes the axis the mesh just parallelized. A failure
        # here propagates: the service's mesh rung counts it
        # (serve.mesh_fallbacks) instead of a silent host multiply.
        from . import mesh_rlc

        prods = np.stack([
            np.stack([out[f"{ns}c.{j}"][r] for j in range(12)])
            for r, ns in (lay.split(c) for c in range(n_chunks))
        ])
        c = mesh_rlc.mesh_fq12_product(prods, mesh)
        RLC_STATS["mesh_reductions"] += 1
        return [fq.from_mont_limbs(c[j]) for j in range(12)]
    total = None
    for c in range(n_chunks):
        r, ns = lay.split(c)
        x = _flat_ints_to_oracle(
            [fq.from_mont_limbs(out[f"{ns}c.{j}"][r]) for j in range(12)]
        )
        total = x if total is None else total * x
    return _oracle_to_flat_ints(total)


def _rlc_combine_jax(fs: np.ndarray, bits: np.ndarray) -> List[int]:
    from . import pairing

    c = np.asarray(pairing.rlc_combine(fs, bits.astype(bool)))
    return [fq.from_mont_limbs(c[j]) for j in range(12)]


def batch_verify_rlc(items, mesh=None, rng=None) -> np.ndarray:
    """N independent verifications decided by random-linear-combination:
    check prod_i f_i^{r_i} == 1 (post final exp) for fresh random nonzero
    128-bit scalars r_i, so the whole micro-batch pays ONE easy part and
    ONE hard part instead of N of each (blst mult_verify's trick; the
    amortization lever of arXiv:2302.00418).

    ``items``: sequence of (kind, pubkeys, messages, signature) with kind
    'fast_aggregate' (one message) or 'aggregate' (per-key messages) —
    the serve plane's micro-batch shape. Items are grouped by
    (kind, K-bucket) for PROG A exactly like SignatureCollector.flush,
    and the Miller outputs feed the combine program as raw loose limbs
    (no per-item host canonicalization or easy part).

    Soundness (Schwartz-Zippel): the final-exp images f_i^E live in the
    order-r subgroup, r prime ~2^255. The combined check is
    g^(sum a_i r_i) == 1 for f_i^E = g^{a_i}; if any a_i != 0, at most
    one value of that r_i (mod r) zeroes the sum, so a batch containing
    any invalid item passes with probability <= 2^-128 over the fresh
    per-combine scalars (drawn from os.urandom; ``rng`` — anything with
    getrandbits — overrides for deterministic tests). False REJECTION is
    impossible: all-valid batches have every a_i = 0.

    A failed combined check falls back to bisection: split the candidate
    list, re-combine each half with fresh scalars, recurse — exact
    per-item finalization at singletons — so callers always get exact
    per-item verdicts with O(log N * #bad) extra combines. A batch of 1
    (or 1 surviving candidate) degenerates to the plain per-item path
    with no combine at all. Verdicts are bit-identical to
    batch_fast_aggregate_verify / batch_aggregate_verify on every input
    (up to the 2^-128 bound, which no test will ever see).

    Each call appends one ``rlc`` flush record (obs/tracing.py) holding
    its seconds, its stage spans and the combines, bisections and final
    exponentiations it ran."""
    items = list(items)
    n = len(items)
    _count_call("batch_verify_rlc", n)
    if n == 0:
        return np.zeros(0, dtype=bool)
    with tracing.rlc_record(n) as rec, tracing.span(
            "rlc.flush", flush=rec["id"], items=n):
        return _batch_verify_rlc(items, mesh, rng)


def _batch_verify_rlc(items, mesh, rng) -> np.ndarray:
    n = len(items)
    verdict = np.zeros(n, dtype=bool)

    groups: Dict[Tuple[str, int], List[int]] = {}
    for i, (kind, pks, _msgs, _sig) in enumerate(items):
        if kind not in ("fast_aggregate", "aggregate"):
            raise ValueError(f"unknown check kind {kind!r}")
        groups.setdefault((kind, _k_bucket(max(1, len(pks)))), []).append(i)

    # PROG A per (kind, bucket) group; gather surviving candidates' Miller
    # outputs as raw limb rows (host precheck / infinite-aggregate
    # failures are False without any finalization work)
    cand_idx: List[int] = []
    fs_rows: List[np.ndarray] = []
    for (kind, _bucket), idxs in groups.items():
        sub = [items[i] for i in idxs]
        if kind == "fast_aggregate":
            out, lay, precheck = _miller_fast_aggregate(
                [it[1] for it in sub], [it[2] for it in sub],
                [it[3] for it in sub], mesh,
            )
        else:
            out, lay, precheck = _miller_aggregate(
                [it[1] for it in sub], [it[2] for it in sub],
                [it[3] for it in sub], mesh,
            )
        if out is None:
            continue
        for pos, i in enumerate(idxs):
            if not precheck[pos]:
                continue
            r, ns = lay.split(pos)
            if kind == "fast_aggregate" and (
                fq.from_mont_limbs(out[f"{ns}aggz"][r]) == 0
            ):
                continue  # aggregate pubkey is infinity: False, no crypto
            fs_rows.append(
                np.stack([out[f"{ns}f.{j}"][r] for j in range(12)])
            )
            cand_idx.append(i)

    m = len(cand_idx)
    RLC_STATS["items"] += m
    if m == 0:
        _export_rlc_gauges()
        return verdict
    fs = np.stack(fs_rows)  # (m, 12, L), loose limbs straight from PROG A

    def finalize_item(j: int) -> bool:
        coeffs = [fq.from_mont_limbs(fs[j, c]) for c in range(12)]
        return _final_exp_is_one(coeffs, mesh=mesh)

    def combine_check(sel: List[int]) -> bool:
        RLC_STATS["combines"] += 1
        tracing.count("combines")
        with tracing.span("rlc.combine", n=len(sel)):
            bits = _rlc_scalars(len(sel), rng)
            sub = fs[np.asarray(sel)]
            if _rlc_backend() == "jax":
                coeffs = _rlc_combine_jax(sub, bits)
            else:
                coeffs = _rlc_combine_vm(sub, bits, mesh)
        return _final_exp_is_one(coeffs, mesh=mesh)

    def resolve(sel: List[int]) -> None:
        if len(sel) == 1:
            verdict[cand_idx[sel[0]]] = finalize_item(sel[0])
            return
        if combine_check(sel):
            for j in sel:
                verdict[cand_idx[j]] = True
            return
        RLC_STATS["bisections"] += 1
        tracing.count("bisections")
        mid = len(sel) // 2
        resolve(sel[:mid])
        resolve(sel[mid:])

    if m == 1:
        verdict[cand_idx[0]] = finalize_item(0)  # plain-path degeneration
    else:
        resolve(list(range(m)))
    _export_rlc_gauges()
    return verdict


# ---------------------------------------------------------------------------
# switchboard-facing single-call API (reference utils/bls.py:47-74 semantics)
# ---------------------------------------------------------------------------


def verify(PK: bytes, message: bytes, signature: bytes) -> bool:
    return bool(batch_fast_aggregate_verify([[PK]], [message], [signature])[0])


def fast_aggregate_verify(
    pubkeys: Sequence[bytes], message: bytes, signature: bytes
) -> bool:
    if len(pubkeys) == 0:
        return False
    return bool(
        batch_fast_aggregate_verify([list(pubkeys)], [message], [signature])[0]
    )


def aggregate_verify(
    pubkeys: Sequence[bytes], messages: Sequence[bytes], signature: bytes
) -> bool:
    if len(pubkeys) == 0 or len(pubkeys) != len(messages):
        return False
    return bool(
        batch_aggregate_verify([list(pubkeys)], [list(messages)], [signature])[0]
    )
