"""Default-run slice of the TPU-backend cross-checks (VERDICT r2 #6): one
real verify + one bucket edge, small programs only — so the flagship
correctness path is exercised on every plain `pytest tests/` run, not just
under --run-slow. The deep/wide cases live in test_bls_backend_tpu.py."""
import os
import subprocess
import sys

from consensus_specs_tpu.utils import bls

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_single_verify_and_k2_bucket():
    from consensus_specs_tpu.ops import bls_backend

    sk1, sk2 = 41, 42
    pk1, pk2 = bls.SkToPk(sk1), bls.SkToPk(sk2)
    msg = b"\x05" * 32
    sig1 = bls.Sign(sk1, msg)
    assert bls_backend.verify(pk1, msg, sig1) is True
    assert bls_backend.verify(pk2, msg, sig1) is False

    agg = bls.Aggregate([sig1, bls.Sign(sk2, msg)])
    got = bls_backend.batch_fast_aggregate_verify(
        [[pk1, pk2], [pk1]], [msg, msg], [agg, agg]
    )
    assert list(got) == [True, False]


# -- _cached eviction semantics (ISSUE 2 satellite) --------------------------


def _with_cap(cache, cap):
    from consensus_specs_tpu.ops import bls_backend

    bls_backend._CACHE_CAPS[id(cache)] = cap
    return cache


def test_cached_hit_refreshes_recency_order():
    from consensus_specs_tpu.ops.bls_backend import _CACHE_CAPS, _cached

    cache = _with_cap({}, 8)
    try:
        for i in range(4):
            _cached(cache, bytes([i]), lambda k: ("v", k))
        _cached(cache, b"\x01", lambda k: ("new", k))  # hit: no recompute
        assert cache[b"\x01"] == ("v", b"\x01")
        # dict order IS recency order: the hit key moved last
        assert list(cache.keys()) == [b"\x00", b"\x02", b"\x03", b"\x01"]
    finally:
        del _CACHE_CAPS[id(cache)]


def test_cached_half_eviction_drops_only_cold_half():
    from consensus_specs_tpu.ops.bls_backend import _CACHE_CAPS, _cached

    cache = _with_cap({}, 8)
    try:
        for i in range(8):
            _cached(cache, bytes([i]), lambda k: k)
        for i in (0, 1, 2, 3):  # refresh the first four: now hottest
            _cached(cache, bytes([i]), lambda k: None)
        _cached(cache, b"\x63", lambda k: k)  # overflow -> evict cold half
        assert sorted(cache.keys()) == [
            b"\x00", b"\x01", b"\x02", b"\x03", b"\x63"
        ]
    finally:
        del _CACHE_CAPS[id(cache)]


def test_cached_valueerror_never_cached_and_reraised():
    from consensus_specs_tpu.ops.bls_backend import _CACHE_CAPS, _cached

    calls = []
    cache = _with_cap({}, 8)

    def compute(k):
        calls.append(k)
        return ValueError("bad input")

    try:
        for _ in range(2):
            try:
                _cached(cache, b"k", compute)
                assert False, "expected ValueError"
            except ValueError as e:
                assert str(e) == "bad input"
        assert cache == {}  # never cached ...
        assert len(calls) == 2  # ... so every miss recomputes
    finally:
        del _CACHE_CAPS[id(cache)]


def test_prewarm_codec_path_skips_invalid_values():
    """The batched-codec prewarm fills caches exactly like _cached would:
    validation failures (ValueError VALUES) never enter, valid items do."""
    from consensus_specs_tpu.ops import bls_backend

    sks = list(range(201, 221))
    pks = [bls.SkToPk(sk) for sk in sks]
    bad_pk = b"\xa0" + b"\x01" * 47  # not on curve
    inf_pk = b"\xc0" + b"\x00" * 47  # infinity: KeyValidate rejects
    for pk in pks + [bad_pk, inf_pk]:
        bls_backend._PK_CACHE.pop(pk, None)
    before = dict(bls_backend.PREP_STATS)
    bls_backend.prewarm_host_caches([], [], pks + [bad_pk, inf_pk])
    assert all(pk in bls_backend._PK_CACHE for pk in pks)
    assert bad_pk not in bls_backend._PK_CACHE
    assert inf_pk not in bls_backend._PK_CACHE
    assert (
        bls_backend.PREP_STATS["codec_items"]
        == before["codec_items"] + len(pks) + 2
    )


def test_reset_prep_state_clears_pool_latch_and_counters():
    from consensus_specs_tpu.ops import bls_backend, profiling

    bls_backend._set_pool_broken(True)
    assert bls_backend._POOL_BROKEN is True
    assert bls_backend.PREP_STATS["pool_broken_latches"] >= 1
    assert profiling.summary()["bls.prep_pool_broken"]["gauge"] == 1.0
    bls_backend.reset_prep_state()
    assert bls_backend._POOL_BROKEN is False
    assert all(v == 0 for v in bls_backend.PREP_STATS.values())
    assert profiling.summary()["bls.prep_pool_broken"]["gauge"] == 0.0


def test_easy_parts_pool_failure_is_counted(monkeypatch):
    """A failed easy-part pool still answers (serially) but counts its
    items as serial fallbacks, which chip_smoke.py gates on."""
    import numpy as np

    from consensus_specs_tpu.ops import bls_backend

    def broken(*args, **kwargs):
        raise OSError("fork refused")

    monkeypatch.setattr(bls_backend, "_pool_map", broken)
    monkeypatch.setenv("CONSENSUS_SPECS_TPU_HASH_PROCS", "2")
    monkeypatch.setitem(bls_backend.PREP_STATS, "serial_fallback_items", 0)
    coeffs = {i: [i + 2] + [i] * 11 for i in range(64)}
    got = bls_backend._easy_parts_pooled(coeffs)
    assert bls_backend.PREP_STATS["serial_fallback_items"] == 64
    for i in (0, 63):
        assert np.array_equal(got[i], bls_backend._easy_worker(coeffs[i]))


# -- .vm_cache pruning (ISSUE 6 satellite) -----------------------------------


def test_prune_vm_cache_evicts_by_idle_age_and_size(tmp_path):
    import os
    import time as _time

    from consensus_specs_tpu.ops.bls_backend import prune_vm_cache

    d = str(tmp_path)
    now = _time.time()
    # two stale entries (40 days idle), two fresh, one foreign file
    for name, age_days, size in (
        ("v1_aaaa_old1.pkl", 40, 1000),
        ("v1_aaaa_old2.pkl", 41, 1000),
        ("v1_bbbb_new1.pkl", 1, 1000),
        ("v1_bbbb_new2.pkl", 0, 1000),
    ):
        p = os.path.join(d, name)
        with open(p, "wb") as fh:
            fh.write(b"\x00" * size)
        os.utime(p, (now - age_days * 86400, now - age_days * 86400))
    with open(os.path.join(d, "README.txt"), "w") as fh:
        fh.write("not a cache entry")

    out = prune_vm_cache(max_age_days=30, max_bytes=0, cache_dir=d)
    assert out["evicted"] == 2 and out["kept"] == 2
    left = sorted(os.listdir(d))
    assert left == ["README.txt", "v1_bbbb_new1.pkl", "v1_bbbb_new2.pkl"]
    # the prune publishes what it reclaimed through the registry (ISSUE 7
    # satellite: previously the returned dict was the only record)
    from consensus_specs_tpu.ops import profiling

    summ = profiling.summary()
    assert summ["bls.vm_cache_pruned_entries"] == {"gauge": 2.0}
    assert summ["bls.vm_cache_pruned_bytes"] == {"gauge": 2000.0}

    # size cap: keep only the newest entry's bytes
    out = prune_vm_cache(max_age_days=0, max_bytes=1000, cache_dir=d)
    assert out["evicted"] == 1 and out["kept"] == 1
    assert out["kept_bytes"] == 1000
    assert sorted(os.listdir(d)) == ["README.txt", "v1_bbbb_new2.pkl"]

    # disabled rules (<= 0) evict nothing
    out = prune_vm_cache(max_age_days=0, max_bytes=0, cache_dir=d)
    assert out["evicted"] == 0 and out["kept"] == 1


# -- final-exp row batching (ISSUE 10 tentpole layer 2) ----------------------


def test_final_exp_batcher_coalesces_concurrent_rows(monkeypatch):
    """Concurrent device-routed hard-part rows (one per flush) coalesce
    into ONE multi-row VM execution, and the window's row count lands on
    the bls.final_exp_rows_inflight gauge."""
    import threading
    import time as _time

    import numpy as np

    from consensus_specs_tpu.ops import bls_backend, fq, profiling

    calls = []

    def fake_run(rows, mesh=None, kind=None):
        calls.append((rows.shape[0], kind))
        _time.sleep(0.01)
        return np.ones(rows.shape[0], dtype=bool)

    monkeypatch.setattr(bls_backend, "_run_hard_part", fake_run)
    monkeypatch.setenv("CONSENSUS_SPECS_TPU_FINAL_EXP_WINDOW_MS", "80")
    batcher = bls_backend._FinalExpBatcher()
    results = []
    barrier = threading.Barrier(4)

    def worker():
        barrier.wait()
        g = np.zeros((12, fq.NUM_LIMBS), dtype=np.uint64)
        results.append(batcher.run(g))

    threads = [threading.Thread(target=worker) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert results == [True] * 4
    assert sum(c for c, _ in calls) == 4
    assert len(calls) == 1, calls  # one coalesced window
    # auto-routing at 4 rows picks the frobenius width-for-depth variant
    assert calls[0][1] == "hard_part_frobenius"
    gauge = profiling.summary()["bls.final_exp_rows_inflight"]["gauge"]
    assert gauge == 4.0


def test_final_exp_batcher_never_mixes_meshes(monkeypatch):
    """Windows are keyed by mesh: a sharded caller's row must never be
    diverted onto an unsharded leader's placement (or vice versa)."""
    import threading

    import numpy as np

    from consensus_specs_tpu.ops import bls_backend, fq

    calls = []

    def fake_run(rows, mesh=None, kind=None):
        calls.append((rows.shape[0], mesh))
        return np.ones(rows.shape[0], dtype=bool)

    monkeypatch.setattr(bls_backend, "_run_hard_part", fake_run)
    monkeypatch.setenv("CONSENSUS_SPECS_TPU_FINAL_EXP_WINDOW_MS", "80")
    batcher = bls_backend._FinalExpBatcher()
    barrier = threading.Barrier(4)
    results = []

    def worker(mesh):
        barrier.wait()
        g = np.zeros((12, fq.NUM_LIMBS), dtype=np.uint64)
        results.append(batcher.run(g, mesh=mesh))

    # two callers per "mesh" (a hashable stand-in suffices for keying)
    threads = [threading.Thread(target=worker, args=(m,))
               for m in (None, "mesh-a", None, "mesh-a")]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert results == [True] * 4
    assert sorted(c for c, _ in calls) == [2, 2]  # one window per mesh key
    assert sorted(str(m) for _, m in calls) == ["None", "mesh-a"]


def test_final_exp_batcher_propagates_failures(monkeypatch):
    """A failed window must fail EVERY joined caller (never hang a
    follower), and later windows recover independently."""
    import threading

    import numpy as np

    from consensus_specs_tpu.ops import bls_backend, fq

    def boom(rows, mesh=None, kind=None):
        raise RuntimeError("device fell over")

    monkeypatch.setattr(bls_backend, "_run_hard_part", boom)
    monkeypatch.setenv("CONSENSUS_SPECS_TPU_FINAL_EXP_WINDOW_MS", "50")
    batcher = bls_backend._FinalExpBatcher()
    errs = []
    barrier = threading.Barrier(2)

    def worker():
        barrier.wait()
        g = np.zeros((12, fq.NUM_LIMBS), dtype=np.uint64)
        try:
            batcher.run(g)
            errs.append(None)
        except RuntimeError as e:
            errs.append(str(e))

    threads = [threading.Thread(target=worker) for _ in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert errs == ["device fell over"] * 2
    # recovery: a later lone row succeeds once the backend does
    monkeypatch.setattr(
        bls_backend, "_run_hard_part",
        lambda rows, mesh=None, kind=None: np.ones(rows.shape[0], dtype=bool))
    g = np.zeros((12, fq.NUM_LIMBS), dtype=np.uint64)
    assert batcher.run(g) is True


def test_hard_part_kind_routing(monkeypatch):
    """auto routes small row counts to the frobenius variant and
    lane-saturated batches to the legacy bit-serial chain; the env pin
    always wins."""
    from consensus_specs_tpu.ops import bls_backend

    monkeypatch.delenv("CONSENSUS_SPECS_TPU_HARD_PART", raising=False)
    assert bls_backend._hard_part_kind(1) == "hard_part_frobenius"
    assert bls_backend._hard_part_kind(16) == "hard_part_frobenius"
    assert bls_backend._hard_part_kind(17) == "hard_part"
    monkeypatch.setenv("CONSENSUS_SPECS_TPU_HARD_PART", "windowed")
    assert bls_backend._hard_part_kind(1) == "hard_part_windowed"
    monkeypatch.setenv("CONSENSUS_SPECS_TPU_HARD_PART", "bit_serial")
    assert bls_backend._hard_part_kind(1) == "hard_part"


# -- per-program .vm_cache keys (ISSUE 10 satellite) -------------------------


def test_program_fingerprints_are_per_kind():
    """Every registry kind gets its own cache fingerprint, derived from
    (vm+fq core, shared vmlib source, the kind's claimed builder source)
    — so keys are distinct and deterministic."""
    from consensus_specs_tpu.ops import bls_backend, vmlib

    fps = {k: bls_backend._program_fingerprint(k) for k in vmlib.BUILDERS}
    assert len(set(fps.values())) == len(fps)  # all distinct
    # stable across calls (lru + deterministic hashing)
    assert fps["hard_part"] == bls_backend._program_fingerprint("hard_part")


def test_builder_source_split_claims_only_its_kind():
    """The shared/local source split behind the per-program keys: each
    kind's emit/builder bodies are cut out of the shared hash and claimed
    by that kind alone, while shared algebra stays in the shared part."""
    from consensus_specs_tpu.ops import vmlib

    shared, local_hp = vmlib.builder_source_parts("hard_part")
    _, local_frob = vmlib.builder_source_parts("hard_part_frobenius")
    assert "def _emit_hard_part(" not in shared
    assert "def _emit_hard_part_frobenius(" not in shared
    assert "def _emit_hard_part(" in local_hp
    assert "def _emit_hard_part_frobenius(" in local_frob
    assert "def _emit_hard_part_frobenius(" not in local_hp
    # shared helpers every builder leans on remain in the shared hash
    assert "def f12_mul(" in shared
    assert "def f12_cyclotomic_square_comps(" in shared


def test_editing_one_builder_rekeys_only_that_kind(monkeypatch):
    """The satellite's whole point: a one-builder edit must re-key only
    that kind's cached programs (simulated by perturbing one kind's
    claimed source through builder_source_parts)."""
    from consensus_specs_tpu.ops import bls_backend, vmlib

    before = {
        k: bls_backend._program_fingerprint(k)
        for k in ("hard_part", "hard_part_frobenius", "rlc_combine")
    }
    real = vmlib.builder_source_parts

    def perturbed(kind):
        shared, local = real(kind)
        if kind == "hard_part_frobenius":
            local = local + "# edited\n"
        return shared, local

    monkeypatch.setattr(vmlib, "builder_source_parts", perturbed)
    bls_backend._program_fingerprint.cache_clear()
    bls_backend._core_fingerprint_parts.cache_clear()
    try:
        after = {
            k: bls_backend._program_fingerprint(k)
            for k in ("hard_part", "hard_part_frobenius", "rlc_combine")
        }
    finally:
        monkeypatch.undo()
        bls_backend._program_fingerprint.cache_clear()
        bls_backend._core_fingerprint_parts.cache_clear()
    assert after["hard_part_frobenius"] != before["hard_part_frobenius"]
    assert after["hard_part"] == before["hard_part"]
    assert after["rlc_combine"] == before["rlc_combine"]


def test_prune_evicts_stale_fingerprint_entries(tmp_path):
    """Entries whose cache version or per-program fingerprint no longer
    matches the current sources can never hit again — prune_vm_cache
    evicts them regardless of age; unknown kinds and current-fingerprint
    entries stay."""
    import os

    from consensus_specs_tpu.ops import bls_backend
    from consensus_specs_tpu.ops.bls_backend import (
        _VM_CACHE_VERSION,
        prune_vm_cache,
    )

    d = str(tmp_path)
    cur_fp = bls_backend._program_fingerprint("hard_part")
    v = _VM_CACHE_VERSION
    names = {
        # current version + current fingerprint: kept
        f"v{v}_{cur_fp}_hard_part_k0_f1_w96x192_p256.pkl": False,
        # current version, stale fingerprint for a known kind: evicted
        f"v{v}_{'0' * 10}_hard_part_k0_f32_w96x192_p256.pkl": True,
        # old cache version: evicted
        f"v{v - 1}_{'a' * 10}_hard_part_k0_f1_w96x192_p256.pkl": True,
        # unknown kind (older/newer checkout): kept for age/size rules
        f"v{v}_{'b' * 10}_future_kind_k0_f1_w96x192_p256.pkl": False,
        # non-cache-shaped name: untouched
        "v1_aaaa_old1.pkl": False,
    }
    for name in names:
        with open(os.path.join(d, name), "wb") as fh:
            fh.write(b"\x00" * 10)
    out = prune_vm_cache(max_age_days=0, max_bytes=0, cache_dir=d)
    assert out["evicted"] == 2
    left = set(os.listdir(d))
    for name, evicted in names.items():
        assert (name not in left) == evicted, name
    # evict_stale=False restores the pure age/size behavior
    out = prune_vm_cache(max_age_days=0, max_bytes=0, cache_dir=d,
                         evict_stale=False)
    assert out["evicted"] == 0


# -- where the persistent compile cache goes ---------------------------------
#
# ops/__init__.py is the one place that sets it; each case imports the
# backend in a fresh process, since the placement happens at import.


def _cache_dir_in_fresh_process(env_updates, drop=()):
    env = {k: v for k, v in os.environ.items() if k not in drop}
    env.update(env_updates, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-c",
         "import jax, consensus_specs_tpu.ops.bls_backend; "
         "print(jax.config.jax_compilation_cache_dir)"],
        cwd=_REPO, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return proc.stdout.strip().splitlines()[-1]


def test_compile_cache_honours_jax_compilation_cache_dir(tmp_path):
    want = str(tmp_path / "outside_cache")
    assert _cache_dir_in_fresh_process(
        {"JAX_COMPILATION_CACHE_DIR": want}) == want


def test_compile_cache_defaults_to_repo_jax_cache():
    got = _cache_dir_in_fresh_process(
        {}, drop=("JAX_COMPILATION_CACHE_DIR",))
    assert got == os.path.join(_REPO, ".jax_cache")
