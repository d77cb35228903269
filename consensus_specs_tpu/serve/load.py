"""Synthetic gossip load + the `bench.py --mode serve` driver.

Models the serve plane's production shape: Poisson arrivals of committee
aggregates (n committees of k validators), heavy duplication (the same
aggregate heard from multiple peers), one known-bad aggregate (wrong
message for its signature — must come back False), and an injected
backend failure partway through the run (the poisoned batch must degrade
to the oracle path without losing or corrupting a single in-flight
request).

Emits the sustained signatures/sec + occupancy + cache-hit-rate + p95
latency record that `bench.py --mode serve` prints as its JSON line.

Env overrides (CPU-sized defaults; a chip run can scale up):
  SERVE_COMMITTEES, SERVE_K, SERVE_EVENTS, SERVE_RATE_HZ,
  SERVE_MAX_BATCH, SERVE_MAX_WAIT_MS, SERVE_INJECT_FAILURE (1/0),
  SERVE_SEED, SERVE_METRICS_PORT (opt-in /metrics + /snapshot + /healthz
  endpoint during the run; 0 = ephemeral port, reported in the JSON line)

``run_serve_mesh_sweep`` (`bench.py --mode serve-mesh` / `make
serve-bench-mesh`) runs the same load at several mesh device counts —
each in a fresh child process (`bench.py --mode serve --mesh <d>`),
because the virtual-device count is frozen at backend init — and emits
ONE line whose ``mesh`` section carries per-count sigs/sec, mesh
fallbacks, and the scaling efficiency vs the
single-device run (report-only on CPU virtual devices; the
ok-state is what tools/bench_compare.py gates round over round).
  SERVE_MESH_DEVICES ("1,2,4,8"), SERVE_MESH_TIMEOUT (s/child, 900)
"""
import json
import os
import random
import subprocess
import sys
import time
from collections.abc import Sequence
from dataclasses import dataclass
from typing import List, Tuple

from ..ops import profiling

# north-star share, same constant as bench.py's committee/epoch modes
TARGET_PER_CHIP = 150_000 / 8


class FailingBackendProxy:
    """Delegates to a real backend module but raises on chosen call
    numbers — the bench's device-failure injection. Failing calls 1 and 2
    poisons the FIRST batch twice (attempt + bounded retry), forcing the
    service onto the sequential oracle path while later batches prove the
    backend recovers."""

    def __init__(self, backend, fail_calls=(1, 2)):
        self._backend = backend
        self._fail_calls = set(fail_calls)
        self.calls = 0
        self.fired = 0

    def _maybe_fail(self):
        self.calls += 1
        if self.calls in self._fail_calls:
            self.fired += 1
            raise RuntimeError(f"injected device failure (call {self.calls})")

    def batch_fast_aggregate_verify(self, *args, **kwargs):
        self._maybe_fail()
        return self._backend.batch_fast_aggregate_verify(*args, **kwargs)

    def batch_aggregate_verify(self, *args, **kwargs):
        self._maybe_fail()
        return self._backend.batch_aggregate_verify(*args, **kwargs)

    def batch_verify_rlc(self, *args, **kwargs):
        # the RLC route counts against the same injected-failure schedule:
        # a poisoned combined batch must degrade through the per-group
        # path to the oracle without losing a request
        self._maybe_fail()
        return self._backend.batch_verify_rlc(*args, **kwargs)

    def prewarm_host_caches(self, *args, **kwargs):
        # codec prep never fails here: the injection targets the device
        # hard part, prep degradation has its own PREP_STATS counters
        return self._backend.prewarm_host_caches(*args, **kwargs)


# -- chain-plane gossip fault injection ---------------------------------------
#
# The head replay (bench/head_replay.py), the chain service tests, and the
# multi-node network simulation (consensus_specs_tpu/sim/) drive
# attestation gossip through the SAME VerificationService machinery as the
# signature bench above, but the thing under test is the fork-choice plane,
# not the pairing math — so the verdicts come from a deterministic
# crypto-free backend and the faults are planned per event:
#   "invalid_sig"   the attestation carries BAD_SIGNATURE; the service must
#                   answer False and the chain plane must DROP it;
#   "orphan"        the attestation references a block withheld from the
#                   stream; the chain plane must DEFER it and apply it only
#                   once the block arrives (deferred-then-resolved);
#   "equivocation"  the adversary pairs the event's block with a
#                   conflicting twin proposal at the same slot, published
#                   to a different subset of the network (simnet only —
#                   single-node replays treat it as "ok");
#   "censored_agg"  the adversarial aggregator never publishes this
#                   committee's aggregate — the votes vanish from every
#                   honest view (simnet counts them; the convergence gate
#                   excludes them from the union oracle).

BAD_SIGNATURE = b"\xba" * 96  # the injected invalid-signature marker

# every kind a fault plan may carry, in draw-priority order
FAULT_KINDS = ("ok", "invalid_sig", "orphan", "equivocation", "censored_agg")


@dataclass(frozen=True)
class GossipFaultPlan(Sequence):
    """The stable per-event fault plan shared by the head replay, the chain
    service tests, and ``sim/``'s scenario library.

    Sequence-shaped over the per-event kind strings (``plan[e]``,
    ``len(plan)``, ``plan.count("orphan")`` all work, so pre-dataclass
    callers are untouched) while carrying the rates that produced it —
    equality is structural, which is what the seed-determinism gate
    asserts: same seed, same rates -> identical plan."""

    kinds: Tuple[str, ...]
    invalid_rate: float = 0.0
    orphan_rate: float = 0.0
    equivocation_rate: float = 0.0
    censor_rate: float = 0.0

    def __post_init__(self):
        unknown = set(self.kinds) - set(FAULT_KINDS)
        if unknown:
            raise ValueError(f"unknown fault kinds in plan: {sorted(unknown)}")

    def __len__(self) -> int:
        return len(self.kinds)

    def __getitem__(self, index):
        return self.kinds[index]

    def counts(self) -> dict:
        """{kind: occurrences} over every kind (zeros included) — the
        scenario-matrix report's per-plan composition line."""
        return {kind: self.kinds.count(kind) for kind in FAULT_KINDS}


class VerdictBackend:
    """Crypto-free batched backend: the verdict rides IN the signature
    bytes (``BAD_SIGNATURE`` -> False, anything else -> True), so chain
    replays exercise the full service pipeline — batching, dedup, caching,
    False-verdict routing — without paying pairings for synthetic votes.
    Counts calls/items like the real backend's CALL_COUNTS ledger."""

    def __init__(self):
        self.calls = 0
        self.items = 0

    def _verdicts(self, signatures):
        self.calls += 1
        self.items += len(signatures)
        return [sig != BAD_SIGNATURE for sig in signatures]

    def batch_fast_aggregate_verify(self, pubkey_sets, messages, signatures):
        return self._verdicts([bytes(s) for s in signatures])

    def batch_aggregate_verify(self, pubkey_sets, message_sets, signatures):
        return self._verdicts([bytes(s) for s in signatures])


def plan_gossip_faults(rng: random.Random, events: int,
                       invalid_rate: float = 0.0,
                       orphan_rate: float = 0.0,
                       equivocation_rate: float = 0.0,
                       censor_rate: float = 0.0) -> GossipFaultPlan:
    """Per-event fault plan for an attestation gossip replay: one kind
    from ``FAULT_KINDS`` drawn independently per event (a single uniform
    draw split across the rate bands, so adding a rate never perturbs the
    draws of the kinds before it at a fixed seed). The first event is
    always clean so a replay never starts with an empty applied set."""
    kinds = []
    bands = (
        ("invalid_sig", invalid_rate),
        ("orphan", orphan_rate),
        ("equivocation", equivocation_rate),
        ("censored_agg", censor_rate),
    )
    for e in range(events):
        draw = rng.random()
        kind = "ok"
        if e:
            upper = 0.0
            for name, rate in bands:
                upper += rate
                if draw < upper:
                    kind = name
                    break
        kinds.append(kind)
    return GossipFaultPlan(
        kinds=tuple(kinds),
        invalid_rate=invalid_rate,
        orphan_rate=orphan_rate,
        equivocation_rate=equivocation_rate,
        censor_rate=censor_rate,
    )


def build_committees(n_committees: int, k: int, seed: int = 7
                     ) -> List[Tuple[list, bytes, bytes, bool]]:
    """(pubkeys, message, signature, expected) per committee. The last
    committee is corrupted (message swapped after signing) so the stream
    carries a known False. Signing uses the summed-secret-key identity
    (an aggregate of same-message signatures equals one signature by the
    summed key), so setup is n signs, not n*k."""
    from ..utils import bls
    from ..utils.bls12_381 import R

    committees = []
    for ci in range(n_committees):
        sks = [seed * 100_000 + ci * 1_000 + j + 1 for j in range(k)]
        pks = [bls.SkToPk(sk) for sk in sks]
        msg = ci.to_bytes(32, "little")
        sig = bls.Sign(sum(sks) % R, msg)
        committees.append((pks, msg, sig, True))
    if committees:
        pks, msg, sig, _ = committees[-1]
        committees[-1] = (pks, b"\xff" + msg[1:], sig, False)
    return committees


def _event_schedule(rng: random.Random, committees, events: int):
    """Committee index per event. The first half of the stream only draws
    from the first half of the committees, the rest join later — so new
    distinct content keeps arriving after the (injected-failure) first
    batches and the recovered backend demonstrably serves it."""
    n = len(committees)
    early = max(1, n // 2)
    picks = []
    for e in range(events):
        pool = early if e < events // 2 else n
        picks.append(rng.randrange(pool))
    return picks


def run_serve_bench(target: float = TARGET_PER_CHIP) -> dict:
    """Drive a synthetic Poisson gossip stream through a
    VerificationService; returns bench.py's result dict (ready for
    ``_emit_result``). Raises if any request is lost or answered wrong —
    a serve bench that corrupts the stream must fail loudly, not record a
    throughput number."""
    from ..ops import bls_backend
    from .service import VerificationService

    # clean slate: the serve line always attaches profiling.summary(), and
    # a prior mode's histograms/gauges in this process (multi-mode bench
    # runs, tests) must not bleed into it; the once-per-process vm-cache
    # gauges are re-published after the wipe. The SLO tracker resets too
    # — burn windows start at THIS run
    from ..obs import programs as obs_programs, slo

    profiling.reset()
    obs_programs.export_gauges()
    slo.reset_global()
    # baseline checkpoint at run start: the end-of-run slo section's burn
    # windows then measure THIS run's error mass (one evaluate() with an
    # empty ring would otherwise diff against itself — zero burn forever)
    slo.global_tracker().evaluate()

    # rate sized so a max_wait flush window catches several events (~4 ms
    # apart at 256 Hz): micro-batches then carry >1 unique committee and
    # the RLC combine path actually combines instead of degenerating to
    # single-item flushes (round 6; the JSON line carries every knob)
    n_committees = int(os.environ.get("SERVE_COMMITTEES", "8"))
    k = int(os.environ.get("SERVE_K", "8"))
    events = int(os.environ.get("SERVE_EVENTS", "64"))
    rate_hz = float(os.environ.get("SERVE_RATE_HZ", "256"))
    max_batch = int(os.environ.get("SERVE_MAX_BATCH", "32"))
    max_wait_ms = float(os.environ.get("SERVE_MAX_WAIT_MS", "20"))
    inject = os.environ.get("SERVE_INJECT_FAILURE", "1") == "1"
    seed = int(os.environ.get("SERVE_SEED", "7"))

    rng = random.Random(seed)
    committees = build_committees(n_committees, k, seed=seed)
    picks = _event_schedule(rng, committees, events)

    # pay the XLA compile outside the timed window: one warmup verify of a
    # committee NOT in the stream, straight through the real backend
    from ..utils import bls
    from ..utils.bls12_381 import R

    warm_sks = list(range(1, k + 1))
    warm_msg = b"warmup" + b"\x00" * 26
    t0 = time.perf_counter()
    warm_ok = bls_backend.batch_fast_aggregate_verify(
        [[bls.SkToPk(sk) for sk in warm_sks]],
        [warm_msg],
        [bls.Sign(sum(warm_sks) % R, warm_msg)],
    )
    warmup_s = time.perf_counter() - t0
    assert bool(warm_ok[0]), "serve bench warmup verification failed"

    # with a mesh armed (CONSENSUS_SPECS_TPU_MESH / bench --mesh), pay the
    # SHARDED executables' compiles outside the timed window too: one
    # flush-shaped RLC batch of warm-only committees (a different seed, so
    # none of their content appears in the stream), corrupted last item
    # included so the bisection path's shapes warm as well
    from ..utils import jax_env

    warm_mesh = jax_env.maybe_mesh()
    mesh_warmup_s = 0.0
    if warm_mesh is not None:
        t0 = time.perf_counter()
        warm_items = [
            ("fast_aggregate", pks, msg, sig)
            for pks, msg, sig, _ok in build_committees(
                n_committees, k, seed=seed + 1)
        ]
        # flush sizes vary with stream dedup (a full first flush, then
        # mostly singletons as late committees join), and every size is
        # its own padded executable — warm the common ones, largest
        # first so its program/compile work is in place for the rest.
        # Sizes below the device count warm UNSHARDED: the service
        # routes such narrow flushes single-device (_flush_mesh), so the
        # sharded row-padded shapes would never run in-stream
        import math

        n_dev = math.prod(warm_mesh.shape.values())
        for size in sorted({len(warm_items), max(1, len(warm_items) // 2),
                            2, 1}, reverse=True):
            bls_backend.batch_verify_rlc(
                warm_items[:size],
                mesh=warm_mesh if size >= n_dev else None,
            )
        mesh_warmup_s = time.perf_counter() - t0

    backend = FailingBackendProxy(bls_backend) if inject else bls_backend
    svc = VerificationService(
        backend=backend, max_batch=max_batch, max_wait_ms=max_wait_ms
    )
    # opt-in exposition endpoint, live DURING the load (SERVE_METRICS_PORT;
    # 0 = ephemeral): /metrics Prometheus text, /snapshot ServeMetrics
    # JSON, /healthz — scraped once mid-load to prove it answers under
    # fire. The whole load runs under try/finally: the service drains and
    # the port unbinds even when a submit or the (non-fatal) scrape fails.
    exposition, scrape = None, None
    port_env = (os.environ.get("SERVE_METRICS_PORT") or "").strip()
    try:
        if port_env:
            from ..obs.exposition import start_exposition

            exposition = start_exposition(metrics=svc.metrics,
                                          port=int(port_env))
        futures, expected, sig_count = [], [], 0
        t_start = time.perf_counter()
        t_next = t_start
        for ci in picks:
            pks, msg, sig, ok = committees[ci]
            futures.append(svc.submit("fast_aggregate", pks, msg, sig))
            expected.append(ok)
            sig_count += len(pks)
            t_next += rng.expovariate(rate_hz)
            pause = t_next - time.perf_counter()
            if pause > 0:
                time.sleep(pause)
        scrape_thread, scrape_box = None, {}
        if exposition is not None:
            # the stream is fully submitted but far from drained: this
            # scrape happens under live traffic — on a HELPER thread, so
            # a slow/wedged endpoint can never inflate the elapsed window
            # the sigs/sec headline divides by. A failed scrape is a
            # recorded observation (scrape stays None), never the reason
            # the primary measurement dies
            import threading
            import urllib.request

            def _scrape():
                try:
                    with urllib.request.urlopen(exposition.url("/metrics"),
                                                timeout=30) as resp:
                        scrape_box["body"] = resp.read().decode()
                except Exception:
                    pass

            scrape_thread = threading.Thread(target=_scrape, daemon=True)
            scrape_thread.start()
        # bounded wait FIRST, then harvest: calling f.result(timeout=...)
        # in a loop would raise on the first unresolved future and never
        # reach the lost-request accounting below
        import concurrent.futures as cf

        _, pending = cf.wait(futures, timeout=600)
        elapsed = time.perf_counter() - t_start
        if scrape_thread is not None:
            scrape_thread.join(35)
            scrape = scrape_box.get("body")
    finally:
        svc.close(timeout=60)
        if exposition is not None:
            exposition.close()

    lost = len(pending)
    results = [bool(f.result()) if f.done() else None for f in futures]
    wrong = sum(
        1 for r, ok in zip(results, expected)
        if r is not None and r is not ok
    )
    if lost or wrong:
        raise AssertionError(
            f"serve stream integrity violated: {lost} lost, {wrong} wrong "
            f"of {events} requests (injected_failures="
            f"{getattr(backend, 'fired', 0)})"
        )

    snap = svc.metrics.snapshot()
    # fleet-observability section, evaluated BEFORE the profile
    # snapshot so the slo.* gauges it publishes ride the attached
    # profiling.summary() too: the SLO state the round-over-round gate
    # (tools/bench_compare.py) diffs
    slo_section = slo.global_tracker().bench_section()
    # SERVED vs VERIFIED: the duplicate-heavy stream is answered mostly by
    # the cache/dedup layer, so served/sec is the serving-plane headline
    # while verified/sec (unique content that actually reached crypto) is
    # what compares against the raw-verification north star — vs_baseline
    # must not be inflated by the SERVE_* duplication ratio
    served_per_sec = sig_count / elapsed
    verified_keys = sum(len(committees[ci][0]) for ci in set(picks))
    verified_per_sec = verified_keys / elapsed
    result = dict(
        metric="sustained aggregate BLS signatures served/sec (serve)",
        value=served_per_sec,
        vs_baseline=verified_per_sec / target,
        verified_sigs_per_sec=round(verified_per_sec, 2),
        sigs_served=sig_count,
        sigs_verified=verified_keys,
        mode="serve",
        events=events,
        committees=n_committees,
        k=k,
        rate_hz=rate_hz,
        elapsed_s=round(elapsed, 3),
        warmup_s=round(warmup_s, 3),
        occupancy_mean=snap["occupancy_rows"],
        occupancy_lanes=snap["occupancy_lanes"],
        cache_hit_rate=snap["cache_hit_rate"],
        p50_ms=snap["latency"].get("p50_ms", 0.0),
        p95_ms=snap["latency"].get("p95_ms", 0.0),
        p99_ms=snap["latency"].get("p99_ms", 0.0),
        # observation count behind the percentiles (statistical weight)
        latency_n=snap["latency"].get("n", 0),
        batches=snap["batches"],
        # prep-vs-device split: where each flush's time goes (host codec
        # prep of the NEXT batch overlaps the device hard part, so the
        # pipeline's critical path is max(prep, device), not the sum)
        prep_ms_per_flush=snap["prep_ms_per_flush"],
        device_ms_per_flush=snap["device_ms_per_flush"],
        prep_serial_fallback_items=snap["prep"].get(
            "serial_fallback_items", 0
        ),
        # RLC amortization: final exponentiations per served request (the
        # tentpole's headline — per-item finalization would be ~1.0 before
        # dedup; the combine + cache layers push it well under 0.2 at
        # steady state), with the combine/bisection counts alongside
        final_exps_per_item=snap["final_exps_per_item"],
        rlc_combines=snap["rlc"].get("combines", 0),
        rlc_bisections=snap["rlc"].get("bisections", 0),
        fallback_items=snap["fallback_items"],
        fault_injected=bool(inject and getattr(backend, "fired", 0)),
        lost=lost,
        wrong=wrong,
        slo=slo_section,
        profile=profiling.summary(),
    )
    if svc.mesh_devices:
        # the single-run mesh record (per-device-COUNT rows are the sweep
        # driver's job — run_serve_mesh_sweep assembles its `mesh` section
        # from one child line per count)
        result["mesh_devices"] = svc.mesh_devices
        result["mesh_fallbacks"] = snap["mesh_fallbacks"]
        result["mesh_warmup_s"] = round(mesh_warmup_s, 3)
    if exposition is not None:
        result["metrics_port"] = exposition.port
        result["metrics_scrape_ok"] = scrape is not None
        result["metrics_scrape_lines"] = len((scrape or "").splitlines())
    return result


# -- mesh scaling sweep (`bench.py --mode serve-mesh`) ------------------------


def _parse_last_json_line(stdout: bytes):
    """Last parseable JSON object in a child's stdout, or None."""
    parsed = None
    for line in stdout.decode(errors="replace").strip().splitlines():
        try:
            parsed = json.loads(line)
        except ValueError:
            continue
    return parsed


def run_serve_mesh_sweep() -> dict:
    """Serve-plane mesh scaling matrix: one `bench.py --mode serve
    --mesh <d>` CHILD per device count (the virtual-device count is read
    once at XLA backend init, so counts cannot share a process), fault
    injection off (the sweep measures scaling on clean traffic — the
    degradation ladder has its own bench and tests). Returns bench.py's
    result dict; the ``mesh`` section maps device count -> {sigs_per_sec,
    verified_sigs_per_sec, ok, fallbacks, efficiency}.

    Efficiency (sigs/sec at d devices / (d * single-device sigs/sec)) and
    the 10%-of-single regression check are REPORT-ONLY on CPU virtual
    devices (2 host cores timeshare every "device"; true scaling needs
    real chips) — what bench_compare gates is the ok-STATE: a device
    count that verified last round and errors now fails the round."""
    counts = []
    for tok in os.environ.get("SERVE_MESH_DEVICES", "1,2,4,8").split(","):
        tok = tok.strip()
        if tok and tok.isdigit() and int(tok) > 0:
            counts.append(int(tok))
    timeout = float(os.environ.get("SERVE_MESH_TIMEOUT", "900"))
    bench_path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))), "bench.py")

    rows = {}
    for d in counts:
        env = os.environ.copy()
        env["JAX_PLATFORMS"] = "cpu"
        # hard assignment, not setdefault: the sweep's contract is clean
        # traffic — an inherited SERVE_INJECT_FAILURE=1 would make every
        # child's first sharded flush record a phantom mesh fallback
        env["SERVE_INJECT_FAILURE"] = "0"
        cmd = [sys.executable, bench_path, "--mode", "serve",
               "--mesh", str(d)]
        try:
            out = subprocess.run(cmd, capture_output=True, timeout=timeout,
                                 env=env)
            parsed = _parse_last_json_line(out.stdout)
        except subprocess.TimeoutExpired:
            rows[str(d)] = {"ok": False,
                            "error": f"child exceeded {timeout:.0f}s"}
            continue
        if parsed is None or "error" in parsed or parsed.get("value", 0) <= 0:
            err = (parsed or {}).get("error") or (
                out.stderr.decode(errors="replace").strip()
                .splitlines()[-1:] or ["no parseable output"])[0]
            rows[str(d)] = {"ok": False, "error": str(err)[:300]}
            continue
        rows[str(d)] = {
            "ok": True,
            "sigs_per_sec": round(float(parsed["value"]), 2),
            "verified_sigs_per_sec": parsed.get("verified_sigs_per_sec", 0.0),
            "final_exps_per_item": parsed.get("final_exps_per_item", 0.0),
            "fallbacks": parsed.get("mesh_fallbacks", 0),
            "p99_ms": parsed.get("p99_ms", 0.0),
        }

    single = rows.get("1", {})
    base = single.get("sigs_per_sec", 0.0) if single.get("ok") else 0.0
    for d_str, row in rows.items():
        d = int(d_str)
        if row.get("ok") and base > 0 and d > 1:
            row["efficiency"] = round(
                row["sigs_per_sec"] / (d * base), 4)
            # the CPU acceptance check: sharding must not cost the serve
            # plane more than 10% of single-device throughput (scaling
            # itself is report-only until real accelerator rounds)
            row["within_10pct_of_single"] = bool(
                row["sigs_per_sec"] >= 0.9 * base)

    ok_rows = {d: r for d, r in rows.items() if r.get("ok")}
    best = max((r["sigs_per_sec"] for r in ok_rows.values()), default=0.0)
    best_verified = max(
        (float(r.get("verified_sigs_per_sec") or 0.0)
         for r in ok_rows.values()), default=0.0)
    return dict(
        metric="sustained aggregate BLS signatures served/sec "
               "(serve, mesh sweep)",
        value=best,
        vs_baseline=best_verified / TARGET_PER_CHIP,
        platform="cpu",
        mode="serve-mesh",
        device_counts=counts,
        mesh=rows,
    )
