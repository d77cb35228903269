"""Fleet observability: the cross-plane flight recorder
(obs/flight.py) with its fault-triggered JSONL dump, and SLO burn-rate
tracking (obs/slo.py) with the upgraded /healthz. Everything runs against crypto-free backends so
tier-1 stays fast; the real-crypto glue is `make serve-trace` /
`make serve-bench`.
"""
import json
import os
import threading
import time
import urllib.error
import urllib.request

import pytest

from consensus_specs_tpu.obs import flight, slo, tracing
from consensus_specs_tpu.obs.exposition import start_exposition
from consensus_specs_tpu.ops import profiling
from consensus_specs_tpu.serve import VerificationService
from consensus_specs_tpu.serve.load import (BAD_SIGNATURE,
                                            FailingBackendProxy,
                                            VerdictBackend)
from consensus_specs_tpu.utils import bls

PK = b"\x01" * 48


@pytest.fixture(autouse=True)
def _clean_slate(monkeypatch):
    monkeypatch.setenv("CONSENSUS_SPECS_TPU_TRACE", "0")
    monkeypatch.setenv("CONSENSUS_SPECS_TPU_FLIGHT", "0")
    monkeypatch.delenv("CONSENSUS_SPECS_TPU_SLO", raising=False)
    profiling.reset()
    tracing.reset_global()
    tracing.reset_flush_records()
    flight.reset_global()
    slo.reset_global()
    was = bls.bls_active
    bls.bls_active = True
    yield
    bls.bls_active = was
    tracing.reset_global()
    tracing.reset_flush_records()
    flight.reset_global()
    slo.reset_global()


class RlcVerdictBackend(VerdictBackend):
    """VerdictBackend + the RLC entry point, so the serve default route
    (and therefore the FULL degradation ladder: RLC -> per-group ->
    oracle) is exercisable with crypto-free verdicts."""

    def batch_verify_rlc(self, items, mesh=None, rng=None):
        self.calls += 1
        return [bytes(sig) != BAD_SIGNATURE
                for _kind, _pks, _msgs, sig in items]


class _Oracle:
    def verify_one(self, pending):
        return bytes(pending.signature) != BAD_SIGNATURE


def _svc(backend, **kw):
    kw.setdefault("bucket_fn", lambda k: 8)
    kw.setdefault("oracle", _Oracle())
    return VerificationService(backend=backend, **kw)


# -- flight recorder ----------------------------------------------------------


def test_flight_ring_is_bounded_and_counts_drops():
    rec = flight.FlightRecorder(capacity=4, clock=lambda: 1.0)
    for i in range(10):
        rec.note("serve", "flush", items=i)
    events = rec.events()
    assert len(events) == 4
    assert [e["data"]["items"] for e in events] == [6, 7, 8, 9]
    c = rec.counters()
    assert c["events"] == 10 and c["dropped"] == 6 and c["retained"] == 4


def test_flight_dump_jsonl_roundtrip(tmp_path):
    rec = flight.FlightRecorder(capacity=16, clock=lambda: 2.5)
    rec.note("chain", "on_block", slot=7, root="ab" * 8)
    rec.note("vm", "assembly_stall", key="hard_part[k=0,fold=32]",
             seconds=6.2)
    path = rec.dump(str(tmp_path / "flight.jsonl"), reason="test")
    lines = [json.loads(l) for l in open(path).read().splitlines()]
    assert lines[0] == {"flight": "v1", "reason": "test", "events": 2,
                        "retained": 2, "dropped": 0}
    assert lines[1]["plane"] == "chain" and lines[1]["kind"] == "on_block"
    assert lines[1]["data"]["slot"] == 7 and lines[1]["seq"] == 1
    assert lines[2]["data"]["key"] == "hard_part[k=0,fold=32]"
    rec.export_gauges()
    summ = profiling.summary()
    assert summ["flight.events"] == {"gauge": 2.0}
    assert summ["flight.dumps"] == {"gauge": 1.0}


def test_flight_off_path_is_a_none_check_and_overhead_is_bounded(
        monkeypatch):
    """The PR 4 zero-cost bar: with the recorder off the service stores
    None (no locks, env reads, or allocations join the hot path); with it
    on, the per-event cost stays at deque-append scale. Both sides are
    measured so the overhead claim is a number, not an assertion."""
    monkeypatch.setenv("CONSENSUS_SPECS_TPU_FLIGHT", "0")
    with _svc(RlcVerdictBackend(), max_batch=1, max_wait_ms=0) as svc:
        assert svc._flight is None
    assert flight.maybe_recorder() is None

    n = 20_000
    # OFF path: the exact branch every hot-path site runs when disabled —
    # one attribute load + identity check, no locks/env reads/allocations
    off_guard = None
    t0 = time.perf_counter()
    acc = 0
    for _ in range(n):
        if off_guard is not None:  # pragma: no cover - never taken
            acc += 1
    per_off = (time.perf_counter() - t0) / n
    # ON path
    rec = flight.FlightRecorder(capacity=4096)
    t0 = time.perf_counter()
    for i in range(n):
        rec.note("serve", "flush", items=i)
    per_event = (time.perf_counter() - t0) / n
    # deque-append scale: microseconds, not milliseconds (generous bounds
    # so a loaded CI host never flaps); both sides measured so the
    # overhead claim is a number, not an assertion
    print(f"flight overhead: off {per_off * 1e9:.0f}ns/event, "
          f"on {per_event * 1e6:.2f}us/event")
    assert per_off < 1e-5, f"off-path guard cost {per_off * 1e9:.0f}ns"
    assert per_event < 1e-3, f"flight note cost {per_event * 1e6:.1f}us"
    assert rec.counters()["events"] == n


def test_flight_ring_env_tolerates_malformed_values(monkeypatch):
    """A typo'd CONSENSUS_SPECS_TPU_FLIGHT_RING must degrade to the
    default capacity, never crash the service construction that armed
    the recorder."""
    monkeypatch.setenv("CONSENSUS_SPECS_TPU_FLIGHT", "1")
    for bad in ("4k", "", "-5"):
        monkeypatch.setenv("CONSENSUS_SPECS_TPU_FLIGHT_RING", bad)
        flight.reset_global()
        rec = flight.maybe_recorder()
        assert rec is not None
        assert rec._ring.maxlen == flight.DEFAULT_RING
    monkeypatch.setenv("CONSENSUS_SPECS_TPU_FLIGHT_RING", "16")
    flight.reset_global()
    assert flight.maybe_recorder()._ring.maxlen == 16


def test_flightdump_endpoint_serves_jsonl_and_404s_when_off(monkeypatch):
    monkeypatch.setenv("CONSENSUS_SPECS_TPU_FLIGHT", "1")
    flight.reset_global()
    flight.note("serve", "flush", items=3)
    with start_exposition(port=0) as server:
        with urllib.request.urlopen(server.url("/flightdump"),
                                    timeout=30) as resp:
            body = resp.read().decode()
        lines = [json.loads(l) for l in body.splitlines()]
        assert lines[0]["flight"] == "v1"
        assert lines[1]["kind"] == "flush"
        monkeypatch.setenv("CONSENSUS_SPECS_TPU_FLIGHT", "0")
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(server.url("/flightdump"), timeout=30)


def test_injected_serve_fault_dumps_a_ladder_reconstruction(
        monkeypatch, tmp_path):
    """The ISSUE 7 acceptance path: BAD_SIGNATURE traffic (serve/load.py)
    flows while an injected backend failure poisons the first flush
    repeatedly; the flight dump written ON the fault must reconstruct the
    degradation-ladder transition — flush, RLC retry, RLC->per-group,
    group retry, ->oracle — in journal order."""
    dump_path = str(tmp_path / "fault.jsonl")
    monkeypatch.setenv("CONSENSUS_SPECS_TPU_FLIGHT", "1")
    monkeypatch.setenv("CONSENSUS_SPECS_TPU_FLIGHT_DUMP", dump_path)
    flight.reset_global()
    # calls 1+2 poison the RLC attempt+retry, 3+4 the per-group
    # attempt+retry -> the ladder bottoms out on the oracle and dumps
    backend = FailingBackendProxy(RlcVerdictBackend(),
                                  fail_calls=(1, 2, 3, 4))
    with _svc(backend, max_batch=4, max_wait_ms=10_000,
              backend_retries=1) as svc:
        futs = [
            svc.submit("fast_aggregate", [PK], b"m0", b"ok"),
            svc.submit("fast_aggregate", [PK], b"m1", BAD_SIGNATURE),
            svc.submit("fast_aggregate", [PK], b"m2", b"ok"),
            svc.submit("fast_aggregate", [PK], b"m3", b"ok"),
        ]
        results = [f.result(timeout=30) for f in futs]
    # stream integrity survived the full degradation
    assert results == [True, False, True, True]
    assert backend.fired == 4
    assert os.path.exists(dump_path), "fault did not dump the journal"
    lines = [json.loads(l) for l in open(dump_path).read().splitlines()]
    assert lines[0]["reason"] == "serve_backend_degraded_to_oracle"
    kinds = [(e["plane"], e["kind"]) for e in lines[1:]]
    ladder = [("serve", "flush"),
              ("serve", "backend_retry"),          # rlc retry
              ("serve", "degraded_rlc_to_groups"),
              ("serve", "backend_retry"),          # per-group retry
              ("serve", "degraded_to_oracle"),
              ("flight", "fault")]
    it = iter(kinds)
    assert all(step in it for step in ladder), (
        f"ladder not reconstructable from {kinds}"
    )
    stages = [e["data"].get("stage") for e in lines[1:]
              if e["kind"] == "backend_retry"]
    assert stages == ["rlc", "group"]
    # seq strictly increases: the journal is ordered evidence
    seqs = [e["seq"] for e in lines[1:]]
    assert seqs == sorted(seqs)


# -- SLO tracking -------------------------------------------------------------


def test_slo_objectives_env_overrides(monkeypatch):
    monkeypatch.setenv("CONSENSUS_SPECS_TPU_SLO",
                       "serve_p99_ms=120,chain_p99_ms=77")
    objs = {o["name"]: o for o in slo.declared_objectives()}
    assert objs["serve_p99"]["threshold_s"] == pytest.approx(0.120)
    assert objs["chain_p99"]["threshold_s"] == pytest.approx(0.077)


def test_slo_vacuously_ok_with_no_traffic():
    tracker = slo.SloTracker(clock=lambda: 0.0)
    out = tracker.evaluate()
    assert all(e["ok"] and e["n"] == 0 for e in out.values())
    summ = profiling.summary()
    assert summ["slo.ok"] == {"gauge": 1.0}
    assert summ["slo.violations"] == {"gauge": 0.0}


def test_slo_violation_and_margin(monkeypatch):
    monkeypatch.setenv("CONSENSUS_SPECS_TPU_SLO", "serve_p99_ms=50")
    for _ in range(100):
        profiling.record_latency("serve.submit_to_result", 0.010)
    for _ in range(10):  # 9% of traffic way over the 50ms objective
        profiling.record_latency("serve.submit_to_result", 0.500)
    tracker = slo.SloTracker(clock=lambda: 0.0)
    out = tracker.evaluate()
    serve = out["serve_p99"]
    assert serve["n"] == 110 and not serve["ok"]
    assert serve["attained_ms"] > 50.0
    assert serve["margin"] < 1.0
    assert serve["bad_fraction"] == pytest.approx(10 / 110, abs=1e-6)
    summ = profiling.summary()
    assert summ["slo.ok"] == {"gauge": 0.0}
    assert summ["slo.violations"] == {"gauge": 1.0}


def test_slo_multi_window_burn_rates_see_a_fresh_burst(monkeypatch):
    """A burst of errors inside the fast window burns hot against the
    60s window while the 300s window (which also saw the clean history)
    burns slower — the multi-window page/ticket split."""
    monkeypatch.setenv("CONSENSUS_SPECS_TPU_SLO", "serve_p99_ms=50")
    t = {"now": 0.0}
    tracker = slo.SloTracker(clock=lambda: t["now"])
    tracker.evaluate()  # empty baseline checkpoint at t=0
    t["now"] = 10.0
    for _ in range(980):
        profiling.record_latency("serve.submit_to_result", 0.010)
    t["now"] = 280.0
    tracker.evaluate()  # clean checkpoint inside the slow window only
    t["now"] = 290.0    # burst now: 50% of fresh traffic is over-objective
    for _ in range(10):
        profiling.record_latency("serve.submit_to_result", 0.500)
    for _ in range(10):
        profiling.record_latency("serve.submit_to_result", 0.010)
    out = tracker.evaluate()
    burn = out["serve_p99"]["burn_rate"]
    # fast window: 10 bad / 20 new = 0.5 bad fraction over a 0.01 budget
    assert burn["60s"] == pytest.approx(50.0)
    # slow window baseline is t=0: 10 bad / 1000 new = 1.0x burn
    assert burn["300s"] == pytest.approx(1.0)
    assert profiling.summary()["slo.worst_burn_rate"] == {"gauge": 50.0}


def test_healthz_reports_slo_state(monkeypatch):
    monkeypatch.setenv("CONSENSUS_SPECS_TPU_SLO", "serve_p99_ms=50")
    slo.reset_global()
    for _ in range(50):
        profiling.record_latency("serve.submit_to_result", 0.200)
    with start_exposition(port=0) as server:
        with urllib.request.urlopen(server.url("/healthz"),
                                    timeout=30) as resp:
            body = json.loads(resp.read().decode())
    assert body["ok"] is False  # violated objective flips liveness detail
    assert body["slo"]["serve_p99"]["ok"] is False
    assert body["slo"]["chain_p99"]["ok"] is True  # vacuous


def test_slo_bench_flow_reports_nonzero_burn(monkeypatch):
    """The bench path (reset -> baseline evaluate -> run -> section):
    violations during the run must show up as burn, not the structural
    0.0 a single end-of-run evaluate would produce with no baseline."""
    monkeypatch.setenv("CONSENSUS_SPECS_TPU_SLO", "serve_p99_ms=50")
    slo.reset_global()
    slo.global_tracker().evaluate()  # the baseline the benches record
    for _ in range(80):
        profiling.record_latency("serve.submit_to_result", 0.010)
    for _ in range(20):
        profiling.record_latency("serve.submit_to_result", 0.500)
    section = slo.global_tracker().bench_section()
    serve = section["serve_p99"]
    assert serve["ok"] is False
    # 20 bad / 100 in-run over a 0.01 budget
    assert serve["burn_rate"]["60s"] == pytest.approx(20.0)


def test_slo_bench_section_shape():
    for _ in range(64):
        profiling.record_latency("serve.submit_to_result", 0.020)
    section = slo.global_tracker().bench_section()
    serve = section["serve_p99"]
    assert serve["ok"] is True and serve["n"] == 64
    assert serve["margin"] > 1.0
    assert set(serve["burn_rate"]) == {"60s", "300s"}
    assert "margin" not in section["chain_p99"]  # no traffic, no margin


# -- concurrent scrape over the whole fleet plane -----------------------------


def test_fleet_writers_vs_scrape_hammer(monkeypatch):
    """Histogram writers + flight notes + flush records racing /metrics
    and /healthz scrapes: no exceptions, consistent totals."""
    monkeypatch.setenv("CONSENSUS_SPECS_TPU_FLIGHT", "1")
    flight.reset_global()
    errors = []
    stop = threading.Event()
    n_threads, iters = 3, 300

    def writer(tid):
        try:
            for i in range(iters):
                profiling.record_latency("serve.submit_to_result",
                                         0.001 * (i % 7 + 1))
                flight.note("serve", "flush", items=i)
                with tracing.rlc_record(tid + 1):
                    with tracing.span("rlc.prep"):
                        pass
        except Exception as e:  # pragma: no cover
            errors.append(e)

    def reader(server):
        try:
            while not stop.is_set():
                urllib.request.urlopen(server.url("/metrics"),
                                       timeout=30).read()
                urllib.request.urlopen(server.url("/healthz"),
                                       timeout=30).read()
        except Exception as e:  # pragma: no cover
            errors.append(e)

    with start_exposition(port=0) as server:
        threads = [threading.Thread(target=writer, args=(t,))
                   for t in range(n_threads)]
        r = threading.Thread(target=reader, args=(server,))
        r.start()
        for t in threads:
            t.start()
        for t in threads:
            t.join(30)
        stop.set()
        r.join(30)
    assert errors == []
    assert flight.global_recorder().counters()["events"] == n_threads * iters
    lat = profiling.latency_summary()["serve.submit_to_result"]
    assert lat["n"] == n_threads * iters
    records = tracing.flush_records()
    assert len(records) == n_threads * iters
    assert tracing.flush_records_dropped() == 0
    assert sorted({r["items"] for r in records}) == list(
        range(1, n_threads + 1))
    assert len({r["id"] for r in records}) == n_threads * iters
