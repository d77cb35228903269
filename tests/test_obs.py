"""Observability plane (consensus_specs_tpu/obs/): span tracing through
the serve pipeline, Chrome trace export (golden-schema gated), the
Prometheus /metrics + /snapshot + /healthz endpoint under live load,
concurrent writers-vs-readers safety, the per-program VM registry, and
the profiling satellites (dynamic ENABLED, full reset).

Everything here runs against crypto-free backends so tier-1 stays fast;
the real-crypto serve path is covered by tests/test_serve.py and the
trace/endpoint glue by `make serve-trace`.
"""
import json
import os
import random
import re
import sys
import threading
import time
import urllib.request

import pytest

from consensus_specs_tpu.obs import flight, slo
from consensus_specs_tpu.obs import programs as obs_programs
from consensus_specs_tpu.obs import registry, tracing
from consensus_specs_tpu.obs.exposition import start_exposition
from consensus_specs_tpu.obs.tracing import (
    CHAIN_STAGES,
    STAGES,
    WORKER_PID_BASE,
    Tracer,
    stitched_chrome,
    trace_to_wire,
    wire_spans,
)
from consensus_specs_tpu.ops import profiling
from consensus_specs_tpu.serve import VerificationService
from consensus_specs_tpu.serve.metrics import ServeMetrics
from consensus_specs_tpu.utils import bls

PK = b"\x01" * 48
GOLDEN = os.path.join(os.path.dirname(os.path.abspath(__file__)), "golden",
                      "obs_trace_golden.json")


@pytest.fixture(autouse=True)
def _clean_slate(monkeypatch):
    # the obs plane and profiling are process-global; every test starts
    # from zero and leaves tracing disabled
    monkeypatch.setenv("CONSENSUS_SPECS_TPU_TRACE", "0")
    monkeypatch.setenv("CONSENSUS_SPECS_TPU_FLIGHT", "0")
    profiling.reset()
    tracing.reset_global()
    obs_programs.reset()
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


class RlcBackend:
    """Crypto-free batched backend WITH the RLC entry point (so the serve
    default route — and therefore the `combine` span — is exercised):
    an item verifies True iff its signature ends with b"ok"."""

    def __init__(self):
        self.rlc_calls = 0
        self.calls = 0

    def batch_verify_rlc(self, items, mesh=None, rng=None):
        self.rlc_calls += 1
        return [sig.endswith(b"ok") for _kind, _pks, _msgs, sig in items]

    def _go(self, signatures):
        self.calls += 1
        return [s.endswith(b"ok") for s in signatures]

    def batch_fast_aggregate_verify(self, pubkey_sets, messages, signatures,
                                    mesh=None):
        return self._go(signatures)

    def batch_aggregate_verify(self, pubkey_lists, message_lists, signatures,
                               mesh=None):
        return self._go(signatures)


class _Oracle:
    def verify_one(self, pending):
        return bytes(pending.signature).endswith(b"ok")


def _svc(backend, **kw):
    kw.setdefault("bucket_fn", lambda k: 8)
    kw.setdefault("oracle", _Oracle())
    return VerificationService(backend=backend, **kw)


# -- tracer core ------------------------------------------------------------


def test_tracer_ring_is_bounded():
    tr = Tracer(capacity=8)
    for i in range(50):
        t = tr.begin("fast_aggregate", 2, t_submit=float(i))
        tr.span(t, "queue_wait", float(i), float(i) + 0.5)
        tr.finish(t, True, t_done=float(i) + 1.0)
    done = tr.completed()
    assert len(done) == 8  # bounded, keeps the newest
    assert done[-1].total_s == 1.0 and done[-1].ok is True
    assert done[0].rid == 43  # 50 begun, first 42 evicted
    assert tr.finished_total() == 50  # the monotone count is NOT capped
    other = tr.to_chrome()["otherData"]
    assert (other["requests"], other["finished_total"]) == (8, 50)


def test_tracer_pins_slow_exemplars_over_running_p99():
    tr = Tracer(capacity=256, exemplar_capacity=4)
    # 100 fast requests establish the running p99, then one 100x outlier
    for i in range(100):
        t = tr.begin("fast_aggregate", 1, t_submit=0.0)
        tr.finish(t, True, t_done=0.010)
    slow = tr.begin("fast_aggregate", 1, t_submit=0.0)
    tr.finish(slow, True, t_done=1.0)
    assert slow.pinned
    assert slow in tr.exemplars()
    assert len(tr.exemplars()) <= 4
    assert tr.running_p99_s() > 0


def test_events_before_tracer_epoch_never_export_negative_ts():
    """The global tracer is created lazily: the first traced VM execution
    (or a trace begun with an earlier explicit t_submit) can predate the
    tracer's epoch. The epoch rewinds so Perfetto never clamps/drops
    those events for sitting before the trace origin."""
    tr = Tracer(clock=lambda: 100.0)  # epoch = 100.0
    tr.note_execution(steps=1, regs=1, batch=(), sharded=False,
                      t0=40.0, seconds=30.0)  # finished before epoch
    early = tr.begin("fast_aggregate", 1, t_submit=50.0)
    tr.span(early, "queue_wait", 50.0, 60.0)
    tr.finish(early, True, t_done=60.0)
    for ev in tr.to_chrome()["traceEvents"]:
        if ev["ph"] == "X":
            assert ev["ts"] >= 0.0, ev
    # the execution sits exactly at the (rewound) origin
    vm_ev = [e for e in tr.to_chrome()["traceEvents"]
             if e["pid"] == 2 and e["ph"] == "X"][0]
    assert vm_ev["ts"] == 0.0


def test_span_many_skips_none_traces():
    tr = Tracer()
    a = tr.begin("aggregate", 3, t_submit=0.0)
    tr.span_many([a, None], "prep", 0.0, 1.0)
    assert a.span_names() == {"prep"}


# -- chrome export ----------------------------------------------------------


def _golden_tracer():
    """Deterministic tracer + registry content (fixed clock, fixed
    timestamps) — the input of the golden-file test."""
    t = {"now": 0.0}

    def clock():
        t["now"] += 0.001
        return t["now"]

    tr = Tracer(capacity=16, exemplar_capacity=4, clock=clock)  # _t0=0.001
    # the serve request carries a gossip ingress record (ISSUE 12): an
    # ingress span from its birth timestamp and flow id 7 — the Chrome
    # flow link the chain batch below terminates
    req = tr.begin("fast_aggregate", 2, t_submit=0.002, flow=7)
    tr.span(req, "ingress", 0.0015, 0.002)
    tr.span(req, "queue_wait", 0.002, 0.004)
    tr.span(req, "prep", 0.004, 0.005)
    tr.span(req, "combine", 0.006, 0.008)
    tr.span(req, "device", 0.005, 0.009)
    tr.span(req, "finalize", 0.009, 0.010)
    tr.finish(req, True, t_done=0.010)
    tr.note_execution(steps=256, regs=640, batch=(4,), sharded=False,
                      t0=0.005, seconds=0.003)
    # one chain-plane batch record (PR 5's validate/sig_wait/apply/sweep
    # stages — part of the golden schema since PR 7 so the trace-coverage
    # gate below can hold every registered stage to an export; the head
    # stage + absorbed flow ids are the ISSUE 12 gossip→head stitching)
    chain = tr.begin("chain_apply", 3, t_submit=0.011)
    tr.span(chain, "validate", 0.011, 0.012)
    tr.span(chain, "sig_wait", 0.012, 0.014)
    tr.span(chain, "apply", 0.014, 0.015)
    tr.span(chain, "sweep", 0.015, 0.016)
    tr.span(chain, "head", 0.016, 0.017)
    # flow 7 is the router-local serve request above; 8 and 9 were
    # forwarded over the worker protocol and STARTED on worker pids
    # (_golden_worker_sections) — the chain batch finishes all three
    # (ISSUE 19: flow ids survive the process boundary)
    chain.flows = (7, 8, 9)
    tr.finish(chain, True, t_done=0.017)
    obs_programs.note_assembly("hard_part[k=0,fold=32]", n_steps=4864,
                               n_regs=1024, seconds=1.5,
                               disk_cache_hit=False)
    obs_programs.note_assembly("miller_product[k=8,fold=8]", n_steps=2816,
                               n_regs=960, seconds=0.0123,
                               disk_cache_hit=True)
    return tr


def _golden_worker_sections():
    """Deterministic per-worker span sections (the shape
    ``FleetAggregator.worker_span_sections`` returns): two workers, one
    request each, every serve stage present, each carrying the flow id
    the router forwarded (8 and 9 — terminated by the chain batch in
    ``_golden_tracer``). w0's submit predates the router tracer's epoch,
    so the stitch's origin-rewind is part of the golden too."""

    def wire(rid, flow, t0):
        return {
            "rid": rid, "kind": "fast_aggregate", "n_keys": 2,
            "t_submit": t0, "ok": True, "pinned": False,
            "total_s": 0.0035, "flow": flow, "flows": [],
            "spans": [["queue_wait", t0, t0 + 0.001],
                      ["prep", t0 + 0.001, t0 + 0.0015],
                      ["combine", t0 + 0.002, t0 + 0.0025],
                      ["device", t0 + 0.0015, t0 + 0.003],
                      ["finalize", t0 + 0.003, t0 + 0.0035]],
        }

    return {"w0": {"pid": 4242, "traces": [wire(1, 8, 0.0005)]},
            "w1": {"pid": 4243, "traces": [wire(1, 9, 0.003)]}}


def _golden_stitched():
    return stitched_chrome(_golden_tracer(), _golden_worker_sections())


def test_chrome_export_schema():
    doc = _golden_tracer().to_chrome()
    assert set(doc) == {"traceEvents", "displayTimeUnit", "programRegistry",
                        "otherData"}
    names = set()
    flow_events = []
    for ev in doc["traceEvents"]:
        # "s"/"f" are Chrome FLOW events (the ISSUE 12 gossip→head links)
        assert ev["ph"] in ("X", "M", "s", "f")
        assert isinstance(ev["pid"], int)
        if ev["ph"] == "X":
            assert ev["ts"] >= 0 and ev["dur"] >= 0
            assert isinstance(ev["tid"], int)
            names.add(ev["name"])
        elif ev["ph"] in ("s", "f"):
            flow_events.append(ev)
    # all five pipeline stages + the ingress hop + the chain batch stages
    # + the VM execution row made it out
    assert set(STAGES) <= names
    assert set(CHAIN_STAGES) <= names
    assert "ingress" in names and "head" in names
    assert any(n.startswith("vm[steps=256") for n in names)
    # the flow arrows: ONE local start (the serve request's finalize) and
    # a finish per absorbed flow id on the chain batch's head stage —
    # ids 8/9 get their starts from worker pids in the STITCHED export
    starts = [e for e in flow_events if e["ph"] == "s"]
    finishes = [e for e in flow_events if e["ph"] == "f"]
    assert len(starts) == 1 and starts[0]["id"] == 7
    assert sorted(e["id"] for e in finishes) == [7, 8, 9]
    assert all(starts[0]["ts"] <= e["ts"] and e["bp"] == "e"
               for e in finishes)
    reg = doc["programRegistry"]
    assert reg["vm_cache"] == {"disk_hits": 1, "disk_misses": 1}
    assert reg["programs"]["hard_part[k=0,fold=32]"]["vm_cache"] == "miss"
    assert reg["programs"]["hard_part[k=0,fold=32]"]["assembly_s"] == 1.5


def test_every_registered_span_stage_is_exported():
    """The trace-coverage gate (ISSUE 7 satellite): every span stage any
    plane registers in ``obs/registry.SPAN_STAGES`` must appear in the
    golden tracer's Chrome export — a plane that registers stages but
    never exports them (or registers a stage the tracing plane dropped)
    fails HERE, so future planes cannot silently ship untraced."""
    doc = _golden_tracer().to_chrome()
    exported = {ev["name"] for ev in doc["traceEvents"] if ev["ph"] == "X"}
    for plane, stages in registry.SPAN_STAGES.items():
        missing = set(stages) - exported
        assert not missing, (
            f"plane {plane!r} registers span stages that no exported "
            f"trace carries: {sorted(missing)} — extend _golden_tracer() "
            "with the new plane's spans (and regen the golden) so the "
            "coverage gate holds it to an export"
        )
    # the re-exported tuples stay in lockstep with the registry
    assert STAGES == registry.SPAN_STAGES["serve"]
    assert CHAIN_STAGES == registry.SPAN_STAGES["chain"]


def test_stitched_chrome_joins_worker_pids_by_flow_id():
    """The ISSUE 19 stitching contract: worker spans render on their own
    pids (WORKER_PID_BASE + index in sorted-label order), every serve
    stage appears on EVERY worker pid, and each forwarded flow id's
    worker-side start has a router-side finish — the fleet trace reads
    as one pipeline across >= 2 processes."""
    doc = _golden_stitched()
    pids = doc["otherData"]["workerPids"]
    assert pids == {"w0": {"pid": WORKER_PID_BASE, "os_pid": 4242},
                    "w1": {"pid": WORKER_PID_BASE + 1, "os_pid": 4243}}
    by_pid = {}
    starts, finishes = {}, set()
    for ev in doc["traceEvents"]:
        if ev["ph"] == "X":
            by_pid.setdefault(ev["pid"], set()).add(ev["name"])
        elif ev["ph"] == "s":
            starts[ev["id"]] = ev
        elif ev["ph"] == "f":
            finishes.add(ev["id"])
    worker_pids = [p for p in by_pid if p >= WORKER_PID_BASE]
    assert len(worker_pids) >= 2
    for pid in worker_pids:
        assert set(STAGES) <= by_pid[pid], f"pid {pid} missing stages"
    # every worker-side flow start joins a router-side finish by id,
    # start before finish (Perfetto draws the cross-pid arrow)
    worker_starts = {fid: ev for fid, ev in starts.items()
                     if ev["pid"] >= WORKER_PID_BASE}
    assert sorted(worker_starts) == [8, 9]
    assert set(worker_starts) <= finishes
    finish_ts = {ev["id"]: ev["ts"] for ev in doc["traceEvents"]
                 if ev["ph"] == "f"}
    for fid, ev in worker_starts.items():
        assert ev["ts"] <= finish_ts[fid]
    # w0's submit (0.0005s) predates the tracer epoch (0.001s): the
    # rewind keeps every stitched timestamp non-negative
    assert all(ev["ts"] >= 0 for ev in doc["traceEvents"]
               if ev["ph"] in ("X", "s", "f"))


def test_trace_wire_roundtrip_and_rid_deltas():
    """`trace_to_wire` is JSON-safe and `wire_spans` ships rid DELTAS —
    the snapshot carrier contract the aggregator's watermarks rely on."""
    t = {"now": 0.0}

    def clock():
        t["now"] += 0.001
        return t["now"]

    tr = Tracer(capacity=8, clock=clock)
    for i in range(3):
        req = tr.begin("fast_aggregate", 1, t_submit=0.001 * i,
                       flow=20 + i)
        tr.span(req, "finalize", 0.001 * i, 0.001 * i + 0.0005)
        tr.finish(req, True, t_done=0.001 * i + 0.0005)
    wires = wire_spans(tr)
    assert [w["rid"] for w in wires] == [1, 2, 3]
    # JSON round trip preserves everything the stitch consumes
    back = json.loads(json.dumps(wires[0]))
    assert back == trace_to_wire(tr.completed()[0])
    assert back["flow"] == 20 and back["spans"][0][0] == "finalize"
    # the incremental form: only rids past the watermark ship
    assert [w["rid"] for w in wire_spans(tr, since_rid=2)] == [3]


def test_chrome_export_matches_golden(tmp_path):
    """The export schema is a public contract (Perfetto/chrome://tracing
    consume it): byte-identical JSON for a fixed synthetic input — the
    STITCHED document since ISSUE 19, so the golden pins worker pids and
    cross-process flow joins too. On intentional schema changes
    regenerate with `python tests/test_obs.py --regen-golden`."""
    path = str(tmp_path / "trace.json")
    with open(path, "w") as fh:
        fh.write(json.dumps(_golden_stitched(), indent=1, sort_keys=True))
    with open(path) as fh:
        got = json.load(fh)
    with open(GOLDEN) as fh:
        want = json.load(fh)
    assert got == want


# -- service integration ----------------------------------------------------


def test_service_traces_all_five_stages():
    be = RlcBackend()
    tracer = Tracer()
    with _svc(be, tracer=tracer, max_batch=4, max_wait_ms=10_000) as svc:
        futs = [
            svc.submit("fast_aggregate", [PK], b"m%d" % i, b"s%d-ok" % i)
            for i in range(4)
        ]
        assert all(f.result(timeout=10) is True for f in futs)
    assert be.rlc_calls >= 1
    done = tracer.completed()
    assert len(done) == 4
    for tr in done:
        assert set(STAGES) <= tr.span_names()
        assert tr.ok is True and tr.total_s > 0
        # spans nest sanely: queue_wait starts at submit, finalize ends last
        spans = {name: (a, b) for name, a, b in tr.spans}
        assert spans["queue_wait"][0] == tr.t_submit
        assert spans["finalize"][1] >= spans["device"][1]
    names = {e["name"] for e in tracer.to_chrome()["traceEvents"]
             if e["ph"] == "X"}
    assert set(STAGES) <= names


def test_service_without_tracer_is_zero_cost():
    # env off + no explicit tracer -> the service stores None and no
    # global tracer traffic happens
    with _svc(RlcBackend(), max_batch=1, max_wait_ms=0) as svc:
        assert svc._tracer is None
        assert svc.submit("fast_aggregate", [PK], b"m", b"s-ok").result(
            timeout=10) is True
    assert tracing.global_tracer().completed() == []


def test_service_picks_up_env_enabled_global_tracer(monkeypatch):
    monkeypatch.setenv("CONSENSUS_SPECS_TPU_TRACE", "1")
    tracing.reset_global()
    with _svc(RlcBackend(), max_batch=1, max_wait_ms=0) as svc:
        assert svc._tracer is tracing.global_tracer()
        assert svc.submit("fast_aggregate", [PK], b"m", b"s-ok").result(
            timeout=10) is True
    assert len(tracing.global_tracer().completed()) == 1


def test_oracle_fallback_requests_still_finish_traces():
    class Broken(RlcBackend):
        def batch_verify_rlc(self, items, mesh=None, rng=None):
            raise RuntimeError("combine exploded")

        def _go(self, signatures):
            raise RuntimeError("device exploded")

    tracer = Tracer()
    with _svc(Broken(), tracer=tracer, max_batch=2, max_wait_ms=10_000,
              backend_retries=0) as svc:
        f1 = svc.submit("fast_aggregate", [PK], b"m1", b"a-ok")
        f2 = svc.submit("fast_aggregate", [PK], b"m2", b"b-bad")
        assert f1.result(timeout=10) is True
        assert f2.result(timeout=10) is False
    done = tracer.completed()
    assert len(done) == 2  # every degraded request still finished a trace
    assert {tr.ok for tr in done} == {True, False}
    for tr in done:
        assert "finalize" in tr.span_names()


# -- exposition endpoint ----------------------------------------------------

_PROM_LINE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})? [-+]?[0-9.eE+-]+$"
)


def _get(url):
    with urllib.request.urlopen(url, timeout=30) as resp:
        return resp.status, resp.read().decode()


def test_exposition_scrapeable_under_load():
    """/metrics parses as Prometheus text WHILE submit threads hammer the
    service; /snapshot is the live ServeMetrics JSON; /healthz answers."""
    be = RlcBackend()
    svc = _svc(be, max_batch=8, max_wait_ms=1)
    server = start_exposition(metrics=svc.metrics, port=0)
    stop = threading.Event()
    errors = []

    def hammer(tid):
        i = 0
        try:
            while not stop.is_set():
                svc.submit("fast_aggregate", [PK], b"t%d-%d" % (tid, i),
                           b"s-ok")
                i += 1
        except Exception as e:  # pragma: no cover - surfaced by assert
            errors.append(e)

    threads = [threading.Thread(target=hammer, args=(t,)) for t in range(3)]
    for t in threads:
        t.start()
    try:
        deadline = time.time() + 5
        seen_queue_gauge = False
        for _ in range(3):
            status, body = _get(server.url("/metrics"))
            assert status == 200
            for line in body.splitlines():
                if not line or line.startswith("#"):
                    continue
                assert _PROM_LINE.match(line), f"unparseable: {line!r}"
            if "consensus_specs_tpu_serve_queue_depth" in body:
                seen_queue_gauge = True
            assert time.time() < deadline
        assert seen_queue_gauge
        status, body = _get(server.url("/snapshot"))
        snap = json.loads(body)
        assert status == 200 and snap["submits"] > 0
        status, body = _get(server.url("/healthz"))
        health = json.loads(body)
        # the PR 7 /healthz upgrade: liveness + SLO state in one body
        assert status == 200 and health["ok"] is True
        assert set(health["slo"]) == {"serve_p99", "chain_p99",
                                      "gossip_to_head_p99"}
        serve_slo = health["slo"]["serve_p99"]
        assert serve_slo["n"] > 0 and serve_slo["ok"] is True
        with pytest.raises(urllib.error.HTTPError):
            _get(server.url("/nope"))
    finally:
        stop.set()
        for t in threads:
            t.join(10)
        svc.close(timeout=30)
        server.close()
    assert errors == []


def test_exposition_default_snapshot_is_profiling_summary():
    profiling.set_gauge("serve.queue_depth", 7)
    with start_exposition(port=0) as server:
        _, body = _get(server.url("/snapshot"))
        snap = json.loads(body)
    assert snap["profile"]["serve.queue_depth"] == {"gauge": 7.0}


# -- concurrency hammer -----------------------------------------------------


def test_concurrent_writers_vs_snapshot_and_trace_readers():
    """Threaded hammer: ServeMetrics note_* + tracer begin/span/finish
    racing snapshot()/completed()/render_prometheus() readers. The
    assertion is consistency at the end and no exceptions in flight."""
    m = ServeMetrics()
    tracer = Tracer(capacity=128)
    n_threads, iters = 4, 400
    errors = []
    done = threading.Event()

    def writer(tid):
        try:
            for i in range(iters):
                m.note_submit()
                m.note_enqueued(i % 7)
                m.note_batch(2, 4, 8, 0.0001)
                m.note_result(0.0001 * (i % 5 + 1))
                tr = tracer.begin("fast_aggregate", 2, t_submit=0.0)
                tracer.span(tr, "queue_wait", 0.0, 0.0001)
                tracer.finish(tr, True, t_done=0.001)
        except Exception as e:  # pragma: no cover
            errors.append(e)

    def reader():
        try:
            while not done.is_set():
                m.snapshot()
                tracer.completed()
                tracer.to_chrome()
                registry.render_prometheus()
        except Exception as e:  # pragma: no cover
            errors.append(e)

    threads = [threading.Thread(target=writer, args=(t,))
               for t in range(n_threads)]
    r = threading.Thread(target=reader)
    r.start()
    for t in threads:
        t.start()
    for t in threads:
        t.join(30)
    done.set()
    r.join(30)
    assert errors == []
    assert m.submits == n_threads * iters
    assert m.batches == n_threads * iters
    assert len(tracer.completed()) == 128  # ring stayed bounded
    snap = m.snapshot()
    assert snap["latency"]["count"] == n_threads * iters


# -- program registry -------------------------------------------------------


def test_program_registry_and_vm_cache_gauges():
    obs_programs.note_assembly("g2_subgroup[k=0,fold=8]", n_steps=512,
                               n_regs=256, seconds=2.5, disk_cache_hit=False)
    obs_programs.note_assembly("g2_subgroup[k=0,fold=8]", n_steps=512,
                               n_regs=256, seconds=0.01, disk_cache_hit=True)
    snap = obs_programs.registry_snapshot()
    assert snap["vm_cache"] == {"disk_hits": 1, "disk_misses": 1}
    assert snap["programs"]["g2_subgroup[k=0,fold=8]"]["vm_cache"] == "hit"
    summ = profiling.summary()
    assert summ["bls.vm_cache_hits"] == {"gauge": 1.0}
    assert summ["bls.vm_cache_misses"] == {"gauge": 1.0}
    # profiling.reset() wipes gauges, and note_assembly fires only once
    # per program per process (lru_cache) — export_gauges() re-publishes
    # so a multi-mode bench's later stages still carry the counters
    profiling.reset()
    assert "bls.vm_cache_hits" not in profiling.summary()
    obs_programs.export_gauges()
    assert profiling.summary()["bls.vm_cache_hits"] == {"gauge": 1.0}


def test_backend_program_resolution_feeds_registry(monkeypatch, tmp_path):
    """ops/bls_backend._program notes (steps, regs, assembly time, disk
    hit/miss) for every program it resolves — checked against a tiny
    synthetic program in an isolated cache dir so no real assembly (or
    repo-level cache state) is involved."""
    from consensus_specs_tpu.ops import bls_backend, vm, vmlib

    calls = {}

    def fake_build(k, fold):
        prog = vm.Prog()
        a = prog.inp("a")
        prog.out(a * a, "out")
        calls["built"] = (k, fold)
        return prog

    monkeypatch.setattr(vmlib, "build_miller_product", fake_build)
    monkeypatch.setattr(bls_backend, "_vm_cache_dir", lambda: str(tmp_path))
    bls_backend._program.cache_clear()
    try:
        assembled, _fold = bls_backend._program("miller_product", 1, 1)
        # second resolution from a cleared lru_cache: the pickle written
        # above answers -> disk HIT recorded
        bls_backend._program.cache_clear()
        bls_backend._program("miller_product", 1, 1)
    finally:
        bls_backend._program.cache_clear()
    assert calls["built"] == (1, 1)
    snap = obs_programs.registry_snapshot()
    entry = snap["programs"].get("miller_product[k=1,fold=1]")
    assert entry is not None
    assert entry["steps"] == assembled.n_steps
    assert entry["regs"] == assembled.n_regs
    assert entry["assembly_s"] >= 0
    assert snap["vm_cache"] == {"disk_hits": 1, "disk_misses": 1}
    assert entry["vm_cache"] == "hit"  # the latest resolution wins the entry


# -- profiling satellites ---------------------------------------------------


def test_profiling_enabled_is_dynamic(monkeypatch):
    monkeypatch.delenv("CONSENSUS_SPECS_TPU_PROFILE", raising=False)
    assert profiling.enabled() is False
    assert profiling.ENABLED is False  # the legacy alias reads live too
    monkeypatch.setenv("CONSENSUS_SPECS_TPU_PROFILE", "1")
    assert profiling.enabled() is True
    assert profiling.ENABLED is True


def test_profiling_reset_clears_all_three_families():
    profiling.record("x.stat", 1.0)
    profiling.record_latency("x.lat", 0.5)
    profiling.set_gauge("x.gauge", 2.0)
    summ = profiling.summary()
    # the three recorded families + the hist.families tracking gauge
    assert {"x.stat", "x.lat", "x.gauge"} <= set(summ)
    assert summ["hist.families"] == {"gauge": 1.0}
    profiling.reset()
    assert profiling.summary() == {}
    assert profiling.latency_summary() == {}


def test_profiling_post_reset_runs_match_fresh_process():
    """Post-reset latency accounting must be identical to a fresh
    process: replay the same stream twice across a reset and require the
    exact same summary (the reruns-are-comparable contract — trivially
    deterministic now that fixed-bucket histograms replaced the sampled
    reservoir, and pinned here so a future implementation keeps it)."""

    def fill():
        profiling.reset()
        rng = random.Random(1)
        for _ in range(4096 + 512):
            profiling.record_latency("l", rng.random())
        return profiling.latency_summary()["l"]

    assert fill() == fill()


def test_profiling_snapshot_carries_observation_counts():
    """Every percentile family exposes ``n`` next to the p50/p95/p99
    points (ISSUE 7 satellite: consumers judge statistical weight)."""
    for _ in range(37):
        profiling.record_latency("serve.submit_to_result", 0.01)
    fam = profiling.snapshot()["serve.submit_to_result"]
    assert fam["n"] == 37 and fam["count"] == 37
    assert {"p50_ms", "p95_ms", "p99_ms"} <= set(fam)


# -- bench --trace glue -----------------------------------------------------


def test_bench_serve_trace_flag_writes_chrome_json(tmp_path, monkeypatch,
                                                   capsys):
    """`bench.py --mode serve --trace out.json` enables tracing before the
    load runs, dumps the global tracer, and attaches the path to the JSON
    line — glued here with a stub load so no crypto/compiles are paid."""
    import importlib.util

    bench_path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "bench.py")
    spec = importlib.util.spec_from_file_location("bench_trace_glue",
                                                  bench_path)
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)

    import consensus_specs_tpu.serve.load as load_mod
    import consensus_specs_tpu.utils.jax_env as jax_env

    def fake_serve_bench():
        # the real run constructs the service AFTER main() set the env;
        # mirror that and push one batch through the traced pipeline
        tracing.reset_global()
        with _svc(RlcBackend(), max_batch=2, max_wait_ms=10_000) as svc:
            a = svc.submit("fast_aggregate", [PK], b"m1", b"s-ok")
            b = svc.submit("fast_aggregate", [PK], b"m2", b"s-ok")
            assert a.result(timeout=10) and b.result(timeout=10)
        return {"value": 1.0, "vs_baseline": 0.0, "mode": "serve"}

    monkeypatch.setattr(load_mod, "run_serve_bench", fake_serve_bench)
    monkeypatch.setattr(jax_env, "force_cpu", lambda *a, **k: None)
    out = tmp_path / "trace.json"
    monkeypatch.setattr(sys, "argv",
                        ["bench.py", "--mode", "serve", "--trace", str(out)])
    bench.main()
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["trace"] == str(out)
    assert line["trace_requests"] == 2
    with open(out) as fh:
        doc = json.load(fh)
    names = {e["name"] for e in doc["traceEvents"] if e.get("ph") == "X"}
    assert set(STAGES) <= names  # all five stages for >= 1 request
    assert "programRegistry" in doc


if __name__ == "__main__" and "--regen-golden" in sys.argv:
    os.environ["CONSENSUS_SPECS_TPU_TRACE"] = "0"
    obs_programs.reset()
    with open(GOLDEN, "w") as fh:
        fh.write(json.dumps(_golden_stitched(), indent=1, sort_keys=True))
    print(f"regenerated {GOLDEN}")
