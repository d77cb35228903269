"""End-to-end gossip→head latency plane (ISSUE 12): the SlotClock math,
deadline-aware flush scheduling in the serve plane, the per-stage +
end-to-end histogram families, Chrome flow links, the adversarial simnet
run with speculation/rollback through the strict convergence gate, and
the fleet's merged scrape carrying the end-to-end histogram.

Everything here runs crypto-free (verdict-style backends, simnet's
VerdictBackend, verdict-mode fleet workers) so tier-1 stays fast; the
real-crypto serve path is covered by tests/test_serve.py and the full
matrix by `make latency-bench`.
"""
import time

import pytest

from consensus_specs_tpu.obs import flight, latency, slo, tracing
from consensus_specs_tpu.obs.tracing import Tracer
from consensus_specs_tpu.ops import profiling
from consensus_specs_tpu.serve.service import SlotClock, VerificationService
from consensus_specs_tpu.utils import bls

PK = b"\x02" * 48


@pytest.fixture(autouse=True)
def _clean_slate(monkeypatch):
    monkeypatch.setenv("CONSENSUS_SPECS_TPU_TRACE", "0")
    monkeypatch.setenv("CONSENSUS_SPECS_TPU_FLIGHT", "0")
    monkeypatch.delenv("CONSENSUS_SPECS_TPU_SLOT_MS", raising=False)
    monkeypatch.delenv("CONSENSUS_SPECS_TPU_SPECULATE", raising=False)
    profiling.reset()
    latency.reset()
    tracing.reset_global()
    flight.reset_global()
    slo.reset_global()
    was = bls.bls_active
    bls.bls_active = True
    yield
    bls.bls_active = was
    profiling.reset()
    latency.reset()
    tracing.reset_global()
    flight.reset_global()
    slo.reset_global()


class OkBackend:
    """Crypto-free backend: verdict rides in the signature (endswith
    b"ok"), same contract the obs tests use."""

    def __init__(self):
        self.calls = 0

    def _go(self, signatures):
        self.calls += 1
        return [bytes(s).endswith(b"ok") for s in signatures]

    def batch_fast_aggregate_verify(self, pubkey_sets, messages, signatures,
                                    mesh=None):
        return self._go(signatures)

    def batch_aggregate_verify(self, pubkey_lists, message_lists, signatures,
                               mesh=None):
        return self._go(signatures)


class _Oracle:
    def verify_one(self, pending):
        return bytes(pending.signature).endswith(b"ok")


def _svc(**kw):
    kw.setdefault("backend", OkBackend())
    kw.setdefault("oracle", _Oracle())
    kw.setdefault("bucket_fn", lambda k: 8)
    return VerificationService(**kw)


# -- SlotClock ----------------------------------------------------------------


def test_slot_clock_math():
    t = {"now": 0.0}
    clk = SlotClock(0.1, clock=lambda: t["now"], origin=0.0)
    assert clk.slot_index(0.25) == 2
    assert clk.slot_end(0.25) == pytest.approx(0.3)
    assert clk.remaining(0.25) == pytest.approx(0.05)
    # exactly on a boundary: the NEXT slot's end
    assert clk.slot_end(0.2) == pytest.approx(0.3)
    t["now"] = 0.41
    assert clk.slot_index() == 4
    assert clk.remaining() == pytest.approx(0.09)


def test_slot_clock_from_env(monkeypatch):
    monkeypatch.delenv("CONSENSUS_SPECS_TPU_SLOT_MS", raising=False)
    assert SlotClock.from_env() is None
    monkeypatch.setenv("CONSENSUS_SPECS_TPU_SLOT_MS", "0")
    assert SlotClock.from_env() is None
    monkeypatch.setenv("CONSENSUS_SPECS_TPU_SLOT_MS", "not-a-number")
    assert SlotClock.from_env() is None  # malformed degrades, never raises
    monkeypatch.setenv("CONSENSUS_SPECS_TPU_SLOT_MS", "250")
    clk = SlotClock.from_env()
    assert clk is not None and clk.slot_s == pytest.approx(0.25)


def test_service_arms_slot_clock_from_env(monkeypatch):
    monkeypatch.setenv("CONSENSUS_SPECS_TPU_SLOT_MS", "125")
    with _svc(max_batch=1, max_wait_ms=0) as svc:
        assert svc.slot_clock is not None
        assert svc.slot_clock.slot_s == pytest.approx(0.125)
    with _svc(max_batch=1, max_wait_ms=0,
              slot_clock=SlotClock(0.5)) as svc:
        assert svc.slot_clock.slot_s == 0.5  # explicit wins over env


# -- deadline-aware flushing --------------------------------------------------


def test_deadline_flush_fires_before_max_wait(monkeypatch):
    """With a 50 ms slot clock and a 10 s max_wait, the slot-budget rule
    — not size, not max_wait — must fire the flush: the submit resolves
    within the slot, the deadline counters tick, and the flight journal
    carries the deadline_flush event."""
    monkeypatch.setenv("CONSENSUS_SPECS_TPU_FLIGHT", "1")
    flight.reset_global()
    t0 = time.perf_counter()
    with _svc(max_batch=64, max_wait_ms=10_000,
              slot_clock=SlotClock(0.05)) as svc:
        futs = [svc.submit("fast_aggregate", [PK], b"m%d" % i, b"s%d-ok" % i)
                for i in range(3)]
        assert all(f.result(timeout=5) is True for f in futs)
        waited = time.perf_counter() - t0
        assert waited < 5.0  # a 10 s max_wait flush would still be parked
        assert svc.metrics.deadline_flushes >= 1
        assert svc.metrics.last_deadline_budget_ms <= 50.0
    _, gauges = profiling.stats_and_gauges()
    assert gauges.get("serve.deadline_flushes", 0) >= 1
    events = flight.global_recorder().events()
    dl = [e for e in events if e["kind"] == "deadline_flush"]
    assert dl and dl[0]["plane"] == "serve"
    assert dl[0]["data"]["items"] >= 1


def test_classic_flush_untouched_without_slot_clock():
    """No slot clock (env unset, no param): the flush rule is exactly
    size-OR-deadline and the deadline counters never move."""
    with _svc(max_batch=4, max_wait_ms=20) as svc:
        assert svc.slot_clock is None
        fut = svc.submit("fast_aggregate", [PK], b"m", b"s-ok")
        assert fut.result(timeout=10) is True
        assert svc.metrics.deadline_flushes == 0


def test_explicit_deadline_wins_over_slot_grid():
    """A caller-supplied deadline_s takes precedence: an already-blown
    deadline flushes immediately even mid-slot."""
    with _svc(max_batch=64, max_wait_ms=10_000,
              slot_clock=SlotClock(3600.0)) as svc:  # huge slot
        fut = svc.submit("fast_aggregate", [PK], b"m", b"s-ok",
                         deadline_s=time.perf_counter() - 1.0)
        assert fut.result(timeout=5) is True
        assert svc.metrics.deadline_flushes >= 1


def test_downstream_p99_shrinks_the_budget():
    """The budget deadline subtracts the live downstream p99: feed a fat
    device-stage distribution and the read must reflect it (the number
    the scheduler subtracts)."""
    for _ in range(64):
        latency.note_stage("device", 0.040)
        latency.note_stage("prep", 0.010)
        latency.note_stage("finalize", 0.001)
    latency.reset()  # cold cache, histograms stay (they live in profiling)
    total = latency.downstream_p99_s()
    assert total >= 0.045  # prep + device + finalize p99s sum
    # the cache answers repeat reads without a fresh histogram walk
    assert latency.downstream_p99_s() == total


# -- per-stage + end-to-end recording -----------------------------------------


def test_stage_histograms_fill_on_a_flush():
    with _svc(max_batch=4, max_wait_ms=5) as svc:
        futs = [svc.submit("fast_aggregate", [PK], b"m%d" % i, b"s-ok")
                for i in range(4)]
        assert all(f.result(timeout=10) for f in futs)
    hists = profiling.latency_histograms()
    for stage in ("queue_wait", "prep", "device", "finalize"):
        h = hists.get(latency.stage_label(stage))
        assert h is not None and h.count >= 1, stage
    assert hists[latency.stage_label("queue_wait")].count >= 4


def test_ingress_span_and_flow_ride_the_request_trace():
    tracer = Tracer()
    b = latency.birth()
    with _svc(max_batch=1, max_wait_ms=0, tracer=tracer) as svc:
        fut = svc.submit("fast_aggregate", [PK], b"m", b"s-ok",
                         birth_s=b.t, flow_id=b.trace_id)
        assert fut.result(timeout=10) is True
    [done] = tracer.completed()
    assert "ingress" in done.span_names()
    assert done.flow == b.trace_id
    # the ingress hop landed in the stage histogram too
    h = profiling.latency_histograms().get(latency.stage_label("ingress"))
    assert h is not None and h.count == 1


def test_birth_ids_are_unique_and_monotone():
    latency.reset()
    ids = [latency.birth().trace_id for _ in range(5)]
    assert ids == sorted(set(ids))


def test_latency_snapshot_selects_the_plane_families():
    latency.note_stage("device", 0.01)
    latency.note_gossip_to_head(0.05)
    profiling.record_latency("serve.submit_to_result", 0.02)  # excluded
    snap = latency.snapshot()
    assert set(snap) == {latency.stage_label("device"),
                         latency.GOSSIP_TO_HEAD_LABEL}
    assert snap[latency.GOSSIP_TO_HEAD_LABEL]["n"] == 1


# -- the adversarial end-to-end run (simnet, crypto-free) ---------------------


def test_sim_latency_plane_end_to_end_with_speculation():
    """One latency_skew scenario (laggard node, deferral churn, invalid
    signatures) with deadline flushing AND speculative head application,
    through the STRICT differential convergence gate — speculation with
    rollback must be invisible to consensus, and the latency plane must
    have filled: gossip_to_head observations, ingress stage mass, the
    declared objective met, deadline flushes and rollbacks exercised."""
    from consensus_specs_tpu.sim.runner import build_world, run_scenario
    from consensus_specs_tpu.sim.scenarios import get_scenario

    spec, anchor_state, anchor_block = build_world()
    report = run_scenario(
        get_scenario("latency_skew"), spec=spec, anchor_state=anchor_state,
        anchor_block=anchor_block, seed=7, strict=True, query_rounds=16,
        service_kwargs={"max_wait_ms": 25.0, "max_batch": 8,
                        "slot_clock": SlotClock(0.010)},
        head_kwargs={"speculative": True})
    assert report.converged
    assert report.events.get("invalid_sig", 0) >= 1  # liars were present

    hists = profiling.latency_histograms()
    g2h = hists.get(latency.GOSSIP_TO_HEAD_LABEL)
    assert g2h is not None and g2h.count > 0
    assert hists[latency.stage_label("ingress")].count > 0
    assert hists[latency.stage_label("head")].count > 0

    evaluated = slo.global_tracker().evaluate(export=False)
    obj = evaluated["gossip_to_head_p99"]
    assert obj["n"] == g2h.count and obj["ok"] is True

    per_node = report.per_node
    assert sum(v["deadline_flushes"] for v in per_node.values()) > 0
    assert sum(v["speculative_applied"] for v in per_node.values()) > 0
    # the invalid-signature traffic forced real rollbacks — and the
    # strict gate above already proved they were exact
    assert sum(v["rollbacks"] for v in per_node.values()) > 0


def test_sim_speculative_and_plain_runs_agree():
    """Same scenario, same seed, with and without speculation: identical
    agreed head and identical per-node applied counts — speculation is
    pure latency, never state."""
    from consensus_specs_tpu.sim.runner import build_world, run_scenario
    from consensus_specs_tpu.sim.scenarios import get_scenario

    spec, anchor_state, anchor_block = build_world()

    def run(speculative):
        profiling.reset()
        latency.reset()
        return run_scenario(
            get_scenario("withheld_orphans"), spec=spec,
            anchor_state=anchor_state, anchor_block=anchor_block, seed=11,
            strict=True, query_rounds=16,
            head_kwargs={"speculative": speculative})

    plain = run(False)
    spec_run = run(True)
    assert plain.head == spec_run.head
    assert plain.head_slot == spec_run.head_slot
    for name in plain.per_node:
        assert (plain.per_node[name]["applied"]
                == spec_run.per_node[name]["applied"])
        assert plain.per_node[name]["dropped"] \
            == spec_run.per_node[name]["dropped"]


# -- fleet: the merged scrape carries the end-to-end histogram ----------------


def test_fleet_merged_scrape_carries_gossip_to_head():
    """Router-side HeadServices consume fleet-routed verdicts while the
    end-to-end histogram accumulates in the ROUTER process — the merged
    fleet /metrics must carry it (n > 0) alongside the worker families
    (the ISSUE 12 acceptance surface)."""
    from consensus_specs_tpu.serve.fleet import FleetRouter
    from consensus_specs_tpu.sim.fleet_replay import run_fleet_replay

    router = FleetRouter(workers=2, backend="verdict",
                         env={"SERVE_MAX_WAIT_MS": "2"})
    try:
        out = run_fleet_replay("partition_heal", router=router, seed=7,
                               strict=True)
        assert out["report"].converged
        text = router.scrape_text()
        fam = ("consensus_specs_tpu_latency_gossip_to_head_latency_hist_"
               "seconds_count")
        [line] = [l for l in text.splitlines() if l.startswith(fam + " ")]
        assert int(line.rsplit(" ", 1)[1]) > 0
        # worker-side serve families still ride the same scrape (the
        # merge stayed a merge, the local overlay did not clobber it)
        assert "consensus_specs_tpu_serve_node" in text
        # the SLO surface must see the router-local end-to-end histogram
        # too — /healthz (and the control loop's burn rates) evaluate the
        # same overlay, not just the worker snapshots, or the declared
        # gossip_to_head_p99 objective could never fire at the fleet level
        health = router.healthz()
        obj = health["slo"]["gossip_to_head_p99"]
        assert obj["n"] > 0
    finally:
        router.close()
