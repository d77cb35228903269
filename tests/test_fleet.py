"""Serve fleet (ISSUE 11): consistent-hash routing, the snapshot merge
algebra on the aggregator, the shed policy, and a REAL 2-worker fleet of
`serve/worker.py` processes (verdict backend — no crypto or compiles,
spawned once per module) driven through verdict identity, cache affinity,
exactness, a forced fault -> SLO-burn -> shed/drain escalation, and the
simnet partition_heal scenario replayed against the live fleet.
"""
import json
import time

import pytest

from consensus_specs_tpu.obs import flight, registry
from consensus_specs_tpu.obs import snapshot as osnap
from consensus_specs_tpu.obs.fleet import FleetAggregator
from consensus_specs_tpu.obs.slo import ShedPolicy, SloTracker, worst_burn
from consensus_specs_tpu.serve.cache import check_key
from consensus_specs_tpu.serve import fleet as fleet_mod
from consensus_specs_tpu.serve.fleet import FleetRouter, HashRing, chip_envs
from consensus_specs_tpu.serve.load import BAD_SIGNATURE
from consensus_specs_tpu.ops import profiling


@pytest.fixture(autouse=True)
def _clean_profiling():
    profiling.reset()
    yield
    profiling.reset()


def _pk(i):
    return bytes([i]) * 48


def _snap(worker, hists=None, gauges=None, stats=None, events=None,
          pid=1, spans=None):
    snap = {"v": osnap.WIRE_VERSION, "worker": worker, "pid": pid,
            "hists": hists or {}, "gauges": gauges or {},
            "stats": stats or {}}
    if events is not None:
        snap["flight"] = {"counters": {"events": len(events)},
                          "events": events}
    if spans is not None:
        snap["spans"] = {"traces": spans}
    return snap


def _wire(values):
    from consensus_specs_tpu.obs.hist import Histogram

    h = Histogram()
    for v in values:
        h.observe(v)
    return osnap.hist_to_wire(h)


# -- consistent-hash ring -----------------------------------------------------


def test_ring_routes_deterministically_and_affinely():
    ring = HashRing()
    for label in ("w0", "w1", "w2"):
        ring.add(label)
    keys = [check_key("fast_aggregate", [_pk(i)], bytes([i]) * 32,
                      bytes([i]) * 96) for i in range(64)]
    first = [ring.route(k) for k in keys]
    assert [ring.route(k) for k in keys] == first  # same key, same worker
    assert len(set(first)) == 3  # all workers own some arc


def test_ring_removal_only_remaps_the_drained_workers_keys():
    ring = HashRing()
    for label in ("w0", "w1", "w2"):
        ring.add(label)
    keys = [check_key("fast_aggregate", [_pk(i)], bytes([i]) * 32,
                      bytes([i]) * 96) for i in range(128)]
    before = {k: ring.route(k) for k in keys}
    ring.remove("w1")
    for k, owner in before.items():
        if owner != "w1":
            # the consistent-hashing property: surviving workers keep
            # every key they had (their result caches stay warm)
            assert ring.route(k) == owner
        else:
            assert ring.route(k) in ("w0", "w2")


# -- aggregator merge algebra -------------------------------------------------


def test_workers_stay_on_cpu_without_a_tpu_or_when_told(monkeypatch):
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    assert chip_envs(3, chips=0) == [{}] * 3
    assert chip_envs(2, {"JAX_PLATFORMS": "cpu"}, chips=4) == [{}] * 2


def test_each_worker_gets_a_chip_of_its_own(monkeypatch):
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setattr(fleet_mod, "_router_holds_tpu", lambda: False)
    envs = chip_envs(4, chips=4)
    assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1", "2", "3"]
    ports = [e[k] for e in envs
             for k in ("TPU_PROCESS_PORT", "TPU_RUNTIME_METRICS_PORTS")]
    assert all(p.isdigit() and int(p) > 0 for p in ports)
    assert all(e["TPU_PROCESS_ADDRESSES"]
               == "localhost:" + e["TPU_PROCESS_PORT"] for e in envs)
    assert all("JAX_PLATFORMS" not in e for e in envs)


@pytest.mark.parametrize("why,chips,router_holds,match", [
    ("more workers than chips", 1, False, "chip of its own"),
    ("router holds the chips", 4, True, "holds every chip")])
def test_chip_assignment_refusals(monkeypatch, why, chips, router_holds,
                                  match):
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setattr(fleet_mod, "_router_holds_tpu",
                        lambda: router_holds)
    with pytest.raises(ValueError, match=match):
        chip_envs(2, chips=chips)


def test_host_chip_count_reads_the_pci_bus(monkeypatch, tmp_path):
    """Chips are TPU devices on the PCI bus (Google vendor id, a TPU
    device id), not any device file: two v5e chips beside a Google NIC
    and a foreign device count as 2."""
    from jax._src import hardware_utils

    for name, vendor, device in [("a", "0x1ae0", "0x0063"),
                                 ("b", "0x1ae0", "0x0063"),
                                 ("c", "0x1ae0", "0x0042"),
                                 ("d", "0x8086", "0x0063")]:
        (tmp_path / name).mkdir()
        (tmp_path / name / "vendor").write_text(vendor + "\n")
        (tmp_path / name / "device").write_text(device + "\n")
    monkeypatch.setattr(hardware_utils.glob, "glob",
                        lambda pat: sorted(str(p) for p in
                                           tmp_path.glob("*/vendor")))
    assert fleet_mod.host_tpu_chips() == 2


def test_fleet_chip_smoke_refuses_a_host_without_chips(capsys):
    from consensus_specs_tpu.serve import fleet_smoke

    assert fleet_smoke.chip_main(2) == 1
    assert "need 2 TPU chips, the host has 0" in capsys.readouterr().out


def test_aggregator_merges_hists_exactly_and_namespaces_gauges():
    aggr = FleetAggregator()
    a, b = [0.01, 0.02, 0.5], [0.015, 4.0]
    aggr.ingest("w0", _snap(
        "w0", hists={"serve.submit_to_result": _wire(a)},
        gauges={"serve.queue_depth": 2.0, "bls.rlc_combines": 3.0,
                "slo.ok": 1.0},
        stats={"serve.batch_flush": {"calls": 2, "total_s": 1.0,
                                     "max_s": 0.7}}))
    aggr.ingest("w1", _snap(
        "w1", hists={"serve.submit_to_result": _wire(b)},
        gauges={"serve.queue_depth": 5.0, "bls.rlc_combines": 4.0},
        stats={"serve.batch_flush": {"calls": 1, "total_s": 0.2,
                                     "max_s": 0.2}}))
    merged = aggr.merged_hists()["serve.submit_to_result"]
    from consensus_specs_tpu.obs.hist import Histogram

    whole = Histogram()
    for v in a + b:
        whole.observe(v)
    assert merged.state()["counts"] == whole.state()["counts"]
    assert merged.count == 5
    gauges = aggr.merged_gauges()
    # instance gauges re-scope per worker; counters sum; slo.* drops
    assert gauges["serve[w0].queue_depth"] == 2.0
    assert gauges["serve[w1].queue_depth"] == 5.0
    assert gauges["bls.rlc_combines"] == 7.0
    assert not any(g.startswith("slo.") for g in gauges)
    stats = aggr.merged_stats()["serve.batch_flush"]
    assert stats == {"calls": 3, "total_s": 1.2, "max_s": 0.7}
    # the merged view renders through the standard Prometheus renderer
    text = aggr.render_metrics(local_gauges={"fleet.workers": 2.0})
    assert ("consensus_specs_tpu_serve_submit_to_result_latency_hist_"
            "seconds_count 5") in text
    assert "consensus_specs_tpu_fleet_workers 2.0" in text
    assert 'serve_node{label="serve[w0].queue_depth"} 2.0' in text


def test_merged_view_local_gauges_never_clobber_worker_counters():
    """The overlay rule: router-authoritative planes (fleet.*, slo.*)
    replace, unknown keys add, but a local counter colliding with the
    worker merge keeps the WORKER sum — e.g. the router dumping its own
    journal sets a local flight.events that must not shadow the fleet's."""
    aggr = FleetAggregator()
    aggr.ingest("w0", _snap("w0", gauges={"flight.events": 5.0}))
    aggr.ingest("w1", _snap("w1", gauges={"flight.events": 7.0}))
    _, gauges, _ = aggr.merged_view(local_gauges={
        "flight.events": 1.0, "fleet.workers": 2.0, "slo.ok": 1.0})
    assert gauges["flight.events"] == 12.0  # worker sum, not the local 1.0
    assert gauges["fleet.workers"] == 2.0
    assert gauges["slo.ok"] == 1.0


def test_snapshot_flight_since_ships_only_new_events(monkeypatch):
    """The control tick's delta protocol: flight_since filters the ring
    worker-side, and the aggregator's last_seq is what the router feeds
    back — re-ingesting a delta continues the journal without gaps."""
    monkeypatch.setenv(flight.FLIGHT_ENV, "1")
    flight.reset_global()
    try:
        rec = flight.global_recorder()
        for i in range(3):
            rec.note("serve", "flush", items=i)
        full = osnap.take_process_snapshot(worker="w0")
        assert [e["seq"] for e in full["flight"]["events"]] == [1, 2, 3]
        delta = osnap.take_process_snapshot(worker="w0", flight_since=2)
        assert [e["seq"] for e in delta["flight"]["events"]] == [3]
        # counters stay cumulative on the delta snapshot
        assert delta["flight"]["counters"]["events"] == 3
        aggr = FleetAggregator()
        aggr.ingest("w0", full)
        assert aggr.last_seq("w0") == 3
        rec.note("serve", "flush", items=3)
        aggr.ingest("w0", osnap.take_process_snapshot(
            worker="w0", flight_since=aggr.last_seq("w0")))
        assert [e["seq"] for e in aggr.journal_events()] == [1, 2, 3, 4]
    finally:
        flight.reset_global()


def test_aggregator_journal_is_incremental_and_worker_stamped():
    aggr = FleetAggregator()
    ev = [{"seq": 1, "t": 0.1, "plane": "serve", "kind": "flush",
           "data": {}},
          {"seq": 2, "t": 0.2, "plane": "serve", "kind": "cache_hit",
           "data": {}}]
    aggr.ingest("w0", _snap("w0", events=ev))
    # re-ingesting the same ring must not duplicate events
    aggr.ingest("w0", _snap("w0", events=ev + [
        {"seq": 3, "t": 0.3, "plane": "serve", "kind": "flush",
         "data": {}}]))
    events = aggr.journal_events()
    assert [e["seq"] for e in events] == [1, 2, 3]
    assert all(e["worker"] == "w0" for e in events)
    jsonl = aggr.journal_jsonl(reason="test")
    header = json.loads(jsonl.splitlines()[0])
    assert header["events"] == 3 and header["workers"] == ["w0"]


def _ev(seq, t=None):
    return {"seq": seq, "t": t if t is not None else seq / 10.0,
            "plane": "serve", "kind": "flush", "data": {}}


def test_aggregator_restart_resets_watermarks_and_keeps_both_journals():
    """The ISSUE 19 restart regression: a respawned worker restarts its
    flight seq / trace rid counters from 1. Watermarks keyed by label
    alone would hide the fresh incarnation's entire journal and span
    stream below the dead process's high water; pid-keyed watermarks
    reset, and the merged journal keeps BOTH incarnations' events."""
    aggr = FleetAggregator()
    aggr.ingest("w0", _snap("w0", pid=100, events=[_ev(1), _ev(2), _ev(3)],
                            spans=[{"rid": 1, "spans": []},
                                   {"rid": 2, "spans": []}]))
    assert aggr.last_seq("w0", pid=100) == 3
    assert aggr.last_rid("w0", pid=100) == 2
    # the router asks on behalf of a pid the aggregator has never seen
    # (the respawn just happened): the delta cursors MUST answer 0 —
    # answering 3 would make the new worker ship nothing, forever
    assert aggr.last_seq("w0", pid=200) == 0
    assert aggr.last_rid("w0", pid=200) == 0
    # the new incarnation's restarted sequence numbers merge from the top
    aggr.ingest("w0", _snap("w0", pid=200, events=[_ev(1, t=9.1),
                                                   _ev(2, t=9.2)],
                            spans=[{"rid": 1, "spans": []}]))
    events = aggr.journal_events()
    assert [e["seq"] for e in events] == [1, 2, 3, 1, 2]
    assert [e["pid"] for e in events] == [100, 100, 100, 200, 200]
    assert aggr.last_seq("w0", pid=200) == 2
    assert aggr.last_rid("w0", pid=200) == 1
    # span sections carry the LIVE incarnation's pid
    assert aggr.worker_span_sections()["w0"]["pid"] == 200


def test_aggregator_same_pid_reingest_still_dedupes():
    # the restart reset must not break the normal incremental contract:
    # the same incarnation re-shipping its ring dedupes by seq
    aggr = FleetAggregator()
    aggr.ingest("w0", _snap("w0", pid=100, events=[_ev(1), _ev(2)]))
    aggr.ingest("w0", _snap("w0", pid=100, events=[_ev(1), _ev(2),
                                                   _ev(3)]))
    assert [e["seq"] for e in aggr.journal_events()] == [1, 2, 3]


def test_aggregator_rejects_wrong_wire_version():
    aggr = FleetAggregator()
    with pytest.raises(osnap.WireError):
        aggr.ingest("w0", {"v": 999})


# -- shed policy --------------------------------------------------------------


def _eval(burns, ok=True, n=10):
    return {"serve_p99": {"label": "serve.submit_to_result", "ok": ok,
                          "n": n, "burn_rate": burns}}


def test_policy_quiet_fleet_decides_nothing():
    policy = ShedPolicy(shed_burn=4.0, drain_burn=32.0)
    assert policy.decide(_eval({"60s": 0.5}), {"w0": _eval({"60s": 0.9})}) \
        == []


def test_policy_sheds_the_worst_burning_worker():
    policy = ShedPolicy(shed_burn=4.0, drain_burn=32.0)
    decisions = policy.decide(
        _eval({"60s": 6.0}),
        {"w0": _eval({"60s": 1.0}), "w1": _eval({"60s": 9.0})})
    assert len(decisions) == 1
    d = decisions[0]
    assert (d.worker, d.action) == ("w1", "shed")
    assert d.burn == 9.0 and d.objective == "serve_p99"


def test_policy_escalates_to_drain():
    policy = ShedPolicy(shed_burn=4.0, drain_burn=32.0)
    # past the drain threshold outright
    d = policy.decide(_eval({"60s": 40.0}),
                      {"w0": _eval({"60s": 40.0})})[0]
    assert d.action == "drain"
    # or shed-to-the-bottom and still burning
    d = policy.decide(_eval({"60s": 6.0}), {"w0": _eval({"60s": 6.0})},
                      rungs={"w0": 2})[0]
    assert d.action == "drain"


def test_worst_burn_picks_the_peak_window():
    obj, window, rate = worst_burn(_eval({"60s": 2.0, "300s": 7.5}))
    assert (obj, window, rate) == ("serve_p99", "300s", 7.5)


# -- a real 2-worker fleet (verdict backend, spawned once per module) ---------


@pytest.fixture(scope="module")
def fleet():
    router = FleetRouter(workers=2, backend="verdict",
                         env={"SERVE_MAX_WAIT_MS": "2"})
    yield router
    router.close()


def test_fleet_verdict_identity_and_affinity(fleet):
    pks = [_pk(1), _pk(2)]
    futs, want = [], []
    for i in range(24):
        msg = bytes([i]) * 32
        sig = BAD_SIGNATURE if i % 6 == 5 else bytes([i]) * 96
        futs.append(fleet.submit("fast_aggregate", pks, msg, sig))
        want.append(i % 6 != 5)
    assert [f.result(timeout=30) for f in futs] == want
    # affinity: resubmitting identical content goes to the same worker
    # and is answered by ITS cache — the fleet verifies each distinct
    # check exactly once
    snaps = fleet.poll_snapshots()
    hits_before = {w: s["extra"]["serve"]["cache_hits"]
                   for w, s in snaps.items()}
    futs = [fleet.submit("fast_aggregate", pks, bytes([i]) * 32,
                         bytes([i]) * 96) for i in range(4)]
    assert all(f.result(timeout=30) for f in futs)
    snaps = fleet.poll_snapshots()
    gained = sum(s["extra"]["serve"]["cache_hits"] - hits_before[w]
                 for w, s in snaps.items())
    assert gained == 4


def test_fleet_merged_scrape_is_exact_merge_of_worker_snapshots(fleet):
    snaps = fleet.poll_snapshots()
    label = "serve.submit_to_result"
    wires = [s["hists"][label] for s in snaps.values()]
    expect_count = sum(w["count"] for w in wires)
    expect_buckets = {}
    for w in wires:
        for idx, n in w["counts"].items():
            expect_buckets[int(idx)] = expect_buckets.get(int(idx), 0) + n
    merged = fleet.aggregator.merged_hists()[label]
    assert merged.count == expect_count
    assert merged.state()["counts"] == expect_buckets
    fam = ("consensus_specs_tpu_serve_submit_to_result_latency_hist_"
           "seconds_count")
    text = fleet.scrape_text()
    [count_line] = [l for l in text.splitlines()
                    if l.startswith(fam + " ")]
    assert int(count_line.rsplit(" ", 1)[1]) == expect_count
    # per-worker namespaced instance gauges ride the same scrape
    assert 'label="serve[w0].queue_depth"' in text


def test_fleet_healthz_and_exposition_endpoint(fleet):
    import urllib.request

    server = fleet.start_exposition(port=0)
    try:
        with urllib.request.urlopen(server.url("/healthz"),
                                    timeout=10) as resp:
            hz = json.loads(resp.read())
        assert hz["ok"] is True and hz["workers"] == ["w0", "w1"]
        with urllib.request.urlopen(server.url("/metrics"),
                                    timeout=10) as resp:
            body = resp.read().decode()
        assert "consensus_specs_tpu_fleet_workers 2.0" in body
    finally:
        server.close()


def test_worker_protocol_answers_unknown_ops_with_errors(fleet):
    from consensus_specs_tpu.serve.fleet import WorkerProtocolError

    with pytest.raises(WorkerProtocolError, match="unknown op"):
        fleet.handle("w0").rpc({"op": "no_such_op"}, timeout=10)


def test_sim_partition_heal_replayed_against_the_live_fleet(fleet):
    """The simnet satellite: a real scenario, real worker PROCESSES doing
    every node's verification, and the strict differential convergence
    gate still green — the fleet is transparent to consensus."""
    from consensus_specs_tpu.sim.fleet_replay import run_fleet_replay

    out = run_fleet_replay("partition_heal", strict=True, router=fleet)
    assert out["report"].converged
    assert out["fleet"]["routed"] > 0
    submits = [w["submits"] for w in out["fleet"]["per_worker"].values()]
    assert sum(submits) > 0 and len(submits) == 2


# -- forced fault -> burn -> shed escalation (its own fleet) ------------------


def test_fault_burns_merged_slo_and_sheds_then_drains(monkeypatch):
    """The control loop end to end on a live fleet: a slow-fault on one
    worker lights up the MERGED histograms, the policy sheds THAT worker
    down the ladder (journaled on both sides), holddown-free ticks
    escalate to rung 2 and finally drain — and the drained worker's keys
    re-home while the fleet keeps answering."""
    # arm the ROUTER-side recorder too: the decisions must journal
    monkeypatch.setenv("CONSENSUS_SPECS_TPU_FLIGHT", "1")
    flight.reset_global()
    objectives = [{"name": "serve_p99", "label": "serve.submit_to_result",
                   "quantile": 99.0, "threshold_s": 0.05}]
    router = FleetRouter(
        workers=2, backend="verdict",
        env={"SERVE_MAX_WAIT_MS": "2", "CONSENSUS_SPECS_TPU_FLIGHT": "1"},
        objectives=objectives,
        policy=ShedPolicy(shed_burn=2.0, drain_burn=10000.0),
        holddown_s=0.0)
    try:
        pks = [_pk(3)]
        futs = [router.submit("fast_aggregate", pks, bytes([i]) * 32,
                              bytes([i]) * 96) for i in range(6)]
        [f.result(timeout=30) for f in futs]
        router.control_tick()  # baseline checkpoint: clean traffic

        # craft distinct traffic that all routes to ONE worker
        target, items, i = None, [], 50
        while len(items) < 6 and i < 250:
            msg, sig = bytes([i]) * 32, bytes([i]) * 96
            label = router.route_label(
                check_key("fast_aggregate", pks, msg, sig))
            if target is None:
                target = label
            if label == target:
                items.append((msg, sig))
            i += 1
        router.handle(target).inject_fault(calls=64, mode="slow", ms=150)
        futs = [router.submit("fast_aggregate", pks, m, s)
                for m, s in items]
        assert all(f.result(timeout=60) for f in futs)

        time.sleep(1.1)  # checkpoint spacing
        tick = router.control_tick()
        assert tick["decisions"], f"no decision: {tick['slo']}"
        d = tick["decisions"][0]
        assert d["worker"] == target and d["action"] == "shed"
        assert d["rung_to"] == 1 and d["burn"] >= 2.0
        snap = router.poll_snapshots()[target]
        assert snap["extra"]["ladder_rung"] == 1

        # escalate: rung 2, then (still burning at the bottom) drain
        d2 = router.control_tick()["decisions"][0]
        assert (d2["action"], d2["rung_to"]) == ("shed", 2)
        d3 = router.control_tick()["decisions"][0]
        assert d3["action"] == "drain"
        assert router.live_workers == [w for w in ("w0", "w1")
                                       if w != target]

        # reconstruction: decision events + the worker's own transitions
        events = [json.loads(l) for l in
                  router.journal_jsonl().splitlines()[1:]]
        fleet_kinds = [e["kind"] for e in events if e["plane"] == "fleet"]
        assert fleet_kinds.count("shed") == 2 and "drain" in fleet_kinds
        transitions = [e["data"] for e in events
                       if e["kind"] == "shed_rung"
                       and e.get("worker") == target]
        assert [(t["rung_from"], t["rung_to"]) for t in transitions] == \
            [(0, 1), (1, 2)]

        # the survivor still answers (the drained arc re-homed)
        fut = router.submit("fast_aggregate", pks, b"\xee" * 32,
                            b"\xdd" * 96)
        assert fut.result(timeout=30) is True
        assert router.sheds == 2 and router.drains == 1
    finally:
        router.close()
        flight.reset_global()


def test_drain_answers_submits_already_on_the_pipe():
    """A submit that routed to a worker just before its drain (the ring
    read races ring.remove) is still answered: the worker keeps reading
    until stdin EOF instead of breaking out at the drain op."""
    router = FleetRouter(workers=1, backend="verdict",
                         env={"SERVE_MAX_WAIT_MS": "2"})
    try:
        h = router.handle("w0")
        h.rpc({"op": "drain"}, timeout=10)
        # the drain is acked but stdin is still open — this submit sits
        # behind it on the pipe, exactly the shed-to-drain race window
        fut = h.submit("fast_aggregate", [_pk(1)], b"\x02" * 32,
                       b"\x03" * 96)
        assert fut.result(timeout=30) is True
    finally:
        router.close()


def test_crashed_worker_is_reaped_from_the_ring(monkeypatch):
    """A kill -9 (not a drain) must not black-hole the dead worker's key
    arc: the next control tick evicts it from the ring, journals
    worker_lost, and the survivor answers the re-homed keys."""
    monkeypatch.setenv("CONSENSUS_SPECS_TPU_FLIGHT", "1")
    flight.reset_global()
    router = FleetRouter(workers=2, backend="verdict",
                         env={"SERVE_MAX_WAIT_MS": "2"})
    try:
        victim = router.route_label(b"\xaa" * 32)
        router.handle(victim)._proc.kill()
        router.handle(victim)._proc.wait(timeout=10)
        router.control_tick()
        assert victim not in router.live_workers
        survivor = [w for w in ("w0", "w1") if w != victim][0]
        # the dead arc re-homed: every key now routes to the survivor
        for i in range(16):
            assert router.route_label(bytes([i]) * 32) == survivor
        fut = router.submit("fast_aggregate", [_pk(9)], b"\xaa" * 32,
                            b"\xbb" * 96)
        assert fut.result(timeout=30) is True
        lost = [e for e in router.journal_jsonl().splitlines()[1:]
                if json.loads(e)["kind"] == "worker_lost"]
        assert len(lost) == 1
        assert json.loads(lost[0])["data"]["worker"] == victim
    finally:
        router.close()
        flight.reset_global()


# -- flight dump collision fix (satellite) ------------------------------------


def test_flight_dump_paths_are_worker_suffixed(tmp_path, monkeypatch):
    base = str(tmp_path / "flight_dump.jsonl")
    monkeypatch.delenv(flight.WORKER_ENV, raising=False)
    assert flight.resolve_dump_path(base) == base  # untouched outside
    monkeypatch.setenv(flight.WORKER_ENV, "w3")
    resolved = flight.resolve_dump_path(base)
    import os

    assert resolved.endswith(f".w3-pid{os.getpid()}.jsonl")
    rec = flight.FlightRecorder()
    rec.note("serve", "flush", items=1)
    written = rec.dump(base, reason="test")
    assert written == resolved and os.path.exists(written)
    # two "processes" (labels) sharing one configured path never collide
    monkeypatch.setenv(flight.WORKER_ENV, "w4")
    assert flight.resolve_dump_path(base) != resolved


def test_fleet_gauges_are_registered_and_documented_shapes():
    for name in ("fleet.workers", "fleet.snapshots", "fleet.requests",
                 "fleet.sheds", "fleet.drains", "serve.ladder_rung"):
        assert registry.known(name), f"{name} unregistered"
    # the worker-namespaced serve family resolves for fleet labels too
    assert registry.known("serve[w0].submit_to_result")
    assert registry.node_label("serve.ladder_rung", "w1") == \
        "serve[w1].ladder_rung"


def test_slo_tracker_accepts_explicit_hists():
    from consensus_specs_tpu.obs.hist import Histogram

    h = Histogram()
    for v in (0.01, 0.02, 5.0):
        h.observe(v)
    clock = [0.0]
    tracker = SloTracker(
        objectives=[{"name": "serve_p99",
                     "label": "serve.submit_to_result",
                     "quantile": 99.0, "threshold_s": 1.0}],
        clock=lambda: clock[0])
    tracker.evaluate(hists={"serve.submit_to_result": Histogram()},
                     export=False)
    clock[0] = 120.0
    out = tracker.evaluate(hists={"serve.submit_to_result": h},
                           export=False)["serve_p99"]
    assert out["n"] == 3 and out["ok"] is False
    # burn: 1 over of 3 in the window, budget 1% -> ~33x
    assert out["burn_rate"]["60s"] == pytest.approx((1 / 3) / 0.01)
    # export=False kept the slo.* gauges untouched
    assert "slo.ok" not in profiling.stats_and_gauges()[1]
