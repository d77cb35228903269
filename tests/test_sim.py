"""simnet: the adversarial multi-node convergence gate (tier-1).

Every named scenario class runs 4 real nodes (HeadService +
VerificationService each) through the deterministic discrete-event
fabric under the STRICT differential gate: identical block sets,
identical latest-message tables, one head everywhere, and that head
bit-identical to ``spec.get_head`` on each node's store AND on a
from-scratch union store. Determinism is pinned by the event-stream
digest (same seed -> identical run), and the fault-plan dataclass
(serve/load.py) gets its own seed-determinism gate here.
"""
import random

import pytest

from consensus_specs_tpu.serve.load import (
    FAULT_KINDS,
    GossipFaultPlan,
    plan_gossip_faults,
)
from consensus_specs_tpu.sim import (
    SCENARIOS,
    build_world,
    get_scenario,
    run_scenario,
    scenario_names,
)


@pytest.fixture(scope="module")
def world():
    return build_world()


# per-scenario evidence the attack actually happened (beyond convergence)
_SCENARIO_EVIDENCE = {
    "partition_heal": lambda r: r.partition_drops > 0 and r.last_heal_s > 0
    and r.sync_sends > 0,
    "latency_skew": lambda r: r.deliveries > 0,
    "lossy_links": lambda r: r.loss_drops > 0 and r.sync_sends > 0,
    "equivocation": lambda r: r.equivocations > 0,
    "withheld_orphans": lambda r: r.withheld > 0 and sum(
        p["resolved"] for p in r.per_node.values()) > 0,
    "long_range_reorg": lambda r: True,  # head-not-on-fork is in the gate
    "censored_aggregates": lambda r: r.censored > 0,
}


def test_scenario_library_shape():
    # the acceptance floor: >= 6 named classes, >= 4 nodes each, and the
    # evidence table stays in lockstep with the library
    assert len(SCENARIOS) >= 6
    assert set(_SCENARIO_EVIDENCE) == set(scenario_names())
    for sc in SCENARIOS.values():
        assert sc.nodes >= 4
        assert sc.review_finding  # docs/simnet_threat_model.md mapping


@pytest.mark.parametrize("name", scenario_names())
def test_scenario_converges(world, name):
    """The tentpole gate: strict differential convergence per scenario —
    any divergence raises SimDivergence inside run_scenario."""
    spec, anchor_state, anchor_block = world
    report = run_scenario(
        get_scenario(name), spec=spec, anchor_state=anchor_state,
        anchor_block=anchor_block, seed=7, strict=True)
    assert report.converged and report.error is None
    assert report.nodes >= 4
    # the network was genuinely disturbed before it converged
    assert report.diverged_samples > 0
    assert _SCENARIO_EVIDENCE[name](report), (
        f"{name}: attack evidence missing from {report.to_dict()}")
    # every node did real work and ended in agreement
    for node_name, snap in report.per_node.items():
        assert snap["applied"] > 0, f"{node_name} applied nothing"
        assert snap["deferred_pending"] == 0
        assert snap["backend_calls"] > 0  # verdicts flowed via the service
    assert report.heads_per_sec_min > 0


def test_same_seed_same_run(world):
    """Full determinism: the event-stream digest, the agreed head, and
    every traffic counter replay identically under a fixed seed."""
    spec, anchor_state, anchor_block = world
    kw = dict(spec=spec, anchor_state=anchor_state,
              anchor_block=anchor_block, seed=23)
    a = run_scenario(get_scenario("partition_heal"), **kw)
    b = run_scenario(get_scenario("partition_heal"), **kw)
    assert a.digest == b.digest
    assert a.head == b.head and a.head_slot == b.head_slot
    assert a.deliveries == b.deliveries
    assert a.heal_to_convergence_s == b.heal_to_convergence_s
    assert a.per_node == {
        n: {**s, "heads_per_sec": a.per_node[n]["heads_per_sec"]}
        for n, s in b.per_node.items()
    }  # wall-clock query rate aside, node outcomes are identical
    c = run_scenario(get_scenario("partition_heal"), **dict(kw, seed=24))
    assert c.digest != a.digest


def test_with_nodes_rescales_the_attack_too():
    """Rescaling a scenario must never disarm it: partition groups
    re-split and latency-skew targets remap onto surviving indices."""
    skewed = get_scenario("latency_skew").with_nodes(3)
    assert skewed.nodes == 3
    assert dict(skewed.latency_skew) == {2: 20.0}  # laggard survives
    split = get_scenario("partition_heal").with_nodes(6)
    assert split.partitions[0].groups == ((0, 1, 2), (3, 4, 5))


def test_more_nodes_still_converge(world):
    """The scenario rescales: 6 nodes re-split the partition groups and
    the gate still holds."""
    spec, anchor_state, anchor_block = world
    report = run_scenario(
        get_scenario("partition_heal"), spec=spec,
        anchor_state=anchor_state, anchor_block=anchor_block, seed=7,
        nodes=6)
    assert report.converged and report.nodes == 6
    assert report.partition_drops > 0


def test_node_labelled_metrics_published(world):
    """After a run, the per-node chain[*]/serve[*] families are in the
    profiling summary — N instances coexisted without gauge collisions."""
    from consensus_specs_tpu.ops import profiling

    spec, anchor_state, anchor_block = world
    run_scenario(get_scenario("equivocation"), spec=spec,
                 anchor_state=anchor_state, anchor_block=anchor_block,
                 seed=7)
    snap = profiling.summary()
    for node in ("n0", "n3"):
        assert f"chain[{node}].head_slot" in snap
        assert f"chain[{node}].blocks" in snap
        assert f"serve[{node}].queue_depth" in snap
    # the per-node head slots agree — same values, separate gauges
    assert (snap["chain[n0].head_slot"]["gauge"]
            == snap["chain[n3].head_slot"]["gauge"])


def test_flight_journals_per_node(world, tmp_path):
    """One JSONL journal per node AND per light client, stamped, on the
    simulated clock."""
    import json

    spec, anchor_state, anchor_block = world
    report = run_scenario(
        get_scenario("withheld_orphans"), spec=spec,
        anchor_state=anchor_state, anchor_block=anchor_block, seed=7,
        flight_dir=str(tmp_path))
    files = sorted(p.name for p in tmp_path.iterdir())
    assert files == [
        f"sim_flight_withheld_orphans_c{i}.jsonl"
        for i in range(report.light_clients)
    ] + [
        f"sim_flight_withheld_orphans_n{i}.jsonl"
        for i in range(report.nodes)
    ]
    first_node = "sim_flight_withheld_orphans_n0.jsonl"
    lines = [json.loads(ln) for ln in
             (tmp_path / first_node).read_text().splitlines()]
    header, events = lines[0], lines[1:]
    assert header["node"] == "n0" and header["events"] > 0
    kinds = {e["kind"] for e in events}
    assert "on_block" in kinds and "defer" in kinds
    assert all(e["node"] == "n0" for e in events)
    # timestamps are simulation seconds, bounded by the run's end
    assert all(0.0 <= e["t"] <= report.sim_end_s for e in events)


def test_slot_hook_fires_once_per_slot_outside_the_digest(world):
    """``slot_hook`` fires once per simulated slot boundary, in order,
    quiet stretches included, with every node's HeadService readable —
    a per-node health ledger is the reader it exists for. It runs
    outside the event queue, so the run is the same run without it."""
    from consensus_specs_tpu.chain.health import HealthLedger

    spec, anchor_state, anchor_block = world
    kw = dict(spec=spec, anchor_state=anchor_state,
              anchor_block=anchor_block, seed=7)
    plain = run_scenario(get_scenario("partition_heal"), **kw)
    slots, ledgers = [], {}

    def hook(slot, sim_nodes):
        slots.append(slot)
        for node in sim_nodes:
            if node.name not in ledgers:
                ledgers[node.name] = HealthLedger(node.head, node=node.name)
            ledgers[node.name].observe_slot(slot=slot, expect_reorgs=True)

    hooked = run_scenario(get_scenario("partition_heal"), slot_hook=hook,
                          **kw)
    assert hooked.digest == plain.digest and hooked.head == plain.head
    sps = int(spec.config.SECONDS_PER_SLOT)
    assert slots == list(range(1, int(hooked.sim_end_s // sps) + 1))
    assert set(ledgers) == set(hooked.per_node)
    for ledger in ledgers.values():
        summary = ledger.summary()
        assert summary["slots_observed"] == len(slots)
        assert summary["unexplained_reorgs"] == 0


# -- fault-plan dataclass (serve/load.py satellite) ---------------------------


def test_fault_plan_seed_determinism():
    """Same seed + rates -> structurally identical plan (the dataclass
    equality the sim's script builder relies on)."""
    args = (200, 0.1, 0.1, 0.1, 0.1)
    a = plan_gossip_faults(random.Random(5), *args)
    b = plan_gossip_faults(random.Random(5), *args)
    assert isinstance(a, GossipFaultPlan)
    assert a == b and a.kinds == b.kinds
    c = plan_gossip_faults(random.Random(6), *args)
    assert a != c


def test_fault_plan_covers_new_kinds():
    plan = plan_gossip_faults(random.Random(3), 400, 0.1, 0.1, 0.1, 0.1)
    assert set(plan.kinds) == set(FAULT_KINDS)
    assert plan[0] == "ok"  # the stream never starts with a fault
    counts = plan.counts()
    assert counts["equivocation"] > 0 and counts["censored_agg"] > 0
    assert sum(counts.values()) == len(plan) == 400
    # sequence protocol (pre-dataclass callers): count/iter/index
    assert plan.count("ok") == counts["ok"]


def test_fault_plan_band_stability():
    """Adding a new rate band never perturbs the draws of earlier kinds
    at a fixed seed — old two-rate callers see the same plan prefix
    behavior they always did."""
    old = plan_gossip_faults(random.Random(9), 300, 0.15, 0.15)
    new = plan_gossip_faults(random.Random(9), 300, 0.15, 0.15, 0.0, 0.0)
    assert old.kinds == new.kinds
    assert set(old.kinds) <= {"ok", "invalid_sig", "orphan"}
