"""Consensus health ledger (ISSUE 19): per-slot rows from a stubbed
HeadService (participation weighting, finality lag, churn/reorg deltas,
unexplained-reorg accounting under declared disruption windows), the
summary/aggregate algebra, the HEALTH gate, and the gauge export the
TSDB samples. Crypto-free: the ledger only reads counters and dicts.
"""
import pytest

from consensus_specs_tpu.chain.health import (
    DEFAULT_PARTICIPATION_FLOOR,
    GAUGE_LABELS,
    HealthLedger,
    aggregate_summaries,
    evaluate_gate,
)
from consensus_specs_tpu.ops import profiling


@pytest.fixture(autouse=True)
def _clean_profiling():
    profiling.reset()
    yield
    profiling.reset()


class _Spec:
    SLOTS_PER_EPOCH = 8

    def get_current_slot(self, store):
        return store.current_slot

    def compute_start_slot_at_epoch(self, epoch):
        return epoch * self.SLOTS_PER_EPOCH


class _Checkpoint:
    def __init__(self, epoch):
        self.epoch = epoch


class _Store:
    def __init__(self):
        self.current_slot = 0
        self.finalized_checkpoint = _Checkpoint(0)


class _ForkChoice:
    def __init__(self):
        self._balances = {}
        self.votes = {}


class _Metrics:
    def __init__(self):
        self._c = {"head_changes": 0, "reorgs": 0, "rollbacks": 0,
                   "last_reorg_depth": 0}

    def counters(self):
        return dict(self._c)


class _FakeHead:
    """The minimal HeadService surface the ledger reads."""

    def __init__(self):
        self.spec = _Spec()
        self.store = _Store()
        self.fc = _ForkChoice()
        self.metrics = _Metrics()
        self.deferred_count = 0


def _vote(head, validator, balance, voted=True):
    head.fc._balances[validator] = balance
    if voted:
        head.fc.votes[validator] = object()


def test_participation_is_balance_weighted():
    head = _FakeHead()
    _vote(head, 0, 32, voted=True)
    _vote(head, 1, 32, voted=True)
    _vote(head, 2, 96, voted=False)  # one heavy abstainer
    rec = HealthLedger(head).observe_slot(slot=5)
    assert rec["participation_rate"] == pytest.approx(64 / 160)
    assert rec["slot"] == 5


def test_empty_validator_set_reads_zero_not_crash():
    rec = HealthLedger(_FakeHead()).observe_slot(slot=0)
    assert rec["participation_rate"] == 0.0


def test_finality_lag_is_slots_past_finalized_epoch_start():
    head = _FakeHead()
    head.store.finalized_checkpoint = _Checkpoint(2)  # start slot 16
    led = HealthLedger(head)
    assert led.observe_slot(slot=18)["finality_lag_slots"] == 2
    assert led.observe_slot(slot=40)["finality_lag_slots"] == 24
    # finalized ahead of the queried slot clamps at 0, never negative
    assert led.observe_slot(slot=10)["finality_lag_slots"] == 0
    assert led.summary()["finality_lag_max"] == 24


def test_counter_deltas_not_cumulatives_per_slot():
    head = _FakeHead()
    led = HealthLedger(head)
    head.metrics._c.update(head_changes=3, rollbacks=1)
    rec = led.observe_slot(slot=1)
    assert rec["head_churn"] == 3 and rec["rollback_rate"] == 1
    # no movement next slot: deltas read 0, totals hold
    rec = led.observe_slot(slot=2)
    assert rec["head_churn"] == 0 and rec["rollback_rate"] == 0
    assert led.head_churn_total == 3 and led.rollbacks_total == 1


def test_unexplained_reorgs_only_accumulate_outside_declared_windows():
    head = _FakeHead()
    led = HealthLedger(head)
    # a reorg inside a declared disruption window: explained
    head.metrics._c.update(reorgs=1, last_reorg_depth=2)
    rec = led.observe_slot(slot=1, expect_reorgs=True)
    assert rec["unexplained_reorgs"] == 0 and rec["reorg_depth"] == 2
    # the same movement outside any window: counted, and it sticks
    head.metrics._c.update(reorgs=3, last_reorg_depth=5)
    rec = led.observe_slot(slot=2, expect_reorgs=False)
    assert rec["unexplained_reorgs"] == 2
    assert led.summary()["unexplained_reorgs"] == 2
    assert led.summary()["reorgs_total"] == 3
    assert led.summary()["reorg_depth_max"] == 5


def test_reorg_depth_reads_zero_when_head_only_extended():
    head = _FakeHead()
    head.metrics._c.update(last_reorg_depth=7)  # stale depth, no reorg
    assert HealthLedger(head).observe_slot(slot=1)["reorg_depth"] == 0


def test_gauges_export_under_node_label():
    head = _FakeHead()
    _vote(head, 0, 32)
    HealthLedger(head, node="n2").observe_slot(slot=3)
    gauges = profiling.stats_and_gauges()[1]
    for label in GAUGE_LABELS:
        name = label.split("health.", 1)[1]
        assert f"health[n2].{name}" in gauges, f"missing {name}"
    assert gauges["health[n2].participation_rate"] == 1.0
    # bare (node=None) form uses the registered base names
    HealthLedger(head).observe_slot(slot=3)
    gauges = profiling.stats_and_gauges()[1]
    assert "health.participation_rate" in gauges


def test_record_window_is_bounded_but_extremes_are_cumulative():
    head = _FakeHead()
    led = HealthLedger(head, window=4)
    _vote(head, 0, 32)
    head.store.finalized_checkpoint = _Checkpoint(0)
    for slot in range(10):
        led.observe_slot(slot=slot)
    assert len(led.records()) == 4
    assert led.summary()["slots_observed"] == 10
    # the max lag happened before the ring dropped it; summary keeps it
    assert led.summary()["finality_lag_max"] == 9


def test_aggregate_summaries_takes_the_worst_case_per_bound():
    a = {"slots_observed": 10, "participation_min": 0.9,
         "participation_mean": 0.95, "participation_last": 0.92,
         "finality_lag_max": 4, "finality_lag_last": 2,
         "reorg_depth_max": 1, "reorgs_total": 2, "unexplained_reorgs": 0,
         "head_churn_total": 5, "rollbacks_total": 1,
         "deferral_depth_max": 3}
    b = dict(a, participation_min=0.7, finality_lag_max=30,
             unexplained_reorgs=1, reorgs_total=1)
    agg = aggregate_summaries([a, b])
    assert agg["participation_min"] == 0.7     # min across nodes
    assert agg["finality_lag_max"] == 30       # max across nodes
    assert agg["unexplained_reorgs"] == 1      # sums
    assert agg["reorgs_total"] == 3
    assert aggregate_summaries([])["slots_observed"] == 0


def test_gate_verdicts_and_reasons():
    head = _FakeHead()
    _vote(head, 0, 32)
    led = HealthLedger(head)
    for slot in range(4):
        led.observe_slot(slot=slot)
    ok = evaluate_gate(led.summary())
    assert ok["ok"] and ok["reasons"] == []
    assert ok["participation_floor"] == DEFAULT_PARTICIPATION_FLOOR
    # each bound trips independently, with a legible reason string
    sick = dict(led.summary(), participation_min=0.1,
                finality_lag_max=999, unexplained_reorgs=2)
    verdict = evaluate_gate(sick)
    assert not verdict["ok"] and len(verdict["reasons"]) == 3
    assert any("participation_min" in r for r in verdict["reasons"])
    assert any("finality_lag_max" in r for r in verdict["reasons"])
    assert any("unexplained_reorgs" in r for r in verdict["reasons"])
    # a lag that grew and recovered still fails the bound it crossed
    recovered = dict(led.summary(), finality_lag_max=100,
                     finality_lag_last=2)
    assert not evaluate_gate(recovered, finality_lag_max_slots=64)["ok"]
    # empty horizon is never a pass
    assert not evaluate_gate(aggregate_summaries([]))["ok"]
