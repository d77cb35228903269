"""HeadService integration: real spec histories, serve-plane signature
routing, deferred/dropped gossip, chain metrics + exposition, tracing
spans, and the gossip fault-plan helpers.

The differential claim here runs on REAL histories (blocks built through
the actual state transition, attestations from real committees) with the
service's inline differential assert enabled — every block and every
attestation batch compares the maintained head against a from-scratch
``spec.get_head``. The synthetic randomized gate lives in
tests/test_chain.py.
"""
import json
import random
import urllib.request

import pytest

from consensus_specs_tpu.builder import build_spec_module
from consensus_specs_tpu.chain import HeadService
from consensus_specs_tpu.obs.tracing import CHAIN_STAGES, Tracer
from consensus_specs_tpu.serve.load import (
    BAD_SIGNATURE,
    VerdictBackend,
    plan_gossip_faults,
)
from consensus_specs_tpu.serve.service import VerificationService
from consensus_specs_tpu.test import context
from consensus_specs_tpu.test.helpers.attestations import (
    get_valid_attestation,
    next_epoch_with_attestations,
)
from consensus_specs_tpu.test.helpers.block import (
    build_empty_block_for_next_slot,
)
from consensus_specs_tpu.test.helpers.state import (
    state_transition_and_sign_block,
)
from consensus_specs_tpu.utils import bls


@pytest.fixture(scope="module")
def spec():
    return build_spec_module("phase0", "minimal")


@pytest.fixture(scope="module")
def genesis_state(spec):
    return context.get_genesis_state(
        spec, context.default_balances, context.default_activation_threshold
    )


@pytest.fixture(autouse=True)
def _bls_off():
    # histories are built with the stubbed switchboard (the reference's
    # `make test` posture); service-routing tests flip it on themselves
    was = bls.bls_active
    bls.bls_active = False
    yield
    bls.bls_active = was


def _service(spec, genesis_state, **kw):
    state = genesis_state.copy()
    anchor_block = spec.BeaconBlock(state_root=state.hash_tree_root())
    head = HeadService(spec, state, anchor_block, **kw)
    return head, state


def _tick_to(spec, head, slot):
    store = head.store
    for s in range(int(spec.get_current_slot(store)) + 1, int(slot) + 1):
        head.on_tick(store.genesis_time + s * int(spec.config.SECONDS_PER_SLOT))


def _fork_pair(spec, base_state, tag_a=b"\x01", tag_b=b"\x02"):
    """Two competing siblings on the next slot."""
    state_a, state_b = base_state.copy(), base_state.copy()
    block_a = build_empty_block_for_next_slot(spec, state_a)
    block_a.body.graffiti = spec.Bytes32(tag_a * 32)
    signed_a = state_transition_and_sign_block(spec, state_a, block_a)
    block_b = build_empty_block_for_next_slot(spec, state_b)
    block_b.body.graffiti = spec.Bytes32(tag_b * 32)
    signed_b = state_transition_and_sign_block(spec, state_b, block_b)
    return (state_a, signed_a), (state_b, signed_b)


# -- real-history differential ------------------------------------------------


def test_real_history_differential(spec, genesis_state):
    """Three epochs of blocks-with-attestations (justified checkpoint
    moves), then a two-sibling fork flipped by a gossip vote — the inline
    differential assert runs after EVERY block and batch."""
    head, _ = _service(spec, genesis_state, differential=True)
    state = genesis_state.copy()
    for _ in range(3):
        _, signed_blocks, state = next_epoch_with_attestations(
            spec, state, True, False)
        for sb in signed_blocks:
            _tick_to(spec, head, sb.message.slot)
            head.on_block(sb)
    assert int(head.store.justified_checkpoint.epoch) > 0
    assert bytes(spec.get_head(head.store)) == bytes(head.get_head())

    (state_a, signed_a), (state_b, signed_b) = _fork_pair(spec, state)
    _tick_to(spec, head, signed_a.message.slot)
    head.on_block(signed_a)
    head.on_block(signed_b)
    root_a = spec.hash_tree_root(signed_a.message)
    root_b = spec.hash_tree_root(signed_b.message)
    tie = head.get_head()
    assert tie in (root_a, root_b)
    loser_state, loser_signed, loser_root = (
        (state_a, signed_a, root_a) if tie == root_b
        else (state_b, signed_b, root_b))
    att = get_valid_attestation(
        spec, loser_state, slot=loser_signed.message.slot, signed=False,
        beacon_block_root=loser_root)
    _tick_to(spec, head, loser_signed.message.slot + 1)
    summary = head.on_attestations([att])
    assert summary["applied"] > 0
    assert head.get_head() == loser_root
    snap = head.metrics.snapshot()
    assert snap["reorgs"] >= 1 and snap["head_changes"] >= 2


@pytest.mark.slow
def test_real_history_finalization_prunes_slow(spec, genesis_state):
    """Five epochs with current+previous-epoch attestations: the store
    FINALIZES on the validated path, the proto-array prunes, and the
    differential assert holds throughout."""
    head, _ = _service(spec, genesis_state, differential=True)
    state = genesis_state.copy()
    for epoch in range(5):
        prev = epoch > 1
        _, signed_blocks, state = next_epoch_with_attestations(
            spec, state, True, prev)
        for sb in signed_blocks:
            _tick_to(spec, head, sb.message.slot)
            head.on_block(sb)
    assert int(head.store.finalized_checkpoint.epoch) > 0
    assert head.metrics.snapshot()["pruned_nodes"] > 0
    assert bytes(spec.get_head(head.store)) == bytes(head.get_head())


# -- serve-plane routing ------------------------------------------------------


def _routed_service(spec, genesis_state):
    """A HeadService over a VerificationService whose verdicts are
    carried by the signature bytes (serve/load.py VerdictBackend)."""
    backend = VerdictBackend()
    svc = VerificationService(backend=backend, max_batch=16, max_wait_ms=2.0)
    head, state = _service(spec, genesis_state, service=svc,
                           differential=True)
    return head, state, svc, backend


def test_service_routes_verdicts(spec, genesis_state):
    """Valid signatures apply; BAD_SIGNATURE comes back False from the
    service and the attestation is dropped WITHOUT touching either fork
    choice — while the spec store and proto array stay head-identical."""
    head, state, svc, backend = _routed_service(spec, genesis_state)
    try:
        (state_a, signed_a), (state_b, signed_b) = _fork_pair(spec, state)
        _tick_to(spec, head, signed_a.message.slot)
        head.on_block(signed_a)
        head.on_block(signed_b)
        root_a = spec.hash_tree_root(signed_a.message)
        root_b = spec.hash_tree_root(signed_b.message)
        tie = head.get_head()
        loser_state, loser_signed, loser_root = (
            (state_a, signed_a, root_a) if tie == root_b
            else (state_b, signed_b, root_b))
        _tick_to(spec, head, loser_signed.message.slot + 1)

        bls.bls_active = True  # verdicts must flow through the service
        bad = get_valid_attestation(
            spec, loser_state, slot=loser_signed.message.slot, signed=False,
            beacon_block_root=loser_root)
        bad.signature = spec.BLSSignature(BAD_SIGNATURE)
        summary = head.on_attestations([bad])
        assert summary == {"applied": 0, "stale": 0, "deferred": 0,
                           "dropped": 1, "resolved": 0}
        assert head.get_head() == tie  # nothing moved
        assert not head.store.latest_messages

        good = get_valid_attestation(
            spec, loser_state, slot=loser_signed.message.slot, signed=False,
            beacon_block_root=loser_root)
        summary = head.on_attestations([good])
        assert summary["applied"] > 0 and summary["dropped"] == 0
        assert head.get_head() == loser_root
        assert backend.calls > 0  # the verdicts really came from the backend
    finally:
        svc.close(timeout=30)


def test_unknown_block_defers_then_resolves(spec, genesis_state):
    """Gossip for a block the store has not seen parks in the deferral
    buffer and applies when the block arrives — the spec's 'delay
    consideration' rule, end to end through the service."""
    head, state, svc, _ = _routed_service(spec, genesis_state)
    try:
        fork_state = state.copy()
        block = build_empty_block_for_next_slot(spec, fork_state)
        signed = state_transition_and_sign_block(spec, fork_state, block)
        root = spec.hash_tree_root(block)
        att = get_valid_attestation(spec, fork_state, slot=block.slot,
                                    signed=False, beacon_block_root=root)
        _tick_to(spec, head, block.slot + 1)

        bls.bls_active = True
        summary = head.on_attestations([att])
        assert summary["deferred"] == 1 and head.deferred_count == 1
        assert head.metrics.snapshot()["deferred_pending"] == 1

        bls.bls_active = False  # the block path verifies inline
        head.on_block(signed)  # arrival retries the deferred gossip
        snap = head.metrics.snapshot()
        assert snap["resolved"] == 1 and snap["deferred_pending"] == 0
        assert head.get_head() == root
    finally:
        svc.close(timeout=30)


def test_deferred_attestation_survives_unrelated_blocks(spec, genesis_state):
    """The order-independence regression (simnet reordering): an
    attestation heard before its block must survive MORE unrelated block
    arrivals than its whole retry budget, then still apply the moment its
    own block lands via a different peer."""
    head, state = _service(spec, genesis_state, defer_retries=2)
    # the attested fork block, withheld from the service for now
    fork_state = state.copy()
    block = build_empty_block_for_next_slot(spec, fork_state)
    block.body.graffiti = spec.Bytes32(b"\x07" * 32)
    signed = state_transition_and_sign_block(spec, fork_state, block)
    root = spec.hash_tree_root(block)
    att = get_valid_attestation(spec, fork_state, slot=block.slot,
                                signed=False, beacon_block_root=root)
    _tick_to(spec, head, block.slot + 1)
    summary = head.on_attestations([att])
    assert summary["deferred"] == 1 and head.deferred_count == 1

    # five unrelated main-chain blocks arrive — far past defer_retries=2.
    # None of them resolves the entry, so none may consume its budget
    # (and the interleaved clock ticks re-examine it uncharged)
    st = state.copy()
    for _ in range(5):
        sb = state_transition_and_sign_block(
            spec, st, build_empty_block_for_next_slot(spec, st))
        _tick_to(spec, head, sb.message.slot)
        head.on_block(sb)
    assert head.deferred_count == 1, "unrelated arrivals evicted the entry"

    # the attested block finally arrives via "a different peer"
    head.on_block(signed)
    snap = head.metrics.snapshot()
    assert snap["resolved"] == 1 and snap["deferred_pending"] == 0
    assert head.store.latest_messages  # the vote applied
    assert bytes(spec.get_head(head.store)) == bytes(head.get_head())


def test_deferred_block_vs_attestation_order_is_irrelevant(spec,
                                                           genesis_state):
    """Same gossip, two delivery orders (block-then-attestation vs
    attestation-then-block): identical head and latest messages."""
    fork_state = genesis_state.copy()
    block = build_empty_block_for_next_slot(spec, fork_state)
    signed = state_transition_and_sign_block(spec, fork_state, block)
    root = spec.hash_tree_root(block)

    def run(block_first: bool):
        head, _ = _service(spec, genesis_state)
        att = get_valid_attestation(spec, fork_state.copy(),
                                    slot=block.slot, signed=False,
                                    beacon_block_root=root)
        _tick_to(spec, head, block.slot + 1)
        if block_first:
            head.on_block(signed)
            head.on_attestations([att])
        else:
            head.on_attestations([att])
            head.on_block(signed)
        table = {
            int(i): (int(m.epoch), bytes(m.root))
            for i, m in head.store.latest_messages.items()
        }
        return bytes(head.get_head()), table

    head_a, votes_a = run(block_first=True)
    head_b, votes_b = run(block_first=False)
    assert head_a == head_b == bytes(root)
    assert votes_a == votes_b and votes_a


def test_stale_deferred_entries_evict_via_epoch_window(spec, genesis_state):
    """An entry whose block never arrives is evicted by the spec's
    stale-epoch rule as the clock advances — not leaked, not charged to
    unrelated arrivals."""
    head, state = _service(spec, genesis_state)
    never_known = spec.Root(b"\x77" * 32)
    att = get_valid_attestation(spec, state.copy(), slot=state.slot,
                                signed=False)
    att.data.beacon_block_root = never_known
    _tick_to(spec, head, state.slot + 2)
    summary = head.on_attestations([att])
    assert summary["deferred"] == 1
    # clock to epoch 3: target epoch 0 leaves the {current, previous}
    # window and the tick's (uncharged) re-route drops the entry
    _tick_to(spec, head, int(spec.SLOTS_PER_EPOCH) * 3)
    assert head.deferred_count == 0
    assert head.metrics.snapshot()["dropped"] == 1


def test_time_gated_deferrals_charge_retries(spec, genesis_state):
    """Entries gated on the CLOCK (far-future target epoch) spend one
    retry per slot tick — the budget still bounds time-gated spinning."""
    head, state = _service(spec, genesis_state, defer_retries=2)
    att = get_valid_attestation(spec, state.copy(), slot=state.slot,
                                signed=False)
    att.data.target.epoch = spec.Epoch(64)  # far future: never applies
    summary = head.on_attestations([att])
    assert summary["deferred"] == 1
    _tick_to(spec, head, state.slot + 1)  # retry 1 -> re-defer (charged)
    assert head.deferred_count == 1
    _tick_to(spec, head, state.slot + 2)  # retry 2 -> budget exhausted
    assert head.deferred_count == 0
    assert head.metrics.snapshot()["dropped"] == 1


def test_stale_epoch_attestation_drops(spec, genesis_state):
    head, state = _service(spec, genesis_state)
    att = get_valid_attestation(spec, state.copy(), slot=state.slot,
                                signed=False)
    # clock far ahead: target epoch 0 is neither current nor previous
    _tick_to(spec, head, int(spec.SLOTS_PER_EPOCH) * 3)
    summary = head.on_attestations([att])
    assert summary == {"applied": 0, "stale": 0, "deferred": 0,
                       "dropped": 1, "resolved": 0}


# -- observability ------------------------------------------------------------


def test_chain_gauges_and_exposition(spec, genesis_state):
    """The chain.* family lands in profiling.summary() and renders on a
    live /metrics endpoint; /snapshot serves the ChainMetrics snapshot."""
    from consensus_specs_tpu.obs.exposition import start_exposition
    from consensus_specs_tpu.ops import profiling

    profiling.reset()
    head, state = _service(spec, genesis_state, differential=True)
    st = state.copy()
    signed = state_transition_and_sign_block(
        spec, st, build_empty_block_for_next_slot(spec, st))
    _tick_to(spec, head, signed.message.slot)
    head.on_block(signed)

    snap = profiling.summary()
    from consensus_specs_tpu.chain.metrics import GAUGE_LABELS

    for label in GAUGE_LABELS:
        assert label in snap, f"{label} missing from profiling summary"
    assert snap["chain.blocks"]["gauge"] == 2.0  # anchor + one block

    with start_exposition(snapshot_fn=head.metrics.snapshot) as server:
        with urllib.request.urlopen(server.url("/metrics"), timeout=10) as r:
            body = r.read().decode()
        chain_lines = [ln for ln in body.splitlines()
                       if ln.startswith("consensus_specs_tpu_chain_")]
        assert len(chain_lines) >= len(GAUGE_LABELS)
        with urllib.request.urlopen(server.url("/snapshot"), timeout=10) as r:
            snapshot = json.loads(r.read().decode())
        assert snapshot["blocks"] == 1 and "apply_latency" in snapshot


def test_batch_spans_traced(spec, genesis_state):
    tracer = Tracer(capacity=64)
    head, state = _service(spec, genesis_state, tracer=tracer)
    att = get_valid_attestation(spec, state.copy(), slot=state.slot,
                                signed=False)
    _tick_to(spec, head, state.slot + 1)
    head.on_attestations([att])
    done = [t for t in tracer.completed() if t.kind == "chain_apply"]
    assert done, "no chain_apply trace finished"
    names = done[-1].span_names()
    assert set(CHAIN_STAGES) <= names


def test_gossip_fault_plan_shape():
    rng = random.Random(3)
    plan = plan_gossip_faults(rng, 200, invalid_rate=0.2, orphan_rate=0.2)
    assert plan[0] == "ok"  # the stream never starts with a fault
    kinds = set(plan)
    assert kinds == {"ok", "invalid_sig", "orphan"}
    assert plan.count("invalid_sig") + plan.count("orphan") < 120


def test_verdict_backend_contract():
    backend = VerdictBackend()
    out = backend.batch_fast_aggregate_verify(
        [[b"k"], [b"k"]], [b"m", b"m"], [b"\x01" * 96, BAD_SIGNATURE])
    assert out == [True, False]
    assert backend.calls == 1 and backend.items == 2
