"""Light-client proof plane (ISSUE 16): artifact construction +
verification, the content-addressed ``ProofService`` front, the simnet
``light_client`` node kind.

Tier-1 budget: everything here is crypto-free (VerdictBackend verdicts,
SHA-256-only Merkle checks) except the two tests that pin the REAL
sync-committee signature path — one pairing each through the pure-Python
oracle, no XLA compiles anywhere.
"""
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from types import SimpleNamespace

import pytest

from consensus_specs_tpu.lightclient.proof_tree import (
    FINALIZED_ROOT_GINDEX,
    NEXT_SYNC_COMMITTEE_GINDEX,
    ProofArtifact,
    ProofWorld,
    build_head_proof,
    proof_key,
    verify_artifact,
    verify_head_proof,
)
from consensus_specs_tpu.lightclient.serve_proofs import (
    ProofCache,
    ProofMetrics,
    ProofService,
)


@pytest.fixture(scope="module")
def spec():
    from consensus_specs_tpu.builder import build_spec_module

    return build_spec_module("altair", "minimal")


@pytest.fixture(scope="module")
def world(spec):
    return ProofWorld(spec)


# -- content addressing ------------------------------------------------------


def test_proof_key_content_addressing():
    r1, r2 = b"\x01" * 32, b"\x02" * 32
    assert proof_key(5, r1) == proof_key(5, r1)
    assert proof_key(5, r1) != proof_key(6, r1)
    assert proof_key(5, r1) != proof_key(5, r2)
    # length framing: (slot, root) pairs never collide by concatenation
    assert proof_key(1, b"\x00" * 4) != proof_key(1, b"\x00" * 8)
    art = ProofArtifact(slot=9, state_root=r1, finalized_root=r2,
                        finality_branch=[])
    assert art.key == proof_key(9, r1)


# -- the bounded cache -------------------------------------------------------


def test_proof_cache_lru_bounds_and_counters():
    cache = ProofCache(capacity=2)
    arts = {i: ProofArtifact(slot=i, state_root=bytes([i]) * 32,
                             finalized_root=b"", finality_branch=[])
            for i in range(3)}
    keys = {i: arts[i].key for i in range(3)}
    assert cache.get(keys[0]) is None  # miss
    cache.put(keys[0], arts[0])
    cache.put(keys[1], arts[1])
    assert cache.get(keys[0]) is arts[0]  # hit; 0 now most-recent
    cache.put(keys[2], arts[2])           # evicts 1, not 0
    assert cache.get(keys[1]) is None
    assert cache.get(keys[0]) is arts[0]
    assert len(cache) == 2
    assert cache.hits == 2 and cache.misses == 2
    assert cache.hit_rate == 0.5


# -- metrics -----------------------------------------------------------------


def test_proof_metrics_hit_rate_counts_joins_and_exports_gauges():
    from consensus_specs_tpu.ops import profiling

    profiling.reset()
    m = ProofMetrics(node=None)
    m.note_build()
    m.note_served()                 # the build
    m.note_served(hit=True)
    m.note_served(joined=True)      # a join is NOT a rebuild: counts hit
    m.note_verdict(True)
    m.note_verdict(False)
    assert m.served == 3 and m.builds == 1
    assert m.hit_rate == pytest.approx(2 / 3)
    m.export_gauges()
    summary = profiling.summary()
    assert summary["lightclient.proofs_served"]["gauge"] == 3
    assert summary["lightclient.proof_builds"]["gauge"] == 1
    assert summary["lightclient.inflight_joins"]["gauge"] == 1
    assert summary["lightclient.updates_verified"]["gauge"] == 1
    assert summary["lightclient.verify_failures"]["gauge"] == 1
    assert summary["lightclient.cache_hit_rate"]["gauge"] == \
        pytest.approx(2 / 3)


# -- the serving front -------------------------------------------------------


def _artifact(slot=7, root=b"\x07" * 32):
    return ProofArtifact(slot=slot, state_root=root, finalized_root=b"",
                         finality_branch=[])


def test_proof_service_builds_once_then_hits():
    svc = ProofService(capacity=8)
    builds = []

    def build():
        builds.append(1)
        return _artifact()

    a1 = svc.serve(7, b"\x07" * 32, build)
    a2 = svc.serve(7, b"\x07" * 32, build)
    assert a1 is a2 and len(builds) == 1
    snap = svc.snapshot()
    assert snap["served"] == 2 and snap["builds"] == 1
    assert snap["cache_hits"] == 1 and snap["hit_rate"] == 0.5
    assert snap["cache_entries"] == 1 and snap["pending"] == 0


def test_proof_service_inflight_dedup_joins_one_build():
    svc = ProofService(capacity=8)
    builds = []
    release = threading.Event()

    def slow_build():
        builds.append(1)
        release.wait(timeout=30)
        return _artifact()

    with ThreadPoolExecutor(max_workers=4) as pool:
        futs = [pool.submit(svc.serve, 7, b"\x07" * 32, slow_build)
                for _ in range(4)]
        # wait until the one owner is inside the build and the three
        # joiners are parked on its future
        deadline = time.time() + 30
        while time.time() < deadline:
            if builds and svc.snapshot()["pending"] == 1:
                break
            time.sleep(0.01)
        release.set()
        got = [f.result(timeout=30) for f in futs]
    assert len(builds) == 1
    assert all(g is got[0] for g in got)
    snap = svc.snapshot()
    assert snap["served"] == 4 and snap["builds"] == 1
    assert snap["inflight_joins"] == 3 and snap["pending"] == 0


def test_proof_service_failed_build_propagates_and_clears():
    svc = ProofService(capacity=8)

    def bad_build():
        raise RuntimeError("no such state")

    with pytest.raises(RuntimeError):
        svc.serve(7, b"\x07" * 32, bad_build)
    assert svc.snapshot()["pending"] == 0
    # the key is not poisoned: a later good build serves
    art = svc.serve(7, b"\x07" * 32, _artifact)
    assert art.slot == 7


def _verdict_artifact(signature):
    """An artifact shaped for ProofService._verify — the update only
    needs the signature attribute, so the VerdictBackend path stays
    crypto-free."""
    art = _artifact()
    art.update = SimpleNamespace(sync_committee_signature=signature)
    art.signing_root = b"\x0a" * 32
    art.participant_pubkeys = [b"\xc0" + b"\x00" * 47]
    return art


def test_proof_service_verdict_routes_through_verification_service():
    from consensus_specs_tpu.serve.load import BAD_SIGNATURE, VerdictBackend
    from consensus_specs_tpu.serve.service import VerificationService

    backend = VerdictBackend()
    verifier = VerificationService(backend, max_batch=8, max_wait_ms=1.0)
    try:
        svc = ProofService(verifier=verifier)
        good = svc.serve(1, b"\x01" * 32,
                         lambda: _verdict_artifact(b"\x05" * 96))
        assert good.verified is True
        bad = svc.serve(2, b"\x02" * 32,
                        lambda: _verdict_artifact(BAD_SIGNATURE))
        assert bad.verified is False
        snap = svc.snapshot()
        assert snap["updates_verified"] == 1
        assert snap["verify_failures"] == 1
        assert backend.calls >= 1  # the verdicts actually flowed through
    finally:
        verifier.close(timeout=30)


def test_proof_service_without_verifier_leaves_verdict_unset():
    svc = ProofService()
    art = svc.serve(3, b"\x03" * 32, lambda: _verdict_artifact(b"\x05" * 96))
    assert art.verified is None


# -- the artifact itself (real sync-committee crypto) ------------------------


def test_world_artifact_verifies_end_to_end(spec, world):
    """The one full-stack check: validate_light_client_update (branches,
    period math, REAL FastAggregateVerify over the sum-sk signature) plus
    the external-root branch checks — against an independently
    re-Merkleized root from a fresh deserialization."""
    slot = world.finalized_slot + 3
    artifact = world.build_artifact(slot)
    assert artifact.finality_gindex == FINALIZED_ROOT_GINDEX
    assert artifact.sync_gindex == NEXT_SYNC_COMMITTEE_GINDEX
    assert len(artifact.participant_pubkeys) == \
        int(spec.SYNC_COMMITTEE_SIZE)
    state = world.head_state(slot)
    fresh = spec.BeaconState.decode_bytes(state.encode_bytes())
    verify_artifact(spec, artifact, world.snapshot,
                    world.genesis_validators_root,
                    state_root=bytes(fresh.hash_tree_root()))


def test_tampered_artifact_fails_verification(spec, world):
    slot = world.finalized_slot + 4
    # a flipped finality-branch byte: the spec validate rejects it
    artifact = world.build_artifact(slot)
    artifact.finality_branch[0] = bytes(
        [artifact.finality_branch[0][0] ^ 1]) + artifact.finality_branch[0][1:]
    artifact.update.finality_branch = [
        spec.Bytes32(b) for b in artifact.finality_branch]
    with pytest.raises(AssertionError):
        verify_artifact(spec, artifact, world.snapshot,
                        world.genesis_validators_root)
    # a corrupted signature: branches fine, FastAggregateVerify False
    artifact = world.build_artifact(slot)
    sig = bytes(artifact.update.sync_committee_signature)
    artifact.update.sync_committee_signature = spec.BLSSignature(
        sig[:-1] + bytes([sig[-1] ^ 1]))
    with pytest.raises(AssertionError):
        verify_artifact(spec, artifact, world.snapshot,
                        world.genesis_validators_root)


def test_unsigned_artifact_branches_still_verify(spec, world):
    """signed=False: the branch/multiproof layer is independent of the
    signature layer (and crypto-free)."""
    from consensus_specs_tpu.lightclient.proof_tree import (
        floorlog2, subtree_index,
    )
    from consensus_specs_tpu.utils.ssz.proofs import verify_merkle_multiproof

    slot = world.finalized_slot + 5
    artifact = world.build_artifact(slot, signed=False)
    assert artifact.participant_pubkeys == []
    g = artifact.finality_gindex
    assert spec.is_valid_merkle_branch(
        spec.Root(artifact.finalized_root),
        [spec.Bytes32(b) for b in artifact.finality_branch],
        floorlog2(g), subtree_index(g),
        spec.Root(artifact.state_root))
    assert verify_merkle_multiproof(
        artifact.multi_leaves, artifact.multi_proof,
        artifact.multi_gindices, artifact.state_root)


# -- the phase0/simnet head-proof shape --------------------------------------


def test_head_proof_round_trip_and_tamper(spec, world):
    state = world.head_state(world.finalized_slot + 6)
    root = bytes(state.hash_tree_root())
    artifact = build_head_proof(spec, state)
    assert artifact.update is None  # phase0 shape: branch only
    verify_head_proof(spec, artifact, root)
    with pytest.raises(AssertionError):
        verify_head_proof(spec, artifact, b"\x99" * 32)
    artifact.finalized_root = b"\x99" * 32
    with pytest.raises(AssertionError):
        verify_head_proof(spec, artifact, root)


# -- the simnet light_client node kind ---------------------------------------


class _StubServer:
    """serve_head_proof()-shaped server for LightClientNode unit tests."""

    def __init__(self, name, response):
        self.name = name
        self.response = response

    def serve_head_proof(self):
        return dict(self.response)


def _head_response(spec, world, slot, node="n0"):
    state = world.head_state(slot)
    block = spec.BeaconBlock(slot=spec.Slot(slot))
    return {
        "state": state,
        "node": node,
        "head_root": bytes(spec.hash_tree_root(block)),
        "head_slot": slot,
        "block": block,
        "artifact": build_head_proof(spec, state),
    }


def test_light_client_node_accepts_rejects_and_staleness(spec, world):
    from consensus_specs_tpu.sim.node import LightClientNode

    fresh = _head_response(spec, world, world.finalized_slot + 8)
    client = LightClientNode(0, spec, fresh["state"])

    assert client.fetch(_StubServer("n0", fresh))
    assert client.verified == 1 and client.head_slot == \
        world.finalized_slot + 8

    # a server whose proof commits to a DIFFERENT state root: rejected
    other_state = world.head_state(world.finalized_slot + 9)
    lying = dict(_head_response(spec, world, world.finalized_slot + 9))
    lying["artifact"] = build_head_proof(spec, other_state)
    assert not client.fetch(_StubServer("n1", lying))
    assert client.failures == 1

    # a served head root that does not re-hash to the served block
    forged = dict(fresh)
    forged["head_root"] = b"\x55" * 32
    assert not client.fetch(_StubServer("n2", forged))
    assert client.failures == 2

    # a lagging node's stale (older-slot) proof: rejected, NOT a failure
    stale = dict(fresh)
    stale["head_slot"] = client.head_slot - 1
    stale["block"] = spec.BeaconBlock(slot=spec.Slot(client.head_slot - 1))
    stale["head_root"] = bytes(spec.hash_tree_root(stale["block"]))
    stale["artifact"] = fresh["artifact"]
    assert not client.fetch(_StubServer("n3", stale))
    assert client.rejected_stale == 1 and client.failures == 2
    assert client.head_slot == world.finalized_slot + 8  # unchanged

    snap = client.snapshot()
    assert snap["fetches"] == 4 and snap["verified"] == 1
    # the rejects landed in the client's own flight journal
    kinds = [e["kind"] for e in client.recorder.events()]
    assert kinds.count("proof_accept") == 1
    assert kinds.count("proof_reject") == 2
    assert kinds.count("proof_stale") == 1


def test_scenario_report_carries_light_client_evidence():
    """One strict scenario run with the default 2 light clients: the
    report's proof plane fields are populated and every client converged
    to the agreed head (the gate would have raised otherwise)."""
    from consensus_specs_tpu.sim import build_world, get_scenario, \
        run_scenario

    spec, anchor_state, anchor_block = build_world()
    report = run_scenario(
        get_scenario("partition_heal"), spec=spec,
        anchor_state=anchor_state, anchor_block=anchor_block, seed=7,
        strict=True)
    assert report.converged
    assert report.light_clients == 2
    assert set(report.per_client) == {"c0", "c1"}
    assert report.proofs_served >= report.light_clients
    assert report.proofs_verified > 0 and report.proof_failures == 0
    assert 0.0 <= report.proof_cache_hit_rate <= 1.0
    heads = {c["head"] for c in report.per_client.values()}
    assert len(heads) == 1  # both clients at the one agreed head
    for snap in report.per_client.values():
        assert snap["verified"] > 0 and snap["failures"] == 0
    # the dict form ships per_client for the matrix report
    assert report.to_dict()["per_client"] == report.per_client
