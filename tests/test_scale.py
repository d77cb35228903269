"""Tier-1 coverage for the mainnet-scale workload plane (ISSUE 20):
registry determinism + spec-shuffle equivalence, lazy iteration memory
bounds, committee-affinity routing, and the hierarchical verify
path's accounting and its index path (keys gathered by validator index
from the pubkey table) against the bytes path and the oracle. Crypto is
kept to a handful of tiny keys so the whole module stays inside the
tier-1 budget; the pubkey table has its own module,
test_scale_pubkeys.py."""
import hashlib
import tracemalloc

import numpy as np
import pytest

from consensus_specs_tpu.obs import tracing
from consensus_specs_tpu.scale import hierarchy, pubkeys, registry, routing
from consensus_specs_tpu.scale.registry import Registry, shuffle_batch


# ---------------------------------------------------------------------------
# registry: determinism + spec equivalence
# ---------------------------------------------------------------------------


def test_registry_digest_is_seed_deterministic():
    a = Registry(24, seed=7).digest()
    b = Registry(24, seed=7).digest()
    c = Registry(24, seed=8).digest()
    assert a == b
    assert a != c
    # sampled digests are deterministic too (the form a 1M registry uses)
    assert (Registry(24, seed=7).digest(sample=5)
            == Registry(24, seed=7).digest(sample=5))


def test_registry_secret_keys_distinct_and_small():
    reg = Registry(1 << 20, seed=3)
    sks = {reg.secret_key(i) for i in (0, 1, 5, (1 << 20) - 1)}
    assert len(sks) == 4
    assert all(0 < sk < (1 << 40) for sk in sks)
    with pytest.raises(IndexError):
        reg.secret_key(1 << 20)


def test_shuffle_batch_matches_spec_minimal_and_mainnet():
    from consensus_specs_tpu.builder import build_spec_module

    seed = hashlib.sha256(b"scale-shuffle-equivalence").digest()
    for preset, n in (("minimal", 97), ("mainnet", 65)):
        spec = build_spec_module("phase0", preset)
        rounds = int(spec.SHUFFLE_ROUND_COUNT)
        mine = shuffle_batch(n, seed, rounds)
        ref = [int(spec.compute_shuffled_index(
            spec.uint64(i), spec.uint64(n), seed)) for i in range(n)]
        assert mine.tolist() == ref


def test_registry_committees_match_spec_compute_committee():
    from consensus_specs_tpu.builder import build_spec_module

    spec = build_spec_module("phase0", "mainnet")
    # pin the registry's baked-in mainnet constants against specsrc
    assert registry.SLOTS_PER_EPOCH == int(spec.SLOTS_PER_EPOCH)
    assert registry.MAX_COMMITTEES_PER_SLOT == int(
        spec.MAX_COMMITTEES_PER_SLOT)
    assert registry.TARGET_COMMITTEE_SIZE == int(spec.TARGET_COMMITTEE_SIZE)
    assert registry.SHUFFLE_ROUND_COUNT == int(spec.SHUFFLE_ROUND_COUNT)

    n, slot = 131, 5
    reg = Registry(n, seed=11)
    per_slot = reg.committees_per_slot()
    assert per_slot == 1  # below the target size floor
    seed = reg.attester_seed(slot // registry.SLOTS_PER_EPOCH)
    count = per_slot * registry.SLOTS_PER_EPOCH
    flat = (slot % registry.SLOTS_PER_EPOCH) * per_slot
    indices = [spec.uint64(i) for i in range(n)]
    ref = [int(v) for v in spec.compute_committee(
        indices, seed, spec.uint64(flat), spec.uint64(count))]
    assert reg.committee(slot, 0).tolist() == ref


def test_committee_fanout_covers_registry_once_per_epoch():
    reg = Registry(4096, seed=2, shuffle_rounds=4)
    seen = []
    for slot in range(registry.SLOTS_PER_EPOCH):
        for com in reg.committees_at_slot(slot):
            seen.extend(int(v) for v in com)
    assert sorted(seen) == list(range(4096))
    assert registry.attesters_per_slot(4096) == 128
    assert registry.committee_count_per_slot(1 << 20) == 64


def test_registry_lazy_iteration_is_memory_bounded():
    # a million-validator registry + one epoch permutation must stay
    # columnar: the uint64 column is 8 MB; the budget leaves headroom
    # for numpy temporaries but is far below any per-validator
    # materialization (1M Python ints alone would be ~28 MB+)
    tracemalloc.start()
    try:
        reg = Registry(1 << 20, seed=5, shuffle_rounds=2)
        com = reg.committee(0, 0)
        assert len(com) == (1 << 20) // (32 * 64)
        # streaming the index column in batches must not accumulate
        count = 0
        for idx, _pks in Registry(256, seed=5).iter_pubkeys(batch=64):
            count += len(idx)
        assert count == 256
        _, peak = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()
    assert peak < 64 * (1 << 20), f"peak {peak} bytes: not columnar"


# ---------------------------------------------------------------------------
# routing: committee affinity on the consistent-hash ring
# ---------------------------------------------------------------------------


class _FakeRouter:
    def __init__(self, labels):
        from consensus_specs_tpu.serve.fleet import HashRing
        import threading

        self._ring = HashRing()
        for lb in labels:
            self._ring.add(lb)
        self._lock = threading.Lock()
        self.requests = 0
        self.submitted = []

    def route_label(self, key):
        return self._ring.route(key)

    def handle(self, label):
        router = self

        class _H:
            def submit(self, kind, pks, msgs, sig, birth_s=None,
                       flow_id=None):
                from concurrent.futures import Future

                router.submitted.append(label)
                fut = Future()
                fut.set_result(True)
                return fut

        return _H()


def test_committee_affinity_is_stable_and_counts_moves():
    fake = _FakeRouter(["w0", "w1", "w2"])
    fleet = routing.CommitteeFleet(router=fake)
    first = fleet.assignment(range(32))
    # stable: resubmitting every committee lands the same worker
    for ci in range(32):
        fleet.submit_committee(ci, "fast_aggregate", [b"\x22" * 48],
                               b"m" * 32, b"\x11" * 96)
    assert fleet.assignment(range(32)) == first
    assert fleet.affinity_moves == 0
    assert fleet.committees_routed == 32
    assert len(set(first.values())) > 1  # committees actually spread

    # ring churn moves only the drained worker's committees
    fake._ring.remove("w1")
    moved = sum(1 for ci, lb in first.items()
                if fleet.label_for(ci) != lb)
    assert moved == sum(1 for lb in first.values() if lb == "w1")
    for ci in range(32):
        fleet.submit_committee(ci, "fast_aggregate", [b"\x22" * 48],
                               b"m" * 32, b"\x11" * 96)
    assert fleet.affinity_moves == moved


# ---------------------------------------------------------------------------
# hierarchy: slot fold accounting + bisection localization
# ---------------------------------------------------------------------------


def test_verify_slot_accounting_and_bad_committee_localization():
    reg = Registry(64, seed=13, slots_per_epoch=8, target_size=2,
                   shuffle_rounds=4)
    assert reg.committees_per_slot() == 4
    table = pubkeys.PubkeyTable.build(reg.pubkey_column())
    items = hierarchy.committee_items(reg, slot=3)
    assert {it[0] for it in items} == {"fast_aggregate_indexed"}
    bad_ci = 2
    items[bad_ci] = hierarchy.corrupt_item(items[bad_ci])

    report = hierarchy.verify_slot(items, slot=3, table=table)
    assert report.committees == 4
    assert report.attestations == sum(len(it[1]) for it in items)
    assert report.bad_committees == [bad_ci]
    assert report.bisections >= 1  # the slot root failed and split

    as_bytes = hierarchy.bytes_items(items, reg)
    flat = hierarchy.verify_slot_flat(as_bytes)
    oracle = hierarchy.verify_slot_oracle(as_bytes)
    assert report.verdicts.tolist() == flat.tolist() == oracle.tolist()

    # all-valid slot: ONE combine, ONE final exp, no bisection, and no
    # key decoded on the host
    good = hierarchy.committee_items(reg, slot=3)
    report2 = hierarchy.verify_slot(good, slot=3, table=table)
    assert report2.all_valid and not report2.bad_committees
    assert report2.combines == 1 and report2.bisections == 0
    assert report2.final_exps_per_slot == 1.0
    record = tracing.flush_records()[-1]
    assert record["host_key_decodes"] == 0
    assert record["keys_gathered"] == report2.attestations


@pytest.fixture(scope="module")
def indexed_slot():
    """Eight committees of four over a 256-validator registry in which
    one validator of another slot holds a key outside G1, and one slot's
    checks: valid, partial, corrupted, an index outside the table, an
    empty column, a committee that also covers the bad key, then two
    valid."""
    reg = Registry(256, seed=17, slots_per_epoch=8, target_size=4,
                   shuffle_rounds=4)
    items = hierarchy.committee_items(reg, slot=5)
    assert len(items) == 8
    bad = min(set(range(256)) - {int(i) for it in items for i in it[1]})
    keys = reg.pubkey_column()
    keys[bad] = _off_subgroup_key()
    table = pubkeys.PubkeyTable.build(keys)
    members, msg, sig = reg.aggregate_members(5, 1, participation=0.5)
    items[1] = ("fast_aggregate_indexed", members, msg, sig)
    items[2] = hierarchy.corrupt_item(items[2])
    kind, cols, msg, sig = items[3]
    items[3] = (kind, np.append(cols, 256), msg, sig)
    items[4] = (kind, np.zeros(0, dtype=np.uint64), items[4][2], items[4][3])
    kind, cols, msg, sig = items[5]
    items[5] = (kind, np.append(cols, bad), msg, sig)
    return reg, keys, table, items


def _off_subgroup_key():
    from consensus_specs_tpu.utils import bls12_381 as O

    x = 1
    while True:
        y = O.fq_sqrt((x ** 3 + 4) % O.P)
        if y is not None and not O.is_in_g1_subgroup(
                O.ec_from_affine((O.Fq(x), O.Fq(y)))):
            return O.g1_to_bytes((O.Fq(x), O.Fq(y)))
        x += 1


def test_index_path_equals_bytes_path_and_oracle(indexed_slot):
    from consensus_specs_tpu.ops import bls_backend

    reg, keys, table, items = indexed_slot
    report = hierarchy.verify_slot(items, slot=5, table=table)
    want = [True, True, False, False, False, False, True, True]
    assert report.verdicts.tolist() == want
    assert report.bad_committees == [2, 3, 4, 5]
    # the same checks over compressed keys (the index outside the table
    # has no key to give: it is left out of the bytes batch)
    as_bytes = [("fast_aggregate", [keys[int(i)] for i in cols], msg, sig)
                for _, cols, msg, sig in items[:3] + items[4:]]
    byte_path = bls_backend.batch_verify_rlc(as_bytes)
    oracle = hierarchy.verify_slot_oracle(as_bytes)
    assert byte_path.tolist() == oracle.tolist() == want[:3] + want[4:]


def test_index_path_traces_the_gather(indexed_slot):
    from consensus_specs_tpu.ops import bls_backend

    _, keys, table, items = indexed_slot
    good = [items[0], items[6], items[7]]
    bls_backend.batch_verify_rlc(good, table=table)
    record = tracing.flush_records()[-1]
    assert record["kind"] == "rlc" and record["items"] == 3
    assert record["spans"]["pubkeys.gather"] > 0
    assert record["spans"]["rlc.prep"] >= record["spans"]["pubkeys.gather"]
    assert record["keys_gathered"] == sum(len(it[1]) for it in good)
    assert record["host_key_decodes"] == 0
    # the bytes path counts its cold keys as host decodes
    cold = [("fast_aggregate", [keys[int(i)] for i in it[1]], it[2], it[3])
            for it in good]
    for _, pks, _, _ in cold:
        for pk in pks:
            bls_backend._PK_CACHE.pop(pk, None)
    bls_backend.batch_verify_rlc(cold)
    record = tracing.flush_records()[-1]
    assert record["host_key_decodes"] == record["items"] * 4
    assert record["keys_gathered"] == 0


def test_index_path_refuses_a_mesh_or_a_missing_table(indexed_slot):
    from consensus_specs_tpu.ops import bls_backend
    from consensus_specs_tpu.utils.jax_env import get_mesh

    _, _, table, items = indexed_slot
    with pytest.raises(ValueError, match="table="):
        bls_backend.batch_verify_rlc(items[:2])
    with pytest.raises(ValueError, match="one device"):
        bls_backend.batch_verify_rlc(items[:2], table=table,
                                     mesh=get_mesh("2"))
