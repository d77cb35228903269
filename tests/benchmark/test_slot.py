"""The ``slot`` loop of ``slot1m.fold`` at a small registry: its inputs
are a function of the seed, its committees cover the registry once an
epoch, ``correct`` comes out true for a sound stand-in and false for the
program with its key gather broken by one lane, and the readers of its
three per-layer metrics read the program's records and trace."""
import multiprocessing as mp
import os

import numpy as np
import pytest

from benchmark import cells, generate, registry_keys, roofline, run
from benchmark.reference import bls as ref
from tests.benchmark import tiny

SEED = 2**31 + 91
slot = tiny.driver("slot")


def slot_cell(validators=2048, committee=8):
    """``slot1m.fold`` cut to ``validators`` keys and committees of
    ``committee`` (eight a slot)."""
    cell = cells.load(tiny.ROOT, "slot1m.fold")
    cell.config.update(active_validators=validators,
                       target_committee_size=committee)
    cell.mix.update(max_slots_per_s=30.0, reference_slots=0)
    return cell


class TableTruth(tiny.TruthBackend):
    """The generator's truth, behind the index path's call."""

    def batch_verify_rlc(self, items, mesh=None, rng=None, table=None):
        assert table is not None and table.n > 0
        assert {kind for kind, *_ in items} == {"fast_aggregate_indexed"}
        return super().batch_verify_rlc(items, mesh, rng)


@pytest.fixture(scope="module")
def workers():
    with mp.get_context("spawn").Pool(2) as p:
        yield p


def test_derive_all_equals_per_key_derivation(workers):
    keys = generate.Keys(SEED)
    registry_keys.derive_all(keys, 700, workers, chunk=256)
    for i in (0, 1, 255, 256, 257, 511, 512, 699):
        want = ref.pubkey(((i + 1) << 16) | keys.salt)
        assert keys.points[i] + (keys.encoded[i],) == want, i
    assert len(keys.encoded) == 700


def test_committees_cover_the_registry_once_an_epoch():
    cfg = slot_cell().config
    layout = slot.Committees(cfg, SEED)
    assert layout.count == 8
    epoch = [c for s in range(64, 96) for c in layout.at(s)]
    assert all(len(c) == 8 for c in epoch)
    assert sorted(np.concatenate(epoch).tolist()) == list(range(2048))
    # another epoch deals other committees
    assert not np.array_equal(layout.at(96)[0], layout.at(64)[0])


def test_same_seed_same_slots_and_the_bad_check_where_the_mix_says(workers):
    cell = slot_cell(validators=512, committee=2)
    keys = generate.Keys(SEED)
    registry_keys.derive_all(keys, 512, workers)
    layout = slot.Committees(cell.config, SEED)

    def bank(seed):
        return slot.slots(cell.mix, seed, keys, layout, range(62, 66),
                          {1: 3}, workers)

    a, b = bank(SEED), bank(SEED)
    assert [[(c.members, c.signature) for c in s] for s in a] == [
        [(c.members, c.signature) for c in s] for s in b]
    assert [[c.truth for c in s] for s in a] == [
        [True] * 8, [True] * 3 + [False] + [True] * 4, [True] * 8, [True] * 8]
    assert all(len(c.members) == 2 for s in a for c in s)  # 90-100% of 2
    # slots 62 and 63 share epoch 1 and so no validator
    assert {m for c in a[0] for m in c.members}.isdisjoint(
        {m for c in a[1] for m in c.members})


def test_sound_stand_in_is_correct(monkeypatch):
    truth = tiny.Truth(monkeypatch)
    result, info = run.execute(slot_cell(), SEED, 1.0, False,
                               backend=TableTruth(truth), require_tpu=False,
                               procs=2)
    compared = {k: v["value"] for k, v in result["compared"].items()}
    assert result["correct"], (compared, info["errors"])
    assert result["attempted"] == 8 * info["slots"] > 0
    assert set(result["metrics"]) == {"sigs_per_s", "setup_s"}
    assert info["pubkey_table"] == {"keys": 2048, "bytes": 65536 * 120,
                                    "valid": 2048}
    assert "table" in info["setup_phases"]


def test_flipped_answer_is_caught(monkeypatch):
    truth = tiny.Truth(monkeypatch)
    result, _ = run.execute(slot_cell(), SEED, 1.0, False,
                            backend=TableTruth(truth, fault="flip"),
                            require_tpu=False, procs=2)
    assert not result["correct"]
    assert result["compared"]["mismatch_truth"]["value"] > 0


def test_gather_broken_by_one_lane_is_caught(monkeypatch):
    from consensus_specs_tpu.ops import bls_backend

    real = bls_backend._gather_keys

    def one_lane_off(table, idx):
        idx = idx.copy()
        idx[0, 0, 0] = (idx[0, 0, 0] + 1) % 512  # another validator's key
        return real(table, idx)

    monkeypatch.setattr(bls_backend, "_gather_keys", one_lane_off)
    cell = slot_cell(validators=512, committee=2)
    cell.mix.update(warm_bad_checks=[None], bad_checks=[[64, 0]])
    result, info = run.execute(cell, SEED, 0.1, False, require_tpu=False,
                               procs=2)
    assert not result["correct"]
    assert result["compared"]["mismatch_truth"]["value"] >= 1
    assert not info["errors"]


def _records(monkeypatch, recs):
    from benchmark import records

    monkeypatch.setattr(records, "_records", lambda: recs)


def _rlc(items, gather_s, gathered, decodes):
    return {"kind": "rlc", "items": items, "seconds": 1.0,
            "spans": {"pubkeys.gather": gather_s, "rlc.prep": 0.05},
            "keys_gathered": gathered, "host_key_decodes": decodes}


def test_readers_take_the_windows_records_and_trace(monkeypatch):
    cell = slot_cell()
    warm = _rlc(64, 9.0, 30000, 500)  # set-up: never folded in
    window = [_rlc(64, 0.002, 31000, 0), _rlc(64, 0.004, 31500, 0)]
    _records(monkeypatch, [warm] + window)
    ctx = {"checks": 128, "slots_traced": 1, "device_kind": "TPU v5 lite",
           "gather_trace": {"device_s": 2e-5, "executions": 1}}
    read = {m["name"]: cell.reader(m["name"]) for m in cell.per_layer}
    assert set(read) == {"pubkeys.gather_ms_per_slot",
                         "pubkeys.host_decodes_per_slot",
                         "pubkeys.gather_hbm_share.slot"}
    assert read["pubkeys.gather_ms_per_slot"](ctx) == pytest.approx(3.0)
    assert read["pubkeys.host_decodes_per_slot"](ctx) == 0
    share = read["pubkeys.gather_hbm_share.slot"](ctx)
    assert share == pytest.approx(100 * 31000 * 244 / 2e-5 / 819e9)
    # a parent without the counters, a count that does not land, or a
    # trace whose gathers do not match the traced slots read nothing
    _records(monkeypatch, [{k: v for k, v in r.items()
                            if k not in ("keys_gathered", "host_key_decodes")}
                           for r in window])
    assert read["pubkeys.host_decodes_per_slot"](ctx) is None
    assert read["pubkeys.gather_hbm_share.slot"](ctx) is None
    _records(monkeypatch, window)
    assert read["pubkeys.gather_ms_per_slot"](dict(ctx, checks=100)) is None
    assert read["pubkeys.gather_hbm_share.slot"](
        dict(ctx, gather_trace={"device_s": 2e-5, "executions": 2})) is None
    assert read["pubkeys.gather_hbm_share.slot"](
        dict(ctx, gather_trace=None)) is None
    with pytest.raises(KeyError):
        read["pubkeys.gather_hbm_share.slot"](dict(ctx, device_kind="cpu"))


def test_module_device_time_from_a_recorded_trace():
    data = os.path.join(os.path.dirname(__file__), "data")
    got = roofline.module_device_s(data, "jit_step")
    assert got["executions"] == 3
    assert 0.012 < got["device_s"] < 0.018  # as trace.reduce's busy time
    assert roofline.module_device_s(data, "jit_pubkey_gather") is None
    assert roofline.gather_bytes(10) == 2440
