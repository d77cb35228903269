"""The per-layer readers of the program's flush records, on hand-built
rings: each reads the window's records only (never a warm-up flush before
them) and gives None when the ring does not cover the window."""
import pytest

from benchmark import cells
from consensus_specs_tpu.obs import tracing
from tests.benchmark import tiny


@pytest.fixture
def ring(monkeypatch):
    ring = tracing.FlushRecords(capacity=64)
    monkeypatch.setattr(tracing, "_FLUSHES", ring)
    return ring


def _reader(cell, name):
    return cells.load(tiny.ROOT, cell).reader(name)


def _rlc(rid, items, prep=0.0, combine=0.0, final_exp=0.0):
    spans = {"rlc.flush": prep + combine + final_exp, "rlc.prep": prep,
             "rlc.final_exp": final_exp}
    if combine:
        spans["rlc.combine"] = combine
    return {"kind": "rlc", "id": rid, "items": items, "seconds": 1.0,
            "spans": spans, "combines": 1, "bisections": 0,
            "final_exps": 1, "first": {}}


def _serve(rid, items, rlc, queue=0.0, handoff=0.0):
    return {"kind": "serve", "id": rid, "items": items, "prep_s": 0.05,
            "device_s": 0.5, "rlc": rlc, "queue_wait_s": queue,
            "handoff_wait_s": handoff}


def _block_ring(ring):
    # a warm-up block (bisecting, slow) and then three window blocks
    ring.append(_rlc(1, 67, prep=9.0, combine=9.0, final_exp=9.0))
    ring.append(_rlc(2, 67, prep=0.5, combine=0.1, final_exp=0.2))
    ring.append(_rlc(3, 67, prep=0.7, combine=0.3, final_exp=0.4))
    ring.append(_rlc(4, 67, prep=0.6, final_exp=0.2))


def _serve_ring(ring):
    # a warm-up flush and then two window flushes (3 and 5 checks)
    ring.append(_rlc(1, 4, combine=5.0, final_exp=5.0))
    ring.append(_serve(2, 4, rlc=1, queue=9.0, handoff=9.0))
    ring.append(_rlc(3, 3, combine=0.1, final_exp=0.2))
    ring.append(_serve(4, 3, rlc=3, queue=0.3, handoff=0.3))
    ring.append(_rlc(5, 5, combine=0.2, final_exp=0.1))
    ring.append(_serve(6, 5, rlc=5, queue=0.5, handoff=0.5))


def test_block_prep_reads_the_window_blocks_only(ring):
    read = _reader("block300k.replay", "rlc.prep_ms_per_flush.block")
    assert read({"checks": 201}) is None  # empty ring
    _block_ring(ring)
    assert read({"checks": 201}) == pytest.approx(600.0)
    assert read({"checks": 134}) == pytest.approx(650.0)
    assert read({"checks": 5 * 67}) is None  # the ring is short
    assert read({"checks": 200}) is None  # the counts do not land


def test_block_finalize_reads_the_window_blocks_only(ring):
    read = _reader("block300k.replay", "rlc.finalize_ms_per_flush.block")
    _block_ring(ring)
    assert read({"checks": 201}) == pytest.approx(400.0)
    assert read({"checks": 67}) == pytest.approx(200.0)
    assert read({"checks": 4 * 67 + 1}) is None
    assert read({"checks": 0}) is None


def test_serve_wait_reads_the_window_flushes_only(ring):
    read = _reader("gossip1m.serve", "serve.wait_ms_per_check")
    _serve_ring(ring)
    ctx = {"serve": {"device_flushes": 2, "enqueued": 8}}
    assert read(ctx) == pytest.approx(1e3 * 1.6 / 8)
    assert read({"serve": {"device_flushes": 3, "enqueued": 12}}) == (
        pytest.approx(1e3 * 19.6 / 12))
    # the flush counted in the window is not the one the ring holds last
    assert read({"serve": {"device_flushes": 2, "enqueued": 9}}) is None
    assert read({"serve": {"device_flushes": 4, "enqueued": 12}}) is None
    assert read({"serve": None}) is None


def test_serve_finalize_reads_the_rlc_records_the_window_names(ring):
    read = _reader("gossip1m.serve", "rlc.finalize_ms_per_flush.serve")
    _serve_ring(ring)
    ctx = {"serve": {"device_flushes": 2, "enqueued": 8}}
    assert read(ctx) == pytest.approx(300.0)
    assert read({"serve": {"device_flushes": 1, "enqueued": 5}}) == (
        pytest.approx(300.0))
    # a named rlc record the ring no longer holds
    ring.append(_serve(7, 2, rlc=99))
    assert read({"serve": {"device_flushes": 1, "enqueued": 2}}) is None
    assert read({"serve": {"device_flushes": 9, "enqueued": 14}}) is None


@pytest.mark.parametrize("cell,name,ctx", [
    ("block300k.replay", "rlc.prep_ms_per_flush.block", {"checks": 67}),
    ("block300k.replay", "rlc.finalize_ms_per_flush.block", {"checks": 67}),
    ("gossip1m.serve", "serve.wait_ms_per_check",
     {"serve": {"device_flushes": 1, "enqueued": 3}}),
    ("gossip1m.serve", "rlc.finalize_ms_per_flush.serve",
     {"serve": {"device_flushes": 1, "enqueued": 3}}),
])
def test_reader_gives_none_for_a_program_without_records(monkeypatch, cell,
                                                         name, ctx):
    monkeypatch.delattr(tracing, "flush_records")
    assert _reader(cell, name)(ctx) is None
