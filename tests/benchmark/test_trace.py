"""The reduction from a profiler trace to busy time, idle gaps and device
operations, on a small trace recorded on a v5e: three steps of a jitted
loop, each inside ``bench.verify``, with a 50 ms ``bench.generate`` sleep
after each, all inside ``bench.window``."""
import os

import pytest

from benchmark import trace

DATA = os.path.join(os.path.dirname(__file__), "data", "v5e_probe.xplane.pb")


@pytest.fixture(scope="module")
def summary():
    return trace.reduce(DATA)


def test_busy_time_is_the_union_of_device_ops(summary):
    # three ~5 ms loops: nested ops (the loop body inside %while) count once
    assert 0.012 < summary["busy_s"] < 0.018
    assert 0.15 < summary["window_s"] < 0.2
    idle = 1 - summary["busy_s"] / summary["window_s"]
    assert 0.85 < idle < 0.95


def test_gaps_are_named_by_the_host_span_open_during_them(summary):
    gaps = summary["idle_gaps"]
    assert len(gaps) <= 10
    assert [name for name, _ in gaps[:3]] == ["bench.generate"] * 3
    assert all(0.045 < s < 0.06 for _, s in gaps[:3])
    assert gaps == sorted(gaps, key=lambda g: -g[1])


def test_device_ops_are_named_by_module_and_instruction(summary):
    ops = summary["device_ops"]
    assert ops[0][0].startswith("jit_step(") and ops[0][0].endswith("/%while")
    assert all(" = " not in name for name, _ in ops)
    assert len(ops) <= 10


def test_union_merges_overlaps():
    assert trace._union([(5, 7), (0, 2), (1, 3), (6, 9)]) == [[0, 3], [5, 9]]
