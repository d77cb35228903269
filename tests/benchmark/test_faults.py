"""``correct`` comes out true for a sound stand-in and false with the timed
path broken underneath: an answer altered where it is made, half of each
batch left out, and the control (the reference without bisection)."""
import pytest

from benchmark import judge, run
from tests.benchmark import tiny

SEED = 2**31 + 77
CELLS = {"replay": tiny.replay_cell, "gossip": tiny.gossip_cell}


def _run(cell, backend):
    result, info = run.execute(cell, SEED, 1.0, False, backend=backend,
                               require_tpu=False, procs=2)
    return result, info


@pytest.mark.parametrize("driver", sorted(CELLS))
@pytest.mark.parametrize("fault", [None, "flip", "half"])
def test_fault_is_caught(monkeypatch, driver, fault):
    truth = tiny.Truth(monkeypatch)
    result, info = _run(CELLS[driver](), tiny.TruthBackend(truth, fault))
    compared = {k: v["value"] for k, v in result["compared"].items()}
    if fault is None:
        assert result["correct"], (compared, info["errors"])
        assert result["attempted"] > 0
    else:
        assert not result["correct"], compared
        assert compared["mismatch_truth"] + compared["missing"] > 0
    assert list(result)[-1] == "compared"


@pytest.mark.parametrize("driver", sorted(CELLS))
def test_control_fails(driver):
    cell = CELLS[driver]()
    cell.mix["drain_seconds"] = 60  # the reference is slow: wait for it
    result, _ = _run(cell, judge.ReferenceBackend(bisect=False))
    assert not result["correct"]
    assert result["compared"]["mismatch_reference"]["value"] > 0
