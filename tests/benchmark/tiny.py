"""Tiny cells and stand-in backends for the benchmark's CPU tests.

The cells are the committed ones, cut to a few keys per committee so a
test run can hold them; the stand-ins take the program's place behind the
same entry points (``SignatureCollector.flush`` and
``VerificationService``)."""
import os
import time

from benchmark import cells, generate

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def driver(name: str):
    """The module ``benchmark/drivers/<name>.py``, as the harness loads it."""
    return cells._module(ROOT, "drivers", name)


def replay_cell():
    cell = cells.load(ROOT, "block300k.replay")
    cell.config.update(active_validators=512, target_committee_size=4,
                       sync_committee_size=8)
    cell.mix.update(bad_checks=[[0, 3], [1, 5]], max_blocks_per_s=30.0,
                    warm_bad_checks=[0], reference_blocks=0)
    return cell


def gossip_cell():
    cell = cells.load(ROOT, "gossip1m.serve")
    cell.config.update(active_validators=2048, target_committee_size=16,
                       aggregators_per_committee=4)
    cell.mix.update(rate_per_s=8.0, reference_units=0, drain_seconds=3)
    return cell


class Truth:
    """Records the generator's truth for every check it signs, so a
    stand-in can answer without the crypto."""

    def __init__(self, monkeypatch):
        self.table = {}
        real = generate.Keys.check

        def check(keys, members, message, signature, truth):
            self.table[signature] = truth
            return real(keys, members, message, signature, truth)

        monkeypatch.setattr(generate.Keys, "check", check)


class TruthBackend:
    """A sound stand-in (``fault=None``) or one with the timed path broken:
    ``"flip"`` alters the first answer of every batch where it is made;
    ``"half"`` leaves the second half of every batch out. Each batch
    takes ``delay`` seconds, so a window of one second sees some tens."""

    def __init__(self, truth: Truth, fault=None, delay=0.05):
        self.truth = truth
        self.fault = fault
        self.delay = delay

    def batch_verify_rlc(self, items, mesh=None, rng=None):
        time.sleep(self.delay)
        got = [self.truth.table[sig] for _k, _p, _m, sig in items]
        if self.fault == "flip":
            got[0] = not got[0]
        elif self.fault == "half":
            got = got[:len(got) // 2]
        return got
