"""What decides ``correct``: the program's verdicts against the plain
reference (``reference/bls.py``), run after the window in spawned
CPU workers over a sample drawn from the seed, and against the
generator's own ground truth over every answer due in the window.

``ReferenceBackend`` puts the reference in the program's place. With
``bisect=False`` it is the control: it decides a whole batch by one
combined verdict and gives that verdict to every item, the step that
would tempt a later PR (no bisection down to single items). The
configurations state the guarantee it breaks: exact per-item verdicts.
"""
import multiprocessing as mp
import os

from .reference import bls as ref

LIMITS = {  # every number compared is exact: a count that must be 0
    "mismatch_reference": 0,
    "mismatch_truth": 0,
    "missing": 0,
    "fallbacks": 0,
}


def pool(procs: int = None):
    """Spawned workers that import only the benchmark's pure-Python
    modules, so they never reach for the chip the parent holds."""
    n = procs or max(1, min(12, (os.cpu_count() or 2) - 1))
    return mp.get_context("spawn").Pool(n)


def _verify(task) -> bool:
    return ref.fast_aggregate_verify(*task)


def _task(keys, members, pubkeys, message, signature):
    return ([keys.points[i] for i in members], list(pubkeys), message,
            signature)


def reference_verdicts(workers, keys, checks):
    tasks = [_task(keys, c.members, c.pubkeys, c.message, c.signature)
             for c in checks]
    return workers.map(_verify, tasks, chunksize=1)


class ReferenceBackend:
    """The reference as a backend of the program's entry points
    (``SignatureCollector.flush`` and ``VerificationService``): the same
    ``batch_verify_rlc(items)`` call, answered by the reference over the
    generator's key points, which the run hands over through ``bind``."""

    def __init__(self, bisect: bool = True):
        self.bisect = bisect

    def bind(self, keys, workers) -> None:
        self.keys = keys
        self.workers = workers
        self.index = {enc: i for i, enc in keys.encoded.items()}

    def batch_verify_rlc(self, items, mesh=None, rng=None):
        tasks = [_task(self.keys, [self.index[pk] for pk in pks], pks, msg, sig)
                 for _kind, pks, msg, sig in items]
        got = self.workers.map(_verify, tasks, chunksize=1)
        if not self.bisect:
            got = [all(got)] * len(got)
        return got


def compare(answers, sample, reference):
    """answers: [(check, verdict or None)] for every check due in the
    window (None: no verdict came, or it raised); sample: the checks sent
    to the reference, with its verdicts in ``reference``."""
    got = {id(c): v for c, v in answers}
    return {
        "mismatch_reference": sum(got[id(c)] is not None and got[id(c)] != r
                                  for c, r in zip(sample, reference)),
        "mismatch_truth": sum(v is not None and v != c.truth
                              for c, v in answers),
        "missing": sum(v is None for _, v in answers),
    }
