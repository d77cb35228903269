"""The two loops' arithmetic: the closed loop counts the block in flight
when the window ran out, and the open loop times every unit from when it
was due, so a stall counts against the units that waited behind it."""
import time

from benchmark import generate
from benchmark.trace import Tracer
from consensus_specs_tpu.serve.service import VerificationService
from tests.benchmark import tiny

replay = tiny.driver("replay")
gossip = tiny.driver("gossip")


class _Run:
    def __init__(self, cell, seconds, backend):
        self.cell = cell
        self.seconds = seconds
        self.backend = backend
        self.seed = 1
        self.errors = []

    def note_error(self, e):
        self.errors.append(repr(e))


class _Cell:
    def __init__(self, mix):
        self.mix = mix
        self.config = {}


def _check(k, tag):
    pks = [bytes([i]) * 48 for i in range(k)]
    return generate.Check(tuple(range(k)), pks, b"m" * 31 + tag, b"s" * 95 + tag,
                          True)


class _Slow:
    def __init__(self, seconds):
        self.seconds = seconds
        self.ends = []

    def batch_verify_rlc(self, items, mesh=None, rng=None):
        time.sleep(self.seconds)
        self.ends.append(time.perf_counter())
        return [True] * len(items)


def test_closed_loop_rate_counts_the_block_in_flight():
    slow = _Slow(0.3)
    d = replay.Driver(_Run(_Cell({}), 1.0, slow))
    d.blocks = [[_check(5, bytes([b])), _check(1, bytes([b]))]
                for b in range(10)]
    got = d.window(Tracer(False, 0, None))
    # the window ran to the end of the block in flight at 1 s
    t0 = slow.ends[-1] - d.window_s
    assert slow.ends[-2] - t0 < 1.0 <= d.window_s
    assert d.done == len(slow.ends) >= 3
    assert got["sigs_per_s"] == d.done * 6 / d.window_s
    assert len(d.answers) == 2 * d.done


class _StallOnce:
    def __init__(self, stall):
        self.stall = stall
        self.calls = 0

    def batch_verify_rlc(self, items, mesh=None, rng=None):
        self.calls += 1
        if self.calls == 1:
            time.sleep(self.stall)
        return [True] * len(items)


def test_open_loop_latency_counts_from_the_due_time():
    backend = _StallOnce(1.0)
    d = gossip.Driver(_Run(_Cell({"drain_seconds": 10}), 1.5, backend))
    d.units = [gossip.Unit([_check(3, bytes([i, j])) for j in range(3)],
                             0.1 * i) for i in range(12)]
    # a queue of three checks: during the stall submit() blocks, so the
    # generator runs late, and that wait must count in the latency
    d.svc = VerificationService(backend=backend, max_queue=3, max_wait_ms=1.0)
    d.snap0 = d.svc.metrics.snapshot()
    got = d.window(Tracer(False, 0, None))
    assert not d.run.errors
    assert all(v is True for _, v in d.answers)
    late = [x * 1e3 for x in d.late]
    assert max(late) > 300.0  # the generator was held up by the stall
    for lat, lag in zip(d.latency_ms, late):
        assert lat >= lag  # timed from the due time, not from submit()
    # the first unit waited out the whole stall
    assert d.latency_ms[0] >= 1000.0
    assert d.info()["aggregate_p95_ms"] >= got["aggregate_p50_ms"] > 0
