"""``gossip``: an open loop of ``SignedAggregateAndProof`` units submitted
on a Poisson schedule into one ``VerificationService`` with its default
settings, each unit timed from when it was due. Set-up warms the service
by serving it a rehearsal of the window: the same schedule, the same
invalid positions, other messages. The shapes the service makes depend on
how its batches fill and bisect, so only the window's own traffic meets
them all; ten seconds of it left 5-7 programs to load inside every window
(PERF.md)."""
import concurrent.futures as cf
import functools
import time
from typing import List

import numpy as np

from benchmark import generate as gen
from benchmark.loop import Loop
from benchmark.stats import nearest_rank
from benchmark.trace import Tracer


class Unit:
    """One ``SignedAggregateAndProof``: the aggregate, the selection proof
    and the aggregator's signature, and when it is due (seconds from the
    start of its stretch of traffic)."""

    __slots__ = ("checks", "due")

    def __init__(self, checks, due):
        self.checks = checks
        self.due = due


def units(cfg: dict, mix: dict, seed: int, keys: gen.Keys,
          layout: gen.SlotLayout, slot0: int, n: int, bad, pool) -> List[Unit]:
    """``n`` units in mainnet order: committee by committee, each
    committee's aggregators in turn, all sharing that committee's
    attestation message. Participation is a fixed spread over the mix's
    range, dealt to units by the seed; the units at the positions in
    ``bad`` carry an aggregate signed over another message."""
    per = int(cfg["aggregators_per_committee"])
    lo, hi = mix["participation"]
    rng = gen.rng_for(seed, b"gossip%d" % slot0)
    shares = rng.permutation(np.array([hi - (hi - lo) * (j + 0.5) / n
                                       for j in range(n)]))
    bad = set(bad)
    specs = []
    for i in range(n):
        slot = slot0 + i // (per * layout.count)
        c = (i // per) % layout.count
        members = layout.committees[c]
        k = min(len(members), int(round(float(shares[i]) * len(members))))
        drop = set(rng.choice(len(members), size=len(members) - k,
                              replace=False).tolist())
        subset = [m for j, m in enumerate(members) if j not in drop]
        aggregator = [members[(i % per) * (len(members) // per)]]
        a_sk = keys.aggregate_sk(aggregator)
        att = gen.root(b"attestation", seed, slot, c)
        specs.append([
            (subset, att, keys.aggregate_sk(subset), i in bad),
            (aggregator, gen.root(b"selection", seed, slot), a_sk, False),
            (aggregator, gen.root(b"aggregate_and_proof", seed, slot0, i), a_sk,
             False),
        ])
    pairs = [(sk, msg + b"!" if wrong else msg)
             for unit in specs for (_, msg, sk, wrong) in unit]
    sigs = iter(gen.sign_all(pool, pairs))
    return [Unit([keys.check(m, msg, next(sigs), not wrong)
                  for (m, msg, _, wrong) in unit], None) for unit in specs]


def _done(done_at, got, i, j, fut):
    done_at[i][j] = time.perf_counter()
    try:
        got[i][j] = bool(fut.result())
    except Exception as e:
        got[i][j] = e


class Driver(Loop):
    def _traffic(self, seconds: float, slot0: int, workers) -> List[Unit]:
        """``seconds`` of the mix's traffic: its rate, its share of invalid
        units spread evenly (one at least), its fixed arrival schedule."""
        mix = self.mix
        rate = float(mix["rate_per_s"])
        n = max(1, round(rate * seconds))
        bad = gen.spread(n, max(1, round(float(mix["bad_share"]) * n)))
        out = units(self.cfg, mix, self.run.seed, self.keys, self.layout,
                    slot0, n, bad, workers)
        for u, due in zip(out, gen.arrivals(n, rate, seconds,
                                            int(mix["schedule_order"]))):
            u.due = due
        return out

    def setup(self, workers, phase):
        run, mix = self.run, self.mix
        n = max(1, round(float(mix["rate_per_s"]) * run.seconds))
        per = int(self.cfg["aggregators_per_committee"])
        with phase("keys"):
            self.keys = gen.Keys(run.seed)
            self.layout = gen.SlotLayout(self.cfg, run.seed)
            touched = min(self.layout.count, -(-n // per))
            self.keys.derive([i for c in self.layout.committees[:touched]
                              for i in c], workers)
            run.keys_ready(self.keys, workers)
        slot0 = 64 + int(gen.rng_for(run.seed, b"slot").integers(1 << 20))
        with phase("bank"):
            self.units = self._traffic(run.seconds, slot0, workers)
            rehearsal = self._traffic(run.seconds, slot0 - 1, workers)
        with phase("pubkeys"):
            prewarm = getattr(run.program_backend, "prewarm_host_caches", None)
            if prewarm is not None:
                prewarm([], [], [self.keys.encoded[i] for i in
                                 sorted(self.keys.encoded)])
        from consensus_specs_tpu.serve.service import VerificationService

        with phase("warm"):
            self.svc = VerificationService(backend=run.backend)
            _, done_at, _, _, _ = self._serve(rehearsal, run.seconds,
                                              Tracer(False, 0.0, None))
            if any(t is None for ends in done_at for t in ends):
                run.note_error(RuntimeError("warm-up verdicts never came"))
            self.snap0 = self.svc.metrics.snapshot()

    def _serve(self, todo, seconds, tracer):
        """Submits each unit's checks at its due time, starts the trace for
        the stretch's last ``tracer.seconds``, waits for the verdicts, and
        returns (start, completion times, verdicts, lateness, give-up)."""
        run, svc = self.run, self.svc
        n = len(todo)
        done_at = [[None] * len(u.checks) for u in todo]
        got = [[None] * len(u.checks) for u in todo]
        futs = []
        late = []
        t0 = time.perf_counter() + 0.01
        trace_at = seconds - tracer.seconds
        for i, u in enumerate(todo):
            if not tracer.active and u.due >= trace_at:
                tracer.start()
            due = t0 + u.due
            with tracer.span("bench.arrival_wait"):
                delay = due - time.perf_counter()
                if delay > 0:
                    time.sleep(delay)
            late.append(time.perf_counter() - due)
            with tracer.span("bench.submit"):
                for j, c in enumerate(u.checks):
                    try:
                        f = svc.submit("fast_aggregate", c.pubkeys,
                                       c.message, c.signature)
                    except Exception as e:
                        run.note_error(e)
                        continue
                    f.add_done_callback(
                        functools.partial(_done, done_at, got, i, j))
                    futs.append(f)
        close = t0 + seconds
        with tracer.span("bench.arrival_wait"):
            time.sleep(max(0.0, close - time.perf_counter()))
        tracer.stop()
        # the drain runs from the close, or from the end of the trace's
        # write-out, which stalls this process in a traced run
        cf.wait(futs, timeout=max(close, time.perf_counter())
                - time.perf_counter() + float(self.mix["drain_seconds"]))
        return t0, done_at, got, late, time.perf_counter()

    def window(self, tracer):
        run, svc = self.run, self.svc
        t0, done_at, got, self.late, give_up = self._serve(
            self.units, run.seconds, tracer)
        self.snap1 = svc.metrics.snapshot()
        svc.close(timeout=5.0)
        self.window_s = run.seconds
        lat = []
        for i, u in enumerate(self.units):
            ends = done_at[i]
            ok = all(t is not None for t in ends) and all(
                isinstance(v, bool) for v in got[i])
            lat.append(((max(ends) if ok else give_up) - (t0 + u.due)) * 1e3)
        self.latency_ms = lat
        self.answers = [(c, v if isinstance(v, bool) else None)
                        for u, g in zip(self.units, got)
                        for c, v in zip(u.checks, g)]
        return {"aggregate_p50_ms": nearest_rank(lat, 50)}

    def serve_delta(self):
        keys = ("enqueued", "device_flushes", "prep_batches", "prep_ms_total",
                "fallback_items", "backend_retries", "mesh_fallbacks")
        return {k: self.snap1[k] - self.snap0[k] for k in keys}

    def sample(self):
        """Every invalid unit and ``reference_units`` more, from the seed."""
        bad = [i for i, u in enumerate(self.units) if not u.checks[0].truth]
        rest = [i for i in range(len(self.units)) if i not in bad]
        rng = gen.rng_for(self.run.seed, b"sample")
        k = min(len(rest), int(self.mix["reference_units"]))
        pick = sorted(bad + rng.choice(rest, size=k, replace=False).tolist())
        return [c for i in pick for c in self.units[i].checks]

    def context(self):
        return {"serve": self.serve_delta()}

    def fallbacks(self):
        s = self.serve_delta()
        return {"serve." + k: s[k] for k in
                ("fallback_items", "backend_retries", "mesh_fallbacks")}

    def info(self):
        """The 95th percentile latency (no bound: it swings with how the
        invalid units' bisections fill the top 5%, PERF.md), the service's
        counters, how late the generator ran, and the backlog trend: the median latency of the window's second half less
        its first's (a backlog that grows through the window shows here;
        each half holds as many invalid units, give or take one)."""
        lat = self.latency_ms
        h = len(lat) // 2
        late = sorted(self.late)
        return {"aggregate_p95_ms": nearest_rank(lat, 95),
                "serve": self.serve_delta(),
                "generator_late_ms": {"median": late[len(late) // 2] * 1e3,
                                      "max": late[-1] * 1e3},
                "latency_trend_ms": (nearest_rank(lat[h:], 50)
                                     - nearest_rank(lat[:h], 50))
                if h else 0.0,
                "first_half_p50_ms": nearest_rank(lat[:h], 50) if h else None}
