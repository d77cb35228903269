"""``replay``: a closed loop of consecutive Altair blocks, each one
``SignatureCollector.flush(rlc=True)``; the next block is verified when
the last one's verdicts are in."""
import math
import statistics
import time
from typing import Dict, List, Sequence

from benchmark import generate as gen
from benchmark.loop import Loop


def blocks(cfg: dict, seed: int, keys: gen.Keys, layout: gen.SlotLayout,
           slots: Sequence[int], bad: Dict[int, int], pool) -> List[List[gen.Check]]:
    """One Altair block per slot, its checks in state-transition order:
    proposer signature, RANDAO reveal, one aggregate per committee (full
    participation), the sync aggregate. ``bad`` maps a block's position in
    ``slots`` to the check that is signed over another message."""
    specs = []  # (members, message, aggregate sk)
    for b, s in enumerate(slots):
        proposer = [layout.attesters[s % layout.per_slot]]
        p_sk = keys.aggregate_sk(proposer)
        block = [(proposer, gen.root(b"block", seed, s), p_sk),
                 (proposer, gen.root(b"randao", seed, s // gen.SLOTS_PER_EPOCH), p_sk)]
        for c, members in enumerate(layout.committees):
            block.append((members, gen.root(b"attestation", seed, s, c),
                          keys.aggregate_sk(members)))
        if len(layout.sync):
            block.append((layout.sync, gen.root(b"sync", seed, s),
                          keys.aggregate_sk(layout.sync)))
        specs.append(block)
    pairs = []
    for b, block in enumerate(specs):
        for j, (_, msg, sk) in enumerate(block):
            pairs.append((sk, msg + b"!" if bad.get(b) == j else msg))
    sigs = iter(gen.sign_all(pool, pairs))
    return [[keys.check(members, msg, next(sigs), bad.get(b) != j)
             for j, (members, msg, _) in enumerate(block)]
            for b, block in enumerate(specs)]


class Driver(Loop):
    def _flush(self, block):
        from consensus_specs_tpu.batch_verify import (CollectedCheck,
                                                      SignatureCollector)

        col = SignatureCollector()
        col.checks = [CollectedCheck("fast_aggregate", c.pubkeys, c.message,
                                     c.signature) for c in block]
        return col.flush(backend=self.run.backend, rlc=True)

    def setup(self, workers, phase):
        run, mix = self.run, self.mix
        with phase("keys"):
            self.keys = gen.Keys(run.seed)
            layout = gen.SlotLayout(self.cfg, run.seed)
            self.keys.derive(list(layout.attesters) + list(layout.sync),
                             workers)
            run.keys_ready(self.keys, workers)
        n = math.ceil(float(mix["max_blocks_per_s"]) * run.seconds) + 1
        rng = gen.rng_for(run.seed, b"replay")
        size = layout.count + 2 + (1 if len(layout.sync) else 0)
        bad = {b: j % size for b, j in mix["bad_checks"]}
        slot0 = gen.SLOTS_PER_EPOCH * (64 + int(rng.integers(1 << 20)))
        warm = mix["warm_bad_checks"]
        with phase("bank"):
            self.blocks = blocks(self.cfg, run.seed, self.keys, layout,
                                 range(slot0, slot0 + n), bad, workers)
            self.warm = blocks(
                self.cfg, run.seed, self.keys, layout,
                range(slot0 - len(warm), slot0),
                {b: j % size for b, j in enumerate(warm) if j is not None},
                workers)
        with phase("pubkeys"):
            prewarm = getattr(run.program_backend, "prewarm_host_caches", None)
            if prewarm is not None:
                prewarm([], [], [self.keys.encoded[i] for i in
                                 sorted(self.keys.encoded)])
        with phase("warm"):
            for block in self.warm:
                self._flush(block)
        self.bad_blocks = sorted(bad)

    def window(self, tracer):
        seconds = self.run.seconds
        self.answers = []
        self.block_s = []
        self.done = 0
        t0 = time.perf_counter()
        tracer.start()
        while True:
            if self.done == len(self.blocks):
                raise RuntimeError(
                    f"input bank of {len(self.blocks)} blocks ran out before "
                    f"{seconds} s: raise max_blocks_per_s in the mix")
            block = self.blocks[self.done]
            tb = time.perf_counter()
            with tracer.span("bench.verify"):
                try:
                    got = [bool(v) for v in self._flush(block)]
                except Exception as e:  # counted as missing verdicts
                    self.run.note_error(e)
                    got = [None] * len(block)
            self.answers += list(zip(block, got))
            self.done += 1
            # a traced run leaves out the trace's write-out, so that its
            # window holds as many blocks as an untraced one
            stall = tracer.stall_s
            elapsed = time.perf_counter() - t0 - stall
            tracer.maybe_stop(elapsed)
            self.block_s.append(time.perf_counter() - tb - (tracer.stall_s - stall))
            if elapsed >= seconds:
                break
        self.window_s = elapsed
        sigs = sum(len(c.members) for b in self.blocks[:self.done] for c in b)
        return {"sigs_per_s": sigs / elapsed}

    def sample(self):
        """Every bad block run in the window and ``reference_blocks`` more,
        drawn from the seed."""
        run = self.blocks[:self.done]
        bad = [b for b in self.bad_blocks if b < self.done]
        rest = [b for b in range(self.done) if b not in bad]
        rng = gen.rng_for(self.run.seed, b"sample")
        k = min(len(rest), int(self.mix["reference_blocks"]))
        pick = sorted(bad + rng.choice(rest, size=k, replace=False).tolist())
        return [c for b in pick for c in run[b]]

    def info(self):
        """How long a block took, and the share of the window that the
        blocks holding an invalid check (which bisect) took."""
        bad = [b for b in self.bad_blocks if b < self.done]
        valid = [s for b, s in enumerate(self.block_s) if b not in bad]
        return {"blocks": self.done,
                "block_s_median": statistics.median(valid) if valid else None,
                "bisecting_block_s": [self.block_s[b] for b in bad],
                "bisecting_share": sum(self.block_s[b] for b in bad)
                / self.window_s}
