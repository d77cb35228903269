"""``slot``: a closed loop of whole slots at a registry held whole. Each
slot is one ``scale.hierarchy.verify_slot`` of its committees' aggregates,
whose keys the program gathers by validator index from its
``PubkeyTable`` of every validator's key, built in set-up from all the
compressed keys; the next slot is verified when the last one's verdicts
are in. The window starts a few slots before an epoch boundary, so it
crosses into a new epoch's committees, and no slot repeats."""
import math
import statistics
import time
from typing import List

import numpy as np

from benchmark import generate as gen
from benchmark import registry_keys, roofline
from benchmark.loop import Loop


class Committees:
    """Each epoch's committees: a permutation of the registry drawn from
    the seed and the epoch, sliced as ``compute_committee`` and
    ``get_beacon_committee`` slice the shuffled indices
    (phase0/beacon-chain.md)."""

    def __init__(self, cfg: dict, seed: int):
        self.n = int(cfg["active_validators"])
        self.seed = seed
        self.per_epoch = int(cfg["slots_per_epoch"])
        self.count = max(1, min(int(cfg["max_committees_per_slot"]),
                                self.n // self.per_epoch
                                // int(cfg["target_committee_size"])))
        self._epoch = None

    def at(self, slot: int) -> List[np.ndarray]:
        epoch = slot // self.per_epoch
        if self._epoch != epoch:
            self._epoch = epoch
            self._perm = gen.rng_for(self.seed, b"epoch%d" % epoch).permutation(
                self.n)
        total = self.count * self.per_epoch
        first = (slot % self.per_epoch) * self.count
        return [self._perm[self.n * f // total:self.n * (f + 1) // total]
                for f in range(first, first + self.count)]


def slots(mix: dict, seed: int, keys: gen.Keys, layout: Committees, numbers,
          bad, pool):
    """One list of checks per slot in ``numbers``: each committee's
    aggregate over a share of its members (a fixed spread over the mix's
    range, dealt out by the seed) and a fresh signing root per (slot,
    committee). ``bad`` maps a slot's position to the check signed over
    another message."""
    numbers = list(numbers)
    lo, hi = mix["participation"]
    n = len(numbers) * layout.count
    rng = gen.rng_for(seed, b"slots%d" % numbers[0])
    shares = rng.permutation(np.array([hi - (hi - lo) * (j + 0.5) / n
                                       for j in range(n)]))
    specs = []  # (members, message, aggregate sk, signed wrong)
    for b, s in enumerate(numbers):
        for c, members in enumerate(layout.at(s)):
            k = min(len(members), int(round(float(shares[len(specs)])
                                            * len(members))))
            keep = np.sort(rng.choice(len(members), size=k, replace=False))
            subset = members[keep]
            specs.append((subset, gen.root(b"attestation", seed, s, c),
                          keys.aggregate_sk(subset), bad.get(b) == c))
    sigs = gen.sign_all(pool, [(sk, msg + b"!" if wrong else msg)
                               for _, msg, sk, wrong in specs])
    checks = [keys.check(m, msg, sig, not wrong)
              for (m, msg, _, wrong), sig in zip(specs, sigs)]
    return [checks[b * layout.count:(b + 1) * layout.count]
            for b in range(len(numbers))]


def _items(checks):
    return [("fast_aggregate_indexed", np.asarray(c.members, dtype=np.int64),
             c.message, c.signature) for c in checks]


class Driver(Loop):
    def _verify(self, number, items):
        from consensus_specs_tpu.scale import hierarchy

        return hierarchy.verify_slot(items, slot=number, table=self.table,
                                     backend=self.run.backend).verdicts

    def setup(self, workers, phase):
        # the index API first: a program without it stops here, in seconds
        from consensus_specs_tpu.scale.pubkeys import PubkeyTable

        run, mix = self.run, self.mix
        n = int(self.cfg["active_validators"])
        with phase("keys"):
            self.keys = gen.Keys(run.seed)
            registry_keys.derive_all(self.keys, n, workers)
            run.keys_ready(self.keys, workers)
        with phase("table"):
            self.table = PubkeyTable.build([self.keys.encoded[i]
                                            for i in range(n)])
        layout = Committees(self.cfg, run.seed)
        count = int(math.ceil(float(mix["max_slots_per_s"]) * run.seconds)) + 1
        rng = gen.rng_for(run.seed, b"slot")
        epoch = 64 + int(rng.integers(1 << 20))
        slot0 = epoch * layout.per_epoch - int(mix["slots_before_epoch"])
        bad = {b: c % layout.count for b, c in mix["bad_checks"]}
        warm = mix["warm_bad_checks"]
        with phase("bank"):
            self.numbers = list(range(slot0, slot0 + count))
            self.slots = slots(mix, run.seed, self.keys, layout,
                               self.numbers, bad, workers)
            warm_slots = slots(
                mix, run.seed, self.keys, layout,
                range(slot0 - len(warm), slot0),
                {b: c % layout.count for b, c in enumerate(warm)
                 if c is not None}, workers)
        self.items = [_items(s) for s in self.slots]
        with phase("warm"):
            for b, checks in enumerate(warm_slots):
                self._verify(slot0 - len(warm) + b, _items(checks))
        self.bad_slots = sorted(bad)

    def window(self, tracer):
        seconds = self.run.seconds
        self.answers = []
        self.slot_s = []
        self.done = 0
        self.traced = 0
        t0 = time.perf_counter()
        tracer.start()
        while True:
            if self.done == len(self.slots):
                raise RuntimeError(
                    f"input bank of {len(self.slots)} slots ran out before "
                    f"{seconds} s: raise max_slots_per_s in the mix")
            checks = self.slots[self.done]
            self.traced += tracer.active
            ts = time.perf_counter()
            with tracer.span("bench.verify"):
                try:
                    got = [bool(v) for v in self._verify(
                        self.numbers[self.done], self.items[self.done])]
                except Exception as e:  # counted as missing verdicts
                    self.run.note_error(e)
                    got = []
            got += [None] * (len(checks) - len(got))
            self.answers += list(zip(checks, got))
            self.done += 1
            # a traced run leaves out the trace's write-out, so that its
            # window holds as many slots as an untraced one
            stall = tracer.stall_s
            elapsed = time.perf_counter() - t0 - stall
            tracer.maybe_stop(elapsed)
            self.slot_s.append(time.perf_counter() - ts
                               - (tracer.stall_s - stall))
            if elapsed >= seconds:
                break
        self.window_s = elapsed
        self.gather = None
        if tracer.on:
            tracer.stop()
            self.gather = roofline.module_device_s(tracer.dir,
                                                   "jit_pubkey_gather")
        sigs = sum(len(c.members) for s in self.slots[:self.done] for c in s)
        return {"sigs_per_s": sigs / elapsed}

    def sample(self):
        """Every bad slot run in the window and ``reference_slots`` more,
        drawn from the seed."""
        bad = [b for b in self.bad_slots if b < self.done]
        rest = [b for b in range(self.done) if b not in bad]
        rng = gen.rng_for(self.run.seed, b"sample")
        k = min(len(rest), int(self.mix["reference_slots"]))
        pick = sorted(bad + rng.choice(rest, size=k, replace=False).tolist())
        return [c for b in pick for c in self.slots[b]]

    def context(self):
        import jax

        return {"slots": self.done, "slots_traced": self.traced,
                "gather_trace": self.gather,
                "device_kind": jax.devices()[0].device_kind}

    def info(self):
        """How long a slot took, the share of the window that the slots
        holding an invalid check (which bisect) took, and the table."""
        bad = [b for b in self.bad_slots if b < self.done]
        valid = [s for b, s in enumerate(self.slot_s) if b not in bad]
        return {"slots": self.done,
                "slot_s_median": statistics.median(valid) if valid else None,
                "bisecting_slot_s": [self.slot_s[b] for b in bad],
                "bisecting_share": sum(self.slot_s[b] for b in bad)
                / self.window_s,
                "slots_traced": self.traced,
                "pubkey_table": {"keys": self.table.n,
                                 "bytes": self.table.nbytes,
                                 "valid": int(self.table.valid.sum())}}
