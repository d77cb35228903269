"""Seeded inputs for every cell: validator keys, committees, signatures
made with the benchmark's own reference BLS, and arrival schedules. The
drivers (``drivers/<driver>.py``) lay these out as blocks or gossip.

Pure Python and no JAX, so the spawned workers that sign never reach for
the chip. Everything is a function of ``--seed``: the same seed gives the
same keys, messages, signatures and arrival times. Seeds change the order
of the work, never its amount: every seed gets the same committee sizes,
the same number of invalid checks and the same set of arrival gaps.
"""
import hashlib
import math
from typing import Dict, List, Sequence

import numpy as np

from .reference import bls as ref
from .reference.bls12_381 import R

SLOTS_PER_EPOCH = 32  # presets/mainnet/phase0.yaml


def root(*parts) -> bytes:
    """A 32-byte signing root from tags and integers (a synthetic
    ``compute_signing_root``: fresh per slot, committee and unit)."""
    h = hashlib.sha256()
    for p in parts:
        h.update(p if isinstance(p, bytes)
                 else int(p).to_bytes(16, "little", signed=True))
    return h.digest()


def rng_for(seed: int, tag: bytes) -> np.random.Generator:
    return np.random.default_rng(int.from_bytes(root(tag, seed)[:8], "little"))


class Check:
    """One signature check: the validator indices whose keys it covers,
    their compressed keys, the message and the signature, and whether the
    generator signed it correctly (``truth``)."""

    __slots__ = ("members", "pubkeys", "message", "signature", "truth")

    def __init__(self, members, pubkeys, message, signature, truth):
        self.members = members
        self.pubkeys = pubkeys
        self.message = message
        self.signature = signature
        self.truth = truth


class Keys:
    """Validator secret keys ``((index + 1) << 16) | salt``: distinct per
    index and per seed, and small, so deriving a public key is cheap."""

    def __init__(self, seed: int):
        self.salt = int.from_bytes(root(b"keys", seed)[:2], "little")
        self.points: Dict[int, tuple] = {}  # index -> (x, y)
        self.encoded: Dict[int, bytes] = {}

    def aggregate_sk(self, members) -> int:
        idx = np.asarray(members, dtype=np.int64)
        return ((int((idx + 1).sum()) << 16) + len(idx) * self.salt) % R

    def derive(self, indices, pool) -> None:
        todo = sorted({int(i) for i in indices} - self.points.keys())
        chunks = [(self.salt, todo[i:i + 256]) for i in range(0, len(todo), 256)]
        for (_, idx), part in zip(chunks, pool.map(_pubkeys, chunks)):
            for i, (x, y, enc) in zip(idx, part):
                self.points[i] = (x, y)
                self.encoded[i] = enc

    def check(self, members, message, signature, truth) -> Check:
        return Check(tuple(int(i) for i in members),
                     [self.encoded[int(i)] for i in members],
                     message, signature, truth)


def _pubkeys(args):
    salt, indices = args
    return [ref.pubkey(((i + 1) << 16) | salt) for i in indices]


def _signs(pairs):
    return [ref.sign(sk, msg) for sk, msg in pairs]


def sign_all(pool, pairs: Sequence, chunk: int = 32) -> List[bytes]:
    chunks = [pairs[i:i + chunk] for i in range(0, len(pairs), chunk)]
    return [s for part in pool.map(_signs, chunks) for s in part]


class SlotLayout:
    """One slot's committees (``get_beacon_committee`` slicing of the
    slot's attesters, phase0/beacon-chain.md) and the sync committee, over
    validators drawn from the registry by the seed."""

    def __init__(self, cfg: dict, seed: int):
        n = int(cfg["active_validators"])
        self.per_slot = n // SLOTS_PER_EPOCH
        self.count = max(1, min(int(cfg["max_committees_per_slot"]),
                                self.per_slot // int(cfg["target_committee_size"])))
        sync = int(cfg.get("sync_committee_size", 0))
        chosen = rng_for(seed, b"layout").choice(n, size=self.per_slot + sync,
                                                 replace=False)
        att = chosen[:self.per_slot]
        self.committees = [att[self.per_slot * c // self.count:
                               self.per_slot * (c + 1) // self.count]
                           for c in range(self.count)]
        self.sync = chosen[self.per_slot:]
        self.attesters = att


def arrivals(n: int, rate: float, seconds: float, order: int) -> List[float]:
    """Poisson arrivals with a fixed set of gaps: the ``n`` quantiles of
    the exponential distribution at ``rate``, dealt out in the order that
    ``order`` draws, and scaled to the window: the first is due at its
    start, and the last gap runs from the last arrival to its end."""
    gaps = np.array([-math.log(1.0 - (j + 0.5) / n) / rate for j in range(n)])
    gaps = rng_for(order, b"arrivals").permutation(gaps) * seconds / gaps.sum()
    return (np.cumsum(gaps) - gaps).tolist()


def spread(n: int, k: int) -> List[int]:
    """``k`` positions spread evenly over ``n``."""
    return [int((j + 0.5) * n / k) for j in range(k)]
