"""Synthetic gossip for the serve and chain planes, and its fault plans.

``FailingBackendProxy`` wraps a real backend module and raises on chosen
call numbers: the device-failure injection the service's retry and
oracle-fallback path is tested against (serve/worker.py's ``fault`` op
is its cross-process sibling). The rest drives attestation gossip
through the chain plane without pairings: ``plan_gossip_faults`` draws
a seeded per-event ``GossipFaultPlan`` (invalid signatures, withheld
blocks, equivocations, censored aggregates), and ``VerdictBackend``
answers each signature by its bytes (``BAD_SIGNATURE`` -> False).
"""
import random
from collections.abc import Sequence
from dataclasses import dataclass
from typing import Tuple


class FailingBackendProxy:
    """Delegates to a real backend module but raises on chosen call
    numbers — in-process device-failure injection. Failing calls 1 and 2
    poisons the FIRST batch twice (attempt + bounded retry), forcing the
    service onto the sequential oracle path while later batches prove the
    backend recovers."""

    def __init__(self, backend, fail_calls=(1, 2)):
        self._backend = backend
        self._fail_calls = set(fail_calls)
        self.calls = 0
        self.fired = 0

    def _maybe_fail(self):
        self.calls += 1
        if self.calls in self._fail_calls:
            self.fired += 1
            raise RuntimeError(f"injected device failure (call {self.calls})")

    def batch_fast_aggregate_verify(self, *args, **kwargs):
        self._maybe_fail()
        return self._backend.batch_fast_aggregate_verify(*args, **kwargs)

    def batch_aggregate_verify(self, *args, **kwargs):
        self._maybe_fail()
        return self._backend.batch_aggregate_verify(*args, **kwargs)

    def batch_verify_rlc(self, *args, **kwargs):
        # the RLC route counts against the same injected-failure schedule:
        # a poisoned combined batch must degrade through the per-group
        # path to the oracle without losing a request
        self._maybe_fail()
        return self._backend.batch_verify_rlc(*args, **kwargs)

    def prewarm_host_caches(self, *args, **kwargs):
        # codec prep never fails here: the injection targets the device
        # hard part, prep degradation has its own PREP_STATS counters
        return self._backend.prewarm_host_caches(*args, **kwargs)


# -- chain-plane gossip fault injection ---------------------------------------
#
# The chain service tests and the multi-node network simulation
# (consensus_specs_tpu/sim/) drive attestation gossip through the SAME
# VerificationService machinery as signature verification, but the thing
# under test is the fork-choice plane, not the pairing math — so the
# verdicts come from a deterministic crypto-free backend and the faults
# are planned per event:
#   "invalid_sig"   the attestation carries BAD_SIGNATURE; the service must
#                   answer False and the chain plane must DROP it;
#   "orphan"        the attestation references a block withheld from the
#                   stream; the chain plane must DEFER it and apply it only
#                   once the block arrives (deferred-then-resolved);
#   "equivocation"  the adversary pairs the event's block with a
#                   conflicting twin proposal at the same slot, published
#                   to a different subset of the network (simnet only —
#                   single-node replays treat it as "ok");
#   "censored_agg"  the adversarial aggregator never publishes this
#                   committee's aggregate — the votes vanish from every
#                   honest view (simnet counts them; the convergence gate
#                   excludes them from the union oracle).

BAD_SIGNATURE = b"\xba" * 96  # the injected invalid-signature marker

# every kind a fault plan may carry, in draw-priority order
FAULT_KINDS = ("ok", "invalid_sig", "orphan", "equivocation", "censored_agg")


@dataclass(frozen=True)
class GossipFaultPlan(Sequence):
    """The stable per-event fault plan shared by the chain service tests
    and ``sim/``'s scenario library.

    Sequence-shaped over the per-event kind strings (``plan[e]``,
    ``len(plan)``, ``plan.count("orphan")`` all work, so pre-dataclass
    callers are untouched) while carrying the rates that produced it —
    equality is structural, which is what the seed-determinism gate
    asserts: same seed, same rates -> identical plan."""

    kinds: Tuple[str, ...]
    invalid_rate: float = 0.0
    orphan_rate: float = 0.0
    equivocation_rate: float = 0.0
    censor_rate: float = 0.0

    def __post_init__(self):
        unknown = set(self.kinds) - set(FAULT_KINDS)
        if unknown:
            raise ValueError(f"unknown fault kinds in plan: {sorted(unknown)}")

    def __len__(self) -> int:
        return len(self.kinds)

    def __getitem__(self, index):
        return self.kinds[index]

    def counts(self) -> dict:
        """{kind: occurrences} over every kind (zeros included) — the
        scenario-matrix report's per-plan composition line."""
        return {kind: self.kinds.count(kind) for kind in FAULT_KINDS}


class VerdictBackend:
    """Crypto-free batched backend: the verdict rides IN the signature
    bytes (``BAD_SIGNATURE`` -> False, anything else -> True), so chain
    replays exercise the full service pipeline — batching, dedup, caching,
    False-verdict routing — without paying pairings for synthetic votes.
    Counts calls/items like the real backend's CALL_COUNTS ledger."""

    def __init__(self):
        self.calls = 0
        self.items = 0

    def _verdicts(self, signatures):
        self.calls += 1
        self.items += len(signatures)
        return [sig != BAD_SIGNATURE for sig in signatures]

    def batch_fast_aggregate_verify(self, pubkey_sets, messages, signatures):
        return self._verdicts([bytes(s) for s in signatures])

    def batch_aggregate_verify(self, pubkey_sets, message_sets, signatures):
        return self._verdicts([bytes(s) for s in signatures])


def plan_gossip_faults(rng: random.Random, events: int,
                       invalid_rate: float = 0.0,
                       orphan_rate: float = 0.0,
                       equivocation_rate: float = 0.0,
                       censor_rate: float = 0.0) -> GossipFaultPlan:
    """Per-event fault plan for an attestation gossip replay: one kind
    from ``FAULT_KINDS`` drawn independently per event (a single uniform
    draw split across the rate bands, so adding a rate never perturbs the
    draws of the kinds before it at a fixed seed). The first event is
    always clean so a replay never starts with an empty applied set."""
    kinds = []
    bands = (
        ("invalid_sig", invalid_rate),
        ("orphan", orphan_rate),
        ("equivocation", equivocation_rate),
        ("censored_agg", censor_rate),
    )
    for e in range(events):
        draw = rng.random()
        kind = "ok"
        if e:
            upper = 0.0
            for name, rate in bands:
                upper += rate
                if draw < upper:
                    kind = name
                    break
        kinds.append(kind)
    return GossipFaultPlan(
        kinds=tuple(kinds),
        invalid_rate=invalid_rate,
        orphan_rate=orphan_rate,
        equivocation_rate=equivocation_rate,
        censor_rate=censor_rate,
    )
