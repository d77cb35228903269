"""Batched signature verification plane: collect-then-verify for epoch replay.

The reference verifies signatures one at a time inside the state-transition
call stack (process_operations loop, reference
specs/phase0/beacon-chain.md:1742-1756; fork-choice on_attestation,
fork-choice.md:393-410). On TPU the win comes from batching every independent
check of a span of blocks into a few device pipelines (SURVEY.md §2.7/P1 —
the committee axis is the DP axis). This module provides that seam:

  with SignatureCollector(spec) as col:
      for block in blocks:
          spec.state_transition(state, block)   # signature checks RECORDED
  ok = col.flush()                              # ... and verified batched
  assert ok.all()

What is deferred vs eager — chosen by the spec's own failure semantics:

- DEFERRED (assert-style; a failure invalidates the whole span anyway):
  aggregate attestation checks (``bls.FastAggregateVerify`` /
  ``bls.AggregateVerify``, incl. attester slashings and altair's
  ``eth_fast_aggregate_verify``), the block proposer signature
  (``verify_block_signature``), and the assert-style ``bls.Verify`` calls
  of ``process_randao``, ``process_voluntary_exit`` and
  ``process_proposer_slashing`` (handler-scoped interception) — every
  independent mainline-fork check rides the batched plane. The custody
  draft's assert-style reveals stay eager (small, draft-only).
- EAGER (oracle, unchanged): ``bls.Verify`` everywhere else — because
  ``process_deposit`` uses it CONDITIONALLY (an invalid deposit PoP skips
  the validator instead of failing the block, reference
  specs/phase0/beacon-chain.md:1871-1887); deferring it optimistically
  would change the post-state.

``flush()`` runs the recorded checks through the TPU backend's batched entry
points, grouped by committee-size bucket so a lone 512-wide sync aggregate
does not pad the whole attestation batch. Bit-identical to the per-call
oracle (cross-checked in tests/test_batch_verify.py). If any check fails,
the span is invalid — the caller re-runs with per-call verification to
locate the offending block (the reference's always-sequential slow path).
"""
from typing import List, Sequence, Tuple

import numpy as np

# hoisted to module scope (was re-imported inside the per-check loop of
# _bucket_of on every flush)
from .ops.bls_backend import _k_bucket
from .utils import bls


class CollectedCheck:
    __slots__ = ("kind", "pubkeys", "messages", "signature")

    def __init__(self, kind: str, pubkeys, messages, signature):
        self.kind = kind  # "fast_aggregate" | "aggregate"
        self.pubkeys = pubkeys
        self.messages = messages  # one message (fast_aggregate) or per-key list
        self.signature = signature


class SignatureCollector:
    """Context manager recording the spec's assert-style BLS verifications,
    answering True during collection; ``flush()`` verifies them batched."""

    def __init__(self, spec=None):
        self.spec = spec
        self.checks: List[CollectedCheck] = []
        # captured eagerly so flush_oracle() resolves through the REAL
        # functions even while the context is active (looking bls.X up at
        # call time inside the context would hit the interceptor and loop)
        self._orig_fast_aggregate_verify = bls.FastAggregateVerify
        self._orig_aggregate_verify = bls.AggregateVerify
        self._orig_verify = bls.Verify
        self._saved_bls: Tuple = ()
        self._saved_vbs = None
        self._saved_handlers: List = []
        # True only while inside process_randao / process_voluntary_exit:
        # their bls.Verify calls are assert-style and safe to defer, unlike
        # process_deposit's conditional use
        self._defer_verify = False

    # -- switchboard interception ------------------------------------------

    def _fast_aggregate_verify(self, pubkeys, message, signature):
        if not bls.bls_active:
            # stub mode (--disable-bls test runs): blocks carry stub
            # signatures that must NOT reach real crypto at flush time;
            # mirror only_with_bls's stub answer and record nothing
            return True
        if len(pubkeys) == 0:
            # the reference returns False without any crypto; preserve that
            # exactly rather than deferring (reference utils/bls.py:67-74)
            return False
        self.checks.append(
            CollectedCheck(
                "fast_aggregate",
                [bytes(pk) for pk in pubkeys],
                bytes(message),
                bytes(signature),
            )
        )
        return True

    def _aggregate_verify(self, pubkeys, messages, signature):
        if not bls.bls_active:
            return True
        if len(pubkeys) == 0 or len(pubkeys) != len(messages):
            return False
        self.checks.append(
            CollectedCheck(
                "aggregate",
                [bytes(pk) for pk in pubkeys],
                [bytes(m) for m in messages],
                bytes(signature),
            )
        )
        return True

    def _verify_block_signature(self, state, signed_block):
        if not bls.bls_active:
            return True
        spec = self.spec
        proposer = state.validators[signed_block.message.proposer_index]
        signing_root = spec.compute_signing_root(
            signed_block.message,
            spec.get_domain(state, spec.DOMAIN_BEACON_PROPOSER),
        )
        self.checks.append(
            CollectedCheck(
                "fast_aggregate",
                [bytes(proposer.pubkey)],
                bytes(signing_root),
                bytes(signed_block.signature),
            )
        )
        return True

    def _verify(self, pubkey, message, signature):
        """bls.Verify interceptor: deferred only inside the assert-style
        handlers (randao/exit); everywhere else — deposits included — the
        real oracle answers eagerly."""
        if not self._defer_verify:
            return self._orig_verify(pubkey, message, signature)
        if not bls.bls_active:
            return True
        self.checks.append(
            CollectedCheck(
                "fast_aggregate", [bytes(pubkey)], bytes(message), bytes(signature)
            )
        )
        return True

    def _deferring(self, handler):
        """Wrap a spec handler so bls.Verify defers for its duration."""
        def wrapped(*args, **kwargs):
            was = self._defer_verify
            self._defer_verify = True
            try:
                return handler(*args, **kwargs)
            finally:
                self._defer_verify = was

        return wrapped

    def __enter__(self):
        self._orig_verify = bls.Verify  # refresh: another collector may wrap
        self._saved_bls = (
            bls.FastAggregateVerify, bls.AggregateVerify, self._orig_verify,
        )
        bls.FastAggregateVerify = self._fast_aggregate_verify
        bls.AggregateVerify = self._aggregate_verify
        bls.Verify = self._verify
        if self.spec is not None and hasattr(self.spec, "verify_block_signature"):
            self._saved_vbs = self.spec.verify_block_signature
            self.spec.verify_block_signature = self._verify_block_signature
        if self.spec is not None:
            for name in ("process_randao", "process_voluntary_exit",
                         "process_proposer_slashing"):
                handler = getattr(self.spec, name, None)
                if handler is not None:
                    self._saved_handlers.append((name, handler))
                    setattr(self.spec, name, self._deferring(handler))
        return self

    def __exit__(self, *exc):
        bls.FastAggregateVerify, bls.AggregateVerify, bls.Verify = self._saved_bls
        if self._saved_vbs is not None:
            self.spec.verify_block_signature = self._saved_vbs
            self._saved_vbs = None
        for name, handler in self._saved_handlers:
            setattr(self.spec, name, handler)
        self._saved_handlers = []
        return False

    # -- batched resolution -------------------------------------------------

    def _unique_checks(self) -> Tuple[List[int], List[List[int]]]:
        """Dedup identical recorded checks: the same attestation included
        in multiple blocks is one verification, fanned out to every
        occurrence. Returns (first-occurrence indices in record order,
        per-unique member index lists)."""
        order: List[int] = []
        members: List[List[int]] = []
        seen = {}
        for i, c in enumerate(self.checks):
            key = _dedup_key(c)
            u = seen.get(key)
            if u is None:
                seen[key] = len(order)
                order.append(i)
                members.append([i])
            else:
                members[u].append(i)
        return order, members

    def flush(self, backend=None, mesh=None, service=None,
              rlc: bool = False) -> np.ndarray:
        """Verify all recorded checks; returns a bool array in record order.

        Identical checks (same kind/pubkeys/message(s)/signature) are
        verified ONCE and the result fanned out to every occurrence.

        With ``service`` (a serve.VerificationService), the unique checks
        ride the streaming plane — micro-batched with whatever else the
        service is carrying, cached, deduped against other submitters.
        Otherwise, without ``rlc``, checks are grouped by (kind,
        K-bucket) so each device batch pads to its own committee-size
        bucket (ops/bls_backend.py _K_BUCKETS). With ``mesh``, each batch
        axis is sharded over the mesh (SURVEY §2.7/P1 — the committee axis
        is the DP axis).

        ``rlc=True`` resolves the whole span through the backend's
        random-linear-combination path (``batch_verify_rlc``): ONE final
        exponentiation for all recorded checks instead of one per check,
        and one Miller program for all fast_aggregate checks instead of
        one per bucket (aggregate checks keep their buckets), with
        bisection recovering exact per-item verdicts on failure. Kept
        opt-in here (unlike the serve plane's default-on) so correctness
        cross-checks against flush_oracle() keep exercising the per-item
        device path."""
        out = np.zeros(len(self.checks), dtype=bool)
        order, members = self._unique_checks()

        if service is not None:
            if backend is not None or mesh is not None:
                raise ValueError(
                    "flush(service=...) uses the service's own backend and "
                    "sharding; pass backend/mesh to the VerificationService "
                    "instead"
                )
            if rlc:
                raise ValueError(
                    "flush(service=..., rlc=True): the service routes its "
                    "micro-batches through the RLC path itself "
                    "(CONSENSUS_SPECS_TPU_RLC governs it)"
                )
            futures = [
                service.submit(c.kind, c.pubkeys, c.messages, c.signature)
                for c in (self.checks[i] for i in order)
            ]
            for m, fut in zip(members, futures):
                out[m] = bool(fut.result())
            return out

        if backend is None:
            from .ops import bls_backend as backend  # noqa: F811

        if rlc:
            checks = [self.checks[i] for i in order]
            res = backend.batch_verify_rlc(
                [(c.kind, c.pubkeys, c.messages, c.signature)
                 for c in checks],
                mesh=mesh,
            )
            for u, r in enumerate(res):
                out[members[u]] = bool(r)
            return out

        groups = {}
        for u, i in enumerate(order):
            c = self.checks[i]
            key = (c.kind, _bucket_of(len(c.pubkeys)))
            groups.setdefault(key, []).append(u)

        for (kind, _bucket), uidxs in groups.items():
            checks = [self.checks[order[u]] for u in uidxs]
            if kind == "fast_aggregate":
                res = backend.batch_fast_aggregate_verify(
                    [c.pubkeys for c in checks],
                    [c.messages for c in checks],
                    [c.signature for c in checks],
                    mesh=mesh,
                )
            else:
                res = backend.batch_aggregate_verify(
                    [c.pubkeys for c in checks],
                    [c.messages for c in checks],
                    [c.signature for c in checks],
                    mesh=mesh,
                )
            for r, u in zip(res, uidxs):
                out[members[u]] = bool(r)
        return out

    def flush_oracle(self) -> np.ndarray:
        """Sequential pure-Python resolution of the same checks (the
        reference's execution model) — the cross-check for flush()."""
        out = np.zeros(len(self.checks), dtype=bool)
        for i, c in enumerate(self.checks):
            if c.kind == "fast_aggregate":
                out[i] = self._orig_fast_aggregate_verify(c.pubkeys, c.messages, c.signature)
            else:
                out[i] = self._orig_aggregate_verify(c.pubkeys, c.messages, c.signature)
        return out


def _bucket_of(k: int) -> int:
    return _k_bucket(max(1, k))


def _dedup_key(c: CollectedCheck):
    msgs = c.messages if isinstance(c.messages, bytes) else tuple(c.messages)
    return (c.kind, tuple(c.pubkeys), msgs, c.signature)


def replay_blocks_batched(spec, state, signed_blocks: Sequence) -> np.ndarray:
    """Replay ``signed_blocks`` through ``spec.state_transition`` with all
    assert-style signature checks collected, then batch-verified. Mutates
    ``state``. Returns the per-check result array (all True = valid span)."""
    with SignatureCollector(spec) as col:
        for signed_block in signed_blocks:
            spec.state_transition(state, signed_block)
    return col.flush()


def feed_attestations_batched(spec, store, attestations: Sequence) -> np.ndarray:
    """Feed wire attestations to fork-choice ``on_attestation`` with their
    FastAggregateVerify checks collected, then batch-verified — the
    fork-choice side of the hot loop (reference
    specs/phase0/fork-choice.md:393-410). Store mutations happen
    optimistically during collection; a False in the result means the span
    must be re-fed per-call against a fresh store (the reference's
    always-sequential path)."""
    with SignatureCollector(spec) as col:
        for attestation in attestations:
            spec.on_attestation(store, attestation)
    return col.flush()


def feed_attestations_streamed(spec, store, attestations, service=None
                               ) -> np.ndarray:
    """Streaming twin of ``feed_attestations_batched``: attestations come
    from an ITERATOR (a live gossip feed), and each recorded check is
    submitted to the serve plane the moment it is recorded — verification
    overlaps ingestion instead of waiting for the span to end, and
    duplicates across the stream (the same aggregate from many peers) are
    verified once by the service's cache/dedup layer.

    With ``service=None`` a private VerificationService is created for
    the call (constructed BEFORE the collector context so its fallback
    oracle captures the real bls functions) and drained afterwards.
    Returns the per-check bool array in record order, exactly like the
    batched feeder."""
    owned = service is None
    if owned:
        from .serve import VerificationService

        service = VerificationService()
    futures = []
    try:
        with SignatureCollector(spec) as col:
            n_seen = 0
            for attestation in attestations:
                spec.on_attestation(store, attestation)
                for c in col.checks[n_seen:]:
                    futures.append(
                        service.submit(c.kind, c.pubkeys, c.messages,
                                       c.signature)
                    )
                n_seen = len(col.checks)
        return np.array([bool(f.result()) for f in futures], dtype=bool)
    finally:
        if owned:
            service.close()
