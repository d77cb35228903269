"""Mainnet-scale workload plane (ISSUE 20 / ROADMAP item 1).

Hierarchical aggregate-of-aggregates verification over a synthetic
million-validator registry — the first workload that composes every
plane at production scale:

- ``registry.py``  — deterministic seed -> millions of validators with
  real index-derived pubkeys and mainnet-preset committee shuffling
  (vectorized swap-or-not, bit-identical to ``spec.compute_committee``),
  emitted lazily as columnar numpy state.
- ``pubkeys.py``   — the pubkey table: every validator's key by index,
  KeyValidated once through ``ops/codec.py``'s batched native
  decompression and held on the device (126 MB at 1,048,576 keys), from
  which the RLC engine gathers a committee's keys by validator index
  (``scale.pubkey_table_*`` gauges).
- ``hierarchy.py`` — per-committee aggregates verified via the RLC
  combine, committee verdicts folded up a slot-level tree so the
  ``_FinalExpBatcher`` keeps cost at ONE final-exp execution per slot,
  with bisection localizing a bad committee exactly.
- ``routing.py``   — committee-affinity fleet routing: consistent-hash
  affinity on committee index keeps per-committee pubkey state warm on
  one worker.
- ``smoke.py``     — ``make mainnet-smoke``: a small-but-mainnet-preset
  slot verified hierarchically == flat == host oracle over
  valid/corrupted/censored traffic, bad committee localized.

Measured on the chip by the ``slot1m.fold`` cell of ``benchmark/``.
"""
