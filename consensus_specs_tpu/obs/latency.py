"""End-to-end gossip→head latency plane (ROADMAP item 5, ISSUE 12).

Every headline number before this module was throughput; the competitive
axis for a consensus runtime is cryptographic finality LATENCY (ACE
Runtime, PAPERS.md). This module stitches the two existing span trees —
the serve pipeline (queue_wait/prep/device/combine/finalize) and the
chain batch stages (validate/sig_wait/apply/sweep/head) — into ONE
per-item timeline from gossip ingress to the moment the attestation
moved the fork-choice head:

- **births**: every gossip item picks up a ``Birth`` (monotone trace id +
  perf-counter timestamp) at ingress — sim fabric delivery
  (``sim/node.py``) or a serve ``submit(birth_s=...)``. The id doubles as
  the Chrome-trace FLOW id linking the serve request's span row to the
  chain batch's span row (``obs/tracing.py`` emits ``ph:"s"``/``"f"``
  flow events), so Perfetto draws the arrow from finalize to head.
- **per-stage histograms**: each pipeline stage records its duration into
  the ``latency[<stage>]`` dynamic family — the same mergeable
  log-bucketed histograms (``obs/hist.py``) every other latency number
  uses, so they merge exactly across devices, nodes, and fleet worker
  processes and render on ``/metrics`` like any other family.
- **the end-to-end number**: ``latency.gossip_to_head`` — birth to the
  head update that reflects the vote (the SPECULATIVE head update when
  ``chain/head_service.py`` speculates, since that is when ``get_head``
  really starts answering with the new vote) — feeds the declared
  ``gossip_to_head_p99`` per-slot SLO in ``obs/slo.py``.
- **the control input**: ``downstream_p99_s()`` reads the live p99 of the
  stages a queued item still has ahead of it (prep/device/finalize) —
  what the serve plane's deadline-aware flush scheduler
  (``serve/service.py``) subtracts from the remaining slot budget to
  decide whether waiting for a fuller batch would blow the deadline.

Recording costs one histogram observe per stage per flush/batch (plus
one per item for queue_wait and the end-to-end number) — flush-scale,
not per-limb-scale, so the plane stays on without an env gate; births
are only tracked where a caller provides them.
"""
import itertools
import threading
import time
from typing import Dict, Optional, Tuple

from ..ops import profiling

# the end-to-end family (registered in obs/registry.py LATENCIES; the
# gossip_to_head_p99 SLO in obs/slo.py reads it by this name)
GOSSIP_TO_HEAD_LABEL = "latency.gossip_to_head"

# per-stage dynamic family: latency[<stage>] — the stage set is the union
# of the serve pipeline stages, the chain batch stages, and the ingress
# hop (birth -> submit accepted); fixed here so the label cardinality is
# bounded by construction
STAGES: Tuple[str, ...] = (
    "ingress", "queue_wait", "prep", "device", "combine", "finalize",
    "validate", "sig_wait", "apply", "sweep", "head",
    # the light-client proof plane (ISSUE 16): artifact build, signature
    # verdict wait, and the full serve() request (hit or build)
    "proof_build", "proof_verify", "proof_serve",
    # the Merkleization plane (ISSUE 18): every ssz_impl.hash_tree_root
    "merkle_root",
)

# what a QUEUED serve item still has ahead of it — the stages whose
# observed p99 the deadline-aware flush scheduler budgets for
DOWNSTREAM_STAGES: Tuple[str, ...] = ("prep", "device", "finalize")

_ids = itertools.count(1)


class Birth:
    """One gossip item's ingress record: a process-unique trace id (the
    Chrome flow id) and the perf-counter timestamp of arrival."""

    __slots__ = ("trace_id", "t")

    def __init__(self, trace_id: int, t: float):
        self.trace_id = trace_id
        self.t = t

    def __repr__(self):
        return f"Birth(id={self.trace_id}, t={self.t:.6f})"


def birth(t: Optional[float] = None) -> Birth:
    """Stamp one gossip arrival (sim fabric delivery / serve ingress)."""
    return Birth(next(_ids), time.perf_counter() if t is None else t)


def stage_label(stage: str) -> str:
    return f"latency[{stage}]"


def note_stage(stage: str, seconds: float) -> None:
    """One stage-duration observation into the mergeable per-stage
    histogram family (``latency[<stage>]``)."""
    profiling.record_latency(stage_label(stage), seconds)


def note_gossip_to_head(seconds: float) -> None:
    """One end-to-end observation: gossip birth -> the head update that
    reflects the item's vote."""
    profiling.record_latency(GOSSIP_TO_HEAD_LABEL, seconds)


# downstream-p99 read cache: the flush scheduler consults it on every
# collect loop, and a per-call latency_histograms() snapshot (one lock +
# dict copy per family) would tax the hot path for a number that moves
# at flush cadence — one read per max_age window is plenty
_p99_lock = threading.Lock()
_p99_cache = {"t": 0.0, "v": 0.0}


def downstream_p99_s(stages: Tuple[str, ...] = DOWNSTREAM_STAGES,
                     max_age_s: float = 0.05) -> float:
    """Sum of the live p99s of ``stages`` (seconds) — the observed cost
    of everything a queued item still has to pay after a flush fires.
    Read from the same histograms the fleet merges, cached ``max_age_s``
    (the cache is shared across callers; every caller in-tree passes the
    default stage set). Stages with no observations contribute 0 — a
    cold pipeline budgets optimistically and learns within one flush."""
    now = time.monotonic()
    with _p99_lock:
        if now - _p99_cache["t"] < max_age_s:
            return _p99_cache["v"]
    hists = profiling.latency_histograms()
    total = 0.0
    for stage in stages:
        h = hists.get(stage_label(stage))
        if h is not None and h.count:
            total += h.percentile(99.0)
    with _p99_lock:
        _p99_cache["t"] = now
        _p99_cache["v"] = total
    return total


def snapshot() -> Dict[str, Dict]:
    """The latency families' summary dicts (stage + end-to-end):
    ``{label: {count/n/mean_ms/max_ms/p50/p95/p99}}``."""
    out: Dict[str, Dict] = {}
    for label, h in profiling.latency_histograms().items():
        if label == GOSSIP_TO_HEAD_LABEL or label.startswith("latency["):
            out[label] = h.summary()
    return out


def reset() -> None:
    """Fresh trace-id counter + cold p99 cache (tests, multi-run benches;
    the histograms themselves live in ``ops/profiling`` and reset with
    ``profiling.reset()``)."""
    global _ids
    _ids = itertools.count(1)
    with _p99_lock:
        _p99_cache["t"] = 0.0
        _p99_cache["v"] = 0.0
