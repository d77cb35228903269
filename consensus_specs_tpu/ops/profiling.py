"""Device-pipeline observability (SURVEY §5 aux: the reference's only
profiling is slow-case prints, gen_runner.py:26; a TPU compute plane needs
per-kernel timing and an XLA trace hook).

- ``record(...)`` is called by vm.execute around every device program run;
  stats accumulate per (program kind, batch shape) in-process.
- ``record_latency(...)`` feeds a mergeable log-bucketed histogram
  (``obs/hist.py``: fixed base-2/8-subbucket bounds, so histograms from
  different devices/nodes/processes aggregate EXACTLY — the Algorithm-R
  reservoir this replaced could not be combined across a fleet).
  Percentile reads interpolate inside the crossing bucket and agree with
  the exact nearest-rank statistic within one bucket width (~9%); the
  published ``p50/p95/p99`` family names are unchanged, and every family
  now carries ``n`` (observation count) so consumers can judge
  statistical weight.
- ``set_gauge(...)`` publishes point-in-time values (queue depth, cache
  hit rate, batch occupancy) from the serve plane.
- ``summary()``/``snapshot()``/``report()`` expose all three; the
  ``/metrics`` renderer reads ``summary()``.
- ``latency_histograms()`` hands detached histogram copies to the
  Prometheus renderer (full ``_bucket``/``_sum``/``_count`` exposition)
  and the SLO burn-rate tracker (``obs/slo.py``).
- ``trace(path)`` wraps a block in jax.profiler's trace for TensorBoard /
  xprof when deeper inspection is wanted (no-op if the profiler is
  unavailable on the platform).
"""
import contextlib
import os
import threading
import time
from collections import defaultdict
from typing import Dict, List

from ..obs import hist


def enabled() -> bool:
    """Whether CONSENSUS_SPECS_TPU_PROFILE=1 — re-read on EVERY call, so
    enabling profiling after import (from a test, the serve endpoint, a
    REPL) takes effect immediately. The historical module-level ``ENABLED``
    read stays correct through the dynamic alias below."""
    return os.environ.get("CONSENSUS_SPECS_TPU_PROFILE") == "1"


def __getattr__(name: str):
    # PEP 562: keep `profiling.ENABLED` working as a DYNAMIC read — a
    # frozen import-time bool silently ignored env flips made after import
    if name == "ENABLED":
        return enabled()
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")

_stats: Dict[str, Dict[str, float]] = defaultdict(
    lambda: {"calls": 0, "total_s": 0.0, "max_s": 0.0}
)

# count of live latency-histogram families, published as a gauge so a
# scrape shows how many distributions the process tracks (drift-gated)
HIST_FAMILIES_LABEL = "hist.families"

_lat: Dict[str, hist.Histogram] = {}
# one lock for every accumulator: the serve plane writes timings, gauges
# AND latencies concurrently from submit threads and its worker, so an
# unlocked summary() could see a dict resize mid-iteration
_lock = threading.Lock()
_gauges: Dict[str, float] = {}


def record(label: str, seconds: float) -> None:
    with _lock:
        s = _stats[label]
        s["calls"] += 1
        s["total_s"] += seconds
        s["max_s"] = max(s["max_s"], seconds)


def record_latency(label: str, seconds: float) -> None:
    """Feed one latency observation into ``label``'s mergeable histogram
    (fixed log buckets: observations land in the same bucket on every
    device/node, so fleet aggregation is exact addition)."""
    with _lock:
        h = _lat.get(label)
        if h is None:
            h = _lat[label] = hist.Histogram()
            _gauges[HIST_FAMILIES_LABEL] = float(len(_lat))
    h.observe(seconds)


def _percentile(sorted_sample: List[float], q: float) -> float:
    """Nearest-rank percentile over an ascending sample (the exact
    statistic the histogram is gated against in tests)."""
    if not sorted_sample:
        return 0.0
    rank = max(1, -(-int(q * len(sorted_sample)) // 100))  # ceil(q*n/100)
    rank = min(rank, len(sorted_sample))
    return sorted_sample[rank - 1]


def stats_and_gauges():
    """One-lock copies of the stat accumulators and gauges — the
    Prometheus renderer reads these alongside ``latency_histograms()``
    instead of paying ``summary()``'s full percentile build per scrape."""
    with _lock:
        return ({k: dict(v) for k, v in _stats.items()}, dict(_gauges))


def latency_histograms() -> Dict[str, hist.Histogram]:
    """Detached histogram copies per label (Prometheus ``_bucket``
    rendering, SLO burn rates, fleet merges)."""
    with _lock:
        snap = dict(_lat)
    return {label: h.snapshot() for label, h in sorted(snap.items())}


def latency_summary() -> Dict[str, Dict[str, float]]:
    out = {}
    for label, h in latency_histograms().items():
        out[label] = h.summary()
    return out


def set_gauge(label: str, value: float) -> None:
    with _lock:
        _gauges[label] = round(float(value), 6)


@contextlib.contextmanager
def timed(label: str):
    t0 = time.perf_counter()
    try:
        yield
    finally:
        record(label, time.perf_counter() - t0)


def summary() -> Dict[str, Dict[str, float]]:
    with _lock:
        stats = {k: dict(v) for k, v in _stats.items()}
        gauges = dict(_gauges)
    out = {
        k: {
            "calls": int(v["calls"]),
            "total_s": round(v["total_s"], 4),
            "mean_s": round(v["total_s"] / max(1, v["calls"]), 4),
            "max_s": round(v["max_s"], 4),
        }
        for k, v in sorted(stats.items())
    }
    out.update(latency_summary())
    for label, value in sorted(gauges.items()):
        out[label] = {"gauge": value}
    return out


def snapshot() -> Dict[str, Dict[str, float]]:
    """Alias of ``summary()`` under the fleet naming: every percentile
    family in it carries ``n`` (= observation count) alongside the
    p50/p95/p99 points, so any consumer of the snapshot can weigh a
    percentile by how many observations back it."""
    return summary()


def reset() -> None:
    """Clear ALL THREE accumulator families — per-label stats, latency
    histograms, gauges — so a post-reset run is indistinguishable from a
    fresh process (histogram bucketing is deterministic, so reruns are
    comparable by construction)."""
    with _lock:
        _stats.clear()
        _lat.clear()
        _gauges.clear()


def report() -> str:
    lines = ["device-pipeline timing:"]
    for label, s in summary().items():
        if "gauge" in s:
            lines.append(f"  {label}: {s['gauge']}")
        elif "p95_ms" in s:
            lines.append(
                f"  {label}: {s['count']} obs, p50 {s['p50_ms']:.1f}ms, "
                f"p95 {s['p95_ms']:.1f}ms, p99 {s['p99_ms']:.1f}ms, "
                f"max {s['max_ms']:.1f}ms"
            )
        else:
            lines.append(
                f"  {label}: {s['calls']} calls, mean {s['mean_s']*1e3:.1f}ms, "
                f"max {s['max_s']*1e3:.1f}ms, total {s['total_s']:.2f}s"
            )
    return "\n".join(lines)


@contextlib.contextmanager
def trace(log_dir: str):
    """jax.profiler trace around a block (view with TensorBoard/xprof)."""
    try:
        import jax

        jax.profiler.start_trace(log_dir)
        started = True
    except Exception:
        started = False
    try:
        yield
    finally:
        if started:
            try:
                import jax

                jax.profiler.stop_trace()
            except Exception:
                pass
