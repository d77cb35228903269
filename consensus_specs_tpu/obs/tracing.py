"""Span-based request tracing for the serve pipeline + VM execution plane.

Every accepted ``VerificationService.submit()`` gets a ``RequestTrace``
that the pipeline stages stamp with spans — ``queue_wait`` (submit ->
pulled by the prep stage), ``prep`` (host codec), ``device`` (the flush's
hard part), ``combine`` (the RLC combined check / bisection inside it) and
``finalize`` (cache write + future resolution). Completed traces live in a
bounded ring buffer; anything slower than the running p99 is pinned into a
separate exemplar ring so the slow tail survives ring churn ("why was THIS
request slow" is answerable after the fact, not only while watching).

Tracing is OPT-IN and zero-cost when off: the service holds ``None``
instead of a tracer (no new locks or branches beyond one ``is not None``
per stage), and ``vm.execute`` checks :func:`trace_enabled` — a plain env
read — before recording anything. Enable with ``CONSENSUS_SPECS_TPU_TRACE=1``
(picked up dynamically, same contract as ``profiling.enabled()``) or pass
an explicit ``Tracer`` to the service.

Export is Chrome trace-event JSON (chrome://tracing or Perfetto's "Open
trace file"): pipeline spans on pid 1 (one row per request), VM program
executions on pid 2, plus the per-program registry (``obs/programs.py``:
steps, register-file size, assembly time, ``.vm_cache/`` hit/miss) under
the top-level ``programRegistry`` key. ``dump_trace(path)`` writes the
global tracer's export.

Program spans and flush records (always on, see the section at the end):
``span(name, **args)`` marks a stage of the RLC flush or of the service
on the profiler's clock (a ``jax.profiler.TraceAnnotation``, recorded
only inside a profiler session) and adds its seconds to the flush record
open on the calling thread; ``flush_records()`` returns the bounded ring
of ``rlc`` and ``serve`` records.
"""
import contextlib
import itertools
import json
import os
import sys
import threading
import time
from collections import deque
from typing import Dict, List, Optional, Tuple

from . import registry as _registry

TRACE_ENV = "CONSENSUS_SPECS_TPU_TRACE"

# the span stages each plane stamps, re-exported from the canonical
# registry (obs/registry.py SPAN_STAGES — the trace-coverage gate in
# tests/test_obs.py asserts every registered stage appears in an exported
# trace, so a new plane cannot silently ship untraced):
# serve: the five per-request pipeline stages (`combine` only appears on
# RLC-routed flushes); chain: the per-gossip-batch stages
# (chain/head_service.py traces one `chain_apply` record per batch:
# structural validation, the wait on the verification service's batched
# signature verdicts, latest-message application, the reverse sweep)
STAGES = _registry.SPAN_STAGES["serve"]
CHAIN_STAGES = _registry.SPAN_STAGES["chain"]
# the gossip→head stitching plane (ISSUE 12): `ingress` rides the serve
# request trace when its submit carried a birth timestamp; the chain
# trace's `head` stage is in CHAIN_STAGES above
LATENCY_STAGES = _registry.SPAN_STAGES["latency"]


def trace_enabled() -> bool:
    """Dynamic env check — flipping the env after import takes effect on
    the next service construction / VM execution."""
    return os.environ.get(TRACE_ENV, "0") not in ("", "0")


class RequestTrace:
    """One request's journey through the pipeline.

    Spans append WITHOUT a lock: every stage is a single writer (submit
    thread -> prep thread -> device thread, strictly sequenced by the
    service's queues), so only the tracer's shared rings need locking.
    """

    __slots__ = ("rid", "kind", "n_keys", "t_submit", "spans", "total_s",
                 "ok", "pinned", "flow", "flows")

    def __init__(self, rid: int, kind: str, n_keys: int, t_submit: float,
                 flow: Optional[int] = None):
        self.rid = rid
        self.kind = kind
        self.n_keys = n_keys
        self.t_submit = t_submit
        self.spans: List[Tuple[str, float, float]] = []
        self.total_s: Optional[float] = None
        self.ok: Optional[bool] = None
        self.pinned = False
        # gossip→head flow linkage (ISSUE 12): `flow` is the ingress trace
        # id a SERVE request carries (the Chrome flow-event id emitted at
        # its finalize); `flows` are the ids a CHAIN batch trace absorbs
        # (the flow arrows terminate at its head stage)
        self.flow = flow
        self.flows: Tuple[int, ...] = ()

    def span_names(self):
        return {name for name, _, _ in self.spans}

    def to_dict(self) -> Dict:
        return {
            "rid": self.rid,
            "kind": self.kind,
            "n_keys": self.n_keys,
            "ok": self.ok,
            "pinned": self.pinned,
            "total_ms": (round(self.total_s * 1e3, 3)
                         if self.total_s is not None else None),
            "spans": {name: round((b - a) * 1e3, 3)
                      for name, a, b in self.spans},
        }


class Tracer:
    """Bounded-memory span collector with slow-request exemplar capture.

    ``capacity`` bounds the completed-trace ring AND the VM-execution ring;
    ``exemplar_capacity`` bounds the pinned slow tail. ``clock`` is
    injectable so the Chrome-export golden test is deterministic.
    """

    # refresh the running-p99 estimate every this many finishes (sorting
    # the window per finish would tax the enabled hot path needlessly)
    _P99_REFRESH = 32

    def __init__(self, capacity: int = 512, exemplar_capacity: int = 32,
                 clock=time.perf_counter):
        assert capacity > 0 and exemplar_capacity > 0
        self.clock = clock
        self._t0 = clock()  # trace epoch: chrome ts are offsets from here
        self._lock = threading.Lock()
        self._ids = itertools.count(1)
        self._ring: "deque[RequestTrace]" = deque(maxlen=capacity)
        self._exemplars: "deque[RequestTrace]" = deque(
            maxlen=exemplar_capacity)
        self._totals: "deque[float]" = deque(maxlen=1024)  # p99 window
        self._p99 = 0.0
        self._finished = 0
        self._executions: "deque[Dict]" = deque(maxlen=capacity)

    # -- recording (service / vm hooks) -------------------------------------

    def begin(self, kind: str, n_keys: int,
              t_submit: Optional[float] = None,
              flow: Optional[int] = None) -> RequestTrace:
        if t_submit is None:
            t_submit = self.clock()
        return RequestTrace(next(self._ids), kind, n_keys, t_submit,
                            flow=flow)

    def span(self, trace: RequestTrace, name: str, t0: float,
             t1: float) -> None:
        trace.spans.append((name, t0, t1))

    def span_many(self, traces, name: str, t0: float, t1: float) -> None:
        """Stamp one shared stage interval onto a whole micro-batch
        (batch stages cost the same wall time for every member)."""
        for tr in traces:
            if tr is not None:
                tr.spans.append((name, t0, t1))

    def finish(self, trace: RequestTrace, ok: bool,
               t_done: Optional[float] = None) -> None:
        if t_done is None:
            t_done = self.clock()
        trace.ok = bool(ok)
        trace.total_s = t_done - trace.t_submit
        with self._lock:
            # a trace begun before this tracer existed (explicit t_submit)
            # must not export negative timestamps — rewind the epoch; an
            # `ingress` span's birth timestamp can predate even t_submit
            # (the item waited at the gossip layer), so the earliest span
            # start participates in the rewind too
            t_first = min((a for _name, a, _b in trace.spans),
                          default=trace.t_submit)
            if min(trace.t_submit, t_first) < self._t0:
                self._t0 = min(trace.t_submit, t_first)
            self._finished += 1
            # pin BEFORE folding this total into the window: "over the
            # RUNNING p99" means the p99 of everything before this request
            pin = bool(self._totals) and trace.total_s >= self._p99
            self._totals.append(trace.total_s)
            if self._p99 == 0.0 or self._finished % self._P99_REFRESH == 1:
                ordered = sorted(self._totals)
                self._p99 = ordered[min(len(ordered) - 1,
                                        (99 * len(ordered)) // 100)]
            if pin:
                trace.pinned = True
                self._exemplars.append(trace)
            self._ring.append(trace)

    def note_execution(self, *, steps: int, regs: int, batch, sharded: bool,
                       t0: float, seconds: float) -> None:
        """One VM program execution (vm.execute hook)."""
        with self._lock:
            # the FIRST traced execution may predate the lazily-created
            # global tracer (t0 is captured before the device call, and
            # that call can be a tens-of-seconds compile): rewind the
            # epoch so Perfetto never clamps/drops the most expensive
            # event for sitting before the trace origin
            if t0 < self._t0:
                self._t0 = t0
            self._executions.append({
                "steps": int(steps),
                "regs": int(regs),
                "batch": list(batch),
                "sharded": bool(sharded),
                "t0": t0,
                "seconds": seconds,
            })

    # -- reading ------------------------------------------------------------

    def completed(self) -> List[RequestTrace]:
        with self._lock:
            return list(self._ring)

    def exemplars(self) -> List[RequestTrace]:
        with self._lock:
            return list(self._exemplars)

    def executions(self) -> List[Dict]:
        with self._lock:
            return [dict(e) for e in self._executions]

    def running_p99_s(self) -> float:
        with self._lock:
            return self._p99

    def finished_total(self) -> int:
        """Monotone count of finished traces — unlike ``completed()``,
        not capped by the ring, so scaled runs can report how many
        requests were traced vs how many the ring still holds."""
        with self._lock:
            return self._finished

    # -- chrome trace-event export -------------------------------------------

    def _us(self, t: float) -> float:
        return round((t - self._t0) * 1e6, 3)

    def to_chrome(self) -> Dict:
        """Chrome trace-event JSON object (load in chrome://tracing or
        Perfetto). Pipeline spans are complete ("X") events on pid 1, one
        tid per request; VM executions are "X" events on pid 2; the
        per-program registry rides the (spec-sanctioned) extra top-level
        key ``programRegistry``."""
        from . import programs

        with self._lock:
            traces = list(self._ring)
            execs = list(self._executions)
            exemplars = list(self._exemplars)
            p99_s = self._p99
            finished = self._finished
        events: List[Dict] = [
            {"ph": "M", "name": "process_name", "pid": 1,
             "args": {"name": "serve-pipeline"}},
            {"ph": "M", "name": "process_name", "pid": 2,
             "args": {"name": "vm-programs"}},
        ]
        for tr in traces:
            events.append({
                "ph": "M", "name": "thread_name", "pid": 1, "tid": tr.rid,
                "args": {"name": f"req-{tr.rid} {tr.kind} k={tr.n_keys}"},
            })
            for name, a, b in tr.spans:
                args = {"kind": tr.kind, "n_keys": tr.n_keys}
                if name == "finalize":
                    args.update(ok=tr.ok, pinned=tr.pinned,
                                total_ms=round((tr.total_s or 0.0) * 1e3, 3))
                events.append({
                    "name": name, "cat": "serve", "ph": "X",
                    "pid": 1, "tid": tr.rid,
                    "ts": self._us(a),
                    "dur": round(max(0.0, b - a) * 1e6, 3),
                    "args": args,
                })
            # gossip→head flow links (ISSUE 12): a serve request carrying
            # an ingress flow id STARTS the flow at the end of its last
            # span (finalize); a chain batch trace that absorbed flow ids
            # FINISHES each at the start of its last span (the head
            # stage) — Perfetto then draws the arrow from the signature
            # verdict to the head move it enabled
            if tr.spans:
                if tr.flow is not None:
                    events.append({
                        "name": "gossip_to_head", "cat": "latency",
                        "ph": "s", "id": tr.flow, "pid": 1, "tid": tr.rid,
                        "ts": self._us(max(b for _n, _a, b in tr.spans)),
                    })
                t_last_start = max(a for _n, a, _b in tr.spans)
                for fid in tr.flows:
                    events.append({
                        "name": "gossip_to_head", "cat": "latency",
                        "ph": "f", "bp": "e", "id": fid,
                        "pid": 1, "tid": tr.rid,
                        "ts": self._us(t_last_start),
                    })
        for ex in execs:
            events.append({
                "name": (f"vm[steps={ex['steps']},regs={ex['regs']},"
                         f"batch={tuple(ex['batch'])}]"),
                "cat": "vm", "ph": "X", "pid": 2, "tid": 1,
                "ts": self._us(ex["t0"]),
                "dur": round(max(0.0, ex["seconds"]) * 1e6, 3),
                "args": {"steps": ex["steps"], "regs": ex["regs"],
                         "batch": ex["batch"], "sharded": ex["sharded"]},
            })
        return {
            "traceEvents": events,
            "displayTimeUnit": "ms",
            "programRegistry": programs.registry_snapshot(),
            "otherData": {
                # requests = spans present in this export (ring-bounded);
                # finished_total = every trace ever finished — when they
                # differ, the ring dropped the oldest (finished_total -
                # requests) requests' spans
                "requests": len(traces),
                "finished_total": finished,
                "exemplars": [t.to_dict() for t in exemplars],
                "running_p99_ms": round(p99_s * 1e3, 3),
            },
        }

    def dump(self, path: str) -> str:
        from . import fsio

        return fsio.atomic_write_text(
            path, json.dumps(self.to_chrome(), indent=1, sort_keys=True))


# -- cross-process span stitching (ISSUE 19) ---------------------------------
#
# A fleet worker's spans died at the process boundary: the router's
# Chrome export showed its own pipeline, and N workers' request spans
# were invisible. The worker snapshot now ships COMPLETED traces as
# JSON-safe wire dicts (`trace_to_wire` / `wire_spans`, rid-delta'd the
# same way flight events are seq-delta'd), and the aggregator re-emits
# them under per-worker pids (`worker_chrome_events`) in ONE stitched
# document (`stitched_chrome`). Timestamps stay comparable because
# `time.perf_counter` is CLOCK_MONOTONIC on Linux — one epoch for every
# process on the host — and the stitch rewinds the router tracer's
# origin to the earliest worker span, the same rule `dump_trace`
# applies to the device/flight lanes. Flow ids survive the boundary:
# the router forwards each submit's `flow_id` over the worker protocol,
# the worker's finalize emits the flow START on its own pid, and the
# router-side chain batch still emits the flow FINISH — Perfetto joins
# the two halves by id across pids.

# worker lanes start here: pids below are the router's own lanes (serve
# 1, vm 2, flight 4), workers take 100+index in snapshot order
WORKER_PID_BASE = 100


def trace_to_wire(tr: RequestTrace) -> Dict:
    """One completed trace as a JSON-safe dict (the snapshot carrier)."""
    return {
        "rid": tr.rid,
        "kind": tr.kind,
        "n_keys": tr.n_keys,
        "t_submit": tr.t_submit,
        "ok": tr.ok,
        "pinned": tr.pinned,
        "total_s": tr.total_s,
        "flow": tr.flow,
        "flows": list(tr.flows),
        "spans": [[name, a, b] for name, a, b in tr.spans],
    }


def wire_spans(tracer: Tracer, since_rid: int = 0) -> List[Dict]:
    """Completed traces with ``rid`` past ``since_rid`` (the aggregator
    passes its high-water rid back, so steady-state snapshots ship span
    DELTAS — same incremental contract as the flight journal)."""
    return [trace_to_wire(tr) for tr in tracer.completed()
            if tr.rid > int(since_rid)]


def earliest_wire_timestamp(traces: List[Dict]) -> Optional[float]:
    times = []
    for tr in traces:
        times.append(float(tr.get("t_submit", 0.0)))
        for _name, a, _b in tr.get("spans", ()):
            times.append(float(a))
    return min(times) if times else None


def worker_chrome_events(traces: List[Dict], pid: int, label: str,
                         us) -> List[Dict]:
    """One worker's wire traces as Chrome events on its own pid —
    the same span/flow shapes ``to_chrome`` emits for the router's
    requests, so the stitched document reads as one pipeline."""
    events: List[Dict] = [
        {"ph": "M", "name": "process_name", "pid": pid,
         "args": {"name": f"worker {label}"}},
    ]
    for tr in traces:
        rid = int(tr.get("rid", 0))
        events.append({
            "ph": "M", "name": "thread_name", "pid": pid, "tid": rid,
            "args": {"name": f"req-{rid} {tr.get('kind')} "
                             f"k={tr.get('n_keys')}"},
        })
        spans = [(name, float(a), float(b))
                 for name, a, b in tr.get("spans", ())]
        for name, a, b in spans:
            args = {"kind": tr.get("kind"), "n_keys": tr.get("n_keys"),
                    "worker": label}
            if name == "finalize":
                args.update(ok=tr.get("ok"), pinned=tr.get("pinned"),
                            total_ms=round(
                                (tr.get("total_s") or 0.0) * 1e3, 3))
            events.append({
                "name": name, "cat": "serve", "ph": "X",
                "pid": pid, "tid": rid,
                "ts": us(a),
                "dur": round(max(0.0, b - a) * 1e6, 3),
                "args": args,
            })
        if spans:
            if tr.get("flow") is not None:
                events.append({
                    "name": "gossip_to_head", "cat": "latency",
                    "ph": "s", "id": int(tr["flow"]), "pid": pid,
                    "tid": rid,
                    "ts": us(max(b for _n, _a, b in spans)),
                })
            t_last_start = max(a for _n, a, _b in spans)
            for fid in tr.get("flows", ()):
                events.append({
                    "name": "gossip_to_head", "cat": "latency",
                    "ph": "f", "bp": "e", "id": int(fid),
                    "pid": pid, "tid": rid,
                    "ts": us(t_last_start),
                })
    return events


def stitched_chrome(tracer: Tracer, worker_sections: Dict[str, Dict]) -> Dict:
    """ONE Chrome document from the router tracer plus per-worker span
    sections (``{label: {"pid": os_pid, "traces": [wire traces]}}`` —
    what ``obs/fleet.FleetAggregator.worker_span_sections`` returns).
    Workers render on pids ``WORKER_PID_BASE + i`` in sorted-label order
    (the worker's OS pid rides the process_name metadata via its label
    row in ``otherData.workerPids``), and every flow id the router
    forwarded joins the worker-side START to the router-side FINISH."""
    earliest = None
    for sec in worker_sections.values():
        t = earliest_wire_timestamp(sec.get("traces", ()))
        if t is not None:
            earliest = t if earliest is None else min(earliest, t)
    if earliest is not None:
        with tracer._lock:
            tracer._t0 = min(tracer._t0, earliest)
    doc = tracer.to_chrome()
    worker_pids = {}
    for i, label in enumerate(sorted(worker_sections)):
        sec = worker_sections[label]
        pid = WORKER_PID_BASE + i
        worker_pids[label] = {"pid": pid,
                              "os_pid": int(sec.get("pid") or 0)}
        doc["traceEvents"].extend(worker_chrome_events(
            sec.get("traces", ()), pid, label, tracer._us))
    doc["otherData"]["workerPids"] = worker_pids
    return doc


# -- process-global tracer ---------------------------------------------------

_global_lock = threading.Lock()
_global: Optional[Tracer] = None


def global_tracer() -> Tracer:
    """The process tracer (created on first use); what ``vm.execute`` and
    env-enabled services record into, and what ``dump_trace`` exports."""
    global _global
    with _global_lock:
        if _global is None:
            _global = Tracer()
        return _global


def maybe_tracer() -> Optional[Tracer]:
    """The global tracer when tracing is enabled, else None — the exact
    value the service stores, so the disabled path is a None check."""
    return global_tracer() if trace_enabled() else None


def reset_global() -> None:
    """Drop the global tracer (tests / multi-run benches)."""
    global _global
    with _global_lock:
        _global = None


def dump_trace(path: str) -> str:
    """Export the global tracer's rings as Chrome trace-event JSON, with
    the flight-recorder journal (obs/flight.py, pid 4 instants) composed
    in: it shares the tracer's clock, so the span view and the black box
    line up on one timeline. A disabled/empty journal contributes nothing
    (``Tracer.dump`` alone stays the lane-free export the golden test
    pins)."""
    from . import flight, fsio

    tracer = global_tracer()
    # epoch rewind for the composed lane: a journal event can predate the
    # lazily-created tracer (e.g. a program resolution noted before the
    # first traced execution) — same rule note_execution applies to its
    # own early events, so no lane exports negative ts
    earliest = flight.earliest_timestamp()
    if earliest is not None:
        with tracer._lock:
            tracer._t0 = min(tracer._t0, earliest)
    doc = tracer.to_chrome()
    doc["traceEvents"].extend(flight.chrome_events(tracer._us))
    return fsio.atomic_write_text(
        path, json.dumps(doc, indent=1, sort_keys=True))


def dump_stitched_trace(path: str, worker_sections: Dict[str, Dict]) -> str:
    """`dump_trace` plus the fleet's cross-process span sections: the
    router's own lanes AND every worker's request spans on per-worker
    pids, flow ids joining across the process boundary.
    ``serve/fleet.FleetRouter.dump_trace`` is the caller."""
    from . import flight, fsio

    tracer = global_tracer()
    earliest = [t for t in (flight.earliest_timestamp(),) if t is not None]
    for sec in worker_sections.values():
        t = earliest_wire_timestamp(sec.get("traces", ()))
        if t is not None:
            earliest.append(t)
    if earliest:
        with tracer._lock:
            tracer._t0 = min(tracer._t0, min(earliest))
    doc = stitched_chrome(tracer, worker_sections)
    doc["traceEvents"].extend(flight.chrome_events(tracer._us))
    return fsio.atomic_write_text(
        path, json.dumps(doc, indent=1, sort_keys=True))


# -- program spans and flush records ------------------------------------------
#
# Where the time goes inside one RLC flush (ops/bls_backend.batch_verify_rlc)
# and one served micro-batch (serve/service.py). Always on: a span costs one
# profiler check and, inside an open record, two perf_counter reads; a flush
# appends one record. Inside a jax.profiler session every span is also a
# TraceMe on the xplane's host plane, on the same clock as the device's
# XLA Ops line, so a device idle gap can be named by the program span open
# during it. Span names never start with ``bench.`` (the benchmark's own
# spans) and never reach ops/profiling (whose ``vm[...]`` labels the
# benchmark sums).

# records kept; older ones are dropped and counted
FLUSH_RING = 4096

_local = threading.local()
_flush_ids = itertools.count(1)
_annotation = None  # jax.profiler.TraceAnnotation once jax is imported


def _annotation_class():
    """TraceAnnotation, or None while jax is not imported (no profiler
    session can be open then, and this module never imports jax)."""
    global _annotation
    if _annotation is None:
        jax = sys.modules.get("jax")
        if jax is None:
            return None
        from jax.profiler import TraceAnnotation

        _annotation = TraceAnnotation
    return _annotation


class span:
    """``with span(name, **args):`` one stage of a flush. Inside a
    profiler session it is a ``jax.profiler.TraceAnnotation`` carrying
    ``args``; inside an open ``rlc`` record it adds its inclusive seconds
    to ``record["spans"][name]`` (the TraceMe encloses the timed interval,
    so the record never reads longer than the trace)."""

    __slots__ = ("name", "args", "_ann", "_rec", "_t0")

    def __init__(self, name: str, **args):
        self.name = name
        self.args = args

    def __enter__(self):
        cls = _annotation_class()
        if cls is not None and cls.is_enabled():
            self._ann = cls(self.name, **self.args)
            self._ann.__enter__()
        else:
            self._ann = None
        self._rec = getattr(_local, "record", None)
        if self._rec is not None:
            self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        rec = self._rec
        if rec is not None:
            spans = rec["spans"]
            spans[self.name] = (spans.get(self.name, 0.0)
                                + time.perf_counter() - self._t0)
        if self._ann is not None:
            self._ann.__exit__(*exc)
        return False


class FlushRecords:
    """Bounded ring of flush records, oldest first; ``dropped`` counts the
    records the ring has overwritten."""

    def __init__(self, capacity: int = FLUSH_RING):
        assert capacity > 0
        self._lock = threading.Lock()
        self._ring: "deque[Dict]" = deque(maxlen=capacity)
        self.dropped = 0

    def append(self, record: Dict) -> None:
        with self._lock:
            if len(self._ring) == self._ring.maxlen:
                self.dropped += 1
            self._ring.append(record)

    def records(self) -> List[Dict]:
        with self._lock:
            return list(self._ring)

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()
            self.dropped = 0


_FLUSHES = FlushRecords()


def flush_records() -> List[Dict]:
    """Every retained flush record, oldest first. An ``rlc`` record:
    ``id``, ``items``, ``seconds`` (the whole ``batch_verify_rlc`` call),
    ``spans`` (span name -> summed inclusive seconds inside the call),
    ``combines``, ``bisections``, ``final_exps``, ``miller_launches``
    (PROG A programs executed), ``keys_gathered`` (keys
    gathered from the pubkey table on the device), ``host_key_decodes``
    (keys decompressed on the host) and ``first`` (each ``vm[...]`` label
    it executed first in this process, mapped to the program kind). A ``serve``
    record: ``id``, ``items``, ``prep_s``, ``device_s``, ``rlc`` (the id
    of the rlc record run inside it, or None), ``queue_wait_s`` and
    ``handoff_wait_s`` (each summed over the flush's checks)."""
    return _FLUSHES.records()


def flush_records_dropped() -> int:
    return _FLUSHES.dropped


def reset_flush_records() -> None:
    _FLUSHES.clear()


def next_flush_id() -> int:
    """A fresh record id (rlc and serve records share one sequence)."""
    return next(_flush_ids)


@contextlib.contextmanager
def rlc_record(items: int):
    """Open the ``rlc`` record of one flush on this thread, and append it
    to the ring when the flush ends, raising or not. Yields the record."""
    rec = {"kind": "rlc", "id": next_flush_id(), "items": int(items),
           "seconds": 0.0, "spans": {}, "combines": 0, "bisections": 0,
           "final_exps": 0, "miller_launches": 0, "keys_gathered": 0,
           "host_key_decodes": 0, "first": {}}
    _local.record = rec
    t0 = time.perf_counter()
    try:
        yield rec
    finally:
        rec["seconds"] = time.perf_counter() - t0
        _local.record = None
        _local.last_rlc = rec["id"]
        _FLUSHES.append(rec)


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to counter ``name`` of the record open on this thread."""
    rec = getattr(_local, "record", None)
    if rec is not None:
        rec[name] += n


def note_first(label: str, kind: str) -> None:
    """A ``vm[...]`` label (of program ``kind``) executed for the first
    time in this process."""
    rec = getattr(_local, "record", None)
    if rec is not None:
        rec["first"][label] = kind


def take_last_rlc() -> Optional[int]:
    """The id of the last ``rlc`` record this thread closed since the
    previous call, or None."""
    rid = getattr(_local, "last_rlc", None)
    _local.last_rlc = None
    return rid


def note_serve_flush(fid: int, items: int, prep_s: float, device_s: float,
                     rlc: Optional[int], queue_wait_s: float,
                     handoff_wait_s: float) -> None:
    """Append the ``serve`` record of one settled service flush."""
    _FLUSHES.append({"kind": "serve", "id": fid, "items": int(items),
                     "prep_s": prep_s, "device_s": device_s, "rlc": rlc,
                     "queue_wait_s": queue_wait_s,
                     "handoff_wait_s": handoff_wait_s})
