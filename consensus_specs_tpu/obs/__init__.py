"""Observability plane: request tracing, metric registry, exposition.

The serve pipeline and the VM execution engine record into this package;
it exports three surfaces:

- ``tracing``   — per-request spans (queue_wait/prep/device/combine/
                  finalize + the chain plane's validate/sig_wait/apply/
                  sweep) in a bounded ring with slow-request exemplar
                  pinning, plus VM execution events; Chrome trace-event
                  export (``dump_trace``) composing the flight-recorder
                  lane. Opt-in
                  ``CONSENSUS_SPECS_TPU_TRACE=1``. Always on: program
                  spans (``span``) on the profiler's clock and the ring
                  of ``rlc``/``serve`` flush records (``flush_records``).
- ``registry``  — the canonical metric-name registry + span-stage
                  registry (both drift-gated by tier-1) and the
                  Prometheus text renderer (histogram exposition incl.).
- ``exposition``— opt-in stdlib HTTP endpoint: ``/metrics`` (Prometheus),
                  ``/snapshot`` (ServeMetrics JSON), ``/healthz``
                  (liveness + SLO state), ``/flightdump`` (JSONL journal).
- ``programs``  — per-VM-program registry (steps, register-file size,
                  assembly time, ``.vm_cache/`` hit/miss).
- ``hist``      — mergeable log-bucketed histograms (fixed base-2/
                  8-subbucket bounds: exact cross-device/node merges) —
                  the latency metric type behind ``ops/profiling``.
- ``flight``    — cross-plane flight recorder (bounded ring journal of
                  serve/chain/vm events, JSONL dump on fault/demand).
- ``slo``       — declared latency objectives + multi-window burn rates
                  over the histograms; feeds ``/healthz`` — plus
                  the fleet ``ShedPolicy`` (burn rates -> shed/drain
                  decisions, ISSUE 11).
- ``snapshot``  — the cross-process wire format: a worker's whole obs
                  state (histograms, stats, gauges, flight journal) as
                  one JSON-safe dict, round-trip-merge-exact.
- ``fleet``     — the ``FleetAggregator`` merging N worker snapshots
                  into one exact fleet-wide metrics/journal surface.

Import cost is stdlib-only; nothing here imports jax, and ``ops`` modules
are only reached lazily at render/record time (so ops <-> obs never
cycles).
"""
from .exposition import ExpositionServer, start_exposition  # noqa: F401
from .tracing import (  # noqa: F401
    STAGES,
    Tracer,
    dump_trace,
    global_tracer,
    maybe_tracer,
    reset_global,
    trace_enabled,
)
