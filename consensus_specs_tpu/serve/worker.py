"""Fleet worker: one ``VerificationService`` process behind the router.

`python -m consensus_specs_tpu.serve.worker` is the process-per-device-
group unit of the serve fleet (ISSUE 11, ROADMAP item 3): the router
(`serve/fleet.py`) spawns N of these, routes checks to them by
consistent-hash content key, and drives them with the control protocol
below. The process boundary is the point — each worker owns its own GIL,
its own XLA client, its own result cache, and its own observability
state, which it ships home as `obs/snapshot.py` wire snapshots for exact
fleet-wide merging.

Protocol: newline-delimited JSON over stdin/stdout, one long-lived
duplex per worker. Binary fields travel as hex. Requests carry an
``id`` the reply echoes; ``submit`` replies arrive in COMPLETION order
(the service resolves futures as flushes finish), everything else
answers in line.

  parent -> worker                      worker -> parent
  ----------------                      ----------------
                                        {"op":"ready","label","pid"}
  {"op":"submit","id",kind,...}         {"op":"result","id","ok"}
  {"op":"snapshot","id",flight_since?}  {"op":"snapshot","id","data"}
  {"op":"ladder","id","rung",reason?}   {"op":"ok","id"}
  {"op":"fault","id","calls",mode?,ms?} {"op":"ok","id"}    (test/smoke)
  {"op":"warm","id","k","sizes"}        {"op":"ok","id"}
  {"op":"drain","id"}                   {"op":"ok","id"}; keeps serving
                                        already-piped requests until
                                        stdin EOF, then {"op":"bye"}
  (stdin EOF)                           drain + exit

Env (set by the router): ``CONSENSUS_SPECS_TPU_FLEET_WORKER`` is the
worker label (also suffixes every flight dump — see
`obs/flight.resolve_dump_path`); ``CONSENSUS_SPECS_TPU_FLEET_BACKEND``
picks the backend — ``bls`` (default: the real device backend, warmed at
spawn) or ``verdict`` (the crypto-free `serve/load.VerdictBackend`, used
by the simnet fleet replay and the tier-1 tests — no BLS math, device
work, or XLA compiles; the package import still pays the jax import,
which ops/__init__ does eagerly);
``SERVE_MAX_BATCH`` / ``SERVE_MAX_WAIT_MS`` size the service's flush.
``CONSENSUS_SPECS_TPU_VM_WARM_BG`` defaults to ``1`` in workers (set
explicitly to ``0`` to disarm): cold shapes background-compile off the
serving path and flip to fused when ready; each snapshot reports the
effective state as ``extra["warm_bg"]`` (the fleet smoke gates on it).

The ``fault`` op arms deterministic backend-fault injection (the
in-process `FailingBackendProxy`'s cross-process sibling): the next
``calls`` backend calls either raise (``mode="fail"`` — the service
walks its retry -> per-group -> oracle ladder) or sleep ``ms``
(``mode="slow"``) — how the fleet smoke and tests light up a worker's
latency histogram to force an SLO burn.
"""
import json
import os
import sys
import threading
import time

WORKER_ENV = "CONSENSUS_SPECS_TPU_FLEET_WORKER"
BACKEND_ENV = "CONSENSUS_SPECS_TPU_FLEET_BACKEND"
CPU_ENV = "CONSENSUS_SPECS_TPU_FLEET_CPU"


def _apply_affinity() -> None:
    """Pin this worker to its core slice (CONSENSUS_SPECS_TPU_FLEET_CPU,
    a comma list of core ids set by the router). Without pinning, N
    workers' XLA thread pools oversubscribe the host N-fold and fleet
    throughput DROPS below single-process (measured 0.63x at 2 workers
    on the 2-core container); with one core slice per worker the
    processes scale like the device groups they model. Best-effort: no
    sched_setaffinity (macOS), malformed values, or an empty slice all
    leave the process unpinned."""
    raw = (os.environ.get(CPU_ENV) or "").strip()
    if not raw or not hasattr(os, "sched_setaffinity"):
        return
    try:
        cores = {int(tok) for tok in raw.split(",") if tok.strip() != ""}
        if cores:
            os.sched_setaffinity(0, cores)
    except (ValueError, OSError):
        pass


class _FaultableBackend:
    """Delegating backend proxy with armable fault injection.

    ``arm(calls, mode, ms)``: the next ``calls`` verification calls
    either raise (``fail``) or sleep ``ms`` milliseconds first
    (``slow``). ``prewarm_host_caches`` and every other attribute pass
    straight through; ``batch_verify_rlc`` is only visible when the
    inner backend has it (so verdict-mode services keep their per-group
    routing)."""

    _GATED = ("batch_fast_aggregate_verify", "batch_aggregate_verify",
              "batch_verify_rlc")

    def __init__(self, inner):
        self._inner = inner
        self._lock = threading.Lock()
        self._remaining = 0
        self._mode = "fail"
        self._ms = 0.0
        self.fired = 0

    def arm(self, calls: int, mode: str = "fail", ms: float = 0.0) -> None:
        with self._lock:
            self._remaining = max(0, int(calls))
            self._mode = mode
            self._ms = float(ms)

    def _gate(self) -> None:
        with self._lock:
            if self._remaining <= 0:
                return
            self._remaining -= 1
            self.fired += 1
            mode, ms = self._mode, self._ms
        if mode == "slow":
            time.sleep(ms / 1e3)
            return
        raise RuntimeError("injected worker fault (fleet fault op)")

    def __getattr__(self, name):
        inner_attr = getattr(self._inner, name)  # AttributeError propagates
        if name not in self._GATED:
            return inner_attr

        def gated(*args, **kwargs):
            self._gate()
            return inner_attr(*args, **kwargs)

        return gated


class _VerdictOracle:
    """Per-item fallback matching `VerdictBackend`'s rule (verdict mode
    never imports the pure-Python pairing oracle)."""

    def verify_one(self, p) -> bool:
        from .load import BAD_SIGNATURE

        return bytes(p.signature) != BAD_SIGNATURE


def _build_service(label: str):
    """(service, faultable backend) for the configured backend mode."""
    from .service import VerificationService

    backend_kind = os.environ.get(BACKEND_ENV, "bls").strip() or "bls"
    max_batch = int(os.environ.get("SERVE_MAX_BATCH", "32"))
    max_wait_ms = float(os.environ.get("SERVE_MAX_WAIT_MS", "20"))
    if backend_kind == "verdict":
        from .load import VerdictBackend
        from .metrics import _pow2

        backend = _FaultableBackend(VerdictBackend())
        svc = VerificationService(
            backend=backend, oracle=_VerdictOracle(),
            bucket_fn=_pow2, max_batch=max_batch,
            max_wait_ms=max_wait_ms)
        return svc, backend
    from ..ops import bls_backend

    backend = _FaultableBackend(bls_backend)
    svc = VerificationService(backend=backend, max_batch=max_batch,
                              max_wait_ms=max_wait_ms)
    return svc, backend


def _warm_committees(k: int, n: int, seed: int = 9901):
    """Synthetic warm-up committees (content disjoint from any stream:
    the seed namespace is the worker's own)."""
    from ..utils import bls
    from ..utils.bls12_381 import R

    items = []
    for ci in range(n):
        sks = [seed * 10_000 + ci * 100 + j + 1 for j in range(k)]
        pks = [bls.SkToPk(sk) for sk in sks]
        msg = (b"warm%04d" % ci) + b"\x00" * 24
        items.append(("fast_aggregate", pks, msg, bls.Sign(sum(sks) % R, msg)))
    return items


def _warm(k: int, sizes) -> None:
    """Pay the XLA/VM compiles for the given flush sizes outside any
    timed window."""
    from ..ops import bls_backend

    sizes = sorted({int(s) for s in sizes if int(s) > 0}, reverse=True)
    if not sizes:
        return
    items = _warm_committees(k, sizes[0])
    for size in sizes:
        bls_backend.batch_verify_rlc(items[:size])


def _device():
    """This worker's JAX devices once its backend is up, and the chip
    device files it holds open (a chip's vfio group or accel node opens
    in one process only); None before (a snapshot never brings JAX up
    itself)."""
    from jax._src import xla_bridge

    if not xla_bridge.backends_are_initialized():
        return None
    import jax

    held = set()
    for fd in os.listdir("/proc/self/fd"):
        try:
            path = os.readlink(f"/proc/self/fd/{fd}")
        except OSError:
            continue  # closed since the listing
        if path.startswith("/dev/accel") or (
                path.startswith("/dev/vfio/") and path != "/dev/vfio/vfio"):
            held.add(path)
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "chip_files": sorted(held)}


def _decode_submit(msg):
    kind = msg["kind"]
    pubkeys = [bytes.fromhex(pk) for pk in msg["pubkeys"]]
    if kind == "fast_aggregate":
        messages = bytes.fromhex(msg["messages"])
    else:
        messages = [bytes.fromhex(m) for m in msg["messages"]]
    signature = bytes.fromhex(msg["signature"])
    return kind, pubkeys, messages, signature


def main() -> int:
    _apply_affinity()
    label = os.environ.get(WORKER_ENV, f"w{os.getpid()}")
    # background VM warming is the fleet default (ISSUE 20 satellite): a
    # fresh worker's auto-routed executions enqueue daemon-thread
    # compiles and flip to fused when they land, instead of staying
    # interpreter-only until someone pays a compile on the serving path.
    # setdefault so an explicit router/operator "0" still disarms it.
    os.environ.setdefault("CONSENSUS_SPECS_TPU_VM_WARM_BG", "1")
    from ..obs import snapshot, timeseries
    from ..ops import vm_compile
    from ..utils import bls

    # verdicts must flow through the service, not the stub's eager True
    bls.bls_active = True
    svc, backend = _build_service(label)

    # telemetry plane (ISSUE 19): when the TSDB env is set (inherited
    # from the router), sample this worker's gauges/histograms on the
    # configured interval — the rings ship home in every snapshot and
    # merge exactly in the aggregator
    sampler = (timeseries.start_sampler() if timeseries.ts_enabled()
               else None)

    out_lock = threading.Lock()

    def send(obj) -> None:
        line = json.dumps(obj, separators=(",", ":"))
        with out_lock:
            sys.stdout.write(line + "\n")
            sys.stdout.flush()

    def on_done(req_id):
        def cb(fut):
            try:
                send({"op": "result", "id": req_id, "ok": bool(fut.result())})
            except Exception as e:  # a lost future must still answer
                send({"op": "error", "id": req_id,
                      "error": f"{type(e).__name__}: {e}"[:200]})
        return cb

    send({"op": "ready", "label": label, "pid": os.getpid()})
    try:
        for raw in sys.stdin:
            raw = raw.strip()
            if not raw:
                continue
            msg = None
            try:
                msg = json.loads(raw)
                op = msg.get("op")
                req_id = msg.get("id")
                if op == "submit":
                    kind, pubkeys, messages, signature = _decode_submit(msg)
                    birth = msg.get("birth")
                    flow = msg.get("flow")
                    fut = svc.submit(
                        kind, pubkeys, messages, signature,
                        birth_s=None if birth is None else float(birth),
                        flow_id=None if flow is None else int(flow))
                    fut.add_done_callback(on_done(req_id))
                elif op == "snapshot":
                    data = snapshot.take_process_snapshot(
                        worker=label,
                        extra={"serve": svc.metrics.snapshot(),
                               "ladder_rung": svc.ladder_rung,
                               "faults_fired": backend.fired,
                               "warm_bg": vm_compile._bg_warm_enabled(),
                               "device": _device()},
                        flight_since=int(msg.get("flight_since", 0)),
                        spans_since=int(msg.get("spans_since", 0)))
                    send({"op": "snapshot", "id": req_id, "data": data})
                elif op == "ladder":
                    svc.set_ladder_rung(int(msg["rung"]),
                                        reason=msg.get("reason", "fleet"))
                    send({"op": "ok", "id": req_id})
                elif op == "fault":
                    backend.arm(int(msg.get("calls", 1)),
                                mode=msg.get("mode", "fail"),
                                ms=float(msg.get("ms", 0.0)))
                    send({"op": "ok", "id": req_id})
                elif op == "warm":
                    _warm(int(msg.get("k", 8)), msg.get("sizes", (1,)))
                    send({"op": "ok", "id": req_id})
                elif op == "drain":
                    # acknowledge but KEEP READING until stdin EOF: a
                    # submit the router routed before removing this
                    # worker from the ring can already be on the pipe
                    # behind the drain op — it must be answered, not
                    # black-holed (the parent closes stdin right after
                    # the ack, which ends the loop)
                    send({"op": "ok", "id": req_id})
                else:
                    send({"op": "error", "id": req_id,
                          "error": f"unknown op {op!r}"})
            except Exception as e:
                send({"op": "error", "id": msg.get("id")
                      if isinstance(msg, dict) else None,
                      "error": f"{type(e).__name__}: {e}"[:200]})
    finally:
        if sampler is not None:
            sampler.close()
        svc.close(timeout=60)
        try:
            send({"op": "bye"})
        except (BrokenPipeError, OSError, ValueError):
            pass  # parent already gone: the drain still completed
    return 0


if __name__ == "__main__":
    sys.exit(main())
