"""Profiler trace of part of the window, and its reduction to numbers.

``Tracer`` starts ``jax.profiler`` at the window's start, opens the
``bench.window`` host span, and stops after ``trace_seconds``. ``reduce``
reads the ``.xplane.pb`` with nothing but JAX: busy time is the union of
the op intervals on each device plane's ``XLA Ops`` line inside the
window, idle gaps are named by the benchmark's own host span (``bench.*``)
that covers most of them.
"""
import bisect
import contextlib
import glob
import os
import re
import shutil
import tempfile
import time

WINDOW = "bench.window"
_DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
_OPS_LINES = ("XLA Ops",)
_MODULES_LINE = "XLA Modules"


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def reduce(path: str, top: int = 10) -> dict:
    """{busy_s, window_s, device_ops, idle_gaps} of the traced window; busy
    is averaged over the device planes. None when the trace holds no
    window span or no device operation."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    window = None
    spans = []
    devices = []
    for plane in pd.planes:
        if _DEVICE_PLANE.match(plane.name):
            ops, modules = [], []
            for line in plane.lines:
                if line.name == _MODULES_LINE:
                    modules += [(e.start_ns, e.start_ns + e.duration_ns,
                                 e.name) for e in line.events]
                elif line.name in _OPS_LINES:
                    ops += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events]
            devices.append(_named(ops, sorted(modules)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW:
                        window = (e.start_ns, e.start_ns + e.duration_ns)
                    elif e.name.startswith("bench."):
                        spans.append((e.name, e.start_ns,
                                      e.start_ns + e.duration_ns))
    if window is None or not any(devices):
        return None
    w0, w1 = window
    per_op = {}
    busy_total = 0.0
    gaps = []
    for ops in devices:
        clipped = [(n, max(s, w0), min(e, w1)) for n, s, e in ops
                   if e > w0 and s < w1]
        for n, s, e in clipped:
            per_op[n] = per_op.get(n, 0) + (e - s)
        busy = _union([(s, e) for _, s, e in clipped])
        busy_total += sum(e - s for s, e in busy)
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gaps.append((_name_gap(spans, s, e), (e - s) / 1e9))
    gaps.sort(key=lambda g: -g[1])
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    return {"busy_s": busy_total / 1e9 / len(devices),
            "window_s": (w1 - w0) / 1e9,
            "device_ops": [[n, ns / 1e9] for n, ns in ops],
            "idle_gaps": [[n, s] for n, s in gaps[:top]]}


def _named(ops, modules):
    """Ops renamed ``<module>/<op>``: the HLO instruction's name (the text
    before `` = ``) under the XLA module running when it started."""
    starts = [m[0] for m in modules]
    out = []
    for name, s, e in ops:
        k = bisect.bisect_right(starts, s) - 1
        mod = modules[k][2] if k >= 0 and s < modules[k][1] else "?"
        out.append((f"{mod}/{name.split(' = ')[0]}", s, e))
    return out


def _name_gap(spans, s, e) -> str:
    best, name = 0, "bench.none"
    for n, a, b in spans:
        overlap = min(b, e) - max(a, s)
        if overlap > best:
            best, name = overlap, n
    return name


class Tracer:
    """Traces ``seconds`` of the window when ``on`` (once: a driver starts
    it where its mix says); a no-op otherwise. ``span(name)`` is a host
    annotation while the trace runs. Writing the trace out stalls the
    process for about two minutes per second of device work it holds
    (the VM's loops emit every step), so traces are kept short."""

    def __init__(self, on: bool, seconds: float, counters):
        self.on = on
        self.seconds = seconds
        self.counters = counters
        self.active = False
        self.dir = None
        self.vm_s = None
        self.traced_s = None
        self.stall_s = 0.0  # spent writing the trace out
        self.summary = None

    def span(self, name: str):
        if not self.active:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    def start(self) -> None:
        if not self.on or self.traced_s is not None:
            return
        import jax

        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        # device ops and TraceMe host spans only: the Python tracer would
        # record every call of the host codec's pure-Python field math
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._window = jax.profiler.TraceAnnotation(WINDOW)
        self._window.__enter__()
        self._vm0 = self.counters.read()["vm_s"]
        self._t0 = time.perf_counter()
        self.active = True

    def maybe_stop(self, elapsed: float) -> None:
        if self.active and elapsed >= self.seconds:
            self.stop()

    def stop(self) -> None:
        if not self.active:
            return
        import jax

        self.traced_s = time.perf_counter() - self._t0
        self.vm_s = self.counters.read()["vm_s"] - self._vm0
        self._window.__exit__(None, None, None)
        self.active = False
        t0 = time.perf_counter()
        jax.profiler.stop_trace()
        self.stall_s = time.perf_counter() - t0

    def finish(self) -> None:
        """Stop if still running, reduce the trace, delete it."""
        if not self.on:
            return
        self.stop()
        try:
            paths = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                              recursive=True)
            self.summary = reduce(paths[0]) if paths else None
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
