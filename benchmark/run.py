"""The benchmark's command:

    python3 -m benchmark.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds the native kernels, finds the TPU (with none, or too few chips, it
exits non-zero and prints no result), makes the cell's inputs from the
seed, warms the cell's own shapes, measures for ``--seconds``, checks the
verdicts against the plain reference, and prints one JSON result as the
last line of standard output. With ``--trace 0`` the metrics are the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics.

This module is imported again by every spawned worker (as the main
module), so its top level touches neither JAX nor the program.
"""
import argparse
import contextlib
import gc
import json
import os
import sys
import time

from . import cells, judge

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class Run:
    """One run of one cell: what the drivers share."""

    def __init__(self, cell, seed, seconds, backend=None):
        self.cell = cell
        self.seed = seed
        self.seconds = seconds
        self.backend = backend  # None: the program's own bls_backend
        if backend is None:
            from consensus_specs_tpu.ops import bls_backend

            backend = bls_backend
        self.program_backend = backend
        self.errors = []
        self.phases = {}

    def keys_ready(self, keys, workers) -> None:
        """A backend that stands in for the program (the control) is given
        the generator's keys and the reference workers once they exist."""
        bind = getattr(self.backend, "bind", None)
        if bind is not None:
            bind(keys, workers)

    def note_error(self, e: BaseException) -> None:
        self.errors.append(f"{type(e).__name__}: {e}"[:300])

    @contextlib.contextmanager
    def phase(self, name: str):
        t0 = time.perf_counter()
        yield
        self.phases[name] = time.perf_counter() - t0


class GcPauses:
    """The garbage collector's passes while it is on: how many, of which
    generation, and how long the longest held the process."""

    def __init__(self):
        self.passes = [0, 0, 0]
        self.max_ms = 0.0
        self.total_ms = 0.0
        self._t0 = None
        gc.callbacks.append(self._event)

    def _event(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            ms = (time.perf_counter() - self._t0) * 1e3
            self.passes[info["generation"]] += 1
            self.max_ms = max(self.max_ms, ms)
            self.total_ms += ms

    def close(self) -> dict:
        gc.callbacks.remove(self._event)
        return {"passes": self.passes, "max_ms": self.max_ms,
                "total_ms": self.total_ms}


def execute(cell, seed: int, seconds: float, trace: bool, *, backend=None,
            require_tpu: bool = True, procs: int = None, t_start=None):
    """One run; returns (result, info). Tests call this with a stand-in
    backend and ``require_tpu=False``; the command never does."""
    from . import device
    from .trace import Tracer

    t_start = time.perf_counter() if t_start is None else t_start
    with judge.pool(procs) as workers:
        devices = device.start_jax(cell.chips, require_tpu)
        counters = device.Counters()
        run = Run(cell, seed, seconds, backend)
        run.phases["start"] = time.perf_counter() - t_start
        driver = cell.driver()(run)
        c0 = counters.read()
        driver.setup(workers, run.phase)
        # what set-up made lives to the end of the run: moved out of the
        # collector's reach, a full pass in the window scans only what the
        # window makes
        gc.collect()
        gc.freeze()
        setup_s = time.perf_counter() - t_start
        tracer = Tracer(trace, float(cell.mix["trace_seconds"]), counters)
        c1 = counters.read()
        pauses = GcPauses()
        try:
            e2e = driver.window(tracer)
        finally:
            gc_window = pauses.close()
            gc.unfreeze()
        c2 = counters.read()
        tracer.finish()
        peak = device.memory_peak(devices)
        sample = driver.sample()
        t_ref = time.perf_counter()
        verdicts = judge.reference_verdicts(workers, driver.keys, sample)
        ref_s = time.perf_counter() - t_ref
    compared = judge.compare(driver.answers, sample, verdicts)
    whole = device.delta(c2, c0)
    window = device.delta(c2, c1)
    fallbacks = dict(whole["fallbacks"], **driver.fallbacks())
    compared["fallbacks"] = sum(fallbacks.values())
    correct = not run.errors and all(
        compared[k] <= judge.LIMITS[k] for k in judge.LIMITS)

    dev = device.describe(devices)
    dev["memory_peak_bytes"] = peak
    metrics = {}
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    if trace:
        ctx = {"trace": tracer.summary, "vm_s": tracer.vm_s,
               "traced_s": tracer.traced_s, "checks": driver.attempted(),
               "window": window}
        ctx.update(driver.context())
        for m in cell.per_layer:
            value = cell.reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if tracer.summary is not None:
            dev["busy_s"] = tracer.summary["busy_s"]
            dev["window_s"] = tracer.summary["window_s"]
    else:
        e2e["setup_s"] = setup_s
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": units[m["name"]]}
    result = {"correct": correct, "attempted": driver.attempted(),
              "failed": compared["missing"], "metrics": metrics, "device": dev}
    if trace and tracer.summary is not None:
        result["breakdown"] = {"device_ops": tracer.summary["device_ops"],
                               "idle_gaps": tracer.summary["idle_gaps"]}
    result["compared"] = {k: {"value": compared[k], "limit": judge.LIMITS[k]}
                          for k in judge.LIMITS}
    info = {"workload": cell.name, "seed": seed, "seconds": seconds,
            "trace": int(trace), "device": dev,
            "window_s": driver.window_s, "setup_phases": run.phases,
            "compiles_in_window": window["compiles"],
            "vm_shapes_new_in_window": sorted(window["vm_shapes"]),
            "vm_executions_in_window": {"fused": window["vm_fused"],
                                        "interp": window["vm_interp"]},
            "rlc_in_window": {k: window[k] for k in
                              ("combines", "bisections", "final_exps")},
            "gc_in_window": gc_window,
            "fallbacks": fallbacks, "reference_checks": len(sample),
            "reference_s": ref_s, "errors": run.errors,
            "jax_cache_mb": _dir_mb(os.environ.get("JAX_COMPILATION_CACHE_DIR"))}
    info.update(driver.info())
    return result, info


def _dir_mb(path):
    if not path or not os.path.isdir(path):
        return None
    return sum(os.path.getsize(os.path.join(d, f)) for d, _, fs in os.walk(path)
               for f in fs) / 2**20


def main(argv=None) -> int:
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = cells.load(ROOT, args.workload)
        from . import device

        device.prepare(ROOT)
        result, info = execute(cell, args.seed, args.seconds,
                               bool(args.trace), t_start=t_start)
    except Exception as e:
        import traceback

        traceback.print_exc()
        print(f"benchmark: FAIL {type(e).__name__}: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"info": info}), flush=True)
    for k, v in result["compared"].items():
        print(f"compared {k} {v['value']} limit {v['limit']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
