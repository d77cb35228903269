"""The chip and the program's process-wide counters.

``prepare`` runs before JAX is imported: it fixes the compile caches inside
the checkout and builds ``csrc/*.so`` (as ``chip_smoke.build_native``
does). Everything else reads what the program and JAX count, as deltas
between two ``Counters.read()`` calls.
"""
import os
import subprocess

NATIVE = (("sha256_batch.c", "libsha256_batch.so"),
          ("vm_sched.c", "libvmsched.so"))
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoAccelerator(RuntimeError):
    pass


def prepare(root: str) -> None:
    """Compile caches at fixed paths inside the checkout (the path is part
    of the cache key, and the two sides of a check must share nothing),
    then the native kernels (gcc to a temp name, then rename)."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(root, ".jax_cache")
    os.environ["CONSENSUS_SPECS_TPU_VM_CACHE"] = os.path.join(root, ".vm_cache")
    for src, lib in NATIVE:
        src_p = os.path.join(root, "csrc", src)
        lib_p = os.path.join(root, "csrc", lib)
        if (os.path.exists(lib_p)
                and os.path.getmtime(lib_p) >= os.path.getmtime(src_p)):
            continue
        tmp = f"{lib_p}.{os.getpid()}.tmp"
        subprocess.run(["gcc", "-O3", "-fPIC", "-shared", "-o", tmp, src_p],
                       check=True)
        os.replace(tmp, lib_p)


def start_jax(chips: int, require_tpu: bool = True):
    """Import the program's compute plane and return the devices; with no
    TPU, or fewer chips than the cell asks for, raise NoAccelerator."""
    import jax

    import consensus_specs_tpu.ops  # noqa: F401  (x64, cache placement)

    devices = jax.devices()
    if not require_tpu:
        return devices
    if devices[0].platform != "tpu" or len(devices) < chips:
        raise NoAccelerator(f"need {chips} TPU chip(s), JAX found "
                            f"{len(devices)} {devices[0].platform} device(s)")
    # every program goes into the persistent cache, however fast it
    # compiled, so that a second run of a cell compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return devices


def describe(devices) -> dict:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


class Counters:
    """Compiles seen by JAX, VM executions by route, and every fallback that
    would hide the device (``chip_smoke.no_fallbacks`` plus the service's
    retry and oracle rungs)."""

    def __init__(self):
        import jax

        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, _secs, **_kw):
        if name == COMPILE_EVENT:
            self.compiles += 1

    def read(self) -> dict:
        from consensus_specs_tpu.ops import bls_backend, profiling, vm_compile

        stats, _ = profiling.stats_and_gauges()
        vm_calls = sum(s["calls"] for k, s in stats.items()
                       if k.startswith("vm["))
        fused = vm_compile._COUNTERS["executions"]
        return {
            "compiles": self.compiles,
            "vm_fused": fused,
            "vm_interp": vm_calls - fused,
            "vm_s": sum(s["total_s"] for k, s in stats.items()
                        if k.startswith("vm[")),
            "vm_shapes": {k for k in stats if k.startswith("vm[")},
            "final_exps": bls_backend.RLC_STATS["final_exps"],
            "combines": bls_backend.RLC_STATS["combines"],
            "bisections": bls_backend.RLC_STATS["bisections"],
            "fallbacks": {
                "vm.fused_fallbacks": vm_compile._COUNTERS["fallbacks"],
                "bls.prep_pool_broken": int(bool(bls_backend._POOL_BROKEN)),
                "bls.codec_prewarm_errors":
                    stats.get("bls.codec_prewarm_error", {}).get("calls", 0),
                "bls.prep_serial_fallback_items":
                    bls_backend.PREP_STATS["serial_fallback_items"],
                "serve.rlc_errors":
                    stats.get("serve.rlc_error", {}).get("calls", 0),
                "serve.prep_errors":
                    stats.get("serve.prep_error", {}).get("calls", 0),
            },
        }


def delta(after: dict, before: dict) -> dict:
    out = {}
    for k, v in after.items():
        out[k] = delta(v, before[k]) if isinstance(v, dict) else v - before[k]
    return out
