"""bench.py's default mode: one process, on the device JAX finds.

A run that was asked for an accelerator and found none prints an error
line and exits non-zero — never a CPU number under the chip metric. An
explicit ``JAX_PLATFORMS=cpu`` run names the CPU it ran on. Also pins
where the persistent compile cache goes (``ops/__init__.py`` is the one
place that sets it) and that fused-plan measurements are keyed by the
device kind.
"""
import importlib.util
import json
import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_BENCH = os.path.join(_REPO, "bench.py")


@pytest.fixture(scope="module")
def bench():
    spec = importlib.util.spec_from_file_location("bench_under_test", _BENCH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run(args, env_updates, drop=()):
    env = dict(os.environ)
    for k in drop:
        env.pop(k, None)
    env.update(env_updates)
    return subprocess.run([sys.executable] + args, cwd=_REPO, env=env,
                          capture_output=True, text=True, timeout=300)


def _json_lines(stdout):
    out = []
    for line in stdout.splitlines():
        try:
            out.append(json.loads(line))
        except ValueError:
            pass
    return out


@pytest.mark.parametrize("platforms", ["tpu", None])
def test_bench_without_accelerator_exits_nonzero_with_no_number(platforms):
    """Asked for a TPU (or for whatever JAX finds) on a machine with none:
    one error line, no value, a failing exit."""
    env = {"BENCH_N": "1", "BENCH_K": "1", "BENCH_REPS": "1"}
    if platforms is None:
        proc = _run([_BENCH], env, drop=("JAX_PLATFORMS",))
    else:
        proc = _run([_BENCH], dict(env, JAX_PLATFORMS=platforms))
    assert proc.returncode != 0
    lines = _json_lines(proc.stdout)
    assert lines and all("value" not in ln for ln in lines)
    assert "error" in lines[-1]


def test_device_info_refuses_an_unrequested_cpu(bench, monkeypatch):
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    with pytest.raises(bench.NoAccelerator):
        bench.device_info()


def test_device_info_names_an_explicit_cpu(bench, monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    info = bench.device_info()
    assert info["platform"] == "cpu" and info["count"] >= 1
    assert info["kind"]


def test_workload_params_defaults_and_overrides(bench, monkeypatch):
    for v in ("BENCH_N", "BENCH_K", "BENCH_REPS", "BENCH_MODE"):
        monkeypatch.delenv(v, raising=False)
    assert bench._workload_params() == (32, 128, 3, "committee")
    monkeypatch.setenv("BENCH_MODE", "epoch")
    monkeypatch.setenv("BENCH_K", "8")
    assert bench._workload_params() == (32, 8, 3, "epoch")


def test_bench_has_no_child_watchdog_or_fallback(bench):
    """The old harness is gone: no child flag, no init watchdog,
    no best-line selection across a child's output."""
    for name in ("_CHILD_FLAG", "_init_backend_with_watchdog", "_best_line",
                 "_run_child_attempt"):
        assert not hasattr(bench, name), name


def _cache_dir_in_fresh_process(env_updates, drop=()):
    proc = _run(["-c", (
        "import jax, consensus_specs_tpu.ops.bls_backend; "
        "print(jax.config.jax_compilation_cache_dir)")],
        dict(env_updates, JAX_PLATFORMS="cpu"), drop=drop)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return proc.stdout.strip().splitlines()[-1]


def test_compile_cache_honours_jax_compilation_cache_dir(tmp_path):
    want = str(tmp_path / "outside_cache")
    assert _cache_dir_in_fresh_process(
        {"JAX_COMPILATION_CACHE_DIR": want}) == want


def test_compile_cache_defaults_to_repo_jax_cache():
    got = _cache_dir_in_fresh_process(
        {}, drop=("JAX_COMPILATION_CACHE_DIR",))
    assert got == os.path.join(_REPO, ".jax_cache")


def test_fused_plan_path_carries_the_device_kind():
    from consensus_specs_tpu.ops import bls_backend, vm_compile

    program, _ = bls_backend._program("g1_subgroup")
    tag = vm_compile.device_kind_tag()
    assert tag == "cpu"
    path = vm_compile._plan_cache_path(program)
    assert os.path.basename(path).endswith(f"_d{tag}.pkl")
    # the cache-prune parser still reads the keyed name
    assert not bls_backend._vm_cache_entry_stale(os.path.basename(path))
