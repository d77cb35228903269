"""chip_smoke.py at a tiny size on the CPU: the phase functions hold the
device answers to the oracle (or the known truth, or the flat path) and
count any fallback as a failure; the script itself refuses to run, and
prints no result, without a TPU or without the repository beside it.

The chip run is ``python chip_smoke.py`` through the chip tool; these
tests only rehearse its control flow and checks.
"""
import json
import os
import shutil
import subprocess
import sys

import pytest

from consensus_specs_tpu.utils.jax_env import force_cpu

force_cpu()

import chip_smoke  # noqa: E402
from consensus_specs_tpu.scale.registry import Registry  # noqa: E402

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def tiny_slot():
    """Four committees of two: a mainnet-shaped slot cut to CPU size."""
    reg = Registry(64, seed=13, slots_per_epoch=8, target_size=2,
                   shuffle_rounds=4)
    return reg, chip_smoke.slot_items(reg)


@pytest.fixture
def device_final(monkeypatch):
    # the chip's hard-part route ('auto' picks it off the CPU)
    monkeypatch.setenv("CONSENSUS_SPECS_TPU_RLC_FINAL", "device")


def _result_lines(stdout):
    lines = []
    for line in stdout.splitlines():
        try:
            lines.append(json.loads(line))
        except ValueError:
            pass
    return [ln for ln in lines if "ok" in ln]


def test_no_tpu_exits_nonzero_without_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=_REPO,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert _result_lines(proc.stdout) == []
    assert "TPU" in proc.stderr


def test_script_alone_exits_nonzero_without_result(tmp_path):
    shutil.copy(os.path.join(_REPO, "chip_smoke.py"), tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0
    assert _result_lines(proc.stdout) == []


def test_slot_items_from_workers_match_committee_items(tiny_slot):
    from consensus_specs_tpu.scale import hierarchy

    reg, items = tiny_slot
    assert chip_smoke.slot_items(reg, procs=2) == items
    assert items == hierarchy.bytes_items(
        hierarchy.committee_items(reg, chip_smoke.SLOT), reg)


def test_switchboard_phase_agrees_with_oracle():
    line = chip_smoke.phase_switchboard(k=4, k_aggregate=2)
    assert line["verdicts"] == {"valid": 3, "invalid": 3}
    assert line["compile_s"] > 0 and line["warm_s"] > 0


def test_serve_phase_matches_ground_truth(tiny_slot, device_final):
    reg, items = tiny_slot
    checks = chip_smoke.serve_checks(reg, items[:2], per_committee=4)
    assert len(checks) > len({id(c) for c, _ in checks})  # duplicates
    assert not all(t for _, t in checks)  # some invalid
    line = chip_smoke.phase_serve(checks)
    assert line["fallback_items"] == 0 and line["mesh_fallbacks"] == 0
    assert line["verdicts"]["invalid"] == sum(not t for _, t in checks)


def test_serve_phase_fails_on_oracle_fallback(tiny_slot, monkeypatch):
    """A device failure the service hides behind the oracle is a smoke
    failure, not a degraded pass."""
    from consensus_specs_tpu.ops import bls_backend

    def broken(*a, **k):
        raise RuntimeError("device lost")

    for name in ("batch_verify_rlc", "batch_fast_aggregate_verify"):
        monkeypatch.setattr(bls_backend, name, broken)
    reg, items = tiny_slot
    checks = chip_smoke.serve_checks(reg, items[:1], per_committee=2)
    with pytest.raises(chip_smoke.SmokeFailure, match="fallback"):
        chip_smoke.phase_serve(checks)


@pytest.fixture
def fresh_counters(monkeypatch):
    """The gate reads process-wide counters that other test files in this
    worker process may have moved; start from a fresh process's zeros."""
    from consensus_specs_tpu.ops import bls_backend, profiling, vm_compile

    monkeypatch.setitem(vm_compile._COUNTERS, "fallbacks", 0)
    monkeypatch.setitem(bls_backend.PREP_STATS, "serial_fallback_items", 0)
    monkeypatch.setattr(bls_backend, "_POOL_BROKEN", False)
    real = profiling.stats_and_gauges

    def fresh():
        stats, gauges = real()
        stats.pop("bls.codec_prewarm_error", None)
        return stats, gauges

    monkeypatch.setattr(profiling, "stats_and_gauges", fresh)


def test_prep_pool_serves_and_fallback_gate(monkeypatch, fresh_counters):
    from consensus_specs_tpu.ops import vm_compile

    assert chip_smoke.prep_pool_check(n=16)["items"] == 16
    assert chip_smoke.no_fallbacks()["vm.fused_fallbacks"] == 0
    monkeypatch.setitem(vm_compile._COUNTERS, "fallbacks", 1)
    with pytest.raises(chip_smoke.SmokeFailure, match="fallback"):
        chip_smoke.no_fallbacks()


@pytest.mark.parametrize("fallback", ["codec_prewarm", "serial_fallback"])
def test_host_prep_fallbacks_fail_the_gate(monkeypatch, fresh_counters,
                                          fallback):
    """A batched-codec failure (its prewarm falls through to the pool,
    e.g. the codec device route refused on a TPU) or host work degraded
    from a pool to serial counts as a fallback."""
    from consensus_specs_tpu.ops import bls_backend, profiling

    if fallback == "codec_prewarm":
        real = profiling.stats_and_gauges

        def with_error():
            stats, gauges = real()
            stats["bls.codec_prewarm_error"] = {"calls": 1, "total_s": 0.0,
                                                "max_s": 0.0}
            return stats, gauges

        monkeypatch.setattr(profiling, "stats_and_gauges", with_error)
    else:
        monkeypatch.setitem(bls_backend.PREP_STATS, "serial_fallback_items",
                            3)
    with pytest.raises(chip_smoke.SmokeFailure, match=fallback):
        chip_smoke.no_fallbacks()
