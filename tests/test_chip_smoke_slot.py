"""chip_smoke.py's slot and Pallas phases at a tiny size on the CPU: the
corrupted committee is localized on the device hard-part route (one
device and a 4-device virtual mesh with every device holding a shard),
and the Pallas modes are bit-identical to the default. Rehearsal only;
the chip run is ``python chip_smoke.py`` through the chip tool.
"""
import pytest

from consensus_specs_tpu.utils.jax_env import force_cpu

force_cpu()

import chip_smoke  # noqa: E402
from consensus_specs_tpu.scale.registry import Registry  # noqa: E402


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


def test_slot_phase_localizes_the_bad_committee(tiny_slot, device_final):
    _, items = tiny_slot
    line = chip_smoke.phase_slot(items, bad=1)
    assert line["bad_committees"] == [1]
    assert line["verdicts"] == {"valid": 3, "invalid": 1}
    assert line["verdict_bits"] == "1011" and line["mesh_reductions"] == 0
    assert line["final_route"] == "device" and line["final_exp_windows"] > 0


def test_slot_phase_refuses_the_host_final_route(tiny_slot, monkeypatch):
    monkeypatch.setenv("CONSENSUS_SPECS_TPU_RLC_FINAL", "host")
    _, items = tiny_slot
    with pytest.raises(chip_smoke.SmokeFailure, match="not device"):
        chip_smoke.phase_slot(items, bad=1)


def test_slot_phase_on_a_mesh_uses_every_device(tiny_slot, device_final):
    from consensus_specs_tpu.utils import jax_env

    mesh = jax_env.get_mesh("4")
    _, items = tiny_slot
    line, n_exec = chip_smoke.mesh_shards(
        mesh, lambda: chip_smoke.phase_slot(items, bad=2, mesh=mesh))
    assert n_exec > 0
    assert line["bad_committees"] == [2]
    assert line["verdict_bits"] == "1101"
    assert line["mesh_reductions"] > 0


def test_slot_phase_on_a_mesh_fails_without_a_mesh_reduction(
        tiny_slot, device_final, monkeypatch):
    """A mesh run whose combines never fold across the devices (every
    combine a single chunk) is a failure, not a pass."""
    from consensus_specs_tpu.ops import bls_backend
    from consensus_specs_tpu.utils import jax_env

    monkeypatch.setattr(bls_backend, "_rlc_chunk",
                        lambda m, mesh=None: bls_backend._pow2(m))
    _, items = tiny_slot
    with pytest.raises(chip_smoke.SmokeFailure, match="across the mesh"):
        chip_smoke.phase_slot(items, bad=2, mesh=jax_env.get_mesh("4"))


def test_pallas_phase_is_bit_identical():
    line = chip_smoke.phase_pallas(batch=1)
    assert line["verdicts"] == {"identical": 2, "different": 0}
    assert set(line["compile_s"]) == {"0", "1", "step"}
