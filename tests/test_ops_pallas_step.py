"""Cross-checks for the fused VM-step Pallas kernel (ops/pallas_step.py):
one kernel doing both ALU units on a 14-bit uint32 register file must be
bit-identical to the default u64 scan path (ops/vm.py _vm_step).

Runs in interpret mode on CPU (Mosaic compilation needs real hardware;
chip_smoke.py holds the Pallas modes bit-identical on the chip).
"""
import numpy as np

from consensus_specs_tpu.utils.jax_env import force_cpu

force_cpu()

import jax.numpy as jnp  # noqa: E402
import pytest  # noqa: E402

from consensus_specs_tpu.ops import fq, pallas_step, vm  # noqa: E402


def _rand_loose(rng, shape, max_bits=401):
    vals = np.zeros(shape + (fq.NUM_LIMBS,), dtype=np.uint64)
    flat = vals.reshape(-1, fq.NUM_LIMBS)
    for i in range(flat.shape[0]):
        flat[i] = fq._int_to_limbs_np(rng.randrange(1 << max_bits))
    return vals


def test_split_join_roundtrip():
    import random

    rng = random.Random(7)
    x = _rand_loose(rng, (4, 3))
    back = np.asarray(pallas_step.join14(pallas_step.split14(x)))
    assert np.array_equal(back, x)


def test_fused_step_matches_u64_step():
    """One synthetic VM step — random operands on both units, mixed
    add/sub lanes — through the fused kernel vs the u64 scan body."""
    import random

    rng = random.Random(13)
    batch, w_mul, w_lin, n_regs = 3, 8, 16, 64

    regs = _rand_loose(rng, (batch, n_regs))
    # sub lanes need b <= MP (the borrowless shift bound): use sub-2^382
    # values on the b side, the compress-output bound every real program
    # maintains (vm.Prog.sub compresses b first)
    msa = np.array([rng.randrange(n_regs) for _ in range(w_mul)], np.int32)
    msb = np.array([rng.randrange(n_regs) for _ in range(w_mul)], np.int32)
    lsa = np.array([rng.randrange(n_regs) for _ in range(w_lin)], np.int32)
    lsb = np.array([rng.randrange(n_regs) for _ in range(w_lin)], np.int32)
    lsub = np.array([rng.random() < 0.5 for _ in range(w_lin)])
    for r in set(lsb[lsub].tolist()):
        regs[:, r] = _rand_loose(rng, (batch,), max_bits=381)
    dests = rng.sample(range(n_regs), w_mul + w_lin)
    msd = np.array(dests[:w_mul], np.int32)
    lsd = np.array(dests[w_mul:], np.int32)
    instr = (msa, msb, msd, lsa, lsb, lsub, lsd)

    want, _ = vm._vm_step(jnp.asarray(regs), tuple(jnp.asarray(x) for x in instr))

    regs14 = pallas_step.split14(jnp.asarray(regs))
    got14, _ = vm._vm_step14(regs14, tuple(jnp.asarray(x) for x in instr))
    got = pallas_step.join14(got14)

    assert np.array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.slow
def test_full_program_matches_u64_path(monkeypatch):
    """A real assembled pairing program end-to-end: vm.execute in fused
    mode must return bit-identical outputs to the default path."""
    from __graft_entry__ import _example_program_and_inputs

    prog, regs, _ = _example_program_and_inputs(batch=2)
    # recover the named inputs from the loaded register file
    ins = {
        name: np.asarray(regs[..., int(r), :])
        for name, r in zip(prog.input_names, prog.input_regs)
    }

    want = vm.execute(prog, ins, batch_shape=(2,))
    monkeypatch.setenv("CONSENSUS_SPECS_TPU_PALLAS", "step")
    got = vm.execute(prog, ins, batch_shape=(2,))

    assert want.keys() == got.keys()
    for name in want:
        assert np.array_equal(got[name], want[name]), name


@pytest.mark.slow  # ~20 s/mode of 8-device mesh compiles (and the jax<0.5
# shard_map fallback only recently made these runnable at all — they were
# collection-time AttributeErrors before; the --run-slow lane keeps them)
@pytest.mark.parametrize("mode", ["1", "step"])
def test_pallas_modes_under_mesh(monkeypatch, mode):
    """Pallas dispatch under an 8-device mesh: a pallas_call is opaque to
    GSPMD, so these modes route through shard_map — every device traces
    its own per-shard kernel on its batch slice. Outputs must be
    bit-identical to the unsharded u64 path."""
    import jax
    from jax.sharding import Mesh

    from __graft_entry__ import _example_program_and_inputs

    prog, regs, _ = _example_program_and_inputs(batch=8)
    ins = {
        name: np.asarray(regs[..., int(r), :])
        for name, r in zip(prog.input_names, prog.input_regs)
    }
    want = vm.execute(prog, ins, batch_shape=(8,))

    mesh = Mesh(np.array(jax.devices("cpu")[:8]), ("batch",))
    monkeypatch.setenv("CONSENSUS_SPECS_TPU_PALLAS", mode)
    got = vm.execute(prog, ins, batch_shape=(8,), mesh=mesh)

    assert want.keys() == got.keys()
    for name in want:
        assert np.array_equal(got[name], want[name]), name
