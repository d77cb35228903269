"""Cross-checks for the Pallas Montgomery-multiply kernel (ops/pallas_fq.py)
against ops/fq.py's jnp lowering and the exact-integer oracle.

Runs the kernel in interpret mode on CPU. Its Mosaic compile for a
described v5e is tests/test_chip_compile.py; on the chip, chip_smoke.py's
Pallas phase holds it bit-identical to the default path.
"""
import numpy as np

from consensus_specs_tpu.utils.jax_env import force_cpu

force_cpu()

from consensus_specs_tpu.ops import fq, pallas_fq  # noqa: E402
from consensus_specs_tpu.utils.bls12_381 import P  # noqa: E402


def _rand_loose(rng, shape, max_bits=401):
    """Random loose Montgomery residues: values < 2^max_bits with limbs
    < 2^28 (the carry invariant every VM register satisfies)."""
    vals = np.zeros(shape + (fq.NUM_LIMBS,), dtype=np.uint64)
    flat = vals.reshape(-1, fq.NUM_LIMBS)
    for i in range(flat.shape[0]):
        x = rng.randrange(1 << max_bits)
        flat[i] = fq._int_to_limbs_np(x)
    return vals


def _as_ints(limbs):
    flat = np.asarray(limbs).reshape(-1, fq.NUM_LIMBS)
    return [fq.limbs_to_int(row) for row in flat]


def test_pallas_mont_mul_matches_oracle_and_fq():
    import random

    rng = random.Random(20260730)
    a = _rand_loose(rng, (5, 3))
    b = _rand_loose(rng, (5, 3))

    got = np.asarray(pallas_fq.mont_mul(a, b))
    want_fq = np.asarray(fq.mont_mul(a, b))

    rinv = pow(fq.R_MONT, -1, P)
    for ga, wa, ia, ib in zip(
        _as_ints(got), _as_ints(want_fq), _as_ints(a), _as_ints(b)
    ):
        # same residue class as the oracle...
        assert ga % P == (ia * ib * rinv) % P
        # ...and within the loose-output magnitude contract
        assert ga < (ia * ib) // fq.R_MONT + P + 1
        assert wa % P == ga % P


def test_pallas_mont_mul_edge_values():
    zero = np.zeros((4, fq.NUM_LIMBS), dtype=np.uint64)
    one = np.broadcast_to(fq.ONE_MONT, (4, fq.NUM_LIMBS)).copy()
    pm1 = np.broadcast_to(
        fq._int_to_limbs_np(P - 1), (4, fq.NUM_LIMBS)
    ).copy()
    maxv = np.full((4, fq.NUM_LIMBS), fq.MASK, dtype=np.uint64)  # 2^420 - 1

    for a, b in [(zero, one), (one, one), (pm1, pm1), (maxv, one), (one, maxv)]:
        got = np.asarray(pallas_fq.mont_mul(a, b))
        want = np.asarray(fq.mont_mul(a, b))
        ga, wa = _as_ints(got), _as_ints(want)
        for g, w in zip(ga, wa):
            assert g % P == w % P
        assert got.max(initial=0) < (1 << 28)


def test_pallas_mont_mul_odd_batch_padding():
    """Batch sizes that are not tile multiples pad with zero lanes."""
    import random

    rng = random.Random(7)
    a = _rand_loose(rng, (3,), max_bits=382)
    b = _rand_loose(rng, (3,), max_bits=382)
    got = np.asarray(pallas_fq.mont_mul(a, b))
    want = np.asarray(fq.mont_mul(a, b))
    for g, w in zip(_as_ints(got), _as_ints(want)):
        assert g % P == w % P


def test_pallas_dispatch_flag(monkeypatch):
    """fq.mont_mul must actually route through the kernel when the flag is
    on (a vacuous mod-p comparison would stay green even if the dispatch
    silently broke — count the kernel calls)."""
    import random

    calls = {"n": 0}
    real = pallas_fq.mont_mul

    def counting(a, b):
        calls["n"] += 1
        return real(a, b)

    monkeypatch.setattr(pallas_fq, "mont_mul", counting)

    rng = random.Random(11)
    a = _rand_loose(rng, (2,), max_bits=382)
    b = _rand_loose(rng, (2,), max_bits=382)

    monkeypatch.setenv("CONSENSUS_SPECS_TPU_PALLAS", "1")
    assert pallas_fq.enabled()
    via_fq = np.asarray(fq.mont_mul(a, b))
    assert calls["n"] == 1, "flag on: fq.mont_mul did not dispatch to the kernel"

    monkeypatch.setenv("CONSENSUS_SPECS_TPU_PALLAS", "0")
    assert not pallas_fq.enabled()
    direct = np.asarray(fq.mont_mul(a, b))
    assert calls["n"] == 1, "flag off: fq.mont_mul still dispatched to the kernel"

    for g, w in zip(_as_ints(via_fq), _as_ints(direct)):
        assert g % P == w % P
