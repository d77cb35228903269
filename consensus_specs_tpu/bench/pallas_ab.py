"""In-window Pallas A/B: hand-tiled Montgomery-multiply kernel
(`ops/pallas_fq.py`) vs the jnp uint64 lowering of `ops/fq.mont_mul`,
on whatever device JAX resolved.

This is the measurement SURVEY §7.3 ranks as research risk #1-#2 and the
round-4 verdict asks for: it decides whether the
CONSENSUS_SPECS_TPU_PALLAS dispatch defaults on. Run it on the chip
through the chip tool, in the process that holds the chip.

Both sides are jit-wrapped identically and validated on a chained
product (each kernel consuming its own output for `iters` rounds), so a
reported ratio is backed by bit-exact agreement with the host oracle.
"""
import time


def run_pallas_ab(batch: int = 4096, iters: int = 32) -> dict:
    """Returns a dict with per-side mul/s, the pallas/u64 ratio, and
    chained-product match flags. Raises on device failure — the caller
    (bench child stage 3) turns that into a probe_error line."""
    import jax
    import numpy as np

    from ..ops import fq, pallas_fq

    xs = [(i * 0x9E3779B97F4A7C15 + 1) % fq.P for i in range(batch)]
    a = np.stack([fq.to_mont_int(x) for x in xs])
    b = np.stack([fq.to_mont_int((x * 7 + 3) % fq.P) for x in xs])
    da, db = jax.device_put(a), jax.device_put(b)

    chain_want = xs[0]
    b0 = (xs[0] * 7 + 3) % fq.P
    for _ in range(iters):
        chain_want = chain_want * b0 % fq.P

    def side(fn):
        f = jax.jit(fn)
        t0 = time.time()
        f(da, db).block_until_ready()
        compile_s = time.time() - t0
        t0 = time.time()
        out = da
        for _ in range(iters):
            out = f(out, db)
        out.block_until_ready()
        run_s = time.time() - t0
        match = fq.from_mont_limbs(np.asarray(out)[0]) == chain_want
        return batch * iters / run_s, compile_s, match

    # baseline MUST be the u64 lowering itself — fq.mont_mul dispatches to
    # the Pallas kernel under CONSENSUS_SPECS_TPU_PALLAS=1, which would
    # silently turn this into a Pallas-vs-Pallas non-measurement
    u64_rate, u64_compile, u64_match = side(lambda u, v: fq.mont_mul_u64(u, v))
    pl_rate, pl_compile, pl_match = side(pallas_fq.mont_mul)

    return {
        "platform": jax.default_backend(),
        "u64_mul_per_s": round(u64_rate),
        "u64_compile_s": round(u64_compile, 1),
        "u64_chain_match": bool(u64_match),
        "pallas_mul_per_s": round(pl_rate),
        "pallas_compile_s": round(pl_compile, 1),
        "pallas_chain_match": bool(pl_match),
        "pallas_over_u64": round(pl_rate / u64_rate, 3),
    }


def run_step_ab(batch: int = 128, reps: int = 3) -> dict:
    """Whole-VM-program A/B across the three dispatch modes — '0' (u64
    scan), '1' (mont_mul-only Pallas), 'step' (fused mul+lin kernel on the
    14-bit register file, ops/pallas_step.py) — on one real assembled
    pairing program. This is the measurement that decides the production
    CONSENSUS_SPECS_TPU_PALLAS default. A mode's speedup ratio is emitted
    ONLY if its outputs matched mode '0' bit-for-bit; a mismatching mode
    reports its raw timings and match=False, never a headline ratio."""
    import os
    import time

    import numpy as np

    from __graft_entry__ import _example_program_and_inputs
    from ..ops import vm

    prog, regs, _ = _example_program_and_inputs(batch=batch)
    ins = {
        name: np.asarray(regs[..., int(r), :])
        for name, r in zip(prog.input_names, prog.input_regs)
    }

    def run_mode(value):
        old = os.environ.get("CONSENSUS_SPECS_TPU_PALLAS")
        os.environ["CONSENSUS_SPECS_TPU_PALLAS"] = value
        try:
            t0 = time.time()
            out = vm.execute(prog, ins, batch_shape=(batch,))
            compile_s = time.time() - t0
            t0 = time.time()
            for _ in range(reps):
                out = vm.execute(prog, ins, batch_shape=(batch,))
            run_s = (time.time() - t0) / reps
        finally:
            if old is None:
                os.environ.pop("CONSENSUS_SPECS_TPU_PALLAS", None)
            else:
                os.environ["CONSENSUS_SPECS_TPU_PALLAS"] = old
        return out, compile_s, run_s

    import jax

    result = {
        "platform": jax.default_backend(),
        "batch": batch,
        "n_steps": prog.n_steps,
    }
    baseline = None
    rates = {}
    matched = {}
    for mode, tag in (("0", "u64"), ("1", "mont"), ("step", "fused")):
        out, compile_s, run_s = run_mode(mode)
        if baseline is None:
            baseline = out
            match = True
        else:
            match = all(
                np.array_equal(out[k], baseline[k]) for k in baseline
            )
        result[f"{tag}_compile_s"] = round(compile_s, 1)
        result[f"{tag}_run_s"] = round(run_s, 3)
        result[f"{tag}_match"] = bool(match)
        rates[tag] = run_s
        matched[tag] = match
    for tag in ("mont", "fused"):
        if matched[tag]:  # a broken kernel never gets a headline ratio
            result[f"{tag}_over_u64"] = round(rates["u64"] / rates[tag], 3)
    return result
