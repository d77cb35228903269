"""Fresh-process time-to-fused-ready probe (ISSUE 15).

Run as a CHILD process (``python -m consensus_specs_tpu.bench.vmexec_cold``)
so nothing is warm: it measures the wall seconds from process entry
(heavy imports included) to a fused-ready program — ``bls_backend
._program`` resolution, structural-plan derivation/load, and
``warm_fused`` for one batch shape — then spot-checks one fused
execution bit-identical to the interpreter. Emits one machine-readable
line::

    VMEXEC_COLD_JSON {"ok": true, "ready_s": ..., "distinct_structs": ...}

The vmexec bench (`make vmexec-bench`) runs two arms, each against a
FRESH persistent-XLA-cache dir: structural dedup on (the default) and
``CONSENSUS_SPECS_TPU_VM_DEDUP=0`` (the PR 13 one-compile-per-chunk
baseline) — their ready_s ratio is the ISSUE 15 acceptance number
(>= 5x for the 955-level g2_subgroup ladder).

``--smoke`` is the CI entry (`make vmexec-cold-smoke`): it forces a
fresh temp XLA cache itself, asserts the process REACHES fused-ready
with bit-identity (exit 1 otherwise), and reports the seconds against
the VMEXEC_COLD_BUDGET_S budget (default 180) — over-budget is a
warning here, not a failure: the budget is STATE-gated round over round
through the bench's cold cells by tools/bench_compare.py, mirroring how
VMEXEC cells gate, rather than hard-failing CI on a slow runner.

Env: VMEXEC_COLD_KIND (default g2_subgroup), VMEXEC_COLD_K (default 0),
VMEXEC_COLD_ROWS (default 1), VMEXEC_COLD_SEED, VMEXEC_COLD_BUDGET_S.
"""
import json
import os
import random
import sys
import time


def main(argv=None) -> int:
    t0 = time.monotonic()
    args = sys.argv[1:] if argv is None else argv
    smoke = "--smoke" in args
    smoke_cache = None
    if smoke:
        # a COLD cache pair is the point of the smoke: a pre-warmed
        # runner XLA cache (or a pre-derived .vm_cache plan) would make
        # the number meaningless (deleted on the way out)
        import tempfile

        smoke_cache = tempfile.mkdtemp(prefix="vmexec_cold_xla_")
        os.environ["JAX_COMPILATION_CACHE_DIR"] = smoke_cache
        os.environ["CONSENSUS_SPECS_TPU_VM_CACHE"] = os.path.join(
            smoke_cache, "vm")

    kind = os.environ.get("VMEXEC_COLD_KIND", "g2_subgroup")
    k = int(os.environ.get("VMEXEC_COLD_K", "0") or 0)
    rows = int(os.environ.get("VMEXEC_COLD_ROWS", "1") or 1)
    budget_s = float(os.environ.get("VMEXEC_COLD_BUDGET_S", "180"))

    from ..utils.jax_env import force_cpu

    force_cpu()

    import numpy as np

    from ..ops import bls_backend as bb, fq, vm, vm_compile
    from ..utils import bls12_381 as O

    result = {
        "ok": False,
        "kind": kind,
        "rows": rows,
        "dedup": vm_compile.dedup_enabled(),
        "budget_s": budget_s,
    }
    try:
        program, _fold = bb._program(kind, k, 1)
        t_prog = time.monotonic()
        fp = vm_compile.fused_program(program)
        warm_s = vm_compile.warm_fused(program, (rows,))
        ready_s = time.monotonic() - t0
        result.update(
            ready_s=round(ready_s, 1),
            program_s=round(t_prog - t0, 1),
            warm_s=round(warm_s, 1),
            within_budget=bool(ready_s <= budget_s),
            struct_misses=vm_compile._COUNTERS["struct_misses"],
            **fp.struct_stats,
        )
        rng = random.Random(int(os.environ.get("VMEXEC_COLD_SEED", "5")))
        ins = {
            name: np.stack([fq.to_mont_int(rng.randrange(O.P))
                            for _ in range(rows)])
            for name in program.input_names
        }
        os.environ["CONSENSUS_SPECS_TPU_VM_EXEC"] = "fused"
        out_f = vm.execute(program, ins, batch_shape=(rows,))
        os.environ["CONSENSUS_SPECS_TPU_VM_EXEC"] = "interp"
        out_i = vm.execute(program, ins, batch_shape=(rows,))
        identical = set(out_f) == set(out_i) and all(
            np.array_equal(np.asarray(out_f[name]),
                           np.asarray(out_i[name]))
            for name in out_f)
        result["identical"] = bool(identical)
        result["ok"] = bool(identical)
    except Exception as e:
        result["error"] = f"{type(e).__name__}: {e}"[:300]

    print("VMEXEC_COLD_JSON " + json.dumps(result), flush=True)
    if smoke_cache:
        import shutil

        shutil.rmtree(smoke_cache, ignore_errors=True)
    if smoke:
        if not result["ok"]:
            print(f"vmexec-cold-smoke FAIL: {result}")
            return 1
        verdict = ("within" if result.get("within_budget")
                   else "OVER (report-only — bench_compare state-gates it)")
        print(
            f"vmexec-cold-smoke: OK — {kind} rows={rows} fused-ready in "
            f"{result['ready_s']}s ({result['distinct_structs']} distinct "
            f"structures / {result['chunks']} chunks, window "
            f"{result['window']}), {verdict} the {budget_s:.0f}s budget")
        return 0
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
