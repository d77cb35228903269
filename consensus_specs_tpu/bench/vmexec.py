"""VM execution-backend microbenchmark (`make vmexec-bench`, ISSUE 13).

Races the scan INTERPRETER against the FUSED straight-line lowering
(ops/vm_compile.py) on identical assembled programs and identical random
field inputs, per (program kind, rows) cell:

  vmexec[kind,rows] -> {
    ok              fused outputs bit-identical to interpreted outputs
                    (full limb identity on every named output),
    interp_ms_row   warm interpreter wall ms / row,
    fused_ms_row    warm fused wall ms / row,
    fused_compile_s trace + XLA-compile wall seconds the fused pipeline
                    paid for this batch shape (0.0 in-process warm;
                    ~persistent-cache-hit cost on later processes),
    speedup         interp_ms_row / fused_ms_row,
  }

Cells are state-gated round over round by tools/bench_compare.py
("VMEXEC ERRORED", mirror of FINALEXP ERRORED — a kind losing its fused
backend, or the two backends disagreeing bitwise, fails the round);
the ms/row and speedup movement is report-only.

Because every warm fused cell ALSO persists its measured ms/row pair
into the program's `.vm_cache` lowering plan, running this bench is what
teaches `CONSENSUS_SPECS_TPU_VM_EXEC=auto` processes on the same machine
which backend wins each program — a later process serves fused for any
shape it warms (`vm_compile.warm_fused`/a pinned-`fused` call) without
re-measuring the interpreter first.

COLD-START CELLS (ISSUE 15). After the warm race, the bench measures
fresh-process time-to-fused-ready by spawning one CHILD per arm
(consensus_specs_tpu/bench/vmexec_cold.py), each against a FRESH
persistent-XLA-cache dir: ``cold,<kind>`` (structural dedup on; its
``ok`` additionally requires ready_s within VMEXEC_COLD_BUDGET_S —
default 180 s — so the seconds-scale claim is STATE-gated round over
round like every other vmexec cell) and ``cold_nodedup,<kind>`` (the
PR 13 one-compile-per-chunk baseline, ok = reached + bit-identical).
The headline ``cold_speedup`` is their ready_s ratio — the ISSUE 15
acceptance number (>= 5x for the 955-level g2_subgroup ladder).

Env: VMEXEC_KINDS (default "g2_subgroup,h2g_finish,hard_part_frobenius"
— a full-registry sweep costs one XLA compile per kind per rows value;
pass a comma list to resize), VMEXEC_ROWS (default "1,8"), VMEXEC_REPS
(default 2), VMEXEC_K (per-item size for the k-carrying kinds, default
2), VMEXEC_SEED (default 7), VMEXEC_COLD (1 = both cold arms, "dedup" =
skip the minutes-scale baseline arm, 0 = skip cold cells),
VMEXEC_COLD_KIND / VMEXEC_COLD_BUDGET_S for the cold probe.
"""
import json
import os
import subprocess
import sys
import tempfile

import numpy as np

from .finalexp import _timed

DEFAULT_KINDS = "g2_subgroup,h2g_finish,hard_part_frobenius"


def _run_cold_arm(dedup: bool, timeout_s: float = None) -> dict:
    """One fresh child process against fresh persistent-XLA-cache AND
    `.vm_cache` dirs (deleted afterwards — the point is a genuinely
    cold runner for BOTH arms, assembly and plan derivation included);
    returns the child's VMEXEC_COLD_JSON payload (or an error cell).
    VMEXEC_COLD_TIMEOUT_S bounds the child (default 1800 — raise it
    along with VMEXEC_COLD_KIND for the aperiodic heavy kinds, whose
    per-chunk baseline arm can exceed half an hour)."""
    import shutil

    if timeout_s is None:
        timeout_s = float(os.environ.get("VMEXEC_COLD_TIMEOUT_S", "1800"))
    env = dict(os.environ)
    cache_dir = tempfile.mkdtemp(prefix="vmexec_cold_xla_")
    env["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    env["CONSENSUS_SPECS_TPU_VM_CACHE"] = os.path.join(cache_dir, "vm")
    env["CONSENSUS_SPECS_TPU_VM_DEDUP"] = "1" if dedup else "0"
    env.pop("CONSENSUS_SPECS_TPU_VM_EXEC", None)
    try:
        proc = subprocess.run(
            [sys.executable, "-m", "consensus_specs_tpu.bench.vmexec_cold"],
            capture_output=True, text=True, env=env, timeout=timeout_s)
        for line in proc.stdout.splitlines():
            if line.startswith("VMEXEC_COLD_JSON "):
                return json.loads(line[len("VMEXEC_COLD_JSON "):])
        return {"ok": False,
                "error": f"no cold JSON (rc={proc.returncode}): "
                         f"{proc.stderr[-200:]}"}
    except subprocess.TimeoutExpired:
        return {"ok": False, "error": f"timeout after {timeout_s:.0f}s"}
    except Exception as e:
        return {"ok": False, "error": f"{type(e).__name__}: {e}"[:200]}
    finally:
        shutil.rmtree(cache_dir, ignore_errors=True)


def run_vmexec_bench() -> dict:
    import random

    from ..ops import bls_backend as bb, fq, vm, vm_compile

    kinds = [
        k for k in os.environ.get("VMEXEC_KINDS", DEFAULT_KINDS).split(",")
        if k
    ]
    rows_list = [
        int(x) for x in os.environ.get("VMEXEC_ROWS", "1,8").split(",")
        if x.strip()
    ]
    reps = max(1, int(os.environ.get("VMEXEC_REPS", "2")))
    k_items = int(os.environ.get("VMEXEC_K", "2"))
    seed = int(os.environ.get("VMEXEC_SEED", "7"))
    rng = random.Random(seed)

    from ..utils import bls12_381 as O

    section = {}
    best_speedup = 0.0
    prev_mode = os.environ.get("CONSENSUS_SPECS_TPU_VM_EXEC")
    try:
        for kind in kinds:
            try:
                k = k_items if kind in ("miller_product", "aggregate_verify",
                                        "rlc_combine") else 0
                program, _fold = bb._program(kind, k, 1)
            except Exception as e:
                for r in rows_list:
                    section[f"{kind},{r}"] = {
                        "ok": False,
                        "error": f"build: {type(e).__name__}: {e}"[:200],
                    }
                continue
            for r in rows_list:
                cell = {"ok": False}
                section[f"{kind},{r}"] = cell
                try:
                    ins = {
                        name: np.stack([
                            fq.to_mont_int(rng.randrange(O.P))
                            for _ in range(r)
                        ]) for name in program.input_names
                    }
                    bs = (r,)

                    os.environ["CONSENSUS_SPECS_TPU_VM_EXEC"] = "interp"
                    out_i = vm.execute(program, ins, batch_shape=bs)  # warm
                    interp_s = min(
                        _timed(lambda: vm.execute(program, ins,
                                                  batch_shape=bs))
                        for _ in range(reps))

                    os.environ["CONSENSUS_SPECS_TPU_VM_EXEC"] = "fused"
                    compile_s = vm_compile.warm_fused(program, bs)
                    out_f = vm.execute(program, ins, batch_shape=bs)
                    fused_s = min(
                        _timed(lambda: vm.execute(program, ins,
                                                  batch_shape=bs))
                        for _ in range(reps))

                    identical = set(out_i) == set(out_f) and all(
                        np.array_equal(np.asarray(out_i[name]),
                                       np.asarray(out_f[name]))
                        for name in out_i)
                    cell.update(
                        ok=bool(identical),
                        interp_ms_row=round(interp_s * 1e3 / r, 3),
                        fused_ms_row=round(fused_s * 1e3 / r, 3),
                        fused_compile_s=round(compile_s, 2),
                        speedup=round(interp_s / fused_s, 2)
                        if fused_s else None,
                    )
                    if not identical:
                        cell["error"] = "fused != interp (bitwise)"
                    elif fused_s:
                        best_speedup = max(best_speedup,
                                           interp_s / fused_s)
                except Exception as e:
                    cell["error"] = f"{type(e).__name__}: {e}"[:200]
    finally:
        if prev_mode is None:
            os.environ.pop("CONSENSUS_SPECS_TPU_VM_EXEC", None)
        else:
            os.environ["CONSENSUS_SPECS_TPU_VM_EXEC"] = prev_mode

    # cold-start arms (ISSUE 15): fresh child processes, fresh XLA caches
    cold_mode = os.environ.get("VMEXEC_COLD", "1")
    cold_speedup = None
    if cold_mode != "0":
        cold_kind = os.environ.get("VMEXEC_COLD_KIND", "g2_subgroup")
        dedup_cell = _run_cold_arm(dedup=True)
        # the seconds-scale budget rides the cell's ok STATE — a round
        # whose cold arm stops fitting the budget fails bench_compare
        dedup_cell["ok"] = bool(
            dedup_cell.get("ok") and dedup_cell.get("within_budget"))
        section[f"cold,{cold_kind}"] = dedup_cell
        if cold_mode != "dedup":
            base_cell = _run_cold_arm(dedup=False)
            section[f"cold_nodedup,{cold_kind}"] = base_cell
            if (dedup_cell.get("ready_s") and base_cell.get("ready_s")):
                cold_speedup = round(
                    base_cell["ready_s"] / dedup_cell["ready_s"], 2)

    return dict(
        metric="best fused-over-interp VM execution speedup (warm ms/row)",
        value=round(best_speedup, 2),
        vs_baseline=round(best_speedup, 2),
        mode="vmexec",
        kinds=kinds,
        rows=rows_list,
        reps=reps,
        chunk_steps=vm_compile.chunk_steps(),
        cold_speedup=cold_speedup,
        vmexec=section,
    )
