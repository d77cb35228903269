"""Chip bring-up smoke: the BLS verify path end to end on one TPU.

    python chip_smoke.py             # one chip: phases 1-4
    python chip_smoke.py --chips 4   # four chips: phase 3 on a 4-device mesh

Everything runs in this one process (a chip belongs to one process).
The native kernels are built from ``csrc/`` first; then, with no TPU
visible, the script exits non-zero before printing any result. Phases:

1. switchboard -- ``utils.bls.use_tpu()``: Verify, FastAggregateVerify
   (K=512) and AggregateVerify on valid and invalid inputs, against the
   ``use_py_ecc()`` oracle;
2. serve -- ``serve.service.VerificationService`` answers a few hundred
   mainnet-shaped checks (committees of 512, duplicates, invalid ones)
   against their known ground truth, with no oracle fallback;
3. slot -- one mainnet slot (1,048,576 validators: 64 committees x 512)
   through ``scale.hierarchy.verify_slot`` with one corrupted committee,
   against ``verify_slot_flat``, on the device hard-part route;
4. pallas -- one assembled VM program under ``CONSENSUS_SPECS_TPU_PALLAS``
   ``1`` and ``step``, bit-identical to mode ``0``.

Each phase prints one JSON line: compile-inclusive seconds, warm seconds
and verdict counts. Any wrong verdict, exception or fallback (fused, oracle,
mesh, codec, prep pool) exits non-zero. The last line is the result,
``{"ok": true, "device": {...}}``.
"""
import argparse
import faulthandler
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
MAINNET_VALIDATORS = 1 << 20
SLOT = 0
BAD_COMMITTEE = 17
NATIVE = (("sha256_batch.c", "libsha256_batch.so"),
          ("vm_sched.c", "libvmsched.so"),
          ("bls_host.c", "libbls_host.so"))


class SmokeFailure(Exception):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def build_native() -> None:
    """Build csrc/*.so (gcc to a temp name, then rename: a process that
    already maps the old library keeps its pages)."""
    for src, lib in NATIVE:
        src_p = os.path.join(REPO, "csrc", src)
        lib_p = os.path.join(REPO, "csrc", lib)
        if (os.path.exists(lib_p)
                and os.path.getmtime(lib_p) >= os.path.getmtime(src_p)):
            continue
        tmp = f"{lib_p}.{os.getpid()}.tmp"
        subprocess.run(["gcc", "-O3", "-fPIC", "-shared", "-o", tmp, src_p],
                       check=True)
        os.replace(tmp, lib_p)


def _counts(verdicts) -> dict:
    verdicts = [bool(v) for v in verdicts]
    return {"valid": sum(verdicts), "invalid": len(verdicts) - sum(verdicts)}


def _bits(verdicts) -> str:
    """The verdict vector, one character per item ("1" valid)."""
    return "".join("1" if v else "0" for v in verdicts)


def _timed_twice(run):
    """(result of the compile-inclusive run, its seconds, warm seconds);
    both runs must return the same verdicts."""
    t0 = time.perf_counter()
    first = run()
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    second = run()
    warm_s = time.perf_counter() - t0
    check([bool(v) for v in first] == [bool(v) for v in second],
          "warm run disagrees with the first run")
    return first, compile_s, warm_s


def no_fallbacks() -> dict:
    """The fallbacks that would hide the device, process-wide."""
    from consensus_specs_tpu.ops import bls_backend, profiling, vm_compile

    stats, _ = profiling.stats_and_gauges()
    got = {"vm.fused_fallbacks": vm_compile._COUNTERS["fallbacks"],
           "bls.prep_pool_broken": bool(bls_backend._POOL_BROKEN),
           "bls.codec_prewarm_errors":
               stats.get("bls.codec_prewarm_error", {}).get("calls", 0),
           "bls.prep_serial_fallback_items":
               bls_backend.PREP_STATS["serial_fallback_items"]}
    check(got["vm.fused_fallbacks"] == 0 and not got["bls.prep_pool_broken"]
          and got["bls.codec_prewarm_errors"] == 0
          and got["bls.prep_serial_fallback_items"] == 0,
          f"fallback taken: {got}")
    return got


# -- inputs ------------------------------------------------------------------


def _committee_items(args):
    """Worker: committees ``cis`` of a registry rebuilt from its recipe."""
    from consensus_specs_tpu.scale.registry import Registry

    recipe, cis = args
    registry = Registry(**recipe)
    return [("fast_aggregate",) + tuple(registry.aggregate(SLOT, ci))
            for ci in cis]


def slot_items(registry, procs: int = 1):
    """The slot's committee aggregates as verify-plane items over
    compressed keys (``scale.hierarchy.bytes_items`` of what
    ``committee_items`` builds), derived by ``procs``
    spawned processes: deriving 32,768 pubkeys is host work the chip
    would otherwise wait ~40 s for. The workers never touch a device."""
    recipe = {k: getattr(registry, k) for k in (
        "n_validators", "seed", "slots_per_epoch", "max_committees",
        "target_size", "shuffle_rounds")}
    n = registry.committees_per_slot()
    if procs <= 1:
        return _committee_items((recipe, range(n)))
    import multiprocessing as mp

    shares = [(recipe, range(i, n, procs)) for i in range(procs)]
    with mp.get_context("spawn").Pool(procs) as pool:
        parts = pool.map(_committee_items, shares)
        pool.close()  # let the workers exit before the with-exit
        pool.join()   # terminates the pool
    items = [None] * n
    for (_, cis), part in zip(shares, parts):
        for ci, item in zip(cis, part):
            items[ci] = item
    return items


def serve_checks(registry, items, per_committee: int, seed: int = 21):
    """Mainnet-shaped serve traffic over the committees of ``items``:
    fresh messages signed by the whole committee (valid), every 7th one
    signed over another message (invalid), and every 5th check submitted
    twice. Returns [(item, truth)] in a seeded submission order."""
    import random

    from consensus_specs_tpu.scale.registry import _sha
    from consensus_specs_tpu.utils import bls
    from consensus_specs_tpu.utils.bls12_381 import R

    out = []
    for ci, (_, pks, _, _) in enumerate(items):
        members = registry.committee(SLOT, ci)[:len(pks)]
        agg_sk = sum(registry.secret_key(int(i)) for i in members) % R
        for j in range(per_committee):
            msg = _sha(b"chip-smoke-serve" + bytes([ci, j, seed]))
            good = (ci * per_committee + j) % 7 != 3
            sig = bls.Sign(agg_sk, msg if good else msg[::-1])
            out.append((("fast_aggregate", pks, msg, sig), good))
            if j % 5 == 0:
                out.append(out[-1])
    random.Random(seed).shuffle(out)
    return out


# -- phases ------------------------------------------------------------------


def phase_switchboard(k: int = 512, k_aggregate: int = 32) -> dict:
    """Verify / FastAggregateVerify / AggregateVerify through the
    switchboard on the device backend, each valid and invalid, against the
    py_ecc oracle. AggregateVerify runs at ``k_aggregate`` distinct
    messages: its oracle pays one Miller loop per message."""
    from consensus_specs_tpu.utils import bls
    from consensus_specs_tpu.utils.bls12_381 import R

    sks = [1000 + 7 * i for i in range(k)]
    pks = [bls.SkToPk(sk) for sk in sks]
    msg = b"chip-smoke-switchboard-message!!"
    fav_sig = bls.Sign(sum(sks) % R, msg)
    msgs = [i.to_bytes(4, "big") * 8 for i in range(k_aggregate)]
    av_sig = bls.Aggregate([bls.Sign(sk, m)
                            for sk, m in zip(sks[:k_aggregate], msgs)])
    v_sig = bls.Sign(sks[0], msg)
    calls = [
        ("Verify", (pks[0], msg, v_sig)),
        ("Verify", (pks[1], msg, v_sig)),
        ("FastAggregateVerify", (pks, msg, fav_sig)),
        ("FastAggregateVerify", (pks, msg[::-1], fav_sig)),
        ("AggregateVerify", (pks[:k_aggregate], msgs, av_sig)),
        ("AggregateVerify", (pks[:k_aggregate], msgs[::-1], av_sig)),
    ]

    def run_all():
        return [getattr(bls, name)(*args) for name, args in calls]

    try:
        bls.use_tpu()
        got, compile_s, warm_s = _timed_twice(run_all)
    finally:
        bls.use_py_ecc()
    want = run_all()
    check(want == [True, False] * 3, f"oracle verdicts {want}")
    check(got == want, f"device verdicts {got} != oracle {want}")
    return {"phase": "switchboard", "k": k, "k_aggregate": k_aggregate,
            "compile_s": compile_s, "warm_s": warm_s, "verdicts": _counts(got)}


def phase_serve(checks, mesh=None) -> dict:
    """The checks through a VerificationService (on ``mesh`` if given):
    verdicts equal the ground truth, no oracle or mesh fallback."""
    from consensus_specs_tpu.serve.service import VerificationService

    truth = [t for _, t in checks]
    snaps = []

    def run():
        with VerificationService(mesh=mesh, max_wait_ms=50.0) as svc:
            futs = [svc.submit(*item) for item, _ in checks]
            got = [f.result(timeout=1200) for f in futs]
        snaps.append(svc.metrics.snapshot())
        return got

    got, compile_s, warm_s = _timed_twice(run)
    check(got == truth, "service verdicts differ from the ground truth: "
          f"{sum(g != t for g, t in zip(got, truth))} of {len(truth)}")
    for snap in snaps:
        check(snap["fallback_items"] == 0,
              f"oracle fallback: {snap['fallback_items']} items")
        check(snap["mesh_fallbacks"] == 0,
              f"mesh fallback: {snap['mesh_fallbacks']}")
    return {"phase": "serve" if mesh is None else "serve_mesh",
            "checks": len(checks), "distinct": len({id(i) for i, _ in checks}),
            "compile_s": compile_s, "warm_s": warm_s,
            "verdicts": _counts(got), "fallback_items": 0, "mesh_fallbacks": 0}


def phase_slot(items, bad: int = None, mesh=None) -> dict:
    """One slot through verify_slot with committee ``bad`` corrupted:
    exactly that committee is found, the verdicts equal verify_slot_flat,
    and the one combined hard part ran on the device route. On a mesh,
    the combines' chunk products must also have been folded across the
    devices (``ops/mesh_rlc``), not multiplied on the host."""
    from consensus_specs_tpu.ops import bls_backend
    from consensus_specs_tpu.scale import hierarchy

    bad = BAD_COMMITTEE if bad is None else bad
    items = list(items)
    items[bad] = hierarchy.corrupt_item(items[bad])
    check(bls_backend._rlc_final_mode() == "device",
          f"final-exp route is {bls_backend._rlc_final_mode()!r}, not device")
    windows0 = bls_backend.RLC_STATS["final_exp_windows"]
    reductions0 = bls_backend.RLC_STATS["mesh_reductions"]
    reports = []

    def run():
        reports.append(hierarchy.verify_slot(items, slot=SLOT, mesh=mesh))
        return reports[-1].verdicts.tolist()

    got, compile_s, warm_s = _timed_twice(run)
    check(bls_backend.RLC_STATS["final_exp_windows"] > windows0,
          "the device hard-part route did not run")
    reductions = bls_backend.RLC_STATS["mesh_reductions"] - reductions0
    check(mesh is None or reductions > 0,
          "no combine reduced its chunk products across the mesh")
    for rep in reports:
        check(rep.bad_committees == [bad],
              f"bad committees {rep.bad_committees}, want [{bad}]")
    t0 = time.perf_counter()
    flat = hierarchy.verify_slot_flat(items, mesh=mesh).tolist()
    flat_s = time.perf_counter() - t0
    check(got == flat, "verify_slot and verify_slot_flat disagree")
    rep = reports[-1]
    return {"phase": "slot" if mesh is None else "slot_mesh",
            "committees": rep.committees, "attestations": rep.attestations,
            "compile_s": compile_s, "warm_s": warm_s, "flat_s": flat_s,
            "verdicts": _counts(got), "verdict_bits": _bits(got),
            "bad_committees": rep.bad_committees,
            "combines": rep.combines, "bisections": rep.bisections,
            "final_exps": rep.final_exps, "final_route": "device",
            "final_exp_windows":
                bls_backend.RLC_STATS["final_exp_windows"] - windows0,
            "mesh_reductions": reductions}


def phase_pallas(batch: int = 2) -> dict:
    """One assembled pairing program under PALLAS=1 and =step,
    bit-identical to PALLAS=0."""
    import numpy as np

    from __graft_entry__ import _example_program_and_inputs
    from consensus_specs_tpu.ops import vm

    prog, regs, _ = _example_program_and_inputs(batch=batch)
    ins = {name: np.asarray(regs[..., int(r), :])
           for name, r in zip(prog.input_names, prog.input_regs)}
    prev = os.environ.get("CONSENSUS_SPECS_TPU_PALLAS")
    outs, secs = {}, {}
    try:
        for mode in ("0", "1", "step"):
            os.environ["CONSENSUS_SPECS_TPU_PALLAS"] = mode
            check(vm._pallas_mode() == mode, f"PALLAS={mode} not taken")
            times = []
            for _ in range(2):
                t0 = time.perf_counter()
                outs[mode] = vm.execute(prog, ins, (batch,))
                times.append(time.perf_counter() - t0)
            secs[mode] = times
    finally:
        if prev is None:
            os.environ.pop("CONSENSUS_SPECS_TPU_PALLAS", None)
        else:
            os.environ["CONSENSUS_SPECS_TPU_PALLAS"] = prev
    for mode in ("1", "step"):
        for name in prog.output_names:
            check(np.array_equal(outs[mode][name], outs["0"][name]),
                  f"PALLAS={mode} output {name} differs from PALLAS=0")
    return {"phase": "pallas", "steps": int(prog.n_steps), "batch": batch,
            "compile_s": {m: s[0] for m, s in secs.items()},
            "warm_s": {m: s[1] for m, s in secs.items()},
            "verdicts": {"identical": 2, "different": 0}}


def prep_pool_check(n: int = 32) -> dict:
    """The fork-based prep pool after libtpu is up: it must serve a batch
    (its workers never touch JAX) and not latch broken."""
    from consensus_specs_tpu.ops import bls_backend

    msgs = [b"chip-smoke-pool-%016d" % i for i in range(n)]
    before = bls_backend.PREP_STATS["pool_batches"]
    bls_backend._prewarm_pool(msgs, [], [])
    check(bls_backend.PREP_STATS["pool_batches"] == before + 1
          and not bls_backend._POOL_BROKEN, "prep pool did not serve")
    check(all(m in bls_backend._MSG_CACHE for m in msgs),
          "prep pool left messages unhashed")
    return {"phase": "prep_pool", "items": n}


def mesh_shards(mesh, run):
    """Run ``run()`` while recording which devices hold a distinct shard
    of every sharded VM execution's output."""
    from consensus_specs_tpu.ops import vm

    seen = []
    inner = vm._execute_device

    def spy(stacked, template, input_regs, output_regs, instr, mesh_, **kw):
        out = inner(stacked, template, input_regs, output_regs, instr, mesh_,
                    **kw)
        if mesh_ is not None:
            idx = out.sharding.devices_indices_map(out.shape)
            seen.append({d.id: str(ix[0]) for d, ix in idx.items()})
        return out

    vm._execute_device = spy
    try:
        result = run()
    finally:
        vm._execute_device = inner
    n_dev = mesh.devices.size
    check(seen, "no sharded VM execution ran on the mesh")
    for shard_map in seen:
        check(len(shard_map) == n_dev
              and len(set(shard_map.values())) == n_dev,
              f"output not split over {n_dev} devices: {shard_map}")
    return result, len(seen)


# -- driver ------------------------------------------------------------------


def _emit(line: dict) -> None:
    print(json.dumps(line), flush=True)


def run_one_chip(items, registry) -> None:
    _emit(phase_switchboard())
    _emit(phase_serve(serve_checks(registry, items[:8], per_committee=30)))
    _emit(phase_slot(items))
    _emit(phase_pallas())
    _emit(prep_pool_check())


def run_four_chips(items) -> None:
    """Phase 3 on a 4-device mesh, against the single-device verdicts of
    the same slot; then the slot's checks through a service on the mesh."""
    from consensus_specs_tpu.scale import hierarchy
    from consensus_specs_tpu.utils import jax_env

    mesh = jax_env.get_mesh("4")
    check(mesh is not None and mesh.devices.size == 4,
          f"no 4-device mesh: {mesh}")
    line, n_exec = mesh_shards(mesh, lambda: phase_slot(items, mesh=mesh))
    line["sharded_executions"] = n_exec
    _emit(line)
    bad = list(items)
    bad[BAD_COMMITTEE] = hierarchy.corrupt_item(bad[BAD_COMMITTEE])
    t0 = time.perf_counter()
    single = hierarchy.verify_slot(bad, slot=SLOT)
    _emit({"phase": "slot_single_device", "seconds": time.perf_counter() - t0,
           "verdicts": _counts(single.verdicts),
           "verdict_bits": _bits(single.verdicts),
           "bad_committees": single.bad_committees})
    check(line["verdict_bits"] == _bits(single.verdicts)
          and line["bad_committees"] == single.bad_committees,
          "mesh verdicts differ from the single-device verdicts")
    line, n_exec = mesh_shards(
        mesh, lambda: phase_serve([(it, True) for it in items], mesh=mesh))
    line["sharded_executions"] = n_exec
    _emit(line)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(REPO, "consensus_specs_tpu")):
        print("chip_smoke: FAIL the repository is not beside this script",
              file=sys.stderr)
        return 2
    build_native()
    # a phase that stalls (a compile that never ends) leaves its Python
    # stack on stderr instead of dying silently at the caller's time limit
    faulthandler.dump_traceback_later(300, repeat=True)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < args.chips:
        print(f"chip_smoke: FAIL need {args.chips} TPU chip(s), JAX found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 1

    from consensus_specs_tpu.scale.registry import Registry

    t0 = time.perf_counter()
    registry = Registry(MAINNET_VALIDATORS, seed=7)
    items = slot_items(registry, procs=min(16, os.cpu_count() or 1))
    _emit({"phase": "setup", "validators": MAINNET_VALIDATORS,
           "committees": len(items),
           "attestations": sum(len(it[1]) for it in items),
           "seconds": time.perf_counter() - t0,
           "cache_dir": jax.config.jax_compilation_cache_dir})
    try:
        if args.chips == 4:
            run_four_chips(items)
        else:
            run_one_chip(items, registry)
        _emit({"phase": "fallbacks", **no_fallbacks()})
    except Exception as e:
        import traceback

        traceback.print_exc()
        print(f"chip_smoke: FAIL {type(e).__name__}: {e}", file=sys.stderr)
        return 1
    _emit({"ok": True, "device": {"platform": devices[0].platform,
                                  "kind": devices[0].device_kind,
                                  "count": len(devices)}})
    return 0


if __name__ == "__main__":
    sys.exit(main())
