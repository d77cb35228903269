"""Mesh-plane convergence canary (`make mesh-smoke`, CI).

One serve-plane flush on a 4-virtual-device CPU mesh, held to the STRICT
verdict-identity gate: every verdict the mesh-sharded service returns must
be bit-identical to (a) the single-device RLC path and (b) the
pure-Python host oracle, over a batch that exercises every input class —
valid committees, a corrupted message (which forces a bisection through
the failed SHARDED combine), a malformed signature, and an infinity
pubkey. The flight recorder is armed for the whole run; on failure the
journal dumps to ``mesh_flight.jsonl`` (uploaded as a CI artifact) so the
divergence post-mortem exists without a rerun, and on success the journal
must show ZERO degradation-ladder transitions — a mesh smoke that only
passes because it silently fell back to the single-device path is a fail.

Exit 0 on pass; nonzero with a diagnosis line otherwise. Kept out of
tier-1 (the sharded XLA compiles cost tens of seconds); the pytest-side
mesh coverage lives in tests/test_mesh_rlc.py.
"""
import os
import sys

MESH_DEVICES = 4


def main() -> int:
    os.environ["CONSENSUS_SPECS_TPU_MESH"] = str(MESH_DEVICES)
    os.environ["CONSENSUS_SPECS_TPU_FLIGHT"] = "1"
    os.environ.setdefault("CONSENSUS_SPECS_TPU_FLIGHT_DUMP",
                          "mesh_flight.jsonl")
    from ..utils.jax_env import force_cpu

    force_cpu(n_devices=MESH_DEVICES)

    from ..obs import flight
    from ..ops import bls_backend
    from ..utils import bls
    from ..utils.bls12_381 import R
    from .service import VerificationService

    def committee(tag, k=1, good=True):
        sks = [9000 * tag + j + 1 for j in range(k)]
        pks = [bls.SkToPk(sk) for sk in sks]
        msg = (b"smk%03d" % tag) + b"\x00" * 26
        sig = bls.Sign(sum(sks) % R, msg)
        if not good:
            msg = b"\xff" + msg[1:]
        return ("fast_aggregate", pks, msg, sig)

    items = [
        committee(1, k=2),
        committee(2),
        committee(3, good=False),                      # corrupted: bisection
        ("fast_aggregate", [bls.SkToPk(7)], b"m" * 32,
         b"\xa0" + b"\x01" * 95),                      # undecodable signature
        ("fast_aggregate", [b"\xc0" + b"\x00" * 47],
         b"p" * 32, bls.Sign(9, b"p" * 32)),           # infinity pubkey
    ]
    want = [True, True, False, False, False]

    rec = flight.global_recorder()
    try:
        # host-oracle truth (the reference's exception-swallowing rules)
        def oracle_one(kind, pks, msg, sig):
            try:
                return bool(bls.FastAggregateVerify(pks, msg, sig))
            except Exception:
                return False

        oracle = [oracle_one(*it) for it in items]
        assert oracle == want, f"oracle drifted from the pinned pattern: " \
            f"{oracle} != {want}"

        # max_wait sized so all five submits join ONE flush even on a
        # slow CI runner — a flush narrower than the mesh would route to
        # the single-device path (service._flush_mesh) and the smoke
        # would no longer exercise the sharded combine at all
        svc = VerificationService(max_wait_ms=300.0)
        assert svc.mesh_devices == MESH_DEVICES, (
            f"mesh not armed: service spans {svc.mesh_devices} devices "
            f"(CONSENSUS_SPECS_TPU_MESH={os.environ['CONSENSUS_SPECS_TPU_MESH']})"
        )
        stats_before = dict(bls_backend.RLC_STATS)
        try:
            futures = [svc.submit(*it) for it in items]
            got = [bool(f.result(timeout=600)) for f in futures]
        finally:
            svc.close(timeout=60)
        # the SERVICE flush's own counters (captured before the
        # single-device reference run below, which also bisects)
        svc_bisections = (bls_backend.RLC_STATS["bisections"]
                          - stats_before["bisections"])
        svc_reductions = (bls_backend.RLC_STATS["mesh_reductions"]
                          - stats_before["mesh_reductions"])
        # direct evidence the flush ran SHARDED: the VM executions it
        # paid must carry sharded=True labels (narrow flushes would have
        # routed single-device and still produced matching verdicts)
        from ..ops import profiling

        stats, _gauges = profiling.stats_and_gauges()
        sharded_execs = [k for k in stats if "sharded=True" in k]

        single = [bool(r) for r in bls_backend.batch_verify_rlc(items)]
        assert got == single == oracle == want, (
            f"verdict identity violated: mesh={got} single={single} "
            f"oracle={oracle} want={want}"
        )
        assert svc.metrics.mesh_fallbacks == 0, (
            f"{svc.metrics.mesh_fallbacks} mesh fallback(s): the smoke "
            "only passed on the single-device path"
        )
        # "zero SILENT fallbacks": no serve-level degraded_* transition,
        # and the flush's combine folded its chunk products over the mesh
        degraded = [e for e in rec.events()
                    if e["kind"].startswith("degraded")]
        assert not degraded, f"degradation transitions on clean traffic: " \
            f"{[e['kind'] for e in degraded]}"
        assert svc_reductions > 0, (
            "the service flush never reduced across the mesh (mesh_rlc)"
        )
        assert svc_bisections > 0, (
            "the service flush never bisected — the corrupted item did "
            "not exercise the failed-sharded-combine path"
        )
        assert sharded_execs, (
            "no sharded VM executions recorded — the flush routed "
            "single-device and the mesh path was never exercised"
        )
        print(
            f"mesh-smoke OK: {len(items)} checks on {MESH_DEVICES} virtual "
            f"devices, verdicts == single-device == oracle, "
            f"{svc_bisections} bisection(s) through the sharded combine "
            f"({len(sharded_execs)} sharded VM execution shapes), "
            "0 fallbacks"
        )
        return 0
    except Exception as e:
        path = rec.dump(reason="mesh_smoke_failure")
        print(f"mesh-smoke FAIL: {type(e).__name__}: {e}")
        print(f"mesh-smoke: flight journal dumped to {path}")
        return 1


if __name__ == "__main__":
    sys.exit(main())
