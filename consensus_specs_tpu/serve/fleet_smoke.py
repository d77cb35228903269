"""Fleet control-plane canary (`make fleet-smoke`, CI; mesh-smoke's
fleet sibling).

Two phases, both against a REAL 2-worker fleet (`serve/worker.py`
processes, real bls backend):

1. **Verdict identity**: a batch exercising every input class — valid
   committees, a corrupted message (RLC bisection), a malformed
   signature, an infinity pubkey — submitted through the fleet router
   must answer bit-identically to (a) a single-process
   ``VerificationService`` over the same backend and (b) the pure-Python
   host oracle. The merged ``/metrics`` scrape must be the exact merge
   of the per-worker snapshots. Every worker snapshot must additionally
   report ``extra["warm_bg"]`` true — background VM warming
   (``CONSENSUS_SPECS_TPU_VM_WARM_BG``) is the fleet-worker default.

2. **Forced worker fault -> SLO-burn-driven decision**: one worker's
   backend is armed to fail, distinct committees routed to THAT worker
   are pushed under load (every flush degrades down the ladder to the
   sequential oracle — slow but correct), and the router's control loop
   must reach a shed/drain decision from the burn rates on the MERGED
   histograms. The gate demands the full reconstruction from the merged
   flight journal: the fleet decision event (worker provenance + burn
   evidence), the worker's own ``shed_rung`` ladder transition, and a
   merged-scrape delta (``fleet.sheds``/``fleet.drains`` moved, merged
   observation counts grew).

The merged journal always dumps to ``fleet_flight.jsonl`` (uploaded as a
CI artifact on failure). Out of tier-1: the workers pay real-backend
compiles (~minutes cold). Exit 0 on pass, 1 with a diagnosis otherwise.
"""
import json
import os
import sys
import time

WORKERS = 2
JOURNAL_PATH = "fleet_flight.jsonl"
# the smoke's objective: tight enough that the fault phase's full
# degradation cascade (two failed RLC attempts + two failed group
# attempts + the sequential pure-Python oracle, ~1-2 s/item even with
# warm host caches) blows it deterministically. No clean traffic exists
# after the baseline checkpoint — phase A's compile-heavy latencies are
# baselined out by the post-identity control tick, and the burn windows
# diff against that checkpoint — so only fault-phase mass can burn and
# the tightness has no false-positive surface.
SLO_OVERRIDE = "serve_p99_ms=500"


def _scrape_gauge(text: str, name: str) -> float:
    for line in text.splitlines():
        if line.startswith(name + " "):
            return float(line.rsplit(" ", 1)[1])
    return 0.0


def _scrape_hist_count(text: str) -> int:
    fam = ("consensus_specs_tpu_serve_submit_to_result_"
           "latency_hist_seconds_count")
    return int(_scrape_gauge(text, fam))


def main() -> int:
    os.environ["CONSENSUS_SPECS_TPU_FLIGHT"] = "1"
    os.environ.setdefault("CONSENSUS_SPECS_TPU_FLIGHT_DUMP", JOURNAL_PATH)
    os.environ.setdefault("CONSENSUS_SPECS_TPU_SLO", SLO_OVERRIDE)
    from ..utils.jax_env import force_cpu

    force_cpu()

    from ..obs.slo import ShedPolicy
    from ..utils import bls
    from ..utils.bls12_381 import R
    from .cache import check_key
    from .fleet import FleetRouter
    from .service import VerificationService

    def committee(tag, k=1, good=True):
        sks = [7000 * tag + j + 1 for j in range(k)]
        pks = [bls.SkToPk(sk) for sk in sks]
        msg = (b"flt%03d" % tag) + b"\x00" * 26
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

    router = None
    try:
        # host-oracle truth (the reference's exception-swallowing rules)
        def oracle_one(kind, pks, msg, sig):
            try:
                return bool(bls.FastAggregateVerify(pks, msg, sig))
            except Exception:
                return False

        oracle = [oracle_one(*it) for it in items]
        assert oracle == want, (
            f"oracle drifted from the pinned pattern: {oracle} != {want}")

        router = FleetRouter(
            workers=WORKERS, backend="bls",
            env={"SERVE_MAX_WAIT_MS": "300",
                 "CONSENSUS_SPECS_TPU_FLIGHT": "1",
                 "CONSENSUS_SPECS_TPU_SLO":
                     os.environ["CONSENSUS_SPECS_TPU_SLO"]},
            policy=ShedPolicy(),  # stock thresholds: shed 4x, drain 32x
        )

        # -- phase 1: verdict identity ----------------------------------------
        fleet_futs = [router.submit(*it) for it in items]
        got_fleet = [bool(f.result(timeout=600)) for f in fleet_futs]

        svc = VerificationService(max_wait_ms=300.0)
        try:
            single_futs = [svc.submit(*it) for it in items]
            got_single = [bool(f.result(timeout=600)) for f in single_futs]
        finally:
            svc.close(timeout=60)
        assert got_fleet == got_single == oracle == want, (
            f"verdict identity violated: fleet={got_fleet} "
            f"single={got_single} oracle={oracle} want={want}")

        # -- background-warm default (ISSUE 20 satellite) ---------------------
        # every worker must report warm_bg armed in its snapshot extra:
        # the fleet's fresh processes background-compile cold shapes off
        # the serving path by default (worker main() setdefaults
        # CONSENSUS_SPECS_TPU_VM_WARM_BG=1; a regression here silently
        # returns the fleet to interpreter-only cold starts)
        snaps = router.poll_snapshots()
        warm_flags = {label: snap.get("extra", {}).get("warm_bg")
                      for label, snap in snaps.items()}
        assert len(warm_flags) == WORKERS and all(warm_flags.values()), (
            f"background VM warming not armed on every worker: "
            f"{warm_flags}")

        # baseline: merge the identity-phase state and checkpoint the
        # burn windows — only fault-phase mass can burn from here
        router.control_tick()
        before = router.scrape_text()
        n_before = _scrape_hist_count(before)
        assert n_before >= len(items), (
            f"merged scrape lost observations: {n_before} < {len(items)}")
        acts_before = (_scrape_gauge(before, "consensus_specs_tpu_fleet_sheds")
                       + _scrape_gauge(before,
                                       "consensus_specs_tpu_fleet_drains"))

        # -- phase 2: forced worker fault -> burn -> decision ------------------
        # distinct valid committees that all consistent-hash to ONE worker
        target, fault_items, tag = None, [], 100
        while len(fault_items) < 5 and tag < 400:
            it = committee(tag, k=1)
            label = router.route_label(check_key(*it))
            if target is None:
                target = label
            if label == target:
                fault_items.append(it)
            tag += 1
        assert len(fault_items) >= 5, "could not craft affine fault traffic"
        router.handle(target).inject_fault(calls=64, mode="fail")

        fault_futs = [router.submit(*it) for it in fault_items]
        got_fault = [bool(f.result(timeout=600)) for f in fault_futs]
        assert all(got_fault), (
            f"fault-phase verdicts wrong (oracle fallback must stay "
            f"correct): {got_fault}")

        time.sleep(1.1)  # burn-tracker checkpoint spacing
        decisions = []
        for _ in range(20):
            decisions = router.control_tick()["decisions"]
            if decisions:
                break
            time.sleep(0.5)
        assert decisions, (
            "no shed/drain decision: the burn on the merged histograms "
            f"never crossed the policy ({router.healthz()['slo']})")
        decision = decisions[0]
        assert decision["worker"] == target, (
            f"decision hit {decision['worker']}, the fault was on {target}")

        # -- reconstruction from the merged journal ---------------------------
        router.poll_snapshots()  # absorb the worker's post-shed journal
        journal = router.journal_jsonl(reason="fleet_smoke")
        events = [json.loads(line) for line in journal.splitlines()[1:]]
        fleet_decisions = [e for e in events if e["plane"] == "fleet"
                           and e["kind"] in ("shed", "drain")]
        assert fleet_decisions, "decision missing from the merged journal"
        devt = fleet_decisions[0]
        assert devt["data"].get("worker") == target
        assert devt["data"].get("burn", 0) > 0
        if devt["kind"] == "shed":
            transitions = [e for e in events if e["kind"] == "shed_rung"
                           and e.get("worker") == target]
            assert transitions, (
                "worker ladder transition missing from the merged journal")
        ladder_evidence = [e for e in events if e.get("worker") == target
                           and e["kind"].startswith("degraded")]
        assert ladder_evidence, (
            "the faulted worker's own degradation events missing from "
            "the merged journal")

        # -- merged-scrape delta ----------------------------------------------
        after = router.scrape_text()
        n_after = _scrape_hist_count(after)
        acts_after = (_scrape_gauge(after, "consensus_specs_tpu_fleet_sheds")
                      + _scrape_gauge(after,
                                      "consensus_specs_tpu_fleet_drains"))
        assert n_after >= n_before + len(fault_items), (
            f"merged scrape missed the fault traffic: {n_before} -> "
            f"{n_after}")
        assert acts_after > acts_before, (
            "fleet.sheds/fleet.drains did not move on the merged scrape")

        with open(JOURNAL_PATH, "w") as fh:
            fh.write(journal)
        print(
            f"fleet-smoke OK: {WORKERS} workers, verdicts == single-process "
            f"== oracle, fault on {target} -> {devt['kind']} "
            f"(burn {devt['data'].get('burn'):.1f}x "
            f"{devt['data'].get('objective')}/{devt['data'].get('window')}), "
            f"merged scrape {n_before} -> {n_after} observations, "
            f"journal {JOURNAL_PATH} ({len(events)} events)"
        )
        return 0
    except Exception as e:
        print(f"fleet-smoke FAIL: {type(e).__name__}: {e}")
        if router is not None:
            try:
                with open(JOURNAL_PATH, "w") as fh:
                    fh.write(router.journal_jsonl(reason="fleet_smoke_fail"))
                print(f"fleet-smoke: merged journal dumped to {JOURNAL_PATH}")
            except Exception:
                pass
        return 1
    finally:
        if router is not None:
            router.close()


def chip_main(n_workers: int) -> int:
    """``--chips N``: an N-worker fleet on a TPU host, each worker on a
    chip of its own (``fleet.chip_envs``). The router signs the checks
    on the CPU and never takes a chip; the verdicts must equal their
    ground truth, and every worker must report one TPU device and hold
    chip device files no other worker holds. Exits non-zero on a host
    without N chips."""
    import jax

    # the router's own JAX stays on the CPU; the environment the workers
    # inherit keeps the host's platforms
    jax.config.update("jax_platforms", "cpu")
    from ..utils import bls
    from ..utils.bls12_381 import R
    from .cache import check_key
    from .fleet import FleetRouter, host_tpu_chips

    chips = host_tpu_chips()
    if chips < n_workers:
        print(f"fleet-smoke FAIL: {n_workers} workers need {n_workers} "
              f"TPU chips, the host has {chips}")
        return 1
    router = None
    try:
        t0 = time.perf_counter()
        router = FleetRouter(workers=n_workers, backend="bls",
                             env={"SERVE_MAX_WAIT_MS": "300"})
        spawn_s = time.perf_counter() - t0
        # committees of 4 until every worker owns at least two checks,
        # every third one signed over another message
        items, want, owners = [], [], {}
        tag = 0
        while min(owners.get(w, 0) for w in router.live_workers) < 2:
            sks = [9000 * tag + j + 1 for j in range(4)]
            msg = (b"chip%04d" % tag) + b"\x00" * 24
            good = tag % 3 != 2
            sig = bls.Sign(sum(sks) % R, msg if good else msg[::-1])
            item = ("fast_aggregate", [bls.SkToPk(sk) for sk in sks],
                    msg, sig)
            label = router.route_label(check_key(*item))
            owners[label] = owners.get(label, 0) + 1
            items.append(item)
            want.append(good)
            tag += 1
        t0 = time.perf_counter()
        got = [bool(f.result(timeout=900))
               for f in [router.submit(*it) for it in items]]
        verify_s = time.perf_counter() - t0
        assert got == want, f"fleet verdicts {got} != ground truth {want}"
        devices = {label: snap["extra"]["device"]
                   for label, snap in router.poll_snapshots().items()}
        assert len(devices) == n_workers, f"snapshots from {list(devices)}"
        for label, dev in devices.items():
            assert dev and dev["platform"] == "tpu" and dev["count"] == 1, (
                f"worker {label} is not on one chip: {dev}")
        held = [f for d in devices.values() for f in d["chip_files"]]
        assert all(d["chip_files"] for d in devices.values()) and (
            len(set(held)) == len(held)), f"workers share a chip: {devices}"
        print(json.dumps({"phase": "fleet", "workers": n_workers,
                          "host_chips": chips, "checks": len(items),
                          "spawn_s": spawn_s, "verify_s": verify_s,
                          "verdicts": {"valid": sum(got),
                                       "invalid": len(got) - sum(got)},
                          "devices": devices}))
        return 0
    except Exception as e:
        print(f"fleet-smoke FAIL: {type(e).__name__}: {e}")
        return 1
    finally:
        if router is not None:
            router.close()


if __name__ == "__main__":
    if sys.argv[1:2] == ["--chips"]:
        sys.exit(chip_main(int(sys.argv[2])))
    sys.exit(main())
