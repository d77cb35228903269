"""Mainnet workload canary (`make mainnet-smoke`, CI; fleet-smoke's
mainnet sibling).

A small-but-mainnet-preset slot: the committee count comes from the
REAL mainnet formula (get_committee_count_per_slot over the registry),
only the validator count is reduced so the smoke fits a CI runner.
The whole registry's keys go into one ``PubkeyTable`` first. Three
traffic rounds over the same slot, each verified three ways —
hierarchical (RLC slot fold, keys gathered from the table by validator
index), flat (per-committee finalization over compressed keys), and
the pure-Python host oracle — with all three verdict vectors required
bit-identical:

1. **valid**: every committee fully covered. The hierarchical fold must
   pay exactly ONE combine and ONE final exp for the whole slot.
2. **censored**: one committee's aggregate covers only a subset (the
   tail censored out). The uncensored cover must still verify AND the
   coverage loss must be detected (censorship evidence: covered <
   fan-out) — Wonderboom's censorship-resilience claim, tested.
3. **forced bad committee**: one committee carries a structurally valid
   but wrong signature. The slot root fails, bisection must localize
   EXACTLY that committee, and the flat/oracle paths must agree.

Phase 4 routes the slot through a real 2-worker fleet with
committee-index affinity (verdict backend — affinity is
crypto-independent) and demands a stable committee->worker assignment
across rounds with zero affinity moves.

The flight journal dumps to ``scale_flight.jsonl`` on failure (CI
uploads it). Out of tier-1: the verify rounds pay real-backend
compiles on a cold cache. Exit 0 on pass, 1 with a diagnosis.
"""
import os
import sys

VALIDATORS_ENV = "CONSENSUS_SPECS_TPU_SCALE_SMOKE_VALIDATORS"
JOURNAL_PATH = "scale_flight.jsonl"
DEFAULT_VALIDATORS = 8192  # mainnet formula -> 2 committees of 128


def main() -> int:
    os.environ["CONSENSUS_SPECS_TPU_FLIGHT"] = "1"
    os.environ.setdefault("CONSENSUS_SPECS_TPU_FLIGHT_DUMP", JOURNAL_PATH)
    from ..utils.jax_env import force_cpu

    force_cpu()

    from ..obs import flight
    from . import hierarchy, pubkeys, routing
    from .registry import Registry

    rec = flight.global_recorder()
    n = int(os.environ.get(VALIDATORS_ENV, str(DEFAULT_VALIDATORS)))
    fleet = None
    try:
        reg = Registry(n, seed=20)
        per_slot = reg.committees_per_slot()
        fanout = sum(len(c) for c in reg.committees_at_slot(0))
        assert per_slot >= 2, (
            f"smoke needs >= 2 committees for localization; "
            f"{n} validators give {per_slot}")
        rec.note("scale", "smoke_registry", validators=n,
                 committees_per_slot=per_slot, fanout=fanout,
                 digest=reg.digest(sample=64))

        table = pubkeys.PubkeyTable.build(reg.pubkey_column())

        def identity(tag, items, report):
            as_bytes = hierarchy.bytes_items(items, reg)
            flat = hierarchy.verify_slot_flat(as_bytes)
            oracle = hierarchy.verify_slot_oracle(as_bytes)
            hier = report.verdicts.tolist()
            rec.note("scale", "smoke_verdicts", round=tag, hier=hier,
                     flat=flat.tolist(), oracle=oracle.tolist(),
                     final_exps=report.final_exps,
                     combines=report.combines,
                     bisections=report.bisections)
            assert hier == flat.tolist() == oracle.tolist(), (
                f"{tag}: verdict divergence hier={hier} "
                f"flat={flat.tolist()} oracle={oracle.tolist()}")
            return hier

        # -- round 1: valid slot, ONE final exp for the whole fold ----------
        items = hierarchy.committee_items(reg, slot=0)
        report = hierarchy.verify_slot(items, slot=0, table=table)
        hier = identity("valid", items, report)
        assert all(hier), f"valid slot rejected: {hier}"
        assert report.combines == 1 and report.bisections == 0, (
            f"valid slot paid {report.combines} combines / "
            f"{report.bisections} bisections; wanted the single slot fold")
        assert report.final_exps_per_slot == 1.0, (
            f"final_exps_per_slot {report.final_exps_per_slot} != 1")
        assert report.attestations == fanout
        print(f"mainnet-smoke: valid slot OK — {per_slot} committees, "
              f"{report.attestations} attestations, "
              f"final_exps_per_slot={report.final_exps_per_slot:.0f}, "
              f"verify {report.verify_s:.2f}s")

        # -- round 2: censored aggregate — subset cover still verifies ------
        censored_ci, participation = 0, 0.75
        items_c = list(hierarchy.committee_items(reg, slot=0))
        members, msg, sig = reg.aggregate_members(
            0, censored_ci, participation=participation)
        items_c[censored_ci] = ("fast_aggregate_indexed", members, msg, sig)
        report_c = hierarchy.verify_slot(items_c, slot=0, table=table)
        hier_c = identity("censored", items_c, report_c)
        assert all(hier_c), f"uncensored cover rejected: {hier_c}"
        censored = fanout - report_c.attestations
        assert censored > 0, "censorship went undetected: full coverage"
        rec.note("scale", "smoke_censorship", committee=censored_ci,
                 censored_validators=censored, covered=report_c.attestations)
        print(f"mainnet-smoke: censored round OK — {censored} validators "
              f"censored out of committee {censored_ci}, subset cover "
              f"verified")

        # -- round 3: forced bad committee, localized by bisection ----------
        bad_ci = per_slot - 1
        items_b = list(hierarchy.committee_items(reg, slot=0))
        items_b[bad_ci] = hierarchy.corrupt_item(items_b[bad_ci])
        report_b = hierarchy.verify_slot(items_b, slot=0, table=table)
        hier_b = identity("bad_committee", items_b, report_b)
        assert report_b.bad_committees == [bad_ci], (
            f"bisection localized {report_b.bad_committees}, "
            f"planted {bad_ci}")
        assert report_b.bisections >= 1, "slot root failed without bisecting"
        assert [i for i, ok in enumerate(hier_b) if ok] == [
            i for i in range(per_slot) if i != bad_ci]
        print(f"mainnet-smoke: bad committee {bad_ci} localized by "
              f"{report_b.bisections} bisection(s)")

        # -- phase 4: committee-affinity fleet routing ----------------------
        with routing.CommitteeFleet(workers=2, backend="verdict") as fleet_:
            fleet = fleet_
            assign = fleet_.assignment(range(per_slot))
            verdict_items = [("fast_aggregate", [b"\x22" * 48],
                              b"scale%03d" % ci + b"\x00" * 23,
                              b"\x11" * 96) for ci in range(per_slot)]
            for _round in range(2):
                got = fleet_.submit_slot(verdict_items)
                assert all(got), f"fleet round verdicts: {got}"
            assert fleet_.assignment(range(per_slot)) == assign, (
                "committee->worker assignment drifted between rounds")
            assert fleet_.affinity_moves == 0, (
                f"{fleet_.affinity_moves} affinity moves on a stable ring")
            rec.note("scale", "smoke_affinity", assignment={
                str(k): v for k, v in assign.items()})
        fleet = None
        print(f"mainnet-smoke: committee affinity stable across rounds "
              f"({len(set(assign.values()))} workers covered)")
        print("mainnet-smoke OK")
        return 0
    except Exception as e:
        print(f"mainnet-smoke FAIL: {type(e).__name__}: {e}")
        try:
            path = rec.dump(JOURNAL_PATH, reason="mainnet_smoke_fail")
            print(f"mainnet-smoke: flight journal dumped to {path}")
        except Exception:
            pass
        return 1
    finally:
        if fleet is not None:
            fleet.close()


if __name__ == "__main__":
    sys.exit(main())
