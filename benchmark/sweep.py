"""Rate sweep of an open-loop cell, run once to place its fixed rate:

    python3 -m benchmark.sweep --workload gossip1m.serve --seconds 50 \\
        --seed 7 --rates 6,7,8

One process (a chip belongs to one process), ``REPEAT`` runs per rate,
each on its own seed, one JSON line per run and a last line with the
knee: the highest rate at which every run was correct, met
``P95_LIMIT_MS`` and held its backlog: the median latency of the window's
second half exceeds its first half's by at most ``BACKLOG_LIMIT`` of
that first median. The cell runs at four fifths of the knee.
"""
import argparse
import json
import sys

from . import cells
from .run import ROOT, execute

P95_LIMIT_MS = 4000.0  # aggregate broadcast at 2/3 of the slot to the next block
BACKLOG_LIMIT = 0.10
REPEAT = 2


def sustained(result: dict, info: dict) -> bool:
    p95 = info["aggregate_p95_ms"]
    return (result["correct"] and p95 <= P95_LIMIT_MS
            and info["latency_trend_ms"]
            <= BACKLOG_LIMIT * info["first_half_p50_ms"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    from . import device

    device.prepare(ROOT)
    held = {}
    k = 0
    for rate in (float(r) for r in args.rates.split(",")):
        for _ in range(REPEAT):
            cell = cells.load(ROOT, args.workload)
            cell.mix["rate_per_s"] = rate
            result, info = execute(cell, args.seed + k, args.seconds, False)
            k += 1
            ok = sustained(result, info)
            held[rate] = held.get(rate, True) and ok
            print(json.dumps({
                "rate_per_s": rate, "seed": args.seed + k - 1,
                "sustained": ok, "correct": result["correct"],
                "metrics": {n: m["value"] for n, m in result["metrics"].items()},
                "aggregate_p95_ms": info["aggregate_p95_ms"],
                "latency_trend_ms": info["latency_trend_ms"],
                "first_half_p50_ms": info["first_half_p50_ms"],
                "generator_late_ms": info["generator_late_ms"],
                "gc_in_window": info["gc_in_window"],
                "serve": info["serve"], "compiles_in_window":
                    info["compiles_in_window"],
                "vm_shapes_new_in_window": info["vm_shapes_new_in_window"],
                "setup_phases": info["setup_phases"]}), flush=True)
    knee = max((r for r, ok in held.items() if ok), default=None)
    print(json.dumps({"knee_per_s": knee, "held": held,
                      "p95_limit_ms": P95_LIMIT_MS,
                      "backlog_limit": BACKLOG_LIMIT}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
