"""The control: the plain reference put in the program's place, without
bisection (``judge.ReferenceBackend(bisect=False)``), driven through a
whole run of a cell at its own size. Its ``correct`` must come out false:

    python3 -m benchmark.control --workload block300k.replay --seed 7 --seconds 10

Not part of the benchmark's own runs; it is how the limits in PERF.md were
shown to separate a sound program from one that breaks a guarantee.
"""
import argparse
import json
import sys

from . import cells, judge
from .run import ROOT, execute


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    from . import device

    device.prepare(ROOT)
    cell = cells.load(ROOT, args.workload)
    result, info = execute(cell, args.seed, args.seconds, False,
                           backend=judge.ReferenceBackend(bisect=False))
    print(json.dumps({"control": args.workload, "seed": args.seed,
                      "correct": result["correct"],
                      "attempted": result["attempted"],
                      "compared": result["compared"],
                      "errors": info["errors"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
