"""Readings that set the limits of the comparison, on the chip.

    python3 -m bench.control --workload <cell> --seconds <s> --seeds 1 2 3 ...

For each seed, in one process: one run of the cell at its own size with
a short window, the comparison of the program's answers with the
reference (the lower readings), and the same comparison with the
control in the program's place: the reference computed one precision
below the program's (the upper readings). Prints one JSON line per seed
and, last, per compared number the largest program reading and the
smallest control reading. The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import sys

from . import discover, run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = discover.resolve(args.workload)
    try:
        run.prepare(require_tpu=True, chips=cell.chips)
    except SystemExit as e:
        print(e, file=sys.stderr)
        return 1
    lower: dict[str, float] = {}
    upper: dict[str, float] = {}
    for seed in args.seeds:
        r = run.execute(cell, seed, args.seconds, False, control=True)
        print(json.dumps({"seed": seed, "correct": r["correct"],
                          "metrics": r["metrics"], "program": r["checks"],
                          "control": r["control_checks"]}), flush=True)
        for name, c in r["checks"].items():
            lower[name] = max(lower.get(name, 0.0), c["value"])
        for name, c in r["control_checks"].items():
            upper[name] = min(upper.get(name, float("inf")), c["value"])
    print(json.dumps({"workload": args.workload, "seeds": args.seeds,
                      "lower": lower, "upper": upper}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
