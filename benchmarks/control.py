#!/usr/bin/env python3
"""Read the control of a cell at the cell's own size: the configuration's
plain reference computed in a lower precision, put in the program's place
and judged by the comparison that decides ``correct``.

    python3 benchmarks/control.py --workload sift_paced --seeds 1,2,3 --precision bfloat16

Prints one JSON line a seed with the numbers compared; PERF.md's limits
are set between these and what sound runs of run.py read.  Host numpy
only; tests/benchmarks_harness/test_control.py keeps the same at a size a
test run can hold.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--precision", default="bfloat16")
    ap.add_argument("--queries", type=int, default=0,
                    help="how many of the seed's queries (default: as many "
                         "as a run compares)")
    args = ap.parse_args(argv)

    from benchmarks import compare, harness

    cell = harness.load_cell(args.workload)
    cfg = cell.cfg
    for seed in (int(s) for s in args.seeds.split(",")):
        data = cell.kind.generate(cfg, seed)
        queries = cell.kind.queries(cfg, data, seed)[
            :args.queries or int(cfg["compare_max"])]
        exact = cell.reference.Reference(cfg, data)
        low = cell.reference.Reference(cfg, data, args.precision)
        rows = [[(str(i), s) for i, s in r] for r in low.topk_many(queries)]
        numbers = compare.compare(exact, queries, rows, cfg["k"])
        numbers.update(failed=0, device_faults=0)
        correct, _ = compare.verdict(numbers, cfg["limits"])
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "precision": args.precision,
                          "queries": len(queries), "correct": correct,
                          **numbers}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
