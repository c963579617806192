#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once:

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process boots a node, installs the configuration's data made from
``--seed``, warms every program the configuration can need, drives the
cell's traffic over HTTP for ``--seconds`` and compares what came back
with the configuration's plain reference.  The last line of standard
output is the result; the last lines of standard error are the numbers
compared, each beside its limit.  Without a TPU it exits 3 and prints no
result: there is no CPU mode (tests/benchmarks_harness drives the same
code at a tiny size).
"""

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchmarks import harness

    cell = harness.load_cell(args.workload)
    try:
        device = harness.find_chip(cell.chips)
    except harness.NoChip as exc:
        harness.say(f"no chip: {exc}")
        return 3
    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), t_process=T_PROCESS,
                              device=device)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
