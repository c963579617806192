#!/usr/bin/env python3
"""Find a traffic file's ``clients`` or ``rate`` once: set a cell up, then
measure a closed loop for ``--seconds`` at each client count.

    python3 benchmarks/sweep.py --workload msmarco_closed --seed 1 --clients 1,2,4,8,16,32

``clients`` is the smallest count whose qps is within 5% of the best;
a paced cell's ``rate`` is the whole number nearest half the qps of one
client.  Prints one JSON line per count.  Needs the chip, like run.py.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--clients", default="1,2,4,8,16,32")
    args = ap.parse_args(argv)

    from benchmarks import harness

    cell = harness.load_cell(args.workload)
    try:
        device = harness.find_chip(cell.chips)
    except harness.NoChip as exc:
        harness.say(f"no chip: {exc}")
        return 3
    session = harness.Session(cell, args.seed, device)
    try:
        for clients in (int(c) for c in args.clients.split(",")):
            win = session.window(args.seconds, mix={
                "loop": "closed", "clients": clients})
            nums = win["nums"]
            print(json.dumps({
                "clients": clients, "qps": nums["qps"],
                "p50_ms": nums["latency_p50_ms"],
                "p95_ms": nums["latency_p95_ms"],
                "failed": nums["failed"],
                "programs_got": win["programs"]}), flush=True)
    finally:
        session.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
