#!/usr/bin/env python
"""What the tracer costs: microseconds to open and close one span with
three attributes, single thread, no profiler session.

The tracer is always on, so this is interpreter-lock time every request
pays once per span.  Give another checkout's ``telemetry.py`` to compare
two commits on one machine (the module imports nothing of the package).

Usage: python tools/span_cost.py [path/to/telemetry.py ...]
Prints one JSON line per file: the best of five rounds of 200,000 spans.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys
import time

ROUNDS, SPANS = 5, 200_000


def span_micros(path: str) -> float:
    spec = importlib.util.spec_from_file_location("span_cost_telemetry", path)
    telemetry = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(telemetry)
    tracer = telemetry.Tracer()
    best = float("inf")
    for _ in range(ROUNDS):
        t0 = time.perf_counter_ns()
        for _ in range(SPANS):
            with tracer.start_span(
                    "segment.dispatch",
                    {"segment": "seg_0", "index": "idx", "shard": 0}):
                pass
        best = min(best, (time.perf_counter_ns() - t0) / SPANS / 1e3)
    return best


def main(argv: list[str]) -> int:
    default = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "opensearch_tpu", "common",
        "telemetry.py")
    for path in argv[1:] or [default]:
        print(json.dumps({"telemetry": os.path.relpath(path),
                          "span_open_close_us": round(span_micros(path), 3),
                          "spans": SPANS, "rounds": ROUNDS}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
