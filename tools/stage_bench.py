#!/usr/bin/env python
"""What staging a request's term-bag inputs costs the host: eight
``DeviceResidencyLedger.stage_input`` calls of a packed ``int32[33]``
(one a segment, a six-term bag at ``t_pad`` 8) against one of the
stacked ``int32[8, 33]`` (``executor.py::_StackedBag``), alone and
under several threads at once, as ``msmarco_paced`` and
``msmarco_closed`` stage them.

A round is one request's staging.  Prints one JSON line a case:
``call_us`` the wall time of the staging calls a request (what a
``segment.prepare`` span's ``bind`` part pays for them), ``ready_us``
the same calls and a ``block_until_ready`` of what they returned, and
requests a second over all threads.  Each case runs twice, in turn, so a
drift of the host shows.  A time is a chip's only where ``platform`` is
``tpu``.

Usage: python tools/stage_bench.py [threads ...]   (default: 1 6)
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import opensearch_tpu.common.jaxenv  # noqa: F401,E402

import jax  # noqa: E402
import numpy as np  # noqa: E402

from opensearch_tpu.common.device_ledger import device_ledger  # noqa: E402

SEGMENTS, WIDTH = 8, 4 * 8 + 1
SECONDS = 3.0


def eight(rows: np.ndarray) -> list:
    led = device_ledger()
    return [led.stage_input(rows[i]) for i in range(SEGMENTS)]


def stacked(rows: np.ndarray) -> list:
    return [device_ledger().stage_input(rows)]


CASES = {"eight": eight, "stacked": stacked}


def run_case(name: str, threads: int, ready: bool) -> dict:
    stage = CASES[name]
    done = [0] * threads
    spent = [0.0] * threads

    def client(i: int):
        rows = np.arange(SEGMENTS * WIDTH, dtype=np.int32).reshape(
            SEGMENTS, WIDTH) + i
        while time.monotonic() < stop:
            rows[:, -1] += 1              # a new request's inputs
            t0 = time.perf_counter()
            out = stage(rows)
            if ready:
                jax.block_until_ready(out)
            spent[i] += time.perf_counter() - t0
            done[i] += 1

    for _ in range(20):                    # warm the transfer path first
        jax.block_until_ready(stage(np.zeros((SEGMENTS, WIDTH), np.int32)))
    t_start = time.monotonic()
    stop = t_start + SECONDS
    pool = [threading.Thread(target=client, args=(i,), daemon=True,
                             name=f"stage-bench-{i}")
            for i in range(threads)]
    for t in pool:
        t.start()
    for t in pool:
        t.join()
    wall = time.monotonic() - t_start
    requests = sum(done)
    dev = jax.devices()[0]
    return {"case": name, "threads": threads, "ready": ready,
            "platform": dev.platform, "device_kind": dev.device_kind,
            "requests": requests, "req_per_s": requests / wall,
            ("ready_us" if ready else "call_us"):
                1e6 * sum(spent) / requests}


def main(argv: list[str]) -> int:
    threads = [int(a) for a in argv] or [1, 6]
    for n in threads:
        for ready in (False, True):
            for name in ("eight", "stacked", "stacked", "eight"):
                print(json.dumps(run_case(name, n, ready)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
