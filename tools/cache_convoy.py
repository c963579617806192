#!/usr/bin/env python
"""What a ``Cache`` costs its callers when six threads miss at once, as
``executor.py::_prepared`` misses in ``msmarco_closed`` (ISSUE 40).

A request is eight rounds, one a segment: ``get`` (a miss: no key comes
twice), a bind (the pad of a six-term bag in Python and numpy, and
``DeviceResidencyLedger.stage_input`` of its packed ``int32[33]``), then
``put`` of ``(dims, (staged, column))``, where ``column`` is the
segment's shared device column.  The cache is ``_prepared``'s: the
fielddata breaker, ``ShardSearcher._prep_weight`` (a column counts
1 MiB), 64 MiB, filled before the clock starts, so every ``put`` evicts
the least recent entry and with it the last reference to its staged
array.  Two variants of the repo's ``Cache``:

  as_now  the removed value's last reference dropped inside the
          critical section (``_remove`` clears it), as before ISSUE 40
  after   the repo's ``Cache``: dropped after the lock is released

Prints one JSON line a case: requests a second, ``put`` and ``get`` ms
a request (the calls' wall time, summed over the eight rounds), and
``cache.<name>.lock_waits`` a request.  A time is a chip's only where
``platform`` is ``tpu``.

Usage: python tools/cache_convoy.py [threads ...]   (default: 1 6)
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
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from opensearch_tpu.common.cache import Cache  # noqa: E402
from opensearch_tpu.common.device_ledger import device_ledger  # noqa: E402
from opensearch_tpu.common.telemetry import metrics  # noqa: E402
from opensearch_tpu.search.executor import ShardSearcher  # noqa: E402

SEGMENTS, TERMS, T_PAD = 8, 6, 8
SECONDS = 4.0


class FreedUnderLock(Cache):
    """The parent's behaviour: a removed value is freed under the lock."""

    def _remove(self, key, reason):
        entry = super()._remove(key, reason)
        if entry is not None:
            entry.value = None
        return entry


VARIANTS = {"as_now": FreedUnderLock, "after": Cache}


def bind(rng_state: list, seg: int):
    """A six-term bag's pad and pack, and its one H2D copy."""
    rng_state[0] = (rng_state[0] * 1103515245 + 12345) & 0x7FFFFFFF
    tids = np.zeros(T_PAD, np.int32)
    active = np.zeros(T_PAD, bool)
    for i in range(TERMS):
        tids[i] = (rng_state[0] >> i) % 400_000
        active[i] = True
    idfs = np.linspace(1.0, 2.0, T_PAD, dtype=np.float32)
    packed = np.concatenate([tids, active.astype(np.int32),
                             idfs.view(np.int32), idfs.view(np.int32),
                             np.array([1], np.int32)])
    return (T_PAD * 4096, T_PAD, True), device_ledger().stage_input(packed)


def run_case(variant: str, threads: int, columns: list) -> dict:
    name = f"tool.convoy.{variant}.{threads}"
    cache = VARIANTS[variant](name, max_weight=64 << 20,
                              breaker="fielddata",
                              weigher=ShardSearcher._prep_weight)
    serial = [0]

    def put_one(seg: int, state: list):
        dims, staged = bind(state, seg)
        serial[0] += 1
        cache.put(((serial[0],), seg), (dims, (staged, columns[seg])))

    state = [threads]
    while cache.weight < (64 << 20) - (2 << 20):       # fill it first
        put_one(serial[0] % SEGMENTS, state)
    jax.block_until_ready([v for _, v, _ in cache.entries()])
    waits0 = metrics().counter(f"cache.{name}.lock_waits").value
    stop = time.monotonic() + SECONDS
    done = [0] * threads
    put_s = [0.0] * threads
    get_s = [0.0] * threads

    def client(i: int):
        st = [i * 7919 + 1]
        n = 0
        while time.monotonic() < stop:
            for seg in range(SEGMENTS):
                n += 1
                key = ((i, n), seg)
                t0 = time.perf_counter()
                assert cache.get(key) is None
                t1 = time.perf_counter()
                dims, staged = bind(st, seg)
                t2 = time.perf_counter()
                cache.put(key, (dims, (staged, columns[seg])))
                t3 = time.perf_counter()
                get_s[i] += t1 - t0
                put_s[i] += t3 - t2
            done[i] += 1

    t_start = time.monotonic()
    pool = [threading.Thread(target=client, args=(i,), daemon=True,
                             name=f"cache-convoy-{i}")
            for i in range(threads)]
    for t in pool:
        t.start()
    for t in pool:
        t.join()
    wall = time.monotonic() - t_start
    requests = sum(done)
    waits = metrics().counter(f"cache.{name}.lock_waits").value - waits0
    cache.invalidate_all()
    dev = jax.devices()[0]
    return {"variant": variant, "threads": threads,
            "platform": dev.platform, "device_kind": dev.device_kind,
            "requests": requests,
            "req_per_s": requests / wall,
            "put_ms_per_request": 1e3 * sum(put_s) / requests,
            "get_ms_per_request": 1e3 * sum(get_s) / requests,
            "lock_waits_per_request": waits / requests,
            "evictions": cache.stats()["evictions"]}


def main(argv: list[str]) -> int:
    threads = [int(a) for a in argv] or [1, 6]
    # one shared column a segment, as dseg.impacts is (2 MiB: capped at 1)
    columns = [jnp.full((1 << 19,), float(s), jnp.float32)
               for s in range(SEGMENTS)]
    jax.block_until_ready(columns)
    for n in threads:
        # each variant twice, in turn, so a drift of the host shows
        for variant in ("as_now", "after", "after", "as_now"):
            print(json.dumps(run_case(variant, n, columns)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
