#!/usr/bin/env python
"""What an exact top-k costs on the device as ``lax.top_k`` over the whole
key and as ``ops/topk.py``'s two stages at several block sizes: the
numbers that set ``block_size``'s rule.

For each ``n x k`` it runs K top-k one after the other inside one program
(so the host's dispatch is not in the number), each over its own row of a
``[K, n]`` key (uniform values, a tenth of the lanes at -inf, as a
segment's padding and unmatched rows are), and checks that every variant
returns what ``lax.top_k`` returns.  Taking a row costs both sides one
copy of the key.  Prints one JSON line a case: microseconds a top-k for
``lax`` and for each block size, and ``chosen``, what ``block_size``
picks there.  A time is a device time only where ``platform`` is ``tpu``.

Usage: python tools/topk_bench.py [nxk ...]
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import opensearch_tpu.common.jaxenv  # noqa: F401,E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import lax  # noqa: E402

from opensearch_tpu.ops import topk  # noqa: E402

K, REPS = 16, 5
BLOCKS = (128, 256, 512, 1024, 2048)
CASES = ["1048576x10", "262144x100", "262144x10", "131072x10",
         "1048576x100", "1048576x1000", "131072x100", "131072x1000",
         "65536x10", "32768x10", "16384x10", "8192x10", "4096x10"]


def _lax(key, k, _b):
    vals, idx = lax.top_k(key, k)
    return vals, idx, jnp.max(key)


def looped(fn, k: int, b: int):
    """K top-k in one program; the sums keep every result alive."""
    def run(keys):
        def body(i, acc):
            vals, idx, mx = fn(keys[i], k, b)
            return (acc[0] + jnp.where(vals > -jnp.inf, vals, 0).sum() + mx,
                    acc[1] + idx.sum(dtype=jnp.int32))
        return lax.fori_loop(0, K, body, (jnp.float32(0), jnp.int32(0)))
    return jax.jit(run)


def timed(fn, keys) -> float:
    jax.block_until_ready(fn(keys))
    best = float("inf")
    for _ in range(REPS):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(keys))
        best = min(best, time.perf_counter() - t0)
    return best / K * 1e6


def main(argv):
    rng = np.random.default_rng(34)
    platform = jax.devices()[0].platform
    for case in argv or CASES:
        n, k = (int(x) for x in case.split("x"))
        keys = rng.random((K, n), dtype=np.float32)
        keys[rng.random((K, n)) < 0.1] = -np.inf
        keys = jnp.asarray(keys)
        want = jax.jit(lambda a: lax.top_k(a, k))(keys[0])
        line = {"n": n, "k": k, "platform": platform,
                "chosen": topk.block_size(n, k),
                "lax_us": round(timed(looped(_lax, k, 0), keys), 1)}
        for b in BLOCKS:
            if n % b or k > n // b or k * b >= n:
                continue
            got = jax.jit(lambda a, b=b: topk._two_stage(a, k, b))(keys[0])
            same = all(np.array_equal(np.asarray(w), np.asarray(g))
                       for w, g in zip(want, got))
            line[f"b{b}_us"] = round(
                timed(looped(topk._two_stage, k, b), keys), 1)
            line["same"] = line.get("same", True) and same
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
