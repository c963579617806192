#!/usr/bin/env python
"""What ``ops/bm25.py::gather_postings`` costs on the device in each of
its two lowerings: the numbers that set ``slice_lowering``'s threshold
and ``copy_chunk``'s floor.

For each ``t_pad x budget`` and each kind of bag it runs K gathers one
after the other inside one program (so the host's dispatch, ~0.5 ms a
call, is not in the number), once with the contiguous-slice copy forced
and once with the element gather forced, over an 8,388,608-slot column
73% full (the ``msmarco-passage-bm25`` segment's), and checks that both
give the same sums.  A ``filled`` bag has three quarters of its slots
active with runs that fill the budget; a ``sparse`` one is what a
SPLADE query brings a segment: three quarters of the slots active, a
quarter to a half of the bucket filled, a few long runs and many short.
Prints one JSON line a case and bag: ``chosen`` is what
``slice_lowering`` picks there, ``chunk`` what ``copy_chunk`` says,
``trips`` the mean count of chunks the copy moves a gather (its loop's
trip count), ``postings`` the mean it places; ``--chunks a,b,...`` also
times the copy at those chunk sizes (``slices_us_at``; a chunk of the
whole budget is one window a slot, the copy before PR 38 less its
inactive slots).  A time is a device time only where ``platform`` is
``tpu``.

Usage: python tools/gather_bench.py [--chunks a,b,...] [t_padxbudget ...]
"""

from __future__ import annotations

import argparse
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

from opensearch_tpu.ops import bm25  # noqa: E402

POSTINGS, FILL, N_PAD = 8_388_608, 0.73, 131_072
K, REPS = 16, 5
CASES = ["4x4096", "8x4096", "8x65536", "8x262144", "32x4096", "32x65536",
         "32x262144", "128x4096", "128x65536", "128x262144", "512x65536",
         "512x262144",
         # the long bags of ``splade_sparse_*`` and ``yfcc_filtered_paced``
         "1x1048576", "2x1048576", "16x1048576", "32x1048576", "64x1048576"]


def make_column(rng):
    """Term runs of every size class up to 2**20, shuffled."""
    fill, lens, total = int(POSTINGS * FILL), [], 0
    while total < fill:
        top = 2 ** int(rng.integers(0, 21))
        lens.append(int(rng.integers(top // 2 + 1, top + 1)))
        total += lens[-1]
    lens = np.array(lens, np.int64)
    lens[-1] -= total - fill
    offsets = np.zeros(len(lens) + 1, np.int32)
    np.cumsum(lens, out=offsets[1:])
    doc_ids = np.zeros(POSTINGS, np.int32)
    tfs = np.zeros(POSTINGS, np.float32)
    doc_ids[:fill] = rng.integers(0, N_PAD - 1, fill)
    tfs[:fill] = rng.random(fill, dtype=np.float32)
    return offsets, doc_ids, tfs, lens


def pick_terms(rng, lens, t_pad, budget):
    """Three quarters of the slots active, runs that fill the budget."""
    per = max(budget // t_pad, 1)
    fits = np.flatnonzero((lens <= per) & (lens > per // 2))
    n = max(t_pad * 3 // 4, 1)
    tids = np.zeros(t_pad, np.int32)
    active = np.zeros(t_pad, bool)
    tids[:n], active[:n] = rng.choice(fits, n), True
    return tids, active


def pick_sparse_terms(rng, lens, t_pad, budget):
    """Three quarters of the slots active, a quarter to a half of the
    budget filled by runs of lognormal shares: one or two take most."""
    n = max(t_pad * 3 // 4, 1)
    share = rng.lognormal(0.0, 1.0, n)
    want = share / share.sum() * rng.uniform(0.25, 0.5) * budget
    by_len = np.argsort(lens)
    at = np.searchsorted(lens[by_len], np.maximum(want, 1.0))
    tids = np.zeros(t_pad, np.int32)
    active = np.zeros(t_pad, bool)
    tids[:n], active[:n] = by_len[np.minimum(at, len(lens) - 1)], True
    while lens[tids[active]].sum() > budget:    # keep the contract
        active[np.flatnonzero(active)[-1]] = False
    return tids, active


BAGS = {"filled": pick_terms, "sparse": pick_sparse_terms}


def program(budget: int, slices: bool, chunk: int | None = None):
    @jax.jit
    def run(offsets, doc_ids, tfs, tids_k, active_k):
        def one(carry, xs):
            d, tf, slot, _valid = bm25.gather_postings(
                offsets, doc_ids, tfs, xs[0], xs[1], budget=budget,
                pad_doc=N_PAD - 1)
            return (carry[0] + (d.sum() + slot.sum()).astype(jnp.int32),
                    carry[1] + tf.sum().astype(jnp.float32)), None
        return lax.scan(one, (jnp.int32(0), jnp.float32(0.0)),
                        (tids_k, active_k))[0]

    def traced_with(*args):
        # both choices are read while the program is traced
        chosen = bm25.slice_lowering, bm25.copy_chunk
        bm25.slice_lowering = lambda t_pad, budget: slices
        if chunk is not None:
            bm25.copy_chunk = lambda t_pad, budget: chunk
        try:
            return run(*args)
        finally:
            bm25.slice_lowering, bm25.copy_chunk = chosen
    return traced_with


def seconds_a_gather(run, args):
    t0 = time.perf_counter()
    out = jax.block_until_ready(run(*args))
    first = time.perf_counter() - t0
    jax.block_until_ready(run(*args))
    t0 = time.perf_counter()
    jax.block_until_ready([run(*args) for _ in range(REPS)])
    return (time.perf_counter() - t0) / REPS / K, first, out


def main(argv: list[str]) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chunks", default=[],
                    type=lambda s: [int(x) for x in s.split(",")])
    ap.add_argument("cases", nargs="*", default=CASES,
                    metavar="t_padxbudget")
    opts = ap.parse_args(argv[1:])
    rng = np.random.default_rng(7)
    offsets, doc_ids, tfs, lens = make_column(rng)
    dev = jax.devices()[0]
    columns = [jnp.asarray(x) for x in (offsets, doc_ids, tfs)]
    for case in opts.cases:
        t_pad, budget = (int(x) for x in case.split("x"))
        chunk = bm25.copy_chunk(t_pad, budget)
        for bag, pick in BAGS.items():
            picks = [pick(rng, lens, t_pad, budget) for _ in range(K)]
            runs = [lens[tids[active]] for tids, active in picks]
            args = columns + [jnp.asarray(np.stack([p[i] for p in picks]))
                              for i in (0, 1)]
            line = {"platform": dev.platform, "device_kind": dev.device_kind,
                    "t_pad": t_pad, "budget": budget, "bag": bag,
                    "postings": float(np.mean([r.sum() for r in runs])),
                    "chosen": "slices" if bm25.slice_lowering(t_pad, budget)
                    else "elements",
                    "chunk": chunk,
                    "trips": float(np.mean(
                        [np.ceil(r / chunk).sum() for r in runs]))}
            sums = []
            for name, slices in (("slices", True), ("elements", False)):
                per, first, out = seconds_a_gather(
                    program(budget, slices), args)
                line[f"{name}_us"] = round(per * 1e6, 1)
                line[f"{name}_first_call_s"] = round(first, 2)
                sums.append([float(x) for x in out])
            line["same_sums"] = (sums[0][0] == sums[1][0] and abs(
                sums[0][1] - sums[1][1]) <= 1e-4 * abs(sums[1][1]))
            at = {c: seconds_a_gather(program(budget, True, c), args)
                  for c in opts.chunks if c <= budget and c != chunk}
            if at:
                line["slices_us_at"] = {
                    str(c): round(per * 1e6, 1) for c, (per, _, _)
                    in at.items()}
                line["same_sums"] &= all(
                    out[0] == sums[0][0] for _, _, out in at.values())
            print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
