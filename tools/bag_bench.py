#!/usr/bin/env python
"""What a scored term bag costs on the device from its gathered lanes to
its top-k, three ways: the numbers that set ``ops/bm25.py::sorted_bag``.

  scatter   the dense path: ``zeros(n_pad).at[d].add(contrib)``, the
            match rule, ``topk_and_max`` over ``[n_pad]``
  flagged   the same accumulator filled slot by slot, each slot's run
            (sorted, unique doc ids) added as one window of
            ``min(budget, n_pad)`` lanes declared ``unique_indices`` and
            ``indices_are_sorted`` (ROADMAP B3 (i); for the record)
  sorted    ``bm25_ops.sorted_lanes_topk``: sort by (doc, slot), fold the
            runs in slot order, ``topk_and_max`` over ``[budget]``

A case is ``t_pad x budget x n_pad x fill``: ``fill`` per cent of the
``budget`` lanes carry a posting, spread evenly over ``t_pad`` terms, each
term on its own sorted sample of the ``n_pad`` docs.  The gather is not
in the number (all three share it).  Each variant runs K bags one after
the other inside one program, each bag its own row of the inputs, and the
three must agree bit for bit (values, total, maximum, and ids above
``-inf``).  Prints one JSON line a case, microseconds a bag.  A time is a
device time only where ``platform`` is ``tpu``.

With ``widths`` first, the numbers that set ``ops/bm25.py::
_NARROW_FLOOR`` instead: the whole ``impact_topk_sorted`` program (gather
included) compiled with one sort (``one``, the narrow width disabled) and
with both widths (``two``), over one CSR segment of ``n_pad`` 262,144
whose ``t_pad`` terms fill three quarters of the ``budget``; ``half`` of
them active fill three eighths, so ``two`` takes its narrow branch there
and its full one with every term active.  A case is ``t_pad x budget``;
a line gives us a bag for each program at each fill, the compile
seconds of each (the compile cache off) and whether the two agree bit
for bit on all four results, the ids of ``-inf`` entries too.

Usage: python tools/bag_bench.py [t_padxbudgetxn_padxfill ...]
       python tools/bag_bench.py widths [t_padxbudget ...]
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

from opensearch_tpu.ops import bm25 as bm25_ops  # noqa: E402
from opensearch_tpu.ops import topk as topk_ops  # noqa: E402

REPS, TOP = 3, 10
SHAPES = ([(8, b, 131072) for b in (4096, 16384, 65536, 262144, 1048576)]
          + [(16, 1048576, 262144)]
          + [(t, b, 262144) for t in (32, 64)
             for b in (262144, 1048576, 4194304)])
CASES = [f"{t}x{b}x{n}x{fill}" for t, b, n in SHAPES for fill in (25, 100)]


def bags(rng, rows: int, t_pad: int, budget: int, n_pad: int, fill: int):
    """``rows`` bags as ``gather_postings`` leaves them: doc ids, float32
    contributions, slots and validity over ``budget`` lanes, slot-major."""
    df = min(budget * fill // 100 // t_pad, n_pad)
    d = np.full((rows, budget), n_pad - 1, np.int32)
    slot = np.full((rows, budget), t_pad - 1, np.int32)
    for r in range(rows):
        for t in range(t_pad):
            # a sorted sample without repeats: a start and random steps
            steps = rng.integers(1, max(n_pad // df, 1) + 1, df)
            d[r, t * df:(t + 1) * df] = np.cumsum(steps) - 1
            slot[r, t * df:(t + 1) * df] = t
    valid = np.broadcast_to(np.arange(budget) < t_pad * df,
                            (rows, budget)).copy()
    contrib = np.where(valid, rng.random((rows, budget), np.float32) + 0.01,
                       np.float32(0))
    starts = np.arange(t_pad, dtype=np.int32) * df
    return (jnp.asarray(d), jnp.asarray(contrib), jnp.asarray(slot),
            jnp.asarray(valid)), starts, df


def _finish(scores):
    matched = scores > 0.0
    vals, idx, mx = topk_ops.topk_and_max(
        jnp.where(matched, scores, -jnp.inf), TOP)
    return vals, idx.astype(jnp.int32), matched.sum(dtype=jnp.int32), mx


def scatter(d, contrib, slot, valid, *, t_pad, n_pad, starts, df):
    return _finish(jnp.zeros(n_pad, jnp.float32).at[d].add(contrib))


def flagged(d, contrib, slot, valid, *, t_pad, n_pad, starts, df):
    budget = d.shape[0]
    win = min(budget, n_pad)
    lane = jnp.arange(win, dtype=jnp.int32)
    d = jnp.pad(d, (0, win))
    contrib = jnp.pad(contrib, (0, win))

    def add_slot(t, acc):
        at = jnp.asarray(starts)[t]
        # lanes past the run get distinct ids past n_pad, in order: dropped
        ids = jnp.where(lane < df, lax.dynamic_slice(d, (at,), (win,)),
                        n_pad + lane)
        return acc.at[ids].add(lax.dynamic_slice(contrib, (at,), (win,)),
                               mode="drop", unique_indices=True,
                               indices_are_sorted=True)
    return _finish(lax.fori_loop(0, t_pad, add_slot,
                                 jnp.zeros(n_pad, jnp.float32)))


def sorted_(d, contrib, slot, valid, *, t_pad, n_pad, starts, df):
    vals, ids, tot, mx = bm25_ops.sorted_lanes_topk(
        d, contrib, slot, valid, jnp.int32(1), jnp.float32(-jnp.inf),
        t_pad=t_pad, n_pad=n_pad, k=TOP, fast=True)
    return vals, ids.astype(jnp.int32), tot.astype(jnp.int32), mx


def looped(fn, rows: int, **kw):
    """``rows`` bags in one program; the sums keep every result alive."""
    def run(d, contrib, slot, valid):
        def body(r, acc):
            vals, ids, tot, mx = fn(d[r], contrib[r], slot[r], valid[r], **kw)
            return (acc[0] + jnp.where(vals > -jnp.inf, vals, 0).sum() + mx,
                    acc[1] + ids.sum(dtype=jnp.int32) + tot)
        return lax.fori_loop(0, rows, body, (jnp.float32(0), jnp.int32(0)))
    return jax.jit(run)


def timed(fn, ins, rows: int) -> float:
    jax.block_until_ready(fn(*ins))
    best = float("inf")
    for _ in range(REPS):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*ins))
        best = min(best, time.perf_counter() - t0)
    return round(best / rows * 1e6, 1)


WIDTH_CASES = [f"{t}x{b}" for b in (65536, 262144, 1048576, 4194304)
               for t in (8, 32, 64)]
WIDTH_N_PAD = 262144


def segment(rng, t_pad: int, budget: int):
    """CSR columns (offsets, doc ids, impacts) of ``t_pad`` terms whose
    runs fill three quarters of ``budget``, each a sorted sample of the
    segment's docs without repeats."""
    df = budget * 3 // 4 // t_pad
    runs = [np.sort(rng.permutation(WIDTH_N_PAD)[:df]) for _ in range(t_pad)]
    offsets = np.arange(t_pad + 2, dtype=np.int32) * df
    offsets[-1] = offsets[-2]
    doc_ids = np.concatenate(runs).astype(np.int32)
    imp = (rng.random(len(doc_ids)) + 0.01).astype(np.float32)
    return jnp.asarray(offsets), jnp.asarray(doc_ids), jnp.asarray(imp)


def bag_program(t_pad: int, budget: int, rows: int, floor: int):
    """``rows`` bags, each the segment's terms in its own slot order, in
    one program; ``floor`` is the ``_NARROW_FLOOR`` it is traced with."""
    def one_bag(offsets, doc_ids, imp, tids, active, idfs, weights):
        return bm25_ops.impact_topk_sorted(
            offsets, doc_ids, imp, tids, active, idfs, weights,
            jnp.int32(1), jnp.float32(-jnp.inf), n_pad=WIDTH_N_PAD,
            budget=budget, k=TOP, fast=True)

    def run(offsets, doc_ids, imp, tids, active, idfs, weights):
        saved, bm25_ops._NARROW_FLOOR = bm25_ops._NARROW_FLOOR, floor
        try:
            first = one_bag(offsets, doc_ids, imp, tids[0], active,
                            idfs[0], weights[0])

            def body(r, acc):
                vals, ids, tot, mx = one_bag(offsets, doc_ids, imp, tids[r],
                                             active, idfs[r], weights[r])
                return (acc[0] + jnp.where(vals > -jnp.inf, vals, 0).sum()
                        + mx,
                        acc[1] + (ids.sum() + tot).astype(jnp.int32))
            return first, lax.fori_loop(1, rows, body,
                                        (jnp.float32(0), jnp.int32(0)))
        finally:
            bm25_ops._NARROW_FLOOR = saved
    return run


def widths(argv):
    jax.config.update("jax_enable_compilation_cache", False)
    rng = np.random.default_rng(43)
    platform = jax.devices()[0].platform
    for case in argv or WIDTH_CASES:
        t_pad, budget = (int(x) for x in case.split("x"))
        rows = 17 if budget <= 262144 else 9 if budget <= 1048576 else 5
        cols = segment(rng, t_pad, budget)
        tids = jnp.asarray(np.stack([rng.permutation(t_pad)
                                     for _ in range(rows)]).astype(np.int32))
        idfs = jnp.asarray((rng.random((rows, t_pad)) + 0.5).astype(np.float32))
        weights = jnp.asarray(
            (rng.random((rows, t_pad)) + 0.5).astype(np.float32))
        fills = {"half": jnp.arange(t_pad) < t_pad // 2,
                 "full": jnp.ones(t_pad, bool)}
        line = {"t_pad": t_pad, "budget": budget, "n_pad": WIDTH_N_PAD,
                "platform": platform, "rows": rows}
        got = {}
        for name, floor in (("one", 2 ** 62), ("two", 0)):
            t0 = time.perf_counter()
            fn = jax.jit(bag_program(t_pad, budget, rows, floor)).lower(
                *cols, tids, fills["full"], idfs, weights).compile()
            line[f"{name}_compile_s"] = round(time.perf_counter() - t0, 2)
            for fill, active in fills.items():
                ins = (*cols, tids, active, idfs, weights)
                got[name, fill] = [np.asarray(x) for x in fn(*ins)[0]]
                line[f"{name}_{fill}_us"] = timed(fn, ins, rows)
        line["same"] = all(a.tobytes() == b.tobytes() for f in fills
                           for a, b in zip(got["one", f], got["two", f]))
        print(json.dumps(line), flush=True)


def main(argv):
    if argv[:1] == ["widths"]:
        return widths(argv[1:])
    rng = np.random.default_rng(36)
    platform = jax.devices()[0].platform
    for case in argv or CASES:
        t_pad, budget, n_pad, fill = (int(x) for x in case.split("x"))
        rows = 16 if budget <= 65536 else 8 if budget <= 1048576 else 4
        ins, starts, df = bags(rng, rows, t_pad, budget, n_pad, fill)
        kw = dict(t_pad=t_pad, n_pad=n_pad, starts=starts, df=df)
        line = {"t_pad": t_pad, "budget": budget, "n_pad": n_pad,
                "fill": fill, "postings": t_pad * df, "platform": platform,
                "chosen": bm25_ops.sorted_bag(t_pad, budget, n_pad, TOP)}
        first = [x[0] for x in ins]
        want = jax.jit(lambda *a: scatter(*a, **kw))(*first)
        keep = np.asarray(want[0]) > -np.inf
        for name, fn in (("flagged", flagged), ("sorted", sorted_)):
            got = jax.jit(lambda *a, fn=fn: fn(*a, **kw))(*first)
            line[f"{name}_same"] = bool(
                np.array_equal(np.asarray(want[0]).view(np.int32),
                               np.asarray(got[0]).view(np.int32))
                and np.array_equal(np.asarray(want[1])[keep],
                                   np.asarray(got[1])[keep])
                and int(want[2]) == int(got[2])
                and float(want[3]) == float(got[3]))
        for name, fn in (("scatter", scatter), ("flagged", flagged),
                         ("sorted", sorted_)):
            line[f"{name}_us"] = timed(looped(fn, rows, **kw), ins, rows)
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
