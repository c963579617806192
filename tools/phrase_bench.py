#!/usr/bin/env python
"""What an exact phrase's frequency costs on the device, old kernel
against new: the numbers behind ``ops/phrase.py`` (PERF.md section 3).

  old   ``old_phrase_freqs``, the kernel until PR 39: every slot gathered
        whole into its own ``1024 * 4^k`` bucket, a lane's posting found
        by a search over the segment's whole ``pos_offsets`` column,
        int64 ``doc * 2^22 + pos`` keys, slot 0 the anchor whatever it
        holds (``old_head_first``: the frequent word leads the phrase,
        "of life")
  new   ``phrase_ops.phrase_freqs``: the rarest slot's run as one window,
        its posting by a search over the anchor term's own run, every
        further slot probed; int32

A case is ``anchor positions x second-slot positions`` in a segment of
``--docs`` articles whose columns are padded as the cell's are
(``--positions`` lanes of positions, half as many postings); ``:new``
after a case leaves the old kernel out.  Half of the
anchor's occurrences are followed by the second word.  Each variant runs
``ROWS`` phrases (an anchor term each) one after the other inside one
program; old and new must agree on every doc's frequency.  Prints one
JSON line a case, microseconds a phrase.  A time is a device time only
where ``platform`` is ``tpu``.

Usage: python tools/phrase_bench.py [--docs N] [--positions N] [AxS ...]
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

from opensearch_tpu.index.segment import pad_bucket, pad_pow2  # noqa: E402
from opensearch_tpu.ops import phrase as phrase_ops  # noqa: E402
from opensearch_tpu.ops.phrase import (KEY_PAD, POS_BASE,  # noqa: E402
                                       gather_term_positions)

REPS, ROWS, ARTICLE = 3, 4, 5500
# ":new" leaves the old kernel out (seconds a phrase at 4M positions)
CASES = ([f"{a}x4096" for a in (600, 2400, 10000, 40000, 160000)]
         + ["600x4000000:new", "2400x4000000", "10000x4000000:new",
            "40000x4000000", "160000x4000000:new"])


def old_phrase_freqs(postings, term_ids, term_active, offsets_in_phrase, *,
                     budgets: tuple, n_pad: int):
    """``ops/phrase.py::phrase_freqs`` as it stood until PR 39 (the
    oracle of ``tests/test_phrase_positions.py`` too)."""
    docs0, pos0, ok = gather_term_positions(
        postings["offsets"], postings["pos_offsets"], postings["positions"],
        postings["doc_ids"], term_ids[0], term_active[0],
        budget=budgets[0], pad_doc=n_pad - 1)
    base0 = offsets_in_phrase[0]
    for j in range(1, len(budgets)):
        docs_j, pos_j, valid_j = gather_term_positions(
            postings["offsets"], postings["pos_offsets"],
            postings["positions"], postings["doc_ids"], term_ids[j],
            term_active[j], budget=budgets[j], pad_doc=n_pad - 1)
        keys_j = jnp.where(valid_j,
                           docs_j.astype(jnp.int64) * POS_BASE + pos_j,
                           KEY_PAD)
        target = (docs0.astype(jnp.int64) * POS_BASE + pos0
                  + (offsets_in_phrase[j] - base0))
        loc = jnp.searchsorted(keys_j, target)
        loc = jnp.clip(loc, 0, budgets[j] - 1)
        ok = ok & (keys_j[loc] == target)
    return jnp.zeros(n_pad, jnp.float32).at[docs0].add(ok.astype(jnp.float32))


def segment(rng, n_docs: int, anchor: int, second: int, lanes: int):
    """Staged columns of ``ROWS`` anchor terms (ids 0 ..) and one second
    term (id ``ROWS``), padded to ``lanes`` lanes of positions and half as
    many postings.  Occurrences are (doc, position) pairs drawn without
    repeats; half of every anchor's are followed by the second word."""
    space = n_docs * ARTICLE
    runs = []
    follows = []
    for _ in range(ROWS):
        at = np.sort(rng.choice(space // 2, anchor, replace=False)) * 2
        runs.append(at)
        follows.append(at[rng.random(anchor) < 0.5] + 1)
    extra = rng.choice(space // 2, max(second - sum(map(len, follows)), 0),
                       replace=False) * 2 + 1
    runs.append(np.unique(np.concatenate(follows + [extra])))
    offsets, doc_ids, pos_offsets, positions, n_pos = [0], [], [], [], 0
    for at in runs:
        doc, pos = at // ARTICLE, at % ARTICLE
        first = np.flatnonzero(np.r_[True, doc[1:] != doc[:-1]])
        doc_ids.append(doc[first])
        pos_offsets.append(n_pos + first)
        positions.append(pos)
        n_pos += len(pos)
        offsets.append(offsets[-1] + len(first))
    doc_ids = np.concatenate(doc_ids)
    positions = np.concatenate(positions)
    pos_offsets = np.concatenate(pos_offsets + [[n_pos]])

    def pad(a, size, fill):
        out = np.full(size, fill, np.int32)
        out[:len(a)] = a
        return jnp.asarray(out)

    p_pad = max(pad_pow2(len(doc_ids) + 1), lanes // 2)
    return {"offsets": pad(offsets, pad_pow2(len(offsets)), offsets[-1]),
            "doc_ids": pad(doc_ids, p_pad, n_docs),
            "pos_offsets": pad(pos_offsets, p_pad, pos_offsets[-1]),
            "positions": pad(positions, max(pad_pow2(len(positions)), lanes),
                             0)}, len(runs[-1])


def looped(one):
    """``ROWS`` phrases in one program; the sum keeps every result."""
    def run(postings):
        def body(r, acc):
            return acc + one(postings, r.astype(jnp.int32))
        return lax.fori_loop(0, ROWS, body, jnp.zeros((), jnp.float32))
    return jax.jit(run)


def timed(fn, postings) -> float:
    jax.block_until_ready(fn(postings))
    best = float("inf")
    for _ in range(REPS):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(postings))
        best = min(best, time.perf_counter() - t0)
    return round(best / ROWS * 1e6, 1)


def main(argv) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--docs", type=int, default=11484)
    ap.add_argument("--positions", type=int, default=1 << 26)
    ap.add_argument("cases", nargs="*", default=CASES)
    args = ap.parse_args(argv)
    rng = np.random.default_rng(39)
    n_pad = pad_pow2(args.docs + 1)
    for case in args.cases:
        case, _, only = case.partition(":")
        anchor, second = (int(x) for x in case.split("x"))
        postings, n_second = segment(rng, args.docs, anchor, second,
                                     args.positions)
        b_anchor = pad_bucket(anchor, minimum=1024)
        b_second = pad_bucket(n_second, minimum=1024)
        new_budget = b_anchor
        pair = jnp.asarray([0, 1], jnp.int32)
        on = jnp.ones(2, bool)

        def old(p, r):
            return old_phrase_freqs(
                p, jnp.stack([r, jnp.int32(ROWS)]), on, pair,
                budgets=(b_anchor, b_second), n_pad=n_pad)

        def old_head_first(p, r):    # the second word leads: "of life"
            return old_phrase_freqs(
                p, jnp.stack([jnp.int32(ROWS), r]), on, pair[::-1],
                budgets=(b_second, b_anchor), n_pad=n_pad)

        def new(p, r):
            ids = jnp.zeros(4, jnp.int32).at[0].set(r).at[1].set(ROWS)
            return phrase_ops.phrase_freqs(
                p, ids, jnp.asarray([0, 1, 0, 0], jnp.int32), jnp.int32(2),
                budget=new_budget, n_pad=n_pad)

        variants = [("old", old), ("old_head_first", old_head_first),
                    ("new", new)]
        if only == "new":
            variants = variants[2:]
        want = np.asarray(jax.jit(variants[0][1])(postings, jnp.int32(0)))
        line = {"anchor_positions": anchor, "second_positions": n_second,
                "old_buckets": [b_anchor, b_second],
                "new_bucket": new_budget, "n_pad": n_pad,
                "phrases_found": int(want.sum()),
                "platform": jax.devices()[0].platform}
        for name, fn in variants[1:]:
            got = np.asarray(jax.jit(fn)(postings, jnp.int32(0)))
            line[f"{name}_same"] = bool(np.array_equal(want, got))
        for name, fn in variants:
            line[f"{name}_us"] = timed(
                looped(lambda p, r, fn=fn: fn(p, r).sum()), postings)
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
