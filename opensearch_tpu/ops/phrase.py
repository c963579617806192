"""Exact-phrase matching as a batched device program.

Lucene's ``PhraseQuery`` walks postings doc-at-a-time advancing position
iterators in lockstep, led by the rarest term (``ExactPhraseMatcher``).
The TPU formulation leads with the rarest term too:

- the *anchor* is the phrase slot with the fewest positions in the
  segment (``PhrasePlan.prepare`` orders the slots; slot 0 here).  Its
  occurrences are one contiguous run of the ``positions`` column, copied
  as one window of ``budget`` lanes, a lane an occurrence;
- a lane's doc comes without a search: the anchor's postings (at most
  a lane each) mark where their position runs start with the step from
  the doc before, and a running sum carries the doc along the window;
- every further slot j is *probed*, never gathered: term j's posting for
  the lane's doc (by a table over the segment's docs where the window is
  a quarter of them or more, ``doc_table``; else by a binary search of
  term j's doc ids), then a binary search of that posting's own position
  run for ``pos + offset_j``.  A lane survives iff every probe hits;
- phrase frequency per doc is a scatter-add of surviving lanes, then BM25
  scores it with idf = sum of the terms' idfs (Lucene PhraseWeight).

A search runs as many steps as the longest run it searches holds bits (a
trip count from the data; an element gather costs ~30 ns a lane and step
on the v5e, a scatter ~9 ns a lane), and the loop over the further slots runs
``n_slots - 1`` times, so neither the slots' sizes nor their number below
the padded ``len(term_ids)`` is part of the program's shape.  int32
throughout: the TPU emulates int64.

``gather_term_positions`` and the (doc, position) keys are
``ops/span.py``'s, which gathers every clause whole.
"""

from __future__ import annotations

import opensearch_tpu.common.jaxenv  # noqa: F401

import jax.numpy as jnp
from jax import lax

POS_BASE = 1 << 22  # > any token position (position_increment_gap padded)
KEY_PAD = jnp.iinfo(jnp.int64).max
_I32 = jnp.int32


def gather_term_positions(offsets, pos_offsets, positions, doc_ids, t_id,
                          active, *, budget: int, pad_doc: int):
    """All (doc, position) occurrences of one term, as fixed-size arrays.

    Returns (docs[B], pos[B], valid[B]).  ``budget`` must cover the term's
    total position count in this segment (host-known, bucketed pow2).
    """
    e0 = offsets[t_id]
    e1 = jnp.where(active, offsets[t_id + 1], e0)
    p0 = pos_offsets[e0]
    p1 = pos_offsets[e1]
    i = jnp.arange(budget, dtype=jnp.int32)
    valid = i < (p1 - p0)
    pidx = jnp.where(valid, p0 + i, 0)
    pos = positions[pidx]
    # owning posting entry: pos_offsets[e] <= pidx < pos_offsets[e+1]
    entry = jnp.searchsorted(pos_offsets, pidx, side="right").astype(jnp.int32) - 1
    entry = jnp.clip(entry, 0, doc_ids.shape[0] - 1)
    docs = jnp.where(valid, doc_ids[entry], pad_doc)
    return docs, pos, valid


def _bits(n):
    """Steps a binary search over a run of ``n`` entries needs:
    ``n.bit_length()``, 0 for an empty run."""
    return _I32(32) - lax.clz(jnp.maximum(n, 0).astype(_I32))


def _lower_bound(column, lo, hi, target, steps):
    """Per lane the first index in ``[lo, hi)`` whose ``column`` entry is
    ``>= target`` (``hi`` where none is): ``steps`` halvings, one element
    gather a lane each.  ``column`` ascends inside every lane's range."""
    last = _I32(column.shape[0] - 1)

    def halve(_, bounds):
        lo, hi = bounds
        mid = lo + ((hi - lo) >> 1)
        below = column[jnp.minimum(mid, last)] < target
        go = lo < hi
        return (jnp.where(go & below, mid + 1, lo),
                jnp.where(go & ~below, mid, hi))

    return lax.fori_loop(_I32(0), steps, halve, (lo, hi))[0]


def _run_window(column, lo, hi, size: int):
    """``size`` lanes of ``column`` that hold ``[lo, hi)`` (``hi - lo <=
    size <= len(column)``): (values, which lanes are of the run, their
    indices)."""
    first = jnp.clip(lo, 0, column.shape[0] - size).astype(_I32)
    at = first + jnp.arange(size, dtype=_I32)
    return (lax.dynamic_slice(column, (first,), (size,)),
            (at >= lo) & (at < hi), at)


def doc_table(n_pad: int, win: int) -> bool:
    """Whether a probe finds a lane's posting by a table over the
    segment's docs (one scatter of ``n_pad`` lanes, one gather a lane)
    and not by a binary search a lane: where the scatter is dearer than
    four lanes' searches it is not worth its fixed cost."""
    return n_pad <= 4 * win


def phrase_freqs(postings, term_ids, rel, n_slots, *, budget: int,
                 n_pad: int):
    """Per-doc exact-phrase frequency, float32 ``[n_pad]``.

    ``postings`` is the staged dict (offsets/pos_offsets/positions/doc_ids).
    ``term_ids`` int32 ``[s_pad]``: slot 0 is the anchor, the slot with the
    fewest positions here; slots ``1 .. n_slots - 1`` are probed; the rest
    is padding.  ``rel[j]`` is slot j's analyzer position less the
    anchor's (stop-word gaps honoured, negative before the anchor).
    ``n_slots`` 0 says the segment lacks a term: nothing matches.
    ``budget`` (static) covers the anchor's position count.
    """
    offsets, pos_offsets = postings["offsets"], postings["pos_offsets"]
    positions, doc_ids = postings["positions"], postings["doc_ids"]
    last_entry = _I32(doc_ids.shape[0] - 1)
    last_off = _I32(pos_offsets.shape[0] - 1)

    e0 = offsets[term_ids[0]]
    e1 = jnp.where(n_slots > 0, offsets[term_ids[0] + 1], e0)
    p0, p1 = pos_offsets[e0], pos_offsets[e1]
    # the anchor's positions: one window of the column, wherever the run
    # lies in it (a window at the column's end starts before the run)
    win = min(budget, positions.shape[0])
    pos, ok, at = _run_window(positions, p0, p1, win)
    # a lane's doc: every posting of the anchor holds a position, so it
    # has at most ``win``; each adds its doc's step where its run starts
    held, mine, entry = _run_window(
        doc_ids, e0, e1, min(win, n_pad, doc_ids.shape[0]))
    step = held - jnp.where(entry > e0, jnp.roll(held, 1), 0)
    begins = lax.dynamic_slice(pos_offsets, (entry[0],), (len(entry),))
    doc = jnp.cumsum(jnp.zeros(win, _I32).at[
        jnp.where(mine, begins - at[0], win)].add(
            jnp.where(mine, step, 0), mode="drop"), dtype=_I32)

    def probe(j, ok):
        f0 = offsets[term_ids[j]]
        f1 = offsets[term_ids[j] + 1]
        if doc_table(n_pad, win):
            docs_j, mine_j, entry_j = _run_window(
                doc_ids, f0, f1, min(n_pad, doc_ids.shape[0]))
            e = jnp.full(n_pad, -1, _I32).at[
                jnp.where(mine_j, docs_j, n_pad)].set(entry_j, mode="drop")[
                    jnp.minimum(doc, n_pad - 1)]
            ok = ok & (e >= 0)
            at_e = jnp.maximum(e, 0)
        else:
            e = _lower_bound(doc_ids, jnp.full(win, f0, _I32),
                             jnp.full(win, f1, _I32), doc, _bits(f1 - f0))
            at_e = jnp.minimum(e, last_entry)
            ok = ok & (e < f1) & (doc_ids[at_e] == doc)
        q0 = pos_offsets[jnp.minimum(at_e, last_off)]
        q1 = pos_offsets[jnp.minimum(at_e + 1, last_off)]
        want = pos + rel[j]
        longest = jnp.max(jnp.where(ok, q1 - q0, 0))
        hit = _lower_bound(positions, q0, q1, want, _bits(longest))
        found = positions[jnp.minimum(hit, _I32(positions.shape[0] - 1))]
        return ok & (hit < q1) & (found == want)

    ok = lax.fori_loop(_I32(1), jnp.maximum(n_slots, 1).astype(_I32),
                       probe, ok)
    docs = jnp.where(ok, doc, n_pad - 1)
    return jnp.zeros(n_pad, jnp.float32).at[docs].add(ok.astype(jnp.float32))
