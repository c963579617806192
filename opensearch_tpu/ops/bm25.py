"""BM25 scoring as batched XLA programs.

The reference's hot loop is doc-at-a-time WAND/MaxScore inside Lucene's
``Weight.bulkScorer`` (ref server/src/main/java/org/opensearch/search/
internal/ContextIndexSearcher.java:318).  On TPU the same work is a
data-parallel program over the whole segment:

    CSR gather of the query terms' postings  ->  BM25 per posting
    ->  one score a doc  ->  exact top-k
        (``ops/topk.py``: block maxima, then the k winning blocks)

There are two ways from the postings to one score a doc:

- dense (``impact_scores`` and its siblings): scatter-add every lane of
  the ``budget`` into a ``[n_pad]`` accumulator.  The TPU adds element
  by element, about 9 ns a lane of ``budget`` whether it carries a
  posting or lands on the dead slot, which was two thirds of a long
  bag's device time.  Every caller that needs the whole vector takes it:
  a bag under a ``bool``, aggregations and sorts (``run_full``), a
  segment with deleted docs and its live mask.
- sorted (``impact_topk_sorted``): where only the top-k is wanted, sort
  the ``budget`` gathered lanes by doc id (about 1.1 ns a lane), add up
  each doc's run of equal ids in slot order, and take the top-k over
  the runs' last lanes.  No accumulator, no scatter, and every float32
  sum keeps the scatter's bits.  ``sorted_bag`` says from the static
  shape where it is taken.

The gather (``gather_postings``) lays each term's postings run, one
contiguous stretch of the staged columns, into a flat ``budget``-sized
space: as contiguous slices up to the threshold of ``slice_lowering``,
as an element gather beyond it.  The TPU gathers element by element at
30-50 ns a lane, which was three quarters of a term-bag program's time;
a slice copy streams.  It moves each run in chunks of ``copy_chunk``
lanes and loops as often as the runs have chunks, so it costs the
postings it places plus a few microseconds a chunk to start: an empty
or inactive slot costs nothing, and a long bag over a large bucket no
longer pays ``budget`` lanes a slot.

This is the BM25S formulation (see PAPERS.md): the tf-side factor
``tf / (tf + k1*(1-b + b*dl/avgdl))`` depends only on segment data plus
the shard-level ``avgdl``, so it is eagerly precomputed ONCE per
(field, avgdl) into a per-posting ``impacts`` column
(``compute_impacts``, staged by ``DeviceSegment.impacts``).  Query-time
scoring then degenerates to gather + weighted scatter-add — no per-query
norm arithmetic, no ``doc_lens`` gather.  Query-time global ``idf``
stays a multiplier so scores remain exactly consistent across segments
(Lucene computes collection-wide stats in IndexSearcher, not per
segment).

All functions here are pure jnp and shape-static; the search executor
composes and ``jit``s them with bucketed shapes.
"""

from __future__ import annotations

import math
from functools import partial

import opensearch_tpu.common.jaxenv  # noqa: F401

import jax
import jax.numpy as jnp
from jax import lax

from opensearch_tpu.ops import topk as topk_ops

K1_DEFAULT = 1.2
B_DEFAULT = 0.75

# Nothing reads this.  tests/benchmarks_harness/conftest.py (a benchmark
# file) still sets it; it goes with that line (ROADMAP D12).
HOST_SCORING = False


def idf(df: int, n_docs: int) -> float:
    """Lucene BM25Similarity idf: ln(1 + (N - df + 0.5) / (df + 0.5))."""
    return math.log(1.0 + (n_docs - df + 0.5) / (df + 0.5))


@partial(jax.jit, static_argnames=("k1", "b"))
def compute_impacts(tfs, doc_ids, doc_lens, avgdl, *,
                    k1: float = K1_DEFAULT, b: float = B_DEFAULT):
    """Per-posting BM25 impact ``tf / (tf + k1*(1-b + b*dl/avgdl))``.

    Everything here is segment data except ``avgdl`` (shard-level, a
    traced scalar so a stats change never recompiles).  Padded posting
    slots carry tf=0 and decode to impact 0.  float32 end to end — the
    score-parity tests pin this expression bitwise, so keep the
    operation order in sync with the numpy reference in
    tests/test_impacts.py."""
    dl = doc_lens[doc_ids]
    norm = k1 * (1.0 - b + b * dl / avgdl)
    return tfs / (tfs + norm)


# Which lowering ``gather_postings`` takes, from its static shape alone.
# Measured on one v5e over an 8,388,608-slot column (PR 28) against the
# slice copy as it then was, one window of ``budget`` lanes a slot: a
# slot cost about 4 us to start plus 0.03 ns a lane, which is 131,072
# lanes' worth; a lane of the element gather costs 30-50 ns, some 1,600
# copied lanes.  So that copy's ``t_pad`` windows won while
#   t_pad * (_SLOT_START_LANES + budget) <= _ELEMENT_LANE * budget:
# up to 32 slots at a budget of 4,096, 512 at 65,536, 1,024 from 262,144.
# Since PR 38 the copy moves chunks of ``copy_chunk`` lanes, as many as
# the runs have, so a slot costs its run and not ``budget`` lanes: the
# break-even lies further towards the slices at a large ``t_pad`` than
# this rule says.  No cell runs ``t_pad`` above 64; the threshold wants
# ``tools/gather_bench.py`` again from 128 up (ROADMAP B3).
_SLOT_START_LANES = 131072
_ELEMENT_LANE = 1600


def slice_lowering(t_pad: int, budget: int) -> bool:
    """True where ``gather_postings`` copies each term's run as one
    contiguous slice, False where it gathers posting by posting.  The
    kernel and the ``device.slice_gather_programs`` counter both ask
    here, so they cannot disagree."""
    return t_pad * (_SLOT_START_LANES + budget) <= _ELEMENT_LANE * budget


# The fewest lanes a chunk of the slice copy moves, where the budget has
# them.  Measured on one v5e (PR 38, ``tools/gather_bench.py``, PERF.md
# section 3): a chunk costs about 3.9 us to start plus 0.08 ns a lane
# (read, select under the mask, write; two columns), so a start is worth
# some 49,000 lanes.  us a gather of a bag as a SPLADE query brings it
# (three quarters of the slots active, a quarter to a half of the bucket
# filled), in chunks of 8,192 / 16,384 / 32,768 / 65,536 / 131,072:
#   (t_pad 64, budget 1,048,576)   771 / 672 / 641 / 674 / 743
#   (32, 1,048,576)                609 / 424 / 362 / 358 / 389
#   (32, 262,144)                  212 / 186 / 190 / 211 / 245
#   (8, 262,144)                   123 /  83 /  68 /  69 /  78
#   (8, 65,536)                     51 /  49 /  50 /  55
# against 5,508, 2,771, 389, 112 and 62 for one window of ``budget``
# lanes a slot, the copy as it was.  (One slot at 1,048,576 lanes: 114
# to 118 as one window, 62 to 77 in chunks of 131,072; ROADMAP B3.)
_CHUNK_FLOOR = 32768


def copy_chunk(t_pad: int, budget: int) -> int:
    """Lanes a chunk of the slice copy moves (``_copy_runs``), from the
    static shape alone: the budget's share of a slot rounded down to a
    power of two, so a full budget is at most ``2 * t_pad`` chunks at
    any shape and a single slot (a ``term`` filter) keeps one window of
    the whole budget; never under ``_CHUNK_FLOOR`` lanes, below which a
    chunk is all start cost, unless the budget itself is."""
    share = max(budget // t_pad, 1)
    return min(budget, max(_CHUNK_FLOOR, 1 << (share.bit_length() - 1)))


def gather_postings(offsets, doc_ids, tfs, term_ids, term_active, *,
                    budget: int, pad_doc: int):
    """Flatten the postings of up to T terms into fixed-size arrays.

    The CSR rows selected by ``term_ids`` are laid end-to-end into a
    ``budget``-sized flat space — fully on-device, shape-static.  A row
    is one contiguous run ``offsets[t] : offsets[t+1]`` of the columns,
    so up to the threshold of ``slice_lowering`` each run is copied as
    contiguous slices (streaming vector work: chunks of ``copy_chunk``
    lanes, as many as the runs have, nothing for an inactive slot);
    beyond it every output lane computes its own address and the columns
    are gathered element by element (a serial gather on the TPU: 30-50
    ns a lane).  Both give the same lanes.

    Contract: the caller must choose ``budget >= sum(df[term_ids])``
    (the executor computes this from host-side df stats and rounds up to a
    power-of-two bucket); entries beyond ``budget`` would be silently
    dropped otherwise.

    Returns (docs[B], tfs[B], slot[B], valid[B]): ``slot`` is the index
    into ``term_ids`` that produced each entry; lanes past the total hold
    ``pad_doc`` / ``0.0``.
    """
    t_pad = term_ids.shape[0]
    starts = offsets[term_ids]
    lens = jnp.where(term_active, offsets[term_ids + 1] - starts, 0)
    cum = jnp.cumsum(lens)
    i = jnp.arange(budget, dtype=jnp.int32)
    valid = i < cum[-1]
    if slice_lowering(t_pad, budget):
        # searchsorted(side="right") as t_pad compares a lane: streaming
        # work, where the binary search's table lookups are element
        # gathers again
        slot = jnp.sum(cum[None, :] <= i[:, None], axis=1, dtype=jnp.int32)
        slot = jnp.minimum(slot, t_pad - 1)
        d, tf = _copy_runs(doc_ids, tfs, starts, lens, cum - lens,
                           budget=budget, pad_doc=pad_doc,
                           chunk=copy_chunk(t_pad, budget))
        return d, tf, slot, valid
    slot = jnp.searchsorted(cum, i, side="right").astype(jnp.int32)
    slot = jnp.minimum(slot, t_pad - 1)
    prev = jnp.where(slot > 0, cum[slot - 1], 0)
    idx = jnp.where(valid, starts[slot] + i - prev, 0)
    d = jnp.where(valid, doc_ids[idx], pad_doc)
    tf = jnp.where(valid, tfs[idx], 0.0)
    return d, tf, slot, valid


def _copy_runs(doc_ids, tfs, starts, lens, prevs, *, budget: int,
               pad_doc: int, chunk: int):
    """The slice lowering of ``gather_postings``: run by run, chunk by
    chunk, read one ``win``-lane window of each column (``win`` is
    ``chunk``, or the column where that is shorter) and write the lanes
    of it that the run still has, and no others, at their place
    ``prevs[t] + c * win`` in the flat space.  A slot has
    ``ceil(lens[t] / win)`` chunks, none when it is inactive or empty,
    and the loop runs once a chunk: its trip count comes from the data,
    its shapes from ``(t_pad, budget)`` alone.

    ``dynamic_slice`` clamps a window's start so that the window fits, so
    a chunk near the column's end begins ``shift`` lanes into its
    window; the write goes ``shift`` lanes earlier to undo that, into a
    buffer with ``win`` spare lanes on either side so that no write is
    clamped in turn.  The write is read-modify-write under the chunk's
    lane mask: what lies beyond a term's own run never reaches the flat
    space, and the order of the chunks does not matter."""
    n_post = doc_ids.shape[0]
    t_pad = starts.shape[0]
    win = min(chunk, n_post)
    lane = jnp.arange(win, dtype=jnp.int32)
    # a caller that broke the contract loses the lanes past ``budget``,
    # as the element gather drops them
    prevs = jnp.minimum(prevs, budget)
    lens = jnp.clip(lens, 0, budget - prevs)
    n_chunks = (lens + (win - 1)) // win
    ends = jnp.cumsum(n_chunks)
    # trip i -> its slot, by t_pad compares a trip, and what that slot
    # has done before it; a table of the most trips the shape allows, so
    # the loop reads three scalars and searches nothing
    trip = jnp.arange(budget // win + t_pad, dtype=jnp.int32)
    t = jnp.minimum(
        jnp.sum(ends[None, :] <= trip[:, None], axis=1, dtype=jnp.int32),
        t_pad - 1)
    done = (trip - (ends[t] - n_chunks[t])) * win
    src, dst, left = starts[t] + done, prevs[t] + done, lens[t] - done

    def copy_one(i, bufs):
        start = jnp.clip(src[i], 0, n_post - win)
        shift = src[i] - start
        keep = (lane >= shift) & (lane < shift + left[i])
        at = dst[i] - shift + win
        return tuple(
            lax.dynamic_update_slice(
                buf, jnp.where(keep,
                               lax.dynamic_slice(col, (start,), (win,)),
                               lax.dynamic_slice(buf, (at,), (win,))),
                (at,))
            for col, buf in zip((doc_ids, tfs), bufs))

    d, tf = lax.fori_loop(
        0, ends[-1], copy_one,
        (jnp.full(budget + 2 * win, pad_doc, doc_ids.dtype),
         jnp.zeros(budget + 2 * win, tfs.dtype)))
    return d[win:win + budget], tf[win:win + budget]


def gather_postings_packed(offsets, packed, base, term_ids, term_active,
                           *, width: int, budget: int, pad_doc: int):
    """``gather_postings`` over BIT-PACKED doc ids (index/codec.py):
    postings store ``doc - base[term]`` deltas at a fixed ``width`` bits,
    and each lane decodes its delta with two aligned uint32 reads — no
    prefix-sum chain, so random access (and therefore the shape-static
    CSR gather) is preserved.

    Returns (docs[B], idx[B], slot[B], valid[B]): ``idx`` is the flat
    posting index (for the quantized-impact gather) and ``slot`` the
    query-term slot, exactly like ``gather_postings``.
    """
    starts = offsets[term_ids]
    lens = jnp.where(term_active, offsets[term_ids + 1] - starts, 0)
    cum = jnp.cumsum(lens)
    total = cum[-1]
    i = jnp.arange(budget, dtype=jnp.int32)
    slot = jnp.searchsorted(cum, i, side="right").astype(jnp.int32)
    slot = jnp.minimum(slot, term_ids.shape[0] - 1)
    prev = jnp.where(slot > 0, cum[slot - 1], 0)
    valid = i < total
    idx = jnp.where(valid, starts[slot] + i - prev, 0)
    # bitpos = idx * width decomposed as idx = 32a + b so the word/bit
    # math never overflows int32 at 10M-doc posting counts
    a, b = idx >> 5, idx & 31
    bit = b * width
    w = a * width + (bit >> 5)
    off = (bit & 31).astype(jnp.uint32)
    pair = (packed[w].astype(jnp.uint64)
            | (packed[w + 1].astype(jnp.uint64) << jnp.uint64(32)))
    mask = jnp.uint64((1 << width) - 1)
    delta = ((pair >> off.astype(jnp.uint64)) & mask).astype(jnp.int32)
    tid = term_ids[slot]
    d = jnp.where(valid, base[tid] + delta, pad_doc)
    return d, idx, slot, valid


def bm25_scores(offsets, doc_ids, tfs, doc_lens, term_ids, term_active,
                idfs, weights, avgdl, *, n_pad: int, budget: int,
                k1: float = K1_DEFAULT, b: float = B_DEFAULT):
    """Dense per-doc BM25 scores for a bag of weighted terms.

    ``idfs``/``weights`` are per query term (weights carry boosts and
    should-clause accumulation).  Returns float32 [n_pad]; score > 0 iff
    the doc matched at least one term.
    """
    d, tf, slot, valid = gather_postings(
        offsets, doc_ids, tfs, term_ids, term_active,
        budget=budget, pad_doc=n_pad - 1)
    dl = doc_lens[d]
    norm = k1 * (1.0 - b + b * dl / avgdl)
    contrib = idfs[slot] * weights[slot] * tf / (tf + norm)
    contrib = jnp.where(valid, contrib, 0.0)
    return jnp.zeros(n_pad, jnp.float32).at[d].add(contrib)


def bm25_score_count(offsets, doc_ids, tfs, doc_lens, term_ids, term_active,
                     idfs, weights, avgdl, *, n_pad: int, budget: int,
                     scored: bool, k1: float = K1_DEFAULT,
                     b: float = B_DEFAULT):
    """One gather, two scatters: dense per-doc BM25 scores AND per-doc count
    of matched query-term slots (for AND / minimum_should_match semantics).
    With ``scored=False`` the score scatter is skipped (filter context)."""
    d, tf, slot, valid = gather_postings(
        offsets, doc_ids, tfs, term_ids, term_active,
        budget=budget, pad_doc=n_pad - 1)
    count = jnp.zeros(n_pad, jnp.int32).at[d].add(valid.astype(jnp.int32))
    if not scored:
        return jnp.zeros(n_pad, jnp.float32), count
    dl = doc_lens[d]
    norm = k1 * (1.0 - b + b * dl / avgdl)
    contrib = idfs[slot] * weights[slot] * tf / (tf + norm)
    scores = jnp.zeros(n_pad, jnp.float32).at[d].add(
        jnp.where(valid, contrib, 0.0))
    return scores, count


def impact_scores(offsets, doc_ids, impacts, term_ids, term_active,
                  idfs, weights, *, n_pad: int, budget: int):
    """Dense per-doc BM25 scores from PRECOMPUTED impacts: pure gather +
    weighted scatter-add, no norm recomputation.  ``impacts`` is the
    staged per-posting column (``compute_impacts``), indexed exactly
    like ``tfs``.  Fast path for required<=1 bags with positive
    weights: score > 0 iff the doc matched, so no count scatter runs."""
    d, imp, slot, valid = gather_postings(
        offsets, doc_ids, impacts, term_ids, term_active,
        budget=budget, pad_doc=n_pad - 1)
    base = idfs[slot] * imp
    contrib = jnp.where(valid, weights[slot] * base, 0.0)
    return jnp.zeros(n_pad, jnp.float32).at[d].add(contrib)


def impact_score_count(offsets, doc_ids, impacts, term_ids, term_active,
                       idfs, weights, *, n_pad: int, budget: int,
                       scored: bool):
    """Impact-path variant of ``bm25_score_count``: one gather, score
    scatter from precomputed impacts + matched-slot count scatter (AND /
    minimum_should_match semantics).  With ``scored=False`` only the
    count scatter runs (filter context)."""
    d, imp, slot, valid = gather_postings(
        offsets, doc_ids, impacts, term_ids, term_active,
        budget=budget, pad_doc=n_pad - 1)
    count = jnp.zeros(n_pad, jnp.int32).at[d].add(valid.astype(jnp.int32))
    if not scored:
        return jnp.zeros(n_pad, jnp.float32), count
    base = idfs[slot] * imp
    contrib = jnp.where(valid, weights[slot] * base, 0.0)
    scores = jnp.zeros(n_pad, jnp.float32).at[d].add(contrib)
    return scores, count


_INT32_MAX = 2 ** 31 - 1


# Which way a scored bag's top-k goes, from its static shape alone.
# Measured on one v5e (PR 36, ``tools/bag_bench.py``, table in PERF.md
# section 3), in us a bag from the gathered lanes to the top 10, dense
# scatter-add / sort + fold, a quarter of the lanes carrying a posting
# and all of them (the same within 6%):
#   (t_pad 8, budget 4,096)           111 /    74
#   (8, 16,384)                       227 /    87
#   (8, 65,536)                       657 /   126
#   (8 to 64, 262,144)              2,440 /   360
#   (8 to 64, 1,048,576)            9,380 / 1,180
#   (32 and 64, 4,194,304)         37,060 / 5,060
# The sort wins at every bucket ``pad_bucket`` gives, so the rule has no
# floor: it only says where the sorted lanes cannot stand for the
# segment.  (The scatter declared ``unique_indices`` and
# ``indices_are_sorted`` slot by slot, ROADMAP B3 (i), still adds lane
# by lane: 7.4 to 9.4 ms at t_pad 8 and 1,048,576 lanes, 115 ms at 64.)
def sorted_bag(t_pad: int, budget: int, n_pad: int, k: int) -> bool:
    """True where a scored bag's top-k goes by ``impact_topk_sorted``
    (a sort of the ``budget`` lanes by doc id) and not by the dense
    accumulator.  The kernel's entry and the ``device.
    sorted_bag_programs`` counter both ask here, so they cannot
    disagree.  No where the top-k would want more lanes than the bag
    has, and where ``doc * t_pad + slot`` would not fit the int32 sort
    key below the dead lanes' ``INT32_MAX``."""
    return k <= budget and n_pad * t_pad < 2 ** 31


def impact_topk_sorted(offsets, doc_ids, impacts, term_ids, term_active,
                       idfs, weights, required, min_score, *, n_pad: int,
                       budget: int, k: int, fast: bool):
    """(top_scores[k], top_local_ids[k], total_matched, max_score) of a
    scored bag over a segment WITHOUT a deleted doc, bit for bit what
    ``impact_scores`` / ``impact_score_count``, the match rule, the
    ``min_score`` cut and ``topk_ops.topk_and_max`` over ``[n_pad]``
    give, without the ``[n_pad]`` accumulator and its scatter-add.

    The gathered lanes are sorted by ``doc * t_pad + slot`` (unique among
    valid lanes, so the sort needs no stability; dead lanes carry
    ``INT32_MAX`` and sort last): the lanes of one doc lie side by side
    in slot order, at most one a slot.  ``p`` is a lane's place in its
    run.  Pass ``j`` of the fold gives every lane at place ``j`` its left
    neighbour's sum plus its own contribution, so a run's last lane ends
    with ``((c0 + c1) + c2) + ...``: the order in which the scatter adds
    and ``TermBagPlan.host_topk`` accumulates, hence the same float32.
    The loop stops at the longest run, never past ``t_pad - 1`` passes.
    A run's length is the doc's count of matched slots.  The key of the
    top-k holds a doc's score at its run's last lane and ``-inf``
    elsewhere; lanes are in doc order, so the lower lane of a tie is the
    lower doc id, as over ``[n_pad]``.

    A posting's doc id is below ``n_docs``, so with every doc live the
    live mask is true on every valid lane and is not read; a segment
    with a deleted doc keeps the dense path (its mask would cost an
    element gather a lane)."""
    t_pad = term_ids.shape[0]
    assert sorted_bag(t_pad, budget, n_pad, k)
    d, imp, slot, valid = gather_postings(
        offsets, doc_ids, impacts, term_ids, term_active,
        budget=budget, pad_doc=n_pad - 1)
    base = idfs[slot] * imp
    contrib = jnp.where(valid, weights[slot] * base, 0.0)
    return sorted_lanes_topk(d, contrib, slot, valid, required, min_score,
                             t_pad=t_pad, n_pad=n_pad, k=k, fast=fast)


def sorted_lanes_topk(d, contrib, slot, valid, required, min_score, *,
                      t_pad: int, n_pad: int, k: int, fast: bool):
    """``impact_topk_sorted`` from the gathered lanes on: the sort by
    ``(doc, slot)``, the fold of each doc's run in slot order, the match
    rule at a run's last lane and the top-k over the lanes."""
    assert t_pad & (t_pad - 1) == 0
    shift = t_pad.bit_length() - 1
    budget = d.shape[0]
    key = jnp.where(valid, (d.astype(jnp.int32) << shift) | slot, _INT32_MAX)
    key, contrib = lax.sort((key, contrib), num_keys=1, is_stable=False)
    valid = key != _INT32_MAX
    doc = key >> shift
    i = jnp.arange(budget, dtype=jnp.int32)
    edge = doc[1:] != doc[:-1]
    first = jnp.concatenate([jnp.ones(1, bool), edge])
    last = jnp.concatenate([edge, jnp.ones(1, bool)])
    # a run's first lane lies inside the t_pad lanes that end at any of
    # its lanes: a running maximum over that window, by doubling (a
    # cummax of the whole array compiles for 27 s at 1,048,576 lanes)
    start, step = jnp.where(first, i, 0), 1
    while step < min(t_pad, budget):
        start = jnp.maximum(start, jnp.concatenate(
            [jnp.zeros(step, jnp.int32), start[:-step]]))
        step *= 2
    p = i - start

    def fold(state):
        j, s = state
        left = jnp.concatenate([s[:1], s[:-1]])
        return j + 1, jnp.where(p == j, left + contrib, s)

    longest = jnp.max(jnp.where(valid, p, 0))
    _, s = lax.while_loop(lambda state: state[0] <= longest, fold,
                          (jnp.int32(1), contrib))
    matched = (valid & last & (s > 0.0 if fast else p + 1 >= required)
               & (s >= min_score))
    vals, idx, mx = topk_ops.topk_and_max(
        jnp.where(matched, s, -jnp.inf), k)
    # an entry at -inf may name a dead lane: keep its id inside the segment
    return vals, jnp.minimum(doc[idx], n_pad - 1), matched.sum(), mx


def match_count(offsets, doc_ids, tfs, term_ids, term_active, *,
                n_pad: int, budget: int):
    """Per-doc count of DISTINCT matched query terms (for conjunctions and
    minimum_should_match).  tf >= 1 per posting entry, so counting entries
    per (term, doc) pair counts terms."""
    d, _tf, _slot, valid = gather_postings(
        offsets, doc_ids, tfs, term_ids, term_active,
        budget=budget, pad_doc=n_pad - 1)
    return jnp.zeros(n_pad, jnp.int32).at[d].add(valid.astype(jnp.int32))
