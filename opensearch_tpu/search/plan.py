"""Query compilation: Query tree -> (plan, bindings) -> jit'd per-segment
XLA program.

Analog of the reference's two-step ``QueryBuilder.rewrite`` +
``toQuery(QueryShardContext)`` (index/query/QueryShardContext.java:95) and
the Lucene ``Weight``/``Scorer`` machinery it produces.  The TPU twist:

- a *plan node* is a frozen, hashable dataclass holding only static
  STRUCTURE (field names, clause layout, scoring flags).  It is a jit
  static argument, so each distinct query SHAPE compiles once; all queries
  of that shape (any terms, bounds, boosts) reuse the compiled program;
- per-query compile-time data (term strings, idfs, bounds, boosts) lives
  in a parallel *bindings tree* mirroring the plan tree, consumed host-side
  by ``prepare`` which emits the dynamic ``ins`` pytree per segment;
- per-segment static sizes (gather budgets, padded term counts) travel as
  the ``dims`` tuple pytree, also static (bucketed pow2 so segments of
  similar size share programs);
- every node evaluates to ``(scores f32 [n_pad], matched bool [n_pad])``;
  scores are zero wherever unmatched, so boolean composition is masked
  arithmetic, not iterator intersection (Lucene ConjunctionDISI analog).
"""

from __future__ import annotations

import bisect
import fnmatch
import math
import re
from dataclasses import dataclass
from functools import partial
from typing import Optional

import numpy as np

import opensearch_tpu.common.jaxenv  # noqa: F401
import jax
import jax.numpy as jnp
from jax import lax

from opensearch_tpu.common.device_ledger import device_ledger
from opensearch_tpu.index.segment import (LONG_MISSING_MAX, pad_bucket,
                                           pad_pow2)
from opensearch_tpu.ops import bm25 as bm25_ops
from opensearch_tpu.ops import filters as filter_ops
from opensearch_tpu.ops import phrase as phrase_ops
from opensearch_tpu.ops import quantized as quantized_ops
from opensearch_tpu.ops import span as span_ops
from opensearch_tpu.ops import topk as topk_ops

_I32 = np.int32
_F32 = np.float32


def _stage_input(host_array):
    """One per-query input onto the device, counted (``_nodes/stats``
    ``device.transfers.input``).  The prepared-bindings cache owns what
    comes back."""
    return device_ledger().stage_input(host_array)


def _scalar(x, dtype):
    return _stage_input(np.asarray(x, dtype=dtype))


def _pad_np(arr, size, fill, dtype):
    out = np.full(size, fill, dtype=dtype)
    a = np.asarray(arr, dtype=dtype)
    out[: len(a)] = a
    return _stage_input(out)


def _pack_term_inputs(tids, active, idfs, weights, required) -> np.ndarray:
    """A term bag's per-query inputs as ONE host ``int32[4 * t_pad +
    1]``: ``[tids | active as 0/1 | idfs' bits | weights' bits |
    required]``, so that they cross to the device in one copy a segment
    program (``_pack_topk``'s mirror on the way out).  ``idfs`` and
    ``weights`` may be shorter than ``t_pad`` (zero bits pad them, as
    0.0 did) or absent (the filter lowering reads neither)."""
    t_pad = len(tids)
    out = np.zeros(4 * t_pad + 1, dtype=_I32)
    out[:t_pad] = tids
    out[t_pad:2 * t_pad] = active
    for at, vals in ((2 * t_pad, idfs), (3 * t_pad, weights)):
        if vals is not None:
            bits = np.asarray(vals, dtype=_F32).view(_I32)
            out[at:at + len(bits)] = bits
    out[4 * t_pad] = required
    return out


def _unpack_term_inputs(packed, t_pad: int):
    """Traced side of ``_pack_term_inputs``: (tids i32[t_pad], active
    bool[t_pad], idfs f32[t_pad], weights f32[t_pad], required i32) by
    static slices; the bit cast gives back every float32 bit for bit."""
    def f32(at):
        return lax.bitcast_convert_type(packed[at:at + t_pad], jnp.float32)
    return (packed[:t_pad], packed[t_pad:2 * t_pad] != 0,
            f32(2 * t_pad), f32(3 * t_pad), packed[4 * t_pad])


# ---------------------------------------------------------------------------
# Plan nodes.  All frozen + hashable: static query structure only.
# Each implements:
#   arrays() -> frozenset[(group, field)]         device arrays needed
#   prepare(bind, seg, dseg, ctx) -> (dims, ins)  host-side, per segment
#   eval(A, dims, ins) -> (scores, matched)       traced, pure jnp
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class Plan:
    def arrays(self) -> frozenset:
        return frozenset()

    def can_match(self, bind, seg) -> bool:
        """Host-side pre-filter: False only when NO doc in this segment
        can match (the CanMatchPreFilterSearchPhase analog, ref
        action/search/CanMatchPreFilterSearchPhase.java:73) — segments
        that can't match never dispatch a device program.  Must stay
        conservative: returning True is always safe."""
        return True

    def skip_arrays(self, dims) -> frozenset:
        """Subset of ``arrays()`` this plan does NOT need fully staged
        for the dims ``prepare`` returned — the executor passes it to
        ``build_arrays`` so a quantized lowering (which carries its
        compressed arrays through ``ins``) doesn't force the f32
        posting columns onto the device.  Composites keep the default
        (empty): only lowerings that opt in skip anything."""
        return frozenset()

    def slice_gathers(self, dims) -> bool:
        """True when the program ``eval`` traces for these dims copies
        postings runs as contiguous slices somewhere — decided by
        ``bm25_ops.slice_lowering``, which ``gather_postings`` itself
        asks, from the same ``t_pad`` and ``budget``.  The executor
        counts such programs (``_nodes/stats`` ``device.
        slice_gather_programs``).  Composites ask their children."""
        return False

    def sorted_topk(self, dims, n_pad: int, k: int) -> bool:
        """True when ``run_topk`` can give this plan's top-k by
        ``eval_topk``, with no dense ``[n_pad]`` score vector: a scored
        term bag at the root, where ``bm25_ops.sorted_bag`` says so.
        That path reads no live mask, so a caller (``ShardSearcher.
        _topk``, the mesh) passes ``run_topk(sorted_bag=True)`` only for
        a segment whose snapshot has no deleted doc (``ShardContext.
        all_live``), and counts it (``_nodes/stats`` ``device.
        sorted_bag_programs``); a segment with a deletion keeps the
        dense path."""
        return False

    def max_score_bound(self, bind, seg) -> float:
        """Safe UPPER bound on any single doc's score in this segment —
        the MaxScore/BMW pruning surface over the per-term block-max
        impact metadata (``Segment.max_impacts``).  The executor skips
        segments whose bound cannot reach the min_score / running k-th
        score.  Returning ``math.inf`` (the default) is always safe;
        finite bounds carry a small multiplicative margin so float32
        kernel rounding can never make a real score exceed them."""
        return math.inf

    def describe(self, bind) -> str:
        """Compact structural description for the Profile API's query
        section (``Query.toString()`` analog): the plan's static fields
        plus bind cardinalities — never document data.  The profiler
        truncates to 200 chars, so nesting may clip."""
        import dataclasses
        parts = [f"{f.name}={getattr(self, f.name)!r}"
                 for f in dataclasses.fields(self)]
        if isinstance(bind, dict):
            for key in ("terms", "values"):
                v = bind.get(key)
                if isinstance(v, (list, tuple)) and v:
                    shown = ",".join(str(x) for x in v[:8])
                    more = ",…" if len(v) > 8 else ""
                    parts.append(f"{key}=[{shown}{more}]")
            for key in ("queries", "children"):
                v = bind.get(key)
                if isinstance(v, (list, tuple)):
                    parts.append(f"{key}#{len(v)}")
        return f"{type(self).__name__}({', '.join(parts)})"


# float32 kernel rounding can nudge a real score a few ulp above the
# float64 host-side bound arithmetic; inflating every finite bound by
# this factor keeps pruning strictly conservative.
_BOUND_MARGIN = 1.0001


def _boost_bound(self, bind, seg) -> float:
    """max_score_bound for constant-score plans: the boost IS the only
    possible score."""
    b = float(bind["boost"])
    return b * _BOUND_MARGIN if b >= 0 else math.inf


@dataclass(frozen=True)
class MatchAllPlan(Plan):
    def prepare(self, bind, seg, dseg, ctx):
        return (), (_scalar(bind["boost"], _F32),)

    def eval(self, A, dims, ins):
        (boost,) = ins
        n_pad = A["live"].shape[0]
        return jnp.full(n_pad, boost, jnp.float32), jnp.ones(n_pad, bool)

    max_score_bound = _boost_bound


@dataclass(frozen=True)
class MatchNonePlan(Plan):
    def prepare(self, bind, seg, dseg, ctx):
        return (), ()

    def eval(self, A, dims, ins):
        n_pad = A["live"].shape[0]
        return jnp.zeros(n_pad, jnp.float32), jnp.zeros(n_pad, bool)

    def max_score_bound(self, bind, seg) -> float:
        return 0.0


class BagDims(tuple):
    """``TermBagPlan``'s program key ``(t_pad, bucket, fast[, width])``.
    Beside the key, and no part of it (a tuple's hash and equality), it
    remembers the postings the bucket was rounded up from: the summed df
    of the terms the segment holds (``search.term_bag.postings``)."""

    postings = 0

    @classmethod
    def of(cls, postings: int, t_pad: int, *rest) -> "BagDims":
        dims = cls((t_pad, pad_bucket(postings)) + rest)
        dims.postings = postings
        return dims


@dataclass(frozen=True)
class TermBagPlan(Plan):
    """Weighted bag of terms over one field's postings: term / match /
    terms-as-should.  BM25-scored (Lucene TermQuery / BooleanQuery of term
    clauses).  bind: {terms, idfs, weights, required}; ``required`` is the
    per-doc matched-clause count needed (1 = OR, n_terms = AND,
    minimum_should_match otherwise).

    ``features``: the field is a ``rank_features`` field (``neural_sparse``):
    a posting's value column holds its stored feature weight, so that
    column stands where BM25's precomputed impacts do, in ``prepare``
    (the device), ``host_topk`` (recovery, parity) and
    ``max_score_bound``; ``idfs`` are 1 and ``weights`` the query's
    token weights.  Same programs, no quantized lowering."""

    field: str = ""
    scored: bool = True
    features: bool = False

    @property
    def bm25_scored(self) -> bool:
        """A scored bag whose scores are BM25 impacts: what the batched
        union kernel (``_msearch``, the batcher) can take."""
        return self.scored and not self.features

    def arrays(self):
        return frozenset({("postings", self.field)})

    def can_match(self, bind, seg):
        pf = seg.postings.get(self.field)
        if pf is None:
            return False
        present = sum(1 for t in bind["terms"] if pf.term_id(t) >= 0)
        # a doc can match at most `present` distinct query terms here
        return present >= max(int(bind.get("required", 1)), 1)

    def max_score_bound(self, bind, seg):
        if not self.scored:
            return 0.0                   # filter context scores are 0
        pf = seg.postings.get(self.field)
        if pf is None:
            return 0.0
        mi = (pf.max_values() if self.features
              else seg.max_impacts(self.field, bind["avgdl"]))
        total = 0.0
        for t, idf_v, w in zip(bind["terms"], bind["idfs"],
                               bind["weights"]):
            if w < 0:
                return math.inf          # negative weights: no bound
            tid = pf.term_id(t)
            if tid >= 0:
                total += float(idf_v) * float(w) * float(mi[tid])
        return total * _BOUND_MARGIN

    def host_topk(self, bind, seg, live, k: int, min_score=None):
        """The degradation backend and the parity reference: score this
        bag host-side from the segment's precomputed impact table
        (``Segment.impact_table``) and return ``(vals f32 [m<=k], idx
        i32 [m], total, max_score)`` with ``run_topk``'s exact semantics
        — float32 contributions in the same multiply order as the device
        kernel, in-order per-term accumulation, live/min_score masking
        excluded from totals, and ``lax.top_k``'s tie-break (score desc,
        then LOWER doc id).

        Never a first choice on any backend: ``ShardSearcher._topk`` and
        ``BatchGroup.run`` come here for a segment the device cannot
        serve (breaker open, segment evicted, device error, non-finite
        result)."""
        n = seg.n_docs
        pf = seg.postings.get(self.field)
        if pf is None:
            return (np.empty(0, _F32), np.empty(0, _I32), 0, -np.inf)
        from opensearch_tpu.index import codec as codec_mod
        if self.features:
            imp = pf.tfs
        elif codec_mod.use_quantized(seg):
            # parity with the QUANTIZED device kernel: reconstruct
            # impacts exactly as ops/quantized.py does (q * scale,
            # exact-guard blocks overridden) so budget-eviction /
            # breaker degradation stays byte-identical on compressed
            # segments too
            imp = seg.quantized_table(self.field,
                                      bind["avgdl"]).dequantized()
        else:
            imp, _mx = seg.impact_table(self.field, bind["avgdl"])
        idfs = np.asarray(bind["idfs"], _F32)
        weights = np.asarray(bind["weights"], _F32)
        required = int(bind["required"])
        fast = (required == 1 and bool((weights > 0).all())
                and bool((idfs > 0).all()))
        scores = np.zeros(n, _F32)
        counts = None if fast else np.zeros(n, np.int32)
        for t, idf_v, w in zip(bind["terms"], idfs, weights):
            tid = pf.term_id(t)
            if tid < 0:
                continue
            e0, e1 = int(pf.offsets[tid]), int(pf.offsets[tid + 1])
            d = pf.doc_ids[e0:e1]
            # doc ids are unique within one postings list: plain fancy-
            # index add accumulates in gather order, matching the
            # device scatter bit-for-bit
            scores[d] += w * (idf_v * imp[e0:e1])
            if counts is not None:
                counts[d] += 1
        matched = (scores > 0.0 if counts is None
                   else counts >= required)
        matched &= live[:n]
        if min_score is not None:
            matched &= scores >= np.float32(min_score)
        midx = np.flatnonzero(matched)
        total = len(midx)
        if total == 0:
            return (np.empty(0, _F32), np.empty(0, _I32), 0, -np.inf)
        mscores = scores[midx]
        mx = float(mscores.max())
        if total > k:
            kth = np.partition(mscores, -k)[-k]
            midx = midx[mscores >= kth]
        order = np.lexsort((midx, -scores[midx]))[:k]
        sel = midx[order]
        return scores[sel], sel.astype(_I32), total, mx

    def prepare(self, bind, seg, dseg, ctx):
        terms = bind["terms"]
        pf = seg.postings.get(self.field)
        t_pad = pad_pow2(len(terms), minimum=1)
        tids = np.zeros(t_pad, dtype=_I32)
        active = np.zeros(t_pad, dtype=bool)
        budget = 0
        for i, t in enumerate(terms):
            tid = pf.term_id(t) if pf is not None else -1
            if tid >= 0:
                tids[i] = tid
                active[i] = True
                budget += int(pf.df[tid])
        if not self.scored:
            packed = _stage_input(_pack_term_inputs(
                tids, active, None, None, bind["required"]))
            return BagDims.of(budget, t_pad, False), (packed,)
        idfs = np.asarray(bind["idfs"], _F32)
        weights = np.asarray(bind["weights"], _F32)
        packed = _stage_input(_pack_term_inputs(
            tids, active, idfs, weights, bind["required"]))
        # fast path: a plain OR bag with positive idf*weight scores > 0
        # exactly on matched docs, so the matched-count scatter (half the
        # kernel's scatter traffic) is skipped entirely
        fast = (int(bind["required"]) == 1
                and bool((weights > 0).all()) and bool((idfs > 0).all()))
        if self.features:
            # the staged weight column itself, never a quantized table
            p = dseg.ensure_postings(self.field)
            ins = (packed, p["tfs"] if p is not None
                   else _stage_input(np.zeros(8, _F32)))
            return BagDims.of(budget, t_pad, fast), ins
        if getattr(dseg, "quantized_mode", False):
            # QUANTIZED lowering (index/codec.py): the compressed
            # columns ride in ``ins`` via the pager, the f32 posting
            # arrays are never staged (see ``skip_arrays``), and dims
            # grows a 4th element — width is a static shape input to
            # the packed gather, and the arity keeps compiled f32
            # programs distinct from quantized ones.
            qarrs = dseg.quantized(self.field, bind["avgdl"])
            qt = seg.quantized_table(self.field, bind["avgdl"])
            ins = (packed, qarrs["qvals"], qarrs["scales"],
                   qarrs["exact_vals"], qarrs["exact_offsets"],
                   qarrs["packed"], qarrs["base"])
            return BagDims.of(budget, t_pad, fast, int(qt.width)), ins
        ins = (packed,
               dseg.impacts(self.field, bind["avgdl"]))  # quantize-ok: f32 lowering (non-quantized segments)
        return BagDims.of(budget, t_pad, fast), ins

    def skip_arrays(self, dims) -> frozenset:
        # 4-tuple dims = quantized lowering: eval only needs the
        # (always-staged) offsets from the postings entry, so the
        # executor must NOT demand-stage the full f32 columns
        if len(dims) == 4:
            return frozenset({("postings", self.field)})
        return frozenset()

    def slice_gathers(self, dims):
        # 4-tuple dims = quantized lowering: the bit-packed gather
        return len(dims) == 3 and bm25_ops.slice_lowering(dims[0], dims[1])

    def sorted_topk(self, dims, n_pad, k):
        # 4-tuple dims = quantized lowering: its own gather and scores
        return (self.scored and len(dims) == 3
                and bm25_ops.sorted_bag(dims[0], dims[1], n_pad, k))

    def eval_topk(self, A, dims, k: int, ins, min_score):
        """``_run_topk``'s four results for this bag at the root of a
        plan, over a segment whose every doc is live (``sorted_topk``):
        the same bits as ``eval`` + ``_key_topk``, by ``bm25_ops.
        impact_topk_sorted``."""
        p = A["postings"][self.field]
        t_pad, budget, fast = dims
        tids, active, idfs, weights, required = _unpack_term_inputs(
            ins[0], t_pad)
        return bm25_ops.impact_topk_sorted(  # engine-ok: TermBag scored lowering
            p["offsets"], p["doc_ids"], ins[1], tids, active, idfs,
            weights, required, min_score, n_pad=A["live"].shape[0],
            budget=budget, k=k, fast=fast)

    def prefetch_quantized(self, bind, segments) -> int:
        """Prefetch oracle for the pager: rank candidate segments by
        their per-term block-max score bound — the best any of their
        docs could contribute, exactly the MaxScore pruning surface —
        and prefetch quantized pages best-first into FREE pager
        capacity (never evicting residents).  Returns segments staged."""
        from opensearch_tpu.index import codec as codec_mod
        from opensearch_tpu.index.segment import prefetch_quantized
        if self.features:
            return 0
        ranked = []
        for seg in segments:
            if not codec_mod.use_quantized(seg):
                continue
            if not self.can_match(bind, seg):
                continue
            ranked.append((self.max_score_bound(bind, seg), seg))
        ranked.sort(key=lambda pair: -pair[0])
        staged = 0
        for _bound, seg in ranked:
            if prefetch_quantized(seg, self.field, bind["avgdl"]):
                staged += 1
        return staged

    def eval(self, A, dims, ins):
        p = A["postings"][self.field]
        n_pad = A["live"].shape[0]
        tids, active, idfs, weights, required = _unpack_term_inputs(
            ins[0], dims[0])
        if self.scored and len(dims) == 4:
            t_pad, budget, fast, width = dims
            qvals, scales, exact_vals, exact_offsets, packed, base = ins[1:]
            if fast:
                scores = quantized_ops.quantized_impact_scores(  # engine-ok: TermBag quantized lowering
                    p["offsets"], packed, base, qvals, scales,
                    exact_vals, exact_offsets, tids, active, idfs,
                    weights, width=width, n_pad=n_pad, budget=budget)
                matched = scores > 0.0
            else:
                scores, count = quantized_ops.quantized_impact_score_count(  # engine-ok: TermBag quantized lowering
                    p["offsets"], packed, base, qvals, scales,
                    exact_vals, exact_offsets, tids, active, idfs,
                    weights, width=width, n_pad=n_pad, budget=budget,
                    scored=True)
                matched = count >= required
            return jnp.where(matched, scores, 0.0), matched
        t_pad, budget, fast = dims
        if not self.scored:
            count = bm25_ops.match_count(  # engine-ok: TermBag filter lowering
                p["offsets"], p["doc_ids"], p["tfs"], tids, active,
                n_pad=n_pad, budget=budget)
            return jnp.zeros(n_pad, jnp.float32), count >= required
        impacts = ins[1]
        if fast:
            scores = bm25_ops.impact_scores(  # engine-ok: TermBag scored lowering
                p["offsets"], p["doc_ids"], impacts, tids, active,
                idfs, weights, n_pad=n_pad, budget=budget)
            matched = scores > 0.0
        else:
            scores, count = bm25_ops.impact_score_count(  # engine-ok: TermBag scored lowering
                p["offsets"], p["doc_ids"], impacts, tids, active,
                idfs, weights, n_pad=n_pad, budget=budget, scored=True)
            matched = count >= required
        return jnp.where(matched, scores, 0.0), matched


class PhraseDims(tuple):
    """``PhrasePlan``'s program key ``(s_pad, bucket)``: the padded slot
    count and ONE ``1024 * 4^k`` bucket, the anchor slot's (a lane of it
    costs ~1 us of searches, and three phrases in four anchor on a word
    with under 1,024 positions in a segment).  Beside the
    key, and no part of it (``BagDims``' way), it remembers what
    ``prepare`` saw: the phrase's slots and the anchor's positions in the
    segment (``search.phrase.slots``, ``search.phrase.anchor_positions``)."""

    slots = 0
    anchor_positions = 0

    @classmethod
    def of(cls, slots: int, anchor_positions: int) -> "PhraseDims":
        dims = cls((pad_pow2(slots, minimum=4),
                    pad_bucket(anchor_positions, minimum=1024)))
        dims.slots = slots
        dims.anchor_positions = anchor_positions
        return dims


def phrase_dims(dims):
    """Every ``PhraseDims`` in a plan's ``dims`` tree, wherever the
    phrase sits (root, ``bool.must``, ``bool.should``, ``dis_max``)."""
    if isinstance(dims, PhraseDims):
        yield dims
    elif isinstance(dims, tuple):
        for d in dims:
            yield from phrase_dims(d)


@dataclass(frozen=True)
class PhrasePlan(Plan):
    """Exact phrase over one field (match_phrase, slop=0).  bind: {terms,
    positions, idf_sum, boost, avgdl}.

    The segment program is keyed by ``PhraseDims``.  ``prepare`` puts the
    slot with the fewest positions in the segment first (the anchor, as
    Lucene leads with the rarest term) and the others behind it, fewest
    first; ``ops/phrase.py`` gathers the anchor's occurrences and probes
    the other slots for each, so a program costs what its rarest word
    holds, and a slot the segment lacks makes it match nothing."""

    field: str = ""
    scored: bool = True

    def arrays(self):
        return frozenset({("postings", self.field)})

    def can_match(self, bind, seg):
        pf = seg.postings.get(self.field)
        if pf is None:
            return False
        # an exact phrase needs EVERY term present
        return all(pf.term_id(t) >= 0 for t in bind["terms"])

    def max_score_bound(self, bind, seg):
        if not self.scored:
            return 0.0
        # tf/(tf+norm) < 1 always (norm >= k1*(1-b) > 0)
        return (float(bind["idf_sum"]) * float(bind["boost"])
                * _BOUND_MARGIN)

    def prepare(self, bind, seg, dseg, ctx):
        terms = bind["terms"]
        pf = seg.postings.get(self.field)
        slots = []                     # (positions here, term id, position)
        for t, at in zip(terms, bind["positions"]):
            tid = pf.term_id(t) if pf is not None else -1
            if tid < 0:
                slots = []
                break
            e0, e1 = int(pf.offsets[tid]), int(pf.offsets[tid + 1])
            slots.append((int(pf.pos_offsets[e1] - pf.pos_offsets[e0]),
                          tid, int(at)))
        slots.sort(key=lambda s: s[0])         # stable: ties keep slot order
        dims = PhraseDims.of(len(terms), slots[0][0] if slots else 0)
        s_pad = dims[0]
        # one int32 array a segment program: [term ids | positions less
        # the anchor's | slots, 0 where a term is missing | the bits of
        # idf_sum, boost, avgdl]
        packed = np.zeros(2 * s_pad + 4, dtype=_I32)
        for j, (_n, tid, at) in enumerate(slots):
            packed[j] = tid
            packed[s_pad + j] = at - slots[0][2]
        packed[2 * s_pad] = len(slots)
        packed[2 * s_pad + 1:] = np.asarray(
            [bind["idf_sum"], bind["boost"], bind["avgdl"]], _F32).view(_I32)
        return dims, (_stage_input(packed),)

    def eval(self, A, dims, ins):
        s_pad, budget = dims
        (packed,) = ins
        idf_sum, boost, avgdl = lax.bitcast_convert_type(
            packed[2 * s_pad + 1:], jnp.float32)
        p = A["postings"][self.field]
        n_pad = A["live"].shape[0]
        tf = phrase_ops.phrase_freqs(
            p, packed[:s_pad], packed[s_pad:2 * s_pad], packed[2 * s_pad],
            budget=budget, n_pad=n_pad)
        matched = tf > 0
        if not self.scored:
            return jnp.zeros(n_pad, jnp.float32), matched
        dl = p["doc_lens"]
        norm = bm25_ops.K1_DEFAULT * (1.0 - bm25_ops.B_DEFAULT
                                      + bm25_ops.B_DEFAULT * dl / avgdl)
        scores = idf_sum * boost * tf / (tf + norm)
        return jnp.where(matched, scores, 0.0), matched


@dataclass(frozen=True)
class SpanNearPlan(Plan):
    """Span/interval proximity over one field (span_near, span_first,
    intervals match — ref SpanNearQueryBuilder.java:51,
    IntervalQueryBuilder.java:43).  bind: {terms, slop, end, idf_sum,
    boost, avgdl}; slop and end are dynamic scalars so tuning proximity
    never recompiles."""

    field: str = ""
    ordered: bool = True
    scored: bool = True

    def arrays(self):
        return frozenset({("postings", self.field)})

    def can_match(self, bind, seg):
        pf = seg.postings.get(self.field)
        if pf is None:
            return False
        return all(pf.term_id(t) >= 0 for t in bind["terms"])

    def max_score_bound(self, bind, seg):
        if not self.scored:
            return 0.0
        return (float(bind["idf_sum"]) * float(bind["boost"])
                * _BOUND_MARGIN)

    def prepare(self, bind, seg, dseg, ctx):
        terms = bind["terms"]
        pf = seg.postings.get(self.field)
        m = len(terms)
        tids = np.zeros(m, dtype=_I32)
        active = np.zeros(m, dtype=bool)
        budgets = []
        for j, t in enumerate(terms):
            tid = pf.term_id(t) if pf is not None else -1
            count = 0
            if tid >= 0:
                tids[j] = tid
                active[j] = True
                e0, e1 = int(pf.offsets[tid]), int(pf.offsets[tid + 1])
                count = int(pf.pos_offsets[e1] - pf.pos_offsets[e0])
            budgets.append(pad_bucket(count, minimum=1024))
        ins = (_stage_input(tids), _stage_input(active),
               _scalar(bind["slop"], _I32), _scalar(bind["end"], _I32),
               _scalar(bind["idf_sum"], _F32),
               _scalar(bind["boost"], _F32),
               _scalar(bind["avgdl"], _F32))
        return (tuple(budgets),), ins

    def eval(self, A, dims, ins):
        (budgets,) = dims
        tids, active, slop, end, idf_sum, boost, avgdl = ins
        p = A["postings"][self.field]
        n_pad = A["live"].shape[0]
        tf = span_ops.span_near_freqs(
            p, tids, active, budgets=budgets, n_pad=n_pad,
            ordered=self.ordered, slop=slop, end=end)
        matched = tf > 0
        if not self.scored:
            return jnp.zeros(n_pad, jnp.float32), matched
        dl = p["doc_lens"]
        norm = bm25_ops.K1_DEFAULT * (1.0 - bm25_ops.B_DEFAULT
                                      + bm25_ops.B_DEFAULT * dl / avgdl)
        scores = idf_sum * boost * tf / (tf + norm)
        return jnp.where(matched, scores, 0.0), matched


@dataclass(frozen=True)
class NumericTermsPlan(Plan):
    """term/terms over a numeric/date column: constant score (the reference
    compiles these to point/doc-values queries under ConstantScore).
    bind: {values, boost}."""

    field: str = ""
    kind: str = "long"               # long | double

    def arrays(self):
        return frozenset({("numeric", self.field)})

    def prepare(self, bind, seg, dseg, ctx):
        vals = bind["values"]
        q_pad = pad_pow2(len(vals), minimum=1)
        dtype = np.int64 if self.kind == "long" else np.float64
        fill = LONG_MISSING_MAX if self.kind == "long" else np.nan
        qv = _pad_np(vals, q_pad, fill, dtype)
        qvalid = _pad_np(np.ones(len(vals), bool), q_pad, False, bool)
        return (q_pad,), (qv, qvalid, _scalar(bind["boost"], _F32))

    def eval(self, A, dims, ins):
        qv, qvalid, boost = ins
        col = A["numeric"][self.field]
        n_pad = A["live"].shape[0]
        ok = (col["values"][:, None] == qv[None, :]) & qvalid[None, :]
        matched = jnp.zeros(n_pad, bool).at[col["value_docs"]].max(ok.any(axis=1))
        return jnp.where(matched, boost, 0.0).astype(jnp.float32), matched


@dataclass(frozen=True)
class NumericRangePlan(Plan):
    """bind: {lo, hi, boost} (inclusivity resolved into the bounds at
    compile time for longs; kept as static flags for doubles)."""

    field: str = ""
    kind: str = "long"               # long | double
    include_lo: bool = True
    include_hi: bool = True

    def arrays(self):
        return frozenset({("numeric", self.field)})

    def can_match(self, bind, seg):
        dv = seg.numeric_dv.get(self.field)
        if dv is None or not len(dv.value_docs):
            return False
        bounds = getattr(dv, "_value_bounds", None)
        if bounds is None:
            # immutable per segment: one scan serves every query
            bounds = dv._value_bounds = (dv.values.min(), dv.values.max())
        seg_lo, seg_hi = bounds
        lo, hi = bind["lo"], bind["hi"]
        if (seg_hi < lo or (seg_hi == lo and not self.include_lo)
                or seg_lo > hi or (seg_lo == hi and not self.include_hi)):
            return False
        return True

    def prepare(self, bind, seg, dseg, ctx):
        dtype = np.int64 if self.kind == "long" else np.float64
        return (), (_scalar(bind["lo"], dtype), _scalar(bind["hi"], dtype),
                    _scalar(bind["boost"], _F32))

    def eval(self, A, dims, ins):
        lo, hi, boost = ins
        col = A["numeric"][self.field]
        n_pad = A["live"].shape[0]
        matched = filter_ops.range_mask(
            col["values"], col["value_docs"], lo, hi,
            include_lo=self.include_lo, include_hi=self.include_hi,
            n_pad=n_pad)
        return jnp.where(matched, boost, 0.0).astype(jnp.float32), matched


@dataclass(frozen=True)
class OrdinalRangePlan(Plan):
    """Keyword range: per-segment ordinal bounds resolved host-side by
    binary search over the sorted term dictionary; the device compares
    ordinals (ordinal order == term order by construction).
    bind: {lo, lo_incl, hi, hi_incl, boost}."""

    field: str = ""

    def arrays(self):
        return frozenset({("ordinal", self.field)})

    def prepare(self, bind, seg, dseg, ctx):
        dv = seg.ordinal_dv.get(self.field)
        terms = dv.ord_terms if dv is not None else []
        lo, hi = bind["lo"], bind["hi"]
        lo_ord = 0
        hi_ord = len(terms)
        if lo is not None:
            lo_ord = (bisect.bisect_left(terms, lo) if bind["lo_incl"]
                      else bisect.bisect_right(terms, lo))
        if hi is not None:
            hi_ord = (bisect.bisect_right(terms, hi) if bind["hi_incl"]
                      else bisect.bisect_left(terms, hi))
        return (), (_scalar(lo_ord, _I32), _scalar(hi_ord, _I32),
                    _scalar(bind["boost"], _F32))

    def eval(self, A, dims, ins):
        lo_ord, hi_ord, boost = ins
        col = A["ordinal"][self.field]
        n_pad = A["live"].shape[0]
        matched = filter_ops.range_mask(
            col["ords"], col["value_docs"], lo_ord, hi_ord,
            include_lo=True, include_hi=False, n_pad=n_pad)
        return jnp.where(matched, boost, 0.0).astype(jnp.float32), matched


@dataclass(frozen=True)
class PostingsMaskPlan(Plan):
    """Constant-score docs-containing-any-of-these-terms (terms query on a
    keyword/text field — Lucene TermInSetQuery).  bind: {terms, boost}."""

    field: str = ""

    def arrays(self):
        return frozenset({("postings", self.field)})

    def prepare(self, bind, seg, dseg, ctx):
        terms = bind["terms"]
        pf = seg.postings.get(self.field)
        t_pad = pad_pow2(len(terms), minimum=1)
        tids = np.zeros(t_pad, dtype=_I32)
        active = np.zeros(t_pad, dtype=bool)
        budget = 0
        for i, t in enumerate(terms):
            tid = pf.term_id(t) if pf is not None else -1
            if tid >= 0:
                tids[i] = tid
                active[i] = True
                budget += int(pf.df[tid])
        return ((t_pad, pad_bucket(budget)),
                (_stage_input(tids), _stage_input(active),
                 _scalar(bind["boost"], _F32)))

    def slice_gathers(self, dims):
        return bm25_ops.slice_lowering(*dims)

    def eval(self, A, dims, ins):
        t_pad, budget = dims
        tids, active, boost = ins
        p = A["postings"][self.field]
        n_pad = A["live"].shape[0]
        matched = filter_ops.postings_mask(
            p["offsets"], p["doc_ids"], p["tfs"], tids, active,
            n_pad=n_pad, budget=budget)
        return jnp.where(matched, boost, 0.0).astype(jnp.float32), matched


@dataclass(frozen=True)
class TermRangeMaskPlan(Plan):
    """Constant-score docs containing any term in a CONTIGUOUS term-id
    range — a prefix is a range of the sorted term dict (Lucene
    PrefixQuery's automaton walk collapses to two binary searches).
    bind: {lo, hi, boost} (string bounds, [lo, hi))."""

    field: str = ""

    def arrays(self):
        return frozenset({("postings", self.field)})

    def prepare(self, bind, seg, dseg, ctx):
        pf = seg.postings.get(self.field)
        lo_tid = hi_tid = 0
        budget = 0
        if pf is not None:
            sterms = ctx.sorted_terms(seg, self.field)
            lo_tid = bisect.bisect_left(sterms, bind["lo"])
            hi_tid = bisect.bisect_left(sterms, bind["hi"])
            budget = int(pf.offsets[hi_tid] - pf.offsets[lo_tid])
        return ((pad_bucket(budget),),
                (_scalar(lo_tid, _I32), _scalar(hi_tid, _I32),
                 _scalar(bind["boost"], _F32)))

    def eval(self, A, dims, ins):
        (budget,) = dims
        lo_tid, hi_tid, boost = ins
        p = A["postings"][self.field]
        n_pad = A["live"].shape[0]
        o_lo = p["offsets"][lo_tid]
        o_hi = p["offsets"][hi_tid]
        i = jnp.arange(budget, dtype=jnp.int32)
        valid = i < (o_hi - o_lo)
        idx = jnp.where(valid, o_lo + i, 0)
        d = jnp.where(valid, p["doc_ids"][idx], n_pad - 1)
        matched = jnp.zeros(n_pad, bool).at[d].max(valid)
        return jnp.where(matched, boost, 0.0).astype(jnp.float32), matched


@dataclass(frozen=True)
class ExpandTermsPlan(Plan):
    """wildcard / regexp / fuzzy: terms enumerated host-side per segment
    against the sorted dictionary, then a constant-score postings mask
    (Lucene MultiTermQuery CONSTANT_SCORE rewrite).
    bind: {pattern, fuzzy_dist, prefix_length, boost}."""

    field: str = ""
    mode: str = "wildcard"           # wildcard | regexp | fuzzy

    def arrays(self):
        return frozenset({("postings", self.field)})

    def _expand(self, bind, sterms: list[str]) -> list[int]:
        pat = bind["pattern"]
        if self.mode == "wildcard":
            flags = re.IGNORECASE if bind.get("nocase") else 0
            rx = re.compile(fnmatch.translate(pat), flags)
            return [i for i, t in enumerate(sterms) if rx.match(t)]
        if self.mode == "regexp":
            rx = re.compile(pat)
            return [i for i, t in enumerate(sterms) if rx.fullmatch(t)]
        out = []
        pre = pat[: bind["prefix_length"]]
        for i, t in enumerate(sterms):
            if pre and not t.startswith(pre):
                continue
            if _edit_distance_le(pat, t, bind["fuzzy_dist"]):
                out.append(i)
        return out

    def prepare(self, bind, seg, dseg, ctx):
        pf = seg.postings.get(self.field)
        tids_list: list[int] = []
        budget = 0
        if pf is not None:
            sterms = ctx.sorted_terms(seg, self.field)
            tids_list = self._expand(bind, sterms)
            budget = int(sum(int(pf.df[t]) for t in tids_list))
        t_pad = pad_pow2(len(tids_list), minimum=1)
        return ((t_pad, pad_bucket(budget)),
                (_pad_np(tids_list, t_pad, 0, _I32),
                 _pad_np(np.ones(len(tids_list), bool), t_pad, False, bool),
                 _scalar(bind["boost"], _F32)))

    slice_gathers = PostingsMaskPlan.slice_gathers
    eval = PostingsMaskPlan.eval


@dataclass(frozen=True)
class ExistsPlan(Plan):
    field: str = ""
    src: str = "numeric"             # numeric | ordinal | vector | geo | norms

    def arrays(self):
        group = "postings" if self.src == "norms" else self.src
        return frozenset({(group, self.field)})

    def prepare(self, bind, seg, dseg, ctx):
        return (), (_scalar(bind["boost"], _F32),)

    def eval(self, A, dims, ins):
        (boost,) = ins
        if self.src == "norms":
            # the norms-entry analog: matches zero-token values too
            matched = A["postings"][self.field]["field_exists"]
        else:
            matched = A[self.src][self.field]["exists"]
        return jnp.where(matched, boost, 0.0).astype(jnp.float32), matched


@dataclass(frozen=True)
class MaskPlan(Plan):
    """Host-precomputed per-segment boolean mask (ids query).
    bind: {mask_fn: (seg, dseg) -> np.bool_[n_pad], boost}."""

    label: str = "ids"

    def prepare(self, bind, seg, dseg, ctx):
        mask = bind["mask_fn"](seg, dseg)
        return (), (_stage_input(mask), _scalar(bind["boost"], _F32))

    def eval(self, A, dims, ins):
        mask, boost = ins
        return jnp.where(mask, boost, 0.0).astype(jnp.float32), mask


@dataclass(frozen=True)
class ScoredMaskPlan(Plan):
    """Precomputed per-segment (scores, matched) — knn pre-pass results are
    injected into the tree through this node.
    bind: {fn: (seg, dseg) -> (scores, mask)}."""

    label: str = "knn"

    def prepare(self, bind, seg, dseg, ctx):
        scores, mask = bind["fn"](seg, dseg)
        return (), (_stage_input(scores), _stage_input(mask))

    def eval(self, A, dims, ins):
        scores, mask = ins
        return jnp.where(mask, scores, 0.0).astype(jnp.float32), mask


@dataclass(frozen=True)
class ScriptScorePlan(Plan):
    """Child plan scores re-mapped by a compiled script expression
    (ScriptScoreQuery; ref index/query/functionscore + the k-NN plugin's
    script-score path).  ``program`` is a scripting.ScriptProgram —
    hashable by (source, params), so identical scripts share one
    compiled XLA program per shape bucket."""

    child: Plan = None
    program: object = None

    def arrays(self):
        return self.child.arrays()

    def prepare(self, bind, seg, dseg, ctx):
        cdims, cins = self.child.prepare(bind["child"], seg, dseg, ctx)
        n_pad = dseg.n_pad
        ncols = []
        for f in self.program.numeric_fields:
            col = dseg.numeric.get(f)
            if col is None:
                ncols.append((jnp.zeros(n_pad, jnp.float32),
                              jnp.zeros(n_pad, bool)))
            else:
                # dense single-value view: min == the value for
                # single-valued fields; missing slots read 0.0
                vals = jnp.where(col["exists"],
                                 col["minv"].astype(jnp.float32), 0.0)
                ncols.append((vals, col["exists"]))
        vcols = []
        for f in self.program.vector_fields:
            vcol = dseg.vector.get(f)
            if vcol is None:
                from opensearch_tpu.search.scripting import ScriptException
                raise ScriptException(
                    f"script references vector field [{f}] with no "
                    "vectors in this index")
            vcols.append((vcol["values"], vcol["exists"]))
        return (cdims,), (cins, tuple(ncols), tuple(vcols),
                          self.program.param_values(),
                          _scalar(bind["boost"], _F32),
                          _scalar(bind.get("min_score")
                                  if bind.get("min_score") is not None
                                  else -np.inf, _F32))

    def slice_gathers(self, dims):
        return self.child.slice_gathers(dims[0])

    def eval(self, A, dims, ins):
        (cdims,) = dims
        cins, ncols, vcols, param_vals, boost, min_score = ins
        scores, matched = self.child.eval(A, cdims, cins)
        new = self.program.eval(
            scores,
            dict(zip(self.program.numeric_fields, ncols)),
            dict(zip(self.program.vector_fields, vcols)),
            param_vals)
        new = (jnp.broadcast_to(new, matched.shape)
               .astype(jnp.float32) * boost)
        matched = matched & (new >= min_score)
        return jnp.where(matched, new, 0.0), matched


def _prepare_children(children, binds, seg, dseg, ctx):
    dims, ins = [], []
    for c, b in zip(children, binds):
        d, i = c.prepare(b, seg, dseg, ctx)
        dims.append(d)
        ins.append(i)
    return tuple(dims), tuple(ins)


@dataclass(frozen=True)
class BoolPlan(Plan):
    """bind: {boost, required, children: tuple of child binds} where
    ``required`` is the resolved minimum matching should-clause count."""

    must: tuple = ()
    should: tuple = ()
    must_not: tuple = ()
    filter: tuple = ()

    def _children(self):
        return (*self.must, *self.should, *self.must_not, *self.filter)

    def can_match(self, bind, seg):
        binds = bind["children"]
        nm, ns = len(self.must), len(self.should)
        nn = len(self.must_not)
        for c, b in zip(self.must, binds[:nm]):
            if not c.can_match(b, seg):
                return False
        for c, b in zip(self.filter, binds[nm + ns + nn:]):
            if not c.can_match(b, seg):
                return False
        if ns and not self.must and not self.filter and \
                int(bind.get("required", 1)) >= 1:
            return any(c.can_match(b, seg)
                       for c, b in zip(self.should, binds[nm: nm + ns]))
        return True

    def max_score_bound(self, bind, seg):
        binds = bind["children"]
        nm, ns = len(self.must), len(self.should)
        boost = float(bind["boost"])
        if boost < 0:
            return math.inf
        total = 0.0
        for c, b in zip(self.must, binds[:nm]):
            total += c.max_score_bound(b, seg)
        for c, b in zip(self.should, binds[nm: nm + ns]):
            total += c.max_score_bound(b, seg)
        return total * boost * _BOUND_MARGIN

    def arrays(self):
        out = frozenset()
        for c in self._children():
            out |= c.arrays()
        return out

    def prepare(self, bind, seg, dseg, ctx):
        cdims, cins = _prepare_children(
            self._children(), bind["children"], seg, dseg, ctx)
        return cdims, (cins, _scalar(bind["boost"], _F32),
                       _scalar(bind["required"], _I32))

    def slice_gathers(self, dims):
        return any(c.slice_gathers(d)
                   for c, d in zip(self._children(), dims))

    def eval(self, A, dims, ins):
        cins, boost, required = ins
        n_pad = A["live"].shape[0]
        outs = [c.eval(A, dims[i], cins[i])
                for i, c in enumerate(self._children())]
        nm, ns, nn = len(self.must), len(self.should), len(self.must_not)
        matched = jnp.ones(n_pad, bool)
        scores = jnp.zeros(n_pad, jnp.float32)
        for s, m in outs[:nm]:                      # must
            matched &= m
            scores += s
        for _s, m in outs[nm + ns + nn:]:           # filter
            matched &= m
        for _s, m in outs[nm + ns: nm + ns + nn]:   # must_not
            matched &= ~m
        if ns:
            cnt = jnp.zeros(n_pad, jnp.int32)
            for s, m in outs[nm: nm + ns]:          # should
                cnt += m.astype(jnp.int32)
                scores += s
            matched &= cnt >= required
        scores = jnp.where(matched, scores * boost, 0.0)
        return scores, matched


@dataclass(frozen=True)
class DisMaxPlan(Plan):
    """bind: {boost, tie_breaker, children}."""

    children: tuple = ()

    def arrays(self):
        out = frozenset()
        for c in self.children:
            out |= c.arrays()
        return out

    def can_match(self, bind, seg):
        return any(c.can_match(b, seg)
                   for c, b in zip(self.children, bind["children"]))

    def max_score_bound(self, bind, seg):
        boost = float(bind["boost"])
        tie = float(bind["tie_breaker"])
        if boost < 0 or tie < 0 or tie > 1:
            return math.inf
        bounds = [c.max_score_bound(b, seg)
                  for c, b in zip(self.children, bind["children"])]
        if not bounds:
            return 0.0
        best = max(bounds)
        return (best + tie * (sum(bounds) - best)) * boost * _BOUND_MARGIN

    def prepare(self, bind, seg, dseg, ctx):
        cdims, cins = _prepare_children(
            self.children, bind["children"], seg, dseg, ctx)
        return cdims, (cins, _scalar(bind["boost"], _F32),
                       _scalar(bind["tie_breaker"], _F32))

    def slice_gathers(self, dims):
        return any(c.slice_gathers(d) for c, d in zip(self.children, dims))

    def eval(self, A, dims, ins):
        cins, boost, tie = ins
        n_pad = A["live"].shape[0]
        best = jnp.zeros(n_pad, jnp.float32)
        total = jnp.zeros(n_pad, jnp.float32)
        matched = jnp.zeros(n_pad, bool)
        for i, c in enumerate(self.children):
            s, m = c.eval(A, dims[i], cins[i])
            best = jnp.maximum(best, s)
            total += s
            matched |= m
        scores = best + tie * (total - best)
        return jnp.where(matched, scores * boost, 0.0), matched


@dataclass(frozen=True)
class ConstScorePlan(Plan):
    """bind: {boost, child}."""

    child: Optional[Plan] = None

    def arrays(self):
        return self.child.arrays()

    def can_match(self, bind, seg):
        return self.child.can_match(bind["child"], seg)

    max_score_bound = _boost_bound

    def prepare(self, bind, seg, dseg, ctx):
        cdims, cins = self.child.prepare(bind["child"], seg, dseg, ctx)
        return cdims, (cins, _scalar(bind["boost"], _F32))

    def slice_gathers(self, dims):
        return self.child.slice_gathers(dims)

    def eval(self, A, dims, ins):
        cins, boost = ins
        _s, matched = self.child.eval(A, dims, cins)
        return jnp.where(matched, boost, 0.0).astype(jnp.float32), matched


# ---------------------------------------------------------------------------
# Nested queries: object-space mini-plans.  A nested path's objects form
# their own padded id space; inner conditions evaluate [n_obj_pad] masks
# which scatter-OR back to parents (ToParentBlockJoinQuery's TPU shape).
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ObjTermsPlan:
    """term/terms membership over one nested child column.
    bind: {"values": [...]} (raw terms for ordinal, numbers for numeric).
    """

    field: str = ""
    kind: str = "ordinal"            # ordinal | numeric

    def prepare(self, bind, block, staged):
        col = (staged["ordinal"] if self.kind == "ordinal"
               else staged["numeric"]).get(self.field)
        if col is None:
            return None
        if self.kind == "ordinal":
            from opensearch_tpu.common.cache import attached_cache
            cache = attached_cache(block, "_term_to_ord",
                                   name="query.term_ords",
                                   max_weight=8 << 20,
                                   breaker="fielddata")
            term_to_ord = cache.get(self.field)
            if term_to_ord is None:
                ord_terms, _ords, _objs = block.ordinal[self.field]
                term_to_ord = {t: o for o, t in enumerate(ord_terms)}
                cache.put(self.field, term_to_ord)
            wanted = [term_to_ord[t] for t in bind["values"]
                      if t in term_to_ord]
            if not wanted:
                return None
            q_pad = pad_pow2(len(wanted), minimum=1)
            return (col["ords"], col["value_objs"],
                    _pad_np(wanted, q_pad, -2, _I32))
        wanted = [float(v) for v in bind["values"]]
        q_pad = pad_pow2(len(wanted), minimum=1)
        return (col["values"], col["value_objs"],
                _pad_np(wanted, q_pad, np.nan, np.float64))

    def eval(self, ins, n_obj_pad):
        if ins is None:
            return jnp.zeros(n_obj_pad, bool)
        vals, objs, wanted = ins
        hit = (vals[:, None] == wanted[None, :]).any(axis=1)
        return jnp.zeros(n_obj_pad, bool).at[objs].max(hit)


@dataclass(frozen=True)
class ObjRangePlan:
    """range over a numeric nested child.  bind: {"lo", "hi"} (floats,
    inclusivity resolved into static flags)."""

    field: str = ""
    include_lo: bool = True
    include_hi: bool = True

    def prepare(self, bind, block, staged):
        col = staged["numeric"].get(self.field)
        if col is None:
            return None
        return (col["values"], col["value_objs"],
                _scalar(bind["lo"], np.float64),
                _scalar(bind["hi"], np.float64))

    def eval(self, ins, n_obj_pad):
        if ins is None:
            return jnp.zeros(n_obj_pad, bool)
        vals, objs, lo, hi = ins
        above = vals >= lo if self.include_lo else vals > lo
        below = vals <= hi if self.include_hi else vals < hi
        return jnp.zeros(n_obj_pad, bool).at[objs].max(above & below)


@dataclass(frozen=True)
class ObjExistsPlan:
    field: str = ""

    def prepare(self, bind, block, staged):
        col = (staged["numeric"].get(self.field)
               or staged["ordinal"].get(self.field))
        if col is None:
            return None
        return (col["value_objs"],)

    def eval(self, ins, n_obj_pad):
        if ins is None:
            return jnp.zeros(n_obj_pad, bool)
        (objs,) = ins
        # padded entries point at the dead object slot
        mask = jnp.zeros(n_obj_pad, bool).at[objs].max(
            objs < n_obj_pad - 1)
        return mask


@dataclass(frozen=True)
class ObjBoolPlan:
    must: tuple = ()
    should: tuple = ()
    must_not: tuple = ()
    # shoulds required only when nothing else constrains (the top-level
    # bool's required-resolution, compiler _c_bool)
    should_required: bool = True

    def prepare(self, bind, block, staged):
        children = (*self.must, *self.should, *self.must_not)
        return tuple(c.prepare(b, block, staged)
                     for c, b in zip(children, bind["children"]))

    def eval(self, ins, n_obj_pad):
        nm, ns = len(self.must), len(self.should)
        mask = jnp.ones(n_obj_pad, bool)
        for c, i in zip(self.must, ins[:nm]):
            mask &= c.eval(i, n_obj_pad)
        if ns and self.should_required:
            any_should = jnp.zeros(n_obj_pad, bool)
            for c, i in zip(self.should, ins[nm: nm + ns]):
                any_should |= c.eval(i, n_obj_pad)
            mask &= any_should
        for c, i in zip(self.must_not, ins[nm + ns:]):
            mask &= ~c.eval(i, n_obj_pad)
        return mask


@dataclass(frozen=True)
class ObjMatchAllPlan:
    def prepare(self, bind, block, staged):
        return ()

    def eval(self, ins, n_obj_pad):
        return jnp.ones(n_obj_pad, bool)


@dataclass(frozen=True)
class NestedPlan(Plan):
    """nested query: inner object-space condition -> parent mask.
    bind: {"inner": inner_bind, "boost": f}."""

    path: str = ""
    inner: object = None             # Obj*Plan

    def prepare(self, bind, seg, dseg, ctx):
        block = seg.nested.get(self.path)
        staged = dseg.nested_staged(self.path)
        if block is None or staged is None:
            return ("missing",), ()
        inner_ins = self.inner.prepare(bind["inner"], block, staged)
        return (staged["n_obj_pad"],), (
            staged["obj_to_doc"], staged["obj_valid"], inner_ins,
            _scalar(bind["boost"], _F32))

    def eval(self, A, dims, ins):
        n_pad = A["live"].shape[0]
        if dims[0] == "missing":
            return jnp.zeros(n_pad, jnp.float32), jnp.zeros(n_pad, bool)
        n_obj_pad = dims[0]
        obj_to_doc, obj_valid, inner_ins, boost = ins
        obj_mask = self.inner.eval(inner_ins, n_obj_pad) & obj_valid
        matched = jnp.zeros(n_pad, bool).at[obj_to_doc].max(obj_mask)
        return jnp.where(matched, boost, 0.0).astype(jnp.float32), matched

    def can_match(self, bind, seg):
        return self.path in seg.nested


def _nearest_value_dist(col, origin):
    """Distance from ``origin`` to the NEAREST of a doc's values: 0 when
    origin lies inside [min, max], else the gap to the closer bound
    (multi-valued semantics of the reference's distance_feature/decay)."""
    mn = col["minv"].astype(jnp.float64)
    mx = col["maxv"].astype(jnp.float64)
    below = jnp.maximum(mn - origin, 0.0)     # origin below the range
    above = jnp.maximum(origin - mx, 0.0)     # origin above the range
    return jnp.maximum(below, above)


_EARTH_R_M = 6371008.8


def _haversine_m(lat1, lon1, lat2, lon2):
    """Vectorized great-circle distance in meters (degrees in)."""
    p1, p2 = jnp.radians(lat1), jnp.radians(lat2)
    dp = p2 - p1
    dl = jnp.radians(lon2) - jnp.radians(lon1)
    a = (jnp.sin(dp / 2) ** 2
         + jnp.cos(p1) * jnp.cos(p2) * jnp.sin(dl / 2) ** 2)
    return 2 * _EARTH_R_M * jnp.arcsin(jnp.sqrt(jnp.clip(a, 0.0, 1.0)))


@dataclass(frozen=True)
class BoostingPlan(Plan):
    """boosting query: positive clause scores, docs also matching the
    negative clause get demoted by negative_boost (BoostingQueryBuilder).
    bind: {boost, negative_boost, children: (pos_bind, neg_bind)}."""

    positive: Plan = None
    negative: Plan = None

    def arrays(self):
        return self.positive.arrays() | self.negative.arrays()

    def can_match(self, bind, seg):
        return self.positive.can_match(bind["children"][0], seg)

    def max_score_bound(self, bind, seg):
        boost = float(bind["boost"])
        if boost < 0:
            return math.inf
        pos = self.positive.max_score_bound(bind["children"][0], seg)
        # negative_boost is usually in [0, 1); a larger value could
        # amplify demoted docs, so bound by whichever factor is bigger
        return (pos * boost * max(1.0, float(bind["negative_boost"]))
                * _BOUND_MARGIN)

    def prepare(self, bind, seg, dseg, ctx):
        cdims, cins = _prepare_children(
            (self.positive, self.negative), bind["children"],
            seg, dseg, ctx)
        return cdims, (cins, _scalar(bind["boost"], _F32),
                       _scalar(bind["negative_boost"], _F32))

    def slice_gathers(self, dims):
        return (self.positive.slice_gathers(dims[0])
                or self.negative.slice_gathers(dims[1]))

    def eval(self, A, dims, ins):
        cins, boost, negative_boost = ins
        scores, matched = self.positive.eval(A, dims[0], cins[0])
        _ns, neg = self.negative.eval(A, dims[1], cins[1])
        scores = jnp.where(neg, scores * negative_boost, scores) * boost
        return jnp.where(matched, scores, 0.0), matched


@dataclass(frozen=True)
class TermsSetPlan(Plan):
    """terms_set: term bag whose per-doc required count comes from a
    NUMERIC FIELD of the doc itself (minimum_should_match_field;
    TermsSetQueryBuilder).  bind: {terms, idfs, weights, avgdl}."""

    field: str = ""
    msm_field: str = ""
    scored: bool = True

    def arrays(self):
        return frozenset({("postings", self.field),
                          ("numeric", self.msm_field)})

    def prepare(self, bind, seg, dseg, ctx):
        terms = bind["terms"]
        pf = seg.postings.get(self.field)
        t_pad = pad_pow2(len(terms), minimum=1)
        tids = np.zeros(t_pad, dtype=_I32)
        active = np.zeros(t_pad, dtype=bool)
        budget = 0
        for i, t in enumerate(terms):
            tid = pf.term_id(t) if pf is not None else -1
            if tid >= 0:
                tids[i] = tid
                active[i] = True
                budget += int(pf.df[tid])
        ins = (_stage_input(tids), _stage_input(active),
               _pad_np(bind["idfs"], t_pad, 0.0, _F32),
               _pad_np(bind["weights"], t_pad, 0.0, _F32),
               dseg.impacts(self.field, bind["avgdl"]))  # quantize-ok: TermsSet stays on the f32 lowering
        return (t_pad, pad_bucket(budget)), ins

    def slice_gathers(self, dims):
        return bm25_ops.slice_lowering(*dims)

    def eval(self, A, dims, ins):
        t_pad, budget = dims
        tids, active, idfs, weights, impacts = ins
        p = A["postings"][self.field]
        msm = A["numeric"][self.msm_field]
        n_pad = A["live"].shape[0]
        scores, count = bm25_ops.impact_score_count(  # engine-ok: TermsSet lowering
            p["offsets"], p["doc_ids"], impacts, tids, active,
            idfs, weights, n_pad=n_pad, budget=budget,
            scored=self.scored)
        # per-doc minimum from the doc's own field; docs without the
        # field never match (the reference skips them)
        required = jnp.where(msm["exists"],
                             msm["minv"].astype(jnp.int64), 2**62)
        matched = (count.astype(jnp.int64) >= required) & (count > 0)
        return jnp.where(matched, scores, 0.0), matched


@dataclass(frozen=True)
class DistanceFeaturePlan(Plan):
    """distance_feature: score = boost * pivot / (pivot + distance) over
    a numeric/date or geo_point field (DistanceFeatureQueryBuilder).
    bind: {boost, pivot, origin} (origin = scalar, or (lat, lon))."""

    field: str = ""
    kind: str = "numeric"              # numeric | geo

    def arrays(self):
        group = "geo" if self.kind == "geo" else "numeric"
        return frozenset({(group, self.field)})

    def prepare(self, bind, seg, dseg, ctx):
        if self.kind == "geo":
            lat, lon = bind["origin"]
            origin = (_stage_input(np.float64(lat)),
                      _stage_input(np.float64(lon)))
        else:
            origin = _scalar(bind["origin"], np.float64)
        return (), (origin, _scalar(bind["pivot"], np.float64),
                    _scalar(bind["boost"], _F32))

    def eval(self, A, dims, ins):
        origin, pivot, boost = ins
        n_pad = A["live"].shape[0]
        if self.kind == "geo":
            g = A["geo"][self.field]
            lat0, lon0 = origin
            d_entry = _haversine_m(g["lats"].astype(jnp.float64),
                                   g["lons"].astype(jnp.float64),
                                   lat0, lon0)
            dist = jnp.full(n_pad, jnp.inf).at[g["value_docs"]].min(d_entry)
            exists = g["exists"]
        else:
            col = A["numeric"][self.field]
            dist = _nearest_value_dist(col, origin)
            exists = col["exists"]
        score = boost * (pivot / (pivot + dist))
        matched = exists
        return jnp.where(matched, score, 0.0).astype(jnp.float32), matched


@dataclass(frozen=True)
class GeoDistancePlan(Plan):
    """geo_distance filter: any of the doc's points within ``distance``
    meters of the origin.  bind: {lat, lon, distance_m, boost}."""

    field: str = ""

    def arrays(self):
        return frozenset({("geo", self.field)})

    def prepare(self, bind, seg, dseg, ctx):
        return (), (_stage_input(np.float64(bind["lat"])),
                    _stage_input(np.float64(bind["lon"])),
                    _stage_input(np.float64(bind["distance_m"])),
                    _scalar(bind["boost"], _F32))

    def eval(self, A, dims, ins):
        lat0, lon0, dist_m, boost = ins
        g = A["geo"][self.field]
        n_pad = A["live"].shape[0]
        d_entry = _haversine_m(g["lats"].astype(jnp.float64),
                               g["lons"].astype(jnp.float64), lat0, lon0)
        hit = jnp.zeros(n_pad, bool).at[g["value_docs"]].max(
            d_entry <= dist_m)
        matched = hit & g["exists"]
        return jnp.where(matched, boost, 0.0).astype(jnp.float32), matched


@dataclass(frozen=True)
class GeoPolygonPlan(Plan):
    """geo_polygon filter: even-odd ray casting over the polygon's edge
    list, vectorized values x edges (GeoPolygonQueryBuilder; planar
    approximation like the reference's legacy path).  bind: {lats, lons
    (padded to v_pad, inactive edges zero-length), boost}."""

    field: str = ""

    def arrays(self):
        return frozenset({("geo", self.field)})

    def prepare(self, bind, seg, dseg, ctx):
        lats = np.asarray(bind["lats"], np.float64)
        lons = np.asarray(bind["lons"], np.float64)
        v_pad = pad_pow2(len(lats), minimum=4)
        # pad by repeating the last vertex: zero-length edges never cross
        plats = np.full(v_pad, lats[-1])
        plons = np.full(v_pad, lons[-1])
        plats[: len(lats)] = lats
        plons[: len(lons)] = lons
        return ((v_pad,), (_stage_input(plats), _stage_input(plons),
                           _scalar(bind["boost"], _F32)))

    def eval(self, A, dims, ins):
        plats, plons, boost = ins
        g = A["geo"][self.field]
        n_pad = A["live"].shape[0]
        y = g["lats"].astype(jnp.float64)[:, None]      # [V, 1]
        x = g["lons"].astype(jnp.float64)[:, None]
        yi, xi = plats[None, :], plons[None, :]         # [1, E]
        yj = jnp.roll(plats, -1)[None, :]
        xj = jnp.roll(plons, -1)[None, :]
        straddles = (yi > y) != (yj > y)
        # safe where straddles is False (the denominator can be 0 there)
        t = jnp.where(straddles, (y - yi) / jnp.where(yj - yi == 0, 1.0,
                                                      yj - yi), 0.0)
        crosses = straddles & (x < xi + t * (xj - xi))
        inside = (crosses.sum(axis=1) % 2) == 1
        hit = jnp.zeros(n_pad, bool).at[g["value_docs"]].max(inside)
        matched = hit & g["exists"]
        return jnp.where(matched, boost, 0.0).astype(jnp.float32), matched


@dataclass(frozen=True)
class GeoBoxPlan(Plan):
    """geo_bounding_box filter.  bind: {top, left, bottom, right, boost}
    (no dateline wrap)."""

    field: str = ""

    def arrays(self):
        return frozenset({("geo", self.field)})

    def prepare(self, bind, seg, dseg, ctx):
        return (), tuple(_stage_input(np.float64(bind[k]))
                         for k in ("top", "left", "bottom", "right")) + (
            _scalar(bind["boost"], _F32),)

    def eval(self, A, dims, ins):
        top, left, bottom, right, boost = ins
        g = A["geo"][self.field]
        n_pad = A["live"].shape[0]
        lats = g["lats"].astype(jnp.float64)
        lons = g["lons"].astype(jnp.float64)
        inside = ((lats <= top) & (lats >= bottom)
                  & (lons >= left) & (lons <= right))
        hit = jnp.zeros(n_pad, bool).at[g["value_docs"]].max(inside)
        matched = hit & g["exists"]
        return jnp.where(matched, boost, 0.0).astype(jnp.float32), matched


@dataclass(frozen=True)
class FunctionSpec:
    """One function_score function — static structure only; its dynamic
    params ride the bind tree."""

    kind: str = "weight"      # weight|field_value_factor|random_score|
    #                           script_score|decay
    filter: Optional[Plan] = None
    field: str = ""           # fvf / decay target
    modifier: str = "none"    # fvf modifier
    decay_fn: str = "gauss"   # gauss|exp|linear
    geo: bool = False         # decay over a geo field
    program: object = None    # scripting.ScriptProgram for script_score


@dataclass(frozen=True)
class FunctionScorePlan(Plan):
    """function_score (FunctionScoreQueryBuilder + functionscore/ dir):
    child score combined with per-doc function factors.
    bind: {boost, child, functions: tuple of per-function binds
    ({filter, weight, ...params}), max_boost, min_score}."""

    child: Plan = None
    functions: tuple = ()              # tuple[FunctionSpec]
    score_mode: str = "multiply"       # multiply|sum|avg|first|max|min
    boost_mode: str = "multiply"       # multiply|replace|sum|avg|max|min

    def arrays(self):
        out = self.child.arrays()
        for f in self.functions:
            if f.filter is not None:
                out |= f.filter.arrays()
            if f.kind in ("field_value_factor", "decay") and not f.geo:
                out |= frozenset({("numeric", f.field)})
            if f.kind == "decay" and f.geo:
                out |= frozenset({("geo", f.field)})
            if f.kind == "script_score" and f.program is not None:
                for nf in f.program.numeric_fields:
                    out |= frozenset({("numeric", nf)})
                for vf in f.program.vector_fields:
                    out |= frozenset({("vector", vf)})
        return out

    # fixed positional param layout per function kind (ins pytrees carry
    # no strings — jit inputs must be arrays)
    _PARAM_ORDER = {
        "weight": ("weight",),
        "field_value_factor": ("factor", "missing", "weight"),
        "random_score": ("seed", "salt", "weight"),
        "script_score": ("weight",),
        "decay": ("origin", "scale", "offset", "decay", "weight"),
        "decay_geo": ("origin_lat", "origin_lon", "scale", "offset",
                      "decay", "weight"),
    }
    _PARAM_DEFAULTS = {"weight": 1.0, "factor": 1.0, "missing": 1.0,
                       "seed": 0.0, "salt": 0.0, "offset": 0.0,
                       "decay": 0.5}

    def _param_names(self, spec):
        key = ("decay_geo" if spec.kind == "decay" and spec.geo
               else spec.kind)
        return self._PARAM_ORDER[key]

    def prepare(self, bind, seg, dseg, ctx):
        cdims, cins = self.child.prepare(bind["child"], seg, dseg, ctx)
        fdims, fins = [], []
        for spec, fb in zip(self.functions, bind["functions"]):
            d_i, i_i = (), []
            if spec.filter is not None:
                fd, fi = spec.filter.prepare(fb["filter"], seg, dseg, ctx)
                d_i = fd
                i_i.append(fi)
            if spec.kind == "script_score":
                i_i.append(spec.program.param_values())
            fb = dict(fb)
            if spec.kind == "random_score":
                # per-segment salt so random_score differs across segments
                import zlib
                fb["salt"] = float(zlib.crc32(seg.seg_id.encode()))
            params = tuple(
                _stage_input(np.float64(
                    fb.get(name, self._PARAM_DEFAULTS.get(name, 0.0))))
                for name in self._param_names(spec))
            i_i.append(params)
            fdims.append(d_i)
            fins.append(tuple(i_i))
        return (cdims, tuple(fdims)), (
            cins, tuple(fins), _scalar(bind["boost"], _F32),
            _scalar(bind.get("max_boost")
                    if bind.get("max_boost") is not None else np.inf,
                    np.float64),
            _scalar(bind.get("min_score")
                    if bind.get("min_score") is not None else -np.inf,
                    _F32))

    def _factor(self, spec, A, fdim, fin, n_pad, child_scores):
        parts = list(fin)
        params = dict(zip(self._param_names(spec), parts[-1]))
        value = None
        if spec.kind == "weight":
            value = jnp.full(n_pad, params["weight"])
        elif spec.kind == "field_value_factor":
            col = A["numeric"][spec.field]
            v = jnp.where(col["exists"],
                          col["minv"].astype(jnp.float64),
                          params.get("missing", 1.0))
            v = v * params.get("factor", 1.0)
            mod = spec.modifier
            if mod == "log":
                v = jnp.log10(jnp.maximum(v, 1e-12))
            elif mod == "log1p":
                v = jnp.log10(1.0 + jnp.maximum(v, 0.0))
            elif mod == "log2p":
                v = jnp.log10(2.0 + jnp.maximum(v, 0.0))
            elif mod == "ln":
                v = jnp.log(jnp.maximum(v, 1e-12))
            elif mod == "ln1p":
                v = jnp.log1p(jnp.maximum(v, 0.0))
            elif mod == "ln2p":
                v = jnp.log(2.0 + jnp.maximum(v, 0.0))
            elif mod == "sqrt":
                v = jnp.sqrt(jnp.maximum(v, 0.0))
            elif mod == "square":
                v = v * v
            elif mod == "reciprocal":
                v = 1.0 / jnp.where(v == 0, 1e-12, v)
            value = v * params.get("weight", 1.0)
        elif spec.kind == "random_score":
            seed = (params["seed"] + params["salt"]).astype(jnp.uint32)
            idx = jnp.arange(n_pad, dtype=jnp.uint32)
            x = idx * jnp.uint32(2654435761) + seed
            x = (x ^ (x >> 16)) * jnp.uint32(0x45D9F3B)
            x = (x ^ (x >> 16)) * jnp.uint32(0x45D9F3B)
            x = x ^ (x >> 16)
            value = (x.astype(jnp.float64) / jnp.float64(2**32)) \
                * params["weight"]
        elif spec.kind == "script_score":
            script_params = parts[-2]
            ncols = {f: (jnp.where(A["numeric"][f]["exists"],
                                   A["numeric"][f]["minv"]
                                   .astype(jnp.float32), 0.0),
                         A["numeric"][f]["exists"])
                     for f in spec.program.numeric_fields}
            vcols = {f: (A["vector"][f]["values"], A["vector"][f]["exists"])
                     for f in spec.program.vector_fields}
            value = spec.program.eval(child_scores, ncols, vcols,
                                      script_params) \
                * params.get("weight", 1.0)
            value = jnp.broadcast_to(value, (n_pad,))
        elif spec.kind == "decay":
            if spec.geo:
                g = A["geo"][spec.field]
                d_entry = _haversine_m(
                    g["lats"].astype(jnp.float64),
                    g["lons"].astype(jnp.float64),
                    params["origin_lat"], params["origin_lon"])
                dist = jnp.full(n_pad, jnp.inf).at[
                    g["value_docs"]].min(d_entry)
                dist = jnp.where(g["exists"], dist, 0.0)
            else:
                col = A["numeric"][spec.field]
                dist = jnp.where(
                    col["exists"],
                    _nearest_value_dist(col, params["origin"]), 0.0)
            eff = jnp.maximum(dist - params.get("offset", 0.0), 0.0)
            scale = params["scale"]
            decay = params.get("decay", 0.5)
            if spec.decay_fn == "gauss":
                sigma2 = -(scale ** 2) / (2.0 * jnp.log(decay))
                value = jnp.exp(-(eff ** 2) / (2.0 * sigma2))
            elif spec.decay_fn == "exp":
                lam = jnp.log(decay) / scale
                value = jnp.exp(lam * eff)
            else:                      # linear
                s = scale / (1.0 - decay)
                value = jnp.maximum((s - eff) / s, 0.0)
            value = value * params.get("weight", 1.0)
        applicable = jnp.ones(n_pad, bool)
        if spec.filter is not None:
            _fs, fmask = spec.filter.eval(A, fdim, parts[0])
            applicable = fmask
        return value.astype(jnp.float64), applicable

    def slice_gathers(self, dims):
        cdims, fdims = dims
        return self.child.slice_gathers(cdims) or any(
            spec.filter is not None and spec.filter.slice_gathers(fd)
            for spec, fd in zip(self.functions, fdims))

    def eval(self, A, dims, ins):
        cdims, fdims = dims
        cins, fins, boost, max_boost, min_score = ins
        scores, matched = self.child.eval(A, cdims, cins)
        n_pad = A["live"].shape[0]
        s64 = scores.astype(jnp.float64)
        if self.functions:
            values, apps = [], []
            for spec, fd, fi in zip(self.functions, fdims, fins):
                v, app = self._factor(spec, A, fd, fi, n_pad, scores)
                values.append(v)
                apps.append(app)
            any_app = apps[0]
            for a in apps[1:]:
                any_app = any_app | a
            if self.score_mode == "multiply":
                factor = jnp.ones(n_pad, jnp.float64)
                for v, a in zip(values, apps):
                    factor = factor * jnp.where(a, v, 1.0)
            elif self.score_mode == "sum":
                factor = jnp.zeros(n_pad, jnp.float64)
                for v, a in zip(values, apps):
                    factor = factor + jnp.where(a, v, 0.0)
            elif self.score_mode == "avg":
                # WEIGHTED average (values already carry their weight;
                # divide by the applicable weights, not the count)
                tot = jnp.zeros(n_pad, jnp.float64)
                wsum = jnp.zeros(n_pad, jnp.float64)
                for v, a, fi in zip(values, apps, fins):
                    w = fi[-1][-1]          # params tuple ends in weight
                    tot = tot + jnp.where(a, v, 0.0)
                    wsum = wsum + jnp.where(a, w, 0.0)
                factor = tot / jnp.maximum(wsum, 1e-12)
            elif self.score_mode == "max":
                factor = jnp.full(n_pad, -jnp.inf)
                for v, a in zip(values, apps):
                    factor = jnp.maximum(factor,
                                         jnp.where(a, v, -jnp.inf))
            elif self.score_mode == "min":
                factor = jnp.full(n_pad, jnp.inf)
                for v, a in zip(values, apps):
                    factor = jnp.minimum(factor, jnp.where(a, v, jnp.inf))
            else:                      # first
                factor = jnp.zeros(n_pad, jnp.float64)
                assigned = jnp.zeros(n_pad, bool)
                for v, a in zip(values, apps):
                    take = a & ~assigned
                    factor = jnp.where(take, v, factor)
                    assigned = assigned | a
            factor = jnp.where(any_app, factor, 1.0)
        else:
            factor = jnp.ones(n_pad, jnp.float64)
        factor = jnp.minimum(factor, max_boost)
        if self.boost_mode == "multiply":
            out = s64 * factor
        elif self.boost_mode == "replace":
            out = factor
        elif self.boost_mode == "sum":
            out = s64 + factor
        elif self.boost_mode == "avg":
            out = (s64 + factor) / 2.0
        elif self.boost_mode == "max":
            out = jnp.maximum(s64, factor)
        else:                          # min
            out = jnp.minimum(s64, factor)
        out = (out * boost).astype(jnp.float32)
        matched = matched & (out >= min_score)
        return jnp.where(matched, out, 0.0), matched


# constant-score leaves: the boost is the only score either of these
# families can produce, so it IS the block-max bound
for _cls in (NumericTermsPlan, NumericRangePlan, OrdinalRangePlan,
             PostingsMaskPlan, TermRangeMaskPlan, ExpandTermsPlan,
             ExistsPlan, MaskPlan, NestedPlan, GeoDistancePlan,
             GeoPolygonPlan, GeoBoxPlan):
    _cls.max_score_bound = _boost_bound
del _cls


def _edit_distance_le(a: str, b: str, k: int) -> bool:
    """Banded optimal-string-alignment distance (Levenshtein WITH
    transpositions — Lucene's fuzzy default, fuzzy_transpositions=true):
    True iff distance(a, b) <= k."""
    if abs(len(a) - len(b)) > k:
        return False
    if k == 0:
        return a == b
    prev2 = None
    prev = list(range(len(b) + 1))
    for i, ca in enumerate(a, 1):
        cur = [i] + [0] * len(b)
        lo = max(1, i - k)
        hi = min(len(b), i + k)
        if lo > 1:
            cur[lo - 1] = k + 1
        for j in range(lo, hi + 1):
            cost = 0 if ca == b[j - 1] else 1
            cur[j] = min(prev[j] + 1, cur[j - 1] + 1, prev[j - 1] + cost)
            if (prev2 is not None and i > 1 and j > 1
                    and ca == b[j - 2] and a[i - 2] == b[j - 1]):
                cur[j] = min(cur[j], prev2[j - 2] + 1)   # transposition
        for j in range(hi + 1, len(b) + 1):
            cur[j] = k + 1
        prev2, prev = prev, cur
        if min(prev) > k:
            return False
    return prev[len(b)] <= k


# ---------------------------------------------------------------------------
# jit entry points.  plan/dims/k are static; A/ins are traced.
# ---------------------------------------------------------------------------


def _key_topk(key, k: int, matched):
    """(top_scores[k], top_local_ids[k], total_matched, max_score) of a
    key that is -inf where nothing matched.  ``lax.top_k``'s lower-index
    tie-break == Lucene's ascending-doc-id tie-break, and ``ops/topk.py``
    keeps it without sorting the segment."""
    vals, idx, mx = topk_ops.topk_and_max(key, k)
    return vals, idx, matched.sum(), mx


def _pack_topk(vals, idx, tot, mx):
    """The four results as ONE ``int32[2k + 2]``, so that a segment
    program's answer crosses to the host in one read: the scores' bits,
    the local ids, the matched count (at most ``n_pad`` < 2**31; int64
    only because x64 is on) and the max's bits.  A bit cast moves every
    float32 bit for bit (-inf, NaN payloads); ``unpack_topk`` splits."""
    return jnp.concatenate([
        lax.bitcast_convert_type(vals, jnp.int32), idx.astype(jnp.int32),
        tot.astype(jnp.int32)[None],
        lax.bitcast_convert_type(mx, jnp.int32)[None]])


def unpack_topk(packed: np.ndarray):
    """Host side of ``_pack_topk``: (scores f32[k], local_ids i32[k],
    total_matched int, max_score float) as views of the one array."""
    k = (len(packed) - 2) // 2
    return (packed[:k].view(np.float32), packed[k:2 * k],
            int(packed[2 * k]), float(packed[2 * k + 1:].view(np.float32)[0]))


def _run_topk(plan: Plan, dims, k: int, A, ins, min_score, sorted_bag):
    if sorted_bag:
        # the caller saw no deleted doc: the sorted lanes read no live mask
        return plan.eval_topk(A, dims, k, ins, min_score)
    scores, matched = plan.eval(A, dims, ins)
    matched = matched & A["live"] & (scores >= min_score)
    return _key_topk(jnp.where(matched, scores, -jnp.inf), k, matched)


@partial(jax.jit, static_argnums=(0, 1, 2), static_argnames=("sorted_bag",))
def run_topk(plan: Plan, dims, k: int, A, ins, min_score, *,
             sorted_bag: bool = False):
    """One segment's top-k, packed (``unpack_topk`` on the host gives
    (top_scores[k], top_local_ids[k], total_matched, max_score)).
    ``min_score`` (-inf when unset) excludes docs from hits AND total,
    matching MinimumScoreCollector semantics.  ``sorted_bag``: the
    caller found ``plan.sorted_topk`` true and no deleted doc in its
    snapshot of the segment; the same bits either way."""
    return _pack_topk(*_run_topk(plan, dims, k, A, ins, min_score,
                                 sorted_bag))


@partial(jax.jit, static_argnums=(0, 1, 2), static_argnames=("sorted_bag",))
def run_topk_parts(plan: Plan, dims, k: int, A, ins, min_score, *,
                   sorted_bag: bool = False):
    """``run_topk``'s four results as four device arrays, for a caller
    that goes on with them on the device (the mesh's shard-local merge)."""
    return _run_topk(plan, dims, k, A, ins, min_score, sorted_bag)


@partial(jax.jit, static_argnums=(1,))
def topk_from_scores(scores, k: int, matched):
    """Packed top-k (as ``run_topk``) over an already-computed (scores,
    matched) pair — used when a full-scores pass already ran for
    aggregations."""
    return _pack_topk(*_key_topk(
        jnp.where(matched, scores, -jnp.inf), k, matched))


@partial(jax.jit, static_argnums=(0, 1))
def run_full(plan: Plan, dims, A, ins, min_score):
    """(scores[n_pad] zeroed-unmatched, matched[n_pad]) — for aggs, sorts,
    counts."""
    scores, matched = plan.eval(A, dims, ins)
    matched = matched & A["live"] & (scores >= min_score)
    return jnp.where(matched, scores, 0.0), matched
