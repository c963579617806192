"""Batched multi-query execution: one device program scores MANY queries.

The reference gets throughput from many concurrent search threads each
running the doc-at-a-time hot loop (ContextIndexSearcher.java:318 under
the ``search`` threadpool).  The TPU equivalent is batching: a block of
term-bag queries is one gather->score->scatter->top_k program — a single
dispatch amortizes host<->device latency and keeps the MXU/VPU busy with
wide, regular work instead of Q tiny kernels.

Served via ``ShardSearcher.msearch`` (the ``_msearch`` REST analog, ref
action/search/TransportMultiSearchAction.java): bodies that compile to a
plain scored term-bag (match / term / multi-term OR-AND) take the batched
kernel; anything else falls back to the sequential path per body —
semantics are identical either way (same impacts, same tie-breaks).

Round-6 kernel shape (impact-ordered scoring): the round-5 kernel
scattered per-posting BM25 into a dense ``[n_pad, T]`` doc x term matrix
and ran TWO ``[Q,T] @ [T,n_pad]`` einsums (scores + AND counts) — the
memory-bound core of the whole path (the 2-D scatter alone was ~60% of
batch wall time on CPU).  Now:

  1. gather the PRECOMPUTED impacts of the batch's distinct terms once
     (``DeviceSegment.impacts`` — no per-posting norm math, no doc_lens
     gather);
  2. ONE flat 1-D scatter-add of ``idf * impact`` into a
     ``[T * n_pad]`` arena (a 1-D scatter is ~6x cheaper than the same
     updates through a 2-D index);
  3. per-query-term weighted ROW gathers accumulate straight into the
     ``[Q, n_pad]`` score block — each query touches only its OWN few
     term rows (contiguous, cache-friendly) instead of a [Q,T]x[T,n]
     matmul over the whole union;
  4. the matched-count side is built the same way, and is SKIPPED
     entirely (static flag) when every query in the group is a plain OR
     bag — scores > 0 is then exactly the match mask;
  5. batched ``lax.top_k`` over [Q, n_pad].

Accumulation order per (query, doc) equals the sequential kernel's
(term order within the query), so batched and sequential scores are
byte-identical — the property tests/test_impacts.py pins.

Group inputs (union slots, per-query term rows) are cached on the
searcher keyed by the group's value signature, so a REPEATED msearch
batch does zero host-side assembly.
"""

from __future__ import annotations

from functools import partial

import numpy as np

import opensearch_tpu.common.jaxenv  # noqa: F401
import jax
import jax.numpy as jnp
from jax import lax

from opensearch_tpu.common.device_ledger import \
    device_ledger as _device_ledger
from opensearch_tpu.common.telemetry import metrics as _metrics
from opensearch_tpu.index.segment import pad_bucket, pad_pow2
from opensearch_tpu.ops import bm25 as bm25_ops

_I32 = np.int32
_F32 = np.float32


@partial(jax.jit, static_argnames=("n_pad", "budget", "k", "need_counts"))
def batch_impact_union_topk(offsets, doc_ids, impacts, live,
                            union_tids, union_active, union_idfs,
                            qslots, qweights, qact, required,
                            *, n_pad: int, budget: int, k: int,
                            need_counts: bool):
    """Score Q term-bag queries against one segment in ONE program via
    the union-of-terms + precomputed-impacts formulation (see module
    docstring).  ``union_tids``/``union_active``/``union_idfs`` are [T];
    ``qslots``/``qweights``/``qact`` are [Q, TQ] — query q's j-th term
    as a union slot, its boost weight, and its occurrence count (0 on
    padding, so duplicate terms keep satisfying AND); ``required`` is
    [Q] (inf on padding rows).  Returns (vals [Q, k], idx [Q, k],
    totals [Q], maxes [Q])."""
    d, imp, slot, valid = bm25_ops.gather_postings(
        offsets, doc_ids, impacts, union_tids, union_active,
        budget=budget, pad_doc=n_pad - 1)
    base = jnp.where(valid, union_idfs[slot] * imp, 0.0)
    t_pad = union_tids.shape[0]
    flat_idx = slot.astype(jnp.int64) * n_pad + d
    dense = jnp.zeros(t_pad * n_pad, jnp.float32).at[flat_idx].add(
        base).reshape(t_pad, n_pad)
    q_pad, tq = qslots.shape
    scores = jnp.zeros((q_pad, n_pad), jnp.float32)
    for j in range(tq):
        scores = scores + qweights[:, j: j + 1] * dense[qslots[:, j], :]
    if need_counts:
        pres = jnp.zeros(t_pad * n_pad, jnp.float32).at[flat_idx].add(
            valid.astype(jnp.float32)).reshape(t_pad, n_pad)
        counts = jnp.zeros((q_pad, n_pad), jnp.float32)
        for j in range(tq):
            counts = counts + qact[:, j: j + 1] * jnp.minimum(
                pres[qslots[:, j], :], 1.0)
        matched = (counts >= required[:, None]) & live[None, :]
    else:
        # every query is a positive-weight OR bag: score > 0 iff matched
        matched = (scores > 0.0) & live[None, :]
    key = jnp.where(matched, scores, -jnp.inf)
    vals, idx = lax.top_k(key, k)
    return vals, idx, matched.sum(axis=1), jnp.max(key, axis=1)


class BatchGroup:
    """Queries sharing (field, k) — batched into one program per
    segment."""

    def __init__(self, field: str, k: int):
        self.field = field
        self.k = k
        self.positions: list[int] = []    # index into the msearch bodies
        self.terms: list[tuple] = []
        self.idfs: list[np.ndarray] = []
        self.weights: list[np.ndarray] = []
        self.required: list[int] = []
        # group-level scanned/pruned counts of the last run() and the
        # backend that served it — shared by every member's insight
        # record (one pass served the group)
        self.last_stats = {"pruned": 0, "scanned": 0,
                           "path": "device_batched"}

    def add(self, pos: int, bind: dict):
        self.positions.append(pos)
        self.terms.append(tuple(bind["terms"]))
        self.idfs.append(np.asarray(bind["idfs"], _F32))
        self.weights.append(np.asarray(bind["weights"], _F32))
        self.required.append(int(bind["required"]))
        self.avgdl = float(bind["avgdl"])

    def signature(self) -> tuple:
        """Value identity of the batch: same signature -> identical
        staged inputs (idfs/avgdl derive from the searcher's stats, and
        the prep cache lives ON that searcher)."""
        return (self.field, self.k, tuple(self.terms),
                tuple(tuple(float(x) for x in w) for w in self.weights),
                tuple(self.required))

    def _prepare(self, searcher) -> dict:
        """Host-side assembly of the per-segment union/query inputs —
        everything that does NOT depend on the live bitmap, staged once
        and reused for every identical batch against this searcher.
        All stagings are ledger-recorded under one ``batch_group``
        owner whose lifetime follows this prep's cache entry."""
        from opensearch_tpu.common.device_ledger import (GroupCloser,
                                                         device_ledger)

        led = device_ledger()
        group = led.open_group(index=searcher.index_name,
                               shard=searcher.shard_id,
                               segment=f"msearch[{self.field},{self.k}]")
        Q = len(self.positions)
        q_pad = pad_pow2(Q, minimum=8)
        tq = pad_pow2(max((len(t) for t in self.terms), default=1),
                      minimum=1)
        need_counts = any(r != 1 for r in self.required) \
            or any((w <= 0).any() for w in self.weights) \
            or any((i <= 0).any() for i in self.idfs)
        req = np.full(q_pad, np.inf, _F32)   # padding rows match nothing
        req[:Q] = self.required
        req_j = led.stage(group, req, kind="batch_group",
                          field=self.field, name="required")
        segs = []
        pruned = 0
        for seg_order, seg in enumerate(searcher.segments):
            pf = seg.postings.get(self.field)
            if pf is None or seg.device().postings.get(self.field) is None:
                continue
            # distinct terms of the whole batch -> union slots
            slot_of: dict[int, int] = {}
            budget = 0
            for terms in self.terms:
                for t in terms:
                    tid = pf.term_id(t)
                    if tid >= 0 and tid not in slot_of:
                        slot_of[tid] = len(slot_of)
                        budget += int(pf.df[tid])
            if not slot_of:
                # no query term exists in this segment: nothing can
                # match, skip without staging or dispatch
                pruned += 1
                continue
            t_pad = pad_pow2(len(slot_of), minimum=8)
            union_tids = np.zeros(t_pad, _I32)
            union_active = np.zeros(t_pad, bool)
            union_idfs = np.zeros(t_pad, _F32)
            qslots = np.zeros((q_pad, tq), _I32)
            qweights = np.zeros((q_pad, tq), _F32)
            qact = np.zeros((q_pad, tq), _F32)
            for tid, si in slot_of.items():
                union_tids[si] = tid
                union_active[si] = True
            for qi, terms in enumerate(self.terms):
                j = 0
                for ti, t in enumerate(terms):
                    tid = pf.term_id(t)
                    if tid < 0:
                        continue
                    si = slot_of[tid]
                    union_idfs[si] = self.idfs[qi][ti]  # idf is per term
                    qslots[qi, j] = si
                    qweights[qi, j] = self.weights[qi][ti]
                    qact[qi, j] = 1.0   # occurrences: duplicate terms
                    j += 1              # keep satisfying AND
            sid = seg.seg_id
            segs.append((seg_order, {
                "union_tids": led.stage(group, union_tids,
                                        kind="batch_group",
                                        field=self.field,
                                        name=f"{sid}/union_tids"),
                "union_active": led.stage(group, union_active,
                                          kind="batch_group",
                                          field=self.field,
                                          name=f"{sid}/union_active"),
                "union_idfs": led.stage(group, union_idfs,
                                        kind="batch_group",
                                        field=self.field,
                                        name=f"{sid}/union_idfs"),
                "qslots": led.stage(group, qslots, kind="batch_group",
                                    field=self.field,
                                    name=f"{sid}/qslots"),
                "qweights": led.stage(group, qweights,
                                      kind="batch_group",
                                      field=self.field,
                                      name=f"{sid}/qweights"),
                "qact": led.stage(group, qact, kind="batch_group",
                                  field=self.field, name=f"{sid}/qact"),
                "budget": pad_bucket(budget),
            }))
        if pruned:
            _metrics().counter("search.segments_pruned").inc(pruned)
        led.seal(group)
        return {"need_counts": need_counts, "required": req_j,
                "segs": segs, "q_pad": q_pad,
                "_ledger": GroupCloser(led, group)}

    def _bind(self, qi: int) -> dict:
        return {"terms": self.terms[qi], "idfs": self.idfs[qi],
                "weights": self.weights[qi],
                "required": self.required[qi], "avgdl": self.avgdl}

    def _run_host(self, searcher, prof=None) -> dict:
        """The group's recovery backend, never its first choice: ``run``
        comes here when the ``batch`` or ``staging`` breaker is open,
        when the device program raised a device error, and when its
        result was not finite.  Every query scores host-side via
        ``TermBagPlan.host_topk`` over the shared per-segment impact
        tables — byte-identical to the device group and to the
        sequential path by construction (same accumulation order).
        Counted as one host fallback a group."""
        import time

        from opensearch_tpu.common.tasks import check_current
        from opensearch_tpu.search.plan import TermBagPlan

        _device_ledger().record_host_fallback()
        if prof is not None:
            prof.set("execution_path", "host_batched")
        plan = TermBagPlan(field=self.field, scored=True)
        acc = {pos: {"v": [], "s": [], "l": [], "tot": 0, "mx": -np.inf}
               for pos in self.positions}
        pruned = 0
        scanned = 0
        for seg_order, seg in enumerate(searcher.segments):
            check_current()    # cancellation point per segment
            t_seg = time.monotonic() if prof is not None else 0.0
            pf = seg.postings.get(self.field)
            if pf is None:
                continue
            if not any(pf.term_id(t) >= 0
                       for terms in self.terms for t in terms):
                pruned += 1    # no query term here: skip scoring
                if prof is not None:
                    prof.seg_pruned(seg.seg_id, "pruned_can_match",
                                    time.monotonic() - t_seg)
                continue
            live = searcher.ctx.lives[id(seg)]
            for qi, pos in enumerate(self.positions):
                vals, idx, tot, mx = plan.host_topk(  # engine-ok: the batch recovery backend
                    self._bind(qi), seg, live,
                    min(self.k, seg.n_docs), None)
                a = acc[pos]
                a["v"].append(vals)
                a["s"].append(np.full(len(vals), seg_order, _I32))
                a["l"].append(idx)
                a["tot"] += int(tot)
                a["mx"] = max(a["mx"], float(mx))
            scanned += 1
            if prof is not None:
                # the per-segment dispatch attribution includes scoring
                prof.seg_scanned(seg.seg_id, time.monotonic() - t_seg)
        if pruned:
            _metrics().counter("search.segments_pruned").inc(pruned)
        # group-level attribution the msearch member insight records
        # carry (shared by construction — ONE pass served the group)
        self.last_stats = {"pruned": pruned, "scanned": scanned,
                           "path": "host_batched"}
        t_red = time.monotonic() if prof is not None else 0.0
        out = {}
        for pos in self.positions:
            a = acc[pos]
            if not a["v"]:
                out[pos] = ([], 0, None)
                continue
            v = np.concatenate(a["v"])
            s = np.concatenate(a["s"])
            l = np.concatenate(a["l"])
            order = np.lexsort((l, s, -v))[: self.k]
            rows = [{"seg": int(s[i]), "local": int(l[i]),
                     "score": float(v[i])} for i in order]
            out[pos] = (rows, a["tot"],
                        None if a["mx"] == -np.inf else float(a["mx"]))
        if prof is not None:
            prof.add("reduce", time.monotonic() - t_red)
        return out

    def run(self, searcher, prof=None) -> dict:
        """Execute against every segment; returns {pos: (rows, total,
        max_score)} in the sequential path's row format.

        Device handles per segment LAUNCH; host-synced once at the end
        (4 D2H transfers per segment, not 4 per query per segment).
        ``_run_host`` is the recovery from an open breaker, a device
        error or a poisoned result.  ``prof`` is the shared GROUP
        profiler (see ShardSearcher.msearch)."""
        from opensearch_tpu.common.device_health import (device_health,
                                                         is_device_error)

        health = device_health()
        if not (health.allow("batch") and health.allow("staging")):
            # open device breaker: the whole group scores on the host
            # impact tables — byte-identical (the PR-5 invariant)
            return self._run_host(searcher, prof=prof)
        try:
            return self._run_device(searcher, health, prof=prof)
        except Exception as exc:
            if not is_device_error(exc):
                raise
            # counted: record_failure -> device.errors; the byte-
            # identical host path serves the group instead of failing
            # the whole msearch/continuous batch
            health.record_failure("batch", exc)
            return self._run_host(searcher, prof=prof)

    def _run_device(self, searcher, health, prof=None) -> dict:
        import time

        from opensearch_tpu.common.cache import attached_cache
        from opensearch_tpu.common.device_health import check_finite
        from opensearch_tpu.common.tasks import check_current

        if prof is not None:
            prof.set("execution_path", "device_batched")
            t_prep = time.monotonic()
        cache = attached_cache(searcher, "_batch_prep_cache",
                               name="search.batch_prep",
                               max_weight=64 << 20,
                               breaker="fielddata")
        sig = self.signature()
        prep = cache.get(sig)
        if prep is None:
            if prof is not None:
                prof.set("batch_prep_cache", "miss")
            prep = self._prepare(searcher)
            cache.put(sig, prep)
        elif prof is not None:
            prof.set("batch_prep_cache", "hit")
        if prof is not None:
            prof.add("prepare", time.monotonic() - t_prep)
            # segments the union prep dropped never dispatch: no query
            # term exists there (the batch path's can-match analog)
            staged = {so for so, _sp in prep["segs"]}
            for so, seg in enumerate(searcher.segments):
                if so not in staged:
                    prof.seg_pruned(seg.seg_id, "pruned_can_match", 0.0)
        self.last_stats = {
            "pruned": len(searcher.segments) - len(prep["segs"]),
            "scanned": len(prep["segs"]), "path": "device_batched"}
        launches = []             # (seg_order, vals[Q,k], idx, tot, mx)
        for seg_order, sp in prep["segs"]:
            check_current()    # cancellation point per segment program
            t_seg = time.monotonic() if prof is not None else 0.0
            seg = searcher.segments[seg_order]
            dseg = seg.device()
            # the batched union kernel stays on the f32 lowering: on
            # quantized segments the full posting columns demand-stage
            # here (DeviceSegment.ensure_postings)
            dseg.ensure_postings(self.field)
            impacts = dseg.impacts(self.field, self.avgdl)  # quantize-ok: batch union stays on the f32 lowering
            live = searcher.ctx.live_jnp(seg, dseg)
            kk = min(self.k, dseg.n_pad)
            vals, idx, tot, mx = batch_impact_union_topk(  # engine-ok: batch device backend
                dseg.postings[self.field]["offsets"],
                dseg.postings[self.field]["doc_ids"],
                impacts, live, sp["union_tids"], sp["union_active"],
                sp["union_idfs"], sp["qslots"], sp["qweights"],
                sp["qact"], prep["required"],
                n_pad=dseg.n_pad, budget=sp["budget"], k=kk,
                need_counts=prep["need_counts"])
            launches.append((seg_order, vals, idx, tot, mx))
            _device_ledger().record_dispatch(
                getattr(dseg, "_ledger_group", None),
                slice_gather=bm25_ops.slice_lowering(
                    sp["union_tids"].shape[0], sp["budget"]))
            if prof is not None:
                prof.seg_scanned(seg.seg_id, time.monotonic() - t_seg)
        # ONE host sync region: convert whole launches after the dispatch loop
        t_sync = time.monotonic()
        t_red = t_sync if prof is not None else 0.0
        synced = [(so, np.asarray(v), np.asarray(i), np.asarray(t),
                   np.asarray(m)) for so, v, i, t, m in launches]
        if synced:
            _device_ledger().record_fetch(
                sum(v.nbytes + i.nbytes + t.nbytes + m.nbytes
                    for _so, v, i, t, m in synced),
                time.monotonic() - t_sync)
        # result-sanity guard at the batch sync region: non-finite
        # scores mean the device returned poison — discard the whole
        # group's device results and recompute on the byte-identical
        # host path (files a flight-recorder capture + feeds the
        # batch breaker via record_poison)
        from opensearch_tpu.common.device_health import check_finite
        for so, v, _i, _t, _m in synced:
            bad = check_finite(v)
            if bad:
                seg = searcher.segments[so]
                health.record_poison(
                    kernel="batch_impact_union_topk",
                    segment=seg.seg_id, index=searcher.index_name,
                    shard=searcher.shard_id, bad=bad)
                return self._run_host(searcher, prof=prof)
        health.record_success("batch")
        out = {}
        for qi, pos in enumerate(self.positions):
            rows_v, rows_s, rows_l = [], [], []
            total = 0
            max_score = -np.inf
            for seg_order, avals, aidx, atot, amx in synced:
                vals, idx = avals[qi], aidx[qi]
                keep = vals > -np.inf
                rows_v.append(vals[keep])
                rows_s.append(np.full(int(keep.sum()), seg_order, _I32))
                rows_l.append(idx[keep])
                total += int(atot[qi])
                max_score = max(max_score, float(amx[qi]))
            if not rows_v:
                out[pos] = ([], 0, None)
                continue
            v = np.concatenate(rows_v)
            s = np.concatenate(rows_s)
            l = np.concatenate(rows_l)
            order = np.lexsort((l, s, -v))[: self.k]
            rows = [{"seg": int(s[i]), "local": int(l[i]),
                     "score": float(v[i])} for i in order]
            out[pos] = (rows, total,
                        None if max_score == -np.inf else float(max_score))
        if prof is not None:
            prof.add("reduce", time.monotonic() - t_red)
        return out


def plan_batches(searcher, bodies: list) -> tuple[dict, list]:
    """Partition msearch bodies into batchable groups and a fallback list.

    Returns ({(field, k): BatchGroup}, [positions needing the sequential
    path]).  Batchable = scored term-bag (TermBagPlan) with no sort /
    aggs / min_score / source filtering beyond defaults.  Compilation
    goes through the searcher's plan cache, so repeated bodies do zero
    parse/compile work here.
    """
    from opensearch_tpu.search import plan as P

    groups: dict = {}
    fallback = []
    for pos, body in enumerate(bodies):
        body = body or {}
        if (body.get("sort") is not None or body.get("aggs")
                or body.get("aggregations") or body.get("min_score")
                or body.get("highlight") or body.get("explain")
                or body.get("docvalue_fields") or body.get("fields")
                or body.get("collapse") or body.get("rescore")
                or body.get("suggest") or body.get("search_after")
                or body.get("stored_fields") or body.get("script_fields")
                or body.get("post_filter")
                or body.get("track_total_hits") is False
                or body.get("timeout") is not None
                or int(body.get("from", 0)) != 0):
            # a timeout budget needs the sequential path's per-segment
            # deadline checks — one fused batch program can't stop
            # early; collapse/rescore/suggest shape the response beyond
            # plain top-k; track_total_hits:false may legally return
            # lower-bound totals sequentially (k-th pruning) which the
            # exact batched totals would not reproduce
            fallback.append(pos)
            continue
        try:
            plan, bind = searcher.compiled(body.get("query"), scored=True)
        except Exception:
            fallback.append(pos)
            continue
        if not isinstance(plan, P.TermBagPlan) or not plan.bm25_scored:
            fallback.append(pos)
            continue
        k = int(body.get("size", 10))
        if k <= 0:
            fallback.append(pos)
            continue
        key = (plan.field, k)
        g = groups.get(key)
        if g is None:
            g = groups[key] = BatchGroup(plan.field, k)
        g.add(pos, bind)
    return groups, fallback
