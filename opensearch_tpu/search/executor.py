"""Shard-side query phase: run a compiled plan over every segment, merge
top-k across segments, fetch sources.

Analog of ``SearchService.executeQueryPhase`` -> ``QueryPhase.execute``
(search/query/QueryPhase.java:133) and the per-leaf loop in
``ContextIndexSearcher.searchLeaf`` (search/internal/
ContextIndexSearcher.java:292).  Where Lucene iterates doc-at-a-time per
leaf, here each segment is one batched XLA program producing dense scores;
the per-shard "reduce" over segments is a host-side k-way merge with
Lucene's tie-break (score desc, then index order = (segment, local doc)).
"""

from __future__ import annotations

import contextlib
import functools
import json
import time
from typing import Optional

import numpy as np

import opensearch_tpu.common.jaxenv  # noqa: F401
import jax.numpy as jnp

from opensearch_tpu.common.errors import IllegalArgumentError
from opensearch_tpu.common.telemetry import metrics as _metrics
from opensearch_tpu.common.telemetry import tracer as _tracer
from opensearch_tpu.index.segment import (
    LONG_MISSING_MAX,
    LONG_MISSING_MIN,
    DeviceSegment,
    Segment,
    pad_pow2,
)
from opensearch_tpu.search import insights
from opensearch_tpu.search import plan as P
from opensearch_tpu.search.compiler import ShardContext, compile_query
from opensearch_tpu.search.fetch import filter_source
from opensearch_tpu.search.insights import UNKEPT
from opensearch_tpu.search.profile import (NULL_PROFILER, QueryProfiler,
                                           describe_plan)
from opensearch_tpu.search.query_dsl import parse_query

_F32 = np.float32
_I32 = np.int32
_I32_MAX = 2**31 - 1

# Cluster-wide default for ``allow_partial_search_results`` (the
# reference's dynamic ``search.default_allow_partial_search_results``
# setting): a request-level value wins; the REST layer updates this via
# _cluster/settings, and the cluster coordinator reads it at scatter
# time.  True = a dead shard copy degrades the response
# (``_shards.failed`` + ``failures[]``) instead of failing it.
DEFAULT_ALLOW_PARTIAL_RESULTS = True


def shards_section(total: int, failures: "Optional[list]" = None,
                   skipped: int = 0) -> dict:
    """The ``_shards`` response block, with the reference's shape: a
    ``failures`` array only when something actually failed."""
    failures = failures or []
    out = {"total": int(total),
           "successful": int(total) - len(failures),
           "skipped": int(skipped), "failed": len(failures)}
    if failures:
        out["failures"] = list(failures)
    return out


def shard_failure_entry(index: str, shard: int, node: "Optional[str]",
                        exc: BaseException) -> dict:
    """One ``_shards.failures[]`` element (ShardSearchFailure analog):
    carries the REMOTE error type when the failure crossed the wire."""
    err_type = getattr(exc, "remote_type", None) \
        or getattr(exc, "error_type", None) \
        or type(exc).__name__
    return {"shard": int(shard), "index": index, "node": node,
            "reason": {"type": err_type, "reason": str(exc)}}


class SearchDeadline:
    """Per-request time budget (QueryPhase's timeout runnable analog).

    Checked between per-segment device programs — the same granularity
    as cancellation.  When the budget expires the query phase stops
    launching segments and the response carries ``timed_out: true`` with
    the partial results collected so far, like the reference's
    TimeExceededException handling in QueryPhase.execute.
    """

    __slots__ = ("_deadline", "timed_out")

    def __init__(self, timeout, t0: Optional[float] = None):
        """``timeout``: "100ms"/"2s"-style or millis; None disables."""
        self.timed_out = False
        if timeout is None:
            self._deadline = None
            return
        from opensearch_tpu.common.settings import parse_time
        seconds = parse_time(timeout)
        self._deadline = (None if seconds < 0
                          else (t0 if t0 is not None
                                else time.monotonic()) + seconds)

    def expired(self) -> bool:
        """True once the budget is spent; latches ``timed_out``."""
        if self._deadline is not None and \
                time.monotonic() >= self._deadline:
            self.timed_out = True
        return self.timed_out


def _dummy_for(group: str, field: str, dseg: DeviceSegment, mapper):
    """Shape-consistent empty arrays for a field absent from this segment
    (all-inactive: matches nothing, scores nothing)."""
    n_pad = dseg.n_pad
    dead = n_pad - 1
    if group == "postings":
        return {
            "offsets": jnp.zeros(8, jnp.int32),
            "doc_ids": jnp.full(8, dead, jnp.int32),
            "tfs": jnp.zeros(8, jnp.float32),
            "doc_lens": jnp.zeros(n_pad, jnp.float32),
            "pos_offsets": jnp.zeros(8, jnp.int32),
            "positions": jnp.zeros(8, jnp.int32),
            "field_exists": jnp.zeros(n_pad, bool),
        }
    if group == "numeric":
        ft = mapper.field_type(field)
        dtype = jnp.float64 if (ft is not None and ft.dv_kind == "double") else jnp.int64
        sentinel_min = np.inf if dtype == jnp.float64 else LONG_MISSING_MAX
        sentinel_max = -np.inf if dtype == jnp.float64 else LONG_MISSING_MIN
        return {
            "values": jnp.zeros(8, dtype),
            "value_docs": jnp.full(8, dead, jnp.int32),
            "minv": jnp.full(n_pad, sentinel_min, dtype),
            "maxv": jnp.full(n_pad, sentinel_max, dtype),
            "exists": jnp.zeros(n_pad, bool),
        }
    if group == "ordinal":
        return {
            "ords": jnp.full(8, -1, jnp.int32),
            "value_docs": jnp.full(8, dead, jnp.int32),
            "min_ord": jnp.full(n_pad, -1, jnp.int32),
            "max_ord": jnp.full(n_pad, -1, jnp.int32),
            "exists": jnp.zeros(n_pad, bool),
        }
    if group == "vector":
        ft = mapper.field_type(field)
        dim = getattr(ft, "dims", 1) or 1
        return {
            "values": jnp.zeros((n_pad, dim), jnp.float32),
            "exists": jnp.zeros(n_pad, bool),
        }
    if group == "geo":
        return {
            "lats": jnp.zeros(8, jnp.float32),
            "lons": jnp.zeros(8, jnp.float32),
            "value_docs": jnp.full(8, dead, jnp.int32),
            "exists": jnp.zeros(n_pad, bool),
        }
    raise IllegalArgumentError(f"unknown array group [{group}]")


def build_arrays(dseg: DeviceSegment, needed, mapper, live=None,
                 partial_ok=frozenset()):
    """Assemble the ``A`` pytree a plan reads: live mask + requested field
    array groups (absent fields get all-inactive dummies).  ``live`` is the
    caller's point-in-time staged live mask (defaults to the segment's
    construction-time state).

    ``partial_ok`` is the plan's ``skip_arrays(dims)`` — (group, field)
    pairs whose partial staging is fine as-is.  Quantized segments stage
    only offsets/doc_lens/field_exists eagerly; any OTHER plan touching
    their postings demand-stages the full f32 columns here
    (``DeviceSegment.ensure_postings``)."""
    from opensearch_tpu.common.cache import attached_cache

    A = {"live": dseg.live if live is None else live}
    sources = {"postings": dseg.postings, "numeric": dseg.numeric,
               "ordinal": dseg.ordinal, "vector": dseg.vector,
               "geo": dseg.geo}
    # per-device-segment dummy-array cache: bounded + accounted against
    # the fielddata breaker (these live in device memory with the real
    # columns); the weakref finalizer releases the accounting when the
    # staging is dropped
    cache = attached_cache(dseg, "_dummy_cache",
                           name="query.dummy_arrays",
                           max_weight=32 << 20, breaker="fielddata")
    for group, field in sorted(needed):
        entry = sources[group].get(field)
        if entry is None:
            entry = cache.get((group, field))
            if entry is None:
                entry = _dummy_for(group, field, dseg, mapper)
                cache.put((group, field), entry)
        elif (group == "postings" and "doc_ids" not in entry
                and (group, field) not in partial_ok):
            entry = dseg.ensure_postings(field)
        A.setdefault(group, {})[field] = {
            k: v for k, v in entry.items() if k != "n_ords"}
    return A


def _parse_sort(spec) -> Optional[list[dict]]:
    """Normalize the request ``sort`` into [{field, order, missing}].
    Returns None for the plain score-sorted path."""
    if spec is None:
        return None
    if not isinstance(spec, list):
        spec = [spec]
    out = []
    for s in spec:
        if isinstance(s, str):
            field, order = s, ("desc" if s == "_score" else "asc")
            out.append({"field": field, "order": order, "missing": "_last"})
        elif isinstance(s, dict):
            if len(s) != 1:
                raise IllegalArgumentError(f"malformed sort clause {s}")
            field, opts = next(iter(s.items()))
            if isinstance(opts, str):
                out.append({"field": field, "order": opts, "missing": "_last"})
            else:
                out.append({"field": field,
                            "order": opts.get("order",
                                              "desc" if field == "_score" else "asc"),
                            "missing": opts.get("missing", "_last")})
        else:
            raise IllegalArgumentError(f"malformed sort clause {s}")
    if len(out) == 1 and out[0]["field"] == "_score" and out[0]["order"] == "desc":
        return None
    return out


_MS_NEG_INF = None


def _min_score_scalar(min_score):
    """Staged min_score scalar; the common None case reuses one device
    constant instead of a fresh 4-byte H2D transfer per query."""
    global _MS_NEG_INF
    if min_score is None:
        if _MS_NEG_INF is None:
            # staging-ok: one cached 4-byte scalar constant
            _MS_NEG_INF = jnp.asarray(np.float32(-np.inf))
        return _MS_NEG_INF
    return jnp.asarray(np.float32(min_score))  # staging-ok: 4-byte scalar


_ROWS: tuple = ()


def _row_scalars(n: int) -> tuple:
    """Device int32 scalars ``0 .. n - 1``, staged once a process: a
    stacked program's row is an input, never a Python int (jit would copy
    it every call) nor static (a program a row)."""
    global _ROWS
    if len(_ROWS) < n:
        _ROWS = _ROWS + tuple(
            # staging-ok: one cached 4-byte scalar a row, once a process
            jnp.asarray(np.int32(i)) for i in range(len(_ROWS), n))
    return _ROWS


def _ledger():
    from opensearch_tpu.common.device_ledger import device_ledger
    return device_ledger()


def _plan_kind(plan) -> str:
    """What a sub-query's plan is, for a span attribute: the label of a
    pre-pass injected as a mask (``knn``, ``percolate``), else the plan
    class in snake case (``term_bag``)."""
    label = getattr(plan, "label", None)
    if label:
        return label
    name = type(plan).__name__.removesuffix("Plan")
    return "".join("_" + c.lower() if c.isupper() and i else c.lower()
                   for i, c in enumerate(name))


def _health():
    from opensearch_tpu.common.device_health import device_health
    return device_health()


def _host_capable(plan) -> bool:
    """True for a plan the host impact-table scorer can recover."""
    return (getattr(plan, "scored", False)
            and getattr(plan, "host_topk", None) is not None)


def _degraded(plan, why: str, exc: Optional[BaseException] = None):
    """The typed error of a device fault on a plan no host scorer can
    recover: the caller turns it into partial ``_shards.failures[]``."""
    from opensearch_tpu.common.device_health import DeviceDegradedError
    cause = f" ({type(exc).__name__}: {exc})" if exc is not None else ""
    return DeviceDegradedError(
        f"{why}: plan [{type(plan).__name__}] has no host fallback{cause}")


class _StackedBag:
    """A scored term bag at a request's root, bound on the host for every
    segment it may launch on before the first launch and staged as ONE
    ``int32[S_pad, 4 * t_pad + 1]``: a segment's packed inputs are a row
    of it, and its program takes the stacked array and the row (a device
    scalar, ``_row_scalars``) in place of its own packed array
    (``plan._term_row``).  ``S_pad``, the segments rounded up to a power
    of two and at least 8, lets a refresh or a merge mint no program key.
    The prepared-bindings cache keeps the request's dims and its one
    array under ``(ckey, the segments' identities)``: one get, one put and
    one eviction a request, and nothing staged on a hit.  The cache and
    the bind run in the first launched segment's ``segment.prepare``
    span; every later one takes its row under ``cache_get``.
    ``ShardSearcher.segment_inputs``, the one caller, asks it for every
    segment; a segment without a row, or every segment where one is
    quantized, is prepared there on its own."""

    def __init__(self, searcher, plan, bind, ckey, prof, ms_host):
        self.searcher = searcher
        self.plan, self.bind, self.ckey = plan, bind, ckey
        self.prof, self.ms_host = prof, ms_host
        self.rows = None           # {id(seg): (dims, ins)} once bound
        self.hit = False

    def inputs(self, seg, span):
        """``seg``'s (dims, ins), or None where it prepares on its own."""
        if self.rows is None:
            self.rows = {}         # a bind that raises leaves it off
            self.rows = self._bound(span)
            got = self.rows.get(id(seg))
        else:
            with span.part("cache_get"):
                got = self.rows.get(id(seg))
        if got is not None:
            if self.hit:
                span.set_attribute("prepared", "hit")
            self.prof.inc("prepared_hits" if self.hit
                          else "prepared_misses")
        return got

    def _bound(self, span):
        s = self.searcher
        segs = [seg for seg in s.segments
                if s._prune_reason(self.plan, self.bind, seg, self.ms_host,
                                   None) is None
                and not getattr(seg, "_device_evicted", False)]
        if self.ckey is None:
            return self._bind(segs, span)
        with span.part("cache_get"):
            cache = s._prepared_cache()
            key = (self.ckey, tuple(id(seg) for seg in segs))
            out = cache.get(key)
        if out is not None:
            self.hit = True
            return out
        out = self._bind(segs, span)
        if out:
            with span.part("cache_put"):
                cache.put(key, out)
        return out

    def _bind(self, segs, span):
        plan, bind, ctx = self.plan, self.bind, self.searcher.ctx
        with span.part("bind"), self.prof.phase("prepare"):
            dsegs = [seg.device() for seg in segs]
            if not segs or (not plan.features and any(
                    getattr(d, "quantized_mode", False) for d in dsegs)):
                return {}
            with P.host_inputs():
                bound = [plan.prepare(bind, seg, dseg, ctx)
                         for seg, dseg in zip(segs, dsegs)]
            host = np.zeros((pad_pow2(len(bound), minimum=8),
                             len(bound[0][1][0])), np.int32)
            for i, (_dims, ins) in enumerate(bound):
                host[i] = ins[0]
            stacked = _ledger().stage_input(host)
            rows = _row_scalars(len(host))
            return {id(seg): (dims, (stacked,) + ins[1:] + (rows[i],))
                    for i, (seg, (dims, ins)) in enumerate(zip(segs, bound))}


class ShardSearcher:
    """Immutable point-in-time view over a shard's segments (the
    Engine.Searcher / reader-context analog, ref search/SearchService.java:986)."""

    def __init__(self, segments: list[Segment], mapper, index_name: str = "index",
                 shard_id: int = 0):
        self.segments = [s for s in segments if s.n_docs > 0]
        self.mapper = mapper
        self.index_name = index_name
        self.shard_id = shard_id
        self.ctx = ShardContext(self.segments, mapper)

    # -- compiled-plan / prepared-bindings caches -------------------------

    def compiled(self, query_json: Optional[dict], scored: bool = True,
                 with_key: bool = False, prof=NULL_PROFILER,
                 iattrs=UNKEPT):
        """(plan, bind) for a raw query body through the searcher's plan
        cache, keyed on the canonicalized JSON (key order in the body
        never misses).  The searcher is an immutable point-in-time view,
        so entries can never go stale — a refresh builds a NEW searcher
        (the PR-3 reader-generation bump) and this cache dies with the
        old one.  A repeated query shape therefore does zero
        parse/compile work (`search.plan_cache.hits`).  ``prof`` (a
        QueryProfiler) times the cache lookup / parse / compile and
        records the hit-vs-miss attribution.  The ``query.plan`` span
        covers all of it: for ``knn`` and ``percolate`` compiling runs
        the whole pre-pass, device programs and host sync included."""
        with _tracer().start_span("query.plan"):
            return self._compiled(query_json, scored, with_key, prof,
                                  iattrs)

    def _compiled(self, query_json, scored, with_key, prof, iattrs):
        from opensearch_tpu.common.cache import attached_cache

        out = None
        with prof.phase("plan_cache"):
            try:
                ckey = (json.dumps(query_json, sort_keys=True,
                                   separators=(",", ":")), scored)
            except (TypeError, ValueError):
                ckey = None
            if ckey is not None:
                cache = attached_cache(self, "_plan_cache",
                                       name="search.plan",
                                       max_weight=16 << 20,
                                       breaker="fielddata")
                out = cache.get(ckey)
        if out is not None:
            _metrics().counter("search.plan_cache.hits").inc()
            prof.set("plan_cache", "hit")
            iattrs["plan_cache"] = "hit"
            return (out, ckey) if with_key else out
        _metrics().counter("search.plan_cache.misses").inc()
        iattrs["plan_cache"] = "miss"
        prof.set("plan_cache", "miss")
        with prof.phase("rewrite"):
            q = parse_query(query_json)
        out = compile_query(q, self.ctx, scored=scored, prof=prof)
        if ckey is not None:
            cache.put(ckey, out)
        return (out, ckey) if with_key else out

    @staticmethod
    def _prep_weight(key, value) -> int:
        """Prepared-bindings weigher: large staged columns referenced
        from the ins pytree (impacts et al.) are owned and accounted by
        the device-segment caches — charging their full nbytes here
        would thrash the cache on shared references, so anything over
        1 MiB is capped at 1 MiB."""
        from opensearch_tpu.common.cache import estimate_weight

        total = estimate_weight(key)

        def walk(v):
            nonlocal total
            nbytes = getattr(v, "nbytes", None)
            if nbytes is not None:
                total += min(int(nbytes), 1 << 20)
            elif isinstance(v, (tuple, list)):
                for x in v:
                    walk(x)
            elif isinstance(v, dict):
                for x in v.values():
                    walk(x)
            else:
                total += 8
        walk(value)
        return total

    def segment_inputs(self, plan, bind, seg, needed, ckey,
                       prof=NULL_PROFILER, dseg=None, stacked=None):
        """(dseg, dims, ins, A): everything one segment's program takes,
        under a ``segment.prepare`` span — the host work and the H2D
        before a launch, for this searcher's programs and the mesh's.
        The span's parts: ``device`` (``seg.device()``), ``cache_get`` /
        ``bind`` / ``cache_put`` (``_prepared``, or the request's
        ``stacked`` bind) and ``arrays`` (the live mask and
        ``build_arrays``).  ``dseg``: a staging of the caller's own (the
        mesh stages each shard on its chip), in place of
        ``seg.device()``."""
        with _tracer().start_span("segment.prepare", {"prepared": "miss"},
                                  cpu=True) as span:
            if dseg is None:
                with span.part("device"):
                    dseg = seg.device()
            # prepare FIRST: dims tells build_arrays which array groups
            # the lowering left deliberately partial (quantized segments)
            got = stacked.inputs(seg, span) if stacked is not None else None
            dims, ins = got or self._prepared(plan, bind, seg, dseg, ckey,
                                              prof, span)
            with span.part("arrays"):
                A = build_arrays(dseg, needed, self.mapper,
                                 live=self.ctx.live_jnp(seg, dseg),
                                 partial_ok=plan.skip_arrays(dims))
        return dseg, dims, ins, A

    def _prepared(self, plan, bind, seg, dseg, ckey, prof, span):
        """``plan.prepare``'s per-(plan, segment) static products —
        padded term ids, staged impact references, device scalars —
        cached so a repeated query shape does zero host-side prepare
        work (and zero H2D transfers) per segment.  ``prof`` records
        prepare time and the per-segment prepared-bindings hit/miss;
        a hit is also written on ``span`` (``segment.prepare``: its
        ``prepared`` attribute), and so are the parts ``cache_get``,
        ``bind`` (``plan.prepare``: padding and the H2D ``stage_input``)
        and ``cache_put`` (weigher, lock, eviction)."""
        def prepare():
            prof.inc("prepared_misses")
            with span.part("bind"), prof.phase("prepare"):
                return plan.prepare(bind, seg, dseg, self.ctx)

        if ckey is None:
            return prepare()
        with span.part("cache_get"):
            cache = self._prepared_cache()
            key = (ckey, id(seg))
            out = cache.get(key)
        if out is None:
            out = prepare()
            with span.part("cache_put"):
                cache.put(key, out)
        else:
            span.set_attribute("prepared", "hit")
            prof.inc("prepared_hits")
        return out

    def _prepared_cache(self):
        from opensearch_tpu.common.cache import attached_cache

        return attached_cache(self, "_prep_cache", name="search.prepare",
                              max_weight=64 << 20, breaker="fielddata",
                              weigher=self._prep_weight)

    # -- public API -------------------------------------------------------

    def doc_count(self) -> int:
        return sum(s.live_count() for s in self.segments)

    def count(self, query_json: Optional[dict] = None) -> int:
        if not self.segments:
            return 0
        (plan, bind), ckey = self.compiled(query_json, scored=False,
                                           with_key=True)
        return self._matched(plan, bind, plan.arrays(), None, ckey=ckey)

    def _matched(self, plan, bind, needed, min_score, **run) -> int:
        """The docs ``plan`` matches: ``_run_full``'s masks, summed.  Its
        can_match skip is safe here: a count only sums, so segments the
        plan provably can't match contribute nothing either way."""
        return sum(int(np.asarray(matched).sum()) for _s, _d, _sc, matched
                   in self._run_full(plan, bind, needed, min_score,
                                     can_match_skip=True, **run))

    def search(self, body: Optional[dict] = None, *,
               agg_partials: bool = False) -> dict:
        """``agg_partials=True`` is the distributed query phase: instead of
        finished aggregations the response carries the shard's mergeable
        ``aggregation_partials`` for a coordinator-side ``reduce_aggs``
        (QueryPhaseResultConsumer partial-reduce analog)."""
        body = body or {}
        t0 = time.monotonic()
        # the profiler exists ONLY for profiled requests; the null one
        # reads no clock
        prof = QueryProfiler() if body.get("profile") else NULL_PROFILER
        with _tracer().start_span(
                "shard.query_phase",
                {"index": self.index_name, "shard": self.shard_id,
                 "segments": len(self.segments)}):
            resp = self._search_body(body, t0, agg_partials=agg_partials,
                                     prof=prof)
        _metrics().histogram("search.query_ms").observe(
            (time.monotonic() - t0) * 1000)
        _metrics().counter("search.queries").inc()
        if resp.get("timed_out"):
            _metrics().counter("search.timed_out").inc()
        return resp

    def _search_body(self, body: dict, t0: float, *,
                     agg_partials: bool, prof) -> dict:
        size = int(body.get("size", 10))
        from_ = int(body.get("from", 0))
        deadline = SearchDeadline(body.get("timeout"), t0)
        q_json = body.get("query")
        fetch_extras = None
        # request-size limits (docvalue_fields, rescore window, result
        # window) are enforced by IndexService._check_search_limits with
        # the index's own settings; the shard searcher stays policy-free
        if (body.get("highlight") or body.get("explain")
                or body.get("docvalue_fields") or body.get("fields")):
            fetch_extras = {"highlight": body.get("highlight"),
                            "explain": bool(body.get("explain")),
                            "docvalue_fields": body.get("docvalue_fields"),
                            "fields": body.get("fields"),
                            "query": parse_query(q_json)}
        if isinstance(q_json, dict) and "hybrid" in q_json:
            from opensearch_tpu.search.query_dsl import HybridQuery
            if isinstance(parse_query(q_json), HybridQuery):
                return self._hybrid_search(body, t0, fetch_extras)
        sort_specs = _parse_sort(body.get("sort"))
        min_score = body.get("min_score")
        source_spec = body.get("_source")
        stored = body.get("stored_fields")
        if stored is not None and source_spec is None:
            # legacy stored_fields: _source returns only when asked for
            # explicitly (RestSearchAction's stored-fields contract)
            if isinstance(stored, str):
                stored = [stored]
            if "_source" not in stored:
                source_spec = False
        search_after = body.get("search_after")
        if search_after is not None:
            if sort_specs is None:
                raise IllegalArgumentError(
                    "[search_after] requires an explicit [sort]")
            if not isinstance(search_after, (list, tuple)):
                raise IllegalArgumentError(
                    "[search_after] must be an array of sort values")
            if len(search_after) != len(sort_specs):
                raise IllegalArgumentError(
                    f"[search_after] has {len(search_after)} values but "
                    f"sort has {len(sort_specs)} fields")

        # field-sorted queries that never reference _score skip BM25 scoring
        needs_scores = (sort_specs is None
                        or any(s["field"] == "_score" for s in sort_specs)
                        or min_score is not None)
        # always-on insight attribution: a few dict writes per query
        # (never per segment sync), drained by whatever edge installed
        # an insight sink — see search/insights.py emit()
        ia = {"plan_cache": "miss", "pruned": 0, "scanned": 0}
        xfer0 = _ledger().transfer_snapshot()
        (plan, bind), ckey = self.compiled(q_json, scored=needs_scores,
                                           with_key=True, prof=prof,
                                           iattrs=ia)
        needed = plan.arrays()
        k_want = from_ + size
        # with exact totals waived, block-max pruning may also skip
        # segments that cannot beat the running k-th score (the
        # reference's track_total_hits=false contract: totals become a
        # lower bound, flagged with relation "gte")
        allow_kth_prune = body.get("track_total_hits") is False

        rescore = body.get("rescore")
        collapse = body.get("collapse")
        if rescore and collapse:
            raise IllegalArgumentError(
                "cannot use [collapse] in conjunction with [rescore]")
        if rescore is not None:
            if sort_specs is not None:
                raise IllegalArgumentError(
                    "rescore is only supported on score-sorted queries")
            # widen the first pass to the rescore window
            spec = rescore[0] if isinstance(rescore, list) else rescore
            k_want = max(k_want, int(spec.get("window_size", 10)))

        aggs_json = body.get("aggs") or body.get("aggregations")
        # with aggs, the full-scores pass runs ONCE and feeds both the
        # top-k and the aggregations (no second device execution)
        views = (list(self._run_full(plan, bind, needed, min_score,
                                     deadline=deadline, ckey=ckey,
                                     prof=prof, iattrs=ia))
                 if aggs_json and self.segments else None)

        total_is_lower_bound = False
        if not self.segments:
            rows, total, max_score = [], 0, None
        elif collapse is not None:
            rows, total, max_score = self._collapsed(
                plan, bind, needed, k_want, sort_specs, min_score,
                collapse, views, search_after=search_after)
        elif sort_specs is None:
            if views is not None:
                with prof.phase("reduce"):
                    rows, total, max_score = self._topk_from_views(
                        views, k_want)
            else:
                rows, total, max_score, total_is_lower_bound = self._topk(
                    plan, bind, needed, k_want, min_score,
                    deadline=deadline, ckey=ckey,
                    allow_kth_prune=allow_kth_prune, prof=prof,
                    iattrs=ia)
        else:
            # host-side key build + comparator sort is the reduce share
            # (the segment scan's phases, recorded inside, are not)
            with prof.phase("reduce"):
                rows, total, max_score = self._field_sorted(
                    plan, bind, needed, k_want, sort_specs, min_score,
                    views, search_after=search_after, deadline=deadline,
                    ckey=ckey, prof=prof)
        if rescore is not None and rows:
            rows, max_score = self._rescored(rows, rescore)
        rows = rows[from_: from_ + size]

        aggregations = partials = None
        if aggs_json:
            from opensearch_tpu.search.aggs import AggregationExecutor
            seg_views = [(seg, dseg, matched)
                         for seg, dseg, _s, matched in (views or [])]
            scores_of = {seg.seg_id: s
                         for seg, _d, s, _m in (views or [])}
            execu = AggregationExecutor(self.ctx, scores_of=scores_of)
            if agg_partials:
                partials = execu.collect(aggs_json, seg_views)
            else:
                aggregations = execu.run(aggs_json, seg_views)

        with prof.phase("fetch"):
            hits = self._fetch(rows, source_spec, fetch_extras)

        took = int((time.monotonic() - t0) * 1000)
        xfer1 = _ledger().transfer_snapshot()
        insights.emit(
            signature=ckey[0] if ckey is not None else None,
            scored=needs_scores,
            took_ms=(time.monotonic() - t0) * 1000,
            execution_path=ia.get("execution_path", "device"),
            plan_cache=ia["plan_cache"],
            pruned=ia["pruned"], scanned=ia["scanned"],
            transfer_bytes=(xfer1[0] - xfer0[0]) + (xfer1[1] - xfer0[1]),
            timed_out=deadline.timed_out)
        resp = {
            "took": took,
            "timed_out": deadline.timed_out,
            "_shards": shards_section(1),
            "hits": {
                "total": {"value": int(total),
                          "relation": ("gte" if total_is_lower_bound
                                       else "eq")},
                "max_score": max_score,
                "hits": hits,
            },
        }
        if body.get("profile"):
            # real phase-attributed profile (search/profile/query/
            # QueryProfiler analog at program granularity: the device
            # runs fused programs, so per-collector callbacks don't
            # exist — phases are the host-side stages around them)
            resp["profile"] = {"shards": [prof.shard_section(
                self.index_name, self.shard_id,
                plan_type=type(plan).__name__,
                description=describe_plan(plan, bind),
                total_segments=len(self.segments))]}
        if aggregations is not None:
            resp["aggregations"] = aggregations
        if partials is not None:
            resp["aggregation_partials"] = partials
        if body.get("suggest"):
            from opensearch_tpu.search.suggest import run_suggest
            resp["suggest"] = run_suggest(body["suggest"], self.ctx)
            for entries in resp["suggest"].values():
                for entry in entries:
                    for opt in entry.get("options", ()):
                        if "_id" in opt and "_index" not in opt:
                            opt["_index"] = self.index_name
        return resp

    def _hybrid_search(self, body: dict, t0, fetch_extras=None) -> dict:
        """Hybrid query: each sub-query runs the shard's own plan and
        top-k path (``compiled`` with its plan cache, ``_topk``), one
        after the other, under a ``hybrid.subquery`` span each; the
        normalization processor (search/pipeline.py) combines the
        per-sub-query top lists host-side under ``hybrid.normalize``.
        ``_hybrid_pipeline`` in the body carries the processor config
        (wired by the REST layer from ?search_pipeline=...); absent ->
        min_max + arithmetic_mean.  A profiled request gets one profiler
        a sub-query."""
        from opensearch_tpu.common.errors import ValidationError
        from opensearch_tpu.search.pipeline import NormalizationConfig

        if (body.get("sort") is not None or body.get("aggs")
                or body.get("aggregations")
                or body.get("min_score") is not None
                or body.get("search_after") is not None):
            raise ValidationError(
                "[hybrid] query does not support [sort], [aggs], "
                "[min_score] or [search_after]")
        size = int(body.get("size", 10))
        from_ = int(body.get("from", 0))
        k_want = from_ + size
        deadline = SearchDeadline(body.get("timeout"), t0)
        conf = NormalizationConfig(body.get("_hybrid_pipeline"))
        profiled = bool(body.get("profile"))
        sections = []
        per_query_rows = []
        max_total = 0
        ia = {"plan_cache": "hit", "pruned": 0, "scanned": 0}
        for i, sub_json in enumerate(body["query"]["hybrid"]["queries"]):
            if deadline.expired():
                break            # partial: combine what completed
            prof = QueryProfiler() if profiled else NULL_PROFILER
            sub_ia = {"pruned": 0, "scanned": 0}
            with _tracer().start_span("hybrid.subquery", {"i": i}) as span:
                (plan, bind), ckey = self.compiled(
                    sub_json, scored=True, with_key=True, prof=prof,
                    iattrs=sub_ia)
                span.set_attribute("type", _plan_kind(plan))
                rows, tot, _mx, _lb = self._topk(
                    plan, bind, plan.arrays(), k_want, None,
                    deadline=deadline, ckey=ckey, prof=prof,
                    iattrs=sub_ia)
            _metrics().counter("search.hybrid.subqueries").inc()
            per_query_rows.append(rows)
            max_total = max(max_total, int(tot))
            if sub_ia.get("plan_cache") != "hit":
                ia["plan_cache"] = "miss"
            if sub_ia.get("execution_path") == "host":
                ia["execution_path"] = "host"
            ia["pruned"] += sub_ia["pruned"]
            ia["scanned"] += sub_ia["scanned"]
            if profiled:
                sections.append(prof.shard_section(
                    self.index_name, self.shard_id,
                    plan_type=type(plan).__name__,
                    description=describe_plan(plan, bind),
                    total_segments=len(self.segments)))
        t_norm = time.monotonic()
        with _tracer().start_span("hybrid.normalize",
                                  {"subqueries": len(per_query_rows)}):
            combined, n_union = conf.apply(per_query_rows, k_want)
        _metrics().counter("search.hybrid.requests").inc()
        _metrics().counter("search.hybrid.candidates").inc(n_union)
        rows = combined[from_: from_ + size]
        t_fetch = time.monotonic()
        hits = self._fetch(rows, body.get("_source"), fetch_extras)
        t_done = time.monotonic()
        insights.emit(
            signature=insights.canonical_query(body.get("query")),
            scored=True,
            took_ms=(t_done - t0) * 1000,
            execution_path=ia.get("execution_path", "device"),
            plan_cache=ia["plan_cache"],
            pruned=ia["pruned"], scanned=ia["scanned"],
            timed_out=deadline.timed_out)
        # per-sub-query top-k truncation means the union is a lower
        # bound beyond the largest sub-query's exact count
        resp = {
            "took": int((t_done - t0) * 1000),
            "timed_out": deadline.timed_out,
            "_shards": shards_section(1),
            "hits": {"total": {"value": max_total, "relation": "gte"},
                     "max_score": (combined[0]["score"] if combined
                                   else None),
                     "hits": hits},
        }
        if profiled:
            # one ``searches`` entry a sub-query, in request order; the
            # shard's ``engine`` block says where the whole request ran
            # and carries each sub-query's own attribution
            resp["profile"] = {"shards": [{
                "id": f"[{self.index_name}][{self.shard_id}]",
                "searches": [s["searches"][0] for s in sections],
                "engine": {
                    "execution_path": ia.get("execution_path", "device"),
                    "request_cache": "bypass",
                    "hybrid": {
                        "sub_queries": [s["engine"] for s in sections],
                        "normalization": conf.normalization,
                        "combination": conf.combination,
                        "candidates": n_union,
                        "normalize_time_in_nanos": int(
                            (t_fetch - t_norm) * 1e9),
                        "fetch_time_in_nanos": int(
                            (t_done - t_fetch) * 1e9)}},
            }]}
        return resp

    def msearch(self, bodies: list) -> list[dict]:
        """Multi-search (the ``_msearch`` analog): bodies that compile to a
        scored term-bag run as ONE batched device program per (field, k,
        segment) — Q queries per dispatch instead of Q dispatches (see
        search/batch.py); everything else runs the normal path.  Response
        order matches request order."""
        import time

        from opensearch_tpu.search.batch import plan_batches

        t0 = time.monotonic()
        if not self.segments:
            return [self.search(b) for b in bodies]
        groups, fallback = plan_batches(self, bodies)
        results: list = [None] * len(bodies)
        for g in groups.values():
            # ONE profiler per coalesced group: members share the
            # group's phase timings by construction (that sharing IS
            # the batch-coalescing attribution)
            gprof = (QueryProfiler() if any(
                (bodies[p] or {}).get("profile") for p in g.positions)
                else NULL_PROFILER)
            # members were parsed/compiled during batch planning
            # (through the plan cache, counted in the
            # search.plan_cache.* metrics) — per-member hit/miss is
            # not attributable after coalescing
            gprof.set("plan_cache", "batched")
            gprof.set("batch", {
                "field": g.field, "k": g.k,
                "queries": len(g.positions),
                "positions": list(g.positions)})
            xfer0 = _ledger().transfer_snapshot()
            g_out = g.run(self, prof=gprof)
            xfer1 = _ledger().transfer_snapshot()
            # ONE batched pass served the whole group: its transfer
            # bytes are shared group attribution, like last_stats
            g_xfer = (xfer1[0] - xfer0[0]) + (xfer1[1] - xfer0[1])
            for pos, (rows, total, max_score) in g_out.items():
                body = bodies[pos] or {}
                with gprof.phase("fetch"):
                    hits = self._hits_from_rows(rows, body.get("_source"))
                # batched bodies never carry a [timeout] (plan_batches
                # sends those to the sequential fallback, which owns the
                # deadline checks), so false is exact here
                results[pos] = {
                    "took": int((time.monotonic() - t0) * 1000),
                    "timed_out": False,
                    "_shards": shards_section(1),
                    "hits": {"total": {"value": int(total),
                                       "relation": "eq"},
                             "max_score": max_score, "hits": hits},
                }
                # one insight record per coalesced member: its OWN plan
                # signature (members of a (field, k) group still differ
                # by terms) + the group size — the measured coalescing
                # the continuous batcher's sizing report aggregates
                insights.emit(
                    signature=insights.canonical_query(
                        body.get("query")),
                    scored=True,
                    took_ms=(time.monotonic() - t0) * 1000,
                    execution_path=g.last_stats["path"],
                    plan_cache="batched",
                    pruned=g.last_stats["pruned"],
                    scanned=g.last_stats["scanned"],
                    transfer_bytes=g_xfer,
                    batched=len(g.positions))
                if body.get("profile"):
                    results[pos]["profile"] = {"shards": [
                        gprof.shard_section(
                            self.index_name, self.shard_id,
                            plan_type="TermBagPlan",
                            description=(f"batched[{g.field}] "
                                         f"member {pos} of "
                                         f"{len(g.positions)}"),
                            total_segments=len(self.segments))]}
        if len(fallback) > 1:
            # non-coalescable members fan out over the engine's bounded
            # search threadpool instead of serializing behind one
            # request thread (overflow runs inline, same semantics)
            from opensearch_tpu.search.engine import query_engine
            outs = query_engine().pool.run_all(
                [(lambda b=bodies[pos]: self.search(b))
                 for pos in fallback])
            for pos, r in zip(fallback, outs):
                results[pos] = r
        else:
            for pos in fallback:
                results[pos] = self.search(bodies[pos])
        return results

    def _fetch(self, rows, source_spec, fetch_extras):
        """The fetch phase under its ``fetch_phase`` span, whose own
        duration is histogram ``search.fetch_ms``'s sample."""
        scope = _tracer().start_span("fetch_phase",
                                     {"index": self.index_name,
                                      "hits": len(rows)})
        try:
            with scope:
                return self._hits_from_rows(rows, source_spec, fetch_extras)
        finally:
            _metrics().histogram("search.fetch_ms").observe(
                scope.span.duration_nanos / 1e6)

    def _hits_from_rows(self, rows, source_spec, fetch_extras=None):
        from opensearch_tpu.search.fetch import (docvalue_fields,
                                                 explain_hit,
                                                 fields_option,
                                                 run_highlight)

        hits = []
        for row in rows:
            seg = self.segments[row["seg"]]
            local = row["local"]
            hit = {"_index": self.index_name, "_id": seg.doc_ids[local],
                   "_score": row.get("score")}
            source = seg.source(local)
            src = filter_source(source, source_spec)
            if src is not None:
                hit["_source"] = src
            if "sort" in row:
                hit["sort"] = row["sort"]
            if "fields" in row:            # collapse key et al.
                hit["fields"] = dict(row["fields"])
            if fetch_extras is not None:
                if fetch_extras.get("highlight"):
                    hl = run_highlight(fetch_extras["highlight"], source,
                                       fetch_extras["query"], self.mapper)
                    if hl:
                        hit["highlight"] = hl
                fields = {}
                if fetch_extras.get("docvalue_fields"):
                    fields.update(docvalue_fields(
                        fetch_extras["docvalue_fields"], seg, local,
                        self.mapper))
                if fetch_extras.get("fields"):
                    fields.update(fields_option(fetch_extras["fields"],
                                                source))
                if fields:
                    hit["fields"] = fields
                if fetch_extras.get("explain"):
                    hit["_explanation"] = explain_hit(
                        row.get("score"), fetch_extras["query"], seg,
                        local, self.ctx)
            hits.append(hit)
        return hits

    # -- internals --------------------------------------------------------

    def _pruned(self, plan, bind, seg, ms_host, kth, iattrs, clock):
        """Why ``seg`` gets no program, or None where it must be
        scored: no query term occurs in it (``pruned_can_match``); its
        block-max bound is under ``min_score`` (``pruned_min_score``,
        exact: docs below min_score never count in totals); its bound
        cannot beat the running k-th score (``pruned_kth``: the k-th
        holder dispatched earlier, so it wins any tie at exactly the
        bound by the seg-asc tie-break, and totals become a lower
        bound).  A pruned segment is counted here, for every caller, and
        given to the profiler's ``clock``."""
        reason = self._prune_reason(plan, bind, seg, ms_host, kth)
        if reason is not None:
            _metrics().counter("search.segments_pruned").inc()
            iattrs["pruned"] += 1
            clock.pruned(reason)
        return reason

    @staticmethod
    def _prune_reason(plan, bind, seg, ms_host, kth):
        """``_pruned``'s reason, uncounted."""
        if not plan.can_match(bind, seg):
            return "pruned_can_match"
        if ms_host is not None or kth is not None:
            bound = plan.max_score_bound(bind, seg)
            if ms_host is not None and bound < ms_host:
                return "pruned_min_score"
            if kth is not None and bound <= kth:
                return "pruned_kth"
        return None

    def _score_on_host(self, plan, bind, seg, k_want, min_score, why,
                       exc=None):
        """The one recovery route of a segment the device cannot serve
        (``why``: breaker open, segment evicted, a device error, a
        non-finite result): the host impact-table scorer, byte-identical
        to the device kernel (the PR-5 invariant), so a fault never
        changes results, only where they are computed.  Counted as one
        host fallback.  A plan without a host scorer degrades into the
        caller's partial ``_shards.failures[]`` instead."""
        if not _host_capable(plan):
            raise _degraded(plan, why, exc) from exc
        _ledger().record_host_fallback()
        return plan.host_topk(  # engine-ok: the recovery backend
            bind, seg, self.ctx.lives[id(seg)],
            min(k_want, seg.n_docs), min_score)

    def _run_full(self, plan, bind, needed, min_score,
                  can_match_skip=False, deadline=None, ckey=None,
                  prof=NULL_PROFILER, iattrs=UNKEPT):
        """``can_match_skip`` is ONLY safe for consumers that don't index
        the yielded tuples by position (views/aggs paths align with
        self.segments and must see every segment).  An expired
        ``deadline`` stops the scan at the next segment boundary — the
        same granularity as cancellation."""
        from opensearch_tpu.common.device_health import is_device_error
        from opensearch_tpu.common.tasks import check_current

        health = _health()
        if not (health.allow("dispatch") and health.allow("staging")):
            # full-scores plans have no host fallback: while the device
            # breaker is open they degrade into PR-2-style partial
            # _shards.failures[] at the caller instead of dispatching
            # onto a failing accelerator (or returning a 500)
            raise _degraded(plan, "device circuit breaker open, "
                                  "full-scores pass")
        ms = _min_score_scalar(min_score)
        for seg in self.segments:
            check_current()        # cancellation point per segment program
            if deadline is not None and deadline.expired():
                return
            clock = prof.segment(seg.seg_id)
            if can_match_skip and self._pruned(plan, bind, seg, None, None,
                                               iattrs, clock):
                continue
            with _tracer().start_span(
                    "segment.dispatch",
                    {"segment": seg.seg_id, "index": self.index_name,
                     "shard": self.shard_id}) as span:
                try:
                    dseg, dims, ins, A = self.segment_inputs(
                        plan, bind, seg, needed, ckey, prof)
                    with span.part("launch"):
                        scores, matched = P.run_full(plan, dims, A, ins,
                                                     ms)
                except Exception as exc:
                    if not is_device_error(exc):
                        raise
                    # counted via record_failure -> device.errors (and
                    # device.restage_failures at the staging site)
                    health.record_failure("dispatch", exc)
                    raise _degraded(
                        plan, f"device failure on segment [{seg.seg_id}], "
                              "full-scores pass", exc) from exc
            health.record_success("dispatch")
            P.book(P.choose(plan, dims, dseg.n_pad), dseg, ins)
            iattrs["scanned"] += 1
            clock.scanned()
            yield seg, dseg, scores, matched

    def _merge_topk(self, per_seg, k_want, total, max_score):
        from opensearch_tpu.common.tasks import charge_current

        if not per_seg:
            return [], 0, None
        scores = np.concatenate([p[0] for p in per_seg])
        segi = np.concatenate([p[1] for p in per_seg])
        local = np.concatenate([p[2] for p in per_seg])
        # the host-side merge buffers are this task's transient heap:
        # charged to the request breaker (released at task unregister)
        # so the backpressure service can rank queries by real cost
        charge_current(scores.nbytes + segi.nbytes + local.nbytes,
                       "search top-k merge")
        order = np.lexsort((local, segi, -scores))[:k_want]
        rows = [{"seg": int(segi[i]), "local": int(local[i]),
                 "score": float(scores[i])} for i in order]
        return rows, total, (None if max_score == -np.inf else float(max_score))

    def _topk(self, plan, bind, needed, k_want, min_score, deadline=None,
              ckey=None, allow_kth_prune=False, prof=NULL_PROFILER,
              iattrs=UNKEPT):
        """Returns (rows, total, max_score, total_is_lower_bound).

        Block-max pruning: segments whose ``plan.max_score_bound`` can't
        reach ``min_score`` are skipped exactly (such docs are excluded
        from hits AND totals anyway).  With ``allow_kth_prune`` (the
        request waived exact totals via track_total_hits=false),
        segments that can't beat the running k-th score are skipped too
        — the k-th score is harvested from programs that already
        finished, never blocking the async dispatch pipeline.

        Every backend runs the one lowering, ``P.run_topk``, as
        ``P.choose`` finds it and ``P.book`` books it.  A segment leaves
        it for ``_score_on_host`` only on what this loop observes: the
        device breaker not allowing, the segment evicted under the
        device budget, a device error at dispatch or at sync, a
        non-finite result (``_sync_topk``).  ``execution_path``: ``host``
        where every scored segment was recovered."""
        from opensearch_tpu.common.device_health import is_device_error
        from opensearch_tpu.common.tasks import check_current

        if k_want == 0:            # size=0: counts only (aggs-style request)
            with prof.phase("reduce"):
                total = self._matched(plan, bind, needed, min_score,
                                      deadline=deadline, ckey=ckey,
                                      prof=prof, iattrs=iattrs)
            return [], total, None, False

        # phase 1: DISPATCH every segment's program without a host sync —
        # jax's async dispatch runs them back to back on the device while
        # the host prepares the next segment (the concurrent-segment-
        # search answer in the XLA model; ref search/query/
        # ConcurrentQueryPhaseSearcher.java gets the same overlap from
        # slice threads)
        health = _health()
        ms = _min_score_scalar(min_score)
        ms_host = None if min_score is None else float(min_score)
        if hasattr(plan, "prefetch_quantized"):
            # pager prefetch oracle: best-bound-first staging of
            # quantized pages into FREE capacity before the dispatch
            # loop.  Best-effort by construction — a prefetch failure
            # surfaces (and is handled) at the segment's own dispatch
            try:
                plan.prefetch_quantized(bind, self.segments)
            except Exception:
                pass
        stacked = None
        if isinstance(plan, P.TermBagPlan) and plan.scored:
            if not allow_kth_prune:
                # one staged input a request; a running k-th prunes after
                # the bind, so that request stages one a segment
                stacked = _StackedBag(self, plan, bind, ckey, prof, ms_host)
            if plan.features:
                # where the bound plan runs, so a plan-cache hit counts
                # too; a feature bag under a bool is not seen here
                _metrics().counter("search.neural_sparse.requests").inc()
                _metrics().counter("search.neural_sparse.query_tokens").inc(
                    len(bind["terms"]))
        phrase_slots = 0           # a request's phrase slots, of one program
        # [si, out]: a recovered segment's (vals, idx, tot, mx), or the
        # device's packed result (P.run_topk), its copy to the host
        # under way
        launched = []
        recovered = 0              # of them, scored on the host
        kth = None                 # running k-th best (harvested, host)
        total_is_lower_bound = False
        for si, seg in enumerate(self.segments):
            check_current()        # cancellation point per segment program
            if deadline is not None and deadline.expired():
                break              # partial top-k; response flags timed_out
            clock = prof.segment(seg.seg_id)
            reason = self._pruned(plan, bind, seg, ms_host, kth, iattrs,
                                  clock)
            if reason is not None:     # no staging, no program
                total_is_lower_bound |= reason == "pruned_kth"
                continue
            clock.decided()
            with _tracer().start_span(
                    "segment.dispatch",
                    {"segment": seg.seg_id, "index": self.index_name,
                     "shard": self.shard_id}) as span:
                device_ok = (health.allow("dispatch")
                             and health.allow("staging"))
                if not device_ok or (getattr(seg, "_device_evicted", False)
                                     and _host_capable(plan)):
                    # an evicted segment of a plan without a host scorer
                    # restages below; behind an open breaker it degrades
                    out = self._score_on_host(
                        plan, bind, seg, k_want, min_score,
                        "device circuit breaker open")
                else:
                    try:
                        dseg, dims, ins, A = self.segment_inputs(
                            plan, bind, seg, needed, ckey, prof,
                            stacked=stacked)
                        k = min(k_want, dseg.n_pad)
                        program = P.choose(plan, dims, dseg.n_pad, k,
                                           self.ctx.all_live(seg))
                        # the part: the jitted program's call, to its
                        # return, and the start of the result's copy
                        # (phase 2 then finds it on the host)
                        with span.part("launch"):
                            out = P.run_topk(plan, dims, k, A, ins, ms,
                                             sorted_bag=program.sorted_bag)
                            out.copy_to_host_async()
                        P.book(program, dseg, ins)
                        if program.phrases:
                            phrase_slots = sum(
                                pd.slots for pd in program.phrases)
                    except Exception as exc:
                        if not is_device_error(exc):
                            raise
                        # counted: record_failure -> device.errors (the
                        # staging site also counts restage_failures);
                        # THIS segment degrades, the breaker decides
                        # whether later segments even try the device
                        health.record_failure("dispatch", exc)
                        out = self._score_on_host(
                            plan, bind, seg, k_want, min_score,
                            f"device failure on segment [{seg.seg_id}]",
                            exc)
                recovered += isinstance(out, tuple)
                launched.append([si, out])
            iattrs["scanned"] += 1
            clock.scanned()
            if allow_kth_prune and si + 1 < len(self.segments):
                kth = self._harvest_kth(launched, k_want, kth)
        if phrase_slots:
            _metrics().counter("search.phrase.requests").inc()
            _metrics().counter("search.phrase.slots").inc(phrase_slots)
        return (*self._sync_topk(plan, bind, launched, recovered, k_want,
                                 min_score, health, prof, iattrs),
                total_is_lower_bound)

    def _sync_topk(self, plan, bind, launched, recovered, k_want,
                   min_score, health, prof, iattrs):
        """``_topk``'s phase 2: ONE host-sync region over every launched
        segment's result, and the merge: (rows, total, max_score).  Also
        the result-sanity guard: non-finite device scores are poison (a
        misbehaving accelerator, not a query property); they are
        discarded, recomputed on the host byte-identically, and filed as
        flight-recorder evidence."""
        from opensearch_tpu.common.device_health import (check_finite,
                                                         is_device_error)

        with prof.phase("reduce"):
            t_sync = time.monotonic()
            per_seg = []
            total = 0
            max_score = -np.inf
            fetched_bytes = fetched_arrays = 0
            # the span only where a device result is read back: a
            # recovered segment left numpy arrays
            with (_tracer().start_span("device.sync", {"site": "topk"},
                                       cpu=True)
                  if recovered < len(launched)
                  else contextlib.nullcontext()):
                for si, out in launched:
                    if not isinstance(out, tuple):   # ONE D2H read
                        seg = self.segments[si]
                        bad, fault = 0, None
                        try:
                            packed = np.asarray(out)
                            out = P.unpack_topk(packed)
                            bad = check_finite(out[0])
                        except Exception as exc:   # fault surfaced at sync
                            if not is_device_error(exc):
                                raise
                            health.record_failure("dispatch", exc)
                            fault = exc
                        if bad:
                            health.record_poison(
                                kernel="run_topk", segment=seg.seg_id,
                                index=self.index_name, shard=self.shard_id,
                                bad=bad)
                        if bad or fault is not None:
                            out = self._score_on_host(
                                plan, bind, seg, k_want, min_score,
                                ("non-finite device scores on" if bad
                                 else "device failure syncing")
                                + f" segment [{seg.seg_id}]", fault)
                            recovered += 1
                        else:
                            health.record_success("dispatch")
                            fetched_bytes += packed.nbytes
                            fetched_arrays += 1
                    vals, idx, tot, mx = out
                    vals = np.asarray(vals)
                    idx = np.asarray(idx)
                    keep = vals > -np.inf
                    per_seg.append((vals[keep],
                                    np.full(int(keep.sum()), si, _I32),
                                    idx[keep]))
                    total += int(tot)
                    max_score = max(max_score, float(mx))
            if fetched_arrays:
                _ledger().record_fetch(fetched_bytes,
                                       time.monotonic() - t_sync,
                                       arrays=fetched_arrays)
            path = ("host" if launched and recovered == len(launched)
                    else "device")
            iattrs["execution_path"] = path
            prof.set("execution_path", path)
            return self._merge_topk(per_seg, k_want, total, max_score)

    @staticmethod
    def _harvest_kth(launched, k_want, kth):
        """Update the running k-th best score from programs that ALREADY
        finished — ``is_ready()`` results live on the host, so reading
        them never blocks the dispatch pipeline (the MaxScore running
        threshold, fed at async-dispatch granularity)."""
        ready = []
        for entry in launched:
            out = entry[1]
            if isinstance(out, tuple):             # a host path's result
                ready.append(np.asarray(out[0]))
                continue
            if not isinstance(out, np.ndarray):
                if not out.is_ready():
                    continue
                # read once; phase 2's np.asarray of it is then free
                out = entry[1] = np.asarray(out)   # sync-ok (is_ready)
            ready.append(P.unpack_topk(out)[0])
        if not ready:
            return kth
        vals = np.concatenate(ready).ravel()
        vals = vals[vals > -np.inf]
        if len(vals) < k_want:
            return kth
        cand = float(np.partition(vals, -k_want)[-k_want])  # sync-ok
        return cand if kth is None or cand > kth else kth

    def _topk_from_views(self, views, k_want):
        """Top-k out of an already-run full-scores pass (aggs requests)."""
        if k_want == 0:
            return [], sum(int(np.asarray(matched).sum())
                           for _seg, _dseg, _scores, matched in views), None
        launched = []              # a packed result a view, its copy under way
        for _seg, dseg, scores, matched in views:
            packed = P.topk_from_scores(scores, min(k_want, dseg.n_pad),
                                        matched)
            packed.copy_to_host_async()
            launched.append(packed)
        per_seg = []
        total = 0
        max_score = -np.inf
        t_sync = time.monotonic()
        fetched_bytes = 0
        for si, packed in enumerate(launched):
            packed = np.asarray(packed)
            vals, idx, tot, mx = P.unpack_topk(packed)
            fetched_bytes += packed.nbytes
            keep = vals > -np.inf
            per_seg.append((vals[keep], np.full(int(keep.sum()), si, _I32),
                            idx[keep]))
            total += tot
            max_score = max(max_score, mx)
        if launched:
            _ledger().record_fetch(fetched_bytes, time.monotonic() - t_sync,
                                   arrays=len(launched))
        return self._merge_topk(per_seg, k_want, total, max_score)

    def _sort_key_columns(self, seg, spec, scores_np):
        """Per-doc sort key for one segment + one sort clause.  Returns
        (keys ndarray or list, is_numeric)."""
        field, order = spec["field"], spec["order"]
        if field == "_score":
            return scores_np.astype(np.float64), True
        if field == "_doc":
            return np.arange(seg.n_docs, dtype=np.int64), True
        ft = self.mapper.field_type(field)
        if ft is None:
            raise IllegalArgumentError(f"No mapping found for [{field}] in order to sort on")
        if ft.dv_kind in ("long", "double"):
            dv = seg.numeric_dv.get(field)
            if dv is None:
                sentinel = _missing_sentinel(ft.dv_kind, order, spec["missing"])
                return np.full(seg.n_docs, sentinel,
                               np.int64 if ft.dv_kind == "long" else np.float64), True
            keys = (dv.minv if order == "asc" else dv.maxv).copy()
            missing = ~dv.exists
            keys[missing] = _missing_sentinel(ft.dv_kind, order, spec["missing"])
            return keys, True
        if ft.dv_kind == "ordinal":
            dv = seg.ordinal_dv.get(field)
            out = []
            for i in range(seg.n_docs):
                if dv is None or not dv.exists[i]:
                    out.append(None)
                else:
                    o = dv.min_ord[i] if order == "asc" else dv.max_ord[i]
                    out.append(dv.ord_terms[o])
            return out, False
        raise IllegalArgumentError(
            f"sorting on field [{field}] of type [{ft.type_name}] is not supported")

    def _field_sorted(self, plan, bind, needed, k_want, sort_specs, min_score,
                      views=None, row_filter=None, search_after=None,
                      deadline=None, ckey=None, prof=NULL_PROFILER):
        """``k_want=None`` returns EVERY matched row (scroll
        materialization); ``row_filter(seg_i, local)`` implements sliced
        scans; ``search_after`` drops rows at-or-before the given sort
        tuple (PIT pagination)."""
        rows = []
        total = 0
        if views is None:
            views = self._run_full(plan, bind, needed, min_score,
                                   deadline=deadline, ckey=ckey,
                                   prof=prof)
        for si, (seg, dseg, scores, matched) in enumerate(views):
            matched_np = np.asarray(matched)[: seg.n_docs]
            scores_np = np.asarray(scores)[: seg.n_docs]
            idxs = np.nonzero(matched_np)[0]
            if row_filter is not None and len(idxs):
                keep = np.fromiter((row_filter(si, int(i)) for i in idxs),
                                   bool, count=len(idxs))
                idxs = idxs[keep]
            # total reflects THIS cursor's doc set: a slice reports the
            # slice's count, not the whole match count
            total += len(idxs)
            if len(idxs) == 0:
                continue
            key_cols = [self._sort_key_columns(seg, spec, scores_np)
                        for spec in sort_specs]
            for i in idxs:
                keyvals = []
                for (col, _num), spec in zip(key_cols, sort_specs):
                    keyvals.append(col[int(i)])
                rows.append({"seg": si, "local": int(i), "sort": keyvals,
                             "score": float(scores_np[i])})
        cmp = _sort_comparator(sort_specs)
        rows.sort(key=functools.cmp_to_key(cmp))
        if search_after is not None:
            coerced = []
            for v, spec in zip(search_after, sort_specs):
                ft = (None if spec["field"] == "_score"
                      else self.ctx.field_type(spec["field"]))
                if ft is not None and isinstance(v, str) \
                        and ft.dv_kind in ("long", "double"):
                    # date strings etc. compare in COLUMN space
                    v = ft.range_bound(v)
                coerced.append(v)
            probe = {"sort": coerced, "seg": _I32_MAX,
                     "local": _I32_MAX}
            rows = [r for r in rows if cmp(r, probe) > 0]
        out = []
        nanos_mult = [1_000_000 if (spec["field"] != "_score"
                                    and getattr(self.ctx.field_type(
                                        spec["field"]), "type_name", "")
                                    == "date_nanos") else None
                      for spec in sort_specs]
        for row in rows[:k_want]:
            vals = []
            for v, mult in zip(row["sort"], nanos_mult):
                sv = _sort_value(v)
                # date_nanos sort keys render in NANOS (the reference's
                # resolution-aware sort serialization)
                vals.append(sv * mult if mult and isinstance(
                    sv, int) else sv)
            out.append({"seg": row["seg"], "local": row["local"],
                        "score": None, "sort": vals})
        return out, total, None

    def _rescored(self, rows, rescore):
        """Query rescorer (search/rescore/QueryRescorer): re-rank the top
        window by combining the original score with a rescore query's
        score for those docs; tail rows keep their order."""
        spec = rescore[0] if isinstance(rescore, list) else rescore
        q = spec.get("query") or {}
        window = int(spec.get("window_size", 10))
        rq_json = q.get("rescore_query")
        if rq_json is None:
            raise IllegalArgumentError(
                "[rescore] requires [query.rescore_query]")
        qw = float(q.get("query_weight", 1.0))
        rw = float(q.get("rescore_query_weight", 1.0))
        mode = str(q.get("score_mode", "total"))
        rplan, rbind = self.compiled(rq_json, scored=True)
        rneeded = rplan.arrays()
        # per-segment rescore scores, read only at the window's docs
        seg_scores: dict[int, np.ndarray] = {}
        seg_matched: dict[int, np.ndarray] = {}
        window_rows = rows[:window]
        segs_needed = {r["seg"] for r in window_rows}
        for si, (seg, dseg, scores, matched) in enumerate(
                self._run_full(rplan, rbind, rneeded, None)):
            if si in segs_needed:
                seg_scores[si] = np.asarray(scores)
                seg_matched[si] = np.asarray(matched)
        combine = {"total": lambda a, b: a + b,
                   "multiply": lambda a, b: a * b,
                   "avg": lambda a, b: (a + b) / 2.0,
                   "max": max, "min": min}.get(mode)
        if combine is None:
            raise IllegalArgumentError(
                f"unknown rescore score_mode [{mode}]")
        out = []
        for r in window_rows:
            base = qw * (r.get("score") or 0.0)
            if seg_matched.get(r["seg"]) is not None and \
                    seg_matched[r["seg"]][r["local"]]:
                rs = rw * float(seg_scores[r["seg"]][r["local"]])
                new = combine(base, rs)
            else:
                new = base       # unmatched docs keep the weighted base
            out.append({**r, "score": new})
        out.sort(key=lambda r: (-r["score"], r["seg"], r["local"]))
        out.extend(rows[window:])
        max_score = out[0]["score"] if out else None
        return out, max_score

    def _collapsed(self, plan, bind, needed, k_want, sort_specs,
                   min_score, collapse, views, search_after=None):
        """Field collapsing (search/collapse/): one hit per distinct
        value of the collapse field — the best-ranked in result order."""
        field = collapse.get("field") if isinstance(collapse, dict) \
            else None
        if not field:
            raise IllegalArgumentError("[collapse] requires a [field]")
        ft = self.ctx.field_type(field)
        if ft is None or ft.dv_kind not in ("long", "double", "ordinal"):
            raise IllegalArgumentError(
                f"cannot collapse on [{field}]: keyword or numeric doc "
                "values required")
        if sort_specs is not None:
            ordered, total, _ = self._field_sorted(
                plan, bind, needed, None, sort_specs, min_score, views,
                search_after=search_after)
        elif views is not None:
            # an aggs pass already ran the full query: rank from it
            # instead of a second device execution
            ordered, total = self._rows_from_views(views)
        else:
            ordered, total = self.scan_rows(
                {"query": None, "min_score": min_score}, None,
                _precompiled=(plan, bind, needed))
        seen: set = set()
        out = []
        for r in ordered:
            seg = self.segments[r["seg"]]
            key = self._collapse_key(seg, field, ft, r["local"])
            if key in seen:
                continue
            seen.add(key)
            out.append({**r, "fields": {field: [key]}})
            if len(out) >= k_want:
                break
        max_score = (out[0].get("score") if out and sort_specs is None
                     else None)
        return out, total, max_score

    def _rows_from_views(self, views):
        """All matched rows in (score desc, seg, local) order out of an
        already-run full-scores pass."""
        per_scores, per_ids = [], []
        total = 0
        for si, (seg, dseg, scores, matched) in enumerate(views):
            m = np.asarray(matched)[: seg.n_docs]
            s = np.asarray(scores)[: seg.n_docs]
            idxs = np.nonzero(m)[0]
            total += len(idxs)
            per_scores.append(s[idxs])
            per_ids.append((np.full(len(idxs), si, np.int32), idxs))
        if not per_scores:
            return [], 0
        sc = np.concatenate(per_scores)
        segi = np.concatenate([a for a, _l in per_ids])
        local = np.concatenate([l for _a, l in per_ids])
        order = np.lexsort((local, segi, -sc))
        return [{"seg": int(segi[i]), "local": int(local[i]),
                 "score": float(sc[i])} for i in order], total

    @staticmethod
    def _collapse_key(seg, field, ft, local):
        ndv = seg.numeric_dv.get(field)
        if ndv is not None and ndv.exists[local]:
            v = ndv.minv[local]
            return int(v) if ft.dv_kind == "long" else float(v)
        odv = seg.ordinal_dv.get(field)
        if odv is not None and odv.exists[local] and \
                odv.min_ord[local] >= 0:
            return odv.ord_terms[int(odv.min_ord[local])]
        return None                      # missing values collapse together

    def scan_rows(self, body: Optional[dict] = None, slice_spec=None,
                  _precompiled=None):
        """Materialize EVERY matched row in result order (scroll-context
        creation; SliceBuilder partition via ``slice_spec``).  Returns
        (rows, total) where rows carry seg/local/score/sort."""
        from opensearch_tpu.search.contexts import slice_filter

        body = body or {}
        pred = slice_filter(slice_spec)
        sort_specs = _parse_sort(body.get("sort"))
        min_score = body.get("min_score")
        if _precompiled is not None:
            plan, bind, needed = _precompiled
        else:
            needs_scores = sort_specs is None or min_score is not None \
                or any(s["field"] == "_score" for s in sort_specs)
            plan, bind = self.compiled(body.get("query"),
                                       scored=needs_scores)
            needed = plan.arrays()
        if not self.segments:
            return [], 0
        if sort_specs is not None:
            rows, total, _ = self._field_sorted(
                plan, bind, needed, None, sort_specs, min_score,
                row_filter=pred)
            return rows, total
        per_seg_scores, per_seg_ids = [], []
        total = 0
        for si, (seg, dseg, scores, matched) in enumerate(
                self._run_full(plan, bind, needed, min_score)):
            m = np.asarray(matched)[: seg.n_docs]
            s = np.asarray(scores)[: seg.n_docs]
            idxs = np.nonzero(m)[0]
            if pred is not None and len(idxs):
                keep = np.fromiter((pred(si, int(i)) for i in idxs), bool,
                                   count=len(idxs))
                idxs = idxs[keep]
            total += len(idxs)     # the slice's own count (see above)
            per_seg_scores.append(s[idxs])
            per_seg_ids.append((np.full(len(idxs), si, np.int32), idxs))
        if not per_seg_scores:
            return [], 0
        sc = np.concatenate(per_seg_scores)
        segi = np.concatenate([a for a, _l in per_seg_ids])
        local = np.concatenate([l for _a, l in per_seg_ids])
        order = np.lexsort((local, segi, -sc))
        rows = [{"seg": int(segi[i]), "local": int(local[i]),
                 "score": float(sc[i])} for i in order]
        # full-materialization cost (scroll creation) attributed to the
        # owning task — the rows themselves move to the ScrollContext's
        # own breaker reservation when a context adopts them
        from opensearch_tpu.common.tasks import charge_current
        charge_current(len(rows) * 96, "scan rows")
        return rows, total


def _missing_sentinel(kind, order, missing):
    if missing not in ("_last", "_first"):
        return int(missing) if kind == "long" else float(missing)
    last = missing == "_last"
    if kind == "long":
        big, small = LONG_MISSING_MAX, LONG_MISSING_MIN
    else:
        big, small = np.inf, -np.inf
    if order == "asc":
        return big if last else small
    return small if last else big


def _cmp_values(a, b, order: str, missing: str) -> int:
    if a is None or b is None:
        if a is None and b is None:
            return 0
        none_first = (missing == "_first")
        if a is None:
            return -1 if none_first else 1
        return 1 if none_first else -1
    if a == b:
        return 0
    lt = a < b
    if order == "desc":
        lt = not lt
    return -1 if lt else 1


def _sort_comparator(specs):
    def cmp(r1, r2):
        for i, spec in enumerate(specs):
            c = _cmp_values(r1["sort"][i], r2["sort"][i], spec["order"],
                           spec["missing"])
            if c:
                return c
        if r1["seg"] != r2["seg"]:
            return -1 if r1["seg"] < r2["seg"] else 1
        return -1 if r1["local"] < r2["local"] else (0 if r1["local"] == r2["local"] else 1)
    return cmp


def scatter_merge(searchers: list, body: dict) -> dict:
    """Every shard's searcher answers on its own path (its own scoring
    statistics: query_then_fetch) and the top hits merge in the
    coordinator's order, with aggregation partials reduced, suggestions
    merged and, for a profiled request, each shard's profile kept: the
    host's scatter-gather, the mesh's counted fallback."""
    from opensearch_tpu.search.aggs import reduce_aggs
    from opensearch_tpu.search.suggest import merge_suggest

    t0 = time.monotonic()
    size = int(body.get("size", 10)
               if body.get("size") is not None else 10)
    from_ = int(body.get("from", 0) or 0)
    aggs_json = body.get("aggs") or body.get("aggregations")
    sub = dict(body)
    sub["from"] = 0
    sub["size"] = from_ + size
    shard_resps = [s.search(sub, agg_partials=bool(aggs_json))
                   for s in searchers]
    rows = []
    total = 0
    max_score = None
    for si, r in enumerate(shard_resps):
        for pos, h in enumerate(r["hits"]["hits"]):
            rows.append((h, si, pos))
        total += r["hits"]["total"]["value"]
        ms_ = r["hits"]["max_score"]
        if ms_ is not None and (max_score is None or ms_ > max_score):
            max_score = ms_
    all_hits = merge_hit_rows(rows, body.get("sort"))
    resp = {
        "took": int((time.monotonic() - t0) * 1000),
        "timed_out": any(r.get("timed_out") for r in shard_resps),
        "hits": {"total": {"value": total, "relation": "eq"},
                 "max_score": max_score,
                 "hits": all_hits[from_: from_ + size]},
    }
    if aggs_json:
        resp["aggregations"] = reduce_aggs(
            aggs_json, [r.get("aggregation_partials") or {}
                        for r in shard_resps])
    if body.get("suggest"):
        resp["suggest"] = merge_suggest(
            [r.get("suggest") for r in shard_resps])
    if body.get("profile"):
        resp["profile"] = {"shards": [
            sh for r in shard_resps
            for sh in (r.get("profile") or {}).get("shards", [])]}
    return resp


def merge_hit_rows(rows, sort_json):
    """Coordinator-side merge of per-source sorted hit lists — the
    SearchPhaseController.sortDocs analog shared by the cluster
    scatter-gather and the REST multi-index merge.

    ``rows``: list of ``(hit, source_ordinal, position)`` where hits from
    each source arrive already sorted and position is the hit's rank
    within its source.  Without a sort clause, merges by
    (score desc, source, position); with one, merges by the hits' sort
    keys with (source, position) as the tie-break.  Returns hits in
    merged order.
    """
    import functools

    specs = _parse_sort(sort_json)
    if specs is None:
        rows = sorted(rows, key=lambda t: (-(t[0]["_score"] or 0.0),
                                           t[1], t[2]))
    else:
        cmp = _sort_comparator(specs)
        rows = sorted(rows, key=functools.cmp_to_key(
            lambda a, b: cmp({"sort": a[0].get("sort", []),
                              "seg": a[1], "local": a[2]},
                             {"sort": b[0].get("sort", []),
                              "seg": b[1], "local": b[2]})))
    return [h for h, _s, _p in rows]


def _sort_value(v):
    if v is None:
        return None
    if isinstance(v, (np.integer,)):
        return int(v)
    if isinstance(v, (np.floating,)):
        return float(v)
    return v
