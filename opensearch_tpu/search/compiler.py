"""Query tree -> (plan, bindings) against a shard's mapping + collection
statistics.

Analog of ``QueryBuilder.toQuery(QueryShardContext)``
(ref index/query/QueryShardContext.java:95) plus the Lucene Weight
construction it triggers: idf/avgdl are computed here from CROSS-SEGMENT
stats (Lucene computes them in IndexSearcher.termStatistics over the whole
reader, not per leaf), so scores are consistent across segments.
"""

from __future__ import annotations

import contextlib
import ipaddress
import math
import re
import time
from dataclasses import dataclass

import numpy as np

from opensearch_tpu.common.errors import (IllegalArgumentError,
                                          OpenSearchTpuError, ParsingError)
from opensearch_tpu.mapping.types import (
    DenseVectorFieldType,
    KeywordFieldType,
    TextFieldType,
    parse_date_millis,
    parse_ip_long,
)
from opensearch_tpu.ops import bm25 as bm25_ops
from opensearch_tpu.search import query_dsl as dsl
from opensearch_tpu.search import plan as P
from opensearch_tpu.search.profile import NULL_PROFILER

_I64_MIN = -(2**63)
_I64_MAX = 2**63 - 1


@dataclass
class FieldStats:
    doc_count: int
    total_len: float

    @property
    def avgdl(self) -> float:
        return self.total_len / self.doc_count if self.doc_count else 1.0


class ShardContext:
    """Per-searcher compile context: mapping + collection statistics over
    the segment set (IndexSearcher.collectionStatistics analog)."""

    def __init__(self, segments, mapper):
        self.segments = segments
        self.mapper = mapper
        # point-in-time live-bitmap snapshot (apply_deletes replaces the
        # array, so this context keeps seeing the state at acquire time)
        self.lives = {id(s): s.live for s in segments}
        self._all_live: dict[int, bool] = {}
        self._fstats: dict[str, FieldStats] = {}
        self._sorted_terms: dict[tuple[int, str], list[str]] = {}

    def live_jnp(self, seg, dseg):
        return dseg.live_jnp(self.lives[id(seg)])

    def all_live(self, seg) -> bool:
        """True where this snapshot of the segment has no deleted doc: a
        program over it may leave the live mask unread."""
        out = self._all_live.get(id(seg))
        if out is None:
            out = self._all_live[id(seg)] = bool(self.lives[id(seg)].all())
        return out

    def field_type(self, field: str):
        return self.mapper.field_type(field)

    def field_stats(self, field: str) -> FieldStats:
        st = self._fstats.get(field)
        if st is None:
            doc_count = 0
            total_len = 0.0
            for seg in self.segments:
                pf = seg.postings.get(field)
                if pf is not None:
                    doc_count += pf.docs_with_field
                    total_len += pf.total_len
            st = FieldStats(doc_count, total_len)
            self._fstats[field] = st
        return st

    def df(self, field: str, term: str) -> int:
        total = 0
        for seg in self.segments:
            pf = seg.postings.get(field)
            if pf is not None:
                tid = pf.term_id(term)
                if tid >= 0:
                    total += int(pf.df[tid])
        return total

    def sorted_terms(self, seg, field: str) -> list[str]:
        key = (id(seg), field)
        out = self._sorted_terms.get(key)
        if out is None:
            out = list(seg.postings[field].terms)
            self._sorted_terms[key] = out
        return out

    def text_fields(self) -> list[str]:
        return [f for f, ft in self.mapper.field_types().items()
                if isinstance(ft, TextFieldType)]


def calc_min_should_match(optional: int, spec) -> int:
    """Lucene ``Queries.calculateMinShouldMatch`` subset: int, "-int",
    "N%", "-N%" (conditional "N<P" specs unsupported).  Percentages
    truncate toward zero (Java int cast).  May return a value LARGER than
    ``optional`` — the caller must then match nothing (Lucene rewrites to
    MatchNoDocsQuery)."""
    if spec is None:
        return 0
    s = str(spec).strip()
    if "<" in s:
        raise IllegalArgumentError(
            f"conditional minimum_should_match [{s}] is not supported")
    if s.endswith("%"):
        pct = int(s[:-1])
        result = (optional + int(optional * pct / 100.0) if pct < 0
                  else int(optional * pct / 100.0))
    else:
        n = int(s)
        result = n if n >= 0 else optional + n
    return max(0, result)


def _idfs_for(ctx: ShardContext, field: str, terms: list[str]) -> np.ndarray:
    stats = ctx.field_stats(field)
    return np.asarray(
        [bm25_ops.idf(ctx.df(field, t), stats.doc_count) for t in terms],
        dtype=np.float32)


def _term_bag(ctx, field, terms, required, boost, scored):
    idfs = _idfs_for(ctx, field, terms)
    bind = {"terms": tuple(terms), "idfs": idfs,
            "weights": np.full(len(terms), boost, np.float32),
            "avgdl": ctx.field_stats(field).avgdl, "required": required}
    return P.TermBagPlan(field=field, scored=scored), bind


def _none():
    return P.MatchNonePlan(), {}


def _require_ft(ctx, field, qname):
    ft = ctx.field_type(field)
    if ft is None:
        return None
    if not ft.index_enabled and ft.dv_kind == "none":
        raise IllegalArgumentError(
            f"Cannot search on field [{field}] since it is not indexed")
    return ft


def _ip_cidr_bind(value: str, boost: float) -> dict:
    net = ipaddress.ip_network(str(value), strict=False)
    return {"lo": parse_ip_long(net.network_address),
            "hi": parse_ip_long(net.broadcast_address), "boost": boost}


def compile_query(q: dsl.Query, ctx: ShardContext, scored: bool = True,
                  prof=NULL_PROFILER):
    """Returns (plan, bind).  ``prof`` (a QueryProfiler) times the plan
    construction into the ``compile`` phase and records the root plan
    type — the compiler is a profile feeder, never a consumer."""
    fn = _COMPILERS.get(type(q))
    if fn is None:
        raise IllegalArgumentError(
            f"query type [{type(q).__name__}] is not supported")
    with prof.phase("compile"):
        out = fn(q, ctx, scored)
    prof.set("query_type", type(q).__name__)
    return out


def _c_match_all(q, ctx, scored):
    return P.MatchAllPlan(), {"boost": q.boost}


def _c_match_none(q, ctx, scored):
    return _none()


def _bag_term(q, ctx):
    """The term a ``term`` clause looks up in its field's postings, or
    None where the clause lowers to something other than a one-term bag
    (the ``_id`` metafield, an unmapped field, a numeric, an ip CIDR)."""
    if q.field == "_id":
        return None
    ft = _require_ft(ctx, q.field, "term")
    if ft is None:
        return None
    if ft.type_name == "ip":
        return (None if "/" in str(q.value)
                else str(ipaddress.ip_address(str(q.value))))
    if ft.dv_kind in ("long", "double") and ft.type_name != "boolean":
        return None
    return ft.term_for_query(q.value)


def _c_term(q, ctx, scored):
    if q.field == "_id":
        # term/terms on the _id metafield = an ids query
        # (IdFieldMapper.termQuery)
        return _c_ids(dsl.IdsQuery(values=[str(q.value)], boost=q.boost),
                      ctx, scored)
    term = _bag_term(q, ctx)
    if term is not None:
        return _term_bag(ctx, q.field, [term], 1, q.boost, scored)
    ft = ctx.field_type(q.field)
    if ft is None:
        return _none()
    if ft.type_name == "ip":
        return (P.NumericRangePlan(field=q.field, kind="long"),
                _ip_cidr_bind(q.value, q.boost))
    return (P.NumericTermsPlan(field=q.field, kind=ft.dv_kind),
            {"values": [ft.term_for_query(q.value)], "boost": q.boost})


def _c_terms(q, ctx, scored):
    if q.field == "_id":
        return _c_ids(dsl.IdsQuery(values=[str(v) for v in q.values],
                                   boost=q.boost), ctx, scored)
    ft = _require_ft(ctx, q.field, "terms")
    if ft is None or not q.values:
        return _none()
    if ft.type_name == "ip":
        cidrs = [v for v in q.values if "/" in str(v)]
        exact = [str(ipaddress.ip_address(str(v))) for v in q.values
                 if "/" not in str(v)]
        if cidrs:
            children, binds = [], []
            if exact:
                p = P.PostingsMaskPlan(field=q.field)
                children.append(p)
                binds.append({"terms": tuple(exact), "boost": 1.0})
            for c in cidrs:
                net = ipaddress.ip_network(str(c), strict=False)
                children.append(P.NumericRangePlan(field=q.field, kind="long"))
                binds.append({"lo": parse_ip_long(net.network_address),
                              "hi": parse_ip_long(net.broadcast_address),
                              "boost": 1.0})
            inner = P.BoolPlan(should=tuple(children))
            return (P.ConstScorePlan(child=inner),
                    {"boost": q.boost,
                     "child": {"boost": 1.0, "required": 1,
                               "children": tuple(binds)}})
        return (P.PostingsMaskPlan(field=q.field),
                {"terms": tuple(exact), "boost": q.boost})
    if ft.dv_kind in ("long", "double") and ft.type_name != "boolean":
        return (P.NumericTermsPlan(field=q.field, kind=ft.dv_kind),
                {"values": [ft.term_for_query(v) for v in q.values],
                 "boost": q.boost})
    terms = [ft.term_for_query(v) for v in q.values]
    return (P.PostingsMaskPlan(field=q.field),
            {"terms": tuple(terms), "boost": q.boost})


def _c_match(q, ctx, scored):
    ft = _require_ft(ctx, q.field, "match")
    if ft is None:
        return _none()
    if not isinstance(ft, TextFieldType):
        try:
            return _c_term(dsl.TermQuery(field=q.field, value=q.query,
                                         boost=q.boost), ctx, scored)
        except (OpenSearchTpuError, ValueError):
            if q.lenient:
                return _none()
            raise
    qa = getattr(q, "analyzer", None)
    if qa:
        terms = ctx.mapper.analyzers.get(qa).terms(str(q.query))
    else:
        terms = ft.search_terms(q.query, ctx.mapper.analyzers)
    if not terms:
        return _none()
    if q.fuzziness is not None:
        children, binds = [], []
        for t in terms:
            children.append(P.ExpandTermsPlan(field=q.field, mode="fuzzy"))
            binds.append({"pattern": t, "fuzzy_dist": _auto_fuzzy(q.fuzziness, t),
                          "prefix_length": 0, "boost": q.boost})
        required = (len(terms) if q.operator == "and"
                    else max(1, calc_min_should_match(
                        len(terms), q.minimum_should_match)))
        # fuzzy clauses are constant-score masks; combine as bool
        plan = P.BoolPlan(should=tuple(children))
        return plan, {"boost": 1.0, "required": required,
                      "children": tuple(binds)}
    if q.operator == "and":
        required = len(terms)
    else:
        required = max(1, calc_min_should_match(len(terms),
                                                q.minimum_should_match))
    if required > len(terms):
        return _none()
    return _term_bag(ctx, q.field, terms, required, q.boost, scored)


def _auto_fuzzy(fuzziness, term: str) -> int:
    s = str(fuzziness).upper()
    if s.startswith("AUTO"):
        n = len(term)
        return 0 if n < 3 else (1 if n <= 5 else 2)
    return int(float(s))


def _c_match_phrase(q, ctx, scored):
    """``match_phrase``: span ``phrase.bind`` covers the analysis of the
    phrase and the bind (attributes ``slots``, and ``known``: the slots
    whose term some segment of the shard holds); the ``search.phrase.*``
    counters move where the bound plan runs (``ShardSearcher._topk``)."""
    from opensearch_tpu.common.telemetry import tracer

    ft = _require_ft(ctx, q.field, "match_phrase")
    if ft is None:
        return _none()
    if not isinstance(ft, TextFieldType):
        return _c_term(dsl.TermQuery(field=q.field, value=q.query,
                                     boost=q.boost), ctx, scored)
    with tracer().start_span("phrase.bind") as span:
        analyzer = ctx.mapper.analyzers.get(ft.search_analyzer_name)
        toks = analyzer.analyze(str(q.query))
        span.set_attribute("slots", len(toks))
        if not toks:
            return _none()
        if len(toks) == 1:
            return _term_bag(ctx, q.field, [toks[0].term], 1, q.boost,
                             scored)
        if q.slop:
            raise IllegalArgumentError(
                "match_phrase slop > 0 is not supported yet")
        terms = [t.term for t in toks]
        span.set_attribute(
            "known", sum(1 for t in terms if ctx.df(q.field, t)))
        return _phrase_from_tokens(ctx, q.field, terms,
                                   [t.position for t in toks], q.boost,
                                   scored)


def _c_multi_match(q, ctx, scored):
    if q.type == "bool_prefix":
        # dis-max of per-field match_bool_prefix
        # (MultiMatchQueryBuilder.Type.BOOL_PREFIX)
        plans, binds = [], []
        for field, fboost in q.fields:
            if ctx.field_type(field) is None:
                continue
            p, b = _c_match_bool_prefix(dsl.MatchBoolPrefixQuery(
                field=field, query=q.query, operator=q.operator,
                analyzer=getattr(q, "analyzer", None),
                minimum_should_match=q.minimum_should_match,
                fuzziness=getattr(q, "fuzziness", None),
                boost=q.boost * fboost), ctx, scored)
            if not isinstance(p, P.MatchNonePlan):
                plans.append(p)
                binds.append(b)
        if not plans:
            return _none()
        if len(plans) == 1:
            return plans[0], binds[0]
        return (P.DisMaxPlan(children=tuple(plans)),
                {"boost": 1.0, "tie_breaker": q.tie_breaker,
                 "children": tuple(binds)})
    if q.type not in ("best_fields", "most_fields", "phrase"):
        raise IllegalArgumentError(
            f"multi_match type [{q.type}] is not supported")
    # "*" expands to every text field (the lenient all-fields default the
    # simple_query_string path already has)
    fields = []
    for field, fboost in q.fields:
        if field == "*":
            fields.extend((f, fboost) for f in ctx.text_fields())
        else:
            fields.append((field, fboost))
    children, binds = [], []
    for field, fboost in fields:
        if ctx.field_type(field) is None:
            continue
        if q.type == "phrase":
            sub = dsl.MatchPhraseQuery(field=field, query=q.query,
                                       boost=q.boost * fboost)
            p, b = _c_match_phrase(sub, ctx, scored)
        else:
            sub = dsl.MatchQuery(field=field, query=q.query,
                                 operator=q.operator,
                                 minimum_should_match=q.minimum_should_match,
                                 lenient=getattr(q, "lenient", False),
                                 analyzer=getattr(q, "analyzer", None),
                                 boost=q.boost * fboost)
            p, b = _c_match(sub, ctx, scored)
        if not isinstance(p, P.MatchNonePlan):
            children.append(p)
            binds.append(b)
    if not children:
        return _none()
    if len(children) == 1:
        return children[0], binds[0]
    plan = P.DisMaxPlan(children=tuple(children))
    return plan, {"boost": 1.0, "tie_breaker": q.tie_breaker,
                  "children": tuple(binds)}


def _fold_filter_terms(clauses, ctx):
    """A ``filter`` group with its ``term`` clauses on one field folded
    into one filtering term bag with ``required`` = the number of distinct
    terms: the conjunction the clauses spell, as ONE segment program per
    ``(t_pad, bucket)`` and one packed input a segment, where a child a
    clause makes the program space the product of the children's buckets
    (a tag filter of a filtered ``knn``).  Returns compiled (plan, bind)
    pairs; a field with one such clause, and every other clause, compiles
    as before."""
    bag_terms = [_bag_term(sub, ctx) if type(sub) is dsl.TermQuery else None
                 for sub in clauses]
    terms_of = {}
    for sub, term in zip(clauses, bag_terms):
        if term is not None:
            terms_of.setdefault(sub.field, {})[term] = None
    out, folded = [], set()
    for sub, term in zip(clauses, bag_terms):
        terms = terms_of[sub.field] if term is not None else ()
        if len(terms) < 2:
            out.append(compile_query(sub, ctx, False))
        elif sub.field not in folded:          # the first stands for all
            folded.add(sub.field)
            out.append(_term_bag(ctx, sub.field, list(terms), len(terms),
                                 1.0, False))
    return out


def _c_bool(q, ctx, scored):
    groups = {}
    for name, qs, sub_scored in (("must", q.must, scored),
                                 ("should", q.should, scored),
                                 ("must_not", q.must_not, False)):
        plans, binds = [], []
        for sub in qs:
            p, b = compile_query(sub, ctx, sub_scored)
            plans.append(p)
            binds.append(b)
        groups[name] = (tuple(plans), tuple(binds))
    folded = _fold_filter_terms(q.filter, ctx)
    groups["filter"] = (tuple(p for p, _b in folded),
                        tuple(b for _p, b in folded))
    n_should = len(groups["should"][0])
    if q.minimum_should_match is not None:
        required = calc_min_should_match(n_should, q.minimum_should_match)
        if required > n_should:
            return _none()   # Lucene rewrites to MatchNoDocsQuery
    else:
        required = 0 if (q.must or q.filter) else (1 if n_should else 0)
    if len(folded) == 1 < len(q.filter) and not (q.must or q.should
                                                 or q.must_not):
        # nothing but the folded bag: the bool IS that bag (scores 0, the
        # same mask), without BoolPlan's two scalars a segment
        return folded[0]
    plan = P.BoolPlan(must=groups["must"][0], should=groups["should"][0],
                      must_not=groups["must_not"][0],
                      filter=groups["filter"][0])
    bind = {"boost": q.boost, "required": required,
            "children": (groups["must"][1] + groups["should"][1]
                         + groups["must_not"][1] + groups["filter"][1])}
    return plan, bind


def _c_range(q, ctx, scored):
    if getattr(q, "lenient", False):
        try:
            return _c_range_strict(q, ctx, scored)
        except (OpenSearchTpuError, ValueError):
            return _none()
    return _c_range_strict(q, ctx, scored)


def _c_range_strict(q, ctx, scored):
    ft = _require_ft(ctx, q.field, "range")
    if ft is None:
        return _none()
    if isinstance(ft, TextFieldType):
        raise IllegalArgumentError(
            f"range query on [text] field [{q.field}] is not supported")
    if isinstance(ft, KeywordFieldType):
        lo, lo_incl = (q.gte, True) if q.gte is not None else (q.gt, False)
        hi, hi_incl = (q.lte, True) if q.lte is not None else (q.lt, False)
        bind = {"lo": None if lo is None else str(lo), "lo_incl": lo_incl,
                "hi": None if hi is None else str(hi), "hi_incl": hi_incl,
                "boost": q.boost}
        return P.OrdinalRangePlan(field=q.field), bind
    kind = "double" if ft.dv_kind == "double" else "long"
    if kind == "long":
        lo = _I64_MIN if q.gte is None and q.gt is None else (
            ft.range_bound(q.gte) if q.gte is not None
            else ft.range_bound(q.gt) + 1)
        hi = _I64_MAX if q.lte is None and q.lt is None else (
            ft.range_bound(q.lte) if q.lte is not None
            else ft.range_bound(q.lt) - 1)
        return (P.NumericRangePlan(field=q.field, kind="long"),
                {"lo": lo, "hi": hi, "boost": q.boost})
    lo, lo_incl = (-np.inf, True)
    if q.gte is not None:
        lo, lo_incl = float(ft.range_bound(q.gte)), True
    elif q.gt is not None:
        lo, lo_incl = float(ft.range_bound(q.gt)), False
    hi, hi_incl = (np.inf, True)
    if q.lte is not None:
        hi, hi_incl = float(ft.range_bound(q.lte)), True
    elif q.lt is not None:
        hi, hi_incl = float(ft.range_bound(q.lt)), False
    return (P.NumericRangePlan(field=q.field, kind="double",
                               include_lo=lo_incl, include_hi=hi_incl),
            {"lo": lo, "hi": hi, "boost": q.boost})


def _c_exists(q, ctx, scored):
    if q.field in ("_id", "_index", "_seq_no", "_version"):
        # always-present metafields: every live doc matches
        # (exists rewrites to match_all for fields with norms/dv on all
        # docs — MetadataFieldMapper existence semantics)
        return _c_match_all(dsl.MatchAllQuery(boost=q.boost), ctx, scored)
    ft = ctx.field_type(q.field)
    if ft is None or ft.type_name == "object":
        # object container (explicit or implicit): exists = any child
        # field exists (ObjectMapper existence expansion)
        children = [f for f in getattr(ctx.mapper, "_fields", {})
                    if f.startswith(q.field + ".")]
        if not children:
            return _none()
        return _c_bool(dsl.BoolQuery(
            should=[dsl.ExistsQuery(field=f) for f in children],
            boost=q.boost), ctx, scored)
    src = {"long": "numeric", "double": "numeric", "ordinal": "ordinal",
           "vector": "vector", "geo_point": "geo", "none": "norms"}[ft.dv_kind]
    if src != "norms" and not ft.doc_values_enabled:
        if ft.indexed and ft.index_enabled:
            # doc_values disabled but indexed: existence via the
            # postings presence column (the reference's _field_names
            # fallback)
            src = "norms"
        else:
            raise IllegalArgumentError(
                f"exists on field [{q.field}] requires doc_values or an "
                "indexed field")
    return P.ExistsPlan(field=q.field, src=src), {"boost": q.boost}


# -- parent-join (modules/parent-join) --------------------------------------


def _find_join_field(ctx):
    for f, ft in ctx.mapper.field_types().items():
        if ft.type_name == "join":
            return f, ft
    return None, None


def _host_run_scored(ctx, q):
    """Run an inner query over every segment host-side; [(seg, scores
    np[n_pad], matched np[n_pad])].  The pre-pass the join queries (and
    knn before them) inject via ScoredMaskPlan."""
    from opensearch_tpu.search.executor import build_arrays
    from opensearch_tpu.search.plan import run_full

    import jax.numpy as jnp

    plan, bind = compile_query(q, ctx, scored=True)
    needed = plan.arrays()
    neg_inf = jnp.asarray(np.float32(-np.inf))  # staging-ok: per-query input
    out = []
    for seg in ctx.segments:
        dseg = seg.device()
        A = build_arrays(dseg, needed, ctx.mapper,
                         live=ctx.live_jnp(seg, dseg))
        dims, ins = plan.prepare(bind, seg, dseg, ctx)
        scores, matched = run_full(plan, dims, A, ins, neg_inf)
        out.append((seg, np.asarray(scores), np.asarray(matched)))
    return out


def _ord_per_doc(seg, field) -> dict:
    """doc -> term for a single-valued hidden ordinal column, cached on
    the segment (segments are immutable)."""
    from opensearch_tpu.common.cache import attached_cache
    cache = attached_cache(seg, "_join_col_cache",
                           name="query.join_columns",
                           max_weight=32 << 20, breaker="fielddata")
    out = cache.get(field)
    if out is None:
        dv = seg.ordinal_dv.get(field)
        out = {} if dv is None else {
            int(d): dv.ord_terms[o]
            for d, o in zip(dv.value_docs, dv.ords) if o >= 0}
        cache.put(field, out)
    return out


def _join_mask_plan(ctx, fn, label):
    return P.ScoredMaskPlan(label=label), {"fn": fn}


def _c_has_child(q, ctx, scored):
    field, jft = _find_join_field(ctx)
    if field is None:
        return _none()
    parent_rel = jft.parent_of(q.type)
    if parent_rel is None:
        raise IllegalArgumentError(
            f"[has_child] join field [{field}] has no child relation "
            f"[{q.type}]")
    state: dict = {}

    def compute():
        agg: dict = {}      # parent _id -> [count, total, mx, mn]
        for seg, scores, matched in _host_run_scored(ctx, q.query):
            names = _ord_per_doc(seg, field + "#name")
            parents = _ord_per_doc(seg, field + "#parent")
            for local in np.nonzero(matched[: seg.n_docs])[0]:
                local = int(local)
                if names.get(local) != q.type:
                    continue
                pid = parents.get(local)
                if pid is None:
                    continue
                s = float(scores[local])
                cur = agg.get(pid)
                if cur is None:
                    agg[pid] = [1, s, s, s]
                else:
                    cur[0] += 1
                    cur[1] += s
                    cur[2] = max(cur[2], s)
                    cur[3] = min(cur[3], s)
        out = {}
        for pid, (count, total, mx, mn) in agg.items():
            if count < q.min_children:
                continue
            if q.max_children is not None and count > q.max_children:
                continue
            out[pid] = {"none": 1.0, "sum": total, "max": mx, "min": mn,
                        "avg": total / count}.get(q.score_mode, 1.0)
        state["scores"] = out

    def fn(seg, dseg):
        if "scores" not in state:
            compute()
        sc = np.zeros(dseg.n_pad, np.float32)
        mk = np.zeros(dseg.n_pad, bool)
        names = _ord_per_doc(seg, field + "#name")
        for pid, s in state["scores"].items():
            local = seg.id_to_local.get(pid)
            if local is None or not seg.live[local]:
                continue
            if names.get(local) != parent_rel:
                continue
            mk[local] = True
            sc[local] = q.boost * s
        return sc, mk

    return _join_mask_plan(ctx, fn, "has_child")


def _c_has_parent(q, ctx, scored):
    field, jft = _find_join_field(ctx)
    if field is None:
        return _none()
    if q.parent_type not in jft.relations:
        raise IllegalArgumentError(
            f"[has_parent] join field [{field}] has no parent relation "
            f"[{q.parent_type}]")
    state: dict = {}

    def compute():
        out = {}
        for seg, scores, matched in _host_run_scored(ctx, q.query):
            names = _ord_per_doc(seg, field + "#name")
            for local in np.nonzero(matched[: seg.n_docs])[0]:
                local = int(local)
                if names.get(local) != q.parent_type:
                    continue
                out[seg.doc_ids[local]] = float(scores[local])
        state["scores"] = out

    def fn(seg, dseg):
        if "scores" not in state:
            compute()
        sc = np.zeros(dseg.n_pad, np.float32)
        mk = np.zeros(dseg.n_pad, bool)
        parents = _ord_per_doc(seg, field + "#parent")
        for local, pid in parents.items():
            s = state["scores"].get(pid)
            if s is None or not seg.live[local]:
                continue
            mk[local] = True
            sc[local] = q.boost * (s if q.score else 1.0)
        return sc, mk

    return _join_mask_plan(ctx, fn, "has_parent")


def _c_parent_id(q, ctx, scored):
    field, jft = _find_join_field(ctx)
    if field is None:
        return _none()
    if jft.parent_of(q.type) is None:
        raise IllegalArgumentError(
            f"[parent_id] join field [{field}] has no child relation "
            f"[{q.type}]")

    def fn(seg, dseg):
        sc = np.zeros(dseg.n_pad, np.float32)
        mk = np.zeros(dseg.n_pad, bool)
        names = _ord_per_doc(seg, field + "#name")
        parents = _ord_per_doc(seg, field + "#parent")
        for local, pid in parents.items():
            if pid == q.id and names.get(local) == q.type \
                    and seg.live[local]:
                mk[local] = True
                sc[local] = q.boost
        return sc, mk

    return _join_mask_plan(ctx, fn, "parent_id")


def _c_ids(q, ctx, scored):
    wanted = set(map(str, q.values))

    def mask_fn(seg, dseg):
        m = np.zeros(dseg.n_pad, bool)
        for did in wanted:
            loc = seg.id_to_local.get(did)
            if loc is not None:
                m[loc] = True
        return m

    return P.MaskPlan(label="ids"), {"mask_fn": mask_fn, "boost": q.boost}


_MAX_CODEPOINT = chr(0x10FFFF)


def _c_prefix(q, ctx, scored):
    ft = _require_ft(ctx, q.field, "prefix")
    if ft is None:
        return _none()
    value = str(q.value)
    return (P.TermRangeMaskPlan(field=q.field),
            {"lo": value, "hi": value + _MAX_CODEPOINT, "boost": q.boost})


def _c_wildcard(q, ctx, scored):
    ft = _require_ft(ctx, q.field, "wildcard")
    if ft is None:
        return _none()
    return (P.ExpandTermsPlan(field=q.field, mode="wildcard"),
            {"pattern": str(q.value), "fuzzy_dist": 0, "prefix_length": 0,
             "nocase": bool(getattr(q, "case_insensitive", False)),
             "boost": q.boost})


def _c_regexp(q, ctx, scored):
    ft = _require_ft(ctx, q.field, "regexp")
    if ft is None:
        return _none()
    return (P.ExpandTermsPlan(field=q.field, mode="regexp"),
            {"pattern": str(q.value), "fuzzy_dist": 0, "prefix_length": 0,
             "boost": q.boost})


def _c_fuzzy(q, ctx, scored):
    ft = _require_ft(ctx, q.field, "fuzzy")
    if ft is None:
        return _none()
    return (P.ExpandTermsPlan(field=q.field, mode="fuzzy"),
            {"pattern": str(q.value),
             "fuzzy_dist": _auto_fuzzy(q.fuzziness, str(q.value)),
             "prefix_length": q.prefix_length, "boost": q.boost})


def _c_constant_score(q, ctx, scored):
    child_plan, child_bind = compile_query(q.query, ctx, scored=False)
    return (P.ConstScorePlan(child=child_plan),
            {"boost": q.boost, "child": child_bind})


def _c_dis_max(q, ctx, scored):
    plans, binds = [], []
    for sub in q.queries:
        p, b = compile_query(sub, ctx, scored)
        plans.append(p)
        binds.append(b)
    if not plans:
        return _none()
    return (P.DisMaxPlan(children=tuple(plans)),
            {"boost": q.boost, "tie_breaker": q.tie_breaker,
             "children": tuple(binds)})


_SQS_TOKEN = re.compile(r'([+-]?)"([^"]*)"|([+-]?)(\S+)')


def _c_simple_query_string(q, ctx, scored):
    fields = q.fields
    if not fields or fields == [("*", 1.0)]:
        fields = [(f, 1.0) for f in ctx.text_fields()]
    sub_queries = []
    for m in _SQS_TOKEN.finditer(q.query.strip()):
        if m.group(2) is not None:       # quoted -> phrase operator
            sign, text, is_phrase = m.group(1), m.group(2), True
        else:
            sign, text, is_phrase = m.group(3), m.group(4), False
            text = text.lstrip("+-")
        if not text.strip():
            continue
        mm = dsl.MultiMatchQuery(fields=fields, query=text,
                                 type="phrase" if is_phrase else "best_fields")
        sub_queries.append((sign == "-", mm))
    if not sub_queries:
        return P.MatchAllPlan(), {"boost": q.boost}
    must, must_not, should = [], [], []
    for negate, mm in sub_queries:
        if negate:
            must_not.append(mm)
        elif q.default_operator == "and":
            must.append(mm)
        else:
            should.append(mm)
    return _c_bool(dsl.BoolQuery(must=must, must_not=must_not, should=should,
                                 boost=q.boost), ctx, scored)


def _knn_filter_masks(q, ctx) -> dict:
    """{seg_order: bool[n_pad]}: a filtered ``knn``'s filter as one mask
    program (``plan.run_full``) a segment that holds the vector field,
    all dispatched before the first scan; nothing is read back.  Span
    ``knn.filter`` covers the filter's compile and the dispatches, its
    part ``launch`` the calls of the mask programs."""
    from opensearch_tpu.common.device_ledger import device_ledger
    from opensearch_tpu.common.telemetry import metrics, tracer
    from opensearch_tpu.search.executor import build_arrays

    ledger = device_ledger()
    f = q.filter
    clauses = (len(f.must) + len(f.should) + len(f.must_not) + len(f.filter)
               if isinstance(f, dsl.BoolQuery) else 1)
    masks = {}
    with tracer().start_span("knn.filter", {"clauses": clauses}) as span:
        fplan, fbind = compile_query(f, ctx, scored=False)
        min_score = ledger.stage_input(np.float32(-np.inf))
        for seg_order, seg in enumerate(ctx.segments):
            dseg = seg.device()
            if dseg.vector.get(q.field) is None:
                continue
            A = build_arrays(dseg, fplan.arrays(), ctx.mapper)
            dims, ins = fplan.prepare(fbind, seg, dseg, ctx)
            with span.part("launch"):
                _s, masks[seg_order] = P.run_full(fplan, dims, A, ins,
                                                  min_score)
            P.book(P.choose(fplan, dims, dseg.n_pad), dseg, ins)
        span.set_attribute("segments", len(masks))
    metrics().counter("search.knn.filtered.requests").inc()
    metrics().counter("search.knn.filter.programs").inc(len(masks))
    return masks


def _c_knn(q, ctx, scored):
    """knn query: per-segment vector search — exact brute force (matmul +
    top-k, ops/knn.py) or ANN when the field mapping declares a ``method``
    of ``ivf``/``ivf_pq`` (cluster-probed search, ops/ivf.py; trained
    structure cached per immutable segment) — with the global per-shard k
    winners injected into the plan tree as a ScoredMaskPlan.  Optional
    ``filter`` restricts candidates BEFORE the k cut (the plugin's
    filtered-knn semantics; ANN falls back to exact under a filter, like
    the plugin's filtered exact-search rescue).  All segment programs are
    dispatched asynchronously; the host syncs ONCE per query.
    """
    from opensearch_tpu.common.device_ledger import device_ledger
    from opensearch_tpu.common.telemetry import tracer
    from opensearch_tpu.ops.ivf import IvfPqIndex, ivf_search, ivfpq_search_l2
    from opensearch_tpu.ops.knn import knn_topk_auto

    ledger = device_ledger()
    ft = ctx.field_type(q.field)
    if ft is None:
        return _none()
    if ft.dv_kind != "vector":
        raise IllegalArgumentError(
            f"[knn] query requires a knn_vector/dense_vector field, "
            f"[{q.field}] is [{ft.type_name}]")
    qvec = np.asarray(q.vector, np.float32)
    if qvec.shape != (ft.dims,):
        raise IllegalArgumentError(
            f"query vector has dimension {qvec.shape[0]} but field "
            f"[{q.field}] expects {ft.dims}")
    space = {"l2": "l2", "cosinesimil": "cosinesimil",
             "innerproduct": "innerproduct"}.get(ft.space_type, "l2")

    method = dict(getattr(ft, "method", None) or {})
    # method_parameters is a SEARCH-TIME knob: only nprobe may be
    # overridden per request — structural params (name/nlist/m) define
    # the trained structure and honoring them here would retrain k-means
    # on the query path per distinct value
    if q.method_parameters and "nprobe" in q.method_parameters:
        method["nprobe"] = int(q.method_parameters["nprobe"])
    ann_name = method.get("name")
    use_ann = ann_name in ("ivf", "ivf_pq")

    fmasks = {}
    scan_span = contextlib.nullcontext()
    if q.filter is not None:
        fmasks = _knn_filter_masks(q, ctx)
        scan_span = tracer().start_span("knn.scan")

    qvec_j = ledger.stage_input(qvec)
    # phase 1: dispatch every segment's device program, keep DEVICE arrays
    # (a filter's mask programs went first, all of them)
    pending = []             # (seg_order, vals_dev, idx_dev)
    with scan_span as span:        # None without a filter: no knn.scan
        for seg_order, seg in enumerate(ctx.segments):
            dseg = seg.device()
            vcol = dseg.vector.get(q.field)
            if vcol is None:
                continue
            live = ctx.live_jnp(seg, dseg)
            valid = vcol["exists"] & live
            if seg_order in fmasks:
                valid = valid & fmasks[seg_order]
            kk = min(q.k, dseg.n_pad)
            ann = (seg.ann_index(q.field, method)
                   if use_ann and q.filter is None else None)
            t_launch = time.monotonic_ns()
            if ann is not None:
                nprobe = min(int(method.get("nprobe", 0))
                             or max(1, ann.nlist // 8), ann.nlist)
                # the probed candidate pool is nprobe*c_pad rows — top_k past
                # that is a compile error
                kk = min(kk, nprobe * ann.c_pad)
                staged = dseg.ann_staged(ann)
                if isinstance(ann, IvfPqIndex) and space == "l2":
                    vals, idx = ivfpq_search_l2(*staged, qvec_j, valid,
                                                k=kk, nprobe=nprobe)
                else:
                    # IvfIndex, or IVF-PQ in a non-l2 space (ADC tables are
                    # l2-residual based; probe the flat layout instead)
                    if isinstance(ann, IvfPqIndex):
                        ann = seg.ann_index(q.field, {**method, "name": "ivf"})
                        staged = dseg.ann_staged(ann)
                    vals, idx = ivf_search(*staged, qvec_j, valid,
                                           space=space, k=kk, nprobe=nprobe)
            else:
                vals, idx = knn_topk_auto(vcol["values"], valid, qvec_j,
                                          space=space, k=kk)
            # both copies queue behind the program, so phase 2's two reads
            # a segment find their arrays on the host
            vals.copy_to_host_async()
            idx.copy_to_host_async()
            if span is not None:
                # the scan's call and the start of its copies
                span.add_part("launch", time.monotonic_ns() - t_launch)
            pending.append((seg_order, vals, idx))
            P.book(P.knn_scan(dseg.n_pad, kk, exact=ann is None), dseg)
    # phase 2: one host sync for all segments' top-k
    candidates = []          # (score, seg_order, local)
    if pending:
        t_sync = time.monotonic()
        fetched_bytes = 0
        with tracer().start_span("device.sync", {"site": "knn_prepass"},
                                 cpu=True):
            for seg_order, vals, idx in pending:
                vals, idx = np.asarray(vals), np.asarray(idx)
                fetched_bytes += vals.nbytes + idx.nbytes
                keep = (vals > -np.inf) & (idx >= 0)
                for v, i in zip(vals[keep], idx[keep]):
                    candidates.append((float(v), seg_order, int(i)))
        ledger.record_fetch(fetched_bytes, time.monotonic() - t_sync,
                            arrays=2 * len(pending))
    candidates.sort(key=lambda t: (-t[0], t[1], t[2]))
    winners: dict[int, list[tuple[int, float]]] = {}
    for score, seg_order, local in candidates[: q.k]:
        winners.setdefault(seg_order, []).append((local, score * q.boost))
    return _winners_plan(ctx, winners, "knn")


def _winners_plan(ctx, winners: dict, label: str):
    """(ScoredMaskPlan, bind) injecting host-computed per-segment winners
    {seg_order: [(local, score)]} into the plan tree (shared by the knn
    pre-pass and percolate)."""
    seg_order_by_id = {id(s): i for i, s in enumerate(ctx.segments)}

    def fn(seg, dseg):
        scores = np.zeros(dseg.n_pad, np.float32)
        mask = np.zeros(dseg.n_pad, bool)
        for local, score in winners.get(
                seg_order_by_id.get(id(seg), -1), []):
            scores[local] = score
            mask[local] = True
        return scores, mask

    return P.ScoredMaskPlan(label=label), {"fn": fn}


def _c_percolate(q, ctx, scored):
    """percolate: reverse search (modules/percolator).  Each stored query
    (the ``percolator`` field's _source JSON) compiles and runs against a
    tiny in-memory segment holding the candidate document(s); stored
    queries that match ANY candidate become hits.  Matching happens at
    compile time — the result is a ScoredMaskPlan over the query docs
    (the same injection pattern as knn's pre-pass)."""
    from opensearch_tpu.index.segment import SegmentWriter
    from opensearch_tpu.search.executor import ShardSearcher
    from opensearch_tpu.search.query_dsl import parse_query

    ft = ctx.field_type(q.field)
    if ft is None or ft.type_name != "percolator":
        raise IllegalArgumentError(
            f"[percolate] field [{q.field}] must be a percolator field")
    # candidate docs in a throwaway searcher over an ISOLATED mapper
    # clone (the percolator's MemoryIndex analog) — dynamic resolution
    # of unmapped candidate fields must never leak into the live index
    # mapping
    from opensearch_tpu.mapping.mapper import DocumentMapper

    tmp_mapper = DocumentMapper(ctx.mapper.to_mapping())
    writer = SegmentWriter()
    parsed = [tmp_mapper.parse(f"_tmp_{i}", d)
              for i, d in enumerate(q.documents)]
    cand = ShardSearcher([writer.build(parsed, "_percolate_tmp")],
                         tmp_mapper)
    winners: dict[int, list[tuple[int, float]]] = {}
    for seg_order, seg in enumerate(ctx.segments):
        live = ctx.lives[id(seg)]    # the searcher's PIT snapshot
        for local in range(seg.n_docs):
            if not live[local]:
                continue
            stored = seg.source(local).get(q.field)
            if not isinstance(stored, dict):
                continue             # absent or malformed: never matches
            try:
                n = cand.count(stored)
            except OpenSearchTpuError:
                continue             # query shape our engine can't run
            if n > 0:
                winners.setdefault(seg_order, []).append(
                    (local, q.boost))
    return _winners_plan(ctx, winners, "percolate")


def _c_nested(q, ctx, scored):
    """nested query: inner conditions compile into object-space
    mini-plans (plan.py Obj*Plan) evaluated against the path's
    object-major columns, scatter-OR'd back to parents.  Scoring is
    constant (the reference's score_mode=none; avg/sum/max degrade to it
    — inner BM25 scoring inside nested blocks is not modeled)."""
    ft = ctx.field_type(q.path)
    if ft is None or ft.dv_kind != "nested":
        if q.ignore_unmapped:
            return _none()
        raise IllegalArgumentError(
            f"[nested] failed to find nested object under path "
            f"[{q.path}]")
    inner, ibind = _compile_obj(q.query, q.path, ctx)
    return (P.NestedPlan(path=q.path, inner=inner),
            {"inner": ibind, "boost": q.boost})


def _compile_obj(node, path, ctx):
    """Inner (object-space) compiler for nested queries."""
    prefix = path + "."

    def child_ft(field):
        if not field.startswith(prefix):
            field = prefix + field       # accept relative child names
        ft = ctx.field_type(field)
        if ft is None:
            raise IllegalArgumentError(
                f"[nested] unknown field [{field}] under [{path}]")
        return field, ft

    if isinstance(node, dsl.MatchAllQuery) or node is None:
        return P.ObjMatchAllPlan(), {}
    if isinstance(node, (dsl.TermQuery, dsl.TermsQuery)):
        raw = ([node.value] if isinstance(node, dsl.TermQuery)
               else list(node.values))
        field, ft = child_ft(node.field)
        if ft.dv_kind in ("long", "double"):
            return (P.ObjTermsPlan(field=field, kind="numeric"),
                    {"values": [float(ft.doc_value(v)) for v in raw]})
        return (P.ObjTermsPlan(field=field, kind="ordinal"),
                {"values": [str(ft.term_for_query(v)) for v in raw]})
    if isinstance(node, dsl.MatchQuery):
        field, ft = child_ft(node.field)
        if hasattr(ft, "search_terms"):
            terms = ft.search_terms(str(node.query), ctx.mapper.analyzers)
            return (P.ObjTermsPlan(field=field, kind="ordinal"),
                    {"values": terms})
        if ft.dv_kind in ("long", "double"):
            return (P.ObjTermsPlan(field=field, kind="numeric"),
                    {"values": [float(ft.doc_value(node.query))]})
        return (P.ObjTermsPlan(field=field, kind="ordinal"),
                {"values": [str(ft.term_for_query(node.query))]})
    if isinstance(node, dsl.RangeQuery):
        field, ft = child_ft(node.field)
        if ft.dv_kind not in ("long", "double"):
            raise IllegalArgumentError(
                f"[nested] range over [{field}] requires a numeric/date "
                "child field")
        def conv(v):
            return float(ft.doc_value(v))
        lo = conv(node.gte) if node.gte is not None else (
            conv(node.gt) if node.gt is not None else -np.inf)
        hi = conv(node.lte) if node.lte is not None else (
            conv(node.lt) if node.lt is not None else np.inf)
        return (P.ObjRangePlan(field=field,
                               include_lo=node.gt is None,
                               include_hi=node.lt is None),
                {"lo": lo, "hi": hi})
    if isinstance(node, dsl.ExistsQuery):
        field, _ft = child_ft(node.field)
        return P.ObjExistsPlan(field=field), {}
    if isinstance(node, dsl.BoolQuery):
        groups = []
        binds = []
        for clause_list in (node.must + node.filter, node.should,
                            node.must_not):
            compiled = [_compile_obj(c, path, ctx) for c in clause_list]
            groups.append(tuple(p for p, _b in compiled))
            binds.extend(b for _p, b in compiled)
        required = calc_min_should_match(
            len(node.should),
            node.minimum_should_match
            if node.minimum_should_match is not None
            else (0 if (node.must or node.filter) else 1))
        return (P.ObjBoolPlan(must=groups[0], should=groups[1],
                              must_not=groups[2],
                              should_required=required >= 1),
                {"children": tuple(binds)})
    raise IllegalArgumentError(
        f"[nested] inner query type [{type(node).__name__}] is not "
        "supported — use term/terms/match/range/exists/bool")


def _c_boosting(q, ctx, scored):
    pos_p, pos_b = compile_query(q.positive, ctx, scored)
    neg_p, neg_b = compile_query(q.negative, ctx, scored=False)
    return (P.BoostingPlan(positive=pos_p, negative=neg_p),
            {"boost": q.boost, "negative_boost": q.negative_boost,
             "children": (pos_b, neg_b)})


def _c_terms_set(q, ctx, scored):
    ft = _require_ft(ctx, q.field, "terms_set")
    if ft is None:
        return _none()
    msm_ft = ctx.field_type(q.minimum_should_match_field)
    if msm_ft is None or msm_ft.dv_kind not in ("long", "double"):
        raise IllegalArgumentError(
            f"[terms_set] minimum_should_match_field "
            f"[{q.minimum_should_match_field}] must be a numeric field")
    terms = [ft.term_for_query(t) for t in q.terms]
    if not terms:
        return _none()
    return (P.TermsSetPlan(field=q.field,
                           msm_field=q.minimum_should_match_field,
                           scored=scored),
            {"terms": tuple(terms),
             "idfs": _idfs_for(ctx, q.field, terms),
             "weights": np.full(len(terms), q.boost, np.float32),
             "avgdl": ctx.field_stats(q.field).avgdl})


def _c_distance_feature(q, ctx, scored):
    from opensearch_tpu.search.query_dsl import (parse_distance_m,
                                                 parse_geo_point)

    ft = _require_ft(ctx, q.field, "distance_feature")
    if ft is None:
        return _none()
    if ft.dv_kind == "geo_point":
        origin = parse_geo_point(q.origin)
        pivot = parse_distance_m(q.pivot)
        kind = "geo"
    elif ft.type_name in ("date", "date_nanos"):
        from opensearch_tpu.search.aggs import _parse_duration_ms
        origin = float(parse_date_millis(q.origin))
        pivot = float(_parse_duration_ms(q.pivot)
                      if isinstance(q.pivot, str) else q.pivot)
        kind = "numeric"
    elif ft.dv_kind in ("long", "double"):
        origin = float(q.origin)
        pivot = float(q.pivot)
        kind = "numeric"
    else:
        raise IllegalArgumentError(
            f"[distance_feature] field [{q.field}] must be date, numeric "
            f"or geo_point, got [{ft.type_name}]")
    if pivot <= 0:
        raise IllegalArgumentError("[distance_feature] pivot must be > 0")
    return (P.DistanceFeaturePlan(field=q.field, kind=kind),
            {"origin": origin, "pivot": pivot, "boost": q.boost})


def _c_geo_distance(q, ctx, scored):
    from opensearch_tpu.search.query_dsl import parse_distance_m

    ft = _require_ft(ctx, q.field, "geo_distance")
    if ft is None:
        return _none()
    if ft.dv_kind != "geo_point":
        raise IllegalArgumentError(
            f"[geo_distance] field [{q.field}] is not a geo_point")
    return (P.GeoDistancePlan(field=q.field),
            {"lat": q.lat, "lon": q.lon,
             "distance_m": parse_distance_m(q.distance), "boost": q.boost})


def _c_geo_bounding_box(q, ctx, scored):
    ft = _require_ft(ctx, q.field, "geo_bounding_box")
    if ft is None:
        return _none()
    if ft.dv_kind != "geo_point":
        raise IllegalArgumentError(
            f"[geo_bounding_box] field [{q.field}] is not a geo_point")
    return (P.GeoBoxPlan(field=q.field),
            {"top": q.top, "left": q.left, "bottom": q.bottom,
             "right": q.right, "boost": q.boost})


def _c_geo_polygon(q, ctx, scored):
    ft = _require_ft(ctx, q.field, "geo_polygon")
    if ft is None:
        return _none()
    if ft.dv_kind != "geo_point":
        raise IllegalArgumentError(
            f"[geo_polygon] field [{q.field}] is not a geo_point")
    return (P.GeoPolygonPlan(field=q.field),
            {"lats": [p[0] for p in q.points],
             "lons": [p[1] for p in q.points], "boost": q.boost})


def _expand_prefix_terms(ctx, field, prefix: str, max_expansions: int):
    """Terms with ``prefix`` across all segments (sorted dictionaries =
    binary-searched range per segment), capped like MultiTermQuery's
    max_expansions."""
    import bisect

    out: list[str] = []
    seen: set = set()
    for seg in ctx.segments:
        pf = seg.postings.get(field)
        if pf is None:
            continue
        sterms = ctx.sorted_terms(seg, field)
        lo = bisect.bisect_left(sterms, prefix)
        for i in range(lo, len(sterms)):
            t = sterms[i]
            if not t.startswith(prefix):
                break
            if t not in seen:
                seen.add(t)
                out.append(t)
            if len(out) >= max_expansions:
                return out
    return out


def _phrase_from_tokens(ctx, field, terms, positions, boost, scored):
    """PhrasePlan bind straight from (term, position) tokens — keeps the
    analyzer's position gaps (stopword holes) intact."""
    if len(terms) == 1:
        return _term_bag(ctx, field, [terms[0]], 1, boost, scored)
    stats = ctx.field_stats(field)
    idf_sum = float(np.sum(_idfs_for(ctx, field, terms)))
    bind = {"terms": tuple(terms), "positions": tuple(positions),
            "idf_sum": idf_sum, "boost": boost, "avgdl": stats.avgdl}
    return P.PhrasePlan(field=field, scored=scored), bind


def _c_match_phrase_prefix(q, ctx, scored):
    """Phrase whose LAST token is a prefix: expand it against the term
    dictionary and dis-max the resulting phrases, substituting the last
    term IN PLACE so original token positions (incl. stopword gaps)
    survive (MatchPhrasePrefixQueryBuilder -> MultiPhrasePrefixQuery)."""
    ft = _require_ft(ctx, q.field, "match_phrase_prefix")
    if ft is None:
        return _none()
    if not isinstance(ft, TextFieldType):
        return _c_term(dsl.TermQuery(field=q.field, value=q.query,
                                     boost=q.boost), ctx, scored)
    analyzer = ctx.mapper.analyzers.get(ft.search_analyzer_name)
    toks = analyzer.analyze(str(q.query))
    if not toks:
        return _none()
    if q.slop:
        raise IllegalArgumentError(
            "match_phrase_prefix slop > 0 is not supported yet")
    terms = [t.term for t in toks]
    positions = [t.position for t in toks]
    expansions = _expand_prefix_terms(ctx, q.field, terms[-1],
                                      int(q.max_expansions))
    if not expansions:
        return _none()
    plans, binds = [], []
    for t in expansions:
        p, b = _phrase_from_tokens(ctx, q.field, terms[:-1] + [t],
                                   positions, q.boost, scored)
        plans.append(p)
        binds.append(b)
    if len(plans) == 1:
        return plans[0], binds[0]
    return (P.DisMaxPlan(children=tuple(plans)),
            {"boost": 1.0, "tie_breaker": 0.0, "children": tuple(binds)})


def _c_match_bool_prefix(q, ctx, scored):
    """Every token a term clause, the last a prefix clause, combined as
    a bool (MatchBoolPrefixQueryBuilder)."""
    ft = _require_ft(ctx, q.field, "match_bool_prefix")
    if ft is None:
        return _none()
    if not isinstance(ft, TextFieldType):
        return _c_term(dsl.TermQuery(field=q.field, value=q.query,
                                     boost=q.boost), ctx, scored)
    analyzer_name = getattr(q, "analyzer", None)
    if analyzer_name:
        terms = ctx.mapper.analyzers.get(analyzer_name).terms(
            str(q.query))
    else:
        terms = ft.search_terms(str(q.query), ctx.mapper.analyzers)
    if not terms:
        return _none()
    fuzz = getattr(q, "fuzziness", None)
    if fuzz is not None:
        clauses = [dsl.FuzzyQuery(field=q.field, value=t,
                                  fuzziness=fuzz) for t in terms[:-1]]
    else:
        clauses = [dsl.TermQuery(field=q.field, value=t)
                   for t in terms[:-1]]
    expansions = _expand_prefix_terms(ctx, q.field, terms[-1],
                                      int(q.max_expansions))
    if expansions:
        # capped dictionary expansion, like the phrase-prefix sibling
        clauses.append(dsl.TermsQuery(field=q.field, values=expansions)
                       if len(expansions) > 1
                       else dsl.TermQuery(field=q.field,
                                          value=expansions[0]))
    elif not clauses:
        return _none()
    # an unexpandable prefix contributes nothing; other clauses (e.g.
    # fuzzy terms) still match under OR semantics
    if q.operator == "and":
        return compile_query(dsl.BoolQuery(must=clauses, boost=q.boost),
                             ctx, scored)
    msm = getattr(q, "minimum_should_match", None) or "1"
    return compile_query(dsl.BoolQuery(should=clauses,
                                       minimum_should_match=str(msm),
                                       boost=q.boost), ctx, scored)


def _positive_float(v, what: str) -> float:
    try:
        f = float(v)
    except (TypeError, ValueError):
        raise ParsingError(
            f"[rank_feature] {what} must be a number, got [{v}]") from None
    if not math.isfinite(f) or f <= 0:
        raise ParsingError(
            f"[rank_feature] {what} must be positive, got [{v}]")
    return f


def _c_rank_feature(q, ctx, scored):
    """rank_feature scoring lowered onto the script-score plan: the
    saturation/log/sigmoid curves are exactly the painless-subset
    expressions over doc['f'].value (RankFeatureQueryBuilder; the
    feature column is a positive numeric doc value)."""
    ft = _require_ft(ctx, q.field, "rank_feature")
    if ft is None:
        return _none()
    if ft.dv_kind not in ("long", "double"):
        raise IllegalArgumentError(
            f"[rank_feature] field [{q.field}] must be numeric "
            f"(rank_feature type), got [{ft.type_name}]")
    f = f"doc['{q.field}'].value"
    if q.log is not None:
        scaling = float(q.log.get("scaling_factor", 1.0))
        src = f"Math.log({scaling} + {f})"
    elif q.sigmoid is not None:
        if "pivot" not in q.sigmoid or "exponent" not in q.sigmoid:
            raise ParsingError(
                "[rank_feature] sigmoid requires [pivot] and [exponent]")
        pivot = _positive_float(q.sigmoid["pivot"], "sigmoid pivot")
        exp = _positive_float(q.sigmoid["exponent"], "sigmoid exponent")
        src = (f"Math.pow({f}, {exp}) / "
               f"(Math.pow({f}, {exp}) + Math.pow({pivot}, {exp}))")
    else:
        pivot = (q.saturation or {}).get("pivot")
        if pivot is not None:
            pivot = _positive_float(pivot, "saturation pivot")
        if pivot is None:
            # default pivot ~ the field's mean positive value (the
            # reference uses an approximate geometric mean)
            total, count = 0.0, 0
            for seg in ctx.segments:
                dv = seg.numeric_dv.get(q.field)
                if dv is not None and len(dv.values):
                    total += float(np.sum(dv.values))
                    count += int(len(dv.values))
            pivot = (total / count) if count else 1.0
        src = f"{f} / ({f} + {float(pivot)})"
    return compile_query(dsl.ScriptScoreQuery(
        query=dsl.ExistsQuery(field=q.field),
        script={"source": src}, boost=q.boost), ctx, scored)


def _c_neural_sparse(q, ctx, scored):
    """``neural_sparse`` with ``query_tokens``: the scored term bag over
    the ``rank_features`` field's postings in its feature lowering
    (``TermBagPlan.features``): idf 1, the query's token weights, any
    token matches.  score = sum over the query's tokens of f32(weight) *
    the stored feature weight, accumulated in float32 (the plug-in's
    BooleanQuery of ``FeatureField.newLinearQuery`` clauses).  Tokens no
    segment of the shard holds are dropped here.  Span ``sparse.bind``
    covers the look-up and the bind; the ``search.neural_sparse.*``
    counters move where the bound plan runs (``ShardSearcher._topk``)."""
    from opensearch_tpu.common.telemetry import tracer

    ft = ctx.field_type(q.field)
    if ft is None or ft.type_name != "rank_features":
        raise IllegalArgumentError(
            f"[neural_sparse] query only works on [rank_features] fields, "
            f"and [{q.field}] is "
            f"{'unmapped' if ft is None else '[' + ft.type_name + ']'}")
    with tracer().start_span("sparse.bind",
                             {"tokens": len(q.tokens)}) as span:
        known = [(t, w) for t, w in q.tokens if ctx.df(q.field, t)]
        span.set_attribute("known", len(known))
        if not known:
            return _none()
        weights = (np.asarray([w for _t, w in known], np.float32)
                   * np.float32(q.boost))
        bind = {"terms": tuple(t for t, _w in known),
                "idfs": np.ones(len(known), np.float32),
                "weights": weights, "avgdl": 1.0, "required": 1}
        return P.TermBagPlan(field=q.field, scored=scored,
                             features=True), bind


# span end disabled: any analyzer position is < this (< ops.phrase
# POS_BASE so doc*POS_BASE+pos arithmetic can't overflow)
_SPAN_NO_END = 1 << 21


def _span_near_state(ctx, field, terms, *, slop, ordered, end, boost,
                     scored):
    stats = ctx.field_stats(field)
    idf_sum = float(np.sum(_idfs_for(ctx, field, terms)))
    bind = {"terms": tuple(terms), "slop": int(slop), "end": int(end),
            "idf_sum": idf_sum, "boost": boost, "avgdl": stats.avgdl}
    return P.SpanNearPlan(field=field, ordered=ordered,
                          scored=scored), bind


def _c_span_term(q, ctx, scored):
    ft = _require_ft(ctx, q.field, "span_term")
    if ft is None:
        return _none()
    return _term_bag(ctx, q.field, [str(q.value)], 1, q.boost, scored)


def _span_clause_terms(clauses, qname):
    """Validate span sub-clauses: span_term only, one shared field."""
    field, terms = None, []
    for c in clauses:
        if not isinstance(c, dsl.SpanTermQuery):
            raise IllegalArgumentError(
                f"[{qname}] supports span_term clauses only, got "
                f"[{type(c).__name__}]")
        if field is None:
            field = c.field
        elif c.field != field:
            raise IllegalArgumentError(
                f"[{qname}] clauses must target a single field, got "
                f"[{field}] and [{c.field}]")
        terms.append(str(c.value))
    return field, terms


def _c_span_near(q, ctx, scored):
    field, terms = _span_clause_terms(q.clauses, "span_near")
    ft = _require_ft(ctx, field, "span_near")
    if ft is None:
        return _none()
    if len(terms) == 1:
        return _term_bag(ctx, field, terms, 1, q.boost, scored)
    if not q.in_order and len(terms) > 2:
        raise IllegalArgumentError(
            "[span_near] with [in_order]=false supports at most 2 "
            "clauses (unordered minimal-window matching beyond pairs "
            "is not implemented)")
    return _span_near_state(ctx, field, terms, slop=q.slop,
                            ordered=q.in_order, end=_SPAN_NO_END,
                            boost=q.boost, scored=scored)


def _c_span_first(q, ctx, scored):
    # restricted to a span_term match so 'span ends before [end]'
    # is exact (a single term at pos occupies [pos, pos+1))
    if not isinstance(q.match, dsl.SpanTermQuery):
        raise IllegalArgumentError(
            "[span_first] supports a span_term [match] only")
    ft = _require_ft(ctx, q.match.field, "span_first")
    if ft is None:
        return _none()
    return _span_near_state(ctx, q.match.field, [str(q.match.value)],
                            slop=0, ordered=True, end=q.end,
                            boost=q.boost, scored=scored)


def _c_span_or(q, ctx, scored):
    _span_clause_terms(q.clauses, "span_or")   # validation only
    return compile_query(
        dsl.BoolQuery(should=list(q.clauses), minimum_should_match="1",
                      boost=q.boost), ctx, scored)


def _c_intervals(q, ctx, scored):
    """intervals: match / any_of / all_of rules (ref
    IntervalQueryBuilder.java:43).  match compiles to the span kernel;
    any_of is a should-of-1; all_of with unbounded gaps and no order is
    positionless AND, otherwise its sub-rules must be single terms so it
    flattens to one ordered/unordered near."""
    ft = _require_ft(ctx, q.field, "intervals")
    if ft is None:
        return _none()

    def rule_terms(rule):
        m = rule.get("match")
        if m is None or not isinstance(m, dict):
            return None
        analyzer = ctx.mapper.analyzers.get(ft.search_analyzer_name)
        return [t.term for t in analyzer.analyze(str(m.get("query", "")))]

    def compile_rule(rule):
        if len(rule) != 1:
            raise IllegalArgumentError(
                f"[intervals] rule must have exactly one key, got "
                f"{sorted(rule)}")
        kind, body = next(iter(rule.items()))
        allowed = {"match": {"query", "ordered", "max_gaps", "mode"},
                   "any_of": {"intervals"},
                   "all_of": {"intervals", "ordered", "max_gaps",
                              "mode"}}
        if kind in allowed and isinstance(body, dict):
            extra = set(body) - allowed[kind]
            if extra:
                # silently dropping filter/analyzer/use_field/... would
                # return over-broad results — reject like every other
                # unsupported interval feature
                raise IllegalArgumentError(
                    f"[intervals] [{kind}] options {sorted(extra)} are "
                    f"not supported — supported: "
                    f"{sorted(allowed[kind])}")
        if kind == "match":
            terms = rule_terms(rule)
            if not terms:
                return _none()
            mode = body.get("mode")
            ordered = (mode == "ordered" if mode is not None
                       else bool(body.get("ordered", False)))
            max_gaps = int(body.get("max_gaps", -1))
            if len(terms) == 1:
                return _term_bag(ctx, q.field, terms, 1, q.boost, scored)
            if max_gaps < 0 and not ordered:
                return compile_query(dsl.BoolQuery(must=[
                    dsl.TermQuery(field=q.field, value=t)
                    for t in terms]), ctx, scored)
            if not ordered and len(terms) > 2:
                raise IllegalArgumentError(
                    "[intervals] unordered [match] with [max_gaps] "
                    "supports at most 2 terms")
            slop = max_gaps if max_gaps >= 0 else _SPAN_NO_END
            return _span_near_state(ctx, q.field, terms, slop=slop,
                                    ordered=ordered, end=_SPAN_NO_END,
                                    boost=q.boost, scored=scored)
        if kind in ("any_of", "all_of"):
            subs = body.get("intervals") or []
            if not subs:
                raise IllegalArgumentError(
                    f"[intervals] [{kind}] requires [intervals]")
            if body.get("mode") is not None:
                body = {**body, "ordered": body["mode"] == "ordered"}
            if kind == "all_of" and (body.get("ordered")
                                     or int(body.get("max_gaps", -1)) >= 0):
                # positional all_of flattens iff every sub-rule is a
                # single-term match
                flat = [rule_terms(s) for s in subs]
                if any(t is None or len(t) != 1 for t in flat):
                    raise IllegalArgumentError(
                        "[intervals] [all_of] with [ordered]/[max_gaps] "
                        "supports single-term [match] sub-rules only")
                terms = [t[0] for t in flat]
                ordered = bool(body.get("ordered", False))
                max_gaps = int(body.get("max_gaps", -1))
                if not ordered and len(terms) > 2:
                    raise IllegalArgumentError(
                        "[intervals] unordered [all_of] with [max_gaps] "
                        "supports at most 2 sub-rules")
                slop = max_gaps if max_gaps >= 0 else _SPAN_NO_END
                return _span_near_state(ctx, q.field, terms, slop=slop,
                                        ordered=ordered,
                                        end=_SPAN_NO_END,
                                        boost=q.boost, scored=scored)
            wrapped = [dsl.IntervalsQuery(field=q.field, rule=s)
                       for s in subs]
            if kind == "any_of":
                return compile_query(
                    dsl.BoolQuery(should=wrapped,
                                  minimum_should_match="1",
                                  boost=q.boost), ctx, scored)
            return compile_query(dsl.BoolQuery(must=wrapped,
                                               boost=q.boost),
                                 ctx, scored)
        if kind in ("prefix", "wildcard", "regexp"):
            # multi-term rules expand against the term dictionary and
            # compile as a should-of-1 over the expansions
            # (IntervalsSourceProvider's Prefix/Wildcard/Regexp; the
            # reference has no fuzzy interval source, so `fuzzy` — an
            # edit-distance expansion with no positional semantics here —
            # is rejected below rather than silently over-matching)
            import re as _re

            if kind == "prefix":
                pat = str(body.get("prefix", ""))
                terms = _expand_prefix_terms(ctx, q.field, pat, 128)
            else:
                pat = str(body.get("pattern", ""))
                flags = (_re.IGNORECASE
                         if body.get("case_insensitive") else 0)
                if kind == "wildcard":
                    import fnmatch
                    rx = _re.compile(fnmatch.translate(pat), flags)
                else:
                    rx = _re.compile(pat, flags)
                terms = []
                seen = set()
                for seg in ctx.segments:
                    if q.field not in seg.postings:
                        continue
                    for t in ctx.sorted_terms(seg, q.field):
                        if t not in seen and rx.fullmatch(t):
                            seen.add(t)
                            terms.append(t)
                        if len(terms) >= 128:
                            break
            if not terms:
                return _none()
            return compile_query(dsl.BoolQuery(
                should=[dsl.TermQuery(field=q.field, value=t)
                        for t in terms],
                minimum_should_match="1", boost=q.boost), ctx, scored)
        raise IllegalArgumentError(
            f"[intervals] unsupported rule [{kind}] — supported: "
            "match, any_of, all_of, prefix, wildcard, regexp")

    return compile_rule(q.rule)


_DECAY_FNS = ("gauss", "exp", "linear")


def _c_function_score(q, ctx, scored):
    """function_score: per-function specs compile to static FunctionSpec
    structure + dynamic param binds (functionscore/ dir; decay, fvf,
    random_score, weight, script_score functions)."""
    from opensearch_tpu.search.query_dsl import (parse_distance_m,
                                                 parse_geo_point,
                                                 parse_query)
    from opensearch_tpu.search.scripting import compile_score_script

    child = q.query if q.query is not None else dsl.MatchAllQuery()
    cplan, cbind = compile_query(child, ctx, scored=True)
    specs, binds = [], []
    for f in q.functions:
        f = dict(f)
        fbind = {}
        fplan = None
        if f.get("filter") is not None:
            fplan, fb = compile_query(parse_query(f["filter"]), ctx,
                                      scored=False)
            fbind["filter"] = fb
        if "weight" in f:
            fbind["weight"] = float(f["weight"])
        decay_fn = next((d for d in _DECAY_FNS if d in f), None)
        if "field_value_factor" in f:
            fvf = f["field_value_factor"]
            field = fvf.get("field")
            ft = ctx.field_type(field or "")
            if ft is None or ft.dv_kind not in ("long", "double"):
                raise IllegalArgumentError(
                    f"[field_value_factor] field [{field}] must be "
                    "numeric")
            specs.append(P.FunctionSpec(
                kind="field_value_factor", filter=fplan, field=field,
                modifier=str(fvf.get("modifier", "none")).lower()))
            fbind.update({"factor": float(fvf.get("factor", 1.0)),
                          "missing": float(fvf.get("missing", 1.0))})
        elif "random_score" in f:
            rs = f.get("random_score") or {}
            specs.append(P.FunctionSpec(kind="random_score",
                                        filter=fplan))
            fbind["seed"] = float(rs.get("seed", 0))
        elif "script_score" in f:
            program = compile_score_script(
                (f["script_score"] or {}).get("script") or {})
            specs.append(P.FunctionSpec(kind="script_score",
                                        filter=fplan, program=program))
        elif decay_fn is not None:
            body = f[decay_fn]
            ((field, conf),) = tuple(body.items()) if len(body) == 1 \
                else (_raise_decay(),)
            ft = ctx.field_type(field)
            if ft is None:
                return _none()
            if ft.dv_kind == "geo_point":
                lat, lon = parse_geo_point(conf["origin"])
                fbind.update({"origin_lat": lat, "origin_lon": lon,
                              "scale": parse_distance_m(conf["scale"]),
                              "offset": parse_distance_m(
                                  conf.get("offset", 0))})
                geo = True
            elif ft.type_name == "date":
                from opensearch_tpu.search.aggs import _parse_duration_ms

                def dur(v):
                    return float(_parse_duration_ms(v)
                                 if isinstance(v, str) else v)
                fbind.update({
                    "origin": float(parse_date_millis(conf["origin"])),
                    "scale": dur(conf["scale"]),
                    "offset": dur(conf.get("offset", 0))})
                geo = False
            elif ft.dv_kind in ("long", "double"):
                fbind.update({"origin": float(conf["origin"]),
                              "scale": float(conf["scale"]),
                              "offset": float(conf.get("offset", 0))})
                geo = False
            else:
                raise IllegalArgumentError(
                    f"[{decay_fn}] field [{field}] must be numeric, "
                    "date or geo_point")
            if fbind["scale"] <= 0:
                raise IllegalArgumentError(
                    f"[{decay_fn}] scale must be > 0")
            fbind["decay"] = float(conf.get("decay", 0.5))
            if not (0.0 < fbind["decay"] < 1.0):
                raise IllegalArgumentError(
                    f"[{decay_fn}] decay must be in (0, 1)")
            specs.append(P.FunctionSpec(kind="decay", filter=fplan,
                                        field=field, decay_fn=decay_fn,
                                        geo=geo))
        elif "weight" in f:
            specs.append(P.FunctionSpec(kind="weight", filter=fplan))
        else:
            raise IllegalArgumentError(
                f"unknown function_score function {sorted(f)}")
        binds.append(fbind)
    if q.score_mode not in ("multiply", "sum", "avg", "first", "max",
                            "min"):
        raise IllegalArgumentError(
            f"unknown score_mode [{q.score_mode}]")
    if q.boost_mode not in ("multiply", "replace", "sum", "avg", "max",
                            "min"):
        raise IllegalArgumentError(
            f"unknown boost_mode [{q.boost_mode}]")
    return (P.FunctionScorePlan(child=cplan, functions=tuple(specs),
                                score_mode=q.score_mode,
                                boost_mode=q.boost_mode),
            {"child": cbind, "functions": tuple(binds), "boost": q.boost,
             "max_boost": q.max_boost, "min_score": q.min_score})


def _raise_decay():
    raise IllegalArgumentError(
        "decay function must name exactly one field")


def _c_more_like_this(q, ctx, scored):
    """more_like_this: host-side tf-idf term selection over the like
    texts/docs, compiled as a should term-bag (MoreLikeThisQueryBuilder's
    interesting-terms selection)."""
    fields = q.fields
    if not fields:
        fields = [f for f, ft in ctx.mapper.field_types().items()
                  if isinstance(ft, TextFieldType)]
    if not fields:
        return _none()
    texts: list[str] = []
    liked_ids: list[str] = []
    for item in q.like:
        if isinstance(item, dict):
            doc_id = item.get("_id")
            src = None
            for seg in ctx.segments:
                local = seg.id_to_local.get(str(doc_id))
                if local is not None:
                    src = seg.source(local)
                    break
            if src is None:
                continue
            liked_ids.append(str(doc_id))
            for f in fields:
                v = src.get(f)
                if isinstance(v, str):
                    texts.append(v)
        else:
            texts.append(str(item))
    if not texts:
        return _none()
    clauses = []
    for field in fields:
        ft = ctx.field_type(field)
        if not isinstance(ft, TextFieldType):
            continue
        tf: dict[str, int] = {}
        for text in texts:
            for t in ft.search_terms(text, ctx.mapper.analyzers):
                tf[t] = tf.get(t, 0) + 1
        n_docs = max(ctx.field_stats(field).doc_count, 1)
        cands = []
        for t, freq in tf.items():
            if freq < q.min_term_freq:
                continue
            df = ctx.df(field, t)
            if df < q.min_doc_freq:
                continue
            idf = np.log(1.0 + (n_docs - df + 0.5) / (df + 0.5))
            cands.append((freq * idf, t))
        cands.sort(key=lambda x: (-x[0], x[1]))
        terms = [t for _s, t in cands[: q.max_query_terms]]
        if terms:
            required = max(1, calc_min_should_match(
                len(terms), q.minimum_should_match))
            clauses.append(_term_bag(ctx, field, terms, required,
                                     q.boost, scored))
    if not clauses:
        return _none()
    if len(clauses) == 1 and not liked_ids:
        return clauses[0]
    # the liked input docs are EXCLUDED unless include:true (the
    # reference's default — a doc is trivially most-like itself)
    must_not = ()
    if liked_ids and not q.include:
        must_not = (compile_query(dsl.IdsQuery(values=liked_ids), ctx,
                                  scored=False),)
    plans = tuple(p for p, _b in clauses)
    return (P.BoolPlan(should=plans,
                       must_not=tuple(p for p, _b in must_not)),
            {"boost": 1.0, "required": 1,
             "children": (tuple(b for _p, b in clauses)
                          + tuple(b for _p, b in must_not))})


def _c_script_score(q, ctx, scored):
    """script_score: the child query's matched set rescored by a compiled
    jnp expression (search/scripting.py); BASELINE config #2's
    knn-via-script shape lowers onto the exact-knn kernels.  Unknown or
    unsupported scripts raise ScriptException -> a clean 400."""
    from opensearch_tpu.search.scripting import (ScriptException,
                                                 compile_score_script)

    program = compile_score_script(q.script)
    for f in program.numeric_fields:
        ft = ctx.field_type(f)
        if ft is not None and ft.dv_kind not in ("long", "double"):
            raise ScriptException(
                f"doc['{f}'].value requires a numeric/date field, "
                f"[{f}] is [{ft.type_name}]")
    for f in program.vector_fields:
        ft = ctx.field_type(f)
        if ft is not None and ft.dv_kind != "vector":
            raise ScriptException(
                f"vector function over [{f}] requires a knn_vector "
                f"field, got [{ft.type_name}]")
    child = q.query if q.query is not None else dsl.MatchAllQuery()
    cplan, cbind = compile_query(child, ctx, scored=program.uses_score)
    return (P.ScriptScorePlan(child=cplan, program=program),
            {"child": cbind, "boost": q.boost, "min_score": q.min_score})


_COMPILERS = {
    dsl.MatchAllQuery: _c_match_all,
    dsl.MatchNoneQuery: _c_match_none,
    dsl.TermQuery: _c_term,
    dsl.TermsQuery: _c_terms,
    dsl.MatchQuery: _c_match,
    dsl.MatchPhraseQuery: _c_match_phrase,
    dsl.MultiMatchQuery: _c_multi_match,
    dsl.BoolQuery: _c_bool,
    dsl.RangeQuery: _c_range,
    dsl.ExistsQuery: _c_exists,
    dsl.IdsQuery: _c_ids,
    dsl.HasChildQuery: _c_has_child,
    dsl.HasParentQuery: _c_has_parent,
    dsl.ParentIdQuery: _c_parent_id,
    dsl.PrefixQuery: _c_prefix,
    dsl.WildcardQuery: _c_wildcard,
    dsl.RegexpQuery: _c_regexp,
    dsl.FuzzyQuery: _c_fuzzy,
    dsl.ConstantScoreQuery: _c_constant_score,
    dsl.DisMaxQuery: _c_dis_max,
    dsl.SimpleQueryStringQuery: _c_simple_query_string,
    dsl.KnnQuery: _c_knn,
    dsl.NeuralSparseQuery: _c_neural_sparse,
    dsl.ScriptScoreQuery: _c_script_score,
    dsl.BoostingQuery: _c_boosting,
    dsl.NestedQuery: _c_nested,
    dsl.PercolateQuery: _c_percolate,
    dsl.TermsSetQuery: _c_terms_set,
    dsl.DistanceFeatureQuery: _c_distance_feature,
    dsl.FunctionScoreQuery: _c_function_score,
    dsl.MoreLikeThisQuery: _c_more_like_this,
    dsl.GeoDistanceQuery: _c_geo_distance,
    dsl.GeoPolygonQuery: _c_geo_polygon,
    dsl.MatchPhrasePrefixQuery: _c_match_phrase_prefix,
    dsl.MatchBoolPrefixQuery: _c_match_bool_prefix,
    dsl.RankFeatureQuery: _c_rank_feature,
    dsl.GeoBoundingBoxQuery: _c_geo_bounding_box,
    dsl.SpanTermQuery: _c_span_term,
    dsl.SpanNearQuery: _c_span_near,
    dsl.SpanFirstQuery: _c_span_first,
    dsl.SpanOrQuery: _c_span_or,
    dsl.IntervalsQuery: _c_intervals,
}
