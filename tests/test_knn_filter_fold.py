"""PR 33's program change: a ``bool.filter`` of ``term`` clauses on one
field is ONE filtering term bag with ``required`` = the number of terms
(``search/compiler.py::_fold_filter_terms``), and a filtered ``knn`` has
spans and counters of its own (``_knn_filter_masks``)."""

import numpy as np
import pytest

from opensearch_tpu.common.telemetry import metrics, tracer
from opensearch_tpu.index.segment import SegmentWriter
from opensearch_tpu.mapping.mapper import DocumentMapper
from opensearch_tpu.search import compiler, plan as P
from opensearch_tpu.search import query_dsl as dsl
from opensearch_tpu.search.executor import ShardSearcher, build_arrays

DIM = 8
TAGS = ["red", "green", "blue", "round", "square", "small"]


def build(n_docs=180, n_segments=3, seed=33):
    """Rows with a vector, a bag of one to four tags, a second keyword
    and a number; tag ``blue`` is missing from the last segment."""
    rng = np.random.default_rng(seed)
    mapper = DocumentMapper({"properties": {
        "vec": {"type": "knn_vector", "dimension": DIM, "space_type": "l2"},
        "tags": {"type": "keyword"}, "kind": {"type": "keyword"},
        "n": {"type": "long"}, "body": {"type": "text"}}})
    writer = SegmentWriter()
    segments, vectors, bags, kinds = [], [], [], []
    per = n_docs // n_segments
    for si in range(n_segments):
        pool = [t for t in TAGS if not (t == "blue" and si == n_segments - 1)]
        parsed = []
        for _ in range(per):
            i = len(vectors)
            v = rng.integers(0, 64, size=DIM).astype(np.float32)
            bag = sorted(rng.choice(pool, size=int(rng.integers(1, 5)),
                                    replace=False).tolist())
            vectors.append(v)
            bags.append(set(bag))
            kinds.append(["a", "b"][i % 2])
            parsed.append(mapper.parse(str(i), {
                "vec": v.tolist(), "tags": bag, "kind": kinds[-1],
                "n": i % 3, "body": "common text"}))
        segments.append(writer.build(parsed, f"s{si}"))
    return ShardSearcher(segments, mapper), np.stack(vectors), bags, kinds


@pytest.fixture(scope="module")
def corpus():
    return build()


def terms(*tags, field="tags"):
    return [{"term": {field: t}} for t in tags]


def masks(searcher, query_json) -> list:
    """The query's matched mask in every segment, through the compiler
    and ``plan.run_full``."""
    ctx = searcher.ctx
    plan, bind = compiler.compile_query(dsl.parse_query(query_json), ctx,
                                        scored=False)
    return plan, _run(searcher, plan, bind)


def _run(searcher, plan, bind) -> list:
    import jax.numpy as jnp

    out = []
    for seg in searcher.segments:
        dseg = seg.device()
        A = build_arrays(dseg, plan.arrays(), searcher.ctx.mapper)
        dims, ins = plan.prepare(bind, seg, dseg, searcher.ctx)
        _s, m = P.run_full(plan, dims, A, ins, jnp.float32(-np.inf))
        out.append(np.asarray(m)[: seg.n_docs])
    return out


def unfolded(searcher, tags):
    """The plan the clauses spelt before the fold: a BoolPlan with one
    one-term bag a clause."""
    subs = [compiler.compile_query(dsl.parse_query(t), searcher.ctx, False)
            for t in terms(*tags)]
    plan = P.BoolPlan(filter=tuple(p for p, _b in subs))
    bind = {"boost": 1.0, "required": 0,
            "children": tuple(b for _p, b in subs)}
    return plan, bind


@pytest.mark.parametrize("tags", [("red", "round"), ("blue", "small"),
                                  ("red", "green", "square"),
                                  ("red", "red", "round"),
                                  ("red", "nowhere")])
def test_same_field_filter_terms_fold_to_one_bag_with_the_same_mask(corpus,
                                                                     tags):
    searcher, _v, bags, _k = corpus
    plan, got = masks(searcher, {"bool": {"filter": terms(*tags)}})
    assert isinstance(plan, P.TermBagPlan) and not plan.scored
    want = np.array([set(tags) <= bag for bag in bags])
    assert np.array_equal(np.concatenate(got), want)
    assert np.array_equal(np.concatenate(_run(searcher,
                                              *unfolded(searcher, tags))),
                          want)


def test_the_fold_is_one_program_input_a_segment(corpus):
    searcher, *_ = corpus
    ctx = searcher.ctx
    plan, bind = compiler.compile_query(dsl.parse_query(
        {"bool": {"filter": terms("red", "round", "small")}}), ctx, False)
    assert bind["required"] == 3 and bind["terms"] == ("red", "round",
                                                       "small")
    seg = searcher.segments[0]
    dims, ins = plan.prepare(bind, seg, seg.device(), ctx)
    assert dims == (4, 4096, False) and len(ins) == 1
    # the same term twice is one term of the conjunction
    _p, twice = compiler.compile_query(dsl.parse_query(
        {"bool": {"filter": terms("red", "red", "round")}}), ctx, False)
    assert twice["required"] == 2 and twice["terms"] == ("red", "round")


def test_the_folded_bool_answers_as_a_plain_query(corpus):
    searcher, _v, bags, _k = corpus
    resp = searcher.search({"query": {"bool": {
        "filter": terms("green", "square")}}, "size": 500})
    want = {str(i) for i, bag in enumerate(bags)
            if {"green", "square"} <= bag}
    assert {h["_id"] for h in resp["hits"]["hits"]} == want
    assert resp["hits"]["total"]["value"] == len(want) > 0
    assert all(h["_score"] == 0.0 for h in resp["hits"]["hits"])


def test_other_conjunctions_are_left_as_they_are(corpus):
    searcher, _v, bags, kinds = corpus
    ctx = searcher.ctx

    def compiled(q, scored=False):
        return compiler.compile_query(dsl.parse_query(q), ctx, scored)

    # two fields: a child a clause
    plan, _b = compiled({"bool": {"filter": terms("red") + terms(
        "a", field="kind")}})
    assert isinstance(plan, P.BoolPlan) and len(plan.filter) == 2
    # two of one field beside one of another: the pair folds, in a BoolPlan
    plan, bind = compiled({"bool": {"filter": terms("red", "round") + terms(
        "a", field="kind")}})
    assert isinstance(plan, P.BoolPlan) and len(plan.filter) == 2
    assert [b["required"] for b in bind["children"]] == [2, 1]
    got = np.concatenate(_run(searcher, plan, bind))
    assert np.array_equal(got, np.array([
        {"red", "round"} <= bag and k == "a" for bag, k in zip(bags, kinds)]))
    # scoring clauses keep a bag each, and their scores
    plan, _b = compiled({"bool": {"must": terms("red", "round")}}, True)
    assert isinstance(plan, P.BoolPlan) and len(plan.must) == 2
    assert all(isinstance(c, P.TermBagPlan) and c.scored
               for c in plan.must)
    # a filter beside a scoring clause stays inside its bool
    plan, _b = compiled({"bool": {"must": [{"match": {"body": "common"}}],
                                  "filter": terms("red", "round")}}, True)
    assert isinstance(plan, P.BoolPlan)
    assert len(plan.must) == 1 and len(plan.filter) == 1
    # numerics lower to their own plan, one clause or two
    plan, _b = compiled({"bool": {"filter": terms(0, 1, field="n")}})
    assert isinstance(plan, P.BoolPlan) and all(
        isinstance(c, P.NumericTermsPlan) for c in plan.filter)
    # one clause is what it was
    plan, _b = compiled({"bool": {"filter": terms("red")}})
    assert isinstance(plan, P.BoolPlan) and len(plan.filter) == 1


def _knn(x: float, flt=None) -> dict:
    spec = {"vector": [x] * DIM, "k": 5}
    if flt is not None:
        spec["filter"] = flt
    return {"query": {"knn": {"vec": spec}}, "size": 5}


def _counters() -> tuple:
    return (metrics().counter("search.knn.filtered.requests").value,
            metrics().counter("search.knn.filter.programs").value)


@pytest.mark.parametrize("flt,clauses", [
    ({"term": {"tags": "red"}}, 1),
    ({"bool": {"filter": terms("red", "round")}}, 2)])
def test_a_filtered_knn_is_exact_and_has_its_spans_and_counters(corpus, flt,
                                                                clauses):
    searcher, vectors, bags, _k = corpus
    need = {"red"} if clauses == 1 else {"red", "round"}
    before = _counters()
    tracer().reset()
    resp = searcher.search(_knn(7.0 + clauses, flt))
    spans = tracer().recent(64)[::-1]
    q = np.full(DIM, 7.0 + clauses)
    d2 = ((vectors.astype(np.float64) - q) ** 2).sum(axis=1)
    passing = [i for i, bag in enumerate(bags) if need <= bag]
    order = sorted(passing, key=lambda i: (d2[i], i))[:5]
    assert [h["_id"] for h in resp["hits"]["hits"]] == [str(i)
                                                       for i in order]
    for h, i in zip(resp["hits"]["hits"], order):
        assert h["_score"] == pytest.approx(1.0 / (1.0 + d2[i]), rel=1e-5)
    by_name = {s["name"]: s for s in spans}
    by_id = {s["span_id"]: s for s in spans}
    for name in ("knn.filter", "knn.scan"):
        parent = by_id[by_name[name]["parent_span_id"]]
        assert parent["name"] == "query.plan"
    assert by_name["knn.filter"]["attributes"] == {
        "clauses": clauses, "segments": len(searcher.segments)}
    sync = next(s for s in spans if s["name"] == "device.sync"
                and s["attributes"]["site"] == "knn_prepass")
    assert by_id[sync["parent_span_id"]]["name"] == "query.plan"
    ends = {n: by_name[n]["start_time_in_nanos"]
            + by_name[n]["duration_in_nanos"]
            for n in ("knn.filter", "knn.scan")}
    assert ends["knn.filter"] <= by_name["knn.scan"]["start_time_in_nanos"]
    assert ends["knn.scan"] <= sync["start_time_in_nanos"]
    assert _counters() == (before[0] + 1,
                           before[1] + len(searcher.segments))


def test_an_unfiltered_knn_has_neither(corpus):
    searcher, *_ = corpus
    before = _counters()
    tracer().reset()
    resp = searcher.search(_knn(11.0))
    assert len(resp["hits"]["hits"]) == 5
    names = [s["name"] for s in tracer().recent(64)]
    assert "query.plan" in names and "device.sync" in names
    assert "knn.filter" not in names and "knn.scan" not in names
    assert _counters() == before


def test_the_filter_s_inputs_are_counted_as_staged(corpus):
    """The mask programs' ``min_score`` crosses once a request, through
    the ledger (``h2d_arrays_per_query`` sees it), beside one packed
    array a segment."""
    from opensearch_tpu.common.device_ledger import device_ledger

    searcher, *_ = corpus

    def staged():
        return device_ledger().stats()["transfers"]["input"]["arrays"]

    a = staged()
    searcher.search(_knn(13.0))
    plain = staged() - a
    a = staged()
    searcher.search(_knn(14.0, {"bool": {"filter": terms("red", "round")}}))
    assert staged() - a == plain + 1 + len(searcher.segments)
