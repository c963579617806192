"""``device.slice_gather_programs`` and the kernel agree: for every kind
of plan that reaches ``gather_postings``, alone or under a composite,
``Plan.slice_gathers(dims)`` (what the executor hands the ledger) says
exactly whether tracing the plan's program copied runs as slices."""

import jax
import numpy as np
import pytest

from opensearch_tpu.common.device_ledger import device_ledger
from opensearch_tpu.index.segment import SegmentWriter
from opensearch_tpu.mapping.mapper import DocumentMapper
from opensearch_tpu.ops import bm25 as bm25_ops
from opensearch_tpu.search import engine
from opensearch_tpu.search.executor import ShardSearcher, build_arrays

VOCAB = [f"w{i}" for i in range(300)]
MANY = VOCAB[:200]                 # t_pad 256 over a 4,096 bucket: elements
TERM = {"match": {"body": "w1 w2 w3"}}
MANY_TERMS = {"terms": {"body": MANY}}


@pytest.fixture(scope="module")
def searcher():
    rng = np.random.default_rng(5)
    mapper = DocumentMapper({"properties": {
        "body": {"type": "text"}, "n": {"type": "integer"}}})
    writer = SegmentWriter()
    segs = []
    for s in range(2):
        docs = [mapper.parse(str(s * 60 + i), {
            "body": " ".join(rng.choice(VOCAB, 12)), "n": 1})
            for i in range(60)]
        segs.append(writer.build(docs, f"sg{s}"))
    yield ShardSearcher(segs, mapper)
    device_ledger().reset()


@pytest.fixture(autouse=True)
def device_path(monkeypatch):
    monkeypatch.setattr(engine, "BATCHER_ENABLED", False)


# query -> does its program copy slices?
QUERIES = {
    "term_bag": (TERM, True),
    "term_bag_and": ({"match": {"body": {"query": "w1 w2",
                                         "operator": "and"}}}, True),
    "terms_filter_small": ({"terms": {"body": ["w1", "w2"]}}, True),
    "terms_filter_beyond_threshold": (MANY_TERMS, False),
    "wildcard_beyond_threshold": ({"wildcard": {"body": "w*"}}, False),
    "wildcard_small": ({"wildcard": {"body": "w29?"}}, True),
    "bool_filter_only_elements": (
        {"bool": {"filter": [MANY_TERMS]}}, False),
    "bool_must_slices_filter_elements": (
        {"bool": {"must": [TERM], "filter": [MANY_TERMS]}}, True),
    "bool_no_postings": (
        {"bool": {"filter": [{"range": {"n": {"gte": 0}}}]}}, False),
    "dis_max": ({"dis_max": {"queries": [MANY_TERMS, TERM]}}, True),
    "constant_score": ({"constant_score": {"filter": TERM}}, True),
    "constant_score_elements": (
        {"constant_score": {"filter": MANY_TERMS}}, False),
    "boosting_negative_side": (
        {"boosting": {"positive": MANY_TERMS, "negative": TERM,
                      "negative_boost": 0.5}}, True),
    "function_score_child": (
        {"function_score": {"query": TERM, "functions": [
            {"filter": MANY_TERMS, "weight": 2.0}]}}, True),
    "function_score_filter": (
        {"function_score": {"query": {"match_all": {}}, "functions": [
            {"filter": TERM, "weight": 2.0}]}}, True),
    "script_score": (
        {"script_score": {"query": TERM,
                          "script": {"source": "_score * 2"}}}, True),
    "terms_set": (
        {"terms_set": {"body": {"terms": ["w1", "w2", "w3"],
                                "minimum_should_match_field": "n"}}}, True),
    "match_all": ({"match_all": {}}, False),
}


@pytest.mark.parametrize("name", sorted(QUERIES))
def test_plan_and_kernel_agree(searcher, monkeypatch, name):
    query, want = QUERIES[name]
    plan, bind = searcher.compiled(query)
    copies = []
    real = bm25_ops._copy_runs
    monkeypatch.setattr(
        bm25_ops, "_copy_runs",
        lambda *a, **kw: copies.append(kw["budget"]) or real(*a, **kw))
    for seg in searcher.segments:
        dseg = seg.device()
        dims, ins = plan.prepare(bind, seg, dseg, searcher.ctx)
        A = build_arrays(dseg, plan.arrays(), searcher.mapper,
                         live=searcher.ctx.live_jnp(seg, dseg))
        del copies[:]
        jax.make_jaxpr(lambda A, ins: plan.eval(A, dims, ins))(A, ins)
        assert plan.slice_gathers(dims) is bool(copies) is want, (
            name, dims, copies)


@pytest.mark.parametrize("name,dispatches,slices", [
    ("term_bag", 2, 2),
    ("terms_filter_beyond_threshold", 2, 0),
    ("bool_must_slices_filter_elements", 2, 2),
    ("match_all", 2, 0),
])
def test_counter_moves_with_the_programs_that_copied_slices(
        searcher, name, dispatches, slices):
    """Over two segments: one program a segment, counted where
    ``record_dispatch`` is."""
    before = device_ledger().stats()
    resp = searcher.search({"query": QUERIES[name][0], "size": 3})
    assert resp["hits"]["total"]["value"] > 0
    after = device_ledger().stats()
    assert after["dispatches"] - before["dispatches"] == dispatches
    assert (after["slice_gather_programs"]
            - before["slice_gather_programs"]) == slices
