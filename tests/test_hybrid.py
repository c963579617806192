"""Hybrid BM25+kNN with score normalization (BASELINE config #4;
VERDICT r3 item 9; ref search/pipeline/SearchPipelineService.java:1 +
the neural-search normalization processor)."""

import json
import urllib.error
import urllib.request

import numpy as np
import pytest

from opensearch_tpu.node import Node
from opensearch_tpu.search.pipeline import (NormalizationConfig,
                                            combine_scores,
                                            normalize_scores)

DIM = 8


@pytest.fixture()
def node(tmp_path):
    n = Node(str(tmp_path / "node"), port=0).start()
    rng = np.random.default_rng(11)
    call(n, "PUT", "/hyb", {"mappings": {"properties": {
        "text": {"type": "text"},
        "vec": {"type": "knn_vector", "dimension": DIM,
                "space_type": "l2"}}}})
    vecs = rng.normal(size=(20, DIM)).astype(np.float32)
    words = ["alpha", "beta", "gamma"]
    for i in range(20):
        call(n, "PUT", f"/hyb/_doc/{i}", {
            "text": f"{words[i % 3]} common token{i}",
            "vec": vecs[i].tolist()})
    call(n, "POST", "/hyb/_refresh")
    n._test_vecs = vecs
    yield n
    n.stop()


def call(node, method, path, body=None):
    url = f"http://127.0.0.1:{node.port}{path}"
    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(
        url, data=data, method=method,
        headers={"Content-Type": "application/json"} if data else {})
    try:
        with urllib.request.urlopen(req) as resp:
            payload = resp.read()
            return resp.status, json.loads(payload) if payload else {}
    except urllib.error.HTTPError as e:
        payload = e.read()
        return e.code, json.loads(payload) if payload else {}


def test_normalize_and_combine_units():
    s = np.asarray([1.0, 3.0, 5.0])
    # the list's lowest candidate is floored at the plugin's MIN_SCORE
    assert normalize_scores(s, "min_max").tolist() == [0.001, 0.5, 1.0]
    l2 = normalize_scores(s, "l2")
    assert l2 @ l2 * (s @ s) == pytest.approx((s @ s))
    assert normalize_scores(np.asarray([2.0, 2.0]),
                            "min_max").tolist() == [1.0, 1.0]
    assert combine_scores([0.4, 0.8], [1, 1], "arithmetic_mean") == \
        pytest.approx(0.6)
    assert combine_scores([0.4, 0.8], [3, 1],
                          "arithmetic_mean") == pytest.approx(0.5)
    assert combine_scores([0.0, 0.8], [1, 1],
                          "geometric_mean") == pytest.approx(0.8)
    assert combine_scores([0.5, 0.0], [1, 1],
                          "harmonic_mean") == pytest.approx(0.5)


def test_hybrid_deterministic_normalized_scores(node):
    """min_max + arithmetic_mean over a BM25 and a knn sub-query must be
    reproducible from the two sub-searches run independently."""
    qv = node._test_vecs[4].tolist()
    hybrid_body = {"query": {"hybrid": {"queries": [
        {"match": {"text": "alpha"}},
        {"knn": {"vec": {"vector": qv, "k": 10}}},
    ]}}, "size": 10}
    code, hresp = call(node, "POST", "/hyb/_search", hybrid_body)
    assert code == 200
    hybrid_scores = {h["_id"]: h["_score"] for h in hresp["hits"]["hits"]}
    assert hybrid_scores

    # oracle: run the two sub-queries, min_max each, arithmetic-mean
    _, bm = call(node, "POST", "/hyb/_search",
                 {"query": {"match": {"text": "alpha"}}, "size": 10})
    _, kn = call(node, "POST", "/hyb/_search",
                 {"query": {"knn": {"vec": {"vector": qv, "k": 10}}},
                  "size": 10})

    def mm(resp):
        hits = resp["hits"]["hits"]
        sc = np.asarray([h["_score"] for h in hits])
        norm = normalize_scores(sc, "min_max")
        return {h["_id"]: float(n) for h, n in zip(hits, norm)}

    n1, n2 = mm(bm), mm(kn)
    for did, score in hybrid_scores.items():
        want = (n1.get(did, 0.0) + n2.get(did, 0.0)) / 2.0
        assert score == pytest.approx(want, rel=1e-6), did
    # the top hybrid doc must satisfy BOTH signals better than a
    # BM25-only loser: every doc in the hybrid top beats docs absent
    # from both sub-query tops (trivially, they weren't returned)
    assert hresp["hits"]["max_score"] == max(hybrid_scores.values())


def test_hybrid_with_named_pipeline_weights(node):
    code, _ = call(node, "PUT", "/_search/pipeline/nlp", {
        "phase_results_processors": [{"normalization-processor": {
            "normalization": {"technique": "l2"},
            "combination": {"technique": "arithmetic_mean",
                            "parameters": {"weights": [0.3, 0.7]}}}}]})
    assert code == 200
    qv = node._test_vecs[2].tolist()
    code, resp = call(node, "POST",
                      "/hyb/_search?search_pipeline=nlp",
                      {"query": {"hybrid": {"queries": [
                          {"match": {"text": "beta"}},
                          {"knn": {"vec": {"vector": qv, "k": 5}}}]}},
                       "size": 5})
    assert code == 200 and resp["hits"]["hits"]
    # pipeline CRUD surface
    code, resp = call(node, "GET", "/_search/pipeline/nlp")
    assert code == 200 and "nlp" in resp
    code, resp = call(node, "DELETE", "/_search/pipeline/nlp")
    assert code == 200
    code, resp = call(node, "GET", "/_search/pipeline/nlp")
    assert code == 404
    code, resp = call(node, "GET", "/hyb/_search?search_pipeline=nlp")
    assert code == 404                     # vanished pipeline -> error


def test_hybrid_rejects_sort_aggs_and_bad_pipeline(node):
    body = {"query": {"hybrid": {"queries": [{"match_all": {}}]}},
            "sort": [{"_score": "desc"}]}
    code, _ = call(node, "POST", "/hyb/_search", body)
    assert code == 400
    code, _ = call(node, "PUT", "/_search/pipeline/bad", {
        "phase_results_processors": [{"normalization-processor": {
            "normalization": {"technique": "softmax"}}}]})
    assert code == 400
    code, _ = call(node, "PUT", "/_search/pipeline/bad2", {
        "phase_results_processors": [{"not-a-processor": {}}]})
    assert code == 400


@pytest.mark.parametrize("scores,want", [
    ([7.5], [1.0]),                        # a single candidate
    ([2.0, 2.0, 2.0], [1.0, 1.0, 1.0]),    # a degenerate range
    ([4.0, 1.0], [1.0, 0.001]),            # only the exact zero is raised
    ([1.0, 1.0005, 2.0], [0.001, 0.0005, 1.0]),
])
def test_min_max_floor_and_single_candidate(scores, want):
    got = normalize_scores(np.asarray(scores, np.float64), "min_max")
    assert got.tolist() == pytest.approx(want, rel=1e-9)


def test_a_listed_candidate_outranks_an_absent_one():
    """The floor's purpose: the last of one list (0.001 / 2) still beats
    nothing, and the union's size comes back beside the rows."""
    rows_a = [{"seg": 0, "local": i, "score": s}
              for i, s in enumerate([3.0, 2.0, 1.0])]
    rows_b = [{"seg": 0, "local": 7, "score": 9.0}]
    combined, n_union = NormalizationConfig().apply([rows_a, rows_b], 10)
    assert n_union == 4 and len(combined) == 4
    by_doc = {r["local"]: r["score"] for r in combined}
    assert by_doc[2] == pytest.approx(0.0005)
    assert by_doc[7] == pytest.approx(0.5) and by_doc[0] == pytest.approx(0.5)
    assert NormalizationConfig().apply([rows_a, rows_b], 2)[1] == 4


def _hybrid_body(node, **extra):
    return {"query": {"hybrid": {"queries": [
        {"match": {"text": "alpha"}},
        {"knn": {"vec": {"vector": node._test_vecs[4].tolist(), "k": 10}}},
    ]}}, "size": 10, **extra}


def test_profiled_hybrid_request_carries_a_shard_section(node):
    code, plain = call(node, "POST", "/hyb/_search", _hybrid_body(node))
    assert code == 200 and "profile" not in plain
    code, resp = call(node, "POST", "/hyb/_search",
                      _hybrid_body(node, profile=True))
    assert code == 200
    shard = resp["profile"]["shards"][0]
    assert shard["id"] == "[hyb][0]"
    assert shard["engine"]["execution_path"] in ("device", "host")
    hybrid = shard["engine"]["hybrid"]
    assert len(shard["searches"]) == len(hybrid["sub_queries"]) == 2
    assert hybrid["normalization"] == "min_max"
    assert hybrid["combination"] == "arithmetic_mean"
    assert hybrid["candidates"] >= len(resp["hits"]["hits"])
    for search, engine in zip(shard["searches"], hybrid["sub_queries"]):
        assert search["query"][0]["breakdown"]["dispatch_count"] >= 1
        assert engine["segments"]["scanned"] >= 1
    assert [s["query"][0]["type"] for s in shard["searches"]] == [
        "TermBagPlan", "ScoredMaskPlan"]
    # profiling changes nothing a user sees
    assert ([(h["_id"], h["_score"]) for h in resp["hits"]["hits"]]
            == [(h["_id"], h["_score"]) for h in plain["hits"]["hits"]])


def test_hybrid_spans_and_counters_once_a_subquery_and_once_a_request(node):
    def counters():
        _, stats = call(node, "GET", "/_nodes/stats")
        c = next(iter(stats["nodes"].values()))["telemetry"]["counters"]
        return {k: c.get(f"search.hybrid.{k}", 0)
                for k in ("requests", "subqueries", "candidates")}

    before = counters()
    _, resp = call(node, "POST", "/hyb/_search", _hybrid_body(node))
    after = counters()
    assert after["requests"] - before["requests"] == 1
    assert after["subqueries"] - before["subqueries"] == 2
    n_union = after["candidates"] - before["candidates"]
    assert len(resp["hits"]["hits"]) <= n_union <= 20

    _, trace = call(node, "GET", "/_nodes/trace?size=200")
    spans = next(iter(trace["nodes"].values()))["spans"]
    phase = next(s for s in spans if s["name"] == "shard.query_phase")
    mine = [s for s in spans if s["trace_id"] == phase["trace_id"]]
    subs = sorted((s for s in mine if s["name"] == "hybrid.subquery"),
                  key=lambda s: s["attributes"]["i"])
    assert [(s["attributes"]["i"], s["attributes"]["type"])
            for s in subs] == [(0, "term_bag"), (1, "knn")]
    assert all(s["parent_span_id"] == phase["span_id"] for s in subs)
    names = [s["name"] for s in mine]
    assert names.count("hybrid.normalize") == 1
    assert names.count("fetch_phase") == 1
    # each sub-query planned under its own span, inside hybrid.subquery
    plans = [s for s in mine if s["name"] == "query.plan"]
    assert sorted(s["parent_span_id"] for s in plans) == sorted(
        s["span_id"] for s in subs)


def test_breaker_limits_are_dynamic_cluster_settings(node):
    from opensearch_tpu.common.breakers import breaker_service

    svc = breaker_service()
    built = (svc.fielddata.limit, svc.parent.limit)
    try:
        code, _ = call(node, "PUT", "/_cluster/settings", {"transient": {
            "breaker.fielddata.limit": "20gb",
            "breaker.total.limit": "24gb"}})
        assert code == 200
        assert (svc.fielddata.limit, svc.parent.limit) == (20 << 30, 24 << 30)
        code, _ = call(node, "PUT", "/_cluster/settings", {"transient": {
            "breaker.fielddata.limit": None, "breaker.total.limit": None}})
        assert code == 200
        assert (svc.fielddata.limit, svc.parent.limit) == built
    finally:
        svc.set_limit("fielddata", 0)
        svc.set_limit("total", 0)
