"""Learned-sparse retrieval (PR 35): the ``rank_features`` field type, the
``neural_sparse`` query with ``query_tokens``, the feature lowering of
``TermBagPlan`` against a dense float64 reference through REST, its host
recovery, and its span and counters."""

import json
import struct
import urllib.error
import urllib.request

import numpy as np
import pytest

from opensearch_tpu.common.errors import (IllegalArgumentError,
                                          MapperParsingError, ParsingError)
from opensearch_tpu.mapping.mapper import DocumentMapper
from opensearch_tpu.mapping.types import (RankFeaturesFieldType,
                                          feature_bits, feature_value)
from opensearch_tpu.node import Node
from opensearch_tpu.search import query_dsl as dsl

VOCAB = 90
N_DOCS = 150                     # three segments of 50
F32_TOL = 2e-6                   # 64 float32 additions against float64


def call(node, method, path, body=None):
    url = f"http://127.0.0.1:{node.port}{path}"
    if isinstance(body, str):
        data = body.encode()
    else:
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


def lucene_bits(v: float) -> int:
    """``Float.floatToIntBits(v) >>> 15`` without numpy."""
    return struct.unpack(">I", struct.pack(">f", v))[0] >> 15


def stored(v: float) -> float:
    return struct.unpack(">f", struct.pack(">I", lucene_bits(v) << 15))[0]


# -- the mapper ---------------------------------------------------------------

@pytest.mark.parametrize("v", [1.0, 0.3, 3.4999, 1e-3, 2.0 ** -100, 7,
                               1.17549435e-38, 3.0e38])
def test_the_stored_value_is_float_bits_shifted_fifteen(v):
    ft = RankFeaturesFieldType("emb")
    got = ft.feature_weights({"tok": v})["tok"]
    assert feature_bits(v) == lucene_bits(v)
    assert got == stored(v) == feature_value(lucene_bits(v))
    assert 0 < got <= np.float32(v)
    # nine significant bits: the low fifteen of the float32 are clear
    assert struct.unpack(">I", struct.pack(">f", got))[0] & 0x7FFF == 0
    assert got >= float(np.float32(v)) * (1 - 2.0 ** -8)


@pytest.mark.parametrize("bad", [0, 0.0, -1.5, "1.0", None, True, [1.0],
                                 {"x": 1.0}, float("inf"), float("nan"),
                                 1e-46, 1e39, 10 ** 400])
def test_the_mapper_refuses_what_upstream_refuses(bad):
    ft = RankFeaturesFieldType("emb")
    with pytest.raises(MapperParsingError):
        ft.feature_weights({"tok": bad})


@pytest.mark.parametrize("bad", [1.5, "tok", ["tok"]])
def test_the_field_wants_an_object(bad):
    mapper = DocumentMapper({"properties": {
        "emb": {"type": "rank_features"}}})
    with pytest.raises(MapperParsingError):
        mapper.parse("1", {"emb": bad})


def test_the_mapper_parses_an_object_of_positive_floats():
    mapper = DocumentMapper({"properties": {
        "emb": {"type": "rank_features"},
        "two": {"type": "rank_features"}}})
    doc = mapper.parse("1", {"emb": {"a": 1.2345, "b.c": 2}, "two": {"a": 4}})
    assert doc.features == {"emb": {"a": stored(1.2345), "b.c": 2.0},
                            "two": {"a": 4.0}}
    assert "emb" not in doc.tokens and "emb" not in doc.doubles
    # no dynamic sub-fields appear under the feature names
    assert set(mapper.field_types()) == {"emb", "two"}
    assert mapper.to_mapping()["properties"]["emb"] == {
        "type": "rank_features"}
    with pytest.raises(MapperParsingError):
        mapper.parse("2", {"emb": [{"a": 1.0}, {"a": 2.0}]})


# -- the parser ---------------------------------------------------------------

def test_the_parser_reads_query_tokens_and_boost():
    q = dsl.parse_query({"neural_sparse": {"emb": {
        "query_tokens": {"a": 1.5, "b": 2}, "boost": 2.5}}})
    assert isinstance(q, dsl.NeuralSparseQuery)
    assert (q.field, q.tokens, q.boost) == ("emb", [("a", 1.5), ("b", 2.0)],
                                            2.5)


@pytest.mark.parametrize("body,error", [
    ({"emb": {"query_text": "what is", "model_id": "m"}},
     IllegalArgumentError),
    ({"emb": {"model_id": "m", "query_tokens": {"a": 1.0}}},
     IllegalArgumentError),
    ({"emb": {"query_text": "what is", "analyzer": "bert-uncased"}},
     ParsingError),
    ({"emb": {"query_tokens": {}}}, IllegalArgumentError),
    ({"emb": {}}, IllegalArgumentError),
    ({"emb": {"query_tokens": {"a": 0}}}, IllegalArgumentError),
    ({"emb": {"query_tokens": {"a": -2.0}}}, IllegalArgumentError),
    ({"emb": {"query_tokens": {"a": "1"}}}, IllegalArgumentError),
    ({"emb": {"query_tokens": {"a": True}}}, IllegalArgumentError),
    ({"emb": {"query_tokens": {"a": 1e39}}}, IllegalArgumentError),
    ({"emb": {"query_tokens": ["a"]}}, IllegalArgumentError),
    ({"emb": {"query_tokens": {"a": 1.0}, "pruning": 0.1}}, ParsingError),
    ({"emb": {"query_tokens": {"a": 1.0}}, "other": {}}, ParsingError),
    ({"emb": 3}, ParsingError)])
def test_the_parser_refuses(body, error):
    with pytest.raises(error) as exc:
        dsl.parse_query({"neural_sparse": body})
    assert exc.value.status == 400


# -- through REST: scores and order against a dense float64 reference --------

def token(t: int) -> str:
    return f"w{t}"


@pytest.fixture(scope="module")
def corpus(tmp_path_factory):
    """150 passages of 4-40 weighted tokens over three segments (``_bulk``
    and a refresh each), a tag beside each; nine are deleted afterwards,
    and tokens 80-89 occur in the first segment alone."""
    rng = np.random.default_rng(35)
    node = Node(str(tmp_path_factory.mktemp("sparse") / "node"),
                port=0).start()
    code, _ = call(node, "PUT", "/sp", {
        "settings": {"number_of_shards": 1, "number_of_replicas": 0},
        "mappings": {"properties": {"emb": {"type": "rank_features"},
                                    "tag": {"type": "keyword"}}}})
    assert code == 200
    dense = np.zeros((N_DOCS, VOCAB), dtype=np.float64)
    tags = []
    for si in range(3):
        lines = []
        for i in range(si * 50, si * 50 + 50):
            pool = VOCAB if si == 0 else VOCAB - 10
            toks = rng.choice(pool, size=int(rng.integers(4, 41)),
                              replace=False)
            raw = np.log1p(rng.exponential(1.5, size=len(toks))) + 1e-3
            dense[i, toks] = [stored(float(w)) for w in raw]
            tags.append("ab"[i % 2])
            lines.append(json.dumps({"index": {"_index": "sp",
                                               "_id": str(i)}}))
            lines.append(json.dumps({
                "emb": {token(t): float(w) for t, w in zip(toks, raw)},
                "tag": tags[-1]}))
        code, resp = call(node, "POST", "/_bulk", "\n".join(lines) + "\n")
        assert code == 200 and not resp["errors"]
        call(node, "POST", "/sp/_refresh")
    deleted = [3, 17, 49, 50, 77, 99, 100, 123, 149]
    for i in deleted:
        assert call(node, "DELETE", f"/sp/_doc/{i}")[0] == 200
    call(node, "POST", "/sp/_refresh")
    code, stats = call(node, "GET", "/sp/_stats")
    assert stats["indices"]["sp"]["total"]["segments"]["count"] == 3
    live = np.ones(N_DOCS, dtype=bool)
    live[deleted] = False
    yield node, dense, live, np.array(tags)
    node.stop()


def make_query(rng, n_tokens: int, unknown: int = 0) -> dict:
    toks = rng.choice(VOCAB, size=n_tokens, replace=False)
    q = {token(t): float(np.float32(np.log1p(rng.exponential(1.5)) + 1e-3))
         for t in toks}
    for j in range(unknown):
        q[f"never{j}"] = 1.0 + j
    return q


def reference(dense, live, q: dict, boost: float = 1.0, keep=None):
    """[(id, score)] of every live passage that holds a query token, best
    first: float64 dot products over the stored weights."""
    qv = np.zeros(VOCAB)
    for name, w in q.items():
        if name.startswith("w"):
            qv[int(name[1:])] = np.float32(w)
    scores = dense @ qv * boost
    ok = live & ((dense > 0) @ (qv > 0) > 0)
    if keep is not None:
        ok &= keep
    ids = np.flatnonzero(ok)
    order = np.lexsort((ids, -scores[ids]))
    return [(int(ids[i]), float(scores[ids[i]])) for i in order]


def assert_same(hits, want, size):
    got = [(int(h["_id"]), h["_score"]) for h in hits["hits"]]
    assert hits["total"]["value"] == len(want)
    assert len(got) == min(size, len(want))
    assert [s for _i, s in got] == pytest.approx(
        [s for _i, s in want[:len(got)]], rel=F32_TOL)
    # order: the same ids, but for neighbours inside the tolerance
    for (gi, _gs), (wi, ws) in zip(got, want):
        if gi != wi:
            other = dict(want)[gi]
            assert other == pytest.approx(ws, rel=F32_TOL)
    if got:
        assert hits["max_score"] == pytest.approx(want[0][1], rel=F32_TOL)


@pytest.mark.parametrize("n_tokens,unknown", [(1, 0), (7, 0), (24, 0),
                                              (64, 0), (7, 3), (24, 1)])
def test_scores_and_order_equal_the_dense_reference(corpus, n_tokens,
                                                    unknown):
    node, dense, live, _tags = corpus
    rng = np.random.default_rng(1000 + n_tokens + unknown)
    for size in (10, 200):
        q = make_query(rng, n_tokens, unknown)
        code, resp = call(node, "POST", "/sp/_search", {
            "query": {"neural_sparse": {"emb": {"query_tokens": q}}},
            "size": size, "_source": False})
        assert code == 200 and resp["_shards"]["failed"] == 0
        assert_same(resp["hits"], reference(dense, live, q), size)


def test_boost_scales_every_score(corpus):
    node, dense, live, _tags = corpus
    q = make_query(np.random.default_rng(5), 12)
    code, resp = call(node, "POST", "/sp/_search", {
        "query": {"neural_sparse": {"emb": {"query_tokens": q,
                                            "boost": 2.5}}}, "size": 10})
    assert code == 200
    assert_same(resp["hits"], reference(dense, live, q, boost=2.5), 10)


def test_only_unknown_tokens_match_nothing(corpus):
    node, *_ = corpus
    code, resp = call(node, "POST", "/sp/_search", {"query": {
        "neural_sparse": {"emb": {"query_tokens": {"never": 1.0}}}}})
    assert code == 200 and resp["hits"]["total"]["value"] == 0


@pytest.mark.parametrize("n_tokens", [7, 24])
def test_as_a_must_clause_of_a_bool_with_a_term_filter(corpus, n_tokens):
    node, dense, live, tags = corpus
    q = make_query(np.random.default_rng(70 + n_tokens), n_tokens, 1)
    code, resp = call(node, "POST", "/sp/_search", {"query": {"bool": {
        "must": [{"neural_sparse": {"emb": {"query_tokens": q}}}],
        "filter": [{"term": {"tag": "a"}}]}}, "size": 10})
    assert code == 200 and resp["_shards"]["failed"] == 0
    assert_same(resp["hits"], reference(dense, live, q, keep=tags == "a"),
                10)


def test_in_filter_context_it_matches_without_scoring(corpus):
    node, dense, live, _tags = corpus
    q = make_query(np.random.default_rng(8), 5)
    code, resp = call(node, "POST", "/sp/_search", {"query": {"bool": {
        "filter": [{"neural_sparse": {"emb": {"query_tokens": q}}}]}},
        "size": 0})
    assert code == 200
    assert resp["hits"]["total"]["value"] == len(reference(dense, live, q))


def test_as_a_hybrid_sub_query_it_is_a_term_bag(corpus):
    """It comes free with the plan: the normalization processor sees a
    scored list like any other."""
    node, dense, live, tags = corpus
    q = make_query(np.random.default_rng(41), 12)
    code, resp = call(node, "POST", "/sp/_search", {"query": {"hybrid": {
        "queries": [{"neural_sparse": {"emb": {"query_tokens": q}}},
                    {"term": {"tag": "b"}}]}}, "size": 200})
    assert code == 200 and resp["_shards"]["failed"] == 0
    want = reference(dense, live, q)
    got = {int(h["_id"]): h["_score"] for h in resp["hits"]["hits"]}
    # min-max puts the sparse list's best at 1.0; the arithmetic mean of
    # two lists halves it
    assert got[want[0][0]] >= 0.5
    _, trace = call(node, "GET", "/_nodes/trace?size=400")
    spans = next(iter(trace["nodes"].values()))["spans"]
    kinds = [s["attributes"]["type"] for s in spans
             if s["name"] == "hybrid.subquery"]
    assert kinds.count("term_bag") >= 2


@pytest.mark.parametrize("query,reason", [
    ({"neural_sparse": {"emb": {"query_text": "x", "model_id": "m"}}},
     "model"),
    ({"neural_sparse": {"tag": {"query_tokens": {"a": 1.0}}}},
     "rank_features"),
    ({"neural_sparse": {"nowhere": {"query_tokens": {"a": 1.0}}}},
     "rank_features"),
    ({"neural_sparse": {"emb": {"query_tokens": {}}}}, "query_tokens"),
    ({"neural_sparse": {"emb": {"query_tokens": {"a": -1}}}}, "positive"),
    ({"term": {"emb": "w1"}}, "neural_sparse"),
    ({"match": {"emb": "w1"}}, "neural_sparse")])
def test_unsupported_forms_answer_400(corpus, query, reason):
    node, *_ = corpus
    code, resp = call(node, "POST", "/sp/_search", {"query": query})
    assert code == 400
    assert reason in resp["error"]["reason"]


def test_bulk_refuses_a_non_positive_weight_for_that_document_alone(corpus):
    node, *_ = corpus
    lines = [json.dumps({"index": {"_index": "sp", "_id": "bad"}}),
             json.dumps({"emb": {"a": 0.0}}),
             json.dumps({"index": {"_index": "sp", "_id": "bad2"}}),
             json.dumps({"emb": {"a": "heavy"}})]
    code, resp = call(node, "POST", "/_bulk", "\n".join(lines) + "\n")
    assert code == 200 and resp["errors"]
    assert [i["index"]["status"] for i in resp["items"]] == [400, 400]
    assert resp["items"][0]["index"]["error"]["type"] == \
        "mapper_parsing_exception"


def test_exists_sees_the_field_and_the_mapping_reads_back(corpus):
    node, _dense, live, _tags = corpus
    code, resp = call(node, "POST", "/sp/_search", {
        "query": {"exists": {"field": "emb"}}, "size": 0})
    assert code == 200 and resp["hits"]["total"]["value"] == int(live.sum())
    _, mapping = call(node, "GET", "/sp/_mapping")
    assert mapping["sp"]["mappings"]["properties"]["emb"] == {
        "type": "rank_features"}


# -- the plan: host recovery, bounds, the segment's columns -------------------

def searcher_of(node):
    return node.indices.get("sp").engine_for(0).acquire_searcher()


def test_the_postings_hold_the_stored_weight_and_no_positions(corpus):
    node, dense, *_ = corpus
    searcher = searcher_of(node)
    for si, seg in enumerate(searcher.segments):
        pf = seg.postings["emb"]
        assert pf.features and not pf.has_norms
        assert len(pf.pos_offsets) == 1 and len(pf.positions) == 0
        assert pf.tfs.dtype == np.float32
        for name, tid in pf.terms.items():
            a, b = pf.offsets[tid], pf.offsets[tid + 1]
            rows = pf.doc_ids[a:b] + si * 50
            assert np.array_equal(pf.tfs[a:b].astype(np.float64),
                                  dense[rows, int(name[1:])])
            assert pf.max_values()[tid] == pf.tfs[a:b].max()
        dseg = seg.device()
        staged = dseg.ensure_postings("emb")
        assert staged["doc_ids"].shape == staged["tfs"].shape
        # two columns a posting: nothing else grows with the postings
        assert staged["pos_offsets"].shape == (8,)
        assert staged["positions"].shape == (8,)
        # and no BM25 impact column is ever made of them
        assert not any(kind == "impacts" and field == "emb"
                       for kind, field, _name in dseg._ledger_group.entries)


@pytest.mark.parametrize("n_tokens", [1, 7, 24, 64])
def test_host_topk_gives_the_same_top_k(corpus, host_recovery, n_tokens):
    node, dense, live, _tags = corpus
    q = make_query(np.random.default_rng(300 + n_tokens), n_tokens, 1)
    body = {"query": {"neural_sparse": {"emb": {"query_tokens": q}}},
            "size": 10, "profile": True}
    code, on_host = call(node, "POST", "/sp/_search", body)
    assert code == 200
    assert on_host["profile"]["shards"][0]["engine"][
        "execution_path"] == "host"
    host_recovery.reset()
    code, on_device = call(node, "POST", "/sp/_search", body)
    assert on_device["profile"]["shards"][0]["engine"][
        "execution_path"] == "device"
    rows = [[(h["_id"], h["_score"]) for h in r["hits"]["hits"]]
            for r in (on_host, on_device)]
    assert rows[0] == rows[1]                     # bit for bit
    assert on_host["hits"]["total"] == on_device["hits"]["total"]
    assert_same(on_host["hits"], reference(dense, live, q), 10)


def test_the_bound_holds_and_prunes_under_min_score(corpus):
    from opensearch_tpu.search import compiler

    node, dense, live, _tags = corpus
    searcher = searcher_of(node)
    q = make_query(np.random.default_rng(9), 10)
    plan, bind = compiler.compile_query(dsl.parse_query(
        {"neural_sparse": {"emb": {"query_tokens": q}}}), searcher.ctx)
    assert plan.features and plan.scored and "features=True" in \
        plan.describe(bind)
    want = reference(dense, np.ones(N_DOCS, bool), q)
    for si, seg in enumerate(searcher.segments):
        best = max((s for i, s in want if i // 50 == si), default=0.0)
        bound = plan.max_score_bound(bind, seg)
        assert best <= bound <= sum(np.float32(w) for w in q.values()) * 4
        assert plan.can_match(bind, seg)
        dims, _ins = plan.prepare(bind, seg, seg.device(), searcher.ctx)
        assert dims == (16, 4096, True) and dims.postings == int(
            (dense[si * 50: si * 50 + 50][:, [int(t[1:]) for t in q]]
             > 0).sum())
    # tokens 80-89 live in the first segment alone
    only_first = {token(t): 1.0 for t in range(80, 90)}
    plan, bind = compiler.compile_query(dsl.parse_query(
        {"neural_sparse": {"emb": {"query_tokens": only_first}}}),
        searcher.ctx)
    assert [plan.can_match(bind, s) for s in searcher.segments] == [
        True, False, False]
    top = want[0][1]
    code, resp = call(node, "POST", "/sp/_search", {
        "query": {"neural_sparse": {"emb": {"query_tokens": q}}},
        "min_score": top * 0.999, "size": 10})
    assert code == 200
    live_want = [r for r in reference(dense, live, q)
                 if r[1] >= np.float32(top * 0.999)]
    assert [int(h["_id"]) for h in resp["hits"]["hits"]] == [
        i for i, _s in live_want]


def test_msearch_and_the_batcher_leave_a_feature_bag_alone(corpus):
    from opensearch_tpu.search import batch

    node, *_ = corpus
    searcher = searcher_of(node)
    bodies = [{"query": {"neural_sparse": {"emb": {"query_tokens": {
        token(t): 1.0, token(t + 1): 0.5}}}}, "size": 3} for t in (1, 5)]
    groups, fallback = batch.plan_batches(searcher, bodies)
    assert not groups and fallback == [0, 1]


# -- the span and the counters -------------------------------------------------

def counters(node) -> dict:
    _, stats = call(node, "GET", "/_nodes/stats")
    c = next(iter(stats["nodes"].values()))["telemetry"]["counters"]
    return {k: c.get(k, 0) for k in (
        "search.neural_sparse.requests", "search.neural_sparse.query_tokens",
        "search.term_bag.postings", "search.term_bag.budget_lanes")}


def test_the_span_its_attributes_and_all_four_counters(corpus):
    node, dense, _live, _tags = corpus
    q = make_query(np.random.default_rng(77), 9, unknown=2)
    before = counters(node)
    code, resp = call(node, "POST", "/sp/_search", {
        "query": {"neural_sparse": {"emb": {"query_tokens": q}}},
        "size": 10})
    assert code == 200
    after = counters(node)
    delta = {k: after[k] - before[k] for k in after}
    known = [int(t[1:]) for t in q if t.startswith("w")]
    assert delta["search.neural_sparse.requests"] == 1
    assert delta["search.neural_sparse.query_tokens"] == len(known) == 9
    assert delta["search.term_bag.postings"] == int(
        (dense[:, known] > 0).sum())
    # one program a segment, each keyed with the smallest bucket
    assert delta["search.term_bag.budget_lanes"] == 3 * 4096

    _, trace = call(node, "GET", "/_nodes/trace?size=400")
    spans = next(iter(trace["nodes"].values()))["spans"]
    bind, = [s for s in spans if s["name"] == "sparse.bind"
             and s["attributes"]["tokens"] == 11]
    assert bind["attributes"] == {"tokens": 11, "known": 9}
    mine = {s["span_id"]: s for s in spans
            if s["trace_id"] == bind["trace_id"]}
    parent = mine[bind["parent_span_id"]]
    assert parent["name"] == "query.plan"
    assert mine[parent["parent_span_id"]]["name"] == "shard.query_phase"
    names = [s["name"] for s in mine.values()]
    assert names.count("sparse.bind") == 1
    assert names.count("segment.dispatch") == 3

    # the same query again is a plan-cache hit: no bind, and the counters
    # move as they did, because they count where the plan runs
    code, again = call(node, "POST", "/sp/_search", {
        "query": {"neural_sparse": {"emb": {"query_tokens": q}}},
        "size": 10})
    assert code == 200 and again["hits"]["hits"] == resp["hits"]["hits"]
    twice = counters(node)
    assert {k: twice[k] - after[k] for k in after} == delta
    _, trace = call(node, "GET", "/_nodes/trace?size=400")
    spans = next(iter(trace["nodes"].values()))["spans"]
    assert len([s for s in spans if s["name"] == "sparse.bind"
                and s["attributes"]["tokens"] == 11]) == 1


def test_a_plain_match_moves_the_term_bag_counters_too(tmp_path):
    node = Node(str(tmp_path / "node"), port=0).start()
    try:
        call(node, "PUT", "/txt", {"mappings": {"properties": {
            "body": {"type": "text"}}}})
        for i in range(12):
            call(node, "PUT", f"/txt/_doc/{i}", {
                "body": "alpha " * (1 + i % 3) + ("beta" if i % 2 else "")})
        call(node, "POST", "/txt/_refresh")
        before = counters(node)
        code, resp = call(node, "POST", "/txt/_search", {
            "query": {"match": {"body": "alpha beta gamma"}}})
        assert code == 200 and resp["hits"]["total"]["value"] == 12
        after = counters(node)
        assert after["search.term_bag.postings"] - before[
            "search.term_bag.postings"] == 12 + 6
        assert after["search.term_bag.budget_lanes"] - before[
            "search.term_bag.budget_lanes"] == 4096
        assert after["search.neural_sparse.requests"] == before[
            "search.neural_sparse.requests"]
    finally:
        node.stop()


def test_a_feature_segment_survives_flush_and_reload(tmp_path):
    from opensearch_tpu.index.store import load_segment, save_segment

    mapper = DocumentMapper({"properties": {
        "emb": {"type": "rank_features"}}})
    from opensearch_tpu.index.segment import SegmentWriter

    docs = [mapper.parse(str(i), {"emb": {token(i % 4): 1.0 + i,
                                          token(9): 0.3}})
            for i in range(6)]
    seg = SegmentWriter().build(docs, "s0")
    save_segment(seg, str(tmp_path))
    back = load_segment(str(tmp_path), "s0")
    a, b = seg.postings["emb"], back.postings["emb"]
    assert b.features and a.terms == b.terms
    for col in ("df", "offsets", "doc_ids", "tfs", "pos_offsets",
                "positions", "present"):
        assert np.array_equal(getattr(a, col), getattr(b, col))
