"""Distributed scatter-gather search on the virtual 8-device CPU mesh:
8-shard results must be identical to 1-shard results on the same corpus
(VERDICT round-1 item 8's 'done' bar)."""

import numpy as np
import pytest

import jax

from opensearch_tpu.index.segment import SegmentWriter
from opensearch_tpu.mapping.mapper import DocumentMapper
from opensearch_tpu.parallel import dist_search
from opensearch_tpu.search.executor import ShardSearcher

MAPPING = {"properties": {"body": {"type": "text"}}}
VOCAB = ("alpha bravo charlie delta echo foxtrot golf hotel india juliet "
         "kilo lima").split()


def build_sharded_corpus(n_shards=8, docs_per_shard=40, seed=3):
    rng = np.random.default_rng(seed)
    mapper = DocumentMapper(MAPPING)
    writer = SegmentWriter()
    segments = []
    doc_no = 0
    for si in range(n_shards):
        parsed = []
        for _ in range(docs_per_shard):
            body = " ".join(rng.choice(VOCAB, size=rng.integers(4, 20)))
            d = mapper.parse(str(doc_no), {"body": body})
            d.seq_no = doc_no
            parsed.append(d)
            doc_no += 1
        segments.append(writer.build(parsed, f"shard_{si}"))
    return mapper, segments


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 virtual devices")
def test_sharded_topk_matches_single_shard():
    mapper, segments = build_sharded_corpus()
    terms = ["alpha", "echo"]
    k = 10

    mesh = dist_search.make_mesh(8)
    stacked, meta = dist_search.prepare_match_query(segments, "body", terms)
    assert "impacts" in stacked and "tfs" not in stacked \
        and "doc_lens" not in stacked       # the port actually landed
    on_mesh = dist_search.put_on_mesh(stacked, mesh)
    step = dist_search.sharded_impact_topk(mesh, n_pad=meta["n_pad"],
                                           budget=meta["budget"], k=k)
    vals, gids = step(on_mesh["offsets"], on_mesh["doc_ids"],
                      on_mesh["impacts"], on_mesh["tids"],
                      on_mesh["active"], on_mesh["idfs"],
                      on_mesh["weights"])
    vals = np.asarray(vals)
    gids = np.asarray(gids)

    # reference: the same 8 segments searched as one shard (global stats
    # are identical by construction)
    searcher = ShardSearcher(segments, mapper)
    resp = searcher.search({"query": {"match": {"body": "alpha echo"}},
                            "size": k})
    ref = resp["hits"]["hits"]

    n_pad = meta["n_pad"]
    got_ids = []
    for gid in gids:
        shard, local = divmod(int(gid), n_pad)
        got_ids.append(segments[shard].doc_ids[local])
    assert got_ids == [h["_id"] for h in ref]
    # BYTE-parity with the host path: both read the same eager impact
    # table in the same accumulation order (the PR-5 invariant extended
    # to the mesh), so scores are bitwise equal, not merely close
    assert [np.float32(v) for v in vals] \
        == [np.float32(h["_score"]) for h in ref]


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 virtual devices")
def test_sharded_topk_term_missing_on_some_shards():
    mapper, segments = build_sharded_corpus(docs_per_shard=12, seed=9)
    mesh = dist_search.make_mesh(8)
    stacked, meta = dist_search.prepare_match_query(segments, "body",
                                                    ["juliet"])
    on_mesh = dist_search.put_on_mesh(stacked, mesh)
    step = dist_search.sharded_impact_topk(mesh, n_pad=meta["n_pad"],
                                           budget=meta["budget"], k=5)
    vals, gids = step(on_mesh["offsets"], on_mesh["doc_ids"],
                      on_mesh["impacts"], on_mesh["tids"],
                      on_mesh["active"], on_mesh["idfs"],
                      on_mesh["weights"])
    searcher = ShardSearcher(segments, mapper)
    resp = searcher.search({"query": {"match": {"body": "juliet"}}, "size": 5})
    exp_scores = [np.float32(h["_score"]) for h in resp["hits"]["hits"]]
    got = [np.float32(v) for v in np.asarray(vals) if v > 0]
    assert got == exp_scores           # byte-parity, not approximate


def _build_sharded_corpus(n_shards=8, per=40, seed=3):
    import numpy as np

    from opensearch_tpu.index.segment import SegmentWriter
    from opensearch_tpu.mapping.mapper import DocumentMapper
    from opensearch_tpu.search.executor import ShardSearcher

    vocab = ("alpha bravo charlie delta echo foxtrot golf hotel india "
             "juliet kilo lima".split())
    rng = np.random.default_rng(seed)
    mapper = DocumentMapper({"properties": {
        "body": {"type": "text"}, "n": {"type": "long"},
        "tag": {"type": "keyword"}}})
    writer = SegmentWriter()
    searchers = []
    doc_no = 0
    for si in range(n_shards):
        parsed = []
        for _ in range(per):
            src = {"body": " ".join(rng.choice(vocab,
                                               size=rng.integers(3, 12))),
                   "n": int(rng.integers(0, 100)),
                   "tag": str(rng.choice(["a", "b", "c"]))}
            d = mapper.parse(str(doc_no), src)
            d.seq_no = doc_no
            parsed.append(d)
            doc_no += 1
        seg = writer.build(parsed, f"s{si}_seg0")
        searchers.append(ShardSearcher([seg], mapper,
                                       index_name="mesh_idx", shard_id=si))
    return searchers


def _host_merge(searchers, body):
    """Reference scatter-gather: per-shard search + coordinator merge —
    the exact semantics MeshSearcher's collective merge must reproduce."""
    from opensearch_tpu.search.executor import merge_hit_rows

    size = int(body.get("size", 10)) + int(body.get("from", 0))
    sub = dict(body, size=size)
    sub["from"] = 0
    rows = []
    total = 0
    for si, s in enumerate(searchers):
        r = s.search(sub)
        total += r["hits"]["total"]["value"]
        for pos, h in enumerate(r["hits"]["hits"]):
            rows.append((h, si, pos))
    hits = merge_hit_rows(rows, None)
    from_ = int(body.get("from", 0))
    return hits[from_: from_ + int(body.get("size", 10))], total


QUERIES = [
    {"query": {"match": {"body": "alpha echo"}}, "size": 10},
    {"query": {"bool": {
        "must": [{"match": {"body": "alpha"}}],
        "filter": [{"range": {"n": {"gte": 20, "lte": 80}}}]}},
     "size": 15},
    {"query": {"bool": {
        "should": [{"match": {"body": "delta"}},
                   {"term": {"tag": "b"}}]}}, "size": 10, "from": 5},
    {"query": {"range": {"n": {"gte": 90}}}, "size": 20},
    {"query": {"constant_score": {
        "filter": {"term": {"tag": "a"}}, "boost": 2.0}}, "size": 10},
]


def test_mesh_searcher_matches_host_merge():
    """The collective all-gather merge must reproduce the host
    scatter-gather bit-for-bit for arbitrary compiled plans (VERDICT r3
    item 3: the mesh path generalized past bag-of-terms)."""
    from opensearch_tpu.parallel.dist_search import MeshSearcher

    searchers = _build_sharded_corpus()
    mesh_s = MeshSearcher(searchers)
    for body in QUERIES:
        host_hits, host_total = _host_merge(searchers, body)
        resp = mesh_s.search(body)
        assert resp["hits"]["total"]["value"] == host_total, body
        got = [(h["_id"], h["_score"]) for h in resp["hits"]["hits"]]
        want = [(h["_id"], h["_score"]) for h in host_hits]
        assert got == want, (body, got, want)


def test_mesh_searcher_empty_and_unmatched():
    from opensearch_tpu.parallel.dist_search import MeshSearcher

    searchers = _build_sharded_corpus(n_shards=4)
    mesh_s = MeshSearcher(searchers)
    resp = mesh_s.search({"query": {"match": {"body": "zzznope"}}})
    assert resp["hits"]["total"]["value"] == 0
    assert resp["hits"]["hits"] == []
    assert resp["hits"]["max_score"] is None


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 virtual devices")
def test_mesh_metric_aggs_collective_reduce():
    """size:0 metric aggs reduce ON the mesh via one all-gather
    collective — results identical to the host-path reduce."""
    mapper = DocumentMapper({"properties": {"body": {"type": "text"},
                                            "n": {"type": "long"}}})
    writer = SegmentWriter()
    rng = np.random.default_rng(5)
    segments = []
    doc_no = 0
    for si in range(8):
        parsed = []
        for _ in range(25):
            body = " ".join(rng.choice(VOCAB, size=rng.integers(4, 12)))
            parsed.append(mapper.parse(
                str(doc_no), {"body": body, "n": int(rng.integers(0, 100))}))
            doc_no += 1
        segments.append(writer.build(parsed, f"m_{si}"))
    shards = [ShardSearcher([s], mapper) for s in segments]
    ms = dist_search.MeshSearcher(shards, dist_search.make_mesh(8))
    aggs = {"tot": {"sum": {"field": "n"}},
            "lo": {"min": {"field": "n"}},
            "hi": {"max": {"field": "n"}},
            "mean": {"avg": {"field": "n"}},
            "cnt": {"value_count": {"field": "n"}},
            "st": {"stats": {"field": "n"}}}
    assert ms.supports_mesh_aggs(aggs)
    body = {"size": 0, "query": {"match": {"body": "alpha"}}}
    got = ms.mesh_metric_aggs(body, aggs)
    want = ShardSearcher(segments, mapper).search({**body, "aggs": aggs})
    assert got["hits"]["total"]["value"] == \
        want["hits"]["total"]["value"]
    for name in ("tot", "lo", "hi", "mean", "cnt"):
        assert got["aggregations"][name]["value"] == pytest.approx(
            want["aggregations"][name]["value"])
    for k in ("count", "min", "max", "avg", "sum"):
        assert got["aggregations"]["st"][k] == pytest.approx(
            want["aggregations"]["st"][k])
    # nested / bucket aggs stay on the host path
    assert not ms.supports_mesh_aggs(
        {"t": {"terms": {"field": "n"}}})
    assert not ms.supports_mesh_aggs(
        {"s": {"sum": {"field": "n"}, "aggs": {"x": {"max":
                                                     {"field": "n"}}}}})
