"""One array back per segment program: ``plan.run_topk`` and
``plan.topk_from_scores`` return their four results packed into one
``int32[2k + 2]`` whose copy to the host starts at launch.  The packed
form against the four-array form bit for bit over every kind of plan,
what the sanity guard and the k-th-score harvest make of it, and the
counter that tells the arrays read from the sync regions
(``device.transfers.fetch.arrays`` beside ``ops``)."""

import http.client
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from opensearch_tpu.common.device_health import device_health
from opensearch_tpu.common.device_ledger import device_ledger
from opensearch_tpu.common.telemetry import metrics
from opensearch_tpu.index.segment import SegmentWriter
from opensearch_tpu.mapping.mapper import DocumentMapper
from opensearch_tpu.node import Node
from opensearch_tpu.search import engine
from opensearch_tpu.search import plan as P
from opensearch_tpu.search.executor import ShardSearcher, build_arrays
from opensearch_tpu.testing.fault_injection import DeviceFaultInjector

VOCAB = [f"w{i}" for i in range(300)]
MANY = VOCAB[:200]
TERM = {"match": {"body": "w1 w2 w3"}}
MANY_TERMS = {"terms": {"body": MANY}}
# the kinds of plan tests/test_slice_gather_counter.py enumerates, the
# kNN winners' mask, and a query that matches nothing
QUERIES = {
    "term_bag": TERM,
    "term_bag_and": {"match": {"body": {"query": "w1 w2",
                                        "operator": "and"}}},
    "terms_filter_small": {"terms": {"body": ["w1", "w2"]}},
    "terms_filter_beyond_threshold": MANY_TERMS,
    "wildcard_beyond_threshold": {"wildcard": {"body": "w*"}},
    "wildcard_small": {"wildcard": {"body": "w29?"}},
    "bool_filter_only_elements": {"bool": {"filter": [MANY_TERMS]}},
    "bool_must_slices_filter_elements": {
        "bool": {"must": [TERM], "filter": [MANY_TERMS]}},
    "bool_no_postings": {
        "bool": {"filter": [{"range": {"n": {"gte": 0}}}]}},
    "dis_max": {"dis_max": {"queries": [MANY_TERMS, TERM]}},
    "constant_score": {"constant_score": {"filter": TERM}},
    "constant_score_elements": {"constant_score": {"filter": MANY_TERMS}},
    "boosting_negative_side": {
        "boosting": {"positive": MANY_TERMS, "negative": TERM,
                     "negative_boost": 0.5}},
    "function_score_child": {
        "function_score": {"query": TERM, "functions": [
            {"filter": MANY_TERMS, "weight": 2.0}]}},
    "function_score_filter": {
        "function_score": {"query": {"match_all": {}}, "functions": [
            {"filter": TERM, "weight": 2.0}]}},
    "script_score": {
        "script_score": {"query": TERM,
                         "script": {"source": "_score * 2"}}},
    "terms_set": {
        "terms_set": {"body": {"terms": ["w1", "w2", "w3"],
                               "minimum_should_match_field": "n"}}},
    "match_all": {"match_all": {}},
    "knn_winners": {"knn": {"v": {"vector": [3.0, 4.0, 1.5, 1.0], "k": 7}}},
    "no_match": {"match": {"body": "absent"}},
}
N_SEG = 60


@pytest.fixture(autouse=True)
def device_path(monkeypatch):
    monkeypatch.setattr(engine, "BATCHER_ENABLED", False)


def _segments(mapper, n_segments, prefix):
    rng = np.random.default_rng(5)
    writer = SegmentWriter()
    segs = []
    for s in range(n_segments):
        docs = [mapper.parse(str(s * N_SEG + i), {
            "body": " ".join(rng.choice(VOCAB, 12)), "n": 1,
            "v": [float(i), i + 1.0, i * 0.5, 1.0]})
            for i in range(N_SEG)]
        segs.append(writer.build(docs, f"{prefix}{s}"))
    return segs


@pytest.fixture(scope="module")
def searcher():
    mapper = DocumentMapper({"properties": {
        "body": {"type": "text"}, "n": {"type": "integer"},
        "v": {"type": "knn_vector", "dimension": 4,
              "method": {"name": "exact", "space_type": "l2"}}}})
    yield ShardSearcher(_segments(mapper, 2, "pk"), mapper)
    device_ledger().reset()
    device_health().reset()


def _bits(a) -> np.ndarray:
    a = np.asarray(a)
    assert a.dtype == np.float32, a.dtype
    return a.reshape(-1).view(np.int32)


def _same(packed, parts) -> None:
    """``packed`` (one device array) holds ``parts`` (four), bit for bit."""
    vals, idx, tot, mx = parts
    k = vals.shape[0]
    packed = np.asarray(packed)
    assert packed.dtype == np.int32 and packed.shape == (2 * k + 2,)
    got_vals, got_idx, got_tot, got_mx = P.unpack_topk(packed)
    assert got_vals.dtype == np.float32 and got_idx.dtype == np.int32
    np.testing.assert_array_equal(_bits(got_vals), _bits(vals))
    np.testing.assert_array_equal(got_idx, np.asarray(idx))
    assert type(got_tot) is int and got_tot == int(tot)
    assert type(got_mx) is float
    np.testing.assert_array_equal(_bits(np.float32(got_mx)), _bits(mx))


@pytest.mark.parametrize("k", [1, 10, 100, 4096])
@pytest.mark.parametrize("name", sorted(QUERIES))
def test_packed_entry_holds_the_four_outputs_bit_for_bit(searcher, name, k):
    """Every kind of plan, ``k`` below, at and past the matches (-inf
    fill; 4,096 is cut to ``n_pad`` as ``_topk`` cuts it), with and
    without a ``min_score`` (which here leaves some, then no match)."""
    plan, bind = searcher.compiled(QUERIES[name])
    seg = searcher.segments[0]
    dseg = seg.device()
    dims, ins = plan.prepare(bind, seg, dseg, searcher.ctx)
    A = build_arrays(dseg, plan.arrays(), searcher.mapper,
                     live=searcher.ctx.live_jnp(seg, dseg))
    kk = min(k, dseg.n_pad)
    top = None
    for ms in (-np.inf, "median", np.inf):
        if ms == "median":
            scores = np.asarray(top[0])
            ms = float(np.median(scores[scores > -np.inf])) \
                if (scores > -np.inf).any() else 1.0
        ms = jnp.asarray(np.float32(ms))
        parts = P.run_topk_parts(plan, dims, kk, A, ins, ms)
        top = top or parts
        _same(P.run_topk(plan, dims, kk, A, ins, ms), parts)
    tot, filled = int(top[2]), np.asarray(top[0]) > -np.inf
    assert filled.sum() == min(kk, tot)
    assert int(parts[2]) == 0 and not (np.asarray(parts[0]) > -np.inf).any()
    assert (tot == 0) == (name in ("no_match", "term_bag_and"))
    assert tot <= N_SEG and (kk > tot) == (not filled.all())


@pytest.mark.parametrize("k", [1, 10, 100, 128])
@pytest.mark.parametrize("matches", ["some", "none", "all"])
def test_topk_from_scores_packs_the_same(k, matches):
    rng = np.random.default_rng(11)
    n_pad = 128
    scores = rng.random(n_pad).astype(np.float32) * 7
    matched = {"some": rng.random(n_pad) < 0.3,
               "none": np.zeros(n_pad, bool),
               "all": np.ones(n_pad, bool)}[matches]
    key = jnp.where(matched, scores, -jnp.inf)
    parts = jax.jit(P._key_topk, static_argnums=1)(key, k, matched)
    _same(P.topk_from_scores(jnp.asarray(scores), k, jnp.asarray(matched)),
          parts)
    assert int(parts[2]) == matched.sum()


def test_a_bit_cast_keeps_every_float32_bit():
    """-inf, a signed zero, a denormal and NaNs with payloads cross in
    the packed array as they are: no conversion touches them."""
    bits = np.array([0xFF800000, 0x80000000, 0x00000001, 0x7FC00000,
                     0x7FC12345, 0xFFC00001, 0x3F800000], np.uint32)
    vals = bits.view(np.float32)
    idx = np.arange(len(bits), dtype=np.int32)
    mx = np.uint32(0x7FA0BEEF).view(np.float32)     # a signalling NaN
    packed = jax.jit(P._pack_topk)(vals, idx, jnp.int64(2**31 - 1), mx)
    got_vals, got_idx, got_tot, _mx = P.unpack_topk(np.asarray(packed))
    np.testing.assert_array_equal(got_vals.view(np.uint32), bits)
    np.testing.assert_array_equal(got_idx, idx)
    assert got_tot == 2**31 - 1
    assert np.asarray(packed)[-1:].view(np.uint32)[0] == 0x7FA0BEEF


# -- what _topk makes of it -------------------------------------------------

BODY = {"query": TERM, "size": 5}


def _fetch(led=None) -> dict:
    return dict((led or device_ledger()).stats()["transfers"]["fetch"])


def test_a_search_reads_one_array_a_segment(searcher):
    before = _fetch()
    resp = searcher.search(dict(BODY))
    after = _fetch()
    assert resp["hits"]["hits"]
    assert after["arrays"] - before["arrays"] == len(searcher.segments)
    assert after["ops"] - before["ops"] == 1
    # 2k + 2 lanes of four bytes a segment
    assert after["bytes"] - before["bytes"] == 2 * (2 * 5 + 2) * 4


def test_aggs_top_k_reads_one_array_a_segment_too(searcher):
    """``_topk_from_views``: the top-k out of the aggregations' one
    full-scores pass."""
    plain = searcher.search(dict(BODY))
    before = _fetch()
    resp = searcher.search({**BODY, "aggs": {"n": {"max": {"field": "n"}}}})
    after = _fetch()
    assert resp["hits"] == plain["hits"]
    assert after["arrays"] - before["arrays"] == len(searcher.segments)


def test_a_poisoned_packed_result_is_caught_and_recomputed(searcher):
    """NaN bits in the packed scores are what ``check_finite`` sees after
    the split: the segment is recomputed on the host, byte-identically,
    and only the sound segment counts as an array read."""
    device_health().reset()
    clean = searcher.search(dict(BODY))
    inj = DeviceFaultInjector(seed=3)
    inj.poison_topk("run_topk", times=1)
    before, fallbacks = _fetch(), device_ledger().host_fallbacks
    with inj:
        poisoned = searcher.search(dict(BODY))
    after = _fetch()
    assert json.dumps(poisoned["hits"], sort_keys=True) == \
        json.dumps(clean["hits"], sort_keys=True)
    assert device_health().stats()["poisoned_results"] == 1
    assert device_ledger().host_fallbacks == fallbacks + 1
    assert after["arrays"] - before["arrays"] == len(searcher.segments) - 1
    assert after["ops"] - before["ops"] == 1
    device_health().reset()


def test_the_injector_poisons_the_scores_of_both_forms():
    nan = DeviceFaultInjector(seed=1)
    nan.poison_topk()
    packed = jnp.arange(2 * 3 + 2, dtype=jnp.int32)
    vals, idx, tot, mx = P.unpack_topk(
        np.asarray(nan._maybe_poison("run_topk", packed)))
    assert np.isnan(vals).all() and len(vals) == 3
    np.testing.assert_array_equal(idx, [3, 4, 5])
    assert tot == 6 and mx == np.int32(7).view(np.float32)
    parts = nan._maybe_poison("run_topk", (jnp.ones(3, jnp.float32), idx))
    assert np.isnan(np.asarray(parts[0])).all() and parts[1] is idx


class _NotReady:
    def is_ready(self):
        return False


def _packed_of(vals) -> jax.Array:
    vals = np.asarray(vals, np.float32)
    out = jax.jit(P._pack_topk)(vals, np.arange(len(vals), dtype=np.int32),
                                jnp.int64(len(vals)), vals.max())
    return jax.block_until_ready(out)


def test_harvest_kth_reads_what_is_ready_and_views_its_scores():
    """A finished program's packed result is read once (phase 2 then
    finds the numpy array), a host path's tuple is taken as it is, and a
    program still running is left alone."""
    pending = _NotReady()
    host = (np.array([9.0, 1.0], np.float32), np.array([0, 1], np.int32),
            2, 9.0)
    launched = [[0, _packed_of([5.0, 4.0, -np.inf])], [1, pending],
                [2, host]]
    harvest = ShardSearcher._harvest_kth
    assert harvest(launched, 3, None) == 4.0       # of 9, 5, 4, 1
    assert isinstance(launched[0][1], np.ndarray)
    assert launched[1][1] is pending and launched[2][1] is host
    assert harvest(launched, 5, None) is None      # four finite scores
    assert harvest(launched, 4, 2.5) == 2.5        # never lowers the k-th
    assert harvest([[0, pending]], 1, None) is None


def test_waived_totals_prune_by_the_harvested_kth(monkeypatch):
    """``track_total_hits: false``: with every program finished when the
    harvest looks (the test waits where ``_topk`` would not), the segment
    that cannot beat the k-th score is never dispatched."""
    mapper = DocumentMapper({"properties": {"body": {"type": "text"}}})
    writer = SegmentWriter()
    high = [mapper.parse(f"H{i}", {"body": "alpha alpha alpha"})
            for i in range(4)]
    low = [mapper.parse(f"L{i}", {"body": "alpha " + "pad " * 200})
           for i in range(3)]
    s = ShardSearcher(
        [writer.build(high, "pk_high"), writer.build(low, "pk_low")], mapper)
    query = {"match": {"body": "alpha"}}
    exact = s.search({"query": query, "size": 3})
    real = P.run_topk
    monkeypatch.setattr(P, "run_topk", lambda *a, **kw:
                        jax.block_until_ready(real(*a, **kw)))
    pruned, before = metrics().counter("search.segments_pruned"), _fetch()
    p0 = pruned.value
    resp = s.search({"query": query, "size": 3, "track_total_hits": False})
    assert pruned.value == p0 + 1
    assert _fetch()["arrays"] - before["arrays"] == 1
    assert resp["hits"]["hits"] == exact["hits"]["hits"]
    assert resp["hits"]["total"] == {"value": 4, "relation": "gte"}
    assert exact["hits"]["total"] == {"value": 7, "relation": "eq"}


# -- over REST --------------------------------------------------------------

TEXT, VECTORS, S = "packed_text", "packed_vectors", 3


def _call(node, method, path, body=None, ndjson=None):
    c = http.client.HTTPConnection("127.0.0.1", node.port)
    headers, data = {}, None
    if ndjson is not None:
        data = "".join(json.dumps(line) + "\n" for line in ndjson)
        headers["Content-Type"] = "application/x-ndjson"
    elif body is not None:
        data = json.dumps(body)
        headers["Content-Type"] = "application/json"
    c.request(method, path, body=data, headers=headers)
    resp = c.getresponse()
    out = json.loads(resp.read() or b"{}")
    c.close()
    return resp.status, out


@pytest.fixture(scope="module")
def node(tmp_path_factory):
    """``S`` text segments and ``S`` vector segments, served over HTTP."""
    node = Node(str(tmp_path_factory.mktemp("packed")), port=0).start()
    one_shard = {"number_of_shards": 1, "number_of_replicas": 0}
    mappings = {TEXT: {"t": {"type": "text"}},
                VECTORS: {"v": {"type": "knn_vector", "dimension": 4,
                                "method": {"name": "exact",
                                           "space_type": "l2"}}}}
    for index, props in mappings.items():
        assert _call(node, "PUT", "/" + index, {
            "settings": one_shard,
            "mappings": {"properties": props}})[0] == 200
        for batch in range(S):               # a refresh each: S segments
            lines = []
            for i in range(20):
                n = batch * 20 + i
                lines += [{"index": {"_index": index, "_id": str(n)}},
                          {"t": f"alpha w{i % 3} beta",
                           "v": [n, n + 1, n * 0.5, 1.0]}]
            status, resp = _call(node, "POST", "/_bulk?refresh=true",
                                 ndjson=lines)
            assert status == 200 and not resp["errors"], resp
        total = _call(node, "GET", f"/{index}/_stats")[1]["indices"][index][
            "total"]
        assert total["segments"]["count"] == S
    yield node
    node.stop()
    device_ledger().reset()


@pytest.mark.parametrize("index,query,arrays,ops", [
    (TEXT, {"match": {"t": "alpha w1"}}, S, 1),
    (VECTORS, {"knn": {"v": {"vector": [7.0, 4, 1.5, 1], "k": 3}}},
     3 * S, 2)], ids=["term_bag", "knn"])
def test_nodes_stats_counts_the_arrays_read(node, index, query, arrays, ops):
    """``device.transfers.fetch.arrays``: one a segment for a term bag;
    for kNN two a segment in the pre-pass and one for the winners' pass.
    ``ops`` goes on counting sync regions."""
    def fetch():
        nodes = _call(node, "GET", "/_nodes/stats")[1]["nodes"]
        return next(iter(nodes.values()))["device"]["transfers"]["fetch"]

    before = fetch()
    status, resp = _call(node, "POST", f"/{index}/_search",
                         {"query": query, "size": 3})
    assert status == 200 and len(resp["hits"]["hits"]) == 3, resp
    after = fetch()
    assert after["arrays"] - before["arrays"] == arrays
    assert after["ops"] - before["ops"] == ops
