"""Pallas kernel parity: the hand-scheduled TPU kernels must agree with
the XLA-fused jnp formulations (interpret mode on CPU; chip_smoke.py
compiles them on the chip against a float32 numpy reference)."""

import jax.numpy as jnp
import numpy as np
import pytest

from opensearch_tpu.ops.knn import knn_scores, knn_topk, knn_topk_auto
from opensearch_tpu.ops.pallas_knn import TILE, knn_scores_pallas

N, D = 2 * TILE, 16


@pytest.fixture
def data(rng):
    vectors = jnp.asarray(rng.normal(size=(N, D)).astype(np.float32))
    valid = jnp.asarray(rng.random(N) > 0.2)
    query = jnp.asarray(rng.normal(size=D).astype(np.float32))
    return vectors, valid, query


@pytest.mark.parametrize("space", ["l2", "cosinesimil", "innerproduct"])
def test_pallas_scores_match_jnp(data, space):
    vectors, valid, query = data
    ref = np.asarray(knn_scores(vectors, valid, query, space=space))
    got = np.asarray(knn_scores_pallas(vectors, valid, query,
                                       space=space, interpret=True))
    np.testing.assert_allclose(got, ref, rtol=1e-6)
    assert np.all(np.isneginf(got[~np.asarray(valid)]))


def test_pallas_unknown_space(data):
    vectors, valid, query = data
    with pytest.raises(ValueError):
        knn_scores_pallas(vectors, valid, query, space="hamming",
                          interpret=True)


def test_topk_auto_pallas_path(data, monkeypatch):
    vectors, valid, query = data
    monkeypatch.setenv("OSTPU_PALLAS", "1")
    pv, pi = knn_topk_auto(vectors, valid, query, space="l2", k=7)
    rv, ri = knn_topk(vectors, valid, query, space="l2", k=7)
    np.testing.assert_allclose(np.asarray(pv), np.asarray(rv), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(pi), np.asarray(ri))


@pytest.mark.parametrize("backend,interpret",
                         [("cpu", True), ("tpu", False), ("gpu", False)])
def test_topk_auto_interprets_only_on_cpu(data, monkeypatch, backend,
                                          interpret):
    """cpu -> interpret, anything else -> compile."""
    import jax

    from opensearch_tpu.ops import pallas_knn

    seen = []

    def fake(vectors, valid, query, *, space, interpret):
        seen.append(interpret)
        return knn_scores(vectors, valid, query, space=space)

    monkeypatch.setenv("OSTPU_PALLAS", "1")
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setattr(pallas_knn, "knn_scores_pallas", fake)
    vectors, valid, query = data
    knn_topk_auto(vectors, valid, query, space="l2", k=3)
    assert seen == [interpret]


def test_topk_auto_falls_back_on_odd_layout(rng, monkeypatch):
    monkeypatch.setenv("OSTPU_PALLAS", "1")
    vectors = jnp.asarray(rng.normal(size=(64, D)).astype(np.float32))
    valid = jnp.ones(64, bool)
    query = jnp.asarray(rng.normal(size=D).astype(np.float32))
    v, i = knn_topk_auto(vectors, valid, query, space="l2", k=3)
    rv, ri = knn_topk(vectors, valid, query, space="l2", k=3)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ri))


def test_end_to_end_knn_search_with_pallas(rng, monkeypatch):
    """A corpus big enough to pad past one tile, searched with the flag
    on, must return the same hits as the default path."""
    from opensearch_tpu.index.segment import SegmentWriter
    from opensearch_tpu.mapping.mapper import DocumentMapper
    from opensearch_tpu.search.executor import ShardSearcher

    mapper = DocumentMapper({"properties": {"v": {
        "type": "knn_vector", "dimension": 4,
        "method": {"name": "exact", "space_type": "l2"}}}})
    docs = [mapper.parse(str(i), {"v": rng.normal(size=4).tolist()})
            for i in range(300)]
    seg = SegmentWriter().build(docs, "p0")
    body = {"query": {"knn": {"v": {
        "vector": [0.0, 0.0, 0.0, 0.0], "k": 5}}}}
    searcher = ShardSearcher([seg], mapper)
    base = [h["_id"] for h in searcher.search(body)["hits"]["hits"]]
    monkeypatch.setenv("OSTPU_PALLAS", "1")
    got = [h["_id"] for h in searcher.search(body)["hits"]["hits"]]
    assert got == base and len(got) == 5


def test_method_level_space_type_honored(rng):
    """Regression: space_type nested inside [method] (the opensearch-knn
    plugin's historical mapping shape) must drive scoring — it was
    silently falling back to l2."""
    from opensearch_tpu.index.segment import SegmentWriter
    from opensearch_tpu.mapping.mapper import DocumentMapper
    from opensearch_tpu.search.executor import ShardSearcher

    mapper = DocumentMapper({"properties": {"v": {
        "type": "knn_vector", "dimension": 4,
        "method": {"name": "exact", "space_type": "cosinesimil"}}}})
    assert mapper.field_type("v").space_type == "cosinesimil"
    raw = [rng.normal(size=4).tolist() for _ in range(30)]
    docs = [mapper.parse(str(i), {"v": v}) for i, v in enumerate(raw)]
    searcher = ShardSearcher([SegmentWriter().build(docs, "m0")], mapper)
    q = rng.normal(size=4)
    resp = searcher.search({"query": {"knn": {"v": {
        "vector": q.tolist(), "k": 3}}}})
    X = np.asarray(raw)
    cos = (X @ q) / (np.linalg.norm(X, axis=1) * np.linalg.norm(q))
    want = np.argsort(-cos)[:3]
    assert [h["_id"] for h in resp["hits"]["hits"]] == [str(i) for i in want]
    assert resp["hits"]["hits"][0]["_score"] == pytest.approx(
        (1 + cos[want[0]]) / 2, rel=1e-5)
