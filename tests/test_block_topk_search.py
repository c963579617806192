"""The two-stage top-k (``ops/topk.py``) under ``ShardSearcher.search``.

Two segments wide enough for the two stages (33,000 rows, ``n_pad``
65,536) and one that is not (500 rows), scores tied by the hundred (a
vocabulary of twelve words in bodies of four; vectors on a coarse grid):
a BM25 term bag against ``TermBagPlan.host_topk``, an exact ``knn``, a
filtered ``knn`` and a ``hybrid`` request against numpy give identical
ids, scores and ``hits.total``; and ``device.block_topk_programs`` rises
by the segment programs whose ``(n_pad, k)`` the rule takes, by none for
a ``size`` that forces ``lax.top_k``."""

import numpy as np
import pytest

from opensearch_tpu.common.device_health import device_health
from opensearch_tpu.common.device_ledger import device_ledger
from opensearch_tpu.index.segment import SegmentWriter
from opensearch_tpu.mapping.mapper import DocumentMapper
from opensearch_tpu.ops.topk import block_size
from opensearch_tpu.search import engine
from opensearch_tpu.search.executor import ShardSearcher

VOCAB = [f"w{i}" for i in range(12)]
SIZES = (33000, 33000, 500)
QVEC = [3.0, 1.0, 1.0, 0.5]
MATCH = {"match": {"body": "w1 w2"}}
KNN = {"knn": {"v": {"vector": QVEC, "k": 10}}}
KNN_B = {"knn": {"v": {"vector": QVEC, "k": 10,
                       "filter": {"term": {"tags": "b"}}}}}


def _vector(g: int) -> list:
    return [float(g % 7), (g // 7) % 5 * 0.5, 1.0, (g % 3) * 0.5]


@pytest.fixture(autouse=True)
def device_path(monkeypatch):
    monkeypatch.setattr(engine, "BATCHER_ENABLED", False)


@pytest.fixture(scope="module")
def searcher():
    mapper = DocumentMapper({"properties": {
        "body": {"type": "text"}, "tags": {"type": "keyword"},
        "v": {"type": "knn_vector", "dimension": 4,
              "method": {"name": "exact", "space_type": "l2"}}}})
    rng = np.random.default_rng(34)
    writer, segs, g = SegmentWriter(), [], 0
    for s, n in enumerate(SIZES):
        words = rng.choice(VOCAB, (n, 4))
        docs = []
        for i in range(n):
            docs.append(mapper.parse(str(g), {
                "body": " ".join(words[i]),
                "tags": ["a", "b"] if g % 3 == 0 else ["a"],
                "v": _vector(g)}))
            g += 1
        segs.append(writer.build(docs, f"bt{s}"))
    yield ShardSearcher(segs, mapper)
    device_ledger().reset()
    device_health().reset()


def _hits(resp) -> tuple:
    assert resp["_shards"]["failed"] == 0
    return ([(h["_id"], h["_score"]) for h in resp["hits"]["hits"]],
            resp["hits"]["total"])


def _knn_reference(k: int, keep=lambda g: True) -> list:
    """[(id, score)]: float32 ``1 / (1 + |v - q|^2)`` (every term a
    multiple of 0.25, so exact in any order of summation), the best ``k``
    by score, then by row."""
    n = sum(SIZES)
    vecs = np.asarray([_vector(g) for g in range(n)], np.float32)
    d2 = ((vecs - np.asarray(QVEC, np.float32)) ** 2).sum(
        axis=1, dtype=np.float32)
    scores = np.float32(1.0) / (np.float32(1.0) + d2)
    rows = [g for g in range(n) if keep(g)]
    rows.sort(key=lambda g: (-scores[g], g))
    return [(str(g), float(scores[g])) for g in rows[:k]]


def _programs() -> int:
    return device_ledger().stats()["block_topk_programs"]


def test_the_index_is_wide_enough_and_narrow_enough(searcher):
    n_pads = [seg.device().n_pad for seg in searcher.segments]
    assert n_pads == [65536, 65536, 512]
    assert [bool(block_size(n, 10)) for n in n_pads] == [True, True, False]
    assert not block_size(65536, 300)


@pytest.mark.parametrize("size", [10, 100, 300])
def test_term_bag_equals_the_host_scorer(searcher, host_recovery, size):
    """Sizes on both sides of the rule; ties run past every one of them
    (bodies of four words from twelve: a few score classes)."""
    body = {"query": MATCH, "size": size}
    host = _hits(searcher.search(dict(body)))
    host_recovery.reset()
    before = _programs()
    device = _hits(searcher.search(dict(body)))
    assert device == host
    assert len(device[0]) == size
    assert len({score for _, score in device[0]}) < size / 2     # ties
    # one program a segment; the two wide ones qualify up to k = 252
    assert _programs() - before == (2 if size <= 252 else 0)


def test_exact_knn_equals_numpy(searcher):
    before = _programs()
    got, total = _hits(searcher.search({"query": KNN, "size": 10}))
    want = _knn_reference(10)
    assert got == want
    assert want[0][1] == want[9][1] == 1.0       # ten of many exact ties
    assert total == {"value": 10, "relation": "eq"}
    # the pre-pass and the winners' pass each run a program a segment
    assert _programs() - before == 2 + 2


def test_filtered_knn_equals_numpy(searcher):
    before = _programs()
    got, total = _hits(searcher.search({"query": KNN_B, "size": 10}))
    want = _knn_reference(10, keep=lambda g: g % 3 == 0)
    assert got == want
    assert all(int(i) % 3 == 0 for i, _ in got)
    assert total == {"value": 10, "relation": "eq"}
    # the mask programs (``run_full``) hold no top-k
    assert _programs() - before == 2 + 2


def test_hybrid_equals_numpy(searcher, host_recovery):
    bm25, bm25_total = _hits(searcher.search({"query": MATCH, "size": 10}))
    host_recovery.reset()
    before = _programs()
    # the default boost spelled out: the plan cache is keyed by the
    # query's text, and a hit (the exact-kNN test's) skips the pre-pass
    knn = {"knn": {"v": {**KNN["knn"]["v"], "boost": 1.0}}}
    got, total = _hits(searcher.search({"query": {"hybrid": {"queries": [
        MATCH, knn]}}, "size": 10}))
    combined = {}
    for qi, rows in enumerate((bm25, _knn_reference(10))):
        scores = np.asarray([s for _, s in rows], np.float64)
        lo, hi = scores.min(), scores.max()
        norm = (np.ones_like(scores) if hi - lo < 1e-12
                else (scores - lo) / (hi - lo))
        norm = np.where(norm == 0.0, 0.001, norm)
        for (doc, _), ns in zip(rows, norm):
            combined.setdefault(doc, [0.0, 0.0])[qi] = float(ns)
    want = sorted(((doc, float(np.float64(a + b) / 2.0))
                   for doc, (a, b) in combined.items()),
                  key=lambda r: (-r[1], int(r[0])))[:10]
    assert got == want
    assert total == {"value": bm25_total["value"], "relation": "gte"}
    # a program a segment for the term bag, the pre-pass and the winners
    assert _programs() - before == 2 + 2 + 2
