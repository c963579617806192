"""Segment writer + BM25 kernel vs a scalar numpy oracle.

Mirrors the reference's correctness bar for the query phase: top-k ids and
scores must match doc-at-a-time BM25 (ContextIndexSearcher.java:318
semantics: ascending-doc-id tie-break, collection-wide idf/avgdl).
"""

import math
import random

import numpy as np
import pytest

from opensearch_tpu.mapping.mapper import DocumentMapper
from opensearch_tpu.index.segment import SegmentWriter
from opensearch_tpu.ops import bm25
from opensearch_tpu.ops.topk import topk_exact

K1, B = 1.2, 0.75

VOCAB = [f"w{i}" for i in range(50)]


@pytest.fixture(scope="module")
def corpus():
    rng = random.Random(42)
    docs = []
    for i in range(500):
        body = " ".join(rng.choice(VOCAB) for _ in range(rng.randint(3, 40)))
        docs.append({"body": body})
    return docs


@pytest.fixture(scope="module")
def segment(corpus):
    mapper = DocumentMapper({"properties": {"body": {"type": "text"}}})
    parsed = [mapper.parse(str(i), d) for i, d in enumerate(corpus)]
    return SegmentWriter().build(parsed, "seg0")


def oracle_scores(corpus, terms):
    """Doc-at-a-time float64 BM25 over whitespace-tokenized bodies."""
    tokenized = [d["body"].lower().split() for d in corpus]
    n = len(corpus)
    dls = [len(t) for t in tokenized]
    avgdl = sum(dls) / n
    scores = np.zeros(n)
    for term in terms:
        df = sum(1 for t in tokenized if term in t)
        if df == 0:
            continue
        idf = math.log(1 + (n - df + 0.5) / (df + 0.5))
        for i, toks in enumerate(tokenized):
            tf = toks.count(term)
            if tf:
                norm = K1 * (1 - B + B * dls[i] / avgdl)
                scores[i] += idf * tf / (tf + norm)
    return scores


def run_kernel(segment, corpus, terms, k=10):
    dev = segment.device()
    pf = segment.postings["body"]
    arrs = dev.postings["body"]
    n = segment.n_docs
    avgdl = pf.total_len / max(pf.docs_with_field, 1)
    tids, idfs, active = [], [], []
    for t in terms:
        tid = pf.term_id(t)
        if tid < 0:
            tids.append(0), idfs.append(0.0), active.append(False)
        else:
            tids.append(tid)
            idfs.append(bm25.idf(int(pf.df[tid]), n))
            active.append(True)
    total = sum(int(pf.df[t]) for t, a in zip(tids, active) if a)
    budget = max(8, 1 << (total - 1).bit_length())
    scores = bm25.bm25_scores(
        arrs["offsets"], arrs["doc_ids"], arrs["tfs"], arrs["doc_lens"],
        np.asarray(tids, np.int32), np.asarray(active),
        np.asarray(idfs, np.float32), np.ones(len(tids), np.float32),
        np.float32(avgdl), n_pad=dev.n_pad, budget=budget)
    scores = np.asarray(scores)
    vals, idx = topk_exact(
        np.where(np.arange(dev.n_pad) < n, scores, -np.inf), k)
    return np.asarray(scores[:n]), np.asarray(vals), np.asarray(idx)


def test_single_term_matches_oracle(segment, corpus):
    want = oracle_scores(corpus, ["w3"])
    got, _, _ = run_kernel(segment, corpus, ["w3"])
    np.testing.assert_allclose(got, want, rtol=1e-5)


def test_multi_term_matches_oracle(segment, corpus):
    terms = ["w1", "w7", "w33"]
    want = oracle_scores(corpus, terms)
    got, vals, idx = run_kernel(segment, corpus, terms)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    # top-10 ordering matches oracle ordering (score desc, doc id asc)
    order = sorted(range(len(want)), key=lambda i: (-want[i], i))[:10]
    assert list(idx) == order


def test_absent_term_contributes_nothing(segment, corpus):
    got, _, _ = run_kernel(segment, corpus, ["nosuchterm", "w5"])
    want = oracle_scores(corpus, ["w5"])
    np.testing.assert_allclose(got, want, rtol=1e-5)


def test_match_count_conjunction(segment, corpus):
    terms = ["w1", "w2"]
    dev = segment.device()
    pf = segment.postings["body"]
    arrs = dev.postings["body"]
    tids = np.asarray([pf.term_id(t) for t in terms], np.int32)
    counts = bm25.match_count(
        arrs["offsets"], arrs["doc_ids"], arrs["tfs"], tids,
        np.asarray([True, True]), n_pad=dev.n_pad, budget=2048)
    counts = np.asarray(counts)[: segment.n_docs]
    for i, d in enumerate(corpus):
        toks = set(d["body"].split())
        assert counts[i] == sum(1 for t in terms if t in toks)


def test_multivalued_numeric_dv(segment):
    # built from a different mapper run: array fields land all values
    mapper = DocumentMapper({"properties": {"n": {"type": "long"}}})
    docs = [mapper.parse(str(i), {"n": v}) for i, v in
            enumerate([[3, 1, 2], 7, [], [5, 5]])]
    seg = SegmentWriter().build(docs, "s")
    dv = seg.numeric_dv["n"]
    assert dv.values.tolist() == [1, 2, 3, 7, 5, 5]
    assert dv.value_docs.tolist() == [0, 0, 0, 1, 3, 3]
    assert dv.minv[0] == 1 and dv.maxv[0] == 3
    assert not dv.exists[2]


# -- write path: per-field columns are built once, not once per doc ----------

DV_MAPPING = {"properties": {
    "body": {"type": "text"}, "n": {"type": "long"},
    "x": {"type": "double"}, "k": {"type": "keyword"},
    "g": {"type": "geo_point"}}}


def _dv_docs(n_docs: int, seed: int = 5) -> list:
    """Sparse, multi-valued doc-value fields: every fourth doc lacks
    ``x``, every fifth has two ``n`` values, every seventh lacks ``g``."""
    rng = random.Random(seed)
    docs = []
    for i in range(n_docs):
        d = {"body": " ".join(rng.choice(VOCAB) for _ in range(6)),
             "n": [i, i % 13] if i % 5 == 0 else i,
             "k": [f"k{i % 11}", f"k{i % 3}"]}
        if i % 4:
            d["x"] = rng.random() * 100
        if i % 7:
            d["g"] = {"lat": rng.uniform(-80, 80),
                      "lon": rng.uniform(-170, 170)}
        docs.append(d)
    return docs


def test_build_columns_equal_the_per_doc_loop_reference():
    """The columns equal what the straightforward per-doc loop yields
    (one list per doc per field, filled by setdefault as the writer used
    to, at O(n) per doc)."""
    mapper = DocumentMapper(DV_MAPPING)
    parsed = [mapper.parse(str(i), d)
              for i, d in enumerate(_dv_docs(200))]
    n = len(parsed)
    seg = SegmentWriter().build(parsed, "cols")

    longs, doubles, ordinals, geos, lens, present = {}, {}, {}, {}, {}, {}
    for i, doc in enumerate(parsed):
        for f, vals in doc.longs.items():
            longs.setdefault(f, [[] for _ in range(n)])[i].extend(vals)
        for f, vals in doc.doubles.items():
            doubles.setdefault(f, [[] for _ in range(n)])[i].extend(vals)
        for f, vals in doc.ordinals.items():
            ordinals.setdefault(f, [[] for _ in range(n)])[i].extend(vals)
        for f, pts in doc.geo_points.items():
            geos.setdefault(f, [[] for _ in range(n)])[i].extend(pts)
        for f, length in doc.field_lengths.items():
            lens.setdefault(f, np.zeros(n, np.float32))[i] = length
            present.setdefault(f, np.zeros(n, bool))[i] = True

    def same(a, b):
        for name, want in vars(b).items():
            got = getattr(a, name)
            if isinstance(want, np.ndarray):
                np.testing.assert_array_equal(got, want, err_msg=name)
            else:
                assert got == want, name

    assert set(seg.numeric_dv) == set(longs) | set(doubles) == {"n", "x"}
    for f, per_doc in longs.items():
        same(seg.numeric_dv[f],
             SegmentWriter._build_numeric(per_doc, n, "long"))
    for f, per_doc in doubles.items():
        same(seg.numeric_dv[f],
             SegmentWriter._build_numeric(per_doc, n, "double"))
    assert set(seg.ordinal_dv) == set(ordinals) == {"k"}
    for f, per_doc in ordinals.items():
        same(seg.ordinal_dv[f], SegmentWriter._build_ordinal(per_doc, n))
    assert set(seg.geo_dv) == set(geos) == {"g"}
    for f, per_doc in geos.items():
        same(seg.geo_dv[f], SegmentWriter._build_geo(per_doc, n))
    for f in lens:
        np.testing.assert_array_equal(seg.postings[f].doc_lens, lens[f])
        np.testing.assert_array_equal(seg.postings[f].present, present[f])


def test_build_30k_docs_with_doc_values_inside_a_fixed_bound():
    """One refresh-sized build stays linear in the buffered docs: 30,000
    docs with long, double, keyword and geo fields finish well inside a
    minute (evaluating an n-element default per doc per field took well
    over ten)."""
    import time

    mapper = DocumentMapper(DV_MAPPING)
    parsed = [mapper.parse(str(i), d)
              for i, d in enumerate(_dv_docs(30_000))]
    t0 = time.monotonic()
    seg = SegmentWriter().build(parsed, "big")
    assert time.monotonic() - t0 < 60.0
    assert seg.n_docs == 30_000
    assert len(seg.numeric_dv["n"].values) == 30_000 + 6_000
