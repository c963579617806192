"""Impact-ordered scoring: byte-exact score parity, plan-cache
zero-recompile hot path, block-max segment pruning, and the hot-path
sync lint.

The tentpole invariant: precomputing per-posting impacts
(``DeviceSegment.impacts``) must not change a single score bit relative
to the impact formula evaluated in numpy float32 — across the
sequential path, the batched msearch path, the pruned path, and after a
refresh rebuilds the searcher with a different avgdl.

The references here mirror the kernels' float32 operation order
(ops/bm25.py ``compute_impacts`` / ``impact_scores``) and accumulate
with ``np.add.at`` in gather order (term-major), which XLA:CPU's
in-order scatter-add reproduces exactly.  Queries use <=2 distinct
terms so per-doc accumulation order is commutativity-safe across the
sequential and batched layouts.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from opensearch_tpu.common.telemetry import metrics
from opensearch_tpu.index.segment import SegmentWriter
from opensearch_tpu.mapping.mapper import DocumentMapper
from opensearch_tpu.search import plan as P
from opensearch_tpu.search.executor import ShardSearcher

K1, B = 1.2, 0.75
TOOLS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools")


def zipf_corpus(rng, n_docs, vocab=120, avg_len=24):
    docs = []
    for _ in range(n_docs):
        n = int(rng.integers(avg_len // 2, avg_len * 2))
        terms = (rng.zipf(1.4, size=n) - 1).clip(0, vocab - 1)
        docs.append({"body": " ".join(f"w{t}" for t in terms)})
    return docs


def build_searcher(docs, seg_sizes):
    mapper = DocumentMapper({"properties": {"body": {"type": "text"}}})
    writer = SegmentWriter()
    segs, i = [], 0
    for si, size in enumerate(seg_sizes):
        batch = [mapper.parse(str(i + j), d)
                 for j, d in enumerate(docs[i: i + size])]
        segs.append(writer.build(batch, f"imp{si}"))
        i += size
    return ShardSearcher(segs, mapper), mapper


def reference_scores(searcher, terms, weights=None):
    """float32 impact-formula scores per (seg, local), mirroring the
    kernel op-for-op: imp = tf/(tf + k1*(1-b + b*dl/avgdl)), contrib =
    w * (idf * imp), accumulated term-major per segment."""
    field = "body"
    stats = searcher.ctx.field_stats(field)
    avgdl = np.float32(stats.avgdl)
    weights = weights or [1.0] * len(terms)
    out = {}
    for si, seg in enumerate(searcher.segments):
        pf = seg.postings[field]
        dl = pf.doc_lens[pf.doc_ids]
        norm = np.float32(K1) * (np.float32(1.0 - B)
                                 + np.float32(B) * dl / avgdl)
        imp = (pf.tfs / (pf.tfs + norm)).astype(np.float32)
        scores = np.zeros(seg.n_docs, np.float32)
        for t, w in zip(terms, weights):
            tid = pf.term_id(t)
            if tid < 0:
                continue
            idf = np.float32(P.bm25_ops.idf(searcher.ctx.df(field, t),
                                            stats.doc_count))
            e0, e1 = int(pf.offsets[tid]), int(pf.offsets[tid + 1])
            base = idf * imp[e0:e1]
            np.add.at(scores, pf.doc_ids[e0:e1], np.float32(w) * base)
        for local in range(seg.n_docs):
            out[(si, local)] = scores[local]
    return out


def hit_scores(searcher, resp):
    """{(seg, local): float32 score} out of a search response."""
    id_of = {}
    for si, seg in enumerate(searcher.segments):
        for local, did in enumerate(seg.doc_ids):
            id_of[did] = (si, local)
    return {id_of[h["_id"]]: np.float32(h["_score"])
            for h in resp["hits"]["hits"]}


@pytest.fixture(params=["host", "device"])
def scoring_path(request):
    """Run the parity suite over the XLA kernels (what every backend
    executes) AND over the host scorer that recovers them (through the
    open breakers of ``host_recovery``).  They must be byte-identical."""
    if request.param == "host":
        request.getfixturevalue("host_recovery")
    return request.param


@pytest.mark.parametrize("seed", [3, 17, 92])
def test_sequential_batched_pruned_scores_byte_exact(seed, scoring_path):
    rng = np.random.default_rng(seed)
    docs = zipf_corpus(rng, 220)
    searcher, _ = build_searcher(docs, [90, 70, 60])
    for _ in range(6):
        a, b = (rng.zipf(1.4, size=2) - 1).clip(0, 119)
        terms = [f"w{a}"] if a == b else [f"w{a}", f"w{b}"]
        query = {"match": {"body": " ".join(terms)}}
        ref = reference_scores(searcher, terms)
        n = sum(s.n_docs for s in searcher.segments)

        # sequential path: every hit byte-equal to the reference formula
        resp = searcher.search({"query": query, "size": n})
        got = hit_scores(searcher, resp)
        assert got, "query matched nothing — bad corpus seed"
        for key, s in got.items():
            assert s == np.float32(ref[key]), (key, s, ref[key])
        assert resp["hits"]["total"]["value"] == \
            sum(1 for v in ref.values() if v > 0)

        # batched msearch path: byte-equal to the sequential path
        [mresp] = searcher.msearch([{"query": query, "size": n}])
        mgot = hit_scores(searcher, mresp)
        assert mgot == got

        # pruned path (min_score): the skip must only drop segments
        # that contribute nothing, never change a surviving score
        cutoff = float(np.median([v for v in ref.values() if v > 0]))
        presp = searcher.search({"query": query, "size": n,
                                 "min_score": cutoff})
        pgot = hit_scores(searcher, presp)
        for key, s in pgot.items():
            assert s == np.float32(ref[key])
        assert set(pgot) == {k for k, s in got.items()
                             if s >= np.float32(cutoff)}


def test_and_semantics_and_weights_byte_exact():
    rng = np.random.default_rng(5)
    docs = zipf_corpus(rng, 150)
    searcher, _ = build_searcher(docs, [80, 70])
    terms = ["w0", "w3"]
    ref = reference_scores(searcher, terms, weights=[2.5, 2.5])
    q = {"match": {"body": {"query": "w0 w3", "operator": "and",
                            "boost": 2.5}}}
    n = sum(s.n_docs for s in searcher.segments)
    resp = searcher.search({"query": q, "size": n})
    got = hit_scores(searcher, resp)
    assert got
    for key, s in got.items():
        assert s == np.float32(ref[key])
    [mresp] = searcher.msearch([{"query": q, "size": n}])
    assert hit_scores(searcher, mresp) == got


def test_refresh_invalidates_staged_impacts(tmp_path):
    """A refresh that changes avgdl must re-derive impacts: scores after
    the refresh must match the reference recomputed against the NEW
    shard stats, exactly."""
    from opensearch_tpu.indices.service import IndexService

    svc = IndexService("imp", str(tmp_path / "imp"), {},
                       {"properties": {"body": {"type": "text"}}})
    rng = np.random.default_rng(11)
    docs = zipf_corpus(rng, 60)
    for i, d in enumerate(docs):
        svc.index_doc(str(i), d)
    svc.refresh()
    q = {"match": {"body": "w0 w2"}}
    s1 = svc.searcher()
    ref1 = reference_scores(s1, ["w0", "w2"])
    got1 = hit_scores(s1, svc.search({"query": q, "size": 100}))
    assert got1
    for key, s in got1.items():
        assert s == np.float32(ref1[key])
    # second wave with much longer docs shifts avgdl
    more = zipf_corpus(rng, 40, avg_len=80)
    for i, d in enumerate(more):
        svc.index_doc(f"n{i}", d)
    svc.refresh()
    s2 = svc.searcher()
    assert s2 is not s1           # reader generation bumped
    ref2 = reference_scores(s2, ["w0", "w2"])
    got2 = hit_scores(s2, svc.search({"query": q, "size": 200}))
    assert got2
    for key, s in got2.items():
        assert s == np.float32(ref2[key])
    # the old searcher's avgdl keys must actually differ (stats moved)
    assert s1.ctx.field_stats("body").avgdl != \
        s2.ctx.field_stats("body").avgdl


def test_repeated_query_zero_compile_zero_retrace(monkeypatch):
    """The zero-recompile hot path: a repeated identical-shape query
    must hit the plan cache (no compile_query), reuse prepared bindings,
    and add no XLA trace cache entries."""
    import opensearch_tpu.search.executor as ex

    rng = np.random.default_rng(7)
    searcher, _ = build_searcher(zipf_corpus(rng, 120), [60, 60])
    body = {"query": {"match": {"body": "w1 w4"}}, "size": 5}
    hits_c = metrics().counter("search.plan_cache.hits")
    miss_c = metrics().counter("search.plan_cache.misses")
    m0h, m0m = hits_c.value, miss_c.value
    first = searcher.search(body)
    assert miss_c.value > m0m          # cold: compiled once
    calls = []
    real = ex.compile_query
    monkeypatch.setattr(ex, "compile_query",
                        lambda *a, **k: calls.append(1) or real(*a, **k))
    traces_before = P.run_topk._cache_size()
    h1 = hits_c.value
    second = searcher.search(body)
    assert calls == []                 # zero compile_query calls
    assert hits_c.value > h1           # served from the plan cache
    assert P.run_topk._cache_size() == traces_before   # zero retraces
    assert [h["_id"] for h in second["hits"]["hits"]] == \
        [h["_id"] for h in first["hits"]["hits"]]
    assert [h["_score"] for h in second["hits"]["hits"]] == \
        [h["_score"] for h in first["hits"]["hits"]]
    # key order in the body must not miss (canonicalized keys)
    h2 = hits_c.value
    searcher.search({"size": 5, "query": {"match": {"body": "w1 w4"}}})
    assert hits_c.value > h2


def test_min_score_pruning_skips_segments_exactly():
    """Segments whose block-max bound can't reach min_score are skipped
    without dispatch, and results are identical to the unpruned path."""
    mapper = DocumentMapper({"properties": {"body": {"type": "text"}}})
    writer = SegmentWriter()
    # seg 0: the term occurs once in a LONG doc (low impact);
    # seg 1: high-tf short docs (high impact)
    low = [mapper.parse("L0", {"body": "alpha " + "pad " * 200})]
    high = [mapper.parse(f"H{i}", {"body": "alpha alpha alpha"})
            for i in range(3)]
    segs = [writer.build(low, "low"), writer.build(high, "high")]
    searcher = ShardSearcher(segs, mapper)
    q = {"match": {"body": "alpha"}}
    all_scores = sorted(
        (h["_score"] for h in
         searcher.search({"query": q, "size": 10})["hits"]["hits"]),
        reverse=True)
    assert len(all_scores) == 4
    cutoff = (all_scores[2] + all_scores[3]) / 2  # between high and low
    plan, bind = searcher.compiled(q, scored=True)
    bounds = [plan.max_score_bound(bind, seg)
              for seg in searcher.segments]
    assert bounds[0] < cutoff <= bounds[1]
    pruned_c = metrics().counter("search.segments_pruned")
    p0 = pruned_c.value
    resp = searcher.search({"query": q, "size": 10, "min_score": cutoff})
    assert pruned_c.value == p0 + 1         # the low segment skipped
    assert resp["hits"]["total"]["value"] == 3
    assert {h["_id"] for h in resp["hits"]["hits"]} == {"H0", "H1", "H2"}
    assert resp["hits"]["total"]["relation"] == "eq"


def test_kth_score_pruning_with_waived_totals():
    """track_total_hits=false lets block-max pruning skip segments that
    can't beat the running k-th score; totals degrade to a lower bound
    flagged with relation gte, top-k hits stay identical."""
    mapper = DocumentMapper({"properties": {"body": {"type": "text"}}})
    writer = SegmentWriter()
    high = [mapper.parse(f"H{i}", {"body": "alpha alpha alpha"})
            for i in range(4)]
    low = [mapper.parse(f"L{i}", {"body": "alpha " + "pad " * 200})
           for i in range(3)]
    searcher = ShardSearcher(
        [writer.build(high, "high"), writer.build(low, "low")], mapper)
    body = {"query": {"match": {"body": "alpha"}}, "size": 3,
            "track_total_hits": False}
    exact = searcher.search({"query": body["query"], "size": 3})
    resp = searcher.search(body)
    assert [h["_id"] for h in resp["hits"]["hits"]] == \
        [h["_id"] for h in exact["hits"]["hits"]]
    if resp["hits"]["total"]["relation"] == "gte":
        assert resp["hits"]["total"]["value"] <= \
            exact["hits"]["total"]["value"]
    else:   # harvest raced slower than dispatch: exact answer is fine
        assert resp["hits"]["total"] == exact["hits"]["total"]


def test_count_skips_unmatchable_segments():
    """ShardSearcher.count() can-match-skips segments the plan provably
    can't match, with identical counts."""
    mapper = DocumentMapper({"properties": {"body": {"type": "text"}}})
    writer = SegmentWriter()
    segs = [writer.build([mapper.parse(f"{si}-{i}",
                                       {"body": f"seg{si} common"})
                          for i in range(4)], f"c{si}")
            for si in range(3)]
    searcher = ShardSearcher(segs, mapper)
    pruned_c = metrics().counter("search.segments_pruned")
    p0 = pruned_c.value
    assert searcher.count({"match": {"body": "seg1"}}) == 4
    assert pruned_c.value == p0 + 2      # two segments never dispatched
    assert searcher.count({"match": {"body": "common"}}) == 12


# -- tools/check_hot_path_sync.py lint --------------------------------------

def test_check_hot_path_sync_lint_passes():
    r = subprocess.run(
        [sys.executable, os.path.join(TOOLS, "check_hot_path_sync.py")],
        capture_output=True, text=True)
    assert r.returncode == 0, r.stdout + r.stderr


def test_check_hot_path_sync_lint_catches_violations(tmp_path):
    bad = tmp_path / "search"
    bad.mkdir()
    (bad / "executor.py").write_text(
        "import numpy as np\n"
        "class ShardSearcher:\n"
        "    def _topk(self, plan):\n"
        "        out = []\n"
        "        for seg in self.segments:\n"
        "            vals = self.run(seg)\n"
        "            out.append(np.asarray(vals))\n"
        "            score = float(vals[0])\n"
        "            ok = np.asarray(vals)  # sync-ok\n"
        "        return out\n")
    r = subprocess.run(
        [sys.executable, os.path.join(TOOLS, "check_hot_path_sync.py"),
         str(tmp_path)],
        capture_output=True, text=True)
    assert r.returncode == 1
    assert "asarray" in r.stdout and "float" in r.stdout
    # the annotated line is not reported
    assert r.stdout.count("asarray") == 1
