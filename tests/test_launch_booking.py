"""A launched program is booked as it was launched: ``plan.choose`` finds
what a segment program does and ``plan.book`` books it, at every launch
site (``ShardSearcher._topk`` and ``_run_full``, the filtered ``knn``'s
mask programs and scans, ``MeshSearcher``).  Each case runs one request
with the jitted entry points wrapped, works out from the arguments each
program took what the ledger's flags and the ``search.term_bag.*`` and
``search.phrase.*`` counters should move by, and compares.  A profiled
request answers as an unprofiled one."""

import collections

import jax
import numpy as np
import pytest

from opensearch_tpu.common.device_ledger import device_ledger
from opensearch_tpu.common.telemetry import metrics
from opensearch_tpu.index.segment import SegmentWriter
from opensearch_tpu.mapping.mapper import DocumentMapper
from opensearch_tpu.ops import bm25 as bm25_ops
from opensearch_tpu.ops import knn as knn_ops
from opensearch_tpu.ops import topk as topk_ops
from opensearch_tpu.parallel import dist_search
from opensearch_tpu.search import engine
from opensearch_tpu.search import plan as P
from opensearch_tpu.search.executor import ShardSearcher

FLAGS = ("slice_gather_programs", "block_topk_programs",
         "sorted_bag_programs", "phrase_programs", "mesh_programs")
COUNTERS = ("search.term_bag.postings", "search.term_bag.budget_lanes",
            "search.term_bag.sorted_lanes",
            "search.term_bag.stacked_segments",
            "search.phrase.anchor_positions", "search.phrase.budget_lanes")
MAPPING = {"properties": {
    "body": {"type": "text"}, "tags": {"type": "keyword"},
    "vec": {"type": "knn_vector", "dimension": 4, "space_type": "l2"}}}
WORDS = "w0 w1 w2 w3 w4 w5 w6 w7".split()


@pytest.fixture(autouse=True)
def device_path(monkeypatch):
    monkeypatch.setattr(engine, "BATCHER_ENABLED", False)


def _segments(name, sizes, seed=11, deleted=()):
    """Segments of ``sizes`` docs, ``common`` in every doc and ``rare``
    in one in fifty; ``deleted``: the segments that lose their first
    doc."""
    rng = np.random.default_rng(seed)
    mapper = DocumentMapper(MAPPING)
    writer = SegmentWriter()
    segs, n = [], 0
    for s, size in enumerate(sizes):
        docs = []
        for i in range(size):
            words = rng.choice(WORDS, size=int(rng.integers(2, 7))).tolist()
            words.append("common")
            if i % 50 == 0:
                words.append("rare")
            docs.append(mapper.parse(str(n), {
                "body": " ".join(words), "tags": ["even", "odd"][n % 2],
                "vec": rng.integers(0, 16, size=4).astype(float).tolist()}))
            n += 1
        seg = writer.build(docs, f"{name}{s}")
        seg.index_name = name
        if s in deleted:
            seg.delete_local(0)
        segs.append(seg)
    return ShardSearcher(segs, mapper, index_name=name)


@pytest.fixture(scope="module")
def live():
    return _segments("book_live", [60, 45, 30])


@pytest.fixture(scope="module")
def deleted():
    return _segments("book_deleted", [60, 45, 30], deleted=(1,))


@pytest.fixture(scope="module")
def wide():
    """One segment of 20,000 docs: ``common`` keys 65,536 lanes,
    ``rare`` 4,096."""
    return _segments("book_wide", [20000])


class Launches:
    """The jitted entry points wrapped: what each launched program took
    adds to ``want``, the deltas the ledger and the counters should
    show."""

    def __init__(self, monkeypatch):
        self.want = collections.Counter()
        self.programs = 0
        for owner, name, took in (
                (P, "run_topk", self._topk), (P, "run_full", self._full),
                (knn_ops, "knn_topk_auto", self._scan),
                (dist_search, "run_topk_mesh", self._mesh),
                (dist_search, "merge_topk_mesh", self._merge)):
            monkeypatch.setattr(owner, name, self._wrap(
                getattr(owner, name), took))

    def _wrap(self, real, took):
        def launch(*args, **kwargs):
            self.programs += 1
            took(*args, **kwargs)
            return real(*args, **kwargs)
        return launch

    def _topk(self, plan, dims, k, A, ins, ms, *, sorted_bag=False):
        w = self.want
        lanes = dims[1] if sorted_bag else A["live"].shape[0]
        w["sorted_bag_programs"] += sorted_bag
        w["block_topk_programs"] += bool(topk_ops.block_size(lanes, k))
        w["slice_gather_programs"] += plan.slice_gathers(dims)
        phrases = list(P.phrase_dims(dims))
        w["phrase_programs"] += bool(phrases)
        w["search.phrase.anchor_positions"] += sum(
            pd.anchor_positions for pd in phrases)
        w["search.phrase.budget_lanes"] += sum(pd[1] for pd in phrases)
        if isinstance(plan, P.TermBagPlan) and plan.scored:
            w["search.term_bag.postings"] += dims.postings
            w["search.term_bag.budget_lanes"] += dims[1]
            w["search.term_bag.sorted_lanes"] += (bm25_ops.sorted_width(
                dims[1], dims.postings, k) if sorted_bag else 0)
            w["search.term_bag.stacked_segments"] += ins[0].ndim == 2

    def _full(self, plan, dims, A, ins, ms):
        self.want["slice_gather_programs"] += plan.slice_gathers(dims)

    def _scan(self, vectors, valid, query, *, space, k):
        self.want["block_topk_programs"] += bool(
            topk_ops.block_size(vectors.shape[0], k))

    def _mesh(self, mesh, axis, plan, dims, k, k_out, sorted_bag, shapes,
              A, ins, ms):
        # the mesh books its lowerings; its phrase and bag counters are
        # the single chip's
        n_pad = A["live"].shape[0] // mesh.devices.size
        w = self.want
        w["mesh_programs"] += 1
        w["sorted_bag_programs"] += sorted_bag
        w["block_topk_programs"] += bool(topk_ops.block_size(
            dims[1] if sorted_bag else n_pad, k))
        w["slice_gather_programs"] += plan.slice_gathers(dims)

    def _merge(self, mesh, axis, k, parts):
        self.want["mesh_programs"] += 1


def _read(index):
    """The flags, the counters and the dispatches of ``index``'s segments
    and of the mesh (another test's segments may be collected, and take
    their dispatches out of the ledger's total, at any time)."""
    stats = device_ledger().stats()
    out = {f: stats[f] for f in FLAGS}
    out.update({c: metrics().counter(c).value for c in COUNTERS})
    out["dispatches"] = stats["mesh_programs"] + stats["indices"].get(
        index, {}).get("dispatches", 0)
    return out


def _booked(monkeypatch, index, run):
    """(what the programs took, what was booked) over one ``run()``."""
    launches = Launches(monkeypatch)
    before = _read(index)
    resp = run()
    after = _read(index)
    booked = {k: after[k] - before[k] for k in after}
    want = {k: launches.want[k] for k in FLAGS + COUNTERS}
    want["dispatches"] = launches.programs
    return resp, want, booked


def _mesh_shards():
    return [_segments(f"book_mesh{si}", [24, 18], seed=40 + si)
            for si in range(4)]


BAG = {"match": {"body": "common w3"}}
CASES = {
    # every segment all live: each program sorts its postings
    "sorted_bag": ("live", {"query": BAG, "size": 5},
                   {"sorted_bag_programs": 3,
                    "search.term_bag.stacked_segments": 3}),
    # a deleted doc keeps its segment on the dense scatter
    "deleted_doc": ("deleted", {"query": BAG, "size": 5},
                    {"sorted_bag_programs": 2}),
    "under_block_size": ("wide", {"query": {"match": {"body": "rare"}},
                                  "size": 10},
                         {"sorted_bag_programs": 1,
                          "block_topk_programs": 0}),
    "over_block_size": ("wide", {"query": {"match": {"body": "common"}},
                                 "size": 10},
                        {"sorted_bag_programs": 1,
                         "block_topk_programs": 1,
                         "search.term_bag.budget_lanes": 65536}),
    "phrase_under_bool": ("live", {"query": {"bool": {
        "must": [{"match": {"body": "common"}}],
        "should": [{"match_phrase": {"body": "w1 w2"}}]}}, "size": 5},
        {"phrase_programs": 3, "sorted_bag_programs": 0}),
    # the filter's mask programs (run_full), the scans, the winners
    "filtered_knn": ("live", {"query": {"knn": {"vec": {
        "vector": [3.0, 5.0, 7.0, 1.0], "k": 5,
        "filter": {"term": {"tags": "even"}}}}}, "size": 5},
        {"dispatches": 9}),
    # a running k-th prunes after the bind: one input a segment
    "no_total_hits": ("live", {"query": BAG, "size": 5,
                               "track_total_hits": False},
                      {"search.term_bag.stacked_segments": 0}),
    "mesh": ("mesh", {"query": BAG, "size": 5},
             {"mesh_programs": 3, "search.term_bag.postings": 0}),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_a_launched_program_is_booked_as_it_ran(name, request,
                                                 monkeypatch):
    where, body, pinned = CASES[name]
    if where == "mesh":
        if len(jax.devices()) < 4:
            pytest.skip("needs 4 virtual devices")
        searcher = dist_search.MeshSearcher(_mesh_shards())
    else:
        searcher = request.getfixturevalue(where)
    resp, want, booked = _booked(monkeypatch, f"book_{where}",
                                 lambda: searcher.search(dict(body)))
    assert resp["hits"]["hits"]
    assert booked == want
    for key, value in pinned.items():
        assert booked[key] == value, key


@pytest.mark.parametrize("body", [
    {"query": BAG, "size": 7},
    {"query": {"bool": {"must": [{"match": {"body": "common"}}],
                        "should": [{"match_phrase": {"body": "w1 w2"}}]}},
     "size": 7},
    {"query": BAG, "size": 0, "aggs": {"t": {"terms": {"field": "tags"}}}},
    {"query": BAG, "size": 7, "sort": [{"tags": "asc"}]}],
    ids=["bag", "phrase", "count", "sorted"])
def test_a_profiled_request_answers_as_an_unprofiled_one(live, body):
    plain = live.search(dict(body))
    profiled = live.search(dict(body, profile=True))
    assert "profile" not in plain and profiled["profile"]["shards"]
    for resp in (plain, profiled):
        resp.pop("took")
        resp.pop("profile", None)
    assert profiled == plain
