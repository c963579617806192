"""One staged input a request: a scored term bag at a plan's root binds
every segment it will launch on, on the host, and crosses to the device
as one ``int32[S_pad, 4 * t_pad + 1]`` (``executor._StackedBag``); each
segment program takes the stacked array and its row
(``plan._term_row``).  The bits against the one-input-a-segment path,
the rows a request stages, the prepared-bindings cache, the requests
that keep one input a segment, and the counter
``search.term_bag.stacked_segments``."""

import jax
import numpy as np
import pytest

from opensearch_tpu.common.device_ledger import device_ledger
from opensearch_tpu.common.telemetry import metrics
from opensearch_tpu.index import codec
from opensearch_tpu.index.segment import SegmentWriter
from opensearch_tpu.mapping.mapper import DocumentMapper
from opensearch_tpu.search import engine, executor
from opensearch_tpu.search.executor import ShardSearcher

PER_SEG = 12
VOCAB = 30
MAPPING = {"properties": {"body": {"type": "text"},
                          "emb": {"type": "rank_features"}}}


@pytest.fixture(autouse=True)
def device_path(monkeypatch):
    monkeypatch.setattr(engine, "BATCHER_ENABLED", False)


def _doc(i: int) -> dict:
    """Words and features from a fixed rule: every segment of twelve
    holds ``w1`` .. ``w4`` and ``f1`` .. ``f3``."""
    words = [f"w{(i * 7 + j * j + (i % 5) * j) % VOCAB}"
             for j in range(5 + i % 7)] + [f"w{1 + i % 4}"]
    feats = {f"f{(i + j) % 9}": 0.25 + ((i * 3 + j) % 11) / 4
             for j in range(3 + i % 4)}
    return {"body": " ".join(words), "emb": feats}


def _searcher(n_segments: int, prefix: str, extra=None) -> ShardSearcher:
    """``extra(segment)``: more words for every doc of that segment."""
    mapper = DocumentMapper(MAPPING)
    writer = SegmentWriter()
    segs = []
    for s in range(n_segments):
        docs = []
        for i in range(PER_SEG):
            n = s * PER_SEG + i
            d = _doc(n)
            if extra is not None:
                d["body"] += extra(s)
            docs.append(mapper.parse(str(n), d))
        segs.append(writer.build(docs, f"{prefix}{s}"))
    return ShardSearcher(segs, mapper)


def _one_a_segment(monkeypatch):
    """The path the stacked bind replaced: every segment prepares and
    stages its own packed input."""
    monkeypatch.setattr(executor._StackedBag, "inputs",
                        lambda self, seg, span: None)


def _answer(resp) -> tuple:
    hits = resp["hits"]
    return ([(h["_id"], np.float32(h["_score"]).view(np.uint32).item())
             for h in hits["hits"]], hits["total"],
            np.float32(hits["max_score"]).view(np.uint32).item())


def _moved(search) -> tuple:
    """(input arrays, input bytes, stacked programs, programs read) that
    one call moves."""
    led = device_ledger()
    counter = metrics().counter("search.term_bag.stacked_segments")
    s0, c0 = led.stats(), counter.value
    resp = search()
    s1 = led.stats()
    return resp, (s1["transfers"]["input"]["arrays"]
                  - s0["transfers"]["input"]["arrays"],
                  s1["transfers"]["input"]["bytes"]
                  - s0["transfers"]["input"]["bytes"],
                  counter.value - c0,
                  s1["transfers"]["fetch"]["arrays"]
                  - s0["transfers"]["fetch"]["arrays"])


QUERIES = {
    "or": ({"match": {"body": "w1 w2 w9"}}, 4),
    "msm": ({"match": {"body": {"query": "w1 w2 w3 w17 w22",
                                "minimum_should_match": 2}}}, 8),
    "neural_sparse": ({"neural_sparse": {"emb": {"query_tokens": {
        "f1": 1.5, "f2": 0.75, "f3": 2.0}}}}, 4),
}


@pytest.mark.parametrize("n_segments,s_pad", [(1, 8), (2, 8), (3, 8),
                                              (9, 16)])
@pytest.mark.parametrize("name", sorted(QUERIES))
@pytest.mark.parametrize("size", [5, 200])
def test_the_stacked_path_gives_the_per_segment_bits(
        name, n_segments, s_pad, size, monkeypatch):
    query, t_pad = QUERIES[name]
    body = {"query": query, "size": size}
    stacked = _searcher(n_segments, f"st{name}{size}")
    resp, moved = _moved(lambda: stacked.search(dict(body)))
    # one array of S_pad rows, a program a segment from it
    assert moved == (1, s_pad * (4 * t_pad + 1) * 4, n_segments,
                     n_segments)
    with monkeypatch.context() as m:
        _one_a_segment(m)
        apart = _searcher(n_segments, f"sa{name}{size}")
        want, moved = _moved(lambda: apart.search(dict(body)))
    assert moved == (n_segments, n_segments * (4 * t_pad + 1) * 4, 0,
                     n_segments)
    assert _answer(resp)[0]
    assert _answer(resp) == _answer(want)


class _Staged:
    """The host arrays handed to the ledger's ``stage_input``."""

    def __init__(self, monkeypatch):
        self.arrays = []
        led = device_ledger()
        real = led.stage_input

        def spy(host_array, sharding=None):
            self.arrays.append(np.array(host_array))
            return real(host_array, sharding)
        monkeypatch.setattr(led, "stage_input", spy)


@pytest.mark.parametrize("holders", [(1,), (0, 2), (0, 1, 2)])
def test_a_segment_that_cannot_match_takes_no_row(holders, monkeypatch):
    """``only`` is a word of the ``holders`` alone: their rows come first,
    in segment order, and the rest of the eight are zeros."""
    s = _searcher(3, "row" + "".join(map(str, holders)),
                  extra=lambda seg: " only" if seg in holders else "")
    staged = _Staged(monkeypatch)
    body = {"query": {"match": {"body": {"query": "only w1",
                                         "operator": "and"}}}, "size": 4}
    resp, moved = _moved(lambda: s.search(dict(body)))
    assert resp["hits"]["total"]["value"] == sum(
        "w1" in _doc(si * PER_SEG + i)["body"].split()
        for si in holders for i in range(PER_SEG))
    assert moved[0] == 1 and moved[2] == moved[3] == len(holders)
    host, = staged.arrays
    assert host.shape == (8, 4 * 2 + 1)
    for row, si in enumerate(holders):
        pf = s.segments[si].postings["body"]
        assert host[row, :2].tolist() == [pf.term_id("only"),
                                          pf.term_id("w1")]
        assert host[row, 2:4].tolist() == [1, 1]     # both active
        assert host[row, 8] == 2                     # required: both
    assert not host[len(holders):].any()


def test_a_plan_cache_hit_stages_nothing(monkeypatch):
    s = _searcher(3, "hit")
    body = {"query": {"match": {"body": "w2 w4"}}, "size": 6}
    first, moved = _moved(lambda: s.search(dict(body)))
    assert moved == (1, 8 * 9 * 4, 3, 3)
    staged = _Staged(monkeypatch)
    again, moved = _moved(lambda: s.search(dict(body)))
    assert moved == (0, 0, 3, 3) and staged.arrays == []
    assert _answer(again) == _answer(first)
    # one entry a request in the prepared-bindings cache, for both
    assert len(s._prep_cache) == 1


def test_a_prepared_hit_is_one_cache_get_a_request(monkeypatch):
    """The request's one ``get`` of the prepared-bindings cache; the
    segments after the first take their row from what it found."""
    s = _searcher(3, "get")
    body = {"query": {"match": {"body": "w1 w3"}}, "size": 6}
    s.search(dict(body))
    cache = s._prep_cache
    gets = []
    real = cache.get
    monkeypatch.setattr(cache, "get", lambda key: gets.append(key)
                        or real(key))
    s.search(dict(body))
    assert len(gets) == 1
    assert gets[0][1] == tuple(id(seg) for seg in s.segments)


def _kth(q):
    return {"query": q, "size": 2, "track_total_hits": False}


BOOL = {"bool": {"must": [{"match": {"body": "w1 w5"}},
                          {"match": {"body": "w2 w3 w4"}}]}}


@pytest.mark.parametrize("body,arrays", [
    # a running k-th score prunes after the bind: one input a program
    (_kth({"match": {"body": "w1 w2"}}), 1),
    # a bag under a bool: two bags and two scalars a segment
    ({"query": BOOL, "size": 5}, 4),
], ids=["track_total_hits_false", "bag_under_a_bool"])
def test_these_keep_one_input_a_segment(body, arrays):
    s = _searcher(3, "keep" + str(arrays))
    resp, moved = _moved(lambda: s.search(dict(body)))
    assert resp["hits"]["hits"]
    n_arrays, _bytes, stacked, programs = moved
    assert 1 <= programs <= 3
    assert (n_arrays, stacked) == (arrays * programs, 0)


def test_a_quantized_segment_keeps_one_input_a_segment(monkeypatch):
    monkeypatch.setattr(codec, "QUANTIZED_MODE", "on")
    s = _searcher(3, "quant")
    body = {"query": {"match": {"body": "w1 w2"}}, "size": 5}
    resp, moved = _moved(lambda: s.search(dict(body)))
    assert moved == (3, 3 * 9 * 4, 0, 3)
    with monkeypatch.context() as m:
        _one_a_segment(m)
        want = _searcher(3, "quant_apart").search(dict(body))
    assert _answer(resp) == _answer(want)


@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs 4 virtual devices")
def test_a_mesh_index_keeps_one_sharded_copy_a_segment_index():
    from opensearch_tpu.parallel.dist_search import MeshSearcher

    shards = []
    for si in range(4):
        s = _searcher(2, f"mesh{si}_")
        s.shard_id = si
        shards.append(s)
    ms = MeshSearcher(shards)
    body = {"query": {"match": {"body": "w1 w4"}}, "size": 5}
    ms.search(dict(body))                   # stages and compiles
    m0 = device_ledger().stats()["mesh_programs"]
    resp, moved = _moved(lambda: ms.search(dict(body)))
    assert resp["hits"]["hits"]
    # a program a segment index and the merge, one copy a segment index,
    # one read
    assert device_ledger().stats()["mesh_programs"] - m0 == 2 + 1
    assert (moved[0], moved[2], moved[3]) == (2, 0, 1)
