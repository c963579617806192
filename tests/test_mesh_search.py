"""The mesh on the one lowering: a multi-shard index with ``search.mesh``
runs every segment index of its shards as one program over the chips
(``run_topk_mesh``), merges on the device (``merge_topk_mesh``) and reads
once.  On the CPU's eight virtual devices: the merge order across shards,
the profile, the counters and spans a request moves, where each shard is
staged, and the counted fallback."""

import numpy as np
import pytest

import jax

from opensearch_tpu.common.device_ledger import device_ledger
from opensearch_tpu.common.telemetry import metrics, tracer
from opensearch_tpu.index.segment import SegmentWriter
from opensearch_tpu.mapping.mapper import DocumentMapper
from opensearch_tpu.parallel.dist_search import MeshSearcher
from opensearch_tpu.search.executor import ShardSearcher, merge_hit_rows

pytestmark = pytest.mark.skipif(len(jax.devices()) < 4,
                                reason="needs 4 virtual devices")

MAPPING = {"properties": {"body": {"type": "text"}}}
VOCAB = "alpha bravo charlie delta echo foxtrot golf hotel".split()


def _shards(bodies_of, n_shards=4, n_segments=2):
    """``bodies_of(shard, segment)`` -> the bodies of one segment; ids are
    ``s<shard>g<segment>d<i>``."""
    mapper = DocumentMapper(MAPPING)
    writer = SegmentWriter()
    shards = []
    for si in range(n_shards):
        segs = []
        for g in range(n_segments):
            parsed = []
            for i, text in enumerate(bodies_of(si, g)):
                d = mapper.parse(f"s{si}g{g}d{i}", {"body": text})
                d.seq_no = i
                parsed.append(d)
            seg = writer.build(parsed, f"s{si}_g{g}")
            seg.index_name, seg.shard_id = "mesh_t", si
            segs.append(seg)
        shards.append(ShardSearcher(segs, mapper, index_name="mesh_t",
                                    shard_id=si))
    return shards


def _random_shards(seed=5, per=30, **kw):
    rng = np.random.default_rng(seed)
    return _shards(lambda si, g: [" ".join(rng.choice(VOCAB, size=int(
        rng.integers(3, 12)))) for _ in range(per)], **kw)


def _host_merge(shards, body):
    size = int(body.get("size", 10)) + int(body.get("from", 0))
    rows, total = [], 0
    for si, s in enumerate(shards):
        r = s.search(dict(body, size=size, **{"from": 0}))
        total += r["hits"]["total"]["value"]
        rows.extend((h, si, pos) for pos, h in enumerate(r["hits"]["hits"]))
    return [(h["_id"], h["_score"]) for h in merge_hit_rows(
        rows, None)[int(body.get("from", 0)):size]], total


def _rows(resp):
    return [(h["_id"], h["_score"]) for h in resp["hits"]["hits"]]


def _moved(before, after, *path):
    for key in path:
        before, after = before.get(key, {}), after.get(key, {})
    return (after or 0) - (before or 0)


def test_a_tie_across_shards_is_broken_by_shard_then_segment_then_doc():
    """Every shard and segment holds the same passages, so every shard's
    statistics and scores are equal: the merged list takes them shard by
    shard, each shard's segment by segment, each doc by doc."""
    texts = ["alpha bravo", "alpha charlie delta", "alpha bravo",
             "echo foxtrot"]
    shards = _shards(lambda si, g: texts)
    resp = MeshSearcher(shards).search(
        {"query": {"match": {"body": "alpha bravo"}}, "size": 12})
    got = _rows(resp)
    assert got == _host_merge(shards, {"query": {"match": {
        "body": "alpha bravo"}}, "size": 12})[0]
    top = [i for i, s in got if s == got[0][1]]
    assert top == [f"s{si}g{g}d{d}" for si in range(4) for g in range(2)
                   for d in (0, 2)][:len(top)]
    assert len(top) == 12 and resp["hits"]["total"]["value"] == 24


@pytest.mark.parametrize("body", [
    {"query": {"match": {"body": "alpha echo"}}, "size": 10},
    {"query": {"match": {"body": "golf"}}, "size": 7, "from": 3},
    {"query": {"match": {"body": "hotel bravo delta"}}, "size": 10,
     "min_score": 0.8},
    {"query": {"match": {"body": "zzz"}}, "size": 10}])
def test_the_mesh_answers_as_the_shards_merged_on_the_host(body):
    shards = _random_shards()
    resp = MeshSearcher(shards).search(dict(body))
    want, total = _host_merge(shards, body)
    assert _rows(resp) == want
    assert resp["hits"]["total"]["value"] == total


def test_a_request_is_one_program_a_segment_index_one_merge_one_read():
    shards = _random_shards(n_segments=3)
    ms = MeshSearcher(shards)
    body = {"query": {"match": {"body": "alpha delta"}}, "size": 10}
    ms.search(dict(body))                   # stages and compiles
    led = device_ledger()
    s0, c0 = led.stats(), metrics().stats()["counters"]
    ms.search(dict(body))
    s1, c1 = led.stats(), metrics().stats()["counters"]
    assert s1["mesh_programs"] - s0["mesh_programs"] == 3 + 1
    assert s1["dispatches"] - s0["dispatches"] == 3 + 1
    assert _moved(s0, s1, "transfers", "fetch", "ops") == 1
    assert _moved(s0, s1, "transfers", "fetch", "arrays") == 1
    # one sharded copy of the four shards' inputs a segment index
    assert _moved(s0, s1, "transfers", "input", "arrays") == 3
    assert c1.get("search.mesh.fallback", 0) == c0.get(
        "search.mesh.fallback", 0)
    assert c1["search.mesh.requests"] - c0["search.mesh.requests"] == 1
    assert c1["search.mesh.shards"] - c0["search.mesh.shards"] == 4


def test_the_spans_of_a_mesh_request():
    shards = _random_shards()
    ms = MeshSearcher(shards)
    body = {"query": {"match": {"body": "alpha"}}, "size": 5}
    ms.search(dict(body))
    totals0 = tracer().totals()
    ms.search(dict(body))
    totals1 = tracer().totals()

    def count(name):
        return (totals1.get(name, {}).get("count", 0)
                - totals0.get(name, {}).get("count", 0))
    assert count("mesh.search") == 1
    assert count("mesh.prepare") == 2
    assert count("mesh.launch") == 2 + 1
    assert count("device.sync") == 1
    assert count("segment.prepare") == 4 * 2
    assert count("query.plan") == 4
    sync = [s for s in tracer().recent(64) if s["name"] == "device.sync"]
    assert sync[0]["attributes"]["site"] == "mesh"


def test_each_shard_is_staged_on_its_own_chip_only():
    device_ledger().reset()
    shards = _random_shards()
    ms = MeshSearcher(shards)
    ms.search({"query": {"match": {"body": "alpha"}}, "size": 5})
    rows = [r for r in device_ledger().segments() if r["index"] == "mesh_t"]
    assert len(rows) == 4 * 2
    for r in rows:
        assert r["device"] == ms.devices[r["shard"]].id, r
    by_device = device_ledger().stats()["devices"]
    for si in range(4):
        held = sum(r["bytes"] for r in rows if r["shard"] == si)
        assert by_device[str(ms.devices[si].id)] >= held > 0
    # nothing of the index went through Segment.device()
    assert all(seg._device is None for s in shards for seg in s.segments)


def test_the_mesh_profiles_every_shard():
    shards = _random_shards()
    resp = MeshSearcher(shards).search(
        {"query": {"match": {"body": "alpha"}}, "size": 5, "profile": True})
    sections = resp["profile"]["shards"]
    assert [s["id"] for s in sections] == [f"[mesh_t][{si}]"
                                          for si in range(4)]
    assert {s["engine"]["execution_path"] for s in sections} == {"device"}


def test_shards_that_cannot_share_a_program_are_served_and_counted():
    rng = np.random.default_rng(3)
    shards = _random_shards()
    # one shard with one segment more: no segment index of every shard
    odd = _shards(lambda si, g: [" ".join(rng.choice(VOCAB, size=5))
                                 for _ in range(20)], n_shards=1,
                  n_segments=3)[0]
    shards[2] = ShardSearcher(odd.segments, odd.mapper, index_name="mesh_t",
                              shard_id=2)
    body = {"query": {"match": {"body": "alpha golf"}}, "size": 10,
            "profile": True}
    before = metrics().counter("search.mesh.fallback").value
    resp = MeshSearcher(shards).search(dict(body))
    assert metrics().counter("search.mesh.fallback").value == before + 1
    assert _rows(resp) == _host_merge(shards, body)[0]
    assert len(resp["profile"]["shards"]) == 4


def test_a_profiled_request_takes_the_mesh_through_the_service(tmp_path):
    from opensearch_tpu.indices.service import IndicesService

    svc = IndicesService(str(tmp_path))
    svc.create("mx", {"settings": {"number_of_shards": 4,
                                   "search.mesh": True},
                      "mappings": MAPPING})
    ix = svc.get("mx")
    try:
        rng = np.random.default_rng(11)
        for i in range(80):
            ix.index_doc(str(i), {"body": " ".join(rng.choice(VOCAB,
                                                              size=6))})
        ix.refresh()
        body = {"query": {"match": {"body": "alpha hotel"}}, "size": 5,
                "profile": True}
        assert ix._use_mesh(body)
        fb0 = metrics().counter("search.mesh.fallback").value
        m0 = device_ledger().stats()["mesh_programs"]
        resp = ix.search(dict(body))
        assert metrics().counter("search.mesh.fallback").value == fb0
        assert device_ledger().stats()["mesh_programs"] > m0
        paths = [s["engine"]["execution_path"]
                 for s in resp["profile"]["shards"]]
        assert paths == ["device"] * 4
        plain = ix.search({k: v for k, v in body.items() if k != "profile"})
        assert _rows(plain) == _rows(resp)
    finally:
        svc.close()


def test_concurrent_first_requests_stage_each_segment_once(monkeypatch):
    """Sixteen threads' first requests at once, the interpreter switching
    every 10 us: each (shard, segment) is staged once, and every answer
    is the one a single request gets."""
    import sys
    import threading

    from opensearch_tpu.index import segment as segment_mod

    shards = _random_shards(seed=9)
    want = _rows(MeshSearcher(shards).search(
        {"query": {"match": {"body": "bravo echo"}}, "size": 10}))
    staged = []
    real = segment_mod.DeviceSegment.__init__

    def counting(self, seg, pads=None):
        staged.append(seg.seg_id)
        real(self, seg, pads)

    monkeypatch.setattr(segment_mod.DeviceSegment, "__init__", counting)
    ms = MeshSearcher(shards)
    got, errors = [], []

    def ask():
        try:
            got.append(_rows(ms.search(
                {"query": {"match": {"body": "bravo echo"}}, "size": 10})))
        except Exception as exc:  # noqa: BLE001  reported below
            errors.append(exc)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=ask) for _ in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert not errors and got == [want] * 16
    assert sorted(staged) == sorted(seg.seg_id for s in shards
                                    for seg in s.segments)
