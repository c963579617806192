"""Device-resident memory & transfer observability (PR 11).

Covers the residency ledger (``common/device_ledger.py``): accounting
parity with the actually staged arrays, LRU-dispatch budget eviction
with byte-identical host-fallback results, the `_nodes/stats` ``device``
section / `_cat/segments` footprint columns / `/_metrics` gauges, the
version-tolerant compile registry, the insights transfer attribution,
the client additions, and the ``tools/check_device_staging.py`` tier-1
lint.
"""

import gc
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from opensearch_tpu.common.device_ledger import (GroupCloser,
                                                 KernelCompileRegistry,
                                                 device_ledger,
                                                 host_footprint,
                                                 kernel_registry)
from opensearch_tpu.mapping.mapper import DocumentMapper
from opensearch_tpu.index.segment import SegmentWriter
from opensearch_tpu.node import Node
from opensearch_tpu.search.executor import ShardSearcher

TOOLS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools")


@pytest.fixture(autouse=True)
def _clean_ledger():
    """The ledger is process-global (like breakers/metrics): reset it
    around every test."""
    led = device_ledger()
    led.reset()
    yield
    led.reset()


MAPPING = {"properties": {"t": {"type": "text"},
                          "k": {"type": "keyword"},
                          "n": {"type": "long"}}}


def _mapper():
    return DocumentMapper(MAPPING)


def _segment(mapper, docs, seg_id, base=0):
    parsed = [mapper.parse(str(base + i),
                           {"t": t, "k": f"g{i % 2}", "n": base + i})
              for i, t in enumerate(docs)]
    return SegmentWriter().build(parsed, seg_id)


def _searcher(n_segs=2):
    mapper = _mapper()
    texts = [["alpha beta", "beta gamma", "alpha alpha gamma"],
             ["beta beta delta", "alpha gamma", "gamma delta"],
             ["alpha delta", "beta", "alpha beta gamma delta"]]
    segs = [_segment(mapper, texts[i % len(texts)], f"s{i}", base=i * 3)
            for i in range(n_segs)]
    return ShardSearcher(segs, mapper, index_name="ledgerix")


# -- accounting parity ------------------------------------------------------

def _staged_nbytes(dseg):
    """Walk the ACTUAL staged arrays of one DeviceSegment."""
    total = 0
    for fam in (dseg.postings, dseg.numeric, dseg.ordinal, dseg.vector,
                dseg.geo):
        for arrs in fam.values():
            total += sum(int(v.nbytes) for k, v in arrs.items()
                         if k != "n_ords")
    for _live_np, staged in dseg._live_cache.values():
        total += int(staged.nbytes)
    return total


def test_ledger_matches_staged_nbytes_exactly():
    s = _searcher(n_segs=2)
    led = device_ledger()
    for seg in s.segments:
        dseg = seg.device()
        assert led.device_footprint(seg) == _staged_nbytes(dseg)
    assert led.resident_bytes() == sum(
        _staged_nbytes(seg.device()) for seg in s.segments)


def test_ledger_tracks_lazy_impacts_and_live_snapshots():
    s = _searcher(n_segs=1)
    seg = s.segments[0]
    dseg = seg.device()
    led = device_ledger()
    before = led.device_footprint(seg)
    imp = dseg.impacts("t", 2.0)
    assert led.device_footprint(seg) == before + int(imp.nbytes)
    # a deletes-invalidated live bitmap stages a NEW snapshot entry
    seg.apply_deletes([0])
    live2 = dseg.live_jnp(seg.live)
    assert led.device_footprint(seg) == (
        before + int(imp.nbytes) + int(live2.nbytes))
    assert led.device_footprint(seg) == _staged_nbytes(dseg) + int(
        imp.nbytes)


def test_refresh_away_releases_ledger_groups():
    s = _searcher(n_segs=2)
    for seg in s.segments:
        seg.device()
    led = device_ledger()
    assert led.resident_bytes() > 0
    assert led.stats()["resident_segments"] == 2
    for seg in s.segments:
        seg._device = None
    del s
    gc.collect()
    assert led.stats()["resident_segments"] == 0
    assert led.resident_bytes() == 0


def test_host_footprint_is_the_single_size_source():
    s = _searcher(n_segs=1)
    seg = s.segments[0]
    total = host_footprint(seg)
    per = host_footprint(seg, per_field=True)
    assert total == sum(per.values()) > 0
    # every host array family is covered (postings + the doc values)
    assert ("postings", "t") in per and ("ordinal", "k") in per \
        and ("numeric", "n") in per
    # the DeviceSegment breaker estimate derives from the same number
    assert seg.device()._breaker_bytes == total * 2


# -- budget eviction --------------------------------------------------------

def test_budget_eviction_is_byte_identical_via_host_fallback():
    s = _searcher(n_segs=2)
    led = device_ledger()
    body = {"query": {"match": {"t": "alpha beta"}}, "size": 5}
    r1 = s.search(body)
    assert led.resident_bytes() > 0
    led.set_budget(1)                       # far below the footprint
    st = led.stats()["budget"]
    assert st["evictions"] == 2 and st["evicted_bytes"] > 0
    assert all(seg._device is None and seg._device_evicted
               for seg in s.segments)
    r2 = s.search(body)                     # host impact-table fallback
    assert json.dumps(r1["hits"], sort_keys=True) == \
        json.dumps(r2["hits"], sort_keys=True)
    assert led.stats()["budget"]["host_fallbacks"] == 2
    # the fallback did NOT restage anything
    assert led.stats()["budget"]["restages"] == 0


def test_budget_eviction_releases_breaker_charge():
    from opensearch_tpu.common.breakers import breaker_service
    s = _searcher(n_segs=1)
    breaker = breaker_service().fielddata
    used0 = breaker.used
    dseg = s.segments[0].device()
    charged = dseg._breaker_bytes
    assert charged > 0 and breaker.used >= used0 + charged
    used_staged = breaker.used
    device_ledger().set_budget(1)
    # eviction released the staging charge exactly once (the GC
    # finalizer on the dead DeviceSegment must not double-release)
    assert breaker.used == used_staged - charged
    del dseg
    gc.collect()
    assert breaker.used == used_staged - charged


def test_eviction_order_is_least_recently_dispatched():
    s = _searcher(n_segs=2)
    led = device_ledger()
    for seg in s.segments:
        seg.device()
    g0 = s.segments[0].device()._ledger_group
    g1 = s.segments[1].device()._ledger_group
    led.record_dispatch(g0)
    led.record_dispatch(g1)
    led.record_dispatch(g0)                 # seg0 dispatched most recently
    budget = led.resident_bytes() - 1       # must evict exactly one
    led.set_budget(budget)
    assert s.segments[1]._device is None    # LRU-dispatch victim
    assert s.segments[0]._device is not None


def test_restage_counted_when_no_host_fallback_exists():
    s = _searcher(n_segs=1)
    led = device_ledger()
    body = {"query": {"match": {"t": "alpha"}}, "size": 2,
            "aggs": {"m": {"max": {"field": "n"}}}}
    r1 = s.search(body)
    led.set_budget(1)                       # evict; aggs path must restage
    r2 = s.search(body)
    assert json.dumps(r1["aggregations"]) == json.dumps(
        r2["aggregations"])
    assert json.dumps(r1["hits"], sort_keys=True) == \
        json.dumps(r2["hits"], sort_keys=True)
    assert led.stats()["budget"]["restages"] >= 1


def test_msearch_batched_path_survives_budget():
    s = _searcher(n_segs=2)
    bodies = [{"query": {"match": {"t": "alpha"}}, "size": 3},
              {"query": {"match": {"t": "beta"}}, "size": 3}]
    r1 = s.msearch(bodies)
    device_ledger().set_budget(1)
    r2 = s.msearch(bodies)
    assert json.dumps([r["hits"] for r in r1], sort_keys=True) == \
        json.dumps([r["hits"] for r in r2], sort_keys=True)


def test_transfer_counters_split_stage_and_fetch():
    s = _searcher(n_segs=1)
    led = device_ledger()
    s.search({"query": {"match": {"t": "alpha"}}, "size": 3})
    t = led.stats()["transfers"]
    assert t["stage"]["bytes"] > 0 and t["stage"]["ops"] > 0
    assert t["fetch"]["bytes"] > 0 and t["fetch"]["ops"] > 0
    snap = led.transfer_snapshot()
    assert snap == (t["stage"]["bytes"], t["fetch"]["bytes"])


def test_stage_input_counts_a_transfer_and_keeps_nothing_resident():
    """A per-query input is a transfer, not residency: ``arrays`` and
    ``bytes`` move under ``transfers.input``; no group, no entry, no
    resident byte, and the staging side's counters stay where they were."""
    s = _searcher(n_segs=1)
    led = device_ledger()
    s.search({"query": {"match": {"t": "alpha"}}, "size": 3})
    before, rows = led.stats(), led.segments()
    assert "time_ms" not in before["transfers"]["input"]
    out = led.stage_input(np.arange(9, dtype=np.int32))
    assert out.dtype == np.int32 and out.shape == (9,)
    np.testing.assert_array_equal(np.asarray(out), np.arange(9))
    led.stage_input(np.float32(2.5))
    after = led.stats()
    assert after["transfers"]["input"] == {
        "bytes": before["transfers"]["input"]["bytes"] + 36 + 4,
        "arrays": before["transfers"]["input"]["arrays"] + 2}
    for key in ("resident_bytes", "resident_segments", "dispatches",
                "indices", "budget"):
        assert after[key] == before[key], key
    assert after["transfers"]["stage"] == before["transfers"]["stage"]
    assert after["transfers"]["fetch"] == before["transfers"]["fetch"]
    assert led.segments() == rows
    assert led.transfer_snapshot() == (
        before["transfers"]["stage"]["bytes"],
        before["transfers"]["fetch"]["bytes"])
    led.reset()
    assert led.stats()["transfers"]["input"] == {"bytes": 0, "arrays": 0}


def test_a_search_counts_its_per_query_inputs():
    """One packed array a term-bag segment (PR 32), none when the same
    query comes again: the prepared-bindings cache owns what was staged."""
    s = _searcher(n_segs=2)
    led = device_ledger()
    body = {"query": {"match": {"t": "alpha beta"}}, "size": 3}
    s.search(dict(body))
    first = led.stats()["transfers"]["input"]
    assert first == {"bytes": 2 * 9 * 4, "arrays": 2}
    s.search(dict(body))
    assert led.stats()["transfers"]["input"] == first


def test_record_dispatch_counts_block_topk_programs():
    """``block_topk`` is what ``ops/topk.py::block_size`` said of the
    program's ``(n_pad, k)``: a block size counts one, 0 and the default
    none; the group's dispatches move as before; ``reset`` zeroes it."""
    from opensearch_tpu.ops.topk import block_size

    led = device_ledger()
    s = _searcher(n_segs=1)
    s.search({"query": {"match": {"t": "alpha"}}, "size": 3})
    g = s.segments[0].device()._ledger_group
    before = led.stats()
    assert before["block_topk_programs"] == 0     # n_pad 8: ``lax.top_k``
    assert before["dispatches"] == 1
    led.record_dispatch(g, block_topk=block_size(1048576, 10))
    led.record_dispatch(g, block_topk=block_size(131072, 10000))
    led.record_dispatch(g, slice_gather=True)
    led.record_dispatch(None, block_topk=True)
    after = led.stats()
    assert after["block_topk_programs"] == 2
    assert after["slice_gather_programs"] == before[
        "slice_gather_programs"] + 1
    assert after["dispatches"] == 4
    led.reset()
    assert led.stats()["block_topk_programs"] == 0


@pytest.mark.parametrize("size", [3, 10000])
def test_a_narrow_segment_search_takes_no_block_topk(size):
    """A tier-1 segment is narrower than the two stages pay for: every
    program is ``lax.top_k`` and the counter says so, whatever ``size``."""
    s = _searcher(n_segs=2)
    led = device_ledger()
    resp = s.search({"query": {"match": {"t": "alpha beta"}}, "size": size})
    assert resp["hits"]["total"]["value"] == 5
    stats = led.stats()
    assert stats["dispatches"] == 2
    assert stats["block_topk_programs"] == 0


@pytest.mark.parametrize("query,deleted,sorted_programs", [
    ({"match": {"t": "alpha beta"}}, False, 2),
    ({"match": {"t": "alpha beta"}}, True, 1),
    ({"bool": {"must": [{"match": {"t": "alpha beta"}}]}}, False, 0),
    ({"bool": {"must": [{"match": {"t": "alpha"}}],
               "filter": [{"term": {"k": "g0"}}]}}, False, 0),
    ({"match_all": {}}, False, 0)],
    ids=["bag", "bag_one_segment_with_a_delete", "bool_of_one_bag",
         "bag_under_a_bool", "match_all"])
def test_sorted_bag_programs_counts_root_bags_of_live_segments(
        query, deleted, sorted_programs):
    """``record_dispatch(sorted_bag=)`` moves for a scored term bag at the
    root of a plan over a segment without a deleted doc, and for
    nothing else (a ``bool`` of one ``must`` is still a ``bool``);
    ``reset`` zeroes it."""
    s = _searcher(n_segs=2)
    if deleted:
        s.segments[0].delete_local(0)
        s = ShardSearcher(s.segments, _mapper())
    led = device_ledger()
    resp = s.search({"query": query, "size": 3})
    assert resp["hits"]["hits"]
    stats = led.stats()
    assert stats["dispatches"] == 2
    assert stats["sorted_bag_programs"] == sorted_programs
    led.record_dispatch(None, sorted_bag=True)
    led.record_dispatch(None, block_topk=True, slice_gather=True)
    assert led.stats()["sorted_bag_programs"] == sorted_programs + 1
    led.reset()
    assert led.stats()["sorted_bag_programs"] == 0


# -- compile registry -------------------------------------------------------

def test_compile_registry_counts_query_kernels():
    s = _searcher(n_segs=1)
    s.search({"query": {"match": {"t": "alpha"}}, "size": 3})
    counts = kernel_registry().counts()
    assert counts["kernels"].get("plan.run_topk", 0) >= 1
    assert counts["total"] >= 1
    assert counts["unavailable"] == 0


def test_compile_registry_lists_the_knn_kernels():
    """Both configurations' kernels: ops/knn.py's jit entries beside the
    plan's."""
    from opensearch_tpu.ops import knn

    vectors = np.arange(32, dtype=np.float32).reshape(8, 4)
    before = kernel_registry().counts()["kernels"]
    assert {"knn.knn_scores", "knn.knn_topk",
            "knn.knn_topk_batch"} <= set(before)
    knn.knn_topk(vectors, np.ones(8, bool), vectors[3], space="l2", k=5)
    after = kernel_registry().counts()
    assert after["kernels"]["knn.knn_topk"] == before["knn.knn_topk"] + 1
    assert after["unavailable"] == 0


def test_compile_registry_unavailable_fallback():
    reg = KernelCompileRegistry()
    reg._defaults_loaded = True             # isolate from the real kernels

    def plain_fn():
        pass

    class Broken:
        def _cache_size(self):
            raise RuntimeError("moved in this jax")

    reg.register("no_introspection", plain_fn)
    reg.register("raises", Broken())

    def good():
        pass
    good._cache_size = lambda: 3
    reg.register("good", good)
    counts = reg.counts()
    assert counts["unavailable"] == 2       # counted, never raising
    assert counts["kernels"] == {"good": 3}
    assert counts["total"] == 3


def test_profiler_xla_compiles_survives_missing_introspection(
        monkeypatch):
    from opensearch_tpu.search import profile as profile_mod
    broken = KernelCompileRegistry()
    broken._defaults_loaded = True          # zero kernels registered
    monkeypatch.setattr(
        "opensearch_tpu.common.device_ledger._registry", broken)
    assert profile_mod.xla_program_count() == 0
    prof = profile_mod.QueryProfiler()
    section = prof.shard_section("ix", 0, plan_type="T",
                                 description="d", total_segments=0)
    assert section["engine"]["xla_compiles"] == 0


# -- insights attribution ---------------------------------------------------

def test_insights_rollups_carry_transfer_bytes():
    from opensearch_tpu.search import insights as insights_mod
    from opensearch_tpu.search.insights import QueryInsightsService
    s = _searcher(n_segs=1)
    svc = QueryInsightsService(node_id="t")
    body = {"query": {"match": {"t": "alpha"}}, "size": 3}
    with insights_mod.collecting() as sink:
        s.search(body)
    for rec in sink:
        assert rec.get("transfer_bytes", 0) > 0   # first run stages
        svc.record(rec)
    sig = insights_mod.signature_hash(
        insights_mod.canonical_query(body["query"]), True)
    roll = svc.section()["signatures"][sig]
    assert roll["device_transfer_bytes"] > 0


# -- REST surfaces ----------------------------------------------------------

@pytest.fixture()
def node(tmp_path):
    n = Node(str(tmp_path / "node"), port=0)
    yield n
    n.stop()


def call(node, method, path, body=None, params=None, ndjson=None):
    if ndjson is not None:
        raw = ("\n".join(json.dumps(l) for l in ndjson) + "\n").encode()
        ctype = "application/x-ndjson"
    else:
        raw = json.dumps(body).encode() if body is not None else None
        ctype = "application/json"
    return node.rest.dispatch(method, path, params or {}, raw, ctype,
                              headers={})


def _seed(node, index="devix", docs=12):
    s, r = call(node, "PUT", f"/{index}", {"mappings": MAPPING})
    assert s == 200, r
    lines = []
    for i in range(docs):
        lines.append({"index": {"_index": index, "_id": str(i)}})
        lines.append({"t": f"alpha w{i % 3}", "k": f"g{i % 2}", "n": i})
    s, r = call(node, "POST", "/_bulk", params={"refresh": "true"},
                ndjson=lines)
    assert s == 200 and not r["errors"], r


def test_nodes_stats_device_section_and_budget_setting(node):
    _seed(node)
    body = {"query": {"match": {"t": "alpha"}}, "size": 5}
    s, r1 = call(node, "POST", "/devix/_search", body)
    assert s == 200
    s, stats = call(node, "GET", "/_nodes/stats")
    dev = stats["nodes"][node.node_id]["device"]
    assert dev["resident_bytes"] > 0
    assert dev["resident_segments"] >= 1
    assert dev["indices"]["devix"]["bytes"] > 0
    assert dev["indices"]["devix"]["dispatches"] >= 1
    assert dev["transfers"]["stage"]["bytes"] > 0
    assert dev["transfers"]["fetch"]["bytes"] > 0
    assert dev["compile_registry"]["total"] >= 1
    assert dev["slice_gather_programs"] >= 1
    assert dev["block_topk_programs"] == 0      # a segment of 40 rows
    # a scored bag at the root over segments with no deleted doc
    assert dev["sorted_bag_programs"] == dev["dispatches"] >= 1
    # what jax actually runs on, so a node that came up on the wrong
    # backend says so from the client's side
    assert dev["backend"]["platform"] == "cpu"
    assert dev["backend"]["device_kind"] == "cpu"
    assert dev["backend"]["device_count"] == 8
    # dynamic budget below the footprint -> counted eviction, and the
    # SAME query answers byte-identically off the host tables
    s, _ = call(node, "PUT", "/_cluster/settings", {
        "transient": {"device.memory.budget_bytes": 1}})
    assert s == 200
    s, r2 = call(node, "POST", "/devix/_search", body)
    assert s == 200
    assert json.dumps(r1["hits"], sort_keys=True) == \
        json.dumps(r2["hits"], sort_keys=True)
    s, stats = call(node, "GET", "/_nodes/stats")
    dev = stats["nodes"][node.node_id]["device"]
    assert dev["budget"]["budget_bytes"] == 1
    assert dev["budget"]["evictions"] >= 1
    assert dev["budget"]["host_fallbacks"] >= 1
    s, _ = call(node, "PUT", "/_cluster/settings", {
        "transient": {"device.memory.budget_bytes": None}})
    assert s == 200
    assert device_ledger().budget_bytes is None


def test_cat_segments_footprint_columns(node):
    _seed(node)
    s, _ = call(node, "POST", "/devix/_search",
                {"query": {"match": {"t": "alpha"}}, "size": 3})
    assert s == 200
    s, rows = call(node, "GET", "/_cat/segments",
                   params={"format": "json"})
    assert s == 200 and rows
    row = next(r for r in rows if r["index"] == "devix")
    assert int(row["size"]) > 0              # host footprint
    assert int(row["size.device"]) > 0       # staged footprint
    # budget eviction empties the device column, host stays
    device_ledger().set_budget(1)
    s, rows = call(node, "GET", "/_cat/segments",
                   params={"format": "json"})
    row = next(r for r in rows if r["index"] == "devix")
    assert int(row["size"]) > 0 and int(row["size.device"]) == 0


def test_cat_fielddata_uses_host_footprint(node):
    _seed(node)
    s, rows = call(node, "GET", "/_cat/fielddata",
                   params={"format": "json"})
    assert s == 200
    krow = next(r for r in rows if r["field"] == "k")
    seg = next(iter(
        node.indices.indices["devix"].local_shards.values())).segments[0]
    per = host_footprint(seg, per_field=True)
    assert int(krow["size"]) == per[("ordinal", "k")]


def test_metrics_exposition_has_device_series(node):
    _seed(node)
    s, _ = call(node, "POST", "/devix/_search",
                {"query": {"match": {"t": "alpha"}}, "size": 3})
    assert s == 200
    s, payload = call(node, "GET", "/_metrics")
    text = payload.text if hasattr(payload, "text") else str(payload)
    assert "opensearch_tpu_device_resident_bytes " in text
    assert "opensearch_tpu_device_budget_bytes 0" in text
    assert 'opensearch_tpu_device_index_resident_bytes{index="devix"}' \
        in text
    # ledger counters flow through the MetricsRegistry exposition
    assert "device_transfer_stage_bytes_total" in text
    assert "device_transfer_fetch_bytes_total" in text


# -- client -----------------------------------------------------------------

def test_client_cat_segments_and_device_stats(tmp_path):
    from opensearch_tpu.client import OpenSearch
    node = Node(str(tmp_path / "cnode"), port=0).start()
    try:
        client = OpenSearch(hosts=[{"host": "127.0.0.1",
                                    "port": node.port}])
        client.indices.create("cix", {"mappings": MAPPING})
        for i in range(6):
            client.index("cix", {"t": f"alpha w{i}", "n": i}, id=str(i))
        client.indices.refresh("cix")
        client.search(index="cix",
                      body={"query": {"match": {"t": "alpha"}}})
        rows = client.cat.segments()
        row = next(r for r in rows if r["index"] == "cix")
        assert int(row["size"]) > 0 and int(row["size.device"]) > 0
        dev = client.nodes.device()
        assert dev[node.node_id]["resident_bytes"] > 0
        assert dev[node.node_id]["transfers"]["stage"]["bytes"] > 0
    finally:
        node.stop()


# -- GroupCloser ------------------------------------------------------------

def test_group_closer_releases_entries_on_cache_drop():
    led = device_ledger()
    group = led.open_group(index="ix", shard=0, segment="batchy")
    led.stage(group, np.zeros(16, np.float32), kind="batch_group",
              name="x")
    led.seal(group)
    assert led.resident_bytes() == 64
    holder = {"_ledger": GroupCloser(led, group)}
    del group
    del holder
    gc.collect()
    assert led.resident_bytes() == 0


# -- tools/check_device_staging.py lint -------------------------------------

def test_check_device_staging_lint_passes():
    r = subprocess.run(
        [sys.executable,
         os.path.join(TOOLS, "check_device_staging.py")],
        capture_output=True, text=True)
    assert r.returncode == 0, r.stdout + r.stderr


def test_check_device_staging_lint_catches_violations(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text(
        "import jax\nimport jax.numpy as jnp\n"
        "x = jnp.asarray([1, 2, 3])\n"
        "y = jax.device_put(x)\n"
        "ok = jnp.asarray([1])  # staging-ok: test annotation\n"
        "# staging-ok: above-line annotation\n"
        "ok2 = jnp.asarray([2])\n")
    r = subprocess.run(
        [sys.executable,
         os.path.join(TOOLS, "check_device_staging.py"),
         str(tmp_path)],
        capture_output=True, text=True)
    assert r.returncode == 1
    assert "bad.py:3" in r.stdout and "bad.py:4" in r.stdout
    assert "bad.py:5" not in r.stdout and "bad.py:7" not in r.stdout
