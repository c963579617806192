"""Real query profiling (PR 9): phase-attributed Profile API, Prometheus
metrics exposition, and the SLO-breach flight recorder.

Pinned invariants:

- profiled and unprofiled responses have byte-identical ``hits`` across
  the sequential host fast path, the XLA device path, and the
  msearch-batched path (profiling is observation, never execution);
- the per-phase breakdown keeps the OpenSearch response shape
  (``shards[].searches[].query[].breakdown``), ``rewrite_time`` is real,
  and query/collector sections are no longer double-stamped with the
  same number;
- segments scanned + pruned (+ not reached) always sums to the
  searcher's segment count, and cluster-mode shard sections sum to the
  same corpus-wide totals as a single-node profile;
- ``profile:true`` responses are never served from or stored into the
  request cache (the indices/service.py admission guard, end-to-end);
- ``GET /_metrics`` parses as Prometheus text format and reports the
  SAME bucket data ``Histogram.stats()`` now exposes as JSON;
- a slow-log trip or a soak SLO breach lands a non-empty capture in the
  flight recorder ring (``GET /_nodes/flight_recorder``).
"""

import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

from opensearch_tpu.common.telemetry import (
    Histogram,
    MetricsRegistry,
    flight_recorder,
    metrics,
    tracer,
)
from opensearch_tpu.index.segment import SegmentWriter
from opensearch_tpu.mapping.mapper import DocumentMapper
from opensearch_tpu.search.executor import ShardSearcher

TOOLS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools")

PHASES = ("rewrite", "plan_cache", "compile", "prepare", "can_match",
          "dispatch", "reduce", "fetch")


@pytest.fixture(autouse=True)
def _clean_telemetry():
    from opensearch_tpu.indices import service as indices_mod
    tracer().reset()
    flight_recorder().reset()
    yield
    tracer().reset()
    flight_recorder().reset()
    indices_mod.SLOWLOG_DEFAULTS.clear()


def build_searcher(n_docs=60, seg_sizes=(20, 20, 20), vocab=40, seed=3):
    mapper = DocumentMapper({"properties": {"body": {"type": "text"}}})
    writer = SegmentWriter()
    rng = np.random.default_rng(seed)
    docs = [{"body": " ".join(
        f"w{int(t)}" for t in (rng.zipf(1.4, size=12) - 1).clip(0, vocab))}
        for _ in range(n_docs)]
    segs, i = [], 0
    for si, size in enumerate(seg_sizes):
        batch = [mapper.parse(str(i + j), d)
                 for j, d in enumerate(docs[i: i + size])]
        segs.append(writer.build(batch, f"p{si}"))
        i += size
    return ShardSearcher(segs, mapper, index_name="profix")


Q = {"query": {"match": {"body": "w1 w2"}}, "size": 5}


def hits_bytes(resp) -> bytes:
    return json.dumps(resp["hits"], sort_keys=True).encode()


# -- profile response shape -------------------------------------------------

def test_breakdown_shape_and_consistency():
    s = build_searcher()
    resp = s.search(dict(Q, profile=True))
    shards = resp["profile"]["shards"]
    assert len(shards) == 1
    sec = shards[0]
    assert sec["id"] == "[profix][0]"
    search = sec["searches"][0]
    query = search["query"][0]
    bd = query["breakdown"]
    # the OpenSearch client-parseable shape, with our phase keys
    for p in PHASES:
        assert p in bd and f"{p}_count" in bd, p
        assert bd[p] >= 0
    # the stub's lies are gone: rewrite_time is the measured parse time
    # (0 only on a plan-cache hit), and query/collector sections carry
    # DIFFERENT numbers (phases, not one double-stamped elapsed)
    assert search["rewrite_time"] == bd["rewrite"]
    assert query["time_in_nanos"] == sum(
        bd[p] for p in ("rewrite", "plan_cache", "compile", "prepare",
                        "can_match", "dispatch"))
    assert search["collector"][0]["time_in_nanos"] == bd["reduce"]
    assert query["time_in_nanos"] != search["collector"][0][
        "time_in_nanos"] or bd["reduce"] == 0
    # phases sum consistently with took (took is ms-truncated, so the
    # phase sum must not exceed took+1ms; monotonic clock ⇒ no negatives)
    phase_sum_ns = sum(bd[p] for p in PHASES)
    assert phase_sum_ns <= (resp["took"] + 1) * 1_000_000
    # segments pruned vs scanned sums to the segment count
    segsum = sec["engine"]["segments"]
    assert segsum["total"] == 3
    assert (segsum["scanned"] + segsum["pruned_can_match"]
            + segsum["pruned_min_score"] + segsum["pruned_kth"]
            + segsum["not_reached"]) == segsum["total"]
    assert len(sec["segments"]) == segsum["scanned"] + sum(
        segsum[k] for k in ("pruned_can_match", "pruned_min_score",
                            "pruned_kth"))


def test_cache_attribution_hit_on_repeat():
    s = build_searcher()
    first = s.search(dict(Q, profile=True))
    second = s.search(dict(Q, profile=True))
    e1 = first["profile"]["shards"][0]["engine"]
    e2 = second["profile"]["shards"][0]["engine"]
    assert e1["plan_cache"] == "miss"
    assert e2["plan_cache"] == "hit"
    # a plan-cache hit does zero parse/compile work
    bd2 = second["profile"]["shards"][0]["searches"][0]["query"][0][
        "breakdown"]
    assert bd2["rewrite"] == 0 and bd2["compile"] == 0
    assert e1["request_cache"] == "bypass"
    assert e1["execution_path"] in ("host", "device")


def test_min_score_pruning_attribution():
    s = build_searcher()
    # a min_score far above any reachable BM25 score prunes via the
    # block-max bound; totals stay exact (pruned docs can't match)
    resp = s.search({"query": {"match": {"body": "w1"}},
                     "min_score": 1e6, "profile": True, "size": 5})
    segsum = resp["profile"]["shards"][0]["engine"]["segments"]
    assert segsum["pruned_min_score"] + segsum["pruned_can_match"] > 0
    assert resp["hits"]["total"]["value"] == 0


# -- byte-identical hits ----------------------------------------------------

@pytest.mark.parametrize("host_scoring", [True, False])
def test_hits_byte_identical_sequential(host_scoring, request):
    s = build_searcher()
    if host_scoring:
        request.getfixturevalue("host_recovery")
    plain = s.search(dict(Q))
    profiled = s.search(dict(Q, profile=True))
    assert hits_bytes(plain) == hits_bytes(profiled)
    assert "profile" not in plain
    path = profiled["profile"]["shards"][0]["engine"]["execution_path"]
    assert path == ("host" if host_scoring else "device")


def test_hits_byte_identical_msearch_batched():
    s = build_searcher()
    # same (field, size) coalesce into one group; the odd size forms
    # its own group
    bodies = [dict(Q), {"query": {"match": {"body": "w3"}}, "size": 5},
              {"query": {"match": {"body": "w1"}}, "size": 4}]
    plain = s.msearch([dict(b) for b in bodies])
    profiled = s.msearch([dict(b, profile=True) for b in bodies])
    for p, pr in zip(plain, profiled):
        assert hits_bytes(p) == hits_bytes(pr)
        assert "profile" in pr and "profile" not in p
    # coalescing attribution: coalesced members report the SAME group
    groups = [r["profile"]["shards"][0]["engine"]["batch"]
              for r in profiled]
    assert groups[0] == groups[1]
    assert groups[0]["queries"] == 2
    assert sorted(groups[0]["positions"]) == [0, 1]
    assert groups[2]["queries"] == 1 and groups[2]["positions"] == [2]
    assert profiled[0]["profile"]["shards"][0]["engine"][
        "execution_path"] in ("host_batched", "device_batched")


def test_field_sorted_profile_consistent():
    s = build_searcher()
    body = {"query": {"match": {"body": "w1"}},
            "sort": [{"_doc": "asc"}], "size": 5}
    plain = s.search(dict(body))
    profiled = s.search(dict(body, profile=True))
    assert hits_bytes(plain) == hits_bytes(profiled)
    segsum = profiled["profile"]["shards"][0]["engine"]["segments"]
    assert segsum["scanned"] + segsum["not_reached"] + sum(
        segsum[k] for k in ("pruned_can_match", "pruned_min_score",
                            "pruned_kth")) == segsum["total"]


# -- request-cache guard (end-to-end) ---------------------------------------

def test_profile_never_request_cached(tmp_path):
    from opensearch_tpu.indices.request_cache import request_cache
    from opensearch_tpu.node import Node
    node = Node(str(tmp_path / "n"), port=0)
    try:
        node.rest.dispatch("PUT", "/rc", {}, json.dumps({
            "mappings": {"properties": {"body": {"type": "text"}}}
        }).encode())
        for i in range(8):
            node.rest.dispatch("PUT", f"/rc/_doc/{i}", {}, json.dumps(
                {"body": f"w{i % 3} common"}).encode())
        node.rest.dispatch("GET", "/rc/_refresh", {}, None)
        body = json.dumps({"query": {"match": {"body": "common"}},
                           "size": 0}).encode()
        # size=0 requests cache by default: miss then hit
        s0 = request_cache().stats()
        node.rest.dispatch("POST", "/rc/_search", {}, body)
        node.rest.dispatch("POST", "/rc/_search", {}, body)
        s1 = request_cache().stats()
        assert s1["miss_count"] - s0["miss_count"] == 1
        assert s1["hit_count"] - s0["hit_count"] == 1
        # the same query with profile:true NEVER touches the cache —
        # not served from it (the response must carry a fresh profile)
        # and not stored into it
        pbody = json.dumps({"query": {"match": {"body": "common"}},
                            "size": 0, "profile": True}).encode()
        st, resp = node.rest.dispatch("POST", "/rc/_search", {}, pbody)
        assert st == 200 and resp.get("profile"), \
            "profiled request served without a profile section"
        s2 = request_cache().stats()
        assert s2["hit_count"] == s1["hit_count"]
        assert s2["miss_count"] == s1["miss_count"]
        assert s2["entries"] == s1["entries"]
        # and the cached unprofiled entry is still served clean
        st, resp = node.rest.dispatch("POST", "/rc/_search", {}, body)
        assert st == 200 and "profile" not in resp
        s3 = request_cache().stats()
        assert s3["hit_count"] - s2["hit_count"] == 1
    finally:
        node.stop()


# -- cluster-mode merge -----------------------------------------------------

def _wait(pred, timeout=20.0):
    import time
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:   # deadline
        if pred():
            return
        import time as _t
        _t.sleep(0.02)                   # deadline
    raise AssertionError("timed out")


def test_cluster_profile_merge_matches_single_node(tmp_path):
    from opensearch_tpu.cluster.node import ClusterNode
    from opensearch_tpu.transport.service import (LocalTransport,
                                                  TransportService)
    hub = LocalTransport.Hub()
    ids = ["n0", "n1", "n2"]
    nodes = {}
    for nid in ids:
        svc = TransportService(nid, LocalTransport(hub))
        node = ClusterNode(nid, str(tmp_path / nid), svc, ids)
        node.search_backpressure.trackers["cpu_usage"].probe = \
            lambda: 0.0
        nodes[nid] = node
    try:
        assert nodes["n0"].start_election()
        _wait(lambda: all(nodes[i].coordinator.state().master_node
                          == "n0" for i in ids))
        nodes["n0"].create_index("cp", {
            "settings": {"number_of_shards": 2,
                         "number_of_replicas": 1},
            "mappings": {"properties": {"body": {"type": "text"}}}})

        def in_sync():
            routing = nodes["n0"].coordinator.state().routing.get(
                "cp", [])
            return routing and all(
                set(e["in_sync"]) == {e["primary"], *e["replicas"]}
                for e in routing)
        _wait(in_sync)
        docs = [{"body": f"w{i % 4} w{(i + 1) % 5} common"}
                for i in range(24)]
        for i, d in enumerate(docs):
            nodes["n0"].index_doc("cp", str(i), d)
        nodes["n0"].refresh("cp")

        body = {"query": {"match": {"body": "common w1"}}, "size": 10}
        plain = nodes["n1"].search("cp", dict(body))
        profiled = nodes["n1"].search("cp", dict(body, profile=True))
        # profiling never changes cluster results either
        assert hits_bytes(plain) == hits_bytes(profiled)
        prof = profiled["profile"]
        assert prof["coordinator"]["sources"] >= 1
        assert prof["coordinator"]["reduce_time_in_nanos"] >= 0
        assert prof["coordinator"]["scatter_time_in_nanos"] > 0
        sections = prof["shards"]
        assert sections, "cluster profile lost its shard sections"
        total_cluster_segments = 0
        for sec in sections:
            group = sec["shard_group"]
            # every section names the copy that served it + provenance
            assert group["node"] in ids
            assert "c3_rank" in group and "in_duress" in group
            assert group["failover_attempts"] >= 0
            assert all("rerouted" in p and "legacy_order" in p
                       for p in group.get("selection", []))
            segsum = sec["engine"]["segments"]
            reached = sum(segsum[k] for k in (
                "scanned", "pruned_can_match", "pruned_min_score",
                "pruned_kth", "not_reached"))
            assert reached == segsum["total"]
            total_cluster_segments += segsum["total"]

        # shard sections sum consistently with a single-node view of
        # the same corpus: same doc->shard routing, same refresh point
        # => the same total segment count, just partitioned over nodes
        from opensearch_tpu.node import Node
        solo = Node(str(tmp_path / "solo"), port=0)
        try:
            solo.rest.dispatch("PUT", "/cp", {}, json.dumps({
                "settings": {"number_of_shards": 2},
                "mappings": {"properties": {"body": {"type": "text"}}},
            }).encode())
            for i, d in enumerate(docs):
                solo.rest.dispatch("PUT", f"/cp/_doc/{i}", {},
                                   json.dumps(d).encode())
            solo.rest.dispatch("GET", "/cp/_refresh", {}, None)
            st, resp = solo.rest.dispatch(
                "POST", "/cp/_search", {},
                json.dumps(dict(body, profile=True)).encode())
            assert st == 200
            solo_sections = resp["profile"]["shards"]
            solo_total = sum(s["engine"]["segments"]["total"]
                             for s in solo_sections)
            assert total_cluster_segments == solo_total
            # both report the same phase vocabulary
            solo_bd = solo_sections[0]["searches"][0]["query"][0][
                "breakdown"]
            cluster_bd = sections[0]["searches"][0]["query"][0][
                "breakdown"]
            assert set(solo_bd) == set(cluster_bd)
        finally:
            solo.stop()
    finally:
        for n in nodes.values():
            n.stop()


# -- /_metrics Prometheus exposition ----------------------------------------

_PROM_LINE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*"
    r"(\{[a-zA-Z_][a-zA-Z0-9_]*=\"[^\"]*\""
    r"(,[a-zA-Z_][a-zA-Z0-9_]*=\"[^\"]*\")*\})? "
    r"[-+]?[0-9]+(\.[0-9]+)?([eE][-+]?[0-9]+)?$")


def test_metrics_endpoint_is_valid_prometheus_text(tmp_path):
    from opensearch_tpu.node import Node
    from opensearch_tpu.rest.controller import PlainText
    node = Node(str(tmp_path / "n"), port=0)
    try:
        node.rest.dispatch("PUT", "/m", {}, b"{}")
        node.rest.dispatch("PUT", "/m/_doc/1", {},
                           json.dumps({"x": 1}).encode())
        node.rest.dispatch("GET", "/m/_refresh", {}, None)
        node.rest.dispatch("POST", "/m/_search", {}, json.dumps(
            {"query": {"match_all": {}}}).encode())
        st, payload = node.rest.dispatch("GET", "/_metrics", {}, None)
        assert st == 200 and isinstance(payload, PlainText)
        assert payload.content_type.startswith("text/plain")
        text = payload.text
        assert text.endswith("\n")
        names_typed = {}
        for line in text.splitlines():
            if not line:
                continue
            if line.startswith("# TYPE "):
                _, _, name, kind = line.split(" ")
                names_typed[name] = kind
                continue
            if line.startswith("#"):
                continue
            assert _PROM_LINE.match(line), f"invalid line: {line!r}"
        assert any(k == "counter" for k in names_typed.values())
        assert any(k == "histogram" for k in names_typed.values())

        # histogram series are complete and cumulative, and report the
        # same underlying data as the JSON stats() buckets
        hname = "search_query_ms"
        buckets = []
        sum_v = count_v = None
        for line in text.splitlines():
            if line.startswith(f"{hname}_bucket"):
                le = line.split('le="')[1].split('"')[0]
                buckets.append((le, int(line.rsplit(" ", 1)[1])))
            elif line.startswith(f"{hname}_sum "):
                sum_v = float(line.rsplit(" ", 1)[1])
            elif line.startswith(f"{hname}_count "):
                count_v = int(line.rsplit(" ", 1)[1])
        assert buckets and buckets[-1][0] == "+Inf"
        counts = [c for _le, c in buckets]
        assert counts == sorted(counts)          # cumulative
        assert counts[-1] == count_v and sum_v is not None
        jstats = metrics().histogram("search.query_ms").stats()
        assert [b["count"] for b in jstats["buckets"]] == counts
    finally:
        node.stop()


def test_histogram_stats_buckets_unit():
    h = Histogram("t.unit", buckets=(1, 10, 100))
    for v in (0.5, 5, 5, 50, 5000):
        h.observe(v)
    st = h.stats()
    assert [b["le"] for b in st["buckets"]] == [1.0, 10.0, 100.0,
                                                "+Inf"]
    assert [b["count"] for b in st["buckets"]] == [1, 3, 4, 5]
    assert st["count"] == 5
    # prometheus rendering agrees with the JSON readout
    reg = MetricsRegistry()
    reg.histogram("t.unit", buckets=(1, 10, 100))
    for v in (0.5, 5, 5, 50, 5000):
        reg.histogram("t.unit").observe(v)
    text = reg.prometheus_text()
    assert 't_unit_ms_bucket{le="10"} 3' in text
    assert 't_unit_ms_bucket{le="+Inf"} 5' in text
    assert "t_unit_ms_count 5" in text


# -- flight recorder --------------------------------------------------------

def test_slowlog_trip_records_flight_capture(tmp_path):
    from opensearch_tpu.node import Node
    node = Node(str(tmp_path / "n"), port=0)
    try:
        node.rest.dispatch("PUT", "/fr", {}, json.dumps({
            "settings": {"index": {"search": {"slowlog": {"threshold": {
                "query": {"warn": "0ms"}}}}}},
            "mappings": {"properties": {"body": {"type": "text"}}},
        }).encode())
        node.rest.dispatch("PUT", "/fr/_doc/1", {},
                           json.dumps({"body": "hello"}).encode())
        node.rest.dispatch("GET", "/fr/_refresh", {}, None)
        node.rest.dispatch("POST", "/fr/_search", {}, json.dumps(
            {"query": {"match": {"body": "hello"}},
             "profile": True}).encode())
        caps = flight_recorder().captures()
        assert caps and caps[0]["trigger"] == "slow_log"
        assert caps[0]["detail"]["index"] == "fr"
        assert caps[0]["detail"]["profile"]["shards"]
        assert caps[0]["counters"]
        # retrievable over REST
        st, resp = node.rest.dispatch("GET", "/_nodes/flight_recorder",
                                      {}, None)
        assert st == 200
        rest_caps = resp["nodes"][node.node_id]["captures"]
        assert rest_caps and rest_caps[0]["trigger"] == "slow_log"
    finally:
        node.stop()


def test_soak_breach_attaches_flight_capture(tmp_path):
    """A forced SLO breach (impossible p99 limit) must ship a non-empty
    flight-recorder capture ON the breach verdict."""
    from opensearch_tpu.testing.workload import SoakConfig, SoakRunner
    cfg = SoakConfig.smoke(
        n_ops=8, n_docs=8, faults_enabled=False, control_run=False,
        slos={"p99_ms": {"search": -1.0},
              "max_rejection_rate": 1.0,
              "max_unexpected_errors": 1000,
              "require_convergence": False})
    report = SoakRunner(str(tmp_path), cfg).run()
    breached = [v for v in report["verdicts"] if not v["ok"]]
    assert breached, "forced breach did not breach"
    for v in breached:
        cap = v["flight_recorder"]
        assert cap["trigger"] == "slo_breach"
        assert v["slo"] in cap["reason"]
        assert cap["counters"], "capture carries no evidence"
        assert cap["detail"]["limit"] == v["limit"]
    assert not report["slo_ok"]


def test_client_metrics_and_flight_recorder_roundtrip(tmp_path):
    """The Python client surfaces both new endpoints: ``metrics()``
    returns the raw Prometheus text, ``nodes.flight_recorder()`` the
    capture ring."""
    from opensearch_tpu.client import OpenSearch
    from opensearch_tpu.node import Node
    node = Node(str(tmp_path / "n"), port=0).start()
    try:
        client = OpenSearch(
            [{"host": "127.0.0.1", "port": node.port}])
        client.index("c", {"x": 1}, id="1")
        text = client.metrics()
        assert isinstance(text, str) and "_total" in text
        flight_recorder().record("slow_log", "test capture")
        resp = client.nodes.flight_recorder()
        caps = resp["nodes"][node.node_id]["captures"]
        assert caps and caps[0]["reason"] == "test capture"
    finally:
        node.stop()


# -- metric-name lint -------------------------------------------------------

def test_check_metric_names_lint_passes():
    r = subprocess.run(
        [sys.executable, os.path.join(TOOLS, "check_metric_names.py")],
        capture_output=True, text=True)
    assert r.returncode == 0, r.stdout + r.stderr


def test_check_metric_names_lint_catches_violations(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text(
        "def f(term):\n"
        "    metrics().counter(f\"q.{term}.hits\").inc()\n"
        "    metrics().histogram(\"UpperCase.Name\").observe(1)\n"
        "    metrics().counter(\"noDotsHere\").inc()\n"
        "    metrics().counter(\"fine.dotted.name\").inc()\n"
        "    metrics().counter(f\"q.{term}\").inc()  # metric-name-ok\n")
    r = subprocess.run(
        [sys.executable, os.path.join(TOOLS, "check_metric_names.py"),
         str(bad)],
        capture_output=True, text=True)
    assert r.returncode == 1
    assert "3 metric-name violation(s)" in r.stdout
