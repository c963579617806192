"""Query insights (PR 10): always-on top-N query attribution,
per-plan-signature workload stats, coalescability reporting, cluster
fan-in, the recovery observability surfaces, the mesh-path fallback,
and the Prometheus label-cardinality lint.

Pinned invariants:
- responses are byte-identical with insights enabled vs disabled (the
  recorder never mutates a response);
- the plan signature recorded by a data node equals the one the
  coordinator computes from the same body (fan-in aggregates correctly);
- every Prometheus label value flows through the bounded signature /
  top-N path (tools/check_prom_labels.py, tier-1 via this file).
"""

import json
import subprocess
import sys
import time

import pytest

from opensearch_tpu.common.breakers import (CircuitBreakerService,
                                            breaker_service, install)
from opensearch_tpu.common.telemetry import (flight_recorder, metrics,
                                             tracer)
from opensearch_tpu.node import Node
from opensearch_tpu.search import insights as insights_mod
from opensearch_tpu.search.insights import (QueryInsightsService,
                                            canonical_query,
                                            merge_sections,
                                            scored_for_body,
                                            signature_hash)

TOOLS = __file__.rsplit("/tests/", 1)[0] + "/tools"


@pytest.fixture(autouse=True)
def _clean_telemetry():
    tracer().reset()
    flight_recorder().reset()
    yield
    tracer().reset()
    flight_recorder().reset()


class FakeClock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def _svc(clock=None, **kw):
    return QueryInsightsService(node_id="test-node",
                                clock=clock or FakeClock(), **kw)


def _rec(sig="q1", took=5.0, **kw):
    rec = {"signature": sig, "scored": True, "took_ms": took,
           "execution_path": "host", "plan_cache": "miss"}
    rec.update(kw)
    return rec


# -- unit: record / rollup / coalescability ---------------------------------

def test_rollup_counts_percentiles_and_interarrival():
    clock = FakeClock()
    svc = _svc(clock)
    for took in (1.0, 2.0, 100.0):
        svc.record(_rec(took=took))
        clock.advance(0.5)                     # 500ms apart
    sec = svc.section()
    sig = signature_hash("q1", True)
    roll = sec["signatures"][sig]
    assert roll["count"] == 3
    assert roll["latency_ms"]["max"] == 100.0
    assert roll["latency_ms"]["p99"] <= 100.0
    assert roll["interarrival_ms"]["mean"] == pytest.approx(500.0)
    assert roll["interarrival_ms"]["min"] == pytest.approx(500.0)
    # 500ms apart with a 10ms window: nothing coalesces
    assert roll["coalescable_fraction"] == 0.0
    assert sec["coalescability"]["coalescable_fraction"] == 0.0


def test_coalescability_fraction_counts_close_arrivals():
    clock = FakeClock()
    svc = _svc(clock, coalesce_window_ms=10.0)
    svc.record(_rec())                        # first arrival never counts
    for _ in range(3):
        clock.advance(0.005)                  # 5ms < 10ms window
        svc.record(_rec())
    clock.advance(5.0)                        # way outside the window
    svc.record(_rec())
    # a DIFFERENT signature arriving nearby does not coalesce with q1
    clock.advance(0.001)
    svc.record(_rec(sig="q2"))
    rep = svc.coalescability()
    assert rep["arrivals"] == 6
    assert rep["coalesced"] == 3
    assert rep["coalescable_fraction"] == pytest.approx(3 / 6)
    assert rep["top_signatures"][0]["signature"] == \
        signature_hash("q1", True)


def test_top_rings_rank_by_latency_cpu_and_heap():
    svc = _svc()
    svc.record(_rec(sig="slow", took=50.0), cpu_nanos=10, heap_bytes=10)
    svc.record(_rec(sig="cpu", took=1.0), cpu_nanos=9_000_000,
               heap_bytes=20)
    svc.record(_rec(sig="heap", took=2.0), cpu_nanos=20,
               heap_bytes=1 << 20)
    assert svc.top(by="latency")[0]["signature"] == \
        signature_hash("slow", True)
    assert svc.top(by="cpu")[0]["signature"] == \
        signature_hash("cpu", True)
    assert svc.top(by="heap")[0]["signature"] == \
        signature_hash("heap", True)
    from opensearch_tpu.common.errors import IllegalArgumentError
    with pytest.raises(IllegalArgumentError):
        svc.top(by="vibes")


def test_sliding_window_expires_ring_entries():
    clock = FakeClock()
    svc = _svc(clock, window_s=60.0)
    svc.record(_rec(sig="old"))
    clock.advance(120.0)
    svc.record(_rec(sig="new"))
    sigs = {r["signature"] for r in svc.top(n=10)}
    assert sigs == {signature_hash("new", True)}
    st = svc.stats()
    assert st["records"] == 2            # lifetime totals keep counting
    assert st["ring_size"] == 1


def test_signature_table_bounded_with_lru_eviction():
    clock = FakeClock()
    svc = _svc(clock, max_signatures=4)
    for i in range(10):
        svc.record(_rec(sig=f"q{i}"))
        clock.advance(1.0)
    st = svc.stats()
    assert st["signatures"] <= 4
    # the most recent signatures survive
    assert signature_hash("q9", True) in svc.section()["signatures"]
    assert signature_hash("q0", True) not in svc.section()["signatures"]


def test_breaker_pressure_evicts_rings_then_drops():
    prev = breaker_service()
    tiny = CircuitBreakerService({"breaker.request.limit": 3000,
                                  "breaker.total.limit": 3000})
    install(tiny)
    try:
        svc = _svc()
        for i in range(50):
            svc.record(_rec(sig=f"q{i}", took=float(i)))
        st = svc.stats()
        # bounded: the ring shrank under pressure instead of growing
        # past the breaker, and the overflow is accounted, not silent
        assert st["ring_bytes"] <= 3000
        assert st["evictions"] > 0 or st["dropped"] > 0
        assert tiny.request.used <= 3000
        svc.reset()
        assert tiny.request.used == 0      # every reservation released
    finally:
        install(prev)


def test_disabled_service_records_nothing():
    svc = _svc()
    svc.set_enabled(False)
    svc.record(_rec())
    assert svc.stats()["records"] == 0
    svc.set_enabled(True)
    svc.record(_rec())
    assert svc.stats()["records"] == 1


# -- unit: signatures -------------------------------------------------------

def test_signature_canonicalization_ignores_key_order():
    a = canonical_query({"bool": {"must": [{"match": {"t": "x"}}],
                                  "filter": []}})
    b = canonical_query({"bool": {"filter": [],
                                  "must": [{"match": {"t": "x"}}]}})
    assert a == b
    assert signature_hash(a, True) == signature_hash(b, True)
    assert signature_hash(a, True) != signature_hash(a, False)
    assert signature_hash(None) == "_unsigned"


def test_scored_for_body_mirrors_executor():
    assert scored_for_body({}) is True
    assert scored_for_body({"sort": [{"n": "asc"}]}) is False
    assert scored_for_body({"sort": ["_score"]}) is True
    assert scored_for_body({"sort": [{"n": "asc"}],
                            "min_score": 0.5}) is True


# -- unit: fan-in merge -----------------------------------------------------

def _section(node, sig_counts, top=()):
    return {
        "node": node,
        "top_queries": [dict(t, node=node) for t in top],
        "signatures": {s: {"count": c, "coalesced": c // 2,
                           "source": s}
                       for s, c in sig_counts.items()},
        "coalescability": {},
        "totals": {"records": sum(sig_counts.values()),
                   "coalesced": sum(c // 2
                                    for c in sig_counts.values())},
    }


def test_merge_sections_is_deterministic_and_provenance_annotated():
    sections = {
        "n1": _section("n1", {"sigA": 4, "sigB": 2},
                       top=[{"signature": "sigA", "took_ms": 9.0}]),
        "n0": _section("n0", {"sigA": 6},
                       top=[{"signature": "sigA", "took_ms": 12.0}]),
        "n2": {"error": "ReceiveTimeoutError: boom"},
    }
    out1 = merge_sections(sections, by="latency", n=5)
    out2 = merge_sections(dict(reversed(list(sections.items()))),
                          by="latency", n=5)
    assert out1 == out2                     # input order never matters
    assert out1["failed_nodes"] == {"n2": "ReceiveTimeoutError: boom"}
    assert out1["top_queries"][0]["node"] == "n0"     # 12ms beats 9ms
    merged_a = out1["signatures"]["sigA"]
    assert merged_a["count"] == 10
    assert set(merged_a["nodes"]) == {"n0", "n1"}     # provenance kept
    assert out1["coalescability"]["arrivals"] == 12


# -- REST integration -------------------------------------------------------

@pytest.fixture(scope="module")
def node(tmp_path_factory):
    n = Node(str(tmp_path_factory.mktemp("insights-node")), port=0)
    yield n
    n.stop()


def call(node, method, path, body=None, params=None, headers=None,
         ndjson=None):
    if ndjson is not None:
        raw = ("\n".join(json.dumps(l) for l in ndjson) + "\n").encode()
        ctype = "application/x-ndjson"
    else:
        raw = json.dumps(body).encode() if body is not None else None
        ctype = "application/json"
    return node.rest.dispatch(method, path, params or {}, raw, ctype,
                              headers=headers or {})


def _seed(node, index, docs=24):
    s, r = call(node, "PUT", f"/{index}", {
        "mappings": {"properties": {"t": {"type": "text"},
                                    "n": {"type": "long"}}}})
    assert s == 200, r
    lines = []
    for i in range(docs):
        lines.append({"index": {"_index": index, "_id": str(i)}})
        lines.append({"t": f"w{i % 5} common", "n": i})
    s, r = call(node, "POST", "/_bulk", params={"refresh": "true"},
                ndjson=lines)
    assert s == 200 and not r["errors"], r


def test_rest_records_and_top_queries_endpoint(node):
    _seed(node, "insix")
    node.insights.reset()
    body = {"query": {"match": {"t": "common"}}, "size": 5}
    for _ in range(3):
        s, r = call(node, "POST", "/insix/_search", body,
                    headers={"X-Opaque-Id": "dashboards-7"})
        assert s == 200 and "_insight" not in r
    s, out = call(node, "GET", "/_insights/top_queries")
    assert s == 200
    sig = signature_hash(canonical_query(body["query"]), True)
    assert [e for e in out["top_queries"] if e["signature"] == sig]
    roll = out["signatures"][sig]
    assert roll["count"] == 3
    # plan-cache attribution: the first run misses, repeats hit
    assert roll["nodes"][node.node_id]["plan_cache_hits"] == 2
    # X-Opaque-Id threads into the rollup's client attribution
    assert roll["nodes"][node.node_id]["clients"] == {"dashboards-7": 3}
    top = out["top_queries"][0]
    assert top["x_opaque_id"] == "dashboards-7"
    assert top["node"] == node.node_id
    assert top["execution_path"] in ("host", "device")
    assert top["cpu_nanos"] >= 0 and "took_ms" in top
    # ranked-by-cpu variant answers too
    s, out = call(node, "GET", "/_insights/top_queries",
                  params={"by": "cpu", "size": "2"})
    assert s == 200 and len(out["top_queries"]) <= 2


def test_responses_byte_identical_with_insights_on_and_off(node):
    _seed(node, "insbyte")
    body = {"query": {"match": {"t": "common"}}, "size": 4}

    def run():
        s, r = call(node, "POST", "/insbyte/_search", body)
        assert s == 200
        r = dict(r)
        r.pop("took")          # wall-clock, varies run to run regardless
        return json.dumps(r, sort_keys=True)

    warm = run()               # plan cache warm for both measurements
    on = run()
    s, _ = call(node, "PUT", "/_cluster/settings", {
        "transient": {"search.insights.enabled": False}})
    assert s == 200
    try:
        off = run()
        assert warm == on == off
        before = node.insights.stats()["records"]
        run()
        assert node.insights.stats()["records"] == before  # truly off
    finally:
        call(node, "PUT", "/_cluster/settings", {
            "transient": {"search.insights.enabled": None}})
    assert node.insights.enabled


def test_msearch_members_recorded_with_batch_attribution(node):
    _seed(node, "insms")
    node.insights.reset()
    lines = []
    for i in range(4):
        lines.append({"index": "insms"})
        lines.append({"query": {"match": {"t": f"w{i}"}}, "size": 3})
    s, r = call(node, "POST", "/_msearch", ndjson=lines)
    assert s == 200
    assert all(m.get("status") == 200 and "_insight" not in m
               for m in r["responses"])
    sec = node.insights.section()
    assert sec["totals"]["records"] == 4       # one record per member
    batched = [e for e in sec["top_queries"] if e.get("batched")]
    assert batched and batched[0]["batched"] == 4   # coalesced group of 4
    assert batched[0]["execution_path"].endswith("_batched")
    # four distinct term sets -> four distinct plan signatures
    assert len(sec["signatures"]) == 4


def test_request_cache_hit_attribution(node):
    _seed(node, "inscache")
    node.insights.reset()
    body = {"query": {"term": {"t": "common"}}, "size": 0}
    for _ in range(2):
        s, _r = call(node, "POST", "/inscache/_search", body)
        assert s == 200
    recs = node.insights.top(n=10)
    states = sorted(r["request_cache"] for r in recs)
    assert states == ["hit", "miss"]
    hit = next(r for r in recs if r["request_cache"] == "hit")
    assert hit["execution_path"] == "cached"
    assert hit["plan_cache"] == "hit"
    # both runs map to the SAME signature (scored=False on both)
    assert len({r["signature"] for r in recs}) == 1


def test_nodes_stats_query_insights_block(node):
    _seed(node, "insstats")
    node.insights.reset()
    call(node, "POST", "/insstats/_search",
         {"query": {"match": {"t": "common"}}})
    s, r = call(node, "GET", "/_nodes/stats")
    assert s == 200
    qi = r["nodes"][node.node_id]["query_insights"]
    assert qi["enabled"] is True
    assert qi["records"] >= 1
    assert qi["signatures"] >= 1
    assert 0.0 <= qi["coalescable_fraction"] <= 1.0
    assert {"rejected", "dropped", "evictions"} <= set(qi)


_PROM_LINE = (r'^[a-zA-Z_:][a-zA-Z0-9_:]*'
              r'(\{[a-zA-Z_][a-zA-Z0-9_]*="[^"]*"'
              r'(,[a-zA-Z_][a-zA-Z0-9_]*="[^"]*")*\})? '
              r'[0-9eE.+-]+(ns|inf|an)?$')


def test_metrics_exposition_carries_bounded_signature_labels(node):
    import re
    _seed(node, "insprom")
    node.insights.reset()
    for _ in range(2):
        call(node, "POST", "/insprom/_search",
             {"query": {"match": {"t": "common"}}})
    s, payload = call(node, "GET", "/_metrics")
    assert s == 200
    text = payload.text
    ins = [l for l in text.splitlines() if "insights" in l]
    series = [l for l in ins if not l.startswith("#")]
    assert series, "no insights series in /_metrics"
    rx = re.compile(_PROM_LINE)
    for line in series:
        assert rx.match(line), f"invalid prometheus line: {line!r}"
        # the signature (or PR-14 tenant) is a LABEL drawn from a
        # bounded path — never part of the metric name
        assert re.search(r'\{signature="[0-9a-f_]{1,12}"', line) \
            or re.search(r'\{tenant="[^"]{1,64}"', line), line
        assert "node=" in line
    counts = [l for l in series
              if l.startswith(
                  "opensearch_tpu_insights_signature_queries_total")]
    assert counts and counts[0].rstrip().endswith("2")


def test_rejected_searches_counted_without_ring_entries(node):
    from opensearch_tpu.search.backpressure import SearchRejectedError
    node.insights.reset()
    orig = node.search_backpressure.admission.acquire

    def rejecting(_name, tenant=None):
        raise SearchRejectedError("saturated", retry_after_seconds=1)
    node.search_backpressure.admission.acquire = rejecting
    try:
        s, _ = call(node, "POST", "/insix/_search",
                    {"query": {"match_all": {}}})
        assert s == 429
    finally:
        node.search_backpressure.admission.acquire = orig
    st = node.insights.stats()
    assert st["rejected"] == 1
    assert st["ring_size"] == 0


# -- dynamic settings -------------------------------------------------------

def test_insights_settings_reach_live_service(node):
    s, _ = call(node, "PUT", "/_cluster/settings", {"transient": {
        "search.insights.top_n": 3,
        "search.insights.coalesce_window_ms": 25.0}})
    assert s == 200
    try:
        assert node.insights.top_n == 3
        assert node.insights.coalesce_window_ms == 25.0
    finally:
        call(node, "PUT", "/_cluster/settings", {"transient": {
            "search.insights.top_n": None,
            "search.insights.coalesce_window_ms": None}})
    assert node.insights.top_n == 10


# -- recovery observability -------------------------------------------------

def test_cat_recovery_and_nodes_stats_recovery_section(node):
    _seed(node, "insrec")
    metrics().counter("recovery.corrupt_blobs").inc(2)
    s, rows = call(node, "GET", "/_cat/recovery/insrec")
    assert s == 200 and rows
    row = rows[0]
    assert row["index"] == "insrec" and row["stage"] == "done"
    assert int(row["corrupt_blobs"]) >= 2
    assert "retries" in row
    s, r = call(node, "GET", "/_nodes/stats")
    rec = r["nodes"][node.node_id]["recovery"]
    assert rec["corrupt_blobs"] >= 2
    assert set(rec["retries"]) == {"start", "report", "fetch"}
    assert {"attempts", "retries", "exhausted"} <= \
        set(rec["retries"]["start"])
    shards = [s_ for s_ in rec["shards"] if s_["index"] == "insrec"]
    assert shards and shards[0]["stage"] == "done"


# -- mesh fallback (satellite: the pre-existing 500) ------------------------

def test_mesh_unavailable_degrades_to_host_scatter(node):
    """A mesh that loses a member mid-collective must not 500: the
    host scatter serves the request with mesh semantics (per-shard
    scoring stats, coordinator merge order) and the fallback is counted
    in search.mesh.fallback."""
    from opensearch_tpu.common.device_health import device_health
    from opensearch_tpu.search.executor import merge_hit_rows
    from opensearch_tpu.testing.fault_injection import DeviceFaultInjector
    s, _ = call(node, "PUT", "/meshfall", {
        "settings": {"number_of_shards": 4, "search.mesh": True},
        "mappings": {"properties": {"t": {"type": "text"},
                                    "n": {"type": "long"}}}})
    assert s == 200
    lines = []
    for i in range(40):
        lines.append({"index": {"_index": "meshfall", "_id": str(i)}})
        lines.append({"t": f"w{i % 7} common", "n": i})
    s, r = call(node, "POST", "/_bulk", params={"refresh": "true"},
                ndjson=lines)
    assert s == 200 and not r["errors"]

    node.insights.reset()
    before = metrics().counter("search.mesh.fallback").value
    body = {"query": {"match": {"t": "common"}}, "size": 8}
    svc = node.indices.get("meshfall")
    assert svc._use_mesh(body)          # the request still opts in
    inj = DeviceFaultInjector(seed=5)
    inj.lose_mesh_member()
    try:
        with inj:
            s, resp = call(node, "POST", "/meshfall/_search", body)
    finally:
        device_health().reset()         # the loss counted a mesh failure
    assert s == 200, resp               # no 500
    assert metrics().counter("search.mesh.fallback").value == before + 1
    assert resp["hits"]["total"]["value"] == 40
    # parity with the per-shard host oracle (the mesh merge semantics)
    rows, total = [], 0
    for si, sh in enumerate(sorted(svc.local_shards)):
        r2 = svc.local_shards[sh].acquire_searcher().search(
            dict(body, size=8))
        total += r2["hits"]["total"]["value"]
        rows.extend((h, si, pos)
                    for pos, h in enumerate(r2["hits"]["hits"]))
    want = [(h["_id"], h["_score"])
            for h in merge_hit_rows(rows, None)[:8]]
    got = [(h["_id"], h["_score"]) for h in resp["hits"]["hits"]]
    assert got == want and total == 40
    # the fallback is attributed in insights, not just a counter
    paths = {e["execution_path"] for e in node.insights.top(n=5)}
    assert "mesh_fallback" in paths


def test_mesh_serves_mesh_when_healthy(node):
    """With every member present the request takes the mesh (no
    fallback count)."""
    before = metrics().counter("search.mesh.fallback").value
    body = {"query": {"match": {"t": "common"}}, "size": 5}
    s, resp = call(node, "POST", "/meshfall/_search", body)
    assert s == 200 and resp["hits"]["hits"]
    assert metrics().counter("search.mesh.fallback").value == before


# -- cluster fan-in ---------------------------------------------------------

def wait_until(pred, timeout=10.0):
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:   # deadline-bounded poll
        if pred():
            return True
        time.sleep(0.05)
    return False


@pytest.fixture
def cluster(tmp_path):
    from opensearch_tpu.cluster.node import ClusterNode
    from opensearch_tpu.transport.service import (LocalTransport,
                                                  TransportService)
    hub = LocalTransport.Hub()
    ids = ["n0", "n1", "n2"]
    nodes = {}
    for nid in ids:
        svc = TransportService(nid, LocalTransport(hub))
        n = ClusterNode(nid, str(tmp_path / nid), svc, ids)
        n.search_backpressure.trackers["cpu_usage"].probe = lambda: 0.0
        nodes[nid] = n
    assert nodes["n0"].start_election()
    assert wait_until(lambda: all(
        nodes[i].coordinator.state().master_node == "n0" for i in ids))
    yield hub, ids, nodes
    for n in nodes.values():
        n.stop()


def test_three_node_fanin_merge_deterministic(cluster):
    from opensearch_tpu.common import tasks as taskmod
    hub, ids, nodes = cluster
    nodes["n0"].create_index("fan", {
        "settings": {"number_of_shards": 3, "number_of_replicas": 1},
        "mappings": {"properties": {"t": {"type": "text"}}}})

    def in_sync():
        routing = nodes["n0"].coordinator.state().routing.get("fan", [])
        return routing and all(
            set(e["in_sync"]) == {e["primary"], *e["replicas"]}
            for e in routing)
    assert wait_until(in_sync)
    for i in range(30):
        nodes["n0"].index_doc("fan", str(i), {"t": f"w{i % 4} common"})
    nodes["n0"].refresh("fan")

    body = {"query": {"match": {"t": "common"}}}
    # X-Opaque-Id rides the ambient task into the scatter payloads
    tm = nodes["n2"].task_manager
    outer = tm.register("rest:test",
                        headers={"X-Opaque-Id": "tenant-42"})
    token = taskmod.set_current(outer)
    try:
        for _ in range(3):
            r = nodes["n2"].search("fan", dict(body))
            assert r["hits"]["total"]["value"] == 30
    finally:
        taskmod.reset_current(token)
        tm.unregister(outer)

    out1 = nodes["n2"].top_queries(by="latency", n=8)
    assert out1["coordinator"] == "n2"
    assert "failed_nodes" not in out1
    sig = signature_hash(canonical_query(body["query"]), True)
    merged = out1["signatures"][sig]
    # coordinator scatter + shard query phases all fold into ONE
    # signature: the coordinator's computed key matches the data nodes'
    # plan-cache stamps (parity), and provenance names every recorder
    assert merged["count"] >= 6
    assert len(merged["nodes"]) == 3
    paths = set()
    for entry in out1["top_queries"]:
        assert entry["node"] in ids            # provenance annotated
        paths.add(entry["execution_path"])
    assert "scatter" in paths                  # coordinator records
    assert paths & {"host", "device"}          # data nodes record
    # X-Opaque-Id reached the DATA nodes' records, not just n2's
    data_entries = [e for e in out1["top_queries"]
                    if e["node"] != "n2"]
    assert data_entries
    assert all(e.get("x_opaque_id") == "tenant-42"
               for e in data_entries)
    # deterministic: a second merge of the same state is identical
    out2 = nodes["n2"].top_queries(by="latency", n=8)
    assert out1 == out2


def test_fanin_reports_unreachable_node(cluster):
    hub, ids, nodes = cluster
    nodes["n1"].stop()
    hub.unregister("n1") if hasattr(hub, "unregister") else None
    out = nodes["n0"].top_queries()
    # n1 may answer from its (stopped) local transport or fail; either
    # way the merge never throws and every live node reports
    assert "n0" in out["nodes"] or out.get("failed_nodes")


# -- SLO breach snapshot ----------------------------------------------------

def test_soak_breach_capture_includes_top_queries_snapshot(tmp_path):
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
    qi = report["chaos"]["query_insights"]
    assert qi["totals"]["records"] > 0
    assert qi["top_queries"], "no workload evidence in the snapshot"
    for v in breached:
        snap = v["flight_recorder"]["detail"]["query_insights"]
        assert snap["totals"]["records"] > 0
        assert 0.0 <= snap["coalescability"]["coalescable_fraction"] <= 1


# -- lint: prometheus label cardinality -------------------------------------

def test_prom_label_lint_repo_clean():
    proc = subprocess.run(
        [sys.executable, f"{TOOLS}/check_prom_labels.py"],
        capture_output=True, text=True)
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_prom_label_lint_catches_unannotated_site(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text(
        'def emit(term):\n'
        '    return f\'my_metric{{query="{term}"}} 1\'\n')
    proc = subprocess.run(
        [sys.executable, f"{TOOLS}/check_prom_labels.py", str(bad)],
        capture_output=True, text=True)
    assert proc.returncode == 1
    assert "label" in proc.stdout
    ok = tmp_path / "ok.py"
    ok.write_text(
        'def emit(sig):\n'
        '    # label-ok: sig is a bounded top-N signature hash\n'
        '    return f\'my_metric{{signature="{sig}"}} 1\'\n')
    proc = subprocess.run(
        [sys.executable, f"{TOOLS}/check_prom_labels.py", str(ok)],
        capture_output=True, text=True)
    assert proc.returncode == 0, proc.stdout
