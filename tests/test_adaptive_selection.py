"""Adaptive replica selection & coordinator-side load shedding (PR 6;
ref node/ResponseCollectorService.java + the C3 rank in
ComputedNodeStats, OperationRouting.rankShardsAndUpdateStats): per-node
response/service/queue EWMAs piggybacked on shard responses and
fault-detection pings, C3-ranked copy ordering with duress derank,
msearch replica spill, and duress shedding into partial results — all
deterministic (injectable clocks, seeded fault injection)."""

import json
import subprocess
import sys
import threading
import time

import pytest

from opensearch_tpu.cluster.node import A_SEARCH_SHARDS, ClusterNode
from opensearch_tpu.cluster import response_collector as rc
from opensearch_tpu.cluster.response_collector import (
    Ewma, ResponseCollectorService)
from opensearch_tpu.cluster.state import copies_of
from opensearch_tpu.common.telemetry import metrics
from opensearch_tpu.node import Node
from opensearch_tpu.testing.fault_injection import FaultInjector
from opensearch_tpu.transport.service import (LocalTransport,
                                              TransportService)

TOOLS = __file__.rsplit("/tests/", 1)[0] + "/tools"


def wait_until(pred, timeout=8.0):
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:   # deadline-bounded poll
        if pred():
            return True
        time.sleep(0.05)
    return False


class FakeClock:
    def __init__(self, t=100.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


# -- EWMA + rank unit layer -------------------------------------------------

def test_ewma_first_sample_then_decay():
    e = Ewma(alpha=0.3)
    assert e.value is None               # "no evidence" != "fast"
    assert e.add(100.0) == 100.0         # first sample seeds the average
    assert e.add(200.0) == pytest.approx(0.3 * 200 + 0.7 * 100)
    # decay toward a sustained new level
    for _ in range(50):
        e.add(10.0)
    assert e.value == pytest.approx(10.0, rel=1e-3)


def test_rank_reflects_response_service_and_queue():
    clock = FakeClock()
    c = ResponseCollectorService(clock=clock)
    c.record_response("fast", 1e6, {"queue_size": 0,
                                    "service_time_ewma_nanos": 1e6})
    c.record_response("slow", 80e6, {"queue_size": 0,
                                     "service_time_ewma_nanos": 80e6})
    c.record_response("queued", 1e6, {"queue_size": 40,
                                      "service_time_ewma_nanos": 1e6})
    assert c.rank("fast") < c.rank("slow")
    assert c.rank("fast") < c.rank("queued")   # cubed queue term bites
    assert c.rank("missing") is None


def test_rank_copies_without_evidence_preserves_legacy_order():
    c = ResponseCollectorService(clock=FakeClock())
    ordered, rerouted = c.rank_copies(["n2", "n0", "n1"])
    assert ordered == ["n2", "n0", "n1"]
    assert rerouted is False


def test_rank_copies_deranks_slow_node_and_flags_reroute():
    c = ResponseCollectorService(clock=FakeClock())
    c.record_response("n2", 300e6, {"service_time_ewma_nanos": 300e6})
    c.record_response("n0", 1e6, {"service_time_ewma_nanos": 1e6})
    ordered, rerouted = c.rank_copies(["n2", "n0"])
    assert ordered == ["n0", "n2"] and rerouted is True
    # an unprobed replica ranks at the fleet mean: it beats the watched
    # slow copy but does not displace a copy performing at par
    ordered, rerouted = c.rank_copies(["n2", "n1"])
    assert ordered == ["n1", "n2"] and rerouted is True
    ordered, rerouted = c.rank_copies(["n0", "n1"])
    assert ordered[0] == "n0" and rerouted is False


def test_record_failure_penalizes_harder_each_time():
    c = ResponseCollectorService(clock=FakeClock())
    c.record_response("n1", 1e6, {"service_time_ewma_nanos": 1e6})
    c.record_response("n2", 1e6, {"service_time_ewma_nanos": 1e6})
    r0 = c.rank("n2")
    c.record_failure("n2", 0.5e9)
    r1 = c.rank("n2")
    c.record_failure("n2", 0.5e9)        # repeated timeouts compound
    r2 = c.rank("n2")
    assert r0 < r1 < r2
    assert c.rank_copies(["n2", "n1"])[0] == ["n1", "n2"]
    assert c.stats()["n2"]["failure_count"] == 2


def test_duress_flag_expires_on_injectable_clock():
    clock = FakeClock()
    c = ResponseCollectorService(clock=clock, duress_ttl_s=5.0)
    c.record_duress("n1", True)
    assert c.in_duress("n1")
    clock.advance(4.9)
    assert c.in_duress("n1")             # still fresh
    clock.advance(0.2)
    assert not c.in_duress("n1")         # stale: probe the node again
    c.record_ping_load("n1", {"duress": True, "queue_size": 1})
    assert c.in_duress("n1")             # ping refreshed the horizon
    c.record_ping_load("n1", {"duress": False, "queue_size": 0})
    assert not c.in_duress("n1")


def test_duress_deranks_but_retains():
    c = ResponseCollectorService(clock=FakeClock())
    c.record_duress("n0", True)
    ordered, rerouted = c.rank_copies(["n0", "n1", "n2"])
    assert ordered == ["n1", "n2", "n0"]   # last resort, never dropped
    assert rerouted is True


def test_stats_block_shape():
    clock = FakeClock()
    c = ResponseCollectorService(clock=clock)
    c.record_response("n1", 2e6, {"queue_size": 3, "duress": True,
                                  "service_time_ewma_nanos": 1e6})
    clock.advance(1.5)
    s = c.stats()["n1"]
    assert s["avg_response_time_ms"] == pytest.approx(2.0)
    assert s["avg_service_time_ms"] == pytest.approx(1.0)
    assert s["avg_queue_size"] == pytest.approx(3.0)
    assert s["in_duress"] is True and s["response_count"] == 1
    assert s["since_last_update_s"] == pytest.approx(1.5)
    assert isinstance(s["rank"], float)


# -- cluster fixture --------------------------------------------------------

@pytest.fixture
def cluster(tmp_path):
    hub = LocalTransport.Hub()
    ids = ["n0", "n1", "n2"]
    nodes = {}
    for nid in ids:
        svc = TransportService(nid, LocalTransport(hub))
        node = ClusterNode(nid, str(tmp_path / nid), svc, ids)
        # neutralize the real CPU probe: a loaded CI host must not leak
        # genuine duress into these deterministic scenarios
        node.search_backpressure.trackers["cpu_usage"].probe = lambda: 0.0
        nodes[nid] = node
    assert nodes["n0"].start_election()
    wait_until(lambda: all(
        nodes[i].coordinator.state().master_node == "n0" for i in ids))
    yield hub, ids, nodes
    for n in nodes.values():
        n.stop()


def _make_index(nodes, name, shards, replicas):
    nodes["n0"].create_index(name, {
        "settings": {"number_of_shards": shards,
                     "number_of_replicas": replicas},
        "mappings": {"properties": {"v": {"type": "long"}}}})

    def in_sync_full():
        routing = nodes["n0"].coordinator.state().routing.get(name, [])
        return routing and all(
            set(e["in_sync"]) == {e["primary"], *e["replicas"]}
            and len(e["replicas"]) >= replicas for e in routing)
    assert wait_until(in_sync_full)
    for i in range(20):
        nodes["n0"].index_doc(name, str(i), {"v": i})
    nodes["n0"].refresh(name)


def _count_search_rpcs(node):
    """Wrap a data node's query-phase handler with a counter."""
    counter = {"n": 0}
    inner = node.transport._handlers[A_SEARCH_SHARDS]

    def counting(payload):
        counter["n"] += 1
        return inner(payload)
    node.transport.register_handler(A_SEARCH_SHARDS, counting)
    return counter


# -- the acceptance bar: slow node gets deranked, queries reroute ----------

def test_delayed_node_deranked_queries_reroute_cleanly(cluster):
    """With n2 fault-injected slow, the coordinator's EWMA spikes, the
    C3 rank deranks every n2 copy, and subsequent searches run entirely
    on healthy replicas: zero `_shards.failures[]`, the reroute counter
    moves, and `adaptive_selection` stats show the deranked node."""
    hub, ids, nodes = cluster
    _make_index(nodes, "ars", 4, 1)
    routing = nodes["n0"].coordinator.state().routing["ars"]
    # a coordinator whose first candidate for some shard IS n2
    coord = next(n for n in ("n0", "n1")
                 if any(e["primary"] == "n2" and n not in copies_of(e)
                        for e in routing))

    # the searches' programs compile first, through another coordinator:
    # a process's first search pays the compile on whichever node serves
    # it, which would rank that healthy node below the slow one here
    warm = next(n for n in ids if n != coord)
    for size in (10, 30):
        nodes[warm].search("ars", {"query": {"match_all": {}},
                                   "size": size})
    faults = FaultInjector(hub, seed=11)
    faults.slow_search_node("n2", 0.3)
    # first search: no evidence yet, legacy order dispatches to n2 —
    # slow but successful, and the coordinator records the spike
    slow = nodes[coord].search("ars", {"query": {"match_all": {}}})
    assert slow["_shards"]["failed"] == 0

    n2_rpcs = _count_search_rpcs(nodes["n2"])
    before = metrics().counter("search.replica_selection.reroutes").value
    resp = nodes[coord].search("ars", {"query": {"match_all": {}},
                                       "size": 30})
    assert resp["hits"]["total"]["value"] == 20
    assert resp["_shards"]["failed"] == 0          # reroute, not failure
    assert n2_rpcs["n"] == 0                       # n2 never dispatched
    assert metrics().counter(
        "search.replica_selection.reroutes").value > before
    stats = nodes[coord].response_collector.stats()
    healthy = [s["rank"] for n, s in stats.items()
               if n != "n2" and s["rank"] is not None]
    assert stats["n2"]["rank"] > max(healthy)      # visibly deranked


def test_scatter_timeout_penalizes_collector_before_failover(cluster):
    """The PR-4-era bug: a timed-out scatter RPC advanced to the next
    copy without teaching the collector anything.  Now the failure
    penalizes the node's EWMA first, so repeated timeouts derank it."""
    hub, ids, nodes = cluster
    _make_index(nodes, "tmo", 2, 1)
    routing = nodes["n0"].coordinator.state().routing["tmo"]
    coord = next(n for n in ("n0", "n1")
                 if any(e["primary"] == "n2" and n not in copies_of(e)
                        for e in routing))
    nodes[coord].search_rpc_timeout = 0.3          # keep the test fast

    faults = FaultInjector(hub, seed=23)
    faults.drop(A_SEARCH_SHARDS, target="n2", times=1, silent=True)
    resp = nodes[coord].search("tmo", {"query": {"match_all": {}},
                                       "size": 30})
    assert resp["_shards"]["failed"] == 0          # failover succeeded
    assert resp["hits"]["total"]["value"] == 20
    st = nodes[coord].response_collector.stats()["n2"]
    assert st["failure_count"] >= 1
    # and the penalty deranks n2 for the follow-up
    n2_rpcs = _count_search_rpcs(nodes["n2"])
    assert nodes[coord].search("tmo", {"query": {"match_all": {}}})[
        "_shards"]["failed"] == 0
    assert n2_rpcs["n"] == 0


# -- the acceptance bar: all copies in duress shed into partial results ----

def test_all_copies_in_duress_sheds_into_partial_results(cluster):
    """Duress progression: the first search learns the primary is in
    duress (piggyback), the second deranks it onto the replica (reroute)
    and learns the replica is drowning too, the third sheds fast into
    `_shards.failures[]` — and once duress clears, traffic resumes."""
    hub, ids, nodes = cluster
    _make_index(nodes, "duress", 1, 1)
    entry = nodes["n0"].coordinator.state().routing["duress"][0]
    primary, replica = entry["primary"], entry["replicas"][0]
    coord = next(i for i in ids if i not in copies_of(entry))
    # the step-by-step progression below requires a coordinator WITHOUT
    # the leader's background ping piggyback (which would teach it both
    # duress flags between searches and shed one step early)
    assert coord != "n0", "allocator change broke this test's setup"
    faults = FaultInjector(hub, seed=7)
    for nid in (primary, replica):
        bp = nodes[nid].search_backpressure
        bp.num_successive_breaches = 1
        faults.induce_search_duress(bp, ticks=1)
        bp.run_once()
        assert bp.in_duress()

    # 1: dispatched to the primary; its duress flag rides back
    r1 = nodes[coord].search("duress", {"query": {"match_all": {}}})
    assert r1["_shards"]["failed"] == 0
    assert nodes[coord].response_collector.in_duress(primary)

    # 2: primary deranked-but-retained → replica serves (a reroute),
    # and now the coordinator knows BOTH copies are drowning
    before = metrics().counter("search.replica_selection.reroutes").value
    r2 = nodes[coord].search("duress", {"query": {"match_all": {}}})
    assert r2["_shards"]["failed"] == 0
    assert metrics().counter(
        "search.replica_selection.reroutes").value > before
    assert nodes[coord].response_collector.in_duress(replica)

    # 3: every in-sync copy in duress → shed fast, no dispatch at all
    sheds_before = metrics().counter(
        "search.replica_selection.sheds").value
    rpcs = {nid: _count_search_rpcs(nodes[nid])
            for nid in (primary, replica)}
    r3 = nodes[coord].search("duress", {"query": {"match_all": {}}})
    assert r3["_shards"]["failed"] == 1
    assert r3["_shards"]["failures"][0]["reason"]["type"] == \
        "node_duress_exception"
    assert r3["hits"]["hits"] == []
    assert metrics().counter(
        "search.replica_selection.sheds").value == sheds_before + 1
    assert all(c["n"] == 0 for c in rpcs.values())

    # all-or-nothing clients are NOT shed: they asked to wait
    r4 = nodes[coord].search("duress", {
        "query": {"match_all": {}}, "size": 30,
        "allow_partial_search_results": False})
    assert r4["_shards"]["failed"] == 0
    assert r4["hits"]["total"]["value"] == 20

    # recovery: duress clears on the data nodes; once the coordinator's
    # flag goes stale it probes again and full service resumes
    for nid in (primary, replica):
        nodes[nid].search_backpressure.run_once()   # streak resets
        assert not nodes[nid].search_backpressure.in_duress()
    nodes[coord].response_collector.duress_ttl_s = 0.05
    time.sleep(0.1)
    r5 = nodes[coord].search("duress", {"query": {"match_all": {}},
                                        "size": 30})
    assert r5["_shards"]["failed"] == 0
    assert r5["hits"]["total"]["value"] == 20
    assert not nodes[coord].response_collector.in_duress(primary)


# -- msearch batch spill ----------------------------------------------------

def test_msearch_spills_batch_across_replicas(cluster):
    """A same-index msearch burst round-robins each shard's healthy
    copies instead of piling every sub-request onto the preferred one."""
    hub, ids, nodes = cluster
    _make_index(nodes, "spill", 1, 1)
    entry = nodes["n0"].coordinator.state().routing["spill"][0]
    coord = next(i for i in ids if i not in copies_of(entry))
    counters = {nid: _count_search_rpcs(nodes[nid])
                for nid in copies_of(entry)}

    body = {"query": {"match_all": {}}, "size": 5}
    out = nodes[coord].msearch("spill", [dict(body) for _ in range(4)])
    assert len(out["responses"]) == 4
    for resp in out["responses"]:
        assert "error" not in resp
        assert resp["hits"]["total"]["value"] == 20
    served = {nid: c["n"] for nid, c in counters.items()}
    assert all(n >= 2 for n in served.values()), served   # both copies


def test_msearch_isolates_per_subrequest_errors(cluster):
    hub, ids, nodes = cluster
    _make_index(nodes, "mix", 1, 0)
    out = nodes["n0"].msearch("mix", [
        {"query": {"match_all": {}}},
        {"query": {"no_such_query": {}}},
        {"query": {"match_all": {}}, "size": 1},
    ])
    assert out["responses"][0]["hits"]["total"]["value"] == 20
    assert "error" in out["responses"][1]
    assert len(out["responses"][2]["hits"]["hits"]) == 1


# -- piggyback freshness + lifecycle ---------------------------------------

def test_fault_detection_pings_refresh_collector(cluster):
    """The leader's follower checks carry each peer's load snapshot, so
    duress/queue stay fresh on an idle coordinator (no search traffic)."""
    hub, ids, nodes = cluster
    nodes["n0"].coordinator.run_checks_once()
    stats = nodes["n0"].response_collector.stats()
    assert {"n1", "n2"} <= set(stats)
    for nid in ("n1", "n2"):
        assert stats[nid]["avg_queue_size"] is not None
        assert stats[nid]["rank"] is None    # pings alone never rank
    # a follower's leader check refreshes ITS view of the leader
    nodes["n1"].coordinator.run_checks_once()
    assert "n0" in nodes["n1"].response_collector.stats()


def test_evicted_node_loses_its_stats(cluster):
    hub, ids, nodes = cluster
    nodes["n0"].coordinator.run_checks_once()
    assert "n2" in nodes["n0"].response_collector.tracked()
    FaultInjector(hub, seed=5).disconnect("n2")
    retries = nodes["n0"].coordinator.follower_checker.settings.retries
    for _ in range(retries):
        nodes["n0"].coordinator.run_checks_once()
    assert wait_until(
        lambda: "n2" not in nodes["n0"].coordinator.state().nodes)
    assert wait_until(
        lambda: "n2" not in nodes["n0"].response_collector.tracked())


def test_monitor_thread_wired_into_cluster_node_lifecycle(tmp_path):
    """ClusterNode.start() runs the backpressure monitor (duress is
    detected between admissions); stop() joins it promptly."""
    hub = LocalTransport.Hub()
    svc = TransportService("solo", LocalTransport(hub))
    node = ClusterNode("solo", str(tmp_path / "solo"), svc, ["solo"])
    assert not node.search_backpressure.monitor_alive()
    node.start()
    assert node.search_backpressure.monitor_alive()
    done = threading.Event()

    def stop():
        node.stop()
        done.set()
    threading.Thread(target=stop, daemon=True).start()
    assert done.wait(timeout=8.0), "ClusterNode.stop() hung"
    assert wait_until(
        lambda: not node.search_backpressure.monitor_alive(), timeout=6.0)


# -- REST + settings surfaces ----------------------------------------------

@pytest.fixture
def node(tmp_path):
    n = Node(str(tmp_path / "node"), port=0)
    yield n
    n.stop()


def test_nodes_stats_exposes_adaptive_selection(node):
    node.response_collector.record_response(
        "peer", 5e6, {"queue_size": 2, "duress": True,
                      "service_time_ewma_nanos": 4e6})
    status, resp = node.rest.dispatch("GET", "/_nodes/stats", {}, None)
    assert status == 200
    block = resp["nodes"][node.node_id]["adaptive_selection"]
    assert block["nodes"]["peer"]["in_duress"] is True
    assert block["nodes"]["peer"]["avg_response_time_ms"] == \
        pytest.approx(5.0)
    assert {"reroutes", "sheds"} <= set(block)


def test_cat_nodes_shows_ranks(node):
    node.response_collector.record_response(
        "peer", 5e6, {"service_time_ewma_nanos": 4e6})
    status, rows = node.rest.dispatch("GET", "/_cat/nodes", {}, None)
    assert status == 200
    by_name = {r["name"]: r for r in rows}
    assert by_name[node.name]["master"] == "*"
    assert by_name[node.name]["search.rank"] == "-"   # no samples on self
    assert float(by_name["peer"]["search.rank"]) > 0
    assert by_name["peer"]["search.duress"] == "false"


def test_replica_selection_dynamic_settings(node):
    try:
        assert rc.ADAPTIVE_ENABLED is True and rc.SHED_ON_DURESS is True
        node.update_cluster_settings(transient={
            "search.replica_selection.adaptive": False,
            "search.replica_selection.shed_on_duress": False})
        assert rc.ADAPTIVE_ENABLED is False
        assert rc.SHED_ON_DURESS is False
        node.update_cluster_settings(transient={
            "search.replica_selection.adaptive": None,
            "search.replica_selection.shed_on_duress": None})
        assert rc.ADAPTIVE_ENABLED is True and rc.SHED_ON_DURESS is True
    finally:
        rc.ADAPTIVE_ENABLED = True       # module globals: always restore
        rc.SHED_ON_DURESS = True


def test_adaptive_disabled_keeps_legacy_order(tmp_path):
    """search.replica_selection.adaptive=false reverts _copy_candidates
    to the static local→primary→replicas order, evidence or not."""
    hub = LocalTransport.Hub()
    svc = TransportService("a", LocalTransport(hub))
    node = ClusterNode("a", str(tmp_path / "a"), svc, ["a"])
    try:
        node.response_collector.record_response(
            "c", 300e6, {"service_time_ewma_nanos": 300e6})
        node.response_collector.record_response(
            "b", 1e6, {"service_time_ewma_nanos": 1e6})
        entry = {"primary": "c", "replicas": ["b"],
                 "in_sync": ["c", "b"], "primary_term": 1}
        assert node._copy_candidates(entry) == ["b", "c"]   # ranked
        rc.ADAPTIVE_ENABLED = False
        try:
            assert node._copy_candidates(entry) == ["c", "b"]  # legacy
        finally:
            rc.ADAPTIVE_ENABLED = True   # module global: always restore
    finally:
        node.stop()


# -- monotonic/injectable-clock lint (tier-1 CI hook) ----------------------

def test_check_monotonic_lint_passes_repo():
    out = subprocess.run(
        [sys.executable, TOOLS + "/check_monotonic.py"],
        capture_output=True, text=True)
    assert out.returncode == 0, out.stdout + out.stderr


def test_check_monotonic_strict_clock_rule(tmp_path):
    """cluster/response_collector.py is an injectable-clock module: a
    naked time.monotonic reference fails the lint; the annotated default
    parameter passes."""
    pkg = tmp_path / "cluster"
    pkg.mkdir()
    (pkg / "response_collector.py").write_text(
        "import time\n"
        "def bad():\n"
        "    return time.monotonic()\n"
        "def ok(clock=time.monotonic):  # clock-default\n"
        "    return clock()\n")
    (tmp_path / "other.py").write_text(
        "import time\nt = time.monotonic()\n")   # non-strict module: fine
    out = subprocess.run(
        [sys.executable, TOOLS + "/check_monotonic.py", str(tmp_path)],
        capture_output=True, text=True)
    assert out.returncode == 1
    assert "response_collector.py:3" in out.stdout
    assert "response_collector.py:4" not in out.stdout
    assert "other.py" not in out.stdout
