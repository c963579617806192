"""One query engine + continuous batching (search/engine.py).

Pins the PR's contract:

- every routed caller (single search, msearch, cluster scatter, mesh)
  returns byte-identical results with the continuous batcher on and
  off — coalescing is an execution decision, never a semantics change;
- concurrent identical-shape REST searches actually coalesce into ONE
  shared batch dispatch (counted in search.batcher.*), each caller
  getting its own response, with per-member ``batched`` group size and
  ``queue_wait_ms`` on the insight records and a ``queue`` phase in
  profiled members' breakdowns;
- non-batchable bodies and serial traffic bypass with no window wait;
- the engine's threadpool is bounded and named, and engine shutdown is
  an idempotent bounded join (Node.stop / ClusterNode.stop);
- the insights coalescability report's prediction brackets realized
  batch occupancy on a zipf arrival schedule (the batcher-sizing loop);
- tools/check_execution_paths.py: scoring kernels are only invoked via
  the engine's sanctioned lowering sites.
"""

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from opensearch_tpu.common.telemetry import metrics
from opensearch_tpu.indices.service import IndexService
from opensearch_tpu.search import engine as engine_mod
from opensearch_tpu.search import insights as insights_mod
from opensearch_tpu.search.engine import ContinuousBatcher, query_engine

TOOLS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools")

MAPPING = {"properties": {"body": {"type": "text"},
                          "n": {"type": "long"}}}


@pytest.fixture(autouse=True)
def _restore_engine_globals():
    saved = (engine_mod.BATCHER_ENABLED, engine_mod.BATCHER_WINDOW_MS,
             engine_mod.BATCHER_MAX_BATCH, engine_mod.AUTO_WINDOW_MS)
    yield
    (engine_mod.BATCHER_ENABLED, engine_mod.BATCHER_WINDOW_MS,
     engine_mod.BATCHER_MAX_BATCH, engine_mod.AUTO_WINDOW_MS) = saved


def build_service(tmp_path, name="qe", n_docs=80, seed=5):
    svc = IndexService(name, str(tmp_path / name), {}, MAPPING)
    rng = np.random.default_rng(seed)
    vocab = [f"w{i}" for i in range(20)]
    for i in range(n_docs):
        svc.index_doc(str(i), {
            "body": " ".join(rng.choice(vocab,
                                        size=int(rng.integers(3, 12)))),
            "n": int(rng.integers(0, 50))})
    svc.refresh()
    return svc


def strip_took(resp):
    resp = json.loads(json.dumps(resp))
    resp.pop("took", None)
    resp.pop("profile", None)
    return resp


def run_concurrent(fn, n):
    """Run ``fn(i)`` on n threads released together; returns results in
    index order, re-raising the first worker error.  A tiny GIL switch
    interval makes the threads actually interleave (a warm sub-ms
    search otherwise finishes inside one 5 ms GIL slice and the
    "concurrent" calls cascade serially)."""
    import sys as _sys

    results = [None] * n
    errors = [None] * n
    barrier = threading.Barrier(n)

    def worker(i):
        try:
            barrier.wait()
            results[i] = fn(i)
        except BaseException as e:  # noqa: BLE001
            errors[i] = e

    interval0 = _sys.getswitchinterval()
    _sys.setswitchinterval(0.0002)
    try:
        threads = [threading.Thread(target=worker, args=(i,),
                                    name=f"qe-test-{i}", daemon=True)
                   for i in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30)
    finally:
        _sys.setswitchinterval(interval0)
    for e in errors:
        if e is not None:
            raise e
    return results


def run_until_coalesced(fn, n, attempts=8):
    """Repeat a concurrent round until at least one batch dispatch
    happened (scheduling can legally serialize one round — the batcher
    never waits without live concurrency evidence).  Returns (results,
    batched_delta, dispatch_delta) of the successful round."""
    m = metrics()
    for attempt in range(attempts):
        b0 = m.counter("search.batcher.batched").value
        d0 = m.counter("search.batcher.dispatches").value
        results = run_concurrent(fn, n)
        batched = m.counter("search.batcher.batched").value - b0
        dispatches = m.counter("search.batcher.dispatches").value - d0
        if batched:
            return results, batched, dispatches
    raise AssertionError(
        f"no coalescing in {attempts} concurrent rounds of {n}")


# -- lint -------------------------------------------------------------------

def test_execution_paths_lint_repo_clean():
    r = subprocess.run(
        [sys.executable, os.path.join(TOOLS,
                                      "check_execution_paths.py")],
        capture_output=True, text=True)
    assert r.returncode == 0, r.stdout + r.stderr


def test_execution_paths_lint_catches_rogue_path(tmp_path):
    bad = tmp_path / "rogue.py"
    bad.write_text(
        "from opensearch_tpu.ops import bm25 as bm25_ops\n"
        "def fifth_path(p):\n"
        "    return bm25_ops.impact_scores(*p, n_pad=8, budget=8)\n")
    r = subprocess.run(
        [sys.executable, os.path.join(TOOLS, "check_execution_paths.py"),
         str(tmp_path)], capture_output=True, text=True)
    assert r.returncode == 1
    assert "rogue.py:3" in r.stdout
    # the annotation silences it
    bad.write_text(
        "from opensearch_tpu.ops import bm25 as bm25_ops\n"
        "def fifth_path(p):\n"
        "    return bm25_ops.impact_scores(*p, n_pad=8, budget=8)"
        "  # engine-ok: test\n")
    r = subprocess.run(
        [sys.executable, os.path.join(TOOLS, "check_execution_paths.py"),
         str(tmp_path)], capture_output=True, text=True)
    assert r.returncode == 0


# -- continuous batcher -----------------------------------------------------

def test_concurrent_searches_coalesce_byte_identical(tmp_path):
    """Single-search caller: 8 concurrent identical-shape requests share
    one batch dispatch; every response is byte-identical to the
    sequential (batcher-off) response."""
    svc = build_service(tmp_path)
    body = {"query": {"match": {"body": "w0 w2"}}, "size": 5}

    engine_mod.BATCHER_ENABLED = False
    ref = strip_took(svc.search(dict(body)))
    assert ref["hits"]["hits"]

    engine_mod.BATCHER_ENABLED = True
    engine_mod.BATCHER_WINDOW_MS = 250.0
    m = metrics()
    w0 = m.counter("search.batcher.window_waits").value
    results, batched, dispatches = run_until_coalesced(
        lambda i: svc.search(dict(body)), 8)
    waits = m.counter("search.batcher.window_waits").value - w0
    assert batched >= 2           # real coalescing happened
    assert dispatches >= 1
    assert waits >= 1
    assert batched / dispatches >= 2      # realized occupancy > 1
    for r in results:
        assert strip_took(r) == ref


def test_differing_queries_same_group_byte_identical(tmp_path):
    """Members of one (field, k) group may carry DIFFERENT terms — each
    caller still gets exactly its own sequential-path response."""
    svc = build_service(tmp_path)
    bodies = [{"query": {"match": {"body": f"w{i % 5} w{(i + 3) % 7}"}},
               "size": 4} for i in range(8)]
    engine_mod.BATCHER_ENABLED = False
    refs = [strip_took(svc.search(dict(b))) for b in bodies]
    engine_mod.BATCHER_ENABLED = True
    engine_mod.BATCHER_WINDOW_MS = 250.0
    results = run_concurrent(lambda i: svc.search(dict(bodies[i])), 8)
    for r, ref in zip(results, refs):
        assert strip_took(r) == ref


def test_serial_traffic_never_waits(tmp_path):
    """No concurrent batchable traffic -> no window wait: serial
    batchable requests take the sequential path with zero added
    latency (the bypass contract)."""
    svc = build_service(tmp_path)
    engine_mod.BATCHER_ENABLED = True
    engine_mod.BATCHER_WINDOW_MS = 5000.0    # a wait would be obvious
    m = metrics()
    w0 = m.counter("search.batcher.window_waits").value
    t0 = time.monotonic()
    for _ in range(3):
        svc.search({"query": {"match": {"body": "w1"}}, "size": 3})
    assert time.monotonic() - t0 < 4.0       # nowhere near the window
    assert m.counter("search.batcher.window_waits").value == w0


def test_non_batchable_and_disabled_bypass(tmp_path):
    svc = build_service(tmp_path)
    m = metrics()
    engine_mod.BATCHER_ENABLED = True
    y0 = m.counter("search.batcher.bypass").value
    sorted_body = {"query": {"match": {"body": "w1"}},
                   "sort": [{"n": "asc"}], "size": 3}
    r1 = svc.search(dict(sorted_body))
    assert m.counter("search.batcher.bypass").value == y0 + 1
    engine_mod.BATCHER_ENABLED = False
    y1 = m.counter("search.batcher.bypass").value
    r2 = svc.search(dict(sorted_body))
    # disabled: the batcher is not even consulted
    assert m.counter("search.batcher.bypass").value == y1
    assert strip_took(r1) == strip_took(r2)


def test_msearch_byte_identity_batcher_on_off(tmp_path):
    """msearch caller: batched groups + the threadpool-fanned fallback
    both return exactly the sequential per-body responses, batcher on
    and off."""
    svc = build_service(tmp_path)
    bodies = [
        {"query": {"match": {"body": "w0 w2"}}, "size": 5},
        {"query": {"match": {"body": "w3"}}, "size": 5},
        {"query": {"match": {"body": "w1"}}, "size": 3,
         "sort": [{"n": "asc"}]},                       # fallback
        {"query": {"range": {"n": {"gte": 10}}}, "size": 4,
         "sort": [{"n": "desc"}]},                      # fallback
    ]
    engine_mod.BATCHER_ENABLED = False
    seq = [strip_took(svc.search(dict(b))) for b in bodies]
    for flag in (True, False):
        engine_mod.BATCHER_ENABLED = flag
        out = svc.msearch([dict(b) for b in bodies])
        for got, want in zip(out, seq):
            got = strip_took(got)
            # msearch members never report timed_out=True here and the
            # shards section matches the single-search one
            assert got == want


def test_insights_batched_group_size_and_queue_wait(tmp_path):
    """Satellite: per-member batched_group_size + batcher queue-wait
    reach the insight records and the per-signature rollups."""
    from opensearch_tpu.search.insights import QueryInsightsService

    svc = build_service(tmp_path)
    engine_mod.BATCHER_ENABLED = True
    engine_mod.BATCHER_WINDOW_MS = 250.0
    body = {"query": {"match": {"body": "w0 w2"}}, "size": 5}
    sinks = []
    sink_lock = threading.Lock()

    def run(i):
        with insights_mod.collecting() as sink:
            svc.search(dict(body))
        with sink_lock:
            sinks.append(sink)

    run_until_coalesced(run, 6)
    recs = [s[0] for s in sinks if s]
    batched = [r for r in recs if r.get("batched")]
    assert batched, recs
    assert all(r["batched"] >= 2 for r in batched)
    assert all(r["queue_wait_ms"] >= 0.0 for r in batched)
    assert all(r["execution_path"] in ("host_batched", "device_batched")
               for r in batched)
    svc_ins = QueryInsightsService(node_id="t")
    for r in recs:
        svc_ins.record(dict(r))
    sig = insights_mod.signature_hash(
        insights_mod.canonical_query(body["query"]), True)
    roll = svc_ins.section()["signatures"][sig]
    assert roll["batched_members"] == len(batched)
    assert roll["batched_group_size"]["max"] >= 2
    assert roll["batched_group_size"]["mean"] >= 2
    assert roll["queue_wait_ms"]["max"] >= 0.0


def test_profile_queue_phase_on_batched_members(tmp_path):
    """Profiled members coalesce too: the shared group attribution plus
    each member's OWN queue wait land in the breakdown, and hits stay
    byte-identical."""
    svc = build_service(tmp_path)
    body = {"query": {"match": {"body": "w0 w2"}}, "size": 5}
    engine_mod.BATCHER_ENABLED = False
    ref = strip_took(svc.search(dict(body)))
    engine_mod.BATCHER_ENABLED = True
    engine_mod.BATCHER_WINDOW_MS = 250.0
    results, _batched, _disp = run_until_coalesced(
        lambda i: svc.search(dict(body, profile=True)), 4)
    batched_secs = []
    for r in results:
        assert strip_took(r) == ref
        sec = r["profile"]["shards"][0]
        bd = sec["searches"][0]["query"][0]["breakdown"]
        assert "queue" in bd and "queue_count" in bd
        if sec["engine"].get("batch"):
            batched_secs.append(sec)
    assert batched_secs            # at least one member truly coalesced
    for sec in batched_secs:
        bd = sec["searches"][0]["query"][0]["breakdown"]
        assert bd["queue"] > 0
        assert sec["engine"]["batch"]["queries"] >= 2
        assert sec["engine"]["execution_path"] in ("host_batched",
                                                   "device_batched")


# -- prediction vs realization ----------------------------------------------

def test_coalescability_report_brackets_realized_occupancy():
    """Satellite: the insights coalescability prediction must bracket
    the batcher's realized occupancy on the zipf workload.  The report
    chains arrivals (each within-window successor coalesces), the
    batcher windows from each group LEADER — so the prediction is an
    upper bound, and with bursty zipf arrivals the realization stays
    within a 3x band above 1."""
    from opensearch_tpu.search.insights import QueryInsightsService

    class FakeClock:
        def __init__(self):
            self.t = 1000.0

    clock = FakeClock()
    svc = QueryInsightsService(node_id="t", coalesce_window_ms=10.0,
                               clock=lambda: clock.t,
                               ring_capacity=4096, max_signatures=64)
    rng = np.random.default_rng(7)
    arrivals = []
    # zipf-shaped traffic: hot signatures arrive in tight bursts, cold
    # ones alone — the measured shape the batcher amortizes
    for _ in range(60):
        sig = f"q{min(int(rng.zipf(1.5)), 8)}"
        burst = int(rng.integers(1, 6)) if sig in ("q1", "q2") else 1
        for _ in range(burst):
            clock.t += float(rng.uniform(0.0005, 0.003))
            arrivals.append((clock.t, sig))
            svc.record({"signature": sig, "scored": True,
                        "took_ms": 1.0, "execution_path": "host",
                        "plan_cache": "hit"})
        clock.t += float(rng.uniform(0.05, 0.3))     # inter-burst gap
    report = svc.coalescability()
    assert 0.0 < report["coalescable_fraction"] < 1.0
    # exact chain-rule occupancy from the raw counts (the rendered
    # fraction is rounded to 4 decimals): every coalesced arrival
    # joined its predecessor's chain, so chains = arrivals - coalesced
    predicted = report["arrivals"] / (report["arrivals"]
                                      - report["coalesced"])
    realized = ContinuousBatcher.simulate_occupancy(arrivals, 0.010)
    assert realized >= 1.0
    # leader-window grouping can only SPLIT a chain, never merge two:
    # the report's prediction is a true upper bound...
    assert realized <= predicted + 1e-9
    # ...and on bursty zipf traffic it stays a tight one (brackets)
    assert realized >= 1.0 + (predicted - 1.0) / 3.0


# -- threadpool / shutdown ---------------------------------------------------

def test_threadpool_named_threads_and_idempotent_shutdown():
    eng = query_engine()
    out = eng.pool.run_all([lambda: threading.current_thread().name
                            for _ in range(4)])
    assert all(n.startswith("search-engine-") for n in out)
    t0 = time.monotonic()
    eng.shutdown()
    eng.shutdown()                 # idempotent
    assert time.monotonic() - t0 < 6.0     # bounded join, no hang
    # post-shutdown work respawns workers (process-global pool serves
    # whichever node is still alive)
    out = eng.pool.run_all([lambda: 1 + 1])
    assert out == [2]


def test_node_stop_joins_engine_and_settings_wire(tmp_path):
    from opensearch_tpu.node import Node

    node = Node(str(tmp_path / "n"), port=0)
    try:
        # defaults replayed at construction
        assert engine_mod.BATCHER_ENABLED is True
        assert engine_mod.BATCHER_MAX_BATCH == 64
        node.update_cluster_settings(transient={
            "search.batcher.enabled": False,
            "search.batcher.window_ms": 25.0,
            "search.batcher.max_batch": 8,
            "search.insights.coalesce_window_ms": 7.0})
        assert engine_mod.BATCHER_ENABLED is False
        assert engine_mod.BATCHER_WINDOW_MS == 25.0
        assert engine_mod.BATCHER_MAX_BATCH == 8
        assert engine_mod.AUTO_WINDOW_MS == 7.0
    finally:
        t0 = time.monotonic()
        node.stop()
        node.stop()                # idempotent, no new stop-hang class
        assert time.monotonic() - t0 < 10.0


# -- cluster scatter ---------------------------------------------------------

def wait_until(pred, timeout=10.0):
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:   # deadline-bounded poll
        if pred():
            return True
        time.sleep(0.05)
    return False


def test_cluster_scatter_byte_identity_batcher_on_off(tmp_path):
    """Cluster caller: the data-node query phase routes through the
    engine; scatter responses are byte-identical with the batcher on
    and off (the per-payload searcher never coalesces, by design)."""
    from opensearch_tpu.cluster import response_collector as rc
    from opensearch_tpu.cluster.node import ClusterNode
    from opensearch_tpu.transport.service import (LocalTransport,
                                                  TransportService)

    # pin copy selection: adaptive C3 ranking is stateful (EWMAs move
    # between calls), which legally reorders equal-score ties across
    # runs — this test pins the BATCHER's effect, not selection's
    adaptive0 = rc.ADAPTIVE_ENABLED
    rc.ADAPTIVE_ENABLED = False
    hub = LocalTransport.Hub()
    ids = ["n0", "n1", "n2"]
    nodes = {}
    for nid in ids:
        tsvc = TransportService(nid, LocalTransport(hub))
        n = ClusterNode(nid, str(tmp_path / nid), tsvc, ids)
        n.search_backpressure.trackers["cpu_usage"].probe = lambda: 0.0
        nodes[nid] = n
    try:
        assert nodes["n0"].start_election()
        assert wait_until(lambda: all(
            nodes[i].coordinator.state().master_node == "n0"
            for i in ids))
        nodes["n0"].create_index("sc", {
            "settings": {"number_of_shards": 2, "number_of_replicas": 1},
            "mappings": {"properties": {"t": {"type": "text"}}}})

        def in_sync():
            routing = nodes["n0"].coordinator.state().routing.get(
                "sc", [])
            return routing and all(
                set(e["in_sync"]) == {e["primary"], *e["replicas"]}
                for e in routing)
        assert wait_until(in_sync)
        for i in range(24):
            nodes["n0"].index_doc("sc", str(i),
                                  {"t": f"w{i % 4} common"})
        nodes["n0"].refresh("sc")
        body = {"query": {"match": {"t": "common w1"}}, "size": 6}
        engine_mod.BATCHER_ENABLED = True
        engine_mod.BATCHER_WINDOW_MS = 50.0
        on = strip_took(nodes["n0"].search("sc", dict(body)))
        engine_mod.BATCHER_ENABLED = False
        off = strip_took(nodes["n0"].search("sc", dict(body)))
        assert on == off
        assert on["hits"]["total"]["value"] == 24
        # msearch at cluster scope too
        engine_mod.BATCHER_ENABLED = True
        mon = nodes["n0"].msearch("sc", [dict(body), dict(body)])
        engine_mod.BATCHER_ENABLED = False
        moff = nodes["n0"].msearch("sc", [dict(body), dict(body)])
        assert [strip_took(r) for r in mon["responses"]] \
            == [strip_took(r) for r in moff["responses"]]
    finally:
        rc.ADAPTIVE_ENABLED = adaptive0
        for n in nodes.values():
            n.stop()


# -- mesh caller -------------------------------------------------------------

def test_mesh_routed_caller_byte_identity_batcher_on_off(tmp_path):
    """Mesh caller: an index opted into search.mesh routes through the
    SAME engine entry; the batcher never touches it, so responses are
    identical with the flag on and off (mesh-vs-host score parity is
    pinned in tests/test_dist_search.py)."""
    import jax

    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices")
    svc = IndexService("mesh", str(tmp_path / "mesh"),
                       {"number_of_shards": 2, "search.mesh": True},
                       MAPPING)
    rng = np.random.default_rng(3)
    vocab = [f"w{i}" for i in range(12)]
    for i in range(40):
        svc.index_doc(str(i), {
            "body": " ".join(rng.choice(vocab,
                                        size=int(rng.integers(3, 9)))),
            "n": i})
    svc.refresh()
    body = {"query": {"match": {"body": "w0 w1"}}, "size": 5}
    engine_mod.BATCHER_ENABLED = True
    on = strip_took(svc.search(dict(body)))
    engine_mod.BATCHER_ENABLED = False
    off = strip_took(svc.search(dict(body)))
    assert on == off
    assert on["hits"]["hits"]
