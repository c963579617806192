"""Tiered caching subsystem: the weighted-LRU primitive
(common/cache.py), the shard request cache (indices/request_cache.py)
end-to-end over REST and in cluster mode, and the ad-hoc-cache lint.

Acceptance bar (ISSUE 3): a repeated identical ``_search`` with
``request_cache=true`` is served from IndicesRequestCache (hit counter
increments, response byte-identical), a refresh+write invalidates it
(miss, fresh results), and ``_nodes/stats`` + ``POST
/<index>/_cache/clear`` report/reset the stats.
"""

import gc
import json
import os
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import pytest

from opensearch_tpu.common.breakers import CircuitBreakerService
from opensearch_tpu.common.cache import (EVICTED, EXPIRED, EXPLICIT,
                                         REPLACED, Cache, attached_cache,
                                         estimate_weight)
from opensearch_tpu.indices.request_cache import request_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- common/cache.py: the weighted-LRU primitive ---------------------------

def test_cache_hit_miss_and_stats():
    c = Cache("t.basic")
    assert c.get("k") is None
    c.put("k", "v")
    assert c.get("k") == "v"
    s = c.stats()
    assert s["hit_count"] == 1 and s["miss_count"] == 1
    assert s["entries"] == 1 and s["memory_size_in_bytes"] > 0


def test_cache_lru_eviction_by_weight():
    c = Cache("t.lru", max_weight=30, weigher=lambda k, v: 10)
    for k in ("a", "b", "c"):
        c.put(k, k)
    c.get("a")                       # a becomes most-recent
    c.put("d", "d")                  # evicts b (LRU)
    assert c.get("b") is None
    assert c.get("a") == "a" and c.get("c") == "c" and c.get("d") == "d"
    assert c.stats()["evictions"] == 1
    assert c.weight <= 30


def test_cache_oversized_entry_rejected():
    c = Cache("t.oversize", max_weight=10, weigher=lambda k, v: 100)
    assert c.put("k", "v") is False
    assert len(c) == 0 and c.stats()["rejections"] == 1


def test_cache_ttl_expiry_with_injected_clock():
    now = [0.0]
    c = Cache("t.ttl", ttl_s=5.0, clock=lambda: now[0])
    c.put("k", "v")
    assert c.get("k") == "v"
    now[0] = 5.1
    assert c.get("k") is None        # expired counts as a miss
    assert len(c) == 0


def test_cache_removal_listener_reasons():
    seen = []
    c = Cache("t.listener", max_weight=20, weigher=lambda k, v: 10,
              removal_listener=lambda k, v, r: seen.append((k, r)))
    c.put("a", 1)
    c.put("a", 2)                    # REPLACED
    c.put("b", 1)
    c.put("c", 1)                    # evicts a
    c.invalidate("b")                # EXPLICIT
    assert ("a", REPLACED) in seen
    assert ("a", EVICTED) in seen
    assert ("b", EXPLICIT) in seen


def test_cache_ttl_expired_reason():
    now = [0.0]
    seen = []
    c = Cache("t.ttl2", ttl_s=1.0, clock=lambda: now[0],
              removal_listener=lambda k, v, r: seen.append(r))
    c.put("k", "v")
    now[0] = 2.0
    c.get("k")
    assert seen == [EXPIRED]


def test_cache_get_or_load():
    calls = []
    c = Cache("t.load")

    def loader():
        calls.append(1)
        return 42
    assert c.get_or_load("k", loader) == 42
    assert c.get_or_load("k", loader) == 42
    assert len(calls) == 1


def test_cache_breaker_accounting_eviction_and_release():
    svc = CircuitBreakerService({"breaker.request.limit": 100,
                                 "breaker.total.limit": 1000})
    c = Cache("t.breaker", weigher=lambda k, v: 40, breaker=svc.request)
    c.put("a", 1)
    c.put("b", 1)
    assert svc.request.used == 80
    # a third 40b entry would trip the 100b breaker: the cache sheds its
    # own LRU tail instead of failing
    assert c.put("c", 1) is True
    assert svc.request.used == 80 and len(c) == 2
    assert c.get("a") is None        # a was the LRU victim
    c.invalidate_all()
    assert svc.request.used == 0     # reservations fully released


def test_cache_breaker_full_from_elsewhere_skips_caching():
    svc = CircuitBreakerService({"breaker.request.limit": 100,
                                 "breaker.total.limit": 1000})
    svc.request.add_estimate(90, "other-component")
    c = Cache("t.breaker2", weigher=lambda k, v: 40, breaker=svc.request)
    assert c.put("a", 1) is False    # not ours to evict; don't cache
    assert svc.request.used == 90
    svc.request.release(90)


def test_attached_cache_reuses_and_releases_on_owner_death():
    class Owner:
        pass
    svc = CircuitBreakerService({"breaker.request.limit": 1000,
                                 "breaker.total.limit": 2000})
    o = Owner()
    c1 = attached_cache(o, "_x_cache", name="t.attached",
                        weigher=lambda k, v: 50, breaker=svc.request)
    c2 = attached_cache(o, "_x_cache", name="t.attached")
    assert c1 is c2
    c1.put("k", "v")
    assert svc.request.used == 50
    del o, c1, c2
    gc.collect()
    assert svc.request.used == 0     # finalizer released the accounting


def test_estimate_weight_shapes():
    import numpy as np
    assert estimate_weight(b"abcd") == 4
    assert estimate_weight(np.zeros(10, np.int64)) == 80
    assert estimate_weight({"a": 1}) > 8
    assert estimate_weight(None) == 8


def test_cache_invalidate_if_and_resize():
    c = Cache("t.inv", weigher=lambda k, v: 10)
    for i in range(6):
        c.put(i, i)
    assert c.invalidate_if(lambda k, v: k % 2 == 0) == 3
    assert len(c) == 3
    c.set_max_weight(10)             # dynamic shrink evicts immediately
    assert len(c) == 1


class _Tracked:
    """A value whose ``__del__`` records whether its cache's lock is held
    (the free of a device array gives the interpreter lock up: ISSUE 40)."""

    def __init__(self, cache, seen):
        self.cache, self.seen = cache, seen

    def __del__(self):
        self.seen.append(self.cache._lock._is_owned())


def _evict(c, seen):
    c.put("a", _Tracked(c, seen))
    c.put("b", "x")                  # over max_weight: a is the LRU


def _replace(c, seen):
    c.put("a", _Tracked(c, seen))
    c.put("a", "x")


def _expire(c, seen, now):
    c.put("a", _Tracked(c, seen))
    now[0] = 10.0
    assert c.get("a") is None


def _set_max(c, seen):
    c.put("a", _Tracked(c, seen))
    c.set_max_weight(0)


@pytest.mark.parametrize("how", [
    _evict, _replace, _expire,
    lambda c, s: (c.put("a", _Tracked(c, s)), c.invalidate("a")),
    lambda c, s: (c.put("a", _Tracked(c, s)), c.invalidate_all()),
    lambda c, s: (c.put("a", _Tracked(c, s)),
                  c.invalidate_if(lambda k, v: k == "a")),
    _set_max,
], ids=["evicted", "replaced", "expired", "invalidate", "invalidate_all",
        "invalidate_if", "set_max_weight"])
def test_cache_frees_removed_values_after_the_lock(how):
    now, seen = [0.0], []
    c = Cache("t.free_after", max_weight=10, ttl_s=5.0,
              weigher=lambda k, v: 10, clock=lambda: now[0])
    if how is _expire:
        how(c, seen, now)
    else:
        how(c, seen)
    assert seen == [False]           # freed once, and not under the lock
    assert not c._lock._is_owned()


def _lock_waits(name):
    from opensearch_tpu.common.telemetry import metrics
    return metrics().counter(f"cache.{name}.lock_waits").value


def test_cache_lock_waits_counts_a_contended_get():
    c = Cache("t.lock_waits")
    c.put("k", "v")
    c.get("k")
    assert _lock_waits("t.lock_waits") == 0      # nothing contends
    c._lock.acquire()
    t = threading.Thread(target=c.get, args=("k",), name="t-lock-waits",
                         daemon=True)
    try:
        t.start()
        deadline = time.monotonic() + 10.0
        # the lock is ours, so a thread inside the section's __enter__
        # has failed its try or is about to
        while (getattr(sys._current_frames().get(t.ident), "f_code", None)
               is None or sys._current_frames()[t.ident].f_code.co_name
               != "__enter__"):
            assert time.monotonic() < deadline, "get never reached the lock"
            time.sleep(0.001)
    finally:
        c._lock.release()
    t.join(timeout=10.0)
    assert not t.is_alive()
    assert _lock_waits("t.lock_waits") == 1 and c._lock_waits == 1
    assert c.stats()["hit_count"] == 2           # stats() does not show it


def test_cache_counters_exact_under_contention():
    """Sixteen threads, a short switch interval: every count the sections
    defer to after the release still lands once."""
    from opensearch_tpu.common.telemetry import metrics
    svc = CircuitBreakerService({"breaker.request.limit": 10_000,
                                 "breaker.total.limit": 20_000})
    c = Cache("t.contended", max_weight=200, weigher=lambda k, v: 10,
              breaker=svc.request)

    def work(i):
        for j in range(300):
            key = (i * 7 + j) % 40
            if c.get(key) is None:
                c.put(key, [i, j])

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work, args=(i,), daemon=True,
                                    name=f"t-contended-{i}")
                   for i in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60.0)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    s = c.stats()
    assert s["hit_count"] + s["miss_count"] == 16 * 300
    for what, n in (("hits", s["hit_count"]), ("misses", s["miss_count"]),
                    ("evictions", s["evictions"]),
                    ("lock_waits", c._lock_waits)):
        assert metrics().counter(f"cache.t.contended.{what}").value == n
    assert svc.request.used == c.weight <= 200
    assert c._removed == []


def _scripted_sequence():
    now, seen, trail = [0.0], [], []
    svc = CircuitBreakerService({"breaker.request.limit": 100,
                                 "breaker.total.limit": 1000})
    c = Cache("t.scripted", max_weight=70, ttl_s=5.0,
              weigher=lambda k, v: 10 * len(v), clock=lambda: now[0],
              removal_listener=lambda k, v, r: seen.append((k, r)),
              breaker=svc.request)

    def step(out):
        s = c.stats()
        trail.append((out, s["entries"], s["memory_size_in_bytes"],
                      s["hit_count"], s["miss_count"], s["evictions"],
                      s["rejections"], svc.request.used))
    step(c.put("a", "xx"))
    step(c.put("b", "xxx"))
    step(c.get("a"))
    step(c.put("c", "xxxx"))          # over 70: b is the LRU
    step(c.get("b"))
    step(c.put("a", "x"))             # replaced
    svc.request.add_estimate(40, "other")
    step(c.put("d", "xxx"))           # the breaker sheds c
    svc.request.release(40)
    step(c.put("e", "x" * 8))         # over max_weight: rejected
    now[0] = 3.0
    step(c.put("f", "xx"))
    now[0] = 5.5
    step(c.get("a"))                  # expired
    step(c.get("f"))
    step(c.invalidate("d"))
    step(c.put("g", "x"))
    step(c.put("h", "xx"))
    step(c.invalidate_if(lambda k, v: len(v) == 1))
    step(c.set_max_weight(10))
    step(c.put("i", "x"))
    step(c.invalidate_all())
    return trail, seen


def test_cache_scripted_sequence_matches_the_parent():
    """What ``Cache`` gave before ISSUE 40 over one scripted sequence:
    (return, entries, bytes, hits, misses, evictions, rejections, the
    breaker's ``used``) after each step, and the listener's reasons."""
    from opensearch_tpu.common.telemetry import metrics
    trail, seen = _scripted_sequence()
    assert trail == [
        (True, 1, 20, 0, 0, 0, 0, 20), (True, 2, 50, 0, 0, 0, 0, 50),
        ("xx", 2, 50, 1, 0, 0, 0, 50), (True, 2, 60, 1, 0, 1, 0, 60),
        (None, 2, 60, 1, 1, 1, 0, 60), (True, 2, 50, 1, 1, 1, 0, 50),
        (True, 2, 40, 1, 1, 2, 0, 80), (False, 2, 40, 1, 1, 2, 1, 40),
        (True, 3, 60, 1, 1, 2, 1, 60), (None, 2, 50, 1, 2, 2, 1, 50),
        ("xx", 2, 50, 2, 2, 2, 1, 50), (None, 1, 20, 2, 2, 2, 1, 20),
        (True, 2, 30, 2, 2, 2, 1, 30), (True, 3, 50, 2, 2, 2, 1, 50),
        (1, 2, 40, 2, 2, 2, 1, 40), (None, 0, 0, 2, 2, 4, 1, 0),
        (True, 1, 10, 2, 2, 4, 1, 10), (None, 0, 0, 2, 2, 4, 1, 0)]
    assert seen == [("b", EVICTED), ("a", REPLACED), ("c", EVICTED),
                    ("a", EXPIRED), ("d", EXPLICIT), ("g", EXPLICIT),
                    ("f", EVICTED), ("h", EVICTED), ("i", EXPLICIT)]
    counters = {w: metrics().counter(f"cache.t.scripted.{w}").value
                for w in ("hits", "misses", "evictions", "lock_waits")}
    assert counters == {"hits": 2, "misses": 2, "evictions": 4,
                        "lock_waits": 0}


# -- REST end-to-end -------------------------------------------------------

@pytest.fixture(scope="module")
def node(tmp_path_factory):
    from opensearch_tpu.node import Node
    n = Node(str(tmp_path_factory.mktemp("rcnode")), port=0).start()
    yield n
    n.stop()


def call(node, method, path, body=None, raw=False):
    url = f"http://127.0.0.1:{node.port}{path}"
    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(
        url, data=data, method=method,
        headers={"Content-Type": "application/json"} if data else {})
    try:
        with urllib.request.urlopen(req) as resp:
            payload = resp.read()
    except urllib.error.HTTPError as e:
        payload = e.read()
        return e.code, (payload if raw else json.loads(payload))
    return 200, (payload if raw else
                 json.loads(payload) if payload else {})


@pytest.fixture(scope="module")
def books(node):
    call(node, "PUT", "/rcbooks", {
        "settings": {"number_of_shards": 1},
        "mappings": {"properties": {"t": {"type": "text"},
                                    "n": {"type": "long"}}}})
    for i in range(8):
        call(node, "PUT", f"/rcbooks/_doc/{i}",
             {"t": f"caching is fast {i}", "n": i})
    call(node, "POST", "/rcbooks/_refresh")
    return "rcbooks"


def _node_rc_stats(node):
    _, body = call(node, "GET", "/_nodes/stats")
    nid = next(iter(body["nodes"]))
    return body["nodes"][nid]["indices"]["request_cache"]


def test_request_cache_hit_is_byte_identical(node, books):
    before = _node_rc_stats(node)
    q = {"query": {"match": {"t": "caching"}}, "size": 5}
    s1, raw1 = call(node, "POST",
                    f"/{books}/_search?request_cache=true", q, raw=True)
    s2, raw2 = call(node, "POST",
                    f"/{books}/_search?request_cache=true", q, raw=True)
    assert s1 == 200 and s2 == 200
    assert raw1 == raw2              # byte-identical, took included
    after = _node_rc_stats(node)
    assert after["hit_count"] == before["hit_count"] + 1
    assert after["miss_count"] == before["miss_count"] + 1
    assert after["memory_size_in_bytes"] > 0


def test_refresh_and_write_invalidate(node, books):
    q = {"query": {"match": {"t": "caching"}}, "size": 20}
    _, r1 = call(node, "POST",
                 f"/{books}/_search?request_cache=true", q)
    before = _node_rc_stats(node)
    call(node, "PUT", f"/{books}/_doc/new1",
         {"t": "caching brand new", "n": 100})
    call(node, "POST", f"/{books}/_refresh")
    _, r2 = call(node, "POST",
                 f"/{books}/_search?request_cache=true", q)
    after = _node_rc_stats(node)
    assert after["miss_count"] == before["miss_count"] + 1   # no stale hit
    assert r2["hits"]["total"]["value"] == \
        r1["hits"]["total"]["value"] + 1                      # fresh data


def test_request_cache_param_must_be_boolean(node, books):
    status, body = call(node, "POST",
                        f"/{books}/_search?request_cache=banana",
                        {"query": {"match_all": {}}})
    assert status == 400
    assert "request_cache" in json.dumps(body)


def test_request_cache_false_and_scroll_rejection(node, books):
    before = _node_rc_stats(node)
    q = {"query": {"term": {"n": 3}}, "size": 0}
    # explicit false wins over the default size=0 caching
    call(node, "POST", f"/{books}/_search?request_cache=false", q)
    call(node, "POST", f"/{books}/_search?request_cache=false", q)
    after = _node_rc_stats(node)
    assert after["hit_count"] == before["hit_count"]
    assert after["miss_count"] == before["miss_count"]
    status, _ = call(
        node, "POST",
        f"/{books}/_search?scroll=1m&request_cache=true",
        {"query": {"match_all": {}}})
    assert status == 400


def test_default_caches_only_size0(node, books):
    before = _node_rc_stats(node)
    q = {"query": {"match": {"t": "fast"}}, "size": 3}
    call(node, "POST", f"/{books}/_search", q)
    call(node, "POST", f"/{books}/_search", q)
    mid = _node_rc_stats(node)
    assert mid["hit_count"] == before["hit_count"]      # size>0: no cache
    q0 = {"query": {"match": {"t": "fast"}}, "size": 0}
    call(node, "POST", f"/{books}/_search", q0)
    call(node, "POST", f"/{books}/_search", q0)
    after = _node_rc_stats(node)
    assert after["hit_count"] == mid["hit_count"] + 1   # size=0: cached


def test_index_setting_disables_default_caching(node):
    call(node, "PUT", "/rcoff", {
        "settings": {"number_of_shards": 1,
                     "index": {"requests": {"cache": {"enable": False}}}},
        "mappings": {"properties": {"t": {"type": "text"}}}})
    call(node, "PUT", "/rcoff/_doc/1", {"t": "hello"})
    call(node, "POST", "/rcoff/_refresh")
    before = _node_rc_stats(node)
    q = {"query": {"match_all": {}}, "size": 0}
    call(node, "POST", "/rcoff/_search", q)
    call(node, "POST", "/rcoff/_search", q)
    mid = _node_rc_stats(node)
    assert mid["hit_count"] == before["hit_count"]      # setting: off
    # the explicit request-level param overrides the index setting
    call(node, "POST", "/rcoff/_search?request_cache=true", q)
    call(node, "POST", "/rcoff/_search?request_cache=true", q)
    after = _node_rc_stats(node)
    assert after["hit_count"] == mid["hit_count"] + 1


def test_eviction_under_cache_size_setting(node, books):
    _, r = call(node, "PUT", "/_cluster/settings",
                {"transient": {"indices.requests.cache.size": 2048}})
    assert r["acknowledged"]
    try:
        for i in range(12):
            call(node, "POST",
                 f"/{books}/_search?request_cache=true",
                 {"query": {"term": {"n": i}}, "size": 2})
        stats = _node_rc_stats(node)
        assert stats["memory_size_in_bytes"] <= 2048
        assert stats["evictions"] > 0
    finally:
        call(node, "PUT", "/_cluster/settings",
             {"transient": {"indices.requests.cache.size": None}})


def test_cache_clear_endpoint_resets(node, books):
    q = {"query": {"match": {"t": "caching"}}, "size": 4}
    call(node, "POST", f"/{books}/_search?request_cache=true", q)
    call(node, "POST", f"/{books}/_search?request_cache=true", q)
    _, st = call(node, "GET", f"/{books}/_stats")
    rc = st["indices"][books]["primaries"]["request_cache"]
    assert rc["entries"] > 0 and rc["memory_size_in_bytes"] > 0
    assert rc["hit_count"] > 0
    # ?request=false leaves the request cache alone
    status, _ = call(node, "POST",
                     f"/{books}/_cache/clear?request=false")
    assert status == 200
    _, st = call(node, "GET", f"/{books}/_stats")
    assert st["indices"][books]["primaries"]["request_cache"][
        "entries"] == rc["entries"]
    status, body = call(node, "POST",
                        f"/{books}/_cache/clear?request=true")
    assert status == 200 and body["_shards"]["failed"] == 0
    _, st = call(node, "GET", f"/{books}/_stats")
    rc2 = st["indices"][books]["primaries"]["request_cache"]
    assert rc2["entries"] == 0 and rc2["memory_size_in_bytes"] == 0
    assert rc2["hit_count"] == 0     # counters reset with the entries


# -- cluster mode: the data-node cache behind the scatter-gather -----------

def wait_until(pred, timeout=8.0):
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
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
        nodes[nid] = ClusterNode(nid, str(tmp_path / nid), svc, ids)
    assert nodes["n0"].start_election()
    wait_until(lambda: all(
        nodes[i].coordinator.state().master_node == "n0" for i in ids))
    yield hub, ids, nodes
    for n in nodes.values():
        n.stop()


def test_cluster_mode_hit_counted_on_data_node(cluster):
    """A remote coordinator's repeated query phase is served from the
    DATA node's request cache: the hit counter increments and the shard
    does NOT re-execute (search.queries execution counter is flat)."""
    from opensearch_tpu.common.telemetry import metrics
    hub, ids, nodes = cluster
    nodes["n0"].create_index("rc", {
        "settings": {"number_of_shards": 1, "number_of_replicas": 0},
        "mappings": {"properties": {"v": {"type": "long"}}}})
    wait_until(lambda: all(
        "rc" in nodes[i].coordinator.state().indices for i in ids))
    primary = nodes["n0"].coordinator.state().routing["rc"][0]["primary"]
    coord = next(i for i in ids if i != primary)
    wait_until(lambda: "rc" in nodes[primary].indices)
    for i in range(10):
        nodes[coord].index_doc("rc", str(i), {"v": i})
    nodes[coord].refresh("rc")

    body = {"query": {"range": {"v": {"gte": 2}}}, "size": 5,
            "request_cache": True}
    before = request_cache().stats()
    r1 = nodes[coord].search("rc", dict(body))
    mid = request_cache().stats()
    assert mid["miss_count"] == before["miss_count"] + 1
    executed = metrics().counter("search.queries").value
    r2 = nodes[coord].search("rc", dict(body))
    after = request_cache().stats()
    assert after["hit_count"] == mid["hit_count"] + 1
    # the cached hit avoided a full shard re-execution on the data node
    assert metrics().counter("search.queries").value == executed
    assert json.dumps(r1, sort_keys=True) == json.dumps(r2,
                                                        sort_keys=True)


def test_cluster_failover_recomputes_then_caches(cluster):
    """Fault-injection: dropping the primary's query-phase RPC fails the
    request over to the in-sync replica, whose OWN cache takes the miss
    and serves the follow-up hit — cached results never cross copies."""
    from opensearch_tpu.cluster.node import A_SEARCH_SHARDS
    from opensearch_tpu.cluster.state import copies_of
    from opensearch_tpu.testing.fault_injection import FaultInjector
    hub, ids, nodes = cluster
    nodes["n0"].create_index("ha", {
        "settings": {"number_of_shards": 1, "number_of_replicas": 1},
        "mappings": {"properties": {"v": {"type": "long"}}}})

    def in_sync_full():
        routing = nodes["n0"].coordinator.state().routing.get("ha", [])
        return routing and all(
            set(e["in_sync"]) == {e["primary"], *e["replicas"]}
            and len(e["replicas"]) >= 1 for e in routing)
    assert wait_until(in_sync_full)
    for i in range(12):
        nodes["n0"].index_doc("ha", str(i), {"v": i})
    nodes["n0"].refresh("ha")

    entry = nodes["n0"].coordinator.state().routing["ha"][0]
    primary = entry["primary"]
    coord = next(i for i in ids if i not in copies_of(entry))

    body = {"query": {"match_all": {}}, "size": 20,
            "request_cache": True}
    r1 = nodes[coord].search("ha", dict(body))     # primes the PRIMARY
    assert r1["hits"]["total"]["value"] == 12

    stats_before = request_cache().stats()
    FaultInjector(hub, seed=7).drop(A_SEARCH_SHARDS, target=primary,
                                    times=1)
    r2 = nodes[coord].search("ha", dict(body))     # replica recomputes
    assert r2["hits"]["total"]["value"] == 12
    assert r2["_shards"]["failed"] == 0            # failover, not failure
    stats_mid = request_cache().stats()
    assert stats_mid["miss_count"] == stats_before["miss_count"] + 1

    r3 = nodes[coord].search("ha", dict(body))     # now a hit (primary)
    stats_after = request_cache().stats()
    assert stats_after["hit_count"] == stats_mid["hit_count"] + 1
    assert json.dumps(r2["hits"], sort_keys=True) == \
        json.dumps(r3["hits"], sort_keys=True)


# -- tools/check_ad_hoc_caches.py lint -------------------------------------

def test_check_ad_hoc_caches_lint_passes():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools",
                                      "check_ad_hoc_caches.py")],
        capture_output=True, text=True)
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_check_ad_hoc_caches_lint_catches_violations(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text(
        "class X:\n"
        "    def f(self):\n"
        "        self._term_cache = {}\n"          # attribute dict
        "GLOBAL_RESULT_CACHE = dict()\n"           # module-level ctor
        "class Y:\n"
        "    def g(self):\n"
        "        # bounded-cache: one entry per shard\n"
        "        self._ok_cache = {}\n")            # annotated: allowed
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools",
                                      "check_ad_hoc_caches.py"),
         str(tmp_path)],
        capture_output=True, text=True)
    assert proc.returncode == 1
    assert "bad.py:3" in proc.stdout
    assert "GLOBAL_RESULT_CACHE" in proc.stdout
    assert "_ok_cache" not in proc.stdout
