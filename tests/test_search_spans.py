"""The search path's spans and counters, over REST against an in-process
node with the kernels on the (CPU) device path: the span tree of a BM25
and of a kNN request, the device counters each moves, the REST edge's
accept wait, the collector's pause time, the host CPU counter, and the
spans' mirror in a profiler trace."""

import gc
import http.client
import json
import time

import pytest

from opensearch_tpu.common.device_ledger import device_ledger
from opensearch_tpu.common.telemetry import gc_timer, tracer
from opensearch_tpu.node import Node
from opensearch_tpu.search import engine

TEXT, VECTORS = "spans_text", "spans_vectors"
BM25 = ("/" + TEXT + "/_search",
        {"query": {"match": {"t": "alpha w1"}}, "size": 5})


def knn(x: float) -> tuple:
    """A kNN request; a body seen before is served from the plan cache,
    pre-pass and all, so each test brings an ``x`` of its own."""
    return ("/" + VECTORS + "/_search",
            {"query": {"knn": {"v": {"vector": [x, 4, 1.5, 1], "k": 3}}},
             "size": 3})


TRACEPARENT = "00-" + "ab" * 16 + "-" + "cd" * 8 + "-01"


_sent = []          # the requests answered since the ring was emptied


def call(node, method, path, body=None, headers=None, ndjson=None,
         conn=None):
    """One request; on its own connection unless ``conn`` is given."""
    c = conn or http.client.HTTPConnection("127.0.0.1", node.port)
    headers = dict(headers or {})
    data = None
    if ndjson is not None:
        data = "".join(json.dumps(line) + "\n" for line in ndjson)
        headers["Content-Type"] = "application/x-ndjson"
    elif body is not None:
        data = json.dumps(body)
        headers["Content-Type"] = "application/json"
    c.request(method, path, body=data, headers=headers)
    resp = c.getresponse()
    out = json.loads(resp.read() or b"{}")
    _sent.append(path)
    if conn is None:
        c.close()
    return resp.status, out


def _bulk(node, index, docs):
    lines = []
    for doc_id, source in docs:
        lines += [{"index": {"_index": index, "_id": str(doc_id)}}, source]
    status, resp = call(node, "POST", "/_bulk?refresh=true", ndjson=lines)
    assert status == 200 and not resp["errors"], resp


@pytest.fixture(scope="module")
def node(tmp_path_factory):
    """Two text segments and one vector segment, served over HTTP."""
    tracer().reset()                     # the ring is the process's:
    _sent.clear()                        # other files leave spans in it
    node = Node(str(tmp_path_factory.mktemp("spans")), port=0).start()
    one_shard = {"number_of_shards": 1, "number_of_replicas": 0}
    assert call(node, "PUT", "/" + TEXT, {
        "settings": one_shard,
        "mappings": {"properties": {"t": {"type": "text"}}}})[0] == 200
    for batch in range(2):               # a refresh each: two segments
        _bulk(node, TEXT, [(batch * 20 + i, {"t": f"alpha w{i % 3} beta"})
                           for i in range(20)])
    assert call(node, "PUT", "/" + VECTORS, {
        "settings": one_shard,
        "mappings": {"properties": {"v": {
            "type": "knn_vector", "dimension": 4,
            "method": {"name": "exact", "space_type": "l2"}}}}})[0] == 200
    _bulk(node, VECTORS, [(i, {"v": [i, i + 1, i * 0.5, 1.0]})
                          for i in range(30)])
    for index, want in ((TEXT, 2), (VECTORS, 1)):
        total = call(node, "GET", f"/{index}/_stats")[1]["indices"][index][
            "total"]
        assert total["segments"]["count"] == want
    yield node
    node.stop()
    device_ledger().reset()


@pytest.fixture(autouse=True)
def device_path(monkeypatch):
    """The XLA kernels on the CPU backend, one request a program (what
    the benchmark's cells run), and a clean ring."""
    monkeypatch.setattr(engine, "BATCHER_ENABLED", False)
    _empty_ring()
    yield
    _empty_ring()


def _stats(node):
    return next(iter(call(node, "GET", "/_nodes/stats")[1]["nodes"].values()))


def _finished_spans() -> list:
    """The ring, oldest first, once every request answered since it was
    emptied has ended its ``http.request`` span: that is after the
    response's last byte has left, so the client is ahead of it."""
    deadline = time.monotonic() + 5.0
    while True:
        spans = tracer().recent(8192)[::-1]
        if sum(s["name"] == "http.request" for s in spans) == len(_sent):
            return spans
        assert time.monotonic() < deadline, (len(_sent), spans)
        time.sleep(0.002)


def _empty_ring() -> None:
    _finished_spans()            # no request's root is still to come
    tracer().reset()
    _sent.clear()


def _search_spans(node, request, headers=None):
    """The spans of one search, oldest first."""
    path, body = request
    _empty_ring()
    status, resp = call(node, "POST", path, body, headers)
    assert status == 200 and resp["hits"]["hits"], resp
    return _finished_spans()


def _check_tree(spans, expected):
    """``expected``: [(name, parent name)] in order of ending."""
    by_id = {s["span_id"]: s for s in spans}
    assert len({s["trace_id"] for s in spans}) == 1
    got = [(s["name"], by_id[s["parent_span_id"]]["name"]
            if s["parent_span_id"] in by_id else None) for s in spans]
    assert got == expected
    for s in spans:
        assert isinstance(s["start_time_in_nanos"], int)
        assert isinstance(s["start_time_in_millis"], float)
        parent = by_id.get(s["parent_span_id"])
        if parent is not None:
            start, end = s["start_time_in_nanos"], (
                s["start_time_in_nanos"] + s["duration_in_nanos"])
            assert parent["start_time_in_nanos"] <= start
            assert end <= (parent["start_time_in_nanos"]
                           + parent["duration_in_nanos"])


REST = "rest:indices:data/read/search"
PER_SEGMENT = [("segment.prepare", "segment.dispatch"),
               ("segment.dispatch", "shard.query_phase")]
TAIL = [("device.sync", "shard.query_phase"),
        ("fetch_phase", "shard.query_phase"),
        ("shard.query_phase", REST), (REST, "http.request"),
        ("http.request", None)]


def test_span_tree_of_a_bm25_search_over_two_segments(node):
    spans = _search_spans(node, BM25)
    _check_tree(spans, [("query.plan", "shard.query_phase")]
                + PER_SEGMENT * 2 + TAIL)
    by_name = {s["name"]: s for s in spans}
    assert by_name["device.sync"]["attributes"] == {"site": "topk"}
    assert by_name["segment.prepare"]["attributes"]["prepared"] == "miss"
    root = by_name["http.request"]
    assert root["parent_span_id"] is None
    assert root["attributes"]["http.status"] == 200
    assert root["attributes"]["accept_wait_ns"] >= 0
    # the same body again is served from what was prepared for it
    again = _search_spans(node, BM25)
    assert {s["attributes"]["prepared"] for s in again
            if s["name"] == "segment.prepare"} == {"hit"}


def test_span_tree_of_a_knn_search_over_one_segment(node):
    spans = _search_spans(node, knn(3.0))
    _check_tree(spans, [("device.sync", "query.plan"),
                        ("query.plan", "shard.query_phase")]
                + PER_SEGMENT + TAIL)
    assert [s["attributes"]["site"] for s in spans
            if s["name"] == "device.sync"] == ["knn_prepass", "topk"]
    assert len(spans) <= 10              # the budget of the kNN path


def test_incoming_traceparent_is_honoured_at_http_request(node):
    spans = _search_spans(node, BM25, {"traceparent": TRACEPARENT})
    assert {s["trace_id"] for s in spans} == {"ab" * 16}
    by_name = {s["name"]: s for s in spans}
    assert by_name["http.request"]["parent_span_id"] == "cd" * 8
    assert by_name[REST]["parent_span_id"] == \
        by_name["http.request"]["span_id"]


@pytest.mark.parametrize("request_,dispatches,fetches", [
    (knn(7.0), 2, 2), (BM25, 2, 1)], ids=["knn", "bm25_two_segments"])
def test_device_counters_count_every_program_and_sync(
        node, request_, dispatches, fetches):
    path, body = request_
    before = _stats(node)["device"]
    assert call(node, "POST", path, body)[0] == 200
    after = _stats(node)["device"]
    assert after["dispatches"] - before["dispatches"] == dispatches
    assert (after["transfers"]["fetch"]["ops"]
            - before["transfers"]["fetch"]["ops"]) == fetches
    assert after["health"]["breakers"]["dispatch"]["failures"] == 0


@pytest.mark.parametrize("query,slices", [
    (BM25[1]["query"], 2),
    ({"bool": {"filter": [{"terms": {"t": [f"w{i}" for i in range(200)]}}]}},
     0)], ids=["term_bag", "terms_filter_beyond_the_threshold"])
def test_slice_gather_programs_counts_what_the_kernel_copied(
        node, query, slices):
    """Two segments, a program each: counted when its static shape took
    ``gather_postings``'s slice lowering (256 term slots over a 4,096
    bucket gather element by element)."""
    before = _stats(node)["device"]
    status, resp = call(node, "POST", "/" + TEXT + "/_search",
                        {"query": query, "size": 3})
    assert status == 200 and resp["hits"]["hits"], resp
    after = _stats(node)["device"]
    assert after["dispatches"] - before["dispatches"] == 2
    assert (after["slice_gather_programs"]
            - before["slice_gather_programs"]) == slices


def test_gc_pause_time_moves_across_a_collection(node):
    before = _stats(node)["runtime"]["gc"]
    gc.collect()
    after = _stats(node)["runtime"]["gc"]
    assert after["collection_count"] > before["collection_count"]
    assert (after["collection_time_in_millis"]
            > before["collection_time_in_millis"])
    assert isinstance(after["collection_time_in_millis"], float)
    # one hook a process, however many nodes it starts
    gc_timer().install()
    assert gc.callbacks.count(gc_timer()) == 1


def test_host_cpu_and_accept_wait_move_across_a_search(node):
    def read():
        t = _stats(node)["telemetry"]
        return (t["counters"].get("search.cpu_micros", 0),
                t["histograms"]["rest.accept_wait_ms"]["count"])

    cpu0, waits0 = read()
    assert call(node, "POST", *BM25)[0] == 200
    cpu1, waits1 = read()
    assert cpu1 > cpu0
    # the search's connection and the second read's
    assert waits1 - waits0 == 2
    # another action's task does not count as search CPU
    assert read()[0] == cpu1


def test_accept_wait_is_recorded_once_a_connection(node):
    conn = http.client.HTTPConnection("127.0.0.1", node.port)
    try:
        for _ in range(2):
            assert call(node, "POST", *BM25, conn=conn)[0] == 200
    finally:
        conn.close()
    roots = [s for s in _finished_spans() if s["name"] == "http.request"]
    assert len(roots) == 2
    assert "accept_wait_ns" in roots[0]["attributes"]
    assert "accept_wait_ns" not in roots[1]["attributes"]


def test_spans_are_host_events_of_a_profiler_trace(node, tmp_path):
    import jax

    from benchmarks import trace

    assert call(node, "POST", *BM25)[0] == 200      # compiled before
    jax.profiler.start_trace(str(tmp_path))
    try:
        _search_spans(node, BM25)       # returns once http.request ended
    finally:
        jax.profiler.stop_trace()
    names = {name for plane, lines in trace.load(str(tmp_path))
             if not plane.startswith("/device:")
             for _line, events in lines for name, _s, _d in events}
    assert {"shard.query_phase", "segment.prepare", "device.sync",
            "http.request"} <= names
