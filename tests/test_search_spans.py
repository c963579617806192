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
from opensearch_tpu.common.telemetry import gc_timer, metrics, tracer
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
# a trace whose spans meter their thread's CPU (one in eight does)
METERED = {"traceparent": "00-" + "ab" * 15 + "a0-" + "cd" * 8 + "-01"}


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
    index = path.split("/")[1]
    before = _stats(node)["device"]
    assert call(node, "POST", path, body)[0] == 200
    after = _stats(node)["device"]
    # the index's own: the node's sum is over the groups still resident,
    # and falls when an earlier test's node is collected
    assert (after["indices"][index]["dispatches"]
            - before["indices"][index]["dispatches"]) == dispatches
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
    assert (after["indices"][TEXT]["dispatches"]
            - before["indices"][TEXT]["dispatches"]) == 2
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


PREPARE_PARTS = {"device", "cache_get", "bind", "cache_put", "arrays"}
EDGE_PARTS = {"read", "route", "after", "respond"}


def test_totals_of_a_bm25_search_and_the_parts_of_its_spans(node):
    """A body no other test sends: both segments miss what is prepared,
    so ``segment.prepare`` shows all five parts; the totals of
    ``_nodes/stats`` move by what the ring's spans say."""
    request = ("/" + TEXT + "/_search",
               {"query": {"match": {"t": "beta w2 alpha"}}, "size": 4})
    _empty_ring()
    before = _stats(node)["telemetry"]
    status, resp = call(node, "POST", *request, headers=METERED)
    assert status == 200 and resp["hits"]["hits"], resp
    both = _finished_spans()             # the first stats read's too
    after = _stats(node)["telemetry"]
    spans = [s for s in both if s["trace_id"] == "ab" * 15 + "a0"]

    def moved(name, key="count"):
        return (after["spans"][name][key]
                - before["spans"].get(name, {}).get(key, 0))

    assert moved("segment.prepare") == 2
    prepares = [s for s in spans if s["name"] == "segment.prepare"]
    assert len(prepares) == 2
    for s in prepares:
        assert set(s["parts"]) == PREPARE_PARTS
        assert sum(s["parts"].values()) <= s["duration_in_nanos"]
        assert 0 <= s["cpu_in_nanos"] <= s["duration_in_nanos"]
    assert set(after["spans"]["segment.prepare"]["parts"]) == PREPARE_PARTS
    # the totals are the spans', to the rounding of a sum of floats
    assert moved("segment.prepare", "time_in_millis") == pytest.approx(
        sum(s["duration_in_nanos"] for s in prepares) / 1e6, abs=1e-6)
    assert after["spans"]["segment.prepare"]["metered_count"] == 2
    # a metered span counts for the eight of its name
    assert after["spans"]["segment.prepare"]["off_cpu_in_millis"] == \
        pytest.approx(8 * sum(s["duration_in_nanos"] - s["cpu_in_nanos"]
                              for s in prepares) / 1e6, abs=1e-6)
    for s in spans:
        if s["name"] == "segment.dispatch":
            assert set(s["parts"]) == {"launch"}
            assert s["parts"]["launch"] <= s["duration_in_nanos"]
            assert "cpu_in_nanos" not in s      # it did not ask
    sync, = [s for s in spans if s["name"] == "device.sync"]
    assert 0 <= sync["cpu_in_nanos"] <= sync["duration_in_nanos"]
    # the REST edge: four parts and the rest: span make up http.request
    root, = [s for s in spans if s["name"] == "http.request"]
    rest, = [s for s in spans if s["name"] == REST]
    assert set(root["parts"]) == EDGE_PARTS
    covered = sum(root["parts"].values()) + rest["duration_in_nanos"]
    assert covered <= root["duration_in_nanos"]
    assert covered >= 0.9 * root["duration_in_nanos"]
    assert 0 <= root["cpu_in_nanos"] <= root["duration_in_nanos"]
    assert root["attributes"]["head_read_ns"] >= 0
    # the tracer's own count: a stats read's two spans end after its
    # body is built, so between the reads lie the first read's and the
    # search's
    assert len(both) == len(spans) + 2
    assert (after["tracer"]["finished"] - before["tracer"]["finished"]
            == len(both))
    assert after["tracer"]["ring"] == 8192


def test_a_prepared_hit_binds_and_puts_nothing(node):
    _search_spans(node, BM25)
    again = _search_spans(node, BM25)
    for s in again:
        if s["name"] == "segment.prepare":
            assert s["attributes"]["prepared"] == "hit"
            assert set(s["parts"]) == {"device", "cache_get", "arrays"}


def test_knn_scans_put_their_launch_on_the_span_they_open(node):
    # without a filter the pre-pass opens no span, and writes into none
    spans = _search_spans(node, knn(11.0))
    assert not [s for s in spans if s["name"] in ("knn.scan", "knn.filter")]
    plan, = [s for s in spans if s["name"] == "query.plan"]
    assert "parts" not in plan
    filtered = ("/" + VECTORS + "/_search",
                {"query": {"knn": {"v": {
                    "vector": [12.0, 4, 1.5, 1], "k": 3,
                    "filter": {"match_all": {}}}}}, "size": 3})
    spans = _search_spans(node, filtered)
    by_name = {s["name"]: s for s in spans}
    for name in ("knn.scan", "knn.filter"):
        assert set(by_name[name]["parts"]) == {"launch"}
        assert by_name[name]["parts"]["launch"] <= \
            by_name[name]["duration_in_nanos"]
    assert "parts" not in by_name["query.plan"]


def test_head_read_is_recorded_once_a_connection(node):
    hist = metrics().histogram("rest.head_read_ms")
    count0 = hist.count
    conn = http.client.HTTPConnection("127.0.0.1", node.port)
    try:
        for _ in range(2):
            assert call(node, "POST", *BM25, conn=conn)[0] == 200
    finally:
        conn.close()
    roots = [s for s in _finished_spans() if s["name"] == "http.request"]
    assert len(roots) == 2
    assert roots[0]["attributes"]["head_read_ns"] >= 0
    assert "head_read_ns" not in roots[1]["attributes"]
    assert hist.count - count0 == 1


def test_nodes_trace_says_what_the_ring_holds(node):
    assert call(node, "POST", *BM25)[0] == 200
    _finished_spans()
    out = next(iter(call(node, "GET", "/_nodes/trace?size=3")[1][
        "nodes"].values()))
    assert len(out["spans"]) == 3 and out["ring"] == 8192
    assert out["finished"] >= 3
    assert out["oldest_start_time_in_nanos"] == min(
        s["start_time_in_nanos"] for s in out["spans"])
    assert out["oldest_start_time_in_nanos"] <= time.monotonic_ns()


def test_metrics_endpoint_shows_the_totals(node):
    assert call(node, "POST", *BM25)[0] == 200
    _finished_spans()
    c = http.client.HTTPConnection("127.0.0.1", node.port)
    try:
        c.request("GET", "/_metrics")
        text = c.getresponse().read().decode()
    finally:
        c.close()
    _sent.append("/_metrics")
    stats = _stats(node)["telemetry"]
    assert 'telemetry_spans_total{span="segment.prepare"} 2' in text
    assert 'telemetry_span_cpu_ms_total{span="http.request"}' in text
    # a sum that a tick of the CPU clock may step back: no counter
    assert "# TYPE telemetry_span_off_cpu_ms gauge" in text
    assert 'telemetry_span_off_cpu_ms{span="http.request"}' in text
    assert 'telemetry_spans_metered_total{span="http.request"}' in text
    assert ('telemetry_span_part_time_ms_total{span="segment.prepare",'
            'part="arrays"}') in text
    assert "rest_head_read_ms_count" in text
    assert "telemetry_tracer_ring 8192" in text
    # both surfaces render the tracer's one set of totals
    assert stats["spans"]["segment.prepare"]["count"] == 2
    assert stats["tracer"]["ring"] == 8192


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
