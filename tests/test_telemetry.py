"""Telemetry spine: Tracer/MetricsRegistry SPI, end-to-end trace
propagation over LocalTransport in cluster mode, slow logs with dynamic
thresholds, timeout budgets with partial-results flagging, X-Opaque-Id
task attribution, and the _nodes/stats | _nodes/trace surfaces."""

import json
import logging
import subprocess
import sys
import time

import pytest

from opensearch_tpu.common.telemetry import (
    MetricsRegistry,
    SpanContext,
    Tracer,
    metrics,
    tracer,
)


@pytest.fixture(autouse=True)
def _clean_telemetry():
    from opensearch_tpu.indices import service as indices_mod
    tracer().reset()
    yield
    tracer().reset()
    indices_mod.SLOWLOG_DEFAULTS.clear()


# -- tracer SPI -----------------------------------------------------------

def test_span_nesting_and_trace_ids():
    t = Tracer()
    with t.start_span("outer", {"a": 1}) as outer:
        assert t.current() is outer
        with t.start_span("inner") as inner:
            assert inner.trace_id == outer.trace_id
            assert inner.parent_span_id == outer.span_id
    assert t.current() is None
    spans = t.recent()
    assert [s["name"] for s in spans] == ["outer", "inner"]
    assert spans[0]["duration_in_nanos"] >= 0
    assert spans[0]["attributes"] == {"a": 1}


def test_traceparent_roundtrip_and_extract():
    t = Tracer()
    with t.start_span("root") as root:
        hdrs = t.inject({})
        assert hdrs["traceparent"] == \
            f"00-{root.trace_id}-{root.span_id}-01"
    ctx = Tracer.extract(hdrs)
    assert ctx.trace_id == root.trace_id
    assert ctx.span_id == root.span_id
    # HTTP headers arrive with arbitrary casing
    assert Tracer.extract({"Traceparent": hdrs["traceparent"]}) is not None
    # malformed values are ignored, never raise
    assert Tracer.extract({"traceparent": "junk"}) is None
    assert Tracer.extract({"traceparent": "00-zz-bad-01"}) is None
    assert SpanContext.from_traceparent(None) is None


def test_explicit_parent_overrides_ambient():
    t = Tracer()
    remote = SpanContext("ab" * 16, "cd" * 8)
    with t.start_span("local-root"):
        with t.start_span("joined", parent=remote) as s:
            assert s.trace_id == remote.trace_id
            assert s.parent_span_id == remote.span_id


def test_span_buffer_is_bounded():
    t = Tracer(max_spans=10)
    for i in range(50):
        with t.start_span(f"s{i}"):
            pass
    spans = t.recent(limit=100)
    assert len(spans) == 10
    assert spans[0]["name"] == "s49"       # newest first


def test_default_ring_holds_8192_spans_newest_first():
    t = Tracer()
    for i in range(8200):
        t.begin_span(f"s{i}").end()
    spans = t.recent(limit=10000)
    assert len(spans) == 8192
    assert spans[0]["name"] == "s8199" and spans[-1]["name"] == "s8"
    assert [s["name"] for s in t.recent(limit=2)] == ["s8199", "s8198"]
    assert len(tracer().recent(limit=0)) == 0
    assert tracer()._finished.maxlen == 8192


def test_span_clocks_and_ids():
    """One reading of each clock at the start: the monotonic one in
    nanoseconds (what durations and ordering come from), the wall one in
    milliseconds with its fraction kept; ids at the W3C widths."""
    t = Tracer()
    mono0, wall0 = time.monotonic_ns(), time.time() * 1e3  # wall-clock
    with t.start_span("outer") as outer:
        with t.start_span("inner"):
            time.sleep(0.002)
    mono1, wall1 = time.monotonic_ns(), time.time() * 1e3  # wall-clock
    outer_d, inner = t.recent()          # newest (last ended) first
    for d in (inner, outer_d):
        assert isinstance(d["start_time_in_nanos"], int)
        assert mono0 <= d["start_time_in_nanos"] <= mono1
        assert isinstance(d["start_time_in_millis"], float)
        assert wall0 - 1 <= d["start_time_in_millis"] <= wall1 + 1
        assert isinstance(d["duration_in_nanos"], int)
        assert len(d["trace_id"]) == 32 and len(d["span_id"]) == 16
        int(d["trace_id"], 16), int(d["span_id"], 16)
    assert inner["duration_in_nanos"] >= 2_000_000
    assert outer_d["start_time_in_nanos"] <= inner["start_time_in_nanos"]
    assert (inner["start_time_in_nanos"] + inner["duration_in_nanos"]
            <= outer_d["start_time_in_nanos"] + outer_d["duration_in_nanos"])
    assert SpanContext.from_traceparent(
        outer.context().to_traceparent()).span_id == outer.span_id
    # read-out builds the dict: a reader's edits do not reach the ring
    inner["attributes"]["x"] = 1
    assert "x" not in t.recent()[1]["attributes"]


def test_a_span_may_end_on_another_thread():
    import threading
    t = Tracer()
    span = t.begin_span("handed.over", {"a": 1})
    assert t.current() is None           # begin_span makes nothing current
    worker = threading.Thread(target=span.end)
    worker.start()
    worker.join()
    span.end()                           # idempotent
    assert [s["name"] for s in t.recent()] == ["handed.over"]


def test_spans_end_on_many_threads_while_the_ring_is_read():
    """Ending a span takes no lock (a deque's append is thread-safe) and a
    read-out copies the ring while spans keep ending: no span is lost and
    no read fails."""
    import threading
    t = Tracer(max_spans=100_000)
    workers, each = 12, 800       # more threads than cores
    stop = threading.Event()
    reads = []

    def end_spans(w):
        for i in range(each):
            with t.start_span(f"w{w}", {"i": i}):
                pass

    def read_ring():
        while not stop.is_set():
            reads.append(len(t.recent(limit=100_000)))

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        reader = threading.Thread(target=read_ring)
        reader.start()
        threads = [threading.Thread(target=end_spans, args=(w,))
                   for w in range(workers)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=60)
        stop.set()
        reader.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not reader.is_alive() and not any(th.is_alive() for th in threads)
    spans = t.recent(limit=100_000)
    assert len(spans) == workers * each
    assert reads and reads == sorted(reads)       # the ring only grew
    per_worker = {}
    for s in spans:
        per_worker.setdefault(s["name"], []).append(s["attributes"]["i"])
    assert all(sorted(v) == list(range(each)) for v in per_worker.values())
    assert len({s["span_id"] for s in spans}) == len(spans)


# a trace whose ``cpu=True`` spans meter (one in eight does, by the id's
# last digit), and one whose do not
METERED = SpanContext("ab" * 15 + "a0", "cd" * 8)
UNMETERED = SpanContext("ab" * 16, "cd" * 8)


def test_span_meters_its_threads_cpu_beside_its_wall_time():
    """A span that spins is all CPU (never more than its duration, on
    this host's exact clock); one that sleeps is all waiting: duration
    less CPU is the time the thread did not run."""
    t = Tracer()
    with t.start_span("spins", parent=METERED, cpu=True):
        until = time.monotonic_ns() + 30_000_000
        while time.monotonic_ns() < until:
            pass
    with t.start_span("sleeps", parent=METERED, cpu=True):
        time.sleep(0.03)
    # the clock is a system call: only a span that asks reads it, and
    # only in one trace of eight
    with t.start_span("did.not.ask", parent=METERED):
        pass
    with t.start_span("other.trace", parent=UNMETERED, cpu=True):
        pass
    other, plain, sleeps, spins = t.recent()
    assert "cpu_in_nanos" not in plain and "cpu_in_nanos" not in other
    from opensearch_tpu.common.telemetry import CPU_METERED, CPU_WEIGHT
    assert len(set(CPU_METERED)) * CPU_WEIGHT == 16 and CPU_WEIGHT == 8
    assert 0 < spins["cpu_in_nanos"] <= spins["duration_in_nanos"]
    # another thread may have held this core for part of the spin
    assert spins["cpu_in_nanos"] >= spins["duration_in_nanos"] // 4
    assert sleeps["duration_in_nanos"] >= 30_000_000
    assert 0 <= sleeps["cpu_in_nanos"] < 5_000_000
    totals = t.totals()
    # a metered span stands for the eight of its name
    assert totals["sleeps"]["off_cpu_in_millis"] == pytest.approx(
        8 * (sleeps["duration_in_nanos"] - sleeps["cpu_in_nanos"]) / 1e6)
    assert totals["spins"]["cpu_in_millis"] == pytest.approx(
        8 * spins["cpu_in_nanos"] / 1e6)
    assert totals["spins"]["metered_count"] == 1
    assert totals["other.trace"]["metered_count"] == 0
    assert totals["other.trace"]["off_cpu_in_millis"] == 0


def test_totals_weight_each_metered_span_where_it_is_folded():
    """One trace in eight meters, and its span counts for eight: the CPU
    totals are sums, taken where a span ends, so they only grow (on an
    exact clock) and a delta holds nothing of the spans before it."""
    t = Tracer()
    with t.start_span("waits", parent=METERED, cpu=True):
        until = time.monotonic_ns() + 10_000_000
        while time.monotonic_ns() < until:
            pass
    spun = t.totals()["waits"]
    seen = [spun]
    for ctx in (UNMETERED, METERED, UNMETERED):
        with t.start_span("waits", parent=ctx, cpu=True):
            time.sleep(0.01)
        seen.append(t.totals()["waits"])
    got = seen[-1]
    assert got["count"] == 4 and got["metered_count"] == 2
    assert got["time_in_millis"] >= 40
    for key in ("cpu_in_millis", "off_cpu_in_millis", "time_in_millis"):
        assert [a[key] for a in seen] == sorted(a[key] for a in seen)
    # an unmetered span moves neither
    assert seen[1]["cpu_in_millis"] == spun["cpu_in_millis"]
    assert seen[1]["off_cpu_in_millis"] == spun["off_cpu_in_millis"]
    # the window of the three sleeps reads as waiting, whatever share of
    # the spin before it was CPU: 8 x one sleep of >= 10 ms
    assert got["off_cpu_in_millis"] - spun["off_cpu_in_millis"] >= 8 * 9.0
    assert got["cpu_in_millis"] - spun["cpu_in_millis"] < 8 * 2.0
    assert spun["cpu_in_millis"] >= 8 * 2.5     # another thread may hold the core


def test_every_eighth_trace_that_starts_here_meters():
    """A new trace's id takes its last digit in turn, so the sample is
    every eighth trace and not one in eight by luck; an id that came with
    the request decides for itself."""
    t = Tracer()
    for _ in range(64):
        with t.start_span("root", cpu=True):
            with t.start_span("child", cpu=True):
                pass
    spans = t.recent(limit=1000)
    ids = {s["trace_id"] for s in spans}
    assert len(ids) == 64 and all(len(i) == 32 for i in ids)
    assert sorted(i[-1] for i in ids) == sorted("0123456789abcdef" * 4)
    for name in ("root", "child"):      # a trace's spans meter together
        assert sum("cpu_in_nanos" in s for s in spans
                   if s["name"] == name) == 8
    assert t.totals()["root"]["metered_count"] == 8
    with t.start_span("given", parent=UNMETERED, cpu=True):
        pass
    assert t.totals()["given"]["metered_count"] == 0


def test_a_span_ended_on_another_thread_meters_no_cpu():
    import threading
    t = Tracer()
    span = t.begin_span("handed.over", parent=METERED, cpu=True)
    worker = threading.Thread(target=span.end)
    worker.start()
    worker.join(timeout=10)
    assert not worker.is_alive()
    t.begin_span("stayed", parent=METERED, cpu=True).end()
    stayed, handed = t.recent()
    assert "cpu_in_nanos" not in handed and span.cpu_nanos is None
    assert stayed["cpu_in_nanos"] >= 0
    # the CPU totals are over the spans that metered it, so a span that
    # could not does not read as time off the CPU
    totals = t.totals()
    assert totals["handed.over"]["count"] == 1
    assert totals["handed.over"]["time_in_millis"] > 0
    assert totals["handed.over"]["cpu_in_millis"] == 0
    assert totals["handed.over"]["off_cpu_in_millis"] == 0


def test_parts_split_a_span_and_sum_to_no_more_than_it():
    t = Tracer()
    with t.start_span("whole") as span:
        with span.part("a"):
            time.sleep(0.002)
        with span.part("b"):
            pass
        with span.part("a"):             # a part adds up
            time.sleep(0.001)
        span.add_part("c", 5)
    with t.start_span("plain"):
        pass
    plain, whole = t.recent()
    assert "parts" not in plain
    assert set(whole["parts"]) == {"a", "b", "c"}
    assert whole["parts"]["a"] >= 3_000_000 and whole["parts"]["c"] == 5
    assert sum(whole["parts"].values()) <= whole["duration_in_nanos"]
    totals = t.totals()
    assert "parts" not in totals["plain"]
    assert totals["whole"]["parts"]["a"]["time_in_millis"] == \
        pytest.approx(whole["parts"]["a"] / 1e6)
    assert sum(p["time_in_millis"] for p in
               totals["whole"]["parts"].values()) <= \
        totals["whole"]["time_in_millis"]


def test_totals_are_exact_under_sixteen_threads_while_they_are_read():
    """Spans end on every request thread: each name's totals are exact
    (a lock a name), and a reader sees them only grow."""
    import threading
    t = Tracer(max_spans=64)             # the ring forgets; totals do not
    workers, each = 16, 10_000
    stop = threading.Event()
    reads = []

    def end_spans(w):
        name = f"n{w % 4}"               # four threads a name
        for _ in range(each):
            with t.start_span(name, parent=METERED, cpu=True) as span:
                span.add_part("p", 3)

    def read_totals():
        while not stop.is_set():
            reads.append((t.stats()["finished"],
                          sum(v["count"] for v in t.totals().values())))

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        reader = threading.Thread(target=read_totals)
        reader.start()
        threads = [threading.Thread(target=end_spans, args=(w,))
                   for w in range(workers)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=120)
        stop.set()
        reader.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not reader.is_alive() and not any(th.is_alive() for th in threads)
    totals = t.totals()
    assert sorted(totals) == ["n0", "n1", "n2", "n3"]
    for v in totals.values():
        assert v["count"] == 4 * each
        assert v["parts"]["p"]["time_in_millis"] == pytest.approx(
            4 * each * 3 / 1e6, rel=1e-12)
        assert v["metered_count"] == 4 * each
        assert 0 <= v["cpu_in_millis"] <= 8 * v["time_in_millis"]
        assert v["cpu_in_millis"] + v["off_cpu_in_millis"] == \
            pytest.approx(8 * v["time_in_millis"], rel=1e-9)
    assert t.stats() == {"finished": workers * each, "ring": 64}
    assert reads and reads == sorted(reads)
    assert len(t.recent(limit=1000)) == 64
    t.reset()
    assert t.totals() == {} and t.stats()["finished"] == 0


def test_the_ring_read_out_loses_a_busy_windows_oldest_spans():
    """What `_nodes/trace?size=4096` can hold of a window: a closed cell
    ends some 2,000 spans a second, so the 4,096 newest are the last two
    seconds of a traced four.  The totals and the tracer's own count
    still account for every span, and the read-out's oldest start tells
    a reader that its window began before what it was given."""
    t = Tracer()
    window_start = time.monotonic_ns()
    total = 6000
    for i in range(total):
        with t.start_span("early" if i < 1500 else "late"):
            pass
    read = t.recent(4096)                # what Served.spans reads
    assert len(read) == 4096
    assert {s["name"] for s in read} == {"late"}      # no early span left
    oldest = min(s["start_time_in_nanos"] for s in read)
    assert oldest > window_start          # the window began before it ...
    assert t.stats() == {"finished": total, "ring": 8192}
    assert t.stats()["finished"] > len(read)          # ... and says so
    totals = t.totals()
    assert totals["early"]["count"] == 1500
    assert totals["late"]["count"] == total - 1500
    assert totals["early"]["time_in_millis"] > 0


def test_tracer_totals_in_the_prometheus_text():
    t = Tracer()
    with t.start_span("segment.prepare") as span:
        span.add_part("bind", 2_000_000)
    text = t.prometheus_text()
    assert 'telemetry_spans_total{span="segment.prepare"} 1' in text
    assert ('telemetry_span_part_time_ms_total{span="segment.prepare",'
            'part="bind"} 2') in text
    assert "telemetry_tracer_finished_total 1" in text
    assert "telemetry_tracer_ring 8192" in text
    for line in text.splitlines():
        assert line.startswith("#") or len(line.rsplit(" ", 1)) == 2
        if not line.startswith("#"):
            float(line.rsplit(" ", 1)[1])
    totals = t.totals()["segment.prepare"]
    assert (f'telemetry_span_time_ms_total{{span="segment.prepare"}} '
            f'{totals["time_in_millis"]:.10g}') in text


def test_span_records_errors():
    t = Tracer()
    with pytest.raises(ValueError):
        with t.start_span("boom"):
            raise ValueError("nope")
    assert "ValueError" in t.recent()[0]["error"]


# -- metrics SPI ----------------------------------------------------------

def test_counters_and_histogram_percentiles():
    m = MetricsRegistry()
    m.counter("c").inc()
    m.counter("c").inc(4)
    h = m.histogram("lat_ms")
    for v in range(1, 101):          # 1..100 ms uniform
        h.observe(float(v))
    stats = m.stats()
    assert stats["counters"]["c"] == 5
    hs = stats["histograms"]["lat_ms"]
    assert hs["count"] == 100
    assert hs["max_in_millis"] == 100.0
    p50 = hs["percentiles"]["50.0"]
    p99 = hs["percentiles"]["99.0"]
    assert 25 <= p50 <= 75           # bucket-interpolated estimate
    assert p99 >= p50
    assert p99 <= 250


def test_histogram_empty_and_single():
    m = MetricsRegistry()
    h = m.histogram("x")
    assert h.percentile(99) == 0.0
    h.observe(3.0)
    assert h.stats()["count"] == 1
    assert h.stats()["percentiles"]["50.0"] <= 5.0


def test_time_ms_context_manager():
    m = MetricsRegistry()
    with m.time_ms("block_ms"):
        pass
    assert m.histogram("block_ms").count == 1


# -- cluster-mode trace propagation (the acceptance criterion) ------------

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


def test_cluster_search_spans_share_one_trace(cluster):
    hub, ids, nodes = cluster
    nodes["n0"].create_index("traced", {
        "settings": {"number_of_shards": 6},
        "mappings": {"properties": {"body": {"type": "text"}}}})
    wait_until(lambda: all("traced" in nodes[i].indices for i in ids))
    for i in range(30):
        nodes["n0"].index_doc("traced", str(i), {"body": f"event {i}"})
    nodes["n0"].refresh("traced")

    tracer().reset()
    resp = nodes["n0"].search("traced", {"query": {"match": {
        "body": "event"}}, "size": 5})
    assert resp["hits"]["total"]["value"] == 30
    assert resp["timed_out"] is False

    spans = tracer().recent(limit=500)
    by_id = {s["span_id"]: s for s in spans}
    coord = [s for s in spans if s["name"] == "search.coordinator"]
    assert len(coord) == 1
    root = coord[0]
    assert root["parent_span_id"] is None
    trace_id = root["trace_id"]

    # the coordinator reduce ran under the same trace
    reduces = [s for s in spans if s["name"] == "coordinator.reduce"]
    assert len(reduces) == 1
    assert reduces[0]["trace_id"] == trace_id
    assert reduces[0]["parent_span_id"] == root["span_id"]

    # one query phase per participating node (shards group per node),
    # EVERY one under the coordinator's trace_id
    qp = [s for s in spans if s["name"] == "shard.query_phase"]
    assert len(qp) == len(ids)
    assert all(s["trace_id"] == trace_id for s in qp)

    # remote query phases parent through the transport server span,
    # which parents directly under the coordinator span
    remote_qp = 0
    for s in qp:
        parent = by_id.get(s["parent_span_id"])
        if parent is None:
            # parent must be the coordinator itself (local execution)
            assert s["parent_span_id"] == root["span_id"]
            continue
        if parent["name"].startswith("transport:"):
            remote_qp += 1
            assert parent["trace_id"] == trace_id
            assert parent["parent_span_id"] == root["span_id"]
        else:
            assert parent["span_id"] == root["span_id"]
    assert remote_qp == 2            # 3 nodes, coordinator is local

    # per-segment device dispatches joined the same trace
    segs = [s for s in spans if s["name"] == "segment.dispatch"]
    assert segs and all(s["trace_id"] == trace_id for s in segs)


def test_cluster_timeout_flag_survives_reduce(cluster):
    hub, ids, nodes = cluster
    nodes["n0"].create_index("budget", {
        "settings": {"number_of_shards": 3},
        "mappings": {"properties": {"body": {"type": "text"}}}})
    wait_until(lambda: all("budget" in nodes[i].indices for i in ids))
    for i in range(12):
        nodes["n0"].index_doc("budget", str(i), {"body": "x " * 5})
    nodes["n0"].refresh("budget")
    resp = nodes["n0"].search("budget", {
        "query": {"match": {"body": "x"}}, "timeout": 0})
    assert resp["timed_out"] is True


# -- timeout budget on the shard path -------------------------------------

@pytest.fixture
def svc(tmp_path):
    from opensearch_tpu.indices.service import IndexService
    s = IndexService("t", str(tmp_path / "t"), {},
                     {"properties": {"body": {"type": "text"},
                                     "n": {"type": "long"}}})
    for i in range(20):
        s.index_doc(str(i), {"body": f"word {i}", "n": i})
    s.refresh()
    yield s
    s.close()


def test_search_timeout_partial_results(svc):
    full = svc.search({"query": {"match": {"body": "word"}}})
    assert full["timed_out"] is False
    assert full["hits"]["total"]["value"] == 20

    cut = svc.search({"query": {"match": {"body": "word"}},
                      "timeout": 0})
    assert cut["timed_out"] is True
    # budget expired before the first segment: partial (empty) results
    assert cut["hits"]["total"]["value"] == 0

    # a generous budget never flags
    ok = svc.search({"query": {"match": {"body": "word"}},
                     "timeout": "30s"})
    assert ok["timed_out"] is False
    assert ok["hits"]["total"]["value"] == 20


def test_sorted_and_agg_timeout_paths(svc):
    cut = svc.search({"query": {"match": {"body": "word"}},
                      "sort": [{"n": "asc"}], "timeout": 0})
    assert cut["timed_out"] is True
    cut = svc.search({"size": 0, "timeout": 0,
                      "aggs": {"m": {"max": {"field": "n"}}}})
    assert cut["timed_out"] is True


def test_msearch_timeout_falls_back_to_sequential(svc):
    out = svc.msearch([
        {"query": {"match": {"body": "word"}}},
        {"query": {"match": {"body": "word"}}, "timeout": 0}])
    assert out[0]["timed_out"] is False
    assert out[0]["hits"]["total"]["value"] == 20
    assert out[1]["timed_out"] is True


# -- slow logs ------------------------------------------------------------

def test_indexing_slowlog_per_index_setting(tmp_path, caplog):
    from opensearch_tpu.indices.service import IndexService
    s = IndexService("w", str(tmp_path / "w"),
                     {"indexing.slowlog.threshold.index.warn": "0ms"},
                     {"properties": {"t": {"type": "text"}}})
    with caplog.at_level(
            logging.WARNING,
            logger="opensearch_tpu.index.indexing.slowlog"):
        s.index_doc("1", {"t": "hello"})
    assert any("took" in r.getMessage() for r in caplog.records)
    s.close()


def test_slowlog_dynamic_update_and_cluster_default(tmp_path):
    """_cluster/settings sets the fleet default; a per-index
    PUT /{index}/_settings overrides it (reference layering)."""
    from opensearch_tpu.node import Node
    node = Node(str(tmp_path / "n"), port=0).start()
    try:
        rest = node.rest
        st, _ = rest.dispatch("PUT", "/slowidx", {}, json.dumps({
            "mappings": {"properties": {"t": {"type": "text"}}}
        }).encode())
        assert st == 200
        st, _ = rest.dispatch(
            "PUT", "/slowidx/_doc/1", {},
            json.dumps({"t": "hello"}).encode())
        assert st in (200, 201)
        rest.dispatch("POST", "/slowidx/_refresh", {}, None)

        logger = logging.getLogger("opensearch_tpu.index.search.slowlog")
        records = []

        class Grab(logging.Handler):
            def emit(self, record):
                records.append(record)
        h = Grab(level=logging.DEBUG)
        logger.addHandler(h)
        logger.setLevel(logging.DEBUG)
        try:
            body = json.dumps({"query": {"match": {"t": "hello"}}}).encode()
            # no thresholds anywhere: silent
            rest.dispatch("POST", "/slowidx/_search", {}, body)
            assert not records

            # cluster-level default catches every index
            st, _ = rest.dispatch("PUT", "/_cluster/settings", {},
                                  json.dumps({"transient": {
                                      "search.slowlog.threshold.query"
                                      ".warn": "0ms"}}).encode())
            assert st == 200
            rest.dispatch("POST", "/slowidx/_search", {}, body)
            assert len(records) == 1
            assert records[0].levelno == logging.WARNING

            # per-index override disables it for this index
            st, _ = rest.dispatch(
                "PUT", "/slowidx/_settings", {},
                json.dumps({"index": {
                    "search.slowlog.threshold.query.warn": "-1"
                }}).encode())
            assert st == 200
            rest.dispatch("POST", "/slowidx/_search", {}, body)
            assert len(records) == 1       # no new record

            # reset the cluster default (null resets, like the reference)
            st, _ = rest.dispatch("PUT", "/_cluster/settings", {},
                                  json.dumps({"transient": {
                                      "search.slowlog.threshold.query"
                                      ".warn": None}}).encode())
            assert st == 200
            from opensearch_tpu.indices.service import SLOWLOG_DEFAULTS
            assert "search.slowlog.threshold.query.warn" \
                not in SLOWLOG_DEFAULTS
        finally:
            logger.removeHandler(h)
            logger.setLevel(logging.NOTSET)
    finally:
        node.stop()


# -- X-Opaque-Id ----------------------------------------------------------

def test_x_opaque_id_reaches_task_and_cat_tasks(tmp_path):
    from opensearch_tpu.node import Node
    node = Node(str(tmp_path / "n"), port=0).start()
    try:
        # the _tasks request lists ITSELF, so its own headers echo back
        st, body = node.rest.dispatch(
            "GET", "/_tasks", {}, None,
            headers={"X-Opaque-Id": "req-42"})
        assert st == 200
        tasks = next(iter(body["nodes"].values()))["tasks"]
        assert any(t.get("headers", {}).get("X-Opaque-Id") == "req-42"
                   for t in tasks.values())

        st, rows = node.rest.dispatch(
            "GET", "/_cat/tasks", {}, None,
            headers={"x-opaque-id": "req-43"})   # case-insensitive
        assert st == 200
        assert any(r.get("x_opaque_id") == "req-43" for r in rows)
    finally:
        node.stop()


# -- REST surfaces --------------------------------------------------------

def test_rest_traceparent_honored_and_stats_histograms(tmp_path):
    from opensearch_tpu.node import Node
    node = Node(str(tmp_path / "n"), port=0).start()
    try:
        rest = node.rest
        rest.dispatch("PUT", "/obs", {}, json.dumps({
            "mappings": {"properties": {"t": {"type": "text"}}}
        }).encode())
        rest.dispatch("PUT", "/obs/_doc/1", {},
                      json.dumps({"t": "hello world"}).encode())
        rest.dispatch("POST", "/obs/_refresh", {}, None)

        tracer().reset()
        incoming = "00-" + "ab" * 16 + "-" + "cd" * 8 + "-01"
        st, _ = rest.dispatch(
            "POST", "/obs/_search", {},
            json.dumps({"query": {"match": {"t": "hello"}}}).encode(),
            headers={"traceparent": incoming})
        assert st == 200
        spans = tracer().recent(limit=200)
        roots = [s for s in spans if s["name"].startswith("rest:")]
        assert roots and all(s["trace_id"] == "ab" * 16 for s in roots)
        # the REST root continues the CLIENT's trace
        assert roots[-1]["parent_span_id"] == "cd" * 8
        # the shard query phase nests under the same client trace
        qp = [s for s in spans if s["name"] == "shard.query_phase"]
        assert qp and all(s["trace_id"] == "ab" * 16 for s in qp)

        # _nodes/stats: telemetry section with non-zero latency counts
        st, body = rest.dispatch("GET", "/_nodes/stats", {}, None)
        assert st == 200
        tele = next(iter(body["nodes"].values()))["telemetry"]
        hist = tele["histograms"]["search.query_ms"]
        assert hist["count"] >= 1
        assert "50.0" in hist["percentiles"]
        assert "99.0" in hist["percentiles"]
        assert tele["histograms"]["indexing.index_ms"]["count"] >= 1
        assert tele["counters"]["search.queries"] >= 1

        # _nodes/trace: the debug span dump, filterable by trace_id
        st, body = rest.dispatch("GET", "/_nodes/trace",
                                 {"trace_id": "ab" * 16}, None)
        assert st == 200
        spans = next(iter(body["nodes"].values()))["spans"]
        assert spans and all(s["trace_id"] == "ab" * 16 for s in spans)

        # hot threads includes this very thread's stack
        st, body = rest.dispatch("GET", "/_nodes/hot_threads", {}, None)
        assert st == 200
        text = next(iter(body["nodes"].values()))["hot_threads"]
        assert "thread [" in text and "h_hot_threads" in text
    finally:
        node.stop()


def test_write_path_metrics(tmp_path):
    from opensearch_tpu.node import Node
    node = Node(str(tmp_path / "n"), port=0).start()
    try:
        before = metrics().histogram("translog.sync_ms").count
        node.rest.dispatch("PUT", "/wm/_doc/1", {},
                           json.dumps({"v": 1}).encode())
        node.rest.dispatch("POST", "/wm/_refresh", {}, None)
        assert metrics().histogram("translog.sync_ms").count > before
        assert metrics().histogram("indexing.refresh_ms").count >= 1
    finally:
        node.stop()


# -- monotonic lint (the tier-1 CI hook) ----------------------------------

def test_check_monotonic_lint_passes():
    import os
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, os.path.join(root, "tools",
                                      "check_monotonic.py")],
        capture_output=True, text=True)
    assert out.returncode == 0, out.stdout + out.stderr


def test_check_monotonic_lint_catches_violations(tmp_path):
    bad = tmp_path / "pkg"
    bad.mkdir()
    (bad / "mod.py").write_text(
        "import time\nt0 = time.time()\n"
        "ok = time.time()  # wall-clock: timestamp\n")
    out = subprocess.run(
        [sys.executable, "tools/check_monotonic.py", str(bad)],
        capture_output=True, text=True,
        cwd=__file__.rsplit("/tests/", 1)[0])
    assert out.returncode == 1
    assert "mod.py:2" in out.stdout
    assert "mod.py:3" not in out.stdout
