"""The reduction from a profiler trace to numbers, on a synthetic trace,
and the roofline byte counts, which follow from shapes alone."""

import json
import os

import pytest

from benchmarks import harness, readers, trace
from benchmarks.kinds import knn_exact, text_bm25
from bench_tiny import tiny_cell

MS = 1e6      # ns


def synthetic():
    ops = [("%fusion.3 = gather", 10 * MS, 20 * MS),
           ("%sort", 30 * MS, 5 * MS),
           # nested in a while: the union counts it once
           ("%while.4", 50 * MS, 10 * MS), ("%fusion.40", 52 * MS, 4 * MS),
           ("%fusion.3 = gather", 80 * MS, 10 * MS),
           # outside the bracketed window
           ("%fusion.3 = gather", 120 * MS, 10 * MS)]
    modules = [("jit_run_topk(1)", 10 * MS, 25 * MS),
               ("jit_knn_topk(2)", 50 * MS, 10 * MS),
               ("jit_run_topk(1)", 80 * MS, 10 * MS)]
    host = [(trace.MARK_BEGIN, 0.0, 1000.0),
            (trace.MARK_END, 100 * MS, 1000.0)]
    return [("/host:CPU", [("bench", host)]),
            ("/device:TPU:0", [("XLA Modules", modules), ("XLA Ops", ops),
                               ("Async XLA Ops", [("%copy-start", 0.0,
                                                   200 * MS)])])]


def test_busy_idle_and_time_by_name():
    s = trace.reduce(synthetic())
    assert s["window_s"] == pytest.approx(0.100)
    assert s["busy_s"] == pytest.approx(0.045)       # 20 + 5 + 10 + 10
    assert s["devices"] == 1
    assert s["ops"]["%fusion.3 = gather"] == pytest.approx(0.030)
    assert trace.kernel_seconds(s, "run_topk") == pytest.approx(0.035)
    assert trace.kernel_seconds(s, "knn_topk") == pytest.approx(0.010)
    assert trace.kernel_seconds(s) == pytest.approx(0.045)
    assert readers.trace_idle({"trace": s}) == pytest.approx(55.0)
    gaps = [(round(a / MS), round(b / MS)) for a, b in s["gaps"]]
    assert gaps == [(0, 10), (35, 50), (60, 80), (90, 100)]
    table = dict(trace.gap_breakdown(
        s, lambda a, b: "long" if b - a > 12 * MS else "short"))
    assert table == {"long": pytest.approx(0.035),
                     "short": pytest.approx(0.020)}
    s["gaps"].append((200 * MS, 200 * MS + 5000.0))     # 5 us: not looked up
    assert dict(trace.gap_breakdown(s, lambda a, b: "x")) == {
        "x": pytest.approx(0.055), trace.SHORT_GAPS: pytest.approx(5e-6)}
    assert trace.top({"a" * 300: 2.0, "b": 3.0}, n=1) == [["b", 3.0]]


def test_without_markers_the_device_events_span_the_window():
    planes = [p for p in synthetic() if p[0] != "/host:CPU"]
    s = trace.reduce(planes)
    assert s["window_s"] == pytest.approx(0.120)
    assert s["busy_s"] == pytest.approx(0.055)


def test_no_device_plane_nothing_to_read():
    assert trace.reduce([("/host:CPU", [("python", [("x", 0.0, 5.0)])])]) \
        == {}
    ctx = {"trace": {}, "trace_queries": []}
    assert readers.trace_idle(ctx) is None
    assert readers.trace_kernel_time(ctx, per="query") is None
    assert readers.roofline_bytes(ctx) is None


def test_roofline_bytes_follow_from_shapes():
    cell = tiny_cell("sift_paced")
    assert knn_exact.work_bytes(cell.cfg, None, None) == 4096 * 16 * 4
    full = dict(cell.cfg, n_docs=1_000_000, dim=128)
    assert knn_exact.work_bytes(full, None, None) == 512_000_000
    # 0.625 ms a query at 819 GB/s
    from benchmarks import peaks
    assert peaks.least_seconds("TPU v5 lite", 512e6, knn_exact.work_flops(
        full, None, None)) == pytest.approx(0.625e-3, rel=2e-3)

    cell = tiny_cell("msmarco_closed")
    data = text_bm25.generate(cell.cfg, 11)
    terms = [0, 1, 5000]
    postings = int(data.df[terms].sum())
    searched = sum(1 for s in data.segments if s.df[terms].sum())
    assert text_bm25.work_bytes(cell.cfg, data, terms) == (
        postings * 8 + searched * 2048 * 8)
    assert text_bm25.work_bytes(cell.cfg, data, terms) == \
        text_bm25.work_bytes(cell.cfg, text_bm25.generate(cell.cfg, 11),
                             terms)


def test_roofline_reader_divides_least_time_by_kernel_time():
    cell = tiny_cell("sift_paced")
    full = dict(cell.cfg, n_docs=1_000_000, dim=128)
    s = trace.reduce(synthetic())
    ctx = {"trace": s, "trace_queries": [None] * 8, "kind": knn_exact,
           "cfg": full, "data": None, "device_kind": "TPU v5 lite"}
    # 8 queries x 0.625 ms over 10 ms of knn_topk programs
    assert readers.roofline_bytes(ctx, match="knn_topk") == pytest.approx(
        50.0, rel=2e-3)
    assert readers.trace_kernel_time(ctx, match="", per="query") == \
        pytest.approx(45.0 / 8)
    ctx["device_kind"] = "cpu"
    with pytest.raises(KeyError):
        readers.roofline_bytes(ctx, match="knn_topk")


def test_every_metric_file_names_a_known_reader():
    bench = harness._json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    for m in bench["per_layer"]:
        spec = harness.metric_spec(m["name"])
        assert spec["reader"]["kind"] in readers.KINDS, m["name"]
    listed = {m["name"] + ".json" for m in bench["per_layer"]}
    assert listed == set(os.listdir(os.path.join(harness.HERE, "metrics")))
    json.dumps(bench)
