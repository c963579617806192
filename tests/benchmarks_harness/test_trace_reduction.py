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


def one_plane() -> list:
    """``synthetic`` with an operation and a program across each edge of
    the bracketed window."""
    host, (name, lines) = synthetic()
    by_line = dict(lines)
    by_line["XLA Ops"] = by_line["XLA Ops"] + [
        ("%fusion.9", 95 * MS, 10 * MS), ("%fusion.1", -5 * MS, 7 * MS)]
    by_line["XLA Modules"] = by_line["XLA Modules"] + [
        ("jit_run_topk(1)", 95 * MS, 10 * MS),
        ("jit_run_full(4)", -5 * MS, 7 * MS)]
    return [host, (name, list(by_line.items()))]


# what the reduction of one device plane returned before it read several,
# field for field: with the markers, and without them
ONE_PLANE = {
    "window_s": 0.1, "busy_s": 0.052, "devices": 1,
    "ops": {"%fusion.3 = gather": 0.03, "%sort": 0.005, "%while.4": 0.01,
            "%fusion.40": 0.004, "%fusion.9": 0.005, "%fusion.1": 0.002},
    "modules": {"jit_run_topk(1)": 0.04, "jit_knn_topk(2)": 0.01,
                "jit_run_full(4)": 0.002},
    "gaps": [(2000000.0, 10000000.0), (35000000.0, 50000000.0),
             (60000000.0, 80000000.0), (90000000.0, 95000000.0)],
    "t0_ns": 0.0}
ONE_PLANE_UNMARKED = {
    "window_s": 0.135, "busy_s": 0.072, "devices": 1,
    "ops": {"%fusion.3 = gather": 0.04, "%sort": 0.005, "%while.4": 0.01,
            "%fusion.40": 0.004, "%fusion.9": 0.01, "%fusion.1": 0.007},
    "modules": {"jit_run_topk(1)": 0.045000000000000005,
                "jit_knn_topk(2)": 0.01, "jit_run_full(4)": 0.007},
    "gaps": [(2000000.0, 10000000.0), (35000000.0, 50000000.0),
             (60000000.0, 80000000.0), (90000000.0, 95000000.0),
             (105000000.0, 120000000.0)],
    "t0_ns": -5000000.0}


@pytest.mark.parametrize("marked,recorded", [(True, ONE_PLANE),
                                             (False, ONE_PLANE_UNMARKED)])
def test_one_plane_reads_as_it_did(marked, recorded):
    planes = one_plane() if marked else one_plane()[1:]
    s = trace.reduce(planes)
    assert s.pop("busy_any_s") == s["busy_s"]      # one plane: the same
    assert s == recorded


def four_planes() -> list:
    """Four chips over a bracketed 100 ms; each plane's programs cover
    its operations.  Chip 0 idles from 30 to 50 ms while chip 1 works
    until 40: only 40-50 is a gap."""
    spans = {0: [(10, 30), (50, 60)], 1: [(20, 40)], 2: [(55, 70)],
             3: [(80, 90), (120, 130)]}          # 120-130: past the end
    host = [(trace.MARK_BEGIN, 0.0, 1000.0), (trace.MARK_END, 100 * MS,
                                              1000.0)]
    planes = [("/host:CPU", [("bench", host)])]
    for chip, busy in spans.items():
        ops = [(f"%fusion.{chip}", a * MS, (b - a) * MS) for a, b in busy]
        modules = [(f"jit_knn_topk({chip})", a * MS, (b - a) * MS)
                   for a, b in busy]
        planes.append((f"/device:TPU:{chip}",
                       [("XLA Modules", modules), ("XLA Ops", ops)]))
    return planes


def test_four_planes_gap_only_where_every_chip_idles():
    s = trace.reduce(four_planes())
    assert s["devices"] == 4
    assert s["window_s"] == pytest.approx(0.100)
    # each chip's busy time 30, 20, 15, 10 ms: their mean, and their union
    assert s["busy_s"] == pytest.approx(0.075 / 4)
    assert s["busy_any_s"] == pytest.approx(0.060)   # 10-40, 50-70, 80-90
    gaps = [(round(a / MS), round(b / MS)) for a, b in s["gaps"]]
    assert gaps == [(0, 10), (40, 50), (70, 80), (90, 100)]
    assert s["ops"] == {"%fusion.0": pytest.approx(0.030),
                        "%fusion.1": pytest.approx(0.020),
                        "%fusion.2": pytest.approx(0.015),
                        "%fusion.3": pytest.approx(0.010)}
    assert trace.kernel_seconds(s) == pytest.approx(0.075)
    assert trace.kernel_seconds(s, "knn_topk") == pytest.approx(0.075)
    assert trace.kernel_seconds(s, "knn_topk(1)") == pytest.approx(0.020)
    table = dict(trace.gap_breakdown(
        s, lambda a, b: "edge" if a == 0 or b == 100 * MS else "between"))
    assert table == {"edge": pytest.approx(0.020),
                     "between": pytest.approx(0.020)}


def test_readers_on_four_planes():
    """Idle is the mean chip's; kernel time and the roofline's device
    time are summed over the chips."""
    cell = tiny_cell("sift_paced")
    full = dict(cell.cfg, n_docs=1_000_000, dim=128)
    ctx = {"trace": trace.reduce(four_planes()),
           "trace_queries": [None] * 12, "kind": knn_exact, "cfg": full,
           "data": None, "device_kind": "TPU v5 lite"}
    assert readers.trace_idle(ctx) == pytest.approx(81.25)
    assert readers.trace_kernel_time(ctx, per="query") == pytest.approx(
        75.0 / 12)
    assert readers.trace_kernel_time(ctx, match="knn_topk") == \
        pytest.approx(75.0)
    # 12 queries x 0.625 ms over 75 ms of knn_topk programs on four chips
    assert readers.roofline_bytes(ctx, match="knn_topk") == pytest.approx(
        10.0, rel=2e-3)


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
