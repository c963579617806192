"""PR 37's per-layer metrics: data files over ``stats_delta``, read from
the tracer's per-name totals (``_nodes/stats`` ``telemetry.spans`` /
``telemetry.tracer``), which are kept where a span ends and so cover the
whole window whatever the ring's read-out held; and the cell
``splade_sparse_closed`` with its kernel's roofline and the ``.tput``
forms of the sparse query's metrics.

Membership and lower bounds only: a later PR appends cells, metrics and
names to lists, and edits no file of this directory."""

import dataclasses
import math
import os

import pytest

from benchmarks import harness, readers
from bench_tiny import LATE, last_line_ok, run_tiny, tiny_cell

NAMES = ("spans_per_query", "rest_ms_per_query", "http_off_cpu_ms_per_query",
         "head_read_ms_per_query", "edge_route_ms_per_query",
         "edge_after_ms_per_query", "edge_respond_ms_per_query",
         "prepare_ms_per_query", "prepare_off_cpu_ms_per_query",
         "prepare_bind_ms_per_query", "prepare_cache_put_ms_per_query",
         "prepare_cache_get_ms_per_query",
         "prepare_arrays_ms_per_query", "launch_ms_per_query",
         "sync_ms_per_query", "sync_off_cpu_ms_per_query",
         "process_cpu_ms_per_query")
# the cells each list holds at least: every closed cell and every paced
# one in which the readers find the totals
LISTS = {".tput": ["msmarco_closed", "splade_sparse_closed", "sift_closed"],
         ".lat": ["sift_paced", "splade_sparse_paced", "pmc_phrase_paced",
                  "msmarco_paced", "nq_hybrid_paced", "yfcc_filtered_paced"]}
CELL = "splade_sparse_closed"
# what the sparse query's layer reports in the new cell
SPARSE = ("sparse_bind_ms.tput", "sparse_tokens_per_query.tput",
          "sparse_postings_per_query.tput",
          "sparse_budget_lanes_per_query.tput")
# the .tput lists the new cell was appended to
JOINED = ("edge_ms.tput", "query_phase_ms.tput", "dispatches_per_query.tput",
          "d2h_reads_per_query.tput", "fetch_phase_ms.tput",
          "kernel_ms_per_query.tput", "device_idle_share.tput",
          "compiles_in_window.tput", "sorted_bag_per_query.tput",
          "block_topk_per_query.tput", "d2h_arrays_per_query.tput",
          "h2d_arrays_per_query.tput", "slice_gathers_per_query.tput")


def _bench() -> dict:
    return harness._json(os.path.join(harness.ROOT, "BENCHMARK.json"))


@pytest.mark.parametrize("name", [n + s for n in NAMES for s in LISTS])
def test_metric_file_reads_the_totals_through_stats_delta(name):
    spec = harness.metric_spec(name)
    assert set(spec) == {"doc", "reader"} and spec["doc"]
    reader = spec["reader"]
    assert reader["kind"] == "stats_delta" and reader["per"] == "query"
    assert reader["kind"] in readers.KINDS
    assert reader["path"].split(".")[0] in ("telemetry", "process")
    entry, = [m for m in _bench()["per_layer"] if m["name"] == name]
    suffix = "." + name.rsplit(".", 1)[1]
    assert set(LISTS[suffix]) <= set(entry["workloads"])
    assert entry["better"] == "lower"
    assert entry["source"] == "program_counter"
    assert entry["unit"] == ("1" if name.startswith("spans_per") else "ms")
    assert entry["moves"] == ("qps" if suffix == ".tput"
                              else "latency_p50_ms")


def test_the_cache_lock_waits_list_every_closed_cell():
    entry, = [m for m in _bench()["per_layer"]
              if m["name"] == "prepare_lock_waits_per_query.tput"]
    assert set(LISTS[".tput"]) <= set(entry["workloads"])
    assert entry["moves"] == "qps" and entry["source"] == "program_counter"
    reader = harness.metric_spec(entry["name"])["reader"]
    assert reader["kind"] == "stats_delta" and reader["per"] == "query"


def test_the_new_cell_loads_and_joins_the_closed_lists():
    cell = harness.load_cell(CELL)
    assert cell.cfg["name"] == "msmarco-passage-splade" and cell.chips == 1
    assert cell.mix["loop"] == "closed" and cell.mix["clients"] == 4
    assert cell.mix["warmup_s"] == 4
    assert {m["name"] for m in cell.metrics("end_to_end")} == {"qps",
                                                               "setup_s"}
    mine = {m["name"] for m in cell.metrics("per_layer")}
    assert mine >= (set(JOINED) | set(SPARSE) | {n + ".tput" for n in NAMES}
                    | {"sparse_topk_roofline.tput"})
    bench = cell.bench
    for m in bench["per_layer"]:
        if m["name"] in JOINED + SPARSE:
            assert CELL in m["workloads"] and m["moves"] == "qps"
    roof, = [m for m in bench["per_layer"]
             if m["name"] == "sparse_topk_roofline.tput"]
    assert CELL in roof["workloads"] and roof["unit"] == "%"
    assert roof["source"] == "device_trace" and roof["better"] == "higher"
    assert harness.metric_spec("sparse_topk_roofline.tput")["reader"] == {
        "kind": "roofline_bytes", "match": "run_topk"}
    assert CELL in {w["name"] for w in bench["workloads"]}
    for name in SPARSE:
        reader = harness.metric_spec(name)["reader"]
        assert reader["kind"] == "stats_delta" and reader["per"] == "query"
    # ~1,100 requests a window, inside the queries the harness leaves it
    head = cell.cfg["n_queries"] - cell.cfg["n_queries"] // 8
    assert 34 * 40 * 1.1 < head


def _new_metrics(result: dict, suffix: str) -> dict:
    got = {}
    for name in NAMES:
        value = result["metrics"][name + suffix]["value"]
        assert isinstance(value, float) and math.isfinite(value), name
        assert value >= 0, name
        got[name] = value
    return got


def _adds_up(got: dict, spans: float, more: float = 0.0) -> None:
    """What the totals must say of any request, whatever the machine:
    ``spans`` a request (up to ``more`` besides), and a little for the
    requests in flight when the window closed and the harness's reads."""
    assert spans * 0.98 <= got["spans_per_query"] <= (spans + more) * 1.15
    prepare = got["prepare_ms_per_query"]
    assert prepare > 0
    assert (got["prepare_bind_ms_per_query"]
            + got["prepare_cache_put_ms_per_query"]
            + got["prepare_cache_get_ms_per_query"]
            + got["prepare_arrays_ms_per_query"]) <= prepare
    assert got["prepare_cache_get_ms_per_query"] > 0
    assert got["launch_ms_per_query"] > 0
    assert got["rest_ms_per_query"] > 0
    edge = (got["edge_route_ms_per_query"] + got["edge_after_ms_per_query"]
            + got["edge_respond_ms_per_query"])
    assert edge > 0
    assert got["process_cpu_ms_per_query"] > 0


@pytest.mark.parametrize("cell_name,suffix,spans", [
    # http.request, rest:, shard.query_phase, query.plan, device.sync,
    # fetch_phase, and two spans a segment: 6 + 2 x 2
    ("msmarco_closed", ".tput", 10), ("msmarco_paced", ".lat", 10),
    # the pre-pass's device.sync besides, one segment: 7 + 2
    ("sift_paced", ".lat", 9)])
def test_traced_cell_reports_every_new_metric_of_its_loop(
        cpu_kernels, cell_name, suffix, spans):
    # test_span_metrics.py's size: programs of other shapes than the files that
    # count what set-up compiles run
    cell = tiny_cell(cell_name)
    cell = dataclasses.replace(cell, cfg={**cell.cfg, "n_docs": 2048})
    want = {n + suffix for n in NAMES}
    assert want <= {m["name"] for m in cell.metrics("per_layer")}
    result = run_tiny(cell, traced=True)
    last_line_ok(result)
    assert result["correct"] is True
    got = _new_metrics(result, suffix)
    _adds_up(got, spans)
    # the ring's mean of the same span is still reported beside it, in
    # the cells that list it
    ring = "segment_prepare_ms" + suffix
    if ring in {m["name"] for m in cell.metrics("per_layer")}:
        assert result["metrics"][ring]["value"] > 0


def test_the_new_cell_reports_its_metrics_at_a_tiny_size(cpu_kernels):
    from opensearch_tpu.common.breakers import breaker_service

    cell = harness.load_cell(CELL)
    cell = dataclasses.replace(
        cell, cfg={**cell.cfg, "n_docs": 4096, "segments": 2,
                   "n_queries": 240, "compare_max": 48},
        mix={**cell.mix, "clients": 2, "warmup_s": 0.3})
    try:
        result = run_tiny(cell, seconds=2.0, traced=True)
    finally:      # the configuration raises the breakers' limits for good
        breaker_service().set_limit("fielddata", 0)
        breaker_service().set_limit("total", 0)
    last_line_ok(result)
    assert result["correct"] is True and result["failed"] == 0
    by_source = {m["name"]: m["source"] for m in cell.metrics("per_layer")}
    assert set(result["metrics"]) >= {
        n for n in JOINED + SPARSE + tuple(n + ".tput" for n in NAMES)
        if by_source[n] != "device_trace"}
    got = _new_metrics(result, ".tput")
    sparse = {n: result["metrics"][n]["value"] for n in SPARSE}
    assert 8 <= sparse["sparse_tokens_per_query.tput"] <= 48
    assert (sparse["sparse_tokens_per_query.tput"]
            < sparse["sparse_postings_per_query.tput"]
            <= sparse["sparse_budget_lanes_per_query.tput"])
    assert 0 < sparse["sparse_bind_ms.tput"] < got["rest_ms_per_query"]
    assert result["metrics"]["slice_gathers_per_query.tput"]["value"] >= 0
    # sparse.bind besides, where the plan cache does not hold the query
    _adds_up(got, 10, more=1)
    # at most a program a segment, each of a scored bag at the plan's root
    dispatches = result["metrics"]["dispatches_per_query.tput"]["value"]
    assert 0 < dispatches <= 2 * LATE
    assert 0 <= result["metrics"]["sorted_bag_per_query.tput"][
        "value"] <= dispatches
    assert result["metrics"]["compiles_in_window.tput"]["value"] == 0
