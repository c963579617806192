"""PR 27's per-layer metrics: sixteen data files over the two readers
that were there, read from the spans and counters the program gained."""

import dataclasses
import math
import os

import pytest

from benchmarks import harness, readers
from bench_tiny import LATE, last_line_ok, run_tiny, tiny_cell

NAMES = ("http_request_ms", "accept_wait_ms", "plan_ms",
         "segment_dispatch_ms", "segment_prepare_ms", "device_sync_ms",
         "gc_ms_per_query", "host_cpu_us_per_query")
CELLS = {"msmarco_closed": ".tput", "sift_paced": ".lat"}


@pytest.mark.parametrize("name", [n + s for n in NAMES
                                  for s in CELLS.values()])
def test_metric_file_names_a_reader_that_is_there(name):
    spec = harness.metric_spec(name)
    assert set(spec) == {"doc", "reader"} and spec["doc"]
    assert spec["reader"]["kind"] in ("span_mean", "stats_delta")
    assert spec["reader"]["kind"] in readers.KINDS
    entry = next(m for m in harness._json(
        os.path.join(harness.ROOT, "BENCHMARK.json"))["per_layer"]
        if m["name"] == name)
    cell = next(c for c, s in CELLS.items() if name.endswith(s))
    assert cell in entry["workloads"] and entry["better"] == "lower"
    assert entry["moves"] == ("qps" if cell == "msmarco_closed"
                              else "latency_p50_ms")


@pytest.mark.parametrize("cell_name", CELLS)
def test_traced_cell_reports_the_eight_with_numbers(cpu_kernels, cell_name):
    # half of bench_tiny's docs: programs of other shapes, so that this
    # file leaves test_warmup_enumeration's still to be compiled in a
    # worker that runs both
    cell = tiny_cell(cell_name)
    cell = dataclasses.replace(cell, cfg={**cell.cfg, "n_docs": 2048})
    want = {n + CELLS[cell_name] for n in NAMES}
    assert want <= {m["name"] for m in cell.metrics("per_layer")}
    result = run_tiny(cell, traced=True)
    last_line_ok(result)
    assert result["correct"] is True
    for name in sorted(want):
        value = result["metrics"][name]["value"]
        assert isinstance(value, float) and math.isfinite(value), name
        assert value >= 0, name
    spans = {n: result["metrics"][n + CELLS[cell_name]]["value"]
             for n in NAMES if n.endswith("_ms") and n != "accept_wait_ms"}
    assert all(v > 0 for v in spans.values()), spans
    # a request adds up from inside the program
    assert spans["segment_prepare_ms"] < spans["segment_dispatch_ms"]
    assert spans["plan_ms"] < spans["http_request_ms"]
    assert result["metrics"]["host_cpu_us_per_query"
                             + CELLS[cell_name]]["value"] > 0
    # the corrected instrument: the pre-pass's program and its sync count,
    # beside the winners' program and read in the one segment
    if cell_name == "sift_paced":
        for name in ("dispatches_per_query.lat", "d2h_reads_per_query.lat"):
            assert 1 <= result["metrics"][name]["value"] <= 2 * LATE, name
