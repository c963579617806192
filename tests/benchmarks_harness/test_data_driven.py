"""Later PRs add cells and metrics as data: a new entry in BENCHMARK.json
and new files, no edit to a file that is there."""

import json
import os
import shutil

from benchmarks import harness
from bench_tiny import TINY, assert_joins_up, last_line_ok, run_tiny

import dataclasses


def _copy_of_the_benchmark(tmp_path):
    root = tmp_path / "checkout"
    os.makedirs(root / "benchmarks")
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(harness.HERE, "configs"),
                    root / "benchmarks" / "configs")
    return root


def test_a_new_cell_and_a_new_metric_are_entries_and_files(
        cpu_kernels, tmp_path, monkeypatch):
    root = _copy_of_the_benchmark(tmp_path)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    # ROADMAP S7's cell: the kNN configuration under the closed loop
    bench["workloads"].append({
        "name": "sift_closed", "config": "sift-128-exact-knn",
        "traffic": "closed", "chips": 1, "why": "concurrent kNN queries"})
    for m in bench["end_to_end"]:
        if m["name"] == "qps":
            m["workloads"].append("sift_closed")
    # a new metric over an existing reader: one more file
    metrics = tmp_path / "metrics"
    shutil.copytree(os.path.join(harness.HERE, "metrics"), metrics)
    (metrics / "segments_pruned_per_query.json").write_text(json.dumps({
        "doc": "can-match pruning", "reader": {
            "kind": "stats_delta", "per": "query",
            "path": "telemetry.counters.search.queries"}}))
    bench["per_layer"].append({
        "name": "segments_pruned_per_query", "unit": "1",
        "better": "higher", "source": "program_counter",
        "layer": "shard query phase", "moves": "qps"})
    for m in bench["per_layer"]:
        if m["name"].endswith(".tput"):
            m["workloads"].append("sift_closed")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    monkeypatch.setattr(
        harness, "metric_spec",
        lambda name: harness._json(str(metrics / (name + ".json"))))

    cell = harness.load_cell("sift_closed", root=str(root))
    cell = dataclasses.replace(
        cell, cfg={**cell.cfg, **TINY["knn_exact"]},
        mix={**cell.mix, "clients": 2, "warmup_s": 0.3})
    assert {m["name"] for m in cell.metrics("end_to_end")} == {"qps",
                                                               "setup_s"}
    result = run_tiny(cell, traced=True)
    last_line_ok(result)
    assert result["correct"] is True
    got = set(result["metrics"])
    # the metric without a list follows the metric it moves
    assert "segments_pruned_per_query" in got
    assert "dispatches_per_query.tput" in got
    assert "sched_lag_ms" not in got and "edge_ms.lat" not in got


def test_committed_benchmark_joins_up():
    assert_joins_up(harness.ROOT)
