"""Shared by the benchmark's tests: the committed cells cut to a size a
test run can hold, driven through the harness's own code on the CPU, and
the rules any checkout of the benchmark keeps."""

import dataclasses
import json
import os
import time

from benchmarks import harness

TINY = {
    "text_bm25": dict(n_docs=4096, segments=2, vocab=6000, n_queries=240,
                      compare_max=48),
    # at dim 16 the nearest squared distances are a tenth of the cell's,
    # so float32's rounding reads that much wider against them
    "knn_exact": dict(n_docs=4096, dim=16, n_queries=160, compare_max=48,
                      limits={"failed": 0, "malformed": 0,
                              "device_faults": 0, "score_err": 4e-5,
                              "rank_gap": 4e-5}),
}
SEEDS = (3000000019, 7, 2147483659)      # past 2**31 - 1 too
# a count per request is a delta over the window over the requests that
# completed inside it: the work of those still in flight when it closed
# may add this much
LATE = 1.06


def chips_the_benchmark_allows(workloads: list) -> bool:
    """Every cell on 1 or 4 chips, and at most half of the cells, rounded
    down, on 4; one four-chip cell always may be."""
    chips = [w["chips"] for w in workloads]
    return (set(chips) <= {1, 4}
            and chips.count(4) <= max(1, len(chips) // 2))


def assert_joins_up(root: str) -> None:
    """Every cell of the checkout at ``root`` finds its configuration,
    reference, traffic and metrics; every configuration its reference;
    and the cells' chips are ones the benchmark allows."""
    bench = harness._json(os.path.join(root, "BENCHMARK.json"))
    for w in bench["workloads"]:
        cell = harness.load_cell(w["name"], root=root)
        assert cell.cfg["name"] == w["config"]
        assert cell.chips == w["chips"]
        assert cell.mix["loop"] in ("closed", "paced")
        assert hasattr(cell.reference, "Reference")
        names = [m["name"] for m in cell.metrics("end_to_end")]
        assert "setup_s" in names and len(names) >= 2
        assert cell.metrics("per_layer")
        assert set(cell.cfg["limits"]) == {"failed", "malformed",
                                           "score_err", "rank_gap",
                                           "device_faults"}
    for c in bench["configs"]:
        assert os.path.exists(os.path.join(
            root, c["file"].replace(".json", ".reference.py")))
        cfg = harness._json(os.path.join(root, c["file"]))
        assert cfg["source"] == c["source"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
    assert chips_the_benchmark_allows(bench["workloads"])


def assert_bucket_rule(buckets: list, bound: float) -> None:
    """The gather buckets of one t_pad: BUCKET_MIN * BUCKET_STEP**k from
    k = 0 up to the first bucket that holds ``bound`` postings."""
    from benchmarks.kinds.text_bm25 import BUCKET_MIN, BUCKET_STEP

    assert buckets == [BUCKET_MIN * BUCKET_STEP ** k
                       for k in range(len(buckets))]
    assert buckets[-1] >= bound
    assert len(buckets) == 1 or buckets[-2] < bound


def tiny_cell(name: str, **mix) -> harness.Cell:
    cell = harness.load_cell(name)
    return dataclasses.replace(
        cell, cfg={**cell.cfg, **TINY[cell.cfg["kind"]]},
        mix={**cell.mix, "warmup_s": 0.3, **mix})


def run_tiny(cell, seed=SEEDS[0], seconds=1.5, traced=False, tamper=None):
    return harness.run_cell(cell, seed, seconds, traced,
                            t_process=time.monotonic(),
                            device=harness.device_info(), tamper=tamper)


def last_line_ok(result: dict) -> None:
    """The contract's keys, in a line that json round-trips."""
    line = json.loads(json.dumps(result))
    assert list(line)[-1] == "compared"
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        assert key in line
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        line["device"])
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"}
    for c in line["compared"].values():
        assert set(c) == {"value", "limit"}
