"""Shared by the benchmark's tests: the committed cells cut to a size a
test run can hold, driven through the harness's own code on the CPU."""

import dataclasses
import json
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
