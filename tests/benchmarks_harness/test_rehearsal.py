"""Each cell end to end at a tiny size on the CPU, through the code the
chip runs: the result line's keys, the ways ``correct`` has to come out
false, and the refusal to report device metrics off the chip."""

import os
import subprocess
import sys

import numpy as np
import pytest

from benchmarks import harness
from bench_tiny import last_line_ok, run_tiny, tiny_cell

CELLS = ("msmarco_closed", "sift_paced")
ROOT = harness.ROOT


@pytest.mark.parametrize("name", CELLS)
def test_cell_runs_end_to_end_and_is_correct(cpu_kernels, name):
    cell = tiny_cell(name)
    result = run_tiny(cell)
    last_line_ok(result)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 10
    want = {m["name"] for m in cell.metrics("end_to_end")}
    assert set(result["metrics"]) == want and "setup_s" in want
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert result["compared"]["responses"]["value"] > 10


@pytest.mark.parametrize("name", CELLS)
def test_traced_run_reports_no_device_metric_off_the_chip(cpu_kernels, name):
    """On the CPU the trace holds no TPU plane: every device_trace metric
    is left out rather than read from host time, and busy_s is absent."""
    cell = tiny_cell(name)
    result = run_tiny(cell, traced=True)
    last_line_ok(result)
    assert result["correct"] is True
    by_source = {m["name"]: m["source"] for m in cell.metrics("per_layer")}
    assert set(result["metrics"]) <= set(by_source)
    assert not [n for n in result["metrics"]
                if by_source[n] == "device_trace"]
    assert {n for n, s in by_source.items() if s != "device_trace"} <= set(
        result["metrics"])
    assert "busy_s" not in result["device"] and "breakdown" not in result
    assert result["metrics"]["compiles_in_window" + (
        ".tput" if name == "msmarco_closed" else ".lat")]["value"] == 0


def _alter_score(qi, resp):
    if qi % 5 == 0 and resp["hits"]["hits"]:
        resp["hits"]["hits"][0]["_score"] *= 1.001
    return resp


def _swap_in_a_stranger(qi, resp):
    hits = resp["hits"]["hits"]
    if qi % 5 == 0 and hits:
        taken = {h["_id"] for h in hits}
        hits[-1]["_id"] = next(str(i) for i in range(100)
                               if str(i) not in taken)
    return resp


def _drop_a_hit(qi, resp):
    if qi % 5 == 0:
        resp["hits"]["hits"] = resp["hits"]["hits"][:-1]
    return resp


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("tamper,number", [
    (_alter_score, "score_err"), (_swap_in_a_stranger, None),
    (_drop_a_hit, "malformed")])
def test_an_altered_answer_flips_correct(cpu_kernels, name, tamper, number):
    result = run_tiny(tiny_cell(name), tamper=tamper)
    last_line_ok(result)
    assert result["correct"] is False
    over = [n for n, c in result["compared"].items()
            if n != "responses" and c["value"] > c["limit"]]
    assert over and (number is None or number in over)


def test_bm25_broken_where_the_answer_is_produced(cpu_kernels, monkeypatch):
    """The program's own cross-segment merge loses its best hit: the
    comparison sees a doc left out that beats the worst one kept."""
    from opensearch_tpu.search.executor import ShardSearcher

    merge = ShardSearcher._merge_topk

    def lossy(self, per_seg, k_want, total, max_score):
        rows, total, max_score = merge(self, per_seg, k_want + 1, total,
                                       max_score)
        return rows[1:], total, max_score

    monkeypatch.setattr(ShardSearcher, "_merge_topk", lossy)
    result = run_tiny(tiny_cell("msmarco_closed"))
    assert result["correct"] is False
    assert (result["compared"]["rank_gap"]["value"]
            > result["compared"]["rank_gap"]["limit"])


def test_knn_broken_where_the_answer_is_produced(cpu_kernels, monkeypatch):
    """The scan runs on operands rounded to bfloat16, one pass of the
    matrix unit: the control, planted in the program itself."""
    import jax.numpy as jnp

    from opensearch_tpu.ops import knn as knn_ops

    exact = knn_ops.knn_topk

    def one_pass(vectors, valid, query, *, space, k):
        def low(a):
            return a.astype(jnp.bfloat16).astype(jnp.float32)

        return exact(low(vectors), valid, low(query), space=space, k=k)

    monkeypatch.setattr(knn_ops, "knn_topk", one_pass)
    result = run_tiny(tiny_cell("sift_paced"))
    assert result["correct"] is False


def test_a_device_fault_answered_from_the_host_flips_correct(cpu_kernels):
    """The search path answers a failing kernel from a byte-identical
    host path with a 200: right answers, no device work."""
    from opensearch_tpu.testing.fault_injection import DeviceFaultInjector

    inj = DeviceFaultInjector(seed=3)
    inj.dispatch_error("run_topk", times=1)
    cell = tiny_cell("msmarco_closed")
    with inj:
        with pytest.raises(RuntimeError, match="did not do the work"):
            run_tiny(cell)                  # the fault hits set-up


def test_a_fault_inside_the_window_is_counted(cpu_kernels, monkeypatch):
    cell = tiny_cell("msmarco_closed")
    real = harness.Served.device_faults
    calls = []

    def faults(self):
        calls.append(1)
        count, lines = real(self)
        return (count, lines) if len(calls) == 1 else (count + 2,
                                                       lines + ["planted"])

    monkeypatch.setattr(harness.Served, "device_faults", faults)
    result = run_tiny(cell)
    assert result["correct"] is False
    assert result["compared"]["device_faults"]["value"] == 2


def test_a_failed_request_counts_and_misses(cpu_kernels):
    def fail(qi, resp):
        if qi == 3:
            resp["_shards"]["failed"] = 1
        return resp

    result = run_tiny(tiny_cell("sift_paced"), seconds=2.0, tamper=fail)
    assert result["failed"] == 1 and result["correct"] is False


def test_no_chip_no_result():
    """Held to the CPU, the command exits non-zero before loading anything
    and prints no result line; BENCH_RUN is the driver's own."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="7")
    r = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", "sift_paced",
         "--seed", "2147483659", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == 3
    assert r.stdout.strip() == ""
    assert "no chip" in r.stderr


def test_unknown_device_has_no_peaks():
    from benchmarks import peaks

    with pytest.raises(KeyError):
        peaks.peaks_of("cpu")
    assert peaks.least_seconds("TPU v5 lite", 819e9, 0.0) == 1.0


def test_seed_gives_the_same_inputs():
    def first_queries(cell, seed):
        data = cell.kind.generate(cell.cfg, seed)
        return [np.asarray(q).tolist()
                for q in cell.kind.queries(cell.cfg, data, seed)[:5]]

    for name in CELLS:
        cell = tiny_cell(name)
        big = 2 ** 31 + 7                  # more than 32 signed bits hold
        assert first_queries(cell, big) == first_queries(cell, big)
        assert first_queries(cell, big) != first_queries(cell, big + 1)
