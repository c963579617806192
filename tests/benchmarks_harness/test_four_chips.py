"""Room for a cell on four chips: the chip rule the benchmark keeps, a
copy of the benchmark with a four-chip cell appended that joins up and
runs, the warm-up's look at every shard, and the count of chips a cell
asks for.  (The trace of four device planes is test_trace_reduction.py's.)"""

import dataclasses
import json
import os
import shutil
import types

import pytest

from benchmarks import harness
from bench_tiny import (TINY, assert_joins_up, chips_the_benchmark_allows,
                        last_line_ok, run_tiny)

# the shape of the next cell: one index of four shards, one a chip
MESH = {"name": "msmarco_mesh4", "config": "msmarco-passage-bm25-4shard",
        "traffic": "closed", "chips": 4,
        "why": "an index of four shards, one a chip: the merge across chips"}
SOURCE_CONFIG = "msmarco-passage-bm25"


def _bench() -> dict:
    return harness._json(os.path.join(harness.ROOT, "BENCHMARK.json"))


def _cells(*chips) -> list:
    return [{"name": f"c{i}", "chips": c} for i, c in enumerate(chips)]


@pytest.mark.parametrize("chips,taken", [
    ((1,), True), ((4,), True),                   # one four-chip cell may
    ((1, 4), True), ((4, 4), False),
    ((1, 1, 4, 4), True), ((1, 1, 4, 4, 4), False),
    ((1,) * 5 + (4,) * 5, True), ((1,) * 4 + (4,) * 6, False),
    ((1, 2), False), ((1, 8), False), ((0,), False)])
def test_the_chip_rule(chips, taken):
    assert chips_the_benchmark_allows(_cells(*chips)) is taken


def test_the_committed_benchmark_keeps_the_chip_rule():
    workloads = _bench()["workloads"]
    assert chips_the_benchmark_allows(workloads)
    assert chips_the_benchmark_allows(workloads + [MESH])
    # four-chip cells up to half of all cells are taken, one more is not
    ones = sum(w["chips"] == 1 for w in workloads)
    most = workloads + [dict(MESH, name=f"mesh{i}")
                        for i in range(ones - (len(workloads) - ones))]
    assert chips_the_benchmark_allows(most)
    assert not chips_the_benchmark_allows(
        most + [dict(MESH, name="one_more")])


@pytest.fixture
def four_chip_checkout(tmp_path):
    """A checkout of the benchmark with ``MESH`` appended as a later change
    would append it: a configuration file and its reference beside the
    others, the cell, and its name in the lists of the metrics it
    reports."""
    root = tmp_path / "checkout"
    configs = root / "benchmarks" / "configs"
    os.makedirs(root)
    shutil.copytree(os.path.join(harness.HERE, "configs"), configs)
    cfg = json.loads((configs / f"{SOURCE_CONFIG}.json").read_text())
    cfg["name"] = MESH["config"]
    (configs / f"{MESH['config']}.json").write_text(json.dumps(cfg))
    shutil.copy(configs / f"{SOURCE_CONFIG}.reference.py",
                configs / f"{MESH['config']}.reference.py")
    bench = _bench()
    entry, = [c for c in bench["configs"] if c["name"] == SOURCE_CONFIG]
    bench["configs"].append(dict(
        entry, name=MESH["config"],
        file=f"benchmarks/configs/{MESH['config']}.json"))
    bench["workloads"].append(dict(MESH))
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m and (m["name"] == "qps"
                                 or m["name"].endswith(".tput")):
            m["workloads"].append(MESH["name"])
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(root)


def test_a_four_chip_cell_joins_up(four_chip_checkout):
    assert_joins_up(four_chip_checkout)
    cell = harness.load_cell(MESH["name"], root=four_chip_checkout)
    assert cell.chips == 4 and cell.cfg["name"] == MESH["config"]
    assert {m["name"] for m in cell.metrics("end_to_end")} == {"qps",
                                                               "setup_s"}
    assert "dispatches_per_query.tput" in {
        m["name"] for m in cell.metrics("per_layer")}
    workloads = cell.bench["workloads"]
    names = [w["name"] for w in workloads]
    assert len(set(names)) == len(names) >= 10
    pairs = [(w["config"], w["traffic"]) for w in workloads]
    assert len(set(pairs)) == len(pairs)


def test_a_four_chip_cell_runs_at_a_tiny_size(cpu_kernels, four_chip_checkout):
    cell = harness.load_cell(MESH["name"], root=four_chip_checkout)
    # half of bench_tiny's docs, as test_span_metrics.py: programs of
    # other shapes than test_warmup_enumeration.py's count from nothing
    cell = dataclasses.replace(
        cell, cfg={**cell.cfg, **TINY["text_bm25"], "n_docs": 2048},
        mix={**cell.mix, "clients": 2, "warmup_s": 0.3})
    result = run_tiny(cell, traced=True)
    last_line_ok(result)
    assert result["correct"] is True and result["failed"] == 0
    assert result["device"]["count"] >= cell.chips   # the CPU's eight
    assert "dispatches_per_query.tput" in result["metrics"]


class _Kind:
    @staticmethod
    def warmup_queries(_cfg, _data):
        return [((4, 4096), [1, 2]), ((4, 16384), [3, 4]),
                ((8, 4096), [5, 6, 7, 8, 9])]

    @staticmethod
    def body(_cfg, terms):
        return {"query": {"terms": terms}}


class _Served:
    """An index that answers every request; a profiled one with
    ``shards`` as its profile (no profile at all where None)."""

    def __init__(self, shards):
        self.shards, self.profiled = shards, 0

    def search(self, body):
        resp = {"hits": {"hits": []}, "_shards": {"failed": 0},
                "timed_out": False}
        if body.get("profile"):
            self.profiled += 1
            if self.shards is not None:
                resp["profile"] = {"shards": self.shards}
        return resp


_CELL = types.SimpleNamespace(kind=_Kind, cfg={})
_DEVICE = {"engine": {"execution_path": "device"}}


@pytest.mark.parametrize("shards", [
    [_DEVICE], [_DEVICE] * 4,
    [_DEVICE, {}, {"engine": {}}, _DEVICE]])   # a shard that names none
def test_warm_up_passes_where_every_shard_ran_on_the_device(shards):
    served = _Served(shards)
    assert harness.warm_programs(_CELL, served, None) == 3
    assert served.profiled == 1


def test_warm_up_refuses_a_shard_served_on_the_host():
    shards = [_DEVICE, {"engine": {"execution_path": "host"}}, _DEVICE,
              _DEVICE]
    with pytest.raises(RuntimeError, match=r"\[host\] in shard 1 of 4"):
        harness.warm_programs(_CELL, _Served(shards), None)


@pytest.mark.parametrize("shards", [None, []])
def test_warm_up_refuses_a_profile_without_shards(shards):
    with pytest.raises(RuntimeError, match="no shard's profile"):
        harness.warm_programs(_CELL, _Served(shards), None)


def test_a_cell_needs_as_many_chips_as_it_asks_for(monkeypatch):
    import jax

    with pytest.raises(harness.NoChip):          # the CPU is no chip
        harness.find_chip(1)
    n = len(jax.devices())
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert harness.find_chip(min(4, n))["count"] == n
    with pytest.raises(harness.NoChip, match=f"needs {n + 1} TPU"):
        harness.find_chip(n + 1)
