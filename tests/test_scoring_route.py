"""One lowering for a scored term bag on every backend (PR 31).

On the CPU backend, as on the chip, an undegraded search runs
``plan.run_topk`` a segment; ``TermBagPlan.host_topk`` is reached only as
the recovery from what ``ShardSearcher._topk`` and ``BatchGroup.run``
observe (breaker state, a device error, a non-finite result), is counted
as a host fallback, and is byte-identical to the kernels.  Nothing
selects a path from a switch any more.
"""

import json
import os
import re

import numpy as np
import pytest

from opensearch_tpu.common.device_health import device_health
from opensearch_tpu.common.device_ledger import device_ledger
from opensearch_tpu.index.segment import SegmentWriter
from opensearch_tpu.mapping.mapper import DocumentMapper
from opensearch_tpu.search import engine, insights
from opensearch_tpu.search.executor import ShardSearcher
from opensearch_tpu.testing.fault_injection import DeviceFaultInjector

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB = [f"w{i}" for i in range(24)]
N_SEGMENTS = 4
BODY = {"query": {"match": {"body": "w1 w2 w5"}}, "size": 10}


@pytest.fixture(autouse=True)
def clean_device_books(monkeypatch):
    monkeypatch.setattr(engine, "BATCHER_ENABLED", False)
    device_health().reset()
    device_ledger().reset()
    yield
    device_health().reset()
    device_ledger().reset()


@pytest.fixture
def searcher():
    mapper = DocumentMapper({"properties": {"body": {"type": "text"}}})
    rng = np.random.default_rng(31)
    writer = SegmentWriter()
    segs = []
    for s in range(N_SEGMENTS):
        docs = [mapper.parse(str(s * 50 + i),
                             {"body": " ".join(rng.choice(VOCAB, 9))})
                for i in range(50)]
        segs.append(writer.build(docs, f"route{s}"))
    # one more segment that holds none of BODY's terms: can-match prunes it
    docs = [mapper.parse(f"x{i}", {"body": "absent words only"})
            for i in range(5)]
    segs.append(writer.build(docs, "route_pruned"))
    return ShardSearcher(segs, mapper)


def _books() -> dict:
    stats = device_ledger().stats()
    return {"dispatches": stats["dispatches"],
            "host_fallbacks": stats["budget"]["host_fallbacks"],
            "fetch_arrays": stats["transfers"]["fetch"]["arrays"]}


def _moved(before: dict) -> dict:
    after = _books()
    return {k: after[k] - before[k] for k in before}


def _profiled(searcher, body=BODY):
    """(hits as bytes, the profile's engine block, the insight record)."""
    with insights.collecting() as records:
        resp = searcher.search(dict(body, profile=True))
    assert resp["_shards"]["failed"] == 0
    return (json.dumps(resp["hits"], sort_keys=True),
            resp["profile"]["shards"][0]["engine"], records[-1])


def test_unforced_search_runs_the_device_lowering_on_the_cpu_backend(
        searcher):
    import jax
    assert jax.default_backend() == "cpu"
    before = _books()
    hits, eng, record = _profiled(searcher)
    assert json.loads(hits)["hits"]
    assert eng["execution_path"] == "device"
    assert record["execution_path"] == "device"
    assert eng["segments"]["pruned_can_match"] == 1
    assert eng["segments"]["scanned"] == N_SEGMENTS
    # one program and one array back a segment that was not pruned, and
    # no segment answered from the host
    assert _moved(before) == {"dispatches": N_SEGMENTS,
                              "host_fallbacks": 0,
                              "fetch_arrays": N_SEGMENTS}
    # the same without a profiler
    before = _books()
    plain = searcher.search(dict(BODY))
    assert json.dumps(plain["hits"], sort_keys=True) == hits
    assert _moved(before)["dispatches"] == N_SEGMENTS
    assert _moved(before)["host_fallbacks"] == 0


def _open_breaker(kind: str) -> None:
    health = device_health()
    health.set_failure_threshold(1)
    health.set_open_interval_s(3600.0)
    health.record_failure(kind)


# trigger -> (arming, segments recovered on the host)
TRIGGERS = {
    "breaker_open_before_dispatch": (
        lambda _inj: _open_breaker("dispatch"), N_SEGMENTS),
    "dispatch_error_once": (
        lambda inj: inj.dispatch_error("run_topk", times=1), 1),
    "dispatch_error_sticky": (
        lambda inj: inj.dispatch_error("run_topk"), N_SEGMENTS),
    "non_finite_result_once": (lambda inj: inj.poison_topk(times=1), 1),
    "non_finite_result_every_segment": (
        lambda inj: inj.poison_topk(times=N_SEGMENTS), N_SEGMENTS),
}


@pytest.mark.parametrize("trigger", sorted(TRIGGERS))
def test_recovery_is_byte_identical_counted_and_reported(searcher, trigger):
    arm, recovered = TRIGGERS[trigger]
    clean, eng, _ = _profiled(searcher)
    assert eng["execution_path"] == "device"
    before = _books()
    inj = DeviceFaultInjector(seed=31)
    arm(inj)
    with inj:
        hits, eng, record = _profiled(searcher)
    assert hits == clean                   # ids and scores, bit for bit
    moved = _moved(before)
    assert moved["host_fallbacks"] == recovered
    want = "host" if recovered == N_SEGMENTS else "device"
    assert eng["execution_path"] == want
    assert record["execution_path"] == want
    # every unpruned segment was scored exactly once, here or there
    assert eng["segments"]["scanned"] == N_SEGMENTS
    if trigger.startswith("non_finite"):
        # the program ran and its result was thrown away
        assert moved["dispatches"] == N_SEGMENTS
        assert moved["fetch_arrays"] == N_SEGMENTS - recovered
        assert device_health().stats()["poisoned_results"] == recovered
    elif trigger == "dispatch_error_sticky":
        # the first errors trip the breaker, the rest never try
        assert moved["dispatches"] == 0
        assert device_health().stats()["breakers"]["dispatch"][
            "trips"] == 1
    else:
        assert moved["dispatches"] == N_SEGMENTS - recovered


def test_batch_group_under_an_open_breaker_equals_the_device_group(
        searcher):
    bodies = [{"query": {"match": {"body": q}}, "size": 7}
              for q in ("w1", "w2 w5", "w3 w4 w9", "absent", "w1 w7")]
    bodies.append({"query": {"match": {"body": "w2"}}, "size": 3})
    before = _books()
    with insights.collecting() as records:
        device = searcher.msearch([dict(b, profile=True) for b in bodies])
    assert _moved(before)["host_fallbacks"] == 0
    assert {r["execution_path"] for r in records} == {"device_batched"}
    _open_breaker("batch")
    before = _books()
    with insights.collecting() as records:
        host = searcher.msearch([dict(b, profile=True) for b in bodies])
    # two groups (size 7, size 3): one counted fallback each, no program
    assert _moved(before) == {"dispatches": 0, "host_fallbacks": 2,
                              "fetch_arrays": 0}
    assert {r["execution_path"] for r in records} == {"host_batched"}
    assert len(host) == len(device) == len(bodies)
    for member, (h, d) in enumerate(zip(host, device)):
        assert json.dumps(h["hits"], sort_keys=True) == \
            json.dumps(d["hits"], sort_keys=True), member
        h_eng = h["profile"]["shards"][0]["engine"]
        d_eng = d["profile"]["shards"][0]["engine"]
        assert h_eng["execution_path"] == "host_batched"
        assert d_eng["execution_path"] == "device_batched"
        assert h_eng["segments"] == d_eng["segments"]


def test_no_source_reads_or_writes_the_retired_switch():
    """``ops/bm25.py`` keeps one ``HOST_SCORING = False`` because a file of
    the benchmark (``tests/benchmarks_harness/conftest.py``) still sets
    the attribute; nothing else may name it, or the selection it made."""
    retired = re.compile("HOST_" + "SCORING|host_scoring_" + "enabled|"
                         "_HOST_" + "AUTO|_topk_host_" + "parallel")
    found = []
    for top in ("opensearch_tpu", "tests", "tools", "bench.py",
                "chip_smoke.py"):
        path = os.path.join(ROOT, top)
        files = [path] if os.path.isfile(path) else [
            os.path.join(d, f) for d, _dirs, fs in os.walk(path)
            for f in fs if f.endswith(".py")]
        for file in files:
            rel = os.path.relpath(file, ROOT)
            if rel.startswith(os.path.join("tests", "benchmarks_harness")) \
                    or file == os.path.abspath(__file__):
                continue
            with open(file, encoding="utf-8") as fh:
                for no, line in enumerate(fh, 1):
                    if retired.search(line):
                        found.append((rel, no, line.strip()))
    assert found == [(os.path.join("opensearch_tpu", "ops", "bm25.py"),
                      found[0][1], "HOST_" + "SCORING = False")], found
    # and the one host scorer call site a module
    calls = {}
    for mod in ("executor.py", "batch.py"):
        with open(os.path.join(ROOT, "opensearch_tpu", "search", mod),
                  encoding="utf-8") as fh:
            calls[mod] = fh.read().count(".host_topk(")
    assert calls == {"executor.py": 1, "batch.py": 1}
