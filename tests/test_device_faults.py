"""Accelerator fault tolerance (PR 15).

Covers the per-kernel-class circuit breakers
(``common/device_health.py``), the seeded ``DeviceFaultInjector``
(``testing/fault_injection.py``), byte-identity of every degraded path
(tripped-breaker host scores == healthy device scores; poison-recompute
== clean run), restage-failure eviction, partial-results degradation of
non-fallbackable plans, mesh demotion to the counted host scatter, the
QoS controller's device-duress adaptation, the ``device_oom`` /
``device_poison`` / ``device_slow`` / ``device_mesh_loss`` /
``device_heal`` soak directives with their SLOs and two-run
determinism, the ``_nodes/stats`` ``device.health`` / ``/_metrics``
surfaces, and the ``tools/check_degraded_paths.py`` tier-1 lint.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from opensearch_tpu.common.device_health import (DeviceDegradedError,
                                                 DeviceHealthService,
                                                 check_finite,
                                                 device_health,
                                                 is_device_error)
from opensearch_tpu.common.device_ledger import device_ledger
from opensearch_tpu.common.telemetry import flight_recorder, metrics
from opensearch_tpu.mapping.mapper import DocumentMapper
from opensearch_tpu.index.segment import SegmentWriter
from opensearch_tpu.search.executor import ShardSearcher
from opensearch_tpu.testing.fault_injection import (DeviceFaultInjector,
                                                    InjectedDeviceError,
                                                    InjectedDispatchError,
                                                    InjectedOOMError)

TOOLS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools")


class FakeClock:
    def __init__(self, t=100.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


@pytest.fixture(autouse=True)
def _clean_device_state():
    """Health service and ledger are process-global: reset them around
    every test."""
    device_health().reset()
    device_ledger().reset()
    yield
    device_health().reset()
    device_ledger().reset()


MAPPING = {"properties": {"t": {"type": "text"},
                          "k": {"type": "keyword"},
                          "n": {"type": "long"}}}


def _searcher(n_segs=3):
    mapper = DocumentMapper(MAPPING)
    texts = [["alpha beta", "beta gamma", "alpha alpha gamma"],
             ["beta beta delta", "alpha gamma", "gamma delta"],
             ["alpha delta", "beta", "alpha beta gamma delta"]]
    segs = []
    for i in range(n_segs):
        parsed = [mapper.parse(str(i * 3 + j),
                               {"t": t, "k": f"g{j % 2}", "n": i * 3 + j})
                  for j, t in enumerate(texts[i % len(texts)])]
        segs.append(SegmentWriter().build(parsed, f"s{i}"))
    return ShardSearcher(segs, mapper, index_name="faultix")


BODY = {"query": {"match": {"t": "alpha gamma"}}, "size": 5}


# -- classifier + sanity guard ---------------------------------------------

def test_is_device_error_classifier():
    assert is_device_error(InjectedOOMError("RESOURCE_EXHAUSTED"))
    assert is_device_error(InjectedDispatchError("boom"))
    assert is_device_error(MemoryError("alloc"))
    assert not is_device_error(ValueError("query"))
    assert not is_device_error(KeyError("x"))
    from opensearch_tpu.common.breakers import CircuitBreakingError
    assert not is_device_error(CircuitBreakingError("breaker tripped"))


def test_check_finite_accepts_neginf_sentinel():
    assert check_finite(np.array([1.0, -np.inf, 0.0], np.float32)) == 0
    assert check_finite(np.array([1.0, np.nan], np.float32)) == 1
    assert check_finite(np.array([np.inf, np.nan], np.float32)) == 2
    assert check_finite(np.array([1, 2, 3], np.int32)) == 0


# -- the breaker state machine ---------------------------------------------

def test_breaker_state_machine_trip_probe_close():
    clock = FakeClock()
    dh = DeviceHealthService(clock=clock)
    dh.set_failure_threshold(2)
    dh.set_open_interval_s(5.0)
    assert dh.allow("dispatch")
    dh.record_failure("dispatch", InjectedDispatchError("a"))
    assert dh.allow("dispatch")          # one failure: still closed
    dh.record_failure("dispatch", InjectedDispatchError("b"))
    st = dh.stats()["breakers"]["dispatch"]
    assert st["state"] == "open" and st["trips"] == 1
    assert not dh.allow("dispatch")      # open, inside cooldown
    clock.advance(4.0)
    assert not dh.allow("dispatch")
    clock.advance(1.5)
    assert dh.allow("dispatch")          # cooldown elapsed: half-open
    assert dh.stats()["breakers"]["dispatch"]["state"] == "half_open"
    # failed probe re-opens WITHOUT a new trip
    dh.record_failure("dispatch", InjectedDispatchError("c"))
    st = dh.stats()["breakers"]["dispatch"]
    assert st["state"] == "open" and st["trips"] == 1
    clock.advance(5.5)
    assert dh.allow("dispatch")
    dh.record_success("dispatch")        # successful probe closes
    st = dh.stats()["breakers"]["dispatch"]
    assert st["state"] == "closed" and st["closes"] == 1
    assert dh.breaker_states()["dispatch"] == "closed"
    assert dh.tripped_kinds() == ["dispatch"]


def test_breaker_success_resets_streak_and_disabled_never_trips():
    dh = DeviceHealthService(clock=FakeClock())
    dh.set_failure_threshold(2)
    dh.record_failure("batch", InjectedDispatchError("a"))
    dh.record_success("batch")
    dh.record_failure("batch", InjectedDispatchError("b"))
    assert dh.stats()["breakers"]["batch"]["state"] == "closed"
    dh.set_enabled(False)
    for _ in range(5):
        dh.record_failure("mesh", InjectedDispatchError("x"))
    assert dh.stats()["breakers"]["mesh"]["state"] == "closed"
    assert dh.allow("mesh")


def test_record_failure_dedups_one_exception_across_layers():
    dh = DeviceHealthService(clock=FakeClock())
    exc = InjectedOOMError("once")
    dh.record_failure("staging", exc)
    dh.record_failure("dispatch", exc)   # layered handler: same fault
    st = dh.stats()["breakers"]
    assert st["staging"]["failures"] == 1
    assert st["dispatch"]["failures"] == 0


# -- the injector -----------------------------------------------------------

def test_injector_seeded_probabilistic_determinism():
    def fired_pattern(seed):
        inj = DeviceFaultInjector(seed=seed)
        rule = inj.dispatch_error(probability=0.5)
        return [rule.matches("dispatch", ("run_topk",))
                for _ in range(32)]
    assert fired_pattern(7) == fired_pattern(7)
    assert fired_pattern(7) != fired_pattern(8)


def test_injector_rule_matching_and_bounds():
    inj = DeviceFaultInjector(seed=1)
    rule = inj.oom("seg_a*", times=2)
    assert not rule.matches("dispatch", ("seg_a1",))   # wrong op
    assert not rule.matches("stage", ("seg_b1",))      # wrong name
    assert rule.matches("stage", ("seg_a1", "postings"))
    assert rule.matches("stage", ("seg_a2",))
    assert not rule.matches("stage", ("seg_a3",))      # times exhausted
    sticky = inj.dispatch_error()
    for _ in range(5):
        assert sticky.matches("dispatch", ("run_full",))
    inj.remove(sticky)
    assert inj._match("dispatch", ("run_full",)) is None
    inj.clear()
    assert inj.stats()["rules"] == 0


# -- byte-identity of the degraded paths ------------------------------------

def test_tripped_breaker_host_results_byte_identical():
    s = _searcher()
    clean = s.search(dict(BODY))
    assert clean["hits"]["hits"]
    dh = device_health()
    dh.set_failure_threshold(2)
    trips0 = metrics().counter("device.breaker.trips").value
    inj = DeviceFaultInjector(seed=4)
    inj.dispatch_error()                 # sticky: every dispatch dies
    with inj:
        r1 = s.search(dict(BODY))        # faults -> per-segment host
    assert json.dumps(r1["hits"], sort_keys=True) == \
        json.dumps(clean["hits"], sort_keys=True)
    assert dh.stats()["breakers"]["dispatch"]["trips"] >= 1
    assert metrics().counter("device.breaker.trips").value > trips0
    # breaker held open (real cooldown): the host route serves without
    # touching the device at all, still byte-identical
    dh.set_open_interval_s(3600.0)
    r2 = s.search(dict(BODY))
    assert json.dumps(r2["hits"], sort_keys=True) == \
        json.dumps(clean["hits"], sort_keys=True)
    # the trip left a flight-recorder capture
    assert any(c["trigger"] == "device_breaker_trip"
               for c in flight_recorder().captures())


def test_poison_recompute_byte_identical_with_capture():
    s = _searcher()
    clean = s.search(dict(BODY))
    inj = DeviceFaultInjector(seed=3)
    inj.poison_topk(times=2)
    with inj:
        poisoned = s.search(dict(BODY))
    assert json.dumps(poisoned["hits"], sort_keys=True) == \
        json.dumps(clean["hits"], sort_keys=True)
    assert device_health().stats()["poisoned_results"] >= 1
    assert metrics().counter("device.poisoned_results").value >= 1
    caps = [c for c in flight_recorder().captures()
            if c["trigger"] == "device_poisoned_result"]
    assert caps and caps[0]["detail"]["kernel"] == "run_topk"


def test_staging_oom_marks_evicted_and_falls_back():
    s = _searcher()
    clean = s.search(dict(BODY))
    led = device_ledger()
    led.set_budget(1)                    # force-evict every staging
    led.set_budget(None)
    rf0 = metrics().counter("device.restage_failures").value
    inj = DeviceFaultInjector(seed=5)
    inj.oom()                            # sticky RESOURCE_EXHAUSTED
    with inj:
        r = s.search(dict(BODY))         # term-bag: host fallback
        assert json.dumps(r["hits"], sort_keys=True) == \
            json.dumps(clean["hits"], sort_keys=True)
        with pytest.raises(InjectedOOMError):
            s.segments[0].device()       # direct restage still fails
    assert metrics().counter("device.restage_failures").value > rf0
    assert s.segments[0]._device_evicted
    # healed: the next device() restages and re-counts
    restages0 = device_ledger().restages
    s.segments[0].device()
    assert device_ledger().restages == restages0 + 1
    assert not s.segments[0]._device_evicted


def test_non_fallbackable_plan_degrades_partial_not_500(tmp_path):
    from opensearch_tpu.indices.service import IndicesService
    svc = IndicesService(str(tmp_path))
    svc.create("ix", {"settings": {"number_of_shards": 1},
                      "mappings": MAPPING})
    ix = svc.get("ix")
    try:
        for i in range(8):
            ix.index_doc(str(i), {"t": f"alpha w{i % 3}", "n": i})
        ix.refresh()
        sort_body = {"query": {"match_all": {}}, "size": 3,
                     "sort": [{"n": "asc"}]}
        ok = ix.search(dict(sort_body))
        assert ok["_shards"]["failed"] == 0
        led = device_ledger()
        led.set_budget(1)
        led.set_budget(None)
        deg0 = metrics().counter("device.degraded_searches").value
        inj = DeviceFaultInjector(seed=6)
        inj.oom()
        with inj:
            r = ix.search(dict(sort_body))
            assert r["_shards"]["failed"] >= 1
            assert r["_shards"]["failures"][0]["reason"]["type"] == \
                "device_degraded_exception"
            assert r["hits"]["hits"] == []
            # all-or-nothing semantics still raise (503-class), not 500
            with pytest.raises(DeviceDegradedError):
                ix.search(dict(sort_body,
                               allow_partial_search_results=False))
        assert metrics().counter(
            "device.degraded_searches").value > deg0
        # healed: full results come back
        r = ix.search(dict(sort_body))
        assert r["_shards"]["failed"] == 0 and r["hits"]["hits"]
    finally:
        svc.close()


def test_batch_group_device_fault_falls_back_byte_identical():
    s = _searcher()
    bodies = [{"query": {"match": {"t": "alpha"}}, "size": 4},
              {"query": {"match": {"t": "gamma delta"}}, "size": 4}]
    clean = s.msearch([dict(b) for b in bodies])
    inj = DeviceFaultInjector(seed=9)
    inj.dispatch_error("batch_impact_union_topk")
    with inj:
        faulted = s.msearch([dict(b) for b in bodies])
    assert json.dumps([r["hits"] for r in faulted], sort_keys=True) == \
        json.dumps([r["hits"] for r in clean], sort_keys=True)
    assert device_health().stats()["breakers"]["batch"]["failures"] >= 1
    # poisoned batch kernel: sanity guard discards + recomputes
    inj2 = DeviceFaultInjector(seed=10)
    inj2.poison_topk("batch_impact_union_topk", times=1)
    with inj2:
        poisoned = s.msearch([dict(b) for b in bodies])
    assert json.dumps([r["hits"] for r in poisoned],
                      sort_keys=True) == \
        json.dumps([r["hits"] for r in clean], sort_keys=True)
    assert device_health().stats()["poisoned_results"] >= 1


def test_mesh_demotes_to_host_scatter(tmp_path):
    from opensearch_tpu.indices.service import IndicesService
    svc = IndicesService(str(tmp_path))
    svc.create("mx", {"settings": {"number_of_shards": 2},
                      "mappings": MAPPING})
    ix = svc.get("mx")
    try:
        for i in range(10):
            ix.index_doc(str(i), {"t": f"alpha w{i % 3}", "n": i})
        ix.refresh()
        body = {"query": {"match": {"t": "alpha"}}, "size": 5}
        fb0 = metrics().counter("search.mesh.fallback").value
        inj = DeviceFaultInjector(seed=11)
        inj.lose_mesh_member()
        with inj:
            # drive the mesh entry directly: member loss (or a mesh
            # that cannot build on a 1-device host) must demote to the
            # host scatter fallback, never raise
            r = ix._mesh_search(dict(body))
        assert r["hits"]["total"]["value"] > 0
        assert metrics().counter("search.mesh.fallback").value > fb0
        assert device_health().stats()["breakers"]["mesh"][
            "failures"] >= 1
        # an OPEN mesh breaker routes straight to the fallback without
        # re-attempting the collective
        dh = device_health()
        dh.set_failure_threshold(1)
        dh.set_open_interval_s(3600.0)
        dh.record_failure("mesh", InjectedDispatchError("down"))
        fb1 = metrics().counter("search.mesh.fallback").value
        r2 = ix._mesh_search(dict(body))
        assert r2["hits"]["total"]["value"] > 0
        assert metrics().counter("search.mesh.fallback").value > fb1
    finally:
        svc.close()


# -- QoS: device duress adapts the node_duress thresholds -------------------

class _StubAdmission:
    tenant_shares: dict = {}
    default_share = 1.0

    def __init__(self):
        self.tenant_penalty = {}

    def stats(self):
        return {"rejected_count": 0, "shed_count": 0, "occupancy": 0.2,
                "tenants": {}}


class _StubInsights:
    coalesce_window_ms = 10.0

    def stats(self):
        return {"records": 0, "coalescable_fraction": 0.0}


def test_qos_device_evidence_tightens_and_relaxes_duress_thresholds():
    from opensearch_tpu.common.tasks import TaskManager
    from opensearch_tpu.search.backpressure import \
        SearchBackpressureService
    from opensearch_tpu.search.qos import QosController

    bp = SearchBackpressureService(TaskManager("t"), clock=FakeClock(),
                                   cpu_load_fn=lambda: 0.0,
                                   cpu_threshold=0.9,
                                   heap_threshold=0.85)
    ctl = QosController(admission=_StubAdmission(),
                        insights=_StubInsights(), backpressure=bp,
                        clock=FakeClock())
    ctl.set_enabled(True)
    ctl.hysteresis_ticks = 1
    ctl.run_once()                       # baseline snapshot
    # device duress: breaker trips + poisoned results since last tick
    metrics().counter("device.breaker.trips").inc()
    metrics().counter("device.poisoned_results").inc(2)
    out = ctl.run_once()
    knobs = [a["knob"] for a in out["adapted"]]
    assert "node_duress.cpu_threshold" in knobs
    assert "node_duress.heap_threshold" in knobs
    assert bp.trackers["cpu_usage"].threshold == pytest.approx(0.45)
    assert bp.trackers["heap_usage"].threshold == pytest.approx(0.425)
    rec = next(a for a in out["adapted"]
               if a["knob"] == "node_duress.cpu_threshold")
    assert rec["evidence"]["device_trips"] == 1
    assert rec["evidence"]["poisoned_results"] == 2
    assert "node_duress" in ctl.stats()["knobs"]
    # clean ticks relax additively back toward the configured base
    out = ctl.run_once()
    assert any(a["knob"].startswith("node_duress.")
               for a in out["adapted"])
    assert bp.trackers["cpu_usage"].threshold == pytest.approx(0.5)
    for _ in range(12):
        ctl.run_once()
    assert bp.trackers["cpu_usage"].threshold == pytest.approx(0.9)
    assert bp.trackers["heap_usage"].threshold == pytest.approx(0.85)


# -- soak: the device-fault directive class ---------------------------------

def test_device_soak_schedule_two_run_determinism():
    from opensearch_tpu.testing.workload import FaultSchedule, SoakConfig
    cfg = SoakConfig.device(seed=42)
    s1 = FaultSchedule.generate(cfg)
    s2 = FaultSchedule.generate(SoakConfig.device(seed=42))
    assert s1 == s2
    kinds = [d["fault"] for d in s1]
    for want in ("device_slow", "device_poison", "device_oom",
                 "device_mesh_loss", "device_heal"):
        assert want in kinds, kinds
    # paired windows stay ordered under the jitter
    assert kinds.index("device_poison") < kinds.index("device_heal")
    steps = [d["step"] for d in s1 if d["fault"].startswith("device_")]
    assert steps == sorted(steps)
    # a different seed moves the schedule
    assert FaultSchedule.generate(SoakConfig.device(seed=43)) != s1
    # the base (non-device) schedule is untouched by the flag
    base = FaultSchedule.generate(SoakConfig(seed=42))
    assert [d for d in s1 if not d["fault"].startswith("device_")] == base


def test_device_soak_slos(tmp_path):
    """The acceptance scenario: OOM + poison + slow + mesh-loss + heal
    under traffic — zero unexpected 5xx, doc/score convergence vs the
    uninjected control, >= 1 breaker trip visible, breakers re-closed
    after heal, >= 1 poisoned result caught."""
    from opensearch_tpu.testing.workload import run_device_soak
    rep = run_device_soak(str(tmp_path / "devsoak"), seed=42)
    by_slo = {v["slo"]: v for v in rep["verdicts"]}
    assert by_slo["unexpected_errors"]["ok"], \
        rep["chaos"]["unexpected_errors"]
    assert by_slo["convergence"]["ok"]
    assert by_slo["device_breaker_trip"]["ok"]
    assert by_slo["device_breaker_reclose"]["ok"]
    assert by_slo["device_poison_detected"]["ok"]
    assert rep["slo_ok"], rep["verdicts"]
    dev = rep["chaos"]["device"]
    assert dev["breaker_trips"] >= 1
    assert dev["poisoned"] >= 1
    assert dev["restage_failures"] >= 1
    assert dev["host_fallbacks"] >= 1
    assert dev["mesh_fallbacks"] >= 1
    assert dev["breaker_states"]["staging"] == "closed"
    assert dev["breaker_states"]["dispatch"] == "closed"
    # the injector's patches are gone
    assert "stage" not in device_ledger().__dict__


@pytest.mark.slow
def test_device_soak_two_run_verdict_determinism(tmp_path):
    from opensearch_tpu.testing.workload import run_device_soak
    r1 = run_device_soak(str(tmp_path / "a"), seed=7)
    r2 = run_device_soak(str(tmp_path / "b"), seed=7)
    assert r1["chaos"]["schedule"] == r2["chaos"]["schedule"]
    assert [(v["slo"], v["ok"]) for v in r1["verdicts"]] == \
        [(v["slo"], v["ok"]) for v in r2["verdicts"]]
    assert r1["chaos"]["final_state"] == r2["chaos"]["final_state"]


# -- surfaces ---------------------------------------------------------------

def test_nodes_stats_health_metrics_and_dynamic_settings(tmp_path):
    from opensearch_tpu.node import Node
    node = Node(str(tmp_path / "node"), port=0)
    try:
        device_health().record_failure(
            "dispatch", InjectedDispatchError("x"))
        s, stats = node.rest.dispatch("GET", "/_nodes/stats", {}, None,
                                      "application/json", headers={})
        assert s == 200
        health = stats["nodes"][node.node_id]["device"]["health"]
        assert health["enabled"] is True
        assert health["breakers"]["dispatch"]["failures"] == 1
        assert set(health["breakers"]) >= {"staging", "dispatch",
                                           "batch", "mesh"}
        s, text = node.rest.dispatch("GET", "/_metrics", {}, None,
                                     "application/json", headers={})
        assert s == 200
        body = text.text if hasattr(text, "text") else str(text)
        assert 'opensearch_tpu_device_breaker_open{kernel="dispatch"}' \
            in body
        # dynamic knobs reach the process-global service immediately
        s, _ = node.rest.dispatch(
            "PUT", "/_cluster/settings", {},
            json.dumps({"transient": {
                "device.health.failure_threshold": 7,
                "device.health.open_interval_s": 1.5,
                "device.health.enabled": False}}).encode(),
            "application/json", headers={})
        assert s == 200
        dh = device_health()
        assert dh.failure_threshold == 7
        assert dh.open_interval_s == 1.5
        assert dh.enabled is False
        s, cstats = node.rest.dispatch("GET", "/_cluster/stats", {},
                                       None, "application/json",
                                       headers={})
        assert s == 200
        assert "breaker_trips" in cstats["device"]
        assert "poisoned_results" in cstats["device"]
    finally:
        node.stop()


def test_insight_outcome_device_degraded(tmp_path):
    from opensearch_tpu.node import Node
    node = Node(str(tmp_path / "node"), port=0)
    try:
        def call(method, path, body=None, ndjson=None):
            if ndjson is not None:
                raw = ("\n".join(json.dumps(x) for x in ndjson)
                       + "\n").encode()
                ctype = "application/x-ndjson"
            else:
                raw = (json.dumps(body).encode()
                       if body is not None else None)
                ctype = "application/json"
            return node.rest.dispatch(method, path, {}, raw, ctype,
                                      headers={})
        s, _ = call("PUT", "/dix", {"mappings": MAPPING})
        assert s == 200
        lines = []
        for i in range(6):
            lines.append({"index": {"_index": "dix", "_id": str(i)}})
            lines.append({"t": f"alpha w{i}", "n": i})
        s, r = call("POST", "/_bulk", ndjson=lines)
        assert s == 200
        node.indices.get("dix").refresh()
        led = device_ledger()
        led.set_budget(1)
        led.set_budget(None)
        inj = DeviceFaultInjector(seed=12)
        inj.oom()
        with inj:
            s, r = call("POST", "/dix/_search",
                        {"query": {"match_all": {}}, "size": 3,
                         "sort": [{"n": "asc"}]})
        # REST response: 200 with partial _shards, never a 500
        assert s == 200, r
        assert r["_shards"]["failed"] >= 1
        outcomes = node.insights.stats().get("outcomes", {})
        assert outcomes.get("device_degraded", 0) >= 1
    finally:
        node.stop()


# -- tier-1 lint ------------------------------------------------------------

def test_check_degraded_paths_lint_clean_on_repo():
    out = subprocess.run(
        [sys.executable, os.path.join(TOOLS, "check_degraded_paths.py")],
        capture_output=True, text=True)
    assert out.returncode == 0, out.stdout + out.stderr


def test_check_degraded_paths_lint_catches_and_annotates(tmp_path):
    tool = os.path.join(TOOLS, "check_degraded_paths.py")
    bad = tmp_path / "search"
    bad.mkdir()
    (bad / "swallow.py").write_text(
        "def f():\n"
        "    try:\n"
        "        g()\n"
        "    except XlaRuntimeError:\n"
        "        pass\n")
    out = subprocess.run([sys.executable, tool, str(tmp_path)],
                         capture_output=True, text=True)
    assert out.returncode == 1
    assert "swallow.py:4" in out.stdout
    # the classify-idiom (broad except + is_device_error) is in scope
    (bad / "swallow.py").write_text(
        "def f():\n"
        "    try:\n"
        "        g()\n"
        "    except Exception as e:\n"
        "        if is_device_error(e):\n"
        "            return None\n"
        "        raise\n")
    out = subprocess.run([sys.executable, tool, str(tmp_path)],
                         capture_output=True, text=True)
    assert out.returncode == 0, out.stdout  # classifier IS evidence
    (bad / "swallow.py").write_text(
        "def f():\n"
        "    try:\n"
        "        g()\n"
        "    except DeviceDegradedError:\n"
        "        return None\n")
    out = subprocess.run([sys.executable, tool, str(tmp_path)],
                         capture_output=True, text=True)
    assert out.returncode == 1
    # evidence (device.* metric) passes
    (bad / "swallow.py").write_text(
        "def f():\n"
        "    try:\n"
        "        g()\n"
        "    except DeviceDegradedError:\n"
        "        metrics().counter(\"device.degraded\").inc()\n")
    out = subprocess.run([sys.executable, tool, str(tmp_path)],
                         capture_output=True, text=True)
    assert out.returncode == 0, out.stdout
    # the degrade-ok annotation passes
    (bad / "swallow.py").write_text(
        "def f():\n"
        "    try:\n"
        "        g()\n"
        "    except DeviceDegradedError:  # degrade-ok\n"
        "        return None\n")
    out = subprocess.run([sys.executable, tool, str(tmp_path)],
                         capture_output=True, text=True)
    assert out.returncode == 0, out.stdout
