"""Open-loop load harness tests (testing/loadgen.py).

Three contracts under test, mirroring the soak determinism pins:

- seeded determinism: identical seed => identical arrival schedule,
  identical per-pack request sequence, identical verdict key set;
- coordinated-omission-free measurement: latency is charged from the
  SCHEDULED arrival, so a stalled server inflates the tail by the
  queue time it caused (a closed-loop recorder would hide it);
- real-edge behavior: the packs run clean against a booted node at low
  offered load (zero 5xx), tenant attribution cross-checks hold, and
  every 429 under a squeezed admission limit carries a Retry-After
  hint the client surfaces.

Plus the two tier-1 lints this PR adds/extends:
``tools/check_open_loop.py`` (closed-loop measurement patterns) and
``tools/check_seeded_rng.py`` coverage of the loadgen module.
"""

import subprocess
import sys
import threading
import time

import pytest

from opensearch_tpu.testing.loadgen import (
    ENVELOPES,
    LoadgenRunner,
    arrival_schedule,
    default_packs,
    run_latency_under_load,
)

REPO = __file__.rsplit("/tests/", 1)[0]
TOOLS = REPO + "/tools"


def _ok_executor(op, tenant):
    return {"status": 200}


# -- arrival processes ------------------------------------------------------

def test_arrival_schedule_deterministic_sorted_bounded():
    for env in sorted(ENVELOPES):
        s1 = arrival_schedule(80, 2.0, seed=7, envelope=env)
        s2 = arrival_schedule(80, 2.0, seed=7, envelope=env)
        assert s1 == s2, env
        assert s1 == sorted(s1)
        assert all(0.0 <= t < 2.0 for t in s1)
        # thinning is normalized by the envelope mean: the realized
        # count stays near rate*duration for EVERY envelope shape
        assert 80 <= len(s1) <= 240, (env, len(s1))
    assert arrival_schedule(80, 2.0, seed=7) != \
        arrival_schedule(80, 2.0, seed=8)
    assert arrival_schedule(0, 2.0, seed=7) == []


def test_arrival_schedule_unknown_envelope_rejected():
    with pytest.raises(ValueError, match="unknown arrival envelope"):
        arrival_schedule(10, 1.0, seed=7, envelope="lunar")


# -- determinism pins (soak-style: two runs, same seed) ---------------------

def test_pack_request_sequences_deterministic():
    for pack in default_packs(n_docs=50, vocab_size=100):
        r1 = pack.requests(42, 8)
        r2 = pack.requests(42, 8)
        assert r1 == r2, pack.name
        assert len(r1) == 8
        assert pack.requests(42, 8) != pack.requests(43, 8), pack.name


def test_two_run_determinism():
    packs = default_packs(n_docs=50, vocab_size=100)
    run1 = LoadgenRunner(packs, _ok_executor, seed=42, duration_s=0.3)
    run2 = LoadgenRunner(packs, _ok_executor, seed=42, duration_s=0.3)
    for qps in (20, 60):
        assert run1.schedule(qps) == run2.schedule(qps)
    assert run1.schedule(20) != LoadgenRunner(
        packs, _ok_executor, seed=43, duration_s=0.3).schedule(20)
    # verdict KEYS are a pure function of the pack set — identical
    # across runs whether or not any 429/5xx occurred
    s1 = run1.sweep([20, 60])
    s2 = run2.sweep([20, 60])
    k1 = [v["slo"] for v in run1.verdicts(s1)]
    k2 = [v["slo"] for v in run2.verdicts(s2)]
    assert k1 == k2
    assert "server_errors_at_lowest_load" in k1
    for p in packs:
        assert f"retry_after_hint.{p.name}" in k1
        assert f"transport_errors.{p.name}" in k1
    # and the per-pack sent counts equal the schedules exactly
    for r1, r2 in zip(s1["points"], s2["points"]):
        assert {n: pr["sent"] for n, pr in r1["packs"].items()} == \
            {n: pr["sent"] for n, pr in r2["packs"].items()}


# -- coordinated-omission-free recording ------------------------------------

def test_latency_charged_from_scheduled_arrival():
    """A single-threaded stalled server: each request holds a lock for
    30ms.  Open-loop accounting must charge waiting requests their full
    queue delay — the tail reflects the backlog (hundreds of ms), not
    the 30ms service time a closed-loop recorder would report."""
    lock = threading.Lock()

    def stalled(op, tenant):
        with lock:
            time.sleep(0.03)
        return {"status": 200}

    packs = default_packs(n_docs=50, vocab_size=100)
    runner = LoadgenRunner(packs, stalled, seed=42, duration_s=0.5)
    point = runner.run_point(100)
    sent = sum(pr["sent"] for pr in point["packs"].values())
    assert sent >= 30
    worst_p99 = max(pr["p99_ms"] for pr in point["packs"].values()
                    if pr["sent"])
    # ~50 requests x 30ms serialized service => the last arrivals wait
    # most of a second; anything near 30ms means the recorder went
    # closed-loop
    assert worst_p99 > 300, worst_p99


def test_retry_honors_hint_and_counts_compliance():
    """429s are retried no earlier than the Retry-After hint (plus
    seeded jitter), and hint presence/absence is tallied per pack."""
    calls = []
    times = []
    lock = threading.Lock()

    def flaky(op, tenant):
        with lock:
            calls.append(op)
            times.append(time.monotonic())
            if len(calls) == 1:
                return {"status": 429, "retry_after": 0.2}
            if len(calls) == 2:
                return {"status": 200}
            return {"status": 429}          # hintless terminal 429

    packs = default_packs(n_docs=50, vocab_size=100)[:1]
    runner = LoadgenRunner(packs, flaky, seed=42, duration_s=0.05,
                           retry_limit=1, retry_jitter_s=0.0)
    # duration 0.05s at 40 qps -> at least 1 request; cap workers so
    # the call order above is meaningful only for the first request
    runner.max_workers = 1
    point = runner.run_point(40)
    pr = point["packs"][packs[0].name]
    assert pr["retries_429"] >= 1
    assert pr["retry_after_present"] >= 1
    # the retry of call #1 respected the 0.2s hint
    assert times[1] - times[0] >= 0.2
    if len(calls) > 2:                      # later requests hit hintless 429s
        assert pr["retry_after_missing"] >= 1


# -- real REST edge ---------------------------------------------------------

def test_real_edge_low_load_and_attribution(tmp_path):
    """One low offered-load point against a booted node: zero 5xx, all
    five tenant packs served, verdicts (including the admission- and
    insights-attribution cross-checks) all green."""
    rep = run_latency_under_load(
        str(tmp_path), seed=42, points=(10.0,), duration_s=1.0,
        n_docs=60, vocab_size=200, retry_wait_cap_s=0.5)
    assert rep["slo_ok"], [v for v in rep["verdicts"] if not v["ok"]]
    point = rep["points"][0]
    assert sum(pr["server_error"] for pr in point["packs"].values()) == 0
    assert sum(pr["ok"] for pr in point["packs"].values()) > 0
    slos = [v["slo"] for v in rep["verdicts"]]
    for tenant in ("lg-lexical", "lg-rag", "lg-analytics", "lg-paging",
                   "lg-ingest"):
        assert f"attribution.{tenant}" in slos
    assert set(rep["packs"]) == {
        "zipf_lexical", "rag_hybrid", "analytics_aggs", "paging_walk",
        "bulk_ingest"}


def test_real_edge_429_all_carry_retry_after(tmp_path):
    """Squeeze admission to one concurrent search: the swarm must see
    429s, and EVERY one must carry a Retry-After hint the client
    exposes (TransportError.retry_after) — a hintless 429 anywhere in
    the edge fails the per-pack compliance verdict."""
    # 160 /s: at 40 /s two searches met only while programs were still
    # compiling, so the test passed alone and failed after the file's
    # earlier tests had compiled them
    rep = run_latency_under_load(
        str(tmp_path), seed=42, points=(160.0,), duration_s=1.5,
        n_docs=60, vocab_size=200, admission_max_concurrent=1,
        retry_limit=1, retry_wait_cap_s=0.2)
    point = rep["points"][0]
    total_429 = sum(pr["retry_after_present"] + pr["retry_after_missing"]
                    for pr in point["packs"].values())
    assert total_429 > 0, "squeezed admission produced no 429s"
    missing = sum(pr["retry_after_missing"]
                  for pr in point["packs"].values())
    assert missing == 0
    for v in rep["verdicts"]:
        if v["slo"].startswith("retry_after_hint."):
            assert v["ok"], v


def test_client_surfaces_retry_after_header(tmp_path):
    """The bundled client parses Retry-After off 429 error responses
    (satellite: the hint used to be discarded with the rest of the
    error headers)."""
    from opensearch_tpu.client import OpenSearch, TransportError
    from opensearch_tpu.node import Node

    node = Node(str(tmp_path), port=0).start()
    try:
        cli = OpenSearch([f"http://127.0.0.1:{node.port}"],
                         headers={"X-Opaque-Id": "ra-probe"})
        cli.indices.create("ra", {"settings": {
            "number_of_shards": 1, "number_of_replicas": 0}})
        cli.bulk([{"index": {"_id": "1"}}, {"body": "t1 t2"}],
                 index="ra")
        cli.indices.refresh("ra")
        cli.cluster.put_settings({"transient": {
            "search_backpressure.max_concurrent_searches": 1}})
        body = {"query": {"match": {"body": "t1"}}}
        saw = None
        barrier = threading.Barrier(8)

        def swarm():
            nonlocal saw
            barrier.wait()
            for _ in range(6):
                try:
                    cli.search(index="ra", body=body)
                except TransportError as e:
                    if e.status_code == 429:
                        saw = e
                        return

        threads = [threading.Thread(target=swarm) for _ in range(8)]
        # the one permit is held while the swarm runs: whether two of
        # its searches overlap inside the gate is the scheduler's to
        # say, and a request that runs through in one interpreter-lock
        # slice never meets another
        with node.search_backpressure.admission.acquire("search"):
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
        assert saw is not None, "no 429 under max_concurrent=1 swarm"
        assert saw.retry_after is not None and saw.retry_after >= 1.0
        assert "Retry-After" in saw.headers
    finally:
        node.stop()


# -- shared corpus shape ----------------------------------------------------

def test_make_doc_delegates_to_shared_corpus_doc():
    """The soak's make_doc and the module-level corpus_doc must stay
    byte-identical for the same seed — the loadgen corpus rides on the
    soak's determinism contract."""
    from opensearch_tpu.testing.workload import (
        MixedWorkload, SoakConfig, corpus_doc)

    wl = MixedWorkload(SoakConfig(seed=7))
    for i in (0, 3, 11):
        assert wl.make_doc(i) == corpus_doc(
            7, i, wl.config.vocab_size, wl.tags)


# -- bench multi-segment geometry -------------------------------------------

def test_bench_make_segments_covers_corpus_and_prunes():
    import importlib.util

    import numpy as np

    spec = importlib.util.spec_from_file_location(
        "bench", REPO + "/bench.py")
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)

    from opensearch_tpu.common.telemetry import metrics
    from opensearch_tpu.mapping.mapper import DocumentMapper
    from opensearch_tpu.search.executor import ShardSearcher

    raw = bench.build_raw_corpus(2_000)
    segs = bench.make_segments(raw, 8)
    assert len(segs) == 8
    assert sum(s.n_docs for s in segs) == 2_000
    # the split preserves every posting: per-term df sums back to the
    # monolith's df
    df_sum = np.zeros_like(raw["df"])
    for s in segs:
        df_sum += s.postings["body"].df
    assert (df_sum == raw["df"]).all()

    mapper = DocumentMapper({"properties": {"body": {"type": "text"}}})
    searcher = ShardSearcher(segs, mapper, index_name="bench")
    # zipf head term lives everywhere; hit totals must match monolith
    mono = ShardSearcher([bench.make_segment(raw)], mapper,
                         index_name="bench_mono")
    q = {"query": {"match": {"body": "t0 t5"}}, "size": 10}
    assert searcher.search(dict(q))["hits"]["total"]["value"] == \
        mono.search(dict(q))["hits"]["total"]["value"]
    # a tail term present in few segments exercises can-match pruning —
    # the counter the single-monolith bench pinned to 0
    df = raw["df"]
    rare = int(np.argmax(df == 1)) if (df == 1).any() else int(
        np.argmin(np.where(df > 0, df, df.max() + 1)))
    before = metrics().counter("search.segments_pruned").value
    searcher.search({"query": {"match": {"body": f"t{rare}"}},
                     "size": 10})
    assert metrics().counter("search.segments_pruned").value > before


# -- bench phase wiring -----------------------------------------------------

def test_bench_latency_under_load_phase(tmp_path, monkeypatch):
    """The latency_under_load phase emits one line per (pack, offered
    point) with the full percentile set, plus a summary line carrying
    per-pack max_sustainable_qps — the ISSUE's acceptance surface."""
    import importlib.util
    import json

    phases = tmp_path / "phases.jsonl"
    monkeypatch.setenv("OSTPU_BENCH_PHASES", str(phases))
    monkeypatch.setenv("OSTPU_BENCH_LOAD_QPS", "6,12,24")
    monkeypatch.setenv("OSTPU_BENCH_LOAD_DURATION", "0.6")
    monkeypatch.setenv("OSTPU_BENCH_LOAD_DOCS", "60")
    spec = importlib.util.spec_from_file_location(
        "bench", REPO + "/bench.py")
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    bench.run_latency_under_load_phase("cpu")
    lines = [json.loads(ln) for ln in phases.read_text().splitlines()]
    points = [ln for ln in lines if ln["phase"] == "latency_under_load"]
    # >= 3 offered-load points for each of the 5 packs
    per_pack: dict = {}
    for ln in points:
        per_pack.setdefault(ln["pack"], []).append(ln)
        for k in ("offered_qps", "sent", "p50_ms", "p99_ms", "p999_ms",
                  "ok", "rejected", "server_error", "achieved_qps"):
            assert k in ln, (k, ln)
    assert len(per_pack) == 5
    assert all(len(v) >= 3 for v in per_pack.values())
    summary = [ln for ln in lines
               if ln["phase"] == "latency_under_load_summary"]
    assert len(summary) == 1
    assert set(summary[0]["max_sustainable_qps"]) == set(per_pack)


# -- tier-1 lints -----------------------------------------------------------

def test_check_open_loop_repo_clean():
    out = subprocess.run(
        [sys.executable, TOOLS + "/check_open_loop.py"],
        capture_output=True, text=True)
    assert out.returncode == 0, out.stdout + out.stderr


def test_check_open_loop_catches_violations(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text(
        "import time\n"
        "def measure(send, reqs):\n"
        "    lat = []\n"
        "    for r in reqs:\n"
        "        t0 = time.monotonic()\n"
        "        send(r)\n"
        "        lat.append(time.monotonic() - t0)\n"          # line 7
        "    return lat\n"
        "def service_time(send, reqs):\n"
        "    lat = []\n"
        "    for r in reqs:\n"
        "        t0 = time.monotonic()\n"
        "        send(r)\n"
        "        # closed-loop-ok\n"
        "        lat.append(time.monotonic() - t0)\n"          # annotated
        "    return lat\n"
        "def stamp():\n"
        "    return time.time()\n")                            # line 18
    out = subprocess.run(
        [sys.executable, TOOLS + "/check_open_loop.py", str(bad)],
        capture_output=True, text=True)
    assert out.returncode == 1
    assert "bad.py:7:" in out.stdout
    assert "bad.py:18:" in out.stdout
    assert "bad.py:15:" not in out.stdout
    # scheduled-arrival subtraction (the open-loop pattern) is fine:
    # the start isn't a clock read taken inside the loop
    good = tmp_path / "good.py"
    good.write_text(
        "import time\n"
        "def run(schedule, send):\n"
        "    base = time.monotonic()\n"
        "    lat = []\n"
        "    for t, r in schedule:\n"
        "        send(r)\n"
        "        lat.append(time.monotonic() - (base + t))\n"
        "    return lat\n")
    out = subprocess.run(
        [sys.executable, TOOLS + "/check_open_loop.py", str(good)],
        capture_output=True, text=True)
    assert out.returncode == 0, out.stdout


def test_check_seeded_rng_covers_loadgen():
    loadgen = (REPO
               + "/opensearch_tpu/testing/loadgen.py")
    out = subprocess.run(
        [sys.executable, TOOLS + "/check_seeded_rng.py", loadgen],
        capture_output=True, text=True)
    assert out.returncode == 0, out.stdout + out.stderr


def test_bench_is_one_process_and_a_raised_phase_fails_it(tmp_path):
    """`python bench.py` runs its phases in ONE process on jax's default
    backend (here the CPU conftest selects), prints exactly one result
    line for the live run, keeps going past a phase that raises — and
    then exits non-zero."""
    import json
    import os
    import subprocess
    import sys

    phases = tmp_path / "phases.jsonl"
    env = dict(os.environ, OSTPU_BENCH_PHASES=str(phases),
               OSTPU_BENCH_DOCS="2000", OSTPU_BENCH_QUERIES="64",
               OSTPU_BENCH_CONCURRENCY="not-a-number")  # continuous raises
    for gate in ("SOAK", "LOAD", "AUTOSCALE", "QOS", "TIER"):
        env[f"OSTPU_BENCH_{gate}"] = "0"
    r = subprocess.run([sys.executable, REPO + "/bench.py"], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 1, r.stderr[-2000:]
    out = [json.loads(ln) for ln in r.stdout.splitlines()]
    assert len(out) == 1 and out[0]["platform"] == "cpu"
    assert "recorded" not in out[0] and out[0]["value"] > 0
    lines = [json.loads(ln) for ln in phases.read_text().splitlines()]
    names = [ln["phase"] for ln in lines]
    assert "ValueError" in lines[names.index("continuous")]["error"]
    # the phases before AND after the one that raised still ran
    assert names[:4] == ["baseline", "smoke", "batched", "sequential"]
    assert {"profile", "insights"} <= set(
        names[names.index("continuous"):])
    assert all("attempt" not in ln for ln in lines)
