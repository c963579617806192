"""BM25 match-query benchmark (BASELINE.md config #1, msmarco-style).

Builds a synthetic corpus with a zipf vocabulary, indexes it into one
array segment, then measures end-to-end query QPS + latency through the
full search path (DSL parse -> compile -> jit'd score/top-k -> merge ->
fetch).  Prints ONE JSON line to stdout.

One process on jax's default backend, run as *phases*, each of which
appends its own JSON line to a phases file the moment it completes —

    baseline    measured numpy BM25 (BM25S-style, no jax) on the same
                corpus+queries: the vs_baseline denominator is MEASURED,
                not assumed
    smoke       backend init + one toy program
    batched     the flagship path: 64-query msearch batches (ONE XLA
                program per union-budget bucket)
    sequential  per-query path (p50/p99 latency; ~4 bucket compiles)

and the later platform phases below.  A later phase that raises is
reported on its own phase line and the remaining phases still run, but
the exit code is then non-zero.

Env knobs: OSTPU_BENCH_DOCS (default 100000), OSTPU_BENCH_QUERIES (200),
OSTPU_BENCH_BATCH (64), OSTPU_BENCH_PHASES (phases file path).
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

VOCAB_SIZE = 30_000
AVG_LEN = 40
K1, B = 1.2, 0.75


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def phase_report(name: str, data: dict):
    """Append one phase-result JSON line to the phases file (fsync'd so a
    later crash cannot lose it) and mirror it to stderr."""
    line = json.dumps({"phase": name, **data})
    log("PHASE " + line)
    path = os.environ.get("OSTPU_BENCH_PHASES")
    if path:
        try:
            with open(path, "a") as f:
                f.write(line + "\n")
                f.flush()
                os.fsync(f.fileno())
        except OSError as e:
            log(f"phase file write failed: {e}")


def build_raw_corpus(n_docs: int, seed: int = 42):
    """Vectorized synthetic corpus -> raw CSR postings (pure numpy, no
    jax import)."""
    rng = np.random.default_rng(seed)
    lens = rng.integers(AVG_LEN // 2, AVG_LEN * 3 // 2, size=n_docs)
    total = int(lens.sum())
    # zipf-ish ranked term ids, clipped to vocab
    terms = (rng.zipf(1.3, size=total) - 1).clip(0, VOCAB_SIZE - 1).astype(np.int32)
    doc_of = np.repeat(np.arange(n_docs, dtype=np.int32), lens)

    t0 = time.monotonic()
    order = np.lexsort((doc_of, terms))
    st, sd = terms[order], doc_of[order]
    # unique (term, doc) pairs -> postings entries with tf counts
    key = st.astype(np.int64) * n_docs + sd
    uniq, counts = np.unique(key, return_counts=True)
    p_terms = (uniq // n_docs).astype(np.int32)
    p_docs = (uniq % n_docs).astype(np.int32)
    tfs = counts.astype(np.float32)
    present_terms, term_starts = np.unique(p_terms, return_index=True)
    T = VOCAB_SIZE
    offsets = np.zeros(T + 1, dtype=np.int32)
    df = np.zeros(T, dtype=np.int32)
    df_present = np.diff(np.append(term_starts, len(p_terms)))
    df[present_terms] = df_present
    offsets[1:] = np.cumsum(df)
    build_s = time.monotonic() - t0
    return {"n_docs": n_docs, "offsets": offsets, "df": df,
            "doc_ids": p_docs, "tfs": tfs,
            "doc_lens": lens.astype(np.float32), "build_s": build_s}


def make_segment(raw):
    """Wrap the raw CSR arrays in a Segment (imports jax transitively)."""
    from opensearch_tpu.index.segment import PostingsField, Segment

    n_docs = raw["n_docs"]
    seg = Segment("bench_0", n_docs)
    seg.doc_ids = [str(i) for i in range(n_docs)]
    seg.id_to_local = {str(i): i for i in range(n_docs)}
    seg.sources = [b"{}"] * n_docs
    doc_lens = raw["doc_lens"]
    seg.postings["body"] = PostingsField(
        terms={f"t{t}": t for t in range(VOCAB_SIZE)}, df=raw["df"],
        offsets=raw["offsets"], doc_ids=raw["doc_ids"], tfs=raw["tfs"],
        pos_offsets=np.zeros(len(raw["doc_ids"]) + 1, dtype=np.int32),
        positions=np.zeros(0, dtype=np.int32),
        doc_lens=doc_lens, total_len=float(doc_lens.sum()),
        docs_with_field=n_docs, has_norms=True,
        present=np.ones(n_docs, dtype=bool))
    return seg


def make_segments(raw, n_segments: int):
    """Split the raw CSR corpus into ``n_segments`` doc-range segments
    (realistic multi-segment shard geometry, vs the single monolith
    ``make_segment`` builds).  With zipf traffic most tail terms live
    in few segments, so block-max can-match pruning
    (``search.segments_pruned``) finally has something to skip — the
    monolith pinned that counter to 0 on every bench phase."""
    from opensearch_tpu.index.segment import PostingsField, Segment

    n_docs = raw["n_docs"]
    n_segments = max(1, min(int(n_segments), n_docs))
    offsets, df = raw["offsets"], raw["df"]
    doc_ids, tfs, doc_lens = raw["doc_ids"], raw["tfs"], raw["doc_lens"]
    # CSR rows are terms; tag every posting with its term id so a
    # doc-range mask can rebuild per-segment CSR in one bincount pass
    term_of = np.repeat(np.arange(VOCAB_SIZE, dtype=np.int32), df)
    bounds = np.linspace(0, n_docs, n_segments + 1).astype(np.int64)
    segs = []
    for s in range(n_segments):
        lo, hi = int(bounds[s]), int(bounds[s + 1])
        n_local = hi - lo
        mask = (doc_ids >= lo) & (doc_ids < hi)
        seg_df = np.bincount(term_of[mask],
                             minlength=VOCAB_SIZE).astype(np.int32)
        seg_offsets = np.zeros(VOCAB_SIZE + 1, dtype=np.int32)
        seg_offsets[1:] = np.cumsum(seg_df)
        local_lens = doc_lens[lo:hi]
        seg = Segment(f"bench_{s}", n_local)
        seg.doc_ids = [str(i) for i in range(lo, hi)]
        seg.id_to_local = {str(i): i - lo for i in range(lo, hi)}
        seg.sources = [b"{}"] * n_local
        # only terms that actually occur here get a dictionary entry:
        # term_id() returning -1 for the rest is what lets can-match
        # prune this segment (the CSR keeps full-vocab rows, so present
        # term ids stay global)
        seg.postings["body"] = PostingsField(
            terms={f"t{int(t)}": int(t)
                   for t in np.nonzero(seg_df)[0]}, df=seg_df,
            offsets=seg_offsets,
            doc_ids=(doc_ids[mask] - lo).astype(np.int32),
            tfs=tfs[mask],
            pos_offsets=np.zeros(int(mask.sum()) + 1, dtype=np.int32),
            positions=np.zeros(0, dtype=np.int32),
            doc_lens=local_lens, total_len=float(local_lens.sum()),
            docs_with_field=n_local, has_norms=True,
            present=np.ones(n_local, dtype=bool))
        segs.append(seg)
    return segs


def gen_query_terms(n_queries: int, seed: int = 7):
    # the seeded zipf query log lives in the soak harness now (the soak
    # workload and this bench measure the SAME traffic shape); identical
    # draws to the pre-refactor inline version
    from opensearch_tpu.testing.workload import zipf_query_log
    return zipf_query_log(n_queries, VOCAB_SIZE, seed=seed)


def numpy_bm25_baseline(raw, pairs, k: int = 10) -> dict:
    """Measured CPU reference: per-query numpy BM25 over the same CSR
    postings (the BM25S formulation per PAPERS.md — per-query gather,
    dense scatter, argpartition top-k).  This is a *strong* CPU baseline:
    BM25S reports it beating Lucene-class engines on rank-1 retrieval,
    so beating it is a stricter bar than the old assumed 500 QPS."""
    n_docs = raw["n_docs"]
    offsets, doc_ids, tfs = raw["offsets"], raw["doc_ids"], raw["tfs"]
    doc_lens, df = raw["doc_lens"], raw["df"]
    avgdl = float(doc_lens.mean())

    def run_once():
        t0 = time.monotonic()
        for a, b in pairs:
            scores = np.zeros(n_docs, np.float32)
            for tid in {a, b}:
                d = doc_ids[offsets[tid]: offsets[tid + 1]]
                tf = tfs[offsets[tid]: offsets[tid + 1]]
                idf = np.log(1.0 + (n_docs - df[tid] + 0.5) / (df[tid] + 0.5))
                norm = K1 * (1.0 - B + B * doc_lens[d] / avgdl)
                # docs are unique within one postings list: plain fancy-
                # index add is safe (no np.add.at cost)
                scores[d] += (idf * tf / (tf + norm)).astype(np.float32)
            top = np.argpartition(scores, -k)[-k:]
            top[np.argsort(-scores[top], kind="stable")]
        return time.monotonic() - t0

    run_once()                      # warm caches/allocator
    wall = run_once()
    return {"qps": len(pairs) / wall, "wall_s": wall, "avgdl": avgdl}


def tpu_smoke(jax, platform):
    """Tiny device smoke: run one jitted matmul+top_k.  Separates
    'framework bug' from 'environment bug'."""
    import jax.numpy as jnp

    t0 = time.monotonic()
    x = jnp.ones((128, 128), dtype=jnp.float32)
    scores = (x @ x.T).sum(axis=1)
    vals, idx = jax.lax.top_k(scores, 5)
    vals.block_until_ready()
    dt = time.monotonic() - t0
    log(f"device smoke ok on {platform}: top1={float(vals[0]):.1f} ({dt:.2f}s)")
    return dt


def main():
    """Staged phases on jax's default backend; completed phases survive
    in the phases file.  Returns the names of the phases that raised."""
    n_docs = int(os.environ.get("OSTPU_BENCH_DOCS", 100_000))
    n_queries = int(os.environ.get("OSTPU_BENCH_QUERIES", 200))
    batch = int(os.environ.get("OSTPU_BENCH_BATCH", 64))
    # keep every batch the same shape: q_pad is part of the XLA program
    # key, so a ragged final batch would be a second compile
    n_queries = max(batch, (n_queries // batch) * batch)

    t0 = time.monotonic()
    raw = build_raw_corpus(n_docs)
    pairs = gen_query_terms(n_queries)
    log(f"corpus: {n_docs} docs, {len(raw['doc_ids'])} postings, "
        f"invert {raw['build_s']:.2f}s")

    # -- phase: measured baseline (numpy, jax-free) -----------------------
    base = numpy_bm25_baseline(raw, pairs)
    baseline_qps = base["qps"]
    phase_report("baseline", {
        "qps": round(baseline_qps, 1), "n_docs": n_docs,
        "n_queries": n_queries,
        "note": "numpy BM25S-style per-query scoring, measured in-process"})

    # -- phase: backend smoke --------------------------------------------
    import jax

    platform = jax.default_backend()
    log(f"platform={platform} devices={len(jax.devices())}")
    smoke_s = tpu_smoke(jax, platform)
    phase_report("smoke", {"platform": platform,
                           "smoke_s": round(smoke_s, 2)})

    from opensearch_tpu.mapping.mapper import DocumentMapper
    from opensearch_tpu.search.executor import ShardSearcher

    def hot_path_counters():
        """Compile/prune behavior for the phase lines: plan-cache reuse,
        block-max pruning, and live XLA program counts (a growing
        program count across reps == retracing in the hot path)."""
        from opensearch_tpu.common.telemetry import metrics
        from opensearch_tpu.search import batch as batch_mod
        from opensearch_tpu.search import plan as plan_mod

        m = metrics()
        return {
            "n_segments": n_segments,
            "plan_cache_hits": m.counter("search.plan_cache.hits").value,
            "plan_cache_misses":
                m.counter("search.plan_cache.misses").value,
            "segments_pruned":
                m.counter("search.segments_pruned").value,
            "batched_programs":
                batch_mod.batch_impact_union_topk._cache_size(),
            "seq_programs": plan_mod.run_topk._cache_size(),
        }

    n_segments = int(os.environ.get("OSTPU_BENCH_SEGMENTS", 8))
    segs = make_segments(raw, n_segments)
    mapper = DocumentMapper({"properties": {"body": {"type": "text"}}})
    searcher = ShardSearcher(segs, mapper, index_name="bench")
    queries = [{"query": {"match": {"body": f"t{a} t{b}"}}, "size": 10}
               for a, b in pairs]

    # -- phase: batched (the flagship TPU path) ---------------------------
    # warm EVERY batch once: the union kernel's program key includes
    # t_pad (distinct terms of the batch) and the union budget bucket,
    # so different batches can be different programs — typically 1-3
    # compiles total, all landing in the persistent cache
    # (common/jaxenv.py) so a re-run starts warm
    t0 = time.monotonic()
    for i in range(0, n_queries, batch):
        searcher.msearch(queries[i: i + batch])
    compile_s = time.monotonic() - t0
    log(f"batched warmup (compiles + staging): {compile_s:.1f}s")
    t0 = time.monotonic()
    reps = 0
    while reps == 0 or time.monotonic() - t0 < 3.0:
        for i in range(0, n_queries, batch):
            searcher.msearch(queries[i: i + batch])
        reps += 1
    wall = time.monotonic() - t0
    qps = n_queries * reps / wall
    phase_report("batched", {
        "platform": platform, "qps": round(qps, 1), "batch": batch,
        "compile_s": round(compile_s, 1),
        "vs_baseline": round(qps / baseline_qps, 3),
        **hot_path_counters()})

    # -- phase: sequential (latency path; ~4 budget-bucket compiles) ------
    # half the queries send track_total_hits:false (head traffic rarely
    # needs exact totals), which arms the running-kth block-max prune —
    # over the multi-segment corpus that makes segments_pruned a live
    # number on this line instead of a pinned 0
    seq_n = min(n_queries, 100)
    seq_queries = [dict(q, track_total_hits=False) if i % 2 else q
                   for i, q in enumerate(queries[:seq_n])]
    t0 = time.monotonic()
    for q in seq_queries[:32]:
        searcher.search(dict(q))
    log(f"sequential warmup: {time.monotonic() - t0:.1f}s")
    lat = []
    t0 = time.monotonic()
    for q in seq_queries:
        qt = time.monotonic()
        searcher.search(dict(q))
        lat.append(time.monotonic() - qt)  # closed-loop-ok
    seq_wall = time.monotonic() - t0
    qps_seq = seq_n / seq_wall
    lat_ms = np.asarray(lat) * 1e3
    p50 = float(np.percentile(lat_ms, 50))
    p99 = float(np.percentile(lat_ms, 99))
    phase_report("sequential", {
        "platform": platform, "qps": round(qps_seq, 1),
        "p50_ms": round(p50, 3), "p99_ms": round(p99, 3),
        **hot_path_counters()})

    failed = []

    def later_phase(name: str, fn, *args, gate: str = ""):
        """Run one later phase unless its ``OSTPU_BENCH_<gate>=0`` switch
        is off.  A phase that raises is reported on its own phase line
        and the remaining phases still run; the exit code says so."""
        if gate and os.environ.get(f"OSTPU_BENCH_{gate}", "1") == "0":
            return
        try:
            fn(*args)
        except Exception as e:  # noqa: BLE001 — report, keep the bench
            failed.append(name)
            phase_report(name, {"platform": platform,
                                "error": f"{type(e).__name__}: {e}"})

    # continuous: REST-edge continuous batching under concurrent clients
    later_phase("continuous", run_continuous_phase, searcher, queries,
                p50, platform)
    # profile: where the time actually goes — the sequential queries
    # re-run with profile:true, so the trajectory records per-phase
    # attribution and the Profile API's own cost (profiled vs unprofiled
    # p50 delta)
    later_phase("profile", run_profile_phase, searcher, queries, seq_n,
                p50, platform, batch)
    # insights: always-on attribution overhead + workload coalescability
    later_phase("insights", run_insights_phase, searcher, queries, seq_n,
                platform, batch)
    # tier: search-only replica fleet over the remote store
    later_phase("tier", run_tier_phase, platform, gate="TIER")
    # qos: noisy-neighbor tenant isolation + adaptive control
    later_phase("qos", run_qos_phase, platform, gate="QOS")
    # latency_under_load: open-loop offered-qps sweep over the real REST
    # edge; coordinated-omission-free
    later_phase("latency_under_load", run_latency_under_load_phase,
                platform, gate="LOAD")
    # autoscale: QoS-driven searcher elasticity — scale-up under
    # pressure, drain-safe retirement when idle
    later_phase("autoscale", run_autoscale_phase, platform,
                gate="AUTOSCALE")
    # soak: chaos SLO scenario over a 3-node cluster (runs LAST so a
    # failure here cannot cost the phases above)
    later_phase("soak", run_soak_phase, platform, gate="SOAK")

    print(json.dumps(final_line(
        qps=qps, baseline_qps=baseline_qps, platform=platform,
        extra={"qps_sequential": round(qps_seq, 1), "p50_ms": round(p50, 3),
               "p99_ms": round(p99, 3), "batch": batch, "n_docs": n_docs})))
    return failed


def run_continuous_phase(searcher, queries, p50_plain: float,
                         platform: str):
    """Continuous-batching phase line (ROADMAP item 1): N concurrent
    client threads drive independent single searches through the
    unified engine entry (the same ``QueryEngine.execute`` call the
    REST edge routes to), and the line reports XLA dispatches per
    query, realized batch occupancy, and p50/p99 under concurrency —
    versus the sequential phase — plus the batcher-OFF sequential p50
    so the bypass cost is measured, not asserted.  Acceptance bar:
    < 1 dispatch per query at concurrency >= 16 with the batcher on,
    and batcher-off sequential p50 within 5% of plain."""
    import threading

    from opensearch_tpu.common.telemetry import metrics
    from opensearch_tpu.search import engine as engine_mod

    class _Svc:
        """Minimal service shim: the bench drives a bare ShardSearcher,
        so the engine's service-scoped backends reduce to the batcher
        (no mesh opt-in)."""

        @staticmethod
        def _use_mesh(body):
            return False

        @staticmethod
        def _mesh_search(body):
            raise RuntimeError("unreachable")

    svc = _Svc()
    eng = engine_mod.query_engine()
    m = metrics()
    conc = int(os.environ.get("OSTPU_BENCH_CONCURRENCY", 16))
    n_total = min(len(queries), max(conc * 16, 128))
    n_total = (n_total // conc) * conc
    sample = queries[:n_total]

    prev = (engine_mod.BATCHER_ENABLED, engine_mod.BATCHER_WINDOW_MS,
            engine_mod.BATCHER_MAX_BATCH)
    try:
        # batcher ON under concurrency: each thread walks its own slice
        engine_mod.BATCHER_ENABLED = True
        engine_mod.BATCHER_WINDOW_MS = float(os.environ.get(
            "OSTPU_BENCH_BATCH_WINDOW_MS", 4.0))
        engine_mod.BATCHER_MAX_BATCH = 64
        # warm the batch kernel's program shapes once
        searcher.msearch([dict(q) for q in sample[:conc]])
        b0 = m.counter("search.batcher.batched").value
        d0 = m.counter("search.batcher.dispatches").value
        y0 = m.counter("search.batcher.bypass").value
        lat: list[float] = []
        lat_lock = threading.Lock()

        def client(tid: int):
            mine = sample[tid::conc]
            for q in mine:
                t0 = time.monotonic()
                eng.execute(searcher, dict(q), service=svc)
                dt = time.monotonic() - t0  # closed-loop-ok
                with lat_lock:
                    lat.append(dt)

        threads = [threading.Thread(target=client, args=(i,),
                                    name=f"bench-client-{i}", daemon=True)
                   for i in range(conc)]
        t0 = time.monotonic()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.monotonic() - t0
        batched = m.counter("search.batcher.batched").value - b0
        groups = m.counter("search.batcher.dispatches").value - d0
        bypass = m.counter("search.batcher.bypass").value - y0
        solo = n_total - batched - bypass
        dispatches = groups + solo + bypass
        lat_ms = np.asarray(lat) * 1e3
        occupancy = batched / groups if groups else 0.0

        # batcher OFF, single-threaded: the bypass-cost regression
        # check.  Plain (searcher.search) and engine-entry p50 are
        # measured BACK-TO-BACK — the sequential phase's p50 was taken
        # in a different cache/thermal state minutes earlier, and at
        # sub-ms p50 that skew dwarfs the entry cost being measured
        # (same rationale as the insights phase)
        engine_mod.BATCHER_ENABLED = False
        n_off = min(100, n_total)
        plain = []
        for q in sample[:n_off]:
            t0 = time.monotonic()
            searcher.search(dict(q))
            plain.append(time.monotonic() - t0)  # closed-loop-ok
        p50_plain_now = float(np.percentile(np.asarray(plain) * 1e3, 50))
        off = []
        for q in sample[:n_off]:
            t0 = time.monotonic()
            eng.execute(searcher, dict(q), service=svc)
            off.append(time.monotonic() - t0)  # closed-loop-ok
        p50_off = float(np.percentile(np.asarray(off) * 1e3, 50))

        phase_report("continuous", {
            "platform": platform,
            "concurrency": conc,
            "n_queries": n_total,
            "qps": round(n_total / wall, 1),
            "batched_members": int(batched),
            "batch_dispatches": int(groups),
            "solo": int(solo),
            "bypass": int(bypass),
            "dispatches_per_query": round(dispatches / n_total, 4),
            "mean_batch_occupancy": round(occupancy, 2),
            "p50_ms": round(float(np.percentile(lat_ms, 50)), 3),
            "p99_ms": round(float(np.percentile(lat_ms, 99)), 3),
            "window_ms": engine_mod.BATCHER_WINDOW_MS or 4.0,
            "seq_p50_batcher_off_ms": round(p50_off, 3),
            "seq_p50_plain_ms": round(p50_plain_now, 3),
            "seq_p50_phase_ms": round(p50_plain, 3),
            "seq_p50_off_delta_pct": round(
                (p50_off - p50_plain_now) / p50_plain_now * 100, 2)
            if p50_plain_now else 0.0,
        })
    finally:
        (engine_mod.BATCHER_ENABLED, engine_mod.BATCHER_WINDOW_MS,
         engine_mod.BATCHER_MAX_BATCH) = prev


def run_profile_phase(searcher, queries, seq_n: int, p50_plain: float,
                      platform: str, batch: int):
    """Profile-API phase line: re-runs the sequential query sample with
    ``profile: true`` and reports (a) ``profile_overhead`` — the
    profiled-vs-unprofiled p50 delta, i.e. what observability costs —
    and (b) the top-3 phase costs summed across the sample, so
    ``bench_phases.jsonl`` finally records WHERE the time goes
    (compile/prepare/dispatch/reduce/fetch), not just totals.  One
    profiled msearch batch rides along to pin the coalesced-group
    attribution on the batched path."""
    lat = []
    totals: dict = {}
    for q in queries[:seq_n]:
        t0 = time.monotonic()
        resp = searcher.search(dict(q, profile=True))
        lat.append(time.monotonic() - t0)  # closed-loop-ok
        bd = resp["profile"]["shards"][0]["searches"][0]["query"][0][
            "breakdown"]
        for key, v in bd.items():
            if not key.endswith("_count"):
                totals[key] = totals.get(key, 0) + v
    p50_prof = float(np.percentile(np.asarray(lat) * 1e3, 50))
    top3 = sorted(totals.items(), key=lambda kv: -kv[1])[:3]
    bresp = searcher.msearch(
        [dict(q, profile=True) for q in queries[:batch]])
    bengine = bresp[0]["profile"]["shards"][0]["engine"]
    phase_report("profile", {
        "platform": platform,
        "n_queries": len(lat),
        "p50_ms": round(p50_prof, 3),
        "profile_overhead": round(p50_prof - p50_plain, 3),
        "top_phases": [{"phase": key, "time_in_nanos": int(v)}
                       for key, v in top3],
        "batched_execution_path": bengine.get("execution_path"),
        "batched_xla_compiles": bengine.get("xla_compiles"),
    })


def run_insights_phase(searcher, queries, seq_n: int,
                       platform: str, batch: int):
    """Query-insights phase line: the sequential zipf sample re-runs
    with an insight sink + recording service installed (the always-on
    production configuration) and reports (a) ``insights_overhead_pct``
    — the recorded-vs-plain sequential p50 delta, the cost of always-on
    attribution — and (b) the measured COALESCABILITY of this bench's
    zipf workload per plan signature: the continuous batcher's sizing
    input (ROADMAP item 1), finally measured instead of assumed."""
    from opensearch_tpu.search import insights as insights_mod
    from opensearch_tpu.search.insights import QueryInsightsService

    svc = QueryInsightsService(node_id="bench", ring_capacity=512,
                               max_signatures=256)
    # fair overhead comparison: re-measure the PLAIN p50 back-to-back
    # with the recorded run (the sequential phase's p50 was taken in a
    # different cache/thermal state minutes earlier — at sub-ms p50
    # that skew dwarfs the recording cost being measured)
    plain = []
    for q in queries[:seq_n]:
        t0 = time.monotonic()
        searcher.search(q)
        plain.append(time.monotonic() - t0)  # closed-loop-ok
    p50_plain = float(np.percentile(np.asarray(plain) * 1e3, 50))
    lat = []
    for q in queries[:seq_n]:
        t0 = time.monotonic()
        with insights_mod.collecting() as sink:
            searcher.search(q)
        for rec in sink:
            svc.record(rec)
        lat.append(time.monotonic() - t0)  # closed-loop-ok
    # one recorded msearch batch rides along: the batched-member records
    # carry the coalesced group size the report below surfaces
    with insights_mod.collecting() as sink:
        searcher.msearch(queries[:batch])
    for rec in sink:
        svc.record(rec)
    p50_ins = float(np.percentile(np.asarray(lat) * 1e3, 50))
    coalesc = svc.coalescability()
    top = svc.top(by="latency", n=3)
    stats = svc.stats()
    phase_report("insights", {
        "platform": platform,
        "n_queries": len(lat),
        "p50_ms": round(p50_ins, 3),
        "insights_overhead_pct": round(
            (p50_ins - p50_plain) / p50_plain * 100, 2)
        if p50_plain else 0.0,
        "coalescable_fraction": coalesc["coalescable_fraction"],
        "coalesce_window_ms": coalesc["window_ms"],
        "distinct_signatures": stats["signatures"],
        "records": stats["records"],
        "top_signatures": coalesc["top_signatures"][:3],
        "slowest_signature": top[0]["signature"] if top else None,
    })


def run_tier_phase(platform: str):
    """Search-tier line: a 3-data-node cluster + a search-only replica
    over the shared remote store serves the zipf query shape while the
    primary publishes checkpoints; the phase measures (a) searcher
    checkpoint lag across publishes (p99, ops), (b) the cold-refill
    time for a FRESH searcher after killing the old one — the tier's
    recovery story is cache refill, zero primary RPCs — and (c) the
    remote-store bytes that refill pulled (ROADMAP item 4)."""
    import shutil as _shutil
    import tempfile
    import time as _time

    from opensearch_tpu.cluster.node import ClusterNode
    from opensearch_tpu.common.telemetry import metrics
    from opensearch_tpu.testing.workload import MixedWorkload, SoakConfig
    from opensearch_tpu.transport.service import (LocalTransport,
                                                  TransportService)

    n_docs = int(os.environ.get("OSTPU_BENCH_TIER_DOCS", 2000))
    n_batches = 8
    root = tempfile.mkdtemp(prefix="bench-tier-")
    remote = os.path.join(root, "remote")
    voting = ["n0", "n1", "n2"]
    t_phase = time.monotonic()

    def build(nid, roles):
        svc = TransportService(nid, LocalTransport(hub))
        return ClusterNode(nid, os.path.join(root, nid), svc, voting,
                           roles=roles, remote_store_path=remote)

    def wait(pred, what, timeout=60.0):
        deadline = _time.monotonic() + timeout
        while _time.monotonic() < deadline:    # deadline
            if pred():
                return
            _time.sleep(0.02)                  # deadline
        raise RuntimeError(f"tier phase: timed out waiting for {what}")

    def searcher_ready(leader, nid):
        routing = leader.coordinator.state().routing.get("tier", [])
        return bool(routing) and all(
            nid in (e.get("search_in_sync") or []) for e in routing)

    hub = LocalTransport.Hub()
    nodes = {nid: build(nid, ("master", "data")) for nid in voting}
    searcher = build("s0", ("search",))
    nodes["s0"] = searcher
    try:
        for n in nodes.values():
            n.start()
        assert nodes["n0"].start_election()
        nodes["n0"].coordinator.add_node(
            "s0", {"name": "s0", "roles": ["search"],
                   "master_eligible": False})
        nodes["n1"].create_index("tier", {
            "settings": {"number_of_shards": 2,
                         "number_of_replicas": 1,
                         "number_of_search_replicas": 1},
            "mappings": {"properties": {"body": {"type": "text"},
                                        "v": {"type": "long"}}}})
        wait(lambda: searcher_ready(nodes["n0"], "s0"),
             "initial searcher refill")
        workload = MixedWorkload(SoakConfig(n_docs=n_docs,
                                            vocab_size=2000))
        docs = workload.seed_docs()
        lags = []
        per_batch = max(1, len(docs) // n_batches)
        for b in range(n_batches):
            for doc_id, src in docs[b * per_batch:(b + 1) * per_batch]:
                nodes["n1"].index_doc("tier", doc_id, src)
            nodes["n1"].refresh("tier")
            lags.append(searcher.search_lag())
        wait(lambda: searcher.search_lag() == 0, "searcher catch-up")
        searcher_docs = sum(e.doc_count()
                            for e in searcher.indices["tier"].shards)
        # the recovery story: kill the searcher, add a FRESH one, time
        # its pure-remote-store refill and count the bytes it pulled
        searcher.stop()
        nodes.pop("s0")
        pulled_before = metrics().counter("segrep.bytes_pulled").value
        fresh = build("s1", ("search",))
        nodes["s1"] = fresh
        fresh.start()
        t0 = time.monotonic()
        nodes["n0"].coordinator.add_node(
            "s1", {"name": "s1", "roles": ["search"],
                   "master_eligible": False})
        wait(lambda: searcher_ready(nodes["n0"], "s1"),
             "fresh searcher refill")
        refill_ms = (time.monotonic() - t0) * 1000.0
        bytes_per_recovery = (metrics().counter(
            "segrep.bytes_pulled").value - pulled_before)
        from opensearch_tpu.cluster.node import (A_FETCH_SEGMENTS,
                                                 A_START_RECOVERY)
        primary_rpcs = (fresh.transport.requests_sent(
            action=A_START_RECOVERY) + fresh.transport.requests_sent(
            action=A_FETCH_SEGMENTS))
        lag_arr = np.asarray(lags, dtype=np.float64)
        data = {
            "platform": platform,
            "wall_s": round(time.monotonic() - t_phase, 1),
            "docs": searcher_docs,
            "publishes": n_batches,
            "searcher_lag_p99_ops": float(np.percentile(lag_arr, 99))
            if len(lag_arr) else 0.0,
            "searcher_lag_max_ops": float(lag_arr.max())
            if len(lag_arr) else 0.0,
            "refill_ms": round(refill_ms, 1),
            "remote_bytes_per_recovery": int(bytes_per_recovery),
            "recovery_primary_rpcs": int(primary_rpcs),
        }
        phase_report("tier", data)
        return data
    finally:
        for n in list(nodes.values()):
            n.stop()
        _shutil.rmtree(root, ignore_errors=True)


def run_qos_phase(platform: str):
    """Noisy-neighbor QoS line: two tenants against one coordinator —
    an aggressor flooding the zipf head in concurrent bursts far over
    its carved admission share, a well-behaved victim issuing
    sequential searches.  The line records the isolation outcome
    (victim p99 + 429-rate vs the aggressor's shed rate) and the
    adaptive controller's activity (adaptations recorded in the audit
    ring) — ROADMAP item 7 as a bench trajectory."""
    import tempfile
    import shutil as _shutil

    from opensearch_tpu.testing.workload import run_noisy_neighbor

    n_ops = int(os.environ.get("OSTPU_BENCH_QOS_OPS", 16))
    root = tempfile.mkdtemp(prefix="bench-qos-")
    t0 = time.monotonic()
    try:
        report = run_noisy_neighbor(root, seed=42, n_ops=n_ops)
    finally:
        _shutil.rmtree(root, ignore_errors=True)
    victim = report["tenants"]["tenant-victim"]
    aggr = report["tenants"]["tenant-aggressor"]
    phase_report("qos", {
        "platform": platform, "wall_s": round(time.monotonic() - t0, 1),
        "ops": report["ops"], "slo_ok": report["slo_ok"],
        "victim_p99_ms": victim["p99_ms"],
        "victim_429_rate": round(
            victim["rejected"] / max(victim["ops"], 1), 4),
        "aggressor_429_rate": round(
            aggr["rejected"] / max(aggr["ops"], 1), 4),
        "aggressor_ops": aggr["ops"],
        "qos_adaptations": report["qos"]["adaptations"],
        "knobs_adapted": sorted({a["knob"]
                                 for a in report["qos"]["audit"]}),
        "unexpected_errors": len(report["unexpected_errors"]),
    })


def run_soak_phase(platform: str):
    """Chaos-soak SLO line: a seeded mixed workload (this bench's zipf
    query shape + bulk/refresh + aggs + paged walks + msearch) drives a
    3-node in-process cluster through a seeded fault schedule (node
    kill + re-election, slow node, drop/stall, induced duress, network
    partition), and the SLO verdicts + degradation counters land in the
    phases file — the robustness spine (PRs 2/4/6) as a bench
    trajectory, not just tests (ROADMAP item 5)."""
    import tempfile
    import shutil as _shutil

    from opensearch_tpu.testing.workload import run_soak

    n_ops = int(os.environ.get("OSTPU_BENCH_SOAK_OPS", 96))
    root = tempfile.mkdtemp(prefix="bench-soak-")
    t0 = time.monotonic()
    try:
        report = run_soak(root, seed=42, n_ops=n_ops)
    finally:
        _shutil.rmtree(root, ignore_errors=True)
    chaos = report["chaos"]
    conv = next((v for v in report["verdicts"]
                 if v["slo"] == "convergence"), {})
    phase_report("soak", {
        "platform": platform, "wall_s": round(time.monotonic() - t0, 1),
        "ops": chaos["ops"], "slo_ok": report["slo_ok"],
        **{f"p99_{k}_ms": v for k, v in sorted(chaos["p99_ms"].items())},
        "rejection_rate": round(chaos["rejected"] / max(chaos["ops"], 1),
                                4),
        "sheds": chaos["sheds"], "reroutes": chaos["reroutes"],
        "failovers": chaos["failovers"],
        "recoveries": chaos["recoveries"],
        "client_retries": chaos["client_retries"],
        "partial_results": chaos["partial_results"],
        "unexpected_errors": len(chaos["unexpected_errors"]),
        "convergence": bool(conv.get("ok")),
        "doc_count": chaos["final_state"].get("doc_count"),
        "fenced_ops": chaos["fenced_ops"],
        "stale_primary_rejections": chaos["stale_primary_rejections"],
        "durability_checked_ops":
            chaos["durability"].get("checked_ops", 0),
    })


def run_latency_under_load_phase(platform: str):
    """Open-loop latency-under-load curve (ROADMAP item 6): the
    ``testing/loadgen.py`` harness boots a real node, drives the
    per-tenant scenario packs (zipf lexical / RAG hybrid / analytics
    aggs / paging walks / bulk side-traffic) at seeded Poisson+envelope
    arrivals across >= 3 offered-qps points, and charges latency from
    the SCHEDULED arrival — coordinated-omission-free, unlike every
    closed-loop phase above.  One phase line per (pack, offered-load
    point) carries p50/p99/p999 + the outcome ledger; the summary line
    carries per-pack max_sustainable_qps and the admission/insights
    attribution verdicts."""
    import tempfile
    import shutil as _shutil

    from opensearch_tpu.testing.loadgen import run_latency_under_load

    points = tuple(
        float(x) for x in os.environ.get(
            "OSTPU_BENCH_LOAD_QPS", "15,45,120").split(","))
    duration_s = float(os.environ.get("OSTPU_BENCH_LOAD_DURATION", 3.0))
    n_docs = int(os.environ.get("OSTPU_BENCH_LOAD_DOCS", 600))
    root = tempfile.mkdtemp(prefix="bench-load-")
    t0 = time.monotonic()
    try:
        report = run_latency_under_load(
            root, seed=42, points=points, duration_s=duration_s,
            n_docs=n_docs, retry_wait_cap_s=duration_s)
    finally:
        _shutil.rmtree(root, ignore_errors=True)
    for point in report["points"]:
        for pack, pr in sorted(point["packs"].items()):
            phase_report("latency_under_load", {
                "platform": platform, "pack": pack, **pr})
    bad_verdicts = [v["slo"] for v in report["verdicts"]
                    if not v["ok"]]
    phase_report("latency_under_load_summary", {
        "platform": platform,
        "wall_s": round(time.monotonic() - t0, 1),
        "points_qps": list(points), "duration_s": duration_s,
        "n_docs": n_docs, "slo_ok": report["slo_ok"],
        "failed_verdicts": bad_verdicts,
        "max_sustainable_qps": {
            name: p["max_sustainable_qps"]
            for name, p in sorted(report["packs"].items())},
    })
    return report


def run_autoscale_phase(platform: str):
    """Elasticity trajectory (ROADMAP item 5, PR 17): the autoscale
    churn soak drives the QoS-hot window that scales the searcher
    fleet up and the idle window that drains it back, and this phase
    line records the loop's quality numbers — time from pressure to a
    serving searcher, drain duration on retirement, p99 across both
    transitions, and that every fleet decision landed in the audit
    ring with its evidence."""
    import tempfile
    import shutil as _shutil

    from opensearch_tpu.testing.workload import run_autoscale_soak

    root = tempfile.mkdtemp(prefix="bench-autoscale-")
    t0 = time.monotonic()
    try:
        report = run_autoscale_soak(root)
    finally:
        _shutil.rmtree(root, ignore_errors=True)
    chaos = report["chaos"]
    asr = chaos.get("autoscale") or {}
    applied = {d.get("fault"): d for d in chaos.get("applied", [])}
    up = applied.get("scale_up_pressure", {})
    down = applied.get("scale_down_idle", {})
    phase_report("autoscale", {
        "platform": platform,
        "wall_s": round(time.monotonic() - t0, 1),
        "slo_ok": report["slo_ok"],
        "scale_ups": asr.get("scale_ups"),
        "scale_downs": asr.get("scale_downs"),
        "hard_kills": asr.get("hard_kills"),
        "abandoned": asr.get("abandoned"),
        "drains_completed": asr.get("drains_completed"),
        "decisions_audited": asr.get("decisions_audited"),
        "time_to_scale_up_s": up.get("time_to_scale_up_s"),
        "drain_s": down.get("drain_s"),
        # transition p99: ops keep flowing while the fleet mutates, so
        # the run-wide search tail IS the across-the-transition tail
        "p99_search_ms": chaos["p99_ms"].get("search"),
        "searchers_final": asr.get("searchers_final"),
        "unexpected_errors": len(chaos["unexpected_errors"]),
    })
    return report


def final_line(*, qps, baseline_qps, platform, extra=None):
    out = {
        "metric": "bm25_match_qps",
        "value": round(qps, 1),
        "unit": "qps",
        "vs_baseline": round(qps / baseline_qps, 3) if baseline_qps else 0.0,
        "measured_baseline_qps": round(baseline_qps, 1),
        "platform": platform,
    }
    if extra:
        out.update(extra)
    return out


if __name__ == "__main__":
    # fresh phases file per run, next to this script unless told otherwise
    phases_path = os.environ.setdefault(
        "OSTPU_BENCH_PHASES",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "bench_phases.jsonl"))
    if os.path.exists(phases_path):
        os.unlink(phases_path)
    try:
        failed_phases = main()
    except Exception as e:  # emit an honest JSON line, signal failure by rc
        import traceback

        traceback.print_exc(file=sys.stderr)
        platform = "unknown"
        if "jax" in sys.modules:
            try:
                platform = sys.modules["jax"].default_backend()
            except Exception:
                pass
        print(json.dumps({
            "metric": "bm25_match_qps",
            "value": 0.0,
            "unit": "qps",
            "vs_baseline": 0.0,
            "platform": platform,
            "n_docs": int(os.environ.get("OSTPU_BENCH_DOCS", 100_000)),
            "error": f"{type(e).__name__}: {e}",
        }))
        sys.exit(1)
    if failed_phases:
        log(f"phases that raised: {failed_phases}")
        sys.exit(1)
