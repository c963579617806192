"""The configuration kind ``knn_filtered`` and the cells PR 33 added, at a
size a test run can hold: the rehearsal through REST against
``yfcc-10m-filtered-knn.reference.py``, the reference against a
brute-force oracle, its controls, its warm-up enumeration, and what the
two new cells report."""

import dataclasses
import math
import os

import numpy as np
import pytest

from benchmarks import compare, harness
from benchmarks.kinds import knn_filtered, text_bm25
from bench_tiny import (LATE, SEEDS, TINY as BENCH_TINY, assert_bucket_rule,
                        chips_the_benchmark_allows, last_line_ok,
                        run_tiny)

# the published width, so that the configuration's own limits hold
TINY = dict(n_docs=4096, segments=2, vocab=3000, n_queries=240,
            compare_max=48)
LAT = {"edge_ms.lat", "query_phase_ms.lat", "dispatches_per_query.lat",
       "d2h_reads_per_query.lat", "fetch_phase_ms.lat",
       "kernel_ms_per_query.lat", "device_idle_share.lat",
       "compiles_in_window.lat", "sched_lag_ms", "tail_p95_ms.lat"}
FILTERED = {"knn_filter_ms.lat", "knn_scan_dispatch_ms.lat",
            "knn_filter_programs_per_query.lat", "knn_filtered_roofline"}
# some of the .tput metrics sift_closed reports: a later change appends
# cells and metrics, so a cell's set has to contain these and may hold more
TPUT = {"edge_ms.tput", "query_phase_ms.tput", "dispatches_per_query.tput",
        "d2h_reads_per_query.tput", "fetch_phase_ms.tput",
        "kernel_ms_per_query.tput", "device_idle_share.tput",
        "compiles_in_window.tput", "knn_scan_roofline.tput"}


def tiny_cell(**mix) -> harness.Cell:
    cell = harness.load_cell("yfcc_filtered_paced")
    return dataclasses.replace(
        cell, cfg={**cell.cfg, **TINY},
        mix={**cell.mix, "warmup_s": 0.3, "rate": 20, **mix})


@pytest.fixture
def breaker_limits():
    """The configuration raises the breakers' limits for good (a dynamic
    cluster setting lands on the process's breaker service)."""
    from opensearch_tpu.common.breakers import breaker_service

    yield
    breaker_service().set_limit("fielddata", 0)
    breaker_service().set_limit("total", 0)


# -- what the cells are ------------------------------------------------------

@pytest.mark.parametrize("name,config,loop,end,layers", [
    ("yfcc_filtered_paced", "yfcc-10m-filtered-knn", "paced",
     "latency_p50_ms", LAT | FILTERED),
    ("sift_closed", "sift-128-exact-knn", "closed", "qps", TPUT)])
def test_new_cell_loads_and_reports_exactly_its_metrics(name, config, loop,
                                                        end, layers):
    cell = harness.load_cell(name)
    assert cell.cfg["name"] == config and cell.chips == 1
    assert cell.mix["loop"] == loop and cell.mix["warmup_s"] == 4
    if loop == "paced":
        assert cell.mix["senders"] == 8
        assert cell.mix["rate"] == int(cell.mix["rate"]) > 0
    else:
        assert cell.mix["clients"] in (1, 2, 4, 6, 8)
    assert {m["name"] for m in cell.metrics("end_to_end")} == {end,
                                                               "setup_s"}
    assert {m["name"] for m in cell.metrics("per_layer")} >= layers
    for m in cell.metrics("per_layer"):
        assert m["moves"] == end
        harness.metric_spec(m["name"])          # its file is there
    bench = cell.bench
    assert len(bench["workloads"]) >= 6
    assert chips_the_benchmark_allows(bench["workloads"])


def test_sift_closed_does_not_wrap_its_query_list():
    """A repeated kNN body would be served from the plan cache, pre-pass
    and all: at the sweep's best rate a 40 s window must stay inside the
    seven eighths of the list the harness leaves it."""
    cell = harness.load_cell("sift_closed")
    head = cell.cfg["n_queries"] - cell.cfg["n_queries"] // 8
    assert head == 8750
    assert cell.mix["qps_at_sweep"] * 40 * 1.1 < head


def test_the_configuration_keeps_the_published_shapes():
    cfg = harness.load_cell("yfcc_filtered_paced").cfg
    pub = cfg["published"]
    assert (cfg["dim"], cfg["space"], cfg["k"], cfg["vocab"]) == (
        192, "l2", 10, 200386) == (pub["dim"], pub["space"], pub["k"],
                                   pub["vocab"])
    assert cfg["query_tags"] == [1, 2] == pub["query_tags"]
    assert pub["n_docs"] == 10_000_000 and pub["n_queries"] == 100_000
    assert cfg["n_docs"] % cfg["segments"] == 0
    # nothing cut, or the one cut the issue's rule allows
    assert (cfg["n_docs"], list(cfg["reduced"])) in (
        (10_000_000, []), (5_000_000, ["n_docs"]))
    one = knn_filtered.body(cfg, ((7,), np.zeros(192, np.float32)))
    two = knn_filtered.body(cfg, ((7, 12345), np.zeros(192, np.float32)))
    spec = one["query"]["knn"]["vec"]
    assert spec["k"] == 10 and len(spec["vector"]) == 192
    assert spec["filter"] == {"term": {"tags": "t000007"}}
    assert two["query"]["knn"]["vec"]["filter"] == {"bool": {"filter": [
        {"term": {"tags": "t000007"}}, {"term": {"tags": "t012345"}}]}}
    assert one["size"] == 10 and one["_source"] is False
    mapping = knn_filtered.index_body(cfg)["mappings"]["properties"]
    assert mapping == {
        "vec": {"type": "knn_vector", "dimension": 192,
                "method": {"name": "exact", "space_type": "l2"}},
        "tags": {"type": "keyword"}}


def test_the_reference_imports_nothing_of_the_program_or_the_benchmark():
    path = os.path.join(harness.HERE, "configs",
                        "yfcc-10m-filtered-knn.reference.py")
    with open(path, encoding="utf-8") as f:
        imports = [line.split()[1].split(".")[0] for line in f
                   if line.startswith(("import ", "from "))]
    assert imports == ["numpy"]


# -- the data ---------------------------------------------------------------

@pytest.fixture(scope="module")
def seeded():
    cfg = tiny_cell().cfg
    data = knn_filtered.generate(cfg, SEEDS[0])
    return cfg, data, knn_filtered.queries(cfg, data, SEEDS[0])


def test_vectors_lie_on_the_grid_and_tags_read_the_same_both_ways(seeded):
    cfg, data, _q = seeded
    raw = data.vectors * knn_filtered.GRID
    assert data.vectors.dtype == np.float32
    assert np.array_equal(raw, np.round(raw))
    assert raw.min() >= 0 and raw.max() < 255 * 64
    again = knn_filtered.generate(cfg, SEEDS[0])
    assert np.array_equal(data.vectors, again.vectors)
    assert all(np.array_equal(a.doc_ids, b.doc_ids)
               for a, b in zip(data.segments, again.segments))
    other = knn_filtered.generate(cfg, SEEDS[1])
    assert not np.array_equal(data.vectors, other.vectors)
    for sd in data.segments:
        by_row = {(int(t), i) for i in range(sd.n_docs)
                  for t in sd.row_tags[sd.row_starts[i]: sd.row_starts[i + 1]]}
        by_tag = {(t, int(d)) for t in np.flatnonzero(sd.df)
                  for d in sd.doc_ids[sd.offsets[t]: sd.offsets[t + 1]]}
        assert by_row == by_tag and len(by_row) == len(sd.doc_ids)
        sizes = np.diff(sd.row_starts)
        assert sizes.min() >= 1 and sizes.max() <= knn_filtered.BAG_MAX
        assert all(np.all(np.diff(sd.row_tags[a:b]) > 0) for a, b in zip(
            sd.row_starts[:64], sd.row_starts[1:65]))
    # the head of the vocabulary is flat and near a tenth of the rows
    share = np.sort(data.df)[::-1] / cfg["n_docs"]
    assert share[0] == pytest.approx(
        knn_filtered.head_shares(cfg["vocab"], 1)[0], rel=0.15)
    assert share[1] > 0.8 * share[0]


def test_queries_take_their_tags_from_one_row_s_bag(seeded):
    cfg, data, queries = seeded
    assert len(queries) == cfg["n_queries"]
    assert [len(t) for t, _v in queries[:8]] == [1, 2] * 4
    counts = []
    for tags, vec in queries:
        assert len(set(tags)) == len(tags) and list(tags) == sorted(tags)
        assert vec.dtype == np.float32 and vec.shape == (cfg["dim"],)
        assert np.array_equal(vec * 64, np.round(vec * 64))
        assert vec.tolist() == [float(repr(x)) for x in vec.tolist()]
        counts.append(len(data.rows_with(tags)))
    counts = np.array(counts)
    assert counts.min() >= 1                       # its own row passes
    assert (counts < cfg["k"]).any() and counts.max() > 0.05 * cfg["n_docs"]
    assert len({(t, v.tobytes()) for t, v in queries}) == len(queries)


def test_work_counts_the_rows_that_pass_and_not_the_rows_scanned(seeded):
    cfg, data, queries = seeded
    for q in queries[:12]:
        tags = list(q[0])
        n_match = len(data.rows_with(tags))
        assert knn_filtered.work_bytes(cfg, data, q) == (
            4.0 * data.df[tags].sum() + n_match * cfg["dim"] * 4.0)
        assert knn_filtered.work_bytes(cfg, data, q) <= (
            4.0 * len(tags) * cfg["n_docs"]
            + cfg["n_docs"] * cfg["dim"] * 4.0)
        assert knn_filtered.work_flops(cfg, data, q) == (
            data.df[tags].sum() + 2.0 * n_match * cfg["dim"])


# -- the reference, an oracle and the controls -------------------------------

def _oracle(data, query, k):
    """Brute force: python sets over the rows' bags, float64 distances."""
    tags, vec = query
    rows = [r for r in range(data.n_docs) if set(tags) <= set(
        data.bag(r).tolist())]
    diff = data.vectors[rows].astype(np.float64) - vec.astype(np.float64)
    scores = 1.0 / (1.0 + (diff * diff).sum(axis=1))
    order = sorted(range(len(rows)), key=lambda i: (-scores[i], rows[i]))
    return rows, scores, [(rows[i], scores[i]) for i in order[:k]]


def test_the_reference_agrees_with_a_brute_force_oracle(seeded):
    cfg, data, queries = seeded
    sub = queries[:24]
    ref = harness.load_cell("yfcc_filtered_paced").reference.Reference(
        cfg, data)
    tops = list(ref.topk_many(sub))
    judged = list(ref.judge_many(sub, [[i for i, _s in t] for t in tops]))
    for q, top, (scores, runner_up, n_match) in zip(sub, tops, judged):
        rows, all_scores, want = _oracle(data, q, cfg["k"])
        assert [i for i, _s in top] == [i for i, _s in want]
        assert [s for _i, s in top] == pytest.approx([s for _i, s in want],
                                                     rel=1e-12)
        assert n_match == len(rows)
        assert scores.tolist() == pytest.approx([s for _i, s in want],
                                                rel=1e-12)
        left = sorted(all_scores)[::-1][len(want):]
        assert runner_up == (pytest.approx(left[0], rel=1e-12) if left
                             else -np.inf)
    # a row that fails the filter, and a row that does not exist, score 0
    q = next(q for q in sub if len(data.rows_with(q[0])) < data.n_docs - 1)
    outside = next(r for r in range(data.n_docs)
                   if r not in set(data.rows_with(q[0]).tolist()))
    (scores, _r, _n), = ref.judge_many([q], [[outside, data.n_docs + 5, -1]])
    assert scores.tolist() == [0.0, 0.0, 0.0]


def _numbers(cell, seed, precision, n=240):
    cfg = cell.cfg
    data = cell.kind.generate(cfg, seed)
    queries = cell.kind.queries(cfg, data, seed)[:n]
    exact = cell.reference.Reference(cfg, data)
    served = cell.reference.Reference(cfg, data, precision)
    rows = [[(str(i), s) for i, s in r] for r in served.topk_many(queries)]
    numbers = compare.compare(exact, queries, rows, cfg["k"])
    numbers.update(failed=0, device_faults=0)
    return numbers, cfg["limits"]


@pytest.mark.parametrize("seed", SEEDS)
def test_the_reference_passes_itself(seed):
    numbers, limits = _numbers(tiny_cell(), seed, "float64")
    correct, lines = compare.verdict(numbers, limits)
    assert correct, lines
    assert numbers["score_err"] == 0.0 and numbers["rank_gap"] == 0.0


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("precision", ["bf16x3", "bfloat16"])
def test_the_control_is_rejected(precision, seed):
    """The scan's matrix product one precision step below the
    configuration's (three bf16 passes, ``Precision.HIGH``), and at one
    pass, put in the program's place: ``score_err`` has to reject both."""
    numbers, limits = _numbers(tiny_cell(), seed, precision)
    correct, lines = compare.verdict(numbers, limits)
    assert not correct, lines
    assert numbers["malformed"] == 0
    assert numbers["score_err"] > limits["score_err"] * (
        1.0 if precision == "bf16x3" else 100.0)


# -- through REST -------------------------------------------------------------

def test_cell_runs_end_to_end_and_is_correct(cpu_kernels, breaker_limits):
    result = run_tiny(tiny_cell(), seconds=2.0)
    last_line_ok(result)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] == 40
    assert set(result["metrics"]) == {"latency_p50_ms", "setup_s"}
    assert result["compared"]["responses"]["value"] == 40
    assert 0 < result["compared"]["score_err"]["value"]      # float32


def test_traced_run_reports_the_filter_layer(cpu_kernels, breaker_limits):
    cell = tiny_cell()
    result = run_tiny(cell, seconds=2.0, traced=True)
    last_line_ok(result)
    assert result["correct"] is True
    got = {n: m["value"] for n, m in result["metrics"].items()}
    by_source = {m["name"]: m["source"] for m in cell.metrics("per_layer")}
    assert set(got) == {n for n, s in by_source.items()
                        if s != "device_trace"}
    assert all(math.isfinite(v) for v in got.values())
    segments = cell.cfg["segments"]
    # at most a mask program, a scan and a winners' program a segment; at
    # most one read for the scans' candidates and one for the top-k
    assert 0 < got["knn_filter_programs_per_query.lat"] <= segments * LATE
    assert 0 < got["dispatches_per_query.lat"] <= 3 * segments * LATE
    assert 1 <= got["d2h_reads_per_query.lat"] <= 2 * LATE
    assert got["compiles_in_window.lat"] == 0
    assert got["knn_filter_ms.lat"] > 0 and got["knn_scan_dispatch_ms.lat"] > 0
    assert (got["knn_filter_ms.lat"] + got["knn_scan_dispatch_ms.lat"]
            < got["query_phase_ms.lat"])


def _swap_in_a_row_without_the_tag(data):
    """A response that holds a row the filter rejects."""
    def tamper(qi, resp):
        hits = resp["hits"]["hits"]
        if qi % 5 == 0 and hits:
            passing = set(data.rows_with(tamper.queries[qi][0]).tolist())
            hits[-1]["_id"] = next(str(r) for r in range(data.n_docs)
                                   if r not in passing)
        return resp
    return tamper


def _alter_score(qi, resp):
    if qi % 5 == 0 and resp["hits"]["hits"]:
        resp["hits"]["hits"][0]["_score"] *= 1.001
    return resp


def test_a_row_without_a_required_tag_is_malformed(cpu_kernels,
                                                   breaker_limits):
    cell = tiny_cell()
    data = knn_filtered.generate(cell.cfg, SEEDS[0])
    tamper = _swap_in_a_row_without_the_tag(data)
    tamper.queries = knn_filtered.queries(cell.cfg, data, SEEDS[0])
    result = run_tiny(cell, seconds=2.0, tamper=tamper)
    assert result["correct"] is False
    c = result["compared"]["malformed"]
    assert c["value"] > c["limit"]


def test_an_altered_score_flips_correct(cpu_kernels, breaker_limits):
    result = run_tiny(tiny_cell(), seconds=2.0, tamper=_alter_score)
    assert result["correct"] is False
    c = result["compared"]["score_err"]
    assert c["value"] > c["limit"]


def test_a_dropped_passing_row_shows_as_rank_gap(seeded):
    """By the comparison itself: the reference's own answers with the
    best row of a full list taken out and the eleventh let in."""
    cfg, data, queries = seeded
    ref = harness.load_cell("yfcc_filtered_paced").reference.Reference(
        {**cfg, "k": cfg["k"] + 1}, data)
    rows = []
    for top in ref.topk_many(queries[:48]):
        keep = top[1:] if len(top) == cfg["k"] + 1 else top[: cfg["k"]]
        rows.append([(str(i), s) for i, s in keep])
    assert any(len(r) == cfg["k"] for r in rows)
    numbers = compare.compare(ref, queries[:48], rows, cfg["k"])
    assert numbers["malformed"] == 0 and numbers["score_err"] == 0.0
    assert numbers["rank_gap"] > 1000 * cfg["limits"]["rank_gap"]


def test_a_scan_one_precision_down_flips_correct(cpu_kernels,
                                                 breaker_limits,
                                                 monkeypatch):
    """The control planted in the program: the scan on operands rounded
    to bfloat16."""
    import jax.numpy as jnp

    from opensearch_tpu.ops import knn as knn_ops

    exact = knn_ops.knn_topk

    def one_pass(vectors, valid, query, *, space, k):
        def low(a):
            return a.astype(jnp.bfloat16).astype(jnp.float32)

        return exact(low(vectors), valid, low(query), space=space, k=k)

    monkeypatch.setattr(knn_ops, "knn_topk", one_pass)
    result = run_tiny(tiny_cell(), seconds=2.0)
    assert result["correct"] is False


def test_sift_closed_runs_end_to_end_at_a_tiny_size(cpu_kernels):
    cell = harness.load_cell("sift_closed")
    cell = dataclasses.replace(
        cell, cfg={**cell.cfg, **BENCH_TINY["knn_exact"], "n_queries": 4000},
        mix={**cell.mix, "warmup_s": 0.3, "clients": 2})
    result = run_tiny(cell, seconds=1.0, traced=True)
    last_line_ok(result)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] < 3500        # no query sent twice
    by_source = {m["name"]: m["source"] for m in cell.metrics("per_layer")}
    assert set(result["metrics"]) == {n for n, s in by_source.items()
                                      if s != "device_trace"}
    assert result["metrics"]["compiles_in_window.tput"]["value"] == 0
    # at most the pre-pass's program and the winners' in the one segment
    assert 0 < result["metrics"]["dispatches_per_query.tput"][
        "value"] <= 2 * LATE


# -- the warm-up enumeration --------------------------------------------------

def test_program_space_of_the_committed_configuration():
    cfg = harness.load_cell("yfcc_filtered_paced").cfg
    space = knn_filtered.program_space(cfg)
    # one folded bag a filter of n tags: t_pad(n) x the bucket rule up to
    # the n most frequent tags' postings in a segment, with a quarter of
    # room; then the scan and the winners' program
    assert space[-2:] == [("knn_topk", 10), ("run_topk_winners", 10)]
    per_seg = cfg["n_docs"] // cfg["segments"]
    lo, hi = cfg["query_tags"]
    for n in range(lo, hi + 1):
        tp = text_bm25.t_pad(n)
        bound = min(1.0, 1.25 * float(
            knn_filtered.head_shares(cfg["vocab"], n).sum())) * per_seg
        assert_bucket_rule([b for t, b in space[:-2] if t == tp], bound)
    assert {t for t, _b in space[:-2]} == {text_bm25.t_pad(n)
                                          for n in range(lo, hi + 1)}
    shares = knn_filtered.head_shares(cfg["vocab"], 2)
    assert shares[0] == pytest.approx(0.101, abs=0.002)
    assert 65536 < 1.25 * shares[0] * 1e6 < 1.25 * shares.sum() * 1e6 < 262144


@pytest.mark.parametrize("seed", SEEDS)
def test_warmup_covers_every_signature_the_query_maker_produces(seed):
    cfg = tiny_cell().cfg
    data = knn_filtered.generate(cfg, seed)
    crafted = knn_filtered.warmup_queries(cfg, data)
    warmed = set()
    for sig, q in crafted:
        assert {knn_filtered.signature(cfg, data, q, si)
                for si in range(cfg["segments"])} == {sig}
        assert 1 <= len(q[0]) <= 2 and q[1].shape == (cfg["dim"],)
        warmed.add(sig)
    produced = {knn_filtered.signature(cfg, data, q, si)
                for q in knn_filtered.queries(cfg, data, seed)
                for si in range(cfg["segments"])}
    assert produced <= warmed <= set(knn_filtered.program_space(cfg))


def test_the_signature_mirrors_the_plan_the_program_compiles(cpu_kernels,
                                                             breaker_limits):
    """Against the program: the folded filter's dims in every segment are
    what ``signature`` says, for one tag and for two."""
    from opensearch_tpu.search import compiler, query_dsl

    session = harness.Session(tiny_cell(), SEEDS[2], harness.device_info())
    try:
        cfg, data = session.cell.cfg, session.data
        shard = session.served.node.indices.get(
            cfg["index"]).engine_for(0)
        searcher = shard.acquire_searcher()
        for q in session.queries[:16]:
            plan, bind = compiler.compile_query(query_dsl.parse_query(
                knn_filtered.tag_filter(q[0])), searcher.ctx, scored=False)
            for si, seg in enumerate(searcher.segments):
                dims, _ins = plan.prepare(bind, seg, seg.device(),
                                          searcher.ctx)
                assert dims[:2] == knn_filtered.signature(cfg, data, q, si)
    finally:
        session.close()


def test_a_new_seed_compiles_nothing_after_the_warm_up(cpu_kernels,
                                                       breaker_limits):
    """Against the program: after set-up, every request of the seed's
    list runs without one more executable (jax's own count)."""
    session = harness.Session(tiny_cell(), SEEDS[1], harness.device_info())
    try:
        before = session.counter.programs
        assert before == session.programs_setup
        for qi in range(len(session.queries)):
            session.send(qi)
        assert session.counter.programs == before
    finally:
        session.close()
