"""The configuration kind ``sparse_features`` and the cell PR 35 added, at a
size a test run can hold: the rehearsal through REST against
``msmarco-passage-splade.reference.py``, the reference against a
brute-force oracle, its two controls, its warm-up enumeration, and what
the new cell reports."""

import dataclasses
import math
import os

import numpy as np
import pytest

from benchmarks import compare, harness
from benchmarks.kinds import sparse_features, text_bm25
from bench_tiny import LATE, SEEDS, last_line_ok, run_tiny

# the published widths (vocabulary, tokens a passage, tokens a query), so
# that the configuration's own limits hold
TINY = dict(n_docs=4096, segments=2, n_queries=240, compare_max=48)
LAT = {"edge_ms.lat", "query_phase_ms.lat", "dispatches_per_query.lat",
       "d2h_reads_per_query.lat", "fetch_phase_ms.lat",
       "kernel_ms_per_query.lat", "device_idle_share.lat",
       "compiles_in_window.lat", "sched_lag_ms", "tail_p95_ms.lat",
       "d2h_arrays_per_query.lat", "h2d_arrays_per_query.lat",
       "block_topk_per_query.lat"}
SPARSE = {"sparse_bind_ms.lat", "sparse_tokens_per_query.lat",
          "sparse_postings_per_query.lat",
          "sparse_budget_lanes_per_query.lat", "sparse_topk_roofline"}
CELL = "splade_sparse_paced"


def tiny_cell(**mix) -> harness.Cell:
    cell = harness.load_cell(CELL)
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


# -- what the cell is ----------------------------------------------------------

def test_the_cell_loads_and_reports_its_metrics():
    cell = harness.load_cell(CELL)
    assert cell.cfg["name"] == "msmarco-passage-splade" and cell.chips == 1
    assert cell.cfg["kind"] == "sparse_features"
    assert cell.mix["loop"] == "paced" and cell.mix["warmup_s"] == 4
    assert cell.mix["senders"] == 8
    assert cell.mix["rate"] == int(cell.mix["rate"]) > 0
    assert {m["name"] for m in cell.metrics("end_to_end")} == {
        "latency_p50_ms", "setup_s"}
    assert {m["name"] for m in cell.metrics("per_layer")} >= LAT | SPARSE
    for m in cell.metrics("per_layer"):
        assert m["moves"] == "latency_p50_ms"
        harness.metric_spec(m["name"])          # its file is there
    # membership and lower bounds only: later PRs append cells and metrics
    new = {m["name"]: m for m in cell.bench["per_layer"]
           if m["name"] in SPARSE}
    assert set(new) == SPARSE
    assert all(CELL in m["workloads"] for m in new.values())
    config, = [c for c in cell.bench["configs"]
               if c["name"] == "msmarco-passage-splade"]
    assert config["reduced"] == ["n_docs"]
    mine, = [w for w in cell.bench["workloads"] if w["name"] == CELL]
    assert mine["chips"] == 1 and mine["config"] == config["name"]
    assert len(cell.bench["workloads"]) >= 7


def test_the_configuration_keeps_the_published_shapes():
    cfg = harness.load_cell(CELL).cfg
    pub = cfg["published"]
    assert cfg["vocab"] == 30522 == pub["vocab"] and cfg["k"] == 10 == pub["k"]
    assert pub["n_docs"] == 8_841_823
    assert cfg["passage_tokens"] == [32, 512] and cfg["query_tokens"] == [8,
                                                                          48]
    assert cfg["n_docs"] % cfg["segments"] == 0
    # the issue's cut, or the one further cut its rule allows
    assert (cfg["n_docs"], cfg["segments"]) in ((2_000_000, 8),
                                                (1_000_000, 8))
    assert list(cfg["reduced"]) == ["n_docs"]
    assert len(cfg["source"]) <= 200
    assert set(cfg["limits"]) == set(compare.NUMBERS)
    assert {"score_err", "rank_gap"} <= set(cfg["limits_why"])
    lengths = sparse_features.query_lengths(cfg, cfg["n_queries"])
    assert lengths.min() == 8 and lengths.max() == 48
    assert lengths.mean() == pytest.approx(24, abs=0.3)
    assert {text_bm25.t_pad(n) for n in lengths} == {8, 16, 32, 64}
    one = sparse_features.body(cfg, ((7, 12345), np.array([0.5, 1.25],
                                                          np.float32)))
    assert one == {"query": {"neural_sparse": {"expansion": {
        "query_tokens": {"w7": 0.5, "w12345": 1.25}}}},
        "size": 10, "_source": False}
    assert sparse_features.index_body(cfg)["mappings"]["properties"] == {
        "expansion": {"type": "rank_features"}}


def test_the_reference_imports_nothing_of_the_program_or_the_benchmark():
    path = os.path.join(harness.HERE, "configs",
                        "msmarco-passage-splade.reference.py")
    with open(path, encoding="utf-8") as f:
        imports = [line.split()[1].split(".")[0] for line in f
                   if line.startswith(("import ", "from "))]
    assert imports == ["numpy"]


# -- the data ------------------------------------------------------------------

@pytest.fixture(scope="module")
def seeded():
    cfg = tiny_cell().cfg
    data = sparse_features.generate(cfg, SEEDS[0])
    return cfg, data, sparse_features.queries(cfg, data, SEEDS[0])


def test_passages_read_the_same_both_ways_and_lie_on_the_grid(seeded):
    cfg, data, _q = seeded
    again = sparse_features.generate(cfg, SEEDS[0])
    other = sparse_features.generate(cfg, SEEDS[1])
    assert not np.array_equal(data.segments[0].row_tokens[:1000],
                              other.segments[0].row_tokens[:1000])
    sizes = []
    for sd, sd2 in zip(data.segments, again.segments):
        assert np.array_equal(sd.row_tokens, sd2.row_tokens)
        assert np.array_equal(sd.row_weights, sd2.row_weights)
        assert sd.row_tokens.dtype == np.uint16
        assert sd.row_weights.dtype == sd.weights.dtype == np.float32
        bits = sd.row_weights.view(np.uint32)
        assert not (bits & 0x7FFF).any()                # FeatureField's grid
        assert 0 < sd.row_weights.min() and sd.row_weights.max() <= 3.5
        sizes.append(np.diff(sd.row_starts))
        # a row's tokens ascend and are distinct
        inner = np.ones(len(sd.row_tokens), dtype=bool)
        inner[sd.row_starts[1:-1]] = False
        assert (np.diff(sd.row_tokens.astype(np.int64))[inner[1:]] > 0).all()
        # token-major = passage-major, turned
        row_of = np.repeat(np.arange(sd.n_docs), sizes[-1])
        by_row = {(int(t), int(r), float(w)) for t, r, w in zip(
            sd.row_tokens[:5000], row_of[:5000], sd.row_weights[:5000])}
        tok_of = np.repeat(np.arange(cfg["vocab"]), sd.df)
        by_tok = set(zip(tok_of.tolist(), sd.doc_ids.tolist(),
                         sd.weights.tolist()))
        assert by_row <= by_tok and len(by_tok) == len(sd.doc_ids)
        assert sd.offsets[-1] == len(sd.doc_ids) == sd.row_starts[-1]
        for t in (0, 5, 300):
            a, b = sd.offsets[t], sd.offsets[t + 1]
            assert (np.diff(sd.doc_ids[a:b]) > 0).all()
    sizes = np.concatenate(sizes)
    assert 20 <= sizes.min() and sizes.max() <= 512
    assert sizes.mean() == pytest.approx(230, rel=0.04)
    # the skew the file states: a flat head on a third of the passages, the
    # median token on under 1%
    share = np.sort(data.df)[::-1] / cfg["n_docs"]
    assert 0.30 <= share[0] <= 0.50 and share[47] > 0.2
    assert np.median(data.df) / cfg["n_docs"] < 0.01


def test_queries_take_their_tokens_from_one_passage(seeded):
    cfg, data, queries = seeded
    assert len(queries) == cfg["n_queries"]
    assert sorted(len(t) for t, _w in queries) == sorted(
        sparse_features.query_lengths(cfg, cfg["n_queries"]).tolist())
    rows = [set(map(int, data.row(r)[0])) for r in range(data.n_docs)]
    for tokens, weights in queries[:60]:
        assert list(tokens) == sorted(set(tokens))
        assert weights.dtype == np.float32 and len(weights) == len(tokens)
        assert 0 < weights.min() and weights.max() <= 3.5
        assert any(set(tokens) <= row for row in rows)
        body = sparse_features.body(cfg, (tokens, weights))
        sent = body["query"]["neural_sparse"]["expansion"]["query_tokens"]
        # the JSON number gives the float32 back
        assert [np.float32(v) for v in sent.values()] == weights.tolist()
    assert len({(t, w.tobytes()) for t, w in queries}) == len(queries)


def test_work_counts_the_postings_and_the_accumulators(seeded):
    cfg, data, queries = seeded
    per_seg = cfg["n_docs"] // cfg["segments"]
    for q in queries[:12]:
        postings = int(data.df[list(q[0])].sum())
        assert sparse_features.work_bytes(cfg, data, q) == (
            8.0 * postings + cfg["segments"] * per_seg * 8.0)
        assert sparse_features.work_flops(cfg, data, q) == postings
        assert postings == sum(
            sparse_features.signature(cfg, data, q, si) is not None
            and int(sd.df[list(q[0])].sum())
            for si, sd in enumerate(data.segments))


# -- the reference, an oracle and the controls --------------------------------

def _oracle(data, query, k):
    """Brute force: python dicts over the passages' own lists, exact
    fractions of float64."""
    tokens, weights = query
    qw = {int(t): float(w) for t, w in zip(tokens, weights)}
    scores = {}
    for r in range(data.n_docs):
        toks, ws = data.row(r)
        hit = [qw[int(t)] * float(w) for t, w in zip(toks, ws)
               if int(t) in qw]
        if hit:
            scores[r] = math.fsum(hit)
    order = sorted(scores, key=lambda r: (-scores[r], r))
    return scores, [(r, scores[r]) for r in order[:k]]


def test_the_reference_agrees_with_a_brute_force_oracle(seeded):
    cfg, data, queries = seeded
    sub = queries[:10]
    ref = harness.load_cell(CELL).reference.Reference(cfg, data)
    tops = list(ref.topk_many(sub))
    judged = list(ref.judge_many(sub, [[i for i, _s in t] for t in tops]))
    for q, top, (scores, runner_up, n_match) in zip(sub, tops, judged):
        all_scores, want = _oracle(data, q, cfg["k"])
        assert [i for i, _s in top] == [i for i, _s in want]
        assert [s for _i, s in top] == pytest.approx([s for _i, s in want],
                                                     rel=1e-13)
        assert n_match == len(all_scores)
        assert scores.tolist() == pytest.approx([s for _i, s in want],
                                                rel=1e-13)
        left = sorted(all_scores.values())[::-1][len(want):]
        assert runner_up == pytest.approx(left[0], rel=1e-13)
    # a passage without any of the query's tokens, and ids the shard does
    # not have, score 0
    q = sub[0]
    all_scores, _w = _oracle(data, q, cfg["k"])
    outside = next(r for r in range(data.n_docs) if r not in all_scores)
    (scores, _r, _n), = ref.judge_many([q], [[outside, data.n_docs + 5, -1]])
    assert scores.tolist() == [0.0, 0.0, 0.0]


def _numbers(cell, seed, precision, n=120):
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
@pytest.mark.parametrize("precision", ["bfloat16", "bf16_weights"])
def test_the_control_is_rejected(precision, seed):
    """The accumulation in bfloat16, and the stored column one mantissa
    bit narrower than FeatureField's, each put in the program's place:
    ``score_err`` has to reject both, by a wide margin."""
    numbers, limits = _numbers(tiny_cell(), seed, precision)
    correct, lines = compare.verdict(numbers, limits)
    assert not correct, lines
    assert numbers["malformed"] == 0
    assert numbers["score_err"] > 20 * limits["score_err"]


# -- through REST ---------------------------------------------------------------

def test_cell_runs_end_to_end_and_is_correct(cpu_kernels, breaker_limits):
    result = run_tiny(tiny_cell(), seconds=2.0)
    last_line_ok(result)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] == 40
    assert set(result["metrics"]) == {"latency_p50_ms", "setup_s"}
    assert result["compared"]["responses"]["value"] == 40
    c = result["compared"]["score_err"]
    assert 0 < c["value"] < c["limit"] / 3                  # float32


def test_traced_run_reports_the_sparse_layer(cpu_kernels, breaker_limits):
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
    # at most a term-bag program a segment, one read of all their results
    assert 0 < got["dispatches_per_query.lat"] <= segments * LATE
    assert 0 < got["d2h_reads_per_query.lat"] <= 1 * LATE
    # at most one packed input in and one packed result back a program
    for name in ("h2d_arrays_per_query.lat", "d2h_arrays_per_query.lat"):
        assert 0 < got[name] <= segments * LATE, name
    # the two-stage top-k (ops/topk.py::block_size) or lax.top_k, a
    # program's choice
    assert 0 <= got["block_topk_per_query.lat"] <= got[
        "dispatches_per_query.lat"]
    assert got["compiles_in_window.lat"] == 0
    assert 8 <= got["sparse_tokens_per_query.lat"] <= 48
    assert (got["sparse_tokens_per_query.lat"] * 20
            < got["sparse_postings_per_query.lat"]
            <= got["sparse_budget_lanes_per_query.lat"])
    assert got["sparse_budget_lanes_per_query.lat"] >= segments * 4096
    assert 0 < got["sparse_bind_ms.lat"] < got["query_phase_ms.lat"]


def _swap_in_a_passage_without_a_token(data):
    def tamper(qi, resp):
        hits = resp["hits"]["hits"]
        if qi % 5 == 0 and hits:
            tokens = set(tamper.queries[qi][0])
            hits[-1]["_id"] = next(
                str(r) for r in range(data.n_docs)
                if not tokens & set(map(int, data.row(r)[0])))
        return resp
    return tamper


def _alter_score(qi, resp):
    if qi % 5 == 0 and resp["hits"]["hits"]:
        resp["hits"]["hits"][0]["_score"] *= 1.001
    return resp


def test_a_passage_without_a_query_token_is_malformed(cpu_kernels,
                                                      breaker_limits):
    cell = tiny_cell()
    data = sparse_features.generate(cell.cfg, SEEDS[0])
    tamper = _swap_in_a_passage_without_a_token(data)
    tamper.queries = sparse_features.queries(cell.cfg, data, SEEDS[0])
    result = run_tiny(cell, seconds=2.0, tamper=tamper)
    assert result["correct"] is False
    c = result["compared"]["malformed"]
    assert c["value"] > c["limit"]


def test_an_altered_score_flips_correct(cpu_kernels, breaker_limits):
    result = run_tiny(tiny_cell(), seconds=2.0, tamper=_alter_score)
    assert result["correct"] is False
    c = result["compared"]["score_err"]
    assert c["value"] > c["limit"]


def test_a_dropped_passage_shows_as_rank_gap(seeded):
    """By the comparison itself: the reference's own answers with the
    best passage taken out and the eleventh let in."""
    cfg, data, queries = seeded
    ref = harness.load_cell(CELL).reference.Reference(
        {**cfg, "k": cfg["k"] + 1}, data)
    rows = [[(str(i), s) for i, s in top[1:]]
            for top in ref.topk_many(queries[:48])]
    assert all(len(r) == cfg["k"] for r in rows)
    numbers = compare.compare(ref, queries[:48], rows, cfg["k"])
    assert numbers["malformed"] == 0 and numbers["score_err"] == 0.0
    assert numbers["rank_gap"] > 1000 * cfg["limits"]["rank_gap"]


def test_a_bfloat16_weight_column_flips_correct(cpu_kernels, breaker_limits,
                                                monkeypatch):
    """The control planted in the program: the installer hands the index
    weights one mantissa bit narrower than the field's."""
    def narrower(weights):
        bits = np.ascontiguousarray(weights, np.float32).view(np.uint32)
        return ((bits >> np.uint32(16)) << np.uint32(16)).view(np.float32)

    generate = sparse_features.generate

    def planted(cfg, seed):
        data = generate(cfg, seed)
        for sd in data.segments:
            sd.weights = narrower(sd.weights)      # the index's column only
        return data

    monkeypatch.setattr(sparse_features, "generate", planted)
    result = run_tiny(tiny_cell(), seconds=2.0)
    assert result["correct"] is False
    c = result["compared"]["score_err"]
    assert c["value"] > 20 * c["limit"]


# -- the warm-up enumeration ---------------------------------------------------

def test_program_space_of_the_committed_configuration():
    cfg = harness.load_cell(CELL).cfg
    per_seg = cfg["n_docs"] // cfg["segments"]
    space = sparse_features.program_space(cfg)
    want = []
    for tp in (8, 16, 32, 64):
        most, b = min(tp, 48) * per_seg, 4096
        while True:
            want.append((tp, b))
            if b >= most:
                break
            b *= 4
    assert space == want
    assert sparse_features.BUCKET_MIN == 4096
    if per_seg == 250_000:
        assert [b for tp, b in space if tp == 8][-1] == 4_194_304
        assert [b for tp, b in space if tp == 64][-1] == 16_777_216
        assert len(space) == 6 + 6 + 7 + 7


@pytest.mark.parametrize("seed", SEEDS)
def test_warmup_covers_every_signature_the_query_maker_produces(seed):
    cfg = tiny_cell().cfg
    data = sparse_features.generate(cfg, seed)
    crafted = sparse_features.warmup_queries(cfg, data)
    warmed = set()
    for sig, q in crafted:
        assert {sparse_features.signature(cfg, data, q, si)
                for si in range(cfg["segments"])} == {sig}
        assert 8 <= len(q[0]) <= 48 and len(q[1]) == len(q[0])
        warmed.add(sig)
    assert len(warmed) == len(crafted)
    produced = {sparse_features.signature(cfg, data, q, si)
                for q in sparse_features.queries(cfg, data, seed)
                for si in range(cfg["segments"])}
    assert produced <= warmed <= set(sparse_features.program_space(cfg))
    assert {tp for tp, _b in produced} == {8, 16, 32, 64}


def test_the_signature_mirrors_the_plan_the_program_compiles(cpu_kernels,
                                                             breaker_limits):
    """Against the program: the feature bag's dims in every segment are
    what ``signature`` says."""
    from opensearch_tpu.search import compiler, query_dsl

    session = harness.Session(tiny_cell(), SEEDS[2], harness.device_info())
    try:
        cfg, data = session.cell.cfg, session.data
        searcher = session.served.node.indices.get(
            cfg["index"]).engine_for(0).acquire_searcher()
        for q in session.queries[:24]:
            body = sparse_features.body(cfg, q)
            plan, bind = compiler.compile_query(query_dsl.parse_query(
                body["query"]), searcher.ctx, scored=True)
            assert plan.features and plan.scored
            for si, seg in enumerate(searcher.segments):
                dims, ins = plan.prepare(bind, seg, seg.device(),
                                         searcher.ctx)
                assert dims[:2] == sparse_features.signature(cfg, data, q,
                                                             si)
                assert dims[2] is True                   # the fast lowering
                assert ins[1] is seg.device().postings["expansion"]["tfs"]
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
