"""The configuration kind ``hybrid_bm25_knn`` and the cells PR 29 added,
at a size a test run can hold: the rehearsal through REST against
``beir-nq-hybrid.reference.py``, its controls, its warm-up enumeration,
and what the two new cells report."""

import dataclasses
import os

import numpy as np
import pytest

from benchmarks import compare, harness
from benchmarks.kinds import hybrid_bm25_knn, text_bm25
from bench_tiny import LATE, SEEDS, assert_bucket_rule, last_line_ok, run_tiny

TINY = dict(n_docs=4096, segments=2, vocab=6000, n_queries=240, dim=32,
            compare_max=48)
N_QUERIES = 48
# .lat metrics the two cells report: a later change appends cells and
# metrics, so a cell's set has to contain these and may hold more
LAT = {"edge_ms.lat", "query_phase_ms.lat", "dispatches_per_query.lat",
       "d2h_reads_per_query.lat", "fetch_phase_ms.lat",
       "kernel_ms_per_query.lat", "device_idle_share.lat",
       "compiles_in_window.lat", "sched_lag_ms", "tail_p95_ms.lat"}
HYBRID = {"hybrid_subquery_ms.lat", "hybrid_normalize_ms.lat",
          "hybrid_subqueries_per_query.lat",
          "hybrid_candidates_per_query.lat", "hybrid_roofline"}


def tiny_cell(**mix) -> harness.Cell:
    cell = harness.load_cell("nq_hybrid_paced")
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

@pytest.mark.parametrize("name,config,extra", [
    ("nq_hybrid_paced", "beir-nq-hybrid", HYBRID),
    ("msmarco_paced", "msmarco-passage-bm25", set())])
def test_new_cell_loads_and_reports_exactly_its_metrics(name, config, extra):
    cell = harness.load_cell(name)
    assert cell.cfg["name"] == config and cell.chips == 1
    assert cell.mix["loop"] == "paced" and cell.mix["senders"] == 8
    assert cell.mix["warmup_s"] == 4 and cell.mix["rate"] == int(
        cell.mix["rate"]) > 0
    assert {m["name"] for m in cell.metrics("end_to_end")} == {
        "latency_p50_ms", "setup_s"}
    assert {m["name"] for m in cell.metrics("per_layer")} >= LAT | extra
    for m in cell.metrics("per_layer"):
        harness.metric_spec(m["name"])          # its file is there


def test_the_configuration_keeps_the_published_shapes():
    cfg = harness.load_cell("nq_hybrid_paced").cfg
    assert (cfg["dim"], cfg["space"], cfg["k"], cfg["knn_k"]) == (
        768, "innerproduct", 10, 100)
    assert cfg["passage_tokens"] == [40, 120]
    assert cfg["query_terms"] == [5, 14] and cfg["n_queries"] == 3452
    assert list(cfg["reduced"]) == ["n_docs"]
    assert cfg["n_docs"] % cfg["segments"] == 0
    assert 1_000_000 <= cfg["n_docs"] <= cfg["published"]["n_docs"]
    body = hybrid_bm25_knn.body(cfg, ([3, 5], np.zeros(768, np.float32)))
    match, knn = body["query"]["hybrid"]["queries"]
    assert match == {"match": {"body": "t3 t5"}}
    assert knn["knn"]["vec"]["k"] == 100 and len(
        knn["knn"]["vec"]["vector"]) == 768
    assert body["size"] == 10 and body["_source"] is False
    lens = hybrid_bm25_knn.query_lengths(cfg["n_queries"])
    assert (lens.min(), lens.max(), len(lens)) == (5, 14, 3452)
    assert lens.mean() == pytest.approx(9.1, abs=0.1)


def test_the_reference_imports_nothing_of_the_program_or_the_benchmark():
    path = os.path.join(harness.HERE, "configs",
                        "beir-nq-hybrid.reference.py")
    with open(path, encoding="utf-8") as f:
        imports = [line.split()[1].split(".")[0] for line in f
                   if line.startswith(("import ", "from "))]
    assert imports == ["numpy"]


# -- the data ---------------------------------------------------------------

def test_vectors_lie_on_the_grid_and_queries_lean_on_their_passage():
    cfg = {**tiny_cell().cfg, "dim": 768}
    data = hybrid_bm25_knn.generate(cfg, SEEDS[0])
    raw = data.vectors * hybrid_bm25_knn.GRID
    assert data.vectors.dtype == np.float32
    assert np.array_equal(raw, np.round(raw))
    assert raw.min() >= -512 and raw.max() < 512
    assert abs(float(data.vectors.mean())) < 0.01
    again = hybrid_bm25_knn.generate(cfg, SEEDS[0])
    assert np.array_equal(data.vectors, again.vectors)
    other = hybrid_bm25_knn.generate(cfg, SEEDS[1])
    assert not np.array_equal(data.vectors, other.vectors)
    queries = hybrid_bm25_knn.queries(cfg, data, SEEDS[0])
    assert len(queries) == cfg["n_queries"]
    leads = 0
    for terms, vec in queries[:40]:
        assert 5 <= len(terms) <= 14 and len(set(terms)) == len(terms)
        assert np.array_equal(vec * 256, np.round(vec * 256))
        assert vec.tolist() == [float(repr(x)) for x in vec.tolist()]
        dots = data.vectors.astype(np.float64) @ vec.astype(np.float64)
        leads += int(np.argmax(dots)) in _docs_holding(data, terms)
    # at the published width the source passage leads the k-NN list
    assert leads >= 36


def _docs_holding(data, terms) -> set:
    """Docs that hold every term of the query: its source passage."""
    docs = None
    for t in terms:
        here = set()
        for sd in data.text.segments:
            a, b = sd.offsets[t], sd.offsets[t + 1]
            here.update((sd.doc_ids[a:b].astype(np.int64) + sd.lo).tolist())
        docs = here if docs is None else docs & here
    return docs


def test_work_is_both_sub_queries():
    cell = tiny_cell()
    data = hybrid_bm25_knn.generate(cell.cfg, 5)
    q = hybrid_bm25_knn.queries(cell.cfg, data, 5)[0]
    scan = cell.cfg["n_docs"] * cell.cfg["dim"] * 4.0
    assert hybrid_bm25_knn.work_bytes(cell.cfg, data, q) == (
        text_bm25.work_bytes(cell.cfg, data.text, q[0]) + scan)
    assert hybrid_bm25_knn.work_flops(cell.cfg, data, q) > scan / 2


# -- the reference and its controls -------------------------------------------

def _numbers(cell, seed, precision):
    cfg = cell.cfg
    data = cell.kind.generate(cfg, seed)
    queries = cell.kind.queries(cfg, data, seed)[:N_QUERIES]
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
@pytest.mark.parametrize("precision", ["bfloat16", "bm25_bfloat16",
                                       "knn_bfloat16"])
def test_the_control_is_rejected(precision, seed):
    """Either sub-query computed one precision below float32, put in the
    program's place: the comparison has to reject it."""
    numbers, limits = _numbers(tiny_cell(), seed, precision)
    correct, lines = compare.verdict(numbers, limits)
    assert not correct, lines
    # a doc on the wrong side of a cut (malformed), or a score that the
    # lower precision moved by far more than the limit
    assert numbers["malformed"] or (
        numbers["score_err"] > 20 * limits["score_err"])


def _reference_module():
    return harness.load_cell("nq_hybrid_paced").reference


def test_reference_normalisation_is_the_plugins():
    ref = _reference_module()
    assert ref.min_max(np.array([7.5])).tolist() == [1.0]
    assert ref.min_max(np.array([2.0, 2.0])).tolist() == [1.0, 1.0]
    assert ref.min_max(np.array([5.0, 3.0, 1.0])).tolist() == [1.0, 0.5,
                                                               0.001]
    ids = np.array([4, 9])
    both = ref.combine([(ids, np.array([3.0, 1.0])),
                        (np.array([9]), np.array([8.0]))])
    assert both == {4: 0.5, 9: pytest.approx(0.5005)}
    assert ref.ip_score(np.array([3.0, 0.0, -1.0])).tolist() == [4.0, 1.0,
                                                                 0.5]


def test_the_cut_is_tie_aware_and_nothing_more():
    ref = _reference_module()
    ids = np.array([1, 2, 3, 4, 5])
    scores = np.array([9.0, 5.0, 5.0 - 2e-6, 5.0 - 3e-6, 1.0])
    assert ref.cut(ids, scores, 2)[0].tolist() == [1, 2]
    # docs within rounding of the cut are interchangeable: the response's
    assert ref.cut(ids, scores, 2, prefer=[4, 5])[0].tolist() == [1, 4]
    assert ref.cut(ids, scores, 3, prefer=[4])[0].tolist() == [1, 2, 4]
    # a doc clearly below the cut never is
    assert ref.cut(ids, scores, 4, prefer=[5])[0].tolist() == [1, 2, 3, 4]
    apart = np.array([9.0, 5.0, 4.999, 4.998, 1.0])
    assert ref.cut(ids, apart, 2, prefer=[3, 4])[0].tolist() == [1, 2]


# -- through REST -------------------------------------------------------------

def test_cell_runs_end_to_end_and_is_correct(cpu_kernels, breaker_limits):
    cell = tiny_cell()
    result = run_tiny(cell, seconds=2.0)
    last_line_ok(result)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] == 40
    assert set(result["metrics"]) == {"latency_p50_ms", "setup_s"}
    assert result["compared"]["responses"]["value"] == 40
    assert result["compared"]["score_err"]["value"] > 0      # float32


def test_traced_run_reports_the_hybrid_layer(cpu_kernels, breaker_limits):
    cell = tiny_cell()
    result = run_tiny(cell, seconds=2.0, traced=True)
    last_line_ok(result)
    assert result["correct"] is True
    got = {n: m["value"] for n, m in result["metrics"].items()}
    by_source = {m["name"]: m["source"] for m in cell.metrics("per_layer")}
    assert set(got) == {n for n, s in by_source.items()
                        if s != "device_trace"}
    segments = cell.cfg["segments"]
    # at most a term-bag program, a scan and a winners' program a
    # segment; at most one read a sub-query's top-k and one for the scan's
    # candidates
    assert 0 < got["dispatches_per_query.lat"] <= 3 * segments * LATE
    assert 1 <= got["d2h_reads_per_query.lat"] <= 3 * LATE
    assert got["hybrid_subqueries_per_query.lat"] == pytest.approx(
        2, rel=0.06)
    assert 10 <= got["hybrid_candidates_per_query.lat"] <= 20 * 1.06
    assert got["compiles_in_window.lat"] == 0
    assert got["hybrid_subquery_ms.lat"] > got["hybrid_normalize_ms.lat"] > 0
    assert got["query_phase_ms.lat"] > 2 * got["hybrid_subquery_ms.lat"] * .9


def _swap_in_a_stranger(qi, resp):
    hits = resp["hits"]["hits"]
    if qi % 5 == 0 and hits:
        taken = {h["_id"] for h in hits}
        hits[-1]["_id"] = next(str(i) for i in range(100)
                               if str(i) not in taken)
    return resp


def _alter_score(qi, resp):
    if qi % 5 == 0 and resp["hits"]["hits"]:
        resp["hits"]["hits"][0]["_score"] *= 1.001
    return resp


@pytest.mark.parametrize("tamper,number", [
    (_alter_score, "score_err"), (_swap_in_a_stranger, "malformed")])
def test_an_altered_answer_flips_correct(cpu_kernels, breaker_limits,
                                         tamper, number):
    result = run_tiny(tiny_cell(), seconds=2.0, tamper=tamper)
    assert result["correct"] is False
    c = result["compared"][number]
    assert c["value"] > c["limit"]


def test_a_scan_one_precision_down_flips_correct(cpu_kernels,
                                                 breaker_limits,
                                                 monkeypatch):
    """The control planted in the program: the k-NN sub-query's scan on
    operands rounded to bfloat16."""
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


# -- the warm-up enumeration --------------------------------------------------

def test_program_space_of_the_committed_configuration():
    cfg = harness.load_cell("nq_hybrid_paced").cfg
    space = hybrid_bm25_knn.program_space(cfg)
    # each t_pad of 5-14 terms x the bucket rule up to what its terms can
    # hold in a segment (no df passes the segment's docs), then the k-NN
    # pair
    assert space[-2:] == [("knn_topk", 100), ("run_topk_winners", 10)]
    lo, hi = cfg["query_terms"]
    pads = sorted({text_bm25.t_pad(n) for n in range(lo, hi + 1)})
    assert [t for t, _b in space[:-2]] == sorted(t for t, _b in space[:-2])
    assert {t for t, _b in space[:-2]} == set(pads)
    per_seg = cfg["n_docs"] // cfg["segments"]
    for tp in pads:
        assert_bucket_rule([b for t, b in space[:-2] if t == tp],
                           min(tp, hi) * per_seg)


@pytest.mark.parametrize("seed", SEEDS)
def test_warmup_covers_every_signature_the_query_maker_produces(seed):
    cfg = tiny_cell().cfg
    data = hybrid_bm25_knn.generate(cfg, seed)
    crafted = hybrid_bm25_knn.warmup_queries(cfg, data)
    warmed = set()
    for sig, (terms, vec) in crafted:
        assert {hybrid_bm25_knn.signature(cfg, data, (terms, vec), si)
                for si in range(cfg["segments"])} == {sig}
        assert 5 <= len(terms) <= 14 and vec.shape == (cfg["dim"],)
        warmed.add(sig)
    produced = {hybrid_bm25_knn.signature(cfg, data, q, si)
                for q in hybrid_bm25_knn.queries(cfg, data, seed)
                for si in range(cfg["segments"])} - {None}
    assert produced <= warmed <= set(hybrid_bm25_knn.program_space(cfg))


def test_a_new_seed_compiles_nothing_after_the_warm_up(cpu_kernels,
                                                       breaker_limits):
    """Against the program: after set-up, every hybrid request of the
    seed's list runs without one more executable (jax's own count)."""
    session = harness.Session(tiny_cell(), SEEDS[1], harness.device_info())
    try:
        before = session.counter.programs
        assert before == session.programs_setup
        for qi in range(len(session.queries)):
            session.send(qi)
        assert session.counter.programs == before
    finally:
        session.close()
