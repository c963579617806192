"""The configuration kind ``text_positions`` and the cell PR 39 added, at a
size a test run can hold: the rehearsal through REST against
``pmc-fulltext-phrase.reference.py``, the reference against a brute-force
oracle, its three controls, its warm-up enumeration, what the new cell
reports, and the installed columns against ``SegmentWriter``'s."""

import collections
import dataclasses
import math
import os

import numpy as np
import pytest

from benchmarks import compare, harness
from benchmarks.kinds import text_positions
from bench_tiny import LATE, SEEDS, last_line_ok, run_tiny

# the published law and request shapes; articles and vocabulary cut to what
# a test run can hold
TINY = dict(n_docs=1200, segments=3, n_queries=320, compare_max=48,
            length_mean=700, article_tokens=[200, 3000], vocab=200000)
LAT = {"edge_ms.lat", "query_phase_ms.lat", "dispatches_per_query.lat",
       "d2h_reads_per_query.lat", "fetch_phase_ms.lat",
       "kernel_ms_per_query.lat", "device_idle_share.lat",
       "compiles_in_window.lat", "sched_lag_ms", "tail_p95_ms.lat",
       "d2h_arrays_per_query.lat", "h2d_arrays_per_query.lat",
       "spans_per_query.lat", "rest_ms_per_query.lat",
       "prepare_ms_per_query.lat", "launch_ms_per_query.lat",
       "sync_ms_per_query.lat", "process_cpu_ms_per_query.lat"}
PHRASE = {"phrase_bind_ms.lat", "phrase_slots_per_query.lat",
          "phrase_anchor_positions_per_query.lat",
          "phrase_budget_lanes_per_query.lat",
          "phrase_programs_per_query.lat", "phrase_roofline"}
CELL = "pmc_phrase_paced"
CONTROLS = ("bfloat16", "tf_capped", "positions_ignored")


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
    assert cell.cfg["name"] == "pmc-fulltext-phrase" and cell.chips == 1
    assert cell.cfg["kind"] == "text_positions"
    assert cell.mix["loop"] == "paced" and cell.mix["warmup_s"] == 4
    assert cell.mix["senders"] == 8 and cell.mix["rate"] > 0
    assert {m["name"] for m in cell.metrics("end_to_end")} == {
        "latency_p50_ms", "setup_s"}
    assert {m["name"] for m in cell.metrics("per_layer")} >= LAT | PHRASE
    for m in cell.metrics("per_layer"):
        assert m["moves"] == "latency_p50_ms"
        harness.metric_spec(m["name"])          # its file is there
    # membership and lower bounds only: later PRs append cells and metrics
    new = {m["name"]: m for m in cell.bench["per_layer"]
           if m["name"] in PHRASE}
    assert set(new) == PHRASE
    assert all(CELL in m["workloads"] for m in new.values())
    assert new["phrase_roofline"]["unit"] == "%"
    config, = [c for c in cell.bench["configs"]
               if c["name"] == "pmc-fulltext-phrase"]
    assert config["reduced"] == ["n_docs"]
    assert config["source"] == cell.cfg["source"]
    mine, = [w for w in cell.bench["workloads"] if w["name"] == CELL]
    assert mine["chips"] == 1 and mine["config"] == config["name"]
    assert len(mine["why"]) <= 200 and len(cell.bench["workloads"]) >= 9


def test_the_configuration_keeps_the_published_shapes():
    cfg = harness.load_cell(CELL).cfg
    pub = cfg["published"]
    assert pub["n_docs"] == 574_199 and pub["primary_shards"] == 5
    assert (pub["k1"], pub["b"], pub["size"]) == (1.2, 0.75, 10)
    assert cfg["k"] == 10 and cfg["n_docs"] % cfg["segments"] == 0
    # one shard of five, or the one further cut the issue's rule allows
    assert (cfg["n_docs"], cfg["segments"]) in ((114_840, 10), (57_420, 5))
    assert list(cfg["reduced"]) == ["n_docs"]
    assert cfg["vocab"] == 2_000_000
    assert cfg["article_tokens"] == [500, 30000]
    assert cfg["length_mean"] == 5500
    assert (cfg["head_ranks"], cfg["plain_from"]) == (50, 200)
    assert cfg["phrase_words"] == [2, 3]
    assert cfg["filter_words"] == [2, 3] and cfg["must_words"] == [3, 5]
    assert len(cfg["source"]) <= 200
    assert set(cfg["limits"]) == set(compare.NUMBERS)
    assert {"score_err", "rank_gap"} <= set(cfg["limits_why"])
    for key in ("vocabulary", "article length", "repetition", "queries",
                "segments", "n_docs"):
        assert key in cfg["assumed"], key
    q = text_positions.PhraseQuery("phrase_filtered", (7, 12345), (30, 400))
    assert text_positions.body(cfg, q) == {"query": {"bool": {
        "must": [{"match_phrase": {"body": "t7 t12345"}}],
        "filter": [{"match": {"body": {"query": "t30 t400",
                                       "operator": "and"}}}]}},
        "size": 10, "_source": False}
    assert text_positions.body(cfg, dataclasses.replace(
        q, shape="phrase"))["query"] == {
            "match_phrase": {"body": "t7 t12345"}}
    assert text_positions.body(cfg, dataclasses.replace(
        q, shape="keywords_boosted"))["query"] == {"bool": {
            "must": [{"match": {"body": {"query": "t30 t400",
                                         "operator": "and"}}}],
            "should": [{"match_phrase": {"body": "t7 t12345"}}]}}
    assert text_positions.index_body(cfg)["mappings"]["properties"] == {
        "body": {"type": "text"}}


def test_the_reference_imports_nothing_of_the_program_or_the_benchmark():
    path = os.path.join(harness.HERE, "configs",
                        "pmc-fulltext-phrase.reference.py")
    with open(path, encoding="utf-8") as f:
        imports = [line.split()[1].split(".")[0] for line in f
                   if line.startswith(("import ", "from "))]
    assert imports == ["numpy"]


# -- the data ------------------------------------------------------------------

@pytest.fixture(scope="module")
def seeded():
    cfg = tiny_cell().cfg
    data = text_positions.generate(cfg, SEEDS[0])
    return cfg, data, text_positions.queries(cfg, data, SEEDS[0])


def test_articles_read_the_same_both_ways(seeded):
    cfg, data, _q = seeded
    again = text_positions.generate(cfg, SEEDS[0])
    other = text_positions.generate(cfg, SEEDS[1])
    assert not np.array_equal(data.segments[0].tokens[:1000],
                              other.segments[0].tokens[:1000])
    for sd, sd2 in zip(data.segments, again.segments):
        assert np.array_equal(sd.tokens, sd2.tokens)
        assert sd.tokens.dtype == sd.positions.dtype == np.int32
        assert sd.pos_offsets.dtype == sd.doc_ids.dtype == np.int32
        assert sd.tfs.dtype == np.float32
        lens = np.diff(sd.starts)
        assert np.array_equal(lens, sd.lens)
        assert 200 <= lens.min() and lens.max() <= 3000
        # term-major = document-major, turned: every (term, article,
        # position) once, a term's articles and an article's positions
        # ascending
        assert sd.offsets[-1] == len(sd.doc_ids) == len(sd.tfs)
        assert sd.pos_offsets[-1] == len(sd.positions) == len(sd.tokens)
        assert np.array_equal(np.diff(sd.pos_offsets), sd.tfs)
        term_of = np.repeat(np.arange(cfg["vocab"]), sd.df)
        posting_of = np.repeat(np.arange(len(sd.doc_ids)),
                               np.diff(sd.pos_offsets))
        back = np.empty_like(sd.tokens)
        back[sd.starts[sd.doc_ids[posting_of]] + sd.positions] = \
            term_of[posting_of]
        assert np.array_equal(back, sd.tokens)
        for t in (0, 5, 300):
            a, b = sd.offsets[t], sd.offsets[t + 1]
            assert (np.diff(sd.doc_ids[a:b]) > 0).all()
            p = sd.positions[sd.pos_offsets[a]: sd.pos_offsets[a + 1]]
            assert (np.diff(p) > 0).all()
        assert np.array_equal(sd.occurrences([0, 7]), [
            (sd.tokens == 0).sum(), (sd.tokens == 7).sum()])
    assert np.array_equal(data.lens, np.concatenate(
        [s.lens for s in data.segments]))
    # the law the file states: the head word in every article and one
    # token in twenty, a quarter of the tokens among the fifty most
    # frequent terms, an article repeats its own words
    tokens = np.concatenate([s.tokens for s in data.segments])
    assert data.df[0] == cfg["n_docs"]
    assert 0.04 < (tokens == 0).mean() < 0.06
    assert 0.2 < (tokens < 50).mean() < 0.35
    postings = sum(len(s.doc_ids) for s in data.segments)
    assert 0.25 < postings / len(tokens) < 0.6


def test_queries_are_phrases_of_one_article_in_fixed_shares(seeded):
    cfg, data, queries = seeded
    assert len(queries) == cfg["n_queries"] == len(set(queries))
    kinds = text_positions.query_kinds(cfg["n_queries"])
    assert [q.shape for q in queries] == [k[0] for k in kinds] == [
        text_positions.SHAPES[i % 4] for i in range(len(queries))]
    # every eighty requests in a row hold the same multiset
    assert all(collections.Counter(kinds[i:i + 80])
               == collections.Counter(kinds[:80]) for i in (1, 37, 160))

    def kind(q):
        return (q.shape, len(q.phrase),
                any(t < cfg["head_ranks"] for t in q.phrase))
    assert collections.Counter(map(kind, queries)) == \
        collections.Counter(kinds)
    words = collections.Counter(len(q.phrase) for q in queries)
    assert words[2] / len(queries) == pytest.approx(0.6, abs=0.01)
    heads = sum(kind(q)[2] for q in queries) / len(queries)
    assert heads == pytest.approx(0.4, abs=0.01)
    other = text_positions.queries(cfg, data, SEEDS[1])
    assert collections.Counter(map(kind, other)) == \
        collections.Counter(kinds) and other != queries
    articles = [data.article(d).tolist() for d in range(data.n_docs)]
    for q in queries[:80]:
        plain = [t for t in q.phrase if t >= cfg["plain_from"]]
        head = [t for t in q.phrase if t < cfg["head_ranks"]]
        assert len(plain) + len(head) == len(q.phrase) and len(head) <= 1
        m = len(q.phrase)
        home = [a for a in articles if any(
            tuple(a[i:i + m]) == q.phrase for i in range(len(a) - m + 1))]
        assert home
        if q.shape == "phrase":
            assert q.keywords == ()
            continue
        assert list(q.keywords) == sorted(set(q.keywords))
        assert any(set(q.keywords) <= set(a) for a in home)
        lo, hi = cfg["filter_words" if q.shape == "phrase_filtered"
                     else "must_words"]
        assert lo <= len(q.keywords) <= hi
        if q.shape == "keywords_boosted":
            assert set(q.phrase) <= set(q.keywords)
        else:
            assert not set(q.phrase) & set(q.keywords)


def test_work_counts_the_rarest_slot_and_the_probes(seeded):
    cfg, data, queries = seeded
    per_seg = cfg["n_docs"] // cfg["segments"]
    for q in queries[:16]:
        want = 0.0
        for si, sd in enumerate(data.segments):
            if text_positions.signature(cfg, data, q, si) is None:
                continue
            want += per_seg * 8.0
            want += 8.0 * sum(int(sd.df[t]) for t in q.keywords)
            held = [int((sd.tokens == t).sum()) for t in q.phrase]
            if all(held):
                j = held.index(min(held))
                want += (4.0 * held[j] * len(q.phrase)
                         + 8.0 * int(sd.df[q.phrase[j]]))
        assert text_positions.work_bytes(cfg, data, q) == want > 0
        assert text_positions.work_flops(cfg, data, q) >= 0


# -- the reference, an oracle and the controls --------------------------------

K1, B = 1.2, 0.75


def _oracle(cfg, data, q):
    """Brute force over python lists: {article: float64 score}."""
    articles = [data.article(d).tolist() for d in range(data.n_docs)]
    n = len(articles)
    avgdl = sum(map(len, articles)) / n
    df = collections.Counter(t for a in articles for t in set(a))

    def idf(t):
        return math.log(1.0 + (n - df[t] + 0.5) / (df[t] + 0.5))

    def sat(tf, dl):
        return tf / (tf + K1 * (1.0 - B + B * dl / avgdl))

    m = len(q.phrase)
    scores = {}
    for d, a in enumerate(articles):
        ptf = sum(tuple(a[i:i + m]) == q.phrase
                  for i in range(len(a) - m + 1))
        phrase = sum(idf(t) for t in q.phrase) * sat(ptf, len(a))
        tfs = [a.count(t) for t in q.keywords]
        if q.shape == "phrase":
            s = phrase
        elif q.shape == "phrase_filtered":
            s = phrase if all(tfs) else 0.0
        else:
            s = (sum(idf(t) * sat(tf, len(a))
                     for t, tf in zip(q.keywords, tfs)) + phrase
                 if all(tfs) else 0.0)
        if s > 0:
            scores[d] = s
    return scores


def test_the_reference_agrees_with_a_brute_force_oracle(seeded):
    cfg, data, queries = seeded
    sub = queries[:12]
    ref = harness.load_cell(CELL).reference.Reference(cfg, data)
    tops = list(ref.topk_many(sub))
    judged = list(ref.judge_many(sub, [[i for i, _s in t] for t in tops]))
    for q, top, (scores, runner_up, n_match) in zip(sub, tops, judged):
        all_scores = _oracle(cfg, data, q)
        order = sorted(all_scores, key=lambda d: (-all_scores[d], d))
        want = [(d, all_scores[d]) for d in order[:cfg["k"]]]
        assert [i for i, _s in top] == [i for i, _s in want]
        assert [s for _i, s in top] == pytest.approx(
            [s for _i, s in want], rel=1e-12)
        assert n_match == len(all_scores) >= 1
        assert scores.tolist() == pytest.approx([s for _i, s in want],
                                                rel=1e-12)
        left = [all_scores[d] for d in order[len(want):]]
        assert runner_up == pytest.approx(left[0] if left else 0.0,
                                          rel=1e-12)
    # an article without the phrase, or without a required keyword, and
    # ids the shard does not have, score 0
    q = next(q for q in sub if q.shape == "phrase_filtered")
    phrase_only = _oracle(cfg, data, dataclasses.replace(
        q, shape="phrase", keywords=()))
    both = _oracle(cfg, data, q)
    outside = next(d for d in range(data.n_docs) if d not in phrase_only)
    lacking = [d for d in phrase_only if d not in both][:1]
    (scores, _r, _n), = ref.judge_many(
        [q], [[outside, data.n_docs + 5, -1] + lacking])
    assert not scores.any()


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
@pytest.mark.parametrize("precision", CONTROLS)
def test_the_control_is_rejected(precision, seed):
    """The scoring in bfloat16, the phrase counted once an article, and
    the phrase read as an ``and`` of its words, each put in the program's
    place: a limit has to reject each."""
    numbers, limits = _numbers(tiny_cell(), seed, precision)
    correct, lines = compare.verdict(numbers, limits)
    assert not correct, lines
    if precision == "positions_ignored":
        assert numbers["malformed"] > 0      # articles without the phrase
    else:
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


def test_traced_run_reports_the_phrase_layer(cpu_kernels, breaker_limits):
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
    # every program of every request holds a phrase; one read of all
    # their results, one packed result back a program
    assert 0 < got["dispatches_per_query.lat"] <= segments
    assert got["phrase_programs_per_query.lat"] == \
        got["dispatches_per_query.lat"] == got["d2h_arrays_per_query.lat"]
    assert 0 < got["d2h_reads_per_query.lat"] <= 1 * LATE
    # one packed input a root phrase; under a bool the bag's, the
    # phrase's and the bool's two scalars
    assert (got["dispatches_per_query.lat"]
            <= got["h2d_arrays_per_query.lat"]
            <= 4 * got["dispatches_per_query.lat"])
    assert got["compiles_in_window.lat"] == 0
    assert 2 <= got["phrase_slots_per_query.lat"] <= 3
    assert (0 < got["phrase_anchor_positions_per_query.lat"]
            < got["phrase_budget_lanes_per_query.lat"])
    assert got["phrase_budget_lanes_per_query.lat"] == pytest.approx(
        1024 * got["phrase_programs_per_query.lat"])
    assert 0 < got["phrase_bind_ms.lat"] < got["query_phase_ms.lat"]


def _swap_in_an_article_without_the_phrase(data):
    def tamper(qi, resp):
        hits = resp["hits"]["hits"]
        if qi % 5 == 0 and hits:
            words = set(tamper.queries[qi].phrase)
            hits[-1]["_id"] = next(
                str(d) for d in range(data.n_docs)
                if not words <= set(data.article(d).tolist()))
        return resp
    return tamper


def _alter_score(qi, resp):
    if qi % 5 == 0 and resp["hits"]["hits"]:
        resp["hits"]["hits"][0]["_score"] *= 1.001
    return resp


def test_an_article_without_the_phrase_is_malformed(cpu_kernels,
                                                    breaker_limits):
    cell = tiny_cell()
    data = text_positions.generate(cell.cfg, SEEDS[0])
    tamper = _swap_in_an_article_without_the_phrase(data)
    tamper.queries = text_positions.queries(cell.cfg, data, SEEDS[0])
    result = run_tiny(cell, seconds=2.0, tamper=tamper)
    assert result["correct"] is False
    c = result["compared"]["malformed"]
    assert c["value"] > c["limit"]


def test_an_altered_score_flips_correct(cpu_kernels, breaker_limits):
    result = run_tiny(tiny_cell(), seconds=2.0, tamper=_alter_score)
    assert result["correct"] is False
    c = result["compared"]["score_err"]
    assert c["value"] > c["limit"]


def test_a_dropped_article_shows_as_rank_gap(seeded):
    """By the comparison itself, at ``k`` 1 (a phrase of rare words lies
    in few articles): the reference's best article taken out and its
    second let in."""
    cfg, data, queries = seeded
    ref = harness.load_cell(CELL).reference.Reference({**cfg, "k": 2}, data)
    tops = list(ref.topk_many(queries))
    pairs = [(q, [(str(i), s) for i, s in top[1:]])
             for q, top in zip(queries, tops) if len(top) == 2
             and top[0][1] > top[1][1] * 1.001]
    assert len(pairs) >= 4
    numbers = compare.compare(ref, [q for q, _r in pairs],
                              [r for _q, r in pairs], 1)
    assert numbers["malformed"] == 0 and numbers["score_err"] == 0.0
    assert numbers["rank_gap"] > 1000 * cfg["limits"]["rank_gap"]


# -- the warm-up enumeration ---------------------------------------------------

def test_program_space_of_the_committed_configuration():
    cfg = harness.load_cell(CELL).cfg
    space = text_positions.program_space(cfg)
    assert len(space) == len(set(space)) < 60
    phrases = sorted({p for _s, p, _b in space})
    # one padded slot count for two and three words, one bucket a key
    assert {s for s, _b in phrases} == {4}
    assert [b for _s, b in phrases][0] == \
        text_positions.ANCHOR_BUCKET_MIN == 1024
    assert text_positions.BUCKET_MIN == 4096
    if (cfg["n_docs"], cfg["segments"]) == (114_840, 10):
        assert [b for _s, b in phrases] == [1024, 4096, 16384, 65536]
        assert len(space) == 4 + 2 * 2 * 3 * 4
    assert {s for s, _p, _b in space} == set(text_positions.SHAPES)
    assert {b[0] for s, _p, b in space if s == "phrase_filtered"} == {2, 4}
    assert {b[0] for s, _p, b in space if s == "keywords_boosted"} == {4, 8}
    assert all(b is None for s, _p, b in space if s == "phrase")


@pytest.mark.parametrize("seed", SEEDS)
def test_warmup_covers_every_signature_the_query_maker_produces(seed):
    cfg = tiny_cell().cfg
    data = text_positions.generate(cfg, seed)
    crafted = text_positions.warmup_queries(cfg, data)
    warmed = set()
    for sig, q in crafted:
        assert {text_positions.signature(cfg, data, q, si)
                for si in range(cfg["segments"])} == {sig}
        assert sig[0] == q.shape
        warmed.add(sig)
    assert len(warmed) == len(crafted)
    produced = {text_positions.signature(cfg, data, q, si)
                for q in text_positions.queries(cfg, data, seed)
                for si in range(cfg["segments"])} - {None}
    assert produced <= warmed <= set(text_positions.program_space(cfg))
    assert {s for s, _p, _b in produced} == set(text_positions.SHAPES)


def test_the_signature_mirrors_the_plan_the_program_compiles(cpu_kernels,
                                                             breaker_limits):
    """Against the program: the phrase's and the bag's dims in every
    segment are what ``signature`` says, and a segment it prunes is one
    ``signature`` calls None."""
    from opensearch_tpu.search import compiler, query_dsl
    from opensearch_tpu.search import plan as P

    session = harness.Session(tiny_cell(), SEEDS[2], harness.device_info())
    try:
        cfg, data = session.cell.cfg, session.data
        searcher = session.served.node.indices.get(
            cfg["index"]).engine_for(0).acquire_searcher()
        seen = collections.Counter()
        for q in session.queries[:60]:
            body = text_positions.body(cfg, q)
            plan, bind = compiler.compile_query(query_dsl.parse_query(
                body["query"]), searcher.ctx, scored=True)
            for si, seg in enumerate(searcher.segments):
                sig = text_positions.signature(cfg, data, q, si)
                assert plan.can_match(bind, seg) == (sig is not None)
                if sig is None:
                    continue
                dims, _ins = plan.prepare(bind, seg, seg.device(),
                                          searcher.ctx)
                phrase, = P.phrase_dims(dims)
                assert tuple(phrase) == sig[1]
                assert phrase.slots == len(q.phrase)
                assert phrase.anchor_positions == min(
                    data.segments[si].occurrences(q.phrase))
                if q.shape == "phrase":
                    assert isinstance(plan, P.PhrasePlan) and dims is phrase
                    continue
                assert isinstance(plan, P.BoolPlan)
                bag, = [d for d in dims if isinstance(d, P.BagDims)]
                assert tuple(bag[:2]) == sig[2] and bag[2] is False
                scored = q.shape == "keywords_boosted"
                assert (plan.must if scored else plan.filter)[0].scored \
                    == scored
                seen[q.shape] += 1
        assert set(seen) == {"phrase_filtered", "keywords_boosted"}
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


# -- the installer ---------------------------------------------------------------

def test_the_installed_columns_are_segment_writers(tmp_path):
    """The columns the kind hands ``install_remote_checkpoint`` are the
    ones ``SegmentWriter`` builds from the same articles sent as text
    through ``_bulk``, term by term (the writer numbers terms in sorted
    order, the kind by frequency)."""
    from opensearch_tpu.client import OpenSearch
    from opensearch_tpu.node import Node

    cfg = {**tiny_cell().cfg, "n_docs": 60, "segments": 1,
           "length_mean": 150, "article_tokens": [50, 400], "vocab": 5000}
    data = text_positions.generate(cfg, SEEDS[0])
    sd = data.segments[0]
    node = Node(str(tmp_path), host="127.0.0.1", port=0).start()
    try:
        client = OpenSearch([f"http://127.0.0.1:{node.port}"])
        client.indices.create("written", text_positions.index_body(cfg))
        lines = []
        for d in range(sd.n_docs):
            lines += [{"index": {"_index": "written", "_id": str(d)}},
                      {"body": " ".join(map(text_positions.term_name,
                                            data.article(d)))}]
        resp = client.bulk(lines, params={"refresh": "true"})
        assert not resp["errors"]
        written, = node.indices.get("written").engine_for(
            0).acquire_searcher().segments
        client.indices.create("installed", text_positions.index_body(cfg))
        from unittest import mock
        with mock.patch.object(text_positions, "compile_side_by_side",
                               lambda *a: None):
            text_positions.install(node, "installed", cfg, data)
        installed, = node.indices.get("installed").engine_for(
            0).acquire_searcher().segments
    finally:
        node.stop()
    w, k = written.postings["body"], installed.postings["body"]
    assert written.doc_ids == installed.doc_ids
    assert set(w.terms) == set(k.terms) and len(w.terms) > 500
    assert np.array_equal(w.doc_lens, k.doc_lens)
    assert (w.total_len, w.docs_with_field) == (k.total_len,
                                                k.docs_with_field)
    assert (len(w.doc_ids), len(w.positions)) == (len(k.doc_ids),
                                                  len(k.positions))
    for name, wt in w.terms.items():
        kt = k.terms[name]
        assert w.df[wt] == k.df[kt]
        (wa, wb), (ka, kb) = w.offsets[wt:wt + 2], k.offsets[kt:kt + 2]
        assert np.array_equal(w.doc_ids[wa:wb], k.doc_ids[ka:kb])
        assert np.array_equal(w.tfs[wa:wb], k.tfs[ka:kb])
        assert np.array_equal(
            w.positions[w.pos_offsets[wa]: w.pos_offsets[wb]],
            k.positions[k.pos_offsets[ka]: k.pos_offsets[kb]])
        assert np.array_equal(np.diff(w.pos_offsets[wa:wb + 1]),
                              np.diff(k.pos_offsets[ka:kb + 1]))
