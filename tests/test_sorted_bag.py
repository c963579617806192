"""A scored term bag's top-k from its sorted postings (``ops/bm25.py::
impact_topk_sorted``, ``plan.run_topk(sorted_bag=True)``).

Kernel level: the sorted function against the dense accumulator
(``impact_scores`` / ``impact_score_count``, the match rule, the
``min_score`` cut and ``_key_topk`` over ``[n_pad]``) on the same CSR
columns, bit for bit on values, ids, total and maximum.

Through ``ShardSearcher.search``: ``match``, ``neural_sparse`` and the
``match`` half of a ``hybrid`` against ``TermBagPlan.host_topk``; a
segment with a deleted doc keeps the dense path; ``device.
sorted_bag_programs`` rises by the programs the rule takes and by none
for a bag under a ``bool`` or a ``size`` past the bag's lanes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from opensearch_tpu.common.device_health import device_health
from opensearch_tpu.common.device_ledger import device_ledger
from opensearch_tpu.index.segment import SegmentWriter
from opensearch_tpu.mapping.mapper import DocumentMapper
from opensearch_tpu.ops import bm25 as bm25_ops
from opensearch_tpu.ops import topk as topk_ops
from opensearch_tpu.search import engine
from opensearch_tpu.search import plan as P
from opensearch_tpu.search.executor import ShardSearcher, build_arrays

# --------------------------------------------------------------------------
# kernel level
# --------------------------------------------------------------------------


def _csr(rng, n_docs: int, dfs, grid: int = 0):
    """CSR columns of ``len(dfs)`` terms: term ``t`` on ``dfs[t]`` docs
    drawn without repeats (ascending, as a postings list is).  Impacts
    are random float32, or multiples of ``1 / grid`` where ties are
    wanted."""
    runs = [np.sort(rng.choice(n_docs, df, replace=False)) for df in dfs]
    offsets = np.concatenate([[0], np.cumsum(dfs)]).astype(np.int32)
    doc_ids = np.concatenate(runs + [np.zeros(0, np.int64)]).astype(np.int32)
    n = len(doc_ids)
    imp = (rng.integers(1, grid + 1, n) / grid if grid
           else rng.random(n) + 0.01).astype(np.float32)
    pad = max(8, 1 << max(n - 1, 1).bit_length())
    return (np.pad(offsets, (0, 1), mode="edge"),
            np.pad(doc_ids, (0, pad - n)), np.pad(imp, (0, pad - n)))


def _dense_topk(offsets, doc_ids, imp, tids, active, idfs, weights,
                required, min_score, *, n_pad, budget, k, fast):
    """The dense path as ``TermBagPlan.eval`` and ``_run_topk`` put it
    together, every doc live."""
    if fast:
        scores = bm25_ops.impact_scores(
            offsets, doc_ids, imp, tids, active, idfs, weights,
            n_pad=n_pad, budget=budget)
        matched = scores > 0.0
    else:
        scores, count = bm25_ops.impact_score_count(
            offsets, doc_ids, imp, tids, active, idfs, weights,
            n_pad=n_pad, budget=budget, scored=True)
        matched = count >= required
    scores = jnp.where(matched, scores, 0.0)
    matched = matched & (scores >= min_score)
    return P._key_topk(jnp.where(matched, scores, -jnp.inf), k, matched)


def _dense(cols, tids, active, idfs, weights, required, min_score, **kw):
    run = jax.jit(lambda *c: _dense_topk(*c, tids, active, idfs, weights,
                                         required, min_score, **kw))
    return [np.asarray(x) for x in run(*cols)]


def _sorted(cols, tids, active, idfs, weights, required, min_score, **kw):
    @jax.jit
    def run(offsets, doc_ids, imp):
        return bm25_ops.impact_topk_sorted(
            offsets, doc_ids, imp, tids, active, idfs, weights,
            jnp.int32(required), jnp.float32(min_score), **kw)
    return [np.asarray(x) for x in run(*cols)]


def _same(cols, n_terms, *, n_pad, budget, k, t_pad=None, required=1,
          min_score=-np.inf, weights=None, fast=None, rng=None):
    """Both paths over the first ``n_terms`` terms of ``cols``; returns
    the sorted path's four results after asserting they are the dense
    path's, bit for bit where a value is above ``-inf``."""
    t_pad = t_pad or max(1, 1 << max(n_terms - 1, 0).bit_length())
    tids = np.zeros(t_pad, np.int32)
    tids[:n_terms] = np.arange(n_terms)
    active = np.arange(t_pad) < n_terms
    rng = rng or np.random.default_rng(3)
    idfs = (rng.random(t_pad) + 0.5).astype(np.float32)
    if weights is None:
        weights = (rng.random(t_pad) + 0.5).astype(np.float32)
    fast = required == 1 if fast is None else fast
    assert bm25_ops.sorted_bag(t_pad, budget, n_pad, k)
    args = (cols, jnp.asarray(tids), jnp.asarray(active), jnp.asarray(idfs),
            jnp.asarray(weights), required, min_score)
    kw = dict(n_pad=n_pad, budget=budget, k=k, fast=fast)
    want = _dense(*args, **kw)
    got = _sorted(*args, **kw)
    for w, g in zip(want, got):
        assert w.dtype == g.dtype and w.shape == g.shape
    keep = want[0] > -np.inf
    assert np.array_equal(want[0].view(np.int32), got[0].view(np.int32))
    assert np.array_equal(want[1][keep], got[1][keep])
    assert ((got[1] >= 0) & (got[1] < n_pad)).all()
    assert int(want[2]) == int(got[2])
    assert want[3].view(np.int32) == got[3].view(np.int32)
    return got


@pytest.mark.parametrize("required", [1, 2, 5], ids=["fast", "msm2", "all"])
def test_fast_and_counted_bags(required):
    """Random float32 impacts, whose sums depend on the order of the
    additions: slot order on both sides."""
    rng = np.random.default_rng(36)
    cols = _csr(rng, 3000, [1900, 2500, 1400, 1700, 2200])
    vals, _ids, total, _mx = _same(cols, 5, n_pad=4096, budget=16384, k=10,
                                   required=required, rng=rng)
    assert (vals > -np.inf).all()
    assert 10 < total < 1000 if required == 5 else total > 2500


def test_counted_bag_with_a_negative_weight():
    """``required`` 1 is counted too where a weight is not positive: a
    doc that matches only the negative term has a score below zero and
    still counts."""
    rng = np.random.default_rng(37)
    cols = _csr(rng, 3000, [900, 1500, 40])
    weights = np.asarray([1.0, -0.25, 2.0, 0.0], np.float32)
    _same(cols, 3, n_pad=4096, budget=4096, k=3000, weights=weights,
          fast=False)


def test_ties_by_the_hundred_go_to_the_lower_doc():
    """Impacts on a grid of two values, weights and idfs of 1: a few
    score classes over thousands of docs."""
    rng = np.random.default_rng(38)
    cols = _csr(rng, 60000, [30000, 20000, 25000], grid=2)
    ones = np.ones(4, np.float32)
    t_pad, n_pad, budget, k = 4, 65536, 262144, 200
    args = (cols, jnp.arange(4, dtype=jnp.int32),
            jnp.asarray([True, True, True, False]), jnp.asarray(ones),
            jnp.asarray(ones), 1, -np.inf)
    kw = dict(n_pad=n_pad, budget=budget, k=k, fast=True)
    want, got = _dense(*args, **kw), _sorted(*args, **kw)
    assert topk_ops.block_size(n_pad, k) and topk_ops.block_size(budget, k)
    assert len(set(want[0].tolist())) <= 4
    for w, g in zip(want, got):
        assert np.array_equal(w, g)
    # inside a score class the ids ascend
    for v in set(got[0].tolist()):
        ids = got[1][got[0] == v]
        assert (np.diff(ids) > 0).all()


def test_the_last_row_of_a_full_segment_is_a_doc():
    """``n_docs == n_pad``: doc ``n_pad - 1`` is real, and the dense
    path's dead lanes add their zeros to it; here they sort apart."""
    rng = np.random.default_rng(39)
    n = 4096
    cols = list(_csr(rng, n, [2000, 1000]))
    # the last posting of each term on the last doc, the best of all
    for t in (0, 1):
        end = cols[0][t + 1] - 1
        cols[1][end] = n - 1
        cols[2][end] = 50.0
    vals, ids, _tot, mx = _same(tuple(cols), 2, n_pad=n, budget=4096, k=5)
    assert ids[0] == n - 1 and vals[0] == mx


@pytest.mark.parametrize("cut", ["some", "all"])
def test_min_score_leaves_docs_out_of_hits_and_total(cut):
    rng = np.random.default_rng(40)
    cols = _csr(rng, 3000, [900, 1500, 700])
    vals, _ids, total, _mx = _same(cols, 3, n_pad=4096, budget=4096, k=100)
    ms = float(vals[50]) if cut == "some" else float(vals[0]) * 2
    vals2, _ids, total2, mx2 = _same(cols, 3, n_pad=4096, budget=4096,
                                     k=100, min_score=ms)
    if cut == "some":
        assert 51 <= total2 < total and (vals2[:51] >= ms).all()
        assert (vals2[total2:] == -np.inf).all()
    else:
        assert total2 == 0 and mx2 == -np.inf


def test_fewer_matches_than_k_and_an_empty_bag():
    rng = np.random.default_rng(41)
    cols = _csr(rng, 3000, [4, 3])
    vals, _ids, total, _mx = _same(cols, 2, n_pad=4096, budget=4096, k=64)
    assert 4 <= total <= 7 and (vals[:total] > 0).all()
    assert (vals[total:] == -np.inf).all()
    # no active term: nothing matches
    vals, _ids, total, mx = _same(cols, 0, t_pad=2, n_pad=4096,
                                  budget=4096, k=64)
    assert total == 0 and mx == -np.inf and (vals == -np.inf).all()


@pytest.mark.parametrize("n_terms,budget,n_pad", [
    (1, 4096, 16384), (64, 65536, 4096), (64, 65536, 262144)],
    ids=["t1", "t64_budget_above", "t64_budget_below"])
def test_one_slot_and_sixty_four(n_terms, budget, n_pad):
    """``t_pad`` 1 (no run longer than one lane, no pass of the fold) and
    64; ``budget`` above and below ``n_pad``."""
    rng = np.random.default_rng(42)
    dfs = [3000] if n_terms == 1 else list(rng.integers(10, 900, n_terms))
    cols = _csr(rng, 4000, dfs)
    _same(cols, n_terms, n_pad=n_pad, budget=budget, k=20, rng=rng)


@pytest.mark.parametrize("required", [1, 16])
def test_a_run_of_full_length(required):
    """Doc 7 holds every one of sixteen terms: its run is ``t_pad`` lanes
    and the fold makes all ``t_pad - 1`` passes; under ``required`` 16 it
    is among the few matches."""
    rng = np.random.default_rng(43)
    cols = list(_csr(rng, 2000, [300] * 16))
    for t in range(16):
        run = cols[1][cols[0][t]:cols[0][t + 1]]
        if 7 not in run:
            run[0] = 7
            run.sort()
    _vals, ids, total, _mx = _same(tuple(cols), 16, n_pad=2048,
                                   budget=16384, k=2000, required=required,
                                   rng=rng)
    assert 7 in ids[:total]
    if required == 16:
        assert total < 10


@pytest.mark.parametrize("t_pad,budget,n_pad,k,takes", [
    (8, 4096, 131072, 10, True),
    (64, 4194304, 262144, 1000, True),
    (8, 4096, 131072, 4097, False),              # k > budget
    (64, 4096, 2 ** 25, 10, False),              # n_pad * t_pad == 2^31
    (32, 4096, 2 ** 25, 10, True),
    (1, 4096, 2 ** 30, 10, True),
], ids=["small", "large", "k_over_budget", "key_overflows", "key_fits",
        "one_slot_wide_segment"])
def test_the_rule(t_pad, budget, n_pad, k, takes):
    assert bm25_ops.sorted_bag(t_pad, budget, n_pad, k) is takes


@pytest.mark.parametrize("dims,scored,takes", [
    ((8, 4096, True), True, True),
    ((8, 4096, False), True, True),
    ((8, 4096, True, 9), True, False),           # the quantized lowering
    ((8, 4096, False), False, False),            # a filtering bag
], ids=["fast", "counted", "quantized_dims", "unscored"])
def test_the_plans_side_of_the_rule(dims, scored, takes):
    bag = P.TermBagPlan(field="body", scored=scored)
    assert bag.sorted_topk(dims, 65536, 10) is takes
    assert bag.sorted_topk(dims, 65536, dims[1] + 1) is False
    # nothing but a bag at the root: composites need the dense vector
    for plan in (P.BoolPlan(should=(bag,)), P.DisMaxPlan(children=(bag,)),
                 P.MatchAllPlan()):
        assert plan.sorted_topk((dims,), 65536, 10) is False


# --------------------------------------------------------------------------
# the narrow width (``sorted_width``)
# --------------------------------------------------------------------------


def _bag(rng, t_pad: int, total: int, n_pad: int):
    """CSR columns of ``t_pad`` terms whose runs hold ``total`` postings
    between them, every term active; random idfs and weights."""
    dfs = np.full(t_pad, total // t_pad)
    dfs[:total % t_pad] += 1
    cols = _csr(rng, n_pad, dfs)
    return cols, (jnp.arange(t_pad, dtype=jnp.int32),
                  jnp.ones(t_pad, bool),
                  jnp.asarray((rng.random(t_pad) + 0.5).astype(np.float32)),
                  jnp.asarray((rng.random(t_pad) + 0.5).astype(np.float32)))


def _programs(terms, *, n_pad, budget, k, fast):
    """The dense path and the sorted one traced with the narrow width
    (``_NARROW_FLOOR`` at ``budget``) and without it, each one program
    over (columns, required, min_score)."""
    tids, active, idfs, weights = terms

    def dense(offsets, doc_ids, imp, required, min_score):
        return _dense_topk(offsets, doc_ids, imp, tids, active, idfs,
                           weights, required, min_score, n_pad=n_pad,
                           budget=budget, k=k, fast=fast)

    def sorted_at(floor):
        def run(offsets, doc_ids, imp, required, min_score):
            saved, bm25_ops._NARROW_FLOOR = bm25_ops._NARROW_FLOOR, floor
            try:
                return bm25_ops.impact_topk_sorted(
                    offsets, doc_ids, imp, tids, active, idfs, weights,
                    required, min_score, n_pad=n_pad, budget=budget, k=k,
                    fast=fast)
            finally:
                bm25_ops._NARROW_FLOOR = saved
        return run

    return [lambda *a, fn=jax.jit(fn): [np.asarray(x) for x in fn(*a)]
            for fn in (dense, sorted_at(budget), sorted_at(2 * budget))]


def _pair_sorts(jaxpr) -> list:
    """The lanes of each pair sort (by key, with the contributions) of a
    jaxpr, those of its branches and loops included."""
    lanes = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "sort" and len(eqn.invars) == 2:
            lanes.append(eqn.invars[0].aval.shape[0])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            lanes += _pair_sorts(sub)
    return sorted(lanes)


@pytest.mark.parametrize("fast", [True, False], ids=["fast", "counted"])
@pytest.mark.parametrize("offset", [-1, 0, 1], ids=["under", "at", "over"])
@pytest.mark.parametrize("t_pad", [1, 8, 64])
def test_the_narrow_width_is_the_full_widths_result(t_pad, offset, fast,
                                                    monkeypatch):
    """A bag of ``budget // 2 + offset`` postings at the floor: the
    program with both widths (the narrow one taken up to ``budget // 2``)
    gives what one sort over the whole budget gives, bit for bit on all
    four results, the ids of ``-inf`` entries too, and the dense path's.
    The counted program also under ``required`` > 1, a ``min_score`` cut
    and a bag with fewer than ``k`` matches."""
    budget = n_pad = 262144
    k = 10
    rng = np.random.default_rng(43 + t_pad)
    total = budget // 2 + offset
    cols, terms = _bag(rng, t_pad, total, n_pad)
    monkeypatch.setattr(bm25_ops, "_NARROW_FLOOR", budget)
    assert bm25_ops.sorted_width(budget, total, k) == (
        budget // 2 if offset <= 0 else budget)
    dense, both, one = _programs(terms, n_pad=n_pad, budget=budget, k=k,
                                 fast=fast)
    cases = [(1, -np.inf)]
    if not fast:
        # two slots of a doc; a cut inside the top k; nothing past the cut
        cut = float(dense(*cols, 1, -np.inf)[0][k // 2])
        cases += [(min(2, t_pad), -np.inf), (1, cut), (1, 1e9)]
    for required, min_score in cases:
        args = (*cols, np.int32(required), np.float32(min_score))
        want, got = dense(*args), both(*args)
        for b, o in zip(got, one(*args)):
            assert b.dtype == o.dtype and b.tobytes() == o.tobytes()
        keep = want[0] > -np.inf
        assert np.array_equal(want[0].view(np.int32), got[0].view(np.int32))
        assert np.array_equal(want[1][keep], got[1][keep])
        assert int(want[2]) == int(got[2])
        assert want[3].view(np.int32) == got[3].view(np.int32)
        if min_score == 1e9:
            assert int(got[2]) == 0 and not keep.any()
        elif min_score > -np.inf:
            assert int(got[2]) > k // 2 and keep[:k // 2 + 1].all()
        elif required == 1:
            assert int(got[2]) > k and keep.all()


@pytest.mark.parametrize("budget,postings,k,width", [
    ("floor", "half", 10, "half"),
    ("floor", "half_and_one", 10, "floor"),
    ("floor", 0, 10, "half"),
    ("floor", "half", "half_and_one", "floor"),      # k past the narrow lanes
    ("floor", "half", "block_differs", "floor"),
    ("four_floors", "half", 10, "half"),
    ("four_floors", "half_and_one", 10, "four_floors"),
], ids=["fits", "one_over", "empty", "k_over_half", "block_size_differs",
        "above_the_floor", "above_the_floor_over"])
def test_the_width_rule(budget, postings, k, width):
    floor = bm25_ops._NARROW_FLOOR
    b = {"floor": floor, "four_floors": 4 * floor}[budget]
    if k == "block_differs":
        k = next(k for k in range(1, b // 2)
                 if topk_ops.block_size(b // 2, k)
                 != topk_ops.block_size(b, k))
    named = {"half": b // 2, "half_and_one": b // 2 + 1, "floor": b,
             "four_floors": b}
    got = bm25_ops.sorted_width(b, named.get(postings, postings),
                                named.get(k, k))
    assert got == named[width] and type(got) is int


@pytest.mark.parametrize("budget", ["floor", "below"])
def test_below_the_floor_nothing_splits(budget):
    """Under ``_NARROW_FLOOR`` a program holds one sort of its whole
    budget whatever its postings; from the floor up, two."""
    floor = bm25_ops._NARROW_FLOOR
    b = floor if budget == "floor" else floor // 4
    for postings in (0, 1, b // 4, b // 2):
        assert bm25_ops.sorted_width(b, postings, 10) == (
            b // 2 if b == floor else b)
    assert bm25_ops.sorted_width(floor // 2, 0, 10) == floor // 2
    n_pad, t_pad = 4 * b, 8
    cols = [jnp.zeros(t_pad + 2, jnp.int32), jnp.zeros(b, jnp.int32),
            jnp.zeros(b, jnp.float32)]
    jaxpr = jax.make_jaxpr(lambda o, d, imp: bm25_ops.impact_topk_sorted(
        o, d, imp, jnp.zeros(t_pad, jnp.int32), jnp.ones(t_pad, bool),
        jnp.ones(t_pad, jnp.float32), jnp.ones(t_pad, jnp.float32),
        jnp.int32(1), jnp.float32(-jnp.inf), n_pad=n_pad, budget=b, k=10,
        fast=True))(*cols)
    assert _pair_sorts(jaxpr.jaxpr) == ([b // 2, b] if b == floor else [b])


# --------------------------------------------------------------------------
# through ShardSearcher.search
# --------------------------------------------------------------------------

VOCAB = [f"w{i}" for i in range(12)]
TOKENS = [f"t{i}" for i in range(40)]
SIZES = (9000, 9000, 500)
MATCH = {"match": {"body": "w1 w2 w3"}}
SPARSE = {"neural_sparse": {"expansion": {"query_tokens": {
    "t1": 1.5, "t2": 0.7, "t3": 2.25, "t5": 0.3, "t8": 1.1}}}}
KNN = {"knn": {"v": {"vector": [3.0, 1.0, 1.0, 0.5], "k": 10}}}


@pytest.fixture(autouse=True)
def device_path(monkeypatch):
    monkeypatch.setattr(engine, "BATCHER_ENABLED", False)


def _segments():
    mapper = DocumentMapper({"properties": {
        "body": {"type": "text"}, "expansion": {"type": "rank_features"},
        "v": {"type": "knn_vector", "dimension": 4,
              "method": {"name": "exact", "space_type": "l2"}}}})
    rng = np.random.default_rng(36)
    writer, segs, g = SegmentWriter(), [], 0
    for s, n in enumerate(SIZES):
        words = rng.choice(VOCAB, (n, 4))
        docs = []
        for i in range(n):
            toks = rng.choice(TOKENS, 6, replace=False)
            docs.append(mapper.parse(str(g), {
                "body": " ".join(words[i]),
                "expansion": {t: float(w) for t, w in zip(
                    toks, rng.integers(1, 9, 6) / 4.0)},
                "v": [float(g % 7), (g // 7) % 5 * 0.5, 1.0,
                      (g % 3) * 0.5]}))
            g += 1
        segs.append(writer.build(docs, f"sb{s}"))
    return segs, mapper


@pytest.fixture(scope="module")
def searcher():
    segs, mapper = _segments()
    yield ShardSearcher(segs, mapper)
    device_ledger().reset()
    device_health().reset()


def _hits(resp) -> tuple:
    assert resp["_shards"]["failed"] == 0
    return ([(h["_id"], h["_score"]) for h in resp["hits"]["hits"]],
            resp["hits"]["total"])


def _sorted_programs() -> int:
    return device_ledger().stats()["sorted_bag_programs"]


@pytest.mark.parametrize("query,size", [
    (MATCH, 10), (MATCH, 300), (SPARSE, 10), (SPARSE, 700),
    ({"match": {"body": {"query": "w1 w2 w3", "operator": "and"}}}, 50),
    ({"match": {"body": {"query": "w1 w2 w3 w4",
                         "minimum_should_match": 2}}}, 50)],
    ids=["match", "match_300", "neural_sparse", "neural_sparse_700",
         "match_and", "match_msm2"])
def test_search_equals_the_host_scorer(searcher, host_recovery, query, size):
    """Every segment is live and every program's shape is inside the rule:
    ids, float32 scores and totals of the device are the host scorer's,
    and each of the three segment programs counts as sorted."""
    body = {"query": query, "size": size}
    host = _hits(searcher.search(dict(body)))
    host_recovery.reset()
    before = _sorted_programs()
    blocks = device_ledger().stats()["block_topk_programs"]
    device = _hits(searcher.search(dict(body)))
    assert device == host and len(device[0]) == size
    assert _sorted_programs() - before == len(SIZES)
    # the top-k's key is the bag's budget, not the segment: what
    # ``block_topk_programs`` counts follows it
    plan, bind = searcher.compiled(query)
    want = 0
    for seg in searcher.segments:
        dims, _ins = plan.prepare(bind, seg, seg.device(), searcher.ctx)
        want += bool(topk_ops.block_size(dims[1],
                                         min(size, seg.device().n_pad)))
    assert (device_ledger().stats()["block_topk_programs"] - blocks
            == want)


def test_the_sorted_program_is_the_dense_programs_result(searcher):
    """``run_topk`` with and without ``sorted_bag`` over the same inputs:
    one packed array, bit for bit (the ids of ``-inf`` entries apart),
    and the mesh's program (``run_topk_mesh``) over a mesh of one chip."""
    from opensearch_tpu.parallel import dist_search

    mesh = dist_search.make_mesh(1)
    for query in (MATCH, SPARSE):
        plan, bind = searcher.compiled(query)
        for seg in searcher.segments:
            dseg = seg.device()
            dims, ins = plan.prepare(bind, seg, dseg, searcher.ctx)
            A = build_arrays(dseg, plan.arrays(), searcher.mapper,
                             live=searcher.ctx.live_jnp(seg, dseg))
            k = min(600, dseg.n_pad)
            assert searcher.ctx.all_live(seg)
            assert plan.sorted_topk(dims, dseg.n_pad, k)
            ms = np.float32(-np.inf)
            dense = P.unpack_topk(np.asarray(
                P.run_topk(plan, dims, k, A, ins, ms)))
            got = P.unpack_topk(np.asarray(
                P.run_topk(plan, dims, k, A, ins, ms, sorted_bag=True)))
            shapes = tuple(tuple(x.shape)
                           for x in jax.tree_util.tree_leaves((A, ins)))
            on_mesh = P.unpack_topk(np.asarray(dist_search.run_topk_mesh(
                mesh, "shards", plan, dims, k, k, True, shapes, A, ins,
                ms)))
            keep = dense[0] > -np.inf
            for other in (got, on_mesh):
                assert np.array_equal(dense[0].view(np.int32),
                                      np.asarray(other[0]).view(np.int32))
                assert np.array_equal(dense[1][keep],
                                      np.asarray(other[1])[keep])
                assert dense[2] == int(other[2])
                assert dense[3] == float(other[3])


def test_the_rule_the_kernels_branch_and_the_counter_agree(searcher,
                                                           monkeypatch):
    """With the floor at the smallest bucket every program of these bags
    has two widths: the one each program's kernel ran (eagerly, so its
    ``lax.cond`` runs one branch), ``sorted_width`` over the postings
    ``prepare`` saw, and what ``search.term_bag.sorted_lanes`` counted
    are the same, and both widths occur."""
    from opensearch_tpu.common.telemetry import metrics

    monkeypatch.setattr(bm25_ops, "_NARROW_FLOOR", 4096)
    ran = []
    inner = bm25_ops.sorted_lanes_topk

    def recording(d, *args, **kw):
        ran.append(d.shape[0])
        return inner(d, *args, **kw)

    monkeypatch.setattr(bm25_ops, "sorted_lanes_topk", recording)
    counter = metrics().counter("search.term_bag.sorted_lanes")
    wide = {"match": {"body": "w1 w2 w3 w4"}}
    choices = set()
    for query in (MATCH, wide, SPARSE):
        plan, bind = searcher.compiled(query)
        rule = []
        for seg in searcher.segments:
            dims, _ins = plan.prepare(bind, seg, seg.device(), searcher.ctx)
            rule.append(bm25_ops.sorted_width(dims[1], dims.postings, 10))
            choices.add(rule[-1] == dims[1])
        before, ran[:] = counter.value, []
        with jax.disable_jit():
            resp = searcher.search({"query": query, "size": 10})
        assert resp["_shards"]["failed"] == 0 and resp["hits"]["hits"]
        assert ran == rule
        assert counter.value - before == sum(rule)
    assert choices == {True, False}


def test_hybrid_match_half_is_sorted(searcher, monkeypatch):
    """The ``match`` sub-query of a ``hybrid`` is a bag at the root of its
    own ``_topk``: three sorted programs, and the response the dense
    programs give (the rule answered no for them)."""
    body = {"query": {"hybrid": {"queries": [MATCH, KNN]}}, "size": 10}
    before = _sorted_programs()
    device = _hits(searcher.search(dict(body)))
    assert len(device[0]) == 10
    assert _sorted_programs() - before == len(SIZES)
    monkeypatch.setattr(bm25_ops, "sorted_bag", lambda *a: False)
    assert _hits(searcher.search(dict(body))) == device
    assert _sorted_programs() - before == len(SIZES)


@pytest.mark.parametrize("query", [
    {"bool": {"must": [MATCH], "filter": [{"match": {"body": "w4"}}]}},
    {"bool": {"should": [MATCH, {"match": {"body": "w7"}}]}},
    {"dis_max": {"queries": [MATCH, {"match": {"body": "w9 w10"}}]}},
    {"function_score": {"query": MATCH, "weight": 2.0}}],
    ids=["bool_filter", "bool_should", "dis_max", "function_score"])
def test_a_bag_below_a_composite_is_not_sorted(searcher, query):
    """It needs the dense vector."""
    before = _sorted_programs()
    resp = searcher.search({"query": query, "size": 10})
    assert resp["hits"]["hits"] and resp["_shards"]["failed"] == 0
    assert _sorted_programs() == before


def test_a_size_past_the_buckets_lanes_is_not_sorted(searcher,
                                                     host_recovery):
    """One word on ~2,650 of a wide segment's docs keys 4,096 lanes, and
    ``size`` 9000 asks for more: dense there.  The narrow segment cuts
    ``k`` to its 512 rows, inside its bucket: sorted."""
    body = {"query": {"match": {"body": "w1"}}, "size": 9000}
    plan, bind = searcher.compiled(body["query"])
    buckets = [plan.prepare(bind, seg, seg.device(), searcher.ctx)[0][1]
               for seg in searcher.segments]
    assert buckets == [4096, 4096, 4096]
    host = _hits(searcher.search(dict(body)))
    host_recovery.reset()
    before = _sorted_programs()
    assert _hits(searcher.search(dict(body))) == host
    assert _sorted_programs() - before == 1


def test_a_segment_with_a_deleted_doc_keeps_the_dense_path(host_recovery):
    """One doc deleted from the first segment, the best hit of the query
    there: that segment's program is dense and the doc is in neither hits
    nor total; the other two stay sorted.  The host scorer agrees."""
    segs, mapper = _segments()
    body = {"query": MATCH, "size": 20}
    whole, whole_total = _hits(ShardSearcher(segs, mapper).search(dict(body)))
    victim = next(int(i) for i, _ in whole if int(i) < SIZES[0])
    before_delete = ShardSearcher(segs, mapper)
    segs[0].delete_local(victim)
    s = ShardSearcher(segs, mapper)
    assert [s.ctx.all_live(seg) for seg in s.segments] == [False, True, True]
    host = _hits(s.search(dict(body)))
    host_recovery.reset()
    before = _sorted_programs()
    got, total = _hits(s.search(dict(body)))
    assert _sorted_programs() - before == 2
    assert (got, total) == host
    assert str(victim) not in [i for i, _ in got]
    assert total["value"] == whole_total["value"] - 1
    want = [row for row in whole if row[0] != str(victim)]
    assert got[:len(want)] == want
    # a searcher opened before the delete keeps its snapshot: all live
    assert _hits(before_delete.search(dict(body))) == (whole, whole_total)
    assert _sorted_programs() - before == 2 + 3
