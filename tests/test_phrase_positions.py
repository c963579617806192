"""Exact phrases over the positions column (``ops/phrase.py``,
``plan.PhrasePlan``), PR 39.

Through REST: ``match_phrase`` after ``_bulk`` over three segments with a
deleted document, against a dense float64 oracle written from the
documents' own token lists: alone, in filter context, under ``min_score``,
under ``bool`` with ``and`` keywords, as a ``hybrid`` sub-query; then the
span, the counters and ``device.phrase_programs``.

Kernel level: the new ``phrase_freqs`` against the kernel it replaced
(``tools/phrase_bench.py::old_phrase_freqs``) bit for bit, the anchor's
choice, the program key, and no 64-bit integer in the compiled program.
"""

import importlib.util
import json
import math
import os
import re
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from opensearch_tpu.index.segment import SegmentWriter
from opensearch_tpu.mapping.mapper import DocumentMapper
from opensearch_tpu.node import Node
from opensearch_tpu.ops import phrase as phrase_ops
from opensearch_tpu.search import engine
from opensearch_tpu.search import plan as P

K1, B = 1.2, 0.75
STOP = {"of", "the"}
SIZES = (70, 60, 50)             # documents a segment
RARE = "w23"                     # a word the third segment lacks
DELETED = "5"


def wire(field, text):
    """The text as it is sent: the ``stop`` analyzer's tokenizer keeps
    letters only, so the ``gap`` field spells a word's number in
    letters (``w7`` -> ``wh``)."""
    if field != "gap":
        return text
    return re.sub(r"w(\d+)", lambda m: "w" + chr(97 + int(m.group(1))), text)


def _bench():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "phrase_bench.py")
    spec = importlib.util.spec_from_file_location("phrase_bench", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _corpus():
    """Seeded documents: ``body`` of words w0 .. w23 (w0 on every line,
    w23 in two segments only) with a few planted phrases, ``gap`` the
    same words with stop words between them."""
    rng = np.random.default_rng(39)
    weights = 1.0 / np.arange(1, 24)
    docs, g = [], 0
    for s, n in enumerate(SIZES):
        for _ in range(n):
            words = [f"w{w}" for w in rng.choice(
                23, size=int(rng.integers(20, 90)), p=weights / weights.sum())]
            for _ in range(int(rng.integers(0, 4))):       # planted, repeated
                at = int(rng.integers(len(words)))
                words[at:at] = ["w7", "w0", "w9"]
            if g % 9 == 0:
                words += ["w4", "w4", "w4"]
            if s < 2 and g % 5 == 0:
                words[3:3] = ["w2", RARE]
            gap = []
            for w in words:
                gap.append(w)
                if rng.random() < 0.3:
                    gap.append("of" if rng.random() < 0.5 else "the")
            docs.append({"_id": str(g), "segment": s, "body": words,
                         "gap": gap})
            g += 1
    return docs


DOCS = _corpus()


@pytest.fixture(autouse=True)
def device_path(monkeypatch):
    monkeypatch.setattr(engine, "BATCHER_ENABLED", False)


def call(node, method, path, body=None, ndjson=None):
    data, headers = None, {}
    if ndjson is not None:
        data = ("\n".join(json.dumps(x) for x in ndjson) + "\n").encode()
        headers["Content-Type"] = "application/x-ndjson"
    elif body is not None:
        data = json.dumps(body).encode()
        headers["Content-Type"] = "application/json"
    req = urllib.request.Request(f"http://127.0.0.1:{node.port}{path}",
                                 data=data, method=method, headers=headers)
    with urllib.request.urlopen(req) as resp:
        return json.loads(resp.read() or b"{}")


@pytest.fixture(scope="module")
def node(tmp_path_factory):
    n = Node(str(tmp_path_factory.mktemp("phrases")), port=0).start()
    call(n, "PUT", "/articles", {
        "settings": {"number_of_shards": 1, "number_of_replicas": 0},
        "mappings": {"properties": {
            "body": {"type": "text"},
            "gap": {"type": "text", "analyzer": "stop"}}}})
    for s in range(len(SIZES)):
        lines = []
        for d in DOCS:
            if d["segment"] == s:
                lines += [{"index": {"_index": "articles", "_id": d["_id"]}},
                          {"body": " ".join(d["body"]),
                           "gap": wire("gap", " ".join(d["gap"]))}]
        assert not call(n, "POST", "/_bulk?refresh=true",
                        ndjson=lines)["errors"]
    call(n, "DELETE", f"/articles/_doc/{DELETED}?refresh=true")
    stats = call(n, "GET", "/articles/_stats")["indices"]["articles"]["total"]
    assert stats["segments"]["count"] == len(SIZES)
    yield n
    n.stop()


# -- the oracle ------------------------------------------------------------

def _tokens(doc, field):
    """[(term, position)] as the field's analyzer leaves them."""
    return [(w, i) for i, w in enumerate(doc[field])
            if not (field == "gap" and w in STOP)]


def _stats(field):
    lens = [len(_tokens(d, field)) for d in DOCS]
    return len(DOCS), sum(lens) / len(DOCS), lens


def _idf(field, word):
    n = len(DOCS)
    df = sum(any(w == word for w, _ in _tokens(d, field)) for d in DOCS)
    return math.log(1.0 + (n - df + 0.5) / (df + 0.5)), df


def _saturation(tf, dl, avgdl):
    return tf / (tf + K1 * (1.0 - B + B * dl / avgdl)) if tf else 0.0


def phrase_tf(doc, field, text):
    words = [(w, i) for i, w in enumerate(text.split())
             if not (field == "gap" and w in STOP)]
    have = set(_tokens(doc, field))
    base = words[0][1]
    return sum(all((w, p + i - base) in have for w, i in words)
               for w0, p in have if w0 == words[0][0])


def phrase_scores(field, text):
    """{doc id: float64 score} of a ``match_phrase`` over every doc, the
    deleted one too (the statistics still count it)."""
    _n, avgdl, lens = _stats(field)
    words = [w for w in text.split() if not (field == "gap" and w in STOP)]
    idf_sum = sum(_idf(field, w)[0] for w in words)
    return {d["_id"]: idf_sum * _saturation(phrase_tf(d, field, text),
                                            lens[i], avgdl)
            for i, d in enumerate(DOCS)}


def bag_scores(field, words):
    """An ``operator: and`` bag: the sum of the words' BM25 where all are
    present, else 0."""
    _n, avgdl, lens = _stats(field)
    out = {}
    for i, d in enumerate(DOCS):
        toks = [w for w, _ in _tokens(d, field)]
        tfs = [toks.count(w) for w in words]
        out[d["_id"]] = (sum(_idf(field, w)[0] * _saturation(
            tf, lens[i], avgdl) for w, tf in zip(words, tfs))
            if all(tfs) else 0.0)
    return out


def top(scores, size=10, min_score=None):
    live = {i: s for i, s in scores.items() if s > 0 and i != DELETED
            and (min_score is None or s >= min_score)}
    order = sorted(live, key=lambda i: (-live[i], int(i)))
    return [(i, live[i]) for i in order[:size]], len(live)


def search(node, query, size=10, **extra):
    resp = call(node, "POST", "/articles/_search",
                {"query": query, "size": size, "_source": False, **extra})
    assert resp["_shards"]["failed"] == 0 and not resp["timed_out"]
    return ([(h["_id"], h["_score"]) for h in resp["hits"]["hits"]],
            resp["hits"]["total"]["value"])


def same(got, want):
    """Ids in order (equal scores may swap) and float32 scores."""
    (hits, total), (ref, n) = got, want
    assert total == n and len(hits) == len(ref)
    assert [s for _i, s in hits] == pytest.approx([s for _i, s in ref],
                                                  rel=2e-6)
    assert sorted(i for i, _s in hits) == sorted(i for i, _s in ref) or \
        ref[-1][1] == pytest.approx(hits[-1][1], rel=2e-6)


PHRASES = [("body", "w1 w2"), ("body", "w7 w0 w9"), ("body", "w0 w9"),
           ("body", "w15 w0"), ("body", "w4 w4"), ("body", "w4 w4 w4"),
           ("body", f"w2 {RARE}"), ("gap", "w1 of w2"), ("gap", "w7 w0 w9"),
           ("gap", "w3 the of w1")]
IDS = ["two_words", "three_words", "head_word_first", "head_word_last",
       "repeated_word", "repeated_thrice", "a_segment_lacks_a_slot",
       "stop_word_gap", "gap_field_no_stop_word", "two_stop_words"]


@pytest.mark.parametrize("field,text", PHRASES, ids=IDS)
def test_match_phrase_is_the_oracles_top(node, field, text):
    want = top(phrase_scores(field, text))
    assert want[1] >= 1
    sent = wire(field, text)
    same(search(node, {"match_phrase": {field: sent}}), want)
    # and every match, not the first ten alone
    same(search(node, {"match_phrase": {field: sent}}, size=200),
         top(phrase_scores(field, text), size=200))


def test_a_repeated_phrase_counts_every_occurrence():
    assert max(phrase_tf(d, "body", "w7 w0 w9") for d in DOCS) >= 2
    assert max(phrase_tf(d, "body", "w4 w4") for d in DOCS) >= 2
    doc = {"body": "w4 w4 w4".split(), "gap": []}
    assert phrase_tf(doc, "body", "w4 w4") == 2


def test_the_deleted_document_held_the_phrase(node):
    assert phrase_scores("body", "w1 w2")[DELETED] > 0 or \
        phrase_scores("body", "w7 w0 w9")[DELETED] > 0 or \
        bag_scores("body", ["w0"])[DELETED] > 0
    hits, _total = search(node, {"match": {"body": "w0"}}, size=200)
    assert DELETED not in {i for i, _s in hits}


def test_filter_context_scores_nothing(node):
    want = top(phrase_scores("body", "w7 w0 w9"), size=200)
    hits, total = search(node, {"bool": {"filter": [
        {"match_phrase": {"body": "w7 w0 w9"}}]}}, size=200)
    assert total == want[1]
    assert {i for i, _s in hits} == {i for i, _s in want[0]}
    assert {s for _i, s in hits} == {0.0}


def test_min_score_cuts_hits_and_total(node):
    scores = phrase_scores("body", "w7 w0 w9")
    ranked, n = top(scores, size=200)
    cut = ranked[n // 2][1]
    want = top(scores, min_score=cut * (1 - 1e-6))
    assert 0 < want[1] < n
    same(search(node, {"match_phrase": {"body": "w7 w0 w9"}},
                min_score=cut * (1 - 1e-6)), want)


def test_phrase_under_must_with_an_and_filter(node):
    phrase, bag = (phrase_scores("body", "w7 w0 w9"),
                   bag_scores("body", ["w3", "w5"]))
    scores = {i: s if bag[i] > 0 else 0.0 for i, s in phrase.items()}
    want = top(scores)
    assert 0 < want[1] < top(phrase)[1]
    same(search(node, {"bool": {
        "must": [{"match_phrase": {"body": "w7 w0 w9"}}],
        "filter": [{"match": {"body": {"query": "w3 w5",
                                       "operator": "and"}}}]}}), want)


def test_phrase_as_a_should_beside_and_keywords(node):
    phrase, bag = (phrase_scores("body", "w7 w0 w9"),
                   bag_scores("body", ["w7", "w9", "w2"]))
    scores = {i: s + phrase[i] if s > 0 else 0.0 for i, s in bag.items()}
    want = top(scores, size=200)
    assert any(phrase[i] == 0 for i, _s in want[0])      # a boost only
    assert any(phrase[i] > 0 for i, _s in want[0])
    same(search(node, {"bool": {
        "must": [{"match": {"body": {"query": "w7 w9 w2",
                                     "operator": "and"}}}],
        "should": [{"match_phrase": {"body": "w7 w0 w9"}}]}}, size=200),
        want)


def test_phrase_under_dis_max(node):
    a, b = phrase_scores("body", "w1 w2"), phrase_scores("body", "w0 w9")
    want = top({i: max(a[i], b[i]) for i in a})
    same(search(node, {"dis_max": {"queries": [
        {"match_phrase": {"body": "w1 w2"}},
        {"match_phrase": {"body": "w0 w9"}}]}}), want)


def test_phrase_as_a_hybrid_sub_query(node):
    """The phrase half of a ``hybrid`` is a ``_topk`` of its own: its
    candidates are the oracle's."""
    resp = call(node, "POST", "/articles/_search", {"query": {"hybrid": {
        "queries": [{"match_phrase": {"body": "w7 w0 w9"}},
                    {"match": {"body": RARE}}]}}, "size": 200,
        "_source": False})
    assert resp["_shards"]["failed"] == 0
    got = {h["_id"] for h in resp["hits"]["hits"]}
    phrase = {i for i, _s in top(phrase_scores("body", "w7 w0 w9"),
                                 size=200)[0]}
    rare = {i for i, _s in top(bag_scores("body", [RARE]), size=200)[0]}
    assert phrase and rare and got == phrase | rare


# -- spans and counters --------------------------------------------------------

def _counters(node):
    stats = next(iter(call(node, "GET", "/_nodes/stats")["nodes"].values()))
    c = stats["telemetry"]["counters"]
    return ({k: c.get(f"search.phrase.{k}", 0) for k in (
        "requests", "slots", "anchor_positions", "budget_lanes")},
        stats["device"]["phrase_programs"], stats["device"]["dispatches"])


def _spans(node, name):
    nodes = call(node, "GET", "/_nodes/trace?size=4096")["nodes"]
    spans = next(iter(nodes.values()))["spans"]
    return [s for s in spans if s["name"] == name], {
        s["span_id"]: s for s in spans}


def _anchor_positions(text):
    """What the rarest word of ``text`` holds, summed over the segments
    that hold every word."""
    total = 0
    for s in range(len(SIZES)):
        held = [sum(w == word for d in DOCS if d["segment"] == s
                    for w in d["body"]) for word in text.split()]
        total += min(held) if all(held) else 0
    return total


def test_the_span_and_the_counters(node):
    text = "w9 w0 w7 w1"          # in no other test: the plan cache misses
    before, programs0, dispatches0 = _counters(node)
    n_spans = len(_spans(node, "phrase.bind")[0])
    search(node, {"match_phrase": {"body": text}})
    after, programs, dispatches = _counters(node)
    found, by_id = _spans(node, "phrase.bind")
    assert len(found) == n_spans + 1
    span = max(found, key=lambda s: s["start_time_in_nanos"])
    assert span["attributes"] == {"slots": 4, "known": 4}
    assert by_id[span["parent_span_id"]]["name"] == "query.plan"
    assert after["requests"] - before["requests"] == 1
    assert after["slots"] - before["slots"] == 4
    assert after["anchor_positions"] - before["anchor_positions"] == \
        _anchor_positions(text)
    assert after["budget_lanes"] - before["budget_lanes"] == 3 * 1024
    assert programs - programs0 == dispatches - dispatches0 == 3
    # a plan-cache hit binds nothing and counts all the same
    search(node, {"match_phrase": {"body": text}})
    again, programs2, _d = _counters(node)
    assert len(_spans(node, "phrase.bind")[0]) == n_spans + 1
    assert again["requests"] - after["requests"] == 1
    assert again["slots"] - after["slots"] == 4
    assert programs2 - programs == 3


def test_a_phrase_under_a_bool_counts_too(node):
    before, programs0, dispatches0 = _counters(node)
    search(node, {"bool": {
        "must": [{"match": {"body": {"query": "w1 w3", "operator": "and"}}}],
        "should": [{"match_phrase": {"body": "w3 w1 w0"}}]}})
    after, programs, dispatches = _counters(node)
    assert after["requests"] - before["requests"] == 1
    assert after["slots"] - before["slots"] == 3
    assert after["budget_lanes"] - before["budget_lanes"] == 3 * 1024
    assert after["anchor_positions"] - before["anchor_positions"] == \
        _anchor_positions("w3 w1 w0")
    assert programs - programs0 == dispatches - dispatches0 == 3
    # a request without a phrase moves none of them
    search(node, {"match": {"body": "w1 w3"}})
    assert _counters(node)[0] == after and _counters(node)[1] == programs


# -- kernel level ------------------------------------------------------------

@pytest.fixture(scope="module")
def staged():
    """One segment of the corpus through ``SegmentWriter``: the staged
    columns and the term ids."""
    mapper = DocumentMapper({"properties": {"body": {"type": "text"}}})
    seg = SegmentWriter().build(
        [mapper.parse(d["_id"], {"body": " ".join(d["body"])})
         for d in DOCS], "phrases")
    pf = seg.postings["body"]
    cols = {k: jnp.asarray(v) for k, v in seg.device().postings[
        "body"].items() if k in ("offsets", "doc_ids", "pos_offsets",
                                 "positions")}
    return pf, cols, seg.device().n_pad


def _held(pf, word):
    tid = pf.term_id(word)
    e0, e1 = pf.offsets[tid], pf.offsets[tid + 1]
    return int(pf.pos_offsets[e1] - pf.pos_offsets[e0])


def _new(pf, cols, n_pad, words, positions, order=None, wider=1):
    order = order if order is not None else np.argsort(
        [_held(pf, w) for w in words], kind="stable")
    budget = wider * P.pad_bucket(_held(pf, words[order[0]]), minimum=1024)
    ids = np.zeros(4, np.int32)
    rel = np.zeros(4, np.int32)
    for j, s in enumerate(order):
        ids[j] = pf.term_id(words[s])
        rel[j] = positions[s] - positions[order[0]]
    return np.asarray(jax.jit(
        phrase_ops.phrase_freqs, static_argnames=("budget", "n_pad"))(
            cols, jnp.asarray(ids), jnp.asarray(rel),
            jnp.int32(len(words)), budget=budget, n_pad=n_pad))


KERNEL_CASES = [(("w1", "w2"), (0, 1)), (("w7", "w0", "w9"), (0, 1, 2)),
                (("w0", "w9"), (0, 1)), (("w4", "w4"), (0, 1)),
                (("w4", "w4", "w4"), (0, 1, 2)), (("w1", "w2"), (0, 2)),
                (("w3", "w1", "w0", "w5"), (0, 1, 3, 4)),
                (("w2", RARE), (0, 1))]


@pytest.mark.parametrize("table", [True, False], ids=["table", "search"])
@pytest.mark.parametrize("words,positions", KERNEL_CASES,
                         ids=["_".join(w) + "_" + "".join(map(str, p))
                              for w, p in KERNEL_CASES])
def test_the_new_kernel_is_the_old_kernels_frequency(staged, monkeypatch,
                                                     words, positions, table):
    """``table``: a probed slot's posting by the table over the segment's
    docs, or by the binary search a lane (``phrase_ops.doc_table`` picks
    by the shapes; here each in turn)."""
    pf, cols, n_pad = staged
    monkeypatch.setattr(phrase_ops, "doc_table", lambda n_pad, win: table)
    old = np.asarray(jax.jit(
        _bench().old_phrase_freqs, static_argnames=("budgets", "n_pad"))(
            cols, jnp.asarray([pf.term_id(w) for w in words], jnp.int32),
            jnp.ones(len(words), bool), jnp.asarray(positions, jnp.int32),
            budgets=tuple(P.pad_bucket(_held(pf, w), minimum=1024)
                          for w in words), n_pad=n_pad))
    new = _new(pf, cols, n_pad, words, positions)
    assert new.dtype == old.dtype == np.float32
    assert np.array_equal(new.view(np.int32), old.view(np.int32))
    if positions == tuple(range(len(words))):
        want = [phrase_tf(d, "body", " ".join(words)) for d in DOCS]
        assert new[:len(DOCS)].tolist() == want and not new[len(DOCS):].any()
    # the rarest slot as the anchor, or slot 0 as until PR 39, or the
    # most frequent: one frequency
    by_held = np.argsort([_held(pf, w) for w in words], kind="stable")
    for order in (np.arange(len(words)), by_held[::-1]):
        assert np.array_equal(
            _new(pf, cols, n_pad, words, positions, order), new)
    # and whatever the bucket
    assert np.array_equal(
        _new(pf, cols, n_pad, words, positions, wider=4), new)


def test_the_table_where_the_window_is_a_quarter_of_the_docs():
    assert phrase_ops.doc_table(16384, 4096)
    assert not phrase_ops.doc_table(16384, 1024)
    assert phrase_ops.doc_table(256, 1024)
    assert not phrase_ops.doc_table(1 << 20, 65536)


def test_no_slots_match_nothing(staged):
    _pf, cols, n_pad = staged
    got = jax.jit(phrase_ops.phrase_freqs,
                  static_argnames=("budget", "n_pad"))(
        cols, jnp.zeros(4, jnp.int32), jnp.zeros(4, jnp.int32),
        jnp.int32(0), budget=4096, n_pad=n_pad)
    assert not np.asarray(got).any()


def test_the_program_holds_no_64_bit_integer(staged):
    _pf, cols, n_pad = staged
    text = jax.jit(phrase_ops.phrase_freqs,
                   static_argnames=("budget", "n_pad")).lower(
        cols, jnp.zeros(4, jnp.int32), jnp.zeros(4, jnp.int32),
        jnp.int32(2), budget=4096, n_pad=n_pad).compile().as_text()
    assert "s64[" not in text and "u64[" not in text


def test_the_key_is_the_padded_slots_and_one_bucket():
    """Two phrases of other slot counts and other slot sizes, one
    bucket: one key."""
    a, b = P.PhraseDims.of(2, 1025), P.PhraseDims.of(3, 4000)
    assert a == b == (4, 4096) and hash(a) == hash(b)
    assert P.PhraseDims.of(2, 1000) == (4, 1024) != a
    assert (a.slots, a.anchor_positions) == (2, 1025)
    assert (b.slots, b.anchor_positions) == (3, 4000)
    assert P.PhraseDims.of(4, 4097) == (4, 16384)
    assert P.PhraseDims.of(5, 0) == (8, 1024)
    assert P.PhraseDims.of(2, 70000) == (4, 262144)
    bag = P.BagDims.of(100, 4, False)
    assert list(P.phrase_dims((bag, a))) == [a]
    assert list(P.phrase_dims(((bag,), (b, ((a,),))))) == [b, a]
    assert list(P.phrase_dims((bag, (), 3))) == []


def test_two_phrases_of_one_bucket_share_a_program(node):
    """Through the searcher: phrases of two and of three words whose
    slots hold other counts compile nothing new."""
    search(node, {"match_phrase": {"body": "w1 w2"}})
    before = P.run_topk._cache_size()
    for text in ("w2 w1", "w5 w6 w1", "w0 w1", "w3 w0 w2 w1"):
        search(node, {"match_phrase": {"body": text}})
    assert P.run_topk._cache_size() == before


def test_prepare_puts_the_rarest_slot_first(staged):
    pf, _cols, _n_pad = staged

    class Seg:
        postings = {"body": pf}

    staged_inputs = []
    plan = P.PhrasePlan(field="body")
    bind = {"terms": ("w0", "w15", "w1"), "positions": (0, 1, 3),
            "idf_sum": 1.5, "boost": 2.0, "avgdl": 50.0}
    import unittest.mock as mock
    with mock.patch.object(P, "_stage_input",
                           lambda a: staged_inputs.append(a) or a):
        dims, (packed,) = plan.prepare(bind, Seg, None, None)
    assert dims == (4, 1024) and dims.slots == 3
    assert dims.anchor_positions == _held(pf, "w15") < _held(pf, "w1")
    assert len(staged_inputs) == 1 and packed.dtype == np.int32
    assert packed[:4].tolist() == [pf.term_id("w15"), pf.term_id("w1"),
                                   pf.term_id("w0"), 0]
    assert packed[4:8].tolist() == [0, 2, -1, 0]
    assert packed[8] == 3
    assert packed[9:].view(np.float32).tolist() == [1.5, 2.0, 50.0]
    # a term the segment lacks: no slots, the smallest bucket
    dims, (packed,) = plan.prepare({**bind, "terms": ("w0", "zz", "w1")},
                                   Seg, None, None)
    assert dims == (4, 1024) and dims.anchor_positions == 0
    assert packed[8] == 0
