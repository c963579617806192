"""One array out per segment program: ``TermBagPlan.prepare`` packs a
term bag's per-query inputs (term ids, active flags, idfs, weights,
``required``) into one ``int32[4 * t_pad + 1]`` and ``eval`` splits it
with static slices and bit casts (``plan._pack_term_inputs`` /
``_unpack_term_inputs``, the outbound mirror of ``_pack_topk``).  The
round trip bit for bit, the three lowerings against the host scorer and
a numpy oracle, the answers the five-array layout gave, and the counter
that tells the arrays staged (``device.transfers.input.arrays``)."""

import http.client
import json

import jax
import numpy as np
import pytest

from opensearch_tpu.common.device_ledger import device_ledger
from opensearch_tpu.index import codec
from opensearch_tpu.index.segment import SegmentWriter
from opensearch_tpu.mapping.mapper import DocumentMapper
from opensearch_tpu.node import Node
from opensearch_tpu.search import engine
from opensearch_tpu.search import plan as P
from opensearch_tpu.search.executor import ShardSearcher, build_arrays


@pytest.fixture(autouse=True)
def device_path(monkeypatch):
    monkeypatch.setattr(engine, "BATCHER_ENABLED", False)


# -- the round trip ---------------------------------------------------------

AWKWARD = np.array([0x80000000,      # -0.0
                    0x00000001,      # the smallest subnormal
                    0xBFC00000,      # -1.5, a negative weight
                    0x7F7FFFFF,      # the largest finite
                    0x3F800001], np.uint32).view(np.float32)


def _bits(a) -> np.ndarray:
    a = np.asarray(a)
    assert a.dtype == np.float32, a.dtype
    return a.reshape(-1).view(np.uint32)


@pytest.mark.parametrize("required", ["or", "and", "msm"])
@pytest.mark.parametrize("t_pad", [1, 2, 4, 8, 16, 32, 64, 128])
def test_pack_unpack_round_trip_bit_for_bit(t_pad, required):
    rng = np.random.default_rng(t_pad)
    n_terms = max(1, t_pad - t_pad // 4)         # shorter than the pad
    required = {"or": 1, "and": n_terms, "msm": max(1, n_terms // 2)}[
        required]
    tids = np.zeros(t_pad, np.int32)
    tids[:n_terms] = rng.integers(0, 2**31 - 1, n_terms)
    active = np.zeros(t_pad, bool)
    active[:n_terms] = rng.random(n_terms) < 0.7
    idfs = np.resize(AWKWARD, n_terms)
    weights = np.resize(AWKWARD[::-1], n_terms)
    packed = P._pack_term_inputs(tids, active, idfs, weights, required)
    assert packed.dtype == np.int32 and packed.shape == (4 * t_pad + 1,)
    got = jax.jit(P._unpack_term_inputs, static_argnums=1)(packed, t_pad)
    g_tids, g_active, g_idfs, g_weights, g_required = map(np.asarray, got)
    assert g_tids.dtype == np.int32 and g_active.dtype == bool
    assert g_required.dtype == np.int32 and g_required.shape == ()
    np.testing.assert_array_equal(g_tids, tids)
    np.testing.assert_array_equal(g_active, active)
    pad = np.zeros(t_pad - n_terms, np.float32)  # +0.0 bits, as _pad_np's
    np.testing.assert_array_equal(_bits(g_idfs),
                                  _bits(np.concatenate([idfs, pad])))
    np.testing.assert_array_equal(_bits(g_weights),
                                  _bits(np.concatenate([weights, pad])))
    assert int(g_required) == required


def test_the_filter_form_leaves_the_float_lanes_zero():
    packed = P._pack_term_inputs(np.array([7, 9], np.int32),
                                 np.array([True, False]), None, None, 2)
    np.testing.assert_array_equal(packed, [7, 9, 1, 0, 0, 0, 0, 0, 2])


# -- the three lowerings ----------------------------------------------------

VOCAB = 40
N_SEG = 48


def _doc(i: int) -> str:
    """Words from a fixed rule, no generator: the pinned answers below
    must not move with a numpy release."""
    return " ".join(f"w{(i * 7 + j * j + (i % 5) * j) % VOCAB}"
                    for j in range(6 + i % 9))


def _build(prefix: str, n_segments: int = 2):
    mapper = DocumentMapper({"properties": {"body": {"type": "text"}}})
    writer = SegmentWriter()
    segs = [writer.build([mapper.parse(str(s * N_SEG + i),
                                       {"body": _doc(s * N_SEG + i)})
                          for i in range(N_SEG)], f"{prefix}{s}")
            for s in range(n_segments)]
    return ShardSearcher(segs, mapper)


def _ranked(resp):
    return [(h["_id"], np.float32(h["_score"])) for h in resp["hits"]["hits"]]


TERMS = "w1 w8 w15 w22"
SCORED = {
    "or": {"match": {"body": TERMS}},
    "and": {"match": {"body": {"query": "w1 w15", "operator": "and"}}},
    "msm": {"match": {"body": {"query": TERMS,
                               "minimum_should_match": 2}}},
}


@pytest.mark.parametrize("quantized", ["off", "on"],
                         ids=["f32", "quantized"])
@pytest.mark.parametrize("required", sorted(SCORED))
def test_scored_lowerings_equal_the_host_scorer(required, quantized,
                                                monkeypatch, host_recovery):
    """``run_topk`` over the packed ``ins`` against ``host_topk``, ids and
    float32 scores bit for bit: the f32 and the quantized lowering, the
    fast path (``required`` 1) and the counted one."""
    monkeypatch.setattr(codec, "QUANTIZED_MODE", quantized)
    body = {"query": SCORED[required], "size": 2 * N_SEG}
    host = _build(f"ph{quantized}{required}").search(dict(body))
    assert device_ledger().stats()["budget"]["host_fallbacks"] == 2
    device_ledger().reset()
    host_recovery.reset()                    # breakers closed: the device
    s = _build(f"pd{quantized}{required}")
    dev = s.search(dict(body))
    assert device_ledger().stats()["budget"]["host_fallbacks"] == 0
    assert _ranked(dev) and _ranked(dev) == _ranked(host)
    assert dev["hits"]["total"] == host["hits"]["total"]
    # the lowering asked for is the one that ran
    plan, bind = s.compiled(body["query"])
    seg = s.segments[0]
    dims, ins = plan.prepare(bind, seg, seg.device(), s.ctx)
    assert len(dims) == (4 if quantized == "on" else 3)
    assert len(ins) == (7 if quantized == "on" else 2)
    assert ins[0].dtype == np.int32 and ins[0].shape == (4 * dims[0] + 1,)
    assert dims[2] == (required == "or")


@pytest.mark.parametrize("terms,required", [
    (TERMS, 1), (TERMS, 2), ("w1 w15", 2), ("w8 w15 w22", 2)],
    ids=["or", "msm_of_4", "and", "msm_of_3"])
def test_filter_lowering_equals_a_numpy_oracle(terms, required):
    """``run_full`` over the short form ``(packed,)``: the docs holding at
    least ``required`` of the terms, counted from the text itself."""
    s = _build(f"pf{len(terms)}{required}")
    terms = terms.split()
    plan, bind = P.TermBagPlan(field="body", scored=False), {
        "terms": terms, "required": required}
    want_total = 0
    for si, seg in enumerate(s.segments):
        dseg = seg.device()
        dims, ins = plan.prepare(bind, seg, dseg, s.ctx)
        assert len(ins) == 1 and dims[2] is False
        A = build_arrays(dseg, plan.arrays(), s.mapper,
                         live=s.ctx.live_jnp(seg, dseg))
        scores, matched = P.run_full(plan, dims, A, ins,
                                     np.float32(-np.inf))
        want = np.array([len(set(_doc(si * N_SEG + i).split())
                             & set(terms)) >= required
                         for i in range(N_SEG)])
        np.testing.assert_array_equal(np.asarray(matched)[:N_SEG], want)
        assert not np.asarray(matched)[N_SEG:].any()
        assert not np.asarray(scores).any()
        want_total += int(want.sum())
    assert want_total > 0
    if required == 1:                   # and through the query DSL
        assert s.count({"match": {"body": TERMS}}) == want_total


# what the parent's five-array layout (38c6da3) returned for SCORED["or"]
# on this index, size 10: doc ids and the float32 scores' bits
PARENT_IDS = ["29", "69", "46", "56", "23", "16", "93", "86", "13", "6"]
PARENT_SCORE_BITS = [0x4001DF65, 0x3FF92298, 0x3FF28006, 0x3FE7A14A,
                     0x3FE5AD82, 0x3FE57970, 0x3FCF3824, 0x3FCC2C7F,
                     0x3FC6B1E8, 0x3FC469C9]


def test_scored_or_gives_the_five_array_layouts_answers():
    resp = _build("pp").search({"query": SCORED["or"], "size": 10})
    assert resp["hits"]["total"] == {"value": 52, "relation": "eq"}
    assert [h["_id"] for h in resp["hits"]["hits"]] == PARENT_IDS
    got = np.array([h["_score"] for h in resp["hits"]["hits"]], np.float32)
    np.testing.assert_array_equal(got.view(np.uint32),
                                  np.array(PARENT_SCORE_BITS, np.uint32))


# -- the counter, over REST ---------------------------------------------------

INDEX, S = "packed_inputs", 3


def _call(node, method, path, body=None, ndjson=None):
    c = http.client.HTTPConnection("127.0.0.1", node.port)
    headers, data = {}, None
    if ndjson is not None:
        data = "".join(json.dumps(line) + "\n" for line in ndjson)
        headers["Content-Type"] = "application/x-ndjson"
    elif body is not None:
        data = json.dumps(body)
        headers["Content-Type"] = "application/json"
    c.request(method, path, body=data, headers=headers)
    resp = c.getresponse()
    out = json.loads(resp.read() or b"{}")
    c.close()
    return resp.status, out


@pytest.fixture(scope="module")
def node(tmp_path_factory):
    """``S`` segments of one shard over HTTP; ``only2`` is a word of the
    last segment alone."""
    node = Node(str(tmp_path_factory.mktemp("packed_in")), port=0).start()
    assert _call(node, "PUT", "/" + INDEX, {
        "settings": {"number_of_shards": 1, "number_of_replicas": 0},
        "mappings": {"properties": {"t": {"type": "text"},
                                    "u": {"type": "text"}}}})[0] == 200
    for batch in range(S):                   # a refresh each: S segments
        lines = []
        for i in range(20):
            n = batch * 20 + i
            lines += [{"index": {"_index": INDEX, "_id": str(n)}},
                      {"t": f"alpha w{i % 3} beta"
                            + (" only2" if batch == 2 else ""),
                       "u": f"gamma x{i % 4}"}]
        status, resp = _call(node, "POST", "/_bulk?refresh=true",
                             ndjson=lines)
        assert status == 200 and not resp["errors"], resp
    yield node
    node.stop()
    device_ledger().reset()


def _input(node) -> dict:
    nodes = _call(node, "GET", "/_nodes/stats")[1]["nodes"]
    return next(iter(nodes.values()))["device"]["transfers"]["input"]


def _staged(node, query, hits=True) -> tuple:
    before = _input(node)
    status, resp = _call(node, "POST", f"/{INDEX}/_search",
                         {"query": query, "size": 3})
    assert status == 200 and bool(resp["hits"]["hits"]) == hits, resp
    after = _input(node)
    return (after["arrays"] - before["arrays"],
            after["bytes"] - before["bytes"])


S_PAD = 8                   # the stacked rows of up to eight segments


def test_one_input_array_a_scanned_segment_and_none_on_a_hit(node):
    # a scored bag at the root: its segments' rows cross as one array;
    # two terms: t_pad 2, nine words of four bytes a row
    assert _staged(node, {"match": {"t": "alpha w1"}}) == (1, S_PAD * 9 * 4)
    # the same body again: every segment's inputs are there already
    assert _staged(node, {"match": {"t": "alpha w1"}}) == (0, 0)
    # another query misses again
    assert _staged(node, {"match": {"t": "alpha w2"}}) == (1, S_PAD * 9 * 4)


def test_a_segment_that_cannot_match_stages_nothing(node):
    """``can_match`` drops the two segments without the word before any
    ``prepare``: one program, one array."""
    assert _staged(node, {"match": {"t": "only2"}}) == (1, S_PAD * 5 * 4)
    assert _staged(node, {"match": {"t": "nowhere"}}, hits=False) == (0, 0)


def test_a_bool_of_two_bags_stages_two_a_segment(node):
    query = {"bool": {"must": [{"match": {"t": "beta w0"}},
                               {"match": {"u": "gamma x1 x2"}}]}}
    # t_pad 2 and 4: nine and seventeen words; the bool's own boost
    # and ``required`` are a scalar each
    assert _staged(node, query) == ((2 + 2) * S, S * (9 + 17 + 2) * 4)


def test_a_filter_bag_stages_one_short_form_too(node):
    query = {"bool": {"filter": [{"match": {"t": "w0 w1"}}]}}
    assert _staged(node, query) == ((1 + 2) * S, S * (9 + 2) * 4)
