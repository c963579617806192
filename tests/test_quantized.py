"""Quantized, paged device index (index/codec.py, ops/quantized.py,
the DevicePager in common/device_ledger.py).

The tentpole invariants: (1) quantized top-k is RANK-IDENTICAL to the
f32 path — the per-term exact-rank-parity guard stores any term whose
quantized order would diverge at full precision; (2) the host fallback
on quantized segments is byte-identical to the device kernels (same
dequantized f32 column, same op order); (3) pager eviction and restage
never change a result bit; (4) ``.quant`` sidecars are crash-safe —
corruption degrades to recompute-and-rewrite, never a failed search.

Also covers the bit-packed doc-id codec (host/device decode parity),
the block-max prefetch oracle, demand-staged full postings for
filter-context/phrase plans on quantized segments, the `_nodes/stats`
``device.pager`` section, and the tools/check_quantized_staging.py
tier-1 lint.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

import jax.numpy as jnp

from opensearch_tpu.common.device_ledger import device_ledger, device_pager
from opensearch_tpu.common.telemetry import metrics
from opensearch_tpu.index import codec
from opensearch_tpu.index import store
from opensearch_tpu.index.segment import SegmentWriter
from opensearch_tpu.mapping.mapper import DocumentMapper
from opensearch_tpu.ops import bm25 as bm25_ops
from opensearch_tpu.search.executor import ShardSearcher

TOOLS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools")


@pytest.fixture(autouse=True)
def _clean_pager_state():
    led = device_ledger()
    led.reset()
    yield
    led.reset()


@pytest.fixture(params=["host", "device"])
def scoring_path(request):
    """The XLA kernels, or the host scorer that recovers them (through
    the open breakers of ``host_recovery``)."""
    if request.param == "host":
        request.getfixturevalue("host_recovery")
    return request.param


def zipf_corpus(rng, n_docs, vocab=120, avg_len=24):
    docs = []
    for _ in range(n_docs):
        n = int(rng.integers(avg_len // 2, avg_len * 2))
        terms = (rng.zipf(1.4, size=n) - 1).clip(0, vocab - 1)
        docs.append({"body": " ".join(f"w{t}" for t in terms)})
    return docs


def build_searcher(docs, seg_sizes, prefix="qz"):
    mapper = DocumentMapper({"properties": {"body": {"type": "text"}}})
    writer = SegmentWriter()
    segs, i = [], 0
    for si, size in enumerate(seg_sizes):
        batch = [mapper.parse(str(i + j), d)
                 for j, d in enumerate(docs[i: i + size])]
        segs.append(writer.build(batch, f"{prefix}{si}"))
        i += size
    return ShardSearcher(segs, mapper), mapper


def ranked_hits(resp):
    return [(h["_id"], np.float32(h["_score"]))
            for h in resp["hits"]["hits"]]


def assert_rank_parity_mod_ties(got, ref, tol=0.03):
    """The quantized ranking must equal the f32 ranking up to
    permutations WITHIN near-tie groups of the reference: per-segment
    scale factors put each doc's dequantized score inside a small error
    band around its f32 score, so docs whose f32 scores are closer than
    the band may swap — any reordering across a larger gap is a bug
    (the per-term exact-rank-parity guard rules it out within a
    segment; across segments the bands themselves bound it)."""
    assert sorted(i for i, _ in got) == sorted(i for i, _ in ref)
    groups, cur = [], []
    for _id, sc in ref:
        if cur and abs(cur[-1][1] - sc) > tol * max(abs(sc), 1e-6):
            groups.append(cur)
            cur = []
        cur.append((_id, sc))
    if cur:
        groups.append(cur)
    pos = 0
    for g in groups:
        want = {i for i, _ in g}
        have = {i for i, _ in got[pos:pos + len(g)]}
        assert have == want, (pos, have, want)
        pos += len(g)


# -- codec: quantization + parity guard -------------------------------------

def test_quantize_postings_bound_safe_and_nonzero():
    """Floor-of-1 quantization: every dequantized impact stays at or
    below the term's block max (the pruning bound stays an upper
    bound), and no matched posting quantizes to zero (score > 0 iff
    matched is preserved)."""
    rng = np.random.default_rng(7)
    _, mapper = build_searcher(zipf_corpus(rng, 50), [50], prefix="cb")
    writer = SegmentWriter()
    batch = [mapper.parse(str(i), d)
             for i, d in enumerate(zipf_corpus(rng, 120))]
    seg = writer.build(batch, "codecseg")
    pf = seg.postings["body"]
    avgdl = float(np.float32(pf.doc_lens.mean()))
    imp, mx = seg.impact_table("body", avgdl)
    qt = codec.quantize_postings(pf, imp, mx, avgdl)

    deq = qt.dequantized()
    assert deq.shape == imp.shape and deq.dtype == np.float32
    per_term_max = mx[np.searchsorted(pf.offsets, np.arange(len(imp)),
                                      side="right") - 1]
    assert np.all(deq <= per_term_max * np.float32(1.0001))
    assert np.all(deq[imp > 0] > 0)
    assert qt.stats["quant_bytes"] < qt.stats["f32_bytes"]
    assert qt.stats["postings"] == len(imp)
    assert qt.nbytes == qt.stats["quant_bytes"]


def test_parity_guard_stores_misranked_terms_exact():
    """A term whose int8 buckets would reorder its postings relative to
    the f32 sort (ties break by doc id) is stored exact-f32 — rank
    parity is guaranteed per construction, not per corpus."""
    # term 0: docs 3 and 5 collapse into the same bucket but doc 5
    # outranks doc 3 at f32 — the quantized tie would invert them
    # term 1: well-separated values, quantizes cleanly
    offsets = np.array([0, 3, 6], dtype=np.int64)
    doc_ids = np.array([3, 5, 9, 1, 2, 4], dtype=np.int32)
    imp = np.array([0.5, 0.5001, 1.0, 0.25, 0.5, 1.0], dtype=np.float32)
    mx = np.array([1.0, 1.0], dtype=np.float32)
    qvals, scales, exact_vals, exact_offsets, stats = \
        codec.quantize_impacts(imp, mx, offsets, doc_ids)
    assert stats["exact_terms"] == 1
    assert stats["exact_postings"] == 3
    assert exact_offsets[1] - exact_offsets[0] == 3
    np.testing.assert_array_equal(exact_vals[:3], imp[:3])
    # clean term stays quantized-only
    assert exact_offsets[2] == exact_offsets[1]


def test_pack_unpack_doc_ids_roundtrip():
    offsets = np.array([0, 3, 3, 7], dtype=np.int64)
    doc_ids = np.array([100, 101, 4096, 5, 6, 1000, 1 << 20],
                       dtype=np.int32)
    packed, base, width = codec.pack_doc_ids(doc_ids, offsets)
    assert packed.dtype == np.uint32
    out = codec.unpack_doc_ids(packed, base, offsets, width)
    np.testing.assert_array_equal(out, doc_ids)
    np.testing.assert_array_equal(base, [100, 0, 5])


def test_gather_postings_packed_matches_unpacked():
    """The device bit-decode gather returns the same doc ids / slots /
    valid lanes as the plain CSR gather it replaces."""
    rng = np.random.default_rng(11)
    _, mapper = build_searcher(zipf_corpus(rng, 40), [40], prefix="gp")
    writer = SegmentWriter()
    batch = [mapper.parse(str(i), d)
             for i, d in enumerate(zipf_corpus(rng, 150))]
    seg = writer.build(batch, "gatherseg")
    pf = seg.postings["body"]
    packed, base, width = codec.pack_doc_ids(pf.doc_ids, pf.offsets)

    T = len(pf.offsets) - 1
    term_ids = jnp.asarray(               # staging-ok: test inputs
        np.array([0, 1, 2, min(3, T - 1)], dtype=np.int32))
    active = jnp.asarray(                 # staging-ok: test inputs
        np.array([True, True, True, True]))
    budget = 1 << int(np.ceil(np.log2(len(pf.doc_ids) + 1)))
    d0, _tf, s0, v0 = bm25_ops.gather_postings(
        jnp.asarray(pf.offsets),          # staging-ok: test inputs
        jnp.asarray(pf.doc_ids),          # staging-ok: test inputs
        jnp.asarray(pf.tfs),              # staging-ok: test inputs
        term_ids, active, budget=budget, pad_doc=seg.n_docs)
    d1, _idx, s1, v1 = bm25_ops.gather_postings_packed(
        jnp.asarray(pf.offsets),          # staging-ok: test inputs
        jnp.asarray(packed),              # staging-ok: test inputs
        jnp.asarray(base),                # staging-ok: test inputs
        term_ids, active, width=width, budget=budget,
        pad_doc=seg.n_docs)
    np.testing.assert_array_equal(np.asarray(v0), np.asarray(v1))
    np.testing.assert_array_equal(np.asarray(d0), np.asarray(d1))
    np.testing.assert_array_equal(np.asarray(s0)[np.asarray(v0)],
                                  np.asarray(s1)[np.asarray(v1)])


# -- engine parity: quantized vs f32, host vs device -------------------------

def test_quantized_single_term_single_segment_exact_rank(
        scoring_path, monkeypatch):
    """The pinned parity suite: within one segment a single term's
    quantized ranking is IDENTICAL to f32 — the exact-rank-parity guard
    stores any term whose quantized order would diverge, so this holds
    per construction, not per corpus (and on both lowerings)."""
    rng = np.random.default_rng(71)
    docs = zipf_corpus(rng, 280)
    monkeypatch.setattr(codec, "QUANTIZED_MODE", "off")
    s_f, _ = build_searcher(docs, [280], prefix="pf")
    refs = {}
    for t in ("w0", "w1", "w2", "w5", "w9", "w17"):
        refs[t] = s_f.search(
            {"query": {"match": {"body": t}}, "size": 280})

    device_ledger().reset()
    monkeypatch.setattr(codec, "QUANTIZED_MODE", "on")
    s_q, _ = build_searcher(docs, [280], prefix="pq")
    for t, r in refs.items():
        got = s_q.search({"query": {"match": {"body": t}}, "size": 280})
        assert [h[0] for h in ranked_hits(got)] == \
            [h[0] for h in ranked_hits(r)], t
        assert got["hits"]["total"]["value"] == \
            r["hits"]["total"]["value"]


@pytest.mark.parametrize("seed", [5, 23])
def test_quantized_rank_parity_vs_f32(seed, scoring_path, monkeypatch):
    """General engine parity: under QUANTIZED_MODE=on multi-term,
    multi-segment rankings match the f32 path up to near-tie
    permutations, with matched-doc sets and totals identical and scores
    within the dequantization tolerance — across the sequential and
    batched msearch paths and both lowerings."""
    rng = np.random.default_rng(seed)
    docs = zipf_corpus(rng, 300)
    queries = []
    for _ in range(5):
        a, b = (rng.zipf(1.4, size=2) - 1).clip(0, 119)
        terms = [f"w{a}"] if a == b else [f"w{a}", f"w{b}"]
        queries.append({"query": {"match": {"body": " ".join(terms)}},
                        "size": 300})

    monkeypatch.setattr(codec, "QUANTIZED_MODE", "off")
    s_f32, _ = build_searcher(docs, [120, 100, 80], prefix=f"f{seed}_")
    ref = [s_f32.search(dict(q)) for q in queries]

    device_ledger().reset()
    monkeypatch.setattr(codec, "QUANTIZED_MODE", "on")
    s_q, _ = build_searcher(docs, [120, 100, 80], prefix=f"q{seed}_")
    for q, r in zip(queries, ref):
        got = s_q.search(dict(q))
        assert_rank_parity_mod_ties(ranked_hits(got), ranked_hits(r))
        assert got["hits"]["total"]["value"] == \
            r["hits"]["total"]["value"]
        for (_, sq), (_, sf) in zip(ranked_hits(got), ranked_hits(r)):
            assert abs(sq - sf) <= 3e-2 * max(abs(sf), 1e-6)
    # batched msearch path: the device union lowering demand-stages the
    # exact f32 impacts while the host fallback scores off the
    # dequantized tables — either way the ranking parity must hold
    mresp = s_q.msearch([dict(q) for q in queries])
    for m, r in zip(mresp, ref):
        assert_rank_parity_mod_ties(ranked_hits(m), ranked_hits(r))


def test_quantized_mesh_search_rank_parity(monkeypatch):
    """The mesh scatter-gather path over quantized shards returns the
    same ranked ids and totals as over f32 shards."""
    from opensearch_tpu.parallel.dist_search import MeshSearcher
    rng = np.random.default_rng(9)
    docs = zipf_corpus(rng, 240)
    body = {"query": {"match": {"body": "w0 w4"}}, "size": 240}

    monkeypatch.setattr(codec, "QUANTIZED_MODE", "off")
    shards_f = [build_searcher(docs[i * 60:(i + 1) * 60], [60],
                               prefix=f"mf{i}_")[0] for i in range(4)]
    ref = MeshSearcher(shards_f).search(dict(body))

    device_ledger().reset()
    monkeypatch.setattr(codec, "QUANTIZED_MODE", "on")
    shards_q = [build_searcher(docs[i * 60:(i + 1) * 60], [60],
                               prefix=f"mq{i}_")[0] for i in range(4)]
    got = MeshSearcher(shards_q).search(dict(body))
    assert_rank_parity_mod_ties(ranked_hits(got), ranked_hits(ref))
    assert got["hits"]["total"]["value"] == ref["hits"]["total"]["value"]


def test_quantized_host_device_byte_identical(monkeypatch, host_recovery):
    """On a quantized segment the host fallback computes scores from
    the SAME dequantized f32 column in the same op order as the device
    kernel — byte-identical, like the f32 path's host/device parity."""
    rng = np.random.default_rng(31)
    docs = zipf_corpus(rng, 260)
    monkeypatch.setattr(codec, "QUANTIZED_MODE", "on")
    body = {"query": {"match": {"body": "w0 w3"}}, "size": 260}

    s_host, _ = build_searcher(docs, [130, 130], prefix="hb")
    host = ranked_hits(s_host.search(dict(body)))
    assert device_ledger().stats()["budget"]["host_fallbacks"] == 2

    device_ledger().reset()
    host_recovery.reset()                    # breakers closed: the device
    s_dev, _ = build_searcher(docs, [130, 130], prefix="db")
    dev = ranked_hits(s_dev.search(dict(body)))
    assert host == dev    # ids AND float32 scores, bit-for-bit


def test_filter_phrase_on_quantized_segments(monkeypatch):
    """Plans that need raw postings (filter context, phrase) demand-
    stage them via ensure_postings on quantized segments and match the
    f32 path exactly — and the staging is counted."""
    rng = np.random.default_rng(17)
    docs = zipf_corpus(rng, 200)
    bodies = [
        {"query": {"bool": {"filter": [{"term": {"body": "w0"}}]}},
         "size": 200},
        {"query": {"bool": {"must": [{"term": {"body": "w0"}},
                                     {"term": {"body": "w1"}}]}},
         "size": 200},
        {"query": {"match_phrase": {"body": "w0 w1"}}, "size": 200},
    ]

    monkeypatch.setattr(codec, "QUANTIZED_MODE", "off")
    s_f32, _ = build_searcher(docs, [100, 100], prefix="ff")
    ref = [s_f32.search(dict(b)) for b in bodies]

    device_ledger().reset()
    monkeypatch.setattr(codec, "QUANTIZED_MODE", "on")
    c0 = metrics().counter("device.quantized.full_postings").value
    s_q, _ = build_searcher(docs, [100, 100], prefix="qf")
    for b, r in zip(bodies, ref):
        got = s_q.search(dict(b))
        assert got["hits"]["total"]["value"] == \
            r["hits"]["total"]["value"]
        assert ranked_hits(got) == ranked_hits(r)
    assert metrics().counter("device.quantized.full_postings").value > c0


# -- pager: LRU eviction, restage identity, prefetch -------------------------

def _mk_loader(i):
    def loader():
        return [("a", "impacts_q", np.full(32, i, dtype=np.int8)),
                ("b", "postings_q",
                 (np.arange(8, dtype=np.uint32) + i))]
    return loader


def test_pager_lru_eviction_and_restage():
    led = device_ledger()
    pager = device_pager()
    pager.set_page_bytes(256)
    led.set_budget(512)                      # capacity: 2 pages
    assert pager.capacity_pages() == 2

    keys = [("ix", 0, f"s{i}", "body", 0.0) for i in range(3)]
    a1 = pager.acquire(keys[0], _mk_loader(1))
    assert pager.stats()["misses"] == 1
    again = pager.acquire(keys[0], _mk_loader(1))
    assert pager.stats()["hits"] == 1 and again is a1
    pager.acquire(keys[1], _mk_loader(2))
    pager.acquire(keys[2], _mk_loader(3))    # evicts LRU (keys[0])
    st = pager.stats()
    assert st["resident_entries"] == 2 and st["evictions"] == 1

    # restage of the evicted entry is byte-identical and evicts anew
    a1b = pager.acquire(keys[0], _mk_loader(1))
    np.testing.assert_array_equal(np.asarray(a1b["a"]),
                                  np.full(32, 1, dtype=np.int8))
    st = pager.stats()
    assert st["misses"] == 4 and st["evictions"] == 2
    assert st["resident_pages"] <= 2


def test_pager_prefetch_never_evicts():
    led = device_ledger()
    pager = device_pager()
    pager.set_page_bytes(256)
    led.set_budget(512)                      # capacity: 2 pages
    keys = [("ix", 0, f"p{i}", "body", 0.0) for i in range(3)]
    pager.acquire(keys[0], _mk_loader(1))
    pager.acquire(keys[1], _mk_loader(2))
    # full: prefetch refuses rather than evicting a resident entry
    assert pager.prefetch(keys[2], _mk_loader(3), 64) is False
    assert pager.stats()["resident_entries"] == 2
    assert pager.stats()["prefetches"] == 0
    led.set_budget(2048)                     # room opens up
    assert pager.prefetch(keys[2], _mk_loader(3), 64) is True
    assert pager.stats()["prefetches"] == 1
    hits0 = pager.stats()["hits"]
    pager.acquire(keys[2], _mk_loader(3))    # prefetched: a hit
    assert pager.stats()["hits"] == hits0 + 1
    # already resident: prefetch is a no-op
    assert pager.prefetch(keys[2], _mk_loader(3), 64) is False


def test_pager_eviction_is_invisible_to_results(monkeypatch):
    """Crush the device budget under the quantized working set: the
    pager thrashes (evictions > 0) but every score bit is unchanged."""
    rng = np.random.default_rng(41)
    docs = zipf_corpus(rng, 240)
    monkeypatch.setattr(codec, "QUANTIZED_MODE", "on")
    s, _ = build_searcher(docs, [80, 80, 80], prefix="ev")
    body = {"query": {"match": {"body": "w0 w2"}}, "size": 240}
    ref = ranked_hits(s.search(dict(body)))
    assert device_pager().stats()["resident_entries"] > 0

    device_ledger().set_budget(1)            # evict everything staged
    got = ranked_hits(s.search(dict(body)))
    assert got == ref                        # bit-for-bit
    assert device_pager().stats()["evictions"] > 0


def test_prefetch_oracle_runs_ahead_of_dispatch(monkeypatch):
    """The block-max prefetch oracle stages every segment's quantized
    tables before the dispatch loop asks — a cold scored query sees
    pager hits, not misses."""
    rng = np.random.default_rng(53)
    docs = zipf_corpus(rng, 210)
    monkeypatch.setattr(codec, "QUANTIZED_MODE", "on")
    s, _ = build_searcher(docs, [70, 70, 70], prefix="po")
    s.search({"query": {"match": {"body": "w1"}}, "size": 10})
    st = device_pager().stats()
    assert st["prefetches"] == 3
    assert st["misses"] == 0
    assert st["hits"] >= 3


def test_pager_stats_in_ledger_and_metrics(monkeypatch):
    monkeypatch.setattr(codec, "QUANTIZED_MODE", "on")
    rng = np.random.default_rng(61)
    s, _ = build_searcher(zipf_corpus(rng, 90), [90], prefix="st")
    s.search({"query": {"match": {"body": "w0"}}, "size": 5})
    led = device_ledger()
    pstats = led.stats()["pager"]
    for key in ("page_bytes", "capacity_pages", "resident_pages",
                "resident_entries", "resident_bytes", "hits", "misses",
                "evictions", "evicted_pages", "prefetches"):
        assert key in pstats
    assert pstats["resident_entries"] >= 1
    text = led.prometheus_text()
    assert "opensearch_tpu_device_pager_resident_pages" in text
    assert "opensearch_tpu_device_pager_capacity_pages" in text


# -- .quant sidecars: durability + corruption matrix -------------------------

def _seg_on_disk(tmp_path, n_docs=70):
    rng = np.random.default_rng(19)
    mapper = DocumentMapper({"properties": {"body": {"type": "text"}}})
    writer = SegmentWriter()
    batch = [mapper.parse(str(i), d)
             for i, d in enumerate(zipf_corpus(rng, n_docs))]
    seg = writer.build(batch, "qsc0")
    store.save_segment(seg, str(tmp_path))
    loaded = store.load_segment(str(tmp_path), "qsc0")
    avgdl = float(np.float32(loaded.postings["body"].doc_lens.mean()))
    return loaded, avgdl


def test_quant_sidecar_roundtrip_and_staleness(tmp_path):
    loaded, avgdl = _seg_on_disk(tmp_path)
    qt = loaded.quantized_table("body", avgdl)
    path = os.path.join(str(tmp_path),
                        store.quant_sidecar_name("qsc0", "body"))
    assert os.path.exists(path)

    back = store.load_quantized_tables(str(tmp_path), "qsc0", "body",
                                       avgdl=avgdl)
    np.testing.assert_array_equal(back.qvals, qt.qvals)
    np.testing.assert_array_equal(back.scales, qt.scales)
    np.testing.assert_array_equal(back.packed, qt.packed)
    np.testing.assert_array_equal(back.base, qt.base)
    assert back.width == qt.width and back.dtype == qt.dtype

    # avgdl moved under a refresh/merge: the sidecar is stale, not wrong
    assert store.load_quantized_tables(str(tmp_path), "qsc0", "body",
                                       avgdl=avgdl + 1.0) is None
    # absent file is absent, not an error
    assert store.load_quantized_tables(str(tmp_path), "qsc0",
                                       "nosuch") is None
    # the sidecar participates in fsck and teardown
    assert store.verify_segment(str(tmp_path), "qsc0") is True
    store.delete_segment_files(str(tmp_path), "qsc0")
    assert not os.path.exists(path)


@pytest.mark.parametrize("corruption", [
    "truncate", "bitflip", "bad_header", "garbage_payload"])
def test_quant_sidecar_corruption_matrix(tmp_path, corruption):
    loaded, avgdl = _seg_on_disk(tmp_path)
    loaded.quantized_table("body", avgdl)
    path = os.path.join(str(tmp_path),
                        store.quant_sidecar_name("qsc0", "body"))
    data = open(path, "rb").read()
    if corruption == "truncate":
        bad = data[:6]
    elif corruption == "bitflip":
        flip = bytearray(data)
        flip[20] ^= 0xFF
        bad = bytes(flip)
    elif corruption == "bad_header":
        bad = b"zzzzzzzz" + data[8:]
    else:                                   # valid CRC over garbage
        import zlib
        payload = b"not an npz at all"
        crc = zlib.crc32(payload) & 0xFFFFFFFF
        bad = f"{crc:08x}".encode() + payload
    with open(path, "wb") as f:
        f.write(bad)

    with pytest.raises(store.CorruptIndexError) as ei:
        store.load_quantized_tables(str(tmp_path), "qsc0", "body")
    assert "qsc0.body.quant" in str(ei.value)
    # fsck surfaces the bad sidecar (verify_segment raises on the
    # first corrupt file, per its contract)
    with pytest.raises(store.CorruptIndexError):
        store.verify_segment(str(tmp_path), "qsc0")

    # the search path degrades: a fresh reader recomputes AND rewrites
    again = store.load_segment(str(tmp_path), "qsc0")
    qt = again.quantized_table("body", avgdl)
    assert qt is not None
    assert store.load_quantized_tables(
        str(tmp_path), "qsc0", "body", avgdl=avgdl) is not None
    assert store.verify_segment(str(tmp_path), "qsc0") is True


# -- tools/check_quantized_staging.py lint -----------------------------------

def test_check_quantized_staging_lint_passes():
    r = subprocess.run(
        [sys.executable,
         os.path.join(TOOLS, "check_quantized_staging.py")],
        capture_output=True, text=True)
    assert r.returncode == 0, r.stdout + r.stderr


def test_check_quantized_staging_lint_catches_violations(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text(
        "imp = dseg.impacts('body')\n"
        "led.stage(g, a, kind=\"impacts\", field='body')\n"
        "ok = dseg.impacts('body')  # quantize-ok: test annotation\n"
        "# quantize-ok: above-line annotation\n"
        "ok2 = led.stage(g, a, kind='impacts')\n"
        "fine = led.stage(g, a, kind='impacts_q')\n")
    exempt = tmp_path / "codec.py"
    exempt.write_text("imp = dseg.impacts('body')\n")
    r = subprocess.run(
        [sys.executable,
         os.path.join(TOOLS, "check_quantized_staging.py"),
         str(tmp_path)],
        capture_output=True, text=True)
    assert r.returncode == 1
    assert "bad.py:1" in r.stdout and "bad.py:2" in r.stdout
    assert "bad.py:3" not in r.stdout and "bad.py:5" not in r.stdout
    assert "bad.py:6" not in r.stdout
    assert f"{exempt}:" not in r.stdout    # codec.py is exempt wholesale
