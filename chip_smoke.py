#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

One process boots a node the way ``opensearch_tpu.node.main`` does, drives
it over HTTP only with ``opensearch_tpu.client.OpenSearch``, and decides
``correct`` by results, never timings:

=============  =====================================================  =====
index          shape (source: BASELINE.json configs 1, 5 and 2)        docs
=============  =====================================================  =====
smoke_f32      msmarco-passage-like ``body`` (28-84 tokens, zipf       65,536
               vocabulary), ``ts`` date, ``tag`` keyword, ``v`` long;
               refresh every 16,384 -> 4 f32 segments
smoke_quant    same mapping, ONE refresh -> one segment past           131,072
               ``QUANTIZED_MIN_DOCS``, quantized under the default
               ``index.device.quantized: auto``
smoke_sift     ``knn_vector`` dim 128, L2 (SIFT shape), seeded         65,536
               NON-integer floats
smoke_mesh     smoke_f32's docs over 4 shards with                     65,536
               ``"search.mesh": true`` (only when >= 4 devices)
=============  =====================================================  =====

Checks: smoke_f32 top-10 against an independent numpy BM25; ``_msearch``
members against their sequential answers; smoke_quant device answers
against the host scorer's over the same ``.quant`` tables (asked for
through an open ``dispatch`` breaker, the product's own degradation
route), again at half the device budget with the pager missing; every agg bucket against numpy;
kNN recall@10 = 1.0 against a float32 numpy scan; the compiled Pallas
kNN kernel against numpy at [262144, 128]; and, read from the client's
side of ``GET /_nodes/stats``, proof that the DEVICE did the work — the
search path answers device faults from a byte-identical host path with a
200, so without those counters a refused kernel would pass unnoticed.

Exit code 0 and a last stdout line ``{"ok": true, "device": {...}}`` only
when every check held on a TPU.  There is no size argument and no CPU
mode: ``tests/test_chip_smoke.py`` imports ``run_smoke`` to debug the same
steps at a tiny size on CPU XLA kernels.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import tempfile
import time
from importlib.metadata import version

import numpy as np

K1, B = 1.2, 0.75
TOP_K = 10
QUERY_TERMS = 6
N_TAGS = 16
TS0, TS_STEP = 1_700_000_000_000, 60_000     # one doc per minute
RTOL_SCORE = 1e-5          # engine f32 scores vs the float64 reference
RTOL_PARITY = 1e-6         # two lowerings of the same f32 arithmetic


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Scale only — the shapes above are the sources' own."""
    f32_docs: int = 65_536
    f32_refresh_every: int = 16_384
    quant_docs: int = 131_072
    vectors: int = 65_536
    dim: int = 128
    vocab: int = 262_144
    seq_queries: int = 32
    msearch_queries: int = 64
    knn_queries: int = 16
    pallas_rows: int = 262_144
    bulk_chunk: int = 2_048


class SmokeFailure(AssertionError):
    """A check did not hold."""


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def require(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# -- seeded data --------------------------------------------------------------

class TextCorpus:
    """Seeded passages plus their own CSR postings: the independent side
    of the BM25 comparison (nothing here touches the engine)."""

    def __init__(self, rng: np.random.Generator, n_docs: int, vocab: int):
        self.n_docs = n_docs
        self.lens = rng.integers(28, 85, size=n_docs)
        cdf = np.cumsum(1.0 / np.arange(1, vocab + 1))       # zipf, s = 1
        cdf /= cdf[-1]
        self.tokens = np.searchsorted(
            cdf, rng.random(int(self.lens.sum()))).astype(np.int64)
        self.starts = np.concatenate([[0], np.cumsum(self.lens)])
        self.tags = np.minimum(rng.zipf(1.5, size=n_docs) - 1, N_TAGS - 1)
        self.ts = TS0 + TS_STEP * np.arange(n_docs, dtype=np.int64)
        doc_of = np.repeat(np.arange(n_docs, dtype=np.int64), self.lens)
        pairs, tfs = np.unique(self.tokens * n_docs + doc_of,
                               return_counts=True)
        self.p_doc = pairs % n_docs
        self.p_tf = tfs.astype(np.float64)
        self.term_start = np.searchsorted(pairs // n_docs,
                                          np.arange(vocab + 1))
        self.distinct_terms = int((np.diff(self.term_start) > 0).sum())
        self._names = np.char.add("t", np.arange(vocab).astype(str))

    def source(self, i: int) -> dict:
        toks = self.tokens[self.starts[i]: self.starts[i + 1]]
        return {"body": " ".join(self._names[toks]), "ts": int(self.ts[i]),
                "tag": f"tag{self.tags[i]}", "v": i}

    def queries(self, rng: np.random.Generator, n: int) -> list:
        """``n`` six-term queries, each drawn from one passage's own
        words so it has a best answer (how msmarco queries relate to
        their passages).  Even ones take any six of its words, head
        terms included (long postings); odd ones its six rarest, which
        whole segments lack (can-match pruning)."""
        df = np.diff(self.term_start)
        out = []
        while len(out) < n:
            i = int(rng.integers(self.n_docs))
            words = np.unique(self.tokens[self.starts[i]:
                                          self.starts[i + 1]])
            if len(words) < QUERY_TERMS:
                continue
            if len(out) % 2:
                pick = words[np.argsort(df[words],
                                        kind="stable")[:QUERY_TERMS]]
            else:
                pick = rng.choice(words, size=QUERY_TERMS, replace=False)
            out.append(sorted(int(t) for t in pick))
        return out

    def body(self, terms: list) -> dict:
        return {"query": {"match": {
            "body": " ".join(f"t{t}" for t in terms)}}, "size": TOP_K}

    def bm25(self, terms: list) -> np.ndarray:
        """Dense float64 BM25 scores, shard-wide idf/avgdl — the
        formulation of ``bench.py::numpy_bm25_baseline``."""
        scores = np.zeros(self.n_docs)
        avgdl = self.lens.mean()
        for t in terms:
            a, b = self.term_start[t], self.term_start[t + 1]
            docs, tf = self.p_doc[a:b], self.p_tf[a:b]
            idf = np.log(1.0 + (self.n_docs - (b - a) + 0.5)
                         / ((b - a) + 0.5))
            norm = K1 * (1.0 - B + B * self.lens[docs] / avgdl)
            scores[docs] += idf * tf / (tf + norm)
        return scores


# -- result comparison --------------------------------------------------------

def hit_rows(resp: dict) -> list:
    return [(h["_id"], float(h["_score"])) for h in resp["hits"]["hits"]]


def check_against_scores(rows: list, ref: np.ndarray, rtol: float,
                         what: str) -> None:
    """``rows`` is a correct top-k of the dense reference ``ref``
    (indexed by int(_id), higher is better, 0 = no match), tie-aware: every returned
    score matches its reference, the list is sorted, and nothing left
    out beats the worst one kept by more than the tolerance."""
    k = min(TOP_K, int((ref > 0).sum()))
    require(len(rows) == k, f"{what}: {len(rows)} hits, expected {k}")
    ids = np.array([int(i) for i, _ in rows])
    got = np.array([s for _, s in rows])
    require(len(set(ids.tolist())) == k, f"{what}: duplicate hits {rows}")
    require(np.allclose(got, ref[ids], rtol=rtol, atol=0.0),
            f"{what}: scores {got} differ from reference {ref[ids]}")
    require((np.diff(got) <= 0).all(), f"{what}: hits not sorted: {got}")
    rest = ref.copy()
    rest[ids] = -np.inf
    runner_up = rest.max()
    require(runner_up <= ref[ids].min() * (1 + rtol) + 1e-30,
            f"{what}: doc {int(rest.argmax())} scores {runner_up} but the "
            f"worst returned hit scores {ref[ids].min()}")


def same_ranking(a: list, b: list, rtol: float) -> bool:
    """Two top-k lists agree up to ties: equal scores rank by rank, and
    equal ids except inside a run of tied scores (the run that reaches
    the cutoff may hold different members on each side)."""
    if len(a) != len(b):
        return False
    sa = np.array([s for _, s in a])
    sb = np.array([s for _, s in b])
    if not np.allclose(sa, sb, rtol=rtol, atol=0.0):
        return False
    start = 0
    for end in range(1, len(a) + 1):
        if end < len(a) and abs(sa[end] - sa[start]) <= rtol * abs(sa[start]):
            continue
        ids_a = {i for i, _ in a[start:end]}
        ids_b = {i for i, _ in b[start:end]}
        if ids_a != ids_b and end < len(a):
            return False
        start = end
    return True


def live_bytes(devices: list) -> list:
    """Bytes of live jax arrays held by each device — where the program
    PLACED things, on any backend."""
    import jax

    held = dict.fromkeys(devices, 0)
    for arr in jax.live_arrays():
        for shard in arr.addressable_shards:
            if shard.device in held:
                held[shard.device] += shard.data.nbytes
    return [held[d] for d in devices]


# -- the run ------------------------------------------------------------------

class Smoke:
    def __init__(self, node, client, sizes: Sizes, platform: str):
        self.node = node
        self.client = client
        self.sizes = sizes
        self.platform = platform
        self.loaded: dict = {}
        self.asked_fallbacks = 0     # host answers ``degraded`` asked for

    # .. REST helpers ..

    def device_stats(self) -> dict:
        nodes = self.client.nodes.stats()["nodes"]
        require(len(nodes) == 1, f"expected one node, got {list(nodes)}")
        return next(iter(nodes.values()))

    def search(self, index: str, body: dict) -> dict:
        return self.checked(self.client.search(index=index, body=body))

    @staticmethod
    def checked(resp: dict) -> dict:
        require(resp["_shards"]["failed"] == 0 and not resp["timed_out"],
                f"response degraded: _shards={resp['_shards']} "
                f"timed_out={resp['timed_out']}")
        return resp

    def assert_device_clean(self, where: str) -> dict:
        """No device fault was answered from the host so far.  Called
        after every phase so a refused kernel stops the run at the phase
        that hit it, with the breaker's own ``last_error``."""
        dev = self.device_stats()["device"]
        bad = []
        for kind, b in dev["health"]["breakers"].items():
            if b["failures"] or b["trips"]:
                bad.append(f"breaker [{kind}]: failures={b['failures']} "
                           f"trips={b['trips']} "
                           f"last_error={b.get('last_error')}")
        if dev["health"]["poisoned_results"]:
            bad.append(f"poisoned_results="
                       f"{dev['health']['poisoned_results']}")
        if dev["budget"]["host_fallbacks"] != self.asked_fallbacks:
            bad.append(f"host_fallbacks={dev['budget']['host_fallbacks']}"
                       f" (asked for: {self.asked_fallbacks})")
        if dev["backend"].get("platform") != self.platform:
            bad.append(f"backend={dev['backend']}")
        for line in bad:
            say(f"DEVICE VIOLATION after {where}: {line}")
        require(not bad, f"the device did not do the work ({where}): {bad}")
        return dev

    # .. load ..

    def bulk_load(self, index: str, n_docs: int, source, *,
                  refresh_every: int, settings: dict, properties: dict):
        t0 = time.monotonic()
        self.client.indices.create(index, {
            "settings": {"number_of_replicas": 0, **settings},
            "mappings": {"properties": properties}})
        chunk = self.sizes.bulk_chunk
        for start in range(0, n_docs, chunk):
            lines = []
            for i in range(start, min(start + chunk, n_docs)):
                lines.append(f'{{"index":{{"_id":"{i}"}}}}')
                lines.append(json.dumps(source(i), separators=(",", ":")))
            resp = self.client.bulk("\n".join(lines) + "\n", index=index)
            require(not resp["errors"], f"_bulk into {index} had errors")
            done = min(start + chunk, n_docs)
            if done % refresh_every == 0 or done == n_docs:
                self.client.indices.refresh(index)
        stats = self.client.transport.perform_request(
            "GET", f"/{index}/_stats")["indices"][index]["total"]
        docs, segments = stats["docs"]["count"], stats["segments"]["count"]
        require(docs == n_docs, f"{index}: {docs} docs, loaded {n_docs}")
        dt = time.monotonic() - t0
        self.loaded[index] = {"docs": docs, "segments": segments,
                              "load_s": round(dt, 1)}
        say(f"loaded {index}: {docs} docs in {segments} segment(s), "
            f"{dt:.1f}s ({docs / dt:.0f} docs/s, refresh included)")

    TEXT_PROPS = {"body": {"type": "text"}, "ts": {"type": "date"},
                  "tag": {"type": "keyword"}, "v": {"type": "long"}}

    def load_text(self, index: str, corpus: TextCorpus, *,
                  refresh_every: int, shards: int = 1, extra=None):
        self.bulk_load(index, corpus.n_docs, corpus.source,
                       refresh_every=refresh_every,
                       settings={"number_of_shards": shards,
                                 **(extra or {})},
                       properties=self.TEXT_PROPS)

    # .. lexical ..

    def sequential(self, index: str, corpus: TextCorpus,
                   queries: list) -> list:
        """The sequential queries, the first one profiled; returns each
        query's (id, score) rows."""
        out = []
        for qi, terms in enumerate(queries):
            body = corpus.body(terms)
            if qi == 0:
                body["profile"] = True
            resp = self.search(index, body)
            if qi == 0:
                engine = resp["profile"]["shards"][0]["engine"]
                require(engine["execution_path"] == "device",
                        f"{index}: profiled request ran on "
                        f"[{engine['execution_path']}]: {engine}")
            out.append(hit_rows(resp))
        return out

    def degraded(self, index: str, corpus: TextCorpus,
                 queries: list) -> list:
        """The same requests through the product's own degradation
        route: with the ``dispatch`` breaker open every segment is
        recovered by the host impact-table scorer.  Each response must
        say so, and the ledger must count the fallbacks; the breaker's
        books are put back, and ``assert_device_clean`` expects the
        fallbacks asked for here from now on."""
        from opensearch_tpu.common.device_health import device_health

        health = device_health()
        saved = (health.enabled, health.failure_threshold,
                 health.open_interval_s)
        before = self.device_stats()["device"]["budget"]["host_fallbacks"]
        health.set_failure_threshold(1)
        health.set_open_interval_s(3600.0)
        health.record_failure("dispatch", RuntimeError(
            "chip_smoke: breaker opened for the host ranking"))
        try:
            out = []
            for terms in queries:
                resp = self.search(index,
                                   {**corpus.body(terms), "profile": True})
                engine = resp["profile"]["shards"][0]["engine"]
                require(engine["execution_path"] == "host",
                        f"{index}: under an open breaker a request ran "
                        f"on [{engine['execution_path']}]: {engine}")
                out.append(hit_rows(resp))
        finally:
            health.reset()
            health.enabled, health.failure_threshold, \
                health.open_interval_s = saved
        moved = (self.device_stats()["device"]["budget"]["host_fallbacks"]
                 - before)
        require(moved >= len(queries),
                f"{index}: {len(queries)} degraded requests counted "
                f"{moved} host fallbacks")
        self.asked_fallbacks += moved
        return out

    def msearch(self, index: str, corpus: TextCorpus, queries: list) -> list:
        lines = []
        for terms in queries:
            lines += [{}, corpus.body(terms)]
        resp = self.client.msearch(lines, index=index)
        require(len(resp["responses"]) == len(queries),
                f"{index}: _msearch answered {len(resp['responses'])} of "
                f"{len(queries)}")
        return [hit_rows(self.checked(r)) for r in resp["responses"]]

    def lexical_f32(self, corpus: TextCorpus, queries: list) -> dict:
        s = self.sizes
        seq = self.sequential("smoke_f32", corpus, queries[:s.seq_queries])
        refs = [corpus.bm25(t) for t in queries]
        for qi, rows in enumerate(seq):
            check_against_scores(rows, refs[qi], RTOL_SCORE,
                                 f"smoke_f32 query {qi}")
        pruned = self.device_stats()["telemetry"]["counters"].get(
            "search.segments_pruned", 0)
        require(pruned > 0, "can-match pruned no segment of smoke_f32")
        self.assert_device_clean("smoke_f32 sequential")
        ms = self.msearch("smoke_f32", corpus, queries)
        for qi, rows in enumerate(ms):
            check_against_scores(rows, refs[qi], RTOL_SCORE,
                                 f"smoke_f32 _msearch member {qi}")
        self.require_parity(ms[:len(seq)], seq,
                            "smoke_f32 _msearch members vs sequential")
        self.assert_device_clean("smoke_f32 _msearch")
        identical = sum(ms[qi] == seq[qi] for qi in range(len(seq)))
        say(f"smoke_f32: {len(seq)} sequential + {len(ms)}-member _msearch "
            f"== numpy BM25 (tie-aware, rtol {RTOL_SCORE}); msearch==seq "
            f"bit-identical {identical}/{len(seq)}; "
            f"segments_pruned={pruned}")
        return {"correct": True, "msearch_bit_identical": identical,
                "segments_pruned": pruned}

    def lexical_quant(self, corpus: TextCorpus, queries: list) -> tuple:
        """Device answers against the host path's; returns the result and
        the host rows, which the half-budget step compares with again."""
        s = self.sizes
        seq_q = queries[:s.seq_queries]
        device = self.sequential("smoke_quant", corpus, seq_q)
        dev = self.assert_device_clean("smoke_quant sequential")
        pager = dev["pager"]
        require(pager["resident_entries"] > 0
                and pager["hits"] + pager["misses"] > 0,
                f"smoke_quant did not take the quantized lowering: "
                f"pager={pager}")
        # the same requests recovered on the host over the same .quant
        # tables
        host = self.degraded("smoke_quant", corpus, seq_q)
        self.assert_device_clean("smoke_quant degraded")
        self.require_parity(device, host, "smoke_quant device vs host")
        f32_share = np.mean([
            [i for i, _ in device[qi]] == [str(d) for d in np.argsort(
                -corpus.bm25(terms), kind="stable")[:TOP_K]]
            for qi, terms in enumerate(seq_q)])
        identical = sum(device[qi] == host[qi] for qi in range(len(seq_q)))
        say(f"smoke_quant: device == host over the same .quant tables "
            f"({len(seq_q)} queries, bit-identical {identical}); share "
            f"equal to the f32 numpy ranking {f32_share:.3f} (printed, "
            f"not asserted)")
        return {"correct": True, "device_host_bit_identical": identical,
                "f32_ranking_share": round(float(f32_share), 3)}, host

    def msearch_quant(self, corpus: TextCorpus, queries: list) -> dict:
        """The batched union kernel stays on the f32 lowering (a
        quantized segment demand-stages its full posting columns for
        it), so its members answer to the f32 reference, not to the
        quantized rows."""
        ms = self.msearch("smoke_quant", corpus, queries)
        for qi, rows in enumerate(ms):
            check_against_scores(rows, corpus.bm25(queries[qi]),
                                 RTOL_SCORE,
                                 f"smoke_quant _msearch member {qi}")
        self.assert_device_clean("smoke_quant _msearch")
        say(f"smoke_quant: {len(ms)}-member _msearch == numpy BM25")
        return {"correct": True}

    @staticmethod
    def require_parity(got: list, want: list, what: str) -> None:
        bad = [qi for qi, (g, w) in enumerate(zip(got, want))
               if not same_ranking(g, w, RTOL_PARITY)]
        require(not bad, f"{what}: rankings differ at queries {bad}: "
                f"{[(got[i], want[i]) for i in bad[:2]]}")

    def half_budget(self, corpus: TextCorpus, queries: list,
                    host: list) -> dict:
        """smoke_quant again with ``device.memory.budget_bytes`` at half
        of what is resident: still the device's answers, now through a
        pager that has to miss.  The budget is device-wide and evicts
        pages first, then the least recently dispatched segments — an
        evicted segment scores on the host, by design — so this runs
        while smoke_quant is the most recent, before its _msearch pulls
        the full f32 postings in beside the compressed pages."""
        before = self.device_stats()["device"]
        budget = before["resident_bytes"] // 2
        self.client.cluster.put_settings(
            {"transient": {"device.memory.budget_bytes": budget}})
        try:
            device = self.sequential(
                "smoke_quant", corpus, queries[:self.sizes.seq_queries])
            after = self.assert_device_clean("smoke_quant at half budget")
        finally:
            self.client.cluster.put_settings(
                {"transient": {"device.memory.budget_bytes": None}})
        self.require_parity(device, host, "smoke_quant at half budget")
        require(after["budget"]["budget_bytes"] == budget,
                f"budget not applied: {after['budget']}")
        pager = {k: after["pager"][k] - before["pager"][k]
                 for k in ("evictions", "misses", "prefetches", "hits")}
        # the budget evicted the compressed pages and they came back: on
        # demand (a miss) or, where they fit the free pages, ahead of it
        # (the prefetch oracle) — one quantized segment always fits
        require(pager["evictions"] > 0
                and pager["misses"] + pager["prefetches"] > 0,
                f"the pager did no work at half budget: {after['pager']}")
        say(f"half budget: {budget} of {before['resident_bytes']} resident "
            f"bytes; ledger evictions="
            f"{after['budget']['evictions'] - before['budget']['evictions']}"
            f", pager {pager}, host_fallbacks=0; device == host")
        return {"correct": True, "budget_bytes": budget, "pager": pager}

    # .. aggregations ..

    def aggs(self, corpus: TextCorpus) -> dict:
        resp = self.search("smoke_f32", {"size": 0, "aggs": {
            "tags": {"terms": {"field": "tag", "size": N_TAGS}},
            "per_day": {"date_histogram": {"field": "ts",
                                           "fixed_interval": "1d"}}}})
        require(resp["hits"]["total"]["value"] == corpus.n_docs,
                f"aggs request matched {resp['hits']['total']}")
        got_tags = {b["key"]: b["doc_count"]
                    for b in resp["aggregations"]["tags"]["buckets"]}
        want_tags = {f"tag{t}": int(c) for t, c in
                     enumerate(np.bincount(corpus.tags)) if c}
        require(got_tags == want_tags,
                f"terms(tag): {got_tags} != numpy {want_tags}")
        day = 86_400_000
        got_days = {int(b["key"]): b["doc_count"] for b in
                    resp["aggregations"]["per_day"]["buckets"]
                    if b["doc_count"]}
        keys, counts = np.unique(corpus.ts // day * day, return_counts=True)
        want_days = dict(zip(keys.tolist(), counts.tolist()))
        require(got_days == want_days,
                f"date_histogram(ts): {got_days} != numpy {want_days}")
        self.assert_device_clean("aggs")
        say(f"aggs: terms(tag) {len(got_tags)} buckets + "
            f"date_histogram(ts) {len(got_days)} buckets == numpy exactly")
        return {"correct": True, "buckets": len(got_tags) + len(got_days)}

    # .. kNN ..

    def knn(self, rng: np.random.Generator) -> dict:
        s = self.sizes

        def draw(shape):
            # SIFT's range on a 1/64 grid: exact in float32 and in short
            # decimal JSON, but 14 bits wide — integers 0-255 would
            # survive a bf16 matmul pass, these do not
            return (rng.integers(0, 255 * 64, size=shape) / 64.0).astype(
                np.float32)

        vectors = draw((s.vectors, s.dim))
        self.bulk_load(
            "smoke_sift", s.vectors,
            lambda i: {"vec": vectors[i].tolist()},
            refresh_every=s.vectors, settings={"number_of_shards": 1},
            properties={"vec": {"type": "knn_vector", "dimension": s.dim,
                                "method": {"name": "exact",
                                           "space_type": "l2"}}})
        recalls = []
        for qi in range(s.knn_queries):
            q = draw(s.dim)
            resp = self.search("smoke_sift", {"size": TOP_K, "query": {
                "knn": {"vec": {"vector": q.tolist(), "k": TOP_K}}}})
            d2 = ((vectors - q) ** 2).sum(axis=1, dtype=np.float32)
            # as a dense "higher is better" reference: the plugin's l2
            # score translation, which the tie-aware check also verifies
            check_against_scores(hit_rows(resp), 1.0 / (1.0 + d2),
                                 RTOL_SCORE, f"knn query {qi}")
            want = set(np.argsort(d2, kind="stable")[:TOP_K].tolist())
            got = {int(h["_id"]) for h in resp["hits"]["hits"]}
            recalls.append(len(got & want) / TOP_K)
        self.assert_device_clean("knn")
        recall = float(np.mean(recalls))
        require(recall == 1.0, f"kNN recall@{TOP_K} = {recall} on the "
                f"EXACT path (per query: {recalls})")
        say(f"knn: {s.knn_queries} queries over {s.vectors} x {s.dim} "
            f"non-integer vectors, recall@{TOP_K} = {recall}")
        return {"correct": True, "recall_at_10": recall}

    def pallas(self, rng: np.random.Generator) -> dict:
        """The one kernel that had only ever run interpreted: compiled
        everywhere but on the CPU backend."""
        import jax.numpy as jnp

        from opensearch_tpu.ops.pallas_knn import knn_scores_pallas

        s = self.sizes
        vectors = rng.normal(size=(s.pallas_rows, s.dim)).astype(np.float32)
        valid = rng.random(s.pallas_rows) > 0.2
        q = rng.normal(size=s.dim).astype(np.float32)
        dots = vectors @ q
        v2 = (vectors * vectors).sum(axis=1)
        q2 = np.float32(q @ q)
        refs = {
            "l2": 1.0 / (1.0 + np.maximum(v2 - 2.0 * dots + q2, 0.0)),
            "cosinesimil": (1.0 + dots / np.maximum(
                np.sqrt(v2) * np.sqrt(q2), 1e-30)) / 2.0,
            "innerproduct": np.where(dots >= 0, dots + 1.0,
                                     1.0 / (1.0 - dots)),
        }
        dv, dvalid, dq = (jnp.asarray(vectors), jnp.asarray(valid),
                          jnp.asarray(q))
        out = {}
        for space, ref in refs.items():
            t0 = time.monotonic()
            got = np.asarray(knn_scores_pallas(
                dv, dvalid, dq, space=space,
                interpret=self.platform == "cpu"))
            require(got.shape == (s.pallas_rows,)
                    and np.isneginf(got[~valid]).all(),
                    f"pallas {space}: masked rows are not -inf")
            require(np.allclose(got[valid], ref[valid], rtol=1e-5, atol=0.0),
                    f"pallas {space}: max relative error "
                    f"{np.max(np.abs(got[valid] / ref[valid] - 1.0))}")
            out[space] = round(time.monotonic() - t0, 2)
        say(f"pallas knn_scores_pallas[{s.pallas_rows}, {s.dim}] "
            f"{'interpreted' if self.platform == 'cpu' else 'compiled'}, "
            f"3 spaces == float32 numpy (rtol 1e-5); first-call s: {out}")
        return {"correct": True, "first_call_s": out}

    # .. four chips ..

    def mesh(self, corpus: TextCorpus, queries: list, n_devices: int) -> dict:
        """The cross-shard merge over ICI, seen on real devices: hits
        equal the host scatter's, nothing fell back, and every one of
        the mesh's devices holds the shard staged onto it."""
        import jax

        import __graft_entry__ as graft

        devices = jax.devices()[:n_devices]
        self.load_text("smoke_mesh", corpus, refresh_every=corpus.n_docs,
                       shards=n_devices, extra={"search.mesh": True})
        svc = self.node.indices.get("smoke_mesh")
        held0 = live_bytes(devices)
        fallback0 = self.device_stats()["telemetry"]["counters"].get(
            "search.mesh.fallback", 0)
        for qi, terms in enumerate(queries[:self.sizes.seq_queries]):
            body = corpus.body(terms)
            require(svc._use_mesh(body), "smoke_mesh does not route to "
                    "the mesh")
            got = hit_rows(self.search("smoke_mesh", body))
            want = hit_rows(svc._host_scatter_search(dict(body)))
            require(same_ranking(got, want, RTOL_PARITY),
                    f"smoke_mesh query {qi}: mesh {got} != host scatter "
                    f"{want}")
        agg_body = {"size": 0, "aggs": {
            "v_sum": {"sum": {"field": "v"}},
            "v_min": {"min": {"field": "v"}},
            "v_max": {"max": {"field": "v"}},
            "v_avg": {"avg": {"field": "v"}}}}
        got = self.search("smoke_mesh", agg_body)["aggregations"]
        n = corpus.n_docs
        want = {"v_sum": n * (n - 1) / 2, "v_min": 0.0, "v_max": n - 1.0,
                "v_avg": (n - 1) / 2}
        require({k: v["value"] for k, v in got.items()} == want,
                f"mesh metric aggs {got} != {want}")
        dev = self.assert_device_clean("smoke_mesh")
        fallback = self.device_stats()["telemetry"]["counters"].get(
            "search.mesh.fallback", 0) - fallback0
        require(fallback == 0, f"search.mesh.fallback rose by {fallback}")
        mesh_breaker = dev["health"]["breakers"]["mesh"]
        require(mesh_breaker["successes"] > 0,
                f"no mesh collective ran: {mesh_breaker}")
        held1 = live_bytes(devices)
        say(f"mesh: live array bytes per device before {held0} after "
            f"{held1} (the single-node indexes live on device 0); "
            f"allocator bytes_in_use "
            f"{[(d.memory_stats() or {}).get('bytes_in_use') for d in devices]}")
        require(all(b > a for a, b in zip(held0, held1)),
                "a mesh device holds nothing new: everything was placed "
                "elsewhere")
        graft.sharded_step_check(n_devices)
        say(f"mesh: {self.sizes.seq_queries} queries + metric aggs over "
            f"{n_devices} devices == host scatter; fallback=0")
        return {"correct": True, "devices": n_devices,
                "live_bytes_per_device": held1}


def run_smoke(sizes: Sizes, seed: int, platform: str,
              device_count: int) -> dict:
    """Every step of the smoke at ``sizes`` against a node started in
    this process; raises ``SmokeFailure`` on the first check that does
    not hold.  ``platform`` is what jax reported: the node's own
    ``_nodes/stats`` must agree with it."""
    from opensearch_tpu.client import OpenSearch
    from opensearch_tpu.node import Node

    rng = np.random.default_rng(seed)
    t_start = time.monotonic()
    results: dict = {}
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as data_path:
        node = Node(data_path, host="127.0.0.1", port=0).start()
        try:
            client = OpenSearch([f"http://127.0.0.1:{node.port}"],
                                timeout=900.0)
            smoke = Smoke(node, client, sizes, platform)
            f32 = TextCorpus(rng, sizes.f32_docs, sizes.vocab)
            quant = TextCorpus(rng, sizes.quant_docs, sizes.vocab)
            say(f"corpora: smoke_f32 {f32.n_docs} docs / "
                f"{f32.distinct_terms} distinct terms, smoke_quant "
                f"{quant.n_docs} docs / {quant.distinct_terms} distinct "
                f"terms (vocabulary {sizes.vocab}, seed {seed})")
            smoke.load_text("smoke_f32", f32,
                            refresh_every=sizes.f32_refresh_every)
            smoke.load_text("smoke_quant", quant,
                            refresh_every=quant.n_docs)
            f32_q = f32.queries(rng, sizes.msearch_queries)
            quant_q = quant.queries(rng, sizes.msearch_queries)
            results["smoke_f32"] = smoke.lexical_f32(f32, f32_q)
            results["smoke_quant"], host = smoke.lexical_quant(quant,
                                                               quant_q)
            results["half_budget"] = smoke.half_budget(quant, quant_q, host)
            results["smoke_quant_msearch"] = smoke.msearch_quant(
                quant, quant_q)
            results["aggs"] = smoke.aggs(f32)
            results["knn"] = smoke.knn(rng)
            results["pallas"] = smoke.pallas(rng)
            if device_count >= 4:
                results["mesh"] = smoke.mesh(f32, f32_q, 4)
            else:
                results["mesh"] = f"not_run_{device_count}_device"
            dev = smoke.assert_device_clean("the whole run")
            kernels = dev["compile_registry"]["kernels"]
            require(dev["dispatches"] > 0, f"no device dispatch: {dev}")
            for name in ("plan.run_topk", "batch.batch_impact_union_topk"):
                require(kernels.get(name, 0) >= 1,
                        f"[{name}] compiled no program: {kernels}")
            results["device"] = {
                "backend": dev["backend"], "dispatches": dev["dispatches"],
                "programs": kernels,
                "host_fallbacks": (dev["budget"]["host_fallbacks"]
                                   - smoke.asked_fallbacks),
                "asked_fallbacks": smoke.asked_fallbacks,
                "breakers": {k: {"failures": b["failures"],
                                 "trips": b["trips"]}
                             for k, b in dev["health"]["breakers"].items()},
                "poisoned_results": dev["health"]["poisoned_results"]}
            results["loaded"] = smoke.loaded
        finally:
            node.stop()
    results["wall_s"] = round(time.monotonic() - t_start, 1)
    return results


class CompileClock:
    """Seconds jax spent getting executables (compiling, or loading them
    from the persistent cache), and how many it got."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring
        self.seconds = 0.0
        self.programs = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if event == self.EVENT:
            self.seconds += duration
            self.programs += 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=20260926,
                    help="seed of every generated document and query")
    args = ap.parse_args(argv)

    import opensearch_tpu.common.jaxenv  # noqa: F401 — x64 + compile cache
    import jax
    import jaxlib

    platform = jax.default_backend()
    devices = jax.devices()
    cache_dir = jax.config.jax_compilation_cache_dir
    say(f"platform: {platform}, device_kind: {devices[0].device_kind}, "
        f"devices: {len(devices)}, jax {jax.__version__}, jaxlib "
        f"{jaxlib.__version__}, libtpu {version('libtpu')}, "
        f"compile cache: {cache_dir}")
    if platform != "tpu":
        say("no TPU: this smoke proves the chip path and runs nowhere else")
        return 2
    cache_files0 = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) \
        else 0
    clock = CompileClock()
    results = run_smoke(Sizes(), args.seed, platform, len(devices))
    cache_files1 = len(os.listdir(cache_dir))
    results["compile"] = {
        "seconds": round(clock.seconds, 1), "programs": clock.programs,
        "cache_dir": cache_dir, "cache_files_before": cache_files0,
        "cache_files_after": cache_files1}
    results["platform"] = platform
    results["device_kind"] = devices[0].device_kind
    say("summary: " + json.dumps(results, sort_keys=True))
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind, "count": len(devices)}}),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
