"""Configuration kind ``sparse_features``: one shard whose every segment
holds a ``rank_features`` field of learned-sparse passage expansions
(token -> weight, as a SPLADE encoder writes them), asked ``neural_sparse``
queries that carry their own ``query_tokens``.  Numpy only above
``install``.

A passage is a bag of distinct wordpieces from a Zipf-Mandelbrot
vocabulary (p(r) ~ 1 / (r + 70): a flat head of expansion tokens that lie
on a third of the passages, a long tail) with a weight each: ``log1p`` of
a positive draw, cut to FeatureField's 9 significant bits, which is what
the index stores.  The passages are made passage-major (a row's tokens
ascending, a weight beside each: what the plain reference reads) and
inverted here into the token-major columns the index holds (what the
program reads).  A query takes its tokens from one passage's own, two
thirds its heaviest and one third any, with float32 weights of its own,
so it has a best answer and postings as long as an encoder's.
"""

from __future__ import annotations

import dataclasses
import math
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmarks.kinds.text_bm25 import (BUCKET_MIN, BUCKET_STEP, bucket,
                                        t_pad)

FIELD = "expansion"
ZIPF_OFFSET = 70                 # p(r) ~ 1 / (r + ZIPF_OFFSET), whole
SIZE_MEAN, SIZE_SIGMA = 246.0, 0.45      # lognormal draws a passage
WEIGHT_SCALE = 1.5               # weight = log1p(WEIGHT_SCALE * Exp(1))
DRAW_MIN, DRAW_MAX = 1e-3, 32.0  # the draw's cut: weights in (0, 3.5]
HEAVY_SHARE = (2, 3)             # of a query's tokens, its source's heaviest


def token_name(token: int) -> str:
    return f"w{int(token)}"


def stored(weights: np.ndarray) -> np.ndarray:
    """float32 as Lucene's FeatureField keeps it: the low 15 bits
    cleared (``floatToIntBits(v) >>> 15``)."""
    bits = np.ascontiguousarray(weights, dtype=np.float32).view(np.uint32)
    return ((bits >> np.uint32(15)) << np.uint32(15)).view(np.float32)


def draw_weights(rng, n: int) -> np.ndarray:
    """``n`` float32 weights in (0, 3.5], off the grid."""
    x = rng.standard_exponential(n, dtype=np.float32)
    x *= np.float32(WEIGHT_SCALE)
    np.clip(x, DRAW_MIN, DRAW_MAX, out=x)
    return np.log1p(x, out=x)


@dataclasses.dataclass
class SegmentFeatures:
    lo: int                      # first shard-wide passage number
    n_docs: int
    # passage-major: what the seed says, and what the reference reads
    row_starts: np.ndarray       # int64 [n_docs + 1], into row_tokens
    row_tokens: np.ndarray       # uint16 [postings], a row's ascending
    row_weights: np.ndarray      # float32 [postings], on the 9-bit grid
    # token-major: what the index holds
    df: np.ndarray               # int32 [vocab]
    offsets: np.ndarray          # int32 [vocab + 1], into doc_ids
    doc_ids: np.ndarray          # int32 [postings], segment-local
    weights: np.ndarray          # float32 [postings]


@dataclasses.dataclass
class SparseData:
    n_docs: int
    vocab: int
    segments: list
    df: np.ndarray               # int64 [vocab], shard-wide

    def row(self, passage: int) -> tuple:
        """(tokens, weights) of shard-wide ``passage``."""
        sd = self.segments[passage // self.segments[0].n_docs]
        a, b = sd.row_starts[passage - sd.lo: passage - sd.lo + 2]
        return sd.row_tokens[a:b], sd.row_weights[a:b]


def _segment(seed_seq, lo: int, n: int, vocab: int, len_lo: int,
             len_hi: int) -> SegmentFeatures:
    rng = np.random.default_rng(seed_seq)
    mu = math.log(SIZE_MEAN) - SIZE_SIGMA ** 2 / 2
    sizes = np.clip(np.rint(rng.lognormal(mu, SIZE_SIGMA, size=n)),
                    len_lo, len_hi).astype(np.int64)
    # the continuous 1 / (x + offset) law, inverted and floored
    u = rng.random(int(sizes.sum()), dtype=np.float32)
    u *= np.float32(math.log((vocab + 1 + ZIPF_OFFSET)
                             / (1 + ZIPF_OFFSET)))
    np.exp(u, out=u)
    u *= np.float32(1 + ZIPF_OFFSET)
    key = np.clip(u.astype(np.int64) - (ZIPF_OFFSET + 1), 0, vocab - 1)
    del u
    key += np.repeat(np.arange(n, dtype=np.int64) * vocab, sizes)
    key = np.unique(key)                       # by passage, then by token
    row_of = (key // vocab).astype(np.int32)
    row_tokens = (key % vocab).astype(np.uint16)
    del key
    row_starts = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(np.bincount(row_of, minlength=n), out=row_starts[1:])
    row_weights = stored(draw_weights(rng, len(row_tokens)))
    by_token = np.argsort(row_tokens, kind="stable")   # passages ascending
    df = np.bincount(row_tokens, minlength=vocab).astype(np.int32)
    offsets = np.zeros(vocab + 1, dtype=np.int32)
    np.cumsum(df, out=offsets[1:])
    return SegmentFeatures(
        lo=lo, n_docs=n, row_starts=row_starts, row_tokens=row_tokens,
        row_weights=row_weights, df=df, offsets=offsets,
        doc_ids=np.take(row_of, by_token),
        weights=np.take(row_weights, by_token))


def generate(cfg: dict, seed: int) -> SparseData:
    n_docs, n_seg, vocab = cfg["n_docs"], cfg["segments"], cfg["vocab"]
    if n_docs % n_seg:
        raise ValueError("segments must divide n_docs: equal segments "
                         "share one set of compiled programs")
    if vocab > np.iinfo(np.uint16).max:
        raise ValueError("row_tokens holds a token in 16 bits")
    per = n_docs // n_seg
    len_lo, len_hi = cfg["passage_tokens"]
    seqs = np.random.SeedSequence([int(seed), 1]).spawn(n_seg)
    with ThreadPoolExecutor(max_workers=min(n_seg, 8)) as pool:
        segs = list(pool.map(
            lambda i: _segment(seqs[i], i * per, per, vocab, len_lo,
                               len_hi), range(n_seg)))
    return SparseData(
        n_docs=n_docs, vocab=vocab, segments=segs,
        df=np.sum([s.df.astype(np.int64) for s in segs], axis=0))


def index_body(cfg: dict) -> dict:
    return {"settings": {"number_of_shards": 1, "number_of_replicas": 0},
            "mappings": {"properties": {FIELD: {"type": "rank_features"}}}}


def install(node, index: str, cfg: dict, data: SparseData) -> None:
    """Each segment's token-major columns as a ``Segment`` whose
    ``rank_features`` postings carry the stored weight as their value
    column, adopted through the engine's segment-copy path (``_bulk``
    parses ~600 documents a second); then the configuration's programs
    compiled side by side."""
    from opensearch_tpu.index.segment import PostingsField, Segment

    names = [token_name(t) for t in range(data.vocab)]
    segments, live = {}, {}
    for si, sd in enumerate(data.segments):
        n = sd.n_docs
        seg = Segment(f"bench_{si}", n)
        seg.doc_ids = [str(i) for i in range(sd.lo, sd.lo + n)]
        seg.id_to_local = {d: i for i, d in enumerate(seg.doc_ids)}
        seg.sources = [b"{}"] * n
        seg.postings[FIELD] = PostingsField(
            terms={names[t]: t for t in np.flatnonzero(sd.df).tolist()},
            df=sd.df, offsets=sd.offsets, doc_ids=sd.doc_ids,
            tfs=sd.weights, pos_offsets=np.zeros(1, dtype=np.int32),
            positions=np.zeros(0, dtype=np.int32),
            doc_lens=np.ones(n, dtype=np.float32), total_len=float(n),
            docs_with_field=n, has_norms=False,
            present=np.ones(n, dtype=bool), features=True)
        segments[seg.seg_id] = seg
        live[seg.seg_id] = np.ones(n, dtype=bool).tobytes()
    ckpt = {"segments": list(segments), "live": live,
            "max_seq_no": data.n_docs - 1, "primary_term": 1}
    node.indices.get(index).engine_for(0).install_remote_checkpoint(
        ckpt, segments)
    compile_side_by_side(node, index, cfg, data)


def compile_side_by_side(node, index: str, cfg: dict,
                         data: SparseData) -> None:
    """``knn_filtered``'s ordering, for its reasons: the first crafted
    request alone, because the first request to reach a segment stages it
    and nothing keeps two first requests from each staging a copy; then
    the other crafted requests at the same time, so that their programs
    compile side by side.  The harness's own pass then finds them
    compiled."""
    from opensearch_tpu.client import OpenSearch

    client = OpenSearch([f"http://127.0.0.1:{node.port}"], timeout=900.0)
    client.indices.refresh(index)
    bodies = [body(cfg, q) for _sig, q in warmup_queries(cfg, data)]

    def send(b: dict) -> None:
        resp = client.search(index=index, body=b)
        if resp.get("_shards", {}).get("failed", 1) or resp.get("timed_out"):
            raise RuntimeError(f"set-up request degraded: {resp}")

    send(bodies[0])
    if len(bodies) > 1:
        with ThreadPoolExecutor(max_workers=len(bodies) - 1) as pool:
            list(pool.map(send, bodies[1:]))


# -- queries ----------------------------------------------------------------

def query_lengths(cfg: dict, n: int) -> np.ndarray:
    """The fixed multiset of query lengths: ``lo`` to ``hi`` tokens along
    a Beta(2, 3), whose mean is two fifths of the way (8 to 48: mean 24).
    Every seed sends the same multiset in another order."""
    lo, hi = cfg["query_tokens"]
    x = np.linspace(0.0, 1.0, 4097)
    cdf = 6 * x ** 2 - 8 * x ** 3 + 3 * x ** 4         # Beta(2, 3)
    at = np.interp(np.arange(n) / max(n - 1, 1), cdf, x)   # both ends
    return (lo + np.rint((hi - lo) * at)).astype(np.int64)


def queries(cfg: dict, data: SparseData, seed: int) -> list:
    """``n_queries`` pairs (tokens, weights): tokens of one passage, the
    heaviest two thirds of the query by the passage's own weights and the
    rest any of its others, ascending; float32 weights of the query's
    own, off the grid."""
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 2]))
    lengths = rng.permutation(query_lengths(cfg, cfg["n_queries"]))
    out = []
    while len(out) < len(lengths):
        want = int(lengths[len(out)])
        tokens, weights = data.row(int(rng.integers(data.n_docs)))
        if len(tokens) < want:
            continue
        n_heavy = -(-want * HEAVY_SHARE[0] // HEAVY_SHARE[1])
        order = np.argsort(-weights, kind="stable")
        pick = np.concatenate([order[:n_heavy], rng.choice(
            order[n_heavy:], size=want - n_heavy, replace=False)])
        out.append((tuple(int(t) for t in np.sort(tokens[pick])),
                    draw_weights(rng, want)))
    return out


def body(cfg: dict, query: tuple) -> dict:
    tokens, weights = query
    return {"query": {"neural_sparse": {FIELD: {"query_tokens": {
        token_name(t): float(w) for t, w in zip(tokens, weights)}}}},
        "size": cfg["k"], "_source": False}


# -- the programs a cell can need -------------------------------------------

def signature(cfg: dict, data: SparseData, query: tuple, si: int):
    """(t_pad, bucket) of the query's term-bag program in segment ``si``,
    or None where none of its tokens occurs there (the program prunes
    such a segment).  ``t_pad`` counts the tokens the shard knows: the
    compiler drops the others before the bag is bound."""
    tokens = [t for t in query[0] if data.df[t]]
    budget = int(data.segments[si].df[tokens].sum())
    return (t_pad(len(tokens)), bucket(budget)) if budget else None


def program_space(cfg: dict) -> list:
    """Every (t_pad, bucket) a query of this configuration can produce:
    ``text_bm25``'s rule (a df cannot pass a segment's passages), a
    function of the file and never of the seed."""
    lo, hi = cfg["query_tokens"]
    per_seg = cfg["n_docs"] // cfg["segments"]
    out = []
    for tp in sorted({t_pad(n) for n in range(lo, hi + 1)}):
        most = min(tp, hi) * per_seg
        b = BUCKET_MIN
        while b == BUCKET_MIN or b // BUCKET_STEP < most:
            out.append((tp, b))
            b *= BUCKET_STEP
    return out


def warmup_queries(cfg: dict, data: SparseData) -> list:
    """One crafted query per (t_pad, bucket) of ``program_space``: tokens
    that are neighbours in document-frequency order, as many as the
    ``t_pad`` allows (fewer where only fewer reach a small bucket), so
    that every segment's budget lands in the bucket, as near its middle
    as the corpus allows.  Returns [((t_pad, bucket), query)]; a bucket
    that no run of tokens reaches in every segment is left out."""
    lo, hi = cfg["query_tokens"]
    dfs = np.stack([s.df.astype(np.int64) for s in data.segments])
    order = np.argsort(-dfs[0], kind="stable")          # head tokens first
    order = order[(dfs[:, order] > 0).all(axis=0)]
    csum = np.concatenate([np.zeros((len(dfs), 1), dtype=np.int64),
                           np.cumsum(dfs[:, order], axis=1)], axis=1)
    out = []
    for tp, b in program_space(cfg):
        floor = 0 if b == BUCKET_MIN else b // BUCKET_STEP
        for n in range(min(tp, hi), max(tp // 2, lo - 1), -1):
            if n > len(order):
                continue
            sums = csum[:, n:] - csum[:, :-n]           # [segments, runs]
            ok = np.flatnonzero(((sums > floor) & (sums <= b)).all(axis=0))
            if len(ok):
                s = ok[np.argmin(np.abs(sums[0, ok] - (floor + b) // 2))]
                tokens = tuple(sorted(int(t) for t in order[s: s + n]))
                out.append(((tp, b), (tokens, np.ones(n, np.float32))))
                break
    return out


# -- the work the algorithm needs (roofline denominators) ------------------

def work_bytes(cfg: dict, data: SparseData, query: tuple) -> float:
    """``text_bm25``'s count for the same algorithm: each posting of each
    query token once (an int32 passage id and a float32 weight), and one
    pass over the score accumulator of every segment searched (written
    once, read once by the top-k)."""
    tokens = list(query[0])
    per_seg = cfg["n_docs"] // cfg["segments"]
    searched = sum(1 for s in data.segments if int(s.df[tokens].sum()))
    return float(data.df[tokens].sum()) * 8.0 + searched * per_seg * 8.0


def work_flops(cfg: dict, data: SparseData, query: tuple) -> float:
    return float(data.df[list(query[0])].sum())   # a multiply-add a posting
