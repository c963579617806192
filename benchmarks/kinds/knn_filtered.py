"""Configuration kind ``knn_filtered``: one shard whose every segment holds
a vector field and a multi-valued ``keyword`` field of tags, asked exact
k-NN queries that a ``filter`` of one or two required tags restricts (the
Big-ANN-Benchmarks filter track's question, in OpenSearch's DSL).  Numpy
only above ``install``.

Vectors follow ``knn_exact``'s argument: values on a 1/64 grid over 0-255,
exact in float32 and in short decimal JSON, 14 bits wide so that a bf16
pass moves the neighbours.  A row's bag of tags is drawn from a
Zipf-Mandelbrot vocabulary (p(r) ~ 1 / (r + 9): a flat head of tags that
each keep 5-10% of the rows, as a year or a camera maker does, and a long
tail) with a heavy-tailed bag size; a query takes its tags from one row's
own bag, so at least that row passes, and its vector is a quarter of that
row's vector plus noise of the rows' own distribution, as
``hybrid_bm25_knn`` makes it.
"""

from __future__ import annotations

import dataclasses
import math
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmarks.kinds import knn_exact
from benchmarks.kinds.text_bm25 import (BUCKET_MIN, BUCKET_STEP, bucket,
                                        t_pad)

VEC_FIELD = knn_exact.FIELD
TAG_FIELD = "tags"
GRID = knn_exact.GRID
SOURCE_SHARE = 4                 # query = source / 4 + 3 * noise / 4
ZIPF_OFFSET = 9                  # p(r) ~ 1 / (r + ZIPF_OFFSET)
BAG_MU, BAG_SIGMA, BAG_MAX = 2.15, 0.7, 64     # lognormal draws a row


def tag_name(tag: int) -> str:
    """Zero-padded, so that the terms' sorted order is the tags' order."""
    return f"t{int(tag):06d}"


@dataclasses.dataclass
class SegmentTags:
    lo: int                      # first shard-wide row number
    n_docs: int
    row_starts: np.ndarray       # int64 [n_docs + 1], into row_tags
    row_tags: np.ndarray         # int32 [postings], a row's tags ascending
    df: np.ndarray               # int32 [vocab]
    offsets: np.ndarray          # int32 [vocab + 1], into doc_ids
    doc_ids: np.ndarray          # int32 [postings], segment-local, by tag


@dataclasses.dataclass
class FilteredData:
    n_docs: int
    dim: int
    vocab: int
    vectors: np.ndarray          # float32 [n_docs, dim], shard-wide
    segments: list
    df: np.ndarray               # int64 [vocab], shard-wide

    def bag(self, row: int) -> np.ndarray:
        """The tags of shard-wide ``row``, ascending."""
        sd = self.segments[row // self.segments[0].n_docs]
        i = row - sd.lo
        return sd.row_tags[sd.row_starts[i]: sd.row_starts[i + 1]]

    def rows_with(self, tags) -> np.ndarray:
        """Shard-wide rows that carry every tag, from the postings."""
        out = []
        for sd in self.segments:
            rows = None
            for t in tags:
                here = sd.doc_ids[sd.offsets[t]: sd.offsets[t + 1]]
                rows = here if rows is None else np.intersect1d(
                    rows, here, assume_unique=True)
            out.append(rows.astype(np.int64) + sd.lo)
        return np.concatenate(out)


def tag_cdf(vocab: int) -> np.ndarray:
    cdf = np.cumsum(1.0 / (np.arange(1, vocab + 1) + ZIPF_OFFSET))
    return cdf / cdf[-1]


def _segment(seed_seq, lo: int, n: int, dim: int, vocab: int, cdf,
             vectors: np.ndarray) -> SegmentTags:
    """One segment's rows: its slice of ``vectors`` filled in place, and
    the tags both ways round (by row for the query maker and the
    reference, by tag for the index)."""
    rng = np.random.default_rng(seed_seq)
    raw = rng.integers(0, 255 * GRID, size=(n, dim), dtype=np.uint16)
    np.divide(raw, np.float32(GRID), out=vectors[lo: lo + n])
    del raw
    sizes = np.clip(np.rint(rng.lognormal(BAG_MU, BAG_SIGMA, size=n)), 1,
                    BAG_MAX).astype(np.int64)
    draws = np.searchsorted(cdf, rng.random(int(sizes.sum())))
    np.minimum(draws, vocab - 1, out=draws)
    row_of = np.repeat(np.arange(n, dtype=np.int64), sizes)
    pairs = np.unique(draws * n + row_of)          # by tag, then by row
    tag_of = (pairs // n).astype(np.int32)
    doc_ids = (pairs % n).astype(np.int32)
    df = np.bincount(tag_of, minlength=vocab).astype(np.int32)
    offsets = np.zeros(vocab + 1, dtype=np.int32)
    np.cumsum(df, out=offsets[1:])
    by_row = np.argsort(doc_ids, kind="stable")    # a row's tags ascending
    row_starts = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(np.bincount(doc_ids, minlength=n), out=row_starts[1:])
    return SegmentTags(lo=lo, n_docs=n, row_starts=row_starts,
                       row_tags=tag_of[by_row], df=df, offsets=offsets,
                       doc_ids=doc_ids)


def generate(cfg: dict, seed: int) -> FilteredData:
    n_docs, n_seg, dim = cfg["n_docs"], cfg["segments"], cfg["dim"]
    vocab = cfg["vocab"]
    if n_docs % n_seg:
        raise ValueError("segments must divide n_docs: equal segments "
                         "share one set of compiled programs")
    per = n_docs // n_seg
    cdf = tag_cdf(vocab)
    vectors = np.empty((n_docs, dim), dtype=np.float32)
    seqs = np.random.SeedSequence([int(seed), 1]).spawn(n_seg)
    with ThreadPoolExecutor(max_workers=min(n_seg, 10)) as pool:
        segs = list(pool.map(
            lambda i: _segment(seqs[i], i * per, per, dim, vocab, cdf,
                               vectors), range(n_seg)))
    return FilteredData(
        n_docs=n_docs, dim=dim, vocab=vocab, vectors=vectors, segments=segs,
        df=np.sum([s.df.astype(np.int64) for s in segs], axis=0))


def index_body(cfg: dict) -> dict:
    return {"settings": {"number_of_shards": 1, "number_of_replicas": 0},
            "mappings": {"properties": {
                VEC_FIELD: {"type": "knn_vector", "dimension": cfg["dim"],
                            "method": {"name": "exact",
                                       "space_type": cfg["space"]}},
                TAG_FIELD: {"type": "keyword"}}}}


def install(node, index: str, cfg: dict, data: FilteredData) -> None:
    """Each segment's rows as a ``Segment`` with the vector column, the
    tags' postings (a ``keyword``: no norms, tf 1, no positions) and their
    ordinals (its doc values), adopted through the engine's segment-copy
    path; then the configuration's programs compiled side by side."""
    from opensearch_tpu.index.segment import (OrdinalDV, PostingsField,
                                              Segment, VectorDV)

    names = [tag_name(t) for t in range(data.vocab)]
    ord_of = {name: t for t, name in enumerate(names)}
    segments, live = {}, {}
    for si, sd in enumerate(data.segments):
        n = sd.n_docs
        seg = Segment(f"bench_{si}", n)
        seg.doc_ids = [str(i) for i in range(sd.lo, sd.lo + n)]
        seg.id_to_local = {d: i for i, d in enumerate(seg.doc_ids)}
        seg.sources = [b"{}"] * n
        seg.vector_dv[VEC_FIELD] = VectorDV(
            values=data.vectors[sd.lo: sd.lo + n],
            exists=np.ones(n, dtype=bool), dim=data.dim,
            similarity="l2_norm")
        seg.postings[TAG_FIELD] = PostingsField(
            terms={names[t]: t for t in np.flatnonzero(sd.df).tolist()},
            df=sd.df, offsets=sd.offsets, doc_ids=sd.doc_ids,
            tfs=np.ones(len(sd.doc_ids), dtype=np.float32),
            pos_offsets=np.zeros(len(sd.doc_ids) + 1, dtype=np.int32),
            positions=np.zeros(0, dtype=np.int32),
            doc_lens=np.ones(n, dtype=np.float32), total_len=float(n),
            docs_with_field=n, has_norms=False,
            present=np.ones(n, dtype=bool))
        # ordinals over the whole vocabulary (a tag's number is its
        # ordinal in every segment; one that no row here carries has none)
        seg.ordinal_dv[TAG_FIELD] = OrdinalDV(
            ord_terms=names, term_to_ord=ord_of,
            offsets=sd.row_starts.astype(np.int32), ords=sd.row_tags,
            value_docs=np.repeat(np.arange(n, dtype=np.int32),
                                 np.diff(sd.row_starts)),
            min_ord=sd.row_tags[sd.row_starts[:-1]],
            max_ord=sd.row_tags[sd.row_starts[1:] - 1],
            exists=np.ones(n, dtype=bool))
        segments[seg.seg_id] = seg
        live[seg.seg_id] = np.ones(n, dtype=bool).tobytes()
    ckpt = {"segments": list(segments), "live": live,
            "max_seq_no": data.n_docs - 1, "primary_term": 1}
    node.indices.get(index).engine_for(0).install_remote_checkpoint(
        ckpt, segments)
    compile_side_by_side(node, index, cfg, data)


def compile_side_by_side(node, index: str, cfg: dict,
                         data: FilteredData) -> None:
    """``hybrid_bm25_knn``'s ordering, for its reasons: the first crafted
    request alone, because the first request to reach a segment stages it
    (805 MB of vectors through a padded host copy) and nothing keeps two
    first requests from each staging a copy; then the other crafted
    requests at the same time, so that the mask programs compile side by
    side instead of one after the other.  The harness's own pass then
    finds them compiled."""
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

def queries(cfg: dict, data: FilteredData, seed: int) -> list:
    """``n_queries`` pairs (tags, vector), each from one row: one or two
    tags of its own bag by turns (any of them, or its rarest, by turns of
    two), and a noisy share of its own vector."""
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 2]))
    out = []
    while len(out) < cfg["n_queries"]:
        want = 1 + len(out) % 2
        row = int(rng.integers(data.n_docs))
        bag = data.bag(row)
        if len(bag) < want:
            continue
        if (len(out) // 2) % 2:
            pick = bag[np.argsort(data.df[bag], kind="stable")[:want]]
        else:
            pick = rng.choice(bag, size=want, replace=False)
        noise = rng.integers(0, 255 * GRID, size=data.dim, dtype=np.int32)
        source = (data.vectors[row] * GRID).astype(np.int32)
        raw = source // SOURCE_SHARE + (
            noise * (SOURCE_SHARE - 1)) // SOURCE_SHARE
        out.append((tuple(sorted(int(t) for t in pick)),
                    raw.astype(np.float32) / np.float32(GRID)))
    return out


def tag_filter(tags) -> dict:
    """The filter as a user writes it: a ``term`` for one tag, a ``bool``
    of ``term`` clauses for more."""
    clauses = [{"term": {TAG_FIELD: tag_name(t)}} for t in tags]
    return clauses[0] if len(clauses) == 1 else {"bool": {"filter": clauses}}


def body(cfg: dict, query: tuple) -> dict:
    tags, vec = query
    return {"query": {"knn": {VEC_FIELD: {
        "vector": vec.tolist(), "k": cfg["k"], "filter": tag_filter(tags)}}},
        "size": cfg["k"], "_source": False}


# -- the programs a cell can need -------------------------------------------

def signature(cfg: dict, data: FilteredData, query: tuple, si: int):
    """(t_pad, bucket) of the filter's mask program in segment ``si``:
    the program runs there whether or not a tag occurs."""
    tags = list(query[0])
    return (t_pad(len(tags)),
            bucket(int(data.segments[si].df[tags].sum())))


def head_shares(vocab: int, n: int) -> np.ndarray:
    """The expected share of the rows that carry each of the ``n`` most
    frequent tags: 1 - E[(1 - p) ** size] over the bag sizes' own
    distribution (the rounded, clipped lognormal, from its normal CDF)."""
    sizes = np.arange(1, BAG_MAX + 1)
    edges = np.concatenate([[0.0], sizes[:-1] + 0.5, [np.inf]])
    with np.errstate(divide="ignore"):
        z = (np.log(edges) - BAG_MU) / (BAG_SIGMA * math.sqrt(2.0))
    cdf = np.array([0.5 * (1.0 + math.erf(x)) for x in z])
    pmf = np.diff(cdf)
    p = np.diff(np.concatenate([[0.0], tag_cdf(vocab)[:n]]))
    return 1.0 - ((1.0 - p[:, None]) ** sizes[None, :] * pmf).sum(axis=1)


def mask_buckets(cfg: dict, n_tags: int) -> list:
    """The gather buckets ``n_tags`` tags can need in one segment: up to
    the bucket of the ``n_tags`` most frequent tags' expected postings
    there, with a quarter of room.  A function of the file and of this
    kind's constants, never of the seed."""
    per_seg = cfg["n_docs"] // cfg["segments"]
    most = min(1.0, 1.25 * float(head_shares(cfg["vocab"],
                                             n_tags).sum())) * per_seg
    out = [BUCKET_MIN]
    while out[-1] < most:
        out.append(out[-1] * BUCKET_STEP)
    return out


def program_space(cfg: dict) -> list:
    """``run_full`` over the folded tag bag at every (t_pad, bucket) one
    or two tags can produce at this segment size, then the scan and
    ``run_topk`` over the winners' mask."""
    lo, hi = cfg["query_tags"]
    return [(t_pad(n), b) for n in range(lo, hi + 1)
            for b in mask_buckets(cfg, n)] + [
        ("knn_topk", cfg["k"]), ("run_topk_winners", cfg["k"])]


def warm_vector(cfg: dict) -> np.ndarray:
    return np.full(cfg["dim"], 127.5, dtype=np.float32)


def warmup_queries(cfg: dict, data: FilteredData) -> list:
    """One crafted query per mask program: tags picked by document
    frequency so that every segment's budget lands in the bucket, as near
    its middle as the corpus allows (neighbours in df order, as
    ``text_bm25`` picks them).  Every one also runs the scan and the
    winners' program, whose shapes no query changes.  A bucket that no
    bag reaches in every segment of this corpus is left out."""
    dfs = np.stack([s.df.astype(np.int64) for s in data.segments])
    order = np.argsort(-dfs[0], kind="stable")           # head tags first
    lo, hi = cfg["query_tags"]
    vec = warm_vector(cfg)
    csum = np.concatenate([np.zeros((len(dfs), 1), dtype=np.int64),
                           np.cumsum(dfs[:, order], axis=1)], axis=1)
    out = []
    for n in range(lo, hi + 1):
        # budgets [segments, runs]: n neighbours in df order
        sums = csum[:, n:] - csum[:, :-n]
        for b in mask_buckets(cfg, n):
            floor = 0 if b == BUCKET_MIN else b // BUCKET_STEP
            ok = np.flatnonzero(((sums > floor) & (sums <= b)).all(axis=0))
            if len(ok):
                s = ok[np.argmin(np.abs(sums[0, ok] - (floor + b) // 2))]
                tags = tuple(sorted(int(t) for t in order[s: s + n]))
                out.append(((t_pad(n), b), (tags, vec)))
    return out


# -- the work the algorithm needs (roofline denominators) ------------------

def _needed(data: FilteredData, query: tuple) -> tuple:
    """(postings of the required tags, rows that carry them all)."""
    tags = list(query[0])
    return float(data.df[tags].sum()), len(data.rows_with(tags))


def work_bytes(cfg: dict, data: FilteredData, query: tuple) -> float:
    """What a filtered exact search has to move: each posting of each
    required tag once (an int32 row id), and one read of every vector that
    passes.  Never what the kernel reads: the program scans every row."""
    postings, n_match = _needed(data, query)
    return postings * 4.0 + n_match * cfg["dim"] * 4.0


def work_flops(cfg: dict, data: FilteredData, query: tuple) -> float:
    postings, n_match = _needed(data, query)
    return postings + 2.0 * n_match * cfg["dim"]
