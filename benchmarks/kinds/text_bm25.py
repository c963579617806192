"""Configuration kind ``text_bm25``: seeded passages, their postings, the
installer, the query maker and the warm-up enumeration.

Everything above ``install`` is numpy only and shares no code with
``opensearch_tpu/``.  The corpus keeps the shapes of ``chip_smoke.py``'s
``TextCorpus`` (passage lengths 28-84, Zipf s = 1 vocabulary, queries made
of one passage's own words) and is made segment by segment, one thread
each, because the sorts release the interpreter lock and set-up is what
every run of every later check pays.
"""

from __future__ import annotations

import dataclasses
from concurrent.futures import ThreadPoolExecutor

import numpy as np

K1, B = 1.2, 0.75
FIELD = "body"
# per-query gather budget buckets of the program's term-bag plan
# (``minimum * 4**k``), mirrored here so that the warm-up can enumerate
# them from the configuration alone; tests/benchmarks_harness checks the
# mirror against the plan signatures the program really produces
BUCKET_MIN, BUCKET_STEP = 4096, 4
# the query lengths of every seed: the same multiset, another order
LENGTH_SHARES = {3: 0.05, 4: 0.10, 5: 0.20, 6: 0.25, 7: 0.25, 8: 0.15}


@dataclasses.dataclass
class SegmentData:
    lo: int                      # first shard-wide doc number
    n_docs: int
    lens: np.ndarray             # int64 [n_docs]
    tokens: np.ndarray           # int32 [sum lens]
    starts: np.ndarray           # int64 [n_docs + 1]
    df: np.ndarray               # int32 [vocab]
    offsets: np.ndarray          # int32 [vocab + 1]
    doc_ids: np.ndarray          # int32 [postings], segment-local
    tfs: np.ndarray              # float32 [postings]


@dataclasses.dataclass
class TextData:
    n_docs: int
    vocab: int
    segments: list
    lens: np.ndarray             # int64 [n_docs], shard-wide
    df: np.ndarray               # int64 [vocab], shard-wide

    @property
    def avgdl(self) -> float:
        return float(self.lens.mean())


def _segment(seed_seq, lo: int, n_docs: int, vocab: int, cdf, len_lo: int,
             len_hi: int) -> SegmentData:
    rng = np.random.default_rng(seed_seq)
    lens = rng.integers(len_lo, len_hi + 1, size=n_docs)
    tokens = np.searchsorted(cdf, rng.random(int(lens.sum()))).astype(
        np.int64)
    np.minimum(tokens, vocab - 1, out=tokens)
    starts = np.concatenate([[0], np.cumsum(lens)])
    doc_of = np.repeat(np.arange(n_docs, dtype=np.int64), lens)
    pairs, tfs = np.unique(tokens * n_docs + doc_of, return_counts=True)
    term_of = pairs // n_docs
    df = np.bincount(term_of, minlength=vocab).astype(np.int32)
    offsets = np.zeros(vocab + 1, dtype=np.int32)
    np.cumsum(df, out=offsets[1:])
    return SegmentData(
        lo=lo, n_docs=n_docs, lens=lens, tokens=tokens.astype(np.int32),
        starts=starts, df=df, offsets=offsets,
        doc_ids=(pairs % n_docs).astype(np.int32),
        tfs=tfs.astype(np.float32))


def generate(cfg: dict, seed: int) -> TextData:
    n_docs, n_seg, vocab = cfg["n_docs"], cfg["segments"], cfg["vocab"]
    if n_docs % n_seg:
        raise ValueError("segments must divide n_docs: equal segments "
                         "share one set of compiled programs")
    cdf = np.cumsum(1.0 / np.arange(1, vocab + 1))            # zipf, s = 1
    cdf /= cdf[-1]
    per = n_docs // n_seg
    seqs = np.random.SeedSequence([int(seed), 1]).spawn(n_seg)
    len_lo, len_hi = cfg["passage_tokens"]
    with ThreadPoolExecutor(max_workers=min(n_seg, 8)) as pool:
        segs = list(pool.map(
            lambda i: _segment(seqs[i], i * per, per, vocab, cdf, len_lo,
                               len_hi), range(n_seg)))
    return TextData(
        n_docs=n_docs, vocab=vocab, segments=segs,
        lens=np.concatenate([s.lens for s in segs]),
        df=np.sum([s.df.astype(np.int64) for s in segs], axis=0))


def index_body(cfg: dict) -> dict:
    return {"settings": {"number_of_shards": 1, "number_of_replicas": 0},
            "mappings": {"properties": {FIELD: {"type": "text"}}}}


def install(node, index: str, cfg: dict, data: TextData) -> None:
    """The engine's own segment-copy path (what a segment-replication
    replica runs): the seeded CSR arrays become ``Segment`` objects and
    are adopted as a checkpoint.  ``_bulk`` at ~570 docs/s would take
    half an hour for a million passages."""
    from opensearch_tpu.index.segment import PostingsField, Segment

    segments, live = {}, {}
    for si, sd in enumerate(data.segments):
        seg = Segment(f"bench_{si}", sd.n_docs)
        seg.doc_ids = [str(i) for i in range(sd.lo, sd.lo + sd.n_docs)]
        seg.id_to_local = {d: i for i, d in enumerate(seg.doc_ids)}
        seg.sources = [b"{}"] * sd.n_docs
        lens = sd.lens.astype(np.float32)
        seg.postings[FIELD] = PostingsField(
            terms={f"t{t}": t for t in np.flatnonzero(sd.df).tolist()},
            df=sd.df, offsets=sd.offsets, doc_ids=sd.doc_ids, tfs=sd.tfs,
            pos_offsets=np.zeros(len(sd.doc_ids) + 1, dtype=np.int32),
            positions=np.zeros(0, dtype=np.int32), doc_lens=lens,
            total_len=float(lens.sum()), docs_with_field=sd.n_docs,
            has_norms=True, present=np.ones(sd.n_docs, dtype=bool))
        segments[seg.seg_id] = seg
        live[seg.seg_id] = np.ones(sd.n_docs, dtype=bool).tobytes()
    ckpt = {"segments": list(segments), "live": live,
            "max_seq_no": data.n_docs - 1, "primary_term": 1}
    node.indices.get(index).engine_for(0).install_remote_checkpoint(
        ckpt, segments)


# -- queries ----------------------------------------------------------------

def query_lengths(n: int) -> np.ndarray:
    """The fixed multiset of query lengths (3-8 terms, mean 6)."""
    counts = {k: int(round(share * n)) for k, share in LENGTH_SHARES.items()}
    counts[6] += n - sum(counts.values())
    return np.repeat(list(counts), list(counts.values()))


def queries(cfg: dict, data: TextData, seed: int) -> list:
    """``n_queries`` term lists, each drawn from one passage's own words so
    that it has a best answer: even ones any of its words (head terms,
    long postings), odd ones its rarest."""
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 2]))
    lengths = rng.permutation(query_lengths(cfg["n_queries"]))
    out = []
    while len(out) < len(lengths):
        want = int(lengths[len(out)])
        sd = data.segments[int(rng.integers(len(data.segments)))]
        i = int(rng.integers(sd.n_docs))
        words = np.unique(sd.tokens[sd.starts[i]: sd.starts[i + 1]])
        if len(words) < want:
            continue
        if len(out) % 2:
            pick = words[np.argsort(data.df[words], kind="stable")[:want]]
        else:
            pick = rng.choice(words, size=want, replace=False)
        out.append(sorted(int(t) for t in pick))
    return out


def body(cfg: dict, terms: list) -> dict:
    return {"query": {"match": {FIELD: " ".join(f"t{t}" for t in terms)}},
            "size": cfg["k"], "_source": False}


# -- the programs a cell can need -------------------------------------------

def t_pad(n_terms: int) -> int:
    return 1 << max(0, int(n_terms) - 1).bit_length()


def bucket(budget: int) -> int:
    b = BUCKET_MIN
    while b < budget:
        b *= BUCKET_STEP
    return b


def signature(cfg: dict, data: TextData, terms: list, si: int):
    """(t_pad, bucket) of ``terms`` in segment ``si``, or None where no
    term occurs there (the program prunes such a segment)."""
    budget = int(data.segments[si].df[terms].sum())
    return (t_pad(len(terms)), bucket(budget)) if budget else None


def program_space(cfg: dict) -> list:
    """Every (t_pad, bucket) a query of this configuration can produce:
    a function of the file, never of the seed."""
    lo, hi = cfg["query_terms"]
    per_seg = cfg["n_docs"] // cfg["segments"]
    out = []
    for tp in sorted({t_pad(n) for n in range(lo, hi + 1)}):
        most = min(tp, hi) * per_seg          # a df cannot pass the docs
        b = BUCKET_MIN
        while b == BUCKET_MIN or b // BUCKET_STEP < most:
            out.append((tp, b))
            b *= BUCKET_STEP
    return out


def warmup_queries(cfg: dict, data: TextData) -> list:
    """One crafted query per (t_pad, bucket) of ``program_space``: terms
    picked by document frequency so that segment 0's budget lands in the
    middle of the bucket (the other segments, equal in size, then land
    there too).  Returns [((t_pad, bucket), terms)]; a bucket that no
    run of terms reaches in this corpus is left out."""
    df0 = data.segments[0].df.astype(np.int64)
    order = np.argsort(-df0, kind="stable")              # head terms first
    order = order[df0[order] > 0]
    csum = np.concatenate([[0], np.cumsum(df0[order])])
    hi = cfg["query_terms"][1]
    out = []
    for tp, b in program_space(cfg):
        n = min(tp, hi)
        floor = 0 if b == BUCKET_MIN else b // BUCKET_STEP
        sums = csum[n:] - csum[:-n]        # n neighbours in df order
        ok = np.flatnonzero((sums > floor) & (sums <= b))
        if len(ok):
            s = ok[np.argmin(np.abs(sums[ok] - (floor + b) // 2))]
            out.append(((tp, b), sorted(int(t) for t in order[s: s + n])))
    return out


# -- the work the algorithm needs (roofline denominators) ------------------

def work_bytes(cfg: dict, data: TextData, terms: list) -> float:
    """Bytes a BM25 top-k over this shard has to move for one query: each
    posting of each query term once (an int32 doc id and an f32 impact),
    and one pass over the score accumulator of every segment searched
    (written once, read once by the top-k)."""
    postings = int(data.df[terms].sum())
    per_seg = cfg["n_docs"] // cfg["segments"]
    searched = sum(1 for s in data.segments if int(s.df[terms].sum()))
    return postings * 8.0 + searched * per_seg * 4.0 * 2


def work_flops(cfg: dict, data: TextData, terms: list) -> float:
    return float(data.df[terms].sum())            # one add per posting
