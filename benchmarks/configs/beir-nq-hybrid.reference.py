"""Plain reference of the configuration ``beir-nq-hybrid``: a ``hybrid``
query of one BM25 sub-query and one exact k-NN sub-query, combined as the
neural-search plugin's normalization processor combines them.

For one query (terms, vector) over the whole shard:

1. BM25, dense, in float64, straight from the seeded postings (shard-wide
   idf and avgdl, ``idf * tf / (tf + k1 * (1 - b + b * len / avgdl))``);
   the list is the ``k`` best docs with a score above 0.
2. Inner products with every vector.  The vectors lie on a 1/256 grid, so
   float64 holds every product exactly; a float32 matrix product, block
   by block of 262,144 rows so that 2M x 768 fits the host, picks 64
   candidates a query and those are measured again in float64.  Scored as
   the k-NN plugin scores ``innerproduct``: ``d + 1`` for ``d >= 0``,
   else ``1 / (1 - d)``.  The list is the ``k`` best of the ``knn_k``
   nearest (``knn_k >= k``, so the ``k`` nearest).
3. Each list min-max normalised, ``(s - min) / (max - min)``; the lists
   combined by the arithmetic mean with equal weights, a doc absent from
   a list counting 0 there; sorted by (score descending, id).

Departures from the plugin's description, each the program's too:

* The floor.  ``MinMaxScoreNormalizationTechnique``, as remembered here
  with no network, returns ``MIN_SCORE = 0.001`` for a score that
  normalises to exactly 0 (the list's lowest) and 1.0 for every score of
  a list whose highest and lowest are equal (a single candidate).  Only
  the exact zero is raised: a score a hair above the lowest keeps its
  tiny value.
* The sub-query depth.  The plugin's collector keeps ``from + size``
  candidates a sub-query and shard; so does ``_hybrid_search``; so does
  this file (``k`` = 10, ``from`` 0).  One shard, so there is no
  coordinator-side merge of shard lists before normalising.
* The tie order.  Lucene breaks a tie by doc id; the program by (segment,
  local doc), which is the same order here, since segment *i* holds docs
  ``i * n .. (i + 1) * n - 1``; this file by id.
* The total.  ``hits.total`` is not judged: the program reports the
  larger sub-query's count as a lower bound (``gte``).

What the judge allows, and why.  Which docs make a list's cut is a step:
two candidates whose exact scores differ in the seventh digit are told
apart by float64 and not by the float32 the configuration states, and
whichever of them is listed moves the combined score of that doc by up to
a half.  So candidates within ``CUT_TIE`` (1e-5, relative) of the list's
``k``-th score are interchangeable at the cut, and the ones the response
holds are taken first.  1e-5 is thirty times the float32 error of a BM25
score (3e-7, PERF.md) and a four-hundredth of one bf16 rounding (4e-3).
Left as a step: which of two candidates within rounding of each other is
the list's lowest and takes the floor.

It imports nothing of the program and nothing of the benchmark.
``precision`` makes the controls, the reference put in the program's
place: ``knn_bf16x3`` computes the matrix product in three bf16 passes
(``jax.lax.Precision.HIGH``, the step below the six the program runs),
``knn_bfloat16`` in one; ``bm25_bfloat16`` rounds every BM25 operand and
intermediate to bfloat16; ``bfloat16`` does both of the latter.
"""

import numpy as np

K1, B = 1.2, 0.75
MIN_SCORE = 0.001
CUT_TIE = 1e-5
CANDIDATES = 64
BLOCK = 1 << 18                  # rows of the vector column a product takes
CHUNK = 128                      # queries a product takes
PASSES = {"float64": 0, "bm25_bfloat16": 0, "knn_bf16x3": 3,
          "knn_bfloat16": 1, "bfloat16": 1}


def bf16(x):
    """Round float32 to the nearest bfloat16 (ties to even), kept as
    float32."""
    u = np.ascontiguousarray(x, dtype=np.float32).view(np.uint32)
    r = ((u >> np.uint32(16)) & np.uint32(1)) + np.uint32(0x7FFF)
    return ((u + r) & np.uint32(0xFFFF0000)).view(np.float32)


def ip_score(d):
    """The k-NN plugin's score of an inner product."""
    d = np.asarray(d)
    pos = d >= 0
    return np.where(pos, d + 1.0, 1.0 / (1.0 - np.where(pos, 0.0, d)))


def by_score_then_id(ids, scores):
    order = np.lexsort((ids, -scores))
    return ids[order], scores[order]


def cut(ids, scores, k, prefer=()):
    """The first ``k`` of a list sorted by (score descending, id); of the
    candidates within ``CUT_TIE`` of the ``k``-th score, those in
    ``prefer`` first."""
    if len(ids) <= k:
        return ids, scores
    kth = scores[k - 1]
    tied = np.abs(scores - kth) <= CUT_TIE * abs(kth)
    if not tied[k:].any():
        return ids[:k], scores[:k]
    rank = np.arange(len(ids))
    sure = rank[~tied & (rank < k)]
    group = rank[tied]
    group = group[np.argsort(~np.isin(ids[group], prefer), kind="stable")]
    keep = np.sort(np.concatenate([sure, group[:k - len(sure)]]))
    return ids[keep], scores[keep]


def min_max(scores):
    lo, hi = scores.min(), scores.max()
    if hi == lo:
        return np.ones(len(scores))
    norm = (scores - lo) / (hi - lo)
    return np.where(norm == 0.0, MIN_SCORE, norm)


def combine(lists):
    """{id: combined score} over the union of the (ids, scores) lists."""
    out = {}
    for ids, scores in lists:
        if len(ids):
            for i, s in zip(ids.tolist(), min_max(
                    np.asarray(scores, dtype=np.float64)).tolist()):
                out[i] = out.get(i, 0.0) + s / len(lists)
    return out


class Reference:
    def __init__(self, cfg, data, precision="float64"):
        if precision not in PASSES:
            raise ValueError(f"unknown precision [{precision}]")
        self.k = cfg["k"]
        if cfg["knn_k"] < self.k:
            raise ValueError("knn_k below k: the k-NN list would be the "
                             "knn_k nearest, which this file does not do")
        self.text, self.v = data.text, data.vectors
        self.passes = PASSES[precision]
        self.low = precision in ("bm25_bfloat16", "bfloat16")
        self.dtype = np.float32 if self.low else np.float64
        self.r = bf16 if self.low else (lambda x: x)
        self.norm = self.r((K1 * (1.0 - B + B * self.text.lens
                                  / self.text.avgdl)).astype(self.dtype))

    # -- the BM25 sub-query ---------------------------------------------

    def bm25(self, terms):
        d, r = self.text, self.r
        out = np.zeros(d.n_docs, dtype=self.dtype)
        for t in terms:
            df = float(d.df[t])
            idf = r(np.asarray(np.log(1.0 + (d.n_docs - df + 0.5)
                                      / (df + 0.5)), dtype=self.dtype))
            for sd in d.segments:
                a, b = sd.offsets[t], sd.offsets[t + 1]
                docs = sd.doc_ids[a:b].astype(np.int64) + sd.lo
                tf = sd.tfs[a:b].astype(self.dtype)
                impact = r(idf * r(tf / r(tf + self.norm[docs])))
                out[docs] = r(out[docs] + impact)
        return out

    def bm25_head(self, terms):
        """The best matching docs by (score descending, id): at least
        ``k + 1`` where that many match, and every tie of the last."""
        dense = self.bm25(terms)
        n = min(self.k + 1, int((dense > 0).sum()))
        if n == 0:
            return np.zeros(0, dtype=np.int64), np.zeros(0)
        ids = np.flatnonzero(dense >= np.partition(dense, -n)[-n] * (
            1.0 - 2 * CUT_TIE))
        return by_score_then_id(ids, dense[ids].astype(np.float64))

    # -- the k-NN sub-query ---------------------------------------------

    def dots(self, block, qs):
        """float32 inner products [rows, queries], the matrix product at
        this reference's precision."""
        if not self.passes:
            return block @ qs.T
        b_hi, q_hi = bf16(block), bf16(qs)
        out = b_hi @ q_hi.T
        if self.passes == 3:
            out += b_hi @ bf16(qs - q_hi).T
            out += bf16(block - b_hi) @ q_hi.T
        return out

    def knn_heads(self, qs):
        """For each query of the chunk: candidate ids and scores by
        (score descending, id).  Exact float64 scores of float32-picked
        candidates, or the low-precision scores themselves."""
        qs = np.asarray(qs, dtype=np.float32)
        n, c = len(self.v), min(CANDIDATES, len(self.v))
        ids = np.zeros((len(qs), 0), dtype=np.int64)
        vals = np.zeros((len(qs), 0), dtype=np.float32)
        for lo in range(0, n, BLOCK):
            d = self.dots(self.v[lo: lo + BLOCK], qs).T   # [queries, rows]
            take = min(c, d.shape[1])
            part = np.argpartition(-d, take - 1, axis=1)[:, :take]
            ids = np.concatenate([ids, part + lo], axis=1)
            vals = np.concatenate([vals, np.take_along_axis(d, part, 1)],
                                  axis=1)
            best = np.argpartition(-vals, c - 1, axis=1)[:, :c]
            ids = np.take_along_axis(ids, best, 1)
            vals = np.take_along_axis(vals, best, 1)
        for j in range(len(qs)):
            if self.passes:
                yield by_score_then_id(
                    ids[j], ip_score(vals[j]).astype(np.float64))
                continue
            exact = self.v[ids[j]].astype(np.float64) @ qs[j].astype(
                np.float64)
            head = by_score_then_id(ids[j], ip_score(exact))
            # a float32 product of these operands is off by far less
            # than 0.5: no doc outside the candidates reaches the cut
            if c < n and vals[j].min() + 0.5 > np.sort(exact)[
                    -min(self.k + 1, c)] * (1.0 - 2 * CUT_TIE):
                raise RuntimeError("reference: 64 candidates do not bound "
                                   f"the top {self.k + 1} of a query")
            yield head

    # -- the hybrid query -----------------------------------------------

    def combined_many(self, queries, prefers):
        """{id: combined score} over each query's union of the two
        lists, cut with ``prefers[i]`` taken first among ties."""
        for lo in range(0, len(queries), CHUNK):
            chunk = queries[lo: lo + CHUNK]
            heads = self.knn_heads([vec for _terms, vec in chunk])
            for j, ((terms, _vec), knn) in enumerate(zip(chunk, heads)):
                prefer = prefers[lo + j]
                yield combine([cut(*self.bm25_head(terms), self.k, prefer),
                               cut(*knn, self.k, prefer)])

    def judge_many(self, queries, ids_list):
        """For each query and the ids a response returned for it: the
        reference's combined score of each id (0 for a doc outside the
        union), the best combined score among the union's other members,
        and the union's size."""
        for ids, scores in zip(ids_list,
                               self.combined_many(queries, ids_list)):
            ref = np.array([scores.get(i, 0.0) for i in ids])
            held = set(ids)
            rest = [s for i, s in scores.items() if i not in held]
            yield ref, max(rest, default=-np.inf), len(scores)

    def topk_many(self, queries):
        """The reference in the program's place: (id, score) rows."""
        for scores in self.combined_many(queries, [()] * len(queries)):
            ids = np.fromiter(scores, dtype=np.int64, count=len(scores))
            ids, vals = by_score_then_id(
                ids, np.array([scores[i] for i in ids.tolist()]))
            yield [(int(i), float(s))
                   for i, s in zip(ids[:self.k], vals[:self.k])]
