"""Plain reference of the configuration ``pmc-fulltext-phrase``: exact
phrases and required keywords over the whole shard in float64.  It reads
the seed's document-major token list (``tokens``, ``starts`` of every
segment), never the term-major columns the installer hands the index.

One pass with a vocabulary-sized look-up table extracts, segment by
segment, where the terms the judged queries name occur.  From that:

- a term's frequency in every article (how many of its occurrences fall
  between two article starts);
- a phrase's frequency: at every occurrence of its least frequent word,
  the article's own tokens at the shifted positions are compared with the
  phrase's other words (the field's analyzer leaves no position gaps);
- BM25 as the program states it: ``idf = ln(1 + (N - df + 0.5) / (df +
  0.5))`` over the shard, ``tf / (tf + k1 * (1 - b + b * dl / avgdl))``
  with exact lengths; a phrase scores ``sum(idf of its words) * ptf / (ptf
  + norm)``, a bag of required keywords the sum of its words' scores;
- the request's shape: the phrase alone; the phrase where every keyword
  of the ``filter`` is present (the filter adds no score); or, for
  ``must`` keywords with the phrase as a ``should``, the keywords' sum
  where all are present plus the phrase's score where it occurs.

It imports nothing of the program and nothing of the benchmark.  Three
controls, each the reference put in the program's place and each to be
rejected: ``bfloat16`` rounds every operand and intermediate to the
nearest precision below the float32 the configuration states;
``tf_capped`` counts a phrase once an article however often it occurs;
``positions_ignored`` reads a phrase as an ``and`` of its words (its
frequency the least of theirs).
"""

import numpy as np

K1, B = 1.2, 0.75
PRECISIONS = ("float64", "bfloat16", "tf_capped", "positions_ignored")


def bf16(x):
    """Round float32 to the nearest bfloat16 (ties to even), kept as
    float32."""
    u = np.ascontiguousarray(x, dtype=np.float32).view(np.uint32)
    r = ((u >> np.uint32(16)) & np.uint32(1)) + np.uint32(0x7FFF)
    return ((u + r) & np.uint32(0xFFFF0000)).view(np.float32)


class Reference:
    def __init__(self, cfg, data, precision="float64"):
        if precision not in PRECISIONS:
            raise ValueError(f"unknown precision [{precision}]")
        self.k = cfg["k"]
        self.vocab = cfg["vocab"]
        self.n_docs = data.n_docs
        self.segments = data.segments
        self.precision = precision
        low = precision == "bfloat16"
        self.dtype = np.float32 if low else np.float64
        self.r = bf16 if low else (lambda x: x)
        lens = np.concatenate([np.diff(s.starts) for s in self.segments])
        avgdl = lens.sum() / self.n_docs
        self.norm = self.r((K1 * (1.0 - B + B * lens / avgdl))
                           .astype(self.dtype))
        self.slot = None             # term -> row of the extraction
        self.found = []              # per segment (run starts, indices)

    # -- the one pass ---------------------------------------------------

    def extract(self, queries) -> None:
        """Where every term the queries name occurs, per segment: indices
        into the segment's tokens, grouped by term, ascending."""
        named = sorted({t for q in queries for t in q.terms()})
        self.slot = {t: i for i, t in enumerate(named)}
        table = np.full(self.vocab, -1, dtype=np.int32)
        table[named] = np.arange(len(named), dtype=np.int32)
        self.found = []
        for sd in self.segments:
            rows = table[sd.tokens]
            at = np.flatnonzero(rows >= 0)
            # 16-bit keys where they fit: a stable sort of those is a
            # counting sort
            rows = rows[at].astype(np.uint16 if len(named) < 1 << 16
                                   else np.int32)
            order = np.argsort(rows, kind="stable")
            runs = np.zeros(len(named) + 1, dtype=np.int64)
            np.cumsum(np.bincount(rows, minlength=len(named)),
                      out=runs[1:])
            self.found.append((runs, at[order]))

    def occurrences(self, term: int, si: int) -> np.ndarray:
        runs, at = self.found[si]
        row = self.slot[term]
        return at[runs[row]: runs[row + 1]]

    def term_freqs(self, term: int) -> np.ndarray:
        """int64 [n_docs]: the term's frequency in every article."""
        return np.concatenate([
            np.diff(np.searchsorted(self.occurrences(term, si), sd.starts))
            for si, sd in enumerate(self.segments)])

    def phrase_freqs(self, phrase: tuple) -> np.ndarray:
        """int64 [n_docs]: how often the words stand side by side, in
        this order, inside one article."""
        held = [sum(len(self.occurrences(t, si))
                    for si in range(len(self.segments))) for t in phrase]
        lead = int(np.argmin(held))
        out = []
        for si, sd in enumerate(self.segments):
            first = self.occurrences(phrase[lead], si) - lead
            doc = np.searchsorted(sd.starts, first + lead, side="right") - 1
            ok = ((first >= sd.starts[doc])
                  & (first + len(phrase) <= sd.starts[doc + 1]))
            first, doc = first[ok], doc[ok]
            ok = np.ones(len(first), dtype=bool)
            for j, t in enumerate(phrase):
                ok &= sd.tokens[first + j] == t
            out.append(np.bincount(doc[ok], minlength=sd.n_docs))
        return np.concatenate(out)

    # -- scoring ----------------------------------------------------------

    def idf(self, df: int):
        return self.r(np.asarray(
            np.log(1.0 + (self.n_docs - df + 0.5) / (df + 0.5)),
            dtype=self.dtype))

    def saturation(self, freqs: np.ndarray) -> np.ndarray:
        """``tf / (tf + norm)`` where ``tf > 0``, else 0."""
        tf = freqs.astype(self.dtype)
        return np.where(freqs > 0, self.r(tf / self.r(tf + self.norm)),
                        self.dtype(0))

    def scores(self, q) -> np.ndarray:
        """Dense scores of every article of the shard; 0 where the
        request does not match it."""
        r = self.r
        word_tfs = {t: self.term_freqs(t) for t in set(q.terms())}
        if self.precision == "positions_ignored":
            ptf = np.min([word_tfs[t] for t in q.phrase], axis=0)
        else:
            ptf = self.phrase_freqs(q.phrase)
        if self.precision == "tf_capped":
            ptf = np.minimum(ptf, 1)
        idf_sum = self.dtype(0)
        for t in q.phrase:           # a repeated word counts each time
            idf_sum = r(idf_sum + self.idf(int((word_tfs[t] > 0).sum())))
        phrase = r(idf_sum * self.saturation(ptf))
        if q.shape == "phrase":
            return phrase
        required = np.ones(self.n_docs, dtype=bool)
        bag = np.zeros(self.n_docs, dtype=self.dtype)
        for t in q.keywords:
            tf = word_tfs[t]
            required &= tf > 0
            bag = r(bag + r(self.idf(int((tf > 0).sum()))
                            * self.saturation(tf)))
        if q.shape == "phrase_filtered":
            return np.where(required, phrase, self.dtype(0))
        return np.where(required, r(bag + phrase), self.dtype(0))

    def judge_many(self, queries, ids_list):
        """For each query and the ids a response returned for it: the
        reference's score of each id (0 for an article the request does
        not match, or an id the shard does not have), the best score
        among all other articles, and how many articles match."""
        self.extract(queries)
        for q, ids in zip(queries, ids_list):
            dense = self.scores(q).astype(np.float64)
            n_match = int((dense > 0).sum())
            ids = np.asarray(ids, dtype=np.int64)
            ok = (ids >= 0) & (ids < len(dense))
            ref = np.where(ok, dense[np.where(ok, ids, 0)], 0.0)
            dense[ids[ok]] = -np.inf
            yield ref, float(dense.max()), n_match

    def topk_many(self, queries):
        """The reference in the program's place: [(article, score)] best
        first, ties by the lower article."""
        self.extract(queries)
        for q in queries:
            dense = self.scores(q)
            k = min(self.k, int((dense > 0).sum()))
            if not k:
                yield []
                continue
            top = np.argpartition(-dense, min(k, len(dense) - 1))[:k]
            top = top[np.lexsort((top, -dense[top]))]
            yield [(int(i), float(dense[i])) for i in top]
