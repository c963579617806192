"""Plain reference of the configuration ``msmarco-passage-splade``: the
dot product of the query's token weights with every passage's stored
feature weights, in float64, over the whole shard.

It reads the seed's own passage-major lists (a passage's tokens and the
weight beside each: ``row_starts``, ``row_tokens``, ``row_weights``),
never the token-major columns the installer hands the index, and turns
them by token itself, once, so that a query costs its tokens' postings
and not the shard's.  A query's float32 weight times a stored 9-bit weight
is exact in float64, so only the sum rounds.  It imports nothing of the
program and nothing of the benchmark.

Two controls, each the reference put in the program's place one
precision step down, which the comparison has to reject:
``precision="bfloat16"`` keeps the float32 products and accumulates them
in bfloat16; ``"bf16_weights"`` accumulates in float32 over stored
weights one mantissa bit narrower than FeatureField's (bfloat16: 8
significant bits where the field keeps 9).
"""

import numpy as np

PRECISIONS = ("float64", "bfloat16", "bf16_weights")


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
        self._by_token = None

    def by_token(self) -> list:
        """Per segment (starts int64 [vocab + 1], passages int32
        shard-wide, weights float32): the passage-major lists ordered by
        token, passages ascending within one (the stable sort of 16-bit
        keys is a counting sort; 3 s a segment of 57M)."""
        if self._by_token is None:
            self._by_token = []
            for sd in self.segments:
                order = np.argsort(sd.row_tokens, kind="stable")
                starts = np.zeros(self.vocab + 1, dtype=np.int64)
                np.cumsum(np.bincount(sd.row_tokens, minlength=self.vocab),
                          out=starts[1:])
                passage = np.repeat(
                    np.arange(sd.lo, sd.lo + sd.n_docs, dtype=np.int32),
                    np.diff(sd.row_starts))
                self._by_token.append((starts, np.take(passage, order),
                                       np.take(sd.row_weights, order)))
        return self._by_token

    def scores(self, query) -> np.ndarray:
        """Dense scores of every passage of the shard; 0 where a passage
        holds none of the query's tokens."""
        tokens, weights = query
        low = self.precision != "float64"
        out = np.zeros(self.n_docs, dtype=np.float32 if low else np.float64)
        for t, q in zip(tokens, np.asarray(weights, dtype=np.float32)):
            for starts, passages, stored in self.by_token():
                a, b = starts[t], starts[t + 1]
                at, w = passages[a:b], stored[a:b]
                # a passage occurs once under a token: a plain indexed
                # add accumulates
                if self.precision == "float64":
                    out[at] += w.astype(np.float64) * float(q)
                elif self.precision == "bfloat16":
                    out[at] = bf16(out[at] + q * w)
                else:
                    out[at] += q * bf16(w)
        return out

    def judge_many(self, queries, ids_list):
        """For each query and the ids a response returned for it: the
        reference's score of each id (0 for a passage without any of the
        query's tokens, or an id the shard does not have), the best
        score among all other passages, and how many passages match."""
        for query, ids in zip(queries, ids_list):
            dense = self.scores(query)
            n_match = int((dense > 0).sum())
            ids = np.asarray(ids, dtype=np.int64)
            ok = (ids >= 0) & (ids < len(dense))
            ref = np.where(ok, dense[np.where(ok, ids, 0)], 0.0)
            dense[ids[ok]] = -np.inf
            yield ref.astype(np.float64), float(dense.max()), n_match

    def topk_many(self, queries):
        """The reference in the program's place: [(passage, score)] best
        first, ties by the lower passage."""
        for query in queries:
            dense = self.scores(query)
            k = min(self.k, int((dense > 0).sum()))
            if not k:
                yield []
                continue
            top = np.argpartition(-dense, min(k, len(dense) - 1))[:k]
            top = top[np.lexsort((top, -dense[top]))]
            yield [(int(i), float(dense[i])) for i in top]
