"""Plain reference of the configuration ``msmarco-passage-bm25``: dense
BM25 over the whole shard in float64, straight from the seeded postings
(the formulation of ``chip_smoke.py``'s ``TextCorpus.bm25``: shard-wide
idf and avgdl, ``idf * tf / (tf + k1 * (1 - b + b * len / avgdl))``).

It imports nothing of the program and nothing of the benchmark.  With
``precision="bfloat16"`` every operand and every intermediate is rounded
to bfloat16, the nearest precision below the float32 the configuration
states: that is the control, the reference put in the program's place,
and the comparison has to reject it.
"""

import numpy as np

K1, B = 1.2, 0.75


def bf16(x):
    """Round float32 to the nearest bfloat16 (ties to even), kept as
    float32."""
    u = np.asarray(x, dtype=np.float32).view(np.uint32)
    r = ((u >> np.uint32(16)) & np.uint32(1)) + np.uint32(0x7FFF)
    return ((u + r) & np.uint32(0xFFFF0000)).view(np.float32)


class Reference:
    def __init__(self, cfg, data, precision="float64"):
        if precision not in ("float64", "bfloat16"):
            raise ValueError(f"unknown precision [{precision}]")
        self.k = cfg["k"]
        self.data = data
        self.low = precision == "bfloat16"
        self.dtype = np.float32 if self.low else np.float64
        self.r = bf16 if self.low else (lambda x: x)
        self.norm = self.r((K1 * (1.0 - B + B * data.lens / data.avgdl))
                           .astype(self.dtype))

    def scores(self, terms):
        d, r = self.data, self.r
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

    def judge_many(self, queries, ids_list):
        """For each query and the ids a response returned for it: the
        reference's score of each id, the best score among all other
        docs, and how many docs match at all."""
        for terms, ids in zip(queries, ids_list):
            dense = self.scores(terms)
            n_match = int((dense > 0).sum())
            ids = np.asarray(ids, dtype=np.int64)
            ok = (ids >= 0) & (ids < len(dense))
            ref = np.where(ok, dense[np.where(ok, ids, 0)], 0.0)
            dense[ids[ok]] = -np.inf
            yield ref, float(dense.max()), n_match

    def topk_many(self, queries):
        """The reference in the program's place: (id, score) rows."""
        for terms in queries:
            dense = self.scores(terms)
            k = min(self.k, int((dense > 0).sum()))
            top = np.argpartition(-dense, min(k, len(dense) - 1))[:max(k, 1)]
            # one id is enough among equal scores: the judge is tie-aware
            top = top[np.lexsort((top, -dense[top]))][:k]
            yield [(int(i), float(dense[i])) for i in top]
