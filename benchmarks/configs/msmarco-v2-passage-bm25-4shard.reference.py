"""Plain reference of the configuration ``msmarco-v2-passage-bm25-4shard``:
dense BM25 in float64, straight from the seeded postings, each shard with
its own statistics (OpenSearch's default ``query_then_fetch``: a shard's
idf from its own df and doc count, its avgdl from its own passages),
the shards' scores side by side in one array of all passages, from which
the comparison takes the merged top-k.

It imports nothing of the program and nothing of the benchmark.  Shard
``s`` holds the seeded segments ``s * per`` to ``(s + 1) * per - 1``, as
the configuration's file states.  Two controls, each put in the program's
place, have to be rejected by the comparison:

- ``precision="bfloat16"``: every operand and every intermediate rounded
  to bfloat16, the nearest precision below the float32 the configuration
  states;
- ``precision="global_stats"``: float64, but idf and avgdl over all the
  shards together (``dfs_query_then_fetch``), where the index's contract is
  per-shard statistics.
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
        if precision not in ("float64", "bfloat16", "global_stats"):
            raise ValueError(f"unknown precision [{precision}]")
        self.k = cfg["k"]
        self.data = data
        self.low = precision == "bfloat16"
        self.dtype = np.float32 if self.low else np.float64
        self.r = bf16 if self.low else (lambda x: x)
        n_shards = cfg["shards"]
        per = len(data.segments) // n_shards
        if precision == "global_stats":
            groups = [data.segments]
        else:
            groups = [data.segments[s * per:(s + 1) * per]
                      for s in range(n_shards)]
        # per group: its segments, doc count, df and the norm of its docs
        self.shards = []
        self.norm = np.zeros(data.n_docs, dtype=self.dtype)
        for segs in groups:
            n = sum(sd.n_docs for sd in segs)
            df = np.sum([sd.df.astype(np.int64) for sd in segs], axis=0)
            avgdl = float(np.concatenate([sd.lens for sd in segs]).mean())
            for sd in segs:
                self.norm[sd.lo: sd.lo + sd.n_docs] = self.r(
                    (K1 * (1.0 - B + B * sd.lens / avgdl)).astype(self.dtype))
            self.shards.append((segs, n, df))

    def scores(self, terms):
        r = self.r
        out = np.zeros(self.data.n_docs, dtype=self.dtype)
        for segs, n, df_all in self.shards:
            for t in terms:
                df = float(df_all[t])
                idf = r(np.asarray(np.log(1.0 + (n - df + 0.5) / (df + 0.5)),
                                   dtype=self.dtype))
                for sd in segs:
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
