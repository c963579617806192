"""Plain reference of the configuration ``sift-128-exact-knn``: exact L2
nearest neighbours, scored as the k-NN plugin does (``1 / (1 + d2)``).

The vectors lie on a 1/64 grid, so float64 holds every squared distance
exactly.  A float32 matrix product over all vectors picks 64 candidates a
query; those, and whatever ids a response returned, are then measured in
float64.  It imports nothing of the program and nothing of the benchmark.

``precision="bfloat16"`` is the control: the same scan with the matrix
product's operands rounded to bfloat16 (one pass of the chip's matrix
unit), put in the program's place.  ``"bf16x3"`` is three such passes
(``jax.lax.Precision.HIGH``), read once for PERF.md.
"""

import numpy as np

CANDIDATES = 64
CHUNK = 128


def bf16(x):
    u = np.ascontiguousarray(x, dtype=np.float32).view(np.uint32)
    r = ((u >> np.uint32(16)) & np.uint32(1)) + np.uint32(0x7FFF)
    return ((u + r) & np.uint32(0xFFFF0000)).view(np.float32)


class Reference:
    def __init__(self, cfg, data, precision="float64"):
        if precision not in ("float64", "bfloat16", "bf16x3"):
            raise ValueError(f"unknown precision [{precision}]")
        self.k = cfg["k"]
        self.v = data.vectors
        self.precision = precision
        self.v2 = np.einsum("ij,ij->i", self.v, self.v)
        if precision != "float64":
            self.v_hi = bf16(self.v)
            self.v_lo = (bf16(self.v - self.v_hi)
                         if precision == "bf16x3" else None)

    def _d2(self, q):
        """Squared distances [n, len(q)] in float32, with the matrix
        product at this reference's precision."""
        q = np.asarray(q, dtype=np.float32)
        if self.precision == "float64":
            dots = self.v @ q.T
        else:
            q_hi = bf16(q)
            dots = self.v_hi @ q_hi.T
            if self.v_lo is not None:
                dots += self.v_hi @ bf16(q - q_hi).T
                dots += self.v_lo @ q_hi.T
        q2 = np.einsum("ij,ij->i", q, q)
        return np.maximum(self.v2[:, None] - 2.0 * dots + q2[None, :], 0.0)

    def _exact(self, q, ids):
        diff = self.v[ids].astype(np.float64) - np.asarray(q, np.float64)
        return np.einsum("ij,ij->i", diff, diff)

    def judge_many(self, queries, ids_list):
        n = len(self.v)
        c = min(CANDIDATES, n)
        for lo in range(0, len(queries), CHUNK):
            qs = np.asarray(queries[lo: lo + CHUNK], dtype=np.float32)
            d2 = self._d2(qs)
            for j in range(len(qs)):
                col = d2[:, j]
                cand = (np.argpartition(col, c - 1)[:c] if c < n
                        else np.arange(n))
                ids = np.asarray(ids_list[lo + j], dtype=np.int64)
                ok = (ids >= 0) & (ids < n)
                exact_c = self._exact(qs[j], cand)
                if c < n and (col[cand].max()
                              < np.sort(exact_c)[min(self.k, c - 1)] + 64.0):
                    raise RuntimeError(
                        "reference: 64 candidates do not bound the top "
                        f"{self.k} of query {lo + j}")
                ref = np.zeros(len(ids))
                ref[ok] = 1.0 / (1.0 + self._exact(qs[j], ids[ok]))
                rest = ~np.isin(cand, ids[ok])
                runner_up = (float((1.0 / (1.0 + exact_c[rest])).max())
                             if rest.any() else -np.inf)
                yield ref, runner_up, n

    def topk_many(self, queries):
        for lo in range(0, len(queries), CHUNK):
            d2 = self._d2(queries[lo: lo + CHUNK])
            scores = (np.float32(1.0) / (np.float32(1.0) + d2)).astype(
                np.float32)
            for j in range(d2.shape[1]):
                col = scores[:, j]
                k = min(self.k, len(col))
                top = np.argpartition(-col, k - 1)[:k]
                top = top[np.lexsort((top, -col[top]))]
                yield [(int(i), float(col[i])) for i in top]
