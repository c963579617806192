"""Plain reference of the configuration ``yfcc-10m-filtered-knn``: exact L2
nearest neighbours among the rows that carry every required tag, scored
as the k-NN plugin does (``1 / (1 + d2)``).

The rows that pass come from the seed's own tag lists, row by row (the
index the program searches is built the other way round, tag by tag).
Their squared distances are measured in float64, which holds them exactly
(the vectors lie on a 1/64 grid), over exactly those rows and in blocks,
so that a head tag's million rows fit.  It imports nothing of the program
and nothing of the benchmark.

``precision="bf16x3"`` is the control: the scan's matrix product at three
bfloat16 passes (``jax.lax.Precision.HIGH``, one step below the six the
configuration states) in float32's ``v2 - 2 * dots + q2``, over the rows
that pass, put in the program's place; ``"bfloat16"`` is one pass.
"""

import numpy as np

BLOCK = 65536
GRID = 64                        # the vectors' values are whole 1/64ths
PRECISIONS = ("float64", "bfloat16", "bf16x3")


def bf16(x):
    u = np.ascontiguousarray(x, dtype=np.float32).view(np.uint32)
    r = ((u >> np.uint32(16)) & np.uint32(1)) + np.uint32(0x7FFF)
    return ((u + r) & np.uint32(0xFFFF0000)).view(np.float32)


def score(d2):
    return 1.0 / (1.0 + d2)


class Reference:
    def __init__(self, cfg, data, precision="float64"):
        if precision not in PRECISIONS:
            raise ValueError(f"unknown precision [{precision}]")
        self.k = cfg["k"]
        self.v = data.vectors
        self.segments = data.segments
        self.vocab = cfg["vocab"]
        self.precision = precision

    # -- the filter ---------------------------------------------------------

    def rows_by_tag(self, tags) -> dict:
        """{tag: ascending shard-wide rows whose bag holds it}, for all
        ``tags`` in one pass over every row's bag."""
        tags = np.unique(np.asarray(list(tags), dtype=np.int64))
        if len(tags) > np.iinfo(np.int16).max:
            raise ValueError("too many distinct tags for one pass")
        code = np.full(self.vocab, -1, dtype=np.int16)
        code[tags] = np.arange(len(tags))
        codes, rows = [], []
        for sd in self.segments:
            c = code[sd.row_tags]
            at = np.flatnonzero(c >= 0)
            codes.append(c[at])
            rows.append(np.searchsorted(sd.row_starts, at, side="right")
                        - 1 + sd.lo)
        codes, rows = np.concatenate(codes), np.concatenate(rows)
        order = np.argsort(codes, kind="stable")      # rows stay ascending
        ends = np.searchsorted(codes[order], np.arange(len(tags) + 1))
        return {int(t): rows[order[ends[i]: ends[i + 1]]]
                for i, t in enumerate(tags)}

    def passing(self, queries) -> list:
        """For each query the ascending rows that carry all its tags."""
        by_tag = self.rows_by_tag({t for tags, _v in queries for t in tags})
        out = []
        for tags, _v in queries:
            rows = by_tag[tags[0]]
            for t in tags[1:]:
                rows = rows[np.isin(rows, by_tag[t], assume_unique=True)]
            out.append(rows)
        return out

    # -- distances over the rows that pass ---------------------------------

    def _d2_exact(self, q, rows):
        """float64 squared distances to ``rows``.  Rows and query lie on
        the 1/64 grid below 256, so their float32 difference is exact (15
        bits) and only the squares and their sum need float64; a query off
        the grid is measured in float64 throughout."""
        q = np.asarray(q)
        on_grid = (q.dtype == np.float32 and np.array_equal(
            q * GRID, np.round(q * GRID)) and 0 <= q.min() and q.max() < 256)
        if not on_grid:
            q = q.astype(np.float64)
        out = np.empty(len(rows), dtype=np.float64)
        for lo in range(0, len(rows), BLOCK):
            diff = self.v[rows[lo: lo + BLOCK]]
            diff = diff - q if on_grid else diff.astype(np.float64) - q
            out[lo: lo + BLOCK] = np.einsum("ij,ij->i", diff, diff,
                                            dtype=np.float64)
        return out

    def _d2_low(self, q, rows):
        """float32 ``v2 - 2 * dots + q2`` as the program's scan forms it,
        the product at this reference's (lower) precision."""
        q = np.asarray(q, dtype=np.float32)
        q_hi = bf16(q)
        out = np.empty(len(rows), dtype=np.float32)
        for lo in range(0, len(rows), BLOCK):
            v = self.v[rows[lo: lo + BLOCK]]
            v_hi = bf16(v)
            dots = v_hi @ q_hi
            if self.precision == "bf16x3":
                dots += v_hi @ bf16(q - q_hi)
                dots += bf16(v - v_hi) @ q_hi
            v2 = np.einsum("ij,ij->i", v, v)
            out[lo: lo + BLOCK] = np.maximum(
                v2 - np.float32(2.0) * dots + np.float32(q @ q), 0.0)
        return out

    # -- what the comparison asks ------------------------------------------

    def judge_many(self, queries, ids_list):
        """For each response's ids: the reference's score of each (0 for
        a row that fails the filter or does not exist), the best score
        among the passing rows left out, and how many rows pass."""
        for (_tags, q), rows, ids in zip(queries, self.passing(queries),
                                         ids_list):
            s = score(self._d2_exact(q, rows))
            ids = np.asarray(ids, dtype=np.int64)
            at = np.minimum(np.searchsorted(rows, ids),
                            max(len(rows) - 1, 0))
            ok = (rows[at] == ids if len(rows)
                  else np.zeros(len(ids), dtype=bool))
            ref = np.zeros(len(ids))
            ref[ok] = s[at[ok]]
            rest = np.ones(len(rows), dtype=bool)
            rest[at[ok]] = False
            runner_up = float(s[rest].max()) if rest.any() else -np.inf
            yield ref, runner_up, len(rows)

    def topk_many(self, queries):
        """The reference in the program's place: [(row, score)] best
        first, ties by the lower row."""
        for (_tags, q), rows in zip(queries, self.passing(queries)):
            if self.precision == "float64":
                s = score(self._d2_exact(q, rows))
            else:
                s = (np.float32(1.0) / (np.float32(1.0)
                                        + self._d2_low(q, rows)))
            top = np.lexsort((rows, -s))[: self.k]
            yield [(int(rows[i]), float(s[i])) for i in top]
