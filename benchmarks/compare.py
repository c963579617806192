"""The comparison that decides ``correct``: what the timed path answered
against the configuration's plain reference, tie-aware.

Numbers compared, each against a limit that the configuration's file
states (``limits``):

``failed``         requests of the window that never gave a usable answer
``malformed``      responses with the wrong number of hits, a repeated or
                   unknown id, or hits out of order
``score_err``      widest relative gap between a returned score and the
                   reference's score of that id
``rank_gap``       widest relative gap by which a doc left out beats the
                   worst doc returned, by the reference's scores (0 where
                   the top-k is right up to ties)
``device_faults``  host fallbacks, breaker failures and trips, poisoned
                   results, or a backend that is not the one jax reports
"""

from __future__ import annotations

import numpy as np

NUMBERS = ("failed", "malformed", "score_err", "rank_gap", "device_faults")


def hit_rows(resp: dict) -> list:
    return [(h["_id"], float(h["_score"])) for h in resp["hits"]["hits"]]


def usable(resp) -> bool:
    """A response that answers: no shard failed, nothing timed out."""
    return (isinstance(resp, dict) and "hits" in resp
            and resp.get("_shards", {}).get("failed", 1) == 0
            and not resp.get("timed_out", True))


def row_ids(rows: list) -> list:
    out = []
    for i, _ in rows:
        try:
            out.append(int(i))
        except (TypeError, ValueError):
            out.append(-1)
    return out


def judge_rows(rows: list, ref: np.ndarray, runner_up: float, n_match: int,
               k: int) -> tuple:
    """(malformed, score_err, rank_gap) of one response."""
    ids = row_ids(rows)
    got = np.array([s for _, s in rows], dtype=np.float64)
    want = min(k, n_match)
    malformed = (len(rows) != want or len(set(ids)) != len(ids)
                 or min(ids, default=0) < 0 or bool((ref <= 0).any())
                 or bool((np.diff(got) > 0).any())
                 or not np.isfinite(got).all())
    if malformed or not len(rows):
        return int(malformed), 0.0, 0.0
    score_err = float(np.max(np.abs(got - ref) / ref))
    worst = float(ref.min())
    rank_gap = max(0.0, (runner_up - worst) / worst)
    return 0, score_err, rank_gap


def compare(reference, queries: list, rows_list: list, k: int) -> dict:
    """Aggregate over the compared responses: counts add, gaps take the
    widest."""
    out = {"malformed": 0, "score_err": 0.0, "rank_gap": 0.0}
    ids_list = [row_ids(rows) for rows in rows_list]
    judged = reference.judge_many(queries, ids_list)
    for rows, (ref, runner_up, n_match) in zip(rows_list, judged):
        bad, err, gap = judge_rows(rows, ref, runner_up, n_match, k)
        out["malformed"] += bad
        out["score_err"] = max(out["score_err"], err)
        out["rank_gap"] = max(out["rank_gap"], gap)
    return out


def verdict(numbers: dict, limits: dict) -> tuple:
    """(correct, lines): every number beside its limit, in a fixed
    order."""
    lines, correct = [], True
    for name in NUMBERS:
        value, limit = numbers[name], limits[name]
        ok = value <= limit
        correct = correct and ok
        lines.append(f"{name} {value:.6g} limit {limit:.6g} "
                     f"{'ok' if ok else 'FAILED'}")
    return correct, lines
