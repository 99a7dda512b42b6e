"""The plain reference and the comparison that decides ``correct``.

The reference is exact cosine top-k by brute force in float64 on the
host, over the benchmark's own corpus (unit-norm, so the inner product
is the cosine).  It imports nothing of the program.

The comparison holds what the timed path served to four numbers, each
with its limit (``LIMITS``; how each was set is in PERF.md):

* ``missing``: requests due in the window that never got an answer.
* ``bad_rows``: answered rows with an id outside the corpus, a repeated
  id, a non-finite score, or scores not in descending order.
* ``recall_at_10``: served top-k against the exact top-k, over every
  answered query.  The configuration states the floor: the paper's
  recall@10 at ef=64 on contrastive embeddings (>= 0.88).
* ``score_gap``: the widest gap between a served score and the float64
  cosine of the query with the served id, over a sample of answered
  queries drawn from the seed.  The configuration states float32
  cosine scores at ``Precision.HIGHEST``: three bfloat16 passes
  (``Precision.HIGH``) read twenty times the program's gap, one pass
  two thousand times.
"""

from __future__ import annotations

import numpy as np

from stats import recall

# name -> (limit, how the value must hold against it)
LIMITS = {
    "missing": (0, "<="),
    "bad_rows": (0, "<="),
    "recall_at_10": (None, ">="),      # from the configuration
    "score_gap": (1.5e-6, "<="),
}
SAMPLE_ROWS = 4096


def exact_topk(base: np.ndarray, queries: np.ndarray, k: int,
               block: int = 256) -> tuple[np.ndarray, np.ndarray]:
    """Exact (ids, cosines) of the k nearest base rows per query,
    float64, in blocks of query rows."""
    b64 = np.asarray(base, dtype=np.float64)
    ids = np.empty((len(queries), k), dtype=np.int64)
    sims = np.empty((len(queries), k), dtype=np.float64)
    for s in range(0, len(queries), block):
        q = np.asarray(queries[s:s + block], dtype=np.float64)
        scores = q @ b64.T
        part = np.argpartition(-scores, k, axis=1)[:, :k]
        top = np.take_along_axis(scores, part, axis=1)
        order = np.argsort(-top, axis=1, kind="stable")
        ids[s:s + block] = np.take_along_axis(part, order, axis=1)
        sims[s:s + block] = np.take_along_axis(top, order, axis=1)
    return ids, sims


def bad_rows(ids: np.ndarray, scores: np.ndarray, n: int) -> int:
    """Rows that no exact answer could be: ids out of range or
    repeated, scores non-finite or not descending."""
    ids = np.asarray(ids)
    scores = np.asarray(scores, dtype=np.float64)
    out_of_range = ((ids < 0) | (ids >= n)).any(axis=1)
    srt = np.sort(ids, axis=1)
    repeated = (srt[:, 1:] == srt[:, :-1]).any(axis=1)
    nonfinite = ~np.isfinite(scores).all(axis=1)
    unsorted = (np.diff(scores, axis=1) > 0).any(axis=1)
    return int((out_of_range | repeated | nonfinite | unsorted).sum())


def score_gap(ids: np.ndarray, scores: np.ndarray, queries: np.ndarray,
              base: np.ndarray) -> float:
    """Widest |served score - float64 cosine(query, base[served id])|;
    ids outside the corpus count as an infinite gap."""
    ids = np.asarray(ids)
    if ((ids < 0) | (ids >= len(base))).any():
        return float("inf")
    rows = np.asarray(base, dtype=np.float64)[ids]           # (Q, k, d)
    truth = np.einsum("qd,qkd->qk", np.asarray(queries, np.float64), rows)
    gap = np.abs(np.asarray(scores, dtype=np.float64) - truth)
    return float(np.nan_to_num(gap, nan=np.inf).max())


def compare(*, served_ids: np.ndarray, served_scores: np.ndarray,
            pool_rows: np.ndarray, missing: int, base: np.ndarray,
            pool: np.ndarray, truth_ids: np.ndarray, recall_floor: float,
            rng: np.random.Generator) -> dict:
    """Every compared number beside its limit.

    ``served_*`` are the answered query rows (Q, k); ``pool_rows`` the
    pool index each answered row asked; ``truth_ids`` the exact top-k
    of every pool member.  Returns ``{name: {"value", "limit", "holds"}}``
    plus ``"correct"``."""
    served_ids = np.asarray(served_ids)
    served_scores = np.asarray(served_scores)
    pool_rows = np.asarray(pool_rows)
    take = (np.arange(len(pool_rows)) if len(pool_rows) <= SAMPLE_ROWS
            else np.sort(rng.choice(len(pool_rows), SAMPLE_ROWS,
                                    replace=False)))
    values = {
        "missing": int(missing),
        "bad_rows": bad_rows(served_ids, served_scores, len(base)),
        "recall_at_10": (recall(served_ids, truth_ids[pool_rows])
                         if len(pool_rows) else 0.0),
        "score_gap": (score_gap(served_ids[take], served_scores[take],
                                pool[pool_rows[take]], base)
                      if len(pool_rows) else float("inf")),
    }
    out = {}
    ok = True
    for name, (limit, holds) in LIMITS.items():
        if name == "recall_at_10":
            limit = recall_floor
        v = values[name]
        good = v <= limit if holds == "<=" else v >= limit
        ok &= bool(good)
        out[name] = {"value": v, "limit": limit, "holds": holds}
    out["correct"] = ok
    return out
