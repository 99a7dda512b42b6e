"""Non-graph baselines + ground truth (paper §5.1 "Exact Flat baselines")."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _normalize(x):
    return x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True), 1e-12)


@functools.partial(jax.jit, static_argnames=("k",))
def _flat_block(queries, base, k):
    # HIGHEST: the TPU default is one bf16 pass, and this is the exact
    # reference every recall is measured against
    sims = jnp.matmul(queries, base.T, precision=jax.lax.Precision.HIGHEST)
    scores, ids = jax.lax.top_k(sims, k)
    return ids, scores


def flat_search(
    vectors: jnp.ndarray,
    queries: jnp.ndarray,
    k: int = 10,
    *,
    query_batch: int = 128,
) -> tuple[np.ndarray, np.ndarray]:
    """Exact brute-force cosine top-k (ground truth / Flat baseline)."""
    base = _normalize(jnp.asarray(vectors, jnp.float32))
    queries = _normalize(jnp.asarray(queries, jnp.float32))
    all_ids, all_scores = [], []
    for s in range(0, queries.shape[0], query_batch):
        ids, scores = _flat_block(queries[s:s + query_batch], base, k)
        all_ids.append(np.asarray(ids))
        all_scores.append(np.asarray(scores))
    return np.concatenate(all_ids), np.concatenate(all_scores)


def recall_at_k(pred_ids: np.ndarray, true_ids: np.ndarray) -> float:
    """Mean |pred ∩ true| / k over queries (Recall@k, the paper's metric)."""
    k = true_ids.shape[1]
    hits = 0
    for p, t in zip(pred_ids, true_ids):
        hits += len(set(p[:k].tolist()) & set(t.tolist()))
    return hits / (k * len(true_ids))
