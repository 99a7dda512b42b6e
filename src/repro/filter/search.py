"""Filtered-search routing: widened graph search vs brute force.

The classic filtered-ANN cliff: graph traversal with a result mask
degrades as selectivity drops (ever more of the beam is spent on
non-matching nodes), while brute force over the match set gets *cheaper*
— at selectivity 0.01 a scan over matches touches 1% of the corpus with
perfect recall.  ``route`` picks the side of the cliff from the
popcount-estimated selectivity; ``widened_ef`` scales the beam so the
graph side keeps ~``ef`` *matching* candidates in flight; and
``brute_force_topk`` is the under-the-floor fallback (exact cosine when
cold vectors exist, backend distances otherwise — the same score
conventions as ``repro.core.index.rerank``).

``build_label_entries`` computes Filtered-Vamana-style per-label entry
points: the member-set medoid of every frequent label, stored alongside
the global medoid in the :class:`~repro.filter.labels.LabelStore`, so a
low-selectivity query starts *inside* its label region instead of
navigating to it from the global medoid.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bq
from repro.core.linking import medoid_scan
from repro.core.metric import MetricSpace
from repro.filter.labels import LabelStore

# below this estimated selectivity, graph navigation falls off the
# filtered-ANN cliff and brute force over the match set wins
DEFAULT_SELECTIVITY_FLOOR = 0.05


def route(selectivity: float, floor: float) -> str:
    """``"graph"`` above the selectivity floor, ``"brute"`` below."""
    return "graph" if selectivity >= floor else "brute"


def widened_ef(ef: int, selectivity: float, floor: float, n: int) -> int:
    """Scale ``ef`` so ~``ef`` *matching* candidates stay in the beam.

    A result mask at selectivity s thins the live result list by ~s, so
    the beam widens by 1/s — clamped at 1/floor (below the floor the
    router brute-forces instead) and at ``n``.  The widening factor is
    quantized to an integer multiple of ``ef``: ``ef`` is a static jit
    argument, so a continuous-valued widening would retrace the beam on
    every selectivity drift under streaming churn; quantization bounds
    the distinct compile keys at ceil(1/floor) per base ``ef``.

    ``n`` caps only the *widening* — the result never drops below the
    caller's ``ef`` (a beam wider than a small live set just carries
    padding, while an ef below the rerank ``k`` would break top-k).
    """
    widen = min(1.0 / max(selectivity, 1e-9), 1.0 / floor)
    return max(ef, min(n, ef * int(np.ceil(widen))))


def _pad_pow2(ids: np.ndarray, lo: int = 64) -> np.ndarray:
    """-1-pad a match-id list to a power-of-two length (bounded traces)."""
    size = lo
    while size < len(ids):
        size *= 2
    out = np.full((size,), -1, dtype=np.int32)
    out[: len(ids)] = ids
    return out


@functools.partial(jax.jit, static_argnames=("k",))
def _brute_cosine(queries, vectors, match_ids, k):
    """Exact cosine top-k over a -1-padded match-id list."""
    from repro.plan.trace import note_trace
    note_trace("brute_cosine")
    safe = jnp.maximum(match_ids, 0)
    cand = vectors[safe]                               # (M, D)
    sims = jnp.matmul(queries, cand.T,                 # (Q, M)
                      precision=jax.lax.Precision.HIGHEST)
    sims = jnp.where(match_ids[None, :] >= 0, sims, -jnp.inf)
    scores, pos = jax.lax.top_k(sims, k)
    ids = jnp.take_along_axis(
        jnp.broadcast_to(match_ids[None, :], sims.shape), pos, axis=-1
    )
    ids = jnp.where(jnp.isfinite(scores), ids, -1)
    return ids, scores


def brute_force_topk(
    queries: jnp.ndarray,          # (Q, D) float32, L2-normalized
    match_ids: np.ndarray,         # (M,) int32 matching node ids
    k: int,
    *,
    vectors: jnp.ndarray | None,
    backend: MetricSpace | None = None,
    reprs: jnp.ndarray | None = None,
) -> tuple[np.ndarray, np.ndarray]:
    """Exact top-k over the match set (the sub-floor fallback).

    With cold ``vectors`` the scores are cosine similarity (identical
    scale to the reranked graph path).  Without, ``backend``/``reprs``
    compute negated backend distances — the ``rerank=False`` scale of
    ``repro.core.index.topk_by_dist``.
    """
    nq = int(queries.shape[0])
    # route telemetry: brute-side query volume, next to the graph side's
    # plan counters (lazy leaf import, same pattern as note_trace)
    from repro.obs.metrics import get_default_registry
    get_default_registry().counter(
        "quiver_brute_queries_total",
        "queries served by the exact brute-force route",
    ).inc(nq)
    if len(match_ids) == 0:
        return (np.full((nq, k), -1, np.int32),
                np.full((nq, k), -np.inf, np.float32))
    # pad to >= k as well: top_k's k may not exceed the candidate axis
    # (missing hits come back as -1/-inf, same as the graph path)
    padded = jnp.asarray(
        _pad_pow2(np.asarray(match_ids, np.int32), lo=max(64, k))
    )
    if vectors is not None:
        ids, scores = _brute_cosine(queries, vectors, padded, k)
        return np.asarray(ids), np.asarray(scores)
    assert backend is not None and reprs is not None, (
        "brute force without cold vectors needs the metric backend"
    )
    valid = padded >= 0
    dists = jax.vmap(
        lambda q: backend.dist_fn(q, jnp.maximum(padded, 0), valid)
    )(reprs)
    dists = jnp.where(valid[None, :], dists, jnp.inf)
    scores, pos = jax.lax.top_k(-dists, k)
    ids = jnp.take_along_axis(
        jnp.broadcast_to(padded[None, :], dists.shape), pos, axis=-1
    )
    ids = jnp.where(jnp.isfinite(scores), ids, -1)
    return np.asarray(ids), np.asarray(scores)


def build_label_entries(
    store: LabelStore,
    backend: MetricSpace,
    *,
    vectors: jnp.ndarray | None = None,
    node_valid: jnp.ndarray | None = None,
    min_count: int = 32,
    chunk: int = 4096,
) -> int:
    """Fill ``store.entries`` with per-label medoids; returns how many.

    For every label whose member count is >= ``min_count`` (frequent
    labels — rare ones route to brute force anyway), the member set's
    centroid is encoded into the backend's query representation and a
    masked medoid scan picks the closest member.  ``node_valid``
    restricts members to live nodes (streaming).
    """
    built = 0
    counts = store.counts
    for label in range(store.n_labels):
        if counts[label] < min_count:
            store.entries[label] = -1
            continue
        member = store.member_mask(label)
        if node_valid is not None:
            member = member & node_valid
        member_f = member.astype(jnp.float32)
        denom = jnp.maximum(member_f.sum(), 1.0)
        if vectors is not None:
            c = (vectors * member_f[:, None]).sum(0) / denom
        else:
            levels = bq.decode_levels(backend.sigs)
            c = (levels * member_f[:, None]).sum(0) / denom
        centroid = backend.encode_queries(c[None])[0]
        store.entries[label] = int(
            medoid_scan(backend, centroid, chunk=chunk, node_valid=member)
        )
        built += 1
    return built
