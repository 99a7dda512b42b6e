"""Streaming graph surgery: live-masked linking and FreshDiskANN repair.

Pure device-side functions, meant to be called *inside* a jit whose
arguments are the mutable index's preallocated arrays (see
``repro.stream.mutable``).  Everything routes through the registered
metric backend that the caller constructed from those arrays — the
repair never leaves the metric space the graph was built in, so no
float topology creeps back after consolidation.

``repair_rows`` is the FreshDiskANN delete-consolidation step: for a
row that points at tombstones, the candidate pool becomes

    (live out-neighbours of the row)
  ∪ (live out-neighbours of each dead out-neighbour)

— the dead node's edges are spliced across it — and the pool is
alpha-pruned with the backend's own ``dist_many``/``pairwise``,
exactly the criterion used at build time (Vamana Alg. 1).
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.core import linking
from repro.core.metric import MetricSpace
from repro.core.prune import alpha_prune_batch

BIG = jnp.float32(3.0e38)


def link_chunk(
    backend: MetricSpace,
    adj,
    deg,
    live,
    chunk_ids,               # (B,) int32, -1 padded
    medoid,
    *,
    ef: int,
    pool: int,
    r: int,
    alpha: float,
    expand: int,
    r_total: int,
):
    """Insert one chunk of freshly-binarized nodes into the live graph.

    The paper's chunked concurrent linking (§4.1) with a live mask:
    beam-search candidates are restricted to live nodes, so new edges
    never target tombstones, then forward rows are installed and
    reverse edges scatter-appended — the shared batch-build primitives.
    """
    fwd_ids, *_ = linking.chunk_forward(
        backend, adj, chunk_ids, medoid,
        ef=ef, pool=pool, r=r, alpha=alpha, expand=expand,
        node_valid=live,
    )
    adj, deg = linking.apply_forward(
        adj, deg, chunk_ids, fwd_ids, r_total=r_total
    )
    adj, deg, added, _, _ = linking.reverse_append(
        adj, deg, chunk_ids, fwd_ids, r_total=r_total
    )
    return adj, deg, added


def overflow_rows(
    backend: MetricSpace, adj, deg, live, row_ids, *,
    r: int, alpha: float, r_total: int,
):
    """Live-masked re-prune of degree-overflowed rows."""
    return linking.consolidate_rows(
        backend, adj, deg, row_ids,
        r=r, alpha=alpha, r_total=r_total, node_valid=live,
    )


def _dedup_rows(cands: jnp.ndarray) -> jnp.ndarray:
    """Per-row candidate dedup: repeats of an id collapse to -1."""
    b = cands.shape[0]
    order = jnp.argsort(cands, axis=1)
    s = jnp.take_along_axis(cands, order, axis=1)
    dup_sorted = jnp.concatenate(
        [jnp.zeros((b, 1), dtype=jnp.bool_),
         (s[:, 1:] == s[:, :-1]) & (s[:, 1:] >= 0)],
        axis=1,
    )
    dup = jnp.zeros_like(dup_sorted).at[
        jnp.arange(b)[:, None], order
    ].set(dup_sorted)
    return jnp.where(dup, -1, cands)


def repair_rows(
    backend: MetricSpace,
    adj,
    deg,
    live,
    row_ids,                 # (B,) int32, -1 padded
    *,
    r: int,
    alpha: float,
    r_total: int,
    pool: int,
):
    """Splice dead out-neighbours' edges into ``row_ids``' pools and
    alpha-prune in the backend's metric space (delete consolidation)."""
    safe_row = jnp.maximum(row_ids, 0)
    rows = adj[safe_row]                                 # (B, T)
    nbr_safe = jnp.maximum(rows, 0)
    nbr_ok = rows >= 0
    nbr_live = nbr_ok & live[nbr_safe]
    nbr_dead = nbr_ok & ~live[nbr_safe]

    # one hop through each dead neighbour: its own live out-edges
    second = adj[jnp.where(nbr_dead, rows, 0)]           # (B, T, T)
    sec_ok = nbr_dead[:, :, None] & (second >= 0)
    sec_ok = sec_ok & live[jnp.maximum(second, 0)]

    b = rows.shape[0]
    cands = jnp.concatenate(
        [jnp.where(nbr_live, rows, -1),
         jnp.where(sec_ok, second, -1).reshape(b, -1)],
        axis=1,
    )                                                    # (B, T + T*T)
    cands = jnp.where(cands == row_ids[:, None], -1, cands)
    cands = _dedup_rows(cands)

    valid = cands >= 0
    safe = jnp.maximum(cands, 0)
    target_repr = backend.query_repr(safe_row)
    d = backend.dist_many(target_repr, safe, valid)
    d = jnp.where(valid, d, BIG)
    order = jnp.argsort(d, axis=-1)[:, :pool]
    cids = jnp.take_along_axis(cands, order, axis=-1)
    cdists = jnp.take_along_axis(d, order, axis=-1)

    pw = backend.pairwise(jnp.maximum(cids, 0))
    new_ids, _ = alpha_prune_batch(cids, cdists, pw, r=r, alpha=alpha)
    return linking.scatter_rows(adj, deg, row_ids, new_ids,
                                r_total=r_total)
