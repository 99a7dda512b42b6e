"""Shared mask-aware Vamana linking primitives (QuIVer §4.1 + streaming).

One owner for the chunk-level graph surgery that both the batch builder
(``repro.core.vamana``) and the streaming subsystem (``repro.stream``)
perform: beam-search a chunk of nodes, alpha-prune their candidate
pools, install forward edges, scatter-append reverse edges, and re-prune
overflowing rows.  The batch builder wraps these in jitted functions
whose cache keys on a *static* backend (arrays frozen for the whole
build); the streaming subsystem jits its own wrappers that take the
mutable arrays as traced arguments and construct the registered backend
inside the trace — same primitives, no retrace per mutation.

Two forms of masking make the primitives streaming-safe while staying
bit-identical on the batch path:

* ``node_valid`` — the live/tombstone mask of a mutable index.  When
  given, beam-search candidates, re-prune pools and medoid scans are
  restricted to live nodes (dead nodes are still *traversed*, see
  ``repro.core.beam``).  ``None`` (the batch build) means all nodes.
* ``chunk_ids`` / ``row_ids`` may contain ``-1`` padding — streaming
  insert batches rarely fill a whole chunk, and padded entries must not
  touch the graph.  Scatters route padded rows to a trash row.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.beam import batched_beam_search
from repro.core.metric import MetricSpace
from repro.core.prune import alpha_prune_batch, alpha_prune_stats_batch

BIG = jnp.float32(3.0e38)


def chunk_forward(
    backend: MetricSpace,
    adj: jnp.ndarray,
    chunk_ids: jnp.ndarray,       # (B,) int32, -1 padded
    medoid: jnp.ndarray,
    *,
    ef: int,
    pool: int,
    r: int,
    alpha: float,
    expand: int = 1,
    node_valid: jnp.ndarray | None = None,
):
    """Beam-search a chunk of nodes and alpha-prune their candidates.

    Returns ((B, r) forward ids, (B, r) dists, (B,) hops, (B,) prune
    pool sizes, (B,) occlusion counts, (B,) relaxed counts).  The last
    three are the build telemetry DESIGN.md §15 aggregates — how full
    each node's candidate pool was when it entered the alpha-prune, how
    many candidates the diversity criterion occluded, and how many kept
    edges only its relaxed tier admitted (same trace; reductions over
    masks the prune already computes).  Rows whose ``chunk_ids`` entry
    is -1 come back all -1 / 0.
    """
    pad_row = (chunk_ids < 0)[:, None]
    queries = backend.query_repr(jnp.maximum(chunk_ids, 0))
    res = batched_beam_search(
        queries, adj, medoid, dist_fn=backend.dist_fn, ef=ef,
        expand=expand, node_valid=node_valid,
    )
    # remove self from each candidate list, keep the best ``pool``
    is_self = res.ids == chunk_ids[:, None]
    drop = is_self | pad_row
    cids = jnp.where(drop, -1, res.ids)
    cdists = jnp.where(drop, BIG, res.dists)
    order = jnp.argsort(cdists, axis=-1)[:, :pool]
    cids = jnp.take_along_axis(cids, order, axis=-1)
    cdists = jnp.take_along_axis(cdists, order, axis=-1)

    safe = jnp.maximum(cids, 0)
    pw = backend.pairwise(safe)
    fwd_ids, fwd_dists, pool_sizes, occluded, relaxed = (
        alpha_prune_stats_batch(cids, cdists, pw, r=r, alpha=alpha)
    )
    return fwd_ids, fwd_dists, res.hops, pool_sizes, occluded, relaxed


def scatter_rows(adj, deg, row_ids, edge_ids, *, r_total):
    """Overwrite ``row_ids``' adjacency rows with ``edge_ids``.

    ``edge_ids`` (B, <= r_total) is right-padded to the full row width;
    degree counters are reset to the count of valid edges.  ``row_ids``
    entries of -1 (chunk padding) scatter into a trash row and leave
    the graph untouched.
    """
    n = adj.shape[0]
    rows = jnp.full(
        (edge_ids.shape[0], r_total), -1, dtype=jnp.int32
    ).at[:, : edge_ids.shape[1]].set(edge_ids)
    tgt = jnp.where(row_ids >= 0, row_ids, n)
    adj_pad = jnp.concatenate(
        [adj, jnp.full((1, r_total), -1, dtype=jnp.int32)], axis=0
    ).at[tgt].set(rows)
    deg_pad = jnp.concatenate(
        [deg, jnp.zeros((1,), dtype=jnp.int32)]
    ).at[tgt].set((edge_ids >= 0).sum(-1).astype(jnp.int32))
    return adj_pad[:n], deg_pad[:n]


def apply_forward(adj, deg, chunk_ids, fwd_ids, *, r_total):
    """Install forward-edge rows for a chunk (padded ids -> trash row)."""
    return scatter_rows(adj, deg, chunk_ids, fwd_ids, r_total=r_total)


def reverse_append(adj, deg, chunk_ids, fwd_ids, *, r_total):
    """Scatter-append reverse edges src -> tgt with capacity drop.

    Returns (adj, deg, added, proposed, dropped): ``proposed`` counts
    the reverse edges not already in their target row, ``dropped``
    those of them that found no free slot below ``r_total``.
    """
    n = adj.shape[0]
    b, r = fwd_ids.shape
    tgt = fwd_ids.reshape(-1)                                   # (B*R,)
    src = jnp.repeat(chunk_ids, r)                              # (B*R,)
    valid = (tgt >= 0) & (src >= 0)
    tgt_safe = jnp.where(valid, tgt, 0)

    # skip proposals whose edge already exists
    exists = (adj[tgt_safe] == src[:, None]).any(-1)
    valid = valid & ~exists

    # rank of each proposal within its target group (sorted by target)
    key_sort = jnp.where(valid, tgt, n + 1)
    order = jnp.argsort(key_sort)
    tgt_s, src_s, valid_s = key_sort[order], src[order], valid[order]
    idx = jnp.arange(tgt_s.shape[0])
    boundary = jnp.concatenate(
        [jnp.array([True]), tgt_s[1:] != tgt_s[:-1]]
    )
    seg_start = jax.lax.cummax(jnp.where(boundary, idx, 0))
    rank = idx - seg_start

    tgt_w = jnp.where(valid_s, tgt_s, n)       # n == trash row
    slot = deg[jnp.minimum(tgt_w, n - 1)] + rank
    ok = valid_s & (slot < r_total)
    tgt_w = jnp.where(ok, tgt_w, n)
    slot_w = jnp.where(ok, slot, r_total)      # r_total == trash col

    adj_pad = jnp.full((n + 1, r_total + 1), -1, dtype=jnp.int32)
    adj_pad = adj_pad.at[:n, :r_total].set(adj)
    adj_pad = adj_pad.at[tgt_w, slot_w].set(
        jnp.where(ok, src_s, -1).astype(jnp.int32)
    )
    adj = adj_pad[:n, :r_total]
    deg = deg.at[jnp.minimum(tgt_w, n - 1)].add(
        ok.astype(jnp.int32) * (tgt_w < n)
    )
    return adj, deg, ok.sum(), valid.sum(), (valid_s & ~ok).sum()


def consolidate_rows(
    backend: MetricSpace,
    adj,
    deg,
    row_ids,                      # (B,) int32, -1 padded
    *,
    r: int,
    alpha: float,
    r_total: int,
    node_valid: jnp.ndarray | None = None,
):
    """Re-prune rows back down to <= r edges (deg overflow / repair).

    With ``node_valid``, dead neighbours are dropped from the pool
    before pruning.  Padded ``row_ids`` entries leave the graph alone.
    """
    safe_row_ids = jnp.maximum(row_ids, 0)
    rows = adj[safe_row_ids]                             # (B, r_total)
    ok = rows >= 0
    if node_valid is not None:
        ok = ok & node_valid[jnp.maximum(rows, 0)]
    rows = jnp.where(ok, rows, -1)
    safe = jnp.maximum(rows, 0)
    # distance of each neighbour to the row's own node
    target_repr = backend.query_repr(safe_row_ids)
    dists = backend.dist_many(target_repr, safe, ok)
    dists = jnp.where(ok, dists, BIG)
    pw = backend.pairwise(safe)
    new_ids, _ = alpha_prune_batch(rows, dists, pw, r=r, alpha=alpha)
    return scatter_rows(adj, deg, row_ids, new_ids, r_total=r_total)


def shard_medoids(
    backend: MetricSpace,
    cent_reprs,                   # (L, ...) query representations
    shard_ids,                    # (L, S) int32, -1 padded
):
    """Batched shard-restricted medoid selection.

    The vectorized form of :func:`medoid_scan`: for each of L random
    shards, pick the member nearest its shard centroid representation.
    One ``dist_many`` call scores all (L, S) members at once — this is
    how the IVF layer picks k-means-free centroids (DESIGN.md §13).
    Returns (L,) int32 medoid node ids.
    """
    valid = shard_ids >= 0
    d = backend.dist_many(cent_reprs, jnp.maximum(shard_ids, 0), valid)
    d = jnp.where(valid, d, BIG)
    best = jnp.argmin(d, axis=-1)
    return jnp.take_along_axis(shard_ids, best[:, None], axis=-1)[:, 0]


def medoid_scan(
    backend: MetricSpace,
    centroid_repr,
    *,
    chunk: int,
    node_valid: jnp.ndarray | None = None,
):
    """Blockwise argmin of distance-to-centroid (restricted to live)."""
    n = backend.n
    n_pad = ((n + chunk - 1) // chunk) * chunk
    ids = jnp.arange(n_pad, dtype=jnp.int32) % n

    def scan_fn(best, block_ids):
        d = backend.dist_fn(
            centroid_repr, block_ids, jnp.ones_like(block_ids, jnp.bool_)
        )
        if node_valid is not None:
            d = jnp.where(node_valid[block_ids], d, BIG)
        i = jnp.argmin(d)
        cand = (d[i], block_ids[i])
        better = cand[0] < best[0]
        return (
            jnp.where(better, cand[0], best[0]),
            jnp.where(better, cand[1], best[1]),
        ), None

    (best_d, best_i), _ = jax.lax.scan(
        scan_fn,
        (BIG, jnp.int32(0)),
        ids.reshape(-1, chunk),
    )
    return best_i
