"""BQ-native Vamana graph construction (QuIVer §3.2 / §4.1).

Two-stage batch construction, adapted from the paper's lock-based
concurrency to pure-functional SPMD:

* **Stage 0 — bulk pre-installation**: all signatures are computed in one
  embarrassingly-parallel pass (``repro.kernels.binarize``) and the flat
  adjacency table is allocated once (``(N, R + slack)`` int32).
* **Stage 1 — chunked concurrent linking**: nodes are processed in chunks
  of ~256.  Each chunk runs `vmap`-batched beam searches against the
  frozen current graph, alpha-prunes its candidate pools *in BQ space*,
  writes forward edges, and scatter-appends reverse edges.  Rows that
  overflow the degree bound R are re-pruned (batched) during periodic
  consolidation — the functional analogue of the paper's per-node
  spin-locked re-prune, amortized exactly like DiskANN's.

The device-side chunk ops are jitted once per (shape, param) signature
and take the metric backend as a traced pytree argument, so the
signatures enter each program as an input rather than as a baked-in
constant; the host driver is a plain Python loop (this is how real
accelerator fleets drive construction too — host orchestrates, device
crunches).

The chunk-level graph surgery itself lives in ``repro.core.linking`` —
shared, mask-aware primitives with one owner, so the streaming
subsystem (``repro.stream``) inserts against a live graph with exactly
the operations this batch builder uses.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bq, linking
from repro.core.metric import MetricBackend

BIG = jnp.float32(3.0e38)


@dataclasses.dataclass(frozen=True)
class BuildParams:
    m: int = 32                  # paper: max degree 2m
    ef_construction: int = 128
    alpha: float = 1.2
    chunk: int = 256
    prune_pool: int = 128        # candidates entering alpha-prune
    reverse_slack: int = 8       # adjacency headroom for reverse appends
    consolidate_every: int = 8   # chunks between overflow re-prunes
    passes: int = 1              # full insertion passes over the data
    seed: int = 0
    beam_expand: int = 1         # beam expansion width L during build
    # IVF-seeded construction (DESIGN.md §13): seed each chunk's prune
    # pool from the node's top-p coarse lists instead of a full-graph
    # beam search — the dominant per-chunk cost drops from
    # O(hops·ef·R) graph traversal to one list scan + one gather,
    # making build time near-linear in N.  ``ivf_lists=0`` means the
    # partition's own √N default.
    ivf_candidates: bool = False
    ivf_lists: int = 0

    @property
    def r(self) -> int:          # out-degree bound
        return 2 * self.m

    @property
    def r_total(self) -> int:    # adjacency row width incl. slack
        return self.r + self.reverse_slack


# ---------------------------------------------------------------------------
# device-side chunk ops
# ---------------------------------------------------------------------------


def _init_graph(n: int, params: BuildParams, seed: int):
    key = jax.random.PRNGKey(seed)
    rand = jax.random.randint(key, (n, params.r), 0, n, dtype=jnp.int32)
    ids = jnp.arange(n, dtype=jnp.int32)[:, None]
    rand = jnp.where(rand == ids, (rand + 1) % n, rand)
    pad = jnp.full((n, params.reverse_slack), -1, dtype=jnp.int32)
    adj = jnp.concatenate([rand, pad], axis=1)
    deg = jnp.full((n,), params.r, dtype=jnp.int32)
    return adj, deg


@functools.partial(
    jax.jit,
    static_argnames=("ef", "pool", "r", "alpha", "n", "expand"),
)
def _chunk_forward(
    adj, chunk_ids, medoid, *,
    backend: MetricBackend, ef, pool, r, alpha, n, expand=1,
):
    """Beam-search a chunk of nodes and alpha-prune their candidates."""
    return linking.chunk_forward(
        backend, adj, chunk_ids, medoid,
        ef=ef, pool=pool, r=r, alpha=alpha, n=n, expand=expand,
    )


@functools.partial(
    jax.jit,
    static_argnames=("scan", "pool", "r", "alpha", "probes"),
)
def _chunk_forward_ivf(
    chunk_ids, rand_ids, sig_words, cent_words, list_ids, *,
    backend: MetricBackend, scan, pool, r, alpha, probes,
):
    """IVF-seeded chunk linking: top-p lists feed the prune pool.

    Replaces the beam search of :func:`_chunk_forward`: each chunk
    node's candidates are the members of its ``probes`` nearest coarse
    lists (scored in the build metric), topped up with ``rand_ids`` —
    random far candidates whose long edges the alpha-criterion can
    keep, preserving navigability that purely local list members would
    lose.  Duplicates between the two pools die in the prune (a
    duplicate is distance-0 from its selected twin).  Hops are 0 by
    construction — there is no traversal.
    """
    from repro.ivf import search as ivf_search

    pad_row = (chunk_ids < 0)[:, None]
    safe_chunk = jnp.maximum(chunk_ids, 0)
    reprs = backend.query_repr(safe_chunk)
    top = ivf_search.top_lists(scan, sig_words[safe_chunk], cent_words,
                               probes)
    mem, d = ivf_search.list_candidates(backend, reprs, list_ids, top)
    drop = (mem == chunk_ids[:, None]) | pad_row
    mem = jnp.where(drop, -1, mem)
    d = jnp.where(drop, BIG, d)
    n_rand = rand_ids.shape[1]
    neg, pos = jax.lax.top_k(-d, max(pool - n_rand, 1))
    local_ids = jnp.take_along_axis(mem, pos, axis=-1)
    local_dists = -neg

    rand_ok = (rand_ids >= 0) & (rand_ids != chunk_ids[:, None]) & ~pad_row
    rd = backend.dist_many(reprs, jnp.maximum(rand_ids, 0), rand_ok)
    cids = jnp.concatenate(
        [local_ids, jnp.where(rand_ok, rand_ids, -1)], axis=-1
    )
    cdists = jnp.concatenate(
        [local_dists, jnp.where(rand_ok, rd, BIG)], axis=-1
    )
    pw = backend.pairwise(jnp.maximum(cids, 0))
    fwd_ids, fwd_dists, pool_sizes, occluded = (
        linking.alpha_prune_stats_batch(
            cids, cdists, pw, r=r, alpha=alpha
        )
    )
    hops = jnp.zeros(chunk_ids.shape, dtype=jnp.int32)
    return fwd_ids, fwd_dists, hops, pool_sizes, occluded


@functools.partial(jax.jit, static_argnames=("r_total",))
def _apply_forward(adj, deg, chunk_ids, fwd_ids, *, r_total):
    return linking.apply_forward(adj, deg, chunk_ids, fwd_ids,
                                 r_total=r_total)


@functools.partial(jax.jit, static_argnames=("r_total",))
def _reverse_append(adj, deg, chunk_ids, fwd_ids, *, r_total):
    """Scatter-append reverse edges src -> tgt with capacity drop."""
    return linking.reverse_append(adj, deg, chunk_ids, fwd_ids,
                                  r_total=r_total)


@functools.partial(
    jax.jit, static_argnames=("r", "alpha", "r_total")
)
def _consolidate_rows(
    adj, deg, row_ids, *, backend: MetricBackend, r, alpha, r_total
):
    """Re-prune overflowing rows (deg > r) back down to <= r edges."""
    return linking.consolidate_rows(
        backend, adj, deg, row_ids, r=r, alpha=alpha, r_total=r_total
    )


@functools.partial(jax.jit, static_argnames=("chunk",))
def _medoid(backend: MetricBackend, centroid_repr, *, chunk: int):
    return linking.medoid_scan(backend, centroid_repr, chunk=chunk)


# ---------------------------------------------------------------------------
# host driver
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class BuildStats:
    seconds: float = 0.0
    chunks: int = 0
    consolidations: int = 0
    reverse_edges_added: int = 0
    mean_hops: float = 0.0
    # build telemetry (DESIGN.md §15): per-chunk means, averaged over
    # the whole build; occluded is the total candidate count the
    # alpha-criterion covered away
    pool_occupancy: float = 0.0    # mean pool fill / prune_pool
    survivor_ratio: float = 0.0    # mean survivors / pool
    occluded_total: int = 0


def build_graph(
    backend: MetricBackend,
    params: BuildParams,
    *,
    medoid: int | None = None,
    ivf=None,
    verbose: bool = False,
) -> tuple[jnp.ndarray, int, BuildStats]:
    """Construct a Vamana graph in ``backend``'s metric space.

    With ``params.ivf_candidates`` each chunk's prune pool is seeded
    from the node's top-p coarse lists (:mod:`repro.ivf`) instead of a
    full-graph beam search — near-linear build.  ``ivf`` is the
    :class:`~repro.ivf.IVFPartition` to seed from; when None it is
    built here from the backend's signatures (requires a
    signature-bearing build metric).

    Build stats accumulate **on device** (one lazy add per chunk) and
    materialize once at the end — the host loop never blocks on a
    device→host sync per chunk.

    Returns (adjacency (N, R+slack) int32, medoid id, stats).
    """
    t0 = time.perf_counter()
    n = backend.n
    stats = BuildStats()
    adj, deg = _init_graph(n, params, params.seed)

    if medoid is None:
        # centroid representation: encode the float mean when available,
        # else use node 0 as the entry point.
        centroid = _centroid_repr(backend)
        medoid = int(_medoid(backend, centroid, chunk=4096)) \
            if centroid is not None else 0
    medoid_arr = jnp.int32(medoid)

    scan = sig_words = probes = n_rand = None
    if params.ivf_candidates:
        if not hasattr(backend, "sigs"):
            raise ValueError(
                "ivf_candidates needs a signature-bearing build metric "
                "(bq2/bq1/adc); float32 builds must beam-search"
            )
        if ivf is None:
            from repro.ivf import build_partition
            ivf = build_partition(
                backend.sigs, n_lists=params.ivf_lists or None,
                seed=params.seed,
            )
        from repro.kernels import dispatch
        route = getattr(backend, "route", None)
        scan = dispatch.list_scan_ops(backend.sigs.dim, route=route).scan
        sig_words = backend.sigs.words
        probes = ivf.build_probes
        n_rand = max(1, min(params.prune_pool // 4, params.r))

    rng = np.random.default_rng(params.seed)
    chunk = params.chunk
    # device-side accumulators: eager jnp adds are async-dispatched, so
    # the loop enqueues work without a per-chunk host round trip
    added_acc = jnp.int32(0)
    hops_sum = jnp.float32(0.0)
    n_hop_chunks = 0
    occl_acc = jnp.int32(0)
    # per-chunk device scalars (async-dispatched; one stack+sync at the
    # end feeds the quiver_build_* histograms without blocking the loop)
    pool_occ_chunks: list = []
    surv_chunks: list = []
    occl_chunks: list = []

    for pass_idx in range(params.passes):
        order = rng.permutation(n).astype(np.int32)
        pad = (-len(order)) % chunk
        if pad:
            order = np.concatenate([order, order[:pad]])
        n_chunks = len(order) // chunk

        for ci in range(n_chunks):
            chunk_ids = jnp.asarray(order[ci * chunk:(ci + 1) * chunk])
            if params.ivf_candidates:
                rand_ids = jnp.asarray(rng.integers(
                    0, n, size=(chunk, n_rand), dtype=np.int32
                ))
                fwd_ids, fwd_dists, hops, pool_sizes, occluded = \
                    _chunk_forward_ivf(
                    chunk_ids, rand_ids, sig_words,
                    ivf.cent_words, ivf.list_ids,
                    backend=backend,
                    scan=scan,
                    pool=params.prune_pool,
                    r=params.r,
                    alpha=params.alpha,
                    probes=probes,
                )
            else:
                fwd_ids, fwd_dists, hops, pool_sizes, occluded = \
                    _chunk_forward(
                    adj, chunk_ids, medoid_arr,
                    backend=backend,
                    ef=params.ef_construction,
                    pool=params.prune_pool,
                    r=params.r,
                    alpha=params.alpha,
                    n=n,
                    expand=params.beam_expand,
                )
            adj, deg = _apply_forward(
                adj, deg, chunk_ids, fwd_ids, r_total=params.r_total
            )
            adj, deg, added = _reverse_append(
                adj, deg, chunk_ids, fwd_ids, r_total=params.r_total
            )
            stats.chunks += 1
            added_acc = added_acc + added
            hops_sum = hops_sum + hops.mean()
            n_hop_chunks += 1
            real = chunk_ids >= 0
            denom = jnp.maximum(real.sum(), 1).astype(jnp.float32)
            pool_mean = jnp.where(real, pool_sizes, 0).sum() / denom
            surv = jnp.where(
                real, (fwd_ids >= 0).sum(-1), 0
            ).sum() / jnp.maximum(
                jnp.where(real, pool_sizes, 0).sum(), 1
            ).astype(jnp.float32)
            occl = jnp.where(real, occluded, 0).sum()
            occl_acc = occl_acc + occl
            pool_occ_chunks.append(pool_mean / params.prune_pool)
            surv_chunks.append(surv)
            occl_chunks.append(occl)

            if (ci + 1) % params.consolidate_every == 0:
                adj, deg, did = _consolidate_overflow(
                    adj, deg, backend, params, chunk
                )
                stats.consolidations += did
            if verbose and ci % 16 == 0:
                # verbose is the debug path: the sync it forces is the
                # point (live numbers), so it is allowed to block
                print(
                    f"[vamana] pass {pass_idx} chunk {ci}/{n_chunks} "
                    f"hops={float(hops.mean()):.1f}"
                )

    adj, deg, did = _consolidate_overflow(adj, deg, backend, params, chunk)
    stats.consolidations += did
    # single materialization of the device accumulators
    stats.reverse_edges_added = int(added_acc)
    stats.mean_hops = (
        float(hops_sum) / n_hop_chunks if n_hop_chunks else 0.0
    )
    stats.occluded_total = int(occl_acc)
    if pool_occ_chunks:
        pool_occ = np.asarray(jnp.stack(pool_occ_chunks))
        surv = np.asarray(jnp.stack(surv_chunks))
        occl = np.asarray(jnp.stack(occl_chunks))
        stats.pool_occupancy = float(pool_occ.mean())
        stats.survivor_ratio = float(surv.mean())
        # per-chunk distributions land in the default registry (core
        # imports obs lazily — same discipline as the index reports)
        from repro.obs.metrics import get_default_registry
        reg = get_default_registry()
        reg.histogram(
            "quiver_build_pool_occupancy",
            "per-chunk prune-pool fill ratio at alpha-prune entry",
            buckets=(0.1, 0.25, 0.5, 0.75, 0.9, 1.0), window=0,
        ).observe_many(pool_occ)
        reg.histogram(
            "quiver_build_survivor_ratio",
            "per-chunk alpha-prune survivors / pool",
            buckets=(0.05, 0.1, 0.25, 0.5, 0.75, 1.0), window=0,
        ).observe_many(surv)
        reg.histogram(
            "quiver_build_occluded",
            "per-chunk candidates occluded by the alpha-criterion",
            buckets=(1.0, 1e1, 1e2, 1e3, 1e4, 1e5), window=0,
        ).observe_many(occl)
    stats.seconds = time.perf_counter() - t0
    return adj, int(medoid), stats


def _centroid_repr(backend) -> Any:
    """Best-effort centroid query representation for medoid selection."""
    if hasattr(backend, "vectors"):
        c = backend.vectors.mean(axis=0, keepdims=True)
        return backend.encode_queries(c)[0]
    if hasattr(backend, "sigs"):
        # decode to ±1/±2 levels, average, re-encode
        c = _mean_levels(backend.sigs.words, dim=backend.sigs.dim)
        return backend.encode_queries(c)[0]
    return None


@functools.partial(jax.jit, static_argnames=("dim",))
def _mean_levels(words, *, dim: int):
    """(1, D) mean decoded level; fused, so the (N, D) float levels are
    never materialized (12 GB of temporaries at 1M x 768 eagerly)."""
    levels = bq.decode_levels(bq.Signature(words=words, dim=dim))
    return levels.mean(axis=0, keepdims=True)


def _consolidate_overflow(adj, deg, backend, params, batch):
    """Host-side: find rows with deg > R, prune them in fixed batches."""
    deg_host = np.asarray(deg)
    overflow = np.nonzero(deg_host > params.r)[0].astype(np.int32)
    if overflow.size == 0:
        return adj, deg, 0
    pad = (-overflow.size) % batch
    if pad:
        overflow = np.concatenate([overflow, overflow[:pad]])
    for i in range(0, overflow.size, batch):
        rows = jnp.asarray(overflow[i:i + batch])
        adj, deg = _consolidate_rows(
            adj, deg, rows,
            backend=backend,
            r=params.r,
            alpha=params.alpha,
            r_total=params.r_total,
        )
    return adj, deg, 1
