"""Symmetric BQ beam search over a fixed-degree graph (QuIVer §3.3, stage 1).

Pure ``jax.lax`` control flow: a ``while_loop`` maintaining a sorted beam
of ``ef`` candidates and an expanded mask.  The loop carries nothing of
the corpus's size: a neighbour is scored unless the beam (or the result
list) holds it, which walks exactly the path a full visited set would
(see :func:`_beam_search`).  Each iteration expands the ``expand``
nearest unexpanded beam entries and
folds their <= expand*R neighbours into the beam with **one** batched
distance evaluation — the TPU-friendly formulation of the paper's
per-hop XOR+popcount loop.  ``expand=1`` is the classic greedy
best-first traversal (bit-for-bit identical to the pre-refactor code);
wider ``expand`` trades hops for batch width, which is what a Pallas/VPU
distance kernel wants: an ``(L*R,)`` distance batch per hop amortizes
kernel launch and HBM streaming far better than ``(R,)``.

The distance function is pluggable so the same traversal serves the
paper's symmetric 2-bit navigation, the 1-bit Hamming baseline, the ADC
ablation and the float32 Vamana reference build — any backend registered
in ``repro.core.metric``.

Two-mask semantics (DESIGN.md §8/§9): the beam splits *navigation*
from *results* under two independent, composable masks —

* ``node_valid`` (tombstones, streaming subsystem): dead nodes are
  still traversed — their edges keep the graph connected between
  deletions and consolidation, exactly as in FreshDiskANN — but never
  returned;
* ``result_valid`` (filtered search, ``repro.filter``): non-matching
  nodes are traversed freely — the predicate restricts what may be
  *returned*, never where the beam may *walk* — so filtered search
  over a mutable index composes with deletes for free.

Either mask alone, or their conjunction, drives one parallel
valid-only result list maintained inside the traversal; with both
``None`` the loop carries no result list at all and is bit-for-bit the
unmasked search.
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

INF = jnp.float32(3.0e38)

# dist_fn(query_repr, ids (k,), valid (k,) bool) -> (k,) float32
DistFn = Callable[[jnp.ndarray, jnp.ndarray, jnp.ndarray], jnp.ndarray]


def batch_bucket(n: int, query_batch: int) -> int:
    """Padded size for a (possibly partial) query batch.

    Tail batches are padded up a small fixed ladder (8, 32, 128, ...,
    ``query_batch``) instead of tracing :func:`batched_beam_search` once
    per distinct tail size: the trace count is bounded by the ladder
    length while tiny batches never pay a full ``query_batch`` of
    padding.  The one owner of the ladder — every search surface
    (core, streaming, filtered, adaptive escalation) pads through it.
    """
    b = 8
    while b < n and b < query_batch:
        b *= 4
    return min(b, query_batch)


def pad_rows(arr: jnp.ndarray, size: int) -> jnp.ndarray:
    """Pad axis 0 to ``size`` rows by repeating the last row (the
    padded rows run real searches whose outputs are sliced away)."""
    pad = size - arr.shape[0]
    if pad <= 0:
        return arr
    return jnp.concatenate(
        [arr, jnp.repeat(arr[-1:], pad, axis=0)], axis=0
    )


@functools.partial(jax.jit, static_argnames=("k", "neutral"))
def beam_margin(dists, k: int, neutral: float) -> jnp.ndarray:
    """Per-query top-k score margin of a beam result.

    ``dists`` is the ``(Q, ef)`` sorted (ascending, INF-padded) distance
    list of a :class:`BeamResult`; ``neutral`` is the navigation
    metric's zero-similarity distance (``MetricSpace.neutral_dist`` —
    e.g. ``4D`` for bq2, ``1.0`` for float32 cosine).  The margin is
    the k-th candidate's normalized score margin over that floor:

        margin = (neutral - d[k-1]) / neutral

    A query whose top candidates all score near the indifference point
    has *tight* margins — the quantized metric barely distinguishes its
    rerank pool from arbitrary points, which is the dominant per-query
    failure mode (margin-vs-recall correlation ~-0.9 on the amber-tier
    surrogates, DESIGN.md §10) — and its rerank pool should widen.
    Beams that found fewer than ``k`` valid candidates report -1
    (starved: escalation is the only way to fill the pool).  The
    escalation threshold is corpus-dependent; ``build(nav="auto")``
    calibrates it from the probe sample
    (``CompatibilityReport.margin_p30``).
    """
    dk = dists[..., k - 1]
    margin = (neutral - dk) / neutral
    return jnp.where(dk < INF / 2, margin, -1.0)


class BeamResult(NamedTuple):
    ids: jnp.ndarray     # (ef,) int32, -1 padded, sorted by distance
    dists: jnp.ndarray   # (ef,) float32, INF padded
    hops: jnp.ndarray    # () int32 — number of expansion rounds performed
    # () int32 — rows scored that were in neither the beam nor the
    # result list; a row that fell out of the beam is scored (and
    # counted) again each time the walk meets it
    evals: jnp.ndarray
    # navigation-path trace (DESIGN.md §15): live descent diagnostics
    # carried out of the jitted loop for the quiver_nav_* histograms
    descent: jnp.ndarray = jnp.float32(0.0)   # () entry dist - best nav dist
    stalls: jnp.ndarray = jnp.int32(0)        # () rounds w/o beam-best gain
    entry_rank: jnp.ndarray = jnp.int32(0)    # () nav dists beating entry


def _conjoin(node_valid, result_valid):
    """Combine the tombstone and predicate result masks (None == all
    valid); the single owner of the two-mask conjunction semantics."""
    if node_valid is not None and result_valid is not None:
        return node_valid & result_valid
    return node_valid if node_valid is not None else result_valid


def _merge_beam(ids, dists, expanded, new_ids, new_dists, ef):
    """Merge new candidates into the sorted beam, keep best ``ef``.

    One stable sort keyed on distance carries the ids and expanded
    flags as payloads, so nothing gathers by a permutation afterwards:
    under ``vmap`` such a gather is a batched per-row gather, which on
    a TPU costs about a hundred times the sort.  Equal distances keep
    their concatenation order, beam entries before new candidates.
    """
    cat_ids = jnp.concatenate([ids, new_ids])
    cat_dists = jnp.concatenate([dists, new_dists])
    cat_exp = jnp.concatenate(
        [expanded, jnp.zeros(new_ids.shape, dtype=jnp.bool_)]
    )
    cat_dists, cat_ids, cat_exp = jax.lax.sort(
        (cat_dists, cat_ids, cat_exp), num_keys=1, is_stable=True
    )
    return cat_ids[:ef], cat_dists[:ef], cat_exp[:ef]


def _merge_results(ids, dists, new_ids, new_dists, ef):
    """Merge live candidates into the sorted result list, keep best
    ``ef``: one stable sort on distance with the ids as its payload,
    as :func:`_merge_beam`."""
    cat_ids = jnp.concatenate([ids, new_ids])
    cat_dists = jnp.concatenate([dists, new_dists])
    cat_dists, cat_ids = jax.lax.sort(
        (cat_dists, cat_ids), num_keys=1, is_stable=True
    )
    return cat_ids[:ef], cat_dists[:ef]


@functools.partial(
    jax.jit,
    static_argnames=(
        "dist_fn", "ef", "max_hops", "expand", "max_evals"
    ),
)
def beam_search(
    query,
    adjacency: jnp.ndarray,   # (N, R) int32, -1 padded
    start: jnp.ndarray,       # () int32 entry point (medoid)
    *,
    dist_fn: DistFn,
    ef: int,
    max_hops: int = 0,
    expand: int = 1,
    max_evals: int = 0,
    node_valid: jnp.ndarray | None = None,     # (n,) bool live mask
    result_valid: jnp.ndarray | None = None,   # (n,) bool predicate mask
) -> BeamResult:
    """Best-first beam search from ``start`` toward ``query``.

    ``expand`` (the beam expansion width L) controls how many unexpanded
    beam entries are expanded per hop; each hop issues a single
    ``(expand * R,)`` distance batch.  ``expand=1`` reproduces greedy
    best-first search exactly.

    ``max_evals`` (0 = unlimited) stops expanding once that many
    distance evaluations have been spent — the budget knob for
    recall-per-distance-evaluation comparisons across expansion widths.
    An evaluation is a row in neither the beam nor the result list, so
    a row the beam evicted counts again each time the walk meets it.

    ``node_valid`` (optional) is the tombstone mask of a mutable index;
    ``result_valid`` (optional) is a filter-predicate match mask
    (``repro.filter``).  Under either (or both — they conjoin), beam
    *navigation* is unchanged: masked-out nodes are still expanded and
    their edges still route.  Only the returned ids/dists are drawn
    from a parallel valid-only result list, so tombstoned and
    non-matching nodes never surface.

    The device ops carry named scopes (their HLO ``op_name``, so a
    profiler's op events map back to them through
    ``PlanCache.op_scopes``): ``quiver.beam`` around the search, and
    per hop ``quiver.beam.pick`` (frontier argsort), ``.neighbours``
    (adjacency gather and, under a mask, the mask's read), ``.dedup``
    (the (L·R)² first-occurrence matrix), ``.visited`` (the (L·R, ef)
    membership test against the beam's and the result list's ids),
    ``.distance`` (``dist_fn``), ``.merge`` (the beam's
    merge and, under a mask, the result list's: each one
    payload-carrying sort) and ``.cond`` (the loop's condition).
    """
    with jax.named_scope("quiver.beam"):
        return _beam_search(
            query, adjacency, start, dist_fn=dist_fn, ef=ef,
            max_hops=max_hops, expand=expand, max_evals=max_evals,
            node_valid=node_valid, result_valid=result_valid,
        )


def _beam_search(query, adjacency, start, *, dist_fn, ef, max_hops,
                 expand, max_evals, node_valid, result_valid):
    # Why membership in the lists is all the walk must remember: a row
    # scored earlier and held by neither list lost a merge, so its
    # distance is >= that list's ef-th distance at the time.  Once a
    # list is full its ef-th distance never rises, and the merge's
    # stable sort puts the list's entries before new candidates, so the
    # row scored again lands at position >= ef and is cut, ties
    # included.  ids, dists, hops and the nav diagnostics are those of
    # a walk that carries a full (n,) visited set; only evals differs
    # (and so where a max_evals budget stops the walk).
    # lowering counter (repro.plan.trace): this body only runs when jax
    # traces it, so the bump counts compilations, not calls.  Imported
    # lazily — trace time is after import time, and beam must not pull
    # the plan package in at module scope.
    from repro.plan.trace import note_trace
    note_trace("beam_search")
    r = adjacency.shape[1]
    max_hops = max_hops or (4 * ef + 128)
    assert 1 <= expand <= ef, (expand, ef)
    lr = expand * r
    res_valid = _conjoin(node_valid, result_valid)
    masked = res_valid is not None

    with jax.named_scope("quiver.beam.distance"):
        d0 = dist_fn(query, start[None], jnp.ones((1,), jnp.bool_))[0]
    ids = jnp.full((ef,), -1, dtype=jnp.int32).at[0].set(start)
    dists = jnp.full((ef,), INF, dtype=jnp.float32).at[0].set(d0)
    # padding entries are marked expanded so they are never picked
    expanded = jnp.ones((ef,), dtype=jnp.bool_).at[0].set(False)
    if masked:
        ok0 = res_valid[start]
        res_ids = jnp.full((ef,), -1, dtype=jnp.int32).at[0].set(
            jnp.where(ok0, start, -1)
        )
        res_dists = jnp.full((ef,), INF, dtype=jnp.float32).at[0].set(
            jnp.where(ok0, d0, INF)
        )
    else:
        res_ids = res_dists = None

    def cond(state):
        ids, dists, expanded, *_rest, hops, evals = state
        with jax.named_scope("quiver.beam.cond"):
            frontier = (~expanded) & (ids >= 0)
            go = frontier.any() & (hops < max_hops)
            if max_evals:
                go = go & (evals < max_evals)
            return go

    def body(state):
        if masked:
            ids, dists, expanded, res_ids, res_dists, stalls, hops, \
                evals = state
        else:
            ids, dists, expanded, stalls, hops, evals = state
        prev_best = dists[0]
        with jax.named_scope("quiver.beam.pick"):
            frontier = (~expanded) & (ids >= 0)
            # stable sort => tie-break by beam position, matching argmin
            # at L=1
            picks = jnp.argsort(jnp.where(frontier, dists, INF))[:expand]
            valid_pick = frontier[picks]
            nodes = jnp.where(valid_pick, ids[picks], 0)
            expanded = expanded.at[picks].max(valid_pick)

        with jax.named_scope("quiver.beam.neighbours"):
            nbrs = adjacency[nodes].reshape(lr)          # (L*R,)
            valid = (nbrs >= 0) & jnp.repeat(valid_pick, r)
            nbrs_safe = jnp.where(valid, nbrs, 0)
        with jax.named_scope("quiver.beam.visited"):
            # the lists as they stand at the pick, before this hop's
            # merge
            seen = (nbrs_safe[:, None] == ids[None, :]).any(axis=1)
            if masked:
                seen = seen | (
                    nbrs_safe[:, None] == res_ids[None, :]
                ).any(axis=1)
            fresh = valid & ~seen
        with jax.named_scope("quiver.beam.dedup"):
            # duplicate neighbours within one batch: keep first
            # occurrence only (invalid slots get unique sentinels so
            # they never alias node 0)
            dedup_key = jnp.where(valid, nbrs, -(jnp.arange(lr) + 1))
            first_occurrence = (
                dedup_key[None, :] == dedup_key[:, None]
            ).argmax(axis=1) == jnp.arange(lr)
            fresh = fresh & first_occurrence

        with jax.named_scope("quiver.beam.distance"):
            nd = dist_fn(query, nbrs_safe, fresh)
        with jax.named_scope("quiver.beam.merge"):
            nd = jnp.where(fresh, nd, INF)
            new_ids = jnp.where(fresh, nbrs_safe, -1).astype(jnp.int32)
            ids, dists, expanded = _merge_beam(
                ids, dists, expanded, new_ids, nd, ef
            )
        evals = evals + fresh.sum().astype(jnp.int32)
        # a round that fails to improve the nav-beam best is a stall:
        # the walk is circling (or backtracking through worse frontier
        # entries) rather than descending — see DESIGN.md §15
        stalls = stalls + (~(dists[0] < prev_best)).astype(jnp.int32)
        if masked:
            with jax.named_scope("quiver.beam.neighbours"):
                live = fresh & res_valid[nbrs_safe]
            with jax.named_scope("quiver.beam.merge"):
                res_ids, res_dists = _merge_results(
                    res_ids, res_dists,
                    jnp.where(live, nbrs_safe, -1).astype(jnp.int32),
                    jnp.where(live, nd, INF), ef,
                )
            return (ids, dists, expanded, res_ids, res_dists, stalls,
                    hops + 1, evals)
        return ids, dists, expanded, stalls, hops + 1, evals

    if masked:
        state = jax.lax.while_loop(
            cond, body,
            (ids, dists, expanded, res_ids, res_dists, jnp.int32(0),
             jnp.int32(0), jnp.int32(1)),
        )
        _, nav_dists, _, res_ids, res_dists, stalls, hops, evals = state
        return BeamResult(
            ids=res_ids, dists=res_dists, hops=hops, evals=evals,
            descent=d0 - nav_dists[0], stalls=stalls,
            entry_rank=(nav_dists < d0).sum().astype(jnp.int32),
        )

    ids, dists, expanded, stalls, hops, evals = jax.lax.while_loop(
        cond, body,
        (ids, dists, expanded, jnp.int32(0), jnp.int32(0), jnp.int32(1)),
    )
    return BeamResult(
        ids=ids, dists=dists, hops=hops, evals=evals,
        descent=d0 - dists[0], stalls=stalls,
        entry_rank=(dists < d0).sum().astype(jnp.int32),
    )


def batched_beam_search(
    queries,
    adjacency: jnp.ndarray,
    start: jnp.ndarray,
    *,
    dist_fn: DistFn,
    ef: int,
    max_hops: int = 0,
    expand: int = 1,
    max_evals: int = 0,
    node_valid: jnp.ndarray | None = None,
    result_valid: jnp.ndarray | None = None,
) -> BeamResult:
    """vmap of :func:`beam_search` over a batch of queries.

    ``queries`` is whatever representation ``dist_fn`` consumes, batched on
    axis 0 (packed signature words for BQ navigation, float vectors for
    ADC / float32 navigation).  ``node_valid`` (tombstones) and
    ``result_valid`` (filter predicate), both shared across the batch,
    are the two result masks of :func:`beam_search`.
    """
    fn = functools.partial(
        beam_search,
        dist_fn=dist_fn,
        ef=ef,
        max_hops=max_hops,
        expand=expand,
        max_evals=max_evals,
    )
    res_valid = _conjoin(node_valid, result_valid)
    if res_valid is None:
        return jax.vmap(fn, in_axes=(0, None, None))(
            queries, adjacency, start
        )
    return jax.vmap(
        lambda q, adj, s, nv: fn(q, adj, s, node_valid=nv),
        in_axes=(0, None, None, None),
    )(queries, adjacency, start, res_valid)
