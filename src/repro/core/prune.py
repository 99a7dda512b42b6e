"""Vamana alpha-diversity pruning on BQ distances (QuIVer Alg. 1).

Vectorized greedy selection: a ``fori_loop`` over the R output slots; at
each step the nearest not-yet-pruned candidate is selected and every
candidate it "covers" (``dist(c, t) > alpha * dist(c, s)``) is pruned.
All distances are the *calibrated non-negative* BQ distances
``d = 4D - similarity`` (see ``repro.core.index`` for why the Table-1
signed similarity needs an offset before the multiplicative alpha
criterion is meaningful).

The pairwise candidate-candidate distance matrix is computed once up
front — the batched analogue of the paper's per-candidate popcount calls.

For ``alpha > 1`` the selection runs in two tiers, as DiskANN's
``occlude_list`` does: candidates are first taken under the strict
criterion (alpha 1), and only the slots left over go to candidates
that the relaxed criterion (``alpha``) still admits.  A single sweep at
``alpha`` admits so many short edges that the R slots fill with the
nearest candidates before the far ones the strict criterion keeps are
reached, so the row loses its long edges and the graph the way
between clusters.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

BIG = jnp.float32(3.0e38)


def _greedy_select(cand_ids, cand_dists, pairwise, *, r, alpha):
    """Distance-sort + tiered greedy cover loop; the prune's shared core.

    Returns (sorted ids, sorted dists, selected mask, (T, C) pruned
    masks) over the sorted candidate order, one mask per tier, strict
    first; the last is the candidates covered at ``alpha``.  Each step
    picks the nearest candidate not covered at the strictest tier that
    still has one.  A pick covers, at the strict
    tier, every candidate the criterion holds for, and at the relaxed
    tier only those after it in distance order (DiskANN's occlusion
    factor), so a relaxed-tier candidate is judged only by nearer picks.
    With ``alpha <= 1`` there is one tier and the loop is the plain
    greedy sweep.
    """
    c = cand_ids.shape[0]
    valid = cand_ids >= 0
    order = jnp.argsort(jnp.where(valid, cand_dists, BIG))
    ids = cand_ids[order]
    dists = cand_dists[order]
    pw = pairwise[order][:, order]
    valid = ids >= 0
    tiers = (1.0, alpha) if alpha > 1.0 else (alpha,)
    scale = jnp.asarray(tiers, jnp.float32)[:, None]       # (T, 1)
    pos = jnp.arange(c)

    def step(_, state):
        selected, pruned = state                            # (C,), (T, C)
        avail = valid & ~selected & ~pruned
        tier_has = avail.any(-1)
        any_avail = tier_has.any()
        # candidates are sorted by distance: first available == nearest
        pick = jnp.argmax(avail[jnp.argmax(tier_has)])
        selected = selected.at[pick].set(selected[pick] | any_avail)
        # prune everything covered by the new pivot, tier by tier
        covered = dists > scale * pw[pick]
        covered = covered.at[1:].set(covered[1:] & (pos > pick))
        covered = covered & ~selected & any_avail
        return selected, pruned | covered

    selected, pruned = jax.lax.fori_loop(
        0,
        r,
        step,
        (jnp.zeros((c,), jnp.bool_), jnp.zeros((len(tiers), c), jnp.bool_)),
    )
    return ids, dists, selected, pruned


def _compact(ids, dists, selected, r):
    """Compact the <= r selected entries (sorted by distance) into (r,)."""
    rank = jnp.cumsum(selected) - 1        # in-order rank among selected
    slot = jnp.where(selected, rank, r)    # r == overflow bucket for the rest
    out_ids = (
        jnp.full((r + 1,), -1, jnp.int32)
        .at[slot]
        .set(jnp.where(selected, ids, -1))[:r]
    )
    out_dists = (
        jnp.full((r + 1,), BIG, jnp.float32)
        .at[slot]
        .set(jnp.where(selected, dists, BIG))[:r]
    )
    return out_ids, out_dists


@functools.partial(jax.jit, static_argnames=("r", "alpha"))
def alpha_prune(
    cand_ids: jnp.ndarray,    # (C,) int32, -1 padded
    cand_dists: jnp.ndarray,  # (C,) float32, distance to target, INF padded
    pairwise: jnp.ndarray,    # (C, C) float32 candidate-candidate distances
    *,
    r: int,
    alpha: float,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Greedy alpha-diversity selection -> ((r,) ids, (r,) dists)."""
    ids, dists, selected, _ = _greedy_select(
        cand_ids, cand_dists, pairwise, r=r, alpha=alpha
    )
    return _compact(ids, dists, selected, r)


@functools.partial(jax.jit, static_argnames=("r", "alpha"))
def alpha_prune_stats(
    cand_ids: jnp.ndarray,
    cand_dists: jnp.ndarray,
    pairwise: jnp.ndarray,
    *,
    r: int,
    alpha: float,
) -> tuple[jnp.ndarray, ...]:
    """:func:`alpha_prune` plus the build-telemetry counts.

    Returns ((r,) ids, (r,) dists, () pool size, () occluded count,
    () relaxed count): *pool* is how many valid candidates entered the
    prune, *occluded* how many the alpha-criterion covered away
    (survivors = pool − occluded, bounded by r), *relaxed* how many of
    the kept edges only the relaxed tier admitted (a candidate the
    strict tier covered, then selected; 0 when ``alpha <= 1``).  Same
    trace as ``alpha_prune`` — the counts are reductions over masks the
    loop already computes.
    """
    ids, dists, selected, pruned = _greedy_select(
        cand_ids, cand_dists, pairwise, r=r, alpha=alpha
    )
    out_ids, out_dists = _compact(ids, dists, selected, r)
    pool = (ids >= 0).sum().astype(jnp.int32)
    occluded = pruned[-1].sum().astype(jnp.int32)
    relaxed = (selected & pruned[0]).sum().astype(jnp.int32)
    return out_ids, out_dists, pool, occluded, relaxed


def alpha_prune_batch(cand_ids, cand_dists, pairwise, *, r, alpha):
    """vmap over a chunk of targets: (B, C) / (B, C, C) -> (B, r)."""
    return jax.vmap(
        functools.partial(alpha_prune, r=r, alpha=alpha)
    )(cand_ids, cand_dists, pairwise)


def alpha_prune_stats_batch(cand_ids, cand_dists, pairwise, *, r, alpha):
    """vmap of :func:`alpha_prune_stats`: adds (B,) pool / occluded /
    relaxed."""
    return jax.vmap(
        functools.partial(alpha_prune_stats, r=r, alpha=alpha)
    )(cand_ids, cand_dists, pairwise)
