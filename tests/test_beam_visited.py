"""The beam's visited set (``core/beam.py``): a neighbour is scored
unless the beam or the result list holds it, and the loop carries
nothing of the corpus's size.  The walk must equal, bit for bit, that of
a beam that carries a full ``(n,)`` visited array — expand 1 and 4,
unmasked and under both result masks, with a beam small enough that it
evicts rows and meets them again — and its carry must not grow with n.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bq
from repro.core.beam import INF, _conjoin, batched_beam_search
from repro.core.metric import MetricArrays, make_backend

jax.config.update("jax_platform_name", "cpu")


def _visited_array_oracle(query, adjacency, start, *, dist_fn, ef, n,
                          expand, res_valid):
    """The beam as it was with a per-query ``(n,)`` visited array: a
    row is scored the first time the walk meets it, never again.
    Returns the fields of a ``BeamResult``, ``evals`` being the count
    of distinct rows scored."""
    r = adjacency.shape[1]
    lr = expand * r
    max_hops = 4 * ef + 128
    masked = res_valid is not None

    def merge(cat_ids, cat_dists, *payload):
        order = jnp.argsort(cat_dists, stable=True)[:ef]
        return tuple(c[order] for c in (cat_ids, cat_dists, *payload))

    d0 = dist_fn(query, start[None], jnp.ones((1,), jnp.bool_))[0]
    ids = jnp.full((ef,), -1, jnp.int32).at[0].set(start)
    dists = jnp.full((ef,), INF, jnp.float32).at[0].set(d0)
    expanded = jnp.ones((ef,), jnp.bool_).at[0].set(False)
    visited = jnp.zeros((n,), jnp.bool_).at[start].set(True)
    if masked:
        ok0 = res_valid[start]
        res_ids = jnp.full((ef,), -1, jnp.int32).at[0].set(
            jnp.where(ok0, start, -1))
        res_dists = jnp.full((ef,), INF, jnp.float32).at[0].set(
            jnp.where(ok0, d0, INF))
    else:
        res_ids = jnp.full((ef,), -1, jnp.int32)
        res_dists = jnp.full((ef,), INF, jnp.float32)

    def cond(state):
        ids, _, expanded, *_rest, hops, _evals = state
        return ((~expanded) & (ids >= 0)).any() & (hops < max_hops)

    def body(state):
        (ids, dists, expanded, res_ids, res_dists, visited, stalls, hops,
         evals) = state
        prev_best = dists[0]
        frontier = (~expanded) & (ids >= 0)
        picks = jnp.argsort(jnp.where(frontier, dists, INF),
                            stable=True)[:expand]
        valid_pick = frontier[picks]
        nodes = jnp.where(valid_pick, ids[picks], 0)
        expanded = expanded.at[picks].max(valid_pick)
        nbrs = adjacency[nodes].reshape(lr)
        valid = (nbrs >= 0) & jnp.repeat(valid_pick, r)
        nbrs_safe = jnp.where(valid, nbrs, 0)
        key = jnp.where(valid, nbrs, -(jnp.arange(lr) + 1))
        first = (key[None, :] == key[:, None]).argmax(1) == jnp.arange(lr)
        fresh = valid & ~visited[nbrs_safe] & first
        visited = visited.at[nbrs_safe].max(valid)
        nd = jnp.where(fresh, dist_fn(query, nbrs_safe, fresh), INF)
        new_ids = jnp.where(fresh, nbrs_safe, -1).astype(jnp.int32)
        ids, dists, expanded = merge(
            jnp.concatenate([ids, new_ids]), jnp.concatenate([dists, nd]),
            jnp.concatenate([expanded, jnp.zeros((lr,), jnp.bool_)]))
        if masked:
            live = fresh & res_valid[nbrs_safe]
            res_ids, res_dists = merge(
                jnp.concatenate([res_ids,
                                 jnp.where(live, nbrs_safe, -1)]),
                jnp.concatenate([res_dists, jnp.where(live, nd, INF)]))
        stalls = stalls + (~(dists[0] < prev_best)).astype(jnp.int32)
        return (ids, dists, expanded, res_ids, res_dists, visited, stalls,
                hops + 1, evals + fresh.sum().astype(jnp.int32))

    (nav_ids, nav_dists, _, res_ids, res_dists, _, stalls, hops,
     evals) = jax.lax.while_loop(
        cond, body,
        (ids, dists, expanded, res_ids, res_dists, visited, jnp.int32(0),
         jnp.int32(0), jnp.int32(1)))
    out_ids, out_dists = ((res_ids, res_dists) if masked
                          else (nav_ids, nav_dists))
    return dict(ids=out_ids, dists=out_dists, hops=hops, evals=evals,
                stalls=stalls, descent=d0 - nav_dists[0],
                entry_rank=(nav_dists < d0).sum().astype(jnp.int32))


N, R, DIM, Q = 600, 12, 64, 24


@functools.lru_cache(maxsize=1)
def _corpus():
    """Random 64-d signatures (BQ2 distances are small integers, so
    ties are everywhere) and random out-lists with -1 padding."""
    rng = np.random.default_rng(18)
    x = jnp.asarray(rng.standard_normal((N + Q, DIM)), jnp.float32)
    words = bq.encode(x).words
    adj = rng.integers(0, N, (N, R)).astype(np.int32)
    adj[:, -3:][rng.random((N, 3)) < 0.5] = -1
    node_valid = jnp.asarray(rng.random(N) < 0.9)
    result_valid = jnp.asarray(rng.random(N) < 0.5)
    return (bq.Signature(words=words[:N], dim=DIM), words[N:],
            jnp.asarray(adj), node_valid, result_valid)


_MASKS = {
    "unmasked": (False, False),
    "node_valid": (True, False),
    "node_and_result_valid": (True, True),
}

_CASES = [("ref", expand, mask, ef)
          for expand in (1, 4) for mask in _MASKS for ef in (8, 32)]
_CASES += [("pallas", 4, "node_and_result_valid", 8),
           ("pallas", 1, "unmasked", 8)]


@functools.lru_cache(maxsize=None)
def _walks(route, expand, mask, ef):
    sigs, queries, adj, node_valid, result_valid = _corpus()
    use_node, use_result = _MASKS[mask]
    nv = node_valid if use_node else None
    rv = result_valid if use_result else None
    backend = make_backend("bq2", MetricArrays(sigs=sigs), route=route)
    start = jnp.int32(0)
    new = jax.jit(lambda q: batched_beam_search(
        q, adj, start, dist_fn=backend.dist_fn, ef=ef, expand=expand,
        node_valid=nv, result_valid=rv))(queries)
    res_valid = _conjoin(nv, rv)
    old = jax.jit(jax.vmap(lambda q: _visited_array_oracle(
        q, adj, start, dist_fn=backend.dist_fn, ef=ef, n=N,
        expand=expand, res_valid=res_valid)))(queries)
    return new, old


@pytest.mark.parametrize("route,expand,mask,ef", _CASES)
def test_walk_equals_the_visited_array_walk(route, expand, mask, ef):
    new, old = _walks(route, expand, mask, ef)
    for field in ("ids", "dists", "hops", "stalls", "descent",
                  "entry_rank"):
        np.testing.assert_array_equal(
            np.asarray(getattr(new, field)), np.asarray(old[field]),
            err_msg=field)
    # a row is scored at least as often as it is first met
    assert (np.asarray(new.evals) >= np.asarray(old["evals"])).all()
    assert int(old["hops"].min()) > 1


def test_small_beam_scores_evicted_rows_again():
    """At ef 8 the walk evicts rows and meets them again: each such
    meeting is scored and counted, so evals exceeds the distinct count
    — the re-score path the equivalence cases above run through."""
    for expand in (1, 4):
        for mask in _MASKS:
            new, old = _walks("ref", expand, mask, 8)
            assert (np.asarray(new.evals).sum()
                    > np.asarray(old["evals"]).sum()), (expand, mask)


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs nested in it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


@pytest.mark.parametrize("masked", [False, True])
def test_loop_carry_does_not_grow_with_n(masked):
    """The beam's while carry is the same at 1,000 and 4,000 rows, and
    nothing the ``quiver.beam.visited`` scope reads or writes has a
    dimension of n."""
    def dist_fn(q, ids, valid):
        return jnp.abs(ids.astype(jnp.float32) - q)

    carries = {}
    for n in (1000, 4000):
        adj = jax.ShapeDtypeStruct((n, R), jnp.int32)
        live = jax.ShapeDtypeStruct((n,), jnp.bool_)
        jaxpr = jax.make_jaxpr(lambda q, a, v: batched_beam_search(
            q, a, jnp.int32(0), dist_fn=dist_fn, ef=16, expand=2,
            node_valid=v if masked else None,
        ))(jax.ShapeDtypeStruct((8,), jnp.float32), adj, live).jaxpr
        eqns = list(_eqns(jaxpr))
        loops = [e for e in eqns if e.primitive.name == "while"]
        assert len(loops) == 1
        carries[n] = [v.aval.shape for v in loops[0].outvars]
        scoped = [e for e in eqns if "quiver.beam.visited"
                  in str(e.source_info.name_stack)]
        assert scoped
        for eqn in scoped:
            for v in (*eqn.invars, *eqn.outvars):
                assert n not in getattr(v.aval, "shape", ()), (eqn, n)
    assert carries[1000] == carries[4000]
