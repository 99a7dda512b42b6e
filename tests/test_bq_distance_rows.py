"""The batched rows kernel (``bq_distance_rows_pallas``) behind BQ2
``dist_rows`` on the ``pallas`` route: each query scored against its own
K rows in one call, interpreted here.  Its similarities are integers, so
they must equal the jnp reference exactly — under vmap, nested vmap,
explicit leading dims and a vmapped ``while_loop`` — and the beam must
walk the same path on either route.  The batched beam must lower to one
kernel call per hop for the whole batch."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bq
from repro.core.beam import batched_beam_search
from repro.core.metric import MetricArrays, make_backend
from repro.kernels import dispatch
from repro.kernels.bq_distance import bq_distance_rows_pallas
from repro.plan import trace

jax.config.update("jax_platform_name", "cpu")


def _sigs(rng, n, dim):
    x = jnp.asarray(rng.standard_normal((n, dim)), jnp.float32)
    return bq.encode(x).words


@pytest.mark.parametrize("dim", [64, 100, 384, 768, 1536])
@pytest.mark.parametrize(
    "b,k", [(1, 32), (3, 64), (256, 32), (257, 1), (257, 64)]
)
def test_rows_kernel_matches_ref(dim, b, k):
    rng = np.random.default_rng(dim * 1000 + b + k)
    w = bq.n_words(dim)
    q = _sigs(rng, b, dim)
    rows = _sigs(rng, b * k, dim).reshape(b, k, 2 * w)
    got = bq_distance_rows_pallas(q, rows, dim=dim)
    want = -dispatch._bq2_sim_ref(q, rows, bq.valid_mask(dim), w)
    assert got.dtype == jnp.int32 and got.shape == (b, k)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("b,k", [(1, 1), (3, 32), (256, 64), (40, 300)])
def test_rows_kernel_shared_rows_match_ref(b, k):
    """(1, K, 2W) rows are scored against every query."""
    rng = np.random.default_rng(b + k)
    dim = 100
    w = bq.n_words(dim)
    q = _sigs(rng, b, dim)
    rows = _sigs(rng, k, dim)[None]
    got = bq_distance_rows_pallas(q, rows, dim=dim)
    want = -dispatch._bq2_sim_ref(q, rows, bq.valid_mask(dim), w)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _loop(dist_rows, table, q, ids):
    """Each query loops a different number of times over its own rows."""

    def one(qq, ii, trips):
        def body(c):
            t, acc = c
            return t + 1, acc + dist_rows(qq, table[ii[t % ii.shape[0]]])

        init = (0, jnp.zeros((ii.shape[-1],), jnp.int32))
        return jax.lax.while_loop(lambda c: c[0] < trips, body, init)[1]

    return jax.jit(jax.vmap(one))(q, ids, jnp.arange(q.shape[0]) + 1)


_FORMS = {
    "vmap": lambda d, t, q, i: jax.vmap(lambda qq, ii: d(qq, t[ii[0]]))(q, i),
    "nested_vmap": lambda d, t, q, i: jax.vmap(
        jax.vmap(lambda qq, ii: d(qq, t[ii]), in_axes=(None, 0))
    )(q, i),
    "leading_dims": lambda d, t, q, i: d(q[:, None], t[i]),
    "vmap_shared_rows": lambda d, t, q, i: jax.vmap(
        lambda qq: d(qq, t[i[0, 0]])
    )(q),
    "vmap_shared_query": lambda d, t, q, i: jax.vmap(
        lambda ii: d(q[0], t[ii])
    )(i),
    "vmap_unbatched_leading_rows": lambda d, t, q, i: jax.vmap(
        lambda qq: d(qq, t[i])
    )(q[:, None, None]),
    "vmapped_while_loop": _loop,
}


@pytest.mark.parametrize("form", sorted(_FORMS))
def test_dist_rows_routes_agree_under_transforms(form):
    dim = 100
    rng = np.random.default_rng(7)
    table = _sigs(rng, 500, dim)
    q = table[:6]
    ids = jnp.asarray(rng.integers(0, 500, (6, 4, 32)))
    ref = dispatch.bq2_ops(dim, route="ref")
    pal = dispatch.bq2_ops(dim, route="pallas")
    want = _FORMS[form](ref.dist_rows, table, q, ids)
    got = _FORMS[form](pal.dist_rows, table, q, ids)
    assert got.shape == want.shape
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _graph(n=400, r=32, seed=0):
    """Random out-lists with some -1 padding: enough to walk a beam."""
    rng = np.random.default_rng(seed)
    adj = rng.integers(0, n, (n, r)).astype(np.int32)
    adj[:, -4:][rng.random((n, 4)) < 0.5] = -1
    return jnp.asarray(adj)


def _beam_fn(masked):
    def run(q, adj, backend, live):
        return batched_beam_search(
            q, adj, jnp.int32(0), dist_fn=backend.dist_fn, ef=24,
            node_valid=live if masked else None,
        )

    return jax.jit(run)


@pytest.mark.parametrize("masked", [False, True])
def test_batched_beam_walks_the_same_path_on_both_routes(masked):
    n, dim = 400, 100
    rng = np.random.default_rng(1)
    sigs = bq.Signature(words=_sigs(rng, n, dim), dim=dim)
    q = _sigs(rng, 16, dim)
    live = jnp.asarray(rng.random(n) < 0.8)
    adj = _graph(n)
    fn = _beam_fn(masked)
    out = {
        route: fn(q, adj, make_backend(
            "bq2", MetricArrays(sigs=sigs), route=route), live)
        for route in ("ref", "pallas")
    }
    for field in ("ids", "dists", "evals", "hops"):
        np.testing.assert_array_equal(
            np.asarray(getattr(out["pallas"], field)),
            np.asarray(getattr(out["ref"], field)), err_msg=field,
        )
    assert int(out["ref"].hops.min()) > 1


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs nested in it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def test_batched_beam_lowers_one_kernel_call_per_hop():
    """The while body holds one pallas_call, whose output covers all B
    queries' K rows; the per-query tile kernel is gone.  The kernel's
    trace event counts lowerings, not calls (the batch size is one no
    other test here traces, so the kernel's own jit cache is cold)."""
    n, dim, b = 400, 100, 8
    rng = np.random.default_rng(2)
    sigs = bq.Signature(words=_sigs(rng, n, dim), dim=dim)
    backend = make_backend("bq2", MetricArrays(sigs=sigs), route="pallas")
    q = _sigs(rng, b, dim)
    adj = _graph(n)
    fn = functools.partial(_beam_fn(False), live=None)

    trace.reset("bq_distance_rows")
    fn(q, adj, backend)
    compiled = trace.trace_counts("bq_distance_rows")
    assert compiled.get("bq_distance_rows", 0) >= 1
    fn(q, adj, backend)
    fn(q[::-1], adj, backend)
    assert trace.trace_counts("bq_distance_rows") == compiled

    jaxpr = jax.make_jaxpr(fn)(q, adj, backend).jaxpr
    (loop,) = [e for e in _eqns(jaxpr) if e.primitive.name == "while"]
    body = loop.params["body_jaxpr"].jaxpr
    calls = [e for e in _eqns(body) if e.primitive.name == "pallas_call"]
    assert len(calls) == 1
    (out,) = calls[0].outvars
    assert sorted(out.aval.shape) == sorted((b, adj.shape[1]))
    names = {e.params.get("name") for e in _eqns(body)}
    assert "bq_distance_pallas" not in names
    assert "bq_distance_rows_pallas" in names
