"""The beam's merges (``core/beam.py``): one payload-carrying stable sort
per merge must keep exactly what the argsort-and-index formulation kept,
ties included, and the compiled loop must gather nothing in its merge
scope."""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.beam import INF, _merge_beam, _merge_results, \
    batched_beam_search
from repro.plan.cache import hlo_scopes

jax.config.update("jax_platform_name", "cpu")


def _argsort_merge_beam(ids, dists, expanded, new_ids, new_dists, ef):
    cat_ids = jnp.concatenate([ids, new_ids])
    cat_dists = jnp.concatenate([dists, new_dists])
    cat_exp = jnp.concatenate(
        [expanded, jnp.zeros(new_ids.shape, dtype=jnp.bool_)]
    )
    order = jnp.argsort(cat_dists)[:ef]
    return cat_ids[order], cat_dists[order], cat_exp[order]


def _argsort_merge_results(ids, dists, new_ids, new_dists, ef):
    cat_ids = jnp.concatenate([ids, new_ids])
    cat_dists = jnp.concatenate([dists, new_dists])
    order = jnp.argsort(cat_dists)[:ef]
    return cat_ids[order], cat_dists[order]


def _rows(rng, rows, width, ids_from):
    """Distances drawn from four values (so most entries tie), about a
    quarter INF padding with id -1, ids unique within a row."""
    dists = rng.integers(0, 4, (rows, width)).astype(np.float32)
    pad = rng.random((rows, width)) < 0.25
    dists[pad] = np.float32(INF)
    ids = np.stack([rng.permutation(1000)[:width] for _ in range(rows)])
    ids = (ids + ids_from).astype(np.int32)
    ids[pad] = -1
    return jnp.asarray(ids), jnp.asarray(dists)


@pytest.mark.parametrize("ef,width", [(8, 6), (16, 48), (64, 32)])
def test_merge_beam_matches_argsort_and_index(ef, width):
    rng = np.random.default_rng(ef * 100 + width)
    ids, dists = _rows(rng, 64, ef, 0)
    new_ids, new_dists = _rows(rng, 64, width, 1000)
    expanded = jnp.asarray(rng.random((64, ef)) < 0.5)
    got = jax.vmap(functools.partial(_merge_beam, ef=ef))(
        ids, dists, expanded, new_ids, new_dists)
    want = jax.vmap(functools.partial(_argsort_merge_beam, ef=ef))(
        ids, dists, expanded, new_ids, new_dists)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    # the ties were real: some kept rows hold equal distances
    kept = np.asarray(got[1])
    assert (np.diff(kept, axis=1) == 0).any()


@pytest.mark.parametrize("ef,width", [(8, 6), (16, 48), (64, 32)])
def test_merge_results_matches_argsort_and_index(ef, width):
    rng = np.random.default_rng(ef * 100 + width + 1)
    ids, dists = _rows(rng, 64, ef, 0)
    new_ids, new_dists = _rows(rng, 64, width, 1000)
    got = jax.vmap(functools.partial(_merge_results, ef=ef))(
        ids, dists, new_ids, new_dists)
    want = jax.vmap(functools.partial(_argsort_merge_results, ef=ef))(
        ids, dists, new_ids, new_dists)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def _line_dist(query, ids, valid):
    """Squared distance on a line of integer points: many ties."""
    return jnp.where(valid, (ids.astype(jnp.float32) - query) ** 2, INF)


_HLO_OPCODE = re.compile(
    r"^\s+(?:ROOT )?%?([\w.\-]+) = .*?\b([a-z][a-z\-]*)\(")


@pytest.mark.parametrize("masked", [False, True])
def test_merge_scope_sorts_and_gathers_nothing(masked):
    n, r, q = 64, 4, 8
    adjacency = jnp.asarray(
        np.random.default_rng(0).integers(0, n, (n, r)), jnp.int32)
    valid = jnp.arange(n) % 3 != 0 if masked else None
    search = functools.partial(
        batched_beam_search, dist_fn=_line_dist, ef=8, expand=2,
        result_valid=valid)
    hlo = jax.jit(search).lower(
        jnp.zeros((q,), jnp.float32), adjacency, jnp.int32(0)
    ).compile().as_text()
    _, scopes = hlo_scopes(hlo)
    opcodes = {}
    for line in hlo.splitlines():
        m = _HLO_OPCODE.match(line)
        if m:
            opcodes[m.group(1)] = m.group(2)
    merge = [opcodes[name] for name, scope in scopes.items()
             if scope == "quiver.beam.merge"]
    assert merge.count("sort") == (2 if masked else 1)
    assert "gather" not in merge
    # the pick step's gathers are scoped as such, so the check can see
    # a gather where there is one
    assert "gather" in [opcodes[name] for name, scope in scopes.items()
                        if scope == "quiver.beam.pick"]
