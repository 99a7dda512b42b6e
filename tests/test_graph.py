"""Beam search + alpha-prune + Vamana build + end-to-end recall tests."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.baselines import flat_search, recall_at_k
from repro.core.beam import batched_beam_search, beam_search
from repro.core.index import QuIVerIndex
from repro.core.prune import alpha_prune
from repro.core.vamana import BuildParams
from repro.data.datasets import make_dataset

jax.config.update("jax_platform_name", "cpu")


def test_alpha_prune_keeps_nearest_and_respects_r():
    # line of points: target at 0, candidates at 1,2,3,...  with alpha=1.2
    # candidate i is covered by candidate j<i when d(i,0) > 1.2*d(i,j).
    ids = jnp.arange(1, 9, dtype=jnp.int32)
    dists = jnp.arange(1, 9, dtype=jnp.float32)
    pos = jnp.arange(1, 9, dtype=jnp.float32)
    pw = jnp.abs(pos[:, None] - pos[None, :])
    out_ids, out_dists = alpha_prune(ids, dists, pw, r=4, alpha=1.2)
    assert int(out_ids[0]) == 1                      # nearest always kept
    valid = np.asarray(out_ids) >= 0
    assert valid.sum() <= 4
    # selected dists are sorted ascending
    sel = np.asarray(out_dists)[valid]
    assert (np.diff(sel) >= 0).all()


def test_alpha_prune_alpha_one_keeps_diverse_only():
    # two clusters of candidates: close pair + far pair in opposite dirs
    ids = jnp.asarray([0, 1, 2, 3], dtype=jnp.int32)
    dists = jnp.asarray([1.0, 1.1, 5.0, 5.05], dtype=jnp.float32)
    # 0 and 1 are near each other; 2 and 3 near each other; clusters far
    pw = jnp.asarray(
        [[0.0, 0.2, 6.0, 6.0],
         [0.2, 0.0, 6.0, 6.0],
         [6.0, 6.0, 0.0, 0.1],
         [6.0, 6.0, 0.1, 0.0]], dtype=jnp.float32)
    out_ids, _ = alpha_prune(ids, dists, pw, r=4, alpha=1.0)
    kept = set(np.asarray(out_ids)[np.asarray(out_ids) >= 0].tolist())
    assert 0 in kept and 2 in kept       # one representative per direction
    assert 1 not in kept                  # covered by 0 (d(1,t)=1.1 > d(1,0)=0.2)
    assert 3 not in kept


def test_alpha_prune_handles_invalid_padding():
    ids = jnp.asarray([5, -1, 7, -1], dtype=jnp.int32)
    dists = jnp.asarray([2.0, 1e30, 3.0, 1e30], dtype=jnp.float32)
    pw = jnp.full((4, 4), 10.0, dtype=jnp.float32)
    out_ids, _ = alpha_prune(ids, dists, pw, r=3, alpha=1.2)
    kept = np.asarray(out_ids)
    assert set(kept[kept >= 0].tolist()) == {5, 7}


def _grid_graph(n_side):
    """2D grid of points with 4-neighbour adjacency — known topology."""
    n = n_side * n_side
    coords = np.stack(
        np.meshgrid(np.arange(n_side), np.arange(n_side), indexing="ij"),
        -1,
    ).reshape(-1, 2).astype(np.float32)
    adj = np.full((n, 4), -1, dtype=np.int32)
    for i, (x, y) in enumerate(coords):
        k = 0
        for dx, dy in ((1, 0), (-1, 0), (0, 1), (0, -1)):
            nx, ny = int(x) + dx, int(y) + dy
            if 0 <= nx < n_side and 0 <= ny < n_side:
                adj[i, k] = nx * n_side + ny
                k += 1
    return coords, jnp.asarray(adj)


def test_beam_search_finds_nearest_on_grid():
    coords, adj = _grid_graph(16)
    coords_j = jnp.asarray(coords)

    def dist_fn(query, ids, valid):
        return jnp.linalg.norm(coords_j[ids] - query, axis=-1)

    query = jnp.asarray([13.2, 2.9], dtype=jnp.float32)
    res = beam_search(
        query, adj, jnp.int32(0), dist_fn=dist_fn, ef=8
    )
    # true nearest grid point to (13.2, 2.9) is (13, 3) -> id 13*16+3
    assert int(res.ids[0]) == 13 * 16 + 3
    assert int(res.hops) > 10   # actually had to walk across the grid


def test_beam_search_batched_matches_single():
    coords, adj = _grid_graph(8)
    coords_j = jnp.asarray(coords)

    def dist_fn(query, ids, valid):
        return jnp.linalg.norm(coords_j[ids] - query, axis=-1)

    queries = jnp.asarray([[1.1, 6.8], [6.2, 0.3]], dtype=jnp.float32)
    bres = batched_beam_search(
        queries, adj, jnp.int32(0), dist_fn=dist_fn, ef=6
    )
    for i in range(2):
        sres = beam_search(
            queries[i], adj, jnp.int32(0), dist_fn=dist_fn, ef=6
        )
        np.testing.assert_array_equal(
            np.asarray(bres.ids[i]), np.asarray(sres.ids)
        )


@pytest.mark.slow
def test_end_to_end_recall_contrastive():
    """The paper's core claim at test scale: BQ-native graph + rerank
    reaches high recall on contrastive-like data."""
    base, queries = make_dataset("minilm-surrogate", n=4000, queries=50)
    params = BuildParams(m=8, ef_construction=48, prune_pool=48, chunk=128)
    idx = QuIVerIndex.build(jnp.asarray(base), params)
    true_ids, _ = flat_search(base, queries, k=10)
    pred_ids, _ = idx.search(jnp.asarray(queries), k=10, ef=64)
    rec = recall_at_k(pred_ids, true_ids)
    assert rec > 0.80, rec


@pytest.mark.slow
def test_monotone_recall_in_ef():
    """Lemma 3 / Finding 2: recall rises monotonically with ef."""
    base, queries = make_dataset("minilm-surrogate", n=2000, queries=40)
    params = BuildParams(m=6, ef_construction=32, prune_pool=32, chunk=128)
    idx = QuIVerIndex.build(jnp.asarray(base), params)
    true_ids, _ = flat_search(base, queries, k=10)
    recalls = []
    for ef in (16, 64, 256):
        pred_ids, _ = idx.search(jnp.asarray(queries), k=10, ef=ef)
        recalls.append(recall_at_k(pred_ids, true_ids))
    assert recalls[0] <= recalls[1] + 0.02
    assert recalls[1] <= recalls[2] + 0.02
    assert recalls[-1] > 0.85


def test_graph_degree_bound_and_no_self_edges():
    base, _ = make_dataset("minilm-surrogate", n=1200, queries=10)
    params = BuildParams(m=6, ef_construction=32, prune_pool=32, chunk=128)
    idx = QuIVerIndex.build(jnp.asarray(base), params)
    adj = np.asarray(idx.adjacency)
    deg = (adj >= 0).sum(-1)
    assert deg.max() <= params.r_total
    n = adj.shape[0]
    ids = np.arange(n)[:, None]
    assert not (adj == ids).any()            # no self edges
    assert (adj < n).all() and (adj >= -1).all()


def test_index_save_load_roundtrip(tmp_path):
    base, queries = make_dataset("minilm-surrogate", n=800, queries=8)
    params = BuildParams(m=4, ef_construction=24, prune_pool=24, chunk=128)
    idx = QuIVerIndex.build(jnp.asarray(base), params)
    p = str(tmp_path / "index.npz")
    idx.save(p)
    idx2 = QuIVerIndex.load(p)
    ids1, _ = idx.search(jnp.asarray(queries), k=5, ef=32)
    ids2, _ = idx2.search(jnp.asarray(queries), k=5, ef=32)
    np.testing.assert_array_equal(ids1, ids2)


def test_memory_breakdown_matches_table2_model():
    base, _ = make_dataset("cohere-surrogate", n=1000, queries=8)
    idx = QuIVerIndex.build(
        jnp.asarray(base),
        BuildParams(m=4, ef_construction=24, prune_pool=24, chunk=128),
    )
    mem = idx.memory_breakdown()
    # signatures: N * 2 * ceil(768/32) * 4 = N * 192 bytes (Table 2: 192MB @ 1M)
    assert mem["hot_signature_bytes"] == 1000 * 192
    assert mem["cold_vector_bytes"] == 1000 * 768 * 4
    assert mem["hot_total_bytes"] < mem["cold_vector_bytes"]
