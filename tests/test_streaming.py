"""Streaming subsystem tests: tombstone-aware beam search, mutable
index freshness (insert/delete/consolidate/freeze), sharded streaming,
the build_sharded tail fix, named-params persistence, and the Retriever
padding-id fix."""

import functools
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.baselines import flat_search, recall_at_k
from repro.core.beam import beam_search
from repro.core.index import QuIVerIndex
from repro.core.vamana import BuildParams
from repro.data.datasets import make_dataset
from repro.stream import MutableQuIVerIndex, StreamingShardedIndex

jax.config.update("jax_platform_name", "cpu")

PARAMS = BuildParams(m=6, ef_construction=32, prune_pool=32, chunk=128)


def _run_with_devices(n_dev: int, code: str) -> str:
    import os
    env = {
        "XLA_FLAGS": f"--xla_force_host_platform_device_count={n_dev}",
        "PYTHONPATH": "src",
        "PATH": os.environ.get("PATH", "/usr/bin:/bin"),
        "JAX_PLATFORMS": "cpu",
    }
    res = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=900, env=env,
        cwd="/root/repo",
    )
    assert res.returncode == 0, res.stderr[-3000:]
    return res.stdout


@functools.lru_cache(maxsize=1)
def _data():
    base, queries = make_dataset("minilm-surrogate", n=2000, queries=25)
    return base, queries


# -- tombstone-aware beam search ---------------------------------------------


def _grid():
    n_side = 12
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
    coords_j = jnp.asarray(coords)

    def dist_fn(query, ids, valid):
        return jnp.linalg.norm(coords_j[ids] - query, axis=-1)

    return n_side, n, jnp.asarray(adj), dist_fn


def test_masked_beam_all_valid_is_bit_identical():
    n_side, n, adj, dist_fn = _grid()
    q = jnp.asarray([8.7, 2.2], dtype=jnp.float32)
    plain = beam_search(q, adj, jnp.int32(0), dist_fn=dist_fn, ef=8)
    masked = beam_search(
        q, adj, jnp.int32(0), dist_fn=dist_fn, ef=8,
        node_valid=jnp.ones((n,), jnp.bool_),
    )
    np.testing.assert_array_equal(np.asarray(plain.ids),
                                  np.asarray(masked.ids))
    np.testing.assert_array_equal(np.asarray(plain.dists),
                                  np.asarray(masked.dists))


def test_masked_beam_navigates_through_dead_wall():
    """Kill a full grid column between start and target: the search
    must still cross it (dead nodes route) but never return dead ids."""
    n_side, n, adj, dist_fn = _grid()
    q = jnp.asarray([9.1, 2.1], dtype=jnp.float32)  # nearest: (9, 2)
    node_valid = jnp.ones((n,), jnp.bool_)
    wall = [5 * n_side + y for y in range(n_side)]   # column x == 5
    node_valid = node_valid.at[jnp.asarray(wall)].set(False)
    res = beam_search(
        q, adj, jnp.int32(0), dist_fn=dist_fn, ef=8,
        node_valid=node_valid,
    )
    ids = np.asarray(res.ids)
    assert int(ids[0]) == 9 * n_side + 2          # found across the wall
    assert not np.isin(ids[ids >= 0], wall).any()  # no dead in results


# -- mutable index lifecycle -------------------------------------------------


def test_freeze_static_corpus_bit_identical():
    """Acceptance: zero-churn freeze() search == the equivalent
    immutable index search, bit for bit."""
    base, queries = _data()
    idx = QuIVerIndex.build(jnp.asarray(base[:1200]), PARAMS)
    mut = MutableQuIVerIndex.from_index(idx)
    frozen = mut.freeze()
    i1, s1 = idx.search(jnp.asarray(queries), k=10, ef=48)
    i2, s2 = frozen.search(jnp.asarray(queries), k=10, ef=48)
    np.testing.assert_array_equal(i1, i2)
    np.testing.assert_array_equal(s1, s2)
    # and the mutable index's own masked search agrees too
    i3, _ = mut.search(jnp.asarray(queries), k=10, ef=48)
    np.testing.assert_array_equal(i1, i3)


def test_inserted_vectors_immediately_findable():
    base, queries = _data()
    mut = MutableQuIVerIndex.build(
        jnp.asarray(base[:1500]), PARAMS, capacity=2600
    )
    mut.insert(jnp.asarray(base[1500:2000]))
    assert mut.n_live == 2000
    # recall over the grown corpus
    gt, _ = flat_search(base[:2000], queries, k=10)
    pred, _ = mut.search(jnp.asarray(queries), k=10, ef=48)
    assert recall_at_k(pred, gt) > 0.75
    # the new vectors themselves are their own nearest neighbours
    qnew = base[1500:1550]
    pred1, _ = mut.search(jnp.asarray(qnew), k=1, ef=48)
    hit = (pred1.ravel() == np.arange(1500, 1550)).mean()
    assert hit > 0.9, hit


def test_deleted_ids_never_in_results_and_consolidation_recovers():
    base, queries = _data()
    mut = MutableQuIVerIndex.build(
        jnp.asarray(base[:1500]), PARAMS, capacity=2600
    )
    dead = np.arange(100, 550)          # heavy: 30% of the corpus
    assert mut.delete(dead) == len(dead)

    pred, _ = mut.search(jnp.asarray(queries), k=10, ef=48)
    assert not np.isin(pred, dead).any()

    keep = np.ones(1500, bool)
    keep[dead] = False
    orig = np.nonzero(keep)[0]
    gt_pos, _ = flat_search(base[:1500][keep], queries, k=10)
    gt = orig[gt_pos]
    recall_before = recall_at_k(pred, gt)

    report = mut.consolidate()
    assert report["reclaimed"] == len(dead)
    assert mut.free_slots >= len(dead)
    pred2, _ = mut.search(jnp.asarray(queries), k=10, ef=48)
    assert not np.isin(pred2, dead).any()
    recall_after = recall_at_k(pred2, gt)
    assert recall_after > 0.75, (recall_before, recall_after)
    assert recall_after >= recall_before - 0.02

    # reclaimed slots are reused by the next insert
    new_ids = mut.insert(jnp.asarray(base[1500:1700]))
    assert np.isin(new_ids, dead).all()


def test_freeze_roundtrips_through_save_load(tmp_path):
    base, queries = _data()
    mut = MutableQuIVerIndex.build(
        jnp.asarray(base[:800]), PARAMS, capacity=1200
    )
    mut.delete(np.arange(0, 80))
    mut.insert(jnp.asarray(base[800:900]))
    mut.consolidate()

    # mutable save/load preserves search behaviour exactly
    p = str(tmp_path / "stream.npz")
    mut.save(p)
    mut2 = MutableQuIVerIndex.load(p)
    a, _ = mut.search(jnp.asarray(queries), k=5, ef=32)
    b, _ = mut2.search(jnp.asarray(queries), k=5, ef=32)
    np.testing.assert_array_equal(a, b)
    assert mut2.generation == mut.generation

    # freeze -> immutable save/load roundtrip
    frozen = mut.freeze()
    pf = str(tmp_path / "frozen.npz")
    frozen.save(pf)
    frozen2 = QuIVerIndex.load(pf)
    fa, _ = frozen.search(jnp.asarray(queries), k=5, ef=32)
    fb, _ = frozen2.search(jnp.asarray(queries), k=5, ef=32)
    np.testing.assert_array_equal(fa, fb)
    # frozen ids are compacted: all within [0, n_live)
    assert fa.max() < mut.n_live
    # an immutable archive can be adopted as a mutable index
    mut3 = MutableQuIVerIndex.load(pf)
    assert mut3.n_live == mut.n_live


def test_empty_and_capacity_edges():
    mut = MutableQuIVerIndex.empty(32, 64, PARAMS)
    ids, scores = mut.search(np.ones((3, 32), np.float32), k=5)
    assert (ids == -1).all()
    with pytest.raises(ValueError, match="capacity"):
        mut.insert(np.ones((65, 32), np.float32))
    with pytest.raises(ValueError, match="cannot freeze"):
        mut.freeze()
    rng = np.random.default_rng(0)
    mut.insert(rng.standard_normal((40, 32)).astype(np.float32))
    assert mut.n_live == 40
    ids, _ = mut.search(np.ones((1, 32), np.float32), k=5)
    assert (ids >= 0).all()


# -- sharded streaming -------------------------------------------------------


def test_streaming_sharded_single_device():
    """1-shard fan-out path runs in-process: global ids, tombstone
    exclusion, and the masked merge all exercise the shard_map code."""
    base, queries = _data()
    idx = StreamingShardedIndex.empty(
        base.shape[-1], n_shards=1, capacity_per_shard=1000,
        params=PARAMS,
    )
    gids = idx.insert(base[:600])
    assert len(set(gids.tolist())) == 600
    kill = gids[50:150]
    idx.delete(kill)
    ids, scores = idx.search(queries, ef=48, k=10)
    assert not np.isin(ids, kill).any()
    assert idx.n_live == 500
    idx.consolidate()
    ids2, _ = idx.search(queries, ef=48, k=10)
    assert not np.isin(ids2, kill).any()


@pytest.mark.slow
def test_streaming_sharded_multi_device():
    out = _run_with_devices(4, """
        import numpy as np
        from repro.stream import StreamingShardedIndex
        from repro.core.vamana import BuildParams
        from repro.core.baselines import flat_search, recall_at_k
        from repro.data.datasets import make_dataset

        base, queries = make_dataset("minilm-surrogate", n=2000,
                                     queries=25)
        params = BuildParams(m=6, ef_construction=32, prune_pool=32,
                             chunk=128)
        idx = StreamingShardedIndex.empty(
            base.shape[-1], n_shards=4, capacity_per_shard=700,
            params=params)
        gids = idx.insert(base[:1600])
        assert len(set(gids.tolist())) == 1600
        # round-robin balance
        assert [s.n_live for s in idx.shards] == [400] * 4

        kill = gids[100:260]
        idx.delete(kill)
        idx.consolidate()
        ids, _ = idx.search(queries, ef=48, k=10)
        assert not np.isin(ids, kill).any()

        gid2orig = {int(g): i for i, g in enumerate(gids)}
        keep = np.ones(1600, bool); keep[100:260] = False
        orig = np.nonzero(keep)[0]
        gt_pos, _ = flat_search(base[:1600][keep], queries, k=10)
        gt = orig[gt_pos]
        pred = np.vectorize(lambda g: gid2orig.get(int(g), -1))(ids)
        rec = recall_at_k(pred, gt)
        print("RECALL", rec)
        assert rec > 0.7, rec
    """)
    assert "RECALL" in out


def test_build_sharded_indexes_every_vector_with_indivisible_n():
    out = _run_with_devices(3, """
        import numpy as np
        from repro.core.distributed import build_sharded, search_sharded
        from repro.core.baselines import flat_search
        from repro.core.vamana import BuildParams
        from repro.data.datasets import make_dataset

        base, _ = make_dataset("minilm-surrogate", n=904, queries=4)
        idx = build_sharded(
            base, 3,
            BuildParams(m=4, ef_construction=24, prune_pool=24,
                        chunk=128))
        per = idx.sig_words.shape[1]
        assert per == 302                       # ceil(904 / 3)
        assert int(np.asarray(idx.live).sum()) == 904
        # the tail vectors (would have been dropped before) are found
        tail = base[900:904]
        ids, _ = search_sharded(idx, tail, ef=48, k=1)
        print("TAIL", ids.ravel().tolist())
        assert ids.ravel().tolist() == [900, 901, 902, 903]
        # padded fill slots never surface
        all_ids, _ = search_sharded(idx, base[:100], ef=48, k=10)
        assert (all_ids < 904).all()
    """)
    assert "TAIL" in out


# -- satellite fixes ---------------------------------------------------------


def test_named_params_save_load_with_legacy_compat(tmp_path):
    base, queries = _data()
    params = BuildParams(m=4, ef_construction=24, prune_pool=24,
                         chunk=128, alpha=1.15, beam_expand=2)
    idx = QuIVerIndex.build(jnp.asarray(base[:600]), params)
    p = str(tmp_path / "named.npz")
    idx.save(p)
    z = np.load(p)
    assert "params" not in z                # positional array is gone
    assert int(z["param_m"]) == 4
    idx2 = QuIVerIndex.load(p)
    assert idx2.params == params            # alpha survives exactly

    # legacy positional archive still loads
    legacy = {k: z[k] for k in z.files if not k.startswith("param_")}
    legacy["params"] = np.array(
        [4, 24, 1150, 128, 24, 8, 8, 1, 0, 2], dtype=np.int64
    )
    pl = str(tmp_path / "legacy.npz")
    np.savez(pl, **legacy)
    idx3 = QuIVerIndex.load(pl)
    assert idx3.params == params
    i2, _ = idx2.search(jnp.asarray(queries), k=5, ef=32)
    i3, _ = idx3.search(jnp.asarray(queries), k=5, ef=32)
    np.testing.assert_array_equal(i2, i3)


def test_retriever_augment_handles_missing_hits():
    """-1 padding ids from a sparse index must inject pad tokens, not
    the last document in the store (the old silent-gather bug)."""
    from repro.serve.engine import Retriever

    rng = np.random.default_rng(0)
    docs = rng.standard_normal((5, 16)).astype(np.float32)
    idx = MutableQuIVerIndex.build(
        jnp.asarray(docs),
        BuildParams(m=2, ef_construction=8, prune_pool=8, chunk=128),
        capacity=32,
    )
    doc_tokens = np.arange(5 * 3, dtype=np.int32).reshape(5, 3) + 100

    def embed(tokens):
        return jnp.asarray(docs[:len(tokens)])

    r = Retriever(index=idx, doc_tokens=doc_tokens, embed_fn=embed,
                  k=8, ef=8)       # k=8 > 5 docs -> guaranteed -1 ids
    out = r.augment(np.zeros((2, 4), np.int32))
    assert out.shape == (2, 8 * 3 + 4)
    ctx = out[:, :8 * 3].reshape(2, 8, 3)
    # padded hits are all pad_token, and never equal the last doc's row
    is_pad = (ctx == 0).all(-1)
    assert is_pad.any(axis=1).all()
    last_doc = doc_tokens[-1]
    n_last = (ctx == last_doc).all(-1).sum(axis=1)
    assert (n_last <= 1).all()      # the real hit, not the pad gathers


def test_retriever_add_documents_grows_mutable_corpus():
    from repro.serve.engine import Retriever

    rng = np.random.default_rng(1)
    docs = rng.standard_normal((20, 24)).astype(np.float32)
    docs /= np.linalg.norm(docs, axis=-1, keepdims=True)
    idx = MutableQuIVerIndex.build(
        jnp.asarray(docs[:10]),
        BuildParams(m=2, ef_construction=8, prune_pool=8, chunk=128),
        capacity=64,
    )
    doc_tokens = np.arange(10 * 3, dtype=np.int32).reshape(10, 3)
    store = {}

    def embed(tokens):
        return jnp.asarray(
            np.stack([store[tuple(t)] for t in np.asarray(tokens)])
        )

    r = Retriever(index=idx, doc_tokens=doc_tokens, embed_fn=embed,
                  k=1, ef=16)
    new_tokens = (np.arange(10 * 3, 20 * 3, dtype=np.int32)
                  .reshape(10, 3))
    ids = r.add_documents(new_tokens, embeddings=docs[10:])
    assert len(ids) == 10 and idx.n_live == 20
    # a query at a new doc's embedding retrieves that doc's tokens
    store[tuple(np.zeros(3, np.int32))] = docs[15]
    out = r.augment(np.zeros((1, 3), np.int32))
    np.testing.assert_array_equal(out[0, :3], r.doc_tokens[ids[5]])


def test_streaming_dedup_matches_batch_semantics():
    from repro.data.dedup import streaming_dedup

    rng = np.random.default_rng(0)
    base = rng.standard_normal((260, 48)).astype(np.float32)
    base /= np.linalg.norm(base, axis=-1, keepdims=True)
    dup = base[:15] + 0.001 * rng.standard_normal((15, 48)).astype(
        np.float32
    )
    corpus = np.concatenate([base[:130], dup, base[130:]], axis=0)
    keep = streaming_dedup(corpus, threshold=0.98, ef=48, scan_batch=64)
    dropped = set(range(len(corpus))) - set(keep.tolist())
    planted = set(range(130, 145))
    assert len(dropped & planted) >= 13
    assert len(dropped - planted) <= 4
    # first occurrence wins: the originals are all kept
    assert set(range(15)) <= set(keep.tolist())
