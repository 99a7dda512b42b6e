"""Filtered-search subsystem tests (DESIGN.md §9): predicate masks,
two-mask beam composition, selectivity routing, label persistence
across save/load/insert/consolidate/freeze, sharded pushdown, and the
filter=None bit-identity guard."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.baselines import flat_search, recall_at_k
from repro.core.beam import beam_search
from repro.core.index import QuIVerIndex, batch_bucket
from repro.core.vamana import BuildParams
from repro.data.datasets import make_dataset
from repro.filter import (
    All,
    Any,
    LabelStore,
    Not,
    estimate_selectivity,
    eval_mask,
    pack_label_rows,
    route,
    widened_ef,
)
from repro.filter.labels import popcount_rows
from repro.stream import MutableQuIVerIndex, StreamingShardedIndex

jax.config.update("jax_platform_name", "cpu")

PARAMS = BuildParams(m=6, ef_construction=32, prune_pool=32, chunk=128)


@functools.lru_cache(maxsize=1)
def _data():
    base, queries = make_dataset("minilm-surrogate", n=2000, queries=25)
    return base, queries


@functools.lru_cache(maxsize=1)
def _labeled_index():
    """Built index + membership matrix at selectivities ~0.5/0.1/0.01."""
    base, queries = _data()
    rng = np.random.default_rng(0)
    member = np.stack(
        [rng.random(len(base)) < p for p in (0.5, 0.1, 0.01)], axis=1
    )
    rows = [np.nonzero(m)[0].tolist() for m in member]
    # the 5-pt acceptance bar tracks graph quality: the filtered path
    # needs the same build strength an unfiltered 95%-recall graph does
    build = BuildParams(m=8, ef_construction=64, prune_pool=64, chunk=128)
    idx = QuIVerIndex.build(jnp.asarray(base), build)
    idx.attach_labels(rows, n_labels=3)
    idx.build_label_entries(min_count=32)
    return idx, member


def _filtered_gt(base, queries, mask, k=10):
    match = np.nonzero(mask)[0]
    gt_pos, _ = flat_search(base[match], queries, k=k)
    return match[gt_pos]


# -- predicate compilation + selectivity ------------------------------------


def test_pack_and_eval_mask_roundtrip():
    rows = [[0], [1, 33], [], [0, 1, 33]]
    words = pack_label_rows(rows, n_labels=40)
    assert words.shape == (4, 2)
    got = np.asarray(eval_mask(jnp.asarray(words), Any(33)))
    np.testing.assert_array_equal(got, [False, True, False, True])
    got = np.asarray(eval_mask(jnp.asarray(words), All(1, 33)))
    np.testing.assert_array_equal(got, [False, True, False, True])
    got = np.asarray(eval_mask(jnp.asarray(words), Not(0)))
    np.testing.assert_array_equal(got, [False, True, True, False])
    got = np.asarray(
        eval_mask(jnp.asarray(words), All(Any(0, 1), Not(33)))
    )
    np.testing.assert_array_equal(got, [True, False, False, False])


def test_predicate_validation_and_selectivity_bounds():
    with pytest.raises(ValueError, match="outside"):
        LabelStore(8, 4).mask(7)
    with pytest.raises(TypeError):
        from repro.filter import as_predicate
        as_predicate("tenant-a")
    counts = {0: 50, 1: 10, 2: 1}
    cf = counts.get
    assert estimate_selectivity(0, cf, 100) == 0.5
    assert estimate_selectivity(Any(0, 1), cf, 100) == pytest.approx(0.6)
    assert estimate_selectivity(All(0, 1), cf, 100) == pytest.approx(0.1)
    assert estimate_selectivity(Not(0), cf, 100) == pytest.approx(0.5)
    assert route(0.5, 0.05) == "graph"
    assert route(0.01, 0.05) == "brute"
    assert widened_ef(64, 0.1, 0.05, 10_000) == 640
    assert widened_ef(64, 0.01, 0.05, 10_000) == 1280   # clamped @ floor
    # quantized to integer multiples of ef: continuous widening would
    # retrace the statically-keyed beam on every selectivity drift
    assert widened_ef(64, 0.9, 0.05, 10_000) == 128
    assert widened_ef(64, 0.34, 0.05, 10_000) == 192
    assert widened_ef(64, 0.1, 0.05, 300) == 300        # capped at n
    assert widened_ef(64, 1.0, 0.05, 8) == 64           # never below ef


def test_label_store_attach_modes_and_counts():
    store = LabelStore(16, 5)
    store.set(np.arange(8), 2)                     # categorical broadcast
    assert store.count(2) == 8
    store.add([0, 1], [[3], [3, 4]])               # multi-tag OR
    assert store.labels_of(0) == [2, 3]
    assert store.labels_of(1) == [2, 3, 4]
    store.set([0], [1])                            # overwrite
    assert store.labels_of(0) == [1]
    store.clear([1])
    assert store.labels_of(1) == []
    assert store.count(2) == 6
    # duplicate ids in one batch OR together, not last-one-wins
    store.add([5, 5], [[3], [4]])
    assert store.labels_of(5) == [2, 3, 4]
    # incremental counts stay exact through every mutation mode
    fresh = popcount_rows(np.asarray(store.words), store.n_labels)
    np.testing.assert_array_equal(store.counts, fresh)


# -- two-mask beam composition ----------------------------------------------


def test_beam_result_valid_all_true_is_bit_identical():
    base, queries = _data()
    idx, _ = _labeled_index()
    n = idx.sigs.words.shape[0]
    backend = idx.backend()
    q = backend.encode_queries(jnp.asarray(queries[:1]))[0]
    plain = beam_search(
        q, idx.adjacency, jnp.int32(idx.medoid),
        dist_fn=backend.dist_fn, ef=16,
    )
    masked = beam_search(
        q, idx.adjacency, jnp.int32(idx.medoid),
        dist_fn=backend.dist_fn, ef=16,
        result_valid=jnp.ones((n,), jnp.bool_),
    )
    np.testing.assert_array_equal(np.asarray(plain.ids),
                                  np.asarray(masked.ids))
    np.testing.assert_array_equal(np.asarray(plain.dists),
                                  np.asarray(masked.dists))


def test_beam_two_masks_conjoin():
    """node_valid ∧ result_valid: a node failing either never surfaces,
    but both kinds of masked nodes still route navigation."""
    idx, member = _labeled_index()
    n = idx.sigs.words.shape[0]
    backend = idx.backend()
    _, queries = _data()
    q = backend.encode_queries(jnp.asarray(queries[:1]))[0]
    rng = np.random.default_rng(3)
    node_valid = jnp.asarray(rng.random(n) > 0.3)
    result_valid = jnp.asarray(member[:, 0])
    res = beam_search(
        q, idx.adjacency, jnp.int32(idx.medoid),
        dist_fn=backend.dist_fn, ef=32,
        node_valid=node_valid, result_valid=result_valid,
    )
    ids = np.asarray(res.ids)
    ids = ids[ids >= 0]
    both = np.asarray(node_valid & result_valid)
    assert ids.size > 0
    assert both[ids].all()


# -- frozen-index filtered search -------------------------------------------


@pytest.mark.parametrize("label,floor_recall", [(0, 0.95), (1, 0.95)])
def test_filtered_recall_within_5pts(label, floor_recall):
    """Acceptance: filtered recall@10 within 5 points of exact filtered
    ground truth at selectivity ~0.5 and ~0.1 (graph route)."""
    base, queries = _data()
    idx, member = _labeled_index()
    gt = _filtered_gt(base, queries, member[:, label])
    pred, scores = idx.search(jnp.asarray(queries), k=10, ef=64,
                              filter=label)
    rec = recall_at_k(pred, gt)
    assert rec >= floor_recall, (label, rec)
    # every returned id matches the predicate
    ok = pred[pred >= 0]
    assert member[ok, label].all()
    # reranked scores are cosine similarities
    assert (scores[np.isfinite(scores)] <= 1.0 + 1e-5).all()


def test_filtered_brute_route_is_exact():
    """Below the selectivity floor the match set is brute-forced:
    recall is exactly 1 against filtered ground truth."""
    base, queries = _data()
    idx, member = _labeled_index()
    mask = member[:, 2]                     # ~1% selectivity
    k = min(10, int(mask.sum()))
    gt = _filtered_gt(base, queries, mask, k=k)
    pred, _ = idx.search(jnp.asarray(queries), k=k, ef=64, filter=2)
    assert recall_at_k(pred[:, :k], gt) == 1.0
    assert member[pred[pred >= 0], 2].all()


def test_filtered_brute_route_k_larger_than_match_set():
    """k above the match count (and above the pad width) must return
    -1/-inf tails, not crash top_k (regression)."""
    base, queries = _data()
    idx, member = _labeled_index()
    n_match = int(member[:, 2].sum())
    pred, scores = idx.search(jnp.asarray(queries), k=100, ef=64,
                              filter=2)
    assert pred.shape == (len(queries), 100)
    valid = pred >= 0
    assert valid.sum(axis=1).max() <= n_match
    assert (pred[~valid] == -1).all()
    assert np.isneginf(scores[~valid]).all()


def test_not_of_union_estimate_cannot_force_giant_brute_scan():
    """Not(Any(a, b)) over overlapping popular labels *estimates* below
    the floor (complement of a union bound) but truly matches ~half the
    corpus: the exact-popcount guard must reroute it to graph search
    (regression — the old code materialized the huge match set)."""
    base, queries = _data()
    n = len(base)
    rng = np.random.default_rng(8)
    both = rng.random(n) < 0.5                   # a and b coincide
    rows = [[0, 1] if b else [] for b in both]
    idx = QuIVerIndex.build(jnp.asarray(base), PARAMS)
    idx.attach_labels(rows, n_labels=2)
    expr = Not(Any(0, 1))
    cf = idx.labels.count_fn()
    assert estimate_selectivity(expr, cf, n) < 0.05   # the bad bound
    pred, _ = idx.search(jnp.asarray(queries), k=10, ef=48, filter=expr)
    ok = pred[pred >= 0]
    assert ok.size > 0
    assert (~both[ok]).all()


def test_filtered_search_small_live_set_does_not_shrink_beam():
    """A filtered search over fewer live docs than ef/k must not clamp
    the beam below k (regression: widened_ef returned n_live=8 and
    top_k crashed)."""
    rng = np.random.default_rng(11)
    docs = rng.standard_normal((8, 24)).astype(np.float32)
    mut = MutableQuIVerIndex.empty(
        24, 64,
        BuildParams(m=2, ef_construction=8, prune_pool=8, chunk=128),
        n_labels=2,
    )
    mut.insert(jnp.asarray(docs), labels=[0] * 8)
    ids, scores = mut.search(jnp.asarray(docs[:2]), k=10, ef=64,
                             filter=0)       # used to crash in top_k
    assert ids.shape == (2, 10)
    valid = ids >= 0
    assert valid[:, 0].all()                 # found live matches
    assert valid.sum(axis=1).max() <= 8      # never more than live
    assert np.isneginf(scores[~valid]).all()


def test_delete_clears_label_bits_for_routing():
    """Deleting most of a label's members must drop its popcount so
    selectivity routing sees live counts, not dead-inflated ones
    (regression)."""
    base, _ = _data()
    mut = MutableQuIVerIndex.empty(base.shape[-1], 800, PARAMS,
                                   n_labels=2)
    ids = mut.insert(jnp.asarray(base[:500]),
                     labels=[1] * 100 + [0] * 400)
    assert mut.labels.count(1) == 100
    mut.delete(ids[:95])                          # kill 95% of label 1
    assert mut.labels.count(1) == 5
    cf = mut.labels.count_fn()
    assert estimate_selectivity(1, cf, mut.n_live) < 0.05


def test_filtered_composite_predicates_only_match():
    base, queries = _data()
    idx, member = _labeled_index()
    expr = All(0, Not(1))
    want = member[:, 0] & ~member[:, 1]
    pred, _ = idx.search(jnp.asarray(queries), k=10, ef=64, filter=expr)
    ok = pred[pred >= 0]
    assert ok.size > 0
    assert want[ok].all()


def test_filter_none_matches_all_true_predicate_and_per_query():
    """filter=None takes the unmasked beam path; an all-matching
    predicate and per-query batching must agree with it exactly."""
    base, queries = _data()
    idx, member = _labeled_index()
    i0, s0 = idx.search(jnp.asarray(queries), k=10, ef=48)
    # tail padding: searching in odd-sized slices hits different pad
    # buckets but must return identical per-query results
    i1a, s1a = idx.search(jnp.asarray(queries[:7]), k=10, ef=48)
    i1b, s1b = idx.search(jnp.asarray(queries[7:]), k=10, ef=48)
    np.testing.assert_array_equal(i0, np.concatenate([i1a, i1b]))
    np.testing.assert_array_equal(s0, np.concatenate([s1a, s1b]))
    # an always-true predicate returns the same ids: estimated
    # selectivity 1.0 keeps ef unwidened, and with per-label entries
    # disabled the start is the medoid, so the only difference is the
    # all-valid masked beam — bit-identical by construction
    saved_entries = idx.labels.entries.copy()
    idx.labels.entries[:] = -1
    try:
        i2, _ = idx.search(jnp.asarray(queries), k=10, ef=48,
                           filter=Any(0, Not(0)))
        np.testing.assert_array_equal(i0, i2)
    finally:
        idx.labels.entries[:] = saved_entries


def test_batch_bucket_ladder():
    assert batch_bucket(1, 256) == 8
    assert batch_bucket(8, 256) == 8
    assert batch_bucket(25, 256) == 32
    assert batch_bucket(129, 256) == 256
    assert batch_bucket(256, 256) == 256
    assert batch_bucket(40, 32) == 32     # never exceeds query_batch


def test_label_entries_route_start_into_region():
    idx, member = _labeled_index()
    assert (idx.labels.entries[:2] >= 0).all()   # frequent labels
    assert idx.labels.entries[2] == -1           # rare label: none
    for lb in (0, 1):
        assert member[idx.labels.entries[lb], lb]


def test_labels_survive_index_save_load(tmp_path):
    base, queries = _data()
    idx, member = _labeled_index()
    p = str(tmp_path / "labeled.npz")
    idx.save(p)
    idx2 = QuIVerIndex.load(p)
    assert idx2.labels is not None
    assert idx2.labels.n_labels == 3
    np.testing.assert_array_equal(idx2.labels.entries, idx.labels.entries)
    a, _ = idx.search(jnp.asarray(queries), k=10, ef=48, filter=Any(0, 1))
    b, _ = idx2.search(jnp.asarray(queries), k=10, ef=48,
                       filter=Any(0, 1))
    np.testing.assert_array_equal(a, b)
    mem = idx2.memory_breakdown()
    assert mem["hot_label_bytes"] > 0
    assert mem["hot_label_bytes"] <= mem["hot_total_bytes"]


# -- mutable index: streaming labels + tombstone composition ----------------


def test_streaming_insert_labels_and_tombstone_composition():
    base, queries = _data()
    rng = np.random.default_rng(1)
    labels = rng.integers(0, 4, 1200)
    mut = MutableQuIVerIndex.empty(
        base.shape[-1], 2000, PARAMS, n_labels=4
    )
    mut.insert(jnp.asarray(base[:1200]), labels=list(labels))
    mask0 = np.asarray(mut.labels.mask(0)) & mut.live
    kill = np.nonzero(mask0)[0][:120]
    mut.delete(kill)

    pred, _ = mut.search(jnp.asarray(queries), k=10, ef=48, filter=0)
    ok = pred[pred >= 0]
    assert ok.size > 0
    assert not np.isin(ok, kill).any()           # no tombstones
    live_match = np.asarray(mut.labels.mask(0)) & mut.live
    assert live_match[ok].all()                  # only live matches

    # recall against live filtered ground truth (an insert-built m=6
    # graph is weaker than a batch build — this guards composition
    # correctness, not peak recall, which test_filtered_recall_within_
    # 5pts pins on the batch-built index)
    match = np.nonzero(live_match)[0]
    gt_pos, _ = flat_search(base[match], queries, k=10)
    gt = match[gt_pos]
    assert recall_at_k(pred, gt) >= 0.75


def test_streaming_labels_survive_consolidate_and_reuse():
    base, _ = _data()
    rng = np.random.default_rng(2)
    labels = rng.integers(0, 3, 600)
    mut = MutableQuIVerIndex.empty(
        base.shape[-1], 1000, PARAMS, n_labels=3
    )
    ids = mut.insert(jnp.asarray(base[:600]), labels=list(labels))
    dead = ids[100:200]
    mut.delete(dead)
    mut.consolidate()
    # reclaimed slots lost their labels...
    assert all(mut.labels.labels_of(int(i)) == [] for i in dead[:10])
    # ...and a label-less reinsert into them stays clean
    new_ids = mut.insert(jnp.asarray(base[600:700]))
    assert np.isin(new_ids, dead).all()
    assert all(mut.labels.labels_of(int(i)) == [] for i in new_ids[:10])
    # live nodes kept their labels
    keep = ids[:100]
    for i in keep[:10]:
        assert mut.labels.labels_of(int(i)) == [int(labels[int(i)])]


def test_streaming_labels_save_load_and_freeze(tmp_path):
    base, queries = _data()
    rng = np.random.default_rng(3)
    labels = rng.integers(0, 3, 500)
    mut = MutableQuIVerIndex.empty(
        base.shape[-1], 800, PARAMS, n_labels=3
    )
    mut.insert(jnp.asarray(base[:500]), labels=list(labels))
    mut.delete(np.arange(0, 50))
    mut.build_label_entries(min_count=16)

    p = str(tmp_path / "stream_labeled.npz")
    mut.save(p)
    mut2 = MutableQuIVerIndex.load(p)
    assert mut2.labels is not None
    a, _ = mut.search(jnp.asarray(queries), k=5, ef=32, filter=1)
    b, _ = mut2.search(jnp.asarray(queries), k=5, ef=32, filter=1)
    np.testing.assert_array_equal(a, b)

    # freeze compacts the store and keeps filtered search consistent
    frozen = mut.freeze()
    assert frozen.labels.words.shape[0] == mut.n_live
    fi, _ = frozen.search(jnp.asarray(queries), k=5, ef=32, filter=1)
    fmask = np.asarray(frozen.labels.mask(1))
    ok = fi[fi >= 0]
    assert ok.size > 0 and fmask[ok].all()
    # adoption keeps labels too
    mut3 = MutableQuIVerIndex.from_index(frozen)
    assert mut3.labels is not None and mut3.labels.n_labels == 3
    c, _ = mut3.search(jnp.asarray(queries), k=5, ef=32, filter=1)
    ok3 = c[c >= 0]
    assert ok3.size > 0 and fmask[ok3].all()


def test_insert_labels_without_store_raises():
    mut = MutableQuIVerIndex.empty(32, 64, PARAMS)
    with pytest.raises(ValueError, match="enable_labels"):
        mut.insert(np.ones((2, 32), np.float32), labels=[0, 1])
    with pytest.raises(ValueError, match="filtered search"):
        mut.insert(np.ones((2, 32), np.float32))
        mut.search(np.ones((1, 32), np.float32), k=2, filter=0)


# -- sharded: predicate pushdown --------------------------------------------


def test_sharded_streaming_filter_pushdown_single_device():
    base, queries = _data()
    rng = np.random.default_rng(4)
    labels = rng.integers(0, 3, 800)
    idx = StreamingShardedIndex.empty(
        base.shape[-1], n_shards=1, capacity_per_shard=1200,
        params=PARAMS, n_labels=3,
    )
    gids = idx.insert(base[:800], labels=list(labels))
    kill = gids[:100]
    idx.delete(kill)
    idx.build_label_entries(min_count=16)

    ids, _ = idx.search(queries, ef=48, k=10, filter=Any(0, 2))
    ok = ids[ids >= 0]
    assert ok.size > 0
    assert not np.isin(ok, kill).any()
    glab = {int(g): int(labels[i]) for i, g in enumerate(gids)}
    assert all(glab[int(g)] in (0, 2) for g in ok)

    # unfiltered search on the same snapshot still works
    ids_u, _ = idx.search(queries, ef=48, k=10)
    assert not np.isin(ids_u[ids_u >= 0], kill).any()


def test_build_sharded_with_labels_filtered_search():
    from repro.core.distributed import build_sharded, search_sharded

    base, queries = _data()
    rng = np.random.default_rng(5)
    labels = rng.integers(0, 2, 900)
    idx = build_sharded(
        base[:900], 1,
        BuildParams(m=4, ef_construction=24, prune_pool=24, chunk=128),
        labels=list(labels), label_entry_min=16,
    )
    assert idx.label_words is not None and idx.n_labels == 2
    ids, _ = search_sharded(idx, queries, ef=48, k=10, filter=1)
    ok = ids[ids >= 0]
    assert ok.size > 0
    assert (labels[ok] == 1).all()
    gt = _filtered_gt(base[:900], queries, labels == 1)
    assert recall_at_k(ids, gt) >= 0.85
    with pytest.raises(ValueError, match="label_words"):
        search_sharded(
            build_sharded(base[:300], 1, PARAMS), queries, k=5, filter=0
        )


# -- retriever: metadata-filtered RAG ---------------------------------------


def test_retriever_filtered_rag():
    from repro.serve.engine import Retriever

    rng = np.random.default_rng(6)
    docs = rng.standard_normal((40, 24)).astype(np.float32)
    docs /= np.linalg.norm(docs, axis=-1, keepdims=True)
    lang = rng.integers(0, 2, 40)                # 0 = "en", 1 = "de"
    idx = MutableQuIVerIndex.empty(
        24, 64,
        BuildParams(m=2, ef_construction=8, prune_pool=8, chunk=128),
        n_labels=2,
    )
    idx.insert(jnp.asarray(docs), labels=list(lang))
    doc_tokens = (
        np.arange(40 * 3, dtype=np.int32).reshape(40, 3) + 100
    )
    store = {}

    def embed(tokens):
        return jnp.asarray(
            np.stack([store[tuple(t)] for t in np.asarray(tokens)])
        )

    r = Retriever(index=idx, doc_tokens=doc_tokens, embed_fn=embed,
                  k=3, ef=32, filter=1)
    probe = np.zeros((1, 3), np.int32)
    store[tuple(probe[0])] = docs[int(np.nonzero(lang == 0)[0][0])]
    out = r.augment(probe)
    ctx = out[0, : 3 * 3].reshape(3, 3)
    # every retrieved document is language 1, even though the probe
    # embedding sits on a language-0 document
    for row in ctx:
        if (row == 0).all():
            continue                             # pad slot
        doc_id = int(row[0] - 100) // 3
        assert lang[doc_id] == 1
    # per-call override beats the configured filter
    out0 = r.augment(probe, filter=0)
    row0 = out0[0, :3]
    assert lang[int(row0[0] - 100) // 3] == 0

    # add_documents carries labels through
    new_tokens = np.arange(300, 306, dtype=np.int32).reshape(2, 3)
    new_ids = r.add_documents(
        new_tokens, embeddings=docs[:2] * -1.0, labels=[1, 1]
    )
    assert all(idx.labels.labels_of(int(i)) == [1] for i in new_ids)
