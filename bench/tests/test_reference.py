"""The reference and the comparison that decides ``correct``."""

import numpy as np
import pytest

from corpus import make_corpus
from reference import bad_rows, compare, exact_topk, score_gap

CORPUS = {"generator": "contrastive_surrogate", "d": 64, "n_topics": 8,
          "subclusters": 4, "intrinsic": 16}


@pytest.fixture(scope="module")
def data():
    base, pool = make_corpus(CORPUS, 500, 40, 2**31 + 7)
    ids, sims = exact_topk(base, pool, 10, block=16)
    return base, pool, ids, sims


def test_corpus_is_unit_norm_and_seeded():
    a = make_corpus(CORPUS, 100, 8, 3)
    b = make_corpus(CORPUS, 100, 8, 3)
    c = make_corpus(CORPUS, 100, 8, 4)
    np.testing.assert_array_equal(a[0], b[0])
    np.testing.assert_array_equal(a[1], b[1])
    assert not np.array_equal(a[0], c[0])
    np.testing.assert_allclose(np.linalg.norm(a[0], axis=1), 1, atol=1e-6)
    np.testing.assert_allclose(np.linalg.norm(a[1], axis=1), 1, atol=1e-6)


def test_exact_topk_is_the_sorted_brute_force(data):
    base, pool, ids, sims = data
    full = pool.astype(np.float64) @ base.astype(np.float64).T
    want = np.argsort(-full, axis=1)[:, :10]
    np.testing.assert_array_equal(ids, want)
    np.testing.assert_allclose(sims, np.take_along_axis(full, want, 1))


def test_exact_answers_are_correct(data):
    base, pool, ids, sims = data
    out = compare(served_ids=ids, served_scores=sims.astype(np.float32),
                  pool_rows=np.arange(len(pool)), missing=0, base=base,
                  pool=pool, truth_ids=ids, recall_floor=0.88,
                  rng=np.random.default_rng(0))
    assert out["correct"]
    assert out["recall_at_10"]["value"] == 1.0
    assert out["score_gap"]["value"] < 1e-7


def test_each_number_fails_on_its_own(data):
    base, pool, ids, sims = data
    rows = np.arange(len(pool))

    def check(**kw):
        args = dict(served_ids=ids, served_scores=sims, pool_rows=rows,
                    missing=0, base=base, pool=pool, truth_ids=ids,
                    recall_floor=0.88, rng=np.random.default_rng(0))
        args.update(kw)
        return compare(**args)

    assert not check(missing=1)["correct"]
    # the right ids scored as if they were their neighbours' rows
    shuffled = check(served_scores=sims[::-1].copy())
    assert shuffled["score_gap"]["value"] > 1e-3
    assert not shuffled["correct"]
    # answers for the wrong queries
    wrong = check(served_ids=ids[::-1].copy(), served_scores=sims[::-1])
    assert wrong["recall_at_10"]["value"] < 0.88
    assert not wrong["correct"]


def test_bad_rows():
    ids = np.array([[0, 1, 2], [0, 0, 2], [0, 1, 9], [0, 1, 2], [0, 1, 2]])
    scores = np.array([[3, 2, 1], [3, 2, 1], [3, 2, 1], [1, 2, 3],
                       [3, np.nan, 1]], dtype=np.float32)
    # rows 1 (repeated id), 2 (id outside 5 rows), 3 (ascending), 4 (nan)
    assert bad_rows(ids, scores, n=5) == 4
    assert score_gap(ids[2:3], scores[2:3], np.ones((1, 2)),
                     np.ones((5, 2))) == float("inf")
