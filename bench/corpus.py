"""The corpus and its query pool, fixed by the configuration.

A copy of the contrastive-embedding surrogate of
``repro.data.datasets.contrastive_surrogate`` and of the held-out query
recipe of ``make_dataset``, kept with the benchmark so that no change
to the program can move the data a cell is measured on.  As in
``make_dataset``, both come from a seed of the configuration's own, so
every run builds and searches the same graph with the same queries;
the run's seed orders them (``harness.request_rows``).
"""

from __future__ import annotations

import numpy as np


def _l2norm(x: np.ndarray) -> np.ndarray:
    return x / np.maximum(np.linalg.norm(x, axis=-1, keepdims=True), 1e-12)


def contrastive_surrogate(rng: np.random.Generator, n: int, d: int, *,
                          n_topics: int, subclusters: int,
                          intrinsic: int) -> np.ndarray:
    """Hierarchical anisotropic clusters in a low-rank subspace of the
    unit sphere, (n, d) float32 (the repo's contrastive surrogate)."""
    topics = _l2norm(rng.standard_normal((n_topics, intrinsic)))
    sub = topics[:, None, :] + 0.45 * rng.standard_normal(
        (n_topics, subclusters, intrinsic)
    )
    sub = _l2norm(sub.reshape(-1, intrinsic))
    assign = rng.integers(0, sub.shape[0], size=n)
    spectrum = 1.0 / np.sqrt(1.0 + np.arange(intrinsic))
    within = rng.standard_normal((n, intrinsic)) * spectrum * 0.35
    low = sub[assign] + within
    basis, _ = np.linalg.qr(rng.standard_normal((d, intrinsic)))
    x = low @ basis.T
    x += 0.02 * rng.standard_normal((n, d))
    return _l2norm(x.astype(np.float32))


GENERATORS = {"contrastive_surrogate": contrastive_surrogate}


def make_corpus(corpus: dict, n: int, pool: int, seed: int):
    """(base (n, d), queries (pool, d)) float32, unit-norm.

    ``pool`` members are drawn from ``n + pool`` generated vectors,
    held out of the base and perturbed by 0.02 Gaussian noise (the
    ``make_dataset`` recipe)."""
    rng = np.random.default_rng(seed)
    spec = dict(corpus)
    gen = GENERATORS[spec.pop("generator")]
    d = spec.pop("d")
    x = gen(rng, n + pool, d, **spec)
    qidx = rng.choice(len(x), size=pool, replace=False)
    mask = np.ones(len(x), dtype=bool)
    mask[qidx] = False
    q = x[qidx] + 0.02 * rng.standard_normal((pool, d)).astype(np.float32)
    return x[mask], _l2norm(q).astype(np.float32)
