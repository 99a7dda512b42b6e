"""Pallas TPU kernel: batched IVF centroid list-scan in BQ space.

The coarse routing primitive of the IVF-over-BQ layer (DESIGN.md §13):
score a block of queries against *every* list centroid signature and
let the caller keep the top-p lists.  It is the Table-1 weighted
similarity of ``repro.kernels.bq_distance`` with the centroid matrix in
the base-row role, so it runs that kernel's tiling: a two-axis grid of
(block_q, block_l) output tiles, the centroid axis cut into 128-wide
blocks.  Only one centroid block and one (block_q, block_l) int32 tile
are VMEM-resident at a time, so the scan compiles at any L — keeping
the whole (L, 2W) centroid matrix resident ran out of VMEM at
L = 1,024 × 768-d (the √N lists of a 1M corpus).

Emits raw int32 similarities (larger = nearer), matching the
``MetricOps`` convention of ``repro.kernels.dispatch``; the top-p
selection itself is a ``lax.top_k`` over the (Q, L) tile — L is small,
so selection is never the bottleneck and staying out of the kernel
keeps Mosaic layouts on the native (8, 128) tiling.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.bq_distance import bq_distance_pallas


@functools.partial(
    jax.jit, static_argnames=("dim", "block_q", "block_l", "interpret")
)
def list_scan_pallas(
    q_words: jnp.ndarray,
    cent_words: jnp.ndarray,
    mask: jnp.ndarray,
    *,
    dim: int,
    block_q: int = 8,
    block_l: int = 128,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """(Q, 2W) queries x (L, 2W) centroids -> (Q, L) int32 similarity.

    Q % block_q == 0 and L % block_l == 0 (pad with zero signatures; a
    zero pad column scores the orthogonal-pair similarity and never
    wins a top-p race against a real centroid for in-distribution
    queries — callers slice pads off anyway).  ``interpret=None``
    resolves by platform: compiled Mosaic on TPU, interpreter elsewhere.
    """
    return -bq_distance_pallas(
        q_words, cent_words, mask, dim=dim, block_q=block_q,
        block_n=block_l, interpret=interpret,
    )
