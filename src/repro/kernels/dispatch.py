"""Kernel dispatch: one owner for every BQ distance evaluation.

Every metric backend in ``repro.core.metric`` obtains its distance
primitives here, *once, at construction time*.  The route is decided by
the accelerator platform (overridable for tests):

* ``pallas`` — the Mosaic-compiled Pallas kernels in this package
  (``interpret=False``); chosen automatically on TPU.
* ``ref``    — the pure-jnp oracle in ``repro.core.bq``; chosen on
  CPU/GPU, where Pallas-TPU kernels would fall back to the (slow)
  interpreter.

Callers never touch ``bq.symmetric_similarity_words`` directly — the
registered backend over this module is the single owner of the BQ2
distance (enforced by a grep test in ``tests/test_metric_layer.py``).

Two primitive shapes cover all callers:

* ``dist_rows``: one query (or a batch of queries, broadcast over
  leading dims) against *gathered* rows — the beam-search hot path,
  ``(..., 2W) x (..., K, 2W) -> (..., K)``.
* ``pairwise``: all-pairs within a candidate pool — the alpha-prune
  path, ``(..., C, 2W) -> (..., C, C)``.

Both return **int32 similarities** (Table-1 weighted sums for BQ2,
negated Hamming for BQ1); the backend applies its own non-negative
distance calibration on top.
"""

from __future__ import annotations

import os
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core import bq


class MetricOps(NamedTuple):
    """Distance primitives bound to one route at backend construction."""

    dist_rows: Callable  # (..., 2W) x (..., K, 2W) -> (..., K) int32 sim
    pairwise: Callable   # (..., C, 2W) -> (..., C, C) int32 sim
    route: str           # "pallas" | "ref" (introspection / tests)


class ListScanOps(NamedTuple):
    """IVF coarse-routing primitive bound to one route (DESIGN.md §13)."""

    scan: Callable       # (Q, 2W) x (L, 2W) -> (Q, L) int32 sim
    route: str           # "pallas" | "ref"


def resolve_route(route: str | None = None) -> str:
    """Pick the kernel route once; ``QUIVER_DISPATCH`` overrides auto.

    auto: Pallas on TPU (compiled Mosaic), jnp reference elsewhere —
    interpret-mode Pallas is a debugger, not a hot path.
    """
    route = route or os.environ.get("QUIVER_DISPATCH", "auto")
    if route == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "ref"
    if route not in ("pallas", "ref"):
        raise ValueError(
            f"unknown dispatch route {route!r}; expected pallas|ref|auto"
        )
    return route


# ---------------------------------------------------------------------------
# BQ2 — symmetric 2-bit Sign-Magnitude similarity
# ---------------------------------------------------------------------------


def _bq2_sim_ref(q_words, rows, mask, w):
    """Broadcasting jnp reference: (..., 2W) x (..., K, 2W) -> (..., K)."""
    qp = q_words[..., None, :w]
    qs = q_words[..., None, w:]
    return bq.symmetric_similarity_words(
        qp, qs, rows[..., :w], rows[..., w:], mask
    )


def _bq2_pairwise_ref(rows, mask, w):
    a = rows[..., :, None, :]
    b = rows[..., None, :, :]
    return bq.symmetric_similarity_words(
        a[..., :w], a[..., w:], b[..., :w], b[..., w:], mask
    )


def _pad_to(x, axis, mult):
    size = x.shape[axis]
    target = ((size + mult - 1) // mult) * mult
    if target == size:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, target - size)
    return jnp.pad(x, widths)


def _flatten_leading(fn):
    """Lift a (2-D q, 2-D rows) kernel call over arbitrary leading dims."""

    def wrapped(q_words, rows):
        lead = rows.shape[:-2]
        k, ww2 = rows.shape[-2], rows.shape[-1]
        q2 = jnp.broadcast_to(q_words, (*lead, ww2)).reshape(-1, ww2)
        r2 = rows.reshape(-1, k, ww2)
        out = jax.vmap(fn)(q2[:, None, :], r2)      # (B, 1, K)
        return out.reshape(*lead, k)

    return wrapped


def bq2_ops(dim: int, route: str | None = None) -> MetricOps:
    """Bind the symmetric 2-bit SM similarity primitives for ``dim``."""
    from repro.kernels.bq_distance import bq_distance_pallas

    route = resolve_route(route)
    mask = bq.valid_mask(dim)
    w = bq.n_words(dim)

    if route == "ref":
        return MetricOps(
            dist_rows=lambda q, rows: _bq2_sim_ref(q, rows, mask, w),
            pairwise=lambda rows: _bq2_pairwise_ref(rows, mask, w),
            route=route,
        )

    block_q, block_n = 8, 128

    def kernel_qn(q2, r2):
        """(Q, 2W) x (N, 2W) -> (Q, N) similarity via the Pallas kernel."""
        qp = _pad_to(q2, 0, block_q)
        rp = _pad_to(r2, 0, block_n)
        d = bq_distance_pallas(
            qp, rp, mask, dim=dim, block_q=block_q, block_n=block_n,
        )
        return -d[: q2.shape[0], : r2.shape[0]]     # kernel emits -sim

    def pairwise(rows):
        lead = rows.shape[:-2]
        c, ww2 = rows.shape[-2], rows.shape[-1]
        r2 = rows.reshape(-1, c, ww2)
        out = jax.vmap(lambda r: kernel_qn(r, r))(r2)
        return out.reshape(*lead, c, c)

    return MetricOps(
        dist_rows=_flatten_leading(kernel_qn),
        pairwise=pairwise,
        route=route,
    )


# ---------------------------------------------------------------------------
# IVF centroid list scan — batched top-p coarse routing (DESIGN.md §13)
# ---------------------------------------------------------------------------


def list_scan_ops(dim: int, route: str | None = None) -> ListScanOps:
    """Bind the batched centroid-scan primitive for ``dim``.

    The scan scores a query block against *every* list centroid
    signature at once — (Q, 2W) x (L, 2W) -> (Q, L) int32 Table-1
    similarity — so a ``lax.top_k`` over the result is the top-p list
    routing decision of the IVF layer.  Same ``QUIVER_DISPATCH``
    switch as the metric ops: compiled Mosaic kernel on TPU
    (centroids tiled in 128-wide blocks, ``repro.kernels.list_scan``),
    jnp reference elsewhere.
    """
    from repro.kernels.list_scan import list_scan_pallas

    route = resolve_route(route)
    mask = bq.valid_mask(dim)
    w = bq.n_words(dim)

    if route == "ref":
        return ListScanOps(
            scan=lambda q, cents: _bq2_sim_ref(q, cents, mask, w),
            route=route,
        )

    block_q, block_l = 8, 128

    def scan(q_words, cent_words):
        qp = _pad_to(q_words, 0, block_q)
        cp = _pad_to(cent_words, 0, block_l)
        sim = list_scan_pallas(qp, cp, mask, dim=dim, block_q=block_q,
                               block_l=block_l)
        return sim[: q_words.shape[0], : cent_words.shape[0]]

    return ListScanOps(scan=scan, route=route)


# ---------------------------------------------------------------------------
# BQ1 — 1-bit SimHash Hamming (sign plane only)
# ---------------------------------------------------------------------------


def _ham_rows_ref(q_words, rows):
    x = q_words[..., None, :] ^ rows
    return -jax.lax.population_count(x).astype(jnp.int32).sum(axis=-1)


def _ham_pairwise_ref(rows):
    x = rows[..., :, None, :] ^ rows[..., None, :, :]
    return -jax.lax.population_count(x).astype(jnp.int32).sum(axis=-1)


def bq1_ops(dim: int, route: str | None = None) -> MetricOps:
    """Bind the 1-bit Hamming primitives (as negated-distance similarity)."""
    from repro.kernels.hamming import hamming_distance_pallas

    route = resolve_route(route)

    if route == "ref":
        return MetricOps(
            dist_rows=_ham_rows_ref,
            pairwise=_ham_pairwise_ref,
            route=route,
        )

    block_q, block_n = 8, 128

    def kernel_qn(q2, r2):
        qp = _pad_to(q2, 0, block_q)
        rp = _pad_to(r2, 0, block_n)
        d = hamming_distance_pallas(
            qp, rp, block_q=block_q, block_n=block_n,
        )
        return -d[: q2.shape[0], : r2.shape[0]]

    def pairwise(rows):
        lead = rows.shape[:-2]
        c, ww = rows.shape[-2], rows.shape[-1]
        r2 = rows.reshape(-1, c, ww)
        out = jax.vmap(lambda r: kernel_qn(r, r))(r2)
        return out.reshape(*lead, c, c)

    return MetricOps(
        dist_rows=_flatten_leading(kernel_qn),
        pairwise=pairwise,
        route=route,
    )
