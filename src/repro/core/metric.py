"""The metric layer: registry-driven metric spaces for the whole index.

QuIVer's whole thesis is *which metric space the graph lives in*; this
module makes that space a first-class, registered object so Vamana
construction, beam search, sharded serving and the benchmarks all pull
the same distance from the same place.  Backends are registered by name
and constructed from a shared :class:`MetricArrays` bundle:

    backend = make_backend("bq2", MetricArrays(sigs=sigs))

* ``bq2``     — the paper: symmetric 2-bit Sign-Magnitude distance,
  calibrated non-negative as ``d = 4D - similarity`` (Table 1 weights are
  signed; the multiplicative alpha-criterion of Algorithm 1 needs d >= 0,
  and this shift is the unique order-preserving calibration with
  ``d(x, x) = 0`` when every dim of x is strong-matched).
* ``bq1``     — 1-bit SimHash Hamming (the §2.1/§5 ablation).
* ``adc``     — asymmetric float-query-vs-decoded-levels navigation
  (§3.3 "Why not ADC for navigation?"), now with a decoded-levels
  ``pairwise`` so ADC-built graphs work too.
* ``float32`` — exact cosine distance (the hnswlib/USearch-like
  full-precision reference build, paper Table 6).

Every BQ distance evaluation routes through ``repro.kernels.dispatch``,
bound once per backend at construction: compiled Pallas kernels on TPU,
the ``bq.py`` jnp reference elsewhere.  No caller outside this module
computes a BQ distance by hand (grep-enforced in the tests).

A backend exposes a query representation per node, a gather-based
distance function for beam search (``dist_fn`` single query,
``dist_many`` batched queries), and batched pairwise distances for
alpha-pruning.  See DESIGN.md §2 for the registry contract.
"""

from __future__ import annotations

import dataclasses
from typing import Protocol

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bq
from repro.kernels import dispatch


@dataclasses.dataclass(frozen=True)
class MetricArrays:
    """Shared array bundle every backend is constructed from.

    ``sigs`` is the hot path (packed 2-bit SM signatures); ``vectors``
    the cold path (float32, L2-normalized) — only ``float32`` needs it.
    """

    sigs: bq.Signature | None = None
    vectors: jnp.ndarray | None = None


class MetricSpace(Protocol):
    """What construction, search and serving require of a metric space."""

    kind: str
    n: int
    neutral_dist: float   # zero-similarity distance (beam_margin scale)

    def query_repr(self, ids: jnp.ndarray) -> jnp.ndarray:
        """Representation handed to beam search for these node ids."""

    def encode_queries(self, x: jnp.ndarray) -> jnp.ndarray:
        """External float32 queries (Q, D) -> beam-search representation."""

    def dist_fn(self, query, ids, valid) -> jnp.ndarray:
        """(K,) distances from one ``query`` to nodes ``ids``; >= 0."""

    def dist_many(self, queries, ids, valid) -> jnp.ndarray:
        """(..., K) distances for a leading batch of queries; >= 0."""

    def pairwise(self, ids: jnp.ndarray) -> jnp.ndarray:
        """(..., C) ids -> (..., C, C) pairwise distances; >= 0."""


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

_REGISTRY: dict[str, type] = {}


def register(name: str):
    """Class decorator: register a backend under ``name``."""

    def deco(cls):
        cls.kind = name
        _REGISTRY[name] = cls
        return cls

    return deco


def registered_kinds() -> list[str]:
    return sorted(_REGISTRY)


def resolve(kind: str) -> type:
    try:
        return _REGISTRY[kind]
    except KeyError:
        raise ValueError(
            f"unknown metric kind {kind!r}; registered: {registered_kinds()}"
        ) from None


def make_backend(
    kind: str, arrays: MetricArrays, *, route: str | None = None
) -> MetricSpace:
    """Construct the registered backend ``kind`` from ``arrays``.

    ``route`` forces the kernel dispatch route (``pallas``/``ref``);
    default auto-selects by platform (see ``repro.kernels.dispatch``).
    """
    return resolve(kind).from_arrays(arrays, route=route)


def encode_queries_for(kind: str, x: jnp.ndarray) -> jnp.ndarray:
    """Instance-free query encoding (sharded serving encodes on the host
    side, before any shard-local backend exists)."""
    return resolve(kind).encode(x)


def _unit(x: jnp.ndarray) -> jnp.ndarray:
    return x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True), 1e-12)


# ---------------------------------------------------------------------------
# backends
# ---------------------------------------------------------------------------
#
# Every backend is a pytree whose leaves are its arrays (signature words
# or float vectors) and whose static part is the dimension and kernel
# route.  A jitted program that takes a backend as an *argument* traces
# those arrays like any other input; one that closes over a backend
# bakes them into the executable as constants — 192 MB of signatures
# per compiled program at 1M x 768.  ``tree_unflatten`` runs on tracers
# and on placeholder leaves, so construction must not compute on them.


class _SignatureLeaves:
    """Pytree protocol of the signature-backed backends: the packed
    words are the one leaf; dim and the kernel route are static."""

    @property
    def n(self) -> int:
        return self.sigs.words.shape[0]

    def tree_flatten(self):
        return (self.sigs.words,), (self.dim, getattr(self, "route", None))

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        dim, route = aux
        return cls(bq.Signature(words=leaves[0], dim=dim), route=route)


@jax.tree_util.register_pytree_node_class
@register("bq2")
class BQ2Backend(_SignatureLeaves):
    """Symmetric 2-bit Sign-Magnitude metric space (the paper's hot path)."""

    def __init__(self, sigs: bq.Signature, *, route: str | None = None):
        self.sigs = sigs
        self.dim = sigs.dim
        self._ops = dispatch.bq2_ops(sigs.dim, route=route)
        self._offset = jnp.float32(4 * sigs.dim)
        # an orthogonal pair scores similarity ~0 -> distance ~offset
        self.neutral_dist = float(4 * sigs.dim)

    @classmethod
    def from_arrays(cls, arrays: MetricArrays, *, route: str | None = None):
        assert arrays.sigs is not None, "bq2 needs packed signatures"
        return cls(arrays.sigs, route=route)

    @classmethod
    def encode(cls, x):
        return bq.encode(x).words

    @property
    def route(self) -> str:
        return self._ops.route

    def query_repr(self, ids):
        return self.sigs.words[ids]

    def encode_queries(self, x):
        return self.encode(x)

    def dist_fn(self, query, ids, valid):
        rows = self.sigs.words[ids]
        sim = self._ops.dist_rows(query, rows)
        return self._offset - sim.astype(jnp.float32)

    dist_many = dist_fn   # dist_rows broadcasts over leading query dims

    def pairwise(self, ids):
        rows = self.sigs.words[ids]
        sim = self._ops.pairwise(rows)
        return self._offset - sim.astype(jnp.float32)


@jax.tree_util.register_pytree_node_class
@register("bq1")
class BQ1Backend(_SignatureLeaves):
    """1-bit SimHash Hamming metric space (ablation baseline)."""

    def __init__(self, sigs: bq.Signature, *, route: str | None = None):
        self.sigs = sigs
        self.dim = sigs.dim
        self._ops = dispatch.bq1_ops(sigs.dim, route=route)
        # expected Hamming distance of independent sign planes
        self.neutral_dist = float(sigs.dim) / 2.0

    @classmethod
    def from_arrays(cls, arrays: MetricArrays, *, route: str | None = None):
        assert arrays.sigs is not None, "bq1 needs packed signatures"
        return cls(arrays.sigs, route=route)

    @classmethod
    def encode(cls, x):
        sig = bq.encode(x)
        return sig.words[..., : sig.w]

    @property
    def route(self) -> str:
        return self._ops.route

    def query_repr(self, ids):
        return self.sigs.pos[ids]

    def encode_queries(self, x):
        return self.encode(x)

    def dist_fn(self, query, ids, valid):
        rows = self.sigs.pos[ids]
        sim = self._ops.dist_rows(query, rows)   # negated Hamming
        return -sim.astype(jnp.float32)

    dist_many = dist_fn

    def pairwise(self, ids):
        rows = self.sigs.pos[ids]
        return -self._ops.pairwise(rows).astype(jnp.float32)


@jax.tree_util.register_pytree_node_class
@register("float32")
class Float32Backend:
    """Exact cosine metric space (full-precision reference build)."""

    neutral_dist = 1.0          # cos 0 -> distance 1

    def __init__(self, vectors: jnp.ndarray):
        """``vectors`` must already be L2-normalized (``from_arrays``
        normalizes)."""
        self.vectors = vectors

    @property
    def n(self) -> int:
        return self.vectors.shape[0]

    @property
    def dim(self) -> int:
        return self.vectors.shape[-1]

    def tree_flatten(self):
        return (self.vectors,), None

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(leaves[0])

    @classmethod
    def from_arrays(cls, arrays: MetricArrays, *, route: str | None = None):
        assert arrays.vectors is not None, "float32 needs cold vectors"
        return cls(_unit(arrays.vectors))

    @classmethod
    def encode(cls, x):
        return _unit(x)

    def query_repr(self, ids):
        return self.vectors[ids]

    def encode_queries(self, x):
        return self.encode(x)

    def dist_fn(self, query, ids, valid):
        rows = self.vectors[ids]
        return 1.0 - rows @ query

    def dist_many(self, queries, ids, valid):
        rows = self.vectors[ids]
        return 1.0 - jnp.einsum("...d,...kd->...k", queries, rows)

    def pairwise(self, ids):
        rows = self.vectors[ids]
        sims = jnp.einsum("...cd,...ed->...ce", rows, rows)
        return 1.0 - sims


@jax.tree_util.register_pytree_node_class
@register("adc")
class ADCBackend(_SignatureLeaves):
    """Asymmetric navigation: float32 query vs decoded 2-bit signatures.

    Search-time ablation (§3.3 "Why not ADC for navigation?").  A node's
    own query representation is its unit-normalized decoded levels, and
    ``pairwise`` is decoded-levels inner products with the same
    calibration — so ADC-built graphs (construction in ADC space) work,
    not just ADC traversal of a symmetric-built graph.
    """

    def __init__(self, sigs: bq.Signature, *, route: str | None = None):
        self.sigs = sigs
        self.dim = sigs.dim
        # non-negative calibration: |<q, levels>| <= ||levels|| <= 2*sqrt(D)
        # for unit q; the offset keeps the alpha-criterion well-defined.
        self._offset = np.float32(2.0) * np.sqrt(np.float32(sigs.dim))
        self.neutral_dist = float(self._offset)   # zero inner product

    @classmethod
    def from_arrays(cls, arrays: MetricArrays, *, route: str | None = None):
        assert arrays.sigs is not None, "adc needs packed signatures"
        return cls(arrays.sigs, route=route)

    @classmethod
    def encode(cls, x):
        return _unit(x)

    def _levels(self, ids):
        rows = bq.Signature(words=self.sigs.words[ids], dim=self.dim)
        return bq.decode_levels(rows)                # (..., K, D)

    def query_repr(self, ids):
        return _unit(self._levels(ids))

    def encode_queries(self, x):
        return self.encode(x)

    def dist_fn(self, query, ids, valid):
        return self._offset - self._levels(ids) @ query

    def dist_many(self, queries, ids, valid):
        levels = self._levels(ids)
        return self._offset - jnp.einsum("...d,...kd->...k", queries, levels)

    def pairwise(self, ids):
        levels = self._levels(ids)                   # (..., C, D)
        q = _unit(levels)
        sims = jnp.einsum("...cd,...ed->...ce", q, levels)
        return self._offset - sims


# legacy alias kept for external callers of the old protocol name
MetricBackend = MetricSpace
