"""MutableQuIVerIndex — the paper's index with a live mutation lifecycle.

Layout (DESIGN.md §8): every array is preallocated at ``capacity`` and
lives on the accelerator for its whole life — the IVF-RaBitQ lesson
(PAPERS.md) that build and search should share device-resident arrays,
extended to a full mutable lifecycle:

    words      (capacity, 2W) uint32   packed 2-bit SM signatures (hot)
    adjacency  (capacity, R+slack) int32
    deg        (capacity,) int32       degree counters
    vectors    (capacity, D) float32   cold rerank tier (optional)
    live       (capacity,) bool        tombstone mask (host-owned)

``insert`` binarizes the new vectors and chunk-links them against the
*live* graph with exactly the shared Vamana primitives the batch
builder uses (``repro.core.linking``) — the paper's chunked concurrent
linking (§4.1) run against a non-frozen graph.  ``delete`` only flips
tombstones: dead nodes keep routing beam searches (FreshDiskANN
semantics) but never surface in results, courtesy of the ``node_valid``
path in ``repro.core.beam``.  ``consolidate`` repairs the topology —
each dead node's out-edges are spliced into its in-neighbours'
candidate pools and alpha-pruned in the index's own registered metric
space — then reclaims the dead slots for reuse.  ``freeze`` compacts
the live set into an immutable :class:`QuIVerIndex`.

Jit discipline: every device op here takes the mutable arrays as
*traced* arguments and constructs the registered metric backend inside
the trace (the ``repro.core.distributed`` pattern).  Cache keys are
(shapes, static params) only — shapes are pinned by ``capacity``, so
mutations never retrace.  Partial chunks are padded to a small set of
bucket sizes to bound the number of traces.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bq
from repro.core.index import QuIVerIndex, params_from_npz, params_to_npz
from repro.core.linking import medoid_scan
from repro.core.metric import MetricArrays, make_backend
from repro.core.vamana import BuildParams
from repro.filter import (
    DEFAULT_SELECTIVITY_FLOOR,
    LabelStore,
    build_label_entries,
)
from repro.plan import PlanCache, resolve_plan
from repro.probe import (
    CompatibilityReport,
    NavPolicy,
    ProbeAccumulator,
    probe_corpus,
    probe_signatures,
)
from repro.stream.consolidate import link_chunk, overflow_rows, repair_rows

_BUCKETS = (16, 64, 256)


def _normalize(x: jnp.ndarray) -> jnp.ndarray:
    return x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True), 1e-12)


def _pad_ids(ids: np.ndarray, size: int) -> jnp.ndarray:
    out = np.full((size,), -1, dtype=np.int32)
    out[: len(ids)] = ids
    return jnp.asarray(out)


def _bucket(n: int, chunk: int) -> int:
    """Smallest padding bucket >= n (bounds the jit trace count)."""
    for b in sorted(set(_BUCKETS) | {chunk}):
        if b >= n:
            return b
    return chunk


def _mk_backend(kind, dim, words, vectors):
    return make_backend(
        kind, MetricArrays(sigs=bq.Signature(words=words, dim=dim),
                           vectors=vectors)
    )


# ---------------------------------------------------------------------------
# device ops — arrays traced, backend constructed inside the trace
# ---------------------------------------------------------------------------


@functools.partial(
    jax.jit,
    static_argnames=("kind", "dim", "ef", "pool", "r", "alpha", "expand",
                     "r_total"),
)
def _link_op(words, vectors, adj, deg, live, chunk_ids, medoid, *,
             kind, dim, ef, pool, r, alpha, expand, r_total):
    backend = _mk_backend(kind, dim, words, vectors)
    return link_chunk(
        backend, adj, deg, live, chunk_ids, medoid,
        ef=ef, pool=pool, r=r, alpha=alpha, expand=expand,
        r_total=r_total,
    )


@functools.partial(
    jax.jit,
    static_argnames=("kind", "dim", "r", "alpha", "r_total", "pool"),
)
def _repair_op(words, vectors, adj, deg, live, row_ids, *,
               kind, dim, r, alpha, r_total, pool):
    backend = _mk_backend(kind, dim, words, vectors)
    return repair_rows(
        backend, adj, deg, live, row_ids,
        r=r, alpha=alpha, r_total=r_total, pool=pool,
    )


@functools.partial(
    jax.jit,
    static_argnames=("kind", "dim", "r", "alpha", "r_total"),
)
def _overflow_op(words, vectors, adj, deg, live, row_ids, *,
                 kind, dim, r, alpha, r_total):
    backend = _mk_backend(kind, dim, words, vectors)
    return overflow_rows(
        backend, adj, deg, live, row_ids,
        r=r, alpha=alpha, r_total=r_total,
    )


@functools.partial(jax.jit, static_argnames=("kind", "dim", "chunk"))
def _medoid_op(words, vectors, live, *, kind, dim, chunk):
    backend = _mk_backend(kind, dim, words, vectors)
    live_f = live.astype(jnp.float32)
    denom = jnp.maximum(live_f.sum(), 1.0)
    if vectors is not None:
        c = (vectors * live_f[:, None]).sum(0) / denom
    else:
        levels = bq.decode_levels(bq.Signature(words=words, dim=dim))
        c = (levels * live_f[:, None]).sum(0) / denom
    centroid = backend.encode_queries(c[None])[0]
    return medoid_scan(backend, centroid, chunk=chunk, node_valid=live)


# ---------------------------------------------------------------------------
# the mutable index
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class StreamStats:
    """Cumulative mutation accounting (since construction or load)."""

    inserts: int = 0
    deletes: int = 0
    consolidations: int = 0
    slots_reclaimed: int = 0
    rows_repaired: int = 0
    reverse_edges_added: int = 0


class MutableQuIVerIndex:
    """A QuIVer index that supports live insert/delete/consolidate.

    Construct with :meth:`empty` (streaming from scratch),
    :meth:`build` (batch build + headroom) or :meth:`from_index`
    (adopt an existing immutable index).
    """

    # coarse partitions go stale under churn: nav="ivf" needs freeze()
    ivf = None

    def __init__(
        self,
        *,
        capacity: int,
        dim: int,
        params: BuildParams,
        metric_kind: str = "bq2",
        keep_vectors: bool = True,
        rotation: jnp.ndarray | None = None,
        n_labels: int | None = None,
        policy: NavPolicy | None = None,
        report: CompatibilityReport | None = None,
    ):
        if capacity <= 0:
            raise ValueError(f"capacity must be positive, got {capacity}")
        if metric_kind == "auto":
            raise ValueError(
                "metric='auto' needs a corpus to probe; use build() "
                "(or probe_report() + select_policy after inserting)"
            )
        w2 = 2 * bq.n_words(dim)
        self.capacity = int(capacity)
        self.dim = int(dim)
        self.params = params
        self.metric_kind = metric_kind
        self.rotation = rotation
        self.words = jnp.zeros((capacity, w2), dtype=jnp.uint32)
        self.adjacency = jnp.full(
            (capacity, params.r_total), -1, dtype=jnp.int32
        )
        self.deg = jnp.zeros((capacity,), dtype=jnp.int32)
        self.vectors = (
            jnp.zeros((capacity, dim), dtype=jnp.float32)
            if keep_vectors else None
        )
        self.labels = (
            LabelStore(capacity, n_labels) if n_labels else None
        )
        self.live = np.zeros((capacity,), dtype=bool)
        self.allocated = np.zeros((capacity,), dtype=bool)
        self.size = 0                    # allocation high-water mark
        self.medoid = -1                 # -1 until the first insert
        self.generation = 0              # bumped on every mutation
        self.stats = StreamStats()
        self._free: list[int] = []       # reclaimed slots, reused first
        # applicability-boundary state (DESIGN.md §10): the nav policy /
        # probe report travel with the index; the accumulator keeps the
        # live set's exact bit-plane statistics current under churn
        self.policy = policy
        self.report = report
        self.probe_acc = ProbeAccumulator(dim)
        # optional probe-drift monitor (DESIGN.md §12): re-scores the
        # accumulator against the calibrated bands after every mutation
        self.drift_monitor = None
        # structural X-ray (DESIGN.md §15): the last GraphHealthReport
        # plus the optional band-crossing monitor that consolidate()
        # re-checks every cycle
        self.graph_health = None
        self.graph_monitor = None
        # compiled query plans (repro.plan, DESIGN.md §11): shapes are
        # pinned by capacity, so churn never retraces a plan
        self.plans = PlanCache(self)

    # -- constructors ------------------------------------------------------

    @classmethod
    def from_index(
        cls, index: QuIVerIndex, *, capacity: int | None = None
    ) -> "MutableQuIVerIndex":
        """Adopt a built :class:`QuIVerIndex` (default headroom: 2x)."""
        n = index.sigs.words.shape[0]
        capacity = capacity or 2 * n
        if capacity < n:
            raise ValueError(f"capacity {capacity} < index size {n}")
        out = cls(
            capacity=capacity,
            dim=index.sigs.dim,
            params=index.params,
            metric_kind=index.metric_kind,
            keep_vectors=index.vectors is not None,
            rotation=index.rotation,
            policy=index.policy,
            report=index.report,
        )
        out.probe_acc.add(np.asarray(index.sigs.words))
        out.words = out.words.at[:n].set(index.sigs.words)
        out.adjacency = out.adjacency.at[:n].set(index.adjacency)
        out.deg = out.deg.at[:n].set(
            (index.adjacency >= 0).sum(-1).astype(jnp.int32)
        )
        if out.vectors is not None:
            out.vectors = out.vectors.at[:n].set(index.vectors)
        out.live[:n] = True
        out.allocated[:n] = True
        out.size = n
        out.medoid = int(index.medoid)
        out.graph_health = index.graph_health
        if index.labels is not None:
            out.labels = index.labels.padded_to(capacity)
        return out

    @classmethod
    def build(
        cls,
        vectors: jnp.ndarray,
        params: BuildParams | None = None,
        *,
        capacity: int | None = None,
        metric: str = "bq2",
        **build_kw,
    ) -> "MutableQuIVerIndex":
        """Batch-build (two-stage Vamana) then adopt with headroom."""
        idx = QuIVerIndex.build(
            jnp.asarray(vectors), params, metric=metric, **build_kw
        )
        return cls.from_index(idx, capacity=capacity)

    @classmethod
    def empty(
        cls,
        dim: int,
        capacity: int,
        params: BuildParams | None = None,
        *,
        metric: str = "bq2",
        keep_vectors: bool = True,
        rotation: jnp.ndarray | None = None,
        n_labels: int | None = None,
    ) -> "MutableQuIVerIndex":
        return cls(
            capacity=capacity,
            dim=dim,
            params=params or BuildParams(),
            metric_kind=metric,
            keep_vectors=keep_vectors,
            rotation=rotation,
            n_labels=n_labels,
        )

    def enable_labels(self, n_labels: int) -> LabelStore:
        """Create (or return) the label store for filtered search."""
        if self.labels is None:
            self.labels = LabelStore(self.capacity, n_labels)
        elif self.labels.n_labels != n_labels:
            raise ValueError(
                f"labels already enabled with n_labels="
                f"{self.labels.n_labels}"
            )
        return self.labels

    def build_label_entries(self, *, min_count: int = 32) -> int:
        """Per-label entry points over the *live* member sets."""
        if self.labels is None:
            raise ValueError("no labels enabled")
        return build_label_entries(
            self.labels, self.backend(), vectors=self.vectors,
            node_valid=self._live_dev(), min_count=min_count,
        )

    # -- applicability probe (DESIGN.md §10) -------------------------------

    def probe_report(
        self,
        *,
        sample: int = 1024,
        queries: int = 64,
        k: int = 10,
        seed: int = 0,
    ) -> CompatibilityReport:
        """Probe the *live* set: sampled statistics plus the exact
        incremental bit-plane entropies from :class:`ProbeAccumulator`.

        The sampled stats (cosine spread, BQ agreement, margins) are
        recomputed from a live sample on demand; the entropy fields are
        taken from the accumulator, which covers every live row exactly
        and costs nothing here.  Vector-free indexes degrade to
        signature-only probes (agreement NaN, verdict capped at amber).
        """
        if self.n_live == 0:
            raise ValueError("cannot probe an empty index")
        live_idx = jnp.asarray(
            np.nonzero(self.live)[0].astype(np.int32)
        )
        if self.vectors is not None:
            # probe the served encoding: signatures were built from
            # rotated vectors, so the sampled stats must be too (the
            # accumulator's words are already rotated)
            v = self.vectors[live_idx]
            if self.rotation is not None:
                v = v @ self.rotation
            r = probe_corpus(
                v, sample=sample, queries=queries, k=k, seed=seed,
            )
        else:
            r = probe_signatures(
                self.words[live_idx], self.dim, sample=sample, k=k,
                seed=seed,
            )
        return dataclasses.replace(
            r,
            sign_entropy=self.probe_acc.sign_entropy,
            strong_entropy=self.probe_acc.strong_entropy,
        )

    # -- drift alarms (DESIGN.md §12) --------------------------------------

    def attach_drift_monitor(self, monitor=None, *, tenant="default",
                             registry=None, **monitor_kw):
        """Arm probe-drift alarms: after every insert/delete/consolidate
        batch the accumulator's exact bit-plane stats are re-scored
        against the calibrated green/amber/red thresholds
        (:class:`repro.obs.DriftMonitor`) and band crossings raise
        alarms through the metrics layer.

        Pass a prebuilt monitor, or kwargs to build one over this
        index's accumulator (thresholds default to the build-time probe
        report's, keeping the live banding consistent with the verdict
        that chose the nav policy).  Returns the armed monitor.
        """
        if monitor is None:
            from repro.obs import DriftMonitor
            if "thresholds" not in monitor_kw and self.report is not None:
                monitor_kw["thresholds"] = self.report.thresholds
            monitor = DriftMonitor(
                self.probe_acc, tenant=tenant, registry=registry,
                **monitor_kw,
            )
        self.drift_monitor = monitor
        monitor.check()                     # establish the current band
        return monitor

    # -- structural health (graph X-ray, DESIGN.md §15) --------------------

    def graph_report(
        self,
        *,
        sample: int = 256,
        agreement_k: int = 8,
        max_hops: int = 64,
        seed: int = 0,
        thresholds=None,
        registry=None,
    ):
        """Compute (and cache as ``graph_health``) the structural
        :class:`~repro.obs.graph.GraphHealthReport` over the live set:
        tombstoned rows route in the BFS but never count as unreachable,
        and tombstone density itself is one of the banded statistics."""
        if self.n_live == 0:
            raise ValueError("cannot X-ray an empty index")
        from repro.obs.graph import (
            DEFAULT_GRAPH_THRESHOLDS,
            graph_health_report,
        )
        self.graph_health = graph_health_report(
            self.adjacency,
            medoid=max(self.medoid, 0),
            words=self.words if self.vectors is not None else None,
            dim=self.dim,
            vectors=self.vectors,
            live=self.live,
            allocated=self.allocated,
            sample=sample,
            agreement_k=agreement_k,
            max_hops=max_hops,
            seed=seed,
            thresholds=thresholds or DEFAULT_GRAPH_THRESHOLDS,
            registry=registry,
        )
        return self.graph_health

    def attach_graph_monitor(self, monitor=None, *, tenant="default",
                             registry=None, **monitor_kw):
        """Arm graph-health banding: every :meth:`consolidate` cycle
        re-X-rays the live graph and band *worsenings* raise
        :class:`~repro.obs.graph.GraphHealthAlarm`s (the trigger class
        :class:`~repro.obs.remediate.RemediationPolicy.attach_graph`
        subscribes to).  The first check runs now, so arming an already
        degraded graph alarms immediately.  Returns the monitor."""
        if monitor is None:
            from repro.obs.graph import GraphHealthMonitor
            monitor = GraphHealthMonitor(
                tenant=tenant, registry=registry, **monitor_kw,
            )
        self.graph_monitor = monitor
        if self.n_live:
            monitor.check(self.graph_report(registry=registry))
        return monitor

    def replan(
        self,
        *,
        nav: str,
        ef_scale: int | None = None,
        adaptive: bool | None = None,
        source: str = "replan",
    ) -> NavPolicy:
        """Switch the live index's default nav at serve time (the
        remediation path, DESIGN.md §14).  Same contract as
        ``QuIVerIndex.replan`` except ``nav="ivf"`` is rejected for the
        same reason ``search(nav="ivf")`` is: coarse partitions go
        stale under churn — freeze() first.

        A mutable index resolves its default nav from ``metric_kind``
        (the policy carries only the ef/escalation schedule), so both
        are updated together, and the old nav's compiled plans are
        invalidated.
        """
        if nav == "ivf":
            raise ValueError(
                "replan(nav='ivf') is not available on a mutable index "
                "(partitions go stale under churn); freeze() first"
            )
        if nav == "float32" and self.vectors is None:
            raise ValueError(
                "replan(nav='float32') needs the cold vector tier; "
                "this index is vector-free"
            )
        if self.policy is not None:
            kw = {"nav": nav, "source": source}
            if ef_scale is not None:
                kw["ef_scale"] = int(ef_scale)
            if adaptive is not None:
                kw["adaptive"] = bool(adaptive)
            self.policy = dataclasses.replace(self.policy, **kw)
        else:
            self.policy = NavPolicy(
                nav=nav, source=source,
                **({} if ef_scale is None else {"ef_scale": int(ef_scale)}),
                **({} if adaptive is None else {"adaptive": bool(adaptive)}),
            )
        old_nav, self.metric_kind = self.metric_kind, nav
        if nav != old_nav:
            self.plans.invalidate(nav=old_nav)
        return self.policy

    def _note_mutation(self, kind: str, count: int):
        """Mutation telemetry + drift re-score (one owner: insert,
        delete and consolidate all funnel through here)."""
        from repro.obs.metrics import get_default_registry
        reg = get_default_registry()
        reg.counter(
            "quiver_stream_mutations_total",
            "streaming mutations by kind", labels=("kind",),
        ).inc(count, kind=kind)
        reg.gauge(
            "quiver_stream_live_rows", "live rows in mutable indexes",
        ).set(self.n_live)
        if self.drift_monitor is not None:
            return self.drift_monitor.check()
        return None

    # -- introspection -----------------------------------------------------

    @property
    def sigs(self) -> bq.Signature:
        return bq.Signature(words=self.words, dim=self.dim)

    @property
    def default_nav(self) -> str:
        """A live index navigates in its build metric (the policy
        carries only the ef/escalation schedule; see :meth:`replan`)."""
        return self.metric_kind

    def backend(self, kind: str | None = None):
        """The metric backend for ``kind`` (default: the index's own)
        over the *current* words and vectors: built afresh, since every
        insert replaces them; a backend is a program argument, so one
        of the same shapes never retraces."""
        return _mk_backend(kind or self.metric_kind, self.dim, self.words,
                           self.vectors)

    @property
    def n_live(self) -> int:
        return int(self.live.sum())

    @property
    def n_dead(self) -> int:
        return int((self.allocated & ~self.live).sum())

    @property
    def free_slots(self) -> int:
        return self.capacity - self.size + len(self._free)

    def __len__(self) -> int:
        return self.n_live

    def memory_breakdown(self) -> dict[str, int]:
        sig_bytes = self.words.size * 4
        adj_bytes = self.adjacency.size * 4 + self.deg.size * 4
        mask_bytes = 2 * self.capacity  # live + allocated, host-side
        label_bytes = (
            self.labels.memory_bytes() if self.labels is not None else 0
        )
        cold = self.vectors.size * 4 if self.vectors is not None else 0
        shadow = getattr(self, "shadow", None)
        shadow_bytes = shadow.memory_bytes() if shadow is not None else 0
        hot = sig_bytes + adj_bytes + mask_bytes + label_bytes
        out = {
            "hot_signature_bytes": int(sig_bytes),
            "hot_adjacency_bytes": int(adj_bytes),
            "hot_mask_bytes": int(mask_bytes),
            "hot_label_bytes": int(label_bytes),
            "hot_total_bytes": int(hot),
            "cold_vector_bytes": int(cold),
            "host_shadow_bytes": int(shadow_bytes),
            "total_bytes": int(hot + cold + shadow_bytes),
        }
        if self.policy is not None:
            out["nav_policy"] = self.policy.describe()
            out["probe_verdict"] = (
                self.report.verdict if self.report is not None else "n/a"
            )
        if self.graph_health is not None:
            out["graph_verdict"] = self.graph_health.verdict
            out["graph_health_score"] = round(
                self.graph_health.health_score, 4
            )
        return out

    def _live_dev(self) -> jnp.ndarray:
        return jnp.asarray(self.live)

    # -- mutation ----------------------------------------------------------

    def _allocate(self, count: int) -> np.ndarray:
        take = min(count, len(self._free))
        ids = self._free[:take]
        fresh = count - take
        if self.size + fresh > self.capacity:
            raise ValueError(
                f"insert of {count} exceeds capacity: "
                f"{self.free_slots} slots free of {self.capacity} "
                f"(consolidate() reclaims tombstoned slots)"
            )
        del self._free[:take]
        ids = ids + list(range(self.size, self.size + fresh))
        self.size += fresh
        return np.asarray(ids, dtype=np.int32)

    def insert(self, vectors: jnp.ndarray, labels=None) -> np.ndarray:
        """Insert a batch of float32 vectors; returns their slot ids.

        Vectors are L2-normalized and binarized, then chunk-linked
        against the live graph: beam search from the medoid, alpha-prune
        in the index's metric space, forward + reverse edge install —
        the shared primitives of ``repro.core.linking``.

        ``labels`` (optional) assigns filter labels on the way in: one
        int or iterable of ints per vector (or a single int for the
        whole batch), written into the :class:`LabelStore` before the
        new nodes become searchable.  Requires ``enable_labels``.
        """
        v = _normalize(jnp.asarray(vectors, dtype=jnp.float32))
        if v.ndim == 1:
            v = v[None]
        if v.shape[-1] != self.dim:
            raise ValueError(f"dim mismatch: {v.shape[-1]} != {self.dim}")
        if labels is not None and self.labels is None:
            raise ValueError(
                "insert(labels=...) needs enable_labels(n_labels) first"
            )
        if v.shape[0] == 0:
            return np.empty((0,), dtype=np.int32)
        ids = self._allocate(v.shape[0])
        pre_live = self.n_live
        if labels is not None:
            self.labels.set(ids, labels)
        elif self.labels is not None:
            self.labels.clear(ids)     # reused slots must start clean

        enc = v @ self.rotation if self.rotation is not None else v
        sig_words = bq.encode(enc).words
        self.probe_acc.add(np.asarray(sig_words))
        dev_ids = jnp.asarray(ids)
        self.words = self.words.at[dev_ids].set(sig_words)
        if self.vectors is not None:
            self.vectors = self.vectors.at[dev_ids].set(v)
        self.live[ids] = True
        self.allocated[ids] = True
        if self.medoid < 0 or pre_live == 0:
            # empty (or fully-tombstoned) graph: a dead medoid inside an
            # all-dead component could strand the new nodes — re-anchor
            self.medoid = int(ids[0])

        p = self.params
        live_before = 0
        pos = 0
        while pos < len(ids):
            # adapt the chunk to the current graph size: a chunk links
            # against a frozen snapshot, so never link more nodes at
            # once than the graph already holds (bootstrap quality)
            live_before = self.n_live - (len(ids) - pos)
            take = min(p.chunk, max(16, live_before), len(ids) - pos)
            block = ids[pos:pos + take]
            pos += take
            padded = _pad_ids(block, _bucket(take, p.chunk))
            self.adjacency, self.deg, added = _link_op(
                self.words, self.vectors, self.adjacency, self.deg,
                self._live_dev(), padded, jnp.int32(self.medoid),
                kind=self.metric_kind, dim=self.dim,
                ef=p.ef_construction, pool=p.prune_pool, r=p.r,
                alpha=p.alpha, expand=p.beam_expand,
                r_total=p.r_total,
            )
            self.stats.reverse_edges_added += int(added)
        self._consolidate_overflow()
        self.stats.inserts += len(ids)
        self.generation += 1
        self._note_mutation("insert", len(ids))
        return ids

    def delete(self, ids) -> int:
        """Tombstone ``ids``; returns how many were live.

        Dead nodes keep routing beam searches until :meth:`consolidate`
        splices them out and reclaims their slots.  Their label bits
        are cleared *now*: popcounts drive selectivity routing, and
        dead-inflated counts would keep a mostly-deleted label on the
        graph route long after brute force became the right answer.
        """
        ids = np.atleast_1d(np.asarray(ids, dtype=np.int64))
        if len(ids) and (ids.min() < 0 or ids.max() >= self.capacity):
            raise ValueError(f"ids out of range [0, {self.capacity})")
        was_live = self.live[ids].sum()
        gone = np.unique(ids[self.live[ids]])
        if gone.size:
            # un-count exactly the rows leaving the live set (duplicate
            # and already-dead ids must not decrement twice)
            self.probe_acc.remove(np.asarray(self.words[jnp.asarray(gone)]))
        self.live[ids] = False
        if self.labels is not None:
            self.labels.clear(ids)
        self.stats.deletes += int(was_live)
        self.generation += 1
        self._note_mutation("delete", int(was_live))
        return int(was_live)

    def _batched_rows(self, rows: np.ndarray, op) -> None:
        """Run a row-repair device op over bucketed batches of rows."""
        chunk = self.params.chunk
        for s in range(0, len(rows), chunk):
            block = rows[s:s + chunk]
            padded = _pad_ids(block, _bucket(len(block), chunk))
            self.adjacency, self.deg = op(padded)

    def _consolidate_overflow(self) -> None:
        """Re-prune rows whose degree overflowed r (build-time analogue)."""
        deg_host = np.asarray(self.deg)
        overflow = np.nonzero(deg_host > self.params.r)[0].astype(np.int32)
        if overflow.size == 0:
            return
        p = self.params
        self._batched_rows(
            overflow,
            lambda row_ids: _overflow_op(
                self.words, self.vectors, self.adjacency, self.deg,
                self._live_dev(), row_ids,
                kind=self.metric_kind, dim=self.dim, r=p.r,
                alpha=p.alpha, r_total=p.r_total,
            ),
        )

    def consolidate(self) -> dict[str, int]:
        """FreshDiskANN-style repair + slot reclamation.

        For every live row that points at a tombstone, splice the dead
        neighbours' own live out-edges into the row's candidate pool
        and alpha-prune it in the registered metric space.  Then clear
        the dead rows, reclaim their slots for reuse, and re-elect the
        medoid if it died.
        """
        dead_mask = self.allocated & ~self.live
        dead = np.nonzero(dead_mask)[0]
        report = {"dead": int(dead.size), "repaired_rows": 0,
                  "reclaimed": int(dead.size)}
        if dead.size == 0:
            return report

        # compute the points-at-dead mask on device: only a (capacity,)
        # bool comes back, never the full adjacency matrix
        adj = self.adjacency
        dead_mask_dev = jnp.asarray(dead_mask)
        points_at_dead = np.asarray(
            ((adj >= 0) & dead_mask_dev[jnp.clip(adj, 0, None)]).any(axis=1)
        )
        affected = np.nonzero(self.live & points_at_dead)[0].astype(
            np.int32
        )
        report["repaired_rows"] = int(affected.size)

        p = self.params
        if affected.size:
            self._batched_rows(
                affected,
                lambda row_ids: _repair_op(
                    self.words, self.vectors, self.adjacency, self.deg,
                    self._live_dev(), row_ids,
                    kind=self.metric_kind, dim=self.dim, r=p.r,
                    alpha=p.alpha, r_total=p.r_total, pool=p.prune_pool,
                ),
            )

        # clear + reclaim the dead slots (labels too: a reclaimed slot
        # must not inherit its previous occupant's filter labels)
        dead_dev = jnp.asarray(dead.astype(np.int32))
        self.adjacency = self.adjacency.at[dead_dev].set(-1)
        self.deg = self.deg.at[dead_dev].set(0)
        if self.labels is not None:
            self.labels.clear(dead)
        self.allocated[dead] = False
        self._free.extend(int(i) for i in dead)

        # re-elect the medoid if it died (or was never set)
        if self.n_live and (self.medoid < 0 or not self.live[self.medoid]):
            self.medoid = int(_medoid_op(
                self.words, self.vectors, self._live_dev(),
                kind=self.metric_kind, dim=self.dim, chunk=4096,
            ))
        elif self.n_live == 0:
            self.medoid = -1

        self.stats.consolidations += 1
        self.stats.rows_repaired += report["repaired_rows"]
        self.stats.slots_reclaimed += report["reclaimed"]
        self.generation += 1
        self._note_mutation("consolidate", 1)
        # per-cycle health delta: re-X-ray the repaired graph so the
        # monitor's delta gauge tracks what each consolidation bought
        # (or failed to buy) and band worsenings reach the remediation
        # ladder before shadow recall moves
        if self.graph_monitor is not None and self.n_live:
            prev = self.graph_monitor.last_score
            rep = self.graph_report()
            self.graph_monitor.check(rep)
            report["health_score"] = rep.health_score
            report["health_band"] = rep.verdict
            if prev is not None:
                report["health_delta"] = rep.health_score - prev
        return report

    # -- search ------------------------------------------------------------

    def search(
        self,
        queries: jnp.ndarray,
        k: int = 10,
        *,
        ef: int = 64,
        rerank: bool = True,
        nav: str | None = None,
        expand: int = 1,
        query_batch: int = 256,
        filter=None,
        selectivity_floor: float = DEFAULT_SELECTIVITY_FLOOR,
        adaptive: bool | None = None,
    ) -> tuple[np.ndarray, np.ndarray]:
        """Tombstone-aware search: same contract as ``QuIVerIndex.search``
        (including the score scale: cosine with ``rerank=True``, negated
        navigation distances with ``rerank=False``, and the
        :class:`NavPolicy` schedule — ef scaling plus per-query
        adaptive escalation — when the index carries one) but dead or
        never-inserted slots cannot appear in the results.

        It lowers to the same :class:`~repro.plan.QueryPlan` path as the
        immutable index: :func:`~repro.plan.resolve_plan` folds the live
        set into the plan's result mask (``live ∧ predicate``, or
        ``live`` alone) while navigation traverses everything, and
        :attr:`plans` runs it — so results are exactly live ∧ matching.
        """
        if self.n_live == 0:
            nq = 1 if np.ndim(queries) == 1 else np.shape(queries)[0]
            return (np.full((nq, k), -1, np.int32),
                    np.full((nq, k), -np.inf, np.float32))
        if nav == "ivf":
            raise ValueError(
                "nav='ivf' serves from a frozen coarse partition, which "
                "would go stale under churn — freeze() this index first "
                "(with BuildParams(ivf_candidates=True) the frozen "
                "snapshot carries a fresh partition)"
            )
        plan, ctx = resolve_plan(
            self, k=k, ef=ef, rerank=rerank, nav=nav, expand=expand,
            query_batch=query_batch, filter=filter,
            selectivity_floor=selectivity_floor, adaptive=adaptive,
        )
        return self.plans.run(plan, ctx, queries)

    # -- snapshots ---------------------------------------------------------

    def freeze(self) -> QuIVerIndex:
        """Compact the live set into an immutable :class:`QuIVerIndex`.

        Live slots keep their relative order; edges to tombstones are
        dropped (they are already absent after :meth:`consolidate`).
        With zero churn this is exactly the arrays the index was built
        with, so searches are bit-identical to the source index.

        When the index was configured with
        ``BuildParams(ivf_candidates=True)`` the snapshot also carries
        a freshly built coarse partition over the compacted live set,
        so ``nav="ivf"`` works on the frozen index (it is rejected on
        the mutable one — the partition would go stale under churn).
        """
        if self.n_live == 0:
            raise ValueError("cannot freeze an empty index")
        live_idx = np.nonzero(self.live)[0]
        remap = np.full((self.capacity + 1,), -1, dtype=np.int32)
        remap[live_idx] = np.arange(live_idx.size, dtype=np.int32)

        sel = jnp.asarray(live_idx.astype(np.int32))
        words = self.words[sel]
        vectors = self.vectors[sel] if self.vectors is not None else None
        adj_host = np.asarray(self.adjacency)[live_idx]
        adj_new = remap[np.clip(adj_host, 0, None)]
        adj_new[adj_host < 0] = -1

        medoid = self.medoid
        if medoid < 0 or not self.live[medoid]:
            medoid = int(_medoid_op(
                self.words, self.vectors, self._live_dev(),
                kind=self.metric_kind, dim=self.dim, chunk=4096,
            ))
        sigs = bq.Signature(words=words, dim=self.dim)
        ivf = None
        if getattr(self.params, "ivf_candidates", False):
            from repro.ivf import build_partition
            ivf = build_partition(sigs, seed=self.params.seed)
        return QuIVerIndex(
            sigs=sigs,
            adjacency=jnp.asarray(adj_new),
            medoid=int(remap[medoid]),
            params=self.params,
            vectors=vectors,
            rotation=self.rotation,
            metric_kind=self.metric_kind,
            labels=(
                self.labels.compact(live_idx)
                if self.labels is not None else None
            ),
            policy=self.policy,
            report=self.report,
            ivf=ivf,
            graph_health=self.graph_health,
        )

    # -- persistence -------------------------------------------------------

    def save(self, path: str) -> None:
        label_fields = (
            self.labels.to_npz_fields() if self.labels is not None else {}
        )
        probe_fields = {}
        if self.policy is not None:
            probe_fields.update(self.policy.to_npz_fields())
        if self.report is not None:
            probe_fields.update(self.report.to_npz_fields())
        if self.graph_health is not None:
            probe_fields.update(self.graph_health.to_npz_fields())
        np.savez_compressed(
            path,
            stream_format=np.int64(1),
            **label_fields,
            **probe_fields,
            words=np.asarray(self.words),
            dim=np.int64(self.dim),
            adjacency=np.asarray(self.adjacency),
            deg=np.asarray(self.deg),
            vectors=(
                np.asarray(self.vectors)
                if self.vectors is not None else np.zeros((0,))
            ),
            rotation=(
                np.asarray(self.rotation)
                if self.rotation is not None else np.zeros((0,))
            ),
            live=self.live,
            allocated=self.allocated,
            free=np.asarray(self._free, dtype=np.int64),
            size=np.int64(self.size),
            medoid=np.int64(self.medoid),
            generation=np.int64(self.generation),
            metric_kind=np.array(self.metric_kind),
            **params_to_npz(self.params),
        )

    @classmethod
    def load(cls, path: str) -> "MutableQuIVerIndex":
        z = np.load(path)
        if "stream_format" not in z:
            # an immutable QuIVerIndex archive: adopt it
            return cls.from_index(QuIVerIndex.load(path))
        params = params_from_npz(z)
        dim = int(z["dim"])
        vectors = z["vectors"]
        rotation = z["rotation"]
        out = cls(
            capacity=z["words"].shape[0],
            dim=dim,
            params=params,
            metric_kind=str(z["metric_kind"]),
            keep_vectors=bool(vectors.size),
            rotation=jnp.asarray(rotation) if rotation.size else None,
        )
        out.words = jnp.asarray(z["words"])
        out.adjacency = jnp.asarray(z["adjacency"])
        out.deg = jnp.asarray(z["deg"])
        if vectors.size:
            out.vectors = jnp.asarray(vectors)
        out.live = z["live"].astype(bool)
        out.allocated = z["allocated"].astype(bool)
        out.labels = LabelStore.from_npz(z)
        out._free = [int(i) for i in z["free"]]
        out.size = int(z["size"])
        out.medoid = int(z["medoid"])
        out.generation = int(z["generation"])
        out.policy = NavPolicy.from_npz(z)
        out.report = CompatibilityReport.from_npz(z)
        from repro.obs.graph import GraphHealthReport
        out.graph_health = GraphHealthReport.from_npz(z)
        # the accumulator is derived state: recompute from the live rows
        # (exactly what the incremental path would have maintained)
        out.probe_acc = ProbeAccumulator.from_words(
            np.asarray(out.words)[out.live], dim
        )
        return out
