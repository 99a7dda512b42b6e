"""QuIVerIndex — the paper's system as a composable public API.

Pipeline (paper Fig. 1):

    float32 vectors ──binarize──▶ 2-bit SM signatures      (hot)
                                   │
                         BQ-native Vamana build             (hot)
                                   │
    query ──encode──▶ symmetric BQ beam search              (hot)
                                   │ top-ef candidates
                      float32 cosine rerank                 (cold)

Hot path = signatures + adjacency; float32 vectors are only touched at
rerank (and may live in host memory / another tier on a real fleet).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Literal

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bq

# bucket-ladder re-exports: pre-plan callers import these from here
# (the beam module stays the one owner)
from repro.core.beam import (  # noqa: F401
    batch_bucket,
    batched_beam_search,
    beam_margin,
    pad_rows,
)
from repro.core.metric import MetricArrays, MetricSpace, make_backend
from repro.core.vamana import BuildParams, BuildStats, build_graph
from repro.filter import (
    DEFAULT_SELECTIVITY_FLOOR,
    LabelStore,
    build_label_entries,
)
from repro.ivf import IVFPartition, build_partition
from repro.plan.cache import PlanCache
from repro.plan.planner import resolve_plan
from repro.probe import (
    CompatibilityReport,
    NavPolicy,
    probe_corpus,
    select_policy,
)

# "ivf" is a navigation *family*, not a build metric: the graph (and
# the partition) live in bq2 space; serving scans top-p coarse lists
# instead of traversing (DESIGN.md §13)
NavKind = Literal["bq2", "bq1", "adc", "float32", "ivf"]

# BuildParams persistence: one named npz field per dataclass field (the
# old format was a positional int64 array — brittle, and alpha had to be
# smuggled as milli-units).  ``params_from_npz`` still reads it.
_PARAM_PREFIX = "param_"


def params_to_npz(params: BuildParams) -> dict:
    """BuildParams -> named npz fields (``param_<name>``)."""
    return {
        _PARAM_PREFIX + f.name: np.asarray(getattr(params, f.name))
        for f in dataclasses.fields(BuildParams)
    }


def params_from_npz(z) -> BuildParams:
    """Named npz fields -> BuildParams, with the legacy positional
    int64 ``params`` array as the backward-compat path."""
    names = {f.name for f in dataclasses.fields(BuildParams)}
    if _PARAM_PREFIX + "m" in z:
        kw = {}
        for name in names:
            key = _PARAM_PREFIX + name
            if key in z:
                val = z[key][()]
                kw[name] = float(val) if name == "alpha" else int(val)
        return BuildParams(**kw)
    p = z["params"]                      # legacy positional archive
    return BuildParams(
        m=int(p[0]), ef_construction=int(p[1]), alpha=p[2] / 1000.0,
        chunk=int(p[3]), prune_pool=int(p[4]), reverse_slack=int(p[5]),
        consolidate_every=int(p[6]), passes=int(p[7]), seed=int(p[8]),
        beam_expand=int(p[9]) if len(p) > 9 else 1,
    )


def _normalize(x: jnp.ndarray) -> jnp.ndarray:
    return x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True), 1e-12)


def random_rotation(dim: int, seed: int) -> jnp.ndarray:
    """Random orthogonal matrix (RaBitQ-style preprocessing; beyond-paper)."""
    key = jax.random.PRNGKey(seed)
    g = jax.random.normal(key, (dim, dim), dtype=jnp.float32)
    q, r = jnp.linalg.qr(g)
    # fix signs for a uniform Haar rotation
    return q * jnp.sign(jnp.diag(r))[None, :]


@dataclasses.dataclass
class QuIVerIndex:
    """A built index. ``vectors`` is the cold path; everything else hot."""

    sigs: bq.Signature               # (N, 2W) packed — hot
    adjacency: jnp.ndarray           # (N, R+slack) int32 — hot
    medoid: int
    params: BuildParams
    vectors: jnp.ndarray | None      # (N, D) float32, L2-normalized — cold
    rotation: jnp.ndarray | None = None
    build_stats: BuildStats | None = None
    metric_kind: NavKind = "bq2"
    labels: LabelStore | None = None     # packed label bitsets — hot
    # applicability-boundary state (repro.probe, DESIGN.md §10): the
    # probe report and nav policy chosen by ``build(nav="auto")``; both
    # persist through save/load so a loaded index keeps its schedule.
    policy: NavPolicy | None = None
    report: CompatibilityReport | None = None
    # IVF-over-BQ coarse partition (repro.ivf, DESIGN.md §13): present
    # when built with ``ivf_candidates`` or attached via ``build_ivf``;
    # enables the ``nav="ivf"`` plan family and targeted scatter
    ivf: IVFPartition | None = None
    # structural X-ray (repro.obs.graph, DESIGN.md §15): the last
    # computed GraphHealthReport; persists through save/load (and
    # freeze, on the streaming side) so a loaded index remembers the
    # topology verdict it shipped with
    graph_health: "object | None" = None
    # backends are constructed once per nav kind and cached: kernel
    # dispatch happens at construction, and beam-search jit caches key on
    # the backend instance, so reusing it avoids re-trace per query batch.
    _backends: dict = dataclasses.field(
        default_factory=dict, repr=False, compare=False
    )
    # compiled query plans (repro.plan, DESIGN.md §11): one cache per
    # index; every distinct plan jit-compiles exactly once and serving
    # only feeds the compiled set
    _plan_cache: PlanCache | None = dataclasses.field(
        default=None, repr=False, compare=False
    )

    def backend(self, kind: NavKind | None = None) -> MetricSpace:
        """The metric backend for ``kind`` (default: the index's own)."""
        kind = kind or self.metric_kind
        if kind not in self._backends:
            self._backends[kind] = make_backend(
                kind, MetricArrays(sigs=self.sigs, vectors=self.vectors)
            )
        return self._backends[kind]

    @property
    def default_nav(self) -> NavKind:
        """The nav a search that leaves ``nav`` unset takes: the
        policy's, else the build metric."""
        return self.policy.nav if self.policy is not None else self.metric_kind

    @property
    def plans(self) -> PlanCache:
        """The index's compiled-plan cache (created on first use)."""
        if self._plan_cache is None:
            self._plan_cache = PlanCache(self)
        return self._plan_cache

    # -- construction ------------------------------------------------------

    @classmethod
    def build(
        cls,
        vectors: jnp.ndarray,
        params: BuildParams | None = None,
        *,
        metric: NavKind | Literal["auto"] = "bq2",
        nav: NavKind | Literal["auto"] | None = None,
        probe_sample: int = 1024,
        probe_seed: int = 0,
        rotate_seed: int | None = None,
        keep_vectors: bool = True,
        verbose: bool = False,
    ) -> "QuIVerIndex":
        """Build the index; ``metric`` (alias ``nav``) picks the space.

        ``metric="auto"`` runs the training-free applicability probe
        (``repro.probe``, DESIGN.md §10) on a ``probe_sample``-row
        slice and selects the nav ladder rung + ef/rerank schedule
        from the verdict: green -> ``bq2``, amber -> ``bq2`` with
        adaptive escalation, red -> ``float32`` (or ``adc`` without
        cold vectors) — so incompatible corpora route around the BQ
        collapse instead of silently serving <15% recall.  The chosen
        :class:`NavPolicy` and :class:`CompatibilityReport` ride the
        index through save/load and drive ``search`` defaults.
        """
        if nav is not None:
            metric = nav
        params = params or BuildParams()
        assert params.prune_pool <= params.ef_construction
        vectors = _normalize(jnp.asarray(vectors, dtype=jnp.float32))
        rotation = None
        encoded = vectors
        if rotate_seed is not None:
            rotation = random_rotation(vectors.shape[-1], rotate_seed)
            encoded = vectors @ rotation
        policy = report = None
        if metric == "auto":
            # probe the encoding the index will actually serve: the
            # bit-plane statistics and BQ agreement are properties of
            # the (possibly rotated) signatures, not the raw vectors
            # (cosine moments are rotation-invariant either way)
            report = probe_corpus(
                encoded, sample=probe_sample, seed=probe_seed
            )
            policy = select_policy(
                report, have_vectors=keep_vectors,
                have_ivf=params.ivf_candidates,
            )
            metric = policy.nav
            if verbose:
                print(f"[probe] {report.summary()} -> {policy.describe()}")
        if metric == "ivf":
            # "ivf" is a nav family over a bq2-built graph + partition,
            # not a construction metric; the policy carries the default
            if policy is None:
                policy = NavPolicy(nav="ivf", source="manual")
            metric = "bq2"
        sigs = bq.encode(encoded)
        ivf = None
        if params.ivf_candidates or (
            policy is not None and policy.nav == "ivf"
        ):
            ivf = build_partition(
                sigs, n_lists=params.ivf_lists or None, seed=params.seed
            )
        backend = make_backend(
            metric, MetricArrays(sigs=sigs, vectors=vectors)
        )
        adj, medoid, stats = build_graph(
            backend, params, ivf=ivf, verbose=verbose
        )
        return cls(
            sigs=sigs,
            adjacency=adj,
            medoid=medoid,
            params=params,
            vectors=vectors if keep_vectors else None,
            rotation=rotation,
            build_stats=stats,
            metric_kind=metric,
            policy=policy,
            report=report,
            ivf=ivf,
        )

    def build_ivf(
        self, *, n_lists: int | None = None, seed: int | None = None
    ) -> IVFPartition:
        """Attach a coarse partition post-hoc (enables ``nav="ivf"``
        and targeted scatter on an index built without one).
        Deterministic under the build seed unless ``seed`` overrides."""
        self.ivf = build_partition(
            self.sigs, n_lists=n_lists,
            seed=self.params.seed if seed is None else seed,
        )
        return self.ivf

    # -- replanning (closed-loop remediation, DESIGN.md §14) ---------------

    def replan(
        self,
        *,
        nav: NavKind,
        ef_scale: int | None = None,
        adaptive: bool | None = None,
        source: str = "replan",
    ) -> NavPolicy:
        """Switch the index's default nav policy at serve time.

        The remediation path (``repro.obs.remediate``) calls this when
        live recall evidence contradicts the build-time verdict: the
        new :class:`NavPolicy` becomes the default for every search
        that leaves ``nav`` unset, and the *old* default's compiled
        plans are invalidated from the :class:`PlanCache` — targeted,
        so every other nav family's executables survive untouched
        (zero retraces for unaffected traffic).

        ``ef_scale`` / ``adaptive`` default to the current policy's
        values (or the :class:`NavPolicy` defaults when none is set).
        """
        if nav == "ivf" and self.ivf is None:
            raise ValueError(
                "replan(nav='ivf') needs a coarse partition; call "
                "build_ivf() first"
            )
        if nav == "float32" and self.vectors is None:
            raise ValueError(
                "replan(nav='float32') needs the cold vector tier; "
                "this index is vector-free"
            )
        old_nav = self.default_nav
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
        if nav != old_nav and self._plan_cache is not None:
            self._plan_cache.invalidate(nav=old_nav)
        return self.policy

    # -- labels (filtered search, DESIGN.md §9) ----------------------------

    def attach_labels(
        self, labels, *, n_labels: int | None = None
    ) -> LabelStore:
        """Attach per-node labels: one int (categorical) or iterable of
        ints (multi-tag) per node, length N.  Returns the store."""
        n = self.sigs.words.shape[0]
        if len(labels) != n:
            raise ValueError(f"{len(labels)} label rows for {n} nodes")
        self.labels = LabelStore.from_rows(labels, n_labels=n_labels)
        return self.labels

    def build_label_entries(self, *, min_count: int = 32) -> int:
        """Per-label entry points (member medoids) for frequent labels;
        returns how many were built."""
        if self.labels is None:
            raise ValueError("no labels attached")
        return build_label_entries(
            self.labels, self.backend(), vectors=self.vectors,
            min_count=min_count,
        )

    # -- search ------------------------------------------------------------

    def search(
        self,
        queries: jnp.ndarray,
        k: int = 10,
        *,
        ef: int = 64,
        rerank: bool = True,
        nav: NavKind | None = None,
        expand: int = 1,
        query_batch: int = 256,
        filter=None,
        selectivity_floor: float = DEFAULT_SELECTIVITY_FLOOR,
        adaptive: bool | None = None,
        probes: int | None = None,
    ) -> tuple[np.ndarray, np.ndarray]:
        """(Q, D) float32 queries -> ((Q, k) ids, (Q, k) scores).

        ``nav="ivf"`` (or an ivf :class:`NavPolicy` default) routes
        through the coarse-list family (DESIGN.md §13): scan the
        centroid signatures, gather the members of the ``probes``
        nearest lists (default: the partition's √L), keep the best
        ``ef`` in bq2 space and rerank — no graph traversal.
        Escalation widens ``probes``; all other knobs compose as on
        the graph route.

        Score scale: with ``rerank=True`` (and cold vectors present)
        scores are exact float32 **cosine similarity** in [-1, 1]; with
        ``rerank=False`` they are **negated navigation distances** on
        the ``nav`` backend's own scale (e.g. ``sim - 4D`` for ``bq2``)
        — larger is still better, but the two scales are not comparable
        (see :func:`rerank`).

        ``nav`` defaults to the metric the index was built in; ``expand``
        is the beam expansion width L (one (L*R,) distance batch/hop).

        An auto-built index (``build(nav="auto")``) applies its
        :class:`NavPolicy` schedule when ``nav`` is left default: ``ef``
        is multiplied by ``policy.ef_scale``, and ``adaptive`` defaults
        to the policy's setting.  ``adaptive=True`` enables per-query
        escalation (DESIGN.md §10): queries whose top-k navigation
        margins are tight (:func:`repro.core.beam.beam_margin` below
        the policy's ``escalate_margin`` — the quantized scores cannot
        separate the rerank pool boundary) re-run with an
        ``escalate_mult``-times wider beam, widening the rerank
        candidate pool exactly where it is needed.

        ``filter`` (optional) is a label predicate — ``repro.filter``'s
        ``Any``/``All``/``Not`` or a bare label id — evaluated against
        the attached :class:`LabelStore`.  Estimated selectivity picks
        the route: above ``selectivity_floor`` the graph is traversed
        with a widened ``ef`` and the predicate as the beam's
        ``result_valid`` mask (non-matching nodes route but never
        surface), starting from the best per-label entry point; below
        the floor the match set is brute-forced exactly.  Adaptive
        escalation composes with the graph route (the escalated pass
        keeps the predicate mask); the brute route is already exact.

        The whole call lowers to a compiled :class:`~repro.plan.QueryPlan`
        (DESIGN.md §11): the nav ladder, the filter route and the
        escalation schedule are resolved *once* into a frozen plan, and
        the index's :class:`~repro.plan.PlanCache` compiles each
        distinct plan exactly once — repeated calls with the same
        configuration only feed cached executables.
        """
        plan, ctx = resolve_plan(
            self, k=k, ef=ef, rerank=rerank, nav=nav, expand=expand,
            query_batch=query_batch, filter=filter,
            selectivity_floor=selectivity_floor, adaptive=adaptive,
            probes=probes,
        )
        return self.plans.run(plan, ctx, queries)

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
        :class:`~repro.obs.graph.GraphHealthReport`: degree structure,
        reciprocity, medoid reachability, and — when cold vectors are
        present — the sampled BQ↔float32 edge-agreement score.  The
        cached report persists through :meth:`save`/:meth:`load`."""
        from repro.obs.graph import (
            DEFAULT_GRAPH_THRESHOLDS,
            graph_health_report,
        )
        self.graph_health = graph_health_report(
            self.adjacency,
            medoid=self.medoid,
            words=self.sigs.words if self.vectors is not None else None,
            dim=self.sigs.dim,
            vectors=self.vectors,
            sample=sample,
            agreement_k=agreement_k,
            max_hops=max_hops,
            seed=seed,
            thresholds=thresholds or DEFAULT_GRAPH_THRESHOLDS,
            registry=registry,
        )
        return self.graph_health

    # -- accounting (paper Table 2) -----------------------------------------

    def memory_breakdown(self) -> dict:
        n = self.sigs.words.shape[0]
        sig_bytes = self.sigs.words.size * 4
        adj_bytes = self.adjacency.size * 4 + n * 4  # + degree counters
        label_bytes = (
            self.labels.memory_bytes() if self.labels is not None else 0
        )
        # the IVF tier (centroid signatures + padded list layout) rides
        # the hot path: every ivf plan gathers from it per query
        ivf_bytes = self.ivf.memory_bytes() if self.ivf is not None else 0
        cold = self.vectors.size * 4 if self.vectors is not None else 0
        # shadow-sampler host state (pending ground-truth copies + the
        # recall window) — attached by repro.obs.quality.ShadowSampler
        shadow = getattr(self, "shadow", None)
        shadow_bytes = shadow.memory_bytes() if shadow is not None else 0
        hot = sig_bytes + adj_bytes + label_bytes + ivf_bytes
        out = {
            "hot_signature_bytes": int(sig_bytes),
            "hot_adjacency_bytes": int(adj_bytes),
            "hot_label_bytes": int(label_bytes),
            "hot_ivf_bytes": int(ivf_bytes),
            "hot_total_bytes": int(hot),
            "cold_vector_bytes": int(cold),
            "host_shadow_bytes": int(shadow_bytes),
            "total_bytes": int(hot + cold + shadow_bytes),
        }
        if self.policy is not None:
            # auto-built indexes report the serving policy next to the
            # bytes it costs: a red-zone float32 ladder means the "cold"
            # tier is actually on the hot path
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

    # -- persistence ---------------------------------------------------------

    def save(self, path: str) -> None:
        label_fields = (
            self.labels.to_npz_fields() if self.labels is not None else {}
        )
        probe_fields = {}
        if self.policy is not None:
            probe_fields.update(self.policy.to_npz_fields())
        if self.report is not None:
            probe_fields.update(self.report.to_npz_fields())
        if self.ivf is not None:
            probe_fields.update(self.ivf.to_npz_fields())
        if self.graph_health is not None:
            probe_fields.update(self.graph_health.to_npz_fields())
        np.savez_compressed(
            path,
            words=np.asarray(self.sigs.words),
            dim=self.sigs.dim,
            adjacency=np.asarray(self.adjacency),
            medoid=self.medoid,
            vectors=(
                np.asarray(self.vectors)
                if self.vectors is not None else np.zeros((0,))
            ),
            rotation=(
                np.asarray(self.rotation)
                if self.rotation is not None else np.zeros((0,))
            ),
            metric_kind=np.array(self.metric_kind),
            **label_fields,
            **probe_fields,
            **params_to_npz(self.params),
        )

    @classmethod
    def load(cls, path: str) -> "QuIVerIndex":
        z = np.load(path)
        if "stream_format" in z:
            raise ValueError(
                "this is a streaming archive; load it with "
                "repro.stream.MutableQuIVerIndex.load (freeze() it for "
                "an immutable QuIVerIndex)"
            )
        from repro.obs.graph import GraphHealthReport
        params = params_from_npz(z)
        vectors = z["vectors"]
        rotation = z["rotation"]
        # pre-refactor archives carried no metric_kind (always bq2)
        metric_kind = str(z["metric_kind"]) if "metric_kind" in z else "bq2"
        return cls(
            sigs=bq.Signature(
                words=jnp.asarray(z["words"]), dim=int(z["dim"])
            ),
            adjacency=jnp.asarray(z["adjacency"]),
            medoid=int(z["medoid"]),
            params=params,
            vectors=jnp.asarray(vectors) if vectors.size else None,
            rotation=jnp.asarray(rotation) if rotation.size else None,
            metric_kind=metric_kind,
            labels=LabelStore.from_npz(z),
            policy=NavPolicy.from_npz(z),
            report=CompatibilityReport.from_npz(z),
            ivf=IVFPartition.from_npz(z),
            graph_health=GraphHealthReport.from_npz(z),
        )


@functools.partial(jax.jit, static_argnames=("k",))
def rerank_f32(beam_ids, queries, vectors, k):
    """Cold-path rerank: exact cosine over the ef candidates (§3.3).

    ``beam_ids`` entries < 0 (padding / masked tombstones) are excluded
    — their similarity is -inf, so they can only surface as trailing -1
    ids when fewer than k valid candidates exist.
    """
    from repro.plan.trace import note_trace
    note_trace("rerank_f32")
    safe = jnp.maximum(beam_ids, 0)
    cand = vectors[safe]                                # (Q, ef, D)
    sims = jnp.einsum("qd,qed->qe", queries, cand,
                      precision=jax.lax.Precision.HIGHEST)
    sims = jnp.where(beam_ids >= 0, sims, -jnp.inf)
    scores, pos = jax.lax.top_k(sims, k)
    ids = jnp.take_along_axis(beam_ids, pos, axis=-1)
    ids = jnp.where(jnp.isfinite(scores), ids, -1)
    return ids, scores


@functools.partial(jax.jit, static_argnames=("k",))
def topk_by_dist(beam_ids, beam_dists, k):
    """Hot-path-only top-k: scores are **negated navigation distances**
    (the beam backend's own scale — e.g. ``sim - 4D`` in [-8D, 0] for
    ``bq2``, negated Hamming for ``bq1``), NOT cosine.  Larger is
    better, but the scale is not comparable to :func:`rerank_f32`."""
    from repro.plan.trace import note_trace
    note_trace("topk_by_dist")
    scores, pos = jax.lax.top_k(-beam_dists, k)
    ids = jnp.take_along_axis(beam_ids, pos, axis=-1)
    return ids, scores


def rerank(beam_ids, beam_dists, queries, vectors, k):
    """Shared rerank entry — the score-convention boundary.

    With cold ``vectors`` present, candidates are re-scored exactly and
    the returned scores are **float32 cosine similarity** in [-1, 1]
    (:func:`rerank_f32`).  With ``vectors=None`` (``rerank=False``
    searches, vector-free indexes) the beam order is kept and the
    scores are **negated navigation distances** on the metric backend's
    own scale (:func:`topk_by_dist`).  Both exclude invalid (-1) beam
    ids; callers comparing scores across searches must hold the
    convention fixed — the two scales are not interchangeable.

    Inside a compiled program its device ops carry the named scope
    ``quiver.rerank``.
    """
    with jax.named_scope("quiver.rerank"):
        if vectors is None:
            return topk_by_dist(beam_ids, beam_dists, k)
        return rerank_f32(beam_ids, queries, vectors, k)


# pre-streaming private names, kept for any out-of-tree callers
_rerank, _rerank_f32, _topk_by_dist = rerank, rerank_f32, topk_by_dist
