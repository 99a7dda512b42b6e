"""Sharded QuIVer: the paper's index distributed over the 'data' axis.

Fleet layout (DESIGN.md §3):
  * base vectors are range-partitioned into one shard per device;
  * each shard builds its own BQ-native Vamana graph — construction is
    embarrassingly parallel (the cluster-scale analogue of the paper's
    chunked concurrent linking: zero cross-shard dependencies);
  * a query fans out to all shards (`shard_map`), runs the local
    beam search + local float32 rerank, and the per-shard top-k are
    all-gathered and merged — one collective of k ids/scores per shard,
    the classic scatter-gather serving pattern.

The shard-local traversal distance is NOT hand-rolled here: each shard
constructs the registered metric backend (``repro.core.metric``) from
its local arrays, so sharded serving navigates in exactly the metric
space the graph was built in — any registered nav kind (``bq2``,
``bq1``, ``adc``, ``float32``), with kernel dispatch decided once at
backend construction (DESIGN.md §2).

Per-chip hot set = (N/S) signatures + adjacency: at 1M x 768 over 256
chips that is ~3 MB/chip — the paper's DDR5-bandwidth-bound hot loop
becomes VMEM/HBM-resident on TPU.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from repro.dist.compat import shard_map

from repro.core import bq
from repro.core.beam import batched_beam_search
from repro.core.index import QuIVerIndex, rerank_f32
from repro.core.metric import (
    MetricArrays,
    encode_queries_for,
    make_backend,
)
from repro.core.vamana import BuildParams
from repro.filter import (
    DEFAULT_SELECTIVITY_FLOOR,
    Label,
    entry_label,
    estimate_selectivity,
    eval_mask,
    validate,
    widened_ef,
)
from repro.probe import (
    CompatibilityReport,
    NavPolicy,
    merge_reports,
    probe_corpus,
    select_policy,
)


class ShardedIndex(NamedTuple):
    """Stacked per-shard index arrays (leading dim = n_shards).

    ``live`` is the per-shard validity mask: padding fill from an
    indivisible partition and streaming tombstones are both False and
    are excluded from search results *before* the all-gather merge.

    ``label_words`` / ``label_entries`` (optional) carry the per-shard
    filtered-search state (DESIGN.md §9): packed label bitsets stacked
    shard-major, and per-(shard, label) entry points.  A filtered
    query's predicate is evaluated per shard and pushed down into the
    fan-out as the beam's ``result_valid`` mask, so every shard merges
    only matching live ids — the top-k collective never widens.
    """
    sig_words: jnp.ndarray    # (S, n, 2W) uint32
    adjacency: jnp.ndarray    # (S, n, R+slack) int32
    medoids: jnp.ndarray      # (S,) int32
    vectors: jnp.ndarray      # (S, n, D) float32 (cold)
    dim: int
    metric: str = "bq2"       # metric kind the shards were built in
    live: jnp.ndarray | None = None   # (S, n) bool; None == all live
    label_words: jnp.ndarray | None = None   # (S, n, W_l) uint32
    n_labels: int = 0
    label_entries: jnp.ndarray | None = None  # (S, n_labels) int32, -1
    label_counts: np.ndarray | None = None    # (n_labels,) fleet-wide
    # applicability boundary (DESIGN.md §10): the fleet-wide merged
    # probe report and the nav policy every shard was built under
    policy: NavPolicy | None = None
    report: CompatibilityReport | None = None


def build_sharded(vectors: np.ndarray, n_shards: int,
                  params: BuildParams | None = None,
                  *, metric: str = "bq2",
                  labels=None, n_labels: int | None = None,
                  label_entry_min: int = 32) -> ShardedIndex:
    """Partition + per-shard build.  With at least ``n_shards``
    devices, shard s is built on device s (one host thread per shard,
    so the builds overlap) and stays there: the stacked arrays are
    sharded over a 1-D ``"data"`` mesh (:func:`_shard_mesh`) and no
    device ever holds another shard's data.  With fewer devices every
    shard is built and stacked on the default device.

    Indivisible N is handled by padding the last shard with repeats of
    the leading vectors; the fill nodes participate in their shard's
    graph (they are real points, so navigation quality is unaffected)
    but are masked out of every search result, so all N input vectors
    — and only those — are retrievable.

    ``labels`` (optional, one int or iterable of ints per vector)
    attaches filter labels: each shard packs its slice into a
    :class:`~repro.filter.labels.LabelStore` and builds per-label
    entry points (``label_entry_min`` member floor), enabling
    ``search_sharded(filter=...)`` predicate pushdown.  Padding fill
    rows inherit the repeated vectors' labels but stay masked by
    ``live``, so they never surface.

    ``metric="auto"`` runs the applicability probe per shard slice,
    merges the shard reports fleet-wide (``repro.probe.merge_reports``)
    and builds every shard under the single policy the *merged* verdict
    selects — one serving schedule for the whole fleet, chosen from
    evidence pooled across all partitions.
    """
    params = params or BuildParams()
    n = len(vectors)
    per = -(-n // n_shards)                      # ceil division
    pad = per * n_shards - n
    arr = np.asarray(vectors)
    if pad:
        arr = np.concatenate([arr, arr[:pad]], axis=0)
    parts = arr.reshape(n_shards, per, arr.shape[-1])
    live = (np.arange(n_shards * per) < n).reshape(n_shards, per)
    policy = report = None
    if metric == "auto":
        # per-shard probes (each host probes only its own slice; the
        # last shard's pad fill repeats leading vectors — a < 1-shard
        # bias on fleet statistics, same as the label popcounts below)
        shard_reports = [
            probe_corpus(parts[s], seed=s) for s in range(n_shards)
        ]
        report = merge_reports(shard_reports)
        policy = select_policy(report)
        metric = policy.nav
    label_parts = None
    if labels is not None:
        if len(labels) != n:
            raise ValueError(f"{len(labels)} label rows for {n} vectors")
        labels = list(labels)
        if n_labels is None:
            flat = [x for item in labels for x in (
                (item,) if np.isscalar(item) else tuple(item))]
            n_labels = int(max(flat)) + 1 if flat else 1
        label_parts = [
            (labels + labels[:pad])[s * per:(s + 1) * per]
            for s in range(n_shards)
        ]
    mesh = _shard_mesh(n_shards)

    def build_one(s):
        # each shard is built on, and stays on, its own device
        dev = mesh.devices.flat[s] if mesh is not None else None
        with jax.default_device(dev):
            idx = QuIVerIndex.build(
                jax.device_put(parts[s], dev), params, metric=metric
            )
            if label_parts is not None:
                idx.attach_labels(label_parts[s], n_labels=n_labels)
                idx.build_label_entries(min_count=label_entry_min)
        return idx

    # one host thread per device: the chunk loops dispatch
    # asynchronously, so the shard builds overlap on their chips
    workers = n_shards if mesh is not None else 1
    with ThreadPoolExecutor(max_workers=workers) as pool:
        shards = list(pool.map(build_one, range(n_shards)))
    stores = [i.labels for i in shards] if label_parts is not None else []
    return ShardedIndex(
        sig_words=_stack_shards([i.sigs.words for i in shards], mesh),
        adjacency=_stack_shards([i.adjacency for i in shards], mesh),
        medoids=jnp.asarray([i.medoid for i in shards], dtype=jnp.int32),
        vectors=_stack_shards([i.vectors for i in shards], mesh),
        dim=vectors.shape[-1],
        metric=metric,
        live=jnp.asarray(live),
        label_words=(_stack_shards([st.words for st in stores], mesh)
                     if stores else None),
        n_labels=n_labels or 0,
        label_entries=(
            jnp.asarray(np.stack([st.entries for st in stores]))
            if stores else None
        ),
        # fleet-wide popcounts for selectivity routing (pad fill rows
        # inflate these by < 1 shard's worth — estimates, not truth)
        label_counts=(np.sum([st.counts for st in stores], axis=0)
                      if stores else None),
        policy=policy,
        report=report,
    )


def _shard_mesh(n_shards: int) -> Mesh | None:
    """The 1-D mesh that holds one shard per device, or None when the
    process has fewer devices than shards (every shard then shares the
    default device)."""
    devices = jax.devices()
    if len(devices) < n_shards:
        return None
    return jax.make_mesh((n_shards,), ("data",),
                         devices=devices[:n_shards],
                         axis_types=(AxisType.Auto,))


def _stack_shards(arrays, mesh: Mesh | None):
    """Per-shard arrays -> one (S, ...) array, sharded over ``mesh``'s
    axis with shard s left on device s (no copy through one device)."""
    if mesh is None:
        return jnp.stack(arrays)
    shape = (len(arrays), *arrays[0].shape)
    sharding = NamedSharding(mesh, P(mesh.axis_names[0]))
    return jax.make_array_from_single_device_arrays(
        shape, sharding,
        [jax.device_put(a[None], d)
         for a, d in zip(arrays, mesh.devices.flat)],
    )


def make_sharded_search(mesh: Mesh, *, dim: int, ef: int, k: int,
                        n_per_shard: int,
                        axis: str | tuple = "data",
                        nav: str = "bq2",
                        expand: int = 1):
    """Compile a fan-out/merge search step over ``mesh[axis]``.

    Returns search(index arrays..., result_valid (S, n), q_repr
    (Q, ...), queries (Q, D)) -> (global_ids (Q, k) int32, scores
    (Q, k) f32), replicated.  ``q_repr`` is the ``nav`` backend's query
    representation (use :func:`repro.core.metric.encode_queries_for`).
    ``live`` is the per-shard tombstone/padding mask and
    ``result_valid`` the per-shard filter-predicate mask (all-True when
    unfiltered): dead and non-matching nodes still route the local beam
    (FreshDiskANN navigation semantics, see ``repro.core.beam``) but
    are masked out of the local top-k *before* the all-gather, so one
    collective of k already-filtered ids/scores per shard is merged.
    """

    def local_search(sig_words, adj, medoid, vectors, live,
                     result_valid, q_repr, queries):
        # shard-local arrays arrive with the leading shard dim stripped
        sig_words = sig_words[0]
        adj = adj[0]
        medoid = medoid[0]
        vectors = vectors[0]
        live = live[0]
        result_valid = result_valid[0]
        # one backend per shard, same registry as everything else — the
        # sharded path owns no private distance function.
        backend = make_backend(nav, MetricArrays(
            sigs=bq.Signature(words=sig_words, dim=dim), vectors=vectors,
        ))

        res = batched_beam_search(
            q_repr, adj, medoid, dist_fn=backend.dist_fn, ef=ef,
            n=n_per_shard, expand=expand, node_valid=live,
            result_valid=result_valid,
        )
        # local cold-path rerank to top-k (res.ids are live-only) —
        # the single shared rerank, not a private copy
        ids, scores = rerank_f32(res.ids, queries, vectors, k)
        # globalize ids with the shard offset
        shard_id = jax.lax.axis_index(axis)
        gids = jnp.where(ids >= 0, ids + shard_id * n_per_shard, -1)

        # merge across shards: gather (S, Q, k) and take global top-k
        all_ids = jax.lax.all_gather(gids, axis)
        all_scores = jax.lax.all_gather(scores, axis)
        s = all_ids.shape[0]
        flat_ids = all_ids.transpose(1, 0, 2).reshape(-1, s * k)
        flat_scores = all_scores.transpose(1, 0, 2).reshape(-1, s * k)
        top_scores, top_pos = jax.lax.top_k(flat_scores, k)
        top_ids = jnp.take_along_axis(flat_ids, top_pos, axis=-1)
        return top_ids, top_scores

    spec_shard = P(axis)
    return shard_map(
        local_search,
        mesh=mesh,
        in_specs=(spec_shard, spec_shard, spec_shard, spec_shard,
                  spec_shard, spec_shard, P(), P()),
        out_specs=(P(), P()),
        check_vma=False,
    )


# -- IVF targeted scatter (DESIGN.md §13) ---------------------------------
#
# The coarse lists double as the fleet's shard unit: whole inverted
# lists are placed on shards (greedy balance by population), the tiny
# centroid tier is replicated everywhere, and a query is scattered only
# to the shards owning its top-p lists — at most min(p, S) of them —
# instead of the all-shard fan-out above.  The scatter is host-driven
# (each contacted shard runs one compiled fused list-scan + rerank
# step over its own arrays), which is exactly the multi-host serving
# shape: routing on the frontend, one RPC per contacted shard.


class IVFShard(NamedTuple):
    """One shard's slice of a list-partitioned corpus."""

    sig_words: jnp.ndarray    # (n_s, 2W) uint32
    vectors: jnp.ndarray      # (n_s, D) float32 (cold/rerank tier)
    ids: np.ndarray           # (n_s,) int32 global corpus ids
    list_ids: jnp.ndarray     # (L_s, cap_s) int32 LOCAL slots, -1 pad
    lists: np.ndarray         # (L_s,) int32 global list ids owned


class IVFShardedIndex(NamedTuple):
    """List-partitioned fleet: replicated routing tier + per-shard
    member slices.  ``list_shard``/``list_local`` map a global list id
    to (owning shard, local list index) — the scatter's routing table.
    """

    cent_words: jnp.ndarray   # (L, 2W) uint32, replicated
    list_shard: np.ndarray    # (L,) int32 owning shard per list
    list_local: np.ndarray    # (L,) int32 local index within owner
    shards: tuple             # tuple[IVFShard, ...]
    dim: int
    default_probes: int

    @property
    def n_shards(self) -> int:
        return len(self.shards)

    @property
    def n_lists(self) -> int:
        return int(self.cent_words.shape[0])


def build_ivf_sharded(vectors: np.ndarray, n_shards: int, *,
                      n_lists: int | None = None,
                      seed: int = 0) -> IVFShardedIndex:
    """Partition by coarse list, then place whole lists on shards.

    One global :func:`repro.ivf.build_partition` over the corpus
    signatures, then greedy balance: lists in descending population
    order each go to the currently lightest shard, so shard loads stay
    within one max-list of each other without splitting any list (a
    list never spans shards — that is what makes the scatter targeted).
    """
    from repro.ivf import build_partition

    v = np.asarray(vectors, dtype=np.float32)
    v = v / np.maximum(
        np.linalg.norm(v, axis=-1, keepdims=True), 1e-12
    )
    sigs = bq.encode(jnp.asarray(v))
    part = build_partition(sigs, n_lists=n_lists, seed=seed)
    L = part.n_lists
    n_shards = max(1, min(n_shards, L))
    counts = np.diff(part.offsets)

    # greedy balance by population, descending
    order = np.argsort(-counts, kind="stable")
    load = np.zeros(n_shards, dtype=np.int64)
    list_shard = np.empty(L, dtype=np.int32)
    for lst in order:
        s = int(np.argmin(load))
        list_shard[lst] = s
        load[s] += counts[lst]
    list_local = np.empty(L, dtype=np.int32)

    shards = []
    for s in range(n_shards):
        owned = np.nonzero(list_shard == s)[0].astype(np.int32)
        list_local[owned] = np.arange(owned.size, dtype=np.int32)
        member_chunks = [
            part.member_ids[part.offsets[l]:part.offsets[l + 1]]
            for l in owned
        ]
        ids = (np.concatenate(member_chunks) if member_chunks
               else np.empty((0,), np.int32)).astype(np.int32)
        slot_of = {}
        cap = max(8, int(-(-max(
            (len(c) for c in member_chunks), default=1) // 8) * 8))
        local = np.full((max(owned.size, 1), cap), -1, dtype=np.int32)
        pos = 0
        for i, chunk_ids in enumerate(member_chunks):
            local[i, :len(chunk_ids)] = np.arange(
                pos, pos + len(chunk_ids), dtype=np.int32
            )
            pos += len(chunk_ids)
        del slot_of
        shards.append(IVFShard(
            sig_words=sigs.words[jnp.asarray(
                ids if ids.size else np.zeros((1,), np.int32)
            )],
            vectors=jnp.asarray(
                v[ids] if ids.size else v[:1] * 0.0
            ),
            ids=ids,
            list_ids=jnp.asarray(local),
            lists=owned,
        ))
    return IVFShardedIndex(
        cent_words=part.cent_words,
        list_shard=list_shard,
        list_local=list_local,
        shards=tuple(shards),
        dim=vectors.shape[-1],
        default_probes=part.default_probes,
    )


def _ivf_shard_step(dim: int, ef: int, k: int):
    """Compiled per-shard scatter step: fused local list scan + rerank."""

    def step(sig_words, vectors, list_ids, probe_local, reprs, queries):
        backend = make_backend("bq2", MetricArrays(
            sigs=bq.Signature(words=sig_words, dim=dim),
            vectors=vectors,
        ))
        q = probe_local.shape[0]
        mem = list_ids[jnp.maximum(probe_local, 0)].reshape(q, -1)
        valid = (
            (probe_local >= 0).repeat(list_ids.shape[1], axis=-1)
            & (mem >= 0)
        )
        d = backend.dist_many(reprs, jnp.maximum(mem, 0), valid)
        d = jnp.where(valid, d, _INF)
        ef_eff = min(ef, mem.shape[1])
        neg, pos = jax.lax.top_k(-d, ef_eff)
        ids = jnp.take_along_axis(mem, pos, axis=-1)
        ids = jnp.where(-neg < _INF / 2, ids, -1)
        return rerank_f32(ids, queries, vectors, k)

    return jax.jit(step)


_INF = jnp.float32(3.0e38)
_IVF_STEP_CACHE: dict = {}


def search_ivf_sharded(index: IVFShardedIndex, queries: np.ndarray, *,
                       k: int = 10, ef: int = 64,
                       probes: int | None = None,
                       broadcast: bool = False,
                       registry=None):
    """Targeted scatter over the list-partitioned fleet.

    Routes each query on the replicated centroid tier, contacts only
    the shards owning its top-p lists (≤ min(p, S) of them; shards a
    query does not route to never see it), and merges the per-shard
    reranked top-k by cosine score — the IVF analogue of
    :func:`search_sharded`'s all-shard fan-out.

    ``broadcast=True`` sends every query to every shard (non-probed
    lists stay masked out) — the all-shard baseline the targeted path
    is equivalence-tested against.  Per-list route counters and the
    shards-contacted histogram land on ``registry`` (default process
    registry).  Returns (global ids (Q, k), cosine scores (Q, k)).
    """
    from repro.core.beam import batch_bucket, pad_rows
    from repro.ivf import record_routes, top_lists
    from repro.kernels import dispatch

    q = jnp.asarray(queries, jnp.float32)
    if q.ndim == 1:
        q = q[None]
    q = q / jnp.maximum(jnp.linalg.norm(q, axis=-1, keepdims=True), 1e-12)
    nq = q.shape[0]
    reprs = encode_queries_for("bq2", q)
    p = probes or index.default_probes
    p = max(1, min(p, index.n_lists))

    ops = dispatch.list_scan_ops(index.dim)
    top = np.asarray(top_lists(ops.scan, reprs, index.cent_words, p))
    shard_of = index.list_shard[top]                       # (Q, p)
    contacted_per_q = np.array([
        len(np.unique(row)) for row in shard_of
    ])
    record_routes(top, contacted_per_q, registry=registry)

    all_ids = np.full((nq, index.n_shards, k), -1, dtype=np.int64)
    all_scores = np.full((nq, index.n_shards, k), -np.inf,
                         dtype=np.float32)
    for s, shard in enumerate(index.shards):
        if broadcast:
            rows = np.arange(nq)
        else:
            rows = np.nonzero((shard_of == s).any(axis=-1))[0]
        if rows.size == 0 or shard.ids.size == 0:
            continue                     # targeted: shard never contacted
        # local probe table: this shard's local index for each probed
        # list it owns, -1 elsewhere (masked inside the fused step)
        sub = top[rows]
        probe_local = np.where(
            index.list_shard[sub] == s, index.list_local[sub], -1
        ).astype(np.int32)
        bucket = batch_bucket(rows.size, 256)
        key = (s, shard.sig_words.shape, shard.list_ids.shape,
               bucket, p, ef, k, index.dim)
        step = _IVF_STEP_CACHE.get(key)
        if step is None:
            step = _ivf_shard_step(index.dim, ef, k)
            _IVF_STEP_CACHE[key] = step
        ids, scores = step(
            shard.sig_words, shard.vectors, shard.list_ids,
            pad_rows(jnp.asarray(probe_local), bucket),
            pad_rows(reprs[jnp.asarray(rows)], bucket),
            pad_rows(q[jnp.asarray(rows)], bucket),
        )
        ids = np.asarray(ids[:rows.size])
        scores = np.asarray(scores[:rows.size])
        gids = np.where(ids >= 0, shard.ids[np.maximum(ids, 0)], -1)
        all_ids[rows, s] = gids
        all_scores[rows, s] = np.where(ids >= 0, scores, -np.inf)

    flat_ids = all_ids.reshape(nq, -1)
    flat_scores = all_scores.reshape(nq, -1)
    order = np.argsort(-flat_scores, axis=-1)[:, :k]
    out_scores = np.take_along_axis(flat_scores, order, axis=-1)
    out_ids = np.take_along_axis(flat_ids, order, axis=-1)
    out_ids[~np.isfinite(out_scores)] = -1
    return out_ids, out_scores


def sharded_count_fn(index: ShardedIndex):
    """``label -> member popcount`` across all shards.

    Uses the precomputed ``label_counts`` carried by the index (kept
    fresh by ``build_sharded`` / ``StreamingShardedIndex.snapshot``);
    falls back to a per-label device popcount for hand-assembled
    indexes, cached for the lifetime of the returned closure.
    """
    if index.label_counts is not None:
        counts = index.label_counts
        return lambda label: int(counts[label])
    live = index.live
    cache: dict[int, int] = {}

    def count(label: int) -> int:
        if label not in cache:
            member = eval_mask(index.label_words, Label(label))
            if live is not None:
                member = member & live
            cache[label] = int(member.sum())
        return cache[label]

    return count


def search_sharded(index: ShardedIndex, queries: np.ndarray, *,
                   mesh: Mesh | None = None, ef: int = 64, k: int = 10,
                   axis: str = "data", nav: str | None = None,
                   expand: int = 1, filter=None,
                   selectivity_floor: float = DEFAULT_SELECTIVITY_FLOOR):
    """Convenience wrapper: encode queries, fan out, merge.

    ``nav`` defaults to the metric the shards were built in, mirroring
    ``QuIVerIndex.search``.

    ``filter`` (optional label predicate) is pushed down per shard: the
    predicate mask is evaluated against each shard's packed label
    bitsets and rides the fan-out as the local beam's ``result_valid``,
    with ``ef`` widened by the popcount-estimated selectivity and each
    shard starting from its own per-label entry point when one exists.
    Every shard therefore contributes only matching live ids to the
    merge — the collective stays one (k ids, k scores) pair per shard.
    (There is no per-shard brute-force route: a shard's match set is
    already 1/S of the corpus, and the masked merge is exact.)

    An auto-built fleet (``build_sharded(metric="auto")``) applies its
    :class:`NavPolicy` ef/rerank schedule when ``nav`` is left default.
    Per-query adaptive escalation is a single-index feature: at fleet
    scale the static ``ef_scale`` rides the one fan-out collective,
    while a second escalated collective per tight query would double
    the serving critical path (DESIGN.md §10).
    """
    sched = index.policy if nav is None else None
    if sched is not None:
        ef = ef * sched.ef_scale
    nav = nav or index.metric
    if mesh is None:
        # serve on the mesh the shards were placed on, so no shard moves
        placed = getattr(index.sig_words, "sharding", None)
        if (isinstance(placed, NamedSharding)
                and placed.mesh.axis_names == (axis,)):
            mesh = placed.mesh
        else:
            n_dev = index.sig_words.shape[0]
            mesh = jax.make_mesh((n_dev,), (axis,))
    q = jnp.asarray(queries, jnp.float32)
    q = q / jnp.maximum(jnp.linalg.norm(q, axis=-1, keepdims=True), 1e-12)
    q_repr = encode_queries_for(nav, q)
    live = index.live
    if live is None:
        live = jnp.ones(index.sig_words.shape[:2], dtype=jnp.bool_)

    result_valid = jnp.ones(index.sig_words.shape[:2], dtype=jnp.bool_)
    medoids = index.medoids
    ef_run = ef
    if filter is not None:
        if index.label_words is None:
            raise ValueError(
                "filtered sharded search needs label_words (build with "
                "labels= or snapshot a labeled streaming index)"
            )
        expr = validate(filter, index.n_labels)
        count_fn = sharded_count_fn(index)
        n_live = int(live.sum())
        sel = estimate_selectivity(expr, count_fn, n_live)
        # (S, n) predicate mask, evaluated shard-major on device
        result_valid = eval_mask(index.label_words, expr)
        ef_run = widened_ef(
            ef, sel, selectivity_floor, index.sig_words.shape[1]
        )
        lbl = entry_label(expr, count_fn)
        if lbl is not None and index.label_entries is not None:
            ent = index.label_entries[:, lbl]
            medoids = jnp.where(ent >= 0, ent, medoids).astype(jnp.int32)
    # cache the compiled fan-out: make_sharded_search returns a fresh
    # closure per call, so without this every search retraces (a
    # serving loop would recompile per request)
    key = (mesh, index.dim, ef_run, k, index.sig_words.shape[1], axis,
           nav, expand)
    fn = _SEARCH_CACHE.get(key)
    if fn is None:
        fn = jax.jit(make_sharded_search(
            mesh, dim=index.dim, ef=ef_run, k=k,
            n_per_shard=index.sig_words.shape[1], axis=axis, nav=nav,
            expand=expand,
        ))
        _SEARCH_CACHE[key] = fn
    ids, scores = fn(
        index.sig_words, index.adjacency, medoids, index.vectors,
        live, result_valid, q_repr, q,
    )
    return np.asarray(ids), np.asarray(scores)


_SEARCH_CACHE: dict = {}
