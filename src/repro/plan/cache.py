"""PlanCache — compile each distinct QueryPlan exactly once, then feed it.

One cache per index, built or mutable (a mutable index's plans carry
its live set in the result mask).  For every
:class:`~repro.plan.plan.QueryPlan` the cache builds a single fused
program — beam search + rerank + margin in one ``jit`` (per-query-bucket
shapes handled by jax's own shape caching, bounded by the bucket
ladder) — and every later request with the same plan reuses it.
Adaptive escalation is the *second stage of the same compiled plan*:
``plan.escalated()`` is just another plan in the cache, precompiled by
:meth:`warmup`, so the tight-margin re-run dispatches a cached
executable.

Trace accounting rides ``repro.plan.trace``: each program is a
``counting_jit`` under this cache's prefix, so
``report()["retraces"]`` is exactly "trace events beyond the first per
(plan, bucket)" — the number the serve benchmark pins to zero in
steady state.

Observability (DESIGN.md §12, §15): with a ``tracer`` attached (the
serve engine attaches its own) ``launch`` times its ``encode`` and
``dispatch`` stages and ``finalize`` its ``escalate`` stage; every
finalize moves the graph programs' per-query nav counters to the host
with the results and adds them to plain loop counters
(``report()``); :meth:`op_scopes` maps a compiled program's device ops
to the ``quiver.*`` named scopes of the beam and rerank.
"""

from __future__ import annotations

import contextlib
import itertools
import re

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.beam import (
    batch_bucket,
    batched_beam_search,
    beam_margin,
    pad_rows,
)
from repro.plan import trace
from repro.plan.plan import PlanContext, QueryPlan

_CACHE_IDS = itertools.count()

# navigation-path trace statistics (DESIGN.md §15): column order of the
# (Q, 5) nav array the graph programs return, with the fixed histogram
# buckets each lands in (windowless — hot-path observes stay vectorized)
NAV_STATS = (
    ("hops", (1, 2, 4, 8, 16, 32, 64, 128, 256)),
    ("evals", (32, 64, 128, 256, 512, 1024, 2048, 4096, 8192)),
    ("descent", (0.0, 1.0, 4.0, 16.0, 64.0, 256.0, 1024.0, 4096.0,
                 16384.0)),
    ("stalls", (0, 1, 2, 4, 8, 16, 32, 64)),
    ("entry_rank", (0, 1, 2, 4, 8, 16, 32, 64, 128)),
)
# per launched chunk, beside the per-query NAV_STATS: the vmapped
# while_loop's iterations (the chunk's largest hops, padded rows
# included) and the row-iterations its real rows spend waiting, done,
# for the chunk's slowest row (lockstep share = hops / (hops + wait))
LOOP_STATS = (
    ("loop_iterations", (1, 2, 4, 8, 16, 32, 64, 128, 256, 512)),
    ("wait_iterations", (0, 64, 256, 1024, 4096, 16384, 65536)),
)


def _nav_trace(res) -> jnp.ndarray:
    """Stack a batched BeamResult's per-query counters into the (Q, 5)
    nav-trace array (float32: one dtype, one transfer)."""
    return jnp.stack([
        res.hops.astype(jnp.float32),
        res.evals.astype(jnp.float32),
        res.descent.astype(jnp.float32),
        res.stalls.astype(jnp.float32),
        res.entry_rank.astype(jnp.float32),
    ], axis=-1)


def _normalize(x: jnp.ndarray) -> jnp.ndarray:
    return x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True), 1e-12)


def _abstract(x):
    """``ShapeDtypeStruct`` of one program argument leaf (enough to
    lower the program again without holding its data)."""
    aval = jax.typeof(x)
    return jax.ShapeDtypeStruct(aval.shape, aval.dtype,
                                weak_type=aval.weak_type,
                                sharding=getattr(x, "sharding", None))


_HLO_MODULE = re.compile(r"^HloModule ([^\s,]+)")
_HLO_OP = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = ")
_HLO_OPERAND = re.compile(r"%([\w.\-]+)")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_SCOPE = re.compile(r"(?:^|/)(quiver\.[\w.]+)")


def hlo_scopes(hlo_text: str) -> tuple[str, dict[str, str]]:
    """(module name, {instruction name: innermost ``quiver.*`` scope})
    of a compiled program's HLO text.

    An instruction's scope is the last ``quiver.*`` component of its
    ``op_name``.  Instructions the compiler adds with no ``op_name`` at
    all (a scatter's expansion into sort and update loops, layout
    copies) take the scope of their first scoped user in the same
    computation.  Parameters, and instructions with no scope either
    way, are left out.
    """
    lines = hlo_text.splitlines()
    m = _HLO_MODULE.match(lines[0]) if lines else None
    module = m.group(1) if m else ""
    ops: dict[str, str] = {}
    comp: list[tuple[str, str]] = []

    def close(comp):
        names = {n for n, _ in comp}
        users: dict[str, list[str]] = {}
        own: dict[str, str | None] = {}
        for name, line in comp:
            rhs = line.split(" = ", 1)[1]
            for operand in _HLO_OPERAND.findall(rhs):
                if operand in names:
                    users.setdefault(operand, []).append(name)
            op_name = _OP_NAME.search(line)
            if " parameter(" in rhs:
                own[name] = ""
            elif op_name is None:
                own[name] = None
            else:
                scopes = _SCOPE.findall(op_name.group(1))
                own[name] = scopes[-1] if scopes else ""
        # users come after their operands: walk backwards
        for name, _ in reversed(comp):
            scope = own[name]
            if scope is None:
                scope = next((ops[u] for u in users.get(name, ())
                              if u in ops), None)
            if scope:
                ops[name] = scope

    for line in lines[1:]:
        op = _HLO_OP.match(line)
        if op is not None:
            comp.append((op.group(1), line))
        elif line.startswith("}"):
            close(comp)
            comp = []
    close(comp)
    return module, ops


class PendingResult:
    """In-flight device results of one launched plan: per-chunk device
    arrays plus splice metadata.  ``PlanCache.finalize`` syncs them to
    host and runs the escalation stage if the plan asks for one.  The
    split exists so the serve engine can overlap the next batch's
    host→device transfer with this batch's compute (double buffering).
    """

    __slots__ = ("plan", "ctx", "queries", "reprs", "chunks", "nav")

    def __init__(self, plan, ctx, queries, reprs, chunks):
        self.plan = plan
        self.ctx = ctx
        self.queries = queries       # (Q, D) normalized, device
        self.reprs = reprs           # encoded queries, device
        self.chunks = chunks         # [(ids, scores, margins, nav, real)]
        # (Q, 5) host float32 nav-trace rows [hops, evals, descent,
        # stalls, entry_rank] — populated by finalize() when the plan
        # traverses the graph; None otherwise
        self.nav = None


class PlanCache:
    """Compiled-executable cache keyed by :class:`QueryPlan`."""

    def __init__(self, index):
        self._index = index
        self._programs: dict[QueryPlan, object] = {}
        # (plan, bucket) pairs that have executed at least once — the
        # closed set of compiled shapes; misses == first-time pairs —
        # with the abstract arguments of their first launch, so
        # op_scopes() can lower the program again without its data
        self._seen: dict[tuple[QueryPlan, int], tuple] = {}
        self._tag = f"plan[{next(_CACHE_IDS)}]:"
        self.hits = 0
        self.misses = 0
        self.executions = 0
        self.invalidated_plans = 0
        # (plan, bucket) shapes evicted by invalidate(): their historic
        # trace events stay in the counters, so retrace accounting
        # subtracts them — a post-replan recompile is intended work, not
        # an accounting anomaly
        self.invalidated_shapes = 0
        # graph-program loop counters, moved to the host at every
        # finalize (warm-up included): chunks, real query rows, the sums
        # of their hops and distance evaluations, the loop's iterations
        # (each chunk's largest hops: the vmapped while_loop runs until
        # its slowest row, padded rows included) and the row-iterations
        # real rows spend done, waiting for that slowest row
        self.nav_chunks = 0
        self.nav_rows = 0
        self.nav_hops = 0
        self.nav_evals = 0
        self.loop_iterations = 0
        self.wait_iterations = 0
        # telemetry hub (repro.obs.ObsHub, DESIGN.md §12): set by the
        # serve engine (or any owner) to land the per-query nav
        # histograms ``quiver_nav_*{nav,plan}`` and escalation counts in
        # ``quiver_escalated_queries_total{plan}``.  None: zero overhead.
        self.obs = None
        # stage tracer (repro.obs.Tracer): times encode / dispatch /
        # escalate when set (the serve engine attaches its own)
        self.tracer = None

    # -- program construction ---------------------------------------------

    def program(self, plan: QueryPlan):
        """The compiled program for ``plan`` (built exactly once)."""
        if plan not in self._programs:
            self._programs[plan] = self._build(plan)
        return self._programs[plan]

    def _nav_backend(self, nav: str):
        """The metric backend a plan's nav family scores with — the
        ivf family navigates coarse lists but scores candidates in
        plain bq2 space (the partition lives there)."""
        return self._index.backend("bq2" if nav == "ivf" else nav)

    def _build(self, plan: QueryPlan):
        if plan.route == "brute":
            raise ValueError("brute plans run through "
                             "filter.brute_force_topk, not a program")
        if plan.route == "ivf":
            return self._build_ivf(plan)
        # lazy: core.index imports this module at its own top level
        from repro.core.index import rerank

        # the metric backend is the program's first argument (a pytree
        # of the index's signatures), never a closure constant
        if plan.filtered:
            def program(backend, reprs, queries, adjacency, vectors, start,
                        result_valid):
                res = batched_beam_search(
                    reprs, adjacency, start, dist_fn=backend.dist_fn,
                    ef=plan.ef, expand=plan.expand,
                    result_valid=result_valid,
                )
                ids, scores = rerank(res.ids, res.dists, queries,
                                     vectors, plan.k)
                margins = beam_margin(res.dists, plan.k,
                                      backend.neutral_dist)
                return ids, scores, margins, _nav_trace(res)
        else:
            def program(backend, reprs, queries, adjacency, vectors, start):
                res = batched_beam_search(
                    reprs, adjacency, start, dist_fn=backend.dist_fn,
                    ef=plan.ef, expand=plan.expand,
                )
                ids, scores = rerank(res.ids, res.dists, queries,
                                     vectors, plan.k)
                margins = beam_margin(res.dists, plan.k,
                                      backend.neutral_dist)
                return ids, scores, margins, _nav_trace(res)

        return trace.counting_jit(
            program, name=self._tag + plan.signature()
        )

    def _build_ivf(self, plan: QueryPlan):
        """One fused ivf program: list scan -> top-p gather -> metric
        top-ef -> rerank -> margin.  ``cent_words``/``list_ids`` enter
        as program arguments (like ``adjacency`` on the graph route) so
        the executable never bakes index arrays in as constants."""
        index = self._index
        part = index.ivf
        if part is None:
            raise ValueError("ivf plan on an index without a partition")
        from repro.core.index import rerank
        from repro.ivf.search import scan_search
        from repro.kernels import dispatch

        scan = dispatch.list_scan_ops(
            index.sigs.dim,
            route=getattr(self._nav_backend(plan.nav), "route", None),
        ).scan
        # clamp to the partition, but never below the fan-in that can
        # fill k (degraded plans halve probes with floor 1)
        p_eff = max(min(plan.probes, part.n_lists),
                    min(part.n_lists, -(-plan.k // part.cap)))

        if plan.filtered:
            def program(backend, reprs, queries, cent_words, list_ids,
                        vectors, result_valid):
                ids, dists = scan_search(
                    backend, scan, reprs, cent_words, list_ids,
                    probes=p_eff, ef=plan.ef, result_valid=result_valid,
                )
                out_ids, scores = rerank(ids, dists, queries, vectors,
                                         plan.k)
                margins = beam_margin(dists, plan.k, backend.neutral_dist)
                return out_ids, scores, margins
        else:
            def program(backend, reprs, queries, cent_words, list_ids,
                        vectors):
                ids, dists = scan_search(
                    backend, scan, reprs, cent_words, list_ids,
                    probes=p_eff, ef=plan.ef,
                )
                out_ids, scores = rerank(ids, dists, queries, vectors,
                                         plan.k)
                margins = beam_margin(dists, plan.k, backend.neutral_dist)
                return out_ids, scores, margins

        return trace.counting_jit(
            program, name=self._tag + plan.signature()
        )

    # -- query encoding ----------------------------------------------------

    def encode(self, plan: QueryPlan, queries: jnp.ndarray) -> jnp.ndarray:
        """Normalized float32 queries -> the plan's beam representation
        (rotation applied for signature-space navigation)."""
        index = self._index
        backend = self._nav_backend(plan.nav)
        enc_in = queries
        if index.rotation is not None and backend.kind != "float32":
            enc_in = queries @ index.rotation
        return backend.encode_queries(enc_in)

    # -- execution ---------------------------------------------------------

    def launch(
        self,
        plan: QueryPlan,
        ctx: PlanContext,
        queries: jnp.ndarray,
        *,
        record: bool = True,
    ) -> PendingResult:
        """Dispatch ``queries`` through ``plan`` without waiting.

        Queries are normalized here; chunks follow the bucket ladder
        (``batch_bucket``) so tail and singleton batches land on the
        small closed set of padded shapes.  Returns device-side results
        (jax async dispatch: compute proceeds while the host goes on to
        stage the next batch).
        """
        with self._span("encode"):
            queries = _normalize(jnp.asarray(queries, dtype=jnp.float32))
            if queries.ndim == 1:
                queries = queries[None]
            if plan.route == "brute":
                return PendingResult(plan, ctx, queries, None, None)
            reprs = self.encode(plan, queries)
        index = self._index
        prog = self.program(plan)
        backend = self._nav_backend(plan.nav)
        vectors = index.vectors if plan.rerank else None
        chunks = []
        with self._span("dispatch"):
            start = jnp.int32(ctx.start)
            for s in range(0, queries.shape[0], plan.query_batch):
                rep = reprs[s:s + plan.query_batch]
                q = queries[s:s + plan.query_batch]
                real = rep.shape[0]
                bucket = batch_bucket(real, plan.query_batch)
                key = (plan, bucket)
                if record:
                    self.executions += 1
                    if key in self._seen:
                        self.hits += 1
                    else:
                        self.misses += 1
                if plan.route == "ivf":
                    args = (backend, pad_rows(rep, bucket),
                            pad_rows(q, bucket), index.ivf.cent_words,
                            index.ivf.list_ids, vectors)
                else:
                    args = (backend, pad_rows(rep, bucket),
                            pad_rows(q, bucket), index.adjacency, vectors,
                            start)
                if plan.filtered:
                    args += (ctx.result_valid,)
                if key not in self._seen:
                    self._seen[key] = jax.tree.map(_abstract, args)
                out = prog(*args)
                # graph programs return a 4th (nav-trace) array; the ivf
                # route has no traversal to trace
                nav = out[3] if len(out) > 3 else None
                chunks.append((out[0], out[1], out[2], nav, real))
        return PendingResult(plan, ctx, queries, reprs, chunks)

    def _span(self, name: str, **attrs):
        if self.tracer is None:
            return contextlib.nullcontext()
        return self.tracer.span(name, **attrs)

    def op_scopes(self, plan: QueryPlan,
                  bucket: int) -> tuple[str, dict[str, str]]:
        """Where each device op of ``plan``'s program at ``bucket`` rows
        comes from: the compiled program's HLO module name (the name a
        profiler gives its device events) and a map from each HLO
        instruction name to its innermost ``quiver.*`` named scope
        (``quiver.beam.pick``, ``quiver.rerank``, ...).

        Lowers and compiles the program again from the abstract
        arguments of its first launch (a compile-cache hit where a
        persistent cache is on; no trace is counted); raises
        ``KeyError`` for a (plan, bucket) never launched."""
        args = self._seen[(plan, bucket)]
        return hlo_scopes(self.program(plan).lower(*args).compile().as_text())

    def finalize(
        self, pending: PendingResult
    ) -> tuple[np.ndarray, np.ndarray]:
        """Sync a launched plan to host and run its second (escalation)
        stage where margins demand one."""
        plan, ctx = pending.plan, pending.ctx
        obs = self.obs
        if plan.route == "brute":
            return self._run_brute(plan, ctx, pending.queries)
        out_ids, out_scores, out_margin, out_nav = [], [], [], []
        loop = []
        for chunk in pending.chunks:
            # one host transfer of everything the chunk returned
            ids, scores, margins, nav = jax.device_get(chunk[:4])
            real = chunk[4]
            out_ids.append(ids[:real])
            out_scores.append(scores[:real])
            out_margin.append(margins[:real])
            if nav is not None:
                loop.append(self._count_loop(nav, real))
                out_nav.append(nav[:real])
        all_ids = np.concatenate(out_ids)
        all_scores = np.concatenate(out_scores)
        if out_nav:
            pending.nav = np.concatenate(out_nav)
        if obs is not None and out_nav:
            # nav-path histograms (DESIGN.md §15) only with a hub
            labels = dict(nav=plan.nav, plan=plan.signature())
            for col, (stat, buckets) in enumerate(NAV_STATS):
                obs.registry.histogram(
                    f"quiver_nav_{stat}",
                    f"per-query beam {stat} by nav family and plan",
                    labels=("nav", "plan"), buckets=buckets, window=0,
                ).observe_many(pending.nav[:, col], **labels)
            for col, (stat, buckets) in enumerate(LOOP_STATS):
                obs.registry.histogram(
                    f"quiver_nav_{stat}",
                    f"per-chunk beam {stat} by nav family and plan",
                    labels=("nav", "plan"), buckets=buckets, window=0,
                ).observe_many(np.asarray(loop, np.float64)[:, col],
                               **labels)
        if plan.adaptive:
            margins = np.concatenate(out_margin)
            esc = np.nonzero(margins < plan.escalate_margin)[0]
            if esc.size:
                take = jnp.asarray(esc.astype(np.int32))
                if obs is not None:
                    obs.registry.counter(
                        "quiver_escalated_queries_total",
                        "tight-margin queries re-run at the escalated "
                        "stage", labels=("plan",),
                    ).inc(int(esc.size), plan=plan.signature())
                with self._span("escalate", plan=plan.signature(),
                                queries=int(esc.size)):
                    esc_ids, esc_scores = self.finalize(self.launch(
                        plan.escalated(), ctx, pending.queries[take]
                    ))
                all_ids[esc] = esc_ids
                all_scores[esc] = esc_scores
        return all_ids, all_scores

    def _count_loop(self, nav: np.ndarray, real: int) -> tuple[int, int]:
        """Add one chunk's (bucket, 5) host nav rows to the loop
        counters; returns its LOOP_STATS (iterations, wait)."""
        hops = nav[:, 0].astype(np.int64)
        top = int(hops.max()) if hops.size else 0
        real_hops = int(hops[:real].sum())
        self.nav_chunks += 1
        self.nav_rows += real
        self.nav_hops += real_hops
        self.nav_evals += int(nav[:real, 1].astype(np.int64).sum())
        self.loop_iterations += top
        self.wait_iterations += real * top - real_hops
        return top, real * top - real_hops

    def run(
        self, plan: QueryPlan, ctx: PlanContext, queries
    ) -> tuple[np.ndarray, np.ndarray]:
        """launch + finalize: the synchronous per-call entry
        (``QuIVerIndex.search`` lowers to exactly this)."""
        return self.finalize(self.launch(plan, ctx, queries))

    def _run_brute(self, plan, ctx, queries):
        # exact top-k over the materialized match set; already a
        # shape-bounded jit (match lists pad to powers of two)
        from repro.filter.search import brute_force_topk

        index = self._index
        if plan.rerank:
            return brute_force_topk(
                queries, ctx.match_ids, plan.k, vectors=index.vectors
            )
        backend = self._nav_backend(plan.nav)
        return brute_force_topk(
            queries, ctx.match_ids, plan.k, vectors=None,
            backend=backend, reprs=self.encode(plan, queries),
        )

    # -- invalidation ------------------------------------------------------

    def invalidate(self, *, nav: str) -> int:
        """Evict every compiled program and shape record whose plan
        navigates in ``nav``; returns the number of plans evicted.

        This is the surgical half of :meth:`QuIVerIndex.replan`: when
        remediation swaps the default nav policy, only the plans of the
        *abandoned* family are dropped — every other plan (forced-nav
        traffic, other k/ef shapes) keeps its program object, so their
        steady-state serve sees zero retraces.  Evicted plans recompile
        on next use (counted as misses, compensated out of the retrace
        audit).
        """
        victims = {p for p in self._programs if p.nav == nav}
        victims |= {p for p, _ in self._seen if p.nav == nav}
        for p in victims:
            self._programs.pop(p, None)
        evicted = [pb for pb in self._seen if pb[0].nav == nav]
        for pb in evicted:
            del self._seen[pb]
        self.invalidated_shapes += len(evicted)
        self.invalidated_plans += len(victims)
        return len(victims)

    # -- warmup & accounting ----------------------------------------------

    def warmup(
        self,
        plan: QueryPlan,
        ctx: PlanContext | None = None,
        *,
        buckets: tuple[int, ...] = (8,),
        with_escalation: bool = True,
    ) -> int:
        """Precompile ``plan`` (and its escalation stage) for the given
        query buckets; returns how many programs were exercised.
        Warmup traffic is excluded from hit/miss stats."""
        if plan.route == "brute":
            return 0
        if ctx is None:
            ctx = PlanContext(start=int(self._index.medoid))
            if plan.filtered:
                n = self._index.sigs.words.shape[0]
                ctx.result_valid = jnp.ones((n,), dtype=jnp.bool_)
        dim = self._index.sigs.dim
        ran = 0
        stages = [plan]
        if with_escalation and plan.adaptive:
            stages.append(plan.escalated())
        for stage in stages:
            for b in buckets:
                q = jnp.zeros((min(b, stage.query_batch), dim),
                              dtype=jnp.float32)
                self.finalize(self.launch(stage, ctx, q, record=False))
                ran += 1
        return ran

    def report(self) -> dict:
        """``memory_breakdown``-style serving-compilation report."""
        tr = trace.trace_report(self._tag)
        lookups = self.hits + self.misses
        return {
            "plans_compiled": len(self._programs),
            "plan_shapes": len(self._seen),
            "executions": self.executions,
            "hits": self.hits,
            "misses": self.misses,
            "hit_rate": self.hits / lookups if lookups else 1.0,
            "invalidated_plans": self.invalidated_plans,
            "trace_events": tr["total_traces"],
            "retraces": (tr["total_traces"] - len(self._seen)
                         - self.invalidated_shapes),
            "nav_chunks": self.nav_chunks,
            "nav_rows": self.nav_rows,
            "nav_hops": self.nav_hops,
            "nav_evals": self.nav_evals,
            "loop_iterations": self.loop_iterations,
            "wait_iterations": self.wait_iterations,
        }

    def trace_prefix(self) -> str:
        """This cache's trace-counter namespace (for snapshots)."""
        return self._tag
