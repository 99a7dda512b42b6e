"""resolve_plan — the one owner of the serve-time decisions.

Every search call, on a built or a live (mutable) index, makes four
decisions:

1. the **nav ladder** — which metric rung and ef/rerank schedule the
   index's :class:`~repro.probe.NavPolicy` prescribes;
2. the **filter route** — widened-ef graph traversal vs exact brute
   force over the match set, from the predicate's estimated
   selectivity (``filter/search.py``);
3. the **entry point** — the global medoid or a live per-label entry;
4. the **escalation schedule** — whether tight-margin queries re-run
   with a wider beam (``PlanCache.finalize``'s ``plan.escalated()``
   stage).

:func:`resolve_plan` makes them one decision with one output: a frozen
:class:`~repro.plan.plan.QueryPlan` (everything jit-static) plus a
:class:`~repro.plan.plan.PlanContext` (the dynamic arrays — entry
point, result mask, brute match set).  The routing *policies* stay
where they live (``resolve_schedule``, ``route``/``widened_ef``/
``entry_label``) — this module only owns their composition.

A mutable index answers one more question, its live set (``live``, a
host tombstone mask; a built index has none): selectivity and the
widening count live rows, the brute match set and the label entry
leave tombstones out, and the result mask is ``live ∧ predicate`` (or
``live`` alone), so its plans are masked programs of the same cache.

Selectivity enters the plan only through ``widened_ef``'s quantized
widening multiple, so predicate drift moves the plan key in bounded
steps (a "selectivity band"), not per-popcount.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.filter import (
    DEFAULT_SELECTIVITY_FLOOR,
    entry_label,
    estimate_selectivity,
    route,
    validate,
    widened_ef,
)
from repro.obs.metrics import get_default_registry
from repro.plan.plan import PlanContext, QueryPlan
from repro.probe import resolve_schedule


def _note_resolution(plan: QueryPlan, selectivity: float | None) -> None:
    """Route-decision telemetry (DESIGN.md §12): every resolution lands
    in the process registry so fleet dashboards see the filter-route
    mix and the selectivity distribution driving it."""
    reg = get_default_registry()
    reg.counter(
        "quiver_plan_resolutions_total",
        "resolve_plan outcomes by route",
        labels=("route", "filtered", "nav"),
    ).inc(route=plan.route, filtered=str(plan.filtered).lower(),
          nav=plan.nav)
    if selectivity is not None:
        reg.histogram(
            "quiver_filter_selectivity",
            "match fraction of filtered requests",
            buckets=(0.001, 0.005, 0.01, 0.05, 0.1, 0.25, 0.5, 1.0),
            window=0,
        ).observe(selectivity)


def resolve_plan(
    index,
    *,
    k: int = 10,
    ef: int = 64,
    rerank: bool = True,
    nav: str | None = None,
    expand: int = 1,
    query_batch: int = 256,
    filter=None,
    selectivity_floor: float = DEFAULT_SELECTIVITY_FLOOR,
    adaptive: bool | None = None,
    probes: int | None = None,
) -> tuple[QueryPlan, PlanContext]:
    """Resolve one search call to (plan, context) for ``index``.

    ``index`` is a :class:`~repro.core.index.QuIVerIndex` or a
    :class:`~repro.stream.MutableQuIVerIndex`: ``sigs``, ``medoid``,
    ``vectors``, ``labels``, ``policy``, ``default_nav`` and, on a
    mutable index, ``live``.  Same (policy, filter selectivity band,
    ef, k, nav, expand, probes) in → equal (hash-identical) plan out:
    the PlanCache key.

    ``kind`` defaults to the index's ``default_nav``: a built index's
    :class:`NavPolicy` may prescribe a navigation *family* the graph
    was not built in (``nav="ivf"`` navigates coarse lists over a
    bq2-built index); a live index navigates in its build metric.
    ``probes`` is the ivf route's list fan-in (default: the
    partition's √L).
    """
    # host (n,) bool tombstone mask of a mutable index; None: all live
    live = getattr(index, "live", None)
    n = index.sigs.words.shape[0] if live is None else int(live.sum())
    policy = getattr(index, "policy", None)
    ef, adaptive, sched = resolve_schedule(policy, nav, ef, adaptive)
    kind = nav or index.default_nav
    do_rerank = rerank and index.vectors is not None

    part = None
    if kind == "ivf":
        part = getattr(index, "ivf", None)
        if part is None:
            raise ValueError(
                "nav='ivf' needs a coarse partition: build with "
                "BuildParams(ivf_candidates=True) or call build_ivf()"
            )
        probes = probes or part.default_probes
        # enough lists to fill k even if every probed list is sparse
        probes = max(min(probes, part.n_lists),
                     min(part.n_lists, -(-k // part.cap)))
        expand = 1                  # no traversal: expansion is meaningless

    ctx = PlanContext(start=max(int(index.medoid), 0))
    filtered = False
    ef_run = ef
    if filter is not None:
        if index.labels is None:
            raise ValueError(
                "filtered search needs labels: attach_labels() first"
            )
        expr = validate(filter, index.labels.n_labels)
        count_fn = index.labels.count_fn()
        sel = estimate_selectivity(expr, count_fn, n)
        mask = index.labels.mask(expr)
        if route(sel, selectivity_floor) == "brute":
            # the popcount estimate is a bound, not a measurement
            # (Not() of a union bound can underestimate badly); verify
            # with the exact mask popcount before committing to
            # materializing the match set
            hits = np.asarray(mask)
            if live is not None:
                hits = hits & live
            match = np.nonzero(hits)[0]
            sel = len(match) / max(n, 1)
            if route(sel, selectivity_floor) == "brute":
                ctx.match_ids = match.astype(np.int32)
                ctx.selectivity = sel
                plan = QueryPlan(
                    nav=kind, k=k, ef=max(ef, k), expand=expand,
                    rerank=do_rerank, route="brute",
                    query_batch=query_batch,
                )
                _note_resolution(plan, sel)
                return plan, ctx
        filtered = True
        ctx.result_valid = mask
        ctx.selectivity = sel
        ef_run = widened_ef(ef, sel, selectivity_floor, n)
        if part is not None and ef_run > ef:
            # the ivf route widens its list fan-in by the same
            # quantized multiple the graph route widens its beam: the
            # predicate thins every probed list uniformly in expectation
            probes = min(part.n_lists, -(-(probes * ef_run) // ef))
        lbl = entry_label(expr, count_fn)
        ent = int(index.labels.entries[lbl]) if lbl is not None else -1
        if ent >= 0 and (live is None or live[ent]):
            ctx.start = ent
    if live is not None:
        # tombstones route but never surface: the beam only ever reads
        # the conjunction of its masks, so one mask carries both
        live_dev = jnp.asarray(live)
        ctx.result_valid = (live_dev if ctx.result_valid is None
                            else ctx.result_valid & live_dev)
        filtered = True

    plan = QueryPlan(
        nav=kind, k=k, ef=ef_run, expand=expand, rerank=do_rerank,
        route="ivf" if kind == "ivf" else "graph",
        filtered=filtered, adaptive=adaptive,
        escalate_margin=sched.escalate_margin,
        escalate_mult=sched.escalate_mult, query_batch=query_batch,
        probes=probes if kind == "ivf" else 0,
    )
    _note_resolution(plan, ctx.selectivity)
    return plan, ctx
