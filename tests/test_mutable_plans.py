"""A mutable index searches through the plan layer (DESIGN.md §8, §11):
``resolve_plan`` folds its live set into the plan's result mask and its
own ``PlanCache`` runs the plan, so churn never retraces, escalation is
the cache's second stage, both filter routes agree with ``freeze()``,
and tombstones never reach a match set, an entry point or a result."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.index import QuIVerIndex
from repro.core.vamana import BuildParams
from repro.data.datasets import make_dataset
from repro.filter import Not
from repro.plan import resolve_plan, trace
from repro.serve.engine import QueryEngine
from repro.stream import MutableQuIVerIndex

jax.config.update("jax_platform_name", "cpu")

PARAMS = BuildParams(m=6, ef_construction=32, prune_pool=32, chunk=128)
N = 800


@functools.lru_cache(maxsize=1)
def _data():
    base, queries = make_dataset("minilm-surrogate", n=N + 100, queries=12)
    return base, np.asarray(queries, np.float32)


@functools.lru_cache(maxsize=1)
def _labeled():
    """Built index over the first N rows: label 0 on ~98 % of them
    (its complement is rare), label 1 on ~1 % (brute-routed)."""
    base, _ = _data()
    rng = np.random.default_rng(0)
    member = np.stack(
        [rng.random(N) < p for p in (0.98, 0.01)], axis=1
    )
    idx = QuIVerIndex.build(jnp.asarray(base[:N]), PARAMS)
    idx.attach_labels(
        [np.nonzero(m)[0].tolist() for m in member], n_labels=2
    )
    idx.build_label_entries(min_count=32)
    return idx, member


def test_churned_index_serves_through_plan_cache_without_retrace():
    base, queries = _data()
    idx, _ = _labeled()
    mut = MutableQuIVerIndex.from_index(idx)
    q = jnp.asarray(queries)
    mut.search(q, k=10, ef=48)
    dead = np.arange(0, 60)
    with trace.assert_no_retrace(mut.plans.trace_prefix(),
                                 "search after churn"):
        new = mut.insert(jnp.asarray(base[N:]))
        mut.delete(dead)
        ids, _ = mut.search(q, k=10, ef=48)
    assert not np.isin(ids, dead).any()
    assert mut.live[ids[ids >= 0]].all()
    rep = mut.plans.report()
    assert rep["retraces"] == 0 and rep["plans_compiled"] == 1
    assert rep["hits"] >= 1
    # the inserted rows are searchable (and are their own neighbours)
    own, _ = mut.search(jnp.asarray(base[N:N + 8]), k=1, ef=48)
    assert np.isin(own.ravel(), new).mean() > 0.9
    # replan evicts the old nav's plans; the new nav compiles its own
    mut.replan(nav="float32")
    assert mut.plans.report()["invalidated_plans"] == 2
    ids, _ = mut.search(q, k=10, ef=48)
    assert mut.live[ids[ids >= 0]].all()
    rep = mut.plans.report()
    assert rep["retraces"] == 0 and rep["plans_compiled"] == 1


def test_adaptive_escalation_runs_in_the_plan_cache():
    base, queries = make_dataset("glove-like", n=1200, queries=20)
    idx = QuIVerIndex.build(base, PARAMS, metric="auto", probe_sample=512)
    # glove-like probes green at this size: give the index the adaptive
    # schedule an amber verdict brings, with a margin its tail reaches
    idx.policy = dataclasses.replace(idx.policy, ef_scale=2, adaptive=True,
                                     escalate_margin=0.25)
    mut = MutableQuIVerIndex.from_index(idx)
    assert mut.policy == idx.policy
    ids_f, sc_f = idx.search(queries, k=10, ef=32)
    ids_m, sc_m = mut.search(queries, k=10, ef=32)
    np.testing.assert_array_equal(ids_m, ids_f)
    np.testing.assert_array_equal(sc_m, sc_f)
    # one base chunk plus the escalated stage's launch, in both caches
    assert mut.plans.report()["executions"] == 2
    assert idx.plans.report()["executions"] == 2
    plan, _ = resolve_plan(mut, k=10, ef=32)
    assert plan.adaptive and plan.ef == 64
    assert plan.escalated() in mut.plans._programs


@pytest.mark.parametrize("flt,route", [(0, "graph"), (1, "brute")])
def test_filter_routes_match_freeze_at_zero_churn(flt, route):
    _, queries = _data()
    idx, _ = _labeled()
    mut = MutableQuIVerIndex.from_index(idx)
    frozen = mut.freeze()
    plan_m, ctx_m = resolve_plan(mut, k=10, ef=48, filter=flt)
    plan_f, ctx_f = resolve_plan(frozen, k=10, ef=48, filter=flt)
    assert plan_m == plan_f and plan_m.route == route
    assert ctx_m.start == ctx_f.start
    q = jnp.asarray(queries)
    ids_m, sc_m = mut.search(q, k=10, ef=48, filter=flt)
    ids_f, sc_f = frozen.search(q, k=10, ef=48, filter=flt)
    np.testing.assert_array_equal(ids_m, ids_f)
    np.testing.assert_array_equal(sc_m, sc_f)


def test_tombstones_leave_match_set_entry_point_and_results():
    _, queries = _data()
    idx, member = _labeled()
    mut = MutableQuIVerIndex.from_index(idx)
    entry = int(mut.labels.entries[0])
    assert entry >= 0 and entry != mut.medoid
    rare = np.nonzero(~member[:, 0])[0]        # rows outside label 0
    dead = np.unique(np.r_[entry, rare[: len(rare) // 2],
                           np.arange(0, 40)])
    dead = dead[dead != mut.medoid]
    mut.delete(dead)

    # Not(0) matches every dead and never-allocated slot: only the
    # live conjunction keeps it rare enough for the brute route
    plan, ctx = resolve_plan(mut, k=10, ef=48, filter=Not(0))
    assert plan.route == "brute"
    assert mut.live[ctx.match_ids].all()
    assert not np.isin(ctx.match_ids, dead).any()
    ids, _ = mut.search(jnp.asarray(queries), k=10, ef=48, filter=Not(0))
    assert np.isin(ids[ids >= 0], ctx.match_ids).all()

    # a dead label entry falls back to the medoid
    plan, ctx = resolve_plan(mut, k=10, ef=48, filter=0)
    assert plan.route == "graph" and ctx.start == mut.medoid

    # unfiltered: the result mask is the live set itself
    plan, ctx = resolve_plan(mut, k=10, ef=48)
    assert plan.filtered
    np.testing.assert_array_equal(np.asarray(ctx.result_valid), mut.live)
    ids, _ = mut.search(jnp.asarray(queries), k=10, ef=48)
    assert mut.live[ids[ids >= 0]].all()


def test_query_engine_serves_a_mutable_index():
    _, queries = _data()
    idx, _ = _labeled()
    mut = MutableQuIVerIndex.from_index(idx)
    mut.delete(np.arange(0, 40))
    engine = QueryEngine(mut, default_k=5, default_ef=32)
    ids_e, sc_e = engine.search(queries[:6])
    ids_d, sc_d = mut.search(jnp.asarray(queries[:6]), k=5, ef=32)
    np.testing.assert_array_equal(ids_e, ids_d)
    np.testing.assert_array_equal(sc_e, sc_d)
    assert mut.live[ids_e[ids_e >= 0]].all()
