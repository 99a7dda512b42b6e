"""Compiled query-plan tests (DESIGN.md §11): QueryPlan key semantics,
PlanCache compile-once identity, derived escalation/degradation stages,
steady-state zero-retrace, and plan stability across save/load/freeze."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.index import QuIVerIndex
from repro.core.vamana import BuildParams
from repro.data.datasets import make_dataset
from repro.plan import QueryPlan, resolve_plan, trace
from repro.plan.plan import PlanContext
from repro.stream import MutableQuIVerIndex

jax.config.update("jax_platform_name", "cpu")

PARAMS = BuildParams(m=6, ef_construction=32, prune_pool=32, chunk=128)


@functools.lru_cache(maxsize=1)
def _index():
    base, queries = make_dataset("minilm-surrogate", n=800, queries=12)
    idx = QuIVerIndex.build(jnp.asarray(base), PARAMS)
    rng = np.random.default_rng(0)
    member = np.stack(
        [rng.random(len(base)) < p for p in (0.5, 0.01)], axis=1
    )
    idx.attach_labels(
        [np.nonzero(m)[0].tolist() for m in member], n_labels=2
    )
    idx.build_label_entries(min_count=32)
    return idx, np.asarray(queries, np.float32)


# -- plan key semantics -----------------------------------------------------


def test_plan_equality_hash_roundtrip():
    a = QueryPlan(nav="bq2", k=10, ef=64)
    b = QueryPlan(nav="bq2", k=10, ef=64)
    assert a == b and hash(a) == hash(b)
    assert {a: "prog"}[b] == "prog"
    assert a != QueryPlan(nav="bq2", k=10, ef=128)
    assert a != QueryPlan(nav="adc", k=10, ef=64)
    assert a.signature() == b.signature()
    assert a.signature() != QueryPlan(nav="bq2", k=10, ef=128).signature()


def test_plan_validation():
    with pytest.raises(ValueError):
        QueryPlan(nav="bq2", k=10, ef=64, route="teleport")
    with pytest.raises(ValueError):
        QueryPlan(nav="bq2", k=10, ef=4)           # graph needs ef >= k
    with pytest.raises(ValueError):
        QueryPlan(nav="bq2", k=10, ef=64, expand=65)
    # brute plans don't constrain ef
    QueryPlan(nav="bq2", k=10, ef=4, route="brute")


def test_derived_stages_closed_set():
    p = QueryPlan(nav="bq2", k=10, ef=64, adaptive=True, escalate_mult=4)
    esc = p.escalated()
    assert esc.ef == 256 and not esc.adaptive
    assert esc == p.escalated()                    # derived plans re-key
    ladder = [p]
    while ladder[-1].can_degrade():
        ladder.append(ladder[-1].degraded())
    assert [q.ef for q in ladder] == [64, 32, 16]
    assert ladder[-1].ef >= ladder[-1].min_ef
    assert not ladder[-1].can_degrade()
    assert ladder[-1].degraded() == ladder[-1]     # floor is a fixpoint
    brute = QueryPlan(nav="bq2", k=10, ef=64, route="brute")
    assert not brute.can_degrade()                 # exact: nothing to give


# -- resolve + cache identity -----------------------------------------------


def test_same_config_same_cached_executable():
    idx, _ = _index()
    p1, _ = resolve_plan(idx, k=10, ef=64)
    p2, _ = resolve_plan(idx, k=10, ef=64)
    assert p1 == p2
    assert idx.plans.program(p1) is idx.plans.program(p2)
    p3, _ = resolve_plan(idx, k=10, ef=48)
    assert idx.plans.program(p3) is not idx.plans.program(p1)


def test_same_selectivity_band_same_plan():
    idx, _ = _index()
    # label 0 (selectivity ~0.5, graph route): two resolutions land on
    # the same quantized widening -> hash-identical plan
    pa, ca = resolve_plan(idx, k=10, ef=64, filter=0)
    pb, cb = resolve_plan(idx, k=10, ef=64, filter=0)
    assert pa.route == "graph" and pa.filtered
    assert pa == pb and hash(pa) == hash(pb)
    assert idx.plans.program(pa) is idx.plans.program(pb)
    assert ca.start == cb.start
    # label 1 (selectivity ~0.01): routes to brute with the exact
    # match set materialized in the context
    pc, cc = resolve_plan(idx, k=10, ef=64, filter=1)
    assert pc.route == "brute" and not pc.filtered
    assert cc.match_ids is not None and len(cc.match_ids) > 0
    assert cc.selectivity < 0.05


def test_search_lowers_to_plan_run():
    idx, queries = _index()
    ids_a, sc_a = idx.search(jnp.asarray(queries), k=10, ef=48)
    plan, ctx = resolve_plan(idx, k=10, ef=48)
    ids_b, sc_b = idx.plans.run(plan, ctx, jnp.asarray(queries))
    np.testing.assert_array_equal(np.asarray(ids_a), ids_b)
    np.testing.assert_allclose(np.asarray(sc_a), sc_b, rtol=1e-6)


# -- steady-state retraces --------------------------------------------------


def test_steady_state_zero_retraces():
    idx, queries = _index()
    plan, ctx = resolve_plan(idx, k=10, ef=64)
    idx.plans.warmup(plan, buckets=(8, 32))
    misses_before = idx.plans.misses
    # warmed shapes: repeated traffic at any size inside the warmed
    # buckets must never re-lower (and never count as a cache miss —
    # warmup itself is excluded from the hit/miss stats)
    with trace.assert_no_retrace(idx.plans.trace_prefix(),
                                 "steady-state search"):
        for nq in (1, 3, 8, 12, 5, 1, 12):
            idx.plans.run(plan, ctx, jnp.asarray(queries[:nq]))
    assert idx.plans.report()["retraces"] == 0
    assert idx.plans.misses == misses_before


def test_warmup_compiles_escalation_stage():
    idx, queries = _index()
    plan, ctx = resolve_plan(idx, k=10, ef=16, adaptive=True)
    assert plan.adaptive
    idx.plans.warmup(plan, buckets=(8, 32))
    assert plan.escalated() in idx.plans._programs
    with trace.assert_no_retrace(idx.plans.trace_prefix(),
                                 "adaptive two-stage search"):
        idx.plans.run(plan, ctx, jnp.asarray(queries))


# -- persistence ------------------------------------------------------------


def test_plan_stable_across_save_load_freeze(tmp_path):
    idx, _ = _index()
    plan, ctx = resolve_plan(idx, k=10, ef=64, filter=0)

    path = str(tmp_path / "planned.npz")
    idx.save(path)
    loaded = QuIVerIndex.load(path)
    plan_l, ctx_l = resolve_plan(loaded, k=10, ef=64, filter=0)
    assert plan_l == plan and hash(plan_l) == hash(plan)
    assert ctx_l.start == ctx.start

    frozen = MutableQuIVerIndex.from_index(idx).freeze()
    plan_f, ctx_f = resolve_plan(frozen, k=10, ef=64, filter=0)
    assert plan_f == plan
    assert ctx_f.start == ctx.start
    # each index owns its own cache (compiled executables never
    # persist; plans re-derive and recompile on first use)
    assert loaded.plans is not idx.plans
    ids_a, _ = idx.plans.run(plan, ctx, jnp.zeros((2, idx.sigs.dim)))
    ids_b, _ = loaded.plans.run(plan_l, ctx_l,
                                jnp.zeros((2, idx.sigs.dim)))
    np.testing.assert_array_equal(ids_a, ids_b)


def _served(engine, queries, sizes):
    for nq in sizes:
        t = engine.submit(queries[:nq])
        engine.pump()
        engine.result(t)


def test_loop_counters_agree_with_the_nav_histograms():
    from repro.obs import MetricsRegistry, ObsHub
    from repro.serve.engine import QueryEngine

    idx, queries = _index()
    hub = ObsHub(registry=MetricsRegistry())
    engine = QueryEngine(idx, default_k=5, default_ef=32, obs=hub)
    before = idx.plans.report()
    _served(engine, queries, (12, 3, 1))
    after = idx.plans.report()
    d = {k: after[k] - before[k] for k in (
        "nav_chunks", "nav_rows", "nav_hops", "nav_evals",
        "loop_iterations", "wait_iterations")}
    snap = hub.registry.snapshot()

    def total(name):
        series = snap[f"quiver_nav_{name}"].values()
        return (sum(s["count"] for s in series),
                sum(s["sum"] for s in series))

    assert d["nav_chunks"] == 3 and d["nav_rows"] == 16
    assert total("hops") == (d["nav_rows"], d["nav_hops"])
    assert total("evals") == (d["nav_rows"], d["nav_evals"])
    assert total("loop_iterations") == (d["nav_chunks"],
                                        d["loop_iterations"])
    assert total("wait_iterations") == (d["nav_chunks"],
                                        d["wait_iterations"])
    # a chunk's rows never out-hop its loop
    assert 0 <= d["wait_iterations"] < 16 * d["loop_iterations"]


def test_loop_iterations_are_the_chunks_largest_hops(monkeypatch):
    idx, queries = _index()
    plans = idx.plans
    monkeypatch.setattr(plans, "obs", None)    # no hub: counters only
    plan, ctx = resolve_plan(idx, k=5, ef=32)
    c0 = plans.report()
    pending = plans.launch(plan, ctx, jnp.asarray(queries[:5]))
    plans.finalize(pending)
    c1 = plans.report()
    # the nav array moves to the host without a hub
    assert pending.nav.shape == (5, 5)
    hops = pending.nav[:, 0]
    # five rows pad to the 8-row bucket by repeating the last row, so
    # the loop's iterations are the real rows' largest hops
    assert c1["loop_iterations"] - c0["loop_iterations"] == hops.max()
    assert (c1["wait_iterations"] - c0["wait_iterations"]
            == 5 * hops.max() - hops.sum())
    assert c1["nav_hops"] - c0["nav_hops"] == hops.sum()
    assert c1["nav_evals"] - c0["nav_evals"] == pending.nav[:, 1].sum()


def test_op_scopes_name_the_beam_and_rerank_parts():
    idx, queries = _index()
    plan, ctx = resolve_plan(idx, k=5, ef=32)
    idx.plans.run(plan, ctx, jnp.asarray(queries[:3]))
    snap = trace.snapshot(idx.plans.trace_prefix())
    module, ops = idx.plans.op_scopes(plan, 8)
    assert snap.delta() == 0                 # lowering again is no trace
    assert module.startswith("jit_")
    found = set(ops.values())
    for scope in ("quiver.beam", "quiver.beam.pick",
                  "quiver.beam.neighbours", "quiver.beam.dedup",
                  "quiver.beam.visited", "quiver.beam.distance",
                  "quiver.beam.merge", "quiver.beam.cond",
                  "quiver.rerank"):
        assert scope in found, scope
    assert any(name.startswith("while") and scope == "quiver.beam"
               for name, scope in ops.items())
    with pytest.raises(KeyError):
        idx.plans.op_scopes(plan, 256)       # never launched at 256


def test_hlo_scopes_innermost_and_inherited():
    from repro.plan.cache import hlo_scopes

    text = """HloModule jit_program, entry_computation_layout={()->()}

%body (p: (s32[4])) -> (s32[4]) {
  %p = (s32[4]) parameter(0)
  %a = s32[4] add(%p, %p), metadata={op_name="jit(program)/quiver.beam/while/body/quiver.beam.pick/add"}
  %sorted = s32[4] sort(%a), to_apply=%cmp
  %b = s32[4] reshape(%sorted), metadata={op_name="jit(program)/quiver.beam/while/body/quiver.beam.visited/scatter-max"}
  ROOT %t = (s32[4]) tuple(%b)
}

ENTRY %main (x: s32[4]) -> s32[4] {
  %x = s32[4] parameter(0)
  %while.1 = (s32[4]) while(%x), condition=%cond, body=%body, metadata={op_name="jit(program)/quiver.beam/while"}
  %c = s32[4] copy(%x), metadata={op_name="jit(program)/concatenate"}
  ROOT %r = s32[4] fusion(%c), kind=kLoop, metadata={op_name="jit(program)/quiver.rerank/jit(rerank_f32)/top_k"}
}
"""
    module, ops = hlo_scopes(text)
    assert module == "jit_program"
    assert ops == {"a": "quiver.beam.pick",
                   "sorted": "quiver.beam.visited",   # from its user
                   "b": "quiver.beam.visited",
                   "while.1": "quiver.beam",
                   "r": "quiver.rerank"}


def test_plan_context_defaults():
    ctx = PlanContext()
    assert ctx.start == 0
    assert ctx.result_valid is None and ctx.match_ids is None
