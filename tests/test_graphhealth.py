"""Graph X-ray tests (DESIGN.md §15): structural health reports,
medoid-BFS reachability, churn monotonicity, calibrated verdicts on the
surrogate tiers, navigation-path counters vs a host-side reference
walk, and the graph-health rung of the remediation ladder."""

import dataclasses
import functools
import io
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import beam
from repro.core.index import QuIVerIndex
from repro.core.vamana import BuildParams
from repro.data.datasets import contrastive_surrogate, make_dataset
from repro.obs import MetricsRegistry, RemediationPolicy
from repro.obs.graph import (
    DEFAULT_GRAPH_THRESHOLDS,
    GraphHealthMonitor,
    GraphHealthReport,
    GraphThresholds,
    graph_health_report,
)
from repro.serve.engine import QueryEngine
from repro.stream.mutable import MutableQuIVerIndex

jax.config.update("jax_platform_name", "cpu")

PARAMS = BuildParams(m=8, ef_construction=48, prune_pool=48, chunk=256)


@functools.lru_cache(maxsize=1)
def _minilm_index():
    base, queries = make_dataset("minilm-surrogate", n=800, queries=8)
    idx = QuIVerIndex.build(jnp.asarray(base), PARAMS)
    return idx, np.asarray(queries, np.float32)


@functools.lru_cache(maxsize=1)
def _siftlike_index():
    base, _ = make_dataset("sift-like", n=800, queries=4)
    return QuIVerIndex.build(jnp.asarray(base), PARAMS)


def _report_fields(rep):
    """to_dict minus NaN pitfalls (NaN != NaN breaks == comparison)."""
    d = rep.to_dict()
    if math.isnan(d["edge_agreement"]):
        d["edge_agreement"] = "nan"
    if isinstance(d.get("health_score"), float) and math.isnan(
            d["health_score"]):
        d["health_score"] = "nan"
    return d


# -- report: determinism + persistence --------------------------------------


def test_report_deterministic_and_npz_roundtrip():
    idx, _ = _minilm_index()
    kw = dict(
        medoid=int(idx.medoid), words=idx.sigs.words, dim=idx.sigs.dim,
        vectors=idx.vectors, sample=64, seed=3,
        registry=MetricsRegistry(),
    )
    r1 = graph_health_report(idx.adjacency, **kw)
    r2 = graph_health_report(idx.adjacency, **kw)
    assert _report_fields(r1) == _report_fields(r2)
    assert not math.isnan(r1.edge_agreement)   # vectors armed the probe

    buf = io.BytesIO()
    np.savez(buf, **r1.to_npz_fields())
    buf.seek(0)
    back = GraphHealthReport.from_npz(np.load(buf))
    assert _report_fields(back) == _report_fields(r1)
    assert back.thresholds == r1.thresholds
    # an archive without the fields reads None, not garbage
    buf2 = io.BytesIO()
    np.savez(buf2, unrelated=np.zeros(3))
    buf2.seek(0)
    assert GraphHealthReport.from_npz(np.load(buf2)) is None


def test_report_persists_through_index_save_load(tmp_path):
    idx, _ = _minilm_index()
    rep = idx.graph_report(sample=64)
    assert idx.graph_health is rep
    idx.save(tmp_path / "idx.npz")
    back = QuIVerIndex.load(tmp_path / "idx.npz")
    assert back.graph_health is not None
    assert back.graph_health.verdict == rep.verdict
    assert back.graph_health.health_score == pytest.approx(
        rep.health_score)
    mem = back.memory_breakdown()
    assert mem["graph_verdict"] == rep.verdict


# -- medoid BFS on a hand-built graph ---------------------------------------


def test_bfs_flags_disconnected_component_as_red():
    # two components: {0,1,2} cycle (holds the medoid) and {3,4}
    adj = np.array(
        [[1, 2], [2, 0], [0, 1], [4, -1], [3, -1]], np.int32)
    rep = graph_health_report(
        jnp.asarray(adj), medoid=0, registry=MetricsRegistry())
    assert rep.n_unreachable == 2
    assert rep.unreachable_frac == pytest.approx(0.4)
    assert rep.hop_max <= 2.0
    assert rep.verdict == "red"
    assert rep.worst_stat()[0] == "unreachable_frac"
    assert math.isnan(rep.edge_agreement)   # no vectors -> structural only

    # fully connected: every live row reached, hop radius == 1
    star = np.array([[1, 2, 3], [0, -1, -1], [0, -1, -1], [0, -1, -1]],
                    np.int32)
    rep2 = graph_health_report(
        jnp.asarray(star), medoid=0, registry=MetricsRegistry())
    assert rep2.n_unreachable == 0
    assert rep2.hop_max == 1.0


# -- churn monotonicity ------------------------------------------------------


def test_tombstone_density_degrades_health_monotonically():
    base = contrastive_surrogate(400, 64, seed=3)
    idx = MutableQuIVerIndex.empty(64, 1024, keep_vectors=True)
    idx.insert(jnp.asarray(base))
    reg = MetricsRegistry()
    reports = [idx.graph_report(sample=64, registry=reg)]
    for stop in (120, 300):           # 30% then 75% tombstones
        start = 0 if len(reports) == 1 else 120
        for i in range(start, stop):
            idx.delete(i)
        reports.append(idx.graph_report(sample=64, registry=reg))
    dens = [r.tombstone_density for r in reports]
    assert dens[0] < dens[1] < dens[2]
    assert dens[2] == pytest.approx(0.75)
    scores = [r.health_score for r in reports]
    assert scores[0] >= scores[1] >= scores[2]
    bands = [("green", "amber", "red").index(r.verdict) for r in reports]
    assert bands == sorted(bands)      # never improves under pure churn
    assert reports[2].verdict == "red"  # 0.75 > tombstone_red
    # heavy churn trips tombstone density, and often medoid
    # reachability with it — either is the honest red stat
    assert reports[2].worst_stat()[0] in (
        "tombstone_density", "unreachable_frac")


# -- calibrated verdicts on the surrogate tiers ------------------------------


def test_verdict_green_on_contrastive_red_on_sign_collapsed():
    idx, _ = _minilm_index()
    rep = idx.graph_report(sample=128)
    assert rep.verdict == "green", rep.summary()
    assert rep.n_unreachable == 0
    assert rep.edge_agreement > 0.65   # BQ ordering tracks f32 cosine
    assert rep.health_score > 0.5

    bad = _siftlike_index().graph_report(sample=128)
    # non-negative data collapses the sign plane: the graph this builds
    # contradicts its own metric space and must not read green
    assert bad.verdict == "red", bad.summary()
    assert bad.health_score < rep.health_score


# -- navigation-path counters vs a host-side reference walk ------------------


def _reference_walk(adj, dist, start, ef):
    """Host-side greedy best-first walk mirroring beam_search(expand=1),
    with a full visited set: returns (hops, evals, scored, stalls,
    final_beam).  ``evals`` counts distinct rows scored; ``scored``
    counts the rows a hop met that were not in the beam at its pick,
    each neighbour once a hop — what the device beam scores, since it
    remembers only its beam."""
    beam_list = [(dist[start], start)]
    visited = {start}
    expanded = set()
    hops, evals, scored, stalls = 0, 1, 1, 0
    while True:
        frontier = [(d, u) for d, u in beam_list if u not in expanded]
        if not frontier:
            break
        prev_best = beam_list[0][0]
        _, u = min(frontier)
        expanded.add(u)
        in_beam = {w for _, w in beam_list}
        scored += len({v for v in adj[u] if v >= 0} - in_beam)
        for v in adj[u]:
            if v >= 0 and v not in visited:
                visited.add(v)
                evals += 1
                beam_list.append((dist[v], v))
        beam_list = sorted(beam_list)[:ef]
        if not beam_list[0][0] < prev_best:
            stalls += 1
        hops += 1
    return hops, evals, scored, stalls, beam_list


def test_nav_counters_match_reference_walk():
    n, ef, target = 40, 8, 37
    adj = np.full((n, 3), -1, np.int32)
    for i in range(n):
        if i:
            adj[i, 0] = i - 1
        if i < n - 1:
            adj[i, 1] = i + 1
    adj[0, 2] = 7                      # shortcuts off the chain
    adj[10, 2] = 25
    # distinct distances (the id epsilon breaks |i - t| ties) so the
    # device and host walks cannot diverge on tie-breaking
    dist = (np.abs(np.arange(n) - target) +
            0.001 * np.arange(n)).astype(np.float32)

    def dist_fn(q, ids, valid):
        d = jnp.abs(ids.astype(jnp.float32) - q)
        return d + 0.001 * ids.astype(jnp.float32)

    res = beam.beam_search(
        jnp.float32(target), jnp.asarray(adj), jnp.int32(0),
        dist_fn=dist_fn, ef=ef,
    )
    hops, evals, scored, stalls, ref_beam = _reference_walk(
        adj, dist, 0, ef)
    assert int(res.hops) == hops
    # the beam scores what it does not hold: rows it evicted are scored
    # again when the walk meets them
    assert int(res.evals) == scored
    assert int(res.evals) >= evals
    assert int(res.stalls) == stalls
    d0 = dist[0]
    assert float(res.descent) == pytest.approx(d0 - ref_beam[0][0],
                                               abs=1e-4)
    assert int(res.entry_rank) == sum(1 for d, _ in ref_beam if d < d0)
    # the walk actually descended the chain
    assert hops >= 10 and float(res.descent) > 30


def test_nav_traces_flow_into_tenant_report():
    from repro.obs import ObsHub
    idx, queries = _minilm_index()
    reg = MetricsRegistry()
    eng = QueryEngine(idx, default_k=4, default_ef=48,
                      obs=ObsHub(registry=reg))
    for q in queries[:8]:
        eng.submit(q[None])
    while eng.pump():
        pass
    nav = eng.tenants.report()["tenants"]["default"]["nav"]
    assert nav["hops"]["n"] == 8 and nav["hops"]["p50"] > 0
    assert nav["evals"]["p50"] > 0
    assert set(nav) == {"hops", "evals", "descent", "stalls",
                        "entry_rank"}
    # and the fleet histograms saw the same samples
    hist = {m.name: m for m in reg.metrics()}["quiver_nav_hops"]
    assert sum(s.count for s in hist.series().values()) == 8


# -- monitor + remediation ---------------------------------------------------


def _mk_report(**over):
    base = dict(
        n_live=100, n_allocated=100, degree_bound=16,
        out_degree_mean=8.0, in_degree_mean=8.0, saturation=0.1,
        reciprocity=0.2, n_unreachable=0, unreachable_frac=0.0,
        hop_p50=3.0, hop_p99=5.0, hop_max=6.0, tombstone_density=0.0,
        edge_agreement=0.8, n_sampled=64, agreement_k=8, seed=0,
    )
    base.update(over)
    return GraphHealthReport(**base)


def test_monitor_edge_triggers_on_worsening_only():
    mon = GraphHealthMonitor(registry=MetricsRegistry())
    assert mon.band is None
    assert mon.check(_mk_report()) is None            # arming green
    a1 = mon.check(_mk_report(tombstone_density=0.3))  # green -> amber
    assert a1 is not None and a1.band == "amber"
    assert a1.stat == "tombstone_density"
    assert mon.check(_mk_report(tombstone_density=0.35)) is None  # held
    a2 = mon.check(_mk_report(tombstone_density=0.7))  # amber -> red
    assert a2 is not None and a2.band == "red"
    assert mon.check(_mk_report()) is None             # recovery: silent
    a3 = mon.check(_mk_report(tombstone_density=0.3))  # crossing again
    assert a3 is not None and a3.band == "amber"
    assert len(mon.alarms) == 3
    assert mon.report()["band"] == "amber"


def test_remediation_walks_graph_ladder_once_per_crossing():
    base = contrastive_surrogate(200, 64, seed=5)
    idx = MutableQuIVerIndex.empty(64, 512, keep_vectors=True)
    idx.insert(jnp.asarray(base))
    reg = MetricsRegistry()
    eng = QueryEngine(idx, default_k=4, default_ef=16)
    pol = RemediationPolicy(eng, auto=False, registry=reg)
    mon = GraphHealthMonitor(registry=reg)
    pol.attach_graph(mon)

    mon.check(_mk_report())                            # healthy baseline
    mon.check(_mk_report(tombstone_density=0.3))       # -> amber
    mon.check(_mk_report(tombstone_density=0.35))      # held: no retrigger
    assert len(pol.triggers) == 1
    ev = pol.check()
    assert ev["action"] == "consolidate" and ev["trigger"] == "graph_health"
    assert pol.check() is None                         # queue drained

    mon.check(_mk_report(tombstone_density=0.7))       # amber -> red
    assert len(pol.triggers) == 1
    ev = pol.check()
    assert ev["action"] == "flag_red"
    assert ev.get("note") == "rebuild-through-probe"
    assert pol.flagged_red
    # once red-flagged the ladder stays parked at the bottom
    mon.check(_mk_report())                            # recover
    mon.check(_mk_report(tombstone_density=0.3))       # re-cross
    ev = pol.check()
    assert ev["action"] == "flag_red"
    assert ev.get("note") == "already red-flagged"


def test_health_verdicts_and_healthz():
    import json
    import urllib.error
    import urllib.request

    from repro.obs import PrometheusServer, health_snapshot

    idx, _ = _minilm_index()
    rep = idx.graph_report(sample=64)    # cached after first X-ray
    eng = QueryEngine(idx, default_k=4, default_ef=32)
    assert eng.health_verdicts() == {
        "graph": rep.verdict, "recall_slo": "green"}

    record, status = health_snapshot(eng.health_verdicts)
    assert status == 200 and record["verdict"] in ("green", "amber")

    srv = PrometheusServer(MetricsRegistry(), port=0,
                           health_fn=lambda: {"graph": "red"})
    try:
        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/healthz")
        assert err.value.code == 503
        assert json.loads(err.value.read())["verdict"] == "red"
    finally:
        srv.close()
