"""Telemetry layer tests (DESIGN.md §12): metrics primitives, sinks,
tracing, token-bucket quotas, per-tenant SLO attribution on the engine,
and probe-drift alarms under churn."""

import functools
import json
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.index import QuIVerIndex
from repro.core.vamana import BuildParams
from repro.data.datasets import make_dataset
from repro.obs import (
    JsonlSink,
    MetricsRegistry,
    ObsHub,
    PrometheusServer,
    Ring,
    TenantLedger,
    TenantQuota,
    TokenBucket,
    Tracer,
    render_prometheus,
)
from repro.serve.engine import QueryEngine
from repro.stream.mutable import MutableQuIVerIndex

jax.config.update("jax_platform_name", "cpu")

PARAMS = BuildParams(m=6, ef_construction=32, prune_pool=32, chunk=128)


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


@functools.lru_cache(maxsize=1)
def _index():
    base, queries = make_dataset("minilm-surrogate", n=800, queries=12)
    idx = QuIVerIndex.build(jnp.asarray(base), PARAMS)
    return idx, np.asarray(queries, np.float32)


# -- metrics primitives -----------------------------------------------------


def test_ring_is_bounded_and_percentile_works():
    r = Ring(4)
    for i in range(10):
        r.append(float(i))
    assert len(r) == 4 and r.maxlen == 4 and r.total == 10
    assert set(r.array()) == {6.0, 7.0, 8.0, 9.0}
    assert r.percentile(50) == pytest.approx(7.5)


def test_counter_gauge_histogram_series():
    reg = MetricsRegistry()
    c = reg.counter("req_total", "requests", labels=("tenant",))
    c.inc(tenant="a")
    c.inc(2, tenant="b")
    assert c.value(tenant="a") == 1 and c.value(tenant="b") == 2
    g = reg.gauge("queue", "depth")
    g.set(7)
    g.add(-2)
    assert g.value() == 5
    h = reg.histogram("lat", "seconds", buckets=(0.1, 1.0, 10.0))
    h.observe_many([0.05, 0.5, 5.0, 50.0])
    snap = reg.snapshot()
    assert snap["req_total"]["tenant=a"] == 1
    assert snap["lat"][""]["count"] == 4


def test_registry_rejects_type_and_label_mismatch():
    reg = MetricsRegistry()
    reg.counter("x", "d")
    with pytest.raises(ValueError):
        reg.gauge("x", "d")
    reg.counter("y", "d", labels=("a",))
    with pytest.raises(ValueError):
        reg.counter("y", "d", labels=("b",))


def test_prometheus_rendering_and_endpoint():
    reg = MetricsRegistry()
    reg.counter("hits_total", "hits", labels=("route",)).inc(3, route="graph")
    reg.histogram("lat_seconds", "lat", buckets=(0.1, 1.0)).observe(0.5)
    text = render_prometheus(reg)
    assert 'hits_total{route="graph"} 3' in text
    assert 'lat_seconds_bucket{le="1"} 1' in text
    assert "lat_seconds_count 1" in text
    srv = PrometheusServer(reg, port=0)
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/metrics", timeout=5
        ) as resp:
            body = resp.read().decode()
        assert 'hits_total{route="graph"} 3' in body
    finally:
        srv.close()


def test_jsonl_sink_and_hub_emit(tmp_path):
    path = tmp_path / "obs.jsonl"
    reg = MetricsRegistry()
    hub = ObsHub(registry=reg, sinks=[JsonlSink(path)])
    reg.counter("n", "d").inc(5)
    hub.emit({"phase": "test"})
    hub.emit()
    hub.close()
    records = [json.loads(ln) for ln in path.read_text().splitlines()]
    assert len(records) == 2
    assert records[0]["phase"] == "test"
    assert records[0]["metrics"]["n"][""] == 5


def test_jsonl_sink_rotates_on_size(tmp_path):
    path = tmp_path / "obs.jsonl"
    # each record is ~40 bytes; cap at ~2 records per generation
    sink = JsonlSink(path, max_bytes=90, keep=2)
    for i in range(7):
        sink.emit({"seq": i, "pad": "x" * 16})
    sink.close()

    def lines(p):
        return [json.loads(ln) for ln in p.read_text().splitlines()]

    live = lines(path)
    gen1 = lines(path.with_name("obs.jsonl.1"))
    gen2 = lines(path.with_name("obs.jsonl.2"))
    # keep=2: no third generation, oldest records dropped
    assert not path.with_name("obs.jsonl.3").exists()
    # every line lands whole in exactly one generation, newest in path
    assert live and live[-1]["seq"] == 6
    seqs = [r["seq"] for r in gen2 + gen1 + live]
    assert seqs == sorted(seqs)                  # oldest -> newest order
    assert len(live) + len(gen1) + len(gen2) < 7  # something rotated out
    # generations respect the size cap
    for p in (path.with_name("obs.jsonl.1"), path.with_name("obs.jsonl.2")):
        assert p.stat().st_size <= 90


def test_jsonl_sink_rotation_disabled_by_default(tmp_path):
    path = tmp_path / "obs.jsonl"
    sink = JsonlSink(path)                       # max_bytes=0: unbounded
    for i in range(50):
        sink.emit({"seq": i})
    sink.close()
    assert len(path.read_text().splitlines()) == 50
    assert not path.with_name("obs.jsonl.1").exists()


def test_jsonl_sink_rejects_bad_keep(tmp_path):
    with pytest.raises(ValueError):
        JsonlSink(tmp_path / "x.jsonl", max_bytes=10, keep=0)


def test_tracer_spans_feed_stage_histogram():
    reg = MetricsRegistry()
    tr = Tracer(reg)
    with tr.span("launch", plan="p"):
        pass
    with tr.span("finalize"):
        pass
    rep = tr.report()
    assert rep["launch"]["count"] == 1
    assert rep["finalize"]["count"] == 1
    assert reg.snapshot()["quiver_stage_seconds"]["stage=launch"]["count"] == 1


def test_tracer_span_is_a_profiler_annotation(monkeypatch):
    import repro.obs.tracing as tracing

    opened = []

    class Recording:
        def __init__(self, name):
            opened.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(tracing, "TraceAnnotation", Recording)
    tr = Tracer()
    with tr.span("launch"):
        with tr.span("encode"):
            pass
    assert opened == ["quiver.launch", "quiver.encode"]


def test_tracer_stage_totals_and_slowest_window_split():
    clk = FakeClock()
    tr = Tracer(max_spans=0, clock=clk)      # no registry, no ring

    def window(launch_s, finalize_s):
        with tr.span("window"):
            with tr.span("launch"):
                with tr.span("encode"):
                    clk.t += launch_s / 2
                clk.t += launch_s / 2
            with tr.span("finalize"):
                clk.t += finalize_s

    window(0.010, 0.100)
    window(0.020, 2.500)                     # the stall
    window(0.010, 0.120)
    rep = tr.stage_report()
    assert tr.spans() == [] and tr.report() == {}
    assert rep["windows"] == 3
    assert rep["stages"]["window"]["count"] == 3
    assert rep["stages"]["finalize"]["total_s"] == pytest.approx(2.72)
    assert rep["stages"]["encode"]["total_s"] == pytest.approx(0.02)
    slow = rep["slowest_window"]
    assert slow["index"] == 1
    assert slow["seconds"] == pytest.approx(2.52)
    assert slow["stages"] == pytest.approx(
        {"launch": 0.02, "encode": 0.01, "finalize": 2.5})
    # a span outside any window counts in the totals, not in a split
    with tr.span("escalate"):
        clk.t += 0.5
    assert tr.stage_report()["stages"]["escalate"]["count"] == 1
    assert tr.stage_report()["slowest_window"]["stages"] == slow["stages"]
    tr.reset()
    assert tr.stage_report() == {"stages": {}, "windows": 0,
                                 "slowest_window": None}


def test_tracer_failing_stage_still_counts():
    tr = Tracer(max_spans=0, clock=FakeClock())
    with pytest.raises(RuntimeError):
        with tr.span("window"):
            with tr.span("launch"):
                raise RuntimeError("device lost")
    rep = tr.stage_report()
    assert rep["stages"]["launch"]["count"] == 1
    assert rep["slowest_window"]["stages"] == {"launch": 0.0}


# -- quotas and tenant accounting -------------------------------------------


def test_token_bucket_refill_semantics():
    clk = FakeClock()
    b = TokenBucket(TenantQuota(qps=2.0, burst=4), clk())
    assert all(b.take(1, clk()) for _ in range(4))   # burst drains
    assert not b.take(1, clk())                      # empty
    clk.t += 1.0                                     # +2 tokens
    assert b.take(2, clk()) and not b.take(1, clk())


def test_ledger_quota_isolation_and_attribution():
    clk = FakeClock()
    led = TenantLedger(clock=clk)
    led.set_quota("paid", qps=1.0, burst=2)
    # over-budget tenant exhausts only its own bucket
    assert led.admit("paid", 1) and led.admit("paid", 1)
    assert not led.admit("paid", 1)
    # unquota'd tenant is never rejected, regardless of paid's state
    for _ in range(50):
        assert led.admit("free", 1)
    led.observe("paid", status="done", latency=0.01)
    led.observe("free", status="dropped", latency=0.5, degraded=True)
    rep = led.report()
    assert rep["quota_violations"] == 0
    assert rep["tenants"]["paid"]["rejected"] == 1
    assert rep["tenants"]["free"]["rejected"] == 0
    assert rep["tenants"]["free"]["dropped"] == 1
    assert rep["tenants"]["free"]["degraded"] == 1
    assert rep["tenants"]["paid"]["p50_ms"] == pytest.approx(10.0)


# -- engine integration -----------------------------------------------------


def test_engine_quota_rejects_over_budget_without_starving_others():
    idx, queries = _index()
    clk = FakeClock()
    engine = QueryEngine(idx, default_k=5, default_ef=32, clock=clk)
    engine.set_quota("greedy", qps=1.0, burst=2)
    tickets = {"greedy": [], "modest": []}
    for i in range(6):
        tickets["greedy"].append(engine.submit(queries[i % 4],
                                               tenant="greedy"))
        tickets["modest"].append(engine.submit(queries[i % 4],
                                               tenant="modest"))
    engine.pump()
    rep = engine.tenants.report()
    # greedy burned its burst of 2, the rest rejected fast with -1 rows
    assert rep["tenants"]["greedy"]["rejected"] == 4
    assert rep["tenants"]["modest"]["rejected"] == 0
    assert rep["quota_violations"] == 0
    rejected = [t for t in tickets["greedy"]
                if engine.ticket(t).status == "rejected"]
    assert len(rejected) == 4
    ids, scores = engine.result(rejected[0])
    assert (ids == -1).all() and np.isneginf(scores).all()
    # every modest request completed normally
    assert all(engine.ticket(t).status == "done"
               for t in tickets["modest"])


def test_engine_attributes_degrades_and_drops_per_tenant():
    idx, queries = _index()
    clk = FakeClock()
    engine = QueryEngine(idx, default_k=5, default_ef=64,
                         latency_slack=1.0, clock=clk)
    # seed the latency model so the engine predicts 1s/launch
    engine.search(queries[:2])                     # warm + EWMA seed
    for p in list(engine._lat_ewma):
        engine._lat_ewma[p] = 1.0
    # hopeless deadline -> drop, attributed to its submitter
    t_drop = engine.submit(queries[0], tenant="dropper", deadline_ms=0.0)
    clk.t += 1.0
    engine.pump()
    assert engine.ticket(t_drop).status == "dropped"
    # tight-but-feasible deadline -> degraded ef, attributed likewise
    t_deg = engine.submit(queries[1], tenant="degrader", deadline_ms=500.0)
    engine.pump()
    assert engine.ticket(t_deg).status == "done"
    rep = engine.tenants.report()
    assert rep["tenants"]["dropper"]["dropped"] == 1
    assert rep["tenants"]["dropper"]["degraded"] == 0
    assert rep["tenants"]["degrader"]["dropped"] == 0
    assert rep["tenants"]["degrader"]["degraded"] == 1


def test_engine_report_and_span_lifecycle():
    idx, queries = _index()
    engine = QueryEngine(idx, default_k=5, default_ef=32)
    t = engine.submit(queries[:4], tenant="acme")
    engine.pump()
    engine.result(t)
    rep = engine.stats_report()
    assert rep["tenant_report"]["tenants"]["acme"]["done"] == 1
    stages = rep["span_report"]
    for stage in ("admission", "coalesce", "launch", "finalize",
                  "request", "window"):
        assert stages[stage]["count"] >= 1, f"no {stage} span recorded"
    assert rep["rejected"] == 0 and rep["latency_window"] > 0


def test_engine_stage_totals_without_a_hub():
    idx, queries = _index()
    clk = FakeClock()
    engine = QueryEngine(idx, default_k=5, default_ef=32, obs=False,
                         clock=clk)
    assert engine.obs is None and engine.tracer.registry is None
    for i in range(3):
        t = engine.submit(queries[i:i + 2])
        engine.pump()
        engine.result(t)
    rep = engine.stats_report()
    assert "span_report" not in rep
    stages = rep["stage_report"]["stages"]
    for stage in ("window", "coalesce", "launch", "encode", "dispatch",
                  "finalize", "scatter"):
        assert stages[stage]["count"] == 3, stage
    # per-request spans stay opt-in: no hub, no ring
    assert "admission" not in stages and "request" not in stages
    assert engine.tracer.spans() == []
    slow = rep["stage_report"]["slowest_window"]
    assert rep["stage_report"]["windows"] == 3 and slow is not None
    assert set(slow["stages"]) == {"coalesce", "launch", "encode",
                                   "dispatch", "finalize", "scatter"}


def test_engine_hub_keeps_every_stage_name():
    idx, queries = _index()
    engine = QueryEngine(idx, default_k=5, default_ef=32,
                         obs=ObsHub(registry=MetricsRegistry()))
    t = engine.submit(queries[:4])
    engine.pump()
    engine.result(t)
    rep = engine.stats_report()
    for stage in ("admission", "coalesce", "launch", "encode", "dispatch",
                  "finalize", "scatter", "request", "window"):
        assert rep["span_report"][stage]["count"] >= 1, stage
    hist = engine.obs.registry.snapshot()["quiver_stage_seconds"]
    assert hist["stage=scatter"]["count"] == 1
    assert "quiver_plan_seconds" not in engine.obs.registry.snapshot()
    assert rep["stage_report"]["stages"]["window"]["count"] == 1


def test_engine_stats_latencies_bounded():
    idx, queries = _index()
    engine = QueryEngine(idx, default_k=5, default_ef=32,
                         latency_window=8)
    for i in range(12):
        engine.search(queries[i % 8])
    assert len(engine.stats.latencies) == 8
    assert engine.stats.latencies.total == 12


# -- drift alarms -----------------------------------------------------------


def _collapsed(rng, n, dim):
    """Sign-collapsed vectors: every coordinate positive, so bit-plane
    entropy collapses toward 0 as they dominate the live set."""
    return np.abs(rng.normal(size=(n, dim))).astype(np.float32) + 3.0


def test_drift_monitor_quiet_on_green_churn():
    rng = np.random.default_rng(0)
    idx = MutableQuIVerIndex.empty(32, 512, PARAMS)
    mon = idx.attach_drift_monitor(tenant="t", min_n=32)
    for _ in range(4):
        idx.insert(rng.normal(size=(64, 32)).astype(np.float32))
    assert mon.band == "green"
    assert len(mon.events) == 0


def test_drift_monitor_alarms_on_incompatible_churn():
    rng = np.random.default_rng(0)
    reg = MetricsRegistry()
    idx = MutableQuIVerIndex.empty(32, 1024, PARAMS)
    mon = idx.attach_drift_monitor(tenant="drifty", min_n=32,
                                   registry=reg)
    good = idx.insert(rng.normal(size=(128, 32)).astype(np.float32))
    assert mon.band == "green" and not mon.events
    idx.insert(_collapsed(rng, 512, 32))
    idx.delete(good)                      # live set is now all-collapsed
    assert mon.band == "red"
    assert len(mon.events) >= 1
    ev = mon.events[-1]
    assert ev.tenant == "drifty" and ev.band == "red"
    assert "drifty" in ev.message()
    assert reg.counter(
        "quiver_drift_alarms_total", "probe-drift band alarms",
        labels=("tenant", "band"),
    ).value(tenant="drifty", band="red") >= 1


def test_drift_monitor_alarm_fires_once_per_crossing():
    rng = np.random.default_rng(1)
    idx = MutableQuIVerIndex.empty(32, 1024, PARAMS)
    mon = idx.attach_drift_monitor(tenant="t", min_n=32)
    idx.insert(_collapsed(rng, 256, 32))
    n_after_crossing = len(mon.events)
    assert n_after_crossing >= 1
    idx.insert(_collapsed(rng, 64, 32))   # still red: no re-alarm
    assert len(mon.events) == n_after_crossing


def test_mutation_metrics_recorded():
    from repro.obs.metrics import get_default_registry
    rng = np.random.default_rng(2)
    idx = MutableQuIVerIndex.empty(32, 256, PARAMS)
    before = get_default_registry().counter(
        "quiver_stream_mutations_total", "streaming mutations by kind",
        labels=("kind",),
    ).value(kind="insert")
    idx.insert(rng.normal(size=(32, 32)).astype(np.float32))
    after = get_default_registry().counter(
        "quiver_stream_mutations_total", "streaming mutations by kind",
        labels=("kind",),
    ).value(kind="insert")
    assert after - before == 32
