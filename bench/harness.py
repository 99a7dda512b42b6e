"""The benchmark's run of one cell: set-up, the measured window, the
comparison with the reference, and the metrics.

Everything a cell is made of is found by name: its configuration is
the file its entry in ``BENCHMARK.json`` names, its traffic mix is
``bench/traffic/<traffic>.json`` and each metric is read by
``bench/metrics/<metric>.py``.  Adding a cell, a mix or a metric adds
files and edits none.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import pathlib
import sys
import time

import numpy as np

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = BENCH / ".jax_cache"

# --rehearse: the whole run at a size the CPU takes in a minute, with
# the Pallas kernels in interpret mode.  Cuts of the cell's sizes only.
REHEARSE = {
    "n": 2000,
    "pool": 128,
    "build": {"m": 16, "ef_construction": 64, "prune_pool": 64, "chunk": 128},
}


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


# -- the cell, found by name ---------------------------------------------


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    end_to_end: list[dict]
    per_layer: list[dict]


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: "
                         f"{sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    config = json.loads((root / conf["file"]).read_text())
    mix = json.loads((BENCH / "traffic" / f"{w['traffic']}.json").read_text())
    if mix["loop"] != "closed":
        raise SystemExit(f"traffic {w['traffic']!r}: the harness runs a "
                         f"closed loop only, not {mix['loop']!r}")
    e2e = [m for m in spec["end_to_end"]
           if name in m.get("workloads", [name])]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in reported)]
    return Cell(name, int(w["chips"]), config, mix, e2e, per_layer)


def reader(metric: str):
    """The ``read(record)`` function of ``bench/metrics/<metric>.py``."""
    path = BENCH / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# -- host spans ------------------------------------------------------------


class Spans:
    """The benchmark's own spans around its calls into the program.

    Traced, each span is also a ``TraceAnnotation`` named ``bench.<name>``
    on the profiler's clock, and its host seconds are kept; untraced,
    a span costs nothing."""

    def __init__(self, traced: bool):
        self.traced = traced
        self.seconds: dict[str, list[float]] = {}

    @contextlib.contextmanager
    def __call__(self, name: str):
        if not self.traced:
            yield
            return
        from jax.profiler import TraceAnnotation

        t = time.perf_counter()
        with TraceAnnotation("bench." + name):
            yield
        self.seconds.setdefault(name, []).append(time.perf_counter() - t)


class CompileCounter:
    """Counts backend compiles (persistent-cache reads included)."""

    def __init__(self):
        import jax
        from jax._src import dispatch

        self.count = 0
        event = dispatch.BACKEND_COMPILE_EVENT

        def on_duration(name, secs, **_):
            if name == event:
                self.count += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)


# -- set-up ----------------------------------------------------------------


@dataclasses.dataclass
class Setup:
    base: np.ndarray
    pool: np.ndarray
    index: object
    engine: object
    hub: object
    build_s: float
    build_stats: object


def sizes(cell: Cell, rehearse: bool) -> tuple[int, int, dict]:
    cfg = cell.config
    if rehearse:
        return REHEARSE["n"], REHEARSE["pool"], REHEARSE["build"]
    return cfg["n"], cfg["pool"], cfg["build"]


def set_up(cell: Cell, *, traced: bool, rehearse: bool) -> Setup:
    import jax

    from corpus import make_corpus
    from repro.core.index import QuIVerIndex
    from repro.core.vamana import BuildParams
    from repro.serve.engine import QueryEngine

    cfg = cell.config
    n, pool_n, build = sizes(cell, rehearse)
    t = time.perf_counter()
    base, pool = make_corpus(cfg["corpus"], n, pool_n, cfg["corpus_seed"])
    log(f"setup: corpus n={n} d={base.shape[1]} pool={pool_n} "
        f"in {time.perf_counter() - t:.2f} s")

    t = time.perf_counter()
    index = QuIVerIndex.build(base, BuildParams(**build), nav=cfg["nav"])
    jax.block_until_ready((index.adjacency, index.sigs.words,
                           index.vectors))
    build_s = time.perf_counter() - t
    st = index.build_stats
    log(f"setup: build {build_s:.2f} s chunks={st.chunks} "
        f"consolidations={st.consolidations} "
        f"mean_hops={st.mean_hops:.1f} route={index.backend().route}")

    hub = None
    if traced:
        from repro.obs import ObsHub
        from repro.obs.metrics import MetricsRegistry

        hub = ObsHub(registry=MetricsRegistry())
    engine = QueryEngine(index, default_k=cfg["k"], default_ef=cfg["ef"],
                         obs=hub if traced else False)
    t = time.perf_counter()
    warm_up(engine, cell.mix, pool)
    log(f"setup: warmed in {time.perf_counter() - t:.2f} s")
    return Setup(base, pool, index, engine, hub, build_s, st)


def warm_up(engine, mix: dict, pool: np.ndarray) -> None:
    """One pass over the pool in admission windows of the mix, through
    ``submit``/``pump``: their coalesced size, ``clients`` x
    ``queries_per_request`` rows, is the only one the window serves."""
    per = int(mix["queries_per_request"])
    size = int(mix["clients"]) * per
    rows = np.arange(max(len(pool), size)) % len(pool)
    for w in range(0, len(rows) - size + 1, size):
        tickets = [engine.submit(pool[rows[s:s + per]])
                   for s in range(w, w + size, per)]
        engine.pump()
        for t in tickets:
            engine.result(t)


def start_trace(trace_dir) -> None:
    """The profiler with its Python function tracer off: the harness's
    own ``bench.*`` annotations are the host spans it reads."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=options)


# -- the measured window ---------------------------------------------------


@dataclasses.dataclass
class Window:
    """What the window served, on the host clock."""
    seconds: float                 # first due request to last answer
    requests: int                  # due inside the window
    answered: int
    queries: int                   # query rows answered
    pool_rows: np.ndarray          # (queries,) pool index of each row
    ids: np.ndarray                # (queries, k)
    scores: np.ndarray             # (queries, k)
    pumps: int
    slowest_s: float               # longest submit-pump-collect round
    slowest_at: int                # its index


def request_rows(rng: np.random.Generator, pool: int, per: int):
    """Pool rows of each request, endlessly: pass after pass over the
    whole pool, each in a fresh order from ``rng``, so that every seed
    asks the same queries equally often, in its own order and batches.
    """
    while True:
        order = rng.permutation(pool)
        for s in range(0, pool - per + 1, per):
            yield order[s:s + per]


def closed_loop(engine, pool, mix, seconds, rng, spans) -> Window:
    clients = int(mix["clients"])
    per = int(mix["queries_per_request"])
    stream = request_rows(rng, len(pool), per)
    clock = time.perf_counter
    asked, ids, scores = [], [], []
    pumps = 0
    done = slowest = 0.0
    slowest_at = 0
    t0 = clock()
    with spans("window"):
        while clock() - t0 < seconds:
            rows = [next(stream) for _ in range(clients)]
            with spans("submit"):
                due = clock() - t0
                tickets = [engine.submit(pool[r]) for r in rows]
            with spans("pump"):
                engine.pump()
            done = clock() - t0
            pumps += 1
            with spans("collect"):
                for r, t in zip(rows, tickets):
                    i, s = engine.result(t)
                    asked.append(r)
                    ids.append(i)
                    scores.append(s)
            took = clock() - t0 - due
            if took > slowest:
                slowest, slowest_at = took, pumps - 1
    n_req = len(asked)
    return Window(done, n_req, n_req, n_req * per, np.concatenate(asked),
                  np.concatenate(ids), np.concatenate(scores), pumps, slowest,
                  slowest_at)


# -- one run ---------------------------------------------------------------


def nav_counters(hub) -> dict:
    """count and sum of the program's ``quiver_nav_*`` histograms."""
    if hub is None:
        return {}
    snap = hub.registry.snapshot()
    out = {}
    for name, series in snap.items():
        if name.startswith("quiver_nav_"):
            stat = name[len("quiver_nav_"):]
            out[stat] = {
                "count": sum(s["count"] for s in series.values()),
                "sum": sum(s["sum"] for s in series.values()),
            }
    return out


def peak_bytes(devices) -> int | None:
    try:
        return max(int(d.memory_stats()["peak_bytes_in_use"])
                   for d in devices)
    except (TypeError, KeyError):
        return None


def device_peaks(kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())
    if kind not in table["devices"]:
        raise SystemExit(f"device kind {kind!r} is not in bench/peaks.json;"
                         f" known: {sorted(table['devices'])}")
    return table["devices"][kind]


def run(cell: Cell, *, seed: int, seconds: float, traced: bool,
        rehearse: bool, t_start: float, devices, trace_dir=None) -> dict:
    """Set up, measure, compare; returns the record the metric readers
    take, with ``checks`` (the comparison) in it."""
    import jax

    spans = Spans(traced)
    compiles = CompileCounter()
    s = set_up(cell, traced=traced, rehearse=rehearse)
    rng = np.random.default_rng(seed)
    stats0 = dict(vars(s.engine.stats))
    nav0 = nav_counters(s.hub)
    c0 = compiles.count
    if traced:
        start_trace(trace_dir)
    setup_s = time.perf_counter() - t_start
    w = closed_loop(s.engine, s.pool, cell.mix, seconds, rng, spans)
    if traced:
        jax.profiler.stop_trace()
    in_window = compiles.count - c0
    st = s.engine.stats
    rejected = st.rejected - stats0["rejected"]
    dropped = st.dropped - stats0["dropped"]
    nav1 = nav_counters(s.hub)
    plan = s.index.plans.report()
    peak = peak_bytes(devices)
    log(f"window: {w.seconds:.3f} s requests={w.requests} "
        f"answered={w.answered} queries={w.queries} pumps={w.pumps} "
        f"slowest={w.slowest_s * 1e3:.1f}ms@{w.slowest_at} "
        f"rejected={rejected} dropped={dropped} "
        f"compiles_in_window={in_window} retraces={plan['retraces']}")
    record = {
        "cell": cell.name,
        "seed": seed,
        "setup_s": setup_s,
        "build_s": s.build_s,
        "window": w,
        "dim": int(s.base.shape[1]),
        "k": int(cell.config["k"]),
        "failed": (w.requests - w.answered) + rejected + dropped,
        "compiles_in_window": in_window,
        "memory_peak_bytes": peak,
        "nav": {k: {"count": nav1[k]["count"] - nav0.get(k, {}).get("count", 0),
                    "sum": nav1[k]["sum"] - nav0.get(k, {}).get("sum", 0.0)}
                for k in nav1},
        "spans": spans.seconds,
    }
    # the program's state goes before the reference runs
    base, pool = s.base, s.pool
    del s
    gc.collect()

    from reference import compare, exact_topk

    t = time.perf_counter()
    truth, _ = exact_topk(base, pool, record["k"])
    record["checks"] = compare(
        served_ids=w.ids, served_scores=w.scores, pool_rows=w.pool_rows,
        missing=w.requests - w.answered, base=base, pool=pool,
        truth_ids=truth, recall_floor=cell.config["recall_at_10_min"],
        rng=np.random.default_rng((seed, 1)),
    )
    record["recall"] = record["checks"]["recall_at_10"]["value"]
    log(f"reference: exact float64 top-{record['k']} of {len(pool)} pool "
        f"queries and the comparison in {time.perf_counter() - t:.2f} s")
    return record
