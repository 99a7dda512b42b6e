"""The reduction by the program's own names (``program_trace.py``) and
the loop readers, on synthetic intervals worked out by hand, and on a
scoped trace recorded on one v5e (``record_scoped_trace.py``)."""

import json
import pathlib
import types

import numpy as np
import pytest

from harness import reader
from program_trace import (
    ProgramTrace,
    _timeline,
    innermost_segments,
    leaf_mask,
    module_name,
    numbers,
    reduce,
)
from trace_reduce import reduce as reduce_bench

DATA = pathlib.Path(__file__).parent / "data"
SCOPED = DATA / "v5e_batch_scoped.xplane.pb.gz"
SCOPED_OPS = DATA / "v5e_batch_scoped.ops.json"


def test_leaf_mask_marks_containers():
    # while [0, 100) holds a [0, 10) and b [10, 60); c [100, 120) is
    # alone; d [130, 130) is empty; e [140, 150) holds f [140, 150)
    start = np.array([0, 0, 10, 100, 130, 140, 140], np.float64)
    end = np.array([100, 10, 60, 120, 130, 150, 150], np.float64)
    leaf = leaf_mask(start, end)
    assert leaf.tolist() == [False, True, True, True, True, False, True]


def test_innermost_segments_nest_and_fill_the_window():
    spans = [("bench.pump", 10, 90), ("quiver.window", 12, 88),
             ("quiver.launch", 15, 30), ("quiver.encode", 15, 20),
             ("quiver.finalize", 40, 80)]
    segs = innermost_segments(spans, 0, 100)
    assert segs == [
        ("unspanned", 0, 10), ("bench.pump", 10, 12),
        ("quiver.window", 12, 15), ("quiver.encode", 15, 20),
        ("quiver.launch", 20, 30), ("quiver.window", 30, 40),
        ("quiver.finalize", 40, 80), ("quiver.window", 80, 88),
        ("bench.pump", 88, 90), ("unspanned", 90, 100)]


def _trace(ops, mods, spans, window=(0.0, 1000.0)):
    return ProgramTrace(window, {0: _timeline(ops, mods, *window)}, spans)


def test_idle_goes_to_the_innermost_span_and_adds_up():
    # device busy [100, 300) (a while holding two leaf ops) and
    # [500, 900); window [0, 1000): idle 100 + 200 + 100 = 400 ns
    ops = [("while.1", 100, 300), ("fusion.1", 100, 180),
           ("sort.2", 180, 300), ("fusion.3", 500, 900)]
    mods = [("jit_program", 90, 310), ("jit_norm", 500, 900)]
    spans = [("bench.pump", 50, 950), ("quiver.window", 60, 940),
             ("quiver.launch", 60, 120), ("quiver.finalize", 300, 450)]
    pt = _trace(ops, mods, spans)
    idle = pt.idle_by_innermost()
    assert idle == pytest.approx({
        "unspanned": 100e-9,          # [0, 50) and [950, 1000)
        "bench.pump": 20e-9,          # [50, 60) and [940, 950)
        "quiver.launch": 40e-9,       # [60, 100)
        "quiver.finalize": 150e-9,    # [300, 450)
        "quiver.window": 90e-9,       # [450, 500) and [900, 940)
    })
    assert sum(idle.values()) == pytest.approx(400e-9)
    tl = pt.devices[0]
    assert tl.names == ["fusion.1", "sort.2", "fusion.3"]
    assert tl.module == ["jit_program", "jit_program", "jit_norm"]
    assert tl.containers == [("while.1", "jit_program", pytest.approx(2e-7))]
    scopes = {"fusion.1": "quiver.beam.pick", "while.1": "quiver.beam"}
    assert pt.scope_seconds("jit_program", scopes) == pytest.approx(
        {"quiver.beam.pick": 80e-9, "unmapped": 120e-9})
    assert pt.unmapped_ops("jit_program", scopes) == pytest.approx(
        {"sort.2": 120e-9})


def test_numbers_per_pump_query_and_iteration():
    ops = [("while.1", 100, 300), ("fusion.1", 100, 180),
           ("sort.2", 180, 300), ("fusion.3", 500, 900)]
    mods = [("jit_program", 90, 310), ("jit_program", 500, 900)]
    spans = [("bench.pump", 50, 950), ("quiver.window", 60, 500),
             ("quiver.launch", 60, 120), ("quiver.finalize", 300, 450),
             ("quiver.window", 500, 940)]
    pt = _trace(ops, mods, spans)
    scopes = {"module": "jit_program",
              "ops": {"while.1": "quiver.beam",
                      "fusion.1": "quiver.beam.pick",
                      "sort.2": "quiver.beam.merge",
                      "fusion.3": "quiver.rerank"}}
    loop = {"nav_chunks": 2, "nav_hops": 30, "wait_iterations": 10,
            "loop_iterations": 10}
    got = numbers(pt, scopes, loop, queries=4)
    assert got["pumps"] == 2
    assert got["engine.launch_idle_ms_per_pump"] == pytest.approx(
        40e-9 / 2 * 1e3)
    assert got["engine.finalize_idle_ms_per_pump"] == pytest.approx(
        150e-9 / 2 * 1e3)
    # stage idle over idle under the pump: (40 + 150) / (20 + 40 + 150
    # + 90): the window's own 90 ns is no stage
    assert got["stage_idle_share_of_pump_idle"] == pytest.approx(190 / 300)
    assert got["beam.iterations_per_batch"] == 5.0
    assert got["beam.lockstep_share"] == pytest.approx(75.0)
    assert got["beam.us_per_iteration"] == pytest.approx(200e-9 / 10 * 1e6)
    assert got["beam.sort_us_per_query"] == pytest.approx(200e-9 / 4 * 1e6)
    assert got["rerank.device_us_per_query"] == pytest.approx(
        400e-9 / 4 * 1e6)
    # back-to-back windows read as one envelope piece [450, 940)
    assert got["window_idle_between"] == pytest.approx(
        {"quiver.finalize>bench.pump": 90e-9,
         "quiver.launch>quiver.finalize": 0.0})
    assert got["mapped_share_of_leaf_s"] == 1.0
    assert got["beam_parts_share_of_loop_s"] == pytest.approx(1.0)
    # without the program's op map the device split is left out
    bare = numbers(pt, None, {}, queries=4)
    assert "beam.sort_us_per_query" not in bare
    assert bare["beam.iterations_per_batch"] is None


def test_module_name_drops_the_run_id():
    assert module_name("jit_program(8487115158256911563)") == "jit_program"
    assert module_name("jit_norm") == "jit_norm"


def test_loop_readers_from_the_nav_histograms():
    rec = {"nav": {"hops": {"count": 512, "sum": 30000.0},
                   "loop_iterations": {"count": 2, "sum": 150.0},
                   "wait_iterations": {"count": 2, "sum": 10000.0}},
           "trace": types.SimpleNamespace(
               devices={0: None},
               op_seconds=lambda pattern: {"while.110": 0.003}
               if pattern.startswith("^while") else {})}
    assert reader("beam.iterations_per_batch.batch")(rec) == 75.0
    assert reader("beam.lockstep_share.batch")(rec) == pytest.approx(75.0)
    assert reader("beam.us_per_iteration.batch")(rec) == pytest.approx(
        0.003 / 150 * 1e6)
    # a program that records no loop histograms reads nothing
    bare = {"nav": {"hops": {"count": 512, "sum": 30000.0}},
            "trace": rec["trace"]}
    for name in ("beam.iterations_per_batch.batch",
                 "beam.lockstep_share.batch",
                 "beam.us_per_iteration.batch"):
        assert reader(name)(bare) is None


# -- the scoped trace recorded on a v5e -------------------------------------


@pytest.fixture(scope="module")
def scoped():
    return reduce(SCOPED), json.loads(SCOPED_OPS.read_text())


def test_every_pump_has_one_window(scoped):
    pt, _ = scoped
    pumps = [(s, e) for n, s, e in pt.spans if n == "bench.pump"]
    windows = [(s, e) for n, s, e in pt.spans if n == "quiver.window"]
    assert len(pumps) >= 2
    for s, e in pumps:
        assert sum(1 for ws, we in windows if s <= ws and we <= e) == 1
    names = {n for n, _, _ in pt.spans}
    assert {"quiver.coalesce", "quiver.launch", "quiver.encode",
            "quiver.dispatch", "quiver.finalize",
            "quiver.scatter"} <= names


def test_scopes_cover_the_plan_modules_leaf_time(scoped):
    pt, scopes = scoped
    by = pt.scope_seconds(scopes["module"], scopes["ops"])
    leaf = sum(by.values())
    assert leaf > 0
    unmapped = pt.unmapped_ops(scopes["module"], scopes["ops"])
    mapped = 1 - by.get("unmapped", 0.0) / leaf
    print(f"mapped {mapped:.4f}; unmapped "
          f"{sorted(unmapped.items(), key=lambda x: -x[1])[:20]}")
    assert mapped >= 0.95
    for scope in ("quiver.beam.pick", "quiver.beam.merge",
                  "quiver.beam.distance", "quiver.beam.visited",
                  "quiver.beam.dedup", "quiver.rerank"):
        assert by.get(scope, 0.0) > 0, scope


def test_idle_by_innermost_span_adds_up_to_the_reducers_idle(scoped):
    pt, _ = scoped
    reduced = reduce_bench(SCOPED)
    idle = pt.idle_by_innermost()
    assert all(v >= 0 for v in idle.values())
    assert sum(idle.values()) == pytest.approx(
        reduced.window_s - reduced.busy_s(), rel=1e-9, abs=1e-9)
