"""The trace reduction, on a small trace recorded on one v5e
(``record_trace.py``: a 4,096-vector index of the 768-d configuration
serving the closed batch mix under the profiler)."""

import pathlib

import numpy as np
import pytest

from harness import reader
from trace_reduce import WINDOW_SPAN, load, reduce, short_name

TRACE = pathlib.Path(__file__).parent / "data" / "v5e_batch_small.xplane.pb.gz"


@pytest.fixture(scope="module")
def reduced():
    return reduce(TRACE)


def naive_busy_s(events, w0, w1) -> float:
    """Busy time by walking a 1 us grid: slow, plain, independent of
    ``union_seconds``."""
    step = 1000.0
    grid = np.arange(w0, w1, step)
    busy = np.zeros(len(grid), dtype=bool)
    for s, e in events:
        busy |= (grid >= s) & (grid < e)
    return busy.sum() * step / 1e9


def test_one_window_and_one_device(reduced):
    assert list(reduced.devices) == [0]
    assert 0 < reduced.window_s < 5
    names = {n for n, _, _ in reduced.spans}
    assert {WINDOW_SPAN, "bench.submit", "bench.pump",
            "bench.collect"} <= names


def test_busy_is_the_union_of_op_intervals(reduced):
    w0, w1 = reduced.window
    pd = load(TRACE)
    events = []
    for plane in pd.planes:
        if plane.name == "/device:TPU:0":
            for line in plane.lines:
                if line.name == "XLA Ops":
                    events += [(max(ev.start_ns, w0), min(ev.end_ns, w1))
                               for ev in line.events
                               if ev.end_ns > w0 and ev.start_ns < w1]
    assert events
    busy = reduced.busy_s()
    assert 0 < busy <= reduced.window_s
    assert busy == pytest.approx(naive_busy_s(events, w0, w1), abs=2e-6 *
                                 len(events) + 1e-5)


def test_idle_by_span_adds_up_to_the_idle_time(reduced):
    idle = reduced.idle_by_span()
    assert all(v >= 0 for v in idle.values())
    assert sum(idle.values()) == pytest.approx(
        reduced.window_s - reduced.busy_s(), rel=1e-9, abs=1e-9)


def test_kernel_and_beam_are_found_by_name(reduced):
    ops = reduced.op_seconds()
    assert any(n.startswith("while") for n in ops)
    kernel = reduced.op_seconds(r"bq_distance")
    assert kernel and all(v > 0 for v in kernel.values())
    # the whole HLO instruction is cut to its own name
    assert all(" = " not in n for n in ops)
    assert short_name("%while.110 = (s32[2]) while(%t), body=%b") == \
        "while.110"


def test_roofline_reads_under_100_percent_on_the_recorded_trace(reduced):
    # the recorded window served one closed-mix pump of 256 queries;
    # 1,000 evaluations each is above what the beam reads at ef=64
    rec = {"trace": reduced, "dim": 768,
           "nav": {"evals": {"count": 256, "sum": 256 * 1000.0}},
           "peaks": {"hbm_bytes_per_s": 819e9}}
    share = reader("bq_distance_roofline.batch")(rec)
    assert 0 < share < 100
