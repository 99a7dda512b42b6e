"""Recall, spread, interval union and the metric readers'
arithmetic, against values worked out by hand."""

import types

import numpy as np
import pytest

from harness import reader, request_rows
from stats import quartile_spread, recall, union_seconds


def test_recall_counts_shared_ids_per_row():
    served = np.array([[1, 2, 3, 4], [5, 6, 7, 8]])
    truth = np.array([[4, 3, 9, 10], [5, 6, 7, 8]])
    # row 0 shares {3, 4}: 2 of 4; row 1 all 4 -> 6 / 8
    assert recall(served, truth) == pytest.approx(0.75)


def test_union_of_overlapping_intervals():
    # [0,10) u [5,15) u [20,25) u [21,22) = 15 + 5 = 20 ns
    starts = [20, 0, 5, 21]
    ends = [25, 10, 15, 22]
    assert union_seconds(starts, ends) == pytest.approx(20e-9)
    assert union_seconds([], []) == 0.0


def test_quartile_spread_is_pythons_quantiles():
    # statistics.quantiles([1..6], n=4) (exclusive): 1.75, 3.5, 5.25
    assert quartile_spread([1, 2, 3, 4, 5, 6]) == pytest.approx(3.5 / 3.5)
    assert quartile_spread([10, 10, 10, 10]) == 0.0


class FakeTrace:
    def __init__(self, busy, window, kernel_s):
        self.devices = {0: None}
        self._busy = busy
        self.window_s = window
        self._kernel = kernel_s

    def busy_s(self):
        return self._busy

    def op_seconds(self, pattern=None):
        return {"bq_distance": self._kernel}


def record(**kw):
    w = types.SimpleNamespace(queries=1000, seconds=2.0)
    rec = {"trace": None, "nav": {}, "dim": 768, "window": w,
           "spans": {}, "peaks": {"hbm_bytes_per_s": 819e9}}
    rec.update(kw)
    return rec


def test_roofline_is_useful_bytes_over_peak_bandwidth():
    # 1e6 evals x (768/4 + 4) B = 196 MB; at 819 GB/s that is
    # 196e6 / 819e9 s = 0.23932 ms; over 1 ms of kernel time -> 23.932 %
    rec = record(trace=FakeTrace(0.5, 1.0, 1e-3),
                 nav={"evals": {"count": 1000, "sum": 1e6}})
    got = reader("bq_distance_roofline.batch")(rec)
    assert got == pytest.approx(100 * 196e6 / 819e9 / 1e-3)


def test_device_readers():
    rec = record(trace=FakeTrace(0.25, 1.0, 1e-3),
                 nav={"evals": {"count": 4, "sum": 1000.0}})
    assert reader("device.idle_share.batch")(rec) == pytest.approx(75.0)
    # 0.25 s busy over 1000 queries = 250 us per query
    assert reader("device.busy_us_per_query.batch")(rec) == \
        pytest.approx(250.0)
    assert reader("beam.evals_per_query.batch")(rec) == pytest.approx(250.0)


def test_readers_without_a_trace_read_nothing():
    rec = record()
    for name in ("bq_distance_roofline.batch", "device.idle_share.batch",
                 "device.busy_us_per_query.batch",
                 "beam.evals_per_query.batch"):
        assert reader(name)(rec) is None


def test_window_readers():
    rec = record(build_s=37.5, setup_s=52.25, recall=0.955)
    assert reader("qps")(rec) == pytest.approx(500.0)
    assert reader("build_s")(rec) == 37.5
    assert reader("setup_s")(rec) == 52.25
    assert reader("recall_at_10")(rec) == 0.955
    idle = record(window=types.SimpleNamespace(queries=0, seconds=0.0))
    assert reader("qps")(idle) is None


def test_request_rows_cover_the_pool_in_the_seeds_order():
    def first(seed, n=12):
        stream = request_rows(np.random.default_rng(seed), 8, 4)
        return np.stack([next(stream) for _ in range(n)])

    a, b, c = first(2**31 + 1), first(2**31 + 1), first(2**31 + 2)
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)
    # every pass of two requests asks each of the 8 pool rows once
    for rows in (a, c):
        for p in range(0, len(rows), 2):
            assert sorted(rows[p:p + 2].ravel()) == list(range(8))
