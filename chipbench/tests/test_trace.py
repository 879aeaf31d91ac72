"""The trace reduction, on a small trace recorded on the CPU
(`data/cpu_window.xplane.pb`: a `window` span around three `sweep.call`
spans, each running a jitted tanh and two matrix products, each followed
by a 4 ms `sweep.post` span and 2 ms outside any span, all idle)."""
import os

import numpy as np
import pytest

from chipbench import trace as T

DATA = os.path.join(os.path.dirname(__file__), "data", "cpu_window.xplane.pb")


@pytest.fixture(scope="module")
def pd():
    from jax.profiler import ProfileData

    return ProfileData.from_file(DATA)


def _coverage_ns(intervals, lo, hi):
    """Covered nanoseconds of [lo, hi), counted on a grid: an independent
    check of `union`."""
    grid = np.zeros(int(hi - lo), bool)
    for s, e in intervals:
        grid[max(int(s - lo), 0):max(int(e - lo), 0)] = True
    return int(grid.sum())


def test_summary_of_recorded_cpu_trace(pd):
    s = T.summarize(pd, window_span="window",
                    span_names=("sweep.call", "sweep.post"))
    (_, lo, hi), = T.host_spans(pd, ["window"])
    assert s.window_s == pytest.approx((hi - lo) * 1e-9)
    assert s.window_s == pytest.approx(0.02195, abs=1e-4)
    ops = T.device_ops(pd)["cpu:0"]
    assert len(ops) == 9  # three calls of three operations
    busy = _coverage_ns([(a, b) for _, a, b in ops], lo, hi)
    assert s.busy_s == pytest.approx(busy * 1e-9, rel=1e-6)
    assert sum(s.op_s.values()) == pytest.approx(s.busy_s, rel=1e-9)
    assert set(s.op_s) == {"dot_general.2", "dot_general.3", "wrapped_tanh"}
    # every idle nanosecond is attributed once
    assert sum(s.idle_s.values()) == pytest.approx(
        s.window_s - s.busy_s, rel=1e-9)
    # the three 4 ms sleeps in sweep.post and 2 ms outside any span
    assert s.idle_s["sweep.post"] == pytest.approx(0.01248, abs=2e-4)
    assert s.idle_s[T.OTHER] == pytest.approx(0.00652, abs=2e-4)
    assert s.ops_matching(r"dot_general") == pytest.approx(
        s.op_s["dot_general.2"] + s.op_s["dot_general.3"])
    assert s.ops_matching(r"no_such_kernel") == 0.0
    top = s.breakdown()
    assert [k for k, _ in top["device_ops"]][0] == "dot_general.2"
    assert top["idle_gaps"][0][0] == "sweep.post"


def test_union_and_gaps():
    assert T.union([(5, 7), (0, 2), (1, 3), (7, 9)]) == [(0, 3), (5, 9)]
    assert T.gaps([(2, 3), (5, 9)], 0, 10) == [(0, 2), (3, 5), (9, 10)]
    assert T.gaps([], 0, 4) == [(0, 4)]


def test_idle_gaps_split_by_span_overlap():
    spans = [("call", 0, 10), ("post", 8, 20)]
    out = T.attribute([(5, 12), (25, 30)], spans)
    assert out == {"call": 5, "post": 4, T.OTHER: 5}


def test_own_time_of_enclosing_operations():
    evs = [("while", 0, 100), ("a", 10, 20), ("b", 15, 30), ("c", 50, 60),
           ("d", 200, 210)]
    assert T.self_times(evs) == {"while": 70, "a": 5, "b": 15, "c": 10,
                                 "d": 10}


def test_tpu_operation_names():
    text = ("%ota_edge_aggregate_kernel.7 = f32[3,256,1,128]{3,2,1,0} "
            "custom-call(f32[3,256,512,128] %pad.48)")
    assert T.op_name(text) == "ota_edge_aggregate_kernel.7"
    assert T.op_name("dot_general.2") == "dot_general.2"
