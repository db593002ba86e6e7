"""The reduction from a profiler trace to device numbers, on a small
trace recorded on a TPU v5e (2 layers at qwen1.5-4b widths, 0.4 s window)
kept in bench/testdata/."""
import os

import numpy as np
import pytest

from bench import spec, xtrace

TRACE = os.path.join(spec.BENCH, "testdata", "tpu_window.xplane.pb")


def test_union_merges_overlaps():
    total, merged = xtrace._union(np.asarray(
        [[5, 7], [0, 2], [1, 3], [6, 9], [10, 10]], np.float64))
    assert total == 7.0
    np.testing.assert_array_equal(merged, [[0, 3], [5, 9], [10, 10]])
    assert xtrace._union(np.zeros((0, 2)))[0] == 0.0


@pytest.fixture(scope="module")
def trace():
    return xtrace.Trace(TRACE)


def test_window_and_busy(trace):
    assert trace.planes == ["/device:TPU:0"]
    assert 0.3 < trace.window_s < 1.0
    assert 0.0 < trace.busy_s < trace.window_s
    # busy is the union of the clipped op intervals, recomputed by hand
    line = trace.ops["/device:TPU:0"]
    total, end = 0.0, -np.inf
    for s, e in sorted(zip(line.start, line.end)):
        if e > end:
            total += e - max(s, end)
            end = e
    assert trace.busy_s == pytest.approx(total * 1e-9)
    assert line.start.min() >= trace.window[0]
    assert line.end.max() <= trace.window[1]


def test_kernel_time_and_gaps(trace):
    swiglu = trace.op_seconds(_pattern("swiglu_roofline"))
    flash = trace.op_seconds(_pattern("flash_attn_roofline"))
    assert 0.0 < swiglu < trace.busy_s and 0.0 < flash < trace.busy_s
    assert trace.op_seconds(r"^no such op$") == 0.0
    idle = sum(v for _, v in trace.idle_gaps(100))
    assert idle == pytest.approx(trace.window_s - trace.busy_s, rel=1e-6)
    top = trace.top_ops(10)
    assert len(top) <= 10 and all(a[1] >= b[1] for a, b in zip(top, top[1:]))


def _pattern(metric):
    return spec.metric_reader(metric).__globals__["PATTERN"]
