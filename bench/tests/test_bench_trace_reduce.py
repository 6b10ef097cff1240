"""The trace reduction on a small trace in the profiler's layout
(``data/sweep_trace.pbtxt``): busy union, idle share, kernel time by name
and idle gaps attributed to host spans."""
import os

import pytest

from bench import trace_reduce

DATA = os.path.join(os.path.dirname(__file__), "data", "sweep_trace.pbtxt")


@pytest.fixture(scope="module")
def reduction():
    from jax.profiler import ProfileData
    with open(DATA) as f:
        text = f.read()
    return trace_reduce.reduce_profile(ProfileData.from_text_proto(text))


def test_window_is_the_bench_window_span(reduction):
    assert reduction.window_s == pytest.approx(10.5e-6)
    assert reduction.devices == 1


def test_busy_is_the_union_of_ops_clipped_to_the_window(reduction):
    # [1.0, 1.2] (copy.1 clipped) + [1.5, 5.8] (kernel and an overlapping
    # fusion) + [6.6, 10.6] us; the "XLA Modules" line is not counted
    assert reduction.busy_s == pytest.approx(8.5e-6)
    assert reduction.idle_pct == pytest.approx(100 * (1 - 8.5 / 10.5))


def test_kernel_time_by_name(reduction):
    assert reduction.kernel_s(["vqs_bf"]) == pytest.approx(8.0e-6)
    assert reduction.kernel_s(["no_such_kernel"]) == 0
    assert reduction.op_s["copy.1"] == pytest.approx(0.2e-6)


def test_gaps_go_to_the_innermost_host_span(reduction):
    # Python-thread events and bench spans label gaps; other threads'
    # events (the runtime's "main/1360") do not
    assert reduction.gaps == pytest.approx({
        "np.asarray(jax.Array)": 0.3e-6,   # [1.2, 1.5], inside a call
        "bench.sweep.keys": 0.8e-6,        # [5.8, 6.6] mid-point in keys
        "host:other": 0.9e-6})             # [10.6, 11.5] after the calls
    b = reduction.breakdown()
    assert b["idle_gaps"][0] == ["host:other", pytest.approx(0.9e-6)]
    assert b["device_ops"][0][0] == "vqs_bf_pallas.1"


@pytest.mark.parametrize("intervals,union", [
    ([(0, 2), (1, 3), (5, 6)], [(0, 3), (5, 6)]),
    ([(4, 5), (0, 1), (1, 2)], [(0, 2), (4, 5)]),
    ([(0, 10), (2, 3)], [(0, 10)]),
])
def test_union(intervals, union):
    assert trace_reduce.union(intervals) == union


def test_a_trace_without_the_window_span_is_refused():
    from jax.profiler import ProfileData
    with open(DATA) as f:
        text = f.read().replace('"bench.window"', '"other"')
    with pytest.raises(ValueError, match="bench.window"):
        trace_reduce.reduce_profile(ProfileData.from_text_proto(text))


def test_a_recorded_chip_trace():
    """A trace recorded on one TPU v5e: the vqs-bf kernel cell, G = 2,
    two calls of about 6.5 s in the traced window."""
    from jax.profiler import ProfileData
    r = trace_reduce.reduce_profile(ProfileData.from_file(os.path.join(
        os.path.dirname(DATA), "kernel_sweep.xplane.pb")))
    assert r.devices == 1
    assert r.window_s == pytest.approx(12.933376436)
    assert r.busy_s == pytest.approx(12.926569882)
    assert r.idle_pct == pytest.approx(0.0526278, rel=1e-4)
    assert r.kernel_s(["vqs_bf"]) == pytest.approx(12.920721886)
    assert r.breakdown()["device_ops"][0][0] == "vqs_bf_pallas.1"
    assert r.breakdown()["idle_gaps"][0][0] == "bench.sweep.call"
