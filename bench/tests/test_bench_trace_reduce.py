"""The trace reduction on a trace recorded on a TPU v5e: a stacked epoch
of four 2^28-bit ``x & y`` queries, a singleton one, and their five
popcounts, inside a ``bench.window`` span. Expected values were read from
the file with a separate script that uses no benchmark code."""

from pathlib import Path

import pytest

from bench import harness, trace_reduce

TRACE = Path(__file__).parent / "data" / "probe.xplane.pb"
WINDOW_NS = 114867517.0 - 47572629.0
OFFSET_NS = 1629830.0        # device clock behind the host's
OPS = 78
OP_NS = 52996454.0           # summed op time = their union here


@pytest.fixture(scope="module")
def red():
    return trace_reduce.reduce_file(str(TRACE))


def test_window_and_clock(red):
    assert red.window_ns == WINDOW_NS
    assert red.clock_offset_ns == OFFSET_NS
    assert red.devices == 1


def test_device_ops_union_and_idle(red):
    assert len(red.ops) == OPS
    assert red.op_ns == OP_NS
    assert red.busy_ns == OP_NS
    assert red.idle_pct == pytest.approx(100 * (1 - OP_NS / WINDOW_NS))


def test_ops_by_label_tell_kernels_apart(red):
    by = dict(red.ops_by_label())
    assert by["jit_compute:fused_bitwise_stacked"] == 16768835.0
    assert by["jit_compute:fused_bitwise"] == 4108913.0
    assert by["jit_popcount_rows:popcount_rows"] == pytest.approx(
        5 * 3.76e6, rel=0.01)
    assert sum(by.values()) == OP_NS


def test_idle_time_is_attributed_to_host_spans(red):
    idle = dict(red.idle_by_host())
    assert sum(idle.values()) == pytest.approx(WINDOW_NS - OP_NS)
    assert set(idle) <= {"bench.popcount", "bench.frontend", "bench.loop"}
    assert red.host_ns("bench.popcount") > red.host_ns("bench.frontend")


def test_interval_helpers():
    busy = trace_reduce.union([(5, 9), (0, 2), (1, 3), (8, 12)], 0, 10)
    assert busy == [(0, 3), (5, 10)]
    assert trace_reduce.gaps(busy, 0, 11) == [(3, 5), (10, 11)]


def test_unknown_device_kind_is_an_error():
    peaks = trace_reduce.load_peaks(str(harness.PEAKS), "TPU v5 lite")
    assert peaks["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        trace_reduce.load_peaks(str(harness.PEAKS), "TPU v9 imaginary")
    with pytest.raises(KeyError):
        trace_reduce.load_peaks(str(harness.PEAKS), "source")
