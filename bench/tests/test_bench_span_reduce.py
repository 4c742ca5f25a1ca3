"""The reduction of the program's own host spans: time outside inner
spans by intervals, each layer's host time per request, idle device
time put down to the innermost span, and all of it read from a served
window of the tiny ``wau_16m.dash16`` cell traced on the CPU."""

import glob
import time
from pathlib import Path

import pytest

from bench import harness, span_reduce, trace_reduce
from bench.tests import rehearse
from repro import obs

TRACE = Path(__file__).parent / "data" / "probe.xplane.pb"


def test_outside_counts_time_once_by_intervals():
    inner = [(0, 10), (5, 15), (40, 50)]
    outer = [(2, 4), (8, 20), (45, 60), (70, 80)]
    # inner covers [0, 15] and [40, 50]; outer takes 2 + 7 of the first
    # and 5 of the second
    assert span_reduce.outside(inner, outer, 0, 100) == 25 - 14
    assert span_reduce.outside(inner, [], 0, 100) == 25
    assert span_reduce.outside(inner, outer, 3, 42) == 14 - 1 - 7
    assert span_reduce.outside([], outer, 0, 100) == 0


def test_layers_of_nested_spans():
    spans = {
        obs.FRONTEND_SUBMIT: [(0, 100)],
        obs.FRONTEND_DRAIN: [(10, 90), (150, 170)],
        obs.SCHEDULER_DRAIN: [(20, 80), (155, 165)],
        obs.PLANNER_EPOCH: [(30, 60), (156, 160)],
        obs.PLANNER_STACK: [(31, 40)],
        obs.PLANNER_LAUNCH: [(41, 50), (157, 159)],
        obs.STORE_POPCOUNT: [(200, 230)],
        obs.STORE_POPCOUNT_WAIT: [(205, 225)],
    }
    got = span_reduce.layer_ms_per_req((0, 1000), spans, requests=2)
    ms = {k: v * 1e6 * 2 for k, v in got.items()}   # back to ns in all
    assert ms == pytest.approx({
        "frontend_self_ms_per_req": 100 + 20 - 60 - 10,
        "scheduler_self_ms_per_req": 60 + 10 - 30 - 4,
        "planner_host_ms_per_req": 34,
        "stack_host_ms_per_req": 9,
        "launch_host_ms_per_req": 11,
        "popcount_host_ms_per_req": 10,
        "popcount_wait_ms_per_req": 20,
    })
    none = span_reduce.layer_ms_per_req((0, 1000), {}, requests=2)
    assert set(none) == set(span_reduce.LAYERS)
    assert all(v is None for v in none.values())


def test_idle_gap_goes_to_the_innermost_span():
    spans = {"bench.frontend": [(0, 100)],
             obs.FRONTEND_DRAIN: [(0, 90)],
             obs.SCHEDULER_DRAIN: [(10, 40)],
             obs.PLANNER_EPOCH: [(20, 30)],
             obs.STORE_POPCOUNT: [(120, 140)]}
    busy = [(4, 6), (22, 24), (26, 28), (34, 36), (60, 86), (95, 96),
            (125, 130), (135, 136), (180, 200)]
    idle = dict(span_reduce.idle_by_innermost(busy, (0, 200), spans))
    assert idle == {
        obs.FRONTEND_DRAIN: 4 + 24,     # [0, 4), and [36, 60) after its
        obs.SCHEDULER_DRAIN: 16 + 6,    # child ended
        obs.PLANNER_EPOCH: 2,
        "bench.frontend": 9,            # [86, 95)
        obs.STORE_POPCOUNT: 5,
        "bench.loop": 29 + 44,          # [96, 125), [136, 180)
    }
    # ``idle_by_host`` takes the latest span to start, and so puts the
    # gaps that follow a child's end inside its parent on bench.loop
    red = trace_reduce.Reduction(window=(0, 200), devices=1, ops=[],
                                 busy=busy, host_spans=spans,
                                 clock_offset_ns=0.0)
    assert dict(red.idle_by_host())["bench.loop"] == 73 + 24 + 6 + 9


def test_innermost_rule_agrees_where_spans_do_not_nest():
    """On the recorded chip trace, whose ``bench.*`` spans do not nest,
    the innermost rule gives what ``idle_by_host`` gives."""
    red = trace_reduce.reduce_file(str(TRACE))
    new = dict(span_reduce.idle_by_innermost(red.busy, red.window,
                                             red.host_spans))
    old = dict(red.idle_by_host())
    assert new.keys() == old.keys()
    for name in old:
        assert new[name] == pytest.approx(old[name])


def test_stack_mib_per_query():
    assert span_reduce.stack_mib_per_query(3 * 2 ** 21, 2) == 3.0
    assert span_reduce.stack_mib_per_query(0, 5) == 0.0
    assert span_reduce.stack_mib_per_query(0, 0) is None


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """A window of the tiny dash16 cell's closed loop, traced: the
    program's spans, the window, requests and queries completed in it,
    and the window's stack bytes."""
    import jax
    from jax.profiler import ProfileData

    from repro.pim import AmbitRuntime
    from repro.serve import QueryFrontend

    cell = rehearse.tiny_cell("wau_16m.dash16")
    data = cell.data.build(rehearse.SEED, cell.cfg)
    rt = AmbitRuntime(backend="pallas")
    catalog = cell.data.load(rt, data, cell.cfg)
    fe = QueryFrontend(rt, max_batch=cell.cfg["frontend"]["max_batch"])
    t0 = time.perf_counter_ns()
    drv = harness.ClosedLoop(cell, rehearse.SEED, fe, rt, catalog,
                             lambda: time.perf_counter_ns() - t0,
                             traced=True)
    stack = rt.metrics.counter(obs.PLANNER_STACK_BYTES)
    done = []

    def on_done(req):
        done.append(req)
        drv.issue(req.client)

    log_dir = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(log_dir)
    try:
        for c in range(cell.mix.clients):
            drv.issue(c)
        b0, q0 = stack.total(), fe.report_counters.completed
        with drv.span(trace_reduce.WINDOW_SPAN):
            while len(done) < cell.mix.clients:
                if not drv.collect(on_done):
                    drv.tick()
        b1, q1 = stack.total(), fe.report_counters.completed
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    window, spans = span_reduce.host_spans(ProfileData.from_file(path))
    return dict(window=window, spans=spans, requests=len(done),
                queries=q1 - q0, stack_bytes=b1 - b0,
                bitmap_bytes=2 * cell.cfg["n_users"] // 8)


def test_a_served_window_feeds_every_layer(served):
    got = span_reduce.layer_ms_per_req(served["window"], served["spans"],
                                       served["requests"])
    assert set(got) == set(span_reduce.LAYERS)
    assert all(v is not None and v > 0 for v in got.values()), got
    mib = span_reduce.stack_mib_per_query(served["stack_bytes"],
                                          served["queries"])
    assert mib > 0                  # dash16 stacks its epochs
    assert served["stack_bytes"] % served["bitmap_bytes"] == 0


def test_layers_add_up_to_the_spans_around_them(served):
    """The frontend's, scheduler's and planner's self times add up to
    the frontend spans' union; the popcount's two parts to its span."""
    window, spans, n = served["window"], served["spans"], served["requests"]
    got = span_reduce.layer_ms_per_req(window, spans, n)

    def union_ms(*names):
        ivs = [iv for name in names for iv in spans[name]]
        return span_reduce.outside(ivs, [], *window) / 1e6 / n

    assert (got["frontend_self_ms_per_req"]
            + got["scheduler_self_ms_per_req"]
            + got["planner_host_ms_per_req"]) == pytest.approx(
        union_ms(obs.FRONTEND_SUBMIT, obs.FRONTEND_DRAIN), rel=1e-9)
    assert (got["popcount_host_ms_per_req"]
            + got["popcount_wait_ms_per_req"]) == pytest.approx(
        union_ms(obs.STORE_POPCOUNT), rel=1e-9)
    assert got["planner_host_ms_per_req"] >= (
        got["stack_host_ms_per_req"] + got["launch_host_ms_per_req"])
