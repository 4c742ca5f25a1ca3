"""The served query path writes its wall-clock host spans and its stack
counter: a tiny ``QueryFrontend`` over ``AmbitRuntime(backend="pallas")``
(kernels in interpret mode) runs one stacked and one singleton epoch and
counts every answer under the JAX profiler, and the trace's host plane
holds every span of ``repro.obs`` with its stats, nested where the
layers nest."""

import glob
from collections import defaultdict

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro import obs
from repro.core import BitVector, Expr
from repro.pim import AmbitRuntime
from repro.serve import QueryFrontend

X, Y = Expr.var("x"), Expr.var("y")
N_BITS = 300
STACKED = 4                     # queries in the stacked epoch (max_batch)

SPANS = (obs.FRONTEND_SUBMIT, obs.FRONTEND_DRAIN, obs.SCHEDULER_DRAIN,
         obs.PLANNER_EPOCH, obs.PLANNER_STACK, obs.PLANNER_LAUNCH,
         obs.STORE_POPCOUNT, obs.STORE_POPCOUNT_WAIT)


@pytest.fixture(scope="module")
def run(tmp_path_factory):
    """One fill drain of ``STACKED`` ``x & y`` queries (one stacked
    epoch), one flushed ``x | y`` query (a singleton epoch), then a
    popcount of every answer, all inside one profiler trace. Returns the
    host spans by name, the runtime, the counter readings after each
    epoch, and the expected counts."""
    rng = np.random.default_rng(13)
    rt = AmbitRuntime(backend="pallas")
    bits = rng.integers(0, 2, (STACKED, 2, N_BITS)).astype(bool)
    envs = [{"x": rt.put(BitVector.from_bits(b[0])),
             "y": rt.put(BitVector.from_bits(b[1]))} for b in bits]
    fe = QueryFrontend(rt, max_batch=STACKED)
    stack_bytes = rt.metrics.counter(obs.PLANNER_STACK_BYTES)
    log_dir = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(log_dir)
    try:
        for env in envs:
            fe.submit("t", X & Y, env)
        after_stacked = stack_bytes.total()
        fe.submit("t", X | Y, envs[0])
        fe.flush()
        after_singleton = stack_bytes.total()
        done = fe.take_completed()
        counts = [rt.popcount(q.result) for q in done]
    finally:
        jax.profiler.stop_trace()
    want = [int((b[0] & b[1]).sum()) for b in bits]
    want.append(int((bits[0, 0] | bits[0, 1]).sum()))
    path, = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    spans = defaultdict(list)
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for ln in plane.lines:
            for ev in ln.events:
                if ev.name.startswith("repro."):
                    stats = {k: v for k, v in ev.stats}
                    spans[ev.name].append((ev.start_ns, ev.end_ns, stats))
    return dict(spans=spans, rt=rt, envs=envs, counts=counts, want=want,
                after_stacked=after_stacked,
                after_singleton=after_singleton)


def _inside(span, outers) -> bool:
    s, e, _ = span
    return any(os_ <= s and e <= oe for os_, oe, _ in outers)


def test_answers_are_right(run):
    assert run["counts"] == run["want"]


def test_every_span_is_written_once_per_layer_call(run):
    spans = run["spans"]
    assert set(spans) == set(SPANS)
    assert len(spans[obs.FRONTEND_SUBMIT]) == STACKED + 1
    assert len(spans[obs.FRONTEND_DRAIN]) == 2
    assert len(spans[obs.SCHEDULER_DRAIN]) == 2
    assert len(spans[obs.PLANNER_EPOCH]) == 2
    assert len(spans[obs.PLANNER_STACK]) == 1       # the stacked epoch
    assert len(spans[obs.PLANNER_LAUNCH]) == 2      # one per epoch
    assert len(spans[obs.STORE_POPCOUNT]) == STACKED + 1
    assert len(spans[obs.STORE_POPCOUNT_WAIT]) == STACKED + 1


def test_spans_nest_as_the_layers_do(run):
    spans = run["spans"]
    frontend = spans[obs.FRONTEND_SUBMIT] + spans[obs.FRONTEND_DRAIN]
    for sp in spans[obs.SCHEDULER_DRAIN]:
        assert _inside(sp, spans[obs.FRONTEND_DRAIN])
        assert _inside(sp, frontend)
    for sp in spans[obs.PLANNER_EPOCH]:
        assert _inside(sp, spans[obs.SCHEDULER_DRAIN])
    for sp in spans[obs.PLANNER_STACK] + spans[obs.PLANNER_LAUNCH]:
        assert _inside(sp, spans[obs.PLANNER_EPOCH])
    for sp in spans[obs.STORE_POPCOUNT_WAIT]:
        assert _inside(sp, spans[obs.STORE_POPCOUNT])
    # the fill drain runs inside the submission that filled the window;
    # the flush drain runs outside any submission
    fill, flush = sorted(spans[obs.FRONTEND_DRAIN])
    assert _inside(fill, spans[obs.FRONTEND_SUBMIT])
    assert not _inside(flush, spans[obs.FRONTEND_SUBMIT])


def test_span_stats(run):
    spans = run["spans"]
    assert [st["seq"] for _, _, st in sorted(spans[obs.FRONTEND_SUBMIT])] \
        == list(range(STACKED + 1))
    drains = [st for _, _, st in sorted(spans[obs.FRONTEND_DRAIN])]
    assert drains == [{"reason": "fill", "queries": STACKED},
                      {"reason": "flush", "queries": 1}]
    assert [st["tickets"] for _, _, st in
            sorted(spans[obs.SCHEDULER_DRAIN])] == [STACKED, 1]
    epochs = [st for _, _, st in sorted(spans[obs.PLANNER_EPOCH])]
    assert [st["queries"] for st in epochs] == [STACKED, 1]
    assert [st["first_ticket"] for st in epochs] == [0, STACKED]
    (_, _, stack), = spans[obs.PLANNER_STACK]
    assert stack == {"operands": 2}


def test_stack_counter_counts_stacked_epochs_only(run):
    per_operand = run["envs"][0]["x"].device_bytes
    assert run["after_stacked"] == STACKED * 2 * per_operand
    assert run["after_singleton"] == run["after_stacked"]


Q6_COLUMNS = (("l_shipdate", 12), ("l_discount", 4), ("l_quantity", 6))
Q6_ROWS = 300
Q6_SPECS = [(("l_shipdate", 366, 730), ("l_discount", 5, 7),
             ("l_quantity", 0, 23))] * 2 + \
    [(("l_shipdate", 1461, 1826), ("l_discount", 1, 3),
      ("l_quantity", 0, 24))]


@pytest.fixture(scope="module")
def q6_runs(tmp_path_factory):
    """``q6_runs(rows)``: a tiny TPC-H Q6 over ``rows`` rows through the
    frontend under the profiler, run once per row count: two equal
    predicates fill a stacked epoch, a third is flushed alone; every plan
    comes from ``predicate_plan``."""
    from repro.apps.bitweaving_db import TpchTable, predicate_plan
    runs = {}

    def run(rows):
        if rows in runs:
            return runs[rows]
        table = TpchTable.synthesize(rows, seed=5, columns=Q6_COLUMNS)
        rt = AmbitRuntime(backend="pallas")
        fe = QueryFrontend(rt, max_batch=2)
        operand_bytes = rt.metrics.counter(obs.PLANNER_OPERAND_BYTES)
        log_dir = str(tmp_path_factory.mktemp("q6_trace"))
        jax.profiler.start_trace(log_dir)
        try:
            for specs in Q6_SPECS:
                fe.submit("t", *predicate_plan(table, specs, rt))
            fe.flush()
            counts = [rt.popcount(q.result) for q in fe.take_completed()]
        finally:
            jax.profiler.stop_trace()
        path, = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
        plans = [{k: v for k, v in ev.stats}
                 for plane in ProfileData.from_file(path).planes
                 if plane.name.startswith("/host:")
                 for ln in plane.lines for ev in ln.events
                 if ev.name == obs.PLAN_PREDICATE]
        runs[rows] = dict(table=table, counts=counts, plans=plans,
                          operand_bytes=operand_bytes.total())
        return runs[rows]

    return run


@pytest.fixture(scope="module")
def q6_run(q6_runs):
    return q6_runs(Q6_ROWS)


def test_q6_answers_are_right(q6_run):
    table = q6_run["table"]
    assert q6_run["counts"] == [int(table.oracle(s).sum()) for s in Q6_SPECS]


def test_plan_span_per_query(q6_run):
    assert q6_run["plans"] == [{"terms": 3, "operands": 22}] * len(Q6_SPECS)


@pytest.mark.parametrize("rows,per_operand", [
    # 10 words: one 1024-word tile, as many bytes as 8 rows of 128 lanes
    (Q6_ROWS, 8 * 128 * 4),
    # 1000 words: one 1024-word tile, where 8 rows of 1024 lanes held 8x
    (32_000, 1024 * 4),
], ids=["10_words", "1000_words"])
def test_operand_bytes_count_the_padded_operands(q6_runs, rows, per_operand):
    """Each launch adds its operands as the fused program receives them:
    one row in whole 1024-word tiles, 4 bytes a word, in the singleton
    and the stacked epoch alike; every count stays right."""
    run = q6_runs(rows)
    assert run["counts"] == [int(run["table"].oracle(s).sum())
                             for s in Q6_SPECS]
    assert run["operand_bytes"] == len(Q6_SPECS) * 22 * per_operand
