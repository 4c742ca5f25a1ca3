"""CPU rehearsal of tpch_q6_sf30.streams4 at about 10^5 rows (not whole
512-word blocks, so the popcount's last block is partial): answered
counts against reference.py, the reference against numpy, the plan's
truth table against ``TpchTable.oracle``, the programs the warm-up
reaches, the least bytes, and a control the check refuses."""

import dataclasses
import time

import numpy as np
import pytest

from bench import control, harness, loadgen
from repro.apps.bitweaving_db import BitWeavingColumn, TpchTable
from repro.core import expr as E
from repro.core.bitvector import unpack_bits

CELL = "tpch_q6_sf30.streams4"
N_ROWS = 100_003            # 3,126 words: neither 128- nor 512-word whole
SEED = 2 ** 33 + 5


@pytest.fixture(scope="module")
def cell():
    return harness.resolve(CELL, cfg_overrides={"n_rows": N_ROWS})


def _narrow(cell, **values):
    """The cell with its mix's parameters cut to ``values``, so that the
    warm-up compiles few programs in interpret mode."""
    params = tuple(loadgen.Param(p.name, tuple(values.get(p.name, p.values)))
                   for p in cell.mix.params)
    return dataclasses.replace(cell, mix=dataclasses.replace(
        cell.mix, params=params))


def test_cell_runs_correct(cell):
    tiny = _narrow(cell, year=(1994, 1997), discount=(6,), quantity=(24,))
    r = harness.run_cell(tiny, SEED, 1.0, False, time.perf_counter())
    assert r["correct"] is True
    assert r["failed"] == 0 and r["attempted"] > tiny.mix.clients
    assert set(r["metrics"]) == {"req_per_s", "p50_ms", "p95_ms",
                                 "setup_s"}
    assert all(c["value"] == 0 for c in r["checks"].values())


def _raw_rows(cell, seed):
    """Each column's values in row order, for the first ``n_rows``."""
    raw = cell.data.raw(seed, cell.cfg)
    return {k: np.asarray(v).T.reshape(-1)[:N_ROWS] for k, v in raw.items()}


def test_reference_matches_numpy(cell):
    v = _raw_rows(cell, SEED)
    assert v["l_shipdate"].min() >= 1 and v["l_shipdate"].max() <= 2526
    assert set(np.unique(v["l_discount"])) == set(range(11))
    assert set(np.unique(v["l_quantity"])) == set(range(1, 51))
    keys = [("q6", 1994, 6, 24), ("q6", 1997, 2, 25), ("q6", 1993, 9, 24)]
    got = cell.reference.answers(SEED, cell.cfg, keys)
    for _, year, disc, qty in keys:
        lo = np.datetime64(f"{year}-01-01") - np.datetime64("1992-01-01")
        hi = np.datetime64(f"{year + 1}-01-01") - np.datetime64("1992-01-01")
        sel = ((v["l_shipdate"] >= lo.astype(int))
               & (v["l_shipdate"] < hi.astype(int))
               & (np.abs(v["l_discount"] - disc) <= 1)
               & (v["l_quantity"] < qty))
        assert got[("q6", year, disc, qty)] == int(sel.sum()) > 0


def test_planes_are_the_values_bit_sliced(cell):
    v = _raw_rows(cell, SEED)
    planes = cell.data.build(SEED, cell.cfg)
    for col, bits in cell.cfg["columns"].items():
        assert len(planes[col]) == bits
        got = sum(np.asarray(unpack_bits(p), np.int64) << (bits - 1 - i)
                  for i, p in enumerate(planes[col]))
        assert np.array_equal(got[:N_ROWS], v[col])
        assert not got[N_ROWS:].any()


def test_plan_truth_table_matches_the_oracle(cell):
    """The plan, evaluated over planes of columns that hold every value
    of their width in every combination, selects what the oracle does,
    for Q6's own ranges and for edge ranges of each column."""
    widths = {"a": 3, "b": 2, "c": 4}
    grid = np.stack(np.meshgrid(*[np.arange(1 << b) for b in
                                  widths.values()], indexing="ij"),
                    -1).reshape(-1, len(widths)).astype(np.uint32)
    values = {col: grid[:, k] for k, col in enumerate(widths)}
    table = TpchTable(len(grid), values, {
        col: BitWeavingColumn.from_values(values[col], b)
        for col, b in widths.items()})
    planes = {col: list(c.planes) for col, c in table.columns.items()}
    for specs in [(("a", 0, 7),), (("a", 3, 3), ("b", 1, 2)),
                  (("a", 2, 6), ("b", 0, 0), ("c", 5, 15)),
                  (("c", 0, 9), ("a", 7, 7))]:
        q = cell.data.Query(None, None, specs)
        expr, env = cell.data.plan(planes, q)
        got = np.asarray(unpack_bits(E.eval_expr(expr, env), len(grid)))
        assert np.array_equal(got, table.oracle(specs)), specs


def test_q6_plan_matches_the_oracle(cell):
    """Q6's plans over random values of its own columns, spread over the
    year boundaries, against ``TpchTable.oracle``."""
    rng = np.random.default_rng(7)
    n = 4096
    values = {"l_shipdate": rng.integers(360, 2200, n).astype(np.uint32),
              "l_discount": rng.integers(0, 11, n).astype(np.uint32),
              "l_quantity": rng.integers(1, 51, n).astype(np.uint32)}
    table = TpchTable(n, values, {
        col: BitWeavingColumn.from_values(values[col], b)
        for col, b in cell.cfg["columns"].items()})
    planes = {col: list(c.planes) for col, c in table.columns.items()}
    for params in [dict(year=1993, discount=2, quantity=24),
                   dict(year=1997, discount=9, quantity=25)]:
        q, = cell.data.queries(["q6"], params, cell.cfg)
        expr, env = cell.data.plan(planes, q)
        assert len(env) == 22
        got = np.asarray(unpack_bits(E.eval_expr(expr, env), n))
        want = table.oracle(q.specs)
        assert want.sum() > 0 and np.array_equal(got, want)


def test_warm_up_reaches_80_programs(cell):
    mix = harness.resolve(CELL).mix
    examples = cell.data.program_examples(
        mix.parts, {p.name: p.values for p in mix.params}, cell.cfg)
    assert len(examples) == 80
    assert len({q.program for q, _ in examples}) == 80
    assert all(n == 1 for _, n in examples)
    assert mix.clients == 4 and mix.loop == "closed"
    assert harness.resolve(CELL).cfg["frontend"]["max_batch"] == 1


def test_least_bytes(cell):
    full = harness.resolve(CELL).cfg
    assert full["n_rows"] == 180_000_000
    assert sum(full["columns"].values()) == 22
    q, = cell.data.queries(["q6"], dict(year=1995, discount=5, quantity=24),
                           full)
    plane = 180_000_000 // 8
    assert cell.data.least_bytes([q], full) == 23 * plane
    assert cell.data.least_bytes([q, q], full) == 46 * plane
    from bench.configs.tpch_q6_sf30 import work
    assert work.PLANES == sum(full["columns"].values())
    assert work.selection_bytes(46 * plane) == 2 * plane


def test_control_is_refused(cell):
    """The reference summed in bfloat16, put in the program's place,
    fails the check."""
    tiny = _narrow(cell)
    r = control.run(tiny, SEED, 12)
    assert r["correct"] is False
    assert r["checks"]["count_gap_max"]["value"] > 0


def test_q6_readers_on_a_synthetic_trace():
    """Each roofline reads its own programs' device time: the fused
    predicate's for the scan, the popcount's kernel, pad and reshape
    (a copy or a loop) for the popcount."""
    from types import SimpleNamespace
    plane = 180_000_000 // 8
    ops = [("jit_ambit_query:fused_bitwise", 0, 4e6),
           ("jit_ambit_query:pad", 4e6, 6e6),
           ("jit_popcount_rows:popcount_rows", 6e6, 7e6),
           ("jit__pad:pad", 7e6, 7.5e6), ("jit_reshape:while", 7.5e6, 8e6),
           ("jit_other:copy", 8e6, 9e6)]
    ctx = SimpleNamespace(trace=SimpleNamespace(ops=ops),
                          query_bytes=2 * 23 * plane, requests=2,
                          peaks={"hbm_bytes_per_s": 819e9})
    scan = harness.metric_reader("q6_scan_hbm_roofline")(ctx)
    pop = harness.metric_reader("q6_popcount_hbm_roofline")(ctx)
    assert scan == pytest.approx(100 * 2 * 23 * plane / 819e9 * 1e9 / 6e6)
    assert pop == pytest.approx(100 * 2 * plane / 819e9 * 1e9 / 2e6)
    assert harness.metric_reader("q6_scan_hbm_roofline")(
        SimpleNamespace(trace=None, query_bytes=1)) is None
