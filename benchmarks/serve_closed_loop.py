"""Closed-loop serving benchmark: Zipfian multi-tenant query replay
through ``serve.QueryFrontend`` (admission quotas + continuous-batching
window) over ``AmbitRuntime.submit/drain``.

Thousands of simulated tenants each keep one query outstanding
(closed-loop: the next arrival fires at the previous completion's
simulated-clock instant), drawn Zipfian over a shared catalog - the
bitmap-index AND queries of Section 8.1 and the BitWeaving range scans
of Section 8.2. Every completion is checked bit-exact against a serial
numpy evaluation (the ``mismatches=0`` token is a structural assertion
CI diffs).

All serving metrics are **ledger-derived**, never wall clock: the
simulated clock advances by the drain timeline - measured DRAM-model ns
on ``ambit_sim``, the deterministic HBM-roofline epoch model on the
accelerator backends - so queries/sec and p50/p99 latency are
bit-reproducible across machines and live in the structural
(integer-token) part of each row. Wall time lives only in the ``us``
column.
"""

from __future__ import annotations

import time
from typing import List, Optional, Tuple

import numpy as np

Row = Tuple[str, float, str]


def _make_tracer(trace_dir: Optional[str]):
    """A live Tracer when tracing was requested, else None (the runtime
    then defaults to the shared zero-overhead NULL_TRACER)."""
    if trace_dir is None:
        return None
    from repro.obs import Tracer
    return Tracer()


def _finish_trace(tracer, trace_dir: Optional[str], name: str) -> None:
    if tracer is None or trace_dir is None:
        return
    import os

    from repro.obs import write_chrome_trace
    os.makedirs(trace_dir, exist_ok=True)
    write_chrome_trace(tracer, os.path.join(trace_dir, f"{name}.json"))


def _obs_tokens(rt, rep, max_batch: int) -> str:
    """Structural observability tokens (integers, so compare.py diffs
    them): epoch-packing efficiency and mean per-bank busy%% over the
    serving span. Backends without per-bank accounting (the fused
    accelerator path) report bank_busy_pct=0 - deterministically."""
    pack = (100.0 * rep.completed / (rep.epochs * max_batch)
            if rep.epochs else 0.0)
    busy = rt.metrics.counter("bank_busy_ns")
    bank = (100.0 * busy.total() / (len(busy.series) * rep.span_ns)
            if busy.series and rep.span_ns > 0 else 0.0)
    return (f"pack_eff_pct={int(round(pack))} "
            f"bank_busy_pct={int(round(bank))}")


def zipf_pairs(rng: np.ndarray, n_items: int, n_tenants: int,
               s: float = 1.1) -> List[Tuple[int, int]]:
    """Assign each tenant a (distinct) catalog pair, Zipfian over pairs:
    pair rank r gets weight 1/r^s, so a few hot pairs dominate - the
    skew that makes batching windows pack well."""
    pairs = [(i, j) for i in range(n_items) for j in range(i + 1, n_items)]
    w = 1.0 / np.arange(1, len(pairs) + 1, dtype=np.float64) ** s
    idx = rng.choice(len(pairs), size=n_tenants, p=w / w.sum())
    return [pairs[i] for i in idx]


def _serve_bitmaps(backend: str, n_tenants: int, n_queries: int,
                   n_users: int, n_items: int, max_batch: int,
                   window_ns: float, trace_dir: Optional[str] = None,
                   **rt_kwargs) -> Row:
    from repro.core import BitVector, Expr
    from repro.pim.runtime import AmbitRuntime
    from repro.serve import QueryFrontend, run_closed_loop

    rng = np.random.default_rng(0)
    tracer = _make_tracer(trace_dir)
    rt = AmbitRuntime(backend=backend, tracer=tracer, **rt_kwargs)
    raw = {f"m{i}": rng.integers(0, 2, n_users).astype(np.uint8)
           for i in range(n_items)}
    hs = {k: rt.put(BitVector.from_bits(v), name=k)
          for k, v in raw.items()}
    # one fixed expression shape: the DevicePlanner stacks same-shape
    # queries into ONE fused launch per epoch (and its jit cache is
    # keyed on the expression, so serving stays compile-light)
    expr = Expr.var("x") & Expr.var("y")
    tenants = [f"t{i}" for i in range(n_tenants)]
    pair_of = dict(zip(tenants, zipf_pairs(rng, n_items, n_tenants)))
    expected = {}

    def next_query(tenant, k):
        i, j = pair_of[tenant]
        a, b = f"m{i}", f"m{j}"
        expected[tenant] = int((raw[a] & raw[b]).sum())
        return expr, {"x": hs[a], "y": hs[b]}

    mism = 0

    def check(q):
        nonlocal mism
        if rt.popcount(q.result) != expected[q.tenant]:
            mism += 1
        rt.free(q.result)

    fe = QueryFrontend(rt, window_ns=window_ns, max_batch=max_batch)
    t0 = time.perf_counter()
    done = run_closed_loop(fe, tenants, next_query, n_queries,
                           on_complete=check)
    wall_us = (time.perf_counter() - t0) * 1e6
    rep = fe.report()
    derived = (f"tenants={n_tenants} queries={done} drains={rep.drains} "
               f"fill={rep.fill_drains} deadline={rep.deadline_drains} "
               f"flush={rep.flush_drains} epochs={rep.epochs} "
               f"p50_ns={int(rep.p50_ns)} p99_ns={int(rep.p99_ns)} "
               f"qps={rep.qps:.1f} mismatches={mism} "
               + _obs_tokens(rt, rep, max_batch))
    _finish_trace(tracer, trace_dir, f"serve_bitmap_{backend}")
    return f"serve_bitmap_{backend}", wall_us, derived


def _serve_bitweaving(n_tenants: int, n_queries: int, n_rows: int,
                      bits: int, max_batch: int,
                      window_ns: float, trace_dir: Optional[str] = None,
                      **rt_kwargs) -> Row:
    from repro.apps.bitweaving_db import BitWeavingColumn, scan_plan
    from repro.pim.runtime import AmbitRuntime
    from repro.serve import QueryFrontend, run_closed_loop

    rng = np.random.default_rng(1)
    values = rng.integers(0, 2 ** bits, n_rows).astype(np.uint32)
    col = BitWeavingColumn.from_values(values, bits)
    tracer = _make_tracer(trace_dir)
    rt = AmbitRuntime(tracer=tracer, **rt_kwargs)
    tenants = [f"t{i}" for i in range(n_tenants)]
    # Zipfian over range predicates: rank-r predicate weight 1/r^1.1
    preds = [(c1, min(2 ** bits - 1, c1 + w))
             for w in (1, 2, 4) for c1 in range(0, 2 ** bits - 1, 2)]
    wts = 1.0 / np.arange(1, len(preds) + 1, dtype=np.float64) ** 1.1
    pred_of = dict(zip(tenants, (
        preds[i] for i in rng.choice(len(preds), size=n_tenants,
                                     p=wts / wts.sum()))))
    expected = {}

    def next_query(tenant, k):
        c1, c2 = pred_of[tenant]
        expected[tenant] = int(((values >= c1) & (values <= c2)).sum())
        return scan_plan(col, c1, c2, rt)

    mism = 0

    def check(q):
        nonlocal mism
        # selection bits beyond n_rows stay zero (plane tails are zero),
        # so the resident popcount is exact without a host read-back
        if rt.popcount(q.result) != expected[q.tenant]:
            mism += 1
        rt.free(q.result)

    fe = QueryFrontend(rt, window_ns=window_ns, max_batch=max_batch)
    t0 = time.perf_counter()
    done = run_closed_loop(fe, tenants, next_query, n_queries,
                           on_complete=check)
    wall_us = (time.perf_counter() - t0) * 1e6
    rep = fe.report()
    derived = (f"tenants={n_tenants} queries={done} drains={rep.drains} "
               f"fill={rep.fill_drains} deadline={rep.deadline_drains} "
               f"flush={rep.flush_drains} epochs={rep.epochs} "
               f"p50_ns={int(rep.p50_ns)} p99_ns={int(rep.p99_ns)} "
               f"qps={rep.qps:.1f} mismatches={mism} "
               + _obs_tokens(rt, rep, max_batch))
    _finish_trace(tracer, trace_dir, "serve_bitweaving_ambit_sim")
    return "serve_bitweaving_ambit_sim", wall_us, derived


def serve_closed_loop(trace_dir: Optional[str] = None) -> List[Row]:
    rows: List[Row] = []
    # DRAM model: measured per-epoch ns drive the clock
    rows.append(_serve_bitmaps(
        "ambit_sim", n_tenants=1024, n_queries=2048, n_users=256,
        n_items=12, max_batch=16, window_ns=5_000.0,
        banks=4, subarrays=2, words=2, trace_dir=trace_dir))
    # accelerator backend: deterministic HBM-roofline epoch cost model
    rows.append(_serve_bitmaps(
        "pallas", n_tenants=1024, n_queries=1100, n_users=4096,
        n_items=12, max_batch=16, window_ns=50_000.0,
        trace_dir=trace_dir))
    rows.append(_serve_bitweaving(
        n_tenants=1024, n_queries=1000, n_rows=192, bits=4,
        max_batch=16, window_ns=5_000.0,
        banks=4, subarrays=2, words=2, trace_dir=trace_dir))
    return rows


def main(argv=None) -> None:
    """Standalone entry point so CI can re-run JUST the serving section
    with tracing on (the trace-determinism job runs it twice and diffs
    the trace JSON byte-for-byte)."""
    import argparse

    ap = argparse.ArgumentParser(
        description="closed-loop serving benchmark")
    ap.add_argument("--trace", metavar="DIR", default=None,
                    help="write Chrome/Perfetto trace JSON per row "
                         "into DIR")
    ap.add_argument("--quick", action="store_true",
                    help="small workload (CI trace-determinism job)")
    args = ap.parse_args(argv)
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    print("name,us_per_call,derived")
    if args.quick:
        rows = [_serve_bitmaps(
            "ambit_sim", n_tenants=64, n_queries=192, n_users=256,
            n_items=8, max_batch=8, window_ns=5_000.0,
            banks=4, subarrays=2, words=2, trace_dir=args.trace)]
    else:
        rows = serve_closed_loop(trace_dir=args.trace)
    for name, us, derived in rows:
        print(f"{name},{us:.2f},{derived}")


if __name__ == "__main__":
    main()
