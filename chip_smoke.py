"""Chip smoke: run the accelerator-resident query path once on one TPU.

Drives ``AmbitRuntime(backend="pallas")`` through its user entry points
at the bitmap-index deployment scale (2^28 users, 32 MiB per bitmap) and
checks every answer against a plain numpy reference computed from the
same seeded data:

  a. serving       - ``QueryFrontend`` + ``run_closed_loop``: 32 tenants,
                     64 Zipf-skewed ``x & y`` queries, 8 per epoch;
  b. multi_root    - ``BitmapIndex.weekly_active_query`` (4 weeks plus a
                     gender bitmap) in one scheduler drain;
  c. donation      - a chain of 4 ``out=`` rebinds that donate the
                     destination buffer, then ``get`` and ``popcount``;
  d. bitweaving    - 4 range predicates over a 12-bit column of 2^28 rows.

Run it from the root of a checkout, on a machine with the chip:

    python chip_smoke.py [--seed N]

It exits non-zero before any phase runs when JAX finds no TPU, and on any
mismatch or exception. Per phase it prints host wall time (ending in
``jax.block_until_ready`` over every live array), the XLA compile
requests and persistent-cache hits, and the device's
``peak_bytes_in_use`` so far. These are the numbers of one smoke run, not
a benchmark. The last line of its output is one JSON object naming the
device.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

N_BITS = 2 ** 28            # users per bitmap / rows per column


class SmokeMismatch(RuntimeError):
    """An answer from the device path differs from the numpy reference."""


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeMismatch(what)


def _random_words(rng: np.random.Generator, n_bits: int) -> np.ndarray:
    """A packed bitmap of ``n_bits`` random bits, made as uint32 words
    (never as a bool array of ``n_bits``)."""
    return rng.integers(0, 2 ** 32, n_bits // 32, dtype=np.uint32)


def _count(words: np.ndarray) -> int:
    return int(np.bitwise_count(words).sum(dtype=np.int64))


def phase_serving(seed: int, n_bits: int = N_BITS, n_bitmaps: int = 16,
                  n_tenants: int = 32, n_queries: int = 64,
                  max_batch: int = 8) -> dict:
    """Closed-loop serving of Zipf-skewed ``x & y`` pairs; every
    completion's device popcount against numpy."""
    from benchmarks.serve_closed_loop import zipf_pairs
    from repro.core import BitVector, Expr
    from repro.pim import AmbitRuntime
    from repro.serve import QueryFrontend, run_closed_loop

    rng = np.random.default_rng(seed)
    rt = AmbitRuntime(backend="pallas")
    raw = [_random_words(rng, n_bits) for _ in range(n_bitmaps)]
    hs = [rt.put(BitVector(w, n_bits), name=f"m{i}")
          for i, w in enumerate(raw)]
    expr = Expr.var("x") & Expr.var("y")
    tenants = [f"t{i}" for i in range(n_tenants)]
    pair_of = dict(zip(tenants, zipf_pairs(rng, n_bitmaps, n_tenants)))
    want = {p: _count(raw[p[0]] & raw[p[1]]) for p in set(pair_of.values())}
    checked = []

    def next_query(tenant, k):
        i, j = pair_of[tenant]
        return expr, {"x": hs[i], "y": hs[j]}

    def check(q):
        _check(q.ok and not q.fallback,
               f"query {q.seq} of {q.tenant}: error={q.error!r} "
               f"fallback={q.fallback}")
        got, exp = rt.popcount(q.result), want[pair_of[q.tenant]]
        _check(got == exp, f"query {q.seq} of {q.tenant}: popcount {got} "
                           f"!= numpy {exp}")
        rt.free(q.result)
        checked.append(q.seq)

    fe = QueryFrontend(rt, max_batch=max_batch)
    done = run_closed_loop(fe, tenants, next_query, n_queries,
                           on_complete=check)
    _check(done == n_queries == len(checked),
           f"{done} completions, {len(checked)} checked, {n_queries} sent")
    rep = fe.report()
    for h in hs:
        rt.free(h)
    return {"queries": done, "drains": rep.drains, "epochs": rep.epochs,
            "fused_dispatches": int(rt.metrics.counter(
                "fused_dispatches").total())}


def phase_multi_root(seed: int, n_bits: int = N_BITS,
                     n_weeks: int = 4) -> dict:
    """The Section 8.1 query: users active in every week, and per week
    the users with the attribute, as one multi-root drain."""
    from repro.apps.bitmap_index import BitmapIndex
    from repro.core import BitVector
    from repro.pim import AmbitRuntime

    rng = np.random.default_rng(seed + 1)
    rt = AmbitRuntime(backend="pallas")
    idx = BitmapIndex(n_bits, runtime=rt)
    weeks = [f"week{k}" for k in range(n_weeks)]
    raw = {nm: _random_words(rng, n_bits) for nm in weeks + ["gender"]}
    for nm, words in raw.items():
        idx.add_bitvector(nm, BitVector(words, n_bits))
    total, per_week, _ = idx.weekly_active_query(weeks, "gender")
    want_total = _count(np.bitwise_and.reduce([raw[w] for w in weeks]))
    want_weeks = [_count(raw[w] & raw["gender"]) for w in weeks]
    _check(total == want_total,
           f"all-weeks count {total} != numpy {want_total}")
    _check(per_week == want_weeks,
           f"per-week counts {per_week} != numpy {want_weeks}")
    _check(rt.scheduler.drains == 1, f"{rt.scheduler.drains} drains, not 1")
    for h in idx.resident.values():
        rt.free(h)
    return {"queries": rt.last_drain.n_queries,
            "epochs": len(rt.last_drain.epochs)}


def phase_donation(seed: int, n_bits: int = N_BITS, steps: int = 4) -> dict:
    """A chain of ``out=`` rebinds, each donating the destination's
    buffer to XLA; the chain's end is read back and counted."""
    from repro.core import BitVector, Expr
    from repro.pim import AmbitRuntime

    rng = np.random.default_rng(seed + 2)
    rt = AmbitRuntime(backend="pallas")
    raw = [_random_words(rng, n_bits) for _ in range(3)]
    a, b, c = (rt.put(BitVector(w, n_bits)) for w in raw)
    acc = rt.and_(a, b)
    want = raw[0] & raw[1]
    step = Expr.var("acc") ^ Expr.var("z")
    for k in range(steps):
        z = (c, a, b)[k % 3]
        old = acc._dev
        acc2 = rt.eval(step, {"acc": acc, "z": z}, out=acc)
        _check(acc2 is acc, "out= did not rebind the destination handle")
        _check(rt.planner.last_report.donated == 1,
               f"step {k}: destination buffer not offered for donation")
        _check(old.is_deleted(), f"step {k}: donated buffer still live")
        want = want ^ raw[(2, 0, 1)[k % 3]]
    got = np.asarray(rt.get(acc).data)
    _check(np.array_equal(got, want), "chain result differs from numpy")
    count, exp = rt.popcount(acc), _count(want)
    _check(count == exp, f"chain popcount {count} != numpy {exp}")
    for h in (acc, a, b, c):
        rt.free(h)
    return {"rebinds": steps, "donated": int(rt.metrics.counter(
        "donated_buffers").total())}


def phase_bitweaving(seed: int, n_rows: int = N_BITS, bits: int = 12,
                     n_preds: int = 4) -> dict:
    """BitWeaving-V range scans ``c1 <= v <= c2``, each submitted and
    drained on its own, against numpy over the raw values."""
    from repro.apps.bitweaving_db import BitWeavingColumn, scan_plan
    from repro.pim import AmbitRuntime

    rng = np.random.default_rng(seed + 3)
    values = rng.integers(0, 2 ** bits, n_rows, dtype=np.uint32)
    col = BitWeavingColumn.from_values(values, bits)
    rt = AmbitRuntime(backend="pallas")
    lows = rng.choice(2 ** bits, size=n_preds, replace=False)
    preds = [(int(lo), int(min(2 ** bits - 1, lo + 2 ** (bits - 2))))
             for lo in lows]
    for c1, c2 in preds:
        expr, env = scan_plan(col, c1, c2, rt)
        ticket = rt.submit(expr, env)
        rt.drain()
        got = rt.popcount(ticket.result)
        rt.free(ticket.result)
        exp = int(((values >= c1) & (values <= c2)).sum(dtype=np.int64))
        _check(got == exp, f"scan [{c1}, {c2}]: {got} != numpy {exp}")
    for h in env.values():
        rt.free(h)
    return {"predicates": len(preds), "rows": n_rows, "bits": bits}


PHASES = (("serving", phase_serving), ("multi_root", phase_multi_root),
          ("donation", phase_donation), ("bitweaving", phase_bitweaving))


class _CompileCounter:
    """Counts XLA compile requests and persistent-cache hits from JAX's
    monitoring events (a request served by the cache is not compiled)."""

    def __init__(self):
        import jax

        self.requests = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._hit)

    def _duration(self, event, duration, **kwargs):
        if event == "/jax/core/compile/backend_compile_duration":
            self.requests += 1

    def _hit(self, event, **kwargs):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def main(argv=None) -> int:
    import jax

    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {device.platform!r}); "
              "nothing was run", file=sys.stderr)
        return 1
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of every bitmap, column and query mix")
    args = ap.parse_args(argv)

    from repro.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    print(f"device: {device.device_kind}, compile cache: {cache_dir}")
    compiles = _CompileCounter()
    for name, phase in PHASES:
        req0, hit0 = compiles.requests, compiles.cache_hits
        t0 = time.perf_counter()
        info = phase(args.seed)
        jax.block_until_ready(jax.live_arrays())
        wall = time.perf_counter() - t0
        peak = (device.memory_stats() or {}).get("peak_bytes_in_use")
        print(f"phase {name}: ok, host wall time {wall} s, "
              f"XLA compile requests {compiles.requests - req0} "
              f"(persistent-cache hits {compiles.cache_hits - hit0}), "
              f"peak_bytes_in_use so far {peak}, {json.dumps(info)}")
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
