"""Serving layer: QueryFrontend differential and property suite.

The frontend reorders WHEN queries run (admission quotas, batching
windows, epoch packing) but must never change WHAT they compute or what
the ledgers record:

  * every query served through the frontend is bit-identical to a serial
    ``eval`` of the same expression, and on ``ambit_sim`` the summed
    drain ledgers conserve energy/AAPs exactly against the serial run;
  * the batching window drains for exactly two reasons - it filled
    (``max_batch``) or its oldest admitted query aged past ``window_ns``
    on the simulated clock - and per-query timestamps are monotone
    (arrival <= admission <= finish);
  * per-tenant ``max_inflight`` quotas block admission without blocking
    the queue - an over-quota tenant's backlog never starves other
    tenants - and pinned working sets are budgeted at both the tenant
    (``TenantQuota.pin_bytes``) and store (``pin_budget_bytes``) levels;
  * the accelerator backends keep the popcount reduction device-side:
    the count matches the host computation bit-for-bit while only the
    int32 scalar (4 bytes) crosses the channel.

Property tests run under hypothesis when installed; without it they fall
back to deterministic seeded sweeps over the same generators.
"""

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

import jax

from repro.core import AmbitError, BitVector, DRAMGeometry, Expr
from repro.core.engine import OpStats
from repro.pim import AmbitRuntime
from repro.serve import QueryFrontend, TenantQuota, run_closed_loop

GEOM = DRAMGeometry(rows_per_subarray=32)  # compact devices
BACKENDS = ("ambit_sim", "jnp", "pallas")

X, Y = Expr.var("x"), Expr.var("y")
EXPRS = [X & Y, X | Y, X ^ Y, ~X, (X & Y) ^ X, ~(X | Y)]


def _rt(backend="ambit_sim", **kw):
    if backend != "ambit_sim":
        return AmbitRuntime(backend=backend, **kw)
    kw.setdefault("banks", 2)
    kw.setdefault("subarrays", 2)
    kw.setdefault("words", 2)
    kw.setdefault("seed", 3)
    return AmbitRuntime(GEOM, **kw)


def _operands(rt, rng, n=4, n_bits=120):
    bits = rng.integers(0, 2, (n, n_bits)).astype(bool)
    return bits, [rt.put(BitVector.from_bits(b)) for b in bits]


# -- frontend differential: served == serial, ledgers conserved ---------------


def check_frontend_matches_serial(seed, backend):
    rng = np.random.default_rng(seed)
    rt_f, rt_s = _rt(backend), _rt(backend)
    bits, hs_f = _operands(rt_f, rng)
    _, hs_s = _operands(rt_s, np.random.default_rng(seed))
    fe = QueryFrontend(rt_f, window_ns=float(rng.integers(1, 6) * 1000),
                       max_batch=int(rng.integers(2, 6)))
    n_q = 12
    picks = [(EXPRS[rng.integers(len(EXPRS))],
              int(rng.integers(4)), int(rng.integers(4)))
             for _ in range(n_q)]

    serial, serial_stats = [], OpStats()
    for expr, i, j in picks:
        out = rt_s.eval(expr, {"x": hs_s[i], "y": hs_s[j]})
        serial_stats += rt_s.last_stats
        serial.append(np.asarray(rt_s.get(out).bits()))
        rt_s.free(out)

    recs = [fe.submit(f"t{k % 3}", expr, {"x": hs_f[i], "y": hs_f[j]})
            for k, (expr, i, j) in enumerate(picks)]
    fe.flush()
    done = fe.take_completed()
    assert sorted(q.seq for q in done) == list(range(n_q))
    for q in done:
        assert q.arrival_ns <= q.admitted_ns <= q.finished_ns
        assert q.latency_ns > 0
    for q, want in zip(sorted(done, key=lambda q: q.seq), serial):
        got = np.asarray(rt_f.get(q.result).bits())
        assert np.array_equal(got, want)
        rt_f.free(q.result)
    rep = fe.report()
    assert rep.completed == n_q
    assert rep.drains == rep.fill_drains + rep.deadline_drains + \
        rep.flush_drains
    assert 0 < rep.p50_ns <= rep.p99_ns <= rep.max_ns
    if backend == "ambit_sim":
        # epoch packing may change WHEN, never what the ledger sums to
        assert rep.stats.energy_nj == pytest.approx(
            serial_stats.energy_nj, rel=1e-12)
        assert rep.stats.aap_count == serial_stats.aap_count
    assert recs[0] in done


@pytest.mark.parametrize("backend", BACKENDS)
def test_frontend_matches_serial(backend):
    check_frontend_matches_serial(11, backend)


if HAVE_HYPOTHESIS:

    @settings(max_examples=8, deadline=None)
    @given(st.integers(0, 2**32 - 1))
    def test_frontend_matches_serial_random(seed):
        check_frontend_matches_serial(seed, "ambit_sim")

else:

    @pytest.mark.parametrize("seed", range(4))
    def test_frontend_matches_serial_random(seed):
        check_frontend_matches_serial(seed, "ambit_sim")


# -- batching window: fill and deadline drains --------------------------------


def test_window_fills_then_drains():
    rng = np.random.default_rng(0)
    rt = _rt()
    _, hs = _operands(rt, rng)
    fe = QueryFrontend(rt, window_ns=1e9, max_batch=3)
    for k in range(2):
        fe.submit(f"t{k}", X & Y, {"x": hs[0], "y": hs[1]})
    assert not fe.take_completed()      # window below max_batch: holds
    fe.submit("t2", X | Y, {"x": hs[2], "y": hs[3]})
    done = fe.take_completed()          # third admission fills it
    assert len(done) == 3
    rep = fe.report()
    assert rep.fill_drains == 1 and rep.deadline_drains == 0


def test_deadline_drains_partial_window():
    rng = np.random.default_rng(0)
    rt = _rt()
    _, hs = _operands(rt, rng)
    fe = QueryFrontend(rt, window_ns=1000.0, max_batch=8)
    q = fe.submit("t0", X & Y, {"x": hs[0], "y": hs[1]}, arrival_ns=0.0)
    fe.tick(999.0)
    assert not fe.take_completed()      # window not yet aged out
    fe.tick(1000.0)
    done = fe.take_completed()
    assert done == [q]
    assert fe.report().deadline_drains == 1
    assert q.finished_ns > 1000.0       # drained at the deadline tick


def test_clock_never_runs_backwards():
    rng = np.random.default_rng(0)
    rt = _rt()
    _, hs = _operands(rt, rng)
    fe = QueryFrontend(rt, window_ns=1e9, max_batch=2)
    fe.submit("a", X & Y, {"x": hs[0], "y": hs[1]}, arrival_ns=5000.0)
    q = fe.submit("b", X | Y, {"x": hs[2], "y": hs[3]}, arrival_ns=10.0)
    assert q.arrival_ns == 10.0         # stale arrival is recorded as-is
    for r in fe.take_completed():
        assert r.admitted_ns >= 5000.0  # but admission uses the clock


# -- quotas: admission control without starvation -----------------------------


def test_quota_blocks_admission_not_the_queue():
    rng = np.random.default_rng(1)
    rt = _rt()
    _, hs = _operands(rt, rng)
    fe = QueryFrontend(rt, window_ns=1e9, max_batch=3,
                       quotas={"greedy": TenantQuota(max_inflight=1)})
    for _ in range(4):
        fe.submit("greedy", X & Y, {"x": hs[0], "y": hs[1]})
    assert fe.inflight("greedy") == 1   # quota admits exactly one
    assert len(fe.backlog) == 3
    # two polite tenants arrive AFTER greedy's backlog - and admit past
    # it, filling the window (no head-of-line starvation)
    fe.submit("p1", X | Y, {"x": hs[2], "y": hs[3]})
    fe.submit("p2", X ^ Y, {"x": hs[1], "y": hs[2]})
    done = fe.take_completed()
    assert {q.tenant for q in done} == {"greedy", "p1", "p2"}
    fe.flush()
    rest = fe.take_completed()
    assert [q.tenant for q in rest] == ["greedy"] * 3
    assert sorted(q.seq for q in rest) == [q.seq for q in rest]  # FIFO


def test_quota_releases_on_completion():
    rng = np.random.default_rng(1)
    rt = _rt()
    _, hs = _operands(rt, rng)
    fe = QueryFrontend(rt, window_ns=1e9, max_batch=2,
                       default_quota=TenantQuota(max_inflight=2))
    for _ in range(6):
        fe.submit("t", X & Y, {"x": hs[0], "y": hs[1]})
    fe.flush()
    assert len(fe.take_completed()) == 6
    assert fe.inflight("t") == 0 and not fe.backlog


# -- pinned working sets: tenant and store budgets ----------------------------


def test_store_pin_budget_enforced():
    rng = np.random.default_rng(2)
    rt = _rt(pin_budget_bytes=1)
    bits = rng.integers(0, 2, 120).astype(bool)
    with pytest.raises(AmbitError, match="pin budget"):
        rt.put(BitVector.from_bits(bits), pin=True)
    rbv = rt.put(BitVector.from_bits(bits))     # unpinned: fine
    with pytest.raises(AmbitError, match="pin budget"):
        rt.pin(rbv)
    assert rt.store.pinned_bytes == 0


def test_pin_budget_refunds_on_unpin_and_free():
    rng = np.random.default_rng(2)
    rt = _rt(pin_budget_bytes=1 << 20)
    _, hs = _operands(rt, rng, n=2)
    rt.pin(hs[0])
    rt.pin(hs[0])                       # idempotent: billed once
    assert rt.store.pinned_bytes == hs[0].device_bytes
    rt.pin(hs[1])
    rt.unpin(hs[0])
    assert rt.store.pinned_bytes == hs[1].device_bytes
    rt.free(hs[1])                      # free refunds the pin bill
    assert rt.store.pinned_bytes == 0


@pytest.mark.parametrize("backend", BACKENDS)
def test_tenant_pin_quota(backend):
    rng = np.random.default_rng(3)
    rt = _rt(backend)
    _, hs = _operands(rt, rng, n=3)
    nbytes = hs[0].device_bytes
    fe = QueryFrontend(rt, quotas={
        "a": TenantQuota(pin_bytes=2 * nbytes)})
    assert fe.pin_working_set("a", hs[:2]) == 2 * nbytes
    with pytest.raises(AmbitError, match="pin budget"):
        fe.pin_working_set("a", [hs[2]])
    fe.unpin_working_set("a", [hs[0]])
    assert fe.pin_working_set("a", [hs[2]]) == nbytes
    with pytest.raises(AmbitError, match="pin budget"):
        fe.pin_working_set("zero-quota", [hs[0]])   # default quota: 0 B


def test_tenant_pin_all_or_nothing_on_store_budget():
    """The tenant quota admits the set, the store budget rejects it
    mid-way: nothing stays pinned."""
    rng = np.random.default_rng(3)
    rt = _rt()
    _, hs = _operands(rt, rng, n=2)
    rt.store.pin_budget_bytes = hs[0].device_bytes      # room for one
    fe = QueryFrontend(rt, default_quota=TenantQuota(pin_bytes=1 << 20))
    with pytest.raises(AmbitError, match="pin budget"):
        fe.pin_working_set("a", hs)
    assert rt.store.pinned_bytes == 0
    assert not hs[0].pinned and not hs[1].pinned


# -- device-side popcount -----------------------------------------------------


@pytest.mark.parametrize("backend", ("jnp", "pallas"))
def test_device_popcount_stays_device_side(backend):
    rng = np.random.default_rng(4)
    bits = rng.integers(0, 2, 300).astype(bool)
    rt = _rt(backend)
    rbv = rt.put(BitVector.from_bits(bits))
    reads0 = rt.store.host_reads
    assert rt.popcount(rbv) == int(bits.sum())
    assert rt.last_stats.bytes_touched == 4     # one int32, not the array
    assert rt.store.host_reads == reads0 + 1


@pytest.mark.parametrize("backend", ("jnp", "pallas"))
def test_device_popcount_on_eval_result(backend):
    """Masked-tail contract: expression results are tail-masked on
    device, so the full-array reduction is exact (incl. NOT, whose raw
    complement would set the padding bits)."""
    rng = np.random.default_rng(5)
    bits = rng.integers(0, 2, (2, 77)).astype(bool)
    rt = _rt(backend)
    x, y = (rt.put(BitVector.from_bits(b)) for b in bits)
    out = rt.eval(~(X & Y), {"x": x, "y": y})
    assert rt.popcount(out) == int((~(bits[0] & bits[1])).sum())


def test_ambit_popcount_unchanged():
    rng = np.random.default_rng(6)
    bits = rng.integers(0, 2, (2, 200)).astype(bool)
    rt = _rt()
    x, y = (rt.put(BitVector.from_bits(b)) for b in bits)
    out = rt.eval(X & Y, {"x": x, "y": y})
    assert rt.popcount(out) == int((bits[0] & bits[1]).sum())
    # the DRAM model has no reduction op: the dirty result is read back
    assert rt.last_stats.bytes_touched == out.device_bytes


# -- closed-loop driver -------------------------------------------------------


def test_closed_loop_completes_and_orders_per_tenant():
    rng = np.random.default_rng(7)
    rt = _rt()
    bits, hs = _operands(rt, rng)
    fe = QueryFrontend(rt, window_ns=2000.0, max_batch=4)
    seen = {}

    def next_query(tenant, k):
        i = (hash(tenant) + k) % 3
        return EXPRS[i], {"x": hs[i], "y": hs[i + 1]}

    def on_complete(q):
        seen.setdefault(q.tenant, []).append(q.seq)
        rt.free(q.result)

    done = run_closed_loop(fe, [f"t{i}" for i in range(5)], next_query,
                           23, on_complete=on_complete)
    assert done == 23
    assert sum(len(v) for v in seen.values()) == 23
    for seqs in seen.values():          # closed loop: per-tenant FIFO
        assert seqs == sorted(seqs)
    rep = fe.report()
    assert rep.completed == 23 and rep.qps > 0 and rep.span_ns > 0


# -- report percentile edge cases + metrics snapshot (ISSUE 7) ----------------


def test_report_on_zero_completions_is_nan_free():
    """p50/p99 over an empty completion set must not raise or emit NaN:
    report() degrades to 0.0 and metrics_snapshot() reports None (JSON
    null), serialisable with allow_nan=False."""
    import json

    fe = QueryFrontend(_rt())
    rep = fe.report()
    assert rep.completed == 0
    assert rep.p50_ns == 0.0 and rep.p99_ns == 0.0
    assert rep.mean_ns == 0.0 and rep.max_ns == 0.0 and rep.qps == 0.0
    snap = fe.metrics_snapshot()
    json.dumps(snap, allow_nan=False)   # must not raise
    assert snap["serving"]["p50_ns"] is None
    assert snap["serving"]["p99_ns"] is None


def test_report_on_single_completion():
    """One completion: every percentile is that query's latency."""
    rng = np.random.default_rng(0)
    rt = _rt()
    _, hs = _operands(rt, rng)
    fe = QueryFrontend(rt, window_ns=1e9, max_batch=8)
    q = fe.submit("t0", X & Y, {"x": hs[0], "y": hs[1]})
    fe.flush()
    rep = fe.report()
    assert rep.completed == 1
    assert rep.p50_ns == rep.p99_ns == rep.mean_ns == rep.max_ns \
        == q.latency_ns > 0
    snap = fe.metrics_snapshot()
    assert snap["serving"]["p50_ns"] == q.latency_ns
    assert snap["serving"]["p99_ns"] == q.latency_ns


def test_frontend_metrics_reconcile_with_report():
    """The registry's serving series are the same numbers report()
    derives - the legacy counters are views over the histogram."""
    rng = np.random.default_rng(1)
    rt = _rt()
    _, hs = _operands(rt, rng)
    fe = QueryFrontend(rt, window_ns=2000.0, max_batch=4)

    def next_query(tenant, k):
        i = (hash(tenant) + k) % 3
        return EXPRS[i], {"x": hs[i], "y": hs[i + 1]}

    run_closed_loop(fe, [f"t{i}" for i in range(4)], next_query, 17,
                    on_complete=lambda q: rt.free(q.result))
    rep = fe.report()
    m = fe.metrics
    assert m is rt.metrics              # shared registry, one namespace
    lat = m.histogram("serve_latency_ns")
    assert lat.count() == rep.completed
    assert m.counter("serve_completed").total() == rep.completed
    assert m.counter("serve_drains").total() == rep.drains
    assert m.counter("serve_admitted").total() == rep.completed
    assert lat.percentile(0.50) == rep.p50_ns
    assert lat.percentile(0.99) == rep.p99_ns


def test_device_popcount_refuses_int32_overflow():
    """Per-row counts are int32: a handle with 2^31 bits per row raises
    instead of wrapping (checked before any device work)."""
    from repro.pim.device_store import DeviceBitVector, DeviceStore
    store = DeviceStore("jnp")
    big = DeviceBitVector(store=store, n_bits=2 ** 31, shape=(),
                          words32=2 ** 26,
                          _dev=jax.numpy.zeros(1, jax.numpy.uint32))
    with pytest.raises(AmbitError, match="2\\^31"):
        store.popcount(big)
