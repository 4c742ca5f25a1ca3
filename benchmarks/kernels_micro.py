"""Kernel microbenchmarks + the engine's own roofline model.

Bulk bitwise ops have arithmetic intensity ~#ops / 12 bytes, so on the
TPU target they are HBM-bound: ideal time = bytes / 819 GB/s. We report
measured CPU wall time (interpret mode - correctness signal only) AND the
modeled TPU roofline time per call, plus the fusion win: a fused
expression of k ops touches (k_inputs+1) buffers instead of 3 per op
(the AAP-chain/RowClone copy-avoidance analogue, Section 3.1.4).

Also measures the ambit_sim device model's batched execution path against
the legacy per-row loop (kern_ambit_batched_6op): the before/after speedup
of the (n_rows, words) vectorization + compiled-program cache."""

from __future__ import annotations

import time
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Row = Tuple[str, float, str]

HBM_BW = 819e9


def _time(fn, *args, reps=3) -> float:
    fn(*args)  # compile
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e6


def ambit_batched_speedup(n_rows: int = 1024, n_bits: int = 2048) -> List[Row]:
    """Batched ambit_sim execution vs the legacy per-row loop (the seed
    behavior, kept as batch_rows=False): one 6-op expression evaluated over
    ``n_rows`` subarray rows. Records the before/after speedup the batched
    simulator + compile cache deliver - the acceptance bar is >= 20x."""
    from repro.core import BitVector, BulkBitwiseEngine, Expr

    x, y, z = Expr.var("x"), Expr.var("y"), Expr.var("z")
    expr = ((x & y) | ~z) ^ ((x | y) & z)  # and,or,not,or,and,xor = 6 ops
    rng = np.random.default_rng(0)
    bits = rng.integers(0, 2, (3, n_rows, n_bits)).astype(bool)
    env = {k: BitVector.from_bits(bits[i]) for i, k in enumerate("xyz")}

    batched = BulkBitwiseEngine("ambit_sim")
    per_row = BulkBitwiseEngine("ambit_sim", batch_rows=False)
    us_b = _time(lambda: batched.eval(expr, env))
    us_p = _time(lambda: per_row.eval(expr, env), reps=1)
    st = batched.last_stats
    assert np.array_equal(np.asarray(batched.eval(expr, env).bits()),
                          np.asarray(per_row.eval(expr, env).bits()))
    return [("kern_ambit_batched_6op", us_b,
             f"rows={n_rows} per_row={us_p:.0f}us "
             f"speedup={us_p / us_b:.1f}x aap={st.aap_count} "
             f"dram_model_ns={st.ns:.0f}")]


CHANNEL_BW = 34e9  # 2-channel DDR3 model (Section 7) for host round-trips


def pim_resident_chain(n_ops: int = 6, rows: int = 128) -> List[Row]:
    """Resident vs non-resident execution of a query_and_all-style chain
    (Section 8.1 shape): ``n_ops`` dependent ANDs over a batch of ``rows``
    row-sized (65,536-bit) bitvectors at real 8 KB geometry. The
    non-resident baseline pays a host write of every operand and a host
    read of every intermediate per op, and executes ops serially; the
    resident path uploads once, chains in-DRAM through the placement-aware
    planner (row groups across banks in parallel), and reads back only the
    final result. The headline is the DRAM cost model: op time + channel
    time for the host traffic each path actually generates."""
    from repro.core import BitVector, BulkBitwiseEngine
    from repro.pim import AmbitRuntime

    rng = np.random.default_rng(0)
    n_bits = 65536  # one full DRAM row per batch row
    bits = rng.integers(0, 2, (n_ops + 1, rows, n_bits)).astype(bool)
    vecs = [BitVector.from_bits(b) for b in bits]

    eng = BulkBitwiseEngine("ambit_sim")

    def host_chain():
        acc, nbytes, ns = vecs[0], 0, 0.0
        for bv in vecs[1:]:
            acc = eng.and_(acc, bv)
            nbytes += eng.last_stats.bytes_touched
            ns += eng.last_stats.ns
        return nbytes, ns

    def resident_chain():
        rt = AmbitRuntime(banks=8, subarrays=4, seed=1)
        rs = []
        for bv in vecs:
            rs.append(rt.put(bv, near=rs[0].slots if rs else None))
        acc = rs[0]
        for r in rs[1:]:
            prev = acc
            acc = rt.and_(acc, r)
            if prev is not rs[0]:
                rt.free(prev)        # intermediates die in-DRAM
        rt.get(acc)
        return rt

    us_host = _time(host_chain, reps=2)
    us_res = _time(resident_chain, reps=2)
    (host_bytes, host_ns), rt = host_chain(), resident_chain()
    assert rt.host_reads == 1        # zero intermediate read-backs
    res_bytes = rt.session_stats.bytes_touched
    host_model = host_ns + host_bytes / CHANNEL_BW * 1e9
    res_model = rt.session_stats.ns + res_bytes / CHANNEL_BW * 1e9
    return [("kern_pim_resident_chain", us_res,
             f"ops={n_ops} rows={rows} model_speedup="
             f"{host_model / res_model:.1f}x "
             f"(dram {host_ns / rt.session_stats.ns:.1f}x, traffic "
             f"{host_bytes / res_bytes:.1f}x: {res_bytes} vs {host_bytes} B) "
             f"host_wall={us_host:.0f}us")]


def pim_sharded_scan(n_ops: int = 6, rows: int = 64,
                     devices: int = 4) -> List[Row]:
    """Sharded multi-device scaling: the same ``n_ops``-AND resident chain
    over a batch of ``rows`` row-sized (65,536-bit) bitvectors, on one
    device vs a ``devices``-device PimCluster with round-robin chunk
    placement. Chunks stripe across devices, so each device executes
    1/devices of every op and the cluster planner reports
    max-over-devices time - near-linear scaling as long as operands stay
    chunk-aligned (the ``near=`` chain guarantees that, so the chain pays
    ZERO inter-device transfers). The kernel then ANDs in one
    deliberately mis-placed operand (packed onto device 0): the cluster's
    cross-device colocation moves its chunks, and the ledger records the
    **measured** inter-device rows/bytes plus the channel ns the move
    re-introduced - the traffic the paper's single-chip story never
    sees."""
    from repro.core import BitVector
    from repro.pim import AmbitRuntime, PACKED

    rng = np.random.default_rng(0)
    n_bits = 65536  # one full 8 KB DRAM row per logical row
    bits = rng.integers(0, 2, (n_ops + 1, rows, n_bits)).astype(bool)
    vecs = [BitVector.from_bits(b) for b in bits]

    def chain(n_devices):
        rt = AmbitRuntime(banks=4, subarrays=2, devices=n_devices, seed=1)
        rs = []
        for bv in vecs:
            rs.append(rt.put(bv, near=rs[0].slots if rs else None))
        acc = rs[0]
        for r in rs[1:]:
            prev = acc
            acc = rt.and_(acc, r)
            if prev is not rs[0]:
                rt.free(prev)
        rt.get(acc)
        return rt, acc

    us_1 = _time(lambda: chain(1), reps=1)
    us_n = _time(lambda: chain(devices), reps=1)
    (rt1, _), (rtn, acc) = chain(1), chain(devices)
    ns_1, ns_n = rt1.session_stats.ns, rtn.session_stats.ns
    assert rtn.store.ledger.inter_device_bytes == 0  # aligned chain: free

    # Mis-placed operand: packed onto one device, colocated on first use.
    mask = rtn.store.put(BitVector.from_bits(bits[0]), placement=PACKED)
    rtn.and_(acc, mask)
    led = rtn.store.ledger
    return [("kern_pim_sharded_scan", us_n,
             f"devices={devices} ops={n_ops} rows={rows} "
             f"dram_speedup={ns_1 / ns_n:.1f}x "
             f"({ns_1:.0f} vs {ns_n:.0f} ns) "
             f"misplaced_op: inter_dev_rows={led.inter_device_rows} "
             f"bytes={led.inter_device_bytes} (measured) "
             f"channel_ns={led.inter_device_ns:.0f} "
             f"single_dev_wall={us_1:.0f}us")]


def pim_async_multiquery(n_queries: int = 4, n_ops: int = 3,
                         rows: int = 8) -> List[Row]:
    """Async multi-query scheduler: ``n_queries`` independent sessions,
    each an ``n_ops``-AND expression over its own operands, placed so the
    queries occupy disjoint banks (single device) or disjoint devices
    (4-device cluster). Serial ``eval`` pays sum-over-queries DRAM time;
    ``submit``+``drain`` packs the bank/device-disjoint queries into ONE
    epoch, so drain time is the max over resources - the paper's
    bank-level parallelism lifted from row groups of one query to whole
    concurrent sessions. The acceptance bar is >= 3x DRAM-op time at 4
    disjoint queries with bit-identical results and identical summed
    energy/AAPs, on both configs."""
    import itertools

    from repro.core import BitVector, Expr
    from repro.pim import AmbitRuntime

    n_bits = 65536          # one full 8 KB DRAM row per logical row
    banks, subarrays = n_queries, 2
    rng = np.random.default_rng(0)
    bits = rng.integers(0, 2, (n_queries, n_ops + 1, rows, n_bits)
                        ).astype(bool)
    expr = Expr.var("v0")
    for k in range(1, n_ops + 1):
        expr = expr & Expr.var(f"v{k}")
    want = [np.bitwise_and.reduce(bits[q]) for q in range(n_queries)]

    def load(rt, devices):
        """Query q's operands confined to bank q (1 device) or device q
        (cluster), chunk-aligned so no staging/transfers are needed."""
        envs = []
        for q in range(n_queries):
            vecs = []
            for k in range(n_ops + 1):
                bv = BitVector.from_bits(bits[q, k])
                if vecs:
                    near = vecs[0].slots
                elif devices == 1:
                    near = [(q, s, 0) for s in range(subarrays)]
                else:
                    near = [(q, (i % banks, (i // banks) % subarrays, 0))
                            for i in range(rows)]
                vecs.append(rt.put(bv, near=near))
            envs.append({f"v{k}": v for k, v in enumerate(vecs)})
        return envs

    out: List[Row] = []
    for devices in (1, 4):
        dev_kw = dict(banks=banks, subarrays=subarrays, seed=1)
        rt_s = AmbitRuntime(devices=1 if devices == 1 else devices, **dev_kw)
        envs_s = load(rt_s, devices)
        serial_res, serial_ns, serial_e, serial_aap = [], 0.0, 0.0, 0
        t0 = time.perf_counter()
        for env in envs_s:
            r = rt_s.eval(expr, env)
            serial_ns += rt_s.last_stats.ns
            serial_e += rt_s.last_stats.energy_nj
            serial_aap += rt_s.last_stats.aap_count
            serial_res.append(np.asarray(rt_s.get(r).bits()))
        us_serial = (time.perf_counter() - t0) * 1e6

        rt_a = AmbitRuntime(devices=1 if devices == 1 else devices, **dev_kw)
        envs_a = load(rt_a, devices)
        t0 = time.perf_counter()
        tickets = [rt_a.submit(expr, env) for env in envs_a]
        rt_a.drain()
        us_async = (time.perf_counter() - t0) * 1e6
        drain = rt_a.last_drain
        async_res = [np.asarray(rt_a.get(t.result).bits()) for t in tickets]

        for w, s, a in zip(want, serial_res, async_res):
            assert np.array_equal(s, w) and np.array_equal(a, w)
        assert drain.stats.energy_nj == serial_e      # conservation-exact
        assert drain.stats.aap_count == serial_aap
        speedup = serial_ns / drain.stats.ns
        assert speedup >= 3.0, f"epoch overlap only {speedup:.2f}x"
        epochs = len(drain.epochs)
        n_res = len(set(itertools.chain.from_iterable(
            e.resources for e in drain.epochs)))
        out.append((f"kern_pim_async_multiquery_d{devices}", us_async,
                    f"queries={n_queries} ops={n_ops} rows={rows} "
                    f"dram_speedup={speedup:.1f}x "
                    f"({serial_ns:.0f} vs {drain.stats.ns:.0f} ns) "
                    f"epochs={epochs} resources={n_res} "
                    f"serial_wall={us_serial:.0f}us"))
    return out


def pim_optimizer(n_tenants: int = 6, n_queries: int = 24) -> List[Row]:
    """Cost-based multi-query optimizer on the TPC-H-flavoured suite:
    ``n_queries`` multi-predicate scans from a Zipfian tenant mix over
    shared-prefix range pools (apps.bitweaving_db). Unoptimized drain
    executes every submitted comparator tree; ``drain(optimize=True)``
    CSE-shares the pooled comparator subtrees across tickets (one
    materialization, DAG references downstream). The acceptance bar is
    >= 1.5x DRAM-op time reduction with bit-exact results vs the numpy
    oracle and ``opt_*`` counters reconciled against the drain ledger.
    A second optimized round resubmits the same mix: every query must
    be served from the result cache with ZERO device ops."""
    from repro.apps.bitweaving_db import (TpchTable, predicate_plan,
                                          zipf_tenant_queries)
    from repro.core import DRAMGeometry
    from repro.pim import AmbitRuntime

    geom = DRAMGeometry(rows_per_subarray=64)

    def build():
        rt = AmbitRuntime(geom, banks=4, devices=1, subarrays=4,
                          words=4, seed=1)
        table = TpchTable.synthesize(n_rows=rt.store.device.words * 64,
                                     seed=2)
        queries = zipf_tenant_queries(table, n_tenants=n_tenants,
                                      n_queries=n_queries, seed=3)
        return rt, table, queries

    def submit_all(rt, table, queries):
        return [rt.submit(*predicate_plan(table, specs, rt))
                for _, specs in queries]

    def check(rt, table, queries, tickets):
        for (_, specs), t in zip(queries, tickets):
            got = np.asarray(rt.get(t.result).bits()).ravel()
            got = got[:table.n_rows].astype(bool)
            assert np.array_equal(got, table.oracle(specs)), specs

    rt_u, table_u, queries = build()
    t0 = time.perf_counter()
    tu = submit_all(rt_u, table_u, queries)
    rt_u.drain()
    us_unopt = (time.perf_counter() - t0) * 1e6
    check(rt_u, table_u, queries, tu)
    su = rt_u.last_drain.stats

    rt_o, table_o, _ = build()
    t0 = time.perf_counter()
    to = submit_all(rt_o, table_o, queries)
    rt_o.drain(optimize=True)
    us_opt = (time.perf_counter() - t0) * 1e6
    check(rt_o, table_o, queries, to)
    so = rt_o.last_drain.stats
    rep = rt_o.last_drain.opt

    # opt_* counters reconcile bit-exactly with the drain's OptReport
    m = rt_o.store.metrics
    assert m.counter("opt_cse_hits").total() == rep.cse_hits
    assert m.counter("opt_cache_misses").total() == rep.cache_misses
    assert rep.cse_hits > 0 and so.aap_count < su.aap_count
    speedup = su.ns / so.ns
    aap_red = su.aap_count / so.aap_count
    assert speedup >= 1.5, f"optimizer saved only {speedup:.2f}x"

    # round 2: the same mix again - served entirely from the result cache
    t2 = submit_all(rt_o, table_o, queries)
    rt_o.drain(optimize=True)
    check(rt_o, table_o, queries, t2)
    rep2 = rt_o.last_drain.opt
    assert rep2.cache_hits == n_queries
    assert rt_o.last_drain.stats.aap_count == 0
    assert m.counter("opt_cache_hits").total() == rep2.cache_hits

    return [("kern_pim_optimizer", us_opt,
             f"queries={n_queries} tenants={n_tenants} "
             f"dram_speedup={speedup:.1f}x "
             f"({su.ns:.0f} vs {so.ns:.0f} ns) aap_reduction="
             f"{aap_red:.1f}x ({su.aap_count} vs {so.aap_count}) "
             f"cse_hits={rep.cse_hits} cse_mat={rep.cse_materialized} "
             f"cache_hits={rep2.cache_hits} "
             f"unopt_wall={us_unopt:.0f}us")]


def pallas_resident_chain(n_ops: int = 6, rows: int = 64,
                          n_queries: int = 4) -> List[Row]:
    """Accelerator-resident DeviceStore vs the non-resident jnp path: a
    ``n_ops``-AND dependent chain over ``rows`` x 8192-bit operands. The
    non-resident engine ships every operand host->device and the result
    back on EVERY op; the resident path uploads each operand once,
    chains on-device through ``out=`` rebinds (donated buffers - no
    allocation churn), and reads back only the final result - measured
    ``bytes_touched`` must drop >= 2x. Then ``n_queries`` same-shape
    queries submit+drain on the pallas backend: the epoch dispatches as
    ONE stacked fused kernel (the ``fused_dispatches`` counter),
    bit-identical to serial eval."""
    from repro.core import BitVector, BulkBitwiseEngine, Expr
    from repro.pim import AmbitRuntime

    rng = np.random.default_rng(0)
    n_bits = 8192
    bits = rng.integers(0, 2, (n_ops + 1, rows, n_bits)).astype(bool)
    vecs = [BitVector.from_bits(b) for b in bits]

    eng = BulkBitwiseEngine("jnp")

    def host_chain():
        acc, nbytes = vecs[0], 0
        for bv in vecs[1:]:
            acc = eng.and_(acc, bv)
            nbytes += eng.last_stats.bytes_touched
        return acc, nbytes

    x, y = Expr.var("x"), Expr.var("y")

    def resident_chain():
        rt = AmbitRuntime(backend="pallas")
        hs = [rt.put(bv) for bv in vecs]
        acc = rt.and_(hs[0], hs[1])
        for h in hs[2:]:                 # donated in-place rebinds
            rt.eval(x & y, {"x": acc, "y": h}, out=acc)
        rt.get(acc)
        return rt, acc

    us_host = _time(lambda: host_chain(), reps=2)
    us_res = _time(lambda: resident_chain(), reps=2)
    (host_acc, host_bytes), (rt, acc) = host_chain(), resident_chain()
    res_bytes = rt.session_stats.bytes_touched
    assert np.array_equal(np.asarray(rt.get(acc).bits()),
                          np.asarray(host_acc.bits()))
    assert host_bytes >= 2 * res_bytes, (host_bytes, res_bytes)

    # multi-query drain: one fused stacked kernel per epoch
    rt2 = AmbitRuntime(backend="pallas")
    qbits = rng.integers(0, 2, (n_queries, 2, rows, n_bits)).astype(bool)
    envs = [{"x": rt2.put(BitVector.from_bits(qb[0])),
             "y": rt2.put(BitVector.from_bits(qb[1]))} for qb in qbits]
    tickets = [rt2.submit(x & y, env) for env in envs]
    rt2.drain()
    epochs = len(rt2.last_drain.epochs)
    dispatches = int(rt2.metrics.counter("fused_dispatches").total())
    assert epochs == 1 and dispatches == 1, (epochs, dispatches)
    for t, qb in zip(tickets, qbits):
        assert np.array_equal(np.asarray(rt2.get(t.result).bits()),
                              qb[0] & qb[1])
    return [("kern_pallas_resident_chain", us_res,
             f"ops={n_ops} rows={rows} "
             f"traffic={host_bytes / res_bytes:.1f}x "
             f"res_bytes={res_bytes} host_bytes={host_bytes} "
             f"queries={n_queries} epochs={epochs} "
             f"fused_dispatches={dispatches} host_wall={us_host:.0f}us")]


def kernels_micro() -> List[Row]:
    from repro.core import expr as E
    from repro.kernels import ops, ref

    rows: List[Row] = []
    rows.extend(ambit_batched_speedup())
    rows.extend(pim_resident_chain())
    rows.extend(pallas_resident_chain())
    rows.extend(pim_sharded_scan())
    rows.extend(pim_async_multiquery())
    rows.extend(pim_optimizer())
    rng = np.random.default_rng(0)
    shape = (256, 4096)  # 4 MB packed = 128 Mbit operands
    nbytes = int(np.prod(shape)) * 4
    arrs = {k: jnp.asarray(rng.integers(0, 2**32, shape, dtype=np.uint32))
            for k in "abc"}

    x, y, z = E.Expr.var("a"), E.Expr.var("b"), E.Expr.var("c")
    single = x & y
    fused = ((x & y) | ~z) ^ (x | z)

    us1 = _time(lambda: ops.bitwise_eval(single, arrs))
    usf = _time(lambda: ops.bitwise_eval(fused, arrs))
    ideal1 = 3 * nbytes / HBM_BW * 1e6
    # fused: 3 inputs + 1 output vs 4 ops x 3 buffers unfused
    ideal_f = 4 * nbytes / HBM_BW * 1e6
    ideal_unfused = 4 * 3 * nbytes / HBM_BW * 1e6
    rows.append(("kern_bitwise_and", us1,
                 f"tpu_roofline={ideal1:.1f}us bytes={3*nbytes}"))
    rows.append(("kern_bitwise_fused4", usf,
                 f"tpu_roofline={ideal_f:.1f}us vs_unfused="
                 f"{ideal_unfused:.1f}us fusion_win="
                 f"{ideal_unfused/ideal_f:.1f}x"))

    us = _time(lambda: ops.popcount(arrs["a"]))
    rows.append(("kern_popcount", us,
                 f"tpu_roofline={nbytes/HBM_BW*1e6:.1f}us"))

    vals = rng.integers(0, 2**12, 2**20).astype(np.uint32)
    planes = ref.bitslice(jnp.asarray(vals), 12)
    us = _time(lambda: ops.bitweaving_scan(planes, 100, 3000))
    pb = int(planes.size) * 4
    rows.append(("kern_bitweaving_b12", us,
                 f"tpu_roofline={pb/HBM_BW*1e6:.2f}us "
                 f"vs_int32_scan={4*2**20/HBM_BW*1e6:.2f}us "
                 f"traffic_saving={4*2**20/pb:.1f}x"))

    m = n = 256
    k = 4096
    from repro.core.bitvector import pack_bits
    a = pack_bits(jnp.asarray(rng.integers(0, 2, (m, k)), jnp.uint32))
    b = pack_bits(jnp.asarray(rng.integers(0, 2, (n, k)), jnp.uint32))
    us_vpu = _time(lambda: ops.binary_matmul(a, b, k))
    us_mxu = _time(lambda: ops.binary_matmul_mxu(a, b, k))
    xnor_ops = m * n * (k // 32) * 3  # xor+popcount+add per word
    mxu_flops = 2 * m * n * k
    rows.append(("kern_binary_matmul_vpu", us_vpu,
                 f"word_ops={xnor_ops:.3g} packed_bytes={(m+n)*k//8}"))
    rows.append(("kern_binary_matmul_mxu", us_mxu,
                 f"mxu_flops={mxu_flops:.3g} "
                 f"tpu_mxu_time={mxu_flops/197e12*1e6:.2f}us"))
    return rows
