"""Bitmap-index analytics (paper Section 8.1): the weekly-active-users
query on all engine backends, with the DRAM ledger *measured* by the
device model - host (non-resident) engine path vs the resident PIM
runtime - and compared against the old analytic formula.

Run:  PYTHONPATH=src python examples/bitmap_analytics.py
"""

import numpy as np

from repro.apps.bitmap_index import BitmapIndex, baseline_cpu_ns
from repro.core import BulkBitwiseEngine
from repro.pim import AmbitRuntime


def main():
    rng = np.random.default_rng(0)
    n_users, weeks = 1 << 20, 6
    week_names = [f"week{w}" for w in range(weeks)]

    def populate(idx):
        member_rng = np.random.default_rng(1)
        for w in week_names:
            idx.add(w, member_rng.choice(n_users, n_users // 3,
                                         replace=False))
        idx.add("male", member_rng.choice(n_users, n_users // 2,
                                          replace=False))

    for backend in ("jnp", "pallas"):
        idx = BitmapIndex(n_users, BulkBitwiseEngine(backend))
        populate(idx)
        uniq, per_week, _ = idx.weekly_active_query(week_names, "male")
        print(f"[{backend:8s}] users active all {weeks} weeks: {uniq}; "
              f"male per week: {per_week}")

    # Measured DRAM ledger, host path: every AND round-trips the channel.
    # Run it geometry-faithfully - each bitmap reshaped to (16, 65536) so
    # one logical row = one real 8 KB DRAM row, the same layout the
    # resident path uses (a flat 2^20-bit operand would be modeled as one
    # fictitious 128 KB row and undercount AAPs 16x).
    idx = BitmapIndex(n_users, BulkBitwiseEngine("ambit_sim"))
    populate(idx)
    uniq, per_week, _ = idx.weekly_active_query(week_names, "male")
    print(f"[ambit_sim] users active all {weeks} weeks: {uniq}; "
          f"male per week: {per_week}")

    from repro.core import BitVector
    from repro.core.engine import OpStats
    eng = BulkBitwiseEngine("ambit_sim")
    host_st = OpStats()
    rows = {nm: BitVector.from_bits(
        np.asarray(idx.bitmaps[nm].bits()).reshape(16, 65536))
        for nm in week_names + ["male"]}
    acc = rows[week_names[0]]
    for nm in week_names[1:]:
        acc = eng.and_(acc, rows[nm])
        host_st += eng.last_stats
    for nm in week_names:
        eng.and_(rows[nm], rows["male"])
        host_st += eng.last_stats
    assert int(acc.popcount().sum()) == uniq
    print(f"[ambit_sim] measured host-path ledger: {host_st.ns/1e3:.1f} us "
          f"{host_st.energy_nj/1e3:.2f} uJ aap={host_st.aap_count} "
          f"host_bytes={host_st.bytes_touched}")

    # Measured DRAM ledger, resident path: bitmaps live in DRAM, queries
    # lower as whole expression trees, only popcounts read data back.
    rt = AmbitRuntime(seed=2)
    idx = BitmapIndex(n_users, runtime=rt)
    populate(idx)
    uniq_r, per_week_r, res_st = idx.weekly_active_query(week_names, "male")
    assert (uniq_r, per_week_r) == (uniq, per_week), "paths disagree"
    print(f"[resident ] measured ledger: {res_st.ns/1e3:.1f} us "
          f"{res_st.energy_nj/1e3:.2f} uJ aap={res_st.aap_count} "
          f"host_bytes={res_st.bytes_touched} "
          f"(upload once: {rt.store.bytes_to_device} B, "
          f"read-backs: {rt.host_reads})")

    # Sharded resident path: the same bitmaps over a 4-device PimCluster.
    # Round-robin chunk placement + the near= chain keep co-queried
    # bitmaps chunk-aligned, so each device runs 1/4 of every op (time is
    # max-over-devices) and the measured inter-device traffic stays zero.
    rt4 = AmbitRuntime(devices=4, seed=2)
    idx = BitmapIndex(n_users, runtime=rt4)
    populate(idx)
    uniq_s, per_week_s, sh_st = idx.weekly_active_query(week_names, "male")
    assert (uniq_s, per_week_s) == (uniq, per_week), "sharded disagrees"
    led = rt4.store.ledger
    print(f"[sharded x4] measured ledger: {sh_st.ns/1e3:.1f} us "
          f"{sh_st.energy_nj/1e3:.2f} uJ aap={sh_st.aap_count} "
          f"({res_st.ns/sh_st.ns:.1f}x vs 1 device; inter-device "
          f"{led.inter_device_bytes} B measured)")

    # Accelerator-resident path: the SAME app code on the pallas backend.
    # Bitmaps upload once as device arrays; the whole weekly query drains
    # as fused stacked kernel launches and only popcounts read back -
    # bytes_touched counts just those transfers (vs 3 buffers/op for the
    # non-resident engine path above).
    rt_dev = AmbitRuntime(backend="pallas")
    idx = BitmapIndex(n_users, runtime=rt_dev)
    populate(idx)
    uniq_d, per_week_d, dev_st = idx.weekly_active_query(week_names, "male")
    assert (uniq_d, per_week_d) == (uniq, per_week), "device disagrees"
    print(f"[pallas res] traffic ledger: query host_bytes="
          f"{dev_st.bytes_touched} B (uploads once: "
          f"{rt_dev.store.bytes_to_device} B, read-backs: "
          f"{rt_dev.host_reads}, fused launches: "
          f"{int(rt_dev.metrics.counter('fused_dispatches').total())})")

    # Observability: the same ledgers as labeled metric series. Bytes
    # are broken down by WHY they crossed the channel (upload vs
    # fault-in vs spill vs read-back) and per-bank busy ns comes from
    # the planner's bank_busy_ns counter - the series the utilization
    # report and trace exporter consume (see README "Observability").
    snap = rt.metrics_snapshot()
    io = {k: int(v) for k, v in snap["counters"].items()
          if k.startswith("store_io_bytes")}
    busy = {k: v for k, v in snap["counters"].items()
            if k.startswith("bank_busy_ns")}
    print("[metrics  ] bytes by cause:")
    for k in sorted(io):
        print(f"             {k} = {io[k]}")
    total_busy = sum(busy.values())
    print(f"[metrics  ] banks={len(busy)} total_busy_ns={total_busy:.0f}"
          + (f" mean_busy_pct="
             f"{100.0 * total_busy / (len(busy) * res_st.ns):.1f}"
             if busy and res_st.ns else ""))

    # Analytic model (what this example used to print) for comparison.
    n_ops = 2 * weeks - 1
    rows = n_users // 65536
    analytic_ns = n_ops * max(1, rows // 8) * 4 * 49.0
    cpu_ns = baseline_cpu_ns(n_users, n_ops)
    print(f"analytic: Ambit {analytic_ns/1e3:.1f} us (vs measured resident "
          f"{res_st.ns/1e3:.1f} us) | CPU {cpu_ns/1e3:.1f} us -> "
          f"{cpu_ns/res_st.ns:.1f}x measured "
          f"(paper reports ~6x end-to-end)")


if __name__ == "__main__":
    main()
