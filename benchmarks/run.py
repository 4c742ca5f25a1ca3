"""Benchmark harness: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV. Sections:
  fig20_*   AAP program counts (compiler opt) + bit-exactness
  fig20b_*  batched ambit_sim engine path (rows/s + compile cache)
  table3_*  TRA failure rate vs process variation (Monte Carlo)
  fig21_*   raw throughput model vs Skylake/GTX745/HMC (+Ambit-3D)
  table4_*  energy nJ/KB vs DDR3 baseline
  fig22_*   bitmap index queries        (Section 8.1)
  fig23_*   BitWeaving predicate scans  (Section 8.2)
  fig24_*   bitvector set operations    (Section 8.3)
  kern_*    Pallas kernel micro + engine roofline model
  refresh_* DRAM timing-rule oracle + refresh-interference model
  serve_*   closed-loop multi-tenant serving (continuous batching)
  faults_*  reliability: TMR tax + serving under injected faults

Machine-readable output: ``--json out.json`` additionally writes every
row as ``{"section", "name", "us", "derived"}`` records (schema 1).
Wall-clock lives only in ``us`` and non-integer derived tokens, so the
structural fields (names, op counts, ledger bytes/ns) diff cleanly
across machines - see benchmarks/compare.py and the committed
BENCH_kernels.json baseline. ``--sections kernels_micro`` (comma list,
substring match on section function names) restricts the run.
``--trace DIR`` threads a simulated-clock Tracer through the sections
that support it (serving) and writes Chrome/Perfetto trace JSON per
row into DIR - summarise with ``python tools/trace_report.py``.
"""

import argparse
import functools
import json
import sys


def sections(trace_dir=None):
    from . import (faults, kernels_micro, paper_apps, paper_tables,
                   refresh, serve_closed_loop)

    serve = serve_closed_loop.serve_closed_loop
    if trace_dir is not None:
        traced = functools.partial(serve, trace_dir=trace_dir)
        functools.update_wrapper(traced, serve)
        serve = traced
    return [
        paper_tables.fig20_programs,
        paper_tables.fig20_batched,
        paper_tables.table3_variation,
        paper_tables.fig21_throughput,
        paper_tables.table4_energy,
        paper_apps.fig22_bitmap,
        paper_apps.fig23_bitweaving,
        paper_apps.fig24_sets,
        kernels_micro.kernels_micro,
        refresh.refresh,
        serve,
        faults.faults,
    ]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(
        description="benchmark harness (see module docstring)")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="also write rows as JSON records")
    ap.add_argument("--sections", default=None,
                    help="comma-separated substring filter on section "
                         "function names (e.g. 'kernels_micro')")
    ap.add_argument("--trace", metavar="DIR", default=None,
                    help="write Chrome/Perfetto trace JSON per serving "
                         "row into DIR")
    args = ap.parse_args(argv)
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()

    wanted = None
    if args.sections:
        wanted = [s.strip() for s in args.sections.split(",") if s.strip()]

    print("name,us_per_call,derived")
    rows, failures = [], 0
    for fn in sections(trace_dir=args.trace):
        if wanted is not None and \
                not any(w in fn.__name__ for w in wanted):
            continue
        try:
            for name, us, derived in fn():
                print(f"{name},{us:.2f},{derived}")
                rows.append({"section": fn.__name__, "name": name,
                             "us": round(us, 2), "derived": derived})
        except Exception as e:  # keep the harness robust
            failures += 1
            print(f"{fn.__name__},0.0,ERROR {type(e).__name__}: {e}")
            sys.stderr.write(f"benchmark {fn.__name__} failed: {e}\n")
    if args.json:
        with open(args.json, "w") as fh:
            json.dump({"schema": 1, "rows": rows}, fh, indent=1,
                      sort_keys=True)
            fh.write("\n")
    if failures:
        raise SystemExit(f"{failures} benchmark section(s) failed")


if __name__ == "__main__":
    main()
