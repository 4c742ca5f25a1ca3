"""The control of the check: the plain reference put in the program's
place, in a form that breaks the configuration's guarantee ("every count
exact"), held to the same check a run uses. The check has to call it
incorrect on every seed.

    python3 bench/control.py --workload <cell> --requests <n> \
        --seeds <a> <b> <c> ...

For each seed it draws the first ``n`` requests of the cell's mix (round
robin over its clients, as a run would issue them), answers every query
with ``reference.answers(..., control=True)`` at the cell's own size, and
prints one JSON line with the check's numbers. The benchmark's own runs
never run this. It refuses to run without a TPU, like run.py.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def control_requests(cell, seed: int, n: int) -> list:
    from bench import harness, loadgen
    streams = [loadgen.ClientStream(cell.mix, seed, c)
               for c in range(cell.mix.clients)]
    out = []
    for k in range(n):
        qs = cell.data.queries(cell.mix.parts,
                               streams[k % len(streams)].next(), cell.cfg)
        out.append(harness.Request(k % len(streams), qs, 0, done_ns=1))
    return out


def run(cell, seed: int, n: int) -> dict:
    from bench import harness
    reqs = control_requests(cell, seed, n)
    keys = sorted({q.key for r in reqs for q in r.queries})
    ctl = cell.reference.answers(seed, cell.cfg, keys, control=True)
    for r in reqs:
        r.counts = {i: ctl[q.key] for i, q in enumerate(r.queries)}
    checks, _ = harness.check(cell, seed, reqs)
    return {"workload": cell.name, "seed": seed, "requests": n,
            "queries": sum(len(r.queries) for r in reqs),
            "correct": all(c["value"] <= c["limit"]
                           for c in checks.values()),
            "checks": checks}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--requests", type=int, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    if jax.devices()[0].platform != "tpu":
        print("control: no TPU; nothing was run", file=sys.stderr)
        return 1
    from bench import harness
    cell = harness.resolve(args.workload)
    for seed in args.seeds:
        print(json.dumps(run(cell, seed, args.requests)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
