"""Run one benchmark cell once: set-up, a measured window of closed-loop
clients through ``QueryFrontend`` over ``AmbitRuntime(backend="pallas")``,
then the check of every answered count against the configuration's plain
reference.

Everything that belongs to one configuration, traffic mix or metric is
found by its name in ``BENCHMARK.json``:

  * ``bench/configs/<config>/``: ``config.json`` (sizes), ``data.py``
    (data made on the device from the seed, queries and their plans) and
    ``reference.py`` (the plain reference);
  * ``bench/traffic/<mix>.json``: the mix that ``loadgen`` draws from;
  * ``bench/metrics/<metric>.py``: ``read(ctx)`` returns the metric, or
    None when the run holds nothing to read it from.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import math
import shutil
import sys
import tempfile
import time
from pathlib import Path
from types import SimpleNamespace
from typing import Dict, List, Optional

from bench import loadgen, trace_reduce

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
PEAKS = BENCH / "peaks.json"
ANSWER_WAIT_S = 60.0        # how long past the close an answer may take
MEMORY_EVERY_NS = 1_000_000  # how often the window samples bytes in use
TOP = 10                    # entries per breakdown list


def _module(path: Path, name: str):
    """Import ``path`` as module ``name`` once (names may hold dots)."""
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    cfg: dict
    mix: loadgen.Mix
    data: object                # the configuration's data.py
    reference: object           # the configuration's reference.py
    end_to_end: List[dict]
    per_layer: List[dict]


def load_benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def metric_reader(name: str):
    return _module(BENCH / "metrics" / f"{name}.py",
                   f"bench.metrics.{name}").read


def resolve(cell_name: str, cfg_overrides: Optional[dict] = None) -> Cell:
    """The cell's configuration, mix, modules and metrics, by name;
    ``cfg_overrides`` shrinks the configuration for CPU rehearsals."""
    bench = load_benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if cell_name not in cells:
        raise KeyError(f"no workload {cell_name!r} in BENCHMARK.json")
    w = cells[cell_name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    cfg_file = ROOT / conf["file"]
    cfg = {**json.loads(cfg_file.read_text()), **(cfg_overrides or {})}
    pkg = f"bench.configs.{conf['name']}"
    e2e = [m for m in bench["end_to_end"]
           if cell_name in m.get("workloads", [cell_name])]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (cell_name in m["workloads"] if "workloads" in m
                     else m["moves"] in e2e_names)]
    return Cell(cell_name, int(w["chips"]), cfg,
                loadgen.Mix.load(BENCH / "traffic" / f"{w['traffic']}.json"),
                _module(cfg_file.parent / "data.py", f"{pkg}.data"),
                _module(cfg_file.parent / "reference.py", f"{pkg}.reference"),
                e2e, per_layer)


class CompileCounter:
    """XLA compiles and persistent-cache loads, from JAX's monitoring
    events: either one inside the window means a program was not ready."""

    def __init__(self):
        import jax
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kwargs):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _event(self, event, **kwargs):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def total(self) -> int:
        return self.compiles + self.cache_hits


@dataclasses.dataclass(eq=False)
class Request:
    client: int
    queries: list
    issued_ns: int
    counts: Dict[int, Optional[int]] = dataclasses.field(default_factory=dict)
    done_ns: Optional[int] = None
    errors: int = 0
    fallbacks: int = 0


class ClosedLoop:
    """The closed loop: each client sends its next request once every
    count of its previous one is on the host."""

    def __init__(self, cell: Cell, seed: int, fe, rt, catalog, clock,
                 traced: bool):
        self.cell, self.fe, self.rt, self.catalog = cell, fe, rt, catalog
        self.clock = clock
        self.streams = [loadgen.ClientStream(cell.mix, seed, c)
                        for c in range(cell.mix.clients)]
        self.requests: List[Request] = []
        self.by_seq: Dict[int, tuple] = {}
        self.traced = traced

    def span(self, name: str):
        if not self.traced:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def issue(self, client: int) -> None:
        cell = self.cell
        qs = cell.data.queries(cell.mix.parts, self.streams[client].next(),
                               cell.cfg)
        req = Request(client, qs, self.clock())
        self.requests.append(req)
        for i, q in enumerate(qs):
            with self.span("bench.plan"):
                expr, env = cell.data.plan(self.catalog, q)
            with self.span("bench.frontend"):
                rec = self.fe.submit(f"c{client}", expr, env,
                                     arrival_ns=req.issued_ns)
            self.by_seq[rec.seq] = (req, i)

    def collect(self, on_done) -> int:
        """Count every completed query on the device, and hand each
        request whose counts are all on the host to ``on_done`` at once,
        so its client can send the next one before the other counts."""
        with self.span("bench.frontend"):
            done = self.fe.take_completed()
        for rec in done:
            req, i = self.by_seq.pop(rec.seq)
            count = None
            if rec.error is not None:
                req.errors += 1
            elif rec.fallback:
                req.fallbacks += 1
            else:
                with self.span("bench.popcount"):
                    count = self.rt.popcount(rec.result)
                self.rt.free(rec.result)
            req.counts[i] = count
            if len(req.counts) == len(req.queries):
                req.done_ns = self.clock()
                on_done(req)
        return len(done)

    def tick(self) -> None:
        with self.span("bench.frontend"):
            self.fe.tick(self.clock())

    def pending(self) -> int:
        return len(self.by_seq)


def warm_up(cell: Cell, rt, catalog) -> None:
    """Run every program the window can reach, at every epoch size its
    traffic can form, through the same submit/drain/popcount path."""
    mix, data = cell.mix, cell.data
    values = {p.name: p.values for p in mix.params}
    max_batch = cell.cfg["frontend"]["max_batch"]
    for query, per_request in data.program_examples(mix.parts, values,
                                                    cell.cfg):
        expr, env = data.plan(catalog, query)
        for size in range(1, min(max_batch, mix.clients * per_request) + 1):
            tickets = [rt.submit(expr, env) for _ in range(size)]
            rt.drain()
            for t in tickets:
                rt.popcount(t.result)
                rt.free(t.result)


def _in_use(device) -> int:
    """Bytes the device's buffers hold now (0 where JAX keeps no count,
    as on the CPU)."""
    return int((device.memory_stats() or {}).get("bytes_in_use", 0))


def _nearest_rank(values: List[float], p: float) -> Optional[float]:
    """The nearest-rank ``p`` quantile (the frontend's definition)."""
    if not values:
        return None
    v = sorted(values)
    return v[min(len(v) - 1, max(0, math.ceil(p * len(v) - 1e-9) - 1))]


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float) -> dict:
    """One run of ``cell``; returns the result line's object."""
    import jax

    from repro.pim import AmbitRuntime
    from repro.serve import QueryFrontend

    device = jax.devices()[0]
    compiles = CompileCounter()
    t0 = time.perf_counter_ns()

    def clock() -> int:
        return time.perf_counter_ns() - t0

    data = cell.data.build(seed, cell.cfg)
    rt = AmbitRuntime(backend="pallas")
    catalog = cell.data.load(rt, data, cell.cfg)
    warm_up(cell, rt, catalog)
    fe = QueryFrontend(rt, max_batch=cell.cfg["frontend"]["max_batch"])
    drv = ClosedLoop(cell, seed, fe, rt, catalog, clock, traced=trace)
    log_dir = None
    if trace:
        log_dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(log_dir, profiler_options=opts)

    # The window opens once every client has a request in flight.
    for c in range(cell.mix.clients):
        drv.issue(c)
    rc0 = dataclasses.replace(fe.report_counters)
    comp0 = compiles.total()
    t_open = clock()
    setup_s = time.perf_counter() - t_start
    deadline = t_open + int(seconds * 1e9)
    latencies: List[float] = []
    in_window: List[Request] = []

    def on_done(req: Request) -> None:
        if req.done_ns >= deadline:
            return
        if req.done_ns >= t_open:
            latencies.append((req.done_ns - req.issued_ns) / 1e6)
            in_window.append(req)
        drv.issue(req.client)

    # The window's memory: the most bytes in use that a sample finds
    # (the allocator's own peak also holds set-up's warm-up epochs).
    memory_peak = _in_use(device)
    next_sample = t_open
    with drv.span(trace_reduce.WINDOW_SPAN):
        while (now := clock()) < deadline:
            if now >= next_sample:
                memory_peak = max(memory_peak, _in_use(device))
                next_sample = now + MEMORY_EVERY_NS
            if not drv.collect(on_done):
                drv.tick()
    rc1 = dataclasses.replace(fe.report_counters)
    window_compiles = compiles.total() - comp0
    if trace:
        jax.profiler.stop_trace()

    # Every request in flight at the close is answered and checked too.
    give_up = clock() + int(ANSWER_WAIT_S * 1e9)
    while drv.pending() and clock() < give_up:
        fe.flush()
        drv.collect(lambda req: None)
    print(f"memory_stats after the window: {device.memory_stats()}",
          file=sys.stderr)

    window_bytes = sum(cell.data.least_bytes(req.queries, cell.cfg)
                       for req in in_window)
    requests = drv.requests
    del drv, fe, rt, catalog, data
    gc.collect()

    checks, ref = check(cell, seed, requests)
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    failed = sum(1 for r in requests if _failed(r, ref))

    red = None
    if trace:
        red = trace_reduce.reduce_file(trace_reduce.find_xplane(log_dir))
        shutil.rmtree(log_dir, ignore_errors=True)
    window_s = (deadline - t_open) / 1e9
    ctx = SimpleNamespace(
        setup_s=setup_s, window_s=window_s,
        latencies_ms=latencies, requests=len(in_window),
        queries=rc1.completed - rc0.completed,
        epochs=rc1.epochs - rc0.epochs, window_compiles=window_compiles,
        memory_peak_bytes=memory_peak, query_bytes=window_bytes,
        peaks=trace_reduce.load_peaks(str(PEAKS), device.device_kind)
        if trace else None,
        trace=red, percentile=_nearest_rank)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = metric_reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    dev = {"platform": device.platform, "kind": device.device_kind,
           "count": len(jax.devices()), "memory_peak_bytes": memory_peak}
    result = {"correct": correct, "attempted": len(requests),
              "failed": failed, "metrics": metrics, "device": dev}
    if red is not None:
        dev["busy_s"] = red.busy_ns / 1e9
        dev["window_s"] = red.window_ns / 1e9
        result["breakdown"] = {
            "device_ops": [[k, v / 1e9] for k, v in red.ops_by_label()[:TOP]],
            "idle_gaps": [[k, v / 1e9] for k, v in red.idle_by_host()[:TOP]]}
    result["checks"] = checks
    return result


def _failed(req: Request, ref: dict) -> bool:
    return bool(req.errors or req.fallbacks or req.done_ns is None or any(
        req.counts.get(i) != ref.get(q.key) for i, q in enumerate(req.queries)))


def check(cell: Cell, seed: int, requests: List[Request]) -> tuple:
    """Every answered count against the plain reference: the numbers
    compared, each with its limit, and the reference's answers."""
    keys = {q.key for r in requests for q in r.queries}
    ref = cell.reference.answers(seed, cell.cfg, sorted(keys))
    gap, unanswered = 0, 0
    for r in requests:
        for i, q in enumerate(r.queries):
            got = r.counts.get(i)
            if got is None:
                unanswered += 1 if i not in r.counts else 0
                continue
            gap = max(gap, abs(got - ref[q.key]))
    return {
        "count_gap_max": {"value": gap, "limit": 0},
        "errors": {"value": sum(r.errors for r in requests), "limit": 0},
        "fallbacks": {"value": sum(r.fallbacks for r in requests),
                      "limit": 0},
        "unanswered": {"value": unanswered, "limit": 0},
    }, ref
