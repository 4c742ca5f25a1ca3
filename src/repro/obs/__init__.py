"""Observability on two clocks: simulated-clock tracing + metrics, and
wall-clock host spans.

tracer.py records spans on the simulated clock (DRAM timing rules, the
scheduler's epoch timeline), so traces are reproducible; metrics.py is
the always-on registry; export.py writes Perfetto JSON and text reports.
Layers accept ``tracer=``/``metrics=`` and default to the disabled
``NULL_TRACER`` / a private registry.

host.py's ``host_span`` is on the host's wall clock: it writes into the
JAX profiler's trace, beside the device's own events, and records only
while a profiler trace is running.
"""

from .tracer import NULL_TRACER, TraceEvent, Tracer
from .metrics import Counter, Gauge, Histogram, MetricsRegistry
from .export import chrome_trace, utilization_report, write_chrome_trace
from .host import (FRONTEND_DRAIN, FRONTEND_SUBMIT, PLAN_PREDICATE,
                   PLANNER_EPOCH, PLANNER_LAUNCH, PLANNER_OPERAND_BYTES,
                   PLANNER_STACK, PLANNER_STACK_BYTES, SCHEDULER_DRAIN,
                   STORE_POPCOUNT, STORE_POPCOUNT_FLAT, STORE_POPCOUNT_WAIT,
                   host_span)

__all__ = [
    "NULL_TRACER",
    "TraceEvent",
    "Tracer",
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "chrome_trace",
    "utilization_report",
    "write_chrome_trace",
    "host_span",
    "FRONTEND_SUBMIT",
    "FRONTEND_DRAIN",
    "SCHEDULER_DRAIN",
    "PLANNER_EPOCH",
    "PLANNER_STACK",
    "PLANNER_LAUNCH",
    "STORE_POPCOUNT",
    "STORE_POPCOUNT_WAIT",
    "PLAN_PREDICATE",
    "PLANNER_STACK_BYTES",
    "PLANNER_OPERAND_BYTES",
    "STORE_POPCOUNT_FLAT",
]
