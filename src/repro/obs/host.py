"""Wall-clock host spans of the served query path.

``host_span`` writes a span into the JAX profiler's own trace, where it
lands on the host clock that the profiler also puts the device's
programs on, so a trace reader can tell which host work the device waits
through. The profiler keeps the spans in memory and writes them out when
the trace stops; with no profiler running a span costs about a
microsecond and records nothing. Spans sit at layer boundaries only,
never per operand.

Every name is spelled here once, and starts with ``repro.``:

  * ``FRONTEND_SUBMIT`` - ``QueryFrontend.submit`` (admission, and any
    drain the submission fills);
  * ``FRONTEND_DRAIN`` - one batching-window drain of the frontend;
  * ``SCHEDULER_DRAIN`` - ``AsyncScheduler.drain``: epoch formation, the
    epochs' dispatch and the timeline's accounting;
  * ``PLANNER_EPOCH`` - one epoch's dispatch through
    ``DevicePlanner.execute_epoch``, singleton epochs included;
  * ``PLANNER_STACK`` - the host's gathering of a multi-query epoch's
    operand buffers (the stacking itself runs inside the epoch program);
  * ``PLANNER_LAUNCH`` - the call of a jitted fused program;
  * ``STORE_POPCOUNT`` - ``DeviceStore.popcount``;
  * ``STORE_POPCOUNT_WAIT`` - its blocking read of the per-row counts;
  * ``PLAN_PREDICATE`` - ``apps.bitweaving_db.conjunction_plan``: the
    host's build of a BitWeaving conjunction's expression and env, once
    per query.

``PLANNER_STACK_BYTES`` names the ``MetricsRegistry`` counter of bytes
the epoch program writes into operand stacks on the device (queries x
operands x bytes per operand). ``PLANNER_OPERAND_BYTES`` counts the
bytes of operands as the fused program receives them, after its row and
lane padding (queries x operands x padded bytes per operand), at every
launch, singleton or stacked. ``STORE_POPCOUNT_FLAT`` counts the
``DeviceStore.popcount`` calls whose kernel reads the bitvector flat,
where it lies (``kernels.bitwise.reads_flat``).
"""

from __future__ import annotations

from jax.profiler import TraceAnnotation

FRONTEND_SUBMIT = "repro.frontend.submit"
FRONTEND_DRAIN = "repro.frontend.drain"
SCHEDULER_DRAIN = "repro.scheduler.drain"
PLANNER_EPOCH = "repro.planner.epoch"
PLANNER_STACK = "repro.planner.stack"
PLANNER_LAUNCH = "repro.planner.launch"
STORE_POPCOUNT = "repro.store.popcount"
STORE_POPCOUNT_WAIT = "repro.store.popcount_wait"
PLAN_PREDICATE = "repro.plan.predicate"

PLANNER_STACK_BYTES = "planner_stack_bytes"
PLANNER_OPERAND_BYTES = "planner_operand_bytes"
STORE_POPCOUNT_FLAT = "store_popcount_flat"


def host_span(name: str, **stats) -> TraceAnnotation:
    """A context manager that records ``name`` (one of the constants
    above) and ``stats`` as a span on the profiler's host clock."""
    return TraceAnnotation(name, **stats)
