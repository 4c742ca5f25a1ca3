"""The program's own host spans in a profiler trace, and the host time
per request of each layer they delimit.

The program writes its spans (names starting with ``repro.``, spelled in
``repro.obs``) into the profiler's trace on the same host clock as the
benchmark's ``bench.*`` spans, so they clip to the same ``bench.window``.
A layer's time is the time inside its spans that its inner spans do not
cover, computed on intervals (nested and repeated spans count once), not
by subtracting sums:

  * ``frontend_self_ms_per_req``: ``repro.frontend.submit`` and
    ``repro.frontend.drain`` outside ``repro.scheduler.drain``;
  * ``scheduler_self_ms_per_req``: ``repro.scheduler.drain`` outside
    ``repro.planner.epoch`` (epoch formation, the timeline, accounting);
  * ``planner_host_ms_per_req``: ``repro.planner.epoch``;
  * ``stack_host_ms_per_req``: ``repro.planner.stack``;
  * ``launch_host_ms_per_req``: ``repro.planner.launch``;
  * ``popcount_host_ms_per_req``: ``repro.store.popcount`` outside
    ``repro.store.popcount_wait``;
  * ``popcount_wait_ms_per_req``: ``repro.store.popcount_wait``.

``stack_mib_per_query`` reads the program's ``planner_stack_bytes``
counter over the window instead. Idle device time is put down to the
innermost span, ``bench.*`` or ``repro.*``, that holds each gap's middle.
"""

from __future__ import annotations

from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Tuple

from bench.trace_reduce import WINDOW_SPAN, Interval, gaps, union
from repro import obs

PREFIX = "repro."

LAYERS: Dict[str, Tuple[Tuple[str, ...], Tuple[str, ...]]] = {
    "frontend_self_ms_per_req": (
        (obs.FRONTEND_SUBMIT, obs.FRONTEND_DRAIN), (obs.SCHEDULER_DRAIN,)),
    "scheduler_self_ms_per_req": (
        (obs.SCHEDULER_DRAIN,), (obs.PLANNER_EPOCH,)),
    "planner_host_ms_per_req": ((obs.PLANNER_EPOCH,), ()),
    "stack_host_ms_per_req": ((obs.PLANNER_STACK,), ()),
    "launch_host_ms_per_req": ((obs.PLANNER_LAUNCH,), ()),
    "popcount_host_ms_per_req": (
        (obs.STORE_POPCOUNT,), (obs.STORE_POPCOUNT_WAIT,)),
    "popcount_wait_ms_per_req": ((obs.STORE_POPCOUNT_WAIT,), ()),
}


def host_spans(profile, window_span: str = WINDOW_SPAN
               ) -> Tuple[Interval, Dict[str, List[Interval]]]:
    """The first ``window_span`` of a ``jax.profiler.ProfileData`` and
    every ``repro.*`` host span, clipped to it."""
    events = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for ln in plane.lines:
            for ev in ln.events:
                if ev.name.startswith(PREFIX) or ev.name == window_span:
                    events.append((ev.name, ev.start_ns, ev.end_ns))
    windows = [(s, e) for n, s, e in events if n == window_span]
    if not windows:
        raise ValueError(f"no {window_span!r} span in the trace")
    lo, hi = windows[0]
    spans: Dict[str, List[Interval]] = defaultdict(list)
    for name, s, e in events:
        cs, ce = max(s, lo), min(e, hi)
        if name != window_span and ce > cs:
            spans[name].append((cs, ce))
    return (lo, hi), dict(spans)


def outside(inner: Iterable[Interval], outer: Iterable[Interval],
            lo: float, hi: float) -> float:
    """Time in [lo, hi] covered by ``inner`` and not by ``outer``."""
    covered = union(list(inner), lo, hi)
    total = sum(e - s for s, e in covered)
    cut = union(list(outer), lo, hi)
    k = 0
    for s, e in covered:
        while k < len(cut) and cut[k][1] <= s:
            k += 1
        j = k
        while j < len(cut) and cut[j][0] < e:
            total -= min(e, cut[j][1]) - max(s, cut[j][0])
            j += 1
    return total


def layer_ms_per_req(window: Interval, spans: Dict[str, List[Interval]],
                     requests: int) -> Dict[str, Optional[float]]:
    """Each layer's host time in the window per request completed in it;
    None for a layer whose spans the trace does not hold."""
    out: Dict[str, Optional[float]] = {}
    for metric, (inner, outer) in LAYERS.items():
        ivs = [iv for name in inner for iv in spans.get(name, ())]
        if not ivs or not requests:
            out[metric] = None
            continue
        cut = [iv for name in outer for iv in spans.get(name, ())]
        out[metric] = outside(ivs, cut, *window) / 1e6 / requests
    return out


def stack_mib_per_query(stack_bytes: float, queries: int
                        ) -> Optional[float]:
    """MiB written into operand stacks in the window per query completed
    in it (the window's delta of ``planner_stack_bytes``)."""
    if not queries:
        return None
    return stack_bytes / 2 ** 20 / queries


def idle_by_innermost(busy: List[Interval], window: Interval,
                      spans: Dict[str, List[Interval]]
                      ) -> List[Tuple[str, float]]:
    """Idle device time in ``window``, by the innermost of ``spans``
    that holds the middle of each gap (``bench.loop`` when none). The
    spans come from one host thread, so they nest."""
    order = sorted((s, -e, name) for name, ivs in spans.items()
                   if name != WINDOW_SPAN for s, e in ivs)
    tot: Dict[str, float] = defaultdict(float)
    stack: List[Tuple[float, str]] = []     # (end, name), outermost first
    k = 0
    for s, e in gaps(busy, *window):
        mid = 0.5 * (s + e)
        while k < len(order) and order[k][0] <= mid:
            start, neg_end, name = order[k]
            while stack and stack[-1][0] <= start:
                stack.pop()
            stack.append((-neg_end, name))
            k += 1
        while stack and stack[-1][0] <= mid:
            stack.pop()
        tot[stack[-1][1] if stack else "bench.loop"] += e - s
    return sorted(tot.items(), key=lambda kv: -kv[1])
