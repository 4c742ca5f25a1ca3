"""Reduction of a profiler trace (``.xplane.pb``) to device and host time.

Device work is read from the ``XLA Ops`` line of each ``/device:`` plane
(one event per operation, with its device start and duration) and the
``XLA Modules`` line (one event per program run). Host spans are the
``TraceAnnotation`` events the benchmark writes (names starting with
``bench.``). The device clock in the trace can run ahead of the host's by
a millisecond or two; it is aligned here by the rule that no program
starts before the host enqueued it (``DoEnqueueProgram`` and the device
module share a ``run_id``).
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Tuple

Interval = Tuple[float, float]

WINDOW_SPAN = "bench.window"


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(
            f"expected one .xplane.pb under {log_dir}, found {paths}")
    return paths[0]


def union(intervals: List[Interval], lo: float, hi: float
          ) -> List[Interval]:
    """Merge intervals clipped to [lo, hi] into disjoint sorted ones."""
    out: List[List[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    """The parts of [lo, hi] that disjoint sorted ``busy`` leaves free."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def op_label(module: str, op: str) -> str:
    """``jit_compute(123)`` and ``%pad.2 = u32[...] pad(...)`` ->
    ``jit_compute:pad``: the program and the HLO instruction, without
    the numeric suffixes that change from one compile to the next."""
    mod = module.split("(")[0]
    ins = op.split(" = ")[0].lstrip("%")
    ins = re.sub(r"\.\d+$", "", ins)
    return f"{mod}:{ins}"


@dataclasses.dataclass
class Reduction:
    """What one traced window holds, on the host's clock (ns)."""

    window: Interval
    devices: int
    ops: List[Tuple[str, float, float]]       # (label, start, end)
    busy: List[Interval]                      # union of op intervals
    host_spans: Dict[str, List[Interval]]     # bench.* spans, clipped
    clock_offset_ns: float

    @property
    def window_ns(self) -> float:
        return self.window[1] - self.window[0]

    @property
    def busy_ns(self) -> float:
        """Union of device op intervals, averaged over devices."""
        return sum(e - s for s, e in self.busy) / self.devices

    @property
    def op_ns(self) -> float:
        """Summed device time of every op, over all devices."""
        return sum(e - s for _, s, e in self.ops)

    @property
    def idle_pct(self) -> float:
        return 100.0 * (1.0 - self.busy_ns / self.window_ns)

    def host_ns(self, name: str) -> float:
        return sum(e - s for s, e in self.host_spans.get(name, []))

    def ops_by_label(self) -> List[Tuple[str, float]]:
        tot: Dict[str, float] = defaultdict(float)
        for label, s, e in self.ops:
            tot[label] += e - s
        return sorted(tot.items(), key=lambda kv: -kv[1])

    def idle_by_host(self) -> List[Tuple[str, float]]:
        """Idle device time, by the innermost ``bench.`` span the host
        was in at the middle of each gap (``bench.loop`` when none)."""
        spans = sorted((s, e, name) for name, ivs in self.host_spans.items()
                       if name != WINDOW_SPAN for s, e in ivs)
        starts = [s for s, _, _ in spans]
        tot: Dict[str, float] = defaultdict(float)
        for s, e in gaps(self.busy, *self.window):
            mid = 0.5 * (s + e)
            k = bisect.bisect_right(starts, mid) - 1
            name = spans[k][2] if k >= 0 and mid < spans[k][1] \
                else "bench.loop"
            tot[name] += e - s
        return sorted(tot.items(), key=lambda kv: -kv[1])


def _stats(event) -> dict:
    return {k: v for k, v in event.stats}


def reduce_profile(profile, window_span: str = WINDOW_SPAN) -> Reduction:
    """Reduce a ``jax.profiler.ProfileData`` to a :class:`Reduction`
    over the first ``window_span`` host span."""
    dev_lines, host_events, enqueue = [], [], {}
    for plane in profile.planes:
        lines = {ln.name: ln for ln in plane.lines}
        if plane.name.startswith("/device:"):
            if "XLA Ops" in lines:
                dev_lines.append((lines.get("XLA Modules"),
                                  lines["XLA Ops"]))
            continue
        if not plane.name.startswith("/host:"):
            continue
        for ln in plane.lines:
            for ev in ln.events:
                if ev.name.startswith("bench."):
                    host_events.append((ev.name, ev.start_ns, ev.end_ns))
                elif ev.name == "DoEnqueueProgram":
                    rid = _stats(ev).get("run_id")
                    if rid is not None:
                        enqueue[rid] = ev.start_ns
    windows = [(s, e) for n, s, e in host_events if n == window_span]
    if not windows:
        raise ValueError(f"no {window_span!r} span in the trace")
    if not dev_lines:
        raise ValueError("no device plane with an 'XLA Ops' line")
    lo, hi = windows[0]
    # Align the device clock: a program cannot start before its enqueue.
    lead = []
    for modules, _ in dev_lines:
        for ev in (modules.events if modules is not None else ()):
            rid = _stats(ev).get("run_id")
            if rid in enqueue:
                lead.append(enqueue[rid] - ev.start_ns)
    offset = max(0.0, max(lead)) if lead else 0.0
    ops: List[Tuple[str, float, float]] = []
    intervals: List[Interval] = []
    for modules, op_line in dev_lines:
        mods = sorted((ev.start_ns + offset, ev.end_ns + offset, ev.name)
                      for ev in (modules.events if modules is not None
                                 else ()))
        k = 0
        for ev in sorted(op_line.events, key=lambda e: e.start_ns):
            s, e = ev.start_ns + offset, ev.end_ns + offset
            while k + 1 < len(mods) and mods[k + 1][0] <= s:
                k += 1
            module = mods[k][2] if mods and mods[k][0] <= s < mods[k][1] \
                else "?"
            cs, ce = max(s, lo), min(e, hi)
            if ce > cs:
                ops.append((op_label(module, ev.name), cs, ce))
                intervals.append((cs, ce))
    host: Dict[str, List[Interval]] = defaultdict(list)
    for name, s, e in host_events:
        cs, ce = max(s, lo), min(e, hi)
        if ce > cs:
            host[name].append((cs, ce))
    return Reduction(window=(lo, hi), devices=len(dev_lines), ops=ops,
                     busy=union(intervals, lo, hi), host_spans=dict(host),
                     clock_offset_ns=offset)


def reduce_file(path: str, window_span: str = WINDOW_SPAN
                ) -> Reduction:
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(path), window_span)


def load_peaks(path: str, device_kind: str) -> dict:
    """The peaks of ``device_kind`` from the peaks table; a kind that the
    table does not hold is an error, never a default."""
    import json
    table = json.loads(open(path).read())
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{path}; add them with their source")
    return table[device_kind]
