"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's device
numbers.

  * the traced window: the host span ``bench.window`` the harness puts
    around the measured window;
  * device busy time: per device plane, the union of the intervals in
    which an operation ran (the ``XLA Ops`` line), clipped to the window;
    the idle share is 1 minus busy over the window, averaged over the
    devices;
  * device time per operation, by the short name of its HLO instruction
    (``while.174``, ``vqs_bf_pallas.1``; a control-flow operation's time
    holds its body's), and the time of the events whose name holds a given
    kernel name;
  * idle gaps: the stretches of the window in which no device ran an
    operation, each labelled by the innermost host event that covers its
    midpoint, among the benchmark's ``bench.*`` spans and the events of the
    Python threads (``np.asarray(jax.Array)``: a device-to-host copy,
    ``PjitFunction(...)``: a dispatch), or ``host:other`` where none does;
    summed per label.
"""
from __future__ import annotations

import glob
import heapq
import os
from bisect import bisect_right
from collections import defaultdict
from dataclasses import dataclass, field

OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."


@dataclass
class Reduction:
    window_s: float
    busy_s: float                    # mean over devices
    devices: int
    op_s: dict = field(default_factory=dict)   # name -> device seconds
    gaps: dict = field(default_factory=dict)   # host label -> idle seconds

    @property
    def idle_pct(self) -> float:
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    def kernel_s(self, names) -> float:
        """Device seconds of the events whose name contains any of
        ``names``."""
        return sum(s for op, s in self.op_s.items()
                   if any(k in op for k in names))

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.gaps.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in gaps]}


def union(intervals):
    """Merge ``(start, end)`` intervals; returns them sorted, disjoint."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def complement(busy, lo, hi):
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if t < hi:
        gaps.append((t, hi))
    return gaps


class Labels:
    """The innermost (shortest) host span at any time: the spans' bounds
    cut time into segments, each labelled once."""

    def __init__(self, spans):
        spans = sorted(spans)
        self.starts, self.names = [], []
        active, i = [], 0
        for b in sorted({t for s, e, _ in spans for t in (s, e)}):
            while i < len(spans) and spans[i][0] <= b:
                s, e, name = spans[i]
                heapq.heappush(active, (e - s, e, name))
                i += 1
            while active and active[0][1] <= b:
                heapq.heappop(active)
            self.starts.append(b)
            self.names.append(active[0][2] if active else None)

    def __call__(self, t: float) -> str:
        i = bisect_right(self.starts, t) - 1
        name = self.names[i] if i >= 0 else None
        return name or "host:other"


def short_name(op: str) -> str:
    """``%fusion.3 = f32[...] fusion(...)`` -> ``fusion.3``."""
    return op.split(" = ", 1)[0].lstrip("%")


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and "CPU" not in name


def reduce_profile(profile) -> Reduction:
    """Reduce a ``jax.profiler.ProfileData``."""
    window, spans, devices = None, [], []
    for plane in profile.planes:
        if is_device_plane(plane.name):
            lines = list(plane.lines)
            ops = [ln for ln in lines if ln.name == OPS_LINE]
            events = [(ev.start_ns, ev.end_ns, short_name(ev.name))
                      for ln in ops for ev in ln.events]
            if events:
                devices.append(events)
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                python = ln.name.startswith("python")
                for ev in ln.events:
                    if ev.name == WINDOW_SPAN:
                        window = (ev.start_ns, ev.end_ns)
                    elif python or ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.start_ns, ev.end_ns, ev.name))
    if window is None:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} host span")
    if not devices:
        raise ValueError("the trace holds no device operation")
    lo, hi = window
    label = Labels(spans)
    busy_total, op_s, gap_s = 0.0, defaultdict(float), defaultdict(float)
    for events in devices:
        inside = clip([(s, e) for s, e, _ in events], lo, hi)
        busy = union(inside)
        busy_total += sum(e - s for s, e in busy)
        for s, e, name in events:
            s2, e2 = max(s, lo), min(e, hi)
            if e2 > s2:
                op_s[name] += (e2 - s2) * 1e-9 / len(devices)
        for s, e in complement(busy, lo, hi):
            gap_s[label((s + e) / 2)] += (e - s) * 1e-9 / len(devices)
    return Reduction(window_s=(hi - lo) * 1e-9,
                     busy_s=busy_total * 1e-9 / len(devices),
                     devices=len(devices), op_s=dict(op_s),
                     gaps=dict(gap_s))


def reduce_dir(trace_dir: str) -> Reduction:
    """Reduce the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return reduce_profile(ProfileData.from_file(max(files,
                                                    key=os.path.getmtime)))
