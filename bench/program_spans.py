"""The program's own host spans in a profiler trace, and the device's idle
time under them.

The program marks the phases of its streaming pipeline and checkpoint
writer with ``jax.profiler.TraceAnnotation("repro.<layer>.<phase>",
chunk=<index>, ...)`` (``repro.ingest.chunk``, ``repro.stream.stage``,
``repro.stream.wait``, ``repro.stream.checkpoint``, ``repro.ckpt.wait``,
``repro.ckpt.fetch``, ``repro.ckpt.write``).  This module collects them
from every host line of the trace, on the worker threads too, clipped to
the benchmark's ``bench.window`` span; a span without a ``chunk`` stat is
left out.  A program that has no such span gives no reading (None).

The per-layer metrics of the replay cell read them through
:func:`of_run`, from the trace of the run in progress: ``bench/run.py``
writes it under ``.bench_runs/<cell>.<pid>/trace``.
"""
from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field

from bench.harness import ROOT
from bench.trace_reduce import (OPS_LINE, WINDOW_SPAN, clip, complement,
                                is_device_plane, union)

PREFIX = "repro."
RUNS_DIR = os.path.join(ROOT, ".bench_runs")


@dataclass
class Span:
    name: str
    start: int        # ns, clipped to the window
    end: int
    line: str         # "<host plane>/<thread line>"
    chunk: int


@dataclass
class Spans:
    window: tuple                                # (start, end) ns
    spans: list = field(default_factory=list)    # Span
    busy: list = field(default_factory=list)     # per device: union, ns

    def span_ms_per_chunk(self, names) -> float | None:
        """The clipped time of the spans named ``names``, in ms per chunk:
        for each name, its time over the number of distinct ``chunk``
        values among its spans, summed over the names.  None where no
        span has any of the names."""
        total, seen = 0.0, False
        for name in names:
            mine = [s for s in self.spans if s.name == name]
            if mine:
                seen = True
                total += sum(s.end - s.start for s in mine) * 1e-6 \
                    / len({s.chunk for s in mine})
        return total if seen else None

    def idle_pct_under(self, name: str) -> float | None:
        """100 x the device-idle time inside the union of ``name``'s
        spans, over the window, averaged over devices as the idle share
        is.  None where there is no such span or no device."""
        under = union([(s.start, s.end) for s in self.spans
                       if s.name == name])
        if not under or not self.busy:
            return None
        lo, hi = self.window
        idle = 0
        for busy in self.busy:
            for s, e in under:
                idle += sum(b - a for a, b in
                            complement(clip(busy, s, e), s, e))
        return 100.0 * idle / len(self.busy) / (hi - lo)


def collect(profile) -> Spans:
    """The ``repro.*`` spans of a ``jax.profiler.ProfileData`` and the
    device busy intervals, both clipped to the ``bench.window`` span."""
    window, raw, devices = None, [], []
    for plane in profile.planes:
        if is_device_plane(plane.name):
            events = [(ev.start_ns, ev.end_ns) for ln in plane.lines
                      if ln.name == OPS_LINE for ev in ln.events]
            if events:
                devices.append(events)
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name == WINDOW_SPAN:
                        window = (ev.start_ns, ev.end_ns)
                    elif ev.name.startswith(PREFIX):
                        chunk = dict(ev.stats).get("chunk")
                        if chunk is not None:
                            raw.append(Span(ev.name, ev.start_ns, ev.end_ns,
                                            f"{plane.name}/{ln.name}",
                                            int(chunk)))
    if window is None:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} host span")
    lo, hi = window
    spans = [Span(s.name, max(s.start, lo), min(s.end, hi), s.line, s.chunk)
             for s in raw if s.end > lo and s.start < hi]
    return Spans(window, spans,
                 [union(clip(events, lo, hi)) for events in devices])


_CACHE: dict = {}


def of_run(run) -> Spans | None:
    """The spans of the traced run in progress in this process; None when
    the run was not traced or its trace cannot be found.  Parsed once for
    all the readers of one run."""
    if run.reduction is None:
        return None
    files = glob.glob(os.path.join(RUNS_DIR, f"*.{os.getpid()}", "trace",
                                   "**", "*.xplane.pb"), recursive=True)
    if not files:
        return None
    path = max(files, key=os.path.getmtime)
    key = (path, os.path.getmtime(path))
    if key not in _CACHE:
        from jax.profiler import ProfileData
        _CACHE.clear()
        _CACHE[key] = collect(ProfileData.from_file(path))
    return _CACHE[key]
