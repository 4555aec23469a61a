"""The program's own spans in a profiler trace, and what they read.

The program opens the span of each serving stage as a
``jax.profiler.TraceAnnotation`` named ``repro.<stage>``
(``repro.obs.trace.span``), on the line of the host thread that ran the
stage, on the clock of the device's programs and operations.  The
``window`` span also carries stats: ``size``, ``wait_ms_sum``,
``wait_ms_max`` and ``backlog``.

``trace.reduce_profile`` keeps only the benchmark's ``bench.*`` spans.
``reduce`` keeps the program's, each with its thread and its stats, and
``load`` reduces a trace directory as ``trace.load`` does and hangs them
on the result as ``program_spans``.  A trace, or a reduction, without
them reads nothing: every reading below is then ``None``.
"""
from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

from benchlib import trace
from benchlib.trace import Event, Reduced, _union

PREFIX = "repro."


@dataclass
class Span(Event):
    thread: int = 0           # the host line (one per thread) it sat on
    stats: Dict[str, Any] = field(default_factory=dict)


def reduce(pd) -> List[Span]:
    """The ``repro.*`` host events of a ``jax.profiler.ProfileData``."""
    out: List[Span] = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(PREFIX):
                    out.append(Span(e.name, e.start_ns, e.duration_ns,
                                    i, dict(e.stats)))
    out.sort(key=lambda s: s.start)
    return out


def load(trace_dir: str) -> Reduced:
    """``trace.load`` that keeps the program's spans as well."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(max(files, key=os.path.getmtime))
    red = trace.reduce_profile(pd)
    red.program_spans = reduce(pd)
    return red


def named(red: Reduced, name: str) -> List[Span]:
    return [s for s in getattr(red, "program_spans", ())
            if s.name == PREFIX + name]


def inner_ms(outer: List[Span], inner: List[Span]) -> Optional[float]:
    """Mean over ``outer`` of the summed time of the ``inner`` spans
    that lie inside each, on its thread, in ms."""
    if not outer:
        return None
    tot = sum(i.dur for o in outer for i in inner
              if i.thread == o.thread and o.start <= i.start
              and i.end <= o.end)
    return tot / len(outer) / 1e6


def _overlap_ns(a: List[List[float]], b: List[List[float]]) -> float:
    """Length of the intersection of two sorted disjoint unions."""
    tot, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        tot += max(hi - lo, 0.0)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def idle_while_open_pct(red: Reduced, spans: List[Span]
                        ) -> Optional[float]:
    """Share of the traced window, in percent, in which ``spans`` were
    open and the device ran no operation, averaged over devices."""
    w = red.window_ns()
    if not spans or not red.devices or w <= 0:
        return None
    opened = _union([(s.start, s.end) for s in spans])
    open_ns = sum(e - s for s, e in opened)
    idle = 0.0
    for dev in red.devices.values():
        busy = _union([(o.start, o.end) for _, o in dev.ops])
        idle += open_ns - _overlap_ns(opened, busy)
    return 100.0 * idle / len(red.devices) / w
