"""Reduce a profiler trace to what the per-layer metrics read.

A trace is the ``*.xplane.pb`` that ``jax.profiler`` writes.  On a TPU
its device planes are named ``/device:TPU:<n>`` with the lines ``XLA
Modules`` (one event per program execution, named ``<jit name>(<id>)``)
and ``XLA Ops`` (one event per operation); the host plane
``/host:CPU`` holds one line per thread, and the benchmark's own
``jax.profiler.TraceAnnotation`` spans (named ``bench.*``) sit on the
line of the thread that made the call.  All times are nanoseconds on
the trace's clock; device and host share it to within about a
millisecond.

``reduce`` keeps, per device: the programs run (stable name without the
id, start, duration), the operations (their program's name, a stable
op kind, start, duration), the busy time (the union of the operation
intervals) and the idle gaps between them; and every ``bench.*`` host
span.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

SPAN_PREFIX = "bench."
_ID_RE = re.compile(r"\(\d+\)$")
_OP_RE = re.compile(r"^%?([A-Za-z_\-]+?)(?:[.\d]*)(?:\s*=.*)?$")


@dataclass
class Event:
    name: str
    start: float          # ns
    dur: float            # ns

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclass
class Device:
    modules: List[Event] = field(default_factory=list)
    ops: List[Tuple[str, Event]] = field(default_factory=list)  # (module, op)
    busy_ns: float = 0.0
    gaps: List[Event] = field(default_factory=list)


@dataclass
class Reduced:
    devices: Dict[str, Device]
    spans: List[Event]
    t_first: float
    t_last: float
    window: float = 0.0       # ns the trace was open, when known

    def window_ns(self) -> float:
        return self.window or (self.t_last - self.t_first)

    def busy_s(self) -> float:
        """Busy seconds averaged over the devices."""
        if not self.devices:
            return 0.0
        return sum(d.busy_ns for d in self.devices.values()) \
            / len(self.devices) / 1e9

    def modules(self) -> List[Event]:
        out = [m for d in self.devices.values() for m in d.modules]
        return sorted(out, key=lambda e: e.start)

    def spans_named(self, name: str) -> List[Event]:
        return [s for s in self.spans if s.name == SPAN_PREFIX + name]


def module_name(raw: str) -> str:
    """``jit_serve_step(1234)`` -> ``jit_serve_step``."""
    return _ID_RE.sub("", raw.strip())


def op_kind(raw: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion``."""
    head = raw.split(" = ", 1)[0].strip()
    m = _OP_RE.match(head)
    return m.group(1) if m else head


def _union(intervals: List[Tuple[float, float]]):
    """Merged (start, end) intervals, sorted."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def reduce_profile(pd) -> Reduced:
    """Reduce a ``jax.profiler.ProfileData``."""
    devices: Dict[str, Device] = {}
    spans: List[Event] = []
    t_first, t_last = float("inf"), float("-inf")
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = Device()
            for line in plane.lines:
                if line.name == "XLA Modules":
                    for e in line.events:
                        dev.modules.append(Event(module_name(e.name),
                                                 e.start_ns, e.duration_ns))
                elif line.name == "XLA Ops":
                    for e in line.events:
                        dev.ops.append(("", Event(op_kind(e.name),
                                                  e.start_ns, e.duration_ns)))
            dev.modules.sort(key=lambda e: e.start)
            dev.ops.sort(key=lambda p: p[1].start)
            _attach_modules(dev)
            merged = _union([(o.start, o.end) for _, o in dev.ops])
            dev.busy_ns = sum(e - s for s, e in merged)
            dev.gaps = [Event("", merged[i][1], merged[i + 1][0]
                              - merged[i][1])
                        for i in range(len(merged) - 1)]
            if merged:
                t_first = min(t_first, merged[0][0])
                t_last = max(t_last, merged[-1][1])
            devices[plane.name] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append(Event(e.name, e.start_ns,
                                           e.duration_ns))
    spans.sort(key=lambda e: e.start)
    return Reduced(devices, spans, t_first, t_last)


def _attach_modules(dev: Device) -> None:
    """Name each operation after the program whose execution covers it."""
    mods = dev.modules
    j = 0
    out = []
    for _, op in dev.ops:
        while j < len(mods) and mods[j].end < op.start:
            j += 1
        name = mods[j].name if j < len(mods) and mods[j].start <= op.start \
            else ""
        out.append((name, op))
    dev.ops = out


def load(trace_dir: str) -> Reduced:
    """Reduce the newest ``*.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no xplane.pb under {trace_dir}")
    return reduce_profile(ProfileData.from_file(max(files,
                                                    key=os.path.getmtime)))


def inside(ev: Event, span: Event, slack_ns: float = 0.0) -> bool:
    return span.start - slack_ns <= ev.start <= span.end + slack_ns


def innermost_span(spans: List[Event], t: float) -> str:
    best = None
    for s in spans:
        if s.start <= t <= s.end and (best is None or s.dur < best.dur):
            best = s
    return best.name[len(SPAN_PREFIX):] if best else "no bench span"


def breakdown(red: Reduced, top: int = 10) -> dict:
    """Device operations that took most time (as ``program:op kind``)
    and the longest idle gaps, named by the benchmark span they fall
    in."""
    tot: Dict[str, float] = {}
    for d in red.devices.values():
        for mod, op in d.ops:
            key = f"{mod}:{op.name}" if mod else op.name
            tot[key] = tot.get(key, 0.0) + op.dur
    n_dev = max(len(red.devices), 1)
    ops = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted((g for d in red.devices.values() for g in d.gaps),
                  key=lambda g: -g.dur)[:top]
    return {"device_ops": [[k, v / n_dev / 1e9] for k, v in ops],
            "idle_gaps": [[innermost_span(red.spans, g.start + g.dur / 2),
                           g.dur / 1e9] for g in gaps]}
