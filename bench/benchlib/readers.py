"""Helpers the per-layer metric readers share.

Program names as the device trace reports them: the fused decision
program is ``jit_analyze_route_step_jit``; the backend's decode step is
``jit_serve_step`` (``make_decode_step``'s function, jitted by the
runner).  A reader that finds nothing to read returns ``None``.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

from benchlib import backend
from benchlib.trace import Event, Reduced

ROUTE_PROGRAM = "analyze_route_step"
DECODE_PROGRAM = "serve_step"
SLACK_NS = 2e6          # host and device clocks agree to about a ms


def programs(red: Reduced, needle: str) -> List[Event]:
    return [m for m in red.modules() if needle in m.name]


def mean_ms(events: List[Event]) -> Optional[float]:
    if not events:
        return None
    return sum(e.dur for e in events) / len(events) / 1e6


def self_time_ms(spans: List[Event], inner: List[Event],
                 slack_ns: float = 0.0) -> Optional[float]:
    """Mean over ``spans`` of the span minus the ``inner`` events that
    start inside it; ``slack_ns`` widens the span where ``inner`` is on
    the device's clock."""
    if not spans:
        return None
    tot = 0.0
    j = 0
    inner = sorted(inner, key=lambda e: e.start)
    for s in spans:
        sub = 0.0
        while j < len(inner) and inner[j].start < s.start - slack_ns:
            j += 1
        k = j
        while k < len(inner) and inner[k].start <= s.end + slack_ns:
            sub += inner[k].dur
            k += 1
        tot += s.dur - sub
    return tot / len(spans) / 1e6


def idle_pct(red: Reduced) -> Optional[float]:
    w = red.window_ns()
    if not red.devices or w <= 0:
        return None
    return 100.0 * (1.0 - red.busy_s() * 1e9 / w)


def generate_runs(ctx) -> List[Tuple[tuple, Event, List[Event], List[Event]]]:
    """Each traced ``generate`` call with its arguments, its span, the
    device programs before its first decode step (the prefill) and its
    decode-step programs."""
    red = ctx.reduced
    spans = red.spans_named("generate")
    mods = red.modules()
    out = []
    # the trace opens before the window's first call and closes at the
    # window's end, so the traced spans are the first calls in order
    for args, s in zip(ctx.generate_calls, spans):
        mine = [m for m in mods if s.start - SLACK_NS <= m.start
                <= s.end + SLACK_NS and ROUTE_PROGRAM not in m.name]
        dec = [m for m in mine if DECODE_PROGRAM in m.name]
        if not dec:
            continue
        pre = [m for m in mine if m.start < dec[0].start
               and DECODE_PROGRAM not in m.name]
        out.append((args, s, pre, dec))
    return out


def decode_least_s(ctx, batch: int, prompt: int, steps: int) -> float:
    """Least time of ``steps`` decode steps after a ``prompt``-token
    prefill, by the roofline of the chip."""
    m = ctx.system.backend
    step = backend.of(m).decode_step
    tot = 0.0
    for j in range(steps):
        c = step(m, batch, prompt + j + 1)
        tot += max(c.flops / ctx.peak.bf16_flops,
                   c.nbytes / ctx.peak.hbm_bytes_per_s)
    return tot
