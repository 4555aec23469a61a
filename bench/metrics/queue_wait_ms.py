"""Serving front (intake): mean time a request waited in the
``MicroBatcher`` queue, from ``offer`` to the start of its window's
service: the ``repro.window`` spans' summed ``wait_ms_sum`` over their
summed ``size``."""
from benchlib import program_spans


def read(ctx):
    windows = program_spans.named(ctx.reduced, "window")
    n = sum(w.stats.get("size", 0) for w in windows)
    if n == 0:
        return None
    return sum(w.stats["wait_ms_sum"] for w in windows) / n
