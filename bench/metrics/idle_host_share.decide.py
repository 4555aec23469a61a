"""Device: share of the traced window in which no operation ran while
the host served a window (a ``repro.window`` span was open), averaged
over devices.  The rest of ``idle_share.decide`` is the front waiting
for requests."""
from benchlib import program_spans


def read(ctx):
    red = ctx.reduced
    return program_spans.idle_while_open_pct(
        red, program_spans.named(red, "window"))
