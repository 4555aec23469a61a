"""Device: share of the traced window in which no operation ran."""
from benchlib import readers


def read(ctx):
    return readers.idle_pct(ctx.reduced)
