"""Client: the 95th percentile of every request's time from its
scheduled arrival to its decision at the caller, beside the end-to-end
median.  A stall of the host for a few seconds moves it far, so it is
read here and not bounded."""
from benchlib import harness


def read(ctx):
    if not ctx.run.records:
        return None
    return harness.outcomes(ctx.system, ctx.run)["decide_p95_ms"]
