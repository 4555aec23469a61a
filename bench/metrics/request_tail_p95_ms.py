"""Client: the 95th percentile of each request's time from its
scheduled arrival to its whole answer at the caller, over the requests
answered by the on-chip backend and the failed ones, beside the
end-to-end median.  A window holds too few such requests for a bounded
95th percentile, so it is read here and not bounded."""
from benchlib import harness


def read(ctx):
    res = harness.outcomes(ctx.system, ctx.run)
    if not res["on_chip_requests"]:
        return None
    return res["request_p95_ms"]
