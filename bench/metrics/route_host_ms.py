"""Router host path: host wall time per ``OptiRoute.route_all``, less
the device time of the fused decision program it dispatched."""
from benchlib import readers


def read(ctx):
    red = ctx.reduced
    return readers.self_time_ms(red.spans_named("route_all"),
                                readers.programs(red, readers.ROUTE_PROGRAM),
                                readers.SLACK_NS)
