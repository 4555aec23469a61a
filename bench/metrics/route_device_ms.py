"""Device program: device time of one fused analyze->route dispatch."""
from benchlib import readers


def read(ctx):
    return readers.mean_ms(readers.programs(ctx.reduced,
                                            readers.ROUTE_PROGRAM))
