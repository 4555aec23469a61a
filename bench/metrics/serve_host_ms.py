"""Serving front: host wall time per ``ServingEngine.submit`` window,
less the ``OptiRoute.route_all`` call inside it."""
from benchlib import readers


def read(ctx):
    red = ctx.reduced
    return readers.self_time_ms(red.spans_named("serve_submit"),
                                red.spans_named("route_all"))
