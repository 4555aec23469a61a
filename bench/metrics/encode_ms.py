"""Router host path: host time per ``OptiRoute.route_all`` spent
pruning and tokenizing the queries: its ``repro.analyze`` span,
averaged over the ``repro.route_all`` spans."""
from benchlib import program_spans


def read(ctx):
    red = ctx.reduced
    return program_spans.inner_ms(program_spans.named(red, "route_all"),
                                  program_spans.named(red, "analyze"))
