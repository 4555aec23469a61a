"""Serving front: host time per ``ServingEngine.submit`` spent finding
the routed models' catalog entries (``MRES.entry``): the
``repro.catalog_lookup`` spans inside each ``repro.submit``, summed,
averaged over the submits."""
from benchlib import program_spans


def read(ctx):
    red = ctx.reduced
    return program_spans.inner_ms(program_spans.named(red, "submit"),
                                  program_spans.named(red, "catalog_lookup"))
