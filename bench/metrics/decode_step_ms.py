"""Backend: device time of one decode-step program (``serve_step``)."""
from benchlib import readers


def read(ctx):
    return readers.mean_ms(readers.programs(ctx.reduced,
                                            readers.DECODE_PROGRAM))
