"""Kernel: least time of the decode steps (the backend's
``decode_step``: its weights in bf16 plus the keys and values attended)
over their device time, in percent, summed over every traced
``generate`` call."""
from benchlib import readers


def read(ctx):
    least = measured = 0.0
    for args, _, _, dec in readers.generate_runs(ctx):
        batch, prompt = args[0].shape
        least += readers.decode_least_s(ctx, batch, prompt, len(dec))
        measured += sum(m.dur for m in dec) / 1e9
    if measured <= 0:
        return None
    return 100.0 * least / measured
