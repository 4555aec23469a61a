"""Whole decode step: the operations the traced decode steps need
(``counts.decode_step``: every matmul weight and the attention over the
cached positions, per sequence) over their device time, as a share of
the chip's bf16 peak, in percent."""
from benchlib import counts, readers


def read(ctx):
    m = ctx.system.backend
    flops = measured = 0.0
    for args, _, _, dec in readers.generate_runs(ctx):
        batch, prompt = args[0].shape
        flops += sum(counts.decode_step(m, batch, prompt + j + 1).flops
                     for j in range(len(dec)))
        measured += sum(e.dur for e in dec) / 1e9
    if measured <= 0:
        return None
    return 100.0 * flops / measured / ctx.peak.bf16_flops
