"""Whole decode step: the operations the traced decode steps need
(the backend's ``decode_step``: every matmul weight and the attention
over the cached positions, per sequence) over their device time, as a
share of the chip's bf16 peak, in percent."""
from benchlib import backend, readers


def read(ctx):
    runs = readers.generate_runs(ctx)
    if not runs:
        return None
    m = ctx.system.backend
    step = backend.of(m).decode_step
    flops = measured = 0.0
    for args, _, _, dec in runs:
        batch, prompt = args[0].shape
        flops += sum(step(m, batch, prompt + j + 1).flops
                     for j in range(len(dec)))
        measured += sum(e.dur for e in dec) / 1e9
    return 100.0 * flops / measured / ctx.peak.bf16_flops
