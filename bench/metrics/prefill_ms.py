"""Backend: device time of a ``generate`` call before its first decode
step (the prefill and what leads to it)."""
from benchlib import readers


def read(ctx):
    runs = readers.generate_runs(ctx)
    if not runs:
        return None
    return sum(sum(m.dur for m in pre) for _, _, pre, _ in runs) \
        / len(runs) / 1e6
