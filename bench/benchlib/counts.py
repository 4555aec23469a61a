"""Operations and bytes that a step needs, from its shapes.

The counting rule: count the work the configuration needs at its
declared dtypes, not what today's code happens to move.  A served
backend's decode step is counted by its module's ``decode_step``
(``bench/backends/<architecture>.py``).

* The decision reads the analyzer's weights once (fp32), the fp32 unit
  rows of the catalog once, each distinct filter row it uses once
  (one byte per entry), gathers the raw metric rows of its candidates,
  and writes B x k results.  It does not write a B x N score matrix.
"""
from __future__ import annotations

from dataclasses import dataclass

BF16 = 2
F32 = 4
I32 = 4


@dataclass(frozen=True)
class Counts:
    flops: float
    nbytes: float

    def __add__(self, other: "Counts") -> "Counts":
        return Counts(self.flops + other.flops, self.nbytes + other.nbytes)


# ----------------------------------------------------------------------
# decision: analyzer encoder + masked cosine kNN + blend at candidates
# ----------------------------------------------------------------------

def analyzer_params(a: dict, n_tt: int, n_dm: int) -> int:
    d, f = a["d_model"], a["d_ff"]
    per = 4 * d * d + 2 * d * f + 2 * d
    return (a["vocab_size"] * d + a["max_len"] * d + a["n_layers"] * per
            + d + d * (n_tt + n_dm + 1))


def analyzer_flops(a: dict, n_tt: int, n_dm: int, batch: int) -> float:
    d, f, L = a["d_model"], a["d_ff"], a["max_len"]
    per_layer = 2.0 * L * (4 * d * d + 2 * d * f) + 4.0 * L * L * d
    return batch * (a["n_layers"] * per_layer
                    + 2.0 * d * (n_tt + n_dm + 1))


def decision(a: dict, n_tt: int, n_dm: int, batch: int, n_entries: int,
             n_metrics: int, k: int, distinct_filter_rows: int) -> Counts:
    """One fused decision dispatch over ``batch`` queries."""
    flops = (analyzer_flops(a, n_tt, n_dm, batch)
             + 2.0 * batch * n_entries * n_metrics
             + 2.0 * batch * k * n_metrics)
    nbytes = (F32 * analyzer_params(a, n_tt, n_dm)
              + I32 * batch * a["max_len"]
              + F32 * batch * n_metrics
              + F32 * n_entries * n_metrics
              + 1.0 * distinct_filter_rows * n_entries
              + F32 * batch * k * n_metrics
              + batch * k * (I32 + F32))
    return Counts(flops, nbytes)
