"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports.

Source: Google Cloud documentation, "TPU v5e" (Cloud TPU system
architecture): per chip 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM
at 819 GB/s.  A device that is not in the table is an error, never a
default.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Peak:
    bf16_flops: float        # FLOP/s
    int8_ops: float          # OP/s
    hbm_bytes_per_s: float   # bytes/s
    hbm_bytes: float         # bytes
    source: str


_V5E = Peak(bf16_flops=197e12, int8_ops=393e12, hbm_bytes_per_s=819e9,
            hbm_bytes=16e9, source='Google Cloud documentation, "TPU v5e"')

PEAKS = {
    "TPU v5 lite": _V5E,
    "TPU v5e": _V5E,
}


class UnknownDevice(LookupError):
    pass


def peak_for(device_kind: str) -> Peak:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"device kind {device_kind!r} has no entry in the peak table "
            f"({sorted(PEAKS)})") from None


def least_time_s(flops: float, nbytes: float, peak: Peak) -> float:
    """The roofline's least time: the larger of the compute and the
    memory bound (bf16 compute peak)."""
    return max(flops / peak.bf16_flops, nbytes / peak.hbm_bytes_per_s)
