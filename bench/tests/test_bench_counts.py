"""The peak table and the shape-derived operation and byte counts of
the decision (a backend's decode counts: ``test_bench_backends``)."""
import pathlib
import sys

import pytest

_BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(_BENCH), str(_BENCH.parent / "src")]

from benchlib import counts, inputs, peaks  # noqa: E402

ANALYZER = {"vocab_size": 4096, "d_model": 128, "n_layers": 2, "n_heads": 4,
            "d_ff": 256, "max_len": 96}


def test_v5e_peaks_and_unknown_device():
    p = peaks.peak_for("TPU v5 lite")
    assert (p.bf16_flops, p.int8_ops, p.hbm_bytes_per_s) == (
        197e12, 393e12, 819e9)
    assert "TPU v5e" in p.source
    with pytest.raises(peaks.UnknownDevice):
        peaks.peak_for("TPU v4")
    with pytest.raises(peaks.UnknownDevice):
        peaks.peak_for("cpu")


def test_least_time_is_the_larger_bound():
    p = peaks.peak_for("TPU v5 lite")
    assert peaks.least_time_s(197e12, 0, p) == pytest.approx(1.0)
    assert peaks.least_time_s(0, 819e9, p) == pytest.approx(1.0)
    assert peaks.least_time_s(197e12, 2 * 819e9, p) == pytest.approx(2.0)


def test_decision_counts_read_the_catalog_once():
    n_tt, n_dm = len(inputs.TASK_TYPES), len(inputs.DOMAINS)
    c1 = counts.decision(ANALYZER, n_tt, n_dm, 256, 1_000_000, 8, 8, 1)
    c2 = counts.decision(ANALYZER, n_tt, n_dm, 256, 1_000_000, 8, 8, 67)
    assert c2.nbytes - c1.nbytes == 66 * 1_000_000
    catalog = 4 * 1_000_000 * 8
    assert catalog < c1.nbytes < catalog + 8e6
    # no B x N score matrix is counted
    assert c1.nbytes < 256 * 1_000_000
    assert c1.flops > 2 * 256 * 1_000_000 * 8
