"""The peak table and the shape-derived operation and byte counts."""
import pathlib
import sys

import pytest

_BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(_BENCH), str(_BENCH.parent / "src")]

from benchlib import counts, inputs, peaks  # noqa: E402

QWEN2 = {"hidden_size": 1536, "num_hidden_layers": 28,
         "num_attention_heads": 12, "num_key_value_heads": 2,
         "intermediate_size": 8960, "vocab_size": 151936, "qkv_bias": True}
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


def test_decoder_params_match_the_program_config():
    """The benchmark's count of qwen2-1.5b's weights agrees with the
    program's own, less the padded rows of its embedding."""
    from repro.configs import get_config
    cfg = get_config("qwen2-1.5b")
    prog = cfg.n_params()
    ours = counts.decoder_params(QWEN2)
    pad = (cfg.vocab_padded - cfg.vocab_size) * cfg.d_model
    assert ours in (prog, prog - pad)
    assert 1.50e9 < ours < 1.56e9


def test_decode_step_reads_bf16_weights_and_the_cache():
    one = counts.decode_step(QWEN2, batch=1, attended=1)
    w = 2 * counts.decoder_params(QWEN2)
    assert one.nbytes == pytest.approx(w, rel=1e-3)
    b8 = counts.decode_step(QWEN2, batch=8, attended=640)
    kv = 2 * 28 * 8 * 640 * 2 * 256
    assert b8.nbytes - w == pytest.approx(kv, rel=0.01)
    assert b8.flops == pytest.approx(
        8 * counts.decode_flops_per_token(QWEN2)
        + 4 * 8 * 28 * 1536 * 640)
    # memory-bound at serving batch sizes: ~3.8 ms on a v5e
    p = peaks.peak_for("TPU v5 lite")
    assert 3.5e-3 < peaks.least_time_s(b8.flops, b8.nbytes, p) < 4.5e-3


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
