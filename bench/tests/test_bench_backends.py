"""The dense backend module (``bench/backends/dense.py``): its counts
against the program's own, and its weights, reference logits and
decode counts against values fixed at a tiny size."""
import hashlib
import pathlib
import sys

import jax
import numpy as np
import pytest

_BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(_BENCH), str(_BENCH.parent / "src")]

from benchlib import backend, peaks  # noqa: E402

dense = backend.load("dense")

QWEN2 = {"hidden_size": 1536, "num_hidden_layers": 28,
         "num_attention_heads": 12, "num_key_value_heads": 2,
         "intermediate_size": 8960, "vocab_size": 151936, "qkv_bias": True}
TINY = {"model": "qwen2-1.5b", "architecture": "dense",
        "source": "arXiv:2407.10671", "hidden_size": 64,
        "num_hidden_layers": 2, "num_attention_heads": 4,
        "num_key_value_heads": 2, "intermediate_size": 128,
        "vocab_size": 512, "qkv_bias": True, "rope_theta": 1000000.0,
        "param_dtype": "float32", "compute_dtype": "bfloat16"}
SEED = 3_000_000_001
TOKENS = (np.arange(24) * 37 % 511 + 1).astype(np.int32)

# sha256 (first 16 hex digits) of each weight's bytes, of the reference's
# logits and of the control's, and the decode counts (flops, bytes), as
# the dense code gave them before it moved into its backend module
WEIGHTS = {"bk": "a808936fcc325566", "bq": "dff72cb296727e15",
           "bv": "1a8cefa645fb57dc", "embed": "645196b8a0cd4510",
           "ln_f": "5341e6b2646979a7", "ln_mix": "076a27c79e5ace2a",
           "ln_mlp": "076a27c79e5ace2a", "wg": "0547f5ba86e3d248",
           "wi": "749e006a69c69f8e", "wk": "71afdcf6d7e341d7",
           "wo": "30f94c04892952a6", "wo_mlp": "5fedcac6e85241a8",
           "wq": "798acc2b26649763", "wv": "5e19e7e18e517472"}
LOGITS = {False: "e5c145ac2481b1a4", True: "c67b423a3efd2d18"}
COUNTS = {(1, 1): (214528.0, 214664), (3, 40): (703488.0, 245656),
          (12, 1544): (12054528.0, 4960480)}
QWEN2_COUNTS = {(1, 1): (3087597568.0, 3087485960),
                (12, 1544): (40236515328.0, 3619007584)}


def _sha(a) -> str:
    return hashlib.sha256(np.asarray(a).tobytes()).hexdigest()[:16]


@pytest.fixture(scope="module")
def tiny_weights():
    mc = dense.program_config(TINY)
    assert mc.vocab_padded == 512
    return dense.make_weights(TINY, SEED, mc.vocab_padded)


def test_weights_are_as_before(tiny_weights):
    assert {k: _sha(v) for k, v in tiny_weights.items()} == WEIGHTS


@pytest.mark.parametrize("control", [False, True],
                         ids=["reference", "control"])
def test_logits_are_as_before(tiny_weights, control):
    lg = np.asarray(dense.logits(tiny_weights, TINY, TOKENS,
                                 control=control))
    assert lg.shape == (24, 512) and lg.dtype == np.float32
    assert _sha(lg) == LOGITS[control]


def test_decode_counts_are_as_before():
    for (b, a), (flops, nbytes) in COUNTS.items():
        c = dense.decode_step(TINY, b, a)
        assert (c.flops, c.nbytes) == (flops, nbytes)
    for (b, a), (flops, nbytes) in QWEN2_COUNTS.items():
        c = dense.decode_step(dict(TINY, **QWEN2), b, a)
        assert (c.flops, c.nbytes) == (flops, nbytes)


def test_program_params_share_the_weights(tiny_weights):
    pp = dense.program_params(tiny_weights)
    leaves = jax.tree_util.tree_leaves(pp)
    assert len(leaves) == len(tiny_weights)
    assert {id(x) for x in leaves} == {id(x) for x in tiny_weights.values()}
    assert pp["layers"]["mlp"]["wo"] is tiny_weights["wo_mlp"]


def test_bf16_weights_are_the_float32_draws_rounded(tiny_weights):
    mc = dense.program_config(TINY)
    w16 = dense.make_weights(dict(TINY, param_dtype="bfloat16"), SEED,
                             mc.vocab_padded)
    for k, v in tiny_weights.items():
        assert w16[k].dtype == jax.numpy.bfloat16, k
        np.testing.assert_array_equal(
            np.asarray(w16[k]), np.asarray(v.astype(jax.numpy.bfloat16)))


def test_decoder_params_match_the_program_config():
    """The benchmark's count of qwen2-1.5b's weights agrees with the
    program's own, less the padded rows of its embedding."""
    from repro.configs import get_config
    cfg = get_config("qwen2-1.5b")
    prog = cfg.n_params()
    ours = dense.params(QWEN2)
    pad = (cfg.vocab_padded - cfg.vocab_size) * cfg.d_model
    assert ours in (prog, prog - pad)
    assert 1.50e9 < ours < 1.56e9


def test_decode_step_reads_bf16_weights_and_the_cache():
    one = dense.decode_step(QWEN2, batch=1, attended=1)
    w = 2 * dense.params(QWEN2)
    assert one.nbytes == pytest.approx(w, rel=1e-3)
    b8 = dense.decode_step(QWEN2, batch=8, attended=640)
    kv = 2 * 28 * 8 * 640 * 2 * 256
    assert b8.nbytes - w == pytest.approx(kv, rel=0.01)
    assert b8.flops == pytest.approx(
        8 * 2 * dense.matmul_params(QWEN2) + 4 * 8 * 28 * 1536 * 640)
    # memory-bound at serving batch sizes: ~3.8 ms on a v5e
    p = peaks.peak_for("TPU v5 lite")
    assert 3.5e-3 < peaks.least_time_s(b8.flops, b8.nbytes, p) < 4.5e-3


def test_unknown_architecture_names_the_path():
    with pytest.raises(backend.UnknownArchitecture) as e:
        backend.load("no-such-architecture")
    assert str(backend.DIR / "no-such-architecture.py") in str(e.value)
