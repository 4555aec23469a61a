"""Backend ``dense``: a causal decoder with GQA, QKV bias, half-split
RoPE, RMSNorm with a ``1 + w`` scale, SwiGLU and a tied output head
(qwen2, arXiv:2407.10671).

Every backend module under ``bench/backends/`` is named by the
``backend.architecture`` of a configuration and gives:

* ``program_config(b)``: the program's ``ModelConfig`` for the
  configuration's ``backend`` group ``b``;
* ``make_weights(b, seed, vocab_rows)``: random weights for ``seed``,
  made on the device in one jitted call in ``b["param_dtype"]``;
  ``vocab_rows`` is the embedding's row count as the program lays it
  out (the vocabulary padded up);
* ``program_params(w)``: those weights as the program's parameter tree,
  without a copy;
* ``logits(w, b, tokens, *, control=False)``: the plain reference's
  logits ``(len(tokens), vocab)`` in float32 over one sequence.  It
  computes in float32 at ``highest`` on the served weights' values;
  ``control`` computes one step below the bf16 compute the
  configuration states (fp8 weights with a scale per output column,
  bf16 activations);
* ``decode_step(b, batch, attended)``: the operations and bytes
  (``counts.Counts``) of one greedy decode step of ``batch`` sequences,
  each attending to ``attended`` positions.

Nothing here imports the program, except ``program_config``, which
builds the program's own configuration object.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from benchlib.counts import BF16, I32, Counts
from benchlib.reference import NEG, quantize_fp8


def program_config(b: dict):
    from repro.configs.base import ModelConfig
    return ModelConfig(
        name=b["model"], arch_type="dense",
        n_layers=b["num_hidden_layers"], d_model=b["hidden_size"],
        n_heads=b["num_attention_heads"], n_kv_heads=b["num_key_value_heads"],
        d_ff=b["intermediate_size"], vocab_size=b["vocab_size"],
        qkv_bias=b["qkv_bias"], rope_theta=float(b["rope_theta"]),
        param_dtype=b["param_dtype"], compute_dtype=b["compute_dtype"],
        source=b["source"]).validate()


# ----------------------------------------------------------------------
# weights
# ----------------------------------------------------------------------

def make_weights(b: dict, seed: int, vocab_rows: int) -> Dict:
    """Drawn in float32 and stored in the parameter dtype, stacked over
    layers."""
    d, L = b["hidden_size"], b["num_hidden_layers"]
    hd = d // b["num_attention_heads"]
    q, kv, f = b["num_attention_heads"] * hd, b["num_key_value_heads"] * hd, \
        b["intermediate_size"]
    dt = jnp.dtype(b["param_dtype"])

    def make(key):
        ks = jax.random.split(key, 9)

        def w(k, shape, fan_in):
            return (jax.random.normal(k, shape, jnp.float32)
                    / math.sqrt(fan_in)).astype(dt)

        def small(k, shape):
            return (jax.random.normal(k, shape, jnp.float32) * 0.02).astype(dt)

        return {
            "embed": small(ks[0], (vocab_rows, d)),
            "ln_f": jnp.zeros((d,), dt),
            "wq": w(ks[1], (L, d, q), d), "wk": w(ks[2], (L, d, kv), d),
            "wv": w(ks[3], (L, d, kv), d), "wo": w(ks[4], (L, q, d), q),
            "bq": small(ks[5], (L, q)), "bk": small(ks[6], (L, kv)),
            "bv": small(ks[7], (L, kv)),
            "wg": w(ks[8], (L, d, f), d),
            "wi": w(jax.random.fold_in(ks[8], 1), (L, d, f), d),
            "wo_mlp": w(jax.random.fold_in(ks[8], 2), (L, f, d), f),
            "ln_mix": jnp.zeros((L, d), dt),
            "ln_mlp": jnp.zeros((L, d), dt),
        }

    key = jax.random.fold_in(jax.random.PRNGKey(seed % 2 ** 32),
                             seed // 2 ** 32)
    return jax.jit(make)(key)


def program_params(w: Dict) -> Dict:
    return {"embed": w["embed"], "ln_f": w["ln_f"],
            "layers": {"ln_mix": w["ln_mix"], "ln_mlp": w["ln_mlp"],
                       "attn": {"wq": w["wq"], "wk": w["wk"], "wv": w["wv"],
                                "wo": w["wo"], "bq": w["bq"], "bk": w["bk"],
                                "bv": w["bv"]},
                       "mlp": {"wg": w["wg"], "wi": w["wi"],
                               "wo": w["wo_mlp"]}}}


# ----------------------------------------------------------------------
# plain reference
# ----------------------------------------------------------------------

def _rms(x, w):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + 1e-6)
    return (y * (1.0 + w.astype(jnp.float32))).astype(x.dtype)


def _rope(x, theta):
    L, H, hd = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(L, dtype=jnp.float32)[:, None] * inv[None]
    sin, cos = jnp.sin(ang)[:, None], jnp.cos(ang)[:, None]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


def _decoder(tokens, w, *, n_heads, n_kv, theta, vocab, precision, dtype,
             fp8):
    dt = jnp.dtype(dtype)
    L = tokens.shape[0]
    d = w["embed"].shape[1]
    hd = d // n_heads
    g = n_heads // n_kv
    causal = jnp.arange(L)[None, :] <= jnp.arange(L)[:, None]

    def cast(a):
        return quantize_fp8(a, dt) if fp8 else a.astype(dt)

    def mm(a, b):
        return jnp.matmul(a, cast(b), precision=precision)

    def layer(x, lp):
        h = _rms(x, lp["ln_mix"])
        q = (mm(h, lp["wq"]) + lp["bq"].astype(dt)).reshape(L, n_heads, hd)
        k = (mm(h, lp["wk"]) + lp["bk"].astype(dt)).reshape(L, n_kv, hd)
        v = (mm(h, lp["wv"]) + lp["bv"].astype(dt)).reshape(L, n_kv, hd)
        q, k = _rope(q, theta), _rope(k, theta)
        s = jnp.einsum("lkgd,mkd->kglm", q.reshape(L, n_kv, g, hd), k,
                       precision=precision).astype(jnp.float32)
        s = jnp.where(causal, s / math.sqrt(hd), NEG)
        a = jax.nn.softmax(s, -1).astype(dt)
        o = jnp.einsum("kglm,mkd->lkgd", a, v, precision=precision)
        x = x + mm(o.reshape(L, d), lp["wo"])
        h = _rms(x, lp["ln_mlp"])
        return x + mm(jax.nn.silu(mm(h, lp["wg"])) * mm(h, lp["wi"]),
                      lp["wo_mlp"]), None

    layers = {k: w[k] for k in _LAYER_KEYS}
    x = w["embed"][tokens].astype(dt)
    x, _ = jax.lax.scan(layer, x, layers)
    h = _rms(x, w["ln_f"])
    head = w["embed"][:vocab]
    return jnp.matmul(h, cast(head).T if fp8 else head.astype(dt).T,
                      precision=precision).astype(jnp.float32)


_LAYER_KEYS = ("wq", "wk", "wv", "wo", "bq", "bk", "bv", "wg", "wi",
               "wo_mlp", "ln_mix", "ln_mlp")
_decoder_jit = jax.jit(_decoder, static_argnames=(
    "n_heads", "n_kv", "theta", "vocab", "precision", "dtype", "fp8"))


def logits(w: Dict, b: dict, tokens: np.ndarray, *,
           control: bool = False) -> jnp.ndarray:
    """The scan reads one layer of the stacked weights at a time."""
    return _decoder_jit(
        jnp.asarray(tokens, jnp.int32), w,
        n_heads=b["num_attention_heads"], n_kv=b["num_key_value_heads"],
        theta=float(b["rope_theta"]), vocab=b["vocab_size"],
        precision="default" if control else "highest",
        dtype="bfloat16" if control else "float32", fp8=control)


# ----------------------------------------------------------------------
# counts
# ----------------------------------------------------------------------
# The decode step reads every weight once in the compute dtype (bf16),
# reads the keys and values of the positions it attends to, and writes
# one position of keys and values per layer.  It does not read an fp32
# master copy, and the padded rows of the embedding are not weights.

def layer_params(b: dict) -> int:
    d, d_ff = b["hidden_size"], b["intermediate_size"]
    hd = d // b["num_attention_heads"]
    q, kv = b["num_attention_heads"] * hd, b["num_key_value_heads"] * hd
    n = d * q + 2 * d * kv + q * d + 3 * d * d_ff + 2 * d
    if b["qkv_bias"]:
        n += q + 2 * kv
    return n


def params(b: dict) -> int:
    """All weights of the model: layers, final norm, tied embedding."""
    return (b["num_hidden_layers"] * layer_params(b) + b["hidden_size"]
            + b["vocab_size"] * b["hidden_size"])


def matmul_params(b: dict) -> int:
    """Weights that take part in a matmul per token: the layers' and
    the output head's (the embedding lookup is a gather)."""
    return (b["num_hidden_layers"] * layer_params(b)
            + b["vocab_size"] * b["hidden_size"])


def decode_step(b: dict, batch: int, attended: int) -> Counts:
    d, L = b["hidden_size"], b["num_hidden_layers"]
    hd = d // b["num_attention_heads"]
    q_dim = b["num_attention_heads"] * hd
    kv_dim = b["num_key_value_heads"] * hd
    flops = (2.0 * batch * matmul_params(b)
             + 4.0 * batch * L * q_dim * attended)
    nbytes = (BF16 * params(b)
              + BF16 * L * batch * attended * 2 * kv_dim
              + BF16 * L * batch * 2 * kv_dim
              + batch * (I32 + I32))
    return Counts(flops, nbytes)
