"""Operations and bytes that a step needs, from its shapes.

The counting rule: count the work the configuration needs at its
declared dtypes, not what today's code happens to move.

* The decode step reads every weight once in the compute dtype (bf16),
  reads the keys and values of the positions it attends to, and writes
  one position of keys and values per layer.  It does not read the fp32
  master copy, and the padded rows of the embedding are not weights.
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
# served backend: a dense decoder with GQA, QKV bias and SwiGLU
# ----------------------------------------------------------------------

def decoder_layer_params(d: int, n_heads: int, n_kv_heads: int, d_ff: int,
                         qkv_bias: bool) -> int:
    hd = d // n_heads
    q, kv = n_heads * hd, n_kv_heads * hd
    n = d * q + 2 * d * kv + q * d + 3 * d * d_ff + 2 * d
    if qkv_bias:
        n += q + 2 * kv
    return n


def decoder_params(m: dict) -> int:
    """All weights of the model: layers, final norm, tied embedding."""
    per = decoder_layer_params(m["hidden_size"], m["num_attention_heads"],
                               m["num_key_value_heads"],
                               m["intermediate_size"], m["qkv_bias"])
    return (m["num_hidden_layers"] * per + m["hidden_size"]
            + m["vocab_size"] * m["hidden_size"])


def decoder_matmul_params(m: dict) -> int:
    """Weights that take part in a matmul per token: the layers' and
    the output head's (the embedding lookup is a gather)."""
    per = decoder_layer_params(m["hidden_size"], m["num_attention_heads"],
                               m["num_key_value_heads"],
                               m["intermediate_size"], m["qkv_bias"])
    return m["num_hidden_layers"] * per + m["vocab_size"] * m["hidden_size"]


def decode_step(m: dict, batch: int, attended: int) -> Counts:
    """One greedy decode step of ``batch`` sequences, each attending to
    ``attended`` positions (its cached prefix and itself)."""
    d, L = m["hidden_size"], m["num_hidden_layers"]
    hd = d // m["num_attention_heads"]
    q_dim = m["num_attention_heads"] * hd
    kv_dim = m["num_key_value_heads"] * hd
    flops = (2.0 * batch * decoder_matmul_params(m)
             + 4.0 * batch * L * q_dim * attended)
    nbytes = (BF16 * decoder_params(m)
              + BF16 * L * batch * attended * 2 * kv_dim
              + BF16 * L * batch * 2 * kv_dim
              + batch * (I32 + I32))
    return Counts(flops, nbytes)


def decode_flops_per_token(m: dict) -> float:
    """Model operations per generated token (matmul weights only)."""
    return 2.0 * decoder_matmul_params(m)


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
