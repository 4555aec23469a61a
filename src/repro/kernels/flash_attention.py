"""Pallas TPU kernel: blocked flash attention (causal / SWA / softcap / GQA).

TPU-flash conventions (DESIGN.md §3): running max/denominator/accumulator
live in VMEM scratch, key/value blocks stream HBM->VMEM along the
innermost (sequential) grid axis, query/head/batch axes are parallel.

  grid = (B, Hq, Lq/BLK_Q, Lk/BLK_K)          (last axis sequential)
  scratch: m (BLK_Q, 1), l (BLK_Q, 1), acc (BLK_Q, hd)
  per step: s = q @ k^T / sqrt(hd)  -> softcap -> causal/window mask
            online-softmax rescale of (m, l, acc)
  last step: out = acc / l

GQA is expressed in the k/v index maps (kv head = q head // group) so
no K/V replication ever materializes.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float("-inf")


def _flash_kernel(q_ref, k_ref, v_ref, valid_ref, o_ref,
                  m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, window: int, softcap: float,
                  blk_q: int, blk_k: int, lq: int, lk: int):
    jk = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(jk == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)                     # (BLK_Q, hd)
    k = k_ref[0, 0].astype(jnp.float32)                     # (BLK_K, hd)
    v = v_ref[0, 0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if softcap:
        s = softcap * jnp.tanh(s / softcap)

    iq = pl.program_id(2) * blk_q + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, 0)
    ik = jk * blk_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    valid = (iq < lq) & (ik < lk)                            # padding mask
    # decode mode: per-sequence valid key length (flash-decode against a
    # partially-filled KV cache); valid_ref holds one int32 per batch row
    valid &= ik < valid_ref[0]
    if causal:
        row = iq + (lk - lq)                                 # align ends
        valid &= ik <= row
        if window:
            valid &= ik > row - window
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[...]                                      # (BLK_Q, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    # fully-masked rows keep m == -inf; guard all exp() through `valid`
    alpha = jnp.where(m_new == NEG_INF, 1.0, jnp.exp(m_prev - m_new))
    p = jnp.where(valid, jnp.exp(s - m_new), 0.0)            # (BLK_Q, BLK_K)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(jk == nk - 1)
    def _emit():
        l = l_ref[...]
        o_ref[0, 0] = jnp.where(
            l > 0, acc_ref[...] / jnp.where(l > 0, l, 1.0), 0.0
        ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "softcap", "blk_q", "blk_k", "interpret"))
def flash_attention_pallas(q, k, v, kv_valid=None, *, causal: bool = True,
                           window: int = 0,
                           softcap: float = 0.0, blk_q: int = 128,
                           blk_k: int = 128, interpret: bool):
    """q (B, Hq, Lq, hd); k, v (B, Hkv, Lk, hd); Hq % Hkv == 0.

    kv_valid: optional (B,) int32 — per-sequence number of valid cache
    keys (flash-decode against a partially-filled KV cache; defaults
    to Lk, i.e. all keys live).
    Lq/Lk need not be block-aligned (padding is masked in-kernel);
    hd should be 128-aligned for MXU efficiency (ops.py pads).
    Returns (B, Hq, Lq, hd) in q.dtype.
    """
    B, Hq, Lq, hd = q.shape
    Hkv, Lk = k.shape[1], k.shape[2]
    G = Hq // Hkv
    blk_q = min(blk_q, max(Lq, 8))
    blk_k = min(blk_k, max(Lk, 8))
    Lqp = math.ceil(Lq / blk_q) * blk_q
    Lkp = math.ceil(Lk / blk_k) * blk_k
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, Lqp - Lq), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, Lkp - Lk), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, Lkp - Lk), (0, 0)))
    if kv_valid is None:
        kv_valid = jnp.full((B,), Lk, jnp.int32)
    kv_valid = jnp.asarray(kv_valid, jnp.int32)

    grid = (B, Hq, Lqp // blk_q, Lkp // blk_k)
    kernel = functools.partial(
        _flash_kernel, scale=1.0 / math.sqrt(hd), causal=causal,
        window=window, softcap=softcap, blk_q=blk_q, blk_k=blk_k,
        lq=Lq, lk=Lk)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, blk_q, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, blk_k, hd),
                         lambda b, h, i, j, G=G: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, blk_k, hd),
                         lambda b, h, i, j, G=G: (b, h // G, j, 0)),
            pl.BlockSpec((1,), lambda b, h, i, j: (b,)),
        ],
        out_specs=pl.BlockSpec((1, 1, blk_q, hd),
                               lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Lqp, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((blk_q, 1), jnp.float32),
            pltpu.VMEM((blk_q, 1), jnp.float32),
            pltpu.VMEM((blk_q, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(qp, kp, vp, kv_valid)
    return out[:, :, :Lq]
