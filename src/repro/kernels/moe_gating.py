"""Pallas TPU kernel: fused MoE gate (softmax + top-k + renormalize).

One VMEM-resident pass per token block: row softmax, k-pass argmax
selection (k static, unrolled — TPU-friendly, no sort network), top-k
renormalization, plus per-block partial sums of probs / assignments so
the wrapper can form the load-balance aux loss without a second pass.

  grid = (T/BLK_T,)  all parallel
  outs: gate_vals (T, k), gate_idx (T, k),
        probs_sum (nblk, E), assign_sum (nblk, E)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float("-inf")


def _gate_kernel(logits_ref, vals_ref, idx_ref, psum_ref, asum_ref, *,
                 k: int, blk_t: int, t_total: int):
    it = pl.program_id(0)
    logits = logits_ref[...].astype(jnp.float32)            # (BLK_T, E)
    E = logits.shape[1]
    row = it * blk_t + jax.lax.broadcasted_iota(jnp.int32, (blk_t, 1), 0)
    live = row < t_total                                     # (BLK_T, 1)

    m = jnp.max(logits, axis=1, keepdims=True)
    p = jnp.exp(logits - m)
    probs = p / jnp.sum(p, axis=1, keepdims=True)           # (BLK_T, E)

    rem = probs
    vs, ids = [], []
    assign = jnp.zeros_like(probs)
    for _ in range(k):
        am = jnp.argmax(rem, axis=1)                        # (BLK_T,)
        onehot = jax.nn.one_hot(am, E, dtype=jnp.float32)
        vs.append(jnp.sum(rem * onehot, axis=1))
        ids.append(am.astype(jnp.int32))
        assign = assign + onehot
        rem = jnp.where(onehot > 0, NEG_INF, rem)
    vals = jnp.stack(vs, axis=1)                            # (BLK_T, k)
    vals_ref[...] = vals / (jnp.sum(vals, axis=1, keepdims=True) + 1e-9)
    idx_ref[...] = jnp.stack(ids, axis=1)

    livef = live.astype(jnp.float32)
    psum_ref[0] = jnp.sum(probs * livef, axis=0)
    asum_ref[0] = jnp.sum(assign * livef, axis=0)


@functools.partial(jax.jit, static_argnames=("k", "blk_t", "interpret"))
def moe_gating_pallas(logits: jnp.ndarray, k: int, *, blk_t: int = 256,
                      interpret: bool):
    """logits (T, E). Returns (vals (T, k) f32, idx (T, k) i32, aux f32)."""
    T, E = logits.shape
    blk_t = min(blk_t, max(T, 8))
    Tp = -(-T // blk_t) * blk_t
    lp = jnp.pad(logits, ((0, Tp - T), (0, 0)))
    nblk = Tp // blk_t

    vals, idx, psum, asum = pl.pallas_call(
        functools.partial(_gate_kernel, k=k, blk_t=blk_t, t_total=T),
        grid=(nblk,),
        in_specs=[pl.BlockSpec((blk_t, E), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((blk_t, k), lambda i: (i, 0)),
            pl.BlockSpec((blk_t, k), lambda i: (i, 0)),
            pl.BlockSpec((1, E), lambda i: (i, 0)),
            pl.BlockSpec((1, E), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Tp, k), jnp.float32),
            jax.ShapeDtypeStruct((Tp, k), jnp.int32),
            jax.ShapeDtypeStruct((nblk, E), jnp.float32),
            jax.ShapeDtypeStruct((nblk, E), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(lp)
    me = jnp.sum(psum, axis=0) / T
    ce = jnp.sum(asum, axis=0) / T
    aux = jnp.sum(me * ce) * E
    return vals[:T], idx[:T], aux
