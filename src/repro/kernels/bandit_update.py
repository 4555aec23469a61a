"""Pallas TPU kernel: fused contextual-bandit posterior update + LinUCB
scoring over packed per-model sufficient statistics.

The adaptive routing layer (``repro.adaptive``) keeps one linear-bandit
posterior (A_n, b_n) per catalog model as packed arrays.  Its serving
cadence is: score the incoming batch under the current posterior, route,
observe rewards, fold the outcome batch back in.  Both halves are pure
matmuls once the rank-1 structure is flattened:

  dA = W^T @ XX        W  (Bu, N) choice mask, XX (Bu, D^2) flattened
                       outer products x x^T — sum of rank-1 updates per
                       model as ONE (N, Bu) x (Bu, D^2) matmul
  db = W^T @ (r * X)   reward-weighted context sums
  ucb = Xs @ theta^T + sqrt(max(XXs @ (alpha^2 Ainv)^T, 0))
                       LinUCB mean + exploration width, the variance
                       x^T Ainv x recast as a (Bs, D^2) x (D^2, N)
                       matmul over the same flattened layout

so the whole learning step stays on the MXU at serving throughput:

  grid = (N/BLK_N,), one independent model block per step
  per step:  dA_blk  = w_blk^T @ xx_up          (MXU)
             db_blk  = w_blk^T @ xr             (MXU)
             ucb_blk = xs @ theta_blk^T
                       + sqrt(relu(xxs @ ainv_blk^T))   (MXU + VPU)

Inputs are pre-flattened/padded by ops.py (D^2 and D lane-padded to 128,
alpha^2 folded into Ainv); the host applies dA/db to the packed stats
and refreshes the tiny (N, D, D) inverses.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _bandit_update_kernel(w_ref, xx_ref, xr_ref, xs_ref, xxs_ref,
                          theta_ref, ainv_ref, da_ref, db_ref, ucb_ref):
    w = w_ref[...].astype(jnp.float32)                  # (Bu, BLK_N)
    xx = xx_ref[...].astype(jnp.float32)                # (Bu, P2)
    xr = xr_ref[...].astype(jnp.float32)                # (Bu, Dp)
    da_ref[...] = jax.lax.dot_general(
        w, xx, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)             # (BLK_N, P2)
    db_ref[...] = jax.lax.dot_general(
        w, xr, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)             # (BLK_N, Dp)

    xs = xs_ref[...].astype(jnp.float32)                # (Bs, Dp)
    xxs = xxs_ref[...].astype(jnp.float32)              # (Bs, P2)
    mean = jax.lax.dot_general(
        xs, theta_ref[...].astype(jnp.float32), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)             # (Bs, BLK_N)
    var = jax.lax.dot_general(
        xxs, ainv_ref[...].astype(jnp.float32), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)             # (Bs, BLK_N)
    ucb_ref[...] = mean + jnp.sqrt(jnp.maximum(var, 0.0))


@functools.partial(jax.jit, static_argnames=("blk_n", "interpret"))
def bandit_update_pallas(w: jnp.ndarray, xx_up: jnp.ndarray,
                         xr: jnp.ndarray, xs: jnp.ndarray,
                         xxs: jnp.ndarray, theta: jnp.ndarray,
                         ainv2: jnp.ndarray, *, blk_n: int = 128,
                         interpret: bool):
    """w (Bu, N) choice mask; xx_up (Bu, P2) flattened outer products;
    xr (Bu, Dp) reward-weighted contexts; xs (Bs, Dp) scoring contexts;
    xxs (Bs, P2) their outer products; theta (N, Dp); ainv2 (N, P2) —
    alpha^2 * Ainv flattened (exploration scale folded in by ops.py).

    N % blk_n == 0; Dp, P2 are 128-lane multiples; Bu, Bs sublane-
    aligned (done by ops.py).  Returns (dA (N, P2), db (N, Dp),
    ucb (Bs, N)), all f32.
    """
    Bu, N = w.shape
    P2 = xx_up.shape[1]
    Dp = xr.shape[1]
    Bs = xs.shape[0]
    assert N % blk_n == 0, (N, blk_n)
    assert theta.shape == (N, Dp) and ainv2.shape == (N, P2)
    grid = (N // blk_n,)

    da, db, ucb = pl.pallas_call(
        _bandit_update_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((Bu, blk_n), lambda j: (0, j)),
            pl.BlockSpec((Bu, P2), lambda j: (0, 0)),
            pl.BlockSpec((Bu, Dp), lambda j: (0, 0)),
            pl.BlockSpec((Bs, Dp), lambda j: (0, 0)),
            pl.BlockSpec((Bs, P2), lambda j: (0, 0)),
            pl.BlockSpec((blk_n, Dp), lambda j: (j, 0)),
            pl.BlockSpec((blk_n, P2), lambda j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((blk_n, P2), lambda j: (j, 0)),
            pl.BlockSpec((blk_n, Dp), lambda j: (j, 0)),
            pl.BlockSpec((Bs, blk_n), lambda j: (0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N, P2), jnp.float32),
            jax.ShapeDtypeStruct((N, Dp), jnp.float32),
            jax.ShapeDtypeStruct((Bs, N), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(w, xx_up, xr, xs, xxs, theta, ainv2)
    return da, db, ucb
