"""Pallas TPU kernel: Mamba2 SSD (state-space duality) chunked scan.

The SSD recurrence  h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t^T,
y_t = C_t . h_t  is evaluated chunk-by-chunk: within a chunk the output
is a (masked, decay-weighted) quadratic form C B^T — dense matmuls the
MXU likes — and the carried state advances once per chunk.  The (P, N)
state lives in VMEM scratch across the sequential chunk axis:

  grid = (B, H, L/CHUNK)                     (chunk axis sequential)
  per chunk: la      = cumsum(dt * A)
             y_inter = exp(la) * (C @ h^T)
             y_intra = ((C @ B^T) * causal-decay * dt) @ x
             h       = exp(la_last) h + (x * contrib)^T @ B

B/C are group-shared over heads (groups=1) so their blocks are indexed
by (batch, chunk) only — no head replication materializes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, h0_ref,
                y_ref, hf_ref, state_ref, *, chunk: int):
    ic = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ic == 0)
    def _init():
        state_ref[...] = h0_ref[0, 0].astype(jnp.float32)

    x = x_ref[0, :, 0, :].astype(jnp.float32)               # (c, P)
    dt = dt_ref[0, :, 0].astype(jnp.float32)                # (c,)
    a = a_ref[0].astype(jnp.float32)                        # scalar
    bm = b_ref[0].astype(jnp.float32)                       # (c, N)
    cm = c_ref[0].astype(jnp.float32)                       # (c, N)
    h = state_ref[...]                                      # (P, N)

    la = jnp.cumsum(dt * a)                                 # (c,) log-decay <= 0
    # inter-chunk: y_i += exp(la_i) * C_i . h
    y_inter = jnp.exp(la)[:, None] * jax.lax.dot_general(
        cm, h, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)                 # (c, P)
    # intra-chunk: masked decay-weighted quadratic form
    cb = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (c, c)
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = ii >= jj
    # mask the exponent before exp (non-causal args are positive, overflow)
    dec = jnp.exp(jnp.where(causal, la[:, None] - la[None, :], 0.0))
    w = jnp.where(causal, dec, 0.0) * dt[None, :]
    y_intra = jax.lax.dot(cb * w, x, preferred_element_type=jnp.float32)
    y_ref[0, :, 0, :] = (y_inter + y_intra).astype(y_ref.dtype)

    # state update: h' = exp(la_last) h + (x * contrib)^T @ B
    contrib = jnp.exp(la[-1] - la) * dt                     # (c,)
    state_ref[...] = h * jnp.exp(la[-1]) + jax.lax.dot_general(
        x * contrib[:, None], bm, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                 # (P, N)

    @pl.when(ic == nc - 1)
    def _emit():
        hf_ref[0, 0] = state_ref[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan_pallas(x, dt, A, B, C, h0=None, *, chunk: int = 128,
                    interpret: bool):
    """SSD scan. Shapes per kernels/ref.py::ssd_scan.

    x (Bb, L, H, P); dt (Bb, L, H); A (H,); B/C (Bb, L, N);
    h0 (Bb, H, P, N) or None.  L is padded to a chunk multiple with
    dt = 0 (unit decay, zero input) so the final state is exact.
    Returns (y (Bb, L, H, P) f32, h_final (Bb, H, P, N) f32).
    """
    Bb, L, H, P = x.shape
    N = B.shape[-1]
    chunk = min(chunk, max(L, 8))
    Lp = -(-L // chunk) * chunk
    pad = ((0, 0), (0, Lp - L))
    xp = jnp.pad(x, pad + ((0, 0), (0, 0)))
    dtp = jnp.pad(dt, pad + ((0, 0),))
    Bp = jnp.pad(B, pad + ((0, 0),))
    Cp = jnp.pad(C, pad + ((0, 0),))
    if h0 is None:
        h0 = jnp.zeros((Bb, H, P, N), jnp.float32)

    grid = (Bb, H, Lp // chunk)
    y, hf = pl.pallas_call(
        functools.partial(_ssd_kernel, chunk=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, 1, P), lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((1, chunk, 1), lambda b, h, c: (b, c, h)),
            pl.BlockSpec((1,), lambda b, h, c: (h,)),
            pl.BlockSpec((1, chunk, N), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, 1, P), lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bb, Lp, H, P), jnp.float32),
            jax.ShapeDtypeStruct((Bb, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(xp, dtp, A, Bp, Cp, h0)
    return y[:, :L], hf
