"""Pallas TPU kernel: fused routing-score top-k over the MRES catalog.

The paper's hot loop is "approximate kNN in an in-memory vector DB".
On TPU we recast it (DESIGN.md §3) as a dense blocked matmul with the
hierarchical-filter mask fused in-register and a running top-k carried
in VMEM scratch across catalog blocks:

  grid = (Q/BLK_Q, N/BLK_N), catalog axis innermost (sequential)
  per step:  scores = q_blk @ emb_blk^T            (MXU, 128-aligned)
             scores = where(mask_blk, scores, -inf) (VPU)
             k rounds of row max / first-lane select merge the block
             into the running (vals, idx) carry (``carry_block_topk``)

The carry update uses only row reductions, iota compares and selects,
which Mosaic lowers; an in-kernel ``lax.top_k`` or gather by computed
index does not compile for the chip.  ``merge_topk`` (bitonic, XLA
side) reduces per-shard carries in the sharded ``route_step``.

Dense blocked scan beats ANN graph traversal on TPU because pointer
chasing is hostile to the systolic pipeline while a 100k x 128 catalog
tile stream is a few MB of sequential VMEM traffic.

Inputs are pre-normalized by ops.py (rows scaled to unit norm, weights
folded into the catalog matrix) so the kernel is a pure
score-mask-select loop.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float("-inf")
# query rows per int8 kernel tile: the TPU's minimum int8 tile is
# (32, 128), so compiled int8 runs stream 32-row query blocks
Q8_BLK_Q = 32


def _pow2_ge(x: int) -> int:
    """Smallest power of two >= x (x >= 1)."""
    return 1 << max(x - 1, 1).bit_length() if x > 1 else 1


def carry_block_topk(cv, ci, scores, col0, k: int):
    """Top-k of the union of the running carry and one scored block.

    cv/ci (Q, k) — the carry, sorted descending with ties in ascending
    column order; scores (Q, M) — one catalog block whose lane j is
    global column ``col0 + j`` (every carry column is lower).  k rounds
    of: row max of each side, first lane holding it (iota + min), take
    the carry's on a tie (it holds the lower columns), then knock the
    taken lane out to -inf.  Only row reductions, iota compares and
    selects — the forms Mosaic lowers — so it runs inside the Pallas
    kernel on the chip; XLA's ``top_k``/gather do not lower there.
    Lanes whose value is -inf (masked, padded, sub-threshold, or the
    tail when fewer than k columns survive) carry index -1.
    Returns the new carry (vals (Q, k) f32, idx (Q, k) i32).
    """
    Q, M = scores.shape
    lane_k = jax.lax.broadcasted_iota(jnp.int32, (Q, k), 1)
    lane_m = jax.lax.broadcasted_iota(jnp.int32, (Q, M), 1)

    def one_round(j, st):
        cv, bv, ov, oi = st
        mc = jnp.max(cv, axis=1, keepdims=True)
        mb = jnp.max(bv, axis=1, keepdims=True)
        take_c = mc >= mb
        pc = jnp.min(jnp.where(cv == mc, lane_k, k), axis=1, keepdims=True)
        pb = jnp.min(jnp.where(bv == mb, lane_m, M), axis=1, keepdims=True)
        ic = jnp.max(jnp.where(lane_k == pc, ci, -1), axis=1,
                     keepdims=True)
        m = jnp.maximum(mc, mb)
        i = jnp.where(m > NEG_INF, jnp.where(take_c, ic, col0 + pb), -1)
        cv = jnp.where(take_c & (lane_k == pc), NEG_INF, cv)
        bv = jnp.where(~take_c & (lane_m == pb), NEG_INF, bv)
        ov = jnp.where(lane_k == j, m, ov)
        oi = jnp.where(lane_k == j, i, oi)
        return cv, bv, ov, oi

    init = (cv, scores, jnp.full((Q, k), NEG_INF, jnp.float32),
            jnp.full((Q, k), -1, jnp.int32))
    _, _, ov, oi = jax.lax.fori_loop(0, k, one_round, init)
    return ov, oi


def _pad_const(p):
    """Pad filler per payload dtype: -1 for integer lanes (index
    semantics), 0 for float side-payloads (masked by -inf values)."""
    return -1 if jnp.issubdtype(p.dtype, jnp.integer) else 0


def merge_topk(av, bv, a_payloads, b_payloads):
    """Top-k union of two sorted-descending (Q, k) carries, with any
    number of payload columns riding along every compare-exchange.

    One bitonic compare-exchange of ``a`` against ``b`` reversed keeps
    the k largest of the 2k, then log2(k) merge stages sort them
    descending — O(k log k); inputs need not be power-of-two wide
    (padded internally).  Each payload in
    ``a_payloads``/``b_payloads`` (tuples of (Q, k) arrays — indices,
    per-lane blend scores, cosines, ...) takes the exact same keep
    mask as the values, so lanes never mix payloads.  Ties keep the
    ``a`` element — chaining merges in shard order therefore resolves
    cross-shard ties toward the LOWEST shard, matching ``lax.top_k``'s
    lowest-index-first contract on a concatenated catalog.  Returns
    (vals (Q, k), tuple of merged payloads).
    """
    assert len(a_payloads) == len(b_payloads)
    k = av.shape[1]
    kp = _pow2_ge(k)
    a_pl, b_pl = list(a_payloads), list(b_payloads)
    if kp != k:
        pad = ((0, 0), (0, kp - k))
        av = jnp.pad(av, pad, constant_values=NEG_INF)
        bv = jnp.pad(bv, pad, constant_values=NEG_INF)
        a_pl = [jnp.pad(p, pad, constant_values=_pad_const(p))
                for p in a_pl]
        b_pl = [jnp.pad(p, pad, constant_values=_pad_const(p))
                for p in b_pl]
    rv = bv[:, ::-1]
    r_pl = [p[:, ::-1] for p in b_pl]
    keep_a = av >= rv
    v = jnp.where(keep_a, av, rv)
    pl = [jnp.where(keep_a, pa, pr) for pa, pr in zip(a_pl, r_pl)]
    # v is bitonic; sort descending with a standard bitonic merger
    s = kp // 2
    while s >= 1:
        pos = jnp.arange(kp)
        pv = v[:, pos ^ s]
        first = ((pos & s) == 0)[None, :]       # lower index of each pair
        keep = jnp.where(first, v >= pv, v <= pv)
        pl = [jnp.where(keep, p, p[:, pos ^ s]) for p in pl]
        v = jnp.where(keep, v, pv)
        s //= 2
    return v[:, :k], tuple(p[:, :k] for p in pl)


def tree_merge_topk(vals, payloads):
    """Pairwise-tree reduction of S sorted-descending per-shard
    carries into ONE global (Q, k) top-k — the cross-shard step of the
    sharded ``route_step``.

    vals (S, Q, k) stacked per-shard top-k values (shard-major, e.g.
    from ``lax.all_gather``); payloads: tuple of (S, Q, k) arrays.
    Merges adjacent pairs per level (log2(S) levels of
    ``merge_topk``), always folding the HIGHER shard into the
    lower so ties resolve toward the lowest shard — the same winner a
    single-device ``top_k`` over the concatenated catalog picks.
    Returns (vals (Q, k), tuple of payloads (Q, k)).
    """
    S = vals.shape[0]
    parts = [(vals[s], tuple(p[s] for p in payloads)) for s in range(S)]
    while len(parts) > 1:
        nxt = []
        for i in range(0, len(parts) - 1, 2):
            (av, apl), (bv, bpl) = parts[i], parts[i + 1]
            nxt.append(merge_topk(av, bv, apl, bpl))
        if len(parts) % 2:
            nxt.append(parts[-1])
        parts = nxt
    return parts[0]


def _router_topk_kernel(q_ref, emb_ref, mask_ref, bias_ref, vals_ref,
                        idx_ref, sv_ref, si_ref, *, k: int, blk_n: int,
                        min_score: float):
    jn = pl.program_id(1)
    nn = pl.num_programs(1)

    @pl.when(jn == 0)
    def _init():
        sv_ref[...] = jnp.full_like(sv_ref, NEG_INF)
        si_ref[...] = jnp.full_like(si_ref, -1)

    q = q_ref[...].astype(jnp.float32)                      # (BLK_Q, D)
    emb = emb_ref[...].astype(jnp.float32)                  # (BLK_N, D)
    mask = mask_ref[...]                                    # (BLK_Q, BLK_N)
    bias = bias_ref[...]                                    # (1, BLK_N)
    scores = jax.lax.dot_general(
        q, emb, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)                 # (BLK_Q, BLK_N)
    # bias joins valid rows only: a heavy load penalty must stay
    # distinguishable from a failed hierarchical filter (-inf)
    scores = jnp.where(mask > 0, scores + bias, NEG_INF)
    if min_score != NEG_INF:
        # fused admission threshold (the semantic cache's similarity
        # floor): sub-threshold rows drop out in-register, so callers
        # never see a "best" match that is not a usable one
        scores = jnp.where(scores >= min_score, scores, NEG_INF)

    new_v, new_i = carry_block_topk(sv_ref[...], si_ref[...], scores,
                                    jn * blk_n, k)
    sv_ref[...] = new_v
    si_ref[...] = new_i

    @pl.when(jn == nn - 1)
    def _emit():
        vals_ref[...] = sv_ref[...]
        idx_ref[...] = si_ref[...]


@functools.partial(jax.jit, static_argnames=("k", "blk_q", "blk_n",
                                             "min_score", "interpret"))
def router_topk_pallas(qn: jnp.ndarray, embn: jnp.ndarray, mask: jnp.ndarray,
                       bias: jnp.ndarray, k: int, *, blk_q: int = 8,
                       blk_n: int = 512, min_score: float = NEG_INF,
                       interpret: bool):
    """qn (Q, D) unit rows; embn (N, D) unit(+weighted) rows;
    mask (Q, N) f32 — per-query hierarchical filter mask (ops.py
    broadcasts a shared (N,) mask to all queries); bias (1, N) f32 —
    additive per-catalog-row score term (zeros when unused), applied
    to mask-valid rows in-register right after the scoring matmul;
    min_score — static score floor fused after mask+bias (rows below
    it surface as -inf; -inf disables the threshold).

    Q % blk_q == 0, N % blk_n == 0, D padded to 128 (done by ops.py).
    Returns (vals (Q, k) f32, idx (Q, k) i32).
    """
    Q, D = qn.shape
    N = embn.shape[0]
    assert Q % blk_q == 0 and N % blk_n == 0, (Q, N, blk_q, blk_n)
    assert mask.shape == (Q, N), (mask.shape, Q, N)
    assert bias.shape == (1, N), (bias.shape, N)
    grid = (Q // blk_q, N // blk_n)

    kernel = functools.partial(_router_topk_kernel, k=k, blk_n=blk_n,
                               min_score=min_score)
    vals, idx = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((blk_q, D), lambda i, j: (i, 0)),
            pl.BlockSpec((blk_n, D), lambda i, j: (j, 0)),
            pl.BlockSpec((blk_q, blk_n), lambda i, j: (i, j)),
            pl.BlockSpec((1, blk_n), lambda i, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((blk_q, k), lambda i, j: (i, 0)),
            pl.BlockSpec((blk_q, k), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Q, k), jnp.float32),
            jax.ShapeDtypeStruct((Q, k), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((blk_q, k), jnp.float32),
            pltpu.VMEM((blk_q, k), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(qn, embn, mask, bias)
    return vals, idx


# ----------------------------------------------------------------------
# int8 variant: dequant-free int32 accumulate, fp32 rescale at the
# top-k boundary
# ----------------------------------------------------------------------

def _router_topk_q8_kernel(q_ref, emb_ref, qs_ref, es_ref, mask_ref,
                           bias_ref, vals_ref, idx_ref, sv_ref, si_ref,
                           *, k: int, blk_n: int, min_score: float):
    jn = pl.program_id(1)
    nn = pl.num_programs(1)

    @pl.when(jn == 0)
    def _init():
        sv_ref[...] = jnp.full_like(sv_ref, NEG_INF)
        si_ref[...] = jnp.full_like(si_ref, -1)

    q8 = q_ref[...]                                         # (BLK_Q, D) i8
    e8 = emb_ref[...]                                       # (BLK_N, D) i8
    # the scan matmul accumulates in int32 — no dequantized fp32 copy
    # of the catalog block ever materializes; the only fp32 work per
    # (BLK_Q, BLK_N) tile is ONE elementwise rescale by the per-row
    # scale outer product, right at the top-k boundary
    # (explicit DEFAULT: an ambient fp32 matmul precision must not
    # reach the integer dot, which Mosaic then refuses)
    acc = jax.lax.dot_general(
        q8, e8, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.DEFAULT,
        preferred_element_type=jnp.int32)                   # (BLK_Q, BLK_N)
    scores = acc.astype(jnp.float32) * (qs_ref[...] * es_ref[...])
    scores = jnp.where(mask_ref[...] > 0, scores + bias_ref[...], NEG_INF)
    if min_score != NEG_INF:
        scores = jnp.where(scores >= min_score, scores, NEG_INF)

    new_v, new_i = carry_block_topk(sv_ref[...], si_ref[...], scores,
                                    jn * blk_n, k)
    sv_ref[...] = new_v
    si_ref[...] = new_i

    @pl.when(jn == nn - 1)
    def _emit():
        vals_ref[...] = sv_ref[...]
        idx_ref[...] = si_ref[...]


@functools.partial(jax.jit, static_argnames=("k", "blk_q", "blk_n",
                                             "min_score", "interpret"))
def router_topk_q8_pallas(q8: jnp.ndarray, e8: jnp.ndarray,
                          qscale: jnp.ndarray, escale: jnp.ndarray,
                          mask: jnp.ndarray, bias: jnp.ndarray, k: int,
                          *, blk_q: int = Q8_BLK_Q, blk_n: int = 512,
                          min_score: float = NEG_INF,
                          interpret: bool):
    """int8-quantized ``router_topk_pallas``.

    q8 (Q, D) / e8 (N, D) int8 rows quantized symmetrically per row;
    qscale (Q, 1) / escale (1, N) f32 per-row scales such that the
    fp32 score of (q, n) is ``(q8[q] . e8[n]) * qscale[q] * escale[n]``.
    The per-block matmul runs on the int8 operands with an int32
    accumulator (``preferred_element_type``) — the catalog stream is
    1/4 the bytes of the fp32 kernel, and on a memory-bandwidth-bound
    scan that is the speedup (see benchmarks/roofline.py) — and the
    fp32 rescale happens once per tile at the top-k boundary.

    Tiling: the TPU int8 minimum tile is (32, 128), so callers pass
    ``blk_q=Q8_BLK_Q`` (``ops`` pads the query axis to it).

    Same shape contract and returns as ``router_topk_pallas``.
    """
    Q, D = q8.shape
    N = e8.shape[0]
    assert q8.dtype == jnp.int8 and e8.dtype == jnp.int8, (q8.dtype,
                                                          e8.dtype)
    assert Q % blk_q == 0 and N % blk_n == 0, (Q, N, blk_q, blk_n)
    assert qscale.shape == (Q, 1) and escale.shape == (1, N), (
        qscale.shape, escale.shape)
    assert mask.shape == (Q, N) and bias.shape == (1, N)
    grid = (Q // blk_q, N // blk_n)

    kernel = functools.partial(_router_topk_q8_kernel, k=k, blk_n=blk_n,
                               min_score=min_score)
    vals, idx = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((blk_q, D), lambda i, j: (i, 0)),
            pl.BlockSpec((blk_n, D), lambda i, j: (j, 0)),
            pl.BlockSpec((blk_q, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, blk_n), lambda i, j: (0, j)),
            pl.BlockSpec((blk_q, blk_n), lambda i, j: (i, j)),
            pl.BlockSpec((1, blk_n), lambda i, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((blk_q, k), lambda i, j: (i, 0)),
            pl.BlockSpec((blk_q, k), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Q, k), jnp.float32),
            jax.ShapeDtypeStruct((Q, k), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((blk_q, k), jnp.float32),
            pltpu.VMEM((blk_q, k), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(q8, e8, qscale, escale, mask, bias)
    return vals, idx
