"""Fused single-dispatch routing step: the whole per-batch hot path of
``RoutingEngine.route_many`` as ONE jitted device program.

The staged path costs several device/numpy passes per batch — kNN
top-k, candidate gathers, feedback/bandit/load blends, argsort, plus
host-side per-row fallback retries.  ``route_step`` collapses all of it
into a single program (one device dispatch per routed batch):

  1. mask lookup        — the catalog's hierarchical-filter structure
     is pre-flattened by ``ops.py`` into ONE stacked mask table
     (task-type x domain combinations, then the fallback rungs:
     task-type-only rows, the generalist row, the live-catalog row)
     with a per-row population-count table.  Per-query masks and every
     ladder count are O(B) gathers — no (B, N) boolean reductions;
  2. score blend        — ONE (B, N) blend of user-weighted metric
     scores + feedback bias + LinUCB bandit estimates (mean + alpha *
     sqrt(x^T Ainv x), both as matmuls over the flattened rank-1
     layout) - load penalty;
  3. fused top-k        — primary rows rank the mask-fused COSINE
     similarities (the kNN), rows whose filter count is zero rank the
     BLEND under their first non-empty fallback rung instead: both
     live in one per-row-selected matrix, so a single ``top_k`` serves
     the kNN and the whole fallback ladder (masked re-scores inside
     the program, not host-side retries);
  4. candidate argmax   — primary candidates gather their blended
     scores from (2) and re-rank in-program (``top_k`` over k lanes),
     so the winner, its score and the ranked candidate list come out
     as arrays.

On TPU (``use_pallas``) the kNN stage runs the Pallas ``router_topk``
kernel (blocked MXU matmul + the in-kernel ``carry_block_topk`` carry
update) and the fallback re-score is its own ``top_k`` — the
structure XLA:TPU prefers; the single-matrix form above is the
XLA:CPU-friendly lowering the test suite exercises.

``jax.lax.optimization_barrier`` pins the big (B, N) intermediates:
without it XLA:CPU duplicates cheap producers (mask gathers, where
chains) into every consumer and the program slows ~20x.

All shapes are static per (Q bucket, padded catalog) pair — ``ops.py``
pads Q up to power-of-two buckets and N to the catalog's 128-aligned
capacity, so steady-state serving re-dispatches one cached executable
regardless of batch size.  Padded query rows compute garbage and are
sliced off; padded catalog columns are False in every mask row.

Mega-catalog extensions (100k–1M entries, same single dispatch):

  * ``quant=True``    — the catalog block arrives int8 row-quantized
    (per-row scales in ``e2s``); the O(N) scan matmul accumulates in
    int32 on the int8 operands and rescales to fp32 ONCE at the top-k
    boundary.  4x fewer catalog bytes; on a memory-bandwidth-bound
    scan that is the speedup (benchmarks/roofline.py).  All integer
    dots are exact, so quantized results are bitwise-reproducible
    across the jnp, Pallas and oracle paths.
  * ``route_step_ivf_jit``     — two-level IVF-pruned search over a
    cell-packed catalog layout: coarse centroid scores select the
    top-``nprobe`` cells per query IN-PROGRAM, only those cells'
    blocks are gathered and scanned (O(nprobe * cell) instead of
    O(N)), and rows whose probed cells miss every filter match escape
    to the exact widened-kNN rung via ``lax.cond``.
  * ``route_step_sharded_jit`` — ``shard_map`` over a 1-D device mesh
    with the catalog axis sharded: each shard runs the SAME fused
    local scan + top-R, emits a sorted (B, R) carry with global
    indices and per-lane blend/cosine payloads, and an allreduce-style
    pairwise tree of the bitonic ``merge_topk`` (``tree_merge_topk``)
    reduces the carries — ties fold toward the lowest shard, so the
    result is bit-identical to the single-device program.

The pure-jnp semantic ground truth lives in ``kernels/ref.py``
(``ref.route_step`` incl. ``quant``/``allowed``, ``ref.route_step_ivf``);
parity is pinned by tests against both the oracle and the staged
numpy path in ``core/routing.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels.ref import quantize_rows
from repro.kernels.router_topk import (Q8_BLK_Q, router_topk_pallas,
                                       router_topk_q8_pallas,
                                       tree_merge_topk)

NEG_INF = float("-inf")


def _f32_matmuls(fn):
    """Trace ``fn`` with fp32 dots at full fp32 precision.  On the TPU
    an f32 dot defaults to one bf16 pass, which moves cosine and blend
    scores by ~1e-3 and reorders near-ties against the fp32 kernel and
    the staged reference; the routing matmuls are tiny next to the
    catalog stream, so exact fp32 costs little.  Integer (int8) dots
    are exact either way."""
    @functools.wraps(fn)
    def traced(*args, **kwargs):
        with jax.default_matmul_precision("float32"):
            return fn(*args, **kwargs)
    return traced


def _hier_topk(z, kk: int, chunk: int = 32):
    """Exact top-kk of (B, Np) via chunk-max pruning.

    XLA:CPU's TopK emitter costs ~O(elements) at a poor rate, while a
    plain max reduction is fast.  So: per-chunk maxima (one cheap
    reduce), keep the kk chunks with the largest maxima — any true
    top-kk element must live in one of them, since each excluded
    chunk's max is dominated by kk other chunks' maxima — gather those
    chunks, and run the expensive TopK over kk*chunk columns instead
    of Np.  Values are exact; index tie-breaks can differ from
    ``lax.top_k`` when equal values straddle chunk boundaries (same
    contract as the Pallas kernel's block merge).
    """
    B, Np = z.shape
    C = Np // chunk
    if kk > chunk or C <= kk or Np % chunk:
        return jax.lax.top_k(z, kk)
    m3 = z.reshape(B, C, chunk)
    mx = m3.max(axis=2)                                   # (B, C)
    _, cj = jax.lax.top_k(mx, kk)                         # (B, kk)
    sub = m3[jnp.arange(B)[:, None], cj]                  # (B, kk, chunk)
    v, p = jax.lax.top_k(sub.reshape(B, kk * chunk), kk)
    gi = jnp.take_along_axis(cj, p // chunk, axis=1) * chunk \
        + p % chunk
    return v, gi


def _knn_pallas(qn, embn, m1, k, blk_q, blk_n, interpret):
    """Mask-fused kNN through the Pallas kernel (TPU path).

    Shapes arrive bucket-padded (Q % blk_q == 0, N % blk_n == 0); only
    the feature axis still needs its 128-lane pad here.
    """
    Q, D = qn.shape
    N = embn.shape[0]
    dpad = (-D) % 128
    qnp = jnp.pad(qn, ((0, 0), (0, dpad)))
    ewp = jnp.pad(embn, ((0, 0), (0, dpad)))
    bias = jnp.zeros((1, N), jnp.float32)
    return router_topk_pallas(qnp, ewp, m1.astype(jnp.float32), bias, k,
                              blk_q=blk_q, blk_n=blk_n,
                              interpret=interpret)


def _knn_pallas_q8(q8, qs, e8, es, m1, k, blk_q, blk_n, interpret):
    """int8 mask-fused kNN through the quantized Pallas kernel.

    Zero-padding the int8 feature axis is exact (zero columns add
    nothing to the int32 dot), so the scales pass through unchanged.
    Query buckets below ``blk_q`` (the int8 tile's 32 rows) pad with
    all-masked rows, which surface as -inf and are sliced off.
    """
    Q, D = q8.shape
    N = e8.shape[0]
    dpad = (-D) % 128
    qpad = (-Q) % blk_q
    q8p = jnp.pad(q8, ((0, qpad), (0, dpad)))
    e8p = jnp.pad(e8, ((0, 0), (0, dpad)))
    qsp = jnp.pad(qs, ((0, qpad), (0, 0)))
    m1p = jnp.pad(m1.astype(jnp.float32), ((0, qpad), (0, 0)))
    bias = jnp.zeros((1, N), jnp.float32)
    vals, idx = router_topk_q8_pallas(q8p, e8p, qsp, es[None, :], m1p,
                                      bias, k, blk_q=blk_q, blk_n=blk_n,
                                      interpret=interpret)
    return vals[:Q], idx[:Q]


# ----------------------------------------------------------------------
# shared program pieces (dense / IVF / sharded variants)
# ----------------------------------------------------------------------

def _ladder(counts_table, ti, di, n_tt: int, n_dm: int):
    """Per-query mask rows and ladder counts: O(B) table gathers.

    Returns (ci combined-mask row, c_wide, has_primary, fi first
    non-empty fallback row, stage_f its FALLBACK_LADDER stage).
    """
    n_combo = n_tt * n_dm
    ci = ti * n_dm + di                                   # combined row
    c_wide = counts_table[ci]
    has_primary = c_wide > 0
    c_tt = counts_table[n_combo + ti]
    c_gen = counts_table[n_combo + n_tt]
    # first non-empty fallback rung (widened-kNN == the fused mask, so
    # it is empty for every fallback row by construction): task-type-
    # only -> generalist -> any(live)
    fi = jnp.where(c_tt > 0, n_combo + ti,
                   jnp.where(c_gen > 0, n_combo + n_tt,
                             n_combo + n_tt + 1))
    stage_f = jnp.where(c_tt > 0, 2,
                        jnp.where(c_gen > 0, 3, 4)).astype(jnp.int32)
    return ci, c_wide, has_primary, fi, stage_f


def _extras_matrix(T, fb, theta, ainv_flat, lpen, params, B, Np, *,
                   has_fb: bool, has_ad: bool, has_load: bool):
    """(B, Np) extra blend terms (feedback / bandit / load), or None.

    One matrix when any term is active; None costs nothing.  The same
    per-element formulas serve the dense program over the full
    catalog, the sharded program over each shard's local columns, and
    the IVF fallback branch over the packed layout.
    """
    extras = None
    if has_fb:
        extras = params[0] * fb
    if has_ad:
        ctx = jnp.concatenate(
            [T, jnp.ones((B, 1), jnp.float32)], axis=1)   # (B, Dc)
        mean = ctx @ theta.T                              # (B, Np)
        xx = (ctx[:, :, None] * ctx[:, None, :]).reshape(B, -1)
        var = xx @ ainv_flat.T                            # (B, Np)
        ucb = params[1] * (
            mean + params[2] * jnp.sqrt(jnp.maximum(var, 0.0)))
        extras = ucb if extras is None else extras + ucb
    if has_load:
        lrow = jnp.broadcast_to(-lpen[None, :], (B, Np))
        extras = lrow if extras is None else extras - lpen[None, :]
    if extras is not None:
        extras = jax.lax.optimization_barrier(extras)
    return extras


def _q8_cscore(w8, ws, e8e_rows, ese_rows):
    """Per-candidate quantized blend scores: exact int32 einsum at the
    <=R gathered columns, fp32 rescale — bitwise equal to gathering
    from the full quantized blend matrix."""
    acc = jnp.einsum("bm,brm->br", w8.astype(jnp.int32),
                     e8e_rows.astype(jnp.int32))
    return acc.astype(jnp.float32) * (ws * ese_rows)


def _quant_operands(e2, e2s, M: int):
    """Split the packed quantized catalog block into halves:
    (e8n, esn) unit-row half for the kNN, (e8e, ese) raw-metric half
    for the blend — scales as (Np,) columns of ``e2s``."""
    return (e2[:, :M], e2s[:, 0], e2[:, M:], e2s[:, 1])


# ----------------------------------------------------------------------
# dense single-device program
# ----------------------------------------------------------------------

@_f32_matmuls
def _route_step_body(e2, e2s, masks_table, counts_table, T, W, ti, di, fb,
                     theta, ainv_flat, lpen, params, *, k: int, r: int,
                     n_tt: int, n_dm: int, has_fb: bool,
                     has_ad: bool, has_load: bool, use_pallas: bool,
                     blk_q: int, blk_n: int, interpret: bool,
                     quant: bool = False):
    """Traced body of ``route_step_jit`` (same signature, un-jitted) —
    split out so ``analyze_step.analyze_route_step_jit`` can inline the
    whole routing step after the analyzer encoder inside ONE program
    instead of paying a second dispatch.

    The live catalog size is deliberately NOT a parameter: liveness is
    fully encoded in the mask table (padded columns are False in every
    row, including the live-catalog rung) and the zeroed e2 pad rows,
    so catalog growth within one 128-padded capacity bucket reuses the
    cached executable without recompiling.

    e2 (Np, 2M) catalog block ``[embn | emb]`` — unit-normalized rows
    for the cosine kNN next to the raw normalized-metric rows for the
    score blend, precomputed once per catalog by ``ops.py`` (zero rows
    beyond the live count).  With ``quant=True`` e2 is the int8
    row-quantized block and e2s (Np, 2) carries the per-row scales
    (col 0 = unit half, col 1 = raw half); the scan matmul then runs
    dequant-free on int8 with an int32 accumulator and ONE fp32
    rescale at the top-k boundary (e2s is a (1, 2) dummy otherwise).
    masks_table (n_tt*n_dm + n_tt + 2, Np) stacked
    boolean mask rows — every task-type x domain combination, then the
    fallback rungs (task-type-only rows, the generalist row, the
    live-catalog row); counts_table (rows,) i32 per-row population
    counts; T (Qp, M) kNN task vectors; W (Qp, M) scoring weights;
    ti/di (Qp,) per-query filter row indices; fb (Qp, Np) feedback
    bias (dummy when ``has_fb`` False); theta (Np, Dc) / ainv_flat
    (Np, Dc*Dc) bandit posterior (LinUCB; dummies when ``has_ad``
    False); lpen (Np,) pre-scaled load penalty (dummy when
    ``has_load`` False); params (3,) f32 traced scalars
    [feedback_weight, adaptive_weight, alpha].

    Returns a dict of (Qp,)/(Qp, R) arrays with R = max(k, r):
    ``model_idx``, ``score``, ``stage`` (0 = primary, 1.. = fallback
    ladder rung), ``similarity``, ``cand_idx``/``cand_score`` (ranked,
    -1/-inf padded), ``n_filtered``, ``n_candidates``.
    """
    bar = jax.lax.optimization_barrier
    Np, M2 = e2.shape
    M = M2 // 2
    B = T.shape[0]
    R = max(k, r)

    qn = T / (jnp.linalg.norm(T, axis=1, keepdims=True) + 1e-9)
    ci, c_wide, has_primary, fi, stage_f = _ladder(
        counts_table, ti, di, n_tt, n_dm)
    extras = _extras_matrix(T, fb, theta, ainv_flat, lpen, params, B,
                            Np, has_fb=has_fb, has_ad=has_ad,
                            has_load=has_load)
    if quant:
        e8n, esn, e8e, ese = _quant_operands(e2, e2s, M)
        q8, qs = quantize_rows(qn)
        w8, ws = quantize_rows(W)
    else:
        embn = e2[:, :M]
        emb = e2[:, M:]

    hp = has_primary[:, None]
    kmask = (jnp.arange(R) < k)[None, :]
    if use_pallas:
        # TPU structure: Pallas kernel for the kNN, one jnp top_k for
        # the fallback re-score (primary rows masked out of it)
        m1 = bar(masks_table[ci])
        if quant:
            vals, idx = _knn_pallas_q8(q8, qs, e8n, esn, m1, k,
                                       Q8_BLK_Q, blk_n, interpret)
        else:
            vals, idx = _knn_pallas(qn, embn, m1, k, blk_q, blk_n,
                                    interpret)
        finite = vals > NEG_INF
        idx_safe = jnp.where(finite, idx, 0)
        if quant:
            cscore = _q8_cscore(w8, ws, e8e[idx_safe], ese[idx_safe])
        else:
            cscore = jnp.einsum("bm,brm->br", W, emb[idx_safe])
        if extras is not None:
            cscore = cscore + jnp.take_along_axis(extras, idx_safe,
                                                  axis=1)
        cscore = jnp.where(finite, cscore, NEG_INF)
        cs, pos = jax.lax.top_k(cscore, k)
        cidx = jnp.take_along_axis(idx_safe, pos, axis=1)
        sim_p = jnp.take_along_axis(vals, pos[:, :1], axis=1)[:, 0]
        if R > k:
            cs = jnp.pad(cs, ((0, 0), (0, R - k)),
                         constant_values=NEG_INF)
            cidx = jnp.pad(cidx, ((0, 0), (0, R - k)))
        msel = masks_table[fi]
        if quant:
            acc_f = jax.lax.dot_general(
                w8, e8e, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.int32)
            blend_f = acc_f.astype(jnp.float32) * (ws * ese[None, :])
        else:
            blend_f = W @ emb.T
        if extras is not None:
            blend_f = blend_f + extras
        zf = jnp.where(hp, NEG_INF,
                       jnp.where(msel, blend_f, NEG_INF))
        fv, fidx = jax.lax.top_k(zf, R)
        fidx_safe = jnp.where(fv > NEG_INF, fidx, 0)
        if quant:
            f0 = fidx_safe[:, 0]
            sim_f = (qn * e8n[f0].astype(jnp.float32)).sum(axis=1) \
                * esn[f0]
        else:
            sim_f = (qn * embn[fidx_safe[:, 0]]).sum(axis=1)
        cand_score = jnp.where(hp, cs, fv)
        cand_idx = jnp.where(hp, cidx, fidx_safe).astype(jnp.int32)
    else:
        # XLA:CPU structure: primary rows rank masked COSINE (the
        # kNN), fallback rows rank their rung-masked BLEND — the two
        # matrices are disjoint per row, so ONE block-diagonal matmul
        # ([qn | 0] or [0 | W] against [embn | emb]) and ONE top_k
        # serve the kNN and the whole fallback ladder together
        zi = jnp.where(has_primary, ci, fi)
        zmask = bar(masks_table[zi])                      # (B, Np)
        if quant:
            xsel = jnp.concatenate(
                [jnp.where(hp, q8, 0), jnp.where(hp, 0, w8)], axis=1)
            acc = jax.lax.dot_general(
                xsel, e2, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.int32)         # (B, Np)
            rscale = jnp.where(hp, qs, ws)                # (B, 1)
            cscale = jnp.where(hp, esn[None, :], ese[None, :])
            zsrc = acc.astype(jnp.float32) * (rscale * cscale)
        else:
            xsel = jnp.concatenate(
                [jnp.where(hp, qn, 0.0), jnp.where(hp, 0.0, W)], axis=1)
            zsrc = xsel @ e2.T                            # (B, Np)
        if extras is not None:      # blend terms join fallback rows
            zsrc = zsrc + jnp.where(hp, 0.0, 1.0) * extras
        z = bar(jnp.where(zmask, zsrc, NEG_INF))
        vals, idx = bar(_hier_topk(z, R))
        finite = vals > NEG_INF
        idx_safe = jnp.where(finite, idx, 0)
        # primary candidates = the first k cosine-ranked positions;
        # their blended scores (computed at the k columns only, like
        # the staged gather) re-rank them in-program
        if quant:
            cscore = _q8_cscore(w8, ws, e8e[idx_safe], ese[idx_safe])
        else:
            cscore = jnp.einsum("bm,brm->br", W, emb[idx_safe])
        if extras is not None:
            cscore = cscore + jnp.take_along_axis(extras, idx_safe,
                                                  axis=1)
        cscore = jnp.where(finite & kmask, cscore, NEG_INF)
        cs, pos = jax.lax.top_k(cscore, R)
        cidx = jnp.take_along_axis(idx_safe, pos, axis=1)
        sim_p = jnp.take_along_axis(vals, pos[:, :1], axis=1)[:, 0]
        if quant:
            f0 = idx_safe[:, 0]
            sim_f = (qn * e8n[f0].astype(jnp.float32)).sum(axis=1) \
                * esn[f0]
        else:
            sim_f = (qn * embn[idx_safe[:, 0]]).sum(axis=1)
        cand_score = jnp.where(hp, cs, vals)
        cand_idx = jnp.where(hp, cidx, idx_safe).astype(jnp.int32)

    cand_idx = jnp.where(jnp.isfinite(cand_score), cand_idx, -1)
    nf = jnp.minimum(c_wide, k).astype(jnp.int32)
    return {
        "model_idx": cand_idx[:, 0],
        "score": cand_score[:, 0],
        "stage": jnp.where(has_primary, 0, stage_f).astype(jnp.int32),
        "similarity": jnp.where(has_primary, sim_p, sim_f),
        "cand_idx": cand_idx,
        "cand_score": cand_score,
        "n_filtered": jnp.where(has_primary, nf, 0).astype(jnp.int32),
        "n_candidates": jnp.where(has_primary, nf,
                                  counts_table[fi]).astype(jnp.int32),
    }


route_step_jit = jax.jit(
    _route_step_body,
    static_argnames=("k", "r", "n_tt", "n_dm", "has_fb",
                     "has_ad", "has_load", "use_pallas", "blk_q",
                     "blk_n", "interpret", "quant"))


# ----------------------------------------------------------------------
# IVF-pruned program: coarse centroid probe + packed-cell fine scan
# ----------------------------------------------------------------------

@functools.partial(
    jax.jit,
    static_argnames=("k", "r", "n_tt", "n_dm", "nprobe", "cap",
                     "has_fb", "has_ad", "has_load", "quant"))
@_f32_matmuls
def route_step_ivf_jit(e2, e2s, masks_table, counts_table, orig, cent,
                       T, W, ti, di, fb, theta, ainv_flat, lpen,
                       params, *, k: int, r: int, n_tt: int, n_dm: int,
                       nprobe: int, cap: int, has_fb: bool,
                       has_ad: bool, has_load: bool,
                       quant: bool = False):
    """IVF-pruned fused routing step over a CELL-PACKED catalog.

    ``ops.py`` permutes the catalog into contiguous equal-capacity
    cell blocks (``cap`` slots per cell, dead slots marked by
    ``orig < 0``); every catalog-shaped operand (e2/e2s, mask table
    columns, fb/theta/ainv/lpen) arrives in PACKED order, while
    ``counts_table`` keeps the TRUE full-catalog counts so the ladder
    semantics are untouched.  ``orig`` (Npk,) maps packed slots back
    to original catalog rows for the outputs; ``cent`` (C, M) is the
    unit-row centroid table.

    In-program, per query: rank all C centroids against the unit task
    vector, take the top-``nprobe`` cells, gather ONLY those cells'
    ``nprobe * cap`` packed slots, and run the mask-fused kNN + blend
    re-rank on the gathered sub-catalog — O(nprobe * cap) scan work
    instead of O(N).  Two escape hatches keep the ladder total:
    rows with an empty filter mask walk the usual fallback rungs, and
    rows whose PROBED cells miss every filter match re-score the
    exact full-mask blend (the widened-kNN rung, stage 1) — both
    inside one ``lax.cond`` whose full-catalog branch only executes
    when some row needs it.  Recall@k versus the exhaustive program
    is the ``nprobe`` knob; ``nprobe >= C`` is exhaustive.
    """
    B = T.shape[0]
    Npk = orig.shape[0]
    M = T.shape[1]
    C = cent.shape[0]
    Pn = min(nprobe, C)
    R = max(k, r)
    J = Pn * cap

    qn = T / (jnp.linalg.norm(T, axis=1, keepdims=True) + 1e-9)
    ci, c_wide, has_primary, fi, stage_f = _ladder(
        counts_table, ti, di, n_tt, n_dm)
    if quant:
        e8n, esn, e8e, ese = _quant_operands(e2, e2s, M)
        q8, qs = quantize_rows(qn)
        w8, ws = quantize_rows(W)

    # ---- coarse: rank centroids, select cells, gather their slots
    _, cells = jax.lax.top_k(qn @ cent.T, Pn)             # (B, Pn)
    gidx = (cells[:, :, None] * cap
            + jnp.arange(cap)[None, None, :]).reshape(B, J)
    valid = orig[gidx] >= 0                               # (B, J)
    mrow = masks_table[ci[:, None], gidx]                 # (B, J)

    # ---- fine: mask-fused kNN over the gathered sub-catalog only
    if quant:
        acc = jnp.einsum("bm,bjm->bj", q8.astype(jnp.int32),
                         e8n[gidx].astype(jnp.int32))
        sims = acc.astype(jnp.float32) * (qs * esn[gidx])
    else:
        sims = jnp.einsum("bm,bjm->bj", qn, e2[:, :M][gidx])
    z1 = jnp.where(mrow & valid, sims, NEG_INF)
    if J < k:
        z1 = jnp.pad(z1, ((0, 0), (0, k - J)), constant_values=NEG_INF)
        gidx = jnp.pad(gidx, ((0, 0), (0, k - J)))
    vals, pos = jax.lax.top_k(z1, k)                      # (B, k)
    finite = vals > NEG_INF
    pidx = jnp.take_along_axis(gidx, pos, axis=1)         # packed rows
    pidx_safe = jnp.where(finite, pidx, 0)
    has_knn = finite.any(axis=1)
    nf = finite.sum(axis=1).astype(jnp.int32)

    # ---- candidate re-rank at the k columns (gather-style extras)
    if quant:
        cscore = _q8_cscore(w8, ws, e8e[pidx_safe], ese[pidx_safe])
    else:
        cscore = jnp.einsum("bm,bkm->bk", W, e2[:, M:][pidx_safe])
    if has_fb:
        cscore = cscore + params[0] * jnp.take_along_axis(
            fb, pidx_safe, axis=1)
    if has_ad:
        ctx = jnp.concatenate([T, jnp.ones((B, 1), jnp.float32)],
                              axis=1)
        mean = jnp.einsum("bd,bkd->bk", ctx, theta[pidx_safe])
        xx = (ctx[:, :, None] * ctx[:, None, :]).reshape(B, -1)
        var = jnp.einsum("bd,bkd->bk", xx, ainv_flat[pidx_safe])
        cscore = cscore + params[1] * (
            mean + params[2] * jnp.sqrt(jnp.maximum(var, 0.0)))
    if has_load:
        cscore = cscore - lpen[pidx_safe]
    cscore = jnp.where(finite, cscore, NEG_INF)
    cs, cpos = jax.lax.top_k(cscore, k)
    cidx_pk = jnp.take_along_axis(pidx_safe, cpos, axis=1)
    sim_p = jnp.take_along_axis(vals, cpos[:, :1], axis=1)[:, 0]
    if R > k:
        cs = jnp.pad(cs, ((0, 0), (0, R - k)), constant_values=NEG_INF)
        cidx_pk = jnp.pad(cidx_pk, ((0, 0), (0, R - k)))

    # ---- escape hatch: count-0 ladder rows AND pruned-missed rows
    # (non-empty filter, no probed hit -> exact widened-kNN re-score).
    # One cond: the O(B, Npk) branch only runs when some row needs it.
    fsel = jnp.where(has_primary, ci, fi)
    fstage = jnp.where(has_primary, 1, stage_f).astype(jnp.int32)
    need = ~has_knn

    def _fallback(_):
        if quant:
            acc_f = jax.lax.dot_general(
                w8, e8e, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.int32)
            blend = acc_f.astype(jnp.float32) * (ws * ese[None, :])
        else:
            blend = W @ e2[:, M:].T
        extras = _extras_matrix(T, fb, theta, ainv_flat, lpen, params,
                                B, Npk, has_fb=has_fb, has_ad=has_ad,
                                has_load=has_load)
        if extras is not None:
            blend = blend + extras
        msel = masks_table[fsel]
        zf = jnp.where(need[:, None] & msel & (orig >= 0)[None, :],
                       blend, NEG_INF)
        fv, fpi = jax.lax.top_k(zf, R)
        fpi_safe = jnp.where(fv > NEG_INF, fpi, 0)
        if quant:
            f0 = fpi_safe[:, 0]
            fcos = (qn * e8n[f0].astype(jnp.float32)).sum(axis=1) \
                * esn[f0]
        else:
            fcos = (qn * e2[fpi_safe[:, 0], :M]).sum(axis=1)
        return fv, fpi_safe, fcos

    def _no_fallback(_):
        return (jnp.full((B, R), NEG_INF, jnp.float32),
                jnp.zeros((B, R), jnp.int32),
                jnp.zeros((B,), jnp.float32))

    fv, fpi, fcos = jax.lax.cond(need.any(), _fallback, _no_fallback,
                                 operand=None)

    hk = has_knn[:, None]
    cand_score = jnp.where(hk, cs, fv)
    cand_pk = jnp.where(hk, cidx_pk, fpi)
    cand_idx = jnp.where(jnp.isfinite(cand_score), orig[cand_pk],
                         -1).astype(jnp.int32)
    return {
        "model_idx": cand_idx[:, 0],
        "score": cand_score[:, 0],
        "stage": jnp.where(has_knn, 0, fstage).astype(jnp.int32),
        "similarity": jnp.where(has_knn, sim_p, fcos),
        "cand_idx": cand_idx,
        "cand_score": cand_score,
        "n_filtered": jnp.where(has_knn, nf, 0).astype(jnp.int32),
        "n_candidates": jnp.where(has_knn, nf,
                                  counts_table[fsel]).astype(jnp.int32),
    }


# ----------------------------------------------------------------------
# sharded program: shard_map over the catalog axis + merge_topk tree
# ----------------------------------------------------------------------

@functools.partial(
    jax.jit,
    static_argnames=("mesh", "axis", "k", "r", "n_tt", "n_dm",
                     "has_fb", "has_ad", "has_load", "quant"))
@_f32_matmuls
def route_step_sharded_jit(e2, e2s, masks_table, counts_table, T, W,
                           ti, di, fb, theta, ainv_flat, lpen, params,
                           *, mesh, axis: str, k: int, r: int,
                           n_tt: int, n_dm: int, has_fb: bool,
                           has_ad: bool, has_load: bool,
                           quant: bool = False):
    """Cross-device fused routing step: the catalog axis of every
    (.., N) operand is sharded over ``mesh[axis]``; the batch axis is
    replicated.  STILL one dispatch per routed batch — the collective
    lives inside the one jitted program.

    Per shard (``shard_map`` body): the SAME block-diagonal local scan
    as the dense jnp program (quantized when ``quant``) over the
    shard's n_loc columns, a local exact top-R, then per-lane payloads
    computed LOCALLY while the shard still owns its catalog columns —
    global index (shard offset + local position), the candidate blend
    score, and the lane's cosine.  An ``all_gather`` of the sorted
    (B, R) carries feeds ``tree_merge_topk`` — PR 5's bitonic
    ``merge_topk`` applied as an allreduce-style pairwise tree, ties
    folding toward the lowest shard — so the merged lanes are exactly
    the single-device program's lanes, and the replicated finalize
    (candidate re-rank, fallback select, output masks) never touches
    catalog-sharded data again.  fp32 results are bit-identical to
    ``route_step_jit`` on untied scores; quantized results are
    bitwise-reproducible outright (exact integer dots).

    Shapes: identical to ``route_step_jit`` with Np divisible by
    ``mesh.shape[axis] * 128`` (``ops.n_bucket_sharded``).
    """
    Np = e2.shape[0]
    M = T.shape[1]
    B = T.shape[0]
    R = max(k, r)
    bar = jax.lax.optimization_barrier

    qn = T / (jnp.linalg.norm(T, axis=1, keepdims=True) + 1e-9)
    ci, c_wide, has_primary, fi, stage_f = _ladder(
        counts_table, ti, di, n_tt, n_dm)
    hp = has_primary[:, None]
    zi = jnp.where(has_primary, ci, fi)

    def _shard(e2_l, e2s_l, masks_l, fb_l, th_l, ai_l, lp_l, T, qn,
               W, zi, hpv, params):
        n_loc = e2_l.shape[0]
        hp = hpv[:, None]
        off = (jax.lax.axis_index(axis) * n_loc).astype(jnp.int32)
        extras = _extras_matrix(T, fb_l, th_l, ai_l, lp_l, params, B,
                                n_loc, has_fb=has_fb, has_ad=has_ad,
                                has_load=has_load)
        zmask = bar(masks_l[zi])                          # (B, n_loc)
        if quant:
            e8n, esn, e8e, ese = _quant_operands(e2_l, e2s_l, M)
            q8, qs = quantize_rows(qn)
            w8, ws = quantize_rows(W)
            xsel = jnp.concatenate(
                [jnp.where(hp, q8, 0), jnp.where(hp, 0, w8)], axis=1)
            acc = jax.lax.dot_general(
                xsel, e2_l, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.int32)
            rscale = jnp.where(hp, qs, ws)
            cscale = jnp.where(hp, esn[None, :], ese[None, :])
            zsrc = acc.astype(jnp.float32) * (rscale * cscale)
        else:
            embn_l = e2_l[:, :M]
            emb_l = e2_l[:, M:]
            xsel = jnp.concatenate(
                [jnp.where(hp, qn, 0.0), jnp.where(hp, 0.0, W)],
                axis=1)
            zsrc = xsel @ e2_l.T
        if extras is not None:
            zsrc = zsrc + jnp.where(hp, 0.0, 1.0) * extras
        z = bar(jnp.where(zmask, zsrc, NEG_INF))
        # NOTE: no barrier around the top_k here — XLA:CPU's
        # TopkDecomposer aborts on an opt-barrier between a TopK and
        # its users inside an SPMD-partitioned computation
        vals, pos = _hier_topk(z, R)                      # local top-R
        finite = vals > NEG_INF
        pos_safe = jnp.where(finite, pos, 0)
        gidx = jnp.where(finite, off + pos, -1)
        # per-lane payloads, computed while the columns are local:
        # candidate blend score + lane cosine (the finalize gathers
        # are impossible post-merge — no shard owns the whole catalog)
        if quant:
            csc = _q8_cscore(w8, ws, e8e[pos_safe], ese[pos_safe])
            cos = (qn[:, None, :] * e8n[pos_safe].astype(jnp.float32)
                   ).sum(axis=-1) * esn[pos_safe]
        else:
            csc = jnp.einsum("bm,brm->br", W, emb_l[pos_safe])
            cos = (qn[:, None, :] * embn_l[pos_safe]).sum(axis=-1)
        if extras is not None:
            csc = csc + jnp.take_along_axis(extras, pos_safe, axis=1)
        # ---- cross-shard reduction: pairwise merge_topk tree over
        # the gathered sorted carries (ties -> lowest shard, matching
        # the single-device top_k contract)
        g = jax.lax.all_gather((vals, gidx, csc, cos), axis)
        mv, (mi, mc, ms) = tree_merge_topk(g[0], (g[1], g[2], g[3]))
        return mv, mi, mc, ms

    vals, idx, csc, cos = jax.shard_map(
        _shard, mesh=mesh,
        in_specs=(P(axis, None),
                  P(axis, None) if quant else P(None, None),
                  P(None, axis),
                  P(None, axis) if has_fb else P(None, None),
                  P(axis, None) if has_ad else P(None, None),
                  P(axis, None) if has_ad else P(None, None),
                  P(axis) if has_load else P(None),
                  P(), P(), P(), P(), P(), P()),
        out_specs=(P(), P(), P(), P()),
        check_vma=False,
    )(e2, e2s, masks_table, fb, theta, ainv_flat, lpen,
      T, qn, W, zi, has_primary, params)

    # ---- replicated finalize: identical to the dense jnp tail
    kmask = (jnp.arange(R) < k)[None, :]
    finite = vals > NEG_INF
    idx_safe = jnp.where(finite, idx, 0)
    cscore = jnp.where(finite & kmask, csc, NEG_INF)
    cs, pos = jax.lax.top_k(cscore, R)
    cidx = jnp.take_along_axis(idx_safe, pos, axis=1)
    sim_p = jnp.take_along_axis(vals, pos[:, :1], axis=1)[:, 0]
    sim_f = cos[:, 0]
    cand_score = jnp.where(hp, cs, vals)
    cand_idx = jnp.where(hp, cidx, idx_safe).astype(jnp.int32)

    cand_idx = jnp.where(jnp.isfinite(cand_score), cand_idx, -1)
    nf = jnp.minimum(c_wide, k).astype(jnp.int32)
    return {
        "model_idx": cand_idx[:, 0],
        "score": cand_score[:, 0],
        "stage": jnp.where(has_primary, 0, stage_f).astype(jnp.int32),
        "similarity": jnp.where(has_primary, sim_p, sim_f),
        "cand_idx": cand_idx,
        "cand_score": cand_score,
        "n_filtered": jnp.where(has_primary, nf, 0).astype(jnp.int32),
        "n_candidates": jnp.where(has_primary, nf,
                                  counts_table[fi]).astype(jnp.int32),
    }
