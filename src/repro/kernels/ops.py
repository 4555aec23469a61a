"""Public jit'd wrappers around the Pallas kernels.

Each op pads/normalizes inputs to kernel-friendly (128-aligned) shapes,
invokes the kernel, and slices back.  ``interpret`` defaults to True off
TPU (the kernels execute under the Pallas interpreter on CPU — that is
how this repo validates them); on a real TPU backend it defaults to
compiled mode.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.analyze_step import (analyze_route_step_jit,
                                        analyze_step_jit)
from repro.kernels.bandit_update import bandit_update_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.moe_gating import moe_gating_pallas
from repro.kernels.route_step import (route_step_ivf_jit, route_step_jit,
                                      route_step_sharded_jit)
from repro.kernels.router_topk import (Q8_BLK_Q, router_topk_pallas,
                                       router_topk_q8_pallas)
from repro.kernels.ssd_scan import ssd_scan_pallas
from repro.obs.trace import span

LANE = 128


def default_interpret() -> bool:
    """Interpret mode for the CPU test path (no TPU backend); on a TPU
    the wrappers compile the kernels.  The raw ``*_pallas`` entries
    take no default — their callers state the mode."""
    return jax.default_backend() != "tpu"


def _pad_to(x, mult: int, axis: int):
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _clamp_blk_n(blk_n: int, n: int) -> int:
    """Shrink a catalog block size toward n (rounded up to a power of
    two, floored at one 128 lane) so tiny catalogs are one block."""
    return min(blk_n, max(1 << max(n - 1, 1).bit_length(), 128))


# ----------------------------------------------------------------------
# shape buckets: recompile-free serving-time dispatch
# ----------------------------------------------------------------------
# A jitted program compiles once per input-shape tuple, and a serving
# stream carries every batch size between 1 and the engine's cap.  The
# bucket policy trades a bounded amount of padded compute for a
# bounded, quickly-warmed set of executables:
#   * query axis  -> power-of-two buckets (floor 8): log2(Bmax) shapes
#     cover every batch size, and the pad waste is < 2x;
#   * catalog axis -> the catalog's 128-lane-aligned capacity: batch
#     size never touches it, so it only recompiles when the catalog
#     itself grows (model registration / merging).
# Bucket-padded rows/columns are masked out of every stage, never
# selected, and sliced off the outputs.

def q_bucket(q: int) -> int:
    """Power-of-two query-axis bucket (floor 8)."""
    return max(8, 1 << max(q - 1, 1).bit_length())


def n_bucket(n: int) -> int:
    """128-lane-aligned catalog-axis capacity (floor 128)."""
    return max(128, -(-n // LANE) * LANE)


def n_bucket_sharded(n: int, ndev: int) -> int:
    """Catalog capacity for the mesh-sharded path: every shard gets an
    equal 128-lane-aligned slice, so the bucket is the next multiple
    of ``ndev * 128``."""
    step = ndev * LANE
    return max(step, -(-n // step) * step)


# dispatch/compile counters for the bucketed serving-path ops —
# ``route_step`` also reports each call's (1 dispatch, compile delta)
# straight to an attached Telemetry, so concurrent routing threads
# never misattribute each other's activity.  A "dispatch" counts one
# fused-op invocation (each issues exactly one jitted call); the
# compile counter is the real recompilation guard.
from repro.analysis.sanitize import make_lock as _make_lock

_STATS = {"route_step_dispatches": 0, "route_step_compiles": 0,
          "topk_dispatches": 0, "topk_compiles": 0,
          "analyze_step_dispatches": 0, "analyze_step_compiles": 0}
_STATS_LOCK = _make_lock("ops.stats")


def route_step_stats() -> dict:
    """Copy of the bucketed-dispatch counters."""
    with _STATS_LOCK:
        return dict(_STATS)


def reset_route_step_stats() -> None:
    with _STATS_LOCK:
        for k in _STATS:
            _STATS[k] = 0


def _bump(kind: str, compiles: int) -> None:
    with _STATS_LOCK:
        _STATS[f"{kind}_dispatches"] += 1
        _STATS[f"{kind}_compiles"] += compiles


# optional device-cost profiler hook (obs.profile.DeviceCostProfiler):
# when attached, route_step hands it each shape bucket's bound jitted
# call once so it can read compiled.cost_analysis() — one extra compile
# per NEW bucket while attached, zero steady-state cost when detached
_COST_PROFILER = None


def set_cost_profiler(profiler) -> None:
    """Attach (or detach with ``None``) a per-bucket cost profiler."""
    global _COST_PROFILER
    _COST_PROFILER = profiler


# optional recompile hook (analysis.sanitize.RecompileSentinel): when
# attached, route_step reports every dispatch's shape-bucket signature
# and jit cache-miss delta so the sentinel can fail tests that
# recompile an already-warm bucket.  Same shape as the cost-profiler
# hook: module global, None when detached, zero hot-path cost.
_RECOMPILE_HOOK = None


def set_recompile_hook(hook) -> None:
    """Attach (or detach with ``None``) a per-dispatch recompile hook.

    The hook is called as ``hook(event)`` with ``event = {"path",
    "q_bucket", "n_bucket", "quant", "shards", "compiles"}`` after
    every ``route_step`` dispatch, and likewise after every
    ``analyze_step`` dispatch (``path="analyze"``, ``n_bucket`` = the
    token axis, ``quant`` = analyzer int8) and every fused
    ``analyze_route_step`` dispatch (``path="fused"``, ``quant`` =
    ``(catalog_int8, analyzer_int8)`` — both axes change the compiled
    program, so both belong to the shape-bucket signature)."""
    global _RECOMPILE_HOOK
    _RECOMPILE_HOOK = hook


_DUMMIES = None


def _dummies():
    """Cached device-resident placeholders for inactive blend terms
    ((1, 1) matrix, (1,) vector) — rebuilding + re-transferring them
    per dispatch is measurable on the serving hot path."""
    global _DUMMIES
    if _DUMMIES is None:
        _DUMMIES = (jnp.zeros((1, 1), jnp.float32),
                    jnp.zeros((1,), jnp.float32))
    return _DUMMIES


def _count_compiles(jit_fn, call):
    """Run ``call()`` and return (result, new jit-cache entries), read
    from the jit function's ``_cache_size``."""
    before = jit_fn._cache_size()
    out = call()
    return out, max(0, jit_fn._cache_size() - before)


# the padded catalog constants are identical across every batch routed
# against one MRES snapshot; cache them keyed on the snapshot's
# embedding-array identity.  Entries hold the source array by WEAK
# reference: when the catalog grows, MRES rebuilds its embedding
# matrix, the old one dies, and the stale multi-MB padded copies are
# evicted on the next pack call instead of pinning one near-identical
# padded bucket per historical catalog size (at 1M entries each copy
# is ~GB).  The weakref also makes id-reuse safe: a dead entry whose
# id() is recycled by a NEW array can never be returned, because its
# referent is gone before the id can repeat.
import weakref as _weakref

_CATALOG_CACHE: "list" = []             # [(key, weakref(emb), packed)]
_CATALOG_CACHE_MAX = 4


def catalog_cache_info() -> dict:
    """Live-entry view of the padded-constant cache (tests/debug):
    ``entries`` live packs, ``keys`` their (id, variant...) keys."""
    with _STATS_LOCK:
        live = [(k2, wr) for (k2, wr, _) in _CATALOG_CACHE
                if wr() is not None]
    return {"entries": len(live), "keys": [k2 for k2, _ in live]}


def reset_catalog_cache() -> None:
    with _STATS_LOCK:
        _CATALOG_CACHE.clear()


def _cache_lookup(key):
    """Return the cached pack for ``key`` (and drop dead entries)."""
    with _STATS_LOCK:
        _CATALOG_CACHE[:] = [e for e in _CATALOG_CACHE
                             if e[1]() is not None]
        for k2, _, packed in _CATALOG_CACHE:
            if k2 == key:
                return packed
    return None


def _cache_put(key, emb, packed):
    with _STATS_LOCK:
        _CATALOG_CACHE.append((key, _weakref.ref(emb), packed))
        while len(_CATALOG_CACHE) > _CATALOG_CACHE_MAX:
            _CATALOG_CACHE.pop(0)


def _quantize_rows_np(x: np.ndarray):
    """numpy twin of ``ref.quantize_rows`` (same per-row symmetric
    int8 contract, round-half-even): q int8, s (rows, 1) f32 with
    x ~= q * s.  Bitwise-identical to the jnp version on equal f32
    input — both divide by the same f32 scale and round half-even —
    so host-packed catalogs and in-program query quantization agree.
    """
    x = np.asarray(x, np.float32)
    amax = np.max(np.abs(x), axis=1, keepdims=True)
    s = np.where(amax > 0, amax / np.float32(127.0),
                 np.float32(1.0)).astype(np.float32)
    q = np.clip(np.rint(x / s), -127, 127).astype(np.int8)
    return q, s


def _mask_table(tt, dm, gmask, n: int, np_pad: int):
    """The stacked hierarchical-filter table at width ``np_pad``:
    task-type x domain combinations, task-type-only rows, generalist
    row, live-catalog row.  Padded columns are False in every row."""
    pad = np_pad - n
    ttp = np.pad(np.asarray(tt, bool), ((0, 0), (0, pad)))
    dmp = np.pad(np.asarray(dm, bool), ((0, 0), (0, pad)))
    combo = (ttp[:, None, :] & dmp[None, :, :]).reshape(-1, np_pad)
    live = np.zeros(np_pad, bool)
    live[:n] = True
    return np.vstack([combo, ttp,
                      np.pad(np.asarray(gmask, bool), (0, pad))[None],
                      live[None]])


def _catalog_blocks(emb: np.ndarray, np_pad: int, quant: bool):
    """(e2, e2s) numpy blocks: ``[embn | emb]`` f32, or the int8
    row-quantized pair when ``quant`` (e2s (Np, 2) per-row scales,
    col 0 = unit half, col 1 = raw half; dummy (1, 2) otherwise)."""
    n = emb.shape[0]
    pad = np_pad - n
    embf = emb.astype(np.float32)
    embn = embf / (np.linalg.norm(embf, axis=1, keepdims=True) + 1e-9)
    if not quant:
        e2 = np.pad(np.concatenate([embn, embf], axis=1),
                    ((0, pad), (0, 0)))
        return e2, np.zeros((1, 2), np.float32)
    q8n, sn = _quantize_rows_np(embn)
    q8e, se = _quantize_rows_np(embf)
    e2 = np.pad(np.concatenate([q8n, q8e], axis=1), ((0, pad), (0, 0)))
    e2s = np.pad(np.concatenate([sn, se], axis=1), ((0, pad), (0, 0)))
    return e2, e2s


def _catalog_pack(emb: np.ndarray, tt: np.ndarray, dm: np.ndarray,
                  gmask: np.ndarray, np_pad: int, *,
                  quant: bool = False, mesh=None, axis: str = ""):
    """Padded device constants for ``route_step``:
    (e2, e2s, masks_table, counts_table).

    The hierarchical-filter structure is flattened into ONE stacked
    boolean table plus per-row population counts, so the device
    program resolves per-query masks AND every ladder count as O(B)
    row gathers instead of (B, N) boolean algebra (see
    ``_mask_table``).  The catalog block pairs the unit-normalized
    rows (cosine kNN) with the raw normalized-metric rows (score
    blend) so the per-batch program does no catalog-side
    normalization work; with ``quant`` both halves are int8
    row-quantized with their scales in e2s.  With ``mesh`` the
    catalog-axis operands are device_put under their PartitionSpecs
    (e2/e2s row-sharded, mask table column-sharded) so the sharded
    program never re-lays them out per batch.
    """
    key = (id(emb), np_pad, bool(quant),
           id(mesh) if mesh is not None else None)
    packed = _cache_lookup(key)
    if packed is not None:
        return packed
    n = emb.shape[0]
    table = _mask_table(tt, dm, gmask, n, np_pad)
    e2, e2s = _catalog_blocks(emb, np_pad, quant)
    counts = table.sum(axis=1).astype(np.int32)
    if mesh is None:
        packed = (jnp.asarray(e2), jnp.asarray(e2s),
                  jnp.asarray(table), jnp.asarray(counts))
    else:
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as _P
        from repro.sharding.rules import route_step_specs
        specs = route_step_specs(mesh)
        put = jax.device_put
        packed = (
            put(e2, NamedSharding(mesh, specs["e2"])),
            put(e2s, NamedSharding(mesh, specs["e2s"] if quant
                                   else _P(None, None))),
            put(table, NamedSharding(mesh, specs["masks_table"])),
            put(counts, NamedSharding(mesh, specs["counts_table"])),
        )
    _cache_put(key, emb, packed)
    return packed


def _catalog_pack_ivf(emb: np.ndarray, tt: np.ndarray, dm: np.ndarray,
                      gmask: np.ndarray, cent: np.ndarray,
                      cell_of: np.ndarray, *, quant: bool = False):
    """Cell-packed catalog constants for ``route_step_ivf_jit``:
    (e2, e2s, masks_table, counts_table, orig, cent_d, orig_np, cap).

    Permutes the catalog into contiguous equal-capacity cell blocks
    (``cap`` = max cell size rounded up to 8 slots; dead slots carry
    ``orig == -1``, zero embedding rows, and all-False mask columns)
    so the device program turns "scan the top-nprobe cells" into ONE
    contiguous-stride gather of ``nprobe * cap`` slots.  The counts
    table keeps the TRUE full-catalog populations — ladder semantics
    must not see packing artifacts.
    """
    key = (id(emb), "ivf", id(cent), bool(quant))
    packed = _cache_lookup(key)
    if packed is not None:
        return packed
    n, m = emb.shape
    C = cent.shape[0]
    cell_of = np.asarray(cell_of, np.int64)
    sizes = np.bincount(cell_of, minlength=C)
    cap = max(8, int(-(-int(sizes.max()) // 8) * 8))
    npk = C * cap
    order = np.argsort(cell_of, kind="stable")
    starts = np.concatenate([[0], np.cumsum(sizes)[:-1]])
    pos_in_cell = np.arange(n) - starts[cell_of[order]]
    orig = np.full(npk, -1, np.int64)
    orig[cell_of[order] * cap + pos_in_cell] = order
    valid = orig >= 0
    osafe = np.where(valid, orig, 0)

    table = _mask_table(tt, dm, gmask, n, n)
    counts = table.sum(axis=1).astype(np.int32)
    tablepk = table[:, osafe] & valid[None, :]
    e2, e2s = _catalog_blocks(emb, n, quant)
    e2pk = e2[osafe] * valid[:, None].astype(e2.dtype)
    e2spk = e2s[osafe] * valid[:, None] if quant else e2s
    packed = (jnp.asarray(e2pk), jnp.asarray(e2spk),
              jnp.asarray(tablepk), jnp.asarray(counts),
              jnp.asarray(orig.astype(np.int32)),
              jnp.asarray(np.asarray(cent, np.float32)),
              orig.astype(np.int32), cap)
    _cache_put(key, emb, packed)
    return packed


# ----------------------------------------------------------------------
# router_topk
# ----------------------------------------------------------------------

def router_topk(emb, queries, k: int,
                mask: Optional[jnp.ndarray] = None,
                weights: Optional[jnp.ndarray] = None,
                row_bias: Optional[jnp.ndarray] = None,
                min_score: Optional[float] = None, *,
                blk_q: int = 8, blk_n: int = 512,
                quant: bool = False,
                interpret: Optional[bool] = None
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Weighted-cosine top-k over the catalog (see kernels/ref.py).

    emb (N, D); queries (Q, D); mask (N,) or (Q, N) bool — a 2-D mask
    gives every query its own hierarchical-filter row (the batched
    routing path fuses task-type & domain masks here); weights (D,);
    row_bias (N,) f32 — additive per-catalog-row score term fused into
    the scoring matmul, applied to mask-valid rows only; min_score —
    static score floor fused after mask + bias (the semantic cache's
    similarity threshold): rows below it surface as -inf.
    ``quant`` routes through the int8 kernel: catalog and query rows
    are symmetrically row-quantized (``ref.quantize_rows``) and the
    scoring matmul accumulates int8 x int8 in int32, rescaling to
    fp32 once at the top-k boundary — 4x fewer catalog bytes moved.
    Returns (vals (Q, k) f32, idx (Q, k) i32).  Masked / padded /
    sub-threshold rows surface as vals == -inf, as does the tail when
    k > N.
    """
    emb = jnp.asarray(emb, jnp.float32)
    queries = jnp.asarray(queries, jnp.float32)
    N, D = emb.shape
    Q = queries.shape[0]
    interp = default_interpret() if interpret is None else interpret
    blk_n = _clamp_blk_n(blk_n, N)
    if quant:
        blk_q = Q8_BLK_Q                # the int8 tile's query rows

    # fold weights + row norms into the catalog; unit-normalize queries
    en = jnp.linalg.norm(emb, axis=1, keepdims=True) + 1e-9
    ew = emb * (jnp.asarray(weights, jnp.float32)[None, :]
                if weights is not None else 1.0) / en
    qn = queries / (jnp.linalg.norm(queries, axis=1, keepdims=True) + 1e-9)

    maskf = (jnp.asarray(mask, jnp.float32) if mask is not None
             else jnp.ones((N,), jnp.float32))
    maskf = jnp.broadcast_to(maskf, (Q, N)) if maskf.ndim == 1 else maskf
    biasf = (jnp.asarray(row_bias, jnp.float32)[None, :]
             if row_bias is not None else jnp.zeros((1, N), jnp.float32))
    maskp = _pad_to(_pad_to(maskf, blk_n, 1), blk_q, 0)      # pad -> 0 -> -inf
    biasp = _pad_to(biasf, blk_n, 1)
    ms = float("-inf") if min_score is None else float(min_score)

    if quant:
        from repro.kernels.ref import quantize_rows
        e8, es = quantize_rows(ew)
        q8, qs = quantize_rows(qn)
        e8p = _pad_to(_pad_to(e8, LANE, 1), blk_n, 0)
        q8p = _pad_to(_pad_to(q8, LANE, 1), blk_q, 0)
        esp = _pad_to(es, blk_n, 0).T                        # (1, Np)
        qsp = _pad_to(qs, blk_q, 0)                          # (Qp, 1)
        vals, idx = router_topk_q8_pallas(
            q8p, e8p, qsp, esp, maskp, biasp, k, blk_q=blk_q,
            blk_n=blk_n, min_score=ms, interpret=interp)
        return vals[:Q], idx[:Q]

    ewp = _pad_to(_pad_to(ew, LANE, 1), blk_n, 0)
    qnp = _pad_to(_pad_to(qn, LANE, 1), blk_q, 0)
    vals, idx = router_topk_pallas(
        qnp, ewp, maskp, biasp, k, blk_q=blk_q, blk_n=blk_n,
        min_score=ms, interpret=interp)
    return vals[:Q], idx[:Q]


def router_topk_bucketed(emb, queries, k: int,
                         mask: Optional[np.ndarray] = None,
                         min_score: Optional[float] = None, *,
                         quant: bool = False,
                         interpret: Optional[bool] = None
                         ) -> Tuple[np.ndarray, np.ndarray]:
    """``router_topk`` behind the serving-time shape buckets.

    Pads the query axis up to its power-of-two bucket (a 2-D mask pads
    with all-False rows, so bucket rows surface as -inf and are sliced
    off) before dispatching, so a stream of varying batch sizes against
    a fixed store (e.g. the semantic cache's packed capacity) re-uses
    one compiled executable per bucket instead of recompiling per
    batch size.  Counts land in ``route_step_stats`` under ``topk_*``.
    """
    queries = np.asarray(queries, np.float32)
    Q = queries.shape[0]
    qp = q_bucket(Q)
    if qp != Q:
        queries = np.pad(queries, ((0, qp - Q), (0, 0)))
        if mask is not None and np.ndim(mask) == 2:
            mask = np.pad(np.asarray(mask), ((0, qp - Q), (0, 0)))
    jit_fn = router_topk_q8_pallas if quant else router_topk_pallas
    (vals, idx), compiles = _count_compiles(
        jit_fn,
        lambda: router_topk(emb, queries, k, mask=mask,
                            min_score=min_score, quant=quant,
                            interpret=interpret))
    _bump("topk", compiles)
    return vals[:Q], idx[:Q]


# ----------------------------------------------------------------------
# route_step: the fused single-dispatch routing hot path
# ----------------------------------------------------------------------

def route_step(emb, tt_matrix, dm_matrix, gmask, T, W, ti, di, *,
               k: int, r: int,
               fb: Optional[np.ndarray] = None, fb_weight: float = 0.0,
               theta: Optional[np.ndarray] = None,
               ainv: Optional[np.ndarray] = None, alpha: float = 0.0,
               ad_weight: float = 0.0,
               lpen: Optional[np.ndarray] = None,
               use_pallas: bool = False,
               interpret: Optional[bool] = None,
               quant: bool = False, mesh=None,
               ivf=None, nprobe: int = 8,
               telemetry=None, tracer=None) -> dict:
    """One fused routing step per batch (see ``kernels/route_step.py``).

    Pads the batch to its power-of-two Q bucket and the catalog to its
    128-aligned capacity (``q_bucket``/``n_bucket``), dispatches ONE
    jitted device program, and slices the (B,)/(B, R) outputs back.
    ``fb``/``theta``+``ainv``/``lpen`` are optional blend terms —
    absent terms cost nothing on device (their presence is a static
    flag, so toggling one recompiles once and then stays cached).
    Dispatch/compile counts land in ``route_step_stats``; an attached
    ``telemetry`` additionally receives THIS call's (1 dispatch,
    compile delta) directly, so concurrent callers never read each
    other's deltas out of the shared counters.  The dispatch runs in a
    ``route_step`` span (``obs.trace.span``: in the profiler's timeline,
    and in ``tracer``'s ring when one is given, carrying the selected
    path, shape bucket, quantization mode, shard count and compile
    delta); an attached cost profiler (see
    ``set_cost_profiler``) gets each NEW shape bucket's bound call to
    read ``compiled.cost_analysis()`` from.

    Mega-catalog knobs (all still ONE dispatch per batch):

    * ``quant``  — serve from the int8 row-quantized catalog block
      (int32 accumulate, one fp32 rescale at the top-k boundary).
    * ``mesh``   — a 1-D device mesh with a ``catalog`` axis
      (``launch.make_routing_mesh``): the catalog axis of every (.., N)
      operand is sharded across it and the cross-shard top-k merge
      tree runs inside the program.  fp32 results are bit-identical to
      the single-device program.
    * ``ivf``    — ``(centroids, cell_of)`` from ``MRES.ivf_index()``:
      two-level pruned search scanning only the top-``nprobe`` cells
      per query (recall@k knob; ``nprobe >= n_cells`` is exhaustive).
      Not yet composed with ``mesh``.
    """
    emb = np.asarray(emb, np.float32)
    T = np.asarray(T, np.float32)
    W = np.asarray(W, np.float32)
    n, m = emb.shape
    B = T.shape[0]
    assert 1 <= k <= n and 1 <= r <= n, (k, r, n)
    qp = q_bucket(B)
    interp = default_interpret() if interpret is None else interpret
    n_tt = np.asarray(tt_matrix).shape[0]
    n_dm = np.asarray(dm_matrix).shape[0]

    qpad = qp - B
    ti = np.asarray(ti, np.int32)
    di = np.asarray(di, np.int32)
    Tp, Wp, tip, dip = T, W, ti, di
    if qpad:
        Tp = np.pad(T, ((0, qpad), (0, 0)))
        Wp = np.pad(W, ((0, qpad), (0, 0)))
        # bucket rows get the trailing all-True mask rows: they compute
        # a harmless top-k over live columns and are sliced off below
        tip = np.pad(ti, (0, qpad), constant_values=n_tt - 1)
        dip = np.pad(di, (0, qpad), constant_values=n_dm - 1)

    dummy1 = _dummies()
    has_fb = fb is not None
    has_ad = theta is not None
    has_load = lpen is not None
    if has_ad:
        th = np.asarray(theta, np.float32)[:n]
        ai = np.asarray(ainv, np.float32)[:n].reshape(n, -1)
    params = np.array([fb_weight, ad_weight, alpha], np.float32)

    if ivf is not None:
        assert mesh is None, "IVF + mesh sharding is not composed yet"
        cent, cell_of = ivf
        (e2_d, e2s_d, masks_d, counts_d, orig_d, cent_d, orig_np,
         cap) = _catalog_pack_ivf(
            emb, tt_matrix, dm_matrix, gmask,
            np.asarray(cent, np.float32), cell_of, quant=quant)
        valid = orig_np >= 0
        osafe = np.where(valid, orig_np, 0)
        if has_fb:
            fbp = np.asarray(fb, np.float32)[:, osafe] * valid[None, :]
            if qpad:
                fbp = np.pad(fbp, ((0, qpad), (0, 0)))
        else:
            fbp = dummy1[0]
        thp = th[osafe] * valid[:, None] if has_ad else dummy1[0]
        aip = ai[osafe] * valid[:, None] if has_ad else dummy1[0]
        lpp = (np.asarray(lpen, np.float32)[:n][osafe] * valid) \
            if has_load else dummy1[1]
        jit_fn = route_step_ivf_jit
        call = functools.partial(
            route_step_ivf_jit,
            e2_d, e2s_d, masks_d, counts_d, orig_d, cent_d,
            Tp, Wp, tip, dip, fbp, thp, aip, lpp, params,
            k=k, r=r, n_tt=n_tt, n_dm=n_dm, nprobe=int(nprobe),
            cap=cap, has_fb=has_fb, has_ad=has_ad,
            has_load=has_load, quant=quant)
        path, n_pad, shards = "ivf", cap, 1
    elif mesh is not None:
        from repro.sharding.rules import CATALOG_AXIS
        ndev = mesh.shape[CATALOG_AXIS]
        np_pad = n_bucket_sharded(n, ndev)
        npad = np_pad - n
        e2_d, e2s_d, masks_d, counts_d = _catalog_pack(
            emb, tt_matrix, dm_matrix, gmask, np_pad, quant=quant,
            mesh=mesh, axis=CATALOG_AXIS)
        fbp = np.pad(np.asarray(fb, np.float32),
                     ((0, qpad), (0, npad))) if has_fb else dummy1[0]
        if has_ad:
            thp = np.pad(th, ((0, npad), (0, 0)))
            aip = np.pad(ai, ((0, npad), (0, 0)))
        else:
            thp = aip = dummy1[0]
        lpp = np.pad(np.asarray(lpen, np.float32)[:n], (0, npad)) \
            if has_load else dummy1[1]
        jit_fn = route_step_sharded_jit
        call = functools.partial(
            route_step_sharded_jit,
            e2_d, e2s_d, masks_d, counts_d, Tp, Wp, tip, dip,
            fbp, thp, aip, lpp, params, mesh=mesh,
            axis=CATALOG_AXIS, k=k, r=r, n_tt=n_tt, n_dm=n_dm,
            has_fb=has_fb, has_ad=has_ad, has_load=has_load,
            quant=quant)
        path, n_pad, shards = "sharded", np_pad, ndev
    else:
        np_pad = n_bucket(n)
        npad = np_pad - n
        blk_n = 512 if np_pad % 512 == 0 else LANE
        e2_d, e2s_d, masks_d, counts_d = _catalog_pack(
            emb, tt_matrix, dm_matrix, gmask, np_pad, quant=quant)
        fbp = np.pad(np.asarray(fb, np.float32),
                     ((0, qpad), (0, npad))) if has_fb else dummy1[0]
        if has_ad:
            thp = np.pad(th, ((0, npad), (0, 0)))
            aip = np.pad(ai, ((0, npad), (0, 0)))
        else:
            thp = aip = dummy1[0]
        lpp = np.pad(np.asarray(lpen, np.float32)[:n], (0, npad)) \
            if has_load else dummy1[1]
        jit_fn = route_step_jit
        call = functools.partial(
            route_step_jit,
            e2_d, e2s_d, masks_d, counts_d, Tp, Wp, tip, dip, fbp,
            thp, aip, lpp, params, k=k, r=r, n_tt=n_tt, n_dm=n_dm,
            has_fb=has_fb, has_ad=has_ad, has_load=has_load,
            use_pallas=use_pallas, blk_q=8, blk_n=blk_n,
            interpret=interp, quant=quant)
        path, n_pad, shards = "dense", np_pad, 1
    prof = _COST_PROFILER
    if prof is not None:
        prof.capture((path, qp, n_pad, quant, shards), jit_fn, call)
    with span(tracer, "route_step", path=path, batch=B, q_bucket=qp,
              n_bucket=n_pad, catalog_n=n, quant=quant,
              shards=shards) as sp:
        out, compiles = _count_compiles(jit_fn, call)
        sp.set(compiles=compiles)
    _bump("route_step", compiles)
    hook = _RECOMPILE_HOOK
    if hook is not None:
        hook({"path": path, "q_bucket": qp, "n_bucket": n_pad,
              "quant": quant, "shards": shards, "compiles": compiles})
    if telemetry is not None:
        telemetry.record_route_step(dispatches=1, compiles=compiles)
    out = jax.device_get(out)           # ONE host transfer for all
    return {key: v[:B] for key, v in out.items()}


# ----------------------------------------------------------------------
# analyze_step / analyze_route_step: the fused tokens->decision path
# ----------------------------------------------------------------------

def analyzer_quantized(params) -> bool:
    """True when the analyzer params pytree is int8-quantized —
    ``core.analyzer.quantize_int8`` turns every 2-D leaf into an
    ``(int8, scale)`` pair, ``embed`` always among them."""
    return isinstance(params.get("embed"), tuple)


def _fb_table_pack(fb_table, np_pad: int):
    """Device copy of the dense per-cluster feedback-bias table with
    its catalog axis padded to the capacity bucket — cached on the
    table's identity (``FeedbackStore.bias_table`` memoizes per store
    version, so the id is stable until feedback actually changes)."""
    key = (id(fb_table), "fbt", np_pad)
    packed = _cache_lookup(key)
    if packed is not None:
        return packed
    t = np.asarray(fb_table, np.float32)
    packed = jnp.asarray(np.pad(t, ((0, 0), (0, np_pad - t.shape[1]))))
    _cache_put(key, fb_table, packed)
    return packed


def analyze_step(params, cfg, tokens, *, telemetry=None,
                 tracer=None) -> dict:
    """Bucketed analyzer dispatch: ONE jitted program per (Q bucket,
    token length, config, params structure).

    tokens (B, L) int32, B >= 1 — padded up to the power-of-two query
    bucket with all-PAD rows (uniform heads, never read back).  Emits
    the same stats/hook/profiler/telemetry plumbing as ``route_step``
    under the ``analyze_step_*`` counters, with ``path="analyze"`` and
    the token axis as the bucket signature's ``n_bucket``.  Returns
    host numpy ``{tt_idx, dm_idx, cx, conf}`` arrays of length B.
    """
    tokens = np.asarray(tokens, np.int32)
    B, L = tokens.shape
    assert B >= 1, "analyze_step requires a non-empty batch"
    qp = q_bucket(B)
    if qp != B:
        tokens = np.pad(tokens, ((0, qp - B), (0, 0)))
    quant = analyzer_quantized(params)
    call = functools.partial(analyze_step_jit, params,
                             jnp.asarray(tokens), cfg=cfg)
    prof = _COST_PROFILER
    if prof is not None:
        prof.capture(("analyze", qp, L, quant, 1), analyze_step_jit,
                     call)
    with span(tracer, "analyze_step", path="analyze", batch=B,
              q_bucket=qp, n_bucket=L, quant=quant, shards=1) as sp:
        out, compiles = _count_compiles(analyze_step_jit, call)
        sp.set(compiles=compiles)
    _bump("analyze_step", compiles)
    hook = _RECOMPILE_HOOK
    if hook is not None:
        hook({"path": "analyze", "q_bucket": qp, "n_bucket": L,
              "quant": quant, "shards": 1, "compiles": compiles})
    if telemetry is not None:
        telemetry.record_analyze_step(dispatches=1, compiles=compiles)
    out = jax.device_get(out)           # ONE host transfer for all
    return {key: v[:B] for key, v in out.items()}


def analyze_route_step(params, cfg, tokens, emb, tt_matrix, dm_matrix,
                       gmask, W, *, k: int, r: int, threshold: float,
                       acc_col: int, use_complexity: bool = True,
                       fb_table=None, fb_buckets: int = 4,
                       fb_weight: float = 0.0,
                       theta: Optional[np.ndarray] = None,
                       ainv: Optional[np.ndarray] = None,
                       alpha: float = 0.0, ad_weight: float = 0.0,
                       lpen: Optional[np.ndarray] = None,
                       use_pallas: bool = False,
                       interpret: Optional[bool] = None,
                       quant: bool = False,
                       telemetry=None, tracer=None) -> dict:
    """ONE device dispatch from token ids to model choice per batch
    (see ``kernels/analyze_step.analyze_route_step_jit``).

    The analyzer operands ride ``route_step``'s dense-path recipe:
    tokens (B, L) pad to the power-of-two Q bucket with all-PAD rows,
    W (B, M) preference rows pad with zero rows, the catalog packs
    through the same padded-constant cache, and the confidence
    ``threshold`` ships as a traced scalar so tuning it never
    recompiles.  ``fb_table`` is ``FeedbackStore.bias_table(names)``
    ((n_tt * n_dm * fb_buckets, N) dense clusters); its padded device
    copy is cached on table identity.  Dense single-device only — the
    sharded/IVF mega-catalog paths keep the staged analyze.

    One dispatch feeds BOTH counter families (``route_step_*`` and
    ``analyze_step_*``), one ``path="fused"`` hook event whose
    ``quant`` field is ``(catalog_int8, analyzer_int8)``, and one
    ``route_step`` tracer span with an ``analyzer_quant`` attr.
    Returns host numpy ``route_step`` outputs plus ``tt_idx`` /
    ``dm_idx`` / ``cx`` / ``conf`` / ``task_vectors`` sliced to B.
    """
    tokens = np.asarray(tokens, np.int32)
    emb = np.asarray(emb, np.float32)
    W = np.asarray(W, np.float32)
    n, m = emb.shape
    B, L = tokens.shape
    assert B >= 1, "analyze_route_step requires a non-empty batch"
    assert 1 <= k <= n and 1 <= r <= n, (k, r, n)
    qp = q_bucket(B)
    interp = default_interpret() if interpret is None else interpret
    n_tt = np.asarray(tt_matrix).shape[0]
    n_dm = np.asarray(dm_matrix).shape[0]

    qpad = qp - B
    toksp, Wp = tokens, W
    if qpad:
        toksp = np.pad(tokens, ((0, qpad), (0, 0)))
        Wp = np.pad(W, ((0, qpad), (0, 0)))

    dummy1 = _dummies()
    has_fb = fb_table is not None
    has_ad = theta is not None
    has_load = lpen is not None
    np_pad = n_bucket(n)
    npad = np_pad - n
    blk_n = 512 if np_pad % 512 == 0 else LANE
    e2_d, e2s_d, masks_d, counts_d = _catalog_pack(
        emb, tt_matrix, dm_matrix, gmask, np_pad, quant=quant)
    fbt = _fb_table_pack(fb_table, np_pad) if has_fb else dummy1[0]
    if has_ad:
        thp = np.pad(np.asarray(theta, np.float32)[:n],
                     ((0, npad), (0, 0)))
        aip = np.pad(np.asarray(ainv, np.float32)[:n].reshape(n, -1),
                     ((0, npad), (0, 0)))
    else:
        thp = aip = dummy1[0]
    lpp = np.pad(np.asarray(lpen, np.float32)[:n], (0, npad)) \
        if has_load else dummy1[1]
    ascalars = np.array([threshold], np.float32)
    rparams = np.array([fb_weight, ad_weight, alpha], np.float32)
    aquant = analyzer_quantized(params)
    call = functools.partial(
        analyze_route_step_jit, params, jnp.asarray(toksp), Wp,
        ascalars, fbt, e2_d, e2s_d, masks_d, counts_d, thp, aip, lpp,
        rparams, cfg=cfg, acc_col=int(acc_col),
        use_complexity=bool(use_complexity),
        fb_buckets=int(fb_buckets), k=k, r=r, n_tt=n_tt, n_dm=n_dm,
        has_fb=has_fb, has_ad=has_ad, has_load=has_load,
        use_pallas=use_pallas, blk_q=8, blk_n=blk_n,
        interpret=interp, quant=quant)
    prof = _COST_PROFILER
    if prof is not None:
        prof.capture(("fused", qp, np_pad, (quant, aquant), 1),
                     analyze_route_step_jit, call)
    with span(tracer, "route_step", path="fused", batch=B, q_bucket=qp,
              n_bucket=np_pad, catalog_n=n, quant=quant,
              analyzer_quant=aquant, shards=1) as sp:
        out, compiles = _count_compiles(analyze_route_step_jit, call)
        sp.set(compiles=compiles)
    _bump("route_step", compiles)
    _bump("analyze_step", compiles)
    hook = _RECOMPILE_HOOK
    if hook is not None:
        hook({"path": "fused", "q_bucket": qp, "n_bucket": np_pad,
              "quant": (quant, aquant), "shards": 1,
              "compiles": compiles})
    if telemetry is not None:
        telemetry.record_route_step(dispatches=1, compiles=compiles)
        telemetry.record_analyze_step(dispatches=1, compiles=compiles)
    out = jax.device_get(out)           # ONE host transfer for all
    return {key: v[:B] for key, v in out.items()}


# ----------------------------------------------------------------------
# bandit_update
# ----------------------------------------------------------------------

def bandit_update(x_up, w, r, x_score, theta, ainv, alpha: float, *,
                  blk_n: int = 128, interpret: Optional[bool] = None
                  ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Fused bandit posterior delta + LinUCB scores (see kernels/ref.py).

    x_up (Bu, D) outcome contexts; w (Bu, N) choice mask; r (Bu,)
    rewards; x_score (Bs, D) incoming contexts; theta (N, D); ainv
    (N, D, D); alpha >= 0 exploration scale.  Returns
    (dA (N, D, D), db (N, D), ucb (Bs, N)) f32.

    Flattens the rank-1 structure into pure matmuls: outer products
    become (B, D^2) rows, alpha^2 is folded into Ainv, and everything is
    lane/sublane padded before ONE ``bandit_update_pallas`` call.
    """
    assert alpha >= 0.0, alpha
    x_up = jnp.asarray(x_up, jnp.float32)
    x_score = jnp.asarray(x_score, jnp.float32)
    w = jnp.asarray(w, jnp.float32)
    r = jnp.asarray(r, jnp.float32)
    theta = jnp.asarray(theta, jnp.float32)
    ainv = jnp.asarray(ainv, jnp.float32)
    N, D = theta.shape
    Bu, Bs = x_up.shape[0], x_score.shape[0]
    if Bu == 0:                       # empty outcome batch: zero deltas
        x_up = jnp.zeros((1, D), jnp.float32)
        w = jnp.zeros((1, N), jnp.float32)
        r = jnp.zeros((1,), jnp.float32)
    interp = default_interpret() if interpret is None else interpret
    blk_n = _clamp_blk_n(blk_n, N)

    xx_up = (x_up[:, :, None] * x_up[:, None, :]).reshape(x_up.shape[0], -1)
    xxs = (x_score[:, :, None] * x_score[:, None, :]).reshape(Bs, -1)
    xr = x_up * r[:, None]
    ainv2 = (alpha * alpha) * ainv.reshape(N, D * D)

    sub = 8                                              # f32 sublane
    wp = _pad_to(_pad_to(w, blk_n, 1), sub, 0)
    xxup_p = _pad_to(_pad_to(xx_up, LANE, 1), sub, 0)
    xr_p = _pad_to(_pad_to(xr, LANE, 1), sub, 0)
    xs_p = _pad_to(_pad_to(x_score, LANE, 1), sub, 0)
    xxs_p = _pad_to(_pad_to(xxs, LANE, 1), sub, 0)
    theta_p = _pad_to(_pad_to(theta, LANE, 1), blk_n, 0)
    ainv2_p = _pad_to(_pad_to(ainv2, LANE, 1), blk_n, 0)

    da, db, ucb = bandit_update_pallas(
        wp, xxup_p, xr_p, xs_p, xxs_p, theta_p, ainv2_p,
        blk_n=blk_n, interpret=interp)
    return (da[:N, :D * D].reshape(N, D, D), db[:N, :D], ucb[:Bs, :N])


# ----------------------------------------------------------------------
# flash attention
# ----------------------------------------------------------------------

def flash_attention(q, k, v, kv_valid=None, *, causal: bool = True,
                    window: int = 0,
                    softcap: float = 0.0, blk_q: int = 128,
                    blk_k: int = 128, interpret: Optional[bool] = None):
    """q (B, Lq, Hq, hd); k, v (B, Lk, Hkv, hd) — layer layout (L, H, hd).

    kv_valid (B,) int32: per-sequence live key count (decode mode).
    Pads hd to a 128 lane multiple (zero columns are exact for q.k^T and
    are sliced off the value output), transposes to kernel layout, runs
    the blocked flash kernel.  Returns (B, Lq, Hq, hd) in q.dtype.
    """
    interp = default_interpret() if interpret is None else interpret
    hd = q.shape[-1]
    qt = _pad_to(jnp.swapaxes(q, 1, 2), LANE, 3)
    kt = _pad_to(jnp.swapaxes(k, 1, 2), LANE, 3)
    vt = _pad_to(jnp.swapaxes(v, 1, 2), LANE, 3)
    # scale must use the true head_dim, not the padded one
    import math as _m
    scale_fix = _m.sqrt(qt.shape[-1] / hd)
    qt = qt * scale_fix  # kernel divides by sqrt(hd_padded); re-scale
    out = flash_attention_pallas(qt, kt, vt, kv_valid, causal=causal,
                                 window=window,
                                 softcap=softcap, blk_q=blk_q, blk_k=blk_k,
                                 interpret=interp)
    return jnp.swapaxes(out[..., :hd], 1, 2)


def flash_decode(q, k_cache, v_cache, pos, *, softcap: float = 0.0,
                 blk_k: int = 128, interpret: Optional[bool] = None):
    """Flash-decode: one query token against a partially-filled cache.

    q (B, 1, Hq, hd); k_cache/v_cache (B, C, Hkv, hd); pos (B,) int32 —
    the current token index (keys at slots <= pos are live, matching
    models/layers.attention_decode).  Returns (B, 1, Hq, hd).
    """
    return flash_attention(q, k_cache, v_cache, pos + 1, causal=False,
                           softcap=softcap, blk_k=blk_k,
                           interpret=interpret)


# ----------------------------------------------------------------------
# SSD scan
# ----------------------------------------------------------------------

def ssd_scan(x, dt, A, B, C, h0=None, *, chunk: int = 128,
             interpret: Optional[bool] = None):
    """Chunked SSD scan (see kernels/ref.py::ssd_scan for semantics)."""
    interp = default_interpret() if interpret is None else interpret
    return ssd_scan_pallas(x, dt, A, B, C, h0, chunk=chunk,
                           interpret=interp)


# ----------------------------------------------------------------------
# MoE gating
# ----------------------------------------------------------------------

def moe_gating(logits, k: int, *, blk_t: int = 256,
               interpret: Optional[bool] = None):
    """Fused softmax top-k gate. logits (T, E) or (..., E) (flattened)."""
    interp = default_interpret() if interpret is None else interpret
    shape = logits.shape
    flat = logits.reshape(-1, shape[-1])
    vals, idx, aux = moe_gating_pallas(flat, k, blk_t=blk_t,
                                       interpret=interp)
    return (vals.reshape(shape[:-1] + (k,)),
            idx.reshape(shape[:-1] + (k,)), aux)
