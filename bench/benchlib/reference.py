"""Plain references for what the timed path produces.

Nothing here imports the program.  Everything is straightforward
``jax.numpy`` at float32 with ``precision="highest"`` on every matmul,
unless a lower ``precision``/``dtype`` is asked for: that is the
control, the reference computed one step below the precision the
configuration states.

* ``analyzer_heads``: the task analyzer (token ids -> task-type and
  domain probabilities, complexity), a two-layer pre-norm encoder with
  a masked mean pool.
* ``route``: the routing decision over a catalog, given the analyzer's
  outputs: hierarchical task-type x domain filter, masked cosine top-k,
  blend of the user's weights with the metric rows at the candidates,
  and the fallback ladder (task-type only, generalists, any).

A served backend's reference is its module's ``logits``
(``bench/backends/<architecture>.py``).
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from benchlib.inputs import METRICS

NEG = -1e30
LADDER = ("", "widened-knn", "task-type-only", "generalist", "any")
# raw metric -> (embedding axis, higher is better)
RAW_AXES = (("accuracy", "accuracy", True), ("latency_ms", "speed", False),
            ("cost_per_mtok", "cheapness", False),
            ("helpfulness", "helpfulness", True),
            ("harmlessness", "harmlessness", True),
            ("honesty", "honesty", True),
            ("steerability", "steerability", True),
            ("creativity", "creativity", True))


def quantize_fp8(w, dt):
    """fp8 (e4m3) copy of a weight matrix with one scale per output
    column, returned dequantized in ``dt``."""
    s = jnp.max(jnp.abs(w), axis=0, keepdims=True) / 448.0 + 1e-12
    q = (w / s).astype(jnp.float8_e4m3fn)
    return q.astype(dt) * s.astype(dt)


# ----------------------------------------------------------------------
# analyzer
# ----------------------------------------------------------------------

def _ln(x, g):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + 1e-6) * g


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def analyzer_heads(params: Dict, tokens: np.ndarray, n_heads: int, *,
                   control: bool = False):
    """tokens (B, L) -> (task-type probs, domain probs, complexity).
    ``control``: fp8 weights with a scale per output column and bf16
    activations, one step below the bf16 matmuls the configuration
    states (fp32 weights at the TPU's default matmul precision)."""
    tt, dm, cx = _analyzer_jit(params, jnp.asarray(tokens), n_heads,
                               "default" if control else "highest",
                               "bfloat16" if control else "float32", control)
    return jax.nn.softmax(tt, -1), jax.nn.softmax(dm, -1), cx


def analyzer_logits(params, tokens, n_heads, precision, dtype, fp8=False):
    """tokens (B, L) -> (task-type logits, domain logits, complexity)."""
    dt = jnp.dtype(dtype)
    p = jax.tree_util.tree_map(
        lambda a: quantize_fp8(a, dt) if fp8 and a.ndim == 2
        else a.astype(dt), params)
    B, L = tokens.shape
    d = p["embed"].shape[1]
    hd = d // n_heads
    mm = lambda a, b: jnp.matmul(a, b, precision=precision)
    mask = tokens != 0
    x = p["embed"][tokens] + p["pos"][None, :L]
    key_bias = jnp.where(mask, 0.0, NEG).astype(jnp.float32)
    for lp in p["layers"]:
        h = _ln(x, lp["ln1"])
        q = mm(h, lp["wq"]).reshape(B, L, n_heads, hd)
        k = mm(h, lp["wk"]).reshape(B, L, n_heads, hd)
        v = mm(h, lp["wv"]).reshape(B, L, n_heads, hd)
        s = jnp.einsum("blhd,bmhd->bhlm", q, k, precision=precision)
        s = s.astype(jnp.float32) / math.sqrt(hd) + key_bias[:, None, None]
        a = jax.nn.softmax(s, axis=-1).astype(dt)
        o = jnp.einsum("bhlm,bmhd->blhd", a, v,
                       precision=precision).reshape(B, L, d)
        x = x + mm(o, lp["wo"])
        h = _ln(x, lp["ln2"])
        x = x + mm(_gelu_tanh(mm(h, lp["wi"])), lp["wp"])
    x = _ln(x, p["ln_f"])
    m = mask[..., None].astype(dt)
    pooled = jnp.sum(x * m, axis=1) / jnp.maximum(jnp.sum(m, axis=1), 1)
    tt = mm(pooled, p["head_tt"]).astype(jnp.float32)
    dm = mm(pooled, p["head_dm"]).astype(jnp.float32)
    cx = jax.nn.sigmoid(mm(pooled, p["head_cx"]).astype(jnp.float32))[:, 0]
    return tt, dm, cx


_analyzer_jit = jax.jit(analyzer_logits, static_argnums=(2, 3, 4, 5))


# ----------------------------------------------------------------------
# routing decision
# ----------------------------------------------------------------------

def normalize_metrics(raw: np.ndarray) -> np.ndarray:
    """Min-max normalize raw metric columns (in ``RAW_AXES`` order) to
    [0, 1] with 1 = better, placed on the ``METRICS`` axes."""
    raw = np.asarray(raw, np.float64)
    emb = np.zeros((raw.shape[0], len(METRICS)), np.float64)
    for j, (_, axis, higher) in enumerate(RAW_AXES):
        col = raw[:, j]
        lo, hi = col.min(), col.max()
        norm = np.ones_like(col) if hi - lo < 1e-12 else (col - lo) / (hi - lo)
        emb[:, METRICS.index(axis)] = norm if higher else 1.0 - norm
    return emb.astype(np.float32)


class Catalog:
    """A catalog as the reference sees it: normalized metric rows,
    filter membership as (entries x tags) booleans, generalist flags."""

    def __init__(self, emb: np.ndarray, tt_member: np.ndarray,
                 dm_member: np.ndarray, generalist: np.ndarray):
        self.emb = jnp.asarray(emb, jnp.float32)
        norm = np.linalg.norm(np.asarray(emb, np.float64), axis=1) + 1e-9
        self.unit = jnp.asarray(np.asarray(emb, np.float64) / norm[:, None],
                                jnp.float32)
        self.tt = jnp.asarray(tt_member)          # (N, n_tt) bool
        self.dm = jnp.asarray(dm_member)          # (N, n_dm) bool
        self.gen = jnp.asarray(generalist)        # (N,) bool
        self.n = int(emb.shape[0])


@jax.jit
def _masks(tt_m, dm_m, gen, ti, di):
    """Per-query filter rows: primary (task type x domain, ANY = the
    index past the last tag), task-type-only, generalists."""
    n_tt, n_dm = tt_m.shape[1], dm_m.shape[1]
    tt_ok = jnp.where(ti[:, None] >= n_tt, True,
                      tt_m.T[jnp.minimum(ti, n_tt - 1)])
    dm_ok = jnp.where(di[:, None] >= n_dm, True,
                      dm_m.T[jnp.minimum(di, n_dm - 1)])
    return tt_ok & dm_ok, tt_ok, jnp.broadcast_to(gen, tt_ok.shape)


def _scores(unit, emb, T, W, precision):
    tn = T / (jnp.linalg.norm(T, axis=1, keepdims=True) + 1e-9)
    cos = jnp.matmul(tn, unit.T, precision=precision)
    blend = jnp.matmul(W, emb.T, precision=precision)
    return cos, blend


_scores_jit = jax.jit(_scores, static_argnums=(4,))


def _task_rows(cat: Catalog, W, tt_idx, dm_idx, cx, conf, threshold):
    """Task vectors and filter rows from the analyzer's outputs: the
    accuracy weight is raised to the complexity, and an unconfident
    query filters on nothing (index past the last tag)."""
    n_tt, n_dm = cat.tt.shape[1], cat.dm.shape[1]
    W = np.asarray(W, np.float32)
    T = W.copy()
    acc = METRICS.index("accuracy")
    T[:, acc] = np.maximum(T[:, acc], np.asarray(cx, np.float32))
    confident = np.asarray(conf) >= threshold
    ti = np.where(confident, tt_idx, n_tt).astype(np.int32)
    di = np.where(confident, dm_idx, n_dm).astype(np.int32)
    return W, T, ti, di


def _decide_impl(cos, blend, prim, tto, gen, *, k):
    """Decisions from full score rows: primary queries take the top k
    masked cosines and the best blend among them; a query whose filter
    is empty takes the best blend in its first non-empty rung."""
    B, N = cos.shape
    n_prim = prim.sum(axis=1)
    has_p = n_prim > 0
    kv, ki = jax.lax.top_k(jnp.where(prim, cos, -jnp.inf), k)
    fin = jnp.isfinite(kv)
    kth = jnp.min(jnp.where(fin, kv, jnp.inf), axis=1)
    cb = jnp.where(fin, jnp.take_along_axis(blend, ki, axis=1), -jnp.inf)
    pw = jnp.take_along_axis(ki, jnp.argmax(cb, axis=1)[:, None], 1)[:, 0]
    any_tt = tto.sum(axis=1) > 0
    any_gen = gen.sum(axis=1) > 0
    rung = jnp.where(any_tt[:, None], tto,
                     jnp.where(any_gen[:, None], gen, True))
    fw = jnp.argmax(jnp.where(rung, blend, -jnp.inf), axis=1)
    winner = jnp.where(has_p, pw, fw)
    rows = jnp.arange(B)
    return {"stage": jnp.where(has_p, 0, jnp.where(any_tt, 2,
                                                   jnp.where(any_gen, 3, 4))),
            "winner": winner, "score": blend[rows, winner],
            "similarity": cos[rows, winner],
            "kth": jnp.where(has_p, kth, -jnp.inf),
            "valid": jnp.where(has_p[:, None], prim, rung)}


def _errors_impl(cos, blend, prim, tto, gen, stage, winner, score, sim,
                 *, k, sure):
    """Per-query error of a decision against the reference rows: the
    largest of (a) how far the winner's cosine lies below the k-th best
    (primary), (b) how far its blend lies below the best blend among
    the rows that are surely in the top k (cosine above the k-th by
    more than ``sure``) or in the fallback rung, (c) the gaps between
    the reported score and similarity and the reference's values at
    the winner.  A wrong stage, or a winner outside the filter, is 1."""
    ref = _decide_impl(cos, blend, prim, tto, gen, k=k)
    B = cos.shape[0]
    rows = jnp.arange(B)
    w = jnp.clip(winner, 0, cos.shape[1] - 1)
    cw, bw = cos[rows, w], blend[rows, w]
    primary = ref["stage"] == 0
    sure_rows = ref["valid"] & (~primary[:, None]
                                | (cos > ref["kth"][:, None] + sure))
    best = jnp.max(jnp.where(sure_rows, blend, -jnp.inf), axis=1)
    e = jnp.maximum(jnp.where(primary, jnp.maximum(ref["kth"] - cw, 0.0),
                              0.0),
                    jnp.maximum(best - bw, 0.0))
    e = jnp.maximum(e, jnp.abs(score - bw))
    e = jnp.maximum(e, jnp.abs(sim - cw))
    bad = (stage != ref["stage"]) | ~ref["valid"][rows, w] | (winner < 0)
    return jnp.where(bad, 1.0, e)


_decide = jax.jit(_decide_impl, static_argnames=("k",))
_errors = jax.jit(_errors_impl, static_argnames=("k", "sure"))


def _blocks(cat, W, tt_idx, dm_idx, cx, conf, threshold, precision, block):
    W, T, ti, di = _task_rows(cat, W, tt_idx, dm_idx, cx, conf, threshold)
    for s in range(0, len(W), block):
        sl = slice(s, s + block)
        masks = _masks(cat.tt, cat.dm, cat.gen, jnp.asarray(ti[sl]),
                       jnp.asarray(di[sl]))
        cos, blend = _scores_jit(cat.unit, cat.emb, jnp.asarray(T[sl]),
                                 jnp.asarray(W[sl]), precision)
        yield sl, cos, blend, masks


def route(cat: Catalog, W, tt_idx, dm_idx, cx, conf, *, k: int,
          threshold: float, precision: str = "highest",
          block: int = 32) -> Dict[str, np.ndarray]:
    """Decisions (stage, winner row, score, similarity) computed at
    ``precision`` for queries with the given analyzer outputs."""
    out = {key: [] for key in ("stage", "winner", "score", "similarity")}
    for _, cos, blend, (prim, tto, gen) in _blocks(
            cat, W, tt_idx, dm_idx, cx, conf, threshold, precision, block):
        res = _decide(cos, blend, prim, tto, gen, k=min(k, cat.n))
        for key in out:
            out[key].append(np.asarray(res[key]))
    return {key: np.concatenate(v) for key, v in out.items()}


def route_errors(cat: Catalog, W, tt_idx, dm_idx, cx, conf, decided: Dict,
                 *, k: int, threshold: float, sure: float = 1e-6,
                 block: int = 32) -> np.ndarray:
    """Per-query error of ``decided`` (stage, winner, score,
    similarity) against the reference at full fp32 precision."""
    errs = []
    for sl, cos, blend, (prim, tto, gen) in _blocks(
            cat, W, tt_idx, dm_idx, cx, conf, threshold, "highest", block):
        errs.append(np.asarray(_errors(
            cos, blend, prim, tto, gen,
            jnp.asarray(np.asarray(decided["stage"])[sl], jnp.int32),
            jnp.asarray(np.asarray(decided["winner"])[sl], jnp.int32),
            jnp.asarray(np.asarray(decided["score"])[sl], jnp.float32),
            jnp.asarray(np.asarray(decided["similarity"])[sl], jnp.float32),
            k=min(k, cat.n), sure=sure)))
    return np.concatenate(errs) if errs else np.zeros(0)
