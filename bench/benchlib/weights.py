"""Weights the benchmark makes: nothing here is taken from the program.

* ``analyzer_weights``: the task analyzer, trained once per checkout
  from the configuration's fixed seed and step count on the frozen
  workload generator (plain Adam on the heads' cross-entropies and the
  complexity error), then kept under ``.bench_cache/`` in the checkout.

A served backend's weights are made by its module's ``make_weights``
(``bench/backends/<architecture>.py``).
"""
from __future__ import annotations

import hashlib
import json
import math
import pathlib
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from benchlib import inputs, reference
from benchlib.tokenize import Tokenizer, prune_text


def _analyzer_init(key, a: dict, n_tt: int, n_dm: int) -> Dict:
    d, f, V = a["d_model"], a["d_ff"], a["vocab_size"]
    ks = jax.random.split(key, 6 + a["n_layers"])

    def mat(k, shape, scale=None):
        std = scale if scale else 1.0 / math.sqrt(shape[0])
        return jax.random.normal(k, shape, jnp.float32) * std

    def layer(k):
        kk = jax.random.split(k, 7)
        return {"wq": mat(kk[0], (d, d)), "wk": mat(kk[1], (d, d)),
                "wv": mat(kk[2], (d, d)), "wo": mat(kk[3], (d, d)),
                "wi": mat(kk[4], (d, f)), "wp": mat(kk[5], (f, d)),
                "ln1": jnp.ones((d,)), "ln2": jnp.ones((d,))}

    return {"embed": mat(ks[0], (V, d), 0.05),
            "pos": mat(ks[1], (a["max_len"], d), 0.02),
            "layers": [layer(ks[2 + i]) for i in range(a["n_layers"])],
            "ln_f": jnp.ones((d,)),
            "head_tt": mat(ks[-3], (d, n_tt), 0.02),
            "head_dm": mat(ks[-2], (d, n_dm), 0.02),
            "head_cx": mat(ks[-1], (d, 1), 0.02)}


def analyzer_tokens(a: dict, texts, tok: Tokenizer = None) -> np.ndarray:
    """Prune and tokenize queries the way the analyzer reads them."""
    tok = tok or Tokenizer(a["vocab_size"])
    pruned = [prune_text(t, a["prune_head"], a["prune_tail"], a["prune_mid"])
              for t in texts]
    return tok.encode_batch(pruned, a["max_len"])


def _train(a: dict) -> Dict:
    tr = a["train"]
    n_tt, n_dm = len(inputs.TASK_TYPES), len(inputs.DOMAINS)
    qs = inputs.make_workload(tr["samples"], seed=tr["seed"],
                              long_frac=tr["long_frac"])
    toks = analyzer_tokens(a, [q.text for q in qs])
    y_tt = np.array([inputs.TASK_TYPES.index(q.task_type) for q in qs])
    y_dm = np.array([inputs.DOMAINS.index(q.domain) for q in qs])
    y_cx = np.array([q.complexity for q in qs], np.float32)
    params = _analyzer_init(jax.random.PRNGKey(tr["seed"]), a, n_tt, n_dm)
    lr, b1, b2 = tr["lr"], 0.9, 0.999

    def loss(p, t, yt, yd, yc):
        tt, dm, cx = reference.analyzer_logits(p, t, a["n_heads"],
                                               "default", "float32")
        ce = lambda lg, y: -jnp.mean(jnp.take_along_axis(
            jax.nn.log_softmax(lg), y[:, None], 1))
        return ce(tt, yt) + ce(dm, yd) + 4.0 * jnp.mean((cx - yc) ** 2)

    @jax.jit
    def step(p, m, v, i, t, yt, yd, yc):
        g = jax.grad(loss)(p, t, yt, yd, yc)
        m = jax.tree_util.tree_map(lambda m_, g_: b1 * m_ + (1 - b1) * g_,
                                   m, g)
        v = jax.tree_util.tree_map(lambda v_, g_: b2 * v_ + (1 - b2) * g_ * g_,
                                   v, g)
        c1, c2 = 1 - b1 ** (i + 1), 1 - b2 ** (i + 1)
        p = jax.tree_util.tree_map(
            lambda p_, m_, v_: p_ - lr * (m_ / c1) / (jnp.sqrt(v_ / c2) + 1e-8),
            p, m, v)
        return p, m, v

    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    m, v = zeros, zeros
    rng = np.random.default_rng(tr["seed"])
    for i in range(tr["steps"]):
        sel = rng.integers(0, len(qs), tr["batch"])
        params, m, v = step(params, m, v, jnp.float32(i), toks[sel],
                            y_tt[sel], y_dm[sel], y_cx[sel])
    return jax.tree_util.tree_map(np.asarray, params)


def _flatten(params: Dict) -> Dict[str, np.ndarray]:
    flat = {k: v for k, v in params.items() if k != "layers"}
    for i, lp in enumerate(params["layers"]):
        for k, v in lp.items():
            flat[f"layers.{i}.{k}"] = v
    return flat


def _unflatten(flat: Dict[str, np.ndarray], n_layers: int) -> Dict:
    out = {k: v for k, v in flat.items() if not k.startswith("layers.")}
    out["layers"] = [
        {k.split(".", 2)[2]: v for k, v in flat.items()
         if k.startswith(f"layers.{i}.")} for i in range(n_layers)]
    return out


def analyzer_weights(a: dict, cache_dir: pathlib.Path) -> Dict:
    """Trained analyzer weights (numpy), cached per configuration."""
    key = hashlib.sha256(json.dumps(a, sort_keys=True).encode()).hexdigest()
    path = cache_dir / f"analyzer-{key[:16]}.npz"
    if path.exists():
        with np.load(path) as z:
            return _unflatten({k: z[k] for k in z.files}, a["n_layers"])
    params = _train(a)
    cache_dir.mkdir(parents=True, exist_ok=True)
    tmp = path.with_suffix(".tmp.npz")
    np.savez(tmp, **_flatten(params))
    tmp.replace(path)
    return params
