"""How ``correct`` is decided: what the timed path produced, against the
plain reference, once the window has closed.

Numbers compared (each has its limit in the configuration's
``limits``):

* ``analyzer_err_mean``: for the fused decision program's analyzer
  heads, per sampled query the largest of: how far the reference
  probability of the task type (and of the domain) that the program
  chose lies below the reference's best, and the absolute gaps of
  complexity and confidence; averaged over the sample.  (The worst
  query swings from seed to seed about as much as the control does, so
  the mean is compared.)
* ``route_err``: for the decision itself, given the program's analyzer
  outputs (``reference.route_errors``), the worst sampled query: a
  winner outside the filter or on the wrong fallback rung counts 1;
  otherwise the largest of how far the winner's cosine lies below the
  k-th best, how far its blend lies below the best blend among rows
  surely in the top k, and the gaps of the reported score and
  similarity.
* ``logit_gap_mean`` (cells with a backend): for a sample of requests
  served on the chip, run the reference once over each prompt and its
  served tokens; how far each served token's reference logit lies below
  the reference's best at its position (greedy decoding: the first
  token comes from the prefill, the rest from decode steps), averaged
  over every served token of the sample.  (The widest gap swings with
  the few near-ties a sample happens to hold.)

The sample is drawn from ``--seed`` among the requests the window
finished; the served sample always holds the longest answer.
"""
from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from benchlib import backend, inputs, reference, system as sysmod
from benchlib.tokenize import Tokenizer
from benchlib.weights import analyzer_tokens

DECISION_SAMPLE = 256
SERVED_SAMPLE = 16
NOTHING = 1e30          # the reading when there was nothing to compare


def prompt_tokens(sysm, texts: List[str]) -> np.ndarray:
    """Prompts as the engine hands them to the backend: hashed words,
    right-padded or cut to ``prompt_len``, clipped to the vocabulary."""
    e = sysm.config["engine"]
    t = Tokenizer(e["vocab_hash"]).encode_batch(texts, e["prompt_len"])
    return np.clip(t, 0, sysm.backend["vocab_size"] - 1).astype(np.int32)


def _decided(sysm, rqs) -> Dict[str, np.ndarray]:
    """The program's analyzer outputs and decisions, as arrays."""
    sig = [rq.sig for rq in rqs]
    dec = [rq.decision for rq in rqs]
    return {
        "tt": np.array([inputs.TASK_TYPES.index(s.task_type) for s in sig]),
        "dm": np.array([inputs.DOMAINS.index(s.domain) for s in sig]),
        "cx": np.array([s.complexity for s in sig], np.float32),
        "conf": np.array([s.confidence for s in sig], np.float32),
        "stage": np.array([reference.LADDER.index(d.fallback_kind)
                           for d in dec]),
        "winner": np.array([sysm.row_of.get(d.model, -1) for d in dec]),
        "score": np.array([d.score for d in dec], np.float32),
        "similarity": np.array([d.similarity for d in dec], np.float32),
    }


def _routed(answer):
    """The RoutedQuery behind an answer (None when it was not routed)."""
    return getattr(answer, "rq", answer) if answer is not None else None


def sample(run, seed: int, want: int, pick, longest=None) -> List:
    """Up to ``want`` finished records that ``pick`` takes, drawn from
    ``seed``; with ``longest`` (a record's length), the longest of them
    is always among the sample."""
    recs = [r for r in run.records if r.done is not None and pick(r)]
    rng = np.random.default_rng([seed, 3])
    idx = rng.permutation(len(recs))[:want]
    if longest is not None and len(recs) and len(idx):
        top = max(range(len(recs)), key=lambda i: longest(recs[i]))
        if top not in idx:
            idx[-1] = top
    return [recs[i] for i in sorted(idx)]


def analyzer_reference(sysm, texts, *, control: bool = False):
    a = sysm.config["analyzer"]
    toks = analyzer_tokens(a, texts)
    p = jax.tree_util.tree_map(jnp.asarray, sysm.analyzer_params)
    tt, dm, cx = reference.analyzer_heads(p, toks, a["n_heads"],
                                          control=control)
    return np.asarray(tt), np.asarray(dm), np.asarray(cx)


def analyzer_err(ref, got) -> float:
    tt, dm, cx = ref
    rows = np.arange(len(cx))
    conf = np.minimum(tt.max(1), dm.max(1))
    g = np.maximum.reduce([tt.max(1) - tt[rows, got["tt"]],
                           dm.max(1) - dm[rows, got["dm"]],
                           np.abs(cx - got["cx"]),
                           np.abs(conf - got["conf"])])
    return float(g.mean()) if len(g) else 0.0


def route_error(sysm, W, got) -> float:
    r = sysm.config["routing"]
    e = reference.route_errors(sysm.ref_catalog, W, got["tt"], got["dm"],
                               got["cx"], got["conf"], got,
                               k=r["knn_k"], threshold=r["confidence_threshold"])
    return float(e.max()) if len(e) else 0.0


def control_decisions(sysm, texts, W) -> Dict[str, np.ndarray]:
    """The control: the reference put in the program's place, one step
    below the configured precision (the analyzer with fp8 weights and
    bf16 activations instead of bf16 matmuls; the routing matmuls at
    ``high``, three bf16 passes, instead of fp32 ``highest``)."""
    tt, dm, cx = analyzer_reference(sysm, texts, control=True)
    got = {"tt": tt.argmax(1), "dm": dm.argmax(1),
           "cx": np.clip(cx, 0, 1), "conf": np.minimum(tt.max(1), dm.max(1))}
    r = sysm.config["routing"]
    got.update(reference.route(sysm.ref_catalog, W, got["tt"], got["dm"],
                               got["cx"], got["conf"], k=r["knn_k"],
                               threshold=r["confidence_threshold"],
                               precision="high"))
    return got


def _gaps_fn(ref_logits, picked, start):
    rows = ref_logits[start:start + picked.shape[0]]
    return rows.max(axis=1) - rows[jnp.arange(picked.shape[0]), picked]


_gaps = jax.jit(_gaps_fn, static_argnums=(2,))


@jax.jit
def _argmax_rows(logits):
    return jnp.argmax(logits, axis=1).astype(jnp.int32)


def served_gap_mean(sysm, recs, *, control: bool = False) -> float:
    """Mean gap of the served tokens below the reference's best (or,
    for the control, of the tokens the control puts first at the same
    positions of the same prompts and served tokens)."""
    m = sysm.backend
    logits = backend.of(m).logits
    prompts = prompt_tokens(sysm, [r.spec.text for r in recs])
    gaps = []
    for p, r in zip(prompts, recs):
        served = np.asarray(r.answer.tokens, np.int32)
        seq = np.concatenate([p, served[:-1]])
        ref = logits(sysm.backend_weights, m, seq)
        start = len(p) - 1
        picked = jnp.asarray(served)
        if control:
            ctl = logits(sysm.backend_weights, m, seq, control=True)
            picked = _argmax_rows(ctl)[start:start + len(served)]
        gaps.append(np.asarray(_gaps(ref, picked, start)))
    return float(np.concatenate(gaps).mean())


def check_run(sysm, run, seed: int, *,
              control: bool = False) -> Dict[str, dict]:
    """Compare a sample of the window's answers with the reference.
    ``control``: put the control (``control_decisions``, and the tokens
    the control puts first) in the program's place, at the same sample."""
    lim = sysm.config["limits"]
    out: Dict[str, dict] = {}
    dec = sample(run, seed, DECISION_SAMPLE,
                 lambda r: _routed(r.answer) is not None and not r.error)
    if dec:
        texts = [r.spec.text for r in dec]
        W = np.stack([sysmod.weights_vector(r.spec.weights) for r in dec])
        got = control_decisions(sysm, texts, W) if control else \
            _decided(sysm, [_routed(r.answer) for r in dec])
        ref = analyzer_reference(sysm, texts)
        out["analyzer_err_mean"] = {"value": analyzer_err(ref, got),
                                    "limit": lim["analyzer_err_mean"]}
        out["route_err"] = {"value": route_error(sysm, W, got),
                            "limit": lim["route_err"]}
    if sysm.runner is not None:
        served = sample(run, seed, SERVED_SAMPLE, lambda r: (
            getattr(r.answer, "tokens", None) is not None
            and r.answer.model in sysm.on_chip),
            longest=lambda r: len(r.answer.tokens))
        # a backend that served nothing in the window cannot be shown
        # correct: that reads as a failed comparison
        out["logit_gap_mean"] = {
            "value": served_gap_mean(sysm, served, control=control)
            if served else NOTHING,
            "limit": lim["logit_gap_mean"]}
    return out


def verdict(checks: Dict[str, dict], failed: int) -> bool:
    """``correct``: every request answered and every number within its
    limit."""
    return bool(checks) and failed == 0 and all(
        c["value"] <= c["limit"] for c in checks.values())
