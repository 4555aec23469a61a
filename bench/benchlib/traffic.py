"""One general generator for every traffic mix.

A mix is a JSON file of parameters under ``bench/traffic/``; nothing
here knows a mix by name.  Every seed gets the same amount of work: the
arrival times come from the mix's own fixed seed, the number of long
queries and their lengths are fixed, and ``--seed`` picks the texts,
which request is long, which tenant sends which request and the
tenants' preference weights (or, with ``pool_seed``, only their order).

Keys of a mix:

* ``loop``: ``open`` (requests at scheduled times), ``clients`` (a
  fixed number of callers, each sending its next request when its reply
  arrives) or ``batches`` (one caller sending batches back to back).
* ``front``: ``async`` (``AsyncServingEngine.submit``, with
  ``max_batch`` and ``max_wait_ms``) or ``route_all``
  (``OptiRoute.route_all``, with ``batch``).
* ``arrivals`` (open loop): ``rps`` base rate, ``burst_factor``,
  ``period_s`` and ``burst_s`` (``burst_factor`` 1 is steady Poisson),
  ``seed``.
* ``clients`` / ``batch``: the closed loops' sizes; ``pool``: how many
  distinct requests a closed loop cycles through.
* ``texts``: ``long_frac`` and ``long_words`` (the range the long
  queries' lengths are spread over); or ``prompt_tokens``, a log-normal
  (``median``, ``sigma``) of each prompt's length in tokens (its words
  and the leading BOS), which every request is filled or cut to.
* ``prefs``: ``tenants`` (name and share of each; weights drawn from
  ``--seed``) or ``profiles`` (weight dicts used in turn).
* ``max_new``: tokens to generate per request; or ``answers``, a
  log-normal (``median``, ``sigma``) of each answer's length, rounded
  to the nearest of ``buckets`` in log space (a bucket takes the
  lengths from the geometric mean with the one below it to that with
  the one above), since each bucket is a shape the backend warms up;
  the median stays in the bucket nearest the file's median.
* ``sources``: where the mix's numbers come from; ``assumed``: those
  that no source gives.
* ``pool_seed`` (optional): draw the texts and preferences from this
  fixed seed instead, and let ``--seed`` only shuffle them, so that
  every seed offers the same requests in another order (where which
  requests reach the backend would otherwise change the work).

A log-normal gives a mix of ``n`` requests the lengths at its ``n``
mid-quantiles, in an order drawn from the seed, so every seed gets the
same set of lengths.
"""
from __future__ import annotations

import statistics
from dataclasses import dataclass
from typing import Dict, List

import numpy as np

from benchlib import inputs


@dataclass
class Spec:
    id: int
    text: str
    weights: Dict[str, float]
    tenant: str
    max_new: int


@dataclass
class Traffic:
    mix: dict
    specs: List[Spec]                 # in arrival order (open) or pool order
    arrivals: np.ndarray              # due times, open loop only


def arrivals(a: dict, seconds: float) -> np.ndarray:
    return inputs.periodic_burst_arrivals(
        seconds, a["rps"], a.get("burst_factor", 1.0),
        a.get("period_s", seconds), a.get("burst_s", 0.0), a["seed"])


def lognormal_lengths(n: int, median: float, sigma: float) -> np.ndarray:
    """The ``n`` mid-quantiles ``(i + 0.5) / n`` of a log-normal, rounded
    to whole tokens (at least 1), in ascending order."""
    nd = statistics.NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    return np.maximum(np.rint(median * np.exp(sigma * z)), 1).astype(int)


def _fit(q: inputs.Query, words: int, rng) -> str:
    """A query's text filled (``inputs.inflate_query``) or cut to
    ``words`` words."""
    if len(q.text.split()) >= words:
        return " ".join(q.text.split()[:words])
    return inputs.inflate_query(q, words, rng).text


def _texts(t: dict, n: int, seed: int) -> List[str]:
    qs = inputs.make_workload(n, seed=seed)
    rng = np.random.default_rng([seed, 1])
    if "prompt_tokens" in t:
        p = t["prompt_tokens"]
        lengths = rng.permutation(lognormal_lengths(n, p["median"],
                                                    p["sigma"]))
        # one token is the BOS the tokenizer puts first
        return [_fit(q, max(int(k) - 1, 1), rng)
                for q, k in zip(qs, lengths)]
    n_long = int(round(t.get("long_frac", 0.0) * n))
    if n_long:
        lo, hi = t["long_words"]
        lengths = np.linspace(lo, hi, n_long, endpoint=False).astype(int)
        where = rng.choice(n, size=n_long, replace=False)
        for i, words in zip(where, rng.permutation(lengths)):
            qs[i] = inputs.inflate_query(qs[i], int(words), rng)
    return [q.text for q in qs]


def _prefs(p: dict, n: int, seed: int):
    """(tenant, weights) per request."""
    rng = np.random.default_rng([seed, 2])
    if "tenants" in p:
        ten = p["tenants"]
        counts = np.floor([t["share"] * n for t in ten]).astype(int)
        counts[0] += n - counts.sum()
        who = rng.permutation(np.repeat(np.arange(len(ten)), counts))
        ws = [{m: float(x) for m, x in zip(inputs.METRICS,
                                           rng.random(len(inputs.METRICS)))}
              for _ in ten]
        return [(ten[j]["name"], ws[j]) for j in who]
    prof = p["profiles"]
    return [("", dict(prof[i % len(prof)])) for i in range(n)]


def _answers(mix: dict, n: int, seed: int) -> List[int]:
    if "answers" not in mix:
        return [mix["max_new"]] * n
    a = mix["answers"]
    buckets = np.array(sorted(a["buckets"]))
    drawn = np.random.default_rng([seed, 5]).permutation(
        lognormal_lengths(n, a["median"], a["sigma"]))
    return bucket_of(drawn, buckets).tolist()


def bucket_of(lengths: np.ndarray, buckets: np.ndarray) -> np.ndarray:
    """Each length's nearest bucket in log space."""
    d = np.abs(np.log(np.asarray(lengths, float))[:, None]
               - np.log(buckets.astype(float))[None, :])
    return buckets[np.argmin(d, axis=1)]


def build(mix: dict, seed: int, seconds: float) -> Traffic:
    loop = mix["loop"]
    if loop == "open":
        due = arrivals(mix["arrivals"], seconds)
        n = len(due)
    else:
        due = np.zeros(0)
        n = mix["pool"]
    src = mix.get("pool_seed", seed)
    texts = _texts(mix["texts"], n, src)
    prefs = _prefs(mix["prefs"], n, src)
    answers = _answers(mix, n, src)
    if "pool_seed" in mix:
        order = np.random.default_rng([seed, 4]).permutation(n)
        texts = [texts[i] for i in order]
        prefs = [prefs[i] for i in order]
        answers = [answers[i] for i in order]
    specs = [Spec(id=i, text=texts[i], weights=prefs[i][1],
                  tenant=prefs[i][0], max_new=answers[i])
             for i in range(n)]
    return Traffic(mix=mix, specs=specs, arrivals=due)
