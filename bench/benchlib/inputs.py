"""Frozen input generators of the benchmark.

Copies of the program's own generators, kept here so that a later
change to the program's copies cannot change what the benchmark
offers.  ``bench/tests/test_bench_inputs.py`` checks that each copy
still reproduces the program's original at fixed seeds.

* ``make_workload`` and what it calls: ``repro.data.workload``.
* ``mega_catalog_arrays``: ``benchmarks.router_scale._mega_catalog``,
  returning the raw arrays instead of registering entries.
* ``poisson_arrivals``: ``repro.data.workload.poisson_arrivals`` with
  the scenario's fields as arguments.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

TASK_TYPES: Tuple[str, ...] = (
    "chat", "code", "reasoning", "summarization", "classification",
    "translation", "transcription", "vqa", "captioning",
    "creative-writing", "long-context",
)
DOMAINS: Tuple[str, ...] = (
    "general", "software", "finance", "legal", "healthcare", "multilingual",
)
METRICS: Tuple[str, ...] = (
    "accuracy", "speed", "cheapness",
    "helpfulness", "harmlessness", "honesty",
    "steerability", "creativity",
)

TEMPLATES: Dict[str, List[str]] = {
    "chat": ["hello can you help me with {topic}",
             "i have a question about {topic}",
             "what do you think about {topic}"],
    "code": ["write a python function that computes {topic}",
             "fix the bug in this code {blob}",
             "refactor this module for readability {blob}"],
    "reasoning": ["solve this step by step {blob}",
                  "prove that {topic} holds for all cases",
                  "which option is correct and why {blob}"],
    "summarization": ["summarize the following article {blob}",
                      "give me a tl dr of this document {blob}",
                      "condense these meeting notes {blob}"],
    "classification": ["find the sentiment of the passage {blob}",
                       "classify this ticket into a category {blob}",
                       "label the intent of this message {blob}"],
    "translation": ["translate this passage to german {blob}",
                    "convert the following text into french {blob}",
                    "translate to spanish keeping the tone {blob}"],
    "transcription": ["transcribe the attached audio about {topic}",
                      "produce a transcript of this recording {topic}",
                      "caption the spoken audio {topic}"],
    "vqa": ["looking at the image what is {topic}",
            "answer the question about the attached picture {topic}",
            "from the screenshot determine {topic}"],
    "captioning": ["describe the attached image of {topic}",
                   "write alt text for this picture of {topic}",
                   "caption this photo about {topic}"],
    "creative-writing": ["write a short story about {topic}",
                         "compose a poem on {topic}",
                         "draft a fictional dialogue about {topic}"],
    "long-context": ["using the entire report below answer {topic} {blob}",
                     "search this long document for {topic} {blob}",
                     "cross reference the chapters below about {topic} {blob}"],
}

DOMAIN_LEXICON: Dict[str, List[str]] = {
    "general": ["weather", "travel", "cooking", "music", "history",
                "sports", "gardening"],
    "software": ["kubernetes", "compiler", "database", "frontend", "api",
                 "microservice", "deployment", "regression"],
    "finance": ["portfolio", "derivatives", "equity", "hedging", "ledger",
                "liquidity", "arbitrage", "quarterly"],
    "legal": ["contract", "liability", "statute", "plaintiff", "clause",
              "compliance", "jurisdiction", "tort"],
    "healthcare": ["diagnosis", "dosage", "radiology", "oncology",
                   "symptom", "clinical", "pathology", "triage"],
    "multilingual": ["german", "mandarin", "localization", "dialect",
                     "idiom", "bilingual", "transliteration"],
}

_FILLER = ["the", "report", "shows", "that", "we", "observed", "several",
           "items", "during", "review", "and", "noted", "further", "points",
           "for", "discussion", "in", "section"]
_HARD_MARKERS = ["however", "sarcastically", "notwithstanding", "paradox",
                 "ambiguous", "nested", "caveat", "irony", "subtle",
                 "counterintuitive"]


class Query:
    """One generated query: its text and ground-truth signature."""
    __slots__ = ("text", "task_type", "domain", "complexity")

    def __init__(self, text: str, task_type: str, domain: str,
                 complexity: float):
        self.text = text
        self.task_type = task_type
        self.domain = domain
        self.complexity = complexity


def _complexity_blob(rng, complexity: float, domain_words) -> str:
    n_fill = int(10 + complexity * 120)
    words = list(rng.choice(_FILLER, n_fill))
    n_hard = int(round(complexity * 6))
    for _ in range(n_hard):
        words.insert(int(rng.integers(0, len(words))),
                     str(rng.choice(_HARD_MARKERS)))
    for _ in range(3):
        words.insert(int(rng.integers(0, len(words))),
                     str(rng.choice(domain_words)))
    return " ".join(words)


def make_query(rng: np.random.Generator) -> Query:
    tt = str(rng.choice(TASK_TYPES))
    dm = str(rng.choice(DOMAINS))
    cx = float(rng.random())
    lex = DOMAIN_LEXICON[dm]
    template = str(rng.choice(TEMPLATES[tt]))
    blob = _complexity_blob(rng, cx, lex)
    topic = " ".join(rng.choice(lex, 2))
    text = template.format(topic=topic, blob=blob)
    cx_obs = min(1.0, (len(text.split()) - 10) / 130.0 * 0.7
                 + sum(text.count(m) for m in _HARD_MARKERS) / 6.0 * 0.3)
    return Query(text, tt, dm, round(max(0.0, cx_obs), 4))


def inflate_query(q: Query, target_words: int,
                  rng: np.random.Generator) -> Query:
    """Pad the middle with filler up to ``target_words`` words."""
    words = q.text.split()
    need = target_words - len(words)
    if need <= 0:
        return q
    blob = list(rng.choice(_FILLER, need))
    cut = max(len(words) // 2, 1)
    return Query(" ".join(words[:cut] + blob + words[cut:]),
                 q.task_type, q.domain, q.complexity)


def make_workload(n: int, seed: int = 0, *, long_frac: float = 0.0,
                  long_words: Tuple[int, int] = (200, 2000)) -> List[Query]:
    rng = np.random.default_rng(seed)
    out = [make_query(rng) for _ in range(n)]
    if long_frac:
        for i in range(n):
            if rng.random() < long_frac:
                out[i] = inflate_query(out[i], int(rng.integers(*long_words)),
                                       rng)
    return out


def mega_catalog_arrays(n: int, seed: int = 0, clusters: int = 256,
                        noise: float = 0.03, generalist_frac: float = 0.2):
    """Clustered synthetic catalog: (raw (n, 8) in [0, 1] as the
    program's ``_mega_catalog`` draws them, task-type index (n,),
    domain index (n,), generalist flags (n,))."""
    rng = np.random.default_rng(seed)
    centers = rng.random((clusters, 8))
    raw = np.clip(centers[rng.integers(0, clusters, size=n)]
                  + rng.normal(0.0, noise, (n, 8)), 0.0, 1.0)
    tt_pick = rng.integers(0, len(TASK_TYPES), size=n)
    dm_pick = rng.integers(0, len(DOMAINS), size=n)
    gen = rng.random(n) < generalist_frac
    return raw, tt_pick, dm_pick, gen


def mega_raw_metrics(v) -> Dict[str, float]:
    """One catalog row's raw metrics, in the program's units."""
    return {"accuracy": float(v[0]),
            "latency_ms": float(v[1] * 500 + 1),
            "cost_per_mtok": float(v[2] * 20 + 0.1),
            "helpfulness": float(v[3]),
            "harmlessness": float(v[4]),
            "honesty": float(v[5]),
            "steerability": float(v[6]),
            "creativity": float(v[7])}


def poisson_arrivals(duration_s: float, base_rate: float, burst_rate: float,
                     burst_start: float, burst_len: float,
                     seed: int) -> np.ndarray:
    """Piecewise-Poisson arrival times by thinning a process drawn at
    the peak rate (one burst window, given as fractions of the
    episode)."""
    rng = np.random.default_rng(seed)
    rmax = burst_rate
    ts: List[np.ndarray] = []
    t = 0.0
    while t < duration_s:
        gaps = rng.exponential(1.0 / rmax, int(rmax * duration_s) + 64)
        chunk = t + np.cumsum(gaps)
        ts.append(chunk)
        t = float(chunk[-1])
    all_ts = np.concatenate(ts)
    all_ts = all_ts[all_ts < duration_s]
    b0 = burst_start * duration_s
    b1 = b0 + burst_len * duration_s
    rate = np.where((all_ts >= b0) & (all_ts < b1), burst_rate, base_rate)
    keep = rng.random(all_ts.size) < rate / rmax
    return all_ts[keep]


def periodic_burst_arrivals(duration_s: float, base_rate: float,
                            burst_factor: float, period_s: float,
                            burst_s: float, seed: int,
                            offset_s: Optional[float] = None) -> np.ndarray:
    """Bursts of ``burst_factor`` x ``base_rate`` for ``burst_s`` of every
    ``period_s``: one ``poisson_arrivals`` episode per period, each with
    its own seed, shifted into place."""
    out = []
    n_periods = int(np.ceil(duration_s / period_s))
    start = (period_s - burst_s) / 2.0 if offset_s is None else offset_s
    for p in range(n_periods):
        ts = poisson_arrivals(period_s, base_rate, base_rate * burst_factor,
                              start / period_s, burst_s / period_s,
                              seed * 1000 + p)
        out.append(p * period_s + ts)
    ts = np.concatenate(out) if out else np.zeros(0)
    return ts[ts < duration_s]
