"""OptiRoute end-to-end orchestrator (paper Fig 1, §3).

Ties together: user preferences -> Task Analyzer -> Routing Engine over
the MRES -> (optional) model-merging fallback -> inference execution ->
feedback loop.  Three operating modes:

  * interactive — every query is analyzed and routed individually;
  * batched per-query (``route_all``) — the whole request batch is
    analyzed in one analyzer forward and routed in one vectorized
    ``route_many`` pass, each query still getting its own decision
    (the serving engine's default path);
  * batch       — a ~2% sample of the batch is analyzed, the aggregate
                  signature routes the WHOLE batch to one model
                  (amortizes the analyzer; paper §3).
"""
from __future__ import annotations

import time
from collections import Counter
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.analyzer import TaskAnalyzer
from repro.core.feedback import FeedbackStore
from repro.core.merging import ModelMerger
from repro.core.mres import MRES, ModelEntry
from repro.core.preferences import (TaskSignature, UserPreferences,
                                    resolve_batch)
from repro.core.routing import RoutingDecision, RoutingEngine
from repro.obs.trace import span


class RoutedQuery:
    """One routed query (text, signature, decision, timings).

    The decision is either eager (single-query ``route``) or LAZY: a
    query routed through the array-first ``route_many_batch`` path
    carries only a (RoutingBatch, row) handle, and the full
    ``RoutingDecision`` object (candidate tuple list, stage_sizes
    dict) materializes on first ``.decision`` access.  The hot-path
    facts — ``model``, ``fallback_kind``, ``task_vector`` — read the
    batch arrays directly, so serving/telemetry/observe never pay the
    Python object loop for queries nobody inspects in depth.

    ``cache_key``/``cache_fp`` are the semantic-cache write-back key,
    stamped by the serving engine at submit time (a cache MISS that
    later validates well becomes the entry that answers the next
    near-duplicate).  ``cache_written`` tracks write-back separately
    from ``observed``: an auto-observing reward_fn marks queries
    observed BEFORE the engine stamps keys, and that must not starve
    the cache of the post-generation write-back.
    """
    __slots__ = ("text", "analyzer_s", "route_s", "response",
                 "observed", "cache_key", "cache_fp", "cache_written",
                 "_sig", "_decision", "_batch", "_bidx")

    def __init__(self, text: str, sig: Optional[TaskSignature] = None,
                 decision: Optional[RoutingDecision] = None,
                 analyzer_s: float = 0.0, route_s: float = 0.0,
                 response: Any = None, batch=None, batch_idx: int = -1):
        assert decision is not None or batch is not None
        self.text = text
        self.analyzer_s = analyzer_s
        self.route_s = route_s
        self.response = response
        self.observed = False         # reward already fed to the bandit
        self.cache_key: Optional[np.ndarray] = None
        self.cache_fp = 0
        self.cache_written = False
        self._sig = sig
        self._decision = decision
        self._batch = batch
        self._bidx = batch_idx

    @property
    def sig(self) -> TaskSignature:
        """Task signature — eager on the staged path, materialized
        lazily from the fused batch's analyzer arrays otherwise."""
        if self._sig is None:
            self._sig = self._batch.signature(self._bidx)
        return self._sig

    @property
    def decision(self) -> RoutingDecision:
        """Full decision object (materialized lazily, memoized)."""
        if self._decision is None:
            self._decision = self._batch.decision(self._bidx)
        return self._decision

    @property
    def model(self) -> str:
        """Chosen model name without materializing the decision."""
        if self._decision is not None:
            return self._decision.model
        return self._batch.model(self._bidx)

    @property
    def fallback_kind(self) -> str:
        if self._decision is not None:
            return self._decision.fallback_kind
        return self._batch.fallback_kind(self._bidx)

    @property
    def task_vector(self) -> np.ndarray:
        if self._decision is not None:
            return self._decision.task_vector
        return self._batch.task_vectors[self._bidx]


class OptiRoute:
    """The deployable facade:
    ``route`` / ``route_all`` / ``route_batch`` / ``serve``."""

    def __init__(self, mres: MRES, analyzer: TaskAnalyzer, *,
                 feedback: Optional[FeedbackStore] = None,
                 knn_k: int = 8, merge_threshold: Optional[float] = None,
                 batch_sample_frac: float = 0.02,
                 use_kernel: bool = False, feedback_weight: float = 0.5,
                 telemetry=None, tracer=None, adaptive=None,
                 adaptive_weight: float = 0.0, reward_fn=None,
                 reward_shaper=None, load=None, load_weight: float = 0.0,
                 cache=None):
        self.mres = mres
        self.analyzer = analyzer
        self.feedback = feedback if feedback is not None else FeedbackStore()
        self.engine = RoutingEngine(mres, self.feedback, knn_k=knn_k,
                                    use_kernel=use_kernel,
                                    feedback_weight=feedback_weight,
                                    adaptive=adaptive,
                                    adaptive_weight=adaptive_weight,
                                    load=load, load_weight=load_weight,
                                    telemetry=telemetry, tracer=tracer)
        self.merger = (ModelMerger(mres, merge_threshold)
                       if merge_threshold is not None else None)
        self.batch_sample_frac = batch_sample_frac
        self.telemetry = telemetry
        # span sink (obs.trace.Tracer): analyze/route/observe stages
        # report nested spans, propagated down to the fused dispatch
        self.tracer = tracer
        # adaptive loop: bandit + (optional) automatic reward emission.
        # ``reward_fn(rq) -> quality in [0, 1]`` makes ``route_all``
        # close the loop itself; without it, call ``observe`` explicitly.
        self.adaptive = adaptive
        self.reward_fn = reward_fn
        self.reward_shaper = reward_shaper
        # load-aware loop: live per-model capacity state the serving
        # engine maintains and route_many penalizes at ``load_weight``
        self.load = load
        # semantic response cache (repro.cache): the serving engine
        # consults it before routing; ``observe`` writes validated
        # responses back so future near-duplicates short-circuit
        self.cache = cache
        # analyzer dispatches report into the same telemetry/trace
        # stream as route_step (fused path and batched analyze alike)
        if getattr(analyzer, "supports_fused_route", False):
            if analyzer.telemetry is None:
                analyzer.telemetry = telemetry
            if analyzer.tracer is None:
                analyzer.tracer = tracer

    # ------------------------- interactive -------------------------
    def route(self, text: str, prefs) -> RoutedQuery:
        """Single-query routing — B=1 wrapper over ``route_all``.

        Sharing the batched entry means a lone interactive query rides
        the same shape-bucketed (and, when eligible, fused) device
        program as serving batches: the B=1 dispatch reuses the
        8-row-floor bucket instead of compiling its own shape."""
        return self.route_all([text], prefs)[0]

    def _record(self, rq: RoutedQuery) -> None:
        if self.telemetry is not None:
            entry = self.mres.entry(rq.model)
            self.telemetry.record_decision(
                rq, sim_cost=entry.raw_metrics.get("cost_per_mtok", 0.0))

    def _fully_fused_ok(self) -> bool:
        """Whether the single analyze->route device program can serve
        this configuration: a fusable engine (no Thompson bandit, no
        mesh sharding, no IVF pruning — those keep the staged analyze),
        no merger (it needs eager scores and may grow the catalog
        mid-pass), and an analyzer exposing its params/config for
        in-program execution (stub/oracle analyzers do not)."""
        return (self.merger is None
                and getattr(self.analyzer, "supports_fused_route", False)
                and self.engine._fused_ok()
                and self.engine.mesh is None
                and not self.engine.ivf)

    # --------------------- batched per-query ---------------------
    def route_all(self, texts: Sequence[str], prefs) -> List[RoutedQuery]:
        """Analyze and route every query in one vectorized pass.

        Unlike ``route_batch`` (sample-and-aggregate, one decision for
        the whole batch), every query gets its own signature and
        decision; the analyzer runs as one batched forward and the
        Routing Engine as ONE fused ``route_many_batch`` device
        dispatch (per-query decisions materialize lazily off the
        returned ``RoutingBatch``; a merger — which needs eager scores
        and may grow the catalog mid-pass — or a non-fusable engine
        config takes the staged object path).  ``prefs`` is a single
        prefs/profile (broadcast) or one per query.  Reported
        per-query timings are the batch cost amortized over B.
        """
        if len(texts) == 0:
            return []
        B = len(texts)
        prefs_list = resolve_batch(prefs, B)
        if len(prefs_list) != B:
            raise ValueError(f"prefs batch size {len(prefs_list)} != "
                             f"text batch size {B}")
        with span(self.tracer, "route_all", batch=B):
            out = self._route_all(texts, prefs_list)
            for rq in out:
                self._record(rq)
            if self.adaptive is not None and self.reward_fn is not None:
                self.observe(out)
        return out

    def _route_all(self, texts: Sequence[str],
                   prefs_list: List[UserPreferences]) -> List[RoutedQuery]:
        B = len(texts)
        tr = self.tracer
        if self._fully_fused_ok():
            # ONE device program from token ids to model choice: the
            # "analyze" span covers only host-side prune+tokenize (the
            # encoder itself runs inside the fused dispatch, which
            # emits its own route_step span with path="fused")
            an = self.analyzer
            t0 = time.perf_counter()
            with span(tr, "analyze", path="fused", batch=B):
                toks = an.encode_batch(list(texts))
            t1 = time.perf_counter()
            batch = self.engine.route_tokens_batch(
                an.params, an.cfg, toks, prefs_list)
            t2 = time.perf_counter()
            return [RoutedQuery(text=t, batch=batch, batch_idx=i,
                                analyzer_s=(t1 - t0) / B,
                                route_s=(t2 - t1) / B)
                    for i, t in enumerate(texts)]
        t0 = time.perf_counter()
        with span(tr, "analyze", batch=B):
            sigs = self.analyzer.analyze_batch(list(texts))
        t1 = time.perf_counter()
        if self.merger is None and self.engine._fused_ok():
            batch = self.engine.route_many_batch(prefs_list, sigs)
            t2 = time.perf_counter()
            return [RoutedQuery(text=t, sig=s, batch=batch, batch_idx=i,
                                analyzer_s=(t1 - t0) / B,
                                route_s=(t2 - t1) / B)
                    for i, (t, s) in enumerate(zip(texts, sigs))]
        with span(tr, "route_step", path="staged", batch=B):
            decisions = self.engine.route_many(prefs_list, sigs)
        if self.merger is not None:
            low = [i for i, d in enumerate(decisions)
                   if d.score < self.merger.score_threshold]
            grew = False
            for i in low:
                if self.merger.maybe_merge(
                        prefs_list[i], sigs[i],
                        decisions[i].score) is not None:
                    grew = True
            if grew:                   # re-route low scorers in one pass
                redo = self.engine.route_many(
                    [prefs_list[i] for i in low],
                    [sigs[i] for i in low])
                for j, i in enumerate(low):
                    decisions[i] = redo[j]
        t2 = time.perf_counter()
        return [RoutedQuery(text=t, sig=s, decision=d,
                            analyzer_s=(t1 - t0) / B,
                            route_s=(t2 - t1) / B)
                for t, s, d in zip(texts, sigs, decisions)]

    # ----------------------- adaptive loop -----------------------
    def observe(self, rqs: Sequence[RoutedQuery],
                qualities: Optional[Sequence[float]] = None,
                extra_penalty=None) -> Optional[np.ndarray]:
        """Close the adaptive loop for a routed batch.

        Emits one reward observation per query into the bandit: quality
        (from ``qualities`` or ``reward_fn``) shaped by the per-model
        cost/latency penalties of ``reward_shaper`` (plus any realized
        ``extra_penalty`` from telemetry), against the decision's task
        vector as context.  When a semantic cache is attached, each
        newly-observed query whose serving-time cache key is stamped
        also writes its validated (response, RAW quality) back — the
        cache gates on its own ``min_quality`` bar, so only responses
        the quality loop vouches for are ever replayed.  Each query is
        observed AT MOST ONCE (so an auto-observing ``reward_fn`` plus
        an explicit post-generation ``observe`` never double-count an
        outcome, and a response is never cache-written twice).  Returns
        the shaped rewards of the newly-observed queries, or None when
        neither a bandit nor a cache is attached / no quality source
        exists / nothing is new.
        """
        if (self.adaptive is None and self.cache is None) or not rqs:
            return None
        if qualities is None and self.reward_fn is None:
            return None
        if qualities is not None and len(qualities) != len(rqs):
            raise ValueError(f"{len(rqs)} routed queries but "
                             f"{len(qualities)} qualities — observations "
                             "must align one-to-one")
        if extra_penalty is not None and len(extra_penalty) != len(rqs):
            raise ValueError(f"{len(rqs)} routed queries but "
                             f"{len(extra_penalty)} extra penalties")
        # bandit-fresh and cache-unwritten are tracked SEPARATELY: an
        # auto-observing reward_fn consumes bandit freshness inside
        # route_all, before the serving engine has stamped cache keys —
        # the later post-generation observe() must still write back.
        # Quality is only evaluated for queries that need it (quality
        # evaluation can be expensive in real deployments).
        fresh = [] if self.adaptive is None else \
            [i for i, rq in enumerate(rqs) if not rq.observed]
        cacheable = [] if self.cache is None else \
            [i for i, rq in enumerate(rqs)
             if rq.cache_key is not None and not rq.cache_written]
        todo = sorted(set(fresh) | set(cacheable))
        if not todo:
            return None
        with span(self.tracer, "observe", batch=len(rqs),
                  fresh=len(fresh), cacheable=len(cacheable)):
            if qualities is None:
                qual = {i: float(self.reward_fn(rqs[i])) for i in todo}
            else:
                qual = {i: float(qualities[i]) for i in todo}
            # cache write-back takes RAW quality: the cache's admission
            # bar is about answer trustworthiness, not the
            # cost/latency-shaped bandit reward
            for i in cacheable:
                rq = rqs[i]
                kind = self.cache.put(rq.cache_key, rq.cache_fp,
                                      rq.model, rq.response,
                                      qual[i], sig=rq.sig)
                rq.cache_written = True
                if self.telemetry is not None:
                    self.telemetry.record_cache(kind)
            if cacheable and self.telemetry is not None:
                # inserts can evict/expire internally; surface the churn
                for kind, n in self.cache.drain_events().items():
                    self.telemetry.record_cache(kind, n)
            if self.adaptive is None or not fresh:
                for i in fresh:
                    rqs[i].observed = True
                return None
            sub = [rqs[i] for i in fresh]
            sub_q = [qual[i] for i in fresh]
            sub_ep = None if extra_penalty is None else \
                np.asarray(extra_penalty, np.float32)[fresh]
            names = self.mres.snapshot()[1]
            midx = np.array([self.mres.column(rq.model) for rq in sub])
            X = np.stack([rq.task_vector for rq in sub])
            if self.reward_shaper is not None:
                rewards = self.reward_shaper.shape(sub_q, midx, sub_ep)
            else:
                rewards = np.asarray(sub_q, np.float32)
                if sub_ep is not None:
                    rewards = rewards - sub_ep
            self.adaptive.ensure(len(names))
            self.adaptive.update(X, midx, rewards)
            for rq in sub:
                rq.observed = True
            return rewards

    # --------------------------- batch ---------------------------
    def route_batch(self, texts: Sequence[str], prefs, *,
                    seed: int = 0) -> Tuple[RoutingDecision, List[TaskSignature], Dict]:
        """Sample-analyze-aggregate-route (paper batch mode).

        Returns (one decision for the whole batch, sampled signatures,
        stats).  The aggregate signature takes the majority task type /
        domain and the MAX complexity of the sample (the chosen model
        must handle the hardest sampled query).
        """
        n = len(texts)
        if n == 0:
            raise ValueError("route_batch requires at least one text; "
                             "got an empty batch")
        k = max(1, int(round(n * self.batch_sample_frac)))
        rng = np.random.default_rng(seed)
        pick = rng.choice(n, size=min(k, n), replace=False)
        t0 = time.perf_counter()
        sigs = self.analyzer.analyze_batch([texts[i] for i in pick])
        t1 = time.perf_counter()
        tt = Counter(s.task_type for s in sigs).most_common(1)[0][0]
        dm = Counter(s.domain for s in sigs).most_common(1)[0][0]
        agg = TaskSignature(
            task_type=tt, domain=dm,
            complexity=max(s.complexity for s in sigs),
            confidence=float(np.mean([s.confidence for s in sigs])))
        decision = self.engine.route(prefs, agg)
        stats = {"batch": n, "sampled": len(pick),
                 "analyzer_s": t1 - t0,
                 "route_s": time.perf_counter() - t1,
                 "aggregate_sig": agg}
        return decision, sigs, stats

    # -------------------------- serving --------------------------
    def serve(self, text: str, prefs, tokens: np.ndarray,
              max_new: int = 8) -> RoutedQuery:
        """Route + execute on the selected entry's runner."""
        rq = self.route(text, prefs)
        entry = self.mres.entry(rq.model)
        if entry.runner is not None:
            rq.response = entry.runner.generate(tokens, max_new=max_new)
        return rq

    def give_feedback(self, rq: RoutedQuery, thumbs_up: bool) -> float:
        if self.telemetry is not None:
            self.telemetry.attach_thumbs(rq.model, thumbs_up)
        return self.feedback.record(rq.sig, rq.model, thumbs_up)
