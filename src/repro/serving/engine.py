"""Batched serving engine wired to the router (paper §3.5 inference
engine + the MLaaS use-case of §2).

Requests arrive as (text, preferences); the engine routes ALL requests
in one vectorized ``route_all`` pass (interactive mode) or one
sample-and-aggregate call (batch mode), groups requests by their routed
model, executes each group as ONE batched generate call on that model's
runner, and returns per-request results with latency / cost accounting.
With a real ``TaskAnalyzer`` attached, that ``route_all`` pass is ONE
fused device program per batch — token ids in, model choices out
(``kernels/analyze_step``); the engine itself needs no knowledge of
the fusion beyond the lazy ``RoutedQuery`` accessors it already uses.
Thumbs feedback flows back into the router's FeedbackStore, and
post-generation quality observations flow into the router's adaptive
bandit via ``observe`` (shaped rewards against each routed context).

When a ``LoadTracker`` is attached (``load=`` or via the router's
engine), the serving engine maintains the live per-model capacity
signals the router scores against (admit -> start -> finish per
request) and enforces per-request latency SLOs: a request carrying
``deadline_ms`` whose routed model's estimated wait+service misses the
deadline is rerouted to its best-scoring candidate that fits, or shed
outright when none can make it (``Response.admission`` records the
outcome; counts land in ``Telemetry.admission_funnel``).  A runner
exception during one model group's generate degrades ONLY that group:
its requests come back with ``admission="failed"`` (tokens=None,
``Response.error`` carrying the cause) while every other group in the
batch is served normally — one bad model never kills the batch.

When a ``SemanticCache`` is attached (``cache=`` or via the router),
``submit`` consults it FIRST: each request's (preference axes + text
sketch) key is looked up in one fused batched pass, and a hit
short-circuits the entire analyze -> route -> admit -> generate path —
no decode slot is taken, no admission is planned, and the stored
response comes back with ``Response.cache_hit`` set (counts land in
``Telemetry.cache_funnel``).  Misses proceed normally, carrying their
cache key on the routed query so ``observe`` can write the validated
response back.
"""
from __future__ import annotations

import time
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.orchestrator import OptiRoute
from repro.core.preferences import TaskSignature, resolve_batch
from repro.core.telemetry import RouteEvent
from repro.data.tokenizer import HashTokenizer
from repro.obs.trace import NOOP_SPAN, span
from repro.serving.load import LoadTracker, plan_admission


class _Columns:
    """``name -> catalog column`` through the registry's index, read
    by key as the mapping ``plan_admission`` takes."""
    __slots__ = ("_column",)

    def __init__(self, mres):
        self._column = mres.column

    def __getitem__(self, name: str) -> int:
        return self._column(name)


@dataclass
class Request:
    text: str
    prefs: Any                        # UserPreferences | profile name | dict
    id: int = 0
    max_new: int = 8
    deadline_ms: Optional[float] = None   # latency SLO (None = no SLO)
    tenant: str = ""                  # multi-tenant attribution (traces)


@dataclass
class Response:
    request: Request
    model: str
    sig: TaskSignature
    tokens: Optional[np.ndarray]
    sim_latency_s: float
    route_s: float
    analyzer_s: float
    generate_s: float = 0.0           # measured wall time of the group's
                                      # generate, amortized per request
    fallback: str = ""
    rq: Any = None                    # RoutedQuery (adaptive loop handle)
    admission: str = "admitted"       # admitted | rerouted | shed | failed
    est_latency_s: float = 0.0        # admission-time wait+service estimate
    cache_hit: bool = False           # served from the semantic cache
    trace_id: str = ""                # this request's trace (obs.trace)
    trace_root: Any = None            # root Span handle (observe attaches)
    error: str = ""                   # failure detail (admission="failed"
                                      # or an intake rejection reason)

    @property
    def shed(self) -> bool:
        return self.admission == "shed"

    @property
    def failed(self) -> bool:
        return self.admission == "failed"

    @property
    def served(self) -> bool:
        """True when a model actually produced (or simulated) an
        answer — sheds never took a slot, fails took one but raised."""
        return self.admission in ("admitted", "rerouted")


class ServingEngine:
    def __init__(self, router: OptiRoute, *, prompt_len: int = 32,
                 vocab_hash: int = 4096,
                 load: Optional[LoadTracker] = None, cache=None,
                 tracer=None):
        self.router = router
        self.tok = HashTokenizer(vocab_hash)
        self.prompt_len = prompt_len
        self.load = load if load is not None \
            else getattr(router.engine, "load", None)
        self.cache = cache if cache is not None \
            else getattr(router, "cache", None)
        # span sink (obs.trace.Tracer): defaults to the router's, so
        # one tracer covers submit -> route -> kernel dispatch; the
        # attached cache inherits it too (its lookup span must nest
        # under the same batch trace)
        self.tracer = tracer if tracer is not None \
            else getattr(router, "tracer", None)
        if (self.cache is not None and self.tracer is not None
                and getattr(self.cache, "tracer", None) is None):
            self.cache.tracer = self.tracer
        router_cache = getattr(router, "cache", None)
        if cache is not None and router_cache is None:
            # the write-back lives in OptiRoute.observe — an
            # engine-attached cache must be visible there too, or every
            # lookup misses forever (keys stamped, nothing ever stored)
            router.cache = cache
        elif cache is not None and router_cache is not cache:
            # two different stores would split lookup (engine) from
            # write-back (router) into a permanent 0% hit rate
            raise ValueError("ServingEngine(cache=...) conflicts with "
                             "the router's own cache — attach ONE store")
        self.log: List[Response] = []

    def _tokens(self, texts: Sequence[str], vocab_size: int) -> np.ndarray:
        t = self.tok.encode_batch(texts, self.prompt_len)
        return np.clip(t, 0, vocab_size - 1).astype(np.int32)

    # ------------------------------------------------------------------
    def submit(self, requests: Sequence[Request], *,
               mode: str = "interactive") -> List[Response]:
        assert mode in ("interactive", "batch")
        if not requests:
            return []
        if mode == "batch":
            return self._submit_batch(requests)
        # interactive: the semantic cache answers repeats FIRST (one
        # fused batched lookup; a hit skips analyze/route/admit/
        # generate and takes no slot), then the misses flow through
        # one vectorized routing pass + deadline-aware admission +
        # grouped batched generation
        reqs = list(requests)
        out: List[Optional[Response]] = [None] * len(reqs)
        keys = fps = None
        miss = list(range(len(reqs)))
        tel = self.router.telemetry
        with span(self.tracer, "submit", batch=len(reqs),
                  mode="interactive") as batch_span:
            # featurize each request's preferences EXACTLY once: the
            # resolved UserPreferences instances (with their memoized
            # weight vectors) feed the cache key vectors, the
            # fingerprint gates, AND — threaded through to route_all —
            # the routing task vectors, instead of re-resolving (and
            # for dict prefs, re-vectorizing) per consumer
            prefs_res = resolve_batch([r.prefs for r in reqs], len(reqs))
            if self.cache is not None:
                keys = self.cache.keys_for(prefs_res,
                                           [r.text for r in reqs])
                # the decoding budget joins the exact-match gate: a
                # 4-token answer must never serve a 256-token request
                fps = self.cache.fingerprints(
                    prefs_res, extras=[r.max_new for r in reqs])
                # entries materialize under the store's lock: a
                # concurrent eviction can never invalidate a hit
                # between lookup and use
                hit, entries, _ = self.cache.lookup_entries(keys, fps)
                if tel is not None:
                    for kind, n in self.cache.drain_events().items():
                        tel.record_cache(kind, n)
                miss = []
                for i, r in enumerate(reqs):
                    if tel is not None:
                        tel.record_cache("hit" if hit[i] else "miss")
                    if hit[i]:
                        e = entries[i]
                        out[i] = Response(
                            request=r, model=e.model, sig=e.sig,
                            tokens=e.response, sim_latency_s=0.0,
                            route_s=0.0, analyzer_s=0.0, cache_hit=True)
                    else:
                        miss.append(i)
            if miss:
                served = self._route_and_serve(
                    [reqs[i] for i in miss],
                    [prefs_res[i] for i in miss],
                    None if keys is None else keys[miss],
                    None if fps is None else fps[miss])
                for j, i in enumerate(miss):
                    out[i] = served[j]
        self._fanout_trace(reqs, out, batch_span)
        self.log.extend(out)            # type: ignore[arg-type]
        return out                      # type: ignore[return-value]

    def _fanout_trace(self, reqs: Sequence[Request],
                      out: Sequence[Response], batch_span) -> None:
        """Fan the batch-level spans out to one trace PER REQUEST: a
        ``request`` root carrying ids and verdicts, with child spans
        for exactly the stages that ran for it (a cache hit gets only
        its ``cache_lookup``; a shed request stops at ``admission``).
        Durations are measured wall times: the batch's analyze and
        route costs and the request's group's generate, each amortized
        per request.
        Each ``Response`` leaves with its ``trace_id``/``trace_root``
        stamped so later ``observe`` calls can attach to the tree."""
        tr = self.tracer
        if tr is None or not tr.enabled:
            return
        B = len(reqs)
        for r, resp in zip(reqs, out):
            root = tr.record_span(
                "request",
                duration_s=resp.analyzer_s + resp.route_s
                + resp.generate_s,
                request_id=r.id, tenant=r.tenant, batch=B,
                batch_trace=batch_span.trace_id, model=resp.model,
                admission=resp.admission, cache_hit=resp.cache_hit)
            resp.trace_id = root.trace_id
            resp.trace_root = root
            if self.cache is not None:
                tr.record_span(
                    "cache_lookup", parent=root,
                    outcome="hit" if resp.cache_hit else "miss")
            if resp.cache_hit:   # short-circuit: no route/admit/generate
                continue
            tr.record_span("analyze", parent=root,
                           duration_s=resp.analyzer_s)
            tr.record_span("route_step", parent=root,
                           duration_s=resp.route_s,
                           fallback=resp.fallback)
            if self.load is not None and r.deadline_ms is not None:
                tr.record_span("admission", parent=root,
                               verdict=resp.admission,
                               est_latency_s=resp.est_latency_s)
            if resp.failed:
                # the group DID take a slot and raise — the trace tree
                # shows the failed generate stage, not a missing one
                tr.record_span("generate", parent=root,
                               duration_s=0.0, model=resp.model,
                               outcome="failed", error=resp.error)
            elif not resp.shed:
                tr.record_span("generate", parent=root,
                               duration_s=resp.generate_s,
                               model=resp.model)

    def _route_and_serve(self, requests: Sequence[Request], prefs_res,
                         cache_keys, cache_fps) -> List[Response]:
        """Route -> admit -> generate for the cache-miss rows (or the
        whole batch when no cache is attached).  ``prefs_res`` carries
        the already-resolved per-request preferences so routing reuses
        the submit-time featurization."""
        routed_q = self.router.route_all([r.text for r in requests],
                                         prefs_res)
        if cache_keys is not None:
            # stamp each routed query with its write-back key: when the
            # outcome later validates well, observe() turns this miss
            # into the entry answering the next near-duplicate
            for j, rq in enumerate(routed_q):
                rq.cache_key = np.asarray(cache_keys[j])
                rq.cache_fp = int(cache_fps[j])
        routed = list(zip(requests, routed_q))
        col = _Columns(self.router.mres)
        if self.load is not None:
            self.load.ensure(len(self.router.mres.snapshot()[1]))
        plans = []
        tel = self.router.telemetry
        # pending placements from EARLIER requests in this same batch:
        # request #50 of a burst must see the 49 ahead of it, or the
        # whole batch is waved through against a frozen snapshot
        # sized to the TRACKER (which may carry more arms than the
        # catalog) so estimated_latency_s can add it elementwise
        pending = np.zeros(self.load.n_models, np.int64) \
            if self.load is not None else None
        tr = self.tracer
        adm_span = span(tr, "admission", batch=len(routed)) \
            if self.load is not None else NOOP_SPAN
        with adm_span:
            for r, rq in routed:
                if self.load is None:
                    plans.append((rq.model, "admitted", 0.0))
                    continue
                if r.deadline_ms is None:
                    # no SLO: admitted as routed, but the placement
                    # still counts toward what LATER requests in this
                    # batch see.  rq.model reads the batch arrays — the
                    # full decision object only materializes for
                    # deadline-carrying requests, whose candidate lists
                    # admission ranks over
                    model, kind, est = rq.model, "admitted", 0.0
                else:
                    # the funnel is recorded AFTER generation (one
                    # final outcome per request), not here: an admitted
                    # request whose group later fails must count as
                    # "failed", not "admitted"
                    model, kind, est = plan_admission(
                        rq.decision, self.load, col, r.deadline_ms,
                        pending=pending)
                plans.append((model, kind, est))
                if pending is not None and kind != "shed":
                    pending[col[model]] += 1
        groups: Dict[Tuple[str, int], List[int]] = defaultdict(list)
        for i, (r, _) in enumerate(routed):
            model, kind, _ = plans[i]
            if kind != "shed":
                groups[(model, r.max_new)].append(i)
        out: List[Optional[Response]] = [None] * len(requests)
        with span(tr, "generate", groups=len(groups)):
            for (model, max_new), idxs in groups.items():
                with span(tr, "catalog_lookup", model=model):
                    entry = self.router.mres.entry(model)
                g0 = time.perf_counter()
                if self.load is not None:
                    self.load.admit(col[model], count=len(idxs))
                    self.load.start(col[model], count=len(idxs))
                gen, per_req_s, err = None, None, ""
                try:
                    if entry.runner is not None:
                        toks = self._tokens(
                            [requests[i].text for i in idxs],
                            entry.runner.cfg.vocab_size)
                        gen = entry.runner.generate(toks, max_new=max_new)
                    per_req_s = (gen.sim_latency_s / len(idxs)
                                 if gen is not None else
                                 entry.raw_metrics.get("latency_ms",
                                                       0.0) / 1e3)
                except Exception as e:             # noqa: BLE001
                    # one model group failing must never kill the other
                    # groups in the batch: degrade THIS group to
                    # admission="failed" responses and keep serving
                    err = f"{type(e).__name__}: {e}"
                finally:
                    # a generate failure must still release the slots,
                    # or the model's inflight count (and its routing
                    # penalty) stays inflated forever; no EWMA sample
                    # on failure (per_req_s is still None then)
                    if self.load is not None:
                        self.load.finish(col[model], per_req_s,
                                         count=len(idxs))
                gen_s = (time.perf_counter() - g0) / len(idxs)
                for j, i in enumerate(idxs):
                    r, rq = routed[i]
                    # a rerouted request was SERVED by a different
                    # model than its routed decision, and a failed one
                    # produced no outcome at all; dropping the rq
                    # handle keeps observe() from crediting the wrong
                    # (or any) bandit arm
                    out[i] = Response(
                        request=r, model=model, sig=rq.sig,
                        tokens=None if (gen is None or err)
                        else gen.tokens[j],
                        sim_latency_s=0.0 if (gen is None or err)
                        else per_req_s,
                        route_s=rq.route_s, analyzer_s=rq.analyzer_s,
                        generate_s=gen_s, fallback=rq.fallback_kind,
                        rq=rq if (plans[i][1] == "admitted" and not err)
                        else None,
                        admission="failed" if err else plans[i][1],
                        est_latency_s=plans[i][2], error=err)
        for i, (r, rq) in enumerate(routed):   # shed: fail fast, no slot
            if out[i] is None:
                out[i] = Response(
                    request=r, model=plans[i][0], sig=rq.sig, tokens=None,
                    sim_latency_s=0.0, route_s=rq.route_s,
                    analyzer_s=rq.analyzer_s,
                    fallback=rq.fallback_kind, rq=None,
                    admission="shed", est_latency_s=plans[i][2])
        # ONE funnel entry per request, recording the FINAL outcome:
        # deadline-carrying requests land their admission verdict, and
        # a failed group is always recorded (even SLO-less traffic) —
        # the funnel is how an operator sees the failure at all
        if tel is not None:
            for i, (r, _) in enumerate(routed):
                resp = out[i]
                if resp.failed or (self.load is not None
                                   and r.deadline_ms is not None):
                    tel.record_admission(resp.admission,
                                         tenant=r.tenant or None)
        return out                      # type: ignore[return-value]

    def _submit_batch(self, requests: Sequence[Request]) -> List[Response]:
        """Sample-and-aggregate batch mode with the SAME serving
        lifecycle as interactive mode: the semantic cache answers
        repeats first, the miss rows share ONE routed decision
        (``route_batch``), the load tracker sees admit -> start ->
        finish around the single grouped generate, telemetry records
        one route event per served request, and the batch fans out to
        per-request traces.  Batch responses still carry no ``rq``
        handle (one aggregate decision has no per-query bandit
        context), so ``observe`` skips them — the cache is lookup-only
        in this mode."""
        reqs = list(requests)
        out: List[Optional[Response]] = [None] * len(reqs)
        tel = self.router.telemetry
        with span(self.tracer, "submit", batch=len(reqs),
                  mode="batch") as batch_span:
            prefs_res = resolve_batch([r.prefs for r in reqs], len(reqs))
            miss = list(range(len(reqs)))
            if self.cache is not None:
                keys = self.cache.keys_for(prefs_res,
                                           [r.text for r in reqs])
                fps = self.cache.fingerprints(
                    prefs_res, extras=[r.max_new for r in reqs])
                hit, entries, _ = self.cache.lookup_entries(keys, fps)
                if tel is not None:
                    for kind, n in self.cache.drain_events().items():
                        tel.record_cache(kind, n)
                miss = []
                for i, r in enumerate(reqs):
                    if tel is not None:
                        tel.record_cache("hit" if hit[i] else "miss")
                    if hit[i]:
                        e = entries[i]
                        out[i] = Response(
                            request=r, model=e.model, sig=e.sig,
                            tokens=e.response, sim_latency_s=0.0,
                            route_s=0.0, analyzer_s=0.0, cache_hit=True)
                    else:
                        miss.append(i)
            if miss:
                served = self._serve_batch_group([reqs[i] for i in miss])
                for j, i in enumerate(miss):
                    out[i] = served[j]
        self._fanout_trace(reqs, out, batch_span)
        self.log.extend(out)            # type: ignore[arg-type]
        return out                      # type: ignore[return-value]

    def _serve_batch_group(self, requests: Sequence[Request]
                           ) -> List[Response]:
        """One aggregate decision -> one batched generate, with full
        tracker lifecycle, per-group failure degradation and telemetry
        (the batch-mode twin of ``_route_and_serve``'s group loop)."""
        texts = [r.text for r in requests]
        decision, _, stats = self.router.route_batch(
            texts, requests[0].prefs)
        model = decision.model
        with span(self.tracer, "catalog_lookup", model=model):
            entry = self.router.mres.entry(model)
        tel = self.router.telemetry
        col = -1
        if self.load is not None:
            col = self.router.mres.column(model)
            self.load.ensure(len(self.router.mres.snapshot()[1]))
            self.load.admit(col, count=len(requests))
            self.load.start(col, count=len(requests))
        g0 = time.perf_counter()
        gen, per_req_s, err = None, None, ""
        try:
            if entry.runner is not None:
                toks = self._tokens(texts, entry.runner.cfg.vocab_size)
                gen = entry.runner.generate(toks,
                                            max_new=requests[0].max_new)
            per_req_s = (gen.sim_latency_s / len(requests)
                         if gen is not None else
                         entry.raw_metrics.get("latency_ms", 0.0) / 1e3)
        except Exception as e:                     # noqa: BLE001
            err = f"{type(e).__name__}: {e}"
        finally:
            if self.load is not None:
                self.load.finish(col, per_req_s, count=len(requests))
        gen_s = (time.perf_counter() - g0) / len(requests)
        agg = stats["aggregate_sig"]
        out = [Response(
            request=r, model=model, sig=agg,
            tokens=None if (gen is None or err) else gen.tokens[i],
            sim_latency_s=0.0 if (gen is None or err) else per_req_s,
            route_s=stats["route_s"] / len(requests),
            analyzer_s=stats["analyzer_s"] / len(requests),
            generate_s=gen_s, fallback=decision.fallback_kind,
            admission="failed" if err else "admitted",
            error=err) for i, r in enumerate(requests)]
        if tel is not None:
            sim_cost = entry.raw_metrics.get("cost_per_mtok", 0.0)
            for resp in out:
                # route_batch records nothing itself: one event per
                # request served, so sustained batch traffic shows up
                # in QPS / per-model aggregates like interactive does
                tel.record(RouteEvent(
                    ts=time.time(), model=model,
                    task_type=agg.task_type, domain=agg.domain,
                    complexity=agg.complexity,
                    fallback=decision.fallback_kind,
                    analyzer_s=resp.analyzer_s, route_s=resp.route_s,
                    sim_cost=sim_cost))
                if resp.failed:
                    tel.record_admission(
                        "failed", tenant=resp.request.tenant or None)
        return out

    # ------------------------------------------------------------------
    def feedback(self, resp: Response, thumbs_up: bool) -> float:
        return self.router.feedback.record(resp.sig, resp.model, thumbs_up)

    def observe(self, responses: Sequence[Response],
                qualities: Sequence[float]):
        """Close the adaptive loop with post-generation ground truth:
        shaped rewards (quality minus cost/latency penalties) flow into
        the router's bandit against each response's routed context.
        Responses without a routed-query handle are skipped: the
        sample-and-aggregate batch mode (no per-query context), and
        rerouted/shed requests (the routed decision's model is not the
        one that produced — or failed to produce — the outcome)."""
        if len(responses) != len(qualities):
            raise ValueError(f"{len(responses)} responses but "
                             f"{len(qualities)} qualities — observations "
                             "must align one-to-one")
        tr = self.tracer
        pairs = []
        for r, q in zip(responses, qualities):
            if r.rq is None:
                continue
            # hand the generated payload to the routed query so the
            # router's observe() can write it into the semantic cache
            if r.rq.response is None:
                r.rq.response = r.tokens
            # the outcome joins the request's own trace tree, not just
            # the router-level batch span
            if tr is not None and r.trace_root is not None:
                tr.record_span("observe", parent=r.trace_root,
                               quality=float(q), model=r.model)
            pairs.append((r.rq, q))
        if not pairs:
            return None
        return self.router.observe([p[0] for p in pairs],
                                   [p[1] for p in pairs])

    def summary(self) -> Dict[str, Any]:
        if not self.log:
            return {}
        by_model: Dict[str, int] = defaultdict(int)
        lat: Dict[str, List[float]] = defaultdict(list)
        admissions: Dict[str, int] = defaultdict(int)
        cache_hits = 0
        for r in self.log:
            if r.cache_hit:   # answered from the cache: no admission
                cache_hits += 1    # outcome, no slot, no model latency
                continue
            admissions[r.admission] += 1
            if not r.served:  # shed/failed requests were served by NO
                continue      # model — they only show up in the
                              # admission counts
            by_model[r.model] += 1
            lat[r.model].append(r.sim_latency_s + r.route_s
                                + r.analyzer_s)
        # per-model end-to-end latency PERCENTILES, not means: tails
        # are what SLOs are written against, and a mean hides the
        # queueing spikes load-aware routing exists to prevent
        latency = {m: {"p50_s": float(np.quantile(v, 0.5)),
                       "p99_s": float(np.quantile(v, 0.99))}
                   for m, v in lat.items()}
        return {
            "requests": len(self.log),
            "sim_latency_s": sum(r.sim_latency_s for r in self.log),
            "route_s": sum(r.route_s for r in self.log),
            "analyzer_s": sum(r.analyzer_s for r in self.log),
            "models": dict(by_model),
            "latency": latency,
            "admissions": dict(admissions),
            "cache_hits": cache_hits,
        }
