"""Serving launcher: the deployable OptiRoute service loop.

Builds the 10-architecture MRES catalog (reduced runners on CPU), loads
or trains the Task Analyzer, then serves a synthetic request stream
through the batched ServingEngine, printing per-request routing
decisions and the final accounting summary.

  PYTHONPATH=src python -m repro.launch.serve --requests 24 --mode interactive

``--async`` drives the same request stream through the asyncio
front-end (``AsyncServingEngine``): per-request awaitable submits,
micro-batch aggregation windows, per-tenant attribution.  ``--soak
SECONDS`` replays a bursty multi-tenant episode (two quiet tenants plus
a rate-limited flooding one) through the engine's window path in
virtual time and prints the per-tenant admission tally.
"""
from __future__ import annotations

import argparse
import json
import pathlib

import numpy as np

from repro.core.analyzer import AnalyzerConfig, TaskAnalyzer
from repro.core.orchestrator import OptiRoute
from repro.core.preferences import PROFILES
from repro.data.workload import make_workload
from repro.launch.compile_cache import enable_compile_cache
from repro.serving.catalog import build_catalog
from repro.serving.engine import Request, ServingEngine

ANALYZER_CKPT = pathlib.Path(__file__).resolve().parents[3] / "results" / "analyzer.npz"


def load_analyzer(train_steps: int = 250) -> TaskAnalyzer:
    an = TaskAnalyzer(AnalyzerConfig())
    if ANALYZER_CKPT.exists():
        from repro.checkpoint import load
        an.params, _ = load(str(ANALYZER_CKPT))
        return an
    print("[serve] training task analyzer (first run only) ...")
    metrics = an.train(steps=train_steps)
    from repro.checkpoint import save
    save(str(ANALYZER_CKPT), an.params, {"metrics": metrics})
    return an


def _run_async(engine, reqs, args):
    """Drive ``reqs`` through the asyncio front-end; return responses."""
    import asyncio

    from repro.serving.async_engine import AsyncServingEngine

    tenants = ("acme", "globex")
    for i, r in enumerate(reqs):
        r.tenant = r.tenant or tenants[i % len(tenants)]
    aeng = AsyncServingEngine(engine, max_batch=args.max_batch,
                              max_wait_ms=args.max_wait_ms)
    print(f"[serve] submitting {len(reqs)} requests (async, "
          f"max_batch={args.max_batch} max_wait={args.max_wait_ms}ms) ...")

    async def _drive():
        async with aeng:
            return await asyncio.gather(*(aeng.submit(r) for r in reqs))

    resps = asyncio.run(_drive())
    print(f"[serve] async windows: {aeng.windows}")
    return resps


def _run_soak(engine, telemetry, args):
    """Virtual-time bursty multi-tenant replay through the window path.

    Two well-behaved tenants plus a rate-limited flooding one; every
    window goes through the same ``engine.submit`` hot path the flat
    stream uses.  Prints the per-tenant admission funnel.
    """
    from repro.data.workload import (MultiTenantScenario, TenantSpec,
                                     TrafficScenario, multi_tenant_arrivals)
    from repro.serving.async_engine import MicroBatcher, TenantPolicy

    sc = MultiTenantScenario(
        base=TrafficScenario(duration_s=float(args.soak), base_rate=4.0,
                             burst_rate=16.0, burst_start=0.3,
                             burst_len=0.3, deadline_ms=800.0,
                             seed=args.seed),
        tenants=(TenantSpec("acme", weight=2.0),
                 TenantSpec("globex"),
                 TenantSpec("flood", rate_scale=3.0, rate_limit=6.0,
                            deadline_ms=400.0)))
    times, tidx = multi_tenant_arrivals(sc)
    wl = make_workload(64, seed=args.seed + 1)
    mb = MicroBatcher(
        max_batch=args.max_batch, max_wait_s=args.max_wait_ms / 1e3,
        policies={t.name: TenantPolicy(weight=t.weight, rate=t.rate_limit)
                  for t in sc.tenants})
    tally: dict = {}
    windows = []

    def bump(tenant, kind):
        tally.setdefault(tenant, {}).setdefault(kind, 0)
        tally[tenant][kind] += 1

    def flush(now):
        items = mb.take(now)
        if not items:
            return
        windows.append(len(items))
        for r in engine.submit(items):
            bump(r.request.tenant, r.admission)

    print(f"[serve] soak: replaying {times.size} arrivals over "
          f"{float(args.soak):.0f}s virtual time ...")
    for k in range(times.size):
        t = float(times[k])
        while True:                       # flush windows that came due
            dl = mb.next_deadline(t)
            if dl is None or dl > t:
                break
            flush(dl)
        ti = int(tidx[k])
        name = sc.tenants[ti].name
        src = wl[k % len(wl)]
        req = Request(text=src.text, prefs="balanced", id=k,
                      max_new=args.max_new,
                      deadline_ms=sc.deadline_ms_of(ti), tenant=name)
        if mb.offer(name, req, t) != "queued":
            bump(name, "shed")            # intake-level rejection
            if telemetry is not None:
                telemetry.record_admission("shed", tenant=name)
    end = float(times[-1]) if times.size else 0.0
    while mb.pending():                   # drain the tail
        dl = mb.next_deadline(end)
        end = max(end, dl if dl is not None else end)
        flush(end)

    print(f"[serve] soak: {len(windows)} windows "
          f"(max {max(windows) if windows else 0})")
    for name in sorted(tally):
        print(f"  {name:>8}: "
              + ", ".join(f"{k}={v}" for k, v in sorted(tally[name].items())))
    print("[serve] summary:", json.dumps(engine.summary(), indent=2))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--mode", choices=("interactive", "batch"),
                    default="interactive")
    ap.add_argument("--profile", default=None,
                    help="force one preference profile; default cycles")
    ap.add_argument("--max-new", type=int, default=4)
    ap.add_argument("--archs", nargs="*", default=None,
                    help="subset of catalog archs to load runners for")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--merge-threshold", type=float, default=None)
    ap.add_argument("--metrics-out", default=None,
                    help="dump Prometheus text exposition here "
                         "(e.g. results/metrics.prom)")
    ap.add_argument("--trace-out", default=None,
                    help="dump the span ring as JSONL here "
                         "(e.g. results/trace.jsonl)")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="serve GET /metrics on this port while the "
                         "request stream runs (0 = ephemeral)")
    ap.add_argument("--async", dest="use_async", action="store_true",
                    help="drive requests through the asyncio front-end "
                         "(micro-batch windows + per-tenant intake)")
    ap.add_argument("--soak", type=float, default=None, metavar="SECONDS",
                    help="replay a SECONDS-long bursty multi-tenant "
                         "episode through the window path in virtual "
                         "time instead of the flat request stream")
    ap.add_argument("--max-batch", type=int, default=8,
                    help="aggregation window size (--async / --soak)")
    ap.add_argument("--max-wait-ms", type=float, default=10.0,
                    help="aggregation window age bound (--async / --soak)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    obs_on = (args.metrics_out or args.trace_out
              or args.metrics_port is not None)
    tracer = telemetry = None
    if obs_on:
        from repro.core.telemetry import Telemetry
        from repro.obs import Tracer
        tracer = Tracer()
        telemetry = Telemetry()

    print("[serve] building catalog (reduced runners) ...")
    mres = build_catalog(smoke_runners=True, archs=args.archs)
    analyzer = load_analyzer()
    extra = {}
    if args.use_async or args.soak is not None:
        # the window path exercises deadline admission, so give the
        # router a live load tracker (the flat stream keeps the
        # original load-blind behaviour)
        from repro.serving.load import LoadTracker
        extra = dict(load=LoadTracker(default_service_s=0.05),
                     load_weight=1.0)
    router = OptiRoute(mres, analyzer, merge_threshold=args.merge_threshold,
                       telemetry=telemetry, tracer=tracer, **extra)
    engine = ServingEngine(router)

    server = None
    if args.metrics_port is not None:
        from repro.obs import serve_metrics
        server = serve_metrics(telemetry, tracer=tracer,
                               port=args.metrics_port)
        print(f"[serve] /metrics on http://127.0.0.1:{server.port}/metrics")

    profiles = ([args.profile] if args.profile
                else list(PROFILES))
    if args.soak is not None:
        _run_soak(engine, telemetry, args)
    else:
        wl = make_workload(args.requests, seed=args.seed)
        reqs = [Request(text=r.text, prefs=profiles[i % len(profiles)],
                        id=r.id, max_new=args.max_new)
                for i, r in enumerate(wl)]
        if args.use_async:
            resps = _run_async(engine, reqs, args)
        else:
            print(f"[serve] submitting {len(reqs)} requests "
                  f"({args.mode}) ...")
            resps = engine.submit(reqs, mode=args.mode)
        for r in resps:
            print(f"  #{r.request.id:>3} prefs={r.request.prefs:<18} "
                  f"sig=({r.sig.task_type}/{r.sig.domain}"
                  f"/{r.sig.complexity:.2f}) -> {r.model}"
                  f"{'  [' + r.fallback + ']' if r.fallback else ''}")
            # thumbs: synthetic user approves iff the routed model is
            # tagged for the task type
            entry = mres.entry(r.model)
            engine.feedback(r,
                            thumbs_up=r.sig.task_type in entry.task_types)
        print("[serve] summary:", json.dumps(engine.summary(), indent=2))

    if args.metrics_out:
        from repro.obs import write_prom
        pathlib.Path(args.metrics_out).parent.mkdir(parents=True,
                                                    exist_ok=True)
        write_prom(args.metrics_out, telemetry, load=engine.load,
                   tracer=tracer)
        print(f"[serve] metrics -> {args.metrics_out}")
    if args.trace_out:
        pathlib.Path(args.trace_out).parent.mkdir(parents=True,
                                                  exist_ok=True)
        n = tracer.export_jsonl(args.trace_out)
        print(f"[serve] {n} spans -> {args.trace_out}")
    if server is not None:
        server.close()


if __name__ == "__main__":
    main()
