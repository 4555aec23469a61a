"""Observability package: tracer span trees, fixed-memory metric
primitives, Prometheus export round-trip, SLO rules (point + burn
rate), and the device cost profiler hook."""
import json
import math
import threading
import urllib.request

import numpy as np
import pytest

from repro.core.telemetry import RouteEvent, Telemetry
from repro.obs import (DeviceCostProfiler, Tracer, evaluate_rules,
                       metrics_from_prom, parse_prom_text, parse_rule,
                       parse_rules, prometheus_text, serve_metrics,
                       trace_capture)
from repro.obs.metrics import Counter, Gauge, LogHistogram
from repro.obs.slo import SLOEvaluator
from repro.obs.trace import NOOP_SPAN, span


def _ev(ts=1.0, model="m0", fallback="", route_s=0.01, cost=2.0):
    return RouteEvent(ts=ts, model=model, task_type="chat",
                      domain="general", complexity=0.5,
                      fallback=fallback, route_s=route_s, sim_cost=cost)


# ----------------------------------------------------------------------
# tracer
# ----------------------------------------------------------------------
def test_span_nesting_via_contextvar():
    """Nesting needs no explicit parent argument: a span opened inside
    another's ``with`` block (even across function boundaries) becomes
    its child in the same trace."""
    tr = Tracer()

    def inner_layer():                 # no span threading through args
        with tr.span("route_step", batch=4):
            pass

    with tr.start_trace("submit", mode="interactive") as root:
        with tr.span("analyze") as mid:
            inner_layer()

    spans = tr.spans(root.trace_id)
    assert [s.name for s in spans] == ["route_step", "analyze", "submit"]
    by_name = {s.name: s for s in spans}
    assert by_name["analyze"].parent_id == root.span_id
    assert by_name["route_step"].parent_id == mid.span_id
    assert all(s.trace_id == root.trace_id for s in spans)
    tree = tr.summary_tree(root.trace_id)
    assert tree["name"] == "submit"
    assert tree["children"][0]["name"] == "analyze"
    assert tree["children"][0]["children"][0]["name"] == "route_step"


def test_start_trace_always_roots():
    tr = Tracer()
    with tr.start_trace("outer"):
        with tr.start_trace("fresh") as f:
            assert f.parent_id == ""
    assert len({s.trace_id for s in tr.spans()}) == 2


def test_span_attrs_and_set():
    tr = Tracer()
    with tr.span("route_step", path="dense") as sp:
        sp.set(compiles=1)
    (s,) = tr.spans()
    assert s.attrs == {"path": "dense", "compiles": 1}
    assert s.duration_s >= 0.0


def test_record_span_fanout():
    """Retrospective fan-out: one already-finished child per request,
    rooted on demand, stamped with the amortized duration."""
    tr = Tracer()
    root = tr.record_span("request", request_id=7, duration_s=0.25)
    child = tr.record_span("generate", parent=root, duration_s=0.2,
                           model="m1")
    assert root.trace_id and root.parent_id == ""
    assert child.trace_id == root.trace_id
    assert child.parent_id == root.span_id
    tree = tr.summary_tree(root.trace_id)
    assert tree["attrs"]["request_id"] == 7
    assert tree["duration_s"] == pytest.approx(0.25)
    assert [c["name"] for c in tree["children"]] == ["generate"]


def test_disabled_tracer_is_noop(tmp_path):
    tr = Tracer(enabled=False)
    with tr.start_trace("submit") as root:
        with tr.span("analyze") as sp:
            sp.set(batch=3)
    assert root is NOOP_SPAN and sp is NOOP_SPAN
    assert tr.record_span("request") is NOOP_SPAN
    assert tr.stats() == {"spans_total": 0, "spans_retained": 0,
                          "max_spans": 16384}
    assert tr.export_jsonl(tmp_path / "t.jsonl") == 0


def test_span_helper_without_tracer_is_profiler_only():
    """``span`` with no tracer, or a disabled one, records nothing and
    still behaves as a span (``set``, ids) for the code inside it."""
    for tr in (None, Tracer(enabled=False)):
        with span(tr, "route_step", path="dense") as sp:
            sp.set(compiles=0)
        assert sp is not NOOP_SPAN
        assert sp.trace_id == "" and sp.attrs == {}
        if tr is not None:
            assert tr.stats()["spans_total"] == 0


def test_span_helper_with_tracer_records_into_the_ring():
    tr = Tracer()
    stats = {"size": 2, "wait_ms_sum": 3.5, "wait_ms_max": 2.0,
             "backlog": 0}
    with span(tr, "window", stats=stats) as w:
        with span(tr, "submit", batch=2) as sub:
            assert tr.current() is sub
    assert w.stats == stats and sub.stats == {}
    assert sub.parent_id == w.span_id and sub.trace_id == w.trace_id
    assert w.attrs == stats and sub.attrs == {"batch": 2}
    assert [s.name for s in tr.spans()] == ["submit", "window"]


def test_span_ring_bounded_and_monotonic():
    tr = Tracer(max_spans=8)
    first = tr.record_span("request", i=0)
    for i in range(1, 100):
        tr.record_span("request", i=i)
    stats = tr.stats()
    assert stats == {"spans_total": 100, "spans_retained": 8,
                     "max_spans": 8}
    assert [s.attrs["i"] for s in tr.spans()] == list(range(92, 100))
    assert tr.summary_tree(first.trace_id) is None   # evicted


def test_export_jsonl_roundtrip(tmp_path):
    tr = Tracer()
    with tr.start_trace("submit", batch=2) as root:
        with tr.span("route_step", path="dense"):
            pass
    path = tmp_path / "trace.jsonl"
    assert tr.export_jsonl(path) == 2
    recs = [json.loads(ln) for ln in path.read_text().splitlines()]
    assert {r["name"] for r in recs} == {"submit", "route_step"}
    for r in recs:
        assert set(r) == {"trace_id", "span_id", "parent_id", "name",
                          "ts", "duration_s", "attrs"}
        assert r["trace_id"] == root.trace_id
    # filtered export: only the requested trace
    other = Tracer()
    other.record_span("request")
    assert tr.export_jsonl(path, trace_id="t_nonexistent") == 0


def test_current_tracks_ambient_span():
    tr = Tracer()
    assert tr.current() is None
    with tr.span("outer") as o:
        assert tr.current() is o
        with tr.span("inner") as i:
            assert tr.current() is i
        assert tr.current() is o
    assert tr.current() is None


def test_tracer_thread_safe_record():
    tr = Tracer(max_spans=256)

    def worker(k):
        for i in range(200):
            with tr.span(f"w{k}", i=i):
                pass

    threads = [threading.Thread(target=worker, args=(k,))
               for k in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    stats = tr.stats()
    assert stats["spans_total"] == 800
    assert stats["spans_retained"] == 256


# ----------------------------------------------------------------------
# metric primitives
# ----------------------------------------------------------------------
def test_counter_gauge_labels():
    c = Counter("reqs", "requests")
    c.inc(), c.inc(2.0, label="m1")
    assert c.value() == 1.0 and c.value("m1") == 2.0
    assert c.items() == {"": 1.0, "m1": 2.0}
    with pytest.raises(AssertionError):
        c.inc(-1.0)
    g = Gauge("depth")
    g.set(3.0, label="m0")
    g.set(1.5, label="m0")
    assert g.value("m0") == 1.5 and g.value("missing") == 0.0


def test_log_histogram_quantile_accuracy():
    h = LogHistogram()
    vals = (np.arange(1, 2001)) / 1000.0        # 1ms .. 2s uniform
    for v in vals:
        h.record(float(v))
    assert h.count == 2000
    assert h.mean() == pytest.approx(float(vals.mean()))
    for q in (0.1, 0.5, 0.9, 0.99):
        assert h.quantile(q) == pytest.approx(float(np.quantile(vals, q)),
                                              rel=0.02)
    qs = h.quantiles((0.5, 0.9))
    assert qs[0] <= qs[1]


def test_log_histogram_single_sample_exact():
    h = LogHistogram()
    h.record(0.5)
    assert h.quantile(0.5) == h.quantile(0.99) == 0.5
    assert h.snapshot() == {"count": 1, "sum": 0.5, "min": 0.5,
                            "max": 0.5}


def test_log_histogram_edges():
    h = LogHistogram(lo=1e-3, hi=1e1)
    assert h.quantile(0.5) == 0.0               # empty
    h.record(0.0)                               # non-positive -> underflow
    h.record(-1.0)
    assert h.count == 2 and h.quantile(0.5) == 0.0
    h.record(1e-9)                              # below lo: clamps to vmin
    h.record(1e9)                               # above hi: clamps to vmax
    assert h.quantile(0.0) >= 0.0
    assert h.quantile(1.0) == 1e9
    assert math.isclose(h.snapshot()["max"], 1e9)


def test_log_histogram_merge():
    a, b = LogHistogram(), LogHistogram()
    for v in (0.01, 0.02, 0.04):
        a.record(v)
    for v in (0.08, 0.16):
        b.record(v)
    ref = LogHistogram()
    for v in (0.01, 0.02, 0.04, 0.08, 0.16):
        ref.record(v)
    a.merge(b)
    assert a.count == 5 and a.total == pytest.approx(ref.total)
    assert a.quantile(0.5) == pytest.approx(ref.quantile(0.5))
    assert a.snapshot() == pytest.approx(ref.snapshot())
    with pytest.raises(AssertionError):         # incompatible buckets
        a.merge(LogHistogram(lo=1e-2, hi=1e2))


# ----------------------------------------------------------------------
# prometheus export
# ----------------------------------------------------------------------
def _filled_telemetry():
    tel = Telemetry()
    for i in range(10):
        tel.record(_ev(ts=100.0 + i, model=f"m{i % 2}",
                       fallback="any" if i == 9 else "",
                       route_s=0.01 * (i + 1)))
    tel.record_admission("admitted", count=8)
    tel.record_admission("shed", count=2)
    tel.record_cache("hit", count=3)
    tel.record_cache("miss", count=7)
    tel.record_route_step(dispatches=5, compiles=1)
    tel.record_sharding(silent_replications=1)
    return tel


def test_prometheus_text_round_trip():
    tel = _filled_telemetry()
    tr = Tracer()
    tr.record_span("request")
    text = prometheus_text(tel, tracer=tr)
    raw = parse_prom_text(text)
    assert raw["repro_events_total"] == 10
    assert raw['repro_requests_total{model="m0"}'] == 5
    assert raw['repro_fallback_total{stage="any"}'] == 1
    assert raw['repro_fallback_total{stage="none"}'] == 9
    assert raw['repro_admission_total{kind="shed"}'] == 2
    assert raw['repro_cache_total{kind="hit"}'] == 3
    assert raw["repro_route_step_dispatches_total"] == 5
    assert raw["repro_route_step_compiles_total"] == 1
    assert raw["repro_sharding_silent_replications_total"] == 1
    assert raw["repro_trace_spans_total"] == 1
    assert raw["repro_route_latency_seconds_count"] == 10
    assert raw['repro_route_latency_seconds{quantile="0.5"}'] > 0
    # derived ratios for the SLO layer
    m = metrics_from_prom(text)
    assert m["shed_rate"] == pytest.approx(0.2)
    assert m["cache_hit_rate"] == pytest.approx(0.3)
    assert m["route_step_compiles"] == 1
    assert m["route_latency_p99"] >= m["route_latency_p50"] > 0


def test_prometheus_export_with_load_and_cost_profile():
    from repro.serving.load import LoadTracker
    tel = _filled_telemetry()
    load = LoadTracker(3)
    load.admit(1, count=4)
    text = prometheus_text(
        tel, load=load,
        cost_profile={"dense/16/128/False/1":
                      {"flops": 1e6, "bytes_accessed": 2e5}})
    raw = parse_prom_text(text)
    assert raw['repro_load_queue_depth{model="1"}'] == 4
    assert raw['repro_load_inflight{model="0"}'] == 0
    key = 'repro_route_step_flops{bucket="dense/16/128/False/1"}'
    assert raw[key] == 1e6


def test_metrics_server_scrape():
    tel = _filled_telemetry()
    with serve_metrics(tel) as srv:
        url = f"http://127.0.0.1:{srv.port}/metrics"
        body = urllib.request.urlopen(url, timeout=5).read().decode()
        assert parse_prom_text(body)["repro_events_total"] == 10
        tel.record(_ev())                       # live: next scrape moves
        body2 = urllib.request.urlopen(url, timeout=5).read().decode()
        assert parse_prom_text(body2)["repro_events_total"] == 11
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/other", timeout=5)


# ----------------------------------------------------------------------
# SLO rules
# ----------------------------------------------------------------------
def test_parse_rule_forms():
    r = parse_rule("route_latency_p99 <= 0.05")
    assert (r.name, r.metric, r.op, r.threshold) == \
        ("route_latency_p99", "route_latency_p99", "<=", 0.05)
    assert not r.is_burn
    r = parse_rule("shed: shed_rate <= 0.01 burn 60s/600s x2")
    assert r.name == "shed" and r.is_burn
    assert (r.burn_short_s, r.burn_long_s, r.burn_factor) == \
        (60.0, 600.0, 2.0)
    r = parse_rule("recompiles: route_step_compiles == 0")
    assert r.check(0.0) and not r.check(1.0)
    for bad in ("nonsense", "x ~ 3", "a <= 0.1 burn 60s",
                "a <= 0.1 burn 600s/60s"):
        with pytest.raises(ValueError):
            parse_rule(bad)


def test_parse_rules_skips_comments():
    rules = parse_rules("# SLOs\n\nshed_rate <= 0.01  # inline\n"
                        "cache_hit_rate >= 0.3\n")
    assert [r.metric for r in rules] == ["shed_rate", "cache_hit_rate"]


def test_point_evaluation_and_missing_metric():
    rules = parse_rules(["shed_rate <= 0.1", "cache_hit_rate >= 0.5",
                         "unknown_metric == 0"])
    v = evaluate_rules(rules, {"shed_rate": 0.2, "cache_hit_rate": 0.7})
    assert [x.ok for x in v] == [False, True, True]   # missing -> 0.0
    assert "BREACH" in v[0].line() and "OK" in v[1].line()


def test_burn_rate_needs_both_windows():
    """A burn-rate rule fires only when the bad fraction exceeds
    factor*threshold over BOTH windows: a brief spike inside a healthy
    long window does not page."""
    rule = parse_rule("shed: shed_rate <= 0.1 burn 60s/600s x2")
    ev = SLOEvaluator([rule])
    # steady healthy traffic for 10 minutes: 1 bad / 100 total per 30s
    t, bad, total = 0.0, 0.0, 0.0
    while t < 600.0:
        bad += 1.0
        total += 100.0
        ev.observe(t, {}, {"shed_rate": (bad, total)})
        t += 30.0
    (v,) = ev.evaluate({"shed_rate": 0.01}, now=600.0)
    assert v.ok
    # short-window spike: 90% bad for one minute; long window still ok
    for _ in range(2):
        bad += 90.0
        total += 100.0
        ev.observe(t, {}, {"shed_rate": (bad, total)})
        t += 30.0
    (v,) = ev.evaluate({"shed_rate": 0.9}, now=t)
    assert v.ok and "burn" in v.detail
    # sustained badness: both windows exceed 2 * 0.1 -> breach
    while t < 1800.0:
        bad += 90.0
        total += 100.0
        ev.observe(t, {}, {"shed_rate": (bad, total)})
        t += 30.0
    (v,) = ev.evaluate({"shed_rate": 0.9}, now=t)
    assert not v.ok


def test_burn_rule_falls_back_to_point_check_without_history():
    rule = parse_rule("shed: shed_rate <= 0.1 burn 60s/600s")
    (v,) = SLOEvaluator([rule]).evaluate({"shed_rate": 0.05})
    assert v.ok and v.detail == "insufficient history"
    (v,) = SLOEvaluator([rule]).evaluate({"shed_rate": 0.5})
    assert not v.ok


def test_slo_cli_gate(tmp_path):
    from repro.obs import slo, write_prom
    prom = tmp_path / "metrics.prom"
    write_prom(prom, _filled_telemetry())
    ok = ["--metrics", str(prom), "--rule", "shed_rate <= 0.5"]
    assert slo.main(ok) == 0
    breach = ["--metrics", str(prom), "--rule",
              "recompiles: route_step_compiles == 0"]
    assert slo.main(breach) == 1                # fixture recorded 1 compile
    assert slo.main(["--metrics", str(prom)]) == 2   # no rules
    rules = tmp_path / "rules.slo"
    rules.write_text("# gate\nshed_rate <= 0.5\nevents >= 1\n")
    assert slo.main(["--metrics", str(prom),
                     "--rules-file", str(rules)]) == 0


# ----------------------------------------------------------------------
# device cost profiler
# ----------------------------------------------------------------------
def test_cost_profiler_captures_route_step_buckets():
    from repro.core.routing import RoutingEngine
    from repro.kernels import ops as K
    from tests.test_routing_batch import random_catalog
    from benchmarks.router_scale import _random_queries
    mres = random_catalog(8, seed=3)
    eng = RoutingEngine(mres, knn_k=4, use_kernel=False)
    prefs, sigs = _random_queries(4, seed=5)
    prof = DeviceCostProfiler()
    K.set_cost_profiler(prof)
    try:
        eng.route_many_batch(prefs, sigs)
        eng.route_many_batch(prefs, sigs)       # same bucket: no recapture
    finally:
        K.set_cost_profiler(None)
    profile = prof.profile()
    assert len(profile) == 1                    # one shape bucket seen
    assert prof.captures + prof.errors == 1     # capture attempted once
    (bucket, costs), = profile.items()
    assert bucket.startswith("dense/")
    assert set(costs) == {"flops", "bytes_accessed"}
    if prof.captures:                           # backend supports it
        assert costs["flops"] is not None and costs["flops"] > 0
    # detached again: further dispatches must not touch the profiler
    eng.route_many_batch(prefs, sigs)
    assert len(prof.profile()) == 1


def test_trace_capture_degrades_gracefully(tmp_path):
    with trace_capture(None):                   # falsy: pure no-op
        x = 1
    with trace_capture(str(tmp_path / "jx")):   # best-effort profiler
        x += 1
    assert x == 2
