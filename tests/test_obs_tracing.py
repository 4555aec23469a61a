"""End-to-end trace propagation through the serving path: every
Response (hit / miss / rerouted / shed) carries a trace id whose span
tree contains exactly the stages that ran for it."""
import pytest

from repro.cache.semantic import SemanticCache
from repro.core.orchestrator import OptiRoute
from repro.core.telemetry import Telemetry
from repro.obs import Tracer
from repro.serving.engine import Request, ServingEngine
from repro.serving.load import LoadTracker
from tests.test_routing_batch import StubAnalyzer, random_catalog


def build_engine(*, load=None, cache=None, tracer=True, seed=4):
    tel = Telemetry()
    tr = Tracer() if tracer else None
    router = OptiRoute(random_catalog(8, seed=seed), StubAnalyzer(),
                       telemetry=tel, tracer=tr, load=load, cache=cache)
    return ServingEngine(router), tel, tr


def _req(i, text=None, deadline_ms=None):
    return Request(text=text or f"query {i}", prefs="balanced", id=i,
                   max_new=4, deadline_ms=deadline_ms,
                   tenant=f"team{i % 2}")


def _child_names(tree):
    return sorted(c["name"] for c in tree["children"])


def _expected_stages(resp, req, *, cache_attached, load_attached):
    """The stages that actually ran for this response."""
    stages = []
    if cache_attached:
        stages.append("cache_lookup")
    if not resp.cache_hit:
        stages += ["analyze", "route_step"]
        if load_attached and req.deadline_ms is not None:
            stages.append("admission")
        if not resp.shed:
            stages.append("generate")
    return sorted(stages)


def test_every_response_trace_matches_stages_ran():
    """Mixed batch — no-SLO misses, SLO-carrying admits, and forced
    sheds — each response's span tree holds exactly its own stages."""
    eng, _, tr = build_engine(load=LoadTracker(8),
                              cache=SemanticCache(capacity=64))
    reqs = []
    for i in range(9):
        # i%3==0: no SLO; ==1: generous SLO (admitted); ==2: impossible
        # SLO (every arm's estimate exceeds 1us -> shed)
        dl = (None, 10_000.0, 1e-3)[i % 3]
        reqs.append(_req(i, deadline_ms=dl))
    out = eng.submit(reqs)
    assert [r.admission for r in out[2::3]] == ["shed"] * 3
    assert all(not r.cache_hit for r in out)     # cold cache
    for req, resp in zip(reqs, out):
        assert resp.trace_id, "untraced response"
        tree = tr.summary_tree(resp.trace_id)
        assert tree["name"] == "request"
        assert tree["attrs"]["request_id"] == req.id
        assert tree["attrs"]["tenant"] == req.tenant
        assert tree["attrs"]["admission"] == resp.admission
        assert tree["attrs"]["model"] == resp.model
        assert tree["attrs"]["cache_hit"] is False
        assert _child_names(tree) == _expected_stages(
            resp, req, cache_attached=True, load_attached=True)
    # the shed trees stop at admission: verdict recorded, no generate
    shed_tree = tr.summary_tree(out[2].trace_id)
    (adm,) = [c for c in shed_tree["children"]
              if c["name"] == "admission"]
    assert adm["attrs"]["verdict"] == "shed"
    assert adm["attrs"]["est_latency_s"] > 0


def test_cache_hit_trace_short_circuits():
    """A cache hit's tree contains ONLY the lookup — no analyze /
    route_step / admission / generate span exists for it."""
    eng, _, tr = build_engine(load=LoadTracker(8),
                              cache=SemanticCache(capacity=64))
    reqs = [_req(i) for i in range(4)]
    first = eng.submit(reqs)
    eng.observe(first, [0.9] * len(first))       # validate -> store
    second = eng.submit([_req(i) for i in range(4)])
    assert all(r.cache_hit for r in second)
    for r in second:
        tree = tr.summary_tree(r.trace_id)
        assert tree["attrs"]["cache_hit"] is True
        assert _child_names(tree) == ["cache_lookup"]
        (lookup,) = tree["children"]
        assert lookup["attrs"]["outcome"] == "hit"
    # the misses' trees keep their full pipeline, with miss outcomes
    for r in first:
        tree = tr.summary_tree(r.trace_id)
        lookups = [c for c in tree["children"]
                   if c["name"] == "cache_lookup"]
        assert lookups[0]["attrs"]["outcome"] == "miss"
        assert "generate" in _child_names(tree)


def test_rerouted_response_trace():
    """Saturating the routed model makes admission fall to a candidate
    that fits; the trace records the rerouted verdict and still shows a
    generate span (the request WAS served)."""
    load = LoadTracker(8)
    # seed=2's catalog keeps 3 candidates after filtering, so admission
    # has lower-ranked alternates to fall to
    eng, _, tr = build_engine(load=load, seed=2)
    probe = eng.submit([_req(0)])[0]             # learn the routed model
    names = list(eng.router.mres.snapshot()[1])
    load.admit(names.index(probe.model), count=100)   # swamp it
    (resp,) = eng.submit([_req(1, text="fresh text",
                               deadline_ms=500.0)])
    assert resp.admission == "rerouted"
    assert resp.model != probe.model
    tree = tr.summary_tree(resp.trace_id)
    assert tree["attrs"]["admission"] == "rerouted"
    (adm,) = [c for c in tree["children"] if c["name"] == "admission"]
    assert adm["attrs"]["verdict"] == "rerouted"
    assert "generate" in _child_names(tree)


def test_batch_trace_tree_spans_whole_pipeline():
    """The batch-level 'submit' root nests the fused stage spans —
    including the route_step span recorded down in kernels/ops with
    its bucket attributes — via contextvar propagation alone."""
    eng, _, tr = build_engine(load=LoadTracker(8),
                              cache=SemanticCache(capacity=64))
    out = eng.submit([_req(i, deadline_ms=10_000.0) for i in range(5)])
    (submit,) = [s for s in tr.spans() if s.name == "submit"]
    tree = tr.summary_tree(submit.trace_id)
    assert tree["name"] == "submit"
    assert tree["attrs"] == {"batch": 5, "mode": "interactive"}
    assert _child_names(tree) == ["admission", "cache_lookup",
                                  "generate", "route_all"]
    (ra,) = [c for c in tree["children"] if c["name"] == "route_all"]
    assert _child_names(ra) == ["analyze", "route_step"]
    (gen,) = [c for c in tree["children"] if c["name"] == "generate"]
    assert set(_child_names(gen)) == {"catalog_lookup"}
    (rs,) = [c for c in ra["children"] if c["name"] == "route_step"]
    assert rs["attrs"]["batch"] == 5
    assert rs["attrs"]["q_bucket"] >= 5
    assert rs["attrs"]["path"] in ("dense", "sharded", "ivf")
    assert "compiles" in rs["attrs"]
    # per-request roots are separate traces linking back to the batch
    for r in out:
        tree_r = tr.summary_tree(r.trace_id)
        assert r.trace_id != submit.trace_id
        assert tree_r["attrs"]["batch_trace"] == submit.trace_id


def test_observe_attaches_outcome_span():
    eng, _, tr = build_engine(cache=SemanticCache(capacity=64))
    out = eng.submit([_req(i) for i in range(3)])
    eng.observe(out, [0.8, 0.6, 0.7])
    for r, q in zip(out, (0.8, 0.6, 0.7)):
        tree = tr.summary_tree(r.trace_id)
        (obs,) = [c for c in tree["children"] if c["name"] == "observe"]
        assert obs["attrs"]["quality"] == pytest.approx(q)
        assert obs["attrs"]["model"] == r.model


def test_untraced_engine_unchanged():
    eng, _, tr = build_engine(tracer=False)
    out = eng.submit([_req(0)])
    assert tr is None
    assert out[0].trace_id == "" and out[0].trace_root is None
