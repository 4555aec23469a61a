"""Load- and SLO-aware routing: LoadTracker state machine, the
load_weight scoring term (numpy + kernel paths), deadline admission in
the serving engine, and the discrete-event traffic simulator."""
import threading

import numpy as np
import pytest

from repro.core.mres import MRES
from repro.core.preferences import TaskSignature
from repro.core.routing import RoutingEngine
from repro.core.telemetry import Telemetry
from repro.data.workload import (ServingSimulator, TrafficScenario,
                                 poisson_arrivals)
from repro.serving.load import ADMISSION_KINDS, LoadTracker, plan_admission
from tests.conftest import make_entry


def _flat_catalog(n=6, accuracy_step=0.05):
    """All-chat catalog with a strict accuracy ordering (m0 best)."""
    m = MRES()
    for i in range(n):
        m.register(make_entry(
            f"m{i}", accuracy=0.9 - accuracy_step * i,
            latency_ms=50.0 + 10 * i, cost=1.0 + i,
            task_types=("chat",), domains=("general",), generalist=True))
    return m


SIG = TaskSignature(task_type="chat", domain="general", complexity=0.2)


# ----------------------------------------------------------------------
# LoadTracker state machine
# ----------------------------------------------------------------------

def test_tracker_lifecycle_counts():
    lt = LoadTracker(3, capacity=2.0)
    lt.admit(0)
    lt.admit(0)
    lt.admit_many(np.array([1, 1, 1, 2]))
    q, f, c, _ = lt.snapshot()
    assert q.tolist() == [2, 3, 1] and f.tolist() == [0, 0, 0]
    lt.start(0)
    q, f, _, _ = lt.snapshot()
    assert q[0] == 1 and f[0] == 1
    lt.finish(0, 0.5)
    q, f, _, _ = lt.snapshot()
    assert f[0] == 0
    # finish never drives counters negative
    lt.finish(2)
    assert lt.snapshot()[1][2] == 0


def test_tracker_ewma_and_wait_estimates():
    lt = LoadTracker(2, capacity=2.0, ewma_alpha=0.5,
                     default_service_s=0.1)
    # 4 outstanding on capacity 2 at 0.1s each: 3 completions must land
    # before a new arrival starts, draining 2 per 0.1s -> 0.15s wait
    lt.admit(0, count=4)
    np.testing.assert_allclose(lt.estimated_wait_s(), [0.15, 0.0],
                               atol=1e-6)
    np.testing.assert_allclose(lt.estimated_latency_s([0]), [0.25],
                               atol=1e-6)
    # EWMA folds realized service times
    lt.start(0)
    lt.finish(0, 0.3)
    assert lt.snapshot()[3][0] == pytest.approx(0.2)
    # penalty saturates in [0, 1) and is monotone in queue depth
    p1 = lt.penalty()[0]
    lt.admit(0, count=50)
    p2 = lt.penalty()[0]
    assert 0.0 <= p1 < p2 < 1.0
    assert lt.penalty()[1] == 0.0


def test_tracker_ensure_growth_and_capacity():
    lt = LoadTracker(2, capacity=4.0)
    lt.admit(1)
    lt.ensure(5, capacity=[1.0, 2.0, 8.0])
    assert lt.n_models == 5
    q, _, c, _ = lt.snapshot()
    assert q.tolist() == [0, 1, 0, 0, 0]
    assert c.tolist() == [4.0, 4.0, 1.0, 2.0, 8.0]
    lt.ensure(3)                        # shrink is a no-op
    assert lt.n_models == 5
    lt.set_capacity(0, 16.0)
    assert lt.snapshot()[2][0] == 16.0


def test_idle_capacity_has_zero_wait():
    """Regression: one in-flight request on a 4-slot model must not be
    penalized over an idle one — expected wait stays 0 until
    queue + inflight >= capacity (the old (q+f)/c*s estimate reported
    nonzero wait for a model with free slots)."""
    lt = LoadTracker(2, capacity=4.0, default_service_s=0.1)
    lt.admit(0)
    lt.start(0)                          # 1 in flight, 3 slots free
    q, f, c, _ = lt.snapshot()
    assert q[0] == 0 and f[0] == 1 and c[0] == 4.0
    np.testing.assert_allclose(lt.estimated_wait_s(), [0.0, 0.0])
    np.testing.assert_allclose(lt.penalty(), [0.0, 0.0])
    # the estimate turns on exactly at saturation
    lt.admit(0, count=3)                 # q+f == capacity
    assert lt.estimated_wait_s()[0] > 0.0
    assert lt.estimated_wait_s()[1] == 0.0


def test_ensure_accepts_full_length_capacity():
    """Regression: ensure() used to reshape(grow) the capacity input
    and crash on a full-length (n_models,) vector."""
    lt = LoadTracker(2, capacity=4.0)
    full = np.array([9.0, 9.0, 1.0, 2.0, 8.0], np.float32)
    lt.ensure(5, capacity=full)          # full catalog vector: tail
    assert lt.snapshot()[2].tolist() == [4.0, 4.0, 1.0, 2.0, 8.0]
    lt.ensure(6, capacity=[16.0])        # new-arms-only still works
    assert lt.snapshot()[2].tolist() == [4.0, 4.0, 1.0, 2.0, 8.0, 16.0]
    with pytest.raises(ValueError, match="capacity"):
        lt.ensure(8, capacity=[1.0, 2.0, 3.0])   # neither 2 nor 8
    lt.ensure(3, capacity=np.ones(3))    # no growth -> no-op
    assert lt.n_models == 6


def test_tracker_thread_safety():
    lt = LoadTracker(4, capacity=2.0)
    errs = []

    def worker(i):
        try:
            for _ in range(500):
                lt.admit(i % 4)
                lt.start(i % 4)
                lt.finish(i % 4, 0.01)
        except Exception as e:                 # noqa: BLE001
            errs.append(e)

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    q, f, _, _ = lt.snapshot()
    assert (q == 0).all() and (f == 0).all()


# ----------------------------------------------------------------------
# load term in the routing blend
# ----------------------------------------------------------------------

def test_load_weight_zero_matches_no_tracker():
    m = _flat_catalog()
    lt = LoadTracker(len(m))
    lt.admit(0, count=100)              # saturate the static winner
    d0 = RoutingEngine(m).route("accuracy-first", SIG)
    d1 = RoutingEngine(m, load=lt, load_weight=0.0).route(
        "accuracy-first", SIG)
    assert d0.model == d1.model and d0.score == pytest.approx(d1.score)


def test_saturated_model_loses_to_alternate():
    m = _flat_catalog()
    lt = LoadTracker(len(m), capacity=2.0)
    eng = RoutingEngine(m, load=lt, load_weight=1.0)
    assert eng.route("accuracy-first", SIG).model == "m0"
    lt.admit(0, count=200)              # m0 saturates -> penalty ~ 1
    d = eng.route("accuracy-first", SIG)
    assert d.model != "m0"
    lt.reset()                          # drained -> winner returns
    assert eng.route("accuracy-first", SIG).model == "m0"


def test_load_penalty_reaches_fallback_scorer():
    m = MRES()
    m.register(make_entry("gen-a", accuracy=0.9, task_types=("chat",),
                          generalist=True))
    m.register(make_entry("gen-b", accuracy=0.8, task_types=("chat",),
                          generalist=True))
    lt = LoadTracker(2, capacity=1.0)
    eng = RoutingEngine(m, load=lt, load_weight=2.0)
    sig = TaskSignature(task_type="vqa", domain="healthcare")
    assert eng.route("accuracy-first", sig).fallback_kind == "generalist"
    assert eng.route("accuracy-first", sig).model == "gen-a"
    lt.admit(0, count=100)
    d = eng.route("accuracy-first", sig)
    assert d.used_fallback and d.model == "gen-b"


def test_load_kernel_matches_numpy_path():
    from tests.test_routing_batch import random_catalog, random_queries
    m = random_catalog(96, seed=13)
    lt = LoadTracker(96, capacity=2.0)
    rng = np.random.default_rng(3)
    lt.admit_many(rng.integers(0, 96, 400))
    prefs, sigs = random_queries(11, seed=13)
    eng_np = RoutingEngine(m, knn_k=8, load=lt, load_weight=1.5)
    eng_k = RoutingEngine(m, knn_k=8, load=lt, load_weight=1.5,
                          use_kernel=True)
    eng_k._kernel_min_n = 0
    for a, b in zip(eng_np.route_many(prefs, sigs),
                    eng_k.route_many(prefs, sigs)):
        assert a.model == b.model
        assert a.fallback_kind == b.fallback_kind
        assert a.score == pytest.approx(b.score, abs=1e-5)


def test_load_route_single_matches_batch():
    from tests.test_routing_batch import random_catalog, random_queries
    m = random_catalog(32, seed=21)
    lt = LoadTracker(32, capacity=2.0)
    lt.admit_many(np.random.default_rng(0).integers(0, 32, 100))
    eng = RoutingEngine(m, knn_k=8, load=lt, load_weight=1.0)
    prefs, sigs = random_queries(9, seed=21)
    batch = eng.route_many(prefs, sigs)
    for d_b, p, s in zip(batch, prefs, sigs):
        d_1 = eng.route(p, s)
        assert d_b.model == d_1.model
        assert d_b.score == pytest.approx(d_1.score, abs=1e-6)


def test_load_penalty_counted_once_fused_vs_unfused():
    """Regression: the load penalty must affect the final score exactly
    once, at the candidate-column scoring blend.  The old path ALSO
    fused -penalty into the kNN similarity search, where it crowded a
    loaded model out of the candidate set entirely (an unbounded second
    application) — with knn_k < n the loaded-but-still-best model lost
    to a strictly worse alternate."""
    m = _flat_catalog(6)
    lt = LoadTracker(6, capacity=2.0)
    lt.admit(0, count=5)                 # modest load on the leader
    lt.admit(1, count=2)
    eng = RoutingEngine(m, knn_k=4, load=lt, load_weight=1.0)
    d = eng.route_many(["accuracy-first"], [SIG])[0]
    emb, names, *_ = m.snapshot()
    from repro.core.preferences import resolve
    W = resolve("accuracy-first").vector()
    lpen = 1.0 * lt.penalty()
    # brute-force reference: blend over the FULL catalog, penalty once
    ref = emb @ W - lpen
    assert d.model == names[int(np.argmax(ref))]
    assert d.score == pytest.approx(float(ref.max()), abs=1e-5)
    # every surfaced candidate's score carries the penalty exactly once
    for nm, s in d.candidates:
        j = names.index(nm)
        assert s == pytest.approx(float(ref[j]), abs=1e-5)
    # parity pin: an explicitly unfused kNN (bias stripped) must be
    # decision- and score-identical to the engine's own path
    eng2 = RoutingEngine(m, knn_k=4, load=lt, load_weight=1.0)
    orig = eng2._knn_batch
    eng2._knn_batch = \
        lambda T, k, ti, di, snap, bias=None: orig(T, k, ti, di, snap,
                                                   bias=None)
    d2 = eng2.route_many(["accuracy-first"], [SIG])[0]
    assert (d.model, d.fallback_kind) == (d2.model, d2.fallback_kind)
    assert d.score == pytest.approx(d2.score, abs=1e-6)
    assert d.candidates == d2.candidates


def test_fallback_scorer_penalty_counted_once():
    """The fallback ladder's dense scorer applies the same
    penalty-exactly-once blend as the primary path."""
    m = MRES()
    m.register(make_entry("gen-a", accuracy=0.9, task_types=("chat",),
                          generalist=True))
    m.register(make_entry("gen-b", accuracy=0.8, task_types=("chat",),
                          generalist=True))
    lt = LoadTracker(2, capacity=1.0)
    lt.admit(0, count=10)
    eng = RoutingEngine(m, load=lt, load_weight=2.0)
    sig = TaskSignature(task_type="vqa", domain="healthcare")
    d = eng.route("accuracy-first", sig)
    assert d.used_fallback
    emb, names, *_ = m.snapshot()
    from repro.core.preferences import resolve
    W = resolve("accuracy-first").vector()
    ref = emb @ W - 2.0 * lt.penalty()
    for nm, s in d.candidates:
        assert s == pytest.approx(float(ref[names.index(nm)]), abs=1e-5)


# ----------------------------------------------------------------------
# deadline admission planning
# ----------------------------------------------------------------------

def _decision(eng, prefs="accuracy-first", sig=SIG):
    return eng.route(prefs, sig)


def test_plan_admission_paths():
    m = _flat_catalog(3)
    lt = LoadTracker(3, capacity=1.0, default_service_s=0.1)
    col = {f"m{i}": i for i in range(3)}
    eng = RoutingEngine(m, knn_k=3)          # load-blind routing...
    d = _decision(eng)
    # no deadline / no tracker -> admitted untouched
    assert plan_admission(d, lt, col, None) == (d.model, "admitted", 0.0)
    assert plan_admission(d, None, col, 100.0)[1] == "admitted"
    # idle catalog: the routed model fits its SLO
    model, kind, est = plan_admission(d, lt, col, 1000.0)
    assert (model, kind) == (d.model, "admitted") and est > 0.0
    # saturate the winner: reroute to the best-scoring candidate that fits
    lt.admit(col[d.model], count=50)
    model, kind, _ = plan_admission(d, lt, col, 1000.0)
    assert kind == "rerouted" and model != d.model
    second = [c for c, _ in d.candidates][1]
    assert model == second
    # impossible SLO anywhere -> shed
    model, kind, est = plan_admission(d, lt, col, 0.001)
    assert kind == "shed" and est > 0.001 / 1e3
    assert kind in ADMISSION_KINDS


# ----------------------------------------------------------------------
# serving engine integration
# ----------------------------------------------------------------------

def _serving_setup(deadline_ms=None):
    from repro.core.orchestrator import OptiRoute
    from repro.serving.engine import Request, ServingEngine
    from tests.test_routing_batch import StubAnalyzer
    m = _flat_catalog()
    lt = LoadTracker(len(m), capacity=2.0, default_service_s=0.05)
    router = OptiRoute(m, StubAnalyzer(), telemetry=Telemetry(),
                       load=lt, load_weight=1.0)
    engine = ServingEngine(router)
    assert engine.load is lt                 # picked up from the router
    reqs = [Request(text=f"q{i}", prefs="accuracy-first", id=i,
                    deadline_ms=deadline_ms) for i in range(6)]
    return engine, lt, reqs


def test_serving_engine_admits_and_drains_load():
    engine, lt, reqs = _serving_setup(deadline_ms=10_000.0)
    out = engine.submit(reqs)
    assert [r.admission for r in out] == ["admitted"] * 6
    q, f, _, _ = lt.snapshot()               # lifecycle completed
    assert (q == 0).all() and (f == 0).all()
    s = engine.summary()
    assert s["admissions"] == {"admitted": 6}
    funnel = engine.router.telemetry.admission_funnel()
    assert funnel == {"admitted": 6}
    for stats in s["latency"].values():
        assert stats["p50_s"] <= stats["p99_s"]


def test_serving_engine_sheds_on_impossible_deadline():
    engine, lt, reqs = _serving_setup(deadline_ms=1e-6)
    out = engine.submit(reqs)
    assert all(r.shed for r in out)
    assert all(r.tokens is None for r in out)
    q, f, _, _ = lt.snapshot()               # shed burns no capacity
    assert (q == 0).all() and (f == 0).all()
    assert engine.summary()["admissions"] == {"shed": 6}
    assert engine.router.telemetry.admission_funnel() == {"shed": 6}


def test_serving_engine_no_deadline_unchanged():
    engine, _, reqs = _serving_setup(deadline_ms=None)
    out = engine.submit(reqs)
    assert all(r.admission == "admitted" for r in out)
    # no SLO -> nothing lands in the admission funnel
    assert engine.router.telemetry.admission_funnel() == {}


# ----------------------------------------------------------------------
# traffic scenario + simulator
# ----------------------------------------------------------------------

def test_poisson_arrivals_deterministic_and_bursty():
    sc = TrafficScenario(duration_s=10.0, base_rate=20.0,
                         burst_rate=200.0, burst_start=0.4,
                         burst_len=0.2, seed=3)
    a1, a2 = poisson_arrivals(sc), poisson_arrivals(sc)
    np.testing.assert_array_equal(a1, a2)
    assert (np.diff(a1) >= 0).all() and a1[-1] < sc.duration_s
    b0, b1 = sc.burst_window_s
    in_burst = ((a1 >= b0) & (a1 < b1)).sum() / (b1 - b0)
    outside = ((a1 < b0) | (a1 >= b1)).sum() / (sc.duration_s - (b1 - b0))
    assert in_burst > 3 * outside            # rate ratio is 10x


def test_traffic_scenario_validation():
    with pytest.raises(AssertionError):
        TrafficScenario(burst_rate=1.0, base_rate=10.0).validate()
    with pytest.raises(AssertionError):
        TrafficScenario(burst_start=0.9, burst_len=0.5).validate()


def test_simulator_single_server_math():
    """3 back-to-back arrivals on one 1s server: waits 0/1/2 s."""
    sim = ServingSimulator([1.0], [1], tracker=LoadTracker(1))
    res = sim.run(np.array([0.0, 0.0, 0.0]),
                  lambda i, t: (0, "admitted"), deadline_ms=1500.0)
    np.testing.assert_allclose(res["wait_s"], [0.0, 1.0, 2.0])
    np.testing.assert_allclose(res["latency_s"], [1.0, 2.0, 3.0])
    assert res["slo_miss"].tolist() == [False, True, True]
    assert res["slo_miss_rate"] == pytest.approx(2 / 3)


def test_simulator_parallel_servers_and_shed():
    sim = ServingSimulator([1.0, 1.0], [2, 1])
    kinds = ["admitted", "admitted", "rerouted", "shed"]
    models = [0, 0, 1, 0]
    res = sim.run(np.zeros(4),
                  lambda i, t: (models[i], kinds[i]), deadline_ms=1100.0)
    np.testing.assert_allclose(res["latency_s"][:3], [1.0, 1.0, 1.0])
    assert res["shed"].tolist() == [False, False, False, True]
    assert res["rerouted"].tolist() == [False, False, True, False]
    assert np.isnan(res["latency_s"][3])
    assert res["slo_miss"].tolist() == [False, False, False, True]


def test_simulator_mirrors_tracker_state():
    lt = LoadTracker(1, capacity=1.0, default_service_s=9.9)
    sim = ServingSimulator([0.5], [1], tracker=lt)
    seen = []

    def route(i, t):
        seen.append(lt.estimated_wait_s()[0])
        return 0, "admitted"

    sim.run(np.array([0.0, 0.1, 5.0]), route)
    # 2nd arrival sees the 1st in flight; 3rd sees a drained system
    assert seen[0] == 0.0 and seen[1] > 0.0 and seen[2] == 0.0
    q, f, _, _ = lt.snapshot()
    assert (q == 0).all() and (f == 0).all()
    # EWMA pulled toward the realized 0.5s service time
    assert lt.snapshot()[3][0] < 9.9


def test_plan_admission_sees_pending_batch_placements():
    """Request #k of one batch must see the k-1 placements planned
    ahead of it — a burst cannot be waved through (or rerouted onto a
    single alternate) against a frozen pre-batch snapshot."""
    m = _flat_catalog(3)
    lt = LoadTracker(3, capacity=1.0, default_service_s=0.1)
    col = {f"m{i}": i for i in range(3)}
    d = RoutingEngine(m, knn_k=3).route("accuracy-first", SIG)
    pending = np.zeros(3, np.int64)
    kinds = []
    # deadline fits 2 requests per model (wait+service <= 0.25s)
    for _ in range(8):
        model, kind, _ = plan_admission(d, lt, col, 250.0, pending=pending)
        kinds.append(kind)
        if kind != "shed":
            pending[col[model]] += 1
    # 3 models x 2 slots-worth of budget -> 6 placed, the rest shed
    assert kinds.count("shed") == 2
    assert pending.tolist() == [2, 2, 2]
    # without pending accounting every request would be admitted
    assert plan_admission(d, lt, col, 250.0)[1] == "admitted"


def test_serving_engine_intra_batch_admission():
    from repro.serving.engine import Request
    engine, lt, _ = _serving_setup()
    # capacity 2, service estimate 0.05s -> a 0.125s budget fits the
    # first few placements per model, then the batch must spill/shed
    reqs = [Request(text=f"q{i}", prefs="accuracy-first", id=i,
                    deadline_ms=125.0) for i in range(40)]
    out = engine.submit(reqs)
    kinds = {r.admission for r in out}
    assert "shed" in kinds, [r.admission for r in out]
    assert len({r.model for r in out if not r.shed}) > 1
    funnel = engine.router.telemetry.admission_funnel()
    assert funnel.get("shed", 0) + funnel.get("admitted", 0) \
        + funnel.get("rerouted", 0) == 40


def test_similarity_stays_pure_cosine_under_load():
    from repro.core.routing import cosine_sim
    m = _flat_catalog()
    emb = m.embeddings()
    names = m.snapshot()[1]
    lt = LoadTracker(len(m), capacity=2.0)
    lt.admit(0, count=200)
    eng = RoutingEngine(m, load=lt, load_weight=1.0)
    d = eng.route("accuracy-first", SIG)
    j = names.index(d.model)
    pure = float(cosine_sim(emb[j:j + 1], d.task_vector)[0])
    assert d.similarity == pytest.approx(pure, abs=1e-5)
    assert -1.0 - 1e-6 <= d.similarity <= 1.0 + 1e-6


class _BoomCfg:
    vocab_size = 64


class BoomRunner:
    """Test runner whose generate always raises."""
    cfg = _BoomCfg()

    def generate(self, toks, max_new=8):
        raise RuntimeError("boom")


def test_generate_failure_degrades_group_and_releases_slots():
    """A runner crash mid-batch must not leak inflight counts (which
    would permanently penalize a healthy model) — and must not
    propagate out of submit: the failed group's requests come back
    degraded (admission='failed', no tokens, no bandit handle) while
    the batch as a whole survives."""
    from repro.serving.engine import Request

    engine, lt, reqs = _serving_setup()
    routed = engine.router.route_all([r.text for r in reqs[:1]],
                                     "accuracy-first")
    boomed = routed[0].decision.model
    engine.router.mres.entry(boomed).runner = BoomRunner()
    out = engine.submit(reqs)                # must NOT raise
    q, f, _, _ = lt.snapshot()
    assert (f == 0).all() and (q == 0).all()
    assert len(out) == len(reqs)
    for r in out:
        if r.model == boomed:
            assert r.admission == "failed" and r.failed
            assert r.tokens is None and r.rq is None
            assert "boom" in r.error
        else:
            assert r.admission == "admitted"
    # the failure is visible in the funnel even without deadlines
    funnel = engine.router.telemetry.admission_funnel()
    assert funnel.get("failed", 0) == sum(r.failed for r in out) > 0
    # observe() silently skips the handle-less failed responses
    assert engine.observe([r for r in out if r.failed],
                          [1.0] * sum(r.failed for r in out)) is None


def test_failed_group_not_mislabeled_shed():
    """Requests whose ADMITTED group failed must be labeled 'failed',
    never 'shed' — they consumed slot lifecycle, and summary()'s
    admission counts must show real capacity use."""
    from repro.serving.engine import Request

    engine, lt, _ = _serving_setup()
    # a saturating deadline-carrying burst: some requests shed for
    # real, the boomed model's admitted share must stay distinct
    reqs = [Request(text=f"q{i}", prefs="accuracy-first", id=i,
                    deadline_ms=125.0) for i in range(40)]
    routed = engine.router.route_all([reqs[0].text], "accuracy-first")
    boomed = routed[0].decision.model
    engine.router.mres.entry(boomed).runner = BoomRunner()
    out = engine.submit(reqs)
    kinds = {r.admission for r in out}
    assert "failed" in kinds and "shed" in kinds
    for r in out:
        if r.model == boomed and not r.shed:
            assert r.failed
        if r.shed:         # true sheds never touched the boomed runner
            assert r.error == ""
    s = engine.summary()
    assert s["admissions"].get("failed", 0) == sum(r.failed for r in out)
    # failed requests were served by NO model: they are not in models
    assert sum(s["models"].values()) == sum(r.served for r in out)
    # final-outcome funnel still partitions the whole batch
    funnel = engine.router.telemetry.admission_funnel()
    assert sum(funnel.values()) == 40
    q, f, _, _ = lt.snapshot()
    assert (q == 0).all() and (f == 0).all()


def test_batch_mode_full_lifecycle():
    """_submit_batch must drive the same tracker lifecycle + telemetry
    as interactive mode (bugfix: batch traffic used to be invisible to
    load-aware routing and metrics)."""
    from repro.serving.engine import Request

    class ProbeRunner:
        """Asserts the tracker sees the batch in flight DURING
        generate, not just net-zero afterwards."""
        cfg = _BoomCfg()

        def __init__(self, lt, col):
            self.lt, self.col, self.seen = lt, col, -1

        def generate(self, toks, max_new=8):
            self.seen = int(self.lt.snapshot()[1][self.col])
            import types
            return types.SimpleNamespace(
                tokens=np.zeros((toks.shape[0], max_new), np.int32),
                sim_latency_s=0.01 * toks.shape[0])

    engine, lt, reqs = _serving_setup()
    tel = engine.router.telemetry
    names = engine.router.mres.snapshot()[1]
    # batch mode routes ONE aggregate decision; find it, then probe it
    decision, _, _ = engine.router.route_batch(
        [r.text for r in reqs], reqs[0].prefs)
    col = names.index(decision.model)
    probe = ProbeRunner(lt, col)
    engine.router.mres.entry(decision.model).runner = probe
    out = engine.submit(reqs, mode="batch")
    assert len({r.model for r in out}) == 1
    assert probe.seen == len(reqs)           # inflight while generating
    q, f, _, _ = lt.snapshot()
    assert (q == 0).all() and (f == 0).all() # ...and drained after
    assert lt.snapshot()[3][col] != pytest.approx(0.05)  # EWMA folded
    assert tel.summary()["events"] == len(reqs)   # one event per request
    assert all(r.sim_latency_s > 0 for r in out)


def test_batch_mode_failure_degrades_not_raises():
    from repro.serving.engine import Request
    engine, lt, reqs = _serving_setup()
    decision, _, _ = engine.router.route_batch(
        [r.text for r in reqs], reqs[0].prefs)
    engine.router.mres.entry(decision.model).runner = BoomRunner()
    out = engine.submit(reqs, mode="batch")
    assert all(r.failed and r.tokens is None for r in out)
    q, f, _, _ = lt.snapshot()
    assert (q == 0).all() and (f == 0).all()
    funnel = engine.router.telemetry.admission_funnel()
    assert funnel.get("failed", 0) == len(reqs)


def test_rerouted_and_shed_responses_carry_no_bandit_handle():
    """observe() must never credit the routed model's bandit arm with
    an outcome produced by a different model (reroute) or by no model
    (shed): those responses drop their RoutedQuery handle, and
    shed requests vanish from the per-model summary counts."""
    from repro.serving.engine import Request
    engine, lt, _ = _serving_setup()
    reqs = [Request(text=f"q{i}", prefs="accuracy-first", id=i,
                    deadline_ms=125.0) for i in range(40)]
    out = engine.submit(reqs)
    kinds = {r.admission for r in out}
    assert kinds >= {"admitted", "shed"}
    for r in out:
        if r.admission == "admitted":
            assert r.rq is not None and r.rq.decision.model == r.model
        else:
            assert r.rq is None
    # observe() silently skips handle-less responses
    assert engine.observe([r for r in out if r.shed], 
                          [1.0] * sum(r.shed for r in out)) is None
    s = engine.summary()
    assert sum(s["models"].values()) == sum(1 for r in out if not r.shed)


def test_oversized_tracker_routes_and_serves():
    """A tracker pre-sized beyond the catalog (ensure() only grows;
    trackers can be shared / provisioned ahead) must not break routing
    or admission — penalties are sliced to the catalog snapshot."""
    from repro.serving.engine import Request
    m = _flat_catalog(3)
    lt = LoadTracker(8, capacity=2.0)        # 8 arms, 3-model catalog
    lt.admit(0, count=200)
    eng = RoutingEngine(m, load=lt, load_weight=1.0)
    d = eng.route("accuracy-first", SIG)
    assert d.model != "m0"                   # penalty still applies
    from repro.core.orchestrator import OptiRoute
    from repro.serving.engine import ServingEngine
    from tests.test_routing_batch import StubAnalyzer
    router = OptiRoute(m, StubAnalyzer(), telemetry=Telemetry(),
                       load=lt, load_weight=1.0)
    engine = ServingEngine(router)
    out = engine.submit([Request(text="q", prefs="balanced", id=0,
                                 deadline_ms=60_000.0)])
    assert out[0].admission in ADMISSION_KINDS


@pytest.mark.parametrize("mode", ["interactive", "batch"])
def test_load_counts_land_on_the_snapshot_column(mode):
    """Admission counts go to the routed model's column in snapshot()'s
    name order (interactive: ``_route_and_serve`` with deadline
    admission; batch: ``_serve_batch_group``).  The catalog is
    registered out of name order, so a column is not the name's digit."""
    import types
    from repro.core.orchestrator import OptiRoute
    from repro.serving.engine import Request, ServingEngine
    from tests.test_routing_batch import StubAnalyzer

    def entry(i):
        # accuracy bought with latency and cost: profiles disagree
        return make_entry(f"m{i}", accuracy=0.5 + 0.08 * i,
                          latency_ms=50.0 + 40 * i, cost=1.0 + i,
                          generalist=True)

    m = MRES()
    m.register_many([entry(i) for i in (4, 1, 5, 0, 3)])
    m.register(entry(2))
    lt = LoadTracker(len(m), capacity=2.0, default_service_s=0.05)
    engine = ServingEngine(OptiRoute(m, StubAnalyzer(),
                                     telemetry=Telemetry(), load=lt,
                                     load_weight=1.0))
    names = m.snapshot()[1]
    seen = []                                # (model, group size, q, f)

    class Probe:
        cfg = _BoomCfg()

        def __init__(self, name):
            self.name = name

        def generate(self, toks, max_new=8):
            q, f, _, _ = lt.snapshot()
            seen.append((self.name, toks.shape[0], q.copy(), f.copy()))
            return types.SimpleNamespace(
                tokens=np.zeros((toks.shape[0], max_new), np.int32),
                sim_latency_s=0.01 * toks.shape[0])

    for n in names:
        m.entry(n).runner = Probe(n)
    profiles = ["accuracy-first", "cost-effective", "latency-first"]
    reqs = [Request(text=f"q{i}", prefs=profiles[i % 3], id=i,
                    deadline_ms=10_000.0) for i in range(9)]
    out = engine.submit(reqs, mode=mode)
    assert [r.admission for r in out] == ["admitted"] * len(reqs)
    assert len(seen) == len({r.model for r in out})
    assert len(seen) > 1 if mode == "interactive" else len(seen) == 1
    assert sum(k for _, k, _, _ in seen) == len(reqs)
    for name, k, q, f in seen:               # in flight while generating
        want = [0] * len(names)
        want[names.index(name)] = k
        assert q.tolist() == [0] * len(names)
        assert f.tolist() == want
    q, f, _, ewma = lt.snapshot()            # drained, EWMA folded
    assert (q == 0).all() and (f == 0).all()
    served = {names.index(n) for n, _, _, _ in seen}
    for j in range(len(names)):
        assert (ewma[j] != pytest.approx(0.05)) == (j in served), j
