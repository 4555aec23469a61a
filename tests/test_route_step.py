"""Fused single-dispatch routing step: kernel/oracle parity, staged
differential, shape buckets (zero steady-state recompiles), top-k merge
rewrite, and the array-first RoutingBatch laziness contract."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.adaptive.bandit import LinearBandit
from repro.core.feedback import FeedbackStore
from repro.core.preferences import (DOMAINS, METRICS, TASK_TYPES,
                                    TaskSignature, UserPreferences)
from repro.core.routing import RoutingEngine, _topk_two_level
from repro.kernels import ops as K
from repro.kernels import ref as R
from repro.kernels.router_topk import merge_topk
from repro.serving.load import LoadTracker
from tests.test_routing_batch import random_catalog, random_queries

RNG = np.random.default_rng(7)


# ----------------------------------------------------------------------
# the bitonic top-k merge (the sharded route_step's cross-shard step)
# ----------------------------------------------------------------------

@pytest.mark.parametrize("k", [1, 2, 3, 5, 8, 13])
def test_merge_topk_matches_full_sort(k):
    """Merging two sorted carries == top-k of their concatenation,
    including non-power-of-two k and duplicate values."""
    rng = np.random.default_rng(k)
    for _ in range(5):
        a = -np.sort(-rng.integers(0, 6, (4, k)).astype(np.float32))
        b = -np.sort(-rng.integers(0, 6, (4, k)).astype(np.float32))
        ai = rng.integers(0, 100, (4, k)).astype(np.int32)
        bi = rng.integers(100, 200, (4, k)).astype(np.int32)
        v, (i,) = merge_topk(jnp.asarray(a), jnp.asarray(b),
                             (jnp.asarray(ai),), (jnp.asarray(bi),))
        want = -np.sort(-np.concatenate([a, b], axis=1), axis=1)[:, :k]
        np.testing.assert_array_equal(np.asarray(v), want)
        # every returned index carries its own value (no element was
        # duplicated or dropped through the exchanges)
        both_v = np.concatenate([a, b], axis=1)
        both_i = np.concatenate([ai, bi], axis=1)
        for q in range(4):
            pairs = list(zip(both_i[q].tolist(), both_v[q].tolist()))
            for iv, vv in zip(np.asarray(i)[q], np.asarray(v)[q]):
                assert (int(iv), float(vv)) in pairs
                pairs.remove((int(iv), float(vv)))


def test_merge_topk_with_neginf_padding():
    a = np.array([[3.0, 1.0, -np.inf]], np.float32)
    b = np.array([[2.0, -np.inf, -np.inf]], np.float32)
    ai = np.array([[0, 1, -1]], np.int32)
    bi = np.array([[9, -1, -1]], np.int32)
    v, (i,) = merge_topk(jnp.asarray(a), jnp.asarray(b),
                         (jnp.asarray(ai),), (jnp.asarray(bi),))
    np.testing.assert_array_equal(np.asarray(v)[0], [3.0, 2.0, 1.0])
    np.testing.assert_array_equal(np.asarray(i)[0], [0, 9, 1])


# ----------------------------------------------------------------------
# ops.route_step vs the pure-jnp oracle
# ----------------------------------------------------------------------

def _random_problem(B, N, seed, *, with_fb=True, with_ad=True,
                    with_load=True, tt_p=0.4, dm_p=0.5, period=0):
    """A random routing problem; ``period`` > 0 repeats the first
    ``period`` catalog rows (embedding and filter columns) over the
    whole catalog, so equal scores recur across every row."""
    rng = np.random.default_rng(seed)
    M = len(METRICS)
    nt, nd = len(TASK_TYPES), len(DOMAINS)
    emb = rng.random((N, M)).astype(np.float32)
    tt = np.vstack([rng.random((nt, N)) < tt_p, np.ones((1, N), bool)])
    dm = np.vstack([rng.random((nd, N)) < dm_p, np.ones((1, N), bool)])
    gmask = rng.random(N) < 0.2
    if period:
        rep = np.arange(N) % period
        emb, tt, dm, gmask = emb[rep], tt[:, rep], dm[:, rep], gmask[rep]
    T = rng.random((B, M)).astype(np.float32)
    W = rng.random((B, M)).astype(np.float32)
    ti = rng.integers(0, nt + 1, B).astype(np.int32)
    di = rng.integers(0, nd + 1, B).astype(np.int32)
    kw = {}
    if with_fb:
        kw["fb"] = (rng.random((B, N)) - 0.5).astype(np.float32)
        kw["fb_weight"] = 0.5
    if with_ad:
        Dc = M + 1
        kw["theta"] = rng.standard_normal((N, Dc)).astype(np.float32) * 0.1
        L = rng.standard_normal((N, Dc, Dc)).astype(np.float32) * 0.05
        kw["ainv"] = np.einsum("nde,nfe->ndf", L, L) \
            + 0.5 * np.eye(Dc, dtype=np.float32)
        kw["alpha"] = 0.8
        kw["ad_weight"] = 0.6
    if with_load:
        kw["lpen"] = (rng.random(N) * 0.3).astype(np.float32)
    return (emb, tt, dm, gmask, T, W, ti, di), kw


def _ref_kwargs(kw):
    return {k2: (jnp.asarray(v) if isinstance(v, np.ndarray) else v)
            for k2, v in kw.items()}


@pytest.mark.parametrize("B,N,k,flags", [
    pytest.param(1, 5, 3, (True, True, True), id="1-5-3-flags0"),  # B=1
    pytest.param(9, 130, 8, (True, False, True),
                 id="9-130-8-flags1"),  # N just past one lane block
    pytest.param(16, 257, 4, (False, True, False),
                 id="16-257-4-flags2"),  # off-by-one catalog
    pytest.param(33, 96, 2, (False, False, False),
                 id="33-96-2-flags3"),  # blend-free, non-pow2 batch
    # 12 lane tiles, 36 pad columns in the last; Qp 8 and Qp 32
    pytest.param(5, 1500, 8, (True, True, True), id="5-1500-8-flags0"),
    pytest.param(20, 1500, 8, (True, True, True), id="20-1500-8-flags0"),
    pytest.param(20, 1500, 8, (False, False, False),
                 id="20-1500-8-flags3"),
])
def test_route_step_matches_ref(B, N, k, flags):
    args, kw = _random_problem(B, N, seed=B * 1000 + N,
                               with_fb=flags[0], with_ad=flags[1],
                               with_load=flags[2])
    r = min(max(5, k), N)
    got = K.route_step(*args, k=k, r=r, **kw)
    want = R.route_step(*(jnp.asarray(a) for a in args), k, r,
                        **_ref_kwargs(kw))
    for key in ("model_idx", "stage", "cand_idx", "n_filtered",
                "n_candidates"):
        np.testing.assert_array_equal(got[key], np.asarray(want[key]),
                                      err_msg=key)
    for key in ("score", "similarity", "cand_score"):
        np.testing.assert_allclose(got[key], np.asarray(want[key]),
                                   rtol=2e-5, atol=2e-5, err_msg=key)


@pytest.mark.parametrize("B,kind,quant", [
    (5, "ties", False), (20, "ties", False),
    (5, "fallback", False), (20, "fallback", True),
    (5, "extras", True), (20, "extras", True)])
def test_select_stage_matches_ref(B, kind, quant):
    """The dense program's select stage (mask rows, masked scores,
    top-R, candidate gathers) against the oracle over 12 lane tiles.
    ``ties``: every row repeats with period 7, so equal values fill
    each row; a picked index may then differ from the oracle's, but
    names a row identical to it.  ``fallback``: sparse filters, so
    most rows walk the ladder.  ``extras``: feedback, bandit and load
    terms on."""
    N, k = 1500, 8
    args, kw = _random_problem(
        B, N, seed=B + 31, with_fb=kind == "extras",
        with_ad=kind == "extras", with_load=kind == "extras",
        tt_p=0.002 if kind == "fallback" else 0.4,
        dm_p=0.002 if kind == "fallback" else 0.5,
        period=7 if kind == "ties" else 0)
    got = K.route_step(*args, k=k, r=k, quant=quant, **kw)
    want = R.route_step(*(jnp.asarray(a) for a in args), k, k,
                        quant=quant, **_ref_kwargs(kw))
    if kind == "fallback":
        assert (got["stage"] > 0).mean() >= 0.6
    for key in ("stage", "n_filtered", "n_candidates"):
        np.testing.assert_array_equal(got[key], np.asarray(want[key]),
                                      err_msg=key)
    for key in ("score", "similarity", "cand_score"):
        np.testing.assert_allclose(got[key], np.asarray(want[key]),
                                   rtol=2e-5, atol=2e-5, err_msg=key)
    if kind == "ties":
        emb, ci = args[0], np.asarray(want["cand_idx"])
        live = got["cand_idx"] >= 0
        np.testing.assert_array_equal(live, ci >= 0)
        np.testing.assert_array_equal(emb[got["cand_idx"][live]],
                                      emb[ci[live]])
    else:
        for key in ("model_idx", "cand_idx"):
            np.testing.assert_array_equal(got[key], np.asarray(want[key]),
                                          err_msg=key)


def test_route_step_pallas_path_matches_jnp():
    """use_pallas=True (interpret-mode kernel kNN inside the fused
    program) is decision-identical to the jnp top-k path."""
    args, kw = _random_problem(11, 150, seed=3)
    got_j = K.route_step(*args, k=6, r=6, **kw, use_pallas=False)
    got_p = K.route_step(*args, k=6, r=6, **kw, use_pallas=True)
    np.testing.assert_array_equal(got_j["model_idx"], got_p["model_idx"])
    np.testing.assert_array_equal(got_j["stage"], got_p["stage"])
    np.testing.assert_allclose(got_j["score"], got_p["score"],
                               rtol=1e-5, atol=1e-5)


# ----------------------------------------------------------------------
# fused route_many vs the staged numpy reference path
# ----------------------------------------------------------------------

def _assert_decisions_match(fused, staged, *, tol=1e-4):
    assert len(fused) == len(staged)
    for a, b in zip(fused, staged):
        assert a.fallback_kind == b.fallback_kind
        assert a.used_fallback == b.used_fallback
        assert a.stage_sizes == b.stage_sizes
        if a.model == b.model:
            assert a.score == pytest.approx(b.score, abs=tol)
        else:       # fp tie at the top: the picks must tie in score
            assert a.score == pytest.approx(b.score, abs=tol)
        assert a.similarity == pytest.approx(b.similarity, abs=tol)
        assert len(a.candidates) == len(b.candidates)
        for (_, sa), (_, sb) in zip(a.candidates, b.candidates):
            assert sa == pytest.approx(sb, abs=tol)


def _full_engine(n=64, seed=0, *, with_fb=True, with_ad=True,
                 with_load=True):
    mres = random_catalog(n, seed=seed)
    rng = np.random.default_rng(seed + 1)
    fb = None
    if with_fb:
        fb = FeedbackStore()
        for _ in range(50):
            fb.record(TaskSignature(
                task_type=str(rng.choice(TASK_TYPES)),
                domain=str(rng.choice(DOMAINS)),
                complexity=float(rng.random())),
                f"m{int(rng.integers(n))}", bool(rng.random() < 0.5))
    ad = None
    if with_ad:
        ad = LinearBandit(n, seed=seed)
        for _ in range(4):
            X = rng.random((16, len(METRICS))).astype(np.float32)
            ad.update(X, rng.integers(0, n, 16),
                      rng.random(16).astype(np.float32))
    load = None
    if with_load:
        load = LoadTracker(n)
        for j in rng.integers(0, n, 3 * n):
            load.admit(int(j))
    return RoutingEngine(mres, fb, knn_k=8,
                         adaptive=ad, adaptive_weight=0.7 if ad else 0.0,
                         load=load, load_weight=0.5 if load else 0.0)


_FLAGS = [(False, False, False), (True, True, True)]


@pytest.mark.parametrize("b,flags,n", [
    pytest.param(b, f, 64, id=f"flags{i}-{b}")
    for i, f in enumerate(_FLAGS) for b in (1, 13)]
    # 12 lane tiles; Qp 8 and Qp 32
    + [pytest.param(b, f, 1500, id=f"flags{i}-{b}-n1500")
       for i, f in enumerate(_FLAGS) for b in (5, 20)])
def test_fused_matches_staged_full_blend(b, flags, n):
    eng = _full_engine(n, seed=b, with_fb=flags[0], with_ad=flags[1],
                       with_load=flags[2])
    prefs, sigs = random_queries(b, seed=b + 5)
    fused = eng.route_many_batch(prefs, sigs).decisions()
    staged = eng.route_many_staged(prefs, sigs)
    _assert_decisions_match(fused, staged)


def test_fused_matches_staged_fallback_heavy():
    """A catalog with narrow coverage forces every fallback rung."""
    from tests.conftest import make_entry
    from repro.core.mres import MRES
    m = MRES()
    m.register(make_entry("gen", task_types=("chat",), generalist=True))
    m.register(make_entry("coder", task_types=("code",),
                          domains=("software",)))
    m.register(make_entry("fin", task_types=("classification",),
                          domains=("finance",)))
    eng = RoutingEngine(m, knn_k=4)
    sigs = [TaskSignature(task_type="vqa", domain="healthcare"),
            TaskSignature(task_type="code", domain="software"),
            TaskSignature(task_type="code", domain="legal"),
            TaskSignature(task_type="chat", domain="general",
                          confidence=0.1)]
    fused = eng.route_many_batch("balanced", sigs).decisions()
    staged = eng.route_many_staged("balanced", sigs)
    _assert_decisions_match(fused, staged)
    assert fused[0].used_fallback


def test_thompson_policy_falls_back_to_staged():
    """A Thompson bandit samples host RNG per score — the engine must
    refuse to fuse and stay on the staged path."""
    mres = random_catalog(16, seed=2)
    ad = LinearBandit(16, policy="thompson", seed=0)
    eng = RoutingEngine(mres, adaptive=ad, adaptive_weight=0.5)
    assert not eng._fused_ok()
    prefs, sigs = random_queries(4, seed=2)
    out = eng.route_many(prefs, sigs)          # staged, but functional
    assert len(out) == 4


# ----------------------------------------------------------------------
# shape buckets: zero steady-state recompiles, one dispatch per batch
# ----------------------------------------------------------------------

def test_zero_recompiles_across_mixed_batch_sizes():
    mres = random_catalog(48, seed=9)
    eng = RoutingEngine(mres, knn_k=8)
    # warm up every power-of-two bucket the replay will touch
    for b in (1, 9, 17, 33):
        prefs, sigs = random_queries(b, seed=b)
        eng.route_many(prefs, sigs)
    warm = K.route_step_stats()
    replay = (3, 1, 12, 30, 8, 21, 5, 16, 2)
    for i, b in enumerate(replay):
        prefs, sigs = random_queries(b, seed=100 + i)
        eng.route_many(prefs, sigs)
    stats = K.route_step_stats()
    assert stats["route_step_compiles"] == warm["route_step_compiles"], \
        "mixed batch sizes recompiled after warmup"
    # exactly ONE device dispatch per routed batch
    assert stats["route_step_dispatches"] \
        == warm["route_step_dispatches"] + len(replay)


def test_empty_batch_on_empty_catalog_matches_staged():
    """route_many([], []) returns [] even on an EMPTY catalog — the
    fused wrapper must keep the staged path's check order (B == 0
    before the empty-catalog raise)."""
    from repro.core.mres import MRES
    eng = RoutingEngine(MRES())
    assert eng.route_many([], []) == []
    assert eng.route_many_staged([], []) == []
    # a NON-empty batch against an empty catalog raises (RuntimeError
    # from the catalog check, or ValueError from the empty-catalog
    # normalize inside snapshot() — the pre-existing behavior)
    with pytest.raises((RuntimeError, ValueError)):
        eng.route_many([UserPreferences()], [TaskSignature()])


def test_catalog_growth_within_bucket_does_not_recompile():
    """Registering models within one 128-padded capacity bucket must
    reuse the cached executable (liveness lives in the mask table and
    traced arrays, not in the jit's static key)."""
    mres = random_catalog(40, seed=11)
    eng = RoutingEngine(mres, knn_k=8)
    prefs, sigs = random_queries(6, seed=11)
    eng.route_many(prefs, sigs)                    # warm 40-model state
    from tests.conftest import make_entry
    mres.register(make_entry("grown", task_types=("chat",),
                             generalist=True))     # 41 <= 128 bucket
    warm = K.route_step_stats()
    out = eng.route_many(prefs, sigs)
    assert len(out) == 6
    stats = K.route_step_stats()
    assert stats["route_step_compiles"] == warm["route_step_compiles"]


def test_bucket_helpers():
    assert [K.q_bucket(b) for b in (1, 7, 8, 9, 64, 65)] == \
        [8, 8, 8, 16, 64, 128]
    assert [K.n_bucket(n) for n in (1, 128, 129, 4096)] == \
        [128, 128, 256, 4096]


def test_cache_lookup_bucketed_zero_recompiles():
    from repro.cache.semantic import SemanticCache
    cache = SemanticCache(capacity=64, use_kernel=True, kernel_min_n=1,
                          threshold=0.9)
    prefs = UserPreferences()
    texts = [f"query number {i}" for i in range(8)]
    keys = cache.keys_for([prefs] * 8, texts)
    fps = cache.fingerprints([prefs] * 8)
    for i in range(8):
        cache.put(keys[i], int(fps[i]), "m0", np.arange(4), 0.9)
    for b in (1, 5, 8):                               # warm the buckets
        cache.lookup(keys[:b], fps[:b])
    warm = K.route_step_stats()
    for b in (2, 7, 3, 6, 1, 8):
        hit, slot, sim = cache.lookup(keys[:b], fps[:b])
        assert hit.all()
    stats = K.route_step_stats()
    assert stats["topk_compiles"] == warm["topk_compiles"]
    assert stats["topk_dispatches"] == warm["topk_dispatches"] + 6


# ----------------------------------------------------------------------
# RoutingBatch: array-first contract + lazy materialization
# ----------------------------------------------------------------------

def test_routing_batch_lazy_materialization():
    eng = RoutingEngine(random_catalog(32, seed=4), knn_k=8)
    prefs, sigs = random_queries(6, seed=4)
    batch = eng.route_many_batch(prefs, sigs)
    assert len(batch) == 6
    assert all(d is None for d in batch._cache), \
        "decisions materialized eagerly"
    models = batch.models()               # array-only view
    assert all(d is None for d in batch._cache)
    d3 = batch.decision(3)
    assert d3.model == models[3]
    assert batch._cache[3] is d3 and batch._cache[0] is None
    assert batch.decision(3) is d3        # memoized
    # full materialization equals the object API
    assert [d.model for d in batch.decisions()] == models


def test_routed_query_lazy_decision():
    from repro.core.orchestrator import OptiRoute
    from tests.test_routing_batch import StubAnalyzer
    router = OptiRoute(random_catalog(24, seed=6), StubAnalyzer())
    rqs = router.route_all([f"q{i}" for i in range(5)], "balanced")
    assert all(rq._decision is None for rq in rqs), \
        "route_all materialized decisions on the hot path"
    assert rqs[0].model in {e.name for e in router.mres.entries}
    assert rqs[0].fallback_kind == ""
    assert rqs[0]._decision is None       # cheap accessors stay lazy
    d = rqs[0].decision
    assert d.model == rqs[0].model        # materializes on demand


# ----------------------------------------------------------------------
# satellite regression: _topk_two_level must not mutate its input
# ----------------------------------------------------------------------

@pytest.mark.parametrize("n", [256, 300])   # chunk-aligned and not
def test_topk_two_level_does_not_mutate_input(n):
    rng = np.random.default_rng(n)
    ms = rng.random((5, n)).astype(np.float32)
    before = ms.copy()
    vals, idx = _topk_two_level(ms, k=4)
    np.testing.assert_array_equal(ms, before)
    # and it still returns the right answer
    want = -np.sort(-ms, axis=1)[:, :4]
    np.testing.assert_allclose(vals, want, atol=0)
