"""Fused analyze->route path: kernel/oracle parity, fused-vs-staged
differential (single source of truth: ``analyze_batch`` +
``route_many``), vectorized tokenizer/pruning equivalence, empty-batch
and B=1 bucket-reuse regressions, and the one-dispatch / zero-recompile
guards for the tokens->decision program."""
import numpy as np
import pytest

import jax

from repro.core.analyzer import (AnalyzerConfig, TaskAnalyzer,
                                 init_analyzer, prune_text, prune_texts,
                                 quantize_int8)
from repro.core.feedback import FeedbackStore
from repro.core.orchestrator import OptiRoute
from repro.core.preferences import DOMAINS, TASK_TYPES, UserPreferences
from repro.core.routing import RoutingEngine
from repro.data.tokenizer import PAD_ID, HashTokenizer
from repro.kernels import ops as K
from repro.kernels import ref as R
from tests.test_routing_batch import StubAnalyzer, random_catalog

# small config: fast to init, and a distinct max_len per size so two
# differently-shaped analyzers never share a recompile-sentinel
# signature (the sentinel keys on the token axis, not d_model)
CFG = AnalyzerConfig(vocab_size=512, d_model=32, n_layers=1, n_heads=2,
                     d_ff=64, max_len=24)


@pytest.fixture(scope="module")
def analyzer():
    return TaskAnalyzer(CFG, seed=3)


def _tokens(analyzer, b, seed=0):
    texts = _texts(b, seed)
    return analyzer.encode_batch(texts), texts


def _texts(b, seed=0):
    rng = np.random.default_rng(seed)
    vocab = ["summarize", "translate", "code", "legal", "brief",
             "question", "urgent", "report", "python", "medical"]
    return [" ".join(rng.choice(vocab, size=int(rng.integers(2, 12))))
            for _ in range(b)]


# ----------------------------------------------------------------------
# kernel vs oracle parity (repro.kernels.ref)
# ----------------------------------------------------------------------

@pytest.mark.parametrize("quant", [False, True])
def test_analyze_step_matches_ref(analyzer, quant):
    """``ops.analyze_step`` == ``ref.analyze_step`` — the oracle is an
    independently-written jnp encoder, so this pins the moved
    ``analyzer_forward`` AND the on-device argmax/confidence epilogue."""
    params = quantize_int8(analyzer.params) if quant else analyzer.params
    toks, _ = _tokens(analyzer, 5, seed=1)
    got = K.analyze_step(params, CFG, toks)
    want = R.analyze_step(params, CFG, toks, pad_id=PAD_ID)
    np.testing.assert_array_equal(got["tt_idx"],
                                  np.asarray(want["tt_idx"]))
    np.testing.assert_array_equal(got["dm_idx"],
                                  np.asarray(want["dm_idx"]))
    np.testing.assert_allclose(got["cx"], np.asarray(want["cx"]),
                               atol=2e-5)
    np.testing.assert_allclose(got["conf"], np.asarray(want["conf"]),
                               atol=2e-5)


@pytest.mark.parametrize("with_fb,with_ad,with_load", [
    (False, False, False), (True, False, False), (True, True, True)])
def test_analyze_route_step_matches_ref(analyzer, with_fb, with_ad,
                                        with_load):
    """The full fused program == ``ref.analyze_route_step`` (oracle
    encoder composed with the unpadded oracle ``route_step``)."""
    rng = np.random.default_rng(11)
    n, m = 20, 8
    nt, nd = len(TASK_TYPES), len(DOMAINS)
    emb = rng.random((n, m)).astype(np.float32)
    tt = np.vstack([rng.random((nt, n)) < 0.4, np.ones((1, n), bool)])
    dm = np.vstack([rng.random((nd, n)) < 0.5, np.ones((1, n), bool)])
    gmask = rng.random(n) < 0.3
    toks, _ = _tokens(analyzer, 6, seed=2)
    W = rng.random((6, m)).astype(np.float32)
    kw = {}
    if with_fb:
        kw["fb_table"] = rng.normal(
            size=(nt * nd * 4, n)).astype(np.float32) * 0.1
        kw["fb_weight"] = 0.7
    if with_ad:
        dc = m + 1                       # bandit context + intercept
        kw["theta"] = rng.normal(size=(n, dc)).astype(np.float32) * 0.1
        kw["ainv"] = np.stack([np.eye(dc, dtype=np.float32)] * n)
        kw["alpha"] = 0.4
        kw["ad_weight"] = 0.5
    if with_load:
        kw["lpen"] = rng.random(n).astype(np.float32)
    got = K.analyze_route_step(
        analyzer.params, CFG, toks, emb, tt, dm, gmask, W,
        k=5, r=5, threshold=0.08, acc_col=0, **kw)
    want = R.analyze_route_step(
        analyzer.params, CFG, toks, emb, tt, dm, gmask, W, 5, 5,
        threshold=0.08, acc_col=0, pad_id=PAD_ID, **kw)
    for key in ("tt_idx", "dm_idx", "model_idx", "stage",
                "n_filtered", "n_candidates"):
        np.testing.assert_array_equal(got[key], np.asarray(want[key]),
                                      err_msg=key)
    for key in ("cx", "conf", "score", "similarity", "task_vectors"):
        np.testing.assert_allclose(got[key], np.asarray(want[key]),
                                   atol=2e-4, err_msg=key)


@pytest.mark.parametrize("b", [6, 20])          # Qp 8 and Qp 32
@pytest.mark.parametrize("extras", [False, True])
def test_analyze_route_step_matches_ref_over_lane_tiles(analyzer, b,
                                                        extras):
    """The decision cell's program, at its Q buckets (8 and 32), over
    12 lane tiles of catalog: the oracle's outputs."""
    rng = np.random.default_rng(b)
    n, m = 1500, 8
    nt, nd = len(TASK_TYPES), len(DOMAINS)
    emb = rng.random((n, m)).astype(np.float32)
    tt = np.vstack([rng.random((nt, n)) < 0.4, np.ones((1, n), bool)])
    dm = np.vstack([rng.random((nd, n)) < 0.5, np.ones((1, n), bool)])
    gmask = rng.random(n) < 0.3
    toks, _ = _tokens(analyzer, b, seed=b)
    W = rng.random((b, m)).astype(np.float32)
    kw = {}
    if extras:
        kw = dict(fb_table=rng.normal(size=(nt * nd * 4, n)).astype(
            np.float32) * 0.1, fb_weight=0.7, lpen=rng.random(n).astype(
            np.float32))
    got = K.analyze_route_step(
        analyzer.params, CFG, toks, emb, tt, dm, gmask, W, k=8, r=8,
        threshold=0.08, acc_col=0, **kw)
    want = R.analyze_route_step(
        analyzer.params, CFG, toks, emb, tt, dm, gmask, W, 8, 8,
        threshold=0.08, acc_col=0, pad_id=PAD_ID, **kw)
    for key in ("model_idx", "stage", "cand_idx", "n_filtered",
                "n_candidates"):
        np.testing.assert_array_equal(got[key], np.asarray(want[key]),
                                      err_msg=key)
    for key in ("score", "similarity", "cand_score"):
        np.testing.assert_allclose(got[key], np.asarray(want[key]),
                                   atol=2e-4, err_msg=key)


# ----------------------------------------------------------------------
# fused vs staged differential (the semantic pin)
# ----------------------------------------------------------------------

def _engine(mres, **kw):
    return RoutingEngine(mres, kw.pop("feedback", None), knn_k=6, **kw)


def _staged_decisions(eng, analyzer, texts, prefs):
    sigs = analyzer.analyze_batch(texts)
    return sigs, eng.route_many(prefs, sigs)


@pytest.mark.parametrize("b", [1, 3, 8, 17])
def test_fused_tokens_path_matches_staged(analyzer, b):
    """tokens->decision in ONE program == analyze_batch -> route_many,
    decision-identical (model, stage, signature) at every batch size
    including the B=1 interactive shape."""
    mres = random_catalog(24, seed=5)
    eng = _engine(mres)
    texts = _texts(b, seed=b)
    prefs = "balanced"
    toks = analyzer.encode_batch(texts)
    batch = eng.route_tokens_batch(analyzer.params, analyzer.cfg, toks,
                                   prefs)
    sigs, staged = _staged_decisions(eng, analyzer, texts, prefs)
    assert batch.models() == [d.model for d in staged]
    for i, (sig, d) in enumerate(zip(sigs, staged)):
        got = batch.signature(i)
        assert (got.task_type, got.domain) == (sig.task_type, sig.domain)
        assert got.complexity == pytest.approx(sig.complexity, abs=1e-5)
        assert got.confidence == pytest.approx(sig.confidence, abs=1e-5)
        assert batch.fallback_kind(i) == d.fallback_kind
        assert batch.score[i] == pytest.approx(d.score, abs=1e-4)


@pytest.mark.parametrize("threshold", [0.0, 0.12, 1.1])
def test_fused_confidence_threshold_matches_staged(analyzer, threshold):
    """The in-program confidence gate (traced scalar) replicates the
    host-side thresholding at always-confident, mixed, and
    never-confident settings — exercising both ANY-row fallbacks."""
    mres = random_catalog(16, seed=6)
    eng = _engine(mres, confidence_threshold=threshold)
    texts = _texts(9, seed=31)
    toks = analyzer.encode_batch(texts)
    batch = eng.route_tokens_batch(analyzer.params, analyzer.cfg, toks,
                                   "cost-effective")
    _, staged = _staged_decisions(eng, analyzer, texts, "cost-effective")
    assert batch.models() == [d.model for d in staged]
    assert [batch.fallback_kind(i) for i in range(len(batch))] == \
        [d.fallback_kind for d in staged]


def test_fused_feedback_bias_table_matches_staged(analyzer):
    """The dense per-cluster bias table gathered in-program == the
    staged ``bias_batch`` keyed on materialized signatures."""
    mres = random_catalog(12, seed=7)
    fs = FeedbackStore()
    texts = _texts(10, seed=17)
    sigs = analyzer.analyze_batch(texts)
    names = mres.snapshot()[1]
    rng = np.random.default_rng(3)
    for s in sigs[::2]:
        fs.record(s, names[int(rng.integers(len(names)))],
                  bool(rng.integers(2)))
    eng = _engine(mres, feedback=fs, feedback_weight=2.5)
    toks = analyzer.encode_batch(texts)
    batch = eng.route_tokens_batch(analyzer.params, analyzer.cfg, toks,
                                   "balanced")
    _, staged = _staged_decisions(eng, analyzer, texts, "balanced")
    assert batch.models() == [d.model for d in staged]
    np.testing.assert_allclose(batch.score,
                               [d.score for d in staged], atol=1e-4)


def test_fused_int8_analyzer_within_quant_error(analyzer):
    """int8 analyzer through the fused program: decisions match the
    int8 STAGED path exactly (same numerics end to end), and the
    int8-vs-fp32 signature drift stays within the quantization error
    budget."""
    q = TaskAnalyzer(CFG, seed=3)
    q.params = quantize_int8(q.params)
    mres = random_catalog(16, seed=8)
    eng = _engine(mres)
    texts = _texts(12, seed=23)
    toks = q.encode_batch(texts)
    batch = eng.route_tokens_batch(q.params, q.cfg, toks, "balanced")
    sigs_q, staged = _staged_decisions(eng, q, texts, "balanced")
    assert batch.models() == [d.model for d in staged]
    sigs_f = analyzer.analyze_batch(texts)       # fp32 reference
    drift = [abs(a.complexity - b.complexity)
             for a, b in zip(sigs_q, sigs_f)]
    assert max(drift) < 0.15, f"int8 complexity drift {max(drift)}"


def test_bandit_and_load_blend_in_fused_program(analyzer):
    """LinUCB posterior + load penalty ride the fused dispatch and
    match the staged blend."""
    from repro.adaptive.bandit import LinearBandit
    from repro.core.preferences import N_METRICS
    from repro.serving.load import LoadTracker
    mres = random_catalog(12, seed=9)
    names = mres.snapshot()[1]
    bandit = LinearBandit(len(names), policy="linucb", alpha=0.3)
    rng = np.random.default_rng(5)
    bandit.update(rng.random((6, N_METRICS)).astype(np.float32),
                  rng.integers(0, len(names), 6),
                  rng.random(6).astype(np.float32))
    load = LoadTracker(len(names), capacity=2.0)
    for j in range(4):
        load.admit(j % len(names))
    eng = _engine(mres, adaptive=bandit, adaptive_weight=0.6,
                  load=load, load_weight=0.4)
    texts = _texts(7, seed=41)
    toks = analyzer.encode_batch(texts)
    batch = eng.route_tokens_batch(analyzer.params, analyzer.cfg, toks,
                                   "balanced")
    _, staged = _staged_decisions(eng, analyzer, texts, "balanced")
    assert batch.models() == [d.model for d in staged]
    np.testing.assert_allclose(batch.score,
                               [d.score for d in staged], atol=1e-4)


# ----------------------------------------------------------------------
# vectorized tokenizer / pruning == per-row reference
# (randomized hypothesis variants live in tests/test_properties.py)
# ----------------------------------------------------------------------

EDGE_TEXTS = ["", "   ", "a", "hello world", "HELLO World",
              "don't STOP!!! 42 times...", "x " * 200,
              "tabs\tand\nnewlines here", "!!!...???", "é café"]


@pytest.mark.parametrize("max_len", [1, 2, 7, 24])
def test_encode_batch_matches_encode_reference(max_len):
    """Vectorized ``encode_batch`` is bit-identical to the per-row
    reference ``encode`` loop over the edge-case corpus (empty text,
    whitespace-only, truncation, punctuation, unicode)."""
    tok = HashTokenizer(128)
    got = tok.encode_batch(EDGE_TEXTS, max_len)
    want = np.full((len(EDGE_TEXTS), max_len), PAD_ID, np.int32)
    for i, t in enumerate(EDGE_TEXTS):
        ids = tok.encode(t, max_len)
        want[i, :len(ids)] = ids
    np.testing.assert_array_equal(got, want)
    assert tok.encode_batch([], max_len).shape == (0, max_len)


def test_prune_texts_matches_prune_text_reference():
    """Batch pruning == per-text reference pruning, across the budget
    boundary (word counts straddle prune_head+prune_tail+prune_mid):
    same rng stream per text, so the kept-index sets are identical."""
    cfg = AnalyzerConfig(prune_head=10, prune_tail=6, prune_mid=4)
    lengths = [0, 1, 19, 20, 21, 50, 300]
    texts = [" ".join(f"w{i}" for i in range(n)) for n in lengths]
    for seed in (0, 7):
        assert prune_texts(cfg, texts, seed=seed) == \
            [prune_text(cfg, t, seed=seed) for t in texts]
    assert prune_texts(cfg, [], seed=0) == []


# ----------------------------------------------------------------------
# regressions: empty batch, B=1 bucket reuse, dispatch accounting
# ----------------------------------------------------------------------

def test_analyze_batch_empty_is_fast_path(analyzer):
    """Regression: analyze_batch([]) used to pad to a bucket of 1 and
    run the forward on a garbage row; now it returns [] without any
    device dispatch."""
    before = K.route_step_stats()
    assert analyzer.analyze_batch([]) == []
    after = K.route_step_stats()
    assert after["analyze_step_dispatches"] == \
        before["analyze_step_dispatches"]


def test_ops_entries_reject_empty_batch(analyzer):
    """B=0 is the CALLERS' fast path — the bucketed dispatchers fail
    loud rather than pad an empty batch onto the device."""
    empty = np.zeros((0, CFG.max_len), np.int32)
    with pytest.raises(AssertionError):
        K.analyze_step(analyzer.params, CFG, empty)


def test_interactive_route_reuses_batch_bucket(analyzer):
    """Regression: interactive ``OptiRoute.route`` compiled its own
    B=1 analyzer shape.  Routing through the shared bucketed entry, a
    single query after a batch adds dispatches but ZERO compiles (both
    ride the 8-row-floor bucket)."""
    router = OptiRoute(random_catalog(16, seed=12), analyzer)
    router.route_all(_texts(5, seed=51), "balanced")   # warm bucket 8
    warm = K.route_step_stats()
    rq = router.route("one interactive question", "balanced")
    assert rq.model in set(router.mres.snapshot()[1])
    stats = K.route_step_stats()
    assert stats["analyze_step_compiles"] == warm["analyze_step_compiles"]
    assert stats["route_step_compiles"] == warm["route_step_compiles"]
    assert stats["route_step_dispatches"] == \
        warm["route_step_dispatches"] + 1


def test_fused_one_dispatch_zero_recompiles_after_warmup(analyzer):
    """The tokens->decision path: exactly ONE device dispatch per
    routed batch and zero recompiles across mixed batch sizes after
    the buckets are warm."""
    mres = random_catalog(20, seed=13)
    eng = _engine(mres)
    for b in (1, 9):                       # warm buckets 8 and 16
        toks = analyzer.encode_batch(_texts(b, seed=b))
        eng.route_tokens_batch(analyzer.params, analyzer.cfg, toks,
                               "balanced")
    warm = K.route_step_stats()
    replay = (3, 1, 12, 8, 5, 16, 2)
    for i, b in enumerate(replay):
        toks = analyzer.encode_batch(_texts(b, seed=100 + i))
        eng.route_tokens_batch(analyzer.params, analyzer.cfg, toks,
                               "balanced")
    stats = K.route_step_stats()
    assert stats["route_step_compiles"] == warm["route_step_compiles"]
    assert stats["analyze_step_compiles"] == \
        warm["analyze_step_compiles"]
    # the fused dispatch feeds BOTH counter families, one per batch
    assert stats["route_step_dispatches"] == \
        warm["route_step_dispatches"] + len(replay)
    assert stats["analyze_step_dispatches"] == \
        warm["analyze_step_dispatches"] + len(replay)


def test_fused_emits_one_hook_event_per_batch(analyzer):
    """The recompile hook sees exactly one path="fused" event per
    routed batch, with the analyzer quantization folded into the
    bucket signature."""
    mres = random_catalog(12, seed=14)
    eng = _engine(mres)
    events = []
    old = K.set_recompile_hook(events.append)
    try:
        for b in (4, 7, 2):
            toks = analyzer.encode_batch(_texts(b, seed=b + 60))
            eng.route_tokens_batch(analyzer.params, analyzer.cfg,
                                   toks, "balanced")
    finally:
        K.set_recompile_hook(old)
    fused = [e for e in events if e["path"] == "fused"]
    assert len(fused) == 3
    assert all(e["quant"] == (False, False) for e in fused)
    assert [e["q_bucket"] for e in fused] == [8, 8, 8]


def test_route_tokens_batch_empty_and_guards(analyzer):
    """B=0 short-circuits (empty RoutingBatch with analyzer arrays);
    non-fusable configs fail loud."""
    mres = random_catalog(8, seed=15)
    eng = _engine(mres)
    empty = np.zeros((0, CFG.max_len), np.int32)
    batch = eng.route_tokens_batch(analyzer.params, analyzer.cfg,
                                   empty, "balanced")
    assert len(batch) == 0 and batch.signatures() == []
    eng_off = _engine(mres, fused=False)
    with pytest.raises(ValueError):
        eng_off.route_tokens_batch(analyzer.params, analyzer.cfg,
                                   empty, "balanced")


def test_signature_accessor_requires_fused_batch():
    """Batches from the sig-first path carry no analyzer outputs —
    ``signature`` must say so instead of returning garbage."""
    from tests.test_routing_batch import random_queries
    eng = RoutingEngine(random_catalog(8, seed=16), knn_k=4)
    prefs, sigs = random_queries(3, seed=16)
    batch = eng.route_many_batch(prefs, sigs)
    with pytest.raises(ValueError):
        batch.signature(0)


def test_stub_analyzer_keeps_staged_path():
    """Analyzers without ``supports_fused_route`` (stubs, oracles)
    keep the staged analyze->route pipeline."""
    router = OptiRoute(random_catalog(8, seed=17), StubAnalyzer())
    assert not router._fully_fused_ok()
    out = router.route_all(["q1", "q2"], "balanced")
    assert len(out) == 2 and out[0].sig.task_type == "chat"


# ----------------------------------------------------------------------
# observability wiring
# ----------------------------------------------------------------------

def test_fused_telemetry_and_export_wiring(analyzer):
    """One fused dispatch lands in BOTH Telemetry counter families,
    flows to the prometheus exposition, and round-trips through
    ``metrics_from_prom`` (the SLO gate's view)."""
    from repro.core.telemetry import Telemetry
    from repro.obs import Tracer
    from repro.obs.export import metrics_from_prom, prometheus_text
    tel, tr = Telemetry(), Tracer()
    router = OptiRoute(random_catalog(10, seed=18), analyzer,
                       telemetry=tel, tracer=tr)
    router.route_all(_texts(4, seed=71), "balanced")
    rs, an = tel.route_step_stats(), tel.analyze_step_stats()
    assert rs["dispatches"] == 1 and an["dispatches"] == 1
    assert rs["compiles"] == an["compiles"]
    m = metrics_from_prom(prometheus_text(tel, tracer=tr))
    assert m["analyze_step_dispatches"] == 1.0
    assert m["analyze_step_compiles"] == float(an["compiles"])
    (span,) = [s for s in tr.spans() if s.name == "route_step"]
    assert span.attrs["path"] == "fused"
    assert span.attrs["q_bucket"] == 8
    assert span.attrs["analyzer_quant"] is False
    assert "compiles" in span.attrs
    (asp,) = [s for s in tr.spans() if s.name == "analyze"]
    assert asp.attrs == {"path": "fused", "batch": 4}


def test_staged_analyze_batch_reports_analyze_step(analyzer):
    """The solo bucketed analyzer dispatch (staged path) feeds the
    analyze_step counters and its own tracer span."""
    from repro.core.telemetry import Telemetry
    from repro.obs import Tracer
    tel, tr = Telemetry(), Tracer()
    old_tel, old_tr = analyzer.telemetry, analyzer.tracer
    analyzer.telemetry, analyzer.tracer = tel, tr
    try:
        analyzer.analyze_batch(_texts(3, seed=81))
    finally:
        analyzer.telemetry, analyzer.tracer = old_tel, old_tr
    stats = tel.analyze_step_stats()
    assert stats["dispatches"] == 1
    assert tel.route_step_stats()["dispatches"] == 0
    (span,) = [s for s in tr.spans() if s.name == "analyze_step"]
    assert span.attrs["path"] == "analyze"
    assert span.attrs["n_bucket"] == CFG.max_len


def test_feedback_bias_table_identity_and_version():
    """``bias_table`` memoizes on the store version: identical until
    feedback changes (so the device-side padded copy caches on id),
    rebuilt - and re-keyed - after a record."""
    from repro.core.preferences import TaskSignature
    fs = FeedbackStore()
    names = ["m0", "m1", "m2"]
    t0 = fs.bias_table(names)
    assert t0.shape == (len(TASK_TYPES) * len(DOMAINS) * 4, 3)
    assert t0 is fs.bias_table(names)
    v0 = fs.version()
    sig = TaskSignature(task_type=TASK_TYPES[1], domain=DOMAINS[2],
                        complexity=0.9, confidence=0.8)
    fs.record(sig, "m1", True)
    assert fs.version() == v0 + 1
    t1 = fs.bias_table(names)
    assert t1 is not t0
    row = (1 * len(DOMAINS) + 2) * 4 + min(int(0.9 * 4), 3)
    assert t1[row, 1] == pytest.approx(fs.bias(sig, names)[1])
