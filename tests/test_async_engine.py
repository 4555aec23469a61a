"""Asyncio serving front-end: token buckets, tenant policies, the
clock-agnostic MicroBatcher (aggregation windows + weighted-fair
deficit-round-robin dequeue), the AsyncServingEngine end-to-end path,
and a thread hammer on the synchronous engine's submit."""
import asyncio
import threading
from types import SimpleNamespace

import numpy as np
import pytest

from repro.core.mres import MRES
from repro.core.orchestrator import OptiRoute
from repro.core.telemetry import Telemetry
from repro.serving.async_engine import (REJECT_BACKLOG, REJECT_RATE,
                                        AsyncServingEngine, MicroBatcher,
                                        TenantPolicy, TokenBucket,
                                        window_stats)
from repro.serving.engine import Request, ServingEngine
from repro.serving.load import LoadTracker
from tests.conftest import make_entry
from tests.test_routing_batch import StubAnalyzer


class FakeRunner:
    """Zero-weight runner: (B, max_new) token zeros, B * service_s sim
    latency (the engine divides by B -> service_s per request)."""

    cfg = SimpleNamespace(vocab_size=256)

    def __init__(self, service_s=0.001):
        self.service_s = float(service_s)

    def generate(self, toks, max_new=8):
        B = int(np.asarray(toks).shape[0])
        return SimpleNamespace(tokens=np.zeros((B, max_new), np.int32),
                               sim_latency_s=self.service_s * B)


def _engine(tel=None, n=3):
    m = MRES()
    for i in range(n):
        e = make_entry(f"m{i}", accuracy=0.9 - 0.05 * i,
                       latency_ms=50.0 + 10 * i, cost=1.0 + i,
                       generalist=True)
        e.runner = FakeRunner()
        m.register(e)
    tracker = LoadTracker(n, default_service_s=0.01)
    router = OptiRoute(m, StubAnalyzer(), knn_k=n, telemetry=tel,
                       load=tracker, load_weight=1.0)
    return ServingEngine(router), tracker


def _req(i, tenant="acme", **kw):
    return Request(text=f"request {i}", prefs="balanced", id=i,
                   max_new=2, tenant=tenant, **kw)


# ----------------------------------------------------------------------
# token bucket / tenant policy
# ----------------------------------------------------------------------

def test_token_bucket_refill_and_cap():
    tb = TokenBucket(rate=2.0, burst=2.0)
    assert tb.try_take(0.0) and tb.try_take(0.0)
    assert not tb.try_take(0.0)          # bucket empty
    assert tb.try_take(0.5)              # 0.5s * 2/s = 1 token back
    assert not tb.try_take(0.5)
    # a long idle stretch refills to the burst ceiling, not beyond
    assert tb.try_take(100.0) and tb.try_take(100.0)
    assert not tb.try_take(100.0)


def test_tenant_policy_defaults_and_validation():
    assert TenantPolicy().make_bucket() is None       # unlimited
    b = TenantPolicy(rate=3.0).make_bucket()
    assert (b.rate, b.burst) == (3.0, 6.0)            # burst = 2*rate
    assert TenantPolicy(rate=0.2).make_bucket().burst == 1.0
    assert TenantPolicy(rate=5.0, burst=1.0).make_bucket().burst == 1.0
    with pytest.raises(AssertionError):
        TenantPolicy(weight=0.0).validate()
    with pytest.raises(AssertionError):
        TenantPolicy(rate=-1.0).validate()
    with pytest.raises(AssertionError):
        TenantPolicy(max_backlog=0).validate()


# ----------------------------------------------------------------------
# micro-batcher: windows + weighted-fair dequeue (deterministic clock)
# ----------------------------------------------------------------------

def test_microbatcher_window_clock():
    mb = MicroBatcher(max_batch=4, max_wait_s=0.01)
    assert not mb.due(0.0) and mb.next_deadline(0.0) is None
    assert mb.offer("a", "x0", 0.0) == "queued"
    assert not mb.due(0.005)             # window still open
    assert mb.next_deadline(0.005) == pytest.approx(0.01)
    assert mb.due(0.01)                  # oldest item aged out
    # filling the batch makes the window due immediately
    for j in range(3):
        mb.offer("a", f"x{j + 1}", 0.002)
    assert mb.due(0.002)
    assert mb.next_deadline(0.002) == 0.002
    assert mb.take(0.002) == ["x0", "x1", "x2", "x3"]
    assert mb.pending() == 0 and mb.backlog() == {"a": 0}


def test_microbatcher_drr_weight_proportions():
    mb = MicroBatcher(max_batch=16, policies={
        "acme": TenantPolicy(weight=3.0), "globex": TenantPolicy()})
    for j in range(10):
        mb.offer("acme", ("acme", j), 0.0)
        mb.offer("globex", ("globex", j), 0.0)
    out = mb.take(0.0, limit=8)
    by = {"acme": 0, "globex": 0}
    for t, _ in out:
        by[t] += 1
    assert by == {"acme": 6, "globex": 2}    # 3:1 weights
    # FIFO within each tenant
    assert [j for t, j in out if t == "acme"] == list(range(6))


def test_microbatcher_deficit_resets_on_empty_queue():
    mb = MicroBatcher(max_batch=8,
                      policies={"slow": TenantPolicy(weight=0.4)})
    mb.offer("slow", "s0", 0.0)
    assert mb.take(0.0) == ["s0"]        # multiple passes accrue deficit
    # the emptied queue must not bank leftover credit
    assert mb._deficit["slow"] == 0.0
    mb.offer("slow", "s1", 1.0)
    assert mb.take(1.0) == ["s1"]


def test_microbatcher_queue_waits_on_a_fake_clock():
    """Each taken item keeps its offer time; the window's waits run
    from there to the start of its service, on the same clock."""
    mb = MicroBatcher(max_batch=3, max_wait_s=0.005)
    for t, item in ((0.000, "a0"), (0.001, "a1"), (0.004, "a2"),
                    (0.006, "a3")):
        mb.offer("acme", item, t)
    stamped = mb.take_stamped(0.006)
    assert stamped == [(0.000, "a0"), (0.001, "a1"), (0.004, "a2")]
    start = 0.010                       # service starts after the take
    assert [start - t for t, _ in stamped] == pytest.approx(
        [0.010, 0.009, 0.006])
    stats = window_stats([t for t, _ in stamped], start, mb.pending())
    assert stats == pytest.approx({"size": 3, "wait_ms_sum": 25.0,
                                   "wait_ms_max": 10.0, "backlog": 1})
    # the left-over request makes the next window, its wait its own
    (last,) = mb.take_stamped(0.020)
    assert window_stats([last[0]], 0.021, mb.pending()) == pytest.approx(
        {"size": 1, "wait_ms_sum": 15.0, "wait_ms_max": 15.0,
         "backlog": 0})
    assert window_stats([], 0.0, 0)["wait_ms_max"] == 0.0


def test_microbatcher_intake_rejections_and_stats():
    mb = MicroBatcher(max_batch=8, policies={
        "flood": TenantPolicy(rate=1.0, burst=1.0),
        "bursty": TenantPolicy(max_backlog=2)})
    assert mb.offer("flood", "f0", 0.0) == "queued"
    assert mb.offer("flood", "f1", 0.0) == REJECT_RATE
    assert mb.offer("flood", "f2", 1.0) == "queued"   # refilled
    assert [mb.offer("bursty", f"b{j}", 0.0) for j in range(3)] \
        == ["queued", "queued", REJECT_BACKLOG]
    assert mb.stats["flood"] == {"offered": 3, "queued": 2,
                                 "rate_limited": 1, "backlog_shed": 0}
    assert mb.stats["bursty"]["backlog_shed"] == 1
    assert mb.pending() == 4             # rejected items never buffered


# ----------------------------------------------------------------------
# async engine end-to-end (asyncio.run; no pytest-asyncio dependency)
# ----------------------------------------------------------------------

def test_async_engine_serves_windows_and_sheds_flood():
    tel = Telemetry()
    eng, tracker = _engine(tel=tel)
    aeng = AsyncServingEngine(
        eng, max_batch=4, max_wait_ms=5,
        policies={"flood": TenantPolicy(rate=1.0, burst=1.0)})

    async def drive():
        async with aeng:
            # deadline-carrying requests land their verdict in the
            # telemetry funnel (SLO-less traffic is engine-log only)
            good = [aeng.submit(_req(i, deadline_ms=10_000.0))
                    for i in range(10)]
            bad = [aeng.submit(_req(100 + i, tenant="flood"))
                   for i in range(5)]
            return await asyncio.gather(*good, *bad)

    resps = asyncio.run(drive())
    good, bad = resps[:10], resps[10:]
    assert all(r.admission == "admitted" and not r.error for r in good)
    assert [r.request.id for r in good] == list(range(10))
    sheds = [r for r in bad if r.admission == "shed"]
    assert len(sheds) == 4 and all(r.error == REJECT_RATE for r in sheds)
    assert sum(1 for r in bad if r.admission == "admitted") == 1
    # window accounting: every accepted request flushed, bounded windows
    assert sum(aeng.windows) == 11
    assert all(1 <= w <= 4 for w in aeng.windows)
    assert len(eng.log) == 15            # sheds land in the log too
    # tracker nets to zero; per-tenant funnel attributes the sheds
    q, f, _, _ = tracker.snapshot()
    assert (q == 0).all() and (f == 0).all()
    by = tel.admission_by_tenant()
    assert by["acme"]["admitted"] == 10
    assert by["flood"]["shed"] == 4
    assert tel.summary()["counters"]["intake_rate_limited"] == 4


def test_async_engine_stop_drains_backlog():
    eng, _ = _engine()
    aeng = AsyncServingEngine(eng, max_batch=32, max_wait_ms=10_000)

    async def drive():
        async with aeng:
            tasks = [asyncio.ensure_future(aeng.submit(_req(i)))
                     for i in range(3)]
            await asyncio.sleep(0)       # let every submit enqueue
            # exit drains: the 10s window must NOT hold the futures
        return await asyncio.gather(*tasks)

    resps = asyncio.run(drive())
    assert [r.request.id for r in resps] == [0, 1, 2]
    assert all(r.served for r in resps)


def test_async_engine_requires_start():
    eng, _ = _engine()
    aeng = AsyncServingEngine(eng)

    async def drive():
        with pytest.raises(RuntimeError, match="not started"):
            await aeng.submit(_req(0))

    asyncio.run(drive())


# ----------------------------------------------------------------------
# thread hammer on the synchronous submit path
# ----------------------------------------------------------------------

def test_submit_concurrent_thread_hammer():
    tel = Telemetry()
    eng, tracker = _engine(tel=tel)
    errs = []

    def work(tid):
        try:
            for k in range(5):
                reqs = [_req(tid * 100 + k * 10 + j, tenant=f"t{tid}",
                             deadline_ms=10_000.0) for j in range(3)]
                resps = eng.submit(reqs)
                assert len(resps) == 3
                assert all(r.served for r in resps)
        except Exception as e:                     # noqa: BLE001
            errs.append(e)

    threads = [threading.Thread(target=work, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs, errs
    q, f, _, _ = tracker.snapshot()
    assert (q == 0).all() and (f == 0).all()       # no leaked lifecycle
    assert len(eng.log) == 4 * 5 * 3
    s = eng.summary()
    assert s["requests"] == 60
    funnel = tel.admission_funnel()
    assert sum(funnel.values()) == 60
    assert funnel.get("failed", 0) == 0 and funnel.get("shed", 0) == 0
    by = tel.admission_by_tenant()
    assert {t: sum(k.values()) for t, k in by.items()} \
        == {f"t{i}": 15 for i in range(4)}
