"""Load generators: drive the system's entry points and time each
request from the client's side.

Every record carries the time the request was due (open loop: its
scheduled arrival; closed loops: the time it was sent), the time its
answer reached the caller, and the program's answer.  Times are
``time.perf_counter`` seconds.
"""
from __future__ import annotations

import asyncio
import concurrent.futures
import time
from dataclasses import dataclass
from typing import Any, List, Optional

GRACE_S = 60.0      # how long past the window an answer is waited for


@dataclass
class Record:
    spec: Any                 # traffic.Spec
    due: float
    done: Optional[float] = None
    answer: Any = None        # Response (async front) or RoutedQuery
    error: str = ""


@dataclass
class Run:
    records: List[Record]
    t0: float                 # window start
    t1: float                 # window end
    lateness: List[float]     # open loop: send time minus due time


def _aengine(system, mix, executor):
    from repro.serving.async_engine import AsyncServingEngine
    return AsyncServingEngine(system.engine, max_batch=mix["max_batch"],
                              max_wait_ms=mix["max_wait_ms"],
                              executor=executor)


def make_request(system, spec, prefs_cache):
    """The program's ``Request`` for a traffic spec; one preferences
    object per distinct weight set."""
    from repro.core.preferences import UserPreferences
    from repro.serving.engine import Request
    key = tuple(sorted(spec.weights.items()))
    prefs = prefs_cache.get(key)
    if prefs is None:
        prefs = prefs_cache[key] = UserPreferences(weights=spec.weights)
    return Request(text=spec.text, prefs=prefs, id=spec.id,
                   max_new=spec.max_new, tenant=spec.tenant)


async def _open(system, traffic, seconds, executor, hooks) -> Run:
    loop = asyncio.get_running_loop()
    prefs: dict = {}
    reqs = [make_request(system, s, prefs) for s in traffic.specs]
    recs = [Record(spec=s, due=0.0) for s in traffic.specs]
    late: List[float] = []
    aeng = _aengine(system, traffic.mix, executor)

    async def one(i, due):
        try:
            recs[i].answer = await aeng.submit(reqs[i])
        except Exception as e:                     # noqa: BLE001
            recs[i].error = f"{type(e).__name__}: {e}"
        recs[i].done = time.perf_counter()

    await aeng.start()
    timed_out = False
    try:
        t0 = time.perf_counter() + 0.05
        hooks.start()
        tasks = []
        for i, at in enumerate(traffic.arrivals):
            due = t0 + float(at)
            wait = due - time.perf_counter()
            if wait > 0:
                await asyncio.sleep(wait)
            late.append(time.perf_counter() - due)
            recs[i].due = due
            tasks.append(loop.create_task(one(i, due)))
        t1 = t0 + seconds
        await asyncio.sleep(max(t1 - time.perf_counter(), 0.0))
        hooks.end()
        if tasks:
            _, pending = await asyncio.wait(tasks, timeout=max(
                t1 + GRACE_S - time.perf_counter(), 1.0))
            timed_out = bool(pending)
            for t in pending:
                t.cancel()
    finally:
        await aeng.stop(drain=not timed_out)
    return Run(recs, t0, t1, late)


async def _clients(system, traffic, seconds, executor, hooks) -> Run:
    prefs: dict = {}
    pool = traffic.specs
    n_clients = traffic.mix["clients"]
    recs: List[Record] = []
    aeng = _aengine(system, traffic.mix, executor)
    await aeng.start()
    timed_out = False
    try:
        t0 = time.perf_counter()
        t1 = t0 + seconds
        hooks.start()

        async def client(c):
            j = 0
            while True:
                now = time.perf_counter()
                if now >= t1:
                    return
                spec = pool[(c + n_clients * j) % len(pool)]
                j += 1
                rec = Record(spec=spec, due=now)
                recs.append(rec)
                try:
                    rec.answer = await aeng.submit(
                        make_request(system, spec, prefs))
                except Exception as e:             # noqa: BLE001
                    rec.error = f"{type(e).__name__}: {e}"
                rec.done = time.perf_counter()

        tasks = [asyncio.get_running_loop().create_task(client(c))
                 for c in range(n_clients)]
        await asyncio.sleep(seconds)
        hooks.end()
        _, pending = await asyncio.wait(tasks, timeout=GRACE_S)
        timed_out = bool(pending)
        for t in pending:
            t.cancel()
    finally:
        await aeng.stop(drain=not timed_out)
    return Run(recs, t0, t1, [])


def _batches(system, traffic, seconds, hooks) -> Run:
    from repro.core.preferences import UserPreferences
    mix = traffic.mix
    B = mix["batch"]
    pool = traffic.specs
    prefs: dict = {}

    def pref(s):
        key = tuple(sorted(s.weights.items()))
        if key not in prefs:
            prefs[key] = UserPreferences(weights=s.weights)
        return prefs[key]

    batches = [pool[i:i + B] for i in range(0, len(pool) - B + 1, B)]
    inputs = [([s.text for s in b], [pref(s) for s in b]) for b in batches]
    recs: List[Record] = []
    route_all = system.router.route_all
    t0 = time.perf_counter()
    t1 = t0 + seconds
    hooks.start()
    k = 0
    while time.perf_counter() < t1:
        texts, ps = inputs[k % len(inputs)]
        sent = time.perf_counter()
        out = route_all(texts, ps)
        done = time.perf_counter()
        recs.extend(Record(spec=s, due=sent, done=done, answer=rq)
                    for s, rq in zip(batches[k % len(batches)], out))
        k += 1
    hooks.end()
    return Run(recs, t0, t1, [])


class Hooks:
    """Called on the driving thread when the window opens and closes."""

    def start(self) -> None:
        pass

    def end(self) -> None:
        pass


def run(system, traffic, seconds: float, hooks: Hooks = Hooks()) -> Run:
    """Drive ``traffic`` for ``seconds`` and return every record."""
    loop = traffic.mix["loop"]
    if loop == "batches":
        return _batches(system, traffic, seconds, hooks)
    # one worker thread serves the windows, as the engine expects
    with concurrent.futures.ThreadPoolExecutor(max_workers=1) as ex:
        coro = (_open if loop == "open" else _clients)(
            system, traffic, seconds, ex, hooks)
        return asyncio.run(coro)
