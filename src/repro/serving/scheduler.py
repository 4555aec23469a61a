"""Continuous-batching scheduler (vLLM-style, simplified to fixed slots).

One scheduler per routed model: a fixed number of decode SLOTS share a
persistent KV/SSD cache.  Arriving requests are prefilled one at a time
into a free slot (their prefix cache is written into the slot), and all
active slots decode together on every tick — so short requests retire
and hand their slot to queued work without ever stalling long ones.
This is the serving substrate underneath the OptiRoute engine when
request rates exceed what one-shot batching handles.

The decode executable is compiled ONCE for the (slots, cache) shape;
admission and retirement are pure cache-slot updates.
"""
from __future__ import annotations

import collections
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Deque, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import model as M
from repro.training.steps import make_decode_step
from repro.analysis.sanitize import make_lock


@dataclass
class SlotRequest:
    id: int
    tokens: np.ndarray               # (L,) prompt
    max_new: int
    out: List[int] = field(default_factory=list)
    slot: int = -1
    started_s: float = 0.0           # perf_counter at slot admission

    @property
    def done(self) -> bool:
        return len(self.out) >= self.max_new


class ContinuousBatcher:
    """``load``/``model_idx`` optionally mirror this batcher's queue
    depth, slot occupancy and realized per-request service time into a
    ``repro.serving.load.LoadTracker`` arm, so the router's load-aware
    scoring sees this model's live state."""

    def __init__(self, cfg: ModelConfig, params, *, slots: int = 4,
                 ctx_len: int = 256, load=None, model_idx: int = 0):
        self.cfg = cfg
        self.params = params
        self.slots = slots
        self.ctx_len = ctx_len
        self.cache = M.init_cache(cfg, slots, ctx_len)
        self.pos = np.zeros(slots, np.int32)
        self.active: List[Optional[SlotRequest]] = [None] * slots
        self.queue: Deque[SlotRequest] = collections.deque()
        self.finished: List[SlotRequest] = []
        self._decode = jax.jit(make_decode_step(cfg))
        self._next_tok = np.zeros(slots, np.int32)
        self.ticks = 0
        self.load = load
        self.model_idx = model_idx
        self.cancelled: List[SlotRequest] = []
        # guards queue/active membership so submit() from request
        # threads, queue_depth() from the router's scoring path and the
        # tick driver all see one consistent outstanding-work count
        self._lock = make_lock("serving.scheduler")
        if load is not None:
            load.ensure(model_idx + 1)
            load.set_capacity(model_idx, float(slots))

    # ------------------------------------------------------------------
    def submit(self, req: SlotRequest, *, truncate: bool = False) -> None:
        """Queue a request.  The prompt plus every decode step whose
        output is kept must fit the slot cache: positions beyond
        ``ctx_len`` are written with jax's out-of-bounds ``.at[].set``,
        which drops the KV SILENTLY and corrupts later tokens.  The
        last kept token decodes at ``len(tokens) + max_new - 2``, so
        prompts longer than ``ctx_len - max(max_new - 1, 1)`` are
        rejected, or clipped to that limit with ``truncate=True``.
        """
        limit = self.ctx_len - max(req.max_new - 1, 1)
        if len(req.tokens) > limit:
            if not truncate:
                raise ValueError(
                    f"prompt of {len(req.tokens)} tokens with max_new="
                    f"{req.max_new} overflows the ctx_len={self.ctx_len} "
                    f"slot cache (limit {limit}; pass truncate=True to "
                    f"clip)")
            req.tokens = req.tokens[:limit]
        with self._lock:
            self.queue.append(req)
        if self.load is not None:
            self.load.admit(self.model_idx)

    def queue_depth(self) -> int:
        """Queued + active requests (the batcher's outstanding work).
        Taken under the batcher lock so a request mid-transition from
        queue to slot is counted exactly once, never zero or twice."""
        with self._lock:
            return (len(self.queue)
                    + sum(r is not None for r in self.active))

    def _free_slots(self) -> List[int]:
        return [i for i, r in enumerate(self.active) if r is None]

    def _admit(self) -> None:
        """Prefill queued requests into free slots (slot-cache insert)."""
        for i in self._free_slots():
            with self._lock:
                if not self.queue:
                    return
                req = self.queue.popleft()
                # the slot claim happens in the SAME critical section
                # as the dequeue: queue_depth never sees the request in
                # neither place
                self.active[i] = req
            toks = jnp.asarray(req.tokens[None], jnp.int32)
            last, cache1, pos1 = M.prefill(self.params, self.cfg,
                                           {"tokens": toks},
                                           max_len=self.ctx_len)
            # write the single-sequence cache into slot i
            def insert(slot_cache, one):
                return slot_cache.at[:, i].set(one[:, 0])
            self.cache = jax.tree_util.tree_map(insert, self.cache, cache1)
            self.pos[i] = int(pos1[0])
            self._next_tok[i] = int(jnp.argmax(last[0, :self.cfg.vocab_size]))
            req.slot = i
            req.started_s = time.perf_counter()
            if self.load is not None:
                self.load.start(self.model_idx)

    def _retire(self) -> None:
        for i, req in enumerate(self.active):
            if req is not None and req.done:
                with self._lock:
                    self.finished.append(req)
                    self.active[i] = None
                if self.load is not None:
                    self.load.finish(
                        self.model_idx,
                        time.perf_counter() - req.started_s)

    # ------------------------------------------------------------------
    def tick(self) -> int:
        """One scheduler step: admit -> joint decode -> collect -> retire.
        Returns the number of active slots that decoded."""
        self._admit()
        live = [i for i, r in enumerate(self.active) if r is not None]
        if not live:
            return 0
        batch = {"token": jnp.asarray(self._next_tok[:, None], jnp.int32),
                 "pos": jnp.asarray(self.pos, jnp.int32)}
        logits, nxt, self.cache = self._decode(self.params, self.cache,
                                               batch)
        nxt = np.asarray(nxt)[:, 0]
        for i in live:
            self.active[i].out.append(int(self._next_tok[i]))
            self._next_tok[i] = nxt[i]
            self.pos[i] += 1
        self._retire()
        self.ticks += 1
        return len(live)

    def cancel(self) -> List[SlotRequest]:
        """Abandon all outstanding work and ROLL BACK the mirrored
        tracker arm: queued requests decrement the queue counter,
        active ones the inflight counter — with no EWMA sample (no
        service completed).  Without this, a scheduler that gives up
        (``max_ticks``, shutdown) leaves the arm's counters inflated
        forever and the router keeps penalizing a model that is
        actually idle.  Returns the dropped requests (also appended to
        ``self.cancelled``).  Not safe concurrently with ``tick``:
        call it from the tick driver."""
        with self._lock:
            queued = list(self.queue)
            self.queue.clear()
            active = [r for r in self.active if r is not None]
            for i in range(self.slots):
                self.active[i] = None
        if self.load is not None and (queued or active):
            self.load.cancel(self.model_idx, queued=len(queued),
                             inflight=len(active))
        for r in active:
            r.slot = -1
        dropped = queued + active
        self.cancelled.extend(dropped)
        return dropped

    def run_until_drained(self, max_ticks: int = 10_000, *,
                          cancel_leftover: bool = True
                          ) -> List[SlotRequest]:
        """Tick until no work remains or ``max_ticks`` is reached.  On
        a ``max_ticks`` exit the leftover queue/slots are cancelled by
        default so the mirrored tracker arm nets back to zero instead
        of staying inflated forever; pass ``cancel_leftover=False`` to
        keep the backlog (and its tracker counters) for a later drain.
        """
        # lint: ignore[lock-unlocked-read] -- run_until_drained is the
        # single tick-driver thread; submitters only ever grow `queue`,
        # so a stale read here costs one extra loop iteration, not a
        # torn decision (tick() re-checks everything under the lock)
        while (self.queue or any(r is not None for r in self.active)) \
                and self.ticks < max_ticks:
            self.tick()
        if cancel_leftover and (
                self.queue or any(r is not None for r in self.active)):
            self.cancel()
        return self.finished
