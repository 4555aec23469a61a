"""ModelRunner: a servable handle around (config, params).

This is what an MRES entry's ``runner`` points at.  It owns the jitted
prefill / decode-step executables and a KV/SSD cache per active batch,
exposes ``generate`` (greedy, batched), and accounts simulated
cost/latency from the architecture's analytic FLOPs so the routing
benchmarks can charge each request to the model that served it.

``merged_with`` produces the model-soup runner for the §5 fallback.
"""
from __future__ import annotations

import dataclasses
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import model as M
from repro.training.steps import make_decode_step, make_prefill_step

# TPU v5e hardware constants (roofline targets; DESIGN.md §Roofline)
PEAK_FLOPS = 197e12
HBM_BW = 819e9


@dataclass
class GenerationResult:
    tokens: np.ndarray               # (B, new) generated ids
    logits_last: np.ndarray          # (B, V) final-step logits
    prefill_tokens: int
    decode_steps: int
    sim_latency_s: float             # roofline-simulated
    wall_s: float


class ModelRunner:
    def __init__(self, cfg: ModelConfig, params=None, seed: int = 0):
        self.cfg = cfg
        if params is None:
            params = M.init_params(jax.random.PRNGKey(seed), cfg)
        self.params = params
        self._decode = jax.jit(make_decode_step(cfg))
        self._calls: List[Dict[str, float]] = []

    # ------------------------------------------------------------------
    @classmethod
    def from_checkpoint(cls, cfg: ModelConfig, path: str) -> "ModelRunner":
        """Cold-load a runner from an npz checkpoint (the MRES 'stores
        the models' contract — entries can point at checkpoint paths and
        materialize runners lazily)."""
        from repro.checkpoint import load
        params, meta = load(path)
        params = jax.tree_util.tree_map(jnp.asarray, params)
        runner = cls(cfg, params=params)
        runner.meta = meta
        return runner

    def save_checkpoint(self, path: str, metadata=None) -> None:
        from repro.checkpoint import save
        save(path, self.params, {"config": self.cfg.name,
                                 **(metadata or {})})

    # ------------------------------------------------------------------
    def _batch(self, tokens: np.ndarray) -> Dict[str, jnp.ndarray]:
        b: Dict[str, jnp.ndarray] = {"tokens": jnp.asarray(tokens, jnp.int32)}
        cfg = self.cfg
        B = tokens.shape[0]
        if cfg.is_encdec:
            b["src_embeds"] = jnp.zeros((B, 16, cfg.frontend_dim),
                                        jnp.dtype(cfg.compute_dtype))
        elif cfg.frontend:
            b["frontend"] = jnp.zeros((B, cfg.frontend_tokens, cfg.frontend_dim),
                                      jnp.dtype(cfg.compute_dtype))
        return b

    def sim_step_latency(self, batch: int, decode: bool = True) -> float:
        """Roofline latency of one step on a single v5e chip: max of the
        compute term and the weight-streaming memory term."""
        n_act = self.cfg.n_active_params()
        flops = 2.0 * n_act * batch
        mem = 2.0 * n_act  # bf16 weight bytes touched once per step
        return max(flops / PEAK_FLOPS, mem / HBM_BW)

    # ------------------------------------------------------------------
    def generate(self, tokens: np.ndarray, max_new: int = 16
                 ) -> GenerationResult:
        """Greedy generation. tokens (B, L) int32 (right-aligned, no pad)."""
        t0 = time.time()
        cfg = self.cfg
        B, Lp = tokens.shape
        batch = self._batch(tokens)
        last, cache, pos = M.prefill(self.params, cfg, batch,
                                     max_len=Lp + max_new + 8)
        tok = jnp.argmax(last[:, :cfg.vocab_size],
                         axis=-1).astype(jnp.int32)[:, None]
        out = [np.asarray(tok)]
        for _ in range(max_new - 1):
            logits, tok, cache = self._decode(
                self.params, cache, {"token": tok, "pos": pos})
            pos = pos + 1
            out.append(np.asarray(tok))
        sim = (self.sim_step_latency(B, decode=False) * Lp
               + self.sim_step_latency(B) * max_new)
        wall = time.time() - t0
        self._calls.append({"B": B, "prefill": Lp, "decode": max_new,
                            "sim_latency_s": sim, "wall_s": wall})
        return GenerationResult(
            tokens=np.concatenate(out, axis=1),
            logits_last=np.asarray(last),
            prefill_tokens=B * Lp, decode_steps=max_new,
            sim_latency_s=sim, wall_s=wall)

    # ------------------------------------------------------------------
    def merged_with(self, other: "ModelRunner", alpha: float) -> "ModelRunner":
        """Model-soup merge (paper §5): same-family weight average."""
        assert dataclasses.replace(self.cfg, name="") == \
            dataclasses.replace(other.cfg, name=""), "soup needs same family"
        from repro.core.merging import soup
        params = soup([self.params, other.params], [alpha, 1 - alpha])
        merged = ModelRunner.__new__(ModelRunner)
        merged.cfg = self.cfg
        merged.params = params
        merged._decode = self._decode           # same arch: reuse executable
        merged._calls = []
        return merged

    @property
    def stats(self) -> Dict[str, float]:
        if not self._calls:
            return {"calls": 0}
        return {
            "calls": len(self._calls),
            "sim_latency_s": float(sum(c["sim_latency_s"] for c in self._calls)),
            "wall_s": float(sum(c["wall_s"] for c in self._calls)),
            "decode_steps": int(sum(c["decode"] for c in self._calls)),
        }
