"""Step builders: train_step / prefill_step / decode (serve) step.

These are the functions the launcher jits; the dry-run lowers them with
ShapeDtypeStruct inputs against the production mesh.
"""
from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import model as M
from repro.training.optimizer import AdamWConfig, adamw_update


def make_train_step(cfg: ModelConfig, opt_cfg: AdamWConfig = AdamWConfig()):
    def train_step(params, opt_state, batch):
        (total, (lm, aux)), grads = jax.value_and_grad(
            M.loss_fn, has_aux=True)(params, cfg, batch)
        params, opt_state, gnorm = adamw_update(grads, opt_state, params, opt_cfg)
        metrics = {"loss": lm, "aux": aux, "total": total, "gnorm": gnorm}
        return params, opt_state, metrics
    return train_step


def make_prefill_step(cfg: ModelConfig, *, long_mode: bool = False,
                      max_len: int = 0):
    def prefill_step(params, batch):
        last, cache, pos = M.prefill(params, cfg, batch, long_mode=long_mode,
                                     max_len=max_len)
        return last, cache, pos
    return prefill_step


def make_decode_step(cfg: ModelConfig, *, long_mode: bool = False):
    def serve_step(params, cache, batch):
        logits, cache = M.decode_step(params, cfg, cache, batch,
                                      long_mode=long_mode)
        # greedy next token (serving engines may sample outside the
        # jit) over the real vocabulary: the padded tail of the
        # embedding table is not a token
        next_tok = jnp.argmax(logits[:, :cfg.vocab_size],
                              axis=-1).astype(jnp.int32)[:, None]
        return logits, next_tok, cache
    return serve_step
