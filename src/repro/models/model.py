"""Unified model: one composable implementation covering all six
architecture families (dense / moe / ssm / hybrid / encdec / vlm / audio).

Design:
  * params are nested dicts; per-layer params are STACKED along a
    leading ``n_layers`` axis and the stack runs under ``lax.scan``.
  * three entry points, all pure functions of (params, batch):
      - ``forward_full``  : full-sequence logits (training / prefill)
      - ``prefill``       : forward_full + build the decode cache
      - ``decode_step``   : one token against the cache
  * gemma2's local/global alternation is a scanned ``layer_kind`` array;
    local layers mask to the sliding window inside a uniform cache.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L

Params = Dict[str, Any]


# ----------------------------------------------------------------------
# activation sharding constraints
# ----------------------------------------------------------------------

def _act_constraint(x, *, vocab_axis: bool = False):
    """Pin activations to (batch over data axes, ..., vocab over model).

    Without explicit constraints GSPMD propagates the FSDP weight
    layouts into activations — at the LM head it gathered the FULL
    batch of f32 logits (67 GB/device for 256k vocabs; EXPERIMENTS
    §Perf, gemma2 hillclimb).  No-op outside a mesh context (plain
    jit in unit tests) and on non-divisible axes.
    """
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or "data" not in mesh.axis_names:
        return x
    da = ("pod", "data") if "pod" in mesh.axis_names else ("data",)
    da_size = 1
    for a in da:
        da_size *= mesh.shape[a]
    b_ax = da if x.shape[0] % da_size == 0 else None
    spec = [b_ax] + [None] * (x.ndim - 1)
    if vocab_axis and x.shape[-1] % mesh.shape["model"] == 0:
        spec[-1] = "model"
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.PartitionSpec(*spec))


# ======================================================================
# init
# ======================================================================

def _init_decoder_layer(key, cfg: ModelConfig) -> Params:
    ks = jax.random.split(key, 4)
    dt = jnp.dtype(cfg.param_dtype)
    p: Params = {"ln_mix": jnp.zeros((cfg.d_model,), dt),
                 "ln_mlp": jnp.zeros((cfg.d_model,), dt)}
    if cfg.has_attention:
        p["attn"] = L.init_attention(ks[0], cfg)
    if cfg.has_ssm:
        p["ssm"] = L.init_ssm(ks[1], cfg)
    if cfg.arch_type == "hybrid":
        p["ln_attn_out"] = jnp.zeros((cfg.d_model,), dt)
        p["ln_ssm_out"] = jnp.zeros((cfg.d_model,), dt)
    if cfg.is_moe:
        p["moe"] = L.init_moe(ks[2], cfg)
    elif cfg.d_ff > 0:
        p["mlp"] = L.init_mlp(ks[2], cfg)
    if cfg.is_encdec:
        p["cross"] = L.init_attention(ks[3], cfg, cross=True)
        p["ln_cross"] = jnp.zeros((cfg.d_model,), dt)
    return p


def _init_encoder_layer(key, cfg: ModelConfig) -> Params:
    ks = jax.random.split(key, 2)
    dt = jnp.dtype(cfg.param_dtype)
    return {
        "attn": L.init_attention(ks[0], cfg),
        "mlp": L.init_mlp(ks[1], cfg),
        "ln_mix": jnp.zeros((cfg.d_model,), dt),
        "ln_mlp": jnp.zeros((cfg.d_model,), dt),
    }


def layer_kinds(cfg: ModelConfig) -> jnp.ndarray:
    """Per-layer attention kind: 0 = local/SWA, 1 = global/full."""
    if cfg.local_global_pattern:
        return (jnp.arange(cfg.n_layers) % 2).astype(jnp.int32)
    if cfg.sliding_window > 0:
        return jnp.zeros((cfg.n_layers,), jnp.int32)
    return jnp.ones((cfg.n_layers,), jnp.int32)


def init_params(key, cfg: ModelConfig) -> Params:
    dt = jnp.dtype(cfg.param_dtype)
    k_embed, k_layers, k_enc, k_front = jax.random.split(key, 4)
    params: Params = {
        "embed": L.dense_init(k_embed, (cfg.vocab_padded, cfg.d_model), dt, scale=0.02),
        "ln_f": jnp.zeros((cfg.d_model,), dt),
        "layers": jax.vmap(lambda k: _init_decoder_layer(k, cfg))(
            jax.random.split(k_layers, cfg.n_layers)),
    }
    if cfg.is_encdec:
        params["enc_layers"] = jax.vmap(lambda k: _init_encoder_layer(k, cfg))(
            jax.random.split(k_enc, cfg.n_enc_layers))
        params["ln_enc"] = jnp.zeros((cfg.d_model,), dt)
    if cfg.frontend:
        params["front_proj"] = {
            "w": L.dense_init(k_front, (cfg.frontend_dim, cfg.d_model), dt),
            "b": jnp.zeros((cfg.d_model,), dt),
        }
    return params


# ======================================================================
# full-sequence forward (train / prefill)
# ======================================================================

def _mix_full(p, cfg: ModelConfig, x, positions, kind, long_mode: bool):
    """Sequence mixer (attention and/or SSM) over a full sequence.

    Returns (out, kv, ssd) — kv is (k, v) for cacheable attention,
    ssd is (final_state, conv_state) for SSM mixers; either may be None.
    """
    h = L.rms_norm(x, p["ln_mix"])
    kv = None
    ssd = None
    attn_out = None
    if cfg.has_attention:
        if cfg.attn_impl == "blocked":
            attn_out, kv = L.attention_blocked(p["attn"], cfg, h, positions,
                                               kind=kind, long_mode=long_mode)
        else:                                    # "naive" — paper baseline
            Lq = h.shape[1]
            iq = jnp.arange(Lq)[:, None]
            ik = jnp.arange(Lq)[None, :]
            causal = ik <= iq
            W = cfg.sliding_window
            if W and (not cfg.local_global_pattern or long_mode):
                mask = causal & (ik > iq - W)
            elif cfg.local_global_pattern:
                local = causal & (ik > iq - W)
                mask = jnp.where(kind == 0, local, causal)
            else:
                mask = causal
            attn_out, kv = _attention_full_masked(p["attn"], cfg, h,
                                                  positions, mask)
    if cfg.has_ssm:
        ssm_out, h_final, conv_state = L.ssd_chunked(p["ssm"], cfg, h)
        ssd = (h_final, conv_state)
        if attn_out is None:
            return ssm_out, kv, ssd
        # hybrid: per-branch output norm, then mean (Hymba-style fusion)
        fused = 0.5 * (L.rms_norm(attn_out, p["ln_attn_out"])
                       + L.rms_norm(ssm_out, p["ln_ssm_out"]))
        return fused, kv, ssd
    return attn_out, kv, ssd


def _attention_full_masked(p, cfg, h, positions, mask):
    """attention_full with an explicit (Lq, Lk) bool mask."""
    q = L._split_heads(h @ p["wq"] + p.get("bq", 0), cfg.n_heads, cfg.head_dim)
    k = L._split_heads(h @ p["wk"] + p.get("bk", 0), cfg.n_kv_heads, cfg.head_dim)
    v = L._split_heads(h @ p["wv"] + p.get("bv", 0), cfg.n_kv_heads, cfg.head_dim)
    q = L.apply_rope(q, positions, cfg.rope_theta)
    k = L.apply_rope(k, positions, cfg.rope_theta)
    scores = L.gqa_scores(q, k).astype(jnp.float32)
    scores = L.softcap(scores, cfg.attn_softcap)
    scores = jnp.where(mask[None, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(h.dtype)
    out = L.gqa_values(probs, v)
    out = out.reshape(out.shape[:2] + (cfg.q_dim,)) @ p["wo"]
    return out, (k, v)


def _ffn(p, cfg: ModelConfig, x):
    """Feed-forward half of the block. Returns (y, aux)."""
    h = L.rms_norm(x, p["ln_mlp"])
    if cfg.is_moe:
        y, aux = L.moe_block(p["moe"], cfg, h)
    elif cfg.d_ff > 0:
        y, aux = L.mlp(p["mlp"], h), 0.0
    else:
        return x, 0.0
    return x + y, aux


def _decoder_layer_full(p, cfg, x, positions, kind, enc_out, long_mode):
    mix, kv, ssd = _mix_full(p, cfg, x, positions, kind, long_mode)
    x = x + mix
    if cfg.is_encdec and enc_out is not None:
        h = L.rms_norm(x, p["ln_cross"])
        cross, cross_kv = L.attention_full(p["cross"], cfg, h, positions,
                                           kv_x=enc_out, causal=False, rope=False)
        x = x + cross
    else:
        cross_kv = None
    x, aux = _ffn(p, cfg, x)
    return x, kv, cross_kv, ssd, aux


def encode(params, cfg: ModelConfig, src_embeds):
    """Encoder stack over (projected) frontend embeddings."""
    x = _project_frontend(params, cfg, src_embeds)
    pos = jnp.broadcast_to(jnp.arange(x.shape[1], dtype=jnp.int32)[None], x.shape[:2])

    def body(x, p):
        h = L.rms_norm(x, p["ln_mix"])
        if cfg.attn_impl == "blocked":
            out, _ = L.attention_blocked(p["attn"], cfg, h, pos, causal=False)
        else:
            out, _ = L.attention_full(p["attn"], cfg, h, pos, causal=False)
        x = x + out
        x = x + L.mlp(p["mlp"], L.rms_norm(x, p["ln_mlp"]))
        x = _act_constraint(x)
        return x, None

    x, _ = jax.lax.scan(body, x, params["enc_layers"])
    return L.rms_norm(x, params["ln_enc"])


def _project_frontend(params, cfg, embeds):
    fp = params["front_proj"]
    return (embeds.astype(jnp.dtype(cfg.compute_dtype)) @ fp["w"] + fp["b"])


def cast_params(params: Params, cfg: ModelConfig) -> Params:
    """Cast float params to the compute dtype (master weights stay f32)."""
    cdt = jnp.dtype(cfg.compute_dtype)
    return jax.tree_util.tree_map(
        lambda p: p.astype(cdt) if jnp.issubdtype(p.dtype, jnp.floating) else p,
        params)


def _embed_inputs(params, cfg: ModelConfig, batch):
    """Token (+ frontend) embedding. Returns (x, positions)."""
    cdt = jnp.dtype(cfg.compute_dtype)
    tok = params["embed"][batch["tokens"]].astype(cdt)
    if cfg.frontend and not cfg.is_encdec:
        front = _project_frontend(params, cfg, batch["frontend"]).astype(cdt)
        x = jnp.concatenate([front, tok], axis=1)
    else:
        x = tok
    B, Ltot = x.shape[0], x.shape[1]
    positions = jnp.broadcast_to(jnp.arange(Ltot, dtype=jnp.int32)[None], (B, Ltot))
    return x, positions


def forward_full(params, cfg: ModelConfig, batch, *, long_mode: bool = False,
                 collect_cache: bool = False):
    """Full-sequence forward.

    batch: {"tokens": (B, L)} plus "frontend"/"src_embeds" as the family
    requires. Returns (logits, aux_loss, cache_parts_or_None).
    """
    params = cast_params(params, cfg)
    x, positions = _embed_inputs(params, cfg, batch)
    enc_out = encode(params, cfg, batch["src_embeds"]) if cfg.is_encdec else None
    kinds = layer_kinds(cfg)

    def body(carry, per):
        x, aux = carry
        p, kind = per
        x, kv, cross_kv, ssd, aux_i = _decoder_layer_full(p, cfg, x, positions,
                                                          kind, enc_out, long_mode)
        x = _act_constraint(x)
        ys = (kv, cross_kv, ssd) if collect_cache else (None, None, None)
        return (x, aux + aux_i), ys

    body_fn = jax.checkpoint(body) if (cfg.remat and not collect_cache) else body
    (x, aux), caches = jax.lax.scan(body_fn, (x, jnp.float32(0.0)),
                                    (params["layers"], kinds))
    x = L.rms_norm(x, params["ln_f"])
    logits = x @ params["embed"].T.astype(x.dtype)
    logits = _act_constraint(logits, vocab_axis=True)
    logits = L.softcap(logits.astype(jnp.float32), cfg.final_softcap)
    return logits, aux, caches


# ======================================================================
# decode cache
# ======================================================================

def cache_len(cfg: ModelConfig, ctx_len: int, long_mode: bool = False) -> int:
    if not cfg.has_attention:
        return 0
    if cfg.sliding_window and (not cfg.local_global_pattern or long_mode):
        return min(cfg.sliding_window, ctx_len)
    return ctx_len


def init_cache(cfg: ModelConfig, batch_size: int, ctx_len: int, *,
               long_mode: bool = False, enc_len: int = 0,
               dtype: Optional[str] = None) -> Params:
    """Zero-initialized decode cache pytree (leading axis = n_layers)."""
    dt = jnp.dtype(dtype or cfg.compute_dtype)
    nL, B = cfg.n_layers, batch_size
    cache: Params = {}
    C = cache_len(cfg, ctx_len, long_mode)
    int8 = cfg.kv_cache_dtype == "int8"
    if C:
        kv_dt = jnp.int8 if int8 else dt
        cache["k"] = jnp.zeros((nL, B, C, cfg.n_kv_heads, cfg.head_dim), kv_dt)
        cache["v"] = jnp.zeros((nL, B, C, cfg.n_kv_heads, cfg.head_dim), kv_dt)
        if int8:
            cache["k_scale"] = jnp.zeros((nL, B, C, cfg.n_kv_heads, 1),
                                         jnp.float32)
            cache["v_scale"] = jnp.zeros((nL, B, C, cfg.n_kv_heads, 1),
                                         jnp.float32)
    if cfg.has_ssm:
        cache["ssd"] = jnp.zeros((nL, B, cfg.ssm_heads, cfg.ssm_head_dim,
                                  cfg.ssm_state), jnp.float32)
        conv_dim = cfg.d_inner + 2 * cfg.ssm_state
        cache["conv"] = jnp.zeros((nL, B, cfg.ssm_conv_width - 1, conv_dim), dt)
    if cfg.is_encdec:
        cache["cross_k"] = jnp.zeros((nL, B, enc_len, cfg.n_kv_heads, cfg.head_dim), dt)
        cache["cross_v"] = jnp.zeros((nL, B, enc_len, cfg.n_kv_heads, cfg.head_dim), dt)
    return cache


def _mix_decode(p, cfg: ModelConfig, x, cache_slice, positions, kind, long_mode):
    """One-token mixer against this layer's cache slice."""
    h = L.rms_norm(x, p["ln_mix"])
    new_slice = dict(cache_slice)
    attn_out = None
    if cfg.has_attention and "k" in cache_slice:
        C = cache_slice["k"].shape[1]
        ring = bool(cfg.sliding_window) and (not cfg.local_global_pattern or long_mode)
        int8 = "k_scale" in cache_slice
        scales = ({"k_scale": cache_slice["k_scale"],
                   "v_scale": cache_slice["v_scale"]} if int8 else {})
        res = L.attention_decode(
            p["attn"], cfg, h, cache_slice["k"], cache_slice["v"], positions,
            window=C if ring else 0, attn_softcap=cfg.attn_softcap, **scales)
        if int8:
            out, k_new, v_new, ks_new, vs_new = res
            new_slice["k_scale"], new_slice["v_scale"] = ks_new, vs_new
        else:
            out, k_new, v_new = res
        if cfg.local_global_pattern and not long_mode and cfg.sliding_window:
            # local layers additionally mask to the window inside the full cache
            scales2 = ({"k_scale": new_slice["k_scale"],
                        "v_scale": new_slice["v_scale"]} if int8 else {})
            out_local = L.attention_decode(
                p["attn"], cfg, h, k_new, v_new, positions,
                window=0, attn_softcap=cfg.attn_softcap, update_cache=False,
                local_window=cfg.sliding_window, **scales2)[0]
            out = jnp.where(kind == 0, out_local, out)
        new_slice["k"], new_slice["v"] = k_new, v_new
        attn_out = out
    if cfg.has_ssm:
        ssm_out, h_new, conv_new = L.ssd_step(p["ssm"], cfg, h,
                                              cache_slice["ssd"], cache_slice["conv"])
        new_slice["ssd"], new_slice["conv"] = h_new, conv_new
        if attn_out is None:
            return ssm_out, new_slice
        fused = 0.5 * (L.rms_norm(attn_out, p["ln_attn_out"])
                       + L.rms_norm(ssm_out, p["ln_ssm_out"]))
        return fused, new_slice
    return attn_out, new_slice


def decode_step(params, cfg: ModelConfig, cache: Params, batch, *,
                long_mode: bool = False):
    """One decode step.

    batch: {"token": (B, 1) int32, "pos": (B,) int32}.
    Returns (logits (B, vocab_padded), new_cache).
    """
    params = cast_params(params, cfg)
    cdt = jnp.dtype(cfg.compute_dtype)
    x = params["embed"][batch["token"]].astype(cdt)
    positions = batch["pos"]
    kinds = layer_kinds(cfg)

    def body(x, per):
        p, kind, cache_slice = per
        mix, new_slice = _mix_decode(p, cfg, x, cache_slice, positions, kind, long_mode)
        x = x + mix
        if cfg.is_encdec:
            h = L.rms_norm(x, p["ln_cross"])
            out, _, _ = L.attention_decode(
                p["cross"], cfg, h, cache_slice["cross_k"], cache_slice["cross_v"],
                positions, rope=False, update_cache=False, full_valid=True)
            x = x + out
        x, _ = _ffn(p, cfg, x)
        return x, new_slice

    x, new_cache = jax.lax.scan(body, x, (params["layers"], kinds, cache))
    for key in ("cross_k", "cross_v"):
        if key in cache:
            new_cache[key] = cache[key]
    x = L.rms_norm(x, params["ln_f"])
    logits = x[:, 0] @ params["embed"].T.astype(x.dtype)
    logits = _act_constraint(logits, vocab_axis=True)
    logits = L.softcap(logits.astype(jnp.float32), cfg.final_softcap)
    return logits, new_cache


def prefill(params, cfg: ModelConfig, batch, *, long_mode: bool = False,
            max_len: int = 0):
    """Full prefill.

    max_len: decode-cache capacity (>= prefill length); defaults to
    prefill length + 64 headroom for generated tokens.
    Returns (last_logits (B, V), cache, new_pos (B,)).
    """
    logits, _, caches = forward_full(params, cfg, batch, long_mode=long_mode,
                                     collect_cache=True)
    kv, cross_kv, ssd = caches
    ctx = batch["tokens"].shape[1]
    if cfg.frontend and not cfg.is_encdec:
        ctx += cfg.frontend_tokens if "frontend" not in batch else batch["frontend"].shape[1]
    B = batch["tokens"].shape[0]
    # explicit max_len is the cache capacity (must cover the prompt);
    # default: prompt + 64 decode headroom
    cap = max(max_len, ctx) if max_len else ctx + 64
    cache = init_cache(cfg, B, cap, long_mode=long_mode,
                       enc_len=(batch["src_embeds"].shape[1] if cfg.is_encdec else 0))
    if kv is not None and "k" in cache:
        k_all, v_all = kv       # (nL, B, Lctx, Hkv, hd)
        C = cache["k"].shape[2]
        Lctx = k_all.shape[2]
        int8 = "k_scale" in cache
        if int8:
            k_all, k_sc = L.quantize_kv(k_all)
            v_all, v_sc = L.quantize_kv(v_all)
        if C >= Lctx:
            cache["k"] = cache["k"].at[:, :, :Lctx].set(k_all.astype(cache["k"].dtype))
            cache["v"] = cache["v"].at[:, :, :Lctx].set(v_all.astype(cache["v"].dtype))
            if int8:
                cache["k_scale"] = cache["k_scale"].at[:, :, :Lctx].set(k_sc)
                cache["v_scale"] = cache["v_scale"].at[:, :, :Lctx].set(v_sc)
        else:  # ring buffer: slot = pos % C
            shift = Lctx % C
            roll = lambda a: jnp.roll(a[:, :, -C:], shift, axis=2)
            cache["k"] = roll(k_all).astype(cache["k"].dtype)
            cache["v"] = roll(v_all).astype(cache["v"].dtype)
            if int8:
                cache["k_scale"] = roll(k_sc)
                cache["v_scale"] = roll(v_sc)
    if cross_kv is not None and cross_kv[0] is not None and cfg.is_encdec:
        cache["cross_k"] = cross_kv[0].astype(cache["cross_k"].dtype)
        cache["cross_v"] = cross_kv[1].astype(cache["cross_v"].dtype)
    if ssd is not None and ssd[0] is not None and cfg.has_ssm:
        cache["ssd"] = ssd[0]                               # (nL, B, H, P, N) f32
        cache["conv"] = ssd[1].astype(cache["conv"].dtype)
    last = logits[:, -1]
    new_pos = jnp.full((B,), logits.shape[1], jnp.int32)
    return last, cache, new_pos


# ======================================================================
# losses / steps
# ======================================================================

def lm_loss(logits, labels):
    """Cross-entropy with -1 = ignore. logits (B, L, V) f32, labels (B, L).

    The gold logit is picked with a one-hot CONTRACTION rather than
    take_along_axis: a gather along a vocab axis that is sharded over
    'model' forces GSPMD to re-shard the full (B, L, V) logits (a
    ~67 GB/device all-gather+all-reduce for 256k vocabs — EXPERIMENTS
    §Perf, gemma2 hillclimb); the contraction reduces locally and psums
    only (B, L) scalars.
    """
    V = logits.shape[-1]
    mask = labels >= 0
    labels_safe = jnp.where(mask, labels, 0)
    logz = jax.nn.logsumexp(logits, axis=-1)
    onehot = jax.nn.one_hot(labels_safe, V, dtype=logits.dtype)
    gold = jnp.einsum("blv,blv->bl", logits, onehot)
    nll = (logz - gold) * mask
    return jnp.sum(nll) / jnp.maximum(jnp.sum(mask), 1)


def loss_fn(params, cfg: ModelConfig, batch):
    logits, aux, _ = forward_full(params, cfg, batch)
    labels = batch["labels"]
    if logits.shape[1] != labels.shape[1]:  # frontend tokens prepended
        pad = logits.shape[1] - labels.shape[1]
        labels = jnp.concatenate(
            [jnp.full((labels.shape[0], pad), -1, labels.dtype), labels], axis=1)
    loss = lm_loss(logits, labels)
    return loss + 0.01 * aux, (loss, aux)
