"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs ref.py oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops as K
from repro.kernels import ref as R

RNG = np.random.default_rng(42)


# ----------------------------------------------------------------------
# router_topk
# ----------------------------------------------------------------------

@pytest.mark.parametrize("N,D,Q,k", [
    (100, 8, 1, 4), (1000, 8, 5, 8), (513, 8, 3, 8),
    (2048, 16, 8, 16), (37, 8, 2, 4),
])
def test_router_topk_matches_ref(N, D, Q, k):
    emb = RNG.random((N, D)).astype(np.float32)
    q = RNG.random((Q, D)).astype(np.float32)
    v1, i1 = K.router_topk(emb, q, k)
    v2, i2 = R.router_topk(jnp.asarray(emb), jnp.asarray(q), k)
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v2),
                               rtol=1e-5, atol=1e-6)
    # idx may differ on exact ties; scores at the returned idx must match
    sims = np.asarray(R.router_topk(jnp.asarray(emb), jnp.asarray(q), N)[0])
    for qi in range(Q):
        got = np.asarray(v1[qi])
        np.testing.assert_allclose(np.sort(got)[::-1], got, rtol=0, atol=0)


@pytest.mark.parametrize("frac_masked", [0.0, 0.5, 0.95])
def test_router_topk_mask_and_weights(frac_masked):
    N, D, Q, k = 640, 8, 4, 8
    emb = RNG.random((N, D)).astype(np.float32)
    q = RNG.random((Q, D)).astype(np.float32)
    mask = RNG.random(N) >= frac_masked
    w = (RNG.random(D) + 0.05).astype(np.float32)
    v1, i1 = K.router_topk(emb, q, k, mask=mask, weights=w)
    v2, i2 = R.router_topk(jnp.asarray(emb), jnp.asarray(q), k,
                           mask=jnp.asarray(mask), weights=jnp.asarray(w))
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v2),
                               rtol=1e-5, atol=1e-6)
    # no masked row may appear among finite-valued results
    i1 = np.asarray(i1)
    finite = np.isfinite(np.asarray(v1))
    assert mask[i1[finite]].all()


@pytest.mark.parametrize("min_score", [-0.5, 0.1, 0.35, 2.0])
def test_router_topk_min_score_matches_ref(min_score):
    """The fused score floor (the semantic cache's similarity
    threshold) prunes identically on kernel and oracle."""
    N, D, Q, k = 384, 16, 6, 8
    emb = RNG.standard_normal((N, D)).astype(np.float32)
    q = RNG.standard_normal((Q, D)).astype(np.float32)
    mask = RNG.random((Q, N)) < 0.8
    bias = (RNG.random(N) * 0.1).astype(np.float32)
    v1, i1 = K.router_topk(emb, q, k, mask=mask, row_bias=bias,
                           min_score=min_score)
    v2, i2 = R.router_topk(jnp.asarray(emb), jnp.asarray(q), k,
                           mask=jnp.asarray(mask),
                           row_bias=jnp.asarray(bias),
                           min_score=min_score)
    v1 = np.asarray(v1)
    np.testing.assert_allclose(v1, np.asarray(v2), rtol=1e-5, atol=1e-6)
    finite = np.isfinite(v1)
    assert (v1[finite] >= min_score - 1e-6).all()
    # sub-threshold and masked rows surface exactly as -inf, and an
    # impossible floor empties the result entirely
    if min_score >= 2.0:
        assert not finite.any()
    # disabled floor == no floor
    v3, _ = K.router_topk(emb, q, k, mask=mask, row_bias=bias,
                          min_score=None)
    v4, _ = K.router_topk(emb, q, k, mask=mask, row_bias=bias)
    np.testing.assert_array_equal(np.asarray(v3), np.asarray(v4))


def test_router_topk_all_masked():
    N, D = 256, 8
    emb = RNG.random((N, D)).astype(np.float32)
    q = RNG.random((2, D)).astype(np.float32)
    v, i = K.router_topk(emb, q, 4, mask=np.zeros(N, bool))
    assert not np.isfinite(np.asarray(v)).any()


@pytest.mark.parametrize("N,D,Q,k", [
    (130, 8, 1, 4),     # B=1, N not a multiple of any block size
    (512, 8, 1, 8),     # B=1, block-aligned catalog
    (5, 8, 2, 8),       # k >= N: the tail must surface as -inf
    (3, 8, 1, 3),       # k == N == tiny
    (257, 16, 9, 16),   # off-by-one catalog, Q not a blk_q multiple
    (1000, 8, 5, 1000), # k == N, large
])
def test_router_topk_nonaligned_shapes(N, D, Q, k):
    """Regression sweep: shapes OFF the 128-lane/block happy path —
    padding, B=1, and k >= N must all match the oracle exactly."""
    emb = RNG.random((N, D)).astype(np.float32)
    q = RNG.random((Q, D)).astype(np.float32)
    mask = RNG.random(N) >= 0.3
    v1, i1 = K.router_topk(emb, q, k, mask=mask)
    v2, i2 = R.router_topk(jnp.asarray(emb), jnp.asarray(q), k,
                           mask=jnp.asarray(mask))
    v1, v2 = np.asarray(v1), np.asarray(v2)
    np.testing.assert_allclose(v1, v2, rtol=1e-5, atol=1e-6)
    # both backends surface exactly the same number of real candidates,
    # and finite entries never point at masked or padded rows
    fin = np.isfinite(v1)
    assert (fin == np.isfinite(v2)).all()
    i1 = np.asarray(i1)
    assert (i1[fin] < N).all() and mask[i1[fin]].all()


def test_router_topk_row_bias_matches_ref():
    """The fused per-row score bias (load-aware routing) vs. oracle,
    including its interaction with the filter mask: masked rows stay
    -inf no matter how large the bias."""
    N, D, Q, k = 300, 8, 5, 8
    emb = RNG.random((N, D)).astype(np.float32)
    q = RNG.random((Q, D)).astype(np.float32)
    mask = RNG.random(N) >= 0.4
    bias = (RNG.random(N) * -2.0).astype(np.float32)
    bias[~mask] = 100.0                  # must NOT resurrect masked rows
    v1, i1 = K.router_topk(emb, q, k, mask=mask, row_bias=bias)
    v2, i2 = R.router_topk(jnp.asarray(emb), jnp.asarray(q), k,
                           mask=jnp.asarray(mask),
                           row_bias=jnp.asarray(bias))
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v2),
                               rtol=1e-5, atol=1e-6)
    fin = np.isfinite(np.asarray(v1))
    assert mask[np.asarray(i1)[fin]].all()


@pytest.mark.parametrize("quant", [False, True])
@pytest.mark.parametrize("k", [4, 200])
def test_router_topk_ties_resolve_to_lowest_column(k, quant):
    """Exact ties across catalog blocks come back in ascending column
    order (``lax.top_k``'s contract on the whole catalog), k wider
    than a 128-lane block included, and -inf lanes carry index -1."""
    N, D, Q = 384, 8, 3
    base = RNG.random((6, D)).astype(np.float32)
    emb = base[np.arange(N) % 6]                 # 64 copies of 6 rows
    q = RNG.random((Q, D)).astype(np.float32)
    mask = np.arange(N) < N - 32                 # a masked tail
    v1, i1 = K.router_topk(emb, q, k, mask=mask, blk_n=128, quant=quant)
    v2, i2 = R.router_topk(jnp.asarray(emb), jnp.asarray(q), k,
                           mask=jnp.asarray(mask), quant=quant)
    v1, i1, i2 = np.asarray(v1), np.asarray(i1), np.asarray(i2)
    fin = np.isfinite(v1)
    np.testing.assert_array_equal(fin, np.isfinite(np.asarray(v2)))
    np.testing.assert_array_equal(i1[fin], i2[fin])
    assert (i1[~fin] == -1).all()


@pytest.mark.parametrize("Bu,Bs,N,D", [
    (1, 1, 1, 3),       # every axis at its minimum
    (7, 5, 130, 9),     # N just past one 128 block
    (32, 24, 150, 9),   # the adaptive benchmark's shape
    (3, 2, 257, 5),     # off-by-one catalog
])
def test_bandit_update_nonaligned_shapes(Bu, Bs, N, D):
    """Pallas bandit_update vs. oracle on non-lane-aligned shapes
    (B=1, N=1, N not a multiple of the block size)."""
    rng = np.random.default_rng(Bu * 100 + N)
    x_up = rng.random((Bu, D)).astype(np.float32)
    w = np.zeros((Bu, N), np.float32)
    w[np.arange(Bu), rng.integers(0, N, Bu)] = 1.0
    r = rng.random(Bu).astype(np.float32)
    xs = rng.random((Bs, D)).astype(np.float32)
    theta = rng.standard_normal((N, D)).astype(np.float32)
    L = rng.standard_normal((N, D, D)).astype(np.float32) * 0.1
    ainv = np.einsum("nde,nfe->ndf", L, L) + np.eye(D, dtype=np.float32)
    got = K.bandit_update(x_up, w, r, xs, theta, ainv, 0.8)
    want = R.bandit_update(*(jnp.asarray(a) for a in
                             (x_up, w, r, xs, theta, ainv)), 0.8)
    for g, wnt, tol in zip(got, want, (1e-5, 1e-5, 1e-4)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(wnt),
                                   rtol=tol, atol=tol)


# ----------------------------------------------------------------------
# flash attention
# ----------------------------------------------------------------------

@pytest.mark.parametrize("B,Lq,Lk,Hq,Hkv,hd", [
    (1, 64, 64, 2, 2, 32),      # MHA, block-aligned
    (2, 100, 100, 4, 2, 64),    # GQA, ragged lengths
    (1, 1, 300, 8, 2, 64),      # decode-style single query
    (2, 128, 128, 4, 1, 128),   # MQA
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(B, Lq, Lk, Hq, Hkv, hd, dtype):
    q = jnp.asarray(RNG.standard_normal((B, Lq, Hq, hd)), dtype)
    k = jnp.asarray(RNG.standard_normal((B, Lk, Hkv, hd)), dtype)
    v = jnp.asarray(RNG.standard_normal((B, Lk, Hkv, hd)), dtype)
    o1 = K.flash_attention(q, k, v, blk_q=32, blk_k=32)
    o2 = R.mha_attention(q, k, v)
    tol = 2e-4 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(o1, np.float32),
                               np.asarray(o2, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("window,cap,causal", [
    (16, 0.0, True), (0, 30.0, True), (7, 50.0, True), (0, 0.0, False),
])
def test_flash_attention_window_softcap(window, cap, causal):
    B, L, Hq, Hkv, hd = 2, 90, 4, 2, 64
    q = jnp.asarray(RNG.standard_normal((B, L, Hq, hd)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((B, L, Hkv, hd)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((B, L, Hkv, hd)), jnp.float32)
    o1 = K.flash_attention(q, k, v, causal=causal, window=window,
                           softcap=cap, blk_q=32, blk_k=32)
    o2 = R.mha_attention(q, k, v, causal=causal, window=window, softcap=cap)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               rtol=3e-4, atol=3e-4)


# ----------------------------------------------------------------------
# ssd_scan
# ----------------------------------------------------------------------

@pytest.mark.parametrize("Bb,L,H,P,N,chunk", [
    (1, 32, 2, 16, 8, 16), (2, 75, 3, 32, 16, 16),
    (1, 128, 4, 64, 128, 64), (2, 17, 1, 8, 4, 8),
])
def test_ssd_scan_sweep(Bb, L, H, P, N, chunk):
    x = jnp.asarray(RNG.standard_normal((Bb, L, H, P)), jnp.float32)
    dt = jnp.asarray(RNG.random((Bb, L, H)) * 0.5, jnp.float32)
    A = jnp.asarray(-np.exp(RNG.standard_normal(H)), jnp.float32)
    Bm = jnp.asarray(RNG.standard_normal((Bb, L, N)), jnp.float32)
    Cm = jnp.asarray(RNG.standard_normal((Bb, L, N)), jnp.float32)
    h0 = jnp.asarray(RNG.standard_normal((Bb, H, P, N)), jnp.float32)
    y1, hf1 = K.ssd_scan(x, dt, A, Bm, Cm, h0, chunk=chunk)
    y2, hf2 = R.ssd_scan(x, dt, A, Bm, Cm, h0)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=3e-4, atol=3e-4)
    np.testing.assert_allclose(np.asarray(hf1), np.asarray(hf2),
                               rtol=3e-4, atol=3e-4)


def test_ssd_scan_state_chaining():
    """Scanning two halves with carried state == one full scan."""
    Bb, L, H, P, N = 1, 64, 2, 16, 8
    x = jnp.asarray(RNG.standard_normal((Bb, L, H, P)), jnp.float32)
    dt = jnp.asarray(RNG.random((Bb, L, H)) * 0.3, jnp.float32)
    A = jnp.asarray(-np.exp(RNG.standard_normal(H)), jnp.float32)
    Bm = jnp.asarray(RNG.standard_normal((Bb, L, N)), jnp.float32)
    Cm = jnp.asarray(RNG.standard_normal((Bb, L, N)), jnp.float32)
    y_full, h_full = K.ssd_scan(x, dt, A, Bm, Cm, chunk=16)
    h = None
    ys = []
    for s in (slice(0, 32), slice(32, 64)):
        y, h = K.ssd_scan(x[:, s], dt[:, s], A, Bm[:, s], Cm[:, s], h,
                          chunk=16)
        ys.append(y)
    np.testing.assert_allclose(np.concatenate([np.asarray(y) for y in ys], 1),
                               np.asarray(y_full), rtol=3e-4, atol=3e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_full),
                               rtol=3e-4, atol=3e-4)


# ----------------------------------------------------------------------
# moe_gating
# ----------------------------------------------------------------------

@pytest.mark.parametrize("T,E,k,blk", [
    (64, 8, 2, 16), (100, 32, 4, 32), (7, 16, 1, 8), (256, 128, 8, 64),
])
def test_moe_gating_sweep(T, E, k, blk):
    lg = jnp.asarray(RNG.standard_normal((T, E)), jnp.float32)
    v1, i1, a1 = K.moe_gating(lg, k, blk_t=blk)
    v2, i2, a2 = R.moe_gating(lg, k)
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v2),
                               rtol=1e-5, atol=1e-6)
    assert np.array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_allclose(float(a1), float(a2), rtol=1e-5)
    # gates renormalized
    np.testing.assert_allclose(np.asarray(v1).sum(-1), 1.0, rtol=1e-4)
