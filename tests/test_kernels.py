"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps + properties."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, strategies as st

from repro.kernels.ops import (decode_attention, grouped_ffn, masked_compact,
                               ssm_scan)
from repro.kernels.ref import (decode_attention_ref, grouped_ffn_ref,
                               masked_compact_ref, masked_scatter_ref,
                               ssm_scan_ref)

KEY = jax.random.PRNGKey(0)


# --- masked_compact ---------------------------------------------------------
@pytest.mark.parametrize("B,S,D,K", [
    (2, 256, 128, 64), (1, 128, 256, 128), (3, 512, 128, 512),
    (2, 384, 64, 96), (1, 256, 128, 8),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_masked_compact_matches_ref(B, S, D, K, dtype):
    toks = jax.random.normal(KEY, (B, S, D)).astype(dtype)
    mask = jax.random.bernoulli(jax.random.PRNGKey(S + K), 0.35, (B, S))
    o_ref, i_ref, c_ref = masked_compact_ref(toks, mask, K)
    o, i, c = masked_compact(toks, mask, K)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(o_ref, np.float32), rtol=tol, atol=tol)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(i_ref))
    np.testing.assert_array_equal(np.asarray(c), np.asarray(c_ref))


@pytest.mark.parametrize("rate", [0.0, 1.0])
def test_masked_compact_degenerate_masks(rate):
    toks = jax.random.normal(KEY, (2, 128, 64))
    mask = jnp.full((2, 128), bool(rate))
    o, i, c = masked_compact(toks, mask, 128)
    o_ref, i_ref, c_ref = masked_compact_ref(toks, mask, 128)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref), atol=1e-6)
    np.testing.assert_array_equal(np.asarray(c), np.asarray(c_ref))


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 2**16), keep=st.floats(0.05, 0.95))
def test_masked_compact_properties(seed, keep):
    """Invariants: count = min(#masked, K); valid idx strictly increasing;
    compact→scatter→mask-out is the identity on kept tokens."""
    B, S, D, K = 2, 128, 32, 64
    toks = jax.random.normal(jax.random.PRNGKey(seed), (B, S, D))
    mask = jax.random.bernoulli(jax.random.PRNGKey(seed + 1), keep, (B, S))
    out, idx, cnt = masked_compact(toks, mask, K)
    cnt = np.asarray(cnt)
    np.testing.assert_array_equal(
        cnt, np.minimum(np.asarray(mask.sum(1)), K))
    for b in range(B):
        valid = np.asarray(idx[b][:cnt[b]])
        assert (np.diff(valid) > 0).all()           # order-preserving
        assert (np.asarray(idx[b][cnt[b]:]) == -1).all()
    # round-trip
    re = masked_scatter_ref(out, idx, S)
    kept = np.asarray(mask)[:, :, None] & (np.asarray(
        masked_compact_ref(toks, mask, K)[1]) is not None)
    sel = np.asarray(mask.astype(jnp.float32))
    # positions that survived capacity:
    surv = np.asarray((jnp.cumsum(mask, 1) - 1) < K) & np.asarray(mask)
    np.testing.assert_allclose(np.asarray(re)[surv], np.asarray(toks)[surv],
                               rtol=1e-6, atol=1e-6)


# --- decode_attention -------------------------------------------------------
@pytest.mark.parametrize("B,S,H,Hkv,dh,win", [
    (2, 512, 8, 2, 64, 0), (1, 1024, 8, 8, 128, 0),
    (2, 512, 16, 4, 64, 128), (2, 256, 4, 1, 128, 0),
    # caches whose length is not a multiple of the 512-row block: the
    # trailing block overhangs the cache (the launcher sizes max_len as
    # prompt + max_new + 8)
    (3, 600, 8, 2, 64, 0), (2, 1100, 4, 4, 128, 100),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_matches_ref(B, S, H, Hkv, dh, win, dtype):
    q = jax.random.normal(KEY, (B, 1, H, dh)).astype(dtype)
    kc = jax.random.normal(jax.random.PRNGKey(1), (B, S, Hkv, dh)).astype(dtype)
    vc = jax.random.normal(jax.random.PRNGKey(2), (B, S, Hkv, dh)).astype(dtype)
    cl = jnp.asarray(np.linspace(S // 4, S, B, dtype=np.int32))
    r = decode_attention_ref(q, kc, vc, cl, window=win)
    p = decode_attention(q, kc, vc, cl, window=win)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(p, np.float32),
                               np.asarray(r, np.float32), rtol=tol, atol=tol)


@pytest.mark.parametrize("L,B,S,H,Hkv,dh,win,lens", [
    # ragged per-slot lengths: one row, a length on the 512-row block
    # edge, a full cache whose last block overhangs it
    (3, 3, 1100, 8, 2, 64, 0, (1, 512, 1100)),
    # a length one past the block edge, under a sliding window
    (2, 2, 600, 4, 4, 128, 100, (513, 37)),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_reads_stacked_layer(L, B, S, H, Hkv, dh, win, lens,
                                              dtype):
    """Given a layer index, the kernel reads that layer of the stacked
    [L,B,S,Hkv,dh] cache where it lies, and equals the reference on the
    layer's own [B,S,Hkv,dh] cache, for every layer."""
    q = jax.random.normal(KEY, (B, 1, H, dh)).astype(dtype)
    kc = jax.random.normal(jax.random.PRNGKey(1),
                           (L, B, S, Hkv, dh)).astype(dtype)
    vc = jax.random.normal(jax.random.PRNGKey(2),
                           (L, B, S, Hkv, dh)).astype(dtype)
    cl = jnp.asarray(lens, jnp.int32)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    for layer in range(L):
        r = decode_attention_ref(q, kc[layer], vc[layer], cl, window=win)
        p = decode_attention(q, kc, vc, cl, jnp.int32(layer), window=win)
        np.testing.assert_allclose(np.asarray(p, np.float32),
                                   np.asarray(r, np.float32),
                                   rtol=tol, atol=tol)


def test_decode_attention_softmax_property():
    """With identical V rows the output must equal that row (softmax sums
    to 1 over the valid window)."""
    B, S, H, dh = 1, 256, 4, 64
    q = jax.random.normal(KEY, (B, 1, H, dh))
    kc = jax.random.normal(jax.random.PRNGKey(1), (B, S, H, dh))
    row = jax.random.normal(jax.random.PRNGKey(2), (dh,))
    vc = jnp.broadcast_to(row, (B, S, H, dh))
    out = decode_attention(q, kc, vc, jnp.int32(100))
    np.testing.assert_allclose(np.asarray(out)[0, 0],
                               np.broadcast_to(row, (H, dh)), rtol=1e-4)


# --- ssm_scan ---------------------------------------------------------------
@pytest.mark.parametrize("B,S,di,N", [(2, 256, 512, 16), (1, 128, 256, 8)])
def test_ssm_scan_matches_ref(B, S, di, N):
    decay = jax.random.uniform(KEY, (B, S, di, N), jnp.float32, 0.5, 0.999)
    bx = jax.random.normal(jax.random.PRNGKey(1), (B, S, di, N)) * 0.1
    h0 = jax.random.normal(jax.random.PRNGKey(2), (B, di, N))
    r_all, r_last = ssm_scan_ref(decay, bx, h0)
    p_all, p_last = ssm_scan(decay, bx, h0)
    np.testing.assert_allclose(np.asarray(p_all), np.asarray(r_all),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(p_last), np.asarray(r_last),
                               rtol=2e-4, atol=2e-4)


# --- grouped_ffn ------------------------------------------------------------
@pytest.mark.parametrize("E,C,D,F", [(4, 256, 128, 512), (2, 128, 256, 1024),
                                     (8, 128, 64, 512)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_grouped_ffn_matches_ref(E, C, D, F, dtype):
    ks = jax.random.split(KEY, 4)
    buf = (jax.random.normal(ks[0], (E, C, D)) * 0.3).astype(dtype)
    wg = (jax.random.normal(ks[1], (E, D, F)) / np.sqrt(D)).astype(dtype)
    wu = (jax.random.normal(ks[2], (E, D, F)) / np.sqrt(D)).astype(dtype)
    wd = (jax.random.normal(ks[3], (E, F, D)) / np.sqrt(F)).astype(dtype)
    r = grouped_ffn_ref(buf, wg, wu, wd)
    p = grouped_ffn(buf, wg, wu, wd)
    tol = 2e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(p, np.float32),
                               np.asarray(r, np.float32), rtol=tol, atol=tol)


def test_grouped_ffn_zero_rows_property():
    """Empty capacity slots (zero rows) must stay exactly zero — the MoE
    combine relies on it."""
    E, C, D, F = 2, 128, 64, 256
    buf = jnp.zeros((E, C, D)).at[:, :5].set(1.0)
    wg = jnp.ones((E, D, F)) * 0.01
    wd = jnp.ones((E, F, D)) * 0.01
    out = grouped_ffn(buf, wg, wg, wd)
    assert np.abs(np.asarray(out[:, 5:])).max() == 0.0


def test_ssm_scan_decay_property():
    """With bx=0 the scan is a pure decay: h_T = h0 * prod(decay)."""
    B, S, di, N = 1, 128, 256, 8
    decay = jnp.full((B, S, di, N), 0.99)
    bx = jnp.zeros_like(decay)
    h0 = jnp.ones((B, di, N))
    _, h_last = ssm_scan(decay, bx, h0)
    np.testing.assert_allclose(np.asarray(h_last), 0.99 ** S, rtol=1e-3)
