"""GQA / MHA attention with sliding-window, cross-attention and KV caches.

Prefill/train use a chunked online-softmax ("flash-in-XLA") formulation so
activation memory stays O(S·chunk) instead of O(S²) — mandatory for the
prefill_32k shape.  Decode is a single masked pass over the cache (1 query
token); the Pallas kernel in ``repro.kernels.decode_attention`` implements
the same contraction for the TPU hot path.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.layers import apply_rope, norm_apply
from repro.models.sharding import constrain


# ---------------------------------------------------------------------------
def attn_init(key, cfg, dtype, *, cross: bool = False):
    d, h, hkv, dh = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    s = 1.0 / np.sqrt(d)
    p = {
        "wq": (jax.random.normal(ks[0], (d, h, dh)) * s).astype(dtype),
        "wk": (jax.random.normal(ks[1], (d, hkv, dh)) * s).astype(dtype),
        "wv": (jax.random.normal(ks[2], (d, hkv, dh)) * s).astype(dtype),
        "wo": (jax.random.normal(ks[3], (h, dh, d)) * (1.0 / np.sqrt(h * dh))).astype(dtype),
    }
    if cfg.qk_norm and not cross:
        p["q_norm"] = {"scale": jnp.ones((dh,), jnp.float32)}
        p["k_norm"] = {"scale": jnp.ones((dh,), jnp.float32)}
    return p


def _qkv(params, x, kv_x, cfg, q_positions, kv_positions, *, rope: bool):
    q = constrain(jnp.einsum("bsd,dhk->bshk", x, params["wq"]),
                  "batch", None, "model", None)
    k = jnp.einsum("bsd,dhk->bshk", kv_x, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", kv_x, params["wv"])
    if "q_norm" in params:
        class _R:  # rmsnorm over head_dim
            norm_type = "rmsnorm"
        q = norm_apply(params["q_norm"], q, _R)
        k = norm_apply(params["k_norm"], k, _R)
    if rope:
        q = apply_rope(q, q_positions, cfg.rope_theta)
        k = apply_rope(k, kv_positions, cfg.rope_theta)
    return q, k, v


# ---------------------------------------------------------------------------
def chunked_attention(q, k, v, *, causal: bool, window: int,
                      q_positions, kv_positions,
                      q_chunk: int = 512, kv_chunk: int = 1024):
    """Online-softmax attention.

    q: [B,Sq,H,dh]; k,v: [B,Sk,Hkv,dh]; positions give global indices used
    for the causal / sliding-window mask.  Returns [B,Sq,H,dh].
    """
    B, Sq, H, dh = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    # Expand GQA KV to the full H heads up front.  Same FLOPs (scores are
    # H×Sq×Sk either way), but the head axis stays H everywhere — which is
    # what lets GSPMD keep attention head-parallel when Hkv < model-axis
    # size (an [.., Hkv, G, ..] split would replicate across "model").
    if Hkv != H:
        rep = H // Hkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    k = constrain(k, "batch", None, "model", None)
    v = constrain(v, "batch", None, "model", None)
    q = constrain(q, "batch", None, "model", None)
    q_chunk = min(q_chunk, Sq)
    kv_chunk = min(kv_chunk, Sk)
    # pad to chunk multiples (static shapes only)
    pq = (-Sq) % q_chunk
    pk = (-Sk) % kv_chunk
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
        q_positions = jnp.pad(q_positions, ((0, pq),), constant_values=-1)
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
        kv_positions = jnp.pad(kv_positions, ((0, pk),), constant_values=2**30)
    nq, nk = q.shape[1] // q_chunk, k.shape[1] // kv_chunk
    scale = 1.0 / np.sqrt(dh)

    qb = q.reshape(B, nq, q_chunk, H, dh)
    kb = k.reshape(B, nk, kv_chunk, H, dh)
    vb = v.reshape(B, nk, kv_chunk, H, dh)
    qp = q_positions.reshape(nq, q_chunk)
    kp = kv_positions.reshape(nk, kv_chunk)

    def q_block(qi):
        qc = qb[:, qi].astype(jnp.float32)   # [B,Cq,H,dh]
        qpos = qp[qi]                        # [Cq]

        def kv_step(carry, inp):
            m, l, acc = carry
            kc, vc, kpos = inp               # [B,Ck,H,dh], [Ck]
            kc = kc.astype(jnp.float32)
            vc = vc.astype(jnp.float32)
            s = constrain(jnp.einsum("bqhd,bkhd->bhqk", qc, kc) * scale,
                          "batch", "model", None, None)
            mask = jnp.ones((q_chunk, kv_chunk), bool)
            if causal:
                mask &= qpos[:, None] >= kpos[None, :]
            if window:
                mask &= (qpos[:, None] - kpos[None, :]) < window
            mask &= kpos[None, :] < 2**30    # padding keys
            s = jnp.where(mask[None, None], s, -jnp.inf)
            m_new = jnp.maximum(m, s.max(axis=-1))
            # guard fully-masked rows
            m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            p = jnp.exp(s - m_safe[..., None])
            p = jnp.where(jnp.isfinite(s), p, 0.0)
            corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
            l_new = l * corr + p.sum(axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum("bhqk,bkhd->bhqd", p, vc)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, H, q_chunk), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((B, H, q_chunk), jnp.float32)
        a0 = jnp.zeros((B, H, q_chunk, dh), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0),
            (jnp.moveaxis(kb, 1, 0), jnp.moveaxis(vb, 1, 0), kp))
        out = acc / jnp.maximum(l, 1e-20)[..., None]   # [B,H,Cq,dh]
        return jnp.einsum("bhqd->bqhd", out)

    outs = jax.lax.map(q_block, jnp.arange(nq))        # [nq,B,Cq,H,dh]
    out = jnp.moveaxis(outs, 0, 1).reshape(B, nq * q_chunk, H, dh)
    return out[:, :Sq].astype(v.dtype)


# ---------------------------------------------------------------------------
def decode_attention_ref(q, k_cache, v_cache, cache_len, *, window: int):
    """Single-token attention over a cache.  q: [B,1,H,dh];
    caches: [B,S,Hkv,dh]; cache_len: scalar — number of valid entries
    (the new token already written at cache_len-1).

    Unlike prefill, the KV heads are NOT expanded to H here: the dominant
    tensor is the cache itself, which stays in its stored (sequence-sharded
    when Hkv < model-axis) layout — expanding would reshard O(B·S·H·dh)
    bytes across the mesh every step (§Perf iteration C1: 275 GB/chip of
    collective traffic on llama decode_32k).  With the grouped layout the
    only cross-shard data are the [B,H]-sized softmax stats and the
    [B,H,dh] output partials."""
    B, _, H, dh = q.shape
    S, Hkv = k_cache.shape[1], k_cache.shape[2]
    G = H // Hkv
    scale = 1.0 / np.sqrt(dh)
    qf = q.reshape(B, Hkv, G, dh).astype(jnp.float32)
    s = jnp.einsum("bhgd,bkhd->bhgk", qf,
                   k_cache.astype(jnp.float32)) * scale
    pos = jnp.arange(S)
    # cache_len: scalar, or per-slot lengths [B] (continuous batching) —
    # a [1]-shaped scalar broadcasts over the batch dim identically
    cl = jnp.atleast_1d(jnp.asarray(cache_len))
    mask = pos[None, :] < cl[:, None]
    if window:
        mask &= pos[None, :] >= (cl[:, None] - window)
    s = jnp.where(mask[:, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgk,bkhd->bhgd", p, v_cache.astype(jnp.float32))
    return out.reshape(B, 1, H, dh).astype(v_cache.dtype)


# ---------------------------------------------------------------------------
def quantize_kv(t):
    """Absmax int8 per (batch, position, head): t [B,1,Hkv,dh] ->
    (int8 values, f32 scale [B,1,Hkv,1])."""
    amax = jnp.max(jnp.abs(t.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(t.astype(jnp.float32) / scale), -127, 127)
    return q.astype(jnp.int8), scale


def _row_update(c, n, i):
    """Single-row cache write: c [S,Hkv,dh], n [1,Hkv,dh] at seq index i."""
    return jax.lax.dynamic_update_slice_in_dim(c, n.astype(c.dtype), i, axis=0)


def _stacked_row_update(cache, new, layer, index):
    """Write one token's rows into layer ``layer`` of the layer-stacked
    cache [L,B,S,Hkv,dh] in place: slot b's row lands at sequence index
    ``index[b]`` (a scalar index serves every slot).  One scatter with the
    slot axis as a batching dim, the stacked twin of ``_row_update``'s
    vmap; indices clamp like ``dynamic_update_slice``'s."""
    B = cache.shape[1]
    pos = jnp.broadcast_to(jnp.asarray(index, jnp.int32), (B,))
    idx = jnp.stack([jnp.broadcast_to(jnp.asarray(layer, jnp.int32), (B,)),
                     pos], axis=1)                              # [B, 2]
    dnums = jax.lax.ScatterDimensionNumbers(
        update_window_dims=(1, 2), inserted_window_dims=(0, 2),
        scatter_dims_to_operand_dims=(0, 2),
        operand_batching_dims=(1,), scatter_indices_batching_dims=(0,))
    return jax.lax.scatter(cache, idx, new[:, 0].astype(cache.dtype), dnums,
                           indices_are_sorted=True, unique_indices=True,
                           mode=jax.lax.GatherScatterMode.CLIP)


def cache_update(cache, new, index):
    """Write one token's K or V into the cache at `index` (seq axis=1).

    A tuple ``index = (layer, position)`` addresses the layer-stacked
    cache [L,B,S,Hkv,dh] instead, whose sequence dim is local: the rows
    are written in place (the decode step's in-place path,
    ``transformer.decode_inplace``).

    On the production mesh the cache's sequence dim is sharded over "model"
    (and "data" when the batch can't shard — long_500k) whenever the KV
    heads don't divide the model axis.  A plain dynamic_update_slice at a
    dynamic index makes GSPMD replicate the whole cache every step
    (~0.5 GB/chip/layer on llama decode_32k — §Perf iteration C2); instead
    a shard_map makes the owning sequence-shard apply the update locally,
    with zero collective traffic.

    ``index`` may be a scalar (static batching: all rows at one position)
    or a per-slot [B] vector (continuous batching: each slot writes its own
    position).  Both ride the same shard_map on a sharded cache — the
    per-slot form vmaps the row update inside each sequence shard and masks
    out the rows whose position lands on another shard, so the continuous
    engine runs unmodified on a model-sharded mesh.
    """
    from repro.models.sharding import active_mesh, seq_shard_layout
    from jax.sharding import PartitionSpec as P

    if isinstance(index, tuple):
        return _stacked_row_update(cache, new, *index)
    mesh = active_mesh()
    vector = bool(jnp.ndim(index))
    B, S, Hkv, dh = cache.shape
    lay = None
    if mesh is not None and "model" in mesh.shape:
        lay = seq_shard_layout(mesh, B, S, Hkv)
    if lay is None:
        # sequence dim not sharded — the plain update is already local
        if vector:
            return jax.vmap(_row_update)(cache, new, index)
        return jax.lax.dynamic_update_slice_in_dim(
            cache, new.astype(cache.dtype), index, axis=1)

    def _shard_start():
        # linear index of this device's sequence shard
        lin = jnp.zeros((), jnp.int32)
        stride = 1
        for ax in reversed(lay.s_axes):
            lin = lin + jax.lax.axis_index(ax) * stride
            stride = stride * mesh.shape[ax]
        return lin * lay.s_local

    if vector:
        def body(c, n, idx):
            start = _shard_start()
            local = jnp.clip(idx - start, 0, lay.s_local - 1)
            mine = (idx >= start) & (idx < start + lay.s_local)   # [B_loc]
            upd = jax.vmap(_row_update)(c, n, local)
            return jnp.where(mine[:, None, None, None], upd, c)
        idx_spec = P(lay.bspec)   # per-row indices shard with the batch dim
    else:
        def body(c, n, idx):
            start = _shard_start()
            local = jnp.clip(idx - start, 0, lay.s_local - 1)
            mine = (idx >= start) & (idx < start + lay.s_local)
            upd = jax.lax.dynamic_update_slice_in_dim(c, n.astype(c.dtype),
                                                      local, axis=1)
            return jnp.where(mine, upd, c)
        idx_spec = P()

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(lay.bspec, lay.sspec, lay.hspec, None),
                  P(lay.bspec, None, lay.hspec, None), idx_spec),
        out_specs=P(lay.bspec, lay.sspec, lay.hspec, None),
        check_vma=False,
    )(cache, new, index)


def attn_apply(params, x, cfg, *, positions, mode: str,
               kv_x=None, kv_positions=None, causal: bool = True,
               cache=None, cache_index=None, use_pallas: bool = False,
               prefix_kv=None, layer=None):
    """Unified attention entry.

    mode "full":   self/cross attention over x (train & prefill).
                   returns (out, (k, v))  — k/v for cache seeding.
                   ``prefix_kv=(k_pre, v_pre)`` resumes a prefill from
                   cached post-RoPE K/V covering positions [0, q): x holds
                   only the TAIL rows (``positions`` are their global
                   indices), queries attend over prefix+tail keys, and the
                   returned k/v are the full-length concatenation — so the
                   seeded cache is laid out exactly like a cold prefill's.
    mode "decode": x is [B,1,D]; cache = {"k","v"} [B,S,Hkv,dh];
                   cache_index = scalar position of the new token (or
                   per-slot positions [B]).  With ``layer`` (a traced
                   scalar) the cache is the layer-stacked [L,B,S,Hkv,dh]
                   one: the new rows are written into layer ``layer`` in
                   place and attention reads that layer where it lies.
                   returns (out, new_cache).
    """
    cross = kv_x is not None
    rope = not cross
    if mode == "full":
        src = kv_x if cross else x
        src_pos = kv_positions if cross else positions
        q, k, v = _qkv(params, x, src, cfg, positions, src_pos, rope=rope)
        if prefix_kv is not None:
            assert not cross, "prefix resume is self-attention only"
            pk, pv = prefix_kv
            k = jnp.concatenate([pk.astype(k.dtype), k], axis=1)
            v = jnp.concatenate([pv.astype(v.dtype), v], axis=1)
            src_pos = jnp.arange(k.shape[1])
        out = chunked_attention(
            q, k, v, causal=causal and not cross,
            window=cfg.sliding_window if not cross else 0,
            q_positions=positions, kv_positions=src_pos)
        y = jnp.einsum("bshk,hkd->bsd", out.astype(x.dtype), params["wo"])
        return y, (k, v)

    assert mode == "decode"
    if cross:
        # cross-attention at decode: cache holds the precomputed encoder K/V
        B = x.shape[0]
        q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
        enc_len = cache["k"].shape[1]
        out = decode_attention_ref(q, cache["k"], cache["v"], enc_len, window=0)
        y = jnp.einsum("bshk,hkd->bsd", out.astype(x.dtype), params["wo"])
        return y, cache
    q, k, v = _qkv(params, x, x, cfg, positions, positions, rope=True)
    if "k_scale" in cache:
        # int8 KV cache (§Perf C4): per-(position,head) absmax quantization
        new_cache = {}
        for name, t in (("k", k), ("v", v)):
            qt, sc = quantize_kv(t)
            new_cache[name] = cache_update(cache[name], qt, cache_index)
            new_cache[name + "_scale"] = cache_update(
                cache[name + "_scale"], sc, cache_index)
        k_cache = new_cache["k"].astype(jnp.float32) * new_cache["k_scale"]
        v_cache = new_cache["v"].astype(jnp.float32) * new_cache["v_scale"]
        out = decode_attention_ref(q, k_cache, v_cache, cache_index + 1,
                                   window=cfg.sliding_window)
        y = jnp.einsum("bshk,hkd->bsd", out.astype(x.dtype), params["wo"])
        return y, new_cache
    at = cache_index if layer is None else (layer, cache_index)
    k_cache = cache_update(cache["k"], k.astype(cache["k"].dtype), at)
    v_cache = cache_update(cache["v"], v.astype(cache["v"].dtype), at)
    # the Pallas decode kernel takes a scalar OR per-slot [B] cache length
    # (continuous batching), so both index shapes ride the TPU hot path
    if use_pallas:
        from repro.kernels.ops import decode_attention as _dec
        out = _dec(q, k_cache, v_cache, cache_index + 1, layer,
                   window=cfg.sliding_window)
    else:
        k_read, v_read = (k_cache, v_cache) if layer is None \
            else (k_cache[layer], v_cache[layer])
        out = decode_attention_ref(q, k_read, v_read, cache_index + 1,
                                   window=cfg.sliding_window)
    y = jnp.einsum("bshk,hkd->bsd", out.astype(x.dtype), params["wo"])
    return y, {"k": k_cache, "v": v_cache}
