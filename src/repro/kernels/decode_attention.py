"""Pallas TPU kernel: GQA decode attention (1 token vs a long KV cache).

The decode hot-spot is memory-bound: every step streams the whole (or the
windowed part of the) KV cache from HBM once.  The grid is
``(B, S/s_block)``: each cell DMAs one ``[s_block, Hkv, dh]`` slab of
layer ``layer`` of the layer-stacked ``[L, B, S, Hkv, dh]`` cache, read
in place (a single layer's ``[B, S, Hkv, dh]`` cache is a stack of one)
— ALL kv heads, so the block's two minor dims are the array's own
``(Hkv, dh)`` and meet Mosaic's (8, 128) tiling rule for any head
geometry — and runs an online-softmax accumulation per kv head over it,
keeping the ``[Hkv, G, dh]`` accumulator in VMEM scratch (G = query
heads per kv head).  The MXU sees ``[G,dh]x[dh,Sb]`` and
``[G,Sb]x[Sb,dh]`` GEMMs per head.

The per-sequence valid lengths ``cache_len [B]`` and the layer index are
scalar-prefetched into SMEM (the index maps receive them too, so a later
clamp of the KV block index to the live length needs no new operand).
``S`` need not be a multiple of ``s_block``: the trailing partial
block's out-of-range rows are masked out of both the scores and the
values.  ``window > 0`` adds the sliding-window mask (mixtral / zamba
long-context).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# VMEM bytes of one K (or V) block; K and V are each double-buffered, so
# the kernel's pipelined footprint is ~4x this — inside v5e's default
# 16 MiB scoped-VMEM limit
_BLOCK_BYTES = 2 * 1024 * 1024


def auto_interpret() -> bool:
    """Compile the kernels on a TPU, interpret them everywhere else (CPU
    and GPU have no Mosaic backend).  There is no override: on a TPU the
    kernels always run compiled."""
    return jax.default_backend() != "tpu"


def _kernel(len_ref, layer_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
            *, s_block: int, n_s: int, seq_len: int, window: int,
            scale: float):
    b, s = pl.program_id(0), pl.program_id(1)

    @pl.when(s == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    cache_len = len_ref[b]
    pos = s * s_block + jax.lax.broadcasted_iota(jnp.int32, (1, s_block), 1)
    valid = pos < cache_len
    if window:
        valid &= pos >= (cache_len - window)
    rows_ok = None
    if seq_len % s_block:
        # the trailing block overhangs the cache: its extra rows hold
        # whatever the VMEM buffer held before, so zero them out of P·V
        rows = s * s_block + jax.lax.broadcasted_iota(
            jnp.int32, (s_block, 1), 0)
        rows_ok = rows < seq_len

    for h in range(q_ref.shape[0]):                       # static, Hkv
        q = q_ref[h].astype(jnp.float32)                  # [G, dh]
        k = k_ref[:, h, :].astype(jnp.float32)            # [Sb, dh]
        v = v_ref[:, h, :].astype(jnp.float32)            # [Sb, dh]
        if rows_ok is not None:
            v = jnp.where(rows_ok, v, 0.0)
        sc = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [G, Sb]
        sc = jnp.where(valid, sc, -jnp.inf)

        m_prev = m_scr[h]                                 # [G, 1]
        m_new = jnp.maximum(m_prev, sc.max(axis=-1, keepdims=True))
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.where(jnp.isfinite(sc), jnp.exp(sc - m_safe), 0.0)
        corr = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_safe), 0.0)
        l_scr[h] = l_scr[h] * corr + p.sum(axis=-1, keepdims=True)
        acc_scr[h] = acc_scr[h] * corr + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_scr[h] = m_new

    @pl.when(s == n_s - 1)
    def _finalize():
        o_ref[...] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-20)
                      ).astype(o_ref.dtype)


def seq_block(S: int, Hkv: int, dh: int, itemsize: int,
              s_block: int = 512) -> int:
    """Sequence rows per KV block: ``s_block`` capped so one block stays
    within ``_BLOCK_BYTES`` (a multiple of 16, bf16's sublane packing),
    or the whole cache when it is shorter than that."""
    fit = max(16, (_BLOCK_BYTES // (Hkv * dh * itemsize)) // 16 * 16)
    blk = min(s_block, fit)
    return S if S <= blk else blk


def decode_attention_pallas(q, k_cache, v_cache, cache_len, layer=None, *,
                            window: int = 0, s_block: int = 512,
                            interpret: Optional[bool] = None):
    """q: [B,1,H,dh]; caches: [B,S,Hkv,dh], or the layer-stacked
    [L,B,S,Hkv,dh] with ``layer`` (a traced scalar) selecting the layer
    the kernel reads in place; cache_len: [B] or scalar.
    Returns [B,1,H,dh] (v dtype).  Matches ref.decode_attention_ref
    (of ``cache[layer]``).
    ``interpret=None`` auto-detects: compiled on TPU, interpreted off it."""
    if interpret is None:
        interpret = auto_interpret()
    if s_block % 16:
        raise ValueError(f"s_block must be a multiple of 16, got {s_block}")
    if layer is None:
        # one layer's cache is a stack of one: the leading axis is free
        k_cache, v_cache, layer = k_cache[None], v_cache[None], 0
    B, _, H, dh = q.shape
    S, Hkv = k_cache.shape[2], k_cache.shape[3]
    G = H // Hkv
    blk = seq_block(S, Hkv, dh, k_cache.dtype.itemsize, s_block)
    n_s = pl.cdiv(S, blk)
    cl = jnp.broadcast_to(jnp.asarray(cache_len, jnp.int32), (B,))
    lyr = jnp.asarray(layer, jnp.int32).reshape((1,))
    qh = q.reshape(B, Hkv, G, dh)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, n_s),
        in_specs=[
            pl.BlockSpec((None, Hkv, G, dh),
                         lambda b, s, lens, lyr: (b, 0, 0, 0)),
            pl.BlockSpec((None, None, blk, Hkv, dh),
                         lambda b, s, lens, lyr: (lyr[0], b, s, 0, 0)),
            pl.BlockSpec((None, None, blk, Hkv, dh),
                         lambda b, s, lens, lyr: (lyr[0], b, s, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, Hkv, G, dh),
                               lambda b, s, lens, lyr: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Hkv, G, 1), jnp.float32),
            pltpu.VMEM((Hkv, G, 1), jnp.float32),
            pltpu.VMEM((Hkv, G, dh), jnp.float32),
        ])
    out = pl.pallas_call(
        functools.partial(_kernel, s_block=blk, n_s=n_s, seq_len=S,
                          window=window, scale=1.0 / np.sqrt(dh)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, dh), v_cache.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="decode_attention",
        interpret=interpret,
    )(cl, lyr, qh, k_cache, v_cache)
    return out.reshape(B, 1, H, dh)
