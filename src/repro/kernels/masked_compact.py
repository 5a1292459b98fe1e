"""Pallas TPU kernel: masked token compaction (paper §VI frame masking).

GPU intuition would be a warp-level stream compaction (ballot + prefix sum
+ scatter).  TPUs have no warp shuffles — the TPU-native formulation turns
both the prefix sum and the scatter into matmuls that the MXU eats:

    local = mask @ U                     (U strictly upper-triangular ones:
                                          exclusive prefix sum, [1, Sb])
    p     = running_count + local        (global slot of each kept token)
    Pᵀ[k, i] = mask_i · [p_i == k]       ([K, Sb] one-hot, built transposed)
    out[K, Dt] += Pᵀ @ tokens[Sb, Dt]    (MXU GEMM)

Grid = (B, nD, nS) with the S axis innermost; a scalar SMEM cell carries the
running count across S-blocks (TPU grid execution is sequential over the
trailing axis, so the carry is well-defined).  Output/idx blocks revisit
across s and accumulate; they are zero/-1-initialized at s == 0.

Every per-row operand carries a unit axis (mask ``[B,1,S]``, idx
``[B,1,K]``, count ``[B,1,1]``) so each block's two minor dims are a
``(1, n)`` row that meets Mosaic's (8, 128) tiling rule.  The index and
token GEMMs run at HIGHEST precision: positions above 256 are not exact
in a single bf16 pass.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_HI = jax.lax.Precision.HIGHEST


def _kernel(mask_ref, tok_ref, out_ref, idx_ref, cnt_ref, count_smem,
            *, capacity: int, s_block: int, n_s: int):
    s = pl.program_id(2)
    d = pl.program_id(1)

    @pl.when(s == 0)
    def _init():
        count_smem[0] = 0
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when((s == 0) & (d == 0))
    def _init_idx():
        idx_ref[...] = jnp.full_like(idx_ref, -1)

    base = count_smem[0]
    m = mask_ref[...].astype(jnp.float32)                  # [1, Sb]
    upper = (jax.lax.broadcasted_iota(jnp.int32, (s_block, s_block), 0)
             < jax.lax.broadcasted_iota(jnp.int32, (s_block, s_block), 1))
    local = jnp.dot(m, upper.astype(jnp.float32), precision=_HI,
                    preferred_element_type=jnp.float32)   # exclusive cumsum
    pos = base + local.astype(jnp.int32)                   # [1, Sb] slot
    keep = (m > 0) & (pos < capacity)

    onehot_t = (jax.lax.broadcasted_iota(jnp.int32, (capacity, s_block), 0)
                == pos) & keep                             # [K, Sb]
    oh_t = onehot_t.astype(jnp.float32)

    tok = tok_ref[...].astype(jnp.float32)                 # [Sb, Dt]
    out_ref[...] += jnp.dot(oh_t, tok, precision=_HI,
                            preferred_element_type=jnp.float32
                            ).astype(out_ref.dtype)

    @pl.when(d == 0)
    def _indices():
        gidx = s * s_block + jax.lax.broadcasted_iota(
            jnp.int32, (1, s_block), 1)
        # empty slots stay -1: accumulate (idx+1) so  -1 + (i+1) = i
        idx_ref[...] += jax.lax.dot_general(
            (gidx + 1).astype(jnp.float32), oh_t,
            (((1,), (1,)), ((), ())), precision=_HI,
            preferred_element_type=jnp.float32).astype(jnp.int32)

    new_count = base + jnp.sum(m).astype(jnp.int32)
    count_smem[0] = new_count

    @pl.when(s == n_s - 1)
    def _finalize():
        cnt_ref[...] = jnp.full_like(cnt_ref, jnp.minimum(new_count,
                                                          capacity))


def masked_compact_pallas(tokens, mask, capacity: int, *,
                          s_block: int = 128, d_block: int = 128,
                          interpret: bool = True):
    """tokens: [B,S,D]; mask: [B,S] bool.  Matches ref.masked_compact_ref."""
    B, S, D = tokens.shape
    s_block = min(s_block, S)
    d_block = min(d_block, D)
    assert S % s_block == 0 and D % d_block == 0, (S, s_block, D, d_block)
    n_s, n_d = S // s_block, D // d_block
    grid = (B, n_d, n_s)

    out, idx, cnt = pl.pallas_call(
        functools.partial(_kernel, capacity=capacity, s_block=s_block, n_s=n_s),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, 1, s_block), lambda b, d, s: (b, 0, s)),
            pl.BlockSpec((None, s_block, d_block), lambda b, d, s: (b, s, d)),
        ],
        out_specs=[
            pl.BlockSpec((None, capacity, d_block), lambda b, d, s: (b, 0, d)),
            pl.BlockSpec((None, 1, capacity), lambda b, d, s: (b, 0, 0)),
            pl.BlockSpec((None, 1, 1), lambda b, d, s: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, capacity, D), tokens.dtype),
            jax.ShapeDtypeStruct((B, 1, capacity), jnp.int32),
            jax.ShapeDtypeStruct((B, 1, 1), jnp.int32),
        ],
        scratch_shapes=[pltpu.SMEM((1,), jnp.int32)],
        name="masked_compact",
        interpret=interpret,
    )(mask.reshape(B, 1, S).astype(jnp.int32), tokens)
    return out, idx.reshape(B, capacity), cnt.reshape(B)
