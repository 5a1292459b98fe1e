"""jit'd public wrappers for the Pallas kernels + fused serving hot-path ops.

``interpret`` follows the backend: on a TPU the kernels always compile
through Mosaic; everywhere else (CPU CI, GPU) they run in interpreter
mode for correctness.  Nothing can force interpret mode on a TPU.

``admit_slots`` is not a Pallas kernel — it is the XLA-fused admission
splice the continuous serving engine dispatches at macro-step boundaries:
one donated program replacing the 4-scatters-per-slot host loop admission
used to cost, so splicing shadow-prefilled requests into the live slot
pool never syncs the host.

``splice_blocks`` (PR 5) is its cache-side sibling for disaggregated
prefill: one leaf-level scatter writing M transferred prefill KV blocks
into M decode slots at once (the engine jits the whole cache-tree walk as
ONE donated program, replacing M sequential per-slot writes).  On a
sequence-sharded mesh the splice routes through a ``shard_map`` resolved
by ``models/sharding.seq_shard_layout`` — the same layout contract as the
decode path's ``cache_update`` — so each shard writes only its own rows
and the multi-GiB cache is never regathered at an admission boundary.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention import auto_interpret


@functools.partial(jax.jit, static_argnums=(2,))
def masked_compact(tokens, mask, capacity: int):
    from repro.kernels.masked_compact import masked_compact_pallas
    return masked_compact_pallas(tokens, mask, capacity,
                                 interpret=auto_interpret())


@functools.partial(jax.jit, static_argnames=("window",))
def decode_attention(q, k_cache, v_cache, cache_len, layer=None, *,
                     window: int = 0):
    from repro.kernels.decode_attention import decode_attention_pallas
    return decode_attention_pallas(q, k_cache, v_cache, cache_len, layer,
                                   window=window,
                                   interpret=auto_interpret())


def admit_state(cur_tok, lengths, remaining, done, slot_ids, last_logits,
                prompt_lens, max_news, *, eos_id: int = -1):
    """Splice newly admitted requests into the decode-state vectors — the
    composable core of :func:`admit_slots`.

    Takes the [M] slot ids being filled, the concatenated prefill logits
    [M, V] and per-request prompt lengths / generation budgets,
    greedy-argmaxes the first tokens ON DEVICE and scatters all four
    state vectors at once.  Callers may PAD the admission vectors to a
    fixed width by repeating the last real entry: duplicate scatter
    indices then carry identical values, so the writes are idempotent and
    every admitted-count reuses one compiled program (and one input
    sharding) instead of tracing per width.

    Not jitted here — the serving engine traces it inside the fused
    boundary program (cache splice + state scatter, one dispatch per
    boundary); :func:`admit_slots` keeps the standalone donated jit for
    the per-step/boundary-blocking admission paths.
    """
    first = jnp.argmax(last_logits, axis=-1).astype(jnp.int32)
    cur_tok = cur_tok.at[slot_ids].set(first)
    lengths = lengths.at[slot_ids].set(prompt_lens)
    remaining = remaining.at[slot_ids].set(max_news - 1)
    done = done.at[slot_ids].set((max_news <= 1) | (first == eos_id))
    return cur_tok, lengths, remaining, done, first


@functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3),
                   static_argnames=("eos_id",))
def admit_slots(cur_tok, lengths, remaining, done, slot_ids, last_logits,
                prompt_lens, max_news, *, eos_id: int = -1):
    """One fused donated dispatch per admission phase (see
    :func:`admit_state` for the semantics and the fixed-width padding
    contract).  The state vectors are donated (updated in place) —
    callers must rebind from the returns, exactly like the decode loop.
    Returns the updated state plus the [M] first tokens, whose host fetch
    the engine defers until the next macro-step block await (by which
    point they are long computed).
    """
    return admit_state(cur_tok, lengths, remaining, done, slot_ids,
                       last_logits, prompt_lens, max_news, eos_id=eos_id)


def splice_blocks(dst, src, slot_ids):
    """Write M stacked prefill-cache blocks into M slots of a big
    decode-cache leaf — the fused cross-group splice.

    ``dst`` is a decode leaf laid out ``[L, B, ...]`` (layers, slots,
    then either a sequence dim of length S plus feature dims, or
    same-shape state dims); ``src`` stacks the M transferred B=1 blocks
    on the slot axis: ``[L, M, P, ...]`` (P ≤ S, written at sequence
    offset 0 — the slot's previous occupant beyond P is hidden by the
    per-slot length masks) or ``[L, M, ...]`` for same-shape leaves
    (SSM states, cross-attention K/V), which are fully replaced.

    Not jitted here: the serving engine traces this inside ONE donated
    program covering the whole cache tree, so a boundary with M admitted
    blocks costs a single dispatch instead of M per-slot writes.  The
    update lowers to M ``dynamic_update_slice`` ops per leaf — NOT an
    advanced-index scatter, which XLA:CPU executes as an element loop
    with a full operand copy (~6x slower than the per-slot writes this
    op replaces).  On a mesh whose sequence dim is sharded
    (``seq_shard_layout`` resolves a layout) the update instead runs as
    a shard_map — each shard gathers its own rows from the (small,
    replicated) source block and writes locally, instead of GSPMD
    regathering the whole cache.
    """
    src = src.astype(dst.dtype)
    lay = mesh = None
    if dst.ndim == 5 and dst.shape[2:] != src.shape[2:]:
        # [L, B, S, Hkv, dh] attention leaves (incl. scales) with the
        # sequence dim possibly sharded
        from repro.models.sharding import active_mesh, seq_shard_layout
        mesh = active_mesh()
        if mesh is not None and "model" in mesh.shape:
            lay = seq_shard_layout(mesh, dst.shape[1], dst.shape[2],
                                   dst.shape[3])
    if lay is None:
        for m in range(src.shape[1]):
            start = (jnp.int32(0), slot_ids[m]) \
                + (jnp.int32(0),) * (dst.ndim - 2)
            dst = jax.lax.dynamic_update_slice(dst, src[:, m:m + 1], start)
        return dst
    P = src.shape[2]

    from jax.sharding import PartitionSpec as Pspec
    baxes = tuple(a for a in ("pod", "data") if a in mesh.shape) \
        if lay.bspec is not None else ()

    def body(d, s, slots):
        # d [L, B_loc, S_loc, H_loc, dh]; s [L, M, P, H_loc, dh] (seq- and
        # batch-replicated: blocks are tiny next to the cache)
        B_loc, S_loc = d.shape[1], d.shape[2]
        seq_start = jnp.zeros((), jnp.int32)
        stride = 1
        for ax in reversed(lay.s_axes):
            seq_start = seq_start + jax.lax.axis_index(ax) * stride
            stride = stride * mesh.shape[ax]
        seq_start = seq_start * lay.s_local
        b_start = jnp.zeros((), jnp.int32)
        stride = 1
        for ax in reversed(baxes):
            b_start = b_start + jax.lax.axis_index(ax) * stride
            stride = stride * mesh.shape[ax]
        b_start = b_start * B_loc
        pos = seq_start + jnp.arange(S_loc)           # my global seq rows
        valid = pos < P
        rows = jnp.take(s, jnp.clip(pos, 0, P - 1), axis=2)  # [L,M,S_loc,..]
        for m in range(s.shape[1]):                   # M is static, small
            slot = slots[m]
            local_b = jnp.clip(slot - b_start, 0, B_loc - 1)
            mine = (slot >= b_start) & (slot < b_start + B_loc)
            cur = d[:, local_b]                       # [L, S_loc, H, dh]
            new = jnp.where(valid[None, :, None, None], rows[:, m], cur)
            d = jnp.where(mine, d.at[:, local_b].set(new), d)
        return d

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(Pspec(None, lay.bspec, lay.sspec, lay.hspec, None),
                  Pspec(None, None, None, lay.hspec, None), Pspec()),
        out_specs=Pspec(None, lay.bspec, lay.sspec, lay.hspec, None),
        check_vma=False,
    )(dst, src, slot_ids)


@jax.jit
def ssm_scan(decay, bx, h0):
    from repro.kernels.ssm_scan import ssm_scan_pallas
    return ssm_scan_pallas(decay, bx, h0, interpret=auto_interpret())


@jax.jit
def grouped_ffn(buf, wg, wu, wd):
    from repro.kernels.grouped_ffn import grouped_ffn_pallas
    return grouped_ffn_pallas(buf, wg, wu, wd, interpret=auto_interpret())
