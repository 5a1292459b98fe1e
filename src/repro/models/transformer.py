"""Decoder / encoder-decoder / SSM / hybrid stacks.

Layers are homogeneous per stack and scanned with ``jax.lax.scan`` over
stacked parameters — the HLO stays O(1) in depth, which is what makes the
94-layer MoE and 64-layer Mamba configs compilable on this 1-core container
and keeps the compiled program small on real pods.

The hybrid (zamba2) stack scans blocks of ``hybrid_attn_every`` Mamba layers
with the weight-SHARED attention block applied between blocks; since the
shared weights are scan-invariant they are captured as constants of the
outer scan body.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import mlp_apply, mlp_init, norm_apply, norm_init
from repro.models.sharding import active_mesh, seq_shard_layout


# ---------------------------------------------------------------------------
# Per-layer init
# ---------------------------------------------------------------------------
def init_block(key, cfg, dtype, kind: str) -> Dict[str, Any]:
    ks = jax.random.split(key, 6)
    d = cfg.d_model
    if kind == "ssm":
        return {"ln1": norm_init(cfg, d), "mamba": ssm_mod.mamba_init(ks[0], cfg, dtype)}
    if kind == "moe":
        return {"ln1": norm_init(cfg, d), "attn": attn.attn_init(ks[0], cfg, dtype),
                "ln2": norm_init(cfg, d), "moe": moe_mod.moe_init(ks[1], cfg, dtype)}
    if kind == "dense":
        return {"ln1": norm_init(cfg, d), "attn": attn.attn_init(ks[0], cfg, dtype),
                "ln2": norm_init(cfg, d), "mlp": mlp_init(ks[1], cfg, d, cfg.d_ff, dtype)}
    if kind == "encoder":  # non-causal dense
        return init_block(key, cfg, dtype, "dense")
    if kind == "decoder_x":  # self-attn + cross-attn + mlp
        return {"ln1": norm_init(cfg, d), "attn": attn.attn_init(ks[0], cfg, dtype),
                "lnx": norm_init(cfg, d), "xattn": attn.attn_init(ks[1], cfg, dtype, cross=True),
                "ln2": norm_init(cfg, d), "mlp": mlp_init(ks[2], cfg, d, cfg.d_ff, dtype)}
    raise ValueError(kind)


def init_stack(key, cfg, dtype, kind: str, n_layers: int):
    keys = jax.random.split(key, n_layers)
    return jax.vmap(lambda k: init_block(k, cfg, dtype, kind))(keys)


# ---------------------------------------------------------------------------
# Per-layer apply  (returns (x, cache_out, aux))
# ---------------------------------------------------------------------------
def block_apply(params, x, cfg, *, kind: str, mode: str, positions,
                cache=None, cache_index=None, enc_out=None, enc_positions=None,
                causal: bool = True, use_pallas: bool = False, layer=None):
    """``layer`` (decode only): ``cache`` is the whole layer-stacked
    cache and this block reads and writes its layer ``layer`` in place
    (see :func:`decode_inplace`)."""
    aux = jnp.zeros((), jnp.float32)
    if kind == "ssm":
        assert mode != "resume", "SSM states fold the whole prefix; resume is attention-only"
        h = norm_apply(params["ln1"], x, cfg)
        y, new_state = ssm_mod.mamba_apply(
            params["mamba"], h, cfg,
            state=cache, mode="full" if mode != "decode" else "decode")
        return x + y, new_state, aux

    # --- attention sublayer ---
    h = norm_apply(params["ln1"], x, cfg)
    if mode == "decode":
        y, new_kv = attn.attn_apply(params["attn"], h, cfg, positions=positions,
                                    mode="decode", cache=cache["self"],
                                    cache_index=cache_index, use_pallas=use_pallas,
                                    layer=layer)
    else:
        # mode "resume": x holds only the tail rows; cache["self"] holds the
        # cached prefix K/V whose rows the tail attends over. The returned
        # cache is the full-length concatenation (cold-prefill layout).
        prefix = (cache["self"]["k"], cache["self"]["v"]) if mode == "resume" else None
        y, kv = attn.attn_apply(params["attn"], h, cfg, positions=positions,
                                mode="full", causal=causal, prefix_kv=prefix)
        new_kv = {"k": kv[0], "v": kv[1]}
    x = x + y

    # --- cross-attention sublayer (audio decoder) ---
    new_cache: Dict[str, Any] = {"self": new_kv}
    if kind == "decoder_x":
        h = norm_apply(params["lnx"], x, cfg)
        if mode == "decode":
            y, _ = attn.attn_apply(params["xattn"], h, cfg, positions=positions,
                                   mode="decode", cache=cache["cross"],
                                   cache_index=None, kv_x=jnp.zeros_like(h))
            new_cache["cross"] = cache["cross"]
        else:
            y, xkv = attn.attn_apply(params["xattn"], h, cfg, positions=positions,
                                     mode="full", kv_x=enc_out,
                                     kv_positions=enc_positions)
            new_cache["cross"] = {"k": xkv[0], "v": xkv[1]}
        x = x + y

    # --- FFN sublayer ---
    h = norm_apply(params["ln2"], x, cfg)
    if kind == "moe":
        y, aux = moe_mod.moe_apply(params["moe"], h, cfg)
    else:
        y = mlp_apply(params["mlp"], h, cfg)
    return x + y, new_cache, aux


# ---------------------------------------------------------------------------
# Stack apply via lax.scan over layers
# ---------------------------------------------------------------------------
def decode_inplace(caches, mesh=None) -> bool:
    """Whether a decode step updates ``caches`` in place: a plain
    layer-stacked ``{"self": {"k", "v"}}`` cache whose sequence dim is
    local on ``mesh`` (``seq_shard_layout`` resolves none).  The layer
    scan then carries the whole cache and each layer writes its new rows
    into it and reads its layer where it lies, so a decode step moves no
    cache bytes but the rows it writes and the keys and values it reads.
    Int8 caches (scales beside K/V), the hybrid and cross-attention
    caches, SSM states and the sequence-sharded layout keep the per-layer
    slices of the scan's ``xs``/``ys``."""
    if not (isinstance(caches, dict) and set(caches) == {"self"}
            and isinstance(caches["self"], dict)
            and set(caches["self"]) == {"k", "v"}):
        return False
    if mesh is None or "model" not in mesh.shape:
        return True
    _, B, S, Hkv, _ = caches["self"]["k"].shape
    return seq_shard_layout(mesh, B, S, Hkv) is None


def stack_apply(stacked, x, cfg, *, kind: str, mode: str, positions,
                caches=None, cache_index=None, enc_out=None, enc_positions=None,
                causal: bool = True, remat: bool = False, use_pallas: bool = False):
    """caches: pytree stacked on leading L axis (or None).
    Returns (x, new_caches_or_None, aux_sum)."""
    if mode == "decode" and decode_inplace(caches, active_mesh()):
        def step(carry, layer_in):
            xc, aux, cache = carry
            lp, l = layer_in
            y, cache, a = block_apply(
                lp, xc, cfg, kind=kind, mode=mode, positions=positions,
                cache=cache, cache_index=cache_index, use_pallas=use_pallas,
                layer=l)
            return (y, aux + a, cache), None

        L = jax.tree.leaves(stacked)[0].shape[0]
        (x, aux, caches), _ = jax.lax.scan(
            step, (x, jnp.zeros((), jnp.float32), caches),
            (stacked, jnp.arange(L, dtype=jnp.int32)))
        return x, caches, aux

    collect = caches is not None or mode == "prefill"

    def body(carry, layer_in):
        xc, aux = carry
        lp, lcache = layer_in
        y, new_cache, a = block_apply(
            lp, xc, cfg, kind=kind, mode=mode, positions=positions,
            cache=lcache, cache_index=cache_index, enc_out=enc_out,
            enc_positions=enc_positions, causal=causal, use_pallas=use_pallas)
        return (y, aux + a), (new_cache if collect else None)

    if remat:
        body = jax.checkpoint(body)
    (x, aux), new_caches = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                                        (stacked, caches))
    return x, new_caches, aux


def hybrid_apply(params, x, cfg, *, mode: str, positions, caches=None,
                 cache_index=None, remat: bool = False, use_pallas: bool = False):
    """Zamba2-style: nb blocks of k Mamba layers + shared attention block.

    params: {"backbone": stacked [L,...], "shared": dense block params}.
    caches: None or {"backbone": [L-stacked mamba states], "shared": [nb-stacked kv]}.
    """
    k = cfg.hybrid_attn_every
    L = cfg.num_layers
    nb = L // k
    backbone = jax.tree.map(lambda a: a.reshape(nb, k, *a.shape[1:]),
                            params["backbone"])
    shared = params["shared"]
    collect = caches is not None or mode == "prefill"
    bb_caches = None if caches is None else jax.tree.map(
        lambda a: a.reshape(nb, k, *a.shape[1:]), caches["backbone"])
    sh_caches = None if caches is None else caches["shared"]

    def outer(carry, layer_in):
        xc, aux = carry
        bp, bc, sc = layer_in
        xc, bc_new, a1 = stack_apply(
            bp, xc, cfg, kind="ssm", mode=mode, positions=positions,
            caches=bc, cache_index=cache_index, remat=remat)
        xc, sc_new, a2 = block_apply(
            shared, xc, cfg, kind="dense", mode=mode, positions=positions,
            cache=sc, cache_index=cache_index, use_pallas=use_pallas)
        return (xc, aux + a1 + a2), ((bc_new, sc_new) if collect else None)

    if remat:
        outer = jax.checkpoint(outer)
    (x, aux), ys = jax.lax.scan(outer, (x, jnp.zeros((), jnp.float32)),
                                (backbone, bb_caches, sh_caches))
    new_caches = None
    if collect:
        bb_new, sh_new = ys
        new_caches = {
            "backbone": jax.tree.map(
                lambda a: a.reshape(L, *a.shape[2:]), bb_new),
            "shared": sh_new,
        }
    return x, new_caches, aux
