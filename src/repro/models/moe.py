"""Mixture-of-Experts layer: top-k router + capacity-bounded sort dispatch.

Dispatch is sort-based (argsort tokens by expert id, scatter into a
[E, capacity, D] buffer) rather than one-hot einsum — the one-hot dispatch
mask would be O(T·E·C) which is infeasible at T = 1M tokens / 128 experts.
Expert weights live on the ``model`` mesh axis (expert parallelism); XLA
inserts the all-to-all when resharding token-sharded activations into the
expert-sharded buffer.

Returns the layer output plus the router aux (load-balance) loss term of
Shazeer et al. / Switch.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.layers import mlp_init, mlp_apply
from repro.models.sharding import constrain


def moe_init(key, cfg, dtype):
    d, f, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    kr, ke, ks = jax.random.split(key, 3)
    s_in, s_out = 1.0 / np.sqrt(d), 1.0 / np.sqrt(f)
    ekeys = jax.random.split(ke, 3)
    p = {
        "router": (jax.random.normal(kr, (d, e)) * s_in).astype(jnp.float32),
        # stacked expert weights: [E, ...] (SwiGLU experts)
        "w_gate": (jax.random.normal(ekeys[0], (e, d, f)) * s_in).astype(dtype),
        "w_up":   (jax.random.normal(ekeys[1], (e, d, f)) * s_in).astype(dtype),
        "w_down": (jax.random.normal(ekeys[2], (e, f, d)) * s_out).astype(dtype),
    }
    if cfg.num_shared_experts:
        p["shared"] = mlp_init(ks, cfg, d, f * cfg.num_shared_experts, dtype)
    return p


def _capacity(tokens: int, cfg) -> int:
    c = int(tokens * cfg.experts_per_token * cfg.moe_capacity_factor
            / cfg.num_experts)
    return max(8, -(-c // 8) * 8)  # round up to 8


def moe_apply(params, x, cfg):
    """x: [B,S,D] -> (y, aux_loss).

    Two dispatch paths:
    * global-index scatter (below) — reference semantics, used on CPU/tests;
    * ``_moe_shardmap`` — the expert-parallel production path (§Perf
      iterations A1/B1): tokens stay on their data shard, every model
      column owns E/model_size experts and dispatches LOCALLY (the tokens
      are already replicated across the model axis, as for any TP layer),
      so the only collective is one psum of the [B_loc,S,D] output.  The
      global-scatter path instead makes GSPMD move O(T·k·D) bytes per
      layer across the mesh.
    """
    from repro.models.sharding import active_mesh
    mesh = active_mesh()
    if mesh is not None and "model" in mesh.shape:
        msize = mesh.shape["model"]
        if cfg.num_experts % msize == 0 and cfg.num_experts >= msize:
            return _moe_shardmap(params, x, cfg, mesh)          # expert-parallel
        if cfg.d_ff % msize == 0 and cfg.d_ff >= msize:
            return _moe_shardmap(params, x, cfg, mesh,
                                 f_parallel=True)               # TP-within-expert
    return _moe_global(params, x, cfg)


def _local_dispatch_ffn(xt, logits, wg, wu, wd, cfg, e0, E_loc, C_loc):
    """Sort-based dispatch + expert FFN over a LOCAL expert range.
    xt: [T,D]; logits: [T,E] (global); returns y_partial [T,D] containing
    only the contributions of experts [e0, e0+E_loc)."""
    T, D = xt.shape
    K = cfg.experts_per_token
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, K)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    rel = expert_ids.reshape(-1) - e0                      # [T*K]
    mine = (rel >= 0) & (rel < E_loc)
    bins = jnp.where(mine, rel, E_loc)
    sort_idx = jnp.argsort(bins)
    sorted_bins = bins[sort_idx]
    counts = jnp.bincount(bins, length=E_loc + 1)
    offsets = jnp.concatenate([jnp.zeros((1,), counts.dtype),
                               jnp.cumsum(counts)[:-1]])
    pos = jnp.arange(T * K) - offsets[sorted_bins]
    keep = (pos < C_loc) & (sorted_bins < E_loc)
    src_token = sort_idx // K

    buf = jnp.zeros((E_loc, C_loc, D), xt.dtype)
    buf = buf.at[jnp.where(keep, sorted_bins, E_loc),
                 jnp.where(keep, pos, 0)].add(
        jnp.where(keep[:, None], xt[src_token], 0).astype(xt.dtype),
        mode="drop")

    g = jnp.einsum("ecd,edf->ecf", buf, wg)
    u = jnp.einsum("ecd,edf->ecf", buf, wu)
    h = jax.nn.silu(g) * u
    out_buf = jnp.einsum("ecf,efd->ecd", h, wd)

    gathered = out_buf[jnp.where(keep, sorted_bins, 0),
                       jnp.where(keep, pos, 0)]
    # combine with ONE [T,D] scatter-add (gate-weighted, accumulating the K
    # slots directly) instead of unsort-to-[T·K,D] + reshape-sum — one less
    # [T·K,D] buffer and HBM pass
    w = gate_vals.reshape(T * K)[sort_idx][:, None].astype(xt.dtype)
    contrib = jnp.where(keep[:, None], gathered * w, 0)
    return jnp.zeros((T, D), xt.dtype).at[src_token].add(contrib)


def _moe_shardmap(params, x, cfg, mesh, *, f_parallel: bool = False):
    """Production MoE.  Two layouts behind one psum:

    * expert-parallel (E >= model axis): each model column owns E/msize
      experts, dispatches its (replicated) tokens locally; psum("model")
      merges the per-expert partial outputs.
    * f_parallel (E < model axis, e.g. mixtral's 8 experts on a 16-wide
      axis): every column holds ALL experts but only a 1/msize slice of
      each expert's hidden width (Megatron TP inside the expert); the same
      psum then merges the partial down-projections.
    """
    B, S, D = x.shape
    E, K = cfg.num_experts, cfg.experts_per_token
    msize = mesh.shape["model"]
    E_loc = E if f_parallel else E // msize
    baxes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    bdiv = int(np.prod([mesh.shape[a] for a in baxes]))
    bspec = (baxes if len(baxes) > 1 else baxes[0]) \
        if (B % bdiv == 0 and B >= bdiv) else None
    T_loc = (B // (bdiv if bspec else 1)) * S
    C_loc = _capacity(T_loc, cfg)

    from jax.sharding import PartitionSpec as P

    def body(router, wg, wu, wd, xblk):
        Bl, Sl, _ = xblk.shape
        xt = xblk.reshape(Bl * Sl, D)
        logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), router)
        probs = jax.nn.softmax(logits, axis=-1)
        # aux load-balance loss (identical on every model column)
        _, top1 = jax.lax.top_k(probs, 1)
        density = jnp.mean(jax.nn.one_hot(top1[:, 0], E, dtype=jnp.float32), 0)
        aux = cfg.router_aux_loss * E * jnp.sum(density * jnp.mean(probs, 0))
        if bspec:
            aux = jax.lax.pmean(aux, baxes if len(baxes) > 1 else baxes[0])

        e0 = jnp.int32(0) if f_parallel \
            else jax.lax.axis_index("model") * E_loc
        y_part = _local_dispatch_ffn(xt, logits, wg, wu, wd,
                                     cfg, e0, E_loc, C_loc)
        y = jax.lax.psum(y_part, "model")
        return y.reshape(Bl, Sl, D), aux[None]

    if f_parallel:
        w_specs = (P(None, None, "model"), P(None, None, "model"),
                   P(None, "model", None))
    else:
        w_specs = (P("model"), P("model"), P("model"))
    y, aux = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), *w_specs, P(bspec, None, None)),
        out_specs=(P(bspec, None, None), P()),
        check_vma=False,
    )(params["router"], params["w_gate"], params["w_up"],
      params["w_down"], x)
    aux = aux[0]
    xt_all = x.reshape(B * S, D)
    if "shared" in params:
        y = y + mlp_apply(params["shared"], xt_all, cfg).astype(x.dtype).reshape(B, S, D)
    return y, aux


def _moe_global(params, x, cfg):
    """Reference dispatch with global indices (CPU/tests)."""
    B, S, D = x.shape
    E, K = cfg.num_experts, cfg.experts_per_token
    T = B * S
    C = _capacity(T, cfg)
    xt = x.reshape(T, D)

    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), params["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, K)        # [T,K]
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    # ---- aux load-balance loss (Switch-style) ----
    density = jnp.mean(jax.nn.one_hot(expert_ids[:, 0], E, dtype=jnp.float32), axis=0)
    router_prob = jnp.mean(probs, axis=0)
    aux = cfg.router_aux_loss * E * jnp.sum(density * router_prob)

    # ---- sort-based dispatch ----
    flat_ids = expert_ids.reshape(-1)                      # [T*K]
    sort_idx = jnp.argsort(flat_ids)                       # [T*K]
    sorted_ids = flat_ids[sort_idx]
    counts = jnp.bincount(flat_ids, length=E)
    offsets = jnp.concatenate([jnp.zeros((1,), counts.dtype),
                               jnp.cumsum(counts)[:-1]])
    pos = jnp.arange(T * K) - offsets[sorted_ids]          # slot within expert
    keep = pos < C
    src_token = sort_idx // K                              # originating token

    buf = jnp.zeros((E, C, D), x.dtype)
    buf = buf.at[sorted_ids, jnp.where(keep, pos, 0)].add(
        jnp.where(keep[:, None], xt[src_token], 0).astype(x.dtype),
        mode="drop")
    # expert-parallel: the scatter above IS the all-to-all when tokens are
    # batch-sharded and the buffer is expert-sharded
    buf = constrain(buf, "model", "data", None)

    # ---- expert FFN (batched over E; E is expert-parallel) ----
    g = jnp.einsum("ecd,edf->ecf", buf, params["w_gate"])
    u = jnp.einsum("ecd,edf->ecf", buf, params["w_up"])
    h = jax.nn.silu(g) * u
    out_buf = constrain(jnp.einsum("ecf,efd->ecd", h, params["w_down"]),
                        "model", "data", None)

    # ---- combine: gather back, weight, unsort, sum over K ----
    gathered = out_buf[sorted_ids, jnp.where(keep, pos, 0)]
    gathered = jnp.where(keep[:, None], gathered, 0)
    unsorted = jnp.zeros((T * K, D), x.dtype).at[sort_idx].set(gathered)
    w = gate_vals.reshape(T * K)[:, None].astype(x.dtype)
    y = (unsorted * w).reshape(T, K, D).sum(axis=1)

    if "shared" in params:
        y = y + mlp_apply(params["shared"], xt, cfg).astype(x.dtype)
    return y.reshape(B, S, D), aux
