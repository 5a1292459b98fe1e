"""Production mesh + sharding rules.

``make_production_mesh`` is a FUNCTION (never module-level state) so that
importing this module never touches jax device initialization — required
because the dry-run forces 512 host devices while tests/benches must see 1.

Sharding strategy (DESIGN.md §5):
  * "model" axis: tensor/expert parallel — attention heads, MLP hidden,
    MoE experts, vocab, SSM inner channels.
  * "data" axis: batch AND FSDP-style parameter sharding (a second param
    dim is sharded over "data" so optimizer+param bytes fit per chip).
  * "pod" axis (multi-pod): pure data parallel — and the HeteroEdge
    primary/auxiliary node-group boundary.
"""
from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.sharding import make_mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devs = jax.devices()
    if len(devs) < n:
        raise RuntimeError(
            f"need {n} devices for the production mesh, have {len(devs)} — "
            "the dry-run must set XLA_FLAGS=--xla_force_host_platform_device_count=512 "
            "before any jax import")
    return make_mesh(shape, axes, devs[:n])


def make_smoke_mesh() -> Mesh:
    """1-device mesh with the same axis names (tests on this container)."""
    return make_mesh((1, 1), ("data", "model"))


# ---------------------------------------------------------------------------
# Parameter sharding rules
# ---------------------------------------------------------------------------
# leaf-name -> preferred model-parallel dim (negative = from the end),
# counted on the UNSTACKED tensor (scan adds a leading L dim handled below).
_MODEL_DIM_BY_NAME = {
    "table": 0,        # [V, D]   vocab-parallel embedding / lm head
    "wq": 1,           # [D, H, dh]
    "wk": 1,           # [D, Hkv, dh]
    "wv": 1,
    "wo": 0,           # [H, dh, D]
    "w_gate": -1,      # [D, F] or [E, D, F]
    "w_up": -1,
    "w_down": -2,      # [F, D] or [E, F, D]
    "router": 1,       # [D, E]
    "in_proj": 1,      # [D, 2di]
    "bc_proj": 0,      # [di, 2N]
    "x_proj": 0,       # [di, r+2N]
    "dt_proj": 1,      # [r, di] / [di, H]
    "out_proj": 0,     # [di, D]
    "conv_w": 1,       # [W, di]
    "conv_b": 0,
    "A_log": 0,        # [di, N] / [H]
    "D": 0,            # [di] / [H]
    "dt_bias": 0,
    "frontend_proj": 1,
}
# MoE expert tensors: expert dim is the model-parallel dim instead
_EXPERT_LEAVES = ("w_gate", "w_up", "w_down")


def _path_names(path) -> Tuple[str, ...]:
    return tuple(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)


def param_spec(path, shape: Tuple[int, ...], *, model_size: int,
               data_size: int, stacked: bool, fsdp: bool = True,
               fsdp_axes: Optional[Tuple[Tuple[str, ...], int]] = None) -> P:
    """PartitionSpec for one parameter tensor."""
    names = _path_names(path)
    leaf = names[-1]
    nd = len(shape)
    spec: list = [None] * nd
    offset = 1 if (stacked and nd >= 2) else 0  # leading scan/L dim

    under_moe = "moe" in names
    preferred = None
    if under_moe and leaf in _EXPERT_LEAVES:
        preferred = offset  # expert dim
    elif leaf in _MODEL_DIM_BY_NAME:
        d = _MODEL_DIM_BY_NAME[leaf]
        preferred = d + nd if d < 0 else d + offset

    def ok_model(i):
        return 0 <= i < nd and shape[i] % model_size == 0 and shape[i] >= model_size

    model_dim = None
    if preferred is not None:
        if ok_model(preferred):
            model_dim = preferred
        else:
            # fallback: largest other dim divisible by the model axis
            # (e.g. internvl2's 14 heads can't take a 16-way axis — its
            # d_model=896 can)
            for i in sorted(range(offset, nd), key=lambda j: -shape[j]):
                if ok_model(i):
                    model_dim = i
                    break
    if model_dim is not None:
        spec[model_dim] = "model"

    if fsdp:
        # FSDP: shard one more large dim over the batch axes — ("pod","data")
        # on the multi-pod mesh, so a 235B MoE's params+optimizer fit
        # (§Perf iteration A4); "data" alone on a single pod.
        axes, size = fsdp_axes if fsdp_axes else (("data",), data_size)
        cands = sorted(range(offset, nd), key=lambda i: -shape[i])
        for i in cands:
            if i != model_dim and spec[i] is None \
                    and shape[i] % size == 0 and shape[i] >= 4 * size:
                spec[i] = axes if len(axes) > 1 else axes[0]
                break
    return P(*spec)


def params_shardings(abs_params, mesh: Mesh, *, fsdp: bool = True):
    """NamedSharding pytree for an abstract param tree."""
    model_size = mesh.shape.get("model", 1)
    data_size = mesh.shape.get("data", 1)
    fsdp_ax = tuple(a for a in ("pod", "data") if a in mesh.shape)
    fsdp_axes = (fsdp_ax, int(np.prod([mesh.shape[a] for a in fsdp_ax]))) \
        if fsdp_ax else None

    def one(path, leaf):
        names = _path_names(path)
        stacked = "blocks" in names or "encoder" in names or "backbone" in names
        # the hybrid "shared" block is NOT stacked
        if "shared" in names and "backbone" not in names:
            stacked = False
        spec = param_spec(path, leaf.shape, model_size=model_size,
                          data_size=data_size, stacked=stacked, fsdp=fsdp,
                          fsdp_axes=fsdp_axes)
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(one, abs_params)


# ---------------------------------------------------------------------------
# Batch / cache shardings
# ---------------------------------------------------------------------------
def batch_axes(mesh: Mesh):
    return tuple(a for a in ("pod", "data") if a in mesh.shape)


def data_shardings(abs_batch, mesh: Mesh):
    """Inputs: batch dim over ("pod","data") when divisible, else replicate
    batch and shard the sequence dim (long_500k decode)."""
    baxes = batch_axes(mesh)
    bsize = int(np.prod([mesh.shape[a] for a in baxes]))
    dsize = mesh.shape.get("data", 1)
    model_size = mesh.shape.get("model", 1)

    def one(path, leaf):
        names = _path_names(path)
        shape = leaf.shape
        if not shape:
            return NamedSharding(mesh, P())
        spec: list = [None] * len(shape)
        is_cache = "cache" in names or len(shape) >= 4
        b_dim = 1 if is_cache and len(shape) >= 3 else 0  # caches: [L,B,...]
        b_sharded = False
        if len(shape) > b_dim and shape[b_dim] % bsize == 0 and shape[b_dim] >= bsize:
            spec[b_dim] = baxes if len(baxes) > 1 else baxes[0]
            b_sharded = True
        if len(shape) == 5:
            # KV cache [L,B,S,Hkv,dh]: prefer kv-head dim on "model";
            # else shard the sequence dim (flash-decode style).  If the
            # batch could not shard (long_500k B=1), the sequence dim also
            # absorbs the data axis.
            s_axes = [] if b_sharded else ["data"]
            if shape[3] % model_size == 0 and shape[3] >= model_size:
                spec[3] = "model"
            else:
                s_axes.append("model")
            div = int(np.prod([mesh.shape[a] for a in s_axes])) if s_axes else 1
            if s_axes and shape[2] % div == 0 and shape[2] >= div:
                spec[2] = tuple(s_axes) if len(s_axes) > 1 else s_axes[0]
        elif len(shape) == 4:
            # SSM state [L,B,di,N] / conv state [L,B,W-1,di]: shard the
            # channel dim on "model"
            for i in (2, 3):
                if shape[i] % model_size == 0 and shape[i] >= model_size:
                    spec[i] = "model"
                    break
        elif len(shape) == 3 and not is_cache and not b_sharded:
            # unbatchable [B,S,D] input (long-context frontend): seq on data
            if shape[1] % dsize == 0 and shape[1] >= dsize:
                spec[1] = "data"
        return NamedSharding(mesh, P(*spec))

    return jax.tree_util.tree_map_with_path(one, abs_batch)


def replicated(mesh: Mesh):
    return NamedSharding(mesh, P())
