"""Activation sharding hints (MaxText-style logical constraints).

GSPMD propagates parameter shardings well through plain einsums but loses
them inside lax.scan / lax.map bodies and around reshapes — at train_4k
scale an unsharded [B,S,V] logits tensor alone is ~0.5 TB.  The model code
calls ``constrain(x, "batch", None, "model")`` at the handful of points
that matter; outside a mesh context (CPU tests) it is a no-op.

Logical names:
  "batch" -> all batch axes present in the mesh ("pod","data")
  "data"  -> the data axis only
  "model" -> the model axis (applied only when the dim is divisible)
"""
from __future__ import annotations

from contextlib import contextmanager
from typing import NamedTuple, Optional, Tuple

import jax
import numpy as np
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

_STATE = {"mesh": None}


@contextmanager
def activation_sharding(mesh):
    """Enable constraints for code traced within this context."""
    old = _STATE["mesh"]
    _STATE["mesh"] = mesh
    try:
        yield
    finally:
        _STATE["mesh"] = old


def active_mesh():
    return _STATE["mesh"]


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...], devices=None):
    """THE mesh constructor: every mesh in the program has Auto axes
    (``jax.make_mesh`` defaults to Explicit axes, under which the model's
    ``with_sharding_constraint`` hints in :func:`constrain` are rejected).
    ``devices`` (default: the first ``prod(shape)`` local devices) are laid
    out in the order given."""
    n = int(np.prod(shape))
    devs = list(jax.devices())[:n] if devices is None else list(devices)
    return jax.sharding.Mesh(np.asarray(devs).reshape(tuple(shape)),
                             tuple(axes),
                             axis_types=(AxisType.Auto,) * len(axes))


def scaleout_mesh(devices=None, axes: Tuple[str, ...] = ("data", "model")):
    """Balanced ("data","model") mesh over the local (or given) devices —
    the emulated multi-host harness's mesh constructor
    (``benchmarks/scaleout.py`` / ``tests/test_scaleout.py``).  Axis sizes
    come from the same balanced factorization the OffloadEngine uses for
    node-group sub-meshes, so 8 devices give (4, 2), 64 give (8, 8)."""
    from repro.core.offload import mesh_axis_sizes
    devs = list(jax.devices()) if devices is None else list(devices)
    return make_mesh(mesh_axis_sizes(len(devs), len(axes)), axes, devs)


def replicated_sharding(mesh):
    """The mesh-replicated NamedSharding — the placement contract for the
    serving engine's carried decode-state vectors (cur_tok / lengths /
    remaining / done).  Tiny [slots] vectors are replicated on every
    device so the fused decode loop's input signature never changes
    between dispatches."""
    return NamedSharding(mesh, P())


def put_replicated(tree, mesh=None):
    """Commit every leaf of ``tree`` to ``mesh`` (default: the active
    mesh) with a replicated sharding — the STICKY initial placement for
    carried decode state.  Freshly created host-side arrays are otherwise
    committed to a single device on first use, so the first fused decode
    dispatch would see a different input sharding than every later one
    (whose carried inputs come back mesh-attached from the previous
    dispatch) and re-trace/re-shard at the steady-state boundary.  A
    no-op off-mesh."""
    mesh = mesh if mesh is not None else _STATE["mesh"]
    if mesh is None:
        return tree
    s = replicated_sharding(mesh)
    return jax.tree.map(lambda x: jax.device_put(x, s), tree)


class SeqShardLayout(NamedTuple):
    """How a [B, S, Hkv, dh] KV-cache leaf lays out on a model-sharded mesh.

    ``bspec``/``sspec``/``hspec`` are the PartitionSpec entries for the
    batch, sequence and kv-head dims; ``s_axes`` are the mesh axes the
    sequence dim shards over and ``s_local`` is the per-shard sequence
    length.  Shared by the scalar and per-slot ``cache_update`` shard_map
    paths so both agree byte-for-byte on the cache layout."""
    bspec: object
    sspec: object
    hspec: Optional[str]
    s_axes: Tuple[str, ...]
    s_local: int


def seq_shard_layout(mesh, B: int, S: int, Hkv: int) -> Optional[SeqShardLayout]:
    """Resolve the KV-cache layout for ``mesh``, or None when the sequence
    dim ends up unsharded (a dynamic-index update is already shard-local).

    Batch axes ("pod"/"data") shard the batch dim when it divides; otherwise
    they spill onto the sequence dim.  The kv-head dim takes "model" when it
    divides, else "model" also shards the sequence — the case the shard_map
    update path exists for."""
    msize = mesh.shape["model"]
    baxes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    bdiv = int(np.prod([mesh.shape[a] for a in baxes])) if baxes else 1
    b_sharded = bool(baxes) and B % bdiv == 0 and B >= bdiv
    s_axes = [] if b_sharded else list(baxes)
    if Hkv % msize != 0 or Hkv < msize:
        s_axes.append("model")
    sdiv = int(np.prod([mesh.shape[a] for a in s_axes])) if s_axes else 1
    if not s_axes or S % sdiv != 0 or S < sdiv:
        return None
    bspec = (baxes if len(baxes) > 1 else baxes[0]) if b_sharded else None
    sspec = tuple(s_axes) if len(s_axes) > 1 else s_axes[0]
    hspec = "model" if (Hkv % msize == 0 and Hkv >= msize) else None
    return SeqShardLayout(bspec, sspec, hspec, tuple(s_axes), S // sdiv)


def constrain(x, *logical):
    """Apply a sharding constraint described by logical axis names."""
    mesh = _STATE["mesh"]
    if mesh is None:
        return x
    assert len(logical) == x.ndim, (logical, x.shape)
    spec = []
    for dim, name in zip(x.shape, logical):
        if name is None:
            spec.append(None)
            continue
        if name == "batch":
            axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
        elif name == "data":
            axes = ("data",) if "data" in mesh.shape else ()
        elif name == "model":
            axes = ("model",) if "model" in mesh.shape else ()
        else:
            raise ValueError(name)
        div = int(np.prod([mesh.shape[a] for a in axes])) if axes else 1
        if axes and dim % div == 0 and dim >= div:
            spec.append(axes if len(axes) > 1 else axes[0])
        else:
            spec.append(None)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*spec)))
