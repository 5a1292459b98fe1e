"""Seeded weights of a dense decoder, made on the device in one jitted call.

Every element is a counter-based hash of (seed, leaf, layer, index), mapped
to a uniform value with the leaf's mean and standard deviation.  Integer
arithmetic makes the values the same on every backend and lets any single
layer be made again alone, bit for bit: the plain reference
(``bench/reference/dense.py``) regenerates one layer at a time and never
reads the arrays handed to the program.

``leaves(cfg)`` lists the leaves of the program's parameter tree for the
dense family (path, per-layer shape, mean, std, dtype, stacked);
``program_params`` builds that tree, ``layer_weights`` one layer of it.
"""
from __future__ import annotations

import zlib
from typing import Dict, List, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

_M1 = np.uint32(0x7FEB352D)
_M2 = np.uint32(0x846CA68B)


class Leaf(NamedTuple):
    path: str                 # "/"-joined keys of the program's tree
    shape: Tuple[int, ...]    # one layer's shape (stacked leaves add [L])
    mean: float
    std: float
    dtype: str
    stacked: bool


def seed_words(seed: int) -> np.ndarray:
    """Two uint32 words from any whole-number seed (beyond 32 bits too)."""
    return np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)


def _mix(x):
    """lowbias32 integer finaliser; uint32 arithmetic wraps."""
    x = x ^ (x >> 16)
    x = x * _M1
    x = x ^ (x >> 15)
    x = x * _M2
    return x ^ (x >> 16)


def leaves(cfg: dict) -> List[Leaf]:
    """The dense family's parameter leaves, as the program lays them out."""
    D, H, Hkv, dh = cfg["d_model"], cfg["num_heads"], cfg["num_kv_heads"], \
        cfg["head_dim"]
    F, V = cfg["d_ff"], cfg["vocab_size"]
    dt = cfg["dtype"]
    out = [Leaf("embed/table", (V, D), 0.0, 0.02, dt, False)]
    if not cfg["tie_embeddings"]:
        out.append(Leaf("lm_head/table", (V, D), 0.0, 0.02, dt, False))
    norms = ["final_norm", "blocks/ln1", "blocks/ln2"]
    if cfg["norm_type"] == "layernorm":
        for n in norms:
            st = n.startswith("blocks")
            out.append(Leaf(f"{n}/scale", (D,), 1.0, 0.1, "float32", st))
            out.append(Leaf(f"{n}/bias", (D,), 0.0, 0.1, "float32", st))
    elif cfg["norm_type"] != "nonparametric":
        raise ValueError(f"norm_type {cfg['norm_type']!r} is not dense-LN")
    out += [
        Leaf("blocks/attn/wq", (D, H, dh), 0.0, D ** -0.5, dt, True),
        Leaf("blocks/attn/wk", (D, Hkv, dh), 0.0, D ** -0.5, dt, True),
        Leaf("blocks/attn/wv", (D, Hkv, dh), 0.0, D ** -0.5, dt, True),
        Leaf("blocks/attn/wo", (H, dh, D), 0.0, (H * dh) ** -0.5, dt, True),
    ]
    if cfg["mlp_type"] == "swiglu":
        out.append(Leaf("blocks/mlp/w_gate", (D, F), 0.0, D ** -0.5, dt, True))
    elif cfg["mlp_type"] != "squared_relu":
        raise ValueError(f"mlp_type {cfg['mlp_type']!r} not supported")
    out += [
        Leaf("blocks/mlp/w_up", (D, F), 0.0, D ** -0.5, dt, True),
        Leaf("blocks/mlp/w_down", (F, D), 0.0, F ** -0.5, dt, True),
    ]
    return out


def _values(words, leaf: Leaf, layer, shape):
    """Element values of ``leaf`` for ``layer`` (a uint32 scalar or an
    array broadcastable against ``shape``'s leading axis)."""
    lid = np.uint32(zlib.crc32(leaf.path.encode()))
    w = jnp.asarray(words).astype(jnp.uint32)
    key = _mix(w[0] ^ _mix(lid ^ _mix(jnp.asarray(layer, jnp.uint32) ^ w[1])))
    idx = jnp.zeros(shape, jnp.uint32)
    stride = 1
    for d in range(len(shape) - 1, len(shape) - len(leaf.shape) - 1, -1):
        idx = idx + jax.lax.broadcasted_iota(jnp.uint32, shape, d) \
            * np.uint32(stride)
        stride *= shape[d]
    h = _mix(_mix(idx ^ key) + key)
    u = (h >> 8).astype(jnp.float32) * np.float32(2.0 ** -24) \
        + np.float32(2.0 ** -25)
    v = np.float32(leaf.mean) + np.float32(leaf.std * np.sqrt(3.0)) \
        * (np.float32(2.0) * u - np.float32(1.0))
    return v.astype(leaf.dtype)


def _set(tree: dict, path: str, value) -> None:
    keys = path.split("/")
    for k in keys[:-1]:
        tree = tree.setdefault(k, {})
    tree[keys[-1]] = value


def _build(words, cfg: dict) -> dict:
    L = cfg["num_layers"]
    tree: Dict = {"final_norm": {}, "blocks": {"ln1": {}, "ln2": {}}}
    for leaf in leaves(cfg):
        if leaf.stacked:
            shape = (L,) + leaf.shape
            layer = jax.lax.broadcasted_iota(
                jnp.uint32, (L,) + (1,) * len(leaf.shape), 0)
        else:
            shape, layer = leaf.shape, jnp.uint32(0)
        _set(tree, leaf.path, _values(words, leaf, layer, shape))
    return tree


def program_params(cfg: dict, seed: int, device=None):
    """The program's whole parameter tree, made in one jitted call on
    ``device`` (default: JAX's default device)."""
    words = seed_words(seed)
    fn = jax.jit(lambda w: _build(w, cfg))
    w = jnp.asarray(words) if device is None else jax.device_put(words, device)
    return jax.block_until_ready(fn(w))


def layer_weights(cfg: dict, seed: int, layer: int) -> dict:
    """One layer's stacked leaves (without the [L] axis), made alone in
    one jitted call."""
    return _layer_jit(cfg)(jnp.asarray(seed_words(seed)), jnp.uint32(layer))


_LAYER_JITS: Dict[tuple, object] = {}


def _layer_jit(cfg: dict):
    ls = tuple(lf for lf in leaves(cfg) if lf.stacked)
    fn = _LAYER_JITS.get(ls)
    if fn is None:
        def one_layer(w, layer):
            out: Dict = {}
            for leaf in ls:
                _set(out, leaf.path[len("blocks/"):],
                     _values(w, leaf, layer, leaf.shape))
            return out
        fn = _LAYER_JITS[ls] = jax.jit(one_layer)
    return fn


def global_weight(cfg: dict, seed: int, path: str):
    """An unstacked leaf (embedding, head or final norm), made alone."""
    leaf = next(lf for lf in leaves(cfg) if lf.path == path)
    return jax.jit(lambda w: _values(w, leaf, jnp.uint32(0), leaf.shape))(
        jnp.asarray(seed_words(seed)))
