"""Seeded weights of a model, made on the device in one jitted call.

Every element is a counter-based hash of (seed, leaf, layer, index), mapped
to a uniform value with the leaf's mean and standard deviation.  Integer
arithmetic makes the values the same on every backend and lets any single
layer be made again alone, bit for bit: a family's plain reference
regenerates one layer at a time and never reads the arrays handed to the
program.

This module holds the hashing only.  Which leaves a tree has comes from
the configuration's family module (``bench/reference/<family>.py``,
``leaves`` and ``empty_subtrees``): each leaf's path, one layer's shape,
mean, std and dtype, and for a stacked leaf the stack it belongs to and
that stack's depth, so stacks of different depths sit in one tree.
``program_params`` builds the whole tree, ``layer_weights`` one layer of
a named stack, ``global_weight`` one unstacked leaf.
"""
from __future__ import annotations

import zlib
from typing import Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference

_M1 = np.uint32(0x7FEB352D)
_M2 = np.uint32(0x846CA68B)


class Leaf(NamedTuple):
    path: str                 # "/"-joined keys of the program's tree
    shape: Tuple[int, ...]    # one layer's shape (stacked leaves add [depth])
    mean: float
    std: float
    dtype: str
    stack: str = ""           # a stacked leaf's stack: a prefix of ``path``
    depth: int = 0            # layers in that stack; 0 for unstacked


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
    fam = reference.family(cfg)
    tree: Dict = {}
    for path in fam.empty_subtrees(cfg):
        _set(tree, path, {})
    for leaf in fam.leaves(cfg):
        if leaf.depth:
            shape = (leaf.depth,) + leaf.shape
            layer = jax.lax.broadcasted_iota(
                jnp.uint32, (leaf.depth,) + (1,) * len(leaf.shape), 0)
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


def layer_weights(cfg: dict, seed: int, layer: int, *, stack: str) -> dict:
    """One layer of the stack ``stack``: its leaves without the [depth]
    axis, keyed by their paths below the stack's prefix, made alone in one
    jitted call."""
    return _layer_jit(cfg, stack)(jnp.asarray(seed_words(seed)),
                                  jnp.uint32(layer))


_LAYER_JITS: Dict[tuple, object] = {}


def _layer_jit(cfg: dict, stack: str):
    ls = tuple(lf for lf in reference.family(cfg).leaves(cfg)
               if lf.depth and lf.stack == stack)
    if not ls:
        raise KeyError(f"no stack {stack!r} in {cfg['name']!r}")
    fn = _LAYER_JITS.get(ls)
    if fn is None:
        def one_layer(w, layer):
            out: Dict = {}
            for leaf in ls:
                _set(out, leaf.path[len(stack) + 1:],
                     _values(w, leaf, layer, leaf.shape))
            return out
        fn = _LAYER_JITS[ls] = jax.jit(one_layer)
    return fn


def global_weight(cfg: dict, seed: int, path: str):
    """An unstacked leaf (embedding, head or final norm), made alone."""
    leaf = next(lf for lf in reference.family(cfg).leaves(cfg)
                if lf.path == path and not lf.depth)
    return jax.jit(lambda w: _values(w, leaf, jnp.uint32(0), leaf.shape))(
        jnp.asarray(seed_words(seed)))
