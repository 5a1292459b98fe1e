"""Compile the main path's kernels and decode program for a described
v5e chip (nothing runs): what the TPU compiler refuses fails here, at
no chip time.  Interpret mode cannot see Mosaic's tiling rules.

The topology is described inside a module-scoped fixture — never while
a module is imported — because only one process at a time may load the
TPU library; every worker then collects the same tests and only the one
given this file loads it.  The persistent compilation cache is off
around these compiles: a TPU executable written here cannot be read
back without a chip.
"""
import dataclasses
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import get_config
from repro.kernels import ops
from repro.kernels.decode_attention import decode_attention_pallas
from repro.kernels.masked_compact import masked_compact_pallas
from repro.models import model as M
from repro.serving.engine import make_decode_loop

HBM_BYTES = 16 * 1024**3        # one v5e chip
SLOTS, PROMPT_LEN, MAX_NEW = 4, 128, 32
SMOKE_MAX_LEN = PROMPT_LEN + MAX_NEW + 8     # chip_smoke.py's cache length


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


@pytest.mark.parametrize("arch", ["olmo-1b", "llama3.2-1b"])
@pytest.mark.parametrize("S", [SMOKE_MAX_LEN, 1000])
def test_decode_attention_compiles(one_chip, arch, S):
    """The decode kernel at published head geometry (olmo: 16x128 MHA,
    llama: 8 kv heads x 64 under GQA), on the smoke's cache length and
    on one whose last block is ragged."""
    cfg = get_config(arch)
    H, Hkv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    bf = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.bfloat16,
                           sharding=one_chip)
    fn = jax.jit(functools.partial(decode_attention_pallas, interpret=False))
    compiled = fn.lower(
        bf((SLOTS, 1, H, dh)), bf((SLOTS, S, Hkv, dh)),
        bf((SLOTS, S, Hkv, dh)),
        jax.ShapeDtypeStruct((SLOTS,), jnp.int32, sharding=one_chip)
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_masked_compact_compiles(one_chip):
    """The KV-hop compaction at olmo-1b's hop shape: 16 layers of 128
    tail rows x (16 kv heads x 128)."""
    fn = jax.jit(functools.partial(masked_compact_pallas, capacity=128,
                                   interpret=False))
    compiled = fn.lower(
        jax.ShapeDtypeStruct((16, 128, 2048), jnp.bfloat16,
                             sharding=one_chip),
        jax.ShapeDtypeStruct((16, 128), jnp.bool_, sharding=one_chip)
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_decode_loop_fits_one_chip(one_chip, monkeypatch):
    """chip_smoke.py's fused decode program (olmo-1b, bf16, 4 slots,
    K=8) holds the Pallas kernel and fits one chip's HBM."""
    # this process's backend is the CPU, whose branch would pick
    # interpret mode: steer the kernel wrapper to Mosaic here
    monkeypatch.setattr(ops, "auto_interpret", lambda: False)
    cfg = get_config("olmo-1b")
    params = _sds(jax.eval_shape(lambda k: M.init_params(cfg, k),
                                 jax.random.PRNGKey(0)), one_chip)
    cache = _sds(jax.eval_shape(
        lambda: M.init_cache(cfg, SLOTS, SMOKE_MAX_LEN)), one_chip)
    vec = [jax.ShapeDtypeStruct((SLOTS,), dt, sharding=one_chip)
           for dt in (jnp.int32, jnp.int32, jnp.int32, jnp.bool_)]
    loop = jax.jit(make_decode_loop(cfg, macro_steps=8, use_pallas=True),
                   donate_argnums=(1, 2, 3, 4, 5))
    compiled = loop.lower(params, cache, *vec).compile()
    assert "tpu_custom_call" in compiled.as_text()
    ma = compiled.memory_analysis()
    used = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert 0 < used < HBM_BYTES, used


def _squeezed(dims: str):
    return tuple(int(d) for d in dims.split(",") if d and d != "1")


def _cache_moves(hlo: str, shapes):
    """The instructions of the optimised ``hlo`` that move a whole array
    of one of ``shapes`` (unit dims ignored): a ``copy`` or
    ``dynamic-slice`` by its result, a ``dynamic-update-slice`` by the
    update it writes.  Writing one row into the stacked cache in place
    moves a row, and is not counted."""
    ident = r"%[\w.\-]+"
    defs = dict(re.findall(rf"({ident}) = \w+\[([\d,]*)\]", hlo))
    inst = re.compile(rf"({ident}) = \w+\[([\d,]*)\]\S* "
                      rf"(copy|dynamic-slice|dynamic-update-slice)"
                      rf"\(({ident})(?:, ({ident}))?")
    moves = []
    for line in hlo.splitlines():
        m = inst.search(line)
        if m is None:
            continue
        _, dims, op, _, update = m.groups()
        if op == "dynamic-update-slice":
            dims = defs.get(update, "")
        if _squeezed(dims) in shapes:
            moves.append(line.strip()[:160])
    return moves


@pytest.mark.parametrize("arch,layers,slots,max_len", [
    ("olmo-1b", None, SLOTS, SMOKE_MAX_LEN),     # chip_smoke.py's program
    ("nemotron-4-15b", 8, 8, 2120),              # nemotron15b-rag's
])
def test_fused_decode_loop_updates_cache_in_place(one_chip, monkeypatch,
                                                  arch, layers, slots,
                                                  max_len):
    """The fused decode loop neither copies the stacked KV cache nor
    slices a layer out of it and stacks it back: each step writes its
    rows into the donated cache and the kernel reads each layer where it
    lies.  So no temp buffer is cache-sized either: beyond the attention
    projections' weights, which XLA relays out once per launch outside
    the step loop, the temps are smaller than one layer of the cache."""
    monkeypatch.setattr(ops, "auto_interpret", lambda: False)
    cfg = get_config(arch)
    if layers:
        cfg = dataclasses.replace(cfg, num_layers=layers)
    params = _sds(jax.eval_shape(lambda k: M.init_params(cfg, k),
                                 jax.random.PRNGKey(0)), one_chip)
    cache = _sds(jax.eval_shape(
        lambda: M.init_cache(cfg, slots, max_len)), one_chip)
    vec = [jax.ShapeDtypeStruct((slots,), dt, sharding=one_chip)
           for dt in (jnp.int32, jnp.int32, jnp.int32, jnp.bool_)]
    loop = jax.jit(make_decode_loop(cfg, macro_steps=8, use_pallas=True),
                   donate_argnums=(1, 2, 3, 4, 5))
    compiled = loop.lower(params, cache, *vec).compile()
    stacked = cache["self"]["k"]
    assert _cache_moves(compiled.as_text(), {
        _squeezed(",".join(map(str, stacked.shape))),
        _squeezed(",".join(map(str, stacked.shape[1:])))}) == []
    layer_bytes = stacked.size // stacked.shape[0] * stacked.dtype.itemsize
    attn = params["blocks"]["attn"]
    relaid = sum(attn[w].size * attn[w].dtype.itemsize
                 for w in ("wq", "wk", "wv"))
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp - relaid < layer_bytes, (temp, relaid, layer_bytes)
