"""Every cell's programs compiled for a described v5e chip (nothing runs):
the B=1 prefill at the mix's prompt length, the fused decode loop with
the Pallas kernel at the mix's slots and cache length, and the admission
boundary.  Each must compile and fit one chip's HBM beside the weights.

    PYTHONPATH=src python -m pytest bench/tests/test_aot.py -s

The topology is described inside a fixture (one process at a time may load
the TPU library); the persistent compilation cache is off around these
compiles, since a TPU executable written here cannot be read back.
"""
import functools
import json
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from bench import run as R
from bench import traffic as TR

ROOT = R.ROOT


def _cells():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    return [w["name"] for w in bench["workloads"] if w["chips"] == 1]


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
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


def _bytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def _used(compiled) -> int:
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)


@pytest.mark.parametrize("workload", _cells())
def test_cell_programs_fit_one_chip(one_chip, workload, monkeypatch):
    from repro.kernels import ops
    from repro.models import model as M
    from repro.serving.engine import (admit_boundary, make_decode_loop,
                                      make_prefill_step)
    monkeypatch.setattr(ops, "auto_interpret", lambda: False)
    cell = R.load_cell(workload)
    spec, mix = cell["spec"], cell["mix"]
    cfg = R.model_config(spec)
    slots = mix["serving"]["slots"]
    P, S = int(mix["prompt_len"]), TR.cache_len(mix)
    K = mix["serving"]["macro_steps"]
    hbm = json.load(open(os.path.join(ROOT, "bench", "peaks.json")))[
        "chips"]["TPU v5 lite"]["hbm_bytes"]
    params = _sds(jax.eval_shape(lambda k: M.init_params(cfg, k),
                                 jax.random.PRNGKey(0)), one_chip)
    cache = _sds(jax.eval_shape(lambda: M.init_cache(cfg, slots, S)),
                 one_chip)
    i32 = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    vec = [i32((slots,), dt) for dt in
           (jnp.int32, jnp.int32, jnp.int32, jnp.bool_)]
    pre = jax.jit(make_prefill_step(cfg)).lower(
        params, {"tokens": i32((1, P), jnp.int32)}).compile()
    loop = jax.jit(make_decode_loop(cfg, macro_steps=K, use_pallas=True),
                   donate_argnums=(1, 2, 3, 4, 5)
                   ).lower(params, cache, *vec).compile()
    assert "tpu_custom_call" in loop.as_text()
    block = _sds(jax.eval_shape(
        lambda p, b: make_prefill_step(cfg)(p, b)[1], params,
        {"tokens": i32((1, P), jnp.int32)}), one_chip)
    adm = jax.jit(functools.partial(admit_boundary, cfg),
                  static_argnames=("eos_id",),
                  donate_argnums=(0, 3, 4, 5, 6)).lower(
        cache, (block,) * slots, i32((slots,), jnp.int32), *vec,
        i32((slots, cfg.vocab_size), jnp.dtype(cfg.dtype)),
        i32((slots,), jnp.int32), i32((slots,), jnp.int32),
        eos_id=-1).compile()
    w, c = _bytes(params), _bytes(cache)
    blocks = slots * _bytes(block)
    report = {"weights": w, "cache": c, "shadow_blocks": blocks,
              "prefill": _used(pre), "decode_loop": _used(loop),
              "admit_boundary": _used(adm)}
    print(f"\n{workload}: " + json.dumps(report))
    # each program with what lives beside it while it runs: the weights,
    # the run's cache and the parked shadow prefills
    beside = {"prefill": c + blocks, "decode_loop": blocks,
              "admit_boundary": w}
    for name, other in beside.items():
        assert report[name] + other < hbm, (name, report)
