#!/usr/bin/env python3
"""Smoke run of the serving path on a TPU: proof that the system starts.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # four chips of one host

One chip: olmo-1b at its published widths (random bf16 weights from a
seed) is served through the stack users run — ``ServingFrontend`` →
``HeteroRuntime`` (pair topology, both groups on the chip) → one
``ContinuousServingEngine`` per group, fused macro-step decode with the
Pallas decode kernel compiled by Mosaic.  Before serving, the compiled
kernel is checked against ``decode_attention_ref`` at the model's cache
shapes, and one decode step's logits with the kernel against the same
step through XLA; after serving, the fused decode program is checked to
contain the kernel (``tpu_custom_call``).

``--chips 4`` runs only the paper's deployment as the chips of one host:
a star of hub + 3 spokes, one chip each, under a fixed split.  It checks
that each group's params, KV cache and decode state live on its own chip,
and that every token stream equals the one served by the same star with
all four groups on one chip (same groups, slots and batch partners, so
the programs and their inputs are identical and the streams must match
exactly).

Lines before the last are smoke facts, not benchmark metrics.  The last
line of stdout is ``{"ok": true, "device": {...}}``.  Any failed check,
or a platform other than ``tpu``, exits non-zero without that line.
Everything runs in this one process, which holds the chips.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

ARCH = "olmo-1b"
SEED = 0
SLOTS = 4
PROMPT_LEN = 128
MAX_NEW = 32
MACRO_STEPS = 8
N_REQUESTS = 8            # one-chip phase
N_REQUESTS_STAR = 16      # four-chip phase: 4 per group under the split
# Stated bounds.  The kernel and the reference read the same bf16 cache
# and accumulate in f32; the kernel's bf16 output is compared element-wise
# with atol = rtol = KERNEL_TOL (a few bf16 ulps at |x| ~ 1).  The decode
# step's logits are compared by relative L2 norm: bf16 rounding of the
# attention output, carried through 16 layers, stays far below
# LOGITS_REL_TOL, while a wrong mask or head mapping is O(1).
KERNEL_TOL = 3e-2
LOGITS_REL_TOL = 5e-2


def fact(msg: str) -> None:
    print(f"smoke fact: {msg}", flush=True)


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {msg}")


def require_tpu(n_chips: int):
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU — JAX runs on "
                         f"{devs[0].platform!r}")
    if len(devs) < n_chips:
        raise SystemExit(f"chip_smoke: needs {n_chips} chips, JAX sees "
                         f"{len(devs)}")
    return devs


def max_len() -> int:
    # the serving launcher's sizing: prompt + generation + 8 spare rows
    return PROMPT_LEN + MAX_NEW + 8


def init_model(cfg, seed: int = SEED):
    from repro.models import model as M
    params = jax.jit(M.init_params, static_argnums=0)(
        cfg, jax.random.PRNGKey(seed))
    return jax.block_until_ready(params)


def make_prompts(cfg, n: int, seed: int = SEED) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return rng.integers(0, cfg.vocab_size, (n, PROMPT_LEN), dtype=np.int32)


def make_runtime(topo, cfg, params, *, max_len_: int):
    import repro.core as C
    rt = C.HeteroRuntime(topo, slots=SLOTS, max_len=max_len_,
                         macro_steps=MACRO_STEPS)
    rt.add_task(cfg.name, cfg, params, max_new=MAX_NEW)
    return rt


def serve_frontend(rt, cfg, prompts: np.ndarray, split):
    """Submit every prompt through the asyncio ingress in one wave and
    stream each to completion; returns the token arrays in order."""
    from repro.core import TenantClass
    from repro.serving.frontend import ServingFrontend
    fe = ServingFrontend(rt, {"smoke": TenantClass("smoke")}, split=split,
                         wave_requests=len(prompts))

    async def drive():
        await fe.start()
        streams = [await fe.submit(p, MAX_NEW, tenant="smoke",
                                   task=cfg.name) for p in prompts]
        toks = [await s.collect() for s in streams]
        await fe.stop()
        return toks

    toks = asyncio.run(drive())
    tel = fe.telemetry()["tenants"]["smoke"]
    check(tel["completed"] == len(prompts),
          f"{tel['completed']}/{len(prompts)} requests completed")
    for i, t in enumerate(toks):
        check(len(t) == MAX_NEW,
              f"request {i} streamed {len(t)} tokens, expected {MAX_NEW}")
        check(bool(((t >= 0) & (t < cfg.vocab_size)).all()),
              f"request {i} streamed a token outside the vocabulary")
    return toks


def check_kernel(cfg, dev, S: int) -> float:
    """Compiled decode kernel vs ``decode_attention_ref`` (f32, highest
    precision) on a bf16 cache of shape [SLOTS, S, Hkv, dh]."""
    from repro.kernels import ops
    from repro.kernels.ref import decode_attention_ref
    B, H, Hkv, dh = SLOTS, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(jax.random.PRNGKey(SEED + 1), 3)
    with jax.default_device(dev):
        q = jax.random.normal(ks[0], (B, 1, H, dh)).astype(jnp.bfloat16)
        kc = jax.random.normal(ks[1], (B, S, Hkv, dh)).astype(jnp.bfloat16)
        vc = jax.random.normal(ks[2], (B, S, Hkv, dh)).astype(jnp.bfloat16)
        cl = jnp.asarray(np.linspace(1, S, B).astype(np.int32))
    compiled = ops.decode_attention.lower(q, kc, vc, cl).compile()
    check("tpu_custom_call" in compiled.as_text(),
          f"decode kernel (S={S}) compiled without a Mosaic custom call")
    got = np.asarray(compiled(q, kc, vc, cl), np.float32)
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(decode_attention_ref(q, kc, vc, cl, window=0),
                         np.float32)
    err = float(np.max(np.abs(got - ref)))
    check(bool(np.isfinite(got).all()), f"decode kernel (S={S}) not finite")
    check(bool(np.allclose(got, ref, rtol=KERNEL_TOL, atol=KERNEL_TOL)),
          f"decode kernel (S={S}) max |err| {err} beyond {KERNEL_TOL}")
    return err


def check_step_logits(cfg, params, prompts: np.ndarray) -> float:
    """One decode step over a prefilled [SLOTS, max_len] cache with the
    Pallas kernel vs the XLA reference attention."""
    from repro.models import model as M
    from repro.serving.engine import (make_prefill_step, make_serve_step,
                                      seed_cache)
    batch = {"tokens": jnp.asarray(prompts[:SLOTS])}
    last, pre = jax.jit(make_prefill_step(cfg))(params, batch)
    cache = seed_cache(cfg, M.init_cache(cfg, SLOTS, max_len()), pre,
                       PROMPT_LEN)
    tok = jnp.argmax(last, axis=-1).astype(jnp.int32)[:, None]
    idx = jnp.full((SLOTS,), PROMPT_LEN, jnp.int32)
    outs = {}
    for use_pallas in (True, False):
        step = jax.jit(make_serve_step(cfg, use_pallas=use_pallas))
        outs[use_pallas] = np.asarray(step(params, cache, tok, idx)[0],
                                      np.float32)
    check(bool(np.isfinite(outs[True]).all()), "pallas logits not finite")
    rel = float(np.linalg.norm(outs[True] - outs[False])
                / np.linalg.norm(outs[False]))
    check(rel <= LOGITS_REL_TOL,
          f"decode-step logits rel L2 {rel} beyond {LOGITS_REL_TOL}")
    return rel


def placement(eng) -> set:
    """Every device holding the engine's params or a fresh run state."""
    leaves = jax.tree.leaves((eng.params, eng.init_state()))
    return {d for leaf in leaves for d in leaf.devices()}


def one_chip() -> None:
    devs = require_tpu(1)
    dev = devs[0]
    import repro.core as C
    from repro.configs.base import get_config
    from repro.launch.compile_cache import enable_compile_cache

    fact(f"compile cache at {enable_compile_cache()}")
    fact(f"device_kind={dev.device_kind} platform={dev.platform} "
         f"visible={len(devs)}")
    t0 = time.perf_counter()
    cfg = get_config(ARCH)
    params = init_model(cfg)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    fact(f"{cfg.name}: {cfg.num_layers} layers, d_model {cfg.d_model}, "
         f"{cfg.num_heads} heads x {cfg.head_dim}, vocab "
         f"{cfg.vocab_size}, {cfg.dtype}, {n_params} params (seed {SEED})")
    fact(f"set-up (param init) s={time.perf_counter() - t0:.3f}")

    for S in (max_len(), 1000):     # the served cache; a ragged last block
        err = check_kernel(cfg, dev, S)
        fact(f"decode kernel compiled (tpu_custom_call) at "
             f"[{SLOTS},{S},{cfg.num_kv_heads},{cfg.head_dim}] bf16: "
             f"max |pallas - ref| = {err} (bound {KERNEL_TOL})")
    prompts = make_prompts(cfg, N_REQUESTS)
    rel = check_step_logits(cfg, params, prompts)
    fact(f"decode-step logits, pallas vs xla: rel L2 = {rel} "
         f"(bound {LOGITS_REL_TOL})")

    hub = C.NodeGroup("primary", [dev], C.JETSON_NANO)
    aux = C.NodeGroup("auxiliary", [dev], C.JETSON_XAVIER)
    rt = make_runtime(C.Topology.pair(hub, aux, C.WIFI_5GHZ), cfg, params,
                      max_len_=max_len())
    engines = rt.tasks[cfg.name].engines
    for name, eng in engines.items():
        check(eng._use_pallas, f"group {name} would decode through XLA")
        check(placement(eng) == {dev}, f"group {name} arrays off {dev}")
        fact(f"group {name} decodes on {eng.device}")
    from repro.serving.engine import ServeRequest
    t0 = time.perf_counter()
    rt.warmup([ServeRequest(uid=-1, prompt=prompts[0], max_new=MAX_NEW,
                            task=cfg.name)])
    fact(f"warm-up (compile) s={time.perf_counter() - t0:.3f}")
    toks = serve_frontend(rt, cfg, prompts, split=0.5)
    fact(f"served through ServingFrontend: {len(toks)}/{N_REQUESTS} "
         f"requests completed, {sum(len(t) for t in toks)} tokens")

    eng = engines["primary"]
    loop = eng._get_loop(MACRO_STEPS)
    text = loop.lower(eng.params, *eng.init_state()).compile().as_text()
    check("tpu_custom_call" in text,
          "the fused decode program holds no Mosaic kernel")
    fact("fused decode program contains the Pallas kernel "
         "(tpu_custom_call)")
    finish(dev, len(devs))


def four_chips() -> None:
    devs = require_tpu(4)[:4]
    import repro.core as C
    from repro.configs.base import get_config
    from repro.launch.compile_cache import enable_compile_cache

    fact(f"compile cache at {enable_compile_cache()}")
    fact(f"device_kind={devs[0].device_kind} platform={devs[0].platform} "
         f"chips={len(devs)}")
    t0 = time.perf_counter()
    cfg = get_config(ARCH)
    params = init_model(cfg)
    fact(f"set-up (param init) s={time.perf_counter() - t0:.3f}")
    prompts = make_prompts(cfg, N_REQUESTS_STAR)
    split = (0.25, 0.25, 0.25, 0.25)
    names = ["hub", "spoke1", "spoke2", "spoke3"]

    def star(chips):
        groups = [C.NodeGroup(n, [d], C.JETSON_NANO if i == 0
                              else C.JETSON_XAVIER)
                  for i, (n, d) in enumerate(zip(names, chips))]
        return C.Topology.star(groups[0], groups[1:], C.ICI_LINK)

    streams = {}
    for label, chips in (("one chip", [devs[0]] * 4), ("four chips", devs)):
        rt = make_runtime(star(chips), cfg, params, max_len_=max_len())
        for (name, eng), d in zip(rt.tasks[cfg.name].engines.items(),
                                  chips):
            check(eng._use_pallas, f"group {name} would decode through XLA")
            where = placement(eng)
            check(where == {d}, f"{label}: group {name} arrays on {where}, "
                                f"expected {d}")
            fact(f"{label}: group {name} params, KV cache and decode "
                 f"state on {d}")
        t0 = time.perf_counter()
        streams[label] = serve_frontend(rt, cfg, prompts, split=split)
        fact(f"{label}: {len(prompts)} requests completed, "
             f"{sum(len(t) for t in streams[label])} tokens, "
             f"serve (incl. compile) s={time.perf_counter() - t0:.3f}")
    same = [bool(np.array_equal(a, b))
            for a, b in zip(streams["one chip"], streams["four chips"])]
    check(all(same), f"streams differ from the one-chip run for requests "
                     f"{[i for i, s in enumerate(same) if not s]}")
    fact(f"all {len(same)} streams on four chips equal the one-chip streams")
    finish(devs[0], len(devs))


def finish(dev, count: int) -> None:
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": count}}), flush=True)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: olmo-1b through the frontend on one chip; "
                         "4: hub + 3 spokes, one chip each, vs one chip")
    args = ap.parse_args(argv)
    if args.chips == 4:
        four_chips()
    else:
        one_chip()


if __name__ == "__main__":
    main()
