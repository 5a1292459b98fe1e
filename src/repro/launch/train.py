"""Distributed training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b \
        --steps 50 --batch 8 --seq 64 [--reduced] [--microbatches 4]

On real hardware this builds the largest mesh the device set supports
(model axis = min(16, n_devices)) and shards with the production rules; on
this CPU container use --reduced for a runnable demonstration on the
1-device mesh (same code path, mesh (1,1)).
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import get_config, list_configs, reduced
from repro.data.pipeline import DataConfig, synthetic_lm_batches
from repro.launch.mesh import data_shardings, params_shardings, replicated
from repro.models import model as M
from repro.models.sharding import activation_sharding, make_mesh
from repro.training.checkpoint import save_checkpoint
from repro.training.optimizer import OptimizerConfig, init_opt_state
from repro.training.train import make_train_step


def build_mesh():
    n = len(jax.devices())
    model = 1
    for cand in (16, 8, 4, 2, 1):
        if n % cand == 0 and cand <= n:
            model = cand
            break
    return make_mesh((n // model, model), ("data", "model"))


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=list_configs(), default="llama3.2-1b")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--reduced", action="store_true",
                    help="CPU-sized variant of the same family")
    ap.add_argument("--remat", action="store_true")
    ap.add_argument("--ckpt", default="")
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    mesh = build_mesh()
    print(f"mesh={dict(mesh.shape)}  arch={cfg.name}"
          f"{' (reduced)' if args.reduced else ''}")

    params = M.init_params(cfg, jax.random.PRNGKey(0))
    opt_state = init_opt_state(params)
    p_shard = params_shardings(params, mesh)
    o_shard = type(opt_state)(step=replicated(mesh),
                              m=params_shardings(opt_state.m, mesh),
                              v=params_shardings(opt_state.v, mesh))
    params = jax.device_put(params, p_shard)
    opt_state = jax.device_put(opt_state, o_shard)

    data = synthetic_lm_batches(DataConfig(
        vocab_size=cfg.vocab_size, seq_len=args.seq, batch_size=args.batch,
        frontend_tokens=cfg.frontend_tokens if cfg.frontend else 0,
        frontend_dim=(cfg.frontend_dim or cfg.d_model) if cfg.frontend else 0))
    batch0 = next(data)
    b_shard = data_shardings(
        jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), batch0),
        mesh)

    opt_cfg = OptimizerConfig(lr=args.lr, warmup_steps=max(args.steps // 10, 1),
                              total_steps=args.steps)
    step_fn = jax.jit(
        make_train_step(cfg, opt_cfg, remat=args.remat,
                        microbatches=args.microbatches),
        in_shardings=(p_shard, o_shard, b_shard),
        out_shardings=(p_shard, o_shard, replicated(mesh)),
        donate_argnums=(0, 1))

    t0 = time.perf_counter()
    with mesh, activation_sharding(mesh):
        for i in range(args.steps):
            batch = jax.device_put(next(data), b_shard)
            params, opt_state, metrics = step_fn(params, opt_state, batch)
            if i % max(args.steps // 10, 1) == 0 or i == args.steps - 1:
                print(f"step {i:5d}  loss={float(metrics['loss']):.4f}  "
                      f"gnorm={float(metrics['grad_norm']):.2f}  "
                      f"lr={float(metrics['lr']):.2e}")
    wall = time.perf_counter() - t0
    toks = args.steps * args.batch * args.seq
    print(f"done: {args.steps} steps, {toks/wall:.0f} tok/s wall")
    if args.ckpt:
        save_checkpoint(args.ckpt, params, opt_state,
                        metadata={"arch": cfg.name, "steps": args.steps})
        print(f"checkpoint -> {args.ckpt}")


if __name__ == "__main__":
    main()
