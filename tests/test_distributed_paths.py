"""Correctness of the production (shard_map) code paths vs the reference
(global) paths.  Runs in a SUBPROCESS with 4 forced host devices so the
main test session keeps its single-device invariant."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json
    import jax, jax.numpy as jnp, numpy as np
    import dataclasses
    from repro.configs.base import get_config, reduced
    from repro.models import moe as moe_mod
    from repro.models import attention as attn_mod
    from repro.models.sharding import activation_sharding, make_mesh

    mesh = make_mesh((2, 2), ("data", "model"))
    out = {}

    # ---- MoE: shard_map path vs global path -----------------------------
    cfg = dataclasses.replace(
        reduced(get_config("qwen3-moe-235b-a22b")),
        num_experts=4, experts_per_token=2, moe_capacity_factor=8.0)
    params = moe_mod.moe_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, cfg.d_model))
    y_ref, aux_ref = jax.jit(lambda p, x: moe_mod._moe_global(p, x, cfg))(params, x)
    with mesh, activation_sharding(mesh):
        y_sm, aux_sm = jax.jit(
            lambda p, x: moe_mod._moe_shardmap(p, x, cfg, mesh))(params, x)
    out["moe_max_err"] = float(jnp.max(jnp.abs(y_ref - y_sm)))
    out["moe_aux_err"] = float(jnp.abs(aux_ref - aux_sm))

    # ---- cache_update: shard_map vs plain dynamic_update_slice ----------
    B, S, Hkv, dh = 4, 16, 1, 8   # Hkv=1 < model=2 -> S gets sharded
    cache = jax.random.normal(jax.random.PRNGKey(2), (B, S, Hkv, dh))
    new = jax.random.normal(jax.random.PRNGKey(3), (B, 1, Hkv, dh))
    errs = []
    for idx in (0, 7, 8, 15):
        ref = jax.lax.dynamic_update_slice_in_dim(cache, new, idx, axis=1)
        with mesh, activation_sharding(mesh):
            got = jax.jit(lambda c, n: attn_mod.cache_update(
                c, n, jnp.int32(idx)))(cache, new)
        errs.append(float(jnp.max(jnp.abs(ref - got))))
    out["cache_max_err"] = max(errs)

    # ---- cache_update: per-slot [B] index vectors on the sharded mesh ---
    # every row writes its own sequence position (continuous batching);
    # rows straddle both sequence shards.  B=4 shards the batch over
    # "data" (indices shard with it); B=3 spills "data" onto the sequence
    # dim (indices replicated) — both layouts must match the vmap
    # reference exactly, with the cache donated through jax.shard_map.
    vec_errs = []
    row_upd = lambda c, n, i: jax.lax.dynamic_update_slice_in_dim(
        c, n, i, axis=0)
    for Bv, idxs in ((4, (3, 7, 8, 15)), (3, (0, 9, 15))):
        cv = jax.random.normal(jax.random.PRNGKey(4), (Bv, S, Hkv, dh))
        nv = jax.random.normal(jax.random.PRNGKey(5), (Bv, 1, Hkv, dh))
        iv = jnp.asarray(idxs, jnp.int32)
        ref = jax.vmap(row_upd)(cv, nv, iv)
        with mesh, activation_sharding(mesh):
            got = jax.jit(lambda c, n, i: attn_mod.cache_update(c, n, i),
                          donate_argnums=(0,))(cv, nv, iv)
        vec_errs.append(float(jnp.max(jnp.abs(ref - got))))
    out["cache_vec_max_err"] = max(vec_errs)

    # ---- splice_blocks: fused cross-group splice on the sharded mesh ----
    # Hkv=1 < model=2 -> sequence dim sharded, so the splice rides the
    # shard_map path (seq_shard_layout); B=4 also shards the batch over
    # "data", B=3 spills "data" onto the sequence dim.  Both must match
    # the plain fused scatter bit-for-bit, with the cache donated.
    from repro.kernels.ops import splice_blocks
    Lc, Sc, Hc, dc, Pc = 2, 16, 1, 8, 5
    sp_errs = []
    for Bc, slots_c in ((4, (3, 0, 2)), (3, (2, 0))):
        dstc = jax.random.normal(jax.random.PRNGKey(6), (Lc, Bc, Sc, Hc, dc))
        srcc = jax.random.normal(jax.random.PRNGKey(7),
                                 (Lc, len(slots_c), Pc, Hc, dc))
        idsc = jnp.asarray(slots_c, jnp.int32)
        ref = dstc.at[:, idsc, :Pc].set(srcc)
        with mesh, activation_sharding(mesh):
            got = jax.jit(splice_blocks, donate_argnums=(0,))(dstc, srcc,
                                                              idsc)
        sp_errs.append(float(jnp.max(jnp.abs(ref - got))))
    out["splice_max_err"] = max(sp_errs)

    # ---- continuous engine end-to-end on the model-sharded mesh ---------
    # Hkv=1 forces the sequence-sharded cache layout, so every decode
    # step's per-slot cache_update rides the shard_map path inside the
    # donated fused loop; tokens must match the off-mesh per-step engine.
    from repro.serving.engine import ContinuousServingEngine, ServeRequest
    from repro.models import model as M
    ecfg = dataclasses.replace(
        reduced(get_config("llama3.2-1b")), num_kv_heads=1)
    eparams = M.init_params(ecfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(3)
    prompts = rng.integers(0, ecfg.vocab_size, (5, 8)).astype(np.int32)
    reqs = [ServeRequest(uid=i, prompt=prompts[i], max_new=m)
            for i, m in enumerate([1, 5, 3, 7, 4])]
    eng_ref = ContinuousServingEngine(ecfg, eparams, slots=2, max_len=32,
                                      macro_steps=0)
    ref_outs, _ = eng_ref.run(reqs)
    with mesh, activation_sharding(mesh):
        eng = ContinuousServingEngine(ecfg, eparams, slots=2, max_len=32,
                                      macro_steps=4)
        outs, stats = eng.run(reqs)
    out["engine_mesh_match"] = int(all(
        np.array_equal(a.tokens, b.tokens)
        for a, b in zip(ref_outs, outs)))
    out["engine_mesh_stalls"] = stats.admission_stalls
    out["engine_mesh_tokens"] = int(stats.total_tokens)

    # ---- disaggregated prefill end-to-end on the same mesh --------------
    # the PrefillWorker detects the active mesh and runs its program
    # mesh-wide; KV blocks then ride the shard_map splice above
    from repro.serving.prefill import PrefillWorker
    import repro.core as C
    with mesh, activation_sharding(mesh):
        w = PrefillWorker(ecfg, eparams, device=jax.devices()[0],
                          link=C.ICI_LINK)
        deng = ContinuousServingEngine(ecfg, eparams, slots=2, max_len=32,
                                       macro_steps=4, prefill_worker=w)
        douts, dstats = deng.run(reqs)
    out["disagg_mesh_match"] = int(all(
        np.array_equal(a.tokens, b.tokens)
        for a, b in zip(ref_outs, douts)))
    out["disagg_mesh_offloaded"] = int(dstats.prefill_offloaded)
    out["disagg_mesh_fallbacks"] = int(dstats.prefill_fallbacks)
    print(json.dumps(out))
""")


@pytest.fixture(scope="module")
def results():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_moe_shardmap_matches_global(results):
    assert results["moe_max_err"] < 1e-4, results
    # aux load-balance loss: the shard_map path averages PER-SHARD
    # density·router_prob products (the standard Switch-style per-device
    # estimator) while the global path uses global means — a Σ(E[xy]) vs
    # Σ(E[x]E[y]) difference, not a bug.  Bound it loosely.
    assert results["moe_aux_err"] < 5e-3, results


def test_cache_update_shardmap_matches_plain(results):
    assert results["cache_max_err"] < 1e-6, results


def test_cache_update_shardmap_per_slot_indices(results):
    """Per-slot [B] index vectors on the sequence-sharded cache: each
    shard vmaps the row update locally and masks foreign rows — exact
    equality with the off-mesh vmap path, donation preserved."""
    assert results["cache_vec_max_err"] < 1e-6, results


def test_continuous_engine_on_sharded_mesh(results):
    """The continuous engine (overlapped admission, fused decode loop,
    donated caches) runs unmodified on a model-sharded mesh and emits the
    off-mesh token streams with zero admission stalls."""
    assert results["engine_mesh_match"] == 1, results
    assert results["engine_mesh_stalls"] == 0, results
    assert results["engine_mesh_tokens"] == 1 + 5 + 3 + 7 + 4, results


def test_splice_blocks_shardmap_matches_plain(results):
    """The fused cross-group splice on a sequence-sharded cache (batch
    sharded and batch-spilled layouts, cache donated) is bit-exact
    against the plain fused scatter."""
    assert results["splice_max_err"] < 1e-6, results


def test_disaggregated_prefill_on_sharded_mesh(results):
    """Disaggregated prefill end-to-end on the sharded mesh: mesh-wide
    PrefillWorker + shard_map splice reproduce the off-mesh streams with
    every prefill offloaded and no fallbacks."""
    assert results["disagg_mesh_match"] == 1, results
    assert results["disagg_mesh_offloaded"] == 5, results
    assert results["disagg_mesh_fallbacks"] == 0, results
