"""Continuous-batching runtime + async offload dispatch + controller tests."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core as C
from repro.configs.base import get_config, reduced
from repro.core.offload import padded_quota_batch, split_sizes
from repro.models import model as M
from repro.models import transformer as tfm
from repro.serving import engine as engine_mod
from repro.serving.engine import (ContinuousServingEngine, ServeRequest,
                                  ServingEngine, make_decode_loop,
                                  make_serve_step)


@pytest.fixture(scope="module")
def small_llama():
    cfg = reduced(get_config("llama3.2-1b"))
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


# --- split_sizes / padded_quota_batch edge cases ---------------------------
@pytest.mark.parametrize("B,r,n_off,n_loc", [
    (10, 0.0, 0, 10),
    (10, 1.0, 10, 0),
    (1, 0.0, 0, 1),
    (1, 1.0, 1, 0),
    (7, 0.7, 5, 2),
])
def test_split_sizes_edges(B, r, n_off, n_loc):
    assert split_sizes(B, r) == (n_off, n_loc)
    assert sum(split_sizes(B, r)) == B


@pytest.mark.parametrize("B,r", [(10, 0.0), (10, 1.0), (1, 0.0), (1, 1.0)])
def test_padded_quota_batch_degenerate_splits(B, r):
    batch = {"x": jnp.arange(B * 2).reshape(B, 2)}
    laid, mask = padded_quota_batch(batch, r=r)
    n_off, n_loc = split_sizes(B, r)
    quota = max(n_off, n_loc, 1)
    assert laid["x"].shape == (2, quota, 2)
    assert int(mask[0].sum()) == n_off and int(mask[1].sum()) == n_loc
    # every original row appears exactly once under the validity mask
    valid = np.asarray(laid["x"])[np.asarray(mask)]
    np.testing.assert_array_equal(np.sort(valid, axis=0),
                                  np.asarray(batch["x"]))


def test_padded_quota_batch_single_item():
    laid, mask = padded_quota_batch({"x": jnp.ones((1, 3))}, r=0.5)
    # round(0.5) -> 0 offloaded: the lone item stays local
    assert int(mask[0].sum()) == 0 and int(mask[1].sum()) == 1
    assert laid["x"].shape == (2, 1, 3)


# --- continuous batching: admit/evict token equivalence --------------------
def test_continuous_matches_static_tokens(small_llama):
    """Requests finishing at different lengths produce exactly the tokens
    static batching produces — per-slot masks isolate each slot."""
    cfg, params = small_llama
    rng = np.random.default_rng(1)
    P, n = 8, 6
    prompts = rng.integers(0, cfg.vocab_size, (n, P)).astype(np.int32)
    max_news = [1, 4, 2, 5, 3, 4]   # includes evict-at-admission (max_new=1)

    static = ServingEngine(cfg, params, max_len=32)
    ref = static.generate(prompts, max_new=max(max_news)).tokens

    cont = ContinuousServingEngine(cfg, params, slots=2, max_len=32)
    outs, stats = cont.run([ServeRequest(uid=i, prompt=prompts[i], max_new=m)
                            for i, m in enumerate(max_news)])
    assert stats.requests == n
    assert stats.total_tokens == sum(max_news)
    for o in outs:
        assert len(o.tokens) == max_news[o.uid]
        np.testing.assert_array_equal(o.tokens, ref[o.uid][:len(o.tokens)])


def test_continuous_eviction_frees_slots(small_llama):
    """More requests than slots drain fully; occupancy stays high because
    evicted slots are re-admitted before the next decode step."""
    cfg, params = small_llama
    rng = np.random.default_rng(2)
    prompts = rng.integers(0, cfg.vocab_size, (5, 8)).astype(np.int32)
    cont = ContinuousServingEngine(cfg, params, slots=2, max_len=32)
    outs, stats = cont.run([ServeRequest(uid=i, prompt=prompts[i], max_new=3)
                            for i in range(5)])
    assert [o.uid for o in outs] == list(range(5))
    assert stats.decode_steps < 5 * 2  # < serial per-request decoding
    assert stats.occupancy > 0.5


def test_continuous_empty_and_single(small_llama):
    cfg, params = small_llama
    cont = ContinuousServingEngine(cfg, params, slots=2, max_len=32)
    outs, stats = cont.run([])
    assert outs == [] and stats.total_tokens == 0
    prompt = np.ones((8,), np.int32)
    outs, stats = cont.run([ServeRequest(uid=0, prompt=prompt, max_new=1)])
    assert len(outs) == 1 and len(outs[0].tokens) == 1
    assert stats.decode_steps == 0  # first token comes from the prefill


# --- fused macro-step decode: bit-identity with the per-step loop ----------
def _family_fixture(arch: str, kv_int8: bool):
    cfg = reduced(get_config(arch))
    if kv_int8:
        cfg = dataclasses.replace(cfg, kv_quant="int8")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(7)
    P, n = 8, 5
    prompts = rng.integers(0, cfg.vocab_size, (n, P)).astype(np.int32)
    frontend = None
    if cfg.frontend:
        frontend = rng.standard_normal(
            (n, cfg.frontend_tokens, cfg.frontend_dim or cfg.d_model)
        ).astype(np.float32)
    # mixed lengths: max_new=1 evicts at admission, 3/4 finish mid-macro
    # (K=4), 9 spans three macro-steps
    max_news = [1, 6, 3, 9, 4]
    reqs = [ServeRequest(uid=i, prompt=prompts[i], max_new=m,
                         frontend=None if frontend is None else frontend[i])
            for i, m in enumerate(max_news)]
    return cfg, params, reqs


@pytest.mark.parametrize("arch,kv_int8", [
    ("llama3.2-1b", False),       # transformer KV cache
    ("falcon-mamba-7b", False),   # SSM conv + state caches
    ("zamba2-2.7b", False),       # hybrid: mamba backbone + shared attn KV
    ("internvl2-1b", True),       # vlm frontend offset + int8-quantized KV
])
def test_fused_macro_step_bit_identity(arch, kv_int8):
    """The fused K-token loop must emit exactly the per-step loop's token
    streams for every cache family: donation, device-side argmax, frozen
    slots and boundary-lagged eviction may not perturb any live slot."""
    cfg, params, reqs = _family_fixture(arch, kv_int8)
    per_step = ContinuousServingEngine(cfg, params, slots=2, max_len=48,
                                       macro_steps=0)
    fused = ContinuousServingEngine(cfg, params, slots=2, max_len=48,
                                    macro_steps=4, share_from=per_step)
    ref, ref_stats = per_step.run(reqs)
    outs, stats = fused.run(reqs)
    assert [o.uid for o in outs] == [o.uid for o in ref]
    for a, b in zip(ref, outs):
        np.testing.assert_array_equal(a.tokens, b.tokens)
    assert stats.total_tokens == ref_stats.total_tokens
    assert stats.macro_dispatches > 0
    # the whole point: strictly fewer device->host round-trips
    assert stats.host_syncs < ref_stats.host_syncs


@pytest.mark.parametrize("arch,kv_int8,inplace", [
    ("llama3.2-1b", False, True),           # dense
    ("qwen3-moe-235b-a22b", False, True),   # MoE
    ("llama3.2-1b", True, False),           # int8 KV: scales beside K/V
    ("zamba2-2.7b", False, False),          # hybrid shared-attention cache
])
def test_decode_cache_inplace(arch, kv_int8, inplace, monkeypatch):
    """The decode step that carries the stacked cache through the layer
    scan gives the tokens and the final cache of the per-layer
    ``xs``/``ys`` step bit for bit, over K fused steps (live, freezing
    and free slots) and over one static step; the engine reports which
    path its launches take in the ``cache_inplace`` stat."""
    cfg, params, reqs = _family_fixture(arch, kv_int8)
    B, S, K = 4, 32, 4
    leaves, tree = jax.tree.flatten(M.init_cache(cfg, B, S))
    keys = jax.random.split(jax.random.PRNGKey(3), len(leaves))
    # stale bytes everywhere, as a slot's previous occupant leaves them
    cache = jax.tree.unflatten(tree, [
        (jax.random.normal(k, a.shape) * 4).astype(a.dtype)
        for k, a in zip(keys, leaves)])
    tok = jnp.asarray([3, 7, 11, 19], jnp.int32)
    lengths = jnp.asarray([5, 9, 0, 12], jnp.int32)
    remaining = jnp.asarray([6, 2, 0, 1], jnp.int32)   # slot 1 and 3 freeze
    done = jnp.asarray([False, False, True, False])    # slot 2 is free

    def decode():
        loop = jax.jit(make_decode_loop(cfg, macro_steps=K,
                                        use_pallas=False))
        step = jax.jit(make_serve_step(cfg, use_pallas=False))
        return jax.device_get((
            loop(params, cache, tok, lengths, remaining, done),
            step(params, cache, tok[:, None], jnp.int32(7))))

    assert tfm.decode_inplace(cache) == inplace
    got = decode()
    with monkeypatch.context() as m:
        m.setattr(tfm, "decode_inplace", lambda *a: False)
        ref = decode()
    assert jax.tree.structure(got) == jax.tree.structure(ref)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(ref)):
        np.testing.assert_array_equal(a, b)

    launches = []
    real_span = engine_mod.span

    def record(name, **stats):
        if name == "engine.launch":
            launches.append(stats)
        return real_span(name, **stats)
    monkeypatch.setattr(engine_mod, "span", record)
    eng = ContinuousServingEngine(cfg, params, slots=2, max_len=48,
                                  macro_steps=K)
    eng.run(reqs)
    assert launches
    assert all(st["cache_inplace"] == int(inplace) for st in launches)


def test_fused_generate_bit_identity(small_llama):
    """ServingEngine: macro-stepped generate == per-step generate, with one
    host sync per macro-step instead of per token."""
    cfg, params = small_llama
    rng = np.random.default_rng(5)
    prompts = rng.integers(0, cfg.vocab_size, (3, 8)).astype(np.int32)
    per_step = ServingEngine(cfg, params, max_len=48, macro_steps=0)
    fused = ServingEngine(cfg, params, max_len=48, macro_steps=8)
    for max_new in (1, 7, 16):    # below / mid / multiple-of-K boundaries
        ref = per_step.generate(prompts, max_new=max_new)
        out = fused.generate(prompts, max_new=max_new)
        np.testing.assert_array_equal(out.tokens, ref.tokens)
        assert ref.host_syncs == max_new
        assert out.host_syncs == 1 + -(-max(max_new - 1, 0) // 8)


def test_fused_mid_macro_eos_eviction(small_llama):
    """A request hitting eos mid-macro-step is truncated at the eos token
    (inclusive) and its slot refilled at the boundary — streams stay
    bit-identical to the per-step loop with the same eos."""
    cfg, params = small_llama
    rng = np.random.default_rng(11)
    prompts = rng.integers(0, cfg.vocab_size, (4, 8)).astype(np.int32)
    probe = ContinuousServingEngine(cfg, params, slots=2, max_len=48)
    full, _ = probe.run([ServeRequest(uid=i, prompt=prompts[i], max_new=10)
                         for i in range(4)])
    # pick an eos that FIRST lands at position 1 or 2 of uid 0's stream:
    # the request then finishes on micro-step 2 or 3 of the first K=4
    # macro-step — strictly mid-macro
    t0 = [int(x) for x in full[0].tokens]
    j = next((k for k in (1, 2) if t0[k] not in t0[:k]), None)
    assert j is not None, f"no unique mid-macro token in {t0}"
    eos = t0[j]
    reqs = [ServeRequest(uid=i, prompt=prompts[i], max_new=10)
            for i in range(4)]
    per_step = ContinuousServingEngine(cfg, params, slots=2, max_len=48,
                                       macro_steps=0, eos_id=eos)
    fused = ContinuousServingEngine(cfg, params, slots=2, max_len=48,
                                    macro_steps=4, eos_id=eos,
                                    share_from=per_step)
    ref, _ = per_step.run(reqs)
    outs, _ = fused.run(reqs)
    for a, b in zip(ref, outs):
        np.testing.assert_array_equal(a.tokens, b.tokens)
    assert len(outs[0].tokens) == j + 1 and outs[0].tokens[-1] == eos
    assert any(len(o.tokens) < 10 for o in outs)     # eos actually evicted
    assert all(o.tokens[-1] == eos or len(o.tokens) == 10 for o in outs)


# --- async offload dispatch ------------------------------------------------
def test_offload_run_overlapped_dispatch_measured(small_llama):
    cfg, params = small_llama

    def task(batch):
        return M.forward(params, cfg, batch, mode="train").logits

    dev = jax.devices()[0]
    eng = C.OffloadEngine(task,
                          C.NodeGroup("pri", [dev], C.JETSON_NANO),
                          C.NodeGroup("aux", [dev], C.JETSON_XAVIER),
                          C.WIFI_5GHZ, payload_bytes_per_item=80e3)
    batch = {"tokens": np.arange(10 * 16).reshape(10, 16).astype(np.int32)
             % cfg.vocab_size}
    rep = eng.run(batch, r=0.7)
    assert rep.t_parallel_s > 0.0          # measured, not derived
    assert rep.t_parallel >= rep.t_parallel_s
    # outputs merge in original batch order: [offloaded slice; local slice]
    direct = np.asarray(task({"tokens": jnp.asarray(batch["tokens"])}))
    np.testing.assert_allclose(np.asarray(rep.outputs), direct,
                               rtol=2e-4, atol=2e-4)
    # degenerate splits keep working and stay measured
    for r in (0.0, 1.0):
        rep = eng.run(batch, r=r)
        assert rep.outputs.shape == direct.shape
        assert rep.t_parallel_s > 0.0


def test_offload_compile_cache_keyed_by_shape(small_llama):
    cfg, params = small_llama

    def task(batch):
        return M.forward(params, cfg, batch, mode="train").logits

    dev = jax.devices()[0]
    eng = C.OffloadEngine(task,
                          C.NodeGroup("pri", [dev], C.JETSON_NANO),
                          C.NodeGroup("aux", [dev], C.JETSON_XAVIER),
                          C.WIFI_5GHZ, payload_bytes_per_item=1e3)
    batch = {"tokens": np.ones((10, 16), np.int32)}
    eng.run(batch, r=0.7)   # 7/3 split
    keys = set(eng._compiled)
    eng.run(batch, r=0.7)   # same shapes -> no new entries
    assert set(eng._compiled) == keys
    eng.run(batch, r=0.5)   # 5/5 split -> new shapes for both groups
    assert len(eng._compiled) == len(keys) + 2


# --- online split-ratio controller -----------------------------------------
def _report(n_loc, n_off, rate_loc, rate_rem, rate_link=0.01):
    return C.OffloadReport(
        r=n_off / max(n_loc + n_off, 1), n_local=n_loc, n_offloaded=n_off,
        t_local_s=rate_loc * n_loc, t_remote_s=rate_rem * n_off,
        t_offload_s=rate_link * n_off, payload_bytes=0.0, e_offload_j=0.0)


def test_controller_shifts_toward_faster_group():
    ctl = C.SplitRatioController(C.ControllerConfig(update_every=1))
    for _ in range(3):
        ctl.observe(_report(4, 4, rate_loc=0.2, rate_rem=0.05))
    assert ctl.r > 0.6, ctl.r            # remote 4x faster -> offload most

    ctl = C.SplitRatioController(C.ControllerConfig(update_every=1))
    for _ in range(3):
        ctl.observe(_report(4, 4, rate_loc=0.05, rate_rem=0.2))
    assert ctl.r < 0.4, ctl.r            # local 4x faster -> keep most


def test_controller_tracks_load_shift():
    """The auxiliary slows down mid-stream; r comes back down."""
    ctl = C.SplitRatioController(C.ControllerConfig(update_every=1, ema=0.6))
    for _ in range(3):
        ctl.observe(_report(4, 4, rate_loc=0.1, rate_rem=0.05))
    r_fast = ctl.r
    for _ in range(5):
        ctl.observe(_report(4, 4, rate_loc=0.1, rate_rem=0.5))
    assert ctl.r < r_fast


def test_controller_exploration_prevents_starvation():
    """Even when one group is hopeless the ratio is held off the 0/1
    extremes and split() keeps routing at least one item to each group —
    otherwise the starved group's EWMA freezes and recovery is invisible."""
    ctl = C.SplitRatioController(C.ControllerConfig(update_every=1))
    for _ in range(3):
        ctl.observe(_report(4, 4, rate_loc=0.01, rate_rem=5.0))
    assert ctl.cfg.explore <= ctl.r <= 1.0 - ctl.cfg.explore
    assert ctl.split(8) >= 1 and ctl.split(8) <= 7
    assert ctl.split(1) in (0, 1)          # can't split a single item
    # the trickle keeps remote observations flowing: a recovered remote
    # pulls the ratio back up (EWMA needs ~10 waves to forget rate 5.0)
    for _ in range(12):
        ctl.observe(_report(7, 1, rate_loc=0.2, rate_rem=0.01))
    assert ctl.r > 0.5


def test_controller_respects_update_cadence():
    ctl = C.SplitRatioController(C.ControllerConfig(update_every=4))
    for i in range(3):
        ctl.observe(_report(4, 4, rate_loc=0.2, rate_rem=0.05))
    assert ctl.history == [] and ctl.r == 0.5   # not re-solved yet
    ctl.observe(_report(4, 4, rate_loc=0.2, rate_rem=0.05))
    assert len(ctl.history) == 1 and ctl.r != 0.5
