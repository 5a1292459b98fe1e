"""Serving engine: prefill + decode steps, batched generation.

``make_prefill_step`` / ``make_serve_step`` return the pure functions the
dry-run lowers (prefill_32k → prefill_step; decode shapes → serve_step:
ONE new token against a seq_len cache).  ``make_decode_loop`` is the fused
serving hot path: a single jitted ``lax.scan`` that advances every slot
``macro_steps`` tokens per dispatch with greedy sampling, per-slot length
bookkeeping and eos detection all on device — the host fetches one
``[K, B]`` token block per macro-step instead of syncing per token, and
``donate_argnums`` lets XLA update the multi-GiB KV cache in place instead
of copying it every token.

``ServingEngine`` wraps them into a batched greedy-decoding loop and plugs
into the HeteroEdge ``OffloadEngine`` as the task function for the
collaborative-serving examples.

``ContinuousServingEngine`` is the slot-based continuous-batching runtime:
a request queue feeds a fixed number of KV-cache slots; each macro-step
advances every occupied slot K tokens with per-slot cache indices (vector
``cache_index`` through the model's decode path), finished requests are
evicted and their slots re-admitted from the queue at macro-step
boundaries.  Token streams are bit-identical to the per-step loop
(``macro_steps=0`` keeps the pre-fusion host loop for A/B benchmarking):
slots only attend to their own positions, so a finished slot decoding junk
until the next boundary cannot perturb any live slot.

With ``overlap_admission`` (the default on the fused path) prefill rides
the spare dispatch instead of stalling the boundary: queued requests are
speculatively prefilled into *shadow slots* — B=1 prefill programs
dispatched right behind the in-flight decode macro-step, never awaited —
and at the next boundary the ready shadows are spliced into freed slots
with the donated slot-write + ``admit_slots`` programs before the next
macro-step launches.  Decode never waits on prefill: the only host sync
per iteration is the macro-step's token-block fetch (the spliced first
tokens piggyback on it), and ``admission_stalls`` counts the boundaries
where a shadow miss forced prefill onto the critical path (zero at steady
state — shadows are kept topped up to the slot count).

With a ``prefill_worker`` (PR 5, disaggregated prefill) the shadow
prefills leave the decode group entirely: they dispatch onto the
topology's dedicated prefill spoke, their KV blocks transfer back over
the priced link at the boundary, and all admitted blocks splice in ONE
donated cross-group program (:func:`splice_slot_caches`).  A prefill
group that dies mid-run degrades to local shadow prefill with
bit-identical streams — ``prefill_fallbacks`` records the recoveries.
"""
from __future__ import annotations

import contextlib
import functools
import time
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import (Any, Callable, Dict, List, Optional, Sequence,
                    Tuple, Union)

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import model as M
from repro.models.transformer import decode_inplace
from repro.serving.spans import span


def resolve_use_pallas(use_pallas: Union[bool, str]) -> bool:
    """Resolve a ``use_pallas`` flag: "auto" enables the Pallas decode
    kernel exactly when a compiled TPU backend is available (off-TPU the
    kernel would run interpreted — orders of magnitude slower than the
    XLA reference path).  The single backend probe lives in
    ``repro.kernels.decode_attention.auto_interpret``; the
    ``REPRO_PALLAS_INTERPRET`` env var does NOT change engine routing —
    it only picks interpret-vs-compile for kernels that DO run."""
    if use_pallas == "auto":
        from repro.kernels.decode_attention import auto_interpret
        return not auto_interpret()
    return bool(use_pallas)


def make_prefill_step(cfg, *, use_pallas: bool = False):
    """(params, batch) -> (last_logits [B,V], caches)."""
    def prefill_step(params, batch):
        out = M.forward(params, cfg, batch, mode="prefill", use_pallas=use_pallas)
        return out.logits[:, -1], out.cache
    return prefill_step


def make_serve_step(cfg, *, use_pallas: Union[bool, str] = "auto"):
    """(params, cache, token [B,1], cache_index) -> (logits [B,V], cache)."""
    use_pallas = resolve_use_pallas(use_pallas)

    def serve_step(params, cache, token, cache_index):
        out = M.forward(params, cfg,
                        {"token": token, "cache": cache,
                         "cache_index": cache_index},
                        mode="decode", use_pallas=use_pallas)
        return out.logits[:, 0], out.cache
    return serve_step


def make_decode_loop(cfg, *, macro_steps: int, eos_id: Optional[int] = None,
                     use_pallas: Union[bool, str] = "auto"):
    """Fused K-token decode: one traced program per macro-step.

    ``(params, cache, cur_tok [B], lengths [B], remaining [B], done [B])
    -> (tokens [K, B], cache, cur_tok, lengths, remaining, done)``

    Each scan iteration runs one decode step for every slot, takes the
    greedy argmax ON DEVICE, and advances only the slots that are still
    live: a slot freezes (lengths/cur_tok/remaining stop moving) the step
    it emits its ``remaining``-th token or ``eos_id``.  Frozen and free
    slots keep executing the model with junk inputs — their cache rows are
    isolated by the per-slot length masks, so live slots' token streams are
    bit-identical to the per-step loop.  Jit this with
    ``donate_argnums=(1, 2, 3, 4, 5)`` so the cache and the decode state
    are updated in place (the caller must treat the donated arguments as
    consumed and only ever use the returned arrays).  Where
    ``transformer.decode_inplace`` holds, each step writes its new K/V
    rows straight into the carried stacked cache, so the cache stays one
    buffer from the donated input to the aliased output.
    """
    use_pallas = resolve_use_pallas(use_pallas)
    eos = -1 if eos_id is None else int(eos_id)

    def decode_loop(params, cache, cur_tok, lengths, remaining, done):
        def body(carry, _):
            cache, tok, lengths, remaining, done = carry
            out = M.forward(params, cfg,
                            {"token": tok[:, None], "cache": cache,
                             "cache_index": lengths},
                            mode="decode", use_pallas=use_pallas)
            new_tok = jnp.argmax(out.logits[:, 0], axis=-1).astype(jnp.int32)
            active = jnp.logical_not(done)
            tok = jnp.where(active, new_tok, tok)
            lengths = lengths + active
            remaining = remaining - active
            done = done | (active & ((remaining <= 0) | (tok == eos)))
            return (out.cache, tok, lengths, remaining, done), tok

        carry, toks = jax.lax.scan(
            body, (cache, cur_tok, lengths, remaining, done), None,
            length=macro_steps)
        cache, cur_tok, lengths, remaining, done = carry
        return toks, cache, cur_tok, lengths, remaining, done

    return decode_loop


# ---------------------------------------------------------------------------
def _loop_program(cfg, loops: Dict, K: int, eos_id: Optional[int],
                  use_pallas: bool):
    """Fetch-or-build the jitted fused loop for (K, eos_id) in ``loops``
    (a cache shared across sibling engines via ``share_from``).  Donation
    covers the cache and all four decode-state vectors."""
    key = (K, eos_id)
    fn = loops.get(key)
    if fn is None:
        fn = jax.jit(
            make_decode_loop(cfg, macro_steps=K, eos_id=eos_id,
                             use_pallas=use_pallas),
            donate_argnums=(1, 2, 3, 4, 5))
        loops[key] = fn
    return fn


def make_wave_driver(cfg, *, macro_steps: int, wave_steps: int,
                     eos_id: Optional[int] = None,
                     use_pallas: Union[bool, str] = "auto"):
    """Multi-macro-step wave driver: M fused K-token macro-steps in ONE
    traced program (an outer ``lax.scan`` over :func:`make_decode_loop`'s
    body), so steady-state decoding costs one host launch per M·K tokens
    instead of one per K.

    ``(params, cache, cur_tok, lengths, remaining, done)
    -> (tokens [M, K, B], cache, cur_tok, lengths, remaining, done)``

    Admission still lands at M-boundaries: the engine fetches the full
    ``[M·K, B]`` token block per launch and slots that finish mid-wave
    freeze exactly as they do mid-macro-step, so token streams stay
    bit-identical to the single-step driver (and to ``macro_steps=0``).
    Jit with ``donate_argnums=(1, 2, 3, 4, 5)`` like the inner loop.
    """
    loop = make_decode_loop(cfg, macro_steps=macro_steps, eos_id=eos_id,
                            use_pallas=use_pallas)

    def wave_driver(params, cache, cur_tok, lengths, remaining, done):
        def body(carry, _):
            cache, tok, lengths, remaining, done = carry
            toks, cache, tok, lengths, remaining, done = loop(
                params, cache, tok, lengths, remaining, done)
            return (cache, tok, lengths, remaining, done), toks

        carry, toks = jax.lax.scan(
            body, (cache, cur_tok, lengths, remaining, done), None,
            length=wave_steps)
        cache, cur_tok, lengths, remaining, done = carry
        return toks, cache, cur_tok, lengths, remaining, done

    return wave_driver


def _wave_program(cfg, waves: Dict, K: int, M: int, eos_id: Optional[int],
                  use_pallas: bool):
    """Fetch-or-build the jitted wave driver for (K, M, eos_id) in
    ``waves`` (shared across sibling engines via ``share_from``, exactly
    like ``_loop_program``)."""
    key = (K, M, eos_id)
    fn = waves.get(key)
    if fn is None:
        fn = jax.jit(
            make_wave_driver(cfg, macro_steps=K, wave_steps=M,
                             eos_id=eos_id, use_pallas=use_pallas),
            donate_argnums=(1, 2, 3, 4, 5))
        waves[key] = fn
    return fn


class _DecodeLauncher:
    """Single background thread that executes fused decode launches.

    Multi-device CPU programs execute synchronously inside the dispatch
    call, so on the emulated scale-out tier the serve loop's
    ``t_dispatch_s`` bucket was really device execution wall — ~99% of
    the 64-device macro-step wall looked like "host launch cost".
    Routing the launch through one worker thread makes the decomposition
    honest and buys real overlap: ``submit`` returns immediately (its
    wall is the true host-side launch tax), the shadow-prefill top-up
    runs while the macro-step executes (XLA releases the GIL), and the
    execution wall lands in ``t_await_s`` at ``Future.result()``.

    ``jax.Mesh`` contexts are thread-local (and key the jit cache), so
    the worker re-enters the mesh the engine was built under — otherwise
    every launch would retrace.  Exceptions surface at the await.  Note
    ``jax.transfer_guard`` is also thread-local: tests that guard the
    decode loop run with ``async_dispatch=False``.

    The FIRST submit of each program runs inline on the caller's thread
    and returns the bare result (callers treat future-less returns as
    already-complete).  First call means jit trace + XLA compile; doing
    that on the worker thread while the main thread concurrently traces
    prefill/boundary programs has deadlocked on wide emulated meshes.
    Steady-state launches — the ones ``t_dispatch_s`` is about — still
    go through the worker.
    """

    def __init__(self, mesh=None):
        self._mesh = mesh
        self._pool: Optional[ThreadPoolExecutor] = None
        self._warm: set = set()

    def _enter_mesh(self):
        # entered once for the worker thread's lifetime
        if self._mesh is not None:
            self._mesh.__enter__()

    def submit(self, fn, *args):
        if id(fn) not in self._warm:
            # compile-on-first-call happens on the caller's thread, which
            # already holds the mesh context
            self._warm.add(id(fn))
            return fn(*args)
        if self._pool is None:
            self._pool = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="decode-launch",
                initializer=self._enter_mesh)
        return self._pool.submit(fn, *args)


# ---------------------------------------------------------------------------
def _merge_cache(cfg, big_cache, prefill_cache, upd):
    """Walk the decode-cache tree, applying ``upd(dst_leaf, src_leaf)`` at
    every leaf and quantizing bf16 prefill K/V into int8 destinations on the
    way.  Shared by full-batch seeding (seed_cache) and per-slot admission
    (write_slot_cache) — only the leaf update differs."""
    def copy_kv(dst, src):
        if "self" in dst:  # unwrap {"self": ...} wrappers (hybrid shared)
            return {key: copy_kv(dst[key], src[key]) for key in dst}
        if "k_scale" in dst and "k_scale" not in src:
            # int8 destination seeded from a bf16 prefill cache
            from repro.models.attention import quantize_kv
            out = {}
            for name in ("k", "v"):
                qt, sc = quantize_kv(src[name])
                out[name] = upd(dst[name], qt)
                out[name + "_scale"] = upd(dst[name + "_scale"], sc)
            return out
        return jax.tree.map(upd, dst, src)

    kind = M._kind(cfg)
    if kind == "ssm":
        return jax.tree.map(upd, big_cache, prefill_cache)
    if kind == "hybrid":
        return {"backbone": jax.tree.map(upd, big_cache["backbone"],
                                         prefill_cache["backbone"]),
                "shared": copy_kv(big_cache["shared"], prefill_cache["shared"])}
    out = {"self": copy_kv(big_cache["self"], prefill_cache["self"])}
    if "cross" in big_cache:
        out["cross"] = jax.tree.map(upd, big_cache["cross"],
                                    prefill_cache["cross"])
    return out


def seed_cache(cfg, big_cache, prefill_cache, prefill_len: int):
    """Copy prefill caches (length P buffers) into full-size decode buffers.

    The leaf update writes the (shorter) prefill buffer at sequence offset 0
    of axis 2; for same-shape leaves (SSM states, cross K/V) that is a full
    replace, so one update covers every cache family."""
    def upd(d, s):
        return jax.lax.dynamic_update_slice_in_dim(
            d, s.astype(d.dtype), 0, axis=2)
    return _merge_cache(cfg, big_cache, prefill_cache, upd)


# ---------------------------------------------------------------------------
@dataclass
class GenerationResult:
    tokens: np.ndarray            # [B, max_new]
    prefill_s: float
    decode_s: float
    tokens_per_s: float
    host_syncs: int = 0           # device→host materializations
    t_per_macro_step_s: float = 0.0   # decode wall per fused dispatch (0.0
                                      # on the per-step macro_steps=0 path)


class ServingEngine:
    """Batched greedy generation with a fixed-capacity KV/SSM cache.

    ``macro_steps=K`` (default 8) runs decoding as fused K-token dispatches
    via :func:`make_decode_loop` with the cache donated in place;
    ``macro_steps=0`` keeps the pre-fusion per-token host loop (one host
    sync per token) for A/B comparison.  Both emit identical tokens."""

    def __init__(self, cfg, params, *, max_len: int = 512,
                 use_pallas: Union[bool, str] = "auto",
                 macro_steps: int = 8):
        self.cfg, self.params, self.max_len = cfg, params, max_len
        self.macro_steps = int(macro_steps)
        self._use_pallas = resolve_use_pallas(use_pallas)
        self.prefill = jax.jit(
            make_prefill_step(cfg, use_pallas=self._use_pallas))
        # the per-step program donates its cache argument too: even the
        # legacy loop updates the KV buffers in place
        self.step = jax.jit(
            make_serve_step(cfg, use_pallas=self._use_pallas),
            donate_argnums=(1,))
        self._loops: Dict[Tuple[int, Optional[int]], Any] = {}

    def _get_loop(self, K: int, eos_id: Optional[int] = None):
        return _loop_program(self.cfg, self._loops, K, eos_id,
                             self._use_pallas)

    def generate(self, prompts: np.ndarray, max_new: int = 16,
                 frontend: Optional[np.ndarray] = None) -> GenerationResult:
        """prompts: [B, P] int32 (pre-padded)."""
        cfg = self.cfg
        B, P = prompts.shape
        batch = {"tokens": jnp.asarray(prompts)}
        if frontend is not None:
            batch["frontend"] = jnp.asarray(frontend)
        t0 = time.perf_counter()
        last_logits, pre_cache = jax.block_until_ready(
            self.prefill(self.params, batch))
        t_prefill = time.perf_counter() - t0

        total = self.max_len
        offset = cfg.frontend_tokens if cfg.family == "vlm" else 0
        cache = M.init_cache(cfg, B, total, dtype=cfg.jnp_dtype)
        cache = seed_cache(cfg, cache, pre_cache, P + offset)

        if self.macro_steps == 0:
            return self._generate_per_step(last_logits, cache, P + offset,
                                           max_new, t_prefill)

        K = self.macro_steps
        loop = self._get_loop(K)
        tok = jnp.argmax(last_logits, axis=-1).astype(jnp.int32)
        lengths = jnp.full((B,), P + offset, jnp.int32)
        remaining = jnp.full((B,), max_new - 1, jnp.int32)
        done = remaining <= 0
        out_toks = [np.asarray(tok)[:, None]]
        host_syncs = 1
        dispatches = 0
        need = max_new - 1
        t0 = time.perf_counter()
        while need > 0:
            toks, cache, tok, lengths, remaining, done = loop(
                self.params, cache, tok, lengths, remaining, done)
            t = np.asarray(toks)          # the macro-step's ONE host sync
            host_syncs += 1
            dispatches += 1
            take = min(need, K)
            out_toks.append(t[:take].T)
            need -= take
        t_decode = time.perf_counter() - t0
        toks = np.concatenate(out_toks, axis=1)
        return GenerationResult(
            tokens=toks, prefill_s=t_prefill, decode_s=t_decode,
            tokens_per_s=B * max_new / max(t_decode + t_prefill, 1e-9),
            host_syncs=host_syncs,
            t_per_macro_step_s=t_decode / max(dispatches, 1))

    def _generate_per_step(self, last_logits, cache, idx: int, max_new: int,
                           t_prefill: float) -> GenerationResult:
        """Pre-fusion host loop: one dispatch + one host sync per token."""
        B = last_logits.shape[0]
        tok = jnp.argmax(last_logits, axis=-1)[:, None].astype(jnp.int32)
        out_toks = [np.asarray(tok)]
        host_syncs = 1
        # device-resident position counter: one seed upload, then the
        # index advances on device instead of re-uploading a fresh
        # jnp.int32(idx) scalar every token.  The per-token np.asarray
        # fetch above is the loop's only host sync — the old trailing
        # block_until_ready(tok) double-synced a token the fetch had
        # already materialized.
        idx_dev = jnp.int32(idx)
        t0 = time.perf_counter()
        for _ in range(max_new - 1):
            logits, cache = self.step(self.params, cache, tok, idx_dev)
            tok = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
            out_toks.append(np.asarray(tok))
            host_syncs += 1
            idx_dev = idx_dev + 1
        t_decode = time.perf_counter() - t0
        toks = np.concatenate(out_toks, axis=1)
        return GenerationResult(
            tokens=toks, prefill_s=t_prefill, decode_s=t_decode,
            tokens_per_s=B * max_new / max(t_decode + t_prefill, 1e-9),
            host_syncs=host_syncs)


# ---------------------------------------------------------------------------
# Continuous batching
# ---------------------------------------------------------------------------
def write_slot_cache(cfg, big_cache, prefill_cache, slot):
    """Write a B=1 prefill cache into slot `slot` of the big decode cache.

    Every cache leaf is laid out [L, B, ...]; the prefill leaf is
    [L, 1, P, ...] (or [L, 1, ...] for SSM states), so a single-slot
    scatter at (0, slot, 0, ...) seeds the slot.  Positions beyond the
    prompt keep stale bytes from the slot's previous occupant — the
    per-slot length mask in decode attention hides them.

    The leaf write routes through ``kernels/ops.splice_blocks`` with a
    one-element slot-id vector: off-mesh this lowers to exactly the old
    per-leaf ``dynamic_update_slice``; on a sequence-sharded mesh
    (``models/sharding.seq_shard_layout``) the write stays shard-local
    like the cross-group splice, instead of GSPMD regathering the whole
    big cache around a replicated update.
    """
    from repro.kernels.ops import splice_blocks

    ids = jnp.asarray(slot, jnp.int32).reshape((1,))

    def upd(dst, src):
        return splice_blocks(dst, src, ids)

    return _merge_cache(cfg, big_cache, prefill_cache, upd)


def splice_slot_caches(cfg, big_cache, blocks, slot_ids):
    """Write M B=1 prefill caches into slots ``slot_ids`` of the big
    decode cache in ONE fused program — the cross-group splice for
    disaggregated prefill: a boundary with M admitted KV-transfer blocks
    costs a single donated dispatch instead of M per-slot writes.

    ``blocks`` is the list of M prefill-cache trees (or a pre-stacked
    tree with leaves ``[L, M, P, ...]``); trace this whole function under
    one ``jax.jit`` so the stack fuses with the scatter — stacking
    outside jit costs one host dispatch per cache leaf.  The leaf scatter
    is ``kernels/ops.splice_blocks`` — mesh-aware through
    ``models/sharding.seq_shard_layout``, so the splice stays shard-local
    on sequence-sharded meshes.  Int8 destinations quantize the bf16
    blocks on the way, exactly like the per-slot write path
    (:func:`write_slot_cache`) — the emitted bytes are identical, only
    the dispatch count changes.
    """
    from repro.kernels.ops import splice_blocks

    if isinstance(blocks, (list, tuple)):
        blocks = stack_prefill_blocks(blocks)

    def upd(dst, src):
        return splice_blocks(dst, src, slot_ids)

    return _merge_cache(cfg, big_cache, blocks, upd)


def stack_prefill_blocks(caches):
    """Stack M B=1 prefill caches on the slot axis (axis 1, after the
    leading layer dim) into the ``blocks`` tree ``splice_slot_caches``
    consumes."""
    return jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=1), *caches)


def admit_boundary(cfg, big_cache, blocks, slot_ids, cur_tok, lengths,
                   remaining, done, last_logits, prompt_lens, max_news,
                   *, eos_id: int = -1):
    """ONE donated program for a whole admission boundary: splice the
    admitted prefill blocks into the big decode cache
    (:func:`splice_slot_caches`) AND scatter all four decode-state
    vectors (``kernels/ops.admit_state``) in a single dispatch — a
    boundary used to cost three (splice or per-slot writes, then
    ``admit_slots``, then the next decode launch saw re-uploaded state).

    All vector arguments are PADDED to the engine's fixed slot width by
    repeating the last real entry (``blocks`` likewise repeats the last
    block): duplicate writes carry identical bytes, so the result is
    unchanged while every admitted-count reuses one compiled program and
    one input sharding.  Returns ``(cache, cur_tok, lengths, remaining,
    done, first)`` — the big cache and the state vectors are donated, so
    callers must rebind from the returns.
    """
    from repro.kernels.ops import admit_state

    cache = splice_slot_caches(cfg, big_cache, blocks, slot_ids)
    cur_tok, lengths, remaining, done, first = admit_state(
        cur_tok, lengths, remaining, done, slot_ids, last_logits,
        prompt_lens, max_news, eos_id=eos_id)
    return cache, cur_tok, lengths, remaining, done, first


def _cfg_program(fn, cfg, name: str):
    """``fn`` with ``cfg`` bound, named so that its program traces as
    ``jit_<name>`` (a bare partial or lambda traces as ``jit__unknown``
    and cannot be told apart in a device trace)."""
    bound = functools.partial(fn, cfg)
    bound.__name__ = name
    return bound


@dataclass
class ServeRequest:
    """One unit of work for the continuous-batching queue."""
    uid: int
    prompt: np.ndarray                 # [P] int32 (padded to the engine's P)
    max_new: int
    frontend: Optional[np.ndarray] = None
    task: str = ""                     # HeteroRuntime registry key ("" =
                                       # sole registered task)


@dataclass
class RequestOutput:
    uid: int
    tokens: np.ndarray                 # [n_generated] int32
    admitted_step: int
    finished_step: int


@dataclass
class ContinuousStats:
    requests: int
    total_tokens: int
    decode_steps: int
    prefill_s: float
    decode_s: float
    tokens_per_s: float
    occupancy: float                   # mean fraction of busy slots per step
    host_syncs: int = 0                # device→host materializations (one
                                       # per macro-step + one per admission
                                       # phase; per-token when macro_steps=0)
    macro_dispatches: int = 0          # fused K-token macro-steps executed
                                       # (wave launches count M each)
    wave_launches: int = 0             # host launches of the fused decode
                                       # driver (== macro_dispatches unless
                                       # wave_steps > 1)
    t_per_macro_step_s: float = 0.0    # decode wall per fused dispatch
    t_prefill_overlap_s: float = 0.0   # host wall spent dispatching shadow
                                       # prefills behind the in-flight decode
                                       # macro-step (off the critical path)
    admission_stalls: int = 0          # boundaries where live slots waited
                                       # on a prefill (shadow miss, or every
                                       # admission phase when not overlapped)
    shadow_prefills: int = 0           # speculative prefills dispatched
    prefill_offloaded: int = 0         # shadows dispatched to the dedicated
                                       # prefill group (disaggregated)
    t_kv_transfer_s: float = 0.0       # priced KV-transfer hop total for
                                       # blocks spliced back from the
                                       # prefill group
    prefill_fallbacks: int = 0         # prefill-group failures recovered by
                                       # falling back to local shadow prefill
    # --- scale-out timing decomposition (PR 6) -------------------------
    # Boundary wall is split into buckets so the emulated multi-host
    # harness (benchmarks/scaleout.py) can see WHERE time goes as the
    # device count grows.  On the fused paths the invariant
    #     decode_s == t_dispatch_s + t_await_s
    # holds exactly (same float additions); all four stay 0.0 on the
    # per-step macro_steps=0 path.
    t_splice_s: float = 0.0            # wall dispatching the fused cross-
                                       # group cache splice (disaggregated
                                       # boundaries)
    t_slot_write_s: float = 0.0        # wall dispatching per-slot big-cache
                                       # writes (local-shadow / boundary
                                       # admission)
    t_dispatch_s: float = 0.0          # host wall launching fused decode
                                       # macro-steps (async dispatch cost —
                                       # grows with program size, not data)
    t_await_s: float = 0.0             # wall blocked on the token-block
                                       # fetch (device execution, incl. any
                                       # collectives the mesh inserts)
    # --- content-aware KV reuse (PR 7: serving/prefix_cache.py) --------
    prefix_hits: int = 0               # requests that reused >= 1 cached
                                       # prefix block (full hits included)
    prefix_blocks_reused: int = 0      # cached KV blocks reused across
                                       # all admitted requests
    prefill_flops_avoided: float = 0.0 # analytic prefill FLOPs skipped by
                                       # resuming from cached prefixes
    prefill_flops_total: float = 0.0   # analytic prefill FLOPs the run
                                       # would cost with no cache (the
                                       # denominator of the avoided ratio)
    kv_hop_bytes_raw: float = 0.0      # prefill→decode KV-transfer bytes
                                       # before sender-side compaction
    kv_hop_bytes_wire: float = 0.0     # ... and what actually crossed the
                                       # link (tail-only, masked-compact)


@dataclass
class _Shadow:
    """One in-flight speculative prefill (shadow slot)."""
    req: ServeRequest
    logits: Any                        # last-token logits (in flight)
    cache: Any                         # B=1 prefill cache; None for
                                       # single-token requests (logits-only)
    remote: bool = False               # lives on the dedicated prefill
                                       # group until fetched
    hit: Any = None                    # PrefixHit backing a resumed remote
                                       # prefill: carries the hub-resident
                                       # prefix for the compacted fetch and
                                       # the pins released after it


@dataclass
class _Slot:
    uid: int = -1
    remaining: int = 0
    tokens: List[int] = field(default_factory=list)
    admitted_step: int = 0
    finished_at: int = -1              # micro-step the last token landed on
                                       # (eviction may lag to the boundary)

    @property
    def busy(self) -> bool:
        return self.uid >= 0


class ContinuousServingEngine:
    """Slot-based continuous batching with greedy decoding.

    Fixed `slots`-wide decode batch; requests are admitted into free slots
    (B=1 prefill written into the slot's cache region), every macro-step
    advances all slots up to ``macro_steps`` tokens with per-slot cache
    indices, and finished requests are evicted at the next macro-step
    boundary (lagging their final token by at most ``macro_steps - 1``
    micro-steps), freeing the slot for the next queued request.  Token
    streams are bit-identical to static batching and to the per-step loop
    because each slot attends only to its own positions 0..len-1 (per-slot
    length masks) — a frozen slot decoding junk until the boundary cannot
    leak into live slots.

    The decode state (``cur_tok`` / ``lengths`` / ``remaining`` / ``done``)
    is device-resident across macro-steps; the host fetches exactly one
    ``[K, slots]`` token block per macro-step and one batched first-token
    block per admission phase.  All decode-path programs donate their cache
    (and state) arguments, so the KV buffers are updated in place.
    ``macro_steps=0`` preserves the pre-fusion per-token host loop for A/B
    benchmarking.

    ``overlap_admission=True`` (the default) runs the fused path with
    speculative shadow-slot prefill: see the module docstring.  Per-request
    token streams are bit-identical across all three schedules (overlapped,
    boundary-blocking, per-step) — admission timing moves, tokens do not.
    """

    def __init__(self, cfg, params, *, slots: int = 4, max_len: int = 512,
                 use_pallas: Union[bool, str] = "auto",
                 eos_id: Optional[int] = None,
                 macro_steps: int = 8,
                 wave_steps: int = 1,
                 overlap_admission: bool = True,
                 async_dispatch: bool = True,
                 prefill_worker: Optional[Any] = None,
                 prefix_cache: Optional[Any] = None,
                 share_from: Optional["ContinuousServingEngine"] = None,
                 device: Optional[Any] = None):
        """`share_from`: another engine over the SAME cfg whose jitted
        prefill/step/slot-write/decode-loop programs this one reuses —
        jax.jit caches per function object, so sibling node-group engines
        would otherwise recompile byte-identical programs.  (Programs are
        traced with the mesh active at first call — don't share across
        different mesh contexts.)

        ``prefill_worker``: a :class:`repro.serving.prefill.PrefillWorker`
        bound to the topology's dedicated prefill group.  On the
        overlapped fused path, shadow prefills are then dispatched to the
        prefill group instead of the decode group and their KV blocks
        spliced back at macro boundaries (disaggregated prefill); if the
        worker dies or ``prefill_remote`` is False the engine falls back
        to PR-4 local shadow prefill with bit-identical token streams.

        ``prefix_cache``: a :class:`repro.serving.prefix_cache.PrefixCache`
        shared by every engine of the task (hub-side).  Every admission
        path consults it before prefilling: exact full-prompt hits skip
        prefill (and, disaggregated, the KV hop) entirely; partial hits
        resume prefill from the matched block span; misses prefill cold.
        All finished prefills are re-indexed.  Token streams stay
        bit-identical — exact-match radix reuse returns the same bytes a
        cold prefill would compute.

        ``wave_steps=M`` (opt-in, fused path only): run M macro-steps per
        host launch through :func:`make_wave_driver` — admission moves to
        M-boundaries, streams stay bit-identical.

        ``async_dispatch`` (default True, overlapped path): launch fused
        decode programs on a background thread so ``t_dispatch_s``
        measures the host-side launch tax and the device execution lands
        in ``t_await_s`` (see :class:`_DecodeLauncher`).

        ``device``: pin the engine to one device (a node group's chip):
        the params are committed there, and the KV cache, the decode
        state, every program the engine launches and every prefill block
        it admits live there too.  None keeps JAX's default placement.
        Inside an ``activation_sharding`` mesh the programs run mesh-wide
        and the pin is dropped (the PrefillWorker does the same)."""
        from repro.models.sharding import active_mesh
        self.cfg = cfg
        self.device = device if active_mesh() is None else None
        self.params = params if self.device is None \
            else jax.device_put(params, self.device)
        self.prefix_cache = prefix_cache
        self.slots, self.max_len, self.eos_id = slots, max_len, eos_id
        self.macro_steps = int(macro_steps)
        self.wave_steps = int(wave_steps)
        if self.wave_steps < 1:
            raise ValueError(f"wave_steps must be >= 1, got {wave_steps}")
        if self.wave_steps > 1 and self.macro_steps == 0:
            raise ValueError("wave_steps > 1 needs the fused decode path "
                             "(macro_steps > 0)")
        self.async_dispatch = bool(async_dispatch)
        self.overlap_admission = bool(overlap_admission)
        self.prefill_worker = prefill_worker
        if prefill_worker is not None and (
                self.macro_steps == 0 or not self.overlap_admission):
            # only the overlapped fused path consults the worker — a
            # silently idle prefill group is a misconfiguration, not a
            # fallback
            raise ValueError(
                "disaggregated prefill (prefill_worker=) requires the "
                "overlapped fused path: macro_steps > 0 and "
                "overlap_admission=True")
        self.prefill_remote = prefill_worker is not None  # routing flag the
        # PrefillRouter flips per wave (True = disaggregate when healthy)
        self._use_pallas = resolve_use_pallas(use_pallas)
        if share_from is not None and share_from.cfg is cfg:
            self.prefill = share_from.prefill
            self.step = share_from.step
            self._write_slot = share_from._write_slot
            self._splice_slots = share_from._splice_slots
            self._admit_boundary = share_from._admit_boundary
            self._loops = share_from._loops
            self._waves = share_from._waves
        else:
            self.prefill = jax.jit(
                make_prefill_step(cfg, use_pallas=self._use_pallas))
            self.step = jax.jit(
                make_serve_step(cfg, use_pallas=self._use_pallas),
                donate_argnums=(1,))
            self._write_slot = jax.jit(
                _cfg_program(write_slot_cache, cfg, "write_slot"),
                donate_argnums=(0,))
            # fused cross-group splice: takes the LIST of M block trees so
            # the stack traces into the same program as the scatter (one
            # dispatch per boundary).  Donates the big cache; the blocks
            # are consumed too, but their [1,P,..] shapes can alias no
            # output, so XLA donation would be a no-op warning — the
            # fault tier instead hard-deletes them after the call to
            # enforce the consumed-after-splice invariant
            self._splice_slots = jax.jit(
                _cfg_program(splice_slot_caches, cfg, "splice_slots"),
                donate_argnums=(0,))
            # fused boundary: cache splice + state scatter in ONE donated
            # program (big cache + all four state vectors); the blocks
            # are consumed-by-contract exactly like _splice_slots'
            self._admit_boundary = jax.jit(
                _cfg_program(admit_boundary, cfg, "admit_boundary"),
                static_argnames=("eos_id",),
                donate_argnums=(0, 3, 4, 5, 6))
            self._loops: Dict[Tuple[int, Optional[int]], Any] = {}
            self._waves: Dict[Tuple[int, int, Optional[int]], Any] = {}
        self._offset = cfg.frontend_tokens if cfg.family == "vlm" else 0
        # live token-streaming hook for the CURRENT run (set per run():
        # the ingress frontend listens; None = batch mode, no streaming)
        self._on_tokens: Optional[Callable[[int, int, List[int]],
                                           None]] = None
        # per-request phase stamps of the CURRENT run (set per run())
        self._on_stamp: Optional[Callable[[int, str, float], None]] = None
        # the launcher thread re-enters the engine's mesh (thread-local in
        # jax); capture it at construction, like the programs' tracings
        self._launcher = _DecodeLauncher(active_mesh()) \
            if self.async_dispatch else None
        # 1 when the decode programs update the KV cache in place (the
        # ``cache_inplace`` stat of every ``engine.launch`` span)
        self._cache_inplace = int(decode_inplace(
            jax.eval_shape(lambda: M.init_cache(cfg, slots, max_len)),
            active_mesh()))

    def _get_loop(self, K: int):
        return _loop_program(self.cfg, self._loops, K, self.eos_id,
                             self._use_pallas)

    def _get_wave(self, K: int, M: int):
        return _wave_program(self.cfg, self._waves, K, M, self.eos_id,
                             self._use_pallas)

    # ------------------------------------------------------------------
    def _placed(self):
        """Context under which a run creates its arrays: on the pinned
        device, so the cache, the decode state and the admission vectors
        never land on device 0 first."""
        if self.device is None:
            return contextlib.nullcontext()
        return jax.default_device(self.device)

    def init_state(self):
        """A run's empty KV cache and device-resident decode state
        ``(cache, cur_tok, lengths, remaining, done)``; ``done=True``
        marks free/frozen slots.  Created on the pinned device.  On a
        mesh the state vectors are committed replicated (sticky), so
        the FIRST fused dispatch already sees the input shardings every
        later dispatch carries back — no steady-state re-shard."""
        from repro.models.sharding import put_replicated
        with self._placed():
            lengths, cur_tok, remaining, done = put_replicated((
                jnp.zeros((self.slots,), jnp.int32),
                jnp.zeros((self.slots,), jnp.int32),
                jnp.zeros((self.slots,), jnp.int32),
                jnp.ones((self.slots,), bool)))
            cache = M.init_cache(self.cfg, self.slots, self.max_len,
                                 dtype=self.cfg.jnp_dtype)
        return cache, cur_tok, lengths, remaining, done

    def _local(self, tree):
        """Move a prefix-cache hit's arrays (the trie is shared by every
        engine of the task, so a hit may live on another group's device)
        onto the pinned device."""
        if self.device is None or tree is None:
            return tree
        return jax.device_put(tree, self.device)

    def _make_batch(self, req: ServeRequest):
        # HOST-side (numpy) batch: the jitted prefill uploads it at call
        # time anyway, and keeping it off-device lets the prefill pool's
        # content-hash affinity key read the prompt bytes without a
        # device->host fetch — eagerly uploading here put one host sync
        # on every pool dispatch
        batch = {"tokens": np.asarray(req.prompt)[None]}
        if req.frontend is not None:
            batch["frontend"] = np.asarray(req.frontend)[None]
        return batch

    def _account_hit(self, hit) -> None:
        """Fold one PrefixHit (hit or miss) into the run's counters."""
        if hit.hit:
            self._pc_hits += 1
            self._pc_blocks += hit.blocks
        self._pc_flops_avoided += hit.flops_avoided
        self._pc_flops_total += hit.flops_total

    def _prefill_via_cache(self, req: ServeRequest):
        """B=1 LOCAL prefill through the prefix cache: consult the trie,
        serve an exact full-prompt hit without touching the device,
        resume from a partial hit (``batch["prefix"]``), and re-index
        whatever was prefilled before the caller consumes it."""
        pc = self.prefix_cache
        batch = self._make_batch(req)
        if pc is None:
            return self.prefill(self.params, batch)
        hit = pc.match(req.prompt, frontend=req.frontend)
        self._account_hit(hit)
        if hit.full is not None:
            return self._local(hit.full)
        if hit.prefix is not None:
            batch = dict(batch, prefix=self._local(hit.prefix))
        logits, cache = self.prefill(self.params, batch)
        pc.insert(req.prompt, logits, cache, frontend=req.frontend)
        pc.release(hit)
        return logits, cache

    # ------------------------------------------------------------------
    def _emit_tokens(self, uid: int, start: int, toks) -> None:
        """Stream host-landed tokens to the run's ``on_tokens`` hook as
        ``(uid, absolute position of toks[0], tokens)``.  Positions make
        replays (a re-queued request re-served on a survivor) safe to
        deduplicate downstream — streams are bit-identical, so the same
        position always carries the same token."""
        if self._on_tokens is not None and len(toks):
            self._on_tokens(uid, start, [int(t) for t in toks])

    def _stamp(self, event: str, uids) -> None:
        """Report ``event`` at this instant (``perf_counter``) for every
        request in ``uids`` to the run's ``on_stamp`` hook.  The engine
        stamps ``"admit"``: the boundary that splices a request into a
        slot (or flushes a single-token one) has been dispatched."""
        if self._on_stamp is not None:
            t = time.perf_counter()
            for uid in uids:
                self._on_stamp(uid, event, t)

    def _consume_block(self, block, slot_states, K: int,
                       step_no: int) -> Tuple[int, float]:
        """Host bookkeeping for one fetched ``[K, slots]`` token block,
        mirroring the device's freeze logic exactly: each live slot
        consumes tokens until its budget runs out or eos lands.  Shared
        by the boundary and overlapped schedules — one source of truth
        for eos trimming, ``finished_at`` stamping and occupancy.
        Returns (steps_used, busy-occupancy increment)."""
        eos = self.eos_id
        consumed = np.zeros((self.slots,), np.int64)
        for i, s in enumerate(slot_states):
            if not s.busy or s.remaining <= 0 or (
                    eos is not None and s.tokens and s.tokens[-1] == eos):
                continue
            col = block[:min(s.remaining, K), i]
            if eos is not None:
                hits = np.nonzero(col == eos)[0]
                if hits.size:
                    col = col[:hits[0] + 1]
            s.tokens.extend(int(x) for x in col)
            self._emit_tokens(s.uid, len(s.tokens) - len(col), col)
            s.remaining -= len(col)
            consumed[i] = len(col)
            if s.remaining <= 0 or (eos is not None
                                    and s.tokens[-1] == eos):
                s.finished_at = step_no + len(col)
        steps_used = int(consumed.max())
        busy_inc = sum(float((consumed > j).sum()) / self.slots
                       for j in range(steps_used))
        return steps_used, busy_inc

    # ------------------------------------------------------------------
    def _pad_admit_args(self, entries):
        """Build the FIXED-WIDTH admission vectors for ``entries`` (a list
        of ``(slot, req, last_logits)``), padded to the engine's slot
        count by repeating the last real entry.  Padded scatter writes
        carry identical values, so they are idempotent — and every
        admitted-count reuses one jitted program and one input sharding
        instead of tracing/re-sharding per distinct width.  Returns
        ``(slot_ids [slots], logits [slots, V], prompt_lens [slots],
        max_news [slots])``."""
        pad = self.slots - len(entries)
        ids = [e[0] for e in entries] + [entries[-1][0]] * pad
        logits = [e[2] for e in entries] + [entries[-1][2]] * pad
        plens = [len(e[1].prompt) + self._offset for e in entries]
        plens += [plens[-1]] * pad
        mnews = [e[1].max_new for e in entries]
        mnews += [mnews[-1]] * pad
        return (jnp.asarray(ids, jnp.int32),
                jnp.concatenate(logits, axis=0),
                jnp.asarray(plens, jnp.int32),
                jnp.asarray(mnews, jnp.int32))

    def _per_step_advance(self, cache, cur_tok, lengths, done):
        """One pre-fusion (``macro_steps=0``) decode step with the state
        advance ON DEVICE: greedy-argmax the next token, move only the
        live (``~done``) slots forward, and fetch a single stream-facing
        NumPy copy of the token vector — the ONE host sync of the step.
        Busy slots are exactly ``~done`` when this runs (eviction froze
        every finished slot, and zero-budget / eos-at-admission slots are
        evicted before they ever decode), so the carried state never
        round-trips through the host: the old path re-uploaded
        ``new_tok``/``busy`` via ``jnp.asarray`` every step."""
        logits, cache = self.step(self.params, cache,
                                  cur_tok[:, None], lengths)
        new_tok_dev = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        adv = jnp.logical_not(done)
        cur_tok = jnp.where(adv, new_tok_dev, cur_tok)
        lengths = lengths + adv
        return cache, cur_tok, lengths, np.asarray(new_tok_dev)

    def _admit_free_slots(self, pending, slot_states, cache, cur_tok,
                          lengths, remaining, done, step_no: int):
        """Admit queued requests into every free slot.  Two phases so the
        B=1 prefills overlap: dispatch ALL prefills + slot writes first
        (JAX async dispatch), then scatter the decode-state vectors in
        ONE padded ``admit_slots`` dispatch and materialize the admitted
        slots' first tokens in ONE batched fetch (a per-slot host
        ``.at[].set(int(argmax))`` loop would re-upload state and sync
        once per admission).  Returns the wall spent dispatching the
        per-slot big-cache writes as the last element (the scale-out
        harness's slot-write bucket)."""
        admitted = []
        t_write = 0.0
        for slot, s in enumerate(slot_states):
            if not s.busy and pending:
                req = pending.popleft()
                last_logits, pre_cache = self._prefill_via_cache(req)
                tw0 = time.perf_counter()
                cache = self._write_slot(cache, pre_cache, slot)
                t_write += time.perf_counter() - tw0
                admitted.append((slot, req, last_logits))
        syncs = 0
        if admitted:
            from repro.kernels import ops as ops_mod
            ids, logits, plens, mnews = self._pad_admit_args(admitted)
            cur_tok, lengths, remaining, done, first_dev = \
                ops_mod.admit_slots(
                    cur_tok, lengths, remaining, done, ids, logits, plens,
                    mnews,
                    eos_id=-1 if self.eos_id is None else int(self.eos_id))
            self._stamp("admit", [req.uid for _, req, _ in admitted])
            firsts = np.asarray(first_dev)
            syncs = 1
            for (slot, req, _), first in zip(admitted, firsts):
                slot_states[slot] = _Slot(
                    uid=req.uid, remaining=req.max_new - 1,
                    tokens=[int(first)], admitted_step=step_no)
                self._emit_tokens(req.uid, 0, [int(first)])
        return cache, cur_tok, lengths, remaining, done, syncs, t_write

    # ------------------------------------------------------------------
    def run(self, requests: Sequence[ServeRequest],
            on_tokens: Optional[Callable[[int, int, List[int]],
                                         None]] = None,
            on_stamp: Optional[Callable[[int, str, float], None]] = None
            ) -> Tuple[List[RequestOutput], ContinuousStats]:
        """Serve ``requests`` to completion.  ``on_tokens(uid, start,
        tokens)`` streams host-landed tokens; ``on_stamp(uid, event, t)``
        receives each request's phase stamps (:meth:`_stamp`)."""
        self._on_tokens = on_tokens
        self._on_stamp = on_stamp
        if not requests:
            return [], ContinuousStats(0, 0, 0, 0.0, 0.0, 0.0, 0.0)
        P = len(requests[0].prompt)
        assert all(len(r.prompt) == P for r in requests), \
            "pad prompts to a common length before submission"
        assert all(r.max_new >= 1 for r in requests)
        assert P + self._offset + max(r.max_new for r in requests) \
            <= self.max_len, "max_len too small for prompt + generation"
        # per-run prefix-cache / KV-hop accounting (the PrefixCache object
        # is shared across engines and runs; these are THIS run's deltas)
        self._pc_hits = self._pc_blocks = 0
        self._pc_flops_avoided = self._pc_flops_total = 0.0
        self._kv_raw = self._kv_wire = 0.0
        with self._placed():
            if self.macro_steps > 0 and self.overlap_admission:
                return self._run_overlapped(requests)
            return self._run_boundary(requests)

    # ------------------------------------------------------------------
    def _run_boundary(self, requests: Sequence[ServeRequest]
                      ) -> Tuple[List[RequestOutput], ContinuousStats]:
        """Boundary-blocking admission (pre-overlap schedule): every macro
        boundary with free slots runs prefill while all live slots wait.
        Kept as the A/B baseline — token streams are identical to the
        overlapped schedule."""
        K = self.macro_steps
        pending = deque(requests)
        slot_states: List[_Slot] = [_Slot() for _ in range(self.slots)]
        cache, cur_tok, lengths, remaining, done = self.init_state()
        outputs: List[RequestOutput] = []
        step_no = 0
        busy_acc = 0.0
        t_prefill = t_decode = 0.0
        t_slot_write = t_dispatch = t_await = 0.0
        host_syncs = 0
        dispatches = 0
        wave_launches = 0
        stalls = 0

        def _finished(s: _Slot) -> bool:
            return s.busy and (s.remaining <= 0
                               or (self.eos_id is not None
                                   and s.tokens[-1] == self.eos_id))

        while pending or any(s.busy for s in slot_states):
            # --- admit into every free slot --------------------------
            t0 = time.perf_counter()
            live_before = any(s.busy for s in slot_states)
            cache, cur_tok, lengths, remaining, done, n_sync, tw = \
                self._admit_free_slots(pending, slot_states, cache, cur_tok,
                                       lengths, remaining, done, step_no)
            t_slot_write += tw
            host_syncs += n_sync
            if n_sync and live_before:
                stalls += 1     # live slots sat idle through this prefill
            t_prefill += time.perf_counter() - t0

            # --- evict completed slots (at admission or post-decode) --
            freed = False
            for i, s in enumerate(slot_states):
                if _finished(s):
                    outputs.append(RequestOutput(
                        uid=s.uid, tokens=np.asarray(s.tokens, np.int32),
                        admitted_step=s.admitted_step,
                        finished_step=s.finished_at if s.finished_at >= 0
                        else step_no))
                    slot_states[i] = _Slot()
                    done = done.at[i].set(True)   # freeze the freed slot
                    freed = True
            if freed and pending:
                continue  # refill freed slots before the next decode step
            if not any(s.busy for s in slot_states):
                break

            if K == 0:
                # --- pre-fusion loop: one step, one sync per token ----
                t0 = time.perf_counter()
                cache, cur_tok, lengths, new_tok = self._per_step_advance(
                    cache, cur_tok, lengths, done)
                host_syncs += 1
                t_decode += time.perf_counter() - t0
                step_no += 1
                busy_acc += sum(
                    1 for s in slot_states if s.busy) / self.slots
                for i, s in enumerate(slot_states):
                    if s.busy:
                        s.tokens.append(int(new_tok[i]))
                        self._emit_tokens(s.uid, len(s.tokens) - 1,
                                          [s.tokens[-1]])
                        s.remaining -= 1
                continue

            # --- one fused macro-step (or wave of M) over all slots ---
            # dispatch (async launch) and await (device execution) are
            # bucketed separately for the scale-out harness; t_decode
            # stays their exact sum
            W = self.wave_steps
            fn = self._get_wave(K, W) if W > 1 else self._get_loop(K)
            t0 = time.perf_counter()
            toks, cache, cur_tok, lengths, remaining, done = \
                fn(self.params, cache, cur_tok, lengths, remaining, done)
            t1 = time.perf_counter()
            block = np.asarray(toks)      # the ONE host sync
            t2 = time.perf_counter()
            if block.ndim == 3:           # wave driver: [W, K, slots]
                block = block.reshape(-1, self.slots)
            t_dispatch += t1 - t0
            t_await += t2 - t1
            host_syncs += 1
            dispatches += W
            wave_launches += 1

            steps_used, busy_inc = self._consume_block(
                block, slot_states, W * K, step_no)
            busy_acc += busy_inc
            step_no += steps_used

        jax.block_until_ready(cache)
        total_tokens = sum(len(o.tokens) for o in outputs)
        if dispatches:
            # fused run: t_decode accumulated nothing per-step, so the
            # bucket-sum invariant decode_s == t_dispatch_s + t_await_s
            # holds exactly
            t_decode = t_dispatch + t_await
        wall = t_prefill + t_decode
        stats = ContinuousStats(
            requests=len(outputs), total_tokens=total_tokens,
            decode_steps=step_no, prefill_s=t_prefill, decode_s=t_decode,
            tokens_per_s=total_tokens / max(wall, 1e-9),
            occupancy=busy_acc / max(step_no, 1),
            host_syncs=host_syncs, macro_dispatches=dispatches,
            wave_launches=wave_launches,
            t_per_macro_step_s=t_decode / max(dispatches, 1) if dispatches
            else 0.0,
            admission_stalls=stalls,
            t_slot_write_s=t_slot_write,
            t_dispatch_s=t_dispatch, t_await_s=t_await,
            prefix_hits=self._pc_hits,
            prefix_blocks_reused=self._pc_blocks,
            prefill_flops_avoided=self._pc_flops_avoided,
            prefill_flops_total=self._pc_flops_total,
            kv_hop_bytes_raw=self._kv_raw,
            kv_hop_bytes_wire=self._kv_wire)
        outputs.sort(key=lambda o: o.uid)
        return outputs, stats

    # ------------------------------------------------------------------
    def _run_overlapped(self, requests: Sequence[ServeRequest]
                        ) -> Tuple[List[RequestOutput], ContinuousStats]:
        """Speculative overlapped admission (the fused-path default).

        Per iteration, in dispatch order (all async — OffloadEngine's
        dispatch-all-then-await pattern):

          1. splice ready shadow prefills into free slots: ONE fused
             donated boundary program (``admit_boundary`` = cache splice
             + decode-state scatter) over FIXED-WIDTH padded admission
             vectors, so every boundary costs one dispatch and one
             compiled program regardless of how many slots it fills
             (the only prefill work on the critical path; a shadow miss
             here with live slots waiting counts as an admission stall),
          2. launch the decode macro-step for the live slots — one
             fused K-step program, or the ``wave_steps=M`` jitted wave
             driver covering M macro-steps per host launch; with
             ``async_dispatch`` the launch happens on the
             :class:`_DecodeLauncher` thread so ``t_dispatch_s`` is the
             true submit cost,
          3. top the shadow queue back up to ``slots`` speculative B=1
             prefills from the pending queue — these execute behind the
             in-flight macro-step, off the critical path,
          4. await the macro-step's ``[M*K, slots]`` token block (the
             ONE host sync), piggybacking the spliced slots' first
             tokens on it (they were enqueued before the decode loop, so
             the fetch returns immediately), then evict finished slots.

        Shadows are request-keyed, not slot-keyed, so a speculative
        prefill is never wasted — at worst it waits another boundary for a
        slot to free.  Token streams are bit-identical to the boundary and
        per-step schedules: each slot attends only to its own positions,
        and admission still lands at macro-step boundaries.

        With a ``prefill_worker`` (disaggregated prefill), shadows are
        dispatched onto the dedicated prefill group instead and their KV
        blocks transferred back ("localized") at the boundary, then all
        admitted blocks — remote and local alike — are spliced in ONE
        donated cross-group splice (``splice_slot_caches``) instead of M
        per-slot writes.  A worker failure at dispatch or fetch falls
        back to local shadow prefill for that request and all later ones:
        ``prefill_fallbacks`` counts the recoveries, the streams do not
        change.
        """
        K = self.macro_steps
        W = self.wave_steps
        eos = self.eos_id
        worker = self.prefill_worker
        pending = deque(requests)
        shadows: deque = deque()          # in-flight speculative prefills
        slot_states: List[_Slot] = [_Slot() for _ in range(self.slots)]
        cache, cur_tok, lengths, remaining, done = self.init_state()
        outputs: List[RequestOutput] = []
        step_no = 0
        busy_acc = 0.0
        t_prefill = t_decode = t_overlap = 0.0
        t_kv_transfer = 0.0
        t_splice = t_slot_write = t_dispatch = t_await = 0.0
        host_syncs = dispatches = stalls = n_shadow = 0
        wave_launches = 0
        n_offloaded = n_fallbacks = 0

        def _worker_error():
            from repro.core.offload import GroupUnavailableError
            from repro.serving.prefill import PrefillWorkerError
            return (PrefillWorkerError, GroupUnavailableError)

        def _use_remote() -> bool:
            return (worker is not None and self.prefill_remote
                    and worker.healthy)

        def _dispatch_shadow(inline: int):
            req = pending.popleft()
            with span("engine.prefill", uid=req.uid, inline=inline):
                _shadow_prefill(req)

        def _shadow_prefill(req: ServeRequest):
            nonlocal n_offloaded, n_fallbacks
            pc = self.prefix_cache
            hit = None
            if pc is not None:
                hit = pc.match(req.prompt, frontend=req.frontend)
                self._account_hit(hit)
                if hit.full is not None:
                    # exact full-prompt hit: no prefill anywhere and —
                    # disaggregated — no KV hop either; the assembled
                    # blocks are already hub-resident fresh copies
                    logits, cache = self._local(hit.full)
                    shadows.append(_Shadow(
                        req, logits,
                        None if req.max_new <= 1 else cache))
                    return
            batch = self._make_batch(req)
            if hit is not None and hit.prefix is not None:
                # partial hit: prefill resumes from the cached span —
                # local and remote dispatch alike run only the tail rows
                # (the worker moves the prefix onto its own device)
                batch = dict(batch, prefix=self._local(hit.prefix))
            # a single-token request never touches a slot: park only its
            # logits, so speculative singles cost no cache memory
            if _use_remote():
                try:
                    last_logits, pre_cache = worker.dispatch(batch)
                    shadows.append(_Shadow(
                        req, last_logits,
                        None if req.max_new <= 1 else pre_cache,
                        remote=True, hit=hit))
                    n_offloaded += 1
                    return
                except _worker_error():
                    n_fallbacks += 1    # group died: this and every later
                                        # shadow prefills locally
            last_logits, pre_cache = self.prefill(self.params, batch)
            if pc is not None:
                pc.insert(req.prompt, last_logits, pre_cache,
                          frontend=req.frontend)
                pc.release(hit)
            shadows.append(_Shadow(req, last_logits,
                                   None if req.max_new <= 1 else pre_cache))

        def _localize(sh: _Shadow) -> Tuple[_Shadow, int]:
            """Bring a shadow's block onto the decode group: the KV
            transfer hop for remote shadows (priced via the worker's
            LinkModel), a no-op for local ones.  A resumed remote prefill
            ships only its compacted tail over the hop (the hub already
            holds the prefix rows — ``prefix=`` below); raw and wire
            bytes both fold into the run's counters.  A fetch failure
            (group died after dispatch — possibly after earlier blocks
            were already admitted) re-prefills locally; the redo is
            EXPOSED prefill, so the caller counts it like a shadow
            miss."""
            nonlocal t_kv_transfer, n_fallbacks
            if not sh.remote:
                return sh, 0
            pc = self.prefix_cache
            prefix = sh.hit.prefix if sh.hit is not None else None
            try:
                logits, blk, t_hop = worker.fetch(sh.logits, sh.cache,
                                                  target=self.device,
                                                  prefix=prefix)
                t_kv_transfer += t_hop
                raw, wire = worker.last_fetch_bytes
                self._kv_raw += raw
                self._kv_wire += wire
                if pc is not None:
                    if blk is not None:
                        pc.insert(sh.req.prompt, logits, blk,
                                  frontend=sh.req.frontend)
                    pc.release(sh.hit)
                return _Shadow(sh.req, logits, blk), 0
            except _worker_error():
                n_fallbacks += 1
                batch = self._make_batch(sh.req)
                if prefix is not None:
                    # the hit's arrays outlive any eviction (plain
                    # references) — the local redo still resumes
                    batch = dict(batch, prefix=self._local(prefix))
                with span("engine.prefill", uid=sh.req.uid, inline=1):
                    logits, pre = self.prefill(self.params, batch)
                if pc is not None:
                    pc.insert(sh.req.prompt, logits, pre,
                              frontend=sh.req.frontend)
                    pc.release(sh.hit)
                return _Shadow(sh.req, logits,
                               None if sh.req.max_new <= 1 else pre), 1

        def _eos_done(s: _Slot) -> bool:
            return bool(s.tokens) and eos is not None and s.tokens[-1] == eos

        while pending or shadows or any(s.busy for s in slot_states):
            # --- 1. splice shadows into free slots (macro boundary) ----
            t0 = time.perf_counter()
            boundary_step = step_no
            n_live = sum(1 for s in slot_states if s.busy)
            inline = 0
            newly: List[Tuple[int, ServeRequest, Any]] = []
            blocks: List[Any] = []
            # singles need no slot: flush every parked one at each
            # boundary so they can never pile up in (or starve) the
            # shadow queue — they complete from their prefill logits at
            # the await below
            singles: List[_Shadow] = [sh for sh in shadows
                                      if sh.req.max_new <= 1]
            if singles:
                fillers = [sh for sh in shadows if sh.req.max_new > 1]
                shadows.clear()
                shadows.extend(fillers)
            free = (i for i, s in enumerate(slot_states) if not s.busy)
            slot = next(free, None)
            while slot is not None:
                if not shadows:
                    if not pending:
                        break
                    _dispatch_shadow(1)  # shadow miss: prefill exposed
                    inline += 1
                sh = shadows.popleft()
                if sh.req.max_new <= 1:
                    # single-token request: its one token is the prefill
                    # argmax — complete it without consuming the slot or
                    # riding a (frozen) macro-step
                    singles.append(sh)
                    continue
                sh, exposed = _localize(sh)
                inline += exposed
                newly.append((slot, sh.req, sh.logits))
                blocks.append(sh.cache)
                slot = next(free, None)
            if singles:
                # localize BEFORE the stall accounting below: a fetch
                # failure here re-prefills on the boundary critical path,
                # which is exposed prefill exactly like a slot shadow's
                flushed = []
                for sh in singles:
                    sh, exposed = _localize(sh)   # logits-only transfer
                    inline += exposed
                    flushed.append(sh)
                singles = flushed
            stalled = int(inline > 0 and n_live > 0)
            stalls += stalled   # decode waited on an un-overlapped prefill
            single_dev = first_dev = None
            with span("engine.boundary", admitted=len(newly), live=n_live,
                      stall=stalled):
                if singles:
                    single_dev = jnp.argmax(jnp.concatenate(
                        [sh.logits for sh in singles], axis=0),
                        axis=-1).astype(jnp.int32)
                    self._stamp("admit", [sh.req.uid for sh in singles])
                if newly:
                    # ONE fused donated boundary dispatch for all admitted
                    # blocks (KV transfers and local shadows alike): cache
                    # splice + decode-state scatter in a single program
                    # over FIXED-WIDTH padded vectors/blocks, so every
                    # boundary reuses one compiled program and one input
                    # sharding regardless of the admitted count.  The
                    # wall lands in the arm's bucket: splice
                    # (disaggregated) vs slot-write (local-shadow
                    # baseline) — never both.
                    tb0 = time.perf_counter()
                    ids, logits_cat, plens, mnews = \
                        self._pad_admit_args(newly)
                    blks = tuple(blocks + [blocks[-1]]
                                 * (self.slots - len(blocks)))
                    cache, cur_tok, lengths, remaining, done, first_dev = \
                        self._admit_boundary(
                            cache, blks, ids, cur_tok, lengths, remaining,
                            done, logits_cat, plens, mnews,
                            eos_id=-1 if eos is None else int(eos))
                    if worker is not None:
                        t_splice += time.perf_counter() - tb0
                    else:
                        t_slot_write += time.perf_counter() - tb0
                    self._stamp("admit", [req.uid for _, req, _ in newly])
                    for slot, req, _ in newly:
                        slot_states[slot] = _Slot(
                            uid=req.uid, remaining=req.max_new - 1,
                            tokens=[], admitted_step=step_no)
            t_prefill += time.perf_counter() - t0

            # --- 2. launch the macro-step (never waits on prefill) -----
            # skip slots the host already knows are spent (budget == 0);
            # an eos-on-first-token slot is frozen device-side instead.
            # With async_dispatch the launch runs on the launcher thread:
            # t_dispatch_s is the true submit cost, device execution
            # lands in t_await_s.  The donated carried buffers are handed
            # to the launch and MUST NOT be touched until the rebind at
            # step 4 (step 3 only dispatches fresh prefills).
            t0 = time.perf_counter()
            launch = None
            n_decoding = sum(1 for s in slot_states if s.busy
                             and s.remaining > 0 and not _eos_done(s))
            if n_decoding:
                with span("engine.launch", live=n_decoding,
                          cache_inplace=self._cache_inplace):
                    fn = self._get_wave(K, W) if W > 1 \
                        else self._get_loop(K)
                    if self._launcher is not None:
                        launch = self._launcher.submit(
                            fn, self.params, cache, cur_tok, lengths,
                            remaining, done)
                    else:
                        launch = fn(self.params, cache, cur_tok, lengths,
                                    remaining, done)
            t_dispatch += time.perf_counter() - t0

            # --- 3. top up speculative shadow prefills -----------------
            # depth counts only slot-FILLING shadows: singles never
            # consume a slot (and are flushed every boundary), so a run
            # of them must not stop the top-up short of the next
            # boundary's worth of fillers — that would put their prefill
            # back on the critical path.  At most `slots` B=1 prefill
            # caches are parked; parked singles hold logits only.
            t0o = time.perf_counter()
            with span("engine.topup", shadows=len(shadows)):
                while pending and sum(1 for sh in shadows
                                      if sh.req.max_new > 1) < self.slots:
                    _dispatch_shadow(0)
                    n_shadow += 1
            dt_overlap = time.perf_counter() - t0o
            t_overlap += dt_overlap

            # --- 4. the ONE await: token block + piggybacked firsts ----
            t0a = time.perf_counter()
            with span("engine.await"):
                block = None
                if launch is not None:
                    res = launch.result() if hasattr(launch, "result") \
                        else launch
                    toks, cache, cur_tok, lengths, remaining, done = res
                    block = np.asarray(toks)
                    if block.ndim == 3:       # wave driver: [W, K, slots]
                        block = block.reshape(-1, self.slots)
                    host_syncs += 1
                    dispatches += W
                    wave_launches += 1
                if first_dev is not None:
                    firsts = np.asarray(first_dev)   # enqueued before the
                    host_syncs += 1                  # loop: instant by now
                    for (slot, req, _), first in zip(newly, firsts):
                        slot_states[slot].tokens.append(int(first))
                        self._emit_tokens(req.uid, 0, [int(first)])
                if single_dev is not None:
                    host_syncs += 1
                    for sh, first in zip(singles, np.asarray(single_dev)):
                        outputs.append(RequestOutput(
                            uid=sh.req.uid,
                            tokens=np.asarray([int(first)], np.int32),
                            admitted_step=boundary_step,
                            finished_step=boundary_step))
                        self._emit_tokens(sh.req.uid, 0, [int(first)])
            t_await += time.perf_counter() - t0a

            if block is not None:
                steps_used, busy_inc = self._consume_block(
                    block, slot_states, W * K, step_no)
                busy_acc += busy_inc
                step_no += steps_used

            # --- evict finished slots (freed slots resplice at step 1;
            #     the device froze them the micro-step they finished) ----
            for i, s in enumerate(slot_states):
                if s.busy and (s.remaining <= 0 or _eos_done(s)):
                    outputs.append(RequestOutput(
                        uid=s.uid, tokens=np.asarray(s.tokens, np.int32),
                        admitted_step=s.admitted_step,
                        finished_step=s.finished_at if s.finished_at >= 0
                        else step_no))
                    slot_states[i] = _Slot()

        jax.block_until_ready(cache)
        total_tokens = sum(len(o.tokens) for o in outputs)
        # t_decode is DEFINED as dispatch + await so the bucket-sum
        # invariant the scale-out tier gates on holds exactly (step 3's
        # overlap window is excluded, as before)
        t_decode = t_dispatch + t_await
        wall = t_prefill + t_decode + t_overlap
        stats = ContinuousStats(
            requests=len(outputs), total_tokens=total_tokens,
            decode_steps=step_no, prefill_s=t_prefill, decode_s=t_decode,
            tokens_per_s=total_tokens / max(wall, 1e-9),
            occupancy=busy_acc / max(step_no, 1),
            host_syncs=host_syncs, macro_dispatches=dispatches,
            wave_launches=wave_launches,
            t_per_macro_step_s=t_decode / max(dispatches, 1) if dispatches
            else 0.0,
            t_prefill_overlap_s=t_overlap, admission_stalls=stalls,
            shadow_prefills=n_shadow,
            prefill_offloaded=n_offloaded,
            t_kv_transfer_s=t_kv_transfer,
            prefill_fallbacks=n_fallbacks,
            t_splice_s=t_splice, t_slot_write_s=t_slot_write,
            t_dispatch_s=t_dispatch, t_await_s=t_await,
            prefix_hits=self._pc_hits,
            prefix_blocks_reused=self._pc_blocks,
            prefill_flops_avoided=self._pc_flops_avoided,
            prefill_flops_total=self._pc_flops_total,
            kv_hop_bytes_raw=self._kv_raw,
            kv_hop_bytes_wire=self._kv_wire)
        outputs.sort(key=lambda o: o.uid)
        return outputs, stats
