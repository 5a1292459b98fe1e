"""Disaggregated prefill: a client for the dedicated prefill node group.

PR 4 overlapped shadow prefills with decode, but both still ran on the
*same* device group — every speculative B=1 prefill steals a dispatch
slot from the decode hot path.  ``PrefillWorker`` moves that work onto a
dedicated prefill group (``Topology.prefill_spoke``): prefill programs
are jitted against the prefill group's device, dispatched asynchronously
(dispatch-all-then-await, the OffloadEngine pattern — a dispatch never
blocks), and the finished KV block is *transferred* back to the decode
group at the macro boundary, where the engine splices it into a freed
slot with the fused cross-group splice (``kernels/ops.splice_blocks``).
The KV-transfer hop is priced with the topology edge's LinkModel
(``t_kv_transfer_s`` in telemetry) so the routing controller can weigh
prefill-offload against PR-4 local shadow prefill from live timings.

Failure semantics are explicit because a remote group can die mid-run:
``dispatch``/``fetch`` raise :class:`PrefillWorkerError` (or its
``PrefillWorkerTimeout`` subclass) once the worker is ``kill()``ed or an
injected fault fires, and the serving engine falls back to local shadow
prefill for that request and every one after — token streams are
bit-identical either way, only ``prefill_fallbacks`` records the event.
``inject_fault`` is the chaos-test hook (``tests/test_prefill_faults.py``)
that makes the fallback path enforceable in CI rather than a code path
that only ever runs during a real outage.
"""
from __future__ import annotations

from typing import Any, Optional, Tuple

import jax

# NOTE: repro.core is imported lazily inside methods — repro.core.__init__
# re-exports this module, so a top-level import here would be circular.
from repro.serving.engine import make_prefill_step, resolve_use_pallas


class PrefillWorkerError(RuntimeError):
    """The prefill group is unreachable (killed, crashed, partitioned)."""


class PrefillWorkerTimeout(PrefillWorkerError):
    """The prefill group did not answer within its deadline."""


def _tree_bytes(tree: Any) -> float:
    """Total payload bytes of a pytree of arrays (the KV-transfer size)."""
    return float(sum(leaf.size * leaf.dtype.itemsize
                     for leaf in jax.tree.leaves(tree)
                     if hasattr(leaf, "dtype")))


class PrefillWorker:
    """One task's prefill client for the dedicated prefill group.

    ``dispatch(batch)`` launches the jitted prefill on the prefill
    group's device and returns the (still in-flight) ``(logits, cache)``
    handles; ``fetch`` moves a finished block to the decode group's
    device and returns the priced KV-transfer latency.  The worker owns a
    device-pinned copy of the params (a no-copy alias when both groups
    share a device, as on CI hosts).

    ``healthy`` goes False on ``kill()`` or when an injected fault fires;
    every later call raises, and the engine stops routing prefills here.
    """

    def __init__(self, cfg, params, *, device, link=None,
                 distance: float = 1.0, name: str = "prefill",
                 use_pallas="auto", kv_keep_rate: Optional[float] = None,
                 share_from: Optional["PrefillWorker"] = None):
        """``kv_keep_rate``: the gated LOSSY hop knob — drop low-salience
        tail rows below this keep fraction on resumed transfers (None =
        lossless, the default; see ``serving/prefix_cache.compact_kv_hop``).
        ``share_from``: another worker over the SAME cfg + device whose
        jitted prefill program and pinned params this one aliases (the
        pool idiom — mirrors the engine's ``share_from``)."""
        self.cfg = cfg
        self.name = name
        self.link = link
        self.distance = float(distance)
        self.kv_keep_rate = kv_keep_rate
        # Inside an activation_sharding mesh the prefill program must run
        # mesh-wide like every other program (a single-device pin would
        # fight the sharding constraints) — the prefill group is then an
        # accounting entity, exactly like decode groups on shared devices.
        from repro.models.sharding import active_mesh
        if active_mesh() is not None:
            device = None
        self.device = device
        # placement by committed params, NOT jit(device=...): the
        # deprecated device= path re-validates/commits every param leaf
        # on every dispatch (~10% per-call overhead at these model
        # sizes); committing the params once pins the computation to the
        # prefill device with zero per-call cost
        if share_from is not None:
            # pool members alias the first worker's pinned params and
            # jitted program — one compile, one params copy per pool
            self.params = share_from.params
            self._prefill = share_from._prefill
        else:
            self.params = params if device is None \
                else jax.device_put(params, device)
            self._prefill = jax.jit(
                make_prefill_step(cfg,
                                  use_pallas=resolve_use_pallas(use_pallas)))
        self.healthy = True
        self._fault: Optional[Tuple[str, int, type]] = None
        self._calls = {"dispatch": 0, "fetch": 0}
        self._payload_cache: dict = {}   # tree-structure id -> bytes (every
        # block of a task has identical shapes, so walk the tree once)
        # accounting the router / telemetry read back
        self.dispatched = 0
        self.transferred_bytes = 0.0
        # raw vs on-the-wire bytes of every fetch (the satellite-6 fix:
        # the router must price what actually crosses the link, not the
        # uncompacted block size).  ``last_fetch_bytes`` is the (raw,
        # wire) pair of the most recent fetch — the engine folds it into
        # per-wave telemetry without changing fetch's return arity.
        self.kv_bytes_raw = 0.0
        self.kv_bytes_wire = 0.0
        self.last_fetch_bytes: Tuple[float, float] = (0.0, 0.0)

    # -- chaos hooks ----------------------------------------------------
    def kill(self) -> None:
        """Simulate losing the prefill group (node crash / partition)."""
        self.healthy = False

    def restore(self) -> None:
        """Simulate the prefill group coming back (node rebooted,
        partition healed).  Clears any armed fault and the call counters
        so the revived group starts clean — the router's bounded-backoff
        re-probe (``PrefillRouter.maybe_revive``) picks it up from the
        wave clock without operator action."""
        self.healthy = True
        self._fault = None
        self._calls = {"dispatch": 0, "fetch": 0}

    def inject_fault(self, kind: str = "dispatch", *, after: int = 0,
                     timeout: bool = False) -> None:
        """Arm a one-shot fault: the (``after``+1)-th ``kind`` call kills
        the worker and raises (``PrefillWorkerTimeout`` when ``timeout``).
        Chaos-test hook — production code never arms it."""
        if kind not in self._calls:
            raise ValueError(f"kind must be one of {sorted(self._calls)}")
        err = PrefillWorkerTimeout if timeout else PrefillWorkerError
        self._fault = (kind, int(after), err)

    def set_link(self, link, distance: Optional[float] = None) -> None:
        """Follow a mobility trace: future KV hops are priced on the live
        edge (the runtime updates this per wave from the LinkTrace, so
        the hop price tracks the traced bandwidth/distance)."""
        self.link = link
        if distance is not None:
            self.distance = float(distance)

    def _check(self, kind: str) -> None:
        if not self.healthy:
            raise PrefillWorkerError(
                f"prefill group {self.name!r} is down")
        self._calls[kind] += 1
        if self._fault is not None and self._fault[0] == kind \
                and self._calls[kind] > self._fault[1]:
            err = self._fault[2]
            self.healthy = False
            raise err(f"prefill group {self.name!r} "
                      f"{'timed out' if err is PrefillWorkerTimeout else 'died'}"
                      f" on {kind} #{self._calls[kind]}")

    # -- hot path -------------------------------------------------------
    def dispatch(self, batch) -> Tuple[Any, Any]:
        """Launch one B=1 prefill on the prefill group (async dispatch —
        returns in-flight handles, never blocks)."""
        self._check("dispatch")
        if self.device is not None and "prefix" in batch:
            # a prefix-cache hit lives decode-side: resume from a copy
            # on this group's device
            batch = dict(batch,
                         prefix=jax.device_put(batch["prefix"], self.device))
        out = self._prefill(self.params, batch)
        self.dispatched += 1
        return out

    def fetch(self, logits, cache=None, *, target=None, prefix=None):
        """Transfer a finished block back to the decode group.

        Returns ``(logits, cache, t_kv_transfer_s)`` with both arrays on
        ``target`` (the decode group's device; None leaves them where
        they are) and the transfer hop priced by the edge's LinkModel
        over the bytes that actually cross the link.  Raises if the group
        died in flight.

        When ``prefix`` is a prefix-cache hit's KV pytree (rows ``[0,q)``
        already resident decode-side), only the tail rows ``[q, S)`` are
        shipped, packed by the sender with the masked-compact kernel
        (``serving/prefix_cache.compact_kv_hop``); the full-length cache
        is reassembled here from the resident prefix + the compacted hop.
        Lossless by default; ``kv_keep_rate`` arms the lossy salience
        filter.  ``last_fetch_bytes`` records the (raw, wire) pair.
        """
        self._check("fetch")
        key = (tuple(logits.shape),
               None if cache is None
               else tuple(jax.tree.leaves(cache)[0].shape))
        raw = self._payload_cache.get(key)
        if raw is None:
            raw = _tree_bytes(logits) + (_tree_bytes(cache)
                                         if cache is not None else 0.0)
            self._payload_cache[key] = raw
        wire = raw
        packed = None
        if prefix is not None and cache is not None:
            from repro.serving.prefix_cache import compact_kv_hop
            q_rows = int(jax.tree.leaves(prefix)[0].shape[2])
            total = int(jax.tree.leaves(cache)[0].shape[2])
            if 0 < q_rows < total:   # full hits never dispatch; q==S is
                # a degenerate re-prefill — ship raw rather than pack 0 rows
                packed, wire_kv = compact_kv_hop(
                    cache, q_rows, keep_rate=self.kv_keep_rate)
                wire = _tree_bytes(logits) + wire_kv
        if target is not None and target != self.device:
            # an actual cross-device move; co-located groups (CI hosts,
            # mesh-wide workers) skip the copy — the hop is still PRICED
            # below, exactly like the engine's simulated link latencies.
            # With a packed hop only the compacted repr crosses; the raw
            # cache stays on the prefill device and is dropped.
            logits = jax.device_put(logits, target)
            if packed is not None:
                packed = {
                    name: ((jax.device_put(val[0], target),
                            jax.device_put(val[1], target), val[2])
                           if isinstance(val, tuple) else val)
                    for name, val in packed.items()}
            elif cache is not None:
                cache = jax.device_put(cache, target)
        if packed is not None:
            from repro.serving.prefix_cache import restore_kv_hop
            if target is not None:
                prefix = jax.device_put(prefix, target)
            cache = restore_kv_hop(packed, prefix)
        self.transferred_bytes += wire
        self.kv_bytes_raw += raw
        self.kv_bytes_wire += wire
        self.last_fetch_bytes = (raw, wire)
        t_hop = 0.0
        if self.link is not None:
            from repro.core.network import offload_latency
            t_hop = float(offload_latency(self.link, wire, self.distance))
        return logits, cache, t_hop

class PrefillWorkerPool:
    """N prefill workers behind one worker-shaped facade (satellite of
    the prefix-cache PR: a single worker serializes every shadow prefill
    of a task, so pools let the dedicated group soak bursts).

    Dispatch is keyed by a content hash of the prompt tokens — the same
    prompt always lands on the same member first (affinity keeps any
    member-local compilation/caching warm and makes schedules
    reproducible), falling over in ring order past unhealthy or
    mid-dispatch-failing members.  ``fetch`` routes each in-flight block
    back to the member that produced it.  Members alias the first
    worker's pinned params and jitted program (``share_from``), so a
    pool costs one compile and one params copy regardless of size.

    Chaos surface matches the single worker: ``kill``/``restore``
    broadcast, ``inject_fault(..., worker=i)`` arms one member, and the
    pool is ``healthy`` while ANY member is — a one-member fault is
    absorbed by failover instead of falling back to local prefill.
    """

    def __init__(self, cfg, params, *, size: int, device, link=None,
                 distance: float = 1.0, name: str = "prefill",
                 use_pallas="auto", kv_keep_rate: Optional[float] = None):
        if size < 1:
            raise ValueError(f"pool size must be >= 1, got {size}")
        self.cfg = cfg
        self.name = name
        self.link = link
        self.distance = float(distance)
        self.kv_keep_rate = kv_keep_rate
        self.workers = []
        for i in range(size):
            self.workers.append(PrefillWorker(
                cfg, params, device=device, link=link, distance=distance,
                name=f"{name}[{i}]", use_pallas=use_pallas,
                kv_keep_rate=kv_keep_rate,
                share_from=self.workers[0] if self.workers else None))
        # id(logits) -> member, for routing fetches back.  id() is safe
        # here: the engine holds the logits handle alive from dispatch
        # to fetch, so the id cannot be recycled while the entry exists.
        self._inflight: dict = {}
        self.last_fetch_bytes: Tuple[float, float] = (0.0, 0.0)

    # -- affinity -------------------------------------------------------
    @staticmethod
    def _batch_key(batch) -> int:
        """Stable content hash of the prompt (tokens only — the frontend
        rides along with the same prompt in every workload we serve).
        The engine hands the batch over host-side (numpy), so hashing
        never forces a device->host transfer on the dispatch path; a
        device-resident batch would pay one sync per pool dispatch."""
        import hashlib

        import numpy as np
        toks = np.asarray(batch["tokens"])
        digest = hashlib.blake2b(toks.tobytes(), digest_size=8).digest()
        return int.from_bytes(digest, "big")

    # -- chaos hooks ----------------------------------------------------
    @property
    def healthy(self) -> bool:
        return any(w.healthy for w in self.workers)

    def kill(self) -> None:
        for w in self.workers:
            w.kill()

    def restore(self) -> None:
        # in-flight entries survive: a block dispatched before the kill
        # still fetches from (and raises on) the member that owned it
        for w in self.workers:
            w.restore()

    def inject_fault(self, kind: str = "dispatch", *, after: int = 0,
                     timeout: bool = False, worker: int = 0) -> None:
        """Arm a one-shot fault on ONE member (default the first)."""
        self.workers[worker].inject_fault(kind, after=after, timeout=timeout)

    def set_link(self, link, distance: Optional[float] = None) -> None:
        """Broadcast a live-link update to every member."""
        self.link = link
        if distance is not None:
            self.distance = float(distance)
        for w in self.workers:
            w.set_link(link, distance)

    # -- hot path -------------------------------------------------------
    def dispatch(self, batch) -> Tuple[Any, Any]:
        """Launch on the affinity member, failing over in ring order.

        Raises :class:`PrefillWorkerError` (the last member's error, or
        a pool-down error) only when every member is unusable — the
        engine then falls back to local shadow prefill exactly as with a
        single dead worker.
        """
        n = len(self.workers)
        start = self._batch_key(batch) % n
        last_err: Optional[PrefillWorkerError] = None
        for off in range(n):
            w = self.workers[(start + off) % n]
            if not w.healthy:
                continue
            try:
                logits, cache = w.dispatch(batch)
            except PrefillWorkerError as e:   # fault fired mid-dispatch
                last_err = e
                continue
            self._inflight[id(logits)] = w
            return logits, cache
        raise last_err if last_err is not None else PrefillWorkerError(
            f"prefill pool {self.name!r}: no healthy workers")

    def fetch(self, logits, cache=None, *, target=None, prefix=None):
        """Fetch from the member that dispatched this block."""
        w = self._inflight.pop(id(logits), None)
        if w is None:
            raise PrefillWorkerError(
                f"prefill pool {self.name!r}: unknown in-flight block")
        out = w.fetch(logits, cache, target=target, prefix=prefix)
        self.last_fetch_bytes = w.last_fetch_bytes
        return out

    # -- aggregate accounting ------------------------------------------
    @property
    def dispatched(self) -> int:
        return sum(w.dispatched for w in self.workers)

    @property
    def transferred_bytes(self) -> float:
        return sum(w.transferred_bytes for w in self.workers)

    @property
    def kv_bytes_raw(self) -> float:
        return sum(w.kv_bytes_raw for w in self.workers)

    @property
    def kv_bytes_wire(self) -> float:
        return sum(w.kv_bytes_wire for w in self.workers)
