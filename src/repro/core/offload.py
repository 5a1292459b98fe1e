"""Offload execution engine (paper §III "task scheduler" actuation).

The paper's runtime is two devices + MQTT: the primary keeps (1−r)·B of the
batch, ships r·B to the auxiliary, both execute, results merge.  Here a
*node group* is a set of JAX devices (a mesh sub-slice; on the production
mesh: pod 0 = primary, pod 1 = auxiliary).  Since PR 2 the engine runs over
an arbitrary :class:`~repro.core.topology.Topology` (ordered node groups +
per-edge links, group 0 = hub); the 2-node constructor survives as a thin
shim so the paper-faithful call sites keep working.  Two execution modes:

* ``run`` — dispatch-level split: one jitted program per group over its own
  sub-mesh, asymmetric static batch split, simulated link latency from each
  edge's LinkModel (wall-clock measured on this host).  ALL groups are
  dispatched asynchronously (JAX async dispatch) BEFORE any is awaited, so
  ``OffloadReport.t_parallel`` is a *measured* makespan of the overlapped
  execution, not a max() over serial timings.
* ``padded_step`` — single-XLA-program variant used by the multi-pod
  dry-run: batch laid out [n_groups, quota_max, ...] over the "pod" axis
  with per-group validity masks; proves the whole collaborative step
  lowers as one program (DESIGN.md §5).
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core.network import LinkModel, offload_energy, offload_latency
from repro.core.profiler import DeviceProfile


class GroupUnavailableError(RuntimeError):
    """A node group is unreachable (killed, partitioned, crashed): work
    dispatched to it must fail fast with the group named, not hang the
    wave.  The serving runtime catches this to re-queue the group's slice
    onto surviving groups."""

    def __init__(self, group: str, msg: str = ""):
        self.group = group
        super().__init__(msg or f"node group {group!r} is unavailable")


class GroupTimeoutError(GroupUnavailableError):
    """The group did not complete within the per-group await timeout
    (``OffloadEngine(group_timeout_s=...)``) — a wedged arm, distinct
    from an outright crash so callers can tell them apart."""


@dataclass
class GroupHealth:
    """Chaos/health surface for a :class:`NodeGroup`, mirroring
    ``PrefillWorker.kill()/restore()/inject_fault()`` so ANY group in the
    topology — decode spokes, the hub's offload arms, not just the
    prefill spoke — can be killed, wedged, or restored mid-serve.

    ``check(kind)`` is the enforcement point: engines call it once per
    dispatch/await of the group; it raises :class:`GroupUnavailableError`
    when the group is down or an armed one-shot fault fires on the
    (``after``+1)-th call of that kind.  ``wedge()`` simulates a hung arm
    that never completes — only an engine's ``group_timeout_s`` clock can
    surface it (as :class:`GroupTimeoutError`).  Production code never
    arms faults; the chaos tier (``tests/test_group_faults.py``) does.
    """
    alive: bool = True
    wedged: bool = False
    _fault: Optional[Tuple[str, int, bool]] = None
    _calls: Dict[str, int] = field(default_factory=dict)

    KINDS = ("dispatch", "await")

    def kill(self) -> None:
        """Simulate losing the group (node crash / partition)."""
        self.alive = False

    def restore(self) -> None:
        """Simulate the group coming back (reboot, partition healed).
        Clears any armed fault, wedge and call counters so the revived
        group starts clean — re-probe clocks pick it up from here."""
        self.alive = True
        self.wedged = False
        self._fault = None
        self._calls = {}

    def wedge(self) -> None:
        """Arm a hang: the group stays ``alive`` but never completes —
        awaits on it only return via an engine's ``group_timeout_s``."""
        self.wedged = True

    def inject_fault(self, kind: str = "dispatch", *, after: int = 0,
                     timeout: bool = False) -> None:
        """Arm a one-shot fault: the (``after``+1)-th ``check(kind)``
        kills the group and raises (:class:`GroupTimeoutError` when
        ``timeout``)."""
        if kind not in self.KINDS:
            raise ValueError(f"kind must be one of {self.KINDS}")
        self._fault = (kind, int(after), bool(timeout))

    def check(self, kind: str, name: str = "group") -> None:
        """Raise if the group is down or an armed fault fires now."""
        if not self.alive:
            raise GroupUnavailableError(name, f"node group {name!r} is down")
        self._calls[kind] = self._calls.get(kind, 0) + 1
        if self._fault is not None and self._fault[0] == kind \
                and self._calls[kind] > self._fault[1]:
            _, _, timeout = self._fault
            self._fault = None            # one-shot: spent once fired
            self.alive = False
            err = GroupTimeoutError if timeout else GroupUnavailableError
            raise err(name, f"node group {name!r} "
                      f"{'timed out' if timeout else 'died'} on "
                      f"{kind} #{self._calls[kind]}")


def mesh_axis_sizes(n_devices: int, n_axes: int,
                    axis_sizes: Optional[Sequence[int]] = None) -> Tuple[int, ...]:
    """Factor ``n_devices`` into ``n_axes`` mesh-axis sizes, largest first.

    An explicit ``axis_sizes`` is validated against the device count;
    otherwise the factorization is balanced greedily — each axis takes the
    smallest divisor of the remainder at or above the even split
    rem^(1/axes_left), which keeps the factors descending — so 8 devices
    over 2 axes give (4, 2), 4 give (2, 2), 12 over 3 give (3, 2, 2) and
    a prime count degenerates to (n, 1, ...).
    """
    if axis_sizes is not None:
        sizes = tuple(int(s) for s in axis_sizes)
        if len(sizes) != n_axes:
            raise ValueError(f"axis_sizes {sizes} has {len(sizes)} entries "
                             f"for {n_axes} axes")
        prod = 1
        for s in sizes:
            prod *= s
        if prod != n_devices:
            raise ValueError(f"axis_sizes {sizes} does not cover "
                             f"{n_devices} devices")
        return sizes
    sizes = []
    rem = n_devices
    for axes_left in range(n_axes, 1, -1):
        # smallest divisor of rem at or above the even split rem^(1/axes):
        # keeps factors descending, e.g. 12 over 3 axes -> (3, 2, 2)
        target = rem ** (1.0 / axes_left)
        d = rem
        for cand in range(1, rem + 1):
            if rem % cand == 0 and cand >= target - 1e-9:
                d = cand
                break
        sizes.append(d)
        rem //= d
    sizes.append(rem)
    return tuple(sizes)


@dataclass
class NodeGroup:
    name: str
    devices: List[Any]
    profile: DeviceProfile
    health: GroupHealth = field(default_factory=GroupHealth)

    # -- chaos delegates (the PrefillWorker surface, fleet-wide) --------
    @property
    def alive(self) -> bool:
        return self.health.alive

    def kill(self) -> None:
        self.health.kill()

    def restore(self) -> None:
        self.health.restore()

    def inject_fault(self, kind: str = "dispatch", *, after: int = 0,
                     timeout: bool = False) -> None:
        self.health.inject_fault(kind, after=after, timeout=timeout)

    def mesh(self, axes=("data",), axis_sizes: Optional[Sequence[int]] = None):
        from repro.models.sharding import make_mesh
        shape = mesh_axis_sizes(len(self.devices), len(axes), axis_sizes)
        return make_mesh(shape, axes, self.devices)


@dataclass
class OffloadReport:
    r: float                    # total offloaded fraction (1 − hub share)
    n_local: int
    n_offloaded: int
    t_local_s: float            # hub completion since joint dispatch
    t_remote_s: float           # slowest spoke completion since joint dispatch
    t_offload_s: float          # slowest spoke link latency (model-predicted)
    payload_bytes: float
    e_offload_j: float
    outputs: Any = None
    t_parallel_s: float = 0.0   # measured makespan of the overlapped dispatch
                                # (0.0 when the task could not overlap, e.g.
                                # host-loop jit=False tasks)
    # --- N-group widening (PR 2), ordered like the topology: hub first ----
    group_names: Tuple[str, ...] = ()
    n_group: Tuple[int, ...] = ()
    t_group_s: Tuple[float, ...] = ()   # per-group completion since dispatch
    t_link_s: Tuple[float, ...] = ()    # per-edge link latency (hub entry 0.0)
    # --- fused-decode accounting (PR 3) -----------------------------------
    host_syncs: int = 0         # device→host materializations this batch:
                                # one await per dispatched group here; the
                                # serving engines report one per macro-step
                                # + one per admission phase
    # --- overlapped-admission accounting (PR 4) ---------------------------
    admission_stalls: int = 0   # macro boundaries where live decode slots
                                # waited on a prefill (0 at steady state
                                # with overlapped admission)
    t_prefill_overlap_s: float = 0.0  # shadow-prefill dispatch wall hidden
                                      # behind in-flight decode macro-steps
    # --- disaggregated-prefill accounting (PR 5) --------------------------
    prefill_offloaded: int = 0  # shadow prefills dispatched to the
                                # dedicated prefill group
    t_kv_transfer_s: float = 0.0  # priced KV-transfer hop total for blocks
                                  # spliced back from the prefill group
    prefill_fallbacks: int = 0  # prefill-group failures recovered by local
                                # shadow prefill (streams unchanged)
    # --- prefix-cache / compressed-hop accounting (PR 7) ------------------
    prefix_hits: int = 0        # admissions that matched the radix trie
                                # (full + partial; full hits skip prefill
                                # AND the KV hop entirely)
    prefix_blocks_reused: int = 0  # trie blocks spliced into resumed
                                   # prefills instead of recomputed
    prefill_flops_avoided: float = 0.0  # analytic prefill FLOPs skipped
    prefill_flops_total: float = 0.0    # ...of this analytic total
    kv_hop_bytes_raw: float = 0.0   # uncompacted block bytes of fetched
                                    # prefill→decode hops
    kv_hop_bytes_wire: float = 0.0  # bytes that actually crossed (tail
                                    # rows, sender-compacted)
    # --- fleet-wide fault domain (PR 8) -----------------------------------
    group_alive: Tuple[bool, ...] = ()  # liveness per DECODE group this wave
                                        # (ordered like group_names); dead
                                        # groups carry zero counts so the
                                        # controller skips their timings
    wave_requeued: int = 0      # requests re-queued onto survivors after a
                                # mid-wave group failure
    wave_retries: int = 0       # re-queued requests completing this wave
    link_bw_hz: Tuple[float, ...] = ()  # live traced bandwidth per decode
                                        # edge (hub entry 0.0)
    mobility_latched: int = 0   # decode edges forced local this wave by the
                                # β-threshold mobility latch (§V-A.5)
    # --- power/memory/busy-factor admission (PR 10) -----------------------
    admission_hot: Tuple[bool, ...] = ()   # per-decode-group hot flag this
                                           # wave (power/memory/busy budget
                                           # tripped — ordered like
                                           # group_names)
    admission_rerouted: int = 0  # requests this wave that the hot-mask
                                 # re-routed off their budget-hot group via
                                 # the masked-simplex split
    power_headroom_w: Tuple[float, ...] = ()   # P_available − threshold per
                                               # decode group (battery Eq. 6;
                                               # wall-power groups report
                                               # their full profile budget)
    mem_headroom_frac: Tuple[float, ...] = ()  # λ − kv_bytes/(chips·HBM)
                                               # per decode group (Alg. 1
                                               # line 3)
    # --- scale-out timing decomposition (PR 6) ----------------------------
    # Summed ContinuousStats buckets across the wave's engines; on fused
    # paths decode wall == t_dispatch_s + t_await_s per engine (see
    # serving/engine.ContinuousStats).
    t_splice_s: float = 0.0     # fused cross-group cache-splice dispatch wall
    t_slot_write_s: float = 0.0  # per-slot big-cache write dispatch wall
    t_dispatch_s: float = 0.0   # fused decode macro-step launch wall
    t_await_s: float = 0.0      # token-block await wall (device execution)

    @property
    def t_parallel(self) -> float:
        """Completion time with full overlap.  Measured when the engine
        dispatched every group before awaiting any; otherwise derived from
        the serial per-group timings."""
        if self.t_group_s:
            derived = max(tl + tg for tl, tg
                          in zip(self.t_link_s, self.t_group_s))
        else:
            derived = max(self.t_local_s, self.t_offload_s + self.t_remote_s)
        if self.t_parallel_s > 0.0:
            return max(self.t_parallel_s, self.t_offload_s + self.t_remote_s)
        return derived

    @property
    def t_serial(self) -> float:
        """Paper-objective-style serial accounting: r(T1+T3) + (1-r)T2,
        generalized to Σ_g (T_g + link_g)."""
        if self.t_group_s:
            return sum(self.t_group_s) + sum(self.t_link_s)
        return self.t_local_s + self.t_remote_s + self.t_offload_s


def split_sizes(batch: int, r: float) -> Tuple[int, int]:
    """(n_offloaded, n_local); n_offloaded = round(r·B) like the paper's
    70 / 30 image split."""
    n_off = int(round(r * batch))
    return n_off, batch - n_off


def _as_fractions(split, n_groups: int) -> Tuple[float, ...]:
    """Normalize a split spec — scalar r, sequence, or SplitVector — into
    per-group fractions ordered hub first.  Raw sequences are projected
    onto the simplex exactly like SplitVector.__post_init__, so a
    non-normalized sequence can never over-allocate the batch."""
    if hasattr(split, "fractions"):
        fr = tuple(float(f) for f in split.fractions)
    elif isinstance(split, (int, float)):
        if n_groups != 2:
            raise ValueError(
                f"scalar split ratio is only defined for 2 groups; this "
                f"topology has {n_groups} — pass a SplitVector")
        fr = (1.0 - float(split), float(split))
    else:
        fr = tuple(max(0.0, float(f)) for f in split)
        s = sum(fr)
        if s <= 0.0:
            raise ValueError(f"split fractions {fr} sum to zero")
        fr = tuple(f / s for f in fr)
    if len(fr) != n_groups:
        raise ValueError(f"split has {len(fr)} fractions for "
                         f"{n_groups} groups")
    return fr


def split_counts(fractions: Sequence[float], batch: int) -> Tuple[int, ...]:
    """Apportion ``batch`` items over the simplex fractions (hub first).

    The 2-group case defers to :func:`split_sizes` so the pair path is
    bit-identical to the PR-1 engine (including Python's banker's rounding
    on .5 quotas); N-group uses largest-remainder apportionment."""
    if len(fractions) == 2:
        n_off, n_loc = split_sizes(batch, fractions[1])
        return (n_loc, n_off)
    quotas = [f * batch for f in fractions]
    counts = [int(q) for q in quotas]
    rem = batch - sum(counts)
    order = sorted(range(len(quotas)),
                   key=lambda g: (quotas[g] - counts[g], -g), reverse=True)
    for g in order[:rem]:
        counts[g] += 1
    return tuple(counts)


class OffloadEngine:
    """Executes one workload batch split across the node groups of a
    topology (group 0 = hub/primary, groups 1.. = spokes/auxiliaries).

    The 2-node positional constructor ``OffloadEngine(task_fn, primary,
    auxiliary, link, ...)`` is kept as a deprecation shim over
    ``Topology.pair`` and is exercised bit-identically by the tests."""

    def __init__(self, task_fn: Callable[[Any], Any],
                 primary: Optional[NodeGroup] = None,
                 auxiliary: Optional[NodeGroup] = None,
                 link: Optional[LinkModel] = None, *,
                 topology: Optional[Any] = None,
                 payload_bytes_per_item: float,
                 distance_fn: Callable[[], float] = lambda: 1.0,
                 jit: bool = True,
                 group_timeout_s: Optional[float] = None):
        if topology is None:
            if primary is None or auxiliary is None or link is None:
                raise ValueError("pass either topology= or the 2-node "
                                 "(primary, auxiliary, link) triple")
            from repro.core.topology import Topology
            topology = Topology.pair(primary, auxiliary, link)
        self.task_fn = task_fn
        self.topology = topology
        self.payload_bytes_per_item = payload_bytes_per_item
        self.distance_fn = distance_fn
        self.jit = jit  # False for host-loop tasks (e.g. a generate() loop)
        # per-group await deadline (None = off, the historical behavior):
        # a group still pending past this wall is killed and surfaced as
        # GroupTimeoutError instead of blocking the wave forever
        if group_timeout_s is not None and group_timeout_s <= 0.0:
            raise ValueError(f"group_timeout_s must be > 0, "
                             f"got {group_timeout_s}")
        self.group_timeout_s = group_timeout_s
        self._compiled: Dict[Tuple[str, int], Any] = {}

    # --- 2-node legacy aliases (deprecation shim) ----------------------
    @property
    def primary(self) -> NodeGroup:
        return self.topology.groups[0]

    @property
    def auxiliary(self) -> NodeGroup:
        return self.topology.groups[1]

    @property
    def link(self) -> LinkModel:
        return self.topology.links[1]

    # ------------------------------------------------------------------
    @staticmethod
    def _shape_key(batch) -> Tuple:
        return tuple((tuple(a.shape), str(getattr(a, "dtype", type(a))))
                     for a in jax.tree.leaves(batch))

    def _get_fn(self, group: NodeGroup, sliced_batch):
        """Per-group compiled-program cache, keyed by the slice's shape
        signature (asymmetric splits give each group its own shapes)."""
        if not self.jit:
            return self.task_fn
        key = (group.name, self._shape_key(sliced_batch))
        if key not in self._compiled:
            dev = group.devices[0]
            self._compiled[key] = jax.jit(self.task_fn, device=dev)
        return self._compiled[key]

    @staticmethod
    def _slice_batch(batch, lo, hi):
        return jax.tree.map(lambda a: a[lo:hi], batch)

    def _await_groups(self, in_flight: Dict[str, Any], t0: float,
                      healths: Optional[Dict[str, GroupHealth]] = None
                      ) -> Dict[str, float]:
        """Wait for every in-flight output, stamping each group's completion
        time relative to the joint dispatch WITHOUT serializing on the other
        groups (blocking on one first would inflate the others' timestamps
        and the controller would never see a faster group).

        Await-stage health checks fire armed ``kind="await"`` faults
        before blocking; a wedged group is never considered ready, so the
        ``group_timeout_s`` clock surfaces it as
        :class:`GroupTimeoutError` (with no timeout configured the wedge
        is raised immediately rather than hanging the host forever)."""
        healths = healths or {}
        pending = {name: jax.tree.leaves(out)
                   for name, out in in_flight.items() if out is not None}
        done = {name: 0.0 for name in in_flight}
        for name in list(pending):
            h = healths.get(name)
            if h is not None:
                h.check("await", name)
                if h.wedged and self.group_timeout_s is None:
                    h.kill()
                    raise GroupUnavailableError(
                        name, f"node group {name!r} is wedged and no "
                        "group_timeout_s is configured — refusing to hang")
        pollable = all(hasattr(leaf, "is_ready")
                       for leaves in pending.values() for leaf in leaves)
        if pollable:
            while pending:
                for name in list(pending):
                    h = healths.get(name)
                    if h is not None and h.wedged:
                        continue   # simulated hang: only the timeout ends it
                    if all(leaf.is_ready() for leaf in pending[name]):
                        done[name] = time.perf_counter() - t0
                        del pending[name]
                if pending:
                    if self.group_timeout_s is not None and \
                            time.perf_counter() - t0 > self.group_timeout_s:
                        for name in pending:
                            h = healths.get(name)
                            if h is not None:
                                h.kill()
                        raise GroupTimeoutError(
                            next(iter(pending)),
                            f"groups {sorted(pending)} still pending after "
                            f"{self.group_timeout_s}s await timeout")
                    time.sleep(1e-4)
        else:
            for name, leaves in pending.items():
                jax.block_until_ready(leaves)
                done[name] = time.perf_counter() - t0
        return done

    def run(self, batch, split=None, *, r: Optional[float] = None
            ) -> OffloadReport:
        """Dispatch every node group, await after — overlapped execution.

        ``split`` is a scalar r for the 2-node shim or a SplitVector /
        fraction sequence (hub first) for N groups; ``r=`` is the
        deprecated 2-node keyword spelling.  With jitted tasks, JAX
        async dispatch returns futures immediately, so every spoke program
        is in flight before the hub is awaited and the measured wall clock
        is the true parallel makespan.  With ``jit=False`` (host-loop tasks
        that block internally) the calls serialize and the report falls
        back to derived-overlap accounting.

        Batch layout matches PR 1's pair engine: spokes take their slices
        from the front of the batch (in topology order), the hub keeps the
        tail — so outputs merge back in original batch order.
        """
        if (split is None) == (r is None):
            raise TypeError("pass exactly one of split or the deprecated r=")
        if split is None:
            split = float(r)
        groups = self.topology.groups
        links = self.topology.links
        G = len(groups)
        fracs = _as_fractions(split, G)
        B = jax.tree.leaves(batch)[0].shape[0]
        counts = split_counts(fracs, B)
        d = float(self.distance_fn())

        # slice bounds: spokes first (groups 1..G-1 in order), hub last
        bounds: List[Tuple[int, int]] = [None] * G
        lo = 0
        for g in range(1, G):
            bounds[g] = (lo, lo + counts[g])
            lo += counts[g]
        bounds[0] = (lo, B)

        t_link = [0.0] * G
        e_link = [0.0] * G
        for g in range(1, G):
            if counts[g]:
                payload = counts[g] * self.payload_bytes_per_item
                t_link[g] = float(offload_latency(links[g], payload, d))
                e_link[g] = float(offload_energy(links[g], payload, d))

        out: List[Any] = [None] * G
        t_group = [0.0] * G
        t_par = 0.0
        t0 = time.perf_counter()
        if self.jit:
            # --- dispatch phase: launch ALL groups, await NONE ---------
            # spokes first: they pay link latency on top of exec.  A dead
            # arm raises the typed error HERE, before any launch hangs.
            for g in list(range(1, G)) + [0]:
                if counts[g]:
                    groups[g].health.check("dispatch", groups[g].name)
                    sl = self._slice_batch(batch, *bounds[g])
                    out[g] = self._get_fn(groups[g], sl)(sl)
            # --- await phase: completion timestamps vs joint dispatch --
            done = self._await_groups(
                {groups[g].name: out[g] for g in range(G)}, t0,
                healths={groups[g].name: groups[g].health
                         for g in range(G) if counts[g]})
            t_group = [done[groups[g].name] for g in range(G)]
            t_par = time.perf_counter() - t0
        else:
            for g in [0] + list(range(1, G)):  # hub first, like PR 1
                if counts[g]:
                    groups[g].health.check("dispatch", groups[g].name)
                    t1 = time.perf_counter()
                    out[g] = jax.block_until_ready(
                        self.task_fn(self._slice_batch(batch, *bounds[g])))
                    t_group[g] = time.perf_counter() - t1

        # merge in slice order (spokes ascending, hub last) = batch order
        parts = [out[g] for g in list(range(1, G)) + [0] if out[g] is not None]
        merged = None
        if parts:
            if len(parts) > 1 and self.jit:
                # groups may hold DISTINCT devices (emulated multi-host
                # scale-out) and jit commits each slice to its group, so
                # collect onto the hub before the concat —
                # jnp.concatenate cannot mix committed devices
                hub = groups[0].devices[0]
                parts = [jax.tree.map(lambda x: jax.device_put(x, hub), p)
                         for p in parts]
            merged = jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0),
                                  *parts) if len(parts) > 1 else parts[0]
        return OffloadReport(
            r=1.0 - fracs[0], n_local=counts[0],
            n_offloaded=B - counts[0],
            t_local_s=t_group[0], t_remote_s=max(t_group[1:], default=0.0),
            t_offload_s=max(t_link[1:], default=0.0),
            payload_bytes=sum(counts[g] * self.payload_bytes_per_item
                              for g in range(1, G) if counts[g]),
            e_offload_j=sum(e_link), outputs=merged, t_parallel_s=t_par,
            group_names=tuple(g.name for g in groups),
            n_group=tuple(counts), t_group_s=tuple(t_group),
            t_link_s=tuple(t_link),
            host_syncs=sum(1 for g in range(G) if counts[g]))


# ---------------------------------------------------------------------------
def padded_quota_batch(batch, r: float, n_groups: int = 2):
    """Re-lay a batch as [n_groups, quota_max, ...] + validity mask for the
    single-program multi-pod step.  Group 0 = auxiliary (gets round(r·B)),
    group 1 = primary."""
    B = jax.tree.leaves(batch)[0].shape[0]
    n_off, n_loc = split_sizes(B, r)
    quota = max(n_off, n_loc, 1)

    def relay(a):
        pad = jnp.zeros((n_groups * quota - B, *a.shape[1:]), a.dtype)
        aux = a[:n_off]
        loc = a[n_off:]
        aux = jnp.concatenate([aux, pad[:quota - n_off]], 0)
        loc = jnp.concatenate([loc, pad[:quota - n_loc]], 0)
        return jnp.stack([aux, loc])

    mask = jnp.stack([jnp.arange(quota) < n_off, jnp.arange(quota) < n_loc])
    return jax.tree.map(relay, batch), mask
