"""Distributed serving launcher with HeteroEdge collaborative offloading.

    PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b \
        --requests 16 --max-new 8 [--reduced] [--kv-int8] [--split auto] \
        [--continuous] [--slots 4] [--macro-steps 8] \
        [--no-overlap-admission] [--prefill-group G] \
        [--topology pair|star] [--nodes N] [--telemetry-json out.json] \
        [--link-trace 4,12,28,12,4 [--mobility-beta 10]]

Serves a Poisson request stream.  ``--split auto`` runs the HeteroEdge
loop: profile a calibration batch, fit, solve for the split, then divide
every arriving batch across the topology's node groups (partitions of the
device set; on 1 device all groups share it — the decision logic and
accounting are identical).

``--topology star --nodes N`` builds the §VIII star (hub + N−1 spokes)
instead of the paper's pair; the split becomes a per-group SplitVector
solved by ``solve_star``.

``--continuous`` swaps the static per-batch engine for the
:class:`~repro.core.topology.HeteroRuntime` session: requests stream
through fixed KV-cache slots on each node group, waves are apportioned by
the live split from ``SplitRatioController`` (EWMA-smoothed measured
timings re-solved every few waves), and the structured per-wave telemetry
can be dumped with ``--telemetry-json``.
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Dict, Optional, Tuple

import jax
import numpy as np

import repro.core as C
from repro.configs.base import get_config, list_configs, reduced
from repro.data.pipeline import request_stream
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model as M
from repro.serving.engine import ServeRequest, ServingEngine


def parse_tenants(spec: str) -> Dict[str, C.TenantClass]:
    """``--tenants`` parser: a comma list of
    ``name[:priority[:weight[:deadline_s]]]`` classes, e.g. the default
    ``interactive:0:2:0.5,batch:1:1`` — priority 0 preempts the
    admission queue (tightest TTFT deadline class), weight sets the
    weighted-deficit fair share, deadline_s the class's TTFT target."""
    tenants: Dict[str, C.TenantClass] = {}
    for part in spec.split(","):
        bits = [b.strip() for b in part.strip().split(":")]
        if not bits[0]:
            raise argparse.ArgumentTypeError(
                f"--tenants entry {part!r} has no name")
        tenants[bits[0]] = C.TenantClass(
            bits[0],
            priority=int(bits[1]) if len(bits) > 1 else 1,
            weight=float(bits[2]) if len(bits) > 2 else 1.0,
            deadline_s=float(bits[3]) if len(bits) > 3 else float("inf"))
    return tenants


def parse_split(value: str) -> Tuple[str, Optional[float]]:
    """One parser for ``--split`` on every path: returns (mode, r) where
    mode ∈ {"auto", "none", "fixed"}.  "auto" → solver decides (r None);
    "none" → keep everything local (r 0.0); a float → fixed ratio clipped
    to [0, 1]."""
    v = value.strip().lower()
    if v == "auto":
        return "auto", None
    if v == "none":
        return "none", 0.0
    try:
        return "fixed", float(np.clip(float(v), 0.0, 1.0))
    except ValueError:
        raise argparse.ArgumentTypeError(
            f'--split must be "auto", "none" or a float, got {value!r}')


def partition_devices(devs: list, nodes: int) -> list:
    """Split the device list into ``nodes`` contiguous groups covering
    EVERY device (earlier groups absorb the remainder of an uneven split
    — no device is left idle); hosts with fewer devices than groups fall
    back to sharing device 0."""
    if len(devs) < nodes:
        return [list(devs[g:g + 1] or devs[:1]) for g in range(nodes)]
    base, rem = divmod(len(devs), nodes)
    slices, lo = [], 0
    for g in range(nodes):
        hi = lo + base + (1 if g < rem else 0)
        slices.append(list(devs[lo:hi]))
        lo = hi
    return slices


def build_topology(kind: str, nodes: int,
                   prefill_group: Optional[int] = None) -> C.Topology:
    """Partition the visible devices into ``nodes`` groups (each falls back
    to sharing device 0 when the host has fewer devices — decision logic
    and accounting are identical).  Hub gets the Nano-class profile, spokes
    the Xavier-class one, per the paper's testbed asymmetry.

    ``prefill_group`` (a spoke's group index, 1..nodes-1) dedicates that
    spoke to disaggregated prefill: it takes no decode waves, shadow
    prefills ship there and their KV blocks splice back over the edge's
    link (PR 5).  On a pair this is *pure* disaggregation — the hub does
    all decoding."""
    if nodes < 2:
        raise ValueError("--nodes must be >= 2 (hub + at least one spoke)")
    if kind == "pair" and nodes != 2:
        raise ValueError("--topology pair implies --nodes 2")
    slices = partition_devices(jax.devices(), nodes)
    hub = C.NodeGroup("primary", slices[0], C.JETSON_NANO)
    spokes = [C.NodeGroup(f"auxiliary{g}" if nodes > 2 else "auxiliary",
                          slices[g], C.JETSON_XAVIER)
              for g in range(1, nodes)]
    if kind == "pair":
        topo = C.Topology.pair(hub, spokes[0], C.WIFI_5GHZ)
        if prefill_group is not None:
            topo = dataclasses.replace(topo, prefill_spoke=prefill_group)
        return topo
    return C.Topology.star(hub, spokes, C.WIFI_5GHZ,
                           prefill_spoke=prefill_group)


def serve_continuous(cfg, params, reqs, *, prompt_len: int, max_new: int,
                     slots: int, split: str, macro_steps: int = 8,
                     wave_steps: int = 1,
                     overlap_admission: bool = True,
                     topology: Optional[C.Topology] = None,
                     link=None, telemetry_path: Optional[str] = None,
                     prefix_cache_blocks: int = 0,
                     prefix_block_size: int = 8, prefill_pool: int = 1,
                     kv_keep_rate: Optional[float] = None,
                     link_trace: Optional[str] = None,
                     mobility_beta: Optional[float] = None,
                     frontend: bool = False,
                     tenants: Optional[Dict[str, C.TenantClass]] = None,
                     queue_depth: int = 64,
                     shed_depth: Optional[int] = None,
                     power_budget_wh: Optional[float] = None,
                     power_threshold_w: float = 8.0
                     ) -> Optional[C.ServeResult]:
    """Continuous-batching collaborative serving over a request stream,
    through the HeteroRuntime session (pair or star topology).

    Requests arrive in waves; each wave is apportioned across the node
    groups by the live SplitVector, every group's slot runtime drains its
    share, and the measured wave timings feed the online controller that
    re-solves the split for the next wave.
    """
    topology = topology or build_topology("pair", 2)
    if link is not None:
        topology = C.Topology(topology.groups,
                              [None] + [link] * (len(topology) - 1),
                              kind=topology.kind)
    offset = cfg.frontend_tokens if cfg.family == "vlm" else 0
    max_len = prompt_len + offset + max_new + 8
    traces = None
    if link_trace:
        # one trace broadcast to every spoke edge: LinkTrace is a pure
        # function of the wave index, so sharing the object is safe
        tr = C.LinkTrace.from_spec(link_trace, beta=mobility_beta)
        traces = {gi: tr for gi in range(1, len(topology))}
    budgets = None
    if power_budget_wh is not None:
        # one battery-style power envelope per decode group: the serving
        # wall drains it (Eqs. 5-6) and hot groups mask out of the split
        budgets = {topology.groups[gi].name: C.GroupBudget(
                       battery=C.BatteryState(capacity_wh=power_budget_wh),
                       power_threshold_w=power_threshold_w)
                   for gi in topology.decode_indices()}
    runtime = C.HeteroRuntime(topology, slots=slots, max_len=max_len,
                              macro_steps=macro_steps,
                              wave_steps=wave_steps,
                              overlap_admission=overlap_admission,
                              prefix_cache_blocks=prefix_cache_blocks,
                              prefix_block_size=prefix_block_size,
                              prefill_pool=prefill_pool,
                              kv_keep_rate=kv_keep_rate,
                              link_traces=traces,
                              group_budgets=budgets)
    runtime.add_task(cfg.name, cfg, params,
                     max_new=max_new,
                     payload_bytes_per_item=prompt_len * cfg.d_model * 2)
    mode, fixed_r = parse_split(split)

    # each request keeps its own completion length (capped at --max-new) —
    # mixed lengths are exactly what the slot runtime absorbs
    requests = [ServeRequest(uid=r.uid, prompt=np.pad(
                    r.prompt[:prompt_len],
                    (0, max(0, prompt_len - len(r.prompt)))).astype(np.int32),
                    max_new=max(1, min(r.max_new_tokens, max_new)),
                    frontend=r.frontend, task=cfg.name)
                for r in reqs]
    if frontend:
        # asyncio ingress in front of the same runtime: tenant-fair
        # admission waves, streamed tokens, power/memory shedding
        import asyncio

        from repro.serving.frontend import (PHASES, FrontendError,
                                           ServingFrontend)
        tenants = tenants or parse_tenants("interactive:0:2:0.5,batch:1:1")
        fe = ServingFrontend(runtime, tenants, queue_depth=queue_depth,
                             shed_depth=shed_depth,
                             split=None if mode == "auto" else fixed_r)
        runtime.warmup(requests[:2])
        tnames = sorted(tenants)

        async def drive() -> int:
            await fe.start()
            streams, refused = [], 0
            for i, req in enumerate(requests):
                try:
                    streams.append(await fe.submit(
                        req.prompt, req.max_new,
                        tenant=tnames[i % len(tnames)], task=cfg.name,
                        frontend=req.frontend))
                except FrontendError:
                    refused += 1   # typed backpressure/shed refusal
            for s in streams:
                await s.collect()
            await fe.stop()
            return refused

        refused = asyncio.run(drive())
        tel = fe.telemetry()
        print(f"frontend[{topology.kind}]: {tel['waves_served']} waves, "
              f"{refused} refused (queue/shed), "
              f"queue_depth={tel['queue_depth']} "
              f"shed_depth={tel['shed_depth']}")
        for name, ts in tel["tenants"].items():
            print(f"  tenant {name}: {ts['completed']}/{ts['submitted']} "
                  f"done, shed={ts['shed']} "
                  f"ttft p50/p99={ts['ttft_p50_s'] * 1e3:.1f}/"
                  f"{ts['ttft_p99_s'] * 1e3:.1f}ms "
                  f"itl p50/p99={ts['itl_p50_s'] * 1e3:.2f}/"
                  f"{ts['itl_p99_s'] * 1e3:.2f}ms; ttft mean by phase "
                  + " ".join(f"{p}={ts[p + '_mean_s'] * 1e3:.1f}"
                             for p in PHASES) + "ms")
        if telemetry_path:
            import json as _json
            with open(telemetry_path, "w") as fh:
                _json.dump({"frontend": tel}, fh, indent=2)
            print(f"telemetry -> {telemetry_path}")
        return None

    result = runtime.serve(requests, wave=2 * slots * (len(topology) - 1),
                           split=None if mode == "auto" else fixed_r,
                           verbose=True)
    tot = result.telemetry["totals"]
    print(f"continuous[{topology.kind}]: {tot['requests']} requests, "
          f"{tot['tokens']} tokens in {tot['wall_s']:.2f}s "
          f"({tot['tok_per_s']:.1f} tok/s), "
          f"final split={tot['final_split']}, "
          f"{tot['host_syncs']} host syncs "
          f"({tot['host_syncs_per_token']:.3f}/token, K={macro_steps}), "
          f"{tot['admission_stalls']} admission stalls"
          f"{' (overlapped)' if overlap_admission else ''}")
    if result.telemetry.get("prefill_group"):
        print(f"disaggregated prefill[{result.telemetry['prefill_group']}]: "
              f"{tot['prefill_offloaded']} offloaded, "
              f"{tot['t_kv_transfer_s'] * 1e3:.2f}ms kv-transfer, "
              f"{tot['prefill_fallbacks']} fallbacks")
    if tot.get("wave_requeued") or tot.get("mobility_latched"):
        print(f"fault domain: {tot['wave_requeued']} re-queued, "
              f"{tot['wave_retries']} retried, "
              f"{tot['mobility_latched']} mobility latches, "
              f"alive={tot['group_alive']}")
    if tot.get("admission_rerouted"):
        print(f"admission: {tot['admission_rerouted']} re-routed off "
              f"budget-hot groups, hot={tot['admission_hot']}, "
              f"power headroom={tot['power_headroom_w']}")
    if prefix_cache_blocks > 0:
        print(f"prefix cache[{prefix_cache_blocks}x{prefix_block_size}]: "
              f"{tot['prefix_hits']} hits, "
              f"{tot['prefix_blocks_reused']} blocks reused, "
              f"{tot['prefill_flops_avoided_frac']:.1%} prefill flops "
              f"avoided, kv hop {tot['kv_hop_bytes_raw'] / 1e3:.0f}kB raw "
              f"-> {tot['kv_hop_bytes_wire'] / 1e3:.0f}kB wire")
    if telemetry_path:
        with open(telemetry_path, "w") as fh:
            fh.write(result.to_json(indent=2))
        print(f"telemetry -> {telemetry_path}")
    return result


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=list_configs(), default="llama3.2-1b")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--kv-int8", action="store_true")
    ap.add_argument("--split", default="auto",
                    help='"auto" (HeteroEdge solver), a float r, or "none"')
    ap.add_argument("--continuous", action="store_true",
                    help="slot-based continuous batching runtime")
    ap.add_argument("--slots", type=int, default=4,
                    help="KV-cache slots per node group (continuous mode)")
    ap.add_argument("--macro-steps", type=int, default=8,
                    help="fused decode tokens per dispatch (0 = pre-fusion "
                         "per-token loop)")
    ap.add_argument("--wave-steps", type=int, default=1,
                    help="fused macro-steps per host launch (>1 = jitted "
                         "wave driver; requires --macro-steps > 0)")
    ap.add_argument("--overlap-admission", default=True,
                    action=argparse.BooleanOptionalAction,
                    help="prefill newly admitted requests into shadow slots "
                         "behind the in-flight decode macro-step "
                         "(--no-overlap-admission = boundary-blocking "
                         "admission for A/B)")
    ap.add_argument("--topology", choices=("pair", "star"), default="pair",
                    help="2-node pair (paper) or §VIII star")
    ap.add_argument("--nodes", type=int, default=None,
                    help="total node groups (default 2 for pair, 3 for star)")
    ap.add_argument("--prefill-group", type=int, default=None,
                    metavar="SPOKE",
                    help="dedicate spoke SPOKE (group index 1..) to "
                         "disaggregated prefill: shadow prefills ship "
                         "there and KV blocks splice back over its link "
                         "(continuous mode; requires --macro-steps > 0)")
    ap.add_argument("--prefix-cache-blocks", type=int, default=0,
                    metavar="N",
                    help="arm the cross-request radix prefix cache with a "
                         "budget of N KV blocks per task (0 = disabled; "
                         "continuous mode)")
    ap.add_argument("--prefix-block-size", type=int, default=8,
                    metavar="T", help="prefix-cache block size in tokens")
    ap.add_argument("--prefill-pool", type=int, default=1, metavar="W",
                    help="prefill workers on the dedicated prefill group "
                         "(>1 = content-hash affinity pool with failover; "
                         "requires --prefill-group)")
    ap.add_argument("--kv-keep-rate", type=float, default=None,
                    metavar="R",
                    help="LOSSY prefill->decode KV-hop compression: keep "
                         "only the top-R salience fraction of shipped tail "
                         "rows (default off = lossless compaction)")
    ap.add_argument("--link-trace", default=None, metavar="SPEC",
                    help="mobility trace replayed per serve wave on every "
                         "spoke edge: comma-separated distances in meters "
                         '("4,12,28,12,4") or @path to a JSON file with '
                         "distances/bandwidths arrays (continuous mode); "
                         "edges whose fitted latency L(d) crosses beta are "
                         "latched local until the trace re-opens them")
    ap.add_argument("--mobility-beta", type=float, default=None,
                    metavar="B",
                    help="latency threshold beta (s) for the --link-trace "
                         "stop-offloading latch (default: MobilityModel's)")
    ap.add_argument("--telemetry-json", default=None, metavar="PATH",
                    help="write HeteroRuntime telemetry JSON here")
    ap.add_argument("--frontend", action="store_true",
                    help="serve through the asyncio multi-tenant ingress "
                         "(streamed tokens, tenant-fair admission waves, "
                         "power/memory shedding; requires --continuous)")
    ap.add_argument("--tenants", default="interactive:0:2:0.5,batch:1:1",
                    metavar="SPEC",
                    help="comma list of name[:priority[:weight"
                         "[:deadline_s]]] tenant classes; requests round-"
                         "robin across them (frontend mode)")
    ap.add_argument("--queue-depth", type=int, default=64, metavar="N",
                    help="bounded admission queue: submissions beyond N "
                         "queued requests are refused (backpressure)")
    ap.add_argument("--shed-depth", type=int, default=None, metavar="N",
                    help="queued requests admitted while the WHOLE "
                         "fleet's power/memory budget is hot before the "
                         "ingress sheds (default: --slots)")
    ap.add_argument("--power-budget-wh", type=float, default=None,
                    metavar="WH",
                    help="arm a battery-style power envelope of WH "
                         "watt-hours on every decode group (Eqs. 5-6): "
                         "serving drains it, hot groups re-route via the "
                         "masked split (continuous mode)")
    ap.add_argument("--power-threshold-w", type=float, default=8.0,
                    metavar="W",
                    help="P_available floor (W) under the power envelope")
    args = ap.parse_args()
    nodes = args.nodes or (2 if args.topology == "pair" else 3)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    if args.kv_int8:
        cfg = dataclasses.replace(cfg, kv_quant="int8")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    print(f"arch={cfg.name}{' (reduced)' if args.reduced else ''}"
          f"{' kv=int8' if args.kv_int8 else ''} "
          f"topology={args.topology}/{nodes}")

    if args.prefill_group is not None and not args.continuous:
        ap.error("--prefill-group requires --continuous (disaggregated "
                 "prefill rides the continuous overlapped-admission path)")
    if args.prefix_cache_blocks and not args.continuous:
        ap.error("--prefix-cache-blocks requires --continuous (the radix "
                 "cache lives in the slot runtime's admission loop)")
    if args.prefill_pool > 1 and args.prefill_group is None:
        ap.error("--prefill-pool > 1 requires --prefill-group (the pool "
                 "lives on the dedicated prefill spoke)")
    if (args.link_trace or args.mobility_beta is not None) \
            and not args.continuous:
        ap.error("--link-trace/--mobility-beta require --continuous (the "
                 "trace replays on the HeteroRuntime wave clock)")
    if args.mobility_beta is not None and not args.link_trace:
        ap.error("--mobility-beta only applies to a --link-trace")
    if args.wave_steps > 1 and not args.continuous:
        ap.error("--wave-steps > 1 requires --continuous (the wave driver "
                 "is the slot runtime's fused decode launcher)")
    if args.frontend and not args.continuous:
        ap.error("--frontend requires --continuous (the ingress feeds the "
                 "slot runtime at wave boundaries)")
    if args.power_budget_wh is not None and not args.continuous:
        ap.error("--power-budget-wh requires --continuous (the envelope "
                 "drains on the HeteroRuntime wave clock)")
    topology = build_topology(args.topology, nodes,
                              prefill_group=args.prefill_group)
    P = args.prompt_len
    reqs = request_stream(cfg.vocab_size, n=args.requests, mean_prompt=P,
                          seed=0, frontend_tokens=cfg.frontend_tokens,
                          frontend_dim=(cfg.frontend_dim or cfg.d_model)
                          if cfg.frontend else 0)
    if args.continuous:
        serve_continuous(cfg, params, reqs, prompt_len=P,
                         max_new=args.max_new, slots=args.slots,
                         split=args.split, macro_steps=args.macro_steps,
                         wave_steps=args.wave_steps,
                         overlap_admission=args.overlap_admission,
                         topology=topology,
                         telemetry_path=args.telemetry_json,
                         prefix_cache_blocks=args.prefix_cache_blocks,
                         prefix_block_size=args.prefix_block_size,
                         prefill_pool=args.prefill_pool,
                         kv_keep_rate=args.kv_keep_rate,
                         link_trace=args.link_trace,
                         mobility_beta=args.mobility_beta,
                         frontend=args.frontend,
                         tenants=parse_tenants(args.tenants),
                         queue_depth=args.queue_depth,
                         shed_depth=args.shed_depth,
                         power_budget_wh=args.power_budget_wh,
                         power_threshold_w=args.power_threshold_w)
        return

    prompts = np.stack([np.pad(r.prompt[:P], (0, max(0, P - len(r.prompt))))
                        for r in reqs]).astype(np.int32)
    batch = {"tokens": prompts}
    if cfg.frontend:
        batch["frontend"] = np.stack([r.frontend for r in reqs])

    def serve_task(b):
        eng = ServingEngine(cfg, params, max_len=P + args.max_new + 8,
                            macro_steps=args.macro_steps)
        return eng.generate(np.asarray(b["tokens"]),
                            max_new=args.max_new,
                            frontend=b.get("frontend")).tokens

    mode, fixed_r = parse_split(args.split)
    if mode == "none":
        t0 = time.perf_counter()
        toks = serve_task(batch)
        wall = time.perf_counter() - t0
        print(f"local-only: {toks.shape} in {wall:.2f}s "
              f"({args.requests * args.max_new / wall:.1f} tok/s)")
        return

    # --- HeteroEdge split -------------------------------------------------
    eng = C.OffloadEngine(lambda b: serve_task(b), topology=topology,
                          payload_bytes_per_item=P * cfg.d_model * 2,
                          jit=False)
    G = len(topology)
    if mode == "auto":
        # calibrate on a probe slice, synthesize profiles, solve
        t0 = time.perf_counter()
        serve_task({k: v[:2] for k, v in batch.items()})
        probe = time.perf_counter() - t0
        rs = [0.0, 0.3, 0.5, 0.7, 1.0]
        aux_p, pri_p, off_p = (C.MeasuredProfile(n) for n in ("a", "p", "o"))
        for r in rs:
            aux_p.add(r, probe * r, 6 * r, 50 * r)
            pri_p.add(r, probe * (1 - r) * 2.2, 5, 60 * (1 - r) + 15)
            off_p.add(r, 0.01 * r * args.requests, 0, 0)
        if G == 2:
            res = C.solve_split_ratio(
                C.fit_profiles(aux_p, pri_p, off_p),
                C.SolverConstraints(tau=probe * 2.2 * args.requests / 2))
            split = res.r_opt
            print(f"solver: r* = {res.r_opt:.2f} "
                  f"(predicted T {res.t_opt:.2f}s)")
        else:
            m = C.fit_profiles(aux_p, pri_p, off_p)
            fn = C.group_times_from_fits(m.T2, [(m.T1, m.T3)] * (G - 1))
            f_opt, t_opt = C.solve_star(fn, G)
            split = C.SplitVector(tuple(f_opt))
            print(f"solve_star: f* = {[f'{x:.2f}' for x in split.fractions]} "
                  f"(predicted makespan {t_opt:.2f}s)")
    else:
        split = C.SplitVector.from_r(fixed_r, G) if G > 2 else fixed_r
    rep = eng.run(batch, split)
    per_group = " ".join(f"{n}={c}" for n, c in zip(rep.group_names,
                                                    rep.n_group))
    print(f"r={rep.r:.2f} [{per_group}]  "
          f"T_parallel={rep.t_parallel:.2f}s T_serial={rep.t_serial:.2f}s "
          f"link={rep.t_offload_s*1e3:.1f}ms")
    print("outputs:", rep.outputs.shape)


if __name__ == "__main__":
    main()
