"""From a profiler trace (``.xplane.pb``) to device busy time, per-program
and per-operation device time, and idle gaps labelled by host spans.

On a TPU the trace holds one plane per chip (``/device:TPU:<n>``) with an
``XLA Modules`` line (one event per program execution, named
``jit_<function>(<fingerprint>)``) and an ``XLA Ops`` line (one event per
HLO instruction executed, named by the instruction's text,
``%<name>.<n> = ...``; a ``while`` event spans the operations of its body).
Host spans written with ``jax.profiler.TraceAnnotation`` sit on the
``/host:CPU`` plane, on the same clock.  Everything is read with
``jax.profiler.ProfileData`` alone.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"

Interval = Tuple[float, float]


def op_name(event_name: str) -> str:
    """``%decode_attention.8 = bf16[...] custom-call(...)`` -> the
    instruction's base name ``decode_attention``."""
    head = event_name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def module_name(event_name: str) -> str:
    """``jit_prefill_step(1125...)`` -> ``jit_prefill_step``."""
    return event_name.split("(", 1)[0]


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merge intervals into disjoint ones, sorted by start."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def self_times(events: Sequence[Tuple[str, float, float]]
               ) -> Dict[str, float]:
    """Per-name time of events that may nest on one line (a ``while``
    spans its body): each event's duration less that of the events it
    directly contains."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    out: Dict[str, float] = defaultdict(float)
    stack: List[list] = []            # [name, end, child time]
    for name, a, b in evs:
        while stack and stack[-1][1] <= a:
            n, _, child = stack.pop()
            out[n] -= child
        if stack:
            stack[-1][2] += b - a
        out[name] += b - a
        stack.append([name, b, 0.0])
    while stack:
        n, _, child = stack.pop()
        out[n] -= child
    return dict(out)


@dataclass
class Device:
    ops: List[Tuple[str, float, float]] = field(default_factory=list)
    modules: List[Tuple[str, float, float]] = field(default_factory=list)


@dataclass
class Trace:
    devices: Dict[int, Device]
    spans: List[Tuple[str, float, float]]     # host annotations


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str, span_names: Optional[Iterable[str]] = None) -> Trace:
    """Read device events of every TPU plane and the host spans whose
    name is in ``span_names`` (all host spans when None)."""
    from jax.profiler import ProfileData
    wanted = None if span_names is None else set(span_names)
    pd = ProfileData.from_file(path)
    devices: Dict[int, Device] = {}
    spans: List[Tuple[str, float, float]] = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = devices.setdefault(int(m.group(1)), Device())
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dev.ops.extend((e.name, e.start_ns, e.end_ns)
                                   for e in line.events)
                elif line.name == MODULES_LINE:
                    dev.modules.extend((e.name, e.start_ns, e.end_ns)
                                       for e in line.events)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if wanted is None or e.name in wanted:
                        spans.append((e.name, e.start_ns, e.end_ns))
    return Trace(devices, spans)


@dataclass
class Reduction:
    window_ns: Interval
    busy_ns: Dict[int, float]                   # per device
    module_ns: Dict[str, float]                 # summed over devices
    module_calls: Dict[str, float]              # a cut call counts its share
    op_self_ns: Dict[str, float]                # by base op name
    program_op_ns: Dict[str, float]             # by "program/op"
    gaps: List[Tuple[float, float, int]]        # (start, end, device)
    spans: List[Tuple[str, float, float]]

    @property
    def window_s(self) -> float:
        return (self.window_ns[1] - self.window_ns[0]) * 1e-9

    def busy_s(self) -> float:
        """Busy seconds averaged over the devices used."""
        return sum(self.busy_ns.values()) / max(len(self.busy_ns), 1) * 1e-9

    def module_s(self, name: str) -> Tuple[float, float]:
        """Device seconds and calls of the program ``name`` inside the
        window; a call cut by an edge counts as its share inside."""
        return (self.module_ns.get(name, 0.0) * 1e-9,
                self.module_calls.get(name, 0.0))

    def op_s(self, name: str) -> float:
        return self.op_self_ns.get(name, 0.0) * 1e-9

    def label(self, a: float, b: float) -> str:
        """The innermost host span open over the middle of [a, b]."""
        mid = 0.5 * (a + b)
        best = None
        for name, s, e in self.spans:
            if s <= mid <= e and (best is None or e - s < best[1]):
                best = (name, e - s)
        return best[0] if best else "no_span"


def reduce(trace: Trace, window_ns: Interval,
           devices: Optional[Iterable[int]] = None) -> Reduction:
    """Reduce ``trace`` over ``window_ns``: events are clipped to it (an
    operation or a program that straddles an edge counts for its part
    inside; a program's calls too)."""
    lo, hi = window_ns
    ids = sorted(trace.devices) if devices is None else list(devices)
    busy: Dict[int, float] = {}
    module_ns: Dict[str, float] = defaultdict(float)
    module_calls: Dict[str, float] = defaultdict(float)
    op_self: Dict[str, float] = defaultdict(float)
    prog_op: Dict[str, float] = defaultdict(float)
    gaps: List[Tuple[float, float, int]] = []
    for d in ids:
        dev = trace.devices.get(d, Device())
        ops = [(op_name(n), max(a, lo), min(b, hi))
               for n, a, b in dev.ops if b > lo and a < hi]
        merged = union((a, b) for _, a, b in ops)
        busy[d] = sum(b - a for a, b in merged)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        gaps.extend((edges[i], edges[i + 1], d)
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i])
        mods = sorted((a, b, module_name(n)) for n, a, b in dev.modules)
        starts = [m[0] for m in mods]
        prog = []
        for name, a, b in ops:
            i = bisect.bisect_right(starts, a) - 1
            inside = i >= 0 and a < mods[i][1]
            prog.append((f"{mods[i][2] if inside else '?'}/{name}", a, b))
        for name, t in self_times(ops).items():
            op_self[name] += t
        for name, t in self_times(prog).items():
            prog_op[name] += t
        for n, a, b in dev.modules:
            if b > lo and a < hi:
                # a program cut by an edge counts for its share inside, in
                # time and in calls alike
                inside = min(b, hi) - max(a, lo)
                module_ns[module_name(n)] += inside
                module_calls[module_name(n)] += inside / (b - a)
    return Reduction(window_ns, busy, dict(module_ns), dict(module_calls),
                     dict(op_self), dict(prog_op), gaps, trace.spans)


def breakdown(red: Reduction, top: int = 10) -> dict:
    """The device operations that took most time (self time summed over
    devices, named ``program/op``) and the longest idle gaps, each
    labelled with the host span open during it."""
    ops = sorted(red.program_op_ns.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(red.gaps, key=lambda g: g[0] - g[1])[:top]
    return {"device_ops": [[n, t * 1e-9] for n, t in ops],
            "idle_gaps": [[f"{red.label(a, b)}@TPU{d}", (b - a) * 1e-9]
                          for a, b, d in gaps]}


def window_of(trace: Trace, span: str) -> Interval:
    """The interval of the host span ``span`` (the traced window)."""
    for name, a, b in trace.spans:
        if name == span:
            return a, b
    raise KeyError(f"no host span {span!r} in the trace")
