#!/usr/bin/env python3
"""Chip benchmark of the serving path: one run of one cell.

    python3 bench/run.py --workload nemotron15b-rag --seed 7 --seconds 40 \
        --trace 0

A cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a model
configuration (``bench/configs/<config>.json``, its correctness limits in
``bench/limits/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``).  The configuration's ``family``
chooses its family module, ``bench/reference/<family>.py`` (the contract
is in ``bench/reference/__init__.py``): the leaves of its weights, its
plain reference and its work counts.  One process holds the cell's chips
and, in order:

1. makes the configuration's weights from ``--seed`` on the device, in the
   type they are served in (``bench/weights.py``, with the family's
   leaves);
2. builds the deployment users run: ``HeteroRuntime`` behind
   ``ServingFrontend``, with the topology, slots and split the mix fixes;
3. warms the cell's own programs (B=1 prefill at the mix's prompt length,
   the admission boundary on a fresh and on a decoded cache, the fused
   decode loop) — all of this, from process start, is ``setup_s``;
4. sends the mix's requests through ``ServingFrontend.submit`` for
   ``--seconds``, each at its due time (open loop), and follows each to
   the last token of its ``TokenStream``;
5. checks what the window served against the family's plain float32
   reference once the program's state is freed, and prints one JSON
   line.

``--trace 0`` reports the end-to-end metrics (client-side clocks);
``--trace 1`` traces the end of the window with the profiler and
reports the per-layer metrics, each read by ``bench/metrics/<name>.py``.
A run on anything but the cell's TPU chips, on a chip missing from
``bench/peaks.json``, or of a configuration whose family has no module,
exits with code 2 and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

import numpy as np  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import reference as FAM  # noqa: E402
from bench import traffic as TR  # noqa: E402
from bench import work  # noqa: E402

# requests still streaming this long after the window closes never came
GRACE_S = 60.0
# the correctness sample: the longest request, then others drawn from the
# seed, until this many served tokens (or this many requests) are held
CHECK_TOKENS = 320
CHECK_REQUESTS = 16
WINDOW_SPAN = "bench_window"
WARM_WAVES = 3
# a traced run traces the window's last this many seconds
TRACE_SECONDS = 10.0


class Refused(SystemExit):
    """No result: wrong platform, too few chips, unknown chip, bad files."""

    def __init__(self, msg: str):
        print(f"bench/run.py: {msg}", file=sys.stderr, flush=True)
        super().__init__(2)


# --------------------------------------------------------------------------
# the cell's files
# --------------------------------------------------------------------------
def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(workload: str, root: str = ROOT) -> dict:
    """The cell's entry, its configuration file and its traffic mix, all
    from the checkout ``root``.  The configuration's family module is
    loaded from there too, and its file kept in the spec
    (``family_file``)."""
    try:
        bench = load_json(os.path.join(root, "BENCHMARK.json"))
        cell = next(w for w in bench["workloads"] if w["name"] == workload)
        entry = next(c for c in bench["configs"]
                     if c["name"] == cell["config"])
        spec = load_json(os.path.join(root, entry["file"]))
        spec["limits"] = load_json(os.path.join(root, "bench", "limits",
                                                cell["config"] + ".json"))
        mix = load_json(os.path.join(root, "bench", "traffic",
                                     cell["traffic"] + ".json"))
    except (OSError, StopIteration, KeyError, ValueError) as e:
        raise Refused(f"cannot load cell {workload!r}: {e!r}")
    try:
        path = FAM.path_of(str(spec.get("family")), root)
    except ValueError as e:
        raise Refused(f"cannot load cell {workload!r}: {e}")
    if not os.path.isfile(path):
        raise Refused(f"configuration {entry['name']!r} names family "
                      f"{spec.get('family')!r}, and "
                      f"{os.path.relpath(path, root)} does not exist")
    FAM.load(path)
    spec["family_file"] = path
    metrics = [m for m in bench["end_to_end"] + bench["per_layer"]
               if workload in m.get("workloads", [workload])]
    return {"cell": cell, "spec": spec, "mix": mix,
            "end_to_end": [m for m in metrics if m in bench["end_to_end"]],
            "per_layer": [m for m in metrics if m in bench["per_layer"]]}


def peak_of(kind: str) -> dict:
    peaks = load_json(os.path.join(BENCH, "peaks.json"))["chips"]
    if kind not in peaks:
        raise Refused(f"device kind {kind!r} is not in bench/peaks.json")
    return peaks[kind]


def require_chips(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise Refused(f"no TPU: JAX runs on {devs[0].platform!r}")
    if len(devs) < chips:
        raise Refused(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs[:chips]


def enable_compile_cache() -> str:
    """The program's persistent compile cache (``JAX_COMPILATION_CACHE_DIR``
    when set, else ``.jax_cache`` in the checkout), holding every program,
    however quick to compile, so that warm-up loads them all."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache as enable
    path = enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def model_config(spec: dict):
    """The program's ModelConfig: the registry's architecture with every
    size of the configuration file applied."""
    from repro.configs.base import ModelConfig, get_config
    fields = {f.name for f in dataclasses.fields(ModelConfig)}
    over = {k: v for k, v in spec.items()
            if k in fields and k not in ("name", "source")}
    return dataclasses.replace(get_config(spec["arch"]), name=spec["name"],
                               **over)


def make_params(spec: dict, cfg, seed: int, device):
    """Weights from the seed, checked against the program's own tree."""
    import jax
    from bench import weights as W
    from repro.models import model as M
    params = W.program_params(spec, seed, device)
    want = jax.eval_shape(lambda k: M.init_params(cfg, k),
                          jax.ShapeDtypeStruct((2,), np.uint32))
    got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    exp = jax.tree.map(lambda a: (a.shape, a.dtype), want)
    if got != exp:
        raise Refused(f"weights do not match the program's tree: "
                      f"{got} vs {exp}")
    return params


# --------------------------------------------------------------------------
# the deployment, and the benchmark's own spans around it
# --------------------------------------------------------------------------
@dataclasses.dataclass
class Wave:
    t_start: float
    uids: List[int]


class RuntimeProbe:
    """The runtime as the frontend sees it, with a span around every
    serve wave (host clock and ``serve_wave`` in the profiler's trace)."""

    def __init__(self, runtime):
        self._rt = runtime
        self.waves: List[Wave] = []

    def __getattr__(self, name):
        return getattr(self._rt, name)

    def serve(self, requests, **kw):
        import jax
        self.waves.append(Wave(time.perf_counter(),
                               [r.uid for r in requests]))
        with jax.profiler.TraceAnnotation("serve_wave"):
            return self._rt.serve(requests, **kw)


def _annotate(obj, attr: str, span: str) -> None:
    """Wrap a call into a layer in a host span of the profiler's trace."""
    import jax
    fn = getattr(obj, attr)

    def wrapped(*a, **kw):
        with jax.profiler.TraceAnnotation(span):
            return fn(*a, **kw)
    setattr(obj, attr, wrapped)


class Deployment:
    def __init__(self, spec: dict, mix: dict, cfg, params, devices):
        import repro.core as C
        from repro.serving.frontend import ServingFrontend
        sv = mix["serving"]
        self.cfg, self.mix = cfg, mix
        self.devices = devices
        names = sv["groups"]
        if sv["topology"] == "pair":
            groups = [C.NodeGroup(n, [devices[0]], prof) for n, prof in
                      zip(names, (C.JETSON_NANO, C.JETSON_XAVIER))]
            topo = C.Topology.pair(groups[0], groups[1], C.WIFI_5GHZ)
        elif sv["topology"] == "star":
            groups = [C.NodeGroup(n, [devices[i % len(devices)]],
                                  C.JETSON_NANO if i == 0
                                  else C.JETSON_XAVIER)
                      for i, n in enumerate(names)]
            topo = C.Topology.star(groups[0], groups[1:], C.ICI_LINK)
        else:
            raise Refused(f"unknown topology {sv['topology']!r}")
        self.rt = C.HeteroRuntime(topo, slots=sv["slots"],
                                  max_len=TR.cache_len(mix),
                                  macro_steps=sv["macro_steps"])
        self.rt.add_task(cfg.name, cfg, params)
        self.engines = self.rt.tasks[cfg.name].engines
        for name, eng in self.engines.items():
            _annotate(eng, "run", f"group_run:{name}")
            _annotate(eng, "prefill", "prefill_dispatch")
            _annotate(eng, "_admit_boundary", "admit_boundary_dispatch")
        self.probe = RuntimeProbe(self.rt)
        self.fe = ServingFrontend(
            self.probe, {"bench": C.TenantClass("bench")},
            queue_depth=sv["queue_depth"], split=sv.get("split"))

    def warm(self, rng: np.random.Generator) -> None:
        """Compile and run every program the window will use, at the
        cell's shapes: per group, ``slots + 1`` requests of two tokens
        each, so that the boundary admits both into a fresh cache and into
        one the decode loop has written; then a few waves through the
        frontend, for the host-side arithmetic of serve waves."""
        from repro.serving.engine import ServeRequest
        P, V = int(self.mix["prompt_len"]), self.cfg.vocab_size
        slots = self.rt.slots

        def prompt():
            return rng.integers(0, V, P, dtype=np.int32)
        for eng in self.engines.values():
            eng.run([ServeRequest(uid=10 ** 9 + i, prompt=prompt(),
                                  max_new=2, task=self.cfg.name)
                     for i in range(slots + 1)])

        async def waves():
            # enough waves for the online split controller to re-solve
            await self.fe.start()
            for _ in range(WARM_WAVES):
                streams = [await self.fe.submit(prompt(), 2, tenant="bench",
                                                task=self.cfg.name)
                           for _ in range(2 * len(self.engines))]
                for s in streams:
                    await s.collect()
            await self.fe.stop()
        asyncio.run(waves())
        self.probe.waves.clear()

    def peak_bytes(self) -> int:
        return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in self.devices)


# --------------------------------------------------------------------------
# the client side
# --------------------------------------------------------------------------
@dataclasses.dataclass
class Record:
    req: TR.Request
    t_due: float = math.nan
    t_submit: float = math.nan
    t_first: float = math.nan
    t_last: float = math.nan
    uid: int = -1
    tokens: List[int] = dataclasses.field(default_factory=list)
    arrivals: List[float] = dataclasses.field(default_factory=list)
    error: str = ""
    done: bool = False


async def _client(fe, task: str, rec: Record) -> None:
    from repro.serving.frontend import FrontendError
    rec.t_submit = time.perf_counter()
    try:
        stream = await fe.submit(rec.req.prompt, rec.req.max_new,
                                 tenant="bench", task=task)
    except FrontendError as e:
        rec.error = f"refused: {e}"
        return
    rec.uid = stream.uid
    try:
        async for tok in stream:
            now = time.perf_counter()
            if not rec.tokens:
                rec.t_first = now
            rec.t_last = now
            rec.tokens.append(int(tok))
            rec.arrivals.append(now)
    except FrontendError as e:
        rec.error = f"aborted: {e}"
        return
    rec.done = True


class Tracer:
    """Profiler trace of part of the window, with the window's own host
    span so the reduction knows its bounds."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        self.t0 = self.t1 = math.nan
        self._ann = None

    def start(self) -> None:
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._ann = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._ann.__enter__()
        self.t0 = time.perf_counter()

    def stop(self) -> None:
        """End the window's span (on the thread that opened it)."""
        self.t1 = time.perf_counter()
        self._ann.__exit__(None, None, None)

    @staticmethod
    def collect() -> None:
        """Stop the profiler and write the trace (any thread)."""
        import jax
        jax.profiler.stop_trace()

    def cleanup(self) -> None:
        shutil.rmtree(self.dir, ignore_errors=True)


async def drive(dep: Deployment, reqs: List[TR.Request], seconds: float,
                tracer: Optional[Tracer] = None) -> dict:
    """Send the schedule through the frontend for ``seconds``, each
    request at its due time; wait for what was sent (up to ``GRACE_S``
    past the close)."""
    fe, task = dep.fe, dep.cfg.name
    await fe.start()
    recs: List[Record] = []
    tasks: List[asyncio.Task] = []
    # the trace covers the window's last ``TRACE_SECONDS`` and stops as it
    # closes (off the event loop), so the schedule is never held up
    trace_from = seconds - min(TRACE_SECONDS, seconds)

    async def trace_window(t0):
        await asyncio.sleep(max(0.0, t0 + trace_from - time.perf_counter()))
        tracer.start()
        await asyncio.sleep(max(0.0, t0 + seconds - time.perf_counter()))
        tracer.stop()
        await asyncio.get_running_loop().run_in_executor(None,
                                                         tracer.collect)

    t0 = time.perf_counter()
    t_end = t0 + seconds
    tr_task = asyncio.create_task(trace_window(t0)) if tracer else None
    for req in reqs:
        due = t0 + req.due_s
        if due >= t_end:
            break
        delay = due - time.perf_counter()
        if delay > 0:
            await asyncio.sleep(delay)
        rec = Record(req, t_due=due)
        recs.append(rec)
        tasks.append(asyncio.create_task(_client(fe, task, rec)))
    await asyncio.sleep(max(0.0, t_end - time.perf_counter()))
    t_close = time.perf_counter()
    if tr_task is not None:
        await tr_task
    if tasks:
        done, _ = await asyncio.wait(tasks, timeout=GRACE_S)
        for t in done:
            t.result()      # a client that raised fails the run
    late = [r for r in recs if not r.done and not r.error]
    for r in late:
        r.error = "never finished"
    if not late:
        await fe.stop()
    return {"records": recs, "t0": t0, "t_close": t_close,
            "stuck": bool(late)}


# --------------------------------------------------------------------------
# the metrics
# --------------------------------------------------------------------------
def end_to_end(records: List[Record], t0: float, t_close: float,
               setup_s: float) -> Dict[str, float]:
    ok = [r for r in records if r.done]
    ttft = [(r.t_first - r.t_due) * 1e3 for r in records if r.done]
    # a failed request misses every limit: it enters the tail as +inf
    failed = len(records) - len(ok)
    ttft += [math.inf] * failed
    tpot = [(r.t_last - r.t_first) / (len(r.tokens) - 1) * 1e3
            for r in ok if len(r.tokens) >= 2]
    tpot += [math.inf] * failed
    toks = sum(1 for r in records for t in r.arrivals if t0 <= t < t_close)
    return {"setup_s": setup_s,
            "ttft_p90_ms": TR.p_quantile(ttft, 90),
            "tpot_p90_ms": TR.p_quantile(tpot, 90),
            "output_tok_per_s": toks / (t_close - t0),
            "ttft_p50_ms": TR.p_quantile(ttft, 50),
            "tpot_p50_ms": TR.p_quantile(tpot, 50)}


def load_reader(name: str):
    path = os.path.join(BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def read_layer_metric(name: str, ctx) -> Optional[float]:
    """A per-layer metric's value, or None where its reader finds nothing
    to read or needs a work count that the family does not give."""
    try:
        return load_reader(name)(ctx)
    except work.Uncounted:
        return None


# --------------------------------------------------------------------------
# correctness
# --------------------------------------------------------------------------
def sample_for_check(records: List[Record], seed: int) -> List[Record]:
    """The longest finished request, then others drawn from the seed."""
    done = [r for r in records if r.done and r.tokens]
    if not done:
        return []
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 1]))
    longest = max(done, key=lambda r: len(r.tokens))
    rest = [done[i] for i in rng.permutation(len(done))
            if done[i] is not longest]
    out, n = [longest], len(longest.tokens)
    for r in rest:
        if n >= CHECK_TOKENS or len(out) >= CHECK_REQUESTS:
            break
        out.append(r)
        n += len(r.tokens)
    return out


def served_gaps(spec: dict, seed: int, sample: List[Record],
                quants=()) -> dict:
    """Reference logits at every served position of the sample.  Returns
    the gap (reference best minus reference logit of the served token) of
    each served token, and for each lower precision in ``quants`` the
    gap of the token that precision puts first."""
    fam = FAM.family(spec)
    P = len(sample[0].req.prompt)
    T = P + max(len(r.tokens) for r in sample) - 1
    seqs = np.zeros((len(sample), T), np.int32)
    rows, served = [], []
    for b, r in enumerate(sample):
        s = np.concatenate([r.req.prompt, np.asarray(r.tokens[:-1],
                                                     np.int32)])
        seqs[b, :len(s)] = s
        rows += [(b, P - 1 + j) for j in range(len(r.tokens))]
        served += r.tokens
    rows = np.asarray(rows)
    served = np.asarray(served)
    ref = np.asarray(fam.logits_at(spec, seed, seqs, rows))
    best = ref.max(axis=-1)
    out = {"served": best - ref[np.arange(len(served)), served],
           "n_tokens": len(served)}
    for q in quants:
        low = np.asarray(fam.logits_at(spec, seed, seqs, rows, quant=q))
        pick = low.argmax(axis=-1)
        out[q] = best - ref[np.arange(len(pick)), pick]
    return out


def check(spec: dict, seed: int, records: List[Record], vocab: int,
          controls=()) -> Dict[str, dict]:
    """The numbers compared, each beside its limit: under ``"program"``
    for what the window served, and under each name in ``controls`` (a
    lower precision of the reference: ``bf16``, ``int8`` or ``fp8``) for
    that control in the program's place, its logit gap read at the tokens
    it puts first on the same prompts and served tokens."""
    limit = float(spec["limits"]["logit_gap"])
    never = sum(1 for r in records if r.error == "never finished")
    wrong_len = sum(1 for r in records
                    if r.done and len(r.tokens) != r.req.max_new)
    bad_tok = sum(1 for r in records if r.done
                  and any(t < 0 or t >= vocab for t in r.tokens))
    checks = {"never_finished": [never, 0],
              "wrong_length": [wrong_len, 0],
              "token_outside_vocab": [bad_tok, 0]}
    # with a request still being served the program's state cannot be
    # freed, and the run is not correct whatever the reference says
    sample = [] if never else sample_for_check(records, seed)
    out = {who: dict(checks) for who in ("program",) + tuple(controls)}
    if sample and not bad_tok:
        g = served_gaps(spec, seed, sample, controls)
        print(f"[bench] reference compared {g['n_tokens']} served tokens "
              f"of {len(sample)} requests", file=sys.stderr, flush=True)
        for who in out:
            gap = g["served" if who == "program" else who]
            out[who]["logit_gap"] = [float(gap.max()), limit]
    else:
        for who in out:
            out[who]["logit_gap"] = [math.inf, limit]
    return out


def is_correct(checks: dict) -> bool:
    return all(v <= lim for v, lim in checks.values())


# --------------------------------------------------------------------------
class CompileCounter:
    """Backend compilations in this process, counted by a listener on
    JAX's own events (registered once, at the first count taken)."""
    count = 0
    _registered = False

    @classmethod
    def now(cls) -> int:
        if not cls._registered:
            import jax

            def listener(event, duration, **kw):
                if "backend_compile" in event:
                    cls.count += 1
            jax.monitoring.register_event_duration_secs_listener(listener)
            cls._registered = True
        return cls.count


def run(workload: str, seed: int, seconds: float, trace: bool,
        cell: Optional[dict] = None, require_chip: bool = True,
        controls=()) -> dict:
    """One run of the cell; returns the result line's object.  ``cell``
    (from :func:`load_cell`) may be given directly; ``require_chip=False``
    skips the look for a chip (tests on the CPU).  Each control named in
    ``controls`` is checked in the program's place as well, under
    ``_controls`` (``bench/calibrate.py``)."""
    import jax
    cell = cell or load_cell(workload)
    spec, mix, chips = cell["spec"], cell["mix"], cell["cell"]["chips"]
    if require_chip:
        devices = require_chips(chips)
        peak = peak_of(devices[0].device_kind)
        enable_compile_cache()
    else:
        devices = jax.devices()[:chips]
        peak = peak_of("TPU v5 lite")
    CompileCounter.now()
    log = lambda m: print(f"[bench] {m}", file=sys.stderr, flush=True)  # noqa
    cfg = model_config(spec)
    t = time.perf_counter()
    params = make_params(spec, cfg, seed, devices[0])
    log(f"weights made in {time.perf_counter() - t:.3f} s")
    dep = Deployment(spec, mix, cfg, params, devices)
    del params
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 2]))
    t = time.perf_counter()
    dep.warm(rng)
    log(f"warm-up {time.perf_counter() - t:.3f} s")
    reqs = TR.schedule(mix, seed, seconds, cfg.vocab_size)
    log(TR.describe(mix, seconds))
    tracer = Tracer() if trace else None
    n_compiles = CompileCounter.now()
    t_setup = time.perf_counter()
    out = asyncio.run(drive(dep, reqs, seconds, tracer))
    setup_s = out["t0"] - T_PROCESS
    in_window = CompileCounter.now() - n_compiles
    recs = out["records"]
    mem = dep.peak_bytes()
    log(f"setup_s {setup_s:.3f} (to the loop start "
        f"{t_setup - T_PROCESS:.3f}); compiles in the window {in_window}; "
        f"waves {len(dep.probe.waves)}")
    lateness = [r.t_submit - r.t_due for r in recs
                if not math.isnan(r.t_submit)]
    if lateness:
        log(f"generator lateness mean {np.mean(lateness) * 1e3:.3f} ms, "
            f"max {np.max(lateness) * 1e3:.3f} ms")
    e2e = end_to_end(recs, out["t0"], out["t_close"], setup_s)
    log("end to end: " + json.dumps(e2e))
    metrics: Dict[str, dict] = {}
    result: Dict = {}
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": chips, "memory_peak_bytes": mem}
    if trace:
        from bench import trace as TRC
        names = ["serve_wave", WINDOW_SPAN, "prefill_dispatch",
                 "admit_boundary_dispatch"] + [f"group_run:{g}"
                                               for g in dep.engines]
        tr = TRC.load(TRC.find_xplane(tracer.dir), names)
        tracer.cleanup()
        red = TRC.reduce(tr, TRC.window_of(tr, WINDOW_SPAN),
                         devices=[d.id for d in devices])
        ctx = Context(spec=spec, mix=mix, peak=peak, records=recs,
                      waves=dep.probe.waves, red=red,
                      window=(tracer.t0, tracer.t1))
        for m in cell["per_layer"]:
            v = read_layer_metric(m["name"], ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        device["busy_s"] = red.busy_s()
        device["window_s"] = red.window_s
        result["breakdown"] = TRC.breakdown(red)
        log("breakdown: " + json.dumps(result["breakdown"]))
    else:
        for m in cell["end_to_end"]:
            if math.isfinite(e2e[m["name"]]):
                metrics[m["name"]] = {"value": float(e2e[m["name"]]),
                                      "unit": m["unit"]}
    failed = sum(1 for r in recs if not r.done)
    # free the program's state before the reference runs
    stuck = out["stuck"]
    if not stuck:
        del dep
        gc.collect()
    t = time.perf_counter()
    by_who = check(spec, seed, recs, cfg.vocab_size, controls)
    checks = by_who.pop("program")
    log(f"reference check {time.perf_counter() - t:.3f} s")
    correct = is_correct(checks)
    result.update({"correct": correct, "attempted": len(recs),
                   "failed": failed, "metrics": metrics, "device": device})
    result["checks"] = {k: {"value": v if math.isfinite(v) else str(v),
                            "limit": lim} for k, (v, lim) in checks.items()}
    for k, (v, lim) in checks.items():
        print(f"check {k}: {v} (limit {lim})", file=sys.stderr, flush=True)
    result["_stuck"] = stuck
    result["_compiles_in_window"] = in_window
    result["_controls"] = by_who
    return result


@dataclasses.dataclass
class Context:
    """What a per-layer reader may read."""
    spec: dict
    mix: dict
    peak: dict
    records: list
    waves: list
    red: object           # bench.trace.Reduction of the traced window
    window: tuple         # traced window, host perf_counter seconds

    def decode_tokens(self):
        """(prompt length, index j >= 1) of every token that the decode
        loop delivered to a client inside the traced window."""
        lo, hi = self.window
        return [(len(r.req.prompt), j) for r in self.records
                for j, t in enumerate(r.arrivals) if j >= 1 and lo <= t < hi]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    res = run(args.workload, args.seed, args.seconds, bool(args.trace))
    stuck = res.pop("_stuck")
    res.pop("_compiles_in_window")
    res.pop("_controls")
    print(json.dumps(res, allow_nan=False), flush=True)
    if stuck:
        # a request never finished: the serve thread may still be busy
        os._exit(0)


if __name__ == "__main__":
    main()
