"""Time to first token, traced inside the program.

* Every completed request's TTFT splits into four phases (queue,
  group_wait, admit_wait, first_token) stamped where the work happens;
  they are non-negative and sum to ``TokenStream.ttft_s``, also when a
  group dies mid-wave and its slice is re-served on a survivor.
* Under the profiler the serving path's named host spans
  (``serving/spans.py``) land on the host plane with their stats, each
  ``runtime.group_run`` inside a ``frontend.wave``; tokens do not move.
* The admission boundary programs trace under stable names, shared by
  sibling engines.
"""
import asyncio
import glob
import os

import jax
import numpy as np
import pytest

import repro.core as C
from repro.configs.base import get_config, reduced
from repro.models import model as M
from repro.serving.engine import ContinuousServingEngine, ServeRequest
from repro.serving.frontend import PHASES, ServingFrontend
from repro.serving.spans import NAMES

SLOTS = 2
MAX_LEN = 48
PROMPT = 8
MACRO_K = 4
MAX_NEWS = [1, 6, 3, 1, 7, 4, 2, 5]
TENANTS = {"a": C.TenantClass("a", priority=0, weight=2.0),
           "b": C.TenantClass("b", priority=1, weight=1.0)}


@pytest.fixture(scope="module")
def small_llama():
    cfg = reduced(get_config("llama3.2-1b"))
    return cfg, M.init_params(cfg, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def prompts(small_llama):
    cfg, _ = small_llama
    rng = np.random.default_rng(0)
    return rng.integers(0, cfg.vocab_size,
                        (len(MAX_NEWS), PROMPT)).astype(np.int32)


def _runtime(cfg, params, spokes=1):
    dev = jax.devices()[0]
    hub = C.NodeGroup("pri", [dev], C.JETSON_NANO)
    aux = [C.NodeGroup(f"aux{i}", [dev], C.JETSON_XAVIER)
           for i in range(spokes)]
    topo = C.Topology.pair(hub, aux[0], C.ICI_LINK) if spokes == 1 \
        else C.Topology.star(hub, aux, C.ICI_LINK)
    rt = C.HeteroRuntime(topo, slots=SLOTS, max_len=MAX_LEN,
                         macro_steps=MACRO_K)
    rt.add_task(cfg.name, cfg, params)
    rt.warmup([ServeRequest(uid=0, prompt=np.zeros(PROMPT, np.int32),
                            max_new=2, task=cfg.name)])
    return topo, rt


def _drive(rt, cfg, prompts, wave_requests=None):
    """Submit every request, collect every stream; returns the streams
    in submission order and the frontend's telemetry."""
    async def go():
        fe = ServingFrontend(rt, TENANTS, split=0.5,
                             wave_requests=wave_requests)
        await fe.start()
        names = sorted(TENANTS)
        streams = [await fe.submit(prompts[i], MAX_NEWS[i],
                                   tenant=names[i % len(names)],
                                   task=cfg.name)
                   for i in range(len(MAX_NEWS))]
        for s in streams:
            await s.collect()
        await fe.stop()
        return streams, fe.telemetry()
    return asyncio.run(go())


def _check_phases(streams):
    for s in streams:
        assert s.done and s.error is None
        assert s.phases is not None and sorted(s.phases) == sorted(PHASES)
        assert all(v >= 0.0 for v in s.phases.values()), s.phases
        assert abs(sum(s.phases.values()) - s.ttft_s) <= 1e-6


def test_phases_sum_to_ttft(small_llama, prompts):
    cfg, params = small_llama
    _, rt = _runtime(cfg, params)
    streams, tel = _drive(rt, cfg, prompts)
    _check_phases(streams)
    for name, t in tel["tenants"].items():
        mine = [s for s in streams if s.tenant == name]
        for p in PHASES:
            assert t[f"{p}_mean_s"] == pytest.approx(
                np.mean([s.phases[p] for s in mine]))


@pytest.mark.parametrize("stage", ["dispatch", "await"])
def test_phases_survive_group_kill(small_llama, prompts, stage):
    """A spoke dies in the second wave, before its engine runs
    (``dispatch``) or after its tokens streamed (``await``): its slice is
    re-served on a survivor, and each request still carries one set of
    phases, that of the attempt that delivered its first token."""
    cfg, params = small_llama
    topo, rt = _runtime(cfg, params, spokes=2)
    topo.groups[1].inject_fault(stage, after=1)
    streams, tel = _drive(rt, cfg, prompts, wave_requests=4)
    assert not topo.groups[1].alive
    assert tel["runtime"]["wave_requeued"] >= 1
    assert [len(s.tokens) for s in streams] == MAX_NEWS
    _check_phases(streams)


def _host_events(log_dir):
    from jax.profiler import ProfileData
    path = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    out = {n: [] for n in NAMES}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in out:
                    out[e.name].append((e.start_ns, e.end_ns,
                                        dict(e.stats)))
    return out


def test_spans_on_the_host_plane(small_llama, prompts, tmp_path):
    cfg, params = small_llama
    _, rt = _runtime(cfg, params)
    plain, _ = _drive(rt, cfg, prompts)
    with jax.profiler.trace(str(tmp_path)):
        traced, _ = _drive(rt, cfg, prompts)
    assert [s.tokens for s in traced] == [s.tokens for s in plain]
    ev = _host_events(str(tmp_path))
    for name in NAMES:
        assert ev[name], f"no {name} span on the host plane"
    assert all("uid" in st and "inline" in st
               for _, _, st in ev["engine.prefill"])
    assert all(1 <= st["live"] <= SLOTS for _, _, st in ev["engine.launch"])
    assert all({"group", "wave", "n"} <= set(st)
               for _, _, st in ev["runtime.group_run"])
    waves = [(a, b) for a, b, _ in ev["frontend.wave"]]
    for a, b, _ in ev["runtime.group_run"]:
        assert any(wa <= a and b <= wb for wa, wb in waves)


def test_boundary_programs_are_named_and_shared(small_llama):
    cfg, params = small_llama
    eng = ContinuousServingEngine(cfg, params, slots=SLOTS, max_len=MAX_LEN,
                                  macro_steps=MACRO_K)
    sib = ContinuousServingEngine(cfg, params, slots=SLOTS, max_len=MAX_LEN,
                                  macro_steps=MACRO_K, share_from=eng)
    for attr in ("_admit_boundary", "_write_slot", "_splice_slots"):
        assert getattr(sib, attr) is getattr(eng, attr)
    cache, cur_tok, lengths, remaining, done = jax.eval_shape(
        eng.init_state)
    batch = {"tokens": np.zeros((1, PROMPT), np.int32)}
    logits, pre = jax.eval_shape(eng.prefill, params, batch)
    ids = jax.ShapeDtypeStruct((SLOTS,), np.int32)
    lowered = {
        "admit_boundary": eng._admit_boundary.lower(
            cache, (pre,) * SLOTS, ids, cur_tok, lengths, remaining, done,
            jax.ShapeDtypeStruct((SLOTS, logits.shape[-1]), logits.dtype),
            ids, ids, eos_id=-1),
        "write_slot": eng._write_slot.lower(
            cache, pre, jax.ShapeDtypeStruct((), np.int32)),
        "splice_slots": eng._splice_slots.lower(cache, [pre] * SLOTS, ids),
    }
    for name, low in lowered.items():
        assert low.as_text().startswith(f"module @jit_{name} "), name
