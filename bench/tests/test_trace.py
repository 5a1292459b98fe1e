"""The reduction from a profiler trace to device busy time, per-program
and per-operation device time and labelled idle gaps: on synthetic events
worked out by hand, and on a small trace recorded on a v5e chip (two
olmo-1b-width layers, 4 slots, K=8: ``data/decode.xplane.pb``, with the
engine's own counts of that run in ``data/decode.json``)."""
import json
import os

import pytest

from bench import trace as T

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_names():
    assert T.op_name("%decode_attention.8 = bf16[4,16,1,128] custom-call("
                     "s32[4] %x)") == "decode_attention"
    assert T.op_name("%while = (s32[]) while(...)") == "while"
    assert T.module_name("jit_prefill_step(1125)") == "jit_prefill_step"


def test_union():
    assert T.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]


def test_self_times_nested():
    evs = [("while", 0, 10), ("a", 1, 3), ("b", 4, 6), ("a", 11, 12)]
    assert T.self_times(evs) == {"while": 6, "a": 3, "b": 2}


def test_reduce_synthetic():
    dev = T.Device(
        ops=[("%while.1 = x", 10, 40), ("%fusion.2 = x", 12, 20),
             ("%decode_attention.3 = x", 22, 30), ("%copy.4 = x", 60, 70)],
        modules=[("jit_decode_loop(1)", 10, 40), ("jit_prefill_step(2)",
                                                  60, 70)])
    spans = [("serve_wave", 0, 100), ("group_run:hub", 35, 65)]
    tr = T.Trace({0: dev}, spans)
    red = T.reduce(tr, (15, 65))
    assert red.busy_ns[0] == (40 - 15) + (65 - 60)
    assert red.window_s == pytest.approx(50e-9)
    # the loop is cut by the window's start: 25 of its 30 ns are inside
    assert red.module_s("jit_decode_loop") == (pytest.approx(25e-9),
                                               pytest.approx(25 / 30))
    assert red.module_s("jit_prefill_step") == (pytest.approx(5e-9),
                                                pytest.approx(0.5))
    assert red.op_s("decode_attention") == pytest.approx(8e-9)
    assert red.op_s("fusion") == pytest.approx(5e-9)
    assert red.op_s("while") == pytest.approx(25e-9 - 13e-9)
    assert red.gaps == [(40, 60, 0)]
    b = T.breakdown(red)
    assert b["idle_gaps"] == [["group_run:hub@TPU0", pytest.approx(2e-8)]]
    assert b["device_ops"][0][0] == "jit_decode_loop/while"
    assert red.program_op_ns["jit_prefill_step/copy"] == 5


@pytest.fixture(scope="module")
def recorded():
    path = os.path.join(DATA, "decode.xplane.pb")
    meta = json.load(open(os.path.join(DATA, "decode.json")))
    tr = T.load(path, [])
    return tr, meta


def test_recorded_trace(recorded):
    tr, meta = recorded
    assert sorted(tr.devices) == [0]
    ops = tr.devices[0].ops
    lo = min(a for _, a, _ in ops)
    hi = max(b for _, _, b in ops)
    red = T.reduce(tr, (lo, hi))
    # the engine's own counts of the traced run
    t, n = red.module_s("jit_decode_loop")
    assert n == meta["macro_dispatches"]
    assert red.module_s("jit_prefill_step")[1] == meta["prefills"]
    kernel_calls = sum(1 for name, _, _ in ops
                       if T.op_name(name) == "decode_attention")
    assert kernel_calls == n * meta["K"] * meta["layers"]
    assert 0 < red.op_s("decode_attention") < t
    # nested events: self times add up to the union of the line exactly
    assert sum(red.op_self_ns.values()) == pytest.approx(red.busy_ns[0])
    assert 0 < red.busy_ns[0] <= hi - lo


def test_recorded_program_cut_by_the_window(recorded):
    """A window that opens in the middle of the first decode loop: that
    call counts for its half inside, so the time per call stays that of a
    whole call (counting it whole would read a quarter low)."""
    tr, meta = recorded
    loops = sorted((a, b) for n, a, b in tr.devices[0].modules
                   if T.module_name(n) == "jit_decode_loop")
    (a0, b0), hi = loops[0], max(b for _, _, b in tr.devices[0].ops)
    red = T.reduce(tr, (0.5 * (a0 + b0), hi))
    t, n = red.module_s("jit_decode_loop")
    assert n == pytest.approx(meta["macro_dispatches"] - 0.5)
    whole = sum(b - a for a, b in loops) / len(loops) * 1e-9
    assert t / n == pytest.approx(whole, rel=0.01)
