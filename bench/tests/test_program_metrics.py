"""The per-layer metrics beside the program's own names and spans: the
seven metrics of the first benchmark read the values pinned on the
recorded v5e trace (``data/decode.xplane.pb``) whether the reduction
holds the harness's host spans or every host span of the trace, and
``admit_boundary_ms`` reads the named admission boundary, and nothing on a
trace from a program that left it unnamed."""
import json
import os

import numpy as np
import pytest

from bench import run as R
from bench import trace as T
from bench import traffic as TR

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
XPLANE = os.path.join(DATA, "decode.xplane.pb")

# the seven metrics as the first benchmark reads them on the recorded trace
PINNED = {"ingress_wait_ms": 250.0,
          "prefill_device_ms": 0.8200025000000001,
          "prefill_mfu": 10.783669665981735,
          "decode_step_ms": 0.6932851875000001,
          "decode_step_mfu": 0.26115012986812014,
          "decode_attn_roofline": 2.403466653306386,
          "device_idle_share.open": 36.05258479233476}


def _context(tr) -> R.Context:
    """Four requests of 64-token prompts, each with four tokens inside the
    window, in one serve wave, over the whole of the trace's device time
    (olmo-1b widths at two layers, as recorded)."""
    ops = tr.devices[0].ops
    red = T.reduce(tr, (min(a for _, a, _ in ops), max(b for _, _, b in ops)))
    with open(os.path.join(os.path.dirname(DATA), "..", "configs",
                           "olmo-1b.json")) as f:
        spec = json.load(f)
    spec["num_layers"] = 2
    recs = [R.Record(TR.Request(i, 0.0, np.zeros(64, np.int32), 4),
                     t_due=0.1 * i, uid=i, arrivals=[0.5, 0.6, 0.7, 0.8])
            for i in range(4)]
    return R.Context(spec=spec, mix={"prompt_len": 64, "serving": {
        "macro_steps": 8, "slots": 4}}, peak=R.peak_of("TPU v5 lite"),
        records=recs, waves=[R.Wave(0.4, [0, 1, 2, 3])], red=red,
        window=(0.0, 1.0))


@pytest.mark.parametrize("span_names", [["serve_wave"], None],
                         ids=["harness_spans", "every_host_span"])
def test_existing_metrics_pinned(span_names):
    ctx = _context(T.load(XPLANE, span_names))
    assert {m: R.load_reader(m)(ctx) for m in PINNED} == PINNED


def test_admit_boundary_ms_reads_the_named_program():
    dev = T.Device(ops=[("%fusion.1 = x", 10, 14), ("%fusion.2 = x", 20, 26)],
                   modules=[("jit_admit_boundary(7)", 10, 14),
                            ("jit_admit_boundary(7)", 20, 26)])
    red = T.reduce(T.Trace({0: dev}, []), (0, 30))
    ctx = R.Context(spec={}, mix={}, peak={}, records=[], waves=[], red=red,
                    window=(0.0, 1.0))
    assert R.load_reader("admit_boundary_ms")(ctx) == pytest.approx(5e-6)


def test_admit_boundary_ms_silent_without_the_name():
    """The recorded trace is of a program whose boundary traced as
    ``jit__unknown``: the metric is left out, and does not raise."""
    ctx = _context(T.load(XPLANE, []))
    assert R.load_reader("admit_boundary_ms")(ctx) is None
