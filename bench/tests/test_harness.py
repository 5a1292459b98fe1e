"""The harness driven end to end on the CPU at a tiny size (the look for a
chip skipped): a sound run comes out correct, and each fault the cells can
have — a token altered where it is produced, a decode step that leaves
its cache unchanged, the lower-precision control in the program's place —
comes out not correct.  Without a TPU, or on a
chip missing from the peaks table, the command exits 2 with no result."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from bench import run as R
from bench.tests import tiny

SEED = 2 ** 31 + 777


def _run(spec, mx, seconds=2.0, trace=False, chips=1):
    return R.run("tiny", SEED, seconds, trace, cell=tiny.cell(spec, mx, chips),
                 require_chip=False)


def test_sound_run_is_correct():
    res = _run(tiny.NEMOTRON_LIKE, tiny.mix())
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] == 40
    assert set(res["metrics"]) == {"setup_s", "ttft_p90_ms", "tpot_p90_ms"}
    assert list(res["checks"])[-1] == "logit_gap"
    assert res["_compiles_in_window"] == 0


def test_traced_run_reads_host_spans():
    """On the CPU the trace has no TPU plane: the device readers find
    nothing and stay silent; the span reader still reads."""
    res = _run(tiny.OLMO_LIKE, tiny.mix(), trace=True)
    assert res["correct"], res["checks"]
    assert "ingress_wait_ms" in res["metrics"]
    for m in ("prefill_mfu", "decode_step_mfu", "decode_attn_roofline"):
        assert m not in res["metrics"]
    assert "breakdown" in res


def test_altered_token_is_not_correct(monkeypatch):
    from repro.serving.engine import ContinuousServingEngine
    orig = ContinuousServingEngine._consume_block

    def consume(self, block, *a, **kw):
        block = np.array(block)
        block[0] = (block[0] + 1) % self.cfg.vocab_size
        return orig(self, block, *a, **kw)
    monkeypatch.setattr(ContinuousServingEngine, "_consume_block", consume)
    res = _run(tiny.OLMO_LIKE, tiny.mix())
    assert not res["correct"]
    assert res["checks"]["logit_gap"]["value"] > \
        res["checks"]["logit_gap"]["limit"]


def test_unwritten_cache_is_not_correct(monkeypatch):
    """A decode step that returns its KV state unchanged (the new token's
    keys and values never written) serves wrong tokens."""
    from repro.models import attention
    monkeypatch.setattr(attention, "cache_update",
                        lambda cache, new, index: cache)
    res = _run(tiny.OLMO_LIKE, tiny.mix())
    assert not res["correct"]
    assert res["checks"]["logit_gap"]["value"] > \
        res["checks"]["logit_gap"]["limit"]


def test_lower_precision_control_is_not_correct():
    """The reference computed one step below the configuration's
    precision (bfloat16 for this float32 cell) in the program's place,
    through the same check on the same prompts and served tokens: it
    misses the limit that the sound run keeps."""
    res = R.run("tiny", SEED, 3.0, False, require_chip=False,
                cell=tiny.cell(tiny.NEMOTRON_LIKE, tiny.mix()),
                controls=("bf16",))
    assert res["correct"], res["checks"]
    control = res["_controls"]["bf16"]
    assert not R.is_correct(control)
    gap, limit = control["logit_gap"]
    assert gap > limit


def test_star_on_four_devices(tmp_path):
    """Hub and three spokes, one forced host device each."""
    code = (
        "import sys, json; sys.path[:0] = [%r, %r]\n"
        "from bench import run as R\n"
        "from bench.tests import tiny\n"
        "mx = tiny.mix(topology='star', split=[0.25] * 4,\n"
        "              groups=('hub', 'spoke1', 'spoke2', 'spoke3'))\n"
        "res = R.run('tiny', 5, 2.0, False, require_chip=False,\n"
        "            cell=tiny.cell(tiny.OLMO_LIKE, mx, chips=4))\n"
        "print(json.dumps({'correct': res['correct'],\n"
        "                  'count': res['device']['count']}))\n"
    ) % (R.ROOT, os.path.join(R.ROOT, "src"))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    assert json.loads(p.stdout.splitlines()[-1]) == {"correct": True,
                                                      "count": 4}


def test_no_tpu_exits_2_without_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(R.ROOT, "bench",
                                                     "run.py"),
                        "--workload", "nemotron15b-rag", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 2
    assert p.stdout.strip() == ""


def test_unknown_chip_is_refused():
    with pytest.raises(SystemExit) as e:
        R.peak_of("TPU v99")
    assert e.value.code == 2
