"""The plain reference against the program at reduced widths on the CPU:
the model's logits over whole sequences, and the served path (B=1
prefill, boundary splice, fused decode loop with the Pallas kernel in
interpret mode, head) whose greedy tokens the reference must rank first.
"""
import numpy as np
import pytest

import jax

from bench import run as R
from bench import weights as W
from bench.reference import dense
from bench.tests.tiny import NEMOTRON_LIKE, OLMO_LIKE

SEED = 2 ** 31 + 12345


@pytest.mark.parametrize("spec", [OLMO_LIKE, NEMOTRON_LIKE],
                         ids=["olmo", "nemotron"])
def test_logits_match_program_forward(spec):
    from repro.models import model as M
    cfg = R.model_config(spec)
    params = W.program_params(spec, SEED)
    rng = np.random.default_rng(0)
    toks = rng.integers(0, spec["vocab_size"], (3, 20), dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        prog = np.asarray(M.forward(params, cfg, {"tokens": toks},
                                    mode="train").logits)
    rows = np.array([(b, t) for b in range(3) for t in range(20)])
    ref = np.asarray(dense.logits_at(spec, SEED, toks, rows))
    np.testing.assert_allclose(ref, prog.reshape(60, -1), atol=2e-4,
                               rtol=2e-4)


@pytest.mark.parametrize("spec", [OLMO_LIKE, NEMOTRON_LIKE],
                         ids=["olmo", "nemotron"])
def test_served_tokens_rank_first(spec):
    """Greedy tokens served through the continuous engine (kernel in
    interpret mode) are the reference's first choices, at float32."""
    from repro.serving.engine import ContinuousServingEngine, ServeRequest
    cfg = R.model_config(spec)
    params = W.program_params(spec, SEED)
    eng = ContinuousServingEngine(cfg, params, slots=3, max_len=40,
                                  macro_steps=4, use_pallas=True)
    rng = np.random.default_rng(1)
    reqs = [ServeRequest(uid=i, prompt=rng.integers(
        0, spec["vocab_size"], 16, dtype=np.int32), max_new=m)
        for i, m in enumerate([12, 5, 9, 3, 16])]
    outs, _ = eng.run(reqs)
    recs = []
    for r, o in zip(reqs, sorted(outs, key=lambda o: o.uid)):
        rec = R.Record(req=R.TR.Request(r.uid, 0.0, r.prompt, r.max_new),
                       tokens=[int(t) for t in o.tokens], done=True)
        recs.append(rec)
    g = R.served_gaps(spec, SEED, recs)
    assert g["n_tokens"] == 45
    assert float(g["served"].max()) <= 1e-5
