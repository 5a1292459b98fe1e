"""The one traffic generator: every seed replays the mix's arrival trace
(the same due times and output lengths) with prompts of its own."""
import json
import os

import numpy as np
import pytest

from bench import traffic as TR

MIX = json.load(open(os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "traffic", "rag-burst.json")))


def _schedule(seed, seconds=40.0, mix=MIX):
    return TR.schedule(mix, seed, seconds, 1000)


def test_same_seed_same_inputs():
    a, b = _schedule(2 ** 31 + 5), _schedule(2 ** 31 + 5)
    assert [(r.due_s, r.max_new) for r in a] == \
        [(r.due_s, r.max_new) for r in b]
    assert all((x.prompt == y.prompt).all() for x, y in zip(a, b))


def test_seeds_share_the_trace_not_the_prompts():
    a, b = _schedule(1), _schedule(2 ** 33 + 1)
    assert [(r.due_s, r.max_new) for r in a] == \
        [(r.due_s, r.max_new) for r in b]
    assert not any((x.prompt == y.prompt).all() for x, y in zip(a, b))
    assert a[0].prompt.shape == (MIX["prompt_len"],)


@pytest.mark.parametrize("process", ["poisson", "gamma"])
def test_rate_and_lengths(process):
    mix = dict(MIX, arrival=dict(MIX["arrival"], process=process))
    n = TR.n_requests(mix, 40.0)
    g = TR.gaps(mix, n)
    assert g.mean() == pytest.approx(1.0 / mix["arrival"]["rate"])
    reqs = _schedule(3, mix=mix)
    assert len(reqs) == n and reqs[0].due_s == 0.0
    assert np.all(np.diff([r.due_s for r in reqs]) > 0)
    lens = [r.max_new for r in reqs]
    o = mix["output"]
    assert o["min"] <= min(lens) and max(lens) <= o["max"]
    assert sorted(lens) == sorted(TR.output_lengths(mix, n))


def test_cache_len_is_the_launchers_sizing():
    assert TR.cache_len(MIX) == MIX["prompt_len"] + MIX["output"]["max"] + 8


def test_p_quantile_nearest_rank():
    v = list(range(1, 11))
    assert TR.p_quantile(v, 90) == 9
    assert TR.p_quantile(v, 50) == 5
    assert TR.p_quantile([3.0], 90) == 3.0
