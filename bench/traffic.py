"""The one traffic generator: a mix file's parameters and a seed in, a
request schedule out.

A mix (``bench/traffic/<name>.json``) gives:

* ``arrival``: ``{"process": "poisson", "rate": r}`` or
  ``{"process": "gamma", "rate": r, "cv": c}`` (renewal process with
  gamma gaps of coefficient of variation ``c``; bursty for ``c > 1``).
* ``prompt_len``: the length of every prompt (uniform random tokens).
* ``output``: ``{"median": m, "sigma": s, "min": a, "max": b}``, a
  lognormal of output lengths clipped to ``[a, b]``.  Prompts hold no
  end-of-sequence token, so each request produces exactly its length.
* ``serving``: the deployment knobs the mix fixes (topology, slots,
  split, macro steps, queue depth), read by ``bench/run.py``.

Output lengths and gaps between arrivals are drawn at fixed quantiles of
their distributions and put in an order that is fixed too: a mix and a
window length give one arrival trace, the same for every seed, as a
recorded trace would be replayed.  The seed draws the prompts' tokens (and
``bench/run.py`` the weights).  Under bursty arrivals a frontend that
serves whole waves turns the order of the bursts into seconds of queueing,
so an order drawn from the seed would make the tail latency of a run a
reading of its seed more than of the system.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import List

import numpy as np
from scipy import stats


# the stream that orders every mix's lengths and gaps (not the run's seed)
TRACE_ORDER = 20240212


@dataclass
class Request:
    index: int
    due_s: float          # offset from the window's start
    prompt: np.ndarray    # [prompt_len] int32
    max_new: int


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def output_lengths(mix: dict, n: int) -> np.ndarray:
    o = mix["output"]
    q = stats.lognorm.ppf(_quantiles(n), o["sigma"], scale=o["median"])
    return np.clip(np.rint(q), o["min"], o["max"]).astype(np.int64)


def gaps(mix: dict, n: int) -> np.ndarray:
    """``n`` gaps between arrivals whose mean is exactly 1 / rate."""
    a = mix["arrival"]
    q = _quantiles(n)
    if a["process"] == "poisson":
        g = stats.expon.ppf(q)
    elif a["process"] == "gamma":
        k = 1.0 / a["cv"] ** 2
        g = stats.gamma.ppf(q, k)
    else:
        raise ValueError(f"no gaps for arrival process {a['process']!r}")
    return g * (n / a["rate"]) / g.sum()


def n_requests(mix: dict, seconds: float) -> int:
    return max(1, int(round(mix["arrival"]["rate"] * seconds)))


def schedule(mix: dict, seed: int, seconds: float, vocab: int
             ) -> List[Request]:
    """The run's requests in the order they are due: the mix's arrival
    trace, with prompts drawn from ``seed``."""
    order = np.random.default_rng(np.random.SeedSequence(TRACE_ORDER))
    n = n_requests(mix, seconds)
    lens = order.permutation(output_lengths(mix, n))
    g = order.permutation(gaps(mix, n))
    rng = np.random.default_rng(np.random.SeedSequence(int(seed)))
    # the first request is due at the window's start
    due = np.concatenate([[0.0], np.cumsum(g)[:-1]])
    P = int(mix["prompt_len"])
    prompts = rng.integers(0, vocab, (n, P), dtype=np.int32)
    return [Request(i, float(due[i]), prompts[i], int(lens[i]))
            for i in range(n)]


def max_output(mix: dict) -> int:
    return int(mix["output"]["max"])


def cache_len(mix: dict) -> int:
    """Rows per slot: prompt + output cap + 8, the launcher's sizing."""
    return int(mix["prompt_len"]) + max_output(mix) + 8


def describe(mix: dict, seconds: float) -> str:
    n = n_requests(mix, seconds)
    lens = output_lengths(mix, n)
    return (f"{n} requests, prompt {mix['prompt_len']}, output mean "
            f"{lens.mean():.1f} max {lens.max()}, "
            f"{int(lens.sum())} output tokens")


def p_quantile(values, q: float) -> float:
    """The q-th percentile (0-100) by nearest rank on the sorted values:
    the smallest value with at least q% of the sample at or below it."""
    v = np.sort(np.asarray(values, np.float64))
    if not len(v):
        return math.nan
    k = max(0, int(math.ceil(q / 100.0 * len(v))) - 1)
    return float(v[k])
