"""Operations and bytes that the serving steps need, from shapes alone.

Counts follow the algorithm, never what today's code happens to read or
compute: a decode token at live length ``n`` (the keys it attends over,
itself included) needs its projections, its attention over ``n`` keys and
the vocabulary head; a prefill of ``P`` tokens needs the projections of
every token, causal attention over the lower triangle and the head of the
last position only.  Free and frozen slots are no work.  Matrix products
count 2 operations per multiply-add; norms, rotary and softmax are left
out, as is usual for model FLOP utilisation.

The formulas belong to the configuration's family: each count below is
the family module's (``bench/reference/<family>.py``, the function of the
same name).  Where the module gives none, the count raises
:class:`Uncounted`, and the harness leaves out the metric whose reader
asked for it; it never borrows another family's formula.
"""
from __future__ import annotations

from typing import Dict, Iterable

import numpy as np

ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4, "int8": 1}


class Uncounted(LookupError):
    """The configuration's family module gives no such count."""


def _count(cfg: dict, name: str):
    from bench import reference
    fn = getattr(reference.family(cfg), name, None)
    if fn is None:
        raise Uncounted(f"family {cfg['family']!r} gives no {name}")
    return fn


def layer_params(cfg: dict) -> int:
    """Weights of one decoder layer's projections."""
    return _count(cfg, "layer_params")(cfg)


def head_params(cfg: dict) -> int:
    return _count(cfg, "head_params")(cfg)


def prefill_flops(cfg: dict, P: int) -> float:
    """One prompt of ``P`` tokens, the last position's head included."""
    return _count(cfg, "prefill_flops")(cfg, P)


def decode_token_flops(cfg: dict, n_keys) -> np.ndarray:
    """One decoded token at live length ``n_keys`` (array or scalar)."""
    return _count(cfg, "decode_token_flops")(cfg, n_keys)


def decode_attn_work(cfg: dict, n_keys) -> Dict[str, np.ndarray]:
    """The decode-attention kernel's work for one token at live length
    ``n_keys``, over all layers: ``flops`` and ``bytes``.  Independent of
    the cache's allocated length."""
    return _count(cfg, "decode_attn_work")(cfg, n_keys)


def least_time_s(flops: float, nbytes: float, peak: dict) -> float:
    """Roofline bound: the larger of compute time and memory time."""
    return max(flops / peak["flops_per_s"], nbytes / peak["bytes_per_s"])


def decode_totals(cfg: dict, tokens: Iterable, peak: dict) -> dict:
    """Sums over decode-step tokens given as (prompt length P, output
    index j >= 1; output 0 comes from the prefill, output j from the step
    whose query sits at position P + j - 1 and sees P + j keys): model
    operations, and the attention kernel's operations, bytes and least
    time (each token's own roofline bound, summed)."""
    token_flops = _count(cfg, "decode_token_flops")
    attn_work = _count(cfg, "decode_attn_work")
    flops = attn_flops = attn_bytes = least = 0.0
    for P, j in tokens:
        n = P + j
        flops += float(token_flops(cfg, n))
        w = attn_work(cfg, n)
        attn_flops += float(w["flops"])
        attn_bytes += float(w["bytes"])
        least += least_time_s(float(w["flops"]), float(w["bytes"]), peak)
    return {"flops": flops, "attn_flops": attn_flops,
            "attn_bytes": attn_bytes, "attn_least_s": least}
