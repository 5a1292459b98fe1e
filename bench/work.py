"""Operations and bytes that the serving steps need, from shapes alone.

Counts follow the algorithm, never what today's code happens to read or
compute: a decode token at live length ``n`` (the keys it attends over,
itself included) needs its projections, its attention over ``n`` keys and
the vocabulary head; a prefill of ``P`` tokens needs the projections of
every token, causal attention over the lower triangle and the head of the
last position only.  Free and frozen slots are no work.  Matrix products
count 2 operations per multiply-add; norms, rotary and softmax are left
out, as is usual for model FLOP utilisation.
"""
from __future__ import annotations

from typing import Dict, Iterable

import numpy as np

ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4, "int8": 1}


def layer_params(cfg: dict) -> int:
    """Weights of one decoder layer's projections."""
    D, H, Hkv, dh, F = (cfg["d_model"], cfg["num_heads"],
                        cfg["num_kv_heads"], cfg["head_dim"], cfg["d_ff"])
    attn = D * H * dh * 2 + D * Hkv * dh * 2
    mlp = (3 if cfg["mlp_type"] == "swiglu" else 2) * D * F
    return attn + mlp


def head_params(cfg: dict) -> int:
    return cfg["vocab_size"] * cfg["d_model"]


def attn_flops_per_key(cfg: dict) -> int:
    """Q·K and P·V of one query against one key, over all layers."""
    return 4 * cfg["num_layers"] * cfg["num_heads"] * cfg["head_dim"]


def prefill_flops(cfg: dict, P: int) -> float:
    """One prompt of ``P`` tokens: projections of every token, causal
    attention (query i sees keys 0..i) and the last position's head."""
    proj = 2.0 * cfg["num_layers"] * layer_params(cfg) * P
    attn = attn_flops_per_key(cfg) * P * (P + 1) / 2.0
    return proj + attn + 2.0 * head_params(cfg)


def decode_token_flops(cfg: dict, n_keys) -> np.ndarray:
    """One decoded token at live length ``n_keys`` (array or scalar)."""
    n = np.asarray(n_keys, np.float64)
    proj = 2.0 * (cfg["num_layers"] * layer_params(cfg) + head_params(cfg))
    return proj + attn_flops_per_key(cfg) * n


def decode_attn_work(cfg: dict, n_keys) -> Dict[str, np.ndarray]:
    """The decode-attention kernel's work for one token at live length
    ``n_keys``, over all layers: operations, and the bytes of the live K/V
    rows plus the query read and the output written.  Independent of the
    cache's allocated length."""
    n = np.asarray(n_keys, np.float64)
    L, H, Hkv, dh = (cfg["num_layers"], cfg["num_heads"],
                     cfg["num_kv_heads"], cfg["head_dim"])
    it = ITEMSIZE[cfg["dtype"]]
    kv = 2.0 * n * Hkv * dh * it
    qo = 2.0 * H * dh * it
    return {"flops": attn_flops_per_key(cfg) * n, "bytes": L * (kv + qo)}


def least_time_s(flops: float, nbytes: float, peak: dict) -> float:
    """Roofline bound: the larger of compute time and memory time."""
    return max(flops / peak["flops_per_s"], nbytes / peak["bytes_per_s"])


def decode_totals(cfg: dict, tokens: Iterable, peak: dict) -> dict:
    """Sums over decode-step tokens given as (prompt length P, output
    index j >= 1; output 0 comes from the prefill, output j from the step
    whose query sits at position P + j - 1 and sees P + j keys): model
    operations, and the attention kernel's operations, bytes and least
    time (each token's own roofline bound, summed)."""
    flops = attn_flops = attn_bytes = least = 0.0
    for P, j in tokens:
        n = P + j
        flops += float(decode_token_flops(cfg, n))
        w = decode_attn_work(cfg, n)
        attn_flops += float(w["flops"])
        attn_bytes += float(w["bytes"])
        least += least_time_s(float(w["flops"]), float(w["bytes"]), peak)
    return {"flops": flops, "attn_flops": attn_flops,
            "attn_bytes": attn_bytes, "attn_least_s": least}
