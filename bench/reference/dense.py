"""The dense decoder family: its parameter leaves, its plain float32
forward pass, and the work its serving steps need.

This is the family module of every configuration whose file says
``"family": "dense"`` (the contract is ``bench/reference/__init__.py``):

* ``leaves`` and ``empty_subtrees``: the program's parameter tree, one
  stack ``blocks`` of ``num_layers`` layers beside the embedding, the
  head and the final norm; ``bench/weights.py`` hashes their elements.
* ``logits_at``: straight ``jax.numpy``: token embedding, then per layer
  a pre-norm causal self-attention with rotary positions (MHA or GQA) and
  a pre-norm MLP, each added to the residual stream, then the final norm
  and the vocabulary head.  No cache, no kernel, no batching tricks;
  every product runs under ``default_matmul_precision("highest")``.  It
  imports nothing of the program: its weights are made again here, layer
  by layer, from the seed, so it shares no array with the run it checks.
* the work counts read through ``bench/work.py``: matrix products of the
  projections, causal attention and the head, from shapes alone.

Family members, as their papers and the program's configurations state:

* OLMo-1B (arXiv:2402.00838): LayerNorm without scale or bias, SwiGLU,
  tied input and output embeddings.
* Nemotron-4 15B (arXiv:2402.16819): LayerNorm with scale and bias,
  squared-ReLU MLP, untied head, GQA with 8 KV heads.

Both rotate each head's two halves (GPT-NeoX layout) with base
``rope_theta``; neither has biases in its projections.

``quant`` computes the same pass in a lower precision, for the control of
the correctness check: ``"int8"`` rounds every projection's weights (per
output channel) and its input activations (per token) to int8 absmax
grids; ``"fp8"`` does the same on float8_e4m3 grids; ``"bf16"`` rounds
both to bfloat16 (the step below a float32 configuration).
"""
from __future__ import annotations

import functools
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W
from bench.work import ITEMSIZE

EPS = 1e-5
STACK = "blocks"


# --------------------------------------------------------------------------
# the program's parameter tree
# --------------------------------------------------------------------------
def leaves(cfg: dict) -> List[W.Leaf]:
    """The dense family's parameter leaves, as the program lays them out."""
    D, H, Hkv, dh = cfg["d_model"], cfg["num_heads"], cfg["num_kv_heads"], \
        cfg["head_dim"]
    F, V, L = cfg["d_ff"], cfg["vocab_size"], cfg["num_layers"]
    dt = cfg["dtype"]

    def layer(path, shape, mean, std, dtype=dt):
        return W.Leaf(f"{STACK}/{path}", shape, mean, std, dtype, STACK, L)
    out = [W.Leaf("embed/table", (V, D), 0.0, 0.02, dt)]
    if not cfg["tie_embeddings"]:
        out.append(W.Leaf("lm_head/table", (V, D), 0.0, 0.02, dt))
    if cfg["norm_type"] == "layernorm":
        norm = (("scale", 1.0), ("bias", 0.0))
        out += [W.Leaf(f"final_norm/{p}", (D,), m, 0.1, "float32")
                for p, m in norm]
        out += [layer(f"{n}/{p}", (D,), m, 0.1, "float32")
                for n in ("ln1", "ln2") for p, m in norm]
    elif cfg["norm_type"] != "nonparametric":
        raise ValueError(f"norm_type {cfg['norm_type']!r} is not dense-LN")
    out += [
        layer("attn/wq", (D, H, dh), 0.0, D ** -0.5),
        layer("attn/wk", (D, Hkv, dh), 0.0, D ** -0.5),
        layer("attn/wv", (D, Hkv, dh), 0.0, D ** -0.5),
        layer("attn/wo", (H, dh, D), 0.0, (H * dh) ** -0.5),
    ]
    if cfg["mlp_type"] == "swiglu":
        out.append(layer("mlp/w_gate", (D, F), 0.0, D ** -0.5))
    elif cfg["mlp_type"] != "squared_relu":
        raise ValueError(f"mlp_type {cfg['mlp_type']!r} not supported")
    out += [
        layer("mlp/w_up", (D, F), 0.0, D ** -0.5),
        layer("mlp/w_down", (F, D), 0.0, F ** -0.5),
    ]
    return out


def empty_subtrees(cfg: dict) -> List[str]:
    """The norms' subtrees, which stay empty for a norm without scale or
    bias."""
    return ["final_norm", f"{STACK}/ln1", f"{STACK}/ln2"]


# --------------------------------------------------------------------------
# the plain reference
# --------------------------------------------------------------------------
def _fake_quant(x, axes, quant: Optional[str]):
    """Round ``x`` to a quantised grid scaled by its absmax over ``axes``."""
    if quant is None:
        return x
    if quant == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    amax = jnp.max(jnp.abs(x), axis=axes, keepdims=True)
    if quant == "int8":
        s = jnp.maximum(amax, 1e-30) / 127.0
        return jnp.clip(jnp.round(x / s), -127, 127) * s
    if quant == "fp8":
        s = jnp.maximum(amax, 1e-30) / 448.0
        return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    raise ValueError(f"unknown quant {quant!r}")


def _proj(x, w, spec: str, w_axes, quant):
    """x @ w with the activation quantised per token, the weight per
    output channel (``w_axes`` are its contraction axes)."""
    xq = _fake_quant(x, (-1,), quant)
    wq = _fake_quant(w, w_axes, quant)
    return jnp.einsum(spec, xq, wq)


def _norm(cfg, x, p):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    y = (x - mu) / jnp.sqrt(var + EPS)
    if cfg["norm_type"] == "layernorm":
        y = y * p["scale"] + p["bias"]
    return y


def _rope(x, theta: float):
    """x [B,T,H,dh]; positions 0..T-1; the two halves of a head rotate."""
    T, dh = x.shape[1], x.shape[-1]
    inv = theta ** (-np.arange(0, dh, 2, dtype=np.float64) / dh)
    ang = jnp.asarray(np.arange(T)[:, None] * inv[None, :], jnp.float32)
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    a, b = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _attention(q, k, v):
    """Causal softmax attention of one sequence: q [T,H,dh], k/v [T,Hkv,dh]."""
    T, H, dh = q.shape
    rep = H // k.shape[1]
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(dh)
    causal = np.tril(np.ones((T, T), bool))
    s = jnp.where(causal[None], s, -jnp.inf)
    return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)


@functools.partial(jax.jit, static_argnames=("cfg_items", "quant"))
def _layer(x, w, *, cfg_items, quant):
    cfg = dict(cfg_items)
    with jax.default_matmul_precision("highest"):
        w = jax.tree.map(lambda a: a.astype(jnp.float32), w)
        a = w["attn"]
        h = _norm(cfg, x, w.get("ln1"))
        q = _rope(_proj(h, a["wq"], "btd,dhk->bthk", (0,), quant),
                  cfg["rope_theta"])
        k = _rope(_proj(h, a["wk"], "btd,dhk->bthk", (0,), quant),
                  cfg["rope_theta"])
        v = _proj(h, a["wv"], "btd,dhk->bthk", (0,), quant)
        o = jax.lax.map(lambda qkv: _attention(*qkv), (q, k, v))
        B, T, H, dh = o.shape
        x = x + _proj(o.reshape(B, T, H * dh),
                      a["wo"].reshape(H * dh, -1), "btf,fd->btd", (0,),
                      quant)
        m = w["mlp"]
        h = _norm(cfg, x, w.get("ln2"))
        up = _proj(h, m["w_up"], "btd,df->btf", (0,), quant)
        if cfg["mlp_type"] == "swiglu":
            gate = _proj(h, m["w_gate"], "btd,df->btf", (0,), quant)
            act = jax.nn.silu(gate) * up
        else:
            act = jnp.square(jax.nn.relu(up))
        return x + _proj(act, m["w_down"], "btf,fd->btd", (0,), quant)


@functools.partial(jax.jit, static_argnames=("cfg_items", "quant"))
def _head(x, table, final, *, cfg_items, quant):
    """Logits [M, V] over vocabulary chunks, so that no float32 copy of
    the whole table is ever held."""
    cfg = dict(cfg_items)
    V, D = table.shape
    chunk = max(c for c in range(1, 32769) if V % c == 0)
    with jax.default_matmul_precision("highest"):
        h = _norm(cfg, x, final)

        def part(t):
            return _proj(h, t.astype(jnp.float32), "md,vd->mv", (1,), quant)
        out = jax.lax.map(part, table.reshape(V // chunk, chunk, D))
    return jnp.moveaxis(out, 0, 1).reshape(x.shape[0], V)


# bytes of float32 activations one block of sequences may take in a layer
_BLOCK_BYTES = 3e9


def logits_at(cfg: dict, seed: int, tokens: np.ndarray, rows: np.ndarray,
              quant: Optional[str] = None) -> jax.Array:
    """Reference logits [len(rows), V] (float32).

    ``tokens`` [B, T] holds whole sequences (each right-padded to T; the
    causal mask keeps padding out of every earlier position), ``rows``
    [M, 2] the (sequence, position) pairs whose next-token logits are
    wanted.  Weights are made one layer at a time from ``seed``, and the
    sequences run through each layer in blocks that fit beside them."""
    items = tuple(sorted((k, v) for k, v in cfg.items()
                         if isinstance(v, (int, float, str, bool))))
    B, T = tokens.shape
    per_seq = 3 * 4 * T * max(cfg["d_ff"], cfg["num_heads"] * T)
    blk = int(max(1, min(B, _BLOCK_BYTES // per_seq)))
    # equal blocks (the last row repeated) keep one compiled layer program
    tokens = np.concatenate([tokens, np.repeat(tokens[-1:], -B % blk, 0)])
    embed = W.global_weight(cfg, seed, "embed/table")
    xs = [jnp.take(embed, jnp.asarray(tokens[i:i + blk]), axis=0
                   ).astype(jnp.float32) for i in range(0, B, blk)]
    if not cfg["tie_embeddings"]:
        del embed
    for layer in range(cfg["num_layers"]):
        w = W.layer_weights(cfg, seed, layer, stack=STACK)
        xs = [_layer(x, w, cfg_items=items, quant=quant) for x in xs]
        del w
    x = jnp.concatenate(xs, axis=0)
    del xs
    rows = np.asarray(rows)
    x = x[rows[:, 0], rows[:, 1]]
    final = None
    if cfg["norm_type"] == "layernorm":
        final = {"scale": W.global_weight(cfg, seed, "final_norm/scale"),
                 "bias": W.global_weight(cfg, seed, "final_norm/bias")}
    table = embed if cfg["tie_embeddings"] \
        else W.global_weight(cfg, seed, "lm_head/table")
    return _head(x, table, final, cfg_items=items, quant=quant)


# --------------------------------------------------------------------------
# work counts (bench/work.py): matrix products at 2 operations per
# multiply-add; norms, rotary and softmax left out
# --------------------------------------------------------------------------
def layer_params(cfg: dict) -> int:
    """Weights of one decoder layer's projections."""
    D, H, Hkv, dh, F = (cfg["d_model"], cfg["num_heads"],
                        cfg["num_kv_heads"], cfg["head_dim"], cfg["d_ff"])
    attn = D * H * dh * 2 + D * Hkv * dh * 2
    mlp = (3 if cfg["mlp_type"] == "swiglu" else 2) * D * F
    return attn + mlp


def head_params(cfg: dict) -> int:
    return cfg["vocab_size"] * cfg["d_model"]


def _attn_flops_per_key(cfg: dict) -> int:
    """Q·K and P·V of one query against one key, over all layers."""
    return 4 * cfg["num_layers"] * cfg["num_heads"] * cfg["head_dim"]


def prefill_flops(cfg: dict, P: int) -> float:
    """One prompt of ``P`` tokens: projections of every token, causal
    attention (query i sees keys 0..i) and the last position's head."""
    proj = 2.0 * cfg["num_layers"] * layer_params(cfg) * P
    attn = _attn_flops_per_key(cfg) * P * (P + 1) / 2.0
    return proj + attn + 2.0 * head_params(cfg)


def decode_token_flops(cfg: dict, n_keys) -> np.ndarray:
    """One decoded token at live length ``n_keys`` (array or scalar)."""
    n = np.asarray(n_keys, np.float64)
    proj = 2.0 * (cfg["num_layers"] * layer_params(cfg) + head_params(cfg))
    return proj + _attn_flops_per_key(cfg) * n


def decode_attn_work(cfg: dict, n_keys) -> dict:
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
    return {"flops": _attn_flops_per_key(cfg) * n, "bytes": L * (kv + qo)}
