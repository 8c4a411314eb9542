"""Plain reference of the Mistral family: Mistral-7B (dense SwiGLU, sliding-
window attention) and Mixtral-8x7B (the same attention, no window, and a
top-2 softmax router over 8 SwiGLU experts).

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision:
full causal attention over the whole sequence, no cache, no kernels, no
batching tricks, nothing imported from ``dynamo_tpu``. It follows the
published descriptions (Jiang et al., "Mistral 7B", 2023; "Mixtral of
Experts", 2024; the HF ``modeling_mistral`` / ``modeling_mixtral`` code):
pre-norm RMSNorm blocks, grouped-query attention with half-rotation RoPE,
``down(silu(gate x) * up x)``; for Mixtral ``softmax`` over all router
logits, the two largest renormalised to sum 1.

Weights are taken from the seed and from nothing the program made. The
served path draws them with ``jax.random`` from ``PRNGKey(seed)`` (normal /
sqrt(fan_in), cast to the served dtype); ``layer_weights`` repeats that
draw — the same splits in the same order — one layer at a time, so a
32-layer model never has to be resident, and upcasts to float32. That the
draw is the program's is a test (``tests/chipbench``), not an import.

A configuration is the ``published`` block of its file (HF key names).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

F32 = jnp.float32


def sizes(cfg: dict) -> dict:
    heads = cfg["num_attention_heads"]
    return {
        "D": cfg["hidden_size"],
        "I": cfg["intermediate_size"],
        "L": cfg["num_hidden_layers"],
        "H": heads,
        "KV": cfg["num_key_value_heads"],
        "hd": cfg.get("head_dim") or cfg["hidden_size"] // heads,
        "V": cfg["vocab_size"],
        "E": cfg.get("num_local_experts") or 0,
        "k": cfg.get("num_experts_per_tok") or 0,
        "window": cfg.get("sliding_window") or 0,
        "theta": float(cfg["rope_theta"]),
        "eps": float(cfg["rms_norm_eps"]),
    }


def _draw(key, shape, fan_in, dtype):
    return (jax.random.normal(key, shape, F32) / (fan_in ** 0.5)).astype(dtype)


def model_keys(seed: int, num_layers: int):
    """(per-layer keys, embedding key, head key) as the served path splits
    ``PRNGKey(seed)``."""
    lk, ek, hk = jax.random.split(jax.random.PRNGKey(seed), 3)
    return jax.random.split(lk, num_layers), ek, hk


def layer_weights(key, cfg: dict, dtype) -> dict:
    """One layer's weights in ``dtype`` ([in, out] layout), drawn in the
    served path's order: q, k, v, o, then router and experts, or the MLP."""
    s = sizes(cfg)
    D, I, H, KV, hd, E = s["D"], s["I"], s["H"], s["KV"], s["hd"], s["E"]
    keys = iter(jax.random.split(key, 16))
    w = {
        "wq": _draw(next(keys), (D, H * hd), D, dtype),
        "wk": _draw(next(keys), (D, KV * hd), D, dtype),
        "wv": _draw(next(keys), (D, KV * hd), D, dtype),
        "wo": _draw(next(keys), (H * hd, D), H * hd, dtype),
        "ln_attn": jnp.ones((D,), dtype),
        "ln_mlp": jnp.ones((D,), dtype),
    }
    if E:
        w["w_router"] = _draw(next(keys), (D, E), D, dtype)
        w["w_gate"] = _draw(next(keys), (E, D, I), D, dtype)
        w["w_up"] = _draw(next(keys), (E, D, I), D, dtype)
        w["w_down"] = _draw(next(keys), (E, I, D), I, dtype)
    else:
        w["w_gate"] = _draw(next(keys), (D, I), D, dtype)
        w["w_up"] = _draw(next(keys), (D, I), D, dtype)
        w["w_down"] = _draw(next(keys), (I, D), I, dtype)
    return w


def embedding(key, cfg: dict, dtype):
    s = sizes(cfg)
    return _draw(key, (s["V"], s["D"]), s["V"], dtype)


def lm_head(key, cfg: dict, dtype):
    s = sizes(cfg)
    return _draw(key, (s["D"], s["V"]), s["D"], dtype)


def rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * w


def rope(x, positions, theta):
    """Half-rotation RoPE: x [..., L, heads, hd], positions [L]."""
    hd = x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=F32) / hd)        # [hd/2]
    ang = positions.astype(F32)[:, None] * inv[None, :]           # [L, hd/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(x, w, s):
    """x [B, L, D] -> [B, L, D]: causal, key j visible to query i when
    j <= i and, under a sliding window W, i - j < W."""
    B, L, _ = x.shape
    H, KV, hd = s["H"], s["KV"], s["hd"]
    pos = jnp.arange(L)
    q = rope((x @ w["wq"].astype(F32)).reshape(B, L, H, hd), pos, s["theta"])
    k = rope((x @ w["wk"].astype(F32)).reshape(B, L, KV, hd), pos, s["theta"])
    v = (x @ w["wv"].astype(F32)).reshape(B, L, KV, hd)
    k = jnp.repeat(k, H // KV, axis=2)
    v = jnp.repeat(v, H // KV, axis=2)
    scores = jnp.einsum("bihd,bjhd->bhij", q, k) / math.sqrt(hd)
    seen = pos[None, :] <= pos[:, None]
    if s["window"]:
        seen &= pos[:, None] - pos[None, :] < s["window"]
    scores = jnp.where(seen[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhij,bjhd->bihd", probs, v).reshape(B, L, H * hd)
    return out @ w["wo"].astype(F32)


def swiglu(x, gate, up, down):
    """Weights arrive in the served dtype and are upcast where used, so
    that only one matrix at a time is resident in float32."""
    gate, up, down = (a.astype(F32) for a in (gate, up, down))
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def experts(x, w, s):
    """Top-k of a softmax over all experts, renormalised; every expert is
    computed for every token and weighted (0 where not chosen)."""
    probs = jax.nn.softmax(x @ w["w_router"].astype(F32), axis=-1)           # [B, L, E]
    top, idx = jax.lax.top_k(probs, s["k"])
    top = top / jnp.sum(top, axis=-1, keepdims=True)
    gates = jnp.sum(
        jax.nn.one_hot(idx, s["E"], dtype=F32) * top[..., None], axis=-2
    )                                                             # [B, L, E]
    out = jnp.zeros_like(x)
    for e in range(s["E"]):
        y = swiglu(x, w["w_gate"][e], w["w_up"][e], w["w_down"][e])
        out = out + gates[..., e : e + 1] * y
    return out


@partial(jax.jit, static_argnames=("cfg_items", "dtype"))
def _layer(key, x, cfg_items, dtype):
    cfg = dict(cfg_items)
    s = sizes(cfg)
    w = layer_weights(key, cfg, jnp.dtype(dtype))
    x = x + attention(rms_norm(x, w["ln_attn"].astype(F32), s["eps"]), w, s)
    h = rms_norm(x, w["ln_mlp"].astype(F32), s["eps"])
    if s["E"]:
        return x + experts(h, w, s)
    return x + swiglu(h, w["w_gate"], w["w_up"], w["w_down"])


@partial(jax.jit, static_argnames=("cfg_items", "dtype"))
def _embed(key, tokens, cfg_items, dtype):
    table = embedding(key, dict(cfg_items), jnp.dtype(dtype))
    return table[tokens].astype(F32)


@partial(jax.jit, static_argnames=("cfg_items", "dtype"))
def _head(key, x, cfg_items, dtype):
    cfg = dict(cfg_items)
    w = lm_head(key, cfg, jnp.dtype(dtype)).astype(F32)
    return rms_norm(x, jnp.ones((x.shape[-1],), F32), sizes(cfg)["eps"]) @ w


def _hashable(cfg: dict) -> tuple:
    keep = (
        "hidden_size", "intermediate_size", "num_hidden_layers",
        "num_attention_heads", "num_key_value_heads", "head_dim",
        "vocab_size", "num_local_experts", "num_experts_per_tok",
        "sliding_window", "rope_theta", "rms_norm_eps",
    )
    return tuple((k, cfg[k]) for k in keep if cfg.get(k) is not None)


def logits(cfg: dict, seed: int, tokens, rows, dtype: str = "bfloat16"):
    """Float32 logits ``[B, R, V]`` at positions ``rows`` [B, R] of the
    full forward pass over ``tokens`` [B, L] (right-padded: causal
    attention keeps padding out of every earlier position)."""
    if cfg.get("tie_word_embeddings"):
        raise NotImplementedError("tied embeddings are not in this family")
    items = _hashable(cfg)
    layer_keys, ek, hk = model_keys(seed, cfg["num_hidden_layers"])
    with jax.default_matmul_precision("highest"):
        x = _embed(ek, jnp.asarray(tokens), items, dtype)
        for li in range(cfg["num_hidden_layers"]):
            x = _layer(layer_keys[li], x, items, dtype)
        picked = jnp.take_along_axis(
            x, jnp.asarray(rows)[:, :, None], axis=1
        )
        return _head(hk, picked, items, dtype)
