"""Plain reference of the Brumby family (Brumby-14B-Base, ``model_type:
brumby``): Qwen3's dense decoder with every attention layer a power-
retention layer of degree 2 ("Scaling Context Requires Rethinking
Attention", arXiv:2507.04239; the ``retention`` package's
``power_retention``).

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision,
the ATTENTION form: no state, no cache, no kernels, nothing imported from
``dynamo_tpu``. For block input ``x`` [T, D]::

    h      = rmsnorm(x, w_in)
    q      = rope(rmsnorm_head(reshape(h Wq, [T, H, d])))     theta, halves
    k      = rope(rmsnorm_head(reshape(h Wk, [T, KV, d])))
    v      =               reshape(h Wv, [T, KV, d])
    lg     = log_sigmoid(h Wg + b)              [T, KV], one gate a cached head
    G      = cumsum(lg)
    w[t,j] = exp(G[t,c] - G[j,c]) * (q[t,a] . k[j,c] / sqrt(d))^2    j <= t
    y[t,a] = sum_j w[t,j] v[j,c] / (sum_j w[t,j] + eps)          c = a // (H/KV)
    x = x + concat_a(y) Wo ;  x = x + swiglu(rmsnorm(x, w_post))

What ``config.json`` does not state is the configuration file's
``assumed`` list: the degree 2, the gate (a projection and a bias a cached
head) through log-sigmoid, the normaliser and its ``eps`` 1e-6, the ``1 / sqrt(d)`` inside the power, the
per-head q/k RMSNorm and the rotary embedding kept on q and k. Departures
from the equations above: none; the rows go in blocks (``ROW_BLOCK``) so
that the [rows, T] weights of 40 heads fit beside 8 layers' activations
at 5,120, one sequence at a time under one draw of a layer's weights, and
a sequence goes no further than its last asked row needs (a causal layer:
what lies behind changes nothing).

Weights are taken from the seed and from nothing the program made: the
served path's splits of ``PRNGKey(seed)`` in its order (q, k, v, o, the
gate, then the FFN's gate, up, down), normal / sqrt(fan_in), cast to the
served dtype, one layer at a time, upcast where used; the gate's bias is
no draw: cached head ``c`` at the logit of ``1 - 2 ** -(5 + c % 8)``, a
retention network's multi-scale decay (arXiv:2307.08621), float32. That the draw is the
program's is a test (``tests/chipbench``), not an import.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

F32 = jnp.float32
EPS = 1e-6
#: rows whose [rows, T] weights are formed at once
ROW_BLOCK = 512


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
    served path's order; every norm's scale is ones."""
    s = sizes(cfg)
    D, I, H, KV, hd = s["D"], s["I"], s["H"], s["KV"], s["hd"]
    keys = iter(jax.random.split(key, 16))
    return {
        "wq": _draw(next(keys), (D, H * hd), D, dtype),
        "wk": _draw(next(keys), (D, KV * hd), D, dtype),
        "wv": _draw(next(keys), (D, KV * hd), D, dtype),
        "wo": _draw(next(keys), (H * hd, D), H * hd, dtype),
        "wg": _draw(next(keys), (D, KV), D, dtype),
        "bg": gate_bias(KV),
        "w_gate": _draw(next(keys), (D, I), D, dtype),
        "w_up": _draw(next(keys), (D, I), D, dtype),
        "w_down": _draw(next(keys), (I, D), I, dtype),
    }


def gate_bias(kv_heads: int):
    """The gate's seeded bias [KV], float32: head ``c`` decays by about
    ``1 - 2 ** -(5 + c % 8)`` a token (32 .. 4,096 tokens of memory)."""
    return jnp.log(2.0 ** (5 + jnp.arange(kv_heads) % 8).astype(F32) - 1.0)


def rms_norm(x, eps):
    """RMSNorm under a scale of ones (what seeded weights hold)."""
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def rope(x, positions, theta):
    """Half-rotation RoPE: x [T, heads, hd], positions [T]."""
    hd = x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = positions.astype(F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def power_retention(q, k, v, lg):
    """The attention form for one sequence: q [T, H, d], k, v [T, KV, d],
    lg [T, KV] -> y [T, H, d]. Rows in blocks of ``ROW_BLOCK``; a block
    forms its weights against every key and masks those ahead of it."""
    T, H, d = q.shape
    KV = k.shape[1]
    G = H // KV
    cum = jnp.cumsum(lg, axis=0)                                  # [T, KV]
    pad = -T % ROW_BLOCK
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, ROW_BLOCK, KV, G, d)
    cb = jnp.pad(cum, ((0, pad), (0, 0))).reshape(-1, ROW_BLOCK, KV)
    tb = jnp.arange(T + pad).reshape(-1, ROW_BLOCK)
    j = jnp.arange(T)

    def block(args):
        q_b, c_b, t_b = args
        s = jnp.einsum("tcgd,jcd->cgtj", q_b, k) / (d ** 0.5)
        seen = j[None, :] <= t_b[:, None]                         # [t, j]
        decay = jnp.exp(
            jnp.minimum(c_b.T[:, :, None] - cum.T[:, None, :], 0.0)
        )                                                         # [c, t, j]
        w = jnp.where(seen[None, None], decay[:, None] * s * s, 0.0)
        num = jnp.einsum("cgtj,jcd->tcgd", w, v)
        den = jnp.sum(w, axis=-1).transpose(2, 0, 1)[..., None]
        return num / (den + EPS)

    y = jax.lax.map(block, (qb, cb, tb))
    return y.reshape(-1, H, d)[:T]


def mixer(x, w, s):
    """x [T, D] (normed) -> [T, D]."""
    T = x.shape[0]
    H, KV, hd = s["H"], s["KV"], s["hd"]
    pos = jnp.arange(T)
    q = (x @ w["wq"].astype(F32)).reshape(T, H, hd)
    k = (x @ w["wk"].astype(F32)).reshape(T, KV, hd)
    v = (x @ w["wv"].astype(F32)).reshape(T, KV, hd)
    q = rope(rms_norm(q, s["eps"]), pos, s["theta"])
    k = rope(rms_norm(k, s["eps"]), pos, s["theta"])
    lg = jax.nn.log_sigmoid(x @ w["wg"].astype(F32) + w["bg"])
    y = power_retention(q, k, v, lg)
    return y.reshape(T, H * hd) @ w["wo"].astype(F32)


def swiglu(x, gate, up, down):
    gate, up, down = (a.astype(F32) for a in (gate, up, down))
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


@partial(jax.jit, static_argnames=("cfg_items", "dtype"))
def _weights(key, cfg_items, dtype):
    """One layer's weights, drawn once for every sequence of the sample."""
    return layer_weights(key, dict(cfg_items), jnp.dtype(dtype))


@partial(jax.jit, static_argnames=("cfg_items",))
def _layer(w, x, cfg_items):
    """One layer over one sequence x [T, D]."""
    s = sizes(dict(cfg_items))
    x = x + mixer(rms_norm(x, s["eps"]), w, s)
    return x + swiglu(rms_norm(x, s["eps"]), w["w_gate"], w["w_up"], w["w_down"])


@partial(jax.jit, static_argnames=("cfg_items", "dtype"))
def _table(key, cfg_items, dtype):
    s = sizes(dict(cfg_items))
    return _draw(key, (s["V"], s["D"]), s["V"], jnp.dtype(dtype))


@partial(jax.jit, static_argnames=("cfg_items", "dtype"))
def _head(key, x, cfg_items, dtype):
    s = sizes(dict(cfg_items))
    w = _draw(key, (s["D"], s["V"]), s["D"], jnp.dtype(dtype)).astype(F32)
    return rms_norm(x, s["eps"]) @ w


def _hashable(cfg: dict) -> tuple:
    keep = (
        "hidden_size", "intermediate_size", "num_hidden_layers",
        "num_attention_heads", "num_key_value_heads", "head_dim",
        "vocab_size", "rope_theta", "rms_norm_eps",
    )
    return tuple((k, cfg[k]) for k in keep if cfg.get(k) is not None)


def _reach(rows, full: int) -> int:
    """How many positions a sequence's forward pass takes for ``rows``: a
    power of two of row blocks (few distinct lengths to compile), at most
    the ``full`` padded length."""
    t = ROW_BLOCK
    while t <= int(rows.max()):
        t *= 2
    return min(t, full)


def logits(cfg: dict, seed: int, tokens, rows, dtype: str = "bfloat16"):
    """Float32 logits ``[B, R, V]`` at positions ``rows`` [B, R] of the
    full forward pass over ``tokens`` [B, T] (right-padded: a causal layer
    keeps padding out of every earlier position)."""
    if cfg.get("tie_word_embeddings"):
        raise NotImplementedError("tied embeddings are not in this family")
    if cfg.get("sliding_window") is not None:
        raise NotImplementedError("a retention layer keeps no keys to window")
    items = _hashable(cfg)
    layer_keys, ek, hk = model_keys(seed, cfg["num_hidden_layers"])
    tokens, rows = jnp.asarray(tokens), jnp.asarray(rows)
    with jax.default_matmul_precision("highest"):
        table = _table(ek, items, dtype)
        xs = [
            table[tokens[b, : _reach(rows[b], tokens.shape[1])]].astype(F32)
            for b in range(tokens.shape[0])
        ]
        del table
        # (layer by layer: a layer's weights are drawn once, not a sequence)
        for li in range(cfg["num_hidden_layers"]):
            w = _weights(layer_keys[li], items, dtype)
            xs = [_layer(w, x, items) for x in xs]
        picked = [x[rows[b]] for b, x in enumerate(xs)]
        return _head(hk, jnp.stack(picked), items, dtype)
