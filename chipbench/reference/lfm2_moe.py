"""Plain reference of the LFM2 mixture-of-experts family (``model_type:
lfm2_moe``; LFM2-24B-A2B), as the family's public modelling code
(``Lfm2Moe*`` in ``transformers``) has it. Every layer is a mixer and a
feed-forward part, each behind an RMSNorm (``eps = norm_eps``, no bias
anywhere): ``x += Mixer(norm_op(x))``; ``x += FFN(norm_ffn(x))``; behind
the last layer ``norm_emb`` and the head, which is the embedding's
transpose.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision:
one full forward pass over the whole sequence, no cache, no state table,
no kernels, no grouping of experts, nothing imported from ``dynamo_tpu``.
The model is computed a layer at a time, each layer's weights drawn inside
its own program and dropped behind it, an expert at a time, so that the
published widths fit on the chip the served program has just given back.

**A ``conv`` layer's mixer** (``layer_types[i] == "conv"``): ``[B | C | u]
= h W_in`` (three parts of ``hidden_size``, in that order); ``a_t = B_t *
u_t``; ``v_t = sum_{j=0..K-1} w_j * a_{t-(K-1)+j}`` a channel (``K =
conv_L_cache``; depthwise, causal, rows before the sequence zero, no bias,
no activation), written here as a plain sum over ``K`` shifted copies;
``y_t = C_t * v_t``; out ``y W_out``.

**A ``full_attention`` layer's**: ``num_attention_heads`` query heads over
``num_key_value_heads`` cached heads of ``head_dim`` (``hidden_size /
num_attention_heads`` where the config has no key); an RMSNorm over each q
head and each k head before the rotation; rotary embedding over the whole
head, halves rotated, at ``rope_parameters.rope_theta``; causal softmax at
``head_dim ** -0.5``; ``out_proj``.

**The FFN**: layers below ``num_dense_layers`` a SwiGLU MLP of
``intermediate_size``, ``W2 (silu(W1 h) * W3 h)``. From there on ``s =
sigmoid(h W_g)`` over ``num_experts``; the ``num_experts_per_tok`` largest
of ``s + b`` (``use_expert_bias``); weights ``s`` at those, over their sum
+ 1e-6 (``norm_topk_prob``), times ``routed_scaling_factor``; ``sum_e w_e
W2_e (silu(W1_e h) * W3_e h)``. No shared expert.

Departures and assumptions, each in the configuration file's ``assumed``:
``head_dim`` 64 = 2,048 / 32 (the config has no key); the embedding tied
(no key either; the family's released configs tie it); the router's bias
``b`` is a trained buffer, DRAWN here as the program draws it (0.1 x
normal); the 1e-6 of the normalisation is the modelling code's constant;
norm weights ones. Every expert
is computed for every token and weighted 0 where not chosen (the same sum,
another order).

Weights are taken from the seed and from nothing the program made, drawn
in the served path's order of splits (``layer_weights``).

**The controls that set the check's limits** (``logits(..., lowered=...)``,
read by ``python3 -m chipbench.control_lowered``; never by a run of the
benchmark): this same pass with a fault the check has to refuse, its
logits standing in the program's place. ``"int8_weights"``: every matrix a
product reads (the projections, the MLPs, the experts, the head; not the
router, the taps, the bias or the embedding's lookup, as weight-only int8
serving leaves them) rounded to 8 bits under one scale an output channel,
``amax / 127``. ``"dropped_tail"``: the convolution reads zeros where a
slot's tail should be: a row's look-back stops at the start of its own
span, the spans being those whose last positions ``rows`` names (the
dispatches of ``steps/span.py`` ``plan_steps``), as a state table that
lost the tail between two dispatches would compute it.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
#: what ``logits`` can compute that the check must refuse
LOWERED = ("int8_weights", "dropped_tail")
#: the matrices of a layer that ``"int8_weights"`` rounds
INT8_ROUNDED = ("w_in", "w_out", "wq", "wk", "wv", "wo", "w1", "w3", "w2")
#: the deviation the router's selection bias is drawn at
EXPERT_BIAS_STD = 0.1
#: the modelling code's constant under the chosen experts' sum
NORM_TOPK_EPS = 1e-6


def sizes(cfg: dict) -> dict:
    heads = cfg["num_attention_heads"]
    return {
        "D": cfg["hidden_size"],
        "I": cfg["intermediate_size"],
        "Im": cfg["moe_intermediate_size"],
        "L": cfg["num_hidden_layers"],
        "types": tuple(cfg["layer_types"]),
        "dense": cfg.get("num_dense_layers", 0),
        "heads": heads,
        "kv_heads": cfg["num_key_value_heads"],
        "hd": cfg.get("head_dim") or cfg["hidden_size"] // heads,
        "V": cfg["vocab_size"],
        "E": cfg["num_experts"],
        "k": cfg["num_experts_per_tok"],
        "K": cfg["conv_L_cache"],
        "scale": float(cfg.get("routed_scaling_factor", 1.0)),
        "norm_topk": bool(cfg.get("norm_topk_prob", True)),
        "bias": bool(cfg.get("use_expert_bias", True)),
        "theta": float(cfg["rope_parameters"]["rope_theta"]),
        "eps": float(cfg["norm_eps"]),
    }


def _draw(key, shape, fan_in, dtype):
    return (jax.random.normal(key, shape, F32) / (fan_in ** 0.5)).astype(dtype)


def _int8_rounded(w):
    """``w`` [.., in, out] as 8 bits under one scale an output channel."""
    w = w.astype(F32)
    scale = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / 127.0
    return jnp.round(w / scale) * scale


def model_keys(seed: int, num_layers: int):
    """(per-layer keys, embedding key) as the served path splits
    ``PRNGKey(seed)`` (the third key is an untied head's: unused)."""
    lk, ek, _ = jax.random.split(jax.random.PRNGKey(seed), 3)
    return jax.random.split(lk, num_layers), ek


def layer_weights(key, s: dict, kind: str, moe: bool, dtype) -> dict:
    """A layer's weights in ``dtype`` ([in, out] layout), drawn in the
    served path's order from the key split 16 ways: the mixer (``conv``:
    the in-projection, the taps, the out-projection; attention: q, k, v,
    o), then the feed-forward part (the router, its bias, the experts'
    W1, W3, W2; or the dense MLP's three). Norm weights are ones: no key."""
    D = s["D"]
    keys = iter(jax.random.split(key, 16))
    w = {}
    if kind == "conv":
        w["w_in"] = _draw(next(keys), (D, 3 * D), D, dtype)
        w["taps"] = _draw(next(keys), (s["K"], D), s["K"], dtype)
        w["w_out"] = _draw(next(keys), (D, D), D, dtype)
    else:
        C, kvC = s["heads"] * s["hd"], s["kv_heads"] * s["hd"]
        w["wq"] = _draw(next(keys), (D, C), D, dtype)
        w["wk"] = _draw(next(keys), (D, kvC), D, dtype)
        w["wv"] = _draw(next(keys), (D, kvC), D, dtype)
        w["wo"] = _draw(next(keys), (C, D), C, dtype)
    if moe:
        E, Im = s["E"], s["Im"]
        w["w_router"] = _draw(next(keys), (D, E), D, dtype)
        w["router_bias"] = (
            EXPERT_BIAS_STD * jax.random.normal(next(keys), (E,), F32)
            if s["bias"] else jnp.zeros((E,), F32))
        w["w1"] = _draw(next(keys), (E, D, Im), D, dtype)
        w["w3"] = _draw(next(keys), (E, D, Im), D, dtype)
        w["w2"] = _draw(next(keys), (E, Im, D), Im, dtype)
    else:
        w["w1"] = _draw(next(keys), (D, s["I"]), D, dtype)
        w["w3"] = _draw(next(keys), (D, s["I"]), D, dtype)
        w["w2"] = _draw(next(keys), (s["I"], D), s["I"], dtype)
    if s.get("lowered") == "int8_weights":
        w.update({k: _int8_rounded(w[k]) for k in INT8_ROUNDED if k in w})
    return w


def rms_norm(x, eps):
    """RMSNorm with a weight of ones (what a seeded model holds)."""
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def short_conv(a, taps, span=None):
    """a [B, L, D], taps [K, D]: ``v_t = sum_j taps[j] a_{t-(K-1)+j}``, a
    plain sum over K shifted copies, zeros before the sequence. ``span``
    [B, L] (the ``"dropped_tail"`` control): the span each position lies
    in; a row reads zeros for a row of an earlier span."""
    K, L = taps.shape[0], a.shape[1]
    v = taps[K - 1] * a
    for back in range(1, K):
        shifted = jnp.pad(a, ((0, 0), (back, 0), (0, 0)))[:, :L]
        if span is not None:
            before = jnp.pad(
                span, ((0, 0), (back, 0)), constant_values=-1)[:, :L]
            shifted = jnp.where((before == span)[..., None], shifted, 0.0)
        v = v + taps[K - 1 - back] * shifted
    return v


def conv_mixer(h, w, span=None):
    """h [B, L, D] (normed) -> [B, L, D]."""
    B, C, u = jnp.split(h @ w["w_in"].astype(F32), 3, axis=-1)
    return (C * short_conv(B * u, w["taps"].astype(F32), span)) @ w[
        "w_out"].astype(F32)


def rotate(x, pos, theta):
    """Rotary embedding over the whole head, halves rotated: x [B, L, H,
    d]."""
    d = x.shape[-1]
    freq = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    angle = pos.astype(F32)[:, None] * freq[None, :]           # [L, d/2]
    cos = jnp.concatenate([jnp.cos(angle)] * 2, axis=-1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(angle)] * 2, axis=-1)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def attention_mixer(h, w, s):
    """h [B, L, D] (normed) -> [B, L, D]: causal softmax attention, a
    query head reading cached head ``i // (heads / kv_heads)``."""
    Bn, L, _ = h.shape
    nh, kvh, hd = s["heads"], s["kv_heads"], s["hd"]
    pos = jnp.arange(L)
    q = (h @ w["wq"].astype(F32)).reshape(Bn, L, nh, hd)
    k = (h @ w["wk"].astype(F32)).reshape(Bn, L, kvh, hd)
    v = (h @ w["wv"].astype(F32)).reshape(Bn, L, kvh, hd)
    q = rotate(rms_norm(q, s["eps"]), pos, s["theta"])
    k = rotate(rms_norm(k, s["eps"]), pos, s["theta"])
    k, v = (jnp.repeat(m, nh // kvh, axis=2) for m in (k, v))
    scores = jnp.einsum("bihd,bjhd->bhij", q, k) / math.sqrt(hd)
    scores = jnp.where((pos[None, :] <= pos[:, None])[None, None],
                       scores, -jnp.inf)
    out = jnp.einsum("bhij,bjhd->bihd", jax.nn.softmax(scores, axis=-1), v)
    return out.reshape(Bn, L, nh * hd) @ w["wo"].astype(F32)


def swiglu(x, w1, w3, w2):
    return (jax.nn.silu(x @ w1) * (x @ w3)) @ w2


def route(h, w, s):
    """Gates [..., E]: mass on each token's chosen experts. The bias ranks;
    the unbiased scores weigh, over their sum + 1e-6."""
    scores = jax.nn.sigmoid(h @ w["w_router"].astype(F32))
    _, idx = jax.lax.top_k(scores + w["router_bias"], s["k"])
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    if s["norm_topk"]:
        chosen = chosen / (
            jnp.sum(chosen, axis=-1, keepdims=True) + NORM_TOPK_EPS)
    return jnp.sum(
        jax.nn.one_hot(idx, s["E"], dtype=F32)
        * (chosen * s["scale"])[..., None], axis=-2)


def expert_layer(h, w, s):
    """The weighted sum over each token's experts, one expert computed at
    a time for every token."""
    gates = route(h, w, s)

    def one(out, e):
        y = swiglu(h, *(w[m][e].astype(F32) for m in ("w1", "w3", "w2")))
        g = jax.lax.dynamic_index_in_dim(gates, e, axis=-1, keepdims=True)
        return out + g * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(h), jnp.arange(s["E"]))
    return out


@partial(jax.jit, static_argnames=("items", "kind", "moe", "dtype"))
def _layer(key, x, span, items, kind, moe, dtype):
    s = dict(items)
    w = layer_weights(key, s, kind, moe, jnp.dtype(dtype))
    h = rms_norm(x, s["eps"])
    if kind == "conv":
        x = x + conv_mixer(h, w, span)
    else:
        x = x + attention_mixer(h, w, s)
    h = rms_norm(x, s["eps"])
    if moe:
        return x + expert_layer(h, w, s)
    return x + swiglu(h, *(w[m].astype(F32) for m in ("w1", "w3", "w2")))


@partial(jax.jit, static_argnames=("items", "dtype"))
def _table(key, items, dtype):
    s = dict(items)
    return _draw(key, (s["V"], s["D"]), s["V"], jnp.dtype(dtype))


@partial(jax.jit, static_argnames=("items",))
def _head(table, x, items):
    s = dict(items)
    w = table.astype(F32).T                          # tied: [D, V]
    if s.get("lowered") == "int8_weights":
        w = _int8_rounded(w)
    return rms_norm(x, s["eps"]) @ w


def spans_of(rows, length: int):
    """[B, L]: the span each position lies in, ``rows`` [B, R] naming the
    spans' last positions (a short sequence's last row repeated: only
    positions it never reads are counted twice)."""
    rows = np.asarray(rows)
    at = np.arange(length)
    return (rows[:, None, :] < at[None, :, None]).sum(-1).astype(np.int32)


def logits(cfg: dict, seed: int, tokens, rows, dtype: str = "bfloat16", *,
           lowered: str | None = None):
    """Float32 logits ``[B, R, V]`` at positions ``rows`` [B, R] of ONE
    full forward pass over ``tokens`` [B, L] (right-padded: causal layers,
    so padding is never seen). ``cfg`` is a configuration's ``published``
    block; ``lowered`` one of ``LOWERED`` for a control."""
    if lowered not in (None, *LOWERED):
        raise ValueError(f"lowered {lowered!r}: one of {LOWERED}")
    if cfg.get("conv_bias"):
        raise NotImplementedError("a convolution bias is not in this family")
    if not cfg.get("tie_word_embeddings", True):
        raise NotImplementedError("an untied head is not in this reference")
    s = {**sizes(cfg), "lowered": lowered}
    items = tuple(sorted(s.items()))
    tokens = jnp.asarray(tokens)
    span = (
        jnp.asarray(spans_of(rows, tokens.shape[1]))
        if lowered == "dropped_tail" else None
    )
    layer_keys, ek = model_keys(seed, s["L"])
    with jax.default_matmul_precision("highest"):
        table = _table(ek, items, dtype)
        x = table[tokens].astype(F32)
        for li in range(s["L"]):
            kind = "conv" if s["types"][li] == "conv" else "attn"
            x = _layer(layer_keys[li], x, span if kind == "conv" else None,
                       items, kind, li >= s["dense"], dtype)
        picked = jnp.take_along_axis(
            x, jnp.asarray(rows)[:, :, None], axis=1)
        return _head(table, picked, items)
