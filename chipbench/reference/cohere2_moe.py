"""Plain reference of the Command A+ family (``model_type: cohere2_moe``;
CohereLabs/command-a-plus-05-2026): a parallel attention + FFN block under
ONE bias-free LayerNorm, ``layer_switch - 1`` sliding-window layers with
interleaved rotary pairs to one full-attention layer without any rotary
embedding, sigmoid-selected experts beside averaged shared experts, tied
embeddings.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision:
one full forward pass over each whole sequence, no cache, no kernels, no
grouped products, nothing imported from ``dynamo_tpu``. A layer, as the
source's ``config.json`` gives it (``first_k_dense_replace`` 0, so the
``prefix_dense_*`` keys are inert)::

    h      = LN(x) = (x - mean x) / sqrt(var x + layer_norm_eps) * w
    q,k,v  = h Wq [H x d], h Wk [kvH x d], h Wv [kvH x d]      (no bias)
    sliding_attention layer (layer_types[l]): q, k <- RoPE(rope_theta,
        rope_gptj: pair i is channels (2i, 2i + 1)); key j visible to
        query i iff j <= i and i - j < sliding_window
    full_attention layer: NO rotary embedding; causal over the whole context
    a      = softmax(q k^T / sqrt(d)) v Wo
    s      = sigmoid(h Wr); the num_experts_per_tok largest;
             w_e = s_e / sum of the chosen (norm_topk_prob)
    routed = sum_e w_e (silu(h Wg_e) * (h Wu_e)) Wd_e
    shared = 1/n sum_{s<n} (silu(h Wg_s) * (h Wu_s)) Wd_s   ("average")
    x      = x + a + routed + shared                  (use_parallel_block)
    logits = logit_scale * LN_f(x) E^T                (tie_word_embeddings)

Attention is computed in blocks of ``ROW_BLOCK`` query rows against all
keys (one softmax a row: the same sums, so that a 12k-token sequence's
scores fit), each sequence at its own length padded to ``LEN_QUANTUM``
(causal layers never see the padding).

**The share** (``source_values`` and ``share``): the router keeps the
source's width; the experts computed are those held here, ``[index * held,
(index + 1) * held)``, and a token's result is the shared experts' mean
plus the weighted sum over those of its chosen experts that are held; what
the absent ones would have added is left out. The vocabulary's slice is a
smaller vocabulary (embedding and tied head over the slice).

Assumptions, each in the configuration file's ``assumed``: the expert's
width is ``intermediate_size``; "average" is the mean of the shared
experts ADDED to the routed sum; the four shared experts are drawn as one
stacked matrix (their sum is one product); interleaved pairs on seeded
weights; the vision tower is not part of this config. Every held expert is
computed for every token and weighted 0 where not chosen (the same sum,
another order).

Weights are taken from the seed and from nothing the program made, drawn
in the served path's order of splits (``layer_weights``): every matrix at
1 / sqrt(fan-in), the embedding rows at deviation 1 (the family's seeded
draw: a token's own row, not the context's mean, leads the stream, so each
token chooses its own experts).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
ROW_BLOCK = 256
LEN_QUANTUM = 1024
NEG = -1e30


def sizes(cfg: dict, source_values: dict | None = None,
          share: dict | None = None) -> dict:
    held = cfg["num_experts"]
    experts = (source_values or {}).get("num_experts", held)
    types = cfg.get("layer_types")
    period = int(cfg.get("layer_switch") or 0)
    L = cfg["num_hidden_layers"]
    if types is None:
        types = ["full_attention" if period and (li + 1) % period == 0
                 else "sliding_attention" for li in range(L)]
    return {
        "D": cfg["hidden_size"],
        "Im": cfg["intermediate_size"],
        "n_shared": cfg.get("num_shared_experts", 0),
        "L": L,
        "H": cfg["num_attention_heads"],
        "kvH": cfg["num_key_value_heads"],
        "hd": cfg["head_dim"],
        "V": cfg["vocab_size"],
        "E": experts,
        "held": held,
        "first": (share or {}).get("index", 0) * held if held < experts else 0,
        "k": cfg["num_experts_per_tok"],
        "window": int(cfg["sliding_window"]),
        "full": tuple(t == "full_attention" for t in types[:L]),
        "theta": float(cfg["rope_theta"]),
        "eps": float(cfg["layer_norm_eps"]),
        "logit_scale": float(cfg.get("logit_scale", 1.0)),
    }


def _draw(key, shape, fan_in, dtype):
    return (jax.random.normal(key, shape, F32) / (fan_in ** 0.5)).astype(dtype)


def model_keys(seed: int, num_layers: int):
    """(per-layer keys, embedding key) as the served path splits
    ``PRNGKey(seed)`` (the head's key is drawn and unused: tied)."""
    lk, ek, _hk = jax.random.split(jax.random.PRNGKey(seed), 3)
    return jax.random.split(lk, num_layers), ek


def layer_weights(key, s: dict, dtype) -> dict:
    """One layer's weights in ``dtype`` ([in, out] layout), drawn in the
    served path's order: the key split 16 ways; q, k, v, o; the router; the
    held experts' gate, up, down (stacked, fan-in scaled); the shared
    experts' gate, up, down as ONE matrix each of width ``n_shared x Im``.
    The norm's weight is ones: no key."""
    D, H, kvH, hd, Im = s["D"], s["H"], s["kvH"], s["hd"], s["Im"]
    keys = iter(jax.random.split(key, 16))
    w = {
        "wq": _draw(next(keys), (D, H * hd), D, dtype),
        "wk": _draw(next(keys), (D, kvH * hd), D, dtype),
        "wv": _draw(next(keys), (D, kvH * hd), D, dtype),
        "wo": _draw(next(keys), (H * hd, D), H * hd, dtype),
        "w_router": _draw(next(keys), (D, s["E"]), D, dtype),
        "w_gate": _draw(next(keys), (s["held"], D, Im), D, dtype),
        "w_up": _draw(next(keys), (s["held"], D, Im), D, dtype),
        "w_down": _draw(next(keys), (s["held"], Im, D), Im, dtype),
    }
    if s["n_shared"]:
        Is = Im * s["n_shared"]
        w["w_shared_gate"] = _draw(next(keys), (D, Is), D, dtype)
        w["w_shared_up"] = _draw(next(keys), (D, Is), D, dtype)
        w["w_shared_down"] = _draw(next(keys), (Is, D), Is, dtype)
    return w


def layer_norm(x, eps: float):
    """Mean-centred, no bias; the weight is ones in a seeded model."""
    xc = x - jnp.mean(x, axis=-1, keepdims=True)
    return xc / jnp.sqrt(jnp.mean(xc * xc, axis=-1, keepdims=True) + eps)


def rope_interleaved(x, positions, theta: float):
    """x [T, heads, d]: pair i is channels (2i, 2i + 1), angle
    ``position * theta^(-i / (d / 2))``."""
    half = x.shape[-1] // 2
    freqs = jnp.exp(-jnp.log(theta) * (jnp.arange(half, dtype=F32) / half))
    ang = positions.astype(F32)[:, None, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    ).reshape(x.shape)


def attention(h, w, s: dict, full: bool, *, window_on_full: bool = False,
              rope_on_full: bool = False):
    """Causal softmax attention of one sequence ``h`` [T, D]: under the
    window and interleaved rotary pairs in a sliding layer, over the whole
    context and without rotary embedding in a full one. The two keyword
    switches are the tests' controls (a window or rotary wrongly applied to
    the full layer)."""
    T = h.shape[0]
    H, kvH, hd = s["H"], s["kvH"], s["hd"]
    q = (h @ w["wq"].astype(F32)).reshape(T, H, hd)
    k = (h @ w["wk"].astype(F32)).reshape(T, kvH, hd)
    v = (h @ w["wv"].astype(F32)).reshape(T, kvH, hd)
    pos = jnp.arange(T)
    if not full or rope_on_full:
        q = rope_interleaved(q, pos, s["theta"])
        k = rope_interleaved(k, pos, s["theta"])
    windowed = not full or window_on_full
    q = q.reshape(T, kvH, H // kvH, hd)
    # Blocks of ROW_BLOCK rows where they tile the sequence (a pad_to that
    # is a multiple of it), else the whole of a short sequence at once.
    nb = T // ROW_BLOCK if T % ROW_BLOCK == 0 else 1
    rb = T // nb

    def block(args):
        qb, qpos = args                      # [rb, kvH, G, hd], [rb]
        sc = jnp.einsum("rkgd,tkd->kgrt", qb, k) / (hd ** 0.5)
        seen = pos[None, :] <= qpos[:, None]
        if windowed:
            seen &= qpos[:, None] - pos[None, :] < s["window"]
        p = jax.nn.softmax(jnp.where(seen[None, None], sc, NEG), axis=-1)
        return jnp.einsum("kgrt,tkd->rkgd", p, v)

    out = jax.lax.map(
        block, (q.reshape(nb, rb, kvH, H // kvH, hd), pos.reshape(nb, rb)))
    return out.reshape(T, H * hd) @ w["wo"].astype(F32)


def swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def route(x, w, s):
    """Dense gates [T, E] over the SOURCE's experts: sigmoid scores, the
    ``k`` largest, their scores divided by their sum."""
    scores = jax.nn.sigmoid(x @ w["w_router"].astype(F32))
    _, idx = jax.lax.top_k(scores, s["k"])
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    chosen = chosen / jnp.sum(chosen, axis=-1, keepdims=True)
    return jnp.sum(
        jax.nn.one_hot(idx, s["E"], dtype=F32) * chosen[..., None], axis=-2)


def routed_experts(x, w, s):
    """The weighted sum over those of each token's chosen experts that are
    held here: ``w``'s stacked matrices are experts ``[s["first"],
    s["first"] + s["held"])``, one computed at a time."""
    gates = route(x, w, s)[..., s["first"] : s["first"] + s["held"]]

    def one(out, e):
        y = swiglu(x, *(w[n][e].astype(F32)
                        for n in ("w_gate", "w_up", "w_down")))
        g = jax.lax.dynamic_index_in_dim(gates, e, axis=-1, keepdims=True)
        return out + g * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(x), jnp.arange(s["held"]))
    return out


def shared_experts(x, w, s):
    """The MEAN of the shared experts: their stacked product is their
    sum."""
    if not s["n_shared"]:
        return jnp.zeros_like(x)
    return swiglu(x, *(w[n].astype(F32) for n in (
        "w_shared_gate", "w_shared_up", "w_shared_down"))) / s["n_shared"]


def block_out(x, w, s, full: bool, *, sequential: bool = False, **controls):
    """One layer: ``x + a + routed + shared``, attention and FFN both over
    the ONE norm of ``x``. ``sequential`` is the tests' control: the FFN
    reads the norm of ``x + a`` instead (a block that is not parallel)."""
    h = layer_norm(x, s["eps"])
    a = attention(h, w, s, full, **controls)
    if sequential:
        x = x + a
        h = layer_norm(x, s["eps"])
        return x + routed_experts(h, w, s) + shared_experts(h, w, s)
    return x + a + routed_experts(h, w, s) + shared_experts(h, w, s)


@partial(jax.jit, static_argnames=("items", "full", "dtype", "controls"))
def _layer(key, x, items, full, dtype, controls=()):
    s = dict(items)
    w = layer_weights(key, s, jnp.dtype(dtype))
    return block_out(x, w, s, full, **dict(controls))


@partial(jax.jit, static_argnames=("items", "dtype"))
def _embed(key, tokens, items, dtype):
    s = dict(items)
    table = _draw(key, (s["V"], s["D"]), 1, jnp.dtype(dtype))
    return table[tokens].astype(F32)


@partial(jax.jit, static_argnames=("items", "dtype"))
def _head(key, x, items, dtype):
    s = dict(items)
    table = _draw(key, (s["V"], s["D"]), 1, jnp.dtype(dtype)).astype(F32)
    return s["logit_scale"] * (layer_norm(x, s["eps"]) @ table.T)


def logits(cfg: dict, seed: int, tokens, rows, dtype: str = "bfloat16", *,
           source_values: dict | None = None, share: dict | None = None,
           controls: tuple = ()):
    """Float32 logits ``[B, R, V]`` at positions ``rows`` [B, R] of ONE
    full forward pass over each of ``tokens`` [B, L] (right-padded; a
    sequence is computed up to its last asked row, padded to
    ``LEN_QUANTUM``: causal layers never see what lies behind a row).
    ``cfg`` is a configuration's ``published`` block; ``source_values`` and
    ``share`` say which experts of the source's are held here."""
    if not cfg.get("tie_word_embeddings", True):
        raise NotImplementedError("untied embeddings are not in this family")
    if cfg.get("first_k_dense_replace"):
        raise NotImplementedError("leading dense layers are not computed")
    if not cfg.get("use_parallel_block", True):
        raise NotImplementedError("only the parallel block is computed")
    s = sizes(cfg, source_values, share)
    items = tuple(sorted(s.items()))
    layer_keys, ek = model_keys(seed, s["L"])
    tokens = np.asarray(tokens)
    rows = np.asarray(rows)
    out = []
    with jax.default_matmul_precision("highest"):
        for b in range(tokens.shape[0]):
            need = int(rows[b].max()) + 1
            n = min(-(-need // LEN_QUANTUM) * LEN_QUANTUM, tokens.shape[1])
            x = _embed(ek, jnp.asarray(tokens[b, :n]), items, dtype)
            for li in range(s["L"]):
                x = _layer(layer_keys[li], x, items, s["full"][li], dtype,
                           controls)
            out.append(_head(ek, x[jnp.asarray(rows[b])], items, dtype))
    return jnp.stack(out)
