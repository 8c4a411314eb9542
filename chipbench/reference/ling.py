"""Plain reference of the Ling-3.0 family (``model_type: bailing_hybrid``;
Ling-3.0-flash): delta-rule linear-attention (KDA) layers with one
latent-attention layer a group, dense MLPs first, then sigmoid-scored
experts with a shared expert.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision:
one full forward pass over the whole sequence, the recurrence a scan over
the tokens, no cache, no kernels, no grouped products, no absorbed
projections, nothing imported from ``dynamo_tpu``. Pre-norm residual
blocks, RMSNorm with ``rms_norm_eps``: ``x += mixer(norm(x)); x +=
mlp(norm(x))``.

**KDA mixer** (layer ``l`` with ``(l + 1) % layer_group_size != 0``; Kimi
Delta Attention, arXiv:2510.26692), per token ``t`` and head ``h``::

    q_t, k_t = L2norm(silu(conv(W_q x)_t)), L2norm(silu(conv(W_k x)_t))
    v_t      = silu(conv(W_v x)_t)
    g_t      = kda_lower_bound * sigmoid(exp(A_log_h) * (W_a x_t + dt_bias))
    beta_t   = sigmoid(W_beta x_t)
    S_t      = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t      = S_t^T (q_t / sqrt(d))
    y_t      = W_o concat_h(sigmoid((W_g x_t)_h) * RMSNorm(o_t))

``conv`` is a causal depthwise convolution of ``short_conv_kernel_size``
taps over the sequence (``y_t = sum_i w[i] x_{t-(K-1)+i}``, zeros before the
first token), ``S_0 = 0`` in float32, no rotary embedding.

**Latent-attention mixer** (the group's last layer): ``q = W_q x`` as ``H``
heads of ``qk_nope_head_dim + qk_rope_head_dim``; ``[c, k_pe] = W_dkv x``,
``c`` of ``kv_lora_rank`` through an RMSNorm; each head's keys ``[W_uk c,
rope(k_pe)]`` (the roped part shared by all heads) and values ``W_uv c``,
materialised; causal softmax at scale ``qk_head_dim^-1/2``; ``W_o``.

**MLP.** The first ``first_k_dense_replace`` layers: SwiGLU of width
``intermediate_size``. The rest: ``sigmoid`` scores over the SOURCE's
number of experts; selection on ``score + bias``, the ``topk_group`` best of
``n_group`` groups by the sum of each group's two best, the
``num_experts_per_tok`` best within them; weights the raw scores of the
chosen, divided by their sum, times ``routed_scaling_factor``; plus one
always-on shared expert. A layer's swiglu limit ``L > 0`` clamps the gate to
at most ``L`` and the up projection into ``[-L, L]`` before the activation.

**The share** (``source_values`` and ``share``): the router keeps the
source's width; the experts computed are those held here,
``[index * held, (index + 1) * held)``, and a token's result is the shared
expert plus the weighted sum over those of its experts that are held; what
the absent ones would have added is left out. The vocabulary's slice is a
smaller vocabulary.

Departures and assumptions, each in the configuration file's ``assumed``:
the bounded form of ``g_t``; the state and recurrence in float32; ``q``
scaled by ``d^-1/2``; the L2 norm's eps 1e-6; the order conv -> silu ->
L2 norm; ``use_qk_norm`` read as the RMSNorm on the latent only; the
multi-token-prediction module left out. Every held expert is computed for
every token and weighted 0 where not chosen (the same sum, another order).

Weights are taken from the seed and from nothing the program made, drawn
in the served path's order of splits (``layer_weights``).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

F32 = jnp.float32
L2_EPS = 1e-6


def sizes(cfg: dict, source_values: dict | None = None,
          share: dict | None = None) -> dict:
    held = cfg["num_experts"]
    experts = (source_values or {}).get("num_experts", held)
    return {
        "D": cfg["hidden_size"],
        "I": cfg["intermediate_size"],
        "Im": cfg["moe_intermediate_size"],
        "Is": cfg.get("moe_shared_expert_intermediate_size",
                      cfg["moe_intermediate_size"])
        * cfg.get("num_shared_experts", 1),
        "L": cfg["num_hidden_layers"],
        "H": cfg["num_attention_heads"],
        "hd": cfg["head_dim"],
        "V": cfg["vocab_size"],
        "E": experts,
        "held": held,
        "first": (share or {}).get("index", 0) * held if held < experts else 0,
        "k": cfg["num_experts_per_tok"],
        "groups": cfg.get("n_group", 1),
        "top_groups": cfg.get("topk_group", 1),
        "scale": float(cfg.get("routed_scaling_factor", 1.0)),
        "dense": cfg.get("first_k_dense_replace", 0),
        "period": cfg["layer_group_size"],
        "K": cfg["short_conv_kernel_size"],
        "lower": float(cfg["kda_lower_bound"]),
        "dc": cfg["kv_lora_rank"],
        "dn": cfg["qk_nope_head_dim"],
        "dr": cfg["qk_rope_head_dim"],
        "dv": cfg["v_head_dim"],
        "theta": float(cfg["rope_theta"]),
        "eps": float(cfg["rms_norm_eps"]),
    }


def is_kda(s: dict, li: int) -> bool:
    return (li + 1) % s["period"] != 0


def limit_of(cfg: dict, key: str, li: int) -> float:
    limits = cfg.get(key) or ()
    return float(limits[li]) if li < len(limits) else 0.0


def _draw(key, shape, fan_in, dtype):
    return (jax.random.normal(key, shape, F32) / (fan_in ** 0.5)).astype(dtype)


def model_keys(seed: int, num_layers: int):
    """(per-layer keys, embedding key, head key) as the served path splits
    ``PRNGKey(seed)``."""
    lk, ek, hk = jax.random.split(jax.random.PRNGKey(seed), 3)
    return jax.random.split(lk, num_layers), ek, hk


def layer_weights(key, s: dict, li: int, dtype) -> dict:
    """Layer ``li``'s weights in ``dtype`` ([in, out] layout), drawn in the
    served path's order. A KDA layer splits its key 24 ways: q, k, v and
    the decay projection, beta, the output gate, the three convolutions,
    ``A_log``, ``dt_bias``, o. A latent-attention layer 16 ways: the
    down-projection, ``W_uk``, ``W_uv``, o, q. Then the MLP: gate, up,
    down; or the router, the held experts' gate, up, down and the shared
    expert's. Norm weights are ones, the router's bias zeros: no key."""
    D, H, hd = s["D"], s["H"], s["hd"]
    C = H * hd
    w = {}
    if is_kda(s, li):
        keys = iter(jax.random.split(key, 24))
        for name in ("wq", "wk", "wv", "w_a"):
            w[name] = _draw(next(keys), (D, C), D, dtype)
        w["w_beta"] = _draw(next(keys), (D, H), D, dtype)
        w["w_g"] = _draw(next(keys), (D, H), D, dtype)
        for name in ("conv_q", "conv_k", "conv_v"):
            w[name] = _draw(next(keys), (s["K"], C), s["K"], dtype)
        w["A_log"] = jnp.log(
            jax.random.uniform(next(keys), (H,), F32, 1.0, 16.0))
        w["dt_bias"] = jax.random.normal(next(keys), (C,), F32)
        w["wo"] = _draw(next(keys), (C, D), C, dtype)
    else:
        keys = iter(jax.random.split(key, 16))
        dc, dn, dr, dv = s["dc"], s["dn"], s["dr"], s["dv"]
        w["w_dkv"] = _draw(next(keys), (D, dc + dr), D, dtype)
        w["w_uk"] = _draw(next(keys), (H, dn, dc), dn, dtype)
        w["w_uv"] = _draw(next(keys), (H, dv, dc), dc, dtype)
        w["wo"] = _draw(next(keys), (H * dv, D), H * dv, dtype)
        w["wq"] = _draw(next(keys), (D, H * (dn + dr)), D, dtype)
    if li < s["dense"]:
        w["w_gate"] = _draw(next(keys), (D, s["I"]), D, dtype)
        w["w_up"] = _draw(next(keys), (D, s["I"]), D, dtype)
        w["w_down"] = _draw(next(keys), (s["I"], D), s["I"], dtype)
        return w
    Eh, Im, Is = s["held"], s["Im"], s["Is"]
    w["w_router"] = _draw(next(keys), (D, s["E"]), D, dtype)
    w["router_bias"] = jnp.zeros((s["E"],), F32)
    w["w_gate"] = _draw(next(keys), (Eh, D, Im), D, dtype)
    w["w_up"] = _draw(next(keys), (Eh, D, Im), D, dtype)
    w["w_down"] = _draw(next(keys), (Eh, Im, D), Im, dtype)
    w["w_shared_gate"] = _draw(next(keys), (D, Is), D, dtype)
    w["w_shared_up"] = _draw(next(keys), (D, Is), D, dtype)
    w["w_shared_down"] = _draw(next(keys), (Is, D), Is, dtype)
    return w


def rms_norm(x, eps):
    """RMSNorm with a weight of ones (what a seeded model holds)."""
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def l2_norm(x):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def rope(x, positions, theta):
    """Half-rotation RoPE: x [..., L, heads, hd], positions [L]."""
    hd = x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = positions.astype(F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def causal_conv(x, w):
    """x [B, L, C], w [K, C]: ``y_t = sum_i w[i] x_{t-(K-1)+i}``."""
    K, L = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    return sum(w[i] * padded[:, i : i + L] for i in range(K))


def kda_scan(q, k, v, g, beta):
    """The delta rule, token by token from ``S_0 = 0``: q, k, v, g
    [B, L, H, d], beta [B, L, H] -> o [B, L, H, d]."""
    B, _, H, d = q.shape

    def step(S, row):
        q_t, k_t, v_t, g_t, b_t = row
        S = jnp.exp(g_t)[..., None] * S                       # [B, H, dk, dv]
        u = b_t[..., None] * (v_t - jnp.einsum("bhk,bhkv->bhv", k_t, S))
        S = S + k_t[..., None] * u[..., None, :]
        return S, jnp.einsum("bhk,bhkv->bhv", q_t, S)

    rows = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta))
    _, o = jax.lax.scan(step, jnp.zeros((B, H, d, d), F32), rows)
    return jnp.moveaxis(o, 0, 1)


def kda_mixer(x, w, s):
    """x [B, L, D] (normed) -> [B, L, D]."""
    B, L, _ = x.shape
    H, d = s["H"], s["hd"]

    def conv(name, cname):
        y = causal_conv(x @ w[name].astype(F32), w[cname].astype(F32))
        return jax.nn.silu(y).reshape(B, L, H, d)

    q = l2_norm(conv("wq", "conv_q")) / math.sqrt(d)
    k = l2_norm(conv("wk", "conv_k"))
    v = conv("wv", "conv_v")
    a = (x @ w["w_a"].astype(F32) + w["dt_bias"]).reshape(B, L, H, d)
    g = s["lower"] * jax.nn.sigmoid(jnp.exp(w["A_log"])[:, None] * a)
    beta = jax.nn.sigmoid(x @ w["w_beta"].astype(F32))
    o = rms_norm(kda_scan(q, k, v, g, beta), s["eps"])
    gate = jax.nn.sigmoid(x @ w["w_g"].astype(F32))
    return (o * gate[..., None]).reshape(B, L, H * d) @ w["wo"].astype(F32)


def latent_mixer(x, w, s):
    """x [B, L, D] (normed) -> [B, L, D]: every head's keys and values
    materialised from the shared latent."""
    B, L, _ = x.shape
    H, dc, dn, dr, dv = s["H"], s["dc"], s["dn"], s["dr"], s["dv"]
    pos = jnp.arange(L)
    q = (x @ w["wq"].astype(F32)).reshape(B, L, H, dn + dr)
    q = jnp.concatenate(
        [q[..., :dn], rope(q[..., dn:], pos, s["theta"])], axis=-1)
    ckr = x @ w["w_dkv"].astype(F32)
    c = rms_norm(ckr[..., :dc], s["eps"])
    k_pe = rope(ckr[..., None, dc:], pos, s["theta"])        # [B, L, 1, dr]
    k_nope = jnp.einsum("blc,hnc->blhn", c, w["w_uk"].astype(F32))
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe, (B, L, H, dr))], axis=-1)
    v = jnp.einsum("blc,hvc->blhv", c, w["w_uv"].astype(F32))
    scores = jnp.einsum("bihd,bjhd->bhij", q, k) / math.sqrt(dn + dr)
    scores = jnp.where((pos[None, :] <= pos[:, None])[None, None],
                       scores, -jnp.inf)
    out = jnp.einsum("bhij,bjhv->bihv", jax.nn.softmax(scores, axis=-1), v)
    return out.reshape(B, L, H * dv) @ w["wo"].astype(F32)


def swiglu(x, gate, up, down, limit: float = 0.0):
    g, u = x @ gate, x @ up
    if limit:
        g, u = jnp.minimum(g, limit), jnp.clip(u, -limit, limit)
    return (jax.nn.silu(g) * u) @ down


def route(x, w, s):
    """Gates [..., E] over the source's experts, mass on each token's
    chosen ones."""
    E, G = s["E"], s["groups"]
    scores = jax.nn.sigmoid(x @ w["w_router"].astype(F32))
    biased = scores + w["router_bias"]
    if G > 1:
        grouped = biased.reshape(*biased.shape[:-1], G, E // G)
        best2, _ = jax.lax.top_k(grouped, min(2, E // G))
        _, keep = jax.lax.top_k(best2.sum(-1), s["top_groups"])
        open_ = jax.nn.one_hot(keep, G, dtype=F32).sum(-2)      # [..., G]
        biased = jnp.where(
            jnp.repeat(open_, E // G, axis=-1) > 0, biased, -jnp.inf)
    _, idx = jax.lax.top_k(biased, s["k"])
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    chosen = chosen / jnp.sum(chosen, axis=-1, keepdims=True) * s["scale"]
    return jnp.sum(
        jax.nn.one_hot(idx, E, dtype=F32) * chosen[..., None], axis=-2)


def expert_layer(x, w, s, limit: float = 0.0, shared_limit: float = 0.0):
    """The shared expert plus the weighted sum over those of each token's
    experts that are held here: ``w``'s stacked matrices are experts
    ``[s["first"], s["first"] + s["held"])``, one computed at a time."""
    gates = route(x, w, s)[..., s["first"] : s["first"] + s["held"]]

    def one(out, e):
        y = swiglu(x, *(w[n][e].astype(F32)
                        for n in ("w_gate", "w_up", "w_down")), limit)
        g = jax.lax.dynamic_index_in_dim(gates, e, axis=-1, keepdims=True)
        return out + g * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(x), jnp.arange(s["held"]))
    return out + swiglu(
        x, *(w[n].astype(F32) for n in
             ("w_shared_gate", "w_shared_up", "w_shared_down")), shared_limit)


@partial(jax.jit, static_argnames=("items", "li", "dtype", "limits"))
def _layer(key, x, items, li, dtype, limits):
    s = dict(items)
    w = layer_weights(key, s, li, jnp.dtype(dtype))
    mixer = kda_mixer if is_kda(s, li) else latent_mixer
    x = x + mixer(rms_norm(x, s["eps"]), w, s)
    h = rms_norm(x, s["eps"])
    if li < s["dense"]:
        return x + swiglu(
            h, *(w[n].astype(F32) for n in ("w_gate", "w_up", "w_down")))
    return x + expert_layer(h, w, s, *limits)


@partial(jax.jit, static_argnames=("items", "dtype"))
def _embed(key, tokens, items, dtype):
    s = dict(items)
    table = _draw(key, (s["V"], s["D"]), s["V"], jnp.dtype(dtype))
    return table[tokens].astype(F32)


@partial(jax.jit, static_argnames=("items", "dtype"))
def _head(key, x, items, dtype):
    s = dict(items)
    w = _draw(key, (s["D"], s["V"]), s["D"], jnp.dtype(dtype)).astype(F32)
    return rms_norm(x, s["eps"]) @ w


def logits(cfg: dict, seed: int, tokens, rows, dtype: str = "bfloat16", *,
           source_values: dict | None = None, share: dict | None = None):
    """Float32 logits ``[B, R, V]`` at positions ``rows`` [B, R] of ONE
    full forward pass over ``tokens`` [B, L] (right-padded: causal layers,
    so padding is never seen). ``cfg`` is a configuration's ``published``
    block; ``source_values`` and ``share`` say which experts of the
    source's are held here."""
    if cfg.get("tie_word_embeddings"):
        raise NotImplementedError("tied embeddings are not in this family")
    if cfg.get("q_lora_rank"):
        raise NotImplementedError("a low-rank q is not in this family")
    s = sizes(cfg, source_values, share)
    items = tuple(sorted(s.items()))
    layer_keys, ek, hk = model_keys(seed, s["L"])
    with jax.default_matmul_precision("highest"):
        x = _embed(ek, jnp.asarray(tokens), items, dtype)
        for li in range(s["L"]):
            limits = (limit_of(cfg, "expert_swiglu_limit_list", li),
                      limit_of(cfg, "share_expert_swiglu_limit_list", li))
            x = _layer(layer_keys[li], x, items, li, dtype, limits)
        picked = jnp.take_along_axis(
            x, jnp.asarray(rows)[:, :, None], axis=1)
        return _head(hk, picked, items, dtype)
