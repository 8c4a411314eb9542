"""Plain reference of the Nemotron-H family (``model_type: nemotron_h``;
NVIDIA-Nemotron-3-Super-120B-A12B): layers that are ONE part each by
``hybrid_override_pattern`` (``M`` a Mamba-2 state-space mixer, ``*``
attention, ``E`` an expert layer), one RMSNorm a layer:
``x += part(norm(x))``; a final RMSNorm and an untied head.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision:
one full forward pass over the whole sequence, the recurrence a scan over
the tokens (no chunked form), no cache, no kernels, no grouped products,
nothing imported from ``dynamo_tpu``.

**``M``, Mamba-2 (SSD)**: ``H = mamba_num_heads`` heads of ``P =
mamba_head_dim``, ``G = n_groups`` groups, ``N = ssm_state_size``, ``K =
conv_kernel``. ``[z | xBC | dt] = h W_in`` (``H P | H P + 2 G N | H``, no
bias). ``xBC = silu(conv(xBC) + b_conv)``, a causal depthwise convolution
over the sequence (``y_t = sum_i w[i] x_{t-(K-1)+i}``, zeros before the
first token). ``[x | B | C] = xBC`` (head ``i`` reads group ``i // (H /
G)``). ``dt = softplus(dt + dt_bias)``; ``a = exp(-exp(A_log) dt)``.
``S_t = a_t S_{t-1} + (dt_t x_t) B_t^T`` from ``S_0 = 0`` in float32;
``y_t = S_t C_t + D x_t``. ``y = GroupRMSNorm(y * silu(z))`` over groups
of ``H P / G`` channels; ``out = y W_out``.

**``*``, attention**: ``num_attention_heads`` query heads over
``num_key_value_heads`` cached heads of ``head_dim``, causal, softmax scale
``head_dim^-1/2``, no bias, NO rotary embedding.

**``E``, latent experts**: ``s = sigmoid(h W_r)`` over the SOURCE's number
of experts; the ``num_experts_per_tok`` largest of ``s + bias`` (``n_group``
1: no group limit); weights ``s[sel] / sum(s[sel]) * routed_scaling_factor``.
``l = h W_dn`` (``moe_latent_size`` wide); expert ``e``: ``relu(l W1_e)^2
W2_e``; ``out = (sum_e g_e f_e(l)) W_up + relu(h U1)^2 U2``, the shared
expert on the whole row.

**The share** (``source_values`` and ``share``): the router keeps the
source's width; the experts computed are those held here, ``[index * held,
(index + 1) * held)``, and a token's routed sum runs over those of its
experts that are held; what the absent ones would have added is left out.
The shared expert, ``W_dn``, ``W_up`` and the router are whole. The
vocabulary's slice is a smaller vocabulary.

Departures and assumptions, each in the configuration file's ``assumed``:
no rotary embedding (``rope_theta`` and ``partial_rotary_factor`` inert);
``dt`` unclamped above; ``time_step_min`` / ``max`` / ``floor`` seed
``dt_bias`` only; ``A_log = log(uniform(1, 16))`` and ``D = 1`` a head,
seeded stand-ins; the gate BEFORE the group norm; the state and the
recurrence in float32; ``chunk_size`` no part of the function; the latent
projections shared by all routed experts; ``relu2 = relu(x)^2``,
non-gated; the embedding rows drawn at deviation 1; the selection bias
zeros; the multi-token-prediction module left out. Every held expert is
computed for every token and weighted 0 where not chosen (the same sum,
another order).

Weights are taken from the seed and from nothing the program made, drawn
in the served path's order of splits (``layer_weights``).

**The controls that set the check's limits** (``logits(..., lowered=...)``,
read by ``python3 -m chipbench.control_lowered``; never by a run of the
benchmark): this same pass in a precision below the one the configuration
states, whose logits stand in the program's place. ``"int8_weights"``:
every matrix a product reads (the projections, the experts, the head; not
the router, the convolution, the vectors or the embedding, as weight-only
int8 serving leaves them) rounded to 8 bits under one scale an output
channel, ``amax / 127``. ``"bf16_state"``: the state rounded to bfloat16
after every token.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

F32 = jnp.float32
#: what ``logits`` can compute below the configuration's precision
LOWERED = ("int8_weights", "bf16_state")
#: the matrices of a layer that ``"int8_weights"`` rounds
INT8_ROUNDED = ("w_in", "w_out", "wq", "wk", "wv", "wo",
                "w_dn", "w1", "w2", "w_up", "u1", "u2")


def sizes(cfg: dict, source_values: dict | None = None,
          share: dict | None = None) -> dict:
    held = cfg["n_routed_experts"]
    experts = (source_values or {}).get("n_routed_experts", held)
    H, P = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    G, N = cfg["n_groups"], cfg["ssm_state_size"]
    return {
        "D": cfg["hidden_size"],
        "Im": cfg["moe_intermediate_size"],
        "Is": cfg["moe_shared_expert_intermediate_size"],
        "Z": cfg["moe_latent_size"],
        "L": cfg["num_hidden_layers"],
        "pattern": cfg["hybrid_override_pattern"],
        "heads": cfg["num_attention_heads"],
        "kv_heads": cfg["num_key_value_heads"],
        "hd": cfg["head_dim"],
        "V": cfg["vocab_size"],
        "E": experts,
        "held": held,
        "first": (share or {}).get("index", 0) * held if held < experts else 0,
        "k": cfg["num_experts_per_tok"],
        "scale": float(cfg.get("routed_scaling_factor", 1.0)),
        "H": H, "P": P, "G": G, "N": N, "K": cfg["conv_kernel"],
        "di": H * P, "cd": H * P + 2 * G * N,
        "dt_min": float(cfg.get("time_step_min", 0.001)),
        "dt_max": float(cfg.get("time_step_max", 0.1)),
        "dt_floor": float(cfg.get("time_step_floor", 1e-4)),
        "eps": float(cfg["layer_norm_epsilon"]),
    }


def _draw(key, shape, fan_in, dtype):
    return (jax.random.normal(key, shape, F32) / (fan_in ** 0.5)).astype(dtype)


def _int8_rounded(w):
    """``w`` [.., in, out] as 8 bits under one scale an output channel."""
    w = w.astype(F32)
    scale = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / 127.0
    return jnp.round(w / scale) * scale


def model_keys(seed: int, num_layers: int):
    """(per-layer keys, embedding key, head key) as the served path splits
    ``PRNGKey(seed)``."""
    lk, ek, hk = jax.random.split(jax.random.PRNGKey(seed), 3)
    return jax.random.split(lk, num_layers), ek, hk


def layer_weights(key, s: dict, li: int, dtype) -> dict:
    """Layer ``li``'s weights in ``dtype`` ([in, out] layout), drawn in the
    served path's order from the key split 16 ways. ``M``: the
    in-projection, the convolution and its bias, ``A_log``, the step that
    ``dt_bias`` inverts, the out-projection. ``*``: q, k, v, o. ``E``: the
    router, the latent's down-projection, the held experts' two matrices,
    the latent's up-projection, the shared expert's two. Norm weights and
    ``D`` are ones, the router's bias zeros: no key."""
    D = s["D"]
    keys = iter(jax.random.split(key, 16))
    letter = s["pattern"][li]
    w = {}
    if letter == "M":
        H, K, di, cd = s["H"], s["K"], s["di"], s["cd"]
        w["w_in"] = _draw(next(keys), (D, di + cd + H), D, dtype)
        w["conv_w"] = _draw(next(keys), (K, cd), K, dtype)
        w["conv_b"] = _draw(next(keys), (cd,), K, dtype)
        w["A_log"] = jnp.log(
            jax.random.uniform(next(keys), (H,), F32, 1.0, 16.0))
        lo, hi = math.log(s["dt_min"]), math.log(s["dt_max"])
        step = jnp.maximum(jnp.exp(
            jax.random.uniform(next(keys), (H,), F32) * (hi - lo) + lo
        ), s["dt_floor"])
        w["dt_bias"] = step + jnp.log(-jnp.expm1(-step))
        w["w_out"] = _draw(next(keys), (di, D), di, dtype)
    elif letter == "*":
        C, kvC = s["heads"] * s["hd"], s["kv_heads"] * s["hd"]
        w["wq"] = _draw(next(keys), (D, C), D, dtype)
        w["wk"] = _draw(next(keys), (D, kvC), D, dtype)
        w["wv"] = _draw(next(keys), (D, kvC), D, dtype)
        w["wo"] = _draw(next(keys), (C, D), C, dtype)
    elif letter == "E":
        Eh, Im, Is, Z = s["held"], s["Im"], s["Is"], s["Z"]
        w["w_router"] = _draw(next(keys), (D, s["E"]), D, dtype)
        w["router_bias"] = jnp.zeros((s["E"],), F32)
        w["w_dn"] = _draw(next(keys), (D, Z), D, dtype)
        w["w1"] = _draw(next(keys), (Eh, Z, Im), Z, dtype)
        w["w2"] = _draw(next(keys), (Eh, Im, Z), Im, dtype)
        w["w_up"] = _draw(next(keys), (Z, D), Z, dtype)
        w["u1"] = _draw(next(keys), (D, Is), D, dtype)
        w["u2"] = _draw(next(keys), (Is, D), Is, dtype)
    else:
        raise NotImplementedError(f"pattern letter {letter!r}")
    if s.get("lowered") == "int8_weights":
        w.update({k: _int8_rounded(w[k]) for k in INT8_ROUNDED if k in w})
    return w


def rms_norm(x, eps):
    """RMSNorm with a weight of ones (what a seeded model holds)."""
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def causal_conv(x, w):
    """x [B, L, C], w [K, C]: ``y_t = sum_i w[i] x_{t-(K-1)+i}``."""
    K, L = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    return sum(w[i] * padded[:, i : i + L] for i in range(K))


def ssd_scan(x, dt, a, B, C, bf16_state=False):
    """The state-space recurrence token by token from ``S_0 = 0``: x [B, L,
    H, P], dt and a [B, L, H], B and C [B, L, H, N] (a head's group's) ->
    y [B, L, H, P] (without the skip). ``bf16_state`` (a control): the
    state rounded to bfloat16 after every token."""
    Bn, _, H, P = x.shape
    N = B.shape[-1]

    def step(S, row):
        x_t, dt_t, a_t, b_t, c_t = row
        S = a_t[..., None, None] * S + (
            (dt_t[..., None] * x_t)[..., None] * b_t[..., None, :])
        if bf16_state:
            # (a convert pair is excess precision the compiler may drop)
            S = jax.lax.reduce_precision(S, exponent_bits=8, mantissa_bits=7)
        return S, jnp.einsum("bhpn,bhn->bhp", S, c_t)

    rows = tuple(jnp.moveaxis(v, 1, 0) for v in (x, dt, a, B, C))
    _, y = jax.lax.scan(step, jnp.zeros((Bn, H, P, N), F32), rows)
    return jnp.moveaxis(y, 0, 1)


def mamba_mixer(h, w, s):
    """h [B, L, D] (normed) -> [B, L, D]."""
    Bn, L, _ = h.shape
    H, P, G, N, di, cd = (s[k] for k in ("H", "P", "G", "N", "di", "cd"))
    zxbcdt = h @ w["w_in"].astype(F32)
    z, xbc, dt = zxbcdt[..., :di], zxbcdt[..., di:di + cd], zxbcdt[..., di + cd:]
    xbc = jax.nn.silu(
        causal_conv(xbc, w["conv_w"].astype(F32)) + w["conv_b"].astype(F32))
    x = xbc[..., :di].reshape(Bn, L, H, P)
    per_head = lambda m: jnp.repeat(m.reshape(Bn, L, G, N), H // G, axis=2)
    Bm = per_head(xbc[..., di:di + G * N])
    Cm = per_head(xbc[..., di + G * N:])
    dt = jax.nn.softplus(dt + w["dt_bias"])
    a = jnp.exp(-jnp.exp(w["A_log"]) * dt)
    y = ssd_scan(x, dt, a, Bm, Cm, s.get("lowered") == "bf16_state") + x  # D = 1
    y = (y.reshape(Bn, L, di) * jax.nn.silu(z)).reshape(Bn, L, G, di // G)
    return rms_norm(y, s["eps"]).reshape(Bn, L, di) @ w["w_out"].astype(F32)


def attention_mixer(h, w, s):
    """h [B, L, D] (normed) -> [B, L, D]: causal softmax attention, a
    query head reading cached head ``i // (heads / kv_heads)``, no rotary
    embedding."""
    Bn, L, _ = h.shape
    nh, kvh, hd = s["heads"], s["kv_heads"], s["hd"]
    pos = jnp.arange(L)
    q = (h @ w["wq"].astype(F32)).reshape(Bn, L, nh, hd)
    k = (h @ w["wk"].astype(F32)).reshape(Bn, L, kvh, hd)
    v = (h @ w["wv"].astype(F32)).reshape(Bn, L, kvh, hd)
    k, v = (jnp.repeat(m, nh // kvh, axis=2) for m in (k, v))
    scores = jnp.einsum("bihd,bjhd->bhij", q, k) / math.sqrt(hd)
    scores = jnp.where((pos[None, :] <= pos[:, None])[None, None],
                       scores, -jnp.inf)
    out = jnp.einsum("bhij,bjhd->bihd", jax.nn.softmax(scores, axis=-1), v)
    return out.reshape(Bn, L, nh * hd) @ w["wo"].astype(F32)


def relu2_mlp(x, w1, w2):
    up = jax.nn.relu(x @ w1)
    return (up * up) @ w2


def route(h, w, s):
    """Gates [..., E] over the source's experts, mass on each token's
    chosen ones."""
    scores = jax.nn.sigmoid(h @ w["w_router"].astype(F32))
    _, idx = jax.lax.top_k(scores + w["router_bias"], s["k"])
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    chosen = chosen / jnp.sum(chosen, axis=-1, keepdims=True) * s["scale"]
    return jnp.sum(
        jax.nn.one_hot(idx, s["E"], dtype=F32) * chosen[..., None], axis=-2)


def routed_part(h, w, s):
    """The weighted sum, in the latent, over those of each token's experts
    that are held here: ``w``'s stacked matrices are experts ``[s["first"],
    s["first"] + s["held"])``, one computed at a time."""
    gates = route(h, w, s)[..., s["first"] : s["first"] + s["held"]]
    lat = h @ w["w_dn"].astype(F32)

    def one(out, e):
        y = relu2_mlp(lat, w["w1"][e].astype(F32), w["w2"][e].astype(F32))
        g = jax.lax.dynamic_index_in_dim(gates, e, axis=-1, keepdims=True)
        return out + g * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(lat), jnp.arange(s["held"]))
    return out


def expert_layer(h, w, s):
    """The routed part back through ``W_up``, plus the shared expert on
    the whole row."""
    return routed_part(h, w, s) @ w["w_up"].astype(F32) + relu2_mlp(
        h, w["u1"].astype(F32), w["u2"].astype(F32))


@partial(jax.jit, static_argnames=("items", "li", "dtype"))
def _layer(key, x, items, li, dtype):
    s = dict(items)
    w = layer_weights(key, s, li, jnp.dtype(dtype))
    h = rms_norm(x, s["eps"])
    letter = s["pattern"][li]
    if letter == "M":
        return x + mamba_mixer(h, w, s)
    if letter == "*":
        return x + attention_mixer(h, w, s)
    return x + expert_layer(h, w, s)


@partial(jax.jit, static_argnames=("items", "dtype"))
def _embed(key, tokens, items, dtype):
    s = dict(items)
    table = jax.random.normal(key, (s["V"], s["D"]), F32).astype(
        jnp.dtype(dtype))                            # deviation 1
    return table[tokens].astype(F32)


@partial(jax.jit, static_argnames=("items", "dtype"))
def _head(key, x, items, dtype):
    s = dict(items)
    w = _draw(key, (s["D"], s["V"]), s["D"], jnp.dtype(dtype)).astype(F32)
    if s.get("lowered") == "int8_weights":
        w = _int8_rounded(w)
    return rms_norm(x, s["eps"]) @ w


def logits(cfg: dict, seed: int, tokens, rows, dtype: str = "bfloat16", *,
           source_values: dict | None = None, share: dict | None = None,
           lowered: str | None = None):
    """Float32 logits ``[B, R, V]`` at positions ``rows`` [B, R] of ONE
    full forward pass over ``tokens`` [B, L] (right-padded: causal layers,
    so padding is never seen). ``cfg`` is a configuration's ``published``
    block; ``source_values`` and ``share`` say which experts of the
    source's are held here; ``lowered`` one of ``LOWERED`` for a control."""
    if lowered not in (None, *LOWERED):
        raise ValueError(f"lowered {lowered!r}: one of {LOWERED}")
    if cfg.get("tie_word_embeddings"):
        raise NotImplementedError("tied embeddings are not in this family")
    if (cfg.get("n_group") or 1) > 1:
        raise NotImplementedError("router groups are not in this family")
    s = {**sizes(cfg, source_values, share), "lowered": lowered}
    items = tuple(sorted(s.items()))
    layer_keys, ek, hk = model_keys(seed, s["L"])
    with jax.default_matmul_precision("highest"):
        x = _embed(ek, jnp.asarray(tokens), items, dtype)
        for li in range(s["L"]):
            x = _layer(layer_keys[li], x, items, li, dtype)
        picked = jnp.take_along_axis(
            x, jnp.asarray(rows)[:, :, None], axis=1)
        return _head(hk, picked, items, dtype)
