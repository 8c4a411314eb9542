"""Plain reference of the SDAR family (``model_type: sdar_moe``;
SDAR-30B-A3B-Chat): Qwen3-MoE decoder layers under a **block-causal** mask,
generated by block diffusion.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision:
one full forward pass over the whole sequence, no cache, no kernels, no
grouped products, nothing imported from ``dynamo_tpu``. The layer, as the
family's ``modeling_sdar_moe`` has it (the Qwen3-MoE layer):

1. ``a = RMSNorm(h)``; ``q = a Wq`` [T, H, hd], ``k = a Wk``, ``v = a Wv``
   [T, KV, hd], no bias; ``q`` and ``k`` through a per-head RMSNorm over
   ``hd`` (learned weight, eps as the model's); half-rotation RoPE.
2. scores ``q k^T / sqrt(hd)``, ``H / KV`` query heads a key head; **key
   ``j`` is visible to query ``i`` iff ``j // B <= i // B``** (``B`` the
   block length: all earlier blocks and the query's own block, both ways);
   ``h += softmax(.) v Wo``.
3. ``m = RMSNorm(h)``; ``g = softmax(m Wr)`` over all experts, the ``k``
   largest divided by their sum (``norm_topk_prob``);
   ``h += sum_e g_e W2_e(silu(W1_e m) * W3_e m)``. Every layer is sparse
   (``decoder_sparse_step`` 1, ``mlp_only_layers`` []), there is no shared
   expert, and ``intermediate_size`` is carried unused.
4. final RMSNorm and an untied head. **No shift**: the logits at a
   position are for the token AT that position, so a position that holds
   the mask id is predicted from its own row.

Departures, each noted: the per-head q/k norms have no key in the
``config.json`` (the family's code has them; they are always on here);
every expert is computed for every token and weighted 0 where not chosen
(the same sum, in another order).

``generate`` is the family's generation loop in its plainest form, for the
CPU tests: no cache, one full pass a denoising step.

Weights are taken from the seed and from nothing the program made: the
served path draws them with ``jax.random`` from ``PRNGKey(seed)`` (normal /
sqrt(fan_in), cast to the served dtype; norm weights ones) and
``layer_weights`` repeats that draw in the same order, one layer at a time.

A configuration is the ``published`` block of its file (HF key names); the
block length comes with the call (``block_length``), from the file's
``check.step_params`` or ``assumed``.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32

#: the family's block length where a caller gives none (the configuration
#: files state it under ``assumed``)
BLOCK_LENGTH = 4


def sizes(cfg: dict) -> dict:
    heads = cfg["num_attention_heads"]
    return {
        "D": cfg["hidden_size"],
        "I": cfg["moe_intermediate_size"],
        "L": cfg["num_hidden_layers"],
        "H": heads,
        "KV": cfg["num_key_value_heads"],
        "hd": cfg.get("head_dim") or cfg["hidden_size"] // heads,
        "V": cfg["vocab_size"],
        "E": cfg["num_experts"],
        "k": cfg["num_experts_per_tok"],
        "renorm": bool(cfg.get("norm_topk_prob", True)),
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
    served path's order: q, k, v, o, router, the experts' gate, up, down.
    The norms (layer and per-head) are ones and take no key."""
    s = sizes(cfg)
    D, I, H, KV, hd, E = s["D"], s["I"], s["H"], s["KV"], s["hd"], s["E"]
    keys = iter(jax.random.split(key, 16))
    return {
        "wq": _draw(next(keys), (D, H * hd), D, dtype),
        "wk": _draw(next(keys), (D, KV * hd), D, dtype),
        "wv": _draw(next(keys), (D, KV * hd), D, dtype),
        "wo": _draw(next(keys), (H * hd, D), H * hd, dtype),
        "w_router": _draw(next(keys), (D, E), D, dtype),
        "w_gate": _draw(next(keys), (E, D, I), D, dtype),
        "w_up": _draw(next(keys), (E, D, I), D, dtype),
        "w_down": _draw(next(keys), (E, I, D), I, dtype),
    }


def rms_norm(x, eps):
    """RMSNorm with a weight of ones (what a seeded model holds)."""
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def rope(x, positions, theta):
    """Half-rotation RoPE: x [..., L, heads, hd], positions [L]."""
    hd = x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=F32) / hd)        # [hd/2]
    ang = positions.astype(F32)[:, None] * inv[None, :]           # [L, hd/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(x, w, s, block: int):
    """x [B, L, D] -> [B, L, D] under the mask by block: key j is visible
    to query i iff ``j // block <= i // block``."""
    B, L, _ = x.shape
    H, KV, hd = s["H"], s["KV"], s["hd"]
    pos = jnp.arange(L)
    q = (x @ w["wq"].astype(F32)).reshape(B, L, H, hd)
    k = (x @ w["wk"].astype(F32)).reshape(B, L, KV, hd)
    v = (x @ w["wv"].astype(F32)).reshape(B, L, KV, hd)
    q = rope(rms_norm(q, s["eps"]), pos, s["theta"])
    k = rope(rms_norm(k, s["eps"]), pos, s["theta"])
    k = jnp.repeat(k, H // KV, axis=2)
    v = jnp.repeat(v, H // KV, axis=2)
    scores = jnp.einsum("bihd,bjhd->bhij", q, k) / math.sqrt(hd)
    seen = pos[None, :] // block <= pos[:, None] // block
    scores = jnp.where(seen[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhij,bjhd->bihd", probs, v).reshape(B, L, H * hd)
    return out @ w["wo"].astype(F32)


def experts(x, w, s):
    """The ``k`` largest of a softmax over all experts, divided by their
    sum; every expert computed for every token, one at a time (so one
    expert's matrices at a time are resident in float32), and weighted by
    its gate, 0 where it was not chosen."""
    probs = jax.nn.softmax(x @ w["w_router"].astype(F32), axis=-1)  # [B, L, E]
    top, idx = jax.lax.top_k(probs, s["k"])
    if s["renorm"]:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    gates = jnp.sum(
        jax.nn.one_hot(idx, s["E"], dtype=F32) * top[..., None], axis=-2
    )                                                               # [B, L, E]

    def one(out, e):
        gate, up, down = (w[n][e].astype(F32) for n in ("w_gate", "w_up", "w_down"))
        y = (jax.nn.silu(x @ gate) * (x @ up)) @ down
        g = jax.lax.dynamic_index_in_dim(gates, e, axis=-1, keepdims=True)
        return out + g * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(x), jnp.arange(s["E"]))
    return out


@partial(jax.jit, static_argnames=("cfg_items", "dtype", "block"))
def _layer(key, x, cfg_items, dtype, block):
    cfg = dict(cfg_items)
    s = sizes(cfg)
    w = layer_weights(key, cfg, jnp.dtype(dtype))
    x = x + attention(rms_norm(x, s["eps"]), w, s, block)
    return x + experts(rms_norm(x, s["eps"]), w, s)


@partial(jax.jit, static_argnames=("cfg_items", "dtype"))
def _embed(key, tokens, cfg_items, dtype):
    s = sizes(dict(cfg_items))
    table = _draw(key, (s["V"], s["D"]), s["V"], jnp.dtype(dtype))
    return table[tokens].astype(F32)


@partial(jax.jit, static_argnames=("cfg_items", "dtype"))
def _head(key, x, cfg_items, dtype):
    s = sizes(dict(cfg_items))
    w = _draw(key, (s["D"], s["V"]), s["D"], jnp.dtype(dtype)).astype(F32)
    return rms_norm(x, s["eps"]) @ w


def _hashable(cfg: dict) -> tuple:
    keep = (
        "hidden_size", "moe_intermediate_size", "num_hidden_layers",
        "num_attention_heads", "num_key_value_heads", "head_dim",
        "vocab_size", "num_experts", "num_experts_per_tok", "norm_topk_prob",
        "rope_theta", "rms_norm_eps",
    )
    return tuple((k, cfg[k]) for k in keep if cfg.get(k) is not None)


def logits(cfg: dict, seed: int, tokens, rows, dtype: str = "bfloat16", *,
           block_length: int = BLOCK_LENGTH):
    """Float32 logits ``[B, R, V]`` at positions ``rows`` [B, R] of ONE
    full forward pass over ``tokens`` [B, L] under the mask by block
    (right-padded to whole blocks: padding lies in later blocks, which no
    earlier position sees). Unshifted: row ``p`` scores the token at
    ``p``."""
    if cfg.get("tie_word_embeddings"):
        raise NotImplementedError("tied embeddings are not in this family")
    if cfg.get("mlp_only_layers") or cfg.get("decoder_sparse_step", 1) != 1:
        raise NotImplementedError("a dense layer is not in this family")
    items = _hashable(cfg)
    layer_keys, ek, hk = model_keys(seed, cfg["num_hidden_layers"])
    with jax.default_matmul_precision("highest"):
        x = _embed(ek, jnp.asarray(tokens), items, dtype)
        for li in range(cfg["num_hidden_layers"]):
            x = _layer(layer_keys[li], x, items, dtype, int(block_length))
        picked = jnp.take_along_axis(
            x, jnp.asarray(rows)[:, :, None], axis=1
        )
        return _head(hk, picked, items, dtype)


def generate(cfg: dict, seed: int, prompt, max_tokens: int,
             dtype: str = "float32", *, block_length: int = BLOCK_LENGTH,
             denoising_steps: int = 4, mask_token_id: int,
             confidence_threshold: float = 0.9) -> list[int]:
    """Greedy block-diffusion generation in its plainest form: ``ceil``
    whole blocks after the prompt's whole blocks, exactly ``max_tokens``
    returned. The prompt's last ``P mod B`` tokens open the first block,
    the rest of which is mask ids. A denoising pass is one full forward
    pass over everything committed plus the block; each still-masked row
    takes its argmax with that token's probability as confidence, and the
    pass commits every masked row at or over the threshold and at least
    ``ceil(B / denoising_steps)`` of the most confident (ties to the lower
    position). (The served path also runs a commit pass a block, which
    only writes its cache: there is none here.)"""
    B = int(block_length)
    floor_rows = -(-B // int(denoising_steps))
    seq = [int(t) for t in prompt]
    want = len(seq) + int(max_tokens)
    pad_to = -(-want // B) * B
    while len(seq) < want:
        start = len(seq) - len(seq) % B
        block = seq[start:] + [None] * (B - (len(seq) - start))
        seq = seq[:start]
        while None in block:
            fed = [mask_token_id if t is None else t for t in block]
            tokens = np.zeros((1, pad_to), np.int32)
            tokens[0, : start + B] = seq + fed
            rows = np.arange(start, start + B, dtype=np.int32)[None]
            out = np.asarray(logits(
                cfg, seed, tokens, rows, dtype, block_length=B
            ))[0].astype(np.float64)
            tok = out.argmax(-1)
            # softmax probability of the argmax
            conf = 1.0 / np.exp(out - out.max(-1, keepdims=True)).sum(-1)
            masked = [j for j in range(B) if block[j] is None]
            best = sorted(masked, key=lambda j: (-conf[j], j))[:floor_rows]
            for j in masked:
                if conf[j] >= confidence_threshold or j in best:
                    block[j] = int(tok[j])
        seq += block
    return seq[len(prompt): want]
