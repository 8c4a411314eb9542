"""Weight-only int8 quantization for the serving path.

Decode is weight-streaming-bound: every step reads the full parameter set
from HBM, so bytes/param is the throughput ceiling. Storing the big matmul weights as
int8 with per-output-channel symmetric scales halves the streamed bytes;
XLA fuses the int8→bf16 convert into the matmul operand read, so the MXU
still runs a bf16 contraction and nothing extra round-trips through HBM.

This is the TPU-idiomatic analogue of the reference's quantized serving
configs (its headline disagg numbers run FP8 via vLLM/TRT-LLM backends,
reference: docs/architecture/architecture.md:75-79 "70B FP8"; the engines
own quantization there — here the engine is native, so we own it).

Representation: a quantized weight is a pytree dict ``{"q": int8[..., in,
out], "s": f32 scales}`` where ``s`` is the weight's shape with the
contraction (``in``) axis removed — [out] for 2-D, [E, out] for stacked
MoE experts. Every consumer goes through :func:`qmm` (or reads ``q``/``s``
directly for the MoE einsums), so plain bf16 arrays and quantized dicts
are interchangeable throughout models/llama.py.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

Params = dict[str, Any]

# Weights eligible for quantization: the large matmul operands. Norm gains,
# biases, the router (tiny, routing-accuracy-critical), and the embedding
# table (a gather, not a matmul; also the tied lm_head) stay bf16.
# MLA (models/llama.py): all 2-D projections plus the per-head absorbed
# w_uk/w_uv; DeepSeekMoE shared experts stream every step, so they
# quantize too. w_dq/ln inputs are small but on the per-step path.
QUANT_KEYS = (
    "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", "lm_head",
    "w_dq", "w_uq", "w_dkv", "w_uk", "w_uv",
    "w_shared_gate", "w_shared_up", "w_shared_down",
)

# Per-matmul policy sites (models/llama.py WeightQuantPolicy): the attn
# group is every attention projection (GQA qkv+o and the MLA ladder);
# the mlp group is the SwiGLU / expert matrices (the router stays full
# precision — tiny and routing-accuracy-critical). Embedding and unembed
# are handled by name (``embed``/``lm_head``) in the policy functions.
ATTN_KEYS = (
    "wq", "wk", "wv", "wo", "w_dq", "w_uq", "w_dkv", "w_uk", "w_uv",
)
MLP_KEYS = (
    "w_gate", "w_up", "w_down",
    "w_shared_gate", "w_shared_up", "w_shared_down",
)

# fp8 weight storage (the other precision the policy can select):
# e4m3 with per-output-channel scales — same dict representation, same
# qdot arithmetic (q converts on the matmul operand), so every consumer
# is format-agnostic. Gated: older jax builds may lack the dtype.
FP8_DTYPE = getattr(jnp, "float8_e4m3fn", None)
FP8_MAX = 448.0
WEIGHT_FORMATS = ("int8", "fp8")

CONTRACT_AXIS = -2  # our weight layout is [..., in, out]

#: per-key contraction-axis overrides: w_uv [H, v, dc] contracts its LAST
#: axis (the latent) in _mla_out's einsum, so scales are per (head, v-dim).
QUANT_AXES = {"w_uv": -1}


def is_quantized(w) -> bool:
    return isinstance(w, dict) and "q" in w and "s" in w


def quantize_weight(
    w: jnp.ndarray, axis: int = CONTRACT_AXIS, fmt: str = "int8"
) -> Params:
    """Symmetric per-output-channel quantization over the contraction axis.

    ``fmt="int8"`` (default): ``q = round(w / s)`` with ``s = amax|w| /
    127`` per out column, so the reconstruction ``q * s`` has <1%
    per-element error and exact zero preservation (symmetric, no zero
    point — the MXU-friendly choice). ``fmt="fp8"``: e4m3 storage with
    ``s = amax|w| / 448`` (rounding is the dtype cast's). Scales keep the
    weight's dtype so dequantized values land back in the model's
    compute dtype.
    """
    wf = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(wf), axis=axis)
    if fmt == "fp8":
        if FP8_DTYPE is None:
            raise ValueError(
                "fp8 weight quantization requires a jax build with "
                "float8_e4m3fn — use fmt='int8' on this install"
            )
        s = jnp.maximum(amax, 1e-8) / FP8_MAX
        q = (wf / jnp.expand_dims(s, axis)).astype(FP8_DTYPE)
        return {"q": q, "s": s.astype(w.dtype)}
    if fmt != "int8":
        raise ValueError(f"unknown weight format {fmt!r} (use {WEIGHT_FORMATS})")
    s = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.round(wf / jnp.expand_dims(s, axis))
    q = jnp.clip(q, -127, 127).astype(jnp.int8)
    return {"q": q, "s": s.astype(w.dtype)}


def dequantize_weight(
    w: Params, dtype=jnp.float32, axis: int = CONTRACT_AXIS
) -> jnp.ndarray:
    """Invert quantize_weight; pass the same `axis` it was quantized with
    (axis=-1 for per-row tables like the tied embedding)."""
    return (
        w["q"].astype(jnp.float32) * jnp.expand_dims(w["s"], axis)
    ).astype(dtype)


def qmm(x: jnp.ndarray, w) -> jnp.ndarray:
    """``x @ w`` for a plain array or a quantized dict.

    The int8→x.dtype convert sits directly on the matmul operand so XLA
    fuses it into the contraction's operand read: int8 bytes stream from
    HBM, bf16 math runs on the MXU, and the per-column scale multiplies
    the [.., out] result (post-psum under a row-sharded contraction).
    """
    if not is_quantized(w):
        return x @ w
    return (x @ w["q"].astype(x.dtype)) * w["s"].astype(x.dtype)


def qdot(x: jnp.ndarray, w) -> jnp.ndarray:
    """The dequantize-in-register dot — the one arithmetic contract every
    matmul site on the unified path runs (docs/architecture/
    weight_quant.md "zero new programs"):

    - quantized ``w``: the stored values convert to ``x.dtype`` ON the
      contraction operand (int8/fp8 bytes stream from HBM, the convert
      fuses into the operand read — in-register, never a dequantized
      copy back in HBM) and the per-output-channel scale multiplies the
      result. This IS the XLA twin: tests assert kernel-vs-oracle parity
      as an EXACT contract (same association, bit-identical on CPU), not
      a tolerance.
    - plain ``w``: ``x @ w`` — so policy-off sites compile the very same
      call graph and the budget-ladder program set is unchanged.
    """
    return qmm(x, w)


def qeinsum(pattern: str, x: jnp.ndarray, w) -> jnp.ndarray:
    """Einsum against a possibly-quantized weight whose scale tree was
    built with the weight's contraction axis removed AND whose remaining
    axes appear, in order, as the trailing output axes (true for the MLA
    per-head einsums "thn,hnc->thc" and "...hc,hvc->...hv") — so the
    scale broadcasts onto the result directly."""
    if not is_quantized(w):
        return jnp.einsum(pattern, x, w)
    out = jnp.einsum(pattern, x, w["q"].astype(x.dtype))
    return out * w["s"].astype(x.dtype)


def embed_lookup(embed, token_ids: jnp.ndarray) -> jnp.ndarray:
    """Embedding-table row gather, plain or per-row-quantized."""
    if not is_quantized(embed):
        return embed[token_ids]
    return embed["q"][token_ids].astype(embed["s"].dtype) * (
        embed["s"][token_ids][..., None]
    )


def tied_head_mm(h: jnp.ndarray, embed) -> jnp.ndarray:
    """``h @ embed.T`` (tied lm_head) for a plain or quantized table.

    A per-ROW (vocab) scaled int8 table serves both the gather above and
    this contraction: rows are this matmul's output channels, so the
    scale multiplies the [.., V] logits — the whole table streams int8
    on every decode step (it is the single largest weight in small tied
    models, e.g. 40% of Llama-3.2-1B's bytes)."""
    if not is_quantized(embed):
        return h @ embed.T
    return (h @ embed["q"].T.astype(h.dtype)) * embed["s"].astype(h.dtype)


def quantize_params(
    params: Params,
    include_lm_head: bool = True,
    tie_embed: bool = False,
) -> Params:
    """Quantize the big matmul weights of a models/llama.py params tree.

    Leaves norms, biases, and the router untouched. With ``tie_embed``
    (tie_word_embeddings models) the embedding table quantizes too with
    per-ROW scales — it doubles as the lm_head matmul operand, so it
    streams every decode step (see tied_head_mm). Jit-friendly: callers
    wrap in jit with quantized out_shardings to quantize directly into a
    sharded layout (engine/runner.py does).
    """
    out: Params = {k: v for k, v in params.items()}
    layers = []
    for layer in params["layers"]:
        qlayer = dict(layer)
        for k in QUANT_KEYS:
            if k in qlayer and k != "lm_head":
                qlayer[k] = quantize_weight(
                    qlayer[k], axis=QUANT_AXES.get(k, CONTRACT_AXIS)
                )
        layers.append(qlayer)
    out["layers"] = layers
    if include_lm_head and "lm_head" in params:
        out["lm_head"] = quantize_weight(params["lm_head"])
    if tie_embed:
        out["embed"] = quantize_weight(params["embed"], axis=-1)
    return out


# ---------------------------------------------------------------------------
# Per-matmul weight-quant policy (docs/architecture/weight_quant.md).
#
# The policy object is duck-typed (models/llama.py WeightQuantPolicy):
# four attributes — ``embedding``, ``attn``, ``mlp``, ``unembed`` — each
# None (full precision) or a WEIGHT_FORMATS entry. The functions below
# are the single mapping from policy sites to param-tree keys, shared by
# quantize-on-load, random init, and the mesh sharding-spec transform,
# so the three can't drift.
# ---------------------------------------------------------------------------


def policy_layer_fmts(policy) -> dict[str, str]:
    """Per-LAYER param key → storage format under ``policy`` (the attn
    and mlp sites; embedding/unembed are top-level, see
    quantize_params_policy)."""
    fmts: dict[str, str] = {}
    if getattr(policy, "attn", None):
        fmts.update({k: policy.attn for k in ATTN_KEYS})
    if getattr(policy, "mlp", None):
        fmts.update({k: policy.mlp for k in MLP_KEYS})
    return fmts


def quantize_params_policy(
    params: Params, policy, tie_embed: bool = False
) -> Params:
    """quantize_params with per-matmul site selection.

    The embedding table quantizes with per-ROW scales (it is a gather;
    when tied it doubles as the unembed matmul operand, so a tied model
    with ``unembed`` set quantizes it even if ``embedding`` is None —
    otherwise the unembed selection would silently be a no-op).
    Jit-friendly like quantize_params: the runner jits this with the
    policy spec tree as out_shardings so the bf16 copy never
    materializes resident beside the quantized one.
    """
    fmts = policy_layer_fmts(policy)
    out: Params = {k: v for k, v in params.items()}
    layers = []
    for layer in params["layers"]:
        qlayer = dict(layer)
        for k, fmt in fmts.items():
            if k in qlayer:
                qlayer[k] = quantize_weight(
                    qlayer[k], axis=QUANT_AXES.get(k, CONTRACT_AXIS), fmt=fmt
                )
        layers.append(qlayer)
    out["layers"] = layers
    unembed = getattr(policy, "unembed", None)
    if unembed and "lm_head" in params:
        out["lm_head"] = quantize_weight(params["lm_head"], fmt=unembed)
    embed_fmt = getattr(policy, "embedding", None) or (
        unembed if tie_embed else None
    )
    if embed_fmt:
        out["embed"] = quantize_weight(params["embed"], axis=-1, fmt=embed_fmt)
    return out


def quantize_param_specs_policy(
    specs: Params, policy, tie_embed: bool = False
) -> Params:
    """Mirror quantize_params_policy on a llama_param_specs tree: ``q``
    keeps the matrix's spec, ``s`` drops the contraction axis (per-row
    tables follow the vocab axis) — scales shard exactly like the
    matrices they scale, minus the reduced dimension."""
    fmts = policy_layer_fmts(policy)
    out: Params = {k: v for k, v in specs.items()}
    layers = []
    for layer in specs["layers"]:
        qlayer = dict(layer)
        for k in fmts:
            if k in qlayer:
                qlayer[k] = quant_spec(
                    qlayer[k], axis=QUANT_AXES.get(k, CONTRACT_AXIS)
                )
        layers.append(qlayer)
    out["layers"] = layers
    unembed = getattr(policy, "unembed", None)
    if unembed and "lm_head" in specs:
        out["lm_head"] = quant_spec(specs["lm_head"])
    embed_fmt = getattr(policy, "embedding", None) or (
        unembed if tie_embed else None
    )
    if embed_fmt:
        spec = specs["embed"]
        out["embed"] = {"q": spec, "s": P(spec[0])}
    return out


def quant_tree_stats(params: Params, dtype_bytes: int = 2) -> tuple[float, float]:
    """(bytes_saved, density) of a possibly-quantized params tree:
    resident bytes saved vs storing every parameter at ``dtype_bytes``,
    and the fraction of parameters stored quantized. Shape/dtype math
    only — works on ShapeDtypeStructs and never touches device data, so
    the runner can publish the gauges without a transfer."""
    total = 0
    qcount = 0
    saved = 0.0
    for leaf in jax.tree.leaves(params, is_leaf=is_quantized):
        if is_quantized(leaf):
            n = int(leaf["q"].size)
            stored = (
                n * jnp.dtype(leaf["q"].dtype).itemsize
                + int(leaf["s"].size) * jnp.dtype(leaf["s"].dtype).itemsize
            )
            saved += n * dtype_bytes - stored
            qcount += n
            total += n
        else:
            total += int(leaf.size)
    return saved, (qcount / total if total else 0.0)


# ---------------------------------------------------------------------------
# KV-cache block quantization (docs/architecture/kv_quant.md).
#
# Decode is HBM-bandwidth-bound (282.8 GB/s effective — older harness, not reproduced),
# so int8 KV blocks roughly double effective decode bandwidth AND double
# KV capacity per chip. The cache keeps its [num_slots, kvH, D] layout but
# stores int8; a per-(block, kv-head) float32 scale rides alongside the
# block-table metadata (``kv_scales: [num_layers, 2, num_blocks, kvH]``).
# Reads dequantize ``int8 * scale`` — in-register inside the Pallas ragged
# kernel, as a gathered multiply in the XLA oracle — with IDENTICAL
# arithmetic, so kernel-vs-oracle parity is exact-contract.
#
# Write law (shared by every dispatch path, so both attention twins see
# the same cache bytes):
#   - a step's new K/V values scatter-max a per-(block, head) amax;
#   - a block whose FIRST slot is written this step is FRESH: its stale
#     scale (from a previous occupant of the physical block) resets, so
#     scales never ratchet up across allocator reuse;
#   - the block scale only GROWS within an occupancy:
#     new_scale = max(old_scale, amax/127). When it grows, the block's
#     EXISTING int8 entries requantize by round(q * old/new) — touched
#     blocks only, so the per-step cost is O(batch · block_size), never
#     O(cache);
#   - new values quantize at the new scale: clip(round(v/new_scale)).
# ---------------------------------------------------------------------------

KV_SCALE_DTYPE = jnp.float32


def quantize_kv_write(
    cache: jnp.ndarray,     # [num_slots, kvH, D] int8
    scales: jnp.ndarray,    # [num_blocks, kvH] float32
    slots: jnp.ndarray,     # [T] int32 — target slot per new token
    vals: jnp.ndarray,      # [T, kvH, D] float — new K or V values
    block_size: int,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Scatter new K/V values into an int8 cache under per-block scales.

    Returns (new_cache, new_scales). Padding rows aimed at trash block 0
    churn only block 0's scale, which is never read as real KV (every
    mask excludes it). Deterministic under duplicate touched blocks: all
    duplicates compute identical requantized rows, so scatter order
    cannot change the result.
    """
    num_blocks = scales.shape[0]
    bs = block_size
    vf = vals.astype(jnp.float32)
    blk = slots // bs                                       # [T]

    # Per-(touched block, head) amax of the NEW values.
    amax = jnp.zeros((num_blocks, scales.shape[1]), jnp.float32)
    amax = amax.at[blk].max(jnp.abs(vf).max(axis=-1))       # [nb, kvH]

    # Fresh-block detection: writing a block's first slot starts a new
    # occupancy — the stale scale from the physical block's previous
    # tenant must not survive into it.
    fresh = jnp.zeros((num_blocks,), bool).at[blk].max(slots % bs == 0)
    old = jnp.where(fresh[:, None], 0.0, scales)
    new_scales = jnp.maximum(old, amax / 127.0)             # [nb, kvH]

    # Requantize the touched blocks' existing entries where the scale
    # grew. Gather/rescale/scatter is bounded by the batch (T*bs slots),
    # not the cache; duplicate blocks write identical values.
    ratio = jnp.where(new_scales > 0, old / jnp.maximum(new_scales, 1e-30), 1.0)
    tslots = (blk[:, None] * bs + jnp.arange(bs)[None, :]).reshape(-1)
    rows = cache[tslots].astype(jnp.float32)                # [T*bs, kvH, D]
    rq = jnp.clip(
        jnp.round(rows * jnp.repeat(ratio[blk], bs, axis=0)[:, :, None]),
        -127, 127,
    ).astype(jnp.int8)
    cache = cache.at[tslots].set(rq)

    # Quantize and write the new tokens at the (possibly grown) scale.
    s_at = new_scales[blk]                                  # [T, kvH]
    q = jnp.clip(
        jnp.round(vf / jnp.maximum(s_at, 1e-30)[:, :, None]), -127, 127
    ).astype(jnp.int8)
    q = jnp.where((s_at > 0)[:, :, None], q, 0)
    # Untouched blocks: amax 0, fresh False => new_scales == scales
    # already; no masking needed.
    return cache.at[slots].set(q), new_scales


def quantize_kv_block_host(
    data: "object", num_kv_heads: int, head_dim: int
):
    """Host-side block quantization for the KVBM tiers: ``data`` is one
    block's values [..., kvH, D] float (any leading dims — typically
    [L, 2, bs, H, D]); scales are per (leading-dims-without-bs, head),
    i.e. amax over (block_size, head_dim). Returns (int8 array, float32
    scales shaped data.shape[:-3] + (kvH,)). numpy-only (pump thread)."""
    import numpy as np

    arr = np.asarray(data, np.float32)
    # amax over the block_size and head_dim axes -> [..., kvH]
    amax = np.abs(arr).max(axis=(-3, -1))
    s = amax / 127.0
    with np.errstate(divide="ignore", invalid="ignore"):
        q = np.where(
            s[..., None, :, None] > 0,
            np.clip(
                np.round(arr / np.maximum(s[..., None, :, None], 1e-30)),
                -127, 127,
            ),
            0.0,
        )
    return q.astype(np.int8), s.astype(np.float32)


def dequantize_kv_block_host(q, scales):
    """Invert quantize_kv_block_host: int8 [..., bs, kvH, D] * scales
    [..., kvH] -> float32 values."""
    import numpy as np

    return np.asarray(q, np.float32) * np.asarray(scales, np.float32)[
        ..., None, :, None
    ]


def quant_spec(spec: P, axis: int = CONTRACT_AXIS) -> Params:
    """Spec pytree for one quantized weight given its bf16 spec.

    ``q`` shards exactly like the original weight; ``s`` drops the
    contraction axis (e.g. wq P(None, "tp") → s P("tp"); wo P("tp", None)
    → s P(); MoE w_gate P("ep", None, "tp") → s P("ep", "tp")).
    """
    axes = list(spec)
    i = len(axes) + axis if axis < 0 else axis
    s_axes = axes[:i] + axes[i + 1 :]
    return {"q": spec, "s": P(*s_axes)}


def quantize_param_specs(
    specs: Params,
    include_lm_head: bool = True,
    tie_embed: bool = False,
) -> Params:
    """Transform a llama_param_specs tree to mirror quantize_params."""
    out: Params = {k: v for k, v in specs.items()}
    layers = []
    for layer in specs["layers"]:
        qlayer = dict(layer)
        for k in QUANT_KEYS:
            if k in qlayer and k != "lm_head":
                qlayer[k] = quant_spec(
                    qlayer[k], axis=QUANT_AXES.get(k, CONTRACT_AXIS)
                )
        layers.append(qlayer)
    out["layers"] = layers
    if include_lm_head and "lm_head" in specs:
        out["lm_head"] = quant_spec(specs["lm_head"])
    if tie_embed:
        # [V, D] with per-row (V) scales: q keeps the table's spec; s
        # follows the vocab axis (unsharded under our feature-sharded
        # embed, parallel/sharding.py).
        spec = specs["embed"]
        out["embed"] = {"q": spec, "s": P(spec[0])}
    return out


def init_params_policy(key, cfg, policy, dtype=jnp.bfloat16):
    """Random-init DIRECTLY into the quantized serving format selected by
    ``policy``, one layer at a time, so the full-precision transient
    never exceeds a single layer — an 8B model (16 GB bf16) can
    therefore init on a 16 GB chip whose steady-state int8 footprint is
    ~8 GB. Weight-IDENTICAL to llama.init_params →
    quantize_params_policy (same lk/ek/hk per-layer key split) —
    tests assert the single-chip and mesh paths produce equal greedy
    tokens, so key consumption here and in init_params must stay in
    lockstep."""
    import functools

    from dynamo_tpu.models import llama

    fmts = policy_layer_fmts(policy)

    @functools.partial(jax.jit, static_argnums=(1,))
    def one_layer(k, li_repr):
        p = llama.init_layer_params(k, cfg, li_repr, dtype)
        return {
            name: (
                quantize_weight(
                    w,
                    axis=QUANT_AXES.get(name, CONTRACT_AXIS),
                    fmt=fmts[name],
                )
                if name in fmts
                else w
            )
            for name, w in p.items()
        }

    # One compile per layer KIND (dense vs MoE, softmax vs linear
    # attention), not per layer index — the index only matters through
    # cfg.moe_layer(li) and cfg.layer_kind(li).
    def kind(i):
        return cfg.moe_layer(i), cfg.layer_kind(i)

    kind_repr = {}
    for i in range(cfg.num_layers):
        kind_repr.setdefault(kind(i), i)
    lk, ek, hk = jax.random.split(key, 3)
    layer_keys = jax.random.split(lk, cfg.num_layers)
    layers = []
    for li in range(cfg.num_layers):
        layer = one_layer(layer_keys[li], kind_repr[kind(li)])
        jax.block_until_ready(jax.tree.leaves(layer)[0])
        layers.append(layer)

    D, V = cfg.hidden_size, cfg.vocab_size
    unembed = getattr(policy, "unembed", None)
    embed_fmt = getattr(policy, "embedding", None) or (
        unembed if cfg.tie_word_embeddings else None
    )
    if embed_fmt:
        embed = jax.jit(
            lambda k: quantize_weight(
                llama._embed_init(k, cfg, dtype), axis=-1, fmt=embed_fmt
            )
        )(ek)
    else:
        embed = jax.jit(lambda k: llama._embed_init(k, cfg, dtype))(ek)
    params = {
        "embed": embed,
        "layers": layers,
        "ln_f": jnp.ones((D,), dtype),
    }
    if not cfg.tie_word_embeddings:
        if unembed:
            params["lm_head"] = jax.jit(
                lambda k: quantize_weight(
                    llama._dense_init(k, (D, V), dtype), fmt=unembed
                )
            )(hk)
        else:
            params["lm_head"] = jax.jit(
                lambda k: llama._dense_init(k, (D, V), dtype)
            )(hk)
    return params


def init_params_int8(key, cfg, dtype=jnp.bfloat16):
    """Legacy whole-model int8 init (EngineConfig.quant="int8"): the
    all-sites policy minus the embedding gather (per-row embed only when
    tied, where the table doubles as the unembed operand)."""
    from types import SimpleNamespace

    policy = SimpleNamespace(
        embedding=None, attn="int8", mlp="int8", unembed="int8"
    )
    return init_params_policy(key, cfg, policy, dtype)
