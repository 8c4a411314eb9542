"""Llama-family transformer in pure JAX over a paged KV cache.

The in-process engine's model: RMSNorm + RoPE + GQA + SwiGLU, written as
plain functions over a params pytree so `jit`/`pjit` can shard it with
NamedSharding annotations (parallel/sharding.py). Weight layout is
``[in, out]`` (already transposed from torch) so the hot matmuls are plain
``x @ w`` on the MXU.

Replaces the reference's delegated engines (vLLM/mistralrs/llamacpp — e.g.
reference: lib/engines/mistralrs/src/lib.rs:48) with a TPU-native model;
covers Llama-2/3/3.x, Qwen2 (qkv_bias), and Mixtral-style sparse MoE
(num_experts > 0 — routed expert MLPs from models/moe.py, ep/tp-sharded).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from dynamo_tpu.models.config import RECURRENT_KINDS, LayerSpec, ModelConfig
from dynamo_tpu.ops.attention import (
    AttnDispatch,
    full_causal_attention,
    page_form,
)
from dynamo_tpu.ops.norms import layer_norm, rms_norm
from dynamo_tpu.ops.quant import (
    CONTRACT_AXIS,
    QUANT_AXES,
    WEIGHT_FORMATS,
    embed_lookup,
    is_quantized,
    policy_layer_fmts,
    qdot,
    qeinsum,
    quantize_weight,
    tied_head_mm,
)
from dynamo_tpu.ops.rope import apply_rope

Params = dict[str, Any]


@dataclasses.dataclass(frozen=True)
class WeightQuantPolicy:
    """Per-matmul weight-quantization policy (docs/architecture/
    weight_quant.md): each SITE — the embedding gather, the attention
    projections (qkv+o and the MLA ladder), the SwiGLU/expert matrices,
    and the unembed head — independently selects None (full precision)
    or a storage format from ops/quant.py WEIGHT_FORMATS.

    The policy is value-level, not code-level: quantized sites store
    ``{"q", "s"}`` dicts in the params tree and every matmul already
    dispatches on the VALUE through ops/quant.py ``qdot``/``qeinsum``/
    ``embed_lookup``/``tied_head_mm`` — so the forward functions compile
    the same call graph either way and the compiled program set (the
    unified budget ladder) is unchanged by any policy choice.
    """

    embedding: str | None = None
    attn: str | None = None
    mlp: str | None = None
    unembed: str | None = None

    SITES = ("embedding", "attn", "mlp", "unembed")

    @classmethod
    def from_string(cls, spec: str | None) -> "WeightQuantPolicy":
        """Parse an EngineConfig.weight_quant / ``--weight-quant`` spec:
        a bare format ("int8", "fp8") selects every site; a comma list
        of ``site=fmt`` pairs ("attn=int8,mlp=int8") selects per site.
        None/"" parses to the all-off policy."""
        if not spec:
            return cls()
        spec = spec.strip()
        if "=" not in spec:
            if spec not in WEIGHT_FORMATS:
                raise ValueError(
                    f"weight_quant format {spec!r} not in {WEIGHT_FORMATS}"
                )
            return cls(embedding=spec, attn=spec, mlp=spec, unembed=spec)
        kw: dict[str, str] = {}
        for part in spec.split(","):
            part = part.strip()
            if not part:
                continue
            site, _, fmt = part.partition("=")
            site, fmt = site.strip(), fmt.strip()
            if site not in cls.SITES:
                raise ValueError(
                    f"weight_quant site {site!r} not in {cls.SITES}"
                )
            if fmt not in WEIGHT_FORMATS:
                raise ValueError(
                    f"weight_quant format {fmt!r} not in {WEIGHT_FORMATS}"
                )
            kw[site] = fmt
        return cls(**kw)

    @property
    def active(self) -> bool:
        return any(getattr(self, s) for s in self.SITES)

    def describe(self) -> str:
        """Canonical spec string (compile-cache fingerprint / gauges)."""
        if not self.active:
            return "off"
        return ",".join(
            f"{s}={getattr(self, s)}" for s in self.SITES if getattr(self, s)
        )


def _dense_init(key, shape, dtype):
    return (
        jax.random.normal(key, shape, jnp.float32) / (shape[0] ** 0.5)
    ).astype(dtype)


def _ln(x: jnp.ndarray, w: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """The family's norm: RMSNorm with its scale convention (Gemma
    checkpoints store w and scale by (1 + w), HF Gemma3RMSNorm; everyone
    else scales by w), or Cohere's mean-centred LayerNorm without bias."""
    if cfg.norm_centered:
        return layer_norm(x, w, cfg.rms_eps)
    if cfg.norm_offset:
        w = 1.0 + w.astype(jnp.float32)
    return rms_norm(x, w, cfg.rms_eps)


def _rope_qk(cfg: ModelConfig, spec: LayerSpec, q, k, positions):
    """q and k under the layer's rotary embedding (``spec.rope``)."""
    if spec.rope == "none":
        return q, k
    th, sc = spec.rope
    return (
        apply_rope(q, positions, th, sc, cfg.rope_interleaved),
        apply_rope(k, positions, th, sc, cfg.rope_interleaved),
    )


def _embed(params: Params, cfg: ModelConfig, token_ids: jnp.ndarray) -> jnp.ndarray:
    x = embed_lookup(params["embed"], token_ids)
    if cfg.embed_scale:
        # Normalizer cast to the activation dtype BEFORE the multiply —
        # bf16 rounding of sqrt(hidden) is part of HF Gemma numerics.
        x = x * jnp.asarray(cfg.hidden_size ** 0.5, x.dtype)
    return x


def _residual_attn(x, layer, attn_out, cfg: ModelConfig):
    """Attention residual add; Gemma's sandwich post-attention norm sits
    on the branch, not the trunk."""
    if cfg.post_norms:
        attn_out = _ln(attn_out, layer["ln_post_attn"], cfg)
    return x + attn_out


def _residual_mlp(
    x, layer, cfg: ModelConfig, spec: LayerSpec, mesh=None, valid=None, h=None
):
    """Pre-norm → gated MLP → (optional post-norm) → residual add.
    ``valid`` marks the rows that hold a token (an expert share's). A
    parallel block (``cfg.parallel_block``) hands in ``h``, the layer's
    ONE normed input that its attention read too: the FFN reads it beside
    the attention, not behind it, and the layer has no second norm."""
    if h is None:
        h = _ln(x, layer["ln_mlp"], cfg)
    m = _mlp(layer, h, cfg, spec, mesh, valid)
    if cfg.post_norms:
        m = _ln(m, layer["ln_post_mlp"], cfg)
    return x + m


def init_layer_params(
    key: jax.Array, cfg: ModelConfig, li: int, dtype=jnp.bfloat16
) -> Params:
    """Random-init ONE layer's params (layer-wise so big models can init →
    quantize → free incrementally; ops/quant.py init_params_int8)."""
    D, H, kvH, hd = cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    I = cfg.intermediate_size

    def dense(key, shape):
        return _dense_init(key, shape, dtype)

    # Gemma stores w with effective scale (1 + w): identity init is zeros.
    def norm_init(shape):
        return (jnp.zeros if cfg.norm_offset else jnp.ones)(shape, dtype)

    if cfg.layer_pattern:
        return _init_one_part_layer(key, cfg, li, dtype)
    kda = cfg.layer_kind(li) == "kda"
    conv = cfg.layer_kind(li) == "conv"
    # (A linear-attention layer draws more matrices than 16 keys hold; the
    # other kinds keep the split their weights have always come from.)
    keys = iter(jax.random.split(key, 24 if kda else 16))
    if kda:
        layer = _init_kda_mixer(keys, cfg, dtype)
    elif conv:
        layer = _init_conv_mixer(keys, cfg, dtype)
    elif cfg.is_mla:
        # DeepSeek-V2/V3 MLA: latent KV compression (kv_lora_rank)
        # plus a decoupled roped path (qk_rope_head_dim); see
        # _qkv_mla for the absorbed-projection attention math.
        dn, dr, dc = (
            cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.kv_lora_rank
        )
        layer = {
            "w_dkv": dense(next(keys), (D, dc + dr)),
            "ln_kv": jnp.ones((dc,), dtype),
            "w_uk": _dense3(next(keys), (H, dn, dc), dn, dtype),
            "w_uv": _dense3(next(keys), (H, cfg.v_head_dim, dc), dc, dtype),
            "wo": dense(next(keys), (H * cfg.v_head_dim, D)),
            "ln_attn": jnp.ones((D,), dtype),
            "ln_mlp": jnp.ones((D,), dtype),
        }
        if cfg.q_lora_rank:
            layer["w_dq"] = dense(next(keys), (D, cfg.q_lora_rank))
            layer["ln_q"] = jnp.ones((cfg.q_lora_rank,), dtype)
            layer["w_uq"] = dense(
                next(keys), (cfg.q_lora_rank, H * (dn + dr))
            )
        else:
            layer["wq"] = dense(next(keys), (D, H * (dn + dr)))
    else:
        layer = {
            "wq": dense(next(keys), (D, H * hd)),
            "wk": dense(next(keys), (D, kvH * hd)),
            "wv": dense(next(keys), (D, kvH * hd)),
            "wo": dense(next(keys), (H * hd, D)),
            "ln_attn": norm_init((D,)),
        }
        if not cfg.parallel_block:  # a parallel block has ONE norm
            layer["ln_mlp"] = norm_init((D,))
        if cfg.post_norms:
            layer["ln_post_attn"] = norm_init((D,))
            layer["ln_post_mlp"] = norm_init((D,))
        if cfg.layer_kind(li) == "retention":
            # One gate a cached head, behind the attention layer's draws,
            # and its bias (no draw: a head's time scale).
            layer["w_gate_r"] = dense(next(keys), (D, kvH))
            layer["b_gate_r"] = retention_gate_bias(kvH)
    if cfg.moe_layer(li):
        # Sparse MLP (models/moe.py): router + stacked expert weights,
        # ep/tp-shardable; DeepSeekMoE adds always-on shared experts
        # and (V3/R1) a sigmoid router with a selection-bias term.
        # The router keeps its published width; the expert matrices are
        # those held here (cfg.num_experts_held; all of them by default).
        E, Eh = cfg.num_experts, cfg.experts_here
        Im = cfg.moe_intermediate_size or I
        layer["w_router"] = dense(next(keys), (D, E))
        if cfg.use_expert_bias:
            # A trained buffer: drawn, so that a seeded model ranks by
            # score + bias and weighs by the score, two different orders.
            layer["router_bias"] = EXPERT_BIAS_INIT_STD * jax.random.normal(
                next(keys), (E,), jnp.float32)
        elif cfg.gating == "sigmoid":
            layer["router_bias"] = jnp.zeros((E,), jnp.float32)
        layer["w_gate"] = _dense3(next(keys), (Eh, D, Im), D, dtype)
        layer["w_up"] = _dense3(next(keys), (Eh, D, Im), D, dtype)
        layer["w_down"] = _dense3(next(keys), (Eh, Im, D), Im, dtype)
        if cfg.n_shared_experts:
            Is = Im * cfg.n_shared_experts
            layer["w_shared_gate"] = dense(next(keys), (D, Is))
            layer["w_shared_up"] = dense(next(keys), (D, Is))
            layer["w_shared_down"] = dense(next(keys), (Is, D))
    else:
        layer["w_gate"] = dense(next(keys), (D, I))
        layer["w_up"] = dense(next(keys), (D, I))
        layer["w_down"] = dense(next(keys), (I, D))
    if cfg.qkv_bias and not (kda or conv):
        layer["bq"] = jnp.zeros((H * hd,), dtype)
        layer["bk"] = jnp.zeros((kvH * hd,), dtype)
        layer["bv"] = jnp.zeros((kvH * hd,), dtype)
    if cfg.qk_norm and not (kda or conv):
        layer["ln_q_head"] = norm_init((hd,))
        layer["ln_k_head"] = norm_init((hd,))
    return layer


def _init_one_part_layer(key, cfg: ModelConfig, li: int, dtype) -> Params:
    """A layer of a model whose layers are ONE part each
    (``cfg.layer_pattern``): its norm and only what its part has: a Mamba-2
    mixer (``_init_ssd_mixer``), an attention layer's four projections, an
    expert layer's router, latent projections, held experts and shared
    expert. The MLPs are the family's form (``hidden_act`` "relu2": two
    matrices, no gate). An initialiser of its own beside
    ``init_layer_params``' body because that one's order of key splits
    seeds the accepted cells' weights: a branch a part threaded through
    it would either move their draws or draw keys for parts a layer of
    this family has not."""
    assert cfg.hidden_act == "relu2", cfg.hidden_act
    D, H, kvH, hd = cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    keys = iter(jax.random.split(key, 16))
    dense = lambda shape: _dense_init(next(keys), shape, dtype)
    layer: Params = {"ln_attn": jnp.ones((D,), dtype)}
    kind, ffn = cfg.layer_kind(li), cfg.layer_ffn(li)
    if kind == "ssd":
        layer.update(_init_ssd_mixer(keys, cfg, dtype))
    elif kind == "attn":
        layer.update(
            wq=dense((D, H * hd)), wk=dense((D, kvH * hd)),
            wv=dense((D, kvH * hd)), wo=dense((H * hd, D)),
        )
    if ffn == "moe":
        E, Eh, Im = cfg.num_experts, cfg.experts_here, cfg.moe_intermediate_size
        Z = cfg.moe_latent_size or D
        layer["w_router"] = dense((D, E))
        if cfg.gating == "sigmoid":
            layer["router_bias"] = jnp.zeros((E,), jnp.float32)
        if cfg.moe_latent_size:
            layer["w_latent_down"] = dense((D, Z))
        layer["w_up"] = _dense3(next(keys), (Eh, Z, Im), Z, dtype)
        layer["w_down"] = _dense3(next(keys), (Eh, Im, Z), Im, dtype)
        if cfg.moe_latent_size:
            layer["w_latent_up"] = dense((Z, D))
        if cfg.n_shared_experts:
            Is = cfg.moe_shared_expert_intermediate_size or (
                Im * cfg.n_shared_experts)
            layer["w_shared_up"] = dense((D, Is))
            layer["w_shared_down"] = dense((Is, D))
    return layer


def _init_ssd_mixer(keys, cfg: ModelConfig, dtype) -> Params:
    """A Mamba-2 mixer: the in-projection to ``[z | x B C | dt]``, the
    depthwise convolution over ``x B C`` WITH its bias, ``A_log``, ``D`` and
    ``dt_bias`` a head, the gated norm's weight, the out-projection.
    Seeded stand-ins for trained values (the configuration file's
    ``assumed``): ``A_log = log(uniform(1, 16))``, ``D = 1``, ``dt_bias``
    the inverse softplus of a log-uniform draw in [0.001, 0.1] floored at
    1e-4 (the family's ``time_step_min`` / ``max`` / ``floor``)."""
    import math

    D, H, K = cfg.hidden_size, cfg.mamba_num_heads, cfg.linear_conv_kernel
    di, cd = cfg.ssd_inner, cfg.ssd_conv_dim
    layer = {
        "w_in": _dense_init(next(keys), (D, di + cd + H), dtype),
        "conv_w": _dense_init(next(keys), (K, cd), dtype),
        "conv_b": _dense3(next(keys), (cd,), K, dtype),
        "A_log": jnp.log(
            jax.random.uniform(next(keys), (H,), jnp.float32, 1.0, 16.0)),
    }
    step = jnp.maximum(jnp.exp(
        jax.random.uniform(next(keys), (H,), jnp.float32)
        * (math.log(0.1) - math.log(0.001)) + math.log(0.001)
    ), 1e-4)
    layer["dt_bias"] = step + jnp.log(-jnp.expm1(-step))
    layer["D"] = jnp.ones((H,), jnp.float32)
    layer["w_out"] = _dense_init(next(keys), (di, D), dtype)
    layer["ln_ssd"] = jnp.ones((di,), dtype)
    return layer


#: The deviation a seeded model draws a router's trained selection bias at
#: (``ModelConfig.use_expert_bias``): scores are sigmoids in (0, 1), so a
#: tenth moves the ranking of near neighbours and leaves most choices.
EXPERT_BIAS_INIT_STD = 0.1


def _init_conv_mixer(keys, cfg: ModelConfig, dtype) -> Params:
    """A gated short convolution's mixer and its layer's two norms: the
    in-projection to ``[B | C | u]``, the depthwise taps ``[K, D]`` (no
    bias), the out-projection."""
    D, K = cfg.hidden_size, cfg.linear_conv_kernel
    return {
        "w_in": _dense_init(next(keys), (D, 3 * D), dtype),
        "conv_w": _dense_init(next(keys), (K, D), dtype),
        "w_out": _dense_init(next(keys), (D, D), dtype),
        "ln_attn": jnp.ones((D,), dtype),
        "ln_mlp": jnp.ones((D,), dtype),
    }


def retention_gate_bias(kv_heads: int) -> jnp.ndarray:
    """A retention layer's gate bias as seeded, float32 [kvH]: cached head
    ``c`` sits at the multi-scale decay ``gamma_c = 1 - 2 ** -(5 + c % 8)``
    of a retention network (arXiv:2307.08621, section 2.1), its logit
    ``log(2 ** (5 + c % 8) - 1)``, so that a head's state remembers about
    32, 64, .. 4,096 tokens and the gate's projection moves that by data.
    Without it a seeded gate sits at sigmoid(0) and a state forgets a
    token within a few: nothing carried from step to step would show."""
    scale = 5 + jnp.arange(kv_heads) % 8
    return jnp.log(2.0 ** scale.astype(jnp.float32) - 1.0)


def _init_kda_mixer(keys, cfg: ModelConfig, dtype) -> Params:
    """A linear-attention (KDA) layer's mixer and its two block norms:
    q/k/v and the full-rank decay projection, the per-head beta and output
    gate, one depthwise convolution each for q, k and v, the decay's
    ``A_log`` (a head) and ``dt_bias`` (a channel), the output norm."""
    D, H, hd, K = (
        cfg.hidden_size, cfg.num_heads, cfg.head_dim, cfg.linear_conv_kernel
    )
    C = H * hd
    layer = {
        name: _dense_init(next(keys), (D, C), dtype)
        for name in ("wq", "wk", "wv", "w_a")
    }
    layer["w_beta"] = _dense_init(next(keys), (D, H), dtype)
    layer["w_g"] = _dense_init(next(keys), (D, H), dtype)
    for name in ("conv_q", "conv_k", "conv_v"):
        layer[name] = _dense_init(next(keys), (K, C), dtype)
    layer["A_log"] = jnp.log(
        jax.random.uniform(next(keys), (H,), jnp.float32, 1.0, 16.0)
    )
    layer["dt_bias"] = jax.random.normal(next(keys), (C,), jnp.float32)
    layer["wo"] = _dense_init(next(keys), (C, D), dtype)
    layer["ln_kda"] = jnp.ones((hd,), dtype)
    layer["ln_attn"] = jnp.ones((D,), dtype)
    layer["ln_mlp"] = jnp.ones((D,), dtype)
    return layer


def _embed_init(key, cfg: ModelConfig, dtype):
    """The embedding rows: 1/sqrt(vocab_size) like every dense matrix, or
    the deviation the model states (``ModelConfig.embed_init_std``)."""
    shape = (cfg.vocab_size, cfg.hidden_size)
    if not cfg.embed_init_std:
        return _dense_init(key, shape, dtype)
    return (
        jax.random.normal(key, shape, jnp.float32) * cfg.embed_init_std
    ).astype(dtype)


def init_params(
    key: jax.Array, cfg: ModelConfig, dtype=jnp.bfloat16
) -> Params:
    """Random-init params with 1/sqrt(fan_in) scaling."""
    lk, ek, hk = jax.random.split(key, 3)
    layer_keys = jax.random.split(lk, cfg.num_layers)
    params: Params = {
        "embed": _embed_init(ek, cfg, dtype),
        "layers": [
            init_layer_params(layer_keys[li], cfg, li, dtype)
            for li in range(cfg.num_layers)
        ],
        "ln_f": (jnp.zeros if cfg.norm_offset else jnp.ones)(
            (cfg.hidden_size,), dtype
        ),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = _dense_init(
            hk, (cfg.hidden_size, cfg.vocab_size), dtype
        )
    return params


def _qkv(layer: Params, x: jnp.ndarray, cfg: ModelConfig):
    q = qdot(x, layer["wq"])
    k = qdot(x, layer["wk"])
    v = qdot(x, layer["wv"])
    if cfg.qkv_bias:
        q = q + layer["bq"]
        k = k + layer["bk"]
        v = v + layer["bv"]
    T = x.shape[0]
    q = q.reshape(T, cfg.num_heads, cfg.head_dim)
    k = k.reshape(T, cfg.num_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        # Qwen3/Gemma-3: per-head RMSNorm on q/k before rope (HF
        # q_norm/k_norm over head_dim; Gemma's (1+w) scale via _ln).
        q = _ln(q, layer["ln_q_head"], cfg)
        k = _ln(k, layer["ln_k_head"], cfg)
    if cfg.query_pre_attn_scalar:
        # Kernels scale scores by 1/sqrt(head_dim); fold the family's
        # 1/sqrt(query_pre_attn_scalar) in as a q pre-multiply.
        q = q * jnp.asarray(
            (cfg.head_dim / cfg.query_pre_attn_scalar) ** 0.5, q.dtype
        )
    return (q, k, v.reshape(T, cfg.num_kv_heads, cfg.head_dim))


def _dense3(key, shape, fan_in, dtype):
    return (jax.random.normal(key, shape, jnp.float32) / (fan_in**0.5)).astype(
        dtype
    )


def _qkv_mla(layer: Params, x: jnp.ndarray, cfg: ModelConfig, positions,
             unabsorbed: bool = False):
    """DeepSeek MLA projections with the absorbed-matrix trick.

    Instead of materializing per-head K/V (reference models do at decode
    cost), queries are projected INTO the latent space: scores
    q_nope·(W_uk c) ≡ (W_uk^T q_nope)·c, so the paged cache stores one
    shared entry [latent ‖ roped k_pe] per token and attention runs as
    MQA over kv_lora_rank + rope dims — the kernels (ops/attention.py,
    ops/pallas) are reused unchanged with kvH=1. Returns
    (q [T, H, dc+dr], k_entry [T, 1, dc+dr], v_entry [T, 1, dc+dr])
    where v_entry is the latent zero-padded to the key width (its roped
    tail contributes nothing to the value read; _mla_out up-projects).
    With ``unabsorbed`` a fourth result is the query as the published form
    takes it, ``[T, H, dn + dr]`` with its tail rotated and NO scale (the
    expanded body's operand, ops/pallas/latent_expanded.py).
    """
    H = cfg.num_heads
    dn, dr, dc = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.kv_lora_rank
    T = x.shape[0]

    if cfg.q_lora_rank:
        cq = rms_norm(qdot(x, layer["w_dq"]), layer["ln_q"], cfg.rms_eps)
        q = qdot(cq, layer["w_uq"])
    else:
        q = qdot(x, layer["wq"])
    q = q.reshape(T, H, dn + dr)
    q_nope, q_pe = q[..., :dn], q[..., dn:]
    q_pe = apply_rope(q_pe, positions, cfg.rope_theta, cfg.rope_scaling)
    # Absorb W_uk: per-head query in latent space.
    q_lat = qeinsum("thn,hnc->thc", q_nope, layer["w_uk"])

    ckr = qdot(x, layer["w_dkv"])                       # [T, dc + dr]
    c = rms_norm(ckr[:, :dc], layer["ln_kv"], cfg.rms_eps)
    k_pe = apply_rope(
        ckr[:, None, dc:], positions, cfg.rope_theta, cfg.rope_scaling
    )[:, 0]                                            # [T, dr] (1 shared head)

    # Attention kernels scale by 1/sqrt(q_width); MLA's true scale is
    # 1/sqrt(dn + dr) — fold the correction into q, along with DeepSeek's
    # yarn softmax-scale multiplier. The reference multiplies the softmax
    # scale by mscale² (HF: softmax_scale * mscale * mscale), and only q
    # carries our correction, so q gets the full square.
    corr = ((dc + dr) / (dn + dr)) ** 0.5
    if cfg.rope_scaling is not None:
        corr *= cfg.rope_scaling.attn_mscale() ** 2
    q_full = jnp.concatenate([q_lat, q_pe], axis=-1) * corr
    k_entry = jnp.concatenate([c, k_pe], axis=-1)[:, None, :]
    v_entry = jnp.pad(c, ((0, 0), (0, dr)))[:, None, :]
    if unabsorbed:
        q_exp = jnp.concatenate([q_nope, q_pe], axis=-1).astype(x.dtype)
        return q_full.astype(x.dtype), k_entry, v_entry, q_exp
    return q_full.astype(x.dtype), k_entry, v_entry


def _mla_out(layer: Params, attn: jnp.ndarray, cfg: ModelConfig,
             expanded=None):
    """Attention output [..., H, dc+dr] → up-project the latent part per
    head (absorbed W_uv) and apply the output projection. ``expanded`` is
    ``(rows [T] bool, values [T, H, v])``: the rows the expanded body
    answered, whose values are up-projected already."""
    dc = cfg.kv_lora_rank
    o_lat = attn[..., :dc]
    o = qeinsum("...hc,hvc->...hv", o_lat, layer["w_uv"])
    if expanded is not None:
        rows, values = expanded
        o = jnp.where(rows[:, None, None], values, o)
    lead = o.shape[:-2]
    return qdot(
        o.reshape(*lead, cfg.num_heads * cfg.v_head_dim).astype(attn.dtype),
        layer["wo"],
    )


def _swiglu(
    layer: Params, x: jnp.ndarray, prefix: str = "w_", act: str = "silu",
    limit: float = 0.0,
) -> jnp.ndarray:
    # "silu" = Llama SwiGLU; "gelu_tanh" = Gemma GeGLU (HF
    # hidden_activation="gelu_pytorch_tanh" = tanh-approximated gelu).
    # `limit` L > 0 clamps before the activation: gate to at most L, up
    # into [-L, L] (Ling-3.0's swiglu limit lists).
    from dynamo_tpu.models.moe import clamp_swiglu

    gate, up = clamp_swiglu(
        qdot(x, layer[f"{prefix}gate"]), qdot(x, layer[f"{prefix}up"]), limit
    )
    gate = (
        jax.nn.silu(gate) if act == "silu"
        else jax.nn.gelu(gate, approximate=True)
    )
    return qdot(gate * up, layer[f"{prefix}down"])


def _mlp(
    layer: Params, x: jnp.ndarray, cfg: ModelConfig, spec: LayerSpec,
    mesh=None, valid=None,
) -> jnp.ndarray:
    # Structure-driven: a router in the layer means routed experts (MoE
    # models may keep their first_k_dense_replace layers dense). `mesh`
    # (from the AttnDispatch) places the grouped expert path's products
    # per shard (models/moe.py _moe_mlp_grouped).
    if "w_router" in layer:
        return _moe_mlp(layer, x, cfg, spec, mesh, valid)
    return _swiglu(layer, x, act=cfg.hidden_act)


def _relu2(layer: Params, x: jnp.ndarray, prefix: str = "w_") -> jnp.ndarray:
    """A non-gated MLP of two matrices: ``relu(x W1)^2 W2``."""
    up = jax.nn.relu(qdot(x, layer[f"{prefix}up"]))
    return qdot(up * up, layer[f"{prefix}down"])


def _moe_mlp(
    layer: Params, x: jnp.ndarray, cfg: ModelConfig, spec: LayerSpec,
    mesh=None, valid=None,
) -> jnp.ndarray:
    """Top-k routed expert MLP over arbitrary leading dims (models/moe.py:
    dense einsums below 16 experts, the grouped path from there up;
    ep/tp-sharded under the mesh), plus
    DeepSeekMoE always-on shared experts when present."""
    from dynamo_tpu.models.moe import MoeConfig, moe_mlp

    mcfg = MoeConfig(
        hidden_size=cfg.hidden_size,
        intermediate_size=cfg.moe_intermediate_size or cfg.intermediate_size,
        num_experts=cfg.num_experts,
        num_experts_per_tok=cfg.num_experts_per_tok,
        gating=cfg.gating,
        norm_topk_prob=cfg.norm_topk_prob,
        norm_topk_eps=cfg.norm_topk_eps,
        routed_scaling_factor=cfg.routed_scaling_factor,
        n_group=cfg.n_group,
        topk_group=cfg.topk_group,
        num_experts_held=cfg.num_experts_held,
        expert_held_offset=cfg.expert_held_offset,
        swiglu_limit=spec.swiglu_limit,
        act="relu2" if cfg.hidden_act == "relu2" else "swiglu",
        expert_input_size=cfg.moe_latent_size,
    )
    lead = x.shape[:-1]
    flat = x.reshape(-1, cfg.hidden_size)
    with jax.named_scope("expert_layer"):
        latent = None
        if cfg.moe_latent_size:
            # Experts in a latent: ONE projection in front of them all (the
            # router and the shared expert read the whole row) and one back
            # behind their weighted sum.
            with jax.named_scope("latent_down"):
                latent = qdot(flat, layer["w_latent_down"])
        out = moe_mlp(
            layer, flat, mcfg, mesh=mesh, valid=valid, expert_x=latent)
        if latent is not None:
            with jax.named_scope("latent_up"):
                out = qdot(out, layer["w_latent_up"])
        if "w_shared_up" in layer:
            with jax.named_scope("shared_experts"):
                shared = (
                    _relu2(layer, flat, prefix="w_shared_")
                    if cfg.hidden_act == "relu2" else _swiglu(
                        layer, flat, prefix="w_shared_",
                        limit=spec.shared_swiglu_limit,
                    )
                )
            if cfg.shared_experts_average:
                # The stacked shared experts' product IS their sum.
                shared = shared / cfg.n_shared_experts
            out = out + shared
    return out.reshape(*lead, cfg.hidden_size)


def _kda_mixer(
    layer: Params, h: jnp.ndarray, cfg: ModelConfig, state, meta,
    state_slot, use_pallas: bool,
):
    """A KDA linear-attention layer's mixer over the flat ragged batch
    (ops/linear_attention.py): ``h`` [T, D] normed rows -> (y [T, D], the
    layer's new state). ``state`` is the layer's (S [N+1, H, d, d],
    convolution tail [N+1, K-1, 3*H*d]); ``meta`` the dispatch's
    (token_seq, token_pos, q_start, q_len, row_start). conv -> silu ->
    L2 norm on q and k, q scaled by d^-1/2; the decay's log in
    (kda_lower_bound, 0) a channel; one beta and one output gate a head;
    no rotary embedding."""
    from dynamo_tpu.ops.linear_attention import causal_conv, kda_ragged

    T = h.shape[0]
    H, d = cfg.num_heads, cfg.head_dim
    S, tail = state
    qkv = jnp.concatenate(
        [qdot(h, layer[w]) for w in ("wq", "wk", "wv")], axis=-1
    )
    conv_w = jnp.concatenate(
        [layer[w] for w in ("conv_q", "conv_k", "conv_v")], axis=-1
    )
    qkv, tail = causal_conv(qkv, conv_w, tail, *meta, state_slot)
    q, k, v = jnp.split(jax.nn.silu(qkv).reshape(T, 3 * H, d), 3, axis=1)

    def l2(x):
        return x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True) + 1e-6)

    g = cfg.kda_lower_bound * jax.nn.sigmoid(
        jnp.exp(layer["A_log"])[None, :, None]
        * (qdot(h, layer["w_a"]).astype(jnp.float32) + layer["dt_bias"])
        .reshape(T, H, d)
    )
    beta = jax.nn.sigmoid(qdot(h, layer["w_beta"]).astype(jnp.float32))
    o, S = kda_ragged(
        l2(q) * d**-0.5, l2(k), v, g, beta, S, *meta, state_slot,
        use_pallas=use_pallas, lower_bound=cfg.kda_lower_bound,
    )
    gate = jax.nn.sigmoid(qdot(h, layer["w_g"]).astype(jnp.float32))
    o = rms_norm(o, layer["ln_kda"], cfg.rms_eps) * gate[:, :, None]
    return qdot(o.reshape(T, H * d).astype(h.dtype), layer["wo"]), (S, tail)


def _ssd_mixer(
    layer: Params, h: jnp.ndarray, cfg: ModelConfig, state, meta,
    state_slot, use_pallas: bool,
):
    """A Mamba-2 (SSD) layer's mixer over the flat ragged batch (ops/
    ssd.py): ``h`` [T, D] normed rows -> (y [T, D], the layer's new state).
    ``state`` is the layer's (S [N+1, H, P, n], convolution tail [N+1,
    K-1, H P + 2 G n]); ``meta`` the dispatch's (token_seq, token_pos,
    q_start, q_len, row_start). ``[z | xBC | dt] = h W_in``; conv WITH
    bias -> silu on ``xBC``; ``dt = softplus(dt + dt_bias)``, the decay
    ``exp(-exp(A_log) dt)`` a head; the skip ``D x``; the gate BEFORE the
    norm, ``norm(y * silu(z))`` over groups of ``H P / G`` channels; no
    rotary embedding."""
    from dynamo_tpu.ops.linear_attention import causal_conv
    from dynamo_tpu.ops.ssd import ssd_ragged

    T = h.shape[0]
    H, P = cfg.mamba_num_heads, cfg.mamba_head_dim
    G, N, di = cfg.mamba_n_groups, cfg.ssm_state_size, cfg.ssd_inner
    S, tail = state
    zxbcdt = qdot(h, layer["w_in"])
    z, xbc, dt = jnp.split(zxbcdt, [di, di + cfg.ssd_conv_dim], axis=-1)
    xbc, tail = causal_conv(
        xbc, layer["conv_w"], tail, *meta, state_slot, bias=layer["conv_b"]
    )
    x, B, C = jnp.split(jax.nn.silu(xbc), [di, di + G * N], axis=-1)
    x = x.reshape(T, H, P)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + layer["dt_bias"])
    y, S = ssd_ragged(
        x, dt, -jnp.exp(layer["A_log"]) * dt, B.reshape(T, G, N),
        C.reshape(T, G, N), S, *meta, state_slot, use_pallas=use_pallas,
    )
    y = (y + layer["D"][None, :, None] * x).reshape(T, di)
    y = rms_norm(
        (y * jax.nn.silu(z.astype(jnp.float32))).reshape(T, G, di // G),
        layer["ln_ssd"].reshape(G, di // G), cfg.rms_eps,
    ).reshape(T, di)
    return qdot(y.astype(h.dtype), layer["w_out"]), (S, tail)


def _conv_mixer(
    layer: Params, h: jnp.ndarray, cfg: ModelConfig, state, meta,
    state_slot, use_pallas: bool,
):
    """A gated short convolution's mixer over the flat ragged batch
    (LFM2): ``h`` [T, D] normed rows -> (y [T, D], the layer's new state).
    ``[B | C | u] = h W_in``; ``a = B * u``; ``v`` the depthwise causal
    convolution of ``a`` (no bias, no activation), continued from the
    slot's tail; ``y = (C * v) W_out``. ``state`` is ONE array, the tail
    ``[N+1, K-1, D]``: the last ``K - 1`` rows of ``a`` before each slot's
    next position, in the served dtype (what the convolution reads of a
    row inside a span it reads of the tail across a dispatch boundary).
    The convolution and the two gates run in XLA: ``use_pallas`` decides
    nothing here."""
    from dynamo_tpu.ops.linear_attention import causal_conv

    (tail,) = state
    with jax.named_scope("in_proj"):
        B, C, u = jnp.split(qdot(h, layer["w_in"]), 3, axis=-1)
    with jax.named_scope("conv"):
        v, tail = causal_conv(B * u, layer["conv_w"], tail, *meta, state_slot)
        y = (C.astype(jnp.float32) * v).astype(h.dtype)
    with jax.named_scope("out_proj"):
        return qdot(y, layer["w_out"]), (tail,)


def _retention_inputs(layer: Params, h: jnp.ndarray, cfg: ModelConfig,
                      spec: LayerSpec, positions):
    """A power-retention layer's (q, k, v, log gate) from its normed rows:
    an attention layer's projections with per-head q/k norms and rotary
    embedding, ``1 / sqrt(d)`` (the scale inside the power) folded into q,
    and one gate a cached head (a projection and a bias) through
    log-sigmoid, float32."""
    q, k, v = _qkv(layer, h, cfg)
    q, k = _rope_qk(cfg, spec, q, k, positions)
    q = q * jnp.asarray(cfg.head_dim ** -0.5, q.dtype)
    lg = jax.nn.log_sigmoid(
        qdot(h, layer["w_gate_r"]).astype(jnp.float32) + layer["b_gate_r"]
    )
    return q, k, v, lg


def _retention_mixer(
    layer: Params, h: jnp.ndarray, cfg: ModelConfig, state, meta,
    state_slot, use_pallas: bool, *, spec: LayerSpec,
):
    """A power-retention layer's mixer over the flat ragged batch
    (ops/power_retention.py): ``h`` [T, D] normed rows -> (y [T, D], the
    layer's new state). ``state`` is the layer's (S, z) pair; ``meta`` the
    dispatch's (token_seq, token_pos, q_start, q_len, row_start)."""
    from dynamo_tpu.ops.power_retention import retention_ragged

    T = h.shape[0]
    q, k, v, lg = _retention_inputs(
        layer, h, cfg, spec, jnp.maximum(meta[1], 0)
    )
    y, state = retention_ragged(
        q, k, v, lg, state, *meta, state_slot, use_pallas=use_pallas
    )
    return qdot(y.reshape(T, -1).astype(h.dtype), layer["wo"]), state


def _to_cache(vals: jnp.ndarray, cache: jnp.ndarray) -> jnp.ndarray:
    """Cast (and lane-pad, when the cache head dim is padded for the
    Pallas kernels) K/V values for a cache scatter."""
    pad = cache.shape[-1] - vals.shape[-1]
    if pad:
        vals = jnp.pad(vals, ((0, 0),) * (vals.ndim - 1) + ((0, pad),))
    return vals.astype(cache.dtype)


def _logits(params: Params, cfg: ModelConfig, h: jnp.ndarray) -> jnp.ndarray:
    h = _ln(h, params["ln_f"], cfg)
    if cfg.tie_word_embeddings:
        logits = tied_head_mm(h, params["embed"]).astype(jnp.float32)
    else:
        logits = qdot(h, params["lm_head"]).astype(jnp.float32)
    return logits * cfg.logit_scale if cfg.logit_scale != 1.0 else logits


#: How often this process traced the layer body, and how often a traced
#: model function called it (once a layer): 1 / 16 a program of a 16-layer
#: model whose layers share one spec (``CompileStats.snapshot``:
#: ``layer_body_traces_total`` / ``layer_body_calls_total``). The process's,
#: as jit's cache is: a second runner of the same model traces nothing.
LAYER_BODY = {"traces": 0, "calls": 0}


def _layer(
    cfg: ModelConfig, spec: LayerSpec, block_size: int,
    attn: AttnDispatch | None, pallas: bool, *operands,
):
    """ONE layer of ``unified`` (``_layer_rows`` over ``operands``). Every
    layer of every trace calls it through ``_layer_body``, the one
    ``jax.jit`` of it, so a program traces and lowers it once a DISTINCT
    layer and not once a layer (docs/architecture/unified_step.md "One
    traced body a layer spec"). Static: the model, the layer's ``spec`` (NO
    layer index: what differs by layer is in the spec or in the operands'
    shapes, and jit's cache decides which layers are one program), the
    block size, the runner's dispatch, and ``pallas`` (``pallas_enabled()``:
    what the trace below reads of the process, here so that jit's cache
    sees it). Returns ``_layer_rows``' results and the grouped expert
    path's traced counts: they cannot leave this trace but as results
    (models/moe.py ``note_experts_hit``)."""
    from dynamo_tpu.models.moe import collect_experts_hit

    LAYER_BODY["traces"] += 1
    with collect_experts_hit() as hit:
        out = _layer_rows(cfg, spec, block_size, attn, *operands)
    return *out, hit.results()


def _layer_rows(
    cfg: ModelConfig, spec: LayerSpec, block_size: int,
    attn: AttnDispatch | None,
    layer: Params, cache, kv_scale, state, x, meta, slot_mapping,
    block_tables, state_slot,
):
    """A layer's operations: norm, the mixer by kind, the cache write, the
    attention call, the output product, the MLP. ``cache`` is the layer's
    pages, ``spec.cache_arrays`` arrays of them ((k, v), or the latent
    once) or ONE array of a (k, v) layer's joined pages (ops/attention.py
    ``page_form``; the engine's choice, read here off the operand), with
    ``kv_scale`` [arrays, num_blocks, kvH] where they are int8; ``state`` a
    recurrent layer's arrays; ``meta`` the step's
    (token_seq, token_pos, q_start, q_len, kv_len, row_start);
    ``slot_mapping`` and ``block_tables`` its cache group's. Returns (x,
    cache, kv_scale, state)."""
    token_seq, token_pos, q_start, q_len, kv_len, row_start = meta
    mesh = attn.mesh if attn is not None else None
    T = x.shape[0]
    # An expert share drops the budget's padding rows with the rows routed
    # elsewhere; a model whose experts are all here computes every row.
    valid = token_pos >= 0 if cfg.num_experts_held else None
    h = _ln(x, layer["ln_attn"], cfg)
    if spec.kind == "none":
        # A feed-forward part alone (docs/architecture/unified_step.md "A
        # layer that is one part"): it reads the layer's ONE norm and owns
        # neither pages nor state.
        x = _residual_mlp(x, layer, cfg, spec, mesh, valid, h=h)
        return x, cache, kv_scale, state
    if spec.kind != "attn":
        # A recurrent layer: its state in and out, no pages.
        mixer = {
            "kda": _kda_mixer, "ssd": _ssd_mixer, "conv": _conv_mixer,
            "retention": partial(_retention_mixer, spec=spec),
        }[spec.kind]
        with jax.named_scope(f"{spec.kind}_mixer"):
            y, state = mixer(
                layer, h, cfg, state,
                (token_seq, token_pos, q_start, q_len, row_start),
                state_slot, attn is not None and attn.use_pallas,
            )
        x = x + y
        if spec.ffn != "none":
            x = _residual_mlp(x, layer, cfg, spec, mesh, valid)
        return x, cache, kv_scale, state
    positions = jnp.maximum(token_pos, 0)
    if attn is None and spec.cache_arrays == 1:
        from dynamo_tpu.ops.attention import default_dispatch

        attn = default_dispatch(block_size, cache[0])
    # Two forms of latent attention, by span (docs/architecture/
    # unified_step.md "Two forms, by span"): a layer whose cache is held
    # once, read by the Pallas kernel from plain arrays of the model's
    # dtype, sends its LONG spans through the expanded body; ``expand`` is
    # the rule's K at this rung, 0 where no span of it can be long (the
    # rung then compiles the program it had).
    expand = 0
    if (
        spec.cache_arrays == 1 and attn.use_pallas and kv_scale is None
        and not attn.kv_sp
        and all(
            not is_quantized(w) and w.dtype == cache[0].dtype == x.dtype
            for w in (layer["w_uk"], layer["w_uv"])
        )
    ):
        from dynamo_tpu.ops.pallas.latent_expanded import (
            expanded_k,
            expanded_spans,
        )

        tp = 1 if attn.mesh is None else attn.mesh.shape.get(attn.tp_axis, 1)
        expand = expanded_k(cfg, T, cfg.num_heads // tp)
    if expand:
        with jax.named_scope("latent_mixer"):
            q, k, v, q_exp = _qkv_mla(layer, h, cfg, positions, True)
        long = expanded_spans(q_len, kv_len, expand)
        q_len_long = jnp.where(long, q_len, 0)
        q_len = jnp.where(long, 0, q_len)   # the absorbed call's
    elif cfg.is_mla:
        with jax.named_scope("latent_mixer"):
            q, k, v = _qkv_mla(layer, h, cfg, positions)
    else:
        q, k, v = _qkv(layer, h, cfg)
        q, k = _rope_qk(cfg, spec, q, k, positions)
    # What the layer writes: keys and values apart, or the latent ONCE
    # (``spec.cache_arrays`` 1: the values are the key entry's first
    # kv_lora_rank columns, and attention reads them from the key slot),
    # or both into a block's ONE joined page.
    new = (k, v)[: spec.cache_arrays]
    if page_form(*cache) == "joined":
        assert kv_scale is None and spec.cache_arrays == 2
        (pages,) = cache
        # ONE scatter puts every slot's key and its value, over the pages
        # as rows ``[blocks * 2 * bs, kvH, Dc]`` (a bitcast): slot ``s`` of
        # block ``b`` has its key at row ``s + b * bs`` and its value ``bs``
        # rows on; padding rows land in block 0. The row scatter of the two
        # arrays apart, once over 2T rows: indexed on the pages' own block
        # and row axes XLA lays the whole pool out anew around it (a copy
        # in and a copy out a layer at a budget of 1,024 rows).
        rows = pages.reshape(-1, *pages.shape[3:])
        key_row = slot_mapping + slot_mapping // block_size * block_size
        rows = rows.at[
            jnp.concatenate([key_row, key_row + block_size])
        ].set(jnp.concatenate([_to_cache(a, pages) for a in new]))
        cache = (rows.reshape(pages.shape),)
        scale_kw = {}
    elif kv_scale is not None:
        from dynamo_tpu.ops.quant import quantize_kv_write

        pad = cache[0].shape[-1] - k.shape[-1]
        if pad:  # lane-padded cache (Pallas head-dim contract)
            widen = ((0, 0),) * (k.ndim - 1) + ((0, pad),)
            new = tuple(jnp.pad(a, widen) for a in new)
        cache, scales = zip(*(
            quantize_kv_write(pages, sc, slot_mapping, a, block_size)
            for pages, sc, a in zip(cache, kv_scale, new)
        ))
        kv_scale = jnp.stack(scales)
        scale_kw = {"k_scales": scales[0], "v_scales": scales[-1]}
    else:
        cache = tuple(
            pages.at[slot_mapping].set(_to_cache(a, pages))
            for pages, a in zip(cache, new)
        )
        scale_kw = {}
    k_cache, v_cache = cache if len(cache) == 2 else (cache[0], None)
    if attn is None:
        from dynamo_tpu.ops.attention import ragged_attention as ragged_fn
    else:
        ragged_fn = attn.ragged
    # A block-diffusion model masks by block; every other keeps the
    # causal call it had.
    block_kw = (
        {"diffusion_block": cfg.diffusion_block_length}
        if cfg.diffusion_block_length > 1 else {}
    )
    if spec.cache_arrays == 1:  # the held-once call, inside latent_mixer
        scope = "latent_mixer/attn_latent"
    else:
        scope = "attn_window" if spec.window else "attn_full"
    with jax.named_scope(scope):
        attn_out = ragged_fn(
            q, k_cache, v_cache, block_tables, token_seq, token_pos,
            q_start, q_len, kv_len, row_start, block_size,
            window=spec.window, **scale_kw, **block_kw,
        )
    if cfg.parallel_block:
        # x + attention(h) + ffn(h): both branches read the ONE norm.
        a = qdot(attn_out.reshape(T, -1), layer["wo"])
        x = _residual_mlp(x, layer, cfg, spec, mesh, valid, h=h) + a
        return x, cache, kv_scale, state
    if cfg.is_mla:
        expanded = None
        if expand:
            with jax.named_scope("latent_mixer/attn_latent"):
                scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
                if cfg.rope_scaling is not None:
                    scale *= cfg.rope_scaling.attn_mscale() ** 2
                values = attn.latent_expanded(
                    q_exp, k_cache, layer["w_uk"], layer["w_uv"],
                    block_tables, q_start, q_len_long, row_start,
                    block_size, scale=scale,
                )
            rows = long[jnp.clip(token_seq, 0, long.shape[0] - 1)]
            expanded = (rows & (token_pos >= 0), values)
        with jax.named_scope("latent_mixer"):
            x = x + _mla_out(layer, attn_out, cfg, expanded)
    else:
        x = _residual_attn(
            x, layer, qdot(attn_out.reshape(T, -1), layer["wo"]), cfg
        )
    if spec.ffn != "none":
        x = _residual_mlp(x, layer, cfg, spec, mesh, valid)
    return x, cache, kv_scale, state


# dynalint: allow[DT016] no program of its own on the serving path: every served call is inside a budget-ladder program's trace (engine/runner.py), where XLA inlines it; it compiles alone only where a test or a tool calls `unified` eagerly
_layer_body = jax.jit(
    _layer, static_argnames=("cfg", "spec", "block_size", "attn", "pallas")
)


def unified(
    cfg: ModelConfig,
    params: Params,
    kv_caches: list[tuple[jnp.ndarray, jnp.ndarray]],
    token_ids: jnp.ndarray,     # [T] flat mixed batch (budget-padded)
    token_pos: jnp.ndarray,     # [T] global position per token (-1 = pad)
    slot_mapping: jnp.ndarray,  # [T] cache slots (trash slots for padding)
    token_seq: jnp.ndarray,     # [T] owning metadata row per token
    block_tables: jnp.ndarray,  # [S, max_blocks] (a tuple: one a cache group)
    q_start: jnp.ndarray,       # [S] span prefix length
    q_len: jnp.ndarray,         # [S] span rows (0 = idle row)
    kv_len: jnp.ndarray,        # [S] context after this step
    row_start: jnp.ndarray,     # [S] span's first flat row
    block_size: int,
    attn: AttnDispatch | None = None,
    kv_scales: jnp.ndarray | None = None,  # [L, 2, num_blocks, kvH] f32
    draft_len: jnp.ndarray | None = None,  # [S] draft rows in each span tail
    verify_rows: int = 1,                  # static: logit rows per span
    embeds: jnp.ndarray | None = None,     # [T, D] soft-prompt overrides
    embed_mask: jnp.ndarray | None = None, # [T] bool — rows from embeds
    rec_state: list | None = None,         # its arrays a recurrent layer
    state_slot: jnp.ndarray | None = None, # [S] each span's state slot
):
    """ONE forward for a mixed prefill+decode token batch (the unified
    step — docs/architecture/unified_step.md). The trunk is the single-
    sequence prefill trunk over arbitrary per-token positions: embed,
    RoPE at ``token_pos``, K/V scatter at ``slot_mapping``, ragged paged
    attention (ops/attention.py AttnDispatch.ragged), MLP. Decode lanes
    are spans of length 1; prefill quanta are their chunk's rows; a
    speculative draft-verify span is ``q_len = draft_len + 1`` rows
    (the fed token plus its drafts — verification is just a short
    "prefill" over the draft positions); the only compiled extent is
    the token budget ``T`` (plus the fixed metadata width ``S``), which
    is what deletes the phase×bucket×lane program grid.

    With ``kv_scales`` (int8 KV caches — docs/architecture/kv_quant.md)
    the K/V scatter quantizes through the shared per-block write law
    (ops/quant.py quantize_kv_write) and attention dequantizes in the
    kernel/oracle; returns (logits, caches, new_scales) then, or the
    legacy (logits, caches) pair when unquantized.

    ``embeds``/``embed_mask`` (a static trace-time branch, same as
    ``prefill``) substitute multimodal soft-prompt rows into the FLAT
    token batch — the one scatter path per-lane embed tensors needed.

    Returns per-span logits: ``verify_rows == 1`` keeps the legacy
    last-row contract ``[S, V]`` (span s's logits come from its LAST
    real token row — mid-prompt quanta's samples are discarded by the
    engine, exactly as chunked prefill did). ``verify_rows = R > 1``
    returns ``[S, R, V]``: row ``j`` of span ``s`` is the logits at
    span row ``q_len - 1 - draft_len + j`` (clamped into the span) —
    for a draft-verify span row 0 scores the first draft and row
    ``draft_len`` is the bonus position; spans with fewer rows repeat
    their last row (masked by the caller's acceptance law).

    A model whose layers fall into more than one cache group
    (``cfg.cache_groups``: window and full layers side by side) takes
    ``slot_mapping`` and ``block_tables`` as tuples, one a group, and each
    layer writes and reads through its own group's
    (docs/architecture/cache_groups.md).

    A model with recurrent layers (``cfg.layer_kind``: "kda", a delta-rule
    linear-attention layer; "retention", a power-retention layer; "ssd", a
    state-space layer; "conv", a gated short convolution) takes
    ``rec_state``, the state arrays of each of them in order (``cfg.
    recurrent_state_arrays``: a (state, convolution tail) pair; an (S, z)
    pair; the tail alone), and ``state_slot``, and returns the new
    ``rec_state`` as its last result; those layers' entries of
    ``kv_caches`` are empty."""
    from dynamo_tpu.models.moe import note_experts_hit
    from dynamo_tpu.ops.attention import pallas_enabled

    T = token_ids.shape[0]
    x = _embed(params, cfg, token_ids)
    if embeds is not None:
        x = jnp.where(embed_mask[:, None], embeds.astype(x.dtype), x)
    new_caches = []
    new_scales = []
    new_rec = []
    if isinstance(block_tables, (tuple, list)):
        slots_of, tables_of = slot_mapping, block_tables
    else:  # one group, handed bare
        slots_of, tables_of = (slot_mapping,), (block_tables,)
    meta = (token_seq, token_pos, q_start, q_len, kv_len, row_start)
    # What a trace reads of the process (DYNAMO_TPU_PALLAS, the backend) goes
    # in as a static operand: jit's cache has to see it.
    pallas = pallas_enabled()
    for li, (layer, cache) in enumerate(zip(params["layers"], kv_caches)):
        spec = cfg.layer_spec(li)
        paged = spec.kind == "attn"
        recurrent = spec.kind in RECURRENT_KINDS
        LAYER_BODY["calls"] += 1
        x, cache, scale, state, hit = _layer_body(
            cfg, spec, block_size, attn, pallas,
            layer, cache,
            kv_scales[li] if kv_scales is not None and paged else None,
            rec_state[len(new_rec)] if recurrent else None,
            x, meta, slots_of[spec.cache_group], tables_of[spec.cache_group],
            state_slot,
        )
        new_caches.append(cache)
        if scale is not None:
            new_scales.append(scale)
        if recurrent:
            new_rec.append(state)
        note_experts_hit(*hit)

    if verify_rows == 1:
        last = jnp.clip(row_start + q_len - 1, 0, T - 1)  # [S]
        logits = _logits(params, cfg, x[last])
    else:
        # Per-span verify rows: the last draft_len + 1 rows of each span,
        # aligned so row j scores draft j+1 (row draft_len = the bonus
        # position). Short spans clamp onto their own last row — never
        # into a neighbouring span — and idle spans (q_len = 0) clamp to
        # row 0 of the batch, masked by the caller (q_len > 0).
        dl = (
            draft_len
            if draft_len is not None
            else jnp.zeros_like(q_len)
        )
        offs = jnp.arange(verify_rows)                      # [R]
        span_row = jnp.clip(
            (q_len - 1 - dl)[:, None] + offs[None, :],
            0,
            jnp.maximum(q_len - 1, 0)[:, None],
        )                                                    # [S, R]
        rows = jnp.clip(row_start[:, None] + span_row, 0, T - 1)
        logits = _logits(params, cfg, x[rows])               # [S, R, V]
    out = (logits, new_caches)
    if kv_scales is not None:
        out += (jnp.stack(new_scales),)
    if rec_state is not None:
        out += (new_rec,)
    return out


def hidden_states(
    cfg: ModelConfig,
    params: Params,
    token_ids: jnp.ndarray,
    embeds: jnp.ndarray | None = None,
    embed_mask: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Full no-cache trunk [T] -> pre-final-norm hidden states [T, D] —
    shared by the logits oracle below and the embeddings pooled forward
    (llm/embedding.py), so architecture changes live in one place.
    `embeds`/`embed_mask` mirror `unified`'s soft-prompt substitution so the
    oracle covers the multimodal path too."""
    T = token_ids.shape[0]
    positions = jnp.arange(T)
    x = _embed(params, cfg, token_ids)
    if embeds is not None:
        x = jnp.where(embed_mask[:, None], embeds.astype(x.dtype), x)
    for li, layer in enumerate(params["layers"]):
        spec = cfg.layer_spec(li)
        h = _ln(x, layer["ln_attn"], cfg)
        if spec.kind == "none":     # a feed-forward part alone
            x = _residual_mlp(x, layer, cfg, spec, h=h)
            continue
        if spec.kind in ("kda", "ssd", "conv"):
            # One span from zeros: slot 1 of a fresh two-slot state.
            one = jnp.ones((1,), jnp.int32)
            mixer = {"kda": _kda_mixer, "ssd": _ssd_mixer,
                     "conv": _conv_mixer}[spec.kind]
            y, _ = mixer(
                layer, h, cfg,
                tuple(
                    jnp.zeros(shape, dt) for shape, dt in
                    cfg.recurrent_state_arrays(li, 2, h.dtype.name)
                ),
                (jnp.zeros((T,), jnp.int32), positions, 0 * one, T * one,
                 0 * one),
                one, False,
            )
            x = x + y
        elif spec.kind == "retention":
            # The attention form: no state at all.
            from dynamo_tpu.ops.power_retention import retention_attention

            y = retention_attention(
                *_retention_inputs(layer, h, cfg, spec, positions)
            )
            x = x + qdot(y.reshape(T, -1).astype(h.dtype), layer["wo"])
        elif cfg.is_mla:
            q, k, v = _qkv_mla(layer, h, cfg, positions)
            attn = full_causal_attention(q, k, v)
            x = x + _mla_out(layer, attn, cfg)
        else:
            q, k, v = _qkv(layer, h, cfg)
            q, k = _rope_qk(cfg, spec, q, k, positions)
            attn = full_causal_attention(
                q, k, v, window=spec.window,
                diffusion_block=max(cfg.diffusion_block_length, 1),
            )
            a = qdot(attn.reshape(T, -1), layer["wo"])
            if cfg.parallel_block:
                x = _residual_mlp(x, layer, cfg, spec, h=h) + a
                continue
            x = _residual_attn(x, layer, a, cfg)
        if spec.ffn != "none":
            x = _residual_mlp(x, layer, cfg, spec)
    return x


def reference_forward(
    cfg: ModelConfig,
    params: Params,
    token_ids: jnp.ndarray,
    embeds: jnp.ndarray | None = None,
    embed_mask: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Full no-cache forward [T] -> logits [T, V]; the correctness oracle the
    paged `unified` step is tested against."""
    return _logits(
        params, cfg, hidden_states(cfg, params, token_ids, embeds, embed_mask)
    )


def _load_one_part_layers(cfg: ModelConfig, tensors: dict, w) -> Params:
    """The params of a model whose layers are one part each
    (``cfg.layer_pattern``; HF ``nemotron_h``) from its checkpoint tensors:
    ``backbone.layers.{i}.norm`` and ``.mixer.*`` by the layer's letter (a
    Mamba-2 mixer's ``in_proj``, ``conv1d`` ([C, 1, K] -> our [K, C]) with
    its bias, ``A_log``, ``D``, ``dt_bias``, gated ``norm``, ``out_proj``;
    attention's four projections; an expert layer's ``gate`` with its
    ``e_score_correction_bias``, ``fc1_latent_proj`` / ``fc2_latent_proj``,
    the held ``experts.{e}.up_proj`` / ``down_proj`` and
    ``shared_experts``). ``w(name)`` reads a tensor transposed to [in,
    out]. No checkpoint of the family is here: the names are the public
    modelling code's, exercised on a seeded state dict
    (tests/test_nemotron_h.py)."""
    f32 = lambda name: jnp.asarray(tensors[name], jnp.float32)
    layers = []
    for i in range(cfg.num_layers):
        m = f"backbone.layers.{i}.mixer"
        layer = {
            "ln_attn": w(f"backbone.layers.{i}.norm.weight", transpose=False)
        }
        kind, ffn = cfg.layer_kind(i), cfg.layer_ffn(i)
        if kind == "ssd":
            conv = jnp.asarray(tensors[f"{m}.conv1d.weight"])
            layer.update(
                w_in=w(f"{m}.in_proj.weight"),
                conv_w=conv[:, 0, :].T.astype(layer["ln_attn"].dtype),
                conv_b=w(f"{m}.conv1d.bias", transpose=False),
                A_log=f32(f"{m}.A_log"), D=f32(f"{m}.D"),
                dt_bias=f32(f"{m}.dt_bias"),
                ln_ssd=w(f"{m}.norm.weight", transpose=False),
                w_out=w(f"{m}.out_proj.weight"),
            )
        elif kind == "attn":
            for ours, theirs in (("wq", "q_proj"), ("wk", "k_proj"),
                                 ("wv", "v_proj"), ("wo", "o_proj")):
                layer[ours] = w(f"{m}.{theirs}.weight")
        if ffn == "moe":
            layer["w_router"] = w(f"{m}.gate.weight")
            layer["router_bias"] = f32(f"{m}.gate.e_score_correction_bias")
            if cfg.moe_latent_size:
                layer["w_latent_down"] = w(f"{m}.fc1_latent_proj.weight")
                layer["w_latent_up"] = w(f"{m}.fc2_latent_proj.weight")
            lo = cfg.expert_held_offset
            for ours, theirs in (("w_up", "up_proj"), ("w_down", "down_proj")):
                layer[ours] = jnp.stack([
                    w(f"{m}.experts.{e}.{theirs}.weight")
                    for e in range(lo, lo + cfg.experts_here)
                ])
            if cfg.n_shared_experts:
                layer["w_shared_up"] = w(f"{m}.shared_experts.up_proj.weight")
                layer["w_shared_down"] = w(
                    f"{m}.shared_experts.down_proj.weight")
        layers.append(layer)
    V = cfg.vocab_size               # a share holds the leading rows
    return {
        "embed": w("backbone.embeddings.weight", transpose=False)[:V],
        "layers": layers,
        "ln_f": w("backbone.norm_f.weight", transpose=False),
        "lm_head": w("lm_head.weight")[:, :V],
    }


def _load_lfm2_layers(cfg: ModelConfig, tensors: dict, w) -> Params:
    """The params of an LFM2 model (``cfg.layer_types``; HF ``lfm2_moe``)
    from its checkpoint tensors: ``model.layers.{i}.operator_norm`` and
    ``.ffn_norm``; a conv layer's ``conv.in_proj``, ``conv.conv`` ([D, 1,
    K] -> our [K, D]) and ``conv.out_proj``; an attention layer's
    ``self_attn.{q,k,v}_proj``, ``out_proj`` and ``{q,k}_layernorm``; a
    dense layer's ``feed_forward.{w1,w3,w2}`` (gate, up, down); an expert
    layer's ``feed_forward.gate``, ``expert_bias`` and
    ``experts.{e}.{w1,w3,w2}``; ``model.embedding_norm`` behind the last
    layer. ``w(name)`` reads a tensor transposed to [in, out]. No
    checkpoint of the family is here: the names are the public modelling
    code's, exercised on a seeded state dict (tests/test_lfm2.py)."""
    layers = []
    for i in range(cfg.num_layers):
        p = f"model.layers.{i}"
        layer = {
            "ln_attn": w(f"{p}.operator_norm.weight", transpose=False),
            "ln_mlp": w(f"{p}.ffn_norm.weight", transpose=False),
        }
        if cfg.layer_kind(i) == "conv":
            taps = jnp.asarray(tensors[f"{p}.conv.conv.weight"])
            layer.update(
                w_in=w(f"{p}.conv.in_proj.weight"),
                conv_w=taps[:, 0, :].T.astype(layer["ln_attn"].dtype),
                w_out=w(f"{p}.conv.out_proj.weight"),
            )
        else:
            a = f"{p}.self_attn"
            layer.update(
                wq=w(f"{a}.q_proj.weight"), wk=w(f"{a}.k_proj.weight"),
                wv=w(f"{a}.v_proj.weight"), wo=w(f"{a}.out_proj.weight"),
                ln_q_head=w(f"{a}.q_layernorm.weight", transpose=False),
                ln_k_head=w(f"{a}.k_layernorm.weight", transpose=False),
            )
        f = f"{p}.feed_forward"
        names = (("w_gate", "w1"), ("w_up", "w3"), ("w_down", "w2"))
        if cfg.moe_layer(i):
            layer["w_router"] = w(f"{f}.gate.weight")
            layer["router_bias"] = (
                jnp.asarray(tensors[f"{f}.expert_bias"], jnp.float32)
                if cfg.use_expert_bias
                else jnp.zeros((cfg.num_experts,), jnp.float32)
            )
            lo = cfg.expert_held_offset
            for ours, theirs in names:
                layer[ours] = jnp.stack([
                    w(f"{f}.experts.{e}.{theirs}.weight")
                    for e in range(lo, lo + cfg.experts_here)
                ])
        else:
            for ours, theirs in names:
                layer[ours] = w(f"{f}.{theirs}.weight")
        layers.append(layer)
    params = {
        "embed": w("model.embed_tokens.weight", transpose=False),
        "layers": layers,
        "ln_f": w("model.embedding_norm.weight", transpose=False),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = w("lm_head.weight")
    return params


def load_hf_weights(
    cfg: ModelConfig,
    model_dir: str,
    dtype=jnp.bfloat16,
    policy: WeightQuantPolicy | None = None,
) -> Params:
    """Load params from a HF checkout's safetensors shards (torch [out,in]
    weights transposed to our [in,out] layout).

    With a ``policy`` (WeightQuantPolicy) each selected weight quantizes
    AS ITS LAYER LOADS — the full-precision transient never exceeds one
    layer, so the resident tree is quantized from the start and the
    bf16 copy of the model never materializes (the same discipline as
    ops/quant.py init_params_policy for random init)."""
    import glob
    import os

    import numpy as np
    from safetensors import safe_open

    fmts = policy_layer_fmts(policy) if policy is not None else {}

    def quantize_layer(layer: Params) -> Params:
        for k, fmt in fmts.items():
            if k in layer:
                layer[k] = quantize_weight(
                    layer[k], axis=QUANT_AXES.get(k, CONTRACT_AXIS), fmt=fmt
                )
        return layer

    tensors: dict[str, np.ndarray] = {}
    files = sorted(glob.glob(os.path.join(model_dir, "*.safetensors")))
    if not files:
        raise FileNotFoundError(f"no safetensors in {model_dir}")
    for path in files:
        with safe_open(path, framework="np") as f:
            for name in f.keys():
                tensors[name] = f.get_tensor(name)

    def w(name: str, transpose: bool = True) -> jnp.ndarray:
        arr = tensors[name]
        if transpose and arr.ndim == 2:
            arr = arr.T
        return jnp.asarray(arr, dtype=dtype)

    if cfg.layer_pattern or cfg.layer_types:
        if policy is not None and policy.active:
            raise NotImplementedError(
                "load_hf_weights: a weight-quant policy over a model whose "
                "layers are one part each, or whose mixers are convolutions, "
                "is not implemented"
            )
        load = _load_one_part_layers if cfg.layer_pattern else _load_lfm2_layers
        return load(cfg, tensors, w)
    layers = []
    for i in range(cfg.num_layers):
        p = f"model.layers.{i}"
        if cfg.layer_kind(i) == "kda":
            # The KDA mixer's checkpoint names are not mapped (the benchmark
            # serves seeded weights): say which tensors are left over
            # rather than load a layer without its mixer.
            raise NotImplementedError(
                f"load_hf_weights: layer {i} is a KDA linear-attention "
                "layer, whose checkpoint tensors are not mapped: "
                + ", ".join(sorted(n for n in tensors if n.startswith(p + ".")))
            )
        if cfg.layer_kind(i) == "retention":
            raise NotImplementedError(
                f"load_hf_weights: layer {i} is a power-retention layer, "
                "whose gate's checkpoint tensor is not mapped: "
                + ", ".join(sorted(n for n in tensors if n.startswith(p + ".")))
            )
        if cfg.is_mla:
            # DeepSeek-V2/V3 MLA layout. kv_b_proj packs per-head
            # [k_nope ‖ v] up-projections over the latent; split it into
            # the absorbed w_uk [H, dn, dc] / w_uv [H, v, dc] our
            # attention uses (models/llama.py _qkv_mla). HF DeepSeek
            # stores the roped dims PAIR-INTERLEAVED and permutes them to
            # half-split at runtime (modeling's view/transpose before
            # rotate_half); we bake that permutation into the q_pe
            # columns / k_pe rows at load so ops/rope.py's NeoX halves
            # reproduce the checkpoint's numerics exactly.
            dn, dr, dc = (
                cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.kv_lora_rank
            )
            H, dv = cfg.num_heads, cfg.v_head_dim
            perm = np.concatenate(
                [np.arange(0, dr, 2), np.arange(1, dr, 2)]
            )  # interleaved pairs -> [evens ‖ odds] (NeoX halves)

            def permute_q(arr):  # [in, H*(dn+dr)] our layout, post-.T
                qr = arr.reshape(arr.shape[0], H, dn + dr)
                pe = qr[..., dn:][..., perm]
                return jnp.concatenate(
                    [qr[..., :dn], pe], axis=-1
                ).reshape(arr.shape[0], H * (dn + dr))

            kvb = tensors[f"{p}.self_attn.kv_b_proj.weight"]  # [H*(dn+dv), dc]
            kvb = kvb.reshape(H, dn + dv, dc)
            dkv = np.asarray(tensors[f"{p}.self_attn.kv_a_proj_with_mqa.weight"]).T
            dkv = np.concatenate([dkv[:, :dc], dkv[:, dc:][:, perm]], axis=1)
            layer = {
                "w_dkv": jnp.asarray(dkv, dtype=dtype),
                "ln_kv": w(f"{p}.self_attn.kv_a_layernorm.weight",
                           transpose=False),
                "w_uk": jnp.asarray(kvb[:, :dn, :], dtype=dtype),
                "w_uv": jnp.asarray(kvb[:, dn:, :], dtype=dtype),
                "wo": w(f"{p}.self_attn.o_proj.weight"),
                "ln_attn": w(f"{p}.input_layernorm.weight", transpose=False),
                "ln_mlp": w(f"{p}.post_attention_layernorm.weight",
                            transpose=False),
            }
            if cfg.q_lora_rank:
                layer["w_dq"] = w(f"{p}.self_attn.q_a_proj.weight")
                layer["ln_q"] = w(f"{p}.self_attn.q_a_layernorm.weight",
                                  transpose=False)
                layer["w_uq"] = permute_q(w(f"{p}.self_attn.q_b_proj.weight"))
            else:
                layer["wq"] = permute_q(w(f"{p}.self_attn.q_proj.weight"))
        else:
            layer = {
                "wq": w(f"{p}.self_attn.q_proj.weight"),
                "wk": w(f"{p}.self_attn.k_proj.weight"),
                "wv": w(f"{p}.self_attn.v_proj.weight"),
                "wo": w(f"{p}.self_attn.o_proj.weight"),
                "ln_attn": w(f"{p}.input_layernorm.weight", transpose=False),
            }
            if cfg.post_norms:
                # Gemma-3 sandwich norms: HF post_attention_layernorm is
                # the POST-attention branch norm; the MLP pre-norm is
                # pre_feedforward_layernorm.
                layer["ln_post_attn"] = w(
                    f"{p}.post_attention_layernorm.weight", transpose=False
                )
                layer["ln_mlp"] = w(
                    f"{p}.pre_feedforward_layernorm.weight", transpose=False
                )
                layer["ln_post_mlp"] = w(
                    f"{p}.post_feedforward_layernorm.weight", transpose=False
                )
            else:
                layer["ln_mlp"] = w(
                    f"{p}.post_attention_layernorm.weight", transpose=False
                )
        if cfg.moe_layer(i):
            if f"{p}.block_sparse_moe.gate.weight" in tensors:
                # Mixtral layout: block_sparse_moe.gate + per-expert
                # w1/w3/w2 (gate/up/down), stacked over the expert dim.
                m = f"{p}.block_sparse_moe"
                enames = ("w1.weight", "w3.weight", "w2.weight")
            else:
                # DeepSeek layout: mlp.gate router (+ optional V3 bias),
                # mlp.experts.{e}.gate/up/down, mlp.shared_experts.*.
                m = f"{p}.mlp"
                enames = ("gate_proj.weight", "up_proj.weight",
                          "down_proj.weight")
            layer["w_router"] = w(f"{m}.gate.weight")
            bias_name = f"{m}.gate.e_score_correction_bias"
            if cfg.gating == "sigmoid":
                layer["router_bias"] = (
                    jnp.asarray(tensors[bias_name], jnp.float32)
                    if bias_name in tensors
                    else jnp.zeros((cfg.num_experts,), jnp.float32)
                )
            lo = cfg.expert_held_offset
            for key, ename in zip(("w_gate", "w_up", "w_down"), enames):
                layer[key] = jnp.stack(
                    [
                        w(f"{m}.experts.{e}.{ename}")
                        for e in range(lo, lo + cfg.experts_here)
                    ]
                )
            if cfg.n_shared_experts:
                layer["w_shared_gate"] = w(f"{m}.shared_experts.gate_proj.weight")
                layer["w_shared_up"] = w(f"{m}.shared_experts.up_proj.weight")
                layer["w_shared_down"] = w(f"{m}.shared_experts.down_proj.weight")
        else:
            layer["w_gate"] = w(f"{p}.mlp.gate_proj.weight")
            layer["w_up"] = w(f"{p}.mlp.up_proj.weight")
            layer["w_down"] = w(f"{p}.mlp.down_proj.weight")
        if cfg.qkv_bias:
            layer["bq"] = w(f"{p}.self_attn.q_proj.bias", transpose=False)
            layer["bk"] = w(f"{p}.self_attn.k_proj.bias", transpose=False)
            layer["bv"] = w(f"{p}.self_attn.v_proj.bias", transpose=False)
        if cfg.qk_norm:
            layer["ln_q_head"] = w(
                f"{p}.self_attn.q_norm.weight", transpose=False
            )
            layer["ln_k_head"] = w(
                f"{p}.self_attn.k_norm.weight", transpose=False
            )
        layers.append(quantize_layer(layer))

    embed = w("model.embed_tokens.weight", transpose=False)
    unembed_fmt = getattr(policy, "unembed", None)
    embed_fmt = getattr(policy, "embedding", None) or (
        unembed_fmt if cfg.tie_word_embeddings else None
    )
    if embed_fmt:
        # Per-ROW scales: the table is a gather (and, tied, the unembed
        # matmul operand whose output channels ARE the rows).
        embed = quantize_weight(embed, axis=-1, fmt=embed_fmt)
    params: Params = {
        "embed": embed,
        "layers": layers,
        "ln_f": w("model.norm.weight", transpose=False),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = (
            quantize_weight(w("lm_head.weight"), fmt=unembed_fmt)
            if unembed_fmt
            else w("lm_head.weight")
        )
    return params
