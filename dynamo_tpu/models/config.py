"""Model architecture configs.

Covers the Llama family tree (Llama-2/3/3.x, TinyLlama, Qwen2 via qkv_bias,
DeepSeek-R1-Distill-Llama) — the architectures named in BASELINE.md's
progression. Loadable from a HF checkout's config.json.
"""

from __future__ import annotations

import json
from dataclasses import dataclass, replace
from pathlib import Path


def _rope_scaling(d):
    from dynamo_tpu.ops.rope import RopeScaling

    return RopeScaling.from_hf(d)


@dataclass(frozen=True)
class LayerSpec:
    """What ``ModelConfig`` answers by layer index, as ONE hashable value
    (``ModelConfig.layer_spec``): the static operand of the served model's
    layer body (models/llama.py ``_layer``, ``_layer_rows``). Two layers are one traced and
    lowered program exactly when their specs and their operands' shapes are
    equal, so a fact that differs by layer and is not here would hand one
    layer another's behaviour in silence: every method of ``ModelConfig``
    that takes a layer index has its field (tests/test_layer_spec.py fails
    by name on one that has not), and no index reaches the body."""

    kind: str            # layer_kind: "attn" | "kda" | "retention"
    window: int          # layer_window: 0 = full attention
    cache_group: int     # layer_cache_group: whose slots and block table
    rope: "tuple | str"  # layer_rope: (theta, scaling) or "none"
    moe: bool            # moe_layer: routed experts, not a dense MLP
    swiglu_limit: float  # swiglu_limit: the routed experts' clamp
    shared_swiglu_limit: float  # swiglu_limit(shared=True)


@dataclass(frozen=True)
class ModelConfig:
    name: str = "llama"
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 14336
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int = 128
    rope_theta: float = 500000.0
    rms_eps: float = 1e-5
    max_position: int = 8192
    tie_word_embeddings: bool = False
    qkv_bias: bool = False  # Qwen2-style
    # Qwen3-style per-head RMSNorm on q/k (applied after the head reshape,
    # before rope).
    qk_norm: bool = False
    # Sliding-window attention (Mistral-style): each token attends to at
    # most the last `sliding_window` keys. 0 = full causal attention.
    sliding_window: int = 0
    # Qwen2-style layer gate: the FIRST max_window_layers layers run full
    # attention; only layers at or above it window. 0 = window every layer.
    max_window_layers: int = 0
    # --- Gemma-3 family knobs (models/llama.py; HF Gemma3TextConfig) ---
    # Gated-MLP activation: "silu" (Llama SwiGLU) or "gelu_tanh" (Gemma
    # GeGLU, HF hidden_activation="gelu_pytorch_tanh").
    hidden_act: str = "silu"
    # Gemma RMSNorm stores w and scales by (1 + w) — checkpoints init
    # norms at 0, not 1.
    norm_offset: bool = False
    # Sandwich norms: post-attention and post-feedforward RMSNorms on the
    # residual branches (Gemma-2/3 layer plan).
    post_norms: bool = False
    # Embedding rows are multiplied by sqrt(hidden_size) at lookup
    # (normalizer cast to the activation dtype, matching HF numerics).
    embed_scale: bool = False
    # Gemma-3 layer plan: every `window_pattern`-th layer ((i+1) % p == 0)
    # runs FULL attention, the rest sliding_window. 0 = no pattern.
    window_pattern: int = 0
    # Rope base for the windowed (local) layers; global layers keep
    # rope_theta (+ rope_scaling). 0 = single rope everywhere.
    rope_local_theta: float = 0.0
    # Attention score scale override: scores use 1/sqrt(this) instead of
    # 1/sqrt(head_dim) (HF query_pre_attn_scalar; applied as a q
    # pre-multiply so the kernels stay unchanged). 0 = head_dim.
    query_pre_attn_scalar: float = 0.0
    # Mixtral-style sparse MoE MLP: num_experts > 0 swaps each layer's
    # SwiGLU for top-k routed experts (models/moe.py; ep/tp sharding).
    num_experts: int = 0
    num_experts_per_tok: int = 2
    # Llama-3.1+ long-context rope scaling (ops/rope.py RopeScaling).
    rope_scaling: "object | None" = None
    # --- DeepSeek-V2/V3/R1 family (models/llama.py MLA branch) ---
    # kv_lora_rank > 0 enables MLA: K/V compress into one shared latent
    # vector per token; the paged cache stores [latent ‖ roped k_pe] as a
    # single "kv head" of kv_lora_rank + qk_rope_head_dim dims.
    kv_lora_rank: int = 0
    q_lora_rank: int = 0          # 0 = direct q projection (V2-Lite style)
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    # DeepSeekMoE: dense layers first, then shared + routed experts.
    n_shared_experts: int = 0
    moe_intermediate_size: int = 0  # routed/shared expert width (per expert)
    first_k_dense_replace: int = 0  # leading layers that keep dense MLP
    # Router scoring: "softmax" (Mixtral/V2) or "sigmoid" (V3/R1, with a
    # per-expert selection-bias correction term).
    gating: str = "softmax"
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    # Group-limited routing (DeepSeek "noaux_tc": experts partition into
    # n_group groups; only the topk_group best groups are eligible).
    n_group: int = 1
    topk_group: int = 1
    # --- block diffusion (SDAR family; docs/architecture/unified_step.md
    # "The block step") --- diffusion_block_length B > 0: attention is
    # masked BY BLOCK (key j visible to query i iff j // B <= i // B),
    # logits are read unshifted, and generation feeds a block of
    # mask_token_id rows and commits the confident ones a pass: every
    # masked row whose sampled token's probability reaches
    # confidence_threshold, and at least ceil(B / denoising_steps) of the
    # most confident. 0 = causal, one token a step.
    diffusion_block_length: int = 0
    mask_token_id: int = 0
    denoising_steps: int = 0
    confidence_threshold: float = 0.0
    # --- hybrid linear attention (Ling-3.0 family, model_type
    # bailing_hybrid; docs/architecture/unified_step.md "State that is not
    # pages") --- layer_group_size G > 0: layer l is softmax attention iff
    # (l + 1) % G == 0, every other layer is a delta-rule linear-attention
    # (KDA) layer that keeps a recurrent state of num_heads x head_dim x
    # head_dim in float32 and the last linear_conv_kernel - 1 rows of its
    # depthwise convolution's input a sequence, no keys and values.
    layer_group_size: int = 0
    linear_conv_kernel: int = 0
    # The log of a KDA layer's per-channel decay lies in (kda_lower_bound, 0).
    kda_lower_bound: float = 0.0
    # --- an expert layer told which experts it holds (docs/architecture/
    # expert_share.md) --- num_experts_held E_h > 0: the router keeps its
    # num_experts outputs and its experts a token; this chip holds experts
    # [expert_held_offset, expert_held_offset + E_h) and computes their part
    # of a row's result. 0 = every expert is here.
    num_experts_held: int = 0
    expert_held_offset: int = 0
    # SwiGLU clamp a layer (gate.clamp(max=L), up.clamp(-L, L); 0 = none),
    # of the routed experts and of the shared expert. () = none anywhere.
    expert_swiglu_limit: tuple = ()
    shared_swiglu_limit: tuple = ()
    # --- Command A+ family (model_type cohere2_moe; docs/architecture/
    # cache_groups.md) --- parallel_block: ONE norm a layer, and attention
    # and the FFN both read it: x + attn(h) + ffn(h). norm_centered: that
    # norm (and the final one) is a LayerNorm without bias, (x - mean) /
    # sqrt(var + eps) * w. rope_interleaved: rotary pairs are the adjacent
    # channels (2i, 2i + 1) (HF rope_gptj), not the two halves.
    # nope_full_layers: a full-attention layer of the window pattern takes
    # NO rotary embedding. logit_scale multiplies the logits.
    # shared_experts_average: the shared experts' sum is divided by
    # n_shared_experts before it is added to the routed sum.
    # cache_by_layer_group: the window layers and the full-attention
    # layers keep a pool and a block table each (``cache_groups``). Off, a
    # model that has both kinds serves over ONE pool and table as before
    # (Gemma-3, Qwen2 with max_window_layers: the windows mask, nothing is
    # released, and everything that reads one table as the whole past
    # serves: prefix caching, a mesh, speculation, int8 KV, KVBM,
    # disaggregation). embed_init_std: the embedding rows of SEEDED weights
    # are drawn at this deviation (0 = 1 / sqrt(vocab_size), as every other
    # family). At that default a row is a hundredth of a layer's output,
    # the stream behind layer 0 is the attention's context mean, alike
    # for every token, and every token picks the same few experts: which
    # share of the rows an expert share holds is then the seed's luck
    # (5.9-18.9 % where an eighth is due: PERF.md section 6, PR 45). At 1 a
    # token's own row leads the stream and the choices are a token's own.
    parallel_block: bool = False
    norm_centered: bool = False
    rope_interleaved: bool = False
    nope_full_layers: bool = False
    logit_scale: float = 1.0
    shared_experts_average: bool = False
    cache_by_layer_group: bool = False
    embed_init_std: float = 0.0
    # --- power retention (Brumby family, model_type brumby; arXiv:2507.04239;
    # docs/architecture/unified_step.md "State that is not pages") ---
    # retention_degree p > 0 (2 is the one implemented): EVERY layer is a
    # power-retention layer: the q/k/v projections, per-head q/k norms and
    # rotary embedding of an attention layer, a log-sigmoid gate a cached
    # head, and in place of keys and values the gated sum of the keys'
    # symmetric p-th powers against their values: num_kv_heads x (head_dim
    # x (head_dim / 2 + 1)) x head_dim in float32 a sequence (ops/
    # power_retention.py). No layer pages: the model has no pool.
    retention_degree: int = 0

    @property
    def is_moe(self) -> bool:
        return self.num_experts > 0

    @property
    def experts_here(self) -> int:
        """Routed experts whose weights this chip holds."""
        return self.num_experts_held or self.num_experts

    def layer_kind(self, layer_idx: int) -> str:
        """"kda" for a delta-rule linear-attention layer and "retention"
        for a power-retention layer (both keep a recurrent state), "attn"
        for one that reads keys and values through the paged cache."""
        if self.retention_degree:
            return "retention"
        if self.layer_group_size and (layer_idx + 1) % self.layer_group_size:
            return "kda"
        return "attn"

    @property
    def recurrent_layers(self) -> tuple:
        """The layers that keep a recurrent state, in order."""
        return tuple(
            li for li in range(self.num_layers)
            if self.layer_kind(li) != "attn"
        )

    @property
    def has_recurrent(self) -> bool:
        return bool(self.recurrent_layers)

    @property
    def has_pool(self) -> bool:
        """Does any layer keep keys and values in pages? A model none of
        whose layers does has no pool, no block table and no allocator:
        state slots are its only resource."""
        return bool(self.cache_groups)

    def recurrent_state_arrays(
        self, layer_idx: int, n_slots: int, dtype: str
    ) -> tuple:
        """THE place that says what a recurrent layer keeps: ``((shape,
        dtype), ...)`` of its arrays over ``n_slots`` slots of the state
        table, by the layer's kind; ``()`` for a layer that pages.
        ``dtype`` is the served activation dtype. "kda": the state ``[N,
        H, d, d]`` float32 and the convolution's tail ``[N, K - 1, 3 H d]``.
        "retention": the gated sum of ``phi(k) v^T``, ``[N, kvH, R, d, d]``
        float32, and of ``phi(k)``, ``[N, kvH, R (padded to 8), d]``
        float32 (ops/power_retention.py ``state_shapes``)."""
        kind = self.layer_kind(layer_idx)
        H, d = self.num_heads, self.head_dim
        if kind == "kda":
            return (
                ((n_slots, H, d, d), "float32"),
                ((n_slots, self.linear_conv_kernel - 1, 3 * H * d), dtype),
            )
        if kind == "retention":
            from dynamo_tpu.ops.power_retention import state_shapes

            return tuple(
                (shape, "float32")
                for shape in state_shapes(n_slots, self.num_kv_heads, d)
            )
        return ()

    def recurrent_state_bytes(self, n_slots: int, dtype: str) -> int:
        """Bytes of the whole state table over ``n_slots`` slots."""
        import math

        import numpy as np

        return sum(
            math.prod(shape) * np.dtype(dt).itemsize
            for li in range(self.num_layers)
            for shape, dt in self.recurrent_state_arrays(li, n_slots, dtype)
        )

    def swiglu_limit(self, layer_idx: int, shared: bool = False) -> float:
        limits = self.shared_swiglu_limit if shared else self.expert_swiglu_limit
        return float(limits[layer_idx]) if layer_idx < len(limits) else 0.0

    @property
    def is_mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def kv_cache_head_dim(self) -> int:
        """Logical per-head cache width (pre-Pallas-padding)."""
        return (
            self.kv_lora_rank + self.qk_rope_head_dim
            if self.is_mla
            else self.head_dim
        )

    @property
    def num_cache_heads(self) -> int:
        return 1 if self.is_mla else self.num_kv_heads

    def moe_layer(self, layer_idx: int) -> bool:
        """Does this layer use the routed-experts MLP?"""
        return self.is_moe and layer_idx >= self.first_k_dense_replace

    def layer_window(self, layer_idx: int) -> int:
        """Sliding-window size for one layer (0 = full attention): HF
        Qwen2 runs the first max_window_layers layers full-attention;
        Gemma-3 and Command A+ make every window_pattern-th layer global.
        The layers of one window are one cache group (``cache_groups``):
        they share a pool and a block table."""
        if not self.sliding_window:
            return 0
        if self.window_pattern:
            if (layer_idx + 1) % self.window_pattern == 0:
                return 0  # global layer
            return self.sliding_window
        if layer_idx >= self.max_window_layers:
            return self.sliding_window
        return 0

    def layer_rope(self, layer_idx: int):
        """(theta, scaling) of one layer's rotary embedding, or "none":
        Gemma-3 runs its windowed (local) layers on rope_local_theta with NO
        position scaling; global layers keep rope_theta + rope_scaling (HF
        Gemma3 rope_local_base_freq). A full-attention layer of a model with
        ``nope_full_layers`` (Command A+) takes no rotary embedding at all."""
        window = self.layer_window(layer_idx)
        if self.nope_full_layers and not window:
            return "none"
        if self.rope_local_theta and window:
            return self.rope_local_theta, None
        return self.rope_theta, self.rope_scaling

    def layer_spec(self, layer_idx: int) -> LayerSpec:
        """Every per-layer fact of one layer (``LayerSpec``)."""
        return LayerSpec(
            kind=self.layer_kind(layer_idx),
            window=self.layer_window(layer_idx),
            cache_group=self.layer_cache_group(layer_idx),
            rope=self.layer_rope(layer_idx),
            moe=self.moe_layer(layer_idx),
            swiglu_limit=self.swiglu_limit(layer_idx),
            shared_swiglu_limit=self.swiglu_limit(layer_idx, shared=True),
        )

    @property
    def cache_groups(self) -> tuple:
        """The paged cache by layer group (docs/architecture/
        cache_groups.md): each group's window, the full-attention group
        (0) first. A group has its own pool and block table; a windowed
        group's blocks are released behind the window as a sequence
        advances (the rolling buffer, per layer group). One entry where
        the attention layers are all of one kind (Mistral: its window);
        the distinct ``layer_window`` values where they are not and the
        model says ``cache_by_layer_group``; else ``(0,)``: one pool keeps
        every layer's whole history. ``()`` where NO layer keeps keys and
        values (every layer a recurrent one): the model has no pool."""
        windows = {
            self.layer_window(li) for li in range(self.num_layers)
            if self.layer_kind(li) == "attn"
        }
        if len(windows) > 1 and not self.cache_by_layer_group:
            return (0,)
        return tuple(sorted(windows))

    def layer_cache_group(self, layer_idx: int) -> int:
        """Which of ``cache_groups`` a layer's keys and values live in."""
        groups = self.cache_groups
        if len(groups) <= 1:  # one pool, or none at all (group 0 of none)
            return 0
        return groups.index(self.layer_window(layer_idx))

    def group_layers(self, group: int) -> int:
        """How many layers keep keys and values in cache group ``group``."""
        return sum(
            self.layer_kind(li) == "attn"
            and self.layer_cache_group(li) == group
            for li in range(self.num_layers)
        )

    @staticmethod
    def from_hf(model_dir: str) -> "ModelConfig":
        cfg = json.loads((Path(model_dir) / "config.json").read_text())
        arch = (cfg.get("architectures") or ["LlamaForCausalLM"])[0]
        if arch.startswith("Gemma2") or cfg.get("attn_logit_softcapping"):
            raise NotImplementedError(
                "Gemma-2 attention-logit softcapping is not implemented; "
                "the Gemma-3 family (softcap-free) is supported"
            )
        if arch.startswith("Gemma3") or "gemma3" in cfg.get("model_type", ""):
            if "text_config" in cfg:  # multimodal wrapper config
                cfg = {**cfg["text_config"],
                       "model_type": cfg.get("model_type", "gemma3")}
            return ModelConfig._from_hf_gemma3(cfg)
        num_heads = cfg["num_attention_heads"]
        hidden = cfg["hidden_size"]
        deepseek = "Deepseek" in arch or "deepseek" in cfg.get("model_type", "")
        sdar = cfg.get("model_type", "").startswith("sdar")
        if cfg.get("model_type") == "bailing_hybrid":
            return ModelConfig._from_hf_bailing_hybrid(cfg)
        if cfg.get("model_type") == "cohere2_moe":
            return ModelConfig._from_hf_cohere2_moe(cfg)
        if cfg.get("model_type") == "brumby":
            return ModelConfig._from_hf_brumby(cfg)
        return ModelConfig(
            name=cfg.get("model_type", "llama"),
            vocab_size=cfg["vocab_size"],
            hidden_size=hidden,
            intermediate_size=cfg["intermediate_size"],
            num_layers=cfg["num_hidden_layers"],
            num_heads=num_heads,
            num_kv_heads=cfg.get("num_key_value_heads", num_heads),
            head_dim=cfg.get("head_dim", hidden // num_heads),
            rope_theta=cfg.get("rope_theta", 10000.0),
            rms_eps=cfg.get("rms_norm_eps", 1e-5),
            max_position=cfg.get("max_position_embeddings", 8192),
            tie_word_embeddings=cfg.get("tie_word_embeddings", False),
            qkv_bias="Qwen2" in arch,
            # SDAR's config.json has no key for them; its layers are
            # Qwen3's, per-head q/k norms included.
            qk_norm="Qwen3" in arch or sdar,
            # Mistral carries sliding_window unconditionally (null = full
            # attention in v0.2+); Qwen2 gates it behind use_sliding_window.
            sliding_window=int(cfg.get("sliding_window") or 0)
            if cfg.get("use_sliding_window", True)
            else 0,
            max_window_layers=int(cfg.get("max_window_layers") or 0)
            if cfg.get("use_sliding_window", True)
            else 0,
            # DeepSeek uses n_routed_experts; Mixtral num_local_experts;
            # Qwen3-MoE and SDAR num_experts.
            num_experts=cfg.get(
                "n_routed_experts",
                cfg.get("num_local_experts", cfg.get("num_experts", 0)),
            ) or 0,
            num_experts_per_tok=cfg.get("num_experts_per_tok", 2),
            rope_scaling=_rope_scaling(cfg.get("rope_scaling")),
            kv_lora_rank=(cfg.get("kv_lora_rank") or 0) if deepseek else 0,
            q_lora_rank=(cfg.get("q_lora_rank") or 0) if deepseek else 0,
            qk_nope_head_dim=cfg.get("qk_nope_head_dim", 128),
            qk_rope_head_dim=cfg.get("qk_rope_head_dim", 64),
            v_head_dim=cfg.get("v_head_dim", cfg.get("head_dim", hidden // num_heads)),
            n_shared_experts=cfg.get("n_shared_experts", 0) or 0,
            moe_intermediate_size=cfg.get("moe_intermediate_size", 0) or 0,
            first_k_dense_replace=cfg.get("first_k_dense_replace", 0) or 0,
            gating="sigmoid" if cfg.get("scoring_func") == "sigmoid" else "softmax",
            norm_topk_prob=cfg.get("norm_topk_prob", True),
            routed_scaling_factor=cfg.get("routed_scaling_factor", 1.0),
            n_group=cfg.get("n_group", 1) or 1,
            topk_group=cfg.get("topk_group", 1) or 1,
            # Generation settings the family's config.json does not carry
            # (its generate script's defaults).
            **(SDAR_GENERATION if sdar else {}),
        )

    @staticmethod
    def _from_hf_bailing_hybrid(cfg: dict) -> "ModelConfig":
        """HF ``bailing_hybrid`` config.json (Ling-3.0) -> ModelConfig:
        KDA linear-attention layers with one latent-attention layer a
        group, sigmoid-scored experts with a selection bias behind the
        leading dense layers. The multi-token-prediction module
        (num_nextn_predict_layers) is not served."""
        if cfg.get("use_kda_lora") or not cfg.get("no_kda_lora", True):
            raise NotImplementedError(
                "a low-rank KDA decay projection is not implemented "
                "(no_kda_lora: true is)"
            )
        if cfg.get("gated_attention_proj_granularity_type", "head_wise") \
                != "head_wise":
            raise NotImplementedError(
                "only the head_wise output gate of the KDA layers is "
                "implemented"
            )
        return ModelConfig(
            name=cfg["model_type"],
            vocab_size=cfg["vocab_size"],
            hidden_size=cfg["hidden_size"],
            intermediate_size=cfg["intermediate_size"],
            num_layers=cfg["num_hidden_layers"],
            num_heads=cfg["num_attention_heads"],
            num_kv_heads=cfg.get(
                "num_key_value_heads", cfg["num_attention_heads"]),
            head_dim=cfg["head_dim"],
            rope_theta=cfg.get("rope_theta", 10000.0),
            rms_eps=cfg.get("rms_norm_eps", 1e-6),
            max_position=cfg.get("max_position_embeddings", 8192),
            tie_word_embeddings=cfg.get("tie_word_embeddings", False),
            rope_scaling=_rope_scaling(cfg.get("rope_scaling")),
            kv_lora_rank=cfg["kv_lora_rank"],
            q_lora_rank=cfg.get("q_lora_rank") or 0,
            qk_nope_head_dim=cfg["qk_nope_head_dim"],
            qk_rope_head_dim=cfg["qk_rope_head_dim"],
            v_head_dim=cfg["v_head_dim"],
            num_experts=cfg["num_experts"],
            num_experts_per_tok=cfg["num_experts_per_tok"],
            n_shared_experts=cfg.get("num_shared_experts", 0) or 0,
            moe_intermediate_size=cfg["moe_intermediate_size"],
            first_k_dense_replace=cfg.get("first_k_dense_replace", 0) or 0,
            gating="sigmoid" if cfg.get("score_function") == "sigmoid"
            else "softmax",
            norm_topk_prob=cfg.get("norm_topk_prob", True),
            routed_scaling_factor=cfg.get("routed_scaling_factor", 1.0),
            n_group=cfg.get("n_group", 1) or 1,
            topk_group=cfg.get("topk_group", 1) or 1,
            layer_group_size=cfg["layer_group_size"],
            linear_conv_kernel=cfg["short_conv_kernel_size"],
            kda_lower_bound=float(cfg["kda_lower_bound"]),
            expert_swiglu_limit=tuple(
                cfg.get("expert_swiglu_limit_list") or ()),
            shared_swiglu_limit=tuple(
                cfg.get("share_expert_swiglu_limit_list") or ()),
        )

    @staticmethod
    def _from_hf_brumby(cfg: dict) -> "ModelConfig":
        """HF ``brumby`` config.json (Brumby-14B-Base) -> ModelConfig:
        Qwen3's dense decoder with every attention layer a power-retention
        layer. The config carries the widths and ``rope_theta`` and nothing
        of the retention itself: the degree (2), the gate and the
        normaliser are the program's (the preset's docstring)."""
        if cfg.get("sliding_window") is not None and cfg.get(
                "use_sliding_window", True):
            raise NotImplementedError(
                "brumby with a sliding_window that is not null is not "
                "implemented: a retention layer keeps no keys to window"
            )
        unserved = {
            "attention_bias": cfg.get("attention_bias"),
            "rope_scaling": cfg.get("rope_scaling"),
            "an activation that is not silu":
                cfg.get("hidden_act", "silu") != "silu",
        }
        for what, on in unserved.items():
            if on:
                raise NotImplementedError(
                    f"brumby with {what} is not implemented")
        heads = cfg["num_attention_heads"]
        return ModelConfig(
            name=cfg["model_type"],
            vocab_size=cfg["vocab_size"],
            hidden_size=cfg["hidden_size"],
            intermediate_size=cfg["intermediate_size"],
            num_layers=cfg["num_hidden_layers"],
            num_heads=heads,
            num_kv_heads=cfg.get("num_key_value_heads", heads),
            head_dim=cfg.get("head_dim", cfg["hidden_size"] // heads),
            rope_theta=float(cfg.get("rope_theta", 10000.0)),
            rms_eps=cfg.get("rms_norm_eps", 1e-6),
            max_position=cfg.get("max_position_embeddings", 32768),
            tie_word_embeddings=cfg.get("tie_word_embeddings", False),
            qk_norm=True,
            retention_degree=2,
        )

    @staticmethod
    def _from_hf_cohere2_moe(cfg: dict) -> "ModelConfig":
        """HF ``cohere2_moe`` config.json (Command A+) -> ModelConfig: a
        parallel attention + FFN block under one bias-free LayerNorm,
        ``layer_switch - 1`` sliding-window layers with interleaved rotary
        pairs to one full-attention layer without rotary embedding,
        sigmoid-selected experts (no groups, no bias) beside averaged
        shared experts, tied embeddings under ``logit_scale``. The vision
        tower is not part of this config and is not served."""
        period = int(cfg.get("layer_switch") or 0)
        n = cfg["num_hidden_layers"]
        want = [
            "full_attention" if period and (li + 1) % period == 0
            else "sliding_attention" for li in range(n)
        ]
        if list(cfg.get("layer_types") or want) != want:
            raise NotImplementedError(
                "cohere2_moe: layer_types is not (layer_switch - 1) "
                f"sliding_attention layers to one full_attention layer "
                f"(layer_switch={period}); only that pattern is implemented"
            )
        unserved = {
            "first_k_dense_replace (leading dense layers)":
                cfg.get("first_k_dense_replace"),
            "use_qk_norm": cfg.get("use_qk_norm"),
            "attention_bias": cfg.get("attention_bias"),
            "a partial rotary_pct": cfg.get("rotary_pct", 1) != 1,
            "an ungated activation": not cfg.get("use_gated_activation", True),
            "a block that is not parallel": not cfg.get(
                "use_parallel_block", True),
            "untied embeddings": not cfg.get("tie_word_embeddings", True),
            "softmax expert selection":
                cfg.get("expert_selection_fn", "sigmoid") != "sigmoid",
            "shared experts that are not averaged": cfg.get(
                "shared_expert_combination_strategy", "average") != "average",
            "rotary pairs that are not interleaved": cfg.get(
                "position_embedding_type", "rope_gptj") != "rope_gptj",
        }
        for what, on in unserved.items():
            if on:
                raise NotImplementedError(
                    f"cohere2_moe with {what} is not implemented")
        return ModelConfig(
            name=cfg["model_type"],
            vocab_size=cfg["vocab_size"],
            hidden_size=cfg["hidden_size"],
            intermediate_size=cfg["intermediate_size"],
            num_layers=n,
            num_heads=cfg["num_attention_heads"],
            num_kv_heads=cfg["num_key_value_heads"],
            head_dim=cfg["head_dim"],
            rope_theta=cfg.get("rope_theta", 50000.0),
            rms_eps=cfg.get("layer_norm_eps", 1e-5),
            max_position=cfg.get("max_position_embeddings", 8192),
            tie_word_embeddings=True,
            sliding_window=int(cfg.get("sliding_window") or 0),
            window_pattern=period,
            num_experts=cfg["num_experts"],
            num_experts_per_tok=cfg["num_experts_per_tok"],
            n_shared_experts=cfg.get("num_shared_experts", 0) or 0,
            # config.json has no key of its own for one expert's width:
            # intermediate_size is read as it (the catalog's inference).
            moe_intermediate_size=cfg["intermediate_size"],
            gating="sigmoid",
            norm_topk_prob=cfg.get("norm_topk_prob", True),
            parallel_block=True,
            norm_centered=True,
            rope_interleaved=True,
            nope_full_layers=True,
            logit_scale=float(cfg.get("logit_scale", 1.0)),
            shared_experts_average=True,
            cache_by_layer_group=True,
            embed_init_std=1.0,
        )

    @staticmethod
    def _from_hf_gemma3(cfg: dict) -> "ModelConfig":
        """HF Gemma3TextConfig → ModelConfig (Gemma-3 1B/4B/12B/27B text
        trunk: GeGLU, (1+w) norms, sandwich norms, scaled embeddings,
        QK-norm, 5-local:1-global window pattern with a separate local
        rope base)."""
        if cfg.get("final_logit_softcapping") or cfg.get(
            "attn_logit_softcapping"
        ):
            raise NotImplementedError(
                "Gemma logit softcapping is not implemented"
            )
        # Published multimodal checkpoints (gemma-3-4b/12b/27b) ship SPARSE
        # text_configs that rely on HF Gemma3TextConfig defaults — fill
        # them in (values from transformers Gemma3TextConfig()).
        defaults = {
            "vocab_size": 262208,
            "hidden_size": 2304,
            "intermediate_size": 9216,
            "num_hidden_layers": 26,
            "num_attention_heads": 8,
            "num_key_value_heads": 4,
            "head_dim": 256,
            "rope_theta": 1_000_000.0,
            "rope_local_base_freq": 10_000.0,
            "sliding_window": 4096,
            "sliding_window_pattern": 6,
            "rms_norm_eps": 1e-6,
            "max_position_embeddings": 131072,
            "tie_word_embeddings": True,
            "query_pre_attn_scalar": 256,
        }
        cfg = {**defaults, **{k: v for k, v in cfg.items() if v is not None}}
        # The global/local layer plan ships either as sliding_window_pattern
        # (config.json) or as an explicit layer_types list (newer HF).
        pattern = int(cfg.get("sliding_window_pattern") or 0)
        lt = cfg.get("layer_types")
        if lt and "full_attention" in lt:
            pattern = lt.index("full_attention") + 1
        return ModelConfig(
            name=cfg.get("model_type", "gemma3"),
            vocab_size=cfg["vocab_size"],
            hidden_size=cfg["hidden_size"],
            intermediate_size=cfg["intermediate_size"],
            num_layers=cfg["num_hidden_layers"],
            num_heads=cfg["num_attention_heads"],
            num_kv_heads=cfg["num_key_value_heads"],
            head_dim=cfg["head_dim"],
            rope_theta=cfg["rope_theta"],
            rms_eps=cfg["rms_norm_eps"],
            max_position=cfg["max_position_embeddings"],
            tie_word_embeddings=cfg["tie_word_embeddings"],
            qk_norm=True,
            sliding_window=int(cfg["sliding_window"] or 0),
            window_pattern=pattern,
            rope_local_theta=float(cfg["rope_local_base_freq"] or 0.0),
            rope_scaling=_rope_scaling(cfg.get("rope_scaling")),
            hidden_act="gelu_tanh",
            norm_offset=True,
            post_norms=True,
            embed_scale=True,
            query_pre_attn_scalar=float(cfg["query_pre_attn_scalar"] or 0.0),
        )

    @staticmethod
    def gemma3_1b() -> "ModelConfig":
        """Gemma-3 1B text (HF google/gemma-3-1b-pt config.json)."""
        return ModelConfig(
            name="gemma3-1b",
            vocab_size=262144,
            hidden_size=1152,
            intermediate_size=6912,
            num_layers=26,
            num_heads=4,
            num_kv_heads=1,
            head_dim=256,
            rope_theta=1_000_000.0,
            rms_eps=1e-6,
            max_position=32768,
            tie_word_embeddings=True,
            qk_norm=True,
            sliding_window=512,
            window_pattern=6,
            rope_local_theta=10000.0,
            hidden_act="gelu_tanh",
            norm_offset=True,
            post_norms=True,
            embed_scale=True,
            query_pre_attn_scalar=256.0,
        )

    @staticmethod
    def tiny_gemma_test(vocab_size: int = 384) -> "ModelConfig":
        """Hermetic Gemma-3-style test model: every family knob on, with a
        window pattern that exercises local AND global layers."""
        return ModelConfig(
            name="tiny-gemma-test",
            vocab_size=vocab_size,
            hidden_size=64,
            intermediate_size=128,
            num_layers=4,
            num_heads=4,
            num_kv_heads=2,
            head_dim=16,
            rope_theta=1_000_000.0,
            rms_eps=1e-6,
            max_position=512,
            tie_word_embeddings=True,
            qk_norm=True,
            sliding_window=32,
            window_pattern=2,
            rope_local_theta=10000.0,
            hidden_act="gelu_tanh",
            norm_offset=True,
            post_norms=True,
            embed_scale=True,
            # Deliberately != head_dim so the score-scale fold is a real
            # multiplier in the tests (27B-style configs have qpa 168 vs
            # head_dim 128; equal values would make the fold a no-op).
            query_pre_attn_scalar=32.0,
        )

    @staticmethod
    def mistral_7b() -> "ModelConfig":
        """Mistral-7B-v0.1 (HF mistralai/Mistral-7B-v0.1): Llama-shaped
        with 4096-token sliding-window attention."""
        return ModelConfig(
            name="mistral-7b",
            vocab_size=32000,
            hidden_size=4096,
            intermediate_size=14336,
            num_layers=32,
            num_heads=32,
            num_kv_heads=8,
            head_dim=128,
            rope_theta=10000.0,
            max_position=32768,
            sliding_window=4096,
        )

    @staticmethod
    def qwen3_06b() -> "ModelConfig":
        """Qwen3-0.6B (HF Qwen/Qwen3-0.6B config.json): QK-norm, no qkv
        bias, explicit head_dim 128."""
        return ModelConfig(
            name="qwen3-0.6b",
            vocab_size=151936,
            hidden_size=1024,
            intermediate_size=3072,
            num_layers=28,
            num_heads=16,
            num_kv_heads=8,
            head_dim=128,
            rope_theta=1000000.0,
            rms_eps=1e-6,
            max_position=40960,
            tie_word_embeddings=True,
            qk_norm=True,
        )

    # -- presets ------------------------------------------------------------
    @staticmethod
    def tiny_test(vocab_size: int = 384) -> "ModelConfig":
        """Hermetic test model (pairs with the byte-level ToyTokenizer)."""
        return ModelConfig(
            name="tiny-test",
            vocab_size=vocab_size,
            hidden_size=64,
            intermediate_size=128,
            num_layers=2,
            num_heads=4,
            num_kv_heads=2,
            head_dim=16,
            rope_theta=10000.0,
            max_position=512,
        )

    @staticmethod
    def tiny_moe_test(vocab_size: int = 384) -> "ModelConfig":
        """Hermetic Mixtral-style MoE test model."""
        return ModelConfig(
            name="tiny-moe-test",
            vocab_size=vocab_size,
            hidden_size=64,
            intermediate_size=96,
            num_layers=2,
            num_heads=4,
            num_kv_heads=2,
            head_dim=16,
            rope_theta=10000.0,
            max_position=512,
            num_experts=4,
            num_experts_per_tok=2,
        )

    @staticmethod
    def tiny_mla_test(vocab_size: int = 384) -> "ModelConfig":
        """Hermetic DeepSeek-style test model: MLA + shared/routed experts
        with sigmoid gating and one leading dense layer (the V3/R1 layer
        plan in miniature)."""
        return ModelConfig(
            name="tiny-mla-test",
            vocab_size=vocab_size,
            hidden_size=64,
            intermediate_size=128,
            num_layers=3,
            num_heads=4,
            num_kv_heads=4,
            head_dim=16,
            rope_theta=10000.0,
            max_position=512,
            kv_lora_rank=32,
            q_lora_rank=48,
            qk_nope_head_dim=16,
            qk_rope_head_dim=8,
            v_head_dim=16,
            num_experts=4,
            num_experts_per_tok=2,
            n_shared_experts=1,
            moe_intermediate_size=48,
            first_k_dense_replace=1,
            gating="sigmoid",
            routed_scaling_factor=2.5,
        )

    @staticmethod
    def _deepseek_yarn(mscale: float) -> "object":
        from dynamo_tpu.ops.rope import RopeScaling

        return RopeScaling(
            kind="yarn",
            factor=40.0,
            original_max_position=4096,
            beta_fast=32.0,
            beta_slow=1.0,
            mscale=mscale,
            mscale_all_dim=mscale,
        )

    @staticmethod
    def deepseek_v2_lite() -> "ModelConfig":
        """DeepSeek-V2-Lite 15.7B (MLA, no q-lora, softmax gating)."""
        return ModelConfig(
            name="deepseek-v2-lite",
            vocab_size=102400,
            hidden_size=2048,
            intermediate_size=10944,
            num_layers=27,
            num_heads=16,
            num_kv_heads=16,
            head_dim=128,
            rope_theta=10000.0,
            max_position=163840,
            kv_lora_rank=512,
            q_lora_rank=0,
            qk_nope_head_dim=128,
            qk_rope_head_dim=64,
            v_head_dim=128,
            num_experts=64,
            num_experts_per_tok=6,
            n_shared_experts=2,
            moe_intermediate_size=1408,
            first_k_dense_replace=1,
            gating="softmax",
            norm_topk_prob=False,
            routed_scaling_factor=1.0,
            n_group=1,
            topk_group=1,
            rope_scaling=ModelConfig._deepseek_yarn(0.707),
        )

    @staticmethod
    def deepseek_r1() -> "ModelConfig":
        """DeepSeek-R1/V3 671B (MLA + q-lora, sigmoid gating, 256 experts)
        — the BASELINE.md stage-5 target; serve ep×tp-sharded."""
        return ModelConfig(
            name="deepseek-r1",
            vocab_size=129280,
            hidden_size=7168,
            intermediate_size=18432,
            num_layers=61,
            num_heads=128,
            num_kv_heads=128,
            head_dim=128,
            rope_theta=10000.0,
            max_position=163840,
            kv_lora_rank=512,
            q_lora_rank=1536,
            qk_nope_head_dim=128,
            qk_rope_head_dim=64,
            v_head_dim=128,
            num_experts=256,
            num_experts_per_tok=8,
            n_shared_experts=1,
            moe_intermediate_size=2048,
            first_k_dense_replace=3,
            gating="sigmoid",
            norm_topk_prob=True,
            routed_scaling_factor=2.5,
            n_group=8,
            topk_group=4,
            rope_scaling=ModelConfig._deepseek_yarn(1.0),
        )

    @staticmethod
    def mixtral_8x7b() -> "ModelConfig":
        return ModelConfig(
            name="mixtral-8x7b",
            vocab_size=32000,
            hidden_size=4096,
            intermediate_size=14336,
            num_layers=32,
            num_heads=32,
            num_kv_heads=8,
            head_dim=128,
            rope_theta=1e6,
            max_position=32768,
            num_experts=8,
            num_experts_per_tok=2,
        )

    @staticmethod
    def sdar_30b_a3b() -> "ModelConfig":
        """SDAR-30B-A3B-Chat (HF JetLM/SDAR-30B-A3B-Chat config.json,
        model_type sdar_moe): Qwen3-MoE layers (GQA 32/4 x 128 with
        per-head q/k norms, 128 experts of width 768, 8 a token
        renormalised, no shared expert, no dense layer) under a
        block-causal mask, generated by block diffusion."""
        return ModelConfig(
            name="sdar-30b-a3b",
            vocab_size=151936,
            hidden_size=2048,
            intermediate_size=6144,  # carried; every layer is sparse
            num_layers=48,
            num_heads=32,
            num_kv_heads=4,
            head_dim=128,
            rope_theta=1000000.0,
            rms_eps=1e-6,
            max_position=32768,
            qk_norm=True,
            num_experts=128,
            num_experts_per_tok=8,
            moe_intermediate_size=768,
            norm_topk_prob=True,
            **SDAR_GENERATION,
        )

    @staticmethod
    def tiny_sdar_test(vocab_size: int = 384) -> "ModelConfig":
        """Hermetic SDAR-style test model: block diffusion over 16 routed
        experts (the grouped expert path), 4 a token."""
        return ModelConfig(
            name="tiny-sdar-test",
            vocab_size=vocab_size,
            hidden_size=64,
            intermediate_size=128,
            num_layers=2,
            num_heads=4,
            num_kv_heads=2,
            head_dim=16,
            rope_theta=1000000.0,
            rms_eps=1e-6,
            max_position=512,
            qk_norm=True,
            num_experts=16,
            num_experts_per_tok=4,
            moe_intermediate_size=32,
            norm_topk_prob=True,
            **{**SDAR_GENERATION, "mask_token_id": vocab_size - 1},
        )

    @staticmethod
    def ling_30_flash() -> "ModelConfig":
        """Ling-3.0-flash (HF inclusionAI/Ling-3.0-flash config.json,
        model_type bailing_hybrid): 42 layers, five KDA linear-attention
        layers to one latent-attention layer (layer_group_size 6), two
        leading dense layers, then 512 sigmoid-scored experts of width
        768, 8 a token out of the 4 best of 8 groups, one shared expert.
        The multi-token-prediction module is not served."""
        return ModelConfig(
            name="ling-3.0-flash",
            vocab_size=157184,
            hidden_size=2560,
            intermediate_size=6144,
            num_layers=42,
            num_heads=32,
            num_kv_heads=32,
            head_dim=128,
            rope_theta=6000000.0,
            rms_eps=1e-6,
            max_position=262144,
            kv_lora_rank=512,
            q_lora_rank=0,
            qk_nope_head_dim=128,
            qk_rope_head_dim=64,
            v_head_dim=128,
            num_experts=512,
            num_experts_per_tok=8,
            n_shared_experts=1,
            moe_intermediate_size=768,
            first_k_dense_replace=2,
            gating="sigmoid",
            norm_topk_prob=True,
            routed_scaling_factor=2.5,
            n_group=8,
            topk_group=4,
            layer_group_size=6,
            linear_conv_kernel=4,
            kda_lower_bound=-5.0,
            expert_swiglu_limit=(0,) * 35 + (4,) * 7,
            shared_swiglu_limit=(0,) * 34 + (5,) * 6 + (7,) * 2,
        )

    @staticmethod
    def ling_30_flash_ep4_l8() -> "ModelConfig":
        """One chip's share of a 4-way expert-parallel Ling-3.0-flash, the
        first eight layers (both dense layers and one whole group): experts
        0-127 of each expert layer, rows 0-39,295 of the vocabulary."""
        return ModelConfig.ling_30_flash().scaled(
            name="ling-3.0-flash-ep4-l8", num_layers=8,
            num_experts_held=128, vocab_size=39296,
        )

    @staticmethod
    def tiny_ling_test(vocab_size: int = 384, held: int = 0) -> "ModelConfig":
        """Hermetic Ling-style test model: 2 dense + 6 expert layers in the
        real pattern (layer 5 latent attention, the rest KDA), 32 experts
        in 4 groups of which ``held`` (0 = all: the grouped path; 8: the
        dense one) live here, a swiglu limit on one layer."""
        return ModelConfig(
            name="tiny-ling-test",
            vocab_size=vocab_size,
            hidden_size=64,
            intermediate_size=128,
            num_layers=8,
            num_heads=4,
            num_kv_heads=4,
            head_dim=16,
            rope_theta=10000.0,
            rms_eps=1e-6,
            max_position=512,
            kv_lora_rank=32,
            q_lora_rank=0,
            qk_nope_head_dim=16,
            qk_rope_head_dim=8,
            v_head_dim=16,
            num_experts=32,
            num_experts_per_tok=4,
            n_shared_experts=1,
            moe_intermediate_size=32,
            first_k_dense_replace=2,
            gating="sigmoid",
            norm_topk_prob=True,
            routed_scaling_factor=2.5,
            n_group=4,
            topk_group=2,
            layer_group_size=6,
            linear_conv_kernel=4,
            kda_lower_bound=-5.0,
            num_experts_held=held,
            expert_swiglu_limit=(0, 0, 0, 1.5),
            shared_swiglu_limit=(0, 0, 0, 0, 1.0),
        )

    @staticmethod
    def brumby_14b() -> "ModelConfig":
        """Brumby-14B-Base (HF manifestai/Brumby-14B-Base config.json,
        model_type brumby): Qwen3-14B's widths (40 layers, 5,120 wide, 40
        query heads over 8 cached heads of 128, a 17,408-wide SwiGLU FFN,
        an untied 151,936-row vocabulary, rope_theta 1e6) with every
        attention layer a power-retention layer (arXiv:2507.04239). What
        config.json does not state is set here: degree 2; a 5,120 -> 8
        gate (a projection and a bias) through log-sigmoid, one a cached
        head; the
        normaliser ``phi(q) . z + 1e-6``; ``1 / sqrt(128)`` inside the
        power; the per-head q/k RMSNorm and the rotary embedding kept on q
        and k; the state in float32."""
        return ModelConfig(
            name="brumby",
            vocab_size=151936,
            hidden_size=5120,
            intermediate_size=17408,
            num_layers=40,
            num_heads=40,
            num_kv_heads=8,
            head_dim=128,
            rope_theta=1000000.0,
            rms_eps=1e-6,
            max_position=32768,
            tie_word_embeddings=False,
            qk_norm=True,
            retention_degree=2,
        )

    @staticmethod
    def tiny_brumby_test(vocab_size: int = 384) -> "ModelConfig":
        """Hermetic Brumby-style test model: four retention layers, 4
        query heads over 2 cached heads of 16."""
        return ModelConfig(
            name="tiny-brumby-test",
            vocab_size=vocab_size,
            hidden_size=64,
            intermediate_size=128,
            num_layers=4,
            num_heads=4,
            num_kv_heads=2,
            head_dim=16,
            rope_theta=1000000.0,
            rms_eps=1e-6,
            max_position=512,
            tie_word_embeddings=False,
            qk_norm=True,
            retention_degree=2,
        )

    @staticmethod
    def command_a_plus() -> "ModelConfig":
        """Command A+ 05-2026 (HF CohereLabs/command-a-plus-05-2026
        config.json, model_type cohere2_moe; 218B-A25B): 32 parallel-block
        layers, three 4,096-token sliding-window layers (interleaved
        rotary pairs) to one full-attention layer without rotary
        embedding, 128 query heads over 8 cached heads, 128 sigmoid-
        selected experts of width 4096, 8 a token, beside four shared
        experts averaged. The vision tower is not served."""
        return ModelConfig(
            name="command-a-plus",
            vocab_size=262144,
            hidden_size=4096,
            intermediate_size=4096,
            num_layers=32,
            num_heads=128,
            num_kv_heads=8,
            head_dim=128,
            rope_theta=50000.0,
            rms_eps=1e-5,
            max_position=200000,
            tie_word_embeddings=True,
            sliding_window=4096,
            window_pattern=4,
            num_experts=128,
            num_experts_per_tok=8,
            n_shared_experts=4,
            moe_intermediate_size=4096,
            gating="sigmoid",
            norm_topk_prob=True,
            parallel_block=True,
            norm_centered=True,
            rope_interleaved=True,
            nope_full_layers=True,
            logit_scale=1.0,
            shared_experts_average=True,
            cache_by_layer_group=True,
            embed_init_std=1.0,
        )

    @staticmethod
    def command_a_plus_ep8_l4() -> "ModelConfig":
        """One chip's share of an 8-way expert-parallel Command A+, the
        first four layers (one whole period: layers 0-2 window, layer 3
        full): experts 0-15 of each layer, rows 0-32,767 of the
        vocabulary."""
        return ModelConfig.command_a_plus().scaled(
            name="command-a-plus-ep8-l4", num_layers=4,
            num_experts_held=16, vocab_size=32768,
        )

    @staticmethod
    def tiny_command_a_test(
        vocab_size: int = 384, held: int = 0
    ) -> "ModelConfig":
        """Hermetic Command-A+-style test model: 8 parallel-block layers
        in the real pattern (layers 3 and 7 full without rotary, the rest
        a 32-token window), 4 queries a cached head, 16 experts of which
        ``held`` (0 = all) live here, 2 shared experts averaged."""
        return ModelConfig(
            name="tiny-command-a-test",
            vocab_size=vocab_size,
            hidden_size=64,
            intermediate_size=32,
            num_layers=8,
            num_heads=8,
            num_kv_heads=2,
            head_dim=16,
            rope_theta=50000.0,
            rms_eps=1e-5,
            max_position=1024,
            tie_word_embeddings=True,
            sliding_window=32,
            window_pattern=4,
            num_experts=16,
            num_experts_per_tok=4,
            n_shared_experts=2,
            moe_intermediate_size=32,
            gating="sigmoid",
            norm_topk_prob=True,
            num_experts_held=held,
            parallel_block=True,
            norm_centered=True,
            rope_interleaved=True,
            nope_full_layers=True,
            logit_scale=0.5,
            shared_experts_average=True,
            cache_by_layer_group=True,
            embed_init_std=1.0,
        )

    @staticmethod
    def llama3_8b() -> "ModelConfig":
        return ModelConfig(
            name="llama3-8b",
            vocab_size=128256,
            hidden_size=4096,
            intermediate_size=14336,
            num_layers=32,
            num_heads=32,
            num_kv_heads=8,
            head_dim=128,
            rope_theta=500000.0,
            max_position=8192,
        )

    @staticmethod
    def llama31_8b() -> "ModelConfig":
        from dynamo_tpu.ops.rope import RopeScaling

        return ModelConfig(
            name="llama3.1-8b",
            vocab_size=128256,
            hidden_size=4096,
            intermediate_size=14336,
            num_layers=32,
            num_heads=32,
            num_kv_heads=8,
            head_dim=128,
            rope_theta=500000.0,
            max_position=131072,
            rope_scaling=RopeScaling(
                factor=8.0,
                low_freq_factor=1.0,
                high_freq_factor=4.0,
                original_max_position=8192,
            ),
        )

    @staticmethod
    def llama32_1b() -> "ModelConfig":
        from dynamo_tpu.ops.rope import RopeScaling

        return ModelConfig(
            name="llama3.2-1b",
            vocab_size=128256,
            hidden_size=2048,
            intermediate_size=8192,
            num_layers=16,
            num_heads=32,
            num_kv_heads=8,
            head_dim=64,
            rope_theta=500000.0,
            max_position=131072,
            tie_word_embeddings=True,
            rope_scaling=RopeScaling(
                factor=32.0,
                low_freq_factor=1.0,
                high_freq_factor=4.0,
                original_max_position=8192,
            ),
        )

    @staticmethod
    def llama3_70b() -> "ModelConfig":
        return ModelConfig(
            name="llama3-70b",
            vocab_size=128256,
            hidden_size=8192,
            intermediate_size=28672,
            num_layers=80,
            num_heads=64,
            num_kv_heads=8,
            head_dim=128,
            rope_theta=500000.0,
            max_position=8192,
        )

    @staticmethod
    def qwen25_05b() -> "ModelConfig":
        return ModelConfig(
            name="qwen2.5-0.5b",
            vocab_size=151936,
            hidden_size=896,
            intermediate_size=4864,
            num_layers=24,
            num_heads=14,
            num_kv_heads=2,
            head_dim=64,
            rope_theta=1000000.0,
            max_position=32768,
            tie_word_embeddings=True,
            qkv_bias=True,
        )

    def scaled(self, **kwargs) -> "ModelConfig":
        return replace(self, **kwargs)


#: SDAR's generation settings (its generate script's defaults; the
#: config.json carries none of them).
SDAR_GENERATION = {
    "diffusion_block_length": 4,
    "mask_token_id": 151669,
    "denoising_steps": 4,
    "confidence_threshold": 0.9,
}

PRESETS = {
    "tiny-test": ModelConfig.tiny_test,
    "tiny-sdar-test": ModelConfig.tiny_sdar_test,
    "sdar-30b-a3b": ModelConfig.sdar_30b_a3b,
    "tiny-moe-test": ModelConfig.tiny_moe_test,
    "tiny-mla-test": ModelConfig.tiny_mla_test,
    "deepseek-v2-lite": ModelConfig.deepseek_v2_lite,
    "deepseek-r1": ModelConfig.deepseek_r1,
    "llama3-8b": ModelConfig.llama3_8b,
    "llama3.1-8b": ModelConfig.llama31_8b,
    "llama3.2-1b": ModelConfig.llama32_1b,
    "llama3-70b": ModelConfig.llama3_70b,
    "mixtral-8x7b": ModelConfig.mixtral_8x7b,
    "qwen2.5-0.5b": ModelConfig.qwen25_05b,
    "qwen3-0.6b": ModelConfig.qwen3_06b,
    "mistral-7b": ModelConfig.mistral_7b,
    "gemma3-1b": ModelConfig.gemma3_1b,
    "tiny-gemma-test": ModelConfig.tiny_gemma_test,
    "ling-3.0-flash": ModelConfig.ling_30_flash,
    "ling-3.0-flash-ep4-l8": ModelConfig.ling_30_flash_ep4_l8,
    "tiny-ling-test": ModelConfig.tiny_ling_test,
    "command-a-plus": ModelConfig.command_a_plus,
    "command-a-plus-ep8-l4": ModelConfig.command_a_plus_ep8_l4,
    "tiny-command-a-test": ModelConfig.tiny_command_a_test,
    "brumby-14b": ModelConfig.brumby_14b,
    "tiny-brumby-test": ModelConfig.tiny_brumby_test,
}
