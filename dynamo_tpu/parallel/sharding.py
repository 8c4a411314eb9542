"""GSPMD sharding specs for model params and the paged KV cache.

Megatron-style tensor parallelism expressed declaratively: column-shard
the q/k/v/gate/up projections, row-shard o/down, shard embeddings on the
feature dim so tied-logits contractions psum instead of all-gathering the
vocab table. XLA/GSPMD inserts the all-reduces — nothing in models/llama.py
mentions a collective (the "annotate shardings, let XLA insert collectives"
recipe; contrast the reference which inherits NCCL TP from vLLM,
SURVEY.md §2 "Parallelism strategies").

KV cache shards over kv-heads on ``tp`` — each chip holds the KV for the
heads it computes, so paged attention needs no cross-chip traffic at all.
"""

from __future__ import annotations

from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from dynamo_tpu.models.config import ModelConfig

Params = dict[str, Any]


def llama_param_specs(cfg: ModelConfig) -> Params:
    """PartitionSpec pytree mirroring models/llama.py's param structure
    (per-layer: MLA vs GQA attention; dense vs shared+routed MLP)."""
    layers = []
    for li in range(cfg.num_layers):
        if cfg.is_mla:
            # MLA: the latent path (w_dkv) is shared by every head →
            # replicated; per-head up-projections and q shard over heads.
            layer = {
                "w_dkv": P(None, None),
                "ln_kv": P(),
                "w_uk": P("tp", None, None),
                "w_uv": P("tp", None, None),
                "wo": P("tp", None),
                "ln_attn": P(),
                "ln_mlp": P(),
            }
            if cfg.q_lora_rank:
                layer.update(
                    {
                        "w_dq": P(None, None),
                        "ln_q": P(),
                        "w_uq": P(None, "tp"),
                    }
                )
            else:
                layer["wq"] = P(None, "tp")
        else:
            layer = {
                "wq": P(None, "tp"),
                "wk": P(None, "tp"),
                "wv": P(None, "tp"),
                "wo": P("tp", None),
                "ln_attn": P(),
                "ln_mlp": P(),
            }
        if cfg.parallel_block:  # ONE norm a layer (models/llama.py)
            del layer["ln_mlp"]
        if cfg.moe_layer(li):
            # MoE: experts over ep, per-expert intermediate over tp; tiny
            # router replicated — one source of truth in models/moe.py.
            from dynamo_tpu.models.moe import moe_param_specs

            layer.update(moe_param_specs())
            if cfg.gating == "sigmoid":
                layer["router_bias"] = P()
            if cfg.n_shared_experts:
                layer.update(
                    {
                        "w_shared_gate": P(None, "tp"),
                        "w_shared_up": P(None, "tp"),
                        "w_shared_down": P("tp", None),
                    }
                )
        else:
            layer.update(
                {
                    "w_gate": P(None, "tp"),
                    "w_up": P(None, "tp"),
                    "w_down": P("tp", None),
                }
            )
        if cfg.qkv_bias:
            layer.update({"bq": P("tp"), "bk": P("tp"), "bv": P("tp")})
        if cfg.qk_norm:
            # Per-head norm gains span ONE head's dims — replicate.
            layer.update({"ln_q_head": P(), "ln_k_head": P()})
        if cfg.post_norms:
            # Gemma sandwich norms: [D] gains — replicate like every norm.
            layer.update({"ln_post_attn": P(), "ln_post_mlp": P()})
        layers.append(layer)
    specs: Params = {
        # Feature-sharded table: lookups stay local; the (tied) logits
        # contraction over D psums instead of gathering the vocab table.
        "embed": P(None, "tp"),
        "layers": layers,
        "ln_f": P(),
    }
    if not cfg.tie_word_embeddings:
        specs["lm_head"] = P("tp", None)
    return specs


def kv_cache_spec(
    replicated: bool = False, sp: bool = False, form: str = "apart"
) -> P:
    """[num_slots, n_cache_heads, head_dim] — heads over tp; under ``form``
    "joined" (``EngineConfig.cache_form``; never with ``sp`` or
    ``replicated``) a (k, v) layer's pages in ONE array [num_blocks, 2,
    block_size, n_cache_heads, head_dim], the same axis. MLA models
    pass replicated=True (one shared latent head per token — q heads
    shard, the cache does not; models/llama.py _qkv_mla). ``sp`` shards
    the SLOT axis over the sp mesh axis IN ADDITION to the tp head
    sharding — the long-context mode where total KV capacity is
    sp x tp x one device's arrays (ops/attention.py AttnDispatch kv_sp;
    composes with tensor parallelism since r05)."""
    if form == "joined":
        assert not sp and not replicated
        return P(None, None, None, "tp", None)
    if sp:
        return P("sp", None, None) if replicated else P("sp", "tp", None)
    return P(None, None, None) if replicated else P(None, "tp", None)


def shard_params(params: Params, mesh: Mesh, specs: Params | None = None,
                 cfg: ModelConfig | None = None) -> Params:
    """device_put the params pytree onto the mesh per the spec pytree."""
    if specs is None:
        assert cfg is not None
        specs = llama_param_specs(cfg)
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        params,
        specs,
        is_leaf=lambda x: isinstance(x, P),
    )


