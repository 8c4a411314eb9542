"""Gemma-3 family tests.

Two layers of proof:
1. An INDEPENDENT oracle: a tiny random-weight HF-transformers
   Gemma3ForCausalLM is saved to disk and loaded through the production
   path (ModelConfig.from_hf + llama.load_hf_weights); our no-cache
   forward must reproduce HF's logits. This pins every family knob —
   (1+w) norms, sandwich norms, GeGLU, scaled embeddings, QK-norm,
   query_pre_attn_scalar, and the local/global rope + window pattern —
   against an implementation we didn't write.
2. The paged serving engine must match the no-cache oracle greedily once
   the context crosses the sliding window, with the global layers' full
   attention live.

Reference parity: the reference serves Gemma through its delegated
engines (e.g. vLLM — reference: launch/dynamo-run/src/subprocess/
vllm_v1_inc.py); here the family is native (models/llama.py).
"""

import asyncio
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.engine.engine import TpuEngine
from dynamo_tpu.llm.protocols.common import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.models import llama
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.runtime.engine import Context
from stepdrive import reference_greedy

pytestmark = pytest.mark.anyio

GCFG = ModelConfig.tiny_gemma_test()


def test_gemma3_matches_hf_transformers(tmp_path):
    """End-to-end HF parity: save a random HF Gemma-3, load it through
    from_hf + load_hf_weights, compare full-sequence logits."""
    torch = pytest.importorskip("torch")
    from transformers import Gemma3ForCausalLM, Gemma3TextConfig

    hf_cfg = Gemma3TextConfig(
        vocab_size=384,
        hidden_size=64,
        intermediate_size=128,
        num_hidden_layers=4,
        num_attention_heads=4,
        num_key_value_heads=2,
        head_dim=16,
        rope_theta=1_000_000.0,
        rope_local_base_freq=10_000.0,
        sliding_window=32,
        sliding_window_pattern=2,
        max_position_embeddings=512,
        rms_norm_eps=1e-6,
        query_pre_attn_scalar=32,  # != head_dim: the scale fold must be live
        hidden_activation="gelu_pytorch_tanh",
        tie_word_embeddings=True,
        attention_bias=False,
    )
    torch.manual_seed(0)
    model = Gemma3ForCausalLM(hf_cfg).eval()
    model.save_pretrained(tmp_path)

    cfg = ModelConfig.from_hf(str(tmp_path))
    assert cfg.window_pattern == 2
    assert cfg.post_norms and cfg.norm_offset and cfg.embed_scale
    assert cfg.hidden_act == "gelu_tanh" and cfg.qk_norm
    assert cfg.rope_local_theta == 10_000.0
    assert cfg.layer_window(0) == 32 and cfg.layer_window(1) == 0

    params = llama.load_hf_weights(cfg, str(tmp_path), dtype=jnp.float32)
    # 48 tokens > the 32-token window, so local masking + the global
    # layers' full span + both rope bases all matter.
    toks = np.random.default_rng(5).integers(1, 384, 48)
    with torch.no_grad():
        want = model(torch.tensor(toks)[None]).logits[0].float().numpy()
    got = np.asarray(llama.reference_forward(cfg, params, jnp.asarray(toks)))
    np.testing.assert_allclose(got, want, atol=2e-3, rtol=2e-3)


def test_gemma2_softcapping_rejected(tmp_path):
    import json

    (tmp_path / "config.json").write_text(
        json.dumps(
            {
                "architectures": ["Gemma2ForCausalLM"],
                "model_type": "gemma2",
                "attn_logit_softcapping": 50.0,
            }
        )
    )
    with pytest.raises(NotImplementedError):
        ModelConfig.from_hf(str(tmp_path))


async def _collect(engine, prompt, n):
    req = PreprocessedRequest(
        token_ids=prompt,
        sampling=SamplingOptions(temperature=0.0),
        stop=StopConditions(max_tokens=n, ignore_eos=True),
    )
    out = []
    async for item in engine.generate(Context(req.to_wire())):
        out += item["token_ids"]
    return out


async def test_gemma3_engine_matches_oracle():
    """Paged serving (prefill chunks + fused decode + per-layer windows)
    must reproduce the no-cache oracle, and the window pattern must be
    live: an all-global variant diverges once ctx exceeds the window."""
    params = llama.init_params(jax.random.PRNGKey(7), GCFG, dtype=jnp.float32)
    prompt = [int(t) for t in
              np.random.default_rng(9).integers(1, GCFG.vocab_size, 40)]

    engine = TpuEngine(
        EngineConfig(
            model=GCFG, num_blocks=64, max_num_seqs=2, max_model_len=128,
            dtype="float32", unified_token_budget=16,
            unified_prefill_quantum=16,
        ),
        params=params,
    )
    await engine.start()
    try:
        tokens = await _collect(engine, prompt, 10)
    finally:
        await engine.stop()
    assert tokens == reference_greedy(GCFG, params, prompt, 10, length=128)
    # The 2-pattern is live: making every layer global changes the tokens
    # (ctx 40 > window 32).
    all_global = dataclasses.replace(GCFG, sliding_window=0, window_pattern=0)
    assert tokens != reference_greedy(all_global, params, prompt, 10, length=128)


def test_gemma3_multimodal_sparse_text_config(tmp_path):
    """Published multimodal Gemma-3 configs ship sparse text_configs that
    lean on HF defaults — from_hf must fill them, not crash or silently
    disable the window plan (google/gemma-3-4b-it shape)."""
    import json

    (tmp_path / "config.json").write_text(
        json.dumps(
            {
                "architectures": ["Gemma3ForConditionalGeneration"],
                "model_type": "gemma3",
                "text_config": {
                    "hidden_size": 2560,
                    "intermediate_size": 10240,
                    "model_type": "gemma3_text",
                    "num_hidden_layers": 34,
                    "rope_scaling": {"factor": 8.0, "rope_type": "linear"},
                    "sliding_window": 1024,
                },
            }
        )
    )
    cfg = ModelConfig.from_hf(str(tmp_path))
    assert cfg.hidden_size == 2560 and cfg.num_layers == 34
    # HF Gemma3TextConfig defaults fill the gaps:
    assert cfg.num_heads == 8 and cfg.num_kv_heads == 4
    assert cfg.head_dim == 256 and cfg.vocab_size == 262208
    assert cfg.sliding_window == 1024 and cfg.window_pattern == 6
    assert cfg.rope_local_theta == 10_000.0
    assert cfg.rope_scaling is not None and cfg.rope_scaling.kind == "linear"
    assert cfg.layer_window(4) == 1024 and cfg.layer_window(5) == 0
