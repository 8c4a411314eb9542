"""DeepSeek-family coverage: MLA paged attention vs the no-cache oracle,
V3 sigmoid routing semantics, shared experts, sharded serving equivalence,
and the HF checkpoint layout round trip (kv_b_proj split into the absorbed
w_uk/w_uv). Reference context: the reference serves DeepSeek-R1 through
vLLM (BASELINE.md stage 5); here MLA is native — the paged cache stores
one [latent ‖ roped k_pe] entry per token and the attention kernels run
as MQA (models/llama.py _qkv_mla)."""

import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.engine.engine import TpuEngine
from dynamo_tpu.engine.runner import ModelRunner
from dynamo_tpu.llm.protocols.common import (
    EngineOutput,
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.models import llama
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.models.moe import MoeConfig, moe_router
from dynamo_tpu.parallel.mesh import build_mesh
from dynamo_tpu.runtime.engine import Context
from stepdrive import reference_greedy, step_token

pytestmark = pytest.mark.anyio

CFG = ModelConfig.tiny_mla_test()
PARAMS = llama.init_params(jax.random.PRNGKey(0), CFG, dtype=jnp.float32)


def test_mla_cache_geometry():
    assert CFG.is_mla
    assert CFG.num_cache_heads == 1
    assert CFG.kv_cache_head_dim == CFG.kv_lora_rank + CFG.qk_rope_head_dim
    ecfg = EngineConfig(
        model=CFG, dtype="float32", block_size=4, num_blocks=32,
        max_num_seqs=2, max_model_len=64,
    )
    r = ModelRunner(ecfg)
    k_cache, _ = r.kv_caches[0]
    assert k_cache.shape[1] == 1  # one shared latent head
    assert k_cache.shape[2] == r.cache_head_dim


async def test_mla_engine_matches_oracle():
    ecfg = EngineConfig(
        model=CFG, dtype="float32", block_size=4, num_blocks=64,
        max_num_seqs=4, max_model_len=128,
    )
    engine = TpuEngine(ecfg, params=PARAMS)
    await engine.start()
    try:
        prompt = [1, 5, 9, 2, 7]  # crosses a block boundary
        pre = PreprocessedRequest(
            token_ids=prompt,
            sampling=SamplingOptions(temperature=0.0),
            stop=StopConditions(max_tokens=10, ignore_eos=True),
        )
        tokens = []
        async for raw in engine.generate(Context(pre.to_wire())):
            tokens.extend(EngineOutput.from_wire(raw).token_ids)
        assert tokens == reference_greedy(CFG, PARAMS, prompt, 10, length=128)
    finally:
        await engine.stop()


def test_mla_sharded_matches_single_chip():
    """tp (q heads) × ep (experts) × dp mesh: MLA's latent cache is
    replicated over tp while q heads shard — greedy tokens must be
    identical to the single-device runner."""
    ecfg = EngineConfig(
        model=CFG, dtype="float32", block_size=16, num_blocks=32,
        max_num_seqs=2, max_model_len=128,
    )
    prompt = list(range(2, 18))
    blocks = [1, 2, 3, 4]
    single = ModelRunner(ecfg, params=PARAMS)
    tok = step_token(single, prompt, blocks)
    mesh = build_mesh({"tp": 2, "ep": 2, "dp": 2})
    sharded = ModelRunner(ecfg, params=PARAMS, mesh=mesh)
    tok2 = step_token(sharded, prompt, blocks)
    assert tok == tok2


def test_sigmoid_router_selection_bias_vs_weights():
    """V3 gating: the per-expert bias steers SELECTION only — the mixture
    weights come from the raw sigmoid probs of the selected experts."""
    cfg = MoeConfig(
        hidden_size=8, num_experts=4, num_experts_per_tok=2,
        gating="sigmoid", norm_topk_prob=True, routed_scaling_factor=1.0,
    )
    x = jnp.ones((1, 8), jnp.float32)
    w = jnp.zeros((8, 4), jnp.float32)
    # logits all 0 → probs all 0.5; bias pushes experts 2,3 into the top-k
    bias = jnp.asarray([0.0, 0.0, 1.0, 1.0], jnp.float32)
    gates = moe_router({"w_router": w, "router_bias": bias}, x, cfg)
    assert gates.shape == (1, 4)
    np.testing.assert_allclose(np.asarray(gates[0]), [0, 0, 0.5, 0.5], atol=1e-6)

    # without bias, softmax gating renormalizes over the selection
    cfg_sm = MoeConfig(
        hidden_size=8, num_experts=4, num_experts_per_tok=2, gating="softmax"
    )
    gates = moe_router({"w_router": w}, x, cfg_sm)
    assert float(gates.sum()) == pytest.approx(1.0, abs=1e-5)


def test_first_k_dense_replace_layer_plan():
    """Layer 0 is dense (no router); later layers carry router + shared
    experts (the V3/R1 layer plan)."""
    assert "w_router" not in PARAMS["layers"][0]
    assert "w_gate" in PARAMS["layers"][0]          # dense SwiGLU
    assert PARAMS["layers"][0]["w_gate"].ndim == 2
    layer1 = PARAMS["layers"][1]
    assert "w_router" in layer1
    assert "router_bias" in layer1                   # sigmoid gating
    assert layer1["w_gate"].ndim == 3                # stacked experts
    assert "w_shared_gate" in layer1


def test_deepseek_hf_load_roundtrip(tmp_path):
    """Synthesize a DeepSeek-layout safetensors checkpoint and load it:
    kv_b_proj splits into w_uk/w_uv per head, the router bias loads, and
    the loaded model's forward is finite and matches the layer plan."""
    from safetensors.numpy import save_file

    cfg = CFG
    rng = np.random.default_rng(0)
    H, dn, dr, dc = (
        cfg.num_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
        cfg.kv_lora_rank,
    )
    D, dv, E = cfg.hidden_size, cfg.v_head_dim, cfg.num_experts

    def t(*shape):
        return (rng.standard_normal(shape) * 0.02).astype(np.float32)

    tensors = {
        "model.embed_tokens.weight": t(cfg.vocab_size, D),
        "model.norm.weight": np.ones(D, np.float32),
        "lm_head.weight": t(cfg.vocab_size, D),
    }
    for i in range(cfg.num_layers):
        p = f"model.layers.{i}"
        tensors |= {
            f"{p}.self_attn.q_a_proj.weight": t(cfg.q_lora_rank, D),
            f"{p}.self_attn.q_a_layernorm.weight": np.ones(
                cfg.q_lora_rank, np.float32
            ),
            f"{p}.self_attn.q_b_proj.weight": t(H * (dn + dr), cfg.q_lora_rank),
            f"{p}.self_attn.kv_a_proj_with_mqa.weight": t(dc + dr, D),
            f"{p}.self_attn.kv_a_layernorm.weight": np.ones(dc, np.float32),
            f"{p}.self_attn.kv_b_proj.weight": t(H * (dn + dv), dc),
            f"{p}.self_attn.o_proj.weight": t(D, H * dv),
            f"{p}.input_layernorm.weight": np.ones(D, np.float32),
            f"{p}.post_attention_layernorm.weight": np.ones(D, np.float32),
        }
        if cfg.moe_layer(i):
            Im = cfg.moe_intermediate_size
            tensors |= {
                f"{p}.mlp.gate.weight": t(E, D),
                f"{p}.mlp.gate.e_score_correction_bias": t(E),
            }
            for e in range(E):
                tensors |= {
                    f"{p}.mlp.experts.{e}.gate_proj.weight": t(Im, D),
                    f"{p}.mlp.experts.{e}.up_proj.weight": t(Im, D),
                    f"{p}.mlp.experts.{e}.down_proj.weight": t(D, Im),
                }
            Is = Im * cfg.n_shared_experts
            tensors |= {
                f"{p}.mlp.shared_experts.gate_proj.weight": t(Is, D),
                f"{p}.mlp.shared_experts.up_proj.weight": t(Is, D),
                f"{p}.mlp.shared_experts.down_proj.weight": t(D, Is),
            }
        else:
            I = cfg.intermediate_size
            tensors |= {
                f"{p}.mlp.gate_proj.weight": t(I, D),
                f"{p}.mlp.up_proj.weight": t(I, D),
                f"{p}.mlp.down_proj.weight": t(D, I),
            }
    save_file(tensors, str(tmp_path / "model.safetensors"))

    params = llama.load_hf_weights(cfg, str(tmp_path), dtype=jnp.float32)
    layer1 = params["layers"][1]
    assert layer1["w_uk"].shape == (H, dn, dc)
    assert layer1["w_uv"].shape == (H, dv, dc)
    np.testing.assert_allclose(
        np.asarray(layer1["router_bias"]),
        tensors["model.layers.1.mlp.gate.e_score_correction_bias"],
        atol=1e-6,
    )
    # the split must reproduce kv_b_proj exactly
    kvb = tensors["model.layers.1.self_attn.kv_b_proj.weight"].reshape(
        H, dn + dv, dc
    )
    np.testing.assert_allclose(np.asarray(layer1["w_uk"]), kvb[:, :dn], atol=1e-6)
    np.testing.assert_allclose(np.asarray(layer1["w_uv"]), kvb[:, dn:], atol=1e-6)
    out = llama.reference_forward(cfg, params, jnp.arange(2, 18, dtype=jnp.int32))
    assert bool(jnp.all(jnp.isfinite(out)))


def test_yarn_rope_scaling():
    """DeepSeek checkpoints ship rope_scaling type 'yarn' — from_hf must
    parse it, long-position rotations must differ from unscaled, and the
    mscale attention correction must match the formula."""
    from dynamo_tpu.ops.rope import RopeScaling, apply_rope

    s = RopeScaling.from_hf(
        {
            "type": "yarn",
            "factor": 40,
            "original_max_position_embeddings": 4096,
            "beta_fast": 32,
            "beta_slow": 1,
            "mscale": 1.0,
            "mscale_all_dim": 1.0,
        }
    )
    assert s.kind == "yarn" and s.factor == 40
    # yarn_get_mscale(40, 1.0) = 0.1*ln(40)+1
    assert s.attn_mscale() == pytest.approx(0.1 * np.log(40) + 1, abs=1e-6)
    assert s.embed_mscale() == pytest.approx(1.0)

    x = jnp.ones((1, 1, 64), jnp.float32)
    far = jnp.asarray([50000])
    scaled = apply_rope(x, far, 10000.0, s)
    unscaled = apply_rope(x, far, 10000.0, None)
    assert bool(jnp.all(jnp.isfinite(scaled)))
    assert float(jnp.max(jnp.abs(scaled - unscaled))) > 1e-3
    # high-frequency (early) dims extrapolate: identical at short range
    near = jnp.asarray([1])
    s_near = apply_rope(x, near, 10000.0, s)
    u_near = apply_rope(x, near, 10000.0, None)
    assert float(jnp.max(jnp.abs(s_near[..., 0] - u_near[..., 0]))) < 1e-5


def test_group_limited_routing():
    """noaux_tc: only experts in the topk_group best groups are eligible,
    even when a masked group holds the globally best expert-by-prob."""
    cfg = MoeConfig(
        hidden_size=4, num_experts=4, num_experts_per_tok=2,
        gating="sigmoid", n_group=2, topk_group=1,
        routed_scaling_factor=1.0,
    )
    x = jnp.ones((1, 4), jnp.float32)
    w = jnp.zeros((4, 4), jnp.float32)  # probs all 0.5
    # bias makes group 1 (experts 2,3) win the group score
    bias = jnp.asarray([0.0, 0.0, 0.6, 0.6], jnp.float32)
    gates = moe_router({"w_router": w, "router_bias": bias}, x, cfg)
    assert float(gates[0, 0]) == 0.0 and float(gates[0, 1]) == 0.0
    assert float(gates[0, 2]) > 0 and float(gates[0, 3]) > 0


def test_quantized_mla_matches_quantized_oracle():
    """int8 quantization covers the MLA projections and shared experts
    (ops/quant.py QUANT_KEYS incl. per-head w_uk/w_uv with axis-aware
    scales); the paged int8 engine must match the int8 oracle exactly."""
    from dynamo_tpu.ops.quant import is_quantized, quantize_params

    qp = jax.jit(quantize_params)(PARAMS)
    layer1 = qp["layers"][1]
    for k in ("w_dq", "w_uq", "w_dkv", "w_uk", "w_uv", "wo",
              "w_shared_gate", "w_shared_up", "w_shared_down"):
        assert is_quantized(layer1[k]), k
    H, dn, dc = CFG.num_heads, CFG.qk_nope_head_dim, CFG.kv_lora_rank
    assert layer1["w_uk"]["s"].shape == (H, dc)            # contract dn
    assert layer1["w_uv"]["s"].shape == (H, CFG.v_head_dim)  # contract dc

    ecfg = EngineConfig(
        model=CFG, dtype="float32", block_size=4, num_blocks=64,
        max_num_seqs=2, max_model_len=128, quant="int8",
    )
    r = ModelRunner(ecfg, params=PARAMS)
    prompt = [1, 5, 9, 2, 7]
    tok = step_token(r, prompt, [1, 2, 3, 4])
    assert [tok] == reference_greedy(CFG, qp, prompt, 1, length=128)


def test_hf_load_applies_rope_permutation(tmp_path):
    """The HF checkpoint's pair-interleaved rope dims are permuted to
    NeoX halves at load: q_pe column k of head h must land at the
    permuted position."""
    from safetensors.numpy import save_file

    cfg = ModelConfig.tiny_mla_test().scaled(num_layers=1, num_experts=0,
                                             first_k_dense_replace=1)
    H, dn, dr, dc = (
        cfg.num_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
        cfg.kv_lora_rank,
    )
    D, dv = cfg.hidden_size, cfg.v_head_dim
    rng = np.random.default_rng(1)

    def t(*shape):
        return (rng.standard_normal(shape) * 0.02).astype(np.float32)

    qb = t(H * (dn + dr), cfg.q_lora_rank)
    dkv = t(dc + dr, D)
    tensors = {
        "model.embed_tokens.weight": t(cfg.vocab_size, D),
        "model.norm.weight": np.ones(D, np.float32),
        "lm_head.weight": t(cfg.vocab_size, D),
        "model.layers.0.self_attn.q_a_proj.weight": t(cfg.q_lora_rank, D),
        "model.layers.0.self_attn.q_a_layernorm.weight": np.ones(
            cfg.q_lora_rank, np.float32
        ),
        "model.layers.0.self_attn.q_b_proj.weight": qb,
        "model.layers.0.self_attn.kv_a_proj_with_mqa.weight": dkv,
        "model.layers.0.self_attn.kv_a_layernorm.weight": np.ones(dc, np.float32),
        "model.layers.0.self_attn.kv_b_proj.weight": t(H * (dn + dv), dc),
        "model.layers.0.self_attn.o_proj.weight": t(D, H * dv),
        "model.layers.0.input_layernorm.weight": np.ones(D, np.float32),
        "model.layers.0.post_attention_layernorm.weight": np.ones(D, np.float32),
        "model.layers.0.mlp.gate_proj.weight": t(cfg.intermediate_size, D),
        "model.layers.0.mlp.up_proj.weight": t(cfg.intermediate_size, D),
        "model.layers.0.mlp.down_proj.weight": t(D, cfg.intermediate_size),
    }
    save_file(tensors, str(tmp_path / "model.safetensors"))
    params = llama.load_hf_weights(cfg, str(tmp_path), dtype=jnp.float32)
    perm = np.concatenate([np.arange(0, dr, 2), np.arange(1, dr, 2)])
    # our w_uq is qb.T [q_lora, H*(dn+dr)]; head 0's pe block permuted
    got = np.asarray(params["layers"][0]["w_uq"]).reshape(
        cfg.q_lora_rank, H, dn + dr
    )[:, 0, dn:]
    want = qb.T.reshape(cfg.q_lora_rank, H, dn + dr)[:, 0, dn:][:, perm]
    np.testing.assert_allclose(got, want, atol=1e-6)
    # k_pe rows of w_dkv permuted the same way
    got_k = np.asarray(params["layers"][0]["w_dkv"])[:, dc:]
    want_k = dkv.T[:, dc:][:, perm]
    np.testing.assert_allclose(got_k, want_k, atol=1e-6)


def test_deepseek_config_from_hf(tmp_path):
    hf = {
        "architectures": ["DeepseekV3ForCausalLM"],
        "model_type": "deepseek_v3",
        "vocab_size": 129280,
        "hidden_size": 7168,
        "intermediate_size": 18432,
        "num_hidden_layers": 61,
        "num_attention_heads": 128,
        "num_key_value_heads": 128,
        "kv_lora_rank": 512,
        "q_lora_rank": 1536,
        "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64,
        "v_head_dim": 128,
        "n_routed_experts": 256,
        "num_experts_per_tok": 8,
        "n_shared_experts": 1,
        "moe_intermediate_size": 2048,
        "first_k_dense_replace": 3,
        "scoring_func": "sigmoid",
        "norm_topk_prob": True,
        "routed_scaling_factor": 2.5,
        "n_group": 8,
        "topk_group": 4,
        "rms_norm_eps": 1e-6,
        "rope_theta": 10000,
        "max_position_embeddings": 163840,
        # real DeepSeek configs ship yarn scaling — from_hf must accept it
        "rope_scaling": {
            "type": "yarn", "factor": 40, "beta_fast": 32, "beta_slow": 1,
            "mscale": 1.0, "mscale_all_dim": 1.0,
            "original_max_position_embeddings": 4096,
        },
    }
    (tmp_path / "config.json").write_text(json.dumps(hf))
    cfg = ModelConfig.from_hf(str(tmp_path))
    assert cfg.is_mla and cfg.is_moe
    assert cfg.kv_lora_rank == 512 and cfg.q_lora_rank == 1536
    assert cfg.gating == "sigmoid"
    assert cfg.n_group == 8 and cfg.topk_group == 4
    assert cfg.rope_scaling.kind == "yarn"
    assert cfg.num_experts == 256 and cfg.n_shared_experts == 1
    assert cfg.first_k_dense_replace == 3
    assert not cfg.moe_layer(2) and cfg.moe_layer(3)
    # 671B MLA cache entry: 576 dims/token vs 128 heads × 128 dims × 2 —
    # the 57x KV compression that makes R1 servable.
    assert cfg.kv_cache_head_dim == 576


def test_mla_absorbed_matches_standard_formulation():
    """ADVICE r03: independent parity oracle for the absorbed MLA math.

    The engine's MLA path (_qkv_mla) projects queries INTO the latent
    space and runs MQA over [latent ‖ k_pe]; hidden_states() shares that
    code, so an error in the absorption algebra or the
    ((dc+dr)/(dn+dr))^0.5 / mscale^2 score correction would cancel out in
    the engine-vs-oracle tests. Here the NON-absorbed formulation (HF
    DeepseekV2Attention: materialize per-head K/V from w_uk/w_uv, standard
    softmax attention at 1/sqrt(dn+dr)) is implemented from scratch and
    must reproduce reference_forward's logits."""
    from dynamo_tpu.models.llama import (
        _logits,
        _mlp,
        apply_rope,
        embed_lookup,
        rms_norm,
    )
    from dynamo_tpu.ops.quant import qmm

    cfg, params = CFG, PARAMS
    H = cfg.num_heads
    dn, dr, dc = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.kv_lora_rank
    token_ids = jnp.asarray([1, 5, 9, 2, 7, 3, 3, 8, 11, 4])
    T = token_ids.shape[0]
    positions = jnp.arange(T)

    def standard_mla_attn(layer, h):
        if cfg.q_lora_rank:
            cq = rms_norm(qmm(h, layer["w_dq"]), layer["ln_q"], cfg.rms_eps)
            q = qmm(cq, layer["w_uq"])
        else:
            q = qmm(h, layer["wq"])
        q = q.reshape(T, H, dn + dr)
        q_nope, q_pe = q[..., :dn], q[..., dn:]
        q_pe = apply_rope(q_pe, positions, cfg.rope_theta, cfg.rope_scaling)
        ckr = qmm(h, layer["w_dkv"])
        c = rms_norm(ckr[:, :dc], layer["ln_kv"], cfg.rms_eps)
        k_pe = apply_rope(
            ckr[:, None, dc:], positions, cfg.rope_theta, cfg.rope_scaling
        )[:, 0]
        # Materialized per-head K/V — kv_b_proj in HF terms.
        k_nope = jnp.einsum("tc,hnc->thn", c, layer["w_uk"])
        v = jnp.einsum("tc,hvc->thv", c, layer["w_uv"])
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_pe[:, None, :], (T, H, dr))], axis=-1
        )
        qh = jnp.concatenate([q_nope, q_pe], axis=-1)
        scale = (dn + dr) ** -0.5
        if cfg.rope_scaling is not None:
            scale *= cfg.rope_scaling.attn_mscale() ** 2
        scores = jnp.einsum("thd,shd->hts", qh, k) * scale
        causal = jnp.tril(jnp.ones((T, T), bool))
        scores = jnp.where(causal[None], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1)
        o = jnp.einsum("hts,shv->thv", probs, v)
        return qmm(o.reshape(T, H * cfg.v_head_dim), layer["wo"])

    x = embed_lookup(params["embed"], token_ids)
    for li, layer in enumerate(params["layers"]):
        h = rms_norm(x, layer["ln_attn"], cfg.rms_eps)
        x = x + standard_mla_attn(layer, h)
        h = rms_norm(x, layer["ln_mlp"], cfg.rms_eps)
        x = x + _mlp(layer, h, cfg, cfg.layer_spec(li))
    standard_logits = np.asarray(_logits(params, cfg, x))

    absorbed_logits = np.asarray(
        llama.reference_forward(cfg, params, token_ids)
    )
    np.testing.assert_allclose(
        standard_logits, absorbed_logits, rtol=2e-4, atol=2e-4
    )
    # And greedy argmax agrees everywhere (the serving-visible contract).
    assert list(standard_logits.argmax(-1)) == list(
        absorbed_logits.argmax(-1)
    )
