"""The grouped expert path (models/moe.py _moe_mlp_grouped): exact for
every routing — it must agree with the dense gate-masked formulation for
few and many experts, under collision, with an expert that gets no row,
with quantized weights, on the grouped matmul kernel, end-to-end through
the engine, and sharded over ep and tp like the dense path."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dynamo_tpu.models import llama, moe
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.models.moe import (
    MoeConfig,
    init_moe_params,
    moe_mlp,
    shard_moe_params,
)
from dynamo_tpu.parallel.mesh import build_mesh
from stepdrive import reference_greedy, step_token

pytestmark = pytest.mark.anyio


def dense(params, x, cfg, monkeypatch):
    """The dense formulation, whatever the expert count."""
    with monkeypatch.context() as m:
        m.setattr(moe, "GROUPED_MIN_EXPERTS", 10**9)
        assert not cfg.grouped
        return moe_mlp(params, x, cfg)


def grouped(params, x, cfg, mesh=None):
    return moe._moe_mlp_grouped(params, x, cfg, mesh)


def _cfg(**kw):
    base = dict(
        hidden_size=32, intermediate_size=48, num_experts=4,
        num_experts_per_tok=2,
    )
    base.update(kw)
    return MoeConfig(**base)


@pytest.mark.parametrize("experts,topk", [(8, 2), (16, 4), (128, 8)])
def test_grouped_matches_dense(experts, topk, monkeypatch):
    cfg = _cfg(num_experts=experts, num_experts_per_tok=topk)
    params = init_moe_params(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (24, 32), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(grouped(params, x, cfg)),
        np.asarray(dense(params, x, cfg, monkeypatch)), atol=2e-5,
    )
    # and moe_mlp picks the path by the expert count alone
    assert cfg.grouped == (experts >= 16)


def test_grouped_matches_dense_sigmoid_grouped(monkeypatch):
    cfg = _cfg(
        gating="sigmoid", n_group=2, topk_group=1, routed_scaling_factor=2.5
    )
    params = init_moe_params(jax.random.PRNGKey(2), cfg)
    params["router_bias"] = jnp.asarray([0.1, 0.0, 0.4, 0.0], jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(3), (12, 32), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(dense(params, x, cfg, monkeypatch)),
        np.asarray(grouped(params, x, cfg)),
        atol=2e-5,
    )


def test_grouped_drops_nothing_under_collision(monkeypatch):
    """Identical tokens route identically: every row lands on the same two
    experts, the collision a capacity buffer overflowed on. The grouped
    path has no capacity: every token keeps its dense value."""
    cfg = _cfg(num_experts=16)
    params = init_moe_params(jax.random.PRNGKey(0), cfg)
    x = jnp.tile(
        jax.random.normal(jax.random.PRNGKey(4), (1, 32), jnp.float32), (16, 1)
    )
    out = grouped(params, x, cfg)
    assert bool(jnp.all(jnp.isfinite(out)))
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(dense(params, x, cfg, monkeypatch)),
        atol=2e-5,
    )
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(out[-1]), atol=1e-6)


def test_grouped_with_experts_that_get_no_row(monkeypatch):
    """Uneven routing with empty groups: the router is biased so that six
    of 16 experts are never chosen and one takes a row from every token."""
    cfg = _cfg(num_experts=16, num_experts_per_tok=4)
    params = init_moe_params(jax.random.PRNGKey(5), cfg)
    bias = np.zeros(16, np.float32)
    bias[[1, 4, 5, 9, 12, 15]] = -50.0
    bias[7] = 50.0
    x = jax.random.normal(jax.random.PRNGKey(6), (20, 32), jnp.float32)
    # a column added to every row's logits through a constant feature
    x = x.at[:, 0].set(1.0)
    params["w_router"] = params["w_router"].at[0].set(jnp.asarray(bias))
    topi, _ = moe.moe_route(params, x, cfg)
    chosen = set(np.asarray(topi).ravel().tolist())
    assert not chosen & {1, 4, 5, 9, 12, 15} and 7 in chosen
    assert (np.asarray(topi) == 7).sum() == 20
    np.testing.assert_allclose(
        np.asarray(grouped(params, x, cfg)),
        np.asarray(dense(params, x, cfg, monkeypatch)), atol=2e-5,
    )


def test_grouped_with_quantized_expert_weights(monkeypatch):
    from dynamo_tpu.ops.quant import quantize_weight

    cfg = _cfg(num_experts=16, num_experts_per_tok=4)
    params = init_moe_params(jax.random.PRNGKey(7), cfg)
    for name in ("w_gate", "w_up", "w_down"):
        params[name] = quantize_weight(params[name])
    x = jax.random.normal(jax.random.PRNGKey(8), (12, 32), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(grouped(params, x, cfg)),
        np.asarray(dense(params, x, cfg, monkeypatch)), atol=5e-5,
    )


def test_grouped_matmul_kernel_matches_ragged_dot(monkeypatch):
    """On the Pallas path (interpret mode here) the products are the
    megablox grouped matmul kernel; widths its tiling takes (multiples of
    128) give what ``jax.lax.ragged_dot`` gives."""
    cfg = _cfg(hidden_size=128, intermediate_size=128, num_experts=16,
               num_experts_per_tok=4)
    params = init_moe_params(jax.random.PRNGKey(9), cfg)
    x = jax.random.normal(jax.random.PRNGKey(10), (32, 128), jnp.float32)
    calls = []
    import importlib

    # (the package's own ``gmm`` attribute is the function, not the module)
    gmm_module = importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")
    real = gmm_module.gmm
    monkeypatch.setattr(
        gmm_module, "gmm",
        lambda *a, **kw: calls.append(kw["interpret"]) or real(*a, **kw),
    )
    monkeypatch.setenv("DYNAMO_TPU_PALLAS", "1")
    kernel = grouped(params, x, cfg)
    assert calls == [True, True, True]      # gate, up, down
    monkeypatch.setenv("DYNAMO_TPU_PALLAS", "0")
    plain = grouped(params, x, cfg)
    assert len(calls) == 3
    np.testing.assert_allclose(
        np.asarray(kernel), np.asarray(plain), atol=2e-5)


async def test_grouped_path_engine_end_to_end():
    """A 16-expert model served through the grouped path produces the
    same greedy tokens as its own oracle (reference_forward runs the same
    expert path), proving the paged serving path composes with it."""
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.engine import TpuEngine
    from dynamo_tpu.llm.protocols.common import (
        EngineOutput,
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )
    from dynamo_tpu.runtime.engine import Context

    cfg = ModelConfig.tiny_moe_test().scaled(
        num_experts=16, num_experts_per_tok=4, intermediate_size=32
    )
    params = llama.init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)

    engine = TpuEngine(
        EngineConfig(
            model=cfg, dtype="float32", block_size=4, num_blocks=64,
            max_num_seqs=2, max_model_len=128,
        ),
        params=params,
    )
    await engine.start()
    try:
        prompt = [1, 5, 9, 2, 7]
        pre = PreprocessedRequest(
            token_ids=prompt,
            sampling=SamplingOptions(temperature=0.0),
            stop=StopConditions(max_tokens=8, ignore_eos=True),
        )
        tokens = []
        async for raw in engine.generate(Context(pre.to_wire())):
            tokens.extend(EngineOutput.from_wire(raw).token_ids)
        assert tokens == reference_greedy(cfg, params, prompt, 8, length=128)
    finally:
        await engine.stop()


def test_grouped_path_sharded_matches_single():
    """ep×tp-sharded grouped path = single-device grouped path, through
    the runner (shard_map places the products; one all-reduce)."""
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.runner import ModelRunner

    cfg = ModelConfig.tiny_moe_test().scaled(
        num_experts=16, num_experts_per_tok=4, intermediate_size=32
    )
    params = llama.init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    ecfg = EngineConfig(
        model=cfg, dtype="float32", block_size=16, num_blocks=32,
        max_num_seqs=2, max_model_len=128,
    )
    prompt = list(range(2, 18))
    tok = step_token(
        ModelRunner(ecfg, params=params), prompt, [1, 2, 3, 4]
    )
    mesh = build_mesh({"ep": 2, "tp": 2, "dp": 2})
    tok2 = step_token(
        ModelRunner(ecfg, params=params, mesh=mesh), prompt, [1, 2, 3, 4]
    )
    assert tok == tok2


def test_path_is_chosen_by_the_expert_count_alone():
    """Dense below 16 experts (Mixtral's 8 keep their program), grouped
    from 16 up; no option names a path."""
    assert not MoeConfig(num_experts=8).grouped
    assert MoeConfig(num_experts=16).grouped
    assert MoeConfig(num_experts=256).grouped
    assert not hasattr(ModelConfig.tiny_moe_test(), "moe_dispatch")
    assert not hasattr(MoeConfig(), "dispatch")
    assert not hasattr(moe, "_moe_mlp_capacity")


@pytest.mark.parametrize("mesh_shape", [
    {"ep": 4, "dp": 2}, {"tp": 2, "dp": 4}, {"ep": 2, "tp": 2, "dp": 2},
])
def test_grouped_under_a_mesh_matches_dense(mesh_shape, monkeypatch):
    """A 16-expert layer under ep, tp and ep×tp meshes: each shard runs
    the groups of its own experts over its slice of their width, and the
    all-reduce gives the dense formulation's output."""
    mesh = build_mesh(mesh_shape)
    kw = dict(
        hidden_size=32, intermediate_size=16, num_experts=16,
        num_experts_per_tok=4,
    )
    cfg = MoeConfig(**kw)
    params = init_moe_params(jax.random.PRNGKey(0), cfg)
    x = jnp.asarray(
        np.random.default_rng(1).standard_normal((24, 32)), jnp.float32
    )
    sharded = shard_moe_params(params, mesh)
    got = jax.jit(lambda p, xx: moe_mlp(p, xx, cfg, mesh=mesh))(sharded, x)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(dense(params, x, cfg, monkeypatch)),
        rtol=2e-4, atol=2e-4,
    )


def test_grouped_is_exact_at_decode_token_counts(monkeypatch):
    """At decode-size T (8 tokens, 32 experts) a capacity buffer held one
    row an expert and dropped the rest; the grouped path has nothing to
    fall back from."""
    cfg = MoeConfig(
        hidden_size=32, intermediate_size=16, num_experts=32,
        num_experts_per_tok=2,
    )
    assert cfg.grouped
    params = init_moe_params(jax.random.PRNGKey(0), cfg)
    x = jnp.asarray(
        np.random.default_rng(2).standard_normal((8, 32)), jnp.float32
    )
    np.testing.assert_allclose(
        np.asarray(moe_mlp(params, x, cfg)),
        np.asarray(dense(params, x, cfg, monkeypatch)), atol=2e-5,
    )
