"""Capacity-dispatch MoE (models/moe.py _moe_mlp_capacity): must agree
with the dense gate-masked formulation when capacity is ample, degrade by
the standard overflow-drop rule when it isn't, stay exact end-to-end
through the engine, and shard over ep like the dense path."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dynamo_tpu.models import llama
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.models.moe import MoeConfig, init_moe_params, moe_mlp
from dynamo_tpu.parallel.mesh import build_mesh
from stepdrive import step_token

pytestmark = pytest.mark.anyio


def _cfgs(**kw):
    base = dict(
        hidden_size=32, intermediate_size=48, num_experts=4,
        num_experts_per_tok=2,
    )
    base.update(kw)
    dense = MoeConfig(**base, dispatch="dense")
    cap = MoeConfig(**base, dispatch="capacity", capacity_factor=4.0)
    return dense, cap


def test_capacity_matches_dense_when_ample():
    dense, cap = _cfgs()
    params = init_moe_params(jax.random.PRNGKey(0), dense)
    x = jax.random.normal(jax.random.PRNGKey(1), (16, 32), jnp.float32)
    out_d = moe_mlp(params, x, dense)
    out_c = moe_mlp(params, x, cap)
    np.testing.assert_allclose(np.asarray(out_d), np.asarray(out_c), atol=2e-5)


def test_capacity_matches_dense_sigmoid_grouped():
    dense, cap = _cfgs(
        gating="sigmoid", n_group=2, topk_group=1, routed_scaling_factor=2.5
    )
    params = init_moe_params(jax.random.PRNGKey(2), dense)
    params["router_bias"] = jnp.asarray([0.1, 0.0, 0.4, 0.0], jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(3), (12, 32), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(moe_mlp(params, x, dense)),
        np.asarray(moe_mlp(params, x, cap)),
        atol=2e-5,
    )


def test_capacity_overflow_drops_tokens():
    """With capacity_factor shrunk below fair share, some (token, expert)
    assignments drop — output differs from dense but stays finite and
    earlier tokens (which claim slots first) keep their dense value."""
    dense, _ = _cfgs()
    tight = MoeConfig(
        hidden_size=32, intermediate_size=48, num_experts=4,
        num_experts_per_tok=2, dispatch="capacity", capacity_factor=0.25,
    )
    params = init_moe_params(jax.random.PRNGKey(0), dense)
    x = jnp.tile(
        jax.random.normal(jax.random.PRNGKey(4), (1, 32), jnp.float32), (16, 1)
    )  # identical tokens → identical routing → guaranteed overflow
    out_d = moe_mlp(params, x, dense)
    out_t = moe_mlp(params, x, tight)
    assert bool(jnp.all(jnp.isfinite(out_t)))
    # first token gets both its slots; dense value preserved
    np.testing.assert_allclose(
        np.asarray(out_d[0]), np.asarray(out_t[0]), atol=2e-5
    )
    # the last token lost at least one expert
    assert float(jnp.max(jnp.abs(out_d[-1] - out_t[-1]))) > 1e-6


async def test_capacity_dispatch_engine_end_to_end():
    """A MoE model served with capacity dispatch produces the same greedy
    tokens as its own oracle (reference_forward shares the dispatch via
    ModelConfig), proving the paged serving path composes with it."""
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
        moe_dispatch="capacity", moe_capacity_factor=4.0
    )
    params = llama.init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)

    def oracle(prompt, n):
        toks = list(prompt)
        out = []
        for _ in range(n):
            logits = llama.reference_forward(cfg, params, jnp.asarray(toks))
            nxt = int(jnp.argmax(logits[-1]))
            toks.append(nxt)
            out.append(nxt)
        return out

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
        assert tokens == oracle(prompt, 8)
    finally:
        await engine.stop()


def test_capacity_dispatch_sharded_matches_single():
    """ep×tp-sharded capacity dispatch = single-device capacity dispatch
    (the scatter/gather cross ep shards; GSPMD inserts the collectives)."""
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.runner import ModelRunner

    cfg = ModelConfig.tiny_moe_test().scaled(
        moe_dispatch="capacity", moe_capacity_factor=4.0
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


def test_auto_dispatch_crossover():
    """"auto" (the default) resolves by expert count: dense below 16
    experts (dense's E/topk FLOP waste is cheaper than dispatch), capacity
    at 16+ (measured crossover — benchmarks/moe_bench.py; on an ep mesh
    capacity wins ~3.9x at E=128)."""
    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.models.moe import MoeConfig

    assert MoeConfig(num_experts=8).resolved_dispatch == "dense"
    assert MoeConfig(num_experts=16).resolved_dispatch == "capacity"
    assert MoeConfig(num_experts=256).resolved_dispatch == "capacity"
    assert MoeConfig(num_experts=256, dispatch="dense").resolved_dispatch == "dense"
    assert ModelConfig.tiny_moe_test().moe_dispatch == "auto"


def test_auto_capacity_ep_mesh_matches_dense(monkeypatch):
    """A 16-expert model under an ep mesh takes the capacity path via
    "auto" with ep-pinned buffers and must produce the same output as the
    dense formulation (ample capacity)."""
    import numpy as np

    from dynamo_tpu.models.moe import (
        MoeConfig,
        init_moe_params,
        moe_mlp,
        shard_moe_params,
    )
    from dynamo_tpu.parallel.mesh import build_mesh

    mesh = build_mesh({"ep": 4, "dp": 2})
    kw = dict(
        hidden_size=32, intermediate_size=16, num_experts=16,
        num_experts_per_tok=4,
    )
    params = init_moe_params(jax.random.PRNGKey(0), MoeConfig(**kw))
    x = jnp.asarray(
        np.random.default_rng(1).standard_normal((24, 32)), jnp.float32
    )
    auto_cfg = MoeConfig(**kw, capacity_factor=4.0)  # auto -> capacity
    assert auto_cfg.resolved_dispatch == "capacity"
    sharded = shard_moe_params(params, mesh)
    got = jax.jit(lambda p, xx: moe_mlp(p, xx, auto_cfg, mesh=mesh))(
        sharded, x
    )
    want = moe_mlp(params, x, MoeConfig(**kw, dispatch="dense"))
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4
    )


def test_auto_falls_back_to_dense_at_decode_token_counts():
    """At decode-size T, capacity C collapses toward 1 and collisions DROP
    routed contributions — "auto" must run dense there and stay exact."""
    import numpy as np

    from dynamo_tpu.models.moe import MoeConfig, init_moe_params, moe_mlp

    kw = dict(
        hidden_size=32, intermediate_size=16, num_experts=32,
        num_experts_per_tok=2,
    )
    cfg = MoeConfig(**kw)  # auto; E=32 >= 16 but T is tiny
    assert cfg.resolved_dispatch == "capacity"
    assert not cfg.auto_capacity_ok(8)   # 8*2 < 2*32
    assert cfg.auto_capacity_ok(64)      # 64*2 >= 2*32
    params = init_moe_params(jax.random.PRNGKey(0), cfg)
    x = jnp.asarray(
        np.random.default_rng(2).standard_normal((8, 32)), jnp.float32
    )
    got = moe_mlp(params, x, cfg)
    want = moe_mlp(params, x, MoeConfig(**kw, dispatch="dense"))
    # Bit-exact: auto at T=8 must have taken the dense path (capacity with
    # C=1 would drop colliding tokens and diverge).
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
