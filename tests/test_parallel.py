"""Mesh/sharding tests on the 8-device virtual CPU mesh (conftest.py).

Mirrors how the reference tests distributed behavior without hardware
(reference: lib/runtime/tests/common/mock.rs mock network); here the mock
is XLA's host-platform device override.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.engine.runner import ModelRunner
from dynamo_tpu.models import llama
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.parallel.mesh import MESH_AXES, build_mesh
from dynamo_tpu.parallel.sharding import shard_params
from dynamo_tpu.parallel.train import make_train_step
from stepdrive import greedy_tokens


def test_build_mesh_defaults_to_tp():
    mesh = build_mesh()
    assert mesh.shape["tp"] == len(jax.devices())
    assert mesh.axis_names == MESH_AXES


def test_build_mesh_explicit_shape():
    mesh = build_mesh({"dp": 2, "tp": 2, "sp": 2})
    assert mesh.shape == {"dp": 2, "sp": 2, "ep": 1, "tp": 2}


def test_build_mesh_bad_shape():
    with pytest.raises(ValueError):
        build_mesh({"dp": 3, "tp": 3})


def test_sharded_decode_matches_single_device():
    """TP-sharded engine step must produce identical tokens to unsharded."""
    cfg = ModelConfig.tiny_test()
    ecfg = EngineConfig(
        model=cfg, num_blocks=32, max_num_seqs=4, max_model_len=64,
        dtype="float32",
    )
    prompt = [5, 9, 2, 7, 11, 3]

    def run(mesh):
        runner = ModelRunner(ecfg, mesh=mesh, rng_seed=0)
        return greedy_tokens(runner, prompt, [1], 4)

    single = run(None)
    sharded = run(build_mesh({"dp": 2, "tp": 2, "sp": 2}))
    assert single == sharded


def test_fed_dispatch_under_a_mesh_compiles_nothing_new():
    """Input shardings are part of jit's cache key. Under a mesh the fed
    tokens arrive as the program's own replicated device output, so the
    stand-in used when nothing is fed (warmup, a first dispatch) must
    carry that sharding too — a plain host array compiled every budget
    rung a second time on the first fed dispatch, mid-traffic and
    counted by nobody (PR 22, four chips: 78 s for ten requests)."""
    ecfg = EngineConfig(
        model=ModelConfig.tiny_test(), num_blocks=32, max_num_seqs=4,
        max_model_len=64, unified_token_budget=32,
    )
    runner = ModelRunner(
        ecfg, mesh=build_mesh({"dp": 2, "tp": 2, "sp": 2}), rng_seed=0
    )
    # Every operand of every call into the program, as jit received it.
    calls = []

    class Spy:
        def __init__(self, program):
            self.program = program

        def __call__(self, *args):
            calls.append(args)
            return self.program(*args)

        def __getattr__(self, name):
            return getattr(self.program, name)

    runner._unified = Spy(runner._unified)
    runner.warmup()
    warmed = runner.unified_executables()
    n_warm = len(calls)
    S, greedy = runner.unified_slots, (0.0, 0, 1.0)
    row, use = np.zeros(S, np.int32), np.zeros(S, bool)
    out = runner.unified_step(
        [([5, 9, 2, 7, 11], [1], 0, greedy)],
        feed=(np.zeros(S, np.int32), row, use),  # the engine's first step
    )
    assert runner.operand_transfers == 1
    use[0] = True
    out = runner.unified_step([([0], [1], 5, greedy)], feed=(out.last, row, use))
    assert runner.operand_transfers == 1
    # A replayed host feed whose values are read (stepcast, tools).
    host = np.asarray(out.last)
    runner.unified_step([([0], [1], 6, greedy)], feed=(host, row, use))
    assert runner.operand_transfers == 2
    assert runner.unified_executables() == warmed
    assert runner.compile_stats.snapshot()["mid_traffic_compiles_total"] == 0
    # The packed operand is placed ONCE, replicated over the mesh — on
    # warmup, on the unfed and on both fed dispatches alike — and nothing
    # the call takes sits on one chip for jit to copy to the others.
    assert n_warm == warmed and len(calls) == n_warm + 3
    devices = set(runner.mesh.devices.flat)
    for args in calls:
        packed, prev_toks = args[-2:]
        assert packed.sharding == prev_toks.sharding == runner._tok_sh
        assert packed.dtype == np.int32 and packed.ndim == 1
        for leaf in jax.tree.leaves(args):
            assert isinstance(leaf, jax.Array), type(leaf)
            assert leaf.sharding.device_set == devices, leaf.sharding


def test_extras_program_under_a_mesh_compiles_once():
    """The count buffer is born with the sharding the extras program
    returns it in, so its second dispatch finds the first's executable."""
    ecfg = EngineConfig(
        model=ModelConfig.tiny_test(), num_blocks=32, max_num_seqs=4,
        max_model_len=64, unified_token_budget=32,
    )
    runner = ModelRunner(
        ecfg, mesh=build_mesh({"dp": 2, "tp": 2, "sp": 2}), rng_seed=0
    )
    extras = {"slots": [0], "counts_add": [False], "reset": [True],
              "freq": [0.0], "pres": [0.0]}
    for _ in range(2):
        runner.unified_step([([5, 9, 2], [1], 0, (0.0, 0, 1.0))], extras=extras)
        assert runner._counts.sharding == runner._tok_sh
    assert runner._unified_full._cache_size() == 1


def test_sharded_pallas_decode_matches_single_device_jnp(monkeypatch):
    """The Pallas kernels under shard_map over tp (interpret mode on CPU)
    must produce the same tokens as the single-chip jnp path — the gate
    before trusting TP-sharded serving perf."""
    cfg = ModelConfig.tiny_test()
    ecfg = EngineConfig(
        model=cfg, num_blocks=32, max_num_seqs=4, max_model_len=64,
        dtype="float32",
    )
    prompt = [5, 9, 2, 7, 11, 3]

    def run(mesh, pallas: bool):
        monkeypatch.setenv("DYNAMO_TPU_PALLAS", "1" if pallas else "0")
        runner = ModelRunner(ecfg, mesh=mesh, rng_seed=0)
        assert runner.attn.use_pallas is pallas
        if pallas and mesh is not None:
            assert runner.attn.mesh is mesh  # shard_map path, not fallback
        return greedy_tokens(runner, prompt, [1, 2, 3, 4], 4)

    baseline = run(None, pallas=False)
    assert run(build_mesh({"dp": 4, "tp": 2}), pallas=True) == baseline
    assert run(build_mesh({"dp": 2, "tp": 2, "sp": 2}), pallas=True) == baseline


def test_train_step_runs_and_learns():
    mesh = build_mesh({"dp": 2, "tp": 2, "sp": 2})
    cfg = ModelConfig.tiny_test()
    params = llama.init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    params = shard_params(params, mesh, cfg=cfg)
    step = make_train_step(cfg, mesh, lr=1e-2)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, cfg.vocab_size)
    params, loss0 = step(params, tokens)
    for _ in range(5):
        params, loss = step(params, tokens)
    assert float(loss) < float(loss0)


def test_moe_ep_sharded_matches_replicated():
    """The ep-sharded MoE layer (models/moe.py) must match an unsharded
    run bit-for-time: GSPMD turns the expert-dim contractions into psums
    over ep, never changing the math (stage-5 prerequisite, BASELINE.md)."""
    import numpy as np

    from dynamo_tpu.models.moe import (
        MoeConfig,
        init_moe_params,
        moe_mlp,
        shard_moe_params,
    )

    cfg = MoeConfig(num_experts=8, num_experts_per_tok=2)
    params = init_moe_params(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (16, cfg.hidden_size))

    ref = moe_mlp(params, x, cfg)
    assert np.isfinite(np.asarray(ref)).all()

    mesh = build_mesh({"dp": 2, "ep": 2, "tp": 2})
    sharded = jax.jit(lambda p, x: moe_mlp(p, x, cfg))(
        shard_moe_params(params, mesh), x
    )
    np.testing.assert_allclose(
        np.asarray(sharded), np.asarray(ref), rtol=2e-5, atol=2e-5
    )
    assert ref.shape == x.shape
    # Router sparsity: exactly top-k experts carry gate mass per token and
    # the renormalized softmax sums to 1.
    from dynamo_tpu.models.moe import moe_router

    gates = np.asarray(moe_router(params, x, cfg))
    assert ((gates > 0).sum(axis=-1) == cfg.num_experts_per_tok).all()
    np.testing.assert_allclose(gates.sum(axis=-1), 1.0, rtol=1e-5)


def test_moe_model_ep_sharded_serving_matches_single_device(monkeypatch):
    """Mixtral-style MoE model under an ep×tp mesh: expert-parallel routed
    MLPs in the serving prefill/decode path must produce tokens identical
    to the single-device runner (the DeepSeek-R1/Mixtral stage-5 serving
    prerequisite — BASELINE.md stage 5)."""
    cfg = ModelConfig.tiny_moe_test()
    ecfg = EngineConfig(
        model=cfg, num_blocks=64, max_num_seqs=4, max_model_len=128,
        dtype="float32",
    )
    prompt = list(range(3, 35))  # 32 tokens

    def run(mesh):
        runner = ModelRunner(ecfg, mesh=mesh, rng_seed=1)
        return greedy_tokens(runner, prompt, [1, 2, 3], 8)

    baseline = run(None)
    assert run(build_mesh({"ep": 2, "tp": 2, "dp": 2})) == baseline
    assert run(build_mesh({"ep": 4, "tp": 2})) == baseline


def test_ring_attention_matches_full_causal():
    """Ring attention (K/V sharded over sp, blocks rotating via ppermute
    with an online-softmax fold) must match plain causal attention — the
    long-context primitive whose per-chip memory is O(T/n)."""
    from dynamo_tpu.ops.attention import full_causal_attention
    from dynamo_tpu.ops.ring_attention import ring_attention_sharded

    T, H, kvH, D = 64, 4, 2, 16
    key = jax.random.PRNGKey(0)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (T, H, D), jnp.float32)
    k = jax.random.normal(kk, (T, kvH, D), jnp.float32)
    v = jax.random.normal(kv, (T, kvH, D), jnp.float32)

    ref = full_causal_attention(q, k, v)
    for sp in (2, 4, 8):
        mesh = build_mesh({"sp": sp, "tp": 1, "dp": 8 // sp})
        got = ring_attention_sharded(mesh, q, k, v)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5,
            err_msg=f"sp={sp}",
        )


def test_llama70b_kv_sp_tp_sharded_step_lowers():
    """Scale proof at the compile-shape level (BASELINE.md steps 4-5):
    the REAL Llama-3-70B config's step (every lane a one-token span of
    the served llama.unified) traces and lowers under a
    {tp: 4, sp: 2} mesh with the kv_sp slot+head-sharded cache —
    abstract params only (280 GB of weights never materialize), so this
    validates shape/divisibility/sharding-spec consistency for the
    beyond-chip target that cannot run in this environment."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.ops.attention import AttnDispatch
    from dynamo_tpu.parallel.sharding import kv_cache_spec, llama_param_specs

    cfg = ModelConfig.llama3_70b()
    mesh = build_mesh({"tp": 4, "sp": 2})
    bs, num_blocks, B, max_blocks = 16, 64, 4, 16

    params_avals = jax.eval_shape(
        lambda k: llama.init_params(k, cfg, dtype=jnp.bfloat16),
        jax.random.PRNGKey(0),
    )
    params_avals = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=NamedSharding(mesh, s)
        ),
        params_avals,
        llama_param_specs(cfg),
        is_leaf=lambda x: isinstance(x, P),
    )
    kv_sh = NamedSharding(mesh, kv_cache_spec(cfg.is_mla, sp=True))
    kv_shape = (num_blocks * bs, cfg.num_cache_heads, cfg.kv_cache_head_dim)
    kv_avals = [
        (
            jax.ShapeDtypeStruct(kv_shape, jnp.bfloat16, sharding=kv_sh),
            jax.ShapeDtypeStruct(kv_shape, jnp.bfloat16, sharding=kv_sh),
        )
        for _ in range(cfg.num_layers)
    ]
    attn = AttnDispatch(use_pallas=False, mesh=mesh, kv_sp=True)

    def step(params, kv, toks, pos, tables, ctx, slots):
        lanes = jnp.arange(B, dtype=jnp.int32)
        return llama.unified(
            cfg, params, kv, toks, pos, slots, lanes, tables, pos,
            jnp.ones_like(lanes), ctx, lanes, bs, attn=attn,
        )

    lowered = jax.jit(step).lower(
        params_avals,
        kv_avals,
        jax.ShapeDtypeStruct((B,), jnp.int32),
        jax.ShapeDtypeStruct((B,), jnp.int32),
        jax.ShapeDtypeStruct((B, max_blocks), jnp.int32),
        jax.ShapeDtypeStruct((B,), jnp.int32),
        jax.ShapeDtypeStruct((B,), jnp.int32),
    )
    # The lowered module exists and carries the mesh's axes.
    assert lowered.as_text()  # non-empty StableHLO


def test_stepcast_replays_every_block_io_form():
    """Multi-host lockstep invariant: every runner method that issues a
    device program over the sharded caches must be in REPLAYED, or rank 0
    issues SPMD programs followers never see and the mesh deadlocks
    (parallel/stepcast.py docstring). Block IO has per-block AND batched
    forms; all of them must replay."""
    from dynamo_tpu.parallel.stepcast import REPLAYED

    for name in (
        "warmup", "unified_step",
        "gather_block", "scatter_block",
        "gather_many", "gather_many_device",
        "scatter_many", "scatter_many_device",
    ):
        assert name in REPLAYED, name
