"""Beyond-one-chip contexts: the paged KV cache's slot axis sharded over
the sp mesh axis.

The engine mode under test: mesh {"sp": n} + EngineConfig.kv_sp=True puts
1/n of the cache slots on each device and runs attention as per-shard
flash partials merged with a logsumexp combine (ops/attention.py
paged_decode_attention_sp / AttnDispatch._kv_sp_decode) — per-call communication is O(query), never
O(cache). The serving proof: a sequence whose KV provably exceeds ONE
device's cache arrays decodes token-identically to a replicated-cache
oracle engine.
"""

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
from dynamo_tpu.parallel.mesh import build_mesh
from dynamo_tpu.runtime.engine import Context

pytestmark = pytest.mark.anyio

CFG = ModelConfig.tiny_test()
PARAMS = llama.init_params(jax.random.PRNGKey(0), CFG, dtype=jnp.float32)


def test_sp_attention_matches_replicated_oracle():
    """Unit parity: slot-sharded decode attention (kv_sp's XLA body) vs
    the replicated-cache reference on a random paged cache."""
    from jax.sharding import PartitionSpec as P

    from dynamo_tpu.ops.attention import (
        paged_decode_attention,
        paged_decode_attention_sp,
    )

    mesh = build_mesh({"sp": 4, "dp": 2})
    rng = np.random.default_rng(0)
    bs, nblocks, kvH, H, D = 4, 16, 2, 4, 8
    slots = nblocks * bs
    k_cache = jnp.asarray(rng.standard_normal((slots, kvH, D)), jnp.float32)
    v_cache = jnp.asarray(rng.standard_normal((slots, kvH, D)), jnp.float32)
    B = 3
    ctx = np.asarray([13, 30, 0], np.int32)
    tables = np.zeros((B, 8), np.int32)
    tables[0, :4] = [1, 2, 3, 4]
    tables[1, :8] = [5, 6, 7, 8, 9, 10, 11, 12]
    q = jnp.asarray(rng.standard_normal((B, H, D)), jnp.float32)

    want = paged_decode_attention(
        q, k_cache, v_cache, jnp.asarray(tables), jnp.asarray(ctx), bs
    )
    sp_cache = P("sp", None, None)
    got = jax.shard_map(
        lambda *a: paged_decode_attention_sp(*a, block_size=bs),
        mesh=mesh,
        in_specs=(P(), sp_cache, sp_cache, P(), P()),
        out_specs=P(),
        check_vma=False,
    )(q, k_cache, v_cache, jnp.asarray(tables), jnp.asarray(ctx))
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )


async def _generate(engine, prompt, max_tokens):
    req = PreprocessedRequest(
        token_ids=prompt,
        sampling=SamplingOptions(temperature=0.0),
        stop=StopConditions(max_tokens=max_tokens, ignore_eos=True),
    )
    toks = []
    async for item in engine.generate(Context(req.to_wire())):
        toks += item["token_ids"]
    return toks


async def test_engine_serves_context_beyond_one_devices_cache():
    """The gate: with 160 total slots sharded 40/device over sp=4, serve a
    sequence needing 130 slots — more than ANY single device's cache
    arrays hold — and match the replicated-cache oracle exactly."""
    mesh = build_mesh({"sp": 4, "dp": 2})
    sp_cfg = EngineConfig(
        model=CFG, dtype="float32", block_size=4, num_blocks=40,
        max_num_seqs=2, max_model_len=144, kv_sp=True,
    )
    oracle_cfg = EngineConfig(
        model=CFG, dtype="float32", block_size=4, num_blocks=64,
        max_num_seqs=2, max_model_len=144,
    )
    prompt = [int(x) for x in
              np.random.default_rng(7).integers(1, CFG.vocab_size, 100)]
    OUT = 30

    oracle = TpuEngine(oracle_cfg, params=PARAMS)
    await oracle.start()
    expected = await _generate(oracle, prompt, OUT)
    await oracle.stop()

    engine = TpuEngine(sp_cfg, params=PARAMS, mesh=mesh)
    await engine.start()
    try:
        # Proof of the capacity claim: each device holds 1/4 of the slots.
        k0 = engine.runner.kv_caches[0][0]
        shard_slots = {
            s.data.shape[0] for s in k0.addressable_shards
        }
        assert shard_slots == {40 * 4 // 4}, shard_slots  # 40 slots/device
        total_needed = len(prompt) + OUT  # 130 > 40 per-device slots
        assert total_needed > 40

        got = await _generate(engine, prompt, OUT)
        assert got == expected, "sp-sharded serving diverged from oracle"
    finally:
        await engine.stop()


def test_kv_sp_validation():
    with pytest.raises(ValueError, match="sp > 1"):
        from dynamo_tpu.engine.runner import ModelRunner

        ModelRunner(
            EngineConfig(
                model=CFG, dtype="float32", block_size=4, num_blocks=40,
                max_num_seqs=2, max_model_len=64, kv_sp=True,
            ),
            params=PARAMS,
        )


def _striped_tables(rng, sp: int, nblocks: int, lane_pages: list[int], width: int):
    """Block tables satisfying the striped allocator's contract: logical
    page i of a lane drawn from shard (i % sp)'s physical range, each
    physical block used once (block 0 reserved for trash)."""
    bps = nblocks // sp
    pools = [
        list(range(s * bps + (1 if s == 0 else 0), (s + 1) * bps))
        for s in range(sp)
    ]
    for p in pools:
        rng.shuffle(p)
    tables = np.zeros((len(lane_pages), width), np.int32)
    for lane, n in enumerate(lane_pages):
        for i in range(n):
            tables[lane, i] = pools[i % sp].pop()
    return tables


@pytest.mark.parametrize("use_pallas", [False, True])
def test_sp_striped_scan_matches_oracle(use_pallas):
    """The r05 striped scan (each sp shard visits ONLY its own stripe of
    logical pages — FLOPs partition sp-ways) against the replicated
    oracles, with tp head-sharding composed in, on both the jnp and the
    Pallas (interpret) paths, through the one entry that reaches it:
    AttnDispatch.ragged, decode lanes and a prefill span in one flat
    batch. Pallas needs D % 128 == 0, so the oracle runs on a
    lane-padded cache too (the production envelope)."""
    from dynamo_tpu.ops.attention import (
        AttnDispatch,
        paged_decode_attention,
        paged_prefill_attention,
    )

    mesh = build_mesh({"sp": 2, "tp": 2, "dp": 2})
    rng = np.random.default_rng(1)
    bs, nblocks, kvH, H = 4, 16, 2, 4
    D = 128 if use_pallas else 8
    slots = nblocks * bs
    k_cache = jnp.asarray(rng.standard_normal((slots, kvH, D)), jnp.float32)
    v_cache = jnp.asarray(rng.standard_normal((slots, kvH, D)), jnp.float32)
    # Rows 0-1: decode lanes at contexts 13 and 30; row 2 idle; row 3 a
    # prefill span extending a 5-token prefix by 8 tokens over lane 1's
    # pages. Flat batch: 2 decode tokens, then the span, then padding.
    tables = _striped_tables(rng, 2, nblocks, [4, 8, 0], width=8)
    tables = jnp.asarray(np.concatenate([tables, tables[1:2]]))
    q_start = np.asarray([12, 29, 0, 5], np.int32)
    q_len = np.asarray([1, 1, 0, 8], np.int32)
    row_start = np.asarray([0, 1, 0, 2], np.int32)
    T = 16
    token_seq = np.zeros(T, np.int32)
    token_pos = np.full(T, -1, np.int32)
    token_seq[:2], token_pos[:2] = [0, 1], [12, 29]
    token_seq[2:10], token_pos[2:10] = 3, np.arange(5, 13)
    q = jnp.asarray(rng.standard_normal((T, H, D)), jnp.float32)

    disp = AttnDispatch(use_pallas=use_pallas, mesh=mesh, kv_sp=True)
    got = np.asarray(disp.ragged(
        q, k_cache, v_cache, tables, jnp.asarray(token_seq),
        jnp.asarray(token_pos), jnp.asarray(q_start), jnp.asarray(q_len),
        jnp.asarray(q_start + q_len), jnp.asarray(row_start), bs,
    ))
    want_d = paged_decode_attention(
        q[:2], k_cache, v_cache, tables[:2], jnp.asarray([13, 30]), bs
    )
    np.testing.assert_allclose(
        got[:2], np.asarray(want_d), rtol=2e-5, atol=2e-5
    )
    want_p = paged_prefill_attention(
        q[2:10], k_cache, v_cache, tables[3], jnp.int32(5), jnp.int32(13), bs
    )
    np.testing.assert_allclose(
        got[2:10], np.asarray(want_p), rtol=2e-5, atol=2e-5
    )
    assert not got[10:].any()  # padding rows stay zero


async def test_engine_kv_sp_composes_with_tp():
    """A {tp: 2, sp: 2} kv_sp engine — heads
    sharded over tp AND slots over sp, striped allocator — serves
    token-identically to the replicated single-chip oracle. This is the
    mode a model too big for one chip needs for beyond-chip contexts."""
    mesh = build_mesh({"sp": 2, "tp": 2, "dp": 2})
    sp_cfg = EngineConfig(
        model=CFG, dtype="float32", block_size=4, num_blocks=40,
        max_num_seqs=2, max_model_len=144, kv_sp=True,
    )
    oracle_cfg = EngineConfig(
        model=CFG, dtype="float32", block_size=4, num_blocks=64,
        max_num_seqs=2, max_model_len=144,
    )
    prompt = [int(x) for x in
              np.random.default_rng(11).integers(1, CFG.vocab_size, 100)]
    OUT = 30

    oracle = TpuEngine(oracle_cfg, params=PARAMS)
    await oracle.start()
    expected = await _generate(oracle, prompt, OUT)
    await oracle.stop()

    engine = TpuEngine(sp_cfg, params=PARAMS, mesh=mesh)
    await engine.start()
    try:
        # Capacity claim: each device holds 1/2 the slots AND 1/2 the
        # kv heads — per-device KV bytes are 1/(sp*tp) of the total.
        k0 = engine.runner.kv_caches[0][0]
        shard_shapes = {s.data.shape for s in k0.addressable_shards}
        assert shard_shapes == {(40 * 4 // 2, 1, CFG.head_dim)}, shard_shapes
        got = await _generate(engine, prompt, OUT)
        assert got == expected, "tp x sp kv_sp serving diverged from oracle"
    finally:
        await engine.stop()


async def test_engine_kv_sp_pallas_path(monkeypatch):
    """kv_sp engine with the Pallas kernels active (interpret mode on
    CPU): per-shard kernel over the compacted stripe + logsumexp merge
    must reproduce the oracle's tokens exactly. block_size=8 so the
    per-shard (bs * local kvH) hits the f32 sublane multiple the compiled
    kernel envelope requires (ops/pallas/attention.py pallas_supported)."""
    mesh = build_mesh({"sp": 2, "tp": 2, "dp": 2})
    sp_cfg = EngineConfig(
        model=CFG, dtype="float32", block_size=8, num_blocks=16,
        max_num_seqs=2, max_model_len=48, kv_sp=True,
    )
    oracle_cfg = EngineConfig(
        model=CFG, dtype="float32", block_size=8, num_blocks=24,
        max_num_seqs=2, max_model_len=48,
    )
    prompt = [int(x) for x in
              np.random.default_rng(3).integers(1, CFG.vocab_size, 20)]
    OUT = 8

    oracle = TpuEngine(oracle_cfg, params=PARAMS)
    await oracle.start()
    expected = await _generate(oracle, prompt, OUT)
    await oracle.stop()

    monkeypatch.setenv("DYNAMO_TPU_PALLAS", "1")
    engine = TpuEngine(sp_cfg, params=PARAMS, mesh=mesh)
    await engine.start()
    try:
        assert engine.runner.attn.use_pallas, "Pallas path not engaged"
        got = await _generate(engine, prompt, OUT)
        assert got == expected, "kv_sp Pallas serving diverged from oracle"
    finally:
        await engine.stop()


async def test_engine_kv_sp_via_mesh_shape():
    """The CLI flow: no mesh object handed to the engine — the runner
    builds it from cfg.mesh_shape. The allocator must still stripe
    (review r05 finding: this path silently got an unstriped allocator
    while the runner ran the striped scan)."""
    sp_cfg = EngineConfig(
        model=CFG, dtype="float32", block_size=4, num_blocks=40,
        max_num_seqs=2, max_model_len=144, kv_sp=True,
        mesh_shape={"sp": 2, "dp": 4},
    )
    oracle_cfg = EngineConfig(
        model=CFG, dtype="float32", block_size=4, num_blocks=64,
        max_num_seqs=2, max_model_len=144,
    )
    prompt = [int(x) for x in
              np.random.default_rng(5).integers(1, CFG.vocab_size, 60)]
    OUT = 12

    oracle = TpuEngine(oracle_cfg, params=PARAMS)
    await oracle.start()
    expected = await _generate(oracle, prompt, OUT)
    await oracle.stop()

    engine = TpuEngine(sp_cfg, params=PARAMS)
    await engine.start()
    try:
        assert engine.allocator.num_shards == 2
        got = await _generate(engine, prompt, OUT)
        assert got == expected, "mesh_shape kv_sp serving diverged"
    finally:
        await engine.stop()


def test_striped_allocator_contract():
    """BlockAllocator(num_shards=n): logical block i lands in shard
    (i % n)'s physical range; exhausting one shard raises even while
    others have space; prefix-matched chains keep the striping."""
    from dynamo_tpu.engine.kv_cache import BlockAllocator

    alloc = BlockAllocator(16, 4, num_shards=4)  # 4 blocks/shard
    seq = alloc.allocate_many(8, first_logical=0)
    for i, b in enumerate(seq):
        assert alloc.shard_of(b) == i % 4, (i, b)
    # Shard 0 has 4 blocks minus trash block 0 = 3; two sequences used 2.
    alloc.allocate(0)  # last shard-0 block
    with pytest.raises(MemoryError, match="shard 0"):
        alloc.allocate(4)  # logical 4 -> shard 0 again: dry
    # Other shards still serve.
    assert alloc.shard_of(alloc.allocate(1)) == 1
    # Logical index is required under striping.
    with pytest.raises(TypeError):
        alloc.allocate()
