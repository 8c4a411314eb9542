"""Quantized KV blocks (docs/architecture/kv_quant.md): int8
dequant-in-kernel on the ragged path vs the XLA oracle (exact-contract
parity on CPU interpret mode), the shared per-block write law, the
KVBM per-tier precision policy (packed rows through G2/G3 with scale
sidecars preserved), the r04-calibrated mocker HBM term, the
precision-aware NetKV transfer pricing, and the greedy-stream quality
gate on the real tiny model."""

import asyncio
import dataclasses
import os
import tempfile

import numpy as np
import pytest

import jax.numpy as jnp

from dynamo_tpu.ops.attention import (
    AttnDispatch,
    paged_decode_attention,
    ragged_paged_attention,
)
from dynamo_tpu.ops.pallas.ragged_attention import (
    ragged_paged_attention_pallas,
)
from dynamo_tpu.ops.quant import (
    dequantize_kv_block_host,
    quantize_kv_block_host,
    quantize_kv_write,
)

BS = 16  # block size


# ---------------------------------------------------------------------------
# Kernel vs oracle: int8 caches + per-block scales, exact-contract parity
# ---------------------------------------------------------------------------


def _quant_caches(rng, num_blocks, kvH, D):
    shape = (num_blocks * BS, kvH, D)
    k = jnp.asarray(rng.integers(-127, 128, shape), jnp.int8)
    v = jnp.asarray(rng.integers(-127, 128, shape), jnp.int8)
    ks = jnp.asarray(rng.uniform(0.002, 0.02, (num_blocks, kvH)), jnp.float32)
    vs = jnp.asarray(rng.uniform(0.002, 0.02, (num_blocks, kvH)), jnp.float32)
    return k, v, ks, vs


def _tables(rng, S, max_blocks, num_blocks):
    ids = rng.permutation(np.arange(1, num_blocks))[: S * max_blocks]
    return jnp.asarray(ids.reshape(S, max_blocks), jnp.int32)


def _flat_batch(rng, spans, T, H, D):
    S = len(spans)
    q_start = np.zeros(S, np.int32)
    q_len = np.zeros(S, np.int32)
    row_start = np.zeros(S, np.int32)
    token_seq = np.zeros(T, np.int32)
    token_pos = np.full(T, -1, np.int32)
    cursor = 0
    for s, (qs, ql) in enumerate(spans):
        q_start[s], q_len[s], row_start[s] = qs, ql, cursor
        token_seq[cursor : cursor + ql] = s
        token_pos[cursor : cursor + ql] = np.arange(qs, qs + ql)
        cursor += ql
    q = jnp.asarray(rng.standard_normal((T, H, D)), jnp.float32)
    return (
        q,
        jnp.asarray(q_start),
        jnp.asarray(q_len),
        jnp.asarray(q_start + q_len),
        jnp.asarray(row_start),
        jnp.asarray(token_seq),
        jnp.asarray(token_pos),
    )


def _both_quant(rng, spans, T, H, kvH, D, window=0, q_tile=8, seed_tables=4):
    k, v, ks, vs = _quant_caches(rng, 64, kvH, D)
    tables = _tables(rng, len(spans), seed_tables, 64)
    q, qs, ql, kv_len, rs, tseq, tpos = _flat_batch(rng, spans, T, H, D)
    want = ragged_paged_attention(
        q, k, v, tables, tseq, tpos, BS, window, k_scales=ks, v_scales=vs
    )
    got = ragged_paged_attention_pallas(
        q, k, v, tables, qs, ql, kv_len, rs, BS, q_tile=q_tile,
        window=window, k_scales=ks, v_scales=vs,
    )
    return np.asarray(want), np.asarray(got)


@pytest.mark.parametrize("H,kvH,D", [(8, 8, 128), (8, 2, 128), (4, 1, 128)])
def test_int8_mixed_batch_matches_oracle(H, kvH, D):
    """Mixed decode spans + prefill quanta + prefix hit + idle row over
    int8 caches: kernel == oracle, padding rows stay zero."""
    rng = np.random.default_rng(0)
    spans = [(36, 1), (0, 1), (0, 20), (16, 13), (0, 0)]
    want, got = _both_quant(rng, spans, 40, H, kvH, D)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    assert not got[35:].any()


def test_int8_decode_only_matches_decode_oracle():
    """Decode-only int8 unified batch == quantized batched decode
    attention (dequant arithmetic identical along both routes)."""
    rng = np.random.default_rng(1)
    H, kvH, D = 8, 2, 128
    k, v, ks, vs = _quant_caches(rng, 64, kvH, D)
    tables = _tables(rng, 4, 4, 64)
    ctx = np.asarray([64, 37, 1, 16], np.int32)
    spans = [(c - 1, 1) for c in ctx]
    q, qs, ql, kv_len, rs, tseq, tpos = _flat_batch(rng, spans, 16, H, D)
    got = ragged_paged_attention_pallas(
        q, k, v, tables, qs, ql, kv_len, rs, BS, k_scales=ks, v_scales=vs
    )
    oracle = paged_decode_attention(
        q[:4], k, v, tables, jnp.asarray(ctx), BS, k_scales=ks, v_scales=vs
    )
    np.testing.assert_allclose(
        np.asarray(got)[:4], np.asarray(oracle), rtol=2e-5, atol=2e-5
    )


@pytest.mark.parametrize("q_tile", [8, 64])
def test_int8_prefill_only_with_prefix_hit(q_tile):
    rng = np.random.default_rng(2)
    spans = [(0, 24), (16, 13)]  # span 1 extends a 16-token prefix
    want, got = _both_quant(rng, spans, 40, 8, 2, 128, q_tile=q_tile)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_int8_sliding_window_mixed_batch():
    rng = np.random.default_rng(3)
    spans = [(60, 1), (0, 30), (30, 10)]
    want, got = _both_quant(rng, spans, 48, 8, 2, 128, window=24)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_int8_dispatch_ragged_threads_scales():
    """AttnDispatch.ragged (the runner's route) hits the same numbers on
    both implementations when scales are threaded through it."""
    rng = np.random.default_rng(4)
    H, kvH, D = 8, 2, 128
    k, v, ks, vs = _quant_caches(rng, 64, kvH, D)
    tables = _tables(rng, 3, 4, 64)
    spans = [(10, 1), (0, 12), (0, 1)]
    q, qs, ql, kv_len, rs, tseq, tpos = _flat_batch(rng, spans, 16, H, D)
    outs = []
    for use_pallas in (False, True):
        outs.append(
            np.asarray(
                AttnDispatch(use_pallas=use_pallas).ragged(
                    q, k, v, tables, tseq, tpos, qs, ql, kv_len, rs, BS,
                    k_scales=ks, v_scales=vs,
                )
            )
        )
    np.testing.assert_allclose(outs[1], outs[0], rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# The shared write law (ops/quant.py quantize_kv_write)
# ---------------------------------------------------------------------------


def test_write_law_fresh_block_resets_stale_scale():
    """A block whose first slot is written starts a NEW occupancy: the
    previous tenant's (large) scale must not survive and wreck the new
    values' resolution."""
    kvH, D = 2, 8
    cache = jnp.zeros((4 * BS, kvH, D), jnp.int8)
    scales = jnp.full((4, kvH), 100.0, jnp.float32)  # stale, huge
    vals = jnp.asarray(
        np.random.default_rng(0).standard_normal((BS, kvH, D)), jnp.float32
    )
    slots = jnp.asarray(np.arange(BS) + 2 * BS, jnp.int32)  # block 2
    cache, scales = quantize_kv_write(cache, scales, slots, vals, BS)
    s2 = np.asarray(scales)[2]
    assert (s2 < 1.0).all()  # reset to the new values' amax/127
    deq = np.asarray(cache[2 * BS : 3 * BS], np.float32) * s2[None, :, None]
    rel = np.abs(deq - np.asarray(vals)).max() / np.abs(vals).max()
    assert rel < 0.01
    # untouched blocks keep their scales exactly
    assert (np.asarray(scales)[[0, 1, 3]] == 100.0).all()


def test_write_law_scale_growth_requants_existing_entries():
    """Appending a larger-magnitude token mid-block grows the block
    scale and requantizes the existing entries by round(q·old/new) —
    dequantized values stay within the coarser grid's error."""
    kvH, D = 1, 4
    rng = np.random.default_rng(1)
    cache = jnp.zeros((2 * BS, kvH, D), jnp.int8)
    scales = jnp.zeros((2, kvH), jnp.float32)
    v_small = jnp.asarray(rng.standard_normal((1, kvH, D)), jnp.float32)
    cache, scales = quantize_kv_write(
        cache, scales, jnp.asarray([BS], jnp.int32), v_small, BS
    )
    s_before = float(np.asarray(scales)[1, 0])
    v_big = jnp.asarray(rng.standard_normal((1, kvH, D)) * 40, jnp.float32)
    cache, scales = quantize_kv_write(
        cache, scales, jnp.asarray([BS + 1], jnp.int32), v_big, BS
    )
    s_after = float(np.asarray(scales)[1, 0])
    assert s_after > s_before
    deq0 = np.asarray(cache[BS], np.float32) * s_after
    # the requantized first token is still within the NEW grid's step
    assert np.abs(deq0 - np.asarray(v_small)[0]).max() <= s_after * 1.01
    deq1 = np.asarray(cache[BS + 1], np.float32) * s_after
    rel = np.abs(deq1 - np.asarray(v_big)[0]).max() / np.abs(v_big).max()
    assert rel < 0.01


def test_int8_spec_verify_spans_match_oracle():
    """kv_quant × spec: draft-verify spans (q_len = k+1 at
    q_start = ctx-1) over int8 caches — kernel == oracle in a mixed
    draft-verify + decode + prefill batch, GQA included."""
    rng = np.random.default_rng(7)
    H, kvH, D = 8, 2, 128
    # verify span (3 drafts), floor verify span (2 drafts), decode,
    # prefill quantum, idle row.
    spans = [(35, 4), (0, 3), (21, 1), (0, 10), (0, 0)]
    want, got = _both_quant(rng, spans, 32, H, kvH, D)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    # Windowed variant of the same batch.
    want_w, got_w = _both_quant(rng, spans, 32, H, kvH, D, window=16)
    np.testing.assert_allclose(got_w, want_w, rtol=2e-5, atol=2e-5)


def test_write_law_spec_span_writes_k_plus_1_rows():
    """A verify span writes its fed token AND every draft's K/V through
    the per-block write law in one shot — including a FRESH block whose
    stale scale must reset when the span's writes open it mid-span."""
    kvH, D = 2, 8
    rng = np.random.default_rng(3)
    cache = jnp.zeros((4 * BS, kvH, D), jnp.int8)
    scales = jnp.full((4, kvH), 50.0, jnp.float32)
    scales = scales.at[1].set(0.0)  # block 1: live, empty
    # Span of 4 rows (fed + 3 drafts) straddling blocks 1→2: the last
    # two writes land in block 2's first slots (allocator-reused block
    # with a stale huge scale).
    k_vals = jnp.asarray(rng.standard_normal((4, kvH, D)), jnp.float32)
    slots = jnp.asarray(
        [2 * BS - 2, 2 * BS - 1, 2 * BS, 2 * BS + 1], jnp.int32
    )
    cache, scales = quantize_kv_write(cache, scales, slots, k_vals, BS)
    s = np.asarray(scales)
    assert (s[2] < 1.0).all(), "fresh-block scale must reset mid-span"
    # Every one of the span's k+1 rows dequantizes back to its value.
    for j, slot in enumerate([2 * BS - 2, 2 * BS - 1, 2 * BS, 2 * BS + 1]):
        blk = slot // BS
        deq = np.asarray(cache[slot], np.float32) * s[blk][:, None]
        rel = np.abs(deq - np.asarray(k_vals)[j]).max() / max(
            np.abs(np.asarray(k_vals)[j]).max(), 1e-9
        )
        assert rel < 0.02, f"span row {j} lost precision"
    assert (s[[0, 3]] == 50.0).all()  # untouched blocks keep scales


def test_int8_spec_engine_stream_matches_plain():
    """kv_quant × spec end-to-end (REAL engine, int8 G1): greedy streams
    with speculative_k on the quantized unified path are byte-identical
    to the same quantized engine without speculation — verify spans
    write k+1 rows through the write law without corrupting KV."""
    import jax

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

    mcfg = ModelConfig.tiny_test()
    params = llama.init_params(jax.random.PRNGKey(0), mcfg, jnp.float32)

    async def run(spec_k: int) -> list[int]:
        eng = TpuEngine(
            EngineConfig(
                model=mcfg, dtype="float32", block_size=4, num_blocks=128,
                max_num_seqs=2, max_model_len=128, kv_quant="int8",
                unified_token_budget=64,
                sampling_extras=False, speculative_k=spec_k,
            ),
            params=params,
        )
        await eng.start()
        try:
            req = PreprocessedRequest(
                token_ids=[1, 5, 9, 2, 7, 9, 2, 7],
                sampling=SamplingOptions(temperature=0.0),
                stop=StopConditions(max_tokens=24, ignore_eos=True),
            )
            toks = []
            async for out in eng.generate(Context(req.to_wire())):
                toks.extend(out["token_ids"])
            return toks
        finally:
            await eng.stop()

    plain = asyncio.run(run(0))
    spec = asyncio.run(run(3))
    assert spec == plain and len(plain) == 24


def test_host_block_quant_roundtrip():
    rng = np.random.default_rng(2)
    vals = rng.standard_normal((2, 2, 4, 3, 8)).astype(np.float32)
    q, s = quantize_kv_block_host(vals, 3, 8)
    assert q.dtype == np.int8 and s.shape == (2, 2, 3)
    deq = dequantize_kv_block_host(q, s)
    rel = np.abs(deq - vals).max() / np.abs(vals).max()
    assert rel < 0.01


# ---------------------------------------------------------------------------
# KVBM per-tier precision policy
# ---------------------------------------------------------------------------


def _quant_layout(**kw):
    from dynamo_tpu.block_manager.config import KvLayoutConfig

    base = dict(
        num_layers=2, page_size=4, num_kv_heads=2, head_dim=8,
        dtype="float32", quant="int8",
    )
    base.update(kw)
    return KvLayoutConfig(**base)


def test_layout_explicit_byte_accounting():
    lay = _quant_layout()
    assert lay.bytes_per_element == 1
    assert lay.scale_elems == 2 * 2 * 2
    assert lay.scale_bytes == 32
    assert lay.block_bytes == lay.block_elems + 32
    assert lay.unquantized_block_bytes == lay.block_elems * 4
    plain = _quant_layout(quant=None)
    assert plain.scale_bytes == 0
    assert plain.block_bytes == plain.block_elems * 4


def test_kvbm_quantizes_g2_and_chains_identical_bytes_to_g3(tmp_path):
    """Quantize-on-offload into G2, byte-identical chain into G3, and a
    promotion back preserves the scale sidecar exactly."""
    from dynamo_tpu.block_manager import quant as bq
    from dynamo_tpu.block_manager.config import KvbmConfig
    from dynamo_tpu.block_manager.manager import KvBlockManager

    layout = _quant_layout()

    async def main():
        mgr = await KvBlockManager(
            KvbmConfig(
                layout=layout, host_blocks=4, disk_blocks=8,
                disk_path=str(tmp_path / "g3.bin"),
            )
        ).start()
        rng = np.random.default_rng(0)
        data = rng.standard_normal((2, 2, 4, 2, 8)).astype(np.float32)
        mgr.offer(101, None, (1, 2, 3), data)
        await mgr.drain_offers()
        (h, _parent, _toks, row) = mgr.match_host([101])[0]
        assert row.nbytes == layout.block_bytes
        deq = bq.dequantize_block(row, layout).reshape(data.shape)
        assert np.abs(deq - data).max() / np.abs(data).max() < 0.02
        # Fill the 4-block host tier so 101 LRU-evicts, then promote it
        # back from disk: bytes (incl. the sidecar) must be identical.
        for i in range(2, 8):
            mgr.offer(
                100 + i, None, (i,),
                rng.standard_normal(data.shape).astype(np.float32),
            )
            await mgr.drain_offers()
        await mgr._g2_to_g3.drain()
        assert await mgr.onboard_from_disk([101]) == 1
        row2 = mgr.match_host([101])[0][3]
        assert np.array_equal(np.asarray(row), np.asarray(row2))
        _q1, s1 = bq.unpack_block(row, layout)
        _q2, s2 = bq.unpack_block(row2, layout)
        assert np.array_equal(s1, s2)
        stats = mgr.stats()
        assert stats["quant_host_density"] == 1.0
        assert stats["quant_disk_density"] == 1.0
        assert stats["quant_bytes_saved_total"] > 0
        await mgr.stop()

    asyncio.run(main())


def test_kvbm_int8_g1_passthrough_preserves_device_scales(tmp_path):
    """An int8 G1's offer (data + scales) packs BIT-EXACTLY — no
    re-quantization drift between the device cache and the host tier."""
    from dynamo_tpu.block_manager import quant as bq
    from dynamo_tpu.block_manager.config import KvbmConfig
    from dynamo_tpu.block_manager.manager import KvBlockManager

    layout = _quant_layout()

    async def main():
        mgr = await KvBlockManager(
            KvbmConfig(layout=layout, host_blocks=4)
        ).start()
        rng = np.random.default_rng(1)
        q = rng.integers(-127, 128, (2, 2, 4, 2, 8)).astype(np.int8)
        scales = rng.uniform(0.01, 0.1, (2, 2, 2)).astype(np.float32)
        mgr.offer(77, None, (9,), q, scales=scales)
        await mgr.drain_offers()
        row = mgr.match_host([77])[0][3]
        q2, s2 = bq.unpack_block(row, layout)
        assert np.array_equal(q2, q)
        assert np.array_equal(s2, scales)
        await mgr.stop()

    asyncio.run(main())


# ---------------------------------------------------------------------------
# Runner packed-row wire form (the disagg frame payload)
# ---------------------------------------------------------------------------


def _unified_runner(kv_quant):
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.runner import ModelRunner
    from dynamo_tpu.models.config import ModelConfig

    cfg = EngineConfig(
        model=ModelConfig.tiny_test(), dtype="float32", num_blocks=32,
        max_num_seqs=2, max_model_len=64, prefill_batch=2,
        unified_token_budget=32, unified_prefill_quantum=16,
        sampling_extras=False, kv_quant=kv_quant,
    )
    cfg.validate()
    return ModelRunner(cfg, rng_seed=0)


def test_export_import_block_rows_roundtrip_between_runners():
    """export_block_rows (prefill side) -> scatter_block per packed row
    (decode side, the wire-frame path): caches AND scales land equal."""
    r1 = _unified_runner("int8")
    sampling = (0.0, 0, 1.0)
    table = [3, 4, 5]
    toks = list(np.random.default_rng(0).integers(1, 300, 40))
    r1.unified_step([(toks[:32], table, 0, sampling)])
    rows = r1.export_block_rows([3, 4])
    assert all(
        r.nbytes == r1._quant_layout().block_bytes for r in rows
    )
    r2 = _unified_runner("int8")
    for idx, row in zip([3, 4], rows):
        r2.scatter_block(idx, row)
    for li, ((k1, _v1), (k2, _v2)) in enumerate(
        zip(r1.kv_caches, r2.kv_caches)
    ):
        np.testing.assert_array_equal(
            np.asarray(k1[3 * 16 : 5 * 16]), np.asarray(k2[3 * 16 : 5 * 16])
        )
    np.testing.assert_array_equal(
        np.asarray(r1.kv_scales[:, :, 3:5]),
        np.asarray(r2.kv_scales[:, :, 3:5]),
    )


def test_import_host_rows_dequantizes_for_bf16_g1():
    """A quantized host tier feeding an UNQUANTIZED G1: import_host_rows
    dequantizes on host and returns no scale rows."""
    from dynamo_tpu.block_manager import quant as bq

    r1 = _unified_runner("int8")
    sampling = (0.0, 0, 1.0)
    toks = list(np.random.default_rng(1).integers(1, 300, 16))
    r1.unified_step([(toks, [6, 7], 0, sampling)])
    layout = r1._quant_layout()
    rows = r1.export_block_rows([6])
    r_plain = _unified_runner(None)
    prepared, sc = r_plain.import_host_rows(rows, layout)
    assert sc is None
    q, s = bq.unpack_block(rows[0], layout)
    want = bq.dequantize_kv_block_host(q, s)
    np.testing.assert_allclose(
        np.asarray(prepared[0], np.float32), want, rtol=1e-6, atol=1e-6
    )


def test_block_batch_carries_scales_through_slicing():
    from dynamo_tpu.disagg.device_transfer import BlockBatch

    data = np.zeros((4, 2, 2, 4, 2, 8), np.int8)
    scales = np.arange(4 * 2 * 2 * 2, dtype=np.float32).reshape(4, 2, 2, 2)
    b = BlockBatch(data, scales=scales)
    assert b.shape[0] == 4 and len(b) == 4
    tail = b[1:]
    assert isinstance(tail, BlockBatch)
    np.testing.assert_array_equal(tail.scales, scales[1:])


def test_int8_engine_cross_restore_via_quantized_host_tier():
    """The whole per-tier loop on REAL engines: an int8-G1 engine A
    prefills, its (int8, scales) blocks pack bit-exactly into the
    quantized host tier; a FRESH int8 engine B onboards them
    (passthrough: data + scale scatter), reports the prefix hit, and
    produces the identical greedy continuation."""
    import jax

    from dynamo_tpu.block_manager.config import KvbmConfig, KvLayoutConfig
    from dynamo_tpu.block_manager.manager import KvBlockManager
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

    mcfg = ModelConfig.tiny_test()
    ecfg = EngineConfig(
        model=mcfg, num_blocks=32, max_num_seqs=2, max_model_len=128,
        dtype="float32", unified_token_budget=64,
        unified_prefill_quantum=16, sampling_extras=False,
        kv_quant="int8",
    )
    layout = KvLayoutConfig(
        num_layers=mcfg.num_layers, page_size=ecfg.block_size,
        num_kv_heads=mcfg.num_kv_heads, head_dim=mcfg.head_dim,
        dtype="float32", quant="int8",
    )
    params = llama.init_params(jax.random.PRNGKey(0), mcfg, dtype="float32")

    async def gen(engine, prompt):
        req = PreprocessedRequest(
            token_ids=prompt,
            sampling=SamplingOptions(temperature=0.0),
            stop=StopConditions(max_tokens=6, ignore_eos=True),
        )
        toks = []
        async for item in engine.generate(Context(req.to_wire())):
            toks += item["token_ids"]
        return toks

    async def main():
        kvbm = await KvBlockManager(
            KvbmConfig(layout=layout, host_blocks=16)
        ).start()
        eng_a = TpuEngine(ecfg, params=params, block_manager=kvbm)
        await eng_a.start()
        prompt = list(range(40))  # 2 full blocks + tail
        cold = await gen(eng_a, prompt)
        await kvbm.drain_offers()
        assert kvbm.stats()["host_registered"] == 2
        assert kvbm.stats()["quant_host_density"] == 1.0
        row = kvbm.match_host(
            [kvbm.host_pool.registered_hashes()[0]]
        )[0][3]
        assert row.nbytes == layout.block_bytes  # packed, not raw
        await eng_a.stop()

        eng_b = TpuEngine(ecfg, params=params, block_manager=kvbm)
        await eng_b.start()
        warm = await gen(eng_b, prompt)
        assert warm == cold
        assert eng_b.prefix_hit_rate > 0.0
        assert eng_b.readiness()["kv_reused_host_blocks_total"] > 0
        await eng_b.stop()
        await kvbm.stop()

    asyncio.run(main())


# ---------------------------------------------------------------------------
# Quality gate: greedy streams on the REAL tiny model, int8 vs bf16
# ---------------------------------------------------------------------------


def test_greedy_stream_quality_gate():
    """Greedy token streams on the REAL tiny model: int8 KV must match
    the full-precision stream at >= the threshold rate (tier-1-sized:
    2 prompts, short OSL; measured 1.0 on this model)."""
    _greedy_quality(n_prompts=2, osl=10, threshold=0.7)


def _greedy_quality(n_prompts, osl, threshold):
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.engine import TpuEngine
    from dynamo_tpu.llm.protocols.common import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )
    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.runtime.engine import Context

    async def run(kv_quant):
        cfg = EngineConfig(
            model=ModelConfig.tiny_test(), dtype="float32", num_blocks=64,
            max_num_seqs=4, max_model_len=128, prefill_batch=2,
            unified_token_budget=64,
            unified_prefill_quantum=16, sampling_extras=False,
            kv_quant=kv_quant,
        )
        eng = TpuEngine(cfg)
        await eng.start()

        async def one(seed):
            rng = np.random.default_rng(seed)
            req = PreprocessedRequest(
                token_ids=rng.integers(0, 384, 24).tolist(),
                sampling=SamplingOptions(temperature=0.0),
                stop=StopConditions(max_tokens=osl, ignore_eos=True),
            )
            toks = []
            async for out in eng.generate(Context(req.to_wire())):
                toks += out["token_ids"]
            return toks

        streams = await asyncio.gather(*[one(s) for s in range(n_prompts)])
        ratio = eng.readiness()["kvbm_kv_quant_ratio"]
        await eng.stop()
        return streams, ratio

    base, ratio_b = asyncio.run(run(None))
    quant, ratio_q = asyncio.run(run("int8"))
    assert ratio_b == 1.0
    # int8 + f32 sidecar vs the float32 compute dtype: ~1/4 the bytes.
    assert 0.2 < ratio_q < 0.3
    match = sum(
        x == y for s1, s2 in zip(base, quant) for x, y in zip(s1, s2)
    )
    total = sum(len(s) for s in base)
    assert total == n_prompts * osl
    rate = match / total
    assert rate >= threshold, (
        f"greedy token-match rate {rate:.2f} below {threshold} "
        f"({match}/{total}) — int8 KV degraded the stream too far"
    )


# ---------------------------------------------------------------------------
# Config validation, calibration, mocker pricing, selector
# ---------------------------------------------------------------------------


def test_kv_quant_config_validation():
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.models.config import ModelConfig

    EngineConfig(model=ModelConfig.tiny_test(), kv_quant="int8").validate()
    # Unknown quant modes reject.
    cfg = EngineConfig(model=ModelConfig.tiny_test(), kv_quant="fp4")
    with pytest.raises(ValueError, match="kv_quant"):
        cfg.validate()


def test_calibration_hbm_constant_rederives_from_artifact():
    """DECODE_HBM_GBPS must equal the recorded r04 measurement —
    the constant and the artifact can't drift apart (same contract as
    the PR 10 decode constants)."""
    from dynamo_tpu.planner import calibration as cal

    rec = cal.recorded_r04()
    assert cal.DECODE_HBM_GBPS == rec["effective_hbm_gbps"] == 282.8


def test_kv_quant_bytes_ratio_math():
    from dynamo_tpu.planner import calibration as cal

    # 1B layout: data 32768 B/token·16 tokens; sidecar 16·2·8·4 B/block.
    data = 16 * 2 * 16 * 8 * 64          # per-block int8 bytes
    scales = 16 * 2 * 8 * 4
    want = (data + scales) / (data * 2)
    assert abs(cal.kv_quant_bytes_ratio() - want) < 1e-9
    assert 0.5 < cal.kv_quant_bytes_ratio() < 0.51
    assert cal.kv_bytes_per_token(None) == cal.KV_BYTES_PER_TOKEN
    assert (
        cal.kv_bytes_per_token("int8")
        == cal.KV_BYTES_PER_TOKEN * cal.kv_quant_bytes_ratio()
    )
    # Precision-aware handoff: int8 moves about half the bytes.
    full = cal.handoff_seconds(2048) - cal.HANDOFF_FIXED_US / 1e6
    packed = (
        cal.handoff_seconds(2048, kv_quant="int8")
        - cal.HANDOFF_FIXED_US / 1e6
    )
    assert abs(packed / full - cal.kv_quant_bytes_ratio()) < 1e-9


def test_mocker_hbm_term_prices_context_bytes():
    """The decode HBM term is linear in context bytes and scales with
    the precision ratio; 0 bandwidth keeps legacy pricing."""
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.mocker.engine import MockerConfig, _SimRunner
    from dynamo_tpu.models.config import ModelConfig

    cfg = EngineConfig(model=ModelConfig.tiny_test())
    sim = _SimRunner(
        cfg,
        MockerConfig(
            decode_hbm_gbps=100.0, kv_bytes_per_token=1e6,
            kv_bytes_ratio=1.0,
        ),
    )
    us = sim._kv_read_us(200)
    assert abs(us - 200 * 1e6 / (100.0 * 1e9) * 1e6) < 1e-6
    sim.sim = MockerConfig(
        decode_hbm_gbps=100.0, kv_bytes_per_token=1e6, kv_bytes_ratio=0.5
    )
    assert abs(sim._kv_read_us(200) - us / 2) < 1e-6
    sim.sim = MockerConfig()  # term off by default
    assert sim._kv_read_us(200) == 0.0


def test_selector_prices_transfer_at_advertised_precision():
    """Two identical workers, one advertising int8 KV blocks: its
    transfer estimate halves, so it wins the tie and the audit shows
    the halved transfer_ms — quantized fleets aren't overcharged 2x."""
    from dynamo_tpu.llm.kv_router.metrics_aggregator import (
        ProcessedEndpoints,
    )
    from dynamo_tpu.llm.kv_router.protocols import ForwardPassMetrics
    from dynamo_tpu.llm.kv_router.scheduler import (
        DefaultWorkerSelector,
        KvRouterConfig,
    )

    def worker(ratio):
        return ForwardPassMetrics(
            kv_total_blocks=128, kv_active_blocks=0,
            num_requests_waiting=0, kvbm_link_g2g1_bps=1e9,
            kvbm_kv_quant_ratio=ratio,
        )

    eps = ProcessedEndpoints(
        metrics={1: worker(1.0), 2: worker(0.502)}, stamp=1.0
    )
    sel = DefaultWorkerSelector(
        KvRouterConfig(network_aware=True), seed=7
    )
    d = sel.select(eps, overlaps={}, isl=512)
    assert d.worker_id == 2
    by_worker = {c["worker"]: c for c in d.candidates}
    assert by_worker[2]["transfer_ms"] == pytest.approx(
        by_worker[1]["transfer_ms"] * 0.502, rel=1e-3
    )
    # and the int8 worker pays the SMALLER normalized penalty
    assert by_worker[2]["transfer_term"] < by_worker[1]["transfer_term"]


def test_quant_gauges_on_wire_and_exporter_surfaces():
    """The kvbm_quant_* gauges survive the ForwardPassMetrics wire
    roundtrip and are registered on the standalone exporter (DT011's
    dynamic complement)."""
    from dynamo_tpu.llm.kv_router.protocols import ForwardPassMetrics
    from dynamo_tpu.llm.metrics_exporter import _GAUGES

    names = {n for n, _ in _GAUGES}
    for g in (
        "kvbm_kv_quant_ratio",
        "kvbm_quant_host_density",
        "kvbm_quant_disk_density",
        "kvbm_quant_bytes_saved_total",
    ):
        assert g in names
        assert hasattr(ForwardPassMetrics(), g)
    m = ForwardPassMetrics.from_wire(
        {"kvbm_kv_quant_ratio": 0.5, "kvbm_quant_bytes_saved_total": 42}
    )
    assert m.kvbm_kv_quant_ratio == 0.5
    assert m.kvbm_quant_bytes_saved_total == 42


def test_disagg_layout_check_rejects_mixed_precision_pair():
    """A quantized decode pool's advertised layout must be refused by a
    bf16 prefill worker (and vice versa): packed rows are not
    repackable into a plain cache."""
    from dynamo_tpu.disagg.worker import PrefillWorker

    class _Cfg:
        kv_quant = None
        block_size = 16

        class model:
            num_layers = 2
            num_cache_heads = 2

    class _Runner:
        cache_head_dim = 128

    class _Eng:
        cfg = _Cfg()
        runner = _Runner()

    op = PrefillWorker.__new__(PrefillWorker)
    op.engine = _Eng()
    base = {
        "num_layers": 2, "num_kv_heads": 2, "block_size": 16,
        "dtype": _Eng.cfg, "head_dim": 128,
    }
    # dtype compares against engine.cfg.dtype — give both sides a str
    _Eng.cfg.dtype = "float32"
    base["dtype"] = "float32"
    assert op._check_layout({"layout": dict(base)})
    assert not op._check_layout(
        {"layout": dict(base, kv_quant="int8")}
    )
    _Eng.cfg.kv_quant = "int8"
    assert op._check_layout({"layout": dict(base, kv_quant="int8")})
    assert not op._check_layout({"layout": dict(base, kv_quant=None)})
    # quantized pairs need head_dim EXACT (no lane repack on packed rows)
    assert not op._check_layout(
        {"layout": dict(base, kv_quant="int8", head_dim=64)}
    )
