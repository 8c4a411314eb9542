"""Disaggregated prefill/decode tests: the full remote-prefill round trip
with REAL engines (tiny model on the virtual CPU mesh) — decode admits,
prefill computes, KV streams over the transfer plane into decode's blocks,
and the greedy continuation must be bit-identical to a local-only run
(the transferred-KV correctness oracle)."""

import asyncio

import jax
import pytest

from dynamo_tpu.disagg import (
    DecodeOperator,
    DisaggConfig,
    DisaggRouter,
    PrefillQueue,
    PrefillWorker,
)
from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.engine.engine import TpuEngine
from dynamo_tpu.llm.protocols.common import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.models import llama
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.runtime.distributed import DistributedRuntime
from dynamo_tpu.runtime.engine import Context

pytestmark = pytest.mark.anyio


def _ecfg():
    return EngineConfig(
        model=ModelConfig.tiny_test(),
        num_blocks=32,
        max_num_seqs=2,
        max_model_len=128,
        dtype="float32",
    )


async def _generate(engine, prompt, max_tokens=6):
    req = PreprocessedRequest(
        token_ids=prompt,
        sampling=SamplingOptions(temperature=0.0),
        stop=StopConditions(max_tokens=max_tokens, ignore_eos=True),
    )
    toks = []
    async for item in engine.generate(Context(req.to_wire())):
        toks += item["token_ids"]
    return toks


def test_disagg_decision():
    r = DisaggRouter.__new__(DisaggRouter)
    r.cfg = DisaggConfig(max_local_prefill_length=100, max_prefill_queue_size=4)
    assert r.prefill_remote(500, 0.0, 0)
    assert not r.prefill_remote(50, 0.0, 0)          # short prompt
    assert not r.prefill_remote(500, 0.9, 0)         # high prefix hit rate
    assert not r.prefill_remote(500, 0.0, 10)        # queue backed up


async def test_disagg_config_watch():
    drt = await DistributedRuntime.in_process()
    router = await DisaggRouter(drt, "ns").start()
    assert router.cfg.max_local_prefill_length == 512
    await router.publish_config(DisaggConfig(max_local_prefill_length=64))
    # A second router on the same store sees the live update.
    router2 = await DisaggRouter(drt, "ns").start()
    assert router2.cfg.max_local_prefill_length == 64
    await router.publish_config(DisaggConfig(max_local_prefill_length=32))
    await asyncio.sleep(0.05)
    assert router2.cfg.max_local_prefill_length == 32
    await drt.shutdown()


@pytest.mark.parametrize("transport", ["tcp", "native", "device"])
async def test_remote_prefill_roundtrip_matches_local(transport):
    params = llama.init_params(
        jax.random.PRNGKey(0), ModelConfig.tiny_test(), dtype="float32"
    )
    prompt = list(range(40))  # 3 blocks (2 full + partial)

    # Oracle: plain local engine.
    local = TpuEngine(_ecfg(), params=params)
    await local.start()
    expected = await _generate(local, prompt)
    await local.stop()

    # Disagg: decode + prefill engines wired through queue + transfer plane.
    drt = await DistributedRuntime.in_process()
    queue = PrefillQueue(drt, "test")
    dis = DisaggRouter.__new__(DisaggRouter)
    dis.cfg = DisaggConfig(max_local_prefill_length=16, max_prefill_queue_size=8)

    decode = TpuEngine(_ecfg(), params=params)
    await decode.start()
    prefill = TpuEngine(_ecfg(), params=params)
    await prefill.start()

    op = await DecodeOperator(decode, queue, dis, transport=transport).start()
    if transport == "device":
        # Same-process pair ⇒ HBM→HBM channel advertised; the wire path
        # (whatever resolved) is only the cross-process fallback.
        assert op.device_receiver is not None
    else:
        assert op.transport == transport
        assert op.device_receiver is None  # pinned wire path
    pw = PrefillWorker(prefill, queue).start()

    req = PreprocessedRequest(
        token_ids=prompt,
        sampling=SamplingOptions(temperature=0.0),
        stop=StopConditions(max_tokens=6, ignore_eos=True),
    )
    toks = []
    async for item in op.generate(Context(req.to_wire())):
        toks += item["token_ids"]

    assert toks == expected
    assert op.remote_count == 1 and op.local_count == 0
    assert pw.served == 1
    if transport == "device":
        assert op.device_receiver.blocks_received > 0  # device path used

    # Short prompt stays local.
    short = await _generate(op, list(range(8)))
    assert op.local_count == 1
    assert len(short) == 6

    await pw.stop()
    await op.stop()
    await decode.stop()
    await prefill.stop()
    await drt.shutdown()


async def test_staging_pressure_degrades_to_tcp_not_local():
    """r05 regression: a transfer the native staging arena can't fund
    must stay REMOTE over the staging-free tcp wire, not silently shed to
    local prefill (which turned the ISL-3000 disagg bench into
    aggregated serving). Tokens still match the local oracle."""
    params = llama.init_params(
        jax.random.PRNGKey(0), ModelConfig.tiny_test(), dtype="float32"
    )
    prompt = list(range(40))  # 3 blocks > the 2-slot arena below

    local = TpuEngine(_ecfg(), params=params)
    await local.start()
    expected = await _generate(local, prompt)
    await local.stop()

    drt = await DistributedRuntime.in_process()
    queue = PrefillQueue(drt, "test")
    dis = DisaggRouter.__new__(DisaggRouter)
    dis.cfg = DisaggConfig(max_local_prefill_length=16, max_prefill_queue_size=8)

    decode = TpuEngine(_ecfg(), params=params)
    await decode.start()
    prefill = TpuEngine(_ecfg(), params=params)
    await prefill.start()
    # Wire pinned to "auto"-resolved native with a 2-slot arena; no
    # same-process device shortcut, so the tcp fallback is what carries it.
    op = await DecodeOperator(
        decode, queue, dis, transport="auto", staging_slots=2
    ).start()
    await op.device_receiver.stop()  # force the wire path (and don't
    op.device_receiver = None        # leak the registry entry)
    assert op.transport == "native" and op.tcp_receiver is not None
    pw = PrefillWorker(prefill, queue).start()

    toks = await _generate(op, prompt)
    assert toks == expected
    assert op.remote_count == 1 and op.local_count == 0
    assert pw.served == 1

    await pw.stop()
    await op.stop()
    await decode.stop()
    await prefill.stop()
    await drt.shutdown()


async def test_tcp_receiver_rejects_unauthenticated_peer():
    """The transfer plane is raw memory writes — a peer without the shared
    secret (carried by the queue entry) must not land a single block."""
    from dynamo_tpu.disagg.transfer import KvReceiver, KvSender

    landed = []
    recv = await KvReceiver(
        on_block=lambda r, i, d: landed.append((r, i)),
        on_finish=lambda r, t: landed.append(("finish", r)),
    ).start()
    import numpy as np

    block = np.ones((2, 4), np.float32)
    bad = KvSender()
    with pytest.raises((ConnectionError, asyncio.IncompleteReadError, OSError)):
        await bad.send_blocks(recv.address, "r1", [block], 7, auth="00" * 16)
    await bad.close()
    assert landed == []

    good = KvSender()
    await good.send_blocks(recv.address, "r1", [block], 7, auth=recv.auth)
    await good.close()
    assert ("finish", "r1") in landed
    await recv.stop()


async def test_native_receiver_rejects_unauthenticated_peer():
    from dynamo_tpu.native import transfer as nt

    if not nt.available():
        pytest.skip("native agent unavailable")
    import numpy as np

    server = nt.TransferServer()
    arena = np.zeros(64, np.uint8)
    server.register(7, arena)

    bad = nt.TransferClient("127.0.0.1", server.port, b"\x00" * 16)
    # The server closes the connection on bad auth; the write may buffer
    # locally, but nothing must land and notify must never complete.
    try:
        bad.write(7, 0, np.full(8, 0xAB, np.uint8))
        bad.notify(1, b"x")
    except ConnectionError:
        pass
    bad.close()
    await asyncio.sleep(0.05)
    assert server.poll() is None
    assert not arena.any()

    good = nt.TransferClient("127.0.0.1", server.port, server.token)
    good.write(7, 0, np.full(8, 0xCD, np.uint8))
    good.notify(2, b"ok")
    for _ in range(100):
        ev = server.poll()
        if ev is not None:
            break
        await asyncio.sleep(0.01)
    assert ev == (2, b"ok")
    assert (arena[:8] == 0xCD).all()
    good.close()
    server.close()


async def test_queue_age_sla_signal():
    """Oldest-item age rides the queue (surviving redelivery) and flips
    the disagg decision to local when the pool is stalled — the per-item
    SLA signal depth alone can't give."""
    from dynamo_tpu.disagg.router import DisaggConfig, DisaggRouter
    from dynamo_tpu.runtime.transports.bus import InProcQueue

    q = InProcQueue()
    assert await q.oldest_age_s() == 0.0
    await q.enqueue(b"stuck")
    await asyncio.sleep(0.15)
    age = await q.oldest_age_s()
    assert age >= 0.15

    # A stuck consumer holding the only item must not hide the stall:
    # in-flight items count toward the age even at depth 0.
    item_id, _ = await q.dequeue_leased(lease_s=30.0)
    assert await q.depth() == 0
    assert await q.oldest_age_s() >= age
    # Redelivery preserves the ORIGINAL enqueue time (the work's wait, not
    # the last lease's).
    await q.nack(item_id)
    assert await q.oldest_age_s() >= age
    assert (await q.stats())[0] == 1

    router = DisaggRouter.__new__(DisaggRouter)
    router.cfg = DisaggConfig(
        max_local_prefill_length=10,
        max_prefill_queue_size=16,
        max_prefill_queue_age_s=0.5,
    )
    # Long prompt, empty-ish queue: remote while the queue is fresh...
    assert router.prefill_remote(1000, 0.0, queue_size=1, queue_age_s=0.1)
    # ...but a stalled queue (old item) keeps prefill local even at depth 1.
    assert not router.prefill_remote(1000, 0.0, queue_size=1, queue_age_s=0.9)


@pytest.mark.parametrize("tp_pair,transport", [
    ((2, 1), "tcp"),
    ((1, 2), "tcp"),
    # Same-process device channel advertised but tp differs: the sender
    # must fall back to the wire (device snapshots carry the sender's
    # sharding) — tokens still correct, zero device blocks.
    ((2, 1), "device"),
])
async def test_heterogeneous_tp_prefill_decode_roundtrip(tp_pair, transport):
    """xPyD with DIFFERENT tensor-parallel degrees per pool
    (reference: docs/architecture/disagg_serving.md:100-109): a
    tp-sharded prefill engine feeds a decode engine of another tp over
    the wire path, and greedy tokens must match the plain local engine.
    The wire carries blocks in the LOGICAL [L, 2, bs, H_total, D] layout,
    so the head-axis reshard is the gather on one side and the scatter
    slice on the other."""
    from dynamo_tpu.parallel.mesh import build_mesh

    prefill_tp, decode_tp = tp_pair
    params = llama.init_params(
        jax.random.PRNGKey(0), ModelConfig.tiny_test(), dtype="float32"
    )
    prompt = list(range(40))

    local = TpuEngine(_ecfg(), params=params)
    await local.start()
    expected = await _generate(local, prompt)
    await local.stop()

    drt = await DistributedRuntime.in_process()
    queue = PrefillQueue(drt, "tp-mix")
    dis = DisaggRouter.__new__(DisaggRouter)
    dis.cfg = DisaggConfig(max_local_prefill_length=16, max_prefill_queue_size=8)

    def mesh_for(tp):
        return build_mesh({"tp": tp}, devices=jax.devices()[:tp]) if tp > 1 else None

    decode = TpuEngine(_ecfg(), params=params, mesh=mesh_for(decode_tp))
    await decode.start()
    prefill = TpuEngine(_ecfg(), params=params, mesh=mesh_for(prefill_tp))
    await prefill.start()

    op = await DecodeOperator(decode, queue, dis, transport=transport).start()
    pw = PrefillWorker(prefill, queue).start()

    # The queue entry advertises the decode pool's tp.
    assert op._layout()["tp"] == decode_tp

    req = PreprocessedRequest(
        token_ids=prompt,
        sampling=SamplingOptions(temperature=0.0),
        stop=StopConditions(max_tokens=6, ignore_eos=True),
    )
    toks = []
    async for item in op.generate(Context(req.to_wire())):
        toks += item["token_ids"]

    assert toks == expected, (
        f"tp={prefill_tp} prefill -> tp={decode_tp} decode diverged"
    )
    assert op.remote_count == 1 and pw.served == 1
    if transport == "device":
        # The guard routed around the device channel.
        assert op.device_receiver is not None
        assert op.device_receiver.blocks_received == 0

    await pw.stop()
    await op.stop()
    await decode.stop()
    await prefill.stop()
    await drt.shutdown()
