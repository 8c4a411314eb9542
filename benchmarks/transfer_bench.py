"""KV-block transfer benchmark: device path (HBM→HBM) vs host-staged TCP.

The acceptance gate: the same-process device path must move
blocks ≥5× faster than gather→TCP→scatter. Run on the real chip:

    python benchmarks/transfer_bench.py

Prints one JSON line with blocks/s for both paths and the speedup.
"""

from __future__ import annotations

import asyncio
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))
import json
import time

import jax
import numpy as np

from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.engine.runner import ModelRunner
from dynamo_tpu.models.config import ModelConfig

N_BLOCKS = 48
N_ROUNDS = 3


def _cfg() -> EngineConfig:
    return EngineConfig(
        model=ModelConfig.llama32_1b(),
        num_blocks=max(64, N_BLOCKS + 2),
        max_num_seqs=4,
        max_model_len=512,
    )


def bench_device(src: ModelRunner, dst: ModelRunner) -> float:
    # warm the two programs
    dst.scatter_block(1, src.gather_block_device(1))
    jax.block_until_ready(dst.kv_caches[0][0])
    t0 = time.monotonic()
    for _ in range(N_ROUNDS):
        for i in range(1, N_BLOCKS + 1):
            dst.scatter_block(i, src.gather_block_device(i))
    jax.block_until_ready(dst.kv_caches[0][0])
    return N_ROUNDS * N_BLOCKS / (time.monotonic() - t0)


async def bench_tcp(src: ModelRunner, dst: ModelRunner) -> float:
    from dynamo_tpu.disagg.transfer import KvReceiver, KvSender

    done = asyncio.Event()

    def on_block(req: str, idx: int, data) -> None:
        dst.scatter_block(idx + 1, data)

    def on_finish(req: str, tok: int) -> None:
        done.set()

    receiver = await KvReceiver(on_block=on_block, on_finish=on_finish).start()
    sender = KvSender()
    # warm connections + programs off the clock
    warm = [np.asarray(src.gather_block(1))]
    await sender.send_blocks(receiver.address, "warm", warm, 0, auth=receiver.auth)
    await asyncio.wait_for(done.wait(), 30)

    t0 = time.monotonic()
    for r in range(N_ROUNDS):
        # The old path end to end: HBM→host gather, TCP, host→HBM scatter.
        blocks = [np.asarray(src.gather_block(i)) for i in range(1, N_BLOCKS + 1)]
        done.clear()
        await sender.send_blocks(
            receiver.address, f"r{r}", blocks, 0, auth=receiver.auth
        )
        await asyncio.wait_for(done.wait(), 60)
    jax.block_until_ready(dst.kv_caches[0][0])
    rate = N_ROUNDS * N_BLOCKS / (time.monotonic() - t0)
    await sender.close()
    await receiver.stop()
    return rate


def bench_host_per_block(src: ModelRunner, dst: ModelRunner) -> float:
    """The r03-era host roundtrip: one dispatch per block each way."""
    dst.scatter_block(1, src.gather_block(1))
    jax.block_until_ready(dst.kv_caches[0][0])
    t0 = time.monotonic()
    for _ in range(N_ROUNDS):
        for i in range(1, N_BLOCKS + 1):
            dst.scatter_block(i, src.gather_block(i))
    jax.block_until_ready(dst.kv_caches[0][0])
    return N_ROUNDS * N_BLOCKS / (time.monotonic() - t0)


def bench_host_batched(src: ModelRunner, dst: ModelRunner) -> float:
    """The batched host roundtrip (one program for all N blocks each way) —
    the KVBM offload/onboard primitive (ops/kv_copy.py gather_blocks/
    scatter_blocks)."""
    idxs = list(range(1, N_BLOCKS + 1))
    dst.scatter_many(idxs, src.gather_many(idxs))
    jax.block_until_ready(dst.kv_caches[0][0])
    t0 = time.monotonic()
    for _ in range(N_ROUNDS):
        dst.scatter_many(idxs, src.gather_many(idxs))
    jax.block_until_ready(dst.kv_caches[0][0])
    return N_ROUNDS * N_BLOCKS / (time.monotonic() - t0)


def bench_device_batched(src: ModelRunner, dst: ModelRunner) -> float:
    """Batched HBM→HBM: one gather program + one scatter program for all N
    blocks, snapshot never leaves the device."""
    idxs = list(range(1, N_BLOCKS + 1))
    from dynamo_tpu.ops.kv_copy import gather_blocks_device, scatter_blocks

    def move():
        snap = gather_blocks_device(src.kv_caches, idxs, src.cfg.block_size)
        dst.kv_caches = scatter_blocks(
            dst.kv_caches, idxs, dst.cfg.block_size, snap
        )

    move()
    jax.block_until_ready(dst.kv_caches[0][0])
    t0 = time.monotonic()
    for _ in range(N_ROUNDS):
        move()
    jax.block_until_ready(dst.kv_caches[0][0])
    return N_ROUNDS * N_BLOCKS / (time.monotonic() - t0)


def main() -> None:
    src = ModelRunner(_cfg())
    dst = ModelRunner(_cfg())
    m = _cfg().model
    block_bytes = (
        m.num_layers * 2 * _cfg().block_size * m.num_kv_heads
        * src.cache_head_dim * np.dtype(_cfg().dtype).itemsize
    )
    dev = bench_device(src, dst)
    dev_b = bench_device_batched(src, dst)
    host_pb = bench_host_per_block(src, dst)
    host_b = bench_host_batched(src, dst)
    tcp = asyncio.run(bench_tcp(src, dst))
    print(
        json.dumps(
            {
                "metric": "kv_block_transfer",
                "block_bytes": block_bytes,
                "device_blocks_per_s": round(dev, 1),
                "device_batched_blocks_per_s": round(dev_b, 1),
                "host_roundtrip_blocks_per_s": round(host_pb, 1),
                "host_roundtrip_batched_blocks_per_s": round(host_b, 1),
                "tcp_blocks_per_s": round(tcp, 1),
                "device_gbps": round(dev * block_bytes / 1e9, 2),
                "device_batched_gbps": round(dev_b * block_bytes / 1e9, 2),
                "host_batched_gbps": round(host_b * block_bytes / 1e9, 2),
                "tcp_gbps": round(tcp * block_bytes / 1e9, 2),
                "speedup": round(dev / tcp, 1),
                "batch_speedup_device": round(dev_b / dev, 1),
                "batch_speedup_host": round(host_b / host_pb, 1),
            }
        )
    )


if __name__ == "__main__":
    main()
