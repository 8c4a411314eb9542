"""Host-DRAM KV offload A/B: follow-up-turn TTFT with and without the G2 tier.

The reference's claim: KV offload to host DRAM improves TTFT ~40% over
GPU-only prefix caching on a multi-turn workload (10 conversations x 80
users; reference: docs/architecture/architecture.md:95-99). This bench is
the one-chip analogue: U users each hold a long distinct prefix; the HBM
arena is sized so a user's G1 prefix blocks are LRU-evicted by the other
users' traffic between their turns. On the follow-up turn the offload
engine onboards the prefix from host DRAM (one batched scatter); the
baseline engine recomputes the whole prefill.

Run via `BENCH_OFFLOAD=1 python bench.py`. Knobs: BENCH_OFFLOAD_USERS,
BENCH_OFFLOAD_PREFIX (tokens), BENCH_MODEL.
"""

from __future__ import annotations

import asyncio
import os
import time

import numpy as np

from dynamo_tpu.block_manager import KvbmConfig, KvBlockManager, KvLayoutConfig
from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.engine.engine import TpuEngine
from dynamo_tpu.llm.protocols.common import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.runtime.engine import Context

USERS = int(os.environ.get("BENCH_OFFLOAD_USERS", 8))
PREFIX = int(os.environ.get("BENCH_OFFLOAD_PREFIX", 1024))
TURN1_OSL = 16
DELTA = 32  # new user tokens on the follow-up turn
TURN2_OSL = 16


def _cfg() -> EngineConfig:
    model = getattr(
        ModelConfig, os.environ.get("BENCH_MODEL", "llama32_1b")
    )()
    blocks_per_prefix = PREFIX // 16
    # Arena holds ~70% of the users' combined prefixes: enough working set
    # for one active sequence, small enough that every user's turn-1 blocks
    # face eviction pressure before their turn 2.
    num_blocks = max(256, int(USERS * blocks_per_prefix * 0.7))
    return EngineConfig(
        model=model,
        num_blocks=num_blocks,
        block_size=16,
        max_num_seqs=4,
        max_model_len=1 << (PREFIX + TURN1_OSL + DELTA + TURN2_OSL).bit_length(),
        prefill_batch=4,
        enable_prefix_caching=True,
        quant=os.environ.get("DYNAMO_TPU_QUANT") or None,
    )


def _kvbm_layout(cfg: EngineConfig, engine: TpuEngine) -> KvLayoutConfig:
    m = cfg.model
    return KvLayoutConfig(
        num_layers=m.num_layers,
        page_size=cfg.block_size,
        num_kv_heads=m.num_cache_heads,
        head_dim=engine.runner.cache_head_dim,
        dtype=cfg.dtype,
    )


async def _turn(engine, tokens: list[int], osl: int):
    req = PreprocessedRequest(
        token_ids=tokens,
        sampling=SamplingOptions(temperature=0.0),
        stop=StopConditions(max_tokens=osl, ignore_eos=True),
    )
    t0 = time.monotonic()
    ttft = None
    out: list[int] = []
    async for item in engine.generate(Context(req.to_wire())):
        if item["token_ids"] and ttft is None:
            ttft = time.monotonic() - t0
        out += item["token_ids"]
    return ttft, out


async def _run_case(mode: str, prompts: list[list[int]]) -> dict:
    """mode: 'baseline' (no host tier), 'adaptive' (host tier + live
    onboard-vs-recompute gate, the production default), or 'raw' (host tier
    with the gate forced off — measures the unconditional onboard path)."""
    import dataclasses

    with_offload = mode != "baseline"
    cfg = _cfg()
    if mode == "raw":
        cfg = dataclasses.replace(cfg, kvbm_adaptive_gate=False)
    kvbm = None
    engine = TpuEngine(cfg)
    await engine.start()
    if with_offload:
        # The layout needs the live runner's (lane-padded) cache head dim;
        # attaching the manager post-start is safe — the serving path reads
        # engine.kvbm per request.
        kvbm = await KvBlockManager(
            KvbmConfig(
                layout=_kvbm_layout(cfg, engine),
                host_blocks=2 * USERS * (PREFIX // cfg.block_size + 8),
            )
        ).start()
        engine.kvbm = kvbm

    # Throwaway session compiles every serving shape (prefill buckets,
    # decode, and - offload case - the gather/scatter block buckets) off
    # the clock.
    rng = np.random.default_rng(1234)
    warm = rng.integers(0, cfg.model.vocab_size, PREFIX).tolist()
    _, w_out = await _turn(engine, warm, TURN1_OSL)
    await _turn(engine, warm + w_out + warm[:DELTA], TURN2_OSL)
    if with_offload:
        # The warm turn-2 hits G1 (no eviction yet), so the batched onboard
        # scatter never compiled — warm its bucket directly against trash
        # block 0 (engine is idle between requests; nothing races the
        # donated cache update).
        n = PREFIX // cfg.block_size
        m = cfg.model
        zeros = np.zeros(
            (
                n, m.num_layers, 2, cfg.block_size, m.num_cache_heads,
                engine.runner.cache_head_dim,
            ),
            np.float32,
        )
        engine.runner.scatter_many([0] * n, zeros)

    # Turn 1, every user in order: builds each prefix once; the arena
    # evicts the oldest users' blocks as later users arrive.
    turn1_out: list[list[int]] = []
    for p in prompts:
        _, out = await _turn(engine, p, TURN1_OSL)
        turn1_out.append(out)
    if kvbm is not None:
        await kvbm.drain_offers()

    # Turn 2, same order: user i's follow-up shares the full turn-1
    # history plus DELTA fresh tokens.
    ttfts, latencies, outs = [], [], []
    hits0 = engine._prefix_hits
    for p, o1 in zip(prompts, turn1_out):
        t0 = time.monotonic()
        ttft, out = await _turn(engine, p + o1 + p[:DELTA], TURN2_OSL)
        latencies.append(time.monotonic() - t0)
        ttfts.append(ttft)
        outs.append(out)

    stats = {
        "p50_ttft_ms": round(1000 * float(np.median(ttfts)), 1),
        "p95_ttft_ms": round(1000 * float(np.percentile(ttfts, 95)), 1),
        "mean_latency_ms": round(1000 * float(np.mean(latencies)), 1),
        "turn2_prefix_hits": engine._prefix_hits - hits0,
        "turn2_requests": len(prompts),
    }
    if kvbm is not None:
        stats["host_tier"] = kvbm.stats()
        stats["onboard_skips"] = engine._onboard_skips
        if engine._onboard_bps is not None:
            stats["onboard_mbps"] = round(engine._onboard_bps / 1e6, 1)
        if engine._prefill_tps is not None:
            stats["prefill_tok_per_s_wall"] = round(engine._prefill_tps, 1)
    await engine.stop()
    if kvbm is not None:
        await kvbm.stop()
    return stats, outs


def main() -> dict:
    rng = np.random.default_rng(7)
    cfg = _cfg()
    prompts = [
        rng.integers(0, cfg.model.vocab_size, PREFIX).tolist()
        for _ in range(USERS)
    ]

    async def run() -> dict:
        base, base_outs = await _run_case("baseline", prompts)
        raw, raw_outs = await _run_case("raw", prompts)
        adapt, adapt_outs = await _run_case("adaptive", prompts)
        return {
            "metric": f"offload_ttft_gain_prefix{PREFIX}_users{USERS}",
            # TTFT improvement of the production (adaptive) host tier over
            # full recompute (reference bar: +40%, architecture.md:95-99).
            "value": round(
                (base["p50_ttft_ms"] - adapt["p50_ttft_ms"])
                / max(base["p50_ttft_ms"], 1e-9),
                3,
            ),
            "unit": "fractional p50 TTFT reduction (ref bar 0.40)",
            "vs_baseline": round(
                base["p50_ttft_ms"] / max(adapt["p50_ttft_ms"], 1e-9), 3
            ),
            "extras": {
                "baseline_recompute": base,
                "host_offload_raw": raw,
                "host_offload_adaptive": adapt,
                "turn2_tokens_identical": base_outs == raw_outs
                and base_outs == adapt_outs,
                "users": USERS,
                "prefix_tokens": PREFIX,
            },
        }

    return asyncio.run(run())


if __name__ == "__main__":
    import json

    print(json.dumps(main()))
