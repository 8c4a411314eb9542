"""Concurrency sweep + agg-vs-disagg comparison harness.

The reference's perf story is exactly these comparisons (reference:
examples/llm/benchmarks/perf.sh — genai-perf concurrency 1→256 sweep;
docs/architecture/architecture.md:75-99 — disagg vs agg headline numbers).

`sweep(engine_like, ...)` drives any AsyncEngine with PreprocessedRequest
wire payloads at fixed concurrency levels and reports per-level
throughput + TTFT/ITL percentiles. ITL is per-request mean inter-token
time ((last−first)/(n−1)) — honest under chunked streaming, where raw
inter-chunk gaps would mix 0s with chunk periods.

Run standalone against the mocker (no device needed):

    python benchmarks/sweep.py            # sweep + agg-vs-disagg on mocker
"""

from __future__ import annotations

import asyncio
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import numpy as np

from benchmarks.synthesizer import Request, WorkloadConfig, generate
from dynamo_tpu.llm.protocols.common import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.runtime.engine import Context


def _pct(xs: list[float], q: float) -> float | None:
    return round(1000 * float(np.percentile(xs, q)), 1) if xs else None


async def run_level(engine, reqs: list[Request], concurrency: int) -> dict:
    """Drive `reqs` at a fixed concurrency; returns the level's metrics."""
    sem = asyncio.Semaphore(concurrency)

    async def one(r: Request):
        async with sem:
            pre = PreprocessedRequest(
                token_ids=list(r.token_ids),
                sampling=SamplingOptions(temperature=0.0),
                stop=StopConditions(max_tokens=r.max_tokens, ignore_eos=True),
            )
            t0 = time.monotonic()
            first = last = None
            n = 0
            async for out in engine.generate(Context(pre.to_wire())):
                toks = out.get("token_ids") or []
                if toks:
                    now = time.monotonic()
                    if first is None:
                        first = now
                    last = now
                    n += len(toks)
            return t0, first, last, n

    t0 = time.monotonic()
    results = await asyncio.gather(*[one(r) for r in reqs])
    elapsed = time.monotonic() - t0

    ttfts = [f - t for t, f, _, _ in results if f is not None]
    itls = [
        (last - first) / (n - 1)
        for _, first, last, n in results
        if first is not None and last is not None and n > 1
    ]
    total = sum(n for _, _, _, n in results)
    return {
        "concurrency": concurrency,
        "requests": len(reqs),
        "elapsed_s": round(elapsed, 2),
        "tok_per_s": round(total / elapsed, 1),
        "p50_ttft_ms": _pct(ttfts, 50),
        "p95_ttft_ms": _pct(ttfts, 95),
        "p50_itl_ms": _pct(itls, 50),
        "p95_itl_ms": _pct(itls, 95),
    }


async def sweep(
    engine,
    levels: tuple[int, ...] = (1, 2, 4, 8, 16, 32, 64),
    requests_per_level: int = 16,
    workload: WorkloadConfig | None = None,
) -> list[dict]:
    wl = workload or WorkloadConfig(num_requests=requests_per_level)
    out = []
    for c in levels:
        reqs = generate(
            WorkloadConfig(**{**wl.__dict__, "seed": wl.seed + c})
        )[:requests_per_level]
        out.append(await run_level(engine, reqs, c))
    return out


# ---------------------------------------------------------------------------
# Standalone: mocker sweep + agg-vs-disagg comparison.
# ---------------------------------------------------------------------------


def _mock_engine(max_len: int = 512):
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.mocker.engine import MockerConfig, MockerEngine
    from dynamo_tpu.models.config import ModelConfig

    return MockerEngine(
        EngineConfig(
            model=ModelConfig.tiny_test(),
            num_blocks=512,
            max_num_seqs=16,
            max_model_len=max_len,
        ),
        MockerConfig(),
    )


async def _agg_vs_disagg(reqs: list[Request]) -> dict:
    """Same workload through one aggregated mocker vs a prefill/decode
    mocker pair over the real disagg operators (queue + transfer plane)."""
    from dynamo_tpu.disagg import (
        DecodeOperator,
        DisaggConfig,
        DisaggRouter,
        PrefillQueue,
        PrefillWorker,
    )
    from dynamo_tpu.runtime.distributed import DistributedRuntime

    agg = _mock_engine()
    await agg.start()
    agg_res = await run_level(agg, reqs, concurrency=16)
    await agg.stop()

    drt = await DistributedRuntime.in_process()
    queue = PrefillQueue(drt, "bench")
    dis = DisaggRouter(
        drt,
        "bench",
        DisaggConfig(max_local_prefill_length=32, max_prefill_queue_size=64),
    )
    decode = _mock_engine()
    await decode.start()
    prefill = _mock_engine()
    await prefill.start()
    op = await DecodeOperator(decode, queue, dis, transport="tcp").start()
    pw = PrefillWorker(prefill, queue).start()
    disagg_res = await run_level(op, reqs, concurrency=16)
    await pw.stop()
    await op.stop()
    await decode.stop()
    await prefill.stop()
    await drt.shutdown()
    return {
        "agg": agg_res,
        "disagg": disagg_res,
        "remote_prefills": op.remote_count,
        "disagg_vs_agg_tok_per_s": round(
            disagg_res["tok_per_s"] / max(agg_res["tok_per_s"], 1e-9), 2
        ),
    }


async def _main() -> None:
    from benchmarks.synthesizer import prefix_stats

    engine = _mock_engine()
    await engine.start()
    wl = WorkloadConfig(num_requests=16, isl_mean=96, osl_mean=16)
    levels = await sweep(engine, levels=(1, 4, 16, 64), workload=wl)
    await engine.stop()

    reqs = generate(WorkloadConfig(num_requests=32, isl_mean=96, osl_mean=16))
    comparison = await _agg_vs_disagg(reqs)
    print(
        json.dumps(
            {
                "metric": "mocker_sweep",
                "workload": prefix_stats(reqs),
                "sweep": levels,
                "agg_vs_disagg": comparison,
            }
        )
    )


if __name__ == "__main__":
    asyncio.run(_main())
