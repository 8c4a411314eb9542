"""Driver benchmark: offline continuous-batching decode throughput.

Runs the full TpuEngine (scheduler → paged KV cache → jitted steps) on a
Llama-3.2-1B-shaped model with random weights: 32 requests, ISL 128 /
OSL 64, greedy. Reports generated tokens/sec/chip.

``vs_baseline`` is measured against the only absolute rate the reference
checks in — its echo test engine at 100 tok/s (reference:
lib/llm/src/engines.rs:66-78; see BASELINE.md, which notes all other
published numbers are relative). The north-star comparisons (8B/70B disagg
vs vLLM-on-H100) need real checkpoints + multi-chip hardware not present
in this harness.

Modes:
- default: the engine's default attention path (Pallas kernels on TPU —
  the r03 A/B winner).
- BENCH_AB=1: run the E2E scenario twice (DYNAMO_TPU_PALLAS on/off child
  processes) and report both, so the attention-path choice stays an
  evidence-backed default rather than a belief.
- BENCH_SMOKE=1: tiny config for CI smoke runs.
"""

from __future__ import annotations

import asyncio
import json
import os
import subprocess
import sys
import time

import numpy as np

# This module imports nothing but numpy at top level, and must stay so:
# _run_ab spawns children that need the chip, and a chip belongs to one
# process at a time — a parent that had touched jax would hold it.
#
# Persistent XLA compile cache: multi-engine scenarios (router/offload/
# disagg) and A/B child processes re-instantiate runners with identical
# shapes. Where it lives is engine/compile_cache.py resolve_cache_base's
# rule ($JAX_COMPILATION_CACHE_DIR, else $DYNAMO_TPU_COMPILE_CACHE_DIR,
# else <checkout>/.jax_cache), asked lazily in _engine_config. Opt out with
# DYNAMO_TPU_COMPILE_CACHE=0, which must actually measure cold compiles:
# the runner falls back to $DYNAMO_TPU_COMPILE_CACHE_DIR when the config
# is None (the shipped container exports it), so override it with the
# disable sentinel for this process and its A/B children.
if os.environ.get("DYNAMO_TPU_COMPILE_CACHE", "1") == "0":
    os.environ["DYNAMO_TPU_COMPILE_CACHE_DIR"] = "none"

SMOKE = bool(os.environ.get("BENCH_SMOKE"))  # tiny config for CI smoke runs
# BENCH_MOCKER=1: run the E2E scenario on the device-free MockerEngine
# (real scheduler/KV/streaming stack, simulated runner) — the CI smoke
# mode ci.sh uses: exercises the full serving path in seconds with no
# XLA compiles, and doubles as the disarmed-faults behavior check
# (tests/test_chaos.py compares its output against a faults-armed run).
MOCKER = bool(os.environ.get("BENCH_MOCKER"))
# The unified single-dispatch path (one ragged mixed prefill+decode
# batch per step; ROADMAP item #2) is the ONLY engine path now.
# BENCH_UNIFIED=1 additionally gates on the unified contract: warmup
# must stay within the budget ladder (≤ 8 programs vs the old
# lane×bucket grid's dozens) and the measured window must stay at zero
# mid-traffic compiles. BENCH_SPEC=1 (the spec A/B leg) implies the
# same gate with speculative decoding enabled.
UNIFIED = bool(
    os.environ.get("BENCH_UNIFIED") or os.environ.get("BENCH_SPEC")
)
UNIFIED_MAX_WARMUP_PROGRAMS = 8
# BENCH_TRACE=1: the observability leg (ci.sh "mocker trace smoke").
# The span capture itself is driven by DYNTPU_TRACE (utils/tracing.py);
# this flag asserts the leg's contract — refusing to run without a
# capture path and echoing it in extras — so the gate can't silently
# measure a run with tracing off.
TRACE = bool(os.environ.get("BENCH_TRACE"))
if TRACE and not os.environ.get("DYNTPU_TRACE"):
    raise SystemExit(
        "BENCH_TRACE=1 requires DYNTPU_TRACE=<capture path> — the trace "
        "leg exists to feed trace_merge.py --assert-complete"
    )
# BENCH_ROUTE_AUDIT=1: the KV-observatory leg (ci.sh "mocker route
# audit"). A multi-worker mocker deployment behind the KV-aware router
# with the trace capture on — route-audit records (predicted) and
# engine-side kv_actual records (actual) land in the same capture, and
# ci.sh closes the loop with benchmarks/route_audit.py --assert.
ROUTE_AUDIT = bool(os.environ.get("BENCH_ROUTE_AUDIT"))
if ROUTE_AUDIT and not os.environ.get("DYNTPU_TRACE"):
    raise SystemExit(
        "BENCH_ROUTE_AUDIT=1 requires DYNTPU_TRACE=<capture path> — the "
        "leg exists to feed route_audit.py --assert"
    )


def _env_int(name: str, default: int) -> int:
    return int(os.environ.get(name, default))


# Scenario knobs (env-overridable for on-chip experiments; the committed
# defaults are what the driver measures). 64 requests / 64 decode lanes:
# an older harness's batch-width study (not reproduced) put decode cost
# nearly flat from B=32→64, so doubling the lanes took E2E 719→1061
# tok/s/chip (+48%) on the same chip.
NUM_REQ = _env_int("BENCH_REQS", 4 if SMOKE else 64)
# BENCH_ISL=3000 BENCH_OSL=150 reproduces the reference harness shape
# (reference: examples/llm/benchmarks/perf.sh).
ISL, OSL = (32, 8) if SMOKE else (
    _env_int("BENCH_ISL", 128), _env_int("BENCH_OSL", 64)
)


def _engine_config():
    from dynamo_tpu.engine.compile_cache import resolve_cache_base
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.models.config import ModelConfig

    # max_num_seqs / prefill_batch below were chosen on an older engine
    # through an older harness (not reproduced on the chip builders have
    # now): wide decode batches were nearly free at these shapes, and a
    # wider fused prefill absorbed the arrival burst. prefill_batch is a
    # cap, not a quota: online latency never waits for stragglers.
    # BENCH_MODEL=llama31_8b (+ DYNAMO_TPU_QUANT=int8 to fit 16 GB HBM)
    # runs the 8B-class scenario (BASELINE.md progression step 2).
    model = (
        ModelConfig.tiny_test()
        if SMOKE
        else getattr(ModelConfig, os.environ.get("BENCH_MODEL", "llama32_1b"))()
    )
    return EngineConfig(
        model=model,
        num_blocks=256 if SMOKE else _env_int("BENCH_BLOCKS", 2048),
        block_size=16,
        max_num_seqs=8 if SMOKE else _env_int("BENCH_SEQS", 64),
        max_model_len=256 if SMOKE else _env_int(
            "BENCH_MAXLEN",
            max(512, 1 << (ISL + OSL - 1).bit_length()),
        ),
        prefill_batch=4 if SMOKE else _env_int("BENCH_PREFILL_BATCH", 16),
        enable_prefix_caching=True,
        # DYNAMO_TPU_QUANT=int8 serves int8 weights (ops/quant.py) — halves
        # decode's weight-streaming bytes; BENCH_QUANT_AB=1 A/Bs it.
        quant=os.environ.get("DYNAMO_TPU_QUANT") or None,
        # BENCH_SPEC_K=N enables prompt-lookup speculative decoding (the
        # random-prompt scenario accepts ~nothing — real value shows on
        # repetitive text; see tests/test_speculative.py).
        speculative_k=_env_int("BENCH_SPEC_K", 0),
        unified_token_budget=_env_int(
            "BENCH_UNIFIED_BUDGET", 64 if SMOKE else 256
        ),
        unified_prefill_quantum=_env_int(
            "BENCH_UNIFIED_QUANTUM", 16 if SMOKE else 64
        ),
        # The bench never requests penalties/logprobs; skipping the
        # extras variant keeps the warmed set at the bare budget ladder
        # (the unified_full top-rung program would be one extra).
        sampling_extras=False,
        compile_cache_dir=resolve_cache_base(),
    )


def _make_engine(cfg):
    if MOCKER:
        from dynamo_tpu.mocker import MockerConfig, MockerEngine

        return MockerEngine(
            cfg, MockerConfig(vocab_size=cfg.model.vocab_size)
        )
    from dynamo_tpu.engine.engine import TpuEngine

    return TpuEngine(cfg)


async def _run_e2e() -> dict:
    from dynamo_tpu.llm.protocols.common import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )
    from dynamo_tpu.runtime.engine import Context

    cfg = _engine_config()
    engine = _make_engine(cfg)
    await engine.start()

    rng = np.random.default_rng(0)
    reqs = [
        PreprocessedRequest(
            token_ids=rng.integers(0, cfg.model.vocab_size, ISL).tolist(),
            sampling=SamplingOptions(temperature=0.0),
            stop=StopConditions(max_tokens=OSL, ignore_eos=True),
        )
        for _ in range(NUM_REQ)
    ]

    async def run_one(req):
        n = 0
        first = None
        async for out in engine.generate(Context(req.to_wire())):
            if out["token_ids"] and first is None:
                first = time.monotonic()
            n += len(out["token_ids"])
        return n, first

    # Warmup: compile the serving shape set off the clock — a first
    # compile costs seconds and would otherwise land inside the measured
    # window. The FULL grid, not a hand-picked subset: variable-length
    # prompts landing on shapes warmup never compiled stall under load.
    # The persistent compile cache makes the grid a one-time cost —
    # relaunches replay it from disk.
    t_warm = time.monotonic()
    warmup_programs = await engine.warmup()
    warmup_s = round(time.monotonic() - t_warm, 1)
    await asyncio.gather(
        *[
            run_one(
                PreprocessedRequest(
                    token_ids=rng.integers(0, cfg.model.vocab_size, ISL).tolist(),
                    sampling=SamplingOptions(temperature=0.0),
                    stop=StopConditions(max_tokens=2, ignore_eos=True),
                )
            )
            for _ in range(3)
        ]
    )

    # DYNTPU_PROFILE=/dir captures an XLA/TPU profile of the measured
    # window (view with tensorboard / xprof) — the profiler-hook surface
    # for digging into dispatch vs device time.
    profile_dir = os.environ.get("DYNTPU_PROFILE")
    if profile_dir:
        import jax

        jax.profiler.start_trace(profile_dir)
    t0 = time.monotonic()
    results = await asyncio.gather(*[run_one(r) for r in reqs])
    elapsed = time.monotonic() - t0
    if profile_dir:
        import jax

        jax.profiler.stop_trace()

    total_tokens = sum(n for n, _ in results)
    ttfts = [f - t0 for _, f in results if f is not None]
    attn = getattr(engine.runner, "attn", None)  # SimRunner has none
    pallas = attn is not None and attn.use_pallas
    spec = {}
    if cfg.speculative_k:
        spec = {
            "spec_k": cfg.speculative_k,
            "spec_tokens_per_step": round(engine.spec_tokens_per_step, 3),
            "spec_active_at_end": engine.spec_active,
            "spec_gate_reprobes": engine.spec_probe_count,
        }
    # BENCH_SWEEP=0 skips the concurrency sweep (the heavyweight 8B /
    # long-context scenarios are long enough without it).
    sweep_levels = (
        await _sweep(engine) if _env_int("BENCH_SWEEP", 1) else []
    )
    compile_extras = _compile_lifecycle_report(
        engine, warmup_programs, warmup_s, sweep_levels
    )
    await engine.stop()
    return {
        "tok_per_s": round(total_tokens / elapsed, 2),
        "total_tokens": total_tokens,
        "elapsed_s": round(elapsed, 2),
        "p50_ttft_ms": round(1000 * float(np.median(ttfts)), 1),
        "max_ttft_ms": round(1000 * float(np.max(ttfts)), 1),
        "attention_path": "sim" if MOCKER else ("pallas" if pallas else "jnp"),
        "quant": cfg.quant or "none",
        **spec,
        **compile_extras,
        "sweep": sweep_levels,
    }


def _compile_lifecycle_report(
    engine, warmup_programs: int, warmup_s: float, sweep_levels: list[dict]
) -> dict:
    """Warmup cost + the two regression tripwires from the r05 collapse:
    the headline/sweep window must see ZERO mid-traffic compiles, and no
    sweep leg may show the compile-stall TTFT signature (p95 > 10x p50).
    Hard failures by default — a silently-regressed number is worse than
    a red bench (BENCH_COMPILE_GUARD=0 to downgrade while debugging)."""
    cs = engine.runner.compile_stats
    ratios, bad = [], []
    for leg in sweep_levels:
        p50, p95 = leg.get("p50_ttft_ms"), leg.get("p95_ttft_ms")
        if not p50 or not p95:
            continue
        r = round(p95 / p50, 2)
        ratios.append(r)
        if r > 10.0:
            bad.append(leg["concurrency"])
    out = {
        "warmup_programs": warmup_programs,
        "warmup_s": warmup_s,
        "warmup_cache_hits": cs.warm_cache_events["hits"],
        "warmup_cache_misses": cs.warm_cache_events["misses"],
        "mid_traffic_compiles": cs.mid_traffic_compiles,
        "compile_stall_ms": round(cs.compile_stall_ms_total, 1),
        "ttft_p95_over_p50_max": max(ratios) if ratios else None,
    }
    guard = os.environ.get("BENCH_COMPILE_GUARD", "1") != "0"
    if cs.mid_traffic_compiles and guard:
        raise RuntimeError(
            f"{cs.mid_traffic_compiles} mid-traffic compile(s) in the "
            f"measured window (shapes: {cs.mid_traffic_keys}) — warmup "
            "no longer covers the serving shape set"
        )
    if UNIFIED and guard and warmup_programs > UNIFIED_MAX_WARMUP_PROGRAMS:
        # The unified path's whole point: the warmed shape set is the
        # budget ladder, not a grid. A creeping program count means a
        # phase-split shape leaked back into the unified warmup plan.
        raise RuntimeError(
            f"unified warmup compiled {warmup_programs} programs "
            f"(> {UNIFIED_MAX_WARMUP_PROGRAMS}) — the budget ladder "
            "contract is broken (compile_cache.default_shape_grid)"
        )
    if UNIFIED:
        out["unified"] = True
        out["unified_max_warmup_programs"] = UNIFIED_MAX_WARMUP_PROGRAMS
    if bad and guard:
        raise RuntimeError(
            f"sweep legs at concurrency {bad} show p95 TTFT > 10x p50 — "
            "the r05 compile-stall signature"
        )
    return out


async def _sweep(engine) -> list[dict]:
    """Concurrency sweep over a prefix-structured synthetic workload
    (benchmarks/sweep.py) — the TTFT/ITL-vs-load curve. Prompt lengths are clamped into the warmed buckets."""
    from benchmarks.sweep import run_level
    from benchmarks.synthesizer import WorkloadConfig, generate

    # Through c=64 — the committed lane width; >=32 requests per level so
    # per-level medians are not noise (12-request levels once made c=32
    # look slower than c=16).
    levels = (1, 4, 16) if SMOKE else (1, 4, 16, 32, 64)
    out = []
    for c in levels:
        reqs = generate(
            WorkloadConfig(
                num_requests=8 if SMOKE else max(32, c),
                isl_mean=ISL - ISL // 4,
                osl_mean=max(OSL // 2, 4),
                vocab_size=min(1000, engine.cfg.model.vocab_size),
                seed=c,
            )
        )
        for r in reqs:
            r.token_ids = r.token_ids[:ISL]
            r.max_tokens = min(r.max_tokens, OSL)
        out.append(await run_level(engine, reqs, c))
    return out


async def _run_disagg() -> dict:
    """Agg vs disagg on REAL engines: the same workload
    through one aggregated engine, then through a prefill+decode engine
    pair co-located on this chip and wired over the device (HBM→HBM)
    transfer plane. One chip can't add compute, so the honest claim this
    measures is the SPLIT's overhead/benefit at fixed silicon: does
    dedicating prefill to a second engine (decode batches never stall
    behind a prompt) beat the aggregated engine's chunked interleave, and
    what does the KV handoff cost end to end."""
    import dataclasses

    from benchmarks.sweep import run_level
    from benchmarks.synthesizer import Request
    from dynamo_tpu.disagg import (
        DecodeOperator,
        DisaggConfig,
        DisaggRouter,
        PrefillQueue,
        PrefillWorker,
    )
    from dynamo_tpu.engine.engine import TpuEngine
    from dynamo_tpu.runtime.distributed import DistributedRuntime

    cfg = _engine_config()
    rng = np.random.default_rng(0)
    reqs = [
        Request(
            token_ids=rng.integers(0, cfg.model.vocab_size, ISL).tolist(),
            max_tokens=OSL,
        )
        for _ in range(NUM_REQ)
    ]
    conc = min(NUM_REQ, cfg.max_num_seqs)

    # Aggregated baseline. Full pruned-grid warmup, not just bucket(ISL):
    # a prompt whose length is not a chunk multiple buckets its LAST
    # chunk small (the r05 hole) — and the persistent cache makes the
    # second/third engine's identical warmups disk replays.
    agg = TpuEngine(cfg)
    await agg.start()
    await agg.warmup()
    agg_res = await run_level(agg, reqs, concurrency=conc)
    params = agg.runner.params  # share weights with the pair (same HBM)
    await agg.stop()

    # Disagg pair: decode keeps the serving arena; prefill gets its own
    # smaller arena (it only holds in-flight prompts' KV). Weights are
    # SHARED device buffers — co-located engines don't pay them twice.
    drt = await DistributedRuntime.in_process()
    queue = PrefillQueue(drt, "bench")
    dis = DisaggRouter.__new__(DisaggRouter)
    if os.environ.get("BENCH_DISAGG_ADAPTIVE"):
        # Production router behavior: the queue-age SLA sheds prefills
        # back to local when the prefill pool can't keep up.
        dis.cfg = DisaggConfig(
            max_local_prefill_length=min(32, ISL - 1),
            max_prefill_queue_size=NUM_REQ * 2,
        )
    else:
        # Forced split: EVERY prefill goes remote so the handoff path
        # (queue + prefill engine + KV transfer) is what gets measured.
        dis.cfg = DisaggConfig(
            max_local_prefill_length=min(32, ISL - 1),
            max_prefill_queue_size=10**6,
            max_prefill_queue_age_s=1e9,
        )
    decode = TpuEngine(dataclasses.replace(cfg, quant=None), params=params)
    await decode.start()
    prefill = TpuEngine(
        dataclasses.replace(
            cfg,
            quant=None,
            num_blocks=max(512, cfg.num_blocks // 2),
        ),
        params=params,
    )
    await prefill.start()
    op = await DecodeOperator(decode, queue, dis, transport="device").start()
    pw = PrefillWorker(prefill, queue).start()
    await decode.warmup()
    await prefill.warmup()
    disagg_res = await run_level(op, reqs, concurrency=conc)
    remote = op.remote_count
    await pw.stop()
    await op.stop()
    await decode.stop()
    await prefill.stop()
    await drt.shutdown()
    return {
        "agg": agg_res,
        "disagg": disagg_res,
        "remote_prefills": remote,
        "transport": "device",
        "concurrency": conc,
        "ratio_tok_per_s": round(
            disagg_res["tok_per_s"] / max(agg_res["tok_per_s"], 1e-9), 3
        ),
    }


def _run_ab(var: str, settings: list[tuple[str, str]]) -> dict:
    """Run the E2E scenario in child processes with `var` set per setting;
    returns all results (the evidence-backed-default pattern from the r03
    Pallas A/B)."""
    results = {}
    for name, flag in settings:
        env = dict(os.environ)
        env[var] = flag
        env.pop("BENCH_AB", None)
        env.pop("BENCH_QUANT_AB", None)
        env.pop("BENCH_SPEC_AB", None)
        out = subprocess.run(
            [sys.executable, os.path.abspath(__file__)],
            env=env, capture_output=True, text=True,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        )
        if out.returncode != 0:
            sys.stderr.write(out.stderr)
            raise RuntimeError(
                f"A/B child {name!r} failed rc={out.returncode}"
            )
        results[name] = json.loads(out.stdout.strip().splitlines()[-1])
    return results


async def _run_overload() -> dict:
    """Overload smoke (ci.sh BENCH_OVERLOAD=1): the FULL HTTP stack over a
    slow mocker engine, driven at offered load ≫ capacity. Hard asserts
    (the acceptance criteria of the overload-safe serving work):

    - a low-load leg sheds NOTHING (every request 200);
    - the overload leg produces 429s carrying ``Retry-After`` (excess
      refused, not queued unboundedly) and zero hangs (everything
      bounded);
    - admitted requests finish within their deadlines;
    - ``shed_requests_total`` / ``deadline_exceeded_total`` / ``draining``
      appear on HTTP /metrics with shed > 0.
    """
    import aiohttp

    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.llm.admission import AdmissionConfig, AdmissionController
    from dynamo_tpu.llm.discovery import ModelManager, ModelWatcher, register_llm
    from dynamo_tpu.llm.http_service import HttpService
    from dynamo_tpu.llm.model_card import ModelDeploymentCard
    from dynamo_tpu.mocker import MockerConfig, MockerEngine
    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.runtime.distributed import DistributedRuntime

    cfg = EngineConfig(
        model=ModelConfig.tiny_test(),
        num_blocks=128,
        max_num_seqs=4,
        max_model_len=256,
        dtype="float32",
        max_waiting=8,           # bounded engine waiting list
    )
    # Slow cost model: ~4 concurrent lanes at ~2 ms/step makes a 64-way
    # burst genuinely over capacity without making the leg slow.
    engine = MockerEngine(
        cfg,
        MockerConfig(
            prefill_time_per_token_us=100.0,
            decode_time_per_step_us=2000.0,
            vocab_size=cfg.model.vocab_size,
        ),
    )
    await engine.start()
    await engine.warmup()

    drt = await DistributedRuntime.in_process()
    ep = drt.namespace("bench").component("mock").endpoint("generate")
    await ep.serve(engine)
    await register_llm(
        drt, ep, ModelDeploymentCard(name="mock", model_path="toy")
    )
    manager = ModelManager()
    await ModelWatcher(drt, manager).start()
    admission = AdmissionController(
        AdmissionConfig(
            max_inflight=8,
            max_engine_waiting=8,
            default_deadline_s=30.0,
            retry_after_s=1.0,
        ),
        engine_stats=engine.readiness,
    )
    service = HttpService(
        manager, host="127.0.0.1", port=0,
        readiness=engine.readiness, admission=admission,
    )
    await service.start()
    base = f"http://127.0.0.1:{service.port}"
    body = {
        "model": "mock",
        "messages": [{"role": "user", "content": "overload probe"}],
        "stream": False,
        "max_tokens": 8,
    }

    async def one(session):
        t0 = time.monotonic()
        async with session.post(
            f"{base}/v1/chat/completions", json=body
        ) as resp:
            await resp.read()
            return resp.status, dict(resp.headers), time.monotonic() - t0

    try:
        async with aiohttp.ClientSession() as session:
            # Low-load leg: sequential trickle well under capacity —
            # nothing may shed.
            low = [await one(session) for _ in range(4)]
            low_bad = [s for s, _, _ in low if s != 200]
            if low_bad:
                raise RuntimeError(f"low-load leg shed/failed: {low_bad}")
            shed_low = OVERLOAD_SHED_SNAPSHOT()
            # Overload leg: one 64-way burst at max_inflight=8. Bounded
            # end to end — a hang here IS the failure being guarded.
            results = await asyncio.wait_for(
                asyncio.gather(*[one(session) for _ in range(64)]),
                timeout=120.0,
            )
            ok = [r for r in results if r[0] == 200]
            shed = [r for r in results if r[0] == 429]
            other = [r[0] for r in results if r[0] not in (200, 429)]
            if other:
                raise RuntimeError(f"unexpected statuses under overload: {other}")
            if not shed:
                raise RuntimeError(
                    "offered load >> capacity produced no 429s — "
                    "admission gate inert"
                )
            missing_retry_after = [
                h for _, h, _ in shed if "Retry-After" not in h
            ]
            if missing_retry_after:
                raise RuntimeError("429 responses missing Retry-After")
            # Admitted requests must finish within the default deadline.
            slow = [t for _, _, t in ok if t > 30.0]
            if slow:
                raise RuntimeError(f"admitted requests blew deadline: {slow}")
            async with session.get(f"{base}/metrics") as resp:
                metrics_text = await resp.text()
    finally:
        await service.stop()
        await drt.shutdown()
        await engine.stop()
    for needle in (
        "shed_requests_total",
        "deadline_exceeded_total",
        "_draining",
    ):
        if needle not in metrics_text:
            raise RuntimeError(f"/metrics missing {needle}")
    shed_total = OVERLOAD_SHED_SNAPSHOT()
    if shed_total <= shed_low:
        raise RuntimeError("shed_requests_total did not increase under overload")
    ttfts = sorted(t for _, _, t in ok)
    return {
        "offered": 64,
        "completed_200": len(ok),
        "shed_429": len(shed),
        "low_load_shed": shed_low,
        "shed_requests_total": shed_total,
        "p95_admitted_latency_ms": round(
            1000 * ttfts[int(0.95 * (len(ttfts) - 1))], 1
        ) if ttfts else None,
    }


async def _run_route_audit() -> dict:
    """KV-observatory leg (ci.sh BENCH_ROUTE_AUDIT=1): a multi-worker
    mocker deployment behind the production KV-aware routing plane
    (KvEventPublisher → bus → radix indexer → PushRouter KV mode) with
    the DYNTPU_TRACE capture on. Every decision writes a ``route`` record
    (predicted overlap + candidates + indexer watermark); every engine
    admission writes a ``kv_actual`` record (per-tier actual reuse); both
    stream into the capture, which ci.sh then feeds to
    benchmarks/route_audit.py --assert — the gate that ≥95% of requests
    join predicted↔actual by trace id, with zero orphan routes and a
    non-zero actual-reuse report.

    Inline hard asserts (this process's half of the contract):
    - every request completes;
    - a route-audit record exists for every routed request;
    - the hit-rate plane carries BOTH kinds (predicted + actual);
    - the indexer applied events and recorded publish→apply lag;
    - follow-up turns actually reused KV (affinity held).
    """
    import random as _random

    import msgpack as _msgpack

    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.llm.kv_router.audit import ROUTE_OBS
    from dynamo_tpu.llm.kv_router.protocols import KV_HIT_RATE_PLANE
    from dynamo_tpu.llm.kv_router.publisher import (
        KvEventPublisher,
        WorkerMetricsPublisher,
    )
    from dynamo_tpu.llm.kv_router.router import KvRouter
    from dynamo_tpu.llm.protocols.common import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )
    from dynamo_tpu.mocker import MockerConfig, MockerEngine
    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.runtime.distributed import DistributedRuntime
    from dynamo_tpu.runtime.egress import PushRouter, RouterMode
    from dynamo_tpu.runtime.engine import Context

    num_workers = _env_int("BENCH_ROUTE_WORKERS", 3)
    sessions = _env_int("BENCH_ROUTE_SESSIONS", 12)
    cfg = EngineConfig(
        model=ModelConfig.tiny_test(),
        num_blocks=512,
        max_num_seqs=8,
        max_model_len=512,
        dtype="float32",
    )

    drt0 = await DistributedRuntime.in_process()
    drts = [drt0]
    engines = []
    for i in range(num_workers):
        drt = (
            drt0
            if i == 0
            else await DistributedRuntime.in_process(
                store=drt0.store, bus=drt0.bus, runtime=drt0.runtime
            )
        )
        if i > 0:
            drts.append(drt)
        comp = drt.namespace("bench").component("worker")
        wm = WorkerMetricsPublisher()
        pub = KvEventPublisher(drt, comp, drt.primary_lease_id)
        eng = MockerEngine(cfg, MockerConfig(seed=i))
        eng._external_kv_event = pub.publish_engine_event
        eng._on_metrics = wm.publish
        # The loop-closing half: per-request actuals onto the hit-rate
        # plane (and the trace capture, via the engine's own flush).
        eng._on_kv_actual = pub.publish_hit_actual
        await eng.start()
        await comp.endpoint("generate").serve(eng)
        await wm.create_endpoint(comp)
        engines.append(eng)

    comp0 = drt0.namespace("bench").component("worker")
    # Count both payload kinds on the hit-rate plane — the loop must be
    # closed ON THE BUS, not just in this process's capture file.
    plane_counts = {"predicted": 0, "actual": 0}
    plane_sub = await drt0.bus.subscribe(
        comp0.event_subject(KV_HIT_RATE_PLANE)
    )

    async def count_plane():
        async for raw in plane_sub:
            kind = _msgpack.unpackb(raw).get("kind", "predicted")
            plane_counts[kind] = plane_counts.get(kind, 0) + 1

    plane_task = asyncio.ensure_future(count_plane())

    router = await KvRouter(drt0, comp0).start()
    push = await PushRouter.create(
        drt0,
        "bench.worker.generate",
        mode=RouterMode.KV,
        selector=router.selector_fn,
    )

    rng = _random.Random(7)
    prompts = [
        [rng.randrange(0, cfg.model.vocab_size) for _ in range(64 + 16 * (s % 3))]
        for s in range(sessions)
    ]

    async def send(tokens, osl=4):
        req = PreprocessedRequest(
            token_ids=list(tokens),
            sampling=SamplingOptions(temperature=0.0),
            stop=StopConditions(max_tokens=osl, ignore_eos=True),
        )
        ctx = Context(req.to_wire())
        out = []
        async for item in push.generate(ctx):
            out += item.get("token_ids", [])
        return out

    routes_before = ROUTE_OBS.routes_total
    # Turn 1: place every session's prefix on whichever worker wins.
    turn1 = await asyncio.gather(*[send(p) for p in prompts])
    await asyncio.sleep(0.4)  # KV events → indexer (lag gets measured)
    # Turn 2: full-history follow-ups — the predicted overlap should be
    # nonzero and the chosen worker should ACTUALLY reuse blocks.
    turn2 = await asyncio.gather(
        *[send(p + o + p[:16]) for p, o in zip(prompts, turn1)]
    )
    await asyncio.sleep(0.4)  # actual records flush + plane broadcasts land

    bad = [i for i, o in enumerate(turn2) if len(o) != 4]
    if bad:
        raise RuntimeError(f"turn-2 requests incomplete: {bad}")
    total_requests = 2 * sessions
    routed = ROUTE_OBS.routes_total - routes_before
    if routed < total_requests:
        raise RuntimeError(
            f"route-audit records missing: {routed} < {total_requests}"
        )
    obs = router.observability()
    if obs["kv_events_applied_total"] <= 0:
        raise RuntimeError("indexer applied no KV events")
    if obs["kv_event_lag_count"] <= 0:
        raise RuntimeError("no publish→apply lag samples recorded")
    reused = sum(
        e._reused_device_blocks + e._reused_host_blocks + e._reused_disk_blocks
        for e in engines
    )
    if reused <= 0:
        raise RuntimeError(
            "follow-up turns reused zero blocks — affinity/actual loop broken"
        )
    if plane_counts["predicted"] <= 0 or plane_counts["actual"] <= 0:
        raise RuntimeError(
            f"hit-rate plane incomplete: {plane_counts} — both kinds required"
        )
    # Turn-2 affinity as seen by the AUDIT RECORDS themselves.
    recent = ROUTE_OBS.snapshot(total_requests)["recent"]
    turn2_recs = recent[-sessions:]
    with_overlap = sum(1 for r in turn2_recs if r["overlap_blocks"] > 0)

    plane_sub.close()
    plane_task.cancel()
    try:
        await plane_task
    except (asyncio.CancelledError, Exception):  # noqa: BLE001 — teardown
        pass
    await router.stop()
    for eng in engines:
        await eng.stop()
    await drt0.shutdown()
    return {
        "workers": num_workers,
        "sessions": sessions,
        "requests": total_requests,
        "route_records": routed,
        "turn2_with_predicted_overlap": with_overlap,
        "kv_events_applied": obs["kv_events_applied_total"],
        "kv_event_lag_p99_ms": obs["kv_event_lag_p99_ms"],
        "reused_blocks_total": reused,
        "hit_rate_plane": dict(plane_counts),
        "trace_capture": os.environ.get("DYNTPU_TRACE", ""),
        "aggregator_scrape_failures_total": obs[
            "aggregator_scrape_failures_total"
        ],
    }


async def _run_spec() -> dict:
    """Unified speculative-decode A/B (ci.sh BENCH_SPEC=1; ROADMAP #2's
    last leg): spec decode now rides the ragged unified step — draft-
    verify spans on the SAME budget-ladder programs, acceptance computed
    in-dispatch. Three mocker legs over one decode-heavy workload:

    - **spec** (accepting regime): deterministic position-free token
      chain (MockerConfig.det_positional=False, small vocab) with the
      prompt pre-seeded on the chain, so prompt-lookup drafts verify —
      the regime speculation exists for;
    - **plain**: the same engine with speculative_k=0;
    - **losing** (free-when-losing): the positional chain (drafts never
      accept) with tight gate windows — the auto-gate must disable and
      keep re-probe overhead inside the probe-window bound.

    Hard gates:
    - warmup ≤ 8 programs (``warmup_programs_total`` — spec adds ZERO
      programs to the ladder) and zero mid-traffic compiles on every
      leg;
    - accepting-draft spec throughput ≥ the plain unified leg's;
    - accepting-draft spec throughput ≥ the RECORDED phased-spec
      baseline — computed from the phased pricing law this suite
      retained when the phased engine was deleted
      (its fused spec-decode program charged the dispatch base ×(1+K)
      per 1-token step);
    - the losing leg's spec steps stay within
      window + probes × probe_window (the phased gate's bound,
      preserved).
    """
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.llm.protocols.common import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )
    from dynamo_tpu.mocker import MockerConfig, MockerEngine
    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.runtime.engine import Context

    spec_k = _env_int("BENCH_SPEC_LEG_K", 4)
    n_req, osl, isl = 4, 120, 64
    # vocab 23 puts the position-free affine chain on an 11-cycle, so a
    # 64-token chain prompt repeats its bigrams several times over —
    # prompt-lookup drafts verify from the first decode step.
    vocab = 23

    def cfg(k: int, **kw) -> EngineConfig:
        return EngineConfig(
            model=ModelConfig.tiny_test(),
            num_blocks=256,
            max_num_seqs=n_req,
            max_model_len=512,
            dtype="float32",
            speculative_k=k,
            unified_token_budget=64,
            sampling_extras=False,
            **kw,
        )

    from dynamo_tpu.mocker import det_next_token

    def chain_prompt(seed_tok: int) -> list[int]:
        # The prompt IS the closed-form chain (built through the SAME
        # helper the sim verifies drafts against), so the trailing
        # bigram always has an earlier occurrence once the cycle closes
        # — the accepting-draft setting.
        toks = [seed_tok]
        for _ in range(isl - 1):
            toks.append(int(det_next_token(toks[-1], 0, vocab, positional=False)))
        return toks

    async def run_leg(engine) -> dict:
        await engine.start()
        await engine.warmup()
        reqs = [
            PreprocessedRequest(
                token_ids=chain_prompt(3 + i),
                sampling=SamplingOptions(temperature=0.0),
                stop=StopConditions(max_tokens=osl, ignore_eos=True),
            )
            for i in range(n_req)
        ]

        async def one(req):
            n = 0
            async for out in engine.generate(Context(req.to_wire())):
                n += len(out["token_ids"])
            return n

        t0 = time.monotonic()
        counts = await asyncio.gather(*[one(r) for r in reqs])
        dt = time.monotonic() - t0
        cs = engine.runner.compile_stats
        leg = {
            "tok_per_s": round(sum(counts) / dt, 1),
            "tokens": sum(counts),
            "warmup_programs_total": cs.snapshot()["warmup_programs_total"],
            "mid_traffic_compiles": cs.mid_traffic_compiles,
            "spec_tokens_per_step": round(engine.spec_tokens_per_step, 3),
            "spec_drafted": engine._spec_drafted,
            "spec_accepted": engine._spec_accepted,
            "spec_active_at_end": engine.spec_active,
        }
        await engine.stop()
        return leg

    sim_accept = MockerConfig(
        vocab_size=vocab, deterministic_tokens=True, det_positional=False
    )
    spec = await run_leg(MockerEngine(cfg(spec_k), sim_accept))
    plain = await run_leg(MockerEngine(cfg(0), sim_accept))

    # Free-when-losing: positional chain (drafts never verify) + tight
    # gate windows; bound identical to the phased gate's contract.
    window, probe_window, probe_steps = 8, 2, 32
    losing_engine = MockerEngine(
        cfg(
            spec_k,
            speculative_window=window,
            speculative_probe_window=probe_window,
            speculative_probe_steps=probe_steps,
        ),
        MockerConfig(vocab_size=vocab, deterministic_tokens=True),
    )
    losing = await run_leg(losing_engine)
    losing["spec_steps"] = losing_engine._spec_steps
    losing["probes"] = losing_engine.spec_probe_count
    # Each window close (the initial window + every probe) can overshoot
    # by up to n_req - 1 steps: the closing dispatch retires one spec
    # step per concurrent lane at once.
    probes = losing_engine.spec_probe_count
    losing_budget = (
        window + probes * probe_window + (probes + 1) * (n_req - 1)
    )

    # The recorded phased-spec baseline: the deleted phased spec-decode
    # sim charged decode_time_per_step_us × (1+K) per fused step and
    # delivered 1 token per lane per step — its throughput at these
    # constants is the closed form below (the law is retained here so
    # the comparison outlives the deleted code).
    base_us = sim_accept.decode_time_per_step_us
    phased_spec_tps = round(n_req / (base_us * (1 + spec_k) / 1e6), 1)

    failures = []
    for name, leg in (("spec", spec), ("plain", plain), ("losing", losing)):
        if leg["warmup_programs_total"] > UNIFIED_MAX_WARMUP_PROGRAMS:
            failures.append(
                f"{name} leg warmed {leg['warmup_programs_total']} programs "
                f"(> {UNIFIED_MAX_WARMUP_PROGRAMS}) — spec must add ZERO "
                "programs to the budget ladder"
            )
        if leg["mid_traffic_compiles"]:
            failures.append(
                f"{name} leg paid {leg['mid_traffic_compiles']} mid-traffic "
                "compile(s)"
            )
    if spec["spec_tokens_per_step"] <= 1.5:
        failures.append(
            f"accepting-draft leg delivered only "
            f"{spec['spec_tokens_per_step']} tok/step — drafts are not "
            "being accepted"
        )
    if spec["tok_per_s"] < plain["tok_per_s"]:
        failures.append(
            f"unified spec {spec['tok_per_s']} tok/s < unified non-spec "
            f"{plain['tok_per_s']} at accepting-draft settings"
        )
    if spec["tok_per_s"] < phased_spec_tps:
        failures.append(
            f"unified spec {spec['tok_per_s']} tok/s < the recorded "
            f"phased-spec baseline {phased_spec_tps}"
        )
    if losing["spec_active_at_end"]:
        failures.append("losing leg never auto-gated speculation off")
    if losing["spec_steps"] > losing_budget:
        failures.append(
            f"losing leg ran {losing['spec_steps']} spec steps; "
            f"free-when-losing bound is {losing_budget}"
        )
    if failures:
        raise RuntimeError(
            "BENCH_SPEC gates failed:\n  " + "\n  ".join(failures)
        )
    return {
        "spec_k": spec_k,
        "spec": spec,
        "plain": plain,
        "losing": losing,
        "phased_spec_baseline_tok_per_s": phased_spec_tps,
        "speedup_vs_plain": round(
            spec["tok_per_s"] / max(plain["tok_per_s"], 1e-9), 3
        ),
        "speedup_vs_phased_spec": round(
            spec["tok_per_s"] / max(phased_spec_tps, 1e-9), 3
        ),
    }


async def _run_coloc() -> dict:
    """Co-location A/B (ci.sh BENCH_COLOC=1; ROADMAP item #3): the same
    ISL3000-style mixed load through (a) SLO-aware ADAPTIVE co-located
    serving (AIMD quantum, engine/coloc.py) and (b) the STATIC-quantum
    baseline (the hand-tuned default the controller replaces), on the
    mocker's per-phase cost model. The phase-alternating aggregated
    baseline is GONE with the phased engine; the live A/B now proves the adaptive
    controller beats the static default it ships over. Hard asserts,
    the acceptance criteria of the co-location work:

    - the adaptive leg's decode ITL p95 DURING the prefill burst stays
      within ``itl_slo_ms``;
    - its prefill throughput (burst prompt tokens / time-to-last-TTFT)
      meets or exceeds the static baseline's (headroom under the SLO
      must convert into quantum growth);
    - zero mid-traffic compiles on the adaptive leg (adaptation is
      batch composition — totals still snap onto the warmed budget
      ladder).
    """
    import dataclasses

    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.llm.protocols.common import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )
    from dynamo_tpu.mocker import MockerConfig, MockerEngine
    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.runtime.engine import Context

    slo_ms = float(os.environ.get("BENCH_COLOC_SLO_MS", 15.0))
    isl = _env_int("BENCH_COLOC_ISL", 3000)
    n_decode, osl_decode, isl_decode = 8, 200, 64
    n_burst, osl_burst = 6, 4
    base_cfg = EngineConfig(
        model=ModelConfig.tiny_test(),
        num_blocks=2048,
        block_size=16,
        # Slots for BOTH populations: the decode cohort holds 8 lanes
        # for the whole run while the prefill burst co-locates into the
        # remaining 4 — otherwise prefill would only run as decode
        # drains and the A/B would measure slot starvation, not
        # co-location.
        max_num_seqs=n_decode + 4,
        max_model_len=4096,
        prefill_batch=4,
        dtype="float32",
        sampling_extras=False,
    )
    # Per-phase cost model: 2 ms dispatch base (weight pass) + 100 us
    # per decode lane + 10 us per prefill token; a standalone prefill
    # dispatch pays a 4 ms base of its own. The steady co-located
    # dispatch is therefore ~2.8 ms + 10 us/quantum-token: quantum
    # changes visibly move ITL, which is what the controller steers.
    sim = MockerConfig(
        prefill_time_per_token_us=10.0,
        prefill_quadratic_us=0.0,
        decode_time_per_step_us=2000.0,
        decode_time_per_lane_us=100.0,
        prefill_dispatch_base_us=4000.0,
        vocab_size=base_cfg.model.vocab_size,
    )

    async def leg(colocated: bool) -> dict:
        if colocated:
            cfg = dataclasses.replace(
                base_cfg,
                unified_token_budget=1024,
                unified_prefill_quantum=64,
                coloc="adaptive",
                itl_slo_ms=slo_ms,
                coloc_min_quantum=16,
            )
        else:
            # Static baseline: the same budget, the hand-tuned default
            # quantum, no controller — what serving looks like without
            # adaptation.
            cfg = dataclasses.replace(
                base_cfg,
                unified_token_budget=1024,
                unified_prefill_quantum=64,
                coloc="static",
            )
        eng = MockerEngine(cfg, sim)
        await eng.start()
        await eng.warmup()
        rng = np.random.default_rng(7)
        gaps: list[tuple[float, float]] = []  # (t_gap_end, gap_ms)

        async def run_decode():
            req = PreprocessedRequest(
                token_ids=rng.integers(
                    0, cfg.model.vocab_size, isl_decode
                ).tolist(),
                sampling=SamplingOptions(temperature=0.0),
                stop=StopConditions(max_tokens=osl_decode, ignore_eos=True),
            )
            last = None
            async for out in eng.generate(Context(req.to_wire())):
                if not out["token_ids"]:
                    continue
                # One gap per delivery frame: tokens sharing a frame
                # arrived together, and recording a zero per extra
                # token would dilute the percentiles with artifacts of
                # delivery batching instead of measuring arrival gaps.
                now = time.monotonic()
                if last is not None:
                    gaps.append((now, 1000.0 * (now - last)))
                last = now

        async def run_burst():
            req = PreprocessedRequest(
                token_ids=rng.integers(0, cfg.model.vocab_size, isl).tolist(),
                sampling=SamplingOptions(temperature=0.0),
                stop=StopConditions(max_tokens=osl_burst, ignore_eos=True),
            )
            first = None
            async for out in eng.generate(Context(req.to_wire())):
                if out["token_ids"] and first is None:
                    first = time.monotonic()
            return first

        decode_tasks = [
            asyncio.create_task(run_decode()) for _ in range(n_decode)
        ]
        await asyncio.sleep(0.15)  # decode population reaches steady state
        t_burst = time.monotonic()
        firsts = await asyncio.gather(*[run_burst() for _ in range(n_burst)])
        t_done = max(f for f in firsts if f is not None)
        # Controller state AT burst end — the p95 window still holds the
        # burst-era dispatch intervals (the post-burst decode-only tail
        # would flush them out).
        coloc_at_burst = dict(eng.coloc.snapshot()) if colocated else None
        await asyncio.gather(*decode_tasks)
        burst_gaps = sorted(
            g for t, g in gaps if t_burst <= t <= t_done
        ) or sorted(g for _, g in gaps)
        p95 = burst_gaps[min(len(burst_gaps) - 1, int(0.95 * len(burst_gaps)))]
        cs = eng.runner.compile_stats
        await eng.stop()
        out = {
            "prefill_tok_per_s": round(n_burst * isl / (t_done - t_burst), 1),
            # Client-observed inter-token gaps: dispatch cadence PLUS
            # asyncio delivery jitter (frames queue behind the event
            # loop). Reported for both legs; the SLO gate below reads
            # the engine-side dispatch-interval p95 — the cadence the
            # controller actually regulates.
            "client_itl_p95_ms": round(p95, 2),
            "client_itl_p50_ms": round(burst_gaps[len(burst_gaps) // 2], 2),
            "mid_traffic_compiles": cs.mid_traffic_compiles,
        }
        if coloc_at_burst is not None:
            out["itl_p95_ms"] = coloc_at_burst["itl_p95_ms"]
            out["itl_ema_ms"] = coloc_at_burst["itl_ema_ms"]
            out["coloc_quantum"] = coloc_at_burst["coloc_quantum"]
            out["itl_slo_violations_total"] = coloc_at_burst[
                "itl_slo_violations_total"
            ]
            out["coloc_prefill_deferrals_total"] = coloc_at_burst[
                "coloc_prefill_deferrals_total"
            ]
        return out

    coloc = await leg(colocated=True)
    agg = await leg(colocated=False)
    if coloc["mid_traffic_compiles"]:
        raise RuntimeError(
            f"co-located leg paid {coloc['mid_traffic_compiles']} "
            "mid-traffic compile(s) — adaptive quantum must stay on the "
            "warmed budget ladder"
        )
    if coloc["itl_p95_ms"] > slo_ms:
        raise RuntimeError(
            f"co-located decode ITL p95 {coloc['itl_p95_ms']} ms (engine "
            f"dispatch-interval, at burst end) violates the {slo_ms} ms "
            "SLO — the quantum controller failed to hold it"
        )
    if coloc["prefill_tok_per_s"] < agg["prefill_tok_per_s"]:
        raise RuntimeError(
            f"adaptive co-located prefill throughput "
            f"{coloc['prefill_tok_per_s']} tok/s fell below the "
            f"static-quantum baseline's {agg['prefill_tok_per_s']} — "
            "SLO headroom must convert into quantum growth"
        )
    return {
        "slo_ms": slo_ms,
        "isl": isl,
        "coloc": coloc,
        "static_baseline": agg,
        "prefill_ratio": round(
            coloc["prefill_tok_per_s"] / max(agg["prefill_tok_per_s"], 1e-9),
            3,
        ),
    }


async def _run_quant() -> dict:
    """Quantized-KV A/B (ci.sh BENCH_QUANT=1; ROADMAP #3 raw-bandwidth
    item; docs/architecture/kv_quant.md): long-context decode through
    (a) an int8-KV unified engine and (b) the bf16 baseline, priced by
    the mocker's decode HBM-bytes term CALIBRATED to the r04 recording's
    282.8 GB/s effective decode bandwidth (older harness, not reproduced)
    (planner/calibration.py DECODE_HBM_GBPS). The int8 leg gets the
    SAME simulated HBM KV byte budget — which fits ~2× the blocks, so
    it runs 2× the decode lanes — and its per-lane KV reads stream at
    the packed int8 ratio (~0.502 of bf16 bytes). Hard asserts:

    - int8 decode throughput ≥ 1.5× the bf16 leg's tok/s/chip;
    - EQUAL SLO: both legs' engine-side decode ITL p95 within
      ``BENCH_QUANT_SLO_MS``;
    - zero mid-traffic compiles and warmup ≤ 8 programs per leg
      (quantization only changes dtypes inside the budget ladder).

    Prefill constants are deliberately cheap (2 µs/token): the gate
    measures the DECODE phase (engine decode-token counters between
    all-lanes-decoding and completion), and pricing prefill at chip
    rates would only slow CI without touching the gated quantity.
    """
    import dataclasses

    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.llm.protocols.common import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )
    from dynamo_tpu.mocker import MockerConfig, MockerEngine
    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.planner import calibration as cal
    from dynamo_tpu.runtime.engine import Context

    slo_ms = float(os.environ.get("BENCH_QUANT_SLO_MS", 25.0))
    isl = _env_int("BENCH_QUANT_ISL", 2048)
    # OSL long enough that decode outlives the staggered prefill span:
    # the gate's window is [last lane's TTFT, first lane's completion],
    # when EVERY lane is decoding — an empty window hard-fails below.
    osl = _env_int("BENCH_QUANT_OSL", 150)
    lanes_bf16 = _env_int("BENCH_QUANT_LANES", 24)
    blocks_bf16 = 3328
    ratio = cal.kv_quant_bytes_ratio()           # ~0.502 (1B layout)
    # Equal HBM budget: the int8 leg spends the SAME KV bytes on ~2×
    # the blocks, and fills them with 2× the decode lanes.
    blocks_int8 = int(blocks_bf16 / ratio)

    base_cfg = EngineConfig(
        model=ModelConfig.tiny_test(),
        block_size=16,
        max_model_len=4096,
        prefill_batch=4,
        dtype="float32",
        sampling_extras=False,
        unified_token_budget=1024,
        unified_prefill_quantum=256,
        coloc="static",
        itl_slo_ms=slo_ms,  # measurement only (static mode): ITL p95
    )

    async def leg(kv_quant: str | None) -> dict:
        lanes = lanes_bf16 * 2 if kv_quant else lanes_bf16
        cfg = dataclasses.replace(
            base_cfg,
            kv_quant=kv_quant,
            num_blocks=blocks_int8 if kv_quant else blocks_bf16,
            max_num_seqs=lanes,
        )
        sim = MockerConfig(
            prefill_time_per_token_us=2.0,
            prefill_quadratic_us=0.0,
            decode_time_per_step_us=cal.DECODE_TIME_PER_STEP_US,
            decode_time_per_lane_us=cal.DECODE_TIME_PER_LANE_US,
            decode_hbm_gbps=cal.DECODE_HBM_GBPS,
            kv_bytes_per_token=cal.KV_BYTES_PER_TOKEN,
            kv_bytes_ratio=ratio if kv_quant else 1.0,
            vocab_size=base_cfg.model.vocab_size,
        )
        snap: dict = {}
        eng = MockerEngine(cfg, sim, on_metrics=snap.update)
        await eng.start()
        await eng.warmup()
        rng = np.random.default_rng(11)
        firsts: list[float] = []
        done_at: list[float] = []

        async def one():
            req = PreprocessedRequest(
                token_ids=rng.integers(
                    0, cfg.model.vocab_size, isl
                ).tolist(),
                sampling=SamplingOptions(temperature=0.0),
                stop=StopConditions(max_tokens=osl, ignore_eos=True),
            )
            first = None
            async for out in eng.generate(Context(req.to_wire())):
                if out["token_ids"] and first is None:
                    first = time.monotonic()
                    firsts.append(first)
            done_at.append(time.monotonic())

        # Decode-phase window: engine decode-token counter deltas over
        # [last lane's TTFT, first lane's completion] — the span where
        # every lane decodes, so neither prefill stragglers nor the
        # drain tail dilute the measured steady-state decode rate.
        tasks = [asyncio.create_task(one()) for _ in range(lanes)]
        while len(firsts) < lanes:
            await asyncio.sleep(0.01)
        await asyncio.sleep(0.05)  # one metrics flush past the last TTFT
        t0 = time.monotonic()
        d0 = snap.get("unified_step_tokens_decode_total", 0)
        while not done_at:
            await asyncio.sleep(0.01)
        t1 = time.monotonic()
        d1 = snap.get("unified_step_tokens_decode_total", 0)
        await asyncio.gather(*tasks)
        coloc = dict(eng.coloc.snapshot())
        cs = eng.runner.compile_stats
        warm = cs.snapshot()
        await eng.stop()
        if t1 - t0 < 0.2 or d1 <= d0:
            raise RuntimeError(
                f"all-lanes decode window too short ({t1 - t0:.3f}s, "
                f"{d1 - d0} tokens) — raise BENCH_QUANT_OSL so decode "
                "outlives the prefill span"
            )
        decode_tokens = d1 - d0
        return {
            "kv_quant": kv_quant or "bf16",
            "lanes": lanes,
            "num_blocks": cfg.num_blocks,
            "decode_tok_per_s": round(decode_tokens / max(t1 - t0, 1e-9), 1),
            "itl_p95_ms": coloc["itl_p95_ms"],
            "mid_traffic_compiles": cs.mid_traffic_compiles,
            "warmup_programs": warm.get("warmup_programs_total", 0),
        }

    int8 = await leg("int8")
    bf16 = await leg(None)
    ratio_tok = int8["decode_tok_per_s"] / max(bf16["decode_tok_per_s"], 1e-9)
    for name, r in (("int8", int8), ("bf16", bf16)):
        if r["mid_traffic_compiles"]:
            raise RuntimeError(
                f"{name} leg paid {r['mid_traffic_compiles']} mid-traffic "
                "compile(s) — quantization must not leave the warmed "
                "budget ladder"
            )
        if r["warmup_programs"] > 8:
            raise RuntimeError(
                f"{name} leg warmed {r['warmup_programs']} programs "
                "(> 8) — the unified budget ladder grew"
            )
        if r["itl_p95_ms"] > slo_ms:
            raise RuntimeError(
                f"{name} leg decode ITL p95 {r['itl_p95_ms']} ms violates "
                f"the shared {slo_ms} ms SLO — the legs are not at equal "
                "SLO and the throughput ratio is not comparable"
            )
    if ratio_tok < 1.5:
        raise RuntimeError(
            f"int8 decode {int8['decode_tok_per_s']} tok/s is only "
            f"{ratio_tok:.2f}x bf16's {bf16['decode_tok_per_s']} — "
            "the quantized path must deliver >= 1.5x at equal SLO"
        )
    return {
        "slo_ms": slo_ms,
        "isl": isl,
        "osl": osl,
        "hbm_gbps": cal.DECODE_HBM_GBPS,
        "kv_bytes_ratio_int8": round(ratio, 4),
        "int8": int8,
        "bf16": bf16,
        "decode_ratio": round(ratio_tok, 3),
    }


def wquant_equal_budget(
    blocks_bf16: int,
    lanes_bf16: int,
    wratio: float,
    tokens_per_lane: int,
    block_size: int = 16,
) -> tuple[int, int]:
    """Equal simulated-HBM-budget lane math for the BENCH_WQUANT A/B
    (unit-gated by tests/test_weight_quant.py): the shared budget is the
    bf16 leg's weight bytes PLUS its KV bytes; the quantized-weights leg
    spends ``wratio`` of the weight bytes and converts every byte it
    frees into KV blocks — and decode lanes scale with the blocks,
    capped so every lane's full ``tokens_per_lane`` sequence fits
    simultaneously (oversubscribing blocks would serialize lanes and
    collapse the all-lanes-decoding measurement window). Returns
    (blocks, lanes) for the quantized leg."""
    import math

    from dynamo_tpu.planner import calibration as cal

    kv_block_bytes = cal.KV_BYTES_PER_TOKEN * block_size
    budget = cal.WEIGHT_BYTES_PER_STEP + blocks_bf16 * kv_block_bytes
    kv_budget = budget - cal.WEIGHT_BYTES_PER_STEP * wratio
    blocks = int(kv_budget // kv_block_bytes)
    blocks_per_lane = math.ceil(tokens_per_lane / block_size)
    lanes = min(
        round(lanes_bf16 * blocks / blocks_bf16),
        blocks // blocks_per_lane,
    )
    return blocks, lanes


async def _run_wquant() -> dict:
    """Quantized-weights A/B (ci.sh BENCH_WQUANT=1; docs/architecture/
    weight_quant.md): long-context decode through (a) an int8-weights
    unified engine and (b) the bf16-weights baseline at the SAME
    simulated HBM byte budget — weight bytes + KV bytes. The quantized
    leg's weight pass streams at the packed ratio (~0.501 of bf16
    bytes, planner/calibration.py weight_quant_bytes_ratio) and every
    byte it frees becomes KV blocks, so it runs ~1.9x the decode lanes
    (bench.wquant_equal_budget). Both legs keep bf16 KV — this gate
    isolates the WEIGHT precision axis; kv_quant composes on top.
    Pricing: the r04-calibrated weight-bytes term (calibration.py
    WEIGHT_BYTES_PER_STEP / DECODE_HBM_GBPS — the same artifact the
    mocker's flat decode base was re-derived from). Hard asserts:

    - int8-weights decode throughput >= 1.3x the bf16 leg's tok/s/chip;
    - EQUAL SLO: both legs' engine-side decode ITL p95 within
      ``BENCH_WQUANT_SLO_MS``;
    - zero mid-traffic compiles and warmup <= 8 programs per leg (the
      policy is value-level — zero new XLA programs).

    Prefill constants are deliberately cheap (2 µs/token), as in the
    kv_quant gate: the measured quantity is the decode phase.
    """
    import dataclasses

    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.llm.protocols.common import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )
    from dynamo_tpu.mocker import MockerConfig, MockerEngine
    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.planner import calibration as cal
    from dynamo_tpu.runtime.engine import Context

    slo_ms = float(os.environ.get("BENCH_WQUANT_SLO_MS", 25.0))
    isl = _env_int("BENCH_WQUANT_ISL", 2048)
    # OSL long enough that decode outlives the staggered prefill span
    # (the gate's window is [last lane's TTFT, first completion]).
    osl = _env_int("BENCH_WQUANT_OSL", 150)
    lanes_bf16 = _env_int("BENCH_WQUANT_LANES", 24)
    blocks_bf16 = 3328
    wratio = cal.weight_quant_bytes_ratio()      # ~0.501 (int8 + f32 row)
    blocks_wq, lanes_wq = wquant_equal_budget(
        blocks_bf16, lanes_bf16, wratio, tokens_per_lane=isl + osl
    )

    base_cfg = EngineConfig(
        model=ModelConfig.tiny_test(),
        block_size=16,
        max_model_len=4096,
        prefill_batch=4,
        dtype="float32",
        sampling_extras=False,
        unified_token_budget=1024,
        unified_prefill_quantum=256,
        coloc="static",
        itl_slo_ms=slo_ms,  # measurement only (static mode): ITL p95
    )

    async def leg(weight_quant: str | None) -> dict:
        cfg = dataclasses.replace(
            base_cfg,
            weight_quant=weight_quant,
            num_blocks=blocks_wq if weight_quant else blocks_bf16,
            max_num_seqs=lanes_wq if weight_quant else lanes_bf16,
        )
        lanes = cfg.max_num_seqs
        sim = MockerConfig(
            prefill_time_per_token_us=2.0,
            prefill_quadratic_us=0.0,
            decode_time_per_step_us=cal.DECODE_TIME_PER_STEP_US,
            decode_time_per_lane_us=cal.DECODE_TIME_PER_LANE_US,
            decode_hbm_gbps=cal.DECODE_HBM_GBPS,
            kv_bytes_per_token=cal.KV_BYTES_PER_TOKEN,
            kv_bytes_ratio=1.0,                  # bf16 KV on BOTH legs
            weight_bytes_per_step=cal.WEIGHT_BYTES_PER_STEP,
            weight_bytes_ratio=wratio if weight_quant else 1.0,
            vocab_size=base_cfg.model.vocab_size,
        )
        snap: dict = {}
        eng = MockerEngine(cfg, sim, on_metrics=snap.update)
        await eng.start()
        await eng.warmup()
        rng = np.random.default_rng(11)
        firsts: list[float] = []
        done_at: list[float] = []

        async def one():
            req = PreprocessedRequest(
                token_ids=rng.integers(
                    0, cfg.model.vocab_size, isl
                ).tolist(),
                sampling=SamplingOptions(temperature=0.0),
                stop=StopConditions(max_tokens=osl, ignore_eos=True),
            )
            first = None
            async for out in eng.generate(Context(req.to_wire())):
                if out["token_ids"] and first is None:
                    first = time.monotonic()
                    firsts.append(first)
            done_at.append(time.monotonic())

        # Decode-phase window: engine decode-token counter deltas over
        # [last lane's TTFT, first lane's completion] — the span where
        # every lane decodes (same law as the kv_quant gate).
        tasks = [asyncio.create_task(one()) for _ in range(lanes)]
        while len(firsts) < lanes:
            await asyncio.sleep(0.01)
        await asyncio.sleep(0.05)  # one metrics flush past the last TTFT
        t0 = time.monotonic()
        d0 = snap.get("unified_step_tokens_decode_total", 0)
        while not done_at:
            await asyncio.sleep(0.01)
        t1 = time.monotonic()
        d1 = snap.get("unified_step_tokens_decode_total", 0)
        await asyncio.gather(*tasks)
        coloc = dict(eng.coloc.snapshot())
        cs = eng.runner.compile_stats
        warm = cs.snapshot()
        await eng.stop()
        if t1 - t0 < 0.2 or d1 <= d0:
            raise RuntimeError(
                f"all-lanes decode window too short ({t1 - t0:.3f}s, "
                f"{d1 - d0} tokens) — raise BENCH_WQUANT_OSL so decode "
                "outlives the prefill span"
            )
        decode_tokens = d1 - d0
        return {
            "weight_quant": weight_quant or "bf16",
            "lanes": lanes,
            "num_blocks": cfg.num_blocks,
            "decode_tok_per_s": round(decode_tokens / max(t1 - t0, 1e-9), 1),
            "itl_p95_ms": coloc["itl_p95_ms"],
            "mid_traffic_compiles": cs.mid_traffic_compiles,
            "warmup_programs": warm.get("warmup_programs_total", 0),
        }

    wq = await leg("int8")
    bf16 = await leg(None)
    ratio_tok = wq["decode_tok_per_s"] / max(bf16["decode_tok_per_s"], 1e-9)
    for name, r in (("int8-weights", wq), ("bf16", bf16)):
        if r["mid_traffic_compiles"]:
            raise RuntimeError(
                f"{name} leg paid {r['mid_traffic_compiles']} mid-traffic "
                "compile(s) — the weight-quant policy must not leave the "
                "warmed budget ladder"
            )
        if r["warmup_programs"] > 8:
            raise RuntimeError(
                f"{name} leg warmed {r['warmup_programs']} programs "
                "(> 8) — the unified budget ladder grew"
            )
        if r["itl_p95_ms"] > slo_ms:
            raise RuntimeError(
                f"{name} leg decode ITL p95 {r['itl_p95_ms']} ms violates "
                f"the shared {slo_ms} ms SLO — the legs are not at equal "
                "SLO and the throughput ratio is not comparable"
            )
    if ratio_tok < 1.3:
        raise RuntimeError(
            f"int8-weights decode {wq['decode_tok_per_s']} tok/s is only "
            f"{ratio_tok:.2f}x bf16's {bf16['decode_tok_per_s']} — "
            "the quantized-weights path must deliver >= 1.3x at equal "
            "simulated HBM budget"
        )
    return {
        "slo_ms": slo_ms,
        "isl": isl,
        "osl": osl,
        "hbm_gbps": cal.DECODE_HBM_GBPS,
        "weight_bytes_ratio_int8": round(wratio, 4),
        "weight_bytes_per_step": cal.WEIGHT_BYTES_PER_STEP,
        "int8_weights": wq,
        "bf16": bf16,
        "decode_ratio": round(ratio_tok, 3),
    }


def OVERLOAD_SHED_SNAPSHOT() -> int:
    from dynamo_tpu.utils.deadline import OVERLOAD

    return OVERLOAD.shed_total


def main() -> None:
    if os.environ.get("BENCH_CHAOS"):
        # Self-healing-fleet proof (docs/architecture/failure_model.md
        # "Mid-stream failover"): a seeded randomized chaos schedule —
        # mid-stream worker kills, a bus partition, dropped KV frames —
        # over a >=4-worker mocker fleet. HARD-FAILS unless every
        # request resolves (zero hangs under the watchdog), failover
        # succeeds whenever healthy capacity remains, greedy streams
        # stay byte-identical across kills, and the planner's crash
        # path heals the fleet back to target size.
        from benchmarks.chaos_bench import run_chaos, run_gates

        report = asyncio.run(run_chaos(
            seed=int(os.environ.get("BENCH_CHAOS_SEED", 1234)),
            decode_workers=_env_int("BENCH_CHAOS_WORKERS", 4),
            requests=_env_int("BENCH_CHAOS_REQUESTS", 24),
        ))
        print(
            json.dumps(
                {
                    "metric": "chaos_fleet_mocker",
                    "value": report["failover_success_total"],
                    "unit": (
                        f"successful mid-stream failovers "
                        f"({report['ok']}/{report['requests']} requests "
                        "ok, fleet healed to target)"
                    ),
                    "extras": report,
                }
            )
        )
        run_gates(report)
        return
    if os.environ.get("BENCH_G4"):
        # G4 peer-tier proof (docs/architecture/kvbm_g4.md): a cold
        # worker PULLS a fleet peer's packed KV rows instead of
        # recomputing them (priced against planner/calibration's
        # recorded link), pre-placement warms a joining worker before
        # traffic reaches it, and a peer killed mid-pull degrades to
        # local recompute. HARD-FAILS unless the pulled TTFT beats
        # recompute >=2x at the calibrated link rate, the pre-placed
        # join reaches steady-state warm-hit rate >=2x faster (in
        # requests) than the cold join, and the mid-pull kill completes
        # byte-identically via recompute with zero hangs.
        from benchmarks.g4_bench import run_g4, run_gates as g4_gates

        report = asyncio.run(run_g4(
            seed=int(os.environ.get("BENCH_G4_SEED", 20260806)),
            prefixes=_env_int("BENCH_G4_PREFIXES", 8),
            join_requests=_env_int("BENCH_G4_REQUESTS", 24),
        ))
        failures = g4_gates(report)
        print(
            json.dumps(
                {
                    "metric": "g4_peer_tier_mocker",
                    "value": report["pull"]["speedup"],
                    "unit": (
                        "x TTFT (pull vs recompute, calibrated link; "
                        f"pre-placed join "
                        f"{report['preplace']['speedup']}x faster to "
                        "steady state, mid-pull peer kill degraded "
                        "cleanly)"
                    ),
                    "extras": report,
                }
            )
        )
        if failures:
            print(
                "BENCH FAILED: G4 gates:\n  " + "\n  ".join(failures),
                file=sys.stderr,
            )
            raise SystemExit(1)
        return
    if os.environ.get("BENCH_INTEGRITY"):
        # End-to-end KV-block integrity proof (docs/architecture/
        # integrity.md): a seeded randomized corruption schedule at all
        # five trust-boundary seams — G2 onboard, G3 read/scrub, G4
        # pull, disagg tcp, disagg native — across multiple seeds.
        # HARD-FAILS unless every injected corruption is detected and
        # attributed to the right tier, every request resolves through
        # degrade-to-recompute with ZERO stream deviations from the
        # deterministic closed form, and the envelope's measured CRC
        # cost stays under 2% of serve wall time.
        from benchmarks.chaos_bench import run_integrity, run_integrity_gates

        base = int(os.environ.get("BENCH_INTEGRITY_SEED", 20260806))
        n_seeds = _env_int("BENCH_INTEGRITY_SEEDS", 3)
        reports, failures = [], []
        for s in range(base, base + n_seeds):
            report = asyncio.run(run_integrity(seed=s))
            reports.append(report)
            failures += [f"seed {s}: {f}" for f in run_integrity_gates(report)]
        detected = sum(
            r[leg]["detected"]
            for r in reports
            for leg in (
                "host_onboard", "disk_scrub", "peer_pull",
                "disagg_tcp", "disagg_native",
            )
        )
        print(
            json.dumps(
                {
                    "metric": "kv_integrity_mocker",
                    "value": detected,
                    "unit": (
                        f"corruptions detected across {n_seeds} seed(s) "
                        "x 5 seams (zero stream deviations, overhead "
                        f"{reports[-1]['overhead']['overhead_fraction']:.4%}"
                        " of serve time)"
                    ),
                    "extras": {"seeds": reports},
                }
            )
        )
        if failures:
            print(
                "BENCH FAILED: integrity gates:\n  " + "\n  ".join(failures),
                file=sys.stderr,
            )
            raise SystemExit(1)
        return
    if os.environ.get("BENCH_INGRESS"):
        # Million-user ingress replay (docs/architecture/
        # ingress_scale.md; ROADMAP #4): >=100k requests of a Mooncake-
        # style trace through >=2 router replicas over >=8 mocker
        # workers, with a mid-replay replica kill + rejoin and an
        # overload burst. HARD-FAILS unless zero requests are lost or
        # hung through the kill, per-class p99 TTFT holds its SLO with
        # zero cross-class inversions, the burst sheds batch (not
        # interactive) with load-proportional Retry-After, rejoin
        # staleness is measured, and route_audit.py's predicted-vs-
        # actual error bound holds across ALL replicas.
        from benchmarks.ingress_bench import run_gates as ingress_gates
        from benchmarks.ingress_bench import run_ingress

        report = asyncio.run(run_ingress(
            requests=_env_int("BENCH_INGRESS_REQUESTS", 100_000),
            workers=_env_int("BENCH_INGRESS_WORKERS", 8),
            replicas=_env_int("BENCH_INGRESS_REPLICAS", 2),
            seed=int(os.environ.get("BENCH_INGRESS_SEED", 20260805)),
        ))
        failures = ingress_gates(report)
        # The full prefix curve + staleness series are bulky; keep the
        # one-line metric digestible and ship the full report as extras.
        print(
            json.dumps(
                {
                    "metric": "ingress_replay_mocker",
                    "value": report["requests"],
                    "unit": (
                        f"requests replayed over {report['replicas']} "
                        f"router replicas / {report['workers']} workers "
                        f"(interactive p99 TTFT "
                        f"{report['ttft_p99_ms']['interactive']} ms, "
                        f"{report['burst'].get('batch_shed', 0)} batch "
                        "429s absorbed)"
                    ),
                    "extras": report,
                }
            )
        )
        if failures:
            print(
                "BENCH FAILED: ingress gates:\n  " + "\n  ".join(failures),
                file=sys.stderr,
            )
            raise SystemExit(1)
        return
    if os.environ.get("BENCH_XPYD"):
        # Fleet projection (ROADMAP #4): the calibrated-mocker xPyD
        # simulation (planner/simulate.py, constants pinned to the
        # recorded r04/r05 runs by planner/calibration.py). HARD-FAILS
        # unless the calibration reproduces the r04 headline within
        # 10%, the 2P1D topology beats the 1-worker aggregated baseline
        # on the prefill-heavy replay, and a decode scale-down mid-run
        # drops zero requests.
        from benchmarks.xpyd_bench import run_gates

        report = run_gates()
        print(
            json.dumps(
                {
                    "metric": "xpyd_projection",
                    "value": report["headline_ratio"],
                    "unit": (
                        "x (2P1D over equal-chip SLO-holding co-located "
                        "fleet, calibrated-mocker sim)"
                    ),
                    "extras": report,
                }
            )
        )
        if not all(report["gates"].values()):
            print(
                f"BENCH FAILED: xPyD gates {report['gates']}",
                file=sys.stderr,
            )
            raise SystemExit(1)
        return
    if os.environ.get("BENCH_ROUTE_AUDIT"):
        # KV-observatory leg: multi-worker mocker behind the KV-aware
        # router with the trace capture on. Hard-fails unless every
        # request is routed+audited, the hit-rate plane carries both
        # predicted and actual kinds, the indexer measured event lag,
        # and follow-up turns actually reused KV. ci.sh then closes the
        # loop with benchmarks/route_audit.py --assert on the capture.
        r = asyncio.run(_run_route_audit())
        print(
            json.dumps(
                {
                    "metric": "route_audit_mocker",
                    "value": r["turn2_with_predicted_overlap"],
                    "unit": (
                        f"of {r['sessions']} follow-ups routed with "
                        "predicted overlap (loop closed by route_audit.py)"
                    ),
                    "extras": r,
                }
            )
        )
        return
    if os.environ.get("BENCH_QUANT"):
        # Quantized-KV A/B (docs/architecture/kv_quant.md): int8 KV at
        # the SAME simulated HBM byte budget must deliver >= 1.5x the
        # bf16 leg's decode tok/s/chip at equal ITL SLO, with zero
        # mid-traffic compiles and the unchanged <= 8-program budget
        # ladder. Pricing: the r04-calibrated decode HBM-bytes term.
        r = asyncio.run(_run_quant())
        print(
            json.dumps(
                {
                    "metric": "kv_quant_ab_mocker",
                    "value": r["decode_ratio"],
                    "unit": (
                        "x (int8 decode tok/s/chip over bf16 at equal "
                        "SLO, r04-calibrated HBM pricing)"
                    ),
                    "extras": r,
                }
            )
        )
        return
    if os.environ.get("BENCH_WQUANT"):
        # Quantized-weights A/B (docs/architecture/weight_quant.md):
        # int8 weights at the SAME simulated HBM byte budget (weight
        # bytes + KV bytes) convert the freed weight HBM into KV lanes
        # and must deliver >= 1.3x the bf16 leg's decode tok/s/chip at
        # equal ITL SLO, with zero mid-traffic compiles and the
        # unchanged <= 8-program budget ladder. Pricing: the
        # r04-calibrated weight-bytes term.
        r = asyncio.run(_run_wquant())
        print(
            json.dumps(
                {
                    "metric": "wquant_ab_mocker",
                    "value": r["decode_ratio"],
                    "unit": (
                        "x (int8-weights decode tok/s/chip over bf16 at "
                        "equal simulated HBM budget and SLO, "
                        "r04-calibrated weight-bytes pricing)"
                    ),
                    "extras": r,
                }
            )
        )
        return
    if os.environ.get("BENCH_SPEC"):
        # Unified speculative-decode A/B (ROADMAP #2's last leg):
        # accepting-draft spec throughput must beat both the unified
        # non-spec leg and the recorded phased-spec baseline, warmup
        # must stay within the budget ladder (spec adds zero programs),
        # and the auto-gate must stay free-when-losing. Hard-fails
        # otherwise.
        r = asyncio.run(_run_spec())
        print(
            json.dumps(
                {
                    "metric": "spec_ab_mocker",
                    "value": r["speedup_vs_plain"],
                    "unit": (
                        "x (unified spec tok/s over unified non-spec at "
                        "accepting-draft settings; "
                        f"{r['speedup_vs_phased_spec']}x over the "
                        "recorded phased-spec baseline)"
                    ),
                    "extras": r,
                }
            )
        )
        return
    if os.environ.get("BENCH_COLOC"):
        # Co-location A/B (ROADMAP #3): co-located unified serving must
        # hold decode ITL p95 within the SLO through an ISL3000-style
        # prefill burst while matching the aggregated baseline's
        # prefill throughput. Hard-fails otherwise.
        r = asyncio.run(_run_coloc())
        print(
            json.dumps(
                {
                    "metric": "coloc_ab_mocker",
                    "value": r["prefill_ratio"],
                    "unit": (
                        "x (co-located prefill tok/s over aggregated, "
                        "decode ITL p95 held within SLO)"
                    ),
                    "extras": r,
                }
            )
        )
        return
    if os.environ.get("BENCH_OVERLOAD"):
        # Overload-safety smoke: offered load >> capacity must shed with
        # 429 + Retry-After, zero hangs, bounded admitted latency.
        r = asyncio.run(_run_overload())
        print(
            json.dumps(
                {
                    "metric": "overload_smoke",
                    "value": r["shed_429"],
                    "unit": "requests shed with 429 (offered >> capacity)",
                    "extras": r,
                }
            )
        )
        return
    if os.environ.get("BENCH_KVSP"):
        # kv_sp striped-scan scaling microbench (benchmarks/kv_sp_bench.py)
        from benchmarks.kv_sp_bench import main as kvsp_main

        print(json.dumps(kvsp_main()))
        return
    if os.environ.get("BENCH_ROUTER"):
        # KV-aware vs random routing A/B (benchmarks/router_bench.py;
        # reference bar: 3x TTFT, architecture.md:86-91)
        from benchmarks.router_bench import main as router_main

        print(json.dumps(router_main()))
        return
    if os.environ.get("BENCH_OFFLOAD"):
        # Host-DRAM KV offload A/B (benchmarks/offload_bench.py; reference
        # bar: +40% TTFT, architecture.md:95-99)
        from benchmarks.offload_bench import main as offload_main

        print(json.dumps(offload_main()))
        return
    if os.environ.get("BENCH_DISAGG"):
        r = asyncio.run(_run_disagg())
        print(
            json.dumps(
                {
                    "metric": f"disagg_vs_agg_isl{ISL}_osl{OSL}",
                    "value": r["ratio_tok_per_s"],
                    "unit": "x (disagg tok/s over aggregated; ref bar +30% multi-node)",
                    "vs_baseline": r["ratio_tok_per_s"],
                    "extras": r,
                }
            )
        )
        return
    ab = None
    if os.environ.get("BENCH_AB"):
        ab = _run_ab("DYNAMO_TPU_PALLAS", [("pallas", "1"), ("jnp", "0")])
    elif os.environ.get("BENCH_QUANT_AB"):
        ab = _run_ab("DYNAMO_TPU_QUANT", [("int8", "int8"), ("bf16", "")])
    elif os.environ.get("BENCH_SPEC_AB"):
        # Speculative decode A/B: same scenario with
        # prompt-lookup drafting (auto-gated) vs plain decode.
        ab = _run_ab("BENCH_SPEC_K", [("spec4", "4"), ("plain", "0")])
    if ab is not None:
        win = max(ab, key=lambda k: ab[k]["value"])
        result = dict(ab[win])
        result["extras"] = dict(result.get("extras", {}))
        result["extras"]["ab"] = {
            k: {
                "tok_per_s": v["value"],
                "p50_ttft_ms": v["extras"]["p50_ttft_ms"],
            }
            for k, v in ab.items()
        }
        result["extras"]["ab_winner"] = win
        print(json.dumps(result))
        return

    r = asyncio.run(_run_e2e())
    print(
        json.dumps(
            {
                "metric": ("decode_throughput_mocker_smoke" if MOCKER
                           else "decode_throughput_tiny_smoke")
                if SMOKE or MOCKER
                else (
                    "decode_throughput_"
                    + {"llama32_1b": "1b", "llama31_8b": "8b"}.get(
                        os.environ.get("BENCH_MODEL", "llama32_1b"),
                        os.environ.get("BENCH_MODEL", "model"),
                    )
                    + f"_isl{ISL}_osl{OSL}"
                ),
                "value": r["tok_per_s"],
                "unit": "tok/s/chip",
                "vs_baseline": round(r["tok_per_s"] / 100.0, 3),
                "extras": {
                    k: v for k, v in r.items() if k != "tok_per_s"
                }
                | {"num_requests": NUM_REQ, "isl": ISL, "osl": OSL}
                | (
                    {"trace_capture": os.environ["DYNTPU_TRACE"]}
                    if TRACE
                    else {}
                ),
            }
        )
    )


if __name__ == "__main__":
    main()
