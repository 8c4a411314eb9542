"""Unified single-dispatch serving (docs/architecture/unified_step.md):
token-budget batch composition, the budget-ladder warmup contract, the
runner's device feed and its one step-program family, and end-to-end
token parity against the no-cache oracle."""

import asyncio

import numpy as np
import pytest

from dynamo_tpu.engine.compile_cache import (
    budget_ladder,
    default_shape_grid,
    token_budget,
)
from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.engine.scheduler import compose_unified
from dynamo_tpu.llm.protocols.common import (
    PreprocessedRequest,
    RequestError,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.runtime.engine import Context

pytestmark = pytest.mark.anyio


# ---------------------------------------------------------------------------
# token budget + shape grid
# ---------------------------------------------------------------------------


def test_token_budget_snaps_to_ladder():
    assert token_budget(1, 256) == 16
    assert token_budget(16, 256) == 16
    assert token_budget(17, 256) == 32
    assert token_budget(100, 256) == 128
    assert token_budget(300, 256) == 256  # capped at the ladder top
    assert budget_ladder(256) == [16, 32, 64, 128, 256]


def test_unified_shape_grid_is_budget_ladder_only():
    """The unified grid IS the ladder (plus ONE top-rung program per
    configured variant) — no prefill buckets, no lane axis, no
    decode-chunk ladder. This is the delete-the-grid contract, and it
    holds with speculation enabled: the spec program IS the ladder."""
    cfg = EngineConfig(
        model=ModelConfig.tiny_test(), num_blocks=64, max_model_len=256,
        unified_token_budget=256, sampling_extras=False,
    )
    specs = default_shape_grid(cfg)
    assert specs == [("unified", b, 0, 0, 0) for b in (16, 32, 64, 128, 256)]
    assert len(specs) <= 8
    # Speculation adds ZERO programs — same ladder, spec-aware program.
    import dataclasses

    spec_cfg = dataclasses.replace(cfg, speculative_k=4)
    assert default_shape_grid(spec_cfg) == specs
    # Extras requests are rejected on spec engines, so the unified_full
    # program would be unreachable dead warmup weight there.
    spec_extras = dataclasses.replace(
        cfg, speculative_k=4, sampling_extras=True
    )
    assert default_shape_grid(spec_extras) == specs
    # Extras and multimodal each add exactly ONE top-rung program.
    full_cfg = dataclasses.replace(cfg, sampling_extras=True, multimodal=True)
    full = default_shape_grid(full_cfg)
    assert full == specs + [
        ("unified_full", 256, 0, 0, 0), ("unified_mm", 256, 0, 0, 0)
    ]
    assert len(full) <= 8


def test_config_validation_one_path():
    base = dict(model=ModelConfig.tiny_test(), num_blocks=64,
                max_model_len=256)
    for bad in (
        dict(unified_token_budget=8),
        dict(unified_prefill_quantum=0),
        dict(speculative_k=16, unified_token_budget=16),  # span > half
    ):
        with pytest.raises(ValueError):
            cfg = dict(base)
            cfg.update(bad)
            EngineConfig(**cfg).validate()
    EngineConfig(**base).validate()  # the plain combo is fine
    # Speculation and multimodal are FIRST-CLASS on the unified path now.
    EngineConfig(**base, speculative_k=4).validate()
    EngineConfig(**base, multimodal=True).validate()


def test_config_budget_clamps_to_reachable_rung():
    """A budget past the largest fillable batch CLAMPS down to the
    biggest reachable rung (with the quantum snapped inside it) instead
    of rejecting — the default budget must stay valid on tiny engines."""
    cfg = EngineConfig(
        model=ModelConfig.tiny_test(), num_blocks=64, max_num_seqs=2,
        max_model_len=32, prefill_batch=2, unified_token_budget=256,
        unified_prefill_quantum=200,
    )
    cfg.validate()
    assert cfg.unified_token_budget == 64  # (2+2)*31 = 124 → rung 64
    assert cfg.unified_prefill_quantum == 64


# ---------------------------------------------------------------------------
# batch composition (pure policy, no engine)
# ---------------------------------------------------------------------------


def test_compose_decode_first_fill():
    """Decode lanes admit first; remaining budget packs prefill quanta."""
    dec = [f"d{i}" for i in range(6)]
    pre = [("p0", 100), ("p1", 30)]
    decode_take, prefill_take = compose_unified(dec, pre, 64, 16)
    assert decode_take == dec  # all decode lanes fit
    assert prefill_take == [("p0", 16), ("p1", 16)]  # one quantum each


def test_compose_prefill_quantum_cap_lifts_when_alone():
    """A prefill-only batch may spend the whole budget on one prompt
    (pure TTFT); under co-location each prompt is quantum-capped."""
    _, alone = compose_unified([], [("p0", 500)], 64, 16)
    assert alone == [("p0", 64)]
    _, shared = compose_unified(["d0"], [("p0", 500)], 64, 16)
    assert shared == [("p0", 16)]


def test_compose_starvation_bounds():
    """A full decode population cannot starve prefill below one quantum,
    and prefill can never displace a decode lane that fits."""
    dec = [f"d{i}" for i in range(64)]
    decode_take, prefill_take = compose_unified(dec, [("p0", 100)], 64, 16)
    assert len(decode_take) == 48  # 64 - 16 reserved
    assert prefill_take == [("p0", 16)]  # prefill always progresses
    # no prefill work -> decode takes the whole budget
    decode_take, prefill_take = compose_unified(dec, [], 64, 16)
    assert len(decode_take) == 64 and prefill_take == []
    # reserve never exceeds the actual prefill demand
    decode_take, prefill_take = compose_unified(dec, [("p0", 3)], 64, 16)
    assert len(decode_take) == 61 and prefill_take == [("p0", 3)]
    # quantum == budget must NOT zero decode out: the reserve is capped
    # so decode keeps at least half the budget (or all it needs).
    decode_take, prefill_take = compose_unified(dec, [("p0", 500)], 64, 64)
    assert len(decode_take) == 32
    assert prefill_take == [("p0", 32)]
    decode_take, prefill_take = compose_unified(
        dec[:2], [("p0", 500)], 64, 64
    )
    assert len(decode_take) == 2  # small decode population fully fits
    assert prefill_take == [("p0", 62)]


def test_compose_budget_exhaustion_stops_packing():
    dec = ["d0", "d1"]
    pre = [("p0", 40), ("p1", 40), ("p2", 40)]
    decode_take, prefill_take = compose_unified(dec, pre, 32, 16)
    assert decode_take == dec
    # 30 tokens left: one full quantum + a truncated one; p2 waits.
    assert prefill_take == [("p0", 16), ("p1", 14)]


# ---------------------------------------------------------------------------
# engine end-to-end (mocker: warmup contract; real engine: token parity)
# ---------------------------------------------------------------------------


def _engine_cfg(**kw) -> EngineConfig:
    return EngineConfig(
        model=ModelConfig.tiny_test(), num_blocks=64, max_num_seqs=4,
        max_model_len=96, dtype="float32",
        unified_token_budget=64,
        unified_prefill_quantum=32, sampling_extras=False, **kw,
    )


async def test_mocker_unified_warmup_and_zero_midtraffic_compiles():
    """Unified mocker engine: warmup compiles exactly the budget ladder
    (≤ 8 programs), mixed traffic runs with ZERO mid-traffic compiles,
    and the unified metrics surface on the engine snapshot."""
    from dynamo_tpu.mocker import MockerConfig, MockerEngine

    cfg = EngineConfig(
        model=ModelConfig.tiny_test(), num_blocks=64, max_num_seqs=4,
        max_model_len=128,
        unified_token_budget=64, unified_prefill_quantum=16,
    )
    eng = MockerEngine(cfg, MockerConfig())
    metrics: list[dict] = []
    eng._on_metrics = metrics.append
    await eng.start()
    warmed = await eng.warmup()
    assert warmed <= 8
    # The ladder plus the single extras top-rung program
    # (sampling_extras defaults True).
    assert warmed == len(budget_ladder(cfg.unified_token_budget)) + 1
    rng = np.random.default_rng(0)

    async def run_one():
        req = PreprocessedRequest(
            token_ids=rng.integers(0, 1000, 40).tolist(),
            sampling=SamplingOptions(temperature=0.0),
            stop=StopConditions(max_tokens=8, ignore_eos=True),
        )
        n = 0
        async for out in eng.generate(Context(req.to_wire())):
            n += len(out["token_ids"])
        return n

    counts = await asyncio.gather(*[run_one() for _ in range(6)])
    assert counts == [8] * 6
    cs = eng.runner.compile_stats
    assert cs.mid_traffic_compiles == 0, cs.mid_traffic_keys
    assert cs.snapshot()["warmup_programs_total"] == warmed
    # Observability satellite: the split + fill ratio reach the metrics
    # callback and the readiness snapshot.
    assert eng._unified_prefill_tokens == 6 * 40
    assert eng._unified_decode_tokens > 0
    m = metrics[-1]
    assert "unified_step_tokens_decode_total" in m
    assert "batch_fill_ratio" in m
    r = eng.readiness()
    assert r["unified_step_tokens_prefill_total"] == 6 * 40
    await eng.stop()


async def test_unified_remote_prefill_uses_budget_programs_only():
    """A unified disagg PREFILL worker must serve remote-prefill batches
    through unified_step spans — never the phase-path prefill programs
    its warmup no longer compiles (that would be a mid-traffic compile
    per bucket, the r05 stall class)."""
    from dynamo_tpu.mocker import MockerConfig, MockerEngine

    cfg = EngineConfig(
        model=ModelConfig.tiny_test(), num_blocks=64, max_num_seqs=4,
        max_model_len=128,
        unified_token_budget=64, unified_prefill_quantum=16,
    )
    eng = MockerEngine(cfg, MockerConfig())
    await eng.start()
    await eng.warmup()
    rng = np.random.default_rng(2)
    items = [
        (
            PreprocessedRequest(
                token_ids=rng.integers(0, 1000, n).tolist(),
                sampling=SamplingOptions(temperature=0.0),
                stop=StopConditions(max_tokens=4, ignore_eos=True),
            ),
            f"rp-{i}",
            False,
        )
        for i, n in enumerate((90, 40))
    ]
    results = await asyncio.gather(*eng.prefill_only_batch(items))
    for (pre, _rid, _dev), res in zip(items, results):
        assert res is not None
        token, blocks = res
        assert isinstance(token, int)
        assert len(blocks) == -(-len(pre.token_ids) // cfg.block_size)
    cs = eng.runner.compile_stats
    assert cs.mid_traffic_compiles == 0, cs.mid_traffic_keys
    assert all(k.startswith("unified") for k in cs.seen), cs.seen
    await eng.stop()


async def test_unified_rejects_extras_only_when_disabled():
    """sampling_extras=False still 400-rejects penalties/logprobs; the
    default unified engine serves them (the extras port)."""
    from dynamo_tpu.mocker import MockerConfig, MockerEngine

    cfg = EngineConfig(
        model=ModelConfig.tiny_test(), num_blocks=64, max_num_seqs=4,
        max_model_len=128, sampling_extras=False,
    )
    eng = MockerEngine(cfg, MockerConfig())
    await eng.start()
    req = PreprocessedRequest(
        token_ids=[1, 2, 3],
        sampling=SamplingOptions(temperature=0.0, frequency_penalty=0.5),
        stop=StopConditions(max_tokens=4, ignore_eos=True),
    )
    with pytest.raises(RequestError):
        async for _ in eng.generate(Context(req.to_wire())):
            pass
    await eng.stop()


async def test_engine_spec_greedy_streams_byte_identical():
    """The tentpole regression gate (pre/post-port byte identity, REAL
    engine): greedy token streams through the unified step are
    byte-identical with speculative decoding ON and OFF — verification
    only ever keeps drafts the plain rollout would have produced, and
    gated-off spec traffic reduces to the exact plain program."""
    from dynamo_tpu.engine.engine import TpuEngine

    async def run(spec_k: int) -> list[list[int]]:
        eng = TpuEngine(_engine_cfg(speculative_k=spec_k))
        await eng.start()
        rng = np.random.default_rng(0)
        prompts = [
            rng.integers(0, 500, n).tolist() for n in (7, 19, 40, 12, 33)
        ]
        out = []
        for p in prompts:
            req = PreprocessedRequest(
                token_ids=p,
                sampling=SamplingOptions(temperature=0.0),
                stop=StopConditions(max_tokens=8, ignore_eos=True),
            )
            toks = []
            async for o in eng.generate(Context(req.to_wire())):
                toks.extend(o["token_ids"])
            out.append(toks)
        assert eng.runner.compile_stats.manifest.count_of("unified:t16")
        await eng.stop()
        return out

    plain = await run(0)
    spec = await run(3)
    assert spec == plain
    assert all(len(t) == 8 for t in plain)


async def test_engine_unified_mixed_concurrency_and_prefix_cache():
    """Concurrent mixed-length prompts (prefill quanta + decode lanes
    co-resident in single dispatches) all complete, and a repeated prompt
    takes the prefix-cache hit path through the unified step."""
    from dynamo_tpu.engine.engine import TpuEngine

    eng = TpuEngine(_engine_cfg())
    await eng.start()
    rng = np.random.default_rng(1)
    base = rng.integers(0, 500, 48).tolist()

    async def run_one(p, n=6):
        req = PreprocessedRequest(
            token_ids=p,
            sampling=SamplingOptions(temperature=0.0),
            stop=StopConditions(max_tokens=n, ignore_eos=True),
        )
        toks = []
        async for o in eng.generate(Context(req.to_wire())):
            toks.extend(o["token_ids"])
        return toks

    prompts = [base, rng.integers(0, 500, 9).tolist(),
               rng.integers(0, 500, 21).tolist()]
    first = await asyncio.gather(*[run_one(p) for p in prompts])
    assert all(len(t) == 6 for t in first)
    # Same prompt again: blocks registered by the first pass give a
    # prefix hit; the continuation must still decode identical tokens.
    again = await run_one(base)
    assert again == first[0]
    assert eng.prefix_hit_rate > 0
    await eng.stop()


# ---------------------------------------------------------------------------
# one step program: what a runner builds, and what is asked of it
# ---------------------------------------------------------------------------


def _tiny_runner():
    from dynamo_tpu.engine.runner import ModelRunner

    return ModelRunner(
        EngineConfig(
            model=ModelConfig.tiny_test(), num_blocks=16, max_num_seqs=2,
            max_model_len=32, dtype="float32",
        )
    )


def test_runner_builds_only_the_unified_programs():
    """A built ModelRunner holds exactly the unified family's jitted
    callables (plus the resident zero feed one small jit produced) — no
    phase-split prefill/decode program comes back unnoticed — and no
    public step entry beside unified_step."""
    import jax

    runner = _tiny_runner()
    jit_type = type(jax.jit(lambda: 0))
    jitted = {k for k, v in vars(runner).items() if isinstance(v, jit_type)}
    assert jitted == {"_unified", "_unified_full", "_unified_mm"}
    assert isinstance(runner._zero_prev, jax.Array)
    assert runner._zero_prev.shape == (runner.unified_slots,)
    for gone in ("prefill", "prefill_batch", "decode", "decode_multi",
                 "last_logprobs"):
        assert not hasattr(runner, gone), gone


def test_engine_and_stepcast_name_only_methods_the_runner_has():
    """Every ``self.runner.<name>`` the engine touches and every method
    stepcast replays exists on a built ModelRunner."""
    import ast
    import inspect

    from dynamo_tpu.engine import engine as engine_mod
    from dynamo_tpu.mocker.engine import _SimRunner
    from dynamo_tpu.parallel.stepcast import REPLAYED

    named = {
        node.attr
        for node in ast.walk(ast.parse(inspect.getsource(engine_mod)))
        if isinstance(node, ast.Attribute)
        and isinstance(node.value, ast.Attribute)
        and node.value.attr == "runner"
        and isinstance(node.value.value, ast.Name)
        and node.value.value.id == "self"
    }
    assert "unified_step" in named and len(named) > 5
    runner = _tiny_runner()
    missing = sorted(n for n in named | set(REPLAYED) if not hasattr(runner, n))
    assert not missing, missing
    # The mocker's double has the one step entry and none of the gone.
    assert hasattr(_SimRunner, "unified_step")
    for gone in ("prefill", "prefill_batch", "decode", "decode_multi"):
        assert not hasattr(_SimRunner, gone), gone
