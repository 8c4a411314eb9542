"""Compile-lifecycle subsystem tests (engine/compile_cache.py):

- shape-manifest roundtrip: record → save → load → warm-plan pruning,
  with fingerprint staleness guarding
- persistent-cache fingerprint namespacing + ledger persistence, and the
  second-cold-start speedup (counting stub — no TPU present)
- readiness gating: warmup_gate="hold" parks admission until the hot set
  is warm; "degraded" serves immediately and flags it
- mid-traffic-compile counter incrementing on an un-warmed shape, and
  staying zero on a warmed engine (real CPU runner)
- /health 503-while-warming + compile gauges on /metrics
"""

import asyncio
import os
import time

import numpy as np
import pytest

from dynamo_tpu.engine.compile_cache import (
    CompileStats,
    PersistentCompileCache,
    ShapeManifest,
    default_shape_grid,
    engine_fingerprint,
    fingerprint_key,
    shape_key,
    split_plan,
)
from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.llm.protocols.common import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.mocker.engine import MockerConfig, MockerEngine
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.runtime.engine import Context

pytestmark = pytest.mark.anyio


def _cfg(**kw) -> EngineConfig:
    defaults = dict(
        model=ModelConfig.tiny_test(),
        num_blocks=128,
        max_num_seqs=4,
        max_model_len=128,
        prefill_batch=4,
    )
    defaults.update(kw)
    return EngineConfig(**defaults)


def _req(n_prompt: int, max_tokens: int = 4) -> dict:
    return PreprocessedRequest(
        token_ids=list(range(1, n_prompt + 1)),
        sampling=SamplingOptions(temperature=0.0),
        stop=StopConditions(max_tokens=max_tokens, ignore_eos=True),
    ).to_wire()


async def _collect(engine, n_prompt: int, max_tokens: int = 4) -> int:
    n = 0
    async for out in engine.generate(Context(_req(n_prompt, max_tokens))):
        n += len(out["token_ids"])
    return n


# ---------------------------------------------------------------------------
# manifest
# ---------------------------------------------------------------------------


def test_manifest_roundtrip_and_fingerprint_guard(tmp_path):
    m = ShapeManifest()
    for _ in range(5):
        m.record("unified", t=128)
    m.record("unified", t=64)
    m.record("unified_full", t=128)
    path = str(tmp_path / "manifest.json")
    m.save(path, "fp-a")

    loaded = ShapeManifest.load(path, "fp-a")
    assert loaded is not None
    assert loaded.count_of(shape_key("unified", t=128)) == 5
    assert loaded.count_of(shape_key("unified_full", t=128)) == 1

    # A manifest written under a different engine fingerprint must be
    # ignored (stale shapes would warm the wrong programs).
    assert ShapeManifest.load(path, "fp-b") is None
    assert ShapeManifest.load(str(tmp_path / "missing.json"), "fp-a") is None


def test_split_plan_orders_unified_grid(tmp_path):
    """The unified grid: every budget rung is a decode-criticality shape
    (any running lane can land on any rung), so the WHOLE family stays
    hot under a manifest — its value is ORDERING: observed rungs warm
    first, by observed count."""
    cfg = _cfg()
    specs = default_shape_grid(cfg)
    keys = [shape_key(*s) for s in specs]
    assert all(k.startswith("unified") for k in keys)

    m = ShapeManifest()
    for _ in range(9):
        m.record("unified", t=64)
    m.record("unified", t=16)
    hot, tail = split_plan(specs, m)
    hot_keys = [shape_key(*s) for s in hot]
    # Everything stays hot (unified kinds are all decode-critical)...
    assert not tail
    assert set(hot_keys) == set(keys)
    # ...and the dominant observed rung warms before the rare one, which
    # warms before the never-observed rest of the ladder.
    assert hot_keys.index(shape_key("unified", t=64)) < hot_keys.index(
        shape_key("unified", t=16)
    )
    assert hot_keys.index(shape_key("unified", t=16)) < hot_keys.index(
        shape_key("unified", t=32)
    )


def test_fingerprint_tracks_compile_relevant_config():
    a = fingerprint_key(engine_fingerprint(_cfg()))
    assert a == fingerprint_key(engine_fingerprint(_cfg()))  # stable
    assert a != fingerprint_key(engine_fingerprint(_cfg(quant="int8")))
    assert a != fingerprint_key(engine_fingerprint(_cfg(max_num_seqs=8)))
    assert a != fingerprint_key(
        engine_fingerprint(_cfg(mesh_shape={"tp": 2}))
    )


# ---------------------------------------------------------------------------
# persistent cache
# ---------------------------------------------------------------------------


def test_cache_ledger_persists_per_fingerprint(tmp_path):
    base = str(tmp_path)
    fp_a = engine_fingerprint(_cfg())
    cache = PersistentCompileCache(base, fp_a)
    assert not cache.has("prefill:t64")
    cache.note("prefill:t64")
    cache.flush()
    # A new instance over the same dir (a relaunched process) sees it.
    again = PersistentCompileCache(base, fp_a)
    assert again.has("prefill:t64")
    assert again.num_ledger_entries == 1
    # A different fingerprint namespaces into a different directory.
    other = PersistentCompileCache(base, engine_fingerprint(_cfg(quant="int8")))
    assert other.dir != cache.dir
    assert not other.has("prefill:t64")


@pytest.fixture
def restore_jax_cache_config():
    """activate() flips process-global jax config: put it back so a
    tmp_path cache cannot outlive its test."""
    import jax

    names = (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes",
    )
    before = {n: getattr(jax.config, n) for n in names}
    yield
    for n, v in before.items():
        jax.config.update(n, v)


def test_cache_rule_env_var_places_everything(
    tmp_path, monkeypatch, restore_jax_cache_config
):
    """$JAX_COMPILATION_CACHE_DIR set: that directory whatever else was
    asked for, no cache-dir config call, ledger under it."""
    import jax

    from dynamo_tpu.engine.compile_cache import resolve_cache_base

    outside = str(tmp_path / "outside")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", outside)
    monkeypatch.delenv("DYNAMO_TPU_COMPILE_CACHE_DIR")
    before = jax.config.jax_compilation_cache_dir
    # Whatever base the rule or a caller hands over (an EngineConfig's
    # own compile_cache_dir, say), the cache object places itself.
    assert PersistentCompileCache(
        resolve_cache_base("auto"), engine_fingerprint(_cfg())
    ).base_dir == outside
    cache = PersistentCompileCache(
        resolve_cache_base(str(tmp_path / "explicit")),
        engine_fingerprint(_cfg()),
    )
    assert cache.base_dir == outside
    cache.activate()
    assert jax.config.jax_compilation_cache_dir == before
    cache.note("unified:t16")
    cache.flush()
    assert os.path.exists(
        os.path.join(outside, cache.key, PersistentCompileCache.LEDGER)
    )
    assert not (tmp_path / "explicit").exists()


def test_cache_rule_default_is_inside_checkout_and_none_disables(
    tmp_path, monkeypatch, restore_jax_cache_config
):
    import jax

    from dynamo_tpu.engine.compile_cache import resolve_cache_base

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    # The suite's own setting (tests/conftest.py) is the disable sentinel.
    assert os.environ["DYNAMO_TPU_COMPILE_CACHE_DIR"] == "none"
    assert resolve_cache_base("auto") is None
    assert resolve_cache_base("none") is None
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert resolve_cache_base("none") is None  # even placed from outside
    assert resolve_cache_base("auto") is None
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    monkeypatch.delenv("DYNAMO_TPU_COMPILE_CACHE_DIR")
    assert resolve_cache_base("auto") == os.path.join(repo, ".jax_cache")
    monkeypatch.setenv("DYNAMO_TPU_COMPILE_CACHE_DIR", str(tmp_path / "dep"))
    assert resolve_cache_base("auto") == str(tmp_path / "dep")
    explicit = str(tmp_path / "explicit")
    assert resolve_cache_base(explicit) == explicit
    # Unset, activate() is what points jax at the base.
    PersistentCompileCache(explicit, engine_fingerprint(_cfg())).activate()
    assert jax.config.jax_compilation_cache_dir == explicit


class _StubWarmRunner:
    """Counting stub standing in for XLA when no TPU is present: a shape
    whose key is in the persistent-cache ledger 'replays from disk'
    (fast); a fresh one 'compiles' (slow). Drives the real CompileStats /
    ledger machinery end to end."""

    COMPILE_S = 0.02
    REPLAY_S = 0.0005

    def __init__(self, cache: PersistentCompileCache) -> None:
        self.compile_stats = CompileStats(cache=cache)

    def warm(self, keys: list[str]) -> float:
        cs = self.compile_stats
        t0 = time.monotonic()
        cs.warming = True
        try:
            for key in keys:
                with cs.observe("stub", t=int(key)):
                    time.sleep(
                        self.REPLAY_S
                        if cs.cache.has(shape_key("stub", t=int(key)))
                        else self.COMPILE_S
                    )
        finally:
            cs.warming = False
            cs.cache.flush()
        return time.monotonic() - t0


def test_second_cold_start_replays_from_cache(tmp_path):
    """Acceptance: a second cold-start warmup against a populated
    persistent cache completes >= 5x faster than the first."""
    fp = engine_fingerprint(_cfg())
    keys = [str(i) for i in range(16, 32)]

    first = _StubWarmRunner(PersistentCompileCache(str(tmp_path), fp))
    t_first = first.warm(keys)
    assert first.compile_stats.warmed_programs == len(keys)
    assert first.compile_stats.replayed_programs == 0

    # Fresh process: new stats + new cache instance, same directory.
    second = _StubWarmRunner(PersistentCompileCache(str(tmp_path), fp))
    t_second = second.warm(keys)
    assert second.compile_stats.replayed_programs == len(keys)
    assert second.compile_stats.mid_traffic_compiles == 0
    assert t_first / t_second >= 5.0


# ---------------------------------------------------------------------------
# readiness gating + mid-traffic accounting (device-free mocker)
# ---------------------------------------------------------------------------


async def test_hold_gate_parks_admission_until_warm():
    engine = MockerEngine(_cfg(warmup_gate="hold"), MockerConfig())
    await engine.start()
    try:
        assert engine.state == "warming" and not engine.is_ready
        task = asyncio.create_task(_collect(engine, n_prompt=8))
        await asyncio.sleep(0.15)
        # Held: the request is queued, not served (and nothing compiled).
        assert not task.done()
        assert engine.runner.compile_stats.seen == set()
        n = await engine.warmup()
        assert n > 0 and engine.is_ready and engine.state == "ready"
        assert await asyncio.wait_for(task, timeout=10) == 4
        assert not engine.served_unwarmed
    finally:
        await engine.stop()


async def test_degraded_gate_serves_and_flags():
    engine = MockerEngine(_cfg(warmup_gate="degraded"), MockerConfig())
    await engine.start()
    try:
        assert engine.state == "warming"
        assert await _collect(engine, n_prompt=8) == 4
        assert engine.state == "ready" and engine.served_unwarmed
        # Un-warmed serving is exactly what the counter exists to expose.
        assert engine.runner.compile_stats.mid_traffic_compiles > 0
    finally:
        await engine.stop()


async def test_mid_traffic_counter_on_unwarmed_shape():
    engine = MockerEngine(_cfg(), MockerConfig())
    await engine.start()
    try:
        # Warm ONLY the bottom of the budget ladder (16/32); a prompt
        # whose batch snaps to the un-warmed 64 rung then compiles
        # mid-traffic and the counters must say so.
        r = engine.runner
        hot, tail = r.warmup_plan()
        small = [
            (key, op) for key, op in hot + tail
            if key in ("unified:t16", "unified:t32")
        ]
        r.run_warm_ops(small)
        engine._state = "ready"
        cs = r.compile_stats
        assert cs.mid_traffic_compiles == 0
        await _collect(engine, n_prompt=16)
        assert cs.mid_traffic_compiles == 0  # covered rungs: free
        await _collect(engine, n_prompt=50)
        assert cs.mid_traffic_compiles >= 1
        assert any("t64" in k for k in cs.mid_traffic_keys)
        stall_after_first = cs.compile_stall_ms_total
        assert stall_after_first > 0
        await _collect(engine, n_prompt=50)  # same shape again: no compile
        assert cs.compile_stall_ms_total == stall_after_first
        assert engine.readiness()["mid_traffic_compiles_total"] >= 1
    finally:
        await engine.stop()


async def test_manifest_saved_on_stop_and_drives_next_warmup(tmp_path):
    path = str(tmp_path / "manifest.json")
    cfg = _cfg(shape_manifest_path=path)
    engine = MockerEngine(cfg, MockerConfig())
    await engine.start()
    await engine.warmup()
    await _collect(engine, n_prompt=40)
    await engine.stop()
    assert os.path.exists(path)

    relaunch = MockerEngine(_cfg(shape_manifest_path=path), MockerConfig())
    await relaunch.start()
    try:
        n_hot = await relaunch.warmup()
        # Every unified rung is decode-critical, so the whole grid stays
        # hot — the manifest's value is ORDERING (observed rungs first)
        # and the zero-mid-traffic replay below.
        assert n_hot == len(default_shape_grid(cfg))
        assert relaunch.is_ready
        # The 40-token prompt's rung was observed and therefore warmed.
        observed = shape_key("unified", t=64)
        assert observed in relaunch.runner.compile_stats.seen
        for _ in range(100):
            if relaunch.warm_tail_pending == 0:
                break
            await asyncio.sleep(0.05)
        assert relaunch.warm_tail_pending == 0
        # Serving the same workload again compiles nothing mid-traffic.
        await _collect(relaunch, n_prompt=40)
        assert relaunch.runner.compile_stats.mid_traffic_compiles == 0
    finally:
        await relaunch.stop()


# ---------------------------------------------------------------------------
# real CPU runner: warmed engine serves with zero mid-traffic compiles
# ---------------------------------------------------------------------------


async def test_real_runner_warmup_covers_serving_shapes():
    from dynamo_tpu.engine.engine import TpuEngine

    engine = TpuEngine(_cfg(
        model=ModelConfig.tiny_test(),
        max_model_len=64,
        unified_token_budget=32,  # rungs {16, 32}; a 33-token prompt chunks
        unified_prefill_quantum=16,
        sampling_extras=False,
        dtype="float32",
    ))
    await engine.start()
    try:
        n = await engine.warmup()
        assert n > 0
        cs = engine.runner.compile_stats
        assert cs.warmed_programs == n
        await asyncio.gather(
            _collect(engine, n_prompt=5),
            _collect(engine, n_prompt=20),
            _collect(engine, n_prompt=33),
        )
        assert cs.mid_traffic_compiles == 0, cs.mid_traffic_keys
    finally:
        await engine.stop()


def test_budget_snapping_covers_every_serving_batch():
    """The lane ladder is GONE — runtime shape snapping is the budget
    ladder alone: every possible unified batch total lands on a warmed
    rung, so the grid covers everything serving can execute (the unified
    successor of the old lane-bucket snapping contract)."""
    from dynamo_tpu.engine.compile_cache import (
        budget_ladder,
        token_budget,
    )

    cap = 256
    ladder = set(budget_ladder(cap))
    for total in (1, 2, 15, 16, 17, 100, 255, 256, 400):
        assert token_budget(total, cap) in ladder
    # And the ladder-deletion is structural: the mixin no longer carries
    # lane-bucket machinery at all.
    from dynamo_tpu.engine.compile_cache import WarmupPlanMixin

    assert not hasattr(WarmupPlanMixin, "lane_bucket")
    assert not hasattr(WarmupPlanMixin, "add_lane_bucket")


# ---------------------------------------------------------------------------
# HTTP surface
# ---------------------------------------------------------------------------


async def test_health_warming_503_and_compile_gauges():
    import aiohttp

    from dynamo_tpu.llm.discovery import ModelManager
    from dynamo_tpu.llm.http_service import HttpService

    state = {"state": "warming", "mid_traffic_compiles_total": 0,
             "warm_tail_pending": 3}
    service = HttpService(
        ModelManager(), host="127.0.0.1", port=0,
        readiness=lambda: dict(state),
    )
    await service.start()
    try:
        base = f"http://127.0.0.1:{service.port}"
        async with aiohttp.ClientSession() as s:
            async with s.get(f"{base}/health") as resp:
                assert resp.status == 503
                body = await resp.json()
                assert body["status"] == "warming"
                assert body["engine"]["warm_tail_pending"] == 3
            async with s.get(f"{base}/live") as resp:
                assert resp.status == 200  # liveness unaffected by warmup
            state["state"] = "ready"
            state["mid_traffic_compiles_total"] = 2
            async with s.get(f"{base}/health") as resp:
                assert resp.status == 200
                assert (await resp.json())["status"] == "healthy"
            async with s.get(f"{base}/metrics") as resp:
                text = await resp.text()
                assert "engine_ready 1.0" in text
                assert "mid_traffic_compiles_total 2" in text
    finally:
        await service.stop()
