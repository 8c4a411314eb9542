"""Compile-lifecycle subsystem tests (engine/compile_cache.py):

- the warm list: the whole grid, the ladder then one top rung a variant
- where the cache lives (one rule; nothing of ours written into it), and
  XLA's own hit / miss events counted during warmup only
- readiness gating: warmup_gate="hold" parks admission until the shape set
  is warm; "degraded" serves immediately and flags it
- mid-traffic-compile counter incrementing on an un-warmed shape, and
  staying zero on a warmed engine (real CPU runner)
- /health 503-while-warming + compile gauges on /metrics
"""

import asyncio
import os

import pytest

from dynamo_tpu.engine.compile_cache import (
    JAX_CACHE_EVENTS,
    activate_cache,
    budget_ladder,
    default_shape_grid,
    shape_key,
)
from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.llm.protocols.common import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.mocker.engine import MockerConfig, MockerEngine, _SimRunner
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
# the warm list
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("variant,tail", [
    ({"sampling_extras": False}, []),
    ({"sampling_extras": True}, ["unified_full:t64"]),
    ({"sampling_extras": False, "multimodal": True}, ["unified_mm:t64"]),
    # Extras are refused on a speculative engine: no unified_full there.
    ({"sampling_extras": True, "multimodal": True, "speculative_k": 2},
     ["unified_mm:t64"]),
])
def test_warm_ops_is_the_whole_grid_in_ladder_order(variant, tail):
    """ONE list, all of it run before the engine turns ready: the budget
    ladder bottom-up, then one top rung a configured variant."""
    cfg = _cfg(unified_token_budget=64, **variant)
    runner = _SimRunner(cfg, MockerConfig())
    keys = [key for key, _op in runner.warm_ops()]
    assert keys == ["unified:t16", "unified:t32", "unified:t64"] + tail
    assert keys == [shape_key(k, t) for k, t in default_shape_grid(cfg)]
    assert keys[:3] == [f"unified:t{b}" for b in budget_ladder(64)]
    cs = runner.compile_stats
    assert runner.run_warm_ops(runner.warm_ops()) == len(keys)
    assert cs.warmed_programs == len(keys) and cs.seen == set(keys)
    assert cs.mid_traffic_compiles == 0 and not cs.warming


# ---------------------------------------------------------------------------
# persistent cache
# ---------------------------------------------------------------------------


@pytest.fixture
def restore_jax_cache_config():
    """activate_cache() flips process-global jax config: put it back so a
    tmp_path cache cannot outlive its test. jax opens its cache ONCE, at
    the first compile that asks (with no directory set then: no cache for
    the life of the process), so it is reset on the way in and out."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    names = (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes",
    )
    before = {n: getattr(jax.config, n) for n in names}
    compilation_cache.reset_cache()
    yield
    for n, v in before.items():
        jax.config.update(n, v)
    compilation_cache.reset_cache()


def test_cache_rule_env_var_places_everything(
    tmp_path, monkeypatch, restore_jax_cache_config
):
    """$JAX_COMPILATION_CACHE_DIR set: that directory whatever else was
    asked for, and no cache-dir config call."""
    import jax

    from dynamo_tpu.engine.compile_cache import resolve_cache_base

    outside = str(tmp_path / "outside")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", outside)
    monkeypatch.delenv("DYNAMO_TPU_COMPILE_CACHE_DIR")
    before = jax.config.jax_compilation_cache_dir
    # Whatever base the rule or a caller hands over (an EngineConfig's
    # own compile_cache_dir, say), activation places the cache.
    assert activate_cache(resolve_cache_base("auto")) == outside
    explicit = resolve_cache_base(str(tmp_path / "explicit"))
    assert activate_cache(explicit) == outside
    assert jax.config.jax_compilation_cache_dir == before
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
    assert jax.config.jax_persistent_cache_min_entry_size_bytes == 0
    assert os.listdir(outside) == []  # made, and nothing of ours in it
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
    # Unset, activate_cache() is what points jax at the base.
    assert activate_cache(explicit) == explicit
    assert jax.config.jax_compilation_cache_dir == explicit
    assert os.listdir(explicit) == []


#: What versions before PR 51 wrote beside XLA's entries (a ledger, the
#: fingerprint's fields, a shape manifest): neither read nor touched now.
#: (The names are split so that a search for them finds no live code.)
_OLDER_FILES = {
    "0123456789abcdef/warmed" "_shapes.json":
        '{"fingerprint": "0123456789abcdef", "shapes": ["unified:t16"]}',
    "0123456789abcdef/meta.json": "{torn",
    "0123456789abcdef/shape" "_manifest.json":
        '{"version": 1, "fingerprint": "x", "shapes": [{"kind": "gone"}]}',
}


def _files_under(root) -> dict[str, bytes]:
    out = {}
    for base, _dirs, names in os.walk(root):
        for name in names:
            path = os.path.join(base, name)
            with open(path, "rb") as f:
                out[os.path.relpath(path, root)] = f.read()
    return out


@pytest.mark.parametrize("seeded", [False, True], ids=["empty", "seeded"])
async def test_start_serve_stop_write_only_xlas_entries(
    tmp_path, monkeypatch, restore_jax_cache_config, seeded
):
    """A start, a warmup, a served request and a stop against a cache
    directory leave XLA's entries there and no file of this program's;
    an older version's files (one of them not JSON) change nothing. A
    second start reads what the first compiled, by XLA's own count."""
    from dynamo_tpu.engine.engine import TpuEngine

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    cache = tmp_path / "cache"
    if seeded:
        for rel, text in _OLDER_FILES.items():
            (cache / rel).parent.mkdir(parents=True, exist_ok=True)
            (cache / rel).write_text(text)
    older = _files_under(cache)

    async def start_warm_serve_stop() -> tuple[dict, dict]:
        engine = TpuEngine(_cfg(
            max_model_len=32, unified_token_budget=16,
            unified_prefill_quantum=16, sampling_extras=False,
            dtype="float32", compile_cache_dir=str(cache),
        ))
        await engine.start()
        try:
            assert engine.runner.compile_cache_dir == str(cache)
            assert await engine.warmup() == 1
            assert await _collect(engine, n_prompt=5) == 4
            cs = engine.runner.compile_stats
            assert cs.seen == {"unified:t16"}
            assert cs.mid_traffic_compiles == 0
            before_stop = _files_under(cache)
        finally:
            await engine.stop()
        assert _files_under(cache) == before_stop  # stop() writes no file
        return dict(cs.warm_cache_events), before_stop

    # A first start compiles everything it asks the cache for.
    events, after = await start_warm_serve_stop()
    assert events["misses"] >= 1 and events["hits"] == 0
    ours = {rel: data for rel, data in after.items() if rel in older}
    assert ours == older  # neither read into anything nor touched
    xla = sorted(set(after) - set(older))
    assert xla and all(
        os.sep not in rel and rel.endswith(("-cache", "-atime"))
        for rel in xla
    ), xla
    # The second reads it all: XLA's count, not a belief of ours.
    again, after_again = await start_warm_serve_stop()
    assert again == {"hits": events["misses"], "misses": 0}
    assert set(after_again) == set(after)


@pytest.mark.parametrize("inside", [
    {"hits": 3, "misses": 0}, {"hits": 1, "misses": 2},
])
def test_cache_counters_count_events_inside_warm_ops_only(inside):
    """warmup_cache_{hits,misses}_total are jax.monitoring's own events,
    listened for while run_warm_ops runs and at no other time."""
    import jax.monitoring

    event_of = {name: event for event, name in JAX_CACHE_EVENTS.items()}

    def emit(counts):
        for name, n in counts.items():
            for _ in range(n):
                jax.monitoring.record_event(event_of[name])
        jax.monitoring.record_event("/jax/compilation_cache/tasks_using_cache")

    runner = _SimRunner(_cfg(), MockerConfig())
    cs = runner.compile_stats
    emit({"hits": 5, "misses": 5})  # before: nobody listens
    assert runner.run_warm_ops(
        [("a", lambda: emit(inside)), ("b", lambda: emit({"hits": 1}))]
    ) == 2
    emit({"hits": 7, "misses": 7})  # after: unregistered
    want = {"hits": inside["hits"] + 1, "misses": inside["misses"]}
    assert cs.warm_cache_events == want
    snap = cs.snapshot()
    assert snap["warmup_cache_hits_total"] == want["hits"]
    assert snap["warmup_cache_misses_total"] == want["misses"]
    # A second warmup (a program warmed later) adds to the same counters.
    runner.run_warm_ops([("c", lambda: emit({"misses": 1}))])
    assert cs.warm_cache_events["misses"] == want["misses"] + 1


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
        small = [
            (key, op) for key, op in r.warm_ops()
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


async def test_readiness_after_warmup_carries_xlas_cache_counts():
    pushed: list[dict] = []  # what goes out on the wire (on_metrics)
    engine = MockerEngine(
        _cfg(warmup_gate="hold"), MockerConfig(), on_metrics=pushed.append
    )
    await engine.start()
    try:
        n = await engine.warmup()
        assert await _collect(engine, n_prompt=8) == 4
        assert engine.readiness()["state"] == "ready"
        for surface in (engine.readiness(), pushed[-1]):
            assert surface["warmup_programs_total"] == n
            assert surface["warmup_cache_hits_total"] == 0
            assert surface["warmup_cache_misses_total"] == 0
            # The gauge of a tail that was always empty and the ledger's
            # belief are gone, from every surface.
            assert not [k for k in surface if "tail" in k or "replayed" in k]
    finally:
        await engine.stop()


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
             "warmup_cache_misses_total": 3}
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
                assert body["engine"]["warmup_cache_misses_total"] == 3
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
                assert "warmup_cache_misses_total 3" in text
    finally:
        await service.stop()
