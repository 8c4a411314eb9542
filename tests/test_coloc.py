"""SLO-aware prefill/decode co-location (engine/coloc.py; ROADMAP #3):
controller convergence from both sides, floor + deadband behavior,
per-phase admission, compose_unified deferral fairness, the phase-aware
HTTP admission watermark, and the mocker e2e where a prefill burst
arrives mid-decode and ITL stays within the SLO."""

import asyncio
import dataclasses
import time

import numpy as np
import pytest

from dynamo_tpu.engine.coloc import ColocController
from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.engine.scheduler import compose_unified
from dynamo_tpu.llm.admission import (
    AdmissionConfig,
    AdmissionController,
    AdmissionRejected,
)
from dynamo_tpu.llm.protocols.common import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.runtime.engine import Context

pytestmark = pytest.mark.anyio

SLO = 10.0


def _cfg(**kw) -> EngineConfig:
    base = dict(
        model=ModelConfig.tiny_test(), num_blocks=64, max_model_len=256,
        unified_token_budget=1024,
        unified_prefill_quantum=64, coloc="adaptive", itl_slo_ms=SLO,
        coloc_min_quantum=16,
    )
    base.update(kw)
    return EngineConfig(**base)


def _drive(ctrl: ColocController, cost_ms, steps: int) -> None:
    """Closed loop: each observed sample is the cost of the quantum the
    controller chose for that step (cost_ms: quantum -> ms)."""
    for _ in range(steps):
        ctrl.observe(cost_ms(ctrl.quantum), decode_lanes=8,
                     prefill_tokens=ctrl.quantum)


# ---------------------------------------------------------------------------
# controller convergence
# ---------------------------------------------------------------------------


def test_oversized_quantum_shrinks_until_itl_meets_slo():
    """From a way-oversized hand-tuned quantum, the loop must converge
    to dispatches within the SLO without collapsing to the floor (the
    cost model leaves plenty of feasible quantum above it)."""
    ctrl = ColocController(_cfg(unified_prefill_quantum=1024))
    cost = lambda q: 2.0 + 0.01 * q  # noqa: E731 — 1024 -> 12.2 ms > SLO
    _drive(ctrl, cost, 200)
    assert cost(ctrl.quantum) <= SLO
    assert ctrl.itl_ema_ms <= SLO
    assert ctrl.quantum < 1024
    assert ctrl.quantum > ctrl.floor  # feasible region is far above 16
    assert ctrl.itl_slo_violations_total >= 1  # the oversized start


def test_undersized_quantum_grows_until_budget_limited():
    """With negligible per-token cost, nothing stops growth before the
    token budget cap — the controller must find it."""
    ctrl = ColocController(_cfg(unified_prefill_quantum=16))
    _drive(ctrl, lambda q: 2.0 + 0.0001 * q, 200)
    assert ctrl.quantum == ctrl.cap == 1024
    assert ctrl.itl_slo_violations_total == 0


def test_undersized_quantum_grows_into_deadband_and_holds():
    """Growth stops inside [headroom_frac * SLO, SLO] — the deadband —
    and stays there: no persistent oscillation under steady load."""
    ctrl = ColocController(_cfg(unified_prefill_quantum=16))
    cost = lambda q: 2.0 + 0.01 * q  # noqa: E731
    _drive(ctrl, cost, 300)
    band = (ctrl.headroom_frac * SLO, SLO)
    assert band[0] <= cost(ctrl.quantum) <= band[1]
    trace = []
    for _ in range(100):
        _drive(ctrl, cost, 1)
        trace.append(ctrl.quantum)
    # Steady state: the quantum must not keep sawing (AIMD converged
    # into the deadband; at most one grow step of residual motion).
    assert max(trace) - min(trace) <= ctrl.grow_tokens


def test_floor_respected_under_sustained_slo_pressure():
    """When even zero prefill can't meet the SLO (decode alone is over),
    the quantum pins at the floor — prefill never fully starves — and
    every dispatch counts a violation."""
    ctrl = ColocController(_cfg(unified_prefill_quantum=512))
    _drive(ctrl, lambda q: 2 * SLO, 100)
    assert ctrl.quantum == ctrl.floor
    assert ctrl.itl_slo_violations_total == 100
    _drive(ctrl, lambda q: 2 * SLO, 50)
    assert ctrl.quantum == ctrl.floor  # stays pinned, never below


def test_prefill_only_dispatches_are_not_itl_evidence():
    ctrl = ColocController(_cfg())
    ctrl.observe(500.0, decode_lanes=0, prefill_tokens=256)
    assert ctrl.steps_observed == 0
    assert ctrl.itl_ema_ms == 0.0
    assert ctrl.quantum == 64  # no adaptation off non-evidence


def test_static_mode_measures_but_never_adapts():
    """coloc='static' with an SLO set is monitoring-only: violations
    and EMA are tracked, the quantum stays hand-tuned, and per-phase
    admission never defers (legacy behavior, the A/B control)."""
    ctrl = ColocController(_cfg(coloc="static", unified_prefill_quantum=96))
    _drive(ctrl, lambda q: 2 * SLO, 50)
    assert ctrl.quantum == 96
    assert ctrl.itl_slo_violations_total == 50
    assert ctrl.itl_ema_ms > SLO
    assert ctrl.admit_prefill() is True
    assert ctrl.prefill_deferrals_total == 0


# ---------------------------------------------------------------------------
# per-phase admission
# ---------------------------------------------------------------------------


def test_admit_prefill_defers_under_pressure_with_bounded_streak():
    ctrl = ColocController(_cfg(), max_defer_steps=5)
    _drive(ctrl, lambda q: 2 * SLO, 10)  # in violation
    assert ctrl.under_pressure
    decisions = [ctrl.admit_prefill() for _ in range(6)]
    # 5 consecutive deferrals, then the anti-starvation valve admits.
    assert decisions == [False] * 5 + [True]
    assert ctrl.prefill_deferrals_total == 5
    # Pressure relieved -> admission resumes immediately.
    _drive(ctrl, lambda q: 1.0, 50)
    assert not ctrl.under_pressure
    assert ctrl.admit_prefill() is True
    assert ctrl.prefill_deferrals_total == 5


def test_config_validation_rejects_bad_coloc_combos():
    with pytest.raises(ValueError, match="coloc="):
        _cfg(coloc="magic").validate()
    with pytest.raises(ValueError, match="itl_slo_ms"):
        _cfg(itl_slo_ms=0.0).validate()
    with pytest.raises(ValueError, match="coloc_min_quantum"):
        _cfg(coloc_min_quantum=4096).validate()
    _cfg().validate()  # the good combo
    # Static + SLO-less stays valid (the historical default).
    _cfg(coloc="static", itl_slo_ms=0.0).validate()


# ---------------------------------------------------------------------------
# compose_unified deferral fairness (rotation)
# ---------------------------------------------------------------------------


def test_compose_rotation_round_robins_deferral():
    lanes = [f"d{i}" for i in range(8)]
    served: dict[str, int] = {l: 0 for l in lanes}
    rotation = 0
    steps = 16
    for _ in range(steps):
        take, _ = compose_unified(lanes, [], budget=4, quantum=2,
                                  rotation=rotation)
        assert len(take) == 4
        rotation += len(take)
        for l in take:
            served[l] += 1
    # Half the population fits per step; over 16 steps every lane is
    # served exactly half the time — round-robin, not head-first.
    assert set(served.values()) == {steps // 2}


def test_compose_rotation_bounds_lane_itl_vs_population_median():
    """No lane's deferral gap may be unboundedly worse than the
    population median: with N lanes and M slots the worst wait between
    services is bounded by ceil(N/M) steps for EVERY lane."""
    n_lanes, budget = 10, 3
    lanes = list(range(n_lanes))
    last_served = {l: 0 for l in lanes}
    worst_gap = {l: 0 for l in lanes}
    rotation = 0
    for step in range(1, 61):
        take, _ = compose_unified(lanes, [], budget=budget, quantum=budget,
                                  rotation=rotation)
        rotation += len(take)
        for l in take:
            worst_gap[l] = max(worst_gap[l], step - last_served[l])
            last_served[l] = step
    gaps = sorted(worst_gap.values())
    median = gaps[len(gaps) // 2]
    bound = -(-n_lanes // budget) + 1  # ceil + slack for the first lap
    assert max(gaps) <= bound
    assert max(gaps) <= 2 * median  # nobody unboundedly worse


def test_compose_rotation_default_keeps_legacy_order():
    take, _ = compose_unified(["a", "b", "c"], [], budget=2, quantum=1)
    assert take == ["a", "b"]  # rotation=0: byte-compatible with PR 6


# ---------------------------------------------------------------------------
# phase-aware HTTP admission watermark
# ---------------------------------------------------------------------------


def test_admission_prefill_backlog_watermark():
    stats = {"prefill_backlog_tokens": 0, "num_requests_waiting": 50}
    gate = AdmissionController(
        AdmissionConfig(max_prefill_backlog_tokens=4096),
        engine_stats=lambda: stats,
    )
    # Deep queue of decode-bound (tiny-backlog) work: NOT shed — the
    # request-count watermark is off and the token watermark sees the
    # real prefill pressure, which is none.
    with gate.admit():
        pass
    # A prompt-token flood trips it with its own typed reason.
    stats["prefill_backlog_tokens"] = 5000
    with pytest.raises(AdmissionRejected) as exc:
        gate.admit()
    assert exc.value.reason == "prefill_backlog"
    assert gate.rejected["prefill_backlog"] == 1


def test_metric_surfaces_carry_coloc_fields():
    """Exporter gauges are rendered via getattr on ForwardPassMetrics —
    every declared gauge must exist there, including the new coloc set,
    and survive the wire roundtrip."""
    from dynamo_tpu.llm.kv_router.protocols import ForwardPassMetrics
    from dynamo_tpu.llm.metrics_exporter import _GAUGES

    m = ForwardPassMetrics()
    for key, _help in _GAUGES:
        assert hasattr(m, key), key
    wire = m.to_wire()
    wire.update(
        coloc_quantum=640, itl_ema_ms=7.5, itl_slo_violations_total=3,
        coloc_prefill_deferrals_total=2, prefill_backlog_tokens=9000,
    )
    back = ForwardPassMetrics.from_wire(wire)
    assert back.coloc_quantum == 640
    assert back.itl_slo_violations_total == 3
    assert back.prefill_backlog_tokens == 9000


# ---------------------------------------------------------------------------
# mocker e2e: burst mid-decode
# ---------------------------------------------------------------------------


async def test_mocker_prefill_burst_mid_decode_holds_itl_slo():
    """The bench leg in miniature: a decode population is mid-stream
    when a long-prompt burst arrives; the adaptive controller must keep
    the engine-side dispatch-interval p95 within the SLO while the
    burst still completes, and the full coloc surface must show up on
    readiness, the metrics callback, and the flight recorder."""
    from dynamo_tpu.mocker import MockerConfig, MockerEngine

    slo = 15.0
    cfg = EngineConfig(
        model=ModelConfig.tiny_test(), num_blocks=512, block_size=16,
        max_num_seqs=6, max_model_len=1024, prefill_batch=2,
        dtype="float32", sampling_extras=False,
        unified_token_budget=512,
        unified_prefill_quantum=32, coloc="adaptive", itl_slo_ms=slo,
        coloc_min_quantum=16,
    )
    sim = MockerConfig(
        prefill_time_per_token_us=10.0, prefill_quadratic_us=0.0,
        decode_time_per_step_us=1000.0, decode_time_per_lane_us=100.0,
        prefill_dispatch_base_us=2000.0,
        vocab_size=cfg.model.vocab_size,
    )
    eng = MockerEngine(cfg, sim)
    metrics: list[dict] = []
    eng._on_metrics = metrics.append
    await eng.start()
    await eng.warmup()
    rng = np.random.default_rng(3)

    async def run(isl, osl):
        req = PreprocessedRequest(
            token_ids=rng.integers(0, 1000, isl).tolist(),
            sampling=SamplingOptions(temperature=0.0),
            stop=StopConditions(max_tokens=osl, ignore_eos=True),
        )
        n = 0
        async for out in eng.generate(Context(req.to_wire())):
            n += len(out["token_ids"])
        return n

    decode_tasks = [
        asyncio.create_task(run(16, 120)) for _ in range(4)
    ]
    await asyncio.sleep(0.05)  # decode mid-stream
    q_before_burst = eng.coloc.quantum
    burst = await asyncio.gather(*[run(600, 2) for _ in range(2)])
    snap = dict(eng.coloc.snapshot())
    assert burst == [2, 2]  # the burst completed (no starvation)
    assert snap["itl_p95_ms"] <= slo, snap
    assert snap["itl_slo_violations_total"] <= max(
        1, int(0.05 * eng.coloc.steps_observed)
    ), snap
    await asyncio.gather(*decode_tasks)
    # Adaptation actually happened: the quantum moved off its
    # hand-tuned start (headroom existed, so it grew).
    assert eng.coloc.quantum != 32 or q_before_burst != 32
    # Metric surfaces: readiness + engine metrics callback.
    r = eng.readiness()
    for key in (
        "coloc_quantum", "itl_ema_ms", "itl_slo_violations_total",
        "coloc_prefill_deferrals_total", "prefill_backlog_tokens",
    ):
        assert key in r, key
    m = metrics[-1]
    assert "coloc_quantum" in m and "itl_slo_violations_total" in m
    assert "prefill_backlog_tokens" in m
    # Flight recorder: unified records carry the quantum decision the
    # trace timeline attributes ITL spikes to.
    unified_recs = [
        rec for rec in eng.debug_steps() if rec.get("kind") == "unified"
    ]
    assert unified_recs
    assert all("quantum" in rec and "itl_ema_ms" in rec
               and "headroom_ms" in rec for rec in unified_recs)
    assert any(rec["quantum"] > 0 for rec in unified_recs)
    cs = eng.runner.compile_stats
    assert cs.mid_traffic_compiles == 0, cs.mid_traffic_keys
    await eng.stop()


async def test_mocker_static_vs_adaptive_quantum_moves_simulated_itl():
    """The per-phase cost model satellite: with prefill priced per
    token, a bigger static quantum must produce measurably longer
    dispatch intervals while prompts are in flight — the observable
    the controller steers. Device-free, deterministic cost model."""
    from dynamo_tpu.mocker import MockerConfig, MockerEngine

    async def measured_ema(quantum: int) -> float:
        cfg = EngineConfig(
            model=ModelConfig.tiny_test(), num_blocks=512, block_size=16,
            max_num_seqs=4, max_model_len=1024, prefill_batch=2,
            dtype="float32", sampling_extras=False,
            unified_token_budget=512,
            unified_prefill_quantum=quantum,
            coloc="static", itl_slo_ms=1e9,  # measure, never adapt
        )
        sim = MockerConfig(
            prefill_time_per_token_us=60.0, prefill_quadratic_us=0.0,
            decode_time_per_step_us=500.0,
            vocab_size=cfg.model.vocab_size,
        )
        eng = MockerEngine(cfg, sim)
        await eng.start()
        await eng.warmup()
        rng = np.random.default_rng(5)

        async def run(isl, osl):
            req = PreprocessedRequest(
                token_ids=rng.integers(0, 1000, isl).tolist(),
                sampling=SamplingOptions(temperature=0.0),
                stop=StopConditions(max_tokens=osl, ignore_eos=True),
            )
            async for _ in eng.generate(Context(req.to_wire())):
                pass

        decode = asyncio.create_task(run(16, 60))
        await asyncio.sleep(0.02)
        await asyncio.gather(run(400, 2), run(400, 2))
        ema = eng.coloc.itl_ema_ms
        await decode
        await eng.stop()
        return ema

    small = await measured_ema(16)
    large = await measured_ema(256)
    # 256-token quanta cost ~15 ms of prefill per dispatch vs ~1 ms: the
    # simulated ITL must visibly follow the quantum (3.2x on an idle host;
    # at 20 us a token it was 2.3x and a loaded host's ~1 ms of overhead
    # a dispatch read 1.31x).
    assert large > small * 1.5, (small, large)
