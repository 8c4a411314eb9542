"""The engine thread's pass and a start, by phase (engine/flight_recorder.py
``StepPhases``): self time on the host's clock, on the step's flight
record, and as host events on the profiler's clock. CPU, tiny models; no
sleep decides anything."""

import asyncio
import dataclasses
import sys
import time
import types

import pytest

import jax

from chipbench import xprof
from dynamo_tpu.engine import flight_recorder as fr
from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.engine.engine import TpuEngine
from dynamo_tpu.engine.flight_recorder import (
    PHASES,
    START_PHASES,
    FlightRecorder,
    StepPhases,
)
from dynamo_tpu.llm.protocols.common import (
    EngineOutput,
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.runtime.engine import Context

HOST_FIELDS = [f"host_{p}_ms" for p in (*PHASES, "other")]
MODELS = {
    # its record is noted at the dispatch's issue
    "plain": ModelConfig.tiny_test(),
    # grouped expert layers hand out counts: noted at the retire
    "experts": dataclasses.replace(
        ModelConfig.tiny_moe_test(), name="tiny-moe16-phases", num_experts=16
    ),
}


@pytest.fixture
def clock(monkeypatch):
    """The recorder module's clock, moved by hand."""
    c = types.SimpleNamespace(now=100.0, time=time.time)
    c.perf_counter = lambda: c.now
    monkeypatch.setattr(fr, "time", c)
    return c


def _nest(p, clock):
    clock.now += 1                      # outside every phase
    with p.phase("compose"):
        clock.now += 2
        with p.phase("pack"):
            clock.now += 4
            with p.phase("put"):
                clock.now += 8
        clock.now += 16
        first = p.take()                # while compose is open
        clock.now += 32
    clock.now += 64
    return first, p.take()


def test_nested_phases_book_self_time(clock):
    p = StepPhases()
    first, second = _nest(p, clock)
    booked = {k: v for k, v in first.items() if v}
    assert booked == {"other": 1, "compose": 18, "pack": 4, "put": 8}
    assert sum(first.values()) == 31    # the wall time up to the take
    assert {k: v for k, v in second.items() if v} == {
        "compose": 32, "other": 64}
    assert set(first) == {*PHASES, "other"}
    # a phase left by an exception is closed all the same
    with pytest.raises(KeyError):
        with p.phase("retire"):
            clock.now += 1
            raise KeyError
    assert p.take()["retire"] == 1 and not p._open


def test_takes_sum_to_the_wall_time_on_the_real_clock():
    p = StepPhases()
    p.take()
    t0 = time.perf_counter()
    for _ in range(200):
        with p.phase("compose"):
            with p.phase("pack"):
                sum(range(50))
            with p.phase("dispatch"):
                pass
    taken = p.take()
    wall = time.perf_counter() - t0
    assert all(v >= 0 for v in taken.values())
    assert abs(sum(taken.values()) - wall) < 1e-3
    assert taken["pack"] > 0 and taken["compose"] > 0
    assert taken["retire"] == 0
    # seconds() reads on, and resets nothing
    start = StepPhases(START_PHASES, "start")
    with start.phase("weights"):
        pass
    assert set(start.seconds()) == set(START_PHASES)
    assert start.seconds()["weights"] == start.seconds()["weights"] > 0


def test_the_clock_runs_where_jax_cannot_be_imported(monkeypatch, clock):
    monkeypatch.setattr(fr, "_trace_annotation", None)
    monkeypatch.setitem(sys.modules, "jax", None)   # import jax -> ImportError
    with pytest.raises(ImportError):
        import jax.profiler  # noqa: F401
    assert fr._annotation("engine/pack") is None
    p = StepPhases()
    with p.span("pass"):
        first, _ = _nest(p, clock)
    assert first["compose"] == 18 and first["put"] == 8
    assert fr._trace_annotation is None


def test_a_record_carries_the_host_mapping_under_its_names():
    rec = FlightRecorder(capacity=8)
    host = dict.fromkeys((*PHASES, "other"), 0.0)
    host.update(pack=0.0012, retire_wait=0.0105, other=0.00005)
    rec.note_step("unified", host=host)
    rec.note_step("unified")            # a caller with no phases: zeros
    first, bare = rec.snapshot()
    assert first["host_pack_ms"] == 1.2 and first["host_other_ms"] == 0.05
    assert first["host_period_ms"] == 11.75
    assert all(bare[f] == 0 for f in (*HOST_FIELDS, "host_period_ms"))
    assert [k for k in first if k.startswith("host_")] == [
        *HOST_FIELDS, "host_period_ms"]


async def _generate(engine, prompt, n):
    pre = PreprocessedRequest(
        token_ids=list(prompt), sampling=SamplingOptions(temperature=0.0),
        stop=StopConditions(max_tokens=n, ignore_eos=True),
    )
    tokens = []
    async for raw in engine.generate(Context(pre.to_wire())):
        tokens.extend(EngineOutput.from_wire(raw).token_ids)
    return tokens


@pytest.fixture(scope="module", params=sorted(MODELS))
def served(request, tmp_path_factory):
    """ONE served engine a model: started, warmed, a few requests at once
    under the profiler as the benchmark's harness sets it; what it left."""
    logdir = str(tmp_path_factory.mktemp(f"trace_{request.param}"))

    async def serve():
        engine = TpuEngine(EngineConfig(
            model=MODELS[request.param], dtype="float32", block_size=4,
            num_blocks=64, max_num_seqs=4, max_model_len=128,
            unified_token_budget=32, unified_prefill_quantum=16,
        ))
        await engine.start()
        try:
            await engine.warmup()
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(logdir, profiler_options=options)
            try:
                outs = await asyncio.gather(*(
                    _generate(engine, range(2, 2 + n), 6)
                    for n in (5, 23, 40, 9)
                ))
            finally:
                jax.profiler.stop_trace()
            assert [len(o) for o in outs] == [6] * 4
            return {
                "steps": [r for r in engine.debug_steps()
                          if "dispatch_ms" in r],
                "readiness": engine.readiness(),
                "retired": request.param == "experts",
            }
        finally:
            await engine.stop()

    out = asyncio.run(serve())
    out["trace"] = xprof.load(logdir)
    return out


def test_every_record_carries_the_phases_and_they_sum_to_the_period(served):
    steps = served["steps"]
    assert len(steps) >= 6
    # where the record was noted: with the expert layers' counts, at retire
    assert all(bool(r["moe_experts_hit"]) == served["retired"] for r in steps)
    for r in steps:
        assert all(r[f] >= 0 for f in (*HOST_FIELDS, "host_period_ms")), r
        assert abs(sum(r[f] for f in HOST_FIELDS) - r["host_period_ms"]) <= 1e-3
        assert r["host_period_ms"] > 0
    # A period is one turn of the loop cut where the record is noted. Cut
    # at the issue it holds that dispatch's compose, pack, put and
    # dispatch; cut at the retire, two retires may follow each other with
    # nothing composed between them (the last dispatches of a drain).
    for name in ("compose", "pack", "put", "dispatch"):
        ran = [r[f"host_{name}_ms"] > 0 for r in steps]
        assert any(ran) if served["retired"] else all(ran), name
    for name in ("side_channels", "drain", "admit"):
        assert sum(r[f"host_{name}_ms"] for r in steps) > 0, name
    assert sum(r["host_retire_wait_ms"] for r in steps) > 0
    assert sum(r["host_retire_ms"] for r in steps) > 0
    # the warm-up's seconds are the start's, not the first step's
    warm_ms = 1e3 * served["readiness"]["start_warmup_seconds"]
    assert warm_ms > 0 and steps[0]["host_period_ms"] < warm_ms


def test_the_benchmarks_readers_find_the_fields(served):
    """The readers the benchmark has (``flight``, ``flight_ratio``,
    ``readiness_peak``) take the fields as they are written: the shares of
    the period by phase come to 100."""
    from chipbench import registry
    from chipbench.observe import Observations

    obs = Observations(
        window=(0.0, 1.0), chips=1, setup_s=1.0, records=[],
        flight=served["steps"], readiness=[served["readiness"]],
    )
    ratio = registry.load("readers", "flight_ratio").read
    shares = [ratio(obs, over=[f], under=["host_period_ms"], scale=100.0)
              for f in HOST_FIELDS]
    # each field is rounded to 0.1 us, and a tiny model's period is short
    assert all(s >= 0 for s in shares) and abs(sum(shares) - 100.0) < 0.5
    p50 = registry.load("readers", "flight").read(
        obs, fields=HOST_FIELDS, stat="p50")
    assert p50 > 0
    peak = registry.load("readers", "readiness_peak").read
    assert all(peak(obs, key=f"start_{p}_seconds") >= 0 for p in START_PHASES)


def test_the_phases_are_host_events_on_the_profilers_clock(served):
    planes = [p for p in served["trace"]["planes"]
              if p["name"].startswith("/host:")]
    lanes = [ln["events"] for p in planes for ln in p["lines"]
             if any(n == "engine/pass" for n, _, _ in ln["events"])]
    assert len(lanes) == 1, "one thread feeds the device"
    names = {n for n, _, _ in lanes[0] if n.startswith("engine/")}
    assert {"engine/pass", "engine/retire", "engine/pack", "engine/put",
            "engine/retire_wait", "engine/dispatch", "engine/compose",
            "engine/side_channels"} <= names
    assert names <= {f"engine/{p}" for p in (*PHASES, "pass")}
    # no other thread's lane holds one: the frontend's loop opens none
    assert not [
        n for p in planes for ln in p["lines"] if ln["events"] is not lanes[0]
        for n, _, _ in ln["events"] if n.startswith("engine/")
    ]
    # The deepest event names an instant: a pack inside its pass. (Over
    # the engine's lane: on the CPU backend the other host lanes are the
    # "device", whose operations would name the instant.)
    host = xprof._HostIndex([
        {"name": "/host:CPU", "lines": [{"name": "python", "events": lanes[0]}]}
    ])
    packs = [(s, d) for n, s, d in lanes[0] if n == "engine/pack" and d > 0]
    assert packs
    for s, d in packs[:5]:
        assert host.at(s + d / 2) == "engine/pack"
    sides = [(s, d) for n, s, d in lanes[0]
             if n == "engine/side_channels" and d > 0]
    assert host.at(sides[0][0] + sides[0][1] / 2) == "engine/side_channels"


def test_readiness_says_what_the_start_was_made_of(served):
    snap = served["readiness"]
    for name in START_PHASES:
        assert snap[f"start_{name}_seconds"] >= 0, name
    # A runner built and warmed here; no CLI booked an import or a read.
    # The build is self time too: less the weights drawn inside it.
    assert snap["start_build_seconds"] > 0 and snap["start_warmup_seconds"] > 0
    assert snap["start_weights_seconds"] > 0
    assert snap["start_runtime_seconds"] == 0
    warm = sum(snap[f"warmup_{p}_seconds_total"]
               for p in ("tracing", "lowering", "backend"))
    assert 0 < warm <= snap["start_warmup_seconds"] + 0.5


@pytest.mark.anyio
async def test_health_and_metrics_carry_the_start(anyio_backend):
    import aiohttp

    from dynamo_tpu.llm.discovery import ModelManager
    from dynamo_tpu.llm.http_service import HttpService

    start = StepPhases(START_PHASES, "start")
    with start.phase("runtime"):
        pass
    snap = {"state": "ready", **{
        f"start_{name}_seconds": 1.5 + i
        for i, name in enumerate(start.seconds())
    }}
    service = HttpService(
        ModelManager(), host="127.0.0.1", port=0, readiness=lambda: dict(snap),
    )
    await service.start()
    try:
        base = f"http://127.0.0.1:{service.port}"
        async with aiohttp.ClientSession() as s:
            async with s.get(f"{base}/health") as resp:
                engine = (await resp.json())["engine"]
            async with s.get(f"{base}/metrics") as resp:
                text = await resp.text()
        for i, name in enumerate(START_PHASES):
            assert engine[f"start_{name}_seconds"] == 1.5 + i
            assert f"start_{name}_seconds {1.5 + i}" in text
    finally:
        await service.stop()
