"""Overload-safe serving (docs/architecture/overload_and_drain.md):
admission control at the HTTP boundary, deadline propagation with per-hop
expiry, bounded queues with oldest-first shedding, and graceful drain.

Invariants under test: excess load is refused with typed retryable errors
(429/503 + Retry-After) instead of queueing unboundedly; expired work is
cancelled at every hop, never executed; shed work is ALWAYS visible
(counters + typed finishes), never silently dropped; a draining service
finishes what it admitted.
"""

import asyncio

import httpx
import pytest

from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.llm.admission import (
    AdmissionConfig,
    AdmissionController,
    AdmissionRejected,
)
from dynamo_tpu.llm.protocols.common import (
    DeadlineError,
    FinishReason,
    PreprocessedRequest,
    SamplingOptions,
    ShedError,
    StopConditions,
)
from dynamo_tpu.mocker import MockerConfig, MockerEngine
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.runtime.engine import Context
from dynamo_tpu.utils.deadline import (
    OVERLOAD,
    Deadline,
    parse_timeout_ms,
)

pytestmark = pytest.mark.anyio


# ---------------------------------------------------------------------------
# Deadline primitive
# ---------------------------------------------------------------------------


def test_deadline_basics():
    d = Deadline.after(10.0)
    assert not d.expired
    assert 9.0 < d.remaining_s() <= 10.0
    assert Deadline.after(-1.0).expired
    assert Deadline.after_ms(0.0).expired

    # Wire round trip: remaining budget, re-anchored on receipt.
    d2 = Deadline.from_wire(d.to_wire())
    assert abs(d2.remaining_s() - d.remaining_s()) < 0.5
    assert Deadline.from_wire(None) is None
    # An expired deadline stays expired across the hop (clamped at 0).
    assert Deadline.from_wire(Deadline.after(-5).to_wire()).expired

    # Unix (wall-clock) form for cross-process queue entries.
    d3 = Deadline.from_unix(d.to_unix())
    assert abs(d3.remaining_s() - d.remaining_s()) < 0.5
    assert Deadline.from_unix(None) is None

    assert parse_timeout_ms("1500") == 1500.0
    assert parse_timeout_ms("nope") is None
    assert parse_timeout_ms("-5") is None
    assert parse_timeout_ms(None) is None


def test_preprocessed_request_deadline_wire():
    pre = PreprocessedRequest(token_ids=[1, 2, 3], deadline=Deadline.after(5))
    wire = pre.to_wire()
    assert 0 < wire["deadline_ms"] <= 5000
    back = PreprocessedRequest.from_wire(wire)
    assert back.deadline is not None and not back.deadline.expired
    # No deadline -> no wire field, None on the far side.
    wire2 = PreprocessedRequest(token_ids=[1]).to_wire()
    assert "deadline_ms" not in wire2
    assert PreprocessedRequest.from_wire(wire2).deadline is None


# ---------------------------------------------------------------------------
# Admission controller
# ---------------------------------------------------------------------------


def test_admission_inflight_cap_and_release():
    c = AdmissionController(AdmissionConfig(max_inflight=2))
    p1 = c.admit()
    p2 = c.admit()
    with pytest.raises(AdmissionRejected) as exc:
        c.admit()
    assert exc.value.reason == "inflight_cap"
    assert not exc.value.draining
    assert exc.value.retry_after_s > 0
    p1.release()
    p3 = c.admit()  # slot freed
    # Double release must not underflow the gauge.
    p1.release()
    assert c.inflight == 2
    p2.release()
    p3.release()
    assert c.inflight == 0
    assert c.admitted_total == 3
    assert c.rejected == {"inflight_cap": 1}


def test_admission_engine_watermarks():
    stats = {"num_requests_waiting": 0, "gpu_cache_usage_perc": 0.2}
    c = AdmissionController(
        AdmissionConfig(max_inflight=99, max_engine_waiting=4, max_kv_usage=0.9),
        engine_stats=lambda: stats,
    )
    c.admit().release()
    stats["num_requests_waiting"] = 4
    with pytest.raises(AdmissionRejected) as exc:
        c.admit()
    assert exc.value.reason == "engine_waiting"
    stats["num_requests_waiting"] = 0
    stats["gpu_cache_usage_perc"] = 0.95
    with pytest.raises(AdmissionRejected) as exc:
        c.admit()
    assert exc.value.reason == "kv_watermark"
    # A BROKEN stats probe fails open on watermarks (the inflight cap and
    # drain latch still protect) — admission must never 500 on a probe.
    c2 = AdmissionController(
        AdmissionConfig(max_inflight=1, max_engine_waiting=1),
        engine_stats=lambda: (_ for _ in ()).throw(RuntimeError("probe")),
    )
    c2.admit()


def test_admission_draining():
    c = AdmissionController(AdmissionConfig(max_inflight=8))
    c.admit()
    c.begin_drain()
    with pytest.raises(AdmissionRejected) as exc:
        c.admit()
    assert exc.value.draining
    snap = c.snapshot()
    assert snap["draining"] and snap["inflight"] == 1


# ---------------------------------------------------------------------------
# Engine: bounded waiting list + deadline hops
# ---------------------------------------------------------------------------


def _cfg(**kw) -> EngineConfig:
    defaults = dict(
        model=ModelConfig.tiny_test(),
        num_blocks=64,
        max_num_seqs=2,
        max_model_len=128,
        dtype="float32",
    )
    defaults.update(kw)
    return EngineConfig(**defaults)


def _req(n=24, max_tokens=4, deadline=None):
    return PreprocessedRequest(
        token_ids=list(range(n)),
        sampling=SamplingOptions(temperature=0.0),
        stop=StopConditions(max_tokens=max_tokens, ignore_eos=True),
        deadline=deadline,
    )


async def _collect(engine, req):
    out = []
    finish = None
    async for item in engine.generate(Context(req.to_wire())):
        out.extend(item["token_ids"])
        if item.get("finish_reason"):
            finish = item["finish_reason"]
    return out, finish


async def test_engine_expired_arrival_raises_deadline_error():
    eng = MockerEngine(_cfg(), MockerConfig())
    await eng.start()
    try:
        base = OVERLOAD.deadline_total
        with pytest.raises(DeadlineError):
            await _collect(eng, _req(deadline=Deadline.after(-1)))
        assert OVERLOAD.deadline_total > base
    finally:
        await eng.stop()


async def test_engine_queued_past_deadline_is_shed_not_executed():
    """A queued prefill whose deadline expires while it waits is cancelled
    with a typed DEADLINE finish — the engine never runs it. Slots are
    pinned by two long-running requests so the victim genuinely queues."""
    eng = MockerEngine(
        _cfg(max_num_seqs=2),
        MockerConfig(decode_time_per_step_us=20000.0),  # slow decode
    )
    await eng.start()
    try:
        hogs = [
            asyncio.ensure_future(_collect(eng, _req(max_tokens=48)))
            for _ in range(2)
        ]
        await asyncio.sleep(0.05)  # hogs admitted, slots full
        out, finish = await asyncio.wait_for(
            _collect(eng, _req(deadline=Deadline.after(0.05))), 30.0
        )
        assert out == []
        assert finish == FinishReason.DEADLINE.value
        for h in hogs:
            toks, fin = await asyncio.wait_for(h, 60.0)
            assert len(toks) == 48 and fin == FinishReason.LENGTH.value
    finally:
        await eng.stop()


async def test_engine_waiting_depth_bound_sheds_oldest():
    """max_waiting=1: with slots full and two more requests queued, the
    OLDEST waiter is shed with FinishReason.SHED; the newest keeps its
    place and completes."""
    eng = MockerEngine(
        _cfg(max_num_seqs=1, max_waiting=1),
        MockerConfig(decode_time_per_step_us=20000.0),
    )
    await eng.start()
    try:
        base = OVERLOAD.shed_total
        hog = asyncio.ensure_future(_collect(eng, _req(max_tokens=32)))
        await asyncio.sleep(0.05)
        first = asyncio.ensure_future(_collect(eng, _req(max_tokens=2)))
        await asyncio.sleep(0.05)  # first is now the oldest waiter
        second = asyncio.ensure_future(_collect(eng, _req(max_tokens=2)))
        out1, fin1 = await asyncio.wait_for(first, 30.0)
        assert (out1, fin1) == ([], FinishReason.SHED.value)
        assert OVERLOAD.shed_total > base
        out2, fin2 = await asyncio.wait_for(second, 60.0)
        assert len(out2) == 2 and fin2 == FinishReason.LENGTH.value
        await asyncio.wait_for(hog, 60.0)
    finally:
        await eng.stop()


async def test_engine_mid_generation_deadline_finishes_stream():
    """A deadline that expires mid-generation ends the stream with a
    DEADLINE finish and the partial output — bounded, no hang."""
    eng = MockerEngine(
        _cfg(),
        MockerConfig(decode_time_per_step_us=30000.0),
    )
    await eng.start()
    try:
        out, finish = await asyncio.wait_for(
            _collect(eng, _req(max_tokens=64, deadline=Deadline.after(0.4))),
            30.0,
        )
        assert finish == FinishReason.DEADLINE.value
        assert 0 < len(out) < 64
    finally:
        await eng.stop()


async def test_engine_drain_refuses_new_finishes_inflight():
    eng = MockerEngine(
        _cfg(), MockerConfig(decode_time_per_step_us=5000.0)
    )
    await eng.start()
    try:
        inflight = asyncio.ensure_future(_collect(eng, _req(max_tokens=16)))
        await asyncio.sleep(0.05)
        eng.begin_drain()
        assert eng.readiness()["state"] == "draining"
        assert eng.readiness()["draining"] is True
        with pytest.raises(ShedError):
            await _collect(eng, _req())
        toks, fin = await asyncio.wait_for(inflight, 30.0)
        assert len(toks) == 16 and fin == FinishReason.LENGTH.value
        assert await eng.wait_drained(10.0)
        assert eng.drained
    finally:
        await eng.stop()


# ---------------------------------------------------------------------------
# HTTP boundary: 429/503/504 + Retry-After + deadline header + drain
# ---------------------------------------------------------------------------


class _SlowEcho:
    """Engine stub: sleeps, then echoes — enough to hold admission slots
    and to observe deadline wire fields."""

    def __init__(self, delay_s=0.0, hold=None):
        self.delay_s = delay_s
        self.hold = hold  # an asyncio.Event: answer only once it is set
        self.seen_deadlines: list = []

    async def generate(self, ctx):
        from dynamo_tpu.llm.protocols.openai import ChatCompletionChunk, StreamChoice, ChatDelta

        self.seen_deadlines.append(ctx.annotations.get("deadline"))
        if self.delay_s:
            await asyncio.sleep(self.delay_s)
        if self.hold is not None:
            await self.hold.wait()
        yield ChatCompletionChunk(
            id="c0", model="m",
            choices=[StreamChoice(
                delta=ChatDelta(role="assistant", content="ok"),
                finish_reason="stop",
            )],
        )


async def _http_service(engine, admission=None):
    from dynamo_tpu.llm.discovery import ModelManager
    from dynamo_tpu.llm.http_service import HttpService

    manager = ModelManager()
    manager.add_model("m", engine)
    service = HttpService(
        manager, host="127.0.0.1", port=0, admission=admission
    )
    await service.start()
    return service


BODY = {
    "model": "m",
    "messages": [{"role": "user", "content": "x"}],
    "stream": False,
}


async def test_http_admission_429_with_retry_after_and_drain_503():
    # The slow request holds the one slot until the 429 has been seen (no
    # clock decides it: on a busy machine neither 0.1 s for its POST to
    # arrive nor 0.5 s for the second to be refused was enough).
    engine = _SlowEcho(hold=asyncio.Event())
    admission = AdmissionController(AdmissionConfig(max_inflight=1))
    service = await _http_service(engine, admission)
    base = f"http://127.0.0.1:{service.port}"
    try:
        async with httpx.AsyncClient() as client:
            slow = asyncio.ensure_future(
                client.post(f"{base}/v1/chat/completions", json=BODY)
            )
            for _ in range(3000):
                if admission.inflight:
                    break
                await asyncio.sleep(0.01)
            assert admission.inflight == 1
            r = await client.post(f"{base}/v1/chat/completions", json=BODY)
            assert r.status_code == 429
            assert "Retry-After" in r.headers
            assert r.json()["error"]["type"] == "overloaded_error"
            engine.hold.set()
            assert (await slow).status_code == 200

            # Drain: health flips 503 first, new requests get 503 +
            # Retry-After, the drain completes once idle.
            drain = asyncio.ensure_future(service.drain(grace_s=10.0))
            await asyncio.sleep(0.05)
            h = await client.get(f"{base}/health")
            assert h.status_code == 503
            assert h.json()["status"] == "draining"
            r = await client.post(f"{base}/v1/chat/completions", json=BODY)
            assert r.status_code == 503
            assert "Retry-After" in r.headers
            assert await asyncio.wait_for(drain, 15.0)

            m = await client.get(f"{base}/metrics")
            assert "shed_requests_total" in m.text
            assert "deadline_exceeded_total" in m.text
            assert "_draining 1.0" in m.text
    finally:
        await service.stop()


async def test_http_deadline_header_reaches_engine_and_expired_maps_504():
    engine = _SlowEcho()
    admission = AdmissionController(
        AdmissionConfig(default_deadline_s=7.0)
    )
    service = await _http_service(engine, admission)
    base = f"http://127.0.0.1:{service.port}"
    try:
        async with httpx.AsyncClient() as client:
            # Header budget wins over the default.
            r = await client.post(
                f"{base}/v1/chat/completions", json=BODY,
                headers={"X-Request-Timeout-Ms": "2000"},
            )
            assert r.status_code == 200
            d = engine.seen_deadlines[-1]
            assert d is not None and 0 < d.remaining_s() <= 2.0
            # No header -> configured default.
            r = await client.post(f"{base}/v1/chat/completions", json=BODY)
            assert r.status_code == 200
            d = engine.seen_deadlines[-1]
            assert d is not None and 2.0 < d.remaining_s() <= 7.0

            # An engine-raised DeadlineError maps to 504.
            class Expired:
                async def generate(self, ctx):
                    raise DeadlineError("expired in queue")
                    yield  # pragma: no cover

            service.manager.add_model("dead", Expired())
            r = await client.post(
                f"{base}/v1/chat/completions",
                json={**BODY, "model": "dead"},
            )
            assert r.status_code == 504
            assert r.json()["error"]["type"] == "deadline_exceeded"

            # A downstream ShedError maps to 429 + Retry-After.
            class Shedding:
                async def generate(self, ctx):
                    raise ShedError("bounded queue full", retry_after_s=3.0)
                    yield  # pragma: no cover

            service.manager.add_model("shed", Shedding())
            r = await client.post(
                f"{base}/v1/chat/completions", json={**BODY, "model": "shed"}
            )
            assert r.status_code == 429
            assert r.headers.get("Retry-After") == "3"
    finally:
        await service.stop()


# ---------------------------------------------------------------------------
# Preprocessor: SHED / DEADLINE zero-token finishes become typed errors
# ---------------------------------------------------------------------------


async def test_preprocessor_maps_shed_finish_to_typed_error():
    from dynamo_tpu.llm.model_card import ModelDeploymentCard
    from dynamo_tpu.llm.preprocessor import OpenAIPreprocessor
    from dynamo_tpu.llm.protocols.common import EngineOutput
    from dynamo_tpu.llm.protocols.openai import ChatCompletionRequest
    from dynamo_tpu.llm.tokenizer import load_tokenizer
    from dynamo_tpu.runtime.engine import EngineAdapter

    card = ModelDeploymentCard(name="m", model_path="toy")
    pre = OpenAIPreprocessor(card, load_tokenizer("toy"))
    oai = ChatCompletionRequest.model_validate(
        {"model": "m", "messages": [{"role": "user", "content": "hi"}]}
    )

    async def shed_engine(ctx):
        yield EngineOutput(finish_reason=FinishReason.SHED).to_wire()

    async def deadline_engine(ctx):
        yield EngineOutput(finish_reason=FinishReason.DEADLINE).to_wire()

    with pytest.raises(ShedError):
        async for _ in pre.generate(Context(oai), EngineAdapter(shed_engine)):
            pass
    with pytest.raises(DeadlineError):
        async for _ in pre.generate(
            Context(oai), EngineAdapter(deadline_engine)
        ):
            pass


# ---------------------------------------------------------------------------
# Disagg queue bounds
# ---------------------------------------------------------------------------


async def test_prefill_queue_try_enqueue_bounds():
    from dynamo_tpu.disagg.queue import PrefillQueue
    from dynamo_tpu.runtime.distributed import DistributedRuntime

    drt = await DistributedRuntime.in_process()
    try:
        q = PrefillQueue(drt, "bounds", max_depth=2)
        base = OVERLOAD.shed_total
        assert await q.try_enqueue({"request_id": "a"})
        assert await q.try_enqueue({"request_id": "b"})
        assert not await q.try_enqueue({"request_id": "c"})  # over depth
        assert OVERLOAD.shed_total == base + 1
        assert await q.depth() == 2

        # Age bound: a stalled consumer pool (old head item) refuses new
        # remote work even at low depth.
        q2 = PrefillQueue(drt, "age", max_depth=0, max_age_s=0.05)
        assert await q2.try_enqueue({"request_id": "old"})
        await asyncio.sleep(0.15)
        assert not await q2.try_enqueue({"request_id": "new"})
    finally:
        await drt.shutdown()


# ---------------------------------------------------------------------------
# Egress: all instances evicted -> typed retryable error
# ---------------------------------------------------------------------------


async def test_egress_no_instances_is_typed_shed_error():
    from dynamo_tpu.runtime.component import EndpointId
    from dynamo_tpu.runtime.distributed import DistributedRuntime
    from dynamo_tpu.runtime.egress import Client, PushRouter

    drt = await DistributedRuntime.in_process()
    try:
        client = await Client.create(drt, EndpointId("ns", "comp", "gen"))
        client.wait_for_instances = lambda timeout_s=0.1: asyncio.wait_for(
            asyncio.Event().wait(), 0.05
        )
        router = PushRouter(drt, client)
        with pytest.raises(ShedError, match="no live instances"):
            async for _ in router.generate(Context({})):
                pass
    finally:
        await drt.shutdown()
