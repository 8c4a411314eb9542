"""The seam between the router and an instance of its own process
(docs/architecture/request_plane.md "The local call").

Every case runs twice, once over the wire (envelope, connect-back,
msgpack frames) and once as the local call (`serve(offer_local=True)`):
the caller must not be able to tell the two apart except by the
dispatch counters.
"""

import asyncio

import httpx
import msgpack
import pytest

from dynamo_tpu.llm.engines import EchoEngineCore
from dynamo_tpu.llm.protocols.common import (
    DeadlineError,
    PreprocessedRequest,
    RequestError,
    SamplingOptions,
    ShedError,
    StopConditions,
    WorkerDiedError,
)
from dynamo_tpu.runtime.distributed import DistributedRuntime
from dynamo_tpu.runtime.egress import PushRouter
from dynamo_tpu.runtime.engine import Context
from dynamo_tpu.runtime.failover import FAILOVER, FailoverEngine
from dynamo_tpu.runtime.ingress import _default
from dynamo_tpu.utils.faults import FAULTS

pytestmark = pytest.mark.anyio

PATHS = ("local", "wire")


@pytest.fixture(autouse=True)
def _clear_faults():
    yield
    FAULTS.clear()


def _wire(prompt, osl=16):
    return PreprocessedRequest(
        token_ids=list(prompt),
        sampling=SamplingOptions(temperature=0.0),
        stop=StopConditions(max_tokens=osl, ignore_eos=True),
    ).to_wire()


def _dispatched() -> dict[str, int]:
    return {p: FAILOVER.dispatch_total(p) for p in PATHS}


async def _serve(drt, engine, path, endpoint="gen"):
    return await drt.namespace("ld").component("w").endpoint(endpoint).serve(
        engine, offer_local=path == "local"
    )


class _Frame:
    def __init__(self, n):
        self.n = n

    def to_wire(self):
        return {"n": self.n, "pair": (self.n, str(self.n))}


class _OddEngine:
    """Frames msgpack changes on the way: an object with ``to_wire``, a
    tuple, a nested dict — and a payload it must receive as its own copy."""

    async def generate(self, request):
        request.payload["seen"] = True  # must not reach the caller's dict
        yield _Frame(1)
        yield {"token_ids": (1, 2), "nested": {"a": [1, (2, 3)]}}
        yield {"token_ids": [7], "text": None, "cum_tokens": 1}
        yield [1, 2, 3]


class _Gated:
    """Streams `n` frames, waiting on a gate before each after the
    first; records how its generator ended."""

    def __init__(self, n=4):
        self.n = n
        self.gate = asyncio.Event()
        self.first = asyncio.Event()
        self.ended: list[str] = []
        self.stopped_seen = False

    async def generate(self, request):
        try:
            for i in range(self.n):
                if i:
                    await self.gate.wait()
                if request.is_stopped:
                    self.stopped_seen = True
                    return
                yield {"token_ids": [i], "cum_tokens": i + 1}
                self.first.set()
            self.ended.append("done")
        except asyncio.CancelledError:
            self.ended.append("cancelled")
            raise
        except GeneratorExit:
            self.ended.append("closed")
            raise


async def _mocker(seed=0):
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.mocker import MockerConfig, MockerEngine
    from dynamo_tpu.models.config import ModelConfig

    eng = MockerEngine(
        EngineConfig(
            model=ModelConfig.tiny_test(), num_blocks=128, max_num_seqs=4,
            max_model_len=256, dtype="float32",
        ),
        MockerConfig(
            vocab_size=100, seed=seed, deterministic_tokens=True,
            decode_time_per_step_us=4000.0,
        ),
    )
    await eng.start()
    return eng


@pytest.mark.parametrize("path", PATHS)
@pytest.mark.parametrize("kind", ["echo", "mocker", "odd"])
async def test_stream_is_equal_frame_for_frame(kind, path):
    """What the router yields is msgpack's round trip of what the engine
    yields, on both paths; the counters say which path ran."""
    if kind == "echo":
        engine, payload = EchoEngineCore(), _wire([3, 1, 4, 1, 5, 9, 2, 6])
    elif kind == "mocker":
        engine, payload = await _mocker(), _wire([5, 6, 7, 8], osl=12)
    else:
        engine, payload = _OddEngine(), {"q": [1, 2]}
    reference = [
        msgpack.unpackb(msgpack.packb(item, default=_default))
        async for item in engine.generate(
            Context(msgpack.unpackb(msgpack.packb(payload)))
        )
    ]
    assert len(reference) >= 4

    drt = await DistributedRuntime.in_process()
    served = await _serve(drt, engine, path)
    push = await PushRouter.create(drt, "ld.w.gen")
    before = _dispatched()
    ctx = Context(payload)
    try:
        got = [item async for item in push.generate(ctx)]
    finally:
        await served.stop()
        if kind == "mocker":
            await engine.stop()
        await drt.shutdown()
    assert got == reference
    assert [type(g) for g in got] == [type(r) for r in reference]
    assert "seen" not in payload
    assert ctx.annotations["worker_id"] == served.instance.instance_id
    other = "wire" if path == "local" else "local"
    after = _dispatched()
    assert after[path] == before[path] + 1
    assert after[other] == before[other]


@pytest.mark.parametrize("path", PATHS)
async def test_call_is_inflight_and_drain_waits_for_it(path):
    drt = await DistributedRuntime.in_process()
    engine = _Gated()
    served = await _serve(drt, engine, path)
    push = await PushRouter.create(drt, "ld.w.gen")
    got = []

    async def consume():
        async for item in push.generate(Context({"q": 1})):
            got.append(item)

    try:
        task = asyncio.ensure_future(consume())
        await asyncio.wait_for(engine.first.wait(), 5)
        assert served.inflight == 1
        drain = asyncio.ensure_future(served.drain(grace_s=10.0))
        await asyncio.sleep(0.1)
        assert not drain.done(), "drain() did not wait for the call"
        # A draining instance takes no new request: a typed, retryable
        # refusal on either path.
        with pytest.raises(ShedError):
            async for _ in push.generate(Context({"q": 2})):
                pass
        engine.gate.set()
        assert await asyncio.wait_for(drain, 10) is True
        await asyncio.wait_for(task, 5)
        assert len(got) == engine.n
        await asyncio.sleep(0)
        assert served.inflight == 0
    finally:
        await drt.shutdown()


@pytest.mark.parametrize("path", PATHS)
@pytest.mark.parametrize("where", ["between_frames", "inside_the_stream"])
async def test_kill_mid_stream_is_worker_death(where, path):
    """`kill()` under a stream: the caller sees the transport's typed
    death, the router evicts the instance, the engine's stream is
    cancelled — whether the caller was waiting inside the stream or
    busy with the last frame."""
    drt = await DistributedRuntime.in_process()
    engine = _Gated()
    served = await _serve(drt, engine, path)
    push = await PushRouter.create(drt, "ld.w.gen", connect_timeout_s=2.0)
    got = []

    async def consume():
        async for item in push.generate(Context({"q": 1})):
            got.append(item)
            if where == "between_frames":
                await served.kill()

    try:
        task = asyncio.ensure_future(consume())
        if where == "inside_the_stream":
            await asyncio.wait_for(engine.first.wait(), 5)
            await asyncio.sleep(0.05)  # the caller waits for frame 2
            await served.kill()
        with pytest.raises(WorkerDiedError) as err:
            await asyncio.wait_for(task, 5)
        assert err.value.transport_dead is True
        assert len(got) == 1
        assert served.instance.instance_id not in push.client.instance_ids()
        await asyncio.sleep(0.05)
        assert engine.ended and engine.ended[0] in ("cancelled", "closed")
        assert served.inflight == 0
        # The consumer's task was not left cancelled by the kill.
        assert not task.cancelled()
    finally:
        await drt.shutdown()


@pytest.mark.parametrize("path", PATHS)
async def test_kill_fails_over_to_a_second_worker(path):
    """The first worker (local, or on the wire) dies mid-decode; the
    failover plane completes the stream on a second, remote one, equal
    to the uninterrupted stream."""
    prompt, osl = [5, 6, 7, 8], 24
    drt = await DistributedRuntime.in_process()
    first, second = await _mocker(0), await _mocker(1)
    served = await _serve(drt, first, path)
    other_drt = await DistributedRuntime.in_process(
        store=drt.store, bus=drt.bus, runtime=drt.runtime
    )
    try:
        push = await PushRouter.create(drt, "ld.w.gen", connect_timeout_s=2.0)
        ref = []
        async for item in push.generate(Context(_wire(prompt, osl))):
            ref += item["token_ids"]
        assert len(ref) == osl
        served2 = await _serve(other_drt, second, "wire")
        await asyncio.sleep(0.05)  # the watch sees the second worker
        ctx = Context(_wire(prompt, osl))
        got, killed = [], False
        async for item in FailoverEngine(push).generate(ctx):
            got += item.get("token_ids", [])
            if len(got) >= 5 and not killed:
                killed = True
                victim = (
                    served
                    if ctx.annotations["worker_id"]
                    == served.instance.instance_id
                    else served2
                )
                await victim.kill()
        assert killed and got == ref
        assert ctx.annotations["worker_id"] != victim.instance.instance_id
    finally:
        await first.stop()
        await second.stop()
        await drt.shutdown()


@pytest.mark.parametrize("path", PATHS)
async def test_worker_kill_fault_fires_before_either_dispatch(path):
    drt = await DistributedRuntime.in_process()
    served = await _serve(drt, EchoEngineCore(), path)
    push = await PushRouter.create(drt, "ld.w.gen")
    before = _dispatched()
    marked = FAILOVER.marked_dead_by_reason.get("dispatch:FaultError", 0)
    FAULTS.arm("fleet.worker_kill", times=1)
    try:
        with pytest.raises(ShedError):
            # The only instance is marked dead at dispatch; this request
            # has nothing left to re-pick.
            async for _ in push.generate(Context(_wire([1, 2, 3]))):
                pass
        assert _dispatched() == before
        assert (
            FAILOVER.marked_dead_by_reason["dispatch:FaultError"] == marked + 1
        )
        # The worker was alive all along: the store brings it back and
        # the next request goes the same way as ever.
        got = [i async for i in push.generate(Context(_wire([1, 2, 3])))]
        assert len(got) == 4
        assert _dispatched()[path] == before[path] + 1
    finally:
        await served.stop()
        await drt.shutdown()


@pytest.mark.parametrize("path", PATHS)
async def test_callers_stop_ends_the_stream(path):
    """A caller that stops its Context gets no further frame on either
    path; on the local call the engine is told, and its stream ends."""
    drt = await DistributedRuntime.in_process()
    engine = _Gated(n=50)
    engine.gate.set()
    served = await _serve(drt, engine, path)
    push = await PushRouter.create(drt, "ld.w.gen")
    ctx = Context({"q": 1})
    got = []
    try:
        stream = push.generate(ctx)
        async for item in stream:
            got.append(item)
            if len(got) == 2:
                ctx.kill()
        await stream.aclose()
        assert len(got) == 2
        if path == "local":
            await asyncio.sleep(0.05)
            assert engine.stopped_seen or engine.ended == ["closed"]
            assert served.inflight == 0
    finally:
        await served.stop()
        await drt.shutdown()


class _Raises:
    def __init__(self, exc):
        self.exc = exc

    async def generate(self, request):
        raise self.exc
        yield  # pragma: no cover


@pytest.mark.parametrize("path", PATHS)
@pytest.mark.parametrize(
    "raised, seen",
    [
        (ShedError("full", retry_after_s=3.0, draining=True), ShedError),
        (DeadlineError("expired in queue"), DeadlineError),
        (RequestError("bad parameter"), RequestError),
        (ValueError("a bug"), RuntimeError),
        (ConnectionResetError("pull reset"), WorkerDiedError),
    ],
    ids=["shed", "deadline", "request", "bug", "connection"],
)
async def test_engine_errors_arrive_typed_alike(raised, seen, path):
    drt = await DistributedRuntime.in_process()
    served = await _serve(drt, _Raises(raised), path)
    push = await PushRouter.create(drt, "ld.w.gen")
    try:
        with pytest.raises(seen) as err:
            async for _ in push.generate(Context({"q": 1})):
                pass
        assert type(err.value) is seen
        if seen is ShedError:
            assert err.value.retry_after_s == 3.0
            assert err.value.draining is True
            assert str(err.value) == "full"
        if seen is WorkerDiedError:
            # Reported by a live worker: fails over, evicts nobody.
            assert err.value.transport_dead is False
            assert served.instance.instance_id in push.client.instance_ids()
    finally:
        await served.stop()
        await drt.shutdown()


@pytest.mark.parametrize("path", PATHS)
async def test_draining_engine_is_503_with_retry_after_over_http(path):
    """Through the pipeline the one-process launcher builds (register,
    watch, preprocessor, failover, router): a draining engine's refusal
    is the same status and header on both paths."""
    from dynamo_tpu.llm.discovery import (
        ModelManager,
        ModelWatcher,
        register_llm,
    )
    from dynamo_tpu.llm.http_service import HttpService
    from dynamo_tpu.llm.model_card import ModelDeploymentCard

    drt = await DistributedRuntime.in_process()
    endpoint = drt.namespace("ld").component("w").endpoint("gen")
    engine = _Raises(
        ShedError("engine draining", retry_after_s=3.0, draining=True)
    )
    await endpoint.serve(engine, offer_local=path == "local")
    await register_llm(
        drt, endpoint, ModelDeploymentCard(name="m", model_path=None)
    )
    manager = ModelManager()
    await ModelWatcher(drt, manager).start()
    service = HttpService(manager, host="127.0.0.1", port=0)
    await service.start()
    try:
        async with httpx.AsyncClient() as client:
            r = await client.post(
                f"http://127.0.0.1:{service.port}/v1/chat/completions",
                json={
                    "model": "m", "stream": False,
                    "messages": [{"role": "user", "content": "x"}],
                },
            )
            assert r.status_code == 503
            assert r.headers.get("Retry-After") == "3"
            assert r.json()["error"]["type"] == "overloaded_error"
            m = await client.get(
                f"http://127.0.0.1:{service.port}/metrics"
            )
            assert "router_dispatch_local_total" in m.text
            assert "router_dispatch_wire_total" in m.text
    finally:
        await service.stop()
        await drt.shutdown()


async def test_offer_is_withdrawn_with_the_instance():
    """stop(), kill() and the runtime's shutdown take the offer back: a
    router that still picks the instance goes to the wire and finds a
    dead subject, as for any other corpse."""
    drt = await DistributedRuntime.in_process()
    served = await _serve(drt, EchoEngineCore(), "local")
    assert drt.local_instances[served.instance.subject] is served
    await served.kill()
    assert not drt.local_instances
    served = await _serve(drt, EchoEngineCore(), "local", endpoint="gen2")
    await served.stop()
    assert not drt.local_instances
    await _serve(drt, EchoEngineCore(), "local", endpoint="gen3")
    assert drt.local_instances
    await drt.shutdown()
    assert not drt.local_instances
