"""The streamed path of the HTTP frontend
(docs/architecture/request_plane.md "The streamed path").

What is fixed for a request is made once (``llm/protocols/stream.py``) and
a plain text delta is written as a template around its text; these tests
hold the bytes on the wire to the object rendering they replaced, the
count of events to the count of tokens, and the per-token work to zero
object constructions. Counts, never times.
"""

import json
import logging
import re

import httpx
import pydantic
import pytest

from dynamo_tpu.llm.backend import Detokenizer
from dynamo_tpu.llm.discovery import ModelManager, ModelWatcher, register_llm
from dynamo_tpu.llm.http_service import HttpService
from dynamo_tpu.llm.model_card import ModelDeploymentCard
from dynamo_tpu.llm.preprocessor import OpenAIPreprocessor
from dynamo_tpu.llm.protocols.annotated import Annotated
from dynamo_tpu.llm.protocols.common import EngineOutput, FinishReason
from dynamo_tpu.llm.protocols.openai import (
    ChatCompletionRequest,
    CompletionRequest,
)
from dynamo_tpu.llm.protocols.sse import DONE, SseEvent, decode_stream
from dynamo_tpu.llm.protocols.stream import ChunkStream, ContentDelta, sse_event
from dynamo_tpu.llm.tokenizer import ToyTokenizer
from dynamo_tpu.runtime.distributed import DistributedRuntime
from dynamo_tpu.runtime.egress import PushRouter
from dynamo_tpu.runtime.engine import Context
from dynamo_tpu.runtime.ingress import _as_wire, _is_token_frame
from dynamo_tpu.runtime.pipeline import Pipeline
from dynamo_tpu.utils.logging import _ScopeFilter
from dynamo_tpu.utils.tracing import tracer

pytestmark = pytest.mark.anyio

TOK = ToyTokenizer()


def _frames(text: str, finish: str | None = "stop", group: int = 1):
    """Token frames of `text`'s bytes, `group` tokens a frame, then the
    engine's own finish frame."""
    ids = TOK.encode(text)
    out = []
    for i in range(0, len(ids), group):
        part = ids[i:i + group]
        out.append(EngineOutput(
            token_ids=part, cum_tokens=i + len(part)
        ).to_wire())
    if finish is not None:
        out.append(EngineOutput(
            token_ids=[], finish_reason=FinishReason(finish),
            cum_tokens=len(ids),
        ).to_wire())
    return out


def _with_logprobs(frames):
    for f in frames:
        if f["token_ids"]:
            f["logprobs"] = [
                {"id": t, "logprob": -0.25, "top": [[t, -0.25], [65, -1.5]]}
                for t in f["token_ids"]
            ]
    return frames


class _Scripted:
    """Yields the frames it was given, whatever the request."""

    def __init__(self, frames, log=None):
        self.frames = frames
        self.log = log
        self.window = None  # set by a test that counts work a token

    async def generate(self, request):
        for i, frame in enumerate(self.frames):
            if self.log is not None:
                self.log.info("frame %d", i)
            if self.window is not None:
                # Open once the second frame is asked for (the first
                # chunk is out), shut before the last token's frame.
                self.window["open"] = 1 <= i < len(self.frames) - 2
            yield dict(frame)


class _Served:
    """The one-process deployment around an engine: register, watch,
    preprocessor, detokenizer, failover, router, the local call."""

    def __init__(self, engine):
        self.engine = engine

    async def __aenter__(self):
        self.drt = await DistributedRuntime.in_process()
        ep = self.drt.namespace("sr").component("w").endpoint("gen")
        await ep.serve(self.engine, offer_local=True)
        await register_llm(
            self.drt, ep, ModelDeploymentCard(name="m", model_path="toy")
        )
        self.manager = ModelManager()
        await ModelWatcher(self.drt, self.manager).start()
        self.service = HttpService(self.manager, host="127.0.0.1", port=0)
        await self.service.start()
        self.base = f"http://127.0.0.1:{self.service.port}"
        return self

    async def __aexit__(self, *exc):
        await self.service.stop()
        await self.drt.shutdown()

    async def post(self, endpoint: str, body: dict) -> bytes:
        async with httpx.AsyncClient() as client:
            r = await client.post(self.base + endpoint, json=body, timeout=30)
            assert r.status_code == 200, r.text
            return r.content


def _body(endpoint: str, **extra) -> dict:
    if endpoint.endswith("chat/completions"):
        return {"model": "m", "messages": [{"role": "user", "content": "q"}],
                **extra}
    return {"model": "m", "prompt": "q", **extra}


def _normalised(body: bytes) -> bytes:
    """`id` and `created` aside (and a tool call's id): what a request
    draws anew."""
    body = re.sub(rb"(chatcmpl|cmpl)-[0-9a-f]{32}", rb"\1-X", body)
    body = re.sub(rb"call-[0-9a-f-]{36}", b"call-X", body)
    return re.sub(rb'"created":\d+', b'"created":0', body)


async def _object_rendering(manager, request) -> bytes:
    """The same frames rendered chunk by chunk from the objects, as the
    HTTP service rendered every chunk before the template:
    ``SseEvent.data_json(chunk.model_dump(exclude_none=True)).encode()``.
    A request that is not streamed gets every chunk in the object form."""
    assert not request.stream
    out = b""
    async for chunk in manager.get("m").generate(Context(request)):
        assert not isinstance(chunk, ContentDelta)
        if isinstance(chunk, Annotated):
            out += chunk.to_sse().encode()
            continue
        obj = (
            chunk.model_dump(exclude_none=True)
            if isinstance(chunk, pydantic.BaseModel) else chunk
        )
        out += SseEvent.data_json(obj).encode()
    return out + SseEvent.done().encode()


TOOLS = [{"type": "function", "function": {
    "name": "get_weather", "parameters": {"type": "object"}}}]

# name -> (frames, request fields, the texts the choices must carry or None)
CASES = {
    # role chunk, plain deltas, the engine's own `stop` finish, usage
    "plain": (_frames("hello"), {}, ["h", "e", "l", "l", "o", None]),
    # partial UTF-8 pieces: tokens whose text is not out yet
    "empty_text": (
        _frames("aé✓b"), {},
        ["a", None, "é", None, None, "✓", "b", None],
    ),
    "escapes": (
        _frames('q"b\\s\n\t\x01\x7f</x> '), {}, None,
    ),
    "non_ascii_wide": (_frames("\U0001f600 中文"), {}, None),
    # `length`: max_tokens ends the stream on a token's own chunk
    "finish_length": (
        _frames("abcdef", finish=None), {"max_tokens": 3}, ["a", "b", "c"],
    ),
    "logprobs": (
        _with_logprobs(_frames("hey")), {"logprobs": True, "top_logprobs": 2},
        None,
    ),
    # held by the jail: "S", "T", "O" go out empty, "P" ends the stream
    "stop_string": (
        _frames("go STOP never"), {"stop": ["STOP"]},
        ["g", "o", " ", None, None, None, None],
    ),
    "annotations": (
        _frames("hi"), {"nvext": {"annotations": ["formatted_prompt"]}}, None,
    ),
    "engine_text": (  # a text-native engine: no tokens, the text its own
        [{"token_ids": [], "text": "ab", "cum_tokens": 1},
         {"token_ids": [], "text": "", "cum_tokens": 2},
         {"token_ids": [], "text": "cd", "cum_tokens": 3},
         {"token_ids": [], "finish_reason": "stop", "cum_tokens": 3}],
        {}, ["ab", "", "cd", None],
    ),
}
CHAT_ONLY = {
    "tool_call": (
        _frames('{"name": "get_weather", "parameters": {"city": "x"}}'),
        {"tools": TOOLS}, None,
    ),
    "tools_plain_content": (  # tools on, an ordinary answer streams through
        _frames("sunny"), {"tools": TOOLS}, None,
    ),
}


def _params():
    for endpoint in ("/v1/chat/completions", "/v1/completions"):
        cases = {**CASES, **CHAT_ONLY} if "chat" in endpoint else CASES
        for name in cases:
            yield pytest.param(endpoint, name, id=f"{endpoint[4:]}-{name}")


@pytest.mark.parametrize("endpoint, case", list(_params()))
async def test_streamed_bytes_equal_the_object_rendering(endpoint, case):
    frames, extra, texts = {**CASES, **CHAT_ONLY}[case]
    chat = "chat" in endpoint
    if not chat and "top_logprobs" in extra:
        extra = {"logprobs": 2}
    async with _Served(_Scripted(frames)) as served:
        got = await served.post(endpoint, _body(endpoint, stream=True, **extra))
        request_type = ChatCompletionRequest if chat else CompletionRequest
        want = await _object_rendering(
            served.manager,
            request_type.model_validate(_body(endpoint, **extra)),
        )
        rendered = dict(served.service.metrics.stream_events)
    assert _normalised(got) == _normalised(want)
    events = [
        json.loads(ev.data) for ev in decode_stream(got.decode())
        if ev.event is None and ev.data != DONE
    ]
    assert len({e["id"] for e in events}) == 1
    if chat:
        assert len({e["created"] for e in events}) == 1
    assert events[-1]["choices"] == [] and events[-1]["usage"]
    choices = [e["choices"][0] for e in events[:-1]]
    if texts is not None:
        key = (lambda c: c["delta"].get("content")) if chat else (
            lambda c: c["text"] or None
        )
        want_texts = texts if chat else [t or None for t in texts]
        assert [key(c) for c in choices] == want_texts
    # Only the first chunk, a chunk with a finish reason, logprobs or tool
    # calls, the usage chunk and an annotation's event take the object form.
    objects = sum(
        1 for i, c in enumerate(choices)
        if i == 0 or c.get("finish_reason") or c.get("logprobs")
        or (chat and c["delta"].get("tool_calls"))
    )
    named = sum(1 for ev in decode_stream(got.decode()) if ev.event)
    assert rendered == {
        "object": objects + 1 + named, "template": len(choices) - objects,
    }


@pytest.mark.parametrize("chat", [True, False], ids=["chat", "completions"])
@pytest.mark.parametrize(
    "text", [None, "", "a", 'q"\\\n\x00\x1f\x7f  \ud800',
             "\U0001f600", "dyntpu-text-mark"],
    ids=["none", "empty", "ascii", "escapes", "wide", "the_mark"],
)
def test_template_is_cut_out_of_the_object_form(chat, text):
    """The renderer alone, a model name that holds the mark included: a
    ``ContentDelta``'s event is its object form's event."""
    stream = ChunkStream("chatcmpl-1", 'm"dyntpu-text-mark', chat=chat)
    delta = ContentDelta(stream, text)
    obj = delta.chunk()
    dumped = obj if isinstance(obj, dict) else obj.model_dump(exclude_none=True)
    assert sse_event(delta) == SseEvent.data_json(dumped).encode()
    assert sse_event(delta) == sse_event(obj)
    assert delta.model_dump(exclude_none=True) == dumped
    assert sse_event(delta).count(b"\n") == 2  # one `data:` line


@pytest.mark.parametrize(
    "group, max_tokens, want",
    [(4, None, 12), (5, None, 12), (12, None, 12), (4, 6, 6), (5, 11, 11),
     (1, 7, 7)],
)
async def test_one_event_a_token_whatever_a_frame_holds(group, max_tokens, want):
    """A frame of several tokens (a block-diffusion pass commits up to a
    block a lane) is an event a token on the wire, and ``max_tokens``
    inside such a frame ends the stream on that token's event."""
    frames = _frames("abcdefghijkl", finish="stop", group=group)
    extra = {} if max_tokens is None else {"max_tokens": max_tokens}
    async with _Served(_Scripted(frames)) as served:
        got = await served.post(
            "/v1/chat/completions",
            _body("/v1/chat/completions", stream=True, **extra),
        )
    events = [
        json.loads(ev.data) for ev in decode_stream(got.decode())
        if ev.data != DONE
    ]
    with_choices = [e for e in events if e["choices"]]
    tokens = [e for e in with_choices
              if e["choices"][0]["delta"].get("content")]
    assert len(tokens) == want
    assert "".join(
        e["choices"][0]["delta"]["content"] for e in tokens
    ) == "abcdefghijkl"[:want]
    assert events[-1]["usage"]["completion_tokens"] == want
    if max_tokens is not None:
        # harness.py's rule: the events with choices are the tokens asked
        assert len(with_choices) == max_tokens
        assert with_choices[-1]["choices"][0]["finish_reason"] == "length"
    else:
        assert with_choices[-1]["choices"][0]["finish_reason"] == "stop"


async def test_detokenizer_splits_a_frame_with_its_logprobs_and_counts():
    frames = _with_logprobs(_frames("abc", finish=None, group=3))
    frames[0]["finish_reason"] = "length"
    frames[0]["cum_tokens"] = 7

    class _E:
        async def generate(self, request):
            for f in frames:
                yield f

    pre = OpenAIPreprocessor(
        ModelDeploymentCard(name="m"), TOK
    ).preprocess(ChatCompletionRequest.model_validate(
        _body("/v1/chat/completions")
    ))
    outs = [
        o async for o in Pipeline.link(Detokenizer(TOK), engine=_E()).generate(
            Context(pre.to_wire())
        )
    ]
    assert [o["token_ids"] for o in outs] == [[97], [98], [99]]
    assert [o["text"] for o in outs] == ["a", "b", "c"]
    assert [o["cum_tokens"] for o in outs] == [5, 6, 7]
    assert [o["finish_reason"] for o in outs] == [None, None, "length"]
    assert [o["logprobs"][0]["id"] for o in outs] == [97, 98, 99]
    assert all(len(o["logprobs"]) == 1 for o in outs)


async def test_a_plain_stream_builds_no_object_after_its_first_chunk(monkeypatch):
    """200 tokens: between the first chunk and the finish chunk no
    pydantic model is validated and none is dumped; the counter says how
    each event was rendered."""
    engine = _Scripted(_frames("x" * 200))
    engine.window = {"open": False}
    counted = {"validate": 0, "dump": 0}
    real_init = pydantic.BaseModel.__init__
    real_validate = pydantic.BaseModel.model_validate.__func__
    real_dump = pydantic.BaseModel.model_dump

    def init(self, /, **data):
        counted["validate"] += engine.window["open"]
        real_init(self, **data)

    def validate(cls, *a, **kw):
        counted["validate"] += engine.window["open"]
        return real_validate(cls, *a, **kw)

    def dump(self, *a, **kw):
        counted["dump"] += engine.window["open"]
        return real_dump(self, *a, **kw)

    monkeypatch.setattr(pydantic.BaseModel, "__init__", init)
    monkeypatch.setattr(
        pydantic.BaseModel, "model_validate", classmethod(validate)
    )
    monkeypatch.setattr(pydantic.BaseModel, "model_dump", dump)
    async with _Served(engine) as served:
        got = await served.post(
            "/v1/chat/completions",
            _body("/v1/chat/completions", stream=True),
        )
        async with httpx.AsyncClient() as client:
            scraped = (await client.get(served.base + "/metrics")).text
        metrics = served.service.metrics
    assert got.count(b'"delta":{"content":"x"}') == 199
    assert counted == {"validate": 0, "dump": 0}
    # the role chunk, the finish chunk and the usage chunk are objects
    assert metrics.stream_events == {"template": 199, "object": 3}
    assert metrics.stream_busy_s > 0.0
    assert (
        'dyntpu_http_service_frontend_stream_events_total{render="template"} 199'
        in scraped
    )
    assert (
        'dyntpu_http_service_frontend_stream_events_total{render="object"} 3'
        in scraped
    )
    busy = re.search(
        r"^dyntpu_http_service_frontend_stream_busy_seconds_total (\S+)$",
        scraped, re.M,
    )
    assert busy and float(busy.group(1)) > 0.0


class _Records(logging.Handler):
    def __init__(self):
        super().__init__()
        self.addFilter(_ScopeFilter())
        self.records = []

    def emit(self, record):
        self.records.append(record)


@pytest.mark.parametrize("caller", ["http", "bare_router"])
async def test_engine_log_lines_carry_the_request_scope(caller):
    """A line logged inside the engine's stream carries the request's
    ``request_id`` / ``trace_id``: under the HTTP handler, which is in
    the scope for the whole request (the local call then only compares),
    and under a caller that is in no scope (it is entered a step)."""
    log = logging.getLogger("test_stream_render.engine")
    log.setLevel(logging.INFO)
    handler = _Records()
    log.addHandler(handler)
    try:
        async with _Served(_Scripted(_frames("abc"), log=log)) as served:
            if caller == "http":
                await served.post(
                    "/v1/chat/completions",
                    _body("/v1/chat/completions", stream=True),
                )
            else:
                push = await PushRouter.create(served.drt, "sr.w.gen")
                ctx = Context({"q": 1}, id="bare-request-7")
                want_trace = tracer().trace_id(ctx.id)
                got = [f async for f in push.generate(ctx)]
                assert len(got) == 4
    finally:
        log.removeHandler(handler)
    assert len(handler.records) == 4
    rids = {r.request_id for r in handler.records}
    traces = {r.trace_id for r in handler.records}
    assert len(rids) == 1 and len(traces) == 1
    assert rids != {""} and traces != {""}
    if caller == "bare_router":
        assert rids == {"bare-request-7"} and traces == {want_trace}


@pytest.mark.parametrize(
    "item, is_token_frame",
    [
        (EngineOutput(token_ids=[5], cum_tokens=3).to_wire(), True),
        (EngineOutput(token_ids=[5, 6], cum_tokens=3).to_wire(), False),
        (EngineOutput(token_ids=[], finish_reason=FinishReason.STOP).to_wire(),
         False),
        (EngineOutput(token_ids=[5], text="x").to_wire(), False),
        (EngineOutput(token_ids=[5], logprobs=[{"id": 5}]).to_wire(), False),
        ({"token_ids": (5,), "text": None, "finish_reason": None,
          "cum_tokens": 1, "kv_transfer_params": None}, False),
        ({"token_ids": [True], "text": None, "finish_reason": None,
          "cum_tokens": 1, "kv_transfer_params": None}, False),
        ({"token_ids": [5]}, False),
        ([5], False),
        (None, False),
    ],
    ids=["token", "two_tokens", "finish", "text", "logprobs", "tuple", "bool",
         "short", "list", "none"],
)
def test_token_frame_is_recognised_and_everything_else_round_trips(
    item, is_token_frame
):
    import msgpack

    from dynamo_tpu.runtime.ingress import _default

    assert _is_token_frame(item) is is_token_frame
    delivered = _as_wire(item)
    assert delivered == msgpack.unpackb(msgpack.packb(item, default=_default))
    if is_token_frame:
        assert delivered is item
