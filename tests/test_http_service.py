"""End-to-end HTTP slice: worker registers model → watcher builds pipeline →
OpenAI requests stream over SSE (model: reference lib/llm/tests/http-service.rs
+ call stack SURVEY.md §3.2)."""

import json

import httpx
import pytest

from dynamo_tpu.llm.discovery import ModelManager, ModelWatcher, register_llm
from dynamo_tpu.llm.engines import EchoEngineCore
from dynamo_tpu.llm.http_service import HttpService
from dynamo_tpu.llm.model_card import ModelDeploymentCard
from dynamo_tpu.llm.protocols.sse import DONE, decode_stream
from dynamo_tpu.runtime.distributed import DistributedRuntime

pytestmark = pytest.mark.anyio


async def _setup():
    drt = await DistributedRuntime.in_process()
    # Worker side: serve the engine endpoint and register the model.
    ep = drt.namespace("dyn").component("tpu").endpoint("generate")
    await ep.serve(EchoEngineCore())
    card = ModelDeploymentCard(name="echo-model", model_path="toy")
    await register_llm(drt, ep, card)

    # Frontend side: watcher + HTTP service.
    manager = ModelManager()
    watcher = ModelWatcher(drt, manager)
    await watcher.start()
    service = HttpService(manager, host="127.0.0.1", port=0)
    await service.start()
    return drt, service


async def test_http_chat_stream_and_aggregate():
    drt, service = await _setup()
    base = f"http://127.0.0.1:{service.port}"
    try:
        async with httpx.AsyncClient() as client:
            r = await client.get(f"{base}/v1/models")
            assert [m["id"] for m in r.json()["data"]] == ["echo-model"]

            body = {
                "model": "echo-model",
                "messages": [{"role": "user", "content": "hello tpu"}],
                "stream": True,
            }
            r = await client.post(f"{base}/v1/chat/completions", json=body)
            assert r.status_code == 200
            events = list(decode_stream(r.text))
            assert events[-1].data == DONE
            text = ""
            for ev in events[:-1]:
                chunk = json.loads(ev.data)
                for choice in chunk.get("choices", []):
                    text += choice.get("delta", {}).get("content") or ""
            assert "hello tpu" in text

            body["stream"] = False
            r = await client.post(f"{base}/v1/chat/completions", json=body)
            data = r.json()
            assert "hello tpu" in data["choices"][0]["message"]["content"]
            assert data["usage"]["completion_tokens"] > 0

            r = await client.post(
                f"{base}/v1/chat/completions",
                json={"model": "nope", "messages": [], "stream": False},
            )
            assert r.status_code == 404

            r = await client.get(f"{base}/metrics")
            assert "dyntpu_http_service_requests_total" in r.text
            assert 'status="success"' in r.text
            # Per-request latency tracing rides the same scrape.
            assert "dyntpu_trace_total_ms_count" in r.text
    finally:
        await service.stop()
        await drt.shutdown()


async def test_http_annotated_sse_events():
    """Requested annotations ride the SSE stream as typed named events
    ahead of the deltas, and the non-stream aggregator skips them
    (reference: lib/runtime/src/protocols/annotated.rs envelope +
    nvext annotations)."""
    drt, service = await _setup()
    base = f"http://127.0.0.1:{service.port}"
    try:
        async with httpx.AsyncClient() as client:
            body = {
                "model": "echo-model",
                "messages": [{"role": "user", "content": "hi there"}],
                "stream": True,
                "nvext": {"annotations": ["formatted_prompt", "token_ids"]},
            }
            r = await client.post(f"{base}/v1/chat/completions", json=body)
            assert r.status_code == 200
            events = list(decode_stream(r.text))
            named = {ev.event: ev for ev in events if ev.event}
            assert "formatted_prompt" in named
            assert "hi there" in json.loads(named["formatted_prompt"].data)
            toks = json.loads(named["token_ids"].data)
            assert isinstance(toks, list) and toks
            # Annotations precede the first delta chunk.
            first_named = next(i for i, ev in enumerate(events) if ev.event)
            first_delta = next(
                i for i, ev in enumerate(events)
                if ev.event is None and ev.data and ev.data != DONE
            )
            assert first_named < first_delta

            # Aggregated (non-stream) response is unaffected by annotations.
            body["stream"] = False
            r = await client.post(f"{base}/v1/chat/completions", json=body)
            assert r.status_code == 200
            assert "hi there" in r.json()["choices"][0]["message"]["content"]
    finally:
        await service.stop()
        await drt.shutdown()


async def test_http_completions_endpoint():
    drt, service = await _setup()
    base = f"http://127.0.0.1:{service.port}"
    try:
        async with httpx.AsyncClient() as client:
            r = await client.post(
                f"{base}/v1/completions",
                json={"model": "echo-model", "prompt": "abc", "stream": False},
            )
            assert r.status_code == 200
            assert r.json()["choices"][0]["text"] == "abc"
    finally:
        await service.stop()
        await drt.shutdown()


async def test_http_embeddings_end_to_end():
    """/v1/embeddings over the full stack: register an embeddings model,
    watcher builds the tokenize-only pipeline, vectors come back unit-norm
    and deterministic."""
    import math

    from dynamo_tpu.llm.embedding import EmbeddingEngine
    from dynamo_tpu.models.config import ModelConfig

    drt = await DistributedRuntime.in_process()
    ep = drt.namespace("dyn").component("embed").endpoint("generate")
    mcfg = ModelConfig.tiny_test()
    await ep.serve(EmbeddingEngine(mcfg, dtype="float32"))
    await register_llm(
        drt,
        ep,
        ModelDeploymentCard(name="tiny-embed", model_path="toy"),
        model_type="embeddings",
    )
    manager = ModelManager()
    await ModelWatcher(drt, manager).start()
    service = HttpService(manager, host="127.0.0.1", port=0)
    await service.start()
    base = f"http://127.0.0.1:{service.port}"
    try:
        async with httpx.AsyncClient() as client:
            r = await client.post(
                f"{base}/v1/embeddings",
                json={
                    "model": "tiny-embed",
                    "input": ["hello world", "second input"],
                },
                timeout=60,
            )
            assert r.status_code == 200, r.text
            data = r.json()
            assert data["model"] == "tiny-embed"
            assert [d["index"] for d in data["data"]] == [0, 1]
            for d in data["data"]:
                vec = d["embedding"]
                assert len(vec) == mcfg.hidden_size
                assert abs(math.sqrt(sum(x * x for x in vec)) - 1.0) < 1e-3
            assert data["data"][0]["embedding"] != data["data"][1]["embedding"]
            assert data["usage"]["prompt_tokens"] > 0

            # Same input -> same vector (deterministic pooled forward).
            r2 = await client.post(
                f"{base}/v1/embeddings",
                json={"model": "tiny-embed", "input": "hello world"},
                timeout=60,
            )
            assert (
                r2.json()["data"][0]["embedding"]
                == data["data"][0]["embedding"]
            )

            # A chat model rejects nothing here, but an unknown model 404s.
            r3 = await client.post(
                f"{base}/v1/embeddings",
                json={"model": "nope", "input": "x"},
            )
            assert r3.status_code == 404
    finally:
        await service.stop()
        await drt.shutdown()


class LogprobEcho:
    """Echo engine that attaches logprob entries, mimicking TpuEngine's
    payload shape — exercises the rendering path (preprocessor chat/
    completions shapes, HTTP aggregation) without jax."""

    async def generate(self, request):
        from dynamo_tpu.llm.protocols.common import (
            EngineOutput,
            FinishReason,
            PreprocessedRequest,
        )

        pre = PreprocessedRequest.from_wire(request.payload)
        want = pre.logprobs
        for i, tid in enumerate(pre.token_ids):
            out = EngineOutput(token_ids=[tid], cum_tokens=i + 1)
            if want is not None:
                out.logprobs = [{
                    "id": tid,
                    "logprob": -0.5,
                    "top": [[tid, -0.5], [tid + 1, -1.5]][:want],
                }]
            yield out.to_wire()
        yield EngineOutput(finish_reason=FinishReason.STOP).to_wire()


async def _setup_logprob():
    drt = await DistributedRuntime.in_process()
    ep = drt.namespace("dyn").component("lp").endpoint("generate")
    await ep.serve(LogprobEcho())
    await register_llm(
        drt, ep, ModelDeploymentCard(name="lp-model", model_path="toy")
    )
    manager = ModelManager()
    await ModelWatcher(drt, manager).start()
    service = HttpService(manager, host="127.0.0.1", port=0)
    await service.start()
    return drt, service


async def test_http_logprobs_chat_and_completions():
    """OpenAI logprob payloads end to end: chat logprobs.content entries
    (token/logprob/bytes/top_logprobs) in both streamed chunks and the
    aggregated response; legacy parallel lists on /v1/completions."""
    drt, service = await _setup_logprob()
    base = f"http://127.0.0.1:{service.port}"
    try:
        async with httpx.AsyncClient() as client:
            body = {
                "model": "lp-model",
                "messages": [{"role": "user", "content": "hi"}],
                "stream": False,
                "logprobs": True,
                "top_logprobs": 2,
            }
            r = await client.post(f"{base}/v1/chat/completions", json=body)
            assert r.status_code == 200
            choice = r.json()["choices"][0]
            content = choice["logprobs"]["content"]
            assert len(content) == r.json()["usage"]["completion_tokens"]
            e = content[0]
            assert set(e) == {"token", "logprob", "bytes", "top_logprobs"}
            assert e["logprob"] == -0.5
            assert len(e["top_logprobs"]) == 2
            assert bytes(e["bytes"]).decode() == e["token"]

            body["stream"] = True
            r = await client.post(f"{base}/v1/chat/completions", json=body)
            chunks = [
                json.loads(ev.data)
                for ev in decode_stream(r.text)
                if ev.data != DONE
            ]
            streamed = [
                c["choices"][0]["logprobs"]["content"][0]
                for c in chunks
                if c.get("choices") and c["choices"][0].get("logprobs")
            ]
            assert streamed and streamed[0]["logprob"] == -0.5

            r = await client.post(
                f"{base}/v1/completions",
                json={
                    "model": "lp-model", "prompt": "abc",
                    "stream": False, "logprobs": 2,
                },
            )
            lp = r.json()["choices"][0]["logprobs"]
            assert lp["tokens"] and len(lp["tokens"]) == len(
                lp["token_logprobs"]
            ) == len(lp["top_logprobs"]) == len(lp["text_offset"])
            assert lp["token_logprobs"][0] == -0.5
            assert lp["text_offset"][0] == 0
    finally:
        await service.stop()
        await drt.shutdown()


async def test_http_unsupported_params_rejected():
    """Unsupported OpenAI knobs 400 instead of being silently dropped."""
    drt, service = await _setup()
    base = f"http://127.0.0.1:{service.port}"
    msg = [{"role": "user", "content": "x"}]
    try:
        async with httpx.AsyncClient() as client:
            for bad in (
                {"n": 2},
                {"best_of": 4},
                {"logit_bias": {"42": 5.0}},
                {"logprobs": True, "top_logprobs": 99},
            ):
                r = await client.post(
                    f"{base}/v1/chat/completions",
                    json={"model": "echo-model", "messages": msg,
                          "stream": False, **bad},
                )
                assert r.status_code == 400, (bad, r.status_code, r.text)
                assert "not supported" in r.text or "exceeds" in r.text
    finally:
        await service.stop()
        await drt.shutdown()
