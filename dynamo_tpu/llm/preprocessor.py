"""OpenAI preprocessor operator.

Forward path: OpenAI chat/completion request → prompt templating →
tokenization → `PreprocessedRequest` (wire dict, transportable). Backward
path: detokenized EngineOutput deltas → OpenAI stream chunks, with a final
usage-bearing chunk (reference: lib/llm/src/preprocessor.rs:63-140
OpenAIPreprocessor + its DeltaGenerator response mapping; annotations
`formatted_prompt` / `token_ids`).
"""

from __future__ import annotations

from typing import Any, AsyncIterator

from dynamo_tpu.llm import slo
from dynamo_tpu.llm.model_card import ModelDeploymentCard
from dynamo_tpu.llm.protocols.annotated import Annotated
from dynamo_tpu.llm.protocols.common import (
    MAX_LOGPROBS,
    DeadlineError,
    FinishReason,
    PreprocessedRequest,
    RequestError,
    ShedError,
)
from dynamo_tpu.llm.protocols.openai import (
    ChatCompletionRequest,
    CompletionRequest,
    Usage,
    new_request_id,
)
from dynamo_tpu.llm.protocols.stream import ChunkStream, ContentDelta
from dynamo_tpu.llm.tokenizer import Tokenizer
from dynamo_tpu.runtime.engine import AsyncEngine, Context
from dynamo_tpu.runtime.pipeline import Operator
from dynamo_tpu.utils.tracing import tracer

ANNOTATION_FORMATTED_PROMPT = "formatted_prompt"
ANNOTATION_TOKEN_IDS = "token_ids"


class OpenAIPreprocessor(Operator):
    def __init__(self, card: ModelDeploymentCard, tokenizer: Tokenizer) -> None:
        self.card = card
        self.tokenizer = tokenizer

    # -- forward ------------------------------------------------------------
    def preprocess(
        self, request: ChatCompletionRequest | CompletionRequest
    ) -> PreprocessedRequest:
        ext = request.extension
        if isinstance(request, ChatCompletionRequest):
            if ext and ext.use_raw_prompt:
                prompt = "".join(m.text() for m in request.messages)
            else:
                # Tools render into the chat template (HF templates take a
                # `tools` variable) unless tool_choice="none" — the
                # request-side half of tool calling (llm/tools.py).
                tools = (
                    request.tools if request.tool_choice != "none" else None
                )
                prompt = self.tokenizer.apply_chat_template(
                    [m.model_dump(exclude_none=True) for m in request.messages],
                    tools=tools,
                )
            token_ids = self.tokenizer.encode(prompt)
        else:
            p = request.prompt
            if isinstance(p, str):
                prompt = p
                token_ids = self.tokenizer.encode(p)
            elif p and isinstance(p[0], int):
                prompt = None
                token_ids = list(p)  # pre-tokenized prompt
            else:
                raise RequestError("batch prompts unsupported; send one prompt")

        stop = request.stop_conditions()
        if not stop.ignore_eos:
            stop.stop_token_ids = list(
                dict.fromkeys(stop.stop_token_ids + self.tokenizer.eos_token_ids)
            )
        budget = self.card.context_length - len(token_ids)
        if budget <= 0:
            raise RequestError(
                f"prompt ({len(token_ids)} tokens) exceeds context length "
                f"{self.card.context_length}"
            )
        stop.max_tokens = min(stop.max_tokens or budget, budget)

        # Explicitly reject unsupported parameters rather than silently
        # ignoring them (reference plumbs or rejects every field —
        # lib/llm/src/protocols/common.rs:248).
        if request.n is not None and request.n > 1:
            raise RequestError("n > 1 is not supported")
        if request.best_of is not None and request.best_of > 1:
            raise RequestError("best_of > 1 is not supported")
        if request.logit_bias:
            raise RequestError("logit_bias is not supported")

        # Logprobs: chat uses a bool gate + top_logprobs count; completions
        # uses an integer count directly.
        logprobs: int | None = None
        if isinstance(request, ChatCompletionRequest):
            if request.logprobs:
                logprobs = int(request.top_logprobs or 0)
        elif request.logprobs is not None and request.logprobs is not False:
            # NB: logprobs=0 is a VALID completions value (chosen-token
            # logprob, no alternatives) — `0 == False` must not drop it.
            logprobs = int(request.logprobs)
        if logprobs is not None and logprobs > MAX_LOGPROBS:
            raise RequestError(
                f"top_logprobs={logprobs} exceeds the supported maximum "
                f"of {MAX_LOGPROBS}"
            )

        pre = PreprocessedRequest(
            token_ids=token_ids,
            sampling=request.sampling_options(),
            stop=stop,
            model=request.model,
            logprobs=logprobs,
        )
        if prompt is not None:
            pre.annotations[ANNOTATION_FORMATTED_PROMPT] = prompt
        return pre

    # -- logprob rendering ---------------------------------------------------
    def _tok_str(self, token_id: int) -> str:
        return self.tokenizer.decode([token_id])

    def _chat_logprobs(self, entries: list[dict]) -> dict:
        """OpenAI chat shape: {"content": [{token, logprob, bytes,
        top_logprobs: [...]}, ...]}."""
        content = []
        for e in entries:
            tok = self._tok_str(e["id"])
            content.append({
                "token": tok,
                "logprob": e["logprob"],
                "bytes": list(tok.encode("utf-8")),
                "top_logprobs": [
                    {
                        "token": (t := self._tok_str(i)),
                        "logprob": lp,
                        "bytes": list(t.encode("utf-8")),
                    }
                    for i, lp in e.get("top", [])
                ],
            })
        return {"content": content}

    def _completion_logprobs(
        self, entries: list[dict], text_offset: int
    ) -> tuple[dict, int]:
        """Legacy completions shape: parallel lists tokens /
        token_logprobs / top_logprobs / text_offset."""
        tokens, token_lps, top, offsets = [], [], [], []
        for e in entries:
            tok = self._tok_str(e["id"])
            tokens.append(tok)
            token_lps.append(e["logprob"])
            top.append(
                {self._tok_str(i): lp for i, lp in e.get("top", [])} or None
            )
            offsets.append(text_offset)
            text_offset += len(tok)
        return (
            {
                "tokens": tokens,
                "token_logprobs": token_lps,
                "top_logprobs": top,
                "text_offset": offsets,
            },
            text_offset,
        )

    async def preprocess_async(
        self, request: ChatCompletionRequest | CompletionRequest
    ) -> PreprocessedRequest:
        """Async preprocessing hook — subclasses that must await external
        services during preprocessing (the multimodal encode worker,
        llm/multimodal.py) override this; the base just wraps the sync
        path."""
        return self.preprocess(request)

    # -- operator -----------------------------------------------------------
    async def generate(
        self, request: Context, downstream: AsyncEngine
    ) -> AsyncIterator[Any]:
        oai: ChatCompletionRequest | CompletionRequest = request.payload
        with tracer().span(request.id, "tokenize"):
            pre = await self.preprocess_async(oai)
        # Deadline propagation: the ingress boundary (HTTP service) parses
        # or defaults the budget and stamps it on the Context; from here it
        # rides the PreprocessedRequest wire through router → disagg queue
        # → scheduler, each hop cancelling expired work.
        pre.deadline = request.annotations.get("deadline")
        # SLO class (llm/slo.py) rides the annotations wire exactly
        # where the deadline travels: router victim selection, the
        # scheduler's shed paths, and class-tagged prefill-queue entries
        # all read it downstream.
        cls = request.annotations.get(slo.ANNOTATION_KEY)
        if cls is not None:
            pre.annotations[slo.ANNOTATION_KEY] = cls
        # Trace propagation rides the same wire: every downstream hop
        # adopts the id, so its spans join this request's timeline.
        pre.trace = tracer().context(request.id, parent_span="tokenize")
        is_chat = isinstance(oai, ChatCompletionRequest)
        rid = new_request_id("chatcmpl" if is_chat else "cmpl")
        prompt_tokens = len(pre.token_ids)

        # Requested annotations ride the stream as typed Annotated events
        # ahead of the first delta (reference: annotated.rs envelope;
        # nvext annotations=["formatted_prompt", "token_ids"]).
        ext = oai.extension
        for name in (ext.annotations if ext and ext.annotations else ()):
            if name == ANNOTATION_TOKEN_IDS:
                yield Annotated.annotation(name, list(pre.token_ids), rid)
            elif name in pre.annotations:
                yield Annotated.annotation(name, pre.annotations[name], rid)

        # Tool-call extraction (llm/tools.py; reference:
        # preprocessor/tools.rs ToolCallingMatcher): with tools in play the
        # content must be inspected whole, so deltas buffer until finish
        # and the stream emits a single content-or-tool_calls chunk.
        matcher = None
        if is_chat and getattr(oai, "tools", None):
            from dynamo_tpu.llm.tools import ToolCallMatcher

            m = ToolCallMatcher(oai.tool_choice or "auto")
            matcher = m if m.enabled else None

        # What is fixed for the request is made here, once
        # (llm/protocols/stream.py). A streamed response's chunks that
        # carry nothing but text take the template form; a chunk with
        # anything else (the role, a finish reason, logprobs, tool calls),
        # and every chunk of a response that is folded and not streamed,
        # the object form.
        stream = ChunkStream(rid, oai.model, chat=is_chat)
        templated = bool(oai.stream)

        def tool_chunk(fallback_finish: str | None):
            """Single buffered chunk: tool_calls if the text matches, else
            the whole content (used at engine finish AND stream-end flush
            so the two paths cannot diverge). With tool_choice="required"
            or a forced function, plain content is an error, not a
            fallback."""
            text = "".join(buffered)
            calls = matcher.match(text)
            if calls:
                return stream.chunk(
                    role="assistant", tool_calls=calls,
                    finish_reason="tool_calls",
                )
            if matcher.required:
                raise RequestError(
                    "tool_choice requires a tool call but the model "
                    "produced none that matches"
                )
            return stream.chunk(
                role="assistant", content=text,
                logprobs=(
                    self._chat_logprobs(buffered_lp) if buffered_lp else None
                ),
                finish_reason=fallback_finish,
            )

        completion_tokens = 0
        finish = None
        first = True
        buffered: list[str] = []
        buffered_lp: list[dict] = []  # logprob entries held with the text
        text_offset = 0  # completions logprobs: running offset in generated text
        async for raw in downstream.generate(request.map(pre.to_wire())):
            # A frame is read where it lies (the keys of
            # ``EngineOutput.to_wire``), not rebuilt as an object.
            frame = raw if type(raw) is dict else raw.to_wire()
            toks = frame.get("token_ids")
            text = frame.get("text")
            finish = frame.get("finish_reason")
            logprobs = frame.get("logprobs")
            if toks:
                completion_tokens += len(toks)
            elif completion_tokens == 0:
                # Shed/expired BEFORE any output: surface a typed error
                # (HTTP 429/503/504), not an empty 200 — clients must be
                # able to tell "retry elsewhere" from "done". Once tokens
                # have streamed, the finish_reason rides the last chunk
                # instead (partial output is better than a broken socket).
                if finish == FinishReason.SHED.value:
                    raise ShedError(
                        "request shed under overload before execution"
                    )
                if finish == FinishReason.DEADLINE.value:
                    raise DeadlineError(
                        "request deadline expired before any output"
                    )
            if matcher is not None:
                if text:
                    buffered.append(text)
                if logprobs:
                    buffered_lp.extend(logprobs)
                # Stream-through fast path (ADVICE r03): once the
                # accumulated text can no longer open a tool-call JSON
                # (not '{', '[' or a code fence), stop buffering and
                # stream normally — agent clients keep incremental deltas
                # for ordinary content. "required"/forced choices always
                # buffer: the final parse decides success vs error.
                lead = "".join(buffered).lstrip()
                if (
                    not matcher.required
                    and finish is None
                    and lead
                    and lead[0] not in "{[`"
                ):
                    matcher = None
                    text = "".join(buffered)
                    buffered.clear()
                    if buffered_lp:
                        # Re-attach every entry held while buffering so the
                        # flushed delta's logprobs align with its text.
                        logprobs = list(buffered_lp)
                        buffered_lp.clear()
                else:
                    if finish is None:
                        continue
                    yield tool_chunk(finish)
                    break
            if templated and not first and finish is None and not logprobs:
                yield ContentDelta(stream, text)
                continue
            lp = None
            if logprobs:
                if is_chat:
                    lp = self._chat_logprobs(logprobs)
                else:
                    lp, text_offset = self._completion_logprobs(
                        logprobs, text_offset
                    )
            yield stream.chunk(
                role="assistant" if first else None, content=text,
                logprobs=lp, finish_reason=finish,
            )
            first = False
            if finish is not None:
                break

        if matcher is not None and buffered and finish is None:
            # Stream ended without a finish marker: flush the buffer.
            yield tool_chunk("stop")

        yield stream.usage_chunk(Usage(
            prompt_tokens=prompt_tokens,
            completion_tokens=completion_tokens,
            total_tokens=prompt_tokens + completion_tokens,
        ))
