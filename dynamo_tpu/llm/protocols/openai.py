"""OpenAI-compatible API types (chat completions, completions, embeddings).

Pydantic models for the HTTP surface, covering the fields the reference's
wrappers expose (reference: lib/llm/src/protocols/openai/* — NvCreate*Request
over async-openai types, plus the `nvext` extension for ignore_eos /
raw-prompt; here spelled `ext`).
"""

from __future__ import annotations

import time
import uuid
from typing import Any, Literal

from pydantic import BaseModel, ConfigDict, Field

from dynamo_tpu.llm.protocols.common import SamplingOptions, StopConditions


class Ext(BaseModel):
    """Framework extension block (reference analogue: nvext)."""

    model_config = ConfigDict(extra="allow")
    ignore_eos: bool | None = None
    use_raw_prompt: bool | None = None
    greedy: bool | None = None
    annotations: list[str] | None = None


class ChatMessage(BaseModel):
    model_config = ConfigDict(extra="allow")
    role: str
    content: str | list[dict[str, Any]] | None = None
    name: str | None = None
    tool_calls: list[dict[str, Any]] | None = None

    def text(self) -> str:
        if isinstance(self.content, str):
            return self.content
        if isinstance(self.content, list):
            return "".join(
                part.get("text", "")
                for part in self.content
                if isinstance(part, dict) and part.get("type") == "text"
            )
        return ""


class _CommonRequest(BaseModel):
    model_config = ConfigDict(extra="allow")
    model: str
    stream: bool = False
    max_tokens: int | None = None
    max_completion_tokens: int | None = None
    temperature: float | None = None
    top_p: float | None = None
    top_k: int | None = None  # extension accepted by most servers
    min_tokens: int | None = None
    seed: int | None = None
    frequency_penalty: float | None = None
    presence_penalty: float | None = None
    stop: str | list[str] | None = None
    n: int | None = None
    # chat: logprobs is a bool gate + top_logprobs the alternative count;
    # completions: logprobs IS the alternative count.
    logprobs: bool | int | None = None
    top_logprobs: int | None = None
    # Parsed so they can be REJECTED explicitly (never silently accept
    # an unsupported knob).
    best_of: int | None = None
    logit_bias: dict[str, float] | None = None
    ext: Ext | None = None
    # accept the reference's extension name too
    nvext: Ext | None = None

    @property
    def extension(self) -> Ext | None:
        return self.ext or self.nvext

    def stop_conditions(self) -> StopConditions:
        stop = self.stop
        if stop is None:
            stop_list: list[str] = []
        elif isinstance(stop, str):
            stop_list = [stop]
        else:
            stop_list = list(stop)
        ext = self.extension
        return StopConditions(
            max_tokens=self.max_completion_tokens or self.max_tokens,
            stop=stop_list,
            min_tokens=self.min_tokens,
            ignore_eos=bool(ext.ignore_eos) if ext and ext.ignore_eos else False,
        )

    def sampling_options(self) -> SamplingOptions:
        ext = self.extension
        temperature = self.temperature
        if ext and ext.greedy:
            temperature = 0.0
        return SamplingOptions(
            temperature=temperature,
            top_p=self.top_p,
            top_k=self.top_k,
            seed=self.seed,
            frequency_penalty=self.frequency_penalty,
            presence_penalty=self.presence_penalty,
        )


class ChatCompletionRequest(_CommonRequest):
    messages: list[ChatMessage]
    tools: list[dict[str, Any]] | None = None
    tool_choice: Any | None = None


class CompletionRequest(_CommonRequest):
    prompt: str | list[str] | list[int] | list[list[int]]
    echo: bool | None = None


class EmbeddingRequest(BaseModel):
    model_config = ConfigDict(extra="allow")
    model: str
    input: str | list[str] | list[int] | list[list[int]]
    encoding_format: Literal["float", "base64"] = "float"


class Usage(BaseModel):
    prompt_tokens: int = 0
    completion_tokens: int = 0
    total_tokens: int = 0


class EmbeddingData(BaseModel):
    object: Literal["embedding"] = "embedding"
    index: int = 0
    # list for encoding_format=float, str for base64 (LE f32 bytes)
    embedding: list[float] | str = []


class EmbeddingResponse(BaseModel):
    object: Literal["list"] = "list"
    data: list[EmbeddingData] = []
    model: str = ""
    usage: Usage = Usage()


class ChatDelta(BaseModel):
    role: str | None = None
    content: str | None = None
    tool_calls: list[dict[str, Any]] | None = None


class StreamChoice(BaseModel):
    index: int = 0
    delta: ChatDelta
    # {"content": [{token, logprob, bytes, top_logprobs: [...]}, ...]}
    logprobs: dict[str, Any] | None = None
    finish_reason: str | None = None


class ChatCompletionChunk(BaseModel):
    id: str
    object: Literal["chat.completion.chunk"] = "chat.completion.chunk"
    created: int = Field(default_factory=lambda: int(time.time()))
    model: str
    choices: list[StreamChoice]
    usage: Usage | None = None


class Choice(BaseModel):
    index: int = 0
    message: ChatMessage
    logprobs: dict[str, Any] | None = None
    finish_reason: str | None = None


class ChatCompletionResponse(BaseModel):
    id: str
    object: Literal["chat.completion"] = "chat.completion"
    created: int = Field(default_factory=lambda: int(time.time()))
    model: str
    choices: list[Choice]
    usage: Usage = Field(default_factory=Usage)


class CompletionChoice(BaseModel):
    index: int = 0
    text: str
    # {"tokens", "token_logprobs", "top_logprobs", "text_offset"} lists
    logprobs: dict[str, Any] | None = None
    finish_reason: str | None = None


class CompletionResponse(BaseModel):
    id: str
    object: Literal["text_completion"] = "text_completion"
    created: int = Field(default_factory=lambda: int(time.time()))
    model: str
    choices: list[CompletionChoice]
    usage: Usage = Field(default_factory=Usage)


class ModelInfo(BaseModel):
    id: str
    object: Literal["model"] = "model"
    created: int = Field(default_factory=lambda: int(time.time()))
    owned_by: str = "dynamo-tpu"


class ModelList(BaseModel):
    object: Literal["list"] = "list"
    data: list[ModelInfo] = Field(default_factory=list)


def new_request_id(prefix: str = "chatcmpl") -> str:
    return f"{prefix}-{uuid.uuid4().hex}"
