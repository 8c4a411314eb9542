"""The one renderer of a streamed response's chunks.

A streamed chat or completions response is a run of chunks that differ,
token after token, in one short string: ``id``, ``object``, ``created``,
``model``, ``index`` and the key order are fixed for the request. A
``ChunkStream`` is made once a request and gives each chunk in two forms
that cannot diverge, because the second is cut out of the first:

- the OBJECT form (``chunk``, ``usage_chunk``): the pydantic model (chat)
  or the dict (completions) every consumer in this process reads, and what
  the first (role) chunk, the finish chunk, the usage chunk, logprobs and
  tool calls are streamed as: once a request, or rare;
- the TEMPLATE form (``ContentDelta``): a chunk that carries nothing but
  text. Its event is ``head + json(text) + tail``, where ``head`` and
  ``tail`` are the object form's own event rendered once around a mark.

``sse_event`` is what the HTTP service writes for either (and for an
``Annotated`` event beside them); nothing else encodes a chunk
(docs/architecture/request_plane.md "The streamed path").
"""

from __future__ import annotations

import time
from json.encoder import encode_basestring_ascii
from typing import Any

from dynamo_tpu.llm.protocols.annotated import Annotated
from dynamo_tpu.llm.protocols.openai import (
    ChatCompletionChunk,
    ChatDelta,
    StreamChoice,
    Usage,
)
from dynamo_tpu.llm.protocols.sse import SseEvent

#: Stands for the text while the template is cut; JSON leaves it as it is.
_MARK = "dyntpu-text-mark"


class ContentDelta:
    """A streamed chunk that carries nothing but text (``None``: a token
    whose text is not out yet, a partial UTF-8 piece or a held stop
    string). ``chunk()`` is its object form."""

    __slots__ = ("stream", "text")

    def __init__(self, stream: "ChunkStream", text: str | None) -> None:
        self.stream = stream
        self.text = text

    def chunk(self) -> ChatCompletionChunk | dict:
        return self.stream.chunk(content=self.text)

    def model_dump(self, **_kwargs) -> dict:
        """The object form as a dict, for a consumer in this process that
        reads chunks by their dump (the CLI's text modes)."""
        return _dumped(self.chunk())


class ChunkStream:
    """The chunks of one streamed response; see the module's docstring."""

    __slots__ = ("rid", "model", "chat", "created", "_head", "_tail", "_empty")

    def __init__(self, rid: str, model: str, chat: bool) -> None:
        self.rid = rid
        self.model = model
        self.chat = chat
        self.created = int(time.time())
        probe = _object_event(self.chunk(content=_MARK))
        # The text is the last string of the event: an id or a model name
        # that holds the mark too lies before it.
        self._head, mark, self._tail = probe.rpartition(
            encode_basestring_ascii(_MARK).encode()
        )
        if not mark:
            raise ValueError(f"cannot cut a template out of {probe!r}")
        self._empty = _object_event(self.chunk(content=None))

    def chunk(
        self,
        *,
        role: str | None = None,
        content: str | None = None,
        tool_calls: list[dict] | None = None,
        logprobs: dict | None = None,
        finish_reason: str | None = None,
    ) -> ChatCompletionChunk | dict:
        """One choice's chunk in the object form."""
        if self.chat:
            return ChatCompletionChunk(
                id=self.rid,
                created=self.created,
                model=self.model,
                choices=[StreamChoice(
                    delta=ChatDelta(
                        role=role, content=content, tool_calls=tool_calls
                    ),
                    logprobs=logprobs,
                    finish_reason=finish_reason,
                )],
            )
        return {
            "id": self.rid,
            "object": "text_completion",
            "model": self.model,
            "choices": [
                {
                    "index": 0,
                    "text": content or "",
                    "logprobs": logprobs,
                    "finish_reason": finish_reason,
                }
            ],
        }

    def usage_chunk(self, usage: Usage) -> ChatCompletionChunk | dict:
        """The stream's last chunk: no choice, the token counts."""
        if self.chat:
            return ChatCompletionChunk(
                id=self.rid, created=self.created, model=self.model,
                choices=[], usage=usage,
            )
        return {
            "id": self.rid,
            "object": "text_completion",
            "model": self.model,
            "choices": [],
            "usage": usage.model_dump(),
        }

    def delta_event(self, text: str | None) -> bytes:
        """A ``ContentDelta``'s event: the template around the text."""
        if text is None and self.chat:
            return self._empty  # the object form leaves `content` out
        return (
            self._head
            + encode_basestring_ascii(text or "").encode()
            + self._tail
        )


def _dumped(obj: Any) -> dict:
    return obj if isinstance(obj, dict) else obj.model_dump(exclude_none=True)


def _object_event(obj: Any) -> bytes:
    return SseEvent.data_json(_dumped(obj)).encode()


def sse_event(item: Any) -> bytes:
    """The bytes one item of a response stream is written as."""
    if type(item) is ContentDelta:
        return item.stream.delta_event(item.text)
    if isinstance(item, Annotated):
        return item.to_sse().encode()
    return _object_event(item)
