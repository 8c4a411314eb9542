"""Detokenizer operator ("Backend" in the reference).

Sits between the preprocessor and the engine: forwards the tokenized request
unchanged, and on the response path incrementally detokenizes engine token
deltas into text, enforcing stop conditions the engine can't see — stop
*strings* via jailing (hold back any emitted tail that could be the prefix of
a stop string until it either matches or can't), eos suppression, max_tokens
(reference: lib/llm/src/backend.rs:63-118 and its Decoder/jail logic).
"""

from __future__ import annotations

from typing import Any, AsyncIterator

from dynamo_tpu.llm.protocols.common import (
    FinishReason,
    PreprocessedRequest,
    StopConditions,
)
from dynamo_tpu.llm.tokenizer import Tokenizer
from dynamo_tpu.runtime.engine import AsyncEngine, Context
from dynamo_tpu.runtime.pipeline import Operator


_STOP = FinishReason.STOP.value
_LENGTH = FinishReason.LENGTH.value


class StopStringJail:
    """Holds back streamed text that might be the start of a stop string."""

    def __init__(self, stop: list[str]) -> None:
        self._stop = [s for s in stop if s]
        self._held = ""

    def push(self, text: str) -> tuple[str, bool]:
        """Feed new text; returns (emittable_text, stopped)."""
        if not self._stop:
            return text, False
        buf = self._held + text
        for s in self._stop:
            idx = buf.find(s)
            if idx != -1:
                self._held = ""
                return buf[:idx], True
        # Longest suffix of buf that is a proper prefix of any stop string.
        max_hold = 0
        for s in self._stop:
            for k in range(min(len(s) - 1, len(buf)), 0, -1):
                if buf.endswith(s[:k]):
                    max_hold = max(max_hold, k)
                    break
        if max_hold:
            self._held = buf[-max_hold:]
            return buf[:-max_hold], False
        self._held = ""
        return buf, False

    def flush(self) -> str:
        held, self._held = self._held, ""
        return held


class Detokenizer(Operator):
    """Frames in, frames out, both as the wire spells them (a dict; the
    keys of ``EngineOutput.to_wire``): a frame is read where it lies and
    not rebuilt as an ``EngineOutput``. One frame OUT a token, so a
    client's stream is an event a token however many tokens the engine
    put into a frame."""

    def __init__(self, tokenizer: Tokenizer) -> None:
        self.tokenizer = tokenizer

    async def generate(
        self, request: Context, downstream: AsyncEngine
    ) -> AsyncIterator[Any]:
        payload = request.payload
        pre = (
            PreprocessedRequest.from_wire(payload)
            if isinstance(payload, dict)
            else payload
        )
        stop: StopConditions = pre.stop
        # Made once a request: what each token is held against.
        stop_ids = () if stop.ignore_eos else frozenset(stop.stop_token_ids)
        max_tokens = stop.max_tokens
        step = self.tokenizer.decode_stream().step
        push = StopStringJail(stop.stop).push if any(stop.stop) else None

        generated = 0
        async for raw in downstream.generate(request.map(payload)):
            frame = raw if type(raw) is dict else raw.to_wire()
            toks = frame.get("token_ids")
            if not toks:
                # A text-native engine's delta (EchoEngineFull) or the
                # engine's own finish frame: nothing to decode.
                yield frame
                if frame.get("finish_reason") is not None:
                    request.stop_generating()
                    return
                continue
            last = len(toks) - 1
            logprobs = frame.get("logprobs")
            for i, tid in enumerate(toks):
                generated += 1
                finish = frame.get("finish_reason") if i == last else None
                # An engine's own text stands where no token gives any.
                text = frame.get("text") if last == 0 else None
                stopped = False
                if tid in stop_ids:
                    finish, stopped = _STOP, True
                else:
                    piece = step(tid)
                    if piece:
                        if push is None:
                            text = piece
                        else:
                            emit, stopped = push(piece)
                            text = emit or text
                            if stopped:
                                finish = _STOP
                    if (
                        not stopped
                        and max_tokens is not None
                        and generated >= max_tokens
                    ):
                        stopped = True
                        if finish is None:
                            finish = _LENGTH
                out = {
                    "token_ids": toks if last == 0 else [tid],
                    "text": text,
                    "finish_reason": finish,
                    "cum_tokens": frame.get("cum_tokens", 0) - (last - i),
                    "kv_transfer_params": frame.get("kv_transfer_params"),
                }
                if logprobs is not None:
                    out["logprobs"] = (
                        logprobs if last == 0 else logprobs[i:i + 1]
                    )
                yield out
                if stopped or finish is not None:
                    request.stop_generating()
                    return
