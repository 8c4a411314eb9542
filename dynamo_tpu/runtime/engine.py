"""The streaming engine contract.

Mirrors the reference's core abstraction (reference: lib/runtime/src/engine.rs:
`AsyncEngine<SingleIn<Req>, ManyOut<Resp>, Error>` :104, `AsyncEngineContext`
:47-85, `ResponseStream` :116): every stage — preprocessor, router, worker
engine — accepts ONE request and yields MANY streamed responses, with a
context carrying the request id and stop/kill signals the whole way through.

In Python the natural spelling is: `generate(request: Context) ->
AsyncIterator[resp]`, where `Context` wraps the payload and the cancellation
signals, and operators transform both the request on the way down and the
response stream on the way back up.
"""

from __future__ import annotations

import uuid
from typing import Any, AsyncIterator, Generic, Protocol, TypeVar, runtime_checkable

from dynamo_tpu.utils.cancellation import CancellationToken

T = TypeVar("T")
U = TypeVar("U")


class Context(Generic[T]):
    """Request envelope: payload + id + stop/kill signals + annotations.

    `stop` requests graceful end-of-generation (finish the current token);
    `kill` aborts immediately. Mirrors AsyncEngineContext stop_generating/kill
    (reference: lib/runtime/src/engine.rs:47-85).
    """

    __slots__ = ("payload", "id", "_stop", "_kill", "annotations")

    def __init__(
        self,
        payload: T,
        id: str | None = None,
        stop: CancellationToken | None = None,
        kill: CancellationToken | None = None,
        annotations: dict[str, Any] | None = None,
    ) -> None:
        self.payload = payload
        self.id = id or uuid.uuid4().hex
        self._stop = stop or CancellationToken()
        self._kill = kill or self._stop.child_token()
        self.annotations = annotations if annotations is not None else {}

    def map(self, payload: U) -> "Context[U]":
        """New payload, same identity/signals — the request-path transform."""
        return Context(
            payload,
            id=self.id,
            stop=self._stop,
            kill=self._kill,
            annotations=self.annotations,
        )

    def linked(self, payload: U) -> "Context[U]":
        """New payload and fresh annotations under the same id and
        signals: what a callee in this process receives in place of the
        Context the wire would rebuild for it (runtime/ingress.py) —
        the caller's stop/kill reach it, the caller's notes do not."""
        return Context(payload, id=self.id, stop=self._stop, kill=self._kill)

    def stop_generating(self) -> None:
        self._stop.cancel()

    def kill(self) -> None:
        self._stop.cancel()
        self._kill.cancel()

    @property
    def is_stopped(self) -> bool:
        return self._stop.is_cancelled()

    @property
    def is_killed(self) -> bool:
        return self._kill.is_cancelled()


@runtime_checkable
class AsyncEngine(Protocol):
    """Anything that turns one request into a stream of responses."""

    def generate(self, request: Context) -> AsyncIterator[Any]:
        ...


class EngineAdapter:
    """Wrap a plain async-generator function as an AsyncEngine."""

    def __init__(self, fn) -> None:
        self._fn = fn

    def generate(self, request: Context) -> AsyncIterator[Any]:
        return self._fn(request)
