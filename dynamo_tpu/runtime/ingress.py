"""Server-side request handling (ingress).

Wraps an AsyncEngine as a served endpoint: subscribe the endpoint's bus
subject, and for each arriving request envelope spawn a handler that runs the
engine and streams responses back over the TCP response plane (reference:
lib/runtime/src/pipeline/network/ingress/push_endpoint.rs:26-111,
network.rs:279-323 `Ingress::for_engine`).

Request envelope (msgpack): ``{"id": str, "payload": <obj>, "resp":
{host, port, stream_id}}``. Response frames carry msgpack-serialized items;
the final frame is an end/err control frame (transports/tcp.py).

An instance served with ``offer_local`` also answers a router of its own
runtime without the wire (``ServedInstance.local_stream``): the same
engine call under the same contract — tracked in ``inflight``, refused
while draining, killed by ``kill()``, errors typed as the wire's decoder
types them, frames equal to what msgpack would have delivered — with no
envelope, socket or handler task (docs/architecture/request_plane.md).
"""

from __future__ import annotations

import asyncio
import logging
from typing import Any, AsyncIterator

import msgpack

from dynamo_tpu.runtime.component import Endpoint, Instance
from dynamo_tpu.runtime.engine import AsyncEngine, Context
from dynamo_tpu.runtime.transports.tcp import (
    ConnectionInfo,
    TcpResponseSender,
    _typed_stream_error,
)
from dynamo_tpu.utils.logging import current_request_scope, request_scope
from dynamo_tpu.utils.task import spawn_tracked
from dynamo_tpu.utils.tracing import TraceContext, tracer

logger = logging.getLogger(__name__)


class _LocalCall(asyncio.Future):
    """One local call as the instance's ``inflight`` set holds it beside
    the wire's handler tasks: done when its stream has ended, so
    ``drain()`` waits for it, and cancelled by ``kill()``. The caller
    runs the engine in its own task: a cancel that finds that task
    inside the stream cancels it (``local_stream`` turns that back into
    ``WorkerDiedError``); one that finds it between two frames is seen
    when it asks for the next."""

    stream: Any = None  # the local_stream generator
    task: asyncio.Task | None = None  # its consumer

    def cancel(self, msg: Any = None) -> bool:
        if not super().cancel(msg):
            return False
        if self.stream.ag_running:
            self.task.cancel()
        return True


class ServedInstance:
    """A live served endpoint plus its teardown. Proxies the registered
    `Instance`'s attributes; ``stop()`` deregisters from the store and
    halts the request pump without shutting down the whole runtime (for
    services that retire an endpoint mid-life, e.g. RouterService);
    ``drain()`` is the loss-free variant: stop accepting, FINISH the
    in-flight request handlers, then deregister."""

    def __init__(
        self, drt, instance: Instance, sub, task, inflight: set, engine
    ) -> None:
        self.instance = instance
        self._drt = drt
        self._sub = sub
        self._task = task
        self._inflight = inflight
        self._engine = engine
        self._draining = False

    def __getattr__(self, name):
        return getattr(self.instance, name)

    @property
    def inflight(self) -> int:
        """Requests currently being handled by this endpoint."""
        return len(self._inflight)

    async def _deregister(self) -> None:
        try:
            await self._drt.store.delete(self.instance.store_key)
        except Exception:  # store may already be gone at runtime teardown
            logger.debug("instance deregister failed", exc_info=True)

    async def drain(self, grace_s: float = 30.0) -> bool:
        """Graceful retirement (docs/architecture/overload_and_drain.md):
        deregister FIRST (routers stop picking this instance — eviction),
        stop the request pump (no new envelope is handled), then wait up
        to `grace_s` for in-flight handlers to finish streaming their
        responses (the response plane is direct TCP, independent of
        discovery, so they complete untouched). Returns True when nothing
        was abandoned."""
        self._draining = True
        await self._deregister()
        self._sub.close()
        self._task.cancel()
        try:
            await self._task
        except asyncio.CancelledError:
            pass
        pending = {t for t in self._inflight if not t.done()}
        if pending:
            done, still = await asyncio.wait(pending, timeout=grace_s)
            if still:
                logger.warning(
                    "drain grace expired with %d request(s) in flight",
                    len(still),
                )
                return False
        return True

    def _withdraw_local(self) -> None:
        """Stop offering the local call: a router that still picks this
        instance goes to the wire and finds no subscriber, as it does for
        any stopped or dead worker."""
        offered = self._drt.local_instances
        if offered.get(self.instance.subject) is self:
            del offered[self.instance.subject]

    async def stop(self) -> None:
        self._withdraw_local()
        self._sub.close()
        self._task.cancel()
        try:
            await self._task
        except asyncio.CancelledError:
            pass
        await self._deregister()

    async def kill(self) -> None:
        """Abrupt worker death (the chaos path — docs/architecture/
        failure_model.md "Mid-stream failover"): the subscription closes,
        the pump dies, and every in-flight handler is CANCELLED — its
        response socket aborts with no terminal frame, so each caller
        sees a typed ``WorkerDiedError`` and fails over. Deliberately
        does NOT deregister: a crashed process never gets to clean up
        discovery — the lease TTL (slow path) or the router's mark-dead
        fast path is what evicts the corpse, which is exactly the seam
        the failover plane exists to cover. A local call is cancelled
        like a handler: its caller sees the same error."""
        self._withdraw_local()
        self._sub.close()
        self._task.cancel()
        doomed = [self._task, *self._inflight]
        for t in doomed[1:]:
            t.cancel()
        for t in doomed:
            try:
                await t
            except (asyncio.CancelledError, Exception):  # noqa: BLE001 — dying
                pass

    def local_stream(self, request: Context) -> AsyncIterator[Any]:
        """The engine's stream for a caller in this process: what
        ``_handle_request`` does for an envelope, in the caller's own
        task and with nothing serialized."""
        call = _LocalCall()
        call.stream = self._local_stream(call, request)
        return call.stream

    async def _local_stream(
        self, call: _LocalCall, request: Context
    ) -> AsyncIterator[Any]:
        from dynamo_tpu.llm.protocols.common import ShedError

        if self._draining:
            raise ShedError(
                "instance draining — retry another instance", draining=True
            )
        rid = request.id
        call.task = asyncio.current_task()
        self._inflight.add(call)
        call.add_done_callback(self._inflight.discard)
        trace = tracer()
        # The hop edge the envelope's trace context records on the wire.
        hop = trace.context(rid, parent_span="route")
        trace.adopt(rid, hop)
        # The payload as the envelope delivers it: the engine's own copy.
        frames = self._engine.generate(
            request.linked(msgpack.unpackb(msgpack.packb(request.payload)))
        ).__aiter__()
        scope = (rid, hop.trace_id)
        step = frames.__anext__
        try:
            while True:
                try:
                    # The engine's log lines carry the request's scope. A
                    # caller that is in it already (the HTTP handler
                    # enters it for the whole request) pays a comparison
                    # a frame; any other is scoped a step, not around the
                    # loop: a generator's frames may be resumed and
                    # closed in other contexts.
                    if current_request_scope() == scope:
                        item = await step()
                    else:
                        with request_scope(rid, hop.trace_id):
                            item = await step()
                except StopAsyncIteration:
                    break
                except asyncio.CancelledError:
                    if not call.cancelled():
                        raise  # the caller's own cancellation
                    _trace_failed(rid)
                    if call.task.uncancel():
                        raise  # and its caller was cancelled besides
                    raise _killed() from None
                except Exception as exc:  # noqa: BLE001 — typed for the caller
                    logger.exception("request %s failed", rid)
                    _trace_failed(rid)
                    raise _typed_stream_error(_wire_error(exc)) from exc
                yield _as_wire(item)
                if call.cancelled():
                    _trace_failed(rid)
                    raise _killed()
            trace.finish(rid)
        finally:
            aclose = getattr(frames, "aclose", None)
            if aclose is not None:
                await aclose()
            if not call.done():
                call.set_result(None)


def _killed() -> Exception:
    """What the caller of a killed instance sees: the error the wire's
    receiver raises for a socket closed with no terminal frame."""
    from dynamo_tpu.llm.protocols.common import WorkerDiedError

    err = WorkerDiedError(
        "local call ended without a terminal frame — worker died mid-stream"
    )
    err.transport_dead = True
    return err


_PLAIN = frozenset((str, int, float, bool, bytes, type(None)))


def _is_token_frame(item: Any) -> bool:
    """Whether `item` is an engine's frame of one token and nothing else,
    spelled as ``EngineOutput.to_wire`` spells it: a dict of five keys, an
    int in a list, an int and three ``None``. A fixed number of checks,
    whatever else an engine may yield."""
    try:
        toks = item["token_ids"]
        return (
            type(item) is dict
            and len(item) == 5
            and type(toks) is list
            and len(toks) == 1
            and type(toks[0]) is int
            and type(item["cum_tokens"]) is int
            and item["text"] is None
            and item["finish_reason"] is None
            and item["kv_transfer_params"] is None
        )
    except (KeyError, TypeError, IndexError):
        return False


def _as_wire(item: Any) -> Any:
    """`item` as ``msgpack.unpackb(msgpack.packb(item))`` would deliver
    it. An engine's token frame is that already and is recognised in
    constant time; any other dict of plain values and short lists of them
    is too and passes after a scan; anything else takes the round trip,
    so both paths deliver equal frames by construction."""
    if _is_token_frame(item):
        return item
    if type(item) is dict:
        for key, val in item.items():
            kind = type(val)
            if type(key) is str and (
                kind in _PLAIN
                or kind is list and all(type(v) in _PLAIN for v in val)
            ):
                continue
            break
        else:
            return item
    return msgpack.unpackb(msgpack.packb(item, default=_default))


async def serve_endpoint(
    drt,
    endpoint: Endpoint,
    engine: AsyncEngine,
    metadata: dict | None = None,
    offer_local: bool = False,
) -> ServedInstance:
    """Register `engine` as a live instance of `endpoint` and start the
    request pump. Returns the registered instance handle. With
    ``offer_local`` a router of this same runtime that picks the instance
    calls the engine directly (``ServedInstance.local_stream``);
    discovery, the lease and the bus subject are the same either way, so
    every other process still reaches it over the wire."""
    lease_id = drt.primary_lease_id
    subject = endpoint.subject_for(lease_id)
    instance = Instance(endpoint=endpoint.id, lease_id=lease_id, subject=subject)

    sub = await drt.bus.subscribe(subject)
    await drt.store.put(instance.store_key, instance.to_json(), lease_id=lease_id)
    # Live handler tasks, tracked so drain() can await their completion
    # (spawn_tracked's registry is process-global; this set is per
    # endpoint). Done tasks remove themselves.
    inflight: set[asyncio.Future] = set()

    async def pump() -> None:
        try:
            async for raw in sub:
                t = spawn_tracked(
                    _handle_request(engine, raw), name="ingress-request"
                )
                inflight.add(t)
                t.add_done_callback(inflight.discard)
        except asyncio.CancelledError:
            pass

    task = asyncio.ensure_future(pump())
    served = ServedInstance(drt, instance, sub, task, inflight, engine)
    if offer_local:
        drt.local_instances[subject] = served
    drt.runtime.token.on_cancel(
        lambda: (served._withdraw_local(), sub.close(), task.cancel())
    )
    logger.info("serving %s on %s (lease %#x)", endpoint.id, subject, lease_id)
    return served


async def _handle_request(engine: AsyncEngine, raw: bytes) -> None:
    envelope = msgpack.unpackb(raw)
    sender: TcpResponseSender | None = None
    rid = envelope.get("id", "")
    # Adopt the caller's trace identity before any work: every span this
    # worker records — and any error frame it sends back — joins the
    # request's cross-process timeline under the same trace id.
    ctx_trace = TraceContext.from_wire(envelope.get("trace"))
    tracer().adopt(rid, ctx_trace)
    trace_id = ctx_trace.trace_id if ctx_trace is not None else None
    with request_scope(rid, trace_id):
        try:
            info = ConnectionInfo.from_wire(envelope["resp"])
            sender = await TcpResponseSender.connect(info)
            ctx: Context[Any] = Context(envelope["payload"], id=rid)
            async for item in engine.generate(ctx):
                await sender.send(msgpack.packb(item, default=_default))
            await sender.end()
            # Generate requests are finished by the engine at delivery;
            # payloads that bypass that path (embeddings, raw dicts) only
            # ever opened a capture via the adopt() above — close it here
            # or each one leaks until the TTL sweep. No-op when the
            # engine already finished.
            tracer().finish(rid)
        except asyncio.CancelledError:
            # Abrupt worker death (ServedInstance.kill / process
            # teardown): abort the response socket with NO terminal
            # frame — the caller must see WorkerDiedError and fail the
            # request over, not a clean-looking truncated stream.
            _trace_failed(rid)
            if sender is not None:
                sender.abort()
            raise
        except Exception as exc:  # noqa: BLE001 — report to caller, don't die
            logger.exception("request %s failed", envelope.get("id"))
            _trace_failed(rid)
            if sender is not None:
                try:
                    await sender.error(_wire_error(exc))
                except Exception:
                    pass


def _trace_failed(rid: str) -> None:
    """The worker-side capture must not leak (or orphan) when the request
    dies on the error plane: mark + finish under the SAME trace id the
    caller will finish its half with."""
    tracer().mark_if_active(rid, "error")
    tracer().finish(rid)


def _wire_error(exc: Exception) -> str:
    """Error-frame text for the response plane. ShedError additionally
    carries its retry/draining hints in a parseable prefix — a REMOTE
    frontend must map an overload rejection to the same 429/503 +
    Retry-After a local one gets (transports/tcp.py _typed_stream_error
    is the decoder). ConnectionError-class failures (engine death, lost
    transport under the handler) collapse to the one name the decoder
    re-typifies as failover-eligible — subclass names would cross as
    unknown types and land as non-retryable RuntimeError."""
    from dynamo_tpu.llm.protocols.common import ShedError

    if isinstance(exc, ShedError):
        return (
            f"ShedError[{exc.retry_after_s:g},{int(exc.draining)}]: {exc}"
        )
    if isinstance(exc, ConnectionError):
        return f"WorkerDiedError: {exc}"
    return f"{type(exc).__name__}: {exc}"


def _default(obj):
    """msgpack fallback for dataclass-ish payloads."""
    if hasattr(obj, "to_wire"):
        return obj.to_wire()
    if hasattr(obj, "__dict__"):
        return obj.__dict__
    raise TypeError(f"cannot serialize {type(obj).__name__}")
