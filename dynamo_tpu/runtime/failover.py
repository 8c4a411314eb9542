"""Mid-stream worker-death failover: the ingress-side survival plane.

At fleet scale worker death is a steady-state event, not an exception
(PAPER §5 failure detection/recovery; Mooncake-style disaggregated
fleets assume recompute-over-error arithmetic — PAPERS.md 2606.03910).
Before this module a worker crashing mid-decode errored every in-flight
stream on it; now request survival is an *ingress-side* property
(docs/architecture/failure_model.md "Mid-stream failover"):

- **Eligibility** is by error CLASS, never by guess: only
  transport/engine-death errors (``ConnectionError`` lineage — the
  receiver's ``WorkerDiedError``, the bus's ``NoSubscriberError``,
  injected ``FaultError``s — plus the engine-fault ``ERROR`` finish
  frame) fail over. ``ShedError`` / ``DeadlineError`` / ``RequestError``
  NEVER do — overload, expiry, and client faults are deliberate
  decisions this plane must not overrule (tests prove the negative).
- **Replay** re-routes through the PushRouter (which already evicted the
  dead instance via its mark-dead fast path) with the REMAINING
  deadline and the ORIGINAL trace id. The replay prompt is
  ``prompt + tokens-already-emitted``: the new worker recomputes the
  delivered prefix as prefill (its prefix cache may hit), so its first
  generated token is exactly token K+1 and the wrapper skips all K
  already-delivered tokens by construction — a greedy stream is
  byte-identical across a mid-stream kill. ``max_tokens``/``min_tokens``
  shrink by K so length accounting never doubles.
- **Bounded**: ``max_attempts`` failovers, then a clean typed 502
  (``FailoverExhausted``) — never a hang, never a generic 500.

``FAILOVER`` is the process-wide counter registry
(``failover_total`` / ``failover_success_total`` /
``workers_marked_dead_total``, split per reason), exported on all three
metric surfaces next to ``retries_total``. It also counts the router's
dispatches by the path each took (``router_dispatch_local_total`` /
``router_dispatch_wire_total``, runtime/egress.py), on the two HTTP
``/metrics`` surfaces: only a process that routes has them.
"""

# dynarace: context[loop]

from __future__ import annotations

import asyncio
import logging
import threading
from typing import Any, AsyncIterator

from dynamo_tpu.utils.tracing import TraceContext, tracer

logger = logging.getLogger(__name__)

#: Bounded failover attempts per request (re-dispatches, not counting
#: the original). Past this the request gets the typed 502.
DEFAULT_MAX_ATTEMPTS = 3


class FailoverStats:
    """Process-wide failover accounting, split per reason — the same
    shape as utils/retry.RetryCounter so the three surfaces export the
    robustness counters uniformly."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.attempts_by_reason: dict[str, int] = {}
        self.success_by_reason: dict[str, int] = {}
        self.marked_dead_by_reason: dict[str, int] = {}
        self.dispatch_by_path: dict[str, int] = {"local": 0, "wire": 0}

    def note_dispatch(self, path: str) -> None:
        """One PushRouter dispatch: ``local`` (a direct call of an engine
        this process serves) or ``wire`` (envelope + response socket)."""
        with self._lock:
            self.dispatch_by_path[path] += 1

    def dispatch_total(self, path: str) -> int:
        with self._lock:
            return self.dispatch_by_path[path]

    def note_attempt(self, reason: str) -> None:
        with self._lock:
            self.attempts_by_reason[reason] = (
                self.attempts_by_reason.get(reason, 0) + 1
            )

    def note_success(self, reason: str) -> None:
        with self._lock:
            self.success_by_reason[reason] = (
                self.success_by_reason.get(reason, 0) + 1
            )

    def note_marked_dead(self, reason: str) -> None:
        with self._lock:
            self.marked_dead_by_reason[reason] = (
                self.marked_dead_by_reason.get(reason, 0) + 1
            )

    # dynarace: context[loop, engine]
    @property
    def total(self) -> int:
        with self._lock:
            return sum(self.attempts_by_reason.values())

    # dynarace: context[loop, engine]
    @property
    def success_total(self) -> int:
        with self._lock:
            return sum(self.success_by_reason.values())

    # dynarace: context[loop, engine]
    @property
    def marked_dead_total(self) -> int:
        with self._lock:
            return sum(self.marked_dead_by_reason.values())

    def snapshot(self) -> dict[str, dict[str, int]]:
        with self._lock:
            return {
                "failover_total": dict(self.attempts_by_reason),
                "failover_success_total": dict(self.success_by_reason),
                "workers_marked_dead_total": dict(self.marked_dead_by_reason),
            }

    def render_labeled(self, prefix: str = "dyntpu") -> str:
        """Per-reason Prometheus series for the failover counters — the
        flat totals ride the gauge surfaces (DT011 parity); this is the
        breakdown an incident actually needs. The per-seam
        ``retries_total`` split lives on the retry registry
        (utils/retry.RETRIES.render_labeled) — each surface appends
        both, so neither plane's observability depends on the other."""
        lines: list[str] = []
        split = self.snapshot()
        for family, label in (
            ("failover_total", "reason"),
            ("failover_success_total", "reason"),
            ("workers_marked_dead_total", "reason"),
        ):
            counts = split[family]
            if not counts:
                continue
            lines.append(f"# TYPE {prefix}_{family}_by_{label} counter")
            for key, n in sorted(counts.items()):
                lines.append(
                    f'{prefix}_{family}_by_{label}{{{label}="{key}"}} {n}'
                )
        return "\n".join(lines) + ("\n" if lines else "")


FAILOVER = FailoverStats()


def failover_eligible(exc: BaseException) -> bool:
    """Transport/engine-death classification. ConnectionError lineage
    covers WorkerDiedError, NoSubscriberError, injected FaultError, and
    reset/refused sockets; IncompleteReadError is a torn frame. Shed /
    Deadline / Request errors are RuntimeError/ValueError subclasses and
    can never match — the taxonomy is structural, not a blocklist."""
    return isinstance(
        exc, (ConnectionError, asyncio.IncompleteReadError)
    )


def _finish_reason(item: Any) -> str | None:
    if isinstance(item, dict):
        return item.get("finish_reason")
    fr = getattr(item, "finish_reason", None)
    return getattr(fr, "value", fr)


def _token_ids(item: Any) -> list[int]:
    if isinstance(item, dict):
        return list(item.get("token_ids") or [])
    return list(getattr(item, "token_ids", None) or [])


class FailoverEngine:
    """AsyncEngine wrapper around the PushRouter: replays a stream that
    died with an engine-death class error onto a surviving worker.

    Sits between the Detokenizer and the router in the serving pipeline
    (llm/discovery.build_serving_pipeline), so the detokenizer upstream
    sees one continuous token stream — its incremental-decode state,
    stop-string jail, and max_tokens count carry straight across the
    failover and the client bytes never skip or repeat."""

    def __init__(self, downstream, max_attempts: int = DEFAULT_MAX_ATTEMPTS):
        self._next = downstream
        self.max_attempts = max_attempts

    def __getattr__(self, name):
        # Router surface passthrough (client, mark_dead, mode...) so
        # everything that introspects the pipeline's terminal engine
        # still finds the PushRouter underneath.
        return getattr(self._next, name)

    async def generate(self, request) -> AsyncIterator[Any]:
        from dynamo_tpu.llm.protocols.common import (
            DeadlineError,
            FailoverExhausted,
            FinishReason,
            ShedError,
        )
        from dynamo_tpu.utils.deadline import OVERLOAD, Deadline

        wire = request.payload if isinstance(request.payload, dict) else None
        replayable = wire is not None and "token_ids" in wire
        deadline = (
            Deadline.from_wire(wire.get("deadline_ms"))
            if replayable and wire.get("deadline_ms") is not None
            else None
        )
        emitted: list[int] = []
        yielded_any = False
        attempt = 0
        last_reason = ""
        trace_id = tracer().trace_id(request.id)
        ctx = request
        resumed: AsyncIterator[Any] | None = None
        while True:
            death: BaseException | None = None
            stream = (
                resumed if resumed is not None else self._next.generate(ctx)
            )
            resumed = None
            death_from_error_frame = False
            try:
                async for item in stream:
                    if type(item) is dict:  # a frame as the wire spells it
                        fr = item.get("finish_reason")
                        toks = item.get("token_ids")
                    else:
                        fr = _finish_reason(item)
                        toks = _token_ids(item)
                    if fr == FinishReason.ERROR.value:
                        # Engine fault frames end the stream NORMALLY
                        # (engine/engine.py _engine_loop) — re-typify to
                        # the death class instead of delivering a corpse
                        # marker to the client.
                        from dynamo_tpu.llm.protocols.common import (
                            WorkerDiedError,
                        )

                        death = WorkerDiedError(
                            "engine fault: stream ended with an ERROR "
                            "finish frame"
                        )
                        death_from_error_frame = True
                        break
                    if toks:
                        emitted.extend(toks)
                    if attempt and isinstance(item, dict) and (
                        "cum_tokens" in item
                    ):
                        # The replay engine restarts its count at 1; the
                        # client-visible cumulative count must keep
                        # climbing across the seam — on EVERY frame,
                        # including the tokenless terminal one (whose
                        # replay-local count would otherwise regress it).
                        item = dict(item)
                        item["cum_tokens"] = len(emitted)
                    yielded_any = True
                    yield item
                    if fr is not None:
                        if attempt:
                            FAILOVER.note_success(last_reason)
                        return
            except (GeneratorExit, asyncio.CancelledError):
                raise
            except BaseException as exc:  # noqa: BLE001 — classified below
                if not failover_eligible(exc):
                    raise
                death = exc
            if death is None:
                # Clean end without a terminal frame (single-shot
                # payloads: embeddings, raw dicts).
                if attempt:
                    FAILOVER.note_success(last_reason)
                return
            # -- the stream died with an engine-death class error --------
            reason = type(death).__name__
            last_reason = reason
            old_worker = request.annotations.get("worker_id")
            if death_from_error_frame and old_worker is not None:
                # An ERROR finish frame arrives over a HEALTHY transport,
                # so egress's mid-stream detection never fired — mark the
                # faulted worker dead here or the replay (KV mode
                # especially: the corpse holds the longest cached prefix
                # for prompt+emitted) routes straight back to it.
                mark = getattr(self._next, "mark_dead", None)
                if mark is not None:
                    mark(old_worker, "engine_fault")
            if not replayable and yielded_any:
                # A non-token stream that already delivered output can't
                # be replayed without duplicating it.
                raise FailoverExhausted(
                    f"stream died ({reason}) after partial non-token "
                    f"output; not replayable",
                    attempts=attempt,
                ) from death
            if attempt >= self.max_attempts:
                raise FailoverExhausted(
                    f"failover attempts exhausted "
                    f"({self.max_attempts}) — last error: {death}",
                    attempts=attempt,
                ) from death
            # The worker can die BETWEEN its final token frame and the
            # tokenless terminal frame (engine/engine.py emits every
            # finish reason as a separate frame): everything owed was
            # already delivered — synthesize the finish instead of
            # replaying, or the client receives tokens past the true
            # end (a max_tokens+1st token / content after the stop id).
            stop = (wire.get("stop") or {}) if replayable else {}
            synth = None
            if (
                stop.get("max_tokens") is not None
                and len(emitted) >= stop["max_tokens"]
            ):
                synth = FinishReason.LENGTH.value
            elif (
                emitted
                and not stop.get("ignore_eos")
                and emitted[-1] in (stop.get("stop_token_ids") or ())
            ):
                synth = FinishReason.STOP.value
            if synth is not None:
                yield {
                    "token_ids": [], "text": None,
                    "finish_reason": synth,
                    "cum_tokens": len(emitted),
                    "kv_transfer_params": None,
                }
                if attempt:
                    FAILOVER.note_success(last_reason)
                return
            if deadline is not None and deadline.expired:
                OVERLOAD.note_deadline("failover")
                raise DeadlineError(
                    "request deadline expired during failover"
                ) from death
            attempt += 1
            FAILOVER.note_attempt(reason)
            # Keep the ORIGINAL trace id across the seam: a dead worker
            # sharing this process's tracer (mocker fleets) closed the
            # trace in its stream teardown — re-adopt under the same id
            # so the failover span, the replay's spans, and the final
            # finish all join ONE cross-process timeline
            # (trace_merge honors the chain instead of red-barring it).
            tracer().adopt(
                request.id, TraceContext(trace_id, sent_unix=None)
            )
            tracer().mark(request.id, "failover")
            tracer().span_begin(request.id, "failover")
            logger.warning(
                "request %s: worker %s died mid-stream (%s) — failover "
                "attempt %d/%d resuming at token %d",
                request.id, hex(old_worker) if old_worker else "?",
                reason, attempt, self.max_attempts, len(emitted),
            )
            if replayable:
                ctx = request.map(
                    self._replay_wire(wire, emitted, deadline)
                )
            # The PushRouter re-picks EXCLUDING everything its mark-dead
            # fast path evicted; it raises ShedError when the fleet has
            # no healthy capacity left — which, inside a failover, IS
            # exhaustion: the clean typed 502. The failover span closes
            # on the replay's first frame (new worker known by then), so
            # it covers exactly the client-visible resume gap. A replay
            # whose first frame ALSO dies loops back through the death
            # path above — every re-dispatch is bounded by max_attempts.
            replay = self._next.generate(ctx)
            try:
                first = await replay.__anext__()
            except StopAsyncIteration:
                tracer().span_end(request.id, "failover")
                FAILOVER.note_success(last_reason)
                return
            except ShedError as exc:
                tracer().span_end(request.id, "failover")
                raise FailoverExhausted(
                    f"no healthy capacity for failover: {exc}",
                    attempts=attempt,
                ) from exc
            except (GeneratorExit, asyncio.CancelledError):
                tracer().span_end(request.id, "failover")
                raise
            except BaseException as exc:  # noqa: BLE001 — classified below
                tracer().span_end(request.id, "failover")
                if not failover_eligible(exc):
                    raise
                # The replacement died too before producing a frame —
                # feed the error back through the bounded death path.
                resumed = _raising(exc)
                continue
            tracer().span_end(request.id, "failover")
            new_worker = request.annotations.get("worker_id")
            self._export_record(
                request.id, reason, attempt, old_worker, new_worker,
                len(emitted),
            )
            resumed = _resume(replay, first)

    @staticmethod
    def _replay_wire(
        wire: dict, emitted: list[int], deadline
    ) -> dict[str, Any]:
        """The replay request: prompt + already-emitted tokens (the new
        worker recomputes the delivered prefix — prefix cache may hit),
        stop budgets shrunk by K, and the REMAINING deadline re-stamped
        (re-shipping the original wire value would re-anchor the full
        budget on the new worker — a deadline reset)."""
        w = dict(wire)
        w["token_ids"] = list(wire["token_ids"]) + list(emitted)
        stop = dict(w.get("stop") or {})
        if stop.get("max_tokens") is not None:
            stop["max_tokens"] = max(1, stop["max_tokens"] - len(emitted))
        if stop.get("min_tokens"):
            stop["min_tokens"] = max(0, stop["min_tokens"] - len(emitted))
        w["stop"] = stop
        if deadline is not None:
            w["deadline_ms"] = deadline.to_wire()
        return w

    @staticmethod
    def _export_record(
        request_id: str, reason: str, attempt: int,
        old_worker, new_worker, resumed_at: int,
    ) -> None:
        """kind="failover" line into the DYNTPU_TRACE capture — joins
        the trace catalog next to route/kv_actual/planner records."""
        try:
            tracer().export({
                "kind": "failover",
                "id": request_id,
                "trace": tracer().trace_id_if_active(request_id) or "",
                "reason": reason,
                "attempt": attempt,
                "old_worker": old_worker,
                "new_worker": new_worker,
                "resumed_at_token": resumed_at,
            })
        except Exception:  # noqa: BLE001 — observability must not fail failover
            logger.exception("failover record export failed")


async def _resume(stream, first) -> AsyncIterator[Any]:
    """The replay stream with its first (already-awaited) frame stitched
    back on front, so the failover loop processes every frame — ERROR
    re-typing, cum_tokens rewrite, emitted tracking — uniformly."""
    yield first
    async for item in stream:
        yield item


async def _raising(exc: BaseException) -> AsyncIterator[Any]:
    """An immediately-dying stream: routes a replay's first-frame death
    back into the failover loop's ONE bounded death path."""
    raise exc
    yield  # pragma: no cover — makes this an async generator
