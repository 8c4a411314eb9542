"""OpenAI-compatible HTTP service (aiohttp).

Routes: POST /v1/chat/completions, POST /v1/completions, GET /v1/models,
GET /health, GET /live, GET /metrics — SSE streaming with usage-final chunks,
non-streaming aggregation, per-request metrics (reference:
lib/llm/src/http/service/openai.rs:123,212,277, service_v2.rs:51-188,
metrics.rs:1-495).
"""

from __future__ import annotations

import asyncio
import logging
import time

from aiohttp import web

from dynamo_tpu.llm.admission import AdmissionController, AdmissionRejected
from dynamo_tpu.llm.discovery import ModelManager
from dynamo_tpu.llm.metrics import Metrics
from dynamo_tpu.llm.protocols.openai import (
    ChatCompletionChunk,
    ChatCompletionRequest,
    ChatCompletionResponse,
    ChatMessage,
    Choice,
    CompletionChoice,
    CompletionRequest,
    CompletionResponse,
    EmbeddingData,
    EmbeddingRequest,
    EmbeddingResponse,
    ModelInfo,
    ModelList,
    Usage,
)
from dynamo_tpu.llm.protocols.annotated import Annotated
from dynamo_tpu.llm.protocols.common import (
    DeadlineError,
    FailoverExhausted,
    RequestError,
    ShedError,
    WorkerDiedError,
)
from dynamo_tpu.llm.protocols.sse import SseEvent
from dynamo_tpu.llm.protocols.stream import ContentDelta, sse_event
from dynamo_tpu.llm import slo
from dynamo_tpu.runtime.engine import Context
from dynamo_tpu.utils import concurrency
from dynamo_tpu.utils.deadline import OVERLOAD, Deadline, parse_timeout_ms
from dynamo_tpu.utils.logging import request_scope
from dynamo_tpu.utils.profiling import ProfileError, Profiler
from dynamo_tpu.utils.tracing import tracer

logger = logging.getLogger(__name__)

#: Header carrying the client's remaining time budget in milliseconds;
#: absent → the admission controller's configured default (if any).
DEADLINE_HEADER = "X-Request-Timeout-Ms"

#: Header carrying the request's SLO class (llm/slo.py: interactive |
#: batch); absent/unknown → the admission config's default class.
REQUEST_CLASS_HEADER = slo.REQUEST_CLASS_HEADER


class HttpService:
    def __init__(
        self,
        manager: ModelManager,
        host: str = "0.0.0.0",
        port: int = 8080,
        readiness=None,
        admission: AdmissionController | None = None,
        debug=None,
        profiler: Profiler | None = None,
    ):
        """`readiness` is an optional zero-arg callable returning the
        serving engine's compile-lifecycle snapshot (TpuEngine.readiness):
        /health turns 503 "warming" until the hot shape set is compiled —
        the k8s-probe face of the engine's admission gate — and /metrics
        exports the compile-stall counters.

        `admission` is the ingress overload gate (llm/admission.py):
        capacity rejections become 429 + Retry-After, draining becomes
        503 + Retry-After, and the gate's watermarks read the same
        readiness snapshot. None builds a default controller (generous
        inflight cap, no engine watermarks) so drain still works.

        `debug` is the local engine handle for /debug/steps (anything
        with ``debug_steps(n)`` — TpuEngine's flight recorder); `profiler`
        enables /debug/profile (docs/architecture/observability.md)."""
        self.manager = manager
        self.metrics = Metrics()
        self._readiness = readiness
        self.admission = admission or AdmissionController(
            engine_stats=readiness
        )
        self._debug = debug
        self.profiler = profiler
        self.host = host
        self.port = port
        self._runner: web.AppRunner | None = None
        self.app = web.Application()
        self.app.add_routes(
            [
                web.post("/v1/chat/completions", self._chat),
                web.post("/v1/completions", self._completions),
                web.post("/v1/embeddings", self._embeddings),
                web.get("/v1/models", self._models),
                web.get("/health", self._health),
                web.get("/live", self._live),
                web.get("/metrics", self._metrics),
                web.get("/debug/steps", self._debug_steps),
                web.get("/debug/trace", self._debug_trace),
                web.get("/debug/routes", self._debug_routes),
                web.get("/debug/profile", self._debug_profile),
            ]
        )

    # -- lifecycle ----------------------------------------------------------
    async def start(self) -> None:
        # Handlers run on this loop: bind it for the runtime affinity
        # checker (no-op unless DYNTPU_CHECK_THREADS=1).
        concurrency.bind_thread("loop")
        self._runner = web.AppRunner(self.app)
        await self._runner.setup()
        site = web.TCPSite(self._runner, self.host, self.port)
        await site.start()
        if self.port == 0:
            for s in self._runner.sites:
                self.port = s._server.sockets[0].getsockname()[1]  # noqa: SLF001
        logger.info("HTTP service on %s:%d", self.host, self.port)

    async def stop(self) -> None:
        if self._runner:
            await self._runner.cleanup()

    async def run(self, token) -> None:
        await self.start()
        try:
            await token.cancelled()
        finally:
            await self.stop()

    async def drain(self, grace_s: float = 30.0) -> bool:
        """Graceful drain: refuse new requests (503 + Retry-After via the
        admission gate, /health flips non-ready) and wait up to `grace_s`
        for admitted requests to finish streaming. Returns True when the
        last in-flight request completed within the grace period."""
        self.admission.begin_drain()
        deadline = asyncio.get_running_loop().time() + grace_s
        while asyncio.get_running_loop().time() < deadline:
            if self.admission.inflight == 0:
                return True
            await asyncio.sleep(0.05)
        return self.admission.inflight == 0

    # -- handlers -----------------------------------------------------------
    def _engine_readiness(self) -> dict | None:
        if self._readiness is None:
            return None
        try:
            return self._readiness() or {}
        except Exception:  # noqa: BLE001 — health must never 500 on a probe
            logger.exception("readiness probe failed")
            return {}

    async def _health(self, _request: web.Request) -> web.Response:
        info = {"status": "healthy", "models": self.manager.models()}
        if self.admission.draining:
            # Readiness flips FIRST on drain: load balancers stop sending
            # while admitted requests finish (loss-free rolling restart).
            info["status"] = "draining"
            return web.json_response(info, status=503)
        eng = self._engine_readiness()
        if eng is not None:
            info["engine"] = eng
            if eng.get("state") == "warming":
                # Load balancers / k8s readiness probes hold traffic until
                # the hot shape set is compiled — no request ever lands on
                # a cold XLA program (the deploy-level admission gate).
                info["status"] = "warming"
                return web.json_response(info, status=503)
            if eng.get("state") == "draining":
                info["status"] = "draining"
                return web.json_response(info, status=503)
        return web.json_response(info)

    async def _live(self, _request: web.Request) -> web.Response:
        return web.json_response({"status": "live"})

    async def _metrics(self, _request: web.Request) -> web.Response:
        eng = self._engine_readiness()
        if eng:
            self.metrics.set_gauge(
                "engine_ready", 1.0 if eng.get("state") == "ready" else 0.0
            )
            for key in (
                "mid_traffic_compiles_total",
                "compile_stall_ms_total",
                "warmed_programs",
                "warmup_programs_total",
                "warmup_cache_hits_total",
                "warmup_cache_misses_total",
                # What the start was made of (engine/flight_recorder.py
                # START_PHASES).
                "start_runtime_seconds",
                "start_weights_seconds",
                "start_build_seconds",
                "start_warmup_seconds",
                "gpu_prefix_cache_hit_rate",
                "spec_tokens_per_step",
                "spec_active",
                "spec_drafted_tokens_total",
                "spec_accepted_tokens_total",
                "degraded_requests_total",
                "unified_step_tokens_decode_total",
                "unified_step_tokens_prefill_total",
                "unified_operand_transfers_total",
                "diffusion_passes_total",
                "diffusion_committed_tokens_total",
                "diffusion_commits_ridden_total",
                "diffusion_commits_lone_total",
                "moe_grouped_rows_total",
                "recurrent_state_slots_in_use",
                "recurrent_state_bytes",
                "recurrent_state_bytes_per_slot",
                "recurrent_state_usage_perc",
                "kda_chunk_tiles_total",
                "kda_chunk_rows_total",
                "ssd_chunk_tiles_total",
                "ssd_chunk_rows_total",
                "ssd_decode_lanes_total",
                # The cache by layer group (docs/architecture/
                # cache_groups.md): each pool's share in use, blocks
                # released behind a window, preemptions by the pool that
                # ran out.
                "kv_full_usage_perc",
                "kv_window_usage_perc",
                "kv_window_released_blocks_total",
                "kv_preemptions_full_pool_total",
                "kv_preemptions_window_pool_total",
                "batch_fill_ratio",
                "coloc_quantum",
                "itl_ema_ms",
                "itl_p95_ms",
                "itl_headroom_ms",
                "itl_slo_violations_total",
                "coloc_prefill_deferrals_total",
                "prefill_backlog_tokens",
                "abandoned_traces_total",
                "flight_steps_total",
                "engine_handoff_wakeups_total",
                "engine_handoff_items_total",
                "engine_handoff_wait_seconds_total",
                'attn_folds_total{tile="short"}',
                'attn_folds_total{tile="long"}',
                "attn_expanded_spans_total",
                "attn_expanded_rows_total",
                "kv_blocks_offered_total",
                "kv_blocks_stored_total",
                "last_dispatch_age_s",
                "num_waiting_interactive",
                "num_waiting_batch",
                "shed_interactive_total",
                "shed_batch_total",
                # Weight precision (docs/architecture/weight_quant.md) —
                # not kv_/kvbm_-prefixed, so the family loop below would
                # miss them.
                "weight_quant_active",
                "weight_quant_bytes_saved",
                "weight_quant_density",
            ):
                if key in eng:
                    self.metrics.set_gauge(key, float(eng[key]))
            # KV observatory gauges carry their family in the name —
            # actual-reuse totals and the block manager's tier telemetry
            # (docs/architecture/observability.md "KV observatory").
            for key, val in eng.items():
                if key.startswith(("kv_reused_", "kvbm_")) and isinstance(
                    val, (int, float)
                ):
                    self.metrics.set_gauge(key, float(val))
        # Router-plane gauges (route counts, indexer staleness, scrape
        # failures) from any KvRouter living in this process — frontends
        # running KV-aware routing export them next to the HTTP metrics.
        from dynamo_tpu.llm.kv_router.audit import ROUTE_OBS

        for key, val in ROUTE_OBS.gauges().items():
            self.metrics.set_gauge(key, float(val))
        # Planner-plane gauges (scale decisions, pool sizes, decision
        # age) from any planner living in this process — the decision
        # JSONL used to be their only sink (docs/architecture/planner.md).
        from dynamo_tpu.planner.obs import PLANNER_OBS

        for key, val in PLANNER_OBS.gauges().items():
            self.metrics.set_gauge(key, float(val))
        # Robustness + overload counters are process-wide (every seam and
        # gate in this process), so they export even without an engine
        # readiness hook (e.g. a frontend-only process shedding load).
        from dynamo_tpu.runtime.failover import FAILOVER
        from dynamo_tpu.utils.faults import FAULTS
        from dynamo_tpu.utils.retry import RETRIES

        self.metrics.set_gauge(
            "faults_injected_total", float(FAULTS.total_injected)
        )
        self.metrics.set_gauge("retries_total", float(RETRIES.total))
        self.metrics.set_gauge(
            "shed_requests_total", float(OVERLOAD.shed_total)
        )
        self.metrics.set_gauge(
            "deadline_exceeded_total", float(OVERLOAD.deadline_total)
        )
        # Failover plane (docs/architecture/failure_model.md "Mid-stream
        # failover"): process-wide — a frontend-only process is exactly
        # where failovers happen, so they export even without an engine.
        self.metrics.set_gauge("failover_total", float(FAILOVER.total))
        self.metrics.set_gauge(
            "failover_success_total", float(FAILOVER.success_total)
        )
        self.metrics.set_gauge(
            "workers_marked_dead_total", float(FAILOVER.marked_dead_total)
        )
        # Which way this process's routers dispatched (runtime/egress.py):
        # a one-process deployment reads every request local and none on
        # the wire, a frontend of remote workers the reverse.
        for path in ("local", "wire"):
            self.metrics.set_gauge(
                f"router_dispatch_{path}_total",
                float(FAILOVER.dispatch_total(path)),
            )
        # Per-class shed counters (llm/slo.py; process-wide like
        # shed_requests_total): the cheapest-first contract is only
        # auditable with the split visible.
        self.metrics.set_gauge(
            "shed_interactive_total",
            float(OVERLOAD.shed_class_total(slo.INTERACTIVE)),
        )
        self.metrics.set_gauge(
            "shed_batch_total", float(OVERLOAD.shed_class_total(slo.BATCH))
        )
        adm = self.admission.snapshot()
        self.metrics.set_gauge("draining", float(adm["draining"]))
        self.metrics.set_gauge("admission_inflight", float(adm["inflight"]))
        self.metrics.set_gauge(
            "admission_rejected_total", float(adm["rejected_total"])
        )
        # Per-class admission gauges: inflight / admitted / rejected by
        # SLO class, plus the live load-proportional Retry-After hints.
        for cls in slo.CLASSES:
            self.metrics.set_gauge(
                f"admission_inflight_{cls}",
                float(adm["inflight_by_class"].get(cls, 0)),
            )
            self.metrics.set_gauge(
                f"admission_admitted_{cls}_total",
                float(adm["admitted_by_class"].get(cls, 0)),
            )
            self.metrics.set_gauge(
                f"admission_rejected_{cls}_total",
                float(adm["rejected_by_class"].get(cls, 0)),
            )
        for reason, hint in adm["retry_after_by_reason"].items():
            self.metrics.set_gauge(
                f"admission_retry_after_{reason}_s", float(hint)
            )
        return web.Response(
            text=self.metrics.render() + tracer().render()
            + FAILOVER.render_labeled() + RETRIES.render_labeled(),
            content_type="text/plain",
        )

    async def _models(self, _request: web.Request) -> web.Response:
        listing = ModelList(data=[ModelInfo(id=m) for m in self.manager.models()])
        return web.json_response(listing.model_dump())

    # -- debug surface (docs/architecture/observability.md) -----------------
    async def _debug_steps(self, request: web.Request) -> web.Response:
        """Last N engine step records from the flight recorder ring."""
        if self._debug is None:
            return _error(404, "no local engine attached", kind="debug_error")
        try:
            n = int(request.query.get("n", 64))
        except ValueError:
            return _error(400, "n must be an integer")
        return web.json_response(
            {"steps": self._debug.debug_steps(n)}
        )

    async def _debug_trace(self, request: web.Request) -> web.Response:
        """Live tracer snapshot: histogram digest + recent completed
        traces (the in-process tail of the DYNTPU_TRACE capture)."""
        try:
            n = int(request.query.get("n", 32))
        except ValueError:
            return _error(400, "n must be an integer")
        return web.json_response(tracer().snapshot(n))

    async def _debug_routes(self, request: web.Request) -> web.Response:
        """Last N route-audit records from any KvRouter in this process
        (docs/architecture/observability.md "KV observatory"): the full
        candidate field per decision plus router-plane gauges."""
        from dynamo_tpu.llm.kv_router.audit import ROUTE_OBS

        try:
            n = int(request.query.get("n", 64))
        except ValueError:
            return _error(400, "n must be an integer")
        return web.json_response(ROUTE_OBS.snapshot(n))

    async def _debug_profile(self, request: web.Request) -> web.Response:
        """On-demand TPU profiling window (?seconds=N) — serving
        continues while the window captures. Requires a configured
        profile directory (utils/profiling.py security rails)."""
        if self.profiler is None or not self.profiler.configured:
            return _error(
                503,
                "profiling not configured — set --profile-dir / "
                "DYNTPU_PROFILE_DIR",
                kind="profile_error",
            )
        try:
            seconds = float(request.query.get("seconds", 5.0))
        except ValueError:
            return _error(400, "seconds must be a number")
        try:
            result = await self.profiler.capture(seconds)
        except ProfileError as exc:
            return _error(
                409 if exc.busy else 503, str(exc), kind="profile_error"
            )
        return web.json_response(result)

    async def _embeddings(self, request: web.Request) -> web.Response:
        """/v1/embeddings: fan each input out to the embeddings pipeline and
        fold the vectors (reference: openai.rs:212)."""
        try:
            body = await request.json()
            oai = EmbeddingRequest.model_validate(body)
        except Exception as exc:  # noqa: BLE001
            return _error(400, f"invalid request: {exc}")
        engine = self.manager.get(oai.model)
        if engine is None:
            return _error(404, f"model {oai.model!r} not found")

        raw = oai.input
        if isinstance(raw, str) or (raw and isinstance(raw[0], int)):
            inputs = [raw]  # one string / one pre-tokenized prompt
        else:
            inputs = list(raw)
        if not inputs or any(not item for item in inputs):
            return _error(400, "input must be non-empty")
        # Admit only after validation: every early return above must not
        # hold a permit (a leaked slot would wedge the gate permanently).
        try:
            permit = self.admission.admit(
                request_class=request.headers.get(REQUEST_CLASS_HEADER)
            )
        except AdmissionRejected as exc:
            return _shed_response(exc.reason, exc.retry_after_s, exc.draining)

        async def one(idx: int, item):
            payload = (
                {"token_ids": list(item)}
                if isinstance(item, list)
                else {"input": item}
            )
            ctx = Context(payload)
            try:
                async for out in engine.generate(ctx):
                    return idx, out
                raise RuntimeError("embedding engine returned no output")
            finally:
                # A router-backed engine opens a trace for this Context
                # (route span + envelope context); embeddings never reach
                # the chat path's finish, so close it here — otherwise
                # every input pins a RequestTrace until the TTL sweep and
                # inflates abandoned_traces_total, burying the real-leak
                # signal that counter exists to catch. No-op for local
                # engines that never opened one.
                tracer().finish(ctx.id)

        with permit, self.metrics.guard(oai.model, "embeddings") as guard:
            try:
                results = await asyncio.gather(
                    *[one(i, item) for i, item in enumerate(inputs)]
                )
            except ValueError as exc:
                return _error(400, str(exc))
            except Exception as exc:  # noqa: BLE001
                logger.exception("embeddings failed")
                return _error(500, str(exc))
            guard.success()
        if oai.encoding_format == "base64":
            # OpenAI contract: little-endian float32 bytes, base64-encoded.
            import base64
            import struct

            def enc(vec):
                return base64.b64encode(
                    struct.pack(f"<{len(vec)}f", *vec)
                ).decode()
        else:
            def enc(vec):
                return vec
        data = [
            EmbeddingData(index=i, embedding=enc(out["embedding"]))
            for i, out in sorted(results)
        ]
        total = sum(out["prompt_tokens"] for _, out in results)
        resp = EmbeddingResponse(
            data=data,
            model=oai.model,
            usage=Usage(prompt_tokens=total, total_tokens=total),
        )
        return web.json_response(resp.model_dump())

    async def _chat(self, request: web.Request) -> web.StreamResponse:
        return await self._serve(request, ChatCompletionRequest, "chat_completions")

    async def _completions(self, request: web.Request) -> web.StreamResponse:
        return await self._serve(request, CompletionRequest, "completions")

    def _request_deadline(self, request: web.Request) -> Deadline | None:
        """Per-request deadline: the client's header budget, else the
        configured default (admission config), else none."""
        ms = parse_timeout_ms(request.headers.get(DEADLINE_HEADER))
        if ms is not None:
            return Deadline.after_ms(ms)
        default_s = self.admission.cfg.default_deadline_s
        return Deadline.after(default_s) if default_s > 0 else None

    async def _serve(
        self, request: web.Request, request_type, endpoint: str
    ) -> web.StreamResponse:
        try:
            body = await request.json()
            oai = request_type.model_validate(body)
        except Exception as exc:  # noqa: BLE001
            return _error(400, f"invalid request: {exc}")

        engine = self.manager.get(oai.model)
        if engine is None:
            return _error(404, f"model {oai.model!r} not found")

        ctx = Context(oai)
        tracer().mark(ctx.id, "received")
        # SLO class (llm/slo.py): the header's label, defaulted by the
        # admission config — it scales the watermarks below and rides
        # the Context annotation onto the PreprocessedRequest wire, so
        # every downstream shed/preempt decision knows the class.
        request_class = slo.normalize_class(
            request.headers.get(REQUEST_CLASS_HEADER),
            self.admission.cfg.default_request_class,
        )
        ctx.annotations[slo.ANNOTATION_KEY] = request_class
        # Admission BEFORE any engine work: excess load is refused with
        # 429 + Retry-After (503 while draining) instead of queueing
        # unboundedly behind a backlog nobody can finish on time.
        # Class-weighted: batch trips the watermarks at lower pressure
        # (cheapest-first degradation), and the Retry-After hint is
        # derived from the live backlog, not a constant.
        try:
            with tracer().span(ctx.id, "admission"):
                permit = self.admission.admit(request_class=request_class)
        except AdmissionRejected as exc:
            # Refused before doing any work: a deliberate drop, not an
            # orphaned capture (trace_merge tells them apart).
            tracer().abandon(ctx.id)
            return _shed_response(exc.reason, exc.retry_after_s, exc.draining)

        deadline = self._request_deadline(request)
        if deadline is not None:
            # Threaded to the preprocessor via the context, then onto the
            # PreprocessedRequest wire through router/queue/scheduler.
            ctx.annotations["deadline"] = deadline
        with request_scope(ctx.id, tracer().trace_id(ctx.id)), permit, \
                self.metrics.guard(oai.model, endpoint) as guard:
            try:
                if oai.stream:
                    return await self._stream(request, engine, ctx, guard)
                return await self._aggregate(engine, ctx, oai, guard)
            except asyncio.CancelledError:
                ctx.kill()
                raise
            except RequestError as exc:
                # Request-validation failures (unsupported parameters,
                # over-limit logprobs, prompt too long) are client errors;
                # plain ValueError from internal bugs stays a logged 500.
                return _error(400, str(exc))
            except ShedError as exc:
                # Shed downstream (bounded queue, draining worker): typed
                # retryable rejection, never a generic 500 — 503 when the
                # instance is going away, 429 at capacity.
                return _shed_response(
                    str(exc),
                    getattr(exc, "retry_after_s", 1.0),
                    getattr(exc, "draining", False),
                )
            except DeadlineError as exc:
                # Counted where it was cancelled (engine/queue hop) — here
                # it only maps to the HTTP status.
                return _error(504, str(exc), kind="deadline_exceeded")
            except (WorkerDiedError, FailoverExhausted) as exc:
                # The worker serving this request died and the failover
                # plane could not (or may not — non-replayable stream)
                # complete it elsewhere: a clean typed 502, never a
                # generic 500 (docs/architecture/failure_model.md
                # "Mid-stream failover").
                return _error(502, str(exc), kind="worker_died")
            except Exception as exc:  # noqa: BLE001
                logger.exception("%s failed", endpoint)
                return _error(500, str(exc))
            finally:
                # Idempotent: the engine usually finished it already; this
                # folds in requests that failed before reaching the engine.
                tracer().finish(ctx.id)

    async def _stream(
        self, request: web.Request, engine, ctx: Context, guard
    ) -> web.StreamResponse:
        resp = web.StreamResponse(
            headers={
                "Content-Type": "text/event-stream",
                "Cache-Control": "no-cache",
            }
        )
        await resp.prepare(request)
        # A token's whole cost in this frame: one rendered event, one
        # transport write, and the two clock reads that say what they took
        # (GIL waits included: the engine's thread shares the interpreter).
        write = resp.write
        metrics = self.metrics
        events = metrics.stream_events
        try:
            async for chunk in engine.generate(ctx):
                arrived = time.monotonic()
                if type(chunk) is ContentDelta:
                    events["template"] += 1
                else:
                    events["object"] += 1
                await write(sse_event(chunk))
                metrics.stream_busy_s += time.monotonic() - arrived
            await resp.write(SseEvent.done().encode())
            guard.success()
        except (ConnectionResetError, asyncio.CancelledError):
            ctx.kill()
            raise
        except (
            RequestError, ShedError, DeadlineError,
            WorkerDiedError, FailoverExhausted,
        ) as exc:
            # Mid-stream request failure (tool_choice="required" with no
            # parseable call, a shed/expired request whose SSE headers
            # already went out, a worker death the failover plane could
            # not absorb): surface a terminal typed SSE error payload
            # instead of a broken socket.
            kind = {
                ShedError: "overloaded_error",
                DeadlineError: "deadline_exceeded",
                WorkerDiedError: "worker_died",
                FailoverExhausted: "worker_died",
            }.get(type(exc), "invalid_request_error")
            await resp.write(
                SseEvent.data_json(
                    {"error": {"message": str(exc), "type": kind}}
                ).encode()
            )
            await resp.write(SseEvent.done().encode())
        await resp.write_eof()
        return resp

    async def _aggregate(
        self, engine, ctx: Context, oai, guard
    ) -> web.Response:
        """Fold the stream into a full response (reference:
        protocols/openai/chat_completions/aggregator.rs)."""
        text_parts: list[str] = []
        tool_calls: list[dict] = []
        lp_content: list[dict] = []      # chat logprob entries
        lp_lists: dict[str, list] = {}   # completions parallel lists
        finish = None
        usage = Usage()
        rid = None
        is_chat = isinstance(oai, ChatCompletionRequest)
        async for chunk in engine.generate(ctx):
            if isinstance(chunk, Annotated):
                continue  # out-of-band events don't aggregate
            if isinstance(chunk, ChatCompletionChunk):
                rid = chunk.id
                for choice in chunk.choices:
                    if choice.delta.content:
                        text_parts.append(choice.delta.content)
                    if choice.delta.tool_calls:
                        tool_calls.extend(choice.delta.tool_calls)
                    if choice.logprobs and choice.logprobs.get("content"):
                        lp_content.extend(choice.logprobs["content"])
                    if choice.finish_reason:
                        finish = choice.finish_reason
                if chunk.usage:
                    usage = chunk.usage
            elif isinstance(chunk, dict):
                rid = chunk.get("id", rid)
                for choice in chunk.get("choices", []):
                    if choice.get("text"):
                        text_parts.append(choice["text"])
                    if choice.get("logprobs"):
                        for k, v in choice["logprobs"].items():
                            lp_lists.setdefault(k, []).extend(v)
                    if choice.get("finish_reason"):
                        finish = choice["finish_reason"]
                if chunk.get("usage"):
                    usage = Usage.model_validate(chunk["usage"])
        guard.success()
        text = "".join(text_parts)
        if is_chat:
            full = ChatCompletionResponse(
                id=rid or "chatcmpl-0",
                model=oai.model,
                choices=[
                    Choice(
                        message=ChatMessage(
                            role="assistant",
                            # OpenAI shape: tool-call turns carry null
                            # content, not "" — agent clients branch on it.
                            content=text if (text or not tool_calls) else None,
                            tool_calls=tool_calls or None,
                        ),
                        logprobs={"content": lp_content} if lp_content else None,
                        finish_reason=finish,
                    )
                ],
                usage=usage,
            )
        else:
            full = CompletionResponse(
                id=rid or "cmpl-0",
                model=oai.model,
                choices=[CompletionChoice(
                    text=text,
                    logprobs=lp_lists or None,
                    finish_reason=finish,
                )],
                usage=usage,
            )
        return web.json_response(full.model_dump())


def _error(
    status: int, message: str, kind: str = "invalid_request_error"
) -> web.Response:
    return web.json_response(
        {"error": {"message": message, "type": kind}},
        status=status,
    )


def _shed_response(
    reason: str, retry_after_s: float, draining: bool
) -> web.Response:
    """Typed overload rejection: 429 at capacity, 503 while draining —
    both with ``Retry-After`` so well-behaved clients and load balancers
    back off instead of retrying into the same overload."""
    return web.json_response(
        {
            "error": {
                "message": f"request rejected: {reason}",
                "type": "overloaded_error",
            }
        },
        status=503 if draining else 429,
        headers={"Retry-After": str(max(1, round(retry_after_s)))},
    )


class HealthServer:
    """Minimal worker-side health/metrics endpoint (no OpenAI surface).

    Workers serving ``dyn://`` endpoints have no HTTP service, but k8s
    readiness probes and the drain flow still need `/health` to flip when
    the engine is warming or draining — this is the probe target the Helm
    worker template points at. `/metrics` exports the engine readiness
    gauges plus the process-wide overload/robustness counters; the
    /debug surface (steps / trace / profile) mirrors HttpService's so a
    headless worker is just as observable as a frontend
    (docs/architecture/observability.md)."""

    def __init__(
        self,
        readiness,
        host: str = "0.0.0.0",
        port: int = 8081,
        debug=None,
        profiler: Profiler | None = None,
    ) -> None:
        self._readiness = readiness
        self._debug = debug
        self.profiler = profiler
        self.metrics = Metrics(prefix="dyntpu_worker")
        self.host = host
        self.port = port
        self._runner: web.AppRunner | None = None
        self.app = web.Application()
        self.app.add_routes(
            [
                web.get("/health", self._health),
                web.get("/live", self._live),
                web.get("/metrics", self._metrics),
                web.get("/debug/steps", self._debug_steps),
                web.get("/debug/trace", self._debug_trace),
                web.get("/debug/routes", self._debug_routes),
                web.get("/debug/profile", self._debug_profile),
            ]
        )

    # The worker-side debug surface delegates to the same handlers as
    # the OpenAI frontend's (unbound — shared implementation, one
    # behavior on both ports).
    _debug_steps = HttpService._debug_steps
    _debug_trace = HttpService._debug_trace
    _debug_routes = HttpService._debug_routes
    _debug_profile = HttpService._debug_profile

    async def start(self) -> "HealthServer":
        self._runner = web.AppRunner(self.app)
        await self._runner.setup()
        site = web.TCPSite(self._runner, self.host, self.port)
        await site.start()
        if self.port == 0:
            for s in self._runner.sites:
                self.port = s._server.sockets[0].getsockname()[1]  # noqa: SLF001
        logger.info("worker health server on %s:%d", self.host, self.port)
        return self

    async def stop(self) -> None:
        if self._runner:
            await self._runner.cleanup()

    def _snapshot(self) -> dict:
        try:
            return self._readiness() or {}
        except Exception:  # noqa: BLE001 — probes must never 500
            logger.exception("worker readiness probe failed")
            return {}

    async def _health(self, _request: web.Request) -> web.Response:
        eng = self._snapshot()
        state = eng.get("state", "ready")
        status = 503 if state in ("warming", "draining") else 200
        return web.json_response(
            {"status": state if status == 503 else "healthy", "engine": eng},
            status=status,
        )

    async def _live(self, _request: web.Request) -> web.Response:
        return web.json_response({"status": "live"})

    async def _metrics(self, _request: web.Request) -> web.Response:
        from dynamo_tpu.runtime.failover import FAILOVER
        from dynamo_tpu.utils.faults import FAULTS
        from dynamo_tpu.utils.retry import RETRIES

        eng = self._snapshot()
        for key, val in eng.items():
            if isinstance(val, (int, float)):  # bool included (int subclass)
                self.metrics.set_gauge(key, float(val))
        self.metrics.set_gauge(
            "engine_ready", 1.0 if eng.get("state") == "ready" else 0.0
        )
        self.metrics.set_gauge(
            "shed_requests_total", float(OVERLOAD.shed_total)
        )
        # Per-class shed split (llm/slo.py): the worker process sheds
        # too (scheduler bounds, queue bounds) — the cheapest-first
        # contract must be auditable on every surface.
        self.metrics.set_gauge(
            "shed_interactive_total",
            float(OVERLOAD.shed_class_total(slo.INTERACTIVE)),
        )
        self.metrics.set_gauge(
            "shed_batch_total", float(OVERLOAD.shed_class_total(slo.BATCH))
        )
        self.metrics.set_gauge(
            "deadline_exceeded_total", float(OVERLOAD.deadline_total)
        )
        self.metrics.set_gauge(
            "faults_injected_total", float(FAULTS.total_injected)
        )
        self.metrics.set_gauge("retries_total", float(RETRIES.total))
        # Failover plane: process-wide, like the retry/fault counters
        # (and already in `eng` when an engine readiness hook exists —
        # set_gauge overwrites with the same registry's values).
        self.metrics.set_gauge("failover_total", float(FAILOVER.total))
        self.metrics.set_gauge(
            "failover_success_total", float(FAILOVER.success_total)
        )
        self.metrics.set_gauge(
            "workers_marked_dead_total", float(FAILOVER.marked_dead_total)
        )
        for path in ("local", "wire"):
            self.metrics.set_gauge(
                f"router_dispatch_{path}_total",
                float(FAILOVER.dispatch_total(path)),
            )
        # Router-plane gauges too: a RouterService process fronts its
        # KvRouter with a HealthServer, and the indexer-staleness /
        # scrape-failure counters live exactly there.
        from dynamo_tpu.llm.kv_router.audit import ROUTE_OBS

        for key, val in ROUTE_OBS.gauges().items():
            self.metrics.set_gauge(key, float(val))
        # Planner-plane gauges too (a planner process can host a
        # HealthServer for probes; docs/architecture/planner.md).
        from dynamo_tpu.planner.obs import PLANNER_OBS

        for key, val in PLANNER_OBS.gauges().items():
            self.metrics.set_gauge(key, float(val))
        # Same surface as the frontend's /metrics: the worker process is
        # where the engine's span/ITL histograms actually accumulate in a
        # bus deployment — without the tracer render they would be
        # invisible to Prometheus exactly where they are recorded. The
        # labeled failover/retry breakdowns ride along for parity.
        return web.Response(
            text=self.metrics.render() + tracer().render()
            + FAILOVER.render_labeled() + RETRIES.render_labeled(),
            content_type="text/plain",
        )
