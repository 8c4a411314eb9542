"""TpuEngine: the first-class JAX serving engine.

The component the reference delegates to external engines (vLLM/SGLang/
TRT-LLM — reference: launch/dynamo-run/src/subprocess/vllm_v1_inc.py) — here
native: continuous batching over a paged HBM KV cache, prefix caching, and
in-process KV-event/metrics emission (no ZMQ hop; reference needed
lib/llm/src/kv_router/publisher.rs:50-120 to bridge vLLM's ZMQ events).

Threading model: JAX dispatch runs on a dedicated engine thread (the
reference's Tokio-vs-engine split); asyncio callers talk to it through
thread-safe queues. Implements the AsyncEngine contract, so it plugs
directly into pipelines/endpoints.
"""

from __future__ import annotations

import asyncio
import functools
import logging
import queue
import threading
import time
from collections import deque
from typing import Any, AsyncIterator, Callable

import numpy as np

from dynamo_tpu.engine.coloc import ColocController
from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.engine.flight_recorder import (
    START_PHASES,
    FlightRecorder,
    StepPhases,
)
from dynamo_tpu.engine.kv_cache import BlockAllocator, KvEvent
from dynamo_tpu.engine.runner import ModelRunner
from dynamo_tpu.engine.scheduler import Scheduler
from dynamo_tpu.engine.sequence import Sequence, SeqStatus
from dynamo_tpu.llm.protocols.common import (
    DeadlineError,
    EngineOutput,
    FinishReason,
    PreprocessedRequest,
    RequestError,
    ShedError,
)
from dynamo_tpu.ops.sampling import commit_floor_rows
from dynamo_tpu.runtime.engine import Context
from dynamo_tpu.runtime.failover import FAILOVER
from dynamo_tpu.utils import concurrency
from dynamo_tpu.utils.deadline import OVERLOAD
from dynamo_tpu.utils.faults import FAULTS
from dynamo_tpu.utils.retry import RETRIES
from dynamo_tpu.utils.tracing import tracer

logger = logging.getLogger(__name__)


def _in_phase(name: str):
    """The method's whole call is the phase ``name`` of the engine
    thread's pass (``self.phases``: flight_recorder.py ``PHASES``)."""

    def wrap(method):
        @functools.wraps(method)
        def in_phase(self, *args, **kwargs):
            with self.phases.phase(name):
                return method(self, *args, **kwargs)

        return in_phase

    return wrap


def _drain_handoff(
    batch: list[tuple[asyncio.Queue, tuple]], taken: Callable[[], None]
) -> None:
    """The loop's side of the hand-off (``TpuEngine._flush_outbox``): the
    frames the engine's thread emitted, each into its stream's queue, in
    the order they were emitted. ``taken`` is queued behind the streams
    the puts woke, so it runs when each of them has written its frames."""
    for out_q, item in batch:
        out_q.put_nowait(item)
    asyncio.get_running_loop().call_soon(taken)


class TpuEngine:
    def __init__(
        self,
        cfg: EngineConfig,
        params=None,
        mesh=None,
        on_kv_event: Callable[[KvEvent], None] | None = None,
        on_metrics: Callable[[dict], None] | None = None,
        block_manager=None,
        donate_params: bool = False,
        on_kv_actual: Callable[[dict], None] | None = None,
        start_phases: StepPhases | None = None,
    ) -> None:
        cfg.validate()
        self.cfg = cfg
        #: Where the engine thread's time goes, by phase of its pass
        #: (flight_recorder.py ``PHASES``): a step's flight record carries
        #: what ran up since the record before, and the same names are the
        #: profiler's host events ``engine/<phase>``.
        self.phases = StepPhases()
        #: And what this start was made of (``START_PHASES``; on
        #: ``readiness()`` as ``start_<phase>_seconds``): the caller's,
        #: where it has booked what it did before there was an engine (the
        #: CLI: jax's import and the backend's start, a checkpoint's read).
        self.start_phases = start_phases or StepPhases(START_PHASES, "start")
        self._params = params
        self._mesh = mesh
        self._donate_params = donate_params
        self._external_kv_event = on_kv_event
        self._on_metrics = on_metrics
        self.kvbm = block_manager  # KvBlockManager (G2/G3 tiers) or None
        #: The model keeps a recurrent state beside the paged cache
        #: (docs/architecture/unified_step.md "State that is not pages").
        self._rec_on = cfg.model.has_recurrent
        #: the kind of its recurrent layers ("kda" | "retention" | "ssd" |
        #: "conv")
        self._rec_kind = (
            cfg.model.layer_kind(cfg.model.recurrent_layers[0])
            if self._rec_on else ""
        )
        #: its layers whose mixer is a gated short convolution
        self._conv_layers = sum(
            cfg.model.layer_kind(li) == "conv"
            for li in cfg.model.recurrent_layers
        )
        #: what the delta rule's chunk kernel served: tiles, and the rows
        #: in them (rows / (tiles x its tile) is how full the tiles run)
        self._kda_chunk_tiles = 0
        self._kda_chunk_rows = 0
        #: and the state-space kernels: the chunk kernel's tiles and rows,
        #: the lanes of one row
        self._ssd_chunk_tiles = 0
        self._ssd_chunk_rows = 0
        self._ssd_decode_lanes = 0
        self._window_released_noted = 0
        #: The model keeps its cache by layer group: window and full
        #: layers in pools and tables of their own
        #: (docs/architecture/cache_groups.md).
        self._grouped = len(cfg.model.cache_groups) > 1
        if self._grouped and block_manager is not None:
            raise ValueError(
                f"{cfg.model.name} keeps its cache by layer group and "
                "serves without a block manager: KVBM offload and onboard "
                "and peer parking move a block of ONE pool, and a position "
                "here has a block in each group's"
            )
        if self._rec_on and block_manager is not None:
            raise ValueError(
                f"{cfg.model.name} has recurrent layers and serves "
                "without a block manager: KVBM offload and onboard and "
                "peer parking move pages, and a page has no recurrent "
                "state behind it"
            )
        # Per-tier precision pairing (docs/architecture/kv_quant.md): an
        # int8 G1 offers (int8 data, scales) — an UNQUANTIZED tier
        # layout would silently drop the sidecars and fail every store
        # on the dtype-width mismatch. (The reverse — bf16 G1 over a
        # quantized tier — is the supported quantize-on-offload path.)
        _lay = getattr(getattr(block_manager, "cfg", None), "layout", None)
        if cfg.kv_quant == "int8" and _lay is not None and _lay.quant != "int8":
            raise ValueError(
                "kv_quant='int8' requires the block manager's "
                "KvLayoutConfig to be quantized too (quant='int8') — an "
                "unquantized G2/G3 layout cannot hold the int8 G1's "
                "scale sidecars"
            )
        self._kv_events_buffer: list[KvEvent] = []
        # KV observatory (docs/architecture/observability.md): per-request
        # ACTUAL-reuse records (device/host/disk block counts) buffered on
        # the engine thread and flushed with the other side channels —
        # to the trace capture and, when wired (`on_kv_actual` →
        # KvEventPublisher.publish_hit_actual), onto the hit-rate plane.
        self._on_kv_actual = on_kv_actual
        self._kv_actuals_buffer: list[dict] = []
        self._reused_device_blocks = 0
        self._reused_host_blocks = 0
        self._reused_disk_blocks = 0
        self._reused_peer_blocks = 0
        # G4 peer pulls (block_manager/peer.py): admitted sequences
        # PARKED waiting — bounded by cfg.kvbm_peer_timeout_s — for an
        # in-flight fleet pull to land their missing prefix blocks in
        # the host tier (request_id -> Sequence; engine-thread only).
        self._peer_parked: dict[str, Sequence] = {}
        # Disagg decode side: request_id -> sequence awaiting remote KV
        # (each carries its own completeness ledger — Sequence.remote_span
        # / remote_landed — read by the activation check).
        self._remote: dict[str, Sequence] = {}
        # Pipelined unified dispatches: issued-but-unprocessed records.
        self._inflight: deque = deque()
        # The previous dispatch's device tokens and id(seq) ->
        # metadata-row map (the device feed), plus the observability
        # counters the co-location A/Bs read.
        self._prev_unified_out = None
        self._prev_unified_rows: dict[int, int] = {}
        self._unified_decode_tokens = 0
        self._unified_prefill_tokens = 0
        self._unified_fill_ratio = 0.0
        # Block diffusion: lane passes dispatched and tokens they committed.
        self._diffusion_passes = 0
        self._diffusion_committed = 0
        # Blocks committed (fed unmasked, their keys and values final) as
        # the first B rows of a 2B span, and by a commit pass of their own.
        self._diffusion_ridden = 0
        self._diffusion_lone = 0
        # SLO-aware co-location (engine/coloc.py; ROADMAP #3): the
        # controller owns the prefill quantum — static passthrough or
        # the adaptive AIMD loop fed by measured dispatch timings below.
        self.coloc = ColocController(cfg)
        # Round-robin deferral (compose_unified rotation): advances by
        # the decode lanes taken each step so an over-budget decode
        # population defers different tail lanes every step.
        self._unified_rotation = 0
        # Timestamp of the last retired unified dispatch — the other
        # half of the ITL sample (inter-retire interval when pipelined).
        self._last_unified_retire: float | None = None
        # Prefill-pressure gauge for the phase-aware HTTP admission
        # watermark: un-fed prompt tokens across waiting + prefilling,
        # refreshed on the engine thread each metrics flush and read by
        # readiness() from the asyncio thread.
        self._prefill_backlog_tokens = 0
        # Per-SLO-class waiting depth (llm/slo.py), refreshed on the
        # engine thread each metrics flush (the deque walk is
        # engine-thread-only) and read by readiness() from the asyncio
        # thread — the planner's class-weighted pressure input.
        self._waiting_by_class: dict[str, int] = {
            "interactive": 0, "batch": 0,
        }
        # Chunked prefill: admitted sequences whose prompts are still being
        # fed chunk by chunk (one chunk batch per engine step, so decode
        # chunks interleave with long prefills and token streaming never
        # stalls behind one long prompt).
        self._prefilling: list[Sequence] = []

        self.runner: ModelRunner | None = None
        self.allocator: BlockAllocator | None = None
        self.scheduler: Scheduler | None = None

        self._loop: asyncio.AbstractEventLoop | None = None
        # The hand-off to the frontend's loop (docs/architecture/
        # request_plane.md "The streamed path"): every emit appends
        # (out_q, item) here, on the engine's thread only, and
        # _flush_outbox hands the lot over in ONE wake-up of the loop.
        self._outbox: list[tuple[asyncio.Queue, tuple]] = []
        self._handoff_wakeups = 0
        self._handoff_items = 0
        self._handoff_noted = 0  # items at the last flight record
        # Set by the loop when it has written the batch it was handed
        # last: the next batch leaves behind it (_flush_outbox).
        self._handoff_taken = threading.Event()
        self._handoff_taken.set()
        self._handoff_wait_s = 0.0
        self._submit_q: queue.Queue = queue.Queue()
        self._wakeup = threading.Event()
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        self._dead: Exception | None = None
        # prefix-cache hit-rate accounting
        self._prefix_hits = 0
        self._prefix_lookups = 0
        # Live rate estimates for the kvbm adaptive onboard gate
        # (EngineConfig.kvbm_adaptive_gate): EMA bytes/s of host→HBM
        # onboarding and EMA tok/s of prefill compute, both wall-clock —
        # wall is the currency TTFT pays in.
        self._onboard_bps: float | None = None
        self._prefill_tps: float | None = None
        self._onboard_skips = 0
        self._onboard_probes = 0  # byte-capped rate probes (first + re-)
        # Injectable clock for the rate EMAs (tests drive convergence with
        # a fake clock instead of real sleeps).
        self._clock = time.monotonic
        # Degradation accounting (docs/architecture/failure_model.md):
        # requests that COMPLETED through a fallback path (remote-KV
        # transfer death ⇒ local recompute). Exported as
        # degraded_requests_total on both Prometheus surfaces.
        self._degraded_requests = 0
        # Speculative-decode observability: delivered tokens vs steps run
        # (acceptance = tokens/steps - 1; exposed via stats()), plus the
        # drafted/accepted token split every unified spec dispatch
        # records (flight recorder "spec" kind + all three metric
        # surfaces).
        self._spec_tokens = 0
        self._spec_steps = 0
        self._spec_drafted = 0
        self._spec_accepted = 0
        # Auto-gating state (cfg.speculative_break_even): rolling-window
        # counters; when the measured tokens/step drops below break-even,
        # speculation disables and plain decode takes over until
        # cfg.speculative_probe_steps plain steps have passed.
        self._spec_enabled = True
        self._spec_win_tokens = 0
        self._spec_win_steps = 0
        self._plain_steps_since_disable = 0
        self.spec_probe_count = 0  # re-enable events (observability/tests)
        # Re-probe mode: the gate disabled speculation and this window is
        # a short PROBE (cfg.speculative_probe_window steps), not a full
        # measurement window — losing traffic pays ~0%, not 12.5%.
        self._spec_probing = False
        # Graceful drain (docs/architecture/overload_and_drain.md): once
        # set, new requests are refused with ShedError while everything
        # already submitted runs to completion; `drained` flips true when
        # the last in-flight sequence finishes.
        self._draining = False
        # Compile lifecycle (engine/compile_cache.py): readiness state,
        # and the degraded-serving flag set when an un-warmed engine takes
        # traffic anyway (warmup_gate="degraded").
        self._state = "init"  # init -> warming -> ready
        self._served_unwarmed = False
        # Last-dispatch heartbeat (docs/architecture/failure_model.md
        # "Mid-stream failover"): monotonic stamp of the most recent
        # engine-thread pass. readiness()/health export its AGE — a
        # wedged dispatch thread shows up as a growing age on a process
        # whose /health would otherwise keep answering 200, which is the
        # liveness signal external watchdogs key failure detection on.
        self._last_dispatch_mono = time.monotonic()
        # Step flight recorder (engine/flight_recorder.py): every
        # dispatch leaves a record in a bounded ring — served live by
        # /debug/steps, dumped to disk when the engine loop faults.
        self.flight = FlightRecorder(
            cfg.flight_record_capacity, cfg.flight_record_dir
        )

    # -- lifecycle ----------------------------------------------------------
    async def start(self) -> None:
        self._loop = asyncio.get_running_loop()
        shards = 1
        if self.cfg.kv_sp:
            # Striped allocation: logical block i on sp shard i % sp, the
            # placement contract the striped attention scan relies on
            # (ops/attention.py; kv_cache.py BlockAllocator docstring).
            # The mesh may arrive as an object OR as cfg.mesh_shape (the
            # CLI flow — the runner builds it later); both must stripe,
            # and _build_runner cross-checks the resolved sp below.
            if self._mesh is not None:
                shards = self._mesh.shape.get("sp", 1)
            else:
                shards = int(self.cfg.mesh_shape.get("sp", 1))
        # One pool a cache group; none for a model no layer of which pages
        # (every layer keeps a recurrent state): slots are its only
        # resource, and `allocator` stays None.
        pools = []
        if self.cfg.model.has_pool:
            self.allocator = BlockAllocator(
                self.cfg.num_blocks,
                self.cfg.block_size,
                enable_prefix_caching=self.cfg.enable_prefix_caching,
                on_event=self._queue_kv_event,
                num_shards=shards,
            )
            # A further cache group (a windowed one beside the
            # full-attention group) has a pool of its own; no prefix is
            # matched there.
            pools = [self.allocator] + [
                BlockAllocator(
                    n, self.cfg.block_size, enable_prefix_caching=False
                )
                for n in self.cfg.group_num_blocks[1:]
            ]
        self.scheduler = Scheduler(self.cfg, *pools)
        # start() runs on the asyncio loop: bind it for the runtime
        # affinity checker (no-op unless DYNTPU_CHECK_THREADS=1).
        concurrency.bind_thread("loop")
        # Device allocation + first compile happen off the event loop.
        await asyncio.to_thread(self._build_runner)
        # dynalint: allow[DT007] deliberate: _state writes are monotonic one-way transitions (init->warming before Thread.start(), warming->ready idempotent from either side); racing writers store the same value
        self._state = "warming"
        self._thread = threading.Thread(
            target=self._engine_loop, name="tpu-engine", daemon=True
        )
        self._thread.start()

    def _build_runner(self) -> None:
        with self.start_phases.phase("build"):
            self.runner = ModelRunner(
                self.cfg, params=self._params, mesh=self._mesh,
                rng_seed=self.cfg.seed, donate_params=self._donate_params,
                phases=self.phases, start_phases=self.start_phases,
            )
        if self.allocator and self.runner.kv_shards != self.allocator.num_shards:
            # Placement/scan contract violated (e.g. a mesh resolved to a
            # different sp than the allocator striped for) — serving would
            # be silently wrong, so die loudly instead.
            raise RuntimeError(
                f"allocator striped for {self.allocator.num_shards} shards "
                f"but the runner's mesh has sp={self.runner.kv_shards}"
            )
        if self._donate_params:
            self._params = None  # donated to the runner; drop the dead ref

    async def stop(self) -> None:
        self._stop.set()
        self._wakeup.set()
        if self._thread:
            await asyncio.to_thread(self._thread.join, 5.0)

    # -- graceful drain -----------------------------------------------------
    def begin_drain(self) -> None:
        """Enter DRAINING: refuse new requests (generate/begin_remote raise
        ShedError, remote prefill batches resolve None so the queue
        redelivers) while every already-submitted sequence runs to
        completion. `/health` flips to 503 via readiness(), so routers and
        k8s evict the instance while in-flight responses finish — the
        loss-free half of a rolling restart."""
        if not self._draining:
            self._draining = True
            logger.info("engine draining: refusing new work")

    @property
    def draining(self) -> bool:
        return self._draining

    @property
    def drained(self) -> bool:
        """True when nothing is left in flight: no scheduled work, no
        remote-KV waits, no issued-but-unprocessed decode chunks, and no
        queued submissions."""
        return (
            self.scheduler is not None
            and not self.scheduler.has_work
            and not self._remote
            and not self._inflight
            and self._submit_q.empty()
        )

    async def wait_drained(self, timeout_s: float = 30.0) -> bool:
        """Await in-flight completion after begin_drain(); returns True if
        the engine fully drained within the grace period."""
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            if self._dead or self.drained:
                return self._dead is None
            await asyncio.sleep(0.02)
        return self.drained

    async def warmup(self) -> int:
        """Compile the serving shape set before taking traffic (runs on the
        engine thread; see ModelRunner.warmup). Serving without this pays
        tens of seconds of XLA compile on the first request of each new
        shape."""
        if self._dead:
            raise RuntimeError(f"engine dead: {self._dead}")
        fut: asyncio.Future = self._loop.create_future()
        self._submit_q.put(("warmup", (fut,)))
        self._wakeup.set()
        return await fut

    def _validate_request(self, pre: PreprocessedRequest) -> None:
        """Reject unsupported parameter combinations loudly (RequestError →
        HTTP 400) — shared by the local path (generate) and the disagg
        decode path (begin_remote)."""
        from dynamo_tpu.ops.sampling import MAX_LOGPROBS

        s = pre.sampling
        if pre.logprobs is not None and pre.logprobs > MAX_LOGPROBS:
            raise RequestError(
                f"top_logprobs={pre.logprobs} exceeds the supported "
                f"maximum of {MAX_LOGPROBS}"
            )
        extras = bool(
            s.frequency_penalty or s.presence_penalty
            or pre.logprobs is not None
        )
        if extras and not self.cfg.sampling_extras:
            raise RequestError(
                "frequency_penalty/presence_penalty/logprobs are disabled "
                "on this engine (sampling_extras=False)"
            )
        if extras and self.cfg.speculative_k:
            raise RequestError(
                "frequency_penalty/presence_penalty/logprobs are not "
                "supported with speculative decoding"
            )
        if self.cfg.model.diffusion_block_length and (
            extras or pre.mm_segments
        ):
            # A block's rows are sampled together and committed out of
            # order: counts over "the tokens so far" and a per-token
            # logprob stream have no defined meaning there yet.
            raise RequestError(
                "frequency_penalty/presence_penalty/logprobs and "
                "multimodal inputs are not supported by a block-diffusion "
                f"model ({self.cfg.model.name})"
            )

    # -- AsyncEngine --------------------------------------------------------
    async def generate(self, request: Context) -> AsyncIterator[dict]:
        if self._dead:
            raise RuntimeError(f"engine dead: {self._dead}")
        if self._draining:
            # Drain: refuse NEW work with a typed retryable error (the
            # router/load balancer sends it elsewhere); everything already
            # submitted keeps running to completion. Class-tagged so the
            # per-class shed split never diverges from the total.
            OVERLOAD.note_shed(
                "engine.draining",
                request_class=_payload_class(request.payload),
            )
            raise ShedError(
                "engine draining — retry another instance", draining=True
            )
        pre = (
            PreprocessedRequest.from_wire(request.payload)
            if isinstance(request.payload, dict)
            else request.payload
        )
        if pre.deadline is not None and pre.deadline.expired:
            OVERLOAD.note_deadline("engine.arrival")
            raise DeadlineError("request deadline expired before admission")
        s = pre.sampling
        self._validate_request(pre)
        out_q: asyncio.Queue = asyncio.Queue()
        assert self._loop is not None
        seq = Sequence(
            request_id=request.id,
            prompt_tokens=list(pre.token_ids),
            sampling=s,
            stop=pre.stop,
            emit=self._emitter(out_q),
            logprobs=pre.logprobs,
            deadline=pre.deadline,
            slo_class=_request_class(pre),
            mm_segments=_decode_mm_segments(pre.mm_segments),
        )
        tracer().adopt(request.id, pre.trace)
        tracer().mark(request.id, "engine_queued")
        self._submit_q.put(("add", seq))
        self._wakeup.set()
        async for item in self._stream(request, seq, out_q):
            yield item

    def _emitter(self, out_q: asyncio.Queue):
        """A sequence's ``emit``: the frame joins the outbox (engine
        thread only) and crosses to the loop at the next flush, behind
        everything emitted before it, so a request's token and its finish
        frame cannot pass each other."""

        def emit(
            token: int | None, finish: FinishReason | None, lp=None
        ) -> None:
            self._outbox.append((out_q, (token, finish, lp)))

        return emit

    def _flush_outbox(self) -> None:
        """Hand what the engine's thread has emitted to the loop: ONE
        wake-up for the lot (a retired dispatch's tokens, or whatever a
        pass of the engine's loop emitted outside a retire), the
        ``put_nowait``s in emission order on the loop's side."""
        batch = self._outbox
        if not batch:
            return
        taken = self._handoff_taken
        if not taken.is_set():
            # The loop is still writing the batch before this one: this
            # thread is a step ahead of it, and hands the interpreter
            # over until it has caught up. One step is in the channel at
            # a time, so tokens queue nowhere when the two threads
            # together need more of the interpreter than a device step
            # lasts; where the device sets the pace the loop has long
            # finished and nothing waits here.
            t0 = time.monotonic()
            with self.phases.phase("handoff_wait"):
                while not taken.wait(0.1) and not self._stop.is_set():
                    pass
            self._handoff_wait_s += time.monotonic() - t0
        taken.clear()
        self._outbox = []
        self._handoff_wakeups += 1
        self._handoff_items += len(batch)
        self._loop.call_soon_threadsafe(_drain_handoff, batch, taken.set)

    async def _stream(
        self, request: Context, seq: Sequence, out_q: asyncio.Queue
    ) -> AsyncIterator[dict]:
        count = 0
        last_tok_s: float | None = None
        rid = request.id
        trace = tracer()
        try:
            while True:
                token, finish, lp = await out_q.get()
                if token is not None:
                    count += 1
                    now = time.monotonic()
                    if count == 1:
                        trace.mark(rid, "first_token")
                        # KV-ready → token-on-the-stream is the tail of
                        # the TTFT decomposition; steady-state decode is
                        # its own span from here.
                        trace.span_end(rid, "decode_first")
                        trace.span_begin(rid, "decode")
                    else:
                        # Per-token ITL observation: the aggregate decode
                        # interval hides the tail — a single stalled gap
                        # is invisible in (finish - first)/n.
                        trace.observe_itl(
                            1000.0 * (now - last_tok_s), rid, now
                        )
                    last_tok_s = now
                    # The frame as ``EngineOutput.to_wire`` spells it.
                    frame = {
                        "token_ids": [token], "text": None,
                        "finish_reason": None, "cum_tokens": count,
                        "kv_transfer_params": None,
                    }
                    if lp is not None:
                        frame["logprobs"] = [lp]
                    yield frame
                if finish is not None:
                    if finish is FinishReason.ERROR:
                        # An engine fault reaches the consumer as an
                        # ERROR finish frame, not an exception — the
                        # stream ends NORMALLY, so no downstream except
                        # clause ever marks the trace. Record it here or
                        # the capture shows a clean completion for a
                        # request that died.
                        trace.mark_if_active(rid, "error")
                    yield EngineOutput(
                        token_ids=[], finish_reason=finish, cum_tokens=count
                    ).to_wire()
                    return
                if request.is_stopped:
                    # Graceful stop: end the stream with CANCELLED rather
                    # than raising into our own consumer.
                    yield EngineOutput(
                        token_ids=[],
                        finish_reason=FinishReason.CANCELLED,
                        cum_tokens=count,
                    ).to_wire()
                    return
        except Exception:
            # A mid-generation fault unwinds THROUGH this generator, so
            # the finally below pops the trace before the consumer's
            # except clause runs — its mark_if_active(rid, "error")
            # would no-op. Record the mark here, under the still-open
            # trace. (GeneratorExit / CancelledError are BaseException:
            # a consumer closing the stream early is not an error.)
            tracer().mark_if_active(request.id, "error")
            raise
        finally:
            tracer().finish(request.id)
            if seq.status is not SeqStatus.FINISHED:
                self._submit_q.put(("abort", seq))
                self._wakeup.set()

    # -- engine thread ------------------------------------------------------
    def _engine_loop(self) -> None:
        # The dedicated dispatch thread: bind it for the runtime
        # affinity checker (no-op unless DYNTPU_CHECK_THREADS=1).
        concurrency.bind_thread("engine")
        try:
            while not self._stop.is_set():
                if not self._pass():
                    with self.phases.phase("idle"):
                        self._wakeup.wait(timeout=0.01)
                    self._wakeup.clear()
        # dynalint: allow[DT003] top-of-thread catch: records _dead, fails every queued seq loudly
        except Exception as exc:
            logger.exception("engine loop died")
            self._dead = exc
            # Black box out FIRST: the steps leading into the fault are
            # the postmortem evidence (best-effort, never raises).
            self.flight.dump_fault(f"{type(exc).__name__}: {exc}")
            for seq in list(self.scheduler.running.values()) + list(
                self.scheduler.waiting
            ):
                seq.status = SeqStatus.FINISHED
                seq.emit(None, FinishReason.ERROR)
            # Fail queued submissions too — a pending warmup/prefill future
            # must error, not hang, on a dead engine.
            while True:
                try:
                    op, arg = self._submit_q.get_nowait()
                except queue.Empty:
                    break
                if op == "add":
                    arg.status = SeqStatus.FINISHED
                    arg.emit(None, FinishReason.ERROR)
                elif op in ("warmup", "remote_prefill_batch", "add_remote"):
                    # Futures live at differing positions per op (batch
                    # submissions carry one per item) — fail them all.
                    futs = [
                        a for a in arg if isinstance(a, asyncio.Future)
                    ]
                    if op == "remote_prefill_batch":
                        futs = [f for _, _, f in arg[0]]
                    for fut in futs:
                        self._loop.call_soon_threadsafe(
                            lambda f=fut, e=exc: f.set_exception(
                                RuntimeError(f"engine dead: {e}")
                            )
                            if not f.done()
                            else None
                        )
            # The ERROR frames, and whatever the pass that died had
            # emitted before it, leave on the way out.
            self._flush_outbox()

    def _pass(self) -> bool:
        """One pass of the engine's loop; whether it did work."""
        with self.phases.span("pass"):
            did_work = self._step()
            # Heartbeat: every completed loop pass (dispatch or idle
            # poll) proves the thread is alive and not wedged inside
            # a collective/compile — the stamp readiness() ages.
            self._last_dispatch_mono = time.monotonic()
            with self.phases.phase("side_channels"):
                self._flush_side_channels()
            # What the pass emitted outside a retire (an expiry, a shed, an
            # abort, a refused prompt) leaves now.
            self._flush_outbox()
        return did_work

    @_in_phase("drain")
    def _drain_submissions(self) -> None:
        while True:
            try:
                op, arg = self._submit_q.get_nowait()
            except queue.Empty:
                return
            if op == "add":
                self.scheduler.add(arg)
            elif op == "abort":
                self.scheduler.abort(arg)
            elif op == "remote_prefill_batch":
                self._run_remote_prefill_batch(*arg)
            elif op == "add_remote":
                self._admit_remote(*arg)
            elif op == "scatter_remote":
                self._scatter_remote(*arg)
            elif op == "scatter_remote_batch":
                self._scatter_remote_batch(*arg)
            elif op == "activate_remote":
                self._activate_remote(*arg)
            elif op == "cancel_remote":
                self._cancel_remote(arg)
            elif op == "warmup":
                self._run_warmup(*arg)

    def _run_warmup(self, fut) -> None:
        """Warm the whole shape set synchronously: the future resolves
        when it is compiled and the engine is ready for traffic."""
        loop = self._loop

        def resolve(action, value):
            # Bind eagerly: the except-variable is cleared when the except
            # block exits, before the loop runs the callback.
            loop.call_soon_threadsafe(
                lambda: action(value) if not fut.done() else None
            )

        try:
            with self.start_phases.phase("warmup"):
                n = self.runner.run_warm_ops(self.runner.warm_ops())
            # The warm-up's seconds are the start's, not a step's.
            self.phases.take()
            self._state = "ready"
            resolve(fut.set_result, n)
        except Exception as exc:  # dynalint: allow[DT003] propagated: the warmup future re-raises on the caller
            resolve(fut.set_exception, exc)

    def _admission_held(self) -> bool:
        """warmup_gate="hold": no new work starts until the shape set
        is compiled — requests queue in the scheduler instead of paying
        (or racing) the compiles."""
        return self.cfg.warmup_gate == "hold" and self._state != "ready"

    def _note_unwarmed_traffic(self) -> None:
        """Degraded-mode transition: an engine that takes traffic before
        any warmup serves it (first shapes compile mid-traffic and are
        counted), and the fact is flagged rather than silent."""
        if self._state == "warming":
            self._state = "ready"
            self._served_unwarmed = True
            logger.warning(
                "serving before warmup completed — first executions of "
                "each shape will compile mid-traffic (degraded; see "
                "mid_traffic_compiles_total)"
            )

    def _step(self) -> bool:
        return self._step_unified()

    # -- THE engine step (docs/architecture/unified_step.md) ---------------
    def _step_unified(self) -> bool:
        """One engine iteration — the ONLY step path: retire ready
        dispatches, admit/advance prefills, compose ONE token-budget
        batch mixing decode lanes (draft-verify spans when speculation
        is active) with chunked-prefill quanta, dispatch it. Prefill
        never head-of-line blocks decode — they share every dispatch —
        and the only compiled shape is the token budget."""
        self._drain_submissions()
        sched = self.scheduler
        did = False
        if sched.waiting:
            with self.phases.phase("admit"):
                sched.expire_waiting()

        # 1. Retire in-flight unified dispatches (device-ready ones, plus
        #    the oldest when the pipeline is at depth). Speculative mode
        #    runs depth-1: each dispatch's variable progress (and the
        #    host token history prompt-lookup drafts from) must be
        #    host-known before the next issue. (A block-diffusion model
        #    keeps its depth: a block's next pass is fed from the device.)
        depth = 1 if self._spec_active else self.cfg.pipeline_depth
        while self._inflight and (
            len(self._inflight) >= depth
            or self._chunk_ready(self._inflight[0])
        ):
            self._process_chunk(self._inflight.popleft())
            self._drain_submissions()
            did = True

        # 2. Admit new prompts into the prefilling set (chunk quanta are
        #    taken by composition below, not by a separate prefill step).
        self._admit_prefills()

        # 3. Compose + dispatch one mixed batch (async — doesn't block).
        if len(self._inflight) < depth and self._issue_unified():
            return True

        # 4. Nothing new to issue — retire the oldest dispatch if any.
        if self._inflight:
            self._process_chunk(self._inflight.popleft())
            return True
        return did

    # Tokens of trailing history the prompt-lookup bigram scan walks per
    # lane per dispatch (engine-thread work — bounded so a match-less
    # long context can't stall the step loop).
    DRAFT_SCAN_WINDOW = 512

    def _draft_tokens(self, seq: Sequence) -> list[int]:
        """Prompt-lookup drafts for one greedy decode lane: the latest
        earlier occurrence of the trailing bigram in the HOST token
        history supplies up to speculative_k continuation tokens. The
        lookup needs no device-resident history: spec runs depth-1, so
        the history is always host-known at issue, and the unified
        dispatch is ONE step."""
        cfg = self.cfg
        limit = min(
            cfg.speculative_k,
            # Context cap: every draft position's KV write must stay
            # inside max_model_len (the bonus sample sits at the next
            # position).
            seq.context_cap(cfg.max_model_len) - 1,
            # A spec span can never exceed half the budget — compose
            # guarantees decode keeps at least that much.
            max(1, cfg.unified_token_budget // 2) - 1,
        )
        if seq.stop.max_tokens is not None:
            # Drafts past the request's remaining budget would be
            # delivered-then-discarded — pure verify waste.
            limit = min(
                limit, seq.stop.max_tokens - len(seq.output_tokens) - 1
            )
        if limit <= 0:
            return []
        prompt, out = seq.prompt_tokens, seq.output_tokens
        P = len(prompt)
        n = P + len(out)
        if n < 3:
            return []

        def tok(i: int) -> int:
            # Virtual prompt‖output indexing — no per-step O(context)
            # concatenation on the engine thread.
            return prompt[i] if i < P else out[i - P]

        a, b = tok(n - 2), tok(n - 1)
        # Bounded backward scan: this runs per greedy lane per dispatch
        # on the engine thread, so an unbounded walk over a long context
        # with no match would serialize ahead of every dispatch. Recent
        # history is also where repetition lives (the prompt-lookup
        # premise); a match further back than the window is unlikely to
        # predict the continuation anyway.
        floor = max(0, n - 3 - self.DRAFT_SCAN_WINDOW)
        for j in range(n - 3, floor - 1, -1):
            if tok(j) == a and tok(j + 1) == b:
                return [
                    tok(i) for i in range(j + 2, min(j + 2 + limit, n))
                ]
        return []

    @_in_phase("compose")
    def _issue_unified(self) -> bool:
        """Compose one token-budget batch (scheduler.compose_unified:
        decode lanes first — draft-verify spans when speculation is
        active — then prefill quanta) and dispatch it through
        ModelRunner.unified_step. Returns True if anything was issued."""
        from dynamo_tpu.engine.scheduler import compose_unified

        t_compose = time.monotonic()
        cfg = self.cfg
        sched = self.scheduler
        spec_on = self._spec_active
        lookahead = (cfg.speculative_k if spec_on else 0) + 1
        decode_ready = []
        for seq in sched.decode_batch(lookahead=lookahead):
            if (
                seq.inflight_chunks > 0
                and id(seq) not in self._prev_unified_rows
            ):
                # Its newest token lives in a dispatch older than the one
                # we kept the row map for — skip this step; it becomes
                # host-known when that dispatch processes.
                continue
            decode_ready.append(seq)
        B_blk = cfg.model.diffusion_block_length
        prefill_items = [
            (s, self._prefill_target(s) - s.prefill_cursor)
            for s in self._prefilling
            if s.status is SeqStatus.PREFILLING
        ]
        # Variant detection BEFORE drafting: draft rows ride only the
        # budget-ladder program — the extras/multimodal variants keep
        # the last-row contract, so a step that needs them composes
        # plain decode spans (extras × spec is request-rejected anyway;
        # an mm prefill co-resident with spec lanes just costs those
        # lanes one plain step).
        has_extras = cfg.sampling_extras and (
            any(s.needs_extras for s in decode_ready)
            or any(s.needs_extras for s, _ in prefill_items)
        )
        has_mm = any(s.mm_segments for s, _ in prefill_items)
        draft_map: dict[int, list[int]] = {}
        if spec_on and not has_extras and not has_mm:
            for seq in decode_ready:
                if seq.inflight_chunks > 0:
                    continue  # token not host-known (depth-1 makes this rare)
                if (
                    seq.sampling.temperature is not None
                    and seq.sampling.temperature > 0.0
                ):
                    # Sampled lanes accept zero drafts by law — drafting
                    # for them would burn budget on guaranteed-rejected
                    # verify rows. (They still count as spec steps for
                    # the auto-gate: see the gate accounting at retire.)
                    continue
                drafts = self._draft_tokens(seq)
                if drafts:
                    draft_map[id(seq)] = drafts
        if B_blk:
            # A lane's span is its block's B rows, or 2B where its commit
            # rides (_commit_rides). Rides are granted inside the rows the
            # budget has left once every lane has its B rows and a waiting
            # prompt its quantum, so that a ride never costs another lane
            # its step: a lane left without takes the lone commit pass.
            room = (
                cfg.unified_token_budget - B_blk * len(decode_ready)
                - min(self.coloc.quantum, sum(r for _, r in prefill_items))
            )
            decode_items = []
            for seq in decode_ready:
                ride = room >= B_blk and self._commit_rides(seq)
                room -= B_blk * ride
                decode_items.append((seq, B_blk * (1 + ride)))
        else:
            decode_items = [
                (seq, 1 + len(draft_map.get(id(seq), [])))
                for seq in decode_ready
            ]
        decode_take, prefill_take = compose_unified(
            decode_items, prefill_items, cfg.unified_token_budget,
            self.coloc.quantum, rotation=self._unified_rotation,
        )
        if not decode_take and not prefill_take:
            return False
        self._unified_rotation += len(decode_take)

        S = self.runner.unified_slots
        use_prev = np.zeros(S, bool)
        prev_row = np.zeros(S, np.int32)
        lanes = []
        draft_lens: list[int] = []
        roles: list[tuple] = []  # (seq, kind, start, n, deliver)
        n_drafted = 0
        for seq, width in decode_take:
            s = len(lanes)
            if B_blk:
                # A block pass: the block's ids at its B positions, -1
                # where a row is fed as a mask. Without a masked row it
                # is the block's commit pass. With a pass of the block
                # still in flight, what that pass was fed is host-known
                # (everything before it has retired) and decides this
                # one: behind a commit pass the NEXT block opens, all
                # masks; behind a denoising pass the same block is fed
                # from the device, whatever that pass commits; and where
                # that pass is known to complete the block (_commit_rides)
                # the commit RIDES: one span of 2B rows, the finished
                # block fed from the device, then the next block's masks.
                start, fed = seq.blk_start, seq.blk_ids
                if seq.blk_inflight > 0:
                    if any(t < 0 for t in fed):
                        use_prev[s] = True
                        prev_row[s] = self._prev_unified_rows[id(seq)]
                        if width > B_blk:
                            self._open_block(seq)
                            fed = fed + seq.blk_ids
                    else:
                        self._open_block(seq)
                        if seq.status is not SeqStatus.RUNNING:
                            continue  # the next block passes the limit
                        start, fed = seq.blk_start, seq.blk_ids
                lanes.append(
                    (fed, seq.lane_block_ids, start, self._lane_sampling(seq))
                )
                draft_lens.append(0)
                roles.append((seq, "block", start, len(fed), True))
                seq.inflight_chunks += 1
                seq.blk_inflight += 1
                continue
            n = seq.device_len
            drafts = draft_map.get(id(seq), []) if width > 1 else []
            if drafts:
                # Draft-verify span: feed the (host-known) last token
                # plus the drafts; per-row logits verify them
                # in-dispatch and the accepted length comes back as a
                # device array (processed at retire, like the tokens).
                lanes.append((
                    [seq.last_token] + drafts, seq.lane_block_ids, n - 1,
                    self._lane_sampling(seq),
                ))
                draft_lens.append(len(drafts))
                roles.append((seq, "spec", n - 1, len(drafts), True))
                n_drafted += len(drafts)
                seq.inflight_chunks += 1
                seq.sched_len = seq.total_len  # reconciled at process time
                continue
            if seq.inflight_chunks > 0:
                use_prev[s] = True
                prev_row[s] = self._prev_unified_rows[id(seq)]
                tok = 0  # replaced on device by the previous dispatch's sample
            else:
                tok = seq.last_token
            lanes.append(
                ([tok], seq.lane_block_ids, n - 1, self._lane_sampling(seq))
            )
            draft_lens.append(0)
            roles.append((seq, "decode", n - 1, 1, True))
            seq.inflight_chunks += 1
            seq.sched_len = n + 1
        mm_rows: list = []
        starved = False
        for seq, n in prefill_take:
            s = len(lanes)
            start = seq.prefill_cursor
            if B_blk:
                # Quanta end on a diffusion block's boundary (the budget,
                # the quantum and the target are multiples of B).
                n -= n % B_blk
                if n <= 0:
                    continue
            if not sched.fund_span(seq, start + n):
                starved = True  # a windowed group's pool is full: next pass
                continue
            toks = seq.prompt_tokens[start : start + n]
            lanes.append(
                (toks, seq.lane_block_ids, start, self._lane_sampling(seq))
            )
            draft_lens.append(0)
            if seq.mm_segments:
                while len(mm_rows) < s:
                    mm_rows.append(None)
                mm_rows.append(_mm_for_chunk(seq, start, n))
            seq.prefill_cursor = start + n
            done = seq.prefill_cursor >= self._prefill_target(seq)
            roles.append((seq, "prefill", start, n, done and not B_blk))
            seq.inflight_chunks += 1
            if done and B_blk:
                # The prompt's whole blocks are fed; its tail opens the
                # first generated block, whose passes hand out the tokens.
                seq.status = SeqStatus.RUNNING
                self._open_block(seq)
            elif done:
                # Decodable from the NEXT dispatch: its first generated
                # token is this dispatch's sample at row s, read on
                # device through the feed (delivered at process time).
                # sched_len counts that PENDING token, so the next decode
                # span feeds at position P with context P+1 even before
                # this dispatch's tokens are host-known.
                seq.status = SeqStatus.RUNNING
                seq.sched_len = seq.total_len + 1

        if starved and not lanes:
            # Nothing was funded: no empty dispatch; the caller retires
            # the oldest one in flight, which releases blocks.
            return False
        extras = None
        if has_extras:
            extras = {
                "slots": [
                    (seq.slot if seq.slot is not None else -1)
                    for seq, *_r in roles
                ],
                # A decode step's FED token counts on entry: decode
                # spans count, prefill quanta never do.
                "counts_add": [kind == "decode" for _, kind, *_r in roles],
                "reset": [],
                "freq": [],
                "pres": [],
            }
            for seq, *_r in roles:
                extras["reset"].append(seq.counts_reset_pending)
                seq.counts_reset_pending = False
                sp = seq.sampling
                extras["freq"].append(sp.frequency_penalty or 0.0)
                extras["pres"].append(sp.presence_penalty or 0.0)
        mm_arg = None
        if any(m for m in mm_rows):
            mm_arg = mm_rows + [None] * (len(lanes) - len(mm_rows))

        prev = (
            self._prev_unified_out
            if self._prev_unified_out is not None
            else np.zeros((S, B_blk) if B_blk else S, np.int32)
        )
        # A sequence's state slot is the batch slot it owns from admission
        # to release (slot 0 of the table is the trash slot).
        rec_kw = (
            {"state_slots": [seq.slot + 1 for seq, *_r in roles]}
            if self._rec_on else {}
        )
        # Dispatch-start timestamp: paired with the retire time in
        # _process_unified_chunk to measure what decode lanes actually
        # waited (the mocker pays its simulated cost inside this call;
        # a real runner dispatches async and the cost shows up as the
        # inter-retire interval instead — the sample logic covers both).
        t_dispatch = self._clock()
        out = self.runner.unified_step(
            lanes,
            feed=(prev, prev_row, use_prev),
            draft_lens=(draft_lens if n_drafted else None),
            extras=extras,
            mm=mm_arg,
            **rec_kw,
        )
        self._prev_unified_out = out.last
        self._prev_unified_rows = {
            id(seq): i for i, (seq, *_r) in enumerate(roles)
        }
        n_dec = (
            sum(r[3] for r in roles if r[1] == "block") if B_blk
            else len(decode_take)
        )
        n_pre = sum(r[3] for r in roles if r[1] == "prefill")
        self._unified_decode_tokens += n_dec
        self._unified_prefill_tokens += n_pre
        self._spec_drafted += n_drafted
        from dynamo_tpu.engine.compile_cache import token_budget

        total_toks = n_dec + n_pre + n_drafted
        # extras/mm dispatches pad to the TOP budget rung (the one warm
        # program per variant) — the fill ratio must reflect the padding
        # actually paid, or the co-location surfaces overstate fill.
        padded = token_budget(
            cfg.unified_token_budget
            if (extras is not None or mm_arg is not None)
            else total_toks,
            cfg.unified_token_budget,
        )
        self._unified_fill_ratio = total_toks / padded
        lp = None
        if extras is not None:
            lp = self.runner.last_unified_logprobs
        # Issue timestamp: prefill-only dispatches sample the recompute-
        # cost EMA for the kvbm adaptive gate at process time; the
        # dispatch-start timestamp feeds the coloc ITL sample.
        # spec_counted: whether this dispatch's decode lanes feed the
        # auto-gate's measurement window — captured AT ISSUE, so plain
        # dispatches already in flight when a re-probe flips the gate on
        # can never contaminate the probe window with 1.0-tok/step
        # samples (they were never given the chance to draft; counting
        # them would re-disable speculation before a single draft-verify
        # dispatch runs: the gate measures spec dispatches only).
        spec_counted = spec_on and not has_extras and not has_mm
        compose_ms = 1000.0 * (time.monotonic() - t_compose)
        # The runner's count for THIS dispatch (a record noted at its
        # retire would read the next one's).
        # (short folds, long folds, expanded spans, expanded rows)
        folds = (
            *getattr(self.runner, "attn_folds", (0, 0)),
            *getattr(self.runner, "attn_expanded", (0, 0)),
        )
        self._inflight.append(
            (
                "unified",
                roles,
                (
                    n_dec, n_pre, self._clock(), t_dispatch, n_drafted,
                    spec_counted, compose_ms, folds,
                ),
                (out, lp),
            )
        )
        if B_blk:
            # A block dispatch records at retire too: what it committed
            # is device-side until then.
            self._diffusion_passes += sum(r[1] == "block" for r in roles)
        elif n_drafted == 0 and out.moe_counts is None:
            # Spec dispatches record at PROCESS time instead (the
            # accepted counts are device-side until retire), and so does
            # one whose expert layers hand out counts; everything else
            # records at issue, as before.
            self._note_step(
                "unified",
                **self._plain_note(roles, n_dec, n_pre, compose_ms, folds),
            )
        # Auto-gate re-probe: after speculative_probe_steps plain decode
        # steps, run a short probe window of spec steps and re-judge
        # against break-even.
        if cfg.speculative_k and not self._spec_enabled and n_dec:
            self._plain_steps_since_disable += 1
            if (
                self._plain_steps_since_disable
                >= cfg.speculative_probe_steps
            ):
                self._spec_enabled = True
                self._spec_probing = True
                self._spec_win_tokens = 0
                self._spec_win_steps = 0
                self.spec_probe_count += 1
                logger.info("speculative decode re-probing")
        return True

    @_in_phase("retire")
    def _process_unified_chunk(self, record) -> None:
        """Force one unified dispatch's tokens and run the host-side
        bookkeeping: decode lanes deliver their token, draft-verify
        spans deliver their accepted drafts + bonus, completed prefill
        lanes deliver the prompt's first token, every lane registers the
        blocks its KV writes filled."""
        _, roles, stats, payload = record
        out, lp = payload
        # The forced reads below are the wait for the device, a phase of
        # their own inside the retire.
        phase = self.phases.phase
        with phase("retire_wait"):
            toks = np.asarray(out.last)  # dynalint: allow[DT005] the pipeline's designed retire point — one forced transfer per dispatch, depth keeps it off the dispatch path
        (
            n_dec, n_pre, t_issue, t_dispatch, drafted,
            spec_counted, compose_ms, folds,
        ) = stats
        B_blk = self.cfg.model.diffusion_block_length
        blk_ids = None
        experts_hit = 0
        if B_blk:
            with phase("retire_wait"):
                if n_dec:
                    blk_ids = np.asarray(out.toks)  # dynalint: allow[DT005] same retirement boundary as `toks`
                experts_hit = int(np.asarray(out.experts_hit))  # dynalint: allow[DT005] same retirement boundary as `toks`
        elif out.moe_counts is not None:
            # The plain program of a model with grouped expert layers: its
            # flight record is noted here, with their counts.
            with phase("retire_wait"):
                hit, rows_held = np.asarray(out.moe_counts).tolist()  # dynalint: allow[DT005] same retirement boundary as `toks`
            self._note_step(
                "unified",
                **self._plain_note(roles, n_dec, n_pre, compose_ms, folds),
                moe_experts_hit=hit,
                moe_rows_held=rows_held,
            )
        spec_counts = spec_toks = None
        if drafted:
            # Spec contract: the emitted rows + device-side accepted
            # lengths force at the same retirement boundary as the
            # tokens (no extra host RTT on the dispatch path).
            with phase("retire_wait"):
                spec_toks = np.asarray(out.toks)  # dynalint: allow[DT005] same retirement boundary as `toks`
                spec_counts = np.asarray(out.counts)  # dynalint: allow[DT005] same retirement boundary as `toks`
        lp_np = None
        if lp is not None and any(
            s.logprobs is not None for s, *_r in roles
        ):
            with phase("retire_wait"):
                # dynalint: allow[DT005, DT005, DT005] logprob arrays force at the same chunk-retirement boundary as the tokens — one batched transfer
                lp_np = tuple(np.asarray(a) for a in lp)
        now = self._clock()
        if n_dec:
            # ITL sample for the coloc controller: when this dispatch
            # was issued BEFORE the previous one retired (pipelined
            # back-to-back), decode lanes experienced the inter-retire
            # interval; otherwise (pipeline drained / mocker, whose
            # simulated cost is paid synchronously inside the issue
            # call) they experienced dispatch-start → retire. max()
            # with the issue-side wall covers the mocker-pipelined
            # corner where retires land back-to-back after serialized
            # sleeps. Draft-verify rows stretch the dispatch exactly
            # like prefill rows do, so they count as prefill-side
            # evidence for the AIMD grow law (engine/coloc.py).
            last = self._last_unified_retire
            if last is not None and last >= t_dispatch:
                gap_ms = 1000.0 * (now - last)
            else:
                gap_ms = 1000.0 * (now - t_dispatch)
            self.coloc.observe(
                max(gap_ms, 1000.0 * (t_issue - t_dispatch)),
                n_dec, n_pre + drafted,
            )
        self._last_unified_retire = now
        if n_pre and not n_dec:
            # Prefill-only dispatch: a clean recompute-rate sample for
            # the kvbm adaptive onboard gate (mixed dispatches would
            # misattribute decode time to prefill; pipelining can only
            # OVERstate the interval, which understates tok/s — the
            # conservative direction for the gate).
            self._note_prefill_rate(n_pre, self._clock() - t_issue)
        for seq, *_rest in roles:
            seq.inflight_chunks -= 1
        n_accepted = 0
        n_committed = denoise_rows = commit_rows = ride_rows = n_spans = 0
        for i, (seq, kind, start, n, deliver) in enumerate(roles):
            if kind == "block":
                # The ids that come back are the span's LAST B rows': the
                # block at ``head``. A span of 2B rows is a ride: its first
                # B rows were the finished block before it, fed unmasked.
                # What the pass was fed at ``head`` is the host's state of
                # that block (for a device-fed pass, what the pass before
                # it left): ``blk_ids``, or ``blk_behind`` where the
                # sequence opened its next block at compose behind this
                # pass (a lone commit pass; the pass a ride follows, which
                # still delivers the block's last tokens).
                seq.blk_inflight -= 1
                n_spans += 1
                head = start + n - B_blk
                moved_on = head != seq.blk_start
                fed_masked = sum(
                    t < 0 for t in (seq.blk_behind if moved_on else seq.blk_ids)
                )
                denoise_rows += B_blk if fed_masked else 0
                commit_rows += n - B_blk if fed_masked else n
                ride_rows += n - B_blk
                if seq.status is not SeqStatus.RUNNING:
                    continue  # stopped while in flight; the pass is void
                if n > B_blk:
                    self._commit_block(seq, start)
                    self._diffusion_ridden += 1
                ids = blk_ids[i].tolist()  # dynalint: allow[DT005] a row of the host array forced above, no device value
                if moved_on:
                    seq.blk_behind = ids
                else:
                    seq.blk_ids = ids
                left = sum(t < 0 for t in ids)
                n_committed += fed_masked - left
                # A token leaves once every position before it is
                # committed: deliver the committed run behind what
                # has been delivered (stop conditions end the request
                # at that token, whatever lies committed behind it).
                while seq.status is SeqStatus.RUNNING:
                    j = seq.total_len - head
                    if j >= B_blk or ids[j] < 0:
                        break
                    self._deliver(seq, ids[j])
                if fed_masked and not left:
                    tracer().span_end(seq.request_id, "block_denoise")
                    if moved_on and seq.status is SeqStatus.RUNNING:
                        # the ride behind this pass denoises the next block
                        tracer().span_begin(seq.request_id, "block_denoise")
                if fed_masked == 0 and seq.status is SeqStatus.RUNNING:
                    # The lone commit pass; the next block opens unless
                    # it was opened behind this pass already.
                    self._commit_block(seq, head)
                    self._diffusion_lone += 1
                    if not moved_on:
                        self._open_block(seq)
            elif kind in ("decode", "spec"):
                if seq.status is not SeqStatus.RUNNING:
                    continue  # stopped while in flight; token discarded
                if spec_counted:
                    # Gate accounting: every decode
                    # lane-step of a dispatch ISSUED with speculation
                    # active counts one spec step; delivered tokens are
                    # the numerator. Dispatches issued while gated off
                    # (or forced plain by extras/mm) never feed the
                    # window — see the issue-side capture.
                    self._spec_steps += 1
                    self._spec_win_steps += 1
                if kind == "spec":
                    c = int(spec_counts[i])
                    n_accepted += max(0, c - 1)
                    for j in range(c):
                        if seq.status is not SeqStatus.RUNNING:
                            break
                        # The step fed seq.last_token — its KV is in
                        # cache now (accepted drafts were fed in this
                        # same dispatch).
                        if seq.hashes is not None:
                            seq.hashes.append(seq.last_token)
                        self.scheduler.register_filled_blocks(
                            seq, seq.total_len
                        )
                        self._deliver(seq, int(spec_toks[i, j]))
                        self._spec_tokens += 1
                        self._spec_win_tokens += 1
                    seq.sched_len = seq.total_len
                else:
                    # The step fed seq.last_token — its KV is now in cache.
                    if seq.hashes is not None:
                        seq.hashes.append(seq.last_token)
                    self.scheduler.register_filled_blocks(seq, seq.total_len)
                    tok = int(toks[i])
                    self._deliver(seq, tok, self._lp_at(lp_np, seq, i, tok))
                    if spec_counted:
                        self._spec_tokens += 1
                        self._spec_win_tokens += 1
            else:
                if seq.status not in (
                    SeqStatus.PREFILLING, SeqStatus.RUNNING
                ):
                    continue  # aborted mid-prompt; KV writes were harmless
                self.scheduler.register_filled_blocks(seq, start + n)
                self.scheduler.evict_behind_window(seq, start + n)
                if deliver and seq.status is SeqStatus.RUNNING:
                    if self.kvbm is not None:
                        # Prompt fully fed: stage its blocks into the
                        # host tier.
                        self._offload_prompt_blocks(seq)
                    tok = int(toks[i])
                    self._deliver(seq, tok, self._lp_at(lp_np, seq, i, tok))
        # The dispatch's lanes are delivered: its tokens cross to the
        # frontend's loop in one wake-up, and stream while this thread
        # admits, composes and waits for the device in the next retire.
        self._flush_outbox()
        for seq, *_rest in roles:
            if seq.defer_release and seq.inflight_chunks == 0:
                seq.defer_release = False
                self.scheduler._release(seq)
            elif seq.status is SeqStatus.RUNNING:
                self.scheduler.evict_behind_window(seq, seq.total_len)
        if B_blk:
            self._diffusion_committed += n_committed
            self._note_step(
                "unified",
                decode_tokens=n_dec,
                prefill_tokens=n_pre,
                fill=self._unified_fill_ratio,
                dispatch_ms=compose_ms,
                lanes=len(roles),
                diffusion_lanes=n_spans,
                denoise_rows=denoise_rows,
                commit_rows=commit_rows,
                ride_rows=ride_rows,
                committed_tokens=n_committed,
                moe_experts_hit=experts_hit,
                folds=folds,
            )
        if drafted:
            self._spec_accepted += n_accepted
            # Spec dispatches record their flight entry at retirement —
            # the drafted/accepted split is the record's whole point.
            # dispatch_ms stays the ISSUE-side compose time (captured in
            # the record) so the field means the same thing on every
            # step kind; the device-side latency is the coloc ITL
            # sample's job, not this field's.
            self._note_step(
                "spec",
                decode_tokens=n_dec,
                prefill_tokens=n_pre,
                fill=self._unified_fill_ratio,
                dispatch_ms=compose_ms,
                lanes=len(roles),
                drafted=drafted,
                accepted=n_accepted,
                folds=folds,
            )
        if self.cfg.speculative_k:
            self._maybe_gate_speculation()

    def _commit_block(self, seq: Sequence, start: int) -> None:
        """The block at ``start`` was fed without a masked row (a lone
        commit pass, or a ride's first B rows): its keys and values are
        final. Its tokens, all delivered by now, join the hash chain (the
        chain holds the prompt from admission on), and only then are the
        pages it completed offered for reuse."""
        end = start + self.cfg.model.diffusion_block_length
        if seq.hashes is not None:
            P = len(seq.prompt_tokens)
            seq.hashes.extend(
                seq.output_tokens[len(seq.hashes) - P : end - P]
            )
        self.scheduler.register_filled_blocks(seq, end)

    def _plain_note(self, roles, n_dec, n_pre, compose_ms, folds) -> dict:
        """The flight-record fields of a plain unified dispatch; where the
        model keeps recurrent state, what its state table saw beside them."""
        note = dict(
            decode_tokens=n_dec,
            prefill_tokens=n_pre,
            fill=self._unified_fill_ratio,
            dispatch_ms=compose_ms,
            lanes=len(roles),
            folds=folds,
        )
        if self._conv_layers:
            # Gated short convolutions: no kernel of their own and no split
            # by span length, so every row fed goes through every such
            # layer alike (rows x those layers).
            note["conv_rows"] = (n_dec + n_pre) * self._conv_layers
        elif self._rec_on:
            # What the state table saw, under its layers' kind.
            kind = self._rec_kind
            lanes = sum(r[3] == 1 for r in roles)
            rows = sum(r[3] for r in roles if r[3] > 1)
            note.update({
                f"{kind}_decode_lanes": lanes,
                # (the state-space record names the rows by their kernel)
                f"{kind}_{'chunk' if kind == 'ssd' else 'prefill'}_rows": rows,
                f"{kind}_fresh_spans": sum(r[2] == 0 for r in roles),
            })
            if kind == "ssd":
                from dynamo_tpu.ops.pallas.ssd import TILE

                # A span of more rows is whole tiles of the chunk kernel.
                tiles = sum(-(-r[3] // TILE) for r in roles if r[3] > 1)
                note["ssd_chunk_tiles"] = tiles
                self._ssd_chunk_tiles += tiles
                self._ssd_chunk_rows += rows
                self._ssd_decode_lanes += lanes
            if kind == "kda":
                from dynamo_tpu.ops.pallas.kda import TILE

                # A span of more rows is whole tiles of the chunk kernel.
                tiles = sum(-(-r[3] // TILE) for r in roles if r[3] > 1)
                note["kda_chunk_tiles"] = tiles
                self._kda_chunk_tiles += tiles
                self._kda_chunk_rows += note["kda_prefill_rows"]
        if self.cfg.model.has_pool:
            note.update(self._cache_note())
        return note

    @functools.cached_property
    def _page_bytes(self) -> int:
        """Bytes of one layer's pages of one block: ``cache_arrays`` arrays
        of ``block_size`` tokens at the runner's (lane-padded) head width."""
        from dynamo_tpu.block_manager.config import DTYPE_BYTES

        m = self.cfg.model
        return (
            m.cache_arrays * self.cfg.block_size * m.num_cache_heads
            * self.runner.cache_head_dim
            * DTYPE_BYTES["int8" if self.cfg.kv_quant else self.cfg.dtype]
        )

    def _cache_note(self) -> dict:
        """What the paged cache holds as a step is noted: the live bytes of
        its pools (a block of a group is its layers' pages, each layer's
        ``ModelConfig.cache_arrays`` arrays: one where a latent is held
        once) and the context tokens of the running sequences: each one's
        WHOLE length, a prefilling one's too. Where the cache is kept by
        layer group, beside them the blocks in use in the full-attention
        and in the windowed pools and the blocks released behind a window
        since the last note. The full-attention group (or the one pool) has
        drawn a block for every token at admission; a windowed group funds
        a span at a time, so a prompt's tokens not yet prefilled are
        counted and hold no bytes there yet."""
        sched = self.scheduler
        used = [sched.blocks_in_use(g) for g in range(len(sched.windows))]
        note = dict(
            kv_bytes_live=self._page_bytes * sum(
                n * layers for n, layers in zip(used, sched.group_layers)),
            context_tokens_live=sum(
                s.total_len for s in sched.running.values()),
        )
        if self._grouped:
            released = sched.window_released
            since, self._window_released_noted = (
                released - self._window_released_noted, released)
            note.update(
                kv_full_blocks=sum(
                    n for n, w in zip(used, sched.windows) if not w),
                kv_window_blocks=sum(
                    n for n, w in zip(used, sched.windows) if w),
                kv_window_released=since,
            )
        return note

    @staticmethod
    def _lp_at(lp_np, seq: Sequence, lane: int, token: int) -> dict | None:
        """One lane's logprob entry from the forced unified_full arrays
        (None when the dispatch carried no extras or the request didn't
        ask)."""
        if lp_np is None or seq.logprobs is None:
            return None
        clp, tids, tlps = lp_np
        k = seq.logprobs
        return {
            "id": token,
            "logprob": float(clp[lane]),
            "top": [
                [int(i), float(l)]
                for i, l in zip(tids[lane][:k], tlps[lane][:k])
            ],
        }

    @staticmethod
    def _chunk_ready(record) -> bool:
        out, _lp = record[3]  # (kind, roles, stats, (UnifiedOut, lp))
        is_ready = getattr(out.last, "is_ready", None)
        return bool(is_ready()) if is_ready is not None else True

    def _prefill_target(self, seq: Sequence) -> int:
        """Prompt tokens that prefill feeds: all of them, or under block
        diffusion the prompt's whole blocks (its last ``P mod B`` tokens
        open the first generated block)."""
        B = self.cfg.model.diffusion_block_length
        P = len(seq.prompt_tokens)
        return P - P % B if B else P

    def _commit_rides(self, seq: Sequence) -> bool:
        """Whether a block lane's next span may be 2B rows: its commit
        RIDES the next block's first denoising pass. The pass in flight
        was fed ``k`` masked rows (``blk_ids``: what came before it has
        retired), and the commit rule's floor hands out at least
        ``commit_floor_rows`` of them whatever the confidences read: with
        ``1 <= k <= floor`` the block comes back complete, the host knows
        it now, and the lane's next span is the finished block's B rows
        (fed from the device, unmasked: the commit) and the next block's
        B masks behind them, one pass where two. With more masks than the
        floor only the data can finish the block early, which the host
        cannot foresee: B rows, fed from the device, and a lone commit
        pass if they turn out unmasked. No ride either where the next
        block would pass the context limit or the pages funded
        (scheduler decode_batch funds a block ahead), or where the
        request's last token is in this block."""
        m = self.cfg.model
        B = m.diffusion_block_length
        end = seq.blk_start + 2 * B
        masks = sum(t < 0 for t in seq.blk_ids)
        limit = seq.stop.max_tokens
        return (
            seq.blk_inflight == 1
            and 1 <= masks <= commit_floor_rows(B, m.denoising_steps)
            and end <= self.cfg.max_model_len
            and all(len(t) * self.cfg.block_size >= end for t in seq.tables)
            and (limit is None or end - B - len(seq.prompt_tokens) < limit)
        )

    def _open_block(self, seq: Sequence) -> None:
        """Open the sequence's next diffusion block: the one behind its
        open block, or where none is open the one at its committed length.
        The tokens already known there (the prompt's tail; after a
        preemption the delivered rows, and whatever else the kept block
        had committed), masks behind them. Opened at compose behind a pass
        still in flight, the block left keeps its ids as that pass was
        fed in ``blk_behind`` until the pass retires. A block that would
        pass the context limit ends the request."""
        B = self.cfg.model.diffusion_block_length
        if seq.blk_start >= 0:
            start = seq.blk_start + B
            seq.blk_behind = seq.blk_ids if seq.blk_inflight else []
        else:
            start = seq.total_len - seq.total_len % B
        if start + B > self.cfg.max_model_len:
            self.scheduler.finish(seq, FinishReason.LENGTH)
            return
        P = len(seq.prompt_tokens)
        known = seq.prompt_tokens[start:] + seq.output_tokens[max(start - P, 0):]
        masks = [-1] * (B - len(known))
        if (
            seq.blk_start < 0
            and len(seq.blk_ids) == B
            and seq.blk_ids[: len(known)] == known
        ):
            # Re-admitted after a preemption: the block it was in.
            masks = seq.blk_ids[len(known):]
        seq.blk_ids = known + masks
        seq.blk_start = start
        tracer().span_begin(seq.request_id, "block_denoise")

    @staticmethod
    def _lane_sampling(seq: Sequence) -> tuple[float, int, float, int]:
        s = seq.sampling
        if s.seed is None:
            seed = -1  # sentinel: unseeded lane
        else:
            # OpenAI allows arbitrary integers; the lane arrays are int32,
            # and an OverflowError on the engine thread would kill serving
            # for everyone. Fold deterministically into [0, 2^31-1).
            seed = int(s.seed) % 0x7FFFFFFF
        return (
            s.temperature if s.temperature is not None else 0.0,
            s.top_k or 0,
            s.top_p if s.top_p is not None else 1.0,
            seed,
        )

    @_in_phase("admit")
    def _admit_prefills(self) -> None:
        """Admit waiting prompts into the PREFILLING set (admission
        hold, kvbm host-prefix onboarding, prefix-hit accounting, cursor
        setup); batch composition takes quanta from it directly."""
        sched = self.scheduler
        self._prefilling = [
            s for s in self._prefilling if s.status is SeqStatus.PREFILLING
        ]
        self._service_peer_parked()
        if (
            sched.waiting
            and len(self._prefilling) < self.cfg.prefill_batch
            and not self._admission_held()
            and not self.coloc.admit_prefill()
        ):
            # Per-phase admission (engine/coloc.py): decode is over its
            # ITL SLO, so growing the co-located prefill population
            # would push it further over — new prompts stay queued this
            # step (bounded: the controller's anti-starvation streak
            # admits eventually; already-PREFILLING sequences keep
            # making floor-quantum progress regardless).
            return
        while (
            not self._admission_held()
            and len(self._prefilling) < self.cfg.prefill_batch
        ):
            seq = sched.next_prefill()
            if seq is None:
                break
            self._note_unwarmed_traffic()
            if seq.status is not SeqStatus.RUNNING:
                continue
            # Admission instant: the waiting time becomes a queue_wait
            # span and the prefill span opens (closed by _deliver at
            # the first token, or by the remote-batch finish). Guards
            # cover RE-admission, which keeps the original arrival_s: a
            # preempted sequence (first_token_s set) must not re-open a
            # prefill span _deliver will never close, and a remote-KV-
            # degraded one (queue_wait already recorded by begin_remote)
            # must not record a second queue_wait spanning its entire
            # failed remote attempt — corrupt spans on exactly the
            # requests a postmortem reads. Recompute time shows up as
            # unattributed remainder instead.
            if self.kvbm is not None and self._maybe_park_for_peer_pull(seq):
                # G4: a fleet peer holds this prompt's host-missing
                # prefix at a winning price — the pull is in flight and
                # the (already funded) sequence waits, bounded, for the
                # rows to land in G2 before the onboard runs.
                continue
            self._finish_admission(seq)

    def _finish_admission(self, seq: Sequence) -> None:
        """The admission tail shared by the direct path and peer-pull
        resume: spans, host-prefix onboard, prefix-hit accounting, the
        kv_actual record, cursor setup, and entry into PREFILLING."""
        if seq.first_token_s is None:
            if not tracer().has_span(seq.request_id, "queue_wait"):
                tracer().add_span(
                    seq.request_id, "queue_wait",
                    start_mono=seq.arrival_s,
                )
            tracer().span_begin(seq.request_id, "prefill")
        if self.kvbm is not None:
            self._onboard_host_prefix(seq)
        self._prefix_lookups += 1
        if seq.num_cached_prefix:
            self._prefix_hits += 1
        self._note_kv_actual(seq)
        seq.prefill_cursor = seq.num_cached_prefix
        if (
            self.cfg.model.diffusion_block_length
            and seq.prefill_cursor >= self._prefill_target(seq)
        ):
            # Every whole block of the prompt is cached (or it has none):
            # straight to the first generated block.
            self._open_block(seq)
            return
        seq.status = SeqStatus.PREFILLING
        self._prefilling.append(seq)

    def _maybe_park_for_peer_pull(self, seq: Sequence) -> bool:
        """G4 decision at admission: when the host tier misses part of
        this prompt's prefix but a fleet peer announced it AND pulling
        beats recomputing under the live cost model, dispatch the pull
        and PARK the sequence (it is already admitted/funded; it just
        doesn't enter PREFILLING yet). Bounded by kvbm_peer_timeout_s —
        _service_peer_parked resumes it, degraded, when the deadline
        passes. One attempt per request."""
        if seq.peer_pull_tried:
            return False
        seq.peer_pull_tried = True
        kvbm = self.kvbm
        if (
            not kvbm.has_peer_client()
            or seq.mm_segments             # mm KV never enters the tier
            or seq.hashes is None
        ):
            return False
        bs = self.cfg.block_size
        start = seq.num_cached_prefix // bs
        limit = (len(seq.prompt_tokens) - 1) // bs
        if start >= limit:
            return False
        hashes = seq.hashes.sequence_hashes()[start:limit]
        n_match = kvbm.peek_host_match(hashes)
        missing = list(hashes[n_match:])
        if not missing:
            return False
        key = kvbm.plan_peer_pull(missing, prefill_tps=self._prefill_tps)
        if key is None:
            return False
        seq.peer_pull_key = key
        seq.peer_pull_deadline = (
            self._clock() + self.cfg.kvbm_peer_timeout_s
        )
        seq.peer_parked = True
        self._peer_parked[seq.request_id] = seq
        return True

    def _service_peer_parked(self) -> None:
        """Resume parked sequences whose pull settled or whose deadline
        passed (the PR 2 completeness-ledger degrade, one tier out: a
        peer death/timeout costs the request its pull, never its
        completion). Engine-thread only; runs every admission pass, and
        the idle loop's 10 ms poll bounds resume latency."""
        if not self._peer_parked:
            return
        for rid in list(self._peer_parked):
            if (
                self._admission_held()
                or len(self._prefilling) >= self.cfg.prefill_batch
            ):
                return
            seq = self._peer_parked[rid]
            if seq.status is not SeqStatus.RUNNING:
                # Preempted/aborted while parked — whoever changed the
                # status owns the sequence now (requeue resets it to
                # WAITING and admission retries it fresh).
                seq.peer_parked = False
                del self._peer_parked[rid]
                continue
            pending = self.kvbm.peer_pull_pending(seq.peer_pull_key)
            if pending and self._clock() < seq.peer_pull_deadline:
                continue
            seq.peer_parked = False
            del self._peer_parked[rid]
            if pending:
                # Deadline hit with the transfer still in flight: the
                # request proceeds by local recompute NOW (the pull
                # keeps running and warms G2 for the next request).
                self.kvbm.note_peer_fallback()
                self._degraded_requests += 1
                logger.warning(
                    "G4 pull for %s timed out after %.1fs; recomputing",
                    rid, self.cfg.kvbm_peer_timeout_s,
                )
            elif self.kvbm.peer_pull_result(seq.peer_pull_key) == 0:
                # Pull settled without landing a single block (peer died
                # mid-transfer past the retry budget, or was evicted/
                # re-priced between plan and fetch) — recompute.
                self._degraded_requests += 1
            self._finish_admission(seq)

    def _run_prefill_compute(self, seq: Sequence) -> int:
        """Shared prefill body for the REMOTE path (disagg prefill worker)
        and its multimodal lanes: onboard host prefix, run the prompt
        through back-to-back unified spans (mm soft-prompt rows scatter
        into the flat batch), register blocks, stage offloads. Returns
        the sampled first token (not yet delivered)."""
        if self.kvbm is not None:
            self._onboard_host_prefix(seq)
        prefix = seq.num_cached_prefix
        self._prefix_lookups += 1
        if prefix:
            self._prefix_hits += 1
        self._note_kv_actual(seq)
        chunk = max(1, self.cfg.unified_token_budget)
        P = len(seq.prompt_tokens)
        cursor = prefix
        token = 0
        t0 = self._clock()
        while cursor < P:
            toks = seq.prompt_tokens[cursor : cursor + chunk]
            lane = (toks, seq.block_ids, cursor, self._lane_sampling(seq))
            mm = _mm_for_chunk(seq, cursor, len(toks))
            out = self.runner.unified_step(
                [lane], mm=[mm] if mm else None
            )
            token = int(np.asarray(out.last)[0])  # dynalint: allow[DT005] remote prefill is synchronous by design — the span's token gates the hand-off
            cursor += len(toks)
        self._note_prefill_rate(P - prefix, self._clock() - t0)
        # KV now covers the whole prompt.
        self.scheduler.register_filled_blocks(seq, P)
        if self.kvbm is not None:
            self._offload_prompt_blocks(seq)
        return token

    def _note_prefill_rate(self, tokens: int, dt: float) -> None:
        """EMA of wall-clock prefill throughput — the recompute side of the
        kvbm adaptive onboard gate's cost model."""
        if tokens <= 0 or dt <= 0:
            return
        tps = tokens / dt
        self._prefill_tps = (
            tps if self._prefill_tps is None
            else 0.7 * self._prefill_tps + 0.3 * tps
        )

    def _note_onboard_rate(self, nbytes: int, dt: float) -> None:
        """EMA of host→HBM onboard bandwidth — the transfer side of the
        gate's cost model. Every sample comes from a BYTE-CAPPED window
        (PROBE_BLOCKS on an unknown/slow link), so one slow sample costs
        milliseconds and extrapolates; the EMA converges over probes."""
        if nbytes <= 0 or dt <= 0:
            return
        bps = nbytes / dt
        self._onboard_bps = (
            bps if self._onboard_bps is None
            else 0.7 * self._onboard_bps + 0.3 * bps
        )

    def _note_kv_actual(self, seq: Sequence) -> None:
        """Record what this request ACTUALLY reused, split by tier —
        the engine-side half of the predicted-vs-actual loop
        (docs/architecture/observability.md "KV observatory"). Called at
        admission, after any host-prefix onboard; once per request
        (re-admission after preemption / remote-KV degradation must not
        double-count). Buffered — flushed with the other side channels."""
        if seq.kv_actual_reported:
            return
        seq.kv_actual_reported = True
        bs = self.cfg.block_size
        total = seq.num_cached_prefix // bs
        # num_cached_prefix now covers the G1 hit PLUS everything
        # onboarded; the device share is the remainder.
        device = max(
            0,
            total
            - seq.reuse_host_blocks
            - seq.reuse_disk_blocks
            - seq.reuse_peer_blocks,
        )
        seq.reuse_device_blocks = device
        self._reused_device_blocks += device
        self._reused_host_blocks += seq.reuse_host_blocks
        self._reused_disk_blocks += seq.reuse_disk_blocks
        self._reused_peer_blocks += seq.reuse_peer_blocks
        self._kv_actuals_buffer.append(
            {
                "kind": "kv_actual",
                "id": seq.request_id,
                # Never re-opens a finished trace; "" when this process
                # holds no trace for the request (e.g. replayed tests).
                "trace": tracer().trace_id_if_active(seq.request_id) or "",
                "isl_blocks": (len(seq.prompt_tokens) + bs - 1) // bs,
                "device_blocks": device,
                "host_blocks": seq.reuse_host_blocks,
                "disk_blocks": seq.reuse_disk_blocks,
                "peer_blocks": seq.reuse_peer_blocks,
                "unix": time.time(),
            }
        )

    # Blocks an adaptive-gate rate probe moves: enough bytes for a stable
    # bandwidth sample, few enough that the FIRST victim on a 6+s-per-
    # prefix slow link pays milliseconds (the
    # unbounded first probe was a 14x p95 TTFT outlier).
    PROBE_BLOCKS = 4

    def _onboard_host_prefix(self, seq: Sequence) -> None:
        """G2→G1: extend the G1 prefix hit with host-tier blocks (scatter
        their bytes into the already-allocated cache blocks and register
        them). Runs on the engine thread, before the prefill step
        (reference: KVBM `onboard`, block_manager/offload.rs)."""
        if seq.mm_segments:
            # Placeholder tokens hash identically across different images —
            # a host-tier hit here would serve another image's KV (same
            # aliasing the scheduler guards against at G1).
            return
        bs = self.cfg.block_size
        P = len(seq.prompt_tokens)
        start = seq.num_cached_prefix // bs
        limit = (P - 1) // bs  # always leave ≥1 token to compute
        if seq.hashes is None or start >= limit:
            return
        hashes = seq.hashes.sequence_hashes()[start:limit]
        # Gate on a bytes-free hash match FIRST — deciding to skip must not
        # itself pay the prefix-sized host memcpy that match_host does.
        n_match = self.kvbm.count_host_match(hashes)
        if n_match < len(hashes):
            # Two-touch disk promotion: whatever the host tier is missing
            # may live on G3 — promote asynchronously so the NEXT request
            # with this prefix hits G2 (no-op without a disk tier).
            self.kvbm.request_disk_promotion(hashes[n_match:])
            # Two-touch G4: a fleet peer may hold it — pull at a winning
            # price so the NEXT request hits G2 (no-op without a peer
            # client; the request-BLOCKING pull already ran at admission
            # via _maybe_park_for_peer_pull, and the per-prefix in-flight
            # dedup makes this a cheap re-ask).
            self.kvbm.plan_peer_pull(
                list(hashes[n_match:]), prefill_tps=self._prefill_tps
            )
        if n_match == 0:
            return
        r = self.runner
        # Bytes per STORED host block from the layout's explicit
        # accounting (quantized tiers move packed rows at roughly half
        # the bytes — the gate must price the real transfer).
        layout = getattr(getattr(self.kvbm, "cfg", None), "layout", None)
        if layout is not None:
            block_bytes = layout.block_bytes
        else:
            block_bytes = (
                self.cfg.model.num_layers * self.cfg.model.cache_arrays * bs
                * self.cfg.model.num_cache_heads * r.cache_head_dim
                * np.dtype(self.cfg.dtype).itemsize
            )
        if self.cfg.kvbm_adaptive_gate and self._onboard_bps is None:
            # No bandwidth estimate yet: probe, don't commit. The first
            # victim onboards only PROBE_BLOCKS and extrapolates bytes/s
            # — the unbounded first onboard was a multi-second engine-
            # thread stall on exactly the slow link the gate exists for;
            # the rest of the prefix recomputes.
            self._onboard_probes += 1
            hashes = hashes[: self.PROBE_BLOCKS]
        elif (
            self.cfg.kvbm_adaptive_gate
            and self._onboard_bps and self._prefill_tps
            and (n_match * block_bytes) / self._onboard_bps
            > (n_match * bs) / self._prefill_tps
        ):
            # Moving the bytes is predicted slower than recomputing them —
            # treat the host hit as a miss (correctness is unaffected; the
            # prefill recomputes identical KV). Every 32nd skip re-probes
            # so a stale estimate (e.g. a compile-contaminated first
            # sample) can't pin the gate shut forever — but BOUNDED to
            # PROBE_BLOCKS: the probe only needs to refresh the rate EMA,
            # and a full-prefix onboard on the slow link the gate exists
            # for would stall the whole engine thread for seconds.
            self._onboard_skips += 1
            if self._onboard_skips % 32 != 0:
                return
            self._onboard_probes += 1
            hashes = hashes[: self.PROBE_BLOCKS]
        matches = self.kvbm.match_host(hashes)
        if not matches:  # raced an eviction between count and fetch
            return
        nbytes = len(matches) * block_bytes
        # One batched device call for the whole matched prefix: per-block
        # scatters pay the fixed dispatch cost once per block.
        blocks = [seq.block_ids[start + i] for i in range(len(matches))]
        sc_rows = None
        try:
            # Host-side normalize/validate BEFORE the donating dispatch: a
            # bad host-tier row (layout drift on a shared kvbm) fails here
            # with the cache untouched, so recompute-recovery is valid.
            # Quantized host tiers hand PACKED rows back: the device
            # policy decides dequant (bf16-hot G1) vs passthrough (int8
            # G1) — runner.import_host_rows.
            prepare = getattr(r, "prepare_blocks_host", None)  # sim: absent
            if (
                layout is not None
                and layout.quant == "int8"
                and prepare is not None
            ):
                rows, sc_rows = r.import_host_rows(
                    [m[3] for m in matches], layout
                )
            elif prepare is not None:
                rows = prepare([m[3] for m in matches])
            else:
                rows = [m[3] for m in matches]
        # dynalint: allow[DT003] pre-dispatch validation failure: no donation happened yet, recompute is safe
        except Exception:
            logger.exception(
                "bad host-tier rows for %s; recomputing", seq.request_id
            )
            return
        try:
            t0 = self._clock()
            if prepare is not None:
                r.scatter_many_prepared(blocks, rows)
                if sc_rows is not None:
                    r.set_block_scales(blocks, sc_rows)
            else:
                r.scatter_many(blocks, rows)
            caches = getattr(r, "kv_caches", None)  # SimRunner has none
            if caches is not None:
                import jax

                # dynalint: allow[DT005] donation safety: scattered host blocks must be resident before the next donating dispatch reuses the cache buffers
                jax.block_until_ready(caches[0][0])
            self._note_onboard_rate(nbytes, max(self._clock() - t0, 1e-6))
            for block, (h, parent, tokens, _data) in zip(blocks, matches):
                self.allocator.register(
                    block, h, parent_hash=parent, token_ids=list(tokens)
                )
            seq.num_cached_prefix = (start + len(matches)) * bs
            # Actual-reuse attribution (KV observatory): split the
            # onboarded blocks into G2-native vs G3-origin (arrived in
            # the host tier via disk promotion) for this request's
            # kv_actual record.
            matched_hashes = [m[0] for m in matches]
            disk_n = self.kvbm.count_disk_origin(matched_hashes)
            peer_n = self.kvbm.count_peer_origin(matched_hashes)
            seq.reuse_host_blocks += len(matches) - disk_n - peer_n
            seq.reuse_disk_blocks += disk_n
            seq.reuse_peer_blocks += peer_n
        except Exception as exc:  # noqa: BLE001
            if getattr(r, "kv_caches", None) is not None:
                # Row validation already passed, so this failure is in (or
                # after) the DONATING dispatch: self.kv_caches may
                # reference invalidated memory, and even a post-dispatch
                # allocator-register failure means prefix-cache state no
                # longer matches the device — "degrade to recompute" would
                # serve garbage or crash on a later step. Fatal: the
                # engine loop fails every sequence loudly (ADVICE r5).
                raise RuntimeError(
                    "host onboard failed at/after the donated KV scatter "
                    f"for {seq.request_id}; cache state is unrecoverable"
                ) from exc
            # Simulated runner (no device cache, nothing donated): degrade
            # to recompute as before.
            logger.exception(
                "host onboard failed for %s; recomputing", seq.request_id
            )

    def _offload_prompt_blocks(self, seq: Sequence) -> None:
        """G1→G2: stage the prompt's full blocks into the host tier (the
        high-reuse blocks — multi-turn prefixes; reference offloads on
        register, offload.rs:99-160)."""
        bs = self.cfg.block_size
        full = len(seq.prompt_tokens) // bs
        if seq.hashes is None or seq.mm_segments:
            return  # mm KV must not enter the token-hash-keyed host tier
        todo = []
        for idx in range(full):
            h = seq.hashes.blocks[idx]
            if self.kvbm.has_host(h.sequence_hash):
                continue
            if seq.block_ids[idx] == 0:
                # Rolling-buffer evicted page: gathering the trash block
                # would poison the host tier under a valid hash.
                continue
            todo.append((seq.block_ids[idx], h))
        if not todo:
            return
        # One async device gather for the whole prompt; the D2H
        # materialization happens on the KVBM pump thread, so this costs
        # the engine thread a dispatch, not a sync (TTFT path). An int8
        # G1 (kv_quant) also snapshots the per-block scales so the host
        # tier packs the exact device bytes instead of re-quantizing.
        ids = [b for b, _ in todo]
        datas = self.runner.gather_many_device(ids)
        scales = (
            self.runner.gather_scales_device(ids)
            if getattr(self.runner, "kv_quant", None)
            else None
        )
        self.kvbm.offer_batch(
            [
                (h.sequence_hash, h.parent_sequence_hash, h.tokens)
                for _, h in todo
            ],
            datas,
            scales=scales,
        )

    def _maybe_gate_speculation(self) -> None:
        """Auto-gate: below break-even delivered
        tokens/step over a window, speculation costs ~(K+1)/1 extra logits
        work for <1 extra token — fall back to plain decode; re-probe
        after cfg.speculative_probe_steps plain steps (traffic changes).
        A RE-probe judges after only speculative_probe_window steps, so
        repeated losing probes stay ~free; a winning probe re-commits to
        full measurement windows."""
        window = (
            self.cfg.speculative_probe_window
            if self._spec_probing
            else self.cfg.speculative_window
        )
        if self._spec_win_steps < window:
            return
        rate = self._spec_win_tokens / self._spec_win_steps
        self._spec_probing = False
        if rate < self.cfg.speculative_break_even:
            self._spec_enabled = False
            self._plain_steps_since_disable = 0
            logger.info(
                "speculative decode disabled: %.2f tok/step < break-even "
                "%.2f over %d steps",
                rate, self.cfg.speculative_break_even, self._spec_win_steps,
            )
        self._spec_win_tokens = 0
        self._spec_win_steps = 0

    def _process_chunk(self, record) -> None:
        """Force one dispatch's tokens and run host-side bookkeeping:
        emission, stop checks, block registration, deferred releases."""
        return self._process_unified_chunk(record)

    def _note_step(
        self,
        kind: str,
        *,
        decode_tokens: int = 0,
        prefill_tokens: int = 0,
        fill: float = 0.0,
        dispatch_ms: float = 0.0,
        lanes: int = 0,
        drafted: int = 0,
        accepted: int = 0,
        # the runner's counts AT ITS ISSUE: the ragged kernel's (short,
        # long) folds, then the (spans, rows) the expanded body took
        folds: tuple[int, ...] = (0, 0, 0, 0),
        **diffusion: int,  # and the expert layers' and recurrent layers' counts
    ) -> None:
        """One dispatch's flight record (engine thread). Counter fields
        are snapshots, so a reader diffs adjacent records to attribute a
        stall or shed to the exact step that paid it. ``kind="spec"``
        records carry the drafted/accepted token split of a unified
        draft-verify dispatch. ``host_*_ms`` on it are this thread's
        seconds by phase since the record before (``StepPhases.take``),
        wherever in the pass this one is noted."""
        cs = getattr(self.runner, "compile_stats", None)
        sched = self.scheduler
        self.flight.note_step(
            kind,
            decode_tokens=decode_tokens,
            prefill_tokens=prefill_tokens,
            batch_fill_ratio=fill,
            dispatch_ms=dispatch_ms,
            lanes=lanes,
            drafted=drafted,
            accepted=accepted,
            attn_short_folds=folds[0],
            attn_long_folds=folds[1],
            attn_expanded_spans=folds[2],
            attn_expanded_rows=folds[3],
            **diffusion,
            # The runner's last dispatch IS this record's: plain records
            # are noted at issue, spec records at retire under depth 1.
            operand_transfers=getattr(self.runner, "operand_transfers", 0),
            handoff_items=self._handoff_items - self._handoff_noted,
            inflight_depth=len(self._inflight),
            waiting=len(sched.waiting) if sched is not None else 0,
            running=len(sched.running) if sched is not None else 0,
            compile_stall_ms_total=(
                cs.compile_stall_ms_total if cs is not None else 0.0
            ),
            mid_traffic_compiles_total=(
                cs.mid_traffic_compiles if cs is not None else 0
            ),
            shed_total=OVERLOAD.shed_total,
            deadline_total=OVERLOAD.deadline_total,
            quantum=self.coloc.quantum if kind == "unified" else 0,
            itl_ema_ms=self.coloc.itl_ema_ms if kind == "unified" else 0.0,
            headroom_ms=self.coloc.headroom_ms if kind == "unified" else 0.0,
            host=self.phases.take(),
        )
        self._handoff_noted = self._handoff_items

    def debug_steps(self, n: int | None = None) -> list[dict]:
        """The flight recorder's last ``n`` step records — the
        /debug/steps payload (llm/http_service.py)."""
        return self.flight.snapshot(n)

    def _deliver(
        self, seq: Sequence, token: int, lp: dict | None = None
    ) -> None:
        seq.output_tokens.append(token)
        if seq.first_token_s is None:
            seq.first_token_s = time.monotonic()
            # First token computed on the engine thread: the prefill
            # span (if this engine ran one — no-op on the disagg decode
            # side) ends here, and the decode_first span covers the gap
            # until _stream puts the token on the wire.
            tracer().span_end(seq.request_id, "prefill")
            tracer().span_begin(seq.request_id, "decode_first")
        reason = seq.should_stop()
        if reason is None and seq.total_len >= self.cfg.max_model_len:
            reason = FinishReason.LENGTH
        if (
            reason is None
            and seq.deadline is not None
            and seq.deadline.expired
        ):
            # Mid-generation expiry: stop now — the tokens already
            # delivered stream out with a DEADLINE finish, further decode
            # work is cancelled.
            OVERLOAD.note_deadline("engine.decode")
            reason = FinishReason.DEADLINE
        seq.emit(token, None, lp)
        if reason is not None:
            self.scheduler.finish(seq, reason)

    # -- disaggregation (reference: docs/architecture/disagg_serving.md) ----
    # Prefill side: run prefill only, hand the KV blocks + first token out.
    # Decode side: admit a sequence whose KV a prefill worker will push in.

    async def prefill_only(
        self, pre: PreprocessedRequest, request_id: str, device: bool = False
    ) -> tuple[int, list] | None:
        """Run one prompt's prefill and return (first_token, blocks) — every
        block covering the prompt, gathered to host (or DEVICE-resident
        snapshots with ``device=True``, the HBM→HBM transfer path). None if
        the engine can't admit it right now (caller requeues). A one-item
        batch — the batched path is the single implementation."""
        return await self.prefill_only_batch([(pre, request_id, device)])[0]

    def _refuse_disagg(self) -> None:
        if self._grouped:
            raise RequestError(
                f"{self.cfg.model.name} keeps its cache by layer group and "
                "serves without remote prefill or disaggregation: a "
                "prompt's blocks are handed over from ONE pool"
            )
        if self._rec_on:
            raise RequestError(
                f"{self.cfg.model.name} has recurrent layers and serves "
                "without remote prefill or disaggregation: a prompt's "
                "blocks carry keys and values and no recurrent state"
            )

    def prefill_only_batch(
        self,
        items: list[tuple[PreprocessedRequest, str, bool]],
    ) -> list[asyncio.Future]:
        """Batched remote prefill: several prompts' chunked prefills run
        through FUSED prefill_batch lanes instead of one-request-at-a-time
        (a serial drain left the prefill engine at 1/lanes of its fused
        throughput). Items are (request, request_id, device_snapshot).

        Returns one future per item, resolved to (first_token, blocks) —
        or None if not admitted — AS EACH prompt completes: waves run
        depth-first, so early finishers ship (and release their arena
        blocks) while later prompts still compute; the caller must not
        wait for the whole batch before sending."""
        self._refuse_disagg()
        futs = [self._loop.create_future() for _ in items]
        if self._draining:
            # Draining prefill worker: refuse the batch so the queue
            # redelivers each item to a live worker (at-least-once).
            # Per-item class tags keep the split exact.
            for pre, _rid, _device in items:
                OVERLOAD.note_shed(
                    "engine.draining", request_class=_request_class(pre)
                )
            for fut in futs:
                fut.set_result(None)
            return futs
        seqs = []
        for (pre, rid, device), fut in zip(items, futs):
            seqs.append((
                Sequence(
                    request_id=rid,
                    prompt_tokens=list(pre.token_ids),
                    sampling=pre.sampling,
                    stop=pre.stop,
                    emit=lambda t, f, lp=None: None,
                    slo_class=_request_class(pre),
                ),
                device,
                fut,
            ))
        self._submit_q.put(("remote_prefill_batch", (seqs,)))
        self._wakeup.set()
        return futs

    def _run_remote_prefill_batch(self, seqs) -> None:
        loop = self._loop

        def resolve(fut: asyncio.Future, value) -> None:
            loop.call_soon_threadsafe(
                lambda: fut.set_result(value) if not fut.done() else None
            )

        bs = self.cfg.block_size
        # Keyed by id(seq), NOT request_id: at-least-once delivery can put
        # two copies of one request_id in a single batch (requeue +
        # redelivery), and shared keys would cross-resolve their futures,
        # leaving one awaited forever.
        done: set[int] = set()

        def finish(seq: Sequence, device: bool, fut: asyncio.Future,
                   token: int, registered: bool = False) -> None:
            """Register + gather + resolve + RELEASE one completed prompt
            immediately — its caller ships while later waves compute and
            its blocks refund the arena for the next admission.
            ``registered=True`` when _run_prefill_compute already did the
            register/offload half (the mm path)."""
            try:
                if not registered:
                    self.scheduler.register_filled_blocks(
                        seq, len(seq.prompt_tokens)
                    )
                    if self.kvbm is not None:
                        self._offload_prompt_blocks(seq)
                n_blocks = (len(seq.prompt_tokens) + bs - 1) // bs
                ids = [seq.block_ids[j] for j in range(n_blocks)]
                quantized = getattr(self.runner, "kv_quant", None)
                if device:
                    # One gather program for the whole prompt; shipped as a
                    # unit so the decode side scatters in one program too.
                    # Quantized caches snapshot the per-block scales in a
                    # second (tiny) gather that rides the batch.
                    from dynamo_tpu.disagg.device_transfer import BlockBatch

                    blocks = BlockBatch(
                        self.runner.gather_many_device(ids),
                        scales=(
                            self.runner.gather_scales_device(ids)
                            if quantized
                            else None
                        ),
                    )
                elif quantized:
                    # Wire frames for a quantized pair are PACKED rows
                    # (int8 data + scale sidecar — half the bytes on the
                    # transfer link); the decode side's scatter_block
                    # unpacks them.
                    blocks = self.runner.export_block_rows(ids)
                else:
                    # Wire path still ships per-block frames, but the host
                    # materialization is one batched D2H, not n_blocks
                    # RTTs. Each frame is COPIED out of the batch: frames
                    # sit in the sender's queue with independent
                    # lifetimes, and a view would pin the whole prompt's
                    # [N, ...] gather until the last frame drained
                    # (ADVICE r5).
                    batch = self.runner.gather_many(ids)
                    # dynalint: allow[DT005] copies out of ONE batched gather (already synced); the copy un-pins the whole [N, ...] buffer (ADVICE r5)
                    blocks = [np.array(batch[j]) for j in range(n_blocks)]
                # Remote prefill never reaches _deliver (the first token
                # ships to the decode side instead): the prefill span
                # closes once the blocks are gathered and ready to ship —
                # kv_transfer starts from here (disagg/worker.py).
                tracer().span_end(seq.request_id, "prefill")
                resolve(fut, (token, blocks))
            # dynalint: allow[DT003] fails ONE item: its future resolves None and the decode side recomputes
            except Exception:
                logger.exception(
                    "remote prefill gather failed for %s", seq.request_id
                )
                resolve(fut, None)
            finally:
                done.add(id(seq))
                self.scheduler._release(seq)
                seq.status = SeqStatus.FINISHED

        admitted: list[tuple[Sequence, bool, asyncio.Future]] = []
        try:
            for seq, device, fut in seqs:
                if (
                    not self._admission_held()
                    and len(seq.prompt_tokens) < self.cfg.max_model_len
                    and self.scheduler.admit(seq)
                ):
                    self._note_unwarmed_traffic()
                    tracer().add_span(
                        seq.request_id, "queue_wait",
                        start_mono=seq.arrival_s,
                    )
                    tracer().span_begin(seq.request_id, "prefill")
                    admitted.append((seq, device, fut))
                else:
                    resolve(fut, None)
            cursors: dict[int, int] = {}
            meta: dict[int, tuple[bool, asyncio.Future]] = {}
            plain: list[Sequence] = []
            for seq, device, fut in admitted:
                if seq.mm_segments:
                    # Multimodal lanes carry per-lane embed tensors the
                    # fused program doesn't take — sequential path (which
                    # registers/offloads itself). Failures stay per-item:
                    # one poison request must not abort its batchmates.
                    try:
                        finish(
                            seq, device, fut, self._run_prefill_compute(seq),
                            registered=True,
                        )
                    # dynalint: allow[DT003] fails ONE item: future resolves None, decode recomputes locally
                    except Exception:
                        logger.exception(
                            "mm remote prefill failed for %s", seq.request_id
                        )
                        resolve(fut, None)
                        done.add(id(seq))
                        self.scheduler._release(seq)
                        seq.status = SeqStatus.FINISHED
                    continue
                if self.kvbm is not None:
                    self._onboard_host_prefix(seq)
                self._prefix_lookups += 1
                if seq.num_cached_prefix:
                    self._prefix_hits += 1
                self._note_kv_actual(seq)
                cursors[id(seq)] = seq.num_cached_prefix
                meta[id(seq)] = (device, fut)
                plain.append(seq)
            # Depth-first waves through unified_step spans — the ONLY
            # programs warmup compiled, so a prefill worker never pays a
            # mid-traffic compile: the first sequences keep their lanes
            # until their prompts COMPLETE (early results), then the
            # next queued sequence takes the freed budget.
            pending = list(plain)
            while pending:
                from dynamo_tpu.engine.scheduler import compose_unified

                items = [
                    (s, len(s.prompt_tokens) - cursors[id(s)])
                    for s in pending
                ]
                _, take = compose_unified(
                    [], items, self.cfg.unified_token_budget,
                    self.cfg.unified_prefill_quantum,
                )
                # Admission is slot-bounded (≤ max_num_seqs <
                # unified_slots), so this is a belt-and-braces cap on
                # the dispatch's metadata rows, not a reachable path.
                take = take[: self.runner.unified_slots]
                wave = [s for s, _ in take]
                fed = [n for _, n in take]
                lanes = [
                    (
                        s.prompt_tokens[
                            cursors[id(s)] : cursors[id(s)] + n
                        ],
                        s.block_ids, cursors[id(s)],
                        self._lane_sampling(s),
                    )
                    for s, n in take
                ]
                out = self.runner.unified_step(lanes)
                outs = [int(t) for t in np.asarray(out.last)[: len(take)]]  # dynalint: allow[DT005] remote prefill is synchronous by design — the wave's tokens gate the depth-first hand-off
                still = []
                for seq, tok, n in zip(wave, outs, fed):
                    c = min(
                        cursors[id(seq)] + n,
                        len(seq.prompt_tokens),
                    )
                    cursors[id(seq)] = c
                    if c >= len(seq.prompt_tokens):
                        device, fut = meta[id(seq)]
                        finish(seq, device, fut, tok)
                    else:
                        still.append(seq)
                in_wave = {id(s) for s in wave}
                rest = [s for s in pending if id(s) not in in_wave]
                pending = still + rest
        # dynalint: allow[DT003] the finally below resolves every unserved future None → local recompute
        except Exception:
            logger.exception("batched remote prefill failed")
        finally:
            for seq, _, fut in admitted:
                if id(seq) not in done:
                    resolve(fut, None)
                    self.scheduler._release(seq)
                    seq.status = SeqStatus.FINISHED

    def begin_remote(self, request: Context, pre: PreprocessedRequest):
        """Decode side: admit `request` with remote KV. Returns an awaitable
        resolving to (num_blocks, stream) or None if admission failed
        (caller falls back to the local path)."""
        self._refuse_disagg()
        if self._draining:
            OVERLOAD.note_shed(
                "engine.draining", request_class=_request_class(pre)
            )
            raise ShedError(
                "engine draining — retry another instance", draining=True
            )
        if pre.deadline is not None and pre.deadline.expired:
            OVERLOAD.note_deadline("engine.arrival")
            raise DeadlineError("request deadline expired before admission")
        self._validate_request(pre)
        tracer().adopt(request.id, pre.trace)
        out_q: asyncio.Queue = asyncio.Queue()
        loop = self._loop
        seq = Sequence(
            request_id=request.id,
            prompt_tokens=list(pre.token_ids),
            sampling=pre.sampling,
            stop=pre.stop,
            emit=self._emitter(out_q),
            logprobs=pre.logprobs,
            deadline=pre.deadline,
            slo_class=_request_class(pre),
        )
        fut: asyncio.Future = loop.create_future()
        self._submit_q.put(("add_remote", (seq, fut)))
        self._wakeup.set()

        async def wait():
            info = await fut
            if info is None:
                return None
            return info, self._stream(request, seq, out_q)

        return wait()

    def _admit_remote(self, seq: Sequence, fut: asyncio.Future) -> None:
        loop = self._loop
        info = None
        if (
            not self._admission_held()
            and len(seq.prompt_tokens) < self.cfg.max_model_len  # as add()
            and self.scheduler.admit(seq)
        ):
            self._note_unwarmed_traffic()
            tracer().add_span(
                seq.request_id, "queue_wait", start_mono=seq.arrival_s
            )
            seq.status = SeqStatus.WAITING_REMOTE
            self._remote[seq.request_id] = seq
            bs = self.cfg.block_size
            # Only the uncached suffix needs transfer — the reference ships
            # just the non-prefix-hit blocks (disagg_serving.md:100-109).
            info = {
                "num_blocks": (len(seq.prompt_tokens) + bs - 1) // bs,
                "start_block": seq.num_cached_prefix // bs,
            }
            # Completeness ledger for activation: which block indices
            # actually landed. A lost frame must degrade to recompute,
            # never activate over a hole of stale KV.
            seq.remote_span = (info["start_block"], info["num_blocks"])
            seq.remote_landed = set()
        loop.call_soon_threadsafe(
            lambda: fut.set_result(info) if not fut.done() else None
        )

    def cancel_remote(self, request_id: str) -> None:
        """Decode side bailed before enqueueing (e.g. no staging slots) —
        free the admitted sequence immediately (thread-safe)."""
        self._submit_q.put(("cancel_remote", request_id))
        self._wakeup.set()

    def _cancel_remote(self, request_id: str) -> None:
        seq = self._remote.pop(request_id, None)
        if seq is not None and seq.status is SeqStatus.WAITING_REMOTE:
            self.scheduler.abort(seq)

    def on_remote_block(self, request_id: str, seq_idx: int, data) -> None:
        """Receiver callback: one block's KV bytes arrived (thread-safe)."""
        self._submit_q.put(("scatter_remote", (request_id, seq_idx, data)))
        self._wakeup.set()

    def on_remote_blocks(self, request_id: str, start_idx: int, data) -> None:
        """Receiver callback: an [N, ...] device-resident batch arrived
        (device channel) — scattered in one program (thread-safe)."""
        self._submit_q.put(
            ("scatter_remote_batch", (request_id, start_idx, data))
        )
        self._wakeup.set()

    def on_remote_finish(self, request_id: str, first_token: int) -> None:
        """Receiver callback: all blocks sent; activate decode."""
        self._submit_q.put(("activate_remote", (request_id, first_token)))
        self._wakeup.set()

    def _degrade_remote_to_local(self, request_id: str, why: str) -> None:
        """Remote-prefill degradation: the KV handoff for `request_id`
        died (transfer failure, prefill-worker death, corrupt frame) —
        release the partially-filled blocks and requeue the sequence for
        LOCAL prefill. The request completes through recompute instead of
        being dropped (the reference's degradation-to-local-prefill
        semantics, disagg_serving.md); recomputed KV overwrites whatever
        the dead transfer left behind, so no corrupt bytes survive. Late
        frames for the request find nothing in _remote and are ignored."""
        seq = self._remote.pop(request_id, None)
        if seq is None or seq.status is not SeqStatus.WAITING_REMOTE:
            return
        logger.warning(
            "remote prefill for %s degraded to local recompute (%s)",
            request_id, why,
        )
        self._degraded_requests += 1
        # trace_merge reads this mark: a degraded request legitimately
        # completes WITHOUT a kv_transfer span (local recompute) — the
        # --assert-complete gate must not flag designed fallback as a
        # broken span chain.
        tracer().mark_if_active(request_id, "degraded_local")
        seq.remote_span = None  # now a plain local sequence
        seq.remote_landed = set()
        self.scheduler.requeue_for_recompute(seq)

    def _scatter_remote(self, request_id: str, seq_idx: int, data) -> None:
        """Wire-supplied index/payload — validate; a corrupt frame must
        degrade ONE request to local recompute, never kill the engine."""
        seq = self._remote.get(request_id)
        if seq is None or seq.status is not SeqStatus.WAITING_REMOTE:
            return
        try:
            start, total = seq.remote_span or (0, len(seq.block_ids))
            if not start <= seq_idx < total:
                # Below-span indices are SHARED prefix-cache blocks other
                # sequences read — scattering there would corrupt them
                # all, not just this request.
                raise ValueError(
                    f"block index {seq_idx} outside the remote span "
                    f"[{start}, {total})"
                )
            self.runner.scatter_block(seq.block_ids[seq_idx], data)
            seq.remote_landed.add(seq_idx)
        except Exception:  # dynalint: allow[DT003] corrupt frame degrades the request to local recompute
            logger.exception("bad remote KV frame for %s", request_id)
            self._degrade_remote_to_local(request_id, "corrupt KV frame")

    def _scatter_remote_batch(self, request_id: str, start_idx: int, data) -> None:
        seq = self._remote.get(request_id)
        if seq is None or seq.status is not SeqStatus.WAITING_REMOTE:
            return
        try:
            n = int(data.shape[0])
            start, total = seq.remote_span or (0, len(seq.block_ids))
            if not (start <= start_idx and start_idx + n <= total):
                # Same shared-prefix protection as _scatter_remote.
                raise ValueError(
                    f"batch [{start_idx}, {start_idx + n}) outside the "
                    f"remote span [{start}, {total})"
                )
            ids = seq.block_ids[start_idx : start_idx + n]
            scales = getattr(data, "scales", None)
            if scales is not None:
                # Quantized device-channel batch (BlockBatch with scale
                # rows): scatter both halves; the data snapshot is
                # already in the cache dtype (int8).
                self.runner.scatter_many_device(ids, data.data)
                self.runner.set_block_scales(ids, scales)
            else:
                self.runner.scatter_many_device(ids, data)
            seq.remote_landed.update(range(start_idx, start_idx + n))
        except Exception:  # dynalint: allow[DT003] corrupt batch degrades the request to local recompute
            logger.exception("bad remote KV batch for %s", request_id)
            self._degrade_remote_to_local(request_id, "corrupt KV batch")

    def _activate_remote(self, request_id: str, first_token: int) -> None:
        seq = self._remote.get(request_id)
        if seq is None or seq.status is not SeqStatus.WAITING_REMOTE:
            return
        if seq.remote_span is not None:
            start, total = seq.remote_span
            # Set difference, not a count: even if an out-of-span index
            # ever slipped into the ledger, it must not mask a hole.
            missing = len(set(range(start, total)) - seq.remote_landed)
            if missing > 0:
                # A finish notification over a hole (lost/dropped block
                # frame): activating would decode over whatever stale KV
                # the blocks held before. Degrade — recompute rewrites
                # every block, so the request completes with CORRECT
                # tokens.
                self._degrade_remote_to_local(
                    request_id,
                    f"incomplete remote KV ({missing} of "
                    f"{total - start} blocks never landed)",
                )
                return
        self._remote.pop(request_id, None)
        seq.status = SeqStatus.RUNNING
        self.scheduler.register_filled_blocks(seq, len(seq.prompt_tokens))
        if self.kvbm is not None:
            self._offload_prompt_blocks(seq)  # remote KV is host-tier-worthy too
        self._deliver(seq, first_token)

    # -- side channels ------------------------------------------------------
    def _queue_kv_event(self, ev: KvEvent) -> None:
        self._kv_events_buffer.append(ev)

    def _expire_stale_remotes(self) -> None:
        """A prefill worker that died mid-transfer must not pin decode slots
        forever — WAITING_REMOTE sequences that time out DEGRADE to local
        recompute (the request still completes; see
        _degrade_remote_to_local) instead of erroring out."""
        now = time.monotonic()
        for rid, seq in list(self._remote.items()):
            if seq.deadline is not None and seq.deadline.expired:
                # Past its deadline while awaiting remote KV: recomputing
                # locally can't finish in time either — cancel with the
                # typed DEADLINE finish instead of degrading.
                OVERLOAD.note_deadline("engine.remote")
                self._remote.pop(rid, None)
                self.scheduler.abort(seq, FinishReason.DEADLINE)
            elif now - seq.arrival_s > self.cfg.remote_kv_timeout_s:
                self._degrade_remote_to_local(rid, "remote KV timeout")

    def _flush_side_channels(self) -> None:
        # Engine-thread-only: walks the scheduler deques and drains the
        # KV side-channel buffers, none of which are locked. The checker
        # makes that contract executable (DYNTPU_CHECK_THREADS=1).
        concurrency.assert_context(
            "engine", what="TpuEngine._flush_side_channels"
        )
        if self._remote:
            self._expire_stale_remotes()
        if self._external_kv_event:
            for ev in self._kv_events_buffer:
                try:
                    self._external_kv_event(ev)
                except Exception:  # dynalint: allow[DT003] subscriber bug must not kill the engine step loop
                    logger.exception("kv event callback failed")
        self._kv_events_buffer.clear()
        if self._kv_actuals_buffer:
            # Actual-reuse records (KV observatory): stream to the trace
            # capture (joined with route records by benchmarks/
            # route_audit.py) and, when wired, onto the hit-rate plane.
            for rec in self._kv_actuals_buffer:
                try:
                    tracer().export(rec)
                    if self._on_kv_actual is not None:
                        self._on_kv_actual(rec)
                except Exception:  # dynalint: allow[DT003] observability export must not kill the engine step loop
                    logger.exception("kv actual export failed")
            self._kv_actuals_buffer.clear()
        if self.scheduler is not None:
            # Phase-aware prefill-pressure gauge (engine thread: the
            # only place it's safe to walk the waiting deque). Read by
            # readiness() for the HTTP admission watermark.
            self._prefill_backlog_tokens = (
                self.scheduler.waiting_prompt_tokens()
                + sum(
                    max(0, len(s.prompt_tokens) - s.prefill_cursor)
                    for s in self._prefilling
                    if s.status is SeqStatus.PREFILLING
                )
            )
            # Per-class waiting split (same engine-thread-only contract
            # as the backlog walk above).
            self._waiting_by_class = self.scheduler.waiting_by_class()
        if self._on_metrics and self.scheduler is not None:
            m = self.scheduler.metrics()
            m["gpu_prefix_cache_hit_rate"] = self._prefix_hits / max(
                self._prefix_lookups, 1
            )
            if self.kvbm is not None:
                # Adaptive-gate observability: an operator can see WHY the
                # host tier is (not) being used on this deployment.
                m["kvbm_onboard_skips"] = self._onboard_skips
                if self._onboard_bps is not None:
                    m["kvbm_onboard_bps"] = round(self._onboard_bps, 1)
            # KV observatory: actual-reuse totals (always — the device
            # tier exists without a kvbm) and the block manager's tier
            # telemetry (kvbm_-prefixed; see _kvbm_gauges).
            m["kv_reused_device_blocks_total"] = self._reused_device_blocks
            m["kv_reused_host_blocks_total"] = self._reused_host_blocks
            m["kv_reused_disk_blocks_total"] = self._reused_disk_blocks
            m["kv_reused_peer_blocks_total"] = self._reused_peer_blocks
            # KV precision (docs/architecture/kv_quant.md): stored-bytes
            # ratio of this worker's G1 cache vs the compute dtype — the
            # network-aware selector's transfer-pricing input.
            m["kvbm_kv_quant_ratio"] = round(
                getattr(self.runner, "kv_bytes_ratio", 1.0), 4
            )
            # Weight precision (docs/architecture/weight_quant.md): the
            # per-matmul policy's resident footprint — HBM bytes the
            # quantized tree saves vs full precision, the quantized
            # fraction of weight bytes, and whether a policy is armed.
            m["weight_quant_active"] = getattr(
                self.runner, "weight_quant_active", 0.0
            )
            m["weight_quant_bytes_saved"] = getattr(
                self.runner, "weight_quant_bytes_saved", 0.0
            )
            m["weight_quant_density"] = round(
                getattr(self.runner, "weight_quant_density", 0.0), 4
            )
            m.update(self._kvbm_gauges())
            if self.cfg.speculative_k:
                m["spec_tokens_per_step"] = self.spec_tokens_per_step
                m["spec_active"] = int(self._spec_active)
            # Unified spec split (flight recorder "spec" kind's
            # cumulative twins): drafted vs accepted draft tokens across
            # every draft-verify dispatch. Registered unconditionally —
            # zero on engines without speculative_k.
            m["spec_drafted_tokens_total"] = self._spec_drafted
            m["spec_accepted_tokens_total"] = self._spec_accepted
            # Unified-path observability (docs/architecture/
            # unified_step.md): the per-phase token split and the
            # batch fill ratio are what the co-location A/Bs
            # (ROADMAP item #3) tune against.
            m["unified_step_tokens_decode_total"] = (
                self._unified_decode_tokens
            )
            m["unified_step_tokens_prefill_total"] = (
                self._unified_prefill_tokens
            )
            m["unified_operand_transfers_total"] = getattr(
                self.runner, "operand_transfers_total", 0
            )
            m.update(self._diffusion_counters())
            m["batch_fill_ratio"] = round(self._unified_fill_ratio, 4)
            # Co-location controller surface (engine/coloc.py):
            # quantum, ITL estimates vs the SLO, violation and
            # per-phase admission-refusal counters.
            m.update(self.coloc.snapshot())
            m["prefill_backlog_tokens"] = self._prefill_backlog_tokens
            # Compile-stall observability: a nonzero mid-traffic counter
            # is the r05 regression happening again — alert on it.
            cs = getattr(self.runner, "compile_stats", None)
            if cs is not None:
                m.update(cs.snapshot())
            m["engine_ready"] = int(self._state == "ready")
            # Robustness counters (docs/architecture/failure_model.md):
            # degraded completions are engine-local; fault injections and
            # retries are process-wide (all seams in this worker).
            m["degraded_requests_total"] = self._degraded_requests
            m["faults_injected_total"] = FAULTS.total_injected
            m["retries_total"] = RETRIES.total
            # Overload counters (docs/architecture/overload_and_drain.md):
            # shed/expired work is process-wide (every gate and queue in
            # this worker); draining is the router-eviction signal.
            m["shed_requests_total"] = OVERLOAD.shed_total
            # SLO-class split (llm/slo.py): per-class sheds are process-
            # wide; per-class waiting depth is the engine-thread cache
            # refreshed above — the cheapest-first contract's audit
            # trail and the planner's class-weighted pressure inputs.
            m["shed_interactive_total"] = OVERLOAD.shed_class_total(
                "interactive"
            )
            m["shed_batch_total"] = OVERLOAD.shed_class_total("batch")
            m["num_waiting_interactive"] = self._waiting_by_class.get(
                "interactive", 0
            )
            m["num_waiting_batch"] = self._waiting_by_class.get("batch", 0)
            m["deadline_exceeded_total"] = OVERLOAD.deadline_total
            m["draining"] = int(self._draining)
            # Failover plane (docs/architecture/failure_model.md
            # "Mid-stream failover"): process-wide like the retry/fault
            # counters, plus the engine-thread liveness heartbeat.
            m["failover_total"] = FAILOVER.total
            m["failover_success_total"] = FAILOVER.success_total
            m["workers_marked_dead_total"] = FAILOVER.marked_dead_total
            m["last_dispatch_age_s"] = round(
                time.monotonic() - self._last_dispatch_mono, 3
            )
            # Observability-plane counters (docs/architecture/
            # observability.md): leaked-then-reaped traces and total
            # recorded dispatches.
            m["abandoned_traces_total"] = tracer().abandoned_total
            m["flight_steps_total"] = self.flight.total_steps
            try:
                self._on_metrics(m)
            except Exception:  # dynalint: allow[DT003] metrics export must not kill the engine step loop
                logger.exception("metrics callback failed")

    # -- introspection ------------------------------------------------------
    @property
    def state(self) -> str:
        """Compile-lifecycle state: "init" (not started), "warming" (hot
        shape set not yet compiled), "ready" (serving shapes compiled, or
        degraded serving acknowledged)."""
        return self._state

    @property
    def is_ready(self) -> bool:
        return self._state == "ready"

    @property
    def served_unwarmed(self) -> bool:
        """True when traffic was admitted before any warmup completed —
        the documented degraded mode (warmup_gate="degraded")."""
        return self._served_unwarmed

    def _kvbm_gauges(self) -> dict:
        """Block-manager tier telemetry, kvbm_-prefixed for the metric
        surfaces (readiness, ForwardPassMetrics, /metrics, exporter) —
        KvBlockManager.stats() was previously computed and surfaced
        nowhere. Empty without an attached block manager."""
        if self.kvbm is None:
            return {}
        try:
            stats = self.kvbm.stats()
        # dynalint: allow[DT003] a telemetry probe must not fail readiness/metrics; gauges just go absent
        except Exception:
            logger.exception("kvbm stats failed")
            return {}
        g = {
            "kvbm_host_registered": stats.get("host_registered", 0),
            "kvbm_host_usage": stats.get("host_usage", 0.0),
            "kvbm_disk_registered": stats.get("disk_registered", 0),
            "kvbm_disk_usage": stats.get("disk_usage", 0.0),
            "kvbm_host_evictions_total": stats.get("host_evictions_total", 0),
            "kvbm_disk_evictions_total": stats.get("disk_evictions_total", 0),
            "kvbm_host_stored_blocks_total": stats.get(
                "host_stored_blocks_total", 0
            ),
            "kvbm_host_hit_blocks_total": stats.get(
                "host_hit_blocks_total", 0
            ),
            "kvbm_host_miss_blocks_total": stats.get(
                "host_miss_blocks_total", 0
            ),
            "kvbm_promoted_blocks_total": stats.get("promoted_blocks_total", 0),
            # Requested vs completed promotions tell a stuck promotion
            # pump apart from simple lack of demand.
            "kvbm_promotions_requested_total": stats.get(
                "promotions_requested_total", 0
            ),
            "kvbm_offloaded_blocks_total": stats.get(
                "offloaded_blocks_total", 0
            ),
            "kvbm_link_g1g2_bps": stats.get("link_g1g2_bps", 0.0),
            "kvbm_link_g2g3_bps": stats.get("link_g2g3_bps", 0.0),
            "kvbm_link_g3g2_bps": stats.get("link_g3g2_bps", 0.0),
            # Quantized-tier telemetry (docs/architecture/kv_quant.md):
            # quantized fraction of stored blocks per tier and the
            # cumulative bytes the int8 packing saved vs the compute
            # dtype, across G2 stores + G3 offloads.
            "kvbm_quant_host_density": stats.get("quant_host_density", 0.0),
            "kvbm_quant_disk_density": stats.get("quant_disk_density", 0.0),
            "kvbm_quant_bytes_saved_total": stats.get(
                "quant_bytes_saved_total", 0
            ),
            # Host→HBM onboard rate is measured engine-side (the EMA the
            # adaptive gate already keeps).
            "kvbm_link_g2g1_bps": (
                round(self._onboard_bps, 1) if self._onboard_bps else 0.0
            ),
            # G4 peer tier (block_manager/peer.py, docs/architecture/
            # kvbm_g4.md): fleet pulls won/moved/degraded and the
            # measured pull-throughput EMA the pricing law feeds on.
            "kvbm_g4_pulls_total": stats.get("g4_pulls_total", 0),
            "kvbm_g4_pull_bytes_total": stats.get("g4_pull_bytes_total", 0),
            "kvbm_g4_pull_fallbacks_total": stats.get(
                "g4_pull_fallbacks_total", 0
            ),
            "kvbm_link_peer_bps": stats.get("link_peer_bps", 0.0),
            # Integrity envelope (docs/architecture/integrity.md):
            # checksum failures per trust boundary (host = G2 onboard,
            # disk = G3 read/promotion/recovery, peer = G4 pull, frame =
            # disagg KV wire) plus the G3 scrubber's sweep counters. A
            # nonzero failure counter with zero stream deviations is the
            # system WORKING — corruption detected, quarantined, and
            # recomputed.
            "kvbm_integrity_failures_total": stats.get(
                "integrity_failures_total", 0
            ),
            "kvbm_integrity_failures_host": stats.get(
                "integrity_failures_host", 0
            ),
            "kvbm_integrity_failures_disk": stats.get(
                "integrity_failures_disk", 0
            ),
            "kvbm_integrity_failures_peer": stats.get(
                "integrity_failures_peer", 0
            ),
            "kvbm_integrity_failures_frame": stats.get(
                "integrity_failures_frame", 0
            ),
            "kvbm_scrub_scanned_total": stats.get("scrub_scanned_total", 0),
            "kvbm_scrub_detected_total": stats.get("scrub_detected_total", 0),
        }
        return g

    def readiness(self) -> dict:
        """Snapshot for /health + /metrics (llm/http_service.py): state,
        degraded flag, compile-stall counters,
        live load (the admission gate's watermark feed), the overload
        counters, and the KV-observatory actual-reuse + tier gauges. A
        draining engine reports state "draining" so readiness probes and
        routers evict it while in-flight work finishes."""
        d = {
            "state": "draining" if self._draining else self._state,
            "served_unwarmed": self._served_unwarmed,
            "degraded_requests_total": self._degraded_requests,
            "draining": self._draining,
            "shed_requests_total": OVERLOAD.shed_total,
            "shed_interactive_total": OVERLOAD.shed_class_total(
                "interactive"
            ),
            "shed_batch_total": OVERLOAD.shed_class_total("batch"),
            "deadline_exceeded_total": OVERLOAD.deadline_total,
            "abandoned_traces_total": tracer().abandoned_total,
            "flight_steps_total": self.flight.total_steps,
            # The hand-off to the loop (_flush_outbox): items / wake-ups
            # is the streamed frames a wake-up of the loop carries.
            "engine_handoff_wakeups_total": self._handoff_wakeups,
            "engine_handoff_items_total": self._handoff_items,
            "engine_handoff_wait_seconds_total": round(
                self._handoff_wait_s, 6
            ),
            # The ragged kernel's work by fold body (runner._count_folds).
            **{
                f'attn_folds_total{{tile="{tile}"}}': n
                for tile, n in zip(
                    ("short", "long"),
                    getattr(self.runner, "attn_folds_total", (0, 0)),
                )
            },
            # What left that kernel for the expanded body (long spans of a
            # latent layer held once: ops/pallas/latent_expanded.py).
            **dict(zip(
                ("attn_expanded_spans_total", "attn_expanded_rows_total"),
                getattr(self.runner, "attn_expanded_total", (0, 0)),
            )),
            # The paged cache as allocated: arrays a layer (1 where a
            # latent is held once, and where a (k, v) layer's pages are
            # joined: `EngineConfig.cache_form`), the bytes of ONE page
            # descriptor of the ragged kernel's ring on a chip and the
            # descriptors a fold starts (0 where the kernel does not serve:
            # a step's `attn_*_folds` times the second is the descriptors it
            # started), and what a live token costs over all layers.
            "kv_cache_arrays_per_layer": getattr(
                self.runner, "kv_arrays_per_layer",
                self.cfg.model.cache_arrays,
            ),
            "kv_page_dma_bytes": getattr(
                self.runner, "kv_page_dma_bytes", 0
            ),
            "kv_page_dmas_per_fold": getattr(
                self.runner, "page_dmas_per_fold", 0
            ),
            "kv_bytes_per_token": getattr(
                self.runner, "kv_bytes_per_token", 0
            ),
            # The share of a stored page that is lane padding (0-1): a half
            # where 64-wide heads are stored 128 wide for the kernel.
            "kv_cache_lane_pad_perc": getattr(
                self.runner, "kv_cache_lane_pad", 0.0
            ),
            "kv_reused_device_blocks_total": self._reused_device_blocks,
            "kv_reused_host_blocks_total": self._reused_host_blocks,
            "kv_reused_disk_blocks_total": self._reused_disk_blocks,
            "kv_reused_peer_blocks_total": self._reused_peer_blocks,
            # Blocks offered for prefix reuse (calls into
            # BlockAllocator.register: one a block that filled, not one a
            # token) and those that stored a new hash.
            "kv_blocks_offered_total": (
                self.allocator.offered_total if self.allocator else 0
            ),
            "kv_blocks_stored_total": (
                self.allocator.stored_total if self.allocator else 0
            ),
            # Surface parity (dynarace DT011): these were on the metrics
            # callback but missing from HTTP /metrics, which reads this
            # snapshot.
            "gpu_prefix_cache_hit_rate": self.prefix_hit_rate,
            "spec_tokens_per_step": self.spec_tokens_per_step,
            "spec_active": int(self._spec_active),
            "spec_drafted_tokens_total": self._spec_drafted,
            "spec_accepted_tokens_total": self._spec_accepted,
            "kvbm_kv_quant_ratio": round(
                getattr(self.runner, "kv_bytes_ratio", 1.0), 4
            ),
            "weight_quant_active": getattr(
                self.runner, "weight_quant_active", 0.0
            ),
            "weight_quant_bytes_saved": getattr(
                self.runner, "weight_quant_bytes_saved", 0.0
            ),
            "weight_quant_density": round(
                getattr(self.runner, "weight_quant_density", 0.0), 4
            ),
            # Which attention implementation the runner compiled in
            # ("pallas" | "xla"): a TPU run that fell back to the XLA
            # twin is visible here, not only in the log.
            "attention_path": getattr(self.runner, "attention_path", "none"),
            # Failover plane (docs/architecture/failure_model.md
            # "Mid-stream failover"): the last-dispatch heartbeat plus
            # the process-wide failover/mark-dead counters.
            "last_dispatch_age_s": round(
                time.monotonic() - self._last_dispatch_mono, 3
            ),
            "failover_total": FAILOVER.total,
            "failover_success_total": FAILOVER.success_total,
            "workers_marked_dead_total": FAILOVER.marked_dead_total,
        }
        d.update(self._kvbm_gauges())
        if self.scheduler is not None:
            # Approximate reads off the asyncio thread (len() is atomic):
            # the live-load half of the admission watermark.
            d["num_requests_waiting"] = len(self.scheduler.waiting)
            d["gpu_cache_usage_perc"] = self.scheduler.cache_usage()
            d.update(self.scheduler.group_gauges())
            # Engine-thread-refreshed per-class split of the waiting
            # depth (see _flush_side_channels).
            d["num_waiting_interactive"] = self._waiting_by_class.get(
                "interactive", 0
            )
            d["num_waiting_batch"] = self._waiting_by_class.get("batch", 0)
            # Engine-thread-refreshed gauge (see _flush_side_channels):
            # the phase-aware half — prefill pressure in TOKENS, so the
            # HTTP gate can shed prefill floods without a deep queue of
            # nearly-done decode-bound work tripping the same wire.
            d["prefill_backlog_tokens"] = self._prefill_backlog_tokens
        d["unified_step_tokens_decode_total"] = (
            self._unified_decode_tokens
        )
        d["unified_step_tokens_prefill_total"] = (
            self._unified_prefill_tokens
        )
        d["unified_operand_transfers_total"] = getattr(
            self.runner, "operand_transfers_total", 0
        )
        d.update(self._diffusion_counters())
        d["batch_fill_ratio"] = round(self._unified_fill_ratio, 4)
        d.update(self.coloc.snapshot())
        cs = getattr(self.runner, "compile_stats", None)
        if cs is not None:
            d.update(cs.snapshot())
        # What this start was made of; the warm-up's own split is the
        # three warmup_*_seconds_total just above.
        for name, secs in self.start_phases.seconds().items():
            d[f"start_{name}_seconds"] = round(secs, 3)
        return d

    def _diffusion_counters(self) -> dict:
        """Block-diffusion and grouped-expert totals (zero on models
        without them): lane passes dispatched, tokens they committed, and
        routed rows through the grouped expert path (rows fed x experts a
        token x expert layers, by arithmetic over the dispatches)."""
        from dynamo_tpu.models.moe import GROUPED_MIN_EXPERTS

        m = self.cfg.model
        grouped = m.is_moe and m.experts_here >= GROUPED_MIN_EXPERTS
        layers = sum(m.moe_layer(li) for li in range(m.num_layers))
        sched = self.scheduler
        return {
            # State that is not pages (a model with recurrent layers):
            # slots of the state table that a sequence owns, and the
            # table's bytes on the device. 0 for every other model.
            "recurrent_state_slots_in_use": (
                len(sched.running) if self._rec_on and sched is not None else 0
            ),
            "recurrent_state_bytes": getattr(
                self.runner, "recurrent_state_bytes", 0
            ),
            "recurrent_state_bytes_per_slot": getattr(
                self.runner, "recurrent_state_bytes_per_slot", 0
            ),
            # slots a sequence owns / slots (the trash slot left out)
            "recurrent_state_usage_perc": (
                len(sched.running) / max(self.cfg.max_num_seqs, 1)
                if self._rec_on and sched is not None else 0.0
            ),
            "kda_chunk_tiles_total": self._kda_chunk_tiles,
            "kda_chunk_rows_total": self._kda_chunk_rows,
            "ssd_chunk_tiles_total": self._ssd_chunk_tiles,
            "ssd_chunk_rows_total": self._ssd_chunk_rows,
            "ssd_decode_lanes_total": self._ssd_decode_lanes,
            "diffusion_passes_total": self._diffusion_passes,
            "diffusion_committed_tokens_total": self._diffusion_committed,
            "diffusion_commits_ridden_total": self._diffusion_ridden,
            "diffusion_commits_lone_total": self._diffusion_lone,
            "moe_grouped_rows_total": (
                (self._unified_decode_tokens + self._unified_prefill_tokens)
                * m.num_experts_per_tok * layers if grouped else 0
            ),
        }

    @property
    def degraded_requests(self) -> int:
        """Requests that completed through a degradation path (remote-KV
        transfer death ⇒ local recompute) rather than being dropped."""
        return self._degraded_requests

    @property
    def prefix_hit_rate(self) -> float:
        return self._prefix_hits / max(self._prefix_lookups, 1)

    @property
    def spec_tokens_per_step(self) -> float:
        """Mean delivered tokens per speculative decode step (≥1.0; the
        speedup multiplier over plain decode at equal step cost)."""
        return self._spec_tokens / max(self._spec_steps, 1)

    @property
    def _spec_active(self) -> bool:
        return bool(self.cfg.speculative_k and self._spec_enabled)

    @property
    def spec_active(self) -> bool:
        """Whether speculative decoding is currently driving decode chunks
        (False = auto-gated off below break-even; see
        cfg.speculative_break_even)."""
        return self._spec_active

    def prefix_overlap(self, token_ids: list[int]) -> float:
        """Fraction of this prompt already covered by the G1 prefix cache —
        the per-request hit rate the disagg decision needs (reference:
        disagg_router.rs uses the router's overlap, not a lifetime average).
        Read-only peek at the allocator from the caller's thread."""
        if not self.cfg.enable_prefix_caching or not token_ids:
            return 0.0
        from dynamo_tpu.llm.tokens import TokenBlockSequence

        bs = self.cfg.block_size
        hashes = TokenBlockSequence.from_tokens(
            token_ids, block_size=bs
        ).sequence_hashes()
        limit = (len(token_ids) - 1) // bs
        n = 0
        for h in hashes[:limit]:
            if not self.allocator.is_registered(h):
                break
            n += 1
        return n * bs / len(token_ids)


def _request_class(pre: PreprocessedRequest) -> str:
    """The request's SLO class from the annotations wire (llm/slo.py) —
    unlabeled/legacy requests are interactive, so the class system can
    only ever improve their treatment."""
    from dynamo_tpu.llm import slo

    return slo.normalize_class((pre.annotations or {}).get(slo.ANNOTATION_KEY))


def _payload_class(payload) -> str:
    """Class label straight off a raw payload (wire dict OR parsed
    request) — for refusal paths that run BEFORE the wire is parsed
    (the draining gate must not start parsing work it is refusing)."""
    ann = (
        payload.get("annotations")
        if isinstance(payload, dict)
        else getattr(payload, "annotations", None)
    )
    from dynamo_tpu.llm import slo

    return slo.normalize_class((ann or {}).get(slo.ANNOTATION_KEY))


def _decode_mm_segments(wire: list[dict]) -> list[tuple[int, Any]]:
    """Wire mm segments → (absolute prompt offset, [n, hidden] array)."""
    out: list[tuple[int, Any]] = []
    for seg in wire or []:
        arr = np.frombuffer(
            seg["data"], dtype=np.dtype(seg.get("dtype", "float32"))
        ).reshape(seg["shape"])
        out.append((int(seg["offset"]), arr))
    return out


def _mm_for_chunk(
    seq: Sequence, start: int, length: int
) -> list[tuple[int, Any]] | None:
    """Intersect a sequence's mm segments with prompt chunk
    [start, start+length); offsets become chunk-relative (what
    ModelRunner.prefill expects). None when the chunk has no overlap."""
    if not seq.mm_segments:
        return None
    out = []
    for off, arr in seq.mm_segments:
        lo = max(off, start)
        hi = min(off + len(arr), start + length)
        if lo < hi:
            out.append((lo - start, arr[lo - off : hi - off]))
    return out or None
