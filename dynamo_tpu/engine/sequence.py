"""In-engine sequence state."""

from __future__ import annotations

import enum
import time
from dataclasses import dataclass, field
from typing import Any, Callable

from dynamo_tpu.llm.protocols.common import (
    FinishReason,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.llm.tokens import TokenBlockSequence


class SeqStatus(enum.Enum):
    WAITING = "waiting"
    RUNNING = "running"
    FINISHED = "finished"
    # Disagg decode side: blocks allocated, KV inbound from a prefill worker.
    WAITING_REMOTE = "waiting_remote"
    # Admitted (slot + blocks held) but the prompt is still being prefilled
    # chunk by chunk; excluded from decode batches until the last chunk.
    PREFILLING = "prefilling"


@dataclass
class Sequence:
    request_id: str
    prompt_tokens: list[int]
    sampling: SamplingOptions
    stop: StopConditions
    # Called from the engine thread with (token_id | None, finish_reason |
    # None[, logprobs_entry]) — engine-side callbacks accept an optional
    # third argument carrying the token's logprob payload.
    emit: Callable[..., None]

    status: SeqStatus = SeqStatus.WAITING
    output_tokens: list[int] = field(default_factory=list)
    # One block table for each of the model's cache groups
    # (docs/architecture/cache_groups.md), position-indexed; most models
    # have one group, and ``block_ids`` is its table. Beside a
    # full-attention group (the first: a block for every position of the
    # context, drawn at admission) a windowed group's table grows a span
    # at a time (Scheduler.fund_span).
    tables: list[list[int]] = field(default_factory=lambda: [[]])
    num_cached_prefix: int = 0      # tokens covered by prefix-cache hit
    slot: int | None = None         # decode batch slot
    arrival_s: float = field(default_factory=time.monotonic)
    first_token_s: float | None = None
    # Chained block hashes over prompt+output (prefix-cache registration).
    hashes: TokenBlockSequence | None = None
    # How far ``hashes.blocks`` has been offered for prefix reuse (a block
    # index; Scheduler.register_filled_blocks). It is set wherever
    # ``hashes`` is: admission starts it behind the matched prefix.
    offered_blocks: int = 0
    # Disaggregation handoff metadata (set for remote prefill).
    kv_transfer: dict[str, Any] | None = None
    # Disagg decode side completeness ledger (WAITING_REMOTE only): the
    # (start_block, num_blocks) span whose KV must arrive, and the block
    # indices that actually landed. Activation over a hole degrades to
    # local recompute instead of decoding stale KV.
    remote_span: tuple[int, int] | None = None
    remote_landed: set[int] = field(default_factory=set)
    # Multimodal soft-prompt segments: (absolute prompt offset, [n, hidden]
    # float array) pairs replacing placeholder-token embeddings at prefill.
    # Non-empty ⇒ prefix caching is skipped (identical placeholder tokens
    # from different images must never alias in the block hash space).
    mm_segments: list[tuple[int, Any]] = field(default_factory=list)
    # Chunked prefill: prompt tokens whose KV is already computed (includes
    # any prefix-cache hit). Meaningful while status is PREFILLING.
    prefill_cursor: int = 0
    # OpenAI logprobs: None = not requested; N = return the chosen token's
    # logprob plus the top-N alternatives per generated token.
    logprobs: int | None = None
    # Absolute deadline (utils/deadline.py Deadline) or None. Checked at
    # every hop: waiting-list expiry sweep, remote-KV wait, and per
    # delivered token — expired work is cancelled with
    # FinishReason.DEADLINE, never executed to completion.
    deadline: Any = None
    # SLO class (llm/slo.py: "interactive" | "batch"), from the request
    # annotations wire. Steers shed/preempt victim selection: batch
    # sequences pay for overload before interactive ones at equal age.
    # Legacy/unlabeled requests default to interactive so the class
    # system can never worsen unlabeled traffic.
    slo_class: str = "interactive"
    # Penalties path: the lane's [vocab] output-token count buffer must be
    # zeroed before this sequence's first decode chunk (slots are reused).
    counts_reset_pending: bool = True
    # Pipelined decode: chunks issued to the device but not yet processed.
    # While > 0 the sequence's blocks are pinned (in-flight KV writes) and
    # its device-side length runs ahead of total_len.
    inflight_chunks: int = 0
    sched_len: int = 0           # device-side length (total_len + issued)
    defer_release: bool = False  # finished while chunks were in flight
    # Rolling-buffer eviction (a windowed cache group): logical pages
    # [0, evicted[g]) of group g's table were released back to its
    # allocator; their entries hold the 0 sentinel (trash block — never
    # allocated, never scanned: windowed attention's page skip starts
    # strictly above them). See Scheduler.evict_behind_window.
    evicted: list[int] = field(default_factory=lambda: [0])
    # KV observatory — ACTUAL reuse split by tier, set at admission
    # (docs/architecture/observability.md): G1 prefix-cache blocks this
    # request found already on device, host-tier blocks onboarded for it,
    # and the G3-origin share of those (blocks that reached the host tier
    # via disk promotion). Reported once per request (kv_actual_reported
    # guards re-admission after preemption / remote-KV degradation).
    reuse_device_blocks: int = 0
    reuse_host_blocks: int = 0
    reuse_disk_blocks: int = 0
    reuse_peer_blocks: int = 0
    kv_actual_reported: bool = False
    # G4 peer pull parking (engine _maybe_park_for_peer_pull): the
    # in-flight pull this admitted-but-parked sequence waits on, its
    # wall-clock give-up point (after which it proceeds by local
    # recompute — counted degraded), and the once-per-request guard.
    peer_pull_key: int | None = None
    peer_pull_deadline: float = 0.0
    peer_pull_tried: bool = False
    # While True the sequence is RUNNING but must not enter decode
    # composition: it has been admitted yet its prompt is still waiting
    # on the peer pull — without this flag decode_batch would treat the
    # un-prefilled prompt as fully cached context and emit from it.
    peer_parked: bool = False

    # Block diffusion (models with diffusion_block_length = B > 0): the
    # in-flight block. blk_start is the position of its first row (-1 =
    # none open yet: the prompt's whole blocks are still prefilling);
    # blk_ids its B ids, -1 where a row is still masked. Everything before
    # blk_start is committed — final tokens, final keys and values — and
    # output_tokens holds what was DELIVERED: the committed rows up to the
    # first masked one. A preempted sequence keeps blk_ids, so its block
    # resumes where it stood. Where the next block was opened at compose
    # behind a pass still in flight (a lone commit pass, or the pass a
    # ride follows: engine _issue_unified), blk_behind is the block behind
    # blk_start as that pass was fed; its retire reads and updates it.
    blk_start: int = -1
    blk_ids: list[int] = field(default_factory=list)
    blk_behind: list[int] = field(default_factory=list)
    blk_inflight: int = 0        # block passes issued and not yet retired

    @property
    def block_ids(self) -> list[int]:
        """The first cache group's block table: the only one of most
        models, the full-attention layers' where a model has several, none
        (empty) where the model has no pool."""
        return self.tables[0] if self.tables else []

    @block_ids.setter
    def block_ids(self, ids: list[int]) -> None:
        self.tables[0] = ids

    @property
    def lane_block_ids(self):
        """A lane's second place (``ModelRunner.unified_step``): the block
        table, or a tuple of one table a cache group where the model has
        several."""
        return self.tables[0] if len(self.tables) == 1 else tuple(self.tables)

    @property
    def total_len(self) -> int:
        return len(self.prompt_tokens) + len(self.output_tokens)

    @property
    def last_token(self) -> int:
        if self.output_tokens:
            return self.output_tokens[-1]
        return self.prompt_tokens[-1]

    @property
    def device_len(self) -> int:
        """Speculative device-side length: host length plus issued-but-
        unprocessed decode steps."""
        return max(self.sched_len, self.total_len)

    def context_cap(self, max_model_len: int) -> int:
        """Remaining KV writes the context limit allows (<= 0 means the
        sequence is speculatively at the limit: no further decode steps or
        block growth — it finishes when in-flight chunks are processed).
        The single eligibility predicate shared by Scheduler.decode_batch
        and TpuEngine._decode_steps; they must agree or the block table can
        overflow."""
        return max_model_len - self.device_len + 1

    @property
    def needs_extras(self) -> bool:
        """True when decode chunks containing this sequence must run the
        full-featured program (penalties and/or logprob outputs)."""
        s = self.sampling
        return bool(
            s.frequency_penalty
            or s.presence_penalty
            or self.logprobs is not None
        )

    def should_stop(self) -> FinishReason | None:
        if not self.output_tokens:
            return None
        n = len(self.output_tokens)
        if self.stop.min_tokens and n < self.stop.min_tokens:
            return None
        if not self.stop.ignore_eos and (
            self.output_tokens[-1] in self.stop.stop_token_ids
        ):
            return FinishReason.STOP
        if self.stop.max_tokens is not None and n >= self.stop.max_tokens:
            return FinishReason.LENGTH
        return None
