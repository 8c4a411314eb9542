"""Continuous-batching scheduler: watermark admission, block growth,
preemption, prefix-cache reuse.

Design template: the reference's engine simulator scheduler (reference:
lib/llm/src/mocker/scheduler.rs:16-60 — watermark-based admission, batched
token budget, LRU preemption), which the reference uses as its model of vLLM;
here it schedules the real JAX engine.

Invariant: before a decode step for a sequence with n tokens, KV slots for
positions [0, n-1] exist — the step feeds token t[n-1], writes its KV at
position n-1, and samples t[n]. Block hashes therefore chain over *fed*
tokens, so a block is registered exactly when its KV is fully written.
"""

from __future__ import annotations

import logging
import time
from collections import deque

from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.engine.kv_cache import BlockAllocator
from dynamo_tpu.engine.sequence import Sequence, SeqStatus
from dynamo_tpu.llm.protocols.common import FinishReason
from dynamo_tpu.llm.tokens import TokenBlockSequence
from dynamo_tpu.utils.deadline import OVERLOAD
from dynamo_tpu.utils.tracing import tracer

logger = logging.getLogger(__name__)


def compose_unified(
    decode_seqs: list,
    prefill_items: list[tuple],
    budget: int,
    quantum: int,
    rotation: int = 0,
) -> tuple[list, list[tuple]]:
    """Token-budget batch composition for the unified step (ROADMAP #2 /
    the Nexus mixed-batch schedule). Pure function over already-eligible
    work so the policy is unit-testable without an engine:

    - ``decode_seqs``: sequences wanting one decode SPAN each (already
      funded for block growth) — either bare sequences (width-1 spans)
      or ``(seq, width)`` pairs, where width = 1 + draft tokens for a
      speculative draft-verify span. The return mirrors the input form.
    - ``prefill_items``: (seq, remaining_prompt_tokens) in arrival order;
    - returns (decode_take, [(seq, take_n), ...]).

    Policy:
    1. **Decode fills first** — prefill can never stall decode ITL by
       head-of-line blocking a step (the phase-alternating failure mode).
    2. **Starvation bound** — when prefill work exists, one quantum of
       budget is RESERVED for it, so a full decode population can never
       starve prompts out of TTFT progress; together with rule 1 neither
       phase can starve the other. Spec spans live under the SAME
       bounds: their draft rows spend decode's budget share, never the
       prefill reserve.
    3. **Quantum cap under co-location** — while decode lanes share the
       batch each prompt takes at most ``quantum`` tokens (bounds the
       step's service time, hence decode ITL); a prefill-only batch may
       spend the whole remaining budget on one prompt (pure TTFT).
    4. **Deferral fairness** — when the decode population exceeds its
       budget slice, the take starts at ``rotation mod population`` and
       wraps, so deferral is round-robin across steps instead of always
       parking the same tail lanes (the caller advances ``rotation`` by
       the lanes taken each step; a fixed head-first slice would make
       tail-lane ITL unboundedly worse than the population median).
    """
    widths = [
        (item[1] if isinstance(item, tuple) else 1) for item in decode_seqs
    ]
    total_prefill = sum(r for _, r in prefill_items if r > 0)
    reserve = min(quantum, total_prefill, budget) if total_prefill else 0
    if decode_seqs:
        # Two-sided bound: the prefill reserve never squeezes decode
        # below half the budget (quantum == budget would otherwise zero
        # decode_take and stall every running sequence's ITL for as long
        # as prompts keep arriving).
        reserve = min(
            reserve, budget - min(sum(widths), budget // 2)
        )
    space = max(budget - reserve, 0)
    n_lanes = len(decode_seqs)
    if space <= 0 or not decode_seqs:
        decode_take = []
        used = 0
    elif space < sum(widths):
        # Rotated fill: lanes whose span fits the remaining space are
        # taken in rotation order; a wide (draft-verify) span that
        # doesn't fit is deferred — rotation brings it to the front of
        # a fuller step soon (width-1 populations degenerate to the
        # legacy head-slice behavior exactly).
        off = rotation % n_lanes
        order = list(range(off, n_lanes)) + list(range(off))
        decode_take = []
        used = 0
        for i in order:
            if used + widths[i] <= space:
                decode_take.append(decode_seqs[i])
                used += widths[i]
    else:
        decode_take = list(decode_seqs)
        used = sum(widths)
    rem = budget - used
    per_seq_cap = quantum if decode_take else budget
    prefill_take: list[tuple] = []
    for seq, r in prefill_items:
        n = min(r, per_seq_cap, rem)
        if n <= 0:
            continue
        prefill_take.append((seq, n))
        rem -= n
        if rem <= 0:
            break
    return decode_take, prefill_take


class Scheduler:
    def __init__(self, cfg: EngineConfig, *allocators: BlockAllocator) -> None:
        """One allocator for each of the model's cache groups
        (``cfg.model.cache_groups``, docs/architecture/cache_groups.md):
        one for most models, and ``allocator`` is it; none for a model no
        layer of which pages (``allocator`` is None): a batch slot, which
        is its state slot, is then the only thing admission hands out,
        and a sequence's memory does not grow with its context."""
        self.cfg = cfg
        self.allocators = list(allocators)
        self.allocator = allocators[0] if allocators else None
        #: each group's window in tokens (0 = the whole context)
        self.windows = tuple(cfg.model.cache_groups)
        assert len(self.windows) == len(self.allocators), (
            "one pool for each of the model's cache groups"
        )
        #: the windowed groups as (group, window, its pool's release): what
        #: evict_behind_window walks after every retire of every sequence
        self._windowed = [
            (g, w, a.release)
            for g, (w, a) in enumerate(zip(self.windows, self.allocators)) if w
        ]
        self._bs = cfg.block_size
        #: blocks released behind a window, and preemptions by the pool
        #: that ran out (readiness() and /metrics read them)
        self.window_released = 0
        self.preemptions_by_group = [0] * len(self.allocators)
        #: layers that keep keys and values in each group: a block of a
        #: group is that many layers' pages
        self.group_layers = [
            cfg.model.group_layers(g) for g in range(len(self.windows))
        ]
        self.waiting: deque[Sequence] = deque()
        self.running: dict[int, Sequence] = {}  # slot -> seq
        self._free_slots: list[int] = list(range(cfg.max_num_seqs - 1, -1, -1))

    # -- queue management ---------------------------------------------------
    def add(self, seq: Sequence) -> None:
        if len(seq.prompt_tokens) >= self.cfg.max_model_len:
            seq.status = SeqStatus.FINISHED
            seq.emit(None, FinishReason.ERROR)
            return
        if seq.deadline is not None and seq.deadline.expired:
            # Already expired on arrival (e.g. a long ingress queue) —
            # executing it would only waste prefill compute nobody reads.
            OVERLOAD.note_deadline("engine.arrival")
            seq.status = SeqStatus.FINISHED
            seq.emit(None, FinishReason.DEADLINE)
            return
        self.waiting.append(seq)
        if self.cfg.max_waiting and len(self.waiting) > self.cfg.max_waiting:
            # Depth bound: shed cheapest-first, then OLDEST-first
            # (llm/slo.py) — any waiting BATCH request is a cheaper
            # victim than every interactive one (batch sheds before
            # interactive at equal age), and within the chosen class the
            # head of the queue has burned the most of its deadline and
            # is the likeliest to be abandoned by its client. Typed
            # finish, never a silent drop.
            victim = self._shed_victim()
            self.waiting.remove(victim)
            OVERLOAD.note_shed(
                "engine.waiting", request_class=victim.slo_class
            )
            logger.warning(
                "waiting list over bound (%d): shedding oldest %s %s",
                self.cfg.max_waiting, victim.slo_class, victim.request_id,
            )
            victim.status = SeqStatus.FINISHED
            victim.emit(None, FinishReason.SHED)

    def _shed_victim(self) -> Sequence:
        """Cheapest-first victim over the waiting list: the oldest
        batch-class entry when any batch work waits, else the oldest
        overall (the pre-SLO-class behavior). One O(n) pass per
        over-bound arrival (n <= max_waiting; a min-scan, not a sort —
        deque order isn't arrival order because requeue_for_recompute
        appendlefts recomputed work)."""
        victim: Sequence | None = None
        for s in self.waiting:
            if s.slo_class == "batch" and (
                victim is None or s.arrival_s < victim.arrival_s
            ):
                victim = s
        return victim if victim is not None else self.waiting[0]

    def expire_waiting(self) -> int:
        """Sweep the waiting list for expired work: deadline-expired
        sequences finish with DEADLINE; sequences older than the age bound
        finish with SHED. Called once per engine step while anything
        waits — a queued prefill past its deadline is shed, not executed.
        Returns the number removed."""
        if not self.waiting:
            return 0
        age_bound = self.cfg.max_queue_delay_s
        now = time.monotonic() if age_bound else 0.0
        removed = 0
        kept: deque[Sequence] = deque()
        for seq in self.waiting:
            if seq.deadline is not None and seq.deadline.expired:
                OVERLOAD.note_deadline("engine.queued")
                seq.status = SeqStatus.FINISHED
                seq.emit(None, FinishReason.DEADLINE)
                removed += 1
            elif age_bound and now - seq.arrival_s > age_bound:
                OVERLOAD.note_shed(
                    "engine.waiting_age", request_class=seq.slo_class
                )
                seq.status = SeqStatus.FINISHED
                seq.emit(None, FinishReason.SHED)
                removed += 1
            else:
                kept.append(seq)
        if removed:
            self.waiting = kept
        return removed

    def abort(
        self, seq: Sequence, reason: FinishReason = FinishReason.CANCELLED
    ) -> None:
        if seq.status is SeqStatus.FINISHED:
            return
        if (
            seq.status
            in (SeqStatus.RUNNING, SeqStatus.WAITING_REMOTE, SeqStatus.PREFILLING)
            and seq.slot is not None
        ):
            if seq.inflight_chunks > 0:
                seq.defer_release = True
            else:
                self._release(seq)
        elif seq in self.waiting:
            self.waiting.remove(seq)
        seq.status = SeqStatus.FINISHED
        seq.emit(None, reason)

    @property
    def has_work(self) -> bool:
        return bool(self.waiting or self.running)

    # -- admission (prefill) ------------------------------------------------
    def next_prefill(self) -> Sequence | None:
        """Pop, fund, and slot the next admissible waiting sequence. Sets up
        its block table and prefix-cache hit; returns None if none fit."""
        if not self.waiting or not self._free_slots:
            return None
        seq = self.waiting[0]
        if not self.admit(seq):
            return None
        self.waiting.remove(seq)
        return seq

    def admit(self, seq: Sequence) -> bool:
        """Fund and slot one sequence (block table, prefix-cache hit, batch
        slot). Standalone entry for the disagg decode side, which admits a
        sequence whose KV arrives from a remote prefill worker."""
        if not self._free_slots:
            return False
        bs = self.cfg.block_size
        P = len(seq.prompt_tokens)
        if self.allocator is None:
            # No pool: a free slot is all a sequence needs.
            seq.tables, seq.evicted = [], []
            seq.num_cached_prefix = 0
            seq.hashes, seq.offered_blocks = None, 0
            self._seat(seq)
            return True

        seq.hashes = TokenBlockSequence(block_size=bs)
        # Prefix match on full prompt blocks, capped so ≥1 token is computed.
        # Multimodal sequences opt out entirely: their placeholder tokens
        # hash identically across DIFFERENT images, so sharing blocks by
        # token hash would serve one image's KV for another's prompt.
        matched: list[int] = []
        if self.cfg.enable_prefix_caching and not seq.mm_segments:
            probe = TokenBlockSequence.from_tokens(seq.prompt_tokens, block_size=bs)
            limit = (P - 1) // bs
            matched = self.allocator.match_prefix(probe.sequence_hashes()[:limit])
        cached_tokens = len(matched) * bs

        total_blocks = (P + bs - 1) // bs
        need = total_blocks - len(matched)
        watermark_blocks = int(self.allocator.num_blocks * self.cfg.watermark)
        if self.allocator.num_free - need < watermark_blocks:
            for b in matched:
                self.allocator.release(b)
            return False
        # A further (windowed) group funds a span at a time (fund_span);
        # admission asks it for room for the window, above its watermark.
        for alloc, w in zip(self.allocators[1:], self.windows[1:]):
            room = min(total_blocks, -(-w // bs) + 1)
            if alloc.num_free - room < int(alloc.num_blocks * self.cfg.watermark):
                for b in matched:
                    self.allocator.release(b)
                return False

        try:
            new_blocks = self.allocator.allocate_many(
                need, first_logical=len(matched)
            )
        except MemoryError:
            for b in matched:
                self.allocator.release(b)
            return False

        seq.tables = [matched + new_blocks, *([] for _ in self.allocators[1:])]
        seq.evicted = [0] * len(seq.tables)
        seq.num_cached_prefix = cached_tokens
        seq.hashes.extend(seq.prompt_tokens)
        seq.offered_blocks = len(matched)  # registered already
        self._seat(seq)
        return True

    def _seat(self, seq: Sequence) -> None:
        """Hand an admitted sequence its batch slot (its state slot too)."""
        seq.sched_len = seq.total_len
        seq.slot = self._free_slots.pop()
        seq.status = SeqStatus.RUNNING
        self.running[seq.slot] = seq

    def register_filled_blocks(self, seq: Sequence, covered_tokens: int) -> None:
        """Offer for prefix reuse every block whose KV is now fully
        written (the first `covered_tokens` positions) and that was not
        offered before: a block is offered ONCE, when it fills, from the
        mark `seq.offered_blocks`. The common step fills no block and
        returns here at a compare."""
        full = covered_tokens // self._bs
        if (
            full <= seq.offered_blocks
            or not self.cfg.enable_prefix_caching
            or seq.hashes is None
            or seq.mm_segments
        ):
            return
        hashes = seq.hashes.blocks
        block_ids = seq.block_ids
        for idx in range(seq.offered_blocks, full):
            block = block_ids[idx]
            if block == 0:
                continue  # rolling-buffer evicted page (sentinel)
            h = hashes[idx]
            self.allocator.register(
                block,
                h.sequence_hash,
                parent_hash=h.parent_sequence_hash,
                token_ids=h.tokens,
            )
        seq.offered_blocks = full

    def evict_behind_window(self, seq: Sequence, covered: int) -> int:
        """Rolling-buffer eviction, per layer group: in every windowed
        cache group release the blocks whose every position is behind the
        sliding window of EVERY query this sequence can still issue (the
        earliest future query position is ≥ `covered` − 1, so keys <
        covered − window are dead). A full-attention group keeps the whole
        history in ITS pool and releases nothing. Entries become the 0
        sentinel — windowed attention's page skip starts strictly above
        them, so tables stay valid without compaction. Registered blocks
        land in the allocator's REUSABLE pool (their KV stays valid and
        hash-discoverable for prefix hits; the router's radix view stays
        truthful — a 'removed' event fires only if LRU pressure actually
        reclaims them). Returns the number of blocks released."""
        n = 0
        for g, w, release in self._windowed:
            table, done = seq.tables[g], seq.evicted[g]
            upto = min(max(covered - w, 0) // self._bs, len(table))
            if upto <= done:
                continue
            for i in range(done, upto):
                b = table[i]
                if b:
                    release(b)
                    table[i] = 0
                    n += 1
            seq.evicted[g] = upto
        self.window_released += n
        return n

    def fund_span(self, seq: Sequence, upto: int) -> bool:
        """Blocks in every cache group for a span that writes positions
        below ``upto`` (a prefill quantum). The first group's were all
        drawn at admission; a windowed group beside it draws a span's here.
        Preempts the cheapest runnable sequence on
        pressure, as decode growth does; False where nothing can be
        preempted now: the span waits for a retire to release blocks."""
        need = -(-upto // self.cfg.block_size)
        for g, table in enumerate(seq.tables):
            while len(table) < need:
                try:
                    table.append(self.allocators[g].allocate(len(table)))
                except MemoryError:
                    victim = self._pick_victim(exclude=seq)
                    if victim is None:
                        return False
                    self._preempt(victim, pool=g)
        return True

    # -- decode -------------------------------------------------------------
    def decode_batch(self, lookahead: int = 1) -> list[Sequence]:
        """Sequences taking part in the next decode step, after ensuring each
        has blocks for `lookahead` incoming KV writes counted from its
        device-side length (may preempt on pressure). lookahead > 1 funds a
        fused multi-step decode chunk."""
        bs = self.cfg.block_size
        # Iterate in arrival order so preemption victims are the newest.
        batch: list[Sequence] = []
        for seq in sorted(self.running.values(), key=lambda s: s.arrival_s):
            if seq.status is not SeqStatus.RUNNING:
                continue
            if seq.peer_parked:
                # Admitted but parked on a G4 peer pull: its prompt has
                # not been prefilled, so a decode lane built from it
                # would fabricate context (engine _maybe_park_for_peer_pull).
                continue
            if seq.context_cap(self.cfg.max_model_len) <= 0:
                # No block growth for capped sequences — they are simply
                # excluded from composition (engine _issue_unified) until
                # their in-flight dispatches retire, same as
                # WAITING_REMOTE slots.
                continue
            B = self.cfg.model.diffusion_block_length
            if B and seq.blk_start < 0:
                continue  # no block open (engine _open_block)
            # Clamp to the block-table width: speculative lookahead can
            # overshoot the context cap; the engine caps draft_len so no
            # verify-span write lands past the allocated span. A
            # block-diffusion lane writes its whole block every pass, and
            # behind a pass in flight that leaves the block complete the
            # engine opens the NEXT block at issue (behind a lone commit
            # pass; in the same span where the commit rides): fund one
            # block ahead.
            needed_block = min(
                (seq.blk_start + 2 * B - 1) // bs if B
                else (seq.device_len - 2 + lookahead) // bs,
                self.cfg.max_blocks_per_seq - 1,
            )
            for g, table in enumerate(seq.tables):
                if needed_block < len(table):
                    continue  # the common step: no group grows
                while (
                    needed_block >= len(table)
                    and seq.status is SeqStatus.RUNNING
                ):
                    try:
                        table.append(self.allocators[g].allocate(len(table)))
                    except MemoryError:
                        victim = self._pick_victim(exclude=seq)
                        if victim is not None:
                            self._preempt(victim, pool=g)
                        elif seq.inflight_chunks == 0:
                            self._preempt(seq, pool=g)
                        else:
                            # Can't preempt anything in flight — stall until
                            # the pipeline drains and zombie blocks free up.
                            return []
            if seq.status is SeqStatus.RUNNING:
                batch.append(seq)
        # A later iteration may have preempted an earlier batch member.
        return [s for s in batch if s.status is SeqStatus.RUNNING]

    def _pick_victim(self, exclude: Sequence) -> Sequence | None:
        candidates = [
            s
            for s in self.running.values()
            if s is not exclude
            and s.status is SeqStatus.RUNNING
            and s.inflight_chunks == 0  # in-flight KV writes pin blocks
        ]
        if not candidates:
            return None
        # Cheapest-first preemption (llm/slo.py): among runnable
        # candidates any BATCH sequence is preferred over every
        # interactive one; within the chosen class the newest arrival
        # pays (it has made the least progress — the pre-class rule).
        return max(
            candidates,
            key=lambda s: (s.slo_class == "batch", s.arrival_s),
        )

    def _preempt(self, seq: Sequence, pool: int = 0) -> None:
        """``pool``: the cache group whose pool ran out."""
        logger.info("preempting %s (blocks exhausted)", seq.request_id)
        self.preemptions_by_group[pool] += 1
        self.requeue_for_recompute(seq)

    def requeue_for_recompute(self, seq: Sequence) -> None:
        """Release everything and requeue for full recompute (the fed tokens
        become the new prompt, so generation resumes seamlessly). Shared by
        preemption and the disagg degradation path: a WAITING_REMOTE
        sequence whose KV transfer died falls back to LOCAL prefill through
        here — the request is recomputed, never lost. A model with
        recurrent layers loses the sequence's state with its slot: the
        replay from position 0 starts the new slot's state from zeros."""
        if self.cfg.model.has_recurrent:
            tracer().mark_if_active(
                seq.request_id, "recurrent_state_discarded"
            )
        self._release(seq)
        seq.prompt_tokens = seq.prompt_tokens + seq.output_tokens
        seq.output_tokens = []
        seq.hashes, seq.offered_blocks = None, 0
        seq.num_cached_prefix = 0
        seq.sched_len = 0
        # A block-diffusion sequence keeps a block that still has a masked
        # row: re-admission opens it at the same position with the rows
        # it had committed. A block without one is all delivered, so it
        # is prompt now.
        seq.blk_start = -1
        seq.blk_inflight = 0
        seq.blk_behind = []
        if all(t >= 0 for t in seq.blk_ids):
            seq.blk_ids = []
        # Re-admission may land in a different slot whose [vocab] penalty
        # count row holds another sequence's history — re-arm the reset.
        seq.counts_reset_pending = True
        seq.status = SeqStatus.WAITING
        self.waiting.appendleft(seq)

    def finish(self, seq: Sequence, reason: FinishReason) -> None:
        seq.status = SeqStatus.FINISHED
        seq.sched_len = seq.total_len
        seq.emit(None, reason)
        if seq.inflight_chunks > 0:
            # In-flight chunks still write into these blocks — release when
            # the pipeline drains (engine._process_chunk).
            seq.defer_release = True
        else:
            self._release(seq)

    def _release(self, seq: Sequence) -> None:
        for alloc, table in zip(self.allocators, seq.tables):
            for b in table:
                if b:  # 0 = rolling-buffer evicted page, already released
                    alloc.release(b)
        seq.tables, seq.evicted = [[]], [0]
        if seq.slot is not None:
            del self.running[seq.slot]
            self._free_slots.append(seq.slot)
            seq.slot = None

    def blocks_in_use(self, group: int) -> int:
        if group >= len(self.allocators):
            return 0  # no pool
        alloc = self.allocators[group]
        return alloc.num_blocks - 1 - alloc.num_free

    def cache_usage(self) -> float:
        """The cache in use over the cache there is, one number: by bytes
        over every group's pool (a group's block is its layers' pages);
        the one pool's share of blocks where the model has one group;
        the slots in use over the slots where it has no pool at all (the
        state table is then the cache there is)."""
        if not self.allocators:
            return len(self.running) / max(self.cfg.max_num_seqs, 1)
        if len(self.allocators) == 1:
            return self.allocator.usage()
        used = sum(
            n * self.blocks_in_use(g) for g, n in enumerate(self.group_layers)
        )
        have = sum(
            n * (a.num_blocks - 1)
            for n, a in zip(self.group_layers, self.allocators)
        )
        return used / max(have, 1)

    def group_gauges(self) -> dict:
        """Each pool's share in use under the kind of its layers
        (``kv_full_usage_perc`` / ``kv_window_usage_perc``), the blocks
        released behind a window and the preemptions by the pool that ran
        out (``readiness()`` and ``/metrics``)."""
        out = {
            "kv_window_released_blocks_total": self.window_released,
            "kv_preemptions_full_pool_total": 0,
            "kv_preemptions_window_pool_total": 0,
        }
        for g, w in enumerate(self.windows):
            kind = "window" if w else "full"
            out[f"kv_{kind}_usage_perc"] = self.allocators[g].usage()
            out[f"kv_preemptions_{kind}_pool_total"] += (
                self.preemptions_by_group[g])
        return out

    def waiting_prompt_tokens(self) -> int:
        """Prompt tokens queued behind admission — the waiting half of
        the phase-aware ``prefill_backlog_tokens`` signal (engine
        thread only: iterates the deque the engine mutates)."""
        return sum(len(s.prompt_tokens) for s in self.waiting)

    def waiting_by_class(self) -> dict[str, int]:
        """Waiting-list depth split by SLO class (engine thread only:
        iterates the deque) — the planner's class-weighted pressure
        input and the per-class admission gauges' feed."""
        out = {"interactive": 0, "batch": 0}
        for s in self.waiting:
            out[s.slo_class if s.slo_class in out else "interactive"] += 1
        return out

    # -- metrics ------------------------------------------------------------
    def metrics(self) -> dict:
        """ForwardPassMetrics snapshot (reference:
        lib/llm/src/kv_router/protocols.rs:43)."""
        return {
            "request_active_slots": len(self.running),
            "request_total_slots": self.cfg.max_num_seqs,
            "kv_active_blocks": self.blocks_in_use(0),
            "kv_total_blocks": (
                self.allocator.num_blocks - 1 if self.allocator else 0
            ),
            "num_requests_waiting": len(self.waiting),
            "gpu_cache_usage_perc": self.cache_usage(),
            "gpu_prefix_cache_hit_rate": 0.0,  # updated by the engine
        }
