"""Host-side KV block accounting: allocation, ref counting, prefix caching.

The G1 (HBM) tier's bookkeeping. Blocks move through the reference's
lifecycle states (reference: docs/architecture/kvbm_components.md:67-94 and
lib/llm/src/block_manager/pool.rs — Reset → Partial → Complete → Registered):
a block is *allocated* to a sequence, *registered* under its sequence hash
once full, and on release either joins the reusable pool (still holding
valid KV, discoverable by hash) or the free list. Allocation prefers truly
free blocks and evicts LRU reusable blocks only on pressure, emitting
KV-cache events (stored/removed) that feed the radix router
(reference: lib/llm/src/kv_router/protocols.rs:88-135 KvCacheEvent).

Block 0 is the trash block for padded writes — never allocated.

Lifecycle typestate: the reference encodes block states in Rust's type
system (MutableBlock/ImmutableBlock, RAII registration handles); Python
can't make invalid states unrepresentable, so `BlockState` + transition
checks make them LOUD instead — every mutation validates the block's
derived state and raises `BlockStateError` on a violation (double-free,
retain-after-free, registering an unallocated block) rather than
corrupting the pool (SURVEY §5 "race/sanitizer discipline").
"""

from __future__ import annotations

import enum
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Callable, Sequence


class BlockState(enum.Enum):
    FREE = "free"              # on the free list, no KV content
    ACTIVE = "active"          # refcounted by ≥1 sequence, not yet hashed
    REGISTERED = "registered"  # refcounted AND published under its hash
    REUSABLE = "reusable"      # refcount 0 but hash-discoverable (LRU pool)


class BlockStateError(RuntimeError):
    """An illegal block lifecycle transition (use-after-free, double free,
    registering an unallocated block, ...)."""


@dataclass
class KvEvent:
    """stored/removed event for the routing plane."""

    kind: str                      # "stored" | "removed"
    block_hashes: list[int] = field(default_factory=list)
    parent_hash: int | None = None
    token_ids: list[list[int]] | None = None


class BlockAllocator:
    def __init__(
        self,
        num_blocks: int,
        block_size: int,
        enable_prefix_caching: bool = True,
        on_event: Callable[[KvEvent], None] | None = None,
        num_shards: int = 1,
    ) -> None:
        """``num_shards > 1``: striped allocation for the kv_sp
        slot-sharded cache. Physical blocks partition into `num_shards`
        contiguous ranges (one per sp shard — matching the GSPMD slot
        sharding), and logical block i of a sequence MUST be served from
        shard i % num_shards. That placement guarantee is what lets each
        sp shard's attention scan ONLY its own stripe of the block table
        (ops/attention.py striped scan) instead of a masked full scan —
        the allocator is the contract's other half."""
        if num_blocks % max(num_shards, 1):
            raise ValueError(
                f"num_blocks={num_blocks} must divide by num_shards={num_shards}"
            )
        self.num_blocks = num_blocks
        self.block_size = block_size
        self.enable_prefix_caching = enable_prefix_caching
        self.on_event = on_event
        self.num_shards = max(num_shards, 1)
        self._bps = num_blocks // self.num_shards  # blocks per shard
        # Per-shard free stacks; block 0 (trash) excluded from shard 0.
        self._free: list[list[int]] = [
            list(range((s + 1) * self._bps - 1, max(s * self._bps, 1) - 1, -1))
            for s in range(self.num_shards)
        ]
        self._refs: dict[int, int] = {}
        self._hash_to_block: dict[int, int] = {}
        self._block_to_hash: dict[int, int] = {}
        # Registered blocks with refcount 0, LRU order (oldest first),
        # per shard so eviction-on-pressure stays within the right range.
        self._reusable: list[OrderedDict[int, None]] = [
            OrderedDict() for _ in range(self.num_shards)
        ]
        # Calls into `register`, and those that stored a new hash
        # (`kv_blocks_offered_total` / `kv_blocks_stored_total`).
        self.offered_total = 0
        self.stored_total = 0

    def shard_of(self, block: int) -> int:
        return block // self._bps

    # -- typestate ----------------------------------------------------------
    def state(self, block: int) -> BlockState:
        """Derived lifecycle state (see module docstring)."""
        if block in self._refs:
            return (
                BlockState.REGISTERED
                if block in self._block_to_hash
                else BlockState.ACTIVE
            )
        if block in self._reusable[self.shard_of(block)]:
            return BlockState.REUSABLE
        return BlockState.FREE

    def _expect(self, block: int, *states: BlockState, op: str) -> BlockState:
        got = self.state(block)
        if got not in states:
            raise BlockStateError(
                f"{op}(block={block}): state is {got.value}, expected "
                f"{'/'.join(s.value for s in states)}"
            )
        return got

    # -- capacity -----------------------------------------------------------
    @property
    def num_free_listed(self) -> int:
        """Blocks on the free lists (no KV content)."""
        return sum(len(f) for f in self._free)

    @property
    def num_reusable(self) -> int:
        """Registered blocks with refcount 0 (evictable on pressure)."""
        return sum(len(r) for r in self._reusable)

    @property
    def num_free(self) -> int:
        return self.num_free_listed + self.num_reusable

    @property
    def num_registered(self) -> int:
        return len(self._hash_to_block)

    def is_registered(self, sequence_hash: int) -> bool:
        return sequence_hash in self._hash_to_block

    def usage(self) -> float:
        used = self.num_blocks - 1 - self.num_free
        return used / max(self.num_blocks - 1, 1)

    # -- allocation ---------------------------------------------------------
    def allocate(self, logical: int | None = None) -> int:
        """Allocate one block (refcount 1); evicts LRU reusable on
        pressure. Under striping (num_shards > 1) ``logical`` — the
        block's index within its sequence — is REQUIRED and pins the
        allocation to shard ``logical % num_shards``."""
        if self.num_shards > 1:
            if logical is None:
                raise TypeError(
                    "striped allocator needs the block's logical index"
                )
            shard = logical % self.num_shards
        else:
            shard = 0
        free, reusable = self._free[shard], self._reusable[shard]
        if free:
            block = free.pop()
        elif reusable:
            block, _ = reusable.popitem(last=False)
            self._forget(block)
        else:
            raise MemoryError(
                "out of KV blocks"
                + (f" on sp shard {shard}" if self.num_shards > 1 else "")
            )
        self._refs[block] = 1
        return block

    def allocate_many(self, n: int, first_logical: int = 0) -> list[int]:
        if self.num_free < n:
            raise MemoryError(f"need {n} blocks, have {self.num_free}")
        out: list[int] = []
        try:
            for i in range(n):
                out.append(self.allocate(first_logical + i))
        except MemoryError:
            for b in out:
                self.release(b)
            raise
        return out

    def retain(self, block: int) -> None:
        self._expect(
            block, BlockState.ACTIVE, BlockState.REGISTERED, op="retain"
        )
        self._refs[block] += 1

    def release(self, block: int) -> None:
        self._expect(
            block, BlockState.ACTIVE, BlockState.REGISTERED, op="release"
        )
        self._refs[block] -= 1
        if self._refs[block] > 0:
            return
        del self._refs[block]
        shard = self.shard_of(block)
        if block in self._block_to_hash and self.enable_prefix_caching:
            self._reusable[shard][block] = None
            self._reusable[shard].move_to_end(block)
        else:
            self._forget(block)
            self._free[shard].append(block)

    # -- prefix caching -----------------------------------------------------
    def register(
        self,
        block: int,
        sequence_hash: int,
        parent_hash: int | None = None,
        token_ids: Sequence[int] | None = None,
    ) -> None:
        """Publish a full block under its chained sequence hash.
        ``token_ids`` is copied only where the block is stored."""
        self._expect(
            block, BlockState.ACTIVE, BlockState.REGISTERED, op="register"
        )
        if not self.enable_prefix_caching:
            return
        self.offered_total += 1
        if sequence_hash in self._hash_to_block:
            # Either duplicate content (keep the first registration) or a
            # re-register of this very block (the host-tier onboard and
            # the retire may both offer it) — in both cases the 'stored'
            # event already went out; re-emitting would spam the routing
            # plane.
            return
        self.stored_total += 1
        self._hash_to_block[sequence_hash] = block
        self._block_to_hash[block] = sequence_hash
        if self.on_event:
            self.on_event(
                KvEvent(
                    kind="stored",
                    block_hashes=[sequence_hash],
                    parent_hash=parent_hash,
                    token_ids=[list(token_ids)] if token_ids else None,
                )
            )

    def match_prefix(self, sequence_hashes: list[int]) -> list[int]:
        """Longest run of cached blocks for a chained hash list; each matched
        block's refcount is bumped (caller owns a reference)."""
        matched: list[int] = []
        for h in sequence_hashes:
            block = self._hash_to_block.get(h)
            if block is None:
                break
            shard = self.shard_of(block)
            if block in self._reusable[shard]:
                del self._reusable[shard][block]
                self._refs[block] = 1
            else:
                self._refs[block] += 1
            matched.append(block)
        return matched

    def _forget(self, block: int) -> None:
        h = self._block_to_hash.pop(block, None)
        if h is not None:
            self._hash_to_block.pop(h, None)
            if self.on_event:
                self.on_event(KvEvent(kind="removed", block_hashes=[h]))

    def clear_reusable(self) -> None:
        """Drop all cached-but-free blocks (tests / cache reset)."""
        for shard, reusable in enumerate(self._reusable):
            while reusable:
                block, _ = reusable.popitem(last=False)
                self._forget(block)
                self._free[shard].append(block)
