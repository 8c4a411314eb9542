"""Ragged unified paged attention — ONE kernel for mixed prefill+decode.

The phase-split kernels (ops/pallas/attention.py) compile one program per
(kind, T-bucket, lane-bucket) point: the shape grid PR 1's compile cache
manages. This kernel deletes the grid instead (ROADMAP item #2, after the
ragged-paged-attention recipe in PAPERS.md): the step takes ONE flat
token batch ``q: [T, H, D]`` in which each sequence owns a contiguous
ragged span of rows — a decode lane is simply a span of length 1, a
chunked-prefill quantum a span of its chunk length, and a speculative
draft-verify span is ``q_len = k+1`` rows (the fed token plus its k
drafts: verification is a short "prefill" over the draft positions, so
the span math is IDENTICAL to a prefill quantum with
``q_start = ctx-1``) — so the only compiled extent is the total token
budget ``T``. Mixed batches run in a
single dispatch: decode steps no longer queue behind prefill dispatches
(the Nexus head-of-line argument), and warmup shrinks from the
lane×bucket grid to a handful of budget shapes.

Metadata (all per-sequence, scalar-prefetched to SMEM):
- ``block_tables[s]``: the sequence's paged-cache block table;
- ``q_start[s]``: global position of the span's first token (its
  already-cached prefix length);
- ``q_len[s]``: span length in rows (0 = idle metadata row);
- ``kv_len[s]``: total context after this step's KV writes, i.e.
  ``q_start + q_len`` (kept explicit on the wire for clarity);
- ``row_start[s]``: the span's first row in the flat batch.

Layout contract: a layer's pages take one of three forms (docs/
architecture/unified_step.md "Three forms of a layer's pages";
``EngineConfig.cache_form`` decides, the kernel reads the form off its
operands: ops/attention.py ``page_form``). APART, as in ops/pallas/attention.py: K and V an
array each, ``[num_slots, kvH, D]`` viewed as pages ``[num_blocks, bs*kvH,
D]``, two rings, two descriptors a page. JOINED (``v_cache=None`` over
``[num_blocks, 2, bs, kvH, D]``; PR 59): a block's keys and then its values
are ONE contiguous page of ONE array, viewed ``[num_blocks, 2, bs*kvH, D]``
(a bitcast), streamed with ONE descriptor a page through one ring of ``2 *
NBUF`` rows, fold ``s`` at rows ``2s`` (its keys) and ``2s + 1`` (its
values): the VMEM and the folds the two rings had, half the descriptors. A
latent cache HELD ONCE (``v_cache=None`` over ``[num_slots, 1, D]``; PR 52)
is one array whose values are the key entry's leading columns: no V operand,
no V ring, both fold bodies read their values from the key's rows. In every
form ``D % 128 == 0`` inside the kernel (lane-padded caches for smaller
head dims), and ``q`` and the output live in ANY (HBM) memory and move by
DMA at dynamic row offsets, so spans need no alignment.

The kernel is ONE program (``grid=(1,)``) that walks the step's spans as
a software pipeline (PR 40; before it, one grid program a span started
cold and paid ~2.7 us a span and 0.12-0.14 us a page whatever the bytes:
``PERF.md`` §6):

- **The ring never drains between spans.** K/V pages stream HBM→VMEM
  through a ring of ``NBUF`` slots of one FOLD (``PP`` pages) each. A
  producer cursor in SMEM — (span, tile, fold) of the next fold to issue
  — runs ``NBUF - 1`` folds ahead of the fold being computed, ACROSS
  tiles and spans, stepping over idle rows (``q_len == 0``); it also
  starts a short span's q rows when it enters the span. A fold always
  issues all ``PP`` pages, the tail's clamped to the last page the tile
  can see: a fold is then one size, ONE wait covers it (a DMA semaphore
  counts bytes), no page is guarded, and a tail column holds real keys
  that the mask drops (no zeroing of unfetched V).
- **Output writes do not block.** A tile's result goes to one of two
  VMEM buffers and its DMA is only started; it is waited for when the
  buffer is next needed and once at the end. A span writes exactly its
  own rows (whole tiles where the span covers them, single rows for the
  tail and for short spans), never a neighbour's.
- **A span is tiled by its length**, read from ``q_len`` inside the one
  compiled kernel; two static tiles, and ONE principle for both fold
  bodies: a fold's vector work goes with the (query, visible key of its
  own cached head) pairs, and its scores lie where the softmax's
  reductions are cheapest. Each choice goes by what the kernel sees in
  its shapes and dtypes (``G = H / kvH``, ``kvH``, ``diffusion_block``,
  the cache's and q's dtype), never by a model's name:
  - *K and V by cached head, as the cache stores them* (``slot_heads``,
    both tiles). Under a bf16 cache and bf16 q with an even ``kvH`` (or
    one head; ``by_word``) a head pair's keys are ONE strided read of the
    slot's 32-bit words (Mosaic strides no packed rows; a packed reshape
    read wrong rows at few heads, PR 22), a shift or a mask being the
    cast (``head_rows``): no f32 cast of the slot, no ``[keys, kvH] ->
    [kvH, keys]`` relayout. q stays bf16 and unscaled, the scores are
    scaled after the product (bf16 x bf16 is exact in f32) and go
    through ``exp2`` in units of log 2 (one multiply a score for scale
    and base). Probabilities stay f32 into PV. int8 and f32 caches keep
    ``dequant`` / ``slab_heads``, a relayout and ``exp``.
  - SHORT, ``q_len <= diffusion_block`` rows (a decode row; a block of a
    block-diffusion model). **By cached head where a head's folded rows
    fill a sublane tile** (``short_by_head``: ``rows * G >= 8`` and more
    than one cached head; PR 47): scores ``[kvH, rows * G, keys]``, a
    head's rows against its own keys, keys along the lanes, one online
    softmax a head. At 128 heads over 8 a fold's scores are 32 vregs
    where the fold below makes 256 of which one column in eight is a
    real pair. **Below that** (4 queries a head: the tool read the fold
    by head no faster there, 409 beside 409-412 us at ``dense`` and 508
    beside 501 at ``tp4``; one latent head is already one head) every
    query head is multiplied against the ring slot AS IT LIES, ``[rows *
    H, D] x [keys * kvH, D]^T``, bf16 K to the MXU as stored, and a score
    counts where the column's cached head is the row's: the masked
    columns cost VPU work in proportion to ``kvH``, which 32 rows bear.
  - LONG, more rows (a prefill quantum, a draft-verify span of k+1
    rows): tiles of ``long_tile(H, kvH)`` rows against one fold of keys
    at a time, one online softmax a cached head, so the visible cache is
    streamed ``q_len / 32`` or ``/ 16`` times. *Scores are held
    transposed*, ``[kvH, keys, rows * G]`` (PR 46's finding, built here):
    keys down the sublanes, a cached head's folded rows along the lanes.
    The max and the sum over keys are elementwise across vregs (eight
    sublane partials; the sum's are carried and joined once a tile), the
    running max, sum and correction are two vregs a head where rows down
    the sublanes spend a vreg on every 8, and the accumulator is ``[kvH,
    D, rows * G]``, turned once a tile. This, not the mask or the cast,
    was what bound the fold. *The tile's rows follow the queries a
    cached head serves*: 512 folded rows (``LONG_FOLD_ROWS``) within 16
    to 32 rows and never under a lane tile of them, so 32 rows at 4, 8
    and 16 queries a head and 16 at Ling's 32 over one latent head. At
    16 queries a head 32 rows hold 17 MiB of VMEM (``VMEM_LIMIT``).
  ``fold_counts`` is the host's mirror of ``tile_folds`` (a step's
  ``attn_short_folds`` / ``attn_long_folds`` on its flight record).
  Read and dropped (chip runs of PR 46 and PR 47, a window layer's call
  at 128 heads over 8, us): the mask alone out of the old long fold
  4,995 -> 4,818, its cast and relayout alone 4,592, both 4,413; the
  strided read WITHOUT the transposed scores 4,980; probabilities as two
  bf16 terms against bf16 V 4,643 where 4,028 without, as ONE bf16 term
  3,965 beside 3,977: the MXU's passes are not what binds. An interior /
  edge split of the long fold (no mask where every key of a fold is
  visible to every row) on THIS layout: 3,874 beside 3,879 under the
  window, 9,467 beside 9,785 (3.3 %) on a full layer with the quantum at
  12k, 2,645 beside 2,565 at 2k, for a second traced body a program
  (``setup_s``): not kept.
- **Pages a fold come from the shape** (``ring_shape``): a fold is
  ``FOLD_KEYS`` keys, fewer where a slot would pass ``SLOT_BYTES`` (never
  under 128 keys, a lane tile of scores). The ring's depth does not: it
  is the constant ``RAGGED_NBUF``, because the ladder read depths 2 to 6
  within 3 % at every page size. The ladder on the chip, microseconds a
  layer's call at the cells' per-chip shapes (``tools/
  ragged_kernel_bench.py``, v5e; NBUF x PP at block 16):

  ==========================  =====  =====  =====  =====  =====  =====
  shape (page of K)            2x16   3x16   4x16   6x16    4x8   3x32
  ==========================  =====  =====  =====  =====  =====  =====
  H 8, kvH 2 (8 KiB), tp=4      377    379    352    373    435    358
  H 32, kvH 8 (32 KiB)          418    412    411    413    407    452
  H 32, kvH 4 (16 KiB), B=4     436    427    428    426    439    458
  H 128, kvH 8, window 4,096  3,889  3,855  3,891  3,871  4,111  3,877
  H 128, kvH 8, no window     9,470  9,398  9,472  9,388 10,406  8,988
  ==========================  =====  =====  =====  =====  =====  =====

  (129 / 65 / 65 spans of contexts 200-1,500; my chip runs, PR 40; the
  first row my chip runs, PR 59: its pages JOINED, 16 KiB a descriptor,
  where K and V apart read 523 / 516 / 533 / 509 / 593 / 513. The
  kernel before PR 40 read 1,410 / 854 / 835 at its 8x8. The last two rows: 45
  lanes at contexts 600-16,000 beside a 770-row quantum ending at 12k,
  32 KiB pages, this kernel; my chip runs, PR 46.) Depth hardly matters
  once the ring spans spans; 128-key folds cost the small pages 15 %,
  512-key folds the large ones 10 % (their clamped tails), and at long
  contexts 128-key folds cost 6-11 % while 512-key folds (a 1 MiB slot,
  over ``SLOT_BYTES``) gain 4 % on a layer without a window and nothing
  under one. **What a page costs** (the split of ``--sweep split``, us a
  page of K and V, two descriptors apart | one joined, beside its bytes;
  my chip runs, PR 59): tp=4's 16 KiB 0.0488 | 0.0308 (0.020; the same
  folds over ONE 8 KiB array, half the bytes, 0.0301: a page there costs
  its descriptor, not its bytes), SDAR's 32 KiB 0.0603 | 0.0406 (0.040),
  one chip's dense 64 KiB 0.0826 | 0.0825 (0.080).

Scores, probabilities, the running max and sum and the accumulator are
f32; every (query, visible key) pair is computed under the same mask as
the twin; int8 pages dequantise as ``int8 * scale``. The order of spans
changes no span's arithmetic.

The jnp semantics twin is ops/attention.py ``ragged_paged_attention``
(the tier-1 oracle); interpret mode runs this kernel's code path on CPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dynamo_tpu.models.config import CACHE_FORMS
from dynamo_tpu.ops.attention import page_form

MEMORY_SPACE_ANY = pltpu.MemorySpace.ANY

NEG_INF = -1e30
LOG2E = 1.4426950408889634

# The ring (module docstring has the ladder): folds in flight, keys a
# fold, and the most one slot of K may hold.
RAGGED_NBUF = 4
FOLD_KEYS = 256
SLOT_BYTES = 512 * 1024
# Rows of a long span's tile: LONG_FOLD_ROWS (row, head) pairs a fold a
# cached head within 16 to LONG_TILE rows, and LANE pairs at least (module
# docstring).
LONG_TILE = 32
LONG_FOLD_ROWS = 512
LANE = 128
# Folded rows a cached head (rows x queries a head) from which a SHORT span
# folds by cached head: a sublane tile.
SHORT_HEAD_ROWS = 8
# The kernel's scoped VMEM: a 32-row tile at 16 queries a cached head holds
# 17 MiB (two 4 MiB f32 score arrays among it), over the compiler's 16.
VMEM_LIMIT = 32 * 1024 * 1024


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _div(a, b: int):
    """``a // b`` for ``a >= 0``: one op to trace and to lower where
    ``//`` is a floor correction of five (a start pays it every rung)."""
    return jax.lax.div(a, jnp.int32(b))


def _cdiv(a, b: int):
    return _div(a + (b - 1), b)


def long_tile(num_heads: int, num_kv_heads: int) -> int:
    """Rows of a long span's tile, from the queries ``G`` a cached head
    serves: a fold's scores a cached head are ``[keys, rows * G]``, the
    folded rows along the lanes, so never under a lane tile of them."""
    g = num_heads // num_kv_heads
    rows = min(LONG_TILE, max(16, LONG_FOLD_ROWS // g))
    return max(rows, LANE // g // 8 * 8)


def short_by_head(rows: int, num_heads: int, num_kv_heads: int) -> bool:
    """Whether a short span of ``rows`` rows (1, or the diffusion block)
    folds one cached head at a time: where a head's ``rows * G`` folded
    rows fill a sublane tile. Below that (4 queries a head: the tool read
    it) and at one cached head the fold takes the ring slot as it lies."""
    folded = rows * (num_heads // num_kv_heads)
    return num_kv_heads > 1 and folded >= SHORT_HEAD_ROWS


def ring_shape(page_bytes: int, block_size: int = 16) -> tuple[int, int]:
    """``(NBUF, PP)``: ring depth in folds (the constant ``RAGGED_NBUF``)
    and pages a fold, from the bytes of one page of K (``block_size * kvH *
    D * itemsize``)."""
    pp = min(FOLD_KEYS // block_size, SLOT_BYTES // page_bytes)
    return RAGGED_NBUF, max(pp, 128 // block_size, 1)


def fold_counts(
    q_start, q_len, kv_len, *, long_rows: int, fold_keys: int,
    window: int = 0, diffusion_block: int = 1,
) -> tuple[int, int]:
    """``(short, long)``: the folds of the ring one call's SHORT and LONG
    tiles walk, on the host; the kernel's ``tile_folds`` summed over every
    tile of the live spans (numpy ``int32`` arrays, no idle row).
    ``long_rows`` is ``long_tile(H, kvH)``, ``fold_keys`` the ring's ``PP *
    block_size``. A span of up to ``diffusion_block`` rows is one tile
    whose last key is its ``kv_len - 1``: array arithmetic over all spans
    at once (this runs on the engine's thread every step); the few longer
    spans are then taken out of that sum and walked a tile at a time in
    plain integers."""
    B, K = diffusion_block, fold_keys
    short = len(q_len) + int(np.add.reduce((kv_len - 1) // K))
    # no span's window starts past fold 0 where no context passes it
    windowed = window and int(kv_len.max()) > window
    if windowed:
        short -= int(np.add.reduce(
            np.maximum(q_start - (window - 1), 0) // K))
    long = 0
    if int(q_len.max()) <= B:
        return short, long
    idx = (q_len > B).nonzero()[0]
    for q0, kv in zip(q_start[idx].tolist(), kv_len[idx].tolist()):
        lo = max(q0 - window + 1, 0) // K if windowed else 0
        short -= (kv - 1) // K + 1 - lo
        for first in range(q0, kv, long_rows):
            hi = first + long_rows
            if B > 1:
                hi = ((hi - 1) // B + 1) * B
            if windowed:
                lo = max(first - window + 1, 0) // K
            long += -(-min(hi, kv) // K) - lo
    return short, long


# Producer / consumer state, int32 scalars in SMEM scratch.
_PS, _PT, _PF, _PHI, _PNB, _GP, _GQ, _GC, _GT, _GL = range(10)
_OSN = 10   # [2] row copies in flight on each short output slot
_OLN = 12   # [2] on each long output slot: rows, or -1 for a whole tile
_NSTATE = 14


def _ragged_kernel(
    # scalar prefetch
    block_tables_ref,  # [S, max_blocks] SMEM
    q_start_ref,       # [S] SMEM — prefix length per sequence
    q_len_ref,         # [S] SMEM — span rows (0 = idle row)
    kv_len_ref,        # [S] SMEM — context after this step's writes
    row_start_ref,     # [S] SMEM — span's first row in the flat batch
    # inputs (q/k/v in ANY memory, DMA'd manually; with `quantized`,
    # a per-block scale array each follows, whole-array-resident in VMEM)
    q_hbm,             # [T + TQL, H, D] flat queries (tail-padded)
    k_hbm,             # [num_blocks, bs*kvH, D] pages; [num_blocks, 2, ...]
    # v_hbm, where ``values`` is "apart"
    # quantized only: k_scales_ref / v_scales_ref [num_blocks, kvH] VMEM
    *rest,
    block_size: int,
    num_kv_heads: int,
    window: int = 0,
    quantized: bool = False,
    diffusion_block: int = 1,
    values: str = "apart",
):
    """ONE program walks every span; see the module docstring. ``values``
    is the form of the layer's pages (``CACHE_FORMS``), which says where a
    fold finds its values. "apart": a V operand, a V ring and a V semaphore
    beside K's. Else ONE stream, and a fold reads its values from the slot
    it has waited for: "once", the key's own rows (a latent cache held
    once); "joined", the slot's second half (a page is a block's keys and
    then its values, one descriptor)."""
    assert values in CACHE_FORMS, values
    apart, joined = values == "apart", values == "joined"
    rest = list(rest)
    v_hbm = rest.pop(0) if apart else k_hbm
    k_scales_ref = v_scales_ref = None
    if quantized:
        k_scales_ref = rest.pop(0)
        v_scales_ref = rest.pop(0) if apart else k_scales_ref
    # o_hbm [T + TQL, H, D]; VMEM q_s [NBUF + 1, TQS, H, D] short spans' q
    # rows, q_l [2, TQL, H, D] long spans' q tiles, o_s [2, TQS, H, D],
    # o_l [2, TQL, H, D], k_buf / v_buf [NBUF, PP*bs*kvH, D] (cache dtype;
    # joined, ONE ring [2 * NBUF, PP*bs*kvH, D]: slot s is rows 2s, a fold's
    # keys, and 2s + 1, its values)
    o_hbm, q_s, q_l, o_s, o_l, k_buf = (rest.pop(0) for _ in range(6))
    v_buf = rest.pop(0) if apart else k_buf
    # DMA semaphores: qs [NBUF + 1], ql / os / ol [2], k / v [NBUF]
    qs_sem, ql_sem, os_sem, ol_sem, k_sem = (rest.pop(0) for _ in range(5))
    v_sem = rest.pop(0) if apart else k_sem
    (st,) = rest       # SMEM [_NSTATE] int32
    # What a fold streams from HBM: K's pages, and V's where they are apart.
    streams = ((k_hbm, k_buf, k_sem), (v_hbm, v_buf, v_sem))[: 2 if apart else 1]
    S = q_len_ref.shape[0]
    NBUF = k_buf.shape[0] // (2 if joined else 1)
    # The producer may have entered NBUF tiles past the one being folded.
    NQ = NBUF + 1
    TQS = q_s.shape[1]          # rows of a short span: diffusion_block
    TQL = q_l.shape[1]
    H, D = q_l.shape[2], q_l.shape[3]
    kvH = num_kv_heads
    G = H // kvH
    bs = block_size
    page_rows = bs * kvH
    PP = k_buf.shape[-2] // page_rows
    N = PP * page_rows          # K rows a fold: PP*bs keys x kvH heads
    scale = 1.0 / (D**0.5)
    B = diffusion_block
    f32 = jnp.float32

    # -- geometry, shared by the producer and the consumer -----------------

    # Where K and V of fold ``slot`` lie in the ring(s): joined, ONE ring of
    # 2 * NBUF rows of ``[N, D]``, a fold's keys at row 2 * slot and its
    # values behind them; else a ring each (or the keys' own rows again).
    def whole(slot):
        """What one wait covers of a ring: fold ``slot``'s rows."""
        return pl.ds(2 * slot, 2) if joined else slot

    def k_at(slot):
        return k_buf, (2 * slot if joined else slot)

    def v_at(slot):
        return v_buf, (2 * slot + 1 if joined else slot)

    def is_short(ql):
        return ql <= TQS

    def tile_folds(s, t):
        """(lo_f, hi_f, nb) of tile ``t`` of span ``s``: the folds of PP
        pages it streams and the pages its last row can see."""
        ql = q_len_ref[s]
        tq = jnp.where(is_short(ql), TQS, TQL)
        first = q_start_ref[s] + t * tq     # position of the tile's row 0
        if B == 1:
            hi = first + tq
        else:
            hi = (_div(first + tq - 1, B) + 1) * B
        nb = _cdiv(jnp.minimum(hi, kv_len_ref[s]), bs)
        if window:
            lo_f = _div(jnp.maximum(first - window + 1, 0), bs * PP)
        else:
            lo_f = jnp.int32(0)
        return lo_f, _cdiv(nb, PP), nb

    def next_live(s):
        """First span at or after ``s`` with rows, or ``S``."""
        return jax.lax.while_loop(
            lambda x: (x < S) & (q_len_ref[jnp.minimum(x, S - 1)] == 0),
            lambda x: x + 1,
            s,
        )

    # -- the producer: runs NBUF - 1 folds ahead, across spans -------------

    def enter_tile(s, t):
        lo_f, hi_f, nb = tile_folds(s, t)
        st[_PF] = lo_f
        st[_PHI] = hi_f
        st[_PNB] = nb

        @pl.when(is_short(q_len_ref[s]))
        def _():
            gq = st[_GQ]
            slot = jax.lax.rem(gq, NQ)
            pltpu.make_async_copy(
                q_hbm.at[pl.ds(row_start_ref[s], TQS)],
                q_s.at[slot], qs_sem.at[slot],
            ).start()
            st[_GQ] = gq + 1

    def produce():
        """Issue the next fold of the step's stream, if one is left: all
        PP pages, the tail's clamped to the last page the tile sees (a
        fold is then one size, so one wait covers it)."""
        ps = st[_PS]

        @pl.when(ps < S)
        def _():
            pf = st[_PF]
            last = st[_PNB] - 1
            gp = st[_GP]
            slot = jax.lax.rem(gp, NBUF)
            def issue(h, c):
                page = block_tables_ref[ps, jnp.minimum(pf * PP + h, last)]
                rows = pl.ds(h * page_rows, page_rows)
                for hbm, buf, sem in streams:
                    # joined: K's rows and V's of the page, ONE descriptor
                    pltpu.make_async_copy(
                        hbm.at[page], buf.at[whole(slot), rows], sem.at[slot]
                    ).start()
                return c

            # Traced once, unrolled by the lowering: the code of a Python
            # loop at a sixteenth of its tracing (a start pays it a rung).
            jax.lax.fori_loop(0, PP, issue, 0, unroll=True)
            st[_GP] = gp + 1
            st[_PF] = pf + 1

            @pl.when(pf + 1 >= st[_PHI])
            def _next_tile():
                ql = q_len_ref[ps]
                ntiles = jnp.where(is_short(ql), 1, _cdiv(ql, TQL))
                more = st[_PT] + 1 < ntiles
                ns = next_live(jnp.where(more, ps, ps + 1))
                nt = jnp.where(more, st[_PT] + 1, 0)
                st[_PS] = ns
                st[_PT] = nt

                @pl.when(ns < S)
                def _():
                    enter_tile(ns, nt)

    def take_fold(i):
        """Keep the producer ahead, then wait for fold ``i`` of the
        step's stream (all ``PP`` pages of K and of V, one wait each);
        returns its ring slot."""
        produce()
        slot = jax.lax.rem(i, NBUF)
        for _, buf, sem in streams:
            pltpu.make_async_copy(
                buf.at[whole(slot)], buf.at[whole(slot)], sem.at[slot]
            ).wait()
        return slot

    def slab(at, slot):
        """K's or V's ``[N, D]`` rows of a fold, as stored."""
        buf, row = at(slot)
        return buf[row]

    def slab_heads(at, slot):
        """The same as f32 ``[PP*bs, kvH, D]``: load, cast, THEN reshape
        (a packed reshape reads wrong rows at few heads, PR 22)."""
        return slab(at, slot).astype(f32).reshape(PP * bs, kvH, D)

    def dequant(at, scales_ref, s, f, slot, last):
        """A ring slot as f32 ``[PP*bs, kvH, D]`` times its pages' scale
        rows (the pages ``produce`` fetched: the tail's are page ``last``):
        exactly ``int8 * scale``, the oracle's arithmetic."""
        x = slab_heads(at, slot)
        rows = []
        for h in range(PP):
            j = jnp.minimum(f * PP + h, last)
            sc = scales_ref[pl.ds(block_tables_ref[s, j], 1), :]  # [1, kvH]
            rows.append(jnp.broadcast_to(sc, (bs, kvH)))
        return x * jnp.concatenate(rows, axis=0)[:, :, None]

    # -- output: start now, wait when the buffer is next needed ------------

    def row_out(buf, slot, r, row, sem):
        return pltpu.make_async_copy(
            buf.at[slot, pl.ds(r, 1)], o_hbm.at[pl.ds(row, 1)], sem.at[slot]
        )

    def drain_short(slot):
        n = st[_OSN + slot]
        for r in range(TQS):
            @pl.when(r < n)
            def _():
                row_out(o_s, slot, r, 0, os_sem).wait()
        st[_OSN + slot] = 0

    def drain_long(slot):
        n = st[_OLN + slot]

        @pl.when(n < 0)
        def _():
            pltpu.make_async_copy(
                o_l.at[slot], o_hbm.at[pl.ds(0, TQL)], ol_sem.at[slot]
            ).wait()

        @pl.when(n > 0)
        def _():
            jax.lax.fori_loop(
                0, n,
                lambda r, c: (row_out(o_l, slot, 0, 0, ol_sem).wait(), c)[1],
                0,
            )

        st[_OLN + slot] = 0

    # -- K and V of a ring slot by cached head ------------------------------

    KEYS = PP * bs
    # A bf16 cache under bf16 q: K reaches the MXU as stored. Row ``key *
    # kvH + h`` of a slot is half of the 32-bit word ``(key * kvH + h) //
    # 2``, so a head pair's keys are ONE strided read of words (Mosaic
    # strides no packed rows).
    mxu_direct = (not quantized) and q_s.dtype == k_buf.dtype == jnp.bfloat16
    by_word = mxu_direct and (kvH == 1 or kvH % 2 == 0)
    # by word the scores go through exp2 in units of log 2: one multiply a
    # score for scale and base, after the (exact) bf16 product
    exp = jnp.exp2 if by_word else jnp.exp
    post = scale * LOG2E if by_word else scale

    def head_rows(at, slot):
        """A bf16 ring slot's K or V as f32 ``[kvH, KEYS, D]``: each cached
        head's keys read at their stride, no cast of the slot and no
        relayout. A word holds row ``2w`` low and row ``2w + 1`` high, and
        a bf16 is the high half of its f32: a shift or a mask IS the cast."""
        if kvH == 1:
            return slab(at, slot).astype(f32)[None]
        buf, row = at(slot)
        words = buf.bitcast(jnp.uint32)
        heads = []
        for j in range(kvH // 2):
            w = words[row, pl.ds(j, KEYS, stride=kvH // 2), :]
            heads.append(pltpu.bitcast(w << 16, f32))
            heads.append(pltpu.bitcast(w & jnp.uint32(0xFFFF0000), f32))
        return jnp.stack(heads)

    def slot_heads(s, f, slot, last):
        """``(K, V)`` of a ring slot as ``[kvH, KEYS, D]``: under
        ``by_word`` bf16 K (the MXU's operand as stored) and f32 V, else
        both f32 through ``dequant`` / ``slab_heads`` and a relayout."""
        if by_word:
            return (head_rows(k_at, slot).astype(jnp.bfloat16),
                    head_rows(v_at, slot))
        if quantized:
            k = dequant(k_at, k_scales_ref, s, f, slot, last)
            v = dequant(v_at, v_scales_ref, s, f, slot, last)
        else:
            k, v = slab_heads(k_at, slot), slab_heads(v_at, slot)
        return jnp.swapaxes(k, 0, 1), jnp.swapaxes(v, 0, 1)

    # -- a short span: a row a lane, or a block of a block-diffusion model --

    # BY CACHED HEAD where a head's folded rows fill a sublane tile
    # (``short_by_head``): scores ``[kvH, TQS * G, KEYS]``, a head's rows
    # against its own keys. Below that every head is multiplied against
    # the ring slot AS IT LIES: row r of the folded q is (row r // H of the
    # span, head r % H); column c of a ring slot is (key c // kvH of the
    # fold, KV head c % kvH), and a score counts where the column's KV head
    # is the row's.
    M = TQS * H
    GT = TQS * G
    by_head = short_by_head(TQS, H, kvH)
    # bf16 q against bf16 K as stored: by head K is read a word at a time
    direct = by_word if by_head else mxu_direct
    if by_head:
        row_tok = _div(jax.lax.broadcasted_iota(jnp.int32, (1, GT, 1), 1), G)
        col_key = jax.lax.broadcasted_iota(jnp.int32, (1, 1, KEYS), 2)
    else:
        row_i = jax.lax.broadcasted_iota(jnp.int32, (M, 1), 0)
        col_i = jax.lax.broadcasted_iota(jnp.int32, (1, N), 1)
        row_tok = _div(row_i, H)
        col_key = _div(col_i, kvH)
        head_match = jax.lax.rem(col_i, kvH) == _div(jax.lax.rem(row_i, H), G)

    def short_span(s):
        ql = q_len_ref[s]
        q0 = q_start_ref[s]
        kv = kv_len_ref[s]
        rs0 = row_start_ref[s]
        lo_f, hi_f, nb = tile_folds(s, 0)
        gt = st[_GT]
        gc0 = st[_GC]
        qslot = jax.lax.rem(gt, NQ)
        pltpu.make_async_copy(
            q_hbm.at[pl.ds(0, TQS)], q_s.at[qslot], qs_sem.at[qslot]
        ).wait()
        rows = [q_s[qslot, t].astype(f32) for t in range(TQS)]
        if by_head:
            # [TQS, H, D] -> [kvH, TQS * G, D]; relaid in f32 (a packed
            # reshape reads wrong rows at few heads)
            q2 = jnp.concatenate(
                [r.reshape(kvH, 1, G, D) for r in rows], axis=1
            ).reshape(kvH, GT, D)
        else:
            q2 = jnp.concatenate(rows, axis=0)     # [M, D]
        q2 = q2.astype(jnp.bfloat16) if direct else q2 * scale
        q_pos = q0 + row_tok
        if B > 1:
            q_pos = (_div(q_pos, B) + 1) * B - 1   # the end of its block
        # rows past the span see nothing
        q_pos = jnp.where(row_tok < ql, q_pos, -1)

        def fold(f, carry):
            m, l, acc = carry
            slot = take_fold(gc0 + (f - lo_f))
            if by_head:
                k, v = slot_heads(s, f, slot, nb - 1)
                qk = (((2,), (2,)), ((0,), (0,)))  # [kvH, GT, KEYS]
                pv = (((2,), (1,)), ((0,), (0,)))  # [kvH, GT, D]
            else:
                if quantized:
                    k = dequant(k_at, k_scales_ref, s, f, slot, nb - 1)
                    v = dequant(v_at, v_scales_ref, s, f, slot, nb - 1)
                    k, v = k.reshape(N, D), v.reshape(N, D)
                else:
                    k = slab(k_at, slot)
                    k = k if direct else k.astype(f32)
                    v = slab(v_at, slot).astype(f32)
                qk = (((1,), (1,)), ((), ()))      # [M, N]
                pv = (((1,), (0,)), ((), ()))      # [M, D]
            scores = jax.lax.dot_general(
                q2, k, qk, preferred_element_type=f32)
            if direct:
                scores = scores * post
            key_pos = f * KEYS + col_key
            mask = (key_pos <= q_pos) & (key_pos < kv)
            if window:
                mask = mask & (key_pos > q_pos - window)
            if not by_head:
                mask = mask & head_match
            scores = jnp.where(mask, scores, NEG_INF)
            m_new = jnp.maximum(m, scores.max(axis=-1, keepdims=True))
            corr = exp(m - m_new)
            p = jnp.where(mask, exp(scores - m_new), 0.0)
            l_new = l * corr + p.sum(axis=-1, keepdims=True)
            # a masked column adds exactly zero
            return m_new, l_new, acc * corr + jax.lax.dot_general(
                p, v, pv, preferred_element_type=f32)

        lead = (kvH, GT) if by_head else (M,)
        init = (
            jnp.full(lead + (1,), NEG_INF, f32),
            jnp.zeros(lead + (1,), f32),
            jnp.zeros(lead + (D,), f32),
        )
        m, l, acc = jax.lax.fori_loop(lo_f, hi_f, fold, init)
        out = jnp.where(l > 0, acc / jnp.maximum(l, 1e-30), 0.0)
        if by_head:
            out = out.reshape(kvH, TQS, G, D)

        oslot = jax.lax.rem(gt, 2)
        drain_short(oslot)
        for t in range(TQS):
            row = out[:, t].reshape(H, D) if by_head else out[t * H:(t + 1) * H]
            o_s[oslot, t] = row.astype(o_s.dtype)
        for r in range(TQS):
            @pl.when(r < ql)
            def _():
                row_out(o_s, oslot, r, rs0 + r, os_sem).start()
        st[_OSN + oslot] = ql
        st[_GT] = gt + 1
        st[_GC] = gc0 + (hi_f - lo_f)

    # -- a long span: tiles of TQL rows, one online softmax a cached head ---

    R = TQL * G
    # Scores are held TRANSPOSED, [kvH, KEYS, R]: keys down the sublanes,
    # folded rows along the lanes. The softmax's max and sum over keys are
    # then elementwise across vregs (``by_sublane``; no cross-lane
    # reduction a fold), and the running max and correction are
    # [kvH, 1, R], the sum its eight sublane partials [kvH, 8, R]: two
    # vregs a head where rows down the sublanes take a vreg every 8 rows.
    lrow = _div(jax.lax.broadcasted_iota(jnp.int32, (1, 1, R), 2), G)
    elem = jax.lax.broadcasted_iota(jnp.int32, (1, KEYS, 1), 1)

    def by_sublane(x):
        """``[kvH, KEYS, R]`` as the vregs it lies in, ``[kvH, KEYS / 8, 8,
        R]``: a reduction over axis 1 is elementwise across vregs."""
        return x.reshape(kvH, KEYS // 8, 8, R)

    def long_span(s):
        ql = q_len_ref[s]
        q0 = q_start_ref[s]
        kv = kv_len_ref[s]
        rs0 = row_start_ref[s]
        ntiles = _cdiv(ql, TQL)
        gl0 = st[_GL]

        def q_in(t):
            slot = jax.lax.rem(gl0 + t, 2)
            return pltpu.make_async_copy(
                q_hbm.at[pl.ds(rs0 + t * TQL, TQL)], q_l.at[slot],
                ql_sem.at[slot],
            )

        q_in(0).start()

        def tile_body(t, _):
            lslot = jax.lax.rem(gl0 + t, 2)
            row0 = rs0 + t * TQL
            tok0 = t * TQL
            lo_f, hi_f, nb = tile_folds(s, t)
            gc0 = st[_GC]
            q_in(t).wait()

            @pl.when(t + 1 < ntiles)
            def _():
                q_in(t + 1).start()

            # [TQL, H, D] -> [kvH, TQL*G, D] folded rows; rows past the
            # span read a neighbour's q but see no key, and are never
            # written back. Relaid in f32 (a packed reshape reads wrong
            # rows at few heads); back to bf16 it is q digit for digit.
            q4 = q_l[lslot].astype(f32)
            if not by_word:
                q4 = q4 * scale
            q4 = q4.reshape(TQL, kvH, G, D)
            qf = jnp.transpose(q4, (1, 0, 2, 3)).reshape(kvH, R, D)
            if by_word:
                qf = qf.astype(jnp.bfloat16)
            q_pos = q0 + tok0 + lrow                 # [1, 1, R]
            if B > 1:
                q_pos = (_div(q_pos, B) + 1) * B - 1
            q_pos = jnp.where(lrow < ql - tok0, q_pos, -1)

            def fold(f, carry):
                m, l, acc = carry
                slot = take_fold(gc0 + (f - lo_f))
                kT, vT = slot_heads(s, f, slot, nb - 1)
                scores = jax.lax.dot_general(
                    kT, qf,
                    (((2,), (2,)), ((0,), (0,))),
                    preferred_element_type=f32,
                )  # [kvH, KEYS, R]
                if by_word:
                    scores = scores * post
                key_pos = f * KEYS + elem
                mask = (key_pos <= q_pos) & (key_pos < kv)
                if window:
                    mask = mask & (key_pos > q_pos - window)
                scores = jnp.where(mask, scores, NEG_INF)
                m_new = jnp.maximum(m, by_sublane(scores).max(axis=1).max(
                    axis=1, keepdims=True))
                corr = exp(m - m_new)
                p = jnp.where(mask, exp(scores - m_new), 0.0)
                l_new = l * corr + by_sublane(p).sum(axis=1)
                pv = jax.lax.dot_general(
                    vT, p,
                    (((1,), (1,)), ((0,), (0,))),
                    preferred_element_type=f32,
                )  # [kvH, D, R]
                return m_new, l_new, acc * corr + pv

            init = (
                jnp.full((kvH, 1, R), NEG_INF, f32),
                jnp.zeros((kvH, 8, R), f32),
                jnp.zeros((kvH, D, R), f32),
            )
            m, l, acc = jax.lax.fori_loop(lo_f, hi_f, fold, init)
            l = l.sum(axis=1, keepdims=True)
            out = jnp.where(l > 0, acc / jnp.maximum(l, 1e-30), 0.0)
            out = jnp.swapaxes(out, 1, 2)            # [kvH, R, D]
            out = jnp.transpose(out.reshape(kvH, TQL, G, D), (1, 0, 2, 3))
            drain_long(lslot)
            o_l[lslot] = out.reshape(TQL, H, D).astype(o_l.dtype)

            rem = jnp.minimum(ql - tok0, TQL)  # the tile's own rows

            @pl.when(rem >= TQL)
            def _whole_tile():
                pltpu.make_async_copy(
                    o_l.at[lslot], o_hbm.at[pl.ds(row0, TQL)],
                    ol_sem.at[lslot],
                ).start()
                st[_OLN + lslot] = -1

            @pl.when(rem < TQL)
            def _tail_rows():
                jax.lax.fori_loop(
                    0, rem,
                    lambda r, c: (
                        row_out(o_l, lslot, r, row0 + r, ol_sem).start(), c
                    )[1],
                    0,
                )
                st[_OLN + lslot] = rem

            st[_GC] = gc0 + (hi_f - lo_f)
            return 0

        jax.lax.fori_loop(0, ntiles, tile_body, 0)
        st[_GL] = gl0 + ntiles

    # -- the walk ----------------------------------------------------------

    for i in range(_NSTATE):
        st[i] = 0
    first = next_live(jnp.int32(0))
    st[_PS] = first

    @pl.when(first < S)
    def _():
        enter_tile(first, 0)

    jax.lax.fori_loop(0, NBUF - 1, lambda i, c: (produce(), c)[1], 0)

    def span_body(s, _):
        ql = q_len_ref[s]

        @pl.when((ql > 0) & is_short(ql))
        def _():
            short_span(s)

        @pl.when(ql > TQS)
        def _():
            long_span(s)

        return 0

    jax.lax.fori_loop(0, S, span_body, 0)
    for slot in range(2):
        drain_short(slot)
        drain_long(slot)


@functools.partial(
    jax.jit,
    static_argnames=("block_size", "q_tile", "window", "diffusion_block"),
)
def ragged_paged_attention_pallas(
    q: jnp.ndarray,             # [T, H, D] flat token batch (budget-padded)
    k_cache: jnp.ndarray,       # [num_slots, kvH, D]; joined, see below
    v_cache: jnp.ndarray | None,  # None: the values are in ``k_cache``
    block_tables: jnp.ndarray,  # [S, max_blocks] int32
    q_start: jnp.ndarray,       # [S] int32 — prefix length per span
    q_len: jnp.ndarray,         # [S] int32 — span rows (0 = idle)
    kv_len: jnp.ndarray,        # [S] int32 — context incl. this step
    row_start: jnp.ndarray,     # [S] int32 — span's first flat row
    block_size: int,
    q_tile: int = 8,
    window: int = 0,
    k_scales: jnp.ndarray | None = None,  # [num_blocks, kvH] f32 (int8 KV)
    v_scales: jnp.ndarray | None = None,
    diffusion_block: int = 1,
) -> jnp.ndarray:
    """Mixed prefill+decode attention over one flat ragged batch; returns
    ``[T, H, D]``. Rows not covered by any span are returned ZEROED (the
    same contract as the jnp twin). ``q_tile`` is kept for its callers:
    it is the least the long spans' tile may be, the kernel takes
    ``long_tile(H, kvH)`` (16 rows or more) where that is more, so a value of 16
    or less changes nothing and no caller in the repo passes one.

    With ``k_scales``/``v_scales`` the caches are int8 and pages
    dequantize in-register (docs/architecture/kv_quant.md): the page DMA
    ring moves half the bytes, the scale arrays (a few KB) sit whole in
    VMEM, and the compiled program count is unchanged — quantization
    only changes dtypes inside the existing budget-ladder grid.

    ``v_cache=None`` is ONE array that holds the values too, and the kernel
    streams it through one ring (``page_form`` reads which off the array).
    ``[num_slots, 1, D]``, a latent cache held once: the key entry ``[latent
    | rotated k_pe]`` holds the values in its leading columns (half the DMA
    bytes, half the ring's VMEM), the fold is ``P @ K``, and the result's
    columns past the latent's width are ``P @ k_pe``, which the caller does
    not read; ``v_scales`` is then ``k_scales``. ``[num_blocks, 2, bs, kvH,
    D]``, a (k, v) layer's pages JOINED: a block's keys and then its values
    are one contiguous page, so a page is one descriptor of twice the bytes
    where two (the ring holds what the two rings held)."""
    T, H, D = q.shape
    S = block_tables.shape[0]
    kvH = k_cache.shape[-2]
    assert diffusion_block == 1 or not window, "no window under a block mask"
    TQS = diffusion_block
    TQL = max(q_tile, long_tile(H, kvH))
    quantized = k_scales is not None
    values = page_form(k_cache, v_cache)
    page_rows = block_size * kvH
    if values == "joined":
        assert not quantized, "int8 pages keep K and V apart (a scale each)"
        pages = [k_cache.reshape(-1, 2, page_rows, D)]
    else:
        pages = [
            c.reshape(-1, page_rows, D)
            for c in ((k_cache, v_cache) if values == "apart" else (k_cache,))
        ]
    scales = [k_scales, v_scales][: len(pages)] if quantized else []
    # K's share of a slot sizes the fold, joined or apart: the ring(s) hold
    # twice ``SLOT_BYTES`` a slot either way.
    nbuf, pp = ring_shape(page_rows * D * k_cache.dtype.itemsize, block_size)
    # joined, ONE ring of 2 * nbuf rows: a fold's keys, then its values
    ring = (2 * nbuf if values == "joined" else nbuf, pp * page_rows, D)
    # Tail pad: the last tile of a span ending near row T-1 reads a whole
    # tile from its dynamic offset; padding keeps every read in bounds
    # without aligning spans. The pad rows are never written back.
    qpad = jnp.pad(q, ((0, TQL), (0, 0), (0, 0)))

    vmem = pltpu.MemorySpace.VMEM
    any_space = pl.BlockSpec(memory_space=MEMORY_SPACE_ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(1,),
        # Per-block scales ride whole in VMEM: the kernel loads each
        # page's [kvH] row at a dynamic offset during the fold.
        in_specs=[any_space] * (1 + len(pages))
        + [pl.BlockSpec(memory_space=vmem)] * len(scales),
        out_specs=any_space,
        scratch_shapes=[
            pltpu.VMEM((nbuf + 1, TQS, H, D), q.dtype),
            pltpu.VMEM((2, TQL, H, D), q.dtype),
            pltpu.VMEM((2, TQS, H, D), q.dtype),
            pltpu.VMEM((2, TQL, H, D), q.dtype),
            *(pltpu.VMEM(ring, p.dtype) for p in pages),
            pltpu.SemaphoreType.DMA((nbuf + 1,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            *(pltpu.SemaphoreType.DMA((nbuf,)) for _ in pages),
            pltpu.SMEM((_NSTATE,), jnp.int32),
        ],
    )
    kernel = functools.partial(
        _ragged_kernel, block_size=block_size, num_kv_heads=kvH,
        window=window, quantized=quantized, diffusion_block=diffusion_block,
        values=values,
    )
    operands = [
        block_tables.astype(jnp.int32),
        q_start.astype(jnp.int32),
        q_len.astype(jnp.int32),
        kv_len.astype(jnp.int32),
        row_start.astype(jnp.int32),
        qpad,
        *pages,
        *(sc.astype(jnp.float32) for sc in scales),
    ]
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((T + TQL, H, D), q.dtype),
        grid_spec=grid_spec,
        interpret=_interpret(),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
    )(*operands)[:T]
    # Rows no span owns (budget padding between/after spans) may hold
    # whatever the output buffer held — zero them so the contract matches
    # the jnp twin and padding can never leak into downstream residuals.
    span = (
        (jnp.arange(T)[:, None] >= row_start[None, :])
        & (jnp.arange(T)[:, None] < (row_start + q_len)[None, :])
        & (q_len[None, :] > 0)
    ).any(axis=1)
    return jnp.where(span[:, None, None], out, 0)
