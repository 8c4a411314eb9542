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

Layout contract is unchanged from ops/pallas/attention.py: the cache is
``[num_slots, kvH, D]`` viewed as pages ``[num_blocks, bs*kvH, D]``, K and
V apart, ``D % 128 == 0`` inside the kernel (lane-padded caches for
smaller head dims); ``q`` and the output live in ANY (HBM) memory and
move by DMA at dynamic row offsets, so spans need no alignment.

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
  compiled kernel; two static tiles:
  - SHORT, ``q_len <= diffusion_block`` rows (a decode row; a block of a
    block-diffusion model): every query head is multiplied against the
    ring slot AS IT LIES — ``[rows*H, D] x [PP*bs*kvH, D]^T`` — and a
    score counts where the column's KV head is the row's. bf16 K goes to
    the MXU as stored (exact products, f32 sums), so the per-fold f32
    cast and the ``[keys, kvH] -> [kvH, keys]`` relayout of K, and the
    relayout of V, are gone; the masked columns cost VPU work in
    proportion to ``kvH``, as the bytes are.
  - LONG, more rows (a prefill quantum, a draft-verify span of k+1
    rows): tiles of ``long_tile(H)`` rows folded one KV head at a time
    in f32, so the visible cache is streamed ``q_len / 32`` or ``/ 16``
    times (``q_len / 8`` before). A fold's work goes with rows x heads
    whatever the span holds, so the tile is 32 rows at a tp=4 chip's 8
    heads and 16 at 32 heads: there 64 draft-verify spans of 5 rows
    read 835 us at 32 rows, 604 at 16, 718 in the kernel before, and a
    cell's dispatch with its 80-row quantum the same (423 | 424); at 8
    heads the quantum costs 23 us more at 16 rows (my chip runs, PR 40).
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
  H 8, kvH 2 (8 KiB), tp=4      523    516    533    509    593    513
  H 32, kvH 8 (32 KiB)          418    412    411    413    407    452
  H 32, kvH 4 (16 KiB), B=4     436    427    428    426    439    458
  ==========================  =====  =====  =====  =====  =====  =====

  (129 / 65 / 65 spans of contexts 200-1,500; my chip runs, PR 40. The
  kernel before read 1,410 / 854 / 835 at its 8x8.) Depth hardly matters
  once the ring spans spans; 128-key folds cost the small pages 15 %,
  512-key folds the large ones 10 % (their clamped tails).

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
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dynamo_tpu.ops.pallas.attention import heads_view

MEMORY_SPACE_ANY = pltpu.MemorySpace.ANY

NEG_INF = -1e30

# The ring (module docstring has the ladder): folds in flight, keys a
# fold, and the most one slot of K may hold.
RAGGED_NBUF = 4
FOLD_KEYS = 256
SLOT_BYTES = 512 * 1024
# Rows of a long span's tile: LONG_FOLD_ROWS (row, head) pairs a fold,
# within 16 to LONG_TILE rows (module docstring).
LONG_TILE = 32
LONG_FOLD_ROWS = 512


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _div(a, b: int):
    """``a // b`` for ``a >= 0``: one op to trace and to lower where
    ``//`` is a floor correction of five (a start pays it every rung)."""
    return jax.lax.div(a, jnp.int32(b))


def _cdiv(a, b: int):
    return _div(a + (b - 1), b)


def long_tile(num_heads: int) -> int:
    """Rows of a long span's tile, from the heads a chip holds."""
    return min(LONG_TILE, max(16, LONG_FOLD_ROWS // num_heads))


def ring_shape(page_bytes: int, block_size: int = 16) -> tuple[int, int]:
    """``(NBUF, PP)``: ring depth in folds (the constant ``RAGGED_NBUF``)
    and pages a fold, from the bytes of one page of K (``block_size * kvH *
    D * itemsize``)."""
    pp = min(FOLD_KEYS // block_size, SLOT_BYTES // page_bytes)
    return RAGGED_NBUF, max(pp, 128 // block_size, 1)


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
    # two per-block scale arrays follow, whole-array-resident in VMEM)
    q_hbm,             # [T + TQL, H, D] flat queries (tail-padded)
    k_hbm,             # [num_blocks, bs*kvH, D] pages
    v_hbm,
    # quantized only: k_scales_ref / v_scales_ref [num_blocks, kvH] VMEM
    *rest,
    block_size: int,
    num_kv_heads: int,
    window: int = 0,
    quantized: bool = False,
    diffusion_block: int = 1,
):
    """ONE program walks every span; see the module docstring."""
    if quantized:
        k_scales_ref, v_scales_ref = rest[0], rest[1]
        rest = rest[2:]
    else:
        k_scales_ref = v_scales_ref = None
    (
        o_hbm,             # [T + TQL, H, D]
        q_s,               # VMEM [NBUF + 1, TQS, H, D] short spans' q rows
        q_l,               # VMEM [2, TQL, H, D]    long spans' q tiles
        o_s,               # VMEM [2, TQS, H, D]
        o_l,               # VMEM [2, TQL, H, D]
        k_buf,             # VMEM [NBUF, PP*bs*kvH, D] (cache dtype)
        v_buf,
        qs_sem,            # DMA [NBUF + 1]
        ql_sem,            # DMA [2]
        os_sem,            # DMA [2]
        ol_sem,            # DMA [2]
        k_sem,             # DMA [NBUF]
        v_sem,
        st,                # SMEM [_NSTATE] int32
    ) = rest
    S = q_len_ref.shape[0]
    NBUF = k_buf.shape[0]
    # The producer may have entered NBUF tiles past the one being folded.
    NQ = NBUF + 1
    TQS = q_s.shape[1]          # rows of a short span: diffusion_block
    TQL = q_l.shape[1]
    H, D = q_l.shape[2], q_l.shape[3]
    kvH = num_kv_heads
    G = H // kvH
    bs = block_size
    page_rows = bs * kvH
    PP = k_buf.shape[1] // page_rows
    N = PP * page_rows          # K rows a fold: PP*bs keys x kvH heads
    scale = 1.0 / (D**0.5)
    B = diffusion_block
    f32 = jnp.float32

    # -- geometry, shared by the producer and the consumer -----------------

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
                pltpu.make_async_copy(
                    k_hbm.at[page], k_buf.at[slot, rows], k_sem.at[slot]
                ).start()
                pltpu.make_async_copy(
                    v_hbm.at[page], v_buf.at[slot, rows], v_sem.at[slot]
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
        for buf, sem in ((k_buf, k_sem), (v_buf, v_sem)):
            pltpu.make_async_copy(
                buf.at[slot], buf.at[slot], sem.at[slot]
            ).wait()
        return slot

    def dequant(ref, scales_ref, s, f, slot, last):
        """A ring slot as f32 ``[PP*bs, kvH, D]`` times its pages' scale
        rows (the pages ``produce`` fetched: the tail's are page ``last``):
        exactly ``int8 * scale``, the oracle's arithmetic."""
        x = heads_view(ref, slot, PP * bs, kvH, D)
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

    # -- a short span: every head against the ring slot as it lies ---------

    # Row r of the folded q is (row r // H of the span, head r % H); column
    # c of a ring slot is (key c // kvH of the fold, KV head c % kvH). A
    # score counts where the column's KV head is the row's.
    M = TQS * H
    row_i = jax.lax.broadcasted_iota(jnp.int32, (M, 1), 0)
    col_i = jax.lax.broadcasted_iota(jnp.int32, (1, N), 1)
    row_tok = _div(row_i, H)
    row_kvh = _div(jax.lax.rem(row_i, H), G)
    col_key = _div(col_i, kvH)
    col_kvh = jax.lax.rem(col_i, kvH)
    mxu_direct = (not quantized) and q_s.dtype == k_buf.dtype == jnp.bfloat16
    head_match = col_kvh == row_kvh            # [M, N]

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
        q2 = jnp.concatenate(
            [q_s[qslot, t].astype(f32) for t in range(TQS)], axis=0
        )  # [M, D]
        q2 = q2.astype(jnp.bfloat16) if mxu_direct else q2 * scale
        q_pos = q0 + row_tok                       # [M, 1]
        if B > 1:
            q_pos = (_div(q_pos, B) + 1) * B - 1   # the end of its block
        # rows past the span see nothing
        q_pos = jnp.where(row_tok < ql, q_pos, -1)

        def fold(f, carry):
            m, l, acc = carry
            slot = take_fold(gc0 + (f - lo_f))
            if quantized:
                k = dequant(k_buf, k_scales_ref, s, f, slot, nb - 1)
                v = dequant(v_buf, v_scales_ref, s, f, slot, nb - 1)
                k, v = k.reshape(N, D), v.reshape(N, D)
            else:
                k = k_buf[slot] if mxu_direct else k_buf[slot].astype(f32)
                v = v_buf[slot].astype(f32)
            scores = jax.lax.dot_general(
                q2, k, (((1,), (1,)), ((), ())), preferred_element_type=f32
            )  # [M, N]
            if mxu_direct:
                scores = scores * scale
            key_pos = f * (PP * bs) + col_key      # [1, N]
            mask = head_match & (key_pos <= q_pos) & (key_pos < kv)
            if window:
                mask = mask & (key_pos > q_pos - window)
            scores = jnp.where(mask, scores, NEG_INF)
            m_new = jnp.maximum(m, scores.max(axis=-1, keepdims=True))
            corr = jnp.exp(m - m_new)
            p = jnp.where(mask, jnp.exp(scores - m_new), 0.0)
            l_new = l * corr + p.sum(axis=-1, keepdims=True)
            pv = jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())), preferred_element_type=f32
            )  # [M, D]: a masked column adds exactly zero
            return m_new, l_new, acc * corr + pv

        init = (
            jnp.full((M, 1), NEG_INF, f32),
            jnp.zeros((M, 1), f32),
            jnp.zeros((M, D), f32),
        )
        m, l, acc = jax.lax.fori_loop(lo_f, hi_f, fold, init)
        out = jnp.where(l > 0, acc / jnp.maximum(l, 1e-30), 0.0)

        oslot = jax.lax.rem(gt, 2)
        drain_short(oslot)
        for t in range(TQS):
            o_s[oslot, t] = out[t * H:(t + 1) * H].astype(o_s.dtype)
        for r in range(TQS):
            @pl.when(r < ql)
            def _():
                row_out(o_s, oslot, r, rs0 + r, os_sem).start()
        st[_OSN + oslot] = ql
        st[_GT] = gt + 1
        st[_GC] = gc0 + (hi_f - lo_f)

    # -- a long span: tiles of TQL rows, one KV head at a time --------------

    R = TQL * G
    lrow = _div(jax.lax.broadcasted_iota(jnp.int32, (1, R, 1), 1), G)
    elem = jax.lax.broadcasted_iota(jnp.int32, (1, 1, PP * bs), 2)

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
            # written back.
            q4 = (q_l[lslot].astype(f32) * scale).reshape(TQL, kvH, G, D)
            qf = jnp.transpose(q4, (1, 0, 2, 3)).reshape(kvH, R, D)
            q_pos = q0 + tok0 + lrow                 # [1, R, 1]
            if B > 1:
                q_pos = (_div(q_pos, B) + 1) * B - 1
            q_pos = jnp.where(lrow < ql - tok0, q_pos, -1)

            def fold(f, carry):
                m, l, acc = carry
                slot = take_fold(gc0 + (f - lo_f))
                if quantized:
                    k = dequant(k_buf, k_scales_ref, s, f, slot, nb - 1)
                    v = dequant(v_buf, v_scales_ref, s, f, slot, nb - 1)
                else:
                    k = heads_view(k_buf, slot, PP * bs, kvH, D)
                    v = heads_view(v_buf, slot, PP * bs, kvH, D)
                kT = jnp.swapaxes(k, 0, 1)  # [kvH, PP*bs, D]
                vT = jnp.swapaxes(v, 0, 1)
                scores = jax.lax.dot_general(
                    qf, kT,
                    (((2,), (2,)), ((0,), (0,))),
                    preferred_element_type=f32,
                )  # [kvH, R, PP*bs]
                key_pos = f * (PP * bs) + elem
                mask = (key_pos <= q_pos) & (key_pos < kv)
                if window:
                    mask = mask & (key_pos > q_pos - window)
                scores = jnp.where(mask, scores, NEG_INF)
                m_new = jnp.maximum(m, scores.max(axis=-1))
                corr = jnp.exp(m - m_new)
                p = jnp.where(mask, jnp.exp(scores - m_new[..., None]), 0.0)
                l_new = l * corr + p.sum(axis=-1)
                pv = jax.lax.dot_general(
                    p, vT,
                    (((2,), (1,)), ((0,), (0,))),
                    preferred_element_type=f32,
                )
                return m_new, l_new, acc * corr[..., None] + pv

            init = (
                jnp.full((kvH, R), NEG_INF, f32),
                jnp.zeros((kvH, R), f32),
                jnp.zeros((kvH, R, D), f32),
            )
            m, l, acc = jax.lax.fori_loop(lo_f, hi_f, fold, init)
            out = jnp.where(
                l[..., None] > 0, acc / jnp.maximum(l[..., None], 1e-30), 0.0
            )
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
    k_cache: jnp.ndarray,       # [num_slots, kvH, D]
    v_cache: jnp.ndarray,
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
    ``long_tile(H)`` (16 or 32) rows where that is more, so a value of 16
    or less changes nothing and no caller in the repo passes one.

    With ``k_scales``/``v_scales`` the caches are int8 and pages
    dequantize in-register (docs/architecture/kv_quant.md): the page DMA
    ring moves half the bytes, the scale arrays (a few KB) sit whole in
    VMEM, and the compiled program count is unchanged — quantization
    only changes dtypes inside the existing budget-ladder grid."""
    T, H, D = q.shape
    S = block_tables.shape[0]
    kvH = k_cache.shape[1]
    assert diffusion_block == 1 or not window, "no window under a block mask"
    TQS = diffusion_block
    TQL = max(q_tile, long_tile(H))
    quantized = k_scales is not None
    kp = k_cache.reshape(-1, block_size * kvH, D)
    vp = v_cache.reshape(-1, block_size * kvH, D)
    nbuf, pp = ring_shape(
        block_size * kvH * D * k_cache.dtype.itemsize, block_size
    )
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
        in_specs=[any_space] * 3
        + [pl.BlockSpec(memory_space=vmem)] * (2 if quantized else 0),
        out_specs=any_space,
        scratch_shapes=[
            pltpu.VMEM((nbuf + 1, TQS, H, D), q.dtype),
            pltpu.VMEM((2, TQL, H, D), q.dtype),
            pltpu.VMEM((2, TQS, H, D), q.dtype),
            pltpu.VMEM((2, TQL, H, D), q.dtype),
            pltpu.VMEM((nbuf, pp * block_size * kvH, D), k_cache.dtype),
            pltpu.VMEM((nbuf, pp * block_size * kvH, D), v_cache.dtype),
            pltpu.SemaphoreType.DMA((nbuf + 1,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((nbuf,)),
            pltpu.SemaphoreType.DMA((nbuf,)),
            pltpu.SMEM((_NSTATE,), jnp.int32),
        ],
    )
    kernel = functools.partial(
        _ragged_kernel, block_size=block_size, num_kv_heads=kvH,
        window=window, quantized=quantized, diffusion_block=diffusion_block,
    )
    operands = [
        block_tables.astype(jnp.int32),
        q_start.astype(jnp.int32),
        q_len.astype(jnp.int32),
        kv_len.astype(jnp.int32),
        row_start.astype(jnp.int32),
        qpad,
        kp,
        vp,
    ]
    if quantized:
        operands += [
            k_scales.astype(jnp.float32), v_scales.astype(jnp.float32)
        ]
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((T + TQL, H, D), q.dtype),
        grid_spec=grid_spec,
        interpret=_interpret(),
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
