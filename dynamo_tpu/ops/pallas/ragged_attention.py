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
``[num_slots, kvH, D]`` viewed as pages ``[num_blocks, bs*kvH, D]``,
``D % 128 == 0`` inside the kernel (lane-padded caches for smaller head
dims), pages stream HBM→VMEM through a double-buffered DMA ring with
``RAGGED_PP`` pages folded per attention step. What is new mechanically:
``q`` and the output live in ANY (HBM) memory space and each grid
program (one per sequence) DMAs its own ragged q rows in — and its
output rows out — at dynamic offsets, full ``q_tile`` blocks where the
span allows and row-by-row for the tail, so spans need no alignment and
a decode row costs a single-row copy.

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

# DMA ring depth and pages-per-fold, matching the measured ladders in
# ops/pallas/attention.py (the fold math and page sizes are identical, so
# the same operating point applies).
RAGGED_NBUF = 8
RAGGED_PP = 8


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _ragged_kernel(
    # scalar prefetch
    block_tables_ref,  # [S, max_blocks] SMEM
    q_start_ref,       # [S] SMEM — prefix length per sequence
    q_len_ref,         # [S] SMEM — span rows (0 = idle row)
    kv_len_ref,        # [S] SMEM — context after this step's writes
    row_start_ref,     # [S] SMEM — span's first row in the flat batch
    # inputs (q/k/v in ANY memory, DMA'd manually; with `quantized`,
    # two per-block scale arrays follow, whole-array-resident in VMEM)
    q_hbm,             # [T + TQ, H, D] flat queries (tail-padded)
    k_hbm,             # [num_blocks, bs*kvH, D] pages
    v_hbm,
    # quantized only: k_scales_ref / v_scales_ref [num_blocks, kvH] VMEM
    *rest,
    block_size: int,
    num_kv_heads: int,
    q_tile_rows: int,
    window: int = 0,
    quantized: bool = False,
    diffusion_block: int = 1,
):
    """One grid program per sequence; inner loop over its q tiles.

    ``diffusion_block = B > 1`` masks BY BLOCK (block-diffusion models):
    a query sees every key of its own block of ``B`` positions and of the
    blocks before it, so the tile's page bound runs to the end of its
    last row's block. ``B = 1`` is the causal mask, compiled as it was.

    Each tile DMAs ``TQ`` q rows in from the flat batch at the span's
    (dynamic) offset, streams the causally visible KV pages through the
    fold ring, and DMAs the result rows back out — whole tiles when the
    span still covers ``TQ`` rows, single rows for the ragged tail (so a
    decode span writes exactly its one row and never clobbers a
    neighbouring span's output).

    ``quantized``: K/V pages stream as int8 through the SAME DMA ring
    (half the HBM bytes — the point of the int8 path) and dequantize
    in-register during the fold: each page's [kvH] scale row loads from
    the VMEM-resident scale arrays by its physical page id, and the
    arithmetic is exactly ``int8 * scale`` — matching the XLA oracle's
    gathered multiply, so parity is exact-contract."""
    if quantized:
        k_scales_ref, v_scales_ref = rest[0], rest[1]
        rest = rest[2:]
    else:
        k_scales_ref = v_scales_ref = None
    (
        o_hbm,             # [T + TQ, H, D]
        q_tile,            # VMEM [TQ, H, D]
        o_tile,            # VMEM [TQ, H, D]
        k_buf,             # VMEM [NBUF, PP*bs*kvH, D] (cache dtype)
        v_buf,
        q_sem,
        o_sem,
        k_sem,             # DMA [NBUF, PP]
        v_sem,
    ) = rest
    s = pl.program_id(0)
    ql = q_len_ref[s]
    q0 = q_start_ref[s]
    kv = kv_len_ref[s]
    rs0 = row_start_ref[s]

    TQ = q_tile_rows
    H, D = q_tile.shape[1], q_tile.shape[2]
    kvH = num_kv_heads
    G = H // kvH
    bs = block_size
    scale = 1.0 / (D**0.5)
    NBUF = RAGGED_NBUF
    PP = RAGGED_PP

    row_idx = jax.lax.broadcasted_iota(jnp.int32, (1, TQ * G, 1), 1) // G

    @pl.when(ql > 0)
    def _():
        ntiles = pl.cdiv(ql, TQ)

        def tile_body(t, _):
            row0 = rs0 + t * TQ        # flat-batch row of this tile
            tok0 = t * TQ              # span-local index of its first row
            pltpu.make_async_copy(
                q_hbm.at[pl.ds(row0, TQ)], q_tile, q_sem
            ).start()

            # Keys this tile can see: causal bound clipped to the context;
            # with a window, pages wholly behind every row's window skip.
            if diffusion_block == 1:
                hi = jnp.minimum(q0 + tok0 + TQ, kv)
            else:
                hi = jnp.minimum(
                    ((q0 + tok0 + TQ - 1) // diffusion_block + 1)
                    * diffusion_block,
                    kv,
                )
            nb = pl.cdiv(hi, bs)
            lo = (
                jnp.maximum(q0 + tok0 - window + 1, 0) // bs
                if window
                else jnp.int32(0)
            )
            lo_f = lo // PP
            hi_f = pl.cdiv(nb, PP)

            def issue(f):
                slot = jax.lax.rem(f, NBUF)
                for h in range(PP):
                    j = f * PP + h

                    @pl.when((f >= lo_f) & (f < hi_f) & (j < nb))
                    def _():
                        page = block_tables_ref[s, j]
                        pltpu.make_async_copy(
                            k_hbm.at[page],
                            k_buf.at[slot, pl.ds(h * bs * kvH, bs * kvH)],
                            k_sem.at[slot, h],
                        ).start()
                        pltpu.make_async_copy(
                            v_hbm.at[page],
                            v_buf.at[slot, pl.ds(h * bs * kvH, bs * kvH)],
                            v_sem.at[slot, h],
                        ).start()

            jax.lax.fori_loop(
                lo_f, lo_f + NBUF - 1, lambda f, c: (issue(f), c)[1], 0
            )
            pltpu.make_async_copy(
                q_hbm.at[pl.ds(row0, TQ)], q_tile, q_sem
            ).wait()

            # [TQ, H, D] -> [kvH, TQ*G, D] folded rows; masked rows (the
            # tail tile's overhang into the next span) read garbage q but
            # every key is masked for them, so they fold to zero and are
            # never written back.
            q4 = (q_tile[...].astype(jnp.float32) * scale).reshape(
                TQ, kvH, G, D
            )
            qf = jnp.transpose(q4, (1, 0, 2, 3)).reshape(kvH, TQ * G, D)
            q_pos = q0 + tok0 + row_idx          # [1, TQ*G, 1]
            if diffusion_block > 1:
                # The last key the row sees: the end of its own block.
                q_pos = (
                    q_pos // diffusion_block + 1
                ) * diffusion_block - 1
            row_ok = row_idx < (ql - tok0)       # [1, TQ*G, 1]

            def fold(f, carry):
                m, l, acc = carry
                issue(f + NBUF - 1)
                slot = jax.lax.rem(f, NBUF)
                for h in range(PP):
                    @pl.when(f * PP + h < nb)
                    def _():
                        pltpu.make_async_copy(
                            k_hbm.at[0],
                            k_buf.at[slot, pl.ds(h * bs * kvH, bs * kvH)],
                            k_sem.at[slot, h],
                        ).wait()
                        pltpu.make_async_copy(
                            v_hbm.at[0],
                            v_buf.at[slot, pl.ds(h * bs * kvH, bs * kvH)],
                            v_sem.at[slot, h],
                        ).wait()
                # Unfetched tail pages hold garbage: zero V's rows
                # (0 * NaN = NaN through the PV matmul); K needs nothing
                # — NaN scores land only in masked columns.
                fetched = (
                    f * PP
                    + jax.lax.broadcasted_iota(
                        jnp.int32, (PP * bs, 1, 1), 0
                    ) // bs
                ) < nb
                k = heads_view(k_buf, slot, PP * bs, kvH, D)
                v = heads_view(v_buf, slot, PP * bs, kvH, D)
                if quantized:
                    # In-register dequant: one [kvH] scale row per page,
                    # loaded from VMEM by physical page id (same id the
                    # ring DMA'd the page by). Unfetched tail pages use a
                    # clamped table entry — their columns are masked, and
                    # V additionally zeroes below.
                    max_blocks = block_tables_ref.shape[1]
                    ks_rows, vs_rows = [], []
                    for h in range(PP):
                        j = jnp.minimum(f * PP + h, max_blocks - 1)
                        page = block_tables_ref[s, j]
                        ks = k_scales_ref[pl.ds(page, 1), :]  # [1, kvH]
                        vs = v_scales_ref[pl.ds(page, 1), :]
                        ks_rows.append(jnp.broadcast_to(ks, (bs, kvH)))
                        vs_rows.append(jnp.broadcast_to(vs, (bs, kvH)))
                    k = k * jnp.concatenate(ks_rows, axis=0)[:, :, None]
                    v = v * jnp.concatenate(vs_rows, axis=0)[:, :, None]
                v = jnp.where(fetched, v, 0.0)
                kT = jnp.swapaxes(k, 0, 1)  # [kvH, PP*bs, D]
                vT = jnp.swapaxes(v, 0, 1)

                scores = jax.lax.dot_general(
                    qf, kT,
                    (((2,), (2,)), ((0,), (0,))),
                    preferred_element_type=jnp.float32,
                )  # [kvH, TQ*G, PP*bs]
                elem = jax.lax.broadcasted_iota(
                    jnp.int32, (1, 1, PP * bs), 2
                )
                key_pos = f * PP * bs + elem
                mask = (
                    (key_pos <= q_pos) & (key_pos < kv) & row_ok
                )
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
                    preferred_element_type=jnp.float32,
                )
                return m_new, l_new, acc * corr[..., None] + pv

            init = (
                jnp.full((kvH, TQ * G), NEG_INF, jnp.float32),
                jnp.zeros((kvH, TQ * G), jnp.float32),
                jnp.zeros((kvH, TQ * G, D), jnp.float32),
            )
            m, l, acc = jax.lax.fori_loop(lo_f, hi_f, fold, init)
            out = jnp.where(
                l[..., None] > 0, acc / jnp.maximum(l[..., None], 1e-30), 0.0
            )
            # [kvH, TQ*G, D] -> [TQ, H, D]
            out = jnp.transpose(out.reshape(kvH, TQ, G, D), (1, 0, 2, 3))
            o_tile[...] = out.reshape(TQ, H, D).astype(o_tile.dtype)

            rem = jnp.minimum(ql - tok0, TQ)  # valid rows in this tile

            @pl.when(rem >= TQ)
            def _full_tile():
                cp = pltpu.make_async_copy(
                    o_tile, o_hbm.at[pl.ds(row0, TQ)], o_sem
                )
                cp.start()
                cp.wait()

            @pl.when(rem < TQ)
            def _tail_rows():
                def row_out(r, c):
                    cp = pltpu.make_async_copy(
                        o_tile.at[pl.ds(r, 1)],
                        o_hbm.at[pl.ds(row0 + r, 1)],
                        o_sem,
                    )
                    cp.start()
                    cp.wait()
                    return c

                jax.lax.fori_loop(0, rem, row_out, 0)

            return 0

        jax.lax.fori_loop(0, ntiles, tile_body, 0)


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
    same contract as the jnp twin). ``q_tile`` trades tail padding
    against per-tile fixed cost; 8 keeps a decode span to one row copy
    while a 256-token quantum still runs 32-row folds.

    With ``k_scales``/``v_scales`` the caches are int8 and pages
    dequantize in-register (docs/architecture/kv_quant.md): the page DMA
    ring moves half the bytes, the scale arrays (a few KB) sit whole in
    VMEM, and the compiled program count is unchanged — quantization
    only changes dtypes inside the existing budget-ladder grid."""
    T, H, D = q.shape
    S = block_tables.shape[0]
    kvH = k_cache.shape[1]
    assert diffusion_block == 1 or not window, "no window under a block mask"
    TQ = min(q_tile, max(T, 1))
    quantized = k_scales is not None
    kp = k_cache.reshape(-1, block_size * kvH, D)
    vp = v_cache.reshape(-1, block_size * kvH, D)
    # Tail pad: the last tile of a span ending near row T-1 reads TQ rows
    # from its dynamic offset; padding keeps every read in bounds without
    # aligning spans. The pad rows are never written back.
    qpad = jnp.pad(q, ((0, TQ), (0, 0), (0, 0)))

    vmem = pltpu.MemorySpace.VMEM
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(S,),
        in_specs=[
            pl.BlockSpec(memory_space=MEMORY_SPACE_ANY),
            pl.BlockSpec(memory_space=MEMORY_SPACE_ANY),
            pl.BlockSpec(memory_space=MEMORY_SPACE_ANY),
        ]
        + (
            # Per-block scales ride whole in VMEM: the kernel loads each
            # page's [kvH] row at a dynamic offset during the fold.
            [
                pl.BlockSpec(memory_space=vmem),
                pl.BlockSpec(memory_space=vmem),
            ]
            if quantized
            else []
        ),
        out_specs=pl.BlockSpec(memory_space=MEMORY_SPACE_ANY),
        scratch_shapes=[
            pltpu.VMEM((TQ, H, D), q.dtype),
            pltpu.VMEM((TQ, H, D), q.dtype),
            pltpu.VMEM(
                (RAGGED_NBUF, RAGGED_PP * block_size * kvH, D), k_cache.dtype
            ),
            pltpu.VMEM(
                (RAGGED_NBUF, RAGGED_PP * block_size * kvH, D), v_cache.dtype
            ),
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA((RAGGED_NBUF, RAGGED_PP)),
            pltpu.SemaphoreType.DMA((RAGGED_NBUF, RAGGED_PP)),
        ],
    )
    kernel = functools.partial(
        _ragged_kernel, block_size=block_size, num_kv_heads=kvH,
        q_tile_rows=TQ, window=window, quantized=quantized,
        diffusion_block=diffusion_block,
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
        out_shape=jax.ShapeDtypeStruct((T + TQ, H, D), q.dtype),
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
