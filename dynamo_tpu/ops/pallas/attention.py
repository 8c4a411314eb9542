"""The paged decode-attention Pallas kernel (kv_sp's striped scan) and the
cache-layout helpers the ragged kernel (ragged_attention.py) shares.

Same math as the jnp reference (ops/attention.py — the test oracle); the
kernels add what XLA can't express over a paged cache:
- each sequence loops only over ITS OWN blocks (``cdiv(context_len, bs)``
  trip count) instead of scanning the full ``max_blocks`` table;
- KV pages stream HBM→VMEM with double-buffered async DMA (linear copies
  at full bandwidth, not XLA gathers);
- score/PV matmuls batch over kv heads with the query-group dim folded
  into rows, keeping the MXU shapes sane for GQA.

Cache-layout contract (Mosaic DMA constraints drove this):
- logical cache stays ``[num_slots, kvH, D]`` (ops/attention.py contract);
- the kernels view it as pages ``[num_blocks, bs*kvH, D]`` — a free
  contiguous reshape whose trailing 2D ``(bs*kvH, D)`` tiles exactly on
  (sublane, 128-lane) boundaries, which page slicing for DMA requires;
- therefore ``D % 128 == 0`` inside the kernel. Models with smaller head
  dims (Llama-3.2-1B: D=64) run with lane-PADDED caches: the engine
  allocates ``[num_slots, kvH, 128]``, K/V scatter zero-pads, and the
  padding is mathematically transparent to attention (zero lanes add
  nothing to scores or outputs). ``pallas_supported()`` gates the path;
  unsupported shapes fall back to the jnp reference.
- inside the kernel, a ring slot's pages are loaded as their 2-D tile,
  cast to f32 and the VALUE reshaped to ``[rows, kvH, D]``
  (``heads_view``). Re-viewing the packed REF instead compiles for any
  ``kvH`` but reads the wrong rows on the chip unless ``kvH`` fills a
  sublane tile (8): exact at the 1B/8B's 8 kv heads, wrong on a tp=4
  shard's 2 (found on the v5e by chip_smoke.py, PR 22; interpret mode
  cannot show it).

Reference provenance: the reference delegates paged attention to
vLLM/FlashAttention CUDA kernels (SURVEY §2 'Native components' #3 makes a
TPU-native kernel our job); blockwise online softmax per the
ragged-paged-attention recipe in PAPERS.md.

On CPU backends (tests, virtual mesh) the kernels run in Pallas interpret
mode — same code path, no Mosaic compile.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

MEMORY_SPACE_ANY = pltpu.MemorySpace.ANY

NEG_INF = -1e30
LANE = 128


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def pallas_supported(block_size: int, kvH: int, D: int, dtype) -> bool:
    """Shapes the compiled kernels can handle. Interpret mode (non-TPU)
    has no tiling constraints but keeps the same gate so tests cover the
    production envelope."""
    # Min sublane tile per dtype width: f32 8, bf16 16, int8 32 (the
    # quantized-KV cache dtype — docs/architecture/kv_quant.md).
    sublane = {1: 32, 2: 16}.get(jnp.dtype(dtype).itemsize, 8)
    return D % LANE == 0 and (block_size * kvH) % sublane == 0


def heads_view(buf, slot, rows: int, kvH: int, D: int):
    """Ring slot ``slot`` of a page buffer ``[NBUF, rows*kvH, D]`` as f32
    ``[rows, kvH, D]`` — load, cast, THEN reshape (module docstring)."""
    return buf[slot].astype(jnp.float32).reshape(rows, kvH, D)


def cache_head_dim(D: int) -> int:
    """Lane-padded head dim for cache allocation under the Pallas path."""
    return ((D + LANE - 1) // LANE) * LANE


# ---------------------------------------------------------------------------
# Decode: one query token per sequence.
# ---------------------------------------------------------------------------


# DMA ring depth for the decode kernel's KV page stream. Pages are small
# (bs*kvH x D ~= 32 KB at 1B shapes), so per-copy LATENCY — not bytes —
# bounds the stream at depth 2; a deeper ring keeps ~2*(NBUF-1) copies in
# flight and lets the HBM controller pipeline them (measured 2.4x on the
# in-scan decode step at B=32, ctx 192, 1B shapes).
DECODE_NBUF = 8
# Pages folded into one decode pipeline step (one wait + one attention
# fold per PP pages): amortizes per-iteration fixed costs (loop scalars,
# mask/softmax VPU ops) and widens the score matmuls' key dimension.
# Measured on-chip at 1B/B=32/ctx192 (us per layer-call):
# PP=1 -> 160, PP=2 -> 112, PP=4 -> 92, PP=8 -> 78. Short-context lanes
# waste at most one PP-wide (masked) fold, which is noise at these sizes.
DECODE_PP = 8


def _decode_kernel(
    # scalar prefetch
    block_tables_ref,  # [B, max_blocks] SMEM (LOCAL stripe when strided)
    context_lens_ref,  # [B] SMEM
    page_off_ref,      # [1] SMEM — this shard's logical-page residue
    # inputs
    q_ref,             # [1, H, D] VMEM (this program's sequence)
    k_hbm,             # [num_blocks, bs*kvH, D] HBM pages
    v_hbm,
    # outputs
    o_ref,             # [1, H, D] VMEM (+ m_ref/l_ref [1, H] with stats)
    # scratch (trailing; m/l outputs spliced before when with_stats)
    *refs,
    block_size: int,
    num_kv_heads: int,
    window: int = 0,
    page_stride: int = 1,
    with_stats: bool = False,
):
    """Per-lane grid programs; DECODE_PP pages per pipeline step: each
    slot holds PP pages fetched by independent DMAs, and the body computes
    one [PP*bs]-wide attention fold — dividing per-iteration fixed costs
    (loop scalar work, mask/softmax VPU ops) by PP and widening the score
    matmuls' key dimension (see the DECODE_PP ladder above). The DMA ring
    still spans grid programs (scratch/semaphores persist across TPU grid
    steps), with a uniform padded trip count so the flat ring position is
    b*nsteps + i.

    ``page_stride > 1``: kv_sp striped-scan mode. The table is this sp
    shard's COMPACTED stripe (column j = local page id of logical page
    off + j*stride); the kernel scans only those pages, computing key
    positions from the logical index — FLOPs and DMA partition sp-ways.
    ``with_stats`` additionally emits the online-softmax (m, l) per head
    so the caller can logsumexp-merge shards."""
    if with_stats:
        m_ref, l_ref = refs[0], refs[1]
        k_buf, v_buf, k_sem, v_sem = refs[2:]
    else:
        k_buf, v_buf, k_sem, v_sem = refs
    b = pl.program_id(0)
    B = pl.num_programs(0)
    ctx = context_lens_ref[b]
    off = page_off_ref[0]

    H, D = q_ref.shape[1], q_ref.shape[2]
    kvH = num_kv_heads
    G = H // kvH
    bs = block_size
    scale = 1.0 / (D**0.5)
    NBUF = DECODE_NBUF
    PP = DECODE_PP

    def local_pages(c):
        """This shard's page count for a lane: local indices j with
        off + j*stride < cdiv(c, bs)."""
        n = pl.cdiv(c, bs)
        if page_stride == 1:
            return n
        return jnp.maximum(
            (n - off + page_stride - 1) // page_stride, 0
        )

    nb = local_pages(ctx)              # real (local) pages this lane

    def start_page(c):
        """First local page this lane must scan, aligned DOWN to PP so the
        PP-wide folds stay uniform: with a sliding window, pages wholly
        behind it are never fetched or scored — windowed decode cost is
        O(window), not O(ctx)."""
        if not window:
            return jnp.int32(0)
        slog = jnp.maximum(c - window, 0) // bs
        s = jnp.maximum(
            (slog - off + page_stride - 1) // page_stride, 0
        ) if page_stride > 1 else slog
        return s // PP * PP

    s0 = start_page(ctx)
    # Uniform per-lane step count across the batch.
    def lane_steps(c):
        return pl.cdiv(
            jnp.maximum(local_pages(c) - start_page(c), 0), PP
        )

    nsteps_g = lane_steps(context_lens_ref[0])
    for i in range(1, B):
        nsteps_g = jnp.maximum(nsteps_g, lane_steps(context_lens_ref[i]))
    total = B * nsteps_g

    # [H, D] -> [kvH, G, D], queries pre-scaled in f32. (Measured: f32
    # loads + f32 dots beat native-bf16 dots here; Mosaic requires dot
    # batch dims at EQUAL operand positions, hence the head-major swaps.)
    q3 = (q_ref[0].astype(jnp.float32) * scale).reshape(kvH, G, D)

    def issue(pos):
        """Issue the K/V DMAs for flat position pos."""
        lane = jnp.minimum(pos // jnp.maximum(nsteps_g, 1), B - 1)
        i = pos - lane * nsteps_g
        lane_ctx = context_lens_ref[lane]
        nb_l = local_pages(lane_ctx)
        slot = jax.lax.rem(pos, NBUF)
        for h in range(PP):
            j = start_page(lane_ctx) + i * PP + h

            @pl.when((pos < total) & (j < nb_l))
            def _():
                page = block_tables_ref[lane, j]
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

    @pl.when(b == 0)
    def _():
        jax.lax.fori_loop(0, NBUF - 1, lambda p, _: (issue(p), 0)[1], 0)

    base = b * nsteps_g

    def body(i, carry):
        m, l, acc = carry
        issue(base + i + NBUF - 1)
        slot = jax.lax.rem(base + i, NBUF)

        def compute(carry):
            m, l, acc = carry
            for h in range(PP):
                @pl.when(s0 + i * PP + h < nb)
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
            # Sublane-merge view [PP*bs*kvH, D] -> [PP*bs, kvH, D], then
            # head-major. An unfetched odd-tail half holds GARBAGE (stale
            # or uninitialized VMEM): its probability columns are masked
            # to 0, but 0 * NaN = NaN through the PV matmul — zero V's
            # unfetched rows. (K needs nothing: NaN scores land only in
            # masked columns, which `where` replaces before use.)
            fetched = (
                (s0 + i * PP) * bs
                + jax.lax.broadcasted_iota(jnp.int32, (PP * bs, 1, 1), 0)
            ) < nb * bs
            k = heads_view(k_buf, slot, PP * bs, kvH, D)
            v = heads_view(v_buf, slot, PP * bs, kvH, D)
            v = jnp.where(fetched, v, 0.0)
            kT = jnp.swapaxes(k, 0, 1)  # [kvH, PP*bs, D]
            vT = jnp.swapaxes(v, 0, 1)

            # [kvH, G, D] x [kvH, PP*bs, D] -> [kvH, G, PP*bs]
            scores = jax.lax.dot_general(
                q3, kT,
                (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32,
            )
            elem = jax.lax.broadcasted_iota(jnp.int32, (1, 1, PP * bs), 2)
            if page_stride == 1:
                key_pos = (s0 + i * PP) * bs + elem
            else:
                # Logical position of a strided page's keys.
                key_pos = (
                    off + (s0 + i * PP + elem // bs) * page_stride
                ) * bs + elem % bs
            mask = key_pos < ctx  # also masks an unfetched odd tail page
            if window:
                # Sliding window: the (single) query position is ctx-1.
                mask = mask & (key_pos >= ctx - window)
            scores = jnp.where(mask, scores, NEG_INF)

            m_new = jnp.maximum(m, scores.max(axis=-1))
            corr = jnp.exp(m - m_new)
            p = jnp.where(mask, jnp.exp(scores - m_new[..., None]), 0.0)
            l_new = l * corr + p.sum(axis=-1)
            # [kvH, G, PP*bs] x [kvH, PP*bs, D] -> [kvH, G, D]
            pv = jax.lax.dot_general(
                p, vT,
                (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32,
            )
            return m_new, l_new, acc * corr[..., None] + pv

        return jax.lax.cond(s0 + i * PP < nb, compute, lambda c: c, carry)

    init = (
        jnp.full((kvH, G), NEG_INF, jnp.float32),
        jnp.zeros((kvH, G), jnp.float32),
        jnp.zeros((kvH, G, D), jnp.float32),
    )
    m, l, acc = jax.lax.fori_loop(0, nsteps_g, body, init)
    out = jnp.where(
        l[..., None] > 0, acc / jnp.maximum(l[..., None], 1e-30), 0.0
    )
    o_ref[0] = out.reshape(H, D).astype(o_ref.dtype)
    if with_stats:
        # Stats land as [B, 1, H] (block (1, 1, H)): a 2-D [B, H] output
        # with block (1, H) violates Mosaic's second-to-minor tiling rule.
        m_ref[0, 0] = m.reshape(H)
        l_ref[0, 0] = l.reshape(H)


@functools.partial(
    jax.jit,
    static_argnames=("block_size", "window", "page_stride", "with_stats"),
)
def paged_decode_attention_pallas(
    q: jnp.ndarray,             # [B, H, D]
    k_cache: jnp.ndarray,       # [num_slots, kvH, D]
    v_cache: jnp.ndarray,
    block_tables: jnp.ndarray,  # [B, max_blocks] int32
    context_lens: jnp.ndarray,  # [B] int32 (0 = inactive slot -> zeros)
    block_size: int,
    window: int = 0,
    page_offset: jnp.ndarray | None = None,  # [1] — kv_sp shard residue
    page_stride: int = 1,
    with_stats: bool = False,
):
    """Returns out [B, H, D]; with ``with_stats`` returns (out, m, l) with
    out in float32 and m/l [B, H] — the kv_sp per-shard call whose stats
    the caller merges across shards (ops/attention.py AttnDispatch)."""
    B, H, D = q.shape
    kvH = k_cache.shape[1]
    kp = k_cache.reshape(-1, block_size * kvH, D)
    vp = v_cache.reshape(-1, block_size * kvH, D)
    if page_offset is None:
        page_offset = jnp.zeros((1,), jnp.int32)

    qspec = pl.BlockSpec(
        (1, H, D), lambda b, *_: (b, 0, 0), memory_space=pltpu.VMEM
    )
    hspec = pl.BlockSpec(
        (1, 1, H), lambda b, *_: (b, 0, 0), memory_space=pltpu.VMEM
    )
    out_shape = jax.ShapeDtypeStruct(
        (B, H, D), jnp.float32 if with_stats else q.dtype
    )
    out_specs = qspec
    if with_stats:
        out_shape = (
            out_shape,
            jax.ShapeDtypeStruct((B, 1, H), jnp.float32),
            jax.ShapeDtypeStruct((B, 1, H), jnp.float32),
        )
        out_specs = (qspec, hspec, hspec)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=[
            qspec,
            pl.BlockSpec(memory_space=MEMORY_SPACE_ANY),
            pl.BlockSpec(memory_space=MEMORY_SPACE_ANY),
        ],
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM(
                (DECODE_NBUF, DECODE_PP * block_size * kvH, D), k_cache.dtype
            ),
            pltpu.VMEM(
                (DECODE_NBUF, DECODE_PP * block_size * kvH, D), v_cache.dtype
            ),
            pltpu.SemaphoreType.DMA((DECODE_NBUF, DECODE_PP)),
            pltpu.SemaphoreType.DMA((DECODE_NBUF, DECODE_PP)),
        ],
    )
    kernel = functools.partial(
        _decode_kernel, block_size=block_size, num_kv_heads=kvH,
        window=window, page_stride=page_stride, with_stats=with_stats,
    )
    res = pl.pallas_call(
        kernel,
        out_shape=out_shape,
        grid_spec=grid_spec,
        interpret=_interpret(),
    )(
        block_tables.astype(jnp.int32),
        context_lens.astype(jnp.int32),
        page_offset.astype(jnp.int32),
        q,
        kp,
        vp,
    )
    if with_stats:
        o, m, l = res
        return o, m[:, 0], l[:, 0]
    return res
