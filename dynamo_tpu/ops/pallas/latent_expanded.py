"""Latent attention in the EXPANDED form over a cache that holds the latent
once — the long spans' body beside ops/pallas/ragged_attention.py.

A latent layer's cache entry is ``[latent c | rotated k_pe | pad]`` (one
array, one cached head; docs/architecture/unified_step.md "A latent cache
held once"). Attention over it has two forms of the same mathematics,
``(W_uk^T q) . c = q . (W_uk c)``:

- ABSORBED (models/llama.py ``_qkv_mla``, the ragged kernel): every query
  is projected INTO the latent space, ``QK^T`` runs over ``rank + rope``
  columns and ``PV`` over ``rank``: ``2 * ((rank + rope) + rank)`` FLOP a
  (query, key, head), 2,176 at DeepSeek-V2's widths;
- EXPANDED (the published form, this file): a cached position's key and
  value are projected UP from the latent first, ``2 * rank * (nope + v)``
  FLOP a (key, head) (262,144), then a pair costs ``2 * ((nope + rope) +
  v)`` (640).

A key seen by n rows of one span costs ``640 + 262,144 / n`` a pair
expanded, so a LONG span takes this form and every other span keeps the
absorbed one (``expanded_spans``: the arithmetic on the model's own widths
times ONE measured margin, ``EXPANDED_MARGIN``). Which spans are long is
data, so one compiled step holds both bodies: the ragged kernel is called
with the long spans' ``q_len`` zeroed, this one with theirs alone.

The two forms want opposite tilings. The absorbed long tile holds 16 rows x
all heads and streams the visible keys ``q_len / 16`` times; here a grid
step holds ``EXPANDED_HEADS`` query heads' ``w_uk`` / ``w_uv`` and ALL of
the span's rows for those heads (tiles of ``EXPANDED_TILE`` rows: the rows
the up-projection amortises over), and streams the one latent array ``H /
EXPANDED_HEADS`` times through a ring of folds as the ragged kernel's
(``ring_shape``, the block table in SMEM; 16k keys x 1,280 B x 8 steps is
a fifth of a millisecond: bytes do not bind). A fold, a head::

    K = [c @ w_uk[h]^T * scale | k_pe * scale]      [keys, nope + rope']
    V^T = w_uv[h] @ c^T                             [v, keys]
    for each chunk of EXPANDED_CHUNK rows that can see the fold:
        S^T = K @ q[h]^T, causal mask               [keys, rows]  (f32)
        online softmax over keys (down the sublanes: elementwise)
        acc^T += V^T @ P^T                          [v, rows]

Operands of every product are the model's dtype (bf16 served), sums, the
scores and the softmax f32 (``exp2`` in units of log 2, the scale folded
into the projected keys ONCE, YaRN's ``mscale^2`` in it); scores are held
transposed as the ragged kernel's long tile holds them. No expanded key or
value ever reaches HBM.

What the chip said of the body (``tools/ragged_kernel_bench.py --shapes dsv2
--sweep forms``, a 977-row quantum behind 8,192 keys, a layer's call of H
128, TPU v5e; my chip runs, PR 53; the absorbed kernel takes 21.3 ms there,
19.8 of them inside the kernel):

- **Independent chains in ONE loop body are what the time goes by.** A
  head's chunk is product -> softmax -> product, each waiting for the one
  before, so alone it leaves the MXU idle under the VPU and back. One
  head a body and 256-row chunks: 14.9 ms a call; two heads a body 13.0,
  four 12.1 (``EXPANDED_PAIR``); four heads and 512-row chunks 11.0
  (``EXPANDED_CHUNK``; 1,024-row chunks 12.0, and a 512-row span then
  pays for 1,024); eight heads a body 10.0 beside 10.2. 128-row chunks
  22.1.
- Read and dropped: a body without the mask for the folds every row sees
  whole (13.1 beside 13.0: the mask is not what binds, and it is a second
  traced body); the softmax's sum as a row of ones under the values, on
  the MXU (11.5 beside 11.5).
- **The glue around the kernel was a fifth of a call.** A first version
  gathered each long span's rows to a chunk-aligned start of an ``[H, rows,
  256]`` copy of q and scattered the output back: 1.9-2.3 ms of XLA
  gathers, transposes and pads beside a 9.1 ms kernel. Now q and the output
  stay the flat batch's, viewed as tiles of 16 heads ``[rows, H / 16, 16,
  width]`` (rows leading, so a DMA starts at ANY row), and the kernel
  splits a staged piece's heads itself: two bf16 heads of a row are one
  32-bit word, a shift or a mask is the cast (as the ragged kernel reads K
  by word). What XLA still runs is the pad of q to 256 columns and the
  zeroing of rows no long span owns: 0.45 ms. A piece is written whole, so
  a span's last piece runs onto LATER rows of the batch; a later long
  span writes its own rows after it (the copies before a tile's writes are
  waited for first), and anyone else's rows there are the absorbed
  kernel's to answer (``_mla_out`` merges by the span mask).

The margin, measured the same way: ONE span alone in the dispatch through
each form, microseconds a layer's call, absorbed | expanded (the absorbed
call carries 1.3 ms of its own glue, the pad of q and the zeroing, which
the step pays anyway for its decode lanes; the expanded call 0.45 ms and
~0.5 ms of a kernel that walks eight steps whatever the span)::

    rows      prefix 0       2,048          8,192           16,384
    128     1,606 | 1,233  2,241 | 2,432   3,980 |  6,008   6,305 | 10,785
    256     1,723 | 1,296  2,944 | 2,479   6,433 |  6,071  11,080 | 10,848
    384     1,984 | 1,498  3,721 | 2,691   8,955 |  6,276  15,935 | 11,053
    512     2,168 | 1,546  4,494 | 2,747  11,477 |  6,329  20,786 | 11,117
    977     3,302 | 2,267  7,807 | 4,251  21,341 | 10,179  39,375 | 18,095

Less the 1.3 ms that the absorbed call costs whether or not the span is in
it, the span itself breaks even at ~350 rows behind 8k-16k keys, ~450
behind 2k and ~1,000 from position 0, where the arithmetic alone says 171
and 341: ``EXPANDED_MARGIN`` 2.0 (K = 683: past 341 rows behind a long
prefix, 683 from position 0). The decode lanes, which stay absorbed: 47
lanes at contexts 2.2k-16.8k take 3.64 ms a call where their bytes take
0.78 (``--sweep parts``), 1.3 ms of it the glue.

The jnp twin (ops/attention.py) stays absorbed; interpret mode runs this
kernel's code path on the CPU (the interpreter stores through no bitcast
ref, so there the output's heads are joined by plain stores).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dynamo_tpu.ops.pallas import ragged_attention as ragged
from dynamo_tpu.ops.pallas.ragged_attention import (
    LOG2E,
    MEMORY_SPACE_ANY,
    NEG_INF,
    _cdiv,
    _div,
    ring_shape,
)

# What the expanded form's saving must exceed the up-projection's cost by
# before a span takes it (module docstring has the table that set it).
EXPANDED_MARGIN = 2.0
# Query heads a grid step: a sublane tile of bf16, so that a step's heads
# are one tile of the flat batch's ``[rows, H / 16, 16, width]`` view and q
# and the output move by DMA at ANY row offset with no relayout in HBM.
EXPANDED_HEADS = 16
# Rows a chunk of scores, rows resident a tile, rows a staged piece of q
# or of the output.
EXPANDED_CHUNK = 512
EXPANDED_TILE = 1024
EXPANDED_PIECE = 128
# Heads whose chunks share ONE loop body: independent chains, so that one
# head's softmax (the VPU) runs under another's products (the MXU).
EXPANDED_PAIR = 4
# Scoped VMEM: 16 heads x 1,024 rows of q (8 MiB) and of the f32
# accumulator (8 MiB), the heads' w_uk / w_uv (4 MiB, twice while the next
# step's arrive), a body's four score arrays; over the ragged kernel's 32.
EXPANDED_VMEM_LIMIT = 56 * 1024 * 1024


def expanded_k(cfg, rows: int, heads: int | None = None) -> int:
    """``K`` of ``expanded_spans`` for a latent model's widths at a rung of
    ``rows`` rows and ``heads`` query heads a chip (the model's, unless a
    mesh shards them), or 0 where no span of the rung can take the
    expanded form: the absorbed form costs no more a pair, a compiled
    kernel's widths are no lane tiles or its heads no sublane tiles, or the
    rung is too short for any prefix to pass the rule (``2 * rows <= K``:
    that rung compiles the program it had). ``K = 2 * up / (absorbed - expanded) * EXPANDED_MARGIN``, rounded
    up: a span from position 0 passes at ``K`` rows, one behind a long
    prefix at ``K / 2``."""
    rank, rope = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    nope, v_dim = cfg.qk_nope_head_dim, cfg.v_head_dim
    saved = 2 * ((rank + rope) + rank) - 2 * ((nope + rope) + v_dim)
    if not rank or saved <= 0:
        return 0
    heads = cfg.num_heads if heads is None else heads
    if not ragged._interpret() and (
        rank % 128 or nope % 128 or v_dim % 128 or heads % EXPANDED_HEADS
    ):
        return 0
    k = math.ceil(2 * (2 * rank * (nope + v_dim)) * EXPANDED_MARGIN / saved)
    return k if 2 * rows > k else 0


def expanded_spans(q_len, kv_len, k: int):
    """Which spans take the expanded form: ``pairs * (absorbed - expanded
    FLOPs a pair) > visible keys * up-projection FLOPs * margin``, with
    ``pairs = n * (2 * kv - n + 1) / 2`` of a span of ``n`` rows ending at
    ``kv``, as ``kv * (2n - K) > n * (n - 1)`` in whole numbers. ONE
    function for the program (jnp int32, traced) and the host's count
    (numpy): operators both array kinds share, no overflow below 46,340
    rows a span."""
    d = 2 * q_len - k
    return (d > 0) & (kv_len > (q_len * (q_len - 1)) // d.clip(1))


def _expanded_kernel(
    # scalar prefetch
    tables_ref,    # [S, max_blocks] SMEM
    q_start_ref,   # [S] prefix length
    q_len_ref,     # [S] span rows (0: not this kernel's)
    row_start_ref,  # [S] the span's first row in the flat batch
    # inputs
    q_hbm,         # [T + RS, H, QW] queries [q_nope | q_pe | 0] (ANY)
    k_hbm,         # [num_blocks, bs, Dc] pages of the one array (ANY)
    wuk_ref,       # [hb, nope, rank] this step's heads (VMEM)
    wuv_ref,       # [hb, v, rank]
    # output
    o_hbm,         # [T + RS, H, v] (ANY)
    # scratch
    q_st,          # [2, RS, hb, QW] a piece of q as the batch holds it
    q_buf,         # [hb, TQ, QW] the tile's q by head
    kh_buf,        # [PAIR, KEYS, QW] a head's expanded keys of a fold
    vh_buf,        # [PAIR, v, KEYS] and its values, transposed
    acc,           # [hb, NCH, v, RC] f32
    m_s,           # [hb, NCH, 1, RC] f32 running max
    l_s,           # [hb, NCH, 8, RC] f32 running sum, sublane partials
    o_st,          # [2, RS, hb, v] a piece of the output as the batch holds it
    k_buf,         # [NBUF, KEYS, Dc]
    q_sem, o_sem, k_sem,
    st,            # SMEM [1]: output copies started
    *,
    block_size: int,
    rank: int,
    scale: float,
    interpret: bool,
):
    S = q_len_ref.shape[0]
    hb, TQ, QW = q_buf.shape
    RS = q_st.shape[1]
    NCH, dv, RC = acc.shape[1:]
    NBUF, KEYS, _ = k_buf.shape
    PAIR = kh_buf.shape[0]
    nope = wuk_ref.shape[1]
    bs = block_size
    PP = KEYS // bs
    cd = q_buf.dtype
    f32 = jnp.float32
    post = scale * LOG2E          # scores in units of log 2, through exp2
    # this step's heads: one sublane tile of the batch's [H, width] faces
    head_tile = pl.ds(pl.multiple_of(pl.program_id(0) * hb, hb), hb)
    nt = (((1,), (1,)), ((), ()))  # A @ B^T
    nn = (((1,), (0,)), ((), ()))
    key_i = jax.lax.broadcasted_iota(jnp.int32, (KEYS, 1), 0)
    row_i = jax.lax.broadcasted_iota(jnp.int32, (1, RC), 1)
    # Two bf16 heads of a row are ONE 32-bit word of the staged piece (head
    # 2i low, 2i + 1 high: Mosaic strides no packed rows), and a bf16 is the
    # high half of its f32, so a shift or a mask IS the cast both ways.
    by_word = cd == jnp.bfloat16 and hb % 2 == 0
    hi = jnp.uint32(0xFFFF0000)

    def by_sublane(x):
        """``[KEYS, RC]`` as the vregs it lies in: a reduction over axis 0
        is then elementwise across vregs."""
        return x.reshape(KEYS // 8, 8, RC)

    def each(n, body):
        """``body(i)`` for i < n, traced once."""
        jax.lax.fori_loop(0, n, lambda i, c: (body(i), c)[1], 0)

    def split_heads(slot, rows):
        """A staged piece ``[RS, hb, QW]`` into ``q_buf[:, rows]`` by head."""
        if not by_word:
            def one(j):
                q_buf[j, rows, :] = q_st[slot, :, j, :]
            return each(hb, one)
        words = q_st.bitcast(jnp.uint32)            # [2, RS, hb / 2, QW]

        def pair(i):
            w = words[slot, :, i, :]
            q_buf[2 * i, rows, :] = pltpu.bitcast(w << 16, f32).astype(cd)
            q_buf[2 * i + 1, rows, :] = pltpu.bitcast(w & hi, f32).astype(cd)

        each(hb // 2, pair)

    def join_heads(slot, piece_of):
        """``piece_of(j)`` (``[RS, v]`` f32 of head j) into the staged
        output piece ``[RS, hb, v]``."""
        if not by_word or interpret:  # the interpreter stores no bitcast ref
            def one(j):
                o_st[slot, :, j, :] = piece_of(j).astype(cd)
            return each(hb, one)
        words = o_st.bitcast(jnp.uint32)            # [2, RS, hb / 2, v]

        def pair(i):
            lo, up = (
                pltpu.bitcast(piece_of(j).astype(cd).astype(f32), jnp.uint32)
                for j in (2 * i, 2 * i + 1)
            )
            words[slot, :, i, :] = (lo >> 16) | (up & hi)

        each(hb // 2, pair)

    def out_copy(slot, row):
        return pltpu.make_async_copy(
            o_st.at[slot], o_hbm.at[pl.ds(row, RS), head_tile], o_sem.at[slot]
        )

    def drain():
        """Wait for the output copies in flight (two slots)."""
        for slot in range(2):
            @pl.when(st[0] > slot)
            def _():
                out_copy(slot, 0).wait()
        st[0] = 0

    def tile(s, t):
        """Rows ``[t * TQ, (t + 1) * TQ)`` of span ``s`` for this step's
        heads: the rows resident, the visible keys streamed once."""
        q0 = q_start_ref[s] + t * TQ
        ql = jnp.minimum(q_len_ref[s] - t * TQ, TQ)
        r0 = row_start_ref[s] + t * TQ
        nch = _cdiv(ql, RC)
        npc = _cdiv(ql, RS)
        nb = _cdiv(q0 + ql, bs)        # pages the tile's last row sees
        nf = _cdiv(nb, PP)

        def q_in(pi):
            slot = jax.lax.rem(pi, 2)
            return pltpu.make_async_copy(
                q_hbm.at[pl.ds(r0 + pi * RS, RS), head_tile], q_st.at[slot],
                q_sem.at[slot],
            )

        def fetch(f):
            """Issue fold ``f``: all PP pages, the tail's clamped to the
            last page the tile sees (one size, one wait)."""
            slot = jax.lax.rem(f, NBUF)

            def issue(h, c):
                page = tables_ref[s, jnp.minimum(f * PP + h, nb - 1)]
                pltpu.make_async_copy(
                    k_hbm.at[page], k_buf.at[slot, pl.ds(h * bs, bs)],
                    k_sem.at[slot],
                ).start()
                return c

            jax.lax.fori_loop(0, PP, issue, 0, unroll=True)

        q_in(0).start()
        for i in range(NBUF - 1):
            @pl.when(i < nf)
            def _():
                fetch(jnp.int32(i))

        def init(ci, c):
            acc[:, ci] = jnp.zeros((hb, dv, RC), f32)
            m_s[:, ci] = jnp.full((hb, 1, RC), NEG_INF, f32)
            l_s[:, ci] = jnp.zeros((hb, 8, RC), f32)
            return c

        jax.lax.fori_loop(0, nch, init, 0)

        def piece_in(pi, c):
            @pl.when(pi + 1 < npc)
            def _():
                q_in(pi + 1).start()

            q_in(pi).wait()
            # A piece past the span's end holds the next rows of the batch
            # (or the pad): masked below, like what a chunk's rows past the
            # last piece still hold of an earlier tile.
            split_heads(
                jax.lax.rem(pi, 2), pl.ds(pl.multiple_of(pi * RS, RS), RS))
            return c

        jax.lax.fori_loop(0, npc, piece_in, 0)

        def fold(f, c):
            @pl.when(f + (NBUF - 1) < nf)
            def _():
                fetch(f + (NBUF - 1))

            slot = jax.lax.rem(f, NBUF)
            pltpu.make_async_copy(
                k_buf.at[slot], k_buf.at[slot], k_sem.at[slot]
            ).wait()
            lat = k_buf[slot, :, :rank]                   # [KEYS, rank]
            # the rotated tail (and the cache's zero pad), scaled once a fold
            pe = (k_buf[slot, :, rank:].astype(f32) * post).astype(cd)
            for u in range(PAIR):
                kh_buf[u, :, nope:] = pe
            key_pos = f * KEYS + key_i
            # the first chunk whose last row reaches the fold's first key
            ahead = f * KEYS - q0
            ci_lo = jnp.where(ahead > 0, _div(ahead, RC), 0)

            def heads(jp):
                """PAIR heads' chunks of this fold in ONE body."""
                for u in range(PAIR):
                    j = jp * PAIR + u
                    kn = jax.lax.dot_general(
                        lat, wuk_ref[j], nt, preferred_element_type=f32)
                    kh_buf[u, :, :nope] = (kn * post).astype(cd)
                    vh_buf[u] = jax.lax.dot_general(
                        wuv_ref[j], lat, nt, preferred_element_type=f32
                    ).astype(cd)

                def chunk(ci, c):
                    r = pl.multiple_of(ci * RC, RC)
                    row = ci * RC + row_i
                    # rows past the span see nothing (and are never read)
                    mask = key_pos <= jnp.where(row < ql, q0 + row, -1)
                    for u in range(PAIR):
                        j = jp * PAIR + u
                        sT = jax.lax.dot_general(
                            kh_buf[u], q_buf[j, pl.ds(r, RC), :], nt,
                            preferred_element_type=f32,
                        )                                  # [KEYS, RC]
                        sT = jnp.where(mask, sT, NEG_INF)
                        m_old = m_s[j, ci]
                        m_new = jnp.maximum(
                            m_old,
                            by_sublane(sT).max(axis=0).max(
                                axis=0, keepdims=True),
                        )
                        corr = jnp.exp2(m_old - m_new)
                        # Fold 0 comes first and every row of the span sees
                        # key 0, so m_new is finite wherever a key is
                        # masked: a masked score is exp2(-1e30 - m) = 0.
                        p = jnp.exp2(sT - m_new)
                        l_s[j, ci] = (
                            l_s[j, ci] * corr + by_sublane(p).sum(axis=0))
                        pv = jax.lax.dot_general(
                            vh_buf[u], p.astype(cd), nn,
                            preferred_element_type=f32,
                        )                                  # [v, RC]
                        acc[j, ci] = acc[j, ci] * corr + pv
                        m_s[j, ci] = m_new
                    return c

                jax.lax.fori_loop(ci_lo, nch, chunk, 0)

            jax.lax.fori_loop(
                0, hb // PAIR, lambda jp, c: (heads(jp), c)[1], 0)
            return c

        jax.lax.fori_loop(0, nf, fold, 0)

        # A piece is written whole: its rows past the span's end land on
        # LATER rows of the batch, whose own span (if it is this kernel's)
        # writes them after this one; so every copy before this tile's is
        # done before its first starts.
        drain()

        def write(ci, c):
            for pc in range(RC // RS):
                @pl.when(ci * RC + pc * RS < ql)
                def _():
                    n = st[0]
                    slot = jax.lax.rem(n, 2)

                    @pl.when(n >= 2)
                    def _():
                        out_copy(slot, 0).wait()

                    def piece_of(j):
                        l = l_s[j, ci, :, pc * RS:(pc + 1) * RS].sum(
                            axis=0, keepdims=True)
                        a = acc[j, ci, :, pc * RS:(pc + 1) * RS]
                        return (a / jnp.maximum(l, 1e-30)).T   # [RS, v]

                    join_heads(slot, piece_of)
                    out_copy(slot, r0 + ci * RC + pc * RS).start()
                    st[0] = n + 1
            return c

        jax.lax.fori_loop(0, nch, write, 0)

    st[0] = 0

    def span(s, c):
        ql = q_len_ref[s]

        @pl.when(ql > 0)
        def _():
            jax.lax.fori_loop(
                0, _cdiv(ql, TQ), lambda t, c: (tile(s, t), c)[1], 0)

        return c

    jax.lax.fori_loop(0, S, span, 0)
    drain()


@functools.partial(jax.jit, static_argnames=("block_size", "scale"))
def ragged_paged_attention_pallas_expanded(
    q: jnp.ndarray,             # [T, H, nope + rope] un-absorbed, rotated tail
    k_cache: jnp.ndarray,       # [num_slots, 1, Dc] the latent held once
    w_uk: jnp.ndarray,          # [H, nope, rank]
    w_uv: jnp.ndarray,          # [H, v, rank]
    block_tables: jnp.ndarray,  # [S, max_blocks] int32
    q_start: jnp.ndarray,       # [S] int32
    q_len: jnp.ndarray,         # [S] int32: the LONG spans' rows, else 0
    row_start: jnp.ndarray,     # [S] int32
    *,
    block_size: int,
    scale: float,
) -> jnp.ndarray:
    """Causal latent attention of the spans with rows in ``q_len``, in the
    expanded form; returns ``[T, H, v]``, the up-projected values, rows of
    no such span ZEROED. ``scale`` is the softmax scale whole (``(nope +
    rope) ** -0.5`` times YaRN's ``mscale ** 2``), applied once, to the
    projected keys. A span's context ends where its rows do: ``kv_len`` is
    ``q_start + q_len``."""
    T, H, qd = q.shape
    Dc = k_cache.shape[-1]
    nope, rank = w_uk.shape[1:]
    dv = w_uv.shape[1]
    assert k_cache.shape[1] == 1 and qd - nope <= Dc - rank
    RS = min(EXPANDED_PIECE, -(-T // 8) * 8)
    RC = min(EXPANDED_CHUNK, -(-T // RS) * RS)
    TQ = min(EXPANDED_TILE, -(-T // RC) * RC)
    assert RC % RS == 0 and TQ % RC == 0, (RS, RC, TQ)
    hb = next(h for h in (EXPANDED_HEADS, 8, 4, 2, 1) if H % h == 0)
    pair = next(n for n in (EXPANDED_PAIR, 2, 1) if hb % n == 0)
    QW = nope + Dc - rank
    q_len = q_len.astype(jnp.int32)
    # The flat batch as it lies, rows leading: a step's hb heads are one
    # sublane tile of it, so its q rows and output rows move by DMA from
    # and to ANY row offset. A piece may run past the batch's last row by
    # less than itself.
    q3 = jnp.pad(q, ((0, RS), (0, 0), (0, QW - qd)))

    pages = k_cache.reshape(-1, block_size, Dc)
    nbuf, pp = ring_shape(block_size * Dc * k_cache.dtype.itemsize, block_size)
    keys = pp * block_size
    any_space = pl.BlockSpec(memory_space=MEMORY_SPACE_ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(H // hb,),
        in_specs=[
            any_space, any_space,
            pl.BlockSpec((hb, nope, rank), lambda g, *_: (g, 0, 0)),
            pl.BlockSpec((hb, dv, rank), lambda g, *_: (g, 0, 0)),
        ],
        out_specs=any_space,
        scratch_shapes=[
            pltpu.VMEM((2, RS, hb, QW), q.dtype),
            pltpu.VMEM((hb, TQ, QW), q.dtype),
            pltpu.VMEM((pair, keys, QW), q.dtype),
            pltpu.VMEM((pair, dv, keys), q.dtype),
            pltpu.VMEM((hb, TQ // RC, dv, RC), jnp.float32),
            pltpu.VMEM((hb, TQ // RC, 1, RC), jnp.float32),
            pltpu.VMEM((hb, TQ // RC, 8, RC), jnp.float32),
            pltpu.VMEM((2, RS, hb, dv), q.dtype),
            pltpu.VMEM((nbuf, keys, Dc), k_cache.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((nbuf,)),
            pltpu.SMEM((1,), jnp.int32),
        ],
    )
    interpret = ragged._interpret()
    kernel = functools.partial(
        _expanded_kernel, block_size=block_size, rank=rank, scale=scale,
        interpret=interpret)
    o3 = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((T + RS, H, dv), q.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=EXPANDED_VMEM_LIMIT,
        ),
        name="ragged_paged_attention_pallas_expanded",
    )(
        block_tables.astype(jnp.int32), q_start.astype(jnp.int32), q_len,
        row_start.astype(jnp.int32), q3, pages, w_uk, w_uv,
    )
    # Rows of no long span hold whatever the buffer held, or a neighbour's
    # overrun: zero them, as the ragged kernel's contract has it.
    t = jnp.arange(T, dtype=jnp.int32)[:, None]
    mine = ((t >= row_start[None]) & (t < (row_start + q_len)[None])).any(axis=1)
    return jnp.where(mine[:, None, None], o3[:T], 0)
