"""The delta-rule state update as Pallas TPU kernels (ops/linear_attention.py
has the mathematics and the XLA twin).

``kda_recurrent`` (``kda_rows``): lanes of one row. One grid step is one
ROW of one sequence: the row's slot of the state table ``[N + 1, H, d,
d]`` is the step's block (scalar-prefetched ``slots``), aliased from input
to output, so a decode lane costs one read and one write of its 2 MiB (H
32, d 128, float32) and nothing else moves. A head's state is ``[d_k,
d_v]`` with ``d_k`` on sublanes; the row's per-``d_k`` vectors (decay, k,
beta*k, q: ``x`` [4H, d]) arrive with ``d`` on lanes and are transposed
once a row, so each is a column that broadcasts along lanes; ``beta * v``
and the output stay rows.

``kda_chunk``: the longer spans in tiles of ``C`` rows, the chunkwise form
(arXiv:2510.26692). For a tile behind a state ``S_0``, with ``b_t`` the
running sum of the log decay within the tile (a channel)::

    A[t, i] = beta_t sum_c k_tc k_ic exp(b_tc - b_ic)        (i <  t)
    P[t, i] =        sum_c q_tc k_ic exp(b_tc - b_ic)        (i <= t)
    (I + A) U = beta V - (beta K exp(b)) S_0
    O   = (Q exp(b)) S_0 + P U
    S_C = Diag(exp(b_C)) S_0 + (K exp(b_C - b))^T U

Everything but the exponentials, the masks and the scaling of ``S_0`` is a
matrix product on the MXU at float32 contract precision. No factor leaves
float32: rows are taken in sub-chunks of ``sub`` rows (``sub x |the log
decay's bound|`` under float32's ~88, a fact of the model); within a
sub-chunk ``exp(b_t - b_i)`` is a product of a factor at most 1 and one at
most ``e^80``, across sub-chunks both factors are taken against the start
of the query row's sub-chunk and are at most 1. ``(I + A)^-1``: the
sub-chunk blocks of the diagonal by ``(I - M)^-1 = (I + M)(I + M^2)(I +
M^4)...`` (``M`` nilpotent: ``log2(sub)`` factors), then pairs of blocks
merged (``[[T1, 0], [-T2 B T1, T2]]``) up to the tile. No loop over rows.

One program, one grid step: a loop over the dispatch's USED tiles (a
scalar-prefetched count, so a dispatch without such a span costs nothing)
reads a tile's rows from the flat batch where they lie (one DMA of ``C``
rows of every head's g, k, beta*k, q, beta*v, the next tile's under this
tile's arithmetic), walks the heads four abreast, and writes the tile's
outputs back to the flat rows. A span's tiles are
consecutive; its state is copied into VMEM at the first (or zeroed where
the span starts the sequence), stays there, and is copied back at the
last: a span reads and writes its state once whatever its length.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: flags a lane or a tile: bit 0 a lane is served (a tile is, where it is
#: among the used ones), bit 1 a tile is its span's first, bit 2 the span
#: starts the sequence (the state starts from zeros, whatever the slot
#: holds), bit 3 a tile is its span's last
ACTIVE, FIRST, FRESH, LAST = 1, 2, 4, 8
#: rows of a chunk tile: the prefill quantum the served cell sends; a
#: longer span is tiles in a row
TILE = 64
#: the arrays a row hands the chunk kernel, stacked by head in this order
G, K, BK, Q, BV = range(5)
HI = jax.lax.Precision.HIGHEST
#: half the log decay a sub-chunk may sum to (``sub_chunk``)
MID = 40.0


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def sub_chunk(lower_bound: float, tile: int) -> int:
    """Rows of a sub-chunk: the largest power of two whose rows' log decay
    sums to ``2 * MID`` at most (``exp`` of it inside float32), the tile at
    most."""
    n = 1
    while 2 * n <= tile and 2 * n * abs(lower_bound) <= 2 * MID:
        n *= 2
    return n


def _kda_kernel(slots_ref, flags_ref, x_ref, bv_ref, s_in_ref, o_ref,
                s_out_ref, *, heads: int):
    del slots_ref
    flag = flags_ref[pl.program_id(0)]
    H = heads

    @pl.when((flag & ACTIVE) != 0)
    def _():
        xt = x_ref[0].T                                   # [d, 4H]
        for h in range(H):
            a, kc, bk, qc = (
                xt[:, n * H + h : n * H + h + 1] for n in range(4)
            )
            # A select, not a product with 0: whatever a slot's last owner
            # left there (a NaN too) ends with the slot's reuse.
            held = s_in_ref[0, h]
            held = jnp.where((flag & FRESH) != 0, jnp.zeros_like(held), held)
            decayed = a * held.astype(jnp.float32)        # [d_k, d_v]
            ks = jnp.sum(bk * decayed, axis=0, keepdims=True)
            new = decayed + kc * (bv_ref[0, h : h + 1, :] - ks)
            s_out_ref[0, h] = new.astype(s_out_ref.dtype)
            o_ref[0, h : h + 1, :] = jnp.sum(qc * new, axis=0, keepdims=True)


def kda_rows(x, bv, state, slots, flags):
    """Advance ``state[slots[r]]`` by the one row of lane ``r``, for every
    served lane.

    ``x`` [R, 4H, d] float32 (decay, k, beta*k, q stacked by head), ``bv``
    [R, H, d] (beta*v), ``state`` [N + 1, H, d, d], ``slots`` [R] (0, the
    trash slot, for a lane not served), ``flags`` [R] (``ACTIVE``,
    ``FRESH``). Returns (o [R, H, d] float32, undefined in lanes not
    served; the state, updated in place). The body is jitted, as
    ``kda_chunk``'s is: a model's layers call with one set of shapes, and
    the kernel is traced once a shape and not once a layer (a start's
    seconds); interpreted or not is part of that cache's key."""
    return _kda_rows(x, bv, state, slots, flags, interpret=_interpret())


@functools.partial(jax.jit, static_argnames="interpret")
def _kda_rows(x, bv, state, slots, flags, *, interpret: bool):
    R, H, d = bv.shape
    row = lambda r, slots, flags: (r, 0, 0)
    slot = lambda r, slots, flags: (slots[r], 0, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(R,),
        in_specs=[
            pl.BlockSpec((1, 4 * H, d), row),
            pl.BlockSpec((1, H, d), row),
            pl.BlockSpec((1, H, d, d), slot),
        ],
        out_specs=[
            pl.BlockSpec((1, H, d), row),
            pl.BlockSpec((1, H, d, d), slot),
        ],
    )
    block = H * d * d * 4
    o, state = pl.pallas_call(
        functools.partial(_kda_kernel, heads=H),
        out_shape=[
            jax.ShapeDtypeStruct((R, H, d), jnp.float32),
            jax.ShapeDtypeStruct(state.shape, state.dtype),
        ],
        grid_spec=grid_spec,
        # operands: slots, flags, x, bv, state -> outputs: o, state
        input_output_aliases={4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=max(32 << 20, 6 * block),
        ),
        name="kda_recurrent",
        interpret=interpret,
    )(
        slots.astype(jnp.int32), flags.astype(jnp.int32),
        x.astype(jnp.float32), bv.astype(jnp.float32), state,
    )
    return o, state


#: heads a stage of the chunk kernel walks abreast (a batch dimension):
#: a head's tile is a chain of some twenty dependent small products, and the
#: MXU takes them in program order, so the chain of one head waits under
#: the other heads' products of the same stage
HEADS_ABREAST = 4


def _dot(a, b, *, nt: bool = False, tn: bool = False):
    """``a @ b`` (``a @ b^T``, ``a^T @ b``) at float32 contract precision."""
    dims = (((0 if tn else 1,), (1 if nt else 0,)), ((), ()))
    return jax.lax.dot_general(
        a, b, dims, precision=HI, preferred_element_type=jnp.float32
    )


def _masks(C: int, sub: int, d: int):
    """The tile's constant masks (``sub`` a power of two): the running-sum
    matrix of a sub-chunk, the two triangles, the identity, the diagonal's
    blocks, what lies below them at each merge, the state's identity."""
    f32 = jnp.float32
    t_i = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    i_i = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    block = lambda rows: (t_i ^ i_i) < rows     # same aligned block of rows
    same = block(sub)
    below, span = [], sub
    while span < C:
        below.append(block(2 * span) & ~block(span))
        span *= 2
    return dict(
        run=jnp.where(same & (i_i <= t_i), 1.0, 0.0).astype(f32),
        upto=i_i <= t_i, before=i_i < t_i, same=same,
        eye=jnp.where(t_i == i_i, 1.0, 0.0).astype(f32), below=below,
        row=jax.lax.broadcasted_iota(jnp.int32, (C, 1), 0),
        eye_d=jax.lax.broadcasted_iota(jnp.int32, (d, d), 0) == (
            jax.lax.broadcasted_iota(jnp.int32, (d, d), 1)),
    )


def _head_tile(x, s0, mask, *, C: int, sub: int):
    """One head's tile: ``x`` maps ``G .. BV`` to its ``[C, d]`` rows (rows
    past the span's end already inert), ``s0`` ``[d_k, d_v]``, ``mask``
    as ``_masks`` makes it. Returns (o [C, d_v], the state behind the
    tile)."""
    f32 = jnp.float32
    d = s0.shape[0]
    nb = C // sub
    # The running log decay within each sub-chunk, then across them.
    lb = _dot(mask["run"], x[G])
    ends = [lb[(m + 1) * sub - 1 : (m + 1) * sub] for m in range(nb)]
    E = [jnp.zeros((1, d), f32)]
    for e in ends:
        E.append(E[-1] + e)                              # E[m]: before m
    over = lambda rows: jnp.concatenate(
        [jnp.broadcast_to(r, (sub, d)) for r in rows], axis=0
    )
    e_start = over(E[:nb])                               # [C, d]
    b = e_start + lb
    # exp(b_t - b_i) within a sub-chunk is reach_t / reach_i, both held
    # inside e^+-40: a factor near e^-80 would lose its low parts on the
    # MXU (a pass's bf16 part below 1e-38 is flushed to zero).
    reach = jnp.exp(lb + MID)
    xi = jnp.concatenate([x[Q] * reach, x[BK] * reach], axis=0)   # [2C, d]
    far = jnp.exp(e_start - MID)                         # reach * far = e^b
    against = _dot(xi * jnp.concatenate([far, far], axis=0), s0)  # [2C, d_v]
    rhs = x[BV] - against[C:]
    # Scores a block row at a time: both factors against the start of the
    # query rows' sub-chunk (the keys of that sub-chunk itself: e^+-40).
    score = []
    for m in range(nb):
        keys = x[K] * jnp.exp(
            jnp.where(mask["row"] < (m + 1) * sub, E[m] - b - MID, 0.0)
        )
        rows = jnp.concatenate(
            [xi[m * sub : (m + 1) * sub],
             xi[C + m * sub : C + (m + 1) * sub]], axis=0,
        )
        score.append(_dot(rows, keys, nt=True))          # [2 sub, C]
    P = jnp.where(
        mask["upto"], jnp.concatenate([s[:sub] for s in score], axis=0), 0.0
    )
    A = jnp.where(
        mask["before"], jnp.concatenate([s[sub:] for s in score], axis=0), 0.0
    )
    # (I + A)^-1: the diagonal's blocks, then pairs of blocks merged.
    power = jnp.where(mask["same"], -A, 0.0)
    inv = mask["eye"] + power
    # inv holds the powers below ``span``; one product a doubling:
    # M^p [inv | M^p] = [M^p inv | M^2p].
    if sub > 2:
        power = _dot(power, power)
    span = 2
    while span < sub:
        last = 2 * span >= sub
        both = _dot(
            power, inv if last else jnp.concatenate([inv, power], axis=1)
        )
        inv, power = inv + both[:, :C], both[:, C:]
        span *= 2
    for below in mask["below"]:
        inv = inv - _dot(_dot(inv, jnp.where(below, A, 0.0)), inv)
    u = _dot(inv, rhs)                                   # [C, d_v]
    o = against[:C] + _dot(P, u)
    # Into the state: each key row's share at the tile's end (<= 1), and
    # what the state held, decayed a d_k channel (its row).
    keep = x[K] * jnp.exp(E[nb] - b)
    carry = jnp.sum(
        jnp.where(
            mask["eye_d"], jnp.broadcast_to(jnp.exp(E[nb]), (d, d)), 0.0
        ),
        axis=1, keepdims=True,
    )                                                    # [d_k, 1]
    return o, carry * s0 + _dot(keep, u, tn=True)


def _chunk_kernel(
    meta_ref, x_hbm, s_hbm, o_hbm, s_out_hbm, x_buf, o_buf, s_buf, sem,
    *, heads: int, R: int, OR: int, C: int, sub: int, abreast: int,
):
    """``meta_ref`` [4, NT + 1]: a tile's slot, flags, first flat row and
    rows; ``[0, NT]`` the number of used tiles. ``sem``: the state's copy,
    then a tile's rows and its outputs by buffer (two each: the next
    tile's rows arrive, and the last tile's outputs leave, under this
    tile's arithmetic)."""
    H = heads
    used = meta_ref[0, meta_ref.shape[1] - 1]
    f32 = jnp.float32
    mask = _masks(C, sub, s_buf.shape[-1])

    def move(src, dst):
        cp = pltpu.make_async_copy(src, dst, sem.at[0])
        cp.start()
        cp.wait()

    def rows_in(i):
        at = pl.multiple_of(meta_ref[2, i] * R, 8)
        return pltpu.make_async_copy(
            x_hbm.at[pl.ds(at, C * R)], x_buf.at[i % 2], sem.at[1 + i % 2]
        )

    def rows_out(i):
        at = pl.multiple_of(meta_ref[2, i] * OR, 8)
        return pltpu.make_async_copy(
            o_buf.at[i % 2], o_hbm.at[pl.ds(at, C * OR)], sem.at[3 + i % 2]
        )

    @pl.when(used > 0)
    def _():
        rows_in(0).start()

    def tile(i, _):
        slot, flag, n = meta_ref[0, i], meta_ref[1, i], meta_ref[3, i]
        first = (flag & FIRST) != 0
        fresh = (flag & FRESH) != 0

        @pl.when(i + 1 < used)
        def _():
            rows_in(i + 1).start()

        @pl.when(first & jnp.logical_not(fresh))
        def _():
            move(s_hbm.at[slot], s_buf)

        @pl.when(first & fresh)
        def _():
            # Zeros, not a product with what the slot held (a NaN too).
            s_buf[...] = jnp.zeros(s_buf.shape, s_buf.dtype)

        rows_in(i).wait()
        x_now, o_now = x_buf.at[i % 2], o_buf.at[i % 2]
        live = jax.lax.broadcasted_iota(jnp.int32, (C, 1), 0) < n

        def group(j, _):
            # ``abreast`` heads as a batch dimension (``HEADS_ABREAST``).
            hs = [j * abreast + u for u in range(abreast)]
            x = [
                jnp.stack([
                    jnp.where(
                        live, x_now[pl.ds(a * H + h, C, stride=R), :], 0.0
                    )
                    for h in hs
                ])
                for a in range(5)
            ]
            s0 = jnp.stack([s_buf[h].astype(f32) for h in hs])
            o, new = jax.vmap(
                lambda *xs: _head_tile(xs[:5], xs[5], mask, C=C, sub=sub)
            )(*x, s0)
            for u, h in enumerate(hs):
                o_now[pl.ds(h, C, stride=OR), :] = o[u]
                s_buf[h] = new[u].astype(s_buf.dtype)
            return 0

        jax.lax.fori_loop(0, H // abreast, group, 0)

        # One copy of outputs in flight at a time: two tiles' rows may
        # overlap in the flat batch, and the later tile's have to land last.
        @pl.when(i > 0)
        def _():
            rows_out(i - 1).wait()

        rows_out(i).start()

        @pl.when((flag & LAST) != 0)
        def _():
            move(s_buf, s_out_hbm.at[slot])

        return 0

    jax.lax.fori_loop(0, used, tile, 0)

    @pl.when(used > 0)
    def _():
        rows_out(used - 1).wait()


def kda_chunk(x, state, slots, flags, row0, nrows, used, *, sub: int):
    """Advance ``state[slots[i]]`` by tile ``i``'s rows, for the first
    ``used`` tiles; a span's tiles are consecutive, flagged ``FIRST`` ..
    ``LAST``, and spans lie in the flat batch in the order of their tiles
    (a tile writes ``TILE`` rows from its first: what lies past its span's
    end is written again by the tile that owns it, or owned by no tile).

    ``x`` [T, 5H, d] float32 (log decay, k, beta*k, q, beta*v stacked by
    head), ``state`` [N + 1, H, d, d], ``slots``, ``flags``, ``row0`` (a
    tile's first flat row), ``nrows`` (its rows that belong to its span)
    [NT]. Returns (o [T, H, d] float32, undefined in rows no tile owns;
    the state, updated in place)."""
    return _kda_chunk(
        x, state, slots, flags, row0, nrows, used, sub=sub,
        interpret=_interpret(),
    )


@functools.partial(jax.jit, static_argnames=("sub", "interpret"))
def _kda_chunk(
    x, state, slots, flags, row0, nrows, used, *, sub: int, interpret: bool
):
    T, R5, d = x.shape
    H = R5 // 5
    C = TILE
    # Rows a tile may read past the batch's end, and a row's arrays padded
    # to whole sublane tiles (a DMA starts at a multiple of 8 rows).
    R, OR = -(-R5 // 8) * 8, -(-H // 8) * 8
    x = jnp.pad(x.astype(jnp.float32), ((0, C), (0, R - R5), (0, 0)))
    meta = jnp.stack([slots, flags, row0, nrows]).astype(jnp.int32)
    meta = jnp.pad(meta, ((0, 0), (0, 1))).at[0, -1].set(used)
    abreast = next(u for u in (HEADS_ABREAST, 2, 1) if H % u == 0)
    any_ = pl.BlockSpec(memory_space=pl.ANY)
    block = H * d * d * 4
    o, state = pl.pallas_call(
        functools.partial(
            _chunk_kernel, heads=H, R=R, OR=OR, C=C, sub=sub,
            abreast=abreast,
        ),
        out_shape=[
            jax.ShapeDtypeStruct(((T + C) * OR, d), jnp.float32),
            jax.ShapeDtypeStruct(state.shape, state.dtype),
        ],
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(1,),
            in_specs=[any_, any_],
            out_specs=[any_, any_],
            scratch_shapes=[
                pltpu.VMEM((2, C * R, d), jnp.float32),
                pltpu.VMEM((2, C * OR, d), jnp.float32),
                pltpu.VMEM((H, d, d), state.dtype),
                pltpu.SemaphoreType.DMA((5,)),
            ],
        ),
        # operands: meta, x, state -> outputs: o, state
        input_output_aliases={2: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=max(32 << 20, 2 * block + 3 * C * (R + OR) * d * 4),
        ),
        name="kda_chunk",
        interpret=interpret,
    )(meta, x.reshape((T + C) * R, d), state)
    return o.reshape(T + C, OR, d)[:T, :H], state
