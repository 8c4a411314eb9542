"""The Mamba-2 state update as Pallas TPU kernels (ops/ssd.py has the
mathematics and the XLA twin).

Both kernels see a head's state ``[P, N]`` float32 packed ``pack`` heads to
a ``[pack * P, N]`` tile (two heads of 64 channels fill the 128 sublanes of
a ``[128, 128]`` tile; a free reshape of the table's ``[N + 1, H, P, N]``),
and walk the heads a GROUP at a time (the ``H / G`` heads that share one
``B`` and one ``C`` row): every lane slice in a kernel is static.

``ssd_recurrent``: lanes of one row. A grid step is one (lane, block of
whole groups under ``RECURRENT_BLOCK_BYTES``): the
block of the lane's slot (scalar-prefetched ``slots``) is read once,
updated on the VPU and written once in place (aliased): 2 x H x P x N x 4
B a lane a layer, 8.39 MB at 128 x 64 x 128, and nothing else of size
moves: bytes-bound. A head's decay and ``dt x`` are columns ``[pack * P,
tiles]`` that broadcast along the lanes;
``B`` and ``C`` are rows that broadcast along the sublanes; ``y = S C`` is
one lane reduction a tile. (The wrapper hands the rows over lane-dense,
``[2 tiles, pack * P]``, and takes ``y`` back so: the kernel transposes
both, and no layout copy runs outside it.)

``ssd_chunk``: the longer spans in tiles of ``TILE`` rows, the masked form
of the state-space duality: within a tile ``y = ((C B^T) .* L) (dt x)`` with
``L[t, j] = exp(l_t - l_j)`` for ``j <= t`` (``l`` the running log decay of
the head), across tiles ``y += exp(l_t) C_t S_0`` and ``S = exp(l_end) S_0 +
(dt x exp(l_end - l))^T B`` on the MXU at float32 contract precision; every
exponent is <= 0. One grid step a group: a loop over the dispatch's USED
tiles (a scalar-prefetched count: a dispatch without such a span costs
nothing) reads a tile's rows from the flat batch where they lie (one DMA
of the group's x, B, C, dt and log decay, the next tile's under this
tile's arithmetic) and writes its outputs back to the flat rows. A span's
tiles are consecutive; its state is copied into VMEM at the first (or
zeroed where the span starts the sequence), stays there, and is copied
back at the last: a span reads and writes its state once whatever its
length. The tile walk and the flags are ``kda_chunk``'s
(ops/linear_attention.py ``span_tiles``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dynamo_tpu.ops.pallas.kda import ACTIVE, FIRST, FRESH, HI, LAST  # noqa: F401

#: rows of a chunk tile (the family's ``chunk_size``); a longer span is
#: tiles in a row
TILE = 128
#: the most of a lane's state one grid step of ``ssd_recurrent`` moves (in
#: and out, double-buffered: four of these in VMEM): whole groups. A lane's
#: whole 4 MiB a step at the served widths: 128 lanes took 2.40 | 2.14 |
#: 2.03 | 1.94 ms a call at 0.5 | 1 | 2 | 4 MiB (my chip run, PR 56)
RECURRENT_BLOCK_BYTES = 4 << 20
F32 = jnp.float32


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def layout(H: int, P: int, G: int, N: int) -> dict:
    """How the kernels see a state of ``H`` heads of ``[P, N]`` in ``G``
    groups: ``hg`` heads a group, ``pack`` heads a tile of ``pack * P``
    sublanes (as many as 128 hold, a divisor of ``hg``), ``pg`` tiles a
    group, ``W`` the lanes of a packed row of the chunk kernel."""
    hg = H // G
    pack = max(1, min(128 // P, hg))
    while hg % pack:
        pack -= 1
    return dict(
        hg=hg, pack=pack, pg=hg // pack, PW=pack * P,
        W=max(pack * P, N, 2 * hg),
    )


def _by_head(cols, P: int, pack: int, axis: int, shape):
    """``cols[u]`` in the ``P`` entries of head ``u`` along ``axis`` of a
    tile of ``shape``: one select a head of the tile."""
    at = jax.lax.broadcasted_iota(jnp.int32, shape, axis) // P
    out = jnp.broadcast_to(cols[pack - 1], shape)
    for u in range(pack - 2, -1, -1):
        out = jnp.where(at == u, cols[u], out)
    return out


def _recurrent_kernel(
    slots_ref, flags_ref, c_ref, bc_ref, s_in_ref, o_ref, s_out_ref,
    *, pg: int, gb: int,
):
    del slots_ref
    flag = flags_ref[pl.program_id(0)]

    @pl.when((flag & ACTIVE) != 0)
    def _():
        fresh = (flag & FRESH) != 0
        # The rows arrive lane-dense, [2 pg, PW] (decay | dt x, a row a
        # tile), and are turned once a step: a tile's column broadcasts
        # along the lanes.
        cols = c_ref[0, 0].T                      # [PW, gb x 2 pg]
        lane = jax.lax.broadcasted_iota(
            jnp.int32, (cols.shape[0], gb * pg), 1)
        out = jnp.zeros((cols.shape[0], gb * pg), F32)
        for j in range(gb):                       # a group of the block
            b_row = bc_ref[0, 0, 2 * j : 2 * j + 1, :]          # [1, N]
            c_row = bc_ref[0, 0, 2 * j + 1 : 2 * j + 2, :]
            for m in range(pg):
                at, col = j * pg + m, j * 2 * pg + m
                # A select, not a product with 0: whatever a slot's last
                # owner left there (a NaN too) ends with the slot's reuse.
                held = s_in_ref[0, 0, at].astype(F32)
                held = jnp.where(fresh, jnp.zeros_like(held), held)
                new = (
                    cols[:, col : col + 1] * held
                    + cols[:, col + pg : col + pg + 1] * b_row
                )
                s_out_ref[0, 0, at] = new.astype(s_out_ref.dtype)
                y = jnp.sum(new * c_row, axis=1, keepdims=True)  # [PW, 1]
                out = jnp.where(lane == at, y, out)
        o_ref[0, 0] = out.T                       # [gb pg, PW]: head-major


def ssd_recurrent(x, dt, la, B, C, state, slots, flags):
    """Advance ``state[slots[r]]`` by the one row of lane ``r``, for every
    served lane.

    ``x`` [R, H, P], ``dt, la`` [R, H] (the step, the decay's log), ``B,
    C`` [R, G, N], ``state`` [N + 1, H, P, N], ``slots`` [R] (anything for
    a lane not served), ``flags`` [R] (``ACTIVE``, ``FRESH``). Returns (y
    [R, H, P] float32, undefined in lanes not served; the state, updated
    in place). The body is jitted, as ``kda_rows``' is: traced once a shape
    and not once a layer."""
    H, P = x.shape[1:]
    G, N = B.shape[1:]
    # Groups a grid step: as many as RECURRENT_BLOCK_BYTES of state hold.
    gb = max(1, min(G, RECURRENT_BLOCK_BYTES // (H // G * P * N * 4)))
    while G % gb:
        gb -= 1
    return _ssd_recurrent(
        x, dt, la, B, C, state, slots, flags, gb=gb, interpret=_interpret()
    )


@functools.partial(jax.jit, static_argnames=("gb", "interpret"))
def _ssd_recurrent(
    x, dt, la, B, C, state, slots, flags, *, gb: int, interpret: bool
):
    R, H, P = x.shape
    G, N = B.shape[1:]
    lay = layout(H, P, G, N)
    pg, PW = lay["pg"], lay["PW"]
    NB = G // gb                                  # blocks a lane

    x, dt, la = (a.astype(F32) for a in (x, dt, la))
    # A tile's row: its ``pack`` heads' P channels side by side (a reshape).
    tiles = lambda a: a.reshape(R, G, pg, PW)
    cols = jnp.concatenate(
        [
            tiles(jnp.broadcast_to(jnp.exp(la)[:, :, None], x.shape)),
            tiles(dt[:, :, None] * x),
        ],
        axis=2,
    ).reshape(R, NB, gb * 2 * pg, PW)             # a group: decay | dt x
    bc = jnp.stack([B, C], axis=2).astype(F32).reshape(R, NB, gb * 2, N)
    table = state.reshape(state.shape[0], NB, gb * pg, PW, N)
    # A lane not served keeps the block of the served lane before it, so
    # the pipeline moves nothing for it.
    active = (flags & ACTIVE) != 0
    last = jax.lax.cummax(jnp.where(active, jnp.arange(R), -1))
    slots = jnp.where(last >= 0, slots[jnp.maximum(last, 0)], 0)

    def at_state(r, g, slots, flags):
        served = (flags[r] & ACTIVE) != 0
        return (slots[r], jnp.where(served, g, NB - 1), 0, 0, 0)

    row = lambda r, g, slots, flags: (r, g, 0, 0)
    block = gb * pg * PW * N * 4
    o, table = pl.pallas_call(
        functools.partial(_recurrent_kernel, pg=pg, gb=gb),
        out_shape=[
            jax.ShapeDtypeStruct((R, NB, gb * pg, PW), F32),
            jax.ShapeDtypeStruct(table.shape, table.dtype),
        ],
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(R, NB),
            in_specs=[
                pl.BlockSpec((1, 1, gb * 2 * pg, PW), row),
                pl.BlockSpec((1, 1, gb * 2, N), row),
                pl.BlockSpec((1, 1, gb * pg, PW, N), at_state),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, gb * pg, PW), row),
                pl.BlockSpec((1, 1, gb * pg, PW, N), at_state),
            ],
        ),
        # operands: slots, flags, cols, bc, table -> outputs: o, table
        input_output_aliases={4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=max(32 << 20, 6 * block),
        ),
        name="ssd_recurrent",
        interpret=interpret,
    )(slots.astype(jnp.int32), flags.astype(jnp.int32), cols, bc, table)
    return o.reshape(R, H, P), table.reshape(state.shape)


def _dot(a, b, *, nt: bool = False, tn: bool = False):
    """``a @ b`` (``a @ b^T``, ``a^T @ b``) at float32 contract precision."""
    dims = (((0 if tn else 1,), (1 if nt else 0,)), ((), ()))
    return jax.lax.dot_general(
        a, b, dims, precision=HI, preferred_element_type=F32
    )


def _chunk_kernel(
    meta_ref, x_hbm, s_hbm, o_hbm, s_out_hbm, x_buf, o_buf, s_buf, sem,
    *, hg: int, pack: int, pg: int, P: int, N: int, RR: int, OR: int, C: int,
):
    """``meta_ref`` [4, NT + 1]: a tile's slot, flags, first flat row and
    rows; ``[0, NT]`` the number of used tiles. A flat row of this group
    is ``RR`` packed rows: ``pg`` tiles of x, the B row, the C row, ``[dt |
    log decay]`` by head. ``sem``: the state's copy, then a tile's rows
    and its outputs by buffer."""
    g = pl.program_id(0)
    used = meta_ref[0, meta_ref.shape[1] - 1]
    PW = pack * P
    t_i = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    j_i = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    upto = j_i <= t_i
    run = jnp.where(upto, 1.0, 0.0).astype(F32)      # running sum down rows
    eye = jnp.where(t_i == j_i, 1.0, 0.0).astype(F32)
    of_head = jnp.where(
        jax.lax.broadcasted_iota(jnp.int32, (pg * PW, hg), 0) // P
        == jax.lax.broadcasted_iota(jnp.int32, (pg * PW, hg), 1),
        1.0, 0.0,
    ).astype(F32)

    def move(src, dst):
        cp = pltpu.make_async_copy(src, dst, sem.at[0])
        cp.start()
        cp.wait()

    def rows_in(i):
        at = pl.multiple_of(meta_ref[2, i] * RR, 8)
        return pltpu.make_async_copy(
            x_hbm.at[g, pl.ds(at, C * RR)], x_buf.at[i % 2], sem.at[1 + i % 2]
        )

    def rows_out(i):
        at = pl.multiple_of(meta_ref[2, i] * OR, 8)
        return pltpu.make_async_copy(
            o_buf.at[i % 2], o_hbm.at[g, pl.ds(at, C * OR)], sem.at[3 + i % 2]
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
            move(s_hbm.at[slot, g], s_buf)

        @pl.when(first & fresh)
        def _():
            # Zeros, not a product with what the slot held (a NaN too).
            s_buf[...] = jnp.zeros(s_buf.shape, s_buf.dtype)

        rows_in(i).wait()
        x_now, o_now = x_buf.at[i % 2], o_buf.at[i % 2]
        live = jax.lax.broadcasted_iota(jnp.int32, (C, 1), 0) < n

        def packed(r):                       # packed row r of every flat row
            return x_now[pl.ds(r, C, stride=RR), :]

        # A row past the span's end steps by 0 and decays by 1: inert.
        dtl = jnp.where(live, packed(pg + 2), 0.0)
        dt_c, la_c = dtl[:, :hg], dtl[:, hg : 2 * hg]            # [C, hg]
        l_c = _dot(run, la_c)                # running log decay, by column
        l_r = _dot(la_c, run, tn=True, nt=True)  # the same, a row a head
        dt_r = _dot(dt_c, eye, tn=True)      # [hg, C]
        l_end = l_c[C - 1 : C, :]            # [1, hg]
        keep_c = dt_c * jnp.exp(l_end - l_c)  # a row's share at the tile's end
        reach_c = jnp.exp(l_c)
        # What the state keeps of itself, a head's scalar down its P
        # sublanes: one product with a 0/1 matrix (Mosaic broadcasts a
        # [1, 1] along lanes or sublanes, not both).
        kept = _dot(
            of_head, jnp.broadcast_to(jnp.exp(l_end), (N, hg)), nt=True
        )                                    # [pg * PW, N]
        b_m = packed(pg)[:, :N]
        c_m = packed(pg + 1)[:, :N]
        scores = _dot(c_m, b_m, nt=True)     # [C, C], the group's

        for m in range(pg):
            hs = [m * pack + u for u in range(pack)]
            x2 = jnp.where(live, packed(m)[:, :PW], 0.0)          # [C, PW]
            near = []
            for h in hs:
                w = jnp.where(
                    upto,
                    scores
                    * jnp.exp(jnp.minimum(
                        l_c[:, h : h + 1] - l_r[h : h + 1, :], 0.0))
                    * dt_r[h : h + 1, :],
                    0.0,
                )
                near.append(_dot(w, x2))                          # [C, PW]
            s0 = s_buf[m].astype(F32)                             # [PW, N]
            col = lambda a, shape: _by_head(
                [a[:, h : h + 1] for h in hs], P, pack, 1, shape)
            y = _by_head(near, P, pack, 1, (C, PW)) + col(
                reach_c, (C, PW)) * _dot(c_m, s0, nt=True)
            o_now[pl.ds(m, C, stride=OR), :] = jnp.pad(
                y, ((0, 0), (0, o_now.shape[-1] - PW)))
            s_buf[m] = (
                kept[m * PW : (m + 1) * PW] * s0
                + _dot(x2 * col(keep_c, (C, PW)), b_m, tn=True)
            ).astype(s_buf.dtype)

        # One copy of outputs in flight at a time: two tiles' rows may
        # overlap in the flat batch, and the later tile's have to land last.
        @pl.when(i > 0)
        def _():
            rows_out(i - 1).wait()

        rows_out(i).start()

        @pl.when((flag & LAST) != 0)
        def _():
            move(s_buf, s_out_hbm.at[slot, g])

        return 0

    jax.lax.fori_loop(0, used, tile, 0)

    @pl.when(used > 0)
    def _():
        rows_out(used - 1).wait()


def ssd_chunk(x, dt, la, B, C, state, slots, flags, row0, nrows, used):
    """Advance ``state[slots[i]]`` by tile ``i``'s rows, for the first
    ``used`` tiles; a span's tiles are consecutive, flagged ``FIRST`` ..
    ``LAST``, and spans lie in the flat batch in the order of their tiles
    (a tile writes ``TILE`` rows from its first: what lies past its span's
    end is written again by the tile that owns it, or owned by no tile).

    ``x`` [T, H, P], ``dt, la`` [T, H], ``B, C`` [T, G, N], ``state`` [N +
    1, H, P, N], ``slots``, ``flags``, ``row0`` (a tile's first flat row),
    ``nrows`` (its rows that belong to its span) [NT]. Returns (y [T, H, P]
    float32, undefined in rows no tile owns; the state, updated in
    place)."""
    return _ssd_chunk(
        x, dt, la, B, C, state, slots, flags, row0, nrows, used,
        interpret=_interpret(),
    )


@functools.partial(jax.jit, static_argnames="interpret")
def _ssd_chunk(
    x, dt, la, B, C, state, slots, flags, row0, nrows, used,
    *, interpret: bool,
):
    T, H, P = x.shape
    G, N = B.shape[1:]
    lay = layout(H, P, G, N)
    hg, pack, pg, PW, W = (lay[k] for k in ("hg", "pack", "pg", "PW", "W"))
    Cc = TILE
    RR, OR = -(-(pg + 3) // 8) * 8, -(-pg // 8) * 8

    def wide(a):                                   # [T, G, r, w] -> lanes W
        return jnp.pad(
            a.astype(F32), ((0, 0), (0, 0), (0, 0), (0, W - a.shape[-1])))

    rows = jnp.concatenate(
        [
            wide(x.reshape(T, G, pg, PW)),
            wide(B[:, :, None, :]), wide(C[:, :, None, :]),
            wide(jnp.concatenate(
                [dt.reshape(T, G, 1, hg), la.reshape(T, G, 1, hg)], axis=-1)),
        ],
        axis=2,
    )                                              # [T, G, pg + 3, W]
    # Rows a tile may read past the batch's end, and a flat row's packed
    # rows padded to whole sublane tiles (a DMA starts at a multiple of 8).
    rows = jnp.pad(rows, ((0, Cc), (0, 0), (0, RR - pg - 3), (0, 0)))
    rows = rows.transpose(1, 0, 2, 3).reshape(G, (T + Cc) * RR, W)
    meta = jnp.stack([slots, flags, row0, nrows]).astype(jnp.int32)
    meta = jnp.pad(meta, ((0, 0), (0, 1))).at[0, -1].set(used)
    table = state.reshape(state.shape[0], G, pg, PW, N)
    any_ = pl.BlockSpec(memory_space=pl.ANY)
    o, table = pl.pallas_call(
        functools.partial(
            _chunk_kernel, hg=hg, pack=pack, pg=pg, P=P, N=N, RR=RR, OR=OR,
            C=Cc,
        ),
        out_shape=[
            jax.ShapeDtypeStruct((G, (T + Cc) * OR, W), F32),
            jax.ShapeDtypeStruct(table.shape, table.dtype),
        ],
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(G,),
            in_specs=[any_, any_],
            out_specs=[any_, any_],
            scratch_shapes=[
                pltpu.VMEM((2, Cc * RR, W), F32),
                pltpu.VMEM((2, Cc * OR, W), F32),
                pltpu.VMEM((pg, PW, N), state.dtype),
                pltpu.SemaphoreType.DMA((5,)),
            ],
        ),
        # operands: meta, rows, table -> outputs: o, table
        input_output_aliases={2: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=max(
                32 << 20, 4 * pg * PW * N * 4 + 3 * Cc * (RR + OR) * W * 4),
        ),
        name="ssd_chunk",
        interpret=interpret,
    )(meta, rows, table)
    y = o.reshape(G, T + Cc, OR, W)[:, :T, :pg, :PW]
    y = y.reshape(G, T, pg, pack, P).transpose(1, 0, 2, 3, 4).reshape(T, H, P)
    return y, table.reshape(state.shape)
