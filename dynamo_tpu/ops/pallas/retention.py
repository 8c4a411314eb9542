"""Power retention's state update as Pallas TPU kernels
(ops/power_retention.py has the mathematics, the layout of ``phi`` and the
XLA twin).

The state of one (slot, cached head) is ``S`` ``[R, d_v, d]`` float32 (the
kernels compute in float32 whatever the table's dtype is: the benchmark's
control holds it in bfloat16), row
``r`` the tile ``S_r[v, i] = sum_t gate * v_t[v] * phi_r(k_t)[i]``, and
``z`` ``[R, d]`` (stored with its rows padded to a sublane tile): 4.26 MB
and 33 KB at ``d`` 128. ``phi_r(u) = m_r * u * roll(u, r)`` is one lane
rotation of the row, formed in VMEM; it never exists in HBM.

``retention_recurrent``: lanes of one row. A grid step is one (lane, cached
head): its block of the table is read once, updated on the VPU and written
once in place (aliased), and the ``G`` query heads of the cached head are
answered from that one read: ``num_a[v] = sum_{r,i} S_r[v, i] phi_r(q_a)
[i]`` accumulated elementwise over ``r`` and reduced along the lanes once.
Bytes-bound: nothing but the state moves.

``retention_chunk``: the longer spans, in tiles of ``C`` rows (the
chunkwise form). Within a tile the masked attention form ``(Q K^T)^2``
under the gates; across tiles ``phi_r(Q) S_r^T`` and ``S_r += V^T
phi_r(K)`` on the MXU, one pair of products a row of ``phi``, at Mosaic's
default contract precision (one bfloat16 pass, float32 sums: three passes
there read no closer to the attention form on the chip and 1.76 times the
kernel's time, PERF.md section 6, PR 48). The state
is copied into VMEM at a span's first tile (or zeroed where the span
starts the sequence), stays there across the span's tiles, and is copied
back at its last: a span reads and writes its state once whatever its
length, and a dispatch without such a span moves nothing.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dynamo_tpu.ops.power_retention import EPS, phi_rows

#: flags a lane or a tile: bit 0 it is served, bit 1 it is its span's
#: first, bit 2 its span starts the sequence (the state starts from zeros,
#: whatever the slot holds), bit 3 it is its span's last
ACTIVE, FIRST, FRESH, LAST = 1, 2, 4, 8
SQRT2 = 2.0 ** 0.5


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _roll(x, r: int):
    """``jnp.roll(x, r, axis=-1)`` as one lane rotation."""
    if r == 0:
        return x
    if _interpret():
        return jnp.roll(x, r, axis=-1)
    return pltpu.roll(x, r, x.ndim - 1)


def _split(x):
    """``x`` (float32) as ``hi + lo``, each a value bfloat16 holds: 16 bits
    of it for an MXU whose default pass takes 8 (Mosaic's default contract
    precision rounds float32 operands to bfloat16 and sums in float32)."""
    hi = x.astype(jnp.bfloat16).astype(jnp.float32)
    return hi, (x - hi).astype(jnp.bfloat16).astype(jnp.float32)


def _phi_tile(x, r: int, d: int):
    """Row ``r`` of ``phi`` for every row of ``x`` [n, d]."""
    m = 1.0 if r in (0, d // 2) else SQRT2
    return (x * m) * _roll(x, r)


def _recurrent_kernel(
    slots_ref, flags_ref, x_ref, s_in_ref, z_in_ref, o_ref, s_out_ref,
    z_out_ref, phi_ref, *, G: int, d: int, vb: int,
):
    del slots_ref
    flag = flags_ref[pl.program_id(0)]
    R = phi_rows(d)

    @pl.when((flag & ACTIVE) != 0)
    def _():
        fresh = (flag & FRESH) != 0
        x = x_ref[0, 0]                       # [XR, d]: k, q x G, v, gate
        g = x[G + 2 : G + 3]                  # [1, d], the gate in every lane
        # phi of the row's k and q's, the normaliser beside it.
        den = jnp.zeros(x.shape, jnp.float32)
        for r in range(R):
            p = _phi_tile(x, r, d)
            phi_ref[r] = p
            held = z_in_ref[0, 0, r : r + 1, :].astype(jnp.float32)
            held = jnp.where(fresh, jnp.zeros_like(held), held)
            zr = g * held + p[0:1]
            z_out_ref[0, 0, r : r + 1, :] = zr.astype(z_out_ref.dtype)
            den = den + p * zr
        den = jnp.sum(den, axis=1, keepdims=True) + EPS        # [XR, 1]
        # v down the sublanes, alike in every lane.
        v_col = jnp.broadcast_to(x[G + 1 : G + 2], (d, d)).T
        lane = jax.lax.broadcasted_iota(jnp.int32, (vb, d), 1)
        for c0 in range(0, d, vb):
            rows = pl.ds(c0, vb)
            v_c = v_col[c0 : c0 + vb]

            def body(r, accs):
                p = phi_ref[r]
                held = s_in_ref[0, 0, r, rows, :].astype(jnp.float32)
                held = jnp.where(fresh, jnp.zeros_like(held), held)
                new = g * held + v_c * p[0:1]
                s_out_ref[0, 0, r, rows, :] = new.astype(s_out_ref.dtype)
                return tuple(
                    acc + new * p[a + 1 : a + 2] for a, acc in enumerate(accs)
                )

            accs = jax.lax.fori_loop(
                0, R, body,
                tuple(jnp.zeros((vb, d), jnp.float32) for _ in range(G)),
            )
            # Head a's answer in lane a of the output tile.
            out = jnp.zeros((vb, d), jnp.float32)
            for a, acc in enumerate(accs):
                y = jnp.sum(acc, axis=1, keepdims=True) / den[a + 1 : a + 2]
                out = jnp.where(lane == a, y, out)
            o_ref[0, 0, rows, :] = out


def retention_recurrent(q, k, v, lg, state, slots, flags):
    """Advance ``state[slots[l]]`` by lane ``l``'s one row, for every
    served lane.

    ``q`` [L, H, d] (scaled), ``k, v`` [L, kvH, d], ``lg`` [L, kvH],
    ``state`` the ``(S, z)`` pair, ``slots`` [L] (anything for a lane not
    served), ``flags`` [L] (``ACTIVE``, ``FRESH``). Returns (y [L, H, d]
    float32, undefined in lanes not served; the state, updated in place)."""
    S, z = state
    L, H, d = q.shape
    kvH = k.shape[1]
    G = H // kvH
    R = phi_rows(d)
    XR = -(-(G + 3) // 8) * 8
    f32 = jnp.float32
    x = jnp.concatenate(
        [
            k.astype(f32)[:, :, None, :],
            q.astype(f32).reshape(L, kvH, G, d),
            v.astype(f32)[:, :, None, :],
            jnp.broadcast_to(
                jnp.exp(lg.astype(f32))[:, :, None, None], (L, kvH, 1, d)
            ),
            jnp.zeros((L, kvH, XR - G - 3, d), f32),
        ],
        axis=2,
    )                                                     # [L, kvH, XR, d]
    # A lane not served keeps the block of the served lane before it, so
    # the pipeline moves nothing for it.
    active = (flags & ACTIVE) != 0
    idx = jnp.arange(L)
    last = jax.lax.cummax(jnp.where(active, idx, -1))
    slots = jnp.where(last >= 0, slots[jnp.maximum(last, 0)], 0)

    def at_state(tail):
        def index(l, c, slots, flags):
            served = (flags[l] & ACTIVE) != 0
            return (slots[l], jnp.where(served, c, kvH - 1)) + (0,) * tail
        return index

    row = lambda l, c, slots, flags: (l, c, 0, 0)
    vb = min(32, d)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(L, kvH),
        in_specs=[
            pl.BlockSpec((1, 1, XR, d), row),
            pl.BlockSpec((1, 1, R, d, d), at_state(3)),
            pl.BlockSpec((1, 1, z.shape[2], d), at_state(2)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, d, d), row),
            pl.BlockSpec((1, 1, R, d, d), at_state(3)),
            pl.BlockSpec((1, 1, z.shape[2], d), at_state(2)),
        ],
        scratch_shapes=[pltpu.VMEM((R, XR, d), f32)],
    )
    block = R * d * d * 4
    o, S, z = pl.pallas_call(
        functools.partial(_recurrent_kernel, G=G, d=d, vb=vb),
        out_shape=[
            jax.ShapeDtypeStruct((L, kvH, d, d), f32),
            jax.ShapeDtypeStruct(S.shape, S.dtype),
            jax.ShapeDtypeStruct(z.shape, z.dtype),
        ],
        grid_spec=grid_spec,
        # operands: slots, flags, x, S, z -> outputs: o, S, z
        input_output_aliases={3: 1, 4: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=max(32 << 20, 5 * block),
        ),
        name="retention_recurrent",
        interpret=_interpret(),
    )(slots.astype(jnp.int32), flags.astype(jnp.int32), x, S, z)
    # [L, kvH, d_v, lane a] -> [L, H, d_v]
    y = o[:, :, :, :G].transpose(0, 1, 3, 2).reshape(L, H, d)
    return y, (S, z)


def _chunk_kernel(
    slots_ref, flags_ref, nrows_ref, eff_ref, q_ref, k_ref, v_ref, gc_ref,
    s_hbm, z_hbm, y_ref, s_out_hbm, z_out_hbm, s_ref, z_ref, sem,
    *, G: int, C: int, d: int,
):
    del eff_ref
    c, i = pl.program_id(0), pl.program_id(1)
    flag, slot = flags_ref[i], slots_ref[i]
    R = phi_rows(d)
    active = (flag & ACTIVE) != 0
    first = (flag & FIRST) != 0
    fresh = (flag & FRESH) != 0
    f32 = jnp.float32

    def copies(src_s, dst_s, src_z, dst_z):
        return (
            pltpu.make_async_copy(src_s, dst_s, sem.at[0]),
            pltpu.make_async_copy(src_z, dst_z, sem.at[1]),
        )

    def move(*ends):
        cps = copies(*ends)
        for cp in cps:
            cp.start()
        for cp in cps:
            cp.wait()

    @pl.when(active & first & jnp.logical_not(fresh))
    def _():
        move(s_hbm.at[slot, c], s_ref, z_hbm.at[slot, c], z_ref)

    @pl.when(active & first & fresh)
    def _():
        s_ref[...] = jnp.zeros(s_ref.shape, s_ref.dtype)
        z_ref[...] = jnp.zeros(z_ref.shape, z_ref.dtype)

    @pl.when(active)
    def _():
        n = nrows_ref[i]
        Q = q_ref[0, 0].astype(f32)                    # [G * C, d], scaled
        K = k_ref[0, 0].astype(f32)                    # [C, d]
        V = v_ref[0, 0].astype(f32)
        g_row = gc_ref[0, 0]                           # [1, C] running log gate
        g_t = jnp.broadcast_to(g_row, (C, C)).T        # [t, j] = G_t
        g_col = g_t[:, 0:1]                            # [C, 1]
        g_end = g_row[:, C - 1 : C]                    # [1, 1]
        t_i = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
        j_i = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
        decay = jnp.where(
            (j_i <= t_i) & (j_i < n),
            jnp.exp(jnp.minimum(g_t - g_row, 0.0)), 0.0,
        )
        # A key row's share of the state at the tile's end; a row past the
        # span's end adds nothing.
        k_keep = jnp.where(
            jax.lax.broadcasted_iota(jnp.int32, (C, 1), 0) < n,
            jnp.exp(g_end - g_col), 0.0,
        )                                              # [C, 1]
        v_t = (V * k_keep).T                           # [d_v, C]
        carry = jnp.exp(g_end)                         # [1, 1]
        reach = jnp.concatenate([jnp.exp(g_col)] * G, axis=0)   # [G * C, 1]
        # Within the tile: the attention form.
        nums, dens = [], []
        for a in range(G):
            s = jax.lax.dot_general(
                Q[a * C : (a + 1) * C], K, (((1,), (1,)), ((), ())),
                preferred_element_type=f32,
            )
            w = s * s * decay
            # (two passes: the weights keep 16 bits against the values)
            nums.append(sum(
                jnp.dot(part, V, preferred_element_type=f32)
                for part in _split(w)
            ))
            dens.append(jnp.sum(w, axis=1, keepdims=True))
        num = jnp.concatenate(nums, axis=0)            # [G * C, d_v]
        den = jnp.concatenate(dens, axis=0)            # [G * C, 1]
        # Across tiles: what the state held before this tile, and the
        # tile's keys and values into the state.
        far_num = jnp.zeros((G * C, d), f32)
        far_den = jnp.zeros((G * C, d), f32)
        for r in range(R):
            s_r = s_ref[r].astype(f32)                 # [d_v, d]
            z_r = z_ref[r : r + 1, :].astype(f32)      # [1, d]
            pq = _phi_tile(Q, r, d)
            far_num = far_num + jax.lax.dot_general(
                pq, s_r, (((1,), (1,)), ((), ())),
                preferred_element_type=f32,
            )
            far_den = far_den + pq * z_r
            pk = _phi_tile(K, r, d)
            s_ref[r] = (
                carry * s_r + jnp.dot(v_t, pk, preferred_element_type=f32)
            ).astype(s_ref.dtype)
            z_ref[r : r + 1, :] = (
                carry * z_r + jnp.sum(pk * k_keep, axis=0, keepdims=True)
            ).astype(z_ref.dtype)
        num = num + reach * far_num
        den = den + reach * jnp.sum(far_den, axis=1, keepdims=True)
        y_ref[0, 0] = (num / (den + EPS)).astype(y_ref.dtype)

    @pl.when(active & ((flag & LAST) != 0))
    def _():
        move(s_ref, s_out_hbm.at[slot, c], z_ref, z_out_hbm.at[slot, c])


def retention_chunk(q, k, v, gc, state, slots, flags, nrows):
    """Advance ``state[slots[i]]`` by tile ``i``'s rows, for every served
    tile; a span's tiles are consecutive and flagged ``FIRST`` .. ``LAST``.

    ``q`` [NT, C, H, d] (scaled), ``k, v`` [NT, C, kvH, d], ``gc`` [kvH,
    NT, C] the log gate (0 in rows past a span's end), ``state`` the ``(S,
    z)`` pair, ``slots``, ``flags``, ``nrows`` [NT] (a tile's rows that
    belong to its span). Served tiles come first. Returns (y [NT, C, H, d]
    in ``q``'s dtype, undefined in rows not served; the state, updated in
    place)."""
    S, z = state
    NT, C, H, d = q.shape
    kvH = k.shape[2]
    G = H // kvH
    R = phi_rows(d)
    f32 = jnp.float32
    # [kvH, NT, G * C, d]: a cached head's query heads stacked by head.
    q_t = q.reshape(NT, C, kvH, G, d).transpose(2, 0, 3, 1, 4).reshape(
        kvH, NT, G * C, d
    )
    k_t, v_t = k.transpose(2, 0, 1, 3), v.transpose(2, 0, 1, 3)
    g_run = jnp.cumsum(gc.astype(f32), axis=-1)[:, :, None, :]  # [kvH,NT,1,C]
    # A tile not served keeps the blocks of the last served one.
    served = (flags & ACTIVE) != 0
    eff = jnp.clip(jnp.minimum(jnp.arange(NT), served.sum() - 1), 0, NT - 1)

    tile = lambda c, i, slots, flags, nrows, eff: (c, eff[i], 0, 0)
    any_ = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(kvH, NT),
        in_specs=[
            pl.BlockSpec((1, 1, G * C, d), tile),
            pl.BlockSpec((1, 1, C, d), tile),
            pl.BlockSpec((1, 1, C, d), tile),
            pl.BlockSpec((1, 1, 1, C), tile),
            any_, any_,
        ],
        out_specs=[pl.BlockSpec((1, 1, G * C, d), tile), any_, any_],
        scratch_shapes=[
            pltpu.VMEM((R, d, d), S.dtype),
            pltpu.VMEM((z.shape[2], d), z.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    y, S, z = pl.pallas_call(
        functools.partial(_chunk_kernel, G=G, C=C, d=d),
        out_shape=[
            jax.ShapeDtypeStruct((kvH, NT, G * C, d), q.dtype),
            jax.ShapeDtypeStruct(S.shape, S.dtype),
            jax.ShapeDtypeStruct(z.shape, z.dtype),
        ],
        grid_spec=grid_spec,
        # operands: slots, flags, nrows, eff, q, k, v, g, S, z -> y, S, z
        input_output_aliases={8: 1, 9: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=max(32 << 20, 3 * R * d * d * 4),
        ),
        name="retention_chunk",
        interpret=_interpret(),
    )(
        slots.astype(jnp.int32), flags.astype(jnp.int32),
        nrows.astype(jnp.int32), eff.astype(jnp.int32),
        q_t, k_t, v_t, g_run, S, z,
    )
    y = y.reshape(kvH, NT, G, C, d).transpose(1, 3, 0, 2, 4).reshape(
        NT, C, H, d
    )
    return y, (S, z)
