"""The delta-rule state update as a Pallas TPU kernel, the state read and
written once, in place (ops/linear_attention.py has the mathematics and
the XLA twin).

One grid step is one ROW of one sequence: the row's slot of the state
table ``[N + 1, H, d, d]`` is the step's block (scalar-prefetched
``slots``), aliased from input to output, so a decode lane costs one read
and one write of its 2 MiB (H 32, d 128, float32) and nothing else moves.
``kda_recurrent`` (``chunked=False``) takes one row a sequence. ``kda_chunk``
(``chunked=True``) takes the flat rows of the longer spans in order: the
rows of one span are consecutive grid steps on ONE block, which the
pipeline neither fetches again nor writes back until the block changes,
so the state stays in VMEM across a span and each row reads the state
the row before it left in the output block.

Layout: a head's state is ``[d_k, d_v]`` with ``d_k`` on sublanes. The
row's per-``d_k`` vectors (decay, k, beta*k, q: ``x`` [4H, d]) arrive
with ``d`` on lanes and are transposed once a row (one [4H, d] -> [d, 4H]
transpose: 128 x 128 at H 32), so each is a column that broadcasts along
lanes; ``beta * v`` and the output stay rows.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: flags a row: bit 0 the row is served, bit 1 it is its span's first, bit
#: 2 its span starts the sequence (the state starts from zeros, whatever
#: the slot holds)
ACTIVE, FIRST, FRESH = 1, 2, 4


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _kda_kernel(slots_ref, flags_ref, x_ref, bv_ref, s_in_ref, o_ref,
                s_out_ref, *, heads: int, chunked: bool):
    del slots_ref
    flag = flags_ref[pl.program_id(0)]
    H = heads

    def advance(read):
        xt = x_ref[0].T                                   # [d, 4H]
        for h in range(H):
            a, kc, bk, qc = (
                xt[:, n * H + h : n * H + h + 1] for n in range(4)
            )
            decayed = a * read(h).astype(jnp.float32)     # [d_k, d_v]
            ks = jnp.sum(bk * decayed, axis=0, keepdims=True)
            new = decayed + kc * (bv_ref[0, h : h + 1, :] - ks)
            s_out_ref[0, h] = new.astype(s_out_ref.dtype)
            o_ref[0, h : h + 1, :] = jnp.sum(qc * new, axis=0, keepdims=True)

    def slot_state(h):
        # A select, not a product with 0: whatever a slot's last owner
        # left there (a NaN too) ends with the slot's reuse.
        held = s_in_ref[0, h]
        return jnp.where((flag & FRESH) != 0, jnp.zeros_like(held), held)

    if not chunked:
        @pl.when((flag & ACTIVE) != 0)
        def _():
            advance(slot_state)
    else:
        @pl.when((flag & (ACTIVE | FIRST)) == (ACTIVE | FIRST))
        def _():
            advance(slot_state)

        @pl.when(flag == ACTIVE)
        def _():
            # The row before this one left the state in the output block.
            advance(lambda h: s_out_ref[0, h])


def kda_rows(x, bv, state, slots, flags, *, chunked: bool):
    """Advance ``state[slots[r]]`` by row ``r`` for every served row.

    ``x`` [R, 4H, d] float32 (decay, k, beta*k, q stacked by head), ``bv``
    [R, H, d] (beta*v), ``state`` [N + 1, H, d, d], ``slots`` [R] (0, the
    trash slot, for a row not served), ``flags`` [R] (``ACTIVE``,
    ``FIRST``, ``FRESH``). Returns (o [R, H, d] float32, undefined in rows
    not served; the state, updated in place)."""
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
        functools.partial(_kda_kernel, heads=H, chunked=chunked),
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
        name="kda_chunk" if chunked else "kda_recurrent",
        interpret=_interpret(),
    )(
        slots.astype(jnp.int32), flags.astype(jnp.int32),
        x.astype(jnp.float32), bv.astype(jnp.float32), state,
    )
    return o, state
