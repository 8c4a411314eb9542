"""The Mamba-2 state-space recurrence (SSD, arXiv:2405.21060) over a flat
ragged batch, with the state it carries from step to step.

A state-space layer keeps, for each sequence, a state ``S`` of ``[H, P, N]``
in float32 (``H`` heads of ``P`` channels, ``N`` the state size) and the
last ``K - 1`` input rows of its depthwise causal convolution: constant in
the context. Both live in the state table's slots beside the other
recurrent kinds' (docs/architecture/unified_step.md "State-space state":
slot 0 is trash, a span names its slot in ``state_slot``, and a span that
starts at position 0 starts from zeros IN THE PROGRAM).

Per token ``t`` and head ``h`` of group ``g = h // (H / G)`` (``a_t`` the
head's scalar decay in (0, 1], ``dt_t`` its step, ``B_t``, ``C_t`` the
group's ``[N]`` rows)::

    S_t = a_t S_{t-1} + (dt_t x_t) B_t^T          y_t = S_t C_t

The skip ``D x_t``, the gate and the norm are the mixer's (models/llama.py
``_ssd_mixer``). ``ssd_ragged`` advances every span of a dispatch: spans of
one row (decode lanes) through the ``ssd_recurrent`` Pallas kernel, spans
of more rows (prefill quanta) through ``ssd_chunk``, the masked ``(C B^T)
.* L`` form within a tile of 128 rows with the state held in VMEM across a
span's tiles (ops/pallas/ssd.py); ``ssd_ragged_xla`` is the XLA twin of
both, the path off the TPU, and stays the recurrence row by row: it is
what the kernels are held to. The state after a span does not depend on
how the prompt was cut into spans.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from dynamo_tpu.ops.linear_attention import span_rows, span_tiles


def ssd_ragged_xla(
    x, dt, la, B, C, state, token_seq, token_pos, q_start, q_len, row_start,
    state_slot,
):
    """The recurrence over every span's rows in flat order, one row a step
    of a loop that runs as many steps as spans own rows.

    ``x`` [T, H, P], ``dt`` [T, H] (the step), ``la`` [T, H] (the log of
    the decay, <= 0), ``B, C`` [T, G, N], all float32; ``state`` [N + 1, H,
    P, N]. Returns (y [T, H, P] float32, the new state); rows no span owns
    read zero."""
    del row_start
    T, H, _ = x.shape
    rep = H // B.shape[1]
    j, owned = span_rows(token_seq, token_pos, q_start, q_len)
    order = jnp.argsort(~owned, stable=True)       # owned rows first
    hi = jax.lax.Precision.HIGHEST

    def body(i, carry):
        cur, state, y = carry
        t = order[i]
        s = token_seq[t]
        slot = state_slot[s]
        first, last = j[t] == 0, j[t] == q_len[s] - 1
        held = jnp.where(q_start[s] == 0, 0.0, state[slot].astype(jnp.float32))
        prev = jnp.where(first, held, cur)
        Bh = jnp.repeat(B[t], rep, axis=0)                       # [H, N]
        Ch = jnp.repeat(C[t], rep, axis=0)
        new = (
            jnp.exp(la[t])[:, None, None] * prev
            + (dt[t][:, None] * x[t])[:, :, None] * Bh[:, None, :]
        )
        y = y.at[t].set(jnp.einsum("hpn,hn->hp", new, Ch, precision=hi))
        state = state.at[slot].set(
            jnp.where(last, new, state[slot].astype(jnp.float32)).astype(
                state.dtype
            )
        )
        return new, state, y

    init = (
        jnp.zeros(state.shape[1:], jnp.float32), state,
        jnp.zeros(x.shape, jnp.float32),
    )
    _, state, y = jax.lax.fori_loop(0, owned.sum(), body, init)
    return y, state


def ssd_ragged(
    x, dt, la, B, C, state, token_seq, token_pos, q_start, q_len, row_start,
    state_slot, *, use_pallas: bool,
):
    """``ssd_ragged_xla``'s contract; on the Pallas path spans of one row go
    through ``ssd_recurrent`` and spans of more through ``ssd_chunk``
    (ops/pallas/ssd.py). Spans lie in the flat batch in their order
    (``row_start`` the running sum of ``q_len``, the runner's packing: the
    chunk kernel's output rows lean on it, as ``kda_chunk``'s do)."""
    operands = (
        x, dt, la, B, C, state, token_seq, token_pos, q_start, q_len,
        row_start, state_slot,
    )
    if not use_pallas:
        return ssd_ragged_xla(*operands)
    from dynamo_tpu.ops.pallas import ssd as kernels

    return ssd_ragged_pallas(kernels, *operands)


def ssd_ragged_pallas(
    kernels, x, dt, la, B, C, state, token_seq, token_pos, q_start, q_len,
    row_start, state_slot,
):
    """``kernels``: ops/pallas/ssd.py (a tool may hand in another
    checkout's)."""
    kd, TILE = kernels, kernels.TILE
    T = x.shape[0]
    _, owned = span_rows(token_seq, token_pos, q_start, q_len)
    fresh = kd.FRESH * (q_start == 0)                        # [S]
    # Decode lanes: one row a span, gathered by span.
    lane = q_len == 1
    at = jnp.clip(row_start, 0, T - 1)
    y_lane, state = kd.ssd_recurrent(
        x[at], dt[at], la[at], B[at], C[at], state,
        jnp.where(lane, state_slot, 0), jnp.where(lane, kd.ACTIVE + fresh, 0),
    )
    # Prefill quanta: tiles of TILE rows read from the flat batch where
    # they lie, a span's tiles consecutive on its state.
    span, off, n, used = span_tiles(q_len, T, TILE)
    y_rows, state = kd.ssd_chunk(
        x, dt, la, B, C, state, state_slot[span],
        jnp.where(off == 0, kd.FIRST + fresh[span], 0)
        + jnp.where(off + TILE >= q_len[span], kd.LAST, 0),
        row_start[span] + off, n, used,
    )
    multi = owned & (q_len[token_seq] > 1)
    y = jnp.where(
        multi[:, None, None], y_rows,
        jnp.where((owned & ~multi)[:, None, None], y_lane[token_seq], 0.0),
    )
    return y, state
