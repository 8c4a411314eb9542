"""Delta-rule linear attention (KDA, arXiv:2510.26692) over a flat ragged
batch, with the state it carries from step to step.

A linear-attention layer keeps, for each sequence, a state ``S`` of
``[H, d_k, d_v]`` in float32 and the last ``K - 1`` input rows of its
depthwise causal convolution: no keys and values, constant in the context.
Both live in a table of ``max_num_seqs + 1`` slots (slot 0 is trash: idle
metadata rows and budget padding aim there), and a span of the unified
step names its slot in ``state_slot`` (docs/architecture/unified_step.md
"State that is not pages"). A span that starts at position 0 starts from
zeros IN THE PROGRAM: a slot is never cleared from the host.

Per token ``t`` and head (``a_t = exp(g_t)`` the per-channel decay)::

    S_t = (I - beta_t k_t k_t^T) Diag(a_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

``kda_ragged`` advances every span of a dispatch: spans of one row
(decode lanes) through the ``kda_recurrent`` Pallas kernel, spans of more
rows (prefill quanta) through ``kda_chunk``, the recurrence's chunkwise
form in tiles of 64 rows as matrix products, with the state held in VMEM
across a span's tiles (ops/pallas/kda.py); ``kda_ragged_xla`` is the XLA
twin of both, the path off the TPU, and stays the recurrence row by row:
it is what the kernels are held to. The state after a span does not
depend on how the prompt was cut into spans.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def span_rows(token_seq, token_pos, q_start, q_len):
    """By flat row: (its span's offset ``j`` within the span, whether a
    span owns the row)."""
    j = token_pos - q_start[token_seq]
    return j, (token_pos >= 0) & (q_len[token_seq] > 0)


def causal_conv(
    x, w, tail, token_seq, token_pos, q_start, q_len, row_start, state_slot,
    bias=None,
):
    """Depthwise causal convolution over each span's rows, continued from
    its slot's tail: ``y_t = sum_i w[i] * x_{t - (K-1) + i}`` (the torch
    conv1d order), rows before the sequence's first reading zero.

    ``x`` [T, C], ``w`` [K, C], ``tail`` [N + 1, K - 1, C] (the K - 1
    input rows before each slot's next position), ``bias`` [C] added to
    every row's result where the layer has one. Returns (y [T, C] in
    float32, the new tail)."""
    K = w.shape[0]
    T = x.shape[0]
    j, _ = span_rows(token_seq, token_pos, q_start, q_len)
    fresh = q_start == 0                                     # [S]
    old = jnp.where(
        fresh[:, None, None], jnp.zeros((), tail.dtype), tail[state_slot]
    )                                                        # [S, K-1, C]
    y = x.astype(jnp.float32) * w[K - 1].astype(jnp.float32)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    for i in range(1, K):
        # The row i back: of this span where it has one, else the tail's.
        in_span = jnp.roll(x, i, axis=0)
        from_tail = old[token_seq, jnp.clip(K - 1 + j - i, 0, K - 2)]
        back = jnp.where((j >= i)[:, None], in_span, from_tail)
        y = y + back.astype(jnp.float32) * w[K - 1 - i].astype(jnp.float32)
    # The tail behind the span: its last K - 1 rows, the old tail's where
    # the span is shorter.
    off = q_len[:, None] - (K - 1) + jnp.arange(K - 1)[None, :]   # [S, K-1]
    rows = jnp.clip(row_start[:, None] + off, 0, T - 1)
    # What the old tail hands on where the span is shorter: its row off +
    # K - 1, picked by selects over the K - 1 rows there are (no gather).
    kept = old
    for i in range(K - 1):
        here = (off + (K - 1) == i)[..., None]
        kept = jnp.where(here, old[:, i : i + 1], kept)
    new = jnp.where((off >= 0)[..., None], x[rows].astype(tail.dtype), kept)
    # Idle metadata rows aim at the trash slot.
    return y, tail.at[jnp.where(q_len > 0, state_slot, 0)].set(new)


def kda_ragged_xla(
    q, k, v, g, beta, state, token_seq, token_pos, q_start, q_len,
    row_start, state_slot,
):
    """The recurrence over every span's rows in flat order, one row a
    step of a loop that runs as many steps as spans own rows.

    ``q, k, v, g`` [T, H, d] float32 (``g`` the log decay), ``beta``
    [T, H], ``state`` [N + 1, H, d, d]. Returns (o [T, H, d] float32, the
    new state); rows no span owns read zero."""
    del row_start
    T = q.shape[0]
    j, owned = span_rows(token_seq, token_pos, q_start, q_len)
    order = jnp.argsort(~owned, stable=True)       # owned rows first
    hi = jax.lax.Precision.HIGHEST

    def body(i, carry):
        cur, state, o = carry
        t = order[i]
        s = token_seq[t]
        slot = state_slot[s]
        first, last = j[t] == 0, j[t] == q_len[s] - 1
        held = jnp.where(q_start[s] == 0, 0.0, state[slot].astype(jnp.float32))
        prev = jnp.where(first, held, cur)
        decayed = jnp.exp(g[t])[:, :, None] * prev
        ks = jnp.einsum("hk,hkv->hv", k[t], decayed, precision=hi)
        u = beta[t][:, None] * (v[t] - ks)
        new = decayed + k[t][:, :, None] * u[:, None, :]
        o = o.at[t].set(jnp.einsum("hk,hkv->hv", q[t], new, precision=hi))
        state = state.at[slot].set(
            jnp.where(last, new, state[slot].astype(jnp.float32)).astype(
                state.dtype
            )
        )
        return new, state, o

    init = (
        jnp.zeros(state.shape[1:], jnp.float32), state,
        jnp.zeros(q.shape, jnp.float32),
    )
    _, state, o = jax.lax.fori_loop(0, owned.sum(), body, init)
    return o, state


def span_tiles(q_len, T: int, C: int):
    """The longer spans cut into tiles of ``C`` rows, a span's tiles in
    order, spans in theirs: for each of ``min(T // 2, T // C + S)`` tiles
    (static: a span of two rows or more fills a tile at least, and ``T``
    rows hold no more) its span, its offset within the span and its rows
    that belong to the span; and how many tiles are used (they come
    first)."""
    S = q_len.shape[0]
    NT = max(1, min(T // 2, T // C + S))
    n_tiles = jnp.where(q_len > 1, -(-q_len // C), 0)           # [S]
    first = jnp.cumsum(n_tiles) - n_tiles                       # [S]
    tile = jnp.arange(NT)
    span = jnp.sum(tile[:, None] >= (first + n_tiles)[None, :], axis=1)
    span = jnp.minimum(span, S - 1)
    off = (tile - first[span]) * C
    return span, off, jnp.clip(q_len[span] - off, 0, C), n_tiles.sum()


def kda_ragged(
    q, k, v, g, beta, state, token_seq, token_pos, q_start, q_len,
    row_start, state_slot, *, use_pallas: bool, lower_bound: float,
):
    """``kda_ragged_xla``'s contract; on the Pallas path
    ``kda_ragged_pallas`` over ops/pallas/kda.py. ``lower_bound`` is the
    model's bound on a row's log decay (``g`` lies in ``(lower_bound,
    0)``): a fact of the model that every caller states, since the chunk
    kernel's sub-chunk follows from it and a wrong one is ``exp`` past
    float32."""
    operands = (
        q, k, v, g, beta, state, token_seq, token_pos, q_start, q_len,
        row_start, state_slot,
    )
    if not use_pallas:
        return kda_ragged_xla(*operands)
    from dynamo_tpu.ops.pallas import kda

    return kda_ragged_pallas(kda, *operands, lower_bound=lower_bound)


def kda_ragged_pallas(
    kernels, q, k, v, g, beta, state, token_seq, token_pos, q_start, q_len,
    row_start, state_slot, *, lower_bound: float,
):
    """Spans of one row through ``kernels.kda_rows`` (``kda_recurrent``)
    and spans of more through ``kernels.kda_chunk``, the chunkwise form
    (``kernels``: ops/pallas/kda.py; tools/kda_kernel_bench.py hands in
    another checkout's). Spans lie in the flat batch in their order
    (``row_start`` the running sum of ``q_len``, the runner's packing:
    tests/test_ling.py pins it, the chunk kernel's output rows lean on
    it)."""
    kd, TILE = kernels, kernels.TILE
    T, H, _ = q.shape
    _, owned = span_rows(token_seq, token_pos, q_start, q_len)
    # A row's operands: the log decay, k, beta * k, q, beta * v.
    b = beta[:, :, None]
    x = jnp.concatenate([g, k, b * k, q, b * v], axis=1)     # [T, 5H, d]
    fresh = kd.FRESH * (q_start == 0)                        # [S]
    # Decode lanes: one row a span, gathered by span.
    lane = q_len == 1
    x_lane = x[jnp.clip(row_start, 0, T - 1)]
    o_lane, state = kd.kda_rows(
        jnp.concatenate(
            [jnp.exp(x_lane[:, :H]), x_lane[:, H : 4 * H]], axis=1
        ),
        x_lane[:, 4 * H :], state, jnp.where(lane, state_slot, 0),
        jnp.where(lane, kd.ACTIVE + fresh, 0),
    )
    # Prefill quanta: tiles of TILE rows read from the flat batch where
    # they lie, a span's tiles consecutive on its state.
    span, off, n, used = span_tiles(q_len, T, TILE)
    o_rows, state = kd.kda_chunk(
        x, state, state_slot[span],
        jnp.where(off == 0, kd.FIRST + fresh[span], 0)
        + jnp.where(off + TILE >= q_len[span], kd.LAST, 0),
        row_start[span] + off, n, used,
        sub=kd.sub_chunk(lower_bound, TILE),
    )
    multi = owned & (q_len[token_seq] > 1)
    o = jnp.where(
        multi[:, None, None], o_rows,
        jnp.where((owned & ~multi)[:, None, None], o_lane[token_seq], 0.0),
    )
    return o, state
