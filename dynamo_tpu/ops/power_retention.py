"""Power retention (degree 2; arXiv:2507.04239) over a flat ragged batch,
with the state it carries from step to step.

A retention layer keeps, for each sequence and cached head, the gated sum
of the keys' symmetric squares against their values, ``S`` of ``[D, d_v]``
in float32, and the gated sum of the squares alone, ``z`` of ``[D]``: no
keys and values, constant in the context. Per token ``t``, cached head
``c`` and query head ``a`` of its group (``g_t = exp(lg_t)`` the gate)::

    S_t = g_t S_{t-1} + phi(k_t) v_t^T        z_t = g_t z_{t-1} + phi(k_t)
    y_t = phi(q_t)^T S_t / (phi(q_t)^T z_t + eps)

which is the same function as attention with the weights ``exp(G_t - G_j)
(q_t . k_j)^2`` over ``j <= t`` normalised by their sum (``G`` the running
sum of ``lg``): ``retention_attention`` below, the no-cache oracle.

``phi`` is the symmetric square laid out by ROTATION: row ``r`` of
``phi(u)`` is ``m_r * u * roll(u, r)`` for ``r = 0 .. d/2``, with ``m_0 =
1`` (the squares), ``m_r = sqrt(2)`` (each unordered pair at circular
distance ``r`` once) and ``m_{d/2} = 1`` (each pair at distance ``d/2``
twice), so that ``phi(q) . phi(k) = (q . k)^2`` exactly. That is ``(d/2 +
1) x d`` entries, 8,320 at ``d`` 128 where the mathematical ``D`` is ``d
(d + 1) / 2`` = 8,256: every row is a whole lane vector formed by one lane
rotation, and no entry is gathered.

Both arrays live in a table of ``max_num_seqs + 1`` slots (slot 0 is
trash), ``S`` as ``[N + 1, kvH, R, d_v, d]`` and ``z`` as ``[N + 1, kvH,
R, d]``; a span names its slot in ``state_slot`` and a span that starts at
position 0 starts from zeros IN THE PROGRAM (docs/architecture/
unified_step.md "State that is not pages"). ``retention_ragged`` advances
every span of a dispatch: spans of one row through the
``retention_recurrent`` Pallas kernel, spans of more rows chunk by chunk
through ``retention_chunk`` on the MXU (ops/pallas/retention.py);
``retention_ragged_xla`` is the XLA twin of both, the path off the TPU.
The state after a span does not depend on how the prompt was cut.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from dynamo_tpu.ops.linear_attention import span_rows

#: added to the normaliser
EPS = 1e-6


def phi_rows(d: int) -> int:
    """Rows of ``phi`` at head size ``d`` (even)."""
    assert d % 2 == 0, "the rotation layout of phi needs an even head size"
    return d // 2 + 1


def phi_weights(d: int) -> jnp.ndarray:
    """``m_r``, one a row of ``phi``."""
    r = jnp.arange(phi_rows(d))
    return jnp.where((r == 0) | (r == d // 2), 1.0, 2.0 ** 0.5).astype(
        jnp.float32
    )


def phi(u: jnp.ndarray) -> jnp.ndarray:
    """The symmetric square of ``u`` [..., d] -> [..., R, d] (float32)."""
    u = u.astype(jnp.float32)
    d = u.shape[-1]
    rolled = jnp.stack(
        [jnp.roll(u, r, axis=-1) for r in range(phi_rows(d))], axis=-2
    )
    return phi_weights(d)[:, None] * u[..., None, :] * rolled


def state_shapes(n_slots: int, kv_heads: int, d: int) -> tuple:
    """The shapes of ``(S, z)`` over ``n_slots`` slots; ``z``'s rows are
    padded to a sublane tile (the rows past ``R`` stay zero)."""
    R = phi_rows(d)
    return (
        (n_slots, kv_heads, R, d, d), (n_slots, kv_heads, -(-R // 8) * 8, d)
    )


def retention_attention(q, k, v, lg, *, block: int = 512) -> jnp.ndarray:
    """The attention form over ONE sequence from position 0, no state: the
    oracle. ``q`` [T, H, d] (scaled: the ``1/sqrt(d)`` inside the power is
    the caller's), ``k, v`` [T, kvH, d], ``lg`` [T, kvH] the log gate.
    Returns y [T, H, d] float32. Rows go in blocks of ``block`` so that the
    [rows, T] weights fit."""
    T, H, d = q.shape
    kvH = k.shape[1]
    G = H // kvH
    hi = jax.lax.Precision.HIGHEST
    q = q.astype(jnp.float32).reshape(T, kvH, G, d)
    k, v = k.astype(jnp.float32), v.astype(jnp.float32)
    cum = jnp.cumsum(lg.astype(jnp.float32), axis=0)            # [T, kvH]
    out = []
    for t0 in range(0, T, block):
        t1 = min(t0 + block, T)
        s = jnp.einsum("tcgd,jcd->cgtj", q[t0:t1], k[:t1], precision=hi)
        decay = cum[t0:t1].T[:, :, None] - cum[:t1].T[:, None, :]  # [c,t,j]
        seen = jnp.arange(t0, t1)[:, None] >= jnp.arange(t1)[None, :]
        w = jnp.where(
            seen[None, None], jnp.exp(jnp.minimum(decay, 0.0))[:, None] * s * s,
            0.0,
        )                                                        # [c,g,t,j]
        num = jnp.einsum("cgtj,jcd->tcgd", w, v[:t1], precision=hi)
        den = w.sum(-1).transpose(2, 0, 1)[..., None]            # [t,c,g,1]
        out.append(num / (den + EPS))
    return jnp.concatenate(out, axis=0).reshape(T, H, d)


def retention_ragged_xla(
    q, k, v, lg, state, token_seq, token_pos, q_start, q_len, row_start,
    state_slot,
):
    """The recurrence over every span's rows in flat order, one row a step
    of a loop that runs as many steps as spans own rows.

    ``q`` [T, H, d] (scaled), ``k, v`` [T, kvH, d], ``lg`` [T, kvH],
    ``state`` the ``(S, z)`` pair. Returns (y [T, H, d] float32, the new
    state); rows no span owns read zero."""
    del row_start
    S, z = state
    T, H, d = q.shape
    kvH = k.shape[1]
    G = H // kvH
    z_pad = ((0, 0), (0, z.shape[2] - phi_rows(d)), (0, 0))
    j, owned = span_rows(token_seq, token_pos, q_start, q_len)
    order = jnp.argsort(~owned, stable=True)       # owned rows first
    hi = jax.lax.Precision.HIGHEST
    q = q.astype(jnp.float32).reshape(T, kvH, G, d)
    k, v, lg = (x.astype(jnp.float32) for x in (k, v, lg))

    def body(i, carry):
        cur_S, cur_z, S, z, y = carry
        t = order[i]
        s = token_seq[t]
        slot = state_slot[s]
        first, last = j[t] == 0, j[t] == q_len[s] - 1
        fresh = q_start[s] == 0
        held_S = jnp.where(fresh, 0.0, S[slot].astype(jnp.float32))
        held_z = jnp.where(fresh, 0.0, z[slot].astype(jnp.float32))
        prev_S = jnp.where(first, held_S, cur_S)
        prev_z = jnp.where(first, held_z, cur_z)
        g = jnp.exp(lg[t])                                      # [kvH]
        pk = phi(k[t])                                          # [kvH, R, d]
        new_S = (
            g[:, None, None, None] * prev_S
            + pk[:, :, None, :] * v[t][:, None, :, None]
        )                                                # [kvH, R, d_v, d]
        new_z = g[:, None, None] * prev_z + jnp.pad(pk, z_pad)
        pq = phi(q[t])                                          # [kvH,G,R,d]
        num = jnp.einsum("cgri,crvi->cgv", pq, new_S, precision=hi)
        den = jnp.einsum(
            "cgri,cri->cg", pq, new_z[:, : pk.shape[1]], precision=hi
        )
        y = y.at[t].set((num / (den[..., None] + EPS)).reshape(H, d))
        S = S.at[slot].set(
            jnp.where(last, new_S, S[slot].astype(jnp.float32)).astype(S.dtype)
        )
        z = z.at[slot].set(
            jnp.where(last, new_z, z[slot].astype(jnp.float32)).astype(z.dtype)
        )
        return new_S, new_z, S, z, y

    init = (
        jnp.zeros(S.shape[1:], jnp.float32), jnp.zeros(z.shape[1:], jnp.float32),
        S, z, jnp.zeros((T, H, d), jnp.float32),
    )
    _, _, S, z, y = jax.lax.fori_loop(0, owned.sum(), body, init)
    return y, (S, z)


def chunk_tiles(q_len, row_start, T: int, C: int):
    """The longer spans cut into tiles of ``C`` rows: for each of the
    ``T // C + S`` tiles (static; more than any dispatch fills) whether it
    is used, its span, its offset within the span, the flat rows it holds
    ([NT, C], ``T`` where a row is past the span's end) and which of them
    belong to the span. The used tiles come first, a span's in order."""
    S = q_len.shape[0]
    NT = T // C + S
    n_tiles = jnp.where(q_len > 1, -(-q_len // C), 0)           # [S]
    first = jnp.cumsum(n_tiles) - n_tiles                       # [S]
    tile = jnp.arange(NT)
    span = jnp.sum(tile[:, None] >= (first + n_tiles)[None, :], axis=1)
    used = span < S
    span_c = jnp.minimum(span, S - 1)
    off = (tile - first[span_c]) * C                            # [NT]
    within = off[:, None] + jnp.arange(C)[None, :]              # [NT, C]
    valid = used[:, None] & (within < q_len[span_c][:, None])
    rows = jnp.where(valid, row_start[span_c][:, None] + within, T)
    return used, span_c, off, rows, valid


def retention_ragged(
    q, k, v, lg, state, token_seq, token_pos, q_start, q_len, row_start,
    state_slot, *, use_pallas: bool,
):
    """``retention_ragged_xla``'s contract; on the Pallas path spans of one
    row go through ``retention_recurrent`` and spans of more through
    ``retention_chunk``."""
    if not use_pallas:
        return retention_ragged_xla(
            q, k, v, lg, state, token_seq, token_pos, q_start, q_len,
            row_start, state_slot,
        )
    from dynamo_tpu.ops.pallas.retention import (
        ACTIVE, FIRST, FRESH, LAST, retention_chunk, retention_recurrent,
    )

    T, H, d = q.shape
    _, owned = span_rows(token_seq, token_pos, q_start, q_len)
    fresh = FRESH * (q_start == 0)                               # [S]
    # Decode lanes: one row a span, gathered by span.
    lane = q_len == 1
    at = jnp.clip(row_start, 0, T - 1)
    y_lane, state = retention_recurrent(
        q[at], k[at], v[at], lg[at], state,
        jnp.where(lane, state_slot, 0), jnp.where(lane, ACTIVE + fresh, 0),
    )
    # Prefill quanta: tiles of C rows, a span's tiles consecutive.
    C = min(128, T)
    used, span, off, rows, valid = chunk_tiles(q_len, row_start, T, C)
    at = jnp.minimum(rows, T - 1)
    flags = jnp.where(
        used,
        ACTIVE
        + jnp.where(off == 0, FIRST + fresh[span], 0)
        + jnp.where(off + C >= q_len[span], LAST, 0),
        0,
    )
    y_tile, state = retention_chunk(
        q[at], k[at], v[at], jnp.where(valid, lg[at].transpose(2, 0, 1), 0.0),
        state, state_slot[span], flags, valid.sum(axis=1),
    )                                                    # [NT, C, H, d]
    y_rows = jnp.zeros((T, H, d), jnp.float32).at[rows.reshape(-1)].set(
        y_tile.reshape(-1, H, d), mode="drop"
    )
    multi = owned & (q_len[token_seq] > 1)
    y = jnp.where(
        multi[:, None, None], y_rows,
        jnp.where((owned & ~multi)[:, None, None], y_lane[token_seq], 0.0),
    )
    return y, state
