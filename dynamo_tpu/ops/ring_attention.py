"""Ring attention: causal attention with K/V sharded over the `sp` axis.

The long-context primitive SURVEY §5 requires natively (the reference
delegates long context to its backend engines): q, K and V are all
sharded over the sequence, so no chip ever holds the whole KV — each sp
shard holds one sequence block of q, k, v; K/V blocks rotate around the
ring via `lax.ppermute` while every shard folds them into a flash-style
online softmax (running max + normalizer). Per-chip memory is O(T/n) and
the ppermute rides the ICI ring concurrently with compute.

Causality falls out of global position masking (q_pos >= k_pos), so the
same code handles the diagonal block (intra-shard causal), fully-visible
earlier blocks, and fully-masked later blocks.

Use under shard_map with q/k/v sharded P("sp", ...) — see
`ring_attention_sharded` for the canonical binding, and
tests/test_parallel.py for the oracle equivalence proof.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def ring_attention(
    q: jnp.ndarray,  # [Tl, H, D] — this shard's query block
    k: jnp.ndarray,  # [Tl, kvH, D] — this shard's key block
    v: jnp.ndarray,  # [Tl, kvH, D]
    axis_name: str = "sp",
) -> jnp.ndarray:
    """Per-shard body (call inside shard_map over `axis_name`)."""
    n = jax.lax.psum(1, axis_name)
    r = jax.lax.axis_index(axis_name)
    Tl, H, D = q.shape
    kvH = k.shape[1]
    G = H // kvH
    scale = 1.0 / (D**0.5)

    q32 = (q.astype(jnp.float32) * scale).reshape(Tl, kvH, G, D)
    q_pos = r * Tl + jnp.arange(Tl)

    perm = [(j, (j + 1) % n) for j in range(n)]

    def fold(acc, k_cur, v_cur, src):
        o, m, l = acc
        # Scores of our q block against the k/v block currently resident
        # (originating from shard `src`), with global causal masking.
        k_pos = src * Tl + jnp.arange(Tl)
        s = jnp.einsum(
            "tkgd,skd->tkgs", q32, k_cur.astype(jnp.float32)
        )
        mask = q_pos[:, None] >= k_pos[None, :]  # [Tq, Tk]
        s = jnp.where(mask[:, None, None, :], s, NEG_INF)

        # Online softmax fold (flash-attention update). The first fold is
        # always the resident diagonal block, so m is finite before any
        # fully-masked future block arrives.
        m_new = jnp.maximum(m, s.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l * alpha + p.sum(axis=-1)
        o_new = o * alpha[..., None] + jnp.einsum(
            "tkgs,skd->tkgd", p, v_cur.astype(jnp.float32)
        )
        return (o_new, m_new, l_new)

    def step(carry, i):
        acc, k_cur, v_cur, src = carry
        # Rotate first, then fold: the resident block was folded before the
        # scan, so only n-1 rotations happen and none is wasted.
        k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
        v_cur = jax.lax.ppermute(v_cur, axis_name, perm)
        src = (src - 1) % n
        return (fold(acc, k_cur, v_cur, src), k_cur, v_cur, src), None

    o0 = jnp.zeros((Tl, kvH, G, D), jnp.float32)
    m0 = jnp.full((Tl, kvH, G), NEG_INF, jnp.float32)
    l0 = jnp.zeros((Tl, kvH, G), jnp.float32)
    acc = fold((o0, m0, l0), k, v, r)
    (acc, _, _, _), _ = jax.lax.scan(
        step, (acc, k, v, r), jnp.arange(n - 1)
    )
    o, m, l = acc
    out = o / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(Tl, H, D).astype(q.dtype)


def ring_attention_sharded(mesh, q, k, v, axis_name: str = "sp"):
    """Canonical binding: q/k/v [T, H, D] global arrays, sequence sharded
    over `axis_name`; returns [T, H, D] with the same sharding."""
    from jax.sharding import PartitionSpec as P

    spec = P(axis_name, None, None)
    fn = jax.shard_map(
        partial(ring_attention, axis_name=axis_name),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)
