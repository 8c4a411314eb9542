"""Token sampling inside the jitted step: greedy / temperature / top-k /
top-p, fully vectorized per batch slot.

Dynamic per-sequence k and p are handled against a static candidate cap
(``MAX_TOP_K``): we take the top-64 logits once (MXU/VPU friendly), then mask
per-sequence within that window — no data-dependent shapes under jit.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

MAX_TOP_K = 64
# Static cap on top-logprob alternatives returned per token (the OpenAI
# surface rejects top_logprobs above this — a static shape under jit).
# Defined in the (jax-free) protocol layer so the HTTP front end can
# validate without importing jax.
from dynamo_tpu.llm.protocols.common import MAX_LOGPROBS  # noqa: E402


def lane_keys(
    key: jax.Array,             # global PRNG key (engine step stream)
    seed: jnp.ndarray,          # [B] int64/int32; < 0 means unseeded
    sample_pos: jnp.ndarray,    # [B] int32 — index of the token being sampled
) -> jax.Array:
    """Per-lane sampling keys [B].

    A seeded lane's key depends ONLY on (seed, token index) — so a request
    with `seed` set reproduces its samples regardless of what other traffic
    it was batched with or which engine step picked it up (the determinism
    contract of the OpenAI `seed` parameter; reference:
    lib/llm/src/protocols/common.rs:248 SamplingOptions.seed). Unseeded
    lanes draw from the engine's global stream, decorrelated per lane.
    """
    B = seed.shape[0]

    def one(lane, s, p):
        seeded = jax.random.fold_in(
            jax.random.PRNGKey(jnp.maximum(s, 0).astype(jnp.uint32)), p
        )
        unseeded = jax.random.fold_in(key, lane)
        return jnp.where(s >= 0, seeded, unseeded)

    return jax.vmap(one)(jnp.arange(B), seed, sample_pos)


def apply_penalties(
    logits: jnp.ndarray,        # [B, V]
    counts: jnp.ndarray,        # [B, V] int — output-token occurrence counts
    frequency_penalty: jnp.ndarray,  # [B] float32
    presence_penalty: jnp.ndarray,   # [B] float32
) -> jnp.ndarray:
    """OpenAI-style penalties over the generated-token counts:
    ``logit[t] -= freq * count[t] + pres * (count[t] > 0)``."""
    c = counts.astype(logits.dtype)
    return (
        logits
        - frequency_penalty[:, None] * c
        - presence_penalty[:, None] * (c > 0)
    )


def token_logprobs(
    logits: jnp.ndarray,        # [B, V]
    chosen: jnp.ndarray,        # [B] int32 — the sampled token ids
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """(chosen_logprob [B], top_ids [B, MAX_LOGPROBS], top_logprobs
    [B, MAX_LOGPROBS]) — log-softmax of the distribution actually sampled
    from (post-penalty), at temperature-1 scale, like the reference's
    engines report."""
    lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    chosen_lp = jnp.take_along_axis(lp, chosen[:, None].astype(jnp.int32), axis=1)[:, 0]
    top_lps, top_ids = jax.lax.top_k(lp, MAX_LOGPROBS)
    return chosen_lp, top_ids.astype(jnp.int32), top_lps


def sample_tokens(
    logits: jnp.ndarray,        # [B, V] float32
    key: jax.Array,             # PRNG key
    temperature: jnp.ndarray,   # [B] float32; <=0 means greedy
    top_k: jnp.ndarray,         # [B] int32; 0 means disabled
    top_p: jnp.ndarray,         # [B] float32; >=1 means disabled
    seed: jnp.ndarray | None = None,        # [B]; < 0 means unseeded
    sample_pos: jnp.ndarray | None = None,  # [B] token index being sampled
) -> jnp.ndarray:
    """Returns sampled token ids [B] int32. With ``seed``/``sample_pos``,
    seeded lanes sample from a per-lane deterministic stream (lane_keys).
    All-greedy batches skip the top-k window at runtime (see below)."""
    B, V = logits.shape
    greedy_ids = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    if seed is not None and sample_pos is None:
        # Zero-filling would reuse ONE key for every step of a seeded
        # lane (degenerate repeated draws) — refuse instead.
        raise ValueError("sample_pos is required when seed is given")

    def sampled(_):
        cap = min(MAX_TOP_K, V)
        top_vals, top_idx = jax.lax.top_k(logits, cap)  # [B, cap] sorted desc

        temp = jnp.maximum(temperature, 1e-6)[:, None]
        scaled = top_vals / temp

        # top-k mask within the candidate window
        k_eff = jnp.where(top_k <= 0, cap, jnp.minimum(top_k, cap))[:, None]
        rank = jnp.arange(cap)[None, :]
        mask = rank < k_eff

        # top-p (nucleus) mask over the sorted candidates
        probs = jax.nn.softmax(jnp.where(mask, scaled, -1e30), axis=-1)
        cumulative = jnp.cumsum(probs, axis=-1)
        p_eff = jnp.where(top_p <= 0, 1.0, jnp.minimum(top_p, 1.0))[:, None]
        # keep tokens whose cumulative mass *before* them is < p (always
        # keep #1)
        before = cumulative - probs
        mask2 = mask & (before < p_eff)

        masked = jnp.where(mask2, scaled, -1e30)
        if seed is None:
            sampled_pos = jax.random.categorical(key, masked, axis=-1)  # [B]
        else:
            keys = lane_keys(key, seed, sample_pos)
            sampled_pos = jax.vmap(
                lambda k, row: jax.random.categorical(k, row)
            )(keys, masked)
        return jnp.take_along_axis(
            top_idx, sampled_pos[:, None], axis=-1
        )[:, 0].astype(jnp.int32)

    # All-greedy batches (the common serving case) skip the whole top-k
    # window at RUNTIME — a real XLA conditional, so no extra compiles.
    sampled_ids = jax.lax.cond(
        jnp.all(temperature <= 0.0), lambda _: greedy_ids, sampled, None
    )
    return jnp.where(temperature <= 0.0, greedy_ids, sampled_ids)


def commit_floor_rows(block_length: int, denoising_steps: int) -> int:
    """The commit rule's floor: the masked rows a denoising pass commits
    whatever their confidences read, so that a block is complete within
    ``denoising_steps`` passes. A pass fed that many masked rows or fewer
    comes back complete: the program's ``commit_block`` and the engine's
    compose (which then knows the block's next span before the pass
    retires) both go by this one expression."""
    return -(-block_length // max(denoising_steps, 1))


def commit_block(
    logits: jnp.ndarray,        # [S, B, V] float32 — a span's block rows
    fed: jnp.ndarray,           # [S, B] int32 — the ids the rows were fed
    masked: jnp.ndarray,        # [S, B] bool — rows fed as masks
    key: jax.Array,
    temperature: jnp.ndarray,   # [S]
    top_k: jnp.ndarray,         # [S]
    top_p: jnp.ndarray,         # [S]
    seed: jnp.ndarray,          # [S]
    first_pos: jnp.ndarray,     # [S] position of the block's first row
    threshold: float,
    floor_rows: int,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """One denoising pass's commit rule (block diffusion, SDAR family):
    every row is sampled like a decode row and the sampled token's
    probability (softmax at temperature 1) is its confidence; a masked row
    is committed when its confidence reaches ``threshold``, and so are the
    ``floor_rows`` most confident masked rows whatever they read (ties to
    the lower position). Returns (ids [S, B]: a committed row's token, a
    row fed unmasked as it was fed, a row still masked -1; committed rows
    a span [S])."""
    S, B, V = logits.shape
    flat = logits.reshape(S * B, V)
    rep = lambda a: jnp.repeat(a, B)
    toks = sample_tokens(
        flat, key, rep(temperature), rep(top_k), rep(top_p), seed=rep(seed),
        sample_pos=(first_pos[:, None] + 1 + jnp.arange(B)).reshape(-1),
    ).reshape(S, B)
    chosen = jnp.take_along_axis(logits, toks[..., None], axis=-1)[..., 0]
    conf = jnp.exp(chosen - jax.nn.logsumexp(logits, axis=-1))   # [S, B]
    ranked = jnp.where(masked, conf, -1.0)
    # rank 0 = the most confident masked row; a stable sort keeps the
    # lower position ahead on a tie.
    order = jnp.argsort(-ranked, axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1, stable=True)
    commit = masked & ((conf >= threshold) | (rank < floor_rows))
    ids = jnp.where(commit, toks, jnp.where(masked, -1, fed))
    return ids.astype(jnp.int32), commit.sum(axis=-1).astype(jnp.int32)
