"""The step of a model that reads spans of tokens through a paged cache
under a causal mask: prompts prefilled in chunks that share ragged
dispatches at the top budget rung, then decode dispatches of one token a
lane. One row for each span, at its last position, and the served program
hands out a token in every one: all rows are judged."""

from __future__ import annotations

import numpy as np

GREEDY = (0.0, 0, 1.0)


def sample_len(n: int, decode_steps: int) -> int:
    return n + decode_steps


def plan_steps(lens, decode_steps: int, budget: int):
    """Dispatches as ``[(sequence, prefix_len, new_tokens), ...]``: prompts
    packed greedily into the budget, a long one split across dispatches
    (chunked prefill), then ``decode_steps`` dispatches of one token each."""
    steps, cur, room = [], [], budget
    for b, n in enumerate(lens):
        done = 0
        while done < n:
            take = min(n - done, room)
            cur.append((b, done, take))
            done += take
            room -= take
            if room == 0:
                steps.append(cur)
                cur, room = [], budget
    if cur:
        steps.append(cur)
    for i in range(decode_steps):
        steps.append([(b, n + i, 1) for b, n in enumerate(lens)])
    return steps


def drive(runner, sample, lens, decode_steps, seed, /) -> dict:
    """The sample through ``runner``, dispatch by dispatch; leaves its keys
    and values in the runner's cache."""
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.models import llama

    cfg = runner.cfg
    bs, T = cfg.block_size, cfg.unified_token_budget
    rng = np.random.default_rng([int(seed), 8])
    need = -(-(max(lens) + decode_steps) // bs)
    assert need <= cfg.max_blocks_per_seq
    ids = rng.permutation(np.arange(1, cfg.num_blocks))[: need * len(lens)]
    tables = ids.reshape(len(lens), need).tolist()

    def logits_fn(params, kv, sc, token_ids, *meta):
        out = llama.unified(
            cfg.model, params, kv, token_ids, *meta, bs, attn=runner.attn,
            kv_scales=sc,
        )
        return (out[0].astype(jnp.float32), *out[1:])

    scales = runner.kv_scales
    kv_sh = jax.tree.map(lambda a: a.sharding, runner.kv_caches)
    out_sh = (None, kv_sh) if scales is None else (None, kv_sh, scales.sharding)
    fn = jax.jit(
        logits_fn, donate_argnums=(1,) if scales is None else (1, 2),
        out_shardings=out_sh,
    )
    rows = [[] for _ in lens]
    decode = [[] for _ in lens]
    got = [[] for _ in lens]
    served = [[] for _ in lens]
    for spans in plan_steps(lens, decode_steps, T):
        lanes = [
            (sample[b, prefix : prefix + n].tolist(), tables[b], prefix, GREEDY)
            for b, prefix, n in spans
        ]
        toks = np.asarray(runner.unified_step(lanes).last)
        # The same dispatch again for its logits: the same keys and values
        # go to the same slots.
        base, meta, *_ = runner._unified_operands(lanes, None, T)
        out = fn(*base, *meta)
        runner.kv_caches = out[1]
        if scales is not None:
            runner.kv_scales = out[2]
        logits = np.asarray(out[0])
        for s, (b, prefix, n) in enumerate(spans):
            rows[b].append(prefix + n - 1)
            decode[b].append(prefix >= lens[b])
            got[b].append(logits[s])
            served[b].append(int(toks[s]))
    width = max(len(r) for r in rows)
    # Pad the short sequences by repeating their last row: both sides
    # then hold the same (duplicated) rows.
    for b in range(len(lens)):
        while len(rows[b]) < width:
            for per_row in (rows, decode, got, served):
                per_row[b].append(per_row[b][-1])
    rows = np.asarray(rows, np.int32)
    return {
        "rows": rows, "decode": np.asarray(decode, bool),
        "logits": np.asarray(got, np.float32),
        "served": np.asarray(served, np.int64),
        "judged": np.ones(rows.shape, bool),
    }
