"""The step of a model that keeps its cache by layer group (window and full
attention layers side by side, each kind with a pool and a block table of
its own): ``steps/span.py``'s plan (prompts prefilled in chunks that share
ragged dispatches at the top budget rung, a long one cut across
dispatches, then decode dispatches of one token a lane), with a lane's
second place holding one table a group, as the engine hands them.

* The full-attention group's table of a sequence holds a block for every
  position, drawn once from its pool.
* A windowed group's table is grown a span at a time from ITS pool, before
  the dispatch that writes the span, and after the dispatch every block
  that lies wholly behind the window of the sequence's next query goes
  back to the pool and its entry becomes 0, the sentinel: what
  ``Scheduler.fund_span`` and ``Scheduler.evict_behind_window`` do. A
  block given back is handed out again, to this sequence or another, so a
  kernel that read behind the window would read another sequence's keys.

One row for each span, at its last position, and the served program hands
out a token in every one: all rows are judged."""

from __future__ import annotations

import numpy as np

from chipbench.steps.span import GREEDY, plan_steps, sample_len  # noqa: F401


def drive(runner, sample, lens, decode_steps, seed, /) -> dict:
    """The sample through ``runner``, dispatch by dispatch; leaves its keys
    and values in the runner's pools."""
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.models import llama

    cfg = runner.cfg
    bs, T = cfg.block_size, cfg.unified_token_budget
    windows = cfg.model.cache_groups
    pools = runner.group_blocks
    assert len(windows) > 1 and not windows[0], \
        "the model keeps one cache group, or none for full attention"
    assert len(lens) <= cfg.max_num_seqs
    rng = np.random.default_rng([int(seed), 8])
    need = -(-(max(lens) + decode_steps) // bs)
    assert need <= cfg.max_blocks_per_seq
    ids = rng.permutation(np.arange(1, pools[0]))[: need * len(lens)]
    full = ids.reshape(len(lens), need).tolist()
    # A windowed group's pool as a stack, and each sequence's table in it.
    free = [rng.permutation(np.arange(1, n)).tolist() for n in pools[1:]]
    tables = [[[] for _ in lens] for _ in pools[1:]]
    behind = [[0] * len(lens) for _ in pools[1:]]
    released = 0

    def logits_fn(params, kv, token_ids, *meta):
        out = llama.unified(
            cfg.model, params, kv, token_ids, *meta, bs, attn=runner.attn)
        return out[0].astype(jnp.float32), out[1]

    fn = jax.jit(logits_fn, donate_argnums=(1,))
    rows = [[] for _ in lens]
    decode = [[] for _ in lens]
    got = [[] for _ in lens]
    served = [[] for _ in lens]
    for spans in plan_steps(lens, decode_steps, T):
        for b, prefix, n in spans:
            for g, mine in enumerate(tables):
                while len(mine[b]) * bs < prefix + n:
                    mine[b].append(free[g].pop())
        lanes = [
            (sample[b, prefix : prefix + n].tolist(),
             (full[b], *(mine[b] for mine in tables)), prefix, GREEDY)
            for b, prefix, n in spans
        ]
        toks = np.asarray(runner.unified_step(lanes).last)
        # The same dispatch again for its logits: the same keys and values
        # go to the same slots of each pool.
        (params, kv, _), meta, *_ = runner._unified_operands(lanes, None, T)
        logits, runner.kv_caches = fn(params, kv, *meta)
        logits = np.asarray(logits)
        for s, (b, prefix, n) in enumerate(spans):
            rows[b].append(prefix + n - 1)
            decode[b].append(prefix >= lens[b])
            got[b].append(logits[s])
            served[b].append(int(toks[s]))
            for g, w in enumerate(windows[1:]):
                upto = max(prefix + n - w, 0) // bs
                for i in range(behind[g][b], upto):
                    free[g].append(tables[g][b][i])
                    tables[g][b][i] = 0
                    released += 1
                behind[g][b] = max(behind[g][b], upto)
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
        "released": released,
    }
