"""The step of a block-diffusion model (SDAR family): spans of whole
blocks of ``block_length`` tokens through a paged cache under the mask by
block (a row sees its own block whole and every block before it), logits
read unshifted at every row of a span's last block, and a commit rule that
runs in the served program.

The fact that makes one reference pass enough: under the mask by block a
full pass over clean tokens gives, for every block, exactly what that
block's **commit pass** computes (the block's final ids fed at its
positions, every earlier block read from the cache); and for the **last**
block of a sequence it gives what a **denoising pass** computes if the
sample carries mask ids there, since no later block exists to see them.

So a sequence of prompt length ``n`` consumes ``n - n % B`` tokens as its
prompt's whole blocks and ``decode_steps`` blocks behind them:

* prefill: the prompt's blocks in chunks that end on block boundaries and
  share ragged dispatches at the top budget rung; the rows read are each
  chunk's last block (``decode`` false; the program hands out no token
  there, ``judged`` false);
* the commit passes of the first ``decode_steps - 1`` blocks behind the
  prompt, all sequences' in one dispatch a step (``decode`` true,
  ``judged`` false);
* the last block's denoising pass: 1 to ``B`` of its rows, seeded, are
  fed as masks, and the mask id is planted **in place** in ``sample`` at
  those positions, which ``check.compare`` hands to the reference next.
  ``judged`` is true on the rows the served program committed, ``served``
  its own token there.
"""

from __future__ import annotations

import numpy as np

GREEDY = (0.0, 0, 1.0)


def sample_len(n: int, decode_steps: int, *, block_length: int,
               mask_token_id: int) -> int:
    return n - n % block_length + block_length * decode_steps


def plan_prefill(lens, B: int, budget: int, lanes: int):
    """Dispatches as ``[(sequence, prefix_len, new_tokens), ...]``: the
    prompts' whole blocks packed greedily into the budget and the
    metadata rows, a long one split across dispatches on a block
    boundary."""
    steps, cur, room = [], [], budget
    for b, n in enumerate(lens):
        done, whole = 0, n - n % B
        while done < whole:
            take = min(whole - done, room - room % B)
            if take <= 0 or len(cur) == lanes:
                steps.append(cur)
                cur, room = [], budget
                continue
            cur.append((b, done, take))
            done += take
            room -= take
    if cur:
        steps.append(cur)
    return steps


def drive(runner, sample, lens, decode_steps, seed, /, *,
          block_length: int, mask_token_id: int) -> dict:
    """The sample through ``runner``, dispatch by dispatch; plants the
    mask ids of each sequence's last block in ``sample``."""
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.models import llama

    cfg = runner.cfg
    model = cfg.model
    B = block_length
    assert (model.diffusion_block_length, model.mask_token_id) == (
        B, mask_token_id), "step_params disagree with the served model"
    bs, T, S = cfg.block_size, cfg.unified_token_budget, runner.unified_slots
    assert len(lens) <= S and decode_steps >= 1
    rng = np.random.default_rng([int(seed), 9])
    need = -(-max(sample_len(n, decode_steps, block_length=B,
                             mask_token_id=mask_token_id)
                  for n in lens) // bs)
    assert need <= cfg.max_blocks_per_seq
    ids = rng.permutation(np.arange(1, cfg.num_blocks))[: need * len(lens)]
    tables = ids.reshape(len(lens), need).tolist()

    def logits_fn(params, kv, sc, token_ids, *meta):
        q_len = meta[5]
        out = llama.unified(
            model, params, kv, token_ids, *meta, bs, attn=runner.attn,
            kv_scales=sc, draft_len=jnp.full_like(q_len, B - 1),
            verify_rows=B,
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
    judged = [[] for _ in lens]

    def dispatch(spans, fed):
        """One dispatch of ``spans`` [(b, prefix, n)] with ``fed`` their
        tokens (-1 = a row fed as a mask); reads each span's last block."""
        lanes = [
            (toks, tables[b], prefix, GREEDY)
            for (b, prefix, _n), toks in zip(spans, fed)
        ]
        block_ids = np.asarray(runner.unified_step(lanes).toks)
        # The same dispatch again for its logits: the same keys and values
        # go to the same slots.
        base, meta, *_ = runner._unified_operands(lanes, None, T)
        out = fn(*base, *meta)
        runner.kv_caches = out[1]
        if scales is not None:
            runner.kv_scales = out[2]
        logits = np.asarray(out[0][: len(lanes)])
        for s, ((b, prefix, n), toks) in enumerate(zip(spans, fed)):
            for j in range(B):
                t = toks[n - B + j]
                rows[b].append(prefix + n - B + j)
                decode[b].append(prefix >= lens[b] - lens[b] % B)
                got[b].append(logits[s, j])
                # A row fed as a mask and committed by the program is the
                # one place it hands out a token.
                judged[b].append(t < 0 and block_ids[s, j] >= 0)
                served[b].append(int(block_ids[s, j]) if t < 0 else t)

    for spans in plan_prefill(lens, B, T, S):
        dispatch(spans, [
            sample[b, prefix: prefix + n].tolist() for b, prefix, n in spans
        ])
    for step in range(decode_steps):
        spans = [(b, n - n % B + B * step, B) for b, n in enumerate(lens)]
        fed = [sample[b, p: p + B].tolist() for b, p, _ in spans]
        if step == decode_steps - 1:
            for (b, p, _), toks in zip(spans, fed):
                for j in rng.choice(B, int(rng.integers(1, B + 1)), False):
                    toks[j] = -1
                    sample[b, p + j] = mask_token_id
        dispatch(spans, fed)
    width = max(len(r) for r in rows)
    # Pad the short sequences by repeating their last row: both sides
    # then hold the same (duplicated) rows.
    for b in range(len(lens)):
        while len(rows[b]) < width:
            for per_row in (rows, decode, got, served, judged):
                per_row[b].append(per_row[b][-1])
    return {
        "rows": np.asarray(rows, np.int32),
        "decode": np.asarray(decode, bool),
        "logits": np.asarray(got, np.float32),
        "served": np.asarray(served, np.int64),
        "judged": np.asarray(judged, bool),
    }
