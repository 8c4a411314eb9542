"""The step of a model that keeps a recurrent state beside the paged cache
(linear-attention layers among softmax ones): ``steps/span.py``'s plan
(prompts prefilled in chunks that share ragged dispatches at the top budget
rung, a long one cut across dispatches, then decode dispatches of one token
a lane), with what a state that is not pages needs.

* A sequence's state lives in ITS slot of the runner's state table, not at
  its place in the dispatch: sample sequence ``b`` owns slot ``b + 1`` (slot
  0 is the trash slot) and every dispatch names its spans' slots
  (``unified_step(..., state_slots=...)``), as the engine does.
* ``span.py`` runs every dispatch twice, once through the served program
  for its tokens and once through the model function for its logits: the
  same keys and values go to the same pages, but a second run would
  advance a recurrent state twice. Here the sample's slots are saved before
  the served run and put back before the logits run, so both start from
  the same state and the second leaves what the first left.
* ``check.free`` gives back the parameters and the paged cache only: the
  state table is deleted here, before the drive returns.

One row for each span, at its last position, and the served program hands
out a token in every one: all rows are judged."""

from __future__ import annotations

import numpy as np

from chipbench.steps.span import GREEDY, plan_steps, sample_len  # noqa: F401


def drive(runner, sample, lens, decode_steps, seed, /) -> dict:
    """The sample through ``runner``, dispatch by dispatch; leaves its keys
    and values in the runner's cache and deletes the runner's state."""
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.models import llama

    cfg = runner.cfg
    bs, T = cfg.block_size, cfg.unified_token_budget
    assert runner.rec_state is not None, "the model keeps no recurrent state"
    assert len(lens) <= cfg.max_num_seqs, "a slot for each sample sequence"
    rng = np.random.default_rng([int(seed), 8])
    need = -(-(max(lens) + decode_steps) // bs)
    assert need <= cfg.max_blocks_per_seq
    ids = rng.permutation(np.arange(1, cfg.num_blocks))[: need * len(lens)]
    tables = ids.reshape(len(lens), need).tolist()
    mine = jnp.arange(1, len(lens) + 1)           # the sample's slots

    def logits_fn(params, kv, rec, state_slot, token_ids, *meta):
        out = llama.unified(
            cfg.model, params, kv, token_ids, *meta, bs, attn=runner.attn,
            rec_state=rec, state_slot=state_slot,
        )
        return out[0].astype(jnp.float32), out[1], out[2]

    fn = jax.jit(logits_fn, donate_argnums=(1, 2))
    save = jax.jit(lambda rec: jax.tree.map(lambda a: a[mine], rec))
    restore = jax.jit(
        lambda rec, saved: jax.tree.map(
            lambda a, s: a.at[mine].set(s), rec, saved),
        donate_argnums=(0,),
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
        slots = [b + 1 for b, _, _ in spans]
        before = save(runner.rec_state)
        toks = np.asarray(
            runner.unified_step(lanes, state_slots=slots).last)
        # The same dispatch again for its logits, from the state it began
        # with: the same keys and values go to the same pages, and the
        # state ends where the served run left it.
        runner.rec_state = restore(runner.rec_state, before)
        (params, kv, _), meta, *_ = runner._unified_operands(lanes, None, T)
        state_slot = np.zeros(runner.unified_slots, np.int32)
        state_slot[: len(slots)] = slots
        logits, runner.kv_caches, runner.rec_state = fn(
            params, kv, runner.rec_state, state_slot, *meta)
        logits = np.asarray(logits)
        for s, (b, prefix, n) in enumerate(spans):
            rows[b].append(prefix + n - 1)
            decode[b].append(prefix >= lens[b])
            got[b].append(logits[s])
            served[b].append(int(toks[s]))
    # The state is no array of the parameters or the cache: give it back.
    for leaf in jax.tree.leaves(runner.rec_state):
        leaf.delete()
    runner.rec_state = None
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
