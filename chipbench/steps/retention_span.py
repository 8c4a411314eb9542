"""The step of a model whose ONLY cache is a recurrent state (every layer a
retention layer, no paged cache at all), where that state is large: tens
of MB a layer a sequence. ``steps/recurrent_span.py``'s idea (a dispatch's
slots kept before its served run and put back before its logits run, so
that the state advances once) with what such a state asks:

* ``recurrent_span.py`` copies ALL the sample's slots on the device before
  every dispatch. At 273 MB a slot beside 14.2 GB resident that copy does
  not fit the chip (my chip run, PR 48: RESOURCE_EXHAUSTED in its ``save``).
  Here a dispatch's own slots are kept, a slot at a time: on the device
  where the dispatch holds ``MAX_SPANS`` spans or fewer, on the HOST where
  it holds more (a WIDE dispatch).
* The plan is its own (``plan_steps``): the sample has as many sequences as
  the served batch has lanes. Each prompt is prefilled in quanta of the top
  budget rung with ONE decode lane of a sequence that is already prefilled
  beside every quantum, as a served mixed step has; the LAST (longest)
  prompt's first quantum goes beside a lane of EVERY other sequence (wide);
  then one decode dispatch with EVERY sequence live (wide), and what is
  left in dispatches of ``MAX_SPANS`` lanes. So the lanes' grid and the
  slots of a full batch are compared, as the timed window runs them.
* Every sequence gives ``rows`` rows: its prefill spans' last rows and
  decode rows for the rest (a short prompt decodes longer), so the arrays
  are rectangular with no row repeated and a quantile weighs each once.
* The model has no pool: a lane's block table is empty and nothing is drawn
  from ``num_blocks``.
* ``check.free`` gives back the parameters and the (empty) cache only: the
  state table is deleted here, before the drive returns.

One row for each span, at its last position, and the served program hands
out a token in every one: all rows are judged."""

from __future__ import annotations

import numpy as np

from chipbench.steps.span import GREEDY


#: the spans of a dispatch whose slots are kept on the device: two copies of
#: 273 MB fit the 0.68 GB that the cell leaves free, a third does not
MAX_SPANS = 2


def quanta(n: int, quantum: int) -> int:
    return -(-n // quantum)


def sample_len(n: int, decode_steps: int, *, rows: int, quantum: int) -> int:
    """The prompt and a decode step for each of ``rows`` rows that is not a
    prefill quantum's."""
    del decode_steps
    return n + rows - quanta(n, quantum)


def plan_steps(lens, rows: int, quantum: int):
    """Dispatches as lists of ``(sequence, prefix, n_tokens)``; a
    sequence's spans in order, ``rows`` spans a sequence. A dispatch of
    more than ``MAX_SPANS`` spans is a wide one."""
    B = len(lens)
    left = {b: rows - quanta(n, quantum) for b, n in enumerate(lens)}
    assert min(left.values()) >= 2, "a decode row for each wide dispatch"
    decoded = dict.fromkeys(range(B), 0)
    ready: list[int] = []                       # prefilled, in order
    turn = 0
    steps = []

    def lane(b):
        left[b] -= 1
        decoded[b] += 1
        return (b, lens[b] + decoded[b] - 1, 1)

    def beside(keep: int):
        """The next ready sequence in turn that has more than ``keep``
        decode steps to go, as one lane."""
        nonlocal turn
        for i in range(len(ready)):
            b = ready[(turn + i) % len(ready)]
            if left[b] > keep:
                turn += i + 1
                return [lane(b)]
        return []

    for b, n in enumerate(lens):
        done = 0
        while done < n:
            if b == B - 1 and done == 0:
                lanes = [lane(o) for o in ready]            # wide, mixed
            else:
                # (a step kept for each wide dispatch still to come)
                lanes = beside(2 if b < B - 1 else 1)
            take = min(n - done, quantum - len(lanes))
            steps.append(lanes + [(b, done, take)])
            done += take
        ready.append(b)
    steps.append([lane(b) for b in ready])                  # wide, decode
    while any(left.values()):
        most = sorted(ready, key=lambda b: -left[b])[:MAX_SPANS]
        steps.append([lane(b) for b in most if left[b]])
    return steps


def drive(runner, sample, lens, decode_steps, seed, /, *, rows: int,
          quantum: int):
    """The sample through ``runner``, dispatch by dispatch; deletes the
    runner's state."""
    del seed
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.models import llama

    cfg = runner.cfg
    bs, T = cfg.block_size, cfg.unified_token_budget
    assert runner.rec_state is not None, "the model keeps no recurrent state"
    assert not cfg.model.has_pool, "steps/recurrent_span.py drives a pool"
    assert len(lens) <= cfg.max_num_seqs, "a slot for each sample sequence"
    assert quantum == T, "check.step_params.quantum is the top budget rung"
    assert rows == quanta(max(lens), T) + decode_steps, (
        "check.step_params.rows is the longest prompt's quanta and "
        "check.decode_steps")
    plan = plan_steps(lens, rows, T)
    for b, n in enumerate(lens):
        spans = sum(s[0] == b and s[1] < n for step in plan for s in step)
        assert spans == quanta(n, T), (
            f"the plan cuts a prompt of {n} tokens into {spans} quanta "
            f"where sample_len counts {quanta(n, T)}: choose another length")

    def logits_fn(params, kv, rec, state_slot, token_ids, *meta):
        out = llama.unified(
            cfg.model, params, kv, token_ids, *meta, bs, attn=runner.attn,
            rec_state=rec, state_slot=state_slot,
        )
        return out[0].astype(jnp.float32), out[1], out[2]

    fn = jax.jit(logits_fn, donate_argnums=(1, 2))
    # (a slot at a time: a copy of one slot beside the table is what fits)
    take = jax.jit(lambda rec, at: jax.tree.map(lambda a: a[at], rec))
    put = jax.jit(
        lambda rec, at, kept: jax.tree.map(
            lambda a, k: a.at[at].set(k), rec, kept),
        donate_argnums=(0,),
    )
    got = [[] for _ in lens]
    at_row = [[] for _ in lens]
    decode = [[] for _ in lens]
    served = [[] for _ in lens]
    for spans in plan:
        lanes = [
            (sample[b, prefix : prefix + n].tolist(), [], prefix, GREEDY)
            for b, prefix, n in spans
        ]
        slots = [b + 1 for b, _, _ in spans]     # slot 0 is the trash slot
        wide = len(spans) > MAX_SPANS
        kept = []
        for slot in slots:
            k = take(runner.rec_state, jnp.int32(slot))
            kept.append(jax.device_get(k) if wide else k)
        toks = np.asarray(
            runner.unified_step(lanes, state_slots=slots).last)
        # The same dispatch again for its logits, from the state it began
        # with: the state ends where the served run left it.
        for slot, k in zip(slots, kept):
            runner.rec_state = put(runner.rec_state, jnp.int32(slot), k)
        del kept
        # (at the top rung whatever the served program took: one program)
        (params, kv, _), meta, *_ = runner._unified_operands(lanes, None, T)
        state_slot = np.zeros(runner.unified_slots, np.int32)
        state_slot[: len(slots)] = slots
        logits, runner.kv_caches, runner.rec_state = fn(
            params, kv, runner.rec_state, state_slot, *meta)
        logits = np.asarray(logits)
        for s, (b, prefix, n) in enumerate(spans):
            at_row[b].append(prefix + n - 1)
            decode[b].append(prefix >= lens[b])
            got[b].append(logits[s])
            served[b].append(int(toks[s]))
    # The state is no array of the parameters or the cache: give it back.
    for leaf in jax.tree.leaves(runner.rec_state):
        leaf.delete()
    runner.rec_state = None
    at_row = np.asarray(at_row, np.int32)        # [B, rows]: no row repeated
    assert at_row.shape == (len(lens), rows)
    return {
        "rows": at_row, "decode": np.asarray(decode, bool),
        "logits": np.asarray(got, np.float32),
        "served": np.asarray(served, np.int64),
        "judged": np.ones(at_row.shape, bool),
    }
