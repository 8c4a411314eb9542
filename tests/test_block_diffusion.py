"""Block diffusion on the served path (SDAR family): the mask by block in
both attention paths, the commit rule, the engine's block step against the
plain reference's ``generate`` token for token, preemption, prefix reuse,
and what such a model refuses."""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import sdar
from dynamo_tpu.engine import engine as engine_mod
from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.engine.engine import TpuEngine
from dynamo_tpu.engine.runner import ModelRunner, operand_layout
from dynamo_tpu.llm.protocols.common import (
    EngineOutput,
    PreprocessedRequest,
    RequestError,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.models import llama
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.ops import attention as attn_ops
from dynamo_tpu.ops.pallas.ragged_attention import (
    ragged_paged_attention_pallas,
)
from dynamo_tpu.ops.sampling import commit_block, commit_floor_rows
from dynamo_tpu.runtime.engine import Context
from stepdrive import slot_rows

pytestmark = pytest.mark.anyio

SEED = 3
#: the tiny preset under the reference's key names
PUBLISHED = dict(
    hidden_size=64, moe_intermediate_size=32, num_hidden_layers=2,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    vocab_size=384, num_experts=16, num_experts_per_tok=4,
    norm_topk_prob=True, rope_theta=1000000.0, rms_norm_eps=1e-6,
)


def tiny(threshold: float = 0.9) -> ModelConfig:
    return ModelConfig.tiny_sdar_test().scaled(confidence_threshold=threshold)


def engine_config(model, **kw) -> EngineConfig:
    base = dict(
        model=model, dtype="float32", block_size=8, num_blocks=64,
        max_num_seqs=4, max_model_len=128, seed=SEED,
        unified_token_budget=32, unified_prefill_quantum=16,
    )
    base.update(kw)
    return EngineConfig(**base)


def want_tokens(model, prompt, n):
    return sdar.generate(
        PUBLISHED, SEED, prompt, n, "float32",
        block_length=model.diffusion_block_length,
        denoising_steps=model.denoising_steps,
        mask_token_id=model.mask_token_id,
        confidence_threshold=model.confidence_threshold,
    )


async def generate(engine, prompt, n, **sampling):
    pre = PreprocessedRequest(
        token_ids=list(prompt),
        sampling=SamplingOptions(temperature=0.0, **sampling),
        stop=StopConditions(max_tokens=n, ignore_eos=True),
    )
    chunks = []
    async for raw in engine.generate(Context(pre.to_wire())):
        chunks.append(EngineOutput.from_wire(raw).token_ids)
    return chunks


# -- the mask by block in both attention paths ---------------------------

def _ragged_case(B, spans, bs=8, H=4, kvH=2, D=128, seed=0):
    """A paged cache holding each span's prefix and new rows, and the
    ragged metadata of one dispatch over ``spans`` [(prefix, n)]."""
    rng = np.random.default_rng(seed)
    S = len(spans) + 1                      # one idle metadata row
    max_blocks = 8
    total = sum(n for _, n in spans)
    T = total + 3                           # budget padding behind
    k_cache = rng.standard_normal((64 * bs, kvH, D)).astype(np.float32)
    v_cache = rng.standard_normal((64 * bs, kvH, D)).astype(np.float32)
    q = rng.standard_normal((T, H, D)).astype(np.float32)
    tables = np.zeros((S, max_blocks), np.int32)
    pages = rng.permutation(np.arange(1, 64))
    meta = {k: np.zeros(S, np.int32) for k in
            ("q_start", "q_len", "kv_len", "row_start")}
    token_seq = np.zeros(T, np.int32)
    token_pos = np.full(T, -1, np.int32)
    row = 0
    for s, (prefix, n) in enumerate(spans):
        tables[s] = pages[s * max_blocks:(s + 1) * max_blocks]
        meta["q_start"][s], meta["q_len"][s] = prefix, n
        meta["kv_len"][s], meta["row_start"][s] = prefix + n, row
        token_seq[row:row + n] = s
        token_pos[row:row + n] = prefix + np.arange(n)
        row += n
    return (jnp.asarray(q), jnp.asarray(k_cache), jnp.asarray(v_cache),
            jnp.asarray(tables), jnp.asarray(token_seq),
            jnp.asarray(token_pos),
            *(jnp.asarray(meta[k]) for k in
              ("q_start", "q_len", "kv_len", "row_start")), bs)


def _dense_oracle(case, B):
    """Every span's rows against its own keys gathered from the pages,
    under ``key // B <= query // B``, by plain softmax."""
    q, kc, vc, tables, _seq, _pos, q_start, q_len, kv_len, row_start, bs = (
        np.asarray(a) if not isinstance(a, int) else a for a in case)
    out = np.zeros_like(q)
    H, kvH = q.shape[1], kc.shape[1]
    for s in range(len(q_len)):
        n, p0, r0 = int(q_len[s]), int(q_start[s]), int(row_start[s])
        if not n:
            continue
        L = int(kv_len[s])
        slots = (tables[s][np.arange(L) // bs] * bs + np.arange(L) % bs)
        k = np.repeat(kc[slots], H // kvH, axis=1)
        v = np.repeat(vc[slots], H // kvH, axis=1)
        for i in range(n):
            seen = np.arange(L) // B <= (p0 + i) // B
            sc = np.einsum("hd,lhd->hl", q[r0 + i], k) / np.sqrt(q.shape[-1])
            sc = np.where(seen[None], sc, -np.inf)
            w = np.exp(sc - sc.max(-1, keepdims=True))
            w /= w.sum(-1, keepdims=True)
            out[r0 + i] = np.einsum("hl,lhd->hd", w, v)
    return out


SPANS = [(8, 4), (0, 12), (20, 4), (4, 8)]


@pytest.mark.parametrize("B", [1, 2, 4])
def test_mask_by_block_kernel_and_twin_agree_with_plain_softmax(B):
    """The Pallas kernel (interpret) and its XLA twin under the mask by
    block, against a dense oracle: block passes of one block, a prefill
    span from position 0 and a chunk behind a cached prefix. B = 1 is the
    causal mask."""
    case = _ragged_case(B, SPANS)
    q, kc, vc, tables, seq, pos, qs, ql, kv, rs, bs = case
    want = _dense_oracle(case, B)
    twin = attn_ops.ragged_paged_attention(
        q, kc, vc, tables, seq, pos, bs, diffusion_block=B, kv_len=kv)
    kern = ragged_paged_attention_pallas(
        q, kc, vc, tables, qs, ql, kv, rs, bs, diffusion_block=B)
    np.testing.assert_allclose(np.asarray(twin), want, atol=2e-5)
    np.testing.assert_allclose(np.asarray(kern), want, atol=2e-5)


def test_block_of_one_is_todays_causal_program_bit_for_bit():
    """``diffusion_block=1`` takes the branches the causal kernel and twin
    had: the same numbers to the last bit, with and without the
    argument, through the dispatch the model calls."""
    case = _ragged_case(1, SPANS, seed=5)
    q, kc, vc, tables, seq, pos, qs, ql, kv, rs, bs = case
    for use_pallas in (False, True):
        d = attn_ops.AttnDispatch(use_pallas=use_pallas)
        plain = d.ragged(q, kc, vc, tables, seq, pos, qs, ql, kv, rs, bs)
        one = d.ragged(q, kc, vc, tables, seq, pos, qs, ql, kv, rs, bs,
                       diffusion_block=1)
        np.testing.assert_array_equal(np.asarray(plain), np.asarray(one))
        four = d.ragged(q, kc, vc, tables, seq, pos, qs, ql, kv, rs, bs,
                        diffusion_block=4)
        assert not np.array_equal(np.asarray(plain), np.asarray(four))


def test_full_attention_oracle_masks_by_block():
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.standard_normal((8, 4, 16)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((8, 2, 16)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((8, 2, 16)), jnp.float32)
    causal = attn_ops.full_causal_attention(q, k, v)
    np.testing.assert_array_equal(
        np.asarray(causal),
        np.asarray(attn_ops.full_causal_attention(q, k, v, diffusion_block=1)),
    )
    by4 = np.asarray(attn_ops.full_causal_attention(q, k, v, diffusion_block=4))
    # a block's last row is the causal row; its first sees the whole block
    np.testing.assert_allclose(by4[3], np.asarray(causal)[3], atol=1e-6)
    np.testing.assert_allclose(by4[0], by4[0] * 0 + by4[0])
    assert not np.allclose(by4[0], np.asarray(causal)[0])


# -- the commit rule -------------------------------------------------------

def _commit(logits, fed, masked, threshold, floor_rows=1):
    S = logits.shape[0]
    z = jnp.zeros(S)
    ids, counts = commit_block(
        jnp.asarray(logits, jnp.float32), jnp.asarray(fed, jnp.int32),
        jnp.asarray(masked, bool), jnp.zeros(2, jnp.uint32), z,
        jnp.zeros(S, jnp.int32), z + 1.0, jnp.full(S, -1, jnp.int32),
        jnp.zeros(S, jnp.int32), threshold, floor_rows,
    )
    return np.asarray(ids).tolist(), np.asarray(counts).tolist()


def test_commit_rule_threshold_floor_and_ties():
    V = 8
    peaked = np.full((4, V), 0.0); peaked[:, 5] = 9.0      # conf ~ 0.999
    flat = np.zeros((4, V)); flat[:, 2] = 0.1              # conf ~ 0.14
    mixed = np.stack([flat[0], peaked[1], flat[2], peaked[3]])
    logits = np.stack([peaked, flat, mixed, flat])
    fed = np.array([[7, 7, 7, 7]] * 4)
    masked = np.array([
        [True, True, True, True],     # all confident: all four commit
        [False, True, True, True],    # none confident: the floor, lowest row
        [True, True, True, False],    # row 1 by threshold only
        [False, False, False, False],  # a commit pass: nothing to commit
    ])
    ids, counts = _commit(logits, fed, masked, 0.9)
    assert ids == [[5, 5, 5, 5], [7, 2, -1, -1], [-1, 5, -1, 7], [7, 7, 7, 7]]
    assert counts == [4, 1, 1, 0]
    # a floor of two rows: the two most confident masked rows, ties low
    ids, counts = _commit(logits, fed, masked, 0.9, floor_rows=2)
    assert ids[1] == [7, 2, 2, -1] and counts[1] == 2
    assert ids[2] == [2, 5, -1, 7] and counts[2] == 2


# -- the engine against the reference's generate ---------------------------

CASES = [(5, 7), (8, 10), (14, 5), (23, 9), (3, 6), (16, 13)]


@pytest.mark.parametrize("threshold", [0.9, 0.02])
async def test_engine_generates_the_references_tokens(threshold):
    """Greedy generation through the served engine, four lanes at once,
    token for token against the plain loop: prompt lengths with
    ``P mod 4`` in 0..3 (one with no whole block), ``max_tokens`` that is
    no multiple of 4, and at the low threshold passes that commit several
    rows, so a step yields 0, 1 and several tokens a lane."""
    model = tiny(threshold)
    engine = TpuEngine(engine_config(model))
    await engine.start()
    try:
        prompts = [list(range(2, 2 + p)) for p, _ in CASES]
        outs = await asyncio.gather(*(
            generate(engine, p, n) for p, (_, n) in zip(prompts, CASES)
        ))
        for prompt, (_, n), chunks in zip(prompts, CASES, outs):
            got = [t for c in chunks for t in c]
            assert got == want_tokens(model, prompt, n), (len(prompt), n)
            # one chunk a token, then the finish
            assert all(len(c) <= 1 for c in chunks)
        steps = [r for r in engine.debug_steps() if r.get("diffusion_lanes")]
        yielded = {r["committed_tokens"] / r["diffusion_lanes"] for r in steps}
        assert 0.0 in yielded                       # commit passes
        assert any(r["commit_rows"] for r in steps)
        if threshold < 0.5:
            assert max(yielded) > 1.0               # several rows a pass
        else:
            assert max(yielded) == 1.0              # the floor
        snap = engine.readiness()
        assert snap["diffusion_committed_tokens_total"] >= sum(
            n for _, n in CASES)
        assert snap["diffusion_passes_total"] == sum(
            r["diffusion_lanes"] for r in steps)
        assert snap["moe_grouped_rows_total"] > 0
    finally:
        await engine.stop()


async def test_a_preempted_block_resumes_to_the_same_tokens(monkeypatch):
    """Too few pages for both sequences' answers: one is preempted in the
    middle of a block (some rows committed, one still masked) and
    recomputed from its committed prefix; the stream goes on to the
    tokens the unpreempted loop gives."""
    model = tiny(0.02)
    cfg = engine_config(model, num_blocks=9, max_model_len=64,
                        max_num_seqs=2, enable_prefix_caching=False)
    engine = TpuEngine(cfg)
    preempted = []
    await engine.start()
    real = engine.scheduler.requeue_for_recompute

    def requeue(seq):
        preempted.append((list(seq.blk_ids), seq.total_len))
        real(seq)

    monkeypatch.setattr(engine.scheduler, "requeue_for_recompute", requeue)
    try:
        prompts = [list(range(5, 24)), list(range(40, 61))]
        outs = await asyncio.gather(
            *(generate(engine, p, 26) for p in prompts))
        assert preempted, "the pool was large enough: nothing was preempted"
        for prompt, chunks in zip(prompts, outs):
            got = [t for c in chunks for t in c]
            # a recomputed request counts its answer anew (as a causal
            # model's does): what matters is that the stream is the
            # loop's own tokens all the way
            assert len(got) >= 26
            assert got == want_tokens(model, prompt, len(got))
    finally:
        await engine.stop()


async def test_only_committed_blocks_are_offered_for_reuse(monkeypatch):
    """Every page published for prefix reuse holds committed diffusion
    blocks only, and a later request that shares the prefix reuses those
    pages and still generates the reference's tokens."""
    model = tiny(0.02)
    engine = TpuEngine(engine_config(model, max_num_seqs=2))
    await engine.start()
    sched = engine.scheduler
    real = sched.register_filled_blocks
    seen = []

    def register(seq, covered):
        committed = seq.blk_start if seq.blk_start >= 0 else (
            engine._prefill_target(seq))
        if seq.blk_start >= 0 and all(t >= 0 for t in seq.blk_ids):
            committed += model.diffusion_block_length
        seen.append((covered, committed))
        real(seq, covered)

    monkeypatch.setattr(sched, "register_filled_blocks", register)
    try:
        first = list(range(2, 21))                     # 19 tokens
        out = [t for c in await generate(engine, first, 17) for t in c]
        assert out == want_tokens(model, first, 17)
        assert seen and all(cov <= com for cov, com in seen), seen
        assert max(cov for cov, _ in seen) >= 32       # answer pages too
        # the same prompt and the start of its answer, as a new prompt
        second = first + out[:14]                      # 33 tokens, 4 pages
        hits = engine._prefix_hits
        out2 = [t for c in await generate(engine, second, 6) for t in c]
        assert engine._prefix_hits == hits + 1
        assert out2 == want_tokens(model, second, 6)
    finally:
        await engine.stop()


# -- the commit rides the next block's first denoising pass -----------------

def no_rides(monkeypatch):
    """The ride forced off in the TEST: the ENGINE's binding of the floor
    reads 0, so compose never knows a block complete ahead of its retire
    (the program keeps its own binding, and the commit rule with it)."""
    monkeypatch.setattr(engine_mod, "commit_floor_rows", lambda B, steps: 0)


async def served(cfg, prompts, n, hook=None, **stop):
    """One engine's run of ``prompts`` at once: each request's tokens and
    finish reason, what its last commit left (the end of the committed
    positions, the hash chain over them, the keys and values the cache
    holds there), the flight records of the block dispatches and the
    counters. ``hook(engine)`` runs behind the start.

    Passes are counted exactly by the tests that call this, so no clock
    may decide one: the requests are taken up in ONE pass (the engine's
    thread drains nothing until all are queued), the early retire of a
    dispatch that the CPU already has ready (``_step_unified``) is held
    back, as on a device slower than the host's round, and the counts are
    read behind the engine thread's end (a pass is booked at its issue,
    its flight record at its retire)."""
    engine = TpuEngine(cfg)
    engine._chunk_ready = lambda record: False
    drain, queued = engine._drain_submissions, []

    def drain_once_all_are_queued():
        if queued or engine._submit_q.qsize() >= len(prompts):
            queued.append(True)
            drain()

    engine._drain_submissions = drain_once_all_are_queued
    await engine.start()
    if hook is not None:
        hook(engine)
    commits = {}
    real = engine._commit_block

    def commit_block_(seq, start):
        real(seq, start)
        assert seq.total_len >= start + 4       # every token of it delivered
        commits.setdefault(tuple(seq.prompt_tokens[:3]), []).append((
            start + 4,
            [b.sequence_hash for b in seq.hashes.blocks],
            list(seq.block_ids),
        ))

    engine._commit_block = commit_block_

    async def one(prompt):
        pre = PreprocessedRequest(
            token_ids=list(prompt),
            sampling=SamplingOptions(temperature=0.0),
            stop=StopConditions(max_tokens=n, **{"ignore_eos": True, **stop}),
        )
        toks, reason = [], None
        async for raw in engine.generate(Context(pre.to_wire())):
            out = EngineOutput.from_wire(raw)
            toks += out.token_ids
            reason = out.finish_reason or reason
        return toks, reason

    try:
        outs = await asyncio.gather(*(one(p) for p in prompts))
        for _ in range(500):      # the void passes behind a finish retire
            if not engine._inflight and not engine.scheduler.running:
                break
            await asyncio.sleep(0.01)
        # the last retire may still be under way: its pass ends first
        await engine.stop()
        bs = cfg.block_size
        caches = [a for layer in engine.runner.kv_caches
                  for a in slot_rows(layer)]
        state = {}
        for key, made in commits.items():
            ends = [end for end, _h, _t in made]
            assert ends == sorted(set(ends)), ends   # once a block, in order
            end, hashes, table = made[-1]
            pos = np.arange(end)
            rows = np.asarray(table)[pos // bs] * bs + pos % bs
            state[key] = (end, hashes[: end // bs], [c[rows] for c in caches])
        steps = [r for r in engine.debug_steps() if r.get("diffusion_lanes")]
        # every pass retired: the scheduler holds nothing
        assert not engine.scheduler.running and not engine._inflight
        return outs, state, steps, engine.readiness()
    finally:
        await engine.stop()


def same_committed_state(a, b):
    assert a.keys() == b.keys()
    for key in a:
        (end_a, hash_a, kv_a), (end_b, hash_b, kv_b) = a[key], b[key]
        assert (end_a, hash_a) == (end_b, hash_b)
        for x, y in zip(kv_a, kv_b):
            np.testing.assert_allclose(x, y, atol=2e-5)


RIDE_PROMPTS = [list(range(2, 10)), list(range(3, 8)), list(range(4, 18)),
                list(range(5, 28))]       # P mod 4: 0, 1, 2, 3


@pytest.mark.parametrize("depth", [2, 1])
async def test_the_ride_is_the_lone_commits_result_in_one_pass_fewer(
        monkeypatch, depth):
    """With the ride and with it forced off: the same tokens (the plain
    loop's), the same hash chain and the same keys and values over every
    committed position, for prompts whose length is and is not a multiple
    of B; a block costs a lane four passes where five, every commit is
    counted once as ridden or lone, and a flight record's fields mean what
    they say. At depth 1 no pass is in flight at compose: nothing rides."""
    model = tiny()
    cfg = lambda: engine_config(model, pipeline_depth=depth)
    n = 21
    ride = await served(cfg(), RIDE_PROMPTS, n)
    no_rides(monkeypatch)
    lone = await served(cfg(), RIDE_PROMPTS, n)
    for (got, _r), (same, _r2), prompt in zip(ride[0], lone[0], RIDE_PROMPTS):
        assert got == same == want_tokens(model, prompt, n), len(prompt)
    same_committed_state(ride[1], lone[1])
    blocks = sum(end - (len(p) - len(p) % 4) for p, (end, _h, _kv)
                 in zip(RIDE_PROMPTS, (ride[1][tuple(p[:3])]
                                       for p in RIDE_PROMPTS))) // 4
    for (_o, _s, steps, snap), rides in ((ride, depth == 2), (lone, False)):
        ridden = snap["diffusion_commits_ridden_total"]
        assert ridden + snap["diffusion_commits_lone_total"] == blocks
        assert (ridden > 0) == rides
        assert snap["diffusion_passes_total"] == sum(
            r["diffusion_lanes"] for r in steps)
        # a span is B rows, or 2B where it carries a finished block
        rows = sum(r["denoise_rows"] + r["commit_rows"] for r in steps)
        assert rows == 4 * snap["diffusion_passes_total"] + sum(
            r["ride_rows"] for r in steps)
        assert sum(r["ride_rows"] for r in steps) >= 4 * ridden
        assert sum(r["decode_tokens"] for r in steps) == rows
        assert all(r["ride_rows"] <= r["commit_rows"] for r in steps)
    if depth == 2:
        # every block but a request's last (its commit is never needed)
        # rides, and each ride is one pass fewer
        assert ride[3]["diffusion_commits_lone_total"] == 0
        assert (lone[3]["diffusion_passes_total"]
                - ride[3]["diffusion_passes_total"]
                == ride[3]["diffusion_commits_ridden_total"])


async def test_a_block_costs_a_lane_four_passes_where_five(monkeypatch):
    """One lane, ten whole blocks of four tokens at four denoising steps
    (the floor hands out one row a pass): four passes a block with the
    ride, five without (the last block's commit pass is issued behind its
    last denoising pass either way and retires void), and tokens a lane
    pass 0.98 where 0.8."""
    model = tiny()
    prompt = list(range(2, 10))
    reads = []
    for rides in (True, False):
        if not rides:
            no_rides(monkeypatch)
        outs, _state, steps, snap = await served(
            engine_config(model, max_num_seqs=1), [prompt], 40)
        assert outs[0][0] == want_tokens(model, prompt, 40)
        reads.append((
            snap["diffusion_passes_total"],
            snap["diffusion_commits_ridden_total"],
            snap["diffusion_commits_lone_total"],
            sum(r["committed_tokens"] for r in steps)
            / sum(r["diffusion_lanes"] for r in steps),
        ))
    assert reads[0][:3] == (4 * 10 + 1, 9, 0)
    assert reads[1][:3] == (5 * 10, 0, 9)
    assert reads[0][3] == 40 / 41 and reads[1][3] == 0.8


async def test_a_block_finished_early_by_the_data_pays_a_lone_commit():
    """More masks in flight than the floor commits: only the data can
    finish the block early, which the host cannot foresee, so the next
    pass is fed from the device and, fed no mask, IS a lone commit pass.
    At a threshold the tiny model's rows reach in part, some blocks end
    by the floor (their commit rides) and some by the data (it cannot):
    the tokens are the plain loop's all the same."""
    model = tiny(0.03)
    outs, _state, steps, snap = await served(
        engine_config(model), RIDE_PROMPTS, 33)
    for (got, _r), prompt in zip(outs, RIDE_PROMPTS):
        assert got == want_tokens(model, prompt, 33), len(prompt)
    assert snap["diffusion_commits_lone_total"] > 0
    assert snap["diffusion_commits_ridden_total"] > 0
    assert max(r["committed_tokens"] / r["diffusion_lanes"]
               for r in steps) > 1.0


async def test_a_ride_that_would_pass_the_context_limit_is_a_lone_commit():
    """The block behind the open one would end past ``max_model_len``:
    no span reaches past the limit, the open block's commit goes alone
    (and retires void: the block's last token ended the request by
    length), every token up to the limit is delivered."""
    model = tiny()
    prompt = list(range(2, 12))                   # 10 tokens: blocks from 8
    outs, state, steps, snap = await served(
        engine_config(model, max_model_len=32, max_num_seqs=1), [prompt], 99)
    (got, reason), = outs
    assert got == want_tokens(model, prompt, 22) and reason == "length"
    # blocks 8 .. 24 rode with the block behind them; 28's could not
    assert snap["diffusion_commits_ridden_total"] == 5
    assert state[tuple(prompt[:3])][0] == 28
    assert sum(r["ride_rows"] for r in steps) == 4 * 5
    assert sum(r["commit_rows"] for r in steps) == 4 * 5 + 4
    assert snap["diffusion_commits_lone_total"] == 0


async def test_a_budget_with_room_for_one_ride_gives_the_rest_lone_commits():
    """Three lanes in step in a budget of 16 rows: every lane's B rows
    first, and what is left holds ONE more block. The lane that gets it
    rides, the others take the lone commit pass in the same dispatch:
    no lane ever skips a step, no token is lost."""
    model = tiny()
    prompts = [list(range(a, a + 8)) for a in (2, 30, 60)]
    cfg = engine_config(model, max_num_seqs=3, unified_token_budget=16,
                        unified_prefill_quantum=8)
    outs, _state, steps, snap = await served(cfg, prompts, 24)
    for (got, _r), prompt in zip(outs, prompts):
        assert got == want_tokens(model, prompt, 24)
    assert snap["diffusion_commits_ridden_total"] > 0
    assert snap["diffusion_commits_lone_total"] > 0
    assert max(r["decode_tokens"] for r in steps) == 16
    # while all three run, every dispatch carries all three
    busy = steps[4:-8]
    assert busy and all(r["diffusion_lanes"] == 3 for r in busy), [
        r["diffusion_lanes"] for r in steps]


async def test_a_stop_token_in_a_block_voids_the_ride_behind_it():
    """The block's last token ends the request while the ride is in
    flight: the ride is void as a void pass is (its writes lie in pages
    the sequence still owns), nothing of the next block is delivered, the
    block it carried is not counted as committed and every page comes
    back."""
    model = tiny()
    prompt = list(range(2, 10))
    want = want_tokens(model, prompt, 16)
    stop_tok = want[7]                            # the second block's last
    assert stop_tok not in want[:7]
    free = []
    outs, state, steps, snap = await served(
        engine_config(model, max_num_seqs=1), [prompt], 40,
        hook=lambda e: free.append((e, e.scheduler.allocator.num_free)),
        stop_token_ids=[stop_tok], ignore_eos=False)
    (got, reason), = outs
    assert reason == "stop" and got[:8] == want[:8][: len(got)]
    assert len(got) in (7, 8) and stop_tok not in got[:7]
    # block 1 rode; block 2's ride was in flight and is void
    assert snap["diffusion_commits_ridden_total"] == 1
    assert snap["diffusion_commits_lone_total"] == 0
    assert state[tuple(prompt[:3])][0] == 12
    assert sum(r["ride_rows"] for r in steps) == 8
    engine, before = free[0]
    assert engine.scheduler.allocator.num_free == before


async def test_a_preempted_lane_rides_again_behind_its_readmission(
        monkeypatch):
    """Too few pages for both answers: a lane is preempted between its
    passes (a lane with a pass in flight, a ride's too, is never the
    victim: its writes pin its pages) with blocks committed by rides
    behind it; recomputed from its delivered tokens it goes on to the
    plain loop's tokens, riding again, and what it keeps of the block
    left behind a ride does not outlive the preemption."""
    model = tiny()
    cfg = engine_config(model, num_blocks=9, max_model_len=64,
                        max_num_seqs=2, enable_prefix_caching=False)
    preempted = []
    prompts = [list(range(5, 24)), list(range(40, 61))]
    engine = TpuEngine(cfg)
    await engine.start()
    real = engine.scheduler.requeue_for_recompute

    def requeue(seq):
        real(seq)
        preempted.append((seq.blk_behind, seq.blk_inflight))

    monkeypatch.setattr(engine.scheduler, "requeue_for_recompute", requeue)
    try:
        outs = await asyncio.gather(
            *(generate(engine, p, 26) for p in prompts))
        assert preempted and all(p == ([], 0) for p in preempted), preempted
        for prompt, chunks in zip(prompts, outs):
            got = [t for c in chunks for t in c]
            assert len(got) >= 26
            assert got == want_tokens(model, prompt, len(got))
        snap = engine.readiness()
        assert snap["diffusion_commits_ridden_total"] > 8
    finally:
        await engine.stop()


def test_the_floor_is_one_expression_for_the_program_and_the_engine():
    from dynamo_tpu.engine import runner as runner_mod

    assert engine_mod.commit_floor_rows is commit_floor_rows
    assert runner_mod.commit_floor_rows is commit_floor_rows
    assert [commit_floor_rows(4, s) for s in (0, 1, 2, 3, 4, 8)] == [
        4, 4, 2, 2, 1, 1]


def test_a_ride_is_one_span_of_two_blocks_for_the_program():
    """The program takes the 2B span as it takes a prefill quantum: the
    device's ids land on the span's FIRST B rows (the finished block, fed
    unmasked), the ids that come back are its LAST B rows' (the next
    block's first denoising pass), and they are what the lone commit pass
    followed by the next block's first pass give."""
    model = tiny()

    def runner():
        return ModelRunner(engine_config(model, max_num_seqs=2),
                           rng_seed=SEED)

    samp = (0.0, 0, 1.0)
    first = [-1, 5, -1, 9]
    masks = [-1] * 4
    lone = runner()
    a = lone.unified_step([(first, [1], 0, samp)])
    ids = np.asarray(a.toks)[0].tolist()
    done = [t if t >= 0 else 7 for t in ids]       # a finished block
    S = lone.unified_slots
    prev = np.zeros((S, 4), np.int32)
    prev[1] = done
    feed = (prev, np.ones(S, np.int32), np.arange(S) == 0)
    lone.unified_step([(first, [1], 0, samp)], feed=feed)        # commit
    want = np.asarray(lone.unified_step([(masks, [1], 4, samp)]).toks)[0]

    ride = runner()
    ride.unified_step([(first, [1], 0, samp)])
    got = np.asarray(ride.unified_step(
        [(first + masks, [1], 0, samp)], feed=feed).toks)[0]
    np.testing.assert_array_equal(got, want)
    assert (got >= 0).sum() == 1                   # the floor: one row
    for one, other in zip(lone.kv_caches, ride.kv_caches):
        for x, y in zip(slot_rows(one), slot_rows(other)):
            assert np.abs(x[8:16]).max() > 0
            np.testing.assert_allclose(x[8:16], y[8:16], atol=2e-5)


#: sha256 (16 digits) of the runner's own ladder program (its jaxpr, the
#: kernels' source locations taken out) at T 32 and T 16, with the Pallas
#: kernels: a dense preset, one with a recurrent state beside a latent
#: layer, and the block program itself, whose compiled ladder the ride
#: leaves as it was (a 2B span is a span the program already took). A PR
#: that MEANS to change those programs regenerates them
#: (``_runner_step_hash`` below). ``tiny_ling_test`` is the tree's BEFORE
#: the ride (commit 89f6afb); the other two were regenerated by PR 59, which
#: meant to change them: a (k, v) layer's pages are ONE joined array
#: whatever the dtype (one scatter, one cache operand a layer), and Ling's
#: latent pair, which stays apart, kept its hashes through it.
PARENT_RUNNER_HASHES = {
    "tiny_test": ("a4ae601558612106", "e0d1bccee1270484"),
    "tiny_ling_test": ("8435d41fc787258d", "a6b644bfcd10cd29"),
    "tiny_sdar_test": ("d0b44d919e8d354e", "e5c4fe3daf58dfe1"),
}


def _runner_step_hash(preset: str, T: int) -> str:
    import hashlib
    import re

    model = getattr(ModelConfig, preset)()
    cfg = EngineConfig(
        model=model, dtype="float32", block_size=8, num_blocks=32,
        max_num_seqs=4, max_model_len=64, seed=0,
        unified_token_budget=32, unified_prefill_quantum=16,
    )
    runner = ModelRunner(cfg, rng_seed=0)
    B = model.diffusion_block_length or 1
    lanes = [([5] * B, [1], 0, (0.0, 0, 1.0)),
             ([7] * 8, [2], 0, (0.0, 0, 1.0))]
    base, _meta, ops = runner._unified_operands(lanes, None, T)
    text = str(jax.make_jaxpr(runner._unified)(
        *runner._program_args(base), ops.buf, ops.prev_toks))
    # a kernel's source location: the checkout's path and a line number
    text = re.sub(r" at /[^\s\]\)]*", "", text)
    return hashlib.sha256(text.encode()).hexdigest()[:16]


@pytest.mark.parametrize("preset", sorted(PARENT_RUNNER_HASHES))
def test_the_step_programs_are_the_parents(monkeypatch, preset):
    monkeypatch.setenv("DYNAMO_TPU_PALLAS", "1")
    got = tuple(_runner_step_hash(preset, T) for T in (32, 16))
    assert got == PARENT_RUNNER_HASHES[preset]


async def test_what_a_block_diffusion_model_refuses():
    model = tiny()
    with pytest.raises(ValueError, match="diffusion_block_length"):
        engine_config(model, block_size=6).validate()
    with pytest.raises(ValueError, match="speculative"):
        engine_config(model, speculative_k=2).validate()
    engine = TpuEngine(engine_config(model, sampling_extras=True))
    await engine.start()
    try:
        for kw in ({"logprobs": 2}, {}):
            pre = PreprocessedRequest(
                token_ids=[1, 2, 3],
                sampling=SamplingOptions(
                    temperature=0.0,
                    frequency_penalty=None if kw else 0.5),
                stop=StopConditions(max_tokens=4, ignore_eos=True), **kw,
            )
            with pytest.raises(RequestError, match="block-diffusion"):
                async for _ in engine.generate(Context(pre.to_wire())):
                    pass
    finally:
        await engine.stop()


def test_block_variant_is_one_more_layout_of_the_one_step_program():
    """The block step is a variant of the ladder's program, as ``spec``
    is: the packed buffer gains the masked-row flags, a negative id is a
    row fed as a mask, and the runner still builds one unified jit."""
    lay = operand_layout(16, 6, 4, 0, "block")
    plain = operand_layout(16, 6, 4, 0, "plain")
    assert set(lay.segs) - set(plain.segs) == {"tok_masked"}
    model = tiny()
    runner = ModelRunner(engine_config(model, max_num_seqs=2), rng_seed=SEED)
    assert runner._ladder_variant == "block"
    # the device trace's module name is how the benchmark finds the step
    # (chipbench/metrics/model.device_step_p50_ms.json: "unified_fn")
    assert "unified_fn" in runner._unified.__name__
    lanes = [([7, -1, 9, -1], [1], 0, (0.0, 0, 1.0))]
    _base, meta, ops = runner._unified_operands(lanes, None, 16)
    assert meta[0][:4].tolist() == [7, model.mask_token_id, 9,
                                    model.mask_token_id]
    assert ops.seg["tok_masked"][:5].tolist() == [0, 1, 0, 1, 0]
    out = runner.unified_step(lanes)
    ids, counts = np.asarray(out.toks), np.asarray(out.counts)
    assert ids.shape == (runner.unified_slots, 4) and counts[0] == 1
    assert ids[0, 0] == 7 and ids[0, 2] == 9
    assert sorted(ids[0, [1, 3]] >= 0) == [False, True]   # the floor: one
    assert runner.unified_executables() == 1


def test_from_hf_reads_the_sdar_family(tmp_path):
    import json

    cfg = {
        "architectures": ["SDARMoeForCausalLM"], "model_type": "sdar_moe",
        "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
        "max_position_embeddings": 32768, "max_window_layers": 48,
        "mlp_only_layers": [], "moe_intermediate_size": 768,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts": 128, "num_experts_per_tok": 8,
        "num_hidden_layers": 48, "num_key_value_heads": 4,
        "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
        "sliding_window": None, "tie_word_embeddings": False,
        "use_sliding_window": False, "vocab_size": 151936,
    }
    (tmp_path / "config.json").write_text(json.dumps(cfg))
    got = ModelConfig.from_hf(str(tmp_path))
    want = ModelConfig.sdar_30b_a3b()
    assert got.scaled(name=want.name) == want
    assert got.qk_norm and got.sliding_window == 0
    assert got.diffusion_block_length == 4 and got.mask_token_id == 151669


def test_oracle_forward_is_the_references_forward():
    """``llama.reference_forward`` (the program's own no-cache oracle)
    under the block mask against ``reference/sdar.py``."""
    model = tiny()
    params = llama.init_params(jax.random.PRNGKey(SEED), model, jnp.float32)
    toks = np.arange(3, 23, dtype=np.int32)
    got = np.asarray(llama.reference_forward(model, params, jnp.asarray(toks)))
    want = np.asarray(sdar.logits(
        PUBLISHED, SEED, toks[None], np.arange(20, dtype=np.int32)[None],
        "float32"))[0]
    np.testing.assert_allclose(got, want, atol=2e-5)
