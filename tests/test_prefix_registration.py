"""Prefix-cache registration at a retire (``Scheduler.register_filled_blocks``):
a block is offered for reuse ONCE, when it fills, from a mark that lives and
dies with ``seq.hashes``. The ``stored`` events the routing plane sees are the
function's contract (every whole block of what a sequence has fed, in order,
each hash once); the calls into ``BlockAllocator.register`` are one a filled
block, not one a token a block. CPU only; no clock decides anything."""

import asyncio
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.engine.engine import TpuEngine
from dynamo_tpu.engine.kv_cache import BlockAllocator
from dynamo_tpu.engine.scheduler import Scheduler
from dynamo_tpu.engine.sequence import Sequence
from dynamo_tpu.llm.protocols.common import (
    EngineOutput,
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.llm.tokens import TokenBlockSequence
from dynamo_tpu.models import llama
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.runtime.engine import Context
from stepdrive import reference_greedy

pytestmark = pytest.mark.anyio

CFG = ModelConfig.tiny_test()
PARAMS = llama.init_params(jax.random.PRNGKey(0), CFG, dtype=jnp.float32)
BS = 4


def engine_config(**kw) -> EngineConfig:
    base = dict(
        model=CFG, dtype="float32", block_size=BS, num_blocks=64,
        max_num_seqs=4, max_model_len=128,
    )
    base.update(kw)
    return EngineConfig(**base)


def watch(allocator: BlockAllocator):
    """Every call into ``register`` as (block, hash) and every event the
    allocator emits, both in order; the engine's own consumer still runs."""
    calls, events = [], []
    real_register, real_event = allocator.register, allocator.on_event

    def register(block, sequence_hash, **kw):
        calls.append((block, sequence_hash))
        return real_register(block, sequence_hash, **kw)

    def on_event(ev):
        events.append(ev)
        if real_event is not None:
            real_event(ev)

    allocator.register, allocator.on_event = register, on_event
    return calls, events


def stored(events):
    return [
        (ev.block_hashes, ev.parent_hash, ev.token_ids)
        for ev in events if ev.kind == "stored"
    ]


def contract(fed_by_request, bs=BS):
    """The ``stored`` stream the function's contract gives for requests
    served one after another: every whole block of what a request fed (its
    prompt and every token it generated but the last, which is never fed),
    in order, a hash once."""
    seen, out = set(), []
    for fed in fed_by_request:
        for b in TokenBlockSequence.from_tokens(fed, block_size=bs).blocks:
            if b.sequence_hash not in seen:
                seen.add(b.sequence_hash)
                out.append((
                    [b.sequence_hash], b.parent_sequence_hash,
                    [list(b.tokens)],
                ))
    return out


async def collect(engine, prompt, max_tokens):
    pre = PreprocessedRequest(
        token_ids=list(prompt),
        sampling=SamplingOptions(temperature=0.0),
        stop=StopConditions(max_tokens=max_tokens, ignore_eos=True),
    )
    tokens = []
    async for raw in engine.generate(Context(pre.to_wire())):
        tokens.extend(EngineOutput.from_wire(raw).token_ids)
    return tokens


def seq_of(prompt, name="s") -> Sequence:
    return Sequence(
        name, list(prompt), SamplingOptions(), StopConditions(),
        lambda tok, reason: None,
    )


def scheduler(cfg=None, **kw):
    cfg = cfg or engine_config(**kw)
    alloc = BlockAllocator(cfg.num_blocks, cfg.block_size)
    return Scheduler(cfg, alloc), alloc


def decode(sched, seq, tokens):
    """What a decode lane's retire does with each fed token."""
    for t in tokens:
        seq.output_tokens.append(t)
        seq.sched_len = seq.total_len
        assert sched.fund_span(seq, seq.total_len + 1)
        seq.hashes.append(t)
        sched.register_filled_blocks(seq, seq.total_len)


# -- (1) the stored events are the contract's, event for event --------------

#: prompts and answer lengths served one after another; a later prompt may
#: be built from an earlier request's prompt and answer: (request, tokens
#: of its answer taken, more tokens behind them)
SCRIPTS = {
    "one_request": [(list(range(1, 20)), 14)],
    "a_shared_prompt": [
        (list(range(1, 18)), 9), (list(range(1, 18)), 5),
        (list(range(1, 14)) + [90, 91, 92, 93, 94], 6),
    ],
    "a_prompt_that_runs_into_an_answer": [
        (list(range(3, 14)), 13), ((0, 9, [7, 7, 5]), 8),
    ],
    "no_whole_block": [([5, 6], 2), ([5, 6, 7], 1)],
}


@pytest.mark.parametrize("script", sorted(SCRIPTS))
async def test_the_stored_events_are_the_contracts_stream(script):
    """Hash, parent hash, token ids and order of every ``stored`` event
    over a scripted run, against the stream written down from the
    contract; ``register`` is entered once for every block a request
    filled itself."""
    engine = TpuEngine(engine_config(), params=PARAMS)
    await engine.start()
    calls, events = watch(engine.allocator)
    try:
        fed, asked, offered = [], [], 0
        for prompt, n in SCRIPTS[script]:
            if isinstance(prompt, tuple):
                src, take, more = prompt
                prompt = asked[src][0] + asked[src][1][:take] + more
            matched = engine._reused_device_blocks
            out = await collect(engine, prompt, n)
            assert out == reference_greedy(CFG, PARAMS, prompt, n, length=128)
            asked.append((prompt, out))
            fed.append(prompt + out[:-1])
            matched = engine._reused_device_blocks - matched
            offered += len(fed[-1]) // BS - matched
        assert stored(events) == contract(fed)
        assert all(ev.kind == "stored" for ev in events)  # no pressure
        assert len(calls) == offered
        assert len(set(calls)) == len(calls)
    finally:
        await engine.stop()


async def test_requests_served_together_store_each_hash_once_parent_first():
    """Served at once the order between requests is the scheduler's, so
    the stream is held to what does not depend on it: the set of events is
    the contract's, a block's parent is stored before it, and no hash is
    stored twice."""
    engine = TpuEngine(engine_config(), params=PARAMS)
    await engine.start()
    _calls, events = watch(engine.allocator)
    try:
        prompts = [list(range(a, a + n)) for a, n in ((1, 9), (20, 14), (40, 6))]
        outs = await asyncio.gather(*(collect(engine, p, 11) for p in prompts))
        got = stored(events)
        want = contract([p + o[:-1] for p, o in zip(prompts, outs)])
        key = lambda e: e[0][0]  # noqa: E731
        assert sorted(got, key=key) == sorted(want, key=key)
        salt = TokenBlockSequence(block_size=BS).salt_hash
        known = {salt}
        for hashes, parent, _tokens in got:
            assert parent in known and hashes[0] not in known
            known.add(hashes[0])
    finally:
        await engine.stop()


# -- (2), (7) once a filled block; the two counters --------------------------

async def test_a_long_decode_enters_register_once_a_block():
    """200 tokens of one request at 16 tokens a block: 12 calls, where a
    call a token a block would be ~1,300; the counters on ``readiness()``
    say the same."""
    engine = TpuEngine(
        engine_config(block_size=16, max_model_len=256), params=PARAMS
    )
    await engine.start()
    calls, events = watch(engine.allocator)
    try:
        prompt = list(range(1, 9))
        out = await collect(engine, prompt, 200)
        assert len(out) == 200
        blocks = (len(prompt) + 199) // 16
        assert blocks == 12 and len(calls) == blocks
        assert stored(events) == contract([prompt + out[:-1]], bs=16)
        ready = engine.readiness()
        assert ready["kv_blocks_offered_total"] == blocks
        assert ready["kv_blocks_stored_total"] == blocks
        # 48 tokens of it as a new prompt: the match leaves a token to
        # compute, so two blocks are reused and not offered, and the third
        # is filled again, offered, and not stored (its hash is owned)
        await collect(engine, prompt + out[:40], 3)
        ready = engine.readiness()
        assert ready["kv_reused_device_blocks_total"] == 47 // 16 == 2
        assert ready["kv_blocks_offered_total"] == blocks + 1
        assert ready["kv_blocks_stored_total"] == blocks
    finally:
        await engine.stop()


def test_a_block_whose_hash_another_owns_is_offered_and_not_stored():
    """Two equal prompts admitted before either registered: the second's
    blocks are offered once each and stored never (the first registration
    is kept), and offered a second time they are not: the mark has passed
    them."""
    sched, alloc = scheduler()
    calls, events = watch(alloc)
    a, b = seq_of(range(1, 14), "a"), seq_of(range(1, 14), "b")
    assert sched.admit(a) and sched.admit(b)
    assert (a.offered_blocks, b.offered_blocks) == (0, 0)
    for seq in (a, b):
        sched.register_filled_blocks(seq, 13)
        assert seq.offered_blocks == 3
    assert (alloc.offered_total, alloc.stored_total) == (6, 3)
    assert len(stored(events)) == 3
    sched.register_filled_blocks(b, 13)
    assert alloc.offered_total == 6 and len(calls) == 6


async def test_the_counters_reach_the_frontends_metrics():
    """``/metrics`` of the frontend copies named keys out of the
    readiness snapshot: the two are among them."""
    import aiohttp

    from dynamo_tpu.llm.discovery import ModelManager
    from dynamo_tpu.llm.http_service import HttpService

    service = HttpService(
        ModelManager(), host="127.0.0.1", port=0,
        readiness=lambda: {
            "state": "ready", "kv_blocks_offered_total": 7,
            "kv_blocks_stored_total": 5,
        },
    )
    await service.start()
    try:
        async with aiohttp.ClientSession() as s:
            async with s.get(
                f"http://127.0.0.1:{service.port}/metrics"
            ) as resp:
                text = await resp.text()
        assert "kv_blocks_offered_total 7" in text
        assert "kv_blocks_stored_total 5" in text
    finally:
        await service.stop()


# -- the mark ----------------------------------------------------------------

def test_the_common_step_is_a_compare_and_a_return():
    """A step that fills no block does not reach the chain, the table or
    the allocator."""
    sched, alloc = scheduler()
    calls, _events = watch(alloc)
    seq = seq_of(range(1, 11))
    assert sched.admit(seq)
    sched.register_filled_blocks(seq, 10)
    assert len(calls) == 2 and seq.offered_blocks == 2

    class Untouchable:
        def __getattr__(self, name):
            raise AssertionError(f"the common step read hashes.{name}")

    chain, seq.hashes = seq.hashes, Untouchable()
    sched.register_filled_blocks(seq, 10)
    sched.register_filled_blocks(seq, 11)
    sched.register_filled_blocks(seq, 8)         # behind the mark: monotone
    seq.hashes = chain
    assert len(calls) == 2 and seq.offered_blocks == 2


@pytest.mark.parametrize("switch", ["prefix_caching_off", "multimodal"])
def test_what_never_registers_still_does_not(switch):
    off = switch == "prefix_caching_off"
    sched, alloc = scheduler(enable_prefix_caching=not off)
    calls, _events = watch(alloc)
    seq = seq_of(range(1, 14))
    if not off:
        seq.mm_segments = [(0, object())]
    assert sched.admit(seq)
    sched.register_filled_blocks(seq, 13)
    decode(sched, seq, range(40, 50))
    assert calls == [] and seq.offered_blocks == 0


def test_the_mark_starts_behind_the_matched_prefix():
    """Admission matched ``len(matched)`` blocks: they are registered
    already, and the first offer starts behind them."""
    sched, alloc = scheduler()
    first = seq_of(range(1, 18), "first")
    assert sched.admit(first)
    sched.register_filled_blocks(first, 17)
    sched.finish(first, None)
    calls, events = watch(alloc)
    second = seq_of(list(range(1, 14)) + [70, 71, 72, 73, 74, 75], "second")
    assert sched.admit(second)
    assert second.num_cached_prefix == 12 and second.offered_blocks == 3
    sched.register_filled_blocks(second, 19)
    assert [b for b, _h in calls] == second.block_ids[3:4]
    assert stored(events) == contract([second.prompt_tokens])[3:]


# -- (3) a preempted sequence publishes again from its matched prefix on -----

@pytest.mark.parametrize("kept", ["pages_kept", "pages_reclaimed"])
def test_a_requeued_sequence_publishes_again_from_its_matched_prefix(kept):
    """``requeue_for_recompute`` drops the chain and the mark with it; the
    re-admission starts the mark where ITS prefix match ended: behind the
    pages that outlived the preemption, or at 0 where none did."""
    sched, alloc = scheduler()
    seq = seq_of(range(1, 12))
    assert sched.admit(seq)
    sched.register_filled_blocks(seq, 11)
    decode(sched, seq, [50, 51, 52, 53, 54, 55])           # 17 tokens: 4 blocks
    assert seq.offered_blocks == 4
    sched.requeue_for_recompute(seq)
    assert seq.hashes is None and seq.offered_blocks == 0
    if kept == "pages_reclaimed":
        alloc.clear_reusable()
    calls, events = watch(alloc)
    assert sched.waiting.popleft() is seq and sched.admit(seq)
    assert len(seq.prompt_tokens) == 17
    matched = 4 if kept == "pages_kept" else 0      # (17 - 1) // 4 at most
    assert seq.offered_blocks == matched
    sched.register_filled_blocks(seq, 17)
    decode(sched, seq, [56, 57, 58, 59])                   # 21 tokens: 5 blocks
    assert seq.offered_blocks == 5
    assert [b for b, _h in calls] == seq.block_ids[matched:5]
    assert stored(events) == contract([seq.prompt_tokens + [56, 57, 58]])[
        matched:]


async def test_a_preemption_in_the_served_engine_keeps_the_mark_true(
        monkeypatch):
    """Too few pages for both answers: one request is preempted and
    re-admitted. Every call into ``register`` is a block its admission had
    not matched and no retire had offered: the calls are what the marks
    moved by, admission by admission, and the tokens are the oracle's."""
    engine = TpuEngine(
        engine_config(num_blocks=14, max_num_seqs=2, max_model_len=40),
        params=PARAMS,
    )
    await engine.start()
    sched = engine.scheduler
    calls, _events = watch(engine.allocator)
    moved, preempted = [], []
    real_admit, real_release = sched.admit, sched._release
    real_requeue = sched.requeue_for_recompute

    def admit(seq):
        ok = real_admit(seq)
        if ok:
            seq._mark_at_admission = seq.offered_blocks
        return ok

    def release(seq):
        moved.append(seq.offered_blocks - seq._mark_at_admission)
        real_release(seq)

    def requeue(seq):
        preempted.append(seq.request_id)
        real_requeue(seq)

    monkeypatch.setattr(sched, "admit", admit)
    monkeypatch.setattr(sched, "_release", release)
    monkeypatch.setattr(sched, "requeue_for_recompute", requeue)
    try:
        prompts = [list(range(1, 10)), list(range(30, 41))]
        outs = await asyncio.gather(*(collect(engine, p, 22) for p in prompts))
        for prompt, out in zip(prompts, outs):
            # a preempted request counts max_tokens anew from its
            # re-admission (as the block-diffusion test of it notes): the
            # stream is the oracle's all the way
            assert len(out) >= 22
            assert out == reference_greedy(
                CFG, PARAMS, prompt, len(out), length=128
            )
        assert preempted, "the pool was large enough: nothing was preempted"
        assert len(moved) == 2 + len(preempted)
        assert len(calls) == sum(moved)
    finally:
        await engine.stop()


# -- (4) a later request that shares a prefix still hits ---------------------

@pytest.mark.parametrize("into_answer", [0, 10], ids=["prompt", "answer"])
async def test_a_later_request_still_hits(into_answer):
    """The blocks a request filled while it decoded are found by a later
    prompt as the prompt's own are."""
    engine = TpuEngine(engine_config(), params=PARAMS)
    await engine.start()
    try:
        first = list(range(1, 18))
        out = await collect(engine, first, 14)
        second = first + out[:into_answer] + [99, 98]
        hits = engine._prefix_hits
        out2 = await collect(engine, second, 5)
        assert out2 == reference_greedy(CFG, PARAMS, second, 5, length=128)
        assert engine._prefix_hits == hits + 1
        assert engine.readiness()["kv_reused_device_blocks_total"] == (
            (17 + into_answer) // BS
        )
    finally:
        await engine.stop()


# -- (5) the rolling buffer's sentinel ---------------------------------------

def test_the_sentinel_of_a_rolling_buffer_page_is_passed_over():
    """Pages released behind the window before they were offered hold the
    0 sentinel: they are not offered, and the mark moves past them."""
    wcfg = dataclasses.replace(CFG, name="tiny-swa", sliding_window=8)
    sched, alloc = scheduler(engine_config(model=wcfg))
    calls, events = watch(alloc)
    seq = seq_of(range(1, 24))
    assert sched.admit(seq)
    assert sched.evict_behind_window(seq, 23) == 3       # (23 - 8) // 4
    assert seq.block_ids[:4] == [0, 0, 0, seq.block_ids[3]]
    sched.register_filled_blocks(seq, 23)
    assert [b for b, _h in calls] == seq.block_ids[3:5] and 0 not in calls[0]
    assert seq.offered_blocks == 5
    assert stored(events) == contract([seq.prompt_tokens])[3:]
    sched.register_filled_blocks(seq, 23)
    assert len(calls) == 2


# -- (6) KV that arrives from a prefill worker -------------------------------

async def test_a_remote_admission_publishes_the_whole_prompt_once():
    """Both sides of a disaggregated prefill reach the function with the
    prompt's length: the prefill worker and the decode side each publish
    every whole block of the prompt, once; the decode side then offers what
    its decode fills, a block at a time."""
    from dynamo_tpu.disagg import (
        DecodeOperator,
        DisaggConfig,
        DisaggRouter,
        PrefillQueue,
        PrefillWorker,
    )
    from dynamo_tpu.runtime.distributed import DistributedRuntime

    ecfg = lambda: engine_config(  # noqa: E731
        block_size=16, num_blocks=32, max_num_seqs=2
    )
    prompt = list(range(40))                       # 2 whole blocks and a tail
    drt = await DistributedRuntime.in_process()
    queue = PrefillQueue(drt, "test")
    dis = DisaggRouter.__new__(DisaggRouter)
    dis.cfg = DisaggConfig(max_local_prefill_length=16, max_prefill_queue_size=8)
    decode_engine = TpuEngine(ecfg(), params=PARAMS)
    await decode_engine.start()
    prefill_engine = TpuEngine(ecfg(), params=PARAMS)
    await prefill_engine.start()
    d_calls, d_events = watch(decode_engine.allocator)
    p_calls, p_events = watch(prefill_engine.allocator)
    op = await DecodeOperator(
        decode_engine, queue, dis, transport="device"
    ).start()
    pw = PrefillWorker(prefill_engine, queue).start()
    try:
        out = await collect(op, prompt, 12)
        assert out == reference_greedy(CFG, PARAMS, prompt, 12, length=128)
        assert op.remote_count == 1 and pw.served == 1
        whole = contract([prompt], bs=16)
        assert len(whole) == 2
        assert len(p_calls) == 2 and stored(p_events) == whole
        assert len(d_calls) == 3                   # 51 tokens fed: 3 blocks
        assert stored(d_events) == contract([prompt + out[:-1]], bs=16)
        assert stored(d_events)[:2] == whole
    finally:
        await pw.stop()
        await op.stop()
        await decode_engine.stop()
        await prefill_engine.stop()
        await drt.shutdown()
