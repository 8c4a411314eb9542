"""A model that keeps its cache by layer group (Command A+ family: window
layers and full-attention layers in pools and block tables of their own, a
parallel attention + FFN block, an expert share): the served path against
the plain reference, the controls the comparison must refuse, the
allocator's accounting by group, what such a model refuses, what it costs
the one-group models (nothing), and its tracing."""

import asyncio
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import check
from chipbench.reference import cohere2_moe
from chipbench.steps import grouped_span
from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.engine.engine import TpuEngine
from dynamo_tpu.engine.kv_cache import BlockAllocator
from dynamo_tpu.engine.runner import ModelRunner, operand_layout
from dynamo_tpu.engine.scheduler import Scheduler
from dynamo_tpu.engine.sequence import Sequence, SeqStatus
from dynamo_tpu.llm.protocols.common import (
    EngineOutput,
    PreprocessedRequest,
    RequestError,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.models import llama, moe
from dynamo_tpu.models.config import PRESETS, ModelConfig
from dynamo_tpu.runtime.engine import Context
from stepdrive import slot_rows

pytestmark = pytest.mark.anyio

SEED = 3
WINDOW = 32
#: the tiny preset under the reference's key names
PUBLISHED = dict(
    hidden_size=64, intermediate_size=32, num_hidden_layers=8,
    num_attention_heads=8, num_key_value_heads=2, head_dim=16,
    vocab_size=384, num_experts=16, num_experts_per_tok=4,
    num_shared_experts=2, sliding_window=WINDOW, layer_switch=4,
    rope_theta=50000, layer_norm_eps=1e-5, logit_scale=0.5,
    tie_word_embeddings=True,
)
#: prompts on both sides of the window, cut across dispatches of 32 rows
LENS = (5, 37, 70, 131)
#: a relative logit error no sound float32 run comes near and every
#: control passes by far (they read 0.1 and more)
SOUND, WRONG = 2e-4, 2e-2


def engine_config(model=None, **kw) -> EngineConfig:
    base = dict(
        model=model or ModelConfig.tiny_command_a_test(), dtype="float32",
        block_size=8, num_blocks=96, max_num_seqs=4, max_model_len=192,
        seed=SEED, unified_token_budget=32, unified_prefill_quantum=32,
    )
    base.update(kw)
    return EngineConfig(**base)


def reference_logits(tokens, rows, held: int = 0, controls=()):
    pub, kw = dict(PUBLISHED), {}
    if held:
        pub["num_experts"] = held
        kw = dict(source_values={"num_experts": 16}, share={"index": 0})
    return np.asarray(cohere2_moe.logits(
        pub, SEED, tokens, rows, "float32", controls=controls, **kw))


def drive(held=0, **kw):
    runner = ModelRunner(
        engine_config(ModelConfig.tiny_command_a_test(held=held), **kw),
        rng_seed=SEED)
    tokens = check.sample_tokens(11, 384, [n + 6 for n in LENS], 160)
    return runner, tokens, grouped_span.drive(runner, tokens, LENS, 6, 11)


async def generate(engine, prompt, n, **request):
    pre = PreprocessedRequest(
        token_ids=list(prompt),
        sampling=SamplingOptions(temperature=0.0),
        stop=StopConditions(max_tokens=n, ignore_eos=True),
        **request,
    )
    chunks = []
    async for raw in engine.generate(Context(pre.to_wire())):
        chunks.append(EngineOutput.from_wire(raw).token_ids)
    return [t for c in chunks for t in c]


def follows_the_reference(prompt, got) -> None:
    """Every served token is the argmax of the reference's ONE full pass
    over the prompt and the tokens served before it (one padded length and
    row count for every call: the reference compiles once)."""
    pad, nrows = 192, 32
    n = len(prompt) + len(got)
    assert n <= pad and len(got) <= nrows
    seq = np.zeros((1, pad), np.int32)
    seq[0, :n] = list(prompt) + list(got)
    rows = np.minimum(
        np.arange(len(prompt) - 1, len(prompt) - 1 + nrows), n - 2
    ).astype(np.int32)[None]
    rows = np.concatenate([rows, [[pad - 1]]], axis=1)  # the whole length
    want = reference_logits(seq, rows)[0][: len(got)]
    top2 = np.sort(want, axis=-1)[:, -2:]
    clear = top2[:, 1] - top2[:, 0] > 1e-3
    assert clear.mean() > 0.9
    assert (np.asarray(got)[clear] == want.argmax(-1)[clear]).all()


# -- the served path against the reference -------------------------------

@pytest.mark.parametrize("held,pallas", [(0, "0"), (8, "0"), (0, "1")])
def test_runner_logits_equal_the_references_forward_pass(
        monkeypatch, held, pallas):
    """Chunked prefill that crosses the window (prompts cut across
    dispatches, quanta beside each other), then six decode steps, through
    the runner's TWO pools by the benchmark's own step driver, window
    blocks given back and handed out again on the way: logits against the
    reference's one full pass. Every expert held (the grouped path), half
    of them (the dense path, an expert share), and the Pallas kernel
    interpreted (4 queries a cached head, the window's page skip)."""
    monkeypatch.setenv("DYNAMO_TPU_PALLAS", pallas)
    runner, tokens, out = drive(held)
    assert runner.attention_path == ("pallas" if pallas == "1" else "xla")
    assert len(runner.group_blocks) == 2
    assert out["released"] > 0 and out["decode"].sum() >= 6 * len(LENS)
    want = reference_logits(tokens, out["rows"], held)
    v = check.verdict(out["logits"], want, out["served"], out["decode"],
                      out["judged"])
    assert v["rel_err"] < SOUND, v
    assert v["token_mismatches"] == 0


def test_runner_counts_the_ragged_kernels_folds_by_layer_window(monkeypatch):
    """The host's count of the kernel's work a dispatch (the flight
    record's ``attn_short_folds`` / ``attn_long_folds``): every layer that
    calls the kernel under ITS window, six windowed and two full here,
    from the spans as the operands are packed; nothing under the twin."""
    from dynamo_tpu.ops.pallas.ragged_attention import long_tile, ring_shape

    lanes = [
        ([5], (list(range(1, 21)), list(range(1, 21))), 149, (0.0, 0, 1.0)),
        ([7], ([21, 22, 23], [21, 22, 23]), 19, (0.0, 0, 1.0)),
        (list(range(40)), (list(range(30, 48)), list(range(30, 48))), 100,
         (0.0, 0, 1.0)),
    ]
    runner = ModelRunner(engine_config(), rng_seed=SEED)
    runner._unified_operands(lanes, None, 64)
    assert runner._fold_plan is None and runner.attn_folds == (0, 0)

    monkeypatch.setenv("DYNAMO_TPU_PALLAS", "1")
    runner = ModelRunner(engine_config(), rng_seed=SEED)
    plan = runner._fold_plan
    assert dict(plan["layers"]) == {WINDOW: 6, 0: 2}
    rule = plan["count"].keywords
    assert rule["long_rows"] == long_tile(8, 2) and rule["diffusion_block"] == 1
    # a page of K: 8 tokens x 2 heads x a lane tile of f32
    keys = ring_shape(8 * 2 * 128 * 4, 8)[1] * 8
    assert rule["fold_keys"] == keys >= 192      # every context: one fold
    runner._unified_operands(lanes, None, 64)
    tiles = -(-40 // rule["long_rows"])
    assert runner.attn_folds == (8 * 2, 8 * tiles)
    runner._unified_operands(lanes[:2], None, 64)
    assert runner.attn_folds == (8 * 2, 0)
    assert runner.attn_folds_total == [8 * 4, 8 * tiles]


@pytest.mark.parametrize("control", [
    "window_on_full", "rope_on_full", "sequential"])
def test_the_comparison_refuses_a_wrong_layer(control):
    """Three ways to get this layer wrong that a loose comparison would
    pass: a window applied to the full layer, rotary applied to the full
    layer, attention and FFN in sequence instead of side by side. Each
    reads at least a hundred times the sound runs' error."""
    _runner, tokens, out = drive()
    wrong = reference_logits(tokens, out["rows"], controls=((control, True),))
    v = check.verdict(out["logits"], wrong, out["served"], out["decode"],
                      out["judged"])
    assert v["rel_err_quantiles"]["p50"] > WRONG, v


def test_the_comparison_refuses_int8_weights():
    """The program's own int8 weights through the same drive: every row's
    error is far above a sound run's."""
    _runner, tokens, out = drive(weight_quant="int8")
    want = reference_logits(tokens, out["rows"])
    v = check.verdict(out["logits"], want, out["served"], out["decode"],
                      out["judged"])
    assert v["rel_err_quantiles"]["p5"] > 20 * SOUND, v


def test_oracle_forward_is_the_references_forward():
    model = ModelConfig.tiny_command_a_test()
    params = llama.init_params(jax.random.PRNGKey(SEED), model, jnp.float32)
    assert "ln_mlp" not in params["layers"][0]        # ONE norm a layer
    tokens = np.zeros((1, 192), np.int32)
    tokens[0, :150] = np.arange(3, 153)
    rows = np.arange(192, dtype=np.int32)[None]
    with jax.default_matmul_precision("highest"):
        got = llama.reference_forward(model, params, jnp.asarray(tokens[0]))
    want = reference_logits(tokens, rows)[0]
    assert check.row_errors(np.asarray(got)[:150], want[:150]).max() < SOUND


async def test_engine_serves_the_references_tokens_and_counts_its_pools():
    """Four lanes at once through ``TpuEngine`` at pipeline depth 2, two of
    them several windows long: decode lanes and prefill quanta share
    dispatches, window blocks are released on the way; the flight record
    and the gauges say so."""
    engine = TpuEngine(engine_config())
    assert engine.cfg.pipeline_depth == 2
    assert not engine.cfg.enable_prefix_caching      # forced off
    await engine.start()
    try:
        prompts = [list(range(2, 2 + p)) for p in (5, 150, 40, 97, 31)]
        outs = await asyncio.gather(*(generate(engine, p, 12) for p in prompts))
        for prompt, got in zip(prompts, outs):
            assert len(got) == 12
            follows_the_reference(prompt, got)
        steps = [r for r in engine.debug_steps() if "dispatch_ms" in r]
        assert any(r["decode_tokens"] and r["prefill_tokens"] for r in steps)
        released = sum(r["kv_window_released"] for r in steps)
        assert released >= (150 - WINDOW) // 8
        page = 2 * 8 * 2 * 16 * 4            # K and V, 8 tokens, 2 heads, f32
        for r in steps:
            assert r["kv_bytes_live"] == page * (
                2 * r["kv_full_blocks"] + 6 * r["kv_window_blocks"])
        # a long context costs less than one table would: 8 layers' pages
        long = max(steps, key=lambda r: r["context_tokens_live"])
        assert long["kv_bytes_live"] < 0.8 * 8 * page / 8 * (
            long["context_tokens_live"] + 8 * long["lanes"])
        snap = engine.readiness()
        assert snap["kv_window_released_blocks_total"] >= released
        assert snap["kv_full_usage_perc"] == 0 == snap["kv_window_usage_perc"]
        assert snap["gpu_cache_usage_perc"] == 0
        assert snap["kv_preemptions_window_pool_total"] == 0
    finally:
        await engine.stop()


async def test_a_preempted_sequence_gives_back_both_pools(monkeypatch):
    """Too few blocks in the full-attention pool for both answers: one
    sequence is preempted, every block of BOTH its tables goes back, and
    its stream goes on with the tokens of an unpreempted run."""
    engine = TpuEngine(engine_config(num_blocks=13, max_model_len=96,
                                     max_num_seqs=2))
    preempted = []
    await engine.start()
    real = engine.scheduler.requeue_for_recompute

    def requeue(seq):
        preempted.append(seq.total_len)
        real(seq)
        assert seq.tables == [[]] and seq.evicted == [0]

    monkeypatch.setattr(engine.scheduler, "requeue_for_recompute", requeue)
    try:
        prompts = [list(range(5, 24)), list(range(40, 61))]
        outs = await asyncio.gather(*(generate(engine, p, 40) for p in prompts))
        assert preempted, "the pool was large enough: nothing was preempted"
        for prompt, got in zip(prompts, outs):
            assert len(got) >= 40
            follows_the_reference(prompt, got[:32])
        sched = engine.scheduler
        assert sched.preemptions_by_group[0] >= 1
        assert [a.num_free for a in sched.allocators] == [
            a.num_blocks - 1 for a in sched.allocators]
    finally:
        await engine.stop()


def test_a_tp_mesh_serves_the_two_pools_as_one_chip_does():
    """Each group's pool shards its cached heads over tp like the one
    pool of other models, the tables ride replicated in the packed
    buffer: the same sample through a tp=2 runner reads the one-chip
    runner's logits, and releases the same blocks on the way."""
    from dynamo_tpu.parallel.mesh import build_mesh

    _, tokens, one = drive()
    cfg = engine_config(mesh_shape={"tp": 2})
    cfg.validate()
    runner = ModelRunner(
        cfg, mesh=build_mesh(cfg.mesh_shape, devices=jax.devices()[:2]),
        rng_seed=SEED)
    assert {slot_rows(layer)[0].shape[0] for layer in runner.kv_caches} == {
        n * cfg.block_size for n in cfg.group_num_blocks}
    two = grouped_span.drive(runner, tokens, LENS, 6, 11)
    err = np.abs(two["logits"] - one["logits"]).max() / np.abs(
        one["logits"]).max()
    assert err < SOUND and two["released"] == one["released"] > 0
    assert (two["served"] == one["served"]).all()


# -- the allocator's accounting by group ---------------------------------

def _scheduler(**kw):
    cfg = engine_config(**kw)
    cfg.validate()
    pools = cfg.group_num_blocks
    allocs = [BlockAllocator(n, cfg.block_size, enable_prefix_caching=False)
              for n in pools]
    return cfg, Scheduler(cfg, *allocs)


def _sequence(n: int) -> Sequence:
    return Sequence(
        request_id=f"r{n}", prompt_tokens=list(range(1, n + 1)),
        sampling=SamplingOptions(temperature=0.0),
        stop=StopConditions(max_tokens=4), emit=lambda *a: None)


def test_a_long_sequence_holds_a_window_in_one_pool_and_all_in_the_other():
    """A sequence of three windows, prefilled a quantum at a time as the
    engine does (fund the span, dispatch, release behind the window): the
    windowed group never holds more than window / block + the span's
    blocks + 1, the full group holds every block; release returns both."""
    cfg, sched = _scheduler()
    bs, quantum = cfg.block_size, 16
    full, window = sched.allocators
    assert cfg.group_num_blocks == (96, 4 * (4 + 4 + 2) + 1)
    seq = _sequence(3 * WINDOW + 5)
    sched.add(seq)
    assert sched.next_prefill() is seq
    P = len(seq.prompt_tokens)
    assert sched.blocks_in_use(0) == -(-P // bs) and sched.blocks_in_use(1) == 0
    most = 0
    for start in range(0, P, quantum):
        end = min(start + quantum, P)
        assert sched.fund_span(seq, end)
        most = max(most, sched.blocks_in_use(1))
        sched.evict_behind_window(seq, end)
        live = [b for b in seq.tables[1] if b]
        assert len(live) == sched.blocks_in_use(1)
        assert len(live) <= WINDOW // bs + 1
    assert most <= WINDOW // bs + quantum // bs + 1
    assert sched.blocks_in_use(0) == -(-P // bs)      # nothing released
    assert seq.evicted == [0, (P - WINDOW) // bs]
    assert seq.lane_block_ids == (seq.block_ids, seq.tables[1])
    assert sched.window_released == (P - WINDOW) // bs
    assert 0 < sched.cache_usage() < full.usage()
    sched.finish(seq, None)
    assert full.num_free == full.num_blocks - 1
    assert window.num_free == window.num_blocks - 1


def test_a_full_window_pool_preempts_and_readmission_refunds_both():
    """The windowed pool runs out under a span: the newest runnable
    sequence is preempted for it (counted under that pool), gives back
    every block of both pools, and is admitted again from position 0."""
    cfg, sched = _scheduler(max_num_seqs=2, max_model_len=96)
    window = sched.allocators[1]
    a, b = _sequence(40), _sequence(41)
    for s in (a, b):
        sched.add(s)
        assert sched.next_prefill() is s
        assert sched.fund_span(s, len(s.prompt_tokens))
    held = window.num_blocks - 1 - window.num_free
    assert held == 5 + 6
    # fill what is left of the windowed pool, then ask for one block more
    grabbed = [window.allocate() for _ in range(window.num_free)]
    assert not sched.fund_span(a, 49) or b.status is SeqStatus.WAITING
    assert b.status is SeqStatus.WAITING and b.tables == [[]]
    assert sched.preemptions_by_group == [0, 1]
    assert sched.group_gauges()["kv_preemptions_window_pool_total"] == 1
    for blk in grabbed:
        window.release(blk)
    assert sched.next_prefill() is b and b.evicted == [0, 0]
    assert len(b.prompt_tokens) == 41
    for s in (a, b):
        sched.finish(s, None)
    assert [x.num_free for x in sched.allocators] == [
        x.num_blocks - 1 for x in sched.allocators]


def test_admission_counts_the_window_pool():
    cfg, sched = _scheduler()
    window = sched.allocators[1]
    grabbed = [window.allocate() for _ in range(window.num_free - 2)]
    seq = _sequence(60)
    sched.add(seq)
    assert sched.next_prefill() is None            # no room for a window
    assert sched.allocators[0].num_free == 95      # and nothing was kept
    for blk in grabbed:
        window.release(blk)
    assert sched.next_prefill() is seq


# -- the expert share -----------------------------------------------------

@pytest.mark.parametrize("held", [2, 16])
def test_the_eight_shares_add_up_to_the_uncut_layer(held):
    """``model-configs`` section 4: the routed parts of all eight shares,
    with the shared experts' mean counted once, add up to what the uncut
    reference gives for the whole layer. 2 held experts run the dense
    path, 16 the grouped one."""
    E, D, Im, T = 8 * held, 64, 32, 24
    kx, kp = jax.random.split(jax.random.PRNGKey(0))
    cfg = moe.MoeConfig(hidden_size=D, intermediate_size=Im, num_experts=E,
                        num_experts_per_tok=4, gating="sigmoid")
    params = moe.init_moe_params(kp, cfg)
    x = jax.random.normal(kx, (T, D), jnp.float32)
    shared = {
        f"w_shared_{n}": 0.1 * jax.random.normal(
            jax.random.PRNGKey(i), shape, jnp.float32)
        for i, (n, shape) in enumerate(
            (("gate", (D, 2 * Im)), ("up", (D, 2 * Im)), ("down", (2 * Im, D))))
    }
    total = jnp.zeros_like(x)
    landed = 0
    for index in range(8):
        lo = index * held
        part_cfg = dataclasses.replace(
            cfg, num_experts_held=held, expert_held_offset=lo)
        part = dict(params, **{
            n: params[n][lo : lo + held] for n in ("w_gate", "w_up", "w_down")})
        with moe.collect_experts_hit() as hit:
            total = total + moe.moe_mlp(part, x, part_cfg)
        if part_cfg.grouped:
            landed += int(hit.rows_held[0])
    if held >= moe.GROUPED_MIN_EXPERTS:
        assert landed == T * cfg.num_experts_per_tok
    s = {"E": E, "held": E, "first": 0, "k": 4, "n_shared": 2}
    w = dict(params, **shared)
    with jax.default_matmul_precision("highest"):
        want = cohere2_moe.routed_experts(x, w, s) + \
            cohere2_moe.shared_experts(x, w, s)
        total = total + cohere2_moe.shared_experts(x, w, s)
    np.testing.assert_allclose(total, want, rtol=2e-4, atol=2e-4)


# -- what such a model refuses, and what it costs the others --------------

@pytest.mark.parametrize("change,match", [
    (dict(speculative_k=2), "speculative drafting"),
    (dict(kv_sp=True), "kv_sp"),
    (dict(kv_quant="int8"), "int8 KV"),
])
def test_what_a_model_with_cache_groups_refuses(change, match):
    with pytest.raises(ValueError, match=match):
        engine_config(**change).validate()


async def test_refused_mechanisms_that_move_pages():
    with pytest.raises(ValueError, match="block manager"):
        TpuEngine(engine_config(), block_manager=object())
    engine = TpuEngine(engine_config())
    pre = PreprocessedRequest(
        token_ids=[1, 2, 3], sampling=SamplingOptions(temperature=0.0),
        stop=StopConditions(max_tokens=2),
    )
    with pytest.raises(RequestError, match="remote prefill"):
        engine.prefill_only_batch([(pre, "r1", False)])
    with pytest.raises(RequestError, match="remote prefill"):
        engine.begin_remote(Context(pre.to_wire()), pre)


def test_prefix_caching_is_forced_off_and_said(caplog):
    import logging

    cfg = engine_config(enable_prefix_caching=True)
    with caplog.at_level(logging.INFO):
        cfg.validate()
    assert not cfg.enable_prefix_caching
    assert "prefix caching is off" in caplog.text


#: ``operand_layout`` of the parent commit (1b3c32d) for the five accepted
#: configurations' variants at T=64, S=8, 16 blocks a sequence, k=2: the
#: size and the template's SHA-1, and the common head's (first, end) words
PARENT_LAYOUTS = {
    "plain": (466, "87da82813a17264264db331f96491f34b12165a2"),
    "plain+rec": (474, "22ec18e3620fa92398a77729ebcf535934526c28"),
    "block": (530, "b92f009d74b132b4c521870c08eb7faedff63799"),
    "spec": (490, "f7cfee4bf151a50cd30d4982039ff98162b49a4c"),
    "extras": (506, "69f55e7234da87ea139ee8c08ae060922428debe"),
}
PARENT_HEAD = {
    "token_ids": (0, 64), "token_pos": (64, 128), "slot_mapping": (128, 192),
    "token_seq": (192, 256), "block_tables": (256, 384),
    "q_start": (384, 392), "q_len": (392, 400), "kv_len": (400, 408),
    "row_start": (408, 416), "use_prev": (416, 424), "prev_row": (424, 432),
    "top_k": (432, 440), "seed": (440, 448), "temp": (448, 456),
    "top_p": (456, 464), "key": (464, 466),
}


@pytest.mark.parametrize("variant", sorted(PARENT_LAYOUTS))
def test_a_one_group_models_operands_are_the_parents(variant):
    """The five accepted configurations' layouts (Mistral and Mixtral
    ``plain``, tp4 ``plain``, SDAR ``block``, Ling ``plain+rec``; ``spec``
    and ``extras`` beside them): every segment where the parent had it,
    nothing added, the template's bytes the same."""
    import hashlib

    lay = operand_layout(64, 8, 16, 2, variant)
    for name, span in PARENT_HEAD.items():
        assert lay.segs[name][:2] == span, name
    assert not any(name.endswith("_1") for name in lay.segs)
    size, template = PARENT_LAYOUTS[variant]
    assert lay.size == size
    assert hashlib.sha1(lay.template.tobytes()).hexdigest() == template


def test_groups_cost_the_other_models_nothing():
    """No segment, no second pool, no table where every layer is of one
    kind; a windowed model with ONE group (Mistral) still releases behind
    its window from its one pool."""
    two = operand_layout(64, 8, 16, 0, "plain+grp2")
    one = operand_layout(64, 8, 16, 0, "plain")
    assert two.size == one.size + 8 * 16 + 64
    assert list(two.segs)[: len(one.segs)] == list(one.segs)
    for preset in ("tiny-test", "tiny-moe-test", "tiny-mla-test",
                   "tiny-sdar-test", "tiny-ling-test", "mistral-7b",
                   "mixtral-8x7b", "sdar-30b-a3b", "ling-3.0-flash-ep4-l8"):
        assert len(PRESETS[preset]().cache_groups) == 1, preset
    assert PRESETS["mistral-7b"]().cache_groups == (4096,)
    assert PRESETS["command-a-plus-ep8-l4"]().cache_groups == (0, 4096)
    # A group's pool cut to num_blocks still holds one sequence's need.
    small = engine_config(num_blocks=13, max_model_len=96, max_num_seqs=4)
    small.validate()
    per_seq = -(-WINDOW // 8) + -(-small.unified_token_budget // 8) + 2
    assert small.group_num_blocks == (13, 13)
    assert 13 >= min(per_seq, small.max_blocks_per_seq) + 1
    cfg = EngineConfig(
        model=ModelConfig.tiny_test().scaled(sliding_window=16),
        dtype="float32", block_size=8, num_blocks=32, max_num_seqs=2,
        max_model_len=64, unified_token_budget=16)
    assert cfg.group_num_blocks == (32,)
    runner = ModelRunner(cfg, rng_seed=0)
    assert runner._ladder_variant == "plain"
    assert {slot_rows(layer)[0].shape[0] for layer in runner.kv_caches} == {
        32 * 8}
    sched = Scheduler(cfg, BlockAllocator(32, 8))
    seq = _sequence(40)
    sched.add(seq)
    assert sched.next_prefill() is seq and len(seq.tables) == 1
    assert sched.fund_span(seq, 40)
    assert sched.evict_behind_window(seq, 40) == (40 - 16) // 8
    assert seq.evicted == [3] and seq.lane_block_ids is seq.block_ids


@pytest.mark.parametrize("preset", [
    "tiny-test", "tiny-moe-test", "tiny-ling-test", "tiny-command-a-test"])
def test_only_this_family_draws_its_embedding_rows_at_deviation_one(preset):
    """``embed_init_std``: a token's own row leads the stream, so seeded
    routing is a token's own (PERF.md section 6, PR 45); every other
    family's table is the 1 / sqrt(vocab) draw it was."""
    cfg = PRESETS[preset]()
    key = jax.random.PRNGKey(3)
    embed = llama.init_params(key, cfg, jnp.float32)["embed"]
    legacy = llama._dense_init(
        jax.random.split(key, 3)[1], embed.shape, jnp.float32)
    if preset == "tiny-command-a-test":
        assert cfg.embed_init_std == 1.0
        assert abs(float(embed.std()) - 1.0) < 0.05
        np.testing.assert_allclose(
            embed, legacy * cfg.vocab_size**0.5, rtol=1e-5)
    else:
        assert not cfg.embed_init_std
        np.testing.assert_array_equal(embed, legacy)


def _held_share(seed: int, embed_fan_in: int) -> float:
    """Per cent of the routed rows that land on experts 0-15 of 128, over
    four layers of the reference at a small width, on letters as the
    benchmark's traffic sends them."""
    ref = cohere2_moe
    cfg = {"num_experts": 16, "num_hidden_layers": 4, "hidden_size": 128,
           "intermediate_size": 64, "num_shared_experts": 4,
           "num_attention_heads": 8, "num_key_value_heads": 2,
           "head_dim": 16, "vocab_size": 4096, "num_experts_per_tok": 8,
           "sliding_window": 128, "layer_switch": 4, "rope_theta": 50000,
           "layer_norm_eps": 1e-5}
    s = ref.sizes(cfg, {"num_experts": 128}, {"index": 0})
    layer_keys, ek = ref.model_keys(seed, 4)
    letters = np.random.default_rng(seed).integers(97, 124, 512)
    x = ref._draw(ek, (s["V"], s["D"]), embed_fan_in, jnp.float32)[letters]
    held = total = 0
    for li in range(4):
        w = ref.layer_weights(layer_keys[li], s, jnp.float32)
        gates = np.asarray(ref.route(ref.layer_norm(x, s["eps"]), w, s)) > 0
        held, total = held + gates[:, :16].sum(), total + gates.sum()
        x = ref.block_out(x, w, s, s["full"][li])
    return 100.0 * held / total


def test_every_seed_gives_the_expert_share_its_eighth():
    """At 1 / sqrt(vocab) the stream behind layer 0 is the attention's
    context mean, every token picks the same few experts, and the share
    of rows held here is the seed's luck (on the chip 5.9-18.9 %, and the
    step's time with it); at deviation 1 each token chooses its own."""
    seeds = range(40, 50)
    before = [_held_share(sd, 4096) for sd in seeds]
    after = [_held_share(sd, 1) for sd in seeds]
    assert np.std(after) < 0.75 * np.std(before), (before, after)
    assert abs(np.mean(after) - 12.5) < 1.5, after
    assert max(after) - min(after) < 6.0, after


@pytest.mark.parametrize("preset", [
    "tiny-gemma-test", "gemma3-1b", "qwen2-windowed"])
def test_the_families_that_mixed_layers_before_serve_as_they_did(preset):
    """Gemma-3 (every sixth layer global) and Qwen2 with max_window_layers
    have window AND full layers and do not say ``cache_by_layer_group``:
    one pool, one table, the parent's operands, and every feature that
    reads one table as the whole past starts as it did: prefix caching
    stays on, a tp mesh, speculation and int8 KV validate. (With the
    flag the same options are refused by name: Command A+.)"""
    model = (
        ModelConfig.tiny_test().scaled(sliding_window=16, max_window_layers=1)
        if preset == "qwen2-windowed" else PRESETS[preset]()
    )
    kinds = {model.layer_window(li) for li in range(model.num_layers)}
    assert len(kinds) == 2 and not model.cache_by_layer_group
    assert model.cache_groups == (0,)
    assert {model.layer_cache_group(li)
            for li in range(model.num_layers)} == {0}
    kw = dict(model=model, dtype="float32", block_size=8, num_blocks=64,
              max_num_seqs=2, max_model_len=64, unified_token_budget=64)
    for options in ({}, {"mesh_shape": {"tp": 2}}, {"speculative_k": 2},
                    {"kv_quant": "int8"}):
        cfg = EngineConfig(**kw, **options)
        cfg.validate()
        assert cfg.enable_prefix_caching, options
        assert cfg.group_num_blocks == (64,)
    grouped = EngineConfig(
        **{**kw, "model": model.scaled(cache_by_layer_group=True)},
        speculative_k=2)
    assert len(grouped.model.cache_groups) == 2
    with pytest.raises(ValueError, match="by layer group"):
        grouped.validate()


def test_a_mixed_model_on_one_table_is_the_parents_program():
    """tiny-gemma-test through the runner: one pool of num_blocks for
    every layer, the plain layout with no group segment, and a lane's
    second place is the sequence's one table; nothing is released behind
    its windows, because its full layers read the same table."""
    cfg = EngineConfig(
        model=PRESETS["tiny-gemma-test"](), dtype="float32", block_size=8,
        num_blocks=32, max_num_seqs=2, max_model_len=64,
        unified_token_budget=64)
    cfg.validate()
    runner = ModelRunner(cfg, rng_seed=0)
    assert runner._ladder_variant == "plain"
    assert {slot_rows(layer)[0].shape[0] for layer in runner.kv_caches} == {
        32 * 8}
    sched = Scheduler(cfg, BlockAllocator(32, 8))
    seq = _sequence(60)
    sched.add(seq)
    assert sched.next_prefill() is seq and sched.fund_span(seq, 60)
    assert sched.evict_behind_window(seq, 60) == 0
    assert seq.lane_block_ids is seq.block_ids and all(seq.block_ids)


def test_named_scopes_mark_the_two_attention_kinds_and_the_shared_experts():
    runner = ModelRunner(engine_config(), rng_seed=SEED)
    text = runner.lower_unified_top().as_text(debug_info=True)
    for scope in ("attn_window", "attn_full", "shared_experts",
                  "expert_layer"):
        assert scope in text, scope


def test_presets_and_from_hf(tmp_path):
    whole = PRESETS["command-a-plus"]()
    share = PRESETS["command-a-plus-ep8-l4"]()
    assert whole.num_layers == 32 and whole.experts_here == 128
    assert [whole.layer_window(li) for li in range(4)] == [4096] * 3 + [0]
    assert [whole.layer_rope(li) == "none" for li in range(4)] == [
        False, False, False, True]
    assert share.num_experts == 128 and share.experts_here == 16
    assert share.num_layers == 4 and share.vocab_size == 32768
    assert share.group_layers(0) == 1 and share.group_layers(1) == 3
    row = next(
        json.loads(line) for line in open(
            "/opt/skills/guides/model-configs/architectures.jsonl")
        if json.loads(line).get("name") == "command-a-plus-05-2026")
    (tmp_path / "config.json").write_text(json.dumps(row["config"]))
    got = ModelConfig.from_hf(str(tmp_path))
    assert got == whole.scaled(name="cohere2_moe")
    odd = dict(row["config"])
    odd["layer_types"] = ["full_attention"] * 32
    (tmp_path / "config.json").write_text(json.dumps(odd))
    with pytest.raises(NotImplementedError, match="layer_types"):
        ModelConfig.from_hf(str(tmp_path))
    # the published count: 218.3 B parameters, 25.0 B active
    attn = 4096 * 16384 + 2 * 4096 * 1024 + 16384 * 4096
    expert = 3 * 4096 * 4096
    layer = attn + 4 * expert + 4096 * 128
    total = 32 * (layer + 128 * expert) + 262144 * 4096
    active = 32 * (layer + 8 * expert) + 262144 * 4096
    assert round(total / 1e9, 1) == 218.3 and round(active / 1e9, 1) == 25.0
