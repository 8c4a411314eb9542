"""A model that keeps a recurrent state beside the paged cache (Ling-3.0
family: KDA linear-attention layers, one latent-attention layer a group,
an expert share): the served path against the plain reference, slot reuse,
preemption, the expert share, what such a model refuses, and its tracing."""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import check
from chipbench.reference import ling
from chipbench.steps import recurrent_span
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
from dynamo_tpu.models import llama, moe
from dynamo_tpu.models.config import PRESETS, ModelConfig
from dynamo_tpu.runtime.engine import Context

pytestmark = pytest.mark.anyio

SEED = 3
#: the tiny preset under the reference's key names
PUBLISHED = dict(
    hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
    moe_shared_expert_intermediate_size=32, num_shared_experts=1,
    num_hidden_layers=8, num_attention_heads=4, head_dim=16, vocab_size=384,
    num_experts=32, num_experts_per_tok=4, n_group=4, topk_group=2,
    routed_scaling_factor=2.5, first_k_dense_replace=2, layer_group_size=6,
    short_conv_kernel_size=4, kda_lower_bound=-5, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    rope_theta=10000.0, rms_norm_eps=1e-6,
    expert_swiglu_limit_list=[0, 0, 0, 1.5],
    share_expert_swiglu_limit_list=[0, 0, 0, 0, 1.0],
)


#: every engine test's sequences fit (prompt + answer), answers up to ROWS
PAD_TO, ROWS = 128, 64


def engine_config(model=None, **kw) -> EngineConfig:
    base = dict(
        model=model or ModelConfig.tiny_ling_test(), dtype="float32",
        block_size=8, num_blocks=64, max_num_seqs=4, max_model_len=128,
        seed=SEED, unified_token_budget=32, unified_prefill_quantum=16,
    )
    base.update(kw)
    return EngineConfig(**base)


def reference_logits(tokens, rows, held: int = 0):
    pub, kw = dict(PUBLISHED), {}
    if held:
        pub["num_experts"] = held
        kw = dict(source_values={"num_experts": 32}, share={"index": 0})
    return np.asarray(ling.logits(pub, SEED, tokens, rows, "float32", **kw))


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


def follows_the_reference(prompt, got, held: int = 0) -> None:
    """Every served token is the argmax of the reference's ONE full forward
    pass over the prompt and the tokens served before it (float32 on both
    sides: a tie is the only way to differ, and the margin rules it out)."""
    # (One padded length and one row count for every call: the reference
    # compiles once. Causal layers never see the padding behind a row.)
    n = len(prompt) + len(got)
    assert n <= PAD_TO and len(got) <= ROWS
    seq = np.zeros((1, PAD_TO), np.int32)
    seq[0, :n] = list(prompt) + list(got)
    rows = np.minimum(
        np.arange(len(prompt) - 1, len(prompt) - 1 + ROWS), n - 2
    ).astype(np.int32)[None]
    want = reference_logits(seq, rows, held)[0][: len(got)]
    top2 = np.sort(want, axis=-1)[:, -2:]
    clear = top2[:, 1] - top2[:, 0] > 1e-3
    assert clear.mean() > 0.9
    assert (np.asarray(got)[clear] == want.argmax(-1)[clear]).all()


# -- the served path against the reference -------------------------------

@pytest.mark.parametrize("held,pallas,lens,budget", [
    (0, "0", (5, 37, 50), 32), (8, "0", (5, 37, 50), 32),
    (0, "1", (5, 37, 50), 32), (0, "1", (70, 150), 128),
])
def test_runner_logits_equal_the_references_forward_pass(
        monkeypatch, held, pallas, lens, budget):
    """Chunked prefill (prompts cut across dispatches, quanta beside decode
    lanes), then six decode steps, through the paged cache and the state
    table, by the benchmark's own step driver: logits against the
    reference's one full pass. Every expert held (the grouped path), a
    quarter of them (the dense path), and the Pallas kernels interpreted;
    the longer prompts go in spans of 70, 58 and 92 rows, across the chunk
    kernel's tile of 64 and its sub-chunks of 16."""
    monkeypatch.setenv("DYNAMO_TPU_PALLAS", pallas)
    # the three older cases run on the default engine_config(); the longer
    # prompts need a wider budget and a longer model length
    longer = {} if budget == 32 else dict(
        unified_token_budget=budget, unified_prefill_quantum=budget // 2,
        max_model_len=160)
    runner = ModelRunner(
        engine_config(ModelConfig.tiny_ling_test(held=held), **longer),
        rng_seed=SEED)
    assert runner.attention_path == ("pallas" if pallas == "1" else "xla")
    pad = 64 * -(-(max(lens) + 6) // 64)
    tokens = check.sample_tokens(11, 384, [n + 6 for n in lens], pad)
    out = recurrent_span.drive(runner, tokens, lens, 6, 11)
    assert runner.rec_state is None          # the driver gave it back
    assert out["decode"].sum() >= 6 * len(lens)
    want = reference_logits(tokens, out["rows"], held)
    v = check.verdict(out["logits"], want, out["served"], out["decode"],
                      out["judged"])
    assert v["rel_err"] < 2e-4, v
    assert v["token_mismatches"] == 0


def test_spans_lie_in_the_flat_batch_in_their_order():
    """What ``kda_chunk`` leans on (ops/linear_attention.py ``kda_ragged``):
    the runner packs a dispatch's spans into the flat batch in span order
    with no gap, ``row_start`` the running sum of ``q_len``. A tile writes
    64 rows from its first and the rows past its span's end are written
    again by the tile that owns them: a packer that reorders spans has to
    change the kernel's output path with it."""
    from dynamo_tpu.engine.runner import META_SEGMENTS

    runner = ModelRunner(engine_config(), rng_seed=SEED)
    greedy = (0.0, 0, 1.0)
    # a lane, a quantum, a lane, two quanta: lanes and quanta interleaved
    spans = [(40, 1), (0, 9), (17, 1), (5, 16), (3, 2)]
    lanes = [(list(range(n)), [1 + s], prefix, greedy)
             for s, (prefix, n) in enumerate(spans)]
    _base, meta, *_ = runner._unified_operands(lanes, None, 32)
    m = {name: np.asarray(a) for name, a in zip(META_SEGMENTS, meta)}
    q_len = np.array([n for _, n in spans])
    want = np.cumsum(q_len) - q_len
    assert np.array_equal(m["q_len"][: len(spans)], q_len)
    assert np.array_equal(m["row_start"][: len(spans)], want)
    owned = q_len.sum()
    assert np.array_equal(m["token_seq"][:owned], np.repeat(
        np.arange(len(spans)), q_len))
    assert np.array_equal(m["token_pos"][:owned], np.concatenate(
        [prefix + np.arange(n) for prefix, n in spans]))
    assert (m["token_pos"][owned:] < 0).all() and not m["q_len"][
        len(spans):].any()


async def test_engine_serves_the_references_tokens_and_counts_its_state():
    """Four lanes at once through ``TpuEngine`` at pipeline depth 2: decode
    lanes and prefill quanta share dispatches; the flight record, the
    gauges and the named scopes are there."""
    engine = TpuEngine(engine_config())
    assert engine.cfg.pipeline_depth == 2
    assert not engine.cfg.enable_prefix_caching      # forced off
    await engine.start()
    try:
        prompts = [list(range(2, 2 + p)) for p in (5, 23, 40, 9, 31, 17)]
        outs = await asyncio.gather(*(generate(engine, p, 9) for p in prompts))
        for prompt, got in zip(prompts, outs):
            assert len(got) == 9
            follows_the_reference(prompt, got)
        steps = [r for r in engine.debug_steps() if "dispatch_ms" in r]
        mixed = [r for r in steps
                 if r["kda_decode_lanes"] and r["kda_prefill_rows"]]
        assert mixed, "no dispatch held decode lanes beside a prefill quantum"
        assert sum(r["kda_fresh_spans"] for r in steps) == len(prompts)
        assert sum(r["kda_prefill_rows"] + r["kda_decode_lanes"]
                   for r in steps) == sum(
            r["decode_tokens"] + r["prefill_tokens"] for r in steps)
        # the chunk kernel's tiles, by hand: a span of more rows than one
        # is whole tiles of 64 rows; this engine's quantum is 16 rows, so
        # every such span is one tile
        assert all(
            r["kda_chunk_tiles"] == r["lanes"] - r["kda_decode_lanes"]
            for r in steps
        )
        # every expert is held: each row of the (padded) budget lands 4
        # times in each of 6 layers
        assert all(r["moe_rows_held"] % 24 == 0 and r["moe_rows_held"] >= 24 * (
            r["decode_tokens"] + r["prefill_tokens"]) for r in steps)
        assert all(0 < r["moe_experts_hit"] <= 6 * 32 for r in steps)
        snap = engine.readiness()
        assert snap["recurrent_state_bytes"] == 7 * 5 * (
            4 * 16 * 16 * 4 + 3 * 3 * 4 * 16 * 4)
        assert snap["recurrent_state_slots_in_use"] == 0
        assert snap["kda_chunk_tiles_total"] == sum(
            r["kda_chunk_tiles"] for r in steps) > 0
        assert snap["kda_chunk_rows_total"] == sum(
            r["kda_prefill_rows"] for r in steps)
        # spans of 130, 64, 65 and 2 rows beside a lane: 3 + 1 + 2 + 1
        note = engine._plain_note(
            [(None, None, at, n) for at, n in
             ((0, 130), (7, 64), (0, 65), (3, 2), (9, 1))], 1, 261, 0.0, (0, 0))
        assert (note["kda_chunk_tiles"], note["kda_prefill_rows"]) == (7, 261)
        assert snap["attention_path"] == "xla"
    finally:
        await engine.stop()


async def test_the_extras_program_carries_the_state_too():
    """A request that asks for logprobs goes through the extras program
    (top rung, the count buffer beside the caches): the state rides there
    as it does on the ladder, beside a plain request in the same engine."""
    engine = TpuEngine(engine_config())
    await engine.start()
    try:
        prompts = [list(range(30, 51)), list(range(60, 100))]
        with_lp, plain = await asyncio.gather(
            generate(engine, prompts[0], 7, logprobs=1),
            generate(engine, prompts[1], 7),
        )
        follows_the_reference(prompts[0], with_lp)
        follows_the_reference(prompts[1], plain)
    finally:
        await engine.stop()


async def test_a_reused_slot_starts_from_zeros():
    """One slot: every request takes the slot the one before it left, with
    that one's state still in it; its tokens are those of a run alone."""
    engine = TpuEngine(engine_config(max_num_seqs=1))
    await engine.start()
    try:
        first = await generate(engine, list(range(50, 90)), 8)
        again = await generate(engine, list(range(7, 20)), 8)
        follows_the_reference(list(range(50, 90)), first)
        follows_the_reference(list(range(7, 20)), again)
    finally:
        await engine.stop()
    solo = TpuEngine(engine_config(max_num_seqs=1))
    await solo.start()
    try:
        assert await generate(solo, list(range(7, 20)), 8) == again
    finally:
        await solo.stop()


async def test_a_preempted_sequence_resumes_to_the_same_tokens(monkeypatch):
    """Too few pages for both answers: one sequence is preempted, its state
    discarded with its slot, and recomputed from position 0; the stream
    goes on with the tokens of an unpreempted run."""
    from dynamo_tpu.utils.tracing import tracer

    engine = TpuEngine(engine_config(num_blocks=9, max_model_len=64,
                                     max_num_seqs=2))
    preempted = []
    marks = []
    await engine.start()
    real = engine.scheduler.requeue_for_recompute
    real_mark = tracer().mark_if_active

    def requeue(seq):
        preempted.append(seq.total_len)
        real(seq)

    def mark(request_id, name):
        marks.append(name)
        return real_mark(request_id, name)

    monkeypatch.setattr(engine.scheduler, "requeue_for_recompute", requeue)
    monkeypatch.setattr(tracer(), "mark_if_active", mark)
    try:
        prompts = [list(range(5, 24)), list(range(40, 61))]
        outs = await asyncio.gather(*(generate(engine, p, 26) for p in prompts))
        assert preempted, "the pool was large enough: nothing was preempted"
        assert "recurrent_state_discarded" in marks
        for prompt, got in zip(prompts, outs):
            assert len(got) >= 26
            follows_the_reference(prompt, got)
    finally:
        await engine.stop()


# -- the expert share -----------------------------------------------------

def _moe_case(E=32, D=64, Im=32, T=24, seed=0):
    key = jax.random.PRNGKey(seed)
    kx, kp = jax.random.split(key)
    cfg = moe.MoeConfig(
        hidden_size=D, intermediate_size=Im, num_experts=E,
        num_experts_per_tok=4, gating="sigmoid", routed_scaling_factor=2.5,
        n_group=4, topk_group=2, swiglu_limit=1.5,
    )
    params = moe.init_moe_params(kp, cfg)
    params["router_bias"] = 0.05 * jax.random.normal(kx, (E,))
    return cfg, params, jax.random.normal(kx, (T, D), jnp.float32)


@pytest.mark.parametrize("held", [8, 16])
def test_the_four_shares_add_up_to_the_uncut_layer(held):
    """``model-configs`` section 4: the routed parts of all the shares,
    with the shared expert counted once, add up to what the uncut
    reference gives for the whole layer. 8 held experts run the dense
    path, 16 the grouped one (rows routed elsewhere dropped before the
    sort)."""
    import dataclasses

    E = 4 * held
    cfg, params, x = _moe_case(E=E)
    shared = {
        f"w_shared_{n}": 0.1 * jax.random.normal(
            jax.random.PRNGKey(i), shape, jnp.float32)
        for i, (n, shape) in enumerate(
            (("gate", (64, 32)), ("up", (64, 32)), ("down", (32, 64))))
    }
    total = jnp.zeros_like(x)
    landed = 0
    for index in range(4):
        lo = index * held
        part_cfg = dataclasses.replace(
            cfg, num_experts_held=held, expert_held_offset=lo)
        assert part_cfg.grouped == (held >= moe.GROUPED_MIN_EXPERTS)
        part = dict(params, **{
            n: params[n][lo : lo + held] for n in ("w_gate", "w_up", "w_down")})
        with moe.collect_experts_hit() as hit:
            total = total + moe.moe_mlp(part, x, part_cfg)
        if part_cfg.grouped:
            landed += int(hit.rows_held[0])
            assert 0 < int(hit[0]) <= held
    if held >= moe.GROUPED_MIN_EXPERTS:
        assert landed == x.shape[0] * cfg.num_experts_per_tok
    s = {"E": E, "held": E, "first": 0, "k": 4, "groups": 4, "top_groups": 2,
         "scale": 2.5}
    with jax.default_matmul_precision("highest"):
        want = ling.expert_layer(x, dict(params, **shared), s, 1.5, 0.0)
        total = total + ling.swiglu(
            x, *(shared[f"w_shared_{n}"] for n in ("gate", "up", "down")))
    np.testing.assert_allclose(total, want, rtol=2e-4, atol=2e-4)


# -- the reference is not its own witness --------------------------------

def test_the_references_scan_equals_the_closed_form():
    """``S_t = sum_i (prod_{j=i+1..t} M_j) beta_i k_i v_i^T`` with ``M_j =
    (I - beta_j k_j k_j^T) Diag(a_j)``, in numpy float64, against the
    reference's token-by-token scan."""
    r = np.random.default_rng(0)
    L, d = 6, 5
    q, k, v = (r.standard_normal((L, d)) for _ in range(3))
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    g = -5 * r.uniform(0.02, 0.4, (L, d))
    beta = r.uniform(0.1, 0.9, L)
    M = [(np.eye(d) - beta[j] * np.outer(k[j], k[j])) @ np.diag(np.exp(g[j]))
         for j in range(L)]
    want = []
    for t in range(L):
        S = np.zeros((d, d))
        for i in range(t + 1):
            P = np.eye(d)
            for j in range(i + 1, t + 1):
                P = M[j] @ P
            S += P @ (beta[i] * np.outer(k[i], v[i]))
        want.append(S.T @ q[t])
    f32 = lambda a: jnp.asarray(a, jnp.float32)[None, :, None]
    got = ling.kda_scan(f32(q), f32(k), f32(v), f32(g),
                        jnp.asarray(beta, jnp.float32)[None, :, None])
    np.testing.assert_allclose(got[0, :, 0], np.array(want), rtol=2e-4,
                               atol=2e-5)


def test_oracle_forward_is_the_references_forward():
    """The program's own no-cache oracle (``reference_forward``) against
    the plain reference, every layer kind in it."""
    model = ModelConfig.tiny_ling_test()
    params = llama.init_params(jax.random.PRNGKey(SEED), model, jnp.float32)
    # (37 tokens at the one padded shape of ``follows_the_reference``: the
    # reference compiles once for both.)
    tokens = np.zeros((1, PAD_TO), np.int32)
    tokens[0, :37] = np.arange(3, 40)
    rows = np.minimum(np.arange(ROWS), 36).astype(np.int32)[None]
    got = llama.reference_forward(model, params, jnp.asarray(tokens[0]))
    want = reference_logits(tokens, rows)[0][:37]
    assert check.row_errors(np.asarray(got)[:37], want).max() < 2e-4


# -- what such a model refuses, and what it costs the others --------------

@pytest.mark.parametrize("change,match", [
    (dict(speculative_k=2), "speculative drafting"),
    (dict(kv_sp=True), "kv_sp"),
    (dict(kv_quant="int8"), "int8 KV"),
    (dict(mesh_shape={"tp": 2}), "device mesh"),
    (dict(model=ModelConfig.tiny_ling_test().scaled(sliding_window=16)),
     "a sliding window"),
])
def test_what_a_model_with_recurrent_layers_refuses(change, match):
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


def test_state_costs_the_other_models_nothing():
    """No segment, no array, no program where every layer is attention:
    the operand layout and the program's operands are what they were."""
    plain = operand_layout(64, 8, 16, 0, "plain")
    rec = operand_layout(64, 8, 16, 0, "plain+rec")
    assert "state_slot" not in plain.segs
    assert rec.size == plain.size + 8
    assert (rec.views(rec.template.copy())["state_slot"] == 0).all()
    runner = ModelRunner(EngineConfig(
        model=ModelConfig.tiny_test(), dtype="float32", block_size=8,
        num_blocks=32, max_num_seqs=2, max_model_len=64,
        unified_token_budget=16), rng_seed=0)
    assert runner.rec_state is None and runner.recurrent_state_bytes == 0
    assert runner._ladder_variant == "plain"
    text = runner.lower_unified_top().as_text()
    n_args = text.split("func.func public @main(")[1].split(") ->")[0].count("%arg")
    leaves = len(jax.tree.leaves((runner.params, runner.kv_caches)))
    assert n_args == leaves + 2            # + the packed buffer and the feed


def test_named_scopes_mark_the_three_layer_kinds():
    runner = ModelRunner(engine_config(), rng_seed=SEED)
    text = runner.lower_unified_top().as_text(debug_info=True)
    for scope in ("kda_mixer", "latent_mixer", "expert_layer"):
        assert scope in text, scope


def test_presets_and_from_hf(tmp_path):
    import json

    whole = PRESETS["ling-3.0-flash"]()
    share = PRESETS["ling-3.0-flash-ep4-l8"]()
    assert whole.num_layers == 42 and whole.experts_here == 512
    assert [whole.layer_kind(li) for li in range(6)] == ["kda"] * 5 + ["attn"]
    assert len(whole.recurrent_layers) == 35
    assert share.num_experts == 512 and share.experts_here == 128
    assert whole.swiglu_limit(34) == 0 and whole.swiglu_limit(35) == 4
    assert whole.swiglu_limit(40, shared=True) == 7
    row = next(
        json.loads(line) for line in open(
            "/opt/skills/guides/model-configs/architectures.jsonl")
        if json.loads(line).get("name") == "Ling-3.0-flash")
    (tmp_path / "config.json").write_text(json.dumps(
        dict(row["config"], architectures=["BailingHybridForCausalLM"])))
    got = ModelConfig.from_hf(str(tmp_path))
    assert got == whole.scaled(name="bailing_hybrid")
