"""A model whose mixers are gated short convolutions among GQA layers of
narrow heads, over sigmoid-routed experts (LFM2 family): the served path
against the plain reference through the state table and the lane-padded
cache, a state that is a tail alone (cut at every offset, moved between
slots, discarded and recomputed), the router, the config and the
checkpoint's names, what such a model refuses, and its tracing."""

import asyncio
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import check, control_lowered
from chipbench.reference import lfm2_moe
from chipbench.steps import recurrent_span
from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.engine.engine import TpuEngine
from dynamo_tpu.engine.runner import ModelRunner
from dynamo_tpu.llm.protocols.common import (
    EngineOutput,
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.models import llama, moe
from dynamo_tpu.models.config import (
    LFM2_24B_LAYER_TYPES,
    PRESETS,
    RECURRENT_KINDS,
    ModelConfig,
)
from dynamo_tpu.runtime.engine import Context

pytestmark = pytest.mark.anyio

SEED = 3
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
#: the tiny preset under the reference's key names (no head_dim, as the
#: catalog's row: 64 / 4)
PUBLISHED = dict(
    conv_L_cache=3, conv_bias=False, hidden_size=64, intermediate_size=128,
    layer_types=list(LFM2_24B_LAYER_TYPES), moe_intermediate_size=32,
    norm_eps=1e-5, norm_topk_prob=True, num_attention_heads=4,
    num_dense_layers=2, num_experts=16, num_experts_per_tok=4,
    num_hidden_layers=7, num_key_value_heads=2,
    rope_parameters={"rope_theta": 1000000, "rope_type": "default"},
    routed_scaling_factor=1, use_expert_bias=True, vocab_size=384,
)
PAD_TO, ROWS = 128, 64
#: float32 served against the float32 reference reads 0.5-1.8e-6 a row
#: (rounding in another order). A tail kept in bfloat16 reads 1e-3 and
#: more and a router rounded to bfloat16 flips choices: both over it.
F32_TOL = 2e-5
#: bfloat16 served: the rows' 25th percentile reads 0.020-0.029 (seeds
#: 3-5); the reference with int8's weights in the program's place 0.054-
#: 0.077, over it in every one.
BF16_P25_TOL = 0.040


def engine_config(model=None, **kw) -> EngineConfig:
    base = dict(
        model=model or ModelConfig.tiny_lfm2_test(), dtype="float32",
        block_size=8, num_blocks=64, max_num_seqs=4, max_model_len=128,
        seed=SEED, unified_token_budget=32, unified_prefill_quantum=16,
    )
    base.update(kw)
    return EngineConfig(**base)


def reference_logits(tokens, rows, dtype="float32", seed=SEED, **kw):
    return np.asarray(
        lfm2_moe.logits(PUBLISHED, seed, tokens, rows, dtype, **kw))


def driven(runner, lens, seed=11):
    pad = 64 * -(-(max(lens) + 6) // 64)
    tokens = check.sample_tokens(seed, 384, [n + 6 for n in lens], pad)
    return tokens, recurrent_span.drive(runner, tokens, lens, 6, seed)


# -- the served path against the reference -------------------------------

@pytest.mark.parametrize("pallas,lens", [
    ("0", (5, 37, 50, 29)), ("1", (5, 37, 50, 29)),
    # the second prompt's first span is 1, 2 and 3 rows: the tail behind it
    # holds one row over a zero, then both rows, then the last two of three
    ("1", (31, 20)), ("1", (30, 20)), ("1", (29, 20)),
])
def test_runner_logits_equal_the_references_forward_pass(
        monkeypatch, pallas, lens):
    """Chunked prefill (prompts cut across dispatches, quanta beside decode
    lanes), then six decode steps of one row, through the state table and
    the paged cache of the two attention layers (lane-padded from 16 to 128
    where the kernel serves), by the benchmark's own step driver: logits
    against the reference's one full pass in float32."""
    monkeypatch.setenv("DYNAMO_TPU_PALLAS", pallas)
    runner = ModelRunner(engine_config(), rng_seed=SEED)
    assert runner.attention_path == ("pallas" if pallas == "1" else "xla")
    stored = 128 if pallas == "1" else 16
    # two layers in seven page: 2 entries x 2 heads x the STORED width x 4 B
    assert runner.kv_bytes_per_token == 2 * 2 * 2 * stored * 4
    assert runner.kv_cache_lane_pad == 1 - 16 / stored
    assert [len(c) for c in runner.kv_caches] == [0, 0, 1, 0, 0, 0, 1]
    # a conv layer's state is ONE array, the tail, in the served dtype
    assert [[a.shape for a in layer] for layer in runner.rec_state] == [
        [(5, 2, 64)]] * 5
    assert runner.recurrent_state_bytes_per_slot == 5 * 2 * 64 * 4
    tokens, out = driven(runner, lens)
    assert runner.rec_state is None          # the driver gave it back
    rows, decode = control_lowered.plan_rows(lens, 6, 32)
    assert (rows == out["rows"]).all() and (decode == out["decode"]).all()
    v = check.verdict(out["logits"], reference_logits(tokens, out["rows"]),
                      out["served"], out["decode"], out["judged"])
    assert v["rel_err"] < F32_TOL, v
    assert v["token_mismatches"] == 0


@pytest.mark.parametrize("what", ["bf16_tail", "bf16_router"])
def test_the_float32_tolerance_refuses_a_lower_precision(what):
    """What ``F32_TOL`` is for: the tail held in bfloat16 (the table cast;
    the convolution rounds to the table's dtype at its store), and the
    router's matrix at bfloat16's precision, each read over it."""
    runner = ModelRunner(engine_config(), rng_seed=SEED)
    if what == "bf16_tail":
        runner.rec_state = jax.tree.map(
            lambda a: a.astype(jnp.bfloat16), runner.rec_state)
    else:
        for layer in runner.params["layers"]:
            if "w_router" in layer:
                layer["w_router"] = layer["w_router"].astype(
                    jnp.bfloat16).astype(jnp.float32)
    tokens, out = driven(runner, (5, 37, 50, 29))
    v = check.verdict(out["logits"], reference_logits(tokens, out["rows"]),
                      out["served"], out["decode"], out["judged"])
    assert v["rel_err"] > 10 * F32_TOL, v


@pytest.mark.parametrize("seed", [3, 5])
def test_bfloat16_served_is_nearer_the_reference_than_int8_weights(
        monkeypatch, seed):
    monkeypatch.setenv("DYNAMO_TPU_PALLAS", "1")
    runner = ModelRunner(
        engine_config(dtype="bfloat16", seed=seed), rng_seed=seed)
    tokens, out = driven(runner, (5, 37, 50, 29), seed=11 + seed)
    want = reference_logits(tokens, out["rows"], "bfloat16", seed)
    sound = check.verdict(
        out["logits"], want, out["served"], out["decode"], out["judged"], 25)
    assert sound["rel_err"] < BF16_P25_TOL, sound
    lowered = reference_logits(
        tokens, out["rows"], "bfloat16", seed, lowered="int8_weights")
    control = check.verdict(
        lowered, want, lowered.argmax(-1), out["decode"], out["judged"], 25)
    assert control["rel_err"] > BF16_P25_TOL, control


def test_hidden_states_is_the_references_full_pass():
    cfg = ModelConfig.tiny_lfm2_test()
    params = llama.init_params(jax.random.PRNGKey(SEED), cfg, jnp.float32)
    tokens = check.sample_tokens(5, 384, [40], 64)
    got = llama.reference_forward(cfg, params, jnp.asarray(tokens[0, :40]))
    rows = np.arange(40, dtype=np.int32)[None]
    want = reference_logits(tokens, rows)[0]
    assert check.row_errors(np.asarray(got), want).max() < F32_TOL


# -- a state that is a tail alone ------------------------------------------

def test_a_tail_is_its_slots_and_not_its_lanes():
    """Two sequences prefilled into slots 1 and 2; then their tails are
    moved to slots 3 and 1 and the decode dispatches name the lanes in the
    other order: each sequence reads its own slot wherever its lane sits,
    and its logits are the reference's."""
    runner = ModelRunner(engine_config(), rng_seed=SEED)
    lens = (21, 34)
    tokens = check.sample_tokens(7, 384, [n + 4 for n in lens], 64)
    tables = [[1, 2, 3, 4, 5], [6, 7, 8, 9, 10]]
    lane = lambda b, at, n: (
        tokens[b, at : at + n].tolist(), tables[b], at, recurrent_span.GREEDY)
    runner.unified_step([lane(0, 0, 21), lane(1, 0, 11)], state_slots=[1, 2])
    runner.unified_step([lane(1, 11, 23)], state_slots=[2])
    moved = {0: 3, 1: 1}                       # sequence -> its new slot
    runner.rec_state = jax.tree.map(
        lambda a: a.at[jnp.asarray([3, 1])].set(a[jnp.asarray([1, 2])])
        .at[2].set(7.0),                       # what slot 2 held is gone
        runner.rec_state)
    served = []
    for i in range(4):
        out = runner.unified_step(
            [lane(1, lens[1] + i, 1), lane(0, lens[0] + i, 1)],
            state_slots=[moved[1], moved[0]])
        served.append(np.asarray(out.last)[:2])
    rows = np.stack([np.arange(n, n + 4) for n in lens]).astype(np.int32)
    want = reference_logits(tokens, rows).argmax(-1)       # [2, 4]
    assert (np.asarray(served).T == want[::-1]).all()


def test_a_conv_layer_keeps_one_array_and_the_older_kinds_what_they_had():
    lfm2 = ModelConfig.tiny_lfm2_test()
    assert lfm2.recurrent_state_arrays(0, 5, "bfloat16") == (
        ((5, 2, 64), "bfloat16"),)
    assert lfm2.recurrent_state_arrays(2, 5, "bfloat16") == ()   # it pages
    assert lfm2.recurrent_layers == (0, 1, 3, 4, 5)
    assert RECURRENT_KINDS == ("kda", "retention", "ssd", "conv")
    ling = ModelConfig.tiny_ling_test()
    assert ling.recurrent_state_arrays(0, 5, "bfloat16") == (
        ((5, 4, 16, 16), "float32"), ((5, 3, 3 * 4 * 16), "bfloat16"))
    brumby = ModelConfig.tiny_brumby_test()
    assert [dt for _, dt in brumby.recurrent_state_arrays(
        0, 5, "bfloat16")] == ["float32", "float32"]
    nemotron = ModelConfig.tiny_nemotron_h_test()
    assert nemotron.recurrent_state_arrays(0, 5, "bfloat16") == (
        ((5, 8, 8, 16), "float32"), ((5, 3, 64 + 2 * 2 * 16), "bfloat16"))


# -- the router ---------------------------------------------------------------

def test_the_bias_ranks_and_the_unbiased_scores_weigh():
    """Four experts, two a token: the bias lifts expert 3 over expert 1 in
    the ranking; the weights are the chosen experts' own sigmoids over
    their sum + 1e-6, which the bias never enters."""
    cfg = moe.MoeConfig(
        hidden_size=2, num_experts=4, num_experts_per_tok=2,
        gating="sigmoid", norm_topk_prob=True, norm_topk_eps=1e-6)
    logit = jnp.asarray([[2.0, 1.0, -3.0, 0.5]])
    params = {"w_router": jnp.concatenate([logit, 0 * logit]),
              "router_bias": jnp.asarray([0.0, 0.0, 0.0, 0.2])}
    x = jnp.asarray([[1.0, 0.0]])
    idx, gates = moe.moe_route(params, x, cfg)
    s = np.asarray(jax.nn.sigmoid(logit))[0]
    assert s[1] > s[3] and s[3] + 0.2 > s[1]
    assert sorted(np.asarray(idx)[0].tolist()) == [0, 3]
    order = np.asarray(idx)[0]
    np.testing.assert_allclose(
        np.asarray(gates)[0], s[order] / (s[order].sum() + 1e-6), rtol=1e-6)
    # without the constant the weights sum to 1; with it, just under
    plain = moe.moe_route(
        params, x, moe.MoeConfig(**{**cfg.__dict__, "norm_topk_eps": 0.0}))[1]
    assert float(plain.sum()) == pytest.approx(1.0, abs=1e-7)
    assert 1.0 - 2e-6 < float(gates.sum()) < 1.0
    # a seeded layer DRAWS the bias, and the reference draws the same
    layer = llama.init_layer_params(
        jax.random.PRNGKey(1), ModelConfig.tiny_lfm2_test(), 2, jnp.float32)
    assert 0.03 < float(jnp.std(layer["router_bias"])) < 0.3
    drawn = lfm2_moe.layer_weights(
        jax.random.PRNGKey(1), lfm2_moe.sizes(PUBLISHED), "attn", True,
        jnp.float32)
    assert np.array_equal(drawn["router_bias"], layer["router_bias"])
    assert np.array_equal(drawn["w2"], layer["w_down"])


# -- the config and the checkpoint's names -------------------------------

def catalog_config() -> dict:
    with open(CATALOG) as f:
        for line in f:
            row = json.loads(line)
            if row["name"] == "LFM2-24B-A2B":
                return row["config"]
    raise AssertionError("the catalog has no such row")


def test_from_hf_reads_the_catalog_rows_config(tmp_path):
    cfg = catalog_config()
    (tmp_path / "config.json").write_text(json.dumps(cfg))
    got = ModelConfig.from_hf(str(tmp_path))
    assert got.scaled(name="lfm2-24b-a2b") == PRESETS["lfm2-24b-a2b"]()
    assert got.head_dim == 64 and got.tie_word_embeddings
    kinds = [got.layer_kind(li) for li in range(40)]
    assert (kinds.count("conv"), kinds.count("attn")) == (30, 10)
    assert [li for li, k in enumerate(kinds) if k == "attn"] == list(
        range(2, 40, 4))
    assert [got.layer_ffn(li) for li in range(3)] == ["dense", "dense", "moe"]


@pytest.mark.parametrize("key,value", [
    ("conv_bias", True),
    ("layer_types", ["conv", "sliding_attention"] * 20),
    ("rope_parameters", {"rope_theta": 1e6, "rope_type": "yarn"}),
    ("num_hidden_layers", 48),
])
def test_from_hf_refuses_what_is_not_served(tmp_path, key, value):
    cfg = {**catalog_config(), key: value}
    (tmp_path / "config.json").write_text(json.dumps(cfg))
    with pytest.raises(NotImplementedError, match="lfm2_moe with"):
        ModelConfig.from_hf(str(tmp_path))


def test_the_cut_preset_is_the_issues_arithmetic():
    m = PRESETS["lfm2-24b-a2b-l10"]()
    assert [m.layer_kind(li) for li in range(m.num_layers)] == [
        "conv", "conv", "attn", "conv", "conv", "conv", "attn", "conv",
        "conv", "conv"]
    assert m.cache_groups == (0,) and m.recurrent_layers == (
        0, 1, 3, 4, 5, 7, 8, 9)
    assert m.recurrent_state_arrays(0, 129, "bfloat16") == (
        ((129, 2, 2048), "bfloat16"),)
    assert m.recurrent_state_bytes(1, "bfloat16") == 65_536
    assert PRESETS["lfm2-24b-a2b"]().recurrent_state_bytes(
        1, "bfloat16") == 240 * 1024
    shapes = jax.eval_shape(
        lambda: llama.init_params(jax.random.PRNGKey(0), m, jnp.bfloat16))
    count = lambda tree: sum(
        int(np.prod(a.shape)) for a in jax.tree.leaves(tree))
    mixer = lambda li, names: sum(
        count(shapes["layers"][li][n]) for n in names)
    assert mixer(0, ("w_in", "conv_w", "w_out")) == 16_783_360
    assert mixer(2, ("wq", "wk", "wv", "wo")) == 10_485_760
    assert mixer(0, ("w_gate", "w_up", "w_down")) == 72_351_744
    assert mixer(2, ("w_gate", "w_up", "w_down")) == 64 * 9_437_184
    assert "lm_head" not in shapes and "wq" not in shapes["layers"][0]
    assert 10.2 < count(shapes) * 2 / 1e9 < 10.8


def test_load_hf_weights_reads_a_seeded_state_dict(tmp_path):
    from safetensors.numpy import save_file

    cfg = ModelConfig.tiny_lfm2_test()
    params = llama.init_params(jax.random.PRNGKey(5), cfg, jnp.float32)
    t = {}
    put = lambda name, a, tr=True: t.__setitem__(
        name, np.ascontiguousarray(np.asarray(a).T if tr else np.asarray(a)))
    put("model.embed_tokens.weight", params["embed"], False)
    put("model.embedding_norm.weight", params["ln_f"], False)
    names = {"w_in": "conv.in_proj", "w_out": "conv.out_proj",
             "wq": "self_attn.q_proj", "wk": "self_attn.k_proj",
             "wv": "self_attn.v_proj", "wo": "self_attn.out_proj",
             "w_router": "feed_forward.gate"}
    ffn = {"w_gate": "w1", "w_up": "w3", "w_down": "w2"}
    for i, layer in enumerate(params["layers"]):
        p = f"model.layers.{i}"
        put(f"{p}.operator_norm.weight", layer["ln_attn"], False)
        put(f"{p}.ffn_norm.weight", layer["ln_mlp"], False)
        for ours, theirs in names.items():
            if ours in layer:
                put(f"{p}.{theirs}.weight", layer[ours])
        if "conv_w" in layer:
            put(f"{p}.conv.conv.weight",
                np.asarray(layer["conv_w"]).T[:, None, :], False)
        if "ln_q_head" in layer:
            put(f"{p}.self_attn.q_layernorm.weight", layer["ln_q_head"], False)
            put(f"{p}.self_attn.k_layernorm.weight", layer["ln_k_head"], False)
        for ours, theirs in ffn.items():
            if "w_router" in layer:
                for e in range(cfg.num_experts):
                    put(f"{p}.feed_forward.experts.{e}.{theirs}.weight",
                        layer[ours][e])
            else:
                put(f"{p}.feed_forward.{theirs}.weight", layer[ours])
        if "w_router" in layer:
            put(f"{p}.feed_forward.expert_bias", layer["router_bias"], False)
    save_file(t, str(tmp_path / "model.safetensors"))
    got = llama.load_hf_weights(cfg, str(tmp_path), jnp.float32)
    for i, (a, b) in enumerate(zip(got["layers"], params["layers"])):
        assert sorted(a) == sorted(b), i
        for name in a:
            assert np.array_equal(np.asarray(a[name]), np.asarray(b[name])), (
                i, name)
    assert sorted(got) == sorted(params) and "lm_head" not in got
    for name in ("embed", "ln_f"):
        assert np.array_equal(np.asarray(got[name]), np.asarray(params[name]))


# -- what such a model refuses --------------------------------------------

@pytest.mark.parametrize("kw,what", [
    (dict(speculative_k=2), "speculative"),
    (dict(kv_sp=2), "kv_sp"),
    (dict(kv_quant="int8"), "int8 KV"),
    (dict(mesh_shape={"tp": 2}), "mesh"),
])
def test_the_family_is_refused_what_every_recurrent_model_is(kw, what):
    with pytest.raises(ValueError, match=what):
        engine_config(**kw).validate()
    cfg = engine_config(enable_prefix_caching=True)
    cfg.validate()
    assert not cfg.enable_prefix_caching


# -- served through the engine -----------------------------------------------

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
    """Every served token is the argmax of the reference's ONE full forward
    pass over the prompt and the tokens served before it."""
    n = len(prompt) + len(got)
    assert n <= PAD_TO and len(got) <= ROWS
    seq = np.zeros((1, PAD_TO), np.int32)
    seq[0, :n] = list(prompt) + list(got)
    rows = np.minimum(
        np.arange(len(prompt) - 1, len(prompt) - 1 + ROWS), n - 2
    ).astype(np.int32)[None]
    want = reference_logits(seq, rows)[0][: len(got)]
    top2 = np.sort(want, axis=-1)[:, -2:]
    # (the tied head over rows drawn at 1 / sqrt(V) gives small logits: the
    # lead is judged against the row's own scale)
    clear = top2[:, 1] - top2[:, 0] > 1e-4 * np.sqrt(
        np.mean(want * want, axis=-1))
    assert clear.mean() > 0.9
    assert (np.asarray(got)[clear] == want.argmax(-1)[clear]).all()


async def test_engine_serves_lanes_that_join_and_leave():
    """Six requests over two lanes at pipeline depth 2: a slot is taken by
    a fresh span with the tail another sequence left in it, decode lanes
    and prefill quanta share dispatches; the flight record, the gauges and
    the counters are there."""
    engine = TpuEngine(engine_config(max_num_seqs=2))
    assert not engine.cfg.enable_prefix_caching      # forced off
    await engine.start()
    try:
        prompts = [list(range(2, 2 + p)) for p in (5, 23, 40, 9, 31, 17)]
        outs = await asyncio.gather(*(
            generate(engine, p, 7 + i) for i, p in enumerate(prompts)))
        for i, (prompt, got) in enumerate(zip(prompts, outs)):
            assert len(got) == 7 + i
            follows_the_reference(prompt, got)
        steps = [r for r in engine.debug_steps() if "dispatch_ms" in r]
        rows = sum(r["decode_tokens"] + r["prefill_tokens"] for r in steps)
        # rows through conv layers x those layers (five of seven)
        assert sum(r["conv_rows"] for r in steps) == 5 * rows
        assert all(r["ssd_decode_lanes"] == r["kda_decode_lanes"] == 0
                   for r in steps)
        assert all(0 < r["moe_experts_hit"] <= 5 * 16 for r in steps)
        # every expert is held, so the budget's padding rows are routed too
        assert sum(r["moe_rows_held"] for r in steps) >= rows * 4 * 5
        snap = engine.readiness()
        assert snap["recurrent_state_bytes_per_slot"] == 5 * 2 * 64 * 4
        assert snap["recurrent_state_bytes"] == 3 * 5 * 2 * 64 * 4
        assert snap["kv_bytes_per_token"] == 2 * 2 * 2 * 16 * 4
        assert snap["kv_cache_lane_pad_perc"] == 0.0     # the XLA twin's
        assert snap["kv_cache_arrays_per_layer"] == 1    # joined pages
        assert snap["moe_grouped_rows_total"] == rows * 4 * 5
    finally:
        await engine.stop()


async def test_a_preempted_sequence_is_recomputed_from_position_0(monkeypatch):
    """Too few pages for both answers: one sequence is preempted, its tail
    discarded with its slot, and recomputed from position 0 as a fresh
    span (zeros where a tail would be); the stream goes on with the tokens
    of the reference's one pass."""
    from dynamo_tpu.utils.tracing import tracer

    engine = TpuEngine(engine_config(num_blocks=9, max_model_len=64,
                                     max_num_seqs=2))
    preempted, marks = [], []
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


def test_the_step_names_its_scopes(monkeypatch):
    """The named scopes a trace is read by, in one lowered step: the conv
    mixer's three, the attention call, the grouped expert path. No Pallas
    kernel is the mixer's own."""
    from test_layer_spec import lower_rung, make_runner

    monkeypatch.setenv("DYNAMO_TPU_PALLAS", "1")
    jax.clear_caches()
    runner = make_runner(ModelConfig.tiny_lfm2_test(), "scopes")
    text = lower_rung(runner, 32).as_text(debug_info=True)
    for scope in ("conv_mixer/in_proj", "conv_mixer/conv",
                  "conv_mixer/out_proj", "attn_full", "moe_grouped_ffn"):
        assert scope in text, scope
    assert "ragged_paged_attention" in text
