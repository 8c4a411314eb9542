"""A model whose layers are ONE part each (Nemotron-H family: Mamba-2
state-space mixers, attention without rotary embedding, non-gated experts
in a latent on an expert share): the served path against the plain
reference, the state-space kernels against their twin and the twin against
the recurrence written out, the share, the config and the checkpoint
names, what such a model refuses, and its tracing."""

import asyncio
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import check, control_lowered
from chipbench.reference import nemotron_h
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
from dynamo_tpu.models.config import PRESETS, ModelConfig
from dynamo_tpu.ops import ssd
from dynamo_tpu.ops.pallas import ssd as ssd_kernels
from dynamo_tpu.runtime.engine import Context

pytestmark = pytest.mark.anyio

SEED = 3
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
#: the tiny preset under the reference's key names
PUBLISHED = dict(
    hidden_size=64, intermediate_size=48, moe_intermediate_size=48,
    moe_shared_expert_intermediate_size=96, moe_latent_size=32,
    num_hidden_layers=7, hybrid_override_pattern="MEM*EME",
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    vocab_size=384, n_routed_experts=32, num_experts_per_tok=6,
    routed_scaling_factor=5.0, mamba_num_heads=8, mamba_head_dim=8,
    n_groups=2, ssm_state_size=16, conv_kernel=4, layer_norm_epsilon=1e-5,
)
PAD_TO, ROWS = 128, 64


def engine_config(model=None, **kw) -> EngineConfig:
    base = dict(
        model=model or ModelConfig.tiny_nemotron_h_test(), dtype="float32",
        block_size=8, num_blocks=64, max_num_seqs=4, max_model_len=128,
        seed=SEED, unified_token_budget=32, unified_prefill_quantum=16,
    )
    base.update(kw)
    return EngineConfig(**base)


def reference_logits(tokens, rows, held: int = 0):
    pub, kw = dict(PUBLISHED), {}
    if held:
        pub["n_routed_experts"] = held
        kw = dict(source_values={"n_routed_experts": 32}, share={"index": 0})
    return np.asarray(
        nemotron_h.logits(pub, SEED, tokens, rows, "float32", **kw))


# -- the served path against the reference -------------------------------

@pytest.mark.parametrize("held,pallas,lens,budget", [
    (16, "0", (5, 37, 50), 32), (16, "1", (5, 37, 50), 32),
    (0, "1", (150, 40), 288),
])
def test_runner_logits_equal_the_references_forward_pass(
        monkeypatch, held, pallas, lens, budget):
    """Chunked prefill (prompts cut across dispatches, quanta beside decode
    lanes), then six decode steps, through the state table and the paged
    cache of the ONE attention layer, by the benchmark's own step driver:
    logits against the reference's one full pass. Every expert held and
    half of them (both the grouped path), the kernels interpreted; the
    longer prompts go in spans of 144 rows and more, across the chunk
    kernel's tile of 128."""
    monkeypatch.setenv("DYNAMO_TPU_PALLAS", pallas)
    longer = {} if budget == 32 else dict(
        unified_token_budget=budget, unified_prefill_quantum=budget // 2,
        max_model_len=192)
    runner = ModelRunner(
        engine_config(ModelConfig.tiny_nemotron_h_test(held=held), **longer),
        rng_seed=SEED)
    assert runner.attention_path == ("pallas" if pallas == "1" else "xla")
    # one layer in seven pages: 2 entries x 2 heads x 16 (lane-padded to 128
    # on the Pallas path) x 4 B, in ONE array of joined pages
    assert runner.kv_bytes_per_token == 2 * 2 * (128 if pallas == "1" else 16) * 4
    assert [len(c) for c in runner.kv_caches] == [0, 0, 0, 1, 0, 0, 0]
    assert len(runner.rec_state) == 3
    pad = 64 * -(-(max(lens) + 6) // 64)
    tokens = check.sample_tokens(11, 384, [n + 6 for n in lens], pad)
    out = recurrent_span.drive(runner, tokens, lens, 6, 11)
    assert runner.rec_state is None          # the driver gave it back
    assert out["decode"].sum() >= 6 * len(lens)
    # the lowered-precision control reads the same rows without a runner
    rows, decode = control_lowered.plan_rows(lens, 6, budget)
    assert (rows == out["rows"]).all() and (decode == out["decode"]).all()
    want = reference_logits(tokens, out["rows"], held)
    v = check.verdict(out["logits"], want, out["served"], out["decode"],
                      out["judged"])
    assert v["rel_err"] < 2e-4, v
    assert v["token_mismatches"] == 0


def test_hidden_states_is_the_references_full_pass():
    cfg = ModelConfig.tiny_nemotron_h_test()
    params = llama.init_params(jax.random.PRNGKey(SEED), cfg, jnp.float32)
    tokens = check.sample_tokens(5, 384, [40], 64)
    got = llama.reference_forward(cfg, params, jnp.asarray(tokens[0, :40]))
    rows = np.arange(40, dtype=np.int32)[None]
    want = reference_logits(tokens, rows)[0]
    assert check.row_errors(np.asarray(got), want).max() < 2e-5


# -- the recurrence: twin against the rows written out, kernels against
# -- the twin ---------------------------------------------------------------

def dispatch(spans, T, S, dims, seed=0, slots=6):
    """Operands of ``ssd_ragged`` for ``spans`` [(prefix, rows, slot)] in a
    flat batch of ``T`` rows and ``S`` metadata rows, on a state table of
    random (finite) values."""
    H, P, G, N = dims
    rng = np.random.default_rng(seed)
    f = lambda *shape: rng.standard_normal(shape).astype(np.float32)
    x, B, C = f(T, H, P), f(T, G, N), f(T, G, N)
    dt = (0.3 * np.log1p(np.exp(f(T, H)))).astype(np.float32)
    la = (-np.exp(rng.uniform(0, 1, (T, H))) * dt).astype(np.float32)
    token_seq = np.zeros(T, np.int32)
    token_pos = -np.ones(T, np.int32)
    q_start, q_len, row_start, slot = (np.zeros(S, np.int32) for _ in range(4))
    r = 0
    for s, (prefix, n, at) in enumerate(spans):
        q_start[s], q_len[s], row_start[s], slot[s] = prefix, n, r, at
        token_seq[r : r + n] = s
        token_pos[r : r + n] = np.arange(prefix, prefix + n)
        r += n
    row_start[len(spans):] = r
    return tuple(jnp.asarray(a) for a in (
        x, dt, la, B, C, f(slots, H, P, N), token_seq, token_pos, q_start,
        q_len, row_start, slot))


def by_hand(ops, spans):
    """The recurrence written out in numpy, a span at a time."""
    x, dt, la, B, C, state = (np.asarray(a, np.float64) for a in ops[:6])
    H, G = x.shape[1], B.shape[1]
    y = np.zeros(x.shape)
    state = state.copy()
    r = 0
    for prefix, n, at in spans:
        S = np.zeros(state.shape[1:]) if prefix == 0 else state[at]
        for t in range(r, r + n):
            Bh, Ch = (np.repeat(m[t], H // G, axis=0) for m in (B, C))
            S = np.exp(la[t])[:, None, None] * S + (
                (dt[t][:, None] * x[t])[:, :, None] * Bh[:, None, :])
            y[t] = np.einsum("hpn,hn->hp", S, Ch)
        state[at] = S
        r += n
    return y, state


SPANS = [(0, 1, 1), (5, 1, 2), (0, 130, 3), (7, 40, 4), (3, 1, 5)]


def test_the_twin_is_the_recurrence_row_by_row():
    ops = dispatch(SPANS, 192, 8, (8, 8, 2, 16))
    y, state = ssd.ssd_ragged_xla(*ops)
    want_y, want_state = by_hand(ops, SPANS)
    assert np.abs(np.asarray(y) - want_y).max() < 2e-4
    assert np.abs(np.asarray(state) - want_state).max() < 2e-5


@pytest.mark.parametrize("dims,spans,T,S", [
    # lanes beside a fresh span of two tiles and a continued one
    ((8, 8, 2, 16), SPANS, 192, 8),
    # two heads to a packed tile of 128 sublanes, as at published widths
    ((4, 64, 2, 128), [(0, 1, 1), (9, 70, 2), (4, 1, 3)], 80, 4),
    # a padded rung: lanes alone, most metadata rows idle
    ((8, 8, 2, 16), [(6, 1, 2), (0, 1, 4)], 16, 8),
    # no lane at all: the chunk kernel alone
    ((8, 8, 2, 16), [(0, 129, 5)], 136, 4),
])
def test_the_kernels_agree_with_the_twin(dims, spans, T, S):
    """Fresh and continued spans, one-row lanes, idle metadata rows (their
    slot is 0, the trash slot: every other slot no span names keeps what
    it held)."""
    ops = dispatch(spans, T, S, dims)
    y0, s0 = ssd.ssd_ragged_xla(*ops)
    y1, s1 = ssd.ssd_ragged_pallas(ssd_kernels, *ops)
    assert np.abs(np.asarray(y0 - y1)).max() < 5e-4
    assert np.abs(np.asarray(s0 - s1))[1:].max() < 2e-5
    named = {at for _, _, at in spans}
    for slot in set(range(1, 6)) - named:
        assert np.array_equal(np.asarray(s1[slot]), np.asarray(ops[5][slot]))


@pytest.mark.parametrize("use_pallas", [False, True])
def test_the_state_does_not_depend_on_how_the_prompt_was_cut(use_pallas):
    dims = (8, 8, 2, 16)
    whole = dispatch([(0, 150, 1)], 160, 4, dims)
    _, want = ssd.ssd_ragged(*whole, use_pallas=use_pallas)
    for cut in ((60, 90), (1, 149), (128, 22), (149, 1)):
        state = whole[5]
        r = 0
        for n in cut:
            part = dispatch([(r, n, 1)], 160, 4, dims)
            # the same rows of the same sequence, fed as a span of its own
            rows = tuple(
                jnp.zeros_like(a).at[:n].set(w[r : r + n])
                for a, w in zip(part[:5], whole[:5]))
            _, state = ssd.ssd_ragged(
                *rows, state, *part[6:], use_pallas=use_pallas)
            r += n
        assert np.abs(np.asarray(state[1] - want[1])).max() < 2e-5, cut


# -- the expert share -----------------------------------------------------

def test_the_four_shares_add_up_to_the_uncut_layer():
    """``model-configs`` section 4: the routed parts of the four shares,
    each through ``W_up``, with the shared expert counted once, add up to
    what the uncut reference gives for the whole expert layer."""
    E, held, D, Z, Im, Is, T = 64, 16, 64, 32, 48, 96, 24
    s = nemotron_h.sizes({
        **PUBLISHED, "n_routed_experts": E, "moe_latent_size": Z,
        "moe_intermediate_size": Im,
        "moe_shared_expert_intermediate_size": Is,
        "hybrid_override_pattern": "E", "num_hidden_layers": 1})
    w = nemotron_h.layer_weights(jax.random.PRNGKey(1), s, 0, jnp.float32)
    w["router_bias"] = 0.05 * jax.random.normal(jax.random.PRNGKey(2), (E,))
    x = jax.random.normal(jax.random.PRNGKey(3), (T, D), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = nemotron_h.expert_layer(x, w, s)
        total = nemotron_h.relu2_mlp(x, w["u1"], w["u2"])   # once
        landed = 0
        for index in range(4):
            mcfg = moe.MoeConfig(
                hidden_size=D, intermediate_size=Im, num_experts=E,
                num_experts_per_tok=6, gating="sigmoid",
                routed_scaling_factor=5.0, num_experts_held=held,
                expert_held_offset=index * held, act="relu2",
                expert_input_size=Z,
            )
            assert mcfg.grouped
            lo = index * held
            params = {
                "w_router": w["w_router"], "router_bias": w["router_bias"],
                "w_up": w["w1"][lo : lo + held],
                "w_down": w["w2"][lo : lo + held],
            }
            with moe.collect_experts_hit() as hit:
                part = moe.moe_mlp(params, x, mcfg, expert_x=x @ w["w_dn"])
            landed += int(hit.rows_held[0])
            assert part.shape == (T, Z)
            total = total + part @ w["w_up"]
    assert landed == T * 6              # every routed pair lands once
    assert np.abs(np.asarray(total - want)).max() < 1e-4 * float(
        jnp.abs(want).max())


@pytest.mark.parametrize("K,N,itemsize,tile", [
    (2048, 768, 2, 768), (768, 2048, 2, 2048),        # SDAR
    (2560, 768, 2, 768), (768, 2560, 2, 2560),        # Ling
    (4096, 4096, 2, 512),                             # Command A+
    (5120, 1536, 2, 384), (1536, 5120, 2, 1280),      # DeepSeek-V2
    (1024, 2688, 2, 896), (2688, 1024, 2, 512),       # this family
    (64, 32, 4, 32),                                  # no multiple of 128
])
def test_the_grouped_kernels_tile_by_shape(K, N, itemsize, tile):
    """The tiles of the expert cells' shapes are what they were before
    ``gmm_tile`` learnt a width that is not a power of two times 128
    (Mixtral's 8 experts stay on the dense path and never ask)."""
    assert moe.gmm_tile(K, N, itemsize) == (K, tile)
    assert N % tile == 0 and K * tile * itemsize <= max(
        moe.GMM_TILE_BYTES, K * 128 * itemsize)
    assert not moe.MoeConfig(num_experts=8).grouped


# -- the config and the checkpoint's names -------------------------------

def catalog_config() -> dict:
    with open(CATALOG) as f:
        for line in f:
            row = json.loads(line)
            if row["name"] == "NVIDIA-Nemotron-3-Super-120B-A12B-BF16":
                return row["config"]
    raise AssertionError("the catalog has no such row")


def test_from_hf_reads_the_catalog_rows_config(tmp_path):
    cfg = catalog_config()
    (tmp_path / "config.json").write_text(json.dumps(cfg))
    got = ModelConfig.from_hf(str(tmp_path))
    assert got.scaled(name="nemotron-3-super") == PRESETS["nemotron-3-super"]()
    # two keys of one config, a field each
    assert (got.n_group, got.mamba_n_groups) == (1, 8)
    assert len(got.layer_pattern) == got.num_layers == 88
    kinds = [got.layer_kind(li) for li in range(88)]
    assert (kinds.count("ssd"), kinds.count("attn"), kinds.count("none")) == (
        40, 8, 40)


@pytest.mark.parametrize("key,value", [
    ("mlp_hidden_act", "silu"), ("n_group", 2), ("use_conv_bias", False),
    # the family's fourth letter, a dense MLP alone: in no configuration
    # that is served, so no branch of the program is kept for it
    ("hybrid_override_pattern", "ME-*" * 22),
])
def test_from_hf_refuses_what_is_not_served(tmp_path, key, value):
    cfg = {**catalog_config(), key: value}
    (tmp_path / "config.json").write_text(json.dumps(cfg))
    with pytest.raises(NotImplementedError, match="nemotron_h with"):
        ModelConfig.from_hf(str(tmp_path))


def test_the_share_preset_is_the_issues_arithmetic():
    m = PRESETS["nemotron-3-super-ep4-l11"]()
    assert m.layer_pattern[: m.num_layers] == "MEMEMEM*EME"
    assert m.cache_groups == (0,) and m.recurrent_layers == (0, 2, 4, 6, 9)
    assert [m.layer_cache_arrays(li) for li in range(11)] == [
        0, 0, 0, 0, 0, 0, 0, 2, 0, 0, 0]
    assert m.recurrent_state_arrays(0, 129, "bfloat16") == (
        ((129, 128, 64, 128), "float32"), ((129, 3, 10240), "bfloat16"))
    assert m.recurrent_state_arrays(1, 129, "bfloat16") == ()
    shapes = jax.eval_shape(
        lambda: llama.init_params(jax.random.PRNGKey(0), m, jnp.bfloat16))
    count = lambda tree: sum(
        int(np.prod(a.shape)) for a in jax.tree.leaves(tree))
    per = [count(layer) for layer in shapes["layers"]]
    assert per[0] == 109_640_064 and per[7] == 35_655_680
    assert per[1] == 54_530_560 + 128 * 5_505_024
    assert "wq" not in shapes["layers"][1] and "w_router" not in shapes[
        "layers"][0] and "w_gate" not in shapes["layers"][1]
    assert round(count(shapes) * 2 / 1e9, 2) == 9.30


def test_load_hf_weights_reads_a_seeded_state_dict(tmp_path):
    from safetensors.numpy import save_file

    cfg = ModelConfig.tiny_nemotron_h_test(held=16)
    whole = cfg.scaled(num_experts_held=0)
    params = llama.init_params(jax.random.PRNGKey(5), whole, jnp.float32)
    t = {}
    put = lambda name, a, tr=True: t.__setitem__(
        name, np.ascontiguousarray(np.asarray(a).T if tr else np.asarray(a)))
    put("backbone.embeddings.weight", params["embed"], False)
    put("backbone.norm_f.weight", params["ln_f"], False)
    put("lm_head.weight", params["lm_head"])
    mixer = {"w_in": "in_proj", "w_out": "out_proj", "wq": "q_proj",
             "wk": "k_proj", "wv": "v_proj", "wo": "o_proj",
             "w_router": "gate", "w_latent_down": "fc1_latent_proj",
             "w_latent_up": "fc2_latent_proj",
             "w_shared_up": "shared_experts.up_proj",
             "w_shared_down": "shared_experts.down_proj"}
    for i, layer in enumerate(params["layers"]):
        p = f"backbone.layers.{i}"
        put(f"{p}.norm.weight", layer["ln_attn"], False)
        for ours, theirs in mixer.items():
            if ours in layer:
                put(f"{p}.mixer.{theirs}.weight", layer[ours])
        if "conv_w" in layer:
            put(f"{p}.mixer.conv1d.weight",
                np.asarray(layer["conv_w"]).T[:, None, :], False)
            put(f"{p}.mixer.conv1d.bias", layer["conv_b"], False)
            put(f"{p}.mixer.norm.weight", layer["ln_ssd"], False)
            for name in ("A_log", "D", "dt_bias"):
                put(f"{p}.mixer.{name}", layer[name], False)
        if "w_router" in layer:
            put(f"{p}.mixer.gate.e_score_correction_bias",
                layer["router_bias"], False)
            for e in range(whole.num_experts):
                put(f"{p}.mixer.experts.{e}.up_proj.weight", layer["w_up"][e])
                put(f"{p}.mixer.experts.{e}.down_proj.weight",
                    layer["w_down"][e])
    save_file(t, str(tmp_path / "model.safetensors"))
    held = cfg.scaled(expert_held_offset=16)
    got = llama.load_hf_weights(held, str(tmp_path), jnp.float32)
    for i, (a, b) in enumerate(zip(got["layers"], params["layers"])):
        assert sorted(a) == sorted(b), i
        for name in a:
            want = b[name][16:32] if name in ("w_up", "w_down") and (
                "w_router" in b) else b[name]
            assert np.array_equal(np.asarray(a[name]), np.asarray(want)), (
                i, name)
    for name in ("embed", "ln_f", "lm_head"):
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


def follows_the_reference(prompt, got, held: int = 0) -> None:
    """Every served token is the argmax of the reference's ONE full forward
    pass over the prompt and the tokens served before it."""
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


async def test_engine_serves_lanes_that_join_and_leave():
    """Six requests over two lanes at pipeline depth 2: slots are reused (a
    fresh span over a slot another sequence left), decode lanes and prefill
    quanta share dispatches; the flight record, the gauges and the counters
    are there."""
    engine = TpuEngine(engine_config(
        ModelConfig.tiny_nemotron_h_test(held=16), max_num_seqs=2))
    assert not engine.cfg.enable_prefix_caching      # forced off
    await engine.start()
    try:
        prompts = [list(range(2, 2 + p)) for p in (5, 23, 40, 9, 31, 17)]
        outs = await asyncio.gather(*(
            generate(engine, p, 7 + i) for i, p in enumerate(prompts)))
        for i, (prompt, got) in enumerate(zip(prompts, outs)):
            assert len(got) == 7 + i
            follows_the_reference(prompt, got, held=16)
        steps = [r for r in engine.debug_steps() if "dispatch_ms" in r]
        assert any(r["ssd_decode_lanes"] and r["ssd_chunk_rows"]
                   for r in steps), "no lane beside a prefill quantum"
        assert sum(r["ssd_fresh_spans"] for r in steps) == len(prompts)
        assert sum(r["ssd_chunk_rows"] + r["ssd_decode_lanes"]
                   for r in steps) == sum(
            r["decode_tokens"] + r["prefill_tokens"] for r in steps)
        # this engine's quantum is 16 rows: every longer span is one tile
        assert all(r["ssd_chunk_tiles"] == r["lanes"] - r["ssd_decode_lanes"]
                   for r in steps)
        # half the experts are held: of a row's 6 pairs in 3 expert layers
        # some land here, never more than all
        rows = sum(r["decode_tokens"] + r["prefill_tokens"] for r in steps)
        landed = sum(r["moe_rows_held"] for r in steps)
        assert 0.3 < landed / (rows * 6 * 3) < 0.7
        assert all(0 < r["moe_experts_hit"] <= 3 * 16 for r in steps)
        snap = engine.readiness()
        assert snap["recurrent_state_bytes"] == 3 * 3 * (
            8 * 8 * 16 * 4 + 3 * (64 + 2 * 2 * 16) * 4)
        assert snap["kv_bytes_per_token"] == 2 * 2 * 16 * 4
        assert snap["kv_cache_arrays_per_layer"] == 1   # joined pages
        assert snap["ssd_chunk_tiles_total"] == sum(
            r["ssd_chunk_tiles"] for r in steps) > 0
        assert snap["ssd_chunk_rows_total"] == sum(
            r["ssd_chunk_rows"] for r in steps)
        assert snap["ssd_decode_lanes_total"] == sum(
            r["ssd_decode_lanes"] for r in steps) > 0
        assert snap["moe_grouped_rows_total"] == rows * 6 * 3
        # spans of 130, 128, 129 and 2 rows beside a lane: 2 + 1 + 2 + 1
        note = engine._plain_note(
            [(None, None, at, n) for at, n in
             ((0, 130), (7, 128), (0, 129), (3, 2), (9, 1))], 1, 389, 0.0,
            (0, 0))
        assert (note["ssd_chunk_tiles"], note["ssd_chunk_rows"]) == (6, 389)
    finally:
        await engine.stop()


def test_the_step_names_its_scopes_and_kernels(monkeypatch):
    """The named scopes the benchmark's trace readers go by, and the two
    state-space kernels under ``ssd_mixer``, in one lowered step."""
    from test_layer_spec import lower_rung, make_runner

    monkeypatch.setenv("DYNAMO_TPU_PALLAS", "1")
    jax.clear_caches()
    runner = make_runner(
        ModelConfig.tiny_nemotron_h_test(held=16), "scopes")
    text = lower_rung(runner, 32).as_text(debug_info=True)
    for scope in ("ssd_mixer", "attn_full", "expert_layer/latent_down",
                  "expert_layer/latent_up", "expert_layer/shared_experts",
                  "moe_grouped_ffn"):
        assert scope in text, scope
    for kernel in ("ssd_recurrent", "ssd_chunk"):
        assert kernel in text, kernel
