"""A model whose EVERY layer is latent attention over a cache that holds
the latent once (DeepSeek-V2 family: a q latent, YaRN, a dense first layer,
softmax-scored experts under group-limited routing, shared experts, an
expert share): the served path against the plain non-absorbed reference,
the share, the cache's geometry and both kernel tiles reading their values
from the key slot, what reads one array and what refuses it, the preset."""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import check
from chipbench.reference import deepseek_v2 as ref
from chipbench.steps import span
from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.engine.engine import TpuEngine
from dynamo_tpu.engine.runner import ModelRunner
from dynamo_tpu.llm.protocols.common import (
    EngineOutput,
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.models import moe
from dynamo_tpu.models.config import PRESETS, ModelConfig
from dynamo_tpu.ops.attention import AttnDispatch, ragged_paged_attention
from dynamo_tpu.ops.pallas.ragged_attention import (
    ragged_paged_attention_pallas,
)
from dynamo_tpu.runtime.engine import Context

pytestmark = pytest.mark.anyio

SEED = 5
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
#: the tiny preset under the reference's key names
PUBLISHED = dict(
    hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
    num_hidden_layers=3, num_attention_heads=4, vocab_size=384,
    n_routed_experts=64, num_experts_per_tok=6, n_shared_experts=2,
    n_group=8, topk_group=3, norm_topk_prob=False, routed_scaling_factor=16,
    scoring_func="softmax", topk_method="group_limited_greedy",
    first_k_dense_replace=1, kv_lora_rank=32, q_lora_rank=48,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    rope_theta=10000.0, rms_norm_eps=1e-6,
    rope_scaling=dict(
        type="yarn", factor=40, original_max_position_embeddings=32,
        beta_fast=32, beta_slow=1, mscale=0.707, mscale_all_dim=0.707),
)
PAD_TO, ROWS = 128, 64


def engine_config(model=None, **kw) -> EngineConfig:
    base = dict(
        model=model or ModelConfig.tiny_deepseek_v2_test(), dtype="float32",
        block_size=8, num_blocks=64, max_num_seqs=4, max_model_len=128,
        seed=SEED, unified_token_budget=32, unified_prefill_quantum=16,
    )
    base.update(kw)
    return EngineConfig(**base)


def reference_logits(tokens, rows, held: int = 0, **changed):
    pub, kw = dict(PUBLISHED, **changed), {}
    if held:
        pub["n_routed_experts"] = held
        kw = dict(source_values={"n_routed_experts": 64}, share={"index": 0})
    return np.asarray(ref.logits(pub, SEED, tokens, rows, "float32", **kw))


# -- (a) the served path against the non-absorbed reference ----------------

@pytest.mark.parametrize("held,pallas", [(0, "0"), (16, "0"), (16, "1")])
def test_runner_logits_equal_the_references_forward_pass(
        monkeypatch, held, pallas):
    """Chunked prefill (prompts of 5-70 tokens packed into 32-row
    dispatches, three of them cut across a dispatch boundary, all past
    YaRN's original length of 32 but the first), then six decode steps of
    four lanes in the padded rung of 16, through the ONE array a layer, by
    the benchmark's own step driver: logits against the reference's one
    full pass in the published, non-absorbed form. Every expert held, and
    a quarter of them (routing groups 0 and 1: the grouped path of a
    share); the Pallas kernel interpreted, both tiles."""
    monkeypatch.setenv("DYNAMO_TPU_PALLAS", pallas)
    runner = ModelRunner(
        engine_config(ModelConfig.tiny_deepseek_v2_test(held=held)),
        rng_seed=SEED)
    assert runner.attention_path == ("pallas" if pallas == "1" else "xla")
    lens = (5, 37, 50, 70)
    tokens = check.sample_tokens(11, 384, [n + 6 for n in lens], PAD_TO)
    out = span.drive(runner, tokens, lens, 6, 11)
    assert out["decode"].sum() >= 6 * len(lens)
    want = reference_logits(tokens, out["rows"], held)
    v = check.verdict(out["logits"], want, out["served"], out["decode"],
                      out["judged"])
    # float32 on both sides: what is left is the order of the sums (the
    # absorbed products against the expanded ones) and, rarely, a routing
    # near-tie; a wrong scale, rotation or mask reads over 1e-2 (below)
    assert v["rel_err"] < 2e-4, v
    assert v["token_mismatches"] == 0
    # the comparison can see each mechanism: without YaRN's softmax factor,
    # with the groups' limit lifted, or with the chosen scores
    # renormalised, the same rows differ
    for changed in (dict(rope_scaling=None), dict(topk_group=8),
                    dict(norm_topk_prob=True)):
        off = reference_logits(tokens, out["rows"], held, **changed)
        assert check.verdict(out["logits"], off, out["served"], out["decode"],
                             out["judged"])["rel_err"] > 1e-2, changed


async def generate(engine, prompt, n):
    pre = PreprocessedRequest(
        token_ids=list(prompt),
        sampling=SamplingOptions(temperature=0.0),
        stop=StopConditions(max_tokens=n, ignore_eos=True),
    )
    chunks = []
    async for raw in engine.generate(Context(pre.to_wire())):
        chunks.append(EngineOutput.from_wire(raw).token_ids)
    return [t for c in chunks for t in c]


def follows_the_reference(prompt, got, pad_to: int = PAD_TO) -> None:
    """Every served token is the argmax of the reference's ONE full forward
    pass over the prompt and the tokens served before it, where its lead is
    clear."""
    n = len(prompt) + len(got)
    assert n <= pad_to and len(got) <= ROWS
    seq = np.zeros((1, pad_to), np.int32)
    seq[0, :n] = list(prompt) + list(got)
    rows = np.minimum(
        np.arange(len(prompt) - 1, len(prompt) - 1 + ROWS), n - 2
    ).astype(np.int32)[None]
    want = reference_logits(seq, rows)[0][: len(got)]
    top2 = np.sort(want, axis=-1)[:, -2:]
    clear = top2[:, 1] - top2[:, 0] > 1e-3
    assert clear.mean() > 0.9
    assert (np.asarray(got)[clear] == want.argmax(-1)[clear]).all()


async def test_a_second_request_reads_the_first_ones_prefix_blocks():
    """Prefix matching over the one array: a block is a block. The second
    prompt shares the first's 40 leading tokens (five whole blocks of 8);
    it reuses them and still serves the reference's tokens, and the
    engine says what its cache is."""
    engine = TpuEngine(engine_config())
    await engine.start()
    try:
        rng = np.random.default_rng(3)
        first = rng.integers(1, 384, 52).tolist()
        second = first[:40] + rng.integers(1, 384, 9).tolist()
        got = await generate(engine, first, 8)
        follows_the_reference(first, got)
        before = engine.readiness()["kv_reused_device_blocks_total"]
        got = await generate(engine, second, 8)
        follows_the_reference(second, got)
        ready = engine.readiness()
        assert ready["kv_reused_device_blocks_total"] - before == 5
        assert ready["kv_cache_arrays_per_layer"] == 1
        # three layers x one array x (32 + 8, lane-padded where the kernel
        # serves) x 4 B
        per_token = 3 * engine.runner.cache_head_dim * 4
        assert ready["kv_bytes_per_token"] == per_token
        steps = [r for r in engine.debug_steps() if "kv_bytes_live" in r]
        assert steps and all(
            r["kv_bytes_live"] % (8 * per_token) == 0 for r in steps)
        assert max(r["context_tokens_live"] for r in steps) >= 52
    finally:
        await engine.stop()


# -- (b) the share ----------------------------------------------------------

@pytest.mark.parametrize("held", [8, 16])
def test_the_four_shares_add_up_to_the_uncut_layer(held):
    """``model-configs`` section 4: the routed parts of all four shares,
    with the shared experts counted once, add up to what the uncut
    reference gives for the whole expert layer, under the softmax router's
    group MAX (8 groups, 3 stay, 6 experts a token, 16 x the score, no
    renormalisation). A share is two whole routing groups. 8 held experts
    run the dense path, 16 the grouped one."""
    E, D, Im, T = 4 * held, 64, 32, 24
    kx, kp = jax.random.split(jax.random.PRNGKey(0))
    cfg = moe.MoeConfig(
        hidden_size=D, intermediate_size=Im, num_experts=E,
        num_experts_per_tok=6, gating="softmax", norm_topk_prob=False,
        routed_scaling_factor=16.0, n_group=8, topk_group=3,
    )
    params = moe.init_moe_params(kp, cfg)
    x = jax.random.normal(kx, (T, D), jnp.float32)
    shared = {
        f"w_shared_{n}": 0.1 * jax.random.normal(
            jax.random.PRNGKey(i), shape, jnp.float32)
        for i, (n, shape) in enumerate(
            (("gate", (D, 2 * Im)), ("up", (D, 2 * Im)),
             ("down", (2 * Im, D))))
    }
    total = jnp.zeros_like(x)
    landed = 0
    for index in range(4):
        lo = index * held
        part_cfg = dataclasses.replace(
            cfg, num_experts_held=held, expert_held_offset=lo)
        assert part_cfg.grouped == (held >= moe.GROUPED_MIN_EXPERTS)
        part = dict(params, **{
            n: params[n][lo: lo + held] for n in ("w_gate", "w_up", "w_down")})
        with moe.collect_experts_hit() as hit:
            total = total + moe.moe_mlp(part, x, part_cfg)
        if part_cfg.grouped:
            landed += int(hit.rows_held[0])
    if held >= moe.GROUPED_MIN_EXPERTS:
        assert landed == T * cfg.num_experts_per_tok
    s = {"E": E, "held": E, "first": 0, "k": 6, "groups": 8, "top_groups": 3,
         "renorm": False, "scale": 16.0, "Is": 2 * Im}
    w = dict(params, **shared)
    with jax.default_matmul_precision("highest"):
        want = ref.routed_experts(x, w, s) + ref.shared_experts(x, w, s)
        total = total + ref.shared_experts(x, w, s)
        gates = np.asarray(ref.route(x, w, s))
    np.testing.assert_allclose(total, want, rtol=2e-4, atol=2e-4)
    # every token keeps 6 experts from at most 3 of the 8 groups
    assert ((gates > 0).sum(-1) == 6).all()
    assert ((gates.reshape(T, 8, -1) > 0).any(-1).sum(-1) <= 3).all()


# -- (c) the latent is held once -------------------------------------------

def test_the_cache_holds_the_latent_once_and_lings_family_keeps_the_pair():
    m = ModelConfig.tiny_deepseek_v2_test()
    assert [m.layer_cache_arrays(li) for li in range(3)] == [1, 1, 1]
    assert m.cache_arrays == 1 and m.num_cache_heads == 1
    runner = ModelRunner(engine_config(m), rng_seed=SEED)
    assert [len(layer) for layer in runner.kv_caches] == [1, 1, 1]
    (cache,) = runner.kv_caches[0]
    assert cache.shape == (64 * 8, 1, runner.cache_head_dim)
    assert runner.kv_bytes_per_token == 3 * runner.cache_head_dim * 4
    for name in ("deepseek-v2", "deepseek-v2-ep4-l5", "deepseek-v2-lite",
                 "deepseek-r1", "tiny-mla-test"):
        whole = PRESETS[name]()
        assert whole.cache_arrays == 1, name
        assert {whole.layer_cache_arrays(li)
                for li in range(whole.num_layers)} == {1}, name
    # Ling's one latent layer a group keeps (k, v) in this PR; its
    # recurrent layers keep no pages; a model without a latent keeps (k, v)
    ling = ModelConfig.tiny_ling_test()
    assert [ling.layer_cache_arrays(li) for li in range(8)] == (
        [0] * 5 + [2] + [0] * 2)
    assert ling.cache_arrays == 2
    assert PRESETS["ling-3.0-flash-ep4-l8"]().cache_arrays == 2
    lrunner = ModelRunner(
        EngineConfig(model=ling, dtype="float32", block_size=8,
                     num_blocks=32, max_num_seqs=2, max_model_len=64,
                     unified_token_budget=32, unified_prefill_quantum=16))
    assert [len(layer) for layer in lrunner.kv_caches] == (
        [0] * 5 + [2] + [0] * 2)
    assert lrunner.kv_bytes_per_token == 2 * lrunner.cache_head_dim * 4
    assert PRESETS["mistral-7b"]().cache_arrays == 2


def _ragged_case(rng, spans, H, D, num_blocks=48, max_blocks=10, bs=16):
    S, T = len(spans), sum(n for _, n in spans) + 3
    q_start = np.array([p for p, _ in spans], np.int32)
    q_len = np.array([n for _, n in spans], np.int32)
    row_start = np.concatenate([[0], np.cumsum(q_len)[:-1]]).astype(np.int32)
    token_seq = np.zeros(T, np.int32)
    token_pos = np.full(T, -1, np.int32)
    for s, (p, n) in enumerate(spans):
        token_seq[row_start[s]: row_start[s] + n] = s
        token_pos[row_start[s]: row_start[s] + n] = np.arange(p, p + n)
    tables = rng.permutation(np.arange(1, num_blocks))[: S * max_blocks]
    tables = tables.reshape(S, max_blocks).astype(np.int32)
    q = jnp.asarray(rng.standard_normal((T, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((num_blocks * bs, 1, D)), jnp.float32)
    meta = tuple(jnp.asarray(a) for a in (
        tables, q_start, q_len, q_start + q_len, row_start))
    return q, k, meta, jnp.asarray(token_seq), jnp.asarray(token_pos)


@pytest.mark.parametrize("spans", [
    [(40, 1), (7, 1), (0, 1)],                 # SHORT tile only: decode rows
    [(0, 37), (20, 21)],                       # LONG tile only: prefill spans
    [(33, 1), (16, 40), (0, 1), (5, 19)],      # both in one call
])
def test_both_tiles_read_their_values_from_the_key_slot(spans):
    """The kernel with no V operand (interpret mode) against the XLA twin
    given K for its values, and against the kernel given K as a second
    array: one array streamed, the same numbers. 8 heads over one cached
    head of 128."""
    rng = np.random.default_rng(len(spans))
    q, k, meta, token_seq, token_pos = _ragged_case(rng, spans, 8, 128)
    tables, _, _, kv_len, _ = meta
    want = ragged_paged_attention(
        q, k, k, tables, token_seq, token_pos, 16, kv_len=kv_len)
    once = ragged_paged_attention_pallas(q, k, None, *meta, block_size=16)
    pair = ragged_paged_attention_pallas(q, k, k, *meta, block_size=16)
    np.testing.assert_allclose(once, want, rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(np.asarray(once), np.asarray(pair))
    # the dispatch both callers go through, kernel and twin
    for use_pallas in (False, True):
        got = AttnDispatch(use_pallas=use_pallas).ragged(
            q, k, None, tables, token_seq, token_pos, *meta[1:], 16)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_int8_pages_of_a_held_once_cache_keep_one_scale_array(monkeypatch):
    """int8 KV over the one array: one scale array a layer, the kernel
    (interpreted) and the twin dequantise alike, and the served token is
    the bf16-free float32 runner's wherever the quantisation leaves the
    lead clear."""
    m = ModelConfig.tiny_deepseek_v2_test()
    prompt = list(range(3, 40))
    toks = {}
    for pallas in ("0", "1"):
        monkeypatch.setenv("DYNAMO_TPU_PALLAS", pallas)
        runner = ModelRunner(
            engine_config(m, kv_quant="int8", unified_token_budget=64,
                          unified_prefill_quantum=64), rng_seed=SEED)
        assert runner.kv_scales.shape == (3, 1, 64, 1)
        assert runner.kv_caches[0][0].dtype == jnp.int8
        out = runner.unified_step(
            [(prompt, [1, 2, 3, 4, 5], 0, span.GREEDY)])
        toks[pallas] = int(np.asarray(out.last)[0])
        assert float(jnp.abs(runner.kv_scales[:, :, 1:6]).min()) > 0
    assert toks["0"] == toks["1"]


def test_block_io_moves_the_one_array_and_back():
    """What KVBM offload/onboard and the device transfer of disaggregation
    call: a block of a held-once cache is [L, 1, bs, 1, D]; gathered,
    wiped and scattered back it is the block again, on the host and on the
    device; the tier's layout counts one array."""
    from dynamo_tpu.block_manager.config import KvLayoutConfig

    runner = ModelRunner(engine_config(), rng_seed=SEED)
    runner.unified_step([(list(range(3, 30)), [1, 2, 3, 4], 0, span.GREEDY)])
    D = runner.cache_head_dim
    held = runner.gather_many([1, 2, 3])
    assert held.shape == (3, 3, 1, 8, 1, D) and np.abs(held).max() > 0
    one = runner.gather_block(2)
    np.testing.assert_array_equal(one, held[1])
    runner.scatter_many([1, 2, 3], list(np.zeros_like(held)))
    assert not np.asarray(runner.gather_many([1, 2, 3])).any()
    runner.scatter_many([1, 2, 3], list(held))
    np.testing.assert_array_equal(runner.gather_many([1, 2, 3]), held)
    runner.scatter_many_device([4], runner.gather_many_device([2]))
    np.testing.assert_array_equal(runner.gather_block(4), held[1])
    lay = KvLayoutConfig.for_engine(runner.cfg, D, quant=None)
    assert lay.outer_dim == 1
    assert lay.block_bytes == held[0].nbytes


def test_what_a_held_once_cache_refuses_and_what_it_says():
    with pytest.raises(ValueError, match="holds its latent cache once"):
        engine_config(kv_sp=True, mesh_shape={"sp": 2}).validate()
    # prefix matching stays on: one pool, one table
    cfg = engine_config()
    cfg.validate()
    assert cfg.enable_prefix_caching
    # the layout a decode worker advertises says one array, and a prefill
    # worker refuses a peer that holds another count
    from dynamo_tpu.disagg import worker

    engine = type("Engine", (), {
        "cfg": cfg, "runner": ModelRunner(cfg, rng_seed=SEED)})()
    layout = worker.DecodeOperator._layout(
        type("Op", (), {"engine": engine})())
    assert layout["cache_arrays"] == 1
    pre = type("Pre", (), {"engine": engine})()
    assert worker.PrefillWorker._check_layout(pre, {"layout": layout})
    assert not worker.PrefillWorker._check_layout(
        pre, {"layout": dict(layout, cache_arrays=2), "request_id": "r"})
    assert not worker.PrefillWorker._check_layout(
        pre, {"layout": {k: v for k, v in layout.items()
                         if k != "cache_arrays"}, "request_id": "r"})


def test_the_held_once_call_is_named_inside_the_latent_mixer():
    runner = ModelRunner(engine_config(), rng_seed=SEED)
    jax.clear_caches()
    text = runner.lower_unified_top().as_text(debug_info=True)
    assert "latent_mixer/attn_latent" in text
    assert "attn_full" not in text and "attn_window" not in text


# -- (c2) two forms of latent attention, by span (PR 53) ---------------------

# The tiny widths' rule: absorbed 2 x (40 + 32) = 144 FLOP a pair, expanded
# 2 x (24 + 16) = 80, the up-projection 2 x 32 x 32 = 2,048 a key:
# K = 2 x 2,048 / 64 x the margin, at a budget of 256 rows.
BUDGET = 256


def _rule_k(budget: int = BUDGET) -> int:
    from dynamo_tpu.ops.pallas.latent_expanded import (
        EXPANDED_MARGIN,
        expanded_k,
    )

    k = expanded_k(ModelConfig.tiny_deepseek_v2_test(), budget)
    assert k == int(np.ceil(64 * EXPANDED_MARGIN)) and budget >= k
    return k


def _rule_sends(lens, decode_steps=0, budget=BUDGET):
    """[spans, rows] the rule sends through the expanded body when the
    benchmark's driver packs ``lens`` (``span.plan_steps``), and the
    (prefix, rows) spans it sends."""
    from dynamo_tpu.ops.pallas.latent_expanded import expanded_spans

    sent = []
    for step in span.plan_steps(lens, decode_steps, budget):
        pre = np.int32([p for _, p, _ in step])
        n = np.int32([n for _, _, n in step])
        gone = expanded_spans(n, pre + n, _rule_k(budget))
        sent += list(zip(pre[gone].tolist(), n[gone].tolist()))
    return [len(sent), sum(n for _, n in sent)], sent


def _long_runner(monkeypatch, pallas="1", held=16, **kw):
    monkeypatch.setenv("DYNAMO_TPU_PALLAS", pallas)
    cfg = dict(unified_token_budget=BUDGET, unified_prefill_quantum=BUDGET,
               max_model_len=1024, num_blocks=512, max_num_seqs=4)
    return ModelRunner(
        engine_config(ModelConfig.tiny_deepseek_v2_test(held=held),
                      **{**cfg, **kw}),
        rng_seed=SEED)


def test_long_spans_cross_attention_in_the_expanded_form(monkeypatch):
    """A budget of 256 rows holds spans over the rule (interpret mode,
    float32): prompts of 5, 200, 600 and 70 tokens packed into dispatches
    of 256, the 600 cut across four of them (quanta of 51, 256, 256 and 37
    rows behind 0, 51, 307 and 563 positions), every one past YaRN's
    original 32 positions, then six decode steps: logits against the
    reference's one pass in the published form. The long spans take the
    expanded body, the short spans and the decode rows the absorbed kernel,
    in ONE program; the scale (YaRN's ``mscale^2`` in it) is applied once:
    without it the rows differ."""
    runner = _long_runner(monkeypatch)
    lens = (5, 200, 600, 70)
    (spans, rows), sent = _rule_sends(lens, 6)
    assert (0, 200) in sent and (51, 256) in sent and (307, 256) in sent
    assert not [1 for p, n in sent if n in (5, 51, 37, 70, 1)]
    tokens = check.sample_tokens(11, 384, [n + 6 for n in lens], 640)
    out = span.drive(runner, tokens, lens, 6, 11)
    want = reference_logits(tokens, out["rows"], 16)
    v = check.verdict(out["logits"], want, out["served"], out["decode"],
                      out["judged"])
    assert v["rel_err"] < 2e-4, v
    assert v["token_mismatches"] == 0
    off = reference_logits(tokens, out["rows"], 16, rope_scaling=None)
    assert check.verdict(out["logits"], off, out["served"], out["decode"],
                         out["judged"])["rel_err"] > 1e-2
    # the host counted what the program's rule sent (the driver packs each
    # dispatch twice: once for the logits, once for the step)
    assert runner.attn_expanded_total == [2 * spans, 2 * rows]
    assert runner.attn_expanded == (0, 0)      # the last dispatch: decode


def test_a_span_one_row_under_the_rule_stays_absorbed(monkeypatch):
    """From position 0 a span passes the rule at K rows: two prompts of K -
    1 and K rows share ONE dispatch, the first stays in the absorbed kernel
    (it is the ragged kernel's only long fold), the second leaves it (the
    counter reads it alone), and both equal the reference."""
    k = _rule_k()
    lens = (k - 1, k)
    assert _rule_sends(lens, 2) == ([1, k], [(0, k)])
    runner = _long_runner(monkeypatch)
    tokens = check.sample_tokens(5, 384, [n + 2 for n in lens], 256)
    out = span.drive(runner, tokens, lens, 2, 5)
    want = reference_logits(tokens, out["rows"], 16)
    v = check.verdict(out["logits"], want, out["served"], out["decode"],
                      out["judged"])
    assert v["rel_err"] < 2e-4 and v["token_mismatches"] == 0, v
    assert runner.attn_expanded_total == [2, 2 * k]
    # the K - 1 rows are still the ragged kernel's long tile's
    assert runner.attn_folds_total[1] > 0


async def test_a_prefix_hit_behind_a_long_span_and_the_engines_counters(
        monkeypatch):
    """The engine's own path: a second prompt reuses the first's 96
    leading tokens (twelve blocks) and its remaining 120 rows, behind that
    prefix, pass the rule (from position 0 they would not). A step's
    flight record carries ``attn_expanded_spans`` / ``attn_expanded_rows``
    as the runner counted them for THAT dispatch, their sums are
    ``attn_expanded_*_total`` on ``readiness()``, and the served tokens
    are the reference's."""
    from dynamo_tpu.ops.pallas.latent_expanded import expanded_spans

    monkeypatch.setenv("DYNAMO_TPU_PALLAS", "1")
    k = _rule_k()
    engine = TpuEngine(engine_config(
        unified_token_budget=BUDGET, unified_prefill_quantum=BUDGET,
        max_model_len=256, num_blocks=128))
    await engine.start()
    try:
        assert engine.runner.attention_path == "pallas"
        rng = np.random.default_rng(3)
        first = rng.integers(1, 384, 200).tolist()
        second = first[:96] + rng.integers(1, 384, 120).tolist()
        for n, kv, sent in ((200, 200, True), (120, 120, False),
                            (120, 216, True)):
            assert bool(expanded_spans(
                np.int32([n]), np.int32([kv]), k)[0]) == sent
        got = await generate(engine, first, 4)
        follows_the_reference(first, got, 256)
        before = engine.readiness()["kv_reused_device_blocks_total"]
        got = await generate(engine, second, 4)
        follows_the_reference(second, got, 256)
        ready = engine.readiness()
        assert ready["kv_reused_device_blocks_total"] - before == 12
        steps = [r for r in engine.debug_steps() if "dispatch_ms" in r]
        took = [(r["attn_expanded_spans"], r["attn_expanded_rows"])
                for r in steps if r["attn_expanded_rows"]]
        assert took == [(1, 200), (1, 120)]
        assert all(r["attn_long_folds"] == 0 for r in steps)
        assert ready["attn_expanded_spans_total"] == 2
        assert ready["attn_expanded_rows_total"] == 320
    finally:
        await engine.stop()


@pytest.mark.parametrize("gate", ["xla_twin", "int8_weights", "int8_kv",
                                  "short_rung"])
def test_the_static_gates_keep_the_absorbed_program(monkeypatch, gate):
    """What the layer body reads of its operands decides whether a rung
    holds the expanded body at all: the XLA twin, int8 weights (``w_uk`` /
    ``w_uv`` quantised), int8 pages and a rung that no long span fits each
    compile the absorbed program alone, and the host counts nothing."""
    kw = {"int8_weights": dict(quant="int8"),
          "int8_kv": dict(kv_quant="int8"),
          "short_rung": dict(unified_token_budget=32,
                             unified_prefill_quantum=32)}.get(gate, {})
    runner = _long_runner(
        monkeypatch, pallas="0" if gate == "xla_twin" else "1", **kw)
    text = runner.lower_unified_top().as_text(debug_info=True)
    assert "pallas_expanded" not in text
    n = runner.cfg.unified_token_budget - 8
    lanes = [(list(range(1, n + 1)), [1 + i for i in range(-(-n // 8))],
              0, (0.0, 0, 1.0))]
    runner._unified_operands(lanes, None, runner.cfg.unified_token_budget)
    assert runner.attn_expanded == (0, 0)
    assert runner.attn_expanded_total == [0, 0]
    if gate == "short_rung":
        return
    # the same model with the gate open holds both bodies
    open_ = _long_runner(monkeypatch)
    assert "pallas_expanded" in open_.lower_unified_top().as_text(
        debug_info=True)
    open_._unified_operands(lanes, None, BUDGET)
    assert open_.attn_expanded == (1, n)


def test_a_tp_mesh_runs_the_expanded_body_a_shard(monkeypatch):
    """Under a ``tp`` mesh (the CPU's virtual devices) each shard runs the
    expanded body on its own query heads, ``w_uk`` / ``w_uv`` sharded with
    them, over the replicated array: a 200-row prompt and two decode steps
    equal the reference."""
    from dynamo_tpu.parallel.mesh import build_mesh

    monkeypatch.setenv("DYNAMO_TPU_PALLAS", "1")
    runner = ModelRunner(
        engine_config(
            unified_token_budget=BUDGET, unified_prefill_quantum=BUDGET,
            max_model_len=512, num_blocks=128),
        rng_seed=SEED, mesh=build_mesh({"tp": 2, "dp": 4}))
    assert runner.attention_path == "pallas"
    tokens = check.sample_tokens(7, 384, [202], 256)
    out = span.drive(runner, tokens, (200,), 2, 7)
    want = reference_logits(tokens, out["rows"])
    v = check.verdict(out["logits"], want, out["served"], out["decode"],
                      out["judged"])
    assert v["rel_err"] < 2e-4 and v["token_mismatches"] == 0, v
    assert runner.attn_expanded_total == [2, 2 * 200]


#: sha256 (16 digits) of the unified step's jaxpr at T 256 and T 16 on the
#: tree BEFORE the expanded form (commit 95a6a70), with the Pallas kernels:
#: Ling's family calls ``_qkv_mla`` and keeps (k, v); Mistral's never enters
#: ``latent_mixer``. Equal hashes: their programs are that tree's,
#: operation for operation. A PR that MEANS to change those programs
#: regenerates them (``_step_hash`` below, on its parent).
PARENT_STEP_HASHES = {
    "tiny_ling_test": ("6cde5bf6168414cc", "a43fb4b18db7ef0a"),
    "tiny_test": ("89e97bf9378c8589", "b5a70d6f103a9f2b"),
}


def _step_hash(cfg: ModelConfig, T: int, rows: int = 12) -> str:
    import hashlib
    import re
    from functools import partial

    from dynamo_tpu.models import llama
    from dynamo_tpu.ops.pallas.attention import cache_head_dim

    sds = jax.ShapeDtypeStruct
    f32, bs = jnp.float32, 16
    params = jax.eval_shape(
        lambda: llama.init_params(jax.random.PRNGKey(0), cfg, f32))
    page = sds(
        (64 * bs, cfg.num_cache_heads, cache_head_dim(cfg.kv_cache_head_dim)),
        f32)
    kv = [(page,) * cfg.layer_cache_arrays(li)
          if cfg.layer_kind(li) == "attn" else ()
          for li in range(cfg.num_layers)]
    rec = [tuple(sds(s, d) for s, d in
                 cfg.recurrent_state_arrays(li, rows, "float32"))
           for li in cfg.recurrent_layers] or None
    i32 = partial(sds, dtype=jnp.int32)
    meta = (i32((T,)),) * 4 + (i32((rows, 32)),) + (i32((rows,)),) * 4

    def step(params, kv, rec, slot, *meta):
        kw = dict(rec_state=rec, state_slot=slot) if rec is not None else {}
        return llama.unified(cfg, params, kv, *meta, bs,
                             attn=AttnDispatch(use_pallas=True), **kw)

    text = str(jax.make_jaxpr(step)(params, kv, rec, i32((rows,)), *meta))
    # a kernel's source location: the checkout's path and a line number
    text = re.sub(r" at /[^\s\]\)]*", "", text)
    return hashlib.sha256(text.encode()).hexdigest()[:16]


@pytest.mark.parametrize("preset", sorted(PARENT_STEP_HASHES))
def test_lings_and_mistrals_programs_are_the_parents(monkeypatch, preset):
    monkeypatch.setenv("DYNAMO_TPU_PALLAS", "1")
    cfg = getattr(ModelConfig, preset)()
    assert cfg.cache_arrays == 2
    got = tuple(_step_hash(cfg, T) for T in (256, 16))
    assert got == PARENT_STEP_HASHES[preset]


# -- (d) the preset is the catalog's row -------------------------------------

def test_preset_is_from_hf_of_the_catalogs_row(tmp_path):
    row = next(r for r in map(json.loads, open(CATALOG))
               if r.get("name") == "DeepSeek-V2")
    (tmp_path / "config.json").write_text(json.dumps(row["config"]))
    loaded = ModelConfig.from_hf(str(tmp_path))
    whole = PRESETS["deepseek-v2"]()
    for f in dataclasses.fields(ModelConfig):
        if f.name != "name":  # the loader names a model by its model_type
            assert getattr(loaded, f.name) == getattr(whole, f.name), f.name
    assert (loaded.name, whole.name) == ("deepseek_v2", "deepseek-v2")
    # what the preset has to reach in models/moe.py moe_route: softmax
    # scores, the group MAX, 3 of 8 groups, 6 experts, 16 x, no renorm
    assert (whole.gating, whole.n_group, whole.topk_group) == ("softmax", 8, 3)
    assert (whole.num_experts, whole.num_experts_per_tok) == (160, 6)
    assert whole.routed_scaling_factor == 16 and not whole.norm_topk_prob
    assert whole.n_shared_experts == 2 and whole.first_k_dense_replace == 1
    assert (whole.q_lora_rank, whole.kv_lora_rank) == (1536, 512)
    assert whole.kv_cache_head_dim == 576 and whole.num_heads == 128
    assert abs(whole.rope_scaling.attn_mscale() - 1.2608) < 1e-4
    assert whole.rope_scaling.embed_mscale() == 1.0
    share = PRESETS["deepseek-v2-ep4-l5"]()
    assert share == whole.scaled(
        name="deepseek-v2-ep4-l5", num_layers=5, num_experts_held=40,
        vocab_size=25600)
    # experts 0-39 are routing groups 0 and 1 of 8, whole
    assert share.experts_here * share.n_group == 2 * share.num_experts
    assert [share.moe_layer(li) for li in range(5)] == [False] + [True] * 4
