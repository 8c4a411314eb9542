"""A model whose every layer is a power-retention layer (Brumby family): no
layer pages, the only cache is a recurrent state. The mathematics of phi,
the recurrent form against the attention form, the served path against the
plain reference, the kernels against their twin, and a model with no pool
in the scheduler and the engine."""

import asyncio
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import check
from chipbench.reference import brumby
from chipbench.steps import retention_span
from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.engine.engine import TpuEngine
from dynamo_tpu.engine.runner import ModelRunner
from dynamo_tpu.llm.protocols.common import (
    EngineOutput,
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.models import llama
from dynamo_tpu.models.config import PRESETS, ModelConfig
from dynamo_tpu.ops import power_retention as pr
from dynamo_tpu.runtime.engine import Context
from tests.stepdrive import reference_greedy

pytestmark = pytest.mark.anyio

SEED = 5
#: the tiny preset under the reference's key names
PUBLISHED = dict(
    hidden_size=64, intermediate_size=128, num_hidden_layers=4,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    vocab_size=384, rope_theta=1000000.0, rms_norm_eps=1e-6,
    sliding_window=None, tie_word_embeddings=False,
)
MAX_LEN = 128


def engine_config(**kw) -> EngineConfig:
    base = dict(
        model=ModelConfig.tiny_brumby_test(), dtype="float32", block_size=8,
        max_num_seqs=4, max_model_len=MAX_LEN, seed=SEED,
        unified_token_budget=32, unified_prefill_quantum=16,
    )
    base.update(kw)
    return EngineConfig(**base)


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


def _rows(T=21, H=4, kvH=2, d=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (T, H, d)) * d ** -0.5
    k = jax.random.normal(ks[1], (T, kvH, d))
    v = jax.random.normal(ks[2], (T, kvH, d))
    lg = jax.nn.log_sigmoid(jax.random.normal(ks[3], (T, kvH)) + 2.0)
    return q, k, v, lg


def _through_spans(rows, cuts, use_pallas, budget=32, spans=4):
    """One sequence through ``retention_ragged`` cut into ``cuts``, its
    span in metadata row 1 at flat row 3, its state in slot 2 of a table
    that holds junk: (y of every row, the slot's state at the end)."""
    q, k, v, lg = rows
    kvH, d = k.shape[1:]
    state = tuple(
        jnp.full(shape, 7.0, jnp.float32)
        for shape in pr.state_shapes(3, kvH, d)
    )
    ys, start, off = [], 0, 3
    for n in cuts:
        meta = {name: np.zeros(spans, np.int32)
                for name in ("q_start", "q_len", "row_start", "slot")}
        meta["q_start"][1], meta["q_len"][1] = start, n
        meta["row_start"][1], meta["slot"][1] = off, 2
        token_seq = np.zeros(budget, np.int32)
        token_pos = np.full(budget, -1, np.int32)
        token_seq[off:off + n] = 1
        token_pos[off:off + n] = np.arange(start, start + n)

        def pad(x):
            out = np.zeros((budget,) + x.shape[1:], np.float32)
            out[off:off + n] = x[start:start + n]
            return jnp.asarray(out)

        y, state = pr.retention_ragged(
            pad(q), pad(k), pad(v), pad(lg), state, jnp.asarray(token_seq),
            jnp.asarray(token_pos), jnp.asarray(meta["q_start"]),
            jnp.asarray(meta["q_len"]), jnp.asarray(meta["row_start"]),
            jnp.asarray(meta["slot"]), use_pallas=use_pallas,
        )
        assert not np.asarray(y[:off]).any() and not np.asarray(y[off + n:]).any()
        ys.append(np.asarray(y[off:off + n]))
        start += n
    R = pr.phi_rows(d)
    return np.concatenate(ys), (
        np.asarray(state[0][2]), np.asarray(state[1][2][:, :R]))


# -- the mathematics ------------------------------------------------------

@pytest.mark.parametrize("d", [2, 16, 128])
def test_phi_is_the_symmetric_square(d):
    """phi(q) . phi(k) == (q . k)^2, in (d / 2 + 1) x d entries."""
    q, k = jax.random.normal(jax.random.PRNGKey(d), (2, 3, d))
    assert pr.phi(q).shape == (3, d // 2 + 1, d)
    got = np.asarray((pr.phi(q) * pr.phi(k)).sum((-1, -2)), np.float64)
    want = np.asarray((q * k).sum(-1), np.float64) ** 2
    np.testing.assert_allclose(got, want, rtol=2e-5)


CUTS = {"one span": [21], "quanta": [8, 13], "row by row": [1] * 21,
        "a quantum, two rows, a quantum": [16, 1, 1, 3]}


@pytest.mark.parametrize("cut", list(CUTS))
def test_the_recurrent_form_is_the_attention_form_whatever_the_cut(cut):
    """One layer's mixer, gates included: the state recurrence over spans
    equals the attention form's one pass, and the state behind the prompt
    does not depend on how the prompt was cut."""
    rows = _rows()
    want = np.asarray(pr.retention_attention(*rows, block=8))
    y, state = _through_spans(rows, CUTS[cut], use_pallas=False)
    np.testing.assert_allclose(y, want, atol=5e-5)
    _, whole = _through_spans(rows, [21], use_pallas=False)
    for got, ref in zip(state, whole):
        np.testing.assert_allclose(got, ref, atol=1e-5)


# (row by row is 21 dispatches of two interpreted kernels: a few rows do)
KERNEL_CUTS = {**{k: v for k, v in CUTS.items() if k != "row by row"},
               "rows, then a quantum": [1, 1, 1, 18]}


@pytest.mark.parametrize("cut", list(KERNEL_CUTS))
def test_the_kernels_interpreted_equal_the_twin(cut):
    """``retention_recurrent`` (one row a span) and ``retention_chunk``
    (tiles of a longer span, the state resident across them) in interpret
    mode against the XLA twin: outputs and the state they leave."""
    rows = _rows(seed=1)
    y_twin, s_twin = _through_spans(rows, KERNEL_CUTS[cut], use_pallas=False)
    y, state = _through_spans(rows, KERNEL_CUTS[cut], use_pallas=True)
    np.testing.assert_allclose(y, y_twin, atol=5e-5)
    for got, ref in zip(state, s_twin):
        np.testing.assert_allclose(got, ref, atol=1e-5)


def test_chunk_tiles_cover_every_longer_span_once():
    q_len = jnp.asarray([1, 0, 37, 1, 16, 2])
    row_start = jnp.asarray([0, 0, 1, 38, 39, 55])
    used, span, off, rows, valid = map(
        np.asarray, pr.chunk_tiles(q_len, row_start, 64, 16))
    assert span.tolist()[:5] == [2, 2, 2, 4, 5]
    assert used[:5].all() and not used[5:].any()
    assert off.tolist()[:5] == [0, 16, 32, 0, 0]
    assert sorted(rows[valid].tolist()) == list(range(1, 38)) + list(
        range(39, 57))
    assert (rows[~valid] == 64).all()


# -- the served path against the reference -------------------------------

@pytest.mark.parametrize("pallas", ["0", "1"])
def test_runner_logits_equal_the_references_forward_pass(monkeypatch, pallas):
    """Chunked prefill (prompts cut across dispatches, quanta beside decode
    lanes, padded and full rungs), then decode steps, through the state
    table alone, by the benchmark's own step driver: logits against the
    reference's one full pass in the attention form."""
    monkeypatch.setenv("DYNAMO_TPU_PALLAS", pallas)
    runner = ModelRunner(engine_config(), rng_seed=SEED)
    # the empty cache: no slots, no bytes, no pool
    assert [a.shape for a in jax.tree.leaves(runner.kv_caches)] == [
        (0, 2, runner.cache_head_dim)] * 8 and runner.group_blocks == ()
    # As many sequences as the batch has lanes: the step's two wide
    # dispatches hold every slot.
    lens, steps, rows = (5, 18, 37, 50), 6, 8
    shape = dict(rows=rows, quantum=32)
    counts = [retention_span.sample_len(n, steps, **shape) for n in lens]
    tokens = check.sample_tokens(11, 384, counts, 64)
    out = retention_span.drive(runner, tokens, lens, steps, 11, **shape)
    assert runner.rec_state is None          # the driver gave it back
    assert out["rows"].shape == (len(lens), rows)
    assert all(len(set(r)) == rows for r in out["rows"].tolist())
    assert out["decode"].sum(axis=1).tolist() == [7, 7, 6, 6]
    want = np.asarray(
        brumby.logits(PUBLISHED, SEED, tokens, out["rows"], "float32"))
    v = check.verdict(out["logits"], want, out["served"], out["decode"],
                      out["judged"])
    assert v["rel_err"] < 2e-4, v
    assert v["token_mismatches"] == 0


def test_oracle_forward_is_the_references_forward():
    cfg = ModelConfig.tiny_brumby_test()
    params = llama.init_params(jax.random.PRNGKey(SEED), cfg, jnp.float32)
    tokens = check.sample_tokens(2, 384, [40], 40)
    got = np.asarray(llama.reference_forward(cfg, params, jnp.asarray(tokens[0])))
    rows = np.arange(40, dtype=np.int32)[None]
    want = np.asarray(brumby.logits(PUBLISHED, SEED, tokens, rows, "float32"))[0]
    assert check.row_errors(got, want).max() < 1e-4


async def test_engine_serves_the_oracles_tokens_and_counts_its_state():
    """Six requests over four slots through ``TpuEngine``: decode lanes and
    prefill quanta share dispatches; every stream is the padded-length
    greedy oracle's; the flight record and the gauges are there."""
    engine = TpuEngine(engine_config())
    assert not engine.cfg.enable_prefix_caching      # forced off
    await engine.start()
    try:
        assert engine.allocator is None              # no pool
        prompts = [list(range(2, 2 + p)) for p in (5, 23, 40, 9, 31, 17)]
        outs = await asyncio.gather(*(generate(engine, p, 9) for p in prompts))
        params = engine.runner.params
        for prompt, got in zip(prompts, outs):
            assert got == reference_greedy(
                engine.cfg.model, params, prompt, 9, length=MAX_LEN)
        steps = [r for r in engine.debug_steps() if "dispatch_ms" in r]
        assert any(r["retention_decode_lanes"] and r["retention_prefill_rows"]
                   for r in steps)
        assert sum(r["retention_fresh_spans"] for r in steps) == len(prompts)
        assert not any(r["kda_decode_lanes"] for r in steps)
        snap = engine.readiness()
        per_slot = 2 * (9 * 16 * 16 + 16 * 16) * 4      # S and z (padded)
        assert snap["recurrent_state_bytes"] == 4 * 5 * per_slot
        assert snap["recurrent_state_slots_in_use"] == 0
        assert snap["recurrent_state_usage_perc"] == 0.0
    finally:
        await engine.stop()


async def test_a_model_with_no_pool_admits_and_reports_by_slots():
    """Two slots, four requests: two run, two wait for a SLOT (no block is
    asked for); usage reads slots in use over slots, not 0 over 0; a
    preempted sequence is replayed from position 0 in a fresh state."""
    engine = TpuEngine(engine_config(max_num_seqs=2))
    await engine.start()
    try:
        sched = engine.scheduler
        assert sched.allocators == [] and sched.allocator is None
        seen = []

        async def poll():
            while True:
                snap = engine.readiness()
                seen.append((snap["recurrent_state_slots_in_use"],
                             snap["gpu_cache_usage_perc"],
                             snap["recurrent_state_usage_perc"]))
                await asyncio.sleep(0.002)

        poller = asyncio.ensure_future(poll())
        prompts = [list(range(3, 30)), list(range(40, 75)),
                   list(range(80, 99)), list(range(7, 60))]
        outs = await asyncio.gather(*(generate(engine, p, 12) for p in prompts))
        poller.cancel()
        params = engine.runner.params
        for prompt, got in zip(prompts, outs):
            assert got == reference_greedy(
                engine.cfg.model, params, prompt, 12, length=MAX_LEN)
        assert max(n for n, _, _ in seen) == 2       # never more than slots
        assert {(u, r) for n, u, r in seen if n == 2} == {(1.0, 1.0)}
        assert {(u, r) for n, u, r in seen if n == 1} <= {(0.5, 0.5)}
        out = sched.metrics()
        assert out["kv_total_blocks"] == 0 and out["kv_active_blocks"] == 0
    finally:
        await engine.stop()


async def test_a_preempted_sequence_replays_from_position_0(monkeypatch):
    """Nothing preempts for memory here (a sequence's memory does not grow
    with its context); the recompute path (``requeue_for_recompute``) still
    serves whoever calls it: the state goes with the slot and the stream
    goes on with the tokens of an uninterrupted run."""
    from dynamo_tpu.utils.tracing import tracer

    engine = TpuEngine(engine_config(max_num_seqs=2))
    marks = []
    real_mark = tracer().mark_if_active
    monkeypatch.setattr(
        tracer(), "mark_if_active",
        lambda rid, name: (marks.append(name), real_mark(rid, name))[1])
    await engine.start()
    try:
        sched = engine.scheduler
        real = sched.decode_batch
        done = []

        def decode_batch(lookahead=1):
            batch = real(lookahead)
            for seq in ([] if done else batch):
                if len(seq.output_tokens) >= 4:
                    if seq.inflight_chunks:
                        return []    # as a full pool: wait for the drain
                    done.append(seq.total_len)
                    sched.requeue_for_recompute(seq)
                    return []
            return batch

        monkeypatch.setattr(sched, "decode_batch", decode_batch)
        prompt = list(range(5, 33))
        got = await generate(engine, prompt, 14)
        assert done and "recurrent_state_discarded" in marks
        # (the tokens served before the recompute count as prompt behind it)
        assert len(got) >= 14
        assert got == reference_greedy(
            engine.cfg.model, engine.runner.params, prompt, len(got),
            length=MAX_LEN)
    finally:
        await engine.stop()


# -- what such a model refuses, its presets and its tracing --------------

@pytest.mark.parametrize("change,match", [
    (dict(speculative_k=2), "speculative drafting"),
    (dict(kv_sp=True), "kv_sp"),
    (dict(kv_quant="int8"), "int8 KV"),
    (dict(mesh_shape={"tp": 2}), "device mesh"),
])
def test_a_retention_model_refuses_what_ling_refuses_in_the_same_words(
        change, match):
    with pytest.raises(ValueError, match=match) as brumby_says:
        engine_config(**change).validate()
    ling = dict(model=ModelConfig.tiny_ling_test(), num_blocks=64)
    with pytest.raises(ValueError, match=match) as ling_says:
        engine_config(**{**ling, **change}).validate()
    assert str(brumby_says.value).split(" has ")[1] == str(
        ling_says.value).split(" has ")[1]


def test_num_blocks_is_not_asked_of_a_model_with_no_pool():
    cfg = engine_config(num_blocks=1)
    cfg.validate()
    assert cfg.group_num_blocks == ()
    with pytest.raises(ValueError, match="num_blocks"):
        engine_config(model=ModelConfig.tiny_test(), num_blocks=1).validate()


def test_state_arrays_by_kind_are_said_in_one_place():
    brum, ling = ModelConfig.tiny_brumby_test(), ModelConfig.tiny_ling_test()
    assert brum.recurrent_state_arrays(0, 5, "bfloat16") == (
        ((5, 2, 9, 16, 16), "float32"), ((5, 2, 16, 16), "float32"))
    assert ling.recurrent_state_arrays(0, 5, "bfloat16") == (
        ((5, 4, 16, 16), "float32"), ((5, 3, 192), "bfloat16"))
    assert ling.recurrent_state_arrays(5, 5, "bfloat16") == ()
    assert brum.cache_groups == () and not brum.has_pool
    assert ling.cache_groups == (0,) and ling.has_pool
    runner = ModelRunner(engine_config(), rng_seed=SEED)
    assert [tuple((a.shape, a.dtype.name) for a in layer)
            for layer in runner.rec_state] == [
        brum.recurrent_state_arrays(li, 5, "float32") for li in range(4)]
    assert runner.recurrent_state_bytes == brum.recurrent_state_bytes(
        5, "float32") == sum(
        a.nbytes for a in jax.tree.leaves(runner.rec_state))


def test_named_scope_and_kernel_names_mark_the_layer(monkeypatch):
    monkeypatch.setenv("DYNAMO_TPU_PALLAS", "1")
    # jit keeps an inner function's jaxpr with the call stack of whoever
    # traced it first: without this, frames of another file's test that
    # ran before in this process (ragged_paged_attention_pallas) ride in
    # this program's debug locations
    jax.clear_caches()
    runner = ModelRunner(engine_config(), rng_seed=SEED)
    text = runner.lower_unified_top().as_text(debug_info=True)
    for name in ("retention_mixer", "retention_recurrent", "retention_chunk"):
        assert name in text, name
    assert "ragged_paged_attention" not in text


def test_presets_and_from_hf(tmp_path):
    whole = PRESETS["brumby-14b"]()
    assert whole.num_layers == 40 and whole.retention_degree == 2
    assert {whole.layer_kind(li) for li in range(40)} == {"retention"}
    assert len(whole.recurrent_layers) == 40 and not whole.has_pool
    # 8,320 rows of phi where the mathematical D is 8,256: under 8,704
    (S, _), (z, _) = whole.recurrent_state_arrays(0, 1, "bfloat16")
    assert S == (1, 8, 65, 128, 128) and z == (1, 8, 72, 128)
    assert 128 * 129 // 2 <= S[2] * S[4] == 8320 <= 8704
    row = next(
        json.loads(line) for line in open(
            "/opt/skills/guides/model-configs/architectures.jsonl")
        if json.loads(line).get("name") == "Brumby-14B-Base")
    (tmp_path / "config.json").write_text(json.dumps(row["config"]))
    assert ModelConfig.from_hf(str(tmp_path)) == whole
    windowed = dict(row["config"], sliding_window=4096, use_sliding_window=True)
    (tmp_path / "config.json").write_text(json.dumps(windowed))
    with pytest.raises(NotImplementedError, match="sliding_window"):
        ModelConfig.from_hf(str(tmp_path))
