"""Unified single-dispatch serving (docs/architecture/unified_step.md):
token-budget batch composition, the budget-ladder warmup contract, the
runner's device feed and its one step-program family, and end-to-end
token parity against the no-cache oracle."""

import asyncio

import numpy as np
import pytest

from dynamo_tpu.engine.compile_cache import (
    budget_ladder,
    default_shape_grid,
    token_budget,
)
from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.engine.scheduler import compose_unified
from dynamo_tpu.llm.protocols.common import (
    PreprocessedRequest,
    RequestError,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.runtime.engine import Context

pytestmark = pytest.mark.anyio


# ---------------------------------------------------------------------------
# token budget + shape grid
# ---------------------------------------------------------------------------


def test_token_budget_snaps_to_ladder():
    assert token_budget(1, 256) == 16
    assert token_budget(16, 256) == 16
    assert token_budget(17, 256) == 32
    assert token_budget(100, 256) == 128
    assert token_budget(300, 256) == 256  # capped at the ladder top
    assert budget_ladder(256) == [16, 32, 64, 128, 256]


def test_unified_shape_grid_is_budget_ladder_only():
    """The unified grid IS the ladder (plus ONE top-rung program per
    configured variant) — no prefill buckets, no lane axis, no
    decode-chunk ladder. This is the delete-the-grid contract, and it
    holds with speculation enabled: the spec program IS the ladder."""
    cfg = EngineConfig(
        model=ModelConfig.tiny_test(), num_blocks=64, max_model_len=256,
        unified_token_budget=256, sampling_extras=False,
    )
    specs = default_shape_grid(cfg)
    assert specs == [("unified", b) for b in (16, 32, 64, 128, 256)]
    assert len(specs) <= 8
    # Speculation adds ZERO programs — same ladder, spec-aware program.
    import dataclasses

    spec_cfg = dataclasses.replace(cfg, speculative_k=4)
    assert default_shape_grid(spec_cfg) == specs
    # Extras requests are rejected on spec engines, so the unified_full
    # program would be unreachable dead warmup weight there.
    spec_extras = dataclasses.replace(
        cfg, speculative_k=4, sampling_extras=True
    )
    assert default_shape_grid(spec_extras) == specs
    # Extras and multimodal each add exactly ONE top-rung program.
    full_cfg = dataclasses.replace(cfg, sampling_extras=True, multimodal=True)
    full = default_shape_grid(full_cfg)
    assert full == specs + [("unified_full", 256), ("unified_mm", 256)]
    assert len(full) <= 8


def test_config_validation_one_path():
    base = dict(model=ModelConfig.tiny_test(), num_blocks=64,
                max_model_len=256)
    for bad in (
        dict(unified_token_budget=8),
        dict(unified_prefill_quantum=0),
        dict(speculative_k=16, unified_token_budget=16),  # span > half
    ):
        with pytest.raises(ValueError):
            cfg = dict(base)
            cfg.update(bad)
            EngineConfig(**cfg).validate()
    EngineConfig(**base).validate()  # the plain combo is fine
    # Speculation and multimodal are FIRST-CLASS on the unified path now.
    EngineConfig(**base, speculative_k=4).validate()
    EngineConfig(**base, multimodal=True).validate()


def test_config_budget_clamps_to_reachable_rung():
    """A budget past the largest fillable batch CLAMPS down to the
    biggest reachable rung (with the quantum snapped inside it) instead
    of rejecting — the default budget must stay valid on tiny engines."""
    cfg = EngineConfig(
        model=ModelConfig.tiny_test(), num_blocks=64, max_num_seqs=2,
        max_model_len=32, prefill_batch=2, unified_token_budget=256,
        unified_prefill_quantum=200,
    )
    cfg.validate()
    assert cfg.unified_token_budget == 64  # (2+2)*31 = 124 → rung 64
    assert cfg.unified_prefill_quantum == 64


# ---------------------------------------------------------------------------
# batch composition (pure policy, no engine)
# ---------------------------------------------------------------------------


def test_compose_decode_first_fill():
    """Decode lanes admit first; remaining budget packs prefill quanta."""
    dec = [f"d{i}" for i in range(6)]
    pre = [("p0", 100), ("p1", 30)]
    decode_take, prefill_take = compose_unified(dec, pre, 64, 16)
    assert decode_take == dec  # all decode lanes fit
    assert prefill_take == [("p0", 16), ("p1", 16)]  # one quantum each


def test_compose_prefill_quantum_cap_lifts_when_alone():
    """A prefill-only batch may spend the whole budget on one prompt
    (pure TTFT); under co-location each prompt is quantum-capped."""
    _, alone = compose_unified([], [("p0", 500)], 64, 16)
    assert alone == [("p0", 64)]
    _, shared = compose_unified(["d0"], [("p0", 500)], 64, 16)
    assert shared == [("p0", 16)]


@pytest.mark.parametrize("rides", [0, 16, 48])
def test_compose_takes_block_spans_of_b_and_2b_rows_whole(rides):
    """A block model's decode items are ``(seq, width)`` pairs of B rows,
    or 2B where a commit rides the next block's first pass (engine
    ``_commit_rides``): inside what the budget has left once every lane has
    its B rows and a waiting prompt its quantum (the engine grants rides
    there and nowhere else) every lane is taken at the width it asked for,
    and the prompts get the quantum reserved for them and what is left."""
    B, budget, quantum = 4, 512, 64
    dec = [(f"d{i}", 2 * B if i < rides else B) for i in range(64)]
    pre = [(f"p{i}", 300) for i in range(4)]
    assert budget - B * 64 - quantum >= B * rides      # the engine's room
    decode_take, prefill_take = compose_unified(dec, pre, budget, quantum)
    assert decode_take == dec
    used = sum(w for _, w in decode_take)
    assert sum(n for _, n in prefill_take) == min(4 * quantum, budget - used)
    assert prefill_take[0] == ("p0", quantum)


def test_compose_defers_a_2b_span_that_does_not_fit_but_not_a_b_span_behind():
    """Outside that room the rotated fill would defer a lane: a span of 2B
    rows that does not fit is passed over for a B-row span that does, which
    is why the engine never asks for a ride it has no room for."""
    B = 4
    dec = [("a", 2 * B), ("b", 2 * B), ("c", B)]
    take, _ = compose_unified(dec, [], budget=12, quantum=4)
    assert take == [("a", 2 * B), ("c", B)]
    # the same lanes at B rows each all run
    take, _ = compose_unified([(s, B) for s, _ in dec], [], 12, 4)
    assert [s for s, _ in take] == ["a", "b", "c"]


def test_compose_starvation_bounds():
    """A full decode population cannot starve prefill below one quantum,
    and prefill can never displace a decode lane that fits."""
    dec = [f"d{i}" for i in range(64)]
    decode_take, prefill_take = compose_unified(dec, [("p0", 100)], 64, 16)
    assert len(decode_take) == 48  # 64 - 16 reserved
    assert prefill_take == [("p0", 16)]  # prefill always progresses
    # no prefill work -> decode takes the whole budget
    decode_take, prefill_take = compose_unified(dec, [], 64, 16)
    assert len(decode_take) == 64 and prefill_take == []
    # reserve never exceeds the actual prefill demand
    decode_take, prefill_take = compose_unified(dec, [("p0", 3)], 64, 16)
    assert len(decode_take) == 61 and prefill_take == [("p0", 3)]
    # quantum == budget must NOT zero decode out: the reserve is capped
    # so decode keeps at least half the budget (or all it needs).
    decode_take, prefill_take = compose_unified(dec, [("p0", 500)], 64, 64)
    assert len(decode_take) == 32
    assert prefill_take == [("p0", 32)]
    decode_take, prefill_take = compose_unified(
        dec[:2], [("p0", 500)], 64, 64
    )
    assert len(decode_take) == 2  # small decode population fully fits
    assert prefill_take == [("p0", 62)]


def test_compose_budget_exhaustion_stops_packing():
    dec = ["d0", "d1"]
    pre = [("p0", 40), ("p1", 40), ("p2", 40)]
    decode_take, prefill_take = compose_unified(dec, pre, 32, 16)
    assert decode_take == dec
    # 30 tokens left: one full quantum + a truncated one; p2 waits.
    assert prefill_take == [("p0", 16), ("p1", 14)]


# ---------------------------------------------------------------------------
# engine end-to-end (mocker: warmup contract; real engine: token parity)
# ---------------------------------------------------------------------------


def _engine_cfg(**kw) -> EngineConfig:
    return EngineConfig(
        model=ModelConfig.tiny_test(), num_blocks=64, max_num_seqs=4,
        max_model_len=96, dtype="float32",
        unified_token_budget=64,
        unified_prefill_quantum=32, sampling_extras=False, **kw,
    )


async def test_mocker_unified_warmup_and_zero_midtraffic_compiles():
    """Unified mocker engine: warmup compiles exactly the budget ladder
    (≤ 8 programs), mixed traffic runs with ZERO mid-traffic compiles,
    and the unified metrics surface on the engine snapshot."""
    from dynamo_tpu.mocker import MockerConfig, MockerEngine

    cfg = EngineConfig(
        model=ModelConfig.tiny_test(), num_blocks=64, max_num_seqs=4,
        max_model_len=128,
        unified_token_budget=64, unified_prefill_quantum=16,
    )
    eng = MockerEngine(cfg, MockerConfig())
    metrics: list[dict] = []
    eng._on_metrics = metrics.append
    await eng.start()
    warmed = await eng.warmup()
    assert warmed <= 8
    # The ladder plus the single extras top-rung program
    # (sampling_extras defaults True).
    assert warmed == len(budget_ladder(cfg.unified_token_budget)) + 1
    rng = np.random.default_rng(0)

    async def run_one():
        req = PreprocessedRequest(
            token_ids=rng.integers(0, 1000, 40).tolist(),
            sampling=SamplingOptions(temperature=0.0),
            stop=StopConditions(max_tokens=8, ignore_eos=True),
        )
        n = 0
        async for out in eng.generate(Context(req.to_wire())):
            n += len(out["token_ids"])
        return n

    counts = await asyncio.gather(*[run_one() for _ in range(6)])
    assert counts == [8] * 6
    cs = eng.runner.compile_stats
    assert cs.mid_traffic_compiles == 0, cs.mid_traffic_keys
    assert cs.snapshot()["warmup_programs_total"] == warmed
    # Observability satellite: the split + fill ratio reach the metrics
    # callback and the readiness snapshot.
    assert eng._unified_prefill_tokens == 6 * 40
    assert eng._unified_decode_tokens > 0
    m = metrics[-1]
    assert "unified_step_tokens_decode_total" in m
    assert "batch_fill_ratio" in m
    r = eng.readiness()
    assert r["unified_step_tokens_prefill_total"] == 6 * 40
    await eng.stop()


async def test_unified_remote_prefill_uses_budget_programs_only():
    """A unified disagg PREFILL worker must serve remote-prefill batches
    through unified_step spans — never the phase-path prefill programs
    its warmup no longer compiles (that would be a mid-traffic compile
    per bucket, the r05 stall class)."""
    from dynamo_tpu.mocker import MockerConfig, MockerEngine

    cfg = EngineConfig(
        model=ModelConfig.tiny_test(), num_blocks=64, max_num_seqs=4,
        max_model_len=128,
        unified_token_budget=64, unified_prefill_quantum=16,
    )
    eng = MockerEngine(cfg, MockerConfig())
    await eng.start()
    await eng.warmup()
    rng = np.random.default_rng(2)
    items = [
        (
            PreprocessedRequest(
                token_ids=rng.integers(0, 1000, n).tolist(),
                sampling=SamplingOptions(temperature=0.0),
                stop=StopConditions(max_tokens=4, ignore_eos=True),
            ),
            f"rp-{i}",
            False,
        )
        for i, n in enumerate((90, 40))
    ]
    results = await asyncio.gather(*eng.prefill_only_batch(items))
    for (pre, _rid, _dev), res in zip(items, results):
        assert res is not None
        token, blocks = res
        assert isinstance(token, int)
        assert len(blocks) == -(-len(pre.token_ids) // cfg.block_size)
    cs = eng.runner.compile_stats
    assert cs.mid_traffic_compiles == 0, cs.mid_traffic_keys
    assert all(k.startswith("unified") for k in cs.seen), cs.seen
    await eng.stop()


async def test_unified_rejects_extras_only_when_disabled():
    """sampling_extras=False still 400-rejects penalties/logprobs; the
    default unified engine serves them (the extras port)."""
    from dynamo_tpu.mocker import MockerConfig, MockerEngine

    cfg = EngineConfig(
        model=ModelConfig.tiny_test(), num_blocks=64, max_num_seqs=4,
        max_model_len=128, sampling_extras=False,
    )
    eng = MockerEngine(cfg, MockerConfig())
    await eng.start()
    req = PreprocessedRequest(
        token_ids=[1, 2, 3],
        sampling=SamplingOptions(temperature=0.0, frequency_penalty=0.5),
        stop=StopConditions(max_tokens=4, ignore_eos=True),
    )
    with pytest.raises(RequestError):
        async for _ in eng.generate(Context(req.to_wire())):
            pass
    await eng.stop()


async def test_engine_spec_greedy_streams_byte_identical():
    """The tentpole regression gate (pre/post-port byte identity, REAL
    engine): greedy token streams through the unified step are
    byte-identical with speculative decoding ON and OFF — verification
    only ever keeps drafts the plain rollout would have produced, and
    gated-off spec traffic reduces to the exact plain program."""
    from dynamo_tpu.engine.engine import TpuEngine

    async def run(spec_k: int) -> list[list[int]]:
        eng = TpuEngine(_engine_cfg(speculative_k=spec_k))
        await eng.start()
        rng = np.random.default_rng(0)
        prompts = [
            rng.integers(0, 500, n).tolist() for n in (7, 19, 40, 12, 33)
        ]
        out = []
        for p in prompts:
            req = PreprocessedRequest(
                token_ids=p,
                sampling=SamplingOptions(temperature=0.0),
                stop=StopConditions(max_tokens=8, ignore_eos=True),
            )
            toks = []
            async for o in eng.generate(Context(req.to_wire())):
                toks.extend(o["token_ids"])
            out.append(toks)
        assert "unified:t16" in eng.runner.compile_stats.seen
        await eng.stop()
        return out

    plain = await run(0)
    spec = await run(3)
    assert spec == plain
    assert all(len(t) == 8 for t in plain)


async def test_engine_unified_mixed_concurrency_and_prefix_cache():
    """Concurrent mixed-length prompts (prefill quanta + decode lanes
    co-resident in single dispatches) all complete, and a repeated prompt
    takes the prefix-cache hit path through the unified step."""
    from dynamo_tpu.engine.engine import TpuEngine

    eng = TpuEngine(_engine_cfg())
    await eng.start()
    rng = np.random.default_rng(1)
    base = rng.integers(0, 500, 48).tolist()

    async def run_one(p, n=6):
        req = PreprocessedRequest(
            token_ids=p,
            sampling=SamplingOptions(temperature=0.0),
            stop=StopConditions(max_tokens=n, ignore_eos=True),
        )
        toks = []
        async for o in eng.generate(Context(req.to_wire())):
            toks.extend(o["token_ids"])
        return toks

    prompts = [base, rng.integers(0, 500, 9).tolist(),
               rng.integers(0, 500, 21).tolist()]
    first = await asyncio.gather(*[run_one(p) for p in prompts])
    assert all(len(t) == 6 for t in first)
    # Same prompt again: blocks registered by the first pass give a
    # prefix hit; the continuation must still decode identical tokens.
    again = await run_one(base)
    assert again == first[0]
    assert eng.prefix_hit_rate > 0
    # Every dispatch handed the device ONE host array (the packed operand
    # buffer); the flight record and the readiness snapshot both say so.
    steps = [r for r in eng.flight.snapshot() if r["kind"] == "unified"]
    assert steps and all(r["operand_transfers"] == 1 for r in steps)
    total = eng.readiness()["unified_operand_transfers_total"]
    assert total == eng.runner.operand_transfers_total >= len(steps)
    await eng.stop()


# ---------------------------------------------------------------------------
# one step program: what a runner builds, and what is asked of it
# ---------------------------------------------------------------------------


def _tiny_runner():
    from dynamo_tpu.engine.runner import ModelRunner

    return ModelRunner(
        EngineConfig(
            model=ModelConfig.tiny_test(), num_blocks=16, max_num_seqs=2,
            max_model_len=32, dtype="float32",
        )
    )


def test_runner_builds_only_the_unified_programs():
    """A built ModelRunner holds exactly the unified family's jitted
    callables (plus the resident zero feed one small jit produced) — no
    phase-split prefill/decode program comes back unnoticed — and no
    public step entry beside unified_step."""
    import jax

    runner = _tiny_runner()
    jit_type = type(jax.jit(lambda: 0))
    jitted = {k for k, v in vars(runner).items() if isinstance(v, jit_type)}
    assert jitted == {"_unified", "_unified_full", "_unified_mm"}
    assert isinstance(runner._zero_prev, jax.Array)
    assert runner._zero_prev.shape == (runner.unified_slots,)
    for gone in ("prefill", "prefill_batch", "decode", "decode_multi",
                 "last_logprobs"):
        assert not hasattr(runner, gone), gone


def test_engine_and_stepcast_name_only_methods_the_runner_has():
    """Every ``self.runner.<name>`` the engine touches and every method
    stepcast replays exists on a built ModelRunner."""
    import ast
    import inspect

    from dynamo_tpu.engine import engine as engine_mod
    from dynamo_tpu.mocker.engine import _SimRunner
    from dynamo_tpu.parallel.stepcast import REPLAYED

    named = {
        node.attr
        for node in ast.walk(ast.parse(inspect.getsource(engine_mod)))
        if isinstance(node, ast.Attribute)
        and isinstance(node.value, ast.Attribute)
        and node.value.attr == "runner"
        and isinstance(node.value.value, ast.Name)
        and node.value.value.id == "self"
    }
    assert "unified_step" in named and len(named) > 5
    runner = _tiny_runner()
    missing = sorted(n for n in named | set(REPLAYED) if not hasattr(runner, n))
    assert not missing, missing
    # The mocker's double has the one step entry and none of the gone.
    assert hasattr(_SimRunner, "unified_step")
    for gone in ("prefill", "prefill_batch", "decode", "decode_multi"):
        assert not hasattr(_SimRunner, gone), gone


# ---------------------------------------------------------------------------
# the packed operand buffer (docs/architecture/unified_step.md)
# ---------------------------------------------------------------------------

_BS = 16  # EngineConfig's default block size
GREEDY = (0.0, 0, 1.0)


def _plain_operands(runner, lanes, feed, T, key, draft_lens, extras):
    """One dispatch's operands the plain way: an array each, a Python
    loop a token through ``slot_of`` (the arithmetic the packed builder
    replaced). ``{segment name: array}``."""
    cfg, S = runner.cfg, runner.unified_slots
    o = {
        "token_ids": np.zeros(T, np.int32),
        "token_pos": np.full(T, -1, np.int32),
        "slot_mapping": np.zeros(T, np.int32),
        "token_seq": np.zeros(T, np.int32),
        "block_tables": np.zeros((S, cfg.max_blocks_per_seq), np.int32),
        "temp": np.zeros(S, np.float32), "top_k": np.zeros(S, np.int32),
        "top_p": np.ones(S, np.float32), "seed": np.full(S, -1, np.int32),
        "use_prev": np.zeros(S, bool), "prev_row": np.zeros(S, np.int32),
        "key": np.asarray(key, np.uint32),
    }
    for name in ("q_start", "q_len", "kv_len", "row_start"):
        o[name] = np.zeros(S, np.int32)
    cursor = 0
    for s, (new_tokens, block_ids, prefix, sampling) in enumerate(lanes):
        n = len(new_tokens)
        o["row_start"][s], o["q_start"][s] = cursor, prefix
        o["q_len"][s], o["kv_len"][s] = n, prefix + n
        o["block_tables"][s, : len(block_ids)] = block_ids
        o["token_ids"][cursor : cursor + n] = new_tokens
        o["token_seq"][cursor : cursor + n] = s
        for j in range(n):
            o["token_pos"][cursor + j] = prefix + j
            o["slot_mapping"][cursor + j] = runner.slot_of(block_ids, prefix + j)
        sampling = tuple(sampling) + (-1,) * (4 - len(sampling))
        o["temp"][s], o["top_k"][s], o["top_p"][s], o["seed"][s] = sampling
        cursor += n
    if feed is not None:
        o["prev_row"][:], o["use_prev"][:] = feed[1], feed[2]
    if cfg.speculative_k and extras is None:
        o["drafts"] = np.zeros((S, cfg.speculative_k), np.int32)
        o["draft_len"] = np.zeros(S, np.int32)
        for s, dl in enumerate(draft_lens or ()):
            if dl:
                o["draft_len"][s] = dl
                o["drafts"][s, :dl] = lanes[s][0][-dl:]
    if extras is not None:
        n_l = len(lanes)
        o["span_slot"] = np.full(S, -1, np.int32)
        o["span_slot"][:n_l] = extras["slots"]
        for name, dtype in (
            ("counts_add", bool), ("reset", bool),
            ("freq", np.float32), ("pres", np.float32),
        ):
            o[name] = np.zeros(S, dtype)
            o[name][:n_l] = extras[name]
    return o


def _bits(a) -> np.ndarray:
    a = np.asarray(a)
    return a.view(np.uint32) if a.dtype.itemsize == 4 else a


def _assert_unpacks_to(runner, buf, want, variant) -> None:
    """``buf`` through the PROGRAM's unpack (static slices, bitcasts,
    ``!= 0``) gives ``want``: names, shapes, dtypes and every bit."""
    import jax

    from dynamo_tpu.engine.runner import operand_layout_of

    cfg = runner.cfg
    lay = operand_layout_of(
        buf.shape[0], runner.unified_slots, cfg.max_blocks_per_seq,
        cfg.speculative_k, variant,
    )
    got = jax.jit(lay.unpack)(buf)
    assert set(got) == set(want)
    for name, w in want.items():
        g = np.asarray(got[name])
        assert g.dtype == w.dtype and g.shape == w.shape, name
        assert np.array_equal(_bits(g), _bits(w)), name


def _greedy_after(runner, context: list[int]) -> int:
    from stepdrive import reference_greedy

    return reference_greedy(
        runner.cfg.model, runner.params, context, 1,
        length=runner.cfg.max_model_len,
    )[0]


def _mix_decode_only(runner):
    """Three decode lanes fed on the device from the prefill dispatch."""
    prompts = [[5, 9, 2, 7, 11], [3, 1, 4, 1, 5, 9, 2], [8, 6, 7]]
    first = runner.unified_step(
        [(p, [i + 1], 0, GREEDY) for i, p in enumerate(prompts)]
    )
    S = runner.unified_slots
    row, use = np.zeros(S, np.int32), np.zeros(S, bool)
    row[:3], use[:3] = [0, 1, 2], True
    fed = [_greedy_after(runner, p) for p in prompts]
    lanes = [([0], [i + 1], len(p), GREEDY) for i, p in enumerate(prompts)]
    return dict(
        lanes=lanes, feed=(first.last, row, use),
        contexts=[p + [t] for p, t in zip(prompts, fed)],
    )


def _mix_decode_and_chunk_over_a_block_edge(runner):
    """A decode lane beside a prefill chunk whose span crosses from its
    sequence's first block into its second."""
    a, b = [5, 9, 2, 7], list(range(40, 40 + _BS + 4))
    head = _BS - 3
    first = runner.unified_step(
        [(a, [1], 0, GREEDY), (b[:head], [2, 3], 0, GREEDY)]
    )
    tok = int(np.asarray(first.last)[0])
    assert tok == _greedy_after(runner, a)
    lanes = [([tok], [1], len(a), GREEDY), (b[head:], [2, 3], head, GREEDY)]
    return dict(lanes=lanes, contexts=[a + [tok], b])


def _mix_draft_verify(runner):
    """A span whose drafts are the greedy continuation (all accepted) and
    one whose drafts are wrong (none accepted), on a speculative engine."""
    a, b = [5, 9, 2, 7, 11], [3, 1, 4, 1, 5]
    first = np.asarray(
        runner.unified_step([(a, [1], 0, GREEDY), (b, [2], 0, GREEDY)]).last
    )
    ctx_a = a + [int(first[0])]
    good = [_greedy_after(runner, ctx_a)]
    good.append(_greedy_after(runner, ctx_a + good))
    wrong = [(_greedy_after(runner, b + [int(first[1])]) + 1) % 256, 7]
    lanes = [
        ([int(first[0])] + good, [1], len(a), GREEDY),
        ([int(first[1])] + wrong, [2], len(b), GREEDY),
    ]
    return dict(
        lanes=lanes, draft_lens=[2, 2],
        # Emitted: both drafts and a bonus; then the bonus alone.
        contexts=[ctx_a + good, b + [int(first[1])]], accepted=[2, 0],
    )


def _mix_extras(runner):
    """The penalties/logprob program: a decode lane that counts its fed
    token and a prefill span, at penalties that leave greedy unmoved."""
    a, b = [5, 9, 2, 7], [3, 1, 4, 1, 5, 9]
    tok = int(np.asarray(runner.unified_step([(a, [1], 0, GREEDY)]).last)[0])
    lanes = [([tok], [1], len(a), GREEDY), (b, [2], 0, GREEDY)]
    extras = {
        "slots": [0, 1], "counts_add": [True, False], "reset": [True, True],
        "freq": [0.0, 0.0], "pres": [0.0, 0.0],
    }
    return dict(lanes=lanes, extras=extras, contexts=[a + [tok], b])


def _mix_empty_tail(runner):
    """One span and nothing else: every row after it is padding."""
    a = [5, 9, 2, 7, 11, 3]
    return dict(lanes=[(a, [4], 0, GREEDY)], contexts=[a])


@pytest.mark.parametrize(
    "mix, cfg_kw",
    [
        (_mix_decode_only, {}),
        (_mix_decode_and_chunk_over_a_block_edge, {}),
        (_mix_draft_verify, {"speculative_k": 3}),
        (_mix_extras, {"sampling_extras": True}),
        (_mix_empty_tail, {}),
    ],
    ids=["decode-only", "decode-and-chunk-over-a-block-edge", "draft-verify",
         "extras", "empty-tail"],
)
def test_packed_operands_equal_the_plain_builder(mix, cfg_kw):
    """One transfer a dispatch carries what sixteen carried: the buffer
    a dispatch hands to the device, unpacked as the program unpacks it,
    equals a plain array-each builder bit for bit (float32 and uint32
    rows through the bitcast); at most two host arrays reach the device;
    and the dispatch's greedy tokens are the no-cache reference's."""
    from dynamo_tpu.engine.compile_cache import token_budget
    from dynamo_tpu.engine.runner import ModelRunner

    ecfg = EngineConfig(
        model=ModelConfig.tiny_test(), num_blocks=16, max_num_seqs=3,
        max_model_len=64, dtype="float32", unified_token_budget=32,
        seed=0xDEADBEEF, **cfg_kw,
    )
    runner = ModelRunner(ecfg)
    case = mix(runner)
    lanes, feed = case["lanes"], case.get("feed")
    draft_lens, extras = case.get("draft_lens"), case.get("extras")
    variant = (
        "extras" if extras is not None
        else "spec" if ecfg.speculative_k else "plain"
    )
    top = ecfg.unified_token_budget
    T = token_budget(
        top if extras is not None else sum(len(t) for t, *_ in lanes), top
    )

    placed, real_put = [], runner._put
    runner._put = lambda x: placed.append(np.array(x)) or real_put(x)
    out = runner.unified_step(
        lanes, feed=feed, draft_lens=draft_lens, extras=extras
    )
    runner._put = real_put
    assert len(placed) == runner.operand_transfers == 1  # at most 2: below
    (buf,) = placed
    assert buf.dtype == np.int32 and buf.ndim == 1
    key = [ecfg.seed & 0xFFFFFFFF, runner._step]
    assert key[0] >> 31  # a key word no int32 holds: it rides as bits
    want = _plain_operands(runner, lanes, feed, T, key, draft_lens, extras)
    _assert_unpacks_to(runner, buf, want, variant)

    # The tokens, against the no-cache reference.
    last = np.asarray(out.last)
    if draft_lens is None:
        for s, ctx in enumerate(case["contexts"]):
            assert int(last[s]) == _greedy_after(runner, ctx), s
    else:
        toks, counts = np.asarray(out.toks), np.asarray(out.counts)
        for s, (ctx, acc) in enumerate(zip(case["contexts"], case["accepted"])):
            assert counts[s] == acc + 1
            assert toks[s, :acc].tolist() == ctx[len(ctx) - acc :]
            assert int(toks[s, acc]) == int(last[s]) == _greedy_after(runner, ctx)
    assert not last[len(lanes) :].any()  # idle rows hand out token 0

    # Sampling rows no int32 holds as a value, and a host feed that is
    # read (one transfer more): still bit for bit, nothing dispatched.
    S = runner.unified_slots
    odd = [
        (t, b, p, (0.7, 5, 0.9, 1234567) if s % 2 else (1e-30, 0, 0.1))
        for s, (t, b, p, _) in enumerate(lanes)
    ]
    row, use = np.arange(S, dtype=np.int32)[::-1].copy(), np.zeros(S, bool)
    use[: len(lanes)] = True
    host_feed = (np.arange(S, dtype=np.int32), row, use)
    base, meta, ops = runner._unified_operands(odd, host_feed, T, variant)
    assert ops.feed_transfers == 1 and isinstance(ops.buf, np.ndarray)
    want = _plain_operands(
        runner, odd, host_feed, T, [0, 0],
        [0] * len(lanes) if ecfg.speculative_k else None,
        None if extras is None else dict(
            extras, slots=[-1] * len(lanes), counts_add=[False] * len(lanes),
            reset=[False] * len(lanes),
        ),
    )
    _assert_unpacks_to(runner, ops.buf, want, variant)
    assert np.array_equal(np.asarray(ops.prev_toks), host_feed[0])


def test_unified_operands_keep_the_contract_the_benchmark_reads():
    """``chipbench/steps/span.py`` calls ``base, meta, *_ =
    runner._unified_operands(lanes, None, T)`` and feeds ``fn(*base,
    *meta)`` to ``llama.unified``: a 3-tuple of (params, caches,
    scales), then the nine metadata arrays in the model function's
    order."""
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.engine.runner import META_SEGMENTS
    from dynamo_tpu.models import llama

    runner = _tiny_runner()
    cfg = runner.cfg
    a = [5, 9, 2, 7, 11, 3]
    lanes = [(a, [1], 0, GREEDY), (a[:3], [2], 0, GREEDY)]
    served = np.asarray(runner.unified_step(lanes).last)
    base, meta, *_ = runner._unified_operands(lanes, None, 16)
    assert isinstance(base, tuple) and len(base) == 3
    assert base[0] is runner.params and base[1] is runner.kv_caches
    assert len(meta) == len(META_SEGMENTS) == 9
    S = runner.unified_slots
    shapes = [(16,)] * 4 + [(S, cfg.max_blocks_per_seq)] + [(S,)] * 4
    assert [m.shape for m in meta] == shapes
    assert all(m.dtype == np.int32 for m in meta)

    def logits_fn(params, kv, sc, token_ids, *rest):
        out = llama.unified(
            cfg.model, params, kv, token_ids, *rest, cfg.block_size,
            attn=runner.attn, kv_scales=sc,
        )
        return out[0].astype(jnp.float32), out[1]

    logits, runner.kv_caches = jax.jit(logits_fn, donate_argnums=(1,))(
        *base, *meta
    )
    assert np.argmax(np.asarray(logits), -1)[:2].tolist() == served[:2].tolist()
    assert served[0] == _greedy_after(runner, a)
