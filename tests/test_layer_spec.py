"""One traced body a layer spec (docs/architecture/unified_step.md): every
layer of ``llama.unified`` goes through ONE ``jax.jit`` of the layer's body,
static on the layer's ``LayerSpec``, so a program traces and lowers one body
a DISTINCT layer. Held here: how often the body is traced, what the lowered
module holds, that the spec says everything ``ModelConfig`` answers by layer
index, and that no index reaches the body."""

import inspect
import re

import jax
import numpy as np
import pytest

from dynamo_tpu.engine import compile_cache
from dynamo_tpu.engine.compile_cache import budget_ladder, jax_phase_seconds
from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.engine.runner import ModelRunner, _unified_warm_lanes
from dynamo_tpu.models import llama
from dynamo_tpu.models.config import (
    PRESETS,
    RECURRENT_KINDS,
    LayerSpec,
    ModelConfig,
)
from dynamo_tpu.parallel.mesh import build_mesh

#: Distinct layer bodies of each family's tiny preset. tiny-mla-test: a dense
#: first layer and expert layers; tiny-gemma-test: window layers on the local
#: theta and full layers on the global one; tiny-ling-test: KDA + dense MLP,
#: KDA + experts, KDA + experts under a routed clamp, under a shared clamp,
#: latent attention + experts; tiny-command-a-test: window layers with
#: rotary pairs, full layers without; tiny-nemotron-h-test: a state-space
#: mixer alone, attention alone, experts alone; tiny-lfm2-test: a gated
#: convolution + dense MLP, a convolution + experts, attention + experts.
TINY_BODIES = {
    "tiny-test": 1, "tiny-moe-test": 1, "tiny-mla-test": 2,
    "tiny-gemma-test": 2, "tiny-sdar-test": 1, "tiny-ling-test": 5,
    "tiny-command-a-test": 2, "tiny-brumby-test": 1,
    "tiny-nemotron-h-test": 3, "tiny-lfm2-test": 3,
}
#: And of the configurations the benchmark's cells serve (ISSUE 50's table).
CELL_BODIES = {
    "mistral-7b": 1, "mixtral-8x7b": 1, "sdar-30b-a3b": 1, "brumby-14b": 1,
    "command-a-plus-ep8-l4": 2, "ling-3.0-flash-ep4-l8": 3,
    "nemotron-3-super-ep4-l11": 3, "lfm2-24b-a2b-l10": 3,
}


def specs_of(m: ModelConfig) -> list[LayerSpec]:
    return [m.layer_spec(li) for li in range(m.num_layers)]


def make_runner(model: ModelConfig, tag: str, mesh=None, **kw) -> ModelRunner:
    """A runner of ``model`` under a name of this test's own: the name is
    part of the body's static operand, so whatever another test of this
    process left in jit's cache, this runner's bodies are traced here."""
    base = dict(
        model=model.scaled(name=f"{model.name}.{tag}"), dtype="float32",
        block_size=8, num_blocks=96, max_num_seqs=4, max_model_len=192,
        seed=7, unified_token_budget=32, unified_prefill_quantum=32,
    )
    base.update(kw)
    return ModelRunner(EngineConfig(**base), mesh=mesh, rng_seed=7)


def lower_rung(runner: ModelRunner, t: int):
    """The runner's ladder program lowered at budget rung ``t`` from the
    warmup's lanes (what ``lower_unified_top`` does at the top rung)."""
    cfg = runner.cfg
    lanes = _unified_warm_lanes(
        t, runner.unified_slots, cfg.max_model_len, runner._trash_table(),
        (0.0, 0, 1.0),
    )
    base, _meta, ops = runner._unified_operands(lanes, None, t)
    return runner._unified.lower(
        *runner._program_args(base), runner._put(ops.buf), ops.prev_toks
    )


class Counted:
    """The body's traces and calls across a block."""

    def __enter__(self):
        self.before = dict(llama.LAYER_BODY)
        return self

    def __exit__(self, *exc):
        self.traces = llama.LAYER_BODY["traces"] - self.before["traces"]
        self.calls = llama.LAYER_BODY["calls"] - self.before["calls"]


def assert_one_body_a_spec(runner: ModelRunner) -> None:
    m = runner.cfg.model
    bodies = len(set(specs_of(m)))
    top, lower = budget_ladder(runner.cfg.unified_token_budget)[-2:][::-1]
    with Counted() as first:
        text = lower_rung(runner, top).as_text()
    assert (first.traces, first.calls) == (bodies, m.num_layers)
    # (b) one private function a spec, called once a layer.
    defined = re.findall(r"func\.func private @(_layer\w*)\(", text)
    called = re.findall(r"call @(_layer\w*)\(", text)
    assert len(defined) == bodies and len(called) == m.num_layers
    assert set(called) == set(defined)
    with Counted() as again:      # the same rung: the program's own cache
        lower_rung(runner, top)
    assert (again.traces, again.calls) == (0, 0)
    with Counted() as rung:       # another rung is another shape
        lower_rung(runner, lower)
    assert (rung.traces, rung.calls) == (bodies, m.num_layers)


@pytest.mark.parametrize("preset", sorted(TINY_BODIES))
def test_a_program_traces_one_body_a_distinct_spec(preset):
    m = PRESETS[preset]()
    assert len(set(specs_of(m))) == TINY_BODIES[preset]
    assert_one_body_a_spec(make_runner(m, "bodies"))


@pytest.mark.parametrize("preset", sorted(CELL_BODIES))
def test_the_cells_configurations_have_the_bodies_the_issue_counted(preset):
    m = PRESETS[preset]()
    assert len(set(specs_of(m))) == CELL_BODIES[preset]


# -- (c) the spec says everything ModelConfig answers by layer index --------

#: Every method of ``ModelConfig`` that takes a layer index, and what the
#: layer's spec says in its place.
SPEC_ANSWERS = {
    "layer_kind": lambda m, li, s: m.layer_kind(li) == s.kind,
    "layer_cache_arrays": lambda m, li, s: (
        m.layer_cache_arrays(li) == s.cache_arrays),
    "layer_window": lambda m, li, s: m.layer_window(li) == s.window,
    "layer_cache_group": lambda m, li, s: (
        m.layer_cache_group(li) == s.cache_group),
    "layer_rope": lambda m, li, s: m.layer_rope(li) == s.rope,
    "moe_layer": lambda m, li, s: m.moe_layer(li) == (s.ffn == "moe"),
    # The feed-forward part, "none" for a layer that is a mixer alone (and
    # kind "none" for one that is a feed-forward part alone).
    "layer_ffn": lambda m, li, s: (
        m.layer_ffn(li) == s.ffn and (s.kind, s.ffn) != ("none", "none")),
    "swiglu_limit": lambda m, li, s: (
        m.swiglu_limit(li) == s.swiglu_limit
        and m.swiglu_limit(li, shared=True) == s.shared_swiglu_limit),
    # Shapes, by the layer's kind alone: the body meets them as its state
    # operand's shapes, which jit's cache sees beside the spec.
    "recurrent_state_arrays": lambda m, li, s: (
        bool(m.recurrent_state_arrays(li, 3, "float32"))
        == (s.kind in RECURRENT_KINDS)),
    "layer_spec": lambda m, li, s: m.layer_spec(li) == s,
}


def index_methods() -> list[str]:
    return sorted(
        name for name, fn in inspect.getmembers(ModelConfig, inspect.isfunction)
        if "layer_idx" in inspect.signature(fn).parameters
    )


@pytest.mark.parametrize("method", index_methods())
def test_every_index_taking_method_is_in_the_spec(method):
    assert method in SPEC_ANSWERS, (
        f"ModelConfig.{method} takes a layer index and LayerSpec does not "
        "say what it answers: the layer body would not see it"
    )


def test_the_table_names_no_method_that_is_gone():
    assert sorted(SPEC_ANSWERS) == index_methods()
    assert {f.name for f in LayerSpec.__dataclass_fields__.values()} == {
        "kind", "cache_arrays", "window", "cache_group", "rope", "ffn",
        "swiglu_limit", "shared_swiglu_limit"}


@pytest.mark.parametrize("preset", sorted(PRESETS))
def test_the_spec_agrees_with_every_index_taking_method(preset):
    m = PRESETS[preset]()
    for li, spec in enumerate(specs_of(m)):
        hash(spec)
        for method, agrees in SPEC_ANSWERS.items():
            assert agrees(m, li, spec), (preset, li, method)


BODY_AND_HELPERS = (
    "_layer", "_layer_rows", "_rope_qk", "_residual_attn", "_residual_mlp", "_mlp",
    "_moe_mlp", "_qkv", "_qkv_mla", "_mla_out", "_kda_mixer",
    "_retention_inputs", "_retention_mixer", "_ssd_mixer", "_relu2",
    "_conv_mixer",
)


@pytest.mark.parametrize("helper", BODY_AND_HELPERS)
def test_no_layer_index_reaches_the_body_or_a_helper_under_it(helper):
    params = inspect.signature(getattr(llama, helper)).parameters
    assert not {"li", "layer_idx", "layer_index"} & set(params), helper
    source = inspect.getsource(getattr(llama, helper))
    assert not re.search(r"\bli\b|layer_idx", source), helper


def test_the_loop_is_one_jit_object_and_no_switch_selects_another():
    source = inspect.getsource(llama.unified)
    assert source.count("_layer_body(") == 1
    assert "environ" not in source and "getattr(cfg" not in source
    assert llama._layer_body.__wrapped__ is llama._layer
    # hidden_states, the oracle, keeps its own plain loop.
    assert "_layer_body" not in inspect.getsource(llama.hidden_states)


# -- what the trace reads of the process is part of jit's key ---------------

def test_the_pallas_switch_is_part_of_the_bodys_key(monkeypatch):
    runner = make_runner(PRESETS["tiny-moe-test"](), "env")
    monkeypatch.setenv("DYNAMO_TPU_PALLAS", "0")
    with Counted() as off:
        lower_rung(runner, 32)
    # (The program's own cache keys on shapes alone, as it always has: a
    # process does not change its mind. Emptied, it asks the body again.)
    runner._unified.clear_cache()
    with Counted() as same:
        lower_rung(runner, 32)
    monkeypatch.setenv("DYNAMO_TPU_PALLAS", "1")
    runner._unified.clear_cache()
    with Counted() as on:
        lower_rung(runner, 32)
    assert (off.traces, same.traces, on.traces) == (1, 0, 1)


# -- the same on a mesh, and the other program variants ---------------------

def test_a_tp4_step_traces_one_body_and_serves_the_oracles_token():
    from stepdrive import reference_greedy, step_token

    m = ModelConfig.tiny_test().scaled(num_heads=8, num_kv_heads=4)
    runner = make_runner(m, "tp4", mesh=build_mesh({"dp": 2, "tp": 4}))
    assert_one_body_a_spec(runner)
    prompt = [5, 9, 2, 7, 11, 3]
    params = jax.device_get(runner.params)
    want = reference_greedy(runner.cfg.model, params, prompt, 1, length=16)
    assert step_token(runner, prompt, [1]) == want[0]


@pytest.mark.parametrize("variant,preset,kw", [
    ("int8-kv", "tiny-test", {"kv_quant": "int8"}),
    ("int8-kv-gemma", "tiny-gemma-test", {"kv_quant": "int8"}),
    ("spec", "tiny-test", {"speculative_k": 2}),
    ("spec-gemma", "tiny-gemma-test", {"speculative_k": 2}),
    ("block", "tiny-sdar-test", {}),
    ("int8-weights", "tiny-mla-test", {"weight_quant": "int8"}),
])
def test_each_program_variant_traces_one_body_a_spec(variant, preset, kw):
    runner = make_runner(PRESETS[preset](), variant, **kw)
    assert_one_body_a_spec(runner)


def test_the_extras_program_shares_the_top_rungs_body():
    """``unified_full`` runs at the top rung on the plain program's operand
    shapes: the body it calls is the one the top rung traced."""
    runner = make_runner(PRESETS["tiny-test"](), "extras")
    with Counted() as warm:
        programs = runner.warmup()
    rungs = len(budget_ladder(runner.cfg.unified_token_budget))
    assert programs == rungs + 1
    assert (warm.traces, warm.calls) == (rungs, programs * 2)


# -- the counters and the start's split by phase ----------------------------

def test_compile_stats_hands_out_the_counters_and_the_warmups_phases():
    runner = make_runner(PRESETS["tiny-test"](), "stats")
    before = runner.compile_stats.snapshot()
    assert before["warmup_tracing_seconds_total"] == 0.0
    runner.warmup()
    snap = runner.compile_stats.snapshot()
    assert snap["layer_body_traces_total"] == llama.LAYER_BODY["traces"]
    assert snap["layer_body_calls_total"] == llama.LAYER_BODY["calls"]
    assert snap["layer_body_calls_total"] > before["layer_body_calls_total"]
    for phase in ("tracing", "lowering", "backend"):
        assert snap[f"warmup_{phase}_seconds_total"] > 0.0, phase


def test_a_phases_seconds_are_the_union_of_its_spans():
    import jax.monitoring

    (trace, lower, _backend) = compile_cache.JAX_PHASES
    into = {"tracing": 1.0}
    with jax_phase_seconds(into):
        # An inner jit's trace inside the outer's, one that overlaps its
        # end, one apart; another phase; an event nobody names.
        jax.monitoring.record_event_time_span(trace, 10.0, 14.0)
        jax.monitoring.record_event_time_span(trace, 11.0, 12.0)
        jax.monitoring.record_event_time_span(trace, 13.0, 15.0)
        jax.monitoring.record_event_time_span(trace, 20.0, 20.5)
        jax.monitoring.record_event_time_span(lower, 11.0, 13.0)
        jax.monitoring.record_event_time_span("/jax/other", 0.0, 99.0)
    assert into == {"tracing": 1.0 + 5.5, "lowering": 2.0, "backend": 0.0}
    jax.monitoring.record_event_time_span(trace, 30.0, 31.0)  # not heard
    assert into["tracing"] == 6.5


def test_the_experts_counts_leave_the_body_as_results():
    """``collect_experts_hit`` around a traced ``unified``: the grouped
    expert layers' counts come out of the jitted body as results, one a
    grouped expert layer, and are real numbers once the step has run."""
    from dynamo_tpu.models.moe import collect_experts_hit

    runner = make_runner(PRESETS["tiny-sdar-test"](), "hit")
    out = runner.unified_step([([5, 9, 2, 7], [1], 0, (0.0, 0, 1.0))])
    m = runner.cfg.model
    assert m.experts_here >= 16  # the grouped path
    hit = np.asarray(out.experts_hit)
    assert 0 < int(hit) <= m.num_layers * m.experts_here
    with collect_experts_hit() as outside:
        pass
    assert list(outside) == []
