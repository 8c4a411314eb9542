"""The plain reference against the program's own models at tiny widths,
and the arithmetic kept with the benchmark (kernel cost, peaks)."""

import dataclasses
import functools
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import check, manifest, registry
from chipbench.peaks import peaks_for
from chipbench.reference import mistral as ref
from chipbench.steps import span
from dynamo_tpu.models import llama
from dynamo_tpu.models.config import ModelConfig


def published(cfg: ModelConfig) -> dict:
    return {
        "vocab_size": cfg.vocab_size, "hidden_size": cfg.hidden_size,
        "intermediate_size": cfg.intermediate_size,
        "num_hidden_layers": cfg.num_layers,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim,
        "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.rms_eps,
        "sliding_window": cfg.sliding_window or None,
        "num_local_experts": cfg.num_experts or None,
        "num_experts_per_tok": cfg.num_experts_per_tok,
    }


DENSE = ModelConfig.tiny_test()
WINDOWED = dataclasses.replace(ModelConfig.tiny_test(), sliding_window=8)
MOE = ModelConfig.tiny_moe_test()
FAMILY = {"dense": DENSE, "sliding_window": WINDOWED, "moe": MOE}


@pytest.mark.parametrize("kind", ["dense", "moe"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_reference_draws_the_programs_weights(kind, dtype):
    """The reference repeats the served path's draw from the seed: the
    same splits in the same order give the same bits."""
    cfg = FAMILY[kind]
    seed = 1234567
    params = llama.init_params(
        jax.random.PRNGKey(seed), cfg, dtype=jnp.dtype(dtype)
    )
    pub = published(cfg)
    layer_keys, ek, hk = ref.model_keys(seed, cfg.num_layers)
    for li in range(cfg.num_layers):
        mine = ref.layer_weights(layer_keys[li], pub, jnp.dtype(dtype))
        theirs = params["layers"][li]
        assert sorted(mine) == sorted(theirs)
        for name in mine:
            assert mine[name].dtype == theirs[name].dtype
            np.testing.assert_array_equal(
                np.asarray(mine[name], np.float32),
                np.asarray(theirs[name], np.float32), err_msg=name,
            )
    np.testing.assert_array_equal(
        np.asarray(ref.embedding(ek, pub, jnp.dtype(dtype)), np.float32),
        np.asarray(params["embed"], np.float32),
    )
    np.testing.assert_array_equal(
        np.asarray(ref.lm_head(hk, pub, jnp.dtype(dtype)), np.float32),
        np.asarray(params["lm_head"], np.float32),
    )


@pytest.mark.parametrize("kind", sorted(FAMILY))
def test_reference_agrees_with_the_programs_forward(kind):
    """Float32 on both sides: the reference's full forward pass gives the
    logits of models/llama.py (and models/moe.py) to rounding."""
    cfg = FAMILY[kind]
    seed = 99
    params = llama.init_params(jax.random.PRNGKey(seed), cfg, dtype=jnp.float32)
    rng = np.random.default_rng(0)
    tokens = rng.integers(1, cfg.vocab_size, (2, 24)).astype(np.int32)
    rows = np.tile(np.arange(24, dtype=np.int32), (2, 1))
    got = np.asarray(ref.logits(published(cfg), seed, tokens, rows, "float32"))
    for b in range(2):
        want = np.asarray(
            llama.reference_forward(cfg, params, jnp.asarray(tokens[b]))
        )
        errs = check.row_errors(got[b], want)
        assert errs.max() < 1e-4, (kind, errs.max())


def test_token_margins_and_judge():
    want = np.asarray([[0.0, 3.0, 1.0, -4.0]])
    rms = np.sqrt(np.mean(want ** 2))
    np.testing.assert_allclose(check.token_margins(want), [2.0 / rms])
    verdict = {"quantile": 100, "rel_err": 0.02, "token_mismatches": 1,
               "phase_quantile": 50,
               "rel_err_by_phase": {"prefill": 0.01, "decode": 0.02}}
    ok = {"limit": 0.03, "token_mismatch_limit": 1}
    assert check.judge(verdict, ok) == []
    assert len(check.judge(verdict, dict(ok, limit=0.015))) == 1
    assert len(check.judge(verdict, dict(ok, phase_limit=0.015))) == 1
    assert len(check.judge(verdict, {"limit": 0.03})) == 1  # tokens: limit 0


def test_verdict_by_phase_and_a_wrong_sampler_is_not_correct():
    """One wrong row fails the largest-row rule; a low quantile over all
    rows lets every prefill row be wrong, and the phase's own median does
    not; served tokens that are not the reference's argmax (a broken
    sampler) fail whatever the logits say."""
    rng = np.random.default_rng(3)
    want = rng.normal(size=(4, 5, 50)).astype(np.float32)
    decode = np.tile(np.asarray([False, False, True, True, True]), (4, 1))
    served = want.argmax(-1)
    judged = np.ones(decode.shape, bool)
    limits = {"limit": 0.01}
    one = want.copy()
    one[2, 3] *= 1.5
    v100 = check.verdict(one, want, served, decode, judged)
    assert v100["rel_err"] == pytest.approx(0.5)
    assert len(check.judge(v100, limits)) == 1
    prefill_wrong = want.copy()
    prefill_wrong[:, :2] *= 1.5
    v25 = check.verdict(prefill_wrong, want, served, decode, judged,
                        quantile=25, phase_quantile=50)
    assert v25["rel_err"] == 0.0                       # 8 of 20 rows wrong
    assert check.judge(v25, limits) == []
    why = check.judge(v25, dict(limits, phase_limit=0.02))
    assert len(why) == 1 and "prefill" in why[0]
    sound = check.verdict(want, want, served, decode, judged)
    assert check.judge(sound, dict(limits, phase_limit=0.02)) == []
    broken = check.verdict(want, want, (served + 1) % 50, decode, judged)
    assert broken["token_mismatches"] == broken["token_rows"] == 20
    assert check.judge(broken, dict(limits, token_mismatch_limit=12))


def test_sliding_window_changes_the_answer():
    tokens = np.arange(1, 25, dtype=np.int32)[None]
    rows = np.asarray([[23]], np.int32)
    full = ref.logits(published(DENSE), 5, tokens, rows, "float32")
    cut = ref.logits(published(WINDOWED), 5, tokens, rows, "float32")
    assert float(jnp.abs(full - cut).max()) > 1e-3


def test_plan_steps_chunks_a_long_prompt_and_then_decodes():
    steps = span.plan_steps((5, 70, 20), decode_steps=2, budget=32)
    assert all(sum(n for _, _, n in s) <= 32 for s in steps)
    covered = {b: 0 for b in range(3)}
    for s in steps[:-2]:
        for b, prefix, n in s:
            assert prefix == covered[b]
            covered[b] += n
    assert covered == {0: 5, 1: 70, 2: 20}
    assert len([s for s in steps[:-2] if any(b == 1 for b, _, _ in s)]) >= 3
    assert steps[-2] == [(0, 5, 1), (1, 70, 1), (2, 20, 1)]
    assert steps[-1] == [(0, 6, 1), (1, 71, 1), (2, 21, 1)]


def test_row_errors_is_relative_l2_by_row():
    want = np.asarray([[3.0, 4.0], [0.0, 2.0]])
    got = np.asarray([[3.0, 4.5], [0.0, 2.0]])
    np.testing.assert_allclose(check.row_errors(got, want), [0.1, 0.0])


def test_ragged_attention_cost():
    cost = registry.load("costs", "ragged_paged_attention").cost
    model = {"num_layers": 2, "num_heads": 8, "num_kv_heads": 2,
             "head_dim": 128, "sliding_window": 0}
    engine = {"tp": 1, "cache_head_dim": 128, "dtype_bytes": 2}
    # one decode lane at context 100: 100 (query, key) pairs
    flops, nbytes = cost(
        [(99, 1)], model=model, engine=engine
    )
    assert flops == 2 * 4 * 100 * 8 * 128
    assert nbytes == 2 * (2 * 100 * 2 * 128 * 2 + 2 * 1 * 8 * 128 * 2)
    # a prefill chunk of 4 rows from 0: 1 + 2 + 3 + 4 pairs
    flops, _ = cost(
        [(0, 4), (0, 0)], model=model, engine=engine
    )
    assert flops == 2 * 4 * 10 * 8 * 128
    # tp=2 halves both; a window caps the keys a row sees
    f1, b1 = cost(
        [(99, 1)], model=model, engine=dict(engine, tp=2)
    )
    assert (f1, b1) == (2 * 4 * 100 * 4 * 128, nbytes // 2)
    fw, _ = cost(
        [(99, 1)], model=dict(model, sliding_window=10), engine=engine
    )
    assert fw == 2 * 4 * 10 * 8 * 128


def test_a_cost_function_joins_by_its_file_and_reads_any_model_field(
        monkeypatch):
    """What ``costs/<name>.py`` would hold, put where the import finds it:
    the reader loads it by the name a metric's file gives, and it reads a
    field of the served model that no accepted kernel needs."""
    from chipbench.harness import scalar_fields
    from chipbench.observe import Observations

    def latent_cost(lanes, *, model, engine):
        rows = sum(prefix + n for prefix, n in lanes)
        width = model["kv_lora_rank"] + model["qk_rope_head_dim"]
        return 0, rows * width * engine["kv_dtype_bytes"] * (
            engine["cache_arrays_per_layer"] * model["num_layers"])

    module = types.ModuleType("chipbench.costs.latent_stub")
    module.cost = latent_cost
    monkeypatch.setitem(sys.modules, module.__name__, module)
    model = scalar_fields(ModelConfig.tiny_mla_test())
    assert model["kv_lora_rank"] > 0 and "rope_scaling" not in model
    assert {"num_layers", "num_heads", "num_kv_heads", "head_dim",
            "sliding_window"} <= set(model)
    obs = Observations(
        window=(0.0, 10.0), chips=1, setup_s=1.0, records=[],
        trace={"op_seconds": {"latent_kernel.3": 0.5},
               "host_window": (1.0, 2.0)},
        dispatches=[(1.5, [(99, 1)])], model=model,
        engine={"kv_dtype_bytes": 2, "cache_arrays_per_layer": 1},
        device_kind="TPU v5 lite",
    )
    got = registry.load("readers", "kernel_roofline").read(
        obs, kernel="latent_kernel", cost="latent_stub")
    nbytes = latent_cost([(99, 1)], model=model, engine=obs.engine)[1]
    assert got == pytest.approx(100 * (nbytes / 819e9) / 0.5)


def _compare_with(monkeypatch, data, module):
    """``check.compare`` with the runner's side canned: what the
    reference named in ``data`` is called with."""
    rows = np.zeros((1, 2), np.int32)
    got = np.ones((1, 2, data["published"]["vocab_size"]), np.float32)
    monkeypatch.setattr(
        span, "drive",
        lambda *a, **kw: {
            "rows": rows, "decode": np.asarray([[False, True]]),
            "logits": got, "served": np.zeros((1, 2), np.int64),
            "judged": np.ones((1, 2), bool),
        },
    )
    monkeypatch.setattr(check, "free", lambda runner: None)
    calls = []

    def logits(*args, **kwargs):
        calls.append((args, kwargs))
        return got

    # under the reference's own signature, which ``compare`` reads to see
    # what it takes (``inspect.signature`` follows ``__wrapped__``)
    monkeypatch.setattr(
        module, "logits", functools.wraps(module.logits)(logits))
    monkeypatch.setitem(sys.modules, module.__name__, module)
    check.compare(data, 5, object(), weights_seed=5, prompt_lens=(3,),
                  decode_steps=1, pad_to=8)
    return calls


def test_compare_tells_a_reference_of_the_share_and_mistral_of_nothing(
        monkeypatch):
    def logits(published, seed, tokens, rows, dtype, *, source_values=None,
               share=None):
        raise AssertionError("stood in for")

    stub = types.ModuleType("chipbench.reference.share_stub")
    stub.logits = logits
    data = manifest.config("tiny-slice-rehearsal")
    with monkeypatch.context() as m:
        (args, kwargs), = _compare_with(
            m, dict(data, reference="share_stub"), stub)
    assert args[0] == data["published"] and args[1] == 5
    assert kwargs == {"dtype": "float32", "share": data["share"],
                      "source_values": {"vocab_size": 384}}
    # reference/mistral.py takes neither: a file without the blocks gets
    # the call it always got, and so does a sliced vocabulary
    for name in ("tiny-rehearsal", "tiny-slice-rehearsal"):
        with monkeypatch.context() as m:
            (args, kwargs), = _compare_with(m, manifest.config(name), ref)
        assert kwargs == {"dtype": "float32"}, name
    # but experts held in part need a reference that is told of them
    held = dict(data, reduced=["num_local_experts"])
    with pytest.raises(ValueError, match="takes no source_values and share"):
        check.share_arguments(held, ref)


def test_peaks_are_keyed_by_device_kind_and_unknown_is_an_error():
    v5e = peaks_for("TPU v5 lite")
    assert v5e["flops_bf16"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks_for("cpu")
