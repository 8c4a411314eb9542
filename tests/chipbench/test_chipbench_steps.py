"""The check's step drivers: ``steps/span.py`` plans and reads what
``check.plan_steps`` and ``check.runner_rows`` did, and a family's own
driver joins by its file, told its ``step_params``, its rows judged for
tokens only where it says a token was served."""

import dataclasses
import sys
import types

import numpy as np
import pytest

from chipbench import check, control, manifest, modelcfg, registry
from chipbench.steps import span
from dynamo_tpu.models import llama

ACCEPTED = ["mistral-7b-l16", "mixtral-8x7b-l4", "mistral-7b-tp4",
            "tiny-rehearsal"]
SEED = 5


def plan_steps_through_pr_30(lens, decode_steps: int, budget: int):
    """``check.plan_steps`` as it stood before the step became a module:
    the oracle for ``span``'s plan."""
    steps, cur, room = [], [], budget
    for b, n in enumerate(lens):
        done = 0
        while done < n:
            take = min(n - done, room)
            cur.append((b, done, take))
            done += take
            room -= take
            if room == 0:
                steps.append(cur)
                cur, room = [], budget
    if cur:
        steps.append(cur)
    for i in range(decode_steps):
        steps.append([(b, n + i, 1) for b, n in enumerate(lens)])
    return steps


@pytest.mark.parametrize("budget", [64, 256])
@pytest.mark.parametrize("config", ACCEPTED)
def test_span_plans_what_plan_steps_planned(config, budget):
    asked = check.compare_kwargs(manifest.config(config))
    assert "step" not in asked and "step_params" not in asked
    lens = tuple(asked.get("prompt_lens", check.PROMPT_LENS))
    decode_steps = asked.get("decode_steps", check.DECODE_STEPS)
    assert span.plan_steps(lens, decode_steps, budget) == \
        plan_steps_through_pr_30(lens, decode_steps, budget)
    assert [span.sample_len(n, decode_steps) for n in lens] == \
        [n + decode_steps for n in lens]


def build_runner(config: str = "tiny-rehearsal", seed: int = SEED):
    """The runner the rehearsal serves with, as ``chipbench.control``
    builds one."""
    from dynamo_tpu.engine.runner import ModelRunner

    data = manifest.config(config)
    modelcfg.register(data)
    ecfg = dataclasses.replace(control.engine_config(data), seed=seed)
    return data, ModelRunner(ecfg, rng_seed=seed)


def compare(data, runner):
    return check.compare(data, SEED, runner, weights_seed=SEED,
                         **check.compare_kwargs(data))


@pytest.fixture
def unified_calls(monkeypatch):
    """``llama.unified`` recorded: ``(the caller's file, its keywords)``."""
    calls = []
    real = llama.unified

    def unified(*args, **kwargs):
        calls.append((sys._getframe(1).f_code.co_filename, dict(kwargs)))
        return real(*args, **kwargs)

    monkeypatch.setattr(llama, "unified", unified)
    return calls


def test_the_model_function_is_called_as_it_was(unified_calls):
    data, runner = build_runner()
    verdict = compare(data, runner)
    assert check.judge(verdict, data["check"]) == []
    # one row a span, and a token served and judged in every one
    assert verdict["rows"] == verdict["token_rows"] == 3 * 3
    drivers = [kw for path, kw in unified_calls if path == span.__file__]
    assert drivers and len(drivers) < len(unified_calls)
    for kw in drivers:
        assert sorted(kw) == ["attn", "kv_scales"]


def test_logits_altered_where_the_driver_reads_them_are_not_correct(
        monkeypatch):
    real = llama.unified

    def unified(*args, **kwargs):
        logits, *rest = real(*args, **kwargs)
        if sys._getframe(1).f_code.co_filename == span.__file__:
            logits = logits.at[:, 0].add(1.0)
        return (logits, *rest)

    monkeypatch.setattr(llama, "unified", unified)
    data, runner = build_runner()
    verdict = compare(data, runner)
    why = check.judge(verdict, data["check"])
    assert len(why) == 1 and why[0].startswith("rel_err_p100")
    assert verdict["token_mismatches"] == 0


def test_a_served_token_altered_is_not_correct():
    data, runner = build_runner()
    real_step = runner.unified_step

    def unified_step(lanes, *a, **kw):
        out = real_step(lanes, *a, **kw)
        return out._replace(last=(out.last + 1) % runner.cfg.model.vocab_size)

    runner.unified_step = unified_step
    verdict = compare(data, runner)
    assert check.judge(verdict, data["check"]) == ["token_mismatches 9 > 0"]
    assert verdict["rel_err"] < data["check"]["limit"]


def test_a_step_driver_joins_by_its_file(monkeypatch):
    """What ``steps/<family>.py`` would hold, put where the import finds
    it: loaded by the name under ``"step"``, told ``step_params``, its
    ``sample_len`` sizing the sample, its unjudged rows' tokens not read."""
    data = dict(manifest.config("tiny-rehearsal"))
    data["check"] = dict(data["check"], step="block_stub", pad_to=64,
                         step_params={"block": 8, "schedule": "all"})
    told = {}

    def sample_len(n, decode_steps, *, block, schedule):
        return n + decode_steps * block

    def drive(runner, sample, lens, decode_steps, seed, /, *, block,
              schedule):
        told.update(block=block, schedule=schedule, lens=lens,
                    filled=(sample != 0).sum(axis=1).tolist())
        rows = np.asarray([[n - 1, n + block - 1] for n in lens], np.int32)
        return {
            "rows": rows,
            "decode": np.tile([False, True], (len(lens), 1)),
            "logits": np.ones((len(lens), 2, 384), np.float32),
            "served": np.zeros((len(lens), 2), np.int64),
            "judged": np.tile([False, True], (len(lens), 1)),
        }

    stub = types.ModuleType("chipbench.steps.block_stub")
    stub.drive, stub.sample_len = drive, sample_len
    monkeypatch.setitem(sys.modules, stub.__name__, stub)
    assert registry.load("steps", "block_stub") is stub
    runner = types.SimpleNamespace(params=None, kv_caches=None, kv_scales=None)
    verdict = check.compare(data, 5, runner, weights_seed=5,
                            **check.compare_kwargs(data))
    assert told == {"block": 8, "schedule": "all", "lens": (5, 17, 40),
                    "filled": [5 + 16, 17 + 16, 40 + 16]}
    assert verdict["rows"] == 6 and verdict["token_rows"] == 3
    with pytest.raises(ValueError, match="tiny-rehearsal.*pad_to is 48"):
        check.compare(data, 5, runner, weights_seed=5, **dict(
            check.compare_kwargs(data), pad_to=48))
    # a phase_limit over this driver's sample: refused only where the
    # driver says that no sequence decodes
    data["check"]["phase_limit"] = 0.02
    check.compare_kwargs(data)
    data["check"]["step_params"] = {"block": 0, "schedule": "all"}
    with pytest.raises(ValueError, match="tiny-rehearsal.*phase_limit"):
        check.compare_kwargs(data)


def test_an_empty_phase_reads_null_and_a_phase_limit_over_it_is_refused(
        monkeypatch):
    rng = np.random.default_rng(3)
    want = rng.normal(size=(2, 3, 50)).astype(np.float32)
    served, judged = want.argmax(-1), np.ones((2, 3), bool)
    for decode, empty in ((np.zeros((2, 3), bool), "decode"),
                          (np.ones((2, 3), bool), "prefill")):
        verdict = check.verdict(want, want, served, decode, judged)
        full = "prefill" if empty == "decode" else "decode"
        assert verdict["rel_err_by_phase"] == {empty: None, full: 0.0}
        assert check.judge(verdict, {"limit": 0.01}) == []
    # where the file is read, and in ``manifest.check()``, not after a drive
    name = manifest.benchmark_json()["configs"][0]["name"]
    data = manifest.config(name)
    data["check"] = dict(data["check"], decode_steps=0)
    assert check.compare_kwargs(data)["decode_steps"] == 0
    data["check"]["phase_limit"] = 0.02
    with pytest.raises(ValueError, match=f"{name}.*phase_limit.*decode_steps 0"):
        check.compare_kwargs(data)
    real = manifest.config
    monkeypatch.setattr(
        manifest, "config", lambda n: data if n == name else real(n))
    assert [line for line in manifest.check() if "phase_limit" in line]


def test_only_judged_rows_count_for_tokens():
    rng = np.random.default_rng(4)
    want = rng.normal(size=(2, 4, 50)).astype(np.float32)
    decode = np.tile([False, True, True, True], (2, 1))
    judged = np.tile([True, False, False, True], (2, 1))
    served = np.where(judged, want.argmax(-1), -1)
    verdict = check.verdict(want, want, served, decode, judged)
    assert (verdict["token_rows"], verdict["token_mismatches"],
            verdict["token_mismatches_all_rows"]) == (4, 0, 0)
    served[0, 3] = (served[0, 3] + 1) % 50
    verdict = check.verdict(want, want, served, decode, judged)
    assert verdict["token_mismatches"] == 1
    assert check.verdict(want, want, served, decode, np.ones((2, 4), bool))[
        "token_mismatches"] == 5
