"""One whole run on the CPU at the rehearsal's tiny sizes (whole, and cut
to a share), the refusal without a chip, and the control of ``correct``
and a planted fault kept as tests."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _env(**extra):
    env = dict(os.environ)
    env.update(
        JAX_PLATFORMS="cpu", DYNAMO_TPU_PALLAS="1",
        DYNAMO_TPU_COMPILE_CACHE_DIR="none", BENCH_RUN="driver-use",
    )
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    # One CPU device is enough and starts faster than the suite's eight;
    # one thread, so a whole server does not crowd the suite's other
    # workers (timing-sensitive tests run beside this file).
    env["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=1 "
        "--xla_cpu_multi_thread_eigen=false intra_op_parallelism_threads=1"
    )
    env["OMP_NUM_THREADS"] = env["OPENBLAS_NUM_THREADS"] = "1"
    env.update(extra)
    return env


def _run(*argv, timeout=900):
    # At a low priority, for the same reason.
    return subprocess.run(
        ["nice", "-n", "15", sys.executable, "-m", *argv], cwd=REPO,
        env=_env(), capture_output=True, text=True, timeout=timeout,
    )


def _last_lines(proc):
    """The result line, the ``say`` lines before it, and what standard
    error ends with."""
    lines = proc.stdout.strip().splitlines()
    said = [json.loads(ln) for ln in lines[:-1] if ln.startswith('{"chipbench"')]
    return json.loads(lines[-1]), said, proc.stderr.strip().splitlines()


@pytest.mark.parametrize("cell,trace", [
    ("tiny-rehearsal.rehearsal", 0), ("tiny-rehearsal.rehearsal", 1),
    ("tiny-slice-rehearsal.rehearsal", 0),
])
def test_whole_run_on_the_cpu_prints_a_well_formed_last_line(cell, trace):
    proc = _run(
        "chipbench", "--workload", cell, "--seed",
        str(2**31 + 12345), "--seconds", "3", "--trace", str(trace),
        "--allow-cpu",
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    result, said, errors = _last_lines(proc)
    # each number compared beside its limit: the result's last key and
    # the last lines of standard error
    assert list(result) == [
        "correct", "attempted", "failed", "metrics", "device", "compared",
    ]
    compared = result["compared"]
    assert {"rel_err_p100", "token_mismatches", "requests_failed",
            "compiles_in_window"} == set(compared)
    assert all(c["value"] <= c["limit"] for c in compared.values())
    assert [ln.split()[:3] for ln in errors[-len(compared):]] == [
        ["chipbench", "compared", name] for name in compared
    ]
    assert result["device"]["platform"] == "cpu"  # never a device number
    assert result["device"]["count"] == 1
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["correct"] is True, proc.stdout[-3000:]
    from chipbench import manifest

    cell = manifest.workload(cell)
    if trace:
        # device-trace metrics have nothing to read on the CPU and are
        # left out; the counters and spans are there
        assert set(result["metrics"]) <= set(cell["per_layer"])
        for name in ("scheduler.tokens_per_dispatch", "runner.dispatch_p50_ms",
                     "runner.compiles_in_window", "frontend.ttft_p95_ms",
                     "scheduler.queue_wait_p95_ms", "kv.pool_used_peak_pct"):
            assert name in result["metrics"], name
        assert not any(
            manifest.metric(n)["source"] == "device_trace"
            for n in result["metrics"]
        )
    else:
        assert sorted(result["metrics"]) == sorted(cell["end_to_end"])
    for name, m in result["metrics"].items():
        assert m["unit"] == manifest.metric(name)["unit"]
        assert isinstance(m["value"], float)
    kinds = {s["chipbench"] for s in said}
    assert {"generator", "server_loop", "runner_vs_reference",
            "compiles_in_window", "requests"} <= kinds
    cmp_line = next(s for s in said if s["chipbench"] == "runner_vs_reference")
    assert cmp_line["rel_err"] <= cmp_line["limits"]["limit"]
    assert cmp_line["token_rows"] > 0 and cmp_line["token_mismatches"] == 0
    # a sliced vocabulary is a smaller vocabulary, on both sides
    vocab = manifest.config(cell["config"])["published"]["vocab_size"]
    assert cmp_line["logit_width"] == [vocab, vocab]
    assert cmp_line["largest_served_token"] < vocab
    if cell["config"] == "tiny-slice-rehearsal":
        assert vocab == 288


#: the served path broken underneath the harness: every token altered
#: where it is produced, the requests answered all the same
ALTERED_TOKENS = """
import sys
from dynamo_tpu.engine.runner import ModelRunner
from chipbench import harness

real = ModelRunner.unified_step

def unified_step(self, lanes, *a, **kw):
    out = real(self, lanes, *a, **kw)
    return out._replace(last=(out.last + 1) % self.cfg.model.vocab_size)

ModelRunner.unified_step = unified_step
harness.main(sys.argv[1:])
"""


def test_a_token_altered_where_it_is_produced_is_not_correct():
    proc = subprocess.run(
        ["nice", "-n", "15", sys.executable, "-c", ALTERED_TOKENS,
         "--workload", "tiny-rehearsal.rehearsal", "--seed", "77",
         "--seconds", "2", "--trace", "0", "--allow-cpu"],
        cwd=REPO, env=_env(), capture_output=True, text=True, timeout=900,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    result, said, errors = _last_lines(proc)
    assert result["correct"] is False
    assert result["attempted"] > 0 and result["failed"] == 0
    tokens = result["compared"]["token_mismatches"]
    assert tokens["value"] > tokens["limit"] == 0
    logits = result["compared"]["rel_err_p100"]
    assert logits["value"] <= logits["limit"]   # the logits are sound
    assert any(ln.startswith("chipbench not_correct: token_mismatches")
               for ln in errors)


def test_without_a_chip_it_exits_non_zero_and_prints_no_result():
    proc = _run(
        "chipbench", "--workload", "tiny-rehearsal.rehearsal", "--seed", "1",
        "--seconds", "1", "--trace", "0",
    )
    assert proc.returncode not in (0, None)
    assert '"correct"' not in proc.stdout
    assert "TPU chip" in proc.stderr


def test_the_control_comes_out_not_correct():
    """The program's own lower-precision paths (int8 weights, int8 KV) in
    the program's place read far above the limit; sound runs far below."""
    proc = _run(
        "chipbench.control", "--config", "tiny-rehearsal", "--seeds", "2",
        "--control-seeds", "3", "--allow-cpu",
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    limit = last["limits_in_file"]["limit"]
    assert last["sound_max"]["rel_err"] < limit / 3
    assert last["sound_max"]["token_mismatches"] == 0
    assert last["sound_not_correct"] == 0
    for path in ("int8_weights", "int8_kv"):
        assert last["control_min"][path]["rel_err"] > 3 * limit, last
        assert last["control_correct"][path] == 0, last
