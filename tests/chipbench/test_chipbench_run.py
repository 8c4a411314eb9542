"""One whole run on the CPU at the rehearsal's tiny sizes, the refusal
without a chip, and the control of ``correct`` kept as a test."""

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


@pytest.mark.parametrize("trace", [0, 1])
def test_whole_run_on_the_cpu_prints_a_well_formed_last_line(trace):
    proc = _run(
        "chipbench", "--workload", "tiny-rehearsal.rehearsal", "--seed",
        str(2**31 + 12345), "--seconds", "3", "--trace", str(trace),
        "--allow-cpu",
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    result = json.loads(lines[-1])
    assert sorted(result) == [
        "attempted", "correct", "device", "failed", "metrics",
    ]
    assert result["device"]["platform"] == "cpu"  # never a device number
    assert result["device"]["count"] == 1
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["correct"] is True, proc.stdout[-3000:]
    from chipbench import manifest

    cell = manifest.workload("tiny-rehearsal.rehearsal")
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
    said = [json.loads(ln) for ln in lines[:-1] if ln.startswith('{"chipbench"')]
    kinds = {s["chipbench"] for s in said}
    assert {"generator", "server_loop", "runner_vs_reference",
            "compiles_in_window", "requests"} <= kinds
    cmp_line = next(s for s in said if s["chipbench"] == "runner_vs_reference")
    assert cmp_line["rel_err"] <= cmp_line["limits"]["limit"]
    assert cmp_line["token_rows"] > 0 and cmp_line["token_mismatches"] == 0


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
