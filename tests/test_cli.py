"""CLI tests: the shell entrypoints actually serve (reference analogue:
launch/dynamo-run's in/out matrix, opt.rs:22-188).

Subprocess-driven like a user would run them; CPU backend, tiny preset.
"""

import asyncio
import json
import os
import re
import signal
import sys

import httpx
import pytest

from dynamo_tpu.cli import _parse_mesh, build_parser

pytestmark = pytest.mark.anyio

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_parse_mesh():
    assert _parse_mesh(None) == {}
    assert _parse_mesh("tp=4") == {"tp": 4}
    assert _parse_mesh("tp=2,dp=2") == {"tp": 2, "dp": 2}
    with pytest.raises(SystemExit):
        _parse_mesh("bogus=3")


def test_parser_defaults():
    args = build_parser().parse_args(["run"])
    assert args.input == "http" and args.output == "tpu"
    args = build_parser().parse_args(
        ["run", "--in", "batch:f.txt", "--out", "echo_core"]
    )
    assert args.input == "batch:f.txt"


async def _spawn_cli(*args: str, ready_pattern: str, timeout: float = 120):
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    env["JAX_PLATFORMS"] = "cpu"
    proc = await asyncio.create_subprocess_exec(
        sys.executable, "-m", "dynamo_tpu", *args,
        stdout=asyncio.subprocess.PIPE,
        stderr=asyncio.subprocess.STDOUT,
        env=env,
        cwd=REPO,
    )
    lines = []
    pat = re.compile(ready_pattern)
    while True:
        line = await asyncio.wait_for(proc.stdout.readline(), timeout)
        if not line:
            raise RuntimeError(
                "CLI died before ready:\n" + "".join(lines)
            )
        text = line.decode()
        lines.append(text)
        m = pat.search(text)
        if m:
            return proc, m


async def _stop(proc) -> None:
    if proc.returncode is None:
        proc.send_signal(signal.SIGINT)
        try:
            await asyncio.wait_for(proc.wait(), 15)
        except asyncio.TimeoutError:
            proc.kill()
            await proc.wait()


async def test_cli_batch_echo(tmp_path):
    prompts = tmp_path / "prompts.txt"
    prompts.write_text("hello world\nsecond prompt\n")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = await asyncio.create_subprocess_exec(
        sys.executable, "-m", "dynamo_tpu", "run",
        "--in", f"batch:{prompts}", "--out", "echo_core",
        stdout=asyncio.subprocess.PIPE,
        stderr=asyncio.subprocess.STDOUT,
        env=env,
        cwd=REPO,
    )
    out, _ = await asyncio.wait_for(proc.communicate(), 120)
    text = out.decode()
    assert proc.returncode == 0, text
    import json

    json_lines = [
        ln for ln in text.strip().splitlines() if ln.startswith("{")
    ]
    assert json_lines, f"no JSON report in output:\n{text}"
    report = json.loads(json_lines[-1])
    assert report["requests"] == 2
    assert report["tokens_out_per_s"] > 0
    assert report["p50_ttft_ms"] is not None


async def test_cli_http_serves_tpu_preset():
    """One shell command serves OpenAI-compatible chat on the real engine
    (tiny preset, CPU)."""
    proc, m = await _spawn_cli(
        "run", "--in", "http", "--out", "tpu",
        "--model-path", "preset:tiny-test",
        "--http-host", "127.0.0.1", "--http-port", "0",
        "--max-model-len", "64", "--num-blocks", "32",
        "--max-num-seqs", "4", "--no-warmup",
        ready_pattern=r"OpenAI server on http://127\.0\.0\.1:(\d+)",
    )
    try:
        port = int(m.group(1))
        async with httpx.AsyncClient() as client:
            r = await client.get(f"http://127.0.0.1:{port}/v1/models")
            assert [x["id"] for x in r.json()["data"]] == ["tiny-test"]
            r = await client.post(
                f"http://127.0.0.1:{port}/v1/chat/completions",
                json={
                    "model": "tiny-test",
                    "messages": [{"role": "user", "content": "hi"}],
                    "stream": False,
                    "max_tokens": 4,
                },
                timeout=120,
            )
            assert r.status_code == 200, r.text
            data = r.json()
            assert data["usage"]["completion_tokens"] > 0
    finally:
        await _stop(proc)


def _dispatch_counts(metrics_text: str) -> dict[str, float]:
    return {
        path: float(re.search(
            rf"^\S*router_dispatch_{path}_total (\S+)$", metrics_text, re.M
        ).group(1))
        for path in ("local", "wire")
    }


async def test_cli_one_process_dispatches_locally():
    """`--in http --out <engine>` in one process: the frontend calls the
    engine it lives with. Every request is counted local, none on the
    wire, and the model is still registered for anyone else."""
    proc, m = await _spawn_cli(
        "run", "--in", "http", "--out", "echo_core",
        "--http-host", "127.0.0.1", "--http-port", "0",
        ready_pattern=r"OpenAI server on http://127\.0\.0\.1:(\d+)",
    )
    try:
        base = f"http://127.0.0.1:{m.group(1)}"
        async with httpx.AsyncClient() as client:
            r = await client.get(f"{base}/v1/models")
            assert [x["id"] for x in r.json()["data"]] == ["echo_core"]
            for i in range(5):
                body = {
                    "model": "echo_core",
                    "messages": [{"role": "user", "content": f"ping {i}"}],
                    "stream": bool(i % 2),
                }
                r = await client.post(
                    f"{base}/v1/chat/completions", json=body, timeout=60
                )
                assert r.status_code == 200, r.text
                assert f"ping {i}" in (
                    "".join(
                        c["delta"].get("content") or ""
                        for line in r.text.splitlines()
                        if line.startswith("data: {")
                        for c in json.loads(line[6:])["choices"]
                    )
                    if body["stream"]
                    else r.json()["choices"][0]["message"]["content"]
                )
            counts = _dispatch_counts((await client.get(f"{base}/metrics")).text)
            assert counts == {"local": 5.0, "wire": 0.0}
    finally:
        await _stop(proc)


async def test_cli_worker_joins_frontend():
    """Two shell commands: a frontend hosting the control plane + HTTP, and
    a separate worker process joining it — the reference's
    `in=http out=dyn` + `in=dyn://... out=...` split (lib.rs:207-240)."""
    front, m = await _spawn_cli(
        "run", "--in", "http", "--out", "dyn",
        "--spawn-control-plane", "0",
        "--http-host", "127.0.0.1", "--http-port", "0",
        ready_pattern=r"control plane on ([0-9.]+:\d+)",
    )
    worker = None
    try:
        cp_addr = m.group(1)
        # The frontend prints its HTTP line next.
        pat = re.compile(r"OpenAI server on http://127\.0\.0\.1:(\d+)")
        while True:
            line = (await asyncio.wait_for(front.stdout.readline(), 60)).decode()
            assert line, "frontend died"
            hit = pat.search(line)
            if hit:
                port = int(hit.group(1))
                break

        worker, _ = await _spawn_cli(
            "run", "--in", "dyn://dynamo.tpu.generate", "--out", "echo_core",
            "--control-plane", cp_addr, "--model-name", "joined-echo",
            ready_pattern=r"worker serving dyn://dynamo\.tpu\.generate",
        )
        async with httpx.AsyncClient() as client:
            deadline = asyncio.get_running_loop().time() + 30
            while True:
                r = await client.get(f"http://127.0.0.1:{port}/v1/models")
                if [x["id"] for x in r.json()["data"]] == ["joined-echo"]:
                    break
                assert asyncio.get_running_loop().time() < deadline, (
                    "worker never discovered"
                )
                await asyncio.sleep(0.2)
            r = await client.post(
                f"http://127.0.0.1:{port}/v1/chat/completions",
                json={
                    "model": "joined-echo",
                    "messages": [{"role": "user", "content": "ping pong"}],
                    "stream": False,
                },
                timeout=60,
            )
            assert r.status_code == 200, r.text
            assert "ping pong" in r.json()["choices"][0]["message"]["content"]
            # Two processes: the picked instance is never local.
            counts = _dispatch_counts(
                (await client.get(f"http://127.0.0.1:{port}/metrics")).text
            )
            assert counts == {"local": 0.0, "wire": 1.0}
    finally:
        await _stop(front)
        if worker is not None:
            await _stop(worker)


async def test_chip_smoke_serve_phase_rehearsal(monkeypatch, capsys):
    """chip_smoke.py's serve and model phases, rehearsed here: the same
    functions the chip run calls, through the same CLI entry (cli._run,
    warmup on, HTTP on 127.0.0.1, SIGTERM to stop), with the tiny preset
    and the Pallas kernel in interpret mode. Catches wrong arguments and
    control flow before chip time is spent; says nothing about the
    chip."""
    monkeypatch.setenv("DYNAMO_TPU_PALLAS", "1")
    monkeypatch.syspath_prepend(REPO)
    import chip_smoke

    report, runner = await chip_smoke.serve_and_query(
        "preset:tiny-test",
        cli_args=[
            "--max-model-len", "256", "--num-blocks", "64",
            "--max-num-seqs", "4", "--unified-token-budget", "32",
            "--unified-prefill-quantum", "16",
        ],
        prompt_lens=[20, 60, 5, 90, 33], max_tokens=4,
        expect_mosaic=False, startup_timeout_s=300, request_timeout_s=120,
    )
    assert report["tokens_returned"] == [4] * 5
    assert report["attention_path"] == "pallas"
    assert report["health"][-1] == [200, "ready"]
    assert report["mid_traffic_compiles_total"] == 0
    assert report["mixed_dispatches"] > 0
    out = capsys.readouterr().out
    assert '"phase": "serve"' in out and '"phase": "shutdown"' in out
    model = chip_smoke.pallas_vs_xla_model(runner, prompt_lens=[7, 19, 5])
    assert [s["step"] for s in model["steps"]] == [
        "prefill", "decode1", "decode2", "decode3"
    ]


def test_chip_smoke_refuses_without_a_tpu():
    """The driver's contract: with no accelerator the script exits
    non-zero and prints no result line."""
    import subprocess

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "needs 1 TPU chip" in proc.stderr
