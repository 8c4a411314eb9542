"""Multi-host serving bootstrap (parallel/multihost.py).

Spawns TWO real OS processes, each owning two virtual CPU devices, joined
through jax.distributed (coordination service + gloo collectives) into one
4-device mesh serving the tiny model — then asserts the greedy tokens are
identical across the processes AND identical to a single-process run of
the same mesh shape. This is the code path a v5p pod slice takes
(reference analogue: MultiNodeConfig multi-node engine bootstrap,
lib/llm/src/engines.rs:42-60, launch/dynamo-run/src/lib.rs:176-258); only
the transport is simulated.
"""

from dynamo_tpu.parallel.multihost import (
    _default_shape,
    run_multihost_check,
    run_serve_harness,
)

STEPS = 16
TOTAL = 4


def test_two_process_mesh_token_identical():
    import jax

    multi_tokens = run_multihost_check(
        total_devices=TOTAL, num_procs=2, steps=STEPS
    )
    # Single-PROCESS baseline over the same mesh shape (4 of the 8 virtual
    # devices the test harness provides).
    single_tokens = run_serve_harness(
        _default_shape(TOTAL), steps=STEPS, devices=jax.devices()[:TOTAL]
    )
    assert multi_tokens == single_tokens, (
        f"2-process serving diverged from single-process:\n"
        f"  multi:  {multi_tokens}\n  single: {single_tokens}"
    )


def test_serve_tokens_match_reference_greedy():
    """The shared serve harness (one prefill dispatch for every lane, then
    one decode dispatch a step, all through unified_step) yields, in every
    lane, the no-cache reference forward's greedy continuation."""
    from dynamo_tpu.engine.runner import ModelRunner
    from dynamo_tpu.parallel.multihost import _tiny_engine_config, serve_tokens
    from stepdrive import reference_greedy

    ecfg = _tiny_engine_config()
    runner = ModelRunner(ecfg)
    prompt, lanes, steps = [1, 2, 3, 4, 5], 3, 6
    got = serve_tokens(runner, ecfg, prompt, lanes, steps)

    want = reference_greedy(
        ecfg.model, runner.params, prompt, steps + 1, length=ecfg.max_model_len
    )
    # Step-major: [first x lanes, step 1 x lanes, ...].
    assert got == [t for t in want for _ in range(lanes)]


def test_graft_entry_jits_and_runs_tiny(monkeypatch):
    """``__graft_entry__.entry()``'s contract — (fn, example_args), fn
    jittable on one device — at a tiny shape: the test swaps the flagship
    preset for the tiny model, the program takes no such option."""
    import sys

    import jax
    import numpy as np

    from dynamo_tpu.models.config import ModelConfig

    monkeypatch.syspath_prepend(REPO)
    monkeypatch.setattr(
        ModelConfig, "llama32_1b", staticmethod(ModelConfig.tiny_test)
    )
    sys.modules.pop("__graft_entry__", None)
    import __graft_entry__ as graft

    fn, args = graft.entry()
    logits, kv = jax.jit(fn)(*args)
    cfg = ModelConfig.tiny_test()
    assert logits.shape == (args[2].shape[0], cfg.vocab_size)
    assert np.isfinite(np.asarray(logits, np.float32)).all()
    # Every lane wrote its one token's K into its own slot.
    k0 = np.asarray(kv[0][0], np.float32)
    assert all(k0[int(s)].any() for s in args[6])


# ---------------------------------------------------------------------------
# Full-stack multi-host serving: control plane +
# HTTP frontend here, a 2-process × 4-device mesh worker joined via the
# CLI's --coordinator path (rank 0 = step leader serving the endpoint,
# rank 1 = stepcast follower), one REAL HTTP completion — token-identical
# to a single-process worker of the same mesh shape.
# ---------------------------------------------------------------------------

import asyncio
import os
import socket
import sys

import pytest

pytestmark_async = pytest.mark.anyio

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


async def _spawn_worker(cp_addr: str, rank: int, num_nodes: int,
                        coordinator: str, devices: int):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = [
        f for f in env.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f
    ]
    env["XLA_FLAGS"] = " ".join(
        flags + [f"--xla_force_host_platform_device_count={devices}"]
    )
    args = [
        sys.executable, "-m", "dynamo_tpu", "run",
        "--in", "dyn://dynamo.tpu.generate", "--out", "tpu",
        "--model-path", "preset:tiny-test",
        "--control-plane", cp_addr,
        "--mesh", "tp=2,dp=2",
        "--dtype", "float32",
        "--max-model-len", "64",
        "--num-blocks", "64",
        "--max-num-seqs", "4",
        "--kv-cache-block-size", "4",
        "--no-warmup",
    ]
    if num_nodes > 1:
        args += [
            "--coordinator", coordinator,
            "--num-nodes", str(num_nodes),
            "--node-rank", str(rank),
        ]
    proc = await asyncio.create_subprocess_exec(
        *args,
        stdout=asyncio.subprocess.PIPE,
        stderr=asyncio.subprocess.STDOUT,
        env=env,
        cwd=REPO,
    )
    return proc, []


async def _wait_ready(proc, log: list, rank: int) -> None:
    ready = "registered at" if rank == 0 else "follower rank"
    while True:
        line = await proc.stdout.readline()
        if not line:
            raise RuntimeError(
                f"worker rank {rank} died:\n" + "".join(log[-60:])
            )
        text = line.decode(errors="replace")
        log.append(text)
        if ready in text:
            return


async def _complete_via_http(cp_addr: str) -> list[int]:
    """Frontend half of the CLI stack, in-process: watcher + HTTP service
    against the shared control plane; returns the completion's tokens."""
    import httpx

    from dynamo_tpu.llm.discovery import ModelManager, ModelWatcher
    from dynamo_tpu.llm.http_service import HttpService
    from dynamo_tpu.runtime.distributed import DistributedRuntime

    drt = await DistributedRuntime.connect(cp_addr)
    manager = ModelManager()
    watcher = ModelWatcher(drt, manager)
    await watcher.start()
    for _ in range(100):
        if manager.models():
            break
        await asyncio.sleep(0.1)
    assert manager.models(), "worker model never appeared in discovery"
    service = HttpService(manager, host="127.0.0.1", port=0)
    await service.start()
    try:
        async with httpx.AsyncClient(timeout=240.0) as client:
            r = await client.post(
                f"http://127.0.0.1:{service.port}/v1/completions",
                json={
                    "model": "tiny-test",
                    "prompt": "hello tpu",
                    "max_tokens": 8,
                    "temperature": 0,
                    "nvext": {"ignore_eos": True},
                },
            )
            assert r.status_code == 200, r.text
            text = r.json()["choices"][0]["text"]
    finally:
        await service.stop()
        await drt.shutdown()
    # Byte-level toy tokenizer: the text is the token identity.
    return list(text.encode())


async def _serve_once(num_nodes: int) -> list[int]:
    from dynamo_tpu.runtime.transports.control_plane import (
        ControlPlaneServer,
    )

    server = await ControlPlaneServer().start()
    procs = []
    try:
        coordinator = f"127.0.0.1:{_free_port()}"
        per = 4 // num_nodes
        # Spawn every rank BEFORE waiting: rank 0's sharded runner build
        # blocks on cross-process collectives until rank 1 is up.
        for rank in range(num_nodes):
            procs.append(
                await _spawn_worker(
                    server.address, rank, num_nodes, coordinator, per
                )
            )
        await asyncio.wait_for(
            asyncio.gather(*[
                _wait_ready(proc, log, rank)
                for rank, (proc, log) in enumerate(procs)
            ]),
            300,
        )
        return await _complete_via_http(server.address)
    finally:
        for proc, log in procs:
            if proc.returncode is None:
                proc.terminate()
                try:
                    await asyncio.wait_for(proc.wait(), 20)
                except asyncio.TimeoutError:
                    proc.kill()
        await server.stop()


@pytest.mark.anyio
async def test_full_stack_multihost_http_matches_single_process():
    multi = await _serve_once(num_nodes=2)
    single = await _serve_once(num_nodes=1)
    assert multi, "empty completion"
    assert multi == single, (
        f"multihost HTTP completion diverged:\n"
        f"  multi:  {multi}\n  single: {single}"
    )
