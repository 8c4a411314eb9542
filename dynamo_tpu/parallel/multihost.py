"""Multi-host engine bootstrap: one mesh spanning every host's chips.

The reference carries ``MultiNodeConfig{num_nodes, node_rank, leader_addr}``
(reference: lib/llm/src/engines.rs:42-60) and wires multi-node engine
startup by delegating to each backend engine's own distributed init — ray
for vLLM, MPI for TRT-LLM (reference: launch/dynamo-run/src/lib.rs:176-258).
The TPU build has no backend to delegate to: the engine itself spans hosts.
Every participating process calls :func:`initialize` with the same
coordinator address; JAX's coordination service forms the global device
set, so ``jax.devices()`` enumerates EVERY host's chips and
``build_mesh`` (parallel/mesh.py) lays one mesh across them. XLA compiles
one SPMD program per process; collectives ride ICI within a slice and DCN
across slices — no NCCL/MPI analogue required.

Processes drive the engine in lockstep: each host feeds the same
(replicated) batch inputs, XLA computes the sharded step, and token
outputs are replicated back to every host (the runner pins its token
outputs to a replicated sharding for exactly this reason —
engine/runner.py). The CLI exposes the reference's knobs verbatim:
``--coordinator``, ``--num-nodes``, ``--node-rank``.

For clusters-free validation, :func:`run_multihost_check` spawns N real OS
processes, each given ``devices_per_proc`` virtual CPU devices
(``--xla_force_host_platform_device_count``), joined through a real
coordination service + gloo collectives — the same code path a v5p pod
slice takes, with only the transport simulated.
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import tempfile
from dataclasses import dataclass

import numpy as np

from dynamo_tpu.utils.atomic_io import atomic_write_text

_REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


@dataclass
class MultiHostConfig:
    """Mirror of the reference MultiNodeConfig (lib/llm/src/engines.rs:42-60):
    ``coordinator`` = leader_addr, plus num_nodes / node_rank."""

    coordinator: str | None = None
    num_nodes: int = 1
    node_rank: int = 0


_initialized = False


def initialize(cfg: MultiHostConfig) -> None:
    """Join the multi-host coordination service (idempotent).

    Must run before any JAX computation touches a device. On the CPU
    backend the gloo collectives implementation is selected so the virtual
    multi-process mesh has working cross-process collectives; on TPU the
    default (ICI/DCN) transport is already correct.
    """
    global _initialized
    if cfg.num_nodes <= 1 or _initialized:
        return
    import jax

    platforms = jax.config.jax_platforms or os.environ.get("JAX_PLATFORMS", "")
    if "cpu" in platforms:
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    if cfg.coordinator:
        jax.distributed.initialize(
            coordinator_address=cfg.coordinator,
            num_processes=cfg.num_nodes,
            process_id=cfg.node_rank,
        )
    else:
        # TPU pod slices: the libtpu runtime knows its own topology.
        jax.distributed.initialize()
    _initialized = True


def serve_tokens(runner, ecfg, prompt: list[int], lanes: int, steps: int) -> list[int]:
    """Shared serve harness (also used by __graft_entry__): prefill
    ``lanes`` copies of ``prompt`` into their own blocks in one unified
    dispatch, then ``steps`` greedy decode dispatches; returns first +
    decoded tokens (step-major) for equality checks against another
    runner / process layout."""
    bs = ecfg.block_size
    blocks_per = (len(prompt) + steps + bs - 1) // bs
    # kv_sp runners need STRIPED placement (logical block i on sp shard
    # i % sp — the engine allocator's contract, engine/kv_cache.py).
    shards = getattr(runner, "kv_shards", 1)
    bps = ecfg.num_blocks // shards
    nxt = [s * bps + (1 if s == 0 else 0) for s in range(shards)]

    def take(logical: int) -> int:
        s = logical % shards
        b = nxt[s]
        nxt[s] += 1
        assert b < (s + 1) * bps, "serve harness overflowed an sp shard"
        return b

    tables = [[take(i) for i in range(blocks_per)] for _ in range(lanes)]

    def step(spans: list[tuple[list[int], int]]) -> list[int]:
        out = runner.unified_step([
            (toks, tables[lane], prefix, (0.0, 0, 1.0))
            for lane, (toks, prefix) in enumerate(spans)
        ])
        return [int(t) for t in np.asarray(out.last)[:lanes]]

    toks = step([(prompt, 0)] * lanes)
    served = list(toks)
    for s in range(steps):
        toks = step([([t], len(prompt) + s) for t in toks])
        served += toks
    return served


def _tiny_engine_config():
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.models.config import ModelConfig

    return EngineConfig(
        model=ModelConfig.tiny_test(),
        num_blocks=64,
        max_num_seqs=4,
        max_model_len=64,
        dtype="float32",
    )


def run_serve_harness(
    mesh_shape: dict[str, int], steps: int = 16, devices=None
) -> list[int]:
    """Build a tiny-model ModelRunner over ``mesh_shape`` (spanning the
    GLOBAL device set if jax.distributed is initialized) and serve."""
    from dynamo_tpu.engine.runner import ModelRunner
    from dynamo_tpu.parallel.mesh import build_mesh

    ecfg = _tiny_engine_config()
    mesh = build_mesh(mesh_shape, devices=devices)
    runner = ModelRunner(ecfg, mesh=mesh)
    return serve_tokens(
        runner, ecfg, prompt=[1, 2, 3, 4, 5], lanes=ecfg.max_num_seqs,
        steps=steps,
    )


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def run_multihost_check(
    total_devices: int = 4,
    num_procs: int = 2,
    steps: int = 16,
    timeout_s: float = 600.0,
    _attempts: int = 2,
) -> list[int]:
    """Spawn ``num_procs`` REAL OS processes, each owning
    ``total_devices/num_procs`` virtual CPU devices, joined via
    jax.distributed + gloo into one ``total_devices``-wide mesh serving the
    tiny model; assert every process emits identical tokens and return
    them. The caller compares against a single-process run of the same
    mesh shape (the token-identity gate).

    The coordinator port is probed then released before rank 0 binds it
    (unavoidable across processes), so a lost race surfaces as a child
    failure — retried once with a fresh port."""
    try:
        return _run_multihost_once(total_devices, num_procs, steps, timeout_s)
    except RuntimeError:
        if _attempts <= 1:
            raise
        return run_multihost_check(
            total_devices, num_procs, steps, timeout_s, _attempts - 1
        )


def _run_multihost_once(
    total_devices: int, num_procs: int, steps: int, timeout_s: float
) -> list[int]:
    assert total_devices % num_procs == 0
    per = total_devices // num_procs
    shape = _default_shape(total_devices)
    port = _free_port()
    procs, outs = [], []
    for rank in range(num_procs):
        fd, out = tempfile.mkstemp(suffix=f".mh{rank}.json")
        os.close(fd)
        outs.append(out)
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        flags = [
            f
            for f in env.get("XLA_FLAGS", "").split()
            if "xla_force_host_platform_device_count" not in f
        ]
        env["XLA_FLAGS"] = " ".join(
            flags + [f"--xla_force_host_platform_device_count={per}"]
        )
        procs.append(
            subprocess.Popen(
                [
                    sys.executable,
                    "-m",
                    "dynamo_tpu.parallel.multihost",
                    "--coordinator",
                    f"127.0.0.1:{port}",
                    "--num-nodes",
                    str(num_procs),
                    "--node-rank",
                    str(rank),
                    "--mesh",
                    ",".join(f"{k}={v}" for k, v in shape.items()),
                    "--steps",
                    str(steps),
                    "--out",
                    out,
                ],
                env=env,
                cwd=_REPO_ROOT,
                stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT,
            )
        )
    logs = []
    try:
        for p in procs:
            stdout, _ = p.communicate(timeout=timeout_s)
            logs.append(stdout.decode(errors="replace"))
        for p, log in zip(procs, logs):
            if p.returncode != 0:
                raise RuntimeError(
                    f"multihost child rc={p.returncode}:\n{log[-4000:]}"
                )
        results = []
        for out in outs:
            with open(out) as f:
                results.append(json.load(f))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        for out in outs:
            if os.path.exists(out):
                os.unlink(out)
    for r in results:
        assert r["process_count"] == num_procs, r
        assert r["global_devices"] == total_devices, r
    tok0 = results[0]["tokens"]
    for r in results[1:]:
        assert r["tokens"] == tok0, (
            f"multihost processes disagree: {tok0} vs {r['tokens']}"
        )
    return tok0


def _default_shape(total_devices: int) -> dict[str, int]:
    """tp=2 when it divides (tiny_test has 2 kv heads), rest on dp."""
    tp = 2 if total_devices % 2 == 0 else 1
    return {"tp": tp, "dp": total_devices // tp}


def _child_main(argv: list[str]) -> None:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--coordinator", required=True)
    ap.add_argument("--num-nodes", type=int, required=True)
    ap.add_argument("--node-rank", type=int, required=True)
    ap.add_argument("--mesh", required=True)
    ap.add_argument("--steps", type=int, default=16)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    import jax

    jax.config.update("jax_platforms", "cpu")
    initialize(
        MultiHostConfig(args.coordinator, args.num_nodes, args.node_rank)
    )
    shape = {
        k: int(v) for k, v in (kv.split("=") for kv in args.mesh.split(","))
    }
    tokens = run_serve_harness(shape, steps=args.steps)
    # Atomic: the parent polls for this file and a torn read would fail
    # the whole multihost drill, not just this rank.
    atomic_write_text(
        args.out,
        json.dumps(
            {
                "tokens": tokens,
                "process_count": jax.process_count(),
                "global_devices": len(jax.devices()),
                "local_devices": len(jax.local_devices()),
            }
        ),
    )
    print(
        f"multihost child rank={args.node_rank}: "
        f"{len(jax.local_devices())}/{len(jax.devices())} devices OK",
        flush=True,
    )


if __name__ == "__main__":
    _child_main(sys.argv[1:])
