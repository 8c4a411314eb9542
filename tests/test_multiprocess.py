"""TRUE multi-process tests: worker processes join a control-plane server
over TCP; the driver (this process) routes requests to them, observes KV
affinity across the process boundary, and verifies that killing a worker
expires its lease, deregisters its instances, and drains routing to the
survivor with zero failed requests (reference behavior:
docs/architecture/disagg_serving.md:111-194 runtime-reconfigurable xPyD;
transports/etcd.rs:100-131 lease-death deregistration).
"""

import asyncio
import os
import signal
import subprocess
import sys

import pytest

from dynamo_tpu.llm.protocols.common import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.llm.tokens import TokenBlockSequence
from dynamo_tpu.runtime.distributed import DistributedRuntime
from dynamo_tpu.runtime.egress import PushRouter, RouterMode
from dynamo_tpu.runtime.engine import Context
from dynamo_tpu.runtime.transports.control_plane import ControlPlaneServer

pytestmark = pytest.mark.anyio

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "procs", "mocker_worker.py")
PREFILL = os.path.join(REPO, "tests", "procs", "prefill_worker.py")
SHARDED = os.path.join(REPO, "tests", "procs", "sharded_worker.py")


async def _spawn_proc(script: str, *args: str):
    """Start a worker subprocess; wait for READY; return (proc, worker_id)."""
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)  # let the script pick cpu itself
    proc = await asyncio.create_subprocess_exec(
        sys.executable,
        script,
        *args,
        stdout=asyncio.subprocess.PIPE,
        stderr=asyncio.subprocess.STDOUT,
        env=env,
        cwd=REPO,
    )
    while True:
        line = await asyncio.wait_for(proc.stdout.readline(), 120)
        if not line:
            raise RuntimeError("worker died before READY")
        text = line.decode().strip()
        if text.startswith("READY "):
            return proc, int(text.split()[1])


def _req(prompt, max_tokens=4):
    return PreprocessedRequest(
        token_ids=prompt,
        sampling=SamplingOptions(temperature=0.0),
        stop=StopConditions(max_tokens=max_tokens, ignore_eos=True),
    ).to_wire()


async def _send(push, prompt, **kw):
    """Returns (tokens, serving worker id)."""
    toks, wid = [], None
    async for item in push.generate(Context(_req(prompt)), **kw):
        toks += item.get("token_ids") or []
        wid = item.get("worker_id", wid)
    return toks, wid


@pytest.fixture
async def plane():
    server = await ControlPlaneServer().start()
    frontend = await DistributedRuntime.connect(server.address)
    procs = []

    async def spawn(seed, ttl=1.0, script=WORKER, extra=()):
        args = ["--addr", server.address, "--ttl", str(ttl), *extra]
        if script == WORKER:
            args += ["--seed", str(seed)]
        proc, wid = await _spawn_proc(script, *args)
        procs.append(proc)
        return proc, wid

    yield server, frontend, spawn
    for proc in procs:
        if proc.returncode is None:
            proc.kill()
        await proc.wait()
    await frontend.shutdown()
    await server.stop()


async def test_cross_process_round_robin_and_worker_death(plane):
    server, frontend, spawn = plane
    proc_a, wid_a = await spawn(seed=1)
    proc_b, wid_b = await spawn(seed=2)
    assert wid_a != wid_b

    push = await PushRouter.create(
        frontend, "test.worker.generate", mode=RouterMode.ROUND_ROBIN
    )
    served = set()
    for i in range(4):
        toks, wid = await _send(push, list(range(16)))
        assert toks, "no tokens streamed back across the process boundary"
        served.add(wid)
    assert served == {wid_a, wid_b}

    # Kill worker A hard (no graceful deregistration): its lease (ttl=1s)
    # must expire, the instance key must vanish, and every subsequent
    # request must land on B without a single failure.
    proc_a.kill()
    await proc_a.wait()
    deadline = asyncio.get_running_loop().time() + 10
    while wid_a in push.client.instance_ids():
        assert asyncio.get_running_loop().time() < deadline, (
            "dead worker instance never deregistered"
        )
        await asyncio.sleep(0.1)

    for _ in range(4):
        toks, wid = await _send(push, list(range(16)))
        assert toks and wid == wid_b


async def test_cross_process_kv_affinity(plane):
    """The round-1 in-process affinity test (tests/test_kv_router.py),
    now with the two mocker workers in separate OS processes: KV events
    and load metrics flow over the wire into the driver's KvRouter."""
    from dynamo_tpu.llm.kv_router.router import KvRouter

    server, frontend, spawn = plane
    _, wid_a = await spawn(seed=1)
    _, wid_b = await spawn(seed=2)

    comp = frontend.namespace("test").component("worker")
    router = await KvRouter(frontend, comp).start()
    push = await PushRouter.create(
        frontend,
        "test.worker.generate",
        mode=RouterMode.KV,
        selector=router.selector_fn,
    )

    prompt = list(range(64))  # 4 full blocks
    toks, first_wid = await _send(push, prompt)
    assert toks and first_wid in (wid_a, wid_b)

    # KV events from the worker process must reach this process's indexer.
    hashes = TokenBlockSequence.from_tokens(prompt, block_size=16).sequence_hashes()
    deadline = asyncio.get_running_loop().time() + 5
    while True:
        overlaps = await router.indexer.find_matches(hashes)
        if overlaps:
            break
        assert asyncio.get_running_loop().time() < deadline, (
            "KV events never crossed the process boundary"
        )
        await asyncio.sleep(0.05)
    assert list(overlaps) == [first_wid]

    # Affinity: identical prompts stick to the block-holding worker.
    for _ in range(3):
        _, wid = await _send(push, prompt)
        assert wid == first_wid

    await router.stop()


@pytest.mark.parametrize("transport", ["tcp", "native"])
async def test_cross_process_disagg_roundtrip(plane, transport):
    """Remote prefill in a REAL separate process: the decode engine (this
    process) routes a long prompt through the shared queue; the prefill
    process computes KV and pushes it over the transfer plane; the greedy
    continuation must be bit-identical to a local-only run."""
    import jax

    from dynamo_tpu.disagg import (
        DecodeOperator,
        DisaggConfig,
        DisaggRouter,
        PrefillQueue,
    )
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.engine import TpuEngine
    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import ModelConfig

    server, frontend, spawn = plane
    mcfg = ModelConfig.tiny_test()
    params = llama.init_params(jax.random.PRNGKey(0), mcfg, dtype="float32")
    ecfg = EngineConfig(
        model=mcfg, num_blocks=32, max_num_seqs=2, max_model_len=128,
        dtype="float32",
    )
    prompt = list(range(40))

    # Local oracle.
    local = TpuEngine(ecfg, params=params)
    await local.start()
    expected, _ = [], None
    async for item in local.generate(Context(_req(prompt, max_tokens=6))):
        expected += item.get("token_ids") or []
    await local.stop()
    assert expected

    await spawn(seed=0, ttl=2.0, script=PREFILL)

    decode = TpuEngine(ecfg, params=params)
    await decode.start()
    dis = DisaggRouter.__new__(DisaggRouter)
    dis.cfg = DisaggConfig(max_local_prefill_length=16, max_prefill_queue_size=8)
    op = await DecodeOperator(
        decode, PrefillQueue(frontend, "test"), dis, transport=transport
    ).start()
    assert op.transport == transport

    toks = []
    async for item in op.generate(Context(_req(prompt, max_tokens=6))):
        toks += item.get("token_ids") or []
    assert toks == expected
    assert op.remote_count == 1 and op.local_count == 0

    await op.stop()
    await decode.stop()


async def test_prefill_worker_death_after_dequeue_redelivers(plane):
    """The 'done' gate for the durable queue: a prefill worker that
    crashes AFTER dequeuing (before pushing KV) must not lose the request —
    its connection death nacks the leased item, a later worker picks it up,
    and the decode stream still completes bit-identical to a local run."""
    import jax

    from dynamo_tpu.disagg import (
        DecodeOperator,
        DisaggConfig,
        DisaggRouter,
        PrefillQueue,
    )
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.engine import TpuEngine
    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import ModelConfig

    server, frontend, spawn = plane
    mcfg = ModelConfig.tiny_test()
    params = llama.init_params(jax.random.PRNGKey(0), mcfg, dtype="float32")
    ecfg = EngineConfig(
        model=mcfg, num_blocks=32, max_num_seqs=2, max_model_len=128,
        dtype="float32",
    )
    prompt = list(range(40))

    local = TpuEngine(ecfg, params=params)
    await local.start()
    expected = []
    async for item in local.generate(Context(_req(prompt, max_tokens=6))):
        expected += item.get("token_ids") or []
    await local.stop()

    # Only the crashing worker is up when the request is enqueued.
    dying, _ = await spawn(
        seed=0, ttl=2.0, script=PREFILL, extra=("--die-after-dequeue",)
    )

    decode = TpuEngine(ecfg, params=params)
    await decode.start()
    dis = DisaggRouter.__new__(DisaggRouter)
    dis.cfg = DisaggConfig(max_local_prefill_length=16, max_prefill_queue_size=8)
    op = await DecodeOperator(
        decode, PrefillQueue(frontend, "test"), dis, transport="tcp"
    ).start()

    async def consume():
        toks = []
        async for item in op.generate(Context(_req(prompt, max_tokens=6))):
            toks += item.get("token_ids") or []
        return toks

    stream = asyncio.ensure_future(consume())
    await asyncio.wait_for(dying.wait(), 30)  # crashed holding the lease
    assert dying.returncode == 17
    assert not stream.done(), "stream must still be pending, not failed"

    # A healthy worker arrives later and must receive the redelivery.
    await spawn(seed=0, ttl=2.0, script=PREFILL)
    toks = await asyncio.wait_for(stream, 60)
    assert toks == expected
    assert op.remote_count == 1 and op.local_count == 0

    await op.stop()
    await decode.stop()


async def test_cross_process_sharded_worker_matches_local(plane):
    """Cross-host × multi-chip serving: a worker PROCESS running a REAL
    TpuEngine over a tp=2 virtual mesh serves requests routed from this
    process, and its greedy tokens are identical to a local single-device
    engine with the same weights (the determinism contract both sides
    build from PRNGKey(0) fp32). This is the multi-process × multi-device
    shape (reference: one engine process per host,
    TP inside — lib/llm/src/engines.rs:42-60 MultiNodeConfig)."""
    import jax

    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.engine import TpuEngine
    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import ModelConfig

    server, frontend, spawn = plane
    # Long TTL: mesh-sharded jit TRACING is Python-side and holds the GIL
    # for seconds inside the engine thread, starving the keepalive
    # coroutine — a real deployment sizes lease TTLs above its worst
    # compile stall for exactly this reason.
    await spawn(seed=0, ttl=30.0, script=SHARDED)

    mcfg = ModelConfig.tiny_test()
    params = llama.init_params(jax.random.PRNGKey(0), mcfg, dtype="float32")
    local = TpuEngine(
        EngineConfig(
            model=mcfg, num_blocks=32, max_num_seqs=2, max_model_len=128,
            dtype="float32",
        ),
        params=params,
    )
    await local.start()
    try:
        push = await PushRouter.create(
            frontend, "test.worker.generate", mode=RouterMode.ROUND_ROBIN
        )
        prompt = [1, 5, 9, 2, 7, 3, 8]
        remote_toks, _ = await _send(push, prompt)

        local_toks = []
        async for item in local.generate(Context(_req(prompt))):
            local_toks += item.get("token_ids") or []
        assert remote_toks == local_toks, (remote_toks, local_toks)
        assert len(remote_toks) == 4
    finally:
        await local.stop()
