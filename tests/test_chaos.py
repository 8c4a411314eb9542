"""Chaos suite: every instrumented fault point armed, every recovery
invariant asserted (no hang — every wait is bounded; no token corruption;
counters incremented; disarmed behavior identical).

The recovery semantics under test are documented in
docs/architecture/failure_model.md; fault points live in
dynamo_tpu/utils/faults.py, the shared backoff policy in
dynamo_tpu/utils/retry.py.
"""

import asyncio
import time

import numpy as np
import pytest

from dynamo_tpu.utils.faults import FAULTS, FaultError, FaultRegistry, _arm_from_env
from dynamo_tpu.utils.retry import RETRIES, RetryPolicy, retry_async, retry_sync

pytestmark = pytest.mark.anyio


@pytest.fixture(autouse=True)
def _disarm_everything():
    FAULTS.clear()
    yield
    FAULTS.clear()


# ---------------------------------------------------------------------------
# Registry + policy primitives
# ---------------------------------------------------------------------------


def test_fault_registry_actions():
    reg = FaultRegistry()
    # Disarmed: free pass, nothing counted.
    assert reg.maybe_fail("p") is True
    assert reg.total_injected == 0

    # raise: fires `times` times then auto-disarms.
    reg.arm("p", "raise", times=2)
    with pytest.raises(FaultError):
        reg.maybe_fail("p")
    with pytest.raises(FaultError):
        reg.maybe_fail("p")
    assert reg.maybe_fail("p") is True  # budget spent
    assert reg.injected["p"] == 2

    # drop: returns False (caller skips the side effect) at drop-capable
    # call sites.
    reg.arm("q", "drop", times=1)
    assert reg.maybe_fail("q", can_drop=True) is False
    assert reg.maybe_fail("q", can_drop=True) is True

    # partition: raises until explicitly disarmed.
    reg.arm("r", "partition")
    for _ in range(5):
        with pytest.raises(FaultError):
            reg.maybe_fail("r")
    reg.disarm("r")
    assert reg.maybe_fail("r") is True

    # delay: proceeds after sleeping.
    reg.arm("s", "delay", delay_s=0.01, times=1)
    t0 = time.monotonic()
    assert reg.maybe_fail("s") is True
    assert time.monotonic() - t0 >= 0.009

    # drop at a seam that cannot skip (can_drop=False, the default) is
    # inert AND uncounted — the counter must never claim a loss that
    # didn't happen.
    reg.arm("t", "drop", times=1)
    assert reg.maybe_fail("t") is True
    assert "t" not in reg.injected
    assert reg.maybe_fail("t", can_drop=True) is False  # still armed
    assert reg.injected["t"] == 1

    # FaultError is transport-shaped: retry filters treat it as loss.
    assert issubclass(FaultError, ConnectionError)
    assert reg.total_injected == sum(reg.injected.values()) > 0


def test_fault_env_arming():
    reg = FaultRegistry()
    _arm_from_env(reg, "a.b:raise:2, c.d:drop , e.f:delay:0.25, ,bad:zap:9")
    assert reg.armed("a.b") and reg.armed("c.d") and reg.armed("e.f")
    assert not reg.armed("bad")  # bad entries are ignored loudly, not fatal
    with pytest.raises(FaultError):
        reg.maybe_fail("a.b")


async def test_retry_async_recovers_and_counts():
    calls = []
    base = RETRIES.total

    async def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise ConnectionError("transient")
        return "ok"

    policy = RetryPolicy(attempts=3, base_delay_s=0.001, jitter=0.0)
    assert await retry_async(flaky, policy, seam="test.flaky") == "ok"
    assert len(calls) == 3
    assert RETRIES.total - base == 2
    assert RETRIES.snapshot().get("test.flaky", 0) >= 2

    # Budget exhaustion re-raises the LAST failure.
    with pytest.raises(ConnectionError):
        await retry_async(
            lambda: (_ for _ in ()).throw(ConnectionError("down")) and None,
            RetryPolicy(attempts=2, base_delay_s=0.001, jitter=0.0),
            seam="test.down",
        )


def test_retry_sync_non_retryable_propagates():
    calls = []

    def bad():
        calls.append(1)
        raise ValueError("logic bug, not transport")

    with pytest.raises(ValueError):
        retry_sync(bad, RetryPolicy(attempts=5, base_delay_s=0.001))
    assert len(calls) == 1  # no blind retry of a non-transport error


def test_retry_deadline_bounds_wall_clock():
    def always_down():
        raise TimeoutError("down")

    policy = RetryPolicy(
        attempts=1000, base_delay_s=0.05, multiplier=1.0, jitter=0.0,
        deadline_s=0.2,
    )
    t0 = time.monotonic()
    with pytest.raises(TimeoutError):
        retry_sync(always_down, policy, seam="test.deadline")
    assert time.monotonic() - t0 < 1.0  # deadline, not 1000 attempts


# ---------------------------------------------------------------------------
# Stepcast typed wire (pickle replacement)
# ---------------------------------------------------------------------------


def test_stepcast_codec_roundtrip():
    from dynamo_tpu.parallel.stepcast import decode_step, encode_step

    toks = np.arange(7, dtype=np.int32)
    tables = np.zeros((2, 4), np.int32)
    args = (
        toks, tables, 5, 2.5, "name", None, True,
        (0.0, 40, 1.0),                      # sampling tuple
        [1, 2, [3, (4, 5)]],                 # nested list/tuple
        {"k": np.float32(1.5), "n": None},   # str-keyed dict
    )
    kwargs = {"mm_embeds": np.ones((2, 3), np.float32), "flag": False}
    seq, name, out_args, out_kwargs = decode_step(
        encode_step(3, "unified_step", args, kwargs)
    )
    assert (seq, name) == (3, "unified_step")
    np.testing.assert_array_equal(out_args[0], toks)
    assert out_args[0].dtype == np.int32
    np.testing.assert_array_equal(out_args[1], tables)
    assert out_args[2:7] == (5, 2.5, "name", None, True)
    assert out_args[7] == (0.0, 40, 1.0) and isinstance(out_args[7], tuple)
    assert out_args[8] == [1, 2, [3, (4, 5)]]
    assert out_args[9] == {"k": 1.5, "n": None}
    np.testing.assert_array_equal(out_kwargs["mm_embeds"], np.ones((2, 3)))
    assert out_kwargs["flag"] is False


def test_stepcast_rejects_malformed():
    import msgpack

    from dynamo_tpu.parallel.stepcast import (
        StepWireError,
        decode_step,
        encode_step,
    )

    # Unknown method name.
    with pytest.raises(StepWireError, match="unexpected replayed call"):
        decode_step(encode_step(0, "eval_evil_code", (), {}))
    # Unknown wire version.
    with pytest.raises(StepWireError, match="version"):
        decode_step(msgpack.packb(
            {"v": 99, "seq": 0, "name": "unified_step", "args": [], "kwargs": {}}
        ))
    # Extra field smuggled in.
    with pytest.raises(StepWireError, match="fields"):
        decode_step(msgpack.packb(
            {"v": 1, "seq": 0, "name": "unified_step", "args": [], "kwargs": {},
             "__reduce__": "rm -rf"}
        ))
    # Unknown value tag.
    with pytest.raises(StepWireError, match="unknown wire tag"):
        decode_step(msgpack.packb(
            {"v": 1, "seq": 0, "name": "unified_step",
             "args": [{"__obj__": "x"}], "kwargs": {}}
        ))
    # Forbidden ndarray dtype (object arrays were pickle's attack surface).
    with pytest.raises(StepWireError, match="dtype"):
        decode_step(msgpack.packb(
            {"v": 1, "seq": 0, "name": "unified_step",
             "args": [{"__nd__": ["|O", [1], b"x"]}], "kwargs": {}}
        ))
    # Malformed ndarray payloads wrap into StepWireError too (reshape /
    # frombuffer / arity errors must not escape as raw ValueError).
    for bad in (
        {"__nd__": ["<f8", ["x"], b""]},          # non-int shape
        {"__nd__": ["<f8", [100], b"\x00" * 8]},  # shape/buffer mismatch
        {"__nd__": ["<f8", [1]]},                 # wrong arity
        {"__nd__": ["not-a-dtype", [1], b"\x00" * 8]},
    ):
        with pytest.raises(StepWireError):
            decode_step(msgpack.packb(
                {"v": 1, "seq": 0, "name": "unified_step", "args": [bad],
                 "kwargs": {}}
            ))
    # Not even msgpack.
    with pytest.raises(StepWireError):
        decode_step(b"\x80\x04\x95pickle-bytes")
    # Leader side refuses unshippable values instead of pickling them.
    with pytest.raises(TypeError):
        encode_step(0, "unified_step", (object(),), {})


def test_stepcast_has_no_pickle():
    """Acceptance tripwire: `grep -rn pickle parallel/stepcast.py` must
    stay empty — the step plane must never regress to object
    deserialization."""
    import dynamo_tpu.parallel.stepcast as sc

    source = open(sc.__file__.rstrip("c")).read()
    assert "pickle" not in source


class _RecordingRunner:
    """Follower-side runner stub: records replayed calls."""

    def __init__(self):
        self.calls = []

    def __getattr__(self, name):
        def call(*args, **kwargs):
            from dynamo_tpu.engine.runner import UnifiedOut

            self.calls.append((name, args, kwargs))
            if name == "unified_step":  # the follower keeps `.last`
                return UnifiedOut(last=np.zeros(4, np.int32))
            return None

        return call


_ONE_TOKEN_STEP = [([1], [], 0, (0.0, 0, 1.0))]


async def test_stepcast_leader_follower_typed_wire():
    from dynamo_tpu.parallel.stepcast import StepLeader, follower_serve
    from dynamo_tpu.runtime.distributed import DistributedRuntime

    drt = await DistributedRuntime.in_process()
    try:
        runner = _RecordingRunner()
        leader_runner = _RecordingRunner()
        follower = asyncio.ensure_future(
            follower_serve(runner, drt, namespace="t", rank=1,
                           heartbeat_s=0.05)
        )
        leader = await asyncio.wait_for(
            StepLeader(
                leader_runner, drt, namespace="t", num_followers=1,
                heartbeat_s=0.05, liveness_timeout_s=5.0,
            ).start(),
            timeout=5.0,
        )
        toks = np.arange(5, dtype=np.int32)
        leader.unified_step([(toks, [1, 2], 0, (0.0, 0, 1.0))])
        leader.scatter_many([1, 2], np.zeros((2, 2), np.int32))
        leader.attn = "passthrough-not-replayed"  # attribute proxying
        await asyncio.sleep(0.2)
        await leader.stop()
        assert await asyncio.wait_for(follower, 5.0) == 2
        assert [c[0] for c in runner.calls] == ["unified_step", "scatter_many"]
        (lane,) = runner.calls[0][1][0]
        np.testing.assert_array_equal(lane[0], toks)
        assert lane[3] == (0.0, 0, 1.0)
        # Leader executed locally too, and non-replayed attrs passed through.
        assert [c[0] for c in leader_runner.calls] == [
            "unified_step", "scatter_many"
        ]
        assert leader_runner.attn == "passthrough-not-replayed"
    finally:
        await drt.shutdown()


async def test_stepcast_unified_feed_ships_sentinel_not_device_array():
    """unified_step's feed tokens are the previous dispatch's DEVICE
    array — the wire must carry the FEED_PREV sentinel instead (a
    per-dispatch device→host sync would defeat the pipelined feed), and
    the follower must substitute ITS OWN previous unified output."""
    from dynamo_tpu.engine.runner import UnifiedOut
    from dynamo_tpu.parallel.stepcast import (
        FEED_PREV,
        StepLeader,
        follower_serve,
    )
    from dynamo_tpu.runtime.distributed import DistributedRuntime

    class _NeverEncoded:
        """Stand-in for a device array: the wire encoder would force it
        via __array__ — the test fails loudly if that ever happens."""

        def __array__(self, *a, **k):  # pragma: no cover - failure path
            raise AssertionError("device feed array reached the wire")

    class _UnifiedRunner:
        def __init__(self):
            self.calls = []

        def unified_step(self, lanes, feed=None, **kw):
            self.calls.append((lanes, feed, kw))
            return UnifiedOut(
                last=np.full(4, 7 + len(self.calls), np.int32)
            )

    drt = await DistributedRuntime.in_process()
    try:
        runner = _UnifiedRunner()
        leader_runner = _UnifiedRunner()
        follower = asyncio.ensure_future(
            follower_serve(runner, drt, namespace="u", rank=1,
                           heartbeat_s=0.05)
        )
        leader = await asyncio.wait_for(
            StepLeader(
                leader_runner, drt, namespace="u", num_followers=1,
                heartbeat_s=0.05, liveness_timeout_s=5.0,
            ).start(),
            timeout=5.0,
        )
        lanes = [([3], [1], 0, (0.0, 0, 1.0))]
        # First dispatch: no lane feeds (use_prev all False).
        leader.unified_step(
            lanes,
            feed=(_NeverEncoded(), np.zeros(4, np.int32),
                  np.zeros(4, bool)),
        )
        # Second dispatch: a feeding lane — the follower must substitute
        # its own previous output, never see the leader's device array.
        leader.unified_step(
            lanes,
            feed=(_NeverEncoded(), np.zeros(4, np.int32),
                  np.array([True, False, False, False])),
        )
        await asyncio.sleep(0.2)
        await leader.stop()
        assert await asyncio.wait_for(follower, 5.0) == 2
        assert len(runner.calls) == 2
        for _lanes, feed, _kw in runner.calls:
            assert not isinstance(feed[0], str) or feed[0] != FEED_PREV
        # The follower's second call fed ITS OWN first output.
        np.testing.assert_array_equal(
            np.asarray(runner.calls[1][1][0]), np.full(4, 8, np.int32)
        )
        # The leader's local calls kept the REAL feed object.
        assert isinstance(leader_runner.calls[0][1][0], _NeverEncoded)
    finally:
        await drt.shutdown()


async def test_stepcast_dropped_step_fails_loudly():
    """An injected broadcast drop leaves a seq gap: the follower must fail
    LOUDLY (collectives would deadlock silently otherwise)."""
    from dynamo_tpu.parallel.stepcast import StepLeader, follower_serve
    from dynamo_tpu.runtime.distributed import DistributedRuntime

    drt = await DistributedRuntime.in_process()
    try:
        escalations: list = []
        follower = asyncio.ensure_future(
            follower_serve(_RecordingRunner(), drt, namespace="d", rank=1,
                           heartbeat_s=0.05)
        )
        leader = await asyncio.wait_for(
            StepLeader(
                _RecordingRunner(), drt, namespace="d", num_followers=1,
                heartbeat_s=0.05, liveness_timeout_s=10.0,
                on_follower_lost=escalations.append,
            ).start(),
            timeout=5.0,
        )
        leader.unified_step(_ONE_TOKEN_STEP)
        FAULTS.arm("stepcast.broadcast", "drop", times=1)
        leader.unified_step([([1], [0], 1, (0.0, 0, 1.0))])  # dropped
        leader.gather_block(3)  # arrives with seq 2 — gap!
        # Prong 1: the follower's gap check fires on the next frame.
        with pytest.raises(RuntimeError, match="lost step"):
            await asyncio.wait_for(follower, 5.0)
        # Prong 2: the leader's watchdog escalates the drop itself —
        # vital on a real mesh, where the engine thread wedges in the
        # dropped step's collective and never sends a next frame.
        t0 = time.monotonic()
        while not escalations and time.monotonic() - t0 < 3.0:
            await asyncio.sleep(0.02)
        assert escalations, "watchdog never escalated the dropped step"
        assert leader._dropped_steps == [1]
        assert FAULTS.injected["stepcast.broadcast"] == 1
        await leader.stop()
    finally:
        await drt.shutdown()


async def test_stepcast_replay_fault_kills_follower_loudly():
    """An injected fault at the follower's replay seam (the step frame
    failing to apply — the SPMD twin diverging) must kill follower_serve
    LOUDLY: a follower that swallows a replay error and keeps acking
    heartbeats would desync the mesh while looking alive."""
    from dynamo_tpu.parallel.stepcast import StepLeader, follower_serve
    from dynamo_tpu.runtime.distributed import DistributedRuntime

    drt = await DistributedRuntime.in_process()
    try:
        follower = asyncio.ensure_future(
            follower_serve(_RecordingRunner(), drt, namespace="r", rank=1,
                           heartbeat_s=0.05)
        )
        leader = await asyncio.wait_for(
            StepLeader(
                _RecordingRunner(), drt, namespace="r", num_followers=1,
                heartbeat_s=0.05, liveness_timeout_s=10.0,
            ).start(),
            timeout=5.0,
        )
        FAULTS.arm("stepcast.replay", "raise", times=1)
        leader.unified_step(_ONE_TOKEN_STEP)
        with pytest.raises(FaultError):
            await asyncio.wait_for(follower, 5.0)
        assert FAULTS.injected["stepcast.replay"] == 1
        await leader.stop()
    finally:
        await drt.shutdown()


async def test_stepcast_leader_detects_dead_follower():
    """Follower death mid-serve: the leader's watchdog must flag it within
    the liveness timeout — never hang waiting for a heartbeat."""
    from dynamo_tpu.parallel.stepcast import StepLeader, follower_serve
    from dynamo_tpu.runtime.distributed import DistributedRuntime

    drt = await DistributedRuntime.in_process()
    try:
        lost: list = []
        follower = asyncio.ensure_future(
            follower_serve(_RecordingRunner(), drt, namespace="w", rank=1,
                           heartbeat_s=0.05)
        )
        leader = await asyncio.wait_for(
            StepLeader(
                _RecordingRunner(), drt, namespace="w", num_followers=1,
                heartbeat_s=0.05, liveness_timeout_s=0.3,
                on_follower_lost=lost.append,
            ).start(),
            timeout=5.0,
        )
        leader.unified_step(_ONE_TOKEN_STEP)
        await asyncio.sleep(0.2)
        assert not lost  # heartbeats flowing — no false positive
        follower.cancel()  # the "process died" moment
        try:
            await follower
        except asyncio.CancelledError:
            pass
        t0 = time.monotonic()
        while not lost and time.monotonic() - t0 < 3.0:
            await asyncio.sleep(0.02)
        assert lost == [["1"]], "watchdog never flagged the dead follower"
        assert leader.followers_lost == ["1"]
        await leader.stop()
    finally:
        await drt.shutdown()


# ---------------------------------------------------------------------------
# Bus / control plane / response plane
# ---------------------------------------------------------------------------


async def test_bus_publish_drop_counted_no_hang():
    from dynamo_tpu.runtime.transports.bus import InProcBus

    bus = InProcBus()
    sub = await bus.subscribe("subj")
    FAULTS.arm("bus.publish", "drop", times=1)
    await bus.publish("subj", b"lost")
    await bus.publish("subj", b"kept")
    got = await asyncio.wait_for(sub.__anext__(), 2.0)
    assert got == b"kept"
    assert FAULTS.injected["bus.publish"] == 1
    sub.close()


async def test_bus_broadcast_drop_loses_whole_fanout_counted():
    """An injected broadcast drop is one lost EVENT, not one lost
    delivery: no subscriber sees the dropped frame (the events plane is
    fire-and-forget — KV events / metrics — so consumers must tolerate
    gaps), and the loss is counted exactly once."""
    from dynamo_tpu.runtime.transports.bus import InProcBus

    bus = InProcBus()
    sub_a = await bus.subscribe("events")
    sub_b = await bus.subscribe("events")
    FAULTS.arm("bus.broadcast", "drop", times=1)
    await bus.broadcast("events", b"lost")
    await bus.broadcast("events", b"kept")
    for sub in (sub_a, sub_b):
        got = await asyncio.wait_for(sub.__anext__(), 2.0)
        assert got == b"kept"
        sub.close()
    assert FAULTS.injected["bus.broadcast"] == 1


async def test_control_keepalive_partition_escalates_to_shutdown():
    """Injected keepalive partition ⇒ the lease cannot renew ⇒ the
    CriticalTask escalates to runtime shutdown (the lease-death ⇒
    shutdown coupling) — within a bounded wait, not a silent wedge."""
    from dynamo_tpu.runtime.distributed import DistributedRuntime
    from dynamo_tpu.runtime.transports.control_plane import ControlPlaneServer

    server = await ControlPlaneServer().start()
    drt = await DistributedRuntime.connect(server.address, lease_ttl_s=0.3)
    try:
        assert not drt.runtime.is_shutdown
        FAULTS.arm("control.keepalive", "partition")
        t0 = time.monotonic()
        while not drt.runtime.is_shutdown and time.monotonic() - t0 < 5.0:
            await asyncio.sleep(0.05)
        assert drt.runtime.is_shutdown, "keepalive death never escalated"
        assert FAULTS.injected["control.keepalive"] >= 1
    finally:
        FAULTS.clear()
        await drt.shutdown()
        await server.stop()


async def test_control_connect_retries_through_refusal():
    """The first dial hitting an injected connection fault must retry
    under the shared policy, not kill the worker (k8s rollout ordering)."""
    from dynamo_tpu.runtime.distributed import DistributedRuntime
    from dynamo_tpu.runtime.transports.control_plane import ControlPlaneServer

    server = await ControlPlaneServer().start()
    base = RETRIES.snapshot().get("control.connect", 0)
    # First RPC (the client's auth-free first _call is grant_lease; the
    # connect seam wraps socket open + first calls) — inject one failure
    # at the control.call seam via partition-then-clear is racy; instead
    # arm a single raise on the call seam and rely on connect's retry.
    FAULTS.arm("control.call", "raise", times=1)
    drt = await DistributedRuntime.connect(server.address, lease_ttl_s=5.0)
    try:
        assert RETRIES.snapshot().get("control.connect", 0) > base
        assert await drt.store.get("nope") is None  # plane usable after
    finally:
        await drt.shutdown()
        await server.stop()


async def test_tcp_respond_fault_bounded_and_recovers():
    """A response-plane failure mid-stream surfaces as a bounded TYPED
    transport error (WorkerDiedError — the failover-eligible class,
    never a hang, never an untyped RuntimeError); the NEXT request
    succeeds on a fresh stream even though the mark-dead fast path
    evicted the instance (the store refresh re-resolves it)."""
    from dynamo_tpu.llm.protocols.common import WorkerDiedError
    from dynamo_tpu.runtime.distributed import DistributedRuntime
    from dynamo_tpu.runtime.egress import PushRouter
    from dynamo_tpu.runtime.engine import Context, EngineAdapter

    async def engine(ctx):
        for tok in ctx.payload["tokens"]:
            yield {"token": tok}

    drt = await DistributedRuntime.in_process()
    try:
        ep = drt.namespace("chaos").component("tcp").endpoint("generate")
        await ep.serve(EngineAdapter(engine))
        router = await PushRouter.create(drt, ep.id)

        injected_before = FAULTS.injected.get("tcp.respond", 0)
        FAULTS.arm("tcp.respond", "raise", times=1)

        async def collect():
            out = []
            async for item in router.generate(Context({"tokens": [1, 2]})):
                out.append(item["token"])
            return out

        with pytest.raises(WorkerDiedError, match="injected fault"):
            await asyncio.wait_for(collect(), 5.0)
        assert await asyncio.wait_for(collect(), 5.0) == [1, 2]
        assert FAULTS.injected["tcp.respond"] == injected_before + 1
    finally:
        await drt.shutdown()


# ---------------------------------------------------------------------------
# KVBM offload pump
# ---------------------------------------------------------------------------


async def test_kvbm_pump_fault_drops_offer_then_recovers():
    from dynamo_tpu.block_manager import (
        KvbmConfig,
        KvBlockManager,
        KvLayoutConfig,
    )

    layout = KvLayoutConfig(
        num_layers=2, page_size=16, num_kv_heads=2, head_dim=16,
        dtype="float32",
    )
    mgr = await KvBlockManager(
        KvbmConfig(host_blocks=4, layout=layout)
    ).start()
    try:
        data = np.full((layout.block_elems,), 3.0, np.float32)
        FAULTS.arm("kvbm.pump", "raise", times=1)
        mgr.offer(0xA1, None, tuple(range(16)), data)
        await asyncio.wait_for(mgr.drain_offers(5.0), 6.0)
        # The faulted batch was dropped (offer is opportunistic cache
        # population — recovery is recompute, never request loss)...
        assert mgr.host_pool.get_by_hash(0xA1) is None
        assert FAULTS.injected["kvbm.pump"] == 1
        # ...and the hash was un-marked, so a re-offer lands cleanly.
        mgr.offer(0xA1, None, tuple(range(16)), data)
        await asyncio.wait_for(mgr.drain_offers(5.0), 6.0)
        assert mgr.host_pool.get_by_hash(0xA1) is not None
        # drop action: the batch is silently lost but un-marked too.
        FAULTS.arm("kvbm.pump", "drop", times=1)
        mgr.offer(0xA2, None, tuple(range(16, 32)), data)
        await asyncio.wait_for(mgr.drain_offers(5.0), 6.0)
        assert mgr.host_pool.get_by_hash(0xA2) is None
        mgr.offer(0xA2, None, tuple(range(16, 32)), data)
        await asyncio.wait_for(mgr.drain_offers(5.0), 6.0)
        assert mgr.host_pool.get_by_hash(0xA2) is not None
    finally:
        await mgr.stop()


async def test_kvbm_pump_materializes_only_kept_rows():
    """Satellite (ADVICE r05): a mostly-duplicate offer batch must
    row-select BEFORE host materialization — only dedup-kept rows pay."""
    from dynamo_tpu.block_manager import (
        KvbmConfig,
        KvBlockManager,
        KvLayoutConfig,
    )

    layout = KvLayoutConfig(
        num_layers=2, page_size=16, num_kv_heads=2, head_dim=16,
        dtype="float32",
    )
    mgr = await KvBlockManager(
        KvbmConfig(host_blocks=8, layout=layout)
    ).start()
    try:
        batch = np.stack(
            [np.full((layout.block_elems,), float(i)) for i in range(4)]
        ).astype(np.float32)

        class SpyArray(np.ndarray):
            """ndarray subclass recording the row-select index, proving
            the host path gathers kept rows BEFORE any full-batch copy."""

            selected = None

            def __getitem__(self, idx):
                if isinstance(idx, np.ndarray):
                    SpyArray.selected = np.asarray(idx)
                return super().__getitem__(idx)

        entries = [
            (0xB0, None, tuple(range(16))),
            (0xB1, 0xB0, tuple(range(16, 32))),
            (0xB2, 0xB1, tuple(range(32, 48))),
            (0xB3, 0xB2, tuple(range(48, 64))),
        ]
        # Pre-store rows 0 and 2 so the batch dedups down to rows 1, 3.
        mgr.offer_batch(entries[:1], batch[:1])
        await asyncio.wait_for(mgr.drain_offers(5.0), 6.0)
        mgr.offer_batch(entries[2:3], batch[2:3])
        await asyncio.wait_for(mgr.drain_offers(5.0), 6.0)

        spy = batch.view(SpyArray)
        mgr.offer_batch(entries, spy)
        await asyncio.wait_for(mgr.drain_offers(5.0), 6.0)
        assert SpyArray.selected is not None, "full batch materialized"
        assert list(SpyArray.selected) == [1, 3]
        for h in (0xB0, 0xB1, 0xB2, 0xB3):
            assert mgr.host_pool.get_by_hash(h) is not None
        # Byte fidelity for the row-selected stores.
        b3 = mgr.host_pool.get_by_hash(0xB3)
        got = mgr.host_pool.storage.read_block(b3.idx)
        np.testing.assert_array_equal(np.asarray(got), batch[3])
    finally:
        await mgr.stop()


# ---------------------------------------------------------------------------
# Disagg transfer plane
# ---------------------------------------------------------------------------


async def test_disagg_transfer_fault_retries_and_lands():
    """One injected send failure: the shared retry policy resends on a
    fresh connection and the blocks land byte-identical."""
    from dynamo_tpu.disagg.transfer import KvReceiver, KvSender

    landed = {}
    finished = []
    recv = await KvReceiver(
        on_block=lambda r, i, d: landed.setdefault((r, i), np.array(d)),
        on_finish=lambda r, t: finished.append((r, t)),
    ).start()
    sender = KvSender()
    base = RETRIES.snapshot().get("disagg.send", 0)
    block = np.arange(8, dtype=np.float32).reshape(2, 4)
    FAULTS.arm("disagg.send", "raise", times=1)
    await asyncio.wait_for(
        sender.send_blocks(recv.address, "r1", [block], 42, auth=recv.auth),
        5.0,
    )
    assert finished == [("r1", 42)]
    np.testing.assert_array_equal(landed[("r1", 0)], block)
    assert RETRIES.snapshot().get("disagg.send", 0) == base + 1
    assert FAULTS.injected["disagg.send"] == 1
    await sender.close()
    await recv.stop()


async def test_disagg_transfer_receiver_death_exhausts_retries():
    """The receiver dying mid-transfer (injected at the landing seam,
    partition) must exhaust the bounded retry budget and raise — the
    caller's requeue/degradation path takes over; never an infinite loop."""
    from dynamo_tpu.disagg.transfer import KvReceiver, KvSender

    recv = await KvReceiver(
        on_block=lambda r, i, d: None, on_finish=lambda r, t: None
    ).start()
    sender = KvSender()
    FAULTS.arm("disagg.recv", "partition")
    block = np.ones((2, 4), np.float32)
    with pytest.raises((ConnectionError, asyncio.IncompleteReadError, OSError)):
        await asyncio.wait_for(
            sender.send_blocks(
                recv.address, "r2", [block], 7, auth=recv.auth
            ),
            10.0,
        )
    assert FAULTS.injected["disagg.recv"] >= 1
    await sender.close()
    FAULTS.clear()
    await recv.stop()


async def test_remote_prefill_transfer_death_degrades_to_local():
    """THE disagg degradation invariant (reference: disagg_serving.md
    degradation-to-local-prefill): the KV push plane dies entirely ⇒ the
    decode side times out the remote wait and completes the request by
    LOCAL recompute — no request loss, degraded counter incremented."""
    from dynamo_tpu.disagg import (
        DecodeOperator,
        DisaggConfig,
        DisaggRouter,
        PrefillQueue,
        PrefillWorker,
    )
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.llm.protocols.common import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )
    from dynamo_tpu.mocker import MockerConfig, MockerEngine
    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.runtime.distributed import DistributedRuntime
    from dynamo_tpu.runtime.engine import Context

    def ecfg():
        return EngineConfig(
            model=ModelConfig.tiny_test(),
            num_blocks=32,
            max_num_seqs=2,
            max_model_len=128,
            dtype="float32",
            remote_kv_timeout_s=0.5,  # fast chaos loop; default is 30 s
        )

    drt = await DistributedRuntime.in_process()
    queue = PrefillQueue(drt, "chaos")
    dis = DisaggRouter.__new__(DisaggRouter)
    dis.cfg = DisaggConfig(max_local_prefill_length=16, max_prefill_queue_size=8)

    decode = MockerEngine(ecfg(), MockerConfig(seed=7))
    await decode.start()
    prefill = MockerEngine(ecfg(), MockerConfig(seed=7))
    await prefill.start()
    op = await DecodeOperator(decode, queue, dis, transport="tcp").start()
    pw = PrefillWorker(prefill, queue).start()
    try:
        # The entire KV push plane is down (partition at the send seam).
        FAULTS.arm("disagg.send", "partition")
        req = PreprocessedRequest(
            token_ids=list(range(40)),  # long ⇒ routed remote
            sampling=SamplingOptions(temperature=0.0),
            stop=StopConditions(max_tokens=6, ignore_eos=True),
        )
        toks = []

        async def run():
            async for item in op.generate(Context(req.to_wire())):
                toks.extend(item["token_ids"])

        await asyncio.wait_for(run(), 30.0)  # bounded: no hang
        assert len(toks) == 6, "request lost under transfer death"
        assert op.remote_count == 1  # it WAS routed remote...
        assert decode.degraded_requests == 1  # ...and degraded to local
        assert decode.readiness()["degraded_requests_total"] == 1
        assert FAULTS.injected["disagg.send"] >= 1

        # Second scenario: ONE silently lost block frame (drop at the
        # landing seam). The finish notification arrives over a hole —
        # activation's completeness check must refuse to decode over
        # stale KV and degrade to recompute instead (no token
        # corruption, no hang, still no request loss).
        FAULTS.clear()
        # Run 1's bounded requeue attempts may still be in flight; once
        # the partition clears, a late attempt SUCCEEDS and its frames
        # would consume the drop budget below. Wait for the queue AND the
        # worker to go quiet (depth 0, served count stable over a window
        # longer than the retry backoff) before arming.
        stable, t0 = 0, time.monotonic()
        while stable < 2 and time.monotonic() - t0 < 15.0:
            before = pw.served
            await asyncio.sleep(0.4)
            if await queue.depth() == 0 and pw.served == before:
                stable += 1
            else:
                stable = 0
        recv_base = FAULTS.snapshot().get("disagg.recv", 0)
        FAULTS.arm("disagg.recv", "drop", times=1)
        req2 = PreprocessedRequest(
            token_ids=list(range(100, 140)),  # fresh prompt: no prefix
            sampling=SamplingOptions(temperature=0.0),  # hit keeps it
            stop=StopConditions(max_tokens=6, ignore_eos=True),  # remote
        )
        toks2: list = []

        async def run2():
            async for item in op.generate(Context(req2.to_wire())):
                toks2.extend(item["token_ids"])

        await asyncio.wait_for(run2(), 30.0)
        assert len(toks2) == 6, "request lost under single-frame loss"
        assert op.remote_count == 2
        assert decode.degraded_requests == 2
        assert FAULTS.snapshot().get("disagg.recv", 0) == recv_base + 1
    finally:
        FAULTS.clear()
        await pw.stop()
        await op.stop()
        await decode.stop()
        await prefill.stop()
        await drt.shutdown()


# ---------------------------------------------------------------------------
# Lease expiry end-to-end (satellite)
# ---------------------------------------------------------------------------


async def test_lease_expiry_end_to_end():
    """Worker lease lapses ⇒ store deregisters ⇒ router stops routing to
    it ⇒ the request already streaming COMPLETES (the response plane is a
    direct TCP stream, independent of discovery)."""
    from dynamo_tpu.runtime.distributed import DistributedRuntime
    from dynamo_tpu.runtime.egress import PushRouter
    from dynamo_tpu.runtime.engine import Context, EngineAdapter
    from dynamo_tpu.runtime.runtime import Runtime

    drt_front = await DistributedRuntime.in_process()
    drt_worker = await DistributedRuntime.in_process(
        runtime=Runtime(), store=drt_front.store, bus=drt_front.bus
    )
    try:
        async def slow_engine(ctx):
            for i in range(5):
                yield {"i": i}
                await asyncio.sleep(0.15)

        ep = drt_worker.namespace("chaos").component("lease").endpoint("gen")
        await ep.serve(EngineAdapter(slow_engine))
        router = await PushRouter.create(drt_front, ep.id)
        assert len(await router.client.wait_for_instances()) == 1

        got = []

        async def consume():
            async for item in router.generate(Context({})):
                got.append(item["i"])

        stream = asyncio.ensure_future(consume())
        await asyncio.sleep(0.2)  # stream in flight

        # The lease lapses: keepalive dies and the TTL runs out.
        drt_worker._keepalive.cancel()
        lease = drt_front.store._leases[drt_worker.primary_lease_id]
        lease.ttl_s = 0.1
        lease.expires_at = time.monotonic() + 0.1

        t0 = time.monotonic()
        while router.client.instances() and time.monotonic() - t0 < 3.0:
            await asyncio.sleep(0.02)
        assert router.client.instances() == [], "router kept a dead worker"

        # New requests have nowhere to go...
        with pytest.raises(asyncio.TimeoutError):
            await router.client.wait_for_instances(timeout_s=0.2)
        # ...but the in-flight stream completes untouched.
        await asyncio.wait_for(stream, 5.0)
        assert got == [0, 1, 2, 3, 4]
    finally:
        await drt_worker.shutdown()
        await drt_front.shutdown()


# ---------------------------------------------------------------------------
# Lease keepalive flap hardening (satellite)
# ---------------------------------------------------------------------------


async def test_keepalive_flap_does_not_deregister():
    """A TRANSIENT control-plane blip shorter than the lease TTL must NOT
    take a healthy worker down: the keepalive retries in place (within
    the TTL budget) and the lease-bound instance key survives. Regression
    for the old behavior where ONE raised keepalive escalated straight to
    runtime shutdown even though the lease had 2/3 of its TTL left."""
    from dynamo_tpu.runtime.distributed import DistributedRuntime
    from dynamo_tpu.runtime.transports.control_plane import ControlPlaneServer

    server = await ControlPlaneServer().start()
    drt = await DistributedRuntime.connect(server.address, lease_ttl_s=1.0)
    try:
        await drt.store.put(
            "flap/instance", b"alive", lease_id=drt.primary_lease_id
        )
        base = RETRIES.snapshot().get("control.keepalive", 0)
        injected_base = FAULTS.snapshot().get("control.keepalive", 0)
        # Two consecutive keepalive failures — a partition far shorter
        # than the TTL (each retried within ~TTL/30 of backoff).
        FAULTS.arm("control.keepalive", "raise", times=2)
        await asyncio.sleep(2.2)  # several keepalive periods
        assert not drt.runtime.is_shutdown, (
            "transient keepalive flap deregistered a healthy worker"
        )
        assert await drt.store.get("flap/instance") == b"alive"
        assert (
            FAULTS.snapshot().get("control.keepalive", 0) == injected_base + 2
        )
        assert RETRIES.snapshot().get("control.keepalive", 0) > base
    finally:
        FAULTS.clear()
        await drt.shutdown()
        await server.stop()


# ---------------------------------------------------------------------------
# Graceful drain (tentpole e2e) + deadline/queue-full chaos
# ---------------------------------------------------------------------------


async def test_drain_verb_end_to_end():
    """Control-plane drain verb on a worker with an in-flight request:
    the in-flight stream COMPLETES, readiness flips to draining, new
    requests are refused with a typed ShedError, the instance key is
    deleted (router eviction), and the engine fully drains."""
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.llm.protocols.common import (
        PreprocessedRequest,
        SamplingOptions,
        ShedError,
        StopConditions,
    )
    from dynamo_tpu.mocker import MockerConfig, MockerEngine
    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.runtime.distributed import DistributedRuntime
    from dynamo_tpu.runtime.drain import request_drain, watch_drain
    from dynamo_tpu.runtime.egress import PushRouter
    from dynamo_tpu.runtime.engine import Context
    from dynamo_tpu.runtime.runtime import Runtime
    from dynamo_tpu.utils.task import spawn_tracked

    drt_front = await DistributedRuntime.in_process()
    drt_worker = await DistributedRuntime.in_process(
        runtime=Runtime(), store=drt_front.store, bus=drt_front.bus
    )
    engine = MockerEngine(
        EngineConfig(
            model=ModelConfig.tiny_test(), num_blocks=64, max_num_seqs=4,
            max_model_len=256, dtype="float32",
        ),
        MockerConfig(decode_time_per_step_us=15000.0),
    )
    await engine.start()
    try:
        ep = drt_worker.namespace("chaos").component("drain").endpoint("gen")
        served = await ep.serve(engine)
        drain_done = asyncio.Event()

        def on_drain():
            async def run():
                # Canonical order (cli._graceful_drain): refuse new work,
                # deregister FIRST for immediate eviction, then drain.
                engine.begin_drain()
                assert await served.drain(30.0)
                assert await engine.wait_drained(10.0)
                drain_done.set()

            spawn_tracked(run(), name="test-drain")

        await watch_drain(drt_worker, "chaos", "drain", on_drain)
        router = await PushRouter.create(drt_front, ep.id)
        assert len(await router.client.wait_for_instances()) == 1

        req = PreprocessedRequest(
            token_ids=list(range(16)),
            sampling=SamplingOptions(temperature=0.0),
            stop=StopConditions(max_tokens=24, ignore_eos=True),
        )
        got: list = []

        async def consume():
            async for item in router.generate(Context(req.to_wire())):
                got.extend(item.get("token_ids") or [])

        stream = asyncio.ensure_future(consume())
        await asyncio.sleep(0.3)  # request genuinely in flight
        assert got and len(got) < 24

        # The control-plane verb (fired from the FRONTEND runtime).
        await request_drain(drt_front, "chaos", "drain")
        await asyncio.wait_for(drain_done.wait(), 30.0)

        # In-flight stream completed in full — nothing dropped.
        await asyncio.wait_for(stream, 10.0)
        assert len(got) == 24

        # Readiness flipped and new work is refused with a typed error.
        assert engine.readiness()["state"] == "draining"
        with pytest.raises(ShedError):
            async for _ in engine.generate(Context(req.to_wire())):
                pass

        # Router evicted the instance (store key deleted by drain).
        t0 = time.monotonic()
        while router.client.instances() and time.monotonic() - t0 < 3.0:
            await asyncio.sleep(0.02)
        assert router.client.instances() == []
    finally:
        await engine.stop()
        await drt_worker.shutdown()
        await drt_front.shutdown()


async def test_sigterm_drain_end_to_end():
    """SIGTERM on a worker PROCESS with an in-flight request: the stream
    completes, the instance deregisters, and the process exits cleanly
    after printing its drain verdict — the loss-free rolling restart."""
    import os
    import signal as _signal
    import sys

    from dynamo_tpu.runtime.component import EndpointId
    from dynamo_tpu.runtime.distributed import DistributedRuntime
    from dynamo_tpu.runtime.egress import PushRouter
    from dynamo_tpu.runtime.engine import Context
    from dynamo_tpu.runtime.transports.control_plane import ControlPlaneServer
    from dynamo_tpu.llm.protocols.common import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    worker_py = os.path.join(repo, "tests", "procs", "drain_worker.py")
    server = await ControlPlaneServer().start()
    proc = await asyncio.create_subprocess_exec(
        sys.executable, worker_py, "--addr", server.address,
        stdout=asyncio.subprocess.PIPE,
        stderr=asyncio.subprocess.STDOUT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    try:
        # Wait for READY.
        while True:
            line = await asyncio.wait_for(proc.stdout.readline(), 60.0)
            assert line, "worker died before READY"
            if line.startswith(b"READY"):
                break
        drt = await DistributedRuntime.connect(server.address)
        try:
            router = await PushRouter.create(
                drt, EndpointId("chaos", "drainw", "generate")
            )
            assert len(await router.client.wait_for_instances(10.0)) == 1
            req = PreprocessedRequest(
                token_ids=list(range(16)),
                sampling=SamplingOptions(temperature=0.0),
                stop=StopConditions(max_tokens=24, ignore_eos=True),
            )
            got: list = []

            async def consume():
                async for item in router.generate(Context(req.to_wire())):
                    got.extend(item.get("token_ids") or [])

            stream = asyncio.ensure_future(consume())
            await asyncio.sleep(0.4)
            assert got and len(got) < 24  # mid-flight

            proc.send_signal(_signal.SIGTERM)
            await asyncio.wait_for(stream, 30.0)
            assert len(got) == 24, "SIGTERM dropped an in-flight request"

            # Instance deregistered (drain deletes the key; lease revoke
            # backs it up), so the router has nowhere to send new work.
            t0 = time.monotonic()
            while (
                router.client.instances() and time.monotonic() - t0 < 10.0
            ):
                await asyncio.sleep(0.05)
            assert router.client.instances() == []
        finally:
            await drt.shutdown()
        out, _ = await asyncio.wait_for(proc.communicate(), 30.0)
        assert b"DRAINED True" in out, out
        assert proc.returncode == 0
    finally:
        if proc.returncode is None:
            proc.kill()
            await proc.wait()
        await server.stop()


async def test_deadline_expiry_under_injected_transfer_delay():
    """Chaos: the disagg KV push plane is slow (injected delay past the
    request's deadline). The decode side's remote-wait sweep cancels the
    request with a typed DEADLINE finish — bounded, counted, no hang and
    no decode over late-arriving KV."""
    from dynamo_tpu.disagg import (
        DecodeOperator,
        DisaggConfig,
        DisaggRouter,
        PrefillQueue,
        PrefillWorker,
    )
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.llm.protocols.common import (
        FinishReason,
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )
    from dynamo_tpu.mocker import MockerConfig, MockerEngine
    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.runtime.distributed import DistributedRuntime
    from dynamo_tpu.runtime.engine import Context
    from dynamo_tpu.utils.deadline import OVERLOAD, Deadline

    def ecfg():
        return EngineConfig(
            model=ModelConfig.tiny_test(), num_blocks=32, max_num_seqs=2,
            max_model_len=128, dtype="float32", remote_kv_timeout_s=30.0,
        )

    drt = await DistributedRuntime.in_process()
    queue = PrefillQueue(drt, "chaos-deadline")
    dis = DisaggRouter.__new__(DisaggRouter)
    dis.cfg = DisaggConfig(
        max_local_prefill_length=16, max_prefill_queue_size=8
    )
    decode = MockerEngine(ecfg(), MockerConfig(seed=7))
    await decode.start()
    prefill = MockerEngine(ecfg(), MockerConfig(seed=7))
    await prefill.start()
    op = await DecodeOperator(decode, queue, dis, transport="tcp").start()
    pw = PrefillWorker(prefill, queue).start()
    try:
        base = OVERLOAD.deadline_total
        # Every KV send stalls 1.2 s — well past the 0.4 s deadline.
        FAULTS.arm("disagg.send", "delay", delay_s=1.2, times=None)
        req = PreprocessedRequest(
            token_ids=list(range(40)),  # long => routed remote
            sampling=SamplingOptions(temperature=0.0),
            stop=StopConditions(max_tokens=6, ignore_eos=True),
            deadline=Deadline.after(0.4),
        )
        toks: list = []
        finish = None

        async def run():
            nonlocal finish
            async for item in op.generate(Context(req.to_wire())):
                toks.extend(item["token_ids"])
                if item.get("finish_reason"):
                    finish = item["finish_reason"]

        await asyncio.wait_for(run(), 30.0)  # bounded — never a hang
        assert op.remote_count == 1
        assert toks == []
        assert finish == FinishReason.DEADLINE.value
        assert OVERLOAD.deadline_total > base
    finally:
        FAULTS.clear()
        await pw.stop()
        await op.stop()
        await decode.stop()
        await prefill.stop()
        await drt.shutdown()


async def test_queue_full_sheds_remote_to_local():
    """Chaos: the prefill queue sits at its depth bound with NO live
    consumer (a stalled pool — the same end state an armed
    ``disagg.send`` partition leaves after the workers' bounded requeues
    give up). New remote-eligible requests must fall back to LOCAL
    prefill — they complete, the shed is counted, nothing queues behind
    the stall."""
    from dynamo_tpu.disagg import (
        DecodeOperator,
        DisaggConfig,
        DisaggRouter,
        PrefillQueue,
    )
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.llm.protocols.common import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )
    from dynamo_tpu.mocker import MockerConfig, MockerEngine
    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.runtime.distributed import DistributedRuntime
    from dynamo_tpu.runtime.engine import Context
    from dynamo_tpu.utils.deadline import OVERLOAD

    drt = await DistributedRuntime.in_process()
    # Hard depth bound of 2; no live consumer (the stalled-pool shape the
    # age/depth bounds exist for).
    queue = PrefillQueue(drt, "chaos-full", max_depth=2)
    dis = DisaggRouter.__new__(DisaggRouter)
    dis.cfg = DisaggConfig(
        max_local_prefill_length=16,
        max_prefill_queue_size=10**6,  # router soft bound out of the way
        max_prefill_queue_age_s=1e9,
    )
    decode = MockerEngine(
        EngineConfig(
            model=ModelConfig.tiny_test(), num_blocks=64, max_num_seqs=4,
            max_model_len=256, dtype="float32",
        ),
        MockerConfig(seed=3),
    )
    await decode.start()
    op = await DecodeOperator(decode, queue, dis, transport="tcp").start()
    try:
        # Fill the queue to its bound (a stalled pool never drains these).
        await queue.enqueue({"request_id": "stuck-1"})
        await queue.enqueue({"request_id": "stuck-2"})
        base = OVERLOAD.shed_total
        req = PreprocessedRequest(
            token_ids=list(range(40)),
            sampling=SamplingOptions(temperature=0.0),
            stop=StopConditions(max_tokens=6, ignore_eos=True),
        )
        toks: list = []

        async def run():
            async for item in op.generate(Context(req.to_wire())):
                toks.extend(item["token_ids"])

        await asyncio.wait_for(run(), 30.0)
        assert len(toks) == 6, "request lost under queue-full shed"
        assert op.remote_count == 0 and op.local_count == 1
        assert OVERLOAD.shed_total > base
        assert await queue.depth() == 2  # nothing new queued behind it
    finally:
        await op.stop()
        await decode.stop()
        await drt.shutdown()


# ---------------------------------------------------------------------------
# Adaptive onboard gate (satellite)
# ---------------------------------------------------------------------------


class _FakeClock:
    """Deterministic monotonic clock: each call advances a fixed step."""

    def __init__(self, step_s: float):
        self.t = 0.0
        self.step = step_s

    def __call__(self) -> float:
        self.t += self.step
        return self.t


def _gate_engine(adaptive=True):
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.mocker import MockerConfig, MockerEngine
    from dynamo_tpu.models.config import ModelConfig

    return MockerEngine(
        EngineConfig(
            model=ModelConfig.tiny_test(),
            num_blocks=64,
            max_num_seqs=2,
            max_model_len=512,
            dtype="float32",
            kvbm_adaptive_gate=adaptive,
        ),
        MockerConfig(),
    )


class _FakeKvbm:
    """count/match stub: every requested hash 'exists' on the host tier,
    with per-call recording of how much the engine actually pulled."""

    def __init__(self, block_bytes):
        self.block_bytes = block_bytes
        self.match_lens = []

    def count_host_match(self, hashes):
        return len(hashes)

    def request_disk_promotion(self, hashes):
        pass

    def match_host(self, hashes):
        self.match_lens.append(len(hashes))
        row = np.zeros(self.block_bytes // 4, np.float32)
        return [(h, None, tuple(range(16)), row) for h in hashes]


async def test_adaptive_gate_first_probe_byte_capped(monkeypatch):
    """the FIRST gate measurement must move at most
    PROBE_BLOCKS blocks — the unbounded first onboard was a 6+ s engine
    stall (14x p95 TTFT) on exactly the slow link the gate exists for."""
    from dynamo_tpu.engine.sequence import Sequence

    eng = _gate_engine()
    await eng.start()
    try:
        cfg = eng.cfg
        block_bytes = (
            cfg.model.num_layers * 2 * cfg.block_size
            * cfg.model.num_cache_heads * eng.runner.cache_head_dim
            * np.dtype(cfg.dtype).itemsize
        )
        fake = _FakeKvbm(block_bytes)
        eng.kvbm = fake
        monkeypatch.setattr(
            eng.allocator, "register", lambda *a, **k: None
        )
        eng._clock = _FakeClock(0.01)

        def seq_for(n_tokens):
            s = Sequence(
                request_id="probe",
                prompt_tokens=list(range(n_tokens)),
                sampling=None,
                stop=None,
                emit=lambda *a: None,
            )
            assert eng.scheduler.admit(s)
            return s

        seq = seq_for(16 * cfg.block_size + 1)  # 16 full prompt blocks
        eng._onboard_host_prefix(seq)
        assert fake.match_lens == [eng.PROBE_BLOCKS], (
            f"first probe pulled {fake.match_lens} blocks, not the cap"
        )
        assert eng._onboard_probes == 1
        # The injected clock advanced one step across the probe window, so
        # the extrapolated rate is exactly probe_bytes / step.
        expected_bps = eng.PROBE_BLOCKS * block_bytes / 0.01
        assert eng._onboard_bps == pytest.approx(expected_bps, rel=1e-6)
    finally:
        await eng.stop()


async def test_adaptive_gate_ema_convergence():
    """EMA convergence under an injected clock: repeated byte-capped
    probes at a stable link rate converge the estimate to that rate."""
    eng = _gate_engine()
    true_bps = 80e6
    probe_bytes = 4 * 2**20
    dt = probe_bytes / true_bps
    # Contaminated first sample (e.g. a compile in the window): 100x slow.
    eng._note_onboard_rate(probe_bytes, dt * 100)
    assert eng._onboard_bps < true_bps / 50
    for _ in range(20):
        eng._note_onboard_rate(probe_bytes, dt)
    assert abs(eng._onboard_bps - true_bps) / true_bps < 0.01, (
        "EMA failed to converge to the true link rate"
    )
    # Prefill-side EMA mirrors it.
    for _ in range(20):
        eng._note_prefill_rate(1000, 0.5)
    assert abs(eng._prefill_tps - 2000.0) < 20.0


# ---------------------------------------------------------------------------
# Disarmed == identical (acceptance)
# ---------------------------------------------------------------------------


async def _mocker_tokens(seed=3):
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.llm.protocols.common import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )
    from dynamo_tpu.mocker import MockerConfig, MockerEngine
    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.runtime.engine import Context

    eng = MockerEngine(
        EngineConfig(
            model=ModelConfig.tiny_test(), num_blocks=32, max_num_seqs=2,
            max_model_len=128, dtype="float32",
        ),
        MockerConfig(seed=seed),
    )
    await eng.start()
    req = PreprocessedRequest(
        token_ids=list(range(24)),
        sampling=SamplingOptions(temperature=0.0),
        stop=StopConditions(max_tokens=8, ignore_eos=True),
    )
    toks = []
    async for item in eng.generate(Context(req.to_wire())):
        toks += item["token_ids"]
    await eng.stop()
    return toks


async def test_disarmed_faults_behavior_identical():
    """With nothing armed the instrumented seams must be pass-through:
    the same seeded serving run produces identical tokens before fault
    arming, after arm+clear, and with a fault armed on an unused point."""
    baseline = await _mocker_tokens()
    FAULTS.arm("some.unused.point", "partition")
    with_unused_fault = await _mocker_tokens()
    FAULTS.clear()
    after_clear = await _mocker_tokens()
    assert baseline == with_unused_fault == after_clear
    assert len(baseline) == 8


# ---------------------------------------------------------------------------
# dynarace runtime checker drills (docs/development/static_analysis.md
# "Concurrency discipline"): a tier-1 subset runs REAL seams with
# DYNTPU_CHECK_THREADS=1 — tracked locks on the block-manager pool, the
# recorder, the tracer and the flight ring, affinity-bound threads — and
# must come out clean. ci.sh re-runs this module plus
# tests/test_concurrency.py with the env var set for the import-time
# enablement path.
# ---------------------------------------------------------------------------


@pytest.fixture
def _checker_on(monkeypatch):
    import os

    from dynamo_tpu.utils import concurrency as ck

    # Restore the OUTER env value on teardown (the ci.sh dynarace leg
    # sets DYNTPU_CHECK_THREADS=1 for the whole session) and refresh
    # AFTER the restore — delenv+refresh would leave the checker
    # silently disarmed for every test that runs after this one.
    prev = os.environ.get("DYNTPU_CHECK_THREADS")
    monkeypatch.setenv("DYNTPU_CHECK_THREADS", "1")
    ck.refresh_enabled()
    ck.reset_tracking()
    yield ck
    if prev is None:
        monkeypatch.delenv("DYNTPU_CHECK_THREADS", raising=False)
    else:
        monkeypatch.setenv("DYNTPU_CHECK_THREADS", prev)
    ck.refresh_enabled()
    ck.reset_tracking()


async def test_kvbm_offload_pipeline_clean_under_checker(_checker_on, tmp_path):
    """The PR 9 seam under the runtime checker: engine-thread-shaped
    stores and loop-side onboard/stats share the tracked pool lock with
    no lock-order inversion and no affinity violation."""
    import numpy as np

    from dynamo_tpu.block_manager.config import KvLayoutConfig
    from dynamo_tpu.block_manager.offload import OffloadManager
    from dynamo_tpu.block_manager.pool import BlockPool
    from dynamo_tpu.block_manager.storage import DiskStorage, HostStorage
    from dynamo_tpu.utils import concurrency as ck

    layout = KvLayoutConfig(
        num_layers=2, page_size=16, num_kv_heads=2, head_dim=16,
        dtype="float32",
    )
    lock = ck.make_lock("kvbm.pool")
    assert isinstance(lock, ck.TrackedLock)
    host = BlockPool(HostStorage(4, layout))
    disk = BlockPool(DiskStorage(4, layout, tmp_path / "kv.bin"))
    mgr = OffloadManager(host, disk, lock=lock)

    data = np.zeros(layout.block_elems, np.float32)
    blocks = host.allocate_blocks(2)
    for i, b in enumerate(blocks):
        host.storage.write_block(b.idx, data)
    regs = [
        host.release(host.register_block(b, 10 + i, None, range(16)))
        or host.get_by_hash(10 + i)
        for i, b in enumerate(blocks)
    ]
    for b in regs:
        mgr.offload(b)
    await mgr.drain()
    assert disk.num_registered == 2
    # Loop-side onboard (to_thread workers bind "worker" via bound()).
    host2 = BlockPool(HostStorage(4, layout))
    mgr2 = OffloadManager(host2, disk, lock=lock)
    up = await mgr2.onboard([10, 11])
    assert [b.sequence_hash for b in up] == [10, 11]
    assert mgr.stats()["offloaded_blocks_total"] == 2


async def test_tracer_and_flight_ring_clean_under_checker(_checker_on, tmp_path):
    """Span storm across engine/loop-bound threads through the tracked
    tracer + recorder + flight-ring locks: no inversion observed."""
    import threading

    from dynamo_tpu.engine.flight_recorder import FlightRecorder
    from dynamo_tpu.utils import concurrency as ck
    from dynamo_tpu.utils.tracing import Tracer

    tr = Tracer(record_path=str(tmp_path / "spans.jsonl"))
    fr = FlightRecorder(capacity=64)
    assert isinstance(tr._lock, ck.TrackedLock)
    assert isinstance(fr._lock, ck.TrackedLock)

    def engine_side():
        ck.bind_thread("engine")
        for i in range(100):
            rid = f"r{i}"
            tr.mark(rid, "received")
            with tr.span(rid, "dispatch"):
                fr.note_step("unified", decode_tokens=1)
            tr.finish(rid)

    def loop_side():
        ck.bind_thread("loop")
        for _ in range(100):
            fr.snapshot(8)
            tr.snapshot(4)
            tr.render()

    threads = [
        threading.Thread(target=engine_side),
        threading.Thread(target=loop_side),
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(30)
        assert not t.is_alive()
    assert fr.total_steps == 100
