"""Leader/follower step broadcast for multi-host serving.

A mesh spanning multiple OS processes executes SPMD programs: EVERY
process must issue the SAME device calls in the SAME order or the
collectives deadlock. The test/dryrun harness (parallel/multihost.py)
satisfies this by running a deterministic script on every rank; real
serving cannot — requests arrive at one HTTP frontend and the engine
makes host-side scheduling decisions (batch composition, chunk sizes)
that would diverge across ranks.

This module makes rank 0 the single decision maker (the reference gets
this property from its backend engines' own orchestration — ray for
vLLM, MPI for TRT-LLM, lib/llm/src/engines.rs:42-60; the TPU engine
spans hosts itself, so the lockstep plane is ours to provide):

- ``StepLeader`` wraps rank 0's ModelRunner. Every top-level device-call
  the engine makes (the unified step / warmup / block IO) is
  published to the control-plane bus BEFORE it executes locally.
- ``follower_serve`` runs on every other rank: subscribe, then replay
  each call verbatim against an identically-built local ModelRunner.
  The replayed call issues the same sharded programs in the same order,
  so the global-mesh collectives line up; outputs are replicated, and
  followers simply drop them.

Only HOST-side arguments cross the wire (token ids, block tables,
sampling params — a few KB per step); tensor traffic stays on ICI/DCN
inside XLA. Serialization is a TYPED msgpack codec (``encode_step`` /
``decode_step``): scalars, strings, (nested) lists/tuples/dicts, and
numeric ndarrays only. Followers validate every frame — unknown wire
version, unknown method, unexpected fields, or an undecodable value
fails LOUDLY instead of executing attacker-shaped input (the previous
wire format deserialized arbitrary objects, handing every follower
code execution from one bad peer).

Liveness: followers heartbeat on a health subject; the leader's watchdog
detects a dead follower within ``liveness_timeout_s`` and fails loudly
(runtime shutdown) instead of hanging forever inside a collective that
can never complete.

Ordering: the leader's engine thread publishes via
``run_coroutine_threadsafe`` from ONE thread, which preserves submission
order through the loop's FIFO; the follower awaits each replay before
the next, so its issue order equals the leader's.
"""

from __future__ import annotations

import asyncio
import logging
from typing import Any, Callable

import msgpack
import numpy as np

from dynamo_tpu.utils.faults import FAULTS

logger = logging.getLogger(__name__)

# Top-level ModelRunner methods the engine invokes; each is one SPMD
# step (or a fixed sequence of them, e.g. warmup) that followers replay.
REPLAYED = (
    "warmup",
    # The serving step: ONE ragged unified dispatch per engine iteration
    # (decode lanes, prefill quanta, and draft-verify spans in one flat
    # batch) — the only step program a runner has.
    "unified_step",
    "gather_block",
    "scatter_block",
    # Batched block IO (ops/kv_copy.py): same SPMD-program rule as the
    # per-block forms — every rank must issue them or the mesh deadlocks.
    "gather_many",
    "gather_many_device",
    "scatter_many",
    "scatter_many_device",
)

_STOP = "__stop__"

#: Wire stand-in for unified_step's device-resident feed tokens. The
#: leader's ``feed[0]`` is the PREVIOUS dispatch's on-device sample
#: array — shipping it would force a device→host sync per dispatch
#: (defeating the pipelined device feed) just to carry bytes every
#: follower already has: the replayed program stream is SPMD, so a
#: follower's own previous unified_step output IS the same replicated
#: array. The leader broadcasts this sentinel instead and each
#: follower substitutes its own previous output at replay.
FEED_PREV = "__feed_prev__"

# -- typed wire codec --------------------------------------------------------
#
# Tagged recursive encoding over plain msgpack. The value domain is
# exactly what REPLAYED methods take: None / bool / int / float / str /
# bytes, tuples, lists, str-keyed dicts, and numeric ndarrays (token
# ids, block tables, sampling vectors, mm embeddings). Anything else is
# a leader-side TypeError — never silently serialized as an object.

WIRE_VERSION = 1
_FRAME_KEYS = frozenset(("v", "seq", "name", "args", "kwargs"))
# ndarray dtype kinds allowed over the wire (bool/int/uint/float/complex)
_ND_KINDS = frozenset("biufc")


class StepWireError(RuntimeError):
    """A malformed / unexpected stepcast frame (follower rejects loudly)."""


def _enc(o: Any) -> Any:
    if o is None or isinstance(o, (bool, str, bytes)):
        return o
    if isinstance(o, (np.integer, np.floating, np.bool_)):
        # dynalint: allow[DT005] isinstance-guarded host numpy scalar: .item() converts to a python number without touching the device
        return o.item()
    if isinstance(o, (int, float)):
        return o
    if isinstance(o, list):
        return [_enc(x) for x in o]
    if isinstance(o, tuple):
        return {"__tu__": [_enc(x) for x in o]}
    if isinstance(o, dict):
        for k in o:
            if not isinstance(k, str):
                raise TypeError(
                    f"stepcast cannot ship dict key {k!r} (str keys only)"
                )
        return {"__di__": {k: _enc(v) for k, v in o.items()}}
    if isinstance(o, np.ndarray) or hasattr(o, "__array__"):
        # dynalint: allow[DT005] wire serialization of the leader's broadcast payload - inputs are host arrays by the stepcast contract (device values never enter frames)
        arr = np.ascontiguousarray(np.asarray(o))
        if arr.dtype.name == "bfloat16":
            # bf16 has no portable wire name — ship its uint16 bits.
            return {
                "__nd__": [
                    "bfloat16", list(arr.shape),
                    arr.view(np.uint16).tobytes(),
                ]
            }
        if arr.dtype.kind not in _ND_KINDS:
            raise TypeError(
                f"stepcast cannot ship ndarray dtype {arr.dtype} "
                "(numeric dtypes only)"
            )
        return {"__nd__": [arr.dtype.str, list(arr.shape), arr.tobytes()]}
    raise TypeError(
        f"stepcast cannot ship value of type {type(o).__name__} — the "
        "typed wire carries scalars, lists/tuples/dicts and numeric "
        "ndarrays only"
    )


def _dec(o: Any) -> Any:
    if o is None or isinstance(o, (bool, int, float, str, bytes)):
        return o
    if isinstance(o, list):
        return [_dec(x) for x in o]
    if isinstance(o, dict):
        if len(o) != 1:
            raise StepWireError(f"untagged dict on the step wire: {list(o)}")
        tag, val = next(iter(o.items()))
        if tag == "__tu__":
            return tuple(_dec(x) for x in val)
        if tag == "__di__":
            return {k: _dec(v) for k, v in val.items()}
        if tag == "__nd__":
            if (
                not isinstance(val, list) or len(val) != 3
                or not isinstance(val[0], str)
                or not isinstance(val[1], list)
                or not all(isinstance(d, int) for d in val[1])
                or not isinstance(val[2], bytes)
            ):
                raise StepWireError(f"malformed ndarray tag: {val!r:.80}")
            dtype_s, shape, raw = val
            try:
                if dtype_s == "bfloat16":
                    import ml_dtypes  # jax dependency, always present

                    return (
                        np.frombuffer(raw, dtype=np.uint16)
                        .reshape(shape)
                        .view(ml_dtypes.bfloat16)
                    )
                dt = np.dtype(dtype_s)
                if dt.kind not in _ND_KINDS:
                    raise StepWireError(f"forbidden wire dtype {dtype_s!r}")
                return np.frombuffer(raw, dtype=dt).reshape(shape)
            except StepWireError:
                raise
            except (ValueError, TypeError) as exc:
                # Bad dtype string, buffer/shape mismatch, … — keep the
                # module contract: every malformation is a StepWireError.
                raise StepWireError(f"malformed ndarray payload: {exc}") from exc
        raise StepWireError(f"unknown wire tag {tag!r}")
    raise StepWireError(f"undecodable wire value type {type(o).__name__}")


def encode_step(seq: int, name: str, args: tuple, kwargs: dict) -> bytes:
    return msgpack.packb(
        {
            "v": WIRE_VERSION,
            "seq": seq,
            "name": name,
            "args": [_enc(a) for a in args],
            "kwargs": {str(k): _enc(v) for k, v in kwargs.items()},
        }
    )


def decode_step(payload: bytes) -> tuple[int, str, tuple, dict]:
    """Validate + decode one step frame. Every malformation raises
    StepWireError — a follower must never guess at a frame."""
    try:
        frame = msgpack.unpackb(payload)
    except Exception as exc:  # noqa: BLE001
        raise StepWireError(f"undecodable step frame: {exc!r}") from exc
    if not isinstance(frame, dict) or set(frame) != _FRAME_KEYS:
        got = sorted(frame) if isinstance(frame, dict) else type(frame).__name__
        raise StepWireError(f"bad step frame fields: {got}")
    if frame["v"] != WIRE_VERSION:
        raise StepWireError(f"unknown step wire version {frame['v']!r}")
    seq, name = frame["seq"], frame["name"]
    if not isinstance(seq, int) or not isinstance(name, str):
        raise StepWireError("bad step frame seq/name types")
    if name != _STOP and name not in REPLAYED:
        raise StepWireError(f"unexpected replayed call {name!r}")
    if not isinstance(frame["args"], list) or not isinstance(
        frame["kwargs"], dict
    ):
        raise StepWireError("bad step frame args/kwargs types")
    args = tuple(_dec(a) for a in frame["args"])
    kwargs = {k: _dec(v) for k, v in frame["kwargs"].items()}
    return seq, name, args, kwargs


def _subjects(namespace: str) -> tuple[str, str, str]:
    return (
        f"{namespace}.multihost.steps",
        f"{namespace}.multihost.ready",
        f"{namespace}.multihost.health",
    )


class StepLeader:
    """Rank-0 runner proxy: broadcast-then-execute every replayed call.

    Everything else (attributes, kv_caches, cfg, non-device helpers)
    passes straight through to the wrapped runner.
    """

    def __init__(
        self,
        runner,
        drt,
        namespace: str = "dynamo",
        num_followers: int = 1,
        heartbeat_s: float = 1.0,
        liveness_timeout_s: float = 10.0,
        on_follower_lost: Callable[[list[str]], None] | None = None,
    ) -> None:
        self._runner = runner
        self._drt = drt
        (
            self._steps_subject,
            self._ready_subject,
            self._health_subject,
        ) = _subjects(namespace)
        self._num_followers = num_followers
        self._heartbeat_s = heartbeat_s
        self._liveness_timeout_s = liveness_timeout_s
        self._on_follower_lost = on_follower_lost
        self._loop: asyncio.AbstractEventLoop | None = None
        self._seq = 0
        self._pending: list[asyncio.Future] = []
        self._ranks: set[str] = set()
        self._monitor_task: asyncio.Task | None = None
        self.followers_lost: list[str] = []
        # Step seqs whose broadcast an injected fault dropped: the mesh
        # is desynced the instant this is non-empty, and the engine
        # thread may already be wedged in the step's collective — the
        # watchdog (on the event loop, still running) escalates.
        self._dropped_steps: list[int] = []

    async def start(self, timeout_s: float = 300.0) -> "StepLeader":
        """Barrier: wait for every follower's ready message so no step is
        published into the void (the bus delivers to LIVE subscribers)."""
        self._loop = asyncio.get_running_loop()
        sub = await self._drt.bus.subscribe(self._ready_subject)
        seen: set[bytes] = set()
        try:
            while len(seen) < self._num_followers:
                payload = await asyncio.wait_for(
                    sub.__anext__(), timeout_s
                )
                seen.add(bytes(payload))
                logger.info(
                    "multihost leader: follower %s ready (%d/%d)",
                    payload.decode(errors="replace"), len(seen),
                    self._num_followers,
                )
        finally:
            sub.close()
        self._ranks = {p.decode(errors="replace") for p in seen}
        self._monitor_task = asyncio.ensure_future(self._monitor())
        return self

    async def _monitor(self) -> None:
        """Follower-liveness watchdog. A follower that stops heartbeating
        (process death, partition) is detected within liveness_timeout_s;
        the leader then FAILS LOUDLY — by default shutting the runtime
        down — instead of hanging forever inside the next collective,
        which can never complete without that rank."""
        sub = await self._drt.bus.subscribe(self._health_subject)
        loop = asyncio.get_running_loop()
        last = {rank: loop.time() for rank in self._ranks}
        try:
            while True:
                def note(payload: bytes) -> None:
                    # Only ranks from OUR barrier: a stray sender on a
                    # shared namespace (another deployment, a stale
                    # follower generation) must not enroll itself — its
                    # later silence would shut down a healthy mesh.
                    rank = payload.decode(errors="replace")
                    if rank in last:
                        last[rank] = loop.time()

                try:
                    note(await asyncio.wait_for(
                        sub.__anext__(), self._heartbeat_s
                    ))
                except asyncio.TimeoutError:
                    pass
                # Drain every backlogged heartbeat before judging: after a
                # leader-side loop stall, queued beats prove the follower
                # was alive the whole time — reading one per tick would
                # declare healthy ranks dead.
                while (extra := sub.poll()) is not None:
                    note(extra)
                now = loop.time()
                dead = sorted(
                    r for r, t in last.items()
                    if now - t > self._liveness_timeout_s
                )
                if dead or self._dropped_steps:
                    self.followers_lost = dead
                    logger.critical(
                        "multihost mesh failed: follower(s) %s silent for "
                        "%.1fs, dropped step seq(s) %s — collectives can "
                        "no longer complete; failing loudly",
                        dead, self._liveness_timeout_s,
                        self._dropped_steps,
                    )
                    if self._on_follower_lost is not None:
                        self._on_follower_lost(dead)
                    else:
                        self._drt.runtime.shutdown()
                    return
        except asyncio.CancelledError:
            raise
        except StopAsyncIteration:
            # Health subscription closed under us (control-plane
            # teardown): the lease keepalive escalates that same loss to
            # shutdown — the watchdog just reports it stopped watching.
            logger.warning(
                "stepcast watchdog: health subscription closed; "
                "follower-liveness detection stopped"
            )
        # dynalint: allow[DT003] watchdog exit is logged loudly; leader liveness checks also cover its death
        except Exception:
            # The watchdog must never die silently — a swallowed error
            # here re-opens the undetected-hang class this PR closes.
            logger.exception("stepcast watchdog failed")
        finally:
            sub.close()

    async def stop(self) -> None:
        # Watchdog first: followers exit (and stop heartbeating) on the
        # stop sentinel — a live monitor would read that as death.
        if self._monitor_task is not None:
            self._monitor_task.cancel()
            try:
                await self._monitor_task
            except asyncio.CancelledError:
                pass
            # dynalint: allow[DT003] teardown must reach the _STOP cast below or followers hang forever
            except Exception:
                # A watchdog that died abnormally must not block teardown
                # — the _STOP cast below is what keeps followers from
                # hanging forever.
                logger.exception("stepcast watchdog ended abnormally")
            self._monitor_task = None
        self._cast(_STOP, (), {})
        for f in list(self._pending):
            try:
                await asyncio.wrap_future(f)
            except Exception:  # dynalint: allow[DT003] stop() drains in-flight casts; their errors already surfaced to callers
                pass

    def _cast(self, name: str, args: tuple, kwargs: dict) -> None:
        # The stop sentinel is teardown control traffic, exempt from
        # injection: dropping it would leave followers waiting on a
        # stream that is by definition over — a hang no later frame can
        # ever convert into the loud gap failure.
        if name != _STOP and not FAULTS.maybe_fail(
            "stepcast.broadcast", can_drop=True
        ):
            # Injected frame drop: the mesh is desynced NOW — the local
            # execution of this step blocks in its collective with no
            # follower issuing the match, so the engine thread may never
            # reach a next broadcast. Recovery is two-pronged: the
            # watchdog (event loop, unaffected by the wedged engine
            # thread) sees _dropped_steps and fails loudly within a
            # heartbeat, and if any later frame does go out, the
            # follower's seq-gap check fires too.
            logger.critical(
                "stepcast: injected drop of step %d (%s) — mesh desynced",
                self._seq, name,
            )
            self._dropped_steps.append(self._seq)
            self._seq += 1
            return
        payload = encode_step(self._seq, name, args, kwargs)
        self._seq += 1
        fut = asyncio.run_coroutine_threadsafe(
            self._drt.bus.broadcast(self._steps_subject, payload),
            self._loop,
        )
        self._pending.append(fut)
        self._pending[:] = [f for f in self._pending if not f.done()]

    def warm_ops(self):
        """Compile lifecycle (engine/compile_cache.py): followers replay
        `warmup` as ONE broadcast REPLAYED call, so the leader's list
        collapses to that single op — every rank must compile the
        identical set in lockstep, and the thunks a per-shape list carries
        are not wire-shippable."""
        return [("warmup", self.warmup)]

    def run_warm_ops(self, ops) -> int:
        n = 0
        for _key, fn in ops:
            out = fn()
            n += out if isinstance(out, int) else 1
        return n

    def __getattr__(self, name: str) -> Any:
        target = getattr(self._runner, name)
        if name not in REPLAYED:
            return target

        def call(*args, **kwargs):
            wire_kwargs = kwargs
            if name == "unified_step" and kwargs.get("feed") is not None:
                # Device-feed sentinel (see FEED_PREV): the broadcast
                # copy must never carry the device token array — the
                # wire encoder's np.asarray would sync the pipeline on
                # every dispatch. The LOCAL call keeps the real feed.
                _prev, prev_row, use_prev = kwargs["feed"]
                wire_kwargs = dict(kwargs)
                wire_kwargs["feed"] = (
                    FEED_PREV,
                    np.asarray(prev_row),  # dynalint: allow[DT005] engine-built host np array (the row map); only feed[0] is ever device-resident
                    np.asarray(use_prev),  # dynalint: allow[DT005] engine-built host np bool mask; only feed[0] is ever device-resident
                )
            self._cast(name, args, wire_kwargs)
            return target(*args, **kwargs)

        return call

    def __setattr__(self, name: str, value: Any) -> None:
        if name.startswith("_") or name == "followers_lost":
            object.__setattr__(self, name, value)
        else:
            setattr(self._runner, name, value)


async def follower_serve(
    runner,
    drt,
    namespace: str = "dynamo",
    rank: int = 1,
    heartbeat_s: float = 1.0,
) -> int:
    """Replay the leader's step stream until its stop sentinel; returns
    the number of replayed calls. The runner must be built from the SAME
    EngineConfig/params the leader's engine used (the CLI guarantees
    this — both ranks load the same model artifacts)."""
    steps_subject, ready_subject, health_subject = _subjects(namespace)
    sub = await drt.bus.subscribe(steps_subject)
    # The bus delivers only to live subscribers with no retention, and
    # the leader subscribes to the ready subject only once its engine is
    # up — a single ready message can land before anyone listens and
    # hang startup. RE-BROADCAST until the first step arrives (the
    # leader's barrier dedups by payload, so repeats are harmless).
    got_first = asyncio.Event()
    stopping = asyncio.Event()

    async def announce() -> None:
        while not got_first.is_set():
            await drt.bus.broadcast(ready_subject, str(rank).encode())
            try:
                await asyncio.wait_for(got_first.wait(), 0.5)
            except asyncio.TimeoutError:
                pass

    async def heartbeat() -> None:
        # Liveness beacon for the leader's watchdog. Stops with the
        # replay loop — after that, silence IS the correct signal. A
        # transient broadcast failure (control-plane blip) must NOT end
        # the beacon: one blip on a healthy follower would read as death
        # and take the whole runtime down. Keep beating; if the bus is
        # truly gone the replay loop dies too and silence is then true.
        while not stopping.is_set():
            try:
                await drt.bus.broadcast(health_subject, str(rank).encode())
            except asyncio.CancelledError:
                raise
            # dynalint: allow[DT003] missed heartbeats are the signal itself: the leader watchdog detects us
            except Exception:
                logger.warning("follower heartbeat failed", exc_info=True)
            try:
                await asyncio.wait_for(stopping.wait(), heartbeat_s)
            except asyncio.TimeoutError:
                pass

    announce_task = asyncio.create_task(announce())
    heartbeat_task = asyncio.create_task(heartbeat())
    n = 0
    expect = 0
    # This follower's previous unified_step output — the local
    # substitute for the leader's FEED_PREV sentinel (the SPMD replay
    # makes it the same replicated array the leader fed).
    prev_unified = None
    try:
        async for payload in sub:
            got_first.set()
            await FAULTS.maybe_fail_async("stepcast.replay")
            # Typed codec: malformed frames / unknown methods raise
            # StepWireError here — the follower dies loudly rather than
            # replaying attacker-shaped input.
            seq, name, args, kwargs = decode_step(payload)
            if seq != expect:
                raise RuntimeError(
                    f"multihost follower lost step(s): expected seq "
                    f"{expect}, got {seq} — collectives would deadlock"
                )
            expect += 1
            if name == _STOP:
                break
            if (
                name == "unified_step"
                and kwargs.get("feed") is not None
                and kwargs["feed"][0] == FEED_PREV
            ):
                _s, prev_row, use_prev = kwargs["feed"]
                if prev_unified is None:
                    # dynalint: allow[DT005] wire-decoded host array (the typed codec only ships host numpy)
                    if np.asarray(use_prev).any():
                        # A feeding dispatch with no prior output means
                        # this follower missed a step — the seq-gap
                        # check should have caught it; die loudly
                        # rather than decode from garbage tokens.
                        raise RuntimeError(
                            "multihost follower: unified_step feed "
                            "references a previous dispatch this rank "
                            "never replayed"
                        )
                    # dynalint: allow[DT006] host feed placeholder sized by the fixed metadata width S (config-derived, not data-dependent)
                    prev_unified = np.zeros(len(use_prev), np.int32)
                kwargs = dict(kwargs)
                kwargs["feed"] = (prev_unified, prev_row, use_prev)
            # Off the event loop: replays block on cross-process
            # collectives until the leader issues the matching step.
            out = await asyncio.to_thread(
                getattr(runner, name), *args, **kwargs
            )
            if name == "unified_step":
                prev_unified = out.last
            n += 1
    finally:
        got_first.set()
        stopping.set()
        for task in (announce_task, heartbeat_task):
            task.cancel()
            try:
                await task
            except asyncio.CancelledError:
                pass
        sub.close()
    logger.info("multihost follower rank %d: %d steps replayed", rank, n)
    return n
