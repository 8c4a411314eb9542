"""Randomized chaos-schedule harness: the self-healing-fleet proof.

``BENCH_CHAOS=1 python bench.py`` (ci.sh "mocker chaos fleet" leg)
replays a request trace over a ≥4-decode-worker mocker fleet — the FULL
production planes: bus dispatch, TCP response streams, shared prefill
queue with remote KV transfer, the ingress failover plane
(runtime/failover.py), and two planner worker pools
(planner/pools.py) — while a SEEDED randomized schedule:

- **kills workers** mid-stream (``ServedInstance.kill()``: the pump and
  every in-flight handler die abruptly, response sockets abort with no
  terminal frame, discovery keys linger — exactly a crashed process);
- **partitions the bus** (``bus.publish`` armed ``partition`` for a
  window: every dispatch fails, the mark-dead fast path evicts the
  whole fleet, the store refresh re-resolves it after heal);
- **drops KV frames** (``disagg.recv`` armed ``drop``: lost transfer
  frames degrade remote prefill to local recompute — the PR 2 ledger).

Hard gates (docs/architecture/failure_model.md "Mid-stream failover"):

1. **Every request resolves** — success or a clean typed error — with
   ZERO hangs under a per-request watchdog.
2. **Failover succeeds whenever healthy capacity remains**: a request
   may fail ONLY while (or right after) a bus partition had the whole
   fleet unreachable; worker kills alone never fail a request.
3. **Streams stay byte-identical**: deterministic-token mode makes
   every greedy stream a pure function of the prompt, so each
   successful request's tokens are checked against the closed-form
   expectation — a failover that skipped or repeated a token fails.
4. **The fleet heals to target size**: dead workers are replaced
   immediately by the pools' crash path (``reap_dead`` — no drain
   accounting) and the run ends at target with every worker alive.

The schedule is ``random.Random(seed)``-driven (``BENCH_CHAOS_SEED``):
reruns with one seed replay one schedule.
"""

# dynarace: context[loop]

from __future__ import annotations

import asyncio
import logging
import os
import random
import sys
import time

if __package__ in (None, ""):  # `python benchmarks/chaos_bench.py`
    sys.path.insert(
        0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )

logger = logging.getLogger(__name__)

#: Mirrors mocker _SimRunner._det_next — the closed-form greedy stream.
_A, _C, _D = 1103515245, 12345, 7


def expected_stream(prompt: list[int], osl: int, vocab: int) -> list[int]:
    """The deterministic tokens ANY healthy serving path must produce."""
    out: list[int] = []
    prev, pos = prompt[-1], len(prompt)
    for _ in range(osl):
        prev = (prev * _A + pos * _C + _D) % vocab
        out.append(prev)
        pos += 1
    return out


class _WorkerHandle:
    """One live mocker worker: served instance + engine (+ operator)."""

    def __init__(self, instance, engine, operator=None, prefill=None):
        self.instance = instance
        self.engine = engine
        self.operator = operator
        self.prefill = prefill
        self.alive = True

    @property
    def worker_id(self) -> int:
        return self.instance.instance.instance_id


class _DecodeConnector:
    """Planner connector spawning in-process mocker decode workers —
    ``alive()`` opts the pool into crash healing (pools.reap_dead)."""

    def __init__(self, spawn_fn):
        self._spawn_fn = spawn_fn
        self.spawned = 0

    async def spawn(self) -> _WorkerHandle:
        self.spawned += 1
        return await self._spawn_fn(self.spawned)

    def alive(self, handle: _WorkerHandle) -> bool:
        return handle.alive

    async def drain(self, handle: _WorkerHandle) -> None:
        if handle.alive:
            await handle.instance.drain(grace_s=10.0)
            await handle.engine.stop()


async def run_chaos(
    seed: int = 1234,
    decode_workers: int = 4,
    prefill_workers: int = 2,
    requests: int = 24,
    osl: int = 24,
    vocab: int = 997,
    watchdog_s: float = 60.0,
) -> dict:
    from dynamo_tpu.disagg import (
        DisaggConfig,
        DisaggRouter,
        DecodeOperator,
        PrefillQueue,
        PrefillWorker,
    )
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.llm.protocols.common import (
        DeadlineError,
        FailoverExhausted,
        PreprocessedRequest,
        SamplingOptions,
        ShedError,
        StopConditions,
        WorkerDiedError,
    )
    from dynamo_tpu.mocker import MockerConfig, MockerEngine
    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.planner.pools import PoolConfig, PrefillLaw, WorkerPool
    from dynamo_tpu.runtime.distributed import DistributedRuntime
    from dynamo_tpu.runtime.egress import PushRouter
    from dynamo_tpu.runtime.engine import Context
    from dynamo_tpu.runtime.failover import FAILOVER, FailoverEngine
    from dynamo_tpu.utils.faults import FAULTS
    from dynamo_tpu.utils.tracing import tracer

    rng = random.Random(seed)
    t_start = time.monotonic()
    drt0 = await DistributedRuntime.in_process()
    queue = PrefillQueue(drt0, "chaos")
    dis = DisaggRouter.__new__(DisaggRouter)
    dis.cfg = DisaggConfig(
        max_local_prefill_length=24, max_prefill_queue_size=256,
    )

    def engine_cfg() -> EngineConfig:
        return EngineConfig(
            model=ModelConfig.tiny_test(), num_blocks=512, max_num_seqs=4,
            max_model_len=512, dtype="float32",
        )

    def sim_cfg(i: int) -> MockerConfig:
        # ~20 ms per fused decode step: streams last ~0.5 s, so the
        # kill schedule reliably lands mid-decode; the whole run stays
        # well under a minute.
        return MockerConfig(
            vocab_size=vocab, seed=i, deterministic_tokens=True,
            decode_time_per_step_us=20000.0,
        )

    async def sub_drt():
        return await DistributedRuntime.in_process(
            store=drt0.store, bus=drt0.bus, runtime=drt0.runtime
        )

    async def spawn_decode(i: int) -> _WorkerHandle:
        eng = MockerEngine(engine_cfg(), sim_cfg(i))
        await eng.start()
        op = await DecodeOperator(eng, queue, dis, transport="tcp").start()
        drt = await sub_drt()
        inst = await drt.namespace("chaos").component("w").endpoint(
            "generate"
        ).serve(op)
        return _WorkerHandle(inst, eng, operator=op)

    async def spawn_prefill(i: int) -> _WorkerHandle:
        eng = MockerEngine(engine_cfg(), sim_cfg(1000 + i))
        await eng.start()
        pw = PrefillWorker(eng, queue).start()
        # Prefill workers are queue consumers, not served endpoints —
        # the handle's "instance" is the worker itself.
        h = _WorkerHandle(_NoInstance(), eng, prefill=pw)
        return h

    class _NoInstance:
        async def kill(self):
            pass

        async def drain(self, grace_s: float = 10.0):
            pass

        class instance:
            instance_id = 0

    class _PrefillConnector(_DecodeConnector):
        async def drain(self, handle: _WorkerHandle) -> None:
            if handle.alive:
                await handle.prefill.stop()
                await handle.engine.stop()

    decode_pool = WorkerPool(
        PoolConfig(
            name="decode", min_workers=decode_workers,
            max_workers=decode_workers + 2,
        ),
        _DecodeConnector(spawn_decode),
        law=None,
    )
    prefill_pool = WorkerPool(
        PoolConfig(
            name="prefill", min_workers=prefill_workers,
            max_workers=prefill_workers + 1,
        ),
        _PrefillConnector(spawn_prefill),
        law=PrefillLaw(),
    )
    await decode_pool.ensure_min()
    await prefill_pool.ensure_min()

    push = await PushRouter.create(
        drt0, "chaos.w.generate", connect_timeout_s=2.0
    )
    engine = FailoverEngine(push)

    # -- the healing loop (planner crash path, every 150 ms) -------------
    replaced = {"n": 0}

    async def heal_loop():
        while True:
            for pool in (decode_pool, prefill_pool):
                replaced["n"] += await pool.reap_dead()
            await asyncio.sleep(0.15)

    healer = asyncio.ensure_future(heal_loop())

    # -- the seeded chaos schedule ---------------------------------------
    kills = {"decode": 0, "prefill": 0}
    partitions: list[tuple[float, float]] = []
    graveyard: list[_WorkerHandle] = []  # killed handles, for teardown

    async def kill_decode():
        live = [h for h in decode_pool.handles if h.alive]
        if len(live) <= 1:
            return  # never kill the last healthy worker
        # Prefer a worker with streams in flight: killing an idle corpse
        # proves only the dispatch fast path — the mid-stream replay is
        # the seam this harness exists to drill.
        busy = [h for h in live if h.instance.inflight > 0]
        victim = rng.choice(busy or live)
        victim.alive = False
        kills["decode"] += 1
        graveyard.append(victim)
        logger.warning("CHAOS: killing decode worker %#x", victim.worker_id)
        await victim.instance.kill()

    async def kill_prefill():
        live = [h for h in prefill_pool.handles if h.alive]
        if len(live) <= 1:
            return
        victim = rng.choice(live)
        victim.alive = False
        kills["prefill"] += 1
        graveyard.append(victim)
        logger.warning("CHAOS: killing a prefill worker")
        await victim.prefill.stop()

    async def partition_bus(window_s: float):
        t0 = time.monotonic() - t_start
        logger.warning("CHAOS: partitioning the bus for %.2fs", window_s)
        FAULTS.arm("bus.publish", "partition")
        await asyncio.sleep(window_s)
        FAULTS.disarm("bus.publish")
        partitions.append((t0, time.monotonic() - t_start))

    async def drop_kv_frames():
        logger.warning("CHAOS: dropping the next 2 KV transfer frames")
        FAULTS.arm("disagg.recv", "drop", times=2)

    events = [
        (1.0 + rng.random() * 0.8, kill_decode),
        (2.2 + rng.random() * 0.8, kill_decode),
        (1.6 + rng.random() * 0.6, kill_prefill),
        (1.2 + rng.random() * 0.5, drop_kv_frames),
        (2.8 + rng.random() * 0.5, drop_kv_frames),
        (4.2 + rng.random() * 0.5, lambda: partition_bus(0.4)),
    ]

    async def run_schedule():
        for delay, fn in sorted(events, key=lambda e: e[0]):
            await asyncio.sleep(
                max(0.0, delay - (time.monotonic() - t_start))
            )
            await fn()

    schedule = asyncio.ensure_future(run_schedule())

    # -- the load ---------------------------------------------------------
    prompts = [
        [rng.randrange(1, vocab) for _ in range(rng.choice((16, 48, 64)))]
        for _ in range(requests)
    ]

    async def one(idx: int, prompt: list[int]):
        await asyncio.sleep(idx * (4.0 / max(requests, 1)))
        req = PreprocessedRequest(
            token_ids=list(prompt),
            sampling=SamplingOptions(temperature=0.0),
            stop=StopConditions(max_tokens=osl, ignore_eos=True),
        )
        ctx = Context(req.to_wire())
        out: list[int] = []
        try:
            async for item in engine.generate(ctx):
                out += item.get("token_ids", [])
            want = expected_stream(prompt, osl, vocab)
            if out != want:
                return ("corrupt", time.monotonic() - t_start,
                        f"req {idx}: got {len(out)} tokens, "
                        f"mismatch vs closed form")
            return ("ok", time.monotonic() - t_start, "")
        except (
            ShedError, DeadlineError, FailoverExhausted, WorkerDiedError,
        ) as exc:
            return ("typed_error", time.monotonic() - t_start,
                    f"req {idx}: {type(exc).__name__}: {exc}")
        except Exception as exc:  # noqa: BLE001 — untyped = gate failure
            return ("untyped_error", time.monotonic() - t_start,
                    f"req {idx}: {type(exc).__name__}: {exc}")
        finally:
            tracer().finish(ctx.id)

    async def guarded(idx, prompt):
        try:
            return await asyncio.wait_for(one(idx, prompt), watchdog_s)
        except asyncio.TimeoutError:
            return ("hang", time.monotonic() - t_start, f"req {idx}: WATCHDOG")

    results = await asyncio.gather(
        *[guarded(i, p) for i, p in enumerate(prompts)]
    )
    await schedule
    # Let the healer finish replacing the last kills, then freeze it.
    for _ in range(60):
        live_d = sum(1 for h in decode_pool.handles if h.alive)
        live_p = sum(1 for h in prefill_pool.handles if h.alive)
        if (
            live_d >= decode_workers and live_p >= prefill_workers
            and replaced["n"] >= kills["decode"] + kills["prefill"]
        ):
            break
        await asyncio.sleep(0.15)
    healer.cancel()
    try:
        await healer
    except asyncio.CancelledError:
        pass
    FAULTS.clear()

    # -- gates -------------------------------------------------------------
    counts: dict[str, int] = {}
    for status, _, _ in results:
        counts[status] = counts.get(status, 0) + 1
    failures: list[str] = []
    if counts.get("hang"):
        failures.append(f"{counts['hang']} request(s) HUNG past the watchdog")
    if counts.get("untyped_error"):
        bad = [d for s, _, d in results if s == "untyped_error"]
        failures.append(f"untyped errors (must be typed): {bad[:3]}")
    if counts.get("corrupt"):
        bad = [d for s, _, d in results if s == "corrupt"]
        failures.append(f"corrupted streams across failover: {bad[:3]}")
    # Gate 2: typed errors are legitimate ONLY while a partition had the
    # fleet unreachable (plus settle slack) — kills alone never fail a
    # request when healthy capacity remains.
    pad = 3.0
    for status, t_done, detail in results:
        if status != "typed_error":
            continue
        if not any(w0 <= t_done <= w1 + pad for w0, w1 in partitions):
            failures.append(
                f"request failed OUTSIDE any partition window (healthy "
                f"capacity remained): {detail} at t={t_done:.2f}s "
                f"windows={partitions}"
            )
    live_decode = sum(1 for h in decode_pool.handles if h.alive)
    live_prefill = sum(1 for h in prefill_pool.handles if h.alive)
    if live_decode < decode_workers:
        failures.append(
            f"decode pool did not heal: {live_decode}/{decode_workers} alive"
        )
    if live_prefill < prefill_workers:
        failures.append(
            f"prefill pool did not heal: "
            f"{live_prefill}/{prefill_workers} alive"
        )
    total_kills = kills["decode"] + kills["prefill"]
    if replaced["n"] < total_kills:
        failures.append(
            f"crash path replaced {replaced['n']} < {total_kills} kills"
        )
    if kills["decode"] and FAILOVER.success_total < 1:
        failures.append(
            "decode workers were killed but no failover completed a "
            "request"
        )

    # -- teardown ----------------------------------------------------------
    for h in list(decode_pool.handles):
        try:
            if h.alive:
                await h.instance.stop()
            await h.engine.stop()
        except Exception:  # noqa: BLE001 — teardown
            pass
    for h in list(prefill_pool.handles):
        try:
            if h.alive and h.prefill is not None:
                await h.prefill.stop()
            await h.engine.stop()
        except Exception:  # noqa: BLE001 — teardown
            pass
    for h in graveyard:
        try:
            await h.engine.stop()
        except Exception:  # noqa: BLE001 — teardown
            pass
    await drt0.shutdown()

    degraded = FAILOVER.snapshot()
    report = {
        "seed": seed,
        "requests": requests,
        "resolved": sum(counts.values()),
        "ok": counts.get("ok", 0),
        "typed_errors": counts.get("typed_error", 0),
        "hangs": counts.get("hang", 0),
        "corrupt": counts.get("corrupt", 0),
        "kills": dict(kills),
        "replaced_dead": replaced["n"],
        "partitions": [
            (round(a, 2), round(b, 2)) for a, b in partitions
        ],
        "failover": degraded,
        "failover_success_total": FAILOVER.success_total,
        "workers_marked_dead_total": FAILOVER.marked_dead_total,
        "decode_pool_final": live_decode,
        "prefill_pool_final": live_prefill,
        "duration_s": round(time.monotonic() - t_start, 2),
        "failures": failures,
    }
    return report


def run_gates(report: dict) -> None:
    """Hard-fail on any gate violation (ci.sh leg + BENCH_CHAOS)."""
    if report["failures"]:
        raise RuntimeError(
            "CHAOS GATES FAILED:\n  " + "\n  ".join(report["failures"])
        )
    if report["resolved"] != report["requests"]:
        raise RuntimeError(
            f"only {report['resolved']}/{report['requests']} requests "
            f"resolved"
        )


# ---------------------------------------------------------------------------
# KV-block integrity: the randomized corruption schedule
# (``BENCH_INTEGRITY=1 python bench.py`` — ci.sh "integrity" leg)
#
# Five trust-boundary seams, each corrupted by a seeded schedule (flip or
# truncate, chosen per leg), each gated on the same invariant: the
# corruption is DETECTED (checksum refusal, counted on the right tier),
# the block is quarantined, and the request rides degrade-to-recompute
# to a stream byte-identical to the deterministic closed form. A nonzero
# failure counter with a zero-deviation stream is the system WORKING.
#
#   1. G2 onboard   — a byte rots in the host DRAM arena; match_host
#                     refuses the block at the G2→G1 crossing.
#   2. G3 scrub     — disk writes corrupted in flight; the paced
#                     scrubber finds every rotten block before a reader.
#   3. G4 pull      — a peer-served frame corrupts on the DCN; the
#                     importer refuses the record mid-pull.
#   4. disagg tcp   — a prefill→decode KV frame corrupts on the wire;
#                     the receiver drops it and the ledger degrades the
#                     request to local recompute.
#   5. disagg native— same seam over the native transfer agent
#                     (checksums ride the notify metadata).
#
# Plus an overhead leg: the envelope's CRC cost per crossing, measured
# directly, must stay under 2% of serve wall time.
# ---------------------------------------------------------------------------

_INT_OSL = 8


def _int_layout():
    from dynamo_tpu.block_manager import KvLayoutConfig

    # block_elems == 8: the mocker runner's 8-float block rows.
    return KvLayoutConfig(
        num_layers=1, page_size=1, num_kv_heads=1, head_dim=4,
        dtype="float32",
    )


def _int_ecfg(**kw):
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.models.config import ModelConfig

    kw.setdefault("num_blocks", 192)
    kw.setdefault("max_num_seqs", 4)
    kw.setdefault("max_model_len", 2048)
    # Tier placement, not the adaptive-gate ramp, is under test.
    kw.setdefault("kvbm_adaptive_gate", False)
    return EngineConfig(model=ModelConfig.tiny_test(), dtype="float32", **kw)


async def _int_worker(main, *, kvbm_cfg=None, ecfg=None, sim_seed=1,
                      link_gbps=0.0):
    """One KVBM-attached mocker worker on the shared fleet planes.
    Returns (drt, kvbm, engine)."""
    from dynamo_tpu.block_manager import KvBlockManager, KvbmConfig
    from dynamo_tpu.mocker.engine import MockerConfig, MockerEngine
    from dynamo_tpu.planner import calibration as cal
    from dynamo_tpu.runtime.distributed import DistributedRuntime

    drt = await DistributedRuntime.in_process(store=main.store, bus=main.bus)
    kvbm = await KvBlockManager(
        kvbm_cfg or KvbmConfig(layout=_int_layout(), host_blocks=128)
    ).start()
    eng = MockerEngine(
        ecfg or _int_ecfg(),
        MockerConfig(
            seed=sim_seed, deterministic_tokens=True,
            peer_link_gbps=link_gbps,
            prefill_time_per_token_us=cal.PREFILL_TIME_PER_TOKEN_US,
        ),
        block_manager=kvbm,
    )
    await eng.start()
    return drt, kvbm, eng


async def _int_generate(engine, prompt, n=_INT_OSL, watchdog_s=60.0):
    """One greedy request; a hang past the watchdog raises (loud gate
    failure), it never wedges the schedule."""
    from dynamo_tpu.llm.protocols.common import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )
    from dynamo_tpu.runtime.engine import Context

    req = PreprocessedRequest(
        token_ids=list(prompt),
        sampling=SamplingOptions(temperature=0.0),
        stop=StopConditions(max_tokens=n, ignore_eos=True),
    )

    async def _drain() -> list[int]:
        out: list[int] = []
        async for item in engine.generate(Context(req.to_wire())):
            out += item.get("token_ids", [])
        return out

    return await asyncio.wait_for(_drain(), watchdog_s)


def _int_chain(tokens, block_size=16):
    from dynamo_tpu.llm.tokens import TokenBlockSequence

    return TokenBlockSequence.from_tokens(
        tokens, block_size=block_size
    ).sequence_hashes()


async def _int_wait_host(kvbm, n, timeout=15.0):
    deadline = asyncio.get_running_loop().time() + timeout
    while kvbm.stats()["host_registered"] < n:
        if asyncio.get_running_loop().time() >= deadline:
            raise TimeoutError(
                f"host tier never reached {n} registered blocks "
                f"(at {kvbm.stats()['host_registered']})"
            )
        await asyncio.sleep(0.02)


def _int_prompt(rng, tokens=130):
    return [rng.randrange(1, 31991) for _ in range(tokens)]


async def _ileg_host_onboard(main, rng) -> dict:
    """Seam 1 — G2→G1: rot one byte in the host arena (no code seam to
    arm: DRAM rot happens between writes), then force a cold onboard."""
    import numpy as np

    from dynamo_tpu.block_manager.integrity import INTEGRITY
    from dynamo_tpu.mocker.engine import MockerConfig, MockerEngine

    INTEGRITY.reset()
    prompt = _int_prompt(rng)
    nblocks = (len(prompt) - 1) // 16
    drt, kvbm, eng_a = await _int_worker(main, sim_seed=1)
    eng_b = None
    try:
        vocab = eng_a.runner.sim.vocab_size
        want = expected_stream(prompt, _INT_OSL, vocab)
        base = await _int_generate(eng_a, prompt)
        await kvbm.drain_offers(20.0)
        await _int_wait_host(kvbm, nblocks)
        regs = set(kvbm.host_pool.registered_hashes())
        victims = [h for h in _int_chain(prompt)[:nblocks] if h in regs]
        blk = kvbm.host_pool.get_by_hash(rng.choice(victims))
        # HostStorage.read_block returns the arena row VIEW — flip one
        # byte in place, exactly silent DRAM rot under the envelope.
        row = kvbm.host_pool.storage.read_block(blk.idx)
        flat = row.view(np.uint8)
        flat[rng.randrange(len(flat))] ^= 0x01
        # A second engine on the SAME kvbm: its cold G1 forces the host
        # onboard, where match_host verifies every matched block.
        eng_b = MockerEngine(
            _int_ecfg(),
            MockerConfig(seed=2, deterministic_tokens=True),
            block_manager=kvbm,
        )
        await eng_b.start()
        toks = await _int_generate(eng_b, prompt)
        snap = INTEGRITY.snapshot()
        return {
            "injected": 1,
            "detected": snap["integrity_failures_host"],
            "tier_split_clean": (
                snap["integrity_failures_total"]
                == snap["integrity_failures_host"]
            ),
            "stream_identical": toks == want and base == want,
        }
    finally:
        for eng in (eng_b, eng_a):
            if eng is not None:
                await eng.stop()
        await kvbm.stop()
        await drt.shutdown()


async def _ileg_disk_scrub(main, rng, tmp) -> dict:
    """Seam 2 — G3: corrupt disk writes in flight (flip or truncate);
    one full scrubber sweep must find and quarantine every rotten block
    BEFORE any reader, and the host-intact re-serve stays identical."""
    from dynamo_tpu.block_manager import KvbmConfig
    from dynamo_tpu.block_manager.integrity import INTEGRITY
    from dynamo_tpu.mocker.engine import MockerConfig, MockerEngine
    from dynamo_tpu.utils.faults import FAULTS

    INTEGRITY.reset()
    action = rng.choice(("flip", "truncate"))
    times = rng.randint(1, 3)
    prompt = _int_prompt(rng)
    nblocks = (len(prompt) - 1) // 16
    cfg = KvbmConfig(
        layout=_int_layout(), host_blocks=64, disk_blocks=64,
        disk_path=os.path.join(tmp, "g3.kv"), disk_persist=True,
    )
    drt, kvbm, eng_a = await _int_worker(main, kvbm_cfg=cfg)
    eng_b = None
    before = FAULTS.snapshot().get("kvbm.corrupt_disk", 0)
    FAULTS.arm("kvbm.corrupt_disk", action, times=times)
    try:
        vocab = eng_a.runner.sim.vocab_size
        want = expected_stream(prompt, _INT_OSL, vocab)
        base = await _int_generate(eng_a, prompt)
        await kvbm.drain_offers(20.0)
        await _int_wait_host(kvbm, nblocks)
        await kvbm._g2_to_g3.drain()
        FAULTS.disarm("kvbm.corrupt_disk")
        injected = FAULTS.snapshot().get("kvbm.corrupt_disk", 0) - before
        scanned, detected = kvbm.scrub_tick(max_blocks=cfg.disk_blocks)
        # Cold-G1 re-serve: intact HOST copies feed the onboard; the
        # rotten disk blocks are already quarantined and un-named.
        eng_b = MockerEngine(
            _int_ecfg(),
            MockerConfig(seed=2, deterministic_tokens=True),
            block_manager=kvbm,
        )
        await eng_b.start()
        toks = await _int_generate(eng_b, prompt)
        snap = INTEGRITY.snapshot()
        return {
            "action": action,
            "injected": injected,
            "scrub_scanned": scanned,
            "scrub_detected": detected,
            "detected": snap["integrity_failures_disk"],
            "tier_split_clean": (
                snap["integrity_failures_total"]
                == snap["integrity_failures_disk"]
            ),
            "stream_identical": toks == want and base == want,
        }
    finally:
        FAULTS.disarm("kvbm.corrupt_disk")
        for eng in (eng_b, eng_a):
            if eng is not None:
                await eng.stop()
        await kvbm.stop()
        await drt.shutdown()


async def _ileg_peer_pull(main, rng) -> dict:
    """Seam 3 — G4: corrupt one peer-served frame mid-pull; the importer
    refuses the record, the parked request resumes on the shortened
    prefix and recomputes the rest, byte-identical."""
    from dynamo_tpu.block_manager.integrity import INTEGRITY
    from dynamo_tpu.block_manager.peer import (
        PeerBlockClient,
        PeerBlockServer,
        layout_fingerprint,
    )
    from dynamo_tpu.planner import calibration as cal
    from dynamo_tpu.utils.faults import FAULTS

    INTEGRITY.reset()
    action = rng.choice(("flip", "truncate"))
    # The pull-win shape (g4_bench leg 1): a long prompt priced against
    # the calibrated link, so the pull is actually planned.
    prompt = [(7 * i + 3) % 31991 for i in range(1600)]
    nblocks = (len(prompt) - 1) // 16
    drt_a, kvbm_a, eng_a = await _int_worker(
        main, link_gbps=cal.HANDOFF_GBPS
    )
    server = None
    drt_b = kvbm_b = eng_b = client = None
    before = FAULTS.snapshot().get("kvbm.corrupt_frame", 0)
    try:
        vocab = eng_a.runner.sim.vocab_size
        want = expected_stream(prompt, 4, vocab)
        base = await _int_generate(eng_a, prompt, n=4)
        await _int_wait_host(kvbm_a, nblocks)
        comp = drt_a.namespace("kv").component("tpu")
        server = await PeerBlockServer(
            drt_a, comp, kvbm_a, layout=_int_layout(), refresh_s=0.05,
            serve_link_gbps=eng_a.runner.sim.peer_link_gbps,
        ).start()

        drt_b, kvbm_b, eng_b = await _int_worker(main, sim_seed=2)
        comp_b = drt_b.namespace("kv").component("tpu")
        client = await PeerBlockClient(
            drt_b, comp_b, layout_fingerprint(_int_layout())
        ).start()
        chain = _int_chain(prompt)
        deadline = asyncio.get_running_loop().time() + 10
        while client.best_peer(chain)[1] < nblocks:
            if asyncio.get_running_loop().time() >= deadline:
                raise TimeoutError("G4 peer discovery never converged")
            await asyncio.sleep(0.02)
        kvbm_b.attach_peer_client(client)

        FAULTS.arm("kvbm.corrupt_frame", action, times=1)
        toks = await _int_generate(eng_b, prompt, n=4)
        FAULTS.disarm("kvbm.corrupt_frame")
        await kvbm_b.drain_pulls(timeout_s=20)
        injected = FAULTS.snapshot().get("kvbm.corrupt_frame", 0) - before
        snap = INTEGRITY.snapshot()
        return {
            "action": action,
            "injected": injected,
            "detected": snap["integrity_failures_peer"],
            "tier_split_clean": (
                snap["integrity_failures_total"]
                == snap["integrity_failures_peer"]
            ),
            "stream_identical": toks == want and base == want,
        }
    finally:
        FAULTS.disarm("kvbm.corrupt_frame")
        for eng in (eng_b, eng_a):
            if eng is not None:
                await eng.stop()
        if client is not None:
            await client.stop()
        if server is not None:
            await server.stop()
        for kvbm in (kvbm_b, kvbm_a):
            if kvbm is not None:
                await kvbm.stop()
        for drt in (drt_b, drt_a):
            if drt is not None:
                await drt.shutdown()


async def _ileg_disagg(main, rng, transport: str) -> dict:
    """Seams 4/5 — prefill→decode KV frames (tcp / native transfer
    agent): the receiver's checksum drops a corrupted frame like a lost
    one, the completeness ledger refuses to activate over the hole, and
    the request degrades to local recompute."""
    from dynamo_tpu.block_manager.integrity import INTEGRITY
    from dynamo_tpu.disagg import (
        DisaggConfig,
        DisaggRouter,
        DecodeOperator,
        PrefillQueue,
        PrefillWorker,
    )
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.mocker import MockerConfig, MockerEngine
    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.runtime.distributed import DistributedRuntime
    from dynamo_tpu.runtime.egress import PushRouter
    from dynamo_tpu.runtime.failover import FailoverEngine
    from dynamo_tpu.utils.faults import FAULTS
    from dynamo_tpu.utils.tracing import tracer

    INTEGRITY.reset()
    action = rng.choice(("flip", "truncate"))
    times = rng.randint(1, 2)
    vocab, osl, ns = 997, _INT_OSL, f"integ-{transport}"
    queue = PrefillQueue(main, ns)
    dis = DisaggRouter.__new__(DisaggRouter)
    dis.cfg = DisaggConfig(
        max_local_prefill_length=24, max_prefill_queue_size=64,
    )

    def ecfg(**kw) -> EngineConfig:
        return EngineConfig(
            model=ModelConfig.tiny_test(), num_blocks=256, max_num_seqs=4,
            max_model_len=512, dtype="float32", **kw,
        )

    # A dropped frame must degrade within the leg, not after a 30 s wait.
    eng_d = MockerEngine(
        ecfg(remote_kv_timeout_s=2.0),
        MockerConfig(vocab_size=vocab, seed=1, deterministic_tokens=True),
    )
    await eng_d.start()
    op = await DecodeOperator(eng_d, queue, dis, transport=transport).start()
    drt_d = await DistributedRuntime.in_process(
        store=main.store, bus=main.bus
    )
    inst = await drt_d.namespace(ns).component("w").endpoint(
        "generate"
    ).serve(op)
    eng_p = MockerEngine(
        ecfg(),
        MockerConfig(vocab_size=vocab, seed=2, deterministic_tokens=True),
    )
    await eng_p.start()
    pw = PrefillWorker(eng_p, queue).start()
    push = await PushRouter.create(
        main, f"{ns}.w.generate", connect_timeout_s=2.0
    )
    engine = FailoverEngine(push)

    before = FAULTS.snapshot().get("kvbm.corrupt_frame", 0)
    FAULTS.arm("kvbm.corrupt_frame", action, times=times)
    try:
        from dynamo_tpu.llm.protocols.common import (
            PreprocessedRequest,
            SamplingOptions,
            StopConditions,
        )
        from dynamo_tpu.runtime.engine import Context

        streams_ok = True
        # >max_local_prefill_length, so every request prefills REMOTELY
        # and its KV rides the corrupted wire back.
        for _ in range(4):
            prompt = [rng.randrange(1, vocab) for _ in range(48)]
            req = PreprocessedRequest(
                token_ids=list(prompt),
                sampling=SamplingOptions(temperature=0.0),
                stop=StopConditions(max_tokens=osl, ignore_eos=True),
            )
            ctx = Context(req.to_wire())
            out: list[int] = []

            async def _drain() -> None:
                async for item in engine.generate(ctx):
                    out.extend(item.get("token_ids", []))

            try:
                await asyncio.wait_for(_drain(), 30.0)
            finally:
                tracer().finish(ctx.id)
            streams_ok = streams_ok and (
                out == expected_stream(prompt, osl, vocab)
            )
        FAULTS.disarm("kvbm.corrupt_frame")
        injected = FAULTS.snapshot().get("kvbm.corrupt_frame", 0) - before
        snap = INTEGRITY.snapshot()
        return {
            "action": action,
            "transport": transport,
            "injected": injected,
            "detected": snap["integrity_failures_frame"],
            "tier_split_clean": (
                snap["integrity_failures_total"]
                == snap["integrity_failures_frame"]
            ),
            "degraded_requests": eng_d.degraded_requests,
            "stream_identical": streams_ok,
        }
    finally:
        FAULTS.disarm("kvbm.corrupt_frame")
        try:
            await inst.stop()
        except Exception:  # noqa: BLE001 — teardown
            pass
        await pw.stop()
        for eng in (eng_d, eng_p):
            await eng.stop()
        await drt_d.shutdown()


async def _ileg_overhead(main) -> dict:
    """The <2% gate: CRC seconds per crossing are measured directly and
    charged against every crossing a real serve causes — an analytic
    bound from measured components, immune to 2%-scale wall noise."""
    import numpy as np

    from dynamo_tpu.block_manager.integrity import block_checksum

    row = np.zeros(_int_layout().block_elems, np.float32)
    reps = 5000
    t0 = time.perf_counter()
    for _ in range(reps):
        block_checksum(row)
    crc_s = (time.perf_counter() - t0) / reps
    # Context figure: envelope throughput on a production-sized row.
    big = np.zeros(2 << 20, np.uint8)
    t0 = time.perf_counter()
    for _ in range(8):
        block_checksum(big)
    crc_gbps = (8 * big.nbytes) / max(time.perf_counter() - t0, 1e-9) / 1e9

    drt, kvbm, eng = await _int_worker(main)
    try:
        t0 = time.monotonic()
        for i in range(6):
            prompt = [(13 * i + j) % 31991 for j in range(1, 130)]
            await _int_generate(eng, prompt)
        await kvbm.drain_offers(20.0)
        wall = max(time.monotonic() - t0, 1e-9)
        stats = kvbm.stats()
        # Upper bound: every stored block is stamped once and verified
        # at most twice more (onboard + scrub) on its way back up.
        crossings = 3 * stats["host_registered"] + stats[
            "scrub_scanned_total"
        ]
        frac = crossings * crc_s / wall
        return {
            "crc_us_per_block": round(crc_s * 1e6, 3),
            "crc_gbps": round(crc_gbps, 2),
            "crossings": crossings,
            "serve_wall_s": round(wall, 3),
            "overhead_fraction": round(frac, 6),
        }
    finally:
        await eng.stop()
        await kvbm.stop()
        await drt.shutdown()


async def run_integrity(seed: int = 20260806) -> dict:
    import tempfile

    from dynamo_tpu.runtime.distributed import DistributedRuntime
    from dynamo_tpu.utils.faults import FAULTS

    rng = random.Random(seed)
    main_drt = await DistributedRuntime.in_process()
    try:
        with tempfile.TemporaryDirectory(prefix="integ-g3-") as tmp:
            host = await _ileg_host_onboard(main_drt, rng)
            disk = await _ileg_disk_scrub(main_drt, rng, tmp)
            peer = await _ileg_peer_pull(main_drt, rng)
            tcp = await _ileg_disagg(main_drt, rng, "tcp")
            native = await _ileg_disagg(main_drt, rng, "native")
            overhead = await _ileg_overhead(main_drt)
    finally:
        FAULTS.clear()
        await main_drt.shutdown()
    return {
        "seed": seed,
        "host_onboard": host,
        "disk_scrub": disk,
        "peer_pull": peer,
        "disagg_tcp": tcp,
        "disagg_native": native,
        "overhead": overhead,
    }


def run_integrity_gates(report: dict) -> list[str]:
    """Hard gates (ISSUE 18). Returns
    failures; empty means every injected corruption was detected on the
    right tier and zero streams diverged."""
    failures: list[str] = []
    for leg in (
        "host_onboard", "disk_scrub", "peer_pull",
        "disagg_tcp", "disagg_native",
    ):
        r = report[leg]
        if not r["stream_identical"]:
            failures.append(f"{leg}: stream DIVERGED from the closed form")
        if r["injected"] < 1:
            failures.append(f"{leg}: schedule injected no corruption")
        if r["detected"] != r["injected"]:
            failures.append(
                f"{leg}: detected {r['detected']} != injected "
                f"{r['injected']} — corruption escaped the envelope"
            )
        if not r["tier_split_clean"]:
            failures.append(f"{leg}: corruption attributed to the wrong tier")
    d = report["disk_scrub"]
    if d["scrub_detected"] != d["injected"]:
        failures.append(
            f"disk_scrub: scrubber found {d['scrub_detected']} of "
            f"{d['injected']} rotten block(s)"
        )
    for leg in ("disagg_tcp", "disagg_native"):
        if report[leg]["degraded_requests"] < 1:
            failures.append(
                f"{leg}: no request degraded to recompute (ledger hole "
                f"went unnoticed)"
            )
    ov = report["overhead"]
    if ov["overhead_fraction"] >= 0.02:
        failures.append(
            f"overhead: envelope costs {ov['overhead_fraction']:.2%} of "
            f"serve time (gate 2%)"
        )
    return failures


def main(argv: list[str] | None = None) -> int:
    import argparse
    import json

    ap = argparse.ArgumentParser(
        prog="python benchmarks/chaos_bench.py",
        description="seeded chaos-schedule proof over a mocker fleet",
    )
    ap.add_argument("--seed", type=int,
                    default=int(os.environ.get("BENCH_CHAOS_SEED", 1234)))
    ap.add_argument("--workers", type=int,
                    default=int(os.environ.get("BENCH_CHAOS_WORKERS", 4)))
    ap.add_argument("--requests", type=int,
                    default=int(os.environ.get("BENCH_CHAOS_REQUESTS", 24)))
    args = ap.parse_args(argv)
    logging.basicConfig(level=logging.WARNING)
    report = asyncio.run(run_chaos(
        seed=args.seed, decode_workers=args.workers,
        requests=args.requests,
    ))
    print(json.dumps(report, indent=2))
    run_gates(report)
    print("chaos gates: OK", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
