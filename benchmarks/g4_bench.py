"""G4 peer-tier proof: pull-vs-recompute win, predictive pre-placement,
and the mid-pull peer-death degrade.

``BENCH_G4=1 python bench.py`` (ci.sh "mocker G4 peer tier" leg) runs
three legs over in-process mocker fleets — the FULL G4 planes: blockset
discovery on the store, paced block serving over the transfer plane,
the admission-time pricing law, and the engine park/resume path
(docs/architecture/kvbm_g4.md):

1. **Pull win** — a cold worker whose prompt prefix lives only on a
   fleet peer must reach first token ≥2× faster by PULLING the packed
   rows (priced against the calibrated link,
   planner/calibration.HANDOFF_GBPS) than an identical cold worker
   recomputing the same prompt. The serve side is paced by the mocker
   peer-link model (``MockerConfig.peer_link_gbps`` →
   ``PeerBlockServer.serve_link_gbps``), so the win is measured against
   simulated DCN time, not loopback memcpy.

2. **Predictive pre-placement** — a popularity-skewed prefix workload
   feeds :class:`~dynamo_tpu.block_manager.peer.PrefixHeat`; a joining
   cold worker that gets ``preplace()``'d (the FleetPlanner
   ``on_scale_up`` hook's payload) must reach steady-state WARM hit
   rate ≥2× faster (in requests) than the same join without
   pre-placement. "Warm" counts G1/G2 hits only — an on-demand G4 pull
   still parks the first toucher, which is exactly the latency
   pre-placement deletes.

3. **Peer death mid-pull** — with the transfer held in flight
   (``kvbm.peer_pull`` delay seam) the serving peer is KILLED; the
   parked request must complete via local recompute within its
   deadline — byte-identical stream, counted degraded, fallback on the
   G4 counters, ZERO hangs under the watchdog.

Seeded (``BENCH_G4_SEED``): one seed replays one trace/schedule.
"""

# dynarace: context[loop]

from __future__ import annotations

import asyncio
import logging
import os
import random
import sys
import time

if __package__ in (None, ""):  # `python benchmarks/g4_bench.py`
    sys.path.insert(
        0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )

logger = logging.getLogger(__name__)

#: Mirrors mocker det_next_token — the closed-form greedy stream.
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


def _ecfg(**kw):
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.models.config import ModelConfig

    kw.setdefault("num_blocks", 192)
    kw.setdefault("max_num_seqs", 4)
    kw.setdefault("max_model_len", 2048)
    # The G2→G1 adaptive gate's probe ramp is the offload bench's story;
    # these legs measure tier PLACEMENT, so onboard the full match.
    kw.setdefault("kvbm_adaptive_gate", False)
    return EngineConfig(model=ModelConfig.tiny_test(), dtype="float32", **kw)


def _layout():
    from dynamo_tpu.block_manager import KvLayoutConfig

    # block_elems == 8: the mocker runner's 8-float block rows.
    return KvLayoutConfig(
        num_layers=1, page_size=1, num_kv_heads=1, head_dim=4,
        dtype="float32",
    )


async def _generate(engine, prompt, n=4):
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
    out: list[int] = []
    ttft = None
    t0 = time.monotonic()
    async for item in engine.generate(Context(req.to_wire())):
        if ttft is None:
            ttft = time.monotonic() - t0
        out += item.get("token_ids", [])
    return out, (ttft if ttft is not None else time.monotonic() - t0)


async def _spawn_worker(main, *, cfg=None, link_gbps=0.0, host_blocks=128,
                        on_kv_actual=None):
    """One mocker worker on the shared fleet planes: runtime (own
    lease), KVBM, engine. Returns (drt, kvbm, engine)."""
    from dynamo_tpu.block_manager import KvbmConfig, KvBlockManager
    from dynamo_tpu.mocker.engine import MockerConfig, MockerEngine
    from dynamo_tpu.runtime.distributed import DistributedRuntime

    from dynamo_tpu.planner import calibration as cal

    drt = await DistributedRuntime.in_process(
        store=main.store, bus=main.bus
    )
    kvbm = await KvBlockManager(
        KvbmConfig(layout=_layout(), host_blocks=host_blocks)
    ).start()
    eng = MockerEngine(
        cfg or _ecfg(),
        MockerConfig(
            seed=1,
            deterministic_tokens=True,
            peer_link_gbps=link_gbps,
            # Pin prefill cost to the calibrated r04 rate so the
            # recompute side of every pull-vs-recompute comparison is
            # the same one the pricing law uses (planner/calibration).
            prefill_time_per_token_us=cal.PREFILL_TIME_PER_TOKEN_US,
        ),
        block_manager=kvbm,
        on_kv_actual=on_kv_actual,
    )
    await eng.start()
    return drt, kvbm, eng


async def _export_peer(drt, kvbm, eng):
    """Export a worker's host tier as a G4 peer, paced at the worker's
    configured simulated link (MockerConfig.peer_link_gbps)."""
    from dynamo_tpu.block_manager.peer import PeerBlockServer

    comp = drt.namespace("kv").component("tpu")
    return await PeerBlockServer(
        drt, comp, kvbm, layout=_layout(), refresh_s=0.05,
        serve_link_gbps=eng.runner.sim.peer_link_gbps,
    ).start()


async def _attach_client(drt, kvbm, want_hashes, depth, timeout=10.0):
    """A G4 client on ``drt``, attached to ``kvbm`` once discovery shows
    a peer holding ``depth`` blocks of ``want_hashes``."""
    from dynamo_tpu.block_manager.peer import (
        PeerBlockClient,
        layout_fingerprint,
    )

    comp = drt.namespace("kv").component("tpu")
    # Handshake on the mocker layout, but price with the calibrated
    # default geometry (no layout_cfg): the 8-float sim rows are not
    # real KV bytes — pricing them as such would make every pull lose
    # to recomputing "one token", a simulation artifact.
    client = await PeerBlockClient(
        drt, comp, layout_fingerprint(_layout())
    ).start()
    deadline = asyncio.get_running_loop().time() + timeout
    while client.best_peer(want_hashes)[1] < depth:
        if asyncio.get_running_loop().time() >= deadline:
            raise TimeoutError("G4 peer discovery never converged")
        await asyncio.sleep(0.02)
    kvbm.attach_peer_client(client)
    return client


def _chain(tokens, block_size=16):
    from dynamo_tpu.llm.tokens import TokenBlockSequence

    return TokenBlockSequence.from_tokens(
        tokens, block_size=block_size
    ).sequence_hashes()


async def _wait_host(kvbm, n, timeout=10.0):
    deadline = asyncio.get_running_loop().time() + timeout
    while kvbm.stats()["host_registered"] < n:
        if asyncio.get_running_loop().time() >= deadline:
            raise TimeoutError(
                f"host tier never reached {n} registered blocks "
                f"(at {kvbm.stats()['host_registered']})"
            )
        await asyncio.sleep(0.02)


# ---------------------------------------------------------------------------
# leg 1: pull beats recompute at the calibrated link
# ---------------------------------------------------------------------------

async def _leg_pull_win(main) -> dict:
    from dynamo_tpu.planner import calibration as cal

    prompt = [(7 * i + 3) % 31991 for i in range(1600)]  # 100 blocks
    drt_a, kvbm_a, eng_a = await _spawn_worker(
        main, link_gbps=cal.HANDOFF_GBPS
    )
    server = None
    drt_b = kvbm_b = eng_b = client = None
    drt_c = kvbm_c = eng_c = None
    try:
        cold_toks, _ = await _generate(eng_a, prompt)
        prefix_blocks = (len(prompt) - 1) // 16
        await _wait_host(kvbm_a, prefix_blocks)
        server = await _export_peer(drt_a, kvbm_a, eng_a)

        # B: cold, peer-attached — parks at admission, pulls, resumes.
        drt_b, kvbm_b, eng_b = await _spawn_worker(main)
        client = await _attach_client(
            drt_b, kvbm_b, _chain(prompt), prefix_blocks
        )
        pulled_toks, ttft_pull = await _generate(eng_b, prompt)

        # C: cold, NO peer client — the recompute baseline.
        drt_c, kvbm_c, eng_c = await _spawn_worker(main)
        recomputed_toks, ttft_recompute = await _generate(eng_c, prompt)

        rd = eng_b.readiness()
        return {
            "prompt_tokens": len(prompt),
            "prefix_blocks": prefix_blocks,
            "ttft_pull_ms": round(ttft_pull * 1e3, 2),
            "ttft_recompute_ms": round(ttft_recompute * 1e3, 2),
            "speedup": round(ttft_recompute / max(ttft_pull, 1e-9), 2),
            "streams_identical": (
                pulled_toks == cold_toks == recomputed_toks
            ),
            "pulls_total": rd["kvbm_g4_pulls_total"],
            "pull_bytes_total": rd["kvbm_g4_pull_bytes_total"],
            "reused_peer_blocks": rd["kv_reused_peer_blocks_total"],
            "link_peer_bps": rd["kvbm_link_peer_bps"],
        }
    finally:
        for eng in (eng_b, eng_c, eng_a):
            if eng is not None:
                await eng.stop()
        if client is not None:
            await client.stop()
        if server is not None:
            await server.stop()
        for kvbm in (kvbm_b, kvbm_c, kvbm_a):
            if kvbm is not None:
                await kvbm.stop()
        for drt in (drt_b, drt_c, drt_a):
            if drt is not None:
                await drt.shutdown()


# ---------------------------------------------------------------------------
# leg 2: predictive pre-placement — cold join reaches steady state faster
# ---------------------------------------------------------------------------

_PREFIX_BLOCKS = 4          # 64-token shared prefixes
_STEADY_WINDOW = 6          # consecutive warm requests = steady state


def _prefix_tokens(p: int) -> list[int]:
    # Distinct leading token per prefix -> distinct hash chains.
    return [(p + 1) * 1000 + i for i in range(_PREFIX_BLOCKS * 16)]


def _join_trace(rng, prefixes: int, requests: int) -> list[int]:
    """Popularity-skewed prefix draws; every prefix appears at least
    once so the no-preplace join must first-touch all of them."""
    pop = [max(prefixes - p, 1) for p in range(prefixes)]
    trace = list(range(prefixes))
    trace += rng.choices(range(prefixes), weights=pop,
                         k=requests - prefixes)
    rng.shuffle(trace)
    return trace


async def _join_and_serve(main, heat, trace, preplaced: bool) -> dict:
    """One cold join serving ``trace``; returns its warm-up curve."""
    from dynamo_tpu.block_manager.peer import preplace

    hot = heat.hottest(1)[0]
    drt, kvbm, eng = None, None, None
    client = None
    actuals: list[dict] = []
    try:
        drt, kvbm, eng = await _spawn_worker(
            main, host_blocks=96, on_kv_actual=actuals.append
        )
        client = await _attach_client(drt, kvbm, hot, _PREFIX_BLOCKS)
        preplaced_blocks = 0
        if preplaced:
            preplaced_blocks = await preplace(
                client, kvbm, heat, top_k=64
            )
        warm: list[bool] = []
        for i, p in enumerate(trace):
            tail = [29000 + i * 8 + j for j in range(8)]
            pulls_before = client.pulls_total
            await _generate(eng, _prefix_tokens(p) + tail, n=2)
            rec = actuals[-1]
            # Warm = the prefix was served from tiers already ON this
            # worker (G1/G2 — including pre-placed peer-origin rows)
            # with no new G4 pull: a first-touch on-demand pull parks
            # the request on the transfer, which is exactly the latency
            # pre-placement deletes.
            warm.append(
                rec["device_blocks"] + rec["host_blocks"]
                + rec["peer_blocks"] >= _PREFIX_BLOCKS
                and client.pulls_total == pulls_before
            )
        steady = len(trace) + _STEADY_WINDOW  # sentinel: never steady
        for i in range(_STEADY_WINDOW, len(trace) + 1):
            if all(warm[i - _STEADY_WINDOW:i]):
                steady = i
                break
        return {
            "requests": len(trace),
            "warm_hits": sum(warm),
            "requests_to_steady": steady,
            "preplaced_blocks": preplaced_blocks,
        }
    finally:
        if eng is not None:
            await eng.stop()
        if client is not None:
            await client.stop()
        if kvbm is not None:
            await kvbm.stop()
        if drt is not None:
            await drt.shutdown()


async def _leg_preplace(main, seed: int, prefixes: int,
                        join_requests: int) -> dict:
    from dynamo_tpu.block_manager.peer import PrefixHeat

    rng = random.Random(seed)
    drt_a, kvbm_a, eng_a = await _spawn_worker(main, host_blocks=96)
    server = None
    try:
        # Warm the donor with the full prefix set; heat mirrors the
        # popularity the router would have observed.
        heat = PrefixHeat(decay=0.995)
        pop = [max(prefixes - p, 1) for p in range(prefixes)]
        for p in range(prefixes):
            toks = _prefix_tokens(p) + [28000 + p]
            await _generate(eng_a, toks, n=2)
            heat.note(_chain(_prefix_tokens(p)), weight=pop[p])
        await _wait_host(kvbm_a, prefixes * _PREFIX_BLOCKS)
        server = await _export_peer(drt_a, kvbm_a, eng_a)

        trace = _join_trace(rng, prefixes, join_requests)
        nopre = await _join_and_serve(main, heat, trace, preplaced=False)
        pre = await _join_and_serve(main, heat, trace, preplaced=True)
        return {
            "prefixes": prefixes,
            "join_requests": join_requests,
            "no_preplace": nopre,
            "preplace": pre,
            "speedup": round(
                nopre["requests_to_steady"]
                / max(pre["requests_to_steady"], 1),
                2,
            ),
        }
    finally:
        await eng_a.stop()
        if server is not None:
            await server.stop()
        await kvbm_a.stop()
        await drt_a.shutdown()


# ---------------------------------------------------------------------------
# leg 3: peer killed mid-pull — recompute, degraded, zero hangs
# ---------------------------------------------------------------------------

async def _leg_peer_death(main) -> dict:
    from dynamo_tpu.utils.faults import FAULTS

    prompt = [(11 * i + 5) % 31991 for i in range(40)]
    drt_a, kvbm_a, eng_a = await _spawn_worker(main)
    server = None
    drt_b = kvbm_b = eng_b = client = None
    try:
        _cold, _ = await _generate(eng_a, prompt)
        await _wait_host(kvbm_a, 2)
        server = await _export_peer(drt_a, kvbm_a, eng_a)

        drt_b, kvbm_b, eng_b = await _spawn_worker(
            main, cfg=_ecfg(kvbm_peer_timeout_s=0.5)
        )
        client = await _attach_client(drt_b, kvbm_b, _chain(prompt), 2)

        # Hold the transfer in flight, then kill the serving peer under
        # it — the deadline must resume the request via recompute.
        FAULTS.arm("kvbm.peer_pull", "delay", times=None, delay_s=5.0)
        task = asyncio.ensure_future(_generate(eng_b, prompt))
        deadline = asyncio.get_running_loop().time() + 10
        while not eng_b._peer_parked:
            if asyncio.get_running_loop().time() >= deadline:
                raise TimeoutError("request never parked on the pull")
            await asyncio.sleep(0.01)
        await server.stop()
        server = None
        toks, _ttft = await asyncio.wait_for(task, timeout=30)

        vocab = eng_b.runner.sim.vocab_size
        rd = eng_b.readiness()
        return {
            "completed": True,
            "stream_identical": toks == expected_stream(prompt, 4, vocab),
            "degraded_requests": eng_b.degraded_requests,
            "pull_fallbacks_total": rd["kvbm_g4_pull_fallbacks_total"],
            "reused_peer_blocks": rd["kv_reused_peer_blocks_total"],
        }
    finally:
        FAULTS.disarm("kvbm.peer_pull")
        for eng in (eng_b, eng_a):
            if eng is not None:
                await eng.stop()
        if kvbm_b is not None:
            try:
                await kvbm_b.drain_pulls(timeout_s=10)
            except TimeoutError:
                pass
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


# ---------------------------------------------------------------------------
# harness
# ---------------------------------------------------------------------------

async def run_g4(
    seed: int = 20260806,
    prefixes: int = 8,
    join_requests: int = 24,
) -> dict:
    from dynamo_tpu.runtime.distributed import DistributedRuntime

    main = await DistributedRuntime.in_process()
    try:
        pull = await _leg_pull_win(main)
        pre = await _leg_preplace(main, seed, prefixes, join_requests)
        death = await _leg_peer_death(main)
    finally:
        await main.shutdown()
    return {"seed": seed, "pull": pull, "preplace": pre,
            "peer_death": death}


def run_gates(report: dict) -> list[str]:
    """Hard gates. Returns failures."""
    failures: list[str] = []
    pull = report["pull"]
    if not pull["streams_identical"]:
        failures.append("pull: streams diverged across the tier")
    if pull["speedup"] < 2.0:
        failures.append(
            f"pull: TTFT speedup {pull['speedup']}x < 2x "
            f"(pull {pull['ttft_pull_ms']} ms vs recompute "
            f"{pull['ttft_recompute_ms']} ms)"
        )
    if pull["pulls_total"] < 1 or pull["reused_peer_blocks"] < 1:
        failures.append("pull: no G4 pull was actually taken")
    pre = report["preplace"]
    if pre["speedup"] < 2.0:
        failures.append(
            f"preplace: steady-state speedup {pre['speedup']}x < 2x "
            f"(no-preplace {pre['no_preplace']['requests_to_steady']} "
            f"vs preplace {pre['preplace']['requests_to_steady']} "
            "requests)"
        )
    if pre["preplace"]["preplaced_blocks"] < 1:
        failures.append("preplace: nothing was pre-placed")
    death = report["peer_death"]
    if not death["completed"]:
        failures.append("peer_death: request hung")
    if not death["stream_identical"]:
        failures.append("peer_death: recomputed stream diverged")
    if death["degraded_requests"] != 1:
        failures.append(
            f"peer_death: degraded_requests "
            f"{death['degraded_requests']} != 1"
        )
    if death["pull_fallbacks_total"] < 1:
        failures.append("peer_death: fallback not counted on G4 surface")
    if death["reused_peer_blocks"] != 0:
        failures.append("peer_death: phantom peer reuse counted")
    return failures


if __name__ == "__main__":
    logging.basicConfig(level=logging.INFO)
    rep = asyncio.run(run_g4())
    import json

    print(json.dumps(rep, indent=2))
    fails = run_gates(rep)
    if fails:
        print("GATES FAILED:\n  " + "\n  ".join(fails), file=sys.stderr)
        raise SystemExit(1)
    print("all G4 gates passed")
