"""KV observatory tests (docs/architecture/observability.md "KV
observatory"): route-decision auditing, indexer staleness measurement,
sharded-indexer equivalence/determinism, aggregator failure counting +
stale-after-TTL endpoints, KVBM tier telemetry, engine-side actual-reuse
reporting with gauge↔ForwardPassMetrics sync, and the
benchmarks/route_audit.py join tool."""

import asyncio
import time
from types import SimpleNamespace

import numpy as np
import pytest

from dynamo_tpu.block_manager import (
    KvbmConfig,
    KvBlockManager,
    KvLayoutConfig,
)
from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.llm.kv_router.audit import RouteAuditRecord, RouteObservatory
from dynamo_tpu.llm.kv_router.indexer import KvIndexer, KvIndexerSharded
from dynamo_tpu.llm.kv_router.metrics_aggregator import KvMetricsAggregator
from dynamo_tpu.llm.kv_router.protocols import (
    ForwardPassMetrics,
    KvCacheEventData,
    RouterEvent,
)
from dynamo_tpu.llm.kv_router.scheduler import (
    DefaultWorkerSelector,
    KvRouterConfig,
)
from dynamo_tpu.llm.protocols.common import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.mocker import MockerConfig, MockerEngine
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.runtime.engine import Context
from dynamo_tpu.utils.faults import FAULTS

pytestmark = pytest.mark.anyio


def _stored(hashes, parent=None, published=None):
    return RouterEvent(
        worker_id=hashes[0] % 7 + 1,
        event=KvCacheEventData(kind="stored", block_hashes=hashes,
                               parent_hash=parent),
        published_unix=published,
    )


# ---------------------------------------------------------------------------
# selector: full candidate field on the decision
# ---------------------------------------------------------------------------


def test_selector_exposes_all_candidates():
    from dynamo_tpu.llm.kv_router.metrics_aggregator import ProcessedEndpoints

    sel = DefaultWorkerSelector(KvRouterConfig(), seed=0)
    eps = ProcessedEndpoints(
        metrics={
            1: ForwardPassMetrics(kv_active_blocks=10, kv_total_blocks=100),
            2: ForwardPassMetrics(kv_active_blocks=90, kv_total_blocks=100,
                                  num_requests_waiting=3),
        }
    )
    d = sel.select(eps, {1: 4}, isl=64)
    assert d.worker_id == 1
    assert {c["worker"] for c in d.candidates} == {1, 2}
    loser = next(c for c in d.candidates if c["worker"] == 2)
    winner = next(c for c in d.candidates if c["worker"] == 1)
    # The audit record can explain WHY 2 lost: lower logit, higher usage.
    assert loser["logit"] < winner["logit"]
    assert loser["usage"] > winner["usage"]
    assert winner["overlap_blocks"] == 4


# ---------------------------------------------------------------------------
# indexer staleness
# ---------------------------------------------------------------------------


async def test_indexer_staleness_accounting():
    idx = KvIndexer().start()
    now = time.time()
    idx.apply(_stored([1, 2], published=now - 0.06))
    idx.apply(_stored([3], parent=2, published=now - 0.06))
    assert idx.pending_events == 2  # nothing applied until the loop runs
    await idx.find_matches([1, 2, 3])
    st = idx.stats()
    assert st["kv_events_applied_total"] == 2
    assert st["kv_events_pending"] == 0
    assert st["kv_event_lag_count"] == 2
    # Events were published ~60ms before apply — the lag histogram must
    # see it (bucketed: the 50/100ms buckets).
    assert st["kv_event_lag_max_ms"] >= 50.0
    assert st["kv_radix_blocks"] == 3
    wm = idx.watermark()
    assert wm["applied"] == 2 and wm["pending"] == 0
    assert "lag_p99_ms" in wm
    # Radix eviction counter: removing every holder prunes the chain
    # ([1,2] landed under worker 2, [3] under worker 4 — _stored keys
    # the worker off the first hash).
    for wid in (2, 4):
        idx.apply(RouterEvent(wid, KvCacheEventData(kind="cleared")))
    await idx.find_matches([1])
    assert idx.stats()["kv_radix_evicted_blocks_total"] >= 3
    await idx.stop()


async def test_indexer_direct_apply_path_counts_too():
    """The consumer-dead fallback (find_matches drains directly) must use
    the same accounting funnel — counters can't diverge from the tree."""
    idx = KvIndexer()  # never started: no consumer task
    idx.apply(_stored([10, 11], published=time.time()))
    assert await idx.find_matches([10, 11]) != {}
    assert idx.events_applied_total == 2 or idx.events_applied_total == 1
    # (one RouterEvent holding two hashes applies as ONE event)
    assert idx.events_applied_total == 1
    assert idx.stats()["kv_event_lag_count"] == 1


async def test_sharded_equivalence_and_determinism():
    """Same event stream ⇒ a sharded indexer answers find_matches
    identically to the unsharded one, and two sharded replicas build
    identical per-shard states (the ROADMAP #5 fan-out invariant)."""
    events = []
    for w in range(1, 6):
        chain = [w * 100 + i for i in range(4)]
        parent = None
        for h in chain:
            events.append(
                RouterEvent(w, KvCacheEventData(
                    kind="stored", block_hashes=[h], parent_hash=parent
                ), published_unix=time.time())
            )
            parent = h

    flat = KvIndexer().start()
    shard_a = KvIndexerSharded(4).start()
    shard_b = KvIndexerSharded(4).start()
    for ev in events:
        flat.apply(ev)
        shard_a.apply(ev)
        shard_b.apply(ev)

    queries = [[100, 101, 102, 103], [300, 301], [500, 999], [42]]
    for q in queries:
        expect = await flat.find_matches(q)
        assert await shard_a.find_matches(q) == expect
        assert await shard_b.find_matches(q) == expect

    # Deterministic fan-out: both replicas applied the same events to the
    # same shard slots.
    counts_a = [s.events_applied_total for s in shard_a.shards]
    counts_b = [s.events_applied_total for s in shard_b.shards]
    assert counts_a == counts_b
    assert sum(counts_a) == len(events)
    st = shard_a.stats()
    assert st["kv_events_applied_total"] == len(events)
    assert st["kv_indexer_shards"] == 4
    await asyncio.gather(flat.stop(), shard_a.stop(), shard_b.stop())


async def test_sharded_staleness_under_delayed_apply_fault():
    """utils/faults.py `indexer.apply` delay = a replica falling behind
    the bus: pending depth must be visible mid-lag, queries must still
    return the complete answer after the drain, and the lag histogram
    must record the delay."""
    idx = KvIndexerSharded(2).start()
    try:
        FAULTS.arm("indexer.apply", "delay", times=4, delay_s=0.05)
        t0 = time.time()
        for w in (1, 2, 3, 4):
            idx.apply(RouterEvent(w, KvCacheEventData(
                kind="stored", block_hashes=[w * 10]
            ), published_unix=t0))
        await asyncio.sleep(0.02)  # consumers now sleeping in the fault
        assert idx.pending_events > 0
        wm = idx.watermark()
        assert wm["pending"] > 0 and len(wm["per_shard_pending"]) == 2
        # The query drains through the delay and still sees everything.
        got = await idx.find_matches([10])
        assert got == {1: 1}
        st = idx.stats()
        assert st["kv_events_applied_total"] == 4
        assert st["kv_events_pending"] == 0
        assert st["kv_event_lag_count"] == 4
        assert st["kv_event_lag_max_ms"] >= 25.0  # delay showed up as lag
    finally:
        FAULTS.disarm("indexer.apply")
        await idx.stop()


async def test_indexer_apply_drop_fault_counts_dropped():
    idx = KvIndexer().start()
    try:
        FAULTS.arm("indexer.apply", "drop", times=1)
        idx.apply(_stored([77], published=time.time()))
        await asyncio.sleep(0.05)
        assert await idx.find_matches([77]) == {}  # event was dropped
        assert idx.events_dropped_total == 1
        assert idx.events_applied_total == 0
    finally:
        FAULTS.disarm("indexer.apply")
        await idx.stop()


# ---------------------------------------------------------------------------
# aggregator: failure counting + stale-after-TTL
# ---------------------------------------------------------------------------


class _StubRouter:
    def __init__(self, ids):
        self.ids = ids
        self.client = SimpleNamespace(
            instances=lambda: [SimpleNamespace(instance_id=i) for i in self.ids]
        )


async def test_aggregator_counts_failures_and_drops_after_ttl():
    agg = KvMetricsAggregator(None, None, endpoint_ttl_s=0.15)
    agg._router = _StubRouter([1, 2])
    failing: set[int] = set()

    async def scrape_one(iid):
        if iid in failing:
            raise RuntimeError("endpoint down")
        return ForwardPassMetrics(kv_active_blocks=iid)

    agg._scrape_one = scrape_one

    eps = await agg.scrape()
    assert set(eps.metrics) == {1, 2}
    assert agg.scrape_failures_total == 0

    # Transient blip: the failure is COUNTED but the last-known snapshot
    # is retained (routing doesn't flap on one timeout).
    failing.add(2)
    eps = await agg.scrape()
    assert agg.scrape_failures_total == 1
    assert set(eps.metrics) == {1, 2}
    assert eps.metrics[2].kv_active_blocks == 2  # last-known value

    # Past the TTL the dead worker's stale load stops being scoreable.
    await asyncio.sleep(0.2)
    eps = await agg.scrape()
    assert set(eps.metrics) == {1}
    assert agg.stale_endpoint_drops_total >= 1
    assert agg.scrape_failures_total == 2

    # Staleness of the WHOLE snapshot (scrape loop dead): age > TTL.
    assert not agg.stale
    await asyncio.sleep(0.2)
    assert agg.stale


# ---------------------------------------------------------------------------
# route observatory
# ---------------------------------------------------------------------------


def test_route_observatory_ring_and_gauges():
    obs = RouteObservatory(capacity=2)
    for i in range(3):
        obs.record(RouteAuditRecord(
            request_id=f"r{i}", trace_id=f"t{i}", worker_id=i,
            overlap_blocks=i, isl_blocks=4, logit=0.5, decision_ms=1.0,
            indexer={"applied": 7, "pending": 0},
        ))
    snap = obs.snapshot(8)
    assert snap["routes_total"] == 3
    assert snap["predicted_blocks_total"] == 0 + 1 + 2
    assert len(snap["recent"]) == 2  # bounded ring
    rec = snap["recent"][-1]
    assert rec["kind"] == "route" and rec["trace"] == "t2"
    assert rec["indexer"]["applied"] == 7

    obs.register_provider(lambda: {"kv_events_applied_total": 5})
    obs.register_provider(lambda: {"kv_events_applied_total": 3})
    g = obs.gauges()
    assert g["kv_router_routes_total"] == 3.0
    assert g["kv_events_applied_total"] == 8.0  # providers sum
    # A broken provider must not take down the gauge merge.
    obs.register_provider(lambda: 1 / 0)
    assert obs.gauges()["kv_router_routes_total"] == 3.0


# ---------------------------------------------------------------------------
# KVBM tier telemetry
# ---------------------------------------------------------------------------

_LAYOUT8 = KvLayoutConfig(
    num_layers=1, page_size=1, num_kv_heads=1, head_dim=4, dtype="float32"
)  # block_elems == 1*2*1*1*4 == 8: the mocker runner's 8-float block rows


def _row(seed: float) -> np.ndarray:
    return np.full((_LAYOUT8.block_elems,), seed, np.float32)


async def _settle(mgr, n):
    deadline = asyncio.get_running_loop().time() + 5
    while mgr.stats()["host_registered"] < n:
        assert asyncio.get_running_loop().time() < deadline
        await asyncio.sleep(0.02)


async def test_kvbm_stats_counters_and_disk_origin(tmp_path):
    mgr = await KvBlockManager(
        KvbmConfig(
            layout=_LAYOUT8, host_blocks=4, disk_blocks=8,
            disk_path=str(tmp_path / "g3.bin"),
        )
    ).start()
    try:
        mgr.offer(100, None, [1] * 4, _row(1.0))
        mgr.offer(200, 100, [2] * 4, _row(2.0))
        await _settle(mgr, 2)
        if mgr._g2_to_g3 is not None:
            await mgr._g2_to_g3.drain()
        st = mgr.stats()
        assert st["host_stored_blocks_total"] == 2
        assert st["offloaded_blocks_total"] == 2      # chained down-tier
        assert st["link_g1g2_bps"] > 0
        assert st["link_g2g3_bps"] > 0
        assert st["disk_registered"] == 2

        # Host-prefix accounting: 2 hits + 1 miss.
        assert mgr.count_host_match([100, 200, 999]) == 2
        st = mgr.stats()
        assert st["host_hit_blocks_total"] == 2
        assert st["host_miss_blocks_total"] == 1

        # Evict the host tier (LRU pressure), then promote back from disk.
        blocks = mgr.host_pool.allocate_blocks(4)
        for b in blocks:
            mgr.host_pool.release(b)
        assert mgr.stats()["host_evictions_total"] >= 2
        assert mgr.count_host_match([100, 200]) == 0

        n = await mgr.onboard_from_disk([100, 200])
        assert n == 2
        st = mgr.stats()
        assert st["promoted_blocks_total"] == 2
        assert st["link_g3g2_bps"] > 0
        # Disk-origin attribution: both host-resident blocks came via G3.
        assert mgr.count_disk_origin([100, 200]) == 2
        assert mgr.count_disk_origin([999]) == 0

        # Re-store from the DEVICE after another eviction: the G3-origin
        # marker must not survive — this reuse is device-fed, not disk.
        blocks = mgr.host_pool.allocate_blocks(4)
        for b in blocks:
            mgr.host_pool.release(b)
        assert mgr.count_host_match([100]) == 0
        mgr.offer(100, None, [1] * 4, _row(1.0))
        await _settle(mgr, 1)
        assert mgr.count_disk_origin([100]) == 0
    finally:
        await mgr.stop()


# ---------------------------------------------------------------------------
# engine: actual-reuse reporting, tier split, gauge sync
# ---------------------------------------------------------------------------


def _ecfg():
    return EngineConfig(
        model=ModelConfig.tiny_test(),
        num_blocks=64,
        max_num_seqs=4,
        max_model_len=256,
        dtype="float32",
    )


async def _generate(engine, prompt, n=4):
    req = PreprocessedRequest(
        token_ids=list(prompt),
        sampling=SamplingOptions(temperature=0.0),
        stop=StopConditions(max_tokens=n, ignore_eos=True),
    )
    out = []
    async for item in engine.generate(Context(req.to_wire())):
        out += item.get("token_ids", [])
    return out


async def test_engine_reports_actuals_split_by_tier():
    """Engine A computes a prompt cold (actual reuse 0) then warm (device
    tier); a FRESH engine B sharing the host tier reuses via G2 — and
    every path lands a kv_actual record with the right split, cumulative
    counters, readiness gauges, and ForwardPassMetrics fields in sync."""
    kvbm = await KvBlockManager(
        KvbmConfig(layout=_LAYOUT8, host_blocks=16)
    ).start()
    actuals_a: list[dict] = []
    metrics_a: list[dict] = []
    eng_a = MockerEngine(
        _ecfg(), MockerConfig(seed=1), block_manager=kvbm,
        on_kv_actual=actuals_a.append, on_metrics=metrics_a.append,
    )
    await eng_a.start()
    prompt = list(range(40))  # 2 full blocks + tail

    await _generate(eng_a, prompt)
    assert len(actuals_a) == 1
    cold = actuals_a[0]
    assert cold["kind"] == "kv_actual" and cold["isl_blocks"] == 3
    assert (cold["device_blocks"], cold["host_blocks"], cold["disk_blocks"]) \
        == (0, 0, 0)

    # Same prompt again on A: pure G1 (device) reuse.
    await _generate(eng_a, prompt)
    warm = actuals_a[1]
    assert warm["device_blocks"] == 2
    assert warm["host_blocks"] == 0 and warm["disk_blocks"] == 0
    assert eng_a._reused_device_blocks == 2

    # Gauge ↔ ForwardPassMetrics sync (the PR 8 coloc-style assertion):
    # the readiness snapshot, the metrics callback dict, and the wire
    # type must agree on every kv observatory key.
    rd = eng_a.readiness()
    assert rd["kv_reused_device_blocks_total"] == 2
    assert rd["kvbm_host_registered"] == kvbm.stats()["host_registered"]
    assert metrics_a, "metrics callback never fired"
    m = metrics_a[-1]
    fpm = ForwardPassMetrics.from_wire(m)
    for key in (
        "kv_reused_device_blocks_total",
        "kv_reused_host_blocks_total",
        "kv_reused_disk_blocks_total",
        "kvbm_host_registered",
        "kvbm_host_stored_blocks_total",
        "kvbm_host_hit_blocks_total",
    ):
        assert key in m, key
        assert getattr(fpm, key) == m[key] == rd[key], key
    await asyncio.sleep(0.3)  # offload pump: blocks → host tier
    await eng_a.stop()

    actuals_b: list[dict] = []
    eng_b = MockerEngine(
        _ecfg(), MockerConfig(seed=2), block_manager=kvbm,
        on_kv_actual=actuals_b.append,
    )
    await eng_b.start()
    await _generate(eng_b, prompt)
    assert len(actuals_b) == 1
    host = actuals_b[0]
    # Cold HBM, warm host tier: the reuse is G2, not G1.
    assert host["host_blocks"] == 2
    assert host["device_blocks"] == 0
    assert eng_b.readiness()["kv_reused_host_blocks_total"] == 2
    await eng_b.stop()
    await kvbm.stop()


def test_metric_surfaces_carry_kv_observatory_fields():
    """Exporter gauges render via getattr on ForwardPassMetrics — every
    declared gauge must exist there, and the new kv observatory fields
    must survive the wire roundtrip."""
    from dynamo_tpu.llm.metrics_exporter import _GAUGES

    m = ForwardPassMetrics()
    for key, _help in _GAUGES:
        assert hasattr(m, key), key
    wire = m.to_wire()
    wire.update(
        kv_reused_device_blocks_total=11,
        kv_reused_host_blocks_total=7,
        kv_reused_disk_blocks_total=3,
        kvbm_host_usage=0.5,
        kvbm_link_g3g2_bps=123.4,
    )
    back = ForwardPassMetrics.from_wire(wire)
    assert back.kv_reused_device_blocks_total == 11
    assert back.kv_reused_host_blocks_total == 7
    assert back.kv_reused_disk_blocks_total == 3
    assert back.kvbm_host_usage == 0.5
    assert back.kvbm_link_g3g2_bps == 123.4


# ---------------------------------------------------------------------------
# route_audit.py: the join tool
# ---------------------------------------------------------------------------


def _route_rec(trace, overlap, pending=0, worker=1):
    return {
        "kind": "route", "id": f"req-{trace}", "trace": trace,
        "worker_id": worker, "overlap_blocks": overlap, "isl_blocks": 8,
        "logit": 0.1, "decision_ms": 2.0, "candidates": [],
        "indexer": {"applied": 10, "pending": pending, "lag_p99_ms": 4.0},
        "indexer_shards": 1, "metrics_age_ms": 100.0, "unix": time.time(),
    }


def _actual_rec(trace, device=0, host=0, disk=0):
    return {
        "kind": "kv_actual", "id": f"req-{trace}", "trace": trace,
        "isl_blocks": 8, "device_blocks": device, "host_blocks": host,
        "disk_blocks": disk, "unix": time.time(),
    }


def test_route_audit_join_and_gates(tmp_path):
    from benchmarks.route_audit import join_report, main, run_asserts
    from dynamo_tpu.utils.recorder import Recorder

    cap = tmp_path / "cap.jsonl"
    rec = Recorder(cap)
    rec.record(_route_rec("t1", overlap=4))               # exact
    rec.record(_actual_rec("t1", device=4))
    rec.record(_route_rec("t2", overlap=6, pending=3))    # stale mispredict
    rec.record(_actual_rec("t2", device=1, host=1))
    rec.record(_route_rec("t3", overlap=2))               # fresh mispredict
    rec.record(_actual_rec("t3", device=0))
    rec.close()

    from benchmarks.route_audit import load_records

    routes, actuals, planner = load_records([str(cap)])
    assert planner == []
    report = join_report(routes, actuals)
    assert report["joined"] == 3 and report["orphan_routes"] == 0
    assert report["join_rate"] == 1.0
    assert report["overlap_error"]["exact"] == 1
    assert report["overlap_error"]["overpredicted"] == 2
    assert report["staleness"]["mispredicted_while_stale"] == 1
    assert report["staleness"]["mispredicted_while_fresh"] == 1
    assert report["staleness"]["indexer_lag_p99_ms"] == 4.0
    assert report["tier_split"] == {
        "device_blocks": 5, "host_blocks": 1, "disk_blocks": 0,
        "peer_blocks": 0,
    }
    assert run_asserts(report, 0.95) == []
    assert main([str(cap), "--assert", "--json"]) == 0

    # An orphan route (no engine actual) hard-fails the gate.
    cap2 = tmp_path / "cap2.jsonl"
    rec = Recorder(cap2)
    rec.record(_route_rec("t9", overlap=4))
    rec.record(_route_rec("t1", overlap=4))
    rec.record(_actual_rec("t1", device=4))
    # A trace cut between a dispatch's halves (the router replica died
    # after it dispatched): the worker's actual carries a fresh trace and
    # joins by request id; the failover's second route joins its own.
    rec.record(_route_rec("t4", overlap=3))
    rec.record({**_actual_rec("t4-fresh", device=3), "id": "req-t4"})
    rec.record({**_route_rec("t5", overlap=6), "id": "req-t4"})
    rec.record({**_actual_rec("t5", device=6), "id": "req-t4"})
    rec.close()
    routes, actuals, _planner = load_records([str(cap2)])
    report = join_report(routes, actuals)
    assert report["joined"] == 3 and report["orphan_actuals"] == 0
    assert report["overlap_error"]["exact"] == 3
    assert report["orphan_routes"] == 1
    assert run_asserts(report, 0.95)
    assert main([str(cap2), "--assert", "--json"]) == 1

    # Zero actual reports is a hard failure even with zero routes joined.
    cap3 = tmp_path / "cap3.jsonl"
    rec = Recorder(cap3)
    rec.record(_route_rec("t1", overlap=4))
    rec.close()
    assert main([str(cap3), "--assert", "--json"]) == 1


# ---------------------------------------------------------------------------
# /debug/routes endpoint
# ---------------------------------------------------------------------------


async def test_debug_routes_endpoint():
    import httpx

    from dynamo_tpu.llm.discovery import ModelManager
    from dynamo_tpu.llm.http_service import HttpService
    from dynamo_tpu.llm.kv_router.audit import ROUTE_OBS

    before = ROUTE_OBS.routes_total
    ROUTE_OBS.record(RouteAuditRecord(
        request_id="r", trace_id="t", worker_id=1, overlap_blocks=2,
        isl_blocks=4, logit=0.0, decision_ms=1.0,
    ))
    service = HttpService(ModelManager(), host="127.0.0.1", port=0)
    await service.start()
    try:
        async with httpx.AsyncClient() as client:
            base = f"http://127.0.0.1:{service.port}"
            r = await client.get(f"{base}/debug/routes?n=4")
            assert r.status_code == 200
            body = r.json()
            assert body["routes_total"] == before + 1
            assert body["recent"][-1]["trace"] == "t"
            assert "kv_router_routes_total" in body["gauges"]
            # The router-plane gauges render on /metrics too.
            r = await client.get(f"{base}/metrics")
            assert "kv_router_routes_total" in r.text
    finally:
        await service.stop()
