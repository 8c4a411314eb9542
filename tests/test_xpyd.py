"""xPyD calibration + projection + network-aware routing tests
(ROADMAP #4; docs/architecture/planner.md).

The calibration fixture is the drift gate: the checked-in constants
(planner/calibration.py) must keep reproducing the RECORDED r04
headline within 10 % — a mocker cost-model edit that silently skews the
xPyD projections fails here, not in a later postmortem."""

import pytest

from dynamo_tpu.llm.kv_router.metrics_aggregator import ProcessedEndpoints
from dynamo_tpu.llm.kv_router.protocols import ForwardPassMetrics
from dynamo_tpu.llm.kv_router.scheduler import (
    DefaultWorkerSelector,
    KvRouterConfig,
)
from dynamo_tpu.planner import calibration as cal
from dynamo_tpu.planner import simulate as sim

# ---------------------------------------------------------------------------
# calibration fixture (<10% vs the recorded r04 run)
# ---------------------------------------------------------------------------


def test_calibration_constants_match_recorded_artifact():
    """The decode-dispatch constants are DERIVED from the r04 recording's
    two measured step times; re-derive from the artifact and compare so
    the constants and the recording can't drift apart."""
    rec = cal.recorded_r04()
    per_lane_us = (rec["decode_step_ms"] - rec["decode_step_ms_b32"]) \
        * 1000.0 / 32.0
    base_us = rec["decode_step_ms_b32"] * 1000.0 - 32.0 * per_lane_us
    assert per_lane_us == pytest.approx(cal.DECODE_TIME_PER_LANE_US,
                                        rel=0.02)
    assert base_us == pytest.approx(cal.DECODE_TIME_PER_STEP_US, rel=0.02)
    assert rec["tok_s"] == cal.R04_HEADLINE_TOK_S
    assert rec["p50_ttft_ms"] == cal.R04_P50_TTFT_MS


def test_calibrated_sim_reproduces_r04_headline_within_10pct():
    """Acceptance: mocker cost model reproduces the recorded r04
    aggregated tok/s and p50 TTFT within 10%."""
    cfg = sim.SimConfig()
    wl = sim.synth_workload(cal.R04_NUM_REQUESTS, cal.R04_ISL, cal.R04_OSL)
    r = sim.simulate_aggregated(cfg, wl, 1)
    assert r.tok_s == pytest.approx(cal.R04_HEADLINE_TOK_S, rel=0.10)
    assert r.p50_ttft_ms == pytest.approx(cal.R04_P50_TTFT_MS, rel=0.10)
    # The current fit is far tighter than the gate; if it degrades past
    # 5% someone changed the cost model — re-derive before loosening.
    assert r.tok_s == pytest.approx(cal.R04_HEADLINE_TOK_S, rel=0.05)
    assert r.p50_ttft_ms == pytest.approx(cal.R04_P50_TTFT_MS, rel=0.05)


def test_calibrated_mocker_config_carries_constants():
    m = cal.calibrated_mocker_config()
    assert m.decode_time_per_lane_us == cal.DECODE_TIME_PER_LANE_US
    assert m.prefill_dispatch_base_us == cal.PREFILL_DISPATCH_BASE_US
    assert m.decode_time_per_step_us == cal.DECODE_TIME_PER_STEP_US
    over = cal.calibrated_mocker_config(decode_time_per_lane_us=1.0)
    assert over.decode_time_per_lane_us == 1.0


def test_handoff_transfer_term_matches_measured_channel():
    """ISL-3000 over the measured 21.7 GB/s device channel lands in
    ~9 ms — the fixed 2-dispatch
    cost plus bytes/rate."""
    s = cal.handoff_seconds(3000)
    assert 0.004 < s < 0.012
    # A wire-rate link (0.005 GB/s) makes the same prompt ~20 s — the
    # asymmetry network-aware selection exists to route around.
    assert cal.handoff_seconds(3000, link_gbps=0.005) > 15.0


# ---------------------------------------------------------------------------
# projection gates (the BENCH_XPYD=1 table)
# ---------------------------------------------------------------------------


def test_xpyd_projection_gates():
    from benchmarks.xpyd_bench import calibration_check, projection, run_gates

    assert calibration_check()["ok"]
    # run_gates is THE gate pipeline bench.py's BENCH_XPYD leg and the
    # --assert CLI both call (single source of truth).
    report = run_gates()
    assert all(report["gates"].values()), report["gates"]
    assert report["headline_ratio"] > 1.30
    proj = projection()
    by_top = {r["topology"]: r for r in proj["rows"]}
    assert set(by_top) >= {"1xAGG", "1xcoloc", "3xcoloc", "1P1D", "2P1D",
                           "2P2D"}
    # The ci.sh gate: 2P1D beats the 1-worker aggregated baseline on
    # the prefill-heavy replay...
    assert by_top["2P1D"]["tok_s"] > by_top["1xAGG"]["tok_s"]
    # ...and beats the SLO-holding co-located fleet at EQUAL chips —
    # the honest form of the "+30% disagg" pillar claim (the dedicated
    # prefill pool runs fused batches; co-located prefill pays the
    # quantum tax to hold decode ITL).
    assert by_top["2P1D"]["tok_s"] > 1.30 * by_top["3xcoloc"]["tok_s"]
    # Disagg decode ITL never sees a prefill stall: max gap ≈ one step.
    assert by_top["2P1D"]["itl_max_ms"] < 25.0
    # The throughput-max aggregated baseline DOES stall decode for whole
    # fused prefill batches (the SLO failure disagg removes).
    assert by_top["1xAGG"]["itl_max_ms"] > 1000.0
    # Nothing dropped anywhere.
    assert all(r["dropped"] == 0 for r in proj["rows"])


def test_scale_down_mid_run_drops_nothing():
    """Acceptance: decode scale-down mid-run — zero dropped requests,
    traffic shifts to the survivor."""
    from benchmarks.xpyd_bench import drain_leg

    d = drain_leg()
    assert d["ok"]
    assert d["row"]["dropped"] == 0
    assert d["survivor_served"] > d["drained_worker_served"] > 0
    # The drain COMPLETED before the run ended (drain ≠ hang).
    assert d["row"]["decode_drained_at_s"] is not None
    assert d["row"]["decode_drained_at_s"] <= d["row"]["elapsed_s"]


def test_sim_drain_with_no_survivor_drops_late_arrivals():
    """Counter-case: draining the ONLY decode worker leaves late
    arrivals unroutable — the simulator reports them as dropped rather
    than hanging (the gate above proves the planner never does this:
    min_workers floors the pool)."""
    cfg = sim.SimConfig()
    wl = sim.synth_workload(8, 128, 16, rate_rps=2.0)
    r = sim.simulate_xpyd(cfg, wl, 1, 1, drain_decode_at=(1.0, 0))
    assert r.dropped > 0
    assert r.completed + r.dropped == 8


def test_sim_netaware_selection_avoids_slow_link():
    """Simulator twin of the router A/B: equal-load decode workers on a
    21.7 vs 0.012 GB/s link split under plain selection but shift to
    the fast link under netaware selection."""
    cfg = sim.SimConfig()

    def run(selector):
        wl = sim.synth_workload(16, 3000, 20)
        return sim.simulate_xpyd(
            cfg, wl, 2, 2, decode_links_gbps=[21.7, 0.012],
            selector=selector,
        )

    plain = run("plain")
    net = run("netaware")
    assert plain.per_decode_worker[1] >= 6       # blind split
    assert net.per_decode_worker[0] >= 14        # fast link wins
    assert net.per_decode_worker[1] <= 2
    # Routing around the slow link pays off end-to-end.
    assert net.p95_ttft_ms < plain.p95_ttft_ms


# ---------------------------------------------------------------------------
# network-aware selector (production scheduler path)
# ---------------------------------------------------------------------------


def _eps(fast_bps=21.7e9, slow_bps=0.012e9, overlap_total=4096):
    return ProcessedEndpoints(
        metrics={
            1: ForwardPassMetrics(kv_total_blocks=overlap_total,
                                  kvbm_link_g2g1_bps=fast_bps),
            2: ForwardPassMetrics(kv_total_blocks=overlap_total,
                                  kvbm_link_g2g1_bps=slow_bps),
        },
        stamp=1.0,
    )


def test_selector_network_aware_shifts_off_slow_link():
    plain = DefaultWorkerSelector(KvRouterConfig(), seed=0)
    net = DefaultWorkerSelector(
        KvRouterConfig(network_aware=True), seed=0
    )
    plain_picks = {1: 0, 2: 0}
    net_picks = {1: 0, 2: 0}
    for _ in range(100):
        plain_picks[plain.select(_eps(), {}, isl=128).worker_id] += 1
        net_picks[net.select(_eps(), {}, isl=128).worker_id] += 1
    # Plain mode: identical candidates -> the predicted-load bump
    # alternates the tie -> a split. No link preference.
    assert 30 <= plain_picks[1] <= 70
    # Network-aware: the slow link pays the full transfer term.
    assert net_picks[1] >= 90


def test_selector_audits_transfer_cost_in_candidates():
    """Acceptance: the decision is visible in the audit records — every
    candidate carries its priced transfer_ms + the applied term."""
    net = DefaultWorkerSelector(KvRouterConfig(network_aware=True), seed=0)
    d = net.select(_eps(), {}, isl=128)
    by_worker = {c["worker"]: c for c in d.candidates}
    assert by_worker[1]["transfer_ms"] < by_worker[2]["transfer_ms"]
    assert by_worker[2]["transfer_term"] == pytest.approx(1.0)
    # (both fields are rounded for the audit record — compare loosely)
    assert by_worker[1]["transfer_term"] == pytest.approx(
        by_worker[1]["transfer_ms"] / by_worker[2]["transfer_ms"], abs=1e-3
    )
    # Plain mode emits no transfer fields (the flag is honest).
    plain = DefaultWorkerSelector(KvRouterConfig(), seed=0)
    d = plain.select(_eps(), {}, isl=128)
    assert all("transfer_ms" not in c for c in d.candidates)


def test_selector_overlap_reduces_transfer_cost():
    """Predicted-overlap blocks don't travel: a full-overlap candidate
    pays zero transfer even on a slow link."""
    net = DefaultWorkerSelector(KvRouterConfig(network_aware=True), seed=0)
    isl = 128
    blocks = (isl + 15) // 16
    # Worker 2 (slow link) holds the whole prefix; worker 1 holds none.
    d = net.select(_eps(), {2: blocks}, isl=isl)
    by_worker = {c["worker"]: c for c in d.candidates}
    assert by_worker[2]["transfer_ms"] == 0.0
    assert d.worker_id == 2   # overlap + zero transfer beats fast link


def test_selector_uniform_links_do_not_distort_selection():
    """Uniform fleet: the normalized term shifts every logit equally,
    so network-aware mode picks exactly what plain mode picks."""
    eps = ProcessedEndpoints(
        metrics={
            1: ForwardPassMetrics(kv_active_blocks=10, kv_total_blocks=100,
                                  kvbm_link_g2g1_bps=21.7e9),
            2: ForwardPassMetrics(kv_active_blocks=90, kv_total_blocks=100,
                                  kvbm_link_g2g1_bps=21.7e9,
                                  num_requests_waiting=3),
        },
        stamp=1.0,
    )
    plain = DefaultWorkerSelector(KvRouterConfig(), seed=0)
    net = DefaultWorkerSelector(KvRouterConfig(network_aware=True), seed=0)
    assert plain.select(eps, {1: 4}, isl=64).worker_id == \
        net.select(eps, {1: 4}, isl=64).worker_id == 1


def test_selector_missing_link_ema_falls_back_to_default():
    """A fresh worker with no EMA yet is priced at the default link,
    not at infinity/zero."""
    eps = ProcessedEndpoints(
        metrics={
            1: ForwardPassMetrics(kv_total_blocks=100),   # no EMA
            2: ForwardPassMetrics(kv_total_blocks=100,
                                  kvbm_link_g2g1_bps=0.012e9),
        },
        stamp=1.0,
    )
    net = DefaultWorkerSelector(KvRouterConfig(network_aware=True), seed=0)
    d = net.select(eps, {}, isl=128)
    assert d.worker_id == 1   # default 21.7 GB/s beats the slow EMA
    by_worker = {c["worker"]: c for c in d.candidates}
    assert 0 < by_worker[1]["transfer_ms"] < by_worker[2]["transfer_ms"]


def test_router_ab_harness():
    """The ci.sh router A/B leg end-to-end (benchmarks/xpyd_bench.py)."""
    from benchmarks.xpyd_bench import router_ab

    ab = router_ab(trials=60)
    assert ab["ok"]
    assert ab["netaware"]["fast_link_share"] >= 0.9
    assert ab["netaware"]["transfer_audited"]
    assert not ab["plain"]["transfer_audited"]


@pytest.mark.anyio
async def test_netaware_decision_visible_in_debug_routes():
    """Acceptance: the transfer-cost decision shows up in /debug/routes
    audit records (candidates carry transfer_ms/transfer_term)."""
    import httpx

    from dynamo_tpu.llm.discovery import ModelManager
    from dynamo_tpu.llm.http_service import HttpService
    from dynamo_tpu.llm.kv_router.audit import ROUTE_OBS, RouteAuditRecord

    net = DefaultWorkerSelector(KvRouterConfig(network_aware=True), seed=0)
    d = net.select(_eps(), {}, isl=128)
    ROUTE_OBS.record(RouteAuditRecord(
        request_id="req-net", trace_id="", worker_id=d.worker_id,
        overlap_blocks=d.overlap_blocks, isl_blocks=8, logit=d.logit,
        decision_ms=0.5, candidates=d.candidates,
    ))
    service = HttpService(ModelManager(), host="127.0.0.1", port=0)
    await service.start()
    try:
        async with httpx.AsyncClient() as client:
            r = await client.get(
                f"http://127.0.0.1:{service.port}/debug/routes?n=4"
            )
            rec = next(x for x in r.json()["recent"]
                       if x["id"] == "req-net")
            assert any("transfer_ms" in c for c in rec["candidates"])
            assert any("transfer_term" in c for c in rec["candidates"])
    finally:
        await service.stop()


# ---------------------------------------------------------------------------
# simulator internals
# ---------------------------------------------------------------------------


def test_sim_decode_worker_balance_and_cap():
    cfg = sim.SimConfig(max_num_seqs=8)
    wl = sim.synth_workload(32, 128, 8)
    r = sim.simulate_xpyd(cfg, wl, 1, 2)
    assert r.completed == 32 and r.dropped == 0
    assert r.per_decode_worker == [16, 16]   # least-loaded split


def test_sim_tok_s_accounting():
    cfg = sim.SimConfig()
    wl = sim.synth_workload(4, 64, 8)
    r = sim.simulate_aggregated(cfg, wl, 1)
    assert r.completed == 4
    assert r.tok_s == pytest.approx(4 * 8 / r.elapsed_s, rel=1e-6)


def test_sim_coloc_mode_holds_itl_while_batch_mode_stalls():
    cfg = sim.SimConfig()
    wl_b = sim.synth_workload(32, 3000, 150)
    wl_c = sim.synth_workload(32, 3000, 150)
    batch = sim.simulate_aggregated(cfg, wl_b, 1, mode="batch")
    coloc = sim.simulate_aggregated(cfg, wl_c, 1, mode="coloc")
    # Co-location: no dispatch ever exceeds ~step+quantum cost.
    assert coloc.itl_max_ms < 40.0
    # Batch mode: a fused ISL-3000x16 prefill stalls decode for seconds.
    assert batch.itl_max_ms > 1000.0
    # The price of holding ITL: prefill efficiency (the tax the
    # dedicated prefill pool removes).
    assert coloc.tok_s < batch.tok_s
