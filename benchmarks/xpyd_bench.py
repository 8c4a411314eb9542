#!/usr/bin/env python
"""xPyD fleet projection on the calibrated mocker cost model.

Replays prefill-heavy workloads through planner/simulate.py (virtual
clock, constants pinned to the recorded r04/r05 chip runs by
planner/calibration.py) across 1P1D / 2P1D / 2P2D disaggregated
topologies and aggregated baselines — both throughput-max ``batch``
mode and the SLO-holding ``coloc`` mode (the PR 8 unified-step shape) —
and emits the projection table (ROADMAP #4: the
pillar-#1 "+30 % disagg" claim, finally quantified).

Legs:
  (default)        print the projection JSON (+ markdown with --markdown)
  --assert         gate: calibration reproduces the r04 headline <10 %;
                   2P1D beats the 1-worker aggregated baseline on the
                   prefill-heavy replay; a decode scale-down mid-run
                   drops ZERO requests and shifts traffic to survivors
  --router-ab      network-aware decode selection A/B on heterogeneous
                   simulated links through the REAL DefaultWorkerSelector:
                   the transfer-cost term must shift selection away from
                   the slow link while plain mode splits

Usage: python benchmarks/xpyd_bench.py [--assert] [--router-ab]
       [--markdown] [--isl N] [--osl N] [--requests N] [--rate RPS]
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from dynamo_tpu.planner import calibration as cal          # noqa: E402
from dynamo_tpu.planner import simulate as sim             # noqa: E402


def calibration_check() -> dict:
    """Single aggregated worker replaying the recorded r04 headline
    workload — the <10 % reproduction gate (tests/test_xpyd.py runs the
    same check; future mocker edits can't silently drift projections)."""
    cfg = sim.SimConfig()
    wl = sim.synth_workload(cal.R04_NUM_REQUESTS, cal.R04_ISL, cal.R04_OSL)
    r = sim.simulate_aggregated(cfg, wl, 1)
    tok_err = abs(r.tok_s - cal.R04_HEADLINE_TOK_S) / cal.R04_HEADLINE_TOK_S
    ttft_err = abs(r.p50_ttft_ms - cal.R04_P50_TTFT_MS) / cal.R04_P50_TTFT_MS
    return {
        "sim_tok_s": round(r.tok_s, 1),
        "recorded_tok_s": cal.R04_HEADLINE_TOK_S,
        "tok_s_err": round(tok_err, 4),
        "sim_p50_ttft_ms": round(r.p50_ttft_ms, 1),
        "recorded_p50_ttft_ms": cal.R04_P50_TTFT_MS,
        "p50_ttft_err": round(ttft_err, 4),
        "ok": tok_err < 0.10 and ttft_err < 0.10,
    }


def projection(
    n: int = 32, isl: int = 3000, osl: int = 150, rate_rps: float = 0.0
) -> dict:
    """The topology table on a prefill-heavy replay (default: the
    ISL 3000 / OSL 150 reference-harness shape, all-at-once burst)."""
    cfg = sim.SimConfig()

    def wl():
        return sim.synth_workload(n, isl, osl, rate_rps=rate_rps)

    rows = [
        sim.simulate_aggregated(cfg, wl(), 1).to_wire(),
        sim.simulate_aggregated(cfg, wl(), 1, mode="coloc").to_wire(),
        sim.simulate_aggregated(cfg, wl(), 3).to_wire(),
        sim.simulate_aggregated(cfg, wl(), 3, mode="coloc").to_wire(),
        sim.simulate_xpyd(cfg, wl(), 1, 1).to_wire(),
        sim.simulate_xpyd(cfg, wl(), 2, 1).to_wire(),
        sim.simulate_xpyd(cfg, wl(), 2, 2).to_wire(),
    ]
    return {"workload": {"n": n, "isl": isl, "osl": osl,
                         "rate_rps": rate_rps}, "rows": rows}


def drain_leg(
    n: int = 48, isl: int = 3000, osl: int = 150, rate_rps: float = 4.0
) -> dict:
    """Fleet elasticity under open arrivals: decode worker 1 of a 2P2D
    fleet starts DRAINING mid-run — it must finish everything already
    routed to it (zero drops) while new selections shift to the
    survivor (the planner's decode-shrink semantics, simulated)."""
    cfg = sim.SimConfig()
    wl = sim.synth_workload(n, isl, osl, rate_rps=rate_rps)
    r = sim.simulate_xpyd(cfg, wl, 2, 2, drain_decode_at=(6.0, 1))
    served = r.per_decode_worker
    return {
        "row": r.to_wire(),
        "drained_worker_served": served[1],
        "survivor_served": served[0],
        "ok": (
            r.dropped == 0
            and r.completed == n
            and served[0] > served[1] > 0
            # The drain COMPLETED: the draining worker went empty
            # before the run ended (drain ≠ hang, not just drain ≠ kill).
            and r.decode_drained_at_s is not None
        ),
    }


def router_ab(trials: int = 200, seed: int = 0) -> dict:
    """Heterogeneous-link A/B through the production selector
    (llm/kv_router/scheduler.py): worker 1 ingests at the calibrated
    21.7 GB/s device rate, worker 2 at a 0.012 GB/s host-roundtrip
    rate (both from an older harness, not reproduced). Identical
    load and overlap otherwise — plain mode has no reason to prefer
    either (ties split via the predicted-load bump), network-aware mode
    must send decode traffic to the fast link."""
    from dynamo_tpu.llm.kv_router.metrics_aggregator import ProcessedEndpoints
    from dynamo_tpu.llm.kv_router.protocols import ForwardPassMetrics
    from dynamo_tpu.llm.kv_router.scheduler import (
        DefaultWorkerSelector,
        KvRouterConfig,
    )

    def endpoints() -> ProcessedEndpoints:
        return ProcessedEndpoints(
            metrics={
                1: ForwardPassMetrics(
                    kv_total_blocks=4096,
                    kvbm_link_g2g1_bps=cal.HANDOFF_GBPS * 1e9,
                ),
                2: ForwardPassMetrics(
                    kv_total_blocks=4096, kvbm_link_g2g1_bps=0.012e9
                ),
            },
            stamp=1.0,
        )

    out: dict = {}
    for mode in ("plain", "netaware"):
        selector = DefaultWorkerSelector(
            KvRouterConfig(network_aware=(mode == "netaware")), seed=seed
        )
        picks = {1: 0, 2: 0}
        transfer_audited = False
        for _ in range(trials):
            d = selector.select(endpoints(), {}, isl=128)
            picks[d.worker_id] += 1
            transfer_audited = transfer_audited or any(
                "transfer_ms" in c for c in d.candidates
            )
        out[mode] = {
            "fast_link_share": round(picks[1] / trials, 3),
            "picks": picks,
            "transfer_audited": transfer_audited,
        }
    out["ok"] = (
        out["netaware"]["fast_link_share"] >= 0.90
        and out["netaware"]["transfer_audited"]
        and 0.30 <= out["plain"]["fast_link_share"] <= 0.70
        and not out["plain"]["transfer_audited"]
    )
    return out


def run_gates(
    n: int = 32, isl: int = 3000, osl: int = 150, rate_rps: float = 0.0
) -> dict:
    """The full BENCH_XPYD gate pipeline — the ONE source of truth for
    the gates, shared by this CLI's ``--assert`` mode and bench.py's
    ``BENCH_XPYD=1`` leg (a gate added here is enforced in both)."""
    calres = calibration_check()
    proj = projection(n, isl, osl, rate_rps)
    drain = drain_leg()
    by_top = {r["topology"]: r for r in proj["rows"]}
    gates = {
        "calibration_ok": calres["ok"],
        "disagg_beats_single_agg": (
            by_top["2P1D"]["tok_s"] > by_top["1xAGG"]["tok_s"]
        ),
        # The "+30%" pillar-claim bound, enforced HERE so
        # the ci.sh leg (not just the test suite) fails if a cost-model
        # change erodes the projected margin.
        "disagg_beats_coloc_fleet_by_30pct": (
            by_top["2P1D"]["tok_s"] > 1.30 * by_top["3xcoloc"]["tok_s"]
        ),
        "scale_down_zero_drops": drain["ok"],
    }
    return {
        "calibration": calres,
        "projection": proj,
        "drain": drain,
        "gates": gates,
        # 2P1D over the equal-chip SLO-holding co-located fleet — the
        # headline the projection table exists to quantify.
        "headline_ratio": round(
            by_top["2P1D"]["tok_s"] / max(by_top["3xcoloc"]["tok_s"], 1e-9),
            3,
        ),
    }


def markdown_table(proj: dict) -> str:
    w = proj["workload"]
    lines = [
        f"| topology | chips | tok/s | tok/s/chip | p50 TTFT ms |"
        f" ITL p95 ms | ITL max ms |",
        "|---|---|---|---|---|---|---|",
    ]
    for r in proj["rows"]:
        lines.append(
            f"| {r['topology']} | {r['chips']} | {r['tok_s']} |"
            f" {r['tok_s_per_chip']} | {r['p50_ttft_ms']} |"
            f" {r['itl_p95_ms']} | {r['itl_max_ms']} |"
        )
    head = (
        f"Workload: {w['n']} requests, ISL {w['isl']} / OSL {w['osl']}"
        + (f", open-loop {w['rate_rps']} req/s" if w["rate_rps"] else
           ", all-at-once burst")
    )
    return head + "\n\n" + "\n".join(lines)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--assert", dest="assert_", action="store_true")
    ap.add_argument("--router-ab", action="store_true")
    ap.add_argument("--markdown", action="store_true")
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--isl", type=int, default=3000)
    ap.add_argument("--osl", type=int, default=150)
    ap.add_argument("--rate", type=float, default=0.0)
    args = ap.parse_args(argv)

    if args.router_ab:
        ab = router_ab()
        print(json.dumps({"router_ab": ab}, indent=2))
        if not ab["ok"]:
            print("ROUTER A/B FAILED: network-aware mode did not shift "
                  "selection off the slow link (or plain mode did)",
                  file=sys.stderr)
            return 1
        return 0

    report = run_gates(args.requests, args.isl, args.osl, args.rate)
    print(json.dumps(report, indent=2))
    if args.markdown:
        print()
        print(markdown_table(report["projection"]))
    if args.assert_ and not all(report["gates"].values()):
        print(f"XPYD GATES FAILED: {report['gates']}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
