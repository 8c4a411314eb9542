#!/usr/bin/env bash
# CI entrypoint — the exact checks .github/workflows/ci.yml runs, kept in
# one script so "CI is green" is reproducible locally with `./ci.sh`.
#
# Stages (each skippable via SKIP_<STAGE>=1 while iterating):
#   lint      byte-compile every Python file (syntax gate; uses ruff when
#             one is installed, which CI images may add — rule set pinned
#             in pyproject.toml [tool.ruff])
#   dynalint  project-native AST analysis (tools/dynalint): async/TPU
#             serving invariants, the dynarace concurrency rules, and
#             the dynaflow whole-program laws (DT012-DT016), all at
#             zero debt — any NEW finding fails
#             (docs/development/static_analysis.md).
#             LINT_ONLY=1 runs just the lint stages and exits — the
#             dedicated ci.yml lint job, red in seconds.
#   tests     the tier-1 CPU suite (ROADMAP.md invocation)
#   dynarace  the chaos subset re-run with DYNTPU_CHECK_THREADS=1: the
#             runtime thread-affinity + lock-order checker armed on the
#             real serving seams
#   helm    chart render check: `helm template` when the binary exists,
#           else the restricted-subset renderer in tests/test_deploy.py
#           (same substitution semantics; see its docstring)
#   bench   mocker-mode bench.py smoke — full serving stack, no device,
#           fails on mid-traffic compiles or the compile-stall TTFT
#           signature
set -euo pipefail
cd "$(dirname "$0")"

export JAX_PLATFORMS=${JAX_PLATFORMS:-cpu}

say() { printf '\n== %s ==\n' "$*"; }

chaos_leg() {
  say "mocker chaos fleet"
  # Self-healing-fleet leg (docs/architecture/failure_model.md
  # "Mid-stream failover"): a SEEDED randomized chaos schedule — mid-
  # stream worker kills, a bus partition, dropped KV frames — over a
  # 4-decode-worker mocker fleet with the trace capture on. HARD-FAILS
  # unless every request resolves with zero hangs, failover succeeds
  # whenever healthy capacity remains, greedy streams stay byte-
  # identical across kills, and the planner crash path heals the fleet
  # to target size; trace_merge then proves failover chains join the
  # request timelines instead of red-barring them. Toggles:
  # CHAOS_ONLY=1 runs just this leg (the ci.yml red check);
  # SKIP_CHAOS=1 skips it (when it already ran standalone).
  CHAOS_CAP=$(mktemp -t dyntpu_chaos_ci.XXXXXX.jsonl)
  rm -f "$CHAOS_CAP"
  BENCH_CHAOS=1 BENCH_CHAOS_SEED=1234 DYNTPU_TRACE="$CHAOS_CAP" \
    python bench.py
  python benchmarks/trace_merge.py "$CHAOS_CAP" --assert-complete >/dev/null
  rm -f "$CHAOS_CAP"*
}

ingress_leg() {
  say "mocker 100k ingress replay"
  # Million-user-ingress leg (docs/architecture/ingress_scale.md;
  # ROADMAP #4): a seeded Mooncake-style trace — 100k requests, 8
  # mocker workers, 2 router replicas — replayed through the FULL
  # replicated ingress (class-weighted admission → failover frontend →
  # router replicas → workers) with a mid-replay replica KILL + rejoin
  # and an overload burst. HARD-FAILS unless zero requests are lost or
  # hung through the kill, per-class p99 TTFT holds its SLO with zero
  # cross-class inversions, the burst's 429s land on batch (not
  # interactive) with load-proportional Retry-After, rejoin staleness
  # is measured, and the route-audit predicted-vs-actual error bound
  # holds across ALL replicas over the merged capture. Toggles:
  # INGRESS_ONLY=1 runs just this leg (the ci.yml red check);
  # SKIP_INGRESS=1 skips it (when it already ran standalone).
  INGRESS_CAP=$(mktemp -t dyntpu_ingress_ci.XXXXXX.jsonl)
  rm -f "$INGRESS_CAP"
  # Generous capture rotation: the 100k replay writes hundreds of MB of
  # route/kv_actual records and the route-audit join is gated over ALL
  # of them — the default 4x64 MB set would drop the oldest.
  BENCH_INGRESS=1 BENCH_INGRESS_SEED=20260805 DYNTPU_TRACE="$INGRESS_CAP" \
    DYNTPU_TRACE_MAX_MB=128 DYNTPU_TRACE_MAX_FILES=8 \
    python bench.py
  rm -f "$INGRESS_CAP"*
}

g4_leg() {
  say "mocker G4 peer tier"
  # G4 peer-tier leg (docs/architecture/kvbm_g4.md): a cold worker PULLS a fleet peer's packed KV rows
  # instead of recomputing them, pre-placement warms a joining worker
  # before traffic reaches it, and a peer killed mid-pull degrades to
  # local recompute. HARD-FAILS unless the pulled TTFT beats recompute
  # >=2x at the calibrated link rate (planner/calibration.HANDOFF_GBPS),
  # the pre-placed join reaches steady-state warm-hit rate >=2x faster
  # than the cold join, and the mid-pull kill completes byte-
  # identically with zero hangs. Toggles: G4_ONLY=1 runs just this leg
  # (the ci.yml red check); SKIP_G4=1 skips it (when it already ran
  # standalone).
  BENCH_G4=1 BENCH_G4_SEED=20260806 python bench.py
}

wquant_leg() {
  say "mocker wquant A/B"
  # Quantized-weights leg (docs/architecture/weight_quant.md): int8
  # weights at the SAME simulated HBM byte budget (weight bytes + KV
  # bytes) vs the bf16 baseline, priced by the r04-calibrated
  # weight-bytes term — the freed weight HBM converts to KV lanes.
  # HARD-FAILS unless the int8-weights leg delivers >= 1.3x decode
  # tok/s/chip at equal ITL SLO with zero mid-traffic compiles and the
  # unchanged <= 8-program budget ladder. Toggles: WQUANT_ONLY=1 runs just this leg (the ci.yml red
  # check); SKIP_WQUANT=1 skips it (when it already ran standalone).
  BENCH_WQUANT=1 python bench.py
}

integrity_leg() {
  say "mocker KV integrity"
  # Integrity-envelope leg (docs/architecture/integrity.md): randomized
  # corruption injected at ALL FIVE tier-crossing seams — G2 host
  # onboard, G3 scrub, G4 peer pull, disagg tcp frames, disagg native
  # frames — across 3 seeds on the deterministic mocker. HARD-FAILS
  # unless every injected corruption is detected and attributed to
  # exactly one per-tier counter split, zero streams deviate from the
  # closed form (corruption degrades to recompute, byte-identical), the
  # wire legs complete via the degrade path, and verification overhead
  # stays < 2% of decode wall-clock. The unit suite then covers the
  # stamp/verify/quarantine laws, the scrubber, sidecar recovery, the
  # kill -9 restart drill, and the mixed-fleet refusals. Toggles:
  # INTEGRITY_ONLY=1 runs just this leg (the ci.yml red check);
  # SKIP_INTEGRITY=1 skips it (when it already ran standalone).
  BENCH_INTEGRITY=1 python bench.py
  timeout -k 10 300 python -m pytest tests/test_integrity.py -q \
    -p no:cacheprovider
}

spec_leg() {
  say "mocker spec A/B"
  # Speculative-decode leg (docs/architecture/unified_step.md
  # "Speculative decode on the ragged step"; ROADMAP #2's last leg):
  # draft-verify spans on the unified budget ladder — HARD-FAILS unless
  # accepting-draft spec throughput beats both the unified non-spec leg
  # and the recorded phased-spec baseline, warmup stays within the
  # budget ladder (spec adds ZERO programs), every leg pays zero
  # mid-traffic compiles, and the auto-gate's free-when-losing
  # probe-window bound holds.
  # Toggles: SPEC_ONLY=1 runs just this leg (the ci.yml red check);
  # SKIP_SPEC=1 skips it (when it already ran standalone).
  BENCH_SPEC=1 python bench.py
}

if [[ -n "${SPEC_ONLY:-}" ]]; then
  spec_leg
  say "ci.sh: spec leg green"
  exit 0
fi

if [[ -n "${CHAOS_ONLY:-}" ]]; then
  chaos_leg
  say "ci.sh: chaos leg green"
  exit 0
fi

if [[ -n "${INGRESS_ONLY:-}" ]]; then
  ingress_leg
  say "ci.sh: ingress leg green"
  exit 0
fi

if [[ -n "${G4_ONLY:-}" ]]; then
  g4_leg
  say "ci.sh: G4 leg green"
  exit 0
fi

if [[ -n "${INTEGRITY_ONLY:-}" ]]; then
  integrity_leg
  say "ci.sh: integrity leg green"
  exit 0
fi

if [[ -n "${WQUANT_ONLY:-}" ]]; then
  wquant_leg
  say "ci.sh: wquant leg green"
  exit 0
fi

if [[ -z "${SKIP_LINT:-}" ]]; then
  say "lint"
  if command -v ruff >/dev/null 2>&1; then
    ruff check dynamo_tpu tests bench.py
  else
    python -m compileall -q dynamo_tpu tests bench.py benchmarks
  fi
fi

dynalint_leg() {
  say "lint-dynalint"
  python -m tools.dynalint --stats
  # dynarace concurrency rules (DT007-DT011) launched at ZERO debt and
  # must stay there repo-wide — no baseline allowance at all; every
  # deliberate exception is a reasoned in-file suppression
  # (docs/development/static_analysis.md "Concurrency discipline").
  python -m tools.dynalint --no-baseline \
    --select DT007,DT008,DT009,DT010,DT011
  # Observability-plane modules are dynalint-clean with NO baseline
  # allowance — new instrumentation must not regress the invariants it
  # exists to observe (docs/architecture/observability.md). The KV
  # observatory extends the set to the routing plane and the block
  # manager tiers it instruments.
  # The fleet-planner subsystem (ROADMAP #4) is dynalint-clean with NO
  # baseline allowance too — its control loops share the asyncio
  # process with the metrics plane (docs/architecture/planner.md).
  python -m tools.dynalint --no-baseline \
    dynamo_tpu/planner/obs.py \
    dynamo_tpu/planner/pools.py \
    dynamo_tpu/planner/fleet.py \
    dynamo_tpu/planner/calibration.py \
    dynamo_tpu/planner/simulate.py \
    dynamo_tpu/planner/planner.py \
    dynamo_tpu/planner/profiles.py \
    benchmarks/xpyd_bench.py \
    dynamo_tpu/utils/tracing.py \
    dynamo_tpu/utils/profiling.py \
    dynamo_tpu/engine/flight_recorder.py \
    dynamo_tpu/engine/coloc.py \
    dynamo_tpu/runtime/debug.py \
    benchmarks/trace_merge.py \
    benchmarks/route_audit.py \
    dynamo_tpu/llm/kv_router/audit.py \
    dynamo_tpu/llm/kv_router/indexer.py \
    dynamo_tpu/llm/kv_router/router.py \
    dynamo_tpu/llm/kv_router/scheduler.py \
    dynamo_tpu/llm/kv_router/metrics_aggregator.py \
    dynamo_tpu/llm/kv_router/publisher.py \
    dynamo_tpu/llm/kv_router/protocols.py \
    dynamo_tpu/block_manager/manager.py \
    dynamo_tpu/block_manager/peer.py \
    dynamo_tpu/block_manager/remote.py \
    benchmarks/g4_bench.py \
    dynamo_tpu/block_manager/offload.py \
    dynamo_tpu/block_manager/pool.py \
    dynamo_tpu/block_manager/quant.py \
    dynamo_tpu/block_manager/storage.py \
    dynamo_tpu/block_manager/config.py \
    dynamo_tpu/block_manager/integrity.py \
    dynamo_tpu/utils/atomic_io.py \
    dynamo_tpu/utils/faults.py \
    dynamo_tpu/disagg/transfer.py \
    dynamo_tpu/disagg/native_transfer.py \
    dynamo_tpu/runtime/failover.py \
    benchmarks/chaos_bench.py \
    dynamo_tpu/llm/slo.py \
    dynamo_tpu/llm/admission.py \
    dynamo_tpu/llm/kv_router/replicas.py \
    dynamo_tpu/llm/router_service.py \
    benchmarks/ingress_bench.py \
    dynamo_tpu/engine/engine.py \
    dynamo_tpu/engine/runner.py \
    dynamo_tpu/engine/scheduler.py \
    dynamo_tpu/engine/compile_cache.py \
    dynamo_tpu/mocker/engine.py \
    dynamo_tpu/ops/quant.py \
    dynamo_tpu/models/llama.py \
    dynamo_tpu/llm/metrics_exporter.py \
    dynamo_tpu/llm/http_service.py \
    dynamo_tpu/engine/config.py
  # The dynaflow laws (DT012-DT016) launched at ZERO debt on their
  # target modules — envelope completeness, atomic durability, fault
  # parity, calibration single-source, and the program-budget ladder
  # are interprocedural facts a baseline must never grandfather
  # (docs/development/static_analysis.md "Whole-program laws").
  python -m tools.dynalint --no-baseline \
    --select DT012,DT013,DT014,DT015,DT016 \
    dynamo_tpu/block_manager \
    dynamo_tpu/disagg \
    dynamo_tpu/planner \
    dynamo_tpu/engine \
    tools \
    benchmarks \
    bench.py
}

if [[ -n "${LINT_ONLY:-}" ]]; then
  # Fast red check: the full dynalint sweep (DT001-DT016, whole-program
  # context included) without the test matrix — ci.yml runs this as its
  # own job so lint failures surface in seconds, independently.
  dynalint_leg
  say "ci.sh: dynalint green"
  exit 0
fi

if [[ -z "${SKIP_DYNALINT:-}" ]]; then
  dynalint_leg
fi

if [[ -z "${SKIP_TESTS:-}" ]]; then
  say "tier-1 tests (CPU)"
  timeout -k 10 870 python -m pytest tests/ -q -m 'not slow' \
    --continue-on-collection-errors -p no:cacheprovider
fi

if [[ -z "${SKIP_DYNARACE:-}" ]]; then
  say "dynarace chaos subset (DYNTPU_CHECK_THREADS=1)"
  # The runtime concurrency checker armed for real: tracked locks feed
  # the lock-order graph and affinity-bound threads are asserted across
  # the chaos drills — an inversion or cross-context touch anywhere in
  # these seams fails CI deterministically instead of deadlocking a
  # production run (dynamo_tpu/utils/concurrency.py).
  DYNTPU_CHECK_THREADS=1 timeout -k 10 300 python -m pytest \
    tests/test_chaos.py tests/test_concurrency.py -q -p no:cacheprovider
fi

if [[ -z "${SKIP_HELM:-}" ]]; then
  say "helm render"
  if command -v helm >/dev/null 2>&1; then
    helm template test-rel deploy/helm/dynamo-tpu >/dev/null
    echo "helm template: OK"
  else
    python -m pytest tests/test_deploy.py -q -p no:cacheprovider
  fi
fi

if [[ -z "${SKIP_BENCH:-}" ]]; then
  say "mocker bench smoke"
  BENCH_SMOKE=1 BENCH_MOCKER=1 python bench.py
  say "mocker overload smoke"
  # Overload-safety leg (docs/architecture/overload_and_drain.md):
  # offered load >> capacity must shed with 429 + Retry-After, hang
  # nothing, keep admitted TTFT bounded; the low-load leg sheds nothing.
  BENCH_SMOKE=1 BENCH_MOCKER=1 BENCH_OVERLOAD=1 python bench.py
  say "mocker unified smoke"
  # Unified-path leg (docs/architecture/unified_step.md): the full
  # serving stack on the unified scheduler — HARD-FAILS unless
  # mid_traffic_compiles == 0 and the warmup plan stays within the
  # budget ladder (≤ 8 programs vs the lane×bucket grid's dozens).
  BENCH_SMOKE=1 BENCH_MOCKER=1 BENCH_UNIFIED=1 python bench.py
  if [[ -z "${SKIP_SPEC:-}" ]]; then
    spec_leg
  fi
  say "mocker coloc A/B"
  # Co-location leg (engine/coloc.py; ROADMAP #3): SLO-aware ADAPTIVE
  # co-located serving vs the static-quantum baseline under an
  # ISL3000-style mixed load — HARD-FAILS unless the adaptive leg's
  # decode ITL p95 holds within the SLO, its prefill throughput meets
  # or exceeds the static baseline's, and it pays zero mid-traffic
  # compiles.
  BENCH_SMOKE=1 BENCH_MOCKER=1 BENCH_COLOC=1 python bench.py
  say "mocker quant A/B"
  # Quantized-KV leg (docs/architecture/kv_quant.md): int8 KV at the
  # SAME simulated HBM byte budget vs the bf16 baseline, priced by the
  # r04-calibrated decode HBM-bytes term — HARD-FAILS unless int8
  # delivers >= 1.5x decode tok/s/chip at equal ITL SLO with zero
  # mid-traffic compiles and the unchanged <= 8-program budget ladder.
  BENCH_QUANT=1 python bench.py
  if [[ -z "${SKIP_WQUANT:-}" ]]; then
    wquant_leg
  fi
  say "mocker trace smoke"
  # Observability leg (docs/architecture/observability.md): the same
  # mocker run with the span capture on; trace_merge --assert-complete
  # HARD-FAILS unless every completed request has a full, gapless span
  # chain and no trace is orphaned — a seam that stops propagating
  # trace context breaks the build, not the next postmortem.
  TRACE_CAP=$(mktemp -t dyntpu_trace_ci.XXXXXX.jsonl)
  rm -f "$TRACE_CAP"
  BENCH_SMOKE=1 BENCH_MOCKER=1 BENCH_TRACE=1 DYNTPU_TRACE="$TRACE_CAP" \
    python bench.py
  python benchmarks/trace_merge.py "$TRACE_CAP" --assert-complete >/dev/null
  rm -f "$TRACE_CAP"*
  say "mocker route audit"
  # KV-observatory leg (docs/architecture/observability.md "KV
  # observatory"): a multi-worker mocker run behind the KV-aware router
  # with the span capture on, then route_audit.py closes the
  # predicted-vs-actual loop — HARD-FAILS unless ≥95% of requests join
  # predicted↔actual by trace id, no route record is orphaned, and the
  # engine reported at least one actual-reuse record.
  ROUTE_CAP=$(mktemp -t dyntpu_route_ci.XXXXXX.jsonl)
  rm -f "$ROUTE_CAP"
  BENCH_SMOKE=1 BENCH_MOCKER=1 BENCH_ROUTE_AUDIT=1 DYNTPU_TRACE="$ROUTE_CAP" \
    python bench.py
  python benchmarks/route_audit.py "$ROUTE_CAP" --assert >/dev/null
  rm -f "$ROUTE_CAP"*
  if [[ -z "${SKIP_CHAOS:-}" ]]; then
    chaos_leg
  fi
  if [[ -z "${SKIP_INGRESS:-}" ]]; then
    ingress_leg
  fi
  if [[ -z "${SKIP_G4:-}" ]]; then
    g4_leg
  fi
  if [[ -z "${SKIP_INTEGRITY:-}" ]]; then
    integrity_leg
  fi
  say "xPyD fleet projection"
  # Fleet-planner leg (ROADMAP #4; docs/architecture/planner.md): the
  # calibrated-mocker xPyD simulation — HARD-FAILS unless the mocker
  # cost model reproduces the recorded r04 headline (older harness, not reproduced) within 10%,
  # the 2P1D topology beats the 1-worker aggregated baseline on the
  # prefill-heavy replay, and a decode scale-down mid-run drops zero
  # requests.
  BENCH_XPYD=1 python bench.py
  say "network-aware router A/B"
  # NetKV-style decode selection on heterogeneous simulated links: the
  # transfer-cost term must shift selection off the slow link while
  # plain mode splits (the term stays honest: off by default).
  python benchmarks/xpyd_bench.py --router-ab >/dev/null
fi

say "ci.sh: all stages green"
