"""dynamo-tpu CLI: launch the framework from a shell.

Mirrors the reference's ``dynamo-run`` input/output matrix (reference:
launch/dynamo-run/src/opt.rs:22-188, lib.rs:51-326):

  dynamo-tpu run [--in {http,text,batch:FILE,dyn://ns.comp.ep}]
                 [--out {tpu,echo_core,echo_full,dyn}] --model-path REF ...

- ``--in http  --out tpu``   one-process OpenAI server on the local engine
- ``--in http  --out dyn``   frontend only: discover workers via the
                             control plane (``--control-plane ADDR``)
- ``--in dyn://ns.c.e --out tpu``  worker only: serve the engine at that
                             endpoint and register the model
- ``--in text``              interactive chat against the same pipeline
- ``--in batch:FILE``        run a prompt file, report TTFT/throughput
                             (reference: input/batch.rs:143-191)
- ``dynamo-tpu control-plane``  standalone discovery/messaging server
- ``dynamo-tpu planner``        auto-scaler (components/planner)

Model references (``--model-path``): ``preset:NAME`` (random weights, toy
tokenizer), a local HF checkout dir, or ``hf://org/name`` (local hub cache).
"""

from __future__ import annotations

import argparse
import asyncio
import contextlib
import json
import logging
import signal
import sys
import time
from pathlib import Path

logger = logging.getLogger(__name__)

DEFAULT_ENDPOINT = "dyn://dynamo.tpu.generate"


def _parse_mesh(spec: str | None) -> dict[str, int]:
    """``tp=4,dp=2`` → {"tp": 4, "dp": 2}."""
    if not spec:
        return {}
    shape: dict[str, int] = {}
    for part in spec.split(","):
        axis, _, n = part.partition("=")
        if axis not in ("dp", "tp", "sp", "ep") or not n.isdigit():
            raise SystemExit(
                f"bad --mesh entry {part!r} (want axis=N, axes dp/tp/sp/ep)"
            )
        shape[axis] = int(n)
    return shape


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="dynamo-tpu")
    sub = p.add_subparsers(dest="cmd", required=True)

    run = sub.add_parser("run", help="serve / chat / batch")
    run.add_argument(
        "--in", dest="input", default="http",
        help="http | text | batch:FILE | dyn://ns.component.endpoint",
    )
    run.add_argument(
        "--out", dest="output", default="tpu",
        help="tpu | echo_core | echo_full | dyn",
    )
    run.add_argument(
        "--model-path", default="preset:llama3.2-1b",
        help="preset:NAME | HF checkout dir | hf://org/name",
    )
    run.add_argument("--model-name", default=None)
    run.add_argument("--model-type", default="chat",
                     choices=["chat", "embeddings"])
    run.add_argument("--endpoint", default=DEFAULT_ENDPOINT,
                     help="endpoint a local engine serves at")
    run.add_argument("--http-host", default="0.0.0.0")
    run.add_argument("--http-port", type=int, default=8080)
    run.add_argument("--control-plane", default=None, metavar="HOST:PORT",
                     help="join an existing control-plane server")
    run.add_argument("--spawn-control-plane", nargs="?", const="0",
                     default=None, metavar="PORT",
                     help="host a control-plane server in this process")
    run.add_argument("--router-mode", default="round_robin",
                     choices=["round_robin", "random", "kv"])
    run.add_argument("--route-network-aware", action="store_true",
                     help="KV router mode: add the NetKV-style transfer-"
                          "cost term to the selection score — candidates "
                          "pay for moving the non-overlapping prefix over "
                          "their per-link ingest-rate EMA "
                          "(docs/architecture/planner.md)")
    run.add_argument("--mesh", default=None, help="e.g. tp=4 or tp=2,dp=2")
    run.add_argument("--kv-sp", action="store_true",
                     help="shard the KV cache's slot axis over the mesh's "
                          "sp axis: max-model-len beyond one device's "
                          "cache (long-context mode; needs --mesh sp=N)")
    # Multi-host engine bootstrap (reference: MultiNodeConfig
    # lib/llm/src/engines.rs:42-60; launch/dynamo-run/src/lib.rs:176-258):
    # every node runs the same command with its own --node-rank; the mesh
    # then spans all nodes' chips (parallel/multihost.py).
    run.add_argument("--coordinator", default=None, metavar="HOST:PORT",
                     help="jax.distributed coordinator (leader) address")
    run.add_argument("--num-nodes", type=int, default=1)
    run.add_argument("--node-rank", type=int, default=0)
    run.add_argument("--dtype", default="bfloat16")
    run.add_argument("--quant", default=None, choices=["int8"],
                     help="weight-only quantization (halves decode's "
                          "weight-streaming bytes; ops/quant.py)")
    run.add_argument("--kv-quant", default=None, choices=["int8"],
                     help="KV-cache quantization — the per-tier precision "
                          "policy's G1 knob (docs/architecture/"
                          "kv_quant.md): int8 KV blocks with per-block "
                          "scales, dequantized in-kernel on the ragged "
                          "path; roughly halves "
                          "decode's KV HBM reads and doubles KV capacity "
                          "per chip. G2 host / G3 disk KVBM tiers "
                          "quantize independently via their layout "
                          "(always int8 when a quantized layout is "
                          "configured), whatever this G1 choice is")
    run.add_argument("--weight-quant", default=None, metavar="POLICY",
                     help="per-matmul weight-quantization policy (docs/"
                          "architecture/weight_quant.md): 'int8' or 'fp8' "
                          "quantizes every site; 'attn=int8,mlp=fp8' "
                          "selects per site group (sites: embedding, "
                          "attn, mlp, unembed). Quantize-on-load — the "
                          "bf16 copy never materializes resident; scales "
                          "ride as jit state beside the matrices. Zero "
                          "new XLA programs (composes "
                          "with --kv-quant; supersedes --quant)")
    run.add_argument("--speculative-k", type=int, default=0,
                     help="prompt-lookup speculative decoding: draft up to "
                          "K tokens per step from the sequence's own "
                          "history, verify in one forward (0 = off)")
    run.add_argument("--max-num-seqs", type=int, default=32)
    run.add_argument("--max-model-len", type=int, default=2048)
    run.add_argument("--num-blocks", type=int, default=2048)
    run.add_argument("--kv-cache-block-size", type=int, default=16)
    run.add_argument("--prefill-batch", type=int, default=4)
    run.add_argument("--unified-token-budget", type=int, default=256,
                     help="max tokens per unified dispatch (snapped to a "
                     "power-of-two ladder)")
    run.add_argument("--unified-prefill-quantum", type=int, default=64,
                     help="prefill tokens per sequence per unified step "
                     "while decode lanes share the batch (decode-ITL "
                     "bound); also the budget reserved for prefill; "
                     "with --coloc adaptive this is only the STARTING "
                     "quantum — the controller owns it from there")
    # SLO-aware co-location (engine/coloc.py; ROADMAP #3).
    run.add_argument("--itl-slo-ms", type=float, default=0.0,
                     help="decode inter-token-latency target in ms the "
                     "co-location controller measures each unified "
                     "dispatch against (0 = no SLO: no violation "
                     "accounting, no adaptation)")
    run.add_argument("--coloc", choices=["static", "adaptive"],
                     default="static",
                     help="unified-step prefill-quantum policy: static "
                     "keeps --unified-prefill-quantum hand-tuned; "
                     "adaptive runs the AIMD feedback loop against "
                     "--itl-slo-ms (grow on headroom, shrink on SLO "
                     "pressure, floor at --coloc-min-quantum) plus "
                     "phase-aware prefill admission")
    run.add_argument("--coloc-min-quantum", type=int, default=16,
                     help="adaptive-quantum floor: minimum prefill "
                     "tokens per unified step, so prefill TTFT "
                     "progress never fully starves under decode SLO "
                     "pressure")
    run.add_argument("--max-prefill-backlog-tokens", type=int, default=0,
                     help="HTTP admission watermark (phase-aware): "
                     "reject (429) while the engine's un-prefilled "
                     "backlog exceeds this many prompt TOKENS (0 = "
                     "off; fed by live engine readiness)")
    run.add_argument("--context-length", type=int, default=None,
                     help="override the card/engine context limit")
    run.add_argument("--no-warmup", action="store_true",
                     help="skip ahead-of-traffic shape compilation")
    run.add_argument("--compile-cache-dir", default="auto",
                     metavar="DIR|auto|none",
                     help="persistent XLA compile cache dir (warmed "
                          "programs are read from disk on relaunch). "
                          "$JAX_COMPILATION_CACHE_DIR, when set, is the "
                          "directory whatever is given here; auto = "
                          "$DYNAMO_TPU_COMPILE_CACHE_DIR, else "
                          ".jax_cache in the checkout; none disables")
    # Overload-safe serving (docs/architecture/overload_and_drain.md).
    run.add_argument("--max-inflight", type=int, default=256,
                     help="HTTP admission gate: max concurrently admitted "
                          "requests; excess gets 429 + Retry-After")
    run.add_argument("--max-engine-waiting", type=int, default=0,
                     help="HTTP admission watermark: reject (429) while "
                          "the engine already has this many requests "
                          "queued (0 = off; fed by live engine metrics)")
    run.add_argument("--default-request-class", default="interactive",
                     choices=["interactive", "batch"],
                     help="SLO class assumed when the client sends no "
                          "X-Request-Class header (docs/architecture/"
                          "ingress_scale.md)")
    run.add_argument("--batch-watermark-scale", type=float, default=0.5,
                     help="batch-class admission watermark scale: batch "
                          "requests 429 at this fraction of every "
                          "configured watermark/cap (cheapest-first "
                          "degradation; 1.0 = class-blind)")
    run.add_argument("--default-deadline-s", type=float, default=0.0,
                     help="per-request deadline applied when the client "
                          "sends no X-Request-Timeout-Ms header (0 = "
                          "none); expired work is cancelled at every hop")
    run.add_argument("--max-waiting", type=int, default=128,
                     help="engine waiting-list depth bound: over it the "
                          "OLDEST waiter is shed with a typed error "
                          "(0 = unbounded)")
    run.add_argument("--max-queue-delay-s", type=float, default=0.0,
                     help="engine waiting-list age bound: waiters older "
                          "than this are shed (0 = unbounded)")
    run.add_argument("--drain-grace-s", type=float, default=30.0,
                     help="graceful-drain budget on SIGTERM / the "
                          "control-plane drain verb: in-flight requests "
                          "get this long to finish before exit")
    run.add_argument("--health-port", type=int, default=0,
                     help="worker-mode health/metrics HTTP port (0 = off): "
                          "/health flips 503 while warming or draining — "
                          "the k8s readinessProbe target (also serves the "
                          "/debug/steps|trace|profile surface)")
    run.add_argument("--profile-dir", default=None, metavar="DIR",
                     help="enable on-demand TPU profiling: /debug/profile"
                          "?seconds=N and the control-plane profile verb "
                          "capture jax.profiler windows under DIR without "
                          "a restart (default $DYNTPU_PROFILE_DIR; unset "
                          "= endpoint disabled — see docs/architecture/"
                          "observability.md security note)")
    run.add_argument("--concurrency", type=int, default=32,
                     help="batch mode: in-flight request cap")
    run.add_argument("--max-tokens", type=int, default=128,
                     help="text/batch mode: generation cap per request")
    run.add_argument("--config", default=None, metavar="FILE.yaml",
                     help="layered deployment config (sections: Frontend, "
                          "Engine, Router; Common + common-configs "
                          "inheritance)")
    run.add_argument("--set", dest="overrides", action="append", default=[],
                     metavar="Component.key=value",
                     help="config override, highest precedence (repeatable)")
    run.add_argument("-v", "--verbose", action="store_true")

    cp = sub.add_parser("control-plane", help="standalone control plane")
    cp.add_argument("--host", default="0.0.0.0")
    cp.add_argument("--port", type=int, default=6380)
    cp.add_argument("--token", default=None)
    cp.add_argument("-v", "--verbose", action="store_true")

    mx = sub.add_parser("metrics", help="Prometheus exporter for worker load")
    mx.add_argument("--control-plane", required=True, metavar="HOST:PORT")
    mx.add_argument("--namespace", default="dynamo")
    mx.add_argument("--component", default="tpu")
    mx.add_argument("--host", default="0.0.0.0")
    mx.add_argument("--port", type=int, default=9091)
    mx.add_argument(
        "--push-url", default=None, metavar="URL",
        help="also push to a Prometheus PushGateway at URL (scrape-"
        "hostile networks; reference components/metrics push mode)",
    )
    mx.add_argument("--push-interval", type=float, default=15.0)
    mx.add_argument("--push-job", default="dynamo_tpu")
    mx.add_argument("-v", "--verbose", action="store_true")

    ap = sub.add_parser("api-store", help="deployment/artifact REST registry")
    ap.add_argument("--control-plane", required=True, metavar="HOST:PORT")
    ap.add_argument("--host", default="0.0.0.0")
    ap.add_argument("--port", type=int, default=8090)
    ap.add_argument("-v", "--verbose", action="store_true")

    rt = sub.add_parser("router", help="standalone KV-aware router service")
    rt.add_argument("--control-plane", required=True, metavar="HOST:PORT")
    rt.add_argument("--endpoint", required=True,
                    metavar="dyn://ns.component.endpoint",
                    help="target worker endpoint to route to")
    rt.add_argument("--component", default="router",
                    help="component name the routed endpoint is served on")
    rt.add_argument("--block-size", type=int, default=16)
    rt.add_argument("--route-network-aware", action="store_true",
                    help="add the NetKV-style transfer-cost term to the "
                         "KV selection score (docs/architecture/planner.md)")
    rt.add_argument("--replica-id", type=int, default=0,
                    help="this router replica's id (docs/architecture/"
                         "ingress_scale.md): run one router process per "
                         "replica on the SAME --component; the id labels "
                         "per-replica route audits so route_audit.py can "
                         "bound each replica's predicted-vs-actual error")
    rt.add_argument("-v", "--verbose", action="store_true")

    pl = sub.add_parser("planner", help="auto-scaler (queue/KV watermarks)")
    pl.add_argument("--control-plane", required=True, metavar="HOST:PORT")
    pl.add_argument("--namespace", default="dynamo")
    pl.add_argument("--min-workers", type=int, default=1)
    pl.add_argument("--max-workers", type=int, default=4, help="chip budget")
    pl.add_argument("--adjustment-interval", type=float, default=10.0)
    pl.add_argument("--metric-interval", type=float, default=1.0)
    pl.add_argument("--worker-cmd", required=True,
                    help="shell command template spawning one worker")
    pl.add_argument("--state-path", default=None, metavar="FILE.json",
                    help="checkpoint for crash/restart resume (default "
                         "~/.dynamo_tpu/state/<namespace>.json)")
    pl.add_argument("--profile", default=None, metavar="BENCH.json",
                    help="perf profile (bench.py output) enabling "
                         "SLA-driven scaling")
    pl.add_argument("--ttft-sla-ms", type=float, default=None)
    pl.add_argument("--itl-sla-ms", type=float, default=None)
    pl.add_argument("--decision-log", default=None, metavar="FILE.jsonl",
                    help="append one JSONL line per scaling decision "
                         "(time-series artifact; reference planner logs "
                         "these to TensorBoard)")
    # Two-pool fleet mode (ROADMAP #4, docs/architecture/planner.md):
    # independent prefill (queue depth/age) and decode (KV util + ITL)
    # pools; --worker-cmd spawns DECODE workers, --prefill-worker-cmd
    # spawns prefill workers.
    pl.add_argument("--two-pool", action="store_true",
                    help="scale prefill and decode pools independently "
                         "(docs/architecture/planner.md)")
    pl.add_argument("--prefill-worker-cmd", default=None,
                    help="shell command template spawning one PREFILL "
                         "worker (required with --two-pool)")
    pl.add_argument("--prefill-min-workers", type=int, default=1)
    pl.add_argument("--prefill-max-workers", type=int, default=4)
    pl.add_argument("--prefill-queue-age-up-s", type=float, default=5.0,
                    help="oldest queued prefill older than this scales "
                         "the prefill pool up at ANY depth")
    pl.add_argument("--decode-component", default="tpu",
                    help="component whose metrics plane scores the "
                         "decode pool")
    pl.add_argument("--decode-itl-up-ms", type=float, default=None,
                    help="decode pool scales up when the pool ITL EMA "
                         "exceeds this (off by default)")
    pl.add_argument("-v", "--verbose", action="store_true")

    op = sub.add_parser(
        "operator",
        help="reconcile api-store deployment specs into k8s objects",
    )
    op.add_argument("--control-plane", required=True, metavar="HOST:PORT")
    op.add_argument("--namespace", default="dynamo",
                    help="k8s namespace the children live in")
    op.add_argument("--interval", type=float, default=30.0,
                    help="resync interval seconds (reconciles are "
                         "watch-driven; this is the missed-event net)")
    op.add_argument("--kubectl", default="kubectl",
                    help="kubectl binary to drive the cluster with")
    op.add_argument("-v", "--verbose", action="store_true")
    return p


def main(argv: list[str] | None = None) -> None:
    args = build_parser().parse_args(argv)
    logging.basicConfig(
        level=logging.DEBUG if args.verbose else logging.INFO,
        format="%(asctime)s %(levelname).1s %(name)s: %(message)s",
    )
    if args.cmd == "run":
        asyncio.run(_run(args))
    elif args.cmd == "control-plane":
        asyncio.run(_control_plane(args))
    elif args.cmd == "planner":
        asyncio.run(_planner(args))
    elif args.cmd == "metrics":
        asyncio.run(_metrics(args))
    elif args.cmd == "router":
        asyncio.run(_router(args))
    elif args.cmd == "api-store":
        asyncio.run(_api_store(args))
    elif args.cmd == "operator":
        asyncio.run(_operator(args))


# ---------------------------------------------------------------------------
# subcommands
# ---------------------------------------------------------------------------


async def _control_plane(args) -> None:
    from dynamo_tpu.runtime.transports.control_plane import ControlPlaneServer

    server = await ControlPlaneServer(
        host=args.host, port=args.port, token=args.token
    ).start()
    print(f"control plane on {server.address}", flush=True)
    await _wait_for_signal()
    await server.stop()


async def _metrics(args) -> None:
    from dynamo_tpu.llm.metrics_exporter import MetricsExporter
    from dynamo_tpu.runtime.distributed import DistributedRuntime

    drt = await DistributedRuntime.connect(args.control_plane)
    exporter = await MetricsExporter(
        drt,
        namespace=args.namespace,
        component=args.component,
        host=args.host,
        port=args.port,
        push_url=args.push_url,
        push_interval_s=args.push_interval,
        push_job=args.push_job,
    ).start()
    print(f"metrics exporter on {args.host}:{exporter.port}", flush=True)
    try:
        await _wait_for_signal()
    finally:
        await exporter.stop()
        await drt.shutdown()


async def _api_store(args) -> None:
    from dynamo_tpu.runtime.distributed import DistributedRuntime
    from dynamo_tpu.sdk.api_store import ApiStore

    drt = await DistributedRuntime.connect(args.control_plane)
    store = await ApiStore(drt, host=args.host, port=args.port).start()
    print(f"api store on {args.host}:{store.port}", flush=True)
    try:
        await _wait_for_signal()
    finally:
        await store.stop()
        await drt.shutdown()


async def _operator(args) -> None:
    from dynamo_tpu.operator import GraphOperator, KubectlApi
    from dynamo_tpu.runtime.distributed import DistributedRuntime

    drt = await DistributedRuntime.connect(args.control_plane)
    operator = await GraphOperator(
        drt,
        KubectlApi(args.kubectl),
        namespace=args.namespace,
        interval_s=args.interval,
    ).start()
    print("operator reconciling", flush=True)
    try:
        await _wait_for_signal()
    finally:
        await operator.stop()
        await drt.shutdown()


async def _router(args) -> None:
    from dynamo_tpu.llm.kv_router.scheduler import KvRouterConfig
    from dynamo_tpu.llm.router_service import RouterService
    from dynamo_tpu.runtime.distributed import DistributedRuntime

    drt = await DistributedRuntime.connect(args.control_plane)
    service = await RouterService(
        drt,
        args.endpoint,
        component_name=args.component,
        cfg=KvRouterConfig(
            block_size=args.block_size,
            network_aware=args.route_network_aware,
        ),
        replica_id=args.replica_id,
    ).start()
    print(
        f"router service at {service.endpoint_path} "
        f"(replica {args.replica_id})",
        flush=True,
    )
    try:
        await _wait_for_signal()
    finally:
        await service.stop()
        await drt.shutdown()


async def _planner(args) -> None:
    from dynamo_tpu.planner.planner import Planner, PlannerConfig
    from dynamo_tpu.runtime.distributed import DistributedRuntime

    if args.two_pool:
        if args.profile or args.ttft_sla_ms is not None \
                or args.itl_sla_ms is not None:
            # The SLA/profile law is single-pool only; accepting the
            # flags and ignoring them would be exactly the silent half-
            # config the guard below rejects. Two-pool SLA shaping is
            # --decode-itl-up-ms (decode) + the queue-age bound
            # (prefill).
            raise SystemExit(
                "--two-pool does not support --profile/--ttft-sla-ms/"
                "--itl-sla-ms (single-pool SLA law); use "
                "--decode-itl-up-ms and --prefill-queue-age-up-s"
            )
        await _fleet_planner(args)
        return
    has_sla = args.ttft_sla_ms is not None or args.itl_sla_ms is not None
    if bool(args.profile) != has_sla:
        raise SystemExit(
            "SLA scaling needs BOTH --profile and at least one of "
            "--ttft-sla-ms/--itl-sla-ms (got only one half; the other "
            "would be silently ignored)"
        )
    profile = None
    if args.profile:
        from dynamo_tpu.planner.profiles import PerfProfile

        profile = PerfProfile.from_bench_json(args.profile)
    drt = await DistributedRuntime.connect(args.control_plane)
    state_path = args.state_path or str(
        Path.home() / ".dynamo_tpu" / "state" / f"{args.namespace}.json"
    )
    planner = Planner(
        drt,
        PlannerConfig(
            namespace=args.namespace,
            min_workers=args.min_workers,
            max_workers=args.max_workers,
            adjustment_interval_s=args.adjustment_interval,
            metric_interval_s=args.metric_interval,
            state_path=state_path,
            ttft_sla_ms=args.ttft_sla_ms,
            itl_sla_ms=args.itl_sla_ms,
            decision_log_path=args.decision_log,
        ),
        worker_cmd=args.worker_cmd,
        profile=profile,
    )
    await planner.start()
    print("planner running", flush=True)
    try:
        await _wait_for_signal()
    finally:
        await planner.stop()
        await drt.shutdown()


async def _fleet_planner(args) -> None:
    """Two-pool mode (docs/architecture/planner.md): --worker-cmd spawns
    decode workers, --prefill-worker-cmd spawns prefill workers; each
    pool runs its own law + hysteresis over the shared sample loop."""
    from dynamo_tpu.planner.fleet import FleetPlanner, FleetPlannerConfig
    from dynamo_tpu.planner.planner import SubprocessConnector
    from dynamo_tpu.planner.pools import (
        DecodeLaw,
        PoolConfig,
        PrefillLaw,
        default_pools,
    )
    from dynamo_tpu.runtime.distributed import DistributedRuntime

    if not args.prefill_worker_cmd:
        raise SystemExit("--two-pool requires --prefill-worker-cmd")
    drt = await DistributedRuntime.connect(args.control_plane)
    state_path = args.state_path or str(
        Path.home() / ".dynamo_tpu" / "state" / f"{args.namespace}.json"
    )
    prefill_pool, decode_pool = default_pools(
        SubprocessConnector(args.prefill_worker_cmd),
        SubprocessConnector(args.worker_cmd),
        prefill_cfg=PoolConfig(
            name="prefill",
            min_workers=args.prefill_min_workers,
            max_workers=args.prefill_max_workers,
        ),
        decode_cfg=PoolConfig(
            name="decode",
            min_workers=args.min_workers,
            max_workers=args.max_workers,
        ),
        prefill_law=PrefillLaw(age_up_s=args.prefill_queue_age_up_s),
        decode_law=DecodeLaw(itl_up_ms=args.decode_itl_up_ms),
    )
    planner = FleetPlanner(
        drt,
        FleetPlannerConfig(
            namespace=args.namespace,
            decode_component=args.decode_component,
            adjustment_interval_s=args.adjustment_interval,
            metric_interval_s=args.metric_interval,
            state_path=state_path,
            decision_log_path=args.decision_log,
        ),
        prefill_pool,
        decode_pool,
    )
    await planner.start()
    print("fleet planner running (two-pool)", flush=True)
    try:
        await _wait_for_signal()
    finally:
        await planner.stop()
        await drt.shutdown()


#: config-section → args-attribute aliases (section key is dash/underscore
#: insensitive; unknown keys in a known section are rejected loudly).
_CONFIG_SECTIONS = {
    "Run": {"in": "input", "out": "output"},
    "Frontend": {"host": "http_host", "port": "http_port"},
    "Engine": {"block_size": "kv_cache_block_size"},
    "Router": {"mode": "router_mode"},
}


def _apply_config(args) -> None:
    """Layer configuration onto the parsed args. Precedence, highest first:
    `--set Component.key=value` > explicit CLI flags > config file / env >
    argparse defaults (the reference SDK's YAML + --Component.key=value
    override model). "Explicit" is detected by comparing against a
    defaults-only parse, so a flag repeated in the YAML never silently
    loses to the file."""
    from dynamo_tpu.utils.config import load_config

    defaults = vars(build_parser().parse_args(["run"]))

    def apply(cfg, force: bool) -> None:
        for section, aliases in _CONFIG_SECTIONS.items():
            for key, val in cfg.component(section).as_dict().items():
                if section == "Engine" and key == "warmup":
                    # Engine.warmup: false == --no-warmup
                    if force or args.no_warmup == defaults["no_warmup"]:
                        args.no_warmup = not val
                    continue
                attr = aliases.get(key, key)
                if not hasattr(args, attr):
                    raise SystemExit(
                        f"unknown config key {section}.{key} "
                        f"(no matching --{attr.replace('_', '-')} option)"
                    )
                if force or getattr(args, attr) == defaults.get(attr):
                    setattr(args, attr, val)
        unknown = set(cfg.sections()) - set(_CONFIG_SECTIONS)
        if unknown:
            raise SystemExit(
                f"unknown config sections: {', '.join(sorted(unknown))} "
                f"(expected {', '.join(_CONFIG_SECTIONS)})"
            )

    # File + env layer: fills in anything the user didn't set on the line.
    apply(
        load_config(args.config, defaults={s: {} for s in _CONFIG_SECTIONS}),
        force=False,
    )
    # --set layer: beats everything, including explicit flags.
    if args.overrides:
        apply(
            load_config(
                None,
                overrides=args.overrides,
                defaults={s: {} for s in _CONFIG_SECTIONS},
                env={},
            ),
            force=True,
        )


async def _run(args) -> None:
    from dynamo_tpu.runtime.distributed import DistributedRuntime

    _apply_config(args)
    stack = _Stack()
    try:
        # 1. control plane / runtime
        if args.spawn_control_plane is not None:
            from dynamo_tpu.runtime.transports.control_plane import (
                ControlPlaneServer,
            )

            server = await ControlPlaneServer(
                port=int(args.spawn_control_plane)
            ).start()
            stack.push(server.stop)
            print(f"control plane on {server.address}", flush=True)
            args.control_plane = server.address
        if args.control_plane:
            drt = await DistributedRuntime.connect(args.control_plane)
        else:
            drt = await DistributedRuntime.in_process()
        stack.push(drt.shutdown)

        # 2. engine side (unless frontend-only out=dyn)
        endpoint_path = args.endpoint
        if args.input.startswith("dyn://"):
            endpoint_path = args.input
        if (
            args.output == "tpu"
            and args.num_nodes > 1
            and args.node_rank > 0
        ):
            # Multi-host follower rank: replay the leader's step stream
            # until it stops; serves no endpoint of its own.
            await _run_follower(args, drt)
            return
        engine_obj = None
        served = None
        if args.output != "dyn":
            endpoint_path, engine_obj, served = await _start_engine(
                args, drt, stack, endpoint_path
            )

        # 3. input side
        if args.input.startswith("dyn://"):
            print(f"worker serving {endpoint_path}", flush=True)
            await _worker_until_drain(
                args, drt, endpoint_path, engine_obj, served, stack
            )
            return
        manager = await _start_frontend(args, drt, stack)
        if args.input == "http":
            service = await _serve_http(args, stack, manager, engine_obj)
            await _wait_for_signal()
            # Graceful drain before unwind: refuse new requests (admission
            # 503s, /health flips), let admitted ones finish streaming.
            await service.drain(args.drain_grace_s)
            if engine_obj is not None:
                engine_obj.begin_drain()
                await engine_obj.wait_drained(args.drain_grace_s)
        elif args.input == "text":
            await _text_chat(args, manager)
        elif args.input.startswith("batch:"):
            await _batch(args, manager, args.input.split(":", 1)[1])
        else:
            raise SystemExit(f"bad --in {args.input!r}")
    finally:
        await stack.unwind()


class _Stack(contextlib.AsyncExitStack):
    """AsyncExitStack with log-and-continue cleanup callbacks."""

    def push(self, fn) -> None:
        async def _safe() -> None:
            try:
                await fn()
            except Exception:  # noqa: BLE001
                logger.exception("cleanup failed")

        self.push_async_callback(_safe)

    async def unwind(self) -> None:
        await self.aclose()


async def _wait_for_signal() -> None:
    loop = asyncio.get_running_loop()
    stop = asyncio.Event()
    for sig in (signal.SIGINT, signal.SIGTERM):
        try:
            loop.add_signal_handler(sig, stop.set)
        except NotImplementedError:  # pragma: no cover - non-unix
            pass
    await stop.wait()
    print("shutting down", flush=True)


async def _worker_until_drain(
    args, drt, endpoint_path: str, engine, served, stack
) -> None:
    """Worker-mode main loop with graceful drain: wait for SIGTERM/SIGINT
    or the control-plane drain verb, then stop admitting, finish in-flight
    sequences, flip readiness, deregister, and return (the caller's unwind
    revokes the lease and exits) — a loss-free rolling restart
    (docs/architecture/overload_and_drain.md)."""
    from dynamo_tpu.runtime.component import EndpointId
    from dynamo_tpu.runtime.drain import watch_drain

    loop = asyncio.get_running_loop()
    stop = asyncio.Event()
    for sig in (signal.SIGINT, signal.SIGTERM):
        try:
            loop.add_signal_handler(sig, stop.set)
        except NotImplementedError:  # pragma: no cover - non-unix
            pass
    eid = EndpointId.parse(endpoint_path)
    watch = await watch_drain(
        drt, eid.namespace, eid.component, stop.set
    )
    from dynamo_tpu.utils.profiling import Profiler

    profiler = Profiler(base_dir=getattr(args, "profile_dir", None))
    if profiler.configured:
        # Control-plane profile verb: operators capture a jax.profiler
        # window on this worker without port-forwarding to its debug
        # endpoint (runtime/debug.py mirrors the drain verb).
        from dynamo_tpu.runtime.debug import watch_profile

        pwatch = await watch_profile(
            drt, eid.namespace, eid.component, profiler
        )
        stack.callback(pwatch.close)
    if args.health_port and engine is not None:
        from dynamo_tpu.llm.http_service import HealthServer

        health = await HealthServer(
            engine.readiness, host="0.0.0.0", port=args.health_port,
            debug=engine if hasattr(engine, "debug_steps") else None,
            profiler=profiler,
        ).start()
        stack.push(health.stop)
    await stop.wait()
    watch.close()
    print("draining", flush=True)
    await _graceful_drain(engine, served, args.drain_grace_s)


async def _graceful_drain(engine, served, grace_s: float) -> bool:
    """The drain state machine's in-process half: (1) the engine stops
    admitting IMMEDIATELY (readiness flips); (2) the served instance
    deregisters FIRST — routers evict now, not after the grace period —
    then awaits its in-flight request handlers (which complete: admitted
    work runs to completion under drain); (3) anything not tied to an
    ingress handler gets the remaining grace. The lease is revoked by the
    runtime unwind right after."""
    t0 = time.monotonic()
    ok = True
    if engine is not None and hasattr(engine, "begin_drain"):
        engine.begin_drain()
    if served is not None:
        ok = await served.drain(grace_s)
    if engine is not None and hasattr(engine, "wait_drained"):
        remaining = max(1.0, grace_s - (time.monotonic() - t0))
        ok = await engine.wait_drained(remaining) and ok
    print(
        "drain complete" if ok else "drain grace expired", flush=True
    )
    return ok


def _tpu_local_and_cfg(args):
    """Model artifacts + EngineConfig for the tpu engine path — shared by
    the serving leader and multi-host follower ranks, which MUST build
    identical runners (parallel/stepcast.py lockstep contract)."""
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.llm.local_model import LocalModel

    from dynamo_tpu.engine.compile_cache import resolve_cache_base

    local = LocalModel.prepare(
        args.model_path,
        name=args.model_name,
        context_length=args.context_length,
        kv_block_size=args.kv_cache_block_size,
    )
    max_len = min(args.max_model_len, local.card.context_length)
    local.card.context_length = max_len
    ecfg = EngineConfig(
        model=local.config,
        dtype=args.dtype,
        block_size=args.kv_cache_block_size,
        num_blocks=args.num_blocks,
        max_num_seqs=args.max_num_seqs,
        max_model_len=max_len,
        prefill_batch=args.prefill_batch,
        unified_token_budget=args.unified_token_budget,
        unified_prefill_quantum=args.unified_prefill_quantum,
        itl_slo_ms=args.itl_slo_ms,
        coloc=args.coloc,
        coloc_min_quantum=args.coloc_min_quantum,
        mesh_shape=_parse_mesh(args.mesh),
        kv_sp=args.kv_sp,
        quant=args.quant,
        kv_quant=args.kv_quant,
        weight_quant=args.weight_quant,
        speculative_k=args.speculative_k,
        coordinator=args.coordinator,
        num_nodes=args.num_nodes,
        node_rank=args.node_rank,
        compile_cache_dir=resolve_cache_base(args.compile_cache_dir),
        # With warmup on, hold admission until the shape set compiles
        # (requests queue instead of racing the compiles); --no-warmup
        # serves immediately in the documented degraded mode.
        warmup_gate="degraded" if args.no_warmup else "hold",
        # Bounded engine waiting list (overload shedding).
        max_waiting=args.max_waiting,
        max_queue_delay_s=args.max_queue_delay_s,
    )
    return local, ecfg


async def _run_follower(args, drt) -> None:
    """Multi-host follower rank (node_rank > 0): no endpoint, no HTTP —
    build the identical ModelRunner over the global mesh and replay the
    leader's step stream so the SPMD collectives line up
    (parallel/stepcast.py)."""
    from dynamo_tpu.engine.runner import ModelRunner
    from dynamo_tpu.parallel.multihost import MultiHostConfig, initialize
    from dynamo_tpu.parallel.stepcast import follower_serve

    initialize(MultiHostConfig(
        args.coordinator, args.num_nodes, args.node_rank
    ))
    local, ecfg = _tpu_local_and_cfg(args)
    params = await asyncio.to_thread(local.load_params, args.dtype)
    runner = await asyncio.to_thread(
        lambda: ModelRunner(
            ecfg, params=params, rng_seed=ecfg.seed, donate_params=True
        )
    )
    ns = _endpoint_namespace(args)
    print(
        f"multihost follower rank {args.node_rank} ready", flush=True
    )
    await follower_serve(runner, drt, namespace=ns, rank=args.node_rank)


def _endpoint_namespace(args) -> str:
    from dynamo_tpu.runtime.component import EndpointId

    path = args.input if args.input.startswith("dyn://") else args.endpoint
    return EndpointId.parse(path).namespace


async def _start_engine(args, drt, stack, endpoint_path: str):
    """Build the local engine (tpu or echo), serve it at the endpoint, and
    register the model. Returns (endpoint path served, engine or None for
    non-tpu outputs — the HTTP /health readiness hook, and the
    ServedInstance handle for graceful drain)."""
    from dynamo_tpu.llm.discovery import register_llm
    from dynamo_tpu.llm.local_model import LocalModel
    from dynamo_tpu.runtime.component import EndpointId

    eid = EndpointId.parse(endpoint_path)
    endpoint = (
        drt.namespace(eid.namespace).component(eid.component).endpoint(eid.name)
    )
    # What this start is made of, by phase (engine/flight_recorder.py
    # START_PHASES; the engine takes it over and books its own two).
    start = None
    if args.output == "tpu":
        from dynamo_tpu.engine.flight_recorder import (
            START_PHASES,
            StepPhases,
        )

        start = StepPhases(START_PHASES, "start")
        # jax's first import/backend-init costs seconds and must not starve
        # the event loop past the lease TTL (see _build_embed note).
        with start.phase("runtime"):
            await asyncio.to_thread(__import__, "jax")

    if args.output in ("echo_core", "echo_full"):
        from dynamo_tpu.llm.engines import EchoEngineCore, EchoEngineFull
        from dynamo_tpu.llm.model_card import ModelDeploymentCard

        engine = (
            EchoEngineCore() if args.output == "echo_core" else EchoEngineFull()
        )
        card = ModelDeploymentCard(
            name=args.model_name or args.output, model_path=None
        )
    elif args.output == "tpu" and args.model_type == "embeddings":
        local = LocalModel.prepare(
            args.model_path,
            name=args.model_name,
            context_length=args.context_length,
        )

        def _build_embed():
            # Heavy jax work stays OFF the event loop: starving it for
            # >lease-TTL kills the runtime's own lease (keepalive is a
            # CriticalTask) and deregisters the model we just announced.
            from dynamo_tpu.llm.embedding import EmbeddingEngine

            eng = EmbeddingEngine(
                local.config, params=local.load_params(args.dtype),
                dtype=args.dtype,
            )
            if not args.no_warmup:
                eng._run([1] * 8)  # compile the smallest bucket
            return eng

        engine = await asyncio.to_thread(_build_embed)
        card = local.card
        card.model_type = "embeddings"
    elif args.output == "tpu":
        from dynamo_tpu.engine.config import EngineConfig
        from dynamo_tpu.engine.engine import TpuEngine
        from dynamo_tpu.llm.kv_router.publisher import (
            KvEventPublisher,
            WorkerMetricsPublisher,
        )

        if args.num_nodes > 1:
            # Must precede any device use (weight loading creates device
            # arrays) or jax.distributed cannot form the global mesh.
            from dynamo_tpu.parallel.multihost import (
                MultiHostConfig,
                initialize,
            )

            initialize(MultiHostConfig(
                args.coordinator, args.num_nodes, args.node_rank
            ))
        import jax

        # The backend's start (the TPU runtime's: seconds), here under its
        # own name and not inside whatever touches a device first.
        with start.phase("runtime"):
            await asyncio.to_thread(jax.devices)
        local, ecfg = _tpu_local_and_cfg(args)
        # KV events + per-pass metrics feed the KV-aware router and the
        # planner over the control plane (in-process — no ZMQ bridge).
        comp = drt.namespace(eid.namespace).component(eid.component)
        kv_pub = KvEventPublisher(drt, comp, drt.primary_lease_id)
        metrics_pub = WorkerMetricsPublisher()
        await metrics_pub.create_endpoint(comp)
        with start.phase("weights"):
            params = await asyncio.to_thread(local.load_params, args.dtype)
        engine = TpuEngine(
            ecfg,
            params=params,
            on_kv_event=kv_pub.publish_engine_event,
            on_metrics=metrics_pub.publish,
            # KV observatory: per-request ACTUAL-reuse records onto the
            # hit-rate plane, closing the router's predicted loop.
            on_kv_actual=kv_pub.publish_hit_actual,
            # Freshly loaded — hand ownership over so a quantized load
            # frees the bf16 buffers as the int8 copies materialize.
            donate_params=True,
            start_phases=start,
        )
        await engine.start()
        if args.num_nodes > 1:
            # Multi-host leader: broadcast every device step so follower
            # ranks replay it (parallel/stepcast.py). Pushed BEFORE
            # engine.stop so unwind stops the engine first, then sends
            # the followers their stop sentinel.
            from dynamo_tpu.parallel.stepcast import StepLeader

            leader = await StepLeader(
                engine.runner, drt, namespace=eid.namespace,
                num_followers=args.num_nodes - 1,
            ).start()
            stack.push(leader.stop)
            engine.runner = leader
        stack.push(engine.stop)
        cache_dir = getattr(engine.runner, "compile_cache_dir", None)
        if cache_dir is not None:
            print(f"compile cache: {cache_dir}", flush=True)
        if not args.no_warmup:
            t0 = time.monotonic()
            n = await engine.warmup()
            cs = engine.runner.compile_stats
            phases = ", ".join(
                f"{phase} {secs:.1f}s"
                for phase, secs in cs.warm_phase_s.items()
            )
            traces, calls = cs.layer_body()
            before = ", ".join(
                f"{phase} {secs:.1f}s"
                for phase, secs in start.seconds().items()
                if phase != "warmup"
            )
            print(
                f"warmup: {n} programs in {time.monotonic() - t0:.1f}s "
                f"({phases}; layer body traced {traces}x for {calls} "
                f"calls; {cs.warm_cache_events['hits']} compile requests "
                f"read from the cache, {cs.warm_cache_events['misses']} "
                f"compiled; before it: {before}) — engine ready",
                flush=True,
            )
        card = local.card
    else:
        raise SystemExit(f"bad --out {args.output!r}")

    # With the frontend in this same process (every --in but dyn://) its
    # router calls the engine directly; a worker has no router to offer
    # it to, and every other process reaches either over the wire.
    served = await endpoint.serve(
        engine, offer_local=not args.input.startswith("dyn://")
    )
    await register_llm(drt, endpoint, card, model_type=card.model_type)
    print(f"model {card.name!r} registered at {endpoint_path}", flush=True)
    tpu_engine = engine if args.output == "tpu" and hasattr(
        engine, "readiness"
    ) else None
    return endpoint_path, tpu_engine, served


async def _start_frontend(args, drt, stack):
    """ModelWatcher + ModelManager over the runtime's discovery plane."""
    from dynamo_tpu.llm.discovery import ModelManager, ModelWatcher
    from dynamo_tpu.llm.kv_router.router import kv_selector_factory
    from dynamo_tpu.llm.kv_router.scheduler import KvRouterConfig
    from dynamo_tpu.runtime.egress import RouterMode

    mode = RouterMode(args.router_mode)
    kv_cfg = KvRouterConfig(
        network_aware=bool(getattr(args, "route_network_aware", False)),
    )
    manager = ModelManager()
    watcher = ModelWatcher(
        drt,
        manager,
        router_mode=mode,
        kv_selector_factory=(
            kv_selector_factory(drt, kv_cfg) if mode is RouterMode.KV else None
        ),
    )
    await watcher.start()
    # Give initial discovery a beat: a worker registered just above is
    # visible immediately (same store), remote ones arrive via the watch.
    for _ in range(50):
        if manager.models():
            break
        await asyncio.sleep(0.1)
    return manager


async def _serve_http(args, stack, manager, engine=None):
    from dynamo_tpu.llm.admission import AdmissionConfig, AdmissionController
    from dynamo_tpu.llm.http_service import HttpService
    from dynamo_tpu.utils.profiling import Profiler

    readiness = engine.readiness if engine is not None else None
    service = HttpService(
        manager, host=args.http_host, port=args.http_port,
        # Local-engine deployments expose the compile-lifecycle state on
        # /health (503 while warming) and /metrics; frontend-only (--out
        # dyn) has no local engine to probe.
        readiness=readiness,
        # Ingress overload gate: 429 + Retry-After past capacity, with
        # watermarks fed by the live engine snapshot when one is local.
        admission=AdmissionController(
            AdmissionConfig(
                max_inflight=args.max_inflight,
                max_engine_waiting=args.max_engine_waiting,
                max_prefill_backlog_tokens=getattr(
                    args, "max_prefill_backlog_tokens", 0
                ),
                default_deadline_s=args.default_deadline_s,
                # SLO classes (docs/architecture/ingress_scale.md):
                # the header-less default and the cheapest-first
                # batch watermark scale.
                default_request_class=getattr(
                    args, "default_request_class", "interactive"
                ),
                class_watermark_scale={
                    "interactive": 1.0,
                    "batch": getattr(args, "batch_watermark_scale", 0.5),
                },
            ),
            engine_stats=readiness,
        ),
        # Observability plane (docs/architecture/observability.md):
        # /debug/steps reads the local engine's flight recorder;
        # /debug/profile captures jax.profiler windows when a directory
        # is configured.
        debug=engine if hasattr(engine, "debug_steps") else None,
        profiler=Profiler(base_dir=getattr(args, "profile_dir", None)),
    )
    await service.start()
    stack.push(service.stop)
    print(
        f"OpenAI server on http://{args.http_host}:{service.port} "
        f"(models: {manager.models() or '<awaiting workers>'})",
        flush=True,
    )
    return service


def _first_model(manager):
    models = manager.models()
    if not models:
        raise SystemExit("no models registered (is a worker connected?)")
    return models[0]


async def _text_chat(args, manager) -> None:
    """Interactive chat loop (reference: input/text.rs)."""
    from dynamo_tpu.llm.protocols.openai import ChatCompletionRequest
    from dynamo_tpu.runtime.engine import Context

    model = _first_model(manager)
    engine = manager.get(model)
    history: list[dict] = []
    print(f"chatting with {model!r} — empty line or Ctrl-D to exit", flush=True)
    while True:
        try:
            line = await asyncio.to_thread(input, "> ")
        except (EOFError, KeyboardInterrupt):
            break
        if not line.strip():
            break
        history.append({"role": "user", "content": line})
        req = ChatCompletionRequest.model_validate(
            {
                "model": model,
                "messages": history,
                "stream": True,
                "max_tokens": args.max_tokens,
            }
        )
        parts: list[str] = []
        async for chunk in engine.generate(Context(req)):
            obj = chunk.model_dump(exclude_none=True) if hasattr(
                chunk, "model_dump"
            ) else chunk
            for choice in obj.get("choices", []):
                piece = (choice.get("delta") or {}).get("content")
                if piece:
                    parts.append(piece)
                    print(piece, end="", flush=True)
        print(flush=True)
        history.append({"role": "assistant", "content": "".join(parts)})


async def _batch(args, manager, path: str) -> None:
    """Prompt-file mini-benchmark: one prompt per line; reports per-request
    latency and aggregate token rates (reference: input/batch.rs:45,143-191)."""
    import numpy as np

    from dynamo_tpu.llm.protocols.openai import ChatCompletionRequest
    from dynamo_tpu.runtime.engine import Context

    def _read_prompts() -> list[str]:
        with open(path) as f:
            return [ln.strip() for ln in f if ln.strip()]

    prompts = await asyncio.to_thread(_read_prompts)
    if not prompts:
        raise SystemExit(f"{path} contains no prompts")
    model = _first_model(manager)
    engine = manager.get(model)
    sem = asyncio.Semaphore(args.concurrency)

    async def run_one(prompt: str):
        async with sem:
            req = ChatCompletionRequest.model_validate(
                {
                    "model": model,
                    "messages": [{"role": "user", "content": prompt}],
                    "stream": True,
                    "max_tokens": args.max_tokens,
                }
            )
            t0 = time.monotonic()
            first = None
            n_tokens = 0
            usage = None
            async for chunk in engine.generate(Context(req)):
                obj = chunk.model_dump(exclude_none=True) if hasattr(
                    chunk, "model_dump"
                ) else chunk
                for choice in obj.get("choices", []):
                    if (choice.get("delta") or {}).get("content"):
                        n_tokens += 1
                        if first is None:
                            first = time.monotonic() - t0
                if obj.get("usage"):
                    usage = obj["usage"]
            out = usage["completion_tokens"] if usage else n_tokens
            inp = usage["prompt_tokens"] if usage else 0
            return time.monotonic() - t0, first, inp, out

    t0 = time.monotonic()
    results = await asyncio.gather(*[run_one(p) for p in prompts])
    elapsed = time.monotonic() - t0
    ttfts = [r[1] for r in results if r[1] is not None]
    toks_in = sum(r[2] for r in results)
    toks_out = sum(r[3] for r in results)
    report = {
        "requests": len(prompts),
        "elapsed_s": round(elapsed, 2),
        "tokens_in_per_s": round(toks_in / elapsed, 1),
        "tokens_out_per_s": round(toks_out / elapsed, 1),
        "p50_ttft_ms": round(1000 * float(np.median(ttfts)), 1) if ttfts else None,
        "p95_ttft_ms": round(
            1000 * float(np.percentile(ttfts, 95)), 1
        ) if ttfts else None,
        "mean_request_s": round(
            float(np.mean([r[0] for r in results])), 2
        ),
    }
    print(json.dumps(report), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
