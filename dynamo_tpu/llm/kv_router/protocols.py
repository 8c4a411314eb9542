"""Router-plane wire types.

Mirrors the reference's protocol surface (reference:
lib/llm/src/kv_router/protocols.rs:43-135): per-worker forward-pass load
metrics and KV-cache stored/removed/cleared events. Block identity here is
the chained *sequence hash* (llm/tokens.py) everywhere — the reference keeps
separate local/external hashes because engines hash differently; our engine
shares the framework's hash chain, so one identity suffices.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any


@dataclass
class ForwardPassMetrics:
    """Per-worker load snapshot (reference: protocols.rs:43)."""

    request_active_slots: int = 0
    request_total_slots: int = 0
    kv_active_blocks: int = 0
    kv_total_blocks: int = 0
    num_requests_waiting: int = 0
    gpu_cache_usage_perc: float = 0.0
    gpu_prefix_cache_hit_rate: float = 0.0
    data_parallel_rank: int = 0
    # Speculative decoding observability: delivered
    # tokens per spec step (≥1.0 when winning; 0.0 = engine not built
    # with speculative_k), whether the auto-gate currently has it on,
    # and the unified draft-verify split — draft tokens fed vs accepted
    # by the in-dispatch accept-prefix law (the cumulative twins of the
    # flight recorder's per-dispatch "spec" records).
    spec_tokens_per_step: float = 0.0
    spec_active: int = 0
    spec_drafted_tokens_total: int = 0
    spec_accepted_tokens_total: int = 0
    # Compile-lifecycle observability (engine/compile_cache.py): shapes
    # that compiled UNDER traffic (the r05 regression signal — must stay
    # 0 on a warmed worker), total first-execution stall, and readiness.
    mid_traffic_compiles_total: int = 0
    compile_stall_ms_total: float = 0.0
    engine_ready: int = 0
    warmup_programs_total: int = 0
    # Unified-step observability (docs/architecture/unified_step.md):
    # per-phase token split across unified dispatches and the latest
    # batch fill ratio (real tokens / padded budget) — what the one-chip
    # co-location A/Bs (ROADMAP item #3) tune against. All zero on a
    # phase-alternating engine.
    unified_step_tokens_decode_total: int = 0
    unified_step_tokens_prefill_total: int = 0
    # Host arrays handed to the device across unified dispatches: one
    # packed operand buffer each (two with a replayed host feed).
    unified_operand_transfers_total: int = 0
    # Block diffusion and the grouped expert path (zero on models without
    # them): lane passes, tokens they committed, routed expert rows.
    diffusion_passes_total: int = 0
    diffusion_committed_tokens_total: int = 0
    moe_grouped_rows_total: int = 0
    # State that is not pages (zero on models without recurrent layers).
    recurrent_state_slots_in_use: int = 0
    recurrent_state_bytes: int = 0
    recurrent_state_bytes_per_slot: int = 0
    recurrent_state_usage_perc: float = 0.0
    batch_fill_ratio: float = 0.0
    # SLO-aware co-location (engine/coloc.py; ROADMAP #3): the live
    # prefill quantum, decode ITL EMA vs the configured SLO, dispatches
    # that violated it, per-phase admission refusals, and the
    # phase-aware prefill-pressure gauge in TOKENS the HTTP admission
    # watermark reads. All zero without unified co-location.
    coloc_quantum: int = 0
    itl_ema_ms: float = 0.0
    itl_p95_ms: float = 0.0
    itl_headroom_ms: float = 0.0
    itl_slo_violations_total: int = 0
    coloc_prefill_deferrals_total: int = 0
    prefill_backlog_tokens: int = 0
    # Robustness observability (docs/architecture/failure_model.md):
    # requests completed via a degradation path (remote-prefill death ⇒
    # local recompute), injected faults fired, and transport retries —
    # all monotonic counters per worker process.
    degraded_requests_total: int = 0
    faults_injected_total: int = 0
    retries_total: int = 0
    # Failover plane (docs/architecture/failure_model.md "Mid-stream
    # failover"): mid-stream re-dispatches attempted / completed, corpses
    # evicted by the mark-dead fast path (all process-wide monotonic),
    # and the engine-thread liveness heartbeat — seconds since the last
    # dispatch-loop pass (a wedged engine shows as unbounded growth).
    failover_total: int = 0
    failover_success_total: int = 0
    workers_marked_dead_total: int = 0
    last_dispatch_age_s: float = 0.0
    # Overload observability (docs/architecture/overload_and_drain.md):
    # load shed by bounded queues/gates, work cancelled past its deadline
    # (both process-wide monotonic counters), and whether this worker is
    # draining (routers should stop picking it; 1 during rolling restart).
    shed_requests_total: int = 0
    deadline_exceeded_total: int = 0
    draining: int = 0
    # SLO classes (llm/slo.py; docs/architecture/ingress_scale.md):
    # per-class waiting depth (the fleet planner's class-weighted
    # pressure inputs) and per-class shed totals (the cheapest-first
    # degradation audit trail — batch must absorb sheds first).
    num_waiting_interactive: int = 0
    num_waiting_batch: int = 0
    shed_interactive_total: int = 0
    shed_batch_total: int = 0
    # Observability-plane counters (docs/architecture/observability.md):
    # request traces auto-opened but never finished (reaped by the TTL
    # sweep — a rising count means marks are landing after cancellation
    # somewhere) and total dispatches recorded by the flight recorder.
    abandoned_traces_total: int = 0
    flight_steps_total: int = 0
    # KV observatory — the ACTUAL side of the predicted-vs-actual loop
    # (docs/architecture/observability.md "KV observatory"): blocks this
    # worker really reused per tier, cumulative. The router's route-audit
    # records carry the PREDICTED overlap; benchmarks/route_audit.py joins
    # the two by trace id.
    kv_reused_device_blocks_total: int = 0   # G1 prefix-cache hits
    kv_reused_host_blocks_total: int = 0     # G2 host-tier onboards
    kv_reused_disk_blocks_total: int = 0     # G3-origin blocks (promoted)
    kv_reused_peer_blocks_total: int = 0     # G4-origin blocks (peer pulls)
    # KVBM tier telemetry (block_manager/manager.py stats(), prefixed
    # kvbm_ by the engine): occupancy, hit/miss/eviction/promotion/
    # offload counters, and per-link byte-rate EMAs — the transfer-cost
    # inputs NetKV-style network-aware decode selection (ROADMAP #4)
    # scores against. All zero without an attached block manager.
    # Adaptive onboard-gate observability (EngineConfig.kvbm_adaptive_
    # gate): onboards skipped because recompute priced cheaper, and the
    # engine-side host→HBM rate EMA the gate prices with. Registered on
    # every surface (dynarace DT011 metric-surface parity).
    kvbm_onboard_skips: int = 0
    kvbm_onboard_bps: float = 0.0
    kvbm_host_registered: int = 0
    kvbm_host_usage: float = 0.0
    kvbm_disk_registered: int = 0
    kvbm_disk_usage: float = 0.0
    kvbm_host_evictions_total: int = 0
    kvbm_disk_evictions_total: int = 0
    kvbm_host_stored_blocks_total: int = 0
    kvbm_host_hit_blocks_total: int = 0
    kvbm_host_miss_blocks_total: int = 0
    kvbm_promoted_blocks_total: int = 0
    kvbm_promotions_requested_total: int = 0
    kvbm_offloaded_blocks_total: int = 0
    kvbm_link_g1g2_bps: float = 0.0   # device→host store rate
    kvbm_link_g2g3_bps: float = 0.0   # host→disk offload rate
    kvbm_link_g3g2_bps: float = 0.0   # disk→host promotion rate
    kvbm_link_g2g1_bps: float = 0.0   # host→HBM onboard rate (engine EMA)
    # KV-block precision (docs/architecture/kv_quant.md): this worker's
    # stored-KV bytes ratio vs the compute dtype (1.0 bf16, ~0.5 int8 —
    # the network-aware selector prices non-overlapping-block transfers
    # with it so quantized fleets aren't overcharged 2×), plus the
    # quantized fraction of stored blocks per KVBM tier and cumulative
    # bytes saved by int8 packing across G2 stores + G3 offloads.
    kvbm_kv_quant_ratio: float = 1.0
    kvbm_quant_host_density: float = 0.0
    kvbm_quant_disk_density: float = 0.0
    kvbm_quant_bytes_saved_total: int = 0
    # Weight precision (docs/architecture/weight_quant.md): whether the
    # per-matmul weight-quant policy is armed on this worker, the HBM
    # bytes its quantized tree saves vs full precision, and the
    # quantized fraction of resident weight bytes. Registered on every
    # surface (dynarace DT011 metric-surface parity).
    weight_quant_active: float = 0.0
    weight_quant_bytes_saved: float = 0.0
    weight_quant_density: float = 0.0
    # G4 peer tier (block_manager/peer.py; docs/architecture/kvbm_g4.md):
    # fleet-wide pulls won against the recompute price, the bytes they
    # moved, pulls that degraded to local recompute (peer death, timeout,
    # losing price after dispatch), and the measured pull-throughput EMA
    # the pricing law feeds back on. All zero without a peer client.
    kvbm_g4_pulls_total: int = 0
    kvbm_g4_pull_bytes_total: int = 0
    kvbm_g4_pull_fallbacks_total: int = 0
    kvbm_link_peer_bps: float = 0.0   # peer→host pull rate (client EMA)
    # Integrity envelope (docs/architecture/integrity.md): per-trust-
    # boundary checksum failures (host = G2 onboard, disk = G3 read/
    # promotion/recovery, peer = G4 pull, frame = disagg KV wire) plus
    # the background G3 scrubber's sweep counters. Registered on every
    # surface (dynarace DT011 metric-surface parity). Nonzero failures
    # with zero stream deviations means detection + quarantine +
    # recompute is WORKING, not that requests were harmed.
    kvbm_integrity_failures_total: int = 0
    kvbm_integrity_failures_host: int = 0
    kvbm_integrity_failures_disk: int = 0
    kvbm_integrity_failures_peer: int = 0
    kvbm_integrity_failures_frame: int = 0
    kvbm_scrub_scanned_total: int = 0
    kvbm_scrub_detected_total: int = 0

    def to_wire(self) -> dict[str, Any]:
        return self.__dict__.copy()

    @staticmethod
    def from_wire(d: dict[str, Any]) -> "ForwardPassMetrics":
        m = ForwardPassMetrics()
        for k in m.__dict__:
            if k in d:
                setattr(m, k, d[k])
        return m


@dataclass
class KvCacheEventData:
    """stored / removed / cleared (reference: protocols.rs:88-135), plus
    ``worker_dead`` — the mark-dead broadcast (kv_router/router.py
    ``note_worker_dead``): the replica that observed a worker death
    shares it on the event plane so every sibling replica prunes the
    corpse's radix blocks AND drops its load snapshot within one apply
    (docs/architecture/ingress_scale.md)."""

    kind: str                 # "stored" | "removed" | "cleared" | "worker_dead"
    block_hashes: list[int] = field(default_factory=list)   # sequence hashes
    parent_hash: int | None = None              # stored: parent of first block
    token_ids: list[list[int]] | None = None    # stored: per-block tokens

    def to_wire(self) -> dict[str, Any]:
        return {
            "kind": self.kind,
            "block_hashes": self.block_hashes,
            "parent_hash": self.parent_hash,
            "token_ids": self.token_ids,
        }

    @staticmethod
    def from_wire(d: dict[str, Any]) -> "KvCacheEventData":
        return KvCacheEventData(
            kind=d["kind"],
            block_hashes=list(d.get("block_hashes") or []),
            parent_hash=d.get("parent_hash"),
            token_ids=d.get("token_ids"),
        )


@dataclass
class RouterEvent:
    """A KV event attributed to a worker (reference: indexer.rs:138).

    ``published_unix`` is the publisher's wall clock at broadcast — the
    indexer's ``recv - published_unix`` is the publish→apply lag, the
    staleness axis the route-audit loop measures (same NTP-level
    assumption as ``deadline_unix`` / the trace clock-offset hint).
    None on legacy frames and replayed recordings (no lag recorded)."""

    worker_id: int
    event: KvCacheEventData
    published_unix: float | None = None

    def to_wire(self) -> dict[str, Any]:
        return {
            "worker_id": self.worker_id,
            "event": self.event.to_wire(),
            "published_unix": self.published_unix,
        }

    @staticmethod
    def from_wire(d: dict[str, Any]) -> "RouterEvent":
        return RouterEvent(
            worker_id=d["worker_id"],
            event=KvCacheEventData.from_wire(d["event"]),
            published_unix=d.get("published_unix"),
        )


KV_EVENT_PLANE = "kv_events"
KV_METRICS_ENDPOINT = "load_metrics"

#: Hit-rate plane payloads (msgpack dicts) come in two kinds, joined by
#: trace id (docs/architecture/observability.md "KV observatory"):
#:   kind="predicted"  router-side, at decision time: worker_id,
#:                     overlap_blocks, isl_blocks, trace, request id
#:   kind="actual"     engine-side, at admission: per-tier reused block
#:                     counts (device/host/disk), trace, request id
#: Legacy frames without a "kind" field are predicted records.
KV_HIT_RATE_PLANE = "kv-hit-rate"

#: Registry re-announce plane (docs/architecture/kvbm_g4.md): any actor
#: may broadcast a (possibly empty) msgpack dict here to ask every
#: worker to re-publish its resident block hashes as idempotent
#: ``stored`` events on KV_EVENT_PLANE. A rejoined router replica uses
#: it to rebuild its radix view of pre-rejoin blocks (the PR 14
#: measured staleness gap); workers also re-announce periodically so a
#: listener that missed the trigger converges anyway.
KV_REANNOUNCE_PLANE = "kv_reannounce"
