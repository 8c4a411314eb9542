"""Worker selection: the KV-aware cost function.

The reference's DefaultWorkerSelector (reference: lib/llm/src/kv_router/
scheduler.rs:248-330): per candidate worker,

    logit = overlap_weight * overlap_blocks * block_size / isl
            - gpu_cache_usage
            - normalized_waiting
            [- transfer_cost_weight * transfer_s / max_transfer_s]

pick the max, break ties randomly, then bump the winner's predicted load so
back-to-back requests don't stampede one worker (scheduler.rs:214). Weights
default to the reference's (KvRouterConfig kv_router.rs:59-81).

The bracketed term is the NetKV-style (arxiv 2606.03910) network-aware
extension (``KvRouterConfig.network_aware`` / ``--route-network-aware``):
the estimated time to land the request's NON-overlapping prefix blocks on
each candidate, priced by the per-worker ingest-rate EMA the KV
observatory exports (docs/architecture/planner.md "network-aware decode
selection"); the per-candidate cost is audited in ``/debug/routes``.
"""

from __future__ import annotations

import logging
import random
from dataclasses import dataclass, field

from dynamo_tpu.llm.kv_router.metrics_aggregator import ProcessedEndpoints
from dynamo_tpu.planner.calibration import (
    HANDOFF_GBPS,
    KV_BYTES_PER_TOKEN,
)

logger = logging.getLogger(__name__)


@dataclass
class KvRouterConfig:
    overlap_score_weight: float = 2.0
    gpu_cache_usage_weight: float = 1.0
    waiting_requests_weight: float = 1.0
    block_size: int = 16
    sharded_indexer_shards: int = 0  # >0: use KvIndexerSharded
    # NetKV-style network-aware decode selection (ROADMAP #4,
    # docs/architecture/planner.md): price each candidate by the time
    # to move the NON-overlapping prefix blocks onto it, over the
    # per-worker ingest-rate EMA the KV observatory already exports
    # (``ForwardPassMetrics.kvbm_link_g2g1_bps`` — host→HBM onboard).
    # The term is normalized against the worst candidate so it stays
    # commensurate with the other O(1) score terms; ``--route-network-
    # aware`` flips it on (cli.py).
    network_aware: bool = False
    transfer_cost_weight: float = 1.0
    # KV bytes per block for the transfer estimate: 16-token blocks of
    # the llama3.2-1b layout (2·16 layers·8 kv-heads·64 dim·2 B =
    # 32 KiB/token). Only the RATIO across candidates shifts selection;
    # the absolute value just scales the audited transfer_ms.
    block_bytes: int = 16 * KV_BYTES_PER_TOKEN
    # Fallback link when a worker exports no rate EMA yet (fresh spawn,
    # no KVBM): the measured batched device channel,
    # single-sourced from planner/calibration.py so a re-fit reprices
    # the router and the G4 peer tier together (drift-gated in
    # tests/test_calibration.py).
    default_link_gbps: float = HANDOFF_GBPS


@dataclass
class SchedulingDecision:
    worker_id: int
    overlap_blocks: int
    logit: float
    # EVERY candidate's score, not just the winner's — the route-audit
    # record needs the full field to explain why a worker lost
    # (docs/architecture/observability.md "KV observatory"). Each entry:
    # {"worker", "logit", "overlap_blocks", "usage", "waiting"}.
    candidates: list[dict] = field(default_factory=list)


class DefaultWorkerSelector:
    def __init__(self, cfg: KvRouterConfig | None = None, seed: int | None = None):
        self.cfg = cfg or KvRouterConfig()
        self._rng = random.Random(seed)
        # Predicted-load bump: worker -> extra active blocks assumed until
        # the next metrics scrape overwrites it.
        self._predicted_blocks: dict[int, int] = {}

    def on_metrics(self) -> None:
        """A fresh scrape landed — predicted deltas are now baked in."""
        self._predicted_blocks.clear()

    def select(
        self,
        endpoints: ProcessedEndpoints,
        overlaps: dict[int, int],
        isl: int,
    ) -> SchedulingDecision | None:
        cfg = self.cfg
        best: list[SchedulingDecision] = []
        if not endpoints.metrics:
            return None
        max_waiting = max(
            (m.num_requests_waiting for m in endpoints.metrics.values()),
            default=0,
        )
        # Network-aware transfer estimate (two passes: the term is
        # normalized against the WORST candidate so a uniformly fast or
        # uniformly slow fleet shifts every logit equally — only link/
        # overlap ASYMMETRY moves the decision).
        transfer_s: dict[int, float] = {}
        if cfg.network_aware:
            isl_blocks = (isl + cfg.block_size - 1) // cfg.block_size
            for wid, m in endpoints.metrics.items():
                missing = max(isl_blocks - overlaps.get(wid, 0), 0)
                link_bps = (
                    getattr(m, "kvbm_link_g2g1_bps", 0.0)
                    or cfg.default_link_gbps * 1e9
                )
                # Price bytes at the worker's ADVERTISED KV block
                # precision (kvbm_kv_quant_ratio ~0.5 on an int8 fleet —
                # docs/architecture/kv_quant.md): cfg.block_bytes is the
                # bf16 layout, so without the ratio a quantized worker's
                # transfers would be overcharged 2× in /debug/routes.
                ratio = getattr(m, "kvbm_kv_quant_ratio", 1.0) or 1.0
                transfer_s[wid] = (
                    missing * cfg.block_bytes * ratio / max(link_bps, 1.0)
                )
        t_max = max(transfer_s.values(), default=0.0)
        candidates: list[dict] = []
        for wid, m in endpoints.metrics.items():
            overlap = overlaps.get(wid, 0)
            total = max(m.kv_total_blocks, 1)
            usage = (
                m.kv_active_blocks + self._predicted_blocks.get(wid, 0)
            ) / total
            waiting = m.num_requests_waiting / max(max_waiting, 1)
            logit = (
                cfg.overlap_score_weight * overlap * cfg.block_size / max(isl, 1)
                - cfg.gpu_cache_usage_weight * usage
                - cfg.waiting_requests_weight * waiting
            )
            cand = {
                "worker": wid,
                "logit": round(logit, 6),
                "overlap_blocks": overlap,
                "usage": round(usage, 4),
                "waiting": round(waiting, 4),
            }
            if cfg.network_aware and t_max > 0:
                term = cfg.transfer_cost_weight * transfer_s[wid] / t_max
                logit -= term
                cand["transfer_ms"] = round(1000.0 * transfer_s[wid], 3)
                cand["transfer_term"] = round(term, 6)
                cand["logit"] = round(logit, 6)
            candidates.append(cand)
            d = SchedulingDecision(wid, overlap, logit)
            if not best or d.logit > best[0].logit + 1e-9:
                best = [d]
            elif abs(d.logit - best[0].logit) <= 1e-9:
                best.append(d)
        if not best:
            return None
        decision = self._rng.choice(best)
        decision.candidates = candidates
        # Bump predicted load by the blocks this request will occupy.
        new_blocks = max(
            (isl - decision.overlap_blocks * cfg.block_size + cfg.block_size - 1)
            // cfg.block_size,
            0,
        )
        self._predicted_blocks[decision.worker_id] = (
            self._predicted_blocks.get(decision.worker_id, 0) + new_blocks
        )
        return decision
