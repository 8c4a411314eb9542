"""Mocker: a device-free simulated engine.

The reference builds a full vLLM simulator (reference: lib/llm/src/mocker/
{scheduler,kv_manager,sequence,evictor}.rs — watermark scheduling, LRU
eviction, quadratic-prefill/linear-decode cost model) to test routing and
KV planes without GPUs. Our engine's scheduler and block allocator are
already framework-owned, so the mocker is simply the real TpuEngine with
the ModelRunner swapped for a cost-model simulator: everything above the
runner (continuous batching, prefix cache, preemption, KV events, metrics)
is the *production* code path, exercised at simulation speed.
"""

from __future__ import annotations

import time
from dataclasses import dataclass

import numpy as np

from dynamo_tpu.engine.compile_cache import (
    CompileStats,
    WarmupPlanMixin,
    token_budget,
)
from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.engine.engine import TpuEngine
from dynamo_tpu.engine.runner import UnifiedOut, _unified_warm_lanes
from dynamo_tpu.planner.calibration import (
    KV_BYTES_PER_TOKEN,
    PREFILL_QUADRATIC_US,
)


@dataclass
class MockerConfig:
    """Cost model (reference: mocker/scheduler.rs:16-42).

    Per-PHASE pricing (ROADMAP #3 / the coloc A/B): a dispatch costs
    f(decode_lanes, prefill_tokens), not a flat per-step constant —
    ``decode_time_per_step_us`` is the per-dispatch base (the weight
    pass every step streams regardless of content),
    ``decode_time_per_lane_us`` prices each decode lane's KV read, and
    prefill tokens pay the linear(+quadratic) compute term. Standalone
    prefill calls (the fleet simulator's prefill pool,
    planner/simulate.py; `_SimRunner` has none) additionally pay
    ``prefill_dispatch_base_us`` — their OWN weight pass, which is
    exactly what co-located prefill quanta don't pay (they ride the
    mixed dispatch's): the measurable mechanism behind the Nexus /
    FlexNPU co-location win, and what makes quantum changes visibly
    move simulated ITL. Defaults keep the legacy flat pricing
    (both new knobs 0) so existing scenarios are unchanged.

    CALIBRATED constants pinned to the recorded r04/r05 chip runs live
    in ``planner/calibration.py`` (``calibrated_mocker_config()``) —
    the fleet simulator's xPyD projections (planner/simulate.py,
    ``BENCH_XPYD=1``) replay this cost model with those values, and
    tests/test_xpyd.py gates the reproduction of the r04 headline at
    <10 % so edits here can't silently drift the projections.
    """

    prefill_time_per_token_us: float = 2.0   # linear term
    prefill_quadratic_us: float = PREFILL_QUADRATIC_US  # * len^2 — attention
    decode_time_per_step_us: float = 500.0   # per dispatch (weight pass)
    decode_time_per_lane_us: float = 0.0     # per decode lane per step
    prefill_dispatch_base_us: float = 0.0    # per standalone prefill call
    # Decode HBM-bytes bandwidth term (the BENCH_QUANT A/B's pricing —
    # docs/architecture/kv_quant.md): each decode lane's step reads its
    # whole KV context from HBM, so a dispatch additionally costs
    #   Σ_lanes ctx_tokens · kv_bytes_per_token · kv_bytes_ratio
    #     / (decode_hbm_gbps · 1e9)   seconds.
    # 0.0 keeps the legacy context-free pricing (every existing
    # scenario unchanged). Calibrated values live in
    # planner/calibration.py: decode_hbm_gbps from the r04 recording's
    # 282.8 GB/s effective (older harness, not reproduced), kv_bytes_per_token = the 32 KiB/token 1B
    # layout, kv_bytes_ratio ~0.502 for int8+scales (1.0 bf16).
    decode_hbm_gbps: float = 0.0
    kv_bytes_per_token: float = float(KV_BYTES_PER_TOKEN)
    kv_bytes_ratio: float = 1.0
    # Weight-pass bytes term (the BENCH_WQUANT A/B's pricing —
    # docs/architecture/weight_quant.md): the dispatch base above IS the
    # per-step weight pass, so when ``weight_bytes_per_step`` > 0 AND
    # ``decode_hbm_gbps`` > 0 the base is REPLACED (not added to) by
    #   weight_bytes_per_step · weight_bytes_ratio
    #     / (decode_hbm_gbps · 1e9)   seconds,
    # for both the decode dispatch base and the standalone-prefill
    # dispatch base — co-located quanta and standalone prefill now price
    # the SAME precision-aware pass instead of a flat constant. With the
    # bytes term off, ``weight_bytes_ratio`` still scales the flat bases
    # so un-calibrated scenarios can A/B precision. Defaults (0.0 / 1.0)
    # keep every existing scenario byte-identical. Calibrated value:
    # planner/calibration.py WEIGHT_BYTES_PER_STEP (~3.02 GB, the r04
    # base at the recorded 282.8 GB/s — older harness, not reproduced); int8-weights ratio ~0.501 from
    # calibration.weight_quant_bytes_ratio().
    weight_bytes_per_step: float = 0.0
    weight_bytes_ratio: float = 1.0
    vocab_size: int = 32000
    seed: int = 0
    # Deterministic greedy stream: every sampled token is a pure affine
    # hash of (previous token, its position), so ANY worker resuming
    # from (last token, length) — e.g. a failover replay of
    # prompt + already-emitted tokens — continues the byte-identical
    # stream a single uninterrupted worker would have produced. This is
    # the device-free stand-in for greedy decoding's determinism, which
    # the mid-stream-failover proof gates on
    # (docs/architecture/failure_model.md "Mid-stream failover").
    # Default off: the seeded-RNG streams every existing test pins.
    deterministic_tokens: bool = False
    # Position term of the deterministic hash. True (default) keeps the
    # PR 13 failover form f(prev, pos). False makes the chain a pure
    # function of the previous token — f(prev) — which (with a small
    # vocab) cycles, so prompt-lookup drafts EVENTUALLY match the chain:
    # the accepting-draft regime the BENCH_SPEC A/B measures. Either
    # way the emitted stream follows the closed form exactly, across
    # accepted AND rejected drafts (the failover byte-identity
    # invariant is acceptance-independent).
    det_positional: bool = True
    # G4 peer-link cost model (docs/architecture/kvbm_g4.md): the pacing
    # rate a mocker worker's PeerBlockServer serves fleet pulls at, in
    # GB/s. 0.0 = serve unpaced (legacy; no G4 scenario armed). The
    # BENCH_G4 A/B sets this to the calibrated HANDOFF_GBPS so the
    # pull-vs-recompute pricing sees a realistic transfer time, and the
    # slow-link leg sets it tiny so pricing must choose recompute.
    peer_link_gbps: float = 0.0


def det_next_token(prev_tok, next_pos, vocab: int, positional: bool = True):
    """The deterministic-token closed form (MockerConfig.deterministic_
    tokens): next token = affine hash of (previous token[, its
    position]). Module-level so the BENCH_SPEC leg and tests build
    on-chain prompts through the SAME law the sim verifies against —
    a constant edit here cannot silently break their acceptance setup."""
    prev = np.asarray(prev_tok, np.int64)
    if not positional:
        return (prev * 1103515245 + 7) % vocab
    pos = np.asarray(next_pos, np.int64)
    return (prev * 1103515245 + pos * 12345 + 7) % vocab


class _SimRunner(WarmupPlanMixin):
    """ModelRunner lookalike: sleeps per the cost model, emits pseudo-tokens.

    Tokens are deterministic in (seed, inputs) so tests can assert streams.
    Mirrors the real runner's compile lifecycle (shape bucketing,
    CompileStats, warmup planning) so readiness gating and mid-traffic-
    compile accounting are testable device-free.
    """

    def __init__(self, cfg: EngineConfig, sim: MockerConfig) -> None:
        self.cfg = cfg
        self.sim = sim
        self.cache_head_dim = cfg.model.head_dim  # layout-handshake parity
        self._rng = np.random.default_rng(sim.seed)
        self.compile_cache_dir = None
        self.compile_stats = CompileStats()
        # Simulated per-block KV bytes so KVBM/disagg paths can verify
        # byte fidelity without a device.
        self._fake_kv: dict[int, np.ndarray] = {}

    def _warm_op(self, kind, t):
        """Warm calls for the sim's program kinds (WarmupPlanMixin) —
        the unified family only, like the real runner."""
        cfg = self.cfg
        sampling = (0.0, 0, 1.0)
        trash = [0] * cfg.max_blocks_per_seq
        warm_lanes = _unified_warm_lanes(
            t, self.unified_slots, cfg.max_model_len, trash, sampling
        )
        if not warm_lanes:
            return None
        if kind == "unified":
            return lambda: self.unified_step(warm_lanes)
        if kind == "unified_full":
            if not cfg.sampling_extras:
                return None
            extras = {
                "slots": [0] * len(warm_lanes),
                "counts_add": [False] * len(warm_lanes),
                "reset": [False] * len(warm_lanes),
                "freq": [0.0] * len(warm_lanes),
                "pres": [0.0] * len(warm_lanes),
            }
            return lambda: self.unified_step(warm_lanes, extras=extras)
        if kind == "unified_mm":
            if not cfg.multimodal:
                return None
            mm = [None] * len(warm_lanes)
            mm[0] = [(0, np.zeros((1, 4), np.float32))]
            return lambda: self.unified_step(warm_lanes, mm=mm)
        return None

    def slot_of(self, block_ids: list[int], position: int) -> int:
        bs = self.cfg.block_size
        return block_ids[position // bs] * bs + position % bs

    def gather_block(self, block_idx: int) -> np.ndarray:
        return self._fake_kv.get(
            block_idx, np.full(8, block_idx, np.float32)
        )

    def gather_block_device(self, block_idx: int) -> np.ndarray:
        # No device in the mocker — the "device-resident snapshot" is the
        # same host array (keeps the device transfer path runnable).
        return self.gather_block(block_idx)

    def scatter_block(self, block_idx: int, data: np.ndarray) -> None:
        self._fake_kv[block_idx] = np.asarray(data)

    # Batched forms (ops/kv_copy.py parity): one "program" for N blocks.
    def gather_many(self, block_idxs) -> np.ndarray:
        return np.stack([self.gather_block(b) for b in block_idxs])

    def gather_many_device(self, block_idxs) -> np.ndarray:
        return self.gather_many(block_idxs)

    def scatter_many(self, block_idxs, datas) -> None:
        for b, d in zip(block_idxs, datas):
            self.scatter_block(b, d)

    def scatter_many_device(self, block_idxs, data) -> None:
        self.scatter_many(block_idxs, data)

    # unified_full/mm twin of the real runner's logprob-array attribute
    # (fake constant arrays set per extras dispatch).
    last_unified_logprobs = None

    def _prefill_cost_us(self, n: int) -> float:
        """Token-compute time of ``n`` prefill (or draft-verify) rows."""
        return (
            self.sim.prefill_time_per_token_us * n
            + self.sim.prefill_quadratic_us * n * n
        )

    # -- deterministic greedy stream (MockerConfig.deterministic_tokens) --
    def _det_next(self, prev_tok, next_pos):
        """Next token = affine hash of (previous token, its position) —
        the property that makes failover replay byte-identical: worker B
        prefilling prompt+emitted (length P+K) samples
        f(emitted[-1], P+K), exactly what worker A's decode at position
        P+K-1 would have produced. int64 math: no overflow at any
        vocab/position this sim sees. With ``det_positional=False`` the
        position term drops — the chain is f(prev) alone (cyclic at
        small vocab: the accepting-draft spec regime)."""
        return det_next_token(
            prev_tok, next_pos, self.sim.vocab_size,
            positional=self.sim.det_positional,
        )

    def _weight_pass_us(self, base_us: float) -> float:
        """The dispatch's weight-pass time at the configured precision:
        bytes-priced when the calibrated term is armed (replacing the
        flat base — the base IS the weight pass), else the flat base
        scaled by the precision ratio."""
        sim = self.sim
        if sim.weight_bytes_per_step > 0 and sim.decode_hbm_gbps > 0:
            return (
                sim.weight_bytes_per_step * sim.weight_bytes_ratio
                / (sim.decode_hbm_gbps * 1e9) * 1e6
            )
        return base_us * sim.weight_bytes_ratio

    def _kv_read_us(self, ctx_tokens: float) -> float:
        """HBM time to stream `ctx_tokens` of KV at the configured
        effective bandwidth and precision (0 when the term is off)."""
        if self.sim.decode_hbm_gbps <= 0:
            return 0.0
        bytes_ = (
            ctx_tokens * self.sim.kv_bytes_per_token * self.sim.kv_bytes_ratio
        )
        return bytes_ / (self.sim.decode_hbm_gbps * 1e9) * 1e6

    @property
    def unified_slots(self) -> int:
        return self.cfg.max_num_seqs + self.cfg.prefill_batch

    @property
    def kv_bytes_ratio(self) -> float:
        """Advertised stored-KV precision ratio (kv_quant parity with
        the real runner) — what the network-aware selector prices
        transfers with on a mocker fleet."""
        if self.cfg.kv_quant != "int8":
            return 1.0
        from dynamo_tpu.block_manager.config import KvLayoutConfig

        lay = KvLayoutConfig.for_engine(self.cfg, self.cache_head_dim)
        return lay.block_bytes / lay.unquantized_block_bytes

    # Weight-quant gauge parity with the real runner (engine
    # _flush_side_channels reads these via getattr): the sim has no
    # resident weights, so "bytes saved" is the simulated per-step
    # streaming saving the cost model actually prices.
    @property
    def weight_quant_bytes_saved(self) -> float:
        return (
            (1.0 - self.sim.weight_bytes_ratio)
            * self.sim.weight_bytes_per_step
        )

    @property
    def weight_quant_density(self) -> float:
        return 1.0 if getattr(self.cfg, "weight_quant", None) else 0.0

    def unified_step(
        self, lanes, feed=None, draft_lens=None, extras=None, mm=None
    ) -> UnifiedOut:
        """Sim twin of ModelRunner.unified_step: one mixed dispatch
        priced per phase — the dispatch base (weight pass) + each decode
        lane's KV read + the prefill quanta's token compute — bucketed
        on the budget ladder for compile accounting. Decode lanes are
        the 1-token spans (a 1-token prefill TAIL quantum misclassifies
        by one token — negligible at sim fidelity). Co-located prefill
        pays NO separate dispatch base, so shrinking/growing the quantum
        visibly moves the simulated ITL the ColocController measures.

        Spec verify spans (``draft_lens``): a lane of 1 + dl tokens
        stays a DECODE lane (its per-lane KV-read term covers the whole
        context) and its dl draft rows price as prefill tokens riding
        the dispatch — the verify-width term: cost scales linearly
        with verify width; the shared weight pass is paid once
        (which is the point of the port). Acceptance is deterministic
        against the closed-form chain, so the emitted stream follows the
        PR 13 failover byte-identity form across accepted AND rejected
        drafts; RNG mode accepts nothing (the losing regime the
        auto-gate must detect)."""
        dls = list(draft_lens) if draft_lens else [0] * len(lanes)
        dls += [0] * (len(lanes) - len(dls))
        total = sum(len(t) for t, _, _, _ in lanes)
        drafted = sum(dls)
        decode_lanes = sum(
            1 for (t, _, _, _), dl in zip(lanes, dls) if len(t) - dl == 1
        )
        prefill_tokens = total - decode_lanes - drafted
        # Decode lanes stream their whole context from HBM each step
        # (prefix + the new token) — the bytes the HBM term prices.
        decode_ctx = sum(
            prefix + len(t)
            for (t, _, prefix, _), dl in zip(lanes, dls)
            if len(t) - dl == 1
        )
        use_mm = mm is not None and any(seg for seg in mm)
        use_full = use_mm or extras is not None
        if use_full:
            kind = "unified_mm" if use_mm else "unified_full"
            T = token_budget(
                self.cfg.unified_token_budget, self.cfg.unified_token_budget
            )
        else:
            kind = "unified"
            T = token_budget(total, self.cfg.unified_token_budget)
        with self.compile_stats.observe(kind, t=T):
            time.sleep(
                (
                    self._weight_pass_us(self.sim.decode_time_per_step_us)
                    + self.sim.decode_time_per_lane_us * decode_lanes
                    + self._kv_read_us(decode_ctx)
                    + self._prefill_cost_us(prefill_tokens + drafted)
                )
                / 1e6
            )
        S = self.unified_slots
        K = max(1, self.cfg.speculative_k)
        last = np.zeros(S, np.int32)
        toks2d = np.zeros((S, K + 1), np.int32)
        counts = np.zeros(S, np.int32)
        if feed is not None:
            # Sim "device" arrays are host numpy — the feed substitution
            # reads the previous return directly.
            prev_toks, prev_row, use_prev = feed
            prev_toks = np.asarray(prev_toks)
        for i, (toks, _blocks, prefix, _samp) in enumerate(lanes):
            dl = dls[i]
            if not toks:
                continue
            fed_last = toks[-1 - dl] if dl else toks[-1]
            if feed is not None and bool(use_prev[i]):
                # The device feed substitutes the span's FIRST row; for
                # the 1-token spans that use it, that IS the fed token —
                # so the deterministic chain stays host-visible through
                # pipelined dispatches (unlike the phased-era caveat).
                fed_last = int(prev_toks[int(prev_row[i])])
            if not self.sim.deterministic_tokens:
                last[i] = int(self._rng.integers(0, self.sim.vocab_size))
                toks2d[i, 0] = last[i]
                counts[i] = 1
                continue
            # Closed-form chain: verify drafts against it, deliver the
            # accepted prefix + the bonus — the emitted tokens ARE the
            # chain whatever the drafts were.
            base_pos = prefix + len(toks) - dl  # index of the next token
            acc = 0
            prev = fed_last
            if dl:
                drafts = list(toks[-dl:])
                for j in range(dl):
                    want = int(self._det_next(prev, base_pos + j))
                    if drafts[j] != want:
                        break
                    acc += 1
                    prev = want
            delivered = []
            prev = fed_last
            for j in range(acc + 1):
                prev = int(self._det_next(prev, base_pos + j))
                delivered.append(prev)
            counts[i] = len(delivered)
            toks2d[i, : len(delivered)] = delivered
            last[i] = delivered[-1]
        if use_full:
            KL = 8  # MAX_LOGPROBS-shaped fake alternatives
            clp = np.full(S, -0.5, np.float32)
            tids = np.tile(last[:, None], (1, KL)).astype(np.int32)
            tlps = np.full((S, KL), -0.5, np.float32)
            self.last_unified_logprobs = (clp, tids, tlps)
            return UnifiedOut(last=last, toks=None, counts=None)
        if self.cfg.speculative_k > 0:
            return UnifiedOut(last=last, toks=toks2d, counts=counts)
        return UnifiedOut(last=last, toks=None, counts=None)


class MockerEngine(TpuEngine):
    """TpuEngine with a simulated runner — the router/KVBM testbed."""

    def __init__(self, cfg: EngineConfig, sim: MockerConfig | None = None,
                 **kwargs) -> None:
        super().__init__(cfg, **kwargs)
        self._sim = sim or MockerConfig()

    def _build_runner(self) -> None:
        self.runner = _SimRunner(self.cfg, self._sim)
