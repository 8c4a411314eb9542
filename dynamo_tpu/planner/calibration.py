"""Mocker cost-model calibration against the recorded r04/r05 runs (an
older engine on an older harness — not reproduced on today's chip).

The mocker (mocker/engine.py) prices a dispatch as
``f(decode_lanes, prefill_tokens)`` but its default constants are
arbitrary. This module pins them to the RECORDED chip runs so the fleet
simulator's xPyD projections (planner/simulate.py) stand
on measured ground:

- **decode dispatch**: r04's device microbench measured
  ``decode_step_ms`` 11.59 at 64 lanes and 11.13 at 32 lanes
  (the r04 recording, `_RECORDED_R04` below). Two points, one line:
  per-lane = (11590 − 11130) / 32 ≈ 14.4 µs, base =
  11130 − 32·14.4 ≈ 10670 µs (the per-step weight pass). r05 measured
  the same slope (12.51/11.68 ms) within 8% — the constant is stable
  across runs.
- **prefill + host overhead**: fitted so the calibrated single-worker
  simulation of the r04 headline workload (64 requests, ISL 128,
  OSL 64, all-at-once) reproduces the recorded aggregated throughput
  (1746.1 tok/s) and p50 TTFT (662.4 ms) — the <10 % gate
  tests/test_xpyd.py enforces so future mocker edits can't silently
  drift the projections. ``HOST_OVERHEAD_US`` is the per-dispatch
  host-side cost the device-side step time doesn't see (the gap
  between r04's 11.59 ms device step and its engine-side elapsed).
- **handoff transfer**: the measured batched device channel: 21.7 GB/s, 2 dispatches per
  handoff at ~456 µs each (2193 per-block dispatches/s measured).

Derived, not tuned: change these only against a NEW recorded run.
"""

from __future__ import annotations

# -- decode dispatch (r04 device microbench, see module docstring) ----------
DECODE_TIME_PER_STEP_US = 10670.0
DECODE_TIME_PER_LANE_US = 14.4

# -- decode HBM bandwidth (r04 device microbench: effective_hbm_gbps in
#    the r04 recording — total streamed bytes / measured decode step
#    time at B=64). The mocker's decode HBM-bytes term
#    (MockerConfig.decode_hbm_gbps) prices KV reads against this, so the
#    BENCH_QUANT A/B's bf16 baseline stands on the measured chip number;
#    tests re-derive it from the artifact (recorded_r04) so the constant
#    and the recording can't drift apart. -------------------------------
DECODE_HBM_GBPS = 282.8

# -- weight pass (derived from the decode dispatch base) --------------------
# The decode dispatch base IS the per-step weight pass (module docstring:
# base = 11130 − 32·14.4 ≈ 10670 µs), so at the measured effective HBM
# rate it streams base·rate bytes per dispatch. Publishing the BYTES
# (not the time) lets the mocker reprice the pass by weight precision:
# int8 weights stream ~half the bytes, so the base shrinks by the same
# ratio the KV term already applies to context reads.
WEIGHT_BYTES_PER_STEP = DECODE_TIME_PER_STEP_US * 1e-6 * DECODE_HBM_GBPS * 1e9

# -- prefill (fitted to the r04 headline; test-gated to <10%) ---------------
PREFILL_TIME_PER_TOKEN_US = 119.8
PREFILL_QUADRATIC_US = 0.0005
# Standalone prefill pays its own weight pass — same streaming bytes as
# the decode dispatch base (what co-located quanta share instead). NOT a
# second fitted constant: derived from the weight-bytes term at the
# measured rate (numerically the decode base, 10670 µs), so repricing
# the weight pass by precision moves standalone prefill and the decode
# base together instead of leaving prefill at a stale flat copy.
PREFILL_DISPATCH_BASE_US = WEIGHT_BYTES_PER_STEP / (DECODE_HBM_GBPS * 1e9) * 1e6

# -- per-dispatch host overhead (fitted; simulator-only, the real engine
#    pays its real scheduler) ----------------------------------------------
HOST_OVERHEAD_US = 8900.0

# -- KV handoff (measured r05-late batched BlockBatch channel) --------------
# THE single source for the fleet's default link-rate fallback: the
# router's NetKV term (kv_router/scheduler.py KvRouterConfig.
# default_link_gbps) and the G4 peer tier's pricing fallback
# (block_manager/peer.py) both import this symbol, and
# tests/test_calibration.py drift-gates that neither carries its own
# copy — a re-fit here repriced every consumer at once.
HANDOFF_GBPS = 21.7
HANDOFF_FIXED_US = 912.0          # 2 dispatches/handoff × ~456 µs
# llama3.2-1b KV bytes/token: 2 (K,V) × 16 layers × 8 kv-heads ×
# 64 head-dim × 2 B (bf16) — the model every recorded run served.
KV_BYTES_PER_TOKEN = 32768


def kv_quant_bytes_ratio(
    block_size: int = 16,
    num_layers: int = 16,
    num_kv_heads: int = 8,
    head_dim: int = 64,
    dtype_bytes: int = 2,
) -> float:
    """Stored-KV bytes ratio of an int8 block (data + f32 per-(layer,
    K/V, head) scale sidecar) vs the bf16 layout — the precision-aware
    factor for the mocker's HBM term and the xPyD simulator's
    32 KiB/token handoff constant (defaults: the 1B layout every
    recorded run served; ~0.502)."""
    data = num_layers * 2 * block_size * num_kv_heads * head_dim
    scales = num_layers * 2 * num_kv_heads * 4
    return (data + scales) / (data * dtype_bytes)


def kv_bytes_per_token(quant: str | None = None) -> float:
    """Handoff/HBM bytes per token for the calibrated 1B layout at the
    given KV precision (None = bf16 baseline)."""
    if quant == "int8":
        return KV_BYTES_PER_TOKEN * kv_quant_bytes_ratio()
    return float(KV_BYTES_PER_TOKEN)


def weight_quant_bytes_ratio(
    in_dim: int = 2048,
    dtype_bytes: int = 2,
) -> float:
    """Resident/streamed bytes ratio of an int8 weight matrix (int8 data
    + one f32 scale per output channel, ops/quant.py ``quantize_weight``)
    vs the bf16 layout: ``(in·1 + 4) / (in·2)`` per output column.
    Defaults: the 1B model's 2048 hidden dim (~0.501 — the scale row
    amortizes over the contraction axis, like the KV block scales)."""
    return (in_dim * 1 + 4) / (in_dim * dtype_bytes)


def weight_bytes_per_step(weight_quant: str | None = None) -> float:
    """Weight bytes one dispatch streams at the given weight precision
    (None = bf16 baseline = the full recorded pass). A non-None policy
    is priced at the full-int8 ratio — partial per-matmul policies
    should pass their blended ratio to MockerConfig.weight_bytes_ratio
    directly instead."""
    if weight_quant:
        return WEIGHT_BYTES_PER_STEP * weight_quant_bytes_ratio()
    return WEIGHT_BYTES_PER_STEP

# -- recorded r04 headline (the calibration target, `_RECORDED_R04`) ----
R04_HEADLINE_TOK_S = 1746.1
R04_P50_TTFT_MS = 662.4
R04_NUM_REQUESTS = 64
R04_ISL = 128
R04_OSL = 64


def calibrated_mocker_config(**overrides):
    """A MockerConfig priced by the measured constants (the per-phase
    cost model the fleet simulator replays; also usable for live
    mocker-engine runs that should approximate chip pacing)."""
    # Deferred import keeps this module a LEAF: the router scheduler
    # imports HANDOFF_GBPS at class-definition time, and pulling the
    # mocker (→ engine → jax) in transitively would make every router
    # import pay the accelerator stack.
    from dynamo_tpu.mocker.engine import MockerConfig

    kw = dict(
        prefill_time_per_token_us=PREFILL_TIME_PER_TOKEN_US,
        prefill_quadratic_us=PREFILL_QUADRATIC_US,
        decode_time_per_step_us=DECODE_TIME_PER_STEP_US,
        decode_time_per_lane_us=DECODE_TIME_PER_LANE_US,
        prefill_dispatch_base_us=PREFILL_DISPATCH_BASE_US,
        # Bytes-priced weight pass: inert until a scenario also arms
        # decode_hbm_gbps (bytes/rate then round-trips to the flat
        # base, so every calibrated projection is unchanged at bf16).
        weight_bytes_per_step=WEIGHT_BYTES_PER_STEP,
    )
    kw.update(overrides)
    return MockerConfig(**kw)


def handoff_seconds(
    isl_tokens: int,
    link_gbps: float = HANDOFF_GBPS,
    kv_quant: str | None = None,
) -> float:
    """Prefill→decode KV handoff time for one prompt over a link of
    ``link_gbps`` (the NetKV transfer term, priced like the measured
    device channel: fixed 2-dispatch cost + bytes/rate). ``kv_quant``
    makes the byte term precision-aware: an int8 fleet moves ~half the
    bytes per token (docs/architecture/kv_quant.md)."""
    bytes_ = isl_tokens * kv_bytes_per_token(kv_quant)
    return HANDOFF_FIXED_US / 1e6 + bytes_ / (link_gbps * 1e9)


#: The eight numbers the calibration was fitted to, kept as a literal: the
#: recording itself (an older phase-alternating engine, measured through
#: an older harness — NOT reproduced on the chip builders have now) is no
#: longer in the tree. Whether the calibration is re-fitted from a device
#: trace or retired is ROADMAP Speed #4.
_RECORDED_R04 = {
    "tok_s": 1746.1,
    "p50_ttft_ms": 662.4,
    "num_requests": 64,
    "isl": 128,
    "osl": 64,
    "decode_step_ms": 11.59,
    "decode_step_ms_b32": 11.13,
    "effective_hbm_gbps": 282.8,
}


def recorded_r04() -> dict:
    """The recorded r04 headline the constants above derive from (tests
    re-derive them from these numbers so the two can't drift apart)."""
    return dict(_RECORDED_R04)
